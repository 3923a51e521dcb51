"""The device's idle share of the traced span, %.

Layer: device. 1 - (union of the device's operation intervals) / span,
from the profiler's ``.xplane.pb`` (``trace_reduce.py``).
"""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    return 100.0 * trace["idle_share"]
