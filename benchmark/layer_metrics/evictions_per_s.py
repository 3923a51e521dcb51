"""Row stacks evicted from the device per second of the window.

Layer: planner residency. Source: ``evictions`` of ``/debug/device``,
over the seconds between its two readings.
"""


def read(ctx):
    if ctx["counters_s"] <= 0:
        return None
    return (ctx["device1"]["evictions"] - ctx["device0"]["evictions"]) \
        / ctx["counters_s"]
