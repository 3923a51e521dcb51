"""Milliseconds a request thread was runnable and did not run, per request.

Layer: host (interpreter lock). Source: ``span.*`` counters of
``/debug/vars`` over the window: the wall seconds of ``http.request`` less
its three named waits (``qos.admit``, ``stack.wait``, ``transfer.wait``,
each 0 where the window never entered it) less its thread CPU. An upper
reading: a wake-up inside the three waits is not in it, a blocking socket
write is.
"""

import span_counters as sc


def read(ctx):
    wall = sc.delta(ctx, "http.request", "wallSeconds")
    cpu = sc.delta(ctx, "http.request", "cpuSeconds")
    if wall is None or cpu is None:
        return None
    waits = sum(sc.delta(ctx, name, "wallSeconds", 0.0)
                for name in ("qos.admit", "stack.wait", "transfer.wait"))
    return sc.ms_per_request(ctx, wall - waits - cpu)
