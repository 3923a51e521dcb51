"""Thread-CPU milliseconds enqueueing device programs, per answered request.

Layer: dispatch (``parallel/coalesce.py``). Source: the thread CPU of the
span ``dispatch.launch`` (around ``fn(*args)``: argument handling, the
executable's enqueue), ``span.dispatch.launch.cpuSeconds`` of
``/debug/vars`` over the window, on the request's thread or the
coalescer's flusher.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(
        ctx, sc.delta(ctx, "dispatch.launch", "cpuSeconds"))
