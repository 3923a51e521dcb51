"""Seconds of host materialisation per row stack uploaded.

Layer: planner residency (``parallel/planner.py:_build_stack``). Source:
the wall seconds of the span ``stack.build`` (the fragment walk that fills
the host arrays, on a prefetch worker or on a request's thread after a
synchronous miss), ``span.stack.build.wallSeconds`` of ``/debug/vars``
over the window, over ``uploads`` of ``/debug/device``.
"""

import span_counters as sc


def read(ctx):
    return sc.s_per_upload(ctx, sc.delta(ctx, "stack.build", "wallSeconds"))
