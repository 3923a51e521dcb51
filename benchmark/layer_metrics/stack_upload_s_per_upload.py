"""Seconds of the upload call per row stack uploaded.

Layer: planner residency. Source: the wall seconds of the span
``stack.upload`` (layout, the transfer call and the cache insertion with
its evictions, host side), ``span.stack.upload.wallSeconds`` of
``/debug/vars`` over the window, over ``uploads`` of ``/debug/device``.
"""

import span_counters as sc


def read(ctx):
    return sc.s_per_upload(ctx, sc.delta(ctx, "stack.upload", "wallSeconds"))
