"""Calls that left the planner for the per-shard interpreter, per
answered request.

Layer: executor (TopN, GroupBy) (``exec/executor.py``: ``_topn_batch_fn``
returned None; ``execute_group_by`` returned None or was not tried).
Source: the program's counters ``executor.fallback.topn`` and
``executor.fallback.groupby`` of ``/debug/vars`` over the window (the
program publishes both at 0). Expected 0: what the cell timed was the
planner's path. None where the program has no such counters (an older
commit).
"""

import call_counters as cc


def read(ctx):
    topn = cc.counter(ctx, "executor.fallback.topn")
    groupby = cc.counter(ctx, "executor.fallback.groupby")
    if topn is None or groupby is None or not ctx["answered"]:
        return None
    return (topn + groupby) / ctx["answered"]
