"""Wall milliseconds of one ``GroupBy``, inside the executor.

Layer: executor (TopN, GroupBy) (``exec/executor.py:_execute_group_by``:
the candidate rows, the planner's lattice of AND and count launches, the
transfer wave, the merge). Source: the span ``Executor.executeGroupBy``,
d(``.wallSeconds``) / d(``.count``) of ``/debug/vars`` over the window.
None where the window made no such call.
"""

import call_counters as cc


def read(ctx):
    return cc.ms_per_call(ctx, "GroupBy")
