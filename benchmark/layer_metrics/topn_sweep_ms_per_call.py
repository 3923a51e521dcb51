"""Wall milliseconds a ``TopN`` spends in the sweep over the fragments.

Layer: planner (TopN sweep) (``parallel/planner.py:execute_topn_counts``:
for every shard the fragment's host tier against the filter's host copy
and one launch a dense part, to the last launch, both passes of the
call). Source: the span ``topn.sweep``, d(``span.topn.sweep.wallSeconds``)
/ d(``span.Executor.executeTopN.count``) of ``/debug/vars`` over the
window. None where the program has no such span (an older commit) or the
window made no ``TopN``.
"""

import call_counters as cc


def read(ctx):
    return cc.ms_per_call(ctx, "TopN", span="topn.sweep")
