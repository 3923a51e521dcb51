"""Device launches of the sweep, per ``TopN``.

Layer: planner (TopN sweep) (``parallel/planner.py:execute_topn_counts``,
``core/fragment.py:intersection_counts_async``: one ``pair_count`` launch
for each dense part of each fragment, in each of the call's two passes).
Source: the program's counter ``planner.topn.launches`` over
d(``span.Executor.executeTopN.count``), both of ``/debug/vars`` over the
window. None where the program has no such counter (an older commit) or
the window made no ``TopN``.
"""

import call_counters as cc


def read(ctx):
    return cc.per_call(ctx, "TopN", "planner.topn.launches")
