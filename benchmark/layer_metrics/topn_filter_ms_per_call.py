"""Wall milliseconds a ``TopN`` spends on its filter.

Layer: planner (TopN sweep) (``parallel/planner.py:execute_topn_counts``:
the filter tree compiled over all shards, ``_tree_stack``, and its one
pull to the host for the sweep's host tier; the second pass finds the
host copy). Source: the span ``topn.filter``,
d(``span.topn.filter.wallSeconds``) /
d(``span.Executor.executeTopN.count``) of ``/debug/vars`` over the window.
None where the program has no such span (an older commit) or the window
made no ``TopN``.
"""

import call_counters as cc


def read(ctx):
    return cc.ms_per_call(ctx, "TopN", span="topn.filter")
