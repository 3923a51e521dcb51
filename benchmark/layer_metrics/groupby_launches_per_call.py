"""Device launches of the lattice, per ``GroupBy``.

Layer: planner (GroupBy lattice) (``parallel/planner.py:execute_group_by``:
one AND launch for each pair of rows below the first level and one count
launch for each group). Source: the program's counter
``planner.groupby.launches`` over
d(``span.Executor.executeGroupBy.count``), both of ``/debug/vars`` over
the window. None where the program has no such counter (an older commit)
or the window made no ``GroupBy``.
"""

import call_counters as cc


def read(ctx):
    return cc.per_call(ctx, "GroupBy", "planner.groupby.launches")
