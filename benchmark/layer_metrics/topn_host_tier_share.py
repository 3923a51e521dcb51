"""Share of the rows a ``TopN`` sweep counted on the host, %.

Layer: planner (TopN sweep) (``core/fragment.py:intersection_counts_async``:
a row a fragment holds as positions is counted by membership in the
filter's host copy, a row it holds dense by ``pair_count`` on the device).
Source: the program's counters ``planner.topn.rowsHostTier`` and
``planner.topn.rowsDeviceTier`` (rows counted by tier, summed over the
fragments) of ``/debug/vars`` over the window: d(host) / d(both). None
where the program has no such counters (an older commit) or no sweep
counted a row.
"""

import call_counters as cc


def read(ctx):
    host = cc.counter(ctx, "planner.topn.rowsHostTier")
    device = cc.counter(ctx, "planner.topn.rowsDeviceTier")
    if host is None or device is None or host + device <= 0:
        return None
    return 100.0 * host / (host + device)
