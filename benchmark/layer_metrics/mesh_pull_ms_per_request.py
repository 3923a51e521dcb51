"""Milliseconds of the results' way back from the devices, per request.

Layer: dispatch (transfer wave) (``parallel/batcher.py``: the resolver
thread's device->host copy of a result, which on the mesh lies a part on
each chip). Source: the wall seconds of the span ``transfer.pull``,
``span.transfer.pull.wallSeconds`` of ``/debug/vars`` over the window,
over the answered requests. A wave its waiter steals is pulled inside
that request's ``transfer.wait`` and is not in this number. None on a
program without the span.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(
        ctx, sc.delta(ctx, "transfer.pull", "wallSeconds"))
