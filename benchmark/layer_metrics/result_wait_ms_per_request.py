"""Milliseconds a request thread waits for its result, per answered request.

Layer: dispatch (transfer wave). Source: the wall seconds of the span
``transfer.wait`` (the request's thread blocked on the dispatch future:
device run, device->host copy, the resolver's hand-off and the wait to
be scheduled again), ``span.transfer.wait.wallSeconds`` of
``/debug/vars`` over the window.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(
        ctx, sc.delta(ctx, "transfer.wait", "wallSeconds"))
