"""Milliseconds residency costs a request: waits and synchronous builds.

Layer: planner residency. Source: the wall seconds of the span
``stack.fetch`` (the prefetch scheduling and the leaf fetches of a plan:
about 0 when every stack is resident, the wait for an upload in flight or
a synchronous build otherwise), ``span.stack.fetch.wallSeconds`` of
``/debug/vars`` over the window.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(ctx, sc.delta(ctx, "stack.fetch", "wallSeconds"))
