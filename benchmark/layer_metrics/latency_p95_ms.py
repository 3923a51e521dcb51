"""95th percentile of the client-side latency of the traced run's window.

Layer: planner residency. For cells whose working set exceeds the device
budget: the closed loop saturates the stack builder there, so the tail is
the wait in its queue and swings by 15-17 % between runs of identical
requests (PERF.md). It stands here, without a bound, and `qps` carries the
bound. Source: the host's clock, every answered request (nearest rank).
"""

import math


def read(ctx):
    lat = sorted(ctx["latencies_ms"])
    if not lat:
        return None
    return lat[min(len(lat) - 1, max(0, math.ceil(0.95 * len(lat)) - 1))]
