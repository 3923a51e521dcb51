"""Median client-side latency of the traced run's window.

Layer: planner residency. For cells whose working set exceeds the device
budget: about a quarter of the requests wait for a stack build, the rest
are hits, and the median sits on the edge between the two: 17-520 ms
between runs of identical requests (PERF.md). No bound can hold it; it
stands here for the reader of a trace. Source: the host's clock.
"""

import statistics


def read(ctx):
    return statistics.median(ctx["latencies_ms"]) \
        if ctx["latencies_ms"] else None
