"""Milliseconds a request waits for an admission slot.

Layer: admission (``qos/admission.py``). Source: the wall seconds of the
span ``qos.admit``, ``span.qos.admit.wallSeconds`` of ``/debug/vars`` over
the window. About 0 while the clients are fewer than the slots.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(ctx, sc.delta(ctx, "qos.admit", "wallSeconds"))
