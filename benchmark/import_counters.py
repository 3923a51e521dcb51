"""The load's spans and counters, as totals from boot.

The ``import-roaring`` load ends before the window opens and every
cell's window is read-only, so what the program counted on the ingest
route by the window's start, ``ctx["counters0"]``, is the load's own:
the spans ``import.roaring`` (the route function), ``import.decode``,
``import.merge`` and ``wal.append`` (``pilosa_tpu/core/fragment.py``),
and the counters ``import.bits`` (positions decoded) and ``wal.bytes``
(bytes the WAL records wrote). The readers under ``layer_metrics/``
divide them by ``import.bits``. None where the program has no such
counter (an older commit), where it decoded no bit, or where the window
moved ``import.bits`` (the reading would no longer be the load's).
"""

BITS = "import.bits"


def bits(ctx):
    """Positions the load decoded; None as the module says."""
    n = ctx["counters0"].get(BITS)
    if not n or ctx["counters1"].get(BITS, n) != n:
        return None
    return n


def per_bit(ctx, key, scale=1.0):
    """counters0[key] x ``scale`` / bits; None where either is missing."""
    n = bits(ctx)
    if n is None or key not in ctx["counters0"]:
        return None
    return ctx["counters0"][key] * scale / n


def ns_per_bit(ctx, span, field="wallSeconds"):
    """Nanoseconds of span.<span>.<field> a bit the load decoded."""
    return per_bit(ctx, f"span.{span}.{field}", 1e9)
