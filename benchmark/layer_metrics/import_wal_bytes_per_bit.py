"""Bytes the WAL wrote a bit loaded.

Layer: ingest (``storage/wal.py:WalWriter.append``: a 15-byte header and
a row id and a column id of 8 bytes each a bit). Source: the counter
``wal.bytes`` / ``import.bits`` of ``/debug/vars``, totals from boot at
the window's start (``import_counters.py``). This reading x the bits
loaded is the disk a load writes before any snapshot.
"""

import import_counters as ic


def read(ctx):
    return ic.per_bit(ctx, "wal.bytes")
