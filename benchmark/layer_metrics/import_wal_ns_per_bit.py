"""Wall nanoseconds of an import's WAL record a bit loaded.

Layer: ingest (every ``op_writer`` call of ``core/fragment.py``, its
arguments' ``tolist`` included, through ``storage/diskstore.py``'s
closure to ``storage/wal.py:WalWriter.append``: the arrays, ``tobytes``,
CRC32, ``write`` and ``flush``). Source: ``span.wal.append.wallSeconds``
/ ``import.bits`` of ``/debug/vars``, totals from boot at the window's
start (``import_counters.py``).
"""

import import_counters as ic


def read(ctx):
    return ic.ns_per_bit(ctx, "wal.append")
