"""Wall nanoseconds of an import's decode a bit loaded.

Layer: ingest (``core/fragment.py:Fragment.import_roaring`` to its call
of ``bulk_import``: the native roaring decode, the row and column split,
both turned into lists). Source: ``span.import.decode.wallSeconds`` /
``import.bits`` of ``/debug/vars``, totals from boot at the window's
start (``import_counters.py``).
"""

import import_counters as ic


def read(ctx):
    return ic.ns_per_bit(ctx, "import.decode")
