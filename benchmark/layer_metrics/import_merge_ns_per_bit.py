"""Wall nanoseconds of an import's merge into the fragment a bit loaded.

Layer: ingest (``core/fragment.py:Fragment.bulk_import`` and the other
bulk paths, from entry to the hand-over to the op writer: the lists made
arrays again, the fragment's lock, the sort, ``HostRow.add_many``,
``_invalidate``). Source: ``span.import.merge.wallSeconds`` /
``import.bits`` of ``/debug/vars``, totals from boot at the window's
start (``import_counters.py``).
"""

import import_counters as ic


def read(ctx):
    return ic.ns_per_bit(ctx, "import.merge")
