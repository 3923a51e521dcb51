"""Wall nanoseconds of the import-roaring route a bit loaded.

Layer: ingest (``server/httpd.py:post_import_roaring``: validation, the
field lookup, the view and fragment get-or-create with its WAL file,
decode, merge and the WAL record). Source: the span ``import.roaring``,
``span.import.roaring.wallSeconds`` / ``import.bits`` of ``/debug/vars``,
totals from boot at the window's start (``import_counters.py``). The
load's eight client threads give each bit 8,000 / ``import_mbits`` ns of
their time; this reading against that is the share the route held them.
"""

import import_counters as ic


def read(ctx):
    return ic.ns_per_bit(ctx, "import.roaring")
