"""Thread-CPU nanoseconds of the import-roaring route a bit loaded.

Layer: ingest. Source: ``span.import.roaring.cpuSeconds`` /
``import.bits`` of ``/debug/vars``, totals from boot at the window's
start (``import_counters.py``); an estimate from one request in
``CPU_SAMPLE_EVERY``, as every CPU counter of the spans. This reading x
``import_mbits`` / 1,000 is the share of one interpreter the route used:
near 1, the interpreter sets the load's pace.
"""

import import_counters as ic


def read(ctx):
    return ic.ns_per_bit(ctx, "import.roaring", "cpuSeconds")
