"""Programs compiled (compile-cache misses) inside the ranked and grouped
window: expected 0 (``qos/warmup.py`` and the cell's warm-up pass have
compiled every TopN, GroupBy and Count program before it opens).

Layer: compile (``parallel/compile_cache.py``). Source: ``compileCache``
of ``/debug/device``, requests less hits, over the window, as
``compiles_in_window`` reads it in the count cells; None where the
program does not publish it.
"""


def read(ctx):
    def misses(dev):
        cc = (dev or {}).get("compileCache")
        if cc is None:
            return None
        return int(cc["requests"]) - int(cc["hits"])

    m0, m1 = misses(ctx.get("device0")), misses(ctx.get("device1"))
    if m0 is None or m1 is None:
        return None
    return float(m1 - m0)
