"""Programs compiled (compile-cache misses) inside the window: expected 0.

Layer: compile (``parallel/compile_cache.py``). Source: ``compileCache``
of ``/debug/device``, requests less hits, over the window.
"""


def read(ctx):
    def misses(dev):
        cc = dev["compileCache"]
        return int(cc["requests"]) - int(cc["hits"])

    return float(misses(ctx["device1"]) - misses(ctx["device0"]))
