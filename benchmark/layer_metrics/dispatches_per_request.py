"""Launches of query programs per answered request, over the window.

Layer: dispatch (``parallel/coalesce.py``, ``parallel/batcher.py``).
Source: the program's counter ``planner.dispatchCount`` (``/debug/vars``),
read before and after the window. 1.0 means one launch for each request;
below 1, the coalescer served several requests with one launch.
"""


def read(ctx):
    if not ctx["answered"]:
        return None
    delta = ctx["counters1"].get("planner.dispatchCount", 0) \
        - ctx["counters0"].get("planner.dispatchCount", 0)
    return delta / ctx["answered"]
