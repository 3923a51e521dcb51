"""Readings per call of one query class, from ``/debug/vars``.

``Executor._execute_call`` opens one span a call, named after the call
(``Executor.executeTopN``, ``...GroupBy``, ``...Count``), so
d(``span.Executor.execute<Call>.count``) is the calls of that class the
window made. The planner's and the executor's plain counters
(``planner.topn.launches`` and the like) are differenced the same way. A
program without the counter (an older commit) reads None, as in
``span_counters.py``, and the metric is left out of the line.
"""

import span_counters as sc


def calls(ctx, call):
    """d(count) of the executor's span of ``call`` (``"TopN"``); None
    where the window made no such call."""
    return sc.delta(ctx, "Executor.execute" + call, "count") or None


def counter(ctx, key):
    """d(key) of a plain counter over the window; None where the program
    has no such counter."""
    if key not in ctx["counters1"]:
        return None
    return ctx["counters1"][key] - ctx["counters0"].get(key, 0)


def ms_per_call(ctx, call, span=None):
    """Wall milliseconds of ``span`` (the call's own span by default) per
    call of ``call``."""
    n = calls(ctx, call)
    seconds = sc.delta(ctx, span or "Executor.execute" + call,
                       "wallSeconds")
    if n is None or seconds is None:
        return None
    return seconds * 1e3 / n


def per_call(ctx, call, key):
    """d(key) of a plain counter per call of ``call``."""
    n, moved = calls(ctx, call), counter(ctx, key)
    if n is None or moved is None:
        return None
    return moved / n
