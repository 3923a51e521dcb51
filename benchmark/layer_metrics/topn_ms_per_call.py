"""Wall milliseconds of one ``TopN``, inside the executor.

Layer: executor (TopN, GroupBy) (``exec/executor.py:_execute_top_n``: both
passes, each the filter's program and its pull to the host, the sweep over
the fragments and the transfer wave). Source: the span
``Executor.executeTopN``, d(``.wallSeconds``) / d(``.count``) of
``/debug/vars`` over the window. This is the ranked query's latency where
``p50_ms`` would mix it with calls a thousand times shorter. None where
the window made no such call.
"""

import call_counters as cc


def read(ctx):
    return cc.ms_per_call(ctx, "TopN")
