"""Wall milliseconds of one ``Count``, inside the executor.

Layer: executor (TopN, GroupBy): the mix's third class, for scale (the
plan, the one fused count program and its transfer wave: the path of the
``count-trees`` cells, here beside calls that take seconds). Source: the
span ``Executor.executeCount``, d(``.wallSeconds``) / d(``.count``) of
``/debug/vars`` over the window. None where the window made no such call.
"""

import call_counters as cc


def read(ctx):
    return cc.ms_per_call(ctx, "Count")
