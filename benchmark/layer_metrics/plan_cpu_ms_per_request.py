"""Thread-CPU milliseconds of parsing and planning, per answered request.

Layer: parser + planner (``pql/``, ``exec/executor.py``,
``parallel/planner.py``). Source: the self thread CPU of the spans
``exec.parse``, every ``Executor.execute<Call>`` (translate, the
map/reduce spine, the reduce), ``plan.prepare`` (plan cache, signature
walk, compiled-program lookup) and ``stack.fetch`` (the leaf lookups),
``span.<name>.selfCpuSeconds`` of ``/debug/vars`` over the window.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(ctx, sc.total(
        sc.delta(ctx, "exec.parse", "selfCpuSeconds"),
        sc.delta_prefix(ctx, "Executor.execute", "selfCpuSeconds"),
        sc.delta(ctx, "plan.prepare", "selfCpuSeconds"),
        sc.delta(ctx, "stack.fetch", "selfCpuSeconds")))
