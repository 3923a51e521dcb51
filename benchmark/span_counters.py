"""The program's span counters, as differences over the window.

Every span of the served read path (``pilosa_tpu/obs/tracing.py``) folds
its count, inclusive wall seconds, inclusive thread-CPU seconds and self
thread-CPU seconds (its own less its children's) into ``/debug/vars``'s
``counters`` as ``span.<name>.count|wallSeconds|cpuSeconds|selfCpuSeconds``.
The readers under ``layer_metrics/`` take their differences between
``ctx["counters0"]`` and ``ctx["counters1"]`` through these helpers. A
program without the counter (an older commit) reads None, and the
metric is left out of the line.
"""


def delta(ctx, name, field, default=None):
    """d(span.<name>.<field>) over the window; ``default`` where the
    program has no such counter (None: the metric cannot be read; 0 for
    a span that a window may never enter)."""
    key = f"span.{name}.{field}"
    if key not in ctx["counters1"]:
        return default
    return ctx["counters1"][key] - ctx["counters0"].get(key, 0)


def delta_prefix(ctx, prefix, field):
    """The sum of d(span.<name>.<field>) over every span whose name
    starts with ``prefix`` (``Executor.execute`` has one span per call
    name); None where there is none."""
    head, tail = f"span.{prefix}", f".{field}"
    keys = [k for k in ctx["counters1"]
            if k.startswith(head) and k.endswith(tail)]
    if not keys:
        return None
    return sum(ctx["counters1"][k] - ctx["counters0"].get(k, 0)
               for k in keys)


def total(*parts):
    """The sum of the parts; None where any is None."""
    return None if any(p is None for p in parts) else sum(parts)


def ms_per_request(ctx, seconds):
    """Seconds over the window -> milliseconds an answered request."""
    if seconds is None or not ctx["answered"]:
        return None
    return seconds * 1e3 / ctx["answered"]


def s_per_upload(ctx, seconds):
    """Seconds over the window -> seconds a row stack uploaded
    (``uploads`` of ``/debug/device``)."""
    uploads = ctx["device1"]["uploads"] - ctx["device0"]["uploads"]
    if seconds is None or uploads <= 0:
        return None
    return seconds / uploads
