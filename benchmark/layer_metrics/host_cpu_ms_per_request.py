"""Thread-CPU milliseconds a request thread spends on one answered request.

Layer: HTTP + admission, parser, executor. Source: the program's counter
``span.http.request.cpuSeconds`` (``/debug/vars``) over the window: the
inclusive thread CPU of the ``http.request`` span, from the request line
having been read to the response written, children included. Near 1,000 /
``qps`` the interpreter is saturated and ``qps`` moves with this number.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(ctx, sc.delta(ctx, "http.request", "cpuSeconds"))
