"""Thread-CPU milliseconds of the HTTP layer itself, per answered request.

Layer: HTTP (``server/httpd.py`` on the stdlib's handler). Source: the
self thread CPU of the spans ``http.request`` (header parse, routing,
parameters, the route function outside its child spans) and ``http.reply``
(JSON encoding, the socket write), ``span.<name>.selfCpuSeconds`` of
``/debug/vars`` over the window.
"""

import span_counters as sc


def read(ctx):
    return sc.ms_per_request(ctx, sc.total(
        sc.delta(ctx, "http.request", "selfCpuSeconds"),
        sc.delta(ctx, "http.reply", "selfCpuSeconds")))
