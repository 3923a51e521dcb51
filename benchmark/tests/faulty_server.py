#!/usr/bin/env python3
"""The server with one fault planted in the timed path, for the tests.

``BENCH_FAULT=answer``: every seventh integer answer is one too high, where
the API produces it. ``BENCH_FAULT=write``: a ``Set`` is acknowledged and
not applied (the state is returned unchanged). Anything else: no fault.
Then the CLI runs as ``python -m pilosa_tpu.cli`` would.
"""

import itertools
import os
import runpy
import sys

sys.path.insert(0, os.getcwd())

from pilosa_tpu.server import api as _api  # noqa: E402

FAULT = os.environ.get("BENCH_FAULT", "")
_orig = _api.API.query
_count = itertools.count(1)


def query(self, index, query, *args, **kwargs):
    if FAULT == "write" and query.lstrip().startswith("Set("):
        return {"results": [True]}
    out = _orig(self, index, query, *args, **kwargs)
    if (FAULT == "answer" and isinstance(out, dict) and out.get("results")
            and type(out["results"][0]) is int and next(_count) % 7 == 0):
        out = dict(out, results=[out["results"][0] + 1])
    return out


_api.API.query = query
runpy.run_module("pilosa_tpu.cli", run_name="__main__", alter_sys=True)
