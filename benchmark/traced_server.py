#!/usr/bin/env python3
"""The server, with a profiler the benchmark can switch on from outside.

``python benchmark/traced_server.py <trace_dir> -m pilosa_tpu.cli server
--bind ...`` starts a watcher thread and then runs the module (or script)
named after ``<trace_dir>`` as ``python`` itself would, with the remaining
arguments unchanged. Only the process that holds the chip can trace it, so
the watcher lives here: it starts ``jax.profiler`` when ``<trace_dir>/start``
appears, stops it when ``<trace_dir>/stop`` appears, and writes
``<trace_dir>/done`` with the wall-clock bounds of the traced span. No line
of ``pilosa_tpu/`` changes.
"""

from __future__ import annotations

import json
import os
import runpy
import sys
import threading
import time

POLL_S = 0.02


def _wait_for(path: str) -> None:
    while not os.path.exists(path):
        time.sleep(POLL_S)


def watch(trace_dir: str) -> None:
    _wait_for(os.path.join(trace_dir, "start"))
    # Not before: an import here while the server's own imports run in
    # the main thread deadlocks on the import locks. By the window the
    # server has long imported jax.
    import jax

    options = jax.profiler.ProfileOptions()
    # The Python tracer would record every call of the server's host
    # code: a trace of gigabytes and a server slowed to a crawl.
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    starting = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    started = time.time()
    _wait_for(os.path.join(trace_dir, "stop"))
    stopping = time.time()
    jax.profiler.stop_trace()
    stopped = time.time()
    tmp = os.path.join(trace_dir, "done.tmp")
    with open(tmp, "w") as f:
        json.dump({"starting": starting, "started": started,
                   "stopping": stopping,
                   "stopped": stopped}, f)
    os.replace(tmp, os.path.join(trace_dir, "done"))


def main() -> None:
    trace_dir, rest = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, os.getcwd())  # as ``python -m`` has it
    threading.Thread(target=watch, args=(trace_dir,), name="trace-watcher",
                     daemon=True).start()
    if rest[0] == "-m":
        sys.argv = rest[1:]
        runpy.run_module(rest[1], run_name="__main__", alter_sys=True)
    else:
        sys.argv = rest
        runpy.run_path(rest[0], run_name="__main__")


if __name__ == "__main__":
    main()
