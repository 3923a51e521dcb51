#!/usr/bin/env python
"""CI cold-start check: a restarted node must reuse its compiled
kernels from the persistent compile cache and replay its observed
traffic shapes through warmup.

Boots a real server twice over the same data dir:

  boot 1: warmup runs, every compiled program is persisted under
          the --compile-cache-dir given, a query is served (so its shape is
          recorded in warmup.json at graceful shutdown).
  boot 2: warmup replays, and the planner's re-traced kernels must
          load from disk — asserted via the compileCache.hits counter
          on /debug/vars, never via wall-clock thresholds (CI runners
          have none to give).

Exit 0 on success, 1 with a diagnostic on any failed assertion.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

TIMEOUT_BOOT_S = 120
TIMEOUT_WARMUP_S = 180


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Node:
    def __init__(self, port: int, data_dir: str, cache_dir: str):
        self.base = f"http://127.0.0.1:{port}"
        # The check owns its cache directory: passed explicitly, and a
        # caller's JAX_COMPILATION_CACHE_DIR (which would override it)
        # is kept out of the child.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--bind", f"127.0.0.1:{port}", "--data-dir", data_dir,
             "--compile-cache-dir", cache_dir],
            env=env)

    def get(self, path: str) -> dict:
        data = urllib.request.urlopen(self.base + path, timeout=10).read()
        return json.loads(data or b"{}")

    def post(self, path: str, body: str = "") -> dict:
        r = urllib.request.Request(self.base + path, data=body.encode(),
                                   method="POST")
        data = urllib.request.urlopen(r, timeout=60).read()
        return json.loads(data or b"{}")

    def wait_up(self) -> None:
        deadline = time.monotonic() + TIMEOUT_BOOT_S
        while time.monotonic() < deadline:
            try:
                self.get("/status")
                return
            except Exception:
                if self.proc.poll() is not None:
                    raise SystemExit(
                        f"FAIL: server exited rc={self.proc.returncode} "
                        "during boot")
                time.sleep(0.25)
        raise SystemExit("FAIL: server did not come up")

    def wait_warmup(self) -> dict:
        deadline = time.monotonic() + TIMEOUT_WARMUP_S
        while time.monotonic() < deadline:
            counters = self.get("/debug/vars").get("counters", {})
            if counters.get("qos.warmupRuns", 0) >= 1:
                return counters
            time.sleep(0.25)
        raise SystemExit("FAIL: warmup never finished "
                         f"(counters={self.get('/debug/vars').get('counters')})")

    def stop(self) -> None:
        # SIGTERM = graceful close: flushes schema.json and warmup.json
        # (the observed-traffic shapes boot 2's warmup replays).
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        self.proc.wait(timeout=30)


def check(cond: bool, msg: str, ctx) -> None:
    if not cond:
        raise SystemExit(f"FAIL: {msg}: {ctx}")
    print(f"ok: {msg}")


def main() -> None:
    port = free_port()
    data_dir = tempfile.mkdtemp(prefix="pilosa-coldstart-")
    cache_dir = os.path.join(data_dir, "compile-cache")

    # ---- boot 1: compile, persist, observe traffic ----
    node = Node(port, data_dir, cache_dir)
    try:
        node.wait_up()
        counters = node.wait_warmup()
        node.post("/index/ci")
        node.post("/index/ci/field/f")
        node.post("/index/ci/field/f/import", json.dumps(
            {"rowIDs": [1] * 64, "columnIDs": list(range(0, 6400, 100))}))
        res = node.post("/index/ci/query", "Count(Row(f=1))")
        check(res["results"][0] == 64, "boot 1 served the query", res)
        counters = node.get("/debug/vars").get("counters", {})
        check(counters.get("compileCache.requests", 0) > 0,
              "boot 1 consulted the persistent compile cache", counters)
    finally:
        node.stop()

    check(os.path.isdir(cache_dir) and len(os.listdir(cache_dir)) > 0,
          "boot 1 persisted compiled programs", cache_dir)
    check(os.path.exists(os.path.join(data_dir, "warmup.json")),
          "boot 1 saved observed traffic for replay", data_dir)

    # ---- boot 2: same data dir; kernels must come from disk ----
    node = Node(port, data_dir, cache_dir)
    try:
        node.wait_up()
        counters = node.wait_warmup()
        check(counters.get("compileCache.hits", 0) > 0,
              "boot 2 loaded compiled kernels from the persistent cache",
              counters)
        check(counters.get("qos.warmupReplayed", 0) >= 1,
              "boot 2 warmup replayed boot 1's observed query shapes",
              counters)
        res = node.post("/index/ci/query", "Count(Row(f=1))")
        check(res["results"][0] == 64, "boot 2 served the query", res)
    finally:
        node.stop()

    print("cold-start check passed")


if __name__ == "__main__":
    main()
