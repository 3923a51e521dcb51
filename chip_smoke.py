#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that pilosa-tpu serves PQL from the chip.

Starts ONE child, ``python -m pilosa_tpu.cli server`` with the CLI's defaults
(planner on), loads the 1B-column star-trace deployment through the public
bulk route, asks the served query path a few requests over HTTP, and compares
every answer, exactly, with numpy on packed bitsets built directly from the
seeded positions. Around each uncached request it reads ``/debug/vars`` and
requires the planner's dispatch counter to rise, so an answer computed on the
host cannot pass.

This script never imports jax (nor any module of the package that does): a
chip belongs to one process, and that process is the server.

Deployment: the upstream project's reference dataset, "1B+ NYC taxi rides",
one column per ride, at the shapes of BASELINE.json's config 1: shard width
2^20, 954 shards = 1,000,341,504 columns, one node, durable data dir, id
fields. Set fields ``f`` and ``g`` hold 8 rows each (``f=1`` and ``g=2`` at
density 0.05, the rest at 0.01; a density is the share of columns drawn per
shard, with replacement); int field ``v`` (0..1000) holds 1M values.

  python chip_smoke.py              one chip, the whole run
  python chip_smoke.py --chips 4    four chips: load, Counts, TopN, GroupBy
  python chip_smoke.py --rehearse   tiny, CPU backend, for the sandbox

Without ``--rehearse`` a platform other than ``tpu`` is a failure. The last
line of stdout is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": N}}``; on any failure ``"ok": false`` and a non-zero exit.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
INDEX = "i"
ROWS = 8
DENSE = 0.05
SPARSE = 0.01
#: (field, row) pairs loaded at DENSE; every other row loads at SPARSE.
DENSE_ROWS = {("f", 1), ("g", 2)}
LOAD_BUDGET_S = 180.0
#: client threads posting import-roaring requests during the load.
LOAD_THREADS = 8
BOOT_TIMEOUT_S = 300.0
#: SIGTERM to exit: the node's graceful close snapshots every fragment
#: the load touched (the durability it promises), minutes at 1B columns.
STOP_TIMEOUT_S = 600.0
#: data scale: the deployment's, and the sandbox rehearsal's cut of it.
#: setShard is where the Set() phase writes its one column.
REAL = {"shards": 954, "bsiShards": 16, "values": 1_000_000,
        "batch": 100_000, "setShard": 900}
REHEARSAL = {"shards": 4, "bsiShards": 2, "values": 20_000,
             "batch": 10_000, "setShard": 3}


class SmokeFailure(Exception):
    """A check of the run failed; the script exits non-zero."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def need(cond: bool, what: str, ctx=None) -> None:
    if not cond:
        raise SmokeFailure(f"{what}: {ctx!r}" if ctx is not None else what)


# ---------------------------------------------------------------------------
# the seeded dataset and its plain numpy reference
# ---------------------------------------------------------------------------


def shard_positions(seed: int, field: str, shard: int,
                    width: int) -> list[np.ndarray]:
    """Sorted unique in-shard columns of each row of ``field`` in
    ``shard``. A pure function of the seed, so loader threads and the
    Set() phase regenerate the same bits in any order."""
    rng = np.random.default_rng([seed, ord(field), shard])
    out = []
    for row in range(ROWS):
        d = DENSE if (field, row) in DENSE_ROWS else SPARSE
        out.append(np.unique(
            rng.integers(0, width, int(width * d), dtype=np.uint32)))
    return out


class Reference:
    """Packed bitsets (one bit per column, little-endian) per (field,
    row), written straight from the seeded positions: never from the
    holder, the executor or the package's bit kernels."""

    def __init__(self, n_shards: int, width: int):
        self.width = width
        self.bits = {(f, r): np.zeros(n_shards * width // 8, dtype=np.uint8)
                     for f in "fg" for r in range(ROWS)}

    def put(self, field: str, row: int, shard: int, pos: np.ndarray) -> None:
        dense = np.zeros(self.width, dtype=bool)
        dense[pos] = True
        lo = shard * self.width // 8
        self.bits[(field, row)][lo:lo + self.width // 8] = np.packbits(
            dense, bitorder="little")

    def set_bit(self, field: str, row: int, col: int) -> None:
        self.bits[(field, row)][col >> 3] |= np.uint8(1 << (col & 7))

    def row(self, field: str, row: int) -> np.ndarray:
        return self.bits[(field, row)].view(np.uint64)

    @staticmethod
    def count(words: np.ndarray) -> int:
        return int(np.bitwise_count(words).sum(dtype=np.int64))


# ---------------------------------------------------------------------------
# the served node, over HTTP only
# ---------------------------------------------------------------------------


class Server:
    def __init__(self, out_dir: str, data_dir: str, env: dict):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(out_dir, "server.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--bind", f"127.0.0.1:{self.port}", "--data-dir", data_dir],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT)

    def http(self, method: str, path: str, body: bytes | None = None,
             timeout: float = 600.0):
        req = urllib.request.Request(self.base + path, data=body,
                                     method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                raw = resp.read()
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path} -> HTTP {e.code}: "
                f"{e.read()[:500].decode(errors='replace')}") from e
        return json.loads(raw) if raw else {}

    def get(self, path: str):
        return self.http("GET", path)

    def post(self, path: str, body: bytes | str = b""):
        if isinstance(body, str):
            body = body.encode()
        return self.http("POST", path, body)

    def query(self, pql: str, cached: bool):
        suffix = "" if cached else "?noCache=true"
        res = self.post(f"/index/{INDEX}/query{suffix}", pql)
        need("results" in res, f"query {pql} returned no results", res)
        return res["results"][0]

    def wait_up(self) -> None:
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while time.monotonic() < deadline:
            need(self.proc.poll() is None,
                 "server exited during start-up", self.proc.returncode)
            try:
                with urllib.request.urlopen(self.base + "/status",
                                            timeout=2.0):
                    return
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)
        raise SmokeFailure(f"server not up after {BOOT_TIMEOUT_S:.0f}s")

    def counters(self) -> dict:
        return self.get("/debug/vars").get("counters", {})

    def stop(self) -> int:
        """SIGTERM, wait, return the exit code (kill on a hang)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=STOP_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode

    def log_tail(self, n_bytes: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n_bytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<no server log: {e}>"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def load_bitmaps(srv: Server, ref: Reference, seed: int, n_shards: int,
                 width: int) -> int:
    """One import-roaring request per (field, shard) carrying every row
    of that fragment; returns the set bits sent."""
    from pilosa_tpu import roaring

    def one(task) -> int:
        field, shard = task
        per_row = shard_positions(seed, field, shard, width)
        for row, pos in enumerate(per_row):
            ref.put(field, row, shard, pos)
        positions = np.concatenate(
            [pos.astype(np.uint64) + np.uint64(row * width)
             for row, pos in enumerate(per_row)])
        srv.post(f"/index/{INDEX}/field/{field}/import-roaring/{shard}",
                 roaring.encode(positions))
        return len(positions)

    tasks = [(f, s) for s in range(n_shards) for f in "fg"]
    with ThreadPoolExecutor(max_workers=LOAD_THREADS) as pool:
        return sum(pool.map(one, tasks))


def load_values(srv: Server, seed: int, bsi_shards: int, width: int,
                n_values: int, batch: int):
    rng = np.random.default_rng([seed, ord("v")])
    cols = np.sort(rng.choice(bsi_shards * width, n_values, replace=False))
    vals = rng.integers(0, 1001, n_values)
    for lo in range(0, n_values, batch):
        srv.post(f"/index/{INDEX}/field/v/import", json.dumps(
            {"columnIDs": cols[lo:lo + batch].tolist(),
             "values": vals[lo:lo + batch].tolist()}))
    return cols, vals


def wait_warmup(srv: Server) -> dict:
    """Boot warm-up compiles in the background; its failures are only
    logged by the server, so the run requires the error counter at 0."""
    deadline = time.monotonic() + 600.0
    while time.monotonic() < deadline:
        c = srv.counters()
        if c.get("qos.warmupRuns", 0) >= 1:
            need(c.get("qos.warmupErrors", 0) == 0,
                 "boot warm-up had failing queries", c)
            return c
        need(srv.proc.poll() is None, "server exited during warm-up",
             srv.proc.returncode)
        time.sleep(0.5)
    raise SmokeFailure("boot warm-up did not finish in 600s")


def ask(srv: Server, name: str, pql: str, expect, norm=lambda r: r) -> dict:
    """One request: uncached (must dispatch to the device), then twice
    through the result cache; all three answers must equal ``expect``."""
    d0 = srv.counters().get("planner.dispatchCount", 0)
    t0 = time.perf_counter()
    cold = norm(srv.query(pql, cached=False))
    cold_ms = (time.perf_counter() - t0) * 1e3
    delta = srv.counters().get("planner.dispatchCount", 0) - d0
    norm(srv.query(pql, cached=True))  # fills the result cache
    t0 = time.perf_counter()
    warm = norm(srv.query(pql, cached=True))
    warm_ms = (time.perf_counter() - t0) * 1e3
    correct = cold == expect and warm == expect
    line = {"request": name, "pql": pql, "answer": cold, "correct": correct,
            "coldMs": round(cold_ms, 3), "warmMs": round(warm_ms, 3),
            "dispatchDelta": delta}
    if not correct:
        line["expected"] = expect
        line["cachedAnswer"] = warm
    emit(line)
    need(correct, f"{name}: wrong answer", line)
    need(delta > 0, f"{name}: no device dispatch for an uncached request",
         line)
    return line


def norm_pairs(res) -> list:
    return [[int(p["id"]), int(p["count"])] for p in res]


def norm_groups(res) -> list:
    return sorted([[int(fr["rowID"]) for fr in g["group"]] + [int(g["count"])]
                   for g in res])


def device_view(srv: Server) -> dict:
    dev = srv.get("/debug/device")
    need(dev.get("enabled") is True, "/debug/device: planner not enabled",
         dev)
    for key in ("platform", "deviceKind", "deviceCount", "perDeviceBytes"):
        need(key in dev, f"/debug/device lacks {key!r}", sorted(dev))
    return dev


def run(args, srv: Server, device: dict) -> None:
    from pilosa_tpu.config import SHARD_WIDTH as width

    rehearse = args.rehearse
    size = REHEARSAL if rehearse else REAL
    n_shards, bsi_shards = size["shards"], size["bsiShards"]
    n_values, batch = size["values"], size["batch"]
    four = args.chips == 4

    srv.wait_up()
    dev = device_view(srv)
    device.update(platform=dev["platform"], kind=dev["deviceKind"],
                  count=dev["deviceCount"])
    need(rehearse or dev["platform"] == "tpu",
         "the server's planner is not on a TPU (no --rehearse given)",
         device)
    need(dev["deviceCount"] == args.chips,
         f"expected {args.chips} device(s)", device)

    srv.post(f"/index/{INDEX}")
    srv.post(f"/index/{INDEX}/field/f")
    srv.post(f"/index/{INDEX}/field/g")

    # ---- load ----
    ref = Reference(n_shards, width)
    t0 = time.perf_counter()
    n_bits = load_bitmaps(srv, ref, args.seed, n_shards, width)
    load_s = time.perf_counter() - t0
    cuts = []
    if rehearse:
        cuts.append(f"--rehearse: {n_shards} shards, not {REAL['shards']}")
    if not four:
        cuts.append(f"BSI leg: int field v holds {n_values} values on the "
                    f"first {bsi_shards} shards only (JSON is the one "
                    f"value-import route the script may use)")
    emit({"dataset": "star-trace 1B (NYC taxi rides shape)",
          "seed": args.seed, "shards": n_shards,
          "columns": n_shards * width, "shardWidth": width,
          "setFields": {"f": ROWS, "g": ROWS}, "setBits": n_bits,
          "reduced": cuts})
    emit({"phase": "load", "route": "import-roaring", "seconds":
          round(load_s, 3), "bitsPerSecond": round(n_bits / load_s),
          "requests": 2 * n_shards, "threads": LOAD_THREADS})
    need(rehearse or load_s <= LOAD_BUDGET_S,
         f"load took {load_s:.0f}s, over the {LOAD_BUDGET_S:.0f}s budget: "
         f"cut rows (never columns, never f=1/g=2) and say so")

    cols = vals = None
    if not four:
        srv.post(f"/index/{INDEX}/field/v", json.dumps(
            {"options": {"type": "int", "min": 0, "max": 1000}}))
        t0 = time.perf_counter()
        cols, vals = load_values(srv, args.seed, bsi_shards, width,
                                 n_values, batch)
        emit({"phase": "load", "route": "import (JSON values)",
              "seconds": round(time.perf_counter() - t0, 3),
              "values": n_values, "batch": batch})

    warm = wait_warmup(srv)
    emit({"phase": "warmup", "programs": warm.get("qos.warmupPrograms", 0),
          "errors": warm.get("qos.warmupErrors", 0)})

    # ---- requests, each against the numpy reference ----
    f1, g2 = ref.row("f", 1), ref.row("g", 2)
    count_q = "Count(Intersect(Row(f=1), Row(g=2)))"
    base = ref.count(f1 & g2)
    ask(srv, "count-intersect", count_q, base)
    ask(srv, "count-union", "Count(Union(Row(f=1), Row(g=2)))",
        ref.count(f1 | g2))
    ask(srv, "count-difference", "Count(Difference(Row(f=1), Row(g=2)))",
        ref.count(f1 & ~g2))
    ask(srv, "count-xor-tree",
        "Count(Xor(Intersect(Row(f=3), Row(g=4)), Row(f=5)))",
        ref.count((ref.row("f", 3) & ref.row("g", 4)) ^ ref.row("f", 5)))
    top = sorted(((ref.count(ref.row("f", r) & g2), r) for r in range(ROWS)),
                 key=lambda cr: (-cr[0], cr[1]))[:3]
    ask(srv, "topn-filtered", "TopN(f, Row(g=2), n=3)",
        [[r, c] for c, r in top], norm_pairs)
    groups = []
    for a in range(ROWS):
        fa = ref.row("f", a)
        for b in range(ROWS):
            n = ref.count(fa & ref.row("g", b))
            if n:
                groups.append([a, b, n])
    ask(srv, "groupby", "GroupBy(Rows(f), Rows(g))", groups, norm_groups)

    # Every set row is resident now and nothing was evicted yet: the
    # planner must hold at least those stacks, spread over the mesh.
    dev = device_view(srv)
    touched = 2 * ROWS * n_shards * width // 8
    emit({"phase": "residency", "residentBytes": dev["bytes"],
          "touchedBytes": touched, "perDeviceBytes": dev["perDeviceBytes"],
          "evictions": dev["evictions"]})
    need(dev["bytes"] >= touched,
         "resident bytes below the stacks the queries touched", dev["bytes"])
    if four:
        total = sum(dev["perDeviceBytes"].values())
        shares = {d: b / total for d, b in dev["perDeviceBytes"].items()}
        need(len(shares) == 4 and all(0.15 <= s <= 0.35
                                      for s in shares.values()),
             "resident bytes are not spread evenly over 4 devices", shares)
        return

    over = vals > 500
    ask(srv, "bsi-sum", "Sum(Row(v > 500), field=v)",
        {"value": int(vals[over].sum()), "count": int(over.sum())})
    ask(srv, "bsi-range-count", "Count(Row(v >= 250))",
        int((vals >= 250).sum()))

    # ---- an acknowledged write is read back, uncached and cached ----
    set_shard = size["setShard"]
    f1_pos = shard_positions(args.seed, "f", set_shard, width)[1]
    g2_pos = shard_positions(args.seed, "g", set_shard, width)[2]
    col = set_shard * width + int(np.setdiff1d(g2_pos, f1_pos)[0])
    ack = srv.query(f"Set({col}, f=1)", cached=False)
    need(ack is True, "Set() was not acknowledged as a change", ack)
    ref.set_bit("f", 1, col)
    need(ref.count(ref.row("f", 1) & g2) == base + 1,
         "reference did not move by one")
    uncached = srv.query(count_q, cached=False)
    cached = srv.query(count_q, cached=True)
    line = {"request": "set-then-count", "column": col, "before": base,
            "uncached": uncached, "cached": cached,
            "correct": uncached == cached == base + 1}
    emit(line)
    need(line["correct"], "acknowledged Set not read back", line)

    # ---- the coalescer and the transfer batcher under load ----
    c0 = srv.counters()
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=16) as pool:
        answers = list(pool.map(
            lambda _: srv.query(count_q, cached=False), range(64)))
    wall_ms = (time.perf_counter() - t0) * 1e3
    c1 = srv.counters()
    line = {"request": "64-concurrent-counts", "threads": 16,
            "correct": all(a == base + 1 for a in answers),
            "wallMs": round(wall_ms, 3),
            "dispatchDelta": c1.get("planner.dispatchCount", 0)
            - c0.get("planner.dispatchCount", 0),
            "coalescedDelta": c1.get("planner.dispatchCoalesced", 0)
            - c0.get("planner.dispatchCoalesced", 0)}
    emit(line)
    need(line["correct"], "concurrent counts disagree", sorted(set(answers)))
    need(line["dispatchDelta"] > 0, "concurrent counts never dispatched",
         line)


def final_checks(srv: Server) -> None:
    """Counters that must hold at the end of any run."""
    dev = device_view(srv)
    vars_ = srv.get("/debug/vars")
    counters, gauges = vars_.get("counters", {}), vars_.get("gauges", {})
    cc = dev["compileCache"]
    emit({"compileCache": {"dir": cc["dir"], "requests": cc["requests"],
                           "hits": cc["hits"]}})
    need(cc["requests"] > 0, "the compile cache was never consulted", cc)
    need(dev["prefetch"]["errors"] == 0, "prefetch uploads failed",
         dev["prefetch"])
    need(counters.get("qos.warmupErrors", 0) == 0, "warm-up errors",
         counters)
    native = "loaded" if gauges.get("runtime.nativeLoaded") == 1.0 \
        else "absent"
    emit({"native": native})
    need(native == "loaded", "the server runs without its native library")
    # The device's own accounting must cover what the planner says it
    # keeps there. The monitor probes the mesh's first device every
    # ~30 s, so wait for a tick taken after the last query.
    first = next(iter(dev["perDeviceBytes"].values()))
    if dev["platform"] == "tpu":
        deadline = time.monotonic() + 60.0
        while (gauges.get("runtime.device_bytes_in_use", 0) < first
               and time.monotonic() < deadline):
            time.sleep(1.0)
            gauges = srv.get("/debug/vars").get("gauges", {})
    mem = {k: gauges.get(f"runtime.{k}")
           for k in ("device_bytes_in_use", "device_bytes_limit")}
    emit({"deviceMemory": mem, "plannerBytesOnFirstDevice": first})
    if dev["platform"] == "tpu":
        need(all(mem.values()), "a TPU reported no device memory stats",
             mem)
        need(mem["device_bytes_in_use"] >= first,
             "the device holds fewer bytes than the planner says it "
             "keeps there", mem)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny data on the CPU backend (sandbox only)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args()

    device = {"platform": None, "kind": None, "count": 0}
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={args.chips}")
        env["XLA_FLAGS"] = " ".join(flags)
    os.makedirs(args.out, exist_ok=True)
    data_dir = os.path.join(HERE, ".chip_smoke", f"data-{os.getpid()}")
    os.makedirs(data_dir)
    srv = None
    ok = False
    try:
        # Build the native codec (host only) before the child starts,
        # so parent and child never run make on the same file at once.
        from pilosa_tpu import native
        native.available()
        srv = Server(args.out, data_dir, env)
        run(args, srv, device)
        final_checks(srv)
        t0 = time.perf_counter()
        rc = srv.stop()
        emit({"childExitCode": rc,
              "stopSeconds": round(time.perf_counter() - t0, 3)})
        need(rc == 0, "the server did not exit cleanly on SIGTERM", rc)
        ok = True
    except Exception:
        traceback.print_exc()
        if srv is not None:
            srv.stop()
            print("---- tail of server.log ----\n" + srv.log_tail(),
                  file=sys.stderr, flush=True)
    finally:
        if srv is not None and srv.proc.poll() is None:
            srv.proc.kill()
        shutil.rmtree(data_dir, ignore_errors=True)
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
