#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run, one result line.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are looked up
by name in ``BENCHMARK.json`` and in this directory's ``configs/``,
``traffic/`` and ``layer_metrics/``: nothing here knows a cell by name.
``--config <name> --traffic <name>`` runs a pair that is in no cell (the
sandbox rehearsal: ``--config rehearsal-4s --traffic count-trees``).

This process never imports jax, nor any module of the program: a chip
belongs to one process, and that process is the server. It starts ONE
child, ``python -m pilosa_tpu.cli server`` with the CLI's defaults (with
``--trace 1``, the same behind ``traced_server.py``), drives it over HTTP
only, and kills it once the last numbers are read.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import http.client  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import dataset  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402

INDEX = "i"
#: client threads posting import-roaring requests during the load, as
#: ``chip_smoke.py``'s: ``import_mbits`` is a function of the code alone.
LOAD_THREADS = 8
#: worker processes of the plain reference, run once the server is gone.
REFERENCE_WORKERS = 8
BOOT_TIMEOUT_S = 300.0
#: an answer may come this long after it was asked: late is late, not wrong.
REQUEST_TIMEOUT_S = 120.0
#: stream numbers of the warm-up passes, apart from the clients' 0..n-1.
WARMUP_STREAM = 1_000_000
PROBE_STREAM = 2_000_000


class BenchFailure(Exception):
    """The run cannot produce a result; the process exits non-zero and
    prints no result line."""


def say(obj: dict) -> None:
    """A phase line, on standard error: standard output holds the result
    line alone, so a run that fails prints nothing there."""
    print(json.dumps(obj), file=sys.stderr, flush=True)


def need(cond: bool, what: str, ctx=None) -> None:
    if not cond:
        raise BenchFailure(f"{what}: {ctx!r}" if ctx is not None else what)


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchFailure(f"no such file: {path}") from None


# ---------------------------------------------------------------------------
# the served node, over HTTP only (copied from chip_smoke.py's Server)
# ---------------------------------------------------------------------------


class Client:
    """One keep-alive connection; one per thread."""

    def __init__(self, port: int):
        self.port = port
        self.conn = None

    def request(self, method: str, path: str, body: bytes | None = None,
                timeout: float = 600.0):
        for attempt in (0, 1):
            fresh = self.conn is None
            if fresh:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=timeout)
            try:
                self.conn.request(method, path, body=body)
                resp = self.conn.getresponse()
                raw = resp.read()
                break
            except (http.client.HTTPException, OSError):
                self.close()
                # A kept-alive connection the server dropped while idle
                # fails on its first use: one new connection, no more.
                if fresh or attempt:
                    raise
        if resp.status != 200:
            raise BenchFailure(f"{method} {path} -> HTTP {resp.status}: "
                               f"{raw[:500].decode(errors='replace')}")
        return json.loads(raw) if raw else {}

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Server:
    def __init__(self, run_dir: str, env: dict, launcher: list[str],
                 traced: bool, cache_args: list[str]):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.data_dir = os.path.join(run_dir, "data")
        self.trace_dir = os.path.join(run_dir, "trace")
        os.makedirs(self.data_dir)
        os.makedirs(self.trace_dir)
        self.log_path = os.path.join(run_dir, "server.log")
        self._log = open(self.log_path, "wb")
        # With a trace asked for, the same node runs behind the wrapper
        # that can switch the profiler on; nothing else differs.
        wrap = [os.path.join(HERE, "traced_server.py"), self.trace_dir] \
            if traced else []
        self.argv = [sys.executable, *wrap, *launcher, "server",
                     "--bind", f"127.0.0.1:{self.port}",
                     "--data-dir", self.data_dir, *cache_args]
        self.proc = subprocess.Popen(self.argv, cwd=ROOT, env=env,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)
        self._local = threading.local()

    def client(self) -> Client:
        c = getattr(self._local, "client", None)
        if c is None:
            c = self._local.client = Client(self.port)
        return c

    def get(self, path: str):
        return self.client().request("GET", path)

    def post(self, path: str, body: bytes | str = b""):
        if isinstance(body, str):
            body = body.encode()
        return self.client().request("POST", path, body)

    def query(self, pql: str, cached: bool,
              timeout: float = REQUEST_TIMEOUT_S):
        suffix = "" if cached else "?noCache=true"
        res = self.client().request(
            "POST", f"/index/{INDEX}/query{suffix}", pql.encode(), timeout)
        need("results" in res, f"query {pql} returned no results", res)
        return res["results"][0]

    def wait_up(self) -> None:
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while time.monotonic() < deadline:
            need(self.proc.poll() is None, "server exited during start-up",
                 self.proc.returncode)
            try:
                self.get("/status")
                return
            except (http.client.HTTPException, OSError):
                time.sleep(0.25)
        raise BenchFailure(f"server not up after {BOOT_TIMEOUT_S:.0f}s")

    def counters(self) -> dict:
        return self.get("/debug/vars").get("counters", {})

    def kill(self) -> None:
        """SIGKILL: the data dir is thrown away with the run, so the
        node's graceful close (a snapshot of every fragment, minutes at
        1B columns) would serve no request."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._log.close()

    def log_tail(self, n_bytes: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n_bytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<no server log: {e}>"


def device_view(srv: Server) -> dict:
    dev = srv.get("/debug/device")
    need(dev.get("enabled") is True, "/debug/device: planner not enabled",
         dev)
    for key in ("platform", "deviceKind", "deviceCount", "perDeviceBytes",
                "evictions", "uploads", "compileCache"):
        need(key in dev, f"/debug/device lacks {key!r}", sorted(dev))
    return dev


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
    raise BenchFailure("boot warm-up did not finish in 600s")


# ---------------------------------------------------------------------------
# set-up: the load; and, once the window has closed, the plain reference
# ---------------------------------------------------------------------------


def load_index(srv: Server, config: dict, seed: int) -> tuple[int, float]:
    """One import-roaring request per (shard, field) carrying every row of
    that fragment, from ``LOAD_THREADS`` client threads. Returns (set bits
    sent, seconds)."""
    width = 1 << int(config["shard_width_exp"])

    def one(shard: int) -> int:
        sent = 0
        for f, per_row in dataset.shard_rows(config, seed, shard).items():
            positions = dataset.fragment_positions(per_row, width)
            srv.post(f"/index/{INDEX}/field/{f}/import-roaring/{shard}",
                     dataset.roaring_encode(positions))
            sent += len(positions)
        return sent

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=LOAD_THREADS) as pool:
        n_bits = sum(pool.map(one, range(int(config["shards"]))))
    return n_bits, time.perf_counter() - t0


def probe_plan(config: dict, seed: int):
    """The write read-back's request: ``Count(Intersect(Row(fa=a),
    Row(fb=b)))`` on the configuration's first two fields, and a seeded
    column of ``fb=b`` that ``fa=a`` lacks, so that an acknowledged
    ``Set(col, fa=a)`` moves the exact answer by one."""
    fa, fb = sorted(config["fields"])[:2]
    width = 1 << int(config["shard_width_exp"])
    rng = np.random.default_rng([seed, PROBE_STREAM])
    a = int(rng.integers(0, int(config["fields"][fa]["rows"])))
    b = int(rng.integers(0, int(config["fields"][fb]["rows"])))
    shard = int(rng.integers(0, int(config["shards"])))
    per_field = dataset.shard_rows(config, seed, shard)
    free = np.setdiff1d(per_field[fb][b], per_field[fa][a])
    need(len(free) > 0, "no column for the write read-back", (a, b, shard))
    col = shard * width + int(free[int(rng.integers(0, len(free)))])
    spec = {"templates": [{
        "share": 1.0,
        "pql": f"Count(Intersect(Row({fa}={{a}}), Row({fb}={{b}})))",
        "draw": {"a": {"row": fa}, "b": {"row": fb}}}]}
    templates = traffic_mod.read_templates(spec)
    t = templates[0]
    req = traffic_mod.Request(0, (), (a, b),
                              traffic_mod.render(t, {"a": a, "b": b}))
    return templates, req, f"Set({col}, {fa}={a})"


# ---------------------------------------------------------------------------
# the cell's own warm-up, and the window
# ---------------------------------------------------------------------------


def fire(srv: Server, requests: list, cached: bool) -> list:
    """All ``requests`` at once, one thread each; answers in order."""
    if not requests:
        return []
    with ThreadPoolExecutor(max_workers=len(requests)) as pool:
        return list(pool.map(lambda r: srv.query(r.pql, cached), requests))


def warm_up(srv: Server, config: dict, spec: dict, templates, picker,
            seed: int) -> dict:
    """Every row once (so that what fits is resident), then every program
    structure of the mix at the widths its share of the clients can bring
    together, then the mix itself for a few seconds. Rows come from a
    stream of their own: the window's requests are not rehearsed."""
    cached = not spec.get("noCache", False)
    clients = int(spec["clients"])
    t0 = time.perf_counter()
    n_rows = 0
    for f in sorted(config["fields"]):
        for r in range(int(config["fields"][f]["rows"])):
            srv.query(f"Count(Row({f}={r}))", cached)
            n_rows += 1
    t_rows = time.perf_counter()

    n_columns = int(config["columns"])
    pool = traffic_mod.draw_stream(templates, picker, n_columns, seed,
                                   WARMUP_STREAM, 64 * clients)
    by_group = {}
    for r in pool:
        by_group.setdefault(r.group, []).append(r)
    waves = 0
    structs = traffic_mod.structures(templates)
    for ti, choices in structs:
        t = templates[ti]
        if t.is_write():
            continue
        share = t.share / max(1, sum(1 for s in structs if s[0] == ti))
        width = int(min(clients, max(2, round(3 * clients * share))))
        have = by_group.get((ti, choices), [])
        for k in range(int(spec.get("warmup_rounds", 1))):
            wave = [have[(k * width + j) % len(have)]
                    for j in range(width)] if have else []
            fire(srv, wave, cached)
            waves += 1
    t_waves = time.perf_counter()

    n_mix = run_clients(srv, [traffic_mod.draw_stream(
        templates, picker, n_columns, seed, WARMUP_STREAM + 1 + c,
        int(spec["stream_length"])) for c in range(clients)],
        float(spec.get("warmup_seconds", 0)), cached)["n"]
    return {"rows": n_rows, "rows_s": t_rows - t0, "waves": waves,
            "waves_s": t_waves - t_rows, "mix_requests": n_mix,
            "mix_s": time.perf_counter() - t_waves}


def run_clients(srv: Server, streams: list, seconds: float, cached: bool,
                on_start=None) -> dict:
    """The closed loop: each client sends the next request of its own
    stream as soon as the last answer is back, until the deadline; a
    request in flight at the deadline is waited for. Returns the records
    ``(client, k, t_send, t_recv, answer | None, error | None)`` with
    times as ``time.time()``."""
    if seconds <= 0:
        return {"n": 0, "records": [], "t0": time.time(), "wrapped": 0}
    barrier = threading.Barrier(len(streams) + 1)
    records = [[] for _ in streams]
    wrapped = [0] * len(streams)
    deadline = [0.0]

    def client(c: int) -> None:
        stream, recs = streams[c], records[c]
        srv.client()  # the connection exists before the clock starts
        barrier.wait()
        k = 0
        while time.time() < deadline[0]:
            if k and k % len(stream) == 0:
                wrapped[c] += 1
            req = stream[k % len(stream)]
            t_send = time.time()
            try:
                ans, err = srv.query(req.pql, cached), None
            except (BenchFailure, http.client.HTTPException, OSError) as e:
                ans, err = None, repr(e)
            recs.append((c, k % len(stream), t_send, time.time(), ans, err))
            k += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(len(streams))]
    for t in threads:
        t.start()
    t0 = time.time()
    deadline[0] = t0 + seconds
    barrier.wait()
    if on_start is not None:
        on_start(t0)
    for t in threads:
        t.join(seconds + REQUEST_TIMEOUT_S + 30.0)
        need(not t.is_alive(), "a client thread never came back")
    flat = [r for recs in records for r in recs]
    return {"n": len(flat), "records": flat, "t0": t0,
            "wrapped": sum(wrapped)}


class TraceSwitch:
    """Drops the start and stop files that ``traced_server.py`` watches:
    a few seconds from the middle of the window."""

    def __init__(self, trace_dir: str, seconds: float, span: float):
        self.trace_dir = trace_dir
        span = min(span, max(seconds - 1.0, 0.5 * seconds))
        self.offsets = ((seconds - span) / 2, (seconds + span) / 2)
        self.timers: list[threading.Timer] = []

    def _drop(self, name: str) -> None:
        with open(os.path.join(self.trace_dir, name), "w"):
            pass

    def arm(self, t0: float) -> None:
        for name, off in zip(("start", "stop"), self.offsets):
            t = threading.Timer(max(0.0, t0 + off - time.time()),
                                self._drop, args=(name,))
            t.daemon = True
            t.start()
            self.timers.append(t)

    def finish(self, srv: Server) -> dict:
        """Wait for the watcher's ``done`` (the profiler has written its
        file by then) and return the span's wall-clock bounds."""
        for t in self.timers:
            t.join()
        done = os.path.join(self.trace_dir, "done")
        deadline = time.monotonic() + 120.0
        while not os.path.exists(done):
            need(srv.proc.poll() is None, "server died while tracing",
                 srv.proc.returncode)
            need(time.monotonic() < deadline,
                 "the profiler did not finish in 120 s")
            time.sleep(0.1)
        with open(done) as f:
            return json.load(f)


def find_xplane(trace_dir: str) -> str:
    for base, _, files in os.walk(trace_dir):
        for name in files:
            if name.endswith(".xplane.pb"):
                return os.path.join(base, name)
    raise BenchFailure(f"the profiler left no .xplane.pb under {trace_dir}")


def reduce_trace(xplane: str) -> dict:
    """In a process of its own, held to the CPU: the server is dead by
    now, and this process still never imports jax."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), xplane],
        env=env, capture_output=True, text=True, timeout=200)
    if out.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------


def judge(pairs: list, expected: dict, readback: dict,
          dispatch_delta: int, who: str = "program") -> dict:
    """Every number compared, beside its limit. ``pairs`` holds each
    request of the window with its answer in the comparable form (None:
    it never came). All comparisons are exact: the configuration states
    exact answers and acknowledged writes read back, so every limit is
    0."""
    wrong = missing = 0
    first_wrong = None
    for req, got in pairs:
        if got is None:
            missing += 1
            continue
        want = expected[(req.group, req.values)]
        if got != want:
            wrong += 1
            if first_wrong is None:
                first_wrong = {"pql": req.pql, "got": got, "want": want}
    want = readback["before"] + 1
    checks = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "missing_answers": {"value": missing, "limit": 0},
        "set_not_acknowledged": {
            "value": 0 if readback["ack"] is True else 1, "limit": 0},
        "readback_pre_gap": {
            "value": abs(readback["pre"] - readback["before"]), "limit": 0},
        "readback_uncached_gap": {
            "value": abs(readback["uncached"] - want), "limit": 0},
        "readback_cached_gap": {
            "value": abs(readback["cached"] - want), "limit": 0},
        "window_without_dispatch": {
            "value": 0 if dispatch_delta > 0 else 1, "limit": 0},
    }
    if first_wrong is not None:
        say({"first_wrong_answer": first_wrong, "of": who})
    return checks


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def write_readback(srv: Server, probe_req, set_pql: str) -> dict:
    """One acknowledged Set, then the affected Count uncached and through
    the result cache (which held the old answer): both must read it."""
    pre = int(srv.query(probe_req.pql, cached=True))
    ack = srv.query(set_pql, cached=False)
    uncached = int(srv.query(probe_req.pql, cached=False))
    cached = int(srv.query(probe_req.pql, cached=True))
    return {"pre": pre, "ack": ack, "uncached": uncached, "cached": cached}


def stale_readback(before: int) -> dict:
    """The control's: a node that acknowledges the write and goes on
    serving the old answer."""
    return {"before": before, "pre": before, "ack": True,
            "uncached": before, "cached": before}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(q * len(s))) - 1))]


def read_layer_metric(name: str, ctx: dict):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise BenchFailure(f"per-layer metric {name!r} has no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def metrics_for(bench: dict, cell: str | None, kind: str) -> list[dict]:
    """The metrics of ``kind`` that this cell reports: those without a
    ``workloads`` key, and those that list the cell. A pair that is in no
    cell (the rehearsal) reports them all."""
    return [m for m in bench[kind]
            if cell is None or "workloads" not in m
            or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def resolve(args) -> tuple[dict, dict | None, str, str]:
    """(BENCHMARK.json, the cell or None, configuration name, mix name)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    if args.workload:
        cells = {w["name"]: w for w in bench["workloads"]}
        need(args.workload in cells, "no such cell in BENCHMARK.json",
             args.workload)
        cell = cells[args.workload]
        return bench, cell, cell["config"], cell["traffic"]
    need(bool(args.config and args.traffic),
         "give --workload, or --config and --traffic")
    return bench, None, args.config, args.traffic


def child_env(config: dict, chips: int) -> tuple[dict, list[str]]:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if config["platform"] == "cpu":
        # Only a configuration that says so runs on the CPU backend: the
        # sandbox rehearsal, which is in no cell.
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={chips}")
        env["XLA_FLAGS"] = " ".join(flags)
    cache_args = []
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        cache_args = ["--compile-cache-dir", os.path.join(ROOT, ".jax_cache")]
    return env, cache_args


def build_native(env: dict) -> None:
    """Build the program's native codec (host only) before the child
    starts, as ``chip_smoke.py`` does, so that the server's first import
    does not wait on a compiler."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from pilosa_tpu import native; "
         "raise SystemExit(0 if native.available() else 1)"],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    need(out.returncode == 0, "the native library does not build",
         out.stderr[-2000:])


#: the users' entry point, untouched.
LAUNCHER = ["-m", "pilosa_tpu.cli"]


@dataclass
class Plan:
    """Everything of a run that is fixed before the server starts."""
    bench: dict
    cell: str | None
    config: dict
    spec: dict
    peaks: dict
    chips: int
    seed: int
    seconds: float
    traced: bool
    keep_trace: str
    templates: list
    picker: object
    streams: list
    probe_cat: object
    probe_req: object
    set_pql: str
    control_shard: int | None


def plan_run(args) -> Plan:
    bench, cell, config_name, traffic_name = resolve(args)
    need(os.path.exists(os.path.join(ROOT, "pilosa_tpu", "cli.py")),
         "the program is not in this checkout (pilosa_tpu/cli.py)")
    config = load_json(HERE, "configs", config_name + ".json")
    # A pair that is in no cell runs on the chips its configuration states.
    chips = int((cell or config)["chips"])
    cell = cell["name"] if cell else None
    spec = load_json(HERE, "traffic", traffic_name + ".json")
    seed = int(args.seed)
    templates = traffic_mod.read_templates(spec)
    picker = traffic_mod.RowPicker(spec, config["fields"], seed)
    # Where the mix names a population seed, every run sends the same
    # requests in the same order by rank, and --seed only says which rows
    # hold those ranks (and what bits the rows hold): the same work from
    # every seed.
    streams = [traffic_mod.draw_stream(
        templates, picker, int(config["columns"]),
        int(spec.get("population_seed", seed)), c,
        int(spec["stream_length"])) for c in range(int(spec["clients"]))]
    probe_templates, probe_req, set_pql = probe_plan(config, seed)
    control_shard = None
    if args.control:
        control_shard = int(np.random.default_rng(
            [seed, 0x6374726C]).integers(0, int(config["shards"])))
    say({"cell": cell, "config": config_name, "traffic": traffic_name,
         "seed": seed, "seconds": float(args.seconds),
         "trace": int(args.trace), "clients": len(streams),
         "stream_length": int(spec["stream_length"])})
    return Plan(bench, cell, config, spec, load_json(HERE, "peaks.json"),
                chips, seed, float(args.seconds), bool(int(args.trace)),
                args.keep_trace, templates, picker, streams,
                reference.Catalogue(probe_templates, [probe_req]),
                probe_req, set_pql, control_shard)


def run(args, launcher: list[str] | None = None) -> dict:
    """One run. ``launcher`` is for the tests under ``benchmark/tests``,
    which put a server with a planted fault in the program's place."""
    plan = plan_run(args)
    env, cache_args = child_env(plan.config, plan.chips)
    build_native(env)
    run_dir = os.path.join(
        ROOT, ".bench_run",
        f"{plan.cell or plan.config['name']}-{os.getpid()}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    srv = None
    try:
        srv = Server(run_dir, env, launcher or LAUNCHER, plan.traced,
                     cache_args)
        return measure(plan, srv)
    except Exception:
        if srv is not None:
            srv.kill()
            print("---- tail of server.log ----\n" + srv.log_tail(),
                  file=sys.stderr, flush=True)
        raise
    finally:
        if srv is not None:
            srv.kill()
        shutil.rmtree(run_dir, ignore_errors=True)


def measure(plan: Plan, srv: Server) -> dict:
    bench, cell, config, spec = plan.bench, plan.cell, plan.config, plan.spec
    peaks, seed, seconds, traced = plan.peaks, plan.seed, plan.seconds, \
        plan.traced
    templates, streams, probe_req = plan.templates, plan.streams, \
        plan.probe_req
    cached = not spec.get("noCache", False)

    # ---- boot ----
    srv.wait_up()
    t_boot = time.perf_counter()
    dev = device_view(srv)
    device = {"platform": dev["platform"], "kind": dev["deviceKind"],
              "count": dev["deviceCount"]}
    need(dev["platform"] == config["platform"],
         f"the server's planner is on {dev['platform']!r}, the "
         f"configuration states {config['platform']!r}", device)
    need(dev["deviceCount"] == plan.chips,
         f"expected {plan.chips} device(s)", device)
    need(dev["deviceKind"] in peaks or config["platform"] == "cpu",
         "device kind is not in benchmark/peaks.json", dev["deviceKind"])
    info = srv.get("/info")
    need(info.get("shardWidth") == 1 << int(config["shard_width_exp"]),
         "the node's shard width is not the configuration's", info)
    srv.post(f"/index/{INDEX}")
    for f in sorted(config["fields"]):
        srv.post(f"/index/{INDEX}/field/{f}")

    # ---- load ----
    n_bits, load_s = load_index(srv, config, seed)
    say({"phase": "load", "route": "import-roaring", "seconds": load_s,
         "set_bits": n_bits, "mbits_per_s": n_bits / load_s / 1e6,
         "requests": len(config["fields"]) * int(config["shards"]),
         "threads": LOAD_THREADS, "boot_s": t_boot - T_START})
    warm = wait_warmup(srv)
    t_boot_warm = time.perf_counter()

    # ---- the cell's warm-up ----
    wu = warm_up(srv, config, spec, templates, plan.picker,
                 int(spec.get("population_seed", seed)))
    dev0 = device_view(srv)
    say({"phase": "warmup", "boot_programs": warm.get("qos.warmupPrograms"),
         "wait_s": t_boot_warm - t_boot - load_s, **wu,
         "resident_bytes": dev0["bytes"], "budget_bytes": dev0["budget_bytes"],
         "class_bytes": dev0["class_bytes"], "evictions": dev0["evictions"],
         "uploads": dev0["uploads"]})
    setup_s = time.perf_counter() - T_START

    # ---- the window ----
    switch = TraceSwitch(srv.trace_dir, seconds,
                         float(spec.get("trace_seconds", 4))) \
        if traced else None
    c0 = srv.counters()
    t_c0 = time.time()
    win = run_clients(srv, streams, seconds, cached,
                      on_start=switch.arm if switch else None)
    c1 = srv.counters()
    t_c1 = time.time()
    dev1 = device_view(srv)
    gauges = srv.get("/debug/vars").get("gauges", {})
    bounds = switch.finish(srv) if switch else None
    mem_peak = int(gauges.get("runtime.device_peak_bytes_in_use", 0))

    # ---- an acknowledged write is read back, outside the timing ----
    readback = write_readback(srv, probe_req, plan.set_pql)
    xplane = find_xplane(srv.trace_dir) if traced else None
    if xplane and plan.keep_trace:
        os.makedirs(plan.keep_trace, exist_ok=True)
        shutil.copy(xplane, os.path.join(
            plan.keep_trace, f"{cell or config['name']}-{seed}.xplane.pb"))
    disk = sum(os.path.getsize(os.path.join(b, f))
               for b, _, fs in os.walk(srv.data_dir) for f in fs)
    srv.kill()

    # ---- the plain reference, for every request the window sent ----
    records = win["records"]
    catalogue = reference.Catalogue(
        templates, [streams[r[0]][r[1]] for r in records])
    t_ref = time.perf_counter()
    control_partials = reference.run_pass(
        [catalogue, plan.probe_cat], config, seed, REFERENCE_WORKERS,
        keep=plan.control_shard)
    ref_s = time.perf_counter() - t_ref
    expected = catalogue.expected()
    readback["before"] = plan.probe_cat.expected()[
        (probe_req.group, probe_req.values)]
    say({"phase": "reference", "seconds": ref_s, "catalogue": len(catalogue),
         "write_readback": dict(readback, set=plan.set_pql)})

    # ---- numbers ----
    t0, t1 = win["t0"], win["t0"] + seconds
    dispatch_delta = c1.get("planner.dispatchCount", 0) - \
        c0.get("planner.dispatchCount", 0)
    pairs = [(streams[c][k],
              None if err is not None or ans is None
              else reference.norm(catalogue.tree(streams[c][k]), ans))
             for c, k, _, _, ans, err in records]
    checks = judge(pairs, expected, readback, dispatch_delta)
    correct = passes(checks)
    answered = [r for r in records if r[5] is None]
    failed = len(records) - len(answered)
    lat_ms = [(r[3] - r[2]) * 1e3 for r in answered]
    in_window = sum(1 for r in answered if r[3] <= t1)
    say({"phase": "window", "requests": len(records), "failed": failed,
         "completed_in_window": in_window, "wrapped_streams": win["wrapped"],
         "dispatch_delta": dispatch_delta,
         "coalesced_delta": c1.get("planner.dispatchCoalesced", 0)
         - c0.get("planner.dispatchCoalesced", 0),
         "evictions_delta": dev1["evictions"] - dev0["evictions"],
         "uploads_delta": dev1["uploads"] - dev0["uploads"],
         "compiles_delta": _compiles(dev1) - _compiles(dev0),
         "resident_bytes": dev1["bytes"], "data_dir_bytes": disk})
    need(bool(lat_ms), "the window answered no request")

    values = {
        "qps": (in_window - checks["wrong_answers"]["value"]) / seconds,
        "p50_ms": statistics.median(lat_ms),
        "p95_ms": percentile(lat_ms, 0.95),
        "import_mbits": n_bits / load_s / 1e6,
        "setup_s": setup_s,
    }
    trace = None
    if traced:
        trace = reduce_trace(xplane)
        need(trace["busy_s"] > 0 or config["platform"] == "cpu",
             "no operation ran on the device in the traced span", trace)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    device["memory_peak_bytes"] = mem_peak

    if traced:
        # The trace's clock starts with the profiling session, so the
        # span's wall-clock start is its end less its length.
        hi = bounds["stopping"]
        lo = hi - trace["window_s"]
        ctx = {"config": config, "traffic": spec,
               "peaks": peaks.get(dev["deviceKind"]), "trace": trace,
               "trace_requests": [streams[r[0]][r[1]].pql for r in answered
                                  if lo <= r[3] <= hi],
               "answered": len(answered), "latencies_ms": lat_ms,
               "counters_s": t_c1 - t_c0,
               "counters0": c0, "counters1": c1,
               "device0": dev0, "device1": dev1}
        metrics = {}
        for m in metrics_for(bench, cell, "per_layer"):
            v = read_layer_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_for(bench, cell, "end_to_end")}

    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and trace.get("device_ops") is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if plan.control_shard is not None:
        # The control: the reference in the program's place, with two of
        # the configuration's guarantees broken.
        ctl = catalogue.expected(without=control_partials)
        ctl_checks = judge(
            [(req, ctl[(req.group, req.values)]) for req, _ in pairs],
            expected,
            stale_readback(readback["before"]), 1, who="control")
        result["control"] = {
            "broken": f"shard {plan.control_shard} left out of every "
                      "answer; the write served stale",
            "correct": passes(ctl_checks), "checks": ctl_checks}
    result["checks"] = checks
    return result


def _compiles(dev: dict) -> int:
    cc = dev["compileCache"]
    return int(cc["requests"]) - int(cc["hits"])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--config", default="")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also judge the control (the reference with one "
                         "guarantee broken) in the program's place")
    ap.add_argument("--keep-trace", default="",
                    help="copy the run's .xplane.pb into this directory")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchFailure as e:
        print(f"benchmark failed: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
