"""Observability tests: stats counting, prometheus exposition, tracing
spans, logger, /metrics endpoint."""

import io
import urllib.request

from pilosa_tpu.core import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.obs import (
    MemoryStats,
    NopStats,
    SimpleTracer,
    StandardLogger,
    prometheus_text,
    set_tracer,
    start_span,
)
from pilosa_tpu.obs import tracing
from pilosa_tpu.obs.tracing import NopTracer


def test_memory_stats_tags():
    s = MemoryStats()
    s.count("Query")
    s.with_tags("index:i").count("Query", 2)
    s.gauge("goroutines", 5)
    s.timing("exec", 0.5)
    assert s.counter_value("Query") == 1
    assert s.counter_value("Query", "index:i") == 2
    text = prometheus_text(s)
    assert 'pilosa_Query{index="i"} 2' in text
    assert "pilosa_goroutines 5" in text
    assert "pilosa_exec_seconds_count 1" in text


def test_executor_counts_calls():
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    stats = MemoryStats()
    e = Executor(h, stats=stats)
    e.execute("i", "Set(1, f=1)")
    e.execute("i", "Count(Row(f=1))")
    assert stats.counter_value("Set", "index:i") == 1
    assert stats.counter_value("Count", "index:i") == 1
    # Count's child Row is not double-counted as a top-level call
    assert stats.counter_value("Row", "index:i") == 0


def test_simple_tracer_records_spans():
    t = SimpleTracer()
    set_tracer(t)
    try:
        h = Holder()
        idx = h.create_index("i")
        idx.create_field("f")
        e = Executor(h)
        e.execute("i", "Set(1, f=1)")
        ops = [s.operation for s in t.spans]
        assert "Executor.executeSet" in ops
        assert all(s.duration is not None for s in t.spans)
    finally:
        set_tracer(NopTracer())


def test_start_span_contextmanager():
    t = SimpleTracer()
    set_tracer(t)
    try:
        with start_span("custom.op") as span:
            span.set_tag("k", "v")
        assert t.spans[0].operation == "custom.op"
        assert t.spans[0].tags["k"] == "v"
        assert "trace.id" in t.spans[0].tags  # spans join a trace
    finally:
        set_tracer(NopTracer())


def test_logger_verbose_gate():
    buf = io.StringIO()
    log = StandardLogger(stream=buf, verbose=False)
    log.printf("hello %s", "world")
    log.debugf("hidden")
    out = buf.getvalue()
    assert "hello world" in out and "hidden" not in out
    log2 = StandardLogger(stream=buf, verbose=True)
    log2.debugf("shown")
    assert "shown" in buf.getvalue()


def test_metrics_endpoint():
    from pilosa_tpu.server.node import ServerNode
    n = ServerNode(bind="127.0.0.1:0", use_planner=False)
    n.open()
    try:
        base = n.address
        urllib.request.urlopen(urllib.request.Request(
            base + "/index/i", data=b"{}", method="POST"), timeout=10)
        urllib.request.urlopen(urllib.request.Request(
            base + "/index/i/field/f", data=b"{}", method="POST"), timeout=10)
        urllib.request.urlopen(urllib.request.Request(
            base + "/index/i/query", data=b"Set(1, f=1)", method="POST"),
            timeout=10)
        text = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
        assert 'pilosa_Set{index="i"} 1' in text
    finally:
        n.close()


def test_statsd_wire_format():
    import socket
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(5)
    port = rx.getsockname()[1]
    from pilosa_tpu.obs import StatsdStats
    st = StatsdStats(host="127.0.0.1", port=port)
    st.count("queries", 3)
    st.gauge("heap", 12.5)
    st.with_tags("index:i").timing("exec", 0.25)
    got = sorted(rx.recv(512).decode() for _ in range(3))
    assert got[0] == "pilosa.exec:250.000|ms|#index:i"
    assert got[1] == "pilosa.heap:12.5|g"
    assert got[2] == "pilosa.queries:3|c"
    rx.close()


def test_runtime_gauges():
    from pilosa_tpu.core import Holder
    from pilosa_tpu.obs import MemoryStats, collect_runtime_gauges
    from pilosa_tpu.parallel import MeshPlanner, make_mesh
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    f.import_bits([1] * 5, [0, 1, 2, 3, 4])
    planner = MeshPlanner(h, make_mesh())
    from pilosa_tpu.exec import Executor
    Executor(h, planner=planner).execute("i", "Count(Row(f=1))")
    stats = MemoryStats()
    out = collect_runtime_gauges(stats, planner)
    assert out["threads"] >= 1
    assert out.get("rssBytes", 1) > 0
    assert out["plannerCacheEntries"] >= 1
    assert out["plannerCacheBytes"] > 0
    assert stats.gauges[("runtime.plannerCacheBudgetBytes", ())] == \
        planner.max_cache_bytes
    from pilosa_tpu import native
    if native.available():
        # Import buffer-pool gauges ride the same sweep.
        assert "poolLimitBytes" in out
        assert out["poolLimitBytes"] > 0


def test_trace_propagates_across_nodes():
    """A remote sub-query's spans carry the coordinator's trace id
    (reference InjectHTTPHeaders/ExtractHTTPHeaders, tracing.go:37)."""
    import json
    import urllib.request
    from pilosa_tpu.obs import SimpleTracer, set_tracer, NopTracer
    from pilosa_tpu.server.node import ServerNode
    import socket

    ports = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    addrs = [f"127.0.0.1:{p}" for p in ports]
    tracer = SimpleTracer()
    set_tracer(tracer)
    nodes = [ServerNode(bind=a, peers=[x for x in addrs if x != a],
                        use_planner=False, anti_entropy_interval=0.0,
                        check_nodes_interval=0.0) for a in addrs]
    for n in nodes:
        n.open()
    try:
        base = nodes[0].address

        def post(path, body=""):
            r = urllib.request.Request(base + path, data=body.encode(),
                                       method="POST")
            return json.loads(urllib.request.urlopen(r, timeout=10).read()
                              or b"{}")

        post("/index/t")
        post("/index/t/field/f")
        # Bits across enough shards that BOTH nodes own some.
        from pilosa_tpu.config import SHARD_WIDTH
        for s in range(16):
            post("/index/t/query", f"Set({s * SHARD_WIDTH}, f=1)")
        tracer.spans.clear()
        assert post("/index/t/query", "Count(Row(f=1))") == \
            {"results": [16]}
        exec_spans = [s for s in tracer.spans
                      if s.operation.startswith("Executor.execute")]
        ids = {s.tags.get("trace.id") for s in exec_spans}
        assert len(exec_spans) >= 2     # coordinator + remote node
        assert len(ids) == 1 and None not in ids
    finally:
        set_tracer(NopTracer())
        for n in nodes:
            try:
                n.close()
            except Exception:
                pass


def test_otlp_exporter_against_collector_double(tmp_path):
    """OTLPTracer (VERDICT r4 #9): spans flush as OTLP/HTTP JSON to a
    local collector double; structure and parentage survive."""
    import http.server
    import json
    import threading

    from pilosa_tpu.obs.otlp import OTLPTracer

    received = []

    class Collector(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Collector)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        tr = OTLPTracer(
            endpoint=f"http://127.0.0.1:{srv.server_port}/v1/traces",
            service_name="test-node", flush_interval=60.0)
        parent = tr.start_span("Executor.Execute")
        parent.set_tag("index", "i")
        child = tr.start_span("planner.count", parent_id=parent.span_id)
        child.finish()
        parent.finish()
        tr.flush()
        assert tr.exported == 2 and tr.dropped == 0
        (batch,) = received
        rs = batch["resourceSpans"][0]
        svc = rs["resource"]["attributes"][0]
        assert svc["key"] == "service.name"
        assert svc["value"]["stringValue"] == "test-node"
        spans = rs["scopeSpans"][0]["spans"]
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"Executor.Execute", "planner.count"}
        p = by_name["Executor.Execute"]
        c = by_name["planner.count"]
        assert c["parentSpanId"] == p["spanId"]
        assert len(p["traceId"]) == 32 and len(p["spanId"]) == 16
        assert int(p["endTimeUnixNano"]) >= int(p["startTimeUnixNano"])
        assert {"key": "index", "value": {"stringValue": "i"}} \
            in p["attributes"]
        tr.close()
    finally:
        srv.shutdown()


def test_otlp_exporter_collector_down_never_raises():
    from pilosa_tpu.obs.otlp import OTLPTracer
    tr = OTLPTracer(endpoint="http://127.0.0.1:1/v1/traces",
                    flush_interval=60.0, timeout=0.5)
    tr.start_span("x").finish()
    tr.flush()  # collector unreachable: drop, don't raise
    assert tr.dropped == 1
    tr.close()


def test_debug_profile_route_returns_pstats_blob(tmp_path):
    """/debug/profile?seconds=N yields a non-empty blob the standard
    pstats tooling loads (VERDICT r4 #9 done-bar)."""
    import pstats
    import threading
    import time
    import urllib.request

    from pilosa_tpu.server.node import ServerNode

    n = ServerNode(bind="127.0.0.1:0", use_planner=False)
    n.open()
    stop = threading.Event()

    def busy():  # give the sampler something to see
        while not stop.is_set():
            sum(i * i for i in range(2000))
            time.sleep(0.001)

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
                n.address + "/debug/profile?seconds=0.4",
                timeout=30) as resp:
            blob = resp.read()
            assert resp.headers["Content-Type"] == \
                "application/octet-stream"
        assert len(blob) > 0
        path = tmp_path / "profile.pstats"
        path.write_bytes(blob)
        st = pstats.Stats(str(path))
        assert st.total_calls > 0
        funcs = {f for (_, _, f) in st.stats}
        assert "busy" in funcs  # the sampler saw the busy thread
    finally:
        stop.set()
        n.close()


def test_heap_stats_accounts_all_tiers():
    """obs.heap.heap_stats answers 'where did the RAM go' in one dict:
    host rows per index, native pool, planner HBM cache, tracemalloc
    (VERDICT r4 #5 done-bar)."""
    import numpy as np

    from pilosa_tpu.obs.heap import heap_stats
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    holder = Holder()
    idx = holder.create_index("hp")
    f = idx.create_field("f")
    rng = np.random.default_rng(7)
    f.import_bits(rng.integers(0, 3, 5000), rng.integers(0, 1 << 21, 5000))
    planner = MeshPlanner(holder, make_mesh(n=4))
    e = Executor(holder, planner=planner)
    e.execute("hp", "Count(Row(f=1))")  # populate the stack cache

    out = heap_stats(holder, planner=planner)
    hp = out["host_rows"]["hp"]
    assert hp["fragments"] >= 2 and hp["rows"] >= 3
    assert hp["host_row_bytes"] > 0
    assert out["planner_cache"]["bytes"] > 0
    assert out["planner_cache"]["budget_bytes"] > 0
    assert "native_pool" in out
    # First call arms tracemalloc; second sees sites.
    out2 = heap_stats(holder, planner=planner)
    assert out2["tracemalloc"]["tracing"] in ("on", "started")
    if out2["tracemalloc"]["tracing"] == "on":
        assert out2["tracemalloc"]["traced_current_bytes"] >= 0


def test_debug_heap_route():
    import json
    import urllib.request

    from pilosa_tpu.server.node import ServerNode

    n = ServerNode(bind="127.0.0.1:0", use_planner=False)
    n.open()
    try:
        urllib.request.urlopen(urllib.request.Request(
            n.address + "/index/hr", method="POST"), timeout=10)
        urllib.request.urlopen(urllib.request.Request(
            n.address + "/index/hr/field/f", method="POST"), timeout=10)
        urllib.request.urlopen(urllib.request.Request(
            n.address + "/index/hr/query", data=b"Set(1, f=1)",
            method="POST"), timeout=10)
        with urllib.request.urlopen(n.address + "/debug/heap?top=5",
                                    timeout=10) as resp:
            out = json.loads(resp.read())
        assert out["host_rows"]["hr"]["rows"] >= 1
        assert out["host_rows"]["hr"]["host_row_bytes"] >= 0
        assert "tracemalloc" in out and "native_pool" in out
        assert out.get("vmrss_kib", 1) > 0
    finally:
        n.close()


# -- ISSUE 11: SLO histograms, per-query profiles, device telemetry ------


def test_log_histogram_observe_merge_quantile():
    from pilosa_tpu.obs import LogHistogram, SECONDS_BOUNDS
    h = LogHistogram()
    for v in (0.0002, 0.0002, 0.01, 0.5):
        h.observe(v)
    assert h.count == 4 and abs(h.sum - 0.5104) < 1e-12
    assert 0.0001 <= h.quantile(0.5) <= 0.01
    items = h.bucket_items()
    assert items[-1] == ("+Inf", 4)
    cums = [c for _, c in items]
    assert cums == sorted(cums)          # cumulative by construction
    other = LogHistogram()
    other.observe(100.0)                 # overflows into +Inf
    h.merge(other)
    assert h.count == 5 and h.bucket_items()[-1] == ("+Inf", 5)
    # a +Inf rank floors to the last finite bound (documented behavior)
    assert other.quantile(0.99) == SECONDS_BOUNDS[-1]
    # memory stays O(buckets) no matter how many observations land
    for _ in range(10_000):
        h.observe(0.001)
    assert len(h.counts) == len(h.bounds) + 1
    snap = h.snapshot()
    assert snap["count"] == h.count and snap["p99"] > 0


def test_log_histogram_exemplars():
    from pilosa_tpu.obs import LogHistogram
    h = LogHistogram()
    for _ in range(200):
        h.observe(0.0002)
    h.observe(5.0, trace_id="t-slow")
    slow_i = next(j for j in range(len(h.counts))
                  if h.exemplar(j) is not None)
    # the slow observation's bucket sits at/above the p99 bucket and
    # keeps its trace id
    assert slow_i >= h.p99_bucket_index()
    assert h.exemplar(slow_i) == (5.0, "t-slow")


def test_memory_stats_timings_bounded():
    """Satellite: the unbounded per-series timing lists are gone —
    10k observations cost O(buckets), and the accessors still work."""
    from pilosa_tpu.obs import LogHistogram
    s = MemoryStats()
    for _ in range(10_000):
        s.timing("exec", 0.001)
    h = s.timings[("exec", ())]
    assert isinstance(h, LogHistogram)
    assert len(h.counts) == len(h.bounds) + 1
    assert s.timing_count("exec") == 10_000
    assert abs(s.timing_sum("exec") - 10.0) < 1e-6
    assert 0.0005 < s.timing_quantile("exec", 0.5) < 0.005


def test_prometheus_histogram_scrape_reparse():
    """Satellite: real `histogram` exposition — scrape the payload and
    re-parse the bucket series, _count/_sum, and the p99 exemplar."""
    import re
    from pilosa_tpu.obs import tracing as tr
    s = MemoryStats()
    for _ in range(200):
        s.timing("exec", 0.0002)
    tok = tr.set_current_trace("trace-slow-1")
    try:
        s.timing("exec", 2.0)     # slow observation carries the trace
    finally:
        tr.reset_current_trace(tok)
    text = prometheus_text(s)
    assert "# TYPE pilosa_exec_seconds histogram" in text
    bucket_re = re.compile(
        r'^pilosa_exec_seconds_bucket\{le="([^"]+)"\} (\d+)'
        r'(?: # \{trace_id="([^"]+)"\} ([0-9.eE+-]+))?$')
    buckets, exemplars = [], {}
    count = total_sum = None
    for line in text.splitlines():
        m = bucket_re.match(line)
        if m:
            buckets.append((m.group(1), int(m.group(2))))
            if m.group(3):
                exemplars[m.group(1)] = (m.group(3), float(m.group(4)))
        elif line.startswith("pilosa_exec_seconds_count "):
            count = int(line.split()[-1])
        elif line.startswith("pilosa_exec_seconds_sum "):
            total_sum = float(line.split()[-1])
    assert buckets and buckets[-1][0] == "+Inf"
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)              # cumulative and monotone
    assert count == 201 and buckets[-1][1] == count
    assert total_sum is not None
    assert abs(total_sum - (200 * 0.0002 + 2.0)) < 1e-9
    # the slow tail carries the exemplar, linked by trace id; the fast
    # (p50) bucket stays exemplar-free
    assert any(tid == "trace-slow-1" for tid, _ in exemplars.values())
    assert "0.0002" not in exemplars


def _free_ports(n):
    import socket
    ports = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    return ports


def test_cluster_profile_accounts_every_leg():
    """Acceptance: ?profile=true on a 3-node cluster returns a timeline
    whose per-peer wire bytes and decode ms sum to the coordinator's
    totals, every remote leg accounted exactly once, each carrying the
    peer's own nested ledger home in the frames header."""
    import json
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.server.node import ServerNode

    addrs = [f"127.0.0.1:{p}" for p in _free_ports(3)]
    nodes = [ServerNode(bind=a, peers=addrs, use_planner=False,
                        anti_entropy_interval=0.0,
                        check_nodes_interval=0.0,
                        qos_slow_query_ms=0.0) for a in addrs]
    for n in nodes:
        n.open()
    try:
        base = nodes[0].address

        def post(path, body=""):
            r = urllib.request.Request(base + path, data=body.encode(),
                                       method="POST")
            return json.loads(urllib.request.urlopen(r, timeout=10).read()
                              or b"{}")

        post("/index/p", "{}")
        post("/index/p/field/f", "{}")
        for s in range(8):
            post("/index/p/query", f"Set({s * SHARD_WIDTH}, f=1)")
        resp = post("/index/p/query?profile=true", "Count(Row(f=1))")
        assert resp["results"] == [8]
        prof = resp["profile"]
        legs = prof["remoteLegs"]
        tot = prof["remoteTotals"]
        # every remote peer appears EXACTLY once (no hedging configured)
        leg_nodes = [leg["node"] for leg in legs]
        assert len(leg_nodes) == len(set(leg_nodes))
        assert set(leg_nodes) <= {n.id for n in nodes[1:]}
        assert not any(leg["hedged"] for leg in legs)
        # the acceptance invariant: totals are the sums of the legs
        assert tot["legs"] == len(legs) >= 1
        assert tot["bytesOut"] == sum(leg["bytesOut"] for leg in legs)
        assert tot["bytesIn"] == sum(leg["bytesIn"] for leg in legs)
        assert abs(tot["decodeMs"]
                   - sum(leg["decodeMs"] for leg in legs)) < 0.01
        assert tot["hedgedLegs"] == 0 and tot["errorLegs"] == 0
        # each leg's nested remote ledger joined the coordinator's trace
        for leg in legs:
            rp = leg["remote"]
            assert rp["traceId"] == prof["traceId"]
            assert rp["node"] == leg["node"]
        # retention: addressable by trace id and listed slowest-first
        tid = prof["traceId"]
        got = json.loads(urllib.request.urlopen(
            base + f"/debug/queries/{tid}", timeout=10).read())
        assert got["remoteTotals"] == tot
        listing = json.loads(urllib.request.urlopen(
            base + "/debug/queries", timeout=10).read())
        assert any(d["traceId"] == tid for d in listing["queries"])
        # satellite: the slow-query log entry links to the profile
        slow = json.loads(urllib.request.urlopen(
            base + "/debug/slow-queries", timeout=10).read())
        entry = next(e for e in slow["queries"]
                     if e.get("traceId") == tid)
        assert entry["profile"] == f"/debug/queries/{tid}"
    finally:
        for n in nodes:
            try:
                n.close()
            except Exception:
                pass


def test_profile_off_bit_identical_and_allocation_free():
    """Satellite: with profiling fully off the query path constructs no
    QueryProfile at all (the ctor is boobytrapped for the duration) and
    answers bit-identically to a profiling node."""
    import json
    from pilosa_tpu.obs import profile as _profile
    from pilosa_tpu.server.node import ServerNode

    def run(node, trap=False):
        base = node.address

        def post(path, body=""):
            r = urllib.request.Request(base + path, data=body.encode(),
                                       method="POST")
            return json.loads(urllib.request.urlopen(r, timeout=10).read()
                              or b"{}")

        post("/index/q", "{}")
        post("/index/q/field/f", "{}")
        for c in (1, 2, 3, 70):
            post("/index/q/query", f"Set({c}, f=1)")
        orig = _profile.QueryProfile.__init__
        if trap:
            def boom(self, *a, **k):
                raise AssertionError("QueryProfile built on the off path")
            _profile.QueryProfile.__init__ = boom
        try:
            return post("/index/q/query", "Row(f=1)")
        finally:
            _profile.QueryProfile.__init__ = orig

    n_off = ServerNode(bind="127.0.0.1:0", use_planner=False,
                       profile_ring_n=0, profile_queries=False)
    n_off.open()
    try:
        off = run(n_off, trap=True)
    finally:
        n_off.close()
    n_on = ServerNode(bind="127.0.0.1:0", use_planner=False)
    n_on.open()
    try:
        on = run(n_on)
    finally:
        n_on.close()
    assert off == on
    assert "profile" not in off


def test_debug_device_route_and_dispatch_profile():
    """/debug/device gathers residency bytes, upload counters, and the
    batch/wave width histograms in one view; a profiled query on a
    planner node ledgers its device dispatches."""
    import json
    from pilosa_tpu.server.node import ServerNode

    n = ServerNode(bind="127.0.0.1:0")   # planner ON
    n.open()
    try:
        base = n.address

        def post(path, body=""):
            r = urllib.request.Request(base + path, data=body.encode(),
                                       method="POST")
            return json.loads(urllib.request.urlopen(r, timeout=30).read()
                              or b"{}")

        post("/index/dv", "{}")
        post("/index/dv/field/f", "{}")
        for c in range(64):
            post("/index/dv/query", f"Set({c}, f=1)")
        resp = post("/index/dv/query?profile=true", "Count(Row(f=1))")
        assert resp["results"] == [64]
        prof = resp["profile"]
        assert prof["dispatch"]["count"] >= 1
        assert len(prof["dispatch"]["widths"]) >= 1
        out = json.loads(urllib.request.urlopen(
            base + "/debug/device", timeout=10).read())
        assert out["enabled"]
        assert out["uploads"] >= 1 and out["upload_bytes"] > 0
        assert out["batch_width_hist"]["count"] >= 1
        assert "queue_depth" in out
        assert "wave_width_hist" in out["transfer"]
    finally:
        n.close()


# -- the span primitive (ISSUE 28) -------------------------------------------

#: the spans a warm, cached-path Count enters, with each one's parent.
_COUNT_PATH = {
    "http.request": None,
    "qos.admit": "http.request",
    "exec.parse": "http.request",
    "exec.cache": "http.request",
    "Executor.executeCount": "http.request",
    "plan.prepare": "Executor.executeCount",
    "stack.fetch": "Executor.executeCount",
    "dispatch.launch": "Executor.executeCount",
    "transfer.wait": "Executor.executeCount",
    "http.reply": "http.request",
}
#: of those, the ones that close while the request's QueryProfile is active.
_IN_PROFILE = set(_COUNT_PATH) - {"http.request", "http.reply"}
#: a wave its waiter steals is pulled on the request's thread: then, and
#: only then, the request has this span too (the resolver's pulls are not
#: the request's).
_STOLEN_PULL = {"transfer.pull": "transfer.wait"}


def _burn(n):
    x = 0
    for i in range(n):
        x += i * i
    return x


def test_span_nesting_parents_and_self_cpu(monkeypatch):
    """Nesting gives parent ids; a span's self CPU is its inclusive CPU
    less its children's, so the selves of a tree sum to the root's
    inclusive CPU."""
    monkeypatch.setattr(tracing, "CPU_SAMPLE_EVERY", 1)
    stats = MemoryStats()
    t = SimpleTracer()
    set_tracer(t)
    try:
        with start_span("t.root", stats=stats):
            _burn(60_000)
            with start_span("t.kid"):
                _burn(60_000)
                with start_span("t.leaf"):
                    _burn(60_000)
            with start_span("t.kid"):
                _burn(20_000)
    finally:
        set_tracer(NopTracer())
    by_op = {}
    for s in t.spans:
        by_op.setdefault(s.operation, []).append(s)
    root, = by_op["t.root"]
    assert root.parent_id is None
    assert [s.parent_id for s in by_op["t.kid"]] == [root.span_id] * 2
    assert by_op["t.leaf"][0].parent_id == by_op["t.kid"][0].span_id
    assert len({s.tags["trace.id"] for s in t.spans}) == 1

    def c(name, field):
        return stats.counter_value(f"span.{name}.{field}")

    assert c("t.root", "count") == 1 and c("t.kid", "count") == 2
    for name in ("t.root", "t.kid", "t.leaf"):
        assert 0 <= c(name, "selfCpuSeconds") <= c(name, "cpuSeconds")
        assert c(name, "cpuSeconds") <= c(name, "wallSeconds") + 1e-3
    assert c("t.leaf", "selfCpuSeconds") == c("t.leaf", "cpuSeconds")
    # children's inclusive CPU = the parent's inclusive less its own ...
    assert abs(c("t.kid", "cpuSeconds")
               - (c("t.root", "cpuSeconds") - c("t.root", "selfCpuSeconds"))
               ) < 1e-9
    # ... so the selves of the whole tree sum to the root's inclusive.
    selves = sum(c(n, "selfCpuSeconds") for n in ("t.root", "t.kid",
                                                  "t.leaf"))
    assert abs(selves - c("t.root", "cpuSeconds")) < 1e-9
    assert c("t.root", "selfCpuSeconds") > 0


def _span_node(**kw):
    """A planner node with two 2-row fields, warmed so that every stack
    is resident and every program compiled; (node, post, get)."""
    import json
    from pilosa_tpu.server.node import ServerNode

    n = ServerNode(bind="127.0.0.1:0", **kw)
    n.open()

    def post(path, body="", headers=None):
        r = urllib.request.Request(n.address + path, data=body.encode(),
                                   method="POST", headers=headers or {})
        return json.loads(urllib.request.urlopen(r, timeout=30).read()
                          or b"{}")

    def get(path):
        return json.loads(urllib.request.urlopen(
            n.address + path, timeout=10).read())

    post("/index/sp", "{}")
    for fld in ("f", "g"):
        post(f"/index/sp/field/{fld}", "{}")
        for c in range(8):
            post("/index/sp/query", f"Set({c}, {fld}={c % 2})")
    for r in (0, 1):
        post("/index/sp/query?noCache=true",
             f"Count(Intersect(Row(f={r}), Row(g={r})))")
    return n, post, get


def _span_counts(get, at_least=None, timeout=5.0):
    """span.<name>.count of /debug/vars; polls until ``at_least`` holds
    (a request thread folds its ledger after the response is written)."""
    import time
    deadline = time.monotonic() + timeout
    while True:
        counters = get("/debug/vars")["counters"]
        out = {k[len("span."):-len(".count")]: v for k, v in counters.items()
               if k.startswith("span.") and k.endswith(".count")}
        if at_least is None or all(out.get(k, 0) >= v
                                   for k, v in at_least.items()) \
                or time.monotonic() > deadline:
            return out
        time.sleep(0.01)


def _want(before, sent):
    return {k: before.get(k, 0) + sent for k in _COUNT_PATH}


def _check_counts(before, after, sent):
    """Every span of the path counted once a request; the two HTTP spans
    also count the /debug/vars reads between the two readings."""
    for k in _COUNT_PATH:
        moved = after.get(k, 0) - before.get(k, 0)
        if k.startswith("http."):
            assert moved >= sent + 1, (k, moved)
        else:
            assert moved == sent, (k, moved)
    assert after["http.request"] - before.get("http.request", 0) >= \
        after["http.reply"] - before.get("http.reply", 0)


def test_cpu_is_read_for_one_tree_in_n_and_scaled(monkeypatch):
    """The thread-CPU clock is read for one span tree in
    CPU_SAMPLE_EVERY (drawn at the outermost span; every span of the tree
    follows it) and counted that many times over, so the counters
    estimate the whole; wall time and counts are exact for every tree."""
    import time
    draws = iter([0.0, 0.9, 0.5, 0.26] * 5)      # every fourth tree
    monkeypatch.setattr(tracing, "CPU_SAMPLE_EVERY", 4)
    monkeypatch.setattr(tracing, "_draw", lambda: next(draws))
    stats = MemoryStats()
    reads = []
    real = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: reads.append(1) or real())
    c0 = real()
    for _ in range(20):
        with start_span("s.root", stats=stats):
            with start_span("s.kid"):
                _burn(20_000)
    burned = real() - c0
    monkeypatch.undo()
    assert len(reads) == 5 * 2 * 2      # 5 sampled trees x 2 spans x 2 ends
    assert stats.counter_value("span.s.root.count") == 20
    assert stats.counter_value("span.s.kid.count") == 20
    est = stats.counter_value("span.s.kid.cpuSeconds")
    assert 0.6 * burned < est < 1.4 * burned, (est, burned)
    assert stats.counter_value("span.s.root.cpuSeconds") >= est


def test_served_request_leaves_every_span_once(monkeypatch):
    """A Count through the HTTP handler: every span of its path once,
    under one (propagated) trace id, parents set, in the tracer; the same
    names as span.* counters whose counts equal the requests sent; those
    that close inside the profile in /debug/queries/<id>."""
    monkeypatch.setattr(tracing, "CPU_SAMPLE_EVERY", 1)
    n, post, get = _span_node()
    t = SimpleTracer()
    try:
        # the node's warm-up sent two Counts: wait for the second one's
        # ledger (a thread folds it after the response is written)
        before = _span_counts(get, at_least={"Executor.executeCount": 2})
        set_tracer(t)
        sent = 3
        for i in range(sent):
            resp = post("/index/sp/query",
                        f"Count(Union(Row(f={i % 2}), Row(g={i // 2})))",
                        headers={"X-Pilosa-Trace-Id": f"tr-obs-{i}"})
            assert resp["results"][0] >= 4  # distinct: none is a cache hit
        set_tracer(NopTracer())
        after = _span_counts(get, at_least=_want(before, sent))
        _check_counts(before, after, sent)
        for i in range(sent):
            mine = [s for s in t.spans
                    if s.tags.get("trace.id") == f"tr-obs-{i}"]
            stolen = {s.operation for s in mine} & set(_STOLEN_PULL)
            path = {**_COUNT_PATH,
                    **{k: v for k, v in _STOLEN_PULL.items() if k in stolen}}
            assert sorted(s.operation for s in mine) == sorted(path)
            by_op = {s.operation: s for s in mine}
            for op, parent in path.items():
                want_pid = by_op[parent].span_id if parent else None
                assert by_op[op].parent_id == want_pid, op
            doc = get(f"/debug/queries/tr-obs-{i}")
            assert set(doc["spans"]) == _IN_PROFILE | stolen
            assert all(e["count"] == 1 and e["selfCpuMs"] <= e["cpuMs"]
                       for e in doc["spans"].values())
            # the three phases the spans feed still appear, and the
            # launch is host time under its own name
            for phase, span in (("parseMs", "exec.parse"),
                                ("cacheLookupMs", "exec.cache"),
                                ("admissionWaitMs", "qos.admit")):
                assert abs(doc["timings"][phase]
                           - doc["spans"][span]["wallMs"]) < 1e-3
            assert abs(doc["dispatch"]["launchMs"]
                       - doc["spans"]["dispatch.launch"]["wallMs"]) < 1e-3
            assert "deviceMs" not in doc["dispatch"]
    finally:
        set_tracer(NopTracer())
        n.close()


def test_profile_off_counts_the_same_spans():
    """profile_ring_n=0, profile_queries=False: no QueryProfile is built
    (the ctor is boobytrapped) and the span counters move all the same."""
    from pilosa_tpu.obs import profile as _profile
    n, post, get = _span_node(profile_ring_n=0, profile_queries=False)
    orig = _profile.QueryProfile.__init__
    try:
        # the node's warm-up sent two Counts: wait for the second one's
        # ledger (a thread folds it after the response is written)
        before = _span_counts(get, at_least={"Executor.executeCount": 2})

        def boom(self, *a, **k):
            raise AssertionError("QueryProfile built on the off path")
        _profile.QueryProfile.__init__ = boom
        assert post("/index/sp/query",
                    "Count(Union(Row(f=0), Row(g=1)))")["results"] == [8]
        _profile.QueryProfile.__init__ = orig
        after = _span_counts(get, at_least=_want(before, 1))
        _check_counts(before, after, 1)
    finally:
        _profile.QueryProfile.__init__ = orig
        n.close()


def test_prefetch_worker_span_reaches_its_planners_registry_only():
    """stack.build/stack.upload run on a prefetch worker: the outermost
    span of that thread folds into ITS planner's registry, and a second
    planner in the process sees none of it."""
    import numpy as np
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    mesh = make_mesh()
    h = Holder()
    idx = h.create_index("pw")
    f = idx.create_field("f")
    f.import_bits(np.full(64, 1), np.arange(64) * (SHARD_WIDTH // 16))
    mine, other = MemoryStats(), MemoryStats()
    p_mine = MeshPlanner(h, mesh, stats=mine)
    p_other = MeshPlanner(h, mesh, stats=other)
    try:
        e = Executor(h, planner=p_mine, result_cache=False, stats=mine)
        assert e.execute("pw", "Count(Row(f=1))", shards=[0, 1, 2, 3]) == [64]
        dbg = p_mine.stacks.upload_stats()
        assert dbg["completed"] >= 1 and dbg["sync_misses"] == 0
        for name in ("stack.build", "stack.upload"):
            assert mine.counter_value(f"span.{name}.count") == \
                dbg["completed"], name
            assert mine.counter_value(f"span.{name}.wallSeconds") > 0
        # the request's thread waited for the upload in flight
        assert mine.counter_value("span.stack.wait.count") >= 1
        assert mine.counter_value("span.stack.fetch.wallSeconds") >= \
            mine.counter_value("span.stack.wait.wallSeconds")
        assert not [k for k in other.counters if k[0].startswith("span.")]
    finally:
        p_mine.close()
        p_other.close()


def test_device_trace_holds_the_spans_with_the_trace_id(tmp_path):
    """With a jax.profiler session open, the spans lie on the host plane
    of the .xplane.pb (the device trace's clock): dispatch.launch carries
    the request's trace_id, inside http.request on the same thread."""
    import glob
    import http.client
    import jax

    n, _, _ = _span_node()
    try:
        # One keep-alive connection is one handler thread: when the
        # second answer is here, the first request's http.request has
        # closed (it closes after its response is written, so a client
        # that only reads the first answer races stop_trace).
        conn = http.client.HTTPConnection(
            n.address.split("//")[1], timeout=30)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for tid, pql in (("tr-xplane-1",
                              "Count(Union(Row(f=1), Row(g=0)))"),
                             ("tr-xplane-2", "Count(Row(f=1))")):
                conn.request("POST", "/index/sp/query?noCache=true",
                             body=pql, headers={"X-Pilosa-Trace-Id": tid})
                assert conn.getresponse().read()
        finally:
            jax.profiler.stop_trace()
            conn.close()
    finally:
        n.close()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in _COUNT_PATH and \
                        dict(ev.stats).get("trace_id") == "tr-xplane-1":
                    found[ev.name] = (line.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
    assert set(found) >= {"http.request", "plan.prepare", "dispatch.launch",
                          "transfer.wait"}
    thread, lo, hi = found["http.request"]
    for name in ("plan.prepare", "dispatch.launch", "transfer.wait"):
        assert found[name][0] == thread
        assert lo <= found[name][1] and found[name][2] <= hi, name
