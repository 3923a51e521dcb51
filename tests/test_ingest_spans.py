"""The ingest path's spans and counters, and the benchmark's readers of
them: one ``import-roaring`` request to an in-process node with a data
dir moves ``import.roaring``, ``import.decode``, ``import.merge`` and
``wal.append`` once each, ``import.bits`` by the positions it carried and
``wal.bytes`` by the record it wrote; a read opens none of them; and the
six ``benchmark/layer_metrics/import_*`` readers divide the load's
totals by ``import.bits``."""

import importlib.util
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import roaring
from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.obs import SimpleTracer, set_tracer
from pilosa_tpu.obs.tracing import NopTracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

INGEST = ("import.roaring", "import.decode", "import.merge", "wal.append")
#: a WAL record: magic u16, op u8, n_rows u32, n_cols u32, crc32 u32.
WAL_HEADER = 15


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """A planner node with a data dir (so a WAL) and two 2-row fields;
    (node, post, counters)."""
    from pilosa_tpu.server.node import ServerNode

    n = ServerNode(bind="127.0.0.1:0",
                   data_dir=str(tmp_path_factory.mktemp("ingest-spans")))
    n.open()

    def post(path, body=b"", headers=None):
        r = urllib.request.Request(n.address + path, data=body,
                                   method="POST", headers=headers or {})
        return json.loads(urllib.request.urlopen(r, timeout=30).read()
                          or b"{}")

    def counters():
        return json.loads(urllib.request.urlopen(
            n.address + "/debug/vars", timeout=10).read())["counters"]

    post("/index/sp", b"{}")
    for fld in ("f", "g"):
        post(f"/index/sp/field/{fld}", b"{}")
        for c in range(8):
            post("/index/sp/query", f"Set({c}, {fld}={c % 2})".encode())
    try:
        yield n, post, counters
    finally:
        n.close()


def _settled(counters, key, want, timeout=5.0):
    """The counters once ``key`` reads ``want`` (a request thread folds
    its spans after the response is written); as they stand at the
    deadline otherwise."""
    deadline = time.monotonic() + timeout
    while True:
        c = counters()
        if c.get(key, 0) >= want or time.monotonic() > deadline:
            return c
        time.sleep(0.01)


def _import(post, field, shard, positions, trace_id=None):
    headers = {"X-Pilosa-Trace-Id": trace_id} if trace_id else None
    post(f"/index/sp/field/{field}/import-roaring/{shard}",
         roaring.encode(np.asarray(positions, dtype=np.uint64)), headers)


def test_one_import_opens_each_ingest_span_once_under_the_route(node):
    """The three inner spans are siblings under ``import.roaring`` (so
    decode + merge + WAL never exceed the handler), itself a child of
    ``http.request``, and each counts once a request."""
    _n, post, counters = node
    before = counters()
    t = SimpleTracer()
    set_tracer(t)
    try:
        _import(post, "f", 1, [3, 7, SHARD_WIDTH + 5], trace_id="tr-ing-1")
    finally:
        set_tracer(NopTracer())
    key = "span.import.roaring.count"
    after = _settled(counters, key, before.get(key, 0) + 1)
    for name in INGEST:
        k = f"span.{name}.count"
        assert after.get(k, 0) - before.get(k, 0) == 1, name
        assert after[f"span.{name}.wallSeconds"] > \
            before.get(f"span.{name}.wallSeconds", 0), name
    mine = {s.operation: s for s in t.spans
            if s.tags.get("trace.id") == "tr-ing-1"}
    assert set(mine) == {"http.request", "http.reply", *INGEST}
    route = mine["import.roaring"]
    assert route.parent_id == mine["http.request"].span_id
    for name in INGEST[1:]:
        assert mine[name].parent_id == route.span_id, name
    inner = sum(mine[name].duration for name in INGEST[1:])
    assert inner <= route.duration


@pytest.mark.parametrize("n_bits", [1, 300, 5000])
def test_an_import_counts_its_bits_and_its_wal_record(node, n_bits):
    """``import.bits`` moves by the body's positions, ``wal.bytes`` by
    one record: a 15-byte header and a row id and a column id of 8 bytes
    each a bit."""
    _n, post, counters = node
    rng = np.random.default_rng(n_bits)
    positions = np.sort(rng.choice(2 * SHARD_WIDTH, n_bits, replace=False))
    before = counters()
    _import(post, "g", 2, positions)
    key = "span.import.roaring.count"
    after = _settled(counters, key, before.get(key, 0) + 1)
    assert after["import.bits"] - before.get("import.bits", 0) == n_bits
    assert after["wal.bytes"] - before.get("wal.bytes", 0) == \
        WAL_HEADER + 16 * n_bits


@pytest.mark.parametrize("pql", [
    "Count(Intersect(Row(f=0), Row(g=0)))",
    "TopN(f, Row(g=1), n=2)",
])
def test_a_read_opens_no_ingest_span(node, pql):
    """A read moves none of the four ingest spans nor the two counters,
    and the spans under its ``http.request`` are the read path's as they
    were: the read path's metrics keep their meaning."""
    _n, post, counters = node
    read_path = {
        "Count": {"http.request", "qos.admit", "exec.parse",
                  "Executor.executeCount", "plan.prepare", "stack.fetch",
                  "dispatch.launch", "transfer.wait", "http.reply"},
        "TopN": {"http.request", "qos.admit", "exec.parse",
                 "Executor.executeTopN", "topn.filter", "topn.sweep",
                 "plan.prepare", "stack.fetch", "dispatch.launch",
                 "transfer.wait", "http.reply"},
    }[pql.split("(")[0]]
    post("/index/sp/query?noCache=true", pql.encode())  # stacks resident
    before = counters()
    t = SimpleTracer()
    set_tracer(t)
    try:
        post("/index/sp/query?noCache=true", pql.encode(),
             {"X-Pilosa-Trace-Id": "tr-read"})
    finally:
        set_tracer(NopTracer())
    after = _settled(counters, "span.http.reply.count",
                     before.get("span.http.reply.count", 0) + 1)
    ops = {s.operation for s in t.spans
           if s.tags.get("trace.id") == "tr-read"}
    assert ops == read_path
    for k in [f"span.{n}.count" for n in INGEST] + ["import.bits",
                                                    "wal.bytes"]:
        assert after.get(k, 0) == before.get(k, 0), k


# ---------------------------------------------------------------------------
# the readers on hand-made counters
# ---------------------------------------------------------------------------


def reader(name):
    """A reader of ``benchmark/layer_metrics``, imported as ``run.py``
    imports it (``benchmark/`` on the path while it loads)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reader_" + name,
            os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
    return mod.read


#: the load's totals at the window's start: 2,000,000 bits.
LOAD = {
    "import.bits": 2_000_000,
    "wal.bytes": 32_000_150,
    "span.import.roaring.wallSeconds": 4.0,
    "span.import.roaring.cpuSeconds": 0.6,
    "span.import.decode.wallSeconds": 0.1,
    "span.import.merge.wallSeconds": 0.3,
    "span.wal.append.wallSeconds": 0.2,
    "span.http.request.wallSeconds": 9.0,
}

#: metric -> (value on LOAD, the counter it cannot be read without)
METRICS = {
    "import_handler_ns_per_bit": (2000.0, "span.import.roaring.wallSeconds"),
    "import_handler_cpu_ns_per_bit": (300.0, "span.import.roaring.cpuSeconds"),
    "import_decode_ns_per_bit": (50.0, "span.import.decode.wallSeconds"),
    "import_merge_ns_per_bit": (150.0, "span.import.merge.wallSeconds"),
    "import_wal_ns_per_bit": (100.0, "span.wal.append.wallSeconds"),
    "import_wal_bytes_per_bit": (16.000075, "wal.bytes"),
}


def _ctx(c0, moved=None):
    """The window's ctx: counters1 = counters0, plus what it moved."""
    c1 = dict(c0)
    c1.update({"span.http.request.wallSeconds": 12.0, **(moved or {})})
    return {"counters0": c0, "counters1": c1, "answered": 100,
            "device0": {}, "device1": {}}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_reader_divides_the_loads_totals_by_its_bits(name):
    """Totals from boot at the window's start, whatever the window did
    to other counters."""
    want, _ = METRICS[name]
    assert reader(name)(_ctx(dict(LOAD))) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_reader_finds_nothing_on_a_program_without_the_counters(name):
    """The parent's program lacks the spans and counters: its line
    leaves the metric out, and raises nothing; the same where the load
    decoded no bit."""
    _, needs = METRICS[name]
    read = reader(name)
    assert read(_ctx({})) is None
    assert read(_ctx({k: v for k, v in LOAD.items() if k != needs})) is None
    assert read(_ctx({k: v for k, v in LOAD.items()
                      if k != "import.bits"})) is None
    assert read(_ctx(dict(LOAD, **{"import.bits": 0}))) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_reader_finds_nothing_once_the_window_imported(name):
    """A window that moved ``import.bits`` imported: the totals would no
    longer be the load's."""
    moved = {"import.bits": LOAD["import.bits"] + 1}
    assert reader(name)(_ctx(dict(LOAD), moved)) is None
