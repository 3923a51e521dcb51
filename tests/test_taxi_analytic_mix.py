"""The ``analytic-mix`` cell at a sandbox size, and the counters it reads.

``taxi-rides-1b-3f`` cut to three shards on the CPU backend, in a temporary
copy of ``benchmark/`` (the repository keeps one file a configuration and
no CPU twin beside it), under the cell's own mix and entries: the served
answers of every program structure meet the plain reference, the control
does not, and the per-layer metrics of the cell are on the line with the
counts the index's shape gives. Then the executor in process: what a
filtered ``TopN`` and a ``GroupBy`` add to the planner's counters, where
their spans hang, and which calls move ``executor.fallback.*``. Counts and
correctness only: no number here is a speed.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG = "taxi-rides-1b-3f"
SHARDS = 3
#: every metric the cell adds is on the traced line of a CPU run too
NEW_METRICS = {"topn_ms_per_call", "groupby_ms_per_call",
               "count_ms_per_call", "topn_sweep_ms_per_call",
               "topn_filter_ms_per_call", "topn_launches_per_call",
               "topn_host_tier_share", "groupby_launches_per_call",
               "class_fallbacks_per_request", "analytic_compiles_in_window"}
TOPN = "TopN(passenger_count, Row(pickup_year=1))"
GROUPBY = "GroupBy(Rows(pickup_year), Rows(passenger_count))"


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


def bench_json(name):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the entries: what three refusals turned on
# ---------------------------------------------------------------------------


def test_the_configuration_the_cell_and_the_metrics_are_entered():
    bench = bench_json("BENCHMARK.json")
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    assert config["reduced"] == ["int_fields", "time_views", "other_fields",
                                 "set_bits_per_ride"]
    stated = bench_json(config["file"])
    assert set(config["reduced"]) == set(stated["reduced"])
    assert stated["source"] == config["source"]
    assert len(config["source"]) <= 200
    assert stated["guarantees"] == bench_json(
        "benchmark/configs/star-trace-1b-16r.json")["guarantees"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == "analytic-mix"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "ranked-grouped", 1)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert declared[name]["workloads"] == ["analytic-mix"], name
        assert declared[name]["moves"] == "qps"
    # the cell reports qps, import_mbits and setup_s, which carry no
    # list; no accepted metric's list gained it (the ingest layer's,
    # entered later, list every cell: all three load through
    # import-roaring)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] not in NEW_METRICS and m.get("layer") != "ingest":
            assert "analytic-mix" not in m.get("workloads", []), m["name"]


def test_the_mix_holds_the_issues_parameters():
    mix = bench_json("benchmark/traffic/ranked-grouped.json")
    assert (mix["mode"], mix["clients"], mix["noCache"]) == \
        ("closed", 4, True)
    assert mix["row_draw"] == {"rule": "zipf", "exponent": 1.0,
                               "rank_order": "seeded"}
    assert isinstance(mix["population_seed"], int)
    assert 0.5 <= mix["trace_seconds"] <= 4
    assert [(t["share"], t["pql"]) for t in mix["templates"]] == [
        (0.3, "TopN(passenger_count, Row(pickup_year={y}))"),
        (0.2, "TopN(passenger_count, Row(cab_type={c}))"),
        (0.2, "GroupBy(Rows(pickup_year), Rows(passenger_count))"),
        (0.3, "Count({op}(Row(pickup_year={y}), Row(passenger_count={p})))")]
    assert mix["templates"][3]["draw"]["op"]["choice"] == [
        "Intersect", "Union", "Difference", "Xor"]


def test_the_configuration_keeps_the_stated_tiers():
    """Two rows of ``passenger_count`` above ``DENSE_CUTOFF`` bits a
    shard (the sweep's device tier), six below it (its host tier); 18
    rows in all, a tenth of a bit a ride and field."""
    from pilosa_tpu.config import DENSE_CUTOFF, SHARD_WIDTH

    config = bench_json(f"benchmark/configs/{CONFIG}.json")
    assert (config["shards"], config["shard_width_exp"]) == (954, 20)
    assert config["columns"] == 954 << 20
    fields = config["fields"]
    assert {f: spec["rows"] for f, spec in fields.items()} == \
        {"cab_type": 2, "passenger_count": 8, "pickup_year": 8}

    def densities(spec):
        dense = {int(r): d for r, d in spec.get("dense_rows", {}).items()}
        return [dense.get(r, spec["density"]) for r in range(spec["rows"])]

    held_dense = [r for r, d in enumerate(densities(fields["passenger_count"]))
                  if d * SHARD_WIDTH > DENSE_CUTOFF]
    assert held_dense == [1, 2]
    sums = {f: round(sum(densities(spec)), 3) for f, spec in fields.items()}
    assert sums == {"cab_type": 0.092, "passenger_count": 0.114,
                    "pickup_year": 0.1}


# ---------------------------------------------------------------------------
# the cell, cut to three shards, through the served path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cut_run(tmp_path_factory):
    """One traced run of the cell with the control, in process, so that
    the pairs ``judge`` compared can be looked at."""
    tmp = tmp_path_factory.mktemp("analytic-mix")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".tmp"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    for name in ("pilosa_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), tmp / name)
    path = tmp / "benchmark" / "configs" / (CONFIG + ".json")
    config = json.loads(path.read_text())
    config.update(platform="cpu", shards=SHARDS, columns=SHARDS << 20)
    path.write_text(json.dumps(config))

    spec = importlib.util.spec_from_file_location(
        "analytic_mix_bench_run", tmp / "benchmark" / "run.py")
    bench_run = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = bench_run  # its dataclass looks itself up
    spec.loader.exec_module(bench_run)
    judged = {}
    judge = bench_run.judge

    def recording_judge(pairs, expected, readback, dispatch_delta,
                        who="program"):
        judged[who] = (pairs, expected)
        return judge(pairs, expected, readback, dispatch_delta, who)

    bench_run.judge = recording_judge
    try:
        result = bench_run.run(argparse.Namespace(
            workload="analytic-mix", config="", traffic="", seed=3800000041,
            seconds=4.0, trace=1, control=True, keep_trace=""))
    finally:
        sys.path.remove(bench_run.HERE)
        del sys.modules[spec.name]
    return result, judged, config, bench_run


def test_the_cut_cell_is_correct_and_its_control_is_not(cut_run):
    result = cut_run[0]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"  # said, never hidden
    assert {"busy_s", "window_s"} <= set(result["device"])
    control = result["control"]
    assert control["correct"] is False
    assert control["checks"]["wrong_answers"]["value"] > 0
    assert control["checks"]["readback_uncached_gap"]["value"] == 1


def test_every_structure_of_the_mix_met_the_reference(cut_run):
    _, judged, _, bench_run = cut_run
    pairs, expected = judged["program"]
    templates = bench_run.traffic_mod.read_templates(bench_run.load_json(
        bench_run.HERE, "traffic", "ranked-grouped.json"))
    structures = bench_run.traffic_mod.structures(templates)
    assert len(structures) == 7
    for group in structures:
        mine = [(req, got) for req, got in pairs if req.group == group]
        assert mine, f"no request of structure {group} in the window"
        for req, got in mine:
            assert got is not None
            assert got == expected[(req.group, req.values)], req.pql
    # a ranked answer names every passenger count; a grouped one every pair
    topn = next(got for req, got in pairs if req.template == 0)
    assert len(topn) == 8
    grouped = next(got for req, got in pairs if req.template == 2)
    assert len(grouped) == 64


def test_the_new_metrics_are_on_the_line_with_the_shapes_counts(cut_run):
    result, _, config, _ = cut_run
    metrics = result["metrics"]
    assert {n for n in metrics if not n.startswith("import_")} == NEW_METRICS
    value = {name: m["value"] for name, m in metrics.items()}
    # passenger_count's eight dense stacks fit the planner's budget: one
    # program a pass counts all of them, in each of TopN's two passes,
    # whatever tier a fragment holds a row in; nothing is counted on the
    # host
    assert value["topn_launches_per_call"] == 2
    assert value["topn_host_tier_share"] == 0.0
    # the lattice: one AND a (year, passenger count) pair, one count a group
    years = config["fields"]["pickup_year"]["rows"]
    counts = config["fields"]["passenger_count"]["rows"]
    assert value["groupby_launches_per_call"] == 2 * years * counts
    # what the window timed was the planner's path
    assert value["class_fallbacks_per_request"] == 0
    # and nothing compiled inside it
    assert value["analytic_compiles_in_window"] == 0
    for name in NEW_METRICS - {"class_fallbacks_per_request",
                               "analytic_compiles_in_window",
                               "topn_host_tier_share"}:
        assert value[name] > 0, name
    assert value["topn_sweep_ms_per_call"] + \
        value["topn_filter_ms_per_call"] < value["topn_ms_per_call"]


# ---------------------------------------------------------------------------
# the readers on hand-made counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_finds_nothing_on_a_program_without_the_counters(name):
    """The parent's program has the executor's spans and lacks the new
    spans and counters: its line leaves those metrics out, and raises
    nothing. The one it can give (a call's own span) it gives."""
    old = {"span.Executor.executeTopN.count": 3,
           "span.Executor.executeTopN.wallSeconds": 6.0}
    ctx = {"counters0": {}, "counters1": old, "answered": 5,
           "device0": {}, "device1": {}}
    value = reader(name)(ctx)
    if name == "topn_ms_per_call":
        assert value == 2000.0
    else:
        assert value is None
    # no counter at all: nothing, and nothing raised
    assert reader(name)({"counters0": {}, "counters1": {}, "answered": 0,
                         "device0": None, "device1": None}) is None


def test_the_compile_counter_reads_the_windows_misses():
    """Requests less hits of ``/debug/device``'s ``compileCache``, over
    the window, as ``compiles_in_window`` reads it in the count cells."""
    read = reader("analytic_compiles_in_window")
    ctx = {"device0": {"compileCache": {"requests": 40, "hits": 9}},
           "device1": {"compileCache": {"requests": 47, "hits": 14}}}
    assert read(ctx) == 2.0
    assert read(dict(ctx, device1=ctx["device0"])) == 0.0
    assert read(ctx) == reader("compiles_in_window")(ctx)


def test_the_windows_first_requests_are_near_the_stated_shares():
    """A filtered TopN takes seconds, so a window holds the first eight
    to ten requests of each client's stream and no more: the population
    seed is one whose first draws hold the mix's shares (PERF.md Â§4 says
    how it was chosen), so that the cell sends what its ``why`` says."""
    sys.path.insert(0, BENCH)
    try:
        import traffic as traffic_mod
    finally:
        sys.path.remove(BENCH)
    mix = bench_json("benchmark/traffic/ranked-grouped.json")
    config = bench_json(f"benchmark/configs/{CONFIG}.json")
    templates = traffic_mod.read_templates(mix)
    picker = traffic_mod.RowPicker(mix, config["fields"], 1)
    for per_client in (8, 9, 10):
        sent = [r.template for c in range(mix["clients"])
                for r in traffic_mod.draw_stream(
                    templates, picker, config["columns"],
                    mix["population_seed"], c, per_client)]
        shares = [sent.count(t) / len(sent) for t in range(4)]
        for got, want in zip(shares, (0.3, 0.2, 0.2, 0.3)):
            assert abs(got - want) <= 0.08, (per_client, shares)


# ---------------------------------------------------------------------------
# the executor in process: counters, spans, fallbacks
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """Two shards, ``passenger_count`` with one row held dense and three
    as positions, three even years; one filtered ``TopN`` and one
    ``GroupBy`` through an executor with a planner, under a recording
    tracer."""
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import MemoryStats, tracing
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    shards, n_counts, n_years = 2, 4, 3
    rng = np.random.default_rng(38)
    h = Holder()
    idx = h.create_index("t")
    pc = idx.create_field("passenger_count")
    py = idx.create_field("pickup_year")
    rows, cols = [], []
    for shard in range(shards):
        base = shard * SHARD_WIDTH
        for r in range(n_counts):
            # row 1 is held dense (20,000 bits a shard), the others are
            # position arrays of 300
            n = 20000 if r == 1 else 300
            rows.append(np.full(n, r))
            cols.append(base + rng.choice(SHARD_WIDTH, n, replace=False))
    pc.import_bits(np.concatenate(rows), np.concatenate(cols))
    year_cols = np.arange(shards * SHARD_WIDTH)
    py.import_bits(year_cols % n_years, year_cols)

    stats = MemoryStats()
    planner = MeshPlanner(h, make_mesh(), stats=stats)
    tracer = tracing.SimpleTracer()
    before = tracing.get_tracer()
    tracing.set_tracer(tracer)
    try:
        fast = Executor(h, planner=planner, result_cache=False, stats=stats)
        published = dict(stats.counters)
        (ranked,) = fast.execute("t", TOPN)
        (grouped,) = fast.execute("t", GROUPBY)
        after_mix = dict(stats.counters)
        yield {"h": h, "fast": fast, "stats": stats, "tracer": tracer,
               "ranked": ranked, "grouped": grouped, "shape":
               (shards, n_counts, n_years), "published": published,
               "after_mix": after_mix, "planner": planner}
    finally:
        tracing.set_tracer(before)
        planner.close()


def test_the_planners_answers_are_the_interpreters(served):
    from pilosa_tpu.exec import Executor

    _, n_counts, n_years = served["shape"]
    plain = Executor(served["h"])
    assert served["ranked"] == plain.execute("t", TOPN)[0]
    assert served["grouped"] == plain.execute("t", GROUPBY)[0]
    assert len(served["ranked"]) == n_counts
    assert len(served["grouped"]) == n_years * n_counts


@pytest.mark.parametrize("name, amount", [
    # two passes, one program each over the four rows' dense stacks:
    # every (row, fragment) pair counted on the device, none on the host
    ("planner.topn.launches", lambda s, c, y: 2),
    ("planner.topn.rowsDeviceTier", lambda s, c, y: 2 * s * c),
    ("planner.topn.rowsHostTier", lambda s, c, y: 0),
    ("planner.topn.passesStacked", lambda s, c, y: 2),
    ("planner.topn.passesSwept", lambda s, c, y: 0),
    # one AND a pair below the first level, one count a group
    ("planner.groupby.launches", lambda s, c, y: 2 * y * c),
    ("planner.groupby.groups", lambda s, c, y: y * c),
    # one span a pass; the lattice once
    ("span.topn.filter.count", lambda s, c, y: 2),
    ("span.topn.sweep.count", lambda s, c, y: 2),
    ("span.groupby.lattice.count", lambda s, c, y: 1),
    # the mix's calls stay on the planner's path
    ("executor.fallback.topn", lambda s, c, y: 0),
    ("executor.fallback.groupby", lambda s, c, y: 0),
])
def test_topn_and_groupby_move_a_counter_by_the_shapes_amount(
        served, name, amount):
    assert (name, ()) in served["after_mix"], name
    assert served["after_mix"][(name, ())] == amount(*served["shape"])


def test_the_fallback_counters_are_published_before_any_call(served):
    assert served["published"][("executor.fallback.topn", ())] == 0
    assert served["published"][("executor.fallback.groupby", ())] == 0
    # every launch of TopN's passes and the lattice is a dispatch as before
    _, n_counts, n_years = served["shape"]
    assert served["after_mix"][("planner.dispatchCount", ())] >= \
        2 + 2 * n_years * n_counts


@pytest.mark.parametrize("span, call", [
    ("topn.filter", "Executor.executeTopN"),
    ("topn.sweep", "Executor.executeTopN"),
    ("groupby.lattice", "Executor.executeGroupBy"),
])
def test_a_span_hangs_under_its_call(served, span, call):
    by_id = {s.span_id: s for s in served["tracer"].spans}

    def ancestors(s):
        while s.parent_id is not None:
            s = by_id[s.parent_id]
            yield s.operation

    spans = [s for s in served["tracer"].spans if s.operation == span]
    assert spans, span
    for s in spans:
        assert call in ancestors(s), (span, list(ancestors(s)))


@pytest.mark.parametrize("pql, counter", [
    # similarity needs each shard's own source count: per-shard path
    ("TopN(passenger_count, Row(pickup_year=1), tanimotoThreshold=50)",
     "executor.fallback.topn"),
    # a limited level is resolved first, and the DFS keeps its cursor
    ("GroupBy(Rows(pickup_year, limit=2), Rows(passenger_count))",
     "executor.fallback.groupby"),
])
def test_a_call_the_planner_cannot_take_moves_its_fallback_counter(
        served, pql, counter):
    from pilosa_tpu.exec import Executor

    value = served["stats"].counter_value
    before = {n: value(n) for n in ("executor.fallback.topn",
                                    "executor.fallback.groupby",
                                    "planner.topn.launches",
                                    "planner.groupby.launches")}
    (got,) = served["fast"].execute("t", pql)
    assert got == Executor(served["h"]).execute("t", pql)[0]
    for name, was in before.items():
        assert value(name) == was + (1 if name == counter else 0), name


def test_a_filterless_topn_opens_no_sweep(served):
    """It reads each fragment's cached counts in the same loop: no filter
    to compile, nothing launched, no fallback; its answer the
    interpreter's."""
    from pilosa_tpu.exec import Executor

    value = served["stats"].counter_value
    names = ("span.topn.filter.count", "span.topn.sweep.count",
             "planner.topn.launches", "planner.topn.rowsHostTier",
             "executor.fallback.topn")
    before = {n: value(n) for n in names}
    (got,) = served["fast"].execute("t", "TopN(passenger_count)")
    assert got == Executor(served["h"]).execute(
        "t", "TopN(passenger_count)")[0]
    assert {n: value(n) for n in names} == before


def test_an_executor_without_a_planner_counts_no_fallback():
    """Host-only mode has no planner's path to leave."""
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import MemoryStats

    h = Holder()
    idx = h.create_index("t")
    idx.create_field("f").import_bits(np.array([0, 1]), np.array([1, 2]))
    stats = MemoryStats()
    ex = Executor(h, stats=stats)
    assert ex.execute("t", "TopN(f, Row(f=0), tanimotoThreshold=50)")
    assert ex.execute("t", "GroupBy(Rows(f, limit=1))")
    assert not [k for k in stats.counters if k[0].startswith(
        "executor.fallback.")]


def test_the_ingest_metrics_read_the_load(cut_run):
    """The load's totals at the window's start, by ``import.bits``: one
    WAL record a fragment of 16 bytes a bit and a 15-byte header, and
    decode, merge and WAL inside the route's span. The route's CPU is
    read for one request in 16, which nine requests may all miss."""
    result, _, _, _ = cut_run
    value = {name: m["value"] for name, m in result["metrics"].items()}
    for name in ("import_handler_ns_per_bit", "import_decode_ns_per_bit",
                 "import_merge_ns_per_bit", "import_wal_ns_per_bit"):
        assert value[name] > 0, name
    assert 16.0 < value["import_wal_bytes_per_bit"] < 16.01
    assert value["import_decode_ns_per_bit"] + \
        value["import_merge_ns_per_bit"] + value["import_wal_ns_per_bit"] \
        <= value["import_handler_ns_per_bit"]
