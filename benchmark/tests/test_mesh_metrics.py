"""The readers of the four-chip cell's metrics: ``mesh_upload_mb_per_request``,
``mesh_pull_ms_per_request`` and ``mesh_count_hbm_roofline``, each on a
hand-made ``ctx``: the value, None where the program lacks its counter or
the trace its planes, and the roofline's guard above 105 %."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAMES = ("mesh_upload_mb_per_request", "mesh_pull_ms_per_request",
         "mesh_count_hbm_roofline")
CELL = "count-trees-mesh4"


def read(name, ctx):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _peaks():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


def make_ctx(answered=200, without=(), busy_s=0.05, planes=4, chips=4,
             n_trace=100):
    counters = {"span.transfer.pull.wallSeconds": (1.5, 1.9)}
    device = {"uploads": (195, 195), "upload_bytes": (3.0e9, 3.0e9 + 64e6)}
    keep = {k: v for k, v in counters.items() if k not in without}
    held = {k: v for k, v in device.items() if k not in without}
    return {"answered": answered,
            "counters0": {k: v[0] for k, v in keep.items()},
            "counters1": {k: v[1] for k, v in keep.items()},
            "device0": {k: v[0] for k, v in held.items()},
            "device1": {k: v[1] for k, v in held.items()},
            "config": {"shard_width_exp": 20, "shards": 954,
                       "chips": chips},
            "peaks": _peaks(),
            "trace": {"busy_s": busy_s, "window_s": 4.0,
                      "device_planes": planes},
            "trace_requests":
                ["Count(Xor(Row(f=1), Row(g=2)))"] * n_trace}


def test_every_reader_is_declared_for_the_four_chip_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 4
    for name in NAMES:
        m = declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "qps"
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_the_four_chip_mix_is_count_trees_with_a_shorter_trace():
    """The same requests from the same population seed; only the traced
    span differs (four device planes make its reduction four times the
    work of one)."""
    def mix(name):
        with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
            return json.load(f)

    ours, theirs = mix("count-trees-4chip"), mix("count-trees")
    assert {k for k in set(ours) | set(theirs)
            if ours.get(k) != theirs.get(k)} == \
        {"name", "why", "trace_seconds"}
    assert ours["trace_seconds"] < theirs["trace_seconds"]


def test_upload_megabytes_per_request():
    assert read("mesh_upload_mb_per_request", make_ctx()) == \
        pytest.approx(64.0 / 200)


def test_pull_milliseconds_per_request():
    assert read("mesh_pull_ms_per_request", make_ctx()) == \
        pytest.approx(0.4 * 1e3 / 200)


def test_roofline_over_the_chips_device_seconds():
    need = 100 * 2 * 954 * 131072
    assert read("mesh_count_hbm_roofline", make_ctx()) == \
        pytest.approx(100.0 * need / (4 * 819e9 * 0.05))


@pytest.mark.parametrize("name, counter", [
    ("mesh_upload_mb_per_request", "upload_bytes"),
    ("mesh_pull_ms_per_request", "span.transfer.pull.wallSeconds")])
def test_none_on_a_program_without_the_counter(name, counter):
    """A program without the counter leaves the metric out; it does not
    raise."""
    assert read(name, make_ctx(without={counter})) is None
    assert read(name, make_ctx(answered=0)) is None


@pytest.mark.parametrize("planes, chips", [(1, 4), (4, 1), (0, 4)])
def test_roofline_none_unless_a_plane_a_chip(planes, chips):
    assert read("mesh_count_hbm_roofline",
                make_ctx(planes=planes, chips=chips)) is None


def test_roofline_none_without_a_trace_or_requests():
    assert read("mesh_count_hbm_roofline",
                dict(make_ctx(), trace=None)) is None
    assert read("mesh_count_hbm_roofline", make_ctx(busy_s=0.0)) is None
    assert read("mesh_count_hbm_roofline", make_ctx(n_trace=0)) is None


def test_roofline_raises_above_105_percent():
    # 100 two-leaf requests need 25 GB: over four chips in 0.005 s busy
    with pytest.raises(ValueError, match="counted too high"):
        read("mesh_count_hbm_roofline", make_ctx(busy_s=0.005))
    with pytest.raises(ValueError, match="no peaks"):
        read("mesh_count_hbm_roofline", dict(make_ctx(), peaks=None))
