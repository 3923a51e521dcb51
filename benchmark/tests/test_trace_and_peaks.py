"""The reduction from a trace to busy time, idle share and names, on
synthetic intervals and on a small trace recorded on the chip; the roofline
reader's guard; the table of peaks."""

import importlib.util
import json
import os

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_busy_is_the_union_and_idle_the_rest():
    ops = {"/device:TPU:0": [("a", 1.0, 2.0), ("b", 1.5, 2.5),
                             ("a", 4.0, 4.5), ("c", 9.5, 12.0)]}
    host = [("wide", 0.0, 10.0), ("launch", 2.5, 4.0), ("tiny", 3.0, 3.1)]
    out = tr.reduce_events(ops, {}, host, 0.0, 10.0, 1)
    assert out["busy_s"] == pytest.approx(1.5 + 0.5 + 0.5)
    assert out["idle_share"] == pytest.approx(0.75)
    assert out["window_s"] == 10.0
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"a": 1.5, "b": 1.0, "c": 0.5})
    gaps = dict(map(tuple, out["idle_gaps"]))
    # the 2.5-4.0 gap is named after the event that just covers it, the
    # others after the only event that covers them
    assert gaps["launch"] == pytest.approx(1.5)
    assert gaps["wide"] == pytest.approx(1.0 + 5.0)
    assert sum(gaps.values()) == pytest.approx(7.5)


def test_modules_name_the_operations_where_the_trace_has_them():
    ops = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("copy.2", 1.0, 2.0)]}
    modules = {"/device:TPU:0": [("jit_program", 0.0, 2.0)]}
    out = tr.reduce_events(ops, modules, [], 0.0, 4.0, 1)
    assert out["device_ops"] == [["jit_program", 2.0]]
    assert out["idle_gaps"] == [["untraced_host", 2.0]]


def test_busy_is_averaged_over_the_chips():
    ops = {"/device:TPU:0": [("a", 0.0, 2.0)], "/device:TPU:1": []}
    out = tr.reduce_events(ops, {}, [], 0.0, 4.0, 2)
    assert out["busy_s"] == pytest.approx(1.0)


def test_an_empty_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": []}, {}, [], 1.0, 1.0, 1)


def _ctx(busy_s: float, n: int) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    return {"config": {"shard_width_exp": 20, "shards": 954},
            "peaks": peaks["TPU v5 lite"],
            "trace": {"busy_s": busy_s, "idle_share": 0.9, "window_s": 4.0},
            "trace_requests": ["Count(Intersect(Row(f=1), Row(g=2)))"] * n}


def test_roofline_share_and_its_guard():
    read = reader("count_hbm_roofline")
    need = 100 * 2 * 954 * 131072
    assert read(_ctx(0.1, 100)) == pytest.approx(
        100.0 * need / 819e9 / 0.1)
    # nothing to read: nothing returned, never a 0
    assert read(dict(_ctx(0.1, 100), trace=None)) is None
    assert read(_ctx(0.1, 0)) is None
    # over 105 %: the count or the time is wrong; no min(..., 100)
    with pytest.raises(ValueError, match="counted too high"):
        read(_ctx(0.02, 100))
    assert reader("device_idle")(_ctx(0.1, 1)) == pytest.approx(90.0)
    assert reader("device_idle")(dict(_ctx(0.1, 1), trace=None)) is None


def test_an_unknown_device_kind_is_an_error():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v9 imaginary" not in peaks
    with pytest.raises(ValueError, match="no peaks"):
        reader("count_hbm_roofline")(dict(_ctx(0.1, 10), peaks=None))


RECORDED = os.path.join(BENCH, "tests", "data", "resident_0.25s.xplane.pb")


def test_reduction_on_the_recorded_trace():
    """The first quarter second of a traced span of count-trees-resident
    on the chip (TPU v5 lite, PR 27, seed 202), cut down to 41 KB. One
    core runs one operation at a time, so busy is the plain sum of the
    ``XLA Ops`` durations; each fused count takes 178 us a leaf."""
    from jax.profiler import ProfileData

    durations = []
    for plane in ProfileData.from_file(RECORDED).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    durations += [ev.duration_ns for ev in line.events]
    assert len(durations) == 21
    assert all(round(d / 178e3, 2) in (1.0, 2.0, 3.0) for d in durations)

    ops, modules, host, t_min, t_max = tr.load(RECORDED)
    assert list(ops) == ["/device:TPU:0"] and len(host) > 100
    out = tr.reduce_events(ops, modules, host, t_min, t_max, 1)
    assert out["busy_s"] == pytest.approx(sum(durations) * 1e-9, rel=1e-9)
    assert out["window_s"] == pytest.approx(t_max - t_min)
    assert out["idle_share"] == pytest.approx(
        1 - sum(durations) * 1e-9 / (t_max - t_min))
    assert 0.9 < out["idle_share"] < 0.95
    assert all(name.startswith("jit_program(")
               for name, _ in out["device_ops"])
    assert sum(s for _, s in out["device_ops"]) == pytest.approx(
        out["busy_s"], rel=1e-3)
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert "np.asarray(jax.Array)" in gaps
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
