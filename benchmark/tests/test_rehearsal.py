"""The whole run on the CPU backend: the rehearsal configuration (4 shards,
in no cell), the control, the planted faults, and a cell added with new
files only. None of these is a measurement; they prove the flow and that
``correct`` can fail."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FAULTY = os.path.join(BENCH, "tests", "faulty_server.py")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cli(root: str, *argv: str) -> tuple[int, dict | None, str]:
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, BENCH_RUN="ignored"))
    lines = out.stdout.strip().splitlines()
    assert len(lines) <= 1, "standard output holds the result line alone"
    return out.returncode, json.loads(lines[0]) if lines else None, out.stderr


@pytest.fixture(scope="module")
def rehearsal():
    rc, last, err = run_cli(ROOT, "--config", "rehearsal-4s", "--traffic",
                            "count-trees", "--seed", "3000000019",
                            "--seconds", "2", "--trace", "0", "--control")
    assert rc == 0, err[-3000:]
    return last, err


def test_rehearsal_line_is_well_formed_and_correct(rehearsal):
    last, err = rehearsal
    assert list(last)[:5] == KEYS and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"qps", "p50_ms", "p95_ms",
                                    "import_mbits", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["device"]["platform"] == "cpu"  # said, never hidden
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    # each number compared stands beside its limit at the end of stderr
    tail = err.strip().splitlines()[-len(last["checks"]):]
    assert all(line.startswith("check ") and "limit" in line
               for line in tail)


def test_control_comes_out_not_correct(rehearsal):
    """The reference with a guarantee broken (one shard left out of every
    answer; the write served stale), put in the program's place."""
    control = rehearsal[0]["control"]
    assert control["correct"] is False
    assert control["checks"]["wrong_answers"]["value"] > 0
    assert control["checks"]["readback_uncached_gap"]["value"] == 1
    assert control["checks"]["readback_cached_gap"]["value"] == 1


@pytest.mark.parametrize("fault, failing", [
    ("answer", "wrong_answers"),
    ("write", "readback_uncached_gap"),
])
def test_a_fault_in_the_timed_path_fails_correct(fault, failing, monkeypatch):
    """Skips nothing but the look for a chip (the rehearsal configuration
    states the CPU), and drives the rest of a run with the served path
    broken underneath: an answer altered where the API produces it; a Set
    acknowledged and not applied."""
    sys.path.insert(0, BENCH)
    import run as bench_run

    monkeypatch.setenv("BENCH_FAULT", fault)
    args = argparse.Namespace(
        workload="", config="rehearsal-4s", traffic="count-trees",
        seed=77, seconds=2.0, trace=0, control=False, keep_trace="")
    result = bench_run.run(args, launcher=[FAULTY])
    assert result["correct"] is False
    assert result["checks"][failing]["value"] > 0


def test_tpu_configuration_refuses_the_cpu(tmp_path):
    """A cell's configuration states ``tpu``: on the CPU backend the run
    exits non-zero and prints no result line."""
    rc, last, err = run_cli(ROOT, "--workload", "count-trees-resident",
                            "--seed", "1", "--seconds", "1", "--trace", "0")
    assert rc != 0 and last is None
    assert "planner is on" in err or "server exited" in err


def test_bare_benchmark_directory_fails(tmp_path):
    """Only BENCHMARK.json and benchmark/: no program, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".tmp"))
    rc, last, err = run_cli(str(tmp_path), "--config", "rehearsal-4s",
                            "--traffic", "count-trees", "--seed", "1",
                            "--seconds", "1", "--trace", "0")
    assert rc != 0 and last is None
    assert "not in this checkout" in err


def test_a_cell_is_added_with_new_files_only(tmp_path):
    """A throw-away configuration, mix, per-layer metric and cell: new
    files and new entries, no edit to a file that is there. The mix holds
    TopN and GroupBy, so their reference meets the served answers too."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".tmp"))
    for name in ("pilosa_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    with open(os.path.join(BENCH, "configs", "rehearsal-4s.json")) as f:
        config = json.load(f)
    config.update(name="throwaway", shards=2, columns=2 << 20,
                  fields={"f": {"rows": 5, "density": 0.02},
                          "g": {"rows": 3, "density": 0.03}})
    mix = {"mode": "closed", "clients": 4, "noCache": True,
           "stream_length": 256, "warmup_rounds": 1, "warmup_seconds": 1,
           "trace_seconds": 1,
           "row_draw": {"rule": "zipf", "exponent": 1.0},
           "templates": [
               {"share": 0.4, "pql": "TopN(f, Row(g={b}), n=3)",
                "draw": {"b": {"row": "g"}}},
               {"share": 0.2, "pql": "GroupBy(Rows(f), Rows(g))"},
               {"share": 0.4, "pql": "Count({op}(Row(f={a}), Row(g={b})))",
                "draw": {"op": {"choice": ["Intersect", "Xor"]},
                         "a": {"row": "f"}, "b": {"row": "g"}}}]}
    reader = ('def read(ctx):\n'
              '    return float(ctx["answered"])\n')
    (tmp_path / "benchmark/configs/throwaway.json").write_text(
        json.dumps(config))
    (tmp_path / "benchmark/traffic/throwaway-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/layer_metrics/answered_requests.py").write_text(
        reader)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "throwaway", "source": "test",
                             "file": "benchmark/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway-cell",
                               "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "answered_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "dispatch", "moves": "qps",
        "workloads": ["throwaway-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, last, err = run_cli(str(tmp_path), "--workload", "throwaway-cell",
                            "--seed", "5", "--seconds", "2", "--trace", "1")
    assert rc == 0, err[-3000:]
    assert last["correct"] is True, last["checks"]
    assert set(last["metrics"]) == {"answered_requests"}
    assert last["metrics"]["answered_requests"]["value"] == last["attempted"]
    assert {"busy_s", "window_s"} <= set(last["device"])
