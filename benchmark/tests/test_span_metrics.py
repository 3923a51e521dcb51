"""The readers of the program's span counters (``span_counters.py`` and
the ten ``layer_metrics/`` files that use it): each on a hand-made
``ctx``, and all that need no upload in a traced rehearsal on the CPU
backend (a proof of the flow, never a measurement)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

#: span.<name>.<field> -> (before, after): 100 answered requests, 4 uploads.
COUNTERS = {
    "span.http.request.wallSeconds": (10.0, 12.5),
    "span.http.request.cpuSeconds": (1.0, 1.3),
    "span.http.request.selfCpuSeconds": (0.5, 0.58),
    "span.http.reply.selfCpuSeconds": (0.2, 0.22),
    "span.qos.admit.wallSeconds": (0.1, 0.15),
    "span.exec.parse.selfCpuSeconds": (0.01, 0.02),
    "span.Executor.executeCount.selfCpuSeconds": (0.05, 0.07),
    "span.Executor.executeTopN.selfCpuSeconds": (0.0, 0.01),
    "span.plan.prepare.selfCpuSeconds": (0.02, 0.04),
    "span.stack.fetch.selfCpuSeconds": (0.01, 0.03),
    "span.stack.fetch.wallSeconds": (1.0, 1.4),
    "span.dispatch.launch.cpuSeconds": (0.03, 0.07),
    "span.transfer.wait.wallSeconds": (5.0, 6.6),
    "span.stack.wait.wallSeconds": (0.5, 0.8),
    "span.stack.build.wallSeconds": (2.0, 2.8),
    "span.stack.upload.wallSeconds": (0.2, 0.32),
}

#: metric -> (value on COUNTERS, a counter it cannot be read without)
METRICS = {
    "host_cpu_ms_per_request": (3.0, "span.http.request.cpuSeconds"),
    "http_cpu_ms_per_request": (1.0, "span.http.reply.selfCpuSeconds"),
    "admission_wait_ms_per_request": (0.5, "span.qos.admit.wallSeconds"),
    "plan_cpu_ms_per_request": (0.8, "span.plan.prepare.selfCpuSeconds"),
    "launch_cpu_ms_per_request": (0.4, "span.dispatch.launch.cpuSeconds"),
    "result_wait_ms_per_request": (16.0, "span.transfer.wait.wallSeconds"),
    # 2.5 s wall - 0.05 admit - 0.3 stack wait - 1.6 transfer - 0.3 cpu
    "interpreter_wait_ms_per_request": (2.5, "span.http.request.wallSeconds"),
    "stack_build_s_per_upload": (0.2, "span.stack.build.wallSeconds"),
    "stack_upload_s_per_upload": (0.03, "span.stack.upload.wallSeconds"),
    "stack_wait_ms_per_request": (4.0, "span.stack.fetch.wallSeconds"),
}
NEED_UPLOADS = {"stack_build_s_per_upload", "stack_upload_s_per_upload"}


def make_ctx(answered=100, uploads=4, without=()):
    keep = {k: v for k, v in COUNTERS.items() if k not in without}
    return {"answered": answered,
            "counters0": {k: v[0] for k, v in keep.items()},
            "counters1": {k: v[1] for k, v in keep.items()},
            "device0": {"uploads": 10}, "device1": {"uploads": 10 + uploads}}


def read(name, ctx):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_every_new_metric_is_declared_with_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in METRICS:
        m = declared[name]
        assert m["source"] == "program_counter" and m["moves"] == "qps"
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
        cells = ["count-trees-oversub"] if name.startswith("stack_") \
            else ["count-trees-resident", "count-trees-oversub"]
        assert m["workloads"] == cells


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_the_value(name):
    assert read(name, make_ctx()) == pytest.approx(METRICS[name][0])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_none_on_a_missing_counter(name):
    """A program without the span (the parent of the PR that brought
    them) leaves the metric out; it does not raise."""
    assert read(name, make_ctx(without={METRICS[name][1]})) is None
    assert read(name, {"answered": 100, "counters0": {}, "counters1": {},
                       "device0": {"uploads": 0},
                       "device1": {"uploads": 3}}) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_none_on_a_zero_divisor(name):
    ctx = make_ctx(uploads=0) if name in NEED_UPLOADS \
        else make_ctx(answered=0)
    assert read(name, ctx) is None


def test_a_window_that_never_waited_reads_its_waits_as_zero():
    """qos.admit, stack.wait and transfer.wait have no counter until a
    request enters them: the interpreter's wait is then wall less CPU."""
    ctx = make_ctx(without={"span.qos.admit.wallSeconds",
                            "span.stack.wait.wallSeconds",
                            "span.transfer.wait.wallSeconds"})
    assert read("interpreter_wait_ms_per_request", ctx) == \
        pytest.approx((2.5 - 0.3) * 1e3 / 100)


def test_traced_rehearsal_line_holds_the_span_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--config",
         "rehearsal-4s", "--traffic", "count-trees", "--seed", "3000000028",
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, BENCH_RUN="ignored"))
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    for name in set(METRICS) - NEED_UPLOADS:
        value = last["metrics"][name]["value"]
        # finite; the interpreter's wait is wall less an ESTIMATE of the
        # CPU (one request in tracing.CPU_SAMPLE_EVERY is read), so 2 s
        # of it may dip under 0
        assert value == value and abs(value) < 1e6, (name, value)
        assert value >= 0 or name == "interpreter_wait_ms_per_request"
    parts = sum(last["metrics"][n]["value"] for n in (
        "http_cpu_ms_per_request", "plan_cpu_ms_per_request",
        "launch_cpu_ms_per_request"))
    assert 0 < parts <= last["metrics"]["host_cpu_ms_per_request"]["value"]
