"""chip_smoke.py, rehearsed: the script that proves the served path on
the chip must itself keep working, and must keep refusing anything but
a TPU unless told it is a rehearsal.

The script runs as a subprocess exactly as the driver runs it (it
starts its own ``cli server`` child and never imports jax); only
``--rehearse`` makes it tiny and pins its server to the CPU backend.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--out", str(tmp_path / "out"), *args],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600, check=False)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def test_rehearsal_passes_with_every_answer_checked(tmp_path):
    proc, lines = _run(tmp_path, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    requests = [ln for ln in lines if "request" in ln]
    assert len(requests) >= 10
    assert all(ln["correct"] is True for ln in requests)
    # Every uncached request reached the planner's device path.
    deltas = [ln["dispatchDelta"] for ln in requests if "dispatchDelta" in ln]
    assert len(deltas) >= 9 and all(d > 0 for d in deltas), deltas
    (cache,) = [ln["compileCache"] for ln in lines if "compileCache" in ln]
    assert cache["requests"] > 0
    assert {"native": "loaded"} in lines
    assert [ln["childExitCode"] for ln in lines if "childExitCode" in ln] \
        == [0]
    last = lines[-1]
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": last["device"]["kind"], "count": 1}}
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]
    # The child's output went to a log file, not to /dev/null.
    assert (tmp_path / "out" / "server.log").stat().st_size > 0


def test_without_rehearse_a_cpu_is_a_failure(tmp_path):
    """No fallback: on the CPU backend, without --rehearse, the script
    must say ok:false and exit non-zero (the driver runs exactly this in
    the sandbox and requires it to fail)."""
    proc, lines = _run(tmp_path)
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert not any(ln.get("ok") is True for ln in lines)
    assert not any("request" in ln for ln in lines)  # failed before loading


def test_node_refuses_to_start_when_its_planner_cannot_be_built(monkeypatch):
    """A planner that was asked for and cannot be built is a start-up
    error, from ServerNode and so from ``cli server``: never a node that
    quietly answers every query from the host."""
    import pilosa_tpu.parallel as parallel
    from pilosa_tpu import cli
    from pilosa_tpu.server.node import ServerNode

    def broken(*args, **kwargs):
        raise RuntimeError("no device for the mesh")

    monkeypatch.setattr(parallel, "MeshPlanner", broken)
    with pytest.raises(RuntimeError, match="no device for the mesh"):
        ServerNode(bind="127.0.0.1:0")
    with pytest.raises(RuntimeError, match="no device for the mesh"):
        cli.main(["server", "--bind", "127.0.0.1:0"])
    # --no-planner stays the explicit way to run without one.
    node = ServerNode(bind="127.0.0.1:0", use_planner=False)
    try:
        assert node.executor.planner is None
    finally:
        node.close()
