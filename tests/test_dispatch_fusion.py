"""Fused plan-step programs + same-plan dispatch coalescing.

Covers the one-dispatch-per-query work (exec/fuse.py,
parallel/coalesce.py, the planner's fused aggregates and ``__const__``
partial fusion, and the TransferBatcher inline-steal knob):

* generative bit-equivalence of fused vs per-step execution over random
  call trees (fusion on/off, three seeds, including BSI Range→Sum),
* dispatches-per-query == 1 for multi-step plans (Count and aggregates),
* a deterministic-barrier concurrency test proving N identical
  concurrent Counts collapse into ONE launch with correct per-caller
  results (coalescer.hold()/release()),
* maximal-subtree (const-leaf) fusion against the scalar executor,
* inline transfer-steal semantics per knob mode,
* knob validation and env-var precedence.
"""

import threading
import time

import numpy as np
import pytest

import jax

from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.core import Holder, FieldOptions
from pilosa_tpu.core.field import FIELD_TYPE_INT
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import fuse as fuse_mod
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.parallel import batcher as batcher_mod
from pilosa_tpu.parallel import coalesce as coalesce_mod


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return make_mesh()


@pytest.fixture
def env(mesh):
    h = Holder()
    idx = h.create_index("i")
    plain = Executor(h)
    fast = Executor(h, planner=MeshPlanner(h, mesh))
    yield h, idx, plain, fast
    fast.planner.close()


def seed(idx, rng, n_shards=3, n_rows=6, bits_per_row=2000):
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field("v",
                         FieldOptions(type=FIELD_TYPE_INT, min=-500, max=500))
    total = n_shards * SHARD_WIDTH
    for field in (f, g):
        rows = rng.integers(0, n_rows, n_rows * bits_per_row)
        cols = rng.integers(0, total, n_rows * bits_per_row)
        field.import_bits(rows, cols)
    vcols = rng.choice(total, 4000, replace=False)
    vvals = rng.integers(-500, 500, len(vcols))
    v.import_values(vcols.tolist(), vvals.tolist())
    idx.add_existence(np.arange(0, total, 7))
    return f, g, v


# ---------------------------------------------------------- knob plumbing


def test_fuse_knob_validation(monkeypatch):
    with pytest.raises(ValueError):
        fuse_mod.set_mode("bogus")
    # env var wins over the server knob
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "off")
    fuse_mod.set_mode("on")
    try:
        assert fuse_mod.mode() == "off"
        assert not fuse_mod.enabled()
        monkeypatch.delenv("PILOSA_TPU_DISPATCH_FUSE")
        assert fuse_mod.mode() == "on"
    finally:
        fuse_mod.set_mode("auto")
    assert fuse_mod.enabled()  # auto resolves to on


def test_coalesce_knob_validation(monkeypatch):
    with pytest.raises(ValueError):
        coalesce_mod.set_mode("sometimes")
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE", "on")
    assert coalesce_mod.mode() == "on"
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE_US", "275.5")
    assert coalesce_mod.default_window_us() == 275.5
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE_US", "not-a-float")
    assert coalesce_mod.default_window_us() == coalesce_mod.DEFAULT_WINDOW_US


def test_inline_knob_validation(monkeypatch):
    with pytest.raises(ValueError):
        batcher_mod.set_inline_mode("never")
    monkeypatch.setenv("PILOSA_TPU_INLINE_TRANSFER", "off")
    assert batcher_mod.inline_mode() == "off"


# ----------------------------------------------- dispatches per query == 1


def test_count_three_step_plan_is_one_dispatch(env):
    """The acceptance check: a 3-step Intersect-of-Rows Count plan runs
    as exactly ONE device dispatch, cold and warm."""
    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    p = fast.planner
    q = "Count(Intersect(Row(f=1), Row(g=2)))"
    for _ in range(2):  # cold (compile) and warm (cached plan)
        d0 = p.dispatches
        fast.execute("i", q, cache=False)
        assert p.dispatches - d0 == 1
    # the span/slowlog observable: 4 plan calls fused into that program
    assert fuse_mod.fused_steps() == 4


@pytest.mark.parametrize("q,steps", [
    ("Sum(field=v)", 1),
    ("Sum(Row(v >< [-100, 100]), field=v)", 2),
    ("Min(Row(f=2), field=v)", 2),
    ("Max(Intersect(Row(f=1), Row(v >= 0)), field=v)", 4),
])
def test_aggregate_is_one_dispatch(env, q, steps, monkeypatch):
    """Fused BSI aggregates: filter tree + plane stack + reduction in
    ONE program (previously three launches). FUSE=on because under
    ``auto`` the planner deliberately steps FILTERED aggregates on the
    XLA CPU backend (see _fuse_agg_ok) — this test pins the fused path
    the TPU backend takes."""
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "on")
    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    p = fast.planner
    (want,) = plain.execute("i", q, cache=False)
    d0 = p.dispatches
    (got,) = fast.execute("i", q, cache=False)
    assert p.dispatches - d0 == 1
    assert (got.val, got.count) == (want.val, want.count), q
    assert fuse_mod.fused_steps() == steps


def test_aggregate_stepped_fallback_matches(env, monkeypatch):
    """PILOSA_TPU_DISPATCH_FUSE=off takes the per-step aggregate path;
    results stay bit-identical and the launch count is honest (>1)."""
    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    p = fast.planner
    q = "Sum(Row(v >< [-50, 150]), field=v)"
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "on")
    (fused,) = fast.execute("i", q, cache=False)
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "off")
    d0 = p.dispatches
    (stepped,) = fast.execute("i", q, cache=False)
    assert p.dispatches - d0 > 1
    assert (fused.val, fused.count) == (stepped.val, stepped.count)


def test_auto_agg_gate_on_cpu(env, monkeypatch):
    """Under ``auto`` on the XLA CPU backend the planner steps FILTERED
    aggregates (the comparator+reduction single-module pathology) but
    still fuses unfiltered ones — both bit-identical to the scalar
    executor either way."""
    assert jax.default_backend() == "cpu"  # conftest guarantees this
    monkeypatch.delenv("PILOSA_TPU_DISPATCH_FUSE", raising=False)
    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    p = fast.planner
    d0 = p.dispatches
    (filt,) = fast.execute("i", "Sum(Row(v > 0), field=v)", cache=False)
    assert p.dispatches - d0 > 1  # gated: stepped path
    d0 = p.dispatches
    (plain_sum,) = fast.execute("i", "Sum(field=v)", cache=False)
    assert p.dispatches - d0 == 1  # unfiltered still fuses
    (w1,) = plain.execute("i", "Sum(Row(v > 0), field=v)", cache=False)
    (w2,) = plain.execute("i", "Sum(field=v)", cache=False)
    assert (filt.val, filt.count) == (w1.val, w1.count)
    assert (plain_sum.val, plain_sum.count) == (w2.val, w2.count)


# ------------------------------------------------ generative equivalence


def _gen_tree(rng, depth):
    """Random plannable bitmap tree as PQL text (set rows + BSI ranges)."""
    if depth == 0:
        k = int(rng.integers(0, 4))
        if k == 0:
            return f"Row(f={int(rng.integers(0, 6))})"
        if k == 1:
            return f"Row(g={int(rng.integers(0, 6))})"
        if k == 2:
            op = ["<", ">", "<=", ">="][int(rng.integers(0, 4))]
            return f"Row(v {op} {int(rng.integers(-200, 200))})"
        lo = -int(rng.integers(0, 200))
        return f"Row(v >< [{lo}, {int(rng.integers(0, 200))}])"
    op = ["Intersect", "Union", "Xor", "Difference", "Not", "Shift"][
        int(rng.integers(0, 6))]
    if op == "Not":
        return f"Not({_gen_tree(rng, depth - 1)})"
    if op == "Shift":
        return f"Shift({_gen_tree(rng, depth - 1)}, n={int(rng.integers(0, 8))})"
    kids = ", ".join(_gen_tree(rng, depth - 1)
                     for _ in range(int(rng.integers(2, 4))))
    return f"{op}({kids})"


@pytest.mark.parametrize("seed_val", [11, 29, 47])
def test_generative_fused_vs_stepped(env, monkeypatch, seed_val):
    """Random call trees: fused execution (one program per query) is
    bit-identical to both the stepped device path (fuse=off) and the
    scalar per-shard executor — Counts and BSI Range→Sum/Min/Max."""
    h, idx, plain, fast = env
    rng = np.random.default_rng(seed_val)
    seed(idx, rng)
    queries = [f"Count({_gen_tree(rng, int(rng.integers(1, 4)))})"
               for _ in range(8)]
    queries += [
        f"Sum({_gen_tree(rng, 1)}, field=v)",
        "Sum(Row(v >< [-120, 80]), field=v)",  # BSI Range -> Sum, always in
        f"Min({_gen_tree(rng, 1)}, field=v)",
        f"Max({_gen_tree(rng, 1)}, field=v)",
    ]

    def run(ex):
        out = []
        for q in queries:
            (r,) = ex.execute("i", q, cache=False)
            out.append((r.val, r.count) if hasattr(r, "val") else r)
        return out

    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "on")
    fused = run(fast)
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "off")
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE", "off")
    stepped = run(fast)
    reference = run(plain)
    for q, a, b, c in zip(queries, fused, stepped, reference):
        assert a == b == c, (seed_val, q, a, b, c)


# ------------------------------------------------- coalescing concurrency


def test_coalesce_barrier_one_launch(env, monkeypatch):
    """Deterministic barrier: N identical concurrent Counts become ONE
    device launch (the identical-argument wave) with every caller
    getting the right answer."""
    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    p = fast.planner
    q = "Count(Intersect(Row(f=1), Row(g=2)))"
    (want,) = plain.execute("i", q, cache=False)
    fast.execute("i", q, cache=False)  # warm the plan/stack caches

    monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE", "on")
    co = p.coalescer
    co.hold()
    results: list = [None] * 4
    try:
        def worker(i):
            (results[i],) = fast.execute("i", q, cache=False)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with co._lock:
                n = sum(len(b.entries) for b in co._pending.values())
            if n == 4:
                break
            time.sleep(0.005)
        assert n == 4, "batch never assembled"
        d0, c0 = p.dispatches, p.dispatches_coalesced
    finally:
        co.release()
    for t in threads:
        t.join(timeout=30)
    assert results == [want] * 4
    assert p.dispatches - d0 == 1           # ONE launch for the wave
    assert p.dispatches_coalesced - c0 == 3  # 3 queries rode along
    assert p.batch_widths()[-1] == 4


def test_coalesce_overflow_batch_not_lost(monkeypatch):
    """Regression: entry MAX_BATCH+1 opens a FRESH batch; the sealed
    full batch must stay pending until flushed (it used to be
    overwritten in the pending map, stranding its futures forever)."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    f.import_bits([1], [0])
    planner = MeshPlanner(h, make_mesh(n=1))
    try:
        from pilosa_tpu.pql import parse
        c1 = parse("Row(f=1)").calls[0]
        fn, a1 = planner.prepare_count(idx, c1, [0])
        co = planner.coalescer
        monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE", "on")
        co.hold()
        n = coalesce_mod.MAX_BATCH + 3
        try:
            futs = [co.dispatch(fn, a1, planner._sum_host)
                    for _ in range(n)]
            with co._lock:
                batches = list(co._pending.values())
            assert sum(len(b.entries) for b in batches) == n
            assert len(batches) == 2  # sealed full batch + fresh one
        finally:
            co.release()
        assert [f.result(timeout=30) for f in futs] == [1] * n
    finally:
        planner.close()


def test_coalesce_off_launches_serially(env, monkeypatch):
    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE", "off")
    p = fast.planner
    d0, c0 = p.dispatches, p.dispatches_coalesced
    for _ in range(3):
        fast.execute("i", "Count(Row(f=1))", cache=False)
    assert p.dispatches - d0 == 3
    assert p.dispatches_coalesced == c0


def test_coalesce_vmapped_wave_same_shape(monkeypatch):
    """Same plan shape, different leaf arrays: the wave stacks to
    [B, ...] and launches ONE vmapped program whose per-slot results
    match solo launches. Needs a 1-device planner (a stack of sharded
    arrays can't keep its NamedSharding)."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    f.import_bits([1, 2, 2], [0, 1, SHARD_WIDTH // 2])
    planner = MeshPlanner(h, make_mesh(n=1))
    try:
        assert planner.coalesce_vmap_supported
        from pilosa_tpu.pql import parse
        shards = [0]
        c1 = parse("Row(f=1)").calls[0]
        c2 = parse("Row(f=2)").calls[0]
        fn1, a1 = planner.prepare_count(idx, c1, shards)
        fn2, a2 = planner.prepare_count(idx, c2, shards)
        assert planner.fn_key(fn1) == planner.fn_key(fn2) is not None
        co = planner.coalescer
        co.hold()
        monkeypatch.setenv("PILOSA_TPU_DISPATCH_COALESCE", "on")
        try:
            f1 = co.dispatch(fn1, a1, planner._sum_host)
            f2 = co.dispatch(fn2, a2, planner._sum_host)
            with co._lock:
                n = sum(len(b.entries) for b in co._pending.values())
            assert n == 2
            d0 = planner.dispatches
        finally:
            co.release()
        assert (f1.result(timeout=30), f2.result(timeout=30)) == (1, 2)
        assert planner.dispatches - d0 == 1
        assert planner.batch_widths()[-1] == 2
    finally:
        planner.close()


# -------------------------------------------------- partial (const) fusion


class _PickyPlanner(MeshPlanner):
    """Rejects rows over field 'g', forcing the executor to lower them
    as host-computed const leaves of an otherwise-fused tree."""

    def supports(self, c):
        if c.name in ("Row", "Range") and "g" in c.args:
            return False
        return super().supports(c)


def test_partial_fusion_const_leaves(mesh):
    h = Holder()
    idx = h.create_index("i")
    plain = Executor(h)
    fast = Executor(h, planner=_PickyPlanner(h, mesh))
    seed(idx, np.random.default_rng(29))
    p = fast.planner
    try:
        for q in ["Count(Intersect(Row(f=1), Row(g=2)))",
                  "Count(Union(Row(f=0), Row(g=0), Row(f=3)))",
                  "Count(Difference(Row(f=1), Row(g=1)))",
                  "Count(Xor(Row(f=2), Union(Row(g=2), Row(g=3))))"]:
            want = plain.execute("i", q, cache=False)
            d0 = p.dispatches
            got = fast.execute("i", q, cache=False)
            assert got == want, q
            assert p.dispatches - d0 == 1, q  # device leg is one program
        # bitmap (segment) results flow through the same const path
        (a,) = plain.execute("i", "Union(Row(f=1), Row(g=2))", cache=False)
        (b,) = fast.execute("i", "Union(Row(f=1), Row(g=2))", cache=False)
        assert np.array_equal(a.columns(), b.columns())
        # no plannable subtree left -> scalar fallback, still correct
        assert (fast.execute("i", "Count(Row(g=2))", cache=False)
                == plain.execute("i", "Count(Row(g=2))", cache=False))
    finally:
        p.close()


def test_partial_fusion_respects_fuse_off(mesh, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "off")
    h = Holder()
    idx = h.create_index("i")
    plain = Executor(h)
    fast = Executor(h, planner=_PickyPlanner(h, mesh))
    seed(idx, np.random.default_rng(47))
    try:
        q = "Count(Intersect(Row(f=1), Row(g=2)))"
        from pilosa_tpu.pql import parse
        assert fast._fuse_partial(parse(q).calls[0].children[0]) is None
        assert (fast.execute("i", q, cache=False)
                == plain.execute("i", q, cache=False))
    finally:
        fast.planner.close()


# ---------------------------------------------------- inline transfer steal


def test_inline_transfer_steal(monkeypatch):
    b = batcher_mod.TransferBatcher()
    # pin the resolver "started" so steals are deterministic (no racing
    # resolver thread); entries only leave the queue via _steal here.
    b._thread = threading.current_thread()
    monkeypatch.setenv("PILOSA_TPU_INLINE_TRANSFER", "on")
    fut = b.submit(np.asarray([2, 3]), lambda hst: int(hst.sum()))
    assert fut.result(timeout=5) == 5  # resolved on THIS thread
    assert b.inline_resolved == 1

    monkeypatch.setenv("PILOSA_TPU_INLINE_TRANSFER", "off")
    f2 = b.submit(np.asarray([4]), lambda hst: int(hst.sum()))
    b._steal(f2)  # what result() would try first
    assert b.inline_resolved == 1 and len(b._queue) == 1  # declined

    monkeypatch.setenv("PILOSA_TPU_INLINE_TRANSFER", "auto")
    f3 = b.submit(np.asarray([6]), lambda hst: int(hst.sum()))
    b._steal(f3)  # auto + two waiters: FIFO pipelining wins, no steal
    assert b.inline_resolved == 1 and len(b._queue) == 2

    monkeypatch.setenv("PILOSA_TPU_INLINE_TRANSFER", "on")
    assert f3.result(timeout=5) == 6  # on-mode steals at any depth
    assert f2.result(timeout=5) == 4
    assert b.inline_resolved == 3 and len(b._queue) == 0


# ------------------------------------------------------------ observability


def test_dispatch_counters_surface(env):
    from pilosa_tpu.obs.runtime import collect_runtime_gauges
    from pilosa_tpu.obs.stats import MemoryStats

    h, idx, plain, fast = env
    seed(idx, np.random.default_rng(11))
    p = fast.planner
    fast.execute("i", "Count(Row(f=1))", cache=False)
    snap = p.cache_stats()
    assert snap["dispatches"] >= 1
    assert "dispatches_coalesced" in snap
    out = collect_runtime_gauges(MemoryStats(), planner=p,
                                 probe_device=False)
    assert out["plannerDispatches"] == float(snap["dispatches"])
    assert "plannerDispatchesCoalesced" in out


def test_slowlog_carries_fused_steps():
    from pilosa_tpu.qos.slowlog import SlowQueryLog
    log = SlowQueryLog(threshold_ms=0.0)
    log.observe("i", "Count(Row(f=1))", 12.5, fused_steps=4)
    assert log.entries()[0]["fusedSteps"] == 4
