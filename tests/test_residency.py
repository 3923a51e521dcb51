"""Device residency tests: container-classed stacks (exec/residency)
and the uploads that run ahead of the requests (parallel/stacks).

The contract mirrors the reference's roaring container-class tests
(roaring_internal_test.go: array/bitmap conversions are bit-exact):
the packed representation must be *bit-identical* to dense through
every query family, proven generatively over seeded random data, while
the oversubscription drill proves the prefetch pipeline keeps the
query path free of synchronous uploads under eviction churn.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.core.field import FIELD_TYPE_INT
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import residency
from pilosa_tpu.ops import bitops
from pilosa_tpu.parallel import MeshPlanner, make_mesh


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return make_mesh()


# -- representation policy ---------------------------------------------------


def test_pack_width_pow2_buckets():
    assert residency.pack_width(0) == residency.MIN_PACK_WIDTH
    assert residency.pack_width(8) == 8
    assert residency.pack_width(9) == 16
    assert residency.pack_width(250) == 256
    assert residency.pack_width(256) == 256
    assert residency.pack_width(257) == 512


def test_choose_class_per_mode(monkeypatch):
    lo, hi = 100, SHARD_WIDTH // 2          # sparse vs pathological rows
    mid = WORDS_PER_SHARD // residency.AUTO_RATIO + 1   # auto's boundary
    monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "off")
    assert residency.choose_class(lo) == residency.DENSE
    monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "auto")
    assert residency.choose_class(lo) == residency.PACKED
    assert residency.choose_class(mid) == residency.DENSE
    monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "on")
    assert residency.choose_class(mid) == residency.PACKED
    # high cardinality falls back to dense in EVERY mode
    for m in ("on", "auto", "off"):
        monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", m)
        assert residency.choose_class(hi) == residency.DENSE, m


def test_mode_knob_validates_and_env_wins(monkeypatch):
    with pytest.raises(ValueError):
        residency.set_mode("sometimes")
    try:
        monkeypatch.delenv("PILOSA_TPU_RESIDENCY_PACKED", raising=False)
        residency.set_mode("on")
        assert residency.mode() == "on"
        monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "off")
        assert residency.mode() == "off"          # env beats server knob
        monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "bogus")
        assert residency.mode() == "on"           # junk env is ignored
    finally:
        residency.set_mode("auto")


# -- kernel variants vs dense references -------------------------------------


def _random_packed(rng, s=4, k=64, fill=0.6):
    """A [s, k] sorted-index stack with sentinel padding, plus the
    equivalent dense [s, W] uint32 planes built independently."""
    mat = np.full((s, k), residency.SENTINEL, dtype=np.int32)
    dense = np.zeros((s, WORDS_PER_SHARD), dtype=np.uint32)
    for i in range(s):
        n = int(rng.integers(0, int(k * fill) + 1))
        pos = np.sort(rng.choice(SHARD_WIDTH, n, replace=False))
        mat[i, :n] = pos
        dense[i, pos >> 5] |= np.uint32(1) << (pos & 31).astype(np.uint32)
    return jnp.asarray(mat), jnp.asarray(dense)


def test_packed_expand_bit_exact(rng):
    idxs, dense = _random_packed(rng)
    out = np.asarray(residency.packed_expand(idxs))
    np.testing.assert_array_equal(out, dense)


def test_packed_count_matches_dense_popcount(rng):
    idxs, dense = _random_packed(rng)
    got = np.asarray(residency.packed_count(idxs))
    want = np.asarray(bitops.count(jnp.asarray(dense)))
    np.testing.assert_array_equal(got, want)


def test_packed_and_dense_count_matches(rng):
    idxs, dense_a = _random_packed(rng)
    _, dense_b = _random_packed(rng, fill=0.9)
    got = np.asarray(residency.packed_and_dense_count(idxs,
                                                      jnp.asarray(dense_b)))
    want = np.asarray(bitops.intersection_count(jnp.asarray(dense_a),
                                                jnp.asarray(dense_b)))
    np.testing.assert_array_equal(got, want)


def test_packed_pair_count_matches(rng):
    a_idx, a_dense = _random_packed(rng)
    b_idx, b_dense = _random_packed(rng, k=32)
    got = np.asarray(residency.packed_pair_count(a_idx, b_idx))
    want = np.asarray(bitops.intersection_count(jnp.asarray(a_dense),
                                                jnp.asarray(b_dense)))
    np.testing.assert_array_equal(got, want)


def test_kernel_lookup_raises_on_unknown_pair():
    assert residency.kernel(residency.PACKED, "count") is residency.packed_count
    with pytest.raises(KeyError, match="no 'count' kernel.*'run'"):
        residency.kernel("run", "count")


# -- generative packed-vs-dense equivalence over query families ---------------

N_SHARDS = 4

#: every planner query family, with trees that route each packed
#: kernel: pair_count (packed∧packed), and_count (packed∧dense),
#: expand (unions/differences/NOT and every aggregate filter).
EQ_QUERIES = [
    "Count(Row(f=0))",
    "Count(Row(f=4))",                                   # dense leaf
    "Count(Intersect(Row(f=1), Row(g=2)))",              # packed ∧ packed
    "Count(Intersect(Row(f=1), Row(f=4)))",              # packed ∧ dense
    "Count(Intersect(Row(f=4), Row(g=5)))",              # dense ∧ dense
    "Count(Union(Row(f=0), Row(g=0), Row(f=3)))",
    "Count(Difference(Row(f=4), Row(g=1)))",
    "Count(Xor(Row(f=2), Row(g=2)))",
    "Count(Not(Row(f=1)))",
    "Count(Intersect(Union(Row(f=0), Row(f=1)), Not(Row(g=3))))",
    "Row(f=1)",
    "TopN(f, n=4)",
    "TopN(f, Row(g=1), n=3)",
    "Sum(Row(f=1), field=v)",
    "Sum(Intersect(Row(f=1), Row(g=2)), field=v)",
    "Min(Row(f=0), field=v)",
    "Max(Row(f=0), field=v)",
    "GroupBy(Rows(f), Rows(g))",
]


def _seed_mixed(idx, rng):
    """Rows 0-3 sparse (packable), rows 4-5 heavy (auto falls back to
    dense; ``on`` packs row 4's wave only if it fits MAX_PACK_WIDTH)."""
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field("v",
                         FieldOptions(type=FIELD_TYPE_INT, min=-500, max=500))
    total = N_SHARDS * SHARD_WIDTH
    for field in (f, g):
        for r in range(4):
            n = int(rng.integers(50, 2000))
            field.import_bits(np.full(n, r), rng.integers(0, total, n))
        for r in (4, 5):
            field.import_bits(np.full(60000, r),
                              rng.integers(0, total, 60000))
    vcols = rng.choice(total, 3000, replace=False)
    v.import_values(vcols.tolist(),
                    rng.integers(-500, 500, len(vcols)).tolist())
    idx.add_existence(np.arange(0, total, 5))
    return f, g, v


def _run_suite(h, mesh, mode_name, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", mode_name)
    planner = MeshPlanner(h, mesh)
    e = Executor(h, planner=planner, result_cache=False)
    shards = list(range(N_SHARDS))
    try:
        out = [e.execute("rq", q, shards=shards) for q in EQ_QUERIES]
        classes = {k.klass for k in planner.stacks.keys()}
        n_packed = sum(1 for k in planner.stacks.keys()
                       if k.klass == residency.PACKED)
        cls_bytes = planner.cache_stats()["class_bytes"]
    finally:
        planner.close()
    return out, classes, n_packed, cls_bytes


@pytest.mark.parametrize("seed", [
    0,
    pytest.param(1, marks=pytest.mark.slow),
    pytest.param(2, marks=pytest.mark.slow),
])
def test_packed_dense_bit_equivalence_generative(mesh, monkeypatch, seed):
    """The acceptance gate: for every query family, packed execution is
    bit-identical to dense, across auto and forced-on policies."""
    h = Holder()
    idx = h.create_index("rq")
    _seed_mixed(idx, np.random.default_rng(seed))
    want, classes, _, _ = _run_suite(h, mesh, "off", monkeypatch)
    assert classes <= {residency.DENSE}
    for mode_name in ("auto", "on"):
        got, classes, n_packed, cls_bytes = _run_suite(
            h, mesh, mode_name, monkeypatch)
        assert got == want, mode_name
        # the packed path actually ran
        assert residency.PACKED in classes, mode_name
        assert cls_bytes[residency.PACKED] > 0, mode_name
        if mode_name == "auto":
            # auto only packs stacks at least AUTO_RATIO× under dense
            assert cls_bytes[residency.PACKED] <= (
                n_packed * residency.dense_nbytes(N_SHARDS)
                // residency.AUTO_RATIO), mode_name


def test_mutation_then_requery_stays_equivalent(mesh, monkeypatch):
    """Epoch bumps must invalidate packed stacks AND replan leaves
    whose class flips (sparse row grown past the auto threshold)."""
    h = Holder()
    idx = h.create_index("rq")
    f, g, _ = _seed_mixed(idx, np.random.default_rng(7))
    queries = EQ_QUERIES[:10]

    def sweep(mode_name, executor):
        monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", mode_name)
        shards = list(range(N_SHARDS))
        return [executor.execute("rq", q, shards=shards) for q in queries]

    dense_p = MeshPlanner(h, mesh)
    packed_p = MeshPlanner(h, mesh)
    try:
        e_dense = Executor(h, planner=dense_p, result_cache=False)
        e_packed = Executor(h, planner=packed_p, result_cache=False)
        assert sweep("auto", e_packed) == sweep("off", e_dense)

        # mutate: grow row 1 past auto's packing threshold (class flip
        # → plan revalidation must drop its cached programs), touch a
        # heavy row, and clear bits from row 0 (stays packed).
        total = N_SHARDS * SHARD_WIDTH
        rng = np.random.default_rng(8)
        f.import_bits(np.full(30000, 1), rng.integers(0, total, 30000))
        g.import_bits(np.full(500, 5), rng.integers(0, total, 500))
        for col in np.asarray(f.row(0).columns()[:20]):
            f.clear_bit(0, int(col))

        assert sweep("auto", e_packed) == sweep("off", e_dense)
    finally:
        dense_p.close()
        packed_p.close()


def test_auto_high_cardinality_rows_stay_dense(mesh, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "auto")
    h = Holder()
    idx = h.create_index("hc")
    f = idx.create_field("f")
    total = N_SHARDS * SHARD_WIDTH
    rng = np.random.default_rng(3)
    f.import_bits(np.full(300, 0), rng.integers(0, total, 300))       # sparse
    f.import_bits(np.full(40000, 1), rng.integers(0, total, 40000))   # heavy
    planner = MeshPlanner(h, mesh)
    e = Executor(h, planner=planner, result_cache=False)
    shards = list(range(N_SHARDS))
    try:
        e.execute("hc", "Count(Row(f=0))", shards=shards)
        e.execute("hc", "Count(Row(f=1))", shards=shards)
        by_row = {k.tag: k.klass for k in planner.stacks.keys()}
        assert by_row[0] == residency.PACKED
        assert by_row[1] == residency.DENSE    # fell back, as documented
        st = planner.cache_stats()
        assert st["residency_mode"] == "auto"
        assert sum(st["class_bytes"].values()) == st["bytes"]
    finally:
        planner.close()


# -- oversubscription drill: the pipelined miss path --------------------------


def test_oversubscribed_prefetch_no_sync_uploads(mesh, monkeypatch):
    """Working set > device budget: eviction churns, yet every
    query-thread miss rendezvouses with an inflight upload — zero
    synchronous uploads on the query path."""
    monkeypatch.setenv("PILOSA_TPU_RESIDENCY_PACKED", "off")  # dense bytes
    h = Holder()
    idx = h.create_index("ov")
    f = idx.create_field("f")
    n_shards = 8
    total = n_shards * SHARD_WIDTH
    rng = np.random.default_rng(5)
    for r in range(6):
        f.import_bits(np.full(2000, r), rng.integers(0, total, 2000))
    stack_bytes = residency.dense_nbytes(8) // mesh.devices.size
    planner = MeshPlanner(h, mesh, max_cache_bytes=3 * stack_bytes)
    e = Executor(h, planner=planner, result_cache=False)
    shards = list(range(n_shards))
    try:
        for _ in range(2):                      # 12 misses through 3 slots
            for r in range(6):
                e.execute("ov", f"Count(Row(f={r}))", shards=shards)
        assert planner.cache_stats()["evictions"] > 0
        dbg = planner.stacks.upload_stats()
        assert dbg["sync_misses"] == 0
        assert dbg["hits"] > 0
        assert dbg["completed"] == dbg["scheduled"] >= 6
        assert dbg["inflight"] == 0 and dbg["queued"] == 0
    finally:
        planner.close()


def test_prefetch_off_counts_sync_misses(mesh):
    class InLockstep(MeshPlanner):
        """As DistributedMeshPlanner: no upload runs ahead."""
        UPLOADS_AHEAD = False

    h = Holder()
    idx = h.create_index("sy")
    f = idx.create_field("f")
    f.import_bits(np.full(100, 0), np.arange(100))
    planner = InLockstep(h, mesh)
    e = Executor(h, planner=planner, result_cache=False)
    try:
        e.execute("sy", "Count(Row(f=0))", shards=[0])
        dbg = planner.stacks.upload_stats()
        assert dbg["scheduled"] == 0
        assert dbg["sync_misses"] >= 1
    finally:
        planner.close()
