"""Key translation at device speed (ISSUE 20): device key planes vs the
host-oracle store, snapshot concurrency, version-bump rebuilds, and the
replica-local read path.

The bit-equivalence half mirrors test_generative.py's model-based stress:
the same logical bit set lives in a keyed index (string keys routed
through the full translation path) and an unkeyed oracle index (raw
ids); every random Row/Intersect/Union/Count/TopN tree must agree under
relabeling, with the device plane path forced on AND forced off.
"""

import threading

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.index import IndexOptions
from pilosa_tpu.core.translate import TranslateStore
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import keyplane as kp
from pilosa_tpu.parallel import MeshPlanner, make_mesh

ROWS = [1, 2, 3, 4]


def _row_key(r: int) -> str:
    return f"r{r}"


def _col_key(c: int) -> str:
    return f"c{c}"


def _build_pair(rng, n_bits=160, n_cols=500):
    """One logical bit set, twice: keyed index (keys pre-translated in a
    single batch, bits imported under the allocated ids) and an id
    oracle index (raw ids). Returns (holder, keyed_exec, oracle_exec,
    col_fwd) where col_fwd maps column key -> keyed column id."""
    h = Holder()
    kidx = h.create_index("kt", IndexOptions(keys=True))
    kf = kidx.create_field("f", FieldOptions(keys=True))
    oidx = h.create_index("ot")
    of = oidx.create_field("f")

    rows = rng.choice(ROWS, n_bits)
    cols = rng.integers(0, n_cols, n_bits)

    # Batched allocation up front — also the satellite (a) path: one
    # translate_keys call per store, one lock, one epoch bump.
    row_ids = kf.translate_store.translate_keys(
        [_row_key(r) for r in ROWS])
    row_map = dict(zip(ROWS, row_ids))
    distinct_cols = sorted(set(cols.tolist()))
    col_ids = kidx.translate_store.translate_keys(
        [_col_key(c) for c in distinct_cols])
    col_map = dict(zip(distinct_cols, col_ids))

    kf.import_bits(
        np.array([row_map[r] for r in rows.tolist()], dtype=np.uint64),
        np.array([col_map[c] for c in cols.tolist()], dtype=np.uint64))
    of.import_bits(rows.astype(np.uint64), cols.astype(np.uint64))

    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    return h, ex, planner


def _gen_tree(rng, depth):
    """(keyed_pql, oracle_pql) pair over Row/Intersect/Union."""
    if depth == 0 or rng.random() < 0.4:
        r = ROWS[rng.integers(0, len(ROWS))]
        return f'Row(f="{_row_key(r)}")', f"Row(f={r})"
    op = ["Intersect", "Union"][rng.integers(0, 2)]
    subs = [_gen_tree(rng, depth - 1) for _ in range(2 + int(rng.integers(0, 2)))]
    return (f"{op}({', '.join(s[0] for s in subs)})",
            f"{op}({', '.join(s[1] for s in subs)})")


def _pairs_as_keys(pairs):
    """TopN pairs -> sorted multiset of (key, count); order between
    equal counts is id-order, which differs between labelings."""
    return sorted((p.key, p.count) for p in pairs)


@pytest.mark.parametrize("seed", [5, 17, 41])
def test_keyed_vs_id_bit_equivalence(seed, monkeypatch):
    """Random Row/Intersect/Count trees + TopN agree between the keyed
    index and the id oracle, with the device plane path forced ON (every
    batch probes the plane) and forced OFF (pure host snapshot path)."""
    rng = np.random.default_rng(seed)
    h, ex, planner = _build_pair(rng)
    trees = [_gen_tree(rng, depth=2 + int(rng.integers(0, 2)))
             for _ in range(25)]

    def run(mode):
        monkeypatch.setenv("PILOSA_TPU_TRANSLATE_PLANES", mode)
        counts, rowsets = [], []
        for kq, oq in trees:
            (want,) = ex.execute("ot", f"Count({oq})", cache=False)
            (got,) = ex.execute("kt", f"Count({kq})", cache=False)
            assert got == want, (mode, kq, got, want)
            counts.append(got)
        # Row columns under relabeling: keyed result keys == oracle
        # columns mapped through the column-key naming.
        for kq, oq in trees[:6]:
            (krow,) = ex.execute("kt", kq, cache=False)
            (orow,) = ex.execute("ot", oq, cache=False)
            want_keys = {_col_key(int(c)) for c in orow.columns()}
            assert set(krow.keys) == want_keys, (mode, kq)
            rowsets.append(sorted(krow.keys))
        # TopN: same (key, count) multiset; keyed pairs carry .key via
        # the batched reverse translation.
        (kpairs,) = ex.execute("kt", "TopN(f)", cache=False)
        (opairs,) = ex.execute("ot", "TopN(f)", cache=False)
        top = sorted((_row_key(p.id), p.count) for p in opairs)
        assert _pairs_as_keys(kpairs) == top, mode
        # TopN with a keyed src filter.
        (kpairs,) = ex.execute(
            "kt", f'TopN(f, Row(f="{_row_key(ROWS[0])}"))', cache=False)
        (opairs,) = ex.execute(
            "ot", f"TopN(f, Row(f={ROWS[0]}))", cache=False)
        assert _pairs_as_keys(kpairs) == \
            sorted((_row_key(p.id), p.count) for p in opairs), mode
        return counts, rowsets

    on = run("on")
    assert ex.keyplanes.device_batches > 0   # device path actually ran
    assert ex.keyplanes.builds >= 1
    off = run("off")
    assert on == off


def test_warm_keyed_count_single_dispatch():
    """Acceptance: a warm keyed Count stays ONE device dispatch — the
    auto-mode threshold keeps single-key translation on the lock-free
    host snapshot, off the device."""
    rng = np.random.default_rng(3)
    h, ex, planner = _build_pair(rng, n_bits=60, n_cols=80)
    q = f'Count(Row(f="{_row_key(ROWS[0])}"))'
    ex.execute("kt", q, cache=False)
    ex.execute("kt", q, cache=False)          # warm compile + stacks
    d0 = planner.dispatches
    ex.execute("kt", q, cache=False)
    assert planner.dispatches - d0 == 1


# ---------------------------------------------------------------------------
# snapshot concurrency (the COW swap in core/translate.py)
# ---------------------------------------------------------------------------


def test_concurrent_allocate_while_lookup():
    """Readers run lock-free against published snapshots while a writer
    allocates batches: no torn state, version monotonic, fwd/rev stay a
    bijection, pre-existing keys never change ids."""
    store = TranslateStore()
    (seed_id,) = store.translate_keys(["seed"])
    stop = threading.Event()
    errors: list[str] = []

    def writer():
        try:
            for i in range(60):
                store.translate_keys([f"w{i}-{j}" for j in range(8)])
        except Exception as e:                       # pragma: no cover
            errors.append(f"writer: {e!r}")
        finally:
            stop.set()

    def reader():
        try:
            last_v = 0
            while not stop.is_set():
                if store.translate_key("seed", create=False) != seed_id:
                    errors.append("seed id changed")
                    return
                v, fwd, rev = store.snapshot()
                if v < last_v:
                    errors.append(f"version went backwards {last_v}->{v}")
                    return
                last_v = v
                if len(fwd) != len(rev):
                    errors.append("fwd/rev size mismatch")
                    return
                for k, id_ in list(fwd.items())[:5]:
                    if rev.get(id_) != k:
                        errors.append(f"rev[{id_}] != {k!r}")
                        return
                # Batched reverse over the snapshot's ids.
                ids = list(rev)[:8]
                names = store.translate_ids(ids)
                for id_, n in zip(ids, names):
                    if n is not None and fwd.get(n) != id_ and \
                            store.translate_key(n, create=False) != id_:
                        errors.append("reverse/forward disagree")
                        return
        except Exception as e:                       # pragma: no cover
            errors.append(f"reader: {e!r}")

    threads = [threading.Thread(target=writer)] + \
        [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    v, fwd, rev = store.snapshot()
    assert len(fwd) == 1 + 60 * 8
    assert len(set(fwd.values())) == len(fwd)        # ids all distinct
    assert sorted(fwd.values()) == sorted(rev)


def test_batch_allocation_one_version_bump():
    """translate_keys publishes ONE snapshot (one version bump, one
    index-epoch bump) per batch, not one per key."""
    h = Holder()
    idx = h.create_index("b", IndexOptions(keys=True))
    store = idx.translate_store
    v0 = store.version
    e0 = idx.epoch.value
    ids = store.translate_keys([f"k{i}" for i in range(100)])
    assert len(set(ids)) == 100
    assert store.version == v0 + 1
    assert idx.epoch.value == e0 + 1
    # All-hits batch: no bump at all.
    store.translate_keys([f"k{i}" for i in range(100)])
    assert store.version == v0 + 1
    assert idx.epoch.value == e0 + 1


# ---------------------------------------------------------------------------
# plane lifecycle (exec/keyplane.py)
# ---------------------------------------------------------------------------


def _keyed_idx():
    h = Holder()
    idx = h.create_index("p", IndexOptions(keys=True))
    return h, idx


def test_plane_rebuilds_on_version_bump(monkeypatch):
    """'on' mode: a store-version bump invalidates the plane; the next
    lookup rebuilds synchronously and resolves the new key."""
    monkeypatch.setenv("PILOSA_TPU_TRANSLATE_PLANES", "on")
    h, idx = _keyed_idx()
    store = idx.translate_store
    ida, idb = store.translate_keys(["a", "b"])
    cache = kp.KeyPlaneCache(stacks=None)
    assert cache.lookup(idx, None, store, ["a", "b"]) == [ida, idb]
    assert cache.builds == 1
    # Same version: plane reused, no rebuild.
    assert cache.lookup(idx, None, store, ["b", "a"]) == [idb, ida]
    assert cache.builds == 1
    # Unknown key is a genuine miss, not an error.
    assert cache.lookup(idx, None, store, ["nope"]) == [None]
    # Allocation bumps the version -> synchronous rebuild on next use.
    (idc,) = store.translate_keys(["c"])
    assert cache.lookup(idx, None, store, ["a", "c"]) == [ida, idc]
    assert cache.builds == 2


def test_plane_auto_serves_stale_and_small_batches_host(monkeypatch):
    """'auto' mode: batches under MIN_DEVICE_BATCH skip the device; a
    stale plane serves what it has (correct-but-incomplete — new keys
    read as misses, never as wrong ids)."""
    h, idx = _keyed_idx()
    store = idx.translate_store
    keys = [f"k{i}" for i in range(kp.MIN_DEVICE_BATCH)]
    ids = store.translate_keys(keys)
    cache = kp.KeyPlaneCache(stacks=None)
    monkeypatch.setenv("PILOSA_TPU_TRANSLATE_PLANES", "on")
    assert cache.lookup(idx, None, store, keys) == ids   # build plane
    monkeypatch.setenv("PILOSA_TPU_TRANSLATE_PLANES", "auto")
    # Small batch: host path (None = "device does not apply").
    assert cache.lookup(idx, None, store, keys[:4]) is None
    # Stale plane after a bump: resident keys resolve, the new key is a
    # miss for the host fallback to re-check.
    (idn,) = store.translate_keys(["new"])
    got = cache.lookup(idx, None, store, keys + ["new"])
    assert got[:-1] == ids and got[-1] is None
    assert cache.stale_served == 1
    monkeypatch.setenv("PILOSA_TPU_TRANSLATE_PLANES", "off")
    assert cache.lookup(idx, None, store, keys) is None


def test_plane_collision_bucket(monkeypatch):
    """Keys whose 64-bit fingerprints collide are excluded from the
    plane at build time and resolve from the host-side bucket."""
    table = {"x": 7, "y": 7, "a": 101, "b": 202, "nope": 303}

    def fake_hash(keys):
        return np.array([table[k] for k in keys], dtype=np.uint64)

    monkeypatch.setattr(kp, "hash_keys", fake_hash)
    monkeypatch.setenv("PILOSA_TPU_TRANSLATE_PLANES", "on")
    h, idx = _keyed_idx()
    store = idx.translate_store
    idx_ids = store.translate_keys(["x", "y", "a", "b"])
    mat, collisions, valid = kp.build_plane(store.snapshot()[1])
    assert set(collisions) == {"x", "y"}
    assert valid == 2
    cache = kp.KeyPlaneCache(stacks=None)
    got = cache.lookup(idx, None, store, ["x", "y", "a", "b", "nope"])
    assert got == idx_ids + [None]
    assert cache.collision_hits == 2


def test_plane_kernels_roundtrip():
    """The residency KERNELS row for the keyplane class: count counts
    allocated slots, and_count counts probe membership, pair_count
    intersects two planes' hash sets."""
    fwd = {f"k{i}": i + 1 for i in range(10)}
    mat, _, valid = kp.build_plane(fwd)
    assert valid == 10
    assert int(kp.plane_count(mat)) == 10
    h = kp.hash_keys(["k3", "k7", "absent"])
    hi = (h >> np.uint64(32)).astype(np.uint32)
    lo = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    ids = np.asarray(kp.plane_lookup(mat, hi, lo))
    assert ids.tolist() == [4, 8, kp.MISS]
    assert int(kp.plane_and_count(mat, hi, lo)) == 2
    sub, _, _ = kp.build_plane({f"k{i}": i + 1 for i in range(5)})
    assert int(kp.plane_pair_count(sub, mat)) == 5


# ---------------------------------------------------------------------------
# replica-local read path (cluster/translate_sync.py)
# ---------------------------------------------------------------------------


class _CountingClient:
    """Transparent client proxy counting forward-translate RPCs."""

    def __init__(self, inner):
        self._inner = inner
        self.translate_calls = 0

    def translate_keys(self, *a, **kw):
        self.translate_calls += 1
        return self._inner.translate_keys(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_replica_synced_keys_zero_coordinator_calls():
    """Keys at or below the replication watermark resolve on the replica
    with ZERO coordinator RPCs; a batch with genuine misses costs
    exactly ONE batched RPC, not one per key."""
    from pilosa_tpu.cluster.harness import LocalCluster

    lc = LocalCluster(3)
    lc.create_index("k", IndexOptions(keys=True))
    lc.create_field("k", "f", FieldOptions(keys=True))
    synced = [f"s{i}" for i in range(10)]
    want = lc.nodes[0].translator("k", "f", synced)   # coordinator allocates
    lc.sync_translation()

    replica = lc.nodes[1].translator
    counting = _CountingClient(replica.client)
    replica.client = counting
    assert replica("k", "f", synced) == want
    assert replica("k", "f", list(reversed(synced))) == list(reversed(want))
    assert counting.translate_calls == 0
    # Mixed batch: the three misses travel in ONE RPC.
    got = replica("k", "f", synced[:2] + ["n1", "n2", "n3"])
    assert got[:2] == want[:2]
    assert len(set(got)) == 5
    assert counting.translate_calls == 1
    # The applied entries make the new keys replica-local too.
    assert replica("k", "f", ["n1", "n2", "n3"]) == got[2:]
    assert counting.translate_calls == 1


def test_entries_since_is_delta_not_full_scan():
    """Satellite (b): entries_since returns exactly the suffix after the
    cursor from the id-ordered log."""
    store = TranslateStore()
    store.translate_keys([f"k{i}" for i in range(20)])   # ids 1..20
    assert store.entries_since(20) == []
    tail = store.entries_since(17)
    assert tail == [(18, "k17"), (19, "k18"), (20, "k19")]
    assert [i for i, _ in store.entries_since(0)] == list(range(1, 21))
    # Out-of-order apply keeps the log id-sorted for later cursors.
    replica = TranslateStore()
    replica.apply_entries([(5, "k4"), (2, "k1")])
    assert replica.entries_since(0) == [(2, "k1"), (5, "k4")]
    assert replica.entries_since(2) == [(5, "k4")]
