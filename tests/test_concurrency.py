"""Concurrency-correctness tests (VERDICT r2 weak #1).

The r2 MeshPlanner stashed the current index in instance state
(self._index_name) read later during leaf fetch; two queries to
different indexes through the threaded HTTP server could interleave and
return (and CACHE) one index's counts under the other's key. These tests
hammer exactly that interleaving.
"""

import json
import threading
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.server.node import ServerNode


def test_planner_two_index_race_direct():
    """Two threads, two indexes, one planner: every answer must match the
    single-threaded truth. Pre-fix this failed within a few hundred
    iterations (index A served index B's cached stacks)."""
    h = Holder()
    counts = {}
    for name, n_bits in (("ia", 37), ("ib", 91)):
        idx = h.create_index(name)
        f = idx.create_field("f")
        cols = np.arange(n_bits, dtype=np.uint64) * 17
        f.import_bits(np.ones(n_bits, dtype=np.uint64), cols)
        counts[name] = n_bits
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    q = "Count(Row(f=1))"
    for name in counts:
        assert ex.execute(name, q) == [counts[name]]

    errors = []
    barrier = threading.Barrier(4)

    def worker(name):
        barrier.wait()
        for i in range(150):
            # Bypass the result cache so the planner path runs every time.
            got = ex.execute(name, q, cache=False)
            if got != [counts[name]]:
                errors.append((name, i, got))
                return

    threads = [threading.Thread(target=worker, args=(n,))
               for n in ("ia", "ib") for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]


def test_planner_two_index_race_http():
    """Same interleaving through one ServerNode's ThreadingHTTPServer."""
    n = ServerNode(bind="127.0.0.1:0", use_planner=True)
    n.open()
    try:
        base = n.address

        def post(path, body=""):
            r = urllib.request.Request(base + path, data=body.encode(),
                                       method="POST")
            with urllib.request.urlopen(r, timeout=10) as resp:
                return json.loads(resp.read() or b"{}")

        expect = {}
        for name, n_bits in (("ra", 23), ("rb", 57)):
            post(f"/index/{name}")
            post(f"/index/{name}/field/f")
            body = json.dumps({"rowIDs": [1] * n_bits,
                               "columnIDs": list(range(0, n_bits * 11, 11))})
            post(f"/index/{name}/field/f/import", body)
            expect[name] = n_bits

        errors = []
        barrier = threading.Barrier(4)

        def worker(name):
            barrier.wait()
            for i in range(60):
                got = post(f"/index/{name}/query", "Count(Row(f=1))")
                if got != {"results": [expect[name]]}:
                    errors.append((name, i, got))
                    return

        threads = [threading.Thread(target=worker, args=(nm,))
                   for nm in ("ra", "rb") for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
    finally:
        n.close()


def test_result_cache_invalidation_on_write():
    """Cached read results must die on ANY write to the index: bits,
    clears, BSI values, attrs."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    f.import_bits([1, 1, 1], [0, 10, 20])
    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    q = "Count(Row(f=1))"
    assert ex.execute("i", q) == [3]
    assert ex.execute("i", q) == [3]          # cache hit
    f.set_bit(1, 30)
    assert ex.execute("i", q) == [4]          # invalidated by write
    f.clear_bit(1, 0)
    assert ex.execute("i", q) == [3]
    # Attr writes invalidate too (they change Row()/TopN payloads).
    ex.execute("i", "Row(f=1)")
    f.row_attr_store.set_attrs(1, {"color": "red"})
    (row,) = ex.execute("i", "Row(f=1)")
    assert row.attrs == {"color": "red"}


def test_result_cache_write_queries_not_cached():
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    assert ex.execute("i", "Set(1, f=1)") == [True]
    assert ex.execute("i", "Set(1, f=1)") == [False]  # not served from cache
    assert ex.execute("i", "Count(Row(f=1))") == [1]


def test_execute_async_matches_sync():
    """Threads calling execute get what serial calls get: concurrent
    Counts of one plan share launches through the coalescer, and a
    query the planner does not answer whole runs beside them."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    f.import_bits(np.ones(50, dtype=np.uint64),
                  np.arange(50, dtype=np.uint64) * 3)
    g.import_bits(np.full(80, 2, dtype=np.uint64),
                  np.arange(80, dtype=np.uint64) * 2)
    ex = Executor(h, planner=MeshPlanner(h, make_mesh(jax.devices()[:1])))
    q = "Count(Intersect(Row(f=1), Row(g=2)))"
    topn = "TopN(f, n=2)"
    want = {q: ex.execute("i", q), topn: ex.execute("i", topn)}
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(lambda t: (t, ex.execute("i", t, cache=False)),
                            [q] * 40 + [topn] * 4))
    assert all(res == want[t] for t, res in got)


def test_batcher_mixed_shapes():
    from pilosa_tpu.parallel.batcher import TransferBatcher
    import jax
    import jax.numpy as jnp

    bt = TransferBatcher()
    futs = []
    for i in range(1, 40):
        arr = jax.device_put(np.full(i % 5 + 1, i, dtype=np.int32))
        futs.append((i, bt.submit(arr, lambda host, i=i: host.sum())))
    for i, fut in futs:
        assert fut.result() == i * (i % 5 + 1)
    bt.close()


def test_batcher_close_drains_and_joins():
    """Regression: close() must wake the resolver, wait for every queued
    future to resolve (no futures dropped on shutdown), and leave later
    submits resolving synchronously. Double-close is safe."""
    import jax
    from pilosa_tpu.parallel.batcher import TransferBatcher

    bt = TransferBatcher()
    futs = [bt.submit(jax.device_put(np.full(3, i, dtype=np.int32)),
                      lambda host: host.sum())
            for i in range(50)]
    bt.close()
    # the resolver thread has fully exited...
    assert bt._thread is not None and not bt._thread.is_alive()
    # ...and nothing it owned was dropped
    assert all(f.done() for f in futs)
    assert [f.result() for f in futs] == [3 * i for i in range(50)]
    # post-close submits resolve synchronously on the caller's thread
    fut = bt.submit(jax.device_put(np.arange(4, dtype=np.int32)),
                    lambda host: int(host.max()))
    assert fut.done() and fut.result() == 3
    # post-close failures surface on the future, not the caller
    bad = bt.submit(jax.device_put(np.arange(2, dtype=np.int32)),
                    lambda host: 1 / 0)
    assert isinstance(bad.exception(), ZeroDivisionError)
    bt.close()  # idempotent


def test_result_cache_index_recreate():
    """A deleted-and-recreated index must never serve its predecessor's
    cached results, even at an identical epoch value."""
    h = Holder()
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    idx = h.create_index("i")
    f = idx.create_field("f")
    f.import_bits([1, 1], [0, 7])
    assert ex.execute("i", "Count(Row(f=1))") == [2]
    old_epoch = idx.epoch.value
    h.delete_index("i")
    idx2 = h.create_index("i")
    f2 = idx2.create_field("f")
    f2.import_bits([1], [3])
    # Reach exactly the same epoch value with different data (the
    # per-import bump count is an implementation detail; line up the
    # remainder manually).
    while idx2.epoch.value < old_epoch:
        idx2.epoch.bump()
    assert idx2.epoch.value == old_epoch, \
        "test setup: recreate overshot the original epoch"
    assert ex.execute("i", "Count(Row(f=1))") == [1]


def test_mutex_import_duplicate_column_last_wins(rng):
    """Batch mutex import keeps input order: the LAST row for a column
    wins, matching sequential set_bit semantics."""
    from pilosa_tpu.core import FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_MUTEX
    h = Holder()
    idx = h.create_index("m")
    f = idx.create_field("f", FieldOptions(type=FIELD_TYPE_MUTEX))
    f.import_bits([5, 2], [10, 10])
    frag = h.fragment("m", "f", "standard", 0)
    assert frag.row_for_column(10) == 2


def test_import_values_empty_batch():
    from pilosa_tpu.core import FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    h = Holder()
    idx = h.create_index("i")
    v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                           min=0, max=100))
    v.import_values([], [])                  # no-op, no crash
    v.import_values([], [], clear=True)      # regression: IndexError


def test_options_wrapped_write_not_cached():
    """Writes hidden under Options() must never be served from cache
    (the cacheability check recurses the whole call tree)."""
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    q = "Options(Set(1, f=1), shards=[0])"
    assert ex.execute("i", q) == [True]
    assert ex.execute("i", q) == [False]     # executed again, not cached
    assert ex.execute("i", "Count(Row(f=1))") == [1]


def test_cluster_coordinator_cache_invalidated_by_owner_write():
    """Cluster-mode coordinator caching is ON (r4): a write applied
    directly on another owner invalidates node 0's cached read once the
    owner's index-dirty broadcast lands (deterministic here via
    flush_now; production pays the coalesce window)."""
    from pilosa_tpu.cluster.harness import LocalCluster
    lc = LocalCluster(3, replica_n=1)
    lc.create_index("i")
    lc.create_field("i", "f")
    lc.query("i", "Set(1, f=1)")
    assert lc.query("i", "Count(Row(f=1))") == [1]
    # Mutate an owner's fragment behind node 0's back (write through a
    # different node / direct owner apply).
    owner = lc[0].cluster.shard_nodes("i", 0)[0]
    lc.client.peers[owner.id].holder.fragment(
        "i", "f", "standard", 0).set_bit(1, 7)
    lc.client.peers[owner.id].dirty.flush_now()
    assert lc.query("i", "Count(Row(f=1))") == [2]  # no stale cache


_SW = SHARD_WIDTH
_FIVE = [0, 1, _SW, _SW + 1, 2 * _SW]


@pytest.mark.parametrize("bits,query,steps", [
    ({"f": {1: [0, 5]}, "g": {2: [0, 9]}},
     "Count(Intersect(Row(f=1), Row(g=2)))",
     [(None, 1), (None, 1), "Set(5, g=2)", (None, 2)]),
    ({"f": {1: [1]}}, "Count(Row(f=1))",
     [(None, 1), (None, 1), "Set(2, f=1)", (None, 2)]),
    ({"f": {1: _FIVE}, "g": {2: _FIVE}},
     "Count(Intersect(Row(f=1), Row(g=2)))",
     [(None, 5), (None, 5), f"Set({3 * _SW}, f=1)", f"Set({3 * _SW}, g=2)",
      (None, 6), ([0], 2)]),
    ({"f": {1: [0, _SW, 2 * _SW]}}, "Count(Row(f=1))",
     [([0], 1), (None, 3), (None, 3)]),
], ids=["intersect", "recount", "new_shard", "shard_subset_then_full"])
def test_plan_cache_invalidated_by_write(bits, query, steps):
    """Prepared plans (program + leaf descriptors) must not answer past
    a write, nor for another shard set: the leaves are fetched anew
    every call, and the plan cache is keyed by the shards. A step is a
    write, or (shards, count) with None for the index's own shards."""
    h = Holder()
    idx = h.create_index("i")
    for name, rows in bits.items():
        fld = idx.create_field(name)
        for row, cols in rows.items():
            fld.import_bits([row] * len(cols), cols)
    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    for step in steps:
        if isinstance(step, str):
            ex.execute("i", step)
            continue
        shards, want = step
        assert ex.execute("i", query, shards=shards, cache=False) == [want]


@pytest.mark.parametrize("change", ["bsi_depth", "index_recreate"])
def test_plan_cache_invalidated_by_schema_change(change):
    """Prepared plans bake BSI structure (bit depth, base folds) and the
    index's identity: a field recreated, its bit depth grown in place,
    or the index deleted and made again must all miss the cache."""
    from pilosa_tpu.core import FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    h = Holder()
    idx = h.create_index("i")
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    if change == "index_recreate":
        idx.create_field("f").import_bits([1, 1], [0, _SW])
        q = "Count(Row(f=1))"
        assert ex.execute("i", q, cache=False) == [2]
        assert ex.execute("i", q, cache=False) == [2]
        h.delete_index("i")
        h.create_index("i").create_field("f")
        assert ex.execute("i", q, cache=False) == [0]
        return
    opts = FieldOptions(type=FIELD_TYPE_INT, min=0, max=7)
    v = idx.create_field("v", opts)
    v.import_values([1, 2], [5, 6])
    q = "Count(Row(v > 4))"
    assert ex.execute("i", q, cache=False) == [2]
    # Recreate with a much wider range (deeper BSI).
    idx.delete_field("v")
    v2 = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                            min=0, max=1000))
    v2.import_values([1, 2], [5, 500])
    assert ex.execute("i", q, cache=False) == [2]   # 5 and 500, new depth
    # In-place bit-depth growth (field.py grows on import) also misses.
    v2.import_values([3], [900])
    assert ex.execute("i", "Count(Row(v > 800))", cache=False) == [1]


def test_concurrent_writers_and_readers_converge():
    """4 writer + 4 reader threads through one executor: no crashes, no
    impossible counts mid-flight, exact convergence at the end."""
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    per_writer = 60
    n_writers = 4
    errors = []
    barrier = threading.Barrier(n_writers + 4)

    def writer(w):
        barrier.wait()
        for i in range(per_writer):
            col = w * per_writer + i
            try:
                ex.execute("i", f"Set({col}, f=1)")
            except Exception as e:  # pragma: no cover
                errors.append(("w", w, repr(e)))
                return

    def reader():
        barrier.wait()
        last = 0
        for _ in range(80):
            try:
                (n,) = ex.execute("i", "Count(Row(f=1))", cache=False)
            except Exception as e:  # pragma: no cover
                errors.append(("r", repr(e)))
                return
            if not (0 <= n <= n_writers * per_writer) or n < last:
                # counts may lag but must be sane and monotone here
                # (single field, set-only workload)
                errors.append(("r", "non-monotone", last, n))
                return
            last = n

    threads = ([threading.Thread(target=writer, args=(w,))
                for w in range(n_writers)]
               + [threading.Thread(target=reader) for _ in range(4)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    assert ex.execute("i", "Count(Row(f=1))", cache=False) == \
        [n_writers * per_writer]
    planner.close()


def test_filtered_topn_follows_rows_written_under_it():
    """Readers rank ``f`` under a filter through the planner's
    one-program route while writers set bits of rows that did not exist
    a moment before: the candidate rows are kept by index epoch
    (`MeshPlanner._field_rows`), so a lost update there would leave a
    new row out of the ranking for good. More threads than cores, a
    short switch interval; no count may fall, and the last answer is the
    per-shard interpreter's."""
    import sys

    import jax

    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.obs import MemoryStats

    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    stats = MemoryStats()
    # One device: rows of a few bits are packed leaves, whose expansion
    # is a collective on a mesh, and the CPU backend's collectives miss
    # their rendezvous when many threads launch them at once.
    planner = MeshPlanner(h, make_mesh(jax.devices()[:1]), stats=stats)
    ex = Executor(h, planner=planner, stats=stats)
    n_writers, n_readers, per_writer = 4, 12, 40
    q = "TopN(f, Row(g=1))"
    errors = []
    barrier = threading.Barrier(n_writers + n_readers)

    def writer(w):
        barrier.wait()
        for i in range(per_writer):
            col = (w * per_writer + i) * 3 + (i % 2) * SHARD_WIDTH
            try:
                # writer w's i-th bit opens row 4 * (i // 8) + w
                ex.execute("i", f"Set({col}, f={4 * (i // 8) + w})")
                ex.execute("i", f"Set({col}, g=1)")
            except Exception as e:  # pragma: no cover
                errors.append(("w", w, repr(e)))
                return

    def reader():
        barrier.wait()
        last = {}
        for _ in range(25):
            try:
                (pairs,) = ex.execute("i", q, cache=False)
            except Exception as e:  # pragma: no cover
                errors.append(("r", repr(e)))
                return
            now = {p.id: p.count for p in pairs}
            if any(now.get(r, 0) < n for r, n in last.items()):
                errors.append(("r", "a count fell", last, now))
                return
            last = now

    threads = ([threading.Thread(target=writer, args=(w,))
                for w in range(n_writers)]
               + [threading.Thread(target=reader) for _ in range(n_readers)])
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    (got,) = ex.execute("i", q, cache=False)
    assert got == Executor(h).execute("i", q)[0]
    assert {p.id: p.count for p in got} == {r: 8 for r in range(20)}
    # (a reader that ran before any row of f existed had nothing to stack)
    assert stats.counter_value("planner.topn.passesStacked") > 0
    planner.close()


def test_plan_cache_invalidated_by_set_value_depth_growth():
    """Single-value Set() grows BSI depth too — must also miss plans."""
    from pilosa_tpu.core import FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    h = Holder()
    idx = h.create_index("i")
    v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                           min=0, max=1000))
    v.set_value(1, 5)
    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    q = "Count(Row(v > 4))"
    assert ex.execute("i", q, cache=False) == [1]   # plan cached, depth 3
    v.set_value(2, 900)                             # grows depth in place
    assert ex.execute("i", q, cache=False) == [2]


def test_mixed_workload_soak(rng):
    """Mixed-operation soak over the planner path: bulk imports (scatter
    + pool-backed blocks + batched epoch bumps), BSI value imports,
    async prepared Counts, TopN, field delete/recreate, and cache churn
    all racing on one executor. Guards the interactions the bulk-import
    optimizations introduced: deferred epoch bumps must never let a
    stale cached count survive a completed import, and pool chunk
    recycling must never hand a live fragment's storage to another
    allocation."""
    h = Holder()
    idx = h.create_index("soak")
    idx.create_field("f")
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    stop = threading.Event()
    errors = []

    def importer():
        g = np.random.default_rng(1)
        total = 0
        while not stop.is_set():
            n = 20_000
            cols = g.integers(0, 4 << 20, n, dtype=np.uint64)
            try:
                idx.field("f").import_bits(
                    np.broadcast_to(np.uint64(1), n), cols)
                total += 1
                # Immediately after an import completes, a cache-bypassed
                # count must reflect SOME state >= what a fresh epoch
                # yields — i.e. executing may never raise or regress
                # below the pre-import count of a set-only workload.
                (c,) = ex.execute("soak", "Count(Row(f=1))", cache=False)
                if c <= 0:
                    errors.append(("imp", "empty after import", c))
                    return
            except Exception as e:
                errors.append(("imp", repr(e)))
                return

    def bsi_churn():
        g = np.random.default_rng(2)
        k = 0
        while not stop.is_set():
            name = f"v{k % 2}"
            k += 1
            try:
                from pilosa_tpu.core import FieldOptions
                from pilosa_tpu.core.field import FIELD_TYPE_INT
                fld = idx.create_field_if_not_exists(
                    name, FieldOptions(type=FIELD_TYPE_INT,
                                       min=-500, max=500))
                cols = g.choice(1 << 20, 5_000, replace=False).astype(
                    np.uint64)
                fld.import_values(cols, g.integers(-500, 500, 5_000))
                ex.execute("soak", f"Sum(field={name})", cache=False)
                idx.delete_field(name)
            except Exception as e:
                errors.append(("bsi", repr(e)))
                return

    def reader():
        last = 0
        while not stop.is_set():
            try:
                vals = [ex.execute("soak", "Count(Row(f=1))",
                                   cache=False)[0] for _ in range(8)]
                ex.execute("soak", "TopN(f, n=3)")
                ex.execute("soak", "Count(Row(f=1))")  # cached path
            except Exception as e:
                errors.append(("rd", repr(e)))
                return
            m = max(vals)
            if m < last:  # set-only single row: counts never shrink
                errors.append(("rd", "regressed", last, m))
                return
            last = m

    threads = [threading.Thread(target=importer),
               threading.Thread(target=bsi_churn),
               threading.Thread(target=reader),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    import time
    time.sleep(6.0)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    # Final exact check: cached and uncached agree post-quiesce.
    a = ex.execute("soak", "Count(Row(f=1))", cache=False)
    b = ex.execute("soak", "Count(Row(f=1))", cache=False)
    assert a == b and a[0] > 0
    planner.close()
