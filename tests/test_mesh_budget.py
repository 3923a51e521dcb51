"""The planner's budget is bytes a device (parallel/stacks, planner).

A row stack sharded over four devices is charged the quarter it places
on each, so four devices hold four times what one holds; on one device
every charge and eviction is what the whole array's bytes gave. Also the
counts of what crosses the link: ``upload_bytes`` of ``/debug/device``
(host to device, every stack upload) and the counter ``transfer.bytes``
with the span ``transfer.pull`` (every result's pull, device to host)."""

from collections import OrderedDict

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.exec import Executor, residency
from pilosa_tpu.obs import MemoryStats
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.parallel.batcher import TransferBatcher
from pilosa_tpu.parallel.mesh import SHARD_AXIS
from pilosa_tpu.parallel.stacks import StackKey, StackStore, device_charge

N_SHARDS = 8
ROWS = 4
#: distinct bits a shard row: over the 4,096 that `auto` packs, so dense.
BITS = 5000
FIELDS = ("f", "g")
OPS = ("Intersect", "Union", "Difference", "Xor")
#: one dense stack over the 8 shards: 8 x 128 KiB.
STACK_BYTES = residency.dense_nbytes(N_SHARDS)
#: every row of both fields on four devices, a quarter of them on one.
BUDGET = len(FIELDS) * ROWS * STACK_BYTES // 4


@pytest.fixture(scope="module")
def index():
    """Two fields of ``ROWS`` dense rows over ``N_SHARDS`` shards, and
    each row's columns as a set for the reference."""
    rng = np.random.default_rng(4242)
    h = Holder()
    idx = h.create_index("mb")
    cols: dict = {}
    for name in FIELDS:
        field = idx.create_field(name)
        for r in range(ROWS):
            per_shard = [s * SHARD_WIDTH + rng.choice(SHARD_WIDTH, BITS,
                                                      replace=False)
                         for s in range(N_SHARDS)]
            c = np.concatenate(per_shard).astype(np.uint64)
            field.import_bits(np.full(len(c), r, dtype=np.uint64), c)
            cols[name, r] = set(c.tolist())
    return h, cols


def _served(h, n_devices):
    stats = MemoryStats()
    planner = MeshPlanner(h, make_mesh(n=n_devices), max_cache_bytes=BUDGET,
                          stats=stats)
    # the executor's spans are the outermost on the caller's thread: a
    # pull nested in them folds into their registry
    return planner, Executor(h, planner=planner, stats=stats,
                             result_cache=False), stats


def _count(e, q):
    (n,) = e.execute("mb", q, shards=list(range(N_SHARDS)))
    return n


def _every_row(e):
    for name in FIELDS:
        for r in range(ROWS):
            _count(e, f"Count(Row({name}={r}))")


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_second_pass_uploads_nothing_where_four_devices_hold_every_row(
        index, n_devices):
    h, _ = index
    planner, e, stats = _served(h, n_devices)
    try:
        _every_row(e)
        first = planner.cache_stats()
        _every_row(e)
        second = planner.cache_stats()
        uploads = second["uploads"] - first["uploads"]
        evictions = second["evictions"] - first["evictions"]
        if n_devices == 4:
            assert (uploads, evictions) == (0, 0)
            assert second["entries"] == len(FIELDS) * ROWS
            assert second["fullest_device_bytes"] == BUDGET
        else:
            # a quarter fits: LRU over a cycle of eight misses every time
            assert uploads == evictions == len(FIELDS) * ROWS
            assert second["entries"] == len(FIELDS) * ROWS // 4
        held = planner.device_debug()["perDeviceBytes"]
        assert len(held) == n_devices
        assert max(held.values()) <= BUDGET
        assert second["fullest_device_bytes"] <= second["budget_bytes"]
        assert second["bytes"] == second["entries"] * STACK_BYTES
        debug = planner.device_debug()
        assert debug["budget_bytes"] == BUDGET
        assert debug["fullest_device_bytes"] == second["fullest_device_bytes"]
    finally:
        planner.close()


def _reference(op, a, b):
    if op == "Intersect":
        return a & b
    if op == "Union":
        return a | b
    if op == "Difference":
        return a - b
    return a ^ b


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("op", OPS)
def test_count_trees_equal_the_reference_on_both_meshes(index, op,
                                                        n_devices):
    h, cols = index
    planner, e, _ = _served(h, n_devices)
    rng = np.random.default_rng([7, OPS.index(op), n_devices])
    try:
        for _ in range(3):
            a, b, c = (int(x) for x in rng.integers(0, ROWS, 3))
            two = _reference(op, cols["f", a], cols["g", b])
            assert _count(e, f"Count({op}(Row(f={a}), Row(g={b})))") \
                == len(two)
            three = _reference(op, two, cols["f", c])
            assert _count(e, f"Count({op}({op}(Row(f={a}), Row(g={b})), "
                             f"Row(f={c})))") == len(three)
    finally:
        planner.close()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_upload_bytes_are_the_uploaded_stacks(index, n_devices):
    h, _ = index
    planner, e, _ = _served(h, n_devices)
    try:
        _every_row(e)
        _every_row(e)
        snap = planner.cache_stats()
        assert snap["uploads"] >= len(FIELDS) * ROWS
        assert planner.device_debug()["upload_bytes"] \
            == snap["uploads"] * STACK_BYTES == snap["upload_bytes"]
    finally:
        planner.close()


@pytest.mark.parametrize("inline", ["off", "auto", "on"])
@pytest.mark.parametrize("n_devices", [1, 4])
def test_transfer_bytes_are_the_pulls_times_the_result(index, n_devices,
                                                       inline, monkeypatch):
    """Whichever thread pulls a wave (the resolver, or with ``auto`` and
    ``on`` the waiter that steals it), the pull is counted once."""
    monkeypatch.setenv("PILOSA_TPU_INLINE_TRANSFER", inline)
    h, _ = index
    planner, e, stats = _served(h, n_devices)
    try:
        _every_row(e)
        _count(e, "Count(Xor(Row(f=0), Row(g=1)))")
        pulls = stats.counter_value("span.transfer.pull.count")
        assert pulls == len(FIELDS) * ROWS + 1
        # a Count's result: one int32 popcount a padded shard slot
        assert stats.counter_value("transfer.bytes") \
            == pulls * planner._pad(N_SHARDS) * 4
        assert stats.counter_value("span.transfer.pull.wallSeconds") > 0
        stolen = planner.batcher.inline_resolved
        if inline == "off":
            assert stolen == 0
        elif inline == "on":
            assert stolen > 0   # waiters pulled their own waves
    finally:
        planner.close()


def test_a_pull_after_close_is_counted():
    stats = MemoryStats()
    batcher = TransferBatcher(stats=stats)
    batcher.close()
    arr = jax.device_put(np.arange(256, dtype=np.int32))
    assert batcher.submit(arr, lambda host: int(host.sum())).result() \
        == int(np.arange(256).sum())
    assert stats.counter_value("span.transfer.pull.count") == 1
    assert stats.counter_value("transfer.bytes") == arr.nbytes


def test_the_budget_is_counted_on_one_device(index):
    h, _ = index
    for n in (1, 2, 4):
        planner = MeshPlanner(h, make_mesh(n=n), max_cache_bytes=BUDGET)
        try:
            assert planner._dense_device_bytes(N_SHARDS) == STACK_BYTES // n
            fit = BUDGET // (STACK_BYTES // n)
            assert planner._stacks_fit(fit, N_SHARDS)
            assert not planner._stacks_fit(fit + 1, N_SHARDS)
        finally:
            planner.close()


# -- one device: the charges are the global bytes ---------------------------


def _global_rule(budget, inserts):
    """The budget as it was counted before it was a device's: each entry
    charged its whole bytes, insert first, evict the least recently
    used after, never the last entry. -> (resident keys, evictions)."""
    held: OrderedDict = OrderedDict()
    evictions = 0
    for tag, nbytes in inserts:
        held.pop(tag, None)
        held[tag] = nbytes
        while sum(held.values()) > budget and len(held) > 1:
            held.popitem(last=False)
            evictions += 1
    return list(held), evictions


def _one_device_array(kind, rows, rng):
    dev = jax.devices()[0]
    if kind == "dense":
        host = rng.integers(0, 2**32, (rows, 64), dtype=np.uint32)
        return jax.device_put(host, NamedSharding(
            make_mesh(n=1), PartitionSpec(SHARD_AXIS, None)))
    if kind == "packed":
        return jax.device_put(
            rng.integers(0, 2**20, (rows, 16), dtype=np.int32), dev)
    return jax.device_put(rng.integers(0, 255, rows * 48, dtype=np.uint8),
                          dev)


@pytest.mark.parametrize("kind", ["dense", "packed", "plane"])
@pytest.mark.parametrize("budget", [1 << 10, 5 << 10, 1 << 20])
def test_one_device_charges_and_evictions_equal_the_global_rule(kind,
                                                                budget):
    rng = np.random.default_rng([budget, len(kind)])
    store = StackStore(budget, ("dense", "packed", "plane"))
    inserts = []
    for step in range(24):
        tag = int(rng.integers(0, 9))
        arr = _one_device_array(kind, int(rng.integers(1, 9)), rng)
        assert device_charge(arr, arr.nbytes) == arr.nbytes
        store.insert(StackKey("i", 1, "f", "standard", tag, (0,), kind),
                     1, (), arr, int(arr.nbytes))
        inserts.append((tag, int(arr.nbytes)))
        snap = store.snapshot()
        assert snap["fullest_device_bytes"] == snap["bytes"]
    keys, evictions = _global_rule(budget, inserts)
    assert [k.tag for k in store.keys()] == keys
    assert store.snapshot()["evictions"] == evictions


def test_a_sharded_array_is_charged_its_fullest_device():
    mesh = make_mesh(n=4)
    sharded = jax.device_put(np.zeros((8, 64), dtype=np.uint32),
                             NamedSharding(mesh, PartitionSpec(SHARD_AXIS)))
    replicated = jax.device_put(np.zeros((8, 64), dtype=np.uint32),
                                NamedSharding(mesh, PartitionSpec()))
    assert device_charge(sharded, sharded.nbytes) == sharded.nbytes // 4
    assert device_charge(replicated, replicated.nbytes) == replicated.nbytes
