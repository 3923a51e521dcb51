"""One shard set a request (core.shardset): the interning, the index's
own set, and what the plan cache and the stack store do with it.

The interning is an economy, never a guarantee: every case that rests
on identity also shows that equal contents alone find the same plan or
stack, and that different contents never do.
"""

import pickle

import numpy as np
import pytest

import jax

from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.core import Holder, shardset
from pilosa_tpu.core.shardset import ShardSet, as_shard_set
from pilosa_tpu.exec import Executor
from pilosa_tpu.obs import MemoryStats
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.parallel.stacks import StackKey, StackStore
from pilosa_tpu.pql.ast import Call

Q2 = "Count(Intersect(Row(f=1), Row(g=2)))"


@pytest.fixture(autouse=True)
def fresh_table(monkeypatch):
    """Each case starts from an empty table of its own."""
    monkeypatch.setattr(shardset, "_table", type(shardset._table)())


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return make_mesh()


def load(idx, n_shards=3):
    """Fields f and g with row 1 / row 2 set on three columns a shard."""
    f = idx.create_field("f")
    g = idx.create_field("g")
    cols = np.concatenate([s * SHARD_WIDTH + np.array([0, 5, 9])
                           for s in range(n_shards)])
    f.import_bits(np.full(len(cols), 1), cols)
    g.import_bits(np.full(len(cols), 2), cols[::2])
    return f, g


class FakeArray:
    nbytes = 8


# -- the type and the table -----------------------------------------------

@pytest.mark.parametrize("spelling", [
    [0, 1, 2, 5],
    (0, 1, 2, 5),
    [5, 2, 1, 0],
    [0, 1, 1, 2, 5, 5],
    np.array([0, 1, 2, 5], dtype=np.uint64),
    iter([2, 0, 5, 1]),
    {0, 1, 2, 5},
])
def test_equal_contents_intern_to_one_object(spelling):
    first = as_shard_set([0, 1, 2, 5])
    got = as_shard_set(spelling)
    assert got is first
    assert tuple(got) == (0, 1, 2, 5)
    assert all(type(s) is int for s in got)
    # What already is one passes through untouched.
    assert as_shard_set(got) is got


def test_shard_set_is_an_immutable_tuple_that_equals_its_contents():
    s = as_shard_set([3, 1, 2])
    assert isinstance(s, tuple) and len(s) == 3 and s[0] == 1 and 2 in s
    assert s == (1, 2, 3) and (1, 2, 3) == s and not s != (1, 2, 3)
    assert hash(s) == hash((1, 2, 3))
    assert s != (1, 2) and s != (1, 2, 4) and s != [1, 2, 3]
    assert {(1, 2, 3): "x"}[s] == "x" and {s: "y"}[(1, 2, 3)] == "y"
    with pytest.raises(TypeError):
        s[0] = 9
    back = pickle.loads(pickle.dumps(s))
    assert type(back) is ShardSet and back == s and hash(back) == hash(s)
    assert not shardset.EMPTY and as_shard_set([]) == shardset.EMPTY


def test_set_fallen_out_of_the_table_equals_and_hashes_as_its_twin():
    old = as_shard_set([1, 2, 3])
    for i in range(shardset.TABLE_SIZE):
        as_shard_set([1000 + i])
    twin = as_shard_set([1, 2, 3])
    assert twin is not old            # the table let the first one go
    assert twin == old and old == twin and hash(twin) == hash(old)
    assert not twin != old
    # ... so a stack or a plan keyed by the one is found by the other.
    store = StackStore(1 << 20, ("dense",))
    arr = FakeArray()
    store.insert(StackKey("i", 1, "f", "standard", 1, old, "dense"),
                 7, (), arr, 8)
    assert store.get(StackKey("i", 1, "f", "standard", 1, twin, "dense"),
                     7) is arr


def test_table_stays_within_its_bound_under_1000_distinct_subsets():
    rng = np.random.default_rng(5)
    kept = []
    for i in range(1000):
        ids = rng.choice(954, size=int(rng.integers(1, 40)), replace=False)
        s = as_shard_set(ids.tolist() + [2000 + i])
        assert tuple(s) == tuple(sorted(set(ids.tolist()) | {2000 + i}))
        kept.append(s)
        assert len(shardset._table) <= shardset.TABLE_SIZE
    assert len(shardset._table) == shardset.TABLE_SIZE
    # Least recently used out first: the newest are still there.
    assert as_shard_set(list(kept[-1])) is kept[-1]
    assert as_shard_set(list(kept[0])) is not kept[0]


def test_table_keeps_what_is_used():
    hot = as_shard_set([7, 8])
    for i in range(4 * shardset.TABLE_SIZE):
        as_shard_set([100 + i])
        assert as_shard_set([7, 8]) is hot


# -- the index's own set ---------------------------------------------------

def test_write_into_an_existing_shard_keeps_the_index_set_the_same_object():
    idx = Holder().create_index("i")
    f, _ = load(idx)
    before = idx.shard_set()
    assert tuple(before) == (0, 1, 2) and idx.shard_set() is before
    epoch = idx.epoch.value
    f.import_bits([1], [SHARD_WIDTH + 77])
    assert idx.epoch.value != epoch          # the memo's stamp moved,
    assert idx.shard_set() is before         # the set did not
    idx.create_field("h")                    # nor for a schema change
    assert idx.shard_set() is before
    assert idx.available_shards() == {0, 1, 2}
    idx.available_shards().add(99)           # a copy of the caller's own
    assert idx.shard_set() is before


def test_write_into_a_new_shard_issues_a_new_set_that_contains_it():
    idx = Holder().create_index("i")
    f, _ = load(idx)
    before = idx.shard_set()
    f.import_bits([1], [6 * SHARD_WIDTH + 1])
    after = idx.shard_set()
    assert after is not before and after != before
    assert tuple(after) == (0, 1, 2, 6) and tuple(before) == (0, 1, 2)
    assert idx.shard_set() is after
    # An explicit list of the same shards is the index's object.
    assert as_shard_set([0, 1, 2, 6]) is after
    assert Holder().create_index("empty").shard_set() == (0,)


def test_counters_issued_and_reused():
    stats = MemoryStats()
    idx = Holder().create_index("i")
    f, _ = load(idx)

    def read():
        return (stats.counter_value(shardset.ISSUED),
                stats.counter_value(shardset.REUSED))

    idx.shard_set(stats)
    assert read() == (1, 0)
    idx.shard_set(stats)                       # by identity, on the index
    f.import_bits([1], [3])
    idx.shard_set(stats)                       # epoch moved, contents not
    assert read() == (1, 2)
    as_shard_set([0, 1, 2], stats)             # by content, in the table
    as_shard_set([0, 1], stats)                # a new subset
    as_shard_set([1, 0, 0], stats)             # found after sorting
    assert read() == (2, 4)
    as_shard_set(idx.shard_set(), stats)       # passes through: no count
    assert read() == (2, 4)


# -- plans and stacks: a set answers for its own shards only ---------------

def test_subset_never_finds_the_full_sets_stack_nor_the_reverse():
    store = StackStore(1 << 20, ("dense",))
    full, sub = as_shard_set([0, 1, 2]), as_shard_set([0, 1])

    def key(shards):
        return StackKey("i", 1, "f", "standard", 1, shards, "dense")

    a, b = FakeArray(), FakeArray()
    store.insert(key(full), 3, (), a, 8)
    assert store.get(key(sub), 3) is None
    assert store.schedule(key(sub), 3, lambda: None) is True  # not resident
    store.close()
    store.insert(key(sub), 3, (), b, 8)
    assert store.get(key(full), 3) is a and store.get(key(sub), 3) is b
    assert store.get(key((0, 1, 2)), 3) is a   # a plain tuple, by content
    assert store.get(key((1, 2)), 3) is None


def test_subset_never_finds_the_full_sets_plan_nor_the_reverse(mesh):
    h = Holder()
    idx = h.create_index("i")
    load(idx)
    plain = Executor(h)
    pl = MeshPlanner(h, mesh)
    fast = Executor(h, planner=pl)
    want_full = plain.execute("i", Q2)
    want_sub = plain.execute("i", Q2, shards=[0, 2])
    assert want_full == [5] and want_sub == [4]
    for _ in range(2):
        assert fast.execute("i", Q2, cache=False) == want_full
        assert fast.execute("i", Q2, shards=[0, 2], cache=False) == want_sub
        assert fast.execute("i", Q2, shards=[2, 0, 2], cache=False) == \
            want_sub
    # One plan and one stack a leaf for each of the two sets, no more.
    assert sorted(k[4] for k in pl._plan_cache) == [(0, 1, 2), (0, 2)]
    assert sorted((k.field, k.shards) for k in pl.stacks.keys()) == [
        ("f", (0, 1, 2)), ("f", (0, 2)), ("g", (0, 1, 2)), ("g", (0, 2))]
    full = idx.shard_set()
    assert all(k[4] is full or k[4] is as_shard_set([0, 2])
               for k in pl._plan_cache)
    pl.stacks.close()


# -- the hot path: nothing element-wise, one rendering ---------------------

class Spy(ShardSet):
    """Counts what costs a pass over the ids."""
    made = 0        # each construction hashes every id once
    content_eq = 0  # __eq__ that identity did not decide
    walks = 0       # iterations: a copy, a sort, a per-shard loop

    def __new__(cls, ids=()):
        Spy.made += 1
        return super().__new__(cls, ids)

    def __eq__(self, other):
        if self is not other:
            Spy.content_eq += 1
        return super().__eq__(other)

    def __ne__(self, other):
        if self is not other:
            Spy.content_eq += 1
        return super().__ne__(other)

    __hash__ = ShardSet.__hash__

    def __iter__(self):
        Spy.walks += 1
        return super().__iter__()


def test_resident_plan_cached_count_touches_no_shard_id(mesh, monkeypatch):
    monkeypatch.setattr(shardset, "ShardSet", Spy)
    renders = []
    real_str = Call.__str__
    depth = [0]

    def counting_str(self):
        if depth[0] == 0:
            renders.append(self.name)
        depth[0] += 1
        try:
            return real_str(self)
        finally:
            depth[0] -= 1

    h = Holder()
    idx = h.create_index("i")
    load(idx)
    stats = MemoryStats()
    pl = MeshPlanner(h, mesh, stats=stats)
    ex = Executor(h, planner=pl, stats=stats)
    want = Executor(h).execute("i", Q2)
    assert ex.execute("i", Q2, cache=False) == want   # plans, uploads
    assert type(idx.shard_set()) is Spy
    monkeypatch.setattr(Call, "__str__", counting_str)
    Spy.made = Spy.content_eq = Spy.walks = 0
    issued = stats.counter_value(shardset.ISSUED)
    reused = stats.counter_value(shardset.REUSED)
    uploads = pl.stacks.snapshot()["uploads"]
    for _ in range(5):
        assert ex.execute("i", Q2, cache=False) == want
    assert (Spy.made, Spy.content_eq, Spy.walks) == (0, 0, 0)
    assert renders == ["Intersect"] * 5               # once a request
    assert stats.counter_value(shardset.ISSUED) == issued
    assert stats.counter_value(shardset.REUSED) == reused + 5
    assert pl.stacks.snapshot()["uploads"] == uploads
    # The same object sits in the plan's key and in every stack's.
    full = idx.shard_set()
    assert [k[4] for k in pl._plan_cache] == [full]
    assert all(k[4] is full for k in pl._plan_cache)
    assert all(k.shards is full for k in pl.stacks.keys())
    pl.stacks.close()


def test_plan_miss_measures_a_leaf_once_an_epoch(mesh, monkeypatch):
    """A tree that misses the text-keyed plan cache asks for its
    leaves' classes; the walk over every shard's fragment is made once
    a leaf and epoch, not once a plan."""
    h = Holder()
    idx = h.create_index("i")
    f, _ = load(idx)
    pl = MeshPlanner(h, mesh)
    ex = Executor(h, planner=pl)
    plain = Executor(h)
    lookups = []
    real = h.fragment
    monkeypatch.setattr(h, "fragment",
                        lambda *a: lookups.append(a) or real(*a))
    qs = [f"Count({op}(Row(f=1), Row(g=2)))"
          for op in ("Intersect", "Union", "Xor")]
    assert ex.execute("i", qs[0], cache=False) == [5]
    first = len(lookups)
    for q in qs[1:]:
        ex.execute("i", q, cache=False)
    assert len(lookups) == first          # two plan misses, no walk
    monkeypatch.setattr(h, "fragment", real)
    assert [ex.execute("i", q, cache=False) for q in qs] == \
        [plain.execute("i", q) for q in qs]
    # A write moves the epoch: the measure is taken again, and a row
    # grown past the packing ceiling is seen.
    f.import_bits(np.full(5000, 1), np.arange(5000) * 3)
    assert ex.execute("i", qs[0], cache=False) == plain.execute("i", qs[0])
    assert all(e[0] == idx.epoch.value for e in pl._leaf_bits.values())
    pl.stacks.close()
