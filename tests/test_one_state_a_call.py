"""One state a call: a query that names one row twice reads that row once,
so a write that lands between the two places that name it cannot give
the call two states of it.

The deterministic cases move one bit of ``f=1`` (clear ``x``, set ``y``)
right after the call's first read of that row. Every state of the index
answers each query the same (``x`` and ``y`` lie outside ``g=2`` and
``f=2``, both inside ``g=3``), so the answer before the move is the one
answer a call may give; a call that read ``f=1`` again after the move
would mix the two states and miss it (``Xor`` keeps both bits, an
``AND`` of the two states loses ``x``).
"""

import threading
import time

import jax
import numpy as np
import pytest

from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec import Executor
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.pql import parse

#: bits a row holds in each shard: above what a packed stack holds, so
#: every row is a dense stack, as in the benchmark's configurations
ROW_BITS = 20_000


def _index(n_shards: int):
    """f=1, f=2, g=2, g=3 over ``n_shards`` shards, and the columns
    ``x`` (in f=1) and ``y`` (in no f row) of shard 0 that the move
    uses: both in g=3, neither in g=2."""
    rng = np.random.default_rng(7)
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    cols = {}
    for name in ("f1", "f2", "g2", "g3"):
        cols[name] = np.concatenate([
            s * SHARD_WIDTH + rng.choice(SHARD_WIDTH, ROW_BITS, replace=False)
            for s in range(n_shards)]).astype(np.uint64)
    f1 = set(cols["f1"].tolist())
    others = set(cols["f2"].tolist()) | set(cols["g2"].tolist())
    x = next(c for c in sorted(f1) if c not in others)
    y = next(c for c in range(SHARD_WIDTH) if c not in f1 | others)
    cols["g3"] = np.concatenate([cols["g3"], [x, y]]).astype(np.uint64)
    for fld, row, name in ((f, 1, "f1"), (f, 2, "f2"), (g, 2, "g2"),
                           (g, 3, "g3")):
        fld.import_bits(np.full(len(cols[name]), row, dtype=np.uint64),
                        cols[name])
    return h, f, x, y


def _planner_executor(h):
    return Executor(h, planner=MeshPlanner(h, make_mesh()))


#: (route, query, whether the planner answers it)
ROUTES = [
    ("count_planner", "Count(Xor(Xor(Row(f=1), Row(g=2)), Row(f=1)))", True),
    ("count_interpreter", "Count(Xor(Xor(Row(f=1), Row(g=2)), Row(f=1)))",
     False),
    ("bitmap_tree_stack", "Xor(Xor(Row(f=1), Row(g=2)), Row(f=1))", True),
    ("topn_stacked", "TopN(f, Row(f=1))", True),
    ("groupby_filter_names_level_row",
     "GroupBy(Rows(f), Rows(g), filter=Row(f=1))", True),
]


@pytest.mark.parametrize("route,query,planned", ROUTES,
                         ids=[r[0] for r in ROUTES])
def test_a_write_between_two_reads_of_one_row_is_not_seen_by_half_the_call(
        monkeypatch, route, query, planned):
    h, f, x, y = _index(2)
    ex = _planner_executor(h) if planned else Executor(h)
    want = ex.execute("i", query, cache=False)
    reads = []

    def move_once():
        if len(reads) == 1:
            f.clear_bit(1, x)
            f.set_bit(1, y)

    if planned:
        fetch = MeshPlanner._fetch_leaf

        def fetch_leaf(self, idx, leaf, shards, **kw):
            arr = fetch(self, idx, leaf, shards, **kw)
            if leaf == ("row", "f", VIEW_STANDARD, 1):
                reads.append(leaf)
                move_once()
            return arr

        monkeypatch.setattr(MeshPlanner, "_fetch_leaf", fetch_leaf)
    else:
        row = Fragment.row

        def frag_row(self, row_id):
            out = row(self, row_id)
            if (self.field, self.shard, row_id) == ("f", 0, 1):
                reads.append(row_id)
                move_once()
            return out

        monkeypatch.setattr(Fragment, "row", frag_row)

    got = ex.execute("i", query, cache=False)
    monkeypatch.undo()
    assert reads, "the call never read f=1"
    assert got == want
    # the move happened, and every state answers the query the same
    assert ex.execute("i", query, cache=False) == want


def test_a_filter_on_the_summed_field_reads_the_planes_the_sum_reads(
        monkeypatch):
    """The stepped Sum (a filtered aggregate off the chip) fetches the
    field's planes and its filter's leaves in one call. Column 0 moves
    from -3 to 3 right after the first read of the planes: the sum over
    ``v > 0`` of one state is 60 (before) or 63 (after); the filter of
    the second state over the values of the first would give 57."""
    from pilosa_tpu.core import FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_FUSE", "auto")
    h = Holder()
    v = h.create_index("i").create_field(
        "v", FieldOptions(type=FIELD_TYPE_INT, min=-10, max=10))
    v.import_values([0] + [SHARD_WIDTH + c for c in range(10)],
                    [-3] + [6] * 10)
    ex = _planner_executor(h)
    q = "Sum(Row(v > 0), field=v)"
    assert ex.planner._fuse_agg_ok(parse(q).calls[0]) is False
    (want,) = ex.execute("i", q, cache=False)
    reads = []
    fetch = MeshPlanner._fetch_leaf

    def fetch_leaf(self, idx, leaf, shards, **kw):
        arr = fetch(self, idx, leaf, shards, **kw)
        if leaf[:2] == ("bsi", "v"):
            reads.append(leaf)
            if len(reads) == 1:
                v.set_value(0, 3)
        return arr

    monkeypatch.setattr(MeshPlanner, "_fetch_leaf", fetch_leaf)
    (got,) = ex.execute("i", q, cache=False)
    monkeypatch.undo()
    assert reads
    assert (want.val, want.count) == (60, 10)
    assert (got.val, got.count) == (60, 10)
    (after,) = ex.execute("i", q, cache=False)
    assert (after.val, after.count) == (63, 11)


@pytest.mark.parametrize("planned", [True, False],
                         ids=["planner", "interpreter"])
def test_readers_beside_writers_of_the_repeated_row_read_one_state(planned):
    """Six threads ask a tree that names f=1 twice while two threads Set
    bits of f=1: f ^ g ^ f is g in every state, so every answer is
    Count(Row(g=2))."""
    h, f, _, _ = _index(4)
    ex = (Executor(h, planner=MeshPlanner(h, make_mesh(jax.devices()[:1])))
          if planned else Executor(h))
    q = "Count(Xor(Xor(Row(f=1), Row(g=2)), Row(f=1)))"
    (want,) = ex.execute("i", "Count(Row(g=2))", cache=False)
    stop = threading.Event()
    wrong, answers, errors = [], [], []

    def reader():
        try:
            while not stop.is_set():
                (got,) = ex.execute("i", q, cache=False)
                answers.append(got)
                if got != want:
                    wrong.append(got)
        except Exception as e:  # surfaced below
            errors.append(repr(e))

    def writer(seed):
        g = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                f.set_bit(1, int(g.integers(0, 4 * SHARD_WIDTH)))
        except Exception as e:
            errors.append(repr(e))

    threads = ([threading.Thread(target=reader) for _ in range(6)]
               + [threading.Thread(target=writer, args=(s,)) for s in (1, 2)])
    for t in threads:
        t.start()
    time.sleep(1.2)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    assert answers
    assert not wrong, (len(wrong), len(answers), wrong[:5])
