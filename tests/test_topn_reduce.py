"""TopN's reduction over shards (`exec/topn.py`) and its second pass.

A TopN pass reduces every shard's counts in one vectorised step: per
shard, the threshold, the attribute filter and the truncation to ``n``
survivors, then a sum by id. That step is held to the per-shard loop it
replaced (kept here as the reference), to the interpreter's
``_top_n_shard`` shard by shard, and to the plain executor's two-pass
answer, on each route the planner takes (one program over the candidate
rows' stacks, the per-fragment sweep, the unfiltered cached counts) and
on one device and the 8-device CPU mesh. When the first pass was one
program over every shard of the call, the second is read from its count
matrix: `executor.topn.passesFromMatrix` counts those, and no other call
moves it.
"""

from functools import reduce

import numpy as np
import pytest

from pilosa_tpu.cluster.harness import LocalCluster
from pilosa_tpu.config import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.result import Pair, merge_pairs, sort_pairs
from pilosa_tpu.exec.topn import TopNCounts, reduce_pairs
from pilosa_tpu.obs import MemoryStats
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.pql import parse

#: shard -> {row of ``f``: count of ``row AND Row(g=1)``}. Ties in shard
#: 0 (rows 0, 1), 2 (rows 2-4) and all of shard 5; row 9 in shard 3
#: alone; every count of shard 4 is under 3; row 6 nowhere.
COUNTS = {
    0: {0: 9, 1: 9, 2: 4, 3: 2, 4: 1},
    1: {0: 1, 1: 7, 2: 7, 3: 6, 5: 3},
    2: {1: 2, 2: 8, 3: 8, 4: 8, 5: 1},
    3: {0: 6, 2: 3, 4: 5, 9: 12},
    4: {0: 2, 1: 1, 3: 2, 5: 2},
    5: {0: 4, 1: 4, 2: 4, 3: 4, 4: 4, 5: 4},
}
N_SHARDS = len(COUNTS)
ATTRS = {0: "x", 2: "x", 3: "z", 9: "y"}


def bits(shards=COUNTS):
    """(field, rows, columns) of the fixture: row r of ``f`` in shard s
    holds COUNTS[s][r] even columns of its own span, and (r + s) % 3 odd
    ones besides, which only the unfiltered count sees; ``g=1`` holds
    every even column of those spans."""
    out = []
    for s in shards:
        for r, k in COUNTS[s].items():
            base = s * SHARD_WIDTH + r * 100
            cols = [base + 2 * j for j in range(k)]
            cols += [base + 2 * j + 1 for j in range((r + s) % 3)]
            out.append(("f", [r] * len(cols), cols))
        even = np.arange(s * SHARD_WIDTH, s * SHARD_WIDTH + 1000, 2)
        out.append(("g", [1] * len(even), even.tolist()))
    return out


def load(idx):
    f, g = idx.create_field("f"), idx.create_field("g")
    for name, rows, cols in bits():
        (f if name == "f" else g).import_bits(rows, cols)
    for rid, cat in ATTRS.items():
        f.row_attr_store.set_attrs(rid, {"cat": cat})


@pytest.fixture(scope="module", params=[8, 1], ids=["8 devices", "1 device"])
def nodes(request):
    h = Holder()
    idx = h.create_index("i")
    load(idx)
    stats = MemoryStats()
    planner = MeshPlanner(h, make_mesh(n=request.param), stats=stats)
    assert planner.n_devices == request.param
    # no result cache: the routes ask the same texts
    yield idx, Executor(h), Executor(h, planner=planner, stats=stats,
                                     result_cache=False), stats
    planner.close()


def _sweep_only(planner):
    """One dense stack of budget: ``f``'s candidate rows keep the
    per-fragment sweep (`_stacks_fit`)."""
    planner.max_cache_bytes = planner._dense_device_bytes(N_SHARDS)


ROUTES = ["stacked", "sweep", "unfiltered"]

CASES = {
    "n 0": "",
    "n 1": "n=1",
    "n 3": "n=3",
    "n past the rows": "n=20",
    "threshold 1": "threshold=1",
    "threshold above some counts": "threshold=3",
    "threshold and n": "n=2, threshold=5",
    "ids": "ids=[0, 2, 9, 42]",
    "ids, n and threshold": "ids=[1, 3, 5, 6], n=1, threshold=4",
    "attribute filter": 'attrName="cat", attrValues=["x"]',
    "attribute filter and n": 'n=1, attrName="cat", attrValues=["x", "z"]',
}


def topn(route, args):
    filt = "" if route == "unfiltered" else ", Row(g=1)"
    return f"TopN(f{filt}{', ' + args if args else ''})"


def pairs(ps):
    return [(p.id, p.count) for p in ps]


def on_route(fast, route, fn):
    budget = fast.planner.max_cache_bytes
    if route == "sweep":
        _sweep_only(fast.planner)
    try:
        return fn()
    finally:
        fast.planner.max_cache_bytes = budget


def test_the_fixture_holds_the_cases_it_names(nodes):
    """Shard 4 is empty under a threshold of 3, row 9 lives in one
    shard, row 6 in none; the interpreter agrees with the table."""
    idx, plain, _, _ = nodes
    call = parse(topn("stacked", "threshold=3")).calls[0]
    assert plain._top_n_shard(idx, call, 4) == []
    call = parse(topn("stacked", "")).calls[0]
    for s, row_counts in COUNTS.items():
        assert dict(pairs(plain._top_n_shard(idx, call, s))) == row_counts
    assert [s for s in COUNTS if 9 in COUNTS[s]] == [3]
    assert not any(6 in c for c in COUNTS.values())


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("case", list(CASES))
def test_one_pass_is_the_per_shard_interpreters(nodes, case, route):
    """The planner's batch over every shard answers what
    ``_top_n_shard`` answers shard by shard, merged by id."""
    idx, plain, fast, _ = nodes
    call = parse(topn(route, CASES[case])).calls[0]
    shards = list(range(N_SHARDS))
    want = sort_pairs(reduce(merge_pairs, (
        plain._top_n_shard(idx, call, s) for s in shards), []))
    batch = fast._topn_batch_fn(idx, call)
    got = on_route(fast, route, lambda: batch(shards))
    assert pairs(got) == pairs(want)


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("case", list(CASES))
def test_the_call_is_the_plain_executors(nodes, case, route):
    idx, plain, fast, stats = nodes
    q = topn(route, CASES[case])
    (want,) = plain.execute("i", q)
    (got,), moved = _moved(stats, lambda: on_route(
        fast, route, lambda: fast.execute("i", q)))
    assert pairs(got) == pairs(want)
    if case == "n past the rows":
        assert len(got) == 7  # rows 0-5 and 9
    # the route is the one named
    took = (moved["planner.topn.passesStacked"] > 0,
            moved["planner.topn.passesSwept"] > 0)
    assert took == {"stacked": (True, False), "sweep": (False, True),
                    "unfiltered": (False, False)}[route]


@pytest.mark.parametrize("route", ["stacked", "sweep"])
def test_pass_two_sums_what_pass_one_truncated(nodes, route):
    """With ``n`` 1 each shard's first pass keeps its top row alone, so
    row 0 leads the candidates (9 + 2 + 4); the second pass sums every
    shard's count of the candidates, and row 2 (26) overtakes it."""
    idx, plain, fast, _ = nodes
    q = topn(route, "n=1")
    batch = fast._topn_batch_fn(idx, parse(q).calls[0])
    first = on_route(fast, route, lambda: batch(list(range(N_SHARDS))))
    assert pairs(first) == [(0, 15), (9, 12), (2, 8), (1, 7)]
    (got,) = on_route(fast, route, lambda: fast.execute("i", q))
    assert pairs(got) == pairs(plain.execute("i", q)[0]) == [(2, 26)]


def _moved(stats, fn):
    names = ("executor.topn.passesFromMatrix", "planner.topn.launches",
             "planner.topn.passesStacked", "planner.topn.passesSwept")
    before = [stats.counter_value(n) for n in names]
    out = fn()
    return out, dict(zip(names, (stats.counter_value(n) - b
                                 for n, b in zip(names, before))))


REUSE = {
    # one program, and the second pass read from its matrix
    "a filtered call": ("stacked", "n=2", 1, 1, 1, 0),
    # explicit ids: one pass only
    "ids": ("stacked", "ids=[0, 2, 9]", 0, 1, 1, 0),
    # the sweep's counts are not one program's: both passes sweep
    "the sweep": ("sweep", "n=2", 0, None, 0, 2),
}


@pytest.mark.parametrize("case", list(REUSE))
def test_the_second_pass_reads_the_matrix_only_after_one_program(nodes, case):
    idx, plain, fast, stats = nodes
    route, args, from_matrix, launches, stacked, swept = REUSE[case]
    q = topn(route, args)
    (got,), moved = _moved(stats, lambda: on_route(
        fast, route, lambda: fast.execute("i", q)))
    assert pairs(got) == pairs(plain.execute("i", q)[0])
    assert got
    assert moved["executor.topn.passesFromMatrix"] == from_matrix
    assert moved["planner.topn.passesStacked"] == stacked
    assert moved["planner.topn.passesSwept"] == swept
    if launches is not None:
        assert moved["planner.topn.launches"] == launches


def test_a_cluster_call_with_a_remote_leg_recounts():
    """The coordinator's local batch covers its own shards only: the
    second pass goes to every node again, and the answer is the single
    node's."""
    lc = LocalCluster(2)
    stats = MemoryStats()
    planners = []
    for i, cn in enumerate(lc.nodes):
        node_stats = stats if i == 0 else MemoryStats()
        cn.executor.stats = node_stats
        cn.executor.result_cache = None
        cn.executor.planner = MeshPlanner(cn.holder, stats=node_stats)
        planners.append(cn.executor.planner)
    try:
        lc.create_index("i")
        lc.create_field("i", "f")
        lc.create_field("i", "g")
        owners = {s: lc[0].cluster.shard_nodes("i", s)[0].id for s in COUNTS}
        assert len(set(owners.values())) == 2, owners
        for s, owner in owners.items():
            idx = lc.client.peers[owner].holder.index("i")
            for name, rows, cols in bits([s]):
                idx.field(name).import_bits(rows, cols)
        h = Holder()
        load(h.create_index("i"))
        for q in (topn("stacked", "n=2"), topn("stacked", "n=1")):
            (got,), moved = _moved(stats, lambda q=q: lc.query(
                "i", q, cache=False))
            assert pairs(got) == pairs(Executor(h).execute("i", q)[0]), q
            assert moved["executor.topn.passesFromMatrix"] == 0, q
            assert moved["planner.topn.passesStacked"] == 2, q
    finally:
        for p in planners:
            p.close()


# ---------------------------------------------------------------------------
# the reducer alone, against the loop it replaced
# ---------------------------------------------------------------------------


def loop_reference(per_shard, n, min_threshold, admit):
    """The per-shard loop `reduce_pairs` replaced, kept as its
    reference: per shard in order, the threshold, the attribute filter,
    the first ``n`` survivors, merged by id."""
    acc = []
    for shard in sorted(per_shard):
        ids, counts = per_shard[shard]
        keep = counts >= min_threshold
        out = []
        for rid, cnt in zip(ids[keep].tolist(), counts[keep].tolist()):
            if admit is not None and not admit(np.asarray([rid]))[0]:
                continue
            out.append(Pair(id=rid, count=cnt))
            if n and len(out) >= n:
                break
        acc = merge_pairs(acc, out)
    return pairs(sort_pairs(acc))


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("n, threshold, filtered", [
    (0, 1, False), (1, 1, False), (3, 2, False), (50, 1, False),
    (2, 3, True), (0, 4, True)])
def test_the_reducer_is_the_loop_it_replaced(seed, n, threshold, filtered):
    """Random ragged shards: some empty, ids drawn from a small range
    (ties and repeats across shards), counts 0 included."""
    rng = np.random.default_rng(seed)
    per_shard = {}
    for shard in rng.choice(40, size=int(rng.integers(0, 12)), replace=False):
        ids = np.sort(rng.choice(30, size=int(rng.integers(0, 10)),
                                 replace=False)).astype(np.uint64)
        counts = rng.integers(0, 6, len(ids)).astype(np.int64)
        order = np.lexsort((ids, -counts))
        per_shard[int(shard)] = (ids[order], counts[order])
    admit = (lambda distinct: distinct % 3 != 0) if filtered else None
    seen = []

    def counting(distinct):
        seen.extend(distinct.tolist())
        return admit(distinct)

    got_ids, got_sums = reduce_pairs(TopNCounts.from_shards(per_shard), n,
                                     threshold,
                                     counting if filtered else None)
    assert list(zip(got_ids.tolist(), got_sums.tolist())) == \
        loop_reference(per_shard, n, threshold, admit)
    assert got_sums.dtype == np.int64
    assert len(seen) == len(set(seen))  # asked once an id


def test_the_dense_and_the_ragged_forms_reduce_alike():
    """The one program's [S, R] matrix, flattened with its zeros, and
    the same counts as ragged shards without them."""
    rng = np.random.default_rng(43)
    cands = np.asarray([2, 5, 7, 11], dtype=np.uint64)
    counts = rng.integers(0, 4, (6, 4)).astype(np.int64)
    order = np.argsort(-counts, axis=1, kind="stable")
    counts = np.take_along_axis(counts, order, axis=1)
    shards = np.arange(6) * 3
    dense = TopNCounts(np.repeat(shards, 4), cands[order].ravel(),
                       counts.ravel(), stacked=True)
    ragged = TopNCounts.from_shards({
        int(s): (cands[o][c > 0], c[c > 0])
        for s, o, c in zip(shards, order, counts) if (c > 0).any()})
    for n in (0, 1, 2):
        a, b = reduce_pairs(dense, n, 1), reduce_pairs(ragged, n, 1)
        assert a[0].tolist() == b[0].tolist()
        assert a[1].tolist() == b[1].tolist()


def test_nothing_to_reduce():
    empty = TopNCounts.from_shards({})
    ids, sums = reduce_pairs(empty, 3, 1)
    assert len(ids) == len(sums) == 0
    one = TopNCounts.from_shards({4: (np.asarray([1], np.uint64),
                                      np.asarray([2], np.int64))})
    assert reduce_pairs(one, 1, 3)[0].tolist() == []
    assert reduce_pairs(one, 1, 2, lambda d: [False])[0].tolist() == []
