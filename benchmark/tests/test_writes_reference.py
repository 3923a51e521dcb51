"""The reference beside writes: hand-built records of reads and Sets with
their client-side times, over a 4-shard configuration, judged against
what the writes acknowledged around each allow; and against a brute force
over every subset of the writes in flight."""

import itertools

import numpy as np
import pytest

import dataset
import reference
import run as bench_run
import traffic

SEED = 11
WIDTH = 1 << 12
FIELDS = {"f": {"rows": 8, "density": 0.01, "dense_rows": {"1": 0.05}},
          "g": {"rows": 6, "density": 0.02}}
CONFIG = {"shard_width_exp": 12, "shards": 4, "fields": FIELDS}
OPS = ["Intersect", "Union", "Difference", "Xor"]
TEMPLATES = traffic.read_templates({"templates": [
    {"share": 0.4, "pql": "Count({op}(Row(f={a}), Row(g={b})))",
     "draw": {"op": {"choice": OPS}, "a": {"row": "f"}, "b": {"row": "g"}}},
    {"share": 0.2, "pql": "Count(Row(f={a}))", "draw": {"a": {"row": "f"}}},
    {"share": 0.2, "pql": "Set({col}, f={a})",
     "draw": {"col": {"column": "uniform"}, "a": {"row": "f"}}},
    {"share": 0.2, "pql": "Set({col}, g={b})",
     "draw": {"col": {"column": "uniform"}, "b": {"row": "g"}}}]})


def pair(op, a, b):
    t = TEMPLATES[0]
    return traffic.Request(0, (op,), (a, b), traffic.render(
        t, {"op": op, "a": a, "b": b}))


def row_f(a):
    return traffic.Request(1, (), (a,), f"Count(Row(f={a}))")


def set_bit(field, col, row):
    return traffic.Request(2 if field == "f" else 3, (), (col, row),
                           f"Set({col}, {field}={row})")


def positions(field, shard):
    return dataset.shard_positions(SEED, field, shard, WIDTH,
                                   dataset.row_densities(FIELDS[field]))


def column(shard, inside=(), outside=(), nth=0):
    """A column of ``shard`` that every (field, row) of ``inside`` holds
    and none of ``outside`` does."""
    ok = np.ones(WIDTH, dtype=bool)
    for f, r in inside:
        held = np.zeros(WIDTH, dtype=bool)
        held[positions(f, shard)[r]] = True
        ok &= held
    for f, r in outside:
        ok[positions(f, shard)[r]] = False
    return shard * WIDTH + int(np.flatnonzero(ok)[nth])


def judged(reads, writes):
    """``reads``: ``[(request, s, e)]``; ``writes``: ``[(request, s, e,
    acknowledged)]``. What the reference admits of each read and of each
    write, and the seeded answers."""
    cat = reference.Catalogue(TEMPLATES, [r[0] for r in reads]
                              + [w[0] for w in writes])
    history = reference.History()
    for req, s, e, ok in writes:
        history.add(cat.bit(req), s, e, ok)
    reference.run_pass([cat], CONFIG, SEED, 2, history=history)
    expected = cat.expected()
    allowed = cat.allowed(history, [r[:3] for r in reads]
                          + [w[:3] for w in writes], expected)
    seeded = [expected.get((r[0].group, r[0].values)) for r in reads]
    return allowed[:len(reads)], allowed[len(reads):], seeded


def test_a_read_after_an_acknowledged_write_must_include_it():
    c = column(1, inside=[("g", 2)], outside=[("f", 3)])
    reads = [(pair("Intersect", 3, 2), 2.0, 2.1), (row_f(3), 2.0, 2.1),
             (pair("Intersect", 3, 2), 0.5, 0.9)]
    got, _, seeded = judged(reads, [(set_bit("f", c, 3), 1.0, 1.1, True)])
    assert got == [(seeded[0] + 1,), (seeded[1] + 1,), (seeded[2],)]


def test_a_read_overlapping_a_write_passes_with_it_and_without():
    c = column(1, inside=[("g", 2)], outside=[("f", 3)])
    w = (set_bit("f", c, 3), 1.0, 1.1, True)
    reads = [(pair("Intersect", 3, 2), 1.05, 1.2),   # inside the write
             (pair("Intersect", 3, 2), 0.9, 1.05),   # answered after send
             (pair("Intersect", 3, 2), 0.9, 1.0)]    # answered at the send
    got, _, seeded = judged(reads, [w])
    base = seeded[0]
    assert got == [(base, base + 1), (base, base + 1), (base,)]
    # A write never acknowledged may have landed, for every later read.
    got, _, _ = judged(reads[:1] + [(pair("Intersect", 3, 2), 9.0, 9.1)],
                       [(w[0], 1.0, 1.1, False)])
    assert got == [(base, base + 1), (base, base + 1)]


def test_a_read_without_a_write_acknowledged_before_it_fails():
    c = column(2, inside=[("g", 0)], outside=[("f", 1)])
    got, _, seeded = judged([(pair("Intersect", 1, 0), 3.0, 3.2)],
                            [(set_bit("f", c, 1), 1.0, 2.0, True)])
    assert not bench_run.admits(got[0], seeded[0])
    assert bench_run.admits(got[0], seeded[0] + 1)
    checks = bench_run.judge(
        [(pair("Intersect", 1, 0), seeded[0])], got,
        {"pre": 1, "ack": True, "uncached": 2, "cached": 2,
         "allowed": [(1,), (True,), (2,), (2,)], "rows": []}, 1)
    assert checks["wrong_answers"]["value"] == 1


@pytest.mark.parametrize("op", ["Difference", "Xor"])
def test_a_write_lowers_a_difference_and_a_xor(op):
    """A column of f=4 that g=1 lacks: Set(col, g=1) takes it out of
    f=4 - g=1 and of f=4 ^ g=1."""
    c = column(3, inside=[("f", 4)], outside=[("g", 1)])
    got, _, seeded = judged([(pair(op, 4, 1), 5.0, 5.1)],
                            [(set_bit("g", c, 1), 1.0, 1.1, True)])
    assert got == [(seeded[0] - 1,)]


def test_a_duplicate_set_answers_false():
    c = column(0, outside=[("f", 5)])
    seeded_col = column(0, inside=[("f", 5)])
    _, got, _ = judged([], [
        (set_bit("f", c, 5), 1.0, 1.1, True),
        (set_bit("f", c, 5), 2.0, 2.1, True),         # after the first
        (set_bit("f", c, 5), 1.05, 1.3, True),        # beside the first
        (set_bit("f", seeded_col, 5), 1.0, 1.1, True)])
    assert got == [(True, False), (False,), (True, False), (False,)]
    assert bench_run.admits(got[1], False)
    assert not bench_run.admits(got[1], True)
    assert not bench_run.admits((True,), 1)  # a Set answers a bool


def test_a_mix_without_writes_keeps_the_seeded_answers():
    """No write: every read admits exactly its seeded answer, and a
    write's request in the catalogue leaves the reads' groups and answers
    as they are."""
    reads = [(pair(op, a, b), 1.0, 1.1) for op in OPS
             for a, b in [(1, 0), (3, 5)]] + [(row_f(1), 1.0, 1.1)]
    got, _, seeded = judged(reads, [])
    assert got == [(s,) for s in seeded]
    only = reference.Catalogue(TEMPLATES, [r[0] for r in reads])
    both = reference.Catalogue(TEMPLATES, [r[0] for r in reads]
                               + [set_bit("f", 7, 1)])
    assert only.writes == {} and list(both.writes) == [(2, ())]
    assert only.groups.keys() == both.groups.keys()
    for key, g in only.groups.items():
        assert g["keys"] == both.groups[key]["keys"]
    reference.run_pass([only, both], CONFIG, SEED, 2)
    assert only.expected() == both.expected()


def _brute(reads, writes):
    """Every subset of the writes in flight, on Python sets of columns."""
    sets = {(f, r): set() for f in FIELDS for r in range(FIELDS[f]["rows"])}
    for shard in range(CONFIG["shards"]):
        for f in FIELDS:
            for r, pos in enumerate(positions(f, shard)):
                sets[(f, r)] |= {shard * WIDTH + int(p) for p in pos}
    ops = {"Intersect": set.__and__, "Union": set.__or__,
           "Difference": set.__sub__, "Xor": set.__xor__}
    out = []
    for req, s, e in reads:
        must = [w for w in writes if w[3] and w[2] < s]
        may = [w for w in writes if w not in must and w[1] < e]
        values = set()
        for k in range(len(may) + 1):
            for sub in itertools.combinations(may, k):
                now = {key: set(v) for key, v in sets.items()}
                for w in must + list(sub):
                    col, row = w[0].values
                    now[("f" if w[0].template == 2 else "g", row)].add(col)
                if req.template == 1:
                    values.add(len(now[("f", req.values[0])]))
                else:
                    a, b = req.values
                    values.add(len(ops[req.choices[0]](now[("f", a)],
                                                       now[("g", b)])))
        out.append(tuple(sorted(values)))
    return out


def test_admissible_answers_match_a_brute_force_over_subsets():
    """Writes on a few columns, some of them several to one column, some
    never acknowledged, reads sent and answered around them."""
    rng = np.random.default_rng(5)
    cols = [column(s, nth=n) for s in (0, 2) for n in (3, 40)] + \
        [column(1, inside=[("f", 1), ("g", 0)])]
    writes = []
    for i in range(9):
        field = "f" if rng.random() < 0.5 else "g"
        row = int(rng.integers(0, 2))
        s = float(rng.uniform(0, 10))
        writes.append((set_bit(field, cols[int(rng.integers(0, len(cols)))],
                                row), s, s + float(rng.uniform(0.01, 3)),
                       bool(rng.random() < 0.85)))
    reads = []
    for i in range(60):
        s = float(rng.uniform(0, 12))
        req = row_f(int(rng.integers(0, 2))) if i % 5 == 0 else pair(
            OPS[i % 4], int(rng.integers(0, 2)), int(rng.integers(0, 2)))
        reads.append((req, s, s + float(rng.uniform(0.01, 2))))
    got, _, _ = judged(reads, writes)
    assert got == _brute(reads, writes)
    assert max(len(a) for a in got) >= 3  # several writes in flight at once
