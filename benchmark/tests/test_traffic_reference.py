"""The template reader, the generator and the plain reference."""

import json
import os

import numpy as np
import pytest

import dataset
import kernel_bytes
import reference
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = {"f": {"rows": 8, "density": 0.01, "dense_rows": {"1": 0.05}},
          "g": {"rows": 6, "density": 0.02}}
WIDTH = 1 << 12


def mix() -> dict:
    with open(os.path.join(BENCH, "traffic", "count-trees.json")) as f:
        return json.load(f)


def extra_templates() -> dict:
    return {"mode": "closed", "templates": [
        {"share": 0.5, "pql": "TopN(f, Row(g={b}), n=3)",
         "draw": {"b": {"row": "g"}}},
        {"share": 0.25, "pql": "Set({col}, f={a})",
         "draw": {"col": {"column": "uniform"}, "a": {"row": "f"}}},
        {"share": 0.25, "pql": "GroupBy(Rows(f), Rows(g))", "draw": {}}]}


def test_reader_understands_the_first_shapes():
    ts = traffic.read_templates(mix())
    assert [t.names for t in ts] == [("op", "a", "b"),
                                     ("op1", "op2", "a", "b", "c"), ("a",)]
    assert [len([s for s in traffic.structures(ts) if s[0] == t.index])
            for t in ts] == [4, 16, 1]
    assert not any(t.is_write() for t in ts)


def test_reader_understands_topn_groupby_and_set():
    ts = traffic.read_templates(extra_templates())
    picker = traffic.RowPicker({"row_draw": {"rule": "zipf", "exponent": 1.0}},
                               FIELDS, seed=5)
    reqs = traffic.draw_stream(ts, picker, 4 * WIDTH, 5, 0, 200)
    kinds = {r.pql.split("(")[0] for r in reqs}
    assert kinds == {"TopN", "Set", "GroupBy"}
    sets = [r for r in reqs if r.pql.startswith("Set(")]
    assert ts[1].is_write() and all(0 <= r.values[0] < 4 * WIDTH
                                    for r in sets)
    assert reference.parse(sets[0].pql)[1] == "Set"
    # A write inside the window has no reference yet: said, not guessed.
    with pytest.raises(reference.PQLError, match="write inside"):
        reference.Catalogue(ts, reqs)


def test_open_loop_and_bad_placeholders_are_refused():
    with pytest.raises(traffic.TrafficError, match="not built yet"):
        traffic.read_templates(dict(mix(), mode="open"))
    bad = {"templates": [{"share": 1.0, "pql": "Count(Row(f={a}))"}]}
    with pytest.raises(traffic.TrafficError, match="placeholder"):
        traffic.read_templates(bad)
    with pytest.raises(traffic.TrafficError, match="sum to"):
        traffic.read_templates({"templates": [
            {"share": 0.5, "pql": "Count(Row(f=1))"}]})


def test_streams_are_a_function_of_the_seed_and_zipf_is_skewed():
    ts = traffic.read_templates(mix())
    big = 3_000_000_011  # more than 32 signed bits hold
    picker = traffic.RowPicker(mix(), FIELDS, big)
    a = traffic.draw_stream(ts, picker, 4 * WIDTH, big, 3, 500)
    b = traffic.draw_stream(ts, picker, 4 * WIDTH, big, 3, 500)
    c = traffic.draw_stream(ts, picker, 4 * WIDTH, big, 4, 500)
    assert a == b and a != c
    share = {i: sum(r.template == i for r in a) / len(a) for i in range(3)}
    assert 0.6 < share[0] < 0.8 and 0.12 < share[1] < 0.28
    rows = picker.draw(np.random.default_rng(1), "f", 20_000)
    hot = np.bincount(rows, minlength=8)[picker.order["f"]]
    assert hot[0] > 1.7 * hot[1] > 1.7 * 1.2 * hot[3]


def _shard(seed: int, shard: int):
    pos = {f: dataset.shard_positions(seed, f, shard, WIDTH,
                                      dataset.row_densities(spec))
           for f, spec in FIELDS.items()}
    return pos, dataset.pack_rows(pos, WIDTH)


def test_reference_agrees_with_sets_of_columns():
    """The packed evaluation against Python sets of column numbers."""
    spec = {"templates": mix()["templates"][:2] + [
        {"share": 0.05, "pql": "TopN(f, Row(g={b}), n=3)",
         "draw": {"b": {"row": "g"}}},
        {"share": 0.05, "pql": "GroupBy(Rows(f), Rows(g))", "draw": {}}]}
    spec["templates"][0]["share"] = 0.7
    ts = traffic.read_templates(spec)
    picker = traffic.RowPicker(mix(), FIELDS, 9)
    reqs = traffic.draw_stream(ts, picker, 3 * WIDTH, 9, 0, 300)
    cat = reference.Catalogue(ts, reqs)
    sets = {f: [set() for _ in range(FIELDS[f]["rows"])] for f in FIELDS}
    for shard in range(3):
        pos, rows = _shard(9, shard)
        cat.add(cat.partials(rows))
        for f in FIELDS:
            for r, p in enumerate(pos[f]):
                sets[f][r] |= {shard * WIDTH + int(c) for c in p}
    expected = cat.expected()
    ops = {"Intersect": set.__and__, "Union": set.__or__,
           "Difference": set.__sub__, "Xor": set.__xor__}
    seen = set()
    for r in reqs:
        got = expected[(r.group, r.values)]
        name = r.pql.split("(")[0]
        seen.add(name)
        if r.template == 0:
            a, b = r.values
            want = len(ops[r.choices[0]](sets["f"][a], sets["g"][b]))
        elif r.template == 1:
            a, b, c = r.values
            inner = ops[r.choices[1]](sets["f"][a], sets["g"][b])
            want = len(ops[r.choices[0]](inner, sets["f"][c]))
        elif name == "TopN":
            pairs = sorted(((len(s & sets["g"][r.values[0]]), i)
                            for i, s in enumerate(sets["f"])),
                           key=lambda cr: (-cr[0], cr[1]))
            want = [[i, c] for c, i in pairs if c][:3]
        else:
            want = [[a, b, len(sa & sb)] for a, sa in enumerate(sets["f"])
                    for b, sb in enumerate(sets["g"]) if sa & sb]
        assert got == want, r.pql
    assert seen == {"Count", "TopN", "GroupBy"}


def test_control_leaves_a_shard_out():
    ts = traffic.read_templates(mix())
    picker = traffic.RowPicker(mix(), FIELDS, 2)
    reqs = traffic.draw_stream(ts, picker, 2 * WIDTH, 2, 0, 50)
    cat = reference.Catalogue(ts, reqs)
    parts = [cat.partials(_shard(2, s)[1]) for s in range(2)]
    for p in parts:
        cat.add(p)
    full, cut = cat.expected(), cat.expected(without=parts[1])
    assert full != cut and all(cut[k] <= v for k, v in full.items())


def test_norm_reads_the_served_shapes():
    topn = reference.parse("TopN(f, Row(g=1), n=2)")
    assert reference.norm(topn, [{"id": 3, "count": 9}]) == [[3, 9]]
    gb = reference.parse("GroupBy(Rows(f), Rows(g))")
    served = [{"group": [{"field": "f", "rowID": 1},
                         {"field": "g", "rowID": 0}], "count": 4}]
    assert reference.norm(gb, served) == [[1, 0, 4]]


def test_roaring_payload_round_trips_through_the_spec():
    """Array and bitmap containers, decoded by hand from the layout."""
    rng = np.random.default_rng(4)
    pos = np.unique(np.concatenate([
        rng.integers(0, 1 << 16, 6000, dtype=np.uint64),          # bitmap
        (1 << 16) + rng.integers(0, 1 << 16, 100, dtype=np.uint64),
        (9 << 16) + rng.integers(0, 1 << 16, 4096, dtype=np.uint64)]))
    buf = dataset.roaring_encode(pos)
    cookie, n = np.frombuffer(buf, "<u4", 2)
    assert cookie == dataset.ROARING_MAGIC and n == 3
    meta = np.frombuffer(buf, dataset._META, n, 8)
    offs = np.frombuffer(buf, "<u4", n, 8 + 12 * n)
    out = []
    for (key, typ, n1), off in zip(meta.tolist(), offs.tolist()):
        if typ == dataset.TYPE_ARRAY:
            vals = np.frombuffer(buf, "<u2", n1 + 1, off).astype(np.uint64)
        else:
            bits = np.unpackbits(np.frombuffer(buf, np.uint8, 8192, off),
                                 bitorder="little")
            vals = np.flatnonzero(bits).astype(np.uint64)
            assert len(vals) == n1 + 1
        out.append((np.uint64(key) << np.uint64(16)) + vals)
    assert (np.concatenate(out) == pos).all()
    with pytest.raises(ValueError):
        dataset.roaring_encode(np.asarray([3, 3], dtype=np.uint64))


def test_kernel_bytes_counts_real_shards():
    cfg = {"shard_width_exp": 20, "shards": 954}
    assert kernel_bytes.request_bytes(
        "Count(Xor(Intersect(Row(f=3), Row(g=4)), Row(f=5)))",
        cfg) == 3 * 954 * 131072
