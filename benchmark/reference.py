"""The plain reference: PQL trees evaluated with numpy on packed words.

It imports nothing of the program. Rows come from the seeded positions
(``dataset.pack_rows``); a tree is parsed by the small parser below and
evaluated on one shard at a time, a whole group of requests (same
structure, different rows) in one pass; the per-shard partial results add
up in int64. The shards are shared out over a few worker processes: the
passes over the words are many small numpy calls, which threads would run
one at a time. Supported: ``Count`` over ``Row``/``Intersect``/``Union``/
``Difference``/``Xor``, filtered or plain ``TopN`` and ``GroupBy`` over
``Rows``. ``Not`` is left out: the roaring route records no existence.
"""

from __future__ import annotations

import multiprocessing
import re
from concurrent.futures import ProcessPoolExecutor

import numpy as np

import dataset
from traffic import structure_text

_TOKEN = re.compile(r"\s*([A-Za-z_][A-Za-z0-9_]*|\{[A-Za-z_][A-Za-z0-9_]*\}"
                    r"|-?\d+|[(),=])")
#: requests of one group evaluated together: bounds the temporaries at
#: CHUNK rows of words per operand, small enough to stay in cache.
CHUNK = 32


class PQLError(ValueError):
    """Text the reference's parser does not understand."""


def parse(text: str):
    """``Name(arg, ...)`` -> ("call", name, [args], {key: value});
    a bare word, number or ``{placeholder}`` is a string."""
    pos = 0
    tokens = []
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise PQLError(f"cannot read {text[pos:pos + 20]!r} in {text!r}")
        tokens.append(m.group(1))
        pos = m.end()
    tree, rest = _parse_call(tokens, 0)
    if rest != len(tokens):
        raise PQLError(f"trailing text in {text!r}")
    return tree


def _parse_call(tokens, i):
    name = tokens[i]
    if i + 1 >= len(tokens) or tokens[i + 1] != "(":
        return name, i + 1
    i += 2
    args, kwargs = [], {}
    while tokens[i] != ")":
        if i + 1 < len(tokens) and tokens[i + 1] == "=":
            kwargs[tokens[i]] = tokens[i + 2]
            i += 3
        else:
            arg, i = _parse_call(tokens, i)
            args.append(arg)
        if tokens[i] == ",":
            i += 1
    return ("call", name, args, kwargs), i + 1


def _value(token: str, values: dict) -> np.ndarray:
    if token.startswith("{"):
        return values[token[1:-1]]
    return np.asarray([int(token)])


def _bitmap(node, rows: dict, values: dict) -> np.ndarray:
    """``[n, words]`` uint64 for a bitmap-valued call."""
    _, name, args, kwargs = node
    if name == "Row":
        (field, token), = kwargs.items()
        return rows[field][_value(token, values)]
    # The first operand is this call's own copy (a gather or a result),
    # so the rest fold into it in place: fewer passes over the words.
    out = _bitmap(args[0], rows, values)
    for arg in args[1:]:
        p = _bitmap(arg, rows, values)
        if name == "Intersect":
            np.bitwise_and(out, p, out=out)
        elif name == "Union":
            np.bitwise_or(out, p, out=out)
        elif name == "Xor":
            np.bitwise_xor(out, p, out=out)
        elif name == "Difference":
            np.bitwise_and(out, np.invert(p, out=p), out=out)
        else:
            raise PQLError(f"the reference has no bitmap call {name!r}")
    return out


def _popcount(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1, dtype=np.int64)


def evaluate(tree, rows: dict, values: dict, n: int) -> np.ndarray:
    """One shard's partial answer for ``n`` requests of one structure.
    Count: ``[n]``; TopN: ``[n, rows(field)]`` counts of every row;
    GroupBy over two ``Rows``: ``[n, rows(a), rows(b)]``."""
    _, name, args, kwargs = tree
    if name == "Count":
        return _popcount(_bitmap(args[0], rows, values))
    if name == "TopN":
        field = args[0]
        if len(args) > 1:
            filt = _bitmap(args[1], rows, values)
            return _popcount(rows[field][None, :, :] & filt[:, None, :])
        return np.broadcast_to(_popcount(rows[field]),
                               (n, len(rows[field]))).copy()
    if name == "GroupBy":
        fa, fb = (a[2][0]
                  for a in args)
        both = rows[fa][:, None, :] & rows[fb][None, :, :]
        return np.broadcast_to(_popcount(both), (n,) + both.shape[:2]).copy()
    raise PQLError(f"the reference cannot answer {name!r}")


def finish(tree, total: np.ndarray):
    """A group's summed partials -> the answers in the shape ``norm``
    gives the served ones."""
    _, name, args, kwargs = tree
    if name == "Count":
        return [int(v) for v in total]
    if name == "TopN":
        n = int(kwargs.get("n", 0))
        out = []
        for counts in total:
            pairs = sorted(((int(c), r) for r, c in enumerate(counts) if c),
                           key=lambda cr: (-cr[0], cr[1]))
            out.append([[r, c] for c, r in (pairs[:n] if n else pairs)])
        return out
    if name == "GroupBy":
        return [[[a, b, int(c)] for a, row in enumerate(m)
                 for b, c in enumerate(row) if c] for m in total]
    raise PQLError(f"the reference cannot answer {name!r}")


def norm(tree, result):
    """A served result -> the comparable form."""
    name = tree[1]
    if name == "Count":
        return int(result)
    if name == "TopN":
        return [[int(p["id"]), int(p["count"])] for p in result]
    if name == "GroupBy":
        return sorted([int(fr["rowID"]) for fr in g["group"]]
                      + [int(g["count"])] for g in result)
    return result


def group_partials(groups: dict, rows: dict) -> dict:
    """Every group's partial answers on one shard's packed rows."""
    out = {}
    for key, g in groups.items():
        n = len(g["keys"])
        parts = []
        for lo in range(0, n, CHUNK):
            vals = {nm: v[lo:lo + CHUNK] for nm, v in g["values"].items()}
            parts.append(evaluate(g["tree"], rows, vals, min(CHUNK, n - lo)))
        out[key] = np.concatenate(parts)
    return out


def shards_partials(groups_list: list, config: dict, seed: int,
                    shards: list, keep: int | None):
    """A worker's share: the summed partials of each catalogue's groups
    over ``shards``, rows drawn from the seed, and apart from them shard
    ``keep``'s own partials of the first catalogue (the control leaves
    that shard out)."""
    width = 1 << int(config["shard_width_exp"])
    totals = [None] * len(groups_list)
    kept = None
    for shard in shards:
        rows = dataset.pack_rows(dataset.shard_rows(config, seed, shard),
                                 width)
        for i, groups in enumerate(groups_list):
            part = group_partials(groups, rows)
            if shard == keep and i == 0:
                kept = part
            if totals[i] is None:
                totals[i] = part
            else:
                for key in part:
                    totals[i][key] += part[key]
    return totals, kept


def run_pass(catalogues: list, config: dict, seed: int, workers: int,
             keep: int | None = None):
    """Evaluate every catalogue over all shards of the configuration;
    returns shard ``keep``'s partials of the first catalogue."""
    n = int(config["shards"])
    shares = [list(range(w, n, workers)) for w in range(workers)]
    groups_list = [c.groups for c in catalogues]
    kept = None
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as pool:
        futures = [pool.submit(shards_partials, groups_list, config, seed,
                               share, keep) for share in shares if share]
        for fut in futures:
            totals, k = fut.result()
            for cat, total in zip(catalogues, totals):
                cat.add(total)
            kept = k if k is not None else kept
    return kept


class Catalogue:
    """The distinct requests of a run, grouped by structure, with their
    expected answers accumulated shard by shard."""

    def __init__(self, templates, requests):
        self.groups = {}
        self.totals = {}
        by_group = {}
        for r in requests:
            by_group.setdefault(r.group, {}).setdefault(r.values, None)
        for (ti, choices), vals in by_group.items():
            t = templates[ti]
            if t.is_write():
                raise PQLError(
                    "a write inside the measured window needs a reference "
                    "that tracks the written columns' membership under "
                    "concurrent order; not built yet (PERF.md, Open "
                    "questions: count-trees-writes)")
            tree = parse(structure_text(t, choices))
            keys = list(vals)
            arr = np.asarray(keys, dtype=np.int64).reshape(len(keys), -1)
            self.groups[(ti, choices)] = {
                "tree": tree, "keys": keys,
                "values": {nm: arr[:, j]
                           for j, nm in enumerate(t.value_names)}}

    def __len__(self) -> int:
        return sum(len(g["keys"]) for g in self.groups.values())

    def partials(self, rows: dict) -> dict:
        return group_partials(self.groups, rows)

    def add(self, partials: dict) -> None:
        for key, part in partials.items():
            self.totals[key] = part if key not in self.totals \
                else self.totals[key] + part

    def expected(self, without: dict | None = None) -> dict:
        """``{(group, values): answer}``; ``without`` takes one shard's
        partials back out (the control: an answer that is no longer
        exact)."""
        out = {}
        for key, g in self.groups.items():
            total = self.totals[key]
            if without is not None:
                total = total - without[key]
            for vals, ans in zip(g["keys"], finish(g["tree"], total)):
                out[(key, vals)] = ans
        return out

    def tree(self, request):
        return self.groups[request.group]["tree"]
