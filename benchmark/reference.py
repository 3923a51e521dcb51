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

Writes (``Set``) are judged in real time, from the client's side. A
``History`` holds every write of a run with the times its client sent it
and got its answer. For a read sent at *s* and answered at *e*, M is every
write acknowledged before *s* (it must be in the answer) and O every other
write sent before *e* (acknowledged late or never: any subset of O may be
in it). The admissible answers of a ``Count`` tree are its seeded answer
plus, for each column that M or O writes, the tree's value on that
column's bits after the writes less its value on the seeded bits; writes
to different columns add independently, so the sums are built one column
at a time over the subsets of that column's own writes in O, exactly. A
``Set`` answers ``true`` exactly when its bit is absent from the seeded
data and M; with a write of the same bit in O either answer passes.

What ``run.py`` asks of a reference module (a traffic file names another
by its ``"reference"`` key): ``Catalogue`` (``writes``, ``expected``,
``tree``, ``bit``, ``leaves``, ``allowed``), ``History`` (``add``,
``holds``, ``writes``), ``run_pass``, ``norm``, ``written_fields``.
"""

from __future__ import annotations

import bisect
import functools
import multiprocessing
import operator
import re
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass

import numpy as np

import dataset
from traffic import structure_text, structures

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


def seeded_bits(per_field: dict, lo: int, columns: np.ndarray) -> dict:
    """``{column: frozenset of (field, row)}``: the rows whose seeded bits
    hold each of ``columns``, in-shard positions ``per_field`` of the
    shard that starts at column ``lo``."""
    local = columns - lo
    held = [set() for _ in local]
    for field, per_row in per_field.items():
        for r, pos in enumerate(per_row):
            if not len(pos):
                continue
            at = np.minimum(np.searchsorted(pos, local), len(pos) - 1)
            for j in np.flatnonzero(pos[at] == local).tolist():
                held[j].add((field, r))
    return {int(c): frozenset(h) for c, h in zip(columns.tolist(), held)}


def shards_partials(groups_list: list, config: dict, seed: int,
                    shards: list, keep: int | None,
                    columns: np.ndarray | None = None):
    """A worker's share: the summed partials of each catalogue's groups
    over ``shards``, rows drawn from the seed, and apart from them shard
    ``keep``'s own partials of the first catalogue (the control leaves
    that shard out), and the seeded bits of the written ``columns`` that
    lie in these shards (``seeded_bits``)."""
    width = 1 << int(config["shard_width_exp"])
    totals = [None] * len(groups_list)
    kept = None
    held = {}
    for shard in shards:
        per_field = dataset.shard_rows(config, seed, shard)
        if columns is not None and len(columns):
            mine = columns[(columns >= shard * width)
                           & (columns < (shard + 1) * width)]
            if len(mine):
                held.update(seeded_bits(per_field, shard * width, mine))
        rows = dataset.pack_rows(per_field, width)
        for i, groups in enumerate(groups_list):
            part = group_partials(groups, rows)
            if shard == keep and i == 0:
                kept = part
            if totals[i] is None:
                totals[i] = part
            else:
                for key in part:
                    totals[i][key] += part[key]
    return totals, kept, held


def run_pass(catalogues: list, config: dict, seed: int, workers: int,
             keep: int | None = None, history: "History | None" = None):
    """Evaluate every catalogue over all shards of the configuration, and
    fill ``history``'s seeded bits of every written column; returns shard
    ``keep``'s partials of the first catalogue."""
    n = int(config["shards"])
    shares = [list(range(w, n, workers)) for w in range(workers)]
    groups_list = [c.groups for c in catalogues]
    columns = history.columns() if history is not None else None
    kept = None
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as pool:
        futures = [pool.submit(shards_partials, groups_list, config, seed,
                               share, keep, columns)
                   for share in shares if share]
        for fut in futures:
            totals, k, held = fut.result()
            for cat, total in zip(catalogues, totals):
                cat.add(total)
            kept = k if k is not None else kept
            if history is not None:
                history.seeded.update(held)
    return kept


def _int(token: str, values: dict) -> int:
    """A number of one request: literal, or its ``{placeholder}``."""
    return int(values[token[1:-1]]) if token.startswith("{") else int(token)


def _holds(node, values: dict, held) -> bool:
    """A bitmap-valued tree (or the ``Count`` around one) on ONE column
    whose rows ``held`` (``{(field, row)}``) hold it; ``_bitmap``'s
    semantics, bit by bit."""
    _, name, args, kwargs = node
    if name == "Row":
        (field, token), = kwargs.items()
        return (field, _int(token, values)) in held
    parts = [_holds(a, values, held) for a in args]
    if name == "Count":
        return parts[0]
    if name == "Intersect":
        return all(parts)
    if name == "Union":
        return any(parts)
    if name == "Xor":
        return functools.reduce(operator.xor, parts)
    if name == "Difference":
        return parts[0] and not any(parts[1:])
    raise PQLError(f"the reference has no bitmap call {name!r}")


def _leaves(node, values: dict) -> list:
    """``(field, row)`` of every ``Row`` the tree names."""
    _, name, args, kwargs = node
    if name == "Row":
        (field, token), = kwargs.items()
        return [(field, _int(token, values))]
    return [leaf for a in args for leaf in _leaves(a, values)]


def _submasks(mask: int):
    sub = mask
    while True:
        yield sub
        if sub == 0:
            return
        sub = (sub - 1) & mask


@dataclass(frozen=True)
class Bit:
    """One written bit: ``Set(column, field=row)``."""
    column: int
    field: str
    row: int

    @property
    def leaf(self) -> tuple:
        return (self.field, self.row)


class History:
    """Every write of a run with its client's times (``add``), and the
    seeded bits of the columns they write (``seeded``, filled by
    ``run_pass``): what each read and write may answer (module doc)."""

    def __init__(self):
        #: ``(bit, t_send, t_recv, acknowledged)``
        self.writes: list[tuple] = []
        #: column -> frozenset of the (field, row) seeded there
        self.seeded: dict[int, frozenset] = {}
        self._index = None

    def add(self, bit: Bit, t_send: float, t_recv: float,
            acked: bool) -> None:
        self.writes.append((bit, float(t_send), float(t_recv), bool(acked)))
        self._index = None

    def columns(self) -> np.ndarray:
        return np.unique(np.asarray([w[0].column for w in self.writes],
                                    dtype=np.int64))

    def holds(self, bit: Bit) -> bool:
        """Whether the seeded data already holds ``bit``."""
        return bit.leaf in self.seeded[bit.column]

    def _by(self) -> tuple[dict, dict]:
        """The writes by (field, row) and by bit, built once."""
        if self._index is None:
            by_leaf, by_bit = {}, {}
            for w in self.writes:
                by_leaf.setdefault(w[0].leaf, []).append(w)
                by_bit.setdefault(w[0], []).append(w)
            self._index = (by_leaf, by_bit)
        return self._index

    def set_answers(self, bit: Bit, s: float, e: float) -> tuple:
        """What a ``Set`` of ``bit`` sent at ``s``, answered at ``e``, may
        answer; the write itself is in the history once."""
        present, maybe, itself = self.holds(bit), False, False
        for _, ts, tr, ok in self._by()[1].get(bit, ()):
            if not itself and (ts, tr) == (s, e):
                itself = True
            elif ok and tr < s:
                present = True
            elif ts < e:
                maybe = True
        if present:
            return (False,)
        return (True, False) if maybe else (True,)

    def count_answers(self, tree, values: dict, seeded: int,
                      reads: list) -> list[tuple]:
        """The admissible answers of ``Count`` tree ``tree`` with
        ``values``, whose answer on the seeded data is ``seeded``, for
        each read ``(s, e)``: one sweep in order of ``s`` over the writes
        of the rows it names."""
        if tree[1] != "Count":
            raise PQLError(f"the reference judges {tree[1]!r} beside "
                           f"writes in no way yet: Count trees only")
        leaves = sorted(set(_leaves(tree, values)))
        pos = {leaf: j for j, leaf in enumerate(leaves)}
        ws = [w for leaf in leaves for w in self._by()[0].get(leaf, ())]
        if not ws:
            return [(seeded,)] * len(reads)
        table = [int(_holds(tree, values,
                            {lf for lf, j in pos.items() if m >> j & 1}))
                 for m in range(1 << len(leaves))]
        acked = sorted((w for w in ws if w[3]), key=lambda w: w[2])
        by_send = sorted(ws, key=lambda w: w[1])
        sends = [w[1] for w in by_send]
        failed = [w for w in ws if not w[3]]
        # An acknowledged write sent this long before s was answered
        # before s: only the writes sent later can be in O.
        slack = max((w[2] - w[1] for w in acked), default=0.0)
        state: dict[int, int] = {}

        def mask(column: int) -> int:
            m = state.get(column)
            if m is None:
                held = self.seeded[column]
                m = sum(1 << j for lf, j in pos.items() if lf in held)
            return m

        out = [None] * len(reads)
        moved = k = 0
        for r in sorted(range(len(reads)), key=lambda r: reads[r][0]):
            s, e = reads[r]
            while k < len(acked) and acked[k][2] < s:
                bit = acked[k][0]
                k += 1
                old = mask(bit.column)
                state[bit.column] = new = old | 1 << pos[bit.leaf]
                moved += table[new] - table[old]
            extra: dict[int, int] = {}
            lo, hi = bisect.bisect_left(sends, s - slack), \
                bisect.bisect_left(sends, e)
            for bit, ts, tr, ok in by_send[lo:hi] + failed:
                if ts < e and not (ok and tr < s):
                    extra[bit.column] = extra.get(bit.column, 0) \
                        | 1 << pos[bit.leaf]
            sums = {0}
            for column, more in extra.items():
                st = mask(column)
                deltas = {table[st | sub] - table[st]
                          for sub in _submasks(more)}
                sums = {a + d for a in sums for d in deltas}
            out[r] = tuple(sorted(seeded + moved + d for d in sums))
        return out


def written_fields(templates) -> list[str]:
    """The fields a mix's writes name, sorted."""
    return sorted({f for ti, choices in structures(templates)
                   if templates[ti].is_write()
                   for f in parse(structure_text(templates[ti], choices))[3]})


class Catalogue:
    """The distinct requests of a run, grouped by structure, with their
    expected answers accumulated shard by shard. Writes are kept apart
    (``bit``): no shard pass answers them, a ``History`` does."""

    def __init__(self, templates, requests):
        self.groups = {}
        self.totals = {}
        #: group -> the parsed Set
        self.writes = {}
        #: group -> its template's value names
        self.names = {}
        by_group = {}
        for r in requests:
            by_group.setdefault(r.group, {}).setdefault(r.values, None)
        for (ti, choices), vals in by_group.items():
            t = templates[ti]
            tree = parse(structure_text(t, choices))
            self.names[(ti, choices)] = t.value_names
            if t.is_write():
                if tree[1] != "Set" or len(tree[2]) != 1 \
                        or len(tree[3]) != 1:
                    raise PQLError(f"the reference takes Set(column, "
                                   f"field=row) alone as a write: {t.pql!r}")
                self.writes[(ti, choices)] = tree
                continue
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
        """``{(group, values): answer}`` on the seeded data; ``without``
        takes one shard's partials back out (the control: an answer that
        is no longer exact)."""
        out = {}
        for key, g in self.groups.items():
            total = self.totals[key]
            if without is not None:
                total = total - without[key]
            for vals, ans in zip(g["keys"], finish(g["tree"], total)):
                out[(key, vals)] = ans
        return out

    def tree(self, request):
        g = self.groups.get(request.group)
        return g["tree"] if g is not None else self.writes[request.group]

    def _values(self, request) -> dict:
        return dict(zip(self.names[request.group], request.values))

    def leaves(self, request) -> list:
        """``(field, row)`` of every row a request names or writes."""
        if request.group in self.writes:
            return [self.bit(request).leaf]
        return _leaves(self.groups[request.group]["tree"],
                       self._values(request))

    def bit(self, request) -> Bit:
        """The bit a write request sets."""
        _, _, (column,), kwargs = self.writes[request.group]
        (field, row), = kwargs.items()
        vals = self._values(request)
        return Bit(_int(column, vals), field, _int(row, vals))

    def allowed(self, history: History, records: list,
                expected: dict) -> list[tuple]:
        """What each of ``records``, ``(request, t_send, t_recv)`` of this
        catalogue's requests, may answer beside ``history``'s writes;
        ``expected`` is this catalogue's ``expected()``."""
        out = [None] * len(records)
        by_key = {}
        for i, (req, s, e) in enumerate(records):
            if req.group in self.writes:
                out[i] = history.set_answers(self.bit(req), s, e)
            else:
                by_key.setdefault((req.group, req.values), []).append(i)
        for key, idx in by_key.items():
            answers = history.count_answers(
                self.groups[key[0]]["tree"], self._values(records[idx[0]][0]),
                expected[key], [records[i][1:] for i in idx])
            for i, a in zip(idx, answers):
                out[i] = a
        return out
