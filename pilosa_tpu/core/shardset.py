"""ShardSet — the set of shards a request runs over, as ONE object.

A query over all of an index's shards used to carry them as loose
integers: copied, sorted, hashed and compared again at every cache on
the way to a launch (the plan cache, the stack store once or twice a
leaf). A `ShardSet` is the sorted ids as an immutable tuple whose hash
is computed once, when it is made, and whose equality is decided by
identity first. `Index.shard_set` hands out one per (epoch, schema
epoch), the same object for as long as the contents stand; any other
list of shards (a remote leg's subset, ``?shards=``, a cluster's
per-node group) is interned by content through one bounded table
(`as_shard_set`), so it is hashed once, at the door.

Correctness never rests on the interning: two sets with the same ids
that are not the same object (one fell out of the table) still hash
and compare equal, to each other and to a plain tuple of the same ids.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Iterable

#: `/debug/vars`: sets made, and requests served a set that existed.
ISSUED = "planner.shardSets.issued"
REUSED = "planner.shardSets.reused"


class ShardSet(tuple):
    """Sorted, duplicate-free shard ids. Built by `as_shard_set` or
    `Index.shard_set`, which put the ids in order; the constructor
    takes them as given."""

    def __new__(cls, ids: Iterable[int] = ()):
        self = super().__new__(cls, ids)
        self._hash = tuple.__hash__(self)
        return self

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return self is other or tuple.__eq__(self, other)

    def __ne__(self, other) -> bool:
        return self is not other and tuple.__ne__(self, other)

    def __reduce__(self):
        return (type(self), (tuple(self),))

    def __repr__(self) -> str:
        return f"ShardSet({tuple.__repr__(self)})"


#: what a query that reads no shard (writes only) carries.
EMPTY = ShardSet()

#: distinct explicit shard lists kept; the least recently used goes
#: first. A node sees its index's full sets, its own per-node groups
#: and the subsets coordinators send it: a few dozen at most.
TABLE_SIZE = 64

_lock = threading.Lock()
_table: "OrderedDict[tuple, ShardSet]" = OrderedDict()


def _count(stats, name: str) -> None:
    if stats is not None:
        stats.count(name, 1)


def _find(ids: tuple, stats) -> ShardSet | None:
    """The table's set with these contents, touched. `_lock` held."""
    hit = _table.get(ids)
    if hit is not None:
        _table.move_to_end(hit)
        _count(stats, REUSED)
    return hit


def _intern(made: ShardSet, stats) -> ShardSet:
    """``made``'s twin from the table if it has one, else ``made``,
    entered. `_lock` held."""
    twin = _find(made, stats)
    if twin is not None:
        return twin
    _table[made] = made
    while len(_table) > TABLE_SIZE:
        _table.popitem(last=False)
    _count(stats, ISSUED)
    return made


def as_shard_set(shards: Iterable[int], stats=None) -> ShardSet:
    """``shards`` as a `ShardSet`: itself when it already is one, else
    the table's object for its contents (sorted, duplicates dropped)."""
    if isinstance(shards, ShardSet):
        return shards
    raw = tuple(shards)
    with _lock:
        # The one hash of the list: a sorted list of ints finds its set
        # at once (a ShardSet hashes and compares as the plain tuple).
        hit = _find(raw, stats)
        if hit is not None:
            return hit
        return _intern(ShardSet(sorted({int(s) for s in raw})), stats)


def reissue(ids: Iterable[int], prev: ShardSet | None, stats=None) -> ShardSet:
    """The set for ``ids`` (any iterable of distinct ints): ``prev``
    itself when it holds exactly these, so that an epoch that moved
    over unchanged contents keeps the set's identity."""
    ids = tuple(sorted(ids))
    if prev is not None and prev == ids:
        _count(stats, REUSED)
        return prev
    with _lock:
        return _intern(ShardSet(ids), stats)
