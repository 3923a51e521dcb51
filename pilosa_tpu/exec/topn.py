"""A TopN pass's counts over many shards, and their reduction to pairs.

The reference merges a TopN shard by shard (executor.go:902): each
shard's counts, sorted by count descending then id ascending, are cut by
the threshold and the attribute filter, truncated to the first ``n``
survivors (fragment.go:1575-1691), and the survivors of every shard are
summed by id. `reduce_pairs` does that for every shard of a batch in one
vectorised step over flat arrays: a survivor's rank within its shard is
a cumulative sum along the sorted entries, so the truncation is a mask.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np


class TopNCounts(NamedTuple):
    """Every shard's counts of one TopN pass, flat. Entry ``i`` is row
    ``ids[i]``'s count in shard ``shards[i]``; a shard's entries are
    contiguous, shards ascending, each shard's entries sorted by count
    descending then id ascending. An entry may count 0. ``stacked``: one
    program counted every (candidate row, shard) pair of the batch."""

    shards: np.ndarray
    ids: np.ndarray
    counts: np.ndarray
    stacked: bool = False

    @classmethod
    def from_shards(cls, per_shard: dict[int, tuple]) -> "TopNCounts":
        """From shard -> (ids, counts), each already sorted."""
        order = sorted(per_shard)
        parts = [per_shard[s] for s in order]
        if not parts:
            return cls(np.zeros(0, np.int64), np.zeros(0, np.uint64),
                       np.zeros(0, np.int64))
        return cls(np.repeat(np.asarray(order, dtype=np.int64),
                             [len(ids) for ids, _ in parts]),
                   np.concatenate([ids for ids, _ in parts]),
                   np.concatenate([c for _, c in parts]).astype(np.int64))


def _rank_in_shard(shards: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """Each entry's 1-based rank among the kept entries of its shard (an
    entry not kept carries the rank of the last kept one before it)."""
    upto = np.cumsum(keep)
    starts = np.flatnonzero(np.r_[True, shards[1:] != shards[:-1]])
    before = (upto - keep)[starts]  # kept entries before each shard
    return upto - np.repeat(before, np.diff(np.r_[starts, len(shards)]))


def reduce_pairs(c: TopNCounts, n: int, min_threshold: int,
                 admit: Callable[[np.ndarray], np.ndarray] | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(ids, sums) sorted by sum descending then id ascending: the sum
    over shards of each shard's first ``n`` entries (all when ``n`` is
    0) among those that count at least ``min_threshold`` and whose id
    ``admit`` admits. ``admit`` maps distinct ids to a bool mask and is
    asked once an id."""
    keep = c.counts >= min_threshold
    if admit is not None and keep.any():
        at = np.flatnonzero(keep)
        distinct, inv = np.unique(c.ids[at], return_inverse=True)
        keep[at] = np.asarray(admit(distinct), dtype=bool)[inv]
    if n and keep.any():
        keep &= _rank_in_shard(c.shards, keep) <= n
    ids, counts = c.ids[keep], c.counts[keep]
    if not len(ids):
        return ids, counts
    order = np.argsort(ids, kind="stable")
    ids, counts = ids[order], counts[order]
    starts = np.flatnonzero(np.r_[True, ids[1:] != ids[:-1]])
    ids, sums = ids[starts], np.add.reduceat(counts, starts)
    order = np.lexsort((ids, -sums))
    return ids[order], sums[order]
