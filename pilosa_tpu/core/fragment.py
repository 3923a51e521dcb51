"""Fragment — one (field, view, shard) bitmap matrix.

Reference: fragment.go (struct :100, setBit/clearBit :645/:729, row :602,
pos encoding :3090, sum/min/max :1111-1227, rangeOp :1272, top :1570,
bulkImport :1997, importValue :2205, Blocks/checksums :1762-1841,
mutex/bool vectors :3094-3164).

Design split (TPU-first):
- **Host truth**: ``rows[row_id] -> HostRow`` — sparse positions at rest,
  dense past cutoff. Mutations are host ops (the device never scatters
  single bits; cf. SURVEY §7 "mutation on TPU").
- **Device cache**: dense uint32 blocks uploaded lazily per row / per row
  stack, invalidated by a generation counter. Query math (set algebra,
  BSI, popcounts) runs on-device over these blocks.
- **Row-count vector**: per-row popcounts maintained incrementally on
  host; TopN/Rows read it directly. This *replaces* the reference's
  rankCache machinery (cache.go:136) — recompute is exact and cheap, so
  there is no threshold staleness to manage.

The reference's positional flattening pos = row*ShardWidth + col%ShardWidth
(fragment.go:3090) survives only in the WAL/serialized format; in memory the
row dimension is explicit (it is the device batch axis).
"""

from __future__ import annotations

import threading
from contextlib import ExitStack
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.config import (
    DEFAULT_CACHE_SIZE,
    HASH_BLOCK_SIZE,
    SHARD_WIDTH,
    WORDS_PER_SHARD,
)
from pilosa_tpu.core.hostrow import HostRow
from pilosa_tpu.core.row import Row
from pilosa_tpu.obs.tracing import start_span
from pilosa_tpu.ops import bitops, bsi as bsi_ops, pallas_kernels

# BSI row layout, reference fragment.go:87-93.
FALSE_ROW_ID = 0
TRUE_ROW_ID = 1
BSI_EXISTS_BIT = 0
BSI_SIGN_BIT = 1
BSI_OFFSET_BIT = 2

#: Row-group tiling (SURVEY §7): streaming count paths materialize at most
#: this many rows on device at once, so TopN/GroupBy over huge fields
#: (1M+ rows; BASELINE "TopN ranked cache 1M×10M") run in O(tile) HBM
#: instead of O(rows) — the reference's analog is per-container iteration
#: (fragment.go:1570-1740).
ROW_TILE = 512
#: Row sets at or below this size use the cached whole-stack fast path
#: (repeat queries hit HBM-resident blocks with zero re-upload).
STACK_CACHE_MAX_ROWS = 1024
#: With ``reuse=True``, up to this many streamed tiles stay device-resident
#: so repeated sweeps over the same row set (GroupBy: one per group prefix)
#: skip re-materialization; larger sets fall back to pure streaming.
MAX_RESIDENT_TILES = 8


class Fragment:
    """One shard of one view of one field."""

    def __init__(self, index: str, field: str, view: str, shard: int,
                 cache_type: str = "ranked", cache_size: int = DEFAULT_CACHE_SIZE,
                 stats=None, op_writer: Callable | None = None,
                 mutex: bool = False, epoch=None):
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.stats = stats
        #: WAL hook: called as op_writer(op, rows, cols) on mutation.
        self.op_writer = op_writer
        #: Mutex semantics: at most one row bit per column (reference
        #: mutexVector fragment.go:3094; bool fields use rows 0/1).
        self.mutex = mutex
        #: index-level Epoch (core.index): bumped on every mutation so
        #: index-wide caches (planner leaf stacks, executor results)
        #: validate in O(1) instead of per-fragment generation walks.
        self.epoch = epoch

        self.rows: dict[int, HostRow] = {}
        self.generation = 0
        #: Mutex vector (fragment.go:3094): lazily-built local-pos -> row_id
        #: map so mutex lookups/imports are O(1) per column instead of a
        #: scan over every row. None = not built / dirty. Maintained
        #: incrementally by set_bit/clear_bit; any other mutation of
        #: ``rows`` must reset it to None.
        self._col_row: dict[int, int] | None = None
        #: generation-stamped (gen, ids, counts) — see row_counts().
        self._count_cache: tuple | None = None
        #: generation-stamped (gen, ids, counts) sorted by count desc —
        #: see top_counts().
        self._top_cache: tuple | None = None
        #: generation-stamped concatenated sparse-row index — see
        #: _sparse_index().
        self._sparse_cache: tuple | None = None
        #: generation-stamped (gen, depth, [depth+1, W] words) host stack
        #: of the sign + magnitude planes — see value().
        self._value_stack: tuple | None = None
        self._lock = threading.RLock()
        # device caches: row_id -> (gen, jax.Array[W]); stack key -> (gen, ids, jax.Array[n, W])
        self._dev_rows: dict[int, tuple[int, jax.Array]] = {}
        self._dev_stacks: dict[object, tuple[int, tuple, jax.Array]] = {}

    # -- position encoding -------------------------------------------------

    def _local(self, column_id: int) -> int:
        lo = self.shard * SHARD_WIDTH
        if not (lo <= column_id < lo + SHARD_WIDTH):
            raise ValueError(f"column:{column_id} out of bounds")
        return column_id - lo

    # -- mutation ----------------------------------------------------------

    def _invalidate(self, bump_epoch: bool = True):
        self.generation += 1
        if bump_epoch and self.epoch is not None:
            # Shard-tagged: plans not touching this shard keep their
            # cached results (Epoch.max_shard_epoch).
            self.epoch.bump(shard=self.shard)
        # Stale device blocks would never be re-hit (generation mismatch) but
        # would pin HBM forever; drop them eagerly.
        self._dev_rows.clear()
        self._dev_stacks.clear()
        self._value_stack = None

    def set_bit(self, row_id: int, column_id: int) -> bool:
        with self._lock:
            pos = self._local(column_id)
            if self.mutex:
                # Unset any other row's bit for this column first
                # (reference handleMutex fragment.go:3094-3164).
                existing = self.row_for_column(column_id)
                if existing is not None and existing != row_id:
                    self.clear_bit(existing, column_id)
            hr = self.rows.get(row_id)
            if hr is None:
                hr = self.rows[row_id] = HostRow()
            changed = hr.add(pos)
            if changed:
                if self.mutex and self._col_row is not None:
                    self._col_row[pos] = row_id
                self._invalidate()
                if self.op_writer:
                    with start_span("wal.append"):
                        self.op_writer("add", [row_id], [column_id])
            return changed

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        with self._lock:
            pos = self._local(column_id)
            hr = self.rows.get(row_id)
            if hr is None:
                return False
            changed = hr.remove(pos)
            if changed:
                if (self.mutex and self._col_row is not None
                        and self._col_row.get(pos) == row_id):
                    del self._col_row[pos]
                self._invalidate()
                if self.op_writer:
                    with start_span("wal.append"):
                        self.op_writer("remove", [row_id], [column_id])
            return changed

    def contains(self, row_id: int, column_id: int) -> bool:
        hr = self.rows.get(row_id)
        return hr is not None and hr.contains(self._local(column_id))

    def clear_row(self, row_id: int) -> bool:
        """Reference clearRow (fragment.go, used by ClearRow/Store)."""
        with self._lock:
            hr = self.rows.pop(row_id, None)
            if hr is None or hr.count() == 0:
                return False
            self._col_row = None
            self._invalidate()
            if self.op_writer:
                with start_span("wal.append"):
                    cols = (hr.to_positions()
                            + np.uint64(self.shard * SHARD_WIDTH))
                    self.op_writer("removeBatch", [row_id] * len(cols),
                                   cols.tolist())
            return True

    def set_row(self, row: Row, row_id: int) -> bool:
        """Replace a row wholesale (reference setRow, used by Store)."""
        with self._lock:
            seg = row.segment(self.shard)
            words = np.asarray(seg) if seg is not None else bitops.np_zero_row()
            self.rows[row_id] = HostRow.from_words(words)
            self._col_row = None
            self._invalidate()
            if self.op_writer:
                with start_span("wal.append"):
                    cols = bitops.words_to_positions(words) + np.uint64(
                        self.shard * SHARD_WIDTH)
                    self.op_writer("setRow", [row_id], cols.tolist())
            return True

    def bulk_import(self, row_ids: Iterable[int], column_ids: Iterable[int],
                    clear: bool = False) -> int:
        """Batched set/clear (reference bulkImport fragment.go:1997).
        Returns number of changed bits.

        The bulk paths take the lock inside ``import.merge`` (its wait is
        part of the merge) and hold it through ``wal.append``, so the log
        keeps the order of the mutations and the two spans do not nest."""
        with ExitStack() as held:
            with start_span("import.merge"):
                held.enter_context(self._lock)
                if not isinstance(row_ids, np.ndarray):
                    row_ids = np.asarray(list(row_ids), dtype=np.uint64)
                row_ids = row_ids.astype(np.uint64, copy=False)
                if not isinstance(column_ids, np.ndarray):
                    column_ids = np.asarray(list(column_ids),
                                            dtype=np.uint64)
                column_ids = column_ids.astype(np.uint64, copy=False)
                if len(row_ids) != len(column_ids):
                    raise ValueError("row/column length mismatch")
                if len(row_ids) == 0:
                    return 0
                local = column_ids - np.uint64(self.shard * SHARD_WIDTH)
                if (local >= SHARD_WIDTH).any():
                    raise ValueError("column out of shard bounds")
                changed = 0
                # Vectorized by-row split: one stable sort + boundary scan
                # (a per-row boolean mask would be O(rows * n)).
                order = np.argsort(row_ids, kind="stable")
                sorted_rows = row_ids[order]
                sorted_local = local[order]
                uniq, starts = np.unique(sorted_rows, return_index=True)
                bounds = np.append(starts, len(sorted_rows))
                for i, rid in enumerate(uniq.tolist()):
                    lo, hi = int(bounds[i]), int(bounds[i + 1])
                    hr = self.rows.get(int(rid))
                    if hr is None:
                        if clear:
                            continue
                        hr = self.rows[int(rid)] = HostRow()
                    if clear:
                        changed += hr.remove_many(sorted_local[lo:hi])
                    else:
                        changed += hr.add_many(sorted_local[lo:hi])
                if changed:
                    self._col_row = None
                    self._invalidate()
            if changed and self.op_writer:
                with start_span("wal.append"):
                    self.op_writer("removeBatch" if clear else "addBatch",
                                   row_ids.tolist(), column_ids.tolist())
            return changed

    def bulk_import_sorted_local(self, row_ids: np.ndarray,
                                 local: np.ndarray, clear: bool = False) -> int:
        """Bulk set/clear of shard-relative positions PRE-SORTED by
        (row, pos) — the no-copy core of the import path (reference
        importPositions fragment.go:2053). Boundary-scans row groups,
        dedupes each group's sorted positions with one diff pass, and
        hands them to HostRow without any further sort."""
        with ExitStack() as held:
            with start_span("import.merge"):
                held.enter_context(self._lock)
                n = len(row_ids)
                if n == 0:
                    return 0
                row_ids = np.asarray(row_ids, dtype=np.int64)
                local = np.asarray(local, dtype=np.uint32)
                cut = np.flatnonzero(row_ids[1:] != row_ids[:-1]) + 1
                bounds = np.concatenate(([0], cut, [n]))
                changed = 0
                for i in range(len(bounds) - 1):
                    lo, hi = int(bounds[i]), int(bounds[i + 1])
                    rid = int(row_ids[lo])
                    seg = local[lo:hi]
                    if hi - lo > 1:  # drop duplicate positions (sorted input)
                        keep = np.empty(hi - lo, dtype=bool)
                        keep[0] = True
                        np.not_equal(seg[1:], seg[:-1], out=keep[1:])
                        if not keep.all():
                            seg = seg[keep]
                    hr = self.rows.get(rid)
                    if hr is None:
                        if clear:
                            continue
                        hr = self.rows[rid] = HostRow()
                    if clear:
                        changed += hr.remove_many_sorted_unique(seg)
                    else:
                        changed += hr.add_many_sorted_unique(seg)
                if changed:
                    self._col_row = None
                    self._invalidate()
            if changed and self.op_writer:
                with start_span("wal.append"):
                    base = np.uint64(self.shard * SHARD_WIDTH)
                    self.op_writer("removeBatch" if clear else "addBatch",
                                   row_ids.astype(np.uint64),
                                   local.astype(np.uint64) + base)
            return changed

    def merge_row_words(self, row_id: int, words: np.ndarray,
                        bit_count: int | None = None,
                        bump_epoch: bool = True,
                        prefer_dense: bool = False) -> int:
        """Merge a freshly-scattered dense word block into one row — the
        landing half of the native bulk-import scatter (reference
        importRoaringBits' container merge, roaring.go:1511). ``words``
        ownership transfers to the fragment; returns bits added.

        Bulk callers landing MANY rows per batch pass bump_epoch=False
        and bump the shared index epoch ONCE at the end (one cache
        invalidation + dirty broadcast per import, not per plane), and
        prefer_dense=True when ``words`` is a view of a scatter buffer
        whose chunk stays pinned by sibling planes anyway — converting a
        near-empty plane to positions there costs a scan and saves no
        memory."""
        from pilosa_tpu import native
        with ExitStack() as held:
            with start_span("import.merge"):
                held.enter_context(self._lock)
                if bit_count is None:
                    bit_count = native.popcount_words(words)
                if bit_count == 0:
                    return 0
                hr = self.rows.get(row_id)
                if hr is None or hr.n == 0:
                    self.rows[row_id] = HostRow.adopt_words(
                        words, bit_count, prefer_dense=prefer_dense)
                    changed = bit_count
                else:
                    changed = hr.merge_words(words)
                if changed:
                    self._col_row = None
                    self._invalidate(bump_epoch=bump_epoch)
            if changed and self.op_writer:
                with start_span("wal.append"):
                    pos = native.words_to_positions(words)
                    base = np.uint64(self.shard * SHARD_WIDTH)
                    self.op_writer("addBatch",
                                   np.full(len(pos), row_id, dtype=np.uint64),
                                   pos + base)
            return changed

    def bulk_import_mutex(self, row_ids, column_ids) -> int:
        """Mutex-field import: setting (row, col) clears any other row's bit
        in that column; last write per column wins (reference
        bulkImportMutex fragment.go:2108). Steals are found through the
        column->row mutex vector (O(1) per column, fragment.go:3094), not
        by scanning every row."""
        with self._lock:
            if len(row_ids) != len(column_ids):
                raise ValueError("row/column length mismatch")
            base = np.uint64(self.shard * SHARD_WIDTH)
            desired: dict[int, int] = {}  # local pos -> row id
            for rid, cid in zip(row_ids, column_ids):
                desired[self._local(int(cid))] = int(rid)
            vec = self._mutex_map()
            changed = 0
            # Clear any column whose bit currently lives in a different row.
            steals: dict[int, list[int]] = {}
            for pos, rid in desired.items():
                cur = vec.get(pos)
                if cur is not None and cur != rid:
                    steals.setdefault(cur, []).append(pos)
            for rid, lpos in steals.items():
                stolen = np.asarray(lpos, dtype=np.uint64)
                changed += self.rows[rid].remove_many(stolen)
                for p in lpos:
                    vec.pop(p, None)
                if self.op_writer:
                    with start_span("wal.append"):
                        self.op_writer("removeBatch", [rid] * len(lpos),
                                       (stolen + base).tolist())
            # Set the desired bits, grouped by row.
            by_row: dict[int, list[int]] = {}
            for pos, rid in desired.items():
                by_row.setdefault(rid, []).append(pos)
            for rid, lpos in by_row.items():
                hr = self.rows.get(rid)
                if hr is None:
                    hr = self.rows[rid] = HostRow()
                added = hr.add_many(np.asarray(lpos, dtype=np.uint64))
                changed += added
                for p in lpos:
                    vec[p] = rid
                if added and self.op_writer:
                    with start_span("wal.append"):
                        self.op_writer("addBatch", [rid] * len(lpos),
                                       [p + int(base) for p in lpos])
            if changed:
                self._invalidate()
            return changed

    def import_roaring(self, data: bytes, clear: bool = False) -> int:
        """Merge a serialized roaring bitmap of pos-encoded bits
        (pos = row*ShardWidth + col_local, fragment.go:3090) into this
        fragment (reference importRoaring fragment.go:2255 →
        ImportRoaringBits roaring.go:1511). Returns changed-bit count."""
        from pilosa_tpu import native
        with start_span("import.decode") as span:
            positions = native.decode_roaring(data)
            # ``import.bits`` goes where the span's counters go: the
            # registry of the thread's outermost span (``http.request``).
            if span.stats is not None:
                span.stats.count("import.bits", len(positions))
            if len(positions) == 0:
                return 0
            rows = (positions // np.uint64(SHARD_WIDTH)).astype(np.uint64)
            cols = (positions % np.uint64(SHARD_WIDTH)).astype(np.uint64)
            abs_cols = cols + np.uint64(self.shard * SHARD_WIDTH)
            row_list, col_list = rows.tolist(), abs_cols.tolist()
        return self.bulk_import(row_list, col_list, clear=clear)

    #: bit budget per streamed transfer chunk (~8 MB of positions):
    #: the resize migration streamer slices rows_snapshot into PTS1
    #: import requests of at most this many (row, col) pairs.
    TRANSFER_CHUNK_BITS = 1 << 20

    def to_roaring(self) -> bytes:
        """Serialize all bits in the reference's pos-encoded roaring
        format (the fragment-data transfer format, fragment.go:2436).
        This materializes the WHOLE fragment — transfer paths (resize,
        sync) instead chunk rows_snapshot through the PTS1 import
        stream in TRANSFER_CHUNK_BITS batches."""
        from pilosa_tpu import native
        parts = [pos + np.uint64(rid * SHARD_WIDTH)
                 for rid, pos in self.rows_snapshot()]
        positions = (np.concatenate(parts) if parts
                     else np.empty(0, dtype=np.uint64))
        return native.encode_roaring(positions)

    # -- reads -------------------------------------------------------------

    def row_ids(self) -> list[int]:
        return sorted(self.rows)

    def max_row_id(self) -> int | None:
        return max(self.rows) if self.rows else None

    def min_row_id(self) -> int | None:
        return min(self.rows) if self.rows else None

    def row_words(self, row_id: int) -> np.ndarray:
        """Host dense block for one row (zeros if absent). Locked: the
        materialization may flush pending adds (hostrow._flush)."""
        with self._lock:
            hr = self.rows.get(row_id)
            if hr is None:
                return bitops.np_zero_row()
            return hr.to_words()

    def row_cardinality(self, row_id: int) -> int:
        """Set-bit count of one row, O(1) (HostRow maintains it
        incrementally); 0 for absent rows. Lockless like `contains`:
        the planner's residency class policy reads this per shard at
        plan time, and an off-by-a-few count under a concurrent write
        only shifts WHICH representation class is chosen, never
        correctness."""
        hr = self.rows.get(row_id)
        return 0 if hr is None else hr.count()

    def row_source(self, row_id: int) -> tuple[int, np.ndarray | None]:
        """What one row IS at this moment, for a stack build that
        writes it later and outside every lock: ``(n, positions)``, the
        set-bit count and the row's own sorted uint64 position array
        (pending single-bit adds flushed first, under this fragment's
        lock), not a copy. The reference stays true after the lock is
        let go because a position array is never written in place:
        every mutator of `HostRow` assigns a new one, and whoever holds
        the old one keeps it alive. ``positions`` is None for a row
        held as a dense block, which writers DO change in place: that
        row is copied under the lock (`row_words_into`). ``(0, None)``
        for an absent or empty row."""
        with self._lock:
            hr = self.rows.get(row_id)
            if hr is None or hr.n == 0:
                return 0, None
            if hr.dense is None:
                hr._flush()  # may densify
            return hr.n, hr.positions

    def row_words_into(self, row_id: int, out: np.ndarray) -> str | None:
        """Write one row's dense block into ``out``, a zeroed uint32[W]
        row of a stack matrix the caller owns, under this fragment's
        lock (pending single-bit adds are flushed first). Returns the
        route `HostRow.words_into` took, or None for an absent or empty
        row, which leaves ``out`` as it is. One call a shard, each
        letting go of the interpreter lock and queueing for it again:
        `MeshPlanner._build_stack` takes it only for rows held dense,
        the distributed planner for every row of its per-device blocks.
        A row held as positions goes through `row_source` and one
        native call a stack (PERF.md §6, PR 34)."""
        with self._lock:
            hr = self.rows.get(row_id)
            if hr is None or hr.n == 0:
                return None
            return hr.words_into(out)

    def row_positions(self, row_id: int) -> np.ndarray:
        """Sorted uint64 in-shard positions of one row (empty if
        absent), the caller's own copy: what the planner's COO and
        packed uploads ship."""
        with self._lock:
            hr = self.rows.get(row_id)
            if hr is None:
                return np.empty(0, dtype=np.uint64)
            return hr.to_positions()

    def rows_snapshot(self) -> list[tuple[int, np.ndarray]]:
        """Atomic [(row_id, positions)] snapshot of every row, sorted by
        id — THE way to read all rows for serialization/checksums (the
        position materialization may flush pending adds, so it must
        happen under the fragment lock)."""
        with self._lock:
            return [(rid, self.rows[rid].to_positions())
                    for rid in sorted(self.rows)]

    def device_row(self, row_id: int) -> jax.Array:
        """Device block for one row, cached until next mutation."""
        with self._lock:
            ent = self._dev_rows.get(row_id)
            if ent is not None and ent[0] == self.generation:
                return ent[1]
            arr = jnp.asarray(self.row_words(row_id))
            self._dev_rows[row_id] = (self.generation, arr)
            return arr

    def device_stack(self, row_ids: tuple[int, ...], key: object = None) -> jax.Array:
        """[len(row_ids), W] device block stack; cached by key until mutation.
        This is the unit the fused planner and BSI ops consume."""
        key = key if key is not None else row_ids
        with self._lock:
            ent = self._dev_stacks.get(key)
            if ent is not None and ent[0] == self.generation and ent[1] == row_ids:
                return ent[2]
            mat = np.stack([self.row_words(r) for r in row_ids]) if row_ids else \
                np.zeros((0, WORDS_PER_SHARD), dtype=np.uint32)
            arr = jnp.asarray(mat)
            self._dev_stacks[key] = (self.generation, row_ids, arr)
            return arr

    def row(self, row_id: int) -> Row:
        """Row result for one bitmap row (reference fragment.row :602)."""
        return Row({self.shard: self.device_row(row_id)})

    def intersection_counts(self, row_ids, seg,
                            reuse: bool = False) -> np.ndarray:
        """popcount(row & seg) for each row id — the exact-count engine
        behind TopN/GroupBy/MinRow/MaxRow.

        Two-tier, matching the storage split: SPARSE rows (position
        arrays) are counted host-side by vectorized membership against
        one host copy of the filter — O(set bits) per row, the analog of
        roaring's array-container intersection (roaring.go:3121) and
        ~1000x less data motion than densifying a 20-bit row to 128 KiB.
        DENSE rows go to the device: small sets ride the cached stack;
        large ones stream fixed [ROW_TILE, W] tiles so device memory is
        O(tile) regardless of field cardinality.

        ``reuse=True`` keeps up to MAX_RESIDENT_TILES streamed tiles
        device-resident (generation-checked) so a caller sweeping the same
        row set against many segments — GroupBy's last level, one sweep
        per group prefix — pays materialization and upload once.

        Deliberate: the lock spans the whole sweep, including device
        dispatches, so the counts vector reflects one atomic fragment
        state — writers stall for the sweep, exactly like the reference's
        fragment.top holding f.mu for its full walk (fragment.go:1570)."""
        out, parts = self.intersection_counts_async(row_ids, seg, reuse)
        for slots, dev in parts:
            out[slots] = np.asarray(dev, dtype=np.int64)[:len(slots)]
        return out

    def intersection_counts_async(self, row_ids, seg, reuse: bool = False,
                                  seg_host: np.ndarray | None = None):
        """Non-blocking intersection_counts: returns (counts, parts)
        where ``counts`` already holds the host-tier (sparse) results and
        ``parts`` is [(slot_indices, device_count_array), ...] — device
        programs DISPATCHED but not synced. Callers sweeping many
        fragments resolve every part in one transfer wave instead of one
        sync per fragment (the r2 filtered-TopN latency). Pass
        ``seg_host`` when the filter already exists host-side so the
        sparse tier never pulls it off the device. ``seg`` is a
        single-device array (or host words): the dense tier is a
        single-device program, and a caller whose stacks span a mesh
        co-locates the segment first (MeshPlanner.execute_topn_counts)."""
        ids = [int(r) for r in row_ids]
        if not ids:
            return np.empty(0, dtype=np.int64), []
        seg = seg if isinstance(seg, jax.Array) else jnp.asarray(seg)
        out = np.zeros(len(ids), dtype=np.int64)
        parts: list[tuple[np.ndarray, jax.Array]] = []
        ids_arr = np.asarray(ids, dtype=np.int64)
        with self._lock:
            s_ids, concat, starts, lens = self._sparse_index()
            dense_ids: list[int] = []
            dense_slots: list[int] = []
            if len(s_ids):
                at = np.searchsorted(s_ids, ids_arr)
                at_c = np.minimum(at, len(s_ids) - 1)
                is_sparse = s_ids[at_c] == ids_arr
            else:
                at_c = np.zeros(len(ids_arr), dtype=np.int64)
                is_sparse = np.zeros(len(ids_arr), dtype=bool)
            for i in np.flatnonzero(~is_sparse).tolist():
                hr = self.rows.get(ids[i])
                if hr is not None and hr.is_dense:
                    dense_ids.append(ids[i])
                    dense_slots.append(i)
                # else: absent/empty row, count stays 0

            sparse_slots = np.flatnonzero(is_sparse)
            if len(sparse_slots):
                if seg_host is None:
                    seg_host = np.asarray(seg, dtype=np.uint32)
                sel = at_c[sparse_slots]
                if len(sel) == len(s_ids) and np.array_equal(
                        sel, np.arange(len(s_ids))):
                    pos = concat            # whole-index sweep: no gather
                    offsets = starts
                else:
                    l_sel = lens[sel]
                    s_sel = starts[sel]
                    total = int(l_sel.sum())
                    # Ragged gather without a per-row loop: ones with
                    # jumps at group heads, cumsum = flat indices.
                    step = np.ones(total, dtype=np.int64)
                    head = np.zeros(len(l_sel), dtype=np.int64)
                    np.cumsum(l_sel[:-1], out=head[1:])
                    step[head[0]] = s_sel[0]
                    if len(l_sel) > 1:
                        step[head[1:]] = (s_sel[1:] - s_sel[:-1]
                                          - l_sel[:-1] + 1)
                    pos = concat[np.cumsum(step)]
                    offsets = head
                word = (pos >> np.uint64(5)).astype(np.int64)
                bit = np.left_shift(
                    np.uint32(1), (pos & np.uint64(31)).astype(np.uint32))
                hits = ((seg_host[word] & bit) != 0).astype(np.int64)
                # All lens > 0, so every reduceat offset is < len(hits).
                out[sparse_slots] = np.add.reduceat(hits, offsets)

            if dense_ids:
                if len(dense_ids) <= STACK_CACHE_MAX_ROWS:
                    stack = self.device_stack(tuple(dense_ids))
                    parts.append((np.asarray(dense_slots, dtype=np.int64),
                                  pallas_kernels.pair_count(stack, seg,
                                                            "and")))
                else:
                    n_tiles = (len(dense_ids) + ROW_TILE - 1) // ROW_TILE
                    cache_tiles = reuse and n_tiles <= MAX_RESIDENT_TILES
                    # Fixed tile shape (zero-padded tail) → one compiled
                    # kernel. Tile keys are positional ("ic_tile", lo),
                    # NOT id-set-keyed, so a fragment never pins more
                    # than MAX_RESIDENT_TILES tiles: a different id set
                    # replaces them (device_stack verifies stored ids).
                    dense_slots_a = np.asarray(dense_slots, dtype=np.int64)
                    for lo in range(0, len(dense_ids), ROW_TILE):
                        chunk = dense_ids[lo:lo + ROW_TILE]
                        if cache_tiles:
                            arr = self.device_stack(tuple(chunk),
                                                    key=("ic_tile", lo))
                        else:
                            # Fresh buffer per tile: uploads are async
                            # (and zero-copy on the CPU backend), so a
                            # reused buffer would be overwritten while
                            # the deferred kernel still reads it.
                            mat = np.zeros((ROW_TILE, WORDS_PER_SHARD),
                                           dtype=np.uint32)
                            for i, r in enumerate(chunk):
                                mat[i] = self.row_words(r)
                            arr = jnp.asarray(mat)
                        parts.append(
                            (dense_slots_a[lo:lo + len(chunk)],
                             pallas_kernels.pair_count(arr, seg, "and")))
        return out, parts

    def _sparse_index(self):
        """(row_ids, concat_positions, starts, lens) over every non-empty
        SPARSE row, cached per generation — the batched count paths'
        replacement for per-row position materialization (one build per
        mutation, then every TopN/GroupBy sweep is pure vectorized
        numpy). Caller must hold the fragment lock."""
        if self._sparse_cache is not None and \
                self._sparse_cache[0] == self.generation:
            return self._sparse_cache[1:]
        ids: list[int] = []
        bufs: list[np.ndarray] = []
        for rid in sorted(self.rows):
            hr = self.rows[rid]
            if hr.is_dense or hr.n == 0:
                continue
            hr._flush()
            ids.append(rid)
            bufs.append(hr.positions)  # no copy: generation guards reuse
        ids_a = np.asarray(ids, dtype=np.int64)
        lens = np.fromiter((len(b) for b in bufs), dtype=np.int64,
                           count=len(bufs))
        concat = (np.concatenate(bufs) if bufs
                  else np.empty(0, dtype=np.uint64))
        starts = np.zeros(len(lens), dtype=np.int64)
        if len(lens) > 1:
            np.cumsum(lens[:-1], out=starts[1:])
        self._sparse_cache = (self.generation, ids_a, concat, starts, lens)
        return ids_a, concat, starts, lens

    def row_counts(self) -> tuple[np.ndarray, np.ndarray]:
        """(row_ids, counts), cached per generation — the exact
        replacement for the reference's rankCache (cache.go:136): first
        TopN after a mutation pays one O(rows) sweep, repeats are O(1).
        Unlike the threshold-gated cache there is no staleness."""
        with self._lock:
            if self._count_cache is not None and \
                    self._count_cache[0] == self.generation:
                return self._count_cache[1], self._count_cache[2]
            ids = np.asarray(sorted(self.rows), dtype=np.uint64)
            counts = np.asarray([self.rows[int(i)].count() for i in ids],
                                dtype=np.int64)
            self._count_cache = (self.generation, ids, counts)
            return ids, counts

    def top_counts(self) -> tuple[np.ndarray, np.ndarray]:
        """(ids, counts) sorted by count desc then id asc, cached per
        generation — the sorted order is what made the reference's
        rankCache O(results) per TopN (cache.go:136); here it is exact."""
        with self._lock:
            if self._top_cache is not None and \
                    self._top_cache[0] == self.generation:
                return self._top_cache[1], self._top_cache[2]
            ids, counts = self.row_counts()
            order = np.lexsort((ids, -counts))
            ids, counts = ids[order], counts[order]
            self._top_cache = (self.generation, ids, counts)
            return ids, counts

    def row_for_column(self, column_id: int) -> int | None:
        """Mutex/bool vector Get (fragment.go:3117): which row holds this
        column's bit, if any."""
        pos = self._local(column_id)
        if self.mutex:
            return self._mutex_map().get(pos)
        for rid, hr in self.rows.items():
            if hr.contains(pos):
                return rid
        return None

    def _mutex_map(self) -> dict[int, int]:
        """The column vector, rebuilt from rows when dirty."""
        with self._lock:
            if self._col_row is None:
                m: dict[int, int] = {}
                for rid in sorted(self.rows):
                    for p in self.rows[rid].to_positions().tolist():
                        m[int(p)] = rid
                self._col_row = m
            return self._col_row

    # -- BSI ---------------------------------------------------------------

    def _bsi_stacks(self, bit_depth: int):
        """(exists, sign, bits[depth, W]) device arrays."""
        ids = tuple(range(BSI_OFFSET_BIT, BSI_OFFSET_BIT + bit_depth))
        bits = self.device_stack(ids, key=("bsi", bit_depth))
        return self.device_row(BSI_EXISTS_BIT), self.device_row(BSI_SIGN_BIT), bits

    def set_value(self, column_id: int, bit_depth: int, value: int) -> bool:
        """Sign-magnitude BSI write (reference setValueBase fragment.go:939)."""
        with self._lock:
            gen_before = self.generation
            changed = False
            changed |= self.set_bit(BSI_EXISTS_BIT, column_id)
            if value < 0:
                changed |= self.set_bit(BSI_SIGN_BIT, column_id)
            else:
                changed |= self.clear_bit(BSI_SIGN_BIT, column_id)
            mag = abs(value)
            for i in range(bit_depth):
                if (mag >> i) & 1:
                    changed |= self.set_bit(BSI_OFFSET_BIT + i, column_id)
                else:
                    changed |= self.clear_bit(BSI_OFFSET_BIT + i, column_id)
            if changed and getattr(self, "_hll_planes", None):
                from pilosa_tpu.sketch import store as sketch_store
                sketch_store.observe_values(
                    self, np.asarray([self._local(column_id)], dtype=np.int64),
                    np.asarray([value], dtype=np.int64),
                    gen_before, self.generation)
            return changed

    #: exists-plane cardinality below which value() keeps the per-bit
    #: probe loop: materializing the plane stack costs O(depth * W), a
    #: loss for tiny fragments but amortized across the thousands of
    #: lookups row materialization makes against a big one.
    VALUE_STACK_MIN = 2048

    def value(self, column_id: int, bit_depth: int) -> tuple[int, bool]:
        """(value, exists) — reference fragment.value (fragment.go:897).

        Row materialization calls this per column, so the per-bit
        ``contains`` loop (one dict probe + searchsorted per plane) was
        the hot path. Planes gather instead as ONE fancy-index into a
        generation-stamped ``[depth+1, W]`` host word stack (sign row
        first, then magnitude rows) rebuilt lazily after mutations."""
        if not self.contains(BSI_EXISTS_BIT, column_id):
            return 0, False
        pos = self._local(column_id)
        vs = self._value_stack
        if vs is None or vs[0] != self.generation or vs[1] < bit_depth:
            hr_e = self.rows.get(BSI_EXISTS_BIT)
            if hr_e is None or hr_e.n < self.VALUE_STACK_MIN:
                mag = 0
                for i in range(bit_depth):
                    if self.contains(BSI_OFFSET_BIT + i, column_id):
                        mag |= 1 << i
                if self.contains(BSI_SIGN_BIT, column_id):
                    mag = -mag
                return mag, True
            vs = self._build_value_stack(bit_depth)
        words = vs[2][: bit_depth + 1, pos >> 5]  # one gather across planes
        on = (words >> np.uint32(pos & 31)) & np.uint32(1)
        mag = int(on[1:].astype(np.uint64)
                  @ (np.uint64(1) << np.arange(bit_depth, dtype=np.uint64)))
        return (-mag if int(on[0]) else mag), True

    def _build_value_stack(self, bit_depth: int) -> tuple:
        with self._lock:
            vs = self._value_stack
            if vs is not None and vs[0] == self.generation and vs[1] >= bit_depth:
                return vs
            mat = np.zeros((bit_depth + 1, WORDS_PER_SHARD), dtype=np.uint32)
            ids = [BSI_SIGN_BIT] + list(range(BSI_OFFSET_BIT,
                                              BSI_OFFSET_BIT + bit_depth))
            for i, rid in enumerate(ids):
                hr = self.rows.get(rid)
                if hr is not None and hr.n:
                    mat[i] = hr.to_words()
            vs = self._value_stack = (self.generation, bit_depth, mat)
            return vs

    def import_values(self, column_ids, values, bit_depth: int, clear: bool = False) -> None:
        """Batched BSI write (reference importValue fragment.go:2205),
        vectorized by bit plane: the batch becomes ONE bulk clear + ONE
        bulk set across the exists/sign/magnitude rows instead of
        per-column per-bit writes. Plane batches are assembled as
        (plane-row, local-pos) arrays, lexsorted once, and fed through
        the pre-sorted bulk path. Last write per column wins, like
        sequential writes."""
        cols = np.asarray(column_ids, dtype=np.int64)
        if len(cols) == 0:
            return
        local_all = (cols & (SHARD_WIDTH - 1)).astype(np.uint32)
        if clear:
            o = np.argsort(local_all, kind="stable")
            self.bulk_import_sorted_local(
                np.full(len(cols), BSI_EXISTS_BIT, dtype=np.int64),
                local_all[o], clear=True)
            # A clear un-exists columns — not expressible as a plane
            # point-overwrite, so drop the sketch state wholesale.
            if (getattr(self, "_hll_planes", None)
                    or getattr(self, "_hll_regs", None)):
                from pilosa_tpu.sketch import store as sketch_store
                sketch_store.invalidate(self)
            return
        vals = np.asarray(values, dtype=np.int64)
        # Keep the LAST occurrence of each duplicated column.
        local_u, idx = np.unique(local_all[::-1], return_index=True)
        vals_u = vals[::-1][idx]
        from pilosa_tpu.exec import ingest_transpose
        if ingest_transpose.use_device(len(local_u) * (bit_depth + 2)):
            self._import_values_device(local_u, vals_u, bit_depth)
            return
        neg = vals_u < 0
        mag = np.abs(vals_u).astype(np.uint64)

        set_rows, set_cols = [], []
        clr_rows, clr_cols = [], []

        def _add(bucket_r, bucket_c, row_id, mask):
            n = int(mask.sum())
            if n:
                bucket_r.append(np.full(n, row_id, dtype=np.int64))
                bucket_c.append(local_u[mask])

        all_mask = np.ones(len(local_u), dtype=bool)
        _add(set_rows, set_cols, BSI_EXISTS_BIT, all_mask)
        _add(set_rows, set_cols, BSI_SIGN_BIT, neg)
        _add(clr_rows, clr_cols, BSI_SIGN_BIT, ~neg)
        for i in range(bit_depth):
            on = ((mag >> np.uint64(i)) & np.uint64(1)) == 1
            _add(set_rows, set_cols, BSI_OFFSET_BIT + i, on)
            _add(clr_rows, clr_cols, BSI_OFFSET_BIT + i, ~on)

        def _run(rows_list, cols_list, clear_flag):
            if not rows_list:
                return
            rows = np.concatenate(rows_list)
            local = np.concatenate(cols_list)
            # Plane buckets are emitted row-ascending with sorted
            # positions inside each (local_u is sorted), so the pairs
            # are already (row, pos)-sorted — no lexsort needed.
            self.bulk_import_sorted_local(rows, local, clear=clear_flag)

        with self._lock:  # one atomic overwrite, clears before sets
            gen_before = self.generation
            _run(clr_rows, clr_cols, True)
            _run(set_rows, set_cols, False)
            if (self.generation != gen_before
                    and getattr(self, "_hll_planes", None)):
                from pilosa_tpu.sketch import store as sketch_store
                sketch_store.observe_values(self, local_u.astype(np.int64),
                                            vals_u, gen_before,
                                            self.generation)

    def _import_values_device(self, local_u: np.ndarray, vals_u: np.ndarray,
                              bit_depth: int) -> None:
        """Device half of import_values: one jitted transpose yields the
        full ``[depth+2, W]`` plane image for the deduplicated batch,
        merged here with word ops. Bit-identical to the host plane
        loop: row 0 doubles as the written-column mask, so
        ``(old & ~mask) | new`` is exactly clear-then-set per column
        (exists only ever ORs in — columns are never un-existed)."""
        from pilosa_tpu.exec import ingest_transpose
        planes = ingest_transpose.transpose_planes(local_u, vals_u, bit_depth)
        colmask = planes[0]
        notmask = np.invert(colmask)
        plane_ids = [BSI_EXISTS_BIT, BSI_SIGN_BIT] + list(
            range(BSI_OFFSET_BIT, BSI_OFFSET_BIT + bit_depth))
        with self._lock:
            gen_before = self.generation
            added = removed = 0
            for j, rid in enumerate(plane_ids):
                set_w = planes[j]
                hr = self.rows.get(rid)
                if hr is None or hr.n == 0:
                    a = int(bitops.np_count(set_w))
                    if a == 0:
                        continue
                    # set_w is a view of the shared plane image: siblings
                    # pin the block anyway, so keep it dense in place.
                    self.rows[rid] = HostRow.adopt_words(
                        set_w, a, prefer_dense=True)
                    added += a
                    continue
                old = hr.to_words()
                if rid == BSI_EXISTS_BIT:
                    new = np.bitwise_or(old, set_w)
                else:
                    new = np.bitwise_or(np.bitwise_and(old, notmask), set_w)
                a = int(bitops.np_count(np.bitwise_and(new, np.invert(old))))
                r = int(bitops.np_count(np.bitwise_and(old, np.invert(new))))
                if a == 0 and r == 0:
                    continue
                self.rows[rid] = HostRow.adopt_words(
                    new, hr.n + a - r, prefer_dense=True)
                added += a
                removed += r
            if added or removed:
                self._col_row = None
                self._invalidate()
                if self.op_writer:
                    self._emit_value_wal(local_u, vals_u, bit_depth,
                                         removed, added)
                if getattr(self, "_hll_planes", None):
                    from pilosa_tpu.sketch import store as sketch_store
                    sketch_store.observe_values(self,
                                                local_u.astype(np.int64),
                                                vals_u, gen_before,
                                                self.generation)

    def _emit_value_wal(self, local_u: np.ndarray, vals_u: np.ndarray,
                        bit_depth: int, removed: int, added: int) -> None:
        """Replay the host path's WAL framing for a device-side value
        import: one removeBatch of every (plane, column) whose bit is
        off in the new values, then one addBatch of every on bit — the
        same full request arrays bulk_import_sorted_local logs, gated
        the same way (a record only when its pass changed bits)."""
        neg = vals_u < 0
        mag = np.abs(vals_u).astype(np.uint64)
        set_rows, set_cols = [], []
        clr_rows, clr_cols = [], []

        def _add(bucket_r, bucket_c, row_id, mask):
            n = int(mask.sum())
            if n:
                bucket_r.append(np.full(n, row_id, dtype=np.uint64))
                bucket_c.append(local_u[mask].astype(np.uint64))

        all_mask = np.ones(len(local_u), dtype=bool)
        _add(set_rows, set_cols, BSI_EXISTS_BIT, all_mask)
        _add(set_rows, set_cols, BSI_SIGN_BIT, neg)
        _add(clr_rows, clr_cols, BSI_SIGN_BIT, ~neg)
        for i in range(bit_depth):
            on = ((mag >> np.uint64(i)) & np.uint64(1)) == 1
            _add(set_rows, set_cols, BSI_OFFSET_BIT + i, on)
            _add(clr_rows, clr_cols, BSI_OFFSET_BIT + i, ~on)
        base = np.uint64(self.shard * SHARD_WIDTH)
        if removed and clr_rows:
            with start_span("wal.append"):
                self.op_writer("removeBatch", np.concatenate(clr_rows),
                               np.concatenate(clr_cols) + base)
        if added and set_rows:
            with start_span("wal.append"):
                self.op_writer("addBatch", np.concatenate(set_rows),
                               np.concatenate(set_cols) + base)

    def _filter_seg(self, filter_row: Row | None) -> jax.Array:
        if filter_row is None:
            return jnp.full((WORDS_PER_SHARD,), jnp.uint32(0xFFFFFFFF))
        seg = filter_row.segment(self.shard)
        if seg is None:
            return jnp.zeros((WORDS_PER_SHARD,), jnp.uint32)
        return seg if isinstance(seg, jax.Array) else jnp.asarray(seg)

    def sum(self, filter_row: Row | None, bit_depth: int) -> tuple[int, int]:
        """(sum, count) — reference fragment.sum (fragment.go:1111)."""
        exists, sign, bits = self._bsi_stacks(bit_depth)
        return bsi_ops.host_sum(exists, sign, bits, self._filter_seg(filter_row), bit_depth)

    def min(self, filter_row: Row | None, bit_depth: int) -> tuple[int, int]:
        exists, sign, bits = self._bsi_stacks(bit_depth)
        return bsi_ops.host_min(exists, sign, bits, self._filter_seg(filter_row), bit_depth)

    def max(self, filter_row: Row | None, bit_depth: int) -> tuple[int, int]:
        exists, sign, bits = self._bsi_stacks(bit_depth)
        return bsi_ops.host_max(exists, sign, bits, self._filter_seg(filter_row), bit_depth)

    def range_op(self, op: str, bit_depth: int, predicate: int) -> Row:
        """op in {eq, neq, lt, lte, gt, gte} (reference rangeOp :1274)."""
        exists, sign, bits = self._bsi_stacks(bit_depth)
        if op == "eq":
            seg = bsi_ops.range_eq(exists, sign, bits, predicate, bit_depth)
        elif op == "neq":
            seg = bsi_ops.range_neq(exists, sign, bits, predicate, bit_depth)
        elif op in ("lt", "lte"):
            seg = bsi_ops.range_lt(exists, sign, bits, predicate, bit_depth, op == "lte")
        elif op in ("gt", "gte"):
            seg = bsi_ops.range_gt(exists, sign, bits, predicate, bit_depth, op == "gte")
        else:
            raise ValueError(f"invalid range op {op!r}")
        return Row({self.shard: seg})

    def range_between(self, bit_depth: int, pmin: int, pmax: int) -> Row:
        exists, sign, bits = self._bsi_stacks(bit_depth)
        seg = bsi_ops.range_between(exists, sign, bits, pmin, pmax, bit_depth)
        return Row({self.shard: seg})

    def not_null(self) -> Row:
        return self.row(BSI_EXISTS_BIT)

    # -- TopN / Rows -------------------------------------------------------

    def top(self, n: int = 0, src: Row | None = None,
            row_ids: Iterable[int] | None = None) -> list[tuple[int, int]]:
        """Top rows by count, optionally filtered to rows intersecting src
        or an explicit row-id set. Exact (device intersection counts), not
        cache-approximate like the reference (fragment.go:1570).
        Returns [(row_id, count)] sorted by count desc, id asc."""
        presorted = False
        if row_ids is not None:
            ids = np.asarray(sorted(set(int(r) for r in row_ids)), dtype=np.uint64)
            if len(ids) == 0:
                return []
            if src is not None:
                counts = self.intersection_counts(ids, self._filter_seg(src))
            else:
                counts = np.asarray(
                    [self.rows[int(i)].count() if int(i) in self.rows else 0
                     for i in ids], dtype=np.int64)
        else:
            if src is not None:
                ids = np.asarray(sorted(self.rows), dtype=np.uint64)
                if len(ids) == 0:
                    return []
                counts = self.intersection_counts(ids, self._filter_seg(src))
            else:
                ids, counts = self.top_counts()  # cached sorted order
                if len(ids) == 0:
                    return []
                presorted = True
        if presorted:
            keep = counts > 0
            ids, counts = ids[keep], counts[keep]
            limit = n if n > 0 else len(ids)
            return [(int(r), int(cnt))
                    for r, cnt in zip(ids[:limit].tolist(),
                                      counts[:limit].tolist())]
        order = np.lexsort((ids, -counts))
        pairs = [(int(ids[i]), int(counts[i])) for i in order if counts[i] > 0]
        if n > 0:
            pairs = pairs[:n]
        return pairs

    def rows_list(self, start_row: int = 0, column: int | None = None,
                  limit: int | None = None,
                  among: Iterable[int] | None = None) -> list[int]:
        """Row IDs present, from start_row, optionally only rows with a bit
        in `column` and/or restricted to the `among` set (reference rows +
        rowFilters fragment.go:2618-2724)."""
        allowed = set(among) if among is not None else None
        out = []
        for r in sorted(self.rows):
            if r < start_row or self.rows[r].n == 0:
                continue
            if allowed is not None and r not in allowed:
                continue
            if column is not None and not self.rows[r].contains(self._local(column)):
                continue
            out.append(r)
            if limit is not None and len(out) >= limit:
                break
        return out

    def _filtered_row_counts(self, filter_row: Row | None) -> tuple[list[int], np.ndarray]:
        """(row_ids, counts[∩ filter]) — one batched device call when a
        filter is present, host counters otherwise."""
        ids = self.rows_list()
        if not ids:
            return ids, np.empty(0, dtype=np.int64)
        if filter_row is None:
            return ids, np.asarray([self.rows[r].count() for r in ids],
                                   dtype=np.int64)
        seg = filter_row.segment(self.shard)
        if seg is None:
            return ids, np.zeros(len(ids), dtype=np.int64)
        return ids, self.intersection_counts(ids, seg)

    def min_row(self, filter_row: Row | None = None) -> tuple[int, int]:
        """(min row id with any bit [∩ filter], its count) or (0, 0)
        (reference minRow fragment.go:1232)."""
        ids, counts = self._filtered_row_counts(filter_row)
        for rid, cnt in zip(ids, counts.tolist()):
            if cnt > 0:
                return rid, int(cnt)
        return 0, 0

    def max_row(self, filter_row: Row | None = None) -> tuple[int, int]:
        """(max row id with any bit [∩ filter], its count) or (0, 0)
        (reference maxRow fragment.go:1253)."""
        ids, counts = self._filtered_row_counts(filter_row)
        for rid, cnt in zip(reversed(ids), reversed(counts.tolist())):
            if cnt > 0:
                return rid, int(cnt)
        return 0, 0

    # -- anti-entropy checksums -------------------------------------------

    def checksum_blocks(self, block_rows: int = HASH_BLOCK_SIZE) -> dict[int, bytes]:
        """Block id -> content hash over 100-row blocks (reference
        Blocks/Checksum fragment.go:1762-1841, xxhash over containers).
        Used by the replica-repair sync protocol. Each row is framed as
        (row id, bit count, positions) so distinct row partitions of the
        same positions can't collide."""
        import hashlib
        blocks: dict[int, "hashlib._Hash"] = {}
        for rid, pos in self.rows_snapshot():
            if len(pos) == 0:
                continue
            b = rid // block_rows
            h = blocks.get(b)
            if h is None:
                h = blocks[b] = hashlib.blake2b(digest_size=16)
            h.update(np.uint64(rid).tobytes())
            h.update(np.uint64(len(pos)).tobytes())
            h.update(pos.tobytes())
        return {b: h.digest() for b, h in blocks.items()}

    def block_data(self, block: int, block_rows: int = HASH_BLOCK_SIZE) -> tuple[np.ndarray, np.ndarray]:
        """(row_ids, column_ids) of all bits in a checksum block."""
        rows_out, cols_out = [], []
        base = np.uint64(self.shard * SHARD_WIDTH)
        for rid, pos in self.rows_snapshot():
            if rid // block_rows != block:
                continue
            rows_out.append(np.full(len(pos), rid, dtype=np.uint64))
            cols_out.append(pos + base)
        if not rows_out:
            return np.empty(0, np.uint64), np.empty(0, np.uint64)
        return np.concatenate(rows_out), np.concatenate(cols_out)

    # -- stats -------------------------------------------------------------

    def bit_count(self) -> int:
        return sum(hr.count() for hr in self.rows.values())

    def __repr__(self):
        return (f"Fragment({self.index}/{self.field}/{self.view}/{self.shard} "
                f"rows={len(self.rows)} bits={self.bit_count()})")
