"""Index — a container of fields plus column metadata.

Reference: index.go (struct :37, createField :416, DeleteField :471,
AvailableShards union :292) and holder.go:46 (existence field ``_exists``
backing Not()/existence semantics).
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass
from typing import Iterable

from pilosa_tpu.config import EXISTENCE_FIELD_NAME
from pilosa_tpu.core import shardset
from pilosa_tpu.core.attrs import AttrStore
from pilosa_tpu.core.field import Field, FieldOptions
from pilosa_tpu.core.row import Row
from pilosa_tpu.core.shardset import ShardSet
from pilosa_tpu.core.translate import TranslateStore
from pilosa_tpu.errors import (
    FieldExistsError,
    FieldNotFoundError,
    validate_name,
)


@dataclass
class IndexOptions:
    """Reference IndexOptions (index.go:910)."""

    keys: bool = False
    track_existence: bool = True

    def to_json(self) -> dict:
        return {"keys": self.keys, "trackExistence": self.track_existence}

    @classmethod
    def from_json(cls, d: dict) -> "IndexOptions":
        return cls(keys=d.get("keys", False),
                   track_existence=d.get("trackExistence", True))


class Epoch:
    """Monotonic mutation counter for one index, with per-shard grain.

    Bumped by every fragment/attr mutation anywhere under the index; the
    planner's leaf-stack cache and the executor's result cache validate
    with ONE epoch compare instead of walking per-fragment generations
    (the per-query 954-fragment walk was the r2 flagship bottleneck).

    Shard grain: a bump that knows which shard mutated records that
    shard's position in the global sequence, so a plan touching shards
    S can stamp itself with ``max_shard_epoch(S)`` — writes to shards
    OUTSIDE S advance ``value`` but leave that max unchanged, and the
    plan's cached result survives. A shardless ``bump()`` (schema-ish
    or index-wide mutations: attrs, key translation, field delete,
    remote-origin invalidation without shard detail) raises the floor
    under every shard instead, which also keeps the per-shard dict from
    accumulating state older than the floor.

    Listeners (cluster mode) turn local bumps into index-dirty
    broadcasts so PEER nodes can invalidate their coordinator result
    caches; listeners are called ``fn(shard)`` with the mutated shard or
    ``None`` for index-wide bumps. Remote-triggered bumps pass
    ``notify=False`` to stop the echo from re-broadcasting forever.
    """

    __slots__ = ("_value", "_floor", "_shards", "_lock", "_listeners")

    def __init__(self):
        self._value = 0
        #: every shard's epoch is at least this (index-wide bumps land here).
        self._floor = 0
        #: shard -> sequence position of its last shard-tagged bump.
        self._shards: dict[int, int] = {}
        self._lock = threading.Lock()
        self._listeners: list = []

    def bump(self, notify: bool = True, shard: int | None = None) -> None:
        with self._lock:
            self._value += 1
            if shard is None:
                self._floor = self._value
                self._shards.clear()  # all <= floor now: drop the detail
            else:
                self._shards[shard] = self._value
        if notify:
            for fn in list(self._listeners):
                try:
                    fn(shard)
                except Exception:
                    pass  # observers never break the write path

    def bump_shards(self, shards: Iterable[int], notify: bool = True) -> None:
        """One sequence increment covering a whole shard batch (bulk
        importers: one cache invalidation + one dirty broadcast per
        batch, not one per shard)."""
        shards = [int(s) for s in shards]
        if not shards:
            return
        with self._lock:
            self._value += 1
            v = self._value
            for s in shards:
                self._shards[s] = v
        if notify:
            for fn in list(self._listeners):
                for s in shards:
                    try:
                        fn(s)
                    except Exception:
                        pass

    def subscribe(self, fn) -> None:
        self._listeners.append(fn)

    @property
    def value(self) -> int:
        return self._value

    # -- per-shard reads (result-cache stamps) -----------------------------

    def shard_epoch(self, shard: int) -> int:
        with self._lock:
            return max(self._shards.get(shard, 0), self._floor)

    def max_shard_epoch(self, shards: Iterable[int]) -> int:
        """Stamp for a plan touching ``shards``: strictly increases when
        any of them mutates (its entry moves to the new sequence head),
        holds still when only other shards do."""
        with self._lock:
            m = self._floor
            get = self._shards.get
            for s in shards:
                v = get(s, 0)
                if v > m:
                    m = v
            return m

    def shard_vector(self, shards: Iterable[int]) -> dict[int, int]:
        """Per-shard epochs for the wire (remote legs report theirs so
        the coordinator can stamp cross-node cache entries)."""
        with self._lock:
            floor = self._floor
            get = self._shards.get
            return {int(s): max(get(int(s), 0), floor) for s in shards}


_instance_counter = itertools.count(1)


class Index:
    """Reference Index (index.go:37)."""

    def __init__(self, name: str, options: IndexOptions | None = None,
                 stats=None, fragment_listener=None, op_writer_factory=None):
        validate_name(name)
        self.name = name
        #: process-unique identity: epoch counters restart at 0 when an
        #: index is deleted and recreated under the same name, so caches
        #: keyed (name, epoch) must also key on this nonce or a recreated
        #: index could serve its predecessor's cached results.
        self.instance_id = next(_instance_counter)
        self.options = options or IndexOptions()
        self.stats = stats
        self.fragment_listener = fragment_listener
        self.op_writer_factory = op_writer_factory
        self.epoch = Epoch()
        #: bumped on STRUCTURAL changes (field create/delete, BSI
        #: bit-depth growth) — prepared query plans bake field structure
        #: (e.g. how many bit planes a comparator reads), so they key on
        #: this, separately from the data epoch.
        self.schema_epoch = Epoch()
        #: (epoch stamp, ShardSet) memo for shard_set().
        self._shard_set_memo: tuple | None = None
        self.fields: dict[str, Field] = {}
        self.column_attr_store = AttrStore(epoch=self.epoch)
        self.translate_store = TranslateStore(epoch=self.epoch)
        self._lock = threading.RLock()
        if self.options.track_existence:
            self._create_existence_field()

    # -- fields ------------------------------------------------------------

    def field(self, name: str) -> Field | None:
        return self.fields.get(name)

    def existence_field(self) -> Field | None:
        return self.fields.get(EXISTENCE_FIELD_NAME)

    def public_fields(self) -> list[Field]:
        return [f for n, f in sorted(self.fields.items())
                if n != EXISTENCE_FIELD_NAME]

    def _create_existence_field(self) -> Field:
        f = Field(self.name, EXISTENCE_FIELD_NAME,
                  FieldOptions(cache_type="none", cache_size=0),
                  stats=self.stats, fragment_listener=self.fragment_listener,
                  op_writer_factory=self.op_writer_factory, epoch=self.epoch)
        self.fields[EXISTENCE_FIELD_NAME] = f
        return f

    def create_field(self, name: str, options: FieldOptions | None = None) -> Field:
        with self._lock:
            if name in self.fields:
                raise FieldExistsError()
            f = Field(self.name, name, options, stats=self.stats,
                      fragment_listener=self.fragment_listener,
                      op_writer_factory=self.op_writer_factory,
                      epoch=self.epoch, schema_epoch=self.schema_epoch)
            self.fields[name] = f
            self.schema_epoch.bump()
            return f

    def create_field_if_not_exists(self, name: str,
                                   options: FieldOptions | None = None) -> Field:
        with self._lock:
            return self.fields.get(name) or self.create_field(name, options)

    def delete_field(self, name: str) -> None:
        with self._lock:
            if name not in self.fields:
                raise FieldNotFoundError()
            del self.fields[name]
            self.epoch.bump()
            self.schema_epoch.bump()

    # -- existence ---------------------------------------------------------

    def add_existence(self, column_ids: Iterable[int]) -> None:
        """Mark columns existing (reference executeSet's existence write,
        executor.go:2096)."""
        ef = self.existence_field()
        if ef is None:
            return
        import numpy as np
        cols = np.asarray(column_ids
                          if isinstance(column_ids, np.ndarray)
                          else list(column_ids), dtype=np.uint64)
        ef.import_bits(np.zeros(len(cols), dtype=np.uint64), cols)

    def existence_row(self) -> Row:
        ef = self.existence_field()
        return ef.row(0) if ef is not None else Row()

    # -- shards ------------------------------------------------------------

    def shard_set(self, stats=None) -> ShardSet:
        """Union over fields (reference index.go:292), as the one
        object every query over the whole index carries (core.shardset).
        Memoized on the (data, schema) epoch pair: every query start
        calls this, and for a time field the underlying walk visits
        hundreds of time views. Any write or schema change invalidates
        the memo, but only a change of the contents changes the
        object: a write into a shard that exists re-issues the same
        set, so that what is keyed by it (plans, resident stacks) is
        still found by identity. ``stats`` (the asking executor's)
        counts the set as issued or reused."""
        stamp = (self.epoch.value, self.schema_epoch.value)
        cached = self._shard_set_memo
        if cached is not None and cached[0] == stamp:
            if stats is not None:
                stats.count(shardset.REUSED, 1)
            return cached[1]
        out: set[int] = set()
        for f in self.fields.values():
            out |= f.available_shards()
        made = shardset.reissue(out or {0},
                                None if cached is None else cached[1],
                                stats)
        self._shard_set_memo = (stamp, made)
        return made

    def available_shards(self) -> set[int]:
        """`shard_set` as a set of the caller's own."""
        return set(self.shard_set())

    # -- schema ------------------------------------------------------------

    def info(self) -> dict:
        return {
            "name": self.name,
            "options": self.options.to_json(),
            "fields": [f.info() for f in self.public_fields()],
        }

    def __repr__(self):
        return f"Index({self.name} fields={sorted(self.fields)})"
