"""The PQL executor: recursive call-tree interpreter with per-shard map
functions and a pluggable map/reduce spine.

Reference: executor.go — dispatch (:293-338), bitmap calls (:659-676,
:1441-1786), aggregates (:406-857), TopN two-pass (:857-999), Rows
(:1272-1441), GroupBy (:1069-1272, iterator :3058-3231), writes
(:1823-2330), Options (:360), mapReduce (:2455), key translation
(:2610-2905).

TPU-first departures (same semantics, different math):
- TopN is exact: per-shard batched intersection counts on device
  (`pair_count` over a row stack) instead of the reference's
  threshold-gated rank cache walk.
- GroupBy batches the innermost field's rows into one device call per
  accumulated prefix instead of per-row roaring intersections.
- The shard loop is a seam: `map_reduce` runs shards locally here; the
  cluster layer substitutes node fan-out, and the mesh planner
  (pilosa_tpu.parallel) substitutes stacked shard_map execution.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from concurrent.futures import Future
from dataclasses import dataclass, field as dc_field, replace
from typing import Any, Callable, Iterable

import numpy as np

from pilosa_tpu.cache.tenant import current_tenant
from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.core import shardset, timequantum as tq
from pilosa_tpu.core.field import FIELD_TYPE_BOOL, FIELD_TYPE_INT, FIELD_TYPE_TIME
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import Index
from pilosa_tpu.core.row import Row
from pilosa_tpu.core.shardset import as_shard_set
from pilosa_tpu.core.view import VIEW_STANDARD, view_bsi_name
from pilosa_tpu.errors import (
    BSIGroupNotFoundError,
    FieldNotFoundError,
    IndexNotFoundError,
    QueryError,
)
from pilosa_tpu.exec import fuse as _fuse
from pilosa_tpu.obs import profile as _profile
from pilosa_tpu.obs.tracing import start_span
from pilosa_tpu.ops import bitops
from pilosa_tpu import sketch as _sketch
from pilosa_tpu.sketch import hll as _hll
from pilosa_tpu.sketch import store as sketch_store
from pilosa_tpu.exec.result import (
    FieldRow,
    GroupCount,
    Pair,
    RowIdentifiers,
    ValCount,
    merge_group_counts,
    merge_pairs,
    merge_row_ids,
    sort_pairs,
)
from pilosa_tpu.pql import BETWEEN, NEQ, Call, Condition, Query, parse
from pilosa_tpu.pql import ast as pql_ast
from pilosa_tpu.qos.deadline import check_current as check_deadline

_MAXINT = (1 << 63) - 1

#: reference defaultMinThreshold (executor.go:90).
DEFAULT_MIN_THRESHOLD = 1

_BITMAP_CALLS = frozenset(
    {"Row", "Range", "Difference", "Intersect", "Union", "Xor", "Not", "Shift"})


def _wrap_result(r):
    """Default finisher for execute_async's dispatch paths: a resolved
    scalar becomes the single-call results list."""
    return [r]


@dataclass
class ExecOptions:
    """Reference execOptions (executor.go:62)."""

    remote: bool = False
    exclude_row_attrs: bool = False
    exclude_columns: bool = False
    column_attrs: bool = False
    shards: list[int] | None = None


class Executor:
    """Reference executor (executor.go:72)."""

    #: bounded sizes for the per-executor caches.
    PARSE_CACHE_SIZE = 512
    #: prepared entries hold references to leaf stacks (device arrays),
    #: so the bound stays small and stale entries are dropped eagerly —
    #: HBM budgeting lives in the planner's stack cache, and a prepared
    #: entry must never out-pin an eviction there for long.
    PREPARED_CACHE_SIZE = 32

    def __init__(self, holder: Holder, cluster=None, node_id: str | None = None,
                 planner=None, stats=None, result_cache: bool = True):
        self.holder = holder
        #: cluster hooks (pilosa_tpu.cluster); None = standalone node.
        self.cluster = cluster
        self.node_id = node_id
        #: MeshPlanner (pilosa_tpu.parallel): SPMD fast path for bitmap
        #: trees and Count() — one XLA program over all shards.
        self.planner = planner
        #: cluster key-allocation hook: (index, field|None, keys) -> ids.
        #: None = allocate in the local store (standalone / coordinator).
        self.translator = None
        #: device key planes (exec/keyplane): read-through forward
        #: translation for large key batches; arrays live in the
        #: planner's budgeted stack cache when a planner is attached.
        from pilosa_tpu.exec.keyplane import KeyPlaneCache
        self.keyplanes = KeyPlaneCache(
            planner.stacks if planner is not None else None)
        from pilosa_tpu.obs import NopStats
        self.stats = stats or NopStats()
        if planner is not None:
            # Calls that left the planner's one-dispatch path for the
            # per-shard interpreter, by query class. Published at 0 so
            # that a reader tells "never fell back" from "no such counter".
            for name in ("executor.fallback.topn",
                         "executor.fallback.groupby"):
                self.stats.count(name, 0)
        #: query-string -> parsed Query. Parsed trees are shared across
        #: threads; every consumer clones before mutating
        #: (_translate_call clones; Options copies opt).
        self._parse_cache: "OrderedDict[str, Query]" = OrderedDict()
        #: plan-signature keyed result cache (pilosa_tpu.cache): entries
        #: stamp the (schema epoch, max shard epoch over the plan's
        #: shards, remote shard-epoch rows) they were computed under and
        #: die by stamp mismatch at lookup — writes to shards OUTSIDE a
        #: plan leave its entries alive. The reference's analog is the
        #: per-fragment rowCache (fragment.go:623); caching whole
        #: read-only results is the system answer to a device link whose
        #: per-sync latency dwarfs compute. ``result_cache`` accepts a
        #: shared ResultCache (ServerNode passes its byte-bounded,
        #: tenant-partitioned one), True for a private default, False/0
        #: to disable.
        if result_cache is True:
            from pilosa_tpu.cache import ResultCache
            self.result_cache = ResultCache(stats=self.stats)
        elif not result_cache:
            self.result_cache = None
        else:
            self.result_cache = result_cache
        #: (index, shard) -> (node, epoch) observed from remote legs and
        #: index-dirty broadcasts; the cross-node half of cache stamps.
        from pilosa_tpu.cache import RemoteEpochTable
        self.remote_epochs = RemoteEpochTable()
        self._prepared_lock = threading.Lock()
        #: (index, query text) -> (instance_id, schema_epoch, data epoch,
        #: shards, jitted fn, leaf device arrays, result-cache key): the
        #: prepared-query dispatch path (execute_async). Unlike the
        #: result cache this caches the PROGRAM, not the answer — the
        #: device still runs every query; epochs gate staleness, and the
        #: arrays are shared references into the planner's budgeted
        #: stack cache (no extra HBM pinned).
        self._prepared: "OrderedDict[tuple, tuple]" = OrderedDict()

    def _planner_for(self, c: Call, opt: "ExecOptions"):
        if self.planner is None:
            return None
        return self.planner if self.planner.supports(c) else None

    # ------------------------------------------------------------------
    # entry
    # ------------------------------------------------------------------

    def execute(self, index_name: str, query: Query | str,
                shards: Iterable[int] | None = None,
                opt: ExecOptions | None = None,
                cache: bool = True) -> list[Any]:
        """Reference executor.Execute (executor.go:113).

        ``cache=False`` bypasses the result cache (reads and writes of
        it) for this call — used by benchmarks to measure the cold path.
        """
        raw = query if isinstance(query, str) else None
        if raw is not None:
            with start_span("exec.parse", stats=self.stats):
                query = self._parse_cached(raw)
        opt = opt or ExecOptions()
        if not opt.remote:
            _fuse.reset_fused_steps()
        idx = self.holder.index(index_name)
        if idx is None:
            raise IndexNotFoundError(f"index not found: {index_name!r}")
        needs_shards = any(c.name not in ("Set", "Clear", "SetRowAttrs",
                                          "SetColumnAttrs")
                           for c in query.calls)
        # One object a request (core.shardset): the index's own set as
        # it stands, neither copied nor sorted; a caller's list by
        # content, hashed here and nowhere after.
        if shards is None:
            shards = (idx.shard_set(self.stats) if needs_shards
                      else shardset.EMPTY)
        else:
            shards = as_shard_set(shards, self.stats)

        # Cluster mode: coordinator-side caching is safe because every
        # node broadcasts index-dirty on its local writes (the
        # DirtyBroadcaster bumps peers' per-shard epochs), so remote
        # mutations invalidate this node's entries within the coalesce
        # window + one control message — the same eventual visibility a
        # remote write has without any cache. Remote legs additionally
        # report their exact shard-epoch vectors in-band (belt and
        # braces against a lost broadcast); the TTL backstop bounds the
        # residual window.
        cacheable = (cache and self.result_cache is not None
                     and raw is not None and not query.has_writes())
        if cacheable:
            key = self._cache_key(idx, query, shards, opt)
            tenant = current_tenant()
            # Local epochs read BEFORE execution: if a write lands
            # mid-query the stored stamp is already stale and the entry
            # dies on its first lookup (never serves post-write state as
            # fresh; may conservatively recompute).
            sch = idx.schema_epoch.value
            loc = idx.epoch.max_shard_epoch(shards)
            with start_span("exec.cache", stats=self.stats):
                hit = self.result_cache.get(
                    tenant, key,
                    (sch, loc,
                     self.remote_epochs.rows_for(idx.name, shards)))
            prof = _profile.current()
            if prof is not None:
                prof.cache_hit = hit is not None
            if hit is not None:
                return hit

        # Key translation happens on the coordinator only; forwarded
        # (remote) queries already carry ids and must return raw internal
        # results so the coordinator can merge them (executor.go:113-160).
        results = []
        for call in query.calls:
            # Between plan steps: an expired/cancelled deadline stops
            # the query before it consumes more device time.
            check_deadline()
            if not opt.remote:
                call = self._translate_call(idx, call)  # clones
            else:
                # The parse cache shares trees across queries/threads and
                # some handlers annotate args in place; never hand them
                # the shared copy.
                call = call.clone()
            results.append(self._execute_call(idx, call, shards, opt))
        if not opt.remote:
            results = [self._translate_result(idx, c, r)
                       for c, r in zip(query.calls, results)]
        if cacheable:
            # Remote rows re-read AFTER the legs: each leg reported the
            # vector it read on its node BEFORE executing (observed into
            # remote_epochs during this query), so the stored remote
            # stamp is exactly as conservative as the pre-exec local one
            # — and the first cold query already stamps consistently
            # instead of dying once on the next lookup.
            self.result_cache.put(
                tenant, key,
                (sch, loc, self.remote_epochs.rows_for(idx.name, shards)),
                results)
        return results

    def _cache_key(self, idx: Index, query: Query, shards: list[int],
                   opt: ExecOptions) -> tuple:
        from pilosa_tpu.cache.signature import cache_key
        return cache_key(idx, query, shards, opt)

    def _exec_stamp(self, idx: Index, shards: list[int]) -> tuple:
        """Pre-dispatch freshness stamp for the prepared/async paths."""
        return (idx.schema_epoch.value, idx.epoch.max_shard_epoch(shards),
                self.remote_epochs.rows_for(idx.name, shards))

    def execute_async(self, index_name: str, query: Query | str,
                      shards: Iterable[int] | None = None,
                      opt: ExecOptions | None = None,
                      cache: bool = True) -> "Future[list[Any]]":
        """Non-blocking submission; resolves to ``execute(...)``'s list.

        Single plannable ``Count(...)`` queries on a standalone node
        dispatch their device program immediately and resolve when their
        TransferBatcher wave lands — so ONE submitting thread can keep
        hundreds of queries in flight over the device link. Anything else
        (writes, cluster fan-out, host-side calls) executes synchronously
        before the future resolves, which keeps the API uniform.
        """
        fut: Future = Future()
        opt = opt or ExecOptions()
        if not opt.remote:
            _fuse.reset_fused_steps()
        raw = query if isinstance(query, str) else None
        if shards is not None and not isinstance(shards, list):
            shards = list(shards)  # one materialization; never consume
            # a caller's iterator twice across validate + execute.
        fast = None
        if (self.cluster is None and self.planner is not None
                and not opt.remote and raw is not None):
            # Prepared-query fast path: a repeated (index, text) pair
            # whose epochs stand still re-dispatches its cached device
            # program directly — no parse, clone, translate, plan-key
            # hash, or leaf fetch per query (the reference's per-query
            # host cost lives in executor.go:2561-2608; here the whole
            # prepared path is a dict hit plus the jax dispatch).
            e = self._prepared.get((index_name, raw))
            if e is not None:
                idx = self.holder.index(index_name)
                stale = (idx is None or e[0] != idx.instance_id
                         or e[1] != idx.schema_epoch.value
                         or e[2] != idx.epoch.value)
                if stale:
                    # Drop device-array references the moment an entry
                    # goes stale (don't wait for LRU churn).
                    with self._prepared_lock:
                        if self._prepared.get((index_name, raw)) is e:
                            del self._prepared[(index_name, raw)]
                    e = None
                if (e is not None
                        and ((shards is None and e[8])
                             or (shards is not None and shards == e[3]))):
                    (_, _, epoch, pshards, fn, arrays, rkey, post, _,
                     steps) = e
                    with self._prepared_lock:
                        if (index_name, raw) in self._prepared:
                            self._prepared.move_to_end((index_name, raw))
                    cacheable = cache and self.result_cache is not None
                    if cacheable:
                        # Stamp + tenant captured NOW: the store runs on
                        # the batcher thread, which has neither this
                        # request's contextvars nor pre-dispatch epochs.
                        stamp = self._exec_stamp(idx, pshards)
                        tenant = current_tenant()
                        hit = self.result_cache.get(tenant, rkey, stamp)
                        if hit is not None:
                            fut.set_result(hit)
                            return fut
                    try:
                        if cacheable:
                            # Store via the batcher callback; closure
                            # only on the cacheable path.
                            def post(host, _k=rkey, _s=stamp,  # noqa: E731
                                     _t=tenant, _p=post):
                                results = _p(host)
                                self.result_cache.put(_t, _k, _s, results)
                                return results
                        _fuse.add_fused_steps(steps)
                        # Return the dispatch future DIRECTLY: a second
                        # Future + callback chain costs more than the
                        # whole remaining fast path on a slow host. The
                        # coalescer is the launch choke point — repeated
                        # prepared queries are exactly the same-plan
                        # waves it batches.
                        return self.planner.dispatch_count(fn, arrays,
                                                           post)
                    except Exception as exc:
                        fut.set_exception(exc)
                        return fut
        if (self.cluster is None and self.planner is not None
                and not opt.remote):
            q = self._parse_cached(raw) if raw is not None else query
            if (len(q.calls) == 1 and q.calls[0].name == "Count"
                    and len(q.calls[0].children) == 1):
                idx = self.holder.index(index_name)
                if idx is not None and self.planner.supports(
                        q.calls[0].children[0]):
                    fast = (q, idx)
            elif (len(q.calls) == 1
                  and q.calls[0].name in ("Sum", "Min", "Max")):
                # BSI aggregates dispatch async too: device program
                # enqueued now, base fold applied when the batcher wave
                # lands — same shape as the Count path below.
                idx = self.holder.index(index_name)
                if idx is not None and self.planner.supports_aggregate(
                        idx, q.calls[0]):
                    fast = (q, idx)
        if fast is None:
            try:
                fut.set_result(self.execute(index_name, query, shards, opt,
                                            cache=cache))
            except Exception as e:
                fut.set_exception(e)
            return fut

        q, idx = fast
        try:
            shards_obj = shards
            shards = (sorted(idx.available_shards()) if shards is None
                      else list(shards))
            epoch = idx.epoch.value
            key = self._cache_key(idx, q, shards, opt) \
                if raw is not None else None
            cacheable = (cache and self.result_cache is not None
                         and raw is not None)
            stamp = self._exec_stamp(idx, shards) if cacheable else None
            tenant = current_tenant()
            if cacheable:
                hit = self.result_cache.get(tenant, key, stamp)
                if hit is not None:
                    fut.set_result(hit)
                    return fut
            call = self._translate_call(idx, q.calls[0])
            finish = _wrap_result  # Count: resolve to [int]
            if call.name in ("Sum", "Min", "Max"):
                field_name, _ = call.string_arg("field")
                base = idx.field(field_name).bsi_group.base
                name = call.name

                def finish(pair, _b=base, _n=name):  # noqa: F811
                    total, cnt = pair
                    if cnt == 0:
                        return [ValCount()]
                    if _n == "Sum":
                        return [ValCount(total + cnt * _b, cnt)]
                    return [ValCount(total + _b, cnt)]

                if name == "Sum":
                    inner = self.planner.dispatch_sum(idx, call, shards)
                else:
                    inner = self.planner.dispatch_min_max(
                        idx, call, shards, name == "Min")
            elif shards:
                fn, arrays = self.planner.prepare_count(
                    idx, call.children[0], shards)
                steps = _fuse.call_steps(call.children[0]) + 1
                if raw is not None:
                    sum_host = self.planner._sum_host
                    with self._prepared_lock:
                        # `shards` is OUR copy — never the caller's
                        # mutable list, which could change under an
                        # identity check. Final flag: prepared from
                        # shards=None (the full available set at this
                        # epoch) — only such entries may serve later
                        # shards=None callers; a subset program must
                        # never answer a full query.
                        self._prepared[(index_name, raw)] = (
                            idx.instance_id, idx.schema_epoch.value,
                            epoch, shards, fn, arrays, key,
                            lambda host, _s=sum_host: [_s(host)],
                            shards_obj is None, steps)
                        while len(self._prepared) > self.PREPARED_CACHE_SIZE:
                            self._prepared.popitem(last=False)
                _fuse.add_fused_steps(steps)
                inner = self.planner.dispatch_count(fn, arrays)
            else:
                inner = self.planner.execute_count_async(
                    idx, call.children[0], shards)
        except Exception as e:
            fut.set_exception(e)
            return fut

        def _done(f):
            try:
                results = finish(f.result())
            except Exception as e:
                fut.set_exception(e)
                return
            if cacheable:
                # stamp/tenant captured pre-dispatch (batcher thread).
                self.result_cache.put(tenant, key, stamp, results)
            fut.set_result(results)

        inner.add_done_callback(_done)
        return fut

    def _parse_cached(self, raw: str) -> Query:
        with self._prepared_lock:
            q = self._parse_cache.get(raw)
            if q is not None:
                self._parse_cache.move_to_end(raw)
                return q
        q = parse(raw)
        with self._prepared_lock:
            self._parse_cache[raw] = q
            while len(self._parse_cache) > self.PARSE_CACHE_SIZE:
                self._parse_cache.popitem(last=False)
        return q

    # ------------------------------------------------------------------
    # dispatch (reference executor.go:293-338)
    # ------------------------------------------------------------------

    def _execute_call(self, idx: Index, c: Call, shards: list[int],
                      opt: ExecOptions) -> Any:
        name = c.name
        # Per-call stats, tagged by index (reference CountWithCustomTags,
        # executor.go:295 etc.).
        self.stats.with_tags(f"index:{idx.name}").count(name)
        with start_span(f"Executor.execute{name}", stats=self.stats) as span:
            before = _fuse.fused_steps()
            try:
                return self._execute_call_inner(idx, c, shards, opt)
            finally:
                # Plan-tree steps this call ran fused into device
                # programs — the observable difference between a query
                # that ran as ONE program and one that stepped.
                span.set_tag("exec.fusedSteps", _fuse.fused_steps() - before)

    def _execute_call_inner(self, idx: Index, c: Call, shards: list[int],
                            opt: ExecOptions) -> Any:
        name = c.name
        if name == "Sum":
            return self._execute_sum(idx, c, shards, opt)
        if name == "Min":
            return self._execute_min_max(idx, c, shards, opt, is_min=True)
        if name == "Max":
            return self._execute_min_max(idx, c, shards, opt, is_min=False)
        if name == "MinRow":
            return self._execute_min_max_row(idx, c, shards, opt, is_min=True)
        if name == "MaxRow":
            return self._execute_min_max_row(idx, c, shards, opt, is_min=False)
        if name == "Clear":
            return self._execute_clear_bit(idx, c, opt)
        if name == "ClearRow":
            return self._execute_clear_row(idx, c, shards, opt)
        if name == "Store":
            return self._execute_store(idx, c, shards, opt)
        if name == "Count":
            return self._execute_count(idx, c, shards, opt)
        if name == "Set":
            return self._execute_set(idx, c, opt)
        if name == "SetRowAttrs":
            self._execute_set_row_attrs(idx, c, opt)
            return None
        if name == "SetColumnAttrs":
            self._execute_set_column_attrs(idx, c, opt)
            return None
        if name == "TopN":
            return self._execute_top_n(idx, c, shards, opt)
        if name == "Rows":
            return self._execute_rows(idx, c, shards, opt)
        if name == "GroupBy":
            return self._execute_group_by(idx, c, shards, opt)
        if name == "Options":
            return self._execute_options(idx, c, shards, opt)
        if name == "Distinct":
            # Bare Distinct() has no client-facing result shape — it is
            # the map half of Count(Distinct(...)), which intercepts it
            # in _execute_count. Remotes DO execute it bare (the
            # coordinator ships the inner call) and return partials.
            if not opt.remote:
                raise QueryError("Distinct() must be wrapped in Count()")
            return self._execute_distinct(idx, c, shards, opt)
        if name == "SimilarTopN":
            return self._execute_similar_top_n(idx, c, shards, opt)
        if name in _BITMAP_CALLS:
            return self._execute_bitmap_call(idx, c, shards, opt)
        raise QueryError(f"unknown call: {name}")

    # ------------------------------------------------------------------
    # map/reduce spine (reference mapReduce executor.go:2455)
    # ------------------------------------------------------------------

    def map_reduce(self, idx: Index, shards: list[int], c: Call,
                   opt: ExecOptions, map_fn: Callable[[int], Any],
                   reduce_fn: Callable[[Any, Any], Any],
                   local_batch_fn: Callable[[list[int]], Any] | None = None) -> Any:
        """Single-node spine: apply map_fn per shard, fold with reduce_fn.
        The cluster layer overrides shard→node grouping + remote exec;
        ``local_batch_fn`` (the mesh planner) takes whole local shard
        batches as one SPMD program."""
        if self.cluster is not None and not opt.remote:
            return self.cluster.map_reduce(self, idx, shards, c, opt,
                                           map_fn, reduce_fn,
                                           local_batch_fn=local_batch_fn)
        # Refuse to serve shards whose local data is quarantined
        # (storage corruption). Standalone this is terminal; as a
        # remote leg it makes the COORDINATOR fail this node over to a
        # replica, exactly like a connection failure.
        q = getattr(self.holder, "quarantine", None)
        if q is not None and len(q):
            blocked = q.blocked_shards(idx.name)
            if blocked and any(s in blocked for s in shards):
                from pilosa_tpu.storage.quarantine import ShardCorruptError
                raise ShardCorruptError()
        if local_batch_fn is not None:
            check_deadline()
            return local_batch_fn(as_shard_set(shards, self.stats))
        acc = None
        for shard in shards:
            # Per-shard cancellation point: an expired deadline stops
            # the scan instead of finishing the remaining shards.
            check_deadline()
            acc = reduce_fn(acc, map_fn(shard))
        return acc

    # ------------------------------------------------------------------
    # bitmap calls
    # ------------------------------------------------------------------

    def _execute_bitmap_call(self, idx: Index, c: Call, shards: list[int],
                             opt: ExecOptions) -> Row:
        planner = self._planner_for(c, opt)

        def map_fn(shard):
            return self._bitmap_call_shard(idx, c, shard)

        def reduce_fn(prev, v):
            if prev is None:
                return v
            return prev.union(v)  # segments are disjoint by shard

        # The cluster layer defers row legs and folds them device-side
        # in one batched program (exec/device_reduce.py) when it sees
        # this tag; untagged reduces keep the pairwise fold.
        reduce_fn.reduce_kind = "row_union"

        if planner is not None:
            local_batch = lambda shs: planner.execute_bitmap(idx, c, shs)
        else:
            fusion = self._fuse_partial(c)
            if fusion is not None:
                fused_call, const_calls = fusion
                local_batch = (lambda shs: self.planner.execute_bitmap(
                    idx, fused_call, shs,
                    const_rows=self._const_rows(idx, const_calls, shs)))
            else:
                local_batch = None
        row = self.map_reduce(idx, shards, c, opt, map_fn, reduce_fn,
                              local_batch_fn=local_batch) or Row()

        # Attach row attributes for plain Row() (executor.go:604-639).
        if c.name == "Row" and not c.has_condition_arg():
            if opt.exclude_row_attrs:
                row.attrs = {}
            else:
                try:
                    field_name = c.field_arg()
                    f = idx.field(field_name)
                    row_id, ok = c.uint_arg(field_name)
                    if f is not None and ok:
                        row.attrs = f.row_attr_store.attrs(row_id)
                except ValueError:
                    pass
        if opt.exclude_columns:
            row.segments = {}
        return row

    def _fuse_partial(self, c: Call):
        """Maximal-subtree fusion for MIXED trees: when the planner
        rejects the whole bitmap tree, rewrite it so every maximal
        plannable subtree still runs on device and each unplannable
        subtree becomes a ``__const__`` leaf (a host-computed Row
        uploaded as a device stack). Returns (fused_call, const_calls)
        or None when partial fusion doesn't apply — the planner handles
        the whole tree, fusion is off, or no plannable subtree remains
        worth lowering."""
        planner = self.planner
        if (planner is None or not _fuse.enabled()
                or not getattr(planner, "fuse_const_supported", False)):
            return None
        if planner.supports(c):
            return None  # whole-tree path already covers it
        consts: list[Call] = []
        kept = [False]

        def rewrite(node: Call) -> Call:
            if planner.supports(node):
                kept[0] = True
                return node
            # Only n-ary set ops descend: Not/Shift carry structural
            # requirements (existence field, shift bounds) the planner
            # validated as part of supports(); an unplannable child
            # makes the whole unary subtree a const leaf.
            if (node.name in ("Intersect", "Union", "Xor", "Difference")
                    and node.children):
                return Call(node.name, args=dict(node.args),
                            children=[rewrite(ch) for ch in node.children])
            consts.append(node)
            return Call("__const__", args={"slot": len(consts) - 1})

        fused = rewrite(c)
        if not kept[0] or not consts:
            return None
        return fused, consts

    def _const_rows(self, idx: Index, const_calls: list[Call],
                    shards: list[int]) -> list[Row]:
        """Evaluate each replaced subtree host-side over ``shards`` —
        the same per-shard interpreter the full fallback would have run,
        but only for the unplannable fraction of the tree."""
        rows = []
        for cc in const_calls:
            segs: dict[int, Any] = {}
            for shard in shards:
                r = self._bitmap_call_shard(idx, cc, shard)
                segs.update(r.segments)
            rows.append(Row(segs))
        return rows

    def _bitmap_call_shard(self, idx: Index, c: Call, shard: int) -> Row:
        """Reference executeBitmapCallShard (executor.go:659)."""
        name = c.name
        if name in ("Row", "Range"):
            return self._row_shard(idx, c, shard)
        if name == "Difference":
            return self._nary_shard(idx, c, shard, "difference")
        if name == "Intersect":
            return self._nary_shard(idx, c, shard, "intersect")
        if name == "Union":
            return self._nary_shard(idx, c, shard, "union")
        if name == "Xor":
            return self._nary_shard(idx, c, shard, "xor")
        if name == "Not":
            return self._not_shard(idx, c, shard)
        if name == "Shift":
            return self._shift_shard(idx, c, shard)
        raise QueryError(f"unknown call: {name}")

    def _nary_shard(self, idx: Index, c: Call, shard: int, op: str) -> Row:
        if not c.children:
            raise QueryError(f"empty {c.name} query is currently not supported")
        rows = [self._bitmap_call_shard(idx, ch, shard) for ch in c.children]
        acc = rows[0]
        for r in rows[1:]:
            acc = getattr(acc, op)(r)
        return acc

    def _not_shard(self, idx: Index, c: Call, shard: int) -> Row:
        if len(c.children) != 1:
            raise QueryError("Not() requires a single row input")
        if idx.existence_field() is None:
            raise QueryError(
                f"index does not support existence tracking: {idx.name}")
        frag = self.holder.fragment(idx.name, idx.existence_field().name,
                                    VIEW_STANDARD, shard)
        existence = frag.row(0) if frag else Row()
        row = self._bitmap_call_shard(idx, c.children[0], shard)
        return existence.difference(row)

    def _shift_shard(self, idx: Index, c: Call, shard: int) -> Row:
        n, _ = c.int_arg("n")
        if len(c.children) != 1:
            raise QueryError("Shift() requires a single row input")
        row = self._bitmap_call_shard(idx, c.children[0], shard)
        return row.shift(n)

    def _row_shard(self, idx: Index, c: Call, shard: int) -> Row:
        """Reference executeRowShard (executor.go:1441)."""
        if c.has_condition_arg():
            return self._row_bsi_shard(idx, c, shard)

        field_name = c.field_arg()
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        row_val = c.args.get(field_name)
        if isinstance(row_val, bool):  # bool field sugar: f=true / f=false
            row_id = 1 if row_val else 0
        else:
            row_id, ok = c.uint_arg(field_name)
            if not ok:
                raise QueryError("Row() must specify row")

        from_time = to_time = None
        if "from" in c.args:
            from_time = tq.parse_time(c.args["from"])
        if "to" in c.args:
            to_time = tq.parse_time(c.args["to"])

        if c.name == "Row" and from_time is None and to_time is None:
            frag = self.holder.fragment(idx.name, field_name, VIEW_STANDARD, shard)
            return frag.row(row_id) if frag else Row()

        q = f.time_quantum()
        if not q:
            return Row()
        if to_time is None:
            import datetime as dt
            to_time = dt.datetime.now() + dt.timedelta(days=1)
        if from_time is None:
            import datetime as dt
            from_time = dt.datetime.min.replace(year=1)
        out = Row()
        for view_name in tq.views_by_time_range(VIEW_STANDARD, from_time,
                                                to_time, q):
            frag = self.holder.fragment(idx.name, field_name, view_name, shard)
            if frag is not None:
                out = out.union(frag.row(row_id))
        return out

    def _row_bsi_shard(self, idx: Index, c: Call, shard: int) -> Row:
        """Reference executeRowBSIGroupShard (executor.go:1536)."""
        if len(c.args) == 0:
            raise QueryError("Row(): condition required")
        if len(c.args) > 1:
            raise QueryError("Row(): too many arguments")
        (field_name, cond), = c.args.items()
        if not isinstance(cond, Condition):
            raise QueryError(f"Row(): expected condition argument")
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        bsig = f.bsi_group
        if bsig is None:
            raise BSIGroupNotFoundError()
        frag = self.holder.fragment(idx.name, field_name,
                                    view_bsi_name(field_name), shard)

        # `!= null` → not-null.
        if cond.op == NEQ and cond.value is None:
            return frag.not_null() if frag else Row()

        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            if len(predicates) != 2:
                raise QueryError(
                    "Row(): BETWEEN condition requires exactly two integer values")
            lo, hi, out_of_range = bsig.base_value_between(*predicates)
            if out_of_range or frag is None:
                return Row()
            if predicates[0] <= bsig.min and predicates[1] >= bsig.max:
                return frag.not_null()
            return frag.range_between(bsig.bit_depth, lo, hi)

        if not isinstance(cond.value, int) or isinstance(cond.value, bool):
            raise QueryError("Row(): conditions only support integer values")
        value = cond.value
        base_value, out_of_range = bsig.base_value(cond.op, value)
        if out_of_range and cond.op != NEQ:
            return Row()
        if frag is None:
            return Row()
        # Fully-encompassing LT/GT → all not-null (executor.go:1648-1652).
        if ((cond.op == pql_ast.LT and value > bsig.max)
                or (cond.op == pql_ast.LTE and value >= bsig.max)
                or (cond.op == pql_ast.GT and value < bsig.min)
                or (cond.op == pql_ast.GTE and value <= bsig.min)):
            return frag.not_null()
        if out_of_range and cond.op == NEQ:
            return frag.not_null()
        from pilosa_tpu.core.field import _op_name
        return frag.range_op(_op_name(cond.op), bsig.bit_depth, base_value)

    # ------------------------------------------------------------------
    # aggregates
    # ------------------------------------------------------------------

    def _agg_filter(self, idx: Index, c: Call, shard: int) -> Row | None:
        if len(c.children) > 1:
            raise QueryError(f"{c.name}() only accepts a single bitmap input")
        if len(c.children) == 1:
            return self._bitmap_call_shard(idx, c.children[0], shard)
        return None

    def _bsi_fragment(self, idx: Index, field_name: str, shard: int):
        f = idx.field(field_name)
        if f is None or f.bsi_group is None:
            return None, None
        frag = self.holder.fragment(idx.name, field_name,
                                    view_bsi_name(field_name), shard)
        return f, frag

    def _execute_sum(self, idx: Index, c: Call, shards, opt) -> ValCount:
        field_name, ok = c.string_arg("field")
        if not ok:
            raise QueryError("Sum(): field required")

        def map_fn(shard):
            f, frag = self._bsi_fragment(idx, field_name, shard)
            if frag is None:
                return ValCount()
            filt = self._agg_filter(idx, c, shard)
            s, cnt = frag.sum(filt, f.bsi_group.bit_depth)
            return ValCount(s + cnt * f.bsi_group.base, cnt)

        local_batch = None
        if self.planner is not None and self.planner.supports_aggregate(idx, c):
            f = idx.field(field_name)

            def local_batch(shs):
                s, cnt = self.planner.execute_sum(idx, c, list(shs))
                return ValCount(s + cnt * f.bsi_group.base, cnt)

        result = self.map_reduce(idx, shards, c, opt, map_fn,
                                 lambda p, v: v if p is None else p.add(v),
                                 local_batch_fn=local_batch)
        result = result or ValCount()
        return ValCount() if result.count == 0 else result

    def _execute_min_max(self, idx: Index, c: Call, shards, opt,
                         is_min: bool) -> ValCount:
        field_name, ok = c.string_arg("field")
        if not ok:
            raise QueryError(f"{c.name}(): field required")

        def map_fn(shard):
            f, frag = self._bsi_fragment(idx, field_name, shard)
            if frag is None:
                return ValCount()
            filt = self._agg_filter(idx, c, shard)
            if is_min:
                v, cnt = frag.min(filt, f.bsi_group.bit_depth)
            else:
                v, cnt = frag.max(filt, f.bsi_group.bit_depth)
            if cnt == 0:
                return ValCount()
            return ValCount(v + f.bsi_group.base, cnt)

        def reduce_fn(p, v):
            if p is None:
                return v
            return p.smaller(v) if is_min else p.larger(v)

        local_batch = None
        if self.planner is not None and self.planner.supports_aggregate(idx, c):
            f = idx.field(field_name)

            def local_batch(shs):
                v, cnt = self.planner.execute_min_max(idx, c, list(shs),
                                                      is_min)
                if cnt == 0:
                    return ValCount()
                return ValCount(v + f.bsi_group.base, cnt)

        result = self.map_reduce(idx, shards, c, opt, map_fn, reduce_fn,
                                 local_batch_fn=local_batch) or ValCount()
        return ValCount() if result.count == 0 else result

    def _execute_min_max_row(self, idx: Index, c: Call, shards, opt,
                             is_min: bool) -> Pair:
        field_name, ok = c.string_arg("field")
        if not ok:
            raise QueryError(f"{c.name}(): field required")

        def map_fn(shard):
            f = idx.field(field_name)
            if f is None:
                return Pair()
            frag = self.holder.fragment(idx.name, field_name, VIEW_STANDARD, shard)
            if frag is None:
                return Pair()
            filt = self._agg_filter(idx, c, shard)
            rid, cnt = frag.min_row(filt) if is_min else frag.max_row(filt)
            return Pair(id=rid, count=cnt)

        def reduce_fn(p, v):
            if p is None or p.count == 0:
                return v
            if v.count == 0:
                return p
            if (v.id < p.id) == is_min and v.id != p.id:
                return v
            return p

        return self.map_reduce(idx, shards, c, opt, map_fn, reduce_fn) or Pair()

    def _execute_count(self, idx: Index, c: Call, shards, opt) -> int:
        if len(c.children) != 1:
            raise QueryError("Count() requires a single bitmap input")
        if c.children[0].name == "Distinct":
            return self._execute_distinct(idx, c.children[0], shards, opt)

        planner = self._planner_for(c.children[0], opt)

        def map_fn(shard):
            return self._bitmap_call_shard(idx, c.children[0], shard).count()

        if planner is not None:
            local_batch = (lambda shs:
                           planner.execute_count(idx, c.children[0], shs))
        else:
            fusion = self._fuse_partial(c.children[0])
            if fusion is not None:
                fused_call, const_calls = fusion
                local_batch = (lambda shs: self.planner.execute_count(
                    idx, fused_call, shs,
                    const_rows=self._const_rows(idx, const_calls, shs)))
            else:
                local_batch = None
        return self.map_reduce(idx, shards, c, opt, map_fn,
                               lambda p, v: (p or 0) + v,
                               local_batch_fn=local_batch) or 0

    # ------------------------------------------------------------------
    # approximate analytics (pilosa_tpu/sketch)
    # ------------------------------------------------------------------

    @staticmethod
    def _row_words_for(filt: Row | None, shard: int) -> np.ndarray | None:
        """A filter Row's [W] uint32 word plane for one shard. None for
        "no filter" (distinct from a filter that matched nothing, which
        is an all-zero plane)."""
        if filt is None:
            return None
        seg = filt.segments.get(shard)
        if seg is None:
            return np.zeros(WORDS_PER_SHARD, dtype=np.uint32)
        return np.asarray(seg, dtype=np.uint32)

    def _execute_distinct(self, idx: Index, c: Call, shards, opt) -> Any:
        """Count(Distinct(filter?, field=f)): HLL estimate over the
        field's register planes, fused to one device dispatch per node
        by the planner, with an EXACT per-shard-unique fallback when
        the estimate lands under the threshold (where relative HLL
        error is most visible and exact is cheapest).

        The coordinator pins the resolved precision/threshold into the
        shipped call so every node sketches at the same precision, and
        remotes (opt.remote) return the raw partial — HLLSketch on the
        sketch leg, DistinctValues on the exact leg — which rides the
        cluster aggregate wire and folds as register-max / set-union.
        """
        field_name, ok = c.string_arg("field")
        if not ok:
            raise QueryError("Distinct(): field required")
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        if f.bsi_group is None:
            raise QueryError(
                f"Distinct(): field {field_name!r} has no BSI data "
                "(int field required)")
        if len(c.children) > 1:
            raise QueryError("Distinct() only accepts a single bitmap input")
        depth = f.bsi_group.bit_depth

        p, has_p = c.uint_arg("precision")
        p = _sketch.validate_precision(p) if has_p else _sketch.precision()
        thr, has_thr = c.uint_arg("threshold")
        if not has_thr:
            thr = _sketch.exact_threshold()

        cc = c.clone()
        cc.args["precision"] = int(p)
        cc.args["threshold"] = int(thr)

        if cc.args.get("exact"):
            part = self._distinct_exact(idx, cc, shards, opt, f, depth)
            return part if opt.remote else int(len(part.values))

        def map_fn(shard):
            _, frag = self._bsi_fragment(idx, field_name, shard)
            if frag is None:
                return _hll.HLLSketch.empty(p)
            filt = self._agg_filter(idx, cc, shard)
            fw = self._row_words_for(filt, shard)
            return sketch_store.shard_sketch(frag, depth, p, fw)

        def reduce_fn(prev, v):
            return v if prev is None else prev.merge(v)

        # The cluster layer defers sketch legs and folds them in one
        # stacked register-max when it sees this tag (mirror of the
        # "row_union" deferred fold in _execute_bitmap_call).
        reduce_fn.reduce_kind = "register_max"

        local_batch = None
        if (self.planner is not None
                and getattr(self.planner, "sketch_supported", False)
                and self.planner.supports_distinct(idx, cc)):
            def local_batch(shs):
                regs = self.planner.execute_distinct_registers(
                    idx, cc, list(shs), p)
                return _hll.HLLSketch(p=p, regs=regs)

        sk = self.map_reduce(idx, shards, cc, opt, map_fn, reduce_fn,
                             local_batch_fn=local_batch)
        sk = sk or _hll.HLLSketch.empty(p)
        if opt.remote:
            return sk
        est = sk.estimate()
        if thr and est < thr:
            ec = cc.clone()
            ec.args["exact"] = True
            part = self._distinct_exact(idx, ec, shards, opt, f, depth)
            return int(len(part.values))
        return int(round(est))

    def _distinct_exact(self, idx: Index, c: Call, shards, opt, f,
                        depth: int) -> "_hll.DistinctValues":
        """Exact leg: per-shard sorted unique values, host union fold.
        Runs through map_reduce so remote nodes produce DistinctValues
        partials over their own shards."""
        base = np.int64(f.bsi_group.base)

        def map_fn(shard):
            _, frag = self._bsi_fragment(idx, f.name, shard)
            if frag is None:
                return _hll.DistinctValues.empty()
            filt = self._agg_filter(idx, c, shard)
            fw = self._row_words_for(filt, shard)
            vals = sketch_store.shard_distinct(frag, depth, fw)
            return _hll.DistinctValues(values=vals + base)

        def reduce_fn(prev, v):
            return v if prev is None else prev.merge(v)

        part = self.map_reduce(idx, shards, c, opt, map_fn, reduce_fn)
        return part or _hll.DistinctValues.empty()

    def _execute_similar_top_n(self, idx: Index, c: Call, shards,
                               opt) -> Any:
        """SimilarTopN(f, Row(...), n=, metric=): Jaccard/overlap of
        the filter row against EVERY row of the field, one fused device
        dispatch per node (row cube ∧ filter popcounts + device top-k).
        Returns the TopN pair shape: Pair(id=row, count=overlap),
        best-score-first."""
        field_name = c.args.get("_field")
        if not field_name:
            raise QueryError("SimilarTopN(): field required")
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        if f.field_type == FIELD_TYPE_INT:
            raise QueryError("SimilarTopN(): set field required")
        if len(c.children) != 1:
            raise QueryError("SimilarTopN() requires a single bitmap input")
        n, has_n = c.uint_arg("n")
        if not has_n or not n:
            n = _sketch.DEFAULT_SIMILAR_N
        metric, has_m = c.string_arg("metric")
        if not has_m:
            metric = "jaccard"
        if metric not in ("jaccard", "overlap"):
            raise QueryError(f"SimilarTopN(): unknown metric {metric!r}")

        cc = c.clone()
        cc.args["n"] = int(n)
        cc.args["metric"] = metric
        filter_call = cc.children[0]

        def map_fn(shard):
            return self._similar_shard(idx, field_name, filter_call, shard)

        def reduce_fn(prev, v):
            return v if prev is None else prev.merge(v)

        local_batch = None
        if (self.planner is not None
                and getattr(self.planner, "sketch_supported", False)
                and self.planner.supports_similar(idx, field_name,
                                                  filter_call)):
            def local_batch(shs):
                shs = list(shs)
                row_ids = self._field_row_ids(idx, field_name, shs)
                res = self.planner.execute_similar(
                    idx, field_name, filter_call, row_ids, shs)
                if res is None:
                    # Cube over the HBM gate — host per-shard fold.
                    acc = None
                    for shard in shs:
                        acc = reduce_fn(acc, map_fn(shard))
                    return acc or _hll.SimPartial.empty()
                ids, inter, selfc, filtc, order = res
                return _hll.SimPartial(ids=ids, overlap=inter,
                                       selfcnt=selfc, filtcnt=filtc,
                                       order=order)

        part = self.map_reduce(idx, shards, cc, opt, map_fn, reduce_fn,
                               local_batch_fn=local_batch)
        part = part or _hll.SimPartial.empty()
        if opt.remote:
            return part
        return [Pair(id=rid, count=cnt)
                for rid, cnt, _score in part.top_pairs(n, metric)]

    def _similar_shard(self, idx: Index, field_name: str,
                       filter_call: Call, shard: int) -> "_hll.SimPartial":
        """Host oracle / remote map half: one shard's overlap and
        cardinality totals for every row of the field."""
        frag = self.holder.fragment(idx.name, field_name, VIEW_STANDARD,
                                    shard)
        if frag is None:
            return _hll.SimPartial.empty()
        filt = self._bitmap_call_shard(idx, filter_call, shard)
        fw = self._row_words_for(filt, shard)
        if fw is None:
            fw = np.zeros(WORDS_PER_SHARD, dtype=np.uint32)
        rids = list(frag.row_ids())
        ids = np.asarray(rids, dtype=np.uint64)
        overlap = np.zeros(len(rids), dtype=np.int64)
        selfcnt = np.zeros(len(rids), dtype=np.int64)
        for i, rid in enumerate(rids):
            words = frag.row_words(rid)
            overlap[i] = bitops.np_count(words & fw)
            selfcnt[i] = bitops.np_count(words)
        return _hll.SimPartial(ids=ids, overlap=overlap, selfcnt=selfcnt,
                               filtcnt=int(bitops.np_count(fw)))

    def _field_row_ids(self, idx: Index, field_name: str,
                       shards) -> list[int]:
        """Sorted union of the field's row ids over the given shards —
        the id-ascending candidate universe the similarity cube stacks."""
        ids: set[int] = set()
        for shard in shards:
            frag = self.holder.fragment(idx.name, field_name,
                                        VIEW_STANDARD, shard)
            if frag is not None:
                ids.update(int(r) for r in frag.row_ids())
        return sorted(ids)

    # ------------------------------------------------------------------
    # TopN (reference executor.go:857 two-pass)
    # ------------------------------------------------------------------

    def _execute_top_n(self, idx: Index, c: Call, shards, opt) -> list[Pair]:
        ids_arg, _ = c.uint_slice_arg("ids")
        n, _ = c.uint_arg("n")

        if self.planner is not None and self._topn_batch_fn(idx, c) is None:
            # once a call, both passes: it leaves the planner for the
            # per-shard path
            self.stats.count("executor.fallback.topn", 1)
        pairs = self._top_n_shards(idx, c, shards, opt)
        if not pairs or ids_arg or opt.remote:
            return pairs

        # Pass 2: exact counts for the merged candidate ids.
        other = c.clone()
        other.args["ids"] = sorted(p.id for p in pairs)
        trimmed = self._top_n_shards(idx, other, shards, opt)
        if n and n < len(trimmed):
            trimmed = trimmed[:n]
        return trimmed

    def _top_n_shards(self, idx: Index, c: Call, shards, opt) -> list[Pair]:
        def reduce_fn(p, v):
            return merge_pairs(p or [], v)

        merged = self.map_reduce(
            idx, shards, c, opt,
            lambda shard: self._top_n_shard(idx, c, shard), reduce_fn,
            local_batch_fn=self._topn_batch_fn(idx, c)) or []
        return sort_pairs(merged)

    def _topn_batch_fn(self, idx: Index, c: Call):
        """Planner TopN: one sparse-aware streamed device program for ALL
        local shards (planner.execute_topn_pairs) instead of a per-shard
        loop, preserving per-shard filter/threshold/truncate semantics.
        Returns None when the call needs the per-shard path (tanimoto
        needs per-shard src counts; unplannable filter trees)."""
        if self.planner is None:
            return None
        field_name = c.args.get("_field")
        f = idx.field(field_name) if field_name else None
        if f is None or f.field_type == FIELD_TYPE_INT:
            return None
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 0:
            return None
        if len(c.children) > 1:
            return None
        filter_call = c.children[0] if c.children else None
        if filter_call is not None and not self.planner.supports(filter_call):
            return None
        row_ids, has_ids = c.uint_slice_arg("ids")
        n, _ = c.uint_arg("n")
        if has_ids:
            n = 0  # explicit ids: no truncation (fragment.go:1575)
        min_threshold, _ = c.uint_arg("threshold")
        if min_threshold == 0:
            min_threshold = DEFAULT_MIN_THRESHOLD

        attr_name = c.args.get("attrName")
        attr_values = c.args.get("attrValues")
        allowed_attrs = set(attr_values) if (attr_name and attr_values) \
            else None

        def batch(shs: list[int]) -> list[Pair]:
            # cache_type 'none' errors only if a fragment exists, exactly
            # like the per-shard path (which never reaches the check when
            # holder.fragment returns None for every shard).
            if f.options.cache_type == "none":
                if any(self.holder.fragment(idx.name, field_name,
                                            VIEW_STANDARD, s) is not None
                       for s in shs):
                    raise QueryError(
                        f'cannot compute TopN(), field has no cache: '
                        f'"{field_name}"')
                return []
            per_shard = self.planner.execute_topn_counts(
                idx, field_name, VIEW_STANDARD, list(shs), filter_call,
                row_ids=[int(r) for r in row_ids] if has_ids else None)
            acc: list[Pair] = []
            for shard in sorted(per_shard):
                # Arrives sorted (count desc, id asc); threshold is an
                # order-preserving mask, then attr filter, then truncate
                # — same order as _top_filter_pairs.
                ids, counts = per_shard[shard]
                keep = counts >= min_threshold
                ids, counts = ids[keep], counts[keep]
                if len(ids) == 0:
                    continue
                if allowed_attrs is None and n:
                    ids, counts = ids[:n], counts[:n]
                pairs: list[Pair] = []
                for rid, cnt in zip(ids.tolist(), counts.tolist()):
                    if allowed_attrs is not None:
                        attrs = f.row_attr_store.attrs(rid)
                        if attrs.get(attr_name) not in allowed_attrs:
                            continue
                    pairs.append(Pair(id=rid, count=cnt))
                    if n and len(pairs) >= n:
                        break
                acc = merge_pairs(acc, pairs)
            return acc

        return batch

    def _top_n_shard(self, idx: Index, c: Call, shard: int) -> list[Pair]:
        """Exact per-shard TopN: device-batched intersection counts over the
        full row stack (replaces the reference's rank-cache walk,
        fragment.go:1570 — exact, no threshold staleness)."""
        field_name = c.args.get("_field")
        n, _ = c.uint_arg("n")
        f = idx.field(field_name) if field_name else None
        if f is not None and f.field_type == FIELD_TYPE_INT:
            raise QueryError(f"cannot compute TopN() on integer field: {field_name!r}")

        attr_name = c.args.get("attrName")
        row_ids, has_ids = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues")
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise QueryError("Tanimoto Threshold is from 1 to 100 only")

        src: Row | None = None
        if len(c.children) == 1:
            src = self._bitmap_call_shard(idx, c.children[0], shard)
        elif len(c.children) > 1:
            raise QueryError("TopN() can only have one input bitmap")

        frag = self.holder.fragment(idx.name, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return []
        if frag.cache_type == "none":
            raise QueryError(f'cannot compute TopN(), field has no cache: "{field_name}"')
        if min_threshold == 0:
            min_threshold = DEFAULT_MIN_THRESHOLD

        if has_ids:
            n = 0  # explicit ids: no truncation (fragment.go:1575)

        # Exact batched counts via the shared fragment kernel path; then
        # layer the threshold/tanimoto/attr-filter predicates on top.
        raw = frag.top(n=0, src=src,
                       row_ids=[int(r) for r in row_ids] if has_ids else None)
        pairs = self._top_filter_pairs(f, frag, raw, src, tanimoto,
                                       min_threshold, c)
        if n:
            pairs = pairs[:n]
        return pairs

    def _top_filter_pairs(self, f, frag, raw, src, tanimoto: int,
                          min_threshold: int, c: Call) -> list[Pair]:
        """Threshold/tanimoto/attr predicates over sorted (rid, count)
        pairs of ONE shard (fragment.go:1617-1691). ``frag``/``src`` are
        only needed when tanimoto > 0."""
        attr_name = c.args.get("attrName")
        attr_values = c.args.get("attrValues")
        src_count = src.count() if (src is not None and tanimoto > 0) else 0
        allowed_attrs = set(attr_values) if (attr_name and attr_values) else None

        pairs = []
        for rid, cnt in raw:
            if tanimoto > 0:
                import math
                base = frag.rows[rid].count() if rid in frag.rows else 0
                t = math.ceil(cnt * 100 / (base + src_count - cnt))
                if t <= tanimoto:
                    continue
            elif cnt < min_threshold:
                continue
            if allowed_attrs is not None:
                attrs = f.row_attr_store.attrs(rid) if f else {}
                if attrs.get(attr_name) not in allowed_attrs:
                    continue
            pairs.append(Pair(id=rid, count=cnt))
        return pairs

    # ------------------------------------------------------------------
    # Rows (reference executor.go:1272)
    # ------------------------------------------------------------------

    def _execute_rows(self, idx: Index, c: Call, shards, opt) -> list[int]:
        """Returns raw row ids (reference RowIDs); the public
        RowIdentifiers wrapping happens in _translate_result, so remote
        responses stay mergeable (executor.go:1272, :2800)."""
        field_name = c.args.get("field") if isinstance(c.args.get("field"), str) \
            else c.args.get("_field")
        if not isinstance(field_name, str):
            raise QueryError("Rows() field required")
        column, has_col = c.uint_arg("column")
        if has_col:
            shards = [column // SHARD_WIDTH]
        limit, has_limit = c.uint_arg("limit")
        limit = limit if has_limit else _MAXINT

        def map_fn(shard):
            return self._rows_shard(idx, field_name, c, shard)

        def reduce_fn(p, v):
            return merge_row_ids(p or [], v, limit)

        return self.map_reduce(idx, shards, c, opt, map_fn, reduce_fn) or []

    def _rows_shard(self, idx: Index, field_name: str, c: Call,
                    shard: int) -> list[int]:
        """Reference executeRowsShard (executor.go:1320)."""
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")

        views = [VIEW_STANDARD]
        if f.field_type == FIELD_TYPE_TIME:
            from_time = tq.parse_time(c.args["from"]) if "from" in c.args else None
            to_time = tq.parse_time(c.args["to"]) if "to" in c.args else None
            if from_time or to_time or f.options.no_standard_view:
                q = f.time_quantum()
                if not q:
                    return []
                lo, hi = f._time_view_bounds()
                if lo is None:
                    return []
                from_time = from_time if (from_time and from_time > lo) else lo
                to_time = to_time if (to_time and to_time < hi) else hi
                views = tq.views_by_time_range(VIEW_STANDARD, from_time,
                                               to_time, q)

        start = 0
        previous, has_prev = c.uint_arg("previous")
        if has_prev:
            start = previous + 1

        column, has_col = c.uint_arg("column")
        if has_col and column // SHARD_WIDTH != shard:
            return []
        limit, has_limit = c.uint_arg("limit")

        out: list[int] = []
        for view_name in views:
            frag = self.holder.fragment(idx.name, field_name, view_name, shard)
            if frag is None:
                continue
            rows = frag.rows_list(
                start_row=start,
                column=column if has_col else None,
                limit=limit if has_limit else None)
            out = merge_row_ids(out, rows, limit if has_limit else _MAXINT)
        return out

    # ------------------------------------------------------------------
    # GroupBy (reference executor.go:1069, iterator :3058)
    # ------------------------------------------------------------------

    def _execute_group_by(self, idx: Index, c: Call, shards, opt) -> list[GroupCount]:
        if not c.children:
            raise QueryError("need at least one child call")
        limit, has_limit = c.uint_arg("limit")
        limit = limit if has_limit else _MAXINT
        filter_call, _ = c.call_arg("filter")

        child_rows: list[list[int] | None] = [None] * len(c.children)
        for i, child in enumerate(c.children):
            if isinstance(child.args.get("field"), str):
                child.args["_field"] = child.args["field"]
            if child.name != "Rows":
                raise QueryError(
                    f"'{child.name}' is not a valid child query for GroupBy, "
                    f"must be 'Rows'")
            _, has_lim = child.uint_arg("limit")
            _, has_col = child.uint_arg("column")
            if has_lim or has_col:
                ids = self._execute_rows(idx, child, shards, opt)
                if not ids:
                    return []
                child_rows[i] = ids

        def map_fn(shard):
            return self._group_by_shard(idx, c, filter_call, shard, child_rows)

        def reduce_fn(p, v):
            # Merge UNBOUNDED: truncating intermediate merges to the
            # user limit drops groups whose counts other legs would
            # still raise — which also made the answer depend on leg
            # completion order. The offset/limit window applies once,
            # after the full fold below.
            return merge_group_counts(p or [], v, _MAXINT)

        local_batch = None
        gb_fields = self._planner_group_by_fields(idx, c, filter_call,
                                                  child_rows)
        if gb_fields is None and self.planner is not None:
            # the planner's lattice was not tried: per-shard path
            self.stats.count("executor.fallback.groupby", 1)
        if gb_fields is not None:
            def local_batch(shs):
                p = self.planner
                cands = [p.group_by_candidates(idx, fn, shs)
                         for fn in gb_fields]
                res = None
                if all(cands):
                    res = p.execute_group_by(idx, gb_fields, cands, shs,
                                             filter_call)
                elif shs:  # a level has no rows anywhere: empty result
                    return []
                if res is None:  # too many pairs: per-shard streaming
                    self.stats.count("executor.fallback.groupby", 1)
                    acc = None
                    for shard in shs:
                        acc = reduce_fn(acc, map_fn(shard))
                    return acc or []
                return [GroupCount(
                    group=[FieldRow(field=gb_fields[i], row_id=rid)
                           for i, rid in enumerate(grp)],
                    count=cnt) for grp, cnt in res]

        results = self.map_reduce(idx, shards, c, opt, map_fn, reduce_fn,
                                  local_batch_fn=local_batch) or []

        offset, has_off = c.uint_arg("offset")
        if has_off and offset < len(results):
            results = results[offset:]
        if has_limit and limit < len(results):
            results = results[:limit]
        return results

    def _planner_group_by_fields(self, idx: Index, c: Call,
                                 filter_call: Call | None,
                                 child_rows) -> list[str] | None:
        """Field names when the planner's batched GroupBy applies: plain
        Rows children (no cursors/column/limit/time windows) over
        non-time fields, plannable filter. None = use the per-shard
        path (which also handles the cursor/seek semantics)."""
        if self.planner is None:
            return None
        if filter_call is not None and not self.planner.supports(filter_call):
            return None
        fields = []
        for i, child in enumerate(c.children):
            if child_rows[i] is not None:
                return None
            if any(a in child.args
                   for a in ("previous", "column", "limit", "from", "to")):
                return None
            field_name = child.args.get("_field")
            f = idx.field(field_name)
            if f is None:
                raise FieldNotFoundError(f"field not found: {field_name!r}")
            if f.field_type == FIELD_TYPE_TIME or f.options.no_standard_view:
                return None
            fields.append(field_name)
        return fields

    def _group_by_shard(self, idx: Index, c: Call, filter_call: Call | None,
                        shard: int, child_rows) -> list[GroupCount]:
        """DFS over row combinations; empty-intersection pruning; the last
        level is one batched device intersection-count per prefix."""
        filter_row = None
        if filter_call is not None:
            filter_row = self._bitmap_call_shard(idx, filter_call, shard)
            fseg = filter_row.segment(shard)
            if fseg is None:
                return []

        fields, frags, cands = [], [], []
        for i, child in enumerate(c.children):
            field_name = child.args.get("_field")
            if idx.field(field_name) is None:
                raise FieldNotFoundError(f"field not found: {field_name!r}")
            frag = self.holder.fragment(idx.name, field_name, VIEW_STANDARD, shard)
            if frag is None:
                return []
            rows = frag.rows_list(among=child_rows[i])
            if not rows:
                return []
            fields.append(field_name)
            frags.append(frag)
            cands.append(rows)

        # Per-child "previous" cursor (reference Seek + ignorePrev cascade,
        # executor.go:3116-3137): each provided previous seeks its level;
        # once a level can't resume exactly at its previous row, deeper
        # levels restart from the beginning.
        prev: list[int | None] = []
        for child in c.children:
            p, has_p = child.uint_arg("previous")
            prev.append(p if has_p else None)
        any_prev = any(p is not None for p in prev)

        limit, has_limit = c.uint_arg("limit")
        limit = limit if has_limit else _MAXINT
        results: list[GroupCount] = []
        k = len(cands)

        def recurse(level: int, acc: Row | None, prefix: list[int],
                    at_cursor: bool):
            if len(results) >= limit:
                return
            rows = cands[level]
            if at_cursor and prev[level] is not None:
                # Resume strictly after the cursor at the last level,
                # at-or-after it at earlier levels.
                lo = prev[level] + (1 if level == k - 1 else 0)
                rows = [r for r in rows if r >= lo]
            if level == k - 1:
                # Batched last level.
                if acc is None and filter_row is None:
                    counts = [(r, frags[level].rows[r].count()) for r in rows]
                else:
                    base = acc if acc is not None else filter_row
                    seg = base.segment(shard)
                    if seg is None:
                        return
                    # Row-group-tiled device counts: O(tile) HBM even for
                    # 1M-row last-level fields; reuse=True keeps moderate
                    # tile sets device-resident across group prefixes.
                    cnts = frags[level].intersection_counts(rows, seg,
                                                            reuse=True)
                    counts = list(zip(rows, cnts.tolist()))
                for r, cnt in counts:
                    if len(results) >= limit:
                        return
                    if cnt > 0:
                        results.append(GroupCount(
                            group=[FieldRow(field=fields[i], row_id=p)
                                   for i, p in enumerate(prefix)] +
                                  [FieldRow(field=fields[level], row_id=r)],
                            count=int(cnt)))
                return
            for j, r in enumerate(rows):
                if len(results) >= limit:
                    return
                row = frags[level].row(r)
                if level == 0 and filter_row is not None:
                    row = row.intersect(filter_row)
                elif acc is not None:
                    row = row.intersect(acc)
                # The cursor chain survives only along the first row of each
                # level, and only if that row IS the previous row (or the
                # level had no previous) — otherwise deeper levels restart
                # (ignorePrev).
                still_cursor = (at_cursor and j == 0
                                and (prev[level] is None or r == prev[level]))
                if not still_cursor and row.is_empty():
                    continue
                recurse(level + 1, row, prefix + [r], still_cursor)

        recurse(0, None, [], any_prev)
        return results

    # ------------------------------------------------------------------
    # writes
    # ------------------------------------------------------------------

    def _execute_set(self, idx: Index, c: Call, opt: ExecOptions) -> bool:
        """Reference executeSet (executor.go:2067)."""
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError("Set() column argument 'col' required")
        field_name = c.field_arg()
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")

        idx.add_existence([col_id])

        if f.field_type == FIELD_TYPE_INT:
            row_val, ok = c.int_arg(field_name)
            if not ok:
                raise QueryError("Set() row argument 'row' required")
            apply = lambda: f.set_value(col_id, row_val)
        else:
            row_arg = c.args.get(field_name)
            if isinstance(row_arg, bool):
                row_id = 1 if row_arg else 0
            else:
                row_id, ok = c.uint_arg(field_name)
                if not ok:
                    raise QueryError("Set() row argument 'row' required")
            timestamp = None
            if "_timestamp" in c.args:
                timestamp = tq.parse_time(c.args["_timestamp"])
            apply = lambda: f.set_bit(row_id, col_id, timestamp)

        if self.cluster is not None:
            # Replicated write: apply on every owner (executor.go:2144).
            return self.cluster.write_fanout(
                idx.name, col_id // SHARD_WIDTH, c, opt, apply)
        return apply()

    def _execute_clear_bit(self, idx: Index, c: Call, opt: ExecOptions) -> bool:
        field_name = c.field_arg()
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        row_arg = c.args.get(field_name)
        if isinstance(row_arg, bool):
            row_id = 1 if row_arg else 0
        else:
            row_id, ok = c.uint_arg(field_name)
            if not ok:
                raise QueryError("row=<row> argument required to Clear() call")
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError(
                "column argument to Clear(<COLUMN>, <FIELD>=<ROW>) required")
        if f.field_type == FIELD_TYPE_INT:
            def apply():
                # Clearing an int value clears the exists bit.
                v = f.view(view_bsi_name(field_name))
                if v is None:
                    return False
                frag = v.fragment(col_id // SHARD_WIDTH)
                if frag is None:
                    return False
                from pilosa_tpu.core.fragment import BSI_EXISTS_BIT
                return frag.clear_bit(BSI_EXISTS_BIT, col_id)
        else:
            def apply():
                return f.clear_bit(row_id, col_id)
        if self.cluster is not None:
            return self.cluster.write_fanout(
                idx.name, col_id // SHARD_WIDTH, c, opt, apply)
        return apply()

    def _execute_clear_row(self, idx: Index, c: Call, shards, opt) -> bool:
        field_name = c.field_arg()
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        if f.field_type == FIELD_TYPE_INT:
            raise QueryError(
                f"ClearRow() is not supported on {f.field_type} field types")
        row_arg = c.args.get(field_name)
        if isinstance(row_arg, bool):
            row_id = 1 if row_arg else 0
        else:
            row_id, ok = c.uint_arg(field_name)
            if not ok:
                raise QueryError("ClearRow() row argument 'row' required")

        def map_fn(shard):
            changed = False
            for _view_name, v in list(f.views.items()):
                frag = v.fragment(shard)
                if frag is not None:
                    changed |= frag.clear_row(row_id)
            return changed

        return bool(self.map_reduce(idx, shards, c, opt, map_fn,
                                    lambda p, v: bool(p) or v))

    def _execute_store(self, idx: Index, c: Call, shards, opt) -> bool:
        """Reference executeSetRow / Store() (executor.go:1990)."""
        field_name = c.field_arg()
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        if f.field_type != "set":
            raise QueryError(f"can't Store() on a {f.field_type} field")
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise QueryError("need the <FIELD>=<ROW> argument on Store()")
        if len(c.children) != 1:
            raise QueryError("Store() requires a source row")

        def map_fn(shard):
            src = self._bitmap_call_shard(idx, c.children[0], shard)
            view = f.create_view_if_not_exists(VIEW_STANDARD)
            frag = view.create_fragment_if_not_exists(shard)
            return frag.set_row(src, row_id)

        return bool(self.map_reduce(idx, shards, c, opt, map_fn,
                                    lambda p, v: bool(p) or v))

    def _execute_set_row_attrs(self, idx: Index, c: Call, opt) -> None:
        field_name = c.args.get("_field")
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        row_id, ok = c.uint_arg("_row")
        if not ok:
            raise QueryError("SetRowAttrs() row field 'row' required")
        attrs = {k: v for k, v in c.args.items() if k not in ("_field", "_row")}
        f.row_attr_store.set_attrs(row_id, attrs)
        if self.cluster is not None:
            self.cluster.broadcast_call(idx.name, c, opt)

    def _execute_set_column_attrs(self, idx: Index, c: Call, opt) -> None:
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError("SetColumnAttrs() col required")
        attrs = {k: v for k, v in c.args.items() if k != "_col"}
        idx.column_attr_store.set_attrs(col_id, attrs)
        if self.cluster is not None:
            self.cluster.broadcast_call(idx.name, c, opt)

    # ------------------------------------------------------------------
    # Options (reference executor.go:360)
    # ------------------------------------------------------------------

    def _execute_options(self, idx: Index, c: Call, shards, opt) -> Any:
        opt_copy = replace(opt)
        if "columnAttrs" in c.args:
            v = c.args["columnAttrs"]
            if not isinstance(v, bool):
                raise QueryError("Query(): columnAttrs must be a bool")
            opt.column_attrs = v  # mutates outer opt, like the reference
        if "excludeRowAttrs" in c.args:
            v = c.args["excludeRowAttrs"]
            if not isinstance(v, bool):
                raise QueryError("Query(): excludeRowAttrs must be a bool")
            opt_copy.exclude_row_attrs = v
        if "excludeColumns" in c.args:
            v = c.args["excludeColumns"]
            if not isinstance(v, bool):
                raise QueryError("Query(): excludeColumns must be a bool")
            opt_copy.exclude_columns = v
        if "shards" in c.args:
            v = c.args["shards"]
            if not isinstance(v, list) or not all(
                    isinstance(s, int) and not isinstance(s, bool) for s in v):
                raise QueryError("Query(): shards must be a list of unsigned integers")
            shards = as_shard_set(v, self.stats)
        if len(c.children) != 1:
            raise QueryError("Options() requires a single child call")
        return self._execute_call(idx, c.children[0], shards, opt_copy)

    # ------------------------------------------------------------------
    # key translation (reference executor.go:2610-2905)
    # ------------------------------------------------------------------

    def _xlate(self, idx: Index, f, key: str) -> int:
        """Allocate/lookup one key's id (single-key convenience over the
        batched resolver)."""
        return self._resolve_keys(idx, f, [key])[0]

    def _resolve_keys(self, idx: Index, f, keys: list[str]) -> list[int]:
        """Batched key → id resolution, the one forward-translate path.

        Read-through order: the device key plane first (exec/keyplane —
        no lock, no allocation, no coordinator), then ONE batched host
        pass for the misses: the cluster translator when set (the
        coordinator is the sole id authority; a replica's translator
        serves its synced local snapshot before batching the remaining
        misses into one RPC) or the local store's ``translate_keys``
        (one lock acquisition, one epoch bump for the whole batch).
        Plane misses are re-checked under the store lock before any
        allocation, so a stale plane costs a host fallback, never a
        duplicate id."""
        fname = f.name if f is not None else None
        store = (f if f is not None else idx).translate_store
        ids = self.keyplanes.lookup(idx, fname, store, keys)
        if ids is None:
            if self.translator is not None:
                return self.translator(idx.name, fname, list(keys))
            return store.translate_keys(keys)
        missing = [i for i, v in enumerate(ids) if v is None]
        if missing:
            sub = [keys[i] for i in missing]
            if self.translator is not None:
                got = self.translator(idx.name, fname, sub)
            else:
                got = store.translate_keys(sub)
            for i, v in zip(missing, got):
                ids[i] = v
        return ids

    def _translate_call(self, idx: Index, c: Call) -> Call:
        """Map string keys to ids in-place on a clone.

        Two passes: collect every string-key slot in the tree (with the
        per-slot validation the reference does in translateCall), then
        resolve all of a field's keys in ONE ``_resolve_keys`` batch per
        (field|index) group — a keyed tree costs one lock/plane/RPC
        round per distinct store instead of one per key."""
        c = c.clone()
        slots: list[tuple[Call, str, str | None, str]] = []
        self._collect_key_slots(idx, c, slots)
        if slots:
            groups: dict[str | None, list[int]] = {}
            for i, (_, _, fname, _) in enumerate(slots):
                groups.setdefault(fname, []).append(i)
            for fname, positions in groups.items():
                f = idx.field(fname) if fname is not None else None
                ids = self._resolve_keys(idx, f,
                                         [slots[i][3] for i in positions])
                for i, id_ in zip(positions, ids):
                    call, arg, _, _ = slots[i]
                    call.args[arg] = id_
        return c

    def _collect_key_slots(self, idx: Index, c: Call,
                           slots: list[tuple[Call, str, str | None, str]]) \
            -> None:
        """Gather (call, arg, field-name|None, key) for every string key
        in the tree; validation mirrors reference translateCall
        (executor.go:2634-2637 for the Rows cursor args)."""
        # Column key (index-level).
        col = c.args.get("_col")
        if isinstance(col, str):
            if not idx.options.keys:
                raise QueryError(f"string 'col' value not allowed unless "
                                 f"index 'keys' option enabled: {col!r}")
            slots.append((c, "_col", None, col))
        # Row keys (field-level).
        for key in list(c.args):
            if pql_ast.is_reserved_arg(key):
                continue
            f = idx.field(key)
            if f is None:
                continue
            val = c.args[key]
            if isinstance(val, str) and f.keys:
                slots.append((c, key, f.name, val))
        row = c.args.get("_row")
        if isinstance(row, str):
            fname = c.args.get("_field")
            f = idx.field(fname) if fname else None
            if f is None or not f.keys:
                raise QueryError("string 'row' value not allowed unless "
                                 "field 'keys' option enabled")
            slots.append((c, "_row", f.name, row))
        if c.name == "Rows":
            fname = c.args.get("_field") or c.args.get("field")
            f = idx.field(fname) if isinstance(fname, str) else None
            p = c.args.get("previous")
            if isinstance(p, str):
                if f is None or not f.keys:
                    raise QueryError("string 'previous' value not allowed "
                                     "unless field 'keys' option enabled")
                slots.append((c, "previous", f.name, p))
            col = c.args.get("column")
            if isinstance(col, str):
                if not idx.options.keys:
                    raise QueryError("string 'column' value not allowed "
                                     "unless index 'keys' option enabled")
                slots.append((c, "column", None, col))
        for ch in c.children:
            self._collect_key_slots(idx, ch, slots)
        for v in c.args.values():
            if isinstance(v, Call):
                self._collect_key_slots(idx, v, slots)

    def _translate_result(self, idx: Index, c: Call, result: Any) -> Any:
        """Map ids back to keys on results (reference :2781) — one
        ``translate_ids`` snapshot pass per result set, not one locked
        lookup per id."""
        if isinstance(result, Row) and idx.options.keys:
            cols = [int(i) for i in result.columns()]
            names = idx.translate_store.translate_ids(cols)
            result.keys = [n if n is not None else str(i)
                           for n, i in zip(names, cols)]
        elif c.name == "Rows" and isinstance(result, list):
            fname = c.args.get("_field") or c.args.get("field")
            f = idx.field(fname) if isinstance(fname, str) else None
            if f is not None and f.keys:
                names = f.translate_store.translate_ids(list(result))
                result = RowIdentifiers(
                    keys=[n if n is not None else str(r)
                          for n, r in zip(names, result)])
            else:
                result = RowIdentifiers(rows=list(result))
        elif isinstance(result, Pair) and c.name in ("MinRow", "MaxRow"):
            fname = c.args.get("field")
            f = idx.field(fname) if isinstance(fname, str) else None
            if f is not None and f.keys:
                result.key = f.translate_store.translate_id(result.id) or ""
        elif isinstance(result, list) and result and isinstance(result[0], Pair):
            fname = c.args.get("_field")
            f = idx.field(fname) if isinstance(fname, str) else None
            if f is not None and f.keys:
                names = f.translate_store.translate_ids(
                    [p.id for p in result])
                for p, n in zip(result, names):
                    p.key = n if n is not None else str(p.id)
        elif isinstance(result, list) and result and isinstance(result[0], GroupCount):
            # One reverse batch per keyed field across ALL groups.
            by_field: dict[str, list] = {}
            for gc in result:
                for fr in gc.group:
                    by_field.setdefault(fr.field, []).append(fr)
            for fname, frs in by_field.items():
                f = idx.field(fname)
                if f is None or not f.keys:
                    continue
                names = f.translate_store.translate_ids(
                    [fr.row_id for fr in frs])
                for fr, n in zip(frs, names):
                    fr.row_key = n if n is not None else ""
        return result
