"""MeshPlanner — compile a PQL bitmap call tree into ONE jitted XLA
program over all shards, laid out on the device mesh.

This is the TPU replacement for the reference's hot loop (executor.go:
2561-2608: per-shard jobs in a worker pool, each running per-container
roaring kernels). Here:

- every leaf Row() of the tree becomes a ``[S, W]`` uint32 block — shard
  ``s``'s row in stack slot ``s`` — placed with a NamedSharding over the
  ``('shard',)`` mesh axis, so each device holds only its shards;
- the whole call tree (and/or/andnot/xor/not + BSI comparators) compiles
  to fused elementwise VPU code; XLA partitions it SPMD over the mesh;
- Count() ends in a per-shard popcount; each device counts its own
  shards and the host sums the [S] int32 vector (``_sum_host``), so the
  compiled program holds no collective (the reference's reduceFn + HTTP
  gather, executor.go:2455,:2414).

Plans are cached two ways: jitted programs by tree *structure* (shape,
ops, depths), and leaf stacks by (fragment identity, generation) so
repeated queries re-upload nothing.
"""

from __future__ import annotations

import functools
import math
import threading
from collections import Counter, OrderedDict, deque
from contextlib import nullcontext
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.core import timequantum as tq
from pilosa_tpu.core.index import Index
from pilosa_tpu.core.row import Row
from pilosa_tpu.core.shardset import ShardSet, as_shard_set
from pilosa_tpu.core.view import VIEW_STANDARD, view_bsi_name
from pilosa_tpu.errors import (
    BSIGroupNotFoundError,
    FieldNotFoundError,
    QueryError,
)
from pilosa_tpu import native
from pilosa_tpu.exec import fuse as _fuse
from pilosa_tpu.exec import residency as _residency
from pilosa_tpu.exec.topn import TopNCounts
from pilosa_tpu.obs import profile as _profile
from pilosa_tpu.obs.tracing import start_span
from pilosa_tpu.obs.histogram import WIDTH_BOUNDS, LogHistogram
from pilosa_tpu.ops import bitops, bsi as bsi_ops, pallas_kernels
from pilosa_tpu.parallel import compile_cache
from pilosa_tpu.parallel.batcher import TransferBatcher
from pilosa_tpu.parallel.coalesce import DispatchCoalescer
from pilosa_tpu.parallel.mesh import (
    SHARD_AXIS,
    make_mesh,
    pad_to_multiple,
    shard_spec,
)
from pilosa_tpu.parallel.stacks import StackKey, StackStore
from pilosa_tpu.pql import BETWEEN, NEQ, Call, Condition
from pilosa_tpu.pql import ast as pql_ast

_BITMAP_CALLS = frozenset(
    {"Row", "Range", "Difference", "Intersect", "Union", "Xor", "Not", "Shift"})


class MeshPlanner:
    """Shard-stacked SPMD execution of bitmap call trees."""

    #: default budget for resident stacks, in bytes a device: four
    #: devices hold four times what one does (parallel.stacks).
    DEFAULT_CACHE_BYTES = 4 << 30
    #: whether the store's workers upload a plan's stacks ahead of the
    #: request. Off for the distributed planner: its stack builds must
    #: run on every process of the mesh in lockstep, not on one node's
    #: worker.
    UPLOADS_AHEAD = True

    def __init__(self, holder, mesh=None,
                 max_cache_bytes: int = DEFAULT_CACHE_BYTES,
                 bucket_policy: str = "pow2", stats=None,
                 coalesce_window_us: float | None = None):
        self.holder = holder
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_devices = int(np.prod(self.mesh.devices.shape))
        self.stats = stats
        #: plan-shape bucketing policy ("pow2" | "none"): stack heights
        #: round up to power-of-two buckets so a never-seen shard count
        #: dispatches into an already-compiled program (see _pad).
        self.bucket_policy = bucket_policy
        #: every device-resident stack (rows, cubes, sketch planes, key
        #: planes): keys, validity, the byte budget, eviction and the
        #: uploads in flight (parallel.stacks). The builders below say
        #: how a stack is made; the store says whether one is needed.
        self.stacks = StackStore(max_cache_bytes, _residency.REPR_CLASSES,
                                 stats=stats,
                                 uploads_ahead=self.UPLOADS_AHEAD)
        #: guards the plan, TopN-filter and vmap caches and the
        #: observed-traffic list — one planner serves every thread of
        #: the HTTP server.
        self._plan_lock = threading.Lock()
        #: structural signature -> jitted tree evaluator
        self._fn_cache: dict[tuple, Callable] = {}
        #: sparse-upload assembler, jitted per mesh so the scatter
        #: output lands sharded (see _build_stack).
        self._assemble_jit = jax.jit(
            _named(_assemble_stack, "stack_assemble"),
            static_argnames=("s_pad",),
            out_shardings=shard_spec(self.mesh))
        #: cross-query transfer coalescing (parallel.batcher): every
        #: Count pull goes through it, so concurrent queries share one
        #: stacked device->host transfer per wave.
        self.batcher = TransferBatcher(stats=stats)
        #: tiny host-side filter cache for the two passes of a TopN
        #: that takes the per-fragment sweep (keyed by call text +
        #: shards + epoch; each pull is a link round-trip).
        self._filter_host_cache: dict[tuple, np.ndarray] = {}
        #: prepared plans: (index identity, call text, shards) ->
        #: (leaf descriptors, jitted fn). A repeated query shape skips
        #: the signature walk; leaves re-resolve through _fetch_leaf
        #: every query (an O(1) epoch-validated stack-cache hit), so
        #: plans pin NO device arrays, never go stale, and all HBM
        #: accounting stays in the one budgeted stack cache. The device
        #: still runs the full program every time (prepared-statement
        #: caching, not result caching).
        self._plan_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.PLAN_CACHE_SIZE = 128
        #: (index instance, field, view, row, shard set) -> (index
        #: epoch, largest per-shard cardinality): what `_leaf_class`
        #: measured, oldest out first. A plan that misses the text-keyed
        #: cache above (the benchmark's count trees are 1,479 distinct
        #: texts over 16 rows) then costs a tree walk, not a walk of
        #: every shard's fragment for every leaf.
        self._leaf_bits: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.LEAF_BITS_SIZE = 1024
        #: (index instance, field, view, shard set) -> (index epoch,
        #: sorted row ids the field holds in any of the shards): a
        #: filtered TopN's candidate rows, kept as `_leaf_bits` is, so
        #: that a pass walks no fragment for metadata.
        self._field_rows: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.FIELD_ROWS_SIZE = 64
        #: structural shapes real traffic compiled for — (index name,
        #: call text, shard count) -> hit count, recency-ordered. The
        #: seed list for warmup-from-observed-traffic: ServerNode
        #: persists it at shutdown and the next boot's WarmupService
        #: replays it, so restart warmup covers what THIS node's
        #: traffic actually runs, not just the canonical set.
        self._observed: "OrderedDict[tuple, int]" = OrderedDict()
        self.OBSERVED_SIZE = 256
        #: program metadata by compiled-function identity: full
        #: structural signature (the coalescer's batch key — the result
        #: cache already proved same-signature plans identical, so the
        #: key comes free) and the raw unjitted program (vmappable for
        #: the [B, ...] batched launch). Entries live exactly as long as
        #: _fn_cache pins the function, so ids never recycle underneath.
        self._fn_info: dict[int, tuple[tuple, Callable]] = {}
        #: plan signature -> jitted vmapped program (jit re-specializes
        #: per [B, ...] shape internally, so one entry per signature).
        self._vmap_cache: dict[tuple, Callable] = {}
        #: query-program launch accounting (planner.dispatchCount /
        #: dispatchCoalesced / coalesceBatchWidth on /debug/vars; the
        #: benchmark's dispatches_per_request reads the counters).
        self._dispatch_lock = threading.Lock()
        self.dispatches = 0
        self.dispatches_coalesced = 0
        self._batch_widths: "deque[int]" = deque(maxlen=512)
        #: bounded width histogram over the node's lifetime (the deque
        #: above is a recency window); /debug/device renders it.
        self._width_hist = LogHistogram(bounds=WIDTH_BOUNDS, lock=False)
        #: same-plan dispatch coalescing (parallel.coalesce): every
        #: Count / fused-aggregate launch goes through it.
        self.coalescer = DispatchCoalescer(self, coalesce_window_us)
        #: overridden off by the distributed planner: its outputs need
        #: cross-process replication the coalescer doesn't reproduce.
        self.coalesce_supported = True
        #: the [B, ...] vmapped wave loses NamedShardings when stacking;
        #: restrict it to single-device meshes (the identical-argument
        #: shared wave is layout-preserving and stays available).
        self.coalesce_vmap_supported = self.n_devices == 1
        #: fused Sum/Min/Max programs (see exec/fuse.py); the
        #: distributed planner keeps the stepped path, whose
        #: _replicate_small hook reshards each output.
        self.fuse_aggregates_supported = True
        #: __const__ leaf injection (executor partial fusion of mixed
        #: trees); off for the distributed planner, whose const upload
        #: would need cross-process placement.
        self.fuse_const_supported = True
        #: packed [S, K] index stacks for low-cardinality rows
        #: (exec/residency); off for the distributed planner — its
        #: _build_stack assembles per-process dense fragments and has
        #: no packed assembly path yet.
        self.residency_packed_supported = True
        #: fused sketch programs (pilosa_tpu.sketch): HLL distinct-count
        #: register planes and the SimilarTopN row-cube ranking; off for
        #: the distributed planner — its per-process stack assembly has
        #: no hll/simtopn build path yet, and the host map/reduce spine
        #: (register-max partials over the wire) covers it instead.
        self.sketch_supported = True

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def supports(self, c: Call) -> bool:
        """True if the call tree is pure bitmap algebra this planner can
        compile (no attrs, no time-shift edge cases we haven't built)."""
        if c.name not in _BITMAP_CALLS:
            return False
        if c.name in ("Row", "Range"):
            return True
        if c.name == "Shift":
            # Full-range on device (word roll + intra-word carry,
            # bitops.shift_left); n ≥ SHARD_WIDTH legally yields zeros.
            n = c.args.get("n", 0)
            if not isinstance(n, int) or isinstance(n, bool) or n < 0:
                return False
        return all(self.supports(ch) for ch in c.children)

    def execute_count(self, idx: Index, c: Call, shards: list[int],
                      const_rows: list | None = None) -> int:
        """Count(tree) as one device program (per-shard popcounts,
        summed on the host); the per-shard popcounts are pulled through
        the TransferBatcher, so concurrent counts share one stacked
        device->host transfer a wave (see parallel.batcher)."""
        if not shards:
            return 0
        fn, arrays = self.prepare_count(idx, c, shards,
                                        const_rows=const_rows)
        _fuse.add_fused_steps(_fuse.call_steps(c) + 1)
        return self._wait(self.dispatch_count(fn, arrays))

    def prepare_count(self, idx: Index, c: Call, shards: list[int],
                      const_rows: list | None = None):
        """Resolve Count(tree) to its (jitted fn, leaf device arrays)
        without dispatching: the plan through the prepared-plan cache,
        the leaves fetched for this call (`_fetch_leaves`)."""
        # Const-leaf plans (partial fusion of a mixed tree) bypass the
        # text-keyed plan cache: their __const__ slots print identically
        # while holding per-query host rows. The structural _fn_cache
        # still shares the compiled program across const values.
        shards = self._shards(shards)

        def build(leaves):
            sig = self._signature(idx, c, leaves, shards)
            return self._compiled(("count",) + sig, sig, len(leaves),
                                  reduce="per_shard")

        if const_rows is None:
            # Observed as the executable form (with the Count wrapper):
            # warmup replays these strings through the Executor, and
            # only a Count() reaches prepare_count again.
            leaves, fn = self._plan_cached(idx, str(c), shards, build,
                                           observed="Count({})")
        else:
            leaves = []
            with start_span("plan.prepare", stats=self.stats):
                fn = build(leaves)
        return fn, self._fetch_leaves(idx, leaves, shards,
                                      const_rows=const_rows)

    def _shards(self, shards) -> ShardSet:
        """The one place a caller's shards become what the plan cache
        and the stack store are keyed by (core.shardset): a `ShardSet`
        passes through as it is, a list is interned by content."""
        return as_shard_set(shards, self.stats)

    def _plan_cached(self, idx: Index, text: str, shards: ShardSet,
                     build: Callable[[list], Callable],
                     observed: str | None = None):
        """(leaf descriptors, jitted fn) through the prepared-plan cache;
        on a miss ``build(leaves)`` fills the leaf list and returns the
        compiled program, and ``observed.format(text)`` (the query's
        executable text, rendered only then) joins the warm-up seed
        list. ``shards`` in the key costs a cached integer and, on the
        hit, a pointer compare."""
        with start_span("plan.prepare", stats=self.stats):
            # schema_epoch: plans bake field STRUCTURE (a BSI
            # comparator's bit-depth, sign-class branches, base folds),
            # so any schema change — field create/delete, bit-depth
            # growth — must miss.
            plan_key = (idx.name, idx.instance_id, idx.schema_epoch.value,
                        text, shards)
            with self._plan_lock:
                hit = self._plan_cache.get(plan_key)
                if hit is not None:
                    self._plan_cache.move_to_end(plan_key)
            if hit is not None:
                hit = self._revalidate_plan(idx, plan_key, hit, shards)
            if hit is not None:
                return hit[0], hit[1]
            leaves: list[tuple] = []
            fn = build(leaves)
            with self._plan_lock:
                self._plan_cache[plan_key] = (leaves, fn, idx.epoch.value)
                while len(self._plan_cache) > self.PLAN_CACHE_SIZE:
                    self._plan_cache.popitem(last=False)
                if observed is not None:
                    okey = (idx.name, observed.format(text), len(shards))
                    self._observed[okey] = self._observed.get(okey, 0) + 1
                    self._observed.move_to_end(okey)
                    while len(self._observed) > self.OBSERVED_SIZE:
                        self._observed.popitem(last=False)
            return leaves, fn

    def _fetch_leaves(self, idx: Index, leaves: list, shards: tuple,
                      const_rows: list | None = None) -> list:
        """A call's leaf arrays, slot for slot: each distinct descriptor
        is fetched once and its array handed to every slot that names
        it, so a row the call names twice is one state of that row,
        whatever a concurrent write does between two fetches. Residency
        as the request sees it (~0 when every stack is resident; waits
        and synchronous builds otherwise)."""
        with start_span("stack.fetch", stats=self.stats):
            distinct = dict.fromkeys(leaves)
            self._prefetch_leaves(idx, distinct, shards)
            for leaf in distinct:
                distinct[leaf] = self._fetch_leaf(idx, leaf, shards,
                                                  const_rows=const_rows)
            return [distinct[leaf] for leaf in leaves]

    def _wait(self, fut):
        """Block the request's thread on a dispatch future: device run,
        device->host copy, resolver hand-off and the wait to be
        scheduled again."""
        with start_span("transfer.wait", stats=self.stats):
            return fut.result()

    def _revalidate_plan(self, idx: Index, plan_key: tuple, hit: tuple,
                         shards: tuple):
        """Representation-class staleness check for prepared plans. The
        class is baked into the compiled program (a ``pleaf`` node runs
        packed kernels), and the plan cache deliberately survives data
        mutations — so a packed leaf whose row has since grown past the
        packing ceiling would keep uploading ever-larger index stacks.
        O(1) on the hot path: only an index-epoch move triggers the
        per-leaf cardinality walk, and only packed leaves are checked
        (a dense plan is always correct; rows rarely shrink). A changed
        class drops the plan entry and the caller replans."""
        leaves, fn, seen_epoch = hit
        epoch = idx.epoch.value
        if seen_epoch == epoch:
            return hit
        for leaf in leaves:
            if leaf[0] != "prow":
                continue
            _, field_name, view, row_id = leaf
            if self._leaf_class(idx, field_name, view, row_id,
                                shards) != _residency.PACKED:
                with self._plan_lock:
                    self._plan_cache.pop(plan_key, None)
                return None
        hit = (leaves, fn, epoch)
        with self._plan_lock:
            if plan_key in self._plan_cache:
                self._plan_cache[plan_key] = hit
        return hit

    @staticmethod
    def _sum_host(host) -> int:
        # Per-shard int32 popcounts (≤2^20 each) summed in Python ints —
        # immune to int32 overflow past ~2k full shards.
        return int(host.astype(np.int64).sum())

    def dispatch_count(self, fn, arrays):
        """Enqueue a prepared count's device program; Future[int].
        Routed through the coalescer so concurrent dispatches of the
        same plan signature share one launch."""
        return self.coalescer.dispatch(fn, arrays, self._sum_host)

    # -- launch accounting / program registry --------------------------

    def _record_dispatch(self, width: int = 1, profs=None) -> None:
        """One device-program launch answering ``width`` queries.

        ``planner.dispatchCount`` sums the query programs of every
        class: a fused count/aggregate/bitmap program (one per query or
        per coalesced wave), a GroupBy's one program, a filtered TopN
        pass's one program (on the sweep's
        route its filter-stack program and each fragment's Pallas tile
        launch). Not counted: stack builds and
        uploads, key-plane lookups, sketch kernels.

        ``profs``: the QueryProfiles of the queries this launch served.
        The coalescer passes them explicitly — its flusher thread has no
        query context, so the profiles were captured at dispatch() time.
        Planner-internal call sites omit it and the active profile (if
        any) is charged.
        """
        with self._dispatch_lock:
            self.dispatches += 1
            if width > 1:
                self.dispatches_coalesced += width - 1
            self._batch_widths.append(width)
            self._width_hist.observe(width)
        if self.stats is not None:
            self.stats.count("planner.dispatchCount", 1)
            if width > 1:
                self.stats.count("planner.dispatchCoalesced", width - 1)
            self.stats.gauge("planner.coalesceBatchWidth", width)
        if profs is None:
            p = _profile.current()
            if p is not None:
                p.add_dispatch(width)
            return
        for p in profs:
            if p is not None:
                p.add_dispatch(width)

    def batch_widths(self) -> list[int]:
        """Recent per-launch batch widths."""
        with self._dispatch_lock:
            return list(self._batch_widths)

    def _register_fn(self, fn, full_sig: tuple, raw) -> None:
        self._fn_info[id(fn)] = (full_sig, raw)

    def fn_key(self, fn):
        """The coalescer's batch key for a compiled program — its full
        structural signature (None for unregistered callables)."""
        info = self._fn_info.get(id(fn))
        return info[0] if info is not None else None

    def fn_raw(self, fn):
        """The raw (unjitted, vmappable) program behind a compiled fn."""
        info = self._fn_info.get(id(fn))
        return info[1] if info is not None else None

    def vmapped(self, full_sig: tuple, raw) -> Callable:
        """jit(vmap(program)) for the [B, ...] coalesced wave; cached by
        signature (jit re-specializes per batch-shape internally)."""
        with self._plan_lock:
            vfn = self._vmap_cache.get(full_sig)
        if vfn is None:
            vfn = jax.jit(_named(jax.vmap(raw), raw.__name__ + "_wave"))
            with self._plan_lock:
                self._vmap_cache[full_sig] = vfn
        return vfn

    def _tree_plan(self, idx: Index, c: Call,
                   shards: ShardSet) -> tuple[list, Callable]:
        """A bitmap tree's leaf descriptors and the program that
        evaluates it to its stacked [S_pad, W] device array."""
        leaves: list[tuple] = []
        with start_span("plan.prepare", stats=self.stats):
            sig = self._signature(idx, c, leaves, shards)
            return leaves, self._compiled(("row",) + sig, sig, len(leaves),
                                          reduce=None)

    def _run_tree(self, c: Call, fn: Callable, arrays: list) -> jax.Array:
        out = fn(*arrays)
        self._record_dispatch(1)
        _fuse.add_fused_steps(_fuse.call_steps(c))
        return out

    def _tree_stack(self, idx: Index, c: Call, shards: list[int],
                    const_rows: list | None = None) -> jax.Array:
        """Evaluate a bitmap tree to its stacked [S_pad, W] device array."""
        shards = self._shards(shards)
        leaves, fn = self._tree_plan(idx, c, shards)
        return self._run_tree(c, fn, self._fetch_leaves(
            idx, leaves, shards, const_rows=const_rows))

    def execute_bitmap(self, idx: Index, c: Call, shards: list[int],
                       const_rows: list | None = None) -> Row:
        """Evaluate the tree to a Row whose segments are device slices of
        the stacked result (no host sync)."""
        if not shards:
            return Row()
        shards = self._shards(shards)
        out = self._tree_stack(idx, c, shards,
                               const_rows=const_rows)  # [S_pad, W]
        return Row({shard: out[i] for i, shard in enumerate(shards)})

    # ------------------------------------------------------------------
    # aggregates: Sum/Min/Max as ONE SPMD program over
    # the BSI leaf stacks + optional filter tree, instead of the per-shard
    # host loop (reference executor.go:406-999). Rows() stays host-side by
    # design: it is a row-id metadata scan with no device math to batch.
    # ------------------------------------------------------------------

    def supports_aggregate(self, idx: Index, c: Call) -> bool:
        """True for Sum/Min/Max calls whose (optional) filter child is a
        plannable bitmap tree over an existing BSI field."""
        if c.name not in ("Sum", "Min", "Max"):
            return False
        if len(c.children) > 1:
            return False
        if c.children and not self.supports(c.children[0]):
            return False
        field_name, ok = c.string_arg("field")
        if not ok:
            return False
        f = idx.field(field_name)
        return f is not None and f.bsi_group is not None

    def _bsi_inputs(self, idx: Index, c: Call, shards: list[int]):
        """(exists, sign, [depth,S,W] stack, filt, depth) device arrays."""
        field_name, _ = c.string_arg("field")
        f = idx.field(field_name)
        depth = f.bsi_group.bit_depth
        shards = self._shards(shards)
        # the field's planes and the filter's leaves in one fetch: a
        # filter on the field itself reads the same state of its planes
        leaves, fn = (self._tree_plan(idx, c.children[0], shards)
                      if c.children else ([], None))
        (exists, sign, bits), *arrays = self._fetch_leaves(
            idx, [("bsi", field_name, depth)] + leaves, shards)
        if c.children:
            filt = self._run_tree(c.children[0], fn, arrays)
        else:
            filt = _jit_full_like(exists)
            self._record_dispatch(1)
        stack = jnp.stack(bits, axis=0) if bits else \
            jnp.zeros((0,) + exists.shape, exists.dtype)
        self._record_dispatch(1)  # the eager plane stack
        return f, exists, sign, stack, filt, depth

    def _prepare_agg(self, idx: Index, c: Call, shards: list[int],
                     kind: str, is_min: bool):
        """Fused Sum/Min/Max: (jitted fn, leaf arrays, depth) for ONE
        program tracing filter tree + plane stack + aggregate kernel.
        Shares the prepared-plan cache, structural program cache, and
        pow2 bucketing with the count path."""
        field_name, _ = c.string_arg("field")
        f = idx.field(field_name)
        depth = f.bsi_group.bit_depth
        shards = self._shards(shards)

        def build(leaves):
            leaves.append(("bsiagg", field_name, depth))
            filt_sig = (self._signature(idx, c.children[0], leaves, shards)
                        if c.children else None)
            return self._compiled_agg((kind, is_min, depth, filt_sig),
                                      kind, depth, filt_sig, is_min)

        leaves, fn = self._plan_cached(
            idx, f"{kind}{int(is_min)}:{c}", shards, build)
        return fn, self._fetch_leaves(idx, leaves, shards), depth

    def _compiled_agg(self, full_sig: tuple, kind: str, depth: int,
                      filt_sig, is_min: bool) -> Callable:
        fn = self._fn_cache.get(full_sig)
        if fn is not None:
            return fn

        def program(*args):
            # args[0] is the "bsiagg" leaf: the plane cube arrives
            # pre-stacked (and cached), so the program is filter+reduce.
            exists, sign, stack = args[0]
            if filt_sig is not None:
                # The barrier pins the comparator output as a single
                # shared value so the 2*depth intersection-count
                # consumers can't each re-derive it. It does NOT undo
                # the XLA:CPU slowdown from compiling the comparator
                # and the broadcast reduction into one module — that
                # case is routed to the stepped path by _fuse_agg_ok.
                filt = jax.lax.optimization_barrier(
                    _eval_node(filt_sig, args))
            else:
                filt = jnp.full_like(exists, jnp.uint32(0xFFFFFFFF))
            if kind == "sum":
                return bsi_ops.sum_counts(exists, sign, stack, filt,
                                          depth)
            return _agg_min_max(exists, sign, stack, filt, depth, is_min)

        name = "bsi_sum" if kind == "sum" else \
            "bsi_min" if is_min else "bsi_max"
        fn = self._jit_program(_named(program, name), None)
        self._fn_cache[full_sig] = fn
        self._register_fn(fn, full_sig, program)
        return fn

    def _fuse_agg_ok(self, c: Call) -> bool:
        """Fused-aggregate gate. Unfiltered aggregates fuse everywhere:
        with the plane cube cached, one program is strictly cheaper than
        the stepped path's per-query eager restack (measured 3.5x on the
        CPU backend). A FILTERED aggregate fuses under ``auto`` only
        off-CPU: XLA's CPU backend compiles the bit-serial comparator
        and the broadcast reduction into a ~2x-slower loop structure
        when they share one module (optimization barriers don't
        dissuade it); on an accelerator one
        launch instead of three is taken to win (not measured on the
        current machine). ``on`` forces fusion — the bit-equivalence
        tests and TPU-style measurement use it."""
        if not (_fuse.enabled() and self.fuse_aggregates_supported):
            return False
        if not c.children or _fuse.mode() == "on":
            return True
        return jax.default_backend() != "cpu"

    def execute_sum(self, idx: Index, c: Call, shards: list[int]):
        """Global (sum-of-base-offsets, count) in one device program; the
        executor applies the BSI base (reference fragment.sum :1111 under
        executeSum :406). The host fold runs on the batcher's resolver
        thread when the transfer wave lands."""
        if not shards:
            return (0, 0)
        if self._fuse_agg_ok(c):
            # Fused: filter tree + plane stack + sum kernel trace into
            # ONE jitted program; the host fold rides the coalescer's
            # transfer wave.
            fn, arrays, depth = self._prepare_agg(idx, c, shards,
                                                  "sum", False)
            _fuse.add_fused_steps(_fuse.call_steps(c))

            def fold_fused(host):
                cnt_host, pos, neg = host
                count = int(np.asarray(cnt_host).astype(np.int64).sum())
                p = np.asarray(pos, dtype=np.int64).sum(axis=-1)
                n = np.asarray(neg, dtype=np.int64).sum(axis=-1)
                total = sum((1 << i) * (int(p[i]) - int(n[i]))
                            for i in range(depth))
                return total, count

            return self._wait(self.coalescer.dispatch(fn, arrays,
                                                      fold_fused))
        _, exists, sign, stack, filt, depth = self._bsi_inputs(idx, c, shards)
        cnt, pos, neg = self._replicate_small(
            *bsi_ops.sum_counts(exists, sign, stack, filt, depth))
        self._record_dispatch(1)  # the aggregate kernel launch
        # Start all three device->host copies before reading any: the
        # copies pipeline, so total latency is ~one transfer round-trip
        # instead of three sequential ones (r2's 3x sum latency).
        _copy_async(cnt, pos, neg)

        def fold(cnt_host):
            count = int(cnt_host.astype(np.int64).sum())
            p = np.asarray(pos, dtype=np.int64).sum(axis=-1)
            n = np.asarray(neg, dtype=np.int64).sum(axis=-1)
            total = sum((1 << i) * (int(p[i]) - int(n[i]))
                        for i in range(depth))
            return total, count

        return self._wait(self.batcher.submit(cnt, fold))

    def execute_min_max(self, idx: Index, c: Call, shards: list[int],
                        is_min: bool):
        """Global (value, count) pre-base: every shard's extremum computed
        in one stacked program (the shape-polymorphic bit-serial descent of
        ops.bsi), host-folded with the reference's smaller/larger rule on
        the batcher's resolver thread."""
        if not shards:
            return (0, 0)
        shards = self._shards(shards)
        n_shards = len(shards)
        if self._fuse_agg_ok(c):
            fn, arrays, _ = self._prepare_agg(idx, c, shards,
                                              "minmax", is_min)
            _fuse.add_fused_steps(_fuse.call_steps(c))

            def fold_fused(host):
                cc, ac, av, bv = host
                return _fold_min_max(np.asarray(cc), np.asarray(ac),
                                     av, bv, n_shards, is_min)

            return self._wait(self.coalescer.dispatch(fn, arrays,
                                                      fold_fused))
        _, exists, sign, stack, filt, depth = self._bsi_inputs(idx, c, shards)
        cons_cnt, alt_cnt, a, b = _agg_min_max(exists, sign, stack, filt,
                                               depth, is_min)
        cons_cnt, alt_cnt, *flat = self._replicate_small(
            cons_cnt, alt_cnt, *a, *b)
        a, b = tuple(flat[:len(a)]), tuple(flat[len(a):])
        self._record_dispatch(1)  # the aggregate kernel launch
        # One pipelined transfer wave for all eight outputs (r2 paid ~8
        # sequential round-trips here: Min was 2.5x slower than Sum).
        _copy_async(cons_cnt, alt_cnt, *a, *b)

        def fold(cons_host):
            return _fold_min_max(cons_host, np.asarray(alt_cnt), a, b,
                                 n_shards, is_min)

        return self._wait(self.batcher.submit(cons_cnt, fold))

    # ------------------------------------------------------------------
    # approximate analytics (pilosa_tpu.sketch): Count(Distinct) as ONE
    # fused program — filter tree → masked register gather → segment-max
    # — and SimilarTopN as ONE program over the field's row cube. The
    # estimate itself (harmonic mean in float64) and the final ranking
    # run in the host fold; no row set ever leaves the device.
    # ------------------------------------------------------------------

    #: refuse to build a SimilarTopN row cube past this HBM footprint —
    #: the executor falls back to the per-shard host oracle instead.
    SIM_CUBE_MAX_BYTES = 1 << 30

    def supports_distinct(self, idx: Index, c: Call) -> bool:
        """True for Distinct calls whose (optional) filter child is a
        plannable bitmap tree over an existing BSI field."""
        if not self.sketch_supported or c.name != "Distinct":
            return False
        if len(c.children) > 1:
            return False
        if c.children and not self.supports(c.children[0]):
            return False
        field_name, ok = c.string_arg("field")
        if not ok:
            return False
        f = idx.field(field_name)
        return f is not None and f.bsi_group is not None

    def execute_distinct_registers(self, idx: Index, c: Call,
                                   shards: list[int], p: int) -> np.ndarray:
        """Merged uint8[2^p] HLL registers of the filtered column set
        across ``shards`` — one device dispatch."""
        return self._wait(self.dispatch_distinct(idx, c, shards, p))

    def dispatch_distinct(self, idx: Index, c: Call, shards: list[int],
                          p: int):
        """Async register fold: Future[uint8[2^p]]. Plans like the fused
        aggregates (shared plan cache, structural program cache); the
        unfiltered form reduces the cached [S, 2^p] register stack, the
        filtered form traces the filter tree into the same program as
        the masked plane gather."""
        from concurrent.futures import Future
        if not shards:
            fut: Future = Future()
            fut.set_result(np.zeros(1 << p, dtype=np.uint8))
            return fut
        fn, arrays = self._prepare_distinct(idx, c, shards, p)
        _fuse.add_fused_steps(_fuse.call_steps(c))

        def fold(host):
            return np.asarray(host, dtype=np.uint8)

        return self.coalescer.dispatch(fn, arrays, fold)

    def _prepare_distinct(self, idx: Index, c: Call, shards: list[int],
                          p: int):
        field_name, _ = c.string_arg("field")
        f = idx.field(field_name)
        depth = f.bsi_group.bit_depth
        shards = self._shards(shards)

        def build(leaves):
            if c.children:
                leaves.append(("hll", field_name, depth, p))
                filt_sig = self._signature(idx, c.children[0], leaves,
                                           shards)
            else:
                leaves.append(("hllreg", field_name, depth, p))
                filt_sig = None
            return self._compiled_distinct(
                ("distinct", p, depth, filt_sig), p, filt_sig)

        leaves, fn = self._plan_cached(idx, f"distinct{p}:{c}", shards,
                                       build)
        return fn, self._fetch_leaves(idx, leaves, shards)

    def _compiled_distinct(self, full_sig: tuple, p: int,
                           filt_sig) -> Callable:
        fn = self._fn_cache.get(full_sig)
        if fn is not None:
            return fn
        hll_expand = _residency.kernel(_residency.HLL, "expand")

        def program(*args):
            if filt_sig is None:
                # args[0]: the cached [S, 2^p] register stack.
                return jnp.max(args[0], axis=0)
            # args[0]: the packed [S, C] bucket|rho plane; the barrier
            # pins the filter tree as one shared value (same rationale
            # as _compiled_agg).
            filt = jax.lax.optimization_barrier(_eval_node(filt_sig, args))
            return jnp.max(hll_expand(args[0], filt, p), axis=0)

        fn = self._jit_program(_named(program, "hll_distinct"), None)
        self._fn_cache[full_sig] = fn
        self._register_fn(fn, full_sig, program)
        return fn

    def supports_similar(self, idx: Index, field_name: str,
                         filter_call: Call | None) -> bool:
        if not self.sketch_supported:
            return False
        if filter_call is not None and not self.supports(filter_call):
            return False
        return idx.field(field_name) is not None

    def execute_similar(self, idx: Index, field_name: str,
                        filter_call: Call, row_ids: list[int],
                        shards: list[int]):
        """One-dispatch row-vs-all similarity: (ids, overlap, selfcnt,
        filtcnt) with int64 host widening, or None when the candidate
        cube would blow the HBM gate (the executor's host oracle takes
        over). The filter tree traces INTO the program, so warm queries
        cost exactly one launch.

        No prepared-plan cache: a cached entry would pin a row-id
        universe that any Set() can grow, and _revalidate_plan only
        re-checks ``prow`` leaves — the structural _fn_cache still
        dedupes compiles by (padded R, filter shape)."""
        if not shards or not row_ids:
            return None
        shards = self._shards(shards)
        s_pad = self._pad(len(shards))
        r = len(row_ids)
        r_pad = max(8, 1 << (r - 1).bit_length())
        if r_pad * s_pad * WORDS_PER_SHARD * 4 > self.SIM_CUBE_MAX_BYTES:
            return None
        ids = tuple(int(x) for x in row_ids)
        leaves: list[tuple] = [("simtopn", field_name, ids, r_pad)]
        filt_sig = self._signature(idx, filter_call, leaves, shards)
        full_sig = ("simtopn", r_pad, filt_sig)
        fn = self._compiled_similar(full_sig, r_pad, filt_sig)
        arrays = self._fetch_leaves(idx, leaves, shards)
        _fuse.add_fused_steps(_fuse.call_steps(filter_call) + 1)
        ids_arr = np.asarray(ids, dtype=np.uint64)

        def fold(host):
            order, inter, selfc, filtc = host
            inter = np.asarray(inter).astype(np.int64)[:r]
            selfc = np.asarray(selfc).astype(np.int64)[:r]
            return (ids_arr, inter, selfc, int(filtc),
                    np.asarray(order)[:r])

        return self._wait(self.coalescer.dispatch(fn, arrays, fold))

    def _compiled_similar(self, full_sig: tuple, r_pad: int,
                          filt_sig) -> Callable:
        fn = self._fn_cache.get(full_sig)
        if fn is not None:
            return fn
        from pilosa_tpu.sketch import kernels as sketch_kernels
        sim = sketch_kernels.similar_program(r_pad)

        def program(*args):
            filt = jax.lax.optimization_barrier(_eval_node(filt_sig, args))
            return sim(args[0], filt)

        fn = self._jit_program(_named(program, "similar_topn"), None)
        self._fn_cache[full_sig] = fn
        self._register_fn(fn, full_sig, program)
        return fn

    # ------------------------------------------------------------------
    # TopN batched counts. Filterless: each fragment's generation-cached
    # sorted counts (O(results) repeat queries — the rankCache
    # replacement). Filtered, two routes chosen by the observed size:
    # a field whose candidate rows' dense stacks fit beside each other
    # (`_stacks_fit`) is counted by ONE program over those resident
    # stacks with the filter tree traced into it; a field of more rows
    # keeps ONE compiled filter tree over all shards, then each
    # fragment's two-tier count sweep (host membership for sparse rows,
    # tiled device popcounts for dense rows —
    # fragment.intersection_counts), where data motion tracks actual
    # set bits, not rows x shard-width.
    # ------------------------------------------------------------------

    def _stacks_fit(self, n_stacks: int, n_shards: int) -> bool:
        """Whether one call may hold ``n_stacks`` dense ``[S_pad, W]``
        stacks at once: it keeps strong references to all of them for
        its whole length, so LRU eviction cannot make room under it.
        Counted on one device, as the budget is."""
        return (n_stacks * self._dense_device_bytes(n_shards)
                <= min(self.max_cache_bytes, 2 << 30))

    def _dense_device_bytes(self, n_shards: int) -> int:
        """Bytes one dense stack over ``n_shards`` places on each device
        of the mesh: what the store charges it."""
        return _residency.dense_nbytes(self._pad(n_shards)) \
            // self.n_devices

    def _field_row_ids(self, idx: Index, field_name: str, view: str,
                       shards: ShardSet) -> np.ndarray:
        """Sorted uint64 ids of the rows ``field_name`` holds in any of
        ``shards``: one walk of the fragments an index epoch (each
        fragment's ids are generation-cached), read before the walk like
        `_leaf_class`'s stamp."""
        key = (idx.instance_id, field_name, view, shards)
        epoch = idx.epoch.value
        hit = self._field_rows.get(key)
        if hit is not None and hit[0] == epoch:
            return hit[1]
        per_frag = [frag.row_counts()[0] for frag in (
            self.holder.fragment(idx.name, field_name, view, shard)
            for shard in shards) if frag is not None]
        ids = (np.unique(np.concatenate(per_frag)) if per_frag
               else np.zeros(0, dtype=np.uint64))
        with self._plan_lock:
            self._field_rows[key] = (epoch, ids)
            while len(self._field_rows) > self.FIELD_ROWS_SIZE:
                self._field_rows.popitem(last=False)
        return ids

    def _count_topn_pass(self, stacked: bool, launches: int,
                         on_device: int, on_host: int) -> None:
        """Once a filtered pass, not once a launch: the programs it
        launched, the (row, fragment) pairs it counted on the device and
        on the host, and the route it took."""
        if self.stats is None:
            return
        for name, n in (("launches", launches),
                        ("rowsDeviceTier", on_device),
                        ("rowsHostTier", on_host),
                        ("passesStacked", int(stacked)),
                        ("passesSwept", int(not stacked))):
            self.stats.count("planner.topn." + name, n)

    def execute_topn_counts(self, idx: Index, field_name: str, view: str,
                            shards: list[int], filter_call: Call | None,
                            row_ids=None) -> TopNCounts:
        """Every shard's (ids, counts), each shard's SORTED by count desc
        / id asc, preserving per-fragment semantics (threshold filtering
        stays per shard in the executor, matching executeTopNShards
        merge semantics, executor.go:902)."""
        allowed = (np.asarray(sorted(set(int(r) for r in row_ids)),
                              dtype=np.uint64)
                   if row_ids is not None else None)
        out: dict[int, tuple] = {}
        filt = filt_host = None
        shards = self._shards(shards)
        if filter_call is not None:
            cands = self._field_row_ids(idx, field_name, view, shards)
            if allowed is not None:
                # an id no fragment holds counts 0 everywhere: no stack
                # is built for it
                cands = np.intersect1d(cands, allowed, assume_unique=True)
            if len(cands) and self._stacks_fit(len(cands), len(shards)):
                return self._topn_counts_stacked(
                    idx, field_name, view, shards, filter_call, cands)
            with start_span("topn.filter", stats=self.stats):
                # [S_pad, W]
                filt = self._tree_stack(idx, filter_call, shards)
                # ONE pull of the filter for every shard's sparse host tier
                # (per-shard pulls each cost a link round-trip), cached
                # across TopN's two passes (same filter, same epoch).
                fkey = (idx.name, idx.instance_id, str(filter_call),
                        shards, idx.epoch.value)
                with self._plan_lock:
                    hit = self._filter_host_cache.get(fkey)
                if hit is not None:
                    filt_host = hit
                else:
                    filt.copy_to_host_async()
                    filt_host = np.asarray(filt, dtype=np.uint32)
                    with self._plan_lock:
                        self._filter_host_cache[fkey] = filt_host
                        while len(self._filter_host_cache) > 4:
                            self._filter_host_cache.pop(
                                next(iter(self._filter_host_cache)))
                if self.n_devices > 1:
                    # The per-fragment sweep is a single-device program over
                    # row stacks on the default device, and a slice of the
                    # mesh-sharded stack spans every chip: the jit around the
                    # Pallas kernel would become an SPMD program, which the
                    # chip's compiler refuses ("Mosaic kernels cannot be
                    # automatically partitioned"; first met on four chips).
                    # ONE upload of the host copy puts every shard's segment
                    # where the fragments' stacks are.
                    filt = jax.device_put(filt_host)
        pending: list[tuple[int, np.ndarray, np.ndarray, list]] = []
        with (start_span("topn.sweep", stats=self.stats)
              if filt is not None else nullcontext()):
            for si, shard in enumerate(shards):
                frag = self.holder.fragment(idx.name, field_name, view, shard)
                if frag is None:
                    continue
                if filt is None:
                    ids, counts = frag.top_counts()  # cached sorted order
                    if allowed is not None and len(ids):
                        keep = np.isin(ids, allowed)
                        ids, counts = ids[keep], counts[keep]
                    if len(ids):
                        out[shard] = (ids, counts)
                    continue
                ids, _ = frag.row_counts()
                if allowed is not None and len(ids):
                    ids = ids[np.isin(ids, allowed, assume_unique=True)]
                if not len(ids):
                    continue
                counts, parts = frag.intersection_counts_async(
                    ids, filt[si], reuse=True, seg_host=filt_host[si])
                for _ in parts:  # one Pallas launch per dense tile
                    self._record_dispatch(1)
                futs = [(slots, self.batcher.submit(dev, lambda h: h))
                        for slots, dev in parts]
                pending.append((shard, ids, counts, futs))
        if filt is not None:
            # rows held dense are counted on the device, rows held as
            # positions (or empty) on the host
            rows = sum(len(ids) for _, ids, _, _ in pending)
            on_device = sum(len(slots) for _, _, _, futs in pending
                            for slots, _ in futs)
            self._count_topn_pass(
                False, sum(len(futs) for _, _, _, futs in pending),
                on_device, rows - on_device)
        # Resolve every shard's device tiles in one pipelined wave.
        with start_span("transfer.wait", stats=self.stats):
            for _, _, counts, futs in pending:
                for slots, fut in futs:
                    counts[slots] = np.asarray(fut.result(),
                                               dtype=np.int64)[:len(slots)]
        for shard, ids, counts, _ in pending:
            order = np.lexsort((ids, -counts))
            out[shard] = (ids[order], counts[order])
        return TopNCounts.from_shards(out)

    def _topn_counts_stacked(self, idx: Index, field_name: str, view: str,
                             shards: ShardSet, filter_call: Call,
                             cands: np.ndarray) -> TopNCounts:
        """One filtered pass as ONE program: the filter tree and the
        per-shard popcount of ``row AND filter`` for every candidate
        row, over the rows' resident dense stacks (fetched like any
        plan's leaves, so the store accounts for them, uploads them
        ahead and may evict them), whatever tier a fragment holds a row
        in. Every (row, shard) pair is an entry, those that count 0
        too: the executor keeps counts of at least one."""
        r = len(cands)
        # the row ids are arguments, the row count a power of two: fields
        # of 5 to 8 rows, and every filter of one shape, share a program
        r_pad = 1 << (r - 1).bit_length()
        with start_span("topn.filter", stats=self.stats):

            def build(leaves):
                filt_sig = self._signature(idx, filter_call, leaves, shards)
                return self._compiled_topn_counts(r_pad, filt_sig,
                                                  len(leaves))

            leaves, fn = self._plan_cached(
                idx, f"topn{r_pad}:{filter_call}", shards, build)
        with start_span("topn.sweep", stats=self.stats):
            # one fetch of the filter's leaves and the candidate rows: a
            # row that is both is one state of it
            arrays = self._fetch_leaves(
                idx, leaves + [("row", field_name, view, rid)
                               for rid in cands.tolist()], shards)
            # the padding slots count the last row again (no stack is
            # made or moved for them); their counts are cut off below
            arrays += arrays[-1:] * (r_pad - r)
            fut = self.coalescer.dispatch(fn, arrays, np.asarray)
        _fuse.add_fused_steps(_fuse.call_steps(filter_call) + 1)
        self._count_topn_pass(True, 1, r * len(shards), 0)
        counts = self._wait(fut)[:r, :len(shards)].T.astype(np.int64)
        # [S, R], every shard at once: a stable sort of the negated
        # counts keeps the candidates' ascending ids among equals
        order = np.argsort(-counts, axis=1, kind="stable")
        counts = np.take_along_axis(counts, order, axis=1)
        return TopNCounts(np.repeat(np.asarray(shards, dtype=np.int64), r),
                          cands[order].ravel(), counts.ravel(), stacked=True)

    def _compiled_topn_counts(self, r_pad: int, filt_sig: tuple,
                              n_filter: int) -> Callable:
        """``[r_pad, S_pad]`` int32 counts of ``row AND filter``: the
        filter tree's leaves come first (``filt_sig``'s slots), then
        ``r_pad`` dense row stacks. The rows stay separate arguments: a
        stacked cube of eight would copy 1 GiB a pass."""
        full_sig = ("topn_counts", r_pad, filt_sig)
        fn = self._fn_cache.get(full_sig)
        if fn is not None:
            return fn

        def program(*args):
            # the barrier pins the filter as one shared value for the
            # r_pad consumers (same rationale as _compiled_agg)
            filt = jax.lax.optimization_barrier(_eval_node(filt_sig, args))
            return jnp.stack([bitops.intersection_count(row, filt)
                              for row in args[n_filter:]])

        fn = self._jit_program(_named(program, "topn_counts"), None)
        self._fn_cache[full_sig] = fn
        # No raw program: passes of different filters must not form the
        # coalescer's [B, ...] wave, which would copy every row stack B
        # times; they launch one by one, and passes of the same filter
        # (the very same arrays) still share a launch.
        self._register_fn(fn, full_sig, None)
        return fn

    # ------------------------------------------------------------------
    # GroupBy: the per-shard DFS paid one device sync per (shard,
    # prefix); here ONE program counts every group over the levels'
    # resident [S, W] row stacks (and the filter tree's leaves), each
    # stack word read once (ops/pallas_kernels.group_counts). Reference:
    # executor.go:3058-3231 walks per-shard row iterators with per-pair
    # roaring intersections.
    # ------------------------------------------------------------------

    #: bound on the groups of a GroupBy through this path; beyond it the
    #: executor's memory-safe per-shard streaming path takes over.
    GROUP_BY_MAX_PAIRS = 8192

    def group_by_candidates(self, idx: Index, field_name: str,
                            shards: list[int]) -> list[int]:
        """Union of row ids present across the shard batch (host
        metadata walk, no device work)."""
        out: set[int] = set()
        for shard in shards:
            frag = self.holder.fragment(idx.name, field_name, VIEW_STANDARD,
                                        shard)
            if frag is not None:
                out.update(frag.row_ids())
        return sorted(out)

    def execute_group_by(self, idx: Index, fields: list[str],
                         cands: list[list[int]], shards: list[int],
                         filter_call: Call | None):
        """[(group_row_ids tuple, total_count), ...] in lexicographic
        group order, zero-count groups dropped. Returns None when the
        shape exceeds GROUP_BY_MAX_PAIRS or the rows' stacks do not fit
        (caller falls back)."""
        lens = [len(rows) for rows in cands]
        if math.prod(lens) > self.GROUP_BY_MAX_PAIRS or not shards:
            return None
        shards = self._shards(shards)
        # Memory bound, not just the group count: the program holds
        # every candidate row of every level. Row-heavy GroupBys keep
        # the per-shard streaming path, which is O(tile) in device
        # memory.
        if not self._stacks_fit(sum(lens), len(shards)):
            return None
        # each level's row count a power of two, as TopN's: fields of 5
        # to 8 rows share a program
        r_pads = tuple(1 << (n - 1).bit_length() for n in lens)
        with start_span("groupby.lattice", stats=self.stats):
            leaves: list[tuple] = []
            if filter_call is None:
                fn = self._compiled_group_counts(r_pads, None, 0)
            else:
                def build(leaves):
                    filt_sig = self._signature(idx, filter_call, leaves,
                                               shards)
                    return self._compiled_group_counts(r_pads, filt_sig,
                                                       len(leaves))

                leaves, fn = self._plan_cached(
                    idx, f"groupby{r_pads}:{filter_call}", shards, build)
            # one fetch, so the prefetch ahead of it covers every level
            arrays = self._fetch_leaves(
                idx, leaves + [("row", field, VIEW_STANDARD, r)
                               for field, rows in zip(fields, cands)
                               for r in rows], shards)
            args, pos = arrays[:len(leaves)], len(leaves)
            for n, r_pad in zip(lens, r_pads):
                # the padding slots repeat the level's last row (no stack
                # is made or moved for them); their groups are cut off
                # below
                level = arrays[pos:pos + n]
                args += level + level[-1:] * (r_pad - n)
                pos += n
            fut = self.coalescer.dispatch(fn, args, np.asarray)
        if self.stats is not None:
            # once a call, not once a launch
            self.stats.count("planner.groupby.launches", 1)
            self.stats.count("planner.groupby.groups", math.prod(lens))
        totals = _group_totals(self._wait(fut), lens, r_pads)
        return [(tuple(rows[i] for rows, i in zip(cands, grp)), cnt)
                for grp, cnt in zip(np.ndindex(*lens), totals.tolist())
                if cnt > 0]

    def _compiled_group_counts(self, r_pads: tuple, filt_sig,
                               n_filter: int) -> Callable:
        """``[S_pad / ts, prod(r_pads)]`` int32 group counts: the filter
        tree's leaves come first (``filt_sig``'s slots), then each
        level's ``r_pad`` dense row stacks. A filter tree of bitwise
        nodes is evaluated tile by tile inside the kernel; any other
        (shift, BSI, packed leaves) is held once as one stack. Each chip
        counts its own shard rows (shard_map: the chip's compiler does
        not partition a Mosaic kernel)."""
        full_sig = ("groupby_counts", r_pads, filt_sig)
        fn = self._fn_cache.get(full_sig)
        if fn is not None:
            return fn
        tiled = filt_sig is None or _bitwise(filt_sig)
        tile_sig = filt_sig if tiled else ("leaf", 0)

        def local(*args):
            n_leaves = n_filter if tiled else 1
            rows, levels = list(args[n_leaves:]), []
            for r_pad in r_pads:
                levels.append(rows[:r_pad])
                rows = rows[r_pad:]
            return pallas_kernels.group_counts(
                levels, args[:n_leaves],
                lambda tiles: _eval_node(tile_sig, tiles),
                interpret=pallas_kernels._interpret())

        def program(*args):
            leaves = args[:n_filter]
            if not tiled:
                leaves = (_eval_node(filt_sig, leaves),)
            ins = leaves + args[n_filter:]
            spec = PartitionSpec(SHARD_AXIS)
            return jax.shard_map(local, mesh=self.mesh,
                                 in_specs=(spec,) * len(ins),
                                 out_specs=spec, check_vma=False)(*ins)

        fn = self._jit_program(_named(program, "groupby_counts"),
                               "per_shard")
        self._fn_cache[full_sig] = fn
        # No raw program, as for topn_counts: calls over different rows
        # must not form the coalescer's [B, ...] wave, which would copy
        # every row stack B times.
        self._register_fn(fn, full_sig, None)
        return fn

    @property
    def max_cache_bytes(self) -> int:
        """The stack store's budget, in bytes a device."""
        return self.stacks.budget_bytes

    @max_cache_bytes.setter
    def max_cache_bytes(self, n: int) -> None:
        self.stacks.budget_bytes = n

    def invalidate(self) -> None:
        self.stacks.clear()
        with self._plan_lock:
            self._filter_host_cache.clear()
            self._plan_cache.clear()

    def drop_index(self, index_name: str) -> None:
        """Evict one index's entries from the stack/filter/plan caches.
        Compiled programs (`_fn_cache`) are structural — not tied to any
        index — and are kept; this is what lets the QoS warmup service
        discard its scratch index without losing the warmed kernels."""
        self.stacks.drop_index(index_name)
        with self._plan_lock:
            for key in [k for k in self._filter_host_cache
                        if k[0] == index_name]:
                del self._filter_host_cache[key]
            for key in [k for k in self._plan_cache if k[0] == index_name]:
                del self._plan_cache[key]

    def observed_traffic(self) -> list[dict]:
        """The structural query shapes this planner compiled for, oldest
        first — what ServerNode persists to warmup.json at shutdown so
        the next boot can precompile the programs real traffic hit."""
        with self._plan_lock:
            return [{"index": i, "query": q, "shards": s, "count": n}
                    for (i, q, s), n in self._observed.items()]

    def close(self) -> None:
        """Release caches and stop the upload workers + coalescer +
        batcher threads."""
        self.stacks.close()
        self.coalescer.close()
        self.invalidate()
        self.batcher.close()

    def cache_stats(self) -> dict:
        """Locked snapshot of HBM-cache occupancy for monitoring."""
        out = self.stacks.snapshot()
        out["bucket_policy"] = self.bucket_policy
        out["residency_mode"] = _residency.mode()
        out["programs"] = len(self._fn_cache)
        with self._dispatch_lock:
            out["dispatches"] = self.dispatches
            out["dispatches_coalesced"] = self.dispatches_coalesced
        return out

    def device_debug(self) -> dict:
        """The /debug/device payload's planner half: residency (per
        representation class), churn, the prefetch pipeline, compiled-
        program population, and the lifetime coalesce batch-width
        histogram."""
        out = self.cache_stats()
        with self._dispatch_lock:
            out["batch_width_hist"] = self._width_hist.snapshot()
        out["queue_depth"] = self.coalescer.queue_depth()
        out["transfer"] = self.batcher.debug()
        out["prefetch"] = self.stacks.upload_stats()
        # WHICH device: the platform is the whole proof that a kernel
        # ran compiled and not interpreted (ops/pallas_kernels), and the
        # per-device split shows whether stacks really spread over the
        # mesh or all sit on its first device.
        devices = list(self.mesh.devices.flat)
        out["platform"] = devices[0].platform
        out["deviceKind"] = devices[0].device_kind
        out["deviceCount"] = len(devices)
        out["perDeviceBytes"] = {**{str(d): 0 for d in devices},
                                 **self.stacks.per_device_bytes()}
        out["compileCache"] = compile_cache.stats()
        return out

    # ------------------------------------------------------------------
    # tree → structural signature + leaf list
    # ------------------------------------------------------------------

    def _leaf_class(self, idx: Index, field_name: str, view: str,
                    row_id: int, shards: tuple) -> str:
        """Representation class for one row stack: measure the largest
        per-shard cardinality (O(1) per fragment — HostRow maintains
        the count incrementally) and apply the residency policy
        (exec/residency.choose_class). Dense whenever the planner can't
        carry packed stacks (distributed mesh) or the knob is off."""
        if not (shards and self.residency_packed_supported
                and _residency.mode() != "off"):
            return _residency.DENSE
        # The walk visits every shard's fragment (954 lookups a leaf at
        # 1B columns), and a plan-cache miss asks for every leaf of its
        # tree: the measure is kept for as long as the index's epoch
        # stands. Read before the walk, so a write during it leaves a
        # stale stamp behind.
        key = (idx.instance_id, field_name, view, row_id, shards)
        epoch = idx.epoch.value
        hit = self._leaf_bits.get(key)
        if hit is not None and hit[0] == epoch:
            return _residency.choose_class(hit[1])
        max_bits = 0
        for shard in shards:
            frag = self.holder.fragment(idx.name, field_name, view, shard)
            if frag is not None:
                n = frag.row_cardinality(row_id)
                if n > max_bits:
                    max_bits = n
        with self._plan_lock:
            self._leaf_bits[key] = (epoch, max_bits)
            while len(self._leaf_bits) > self.LEAF_BITS_SIZE:
                self._leaf_bits.popitem(last=False)
        return _residency.choose_class(max_bits)

    def _signature(self, idx: Index, c: Call, leaves: list[tuple],
                   shards: tuple = ()) -> tuple:
        """DFS the call tree, appending leaf specs and returning a
        hashable structure key. Leaf position in `leaves` is its input
        slot in the compiled function. ``shards`` lets standard row
        leaves choose their representation class by measured
        cardinality — a packed leaf appends a ``prow`` descriptor and
        signs as ``pleaf``, so the class is part of the structural
        signature and compiled programs specialize per class."""
        name = c.name
        if name in ("Row", "Range"):
            if c.has_condition_arg():
                return self._bsi_signature(idx, c, leaves)
            field_name = c.field_arg()
            f = idx.field(field_name)
            if f is None:
                raise FieldNotFoundError(f"field not found: {field_name!r}")
            row_val = c.args.get(field_name)
            if isinstance(row_val, bool):
                row_id = 1 if row_val else 0
            else:
                row_id, ok = c.uint_arg(field_name)
                if not ok:
                    raise QueryError("Row() must specify row")
            from_time = tq.parse_time(c.args["from"]) if "from" in c.args else None
            to_time = tq.parse_time(c.args["to"]) if "to" in c.args else None
            if name == "Row" and from_time is None and to_time is None:
                if self._leaf_class(idx, field_name, VIEW_STANDARD, row_id,
                                    shards) == _residency.PACKED:
                    leaves.append(("prow", field_name, VIEW_STANDARD,
                                   row_id))
                    return ("pleaf", len(leaves) - 1)
                leaves.append(("row", field_name, VIEW_STANDARD, row_id))
            else:
                q = f.time_quantum()
                if not q:
                    leaves.append(("zero",))
                    return ("leaf", len(leaves) - 1)
                leaves.append(("row_time", field_name, row_id,
                               from_time, to_time, q))
            return ("leaf", len(leaves) - 1)
        if name == "Not":
            if len(c.children) != 1:
                raise QueryError("Not() requires a single row input")
            ef = idx.existence_field()
            if ef is None:
                raise QueryError(
                    f"index does not support existence tracking: {idx.name}")
            leaves.append(("row", ef.name, VIEW_STANDARD, 0))
            slot = len(leaves) - 1
            child = self._signature(idx, c.children[0], leaves, shards)
            return ("not", slot, child)
        if name == "Shift":
            n = c.args.get("n", 0)  # IntArg default, executor.go:1770
            child = self._signature(idx, c.children[0], leaves, shards)
            return ("shift", n, child)
        if name in ("Intersect", "Union", "Xor", "Difference"):
            if not c.children:
                raise QueryError(f"empty {name} query is currently not supported")
            kids = tuple(self._signature(idx, ch, leaves, shards)
                         for ch in c.children)
            return (name.lower(), kids)
        if name == "__const__":
            # Partial-fusion leaf: a host-computed Row injected as a
            # device stack (Executor._fuse_partial). Plans with const
            # leaves bypass the text-keyed plan cache (same str(c),
            # different contents) but share the structural program cache.
            leaves.append(("const", c.args["slot"]))
            return ("leaf", len(leaves) - 1)
        raise QueryError(f"unsupported planner call: {name}")

    def _bsi_signature(self, idx: Index, c: Call, leaves: list[tuple]) -> tuple:
        """BSI condition → signature with STATIC branch structure (operator,
        sign class, depth) and TRACED predicate magnitudes — one compiled
        program per operator shape, reused across literals."""
        (field_name, cond), = c.args.items()
        if not isinstance(cond, Condition):
            raise QueryError("Row(): expected condition argument")
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(f"field not found: {field_name!r}")
        bsig = f.bsi_group
        if bsig is None:
            raise BSIGroupNotFoundError()
        depth = bsig.bit_depth
        leaves.append(("bsi", field_name, depth))
        slot = len(leaves) - 1

        def pred(v: int) -> int:
            leaves.append(("pred", abs(v)))
            return len(leaves) - 1

        # Fold base/range handling — mirrors executor._row_bsi_shard
        # (reference executor.go:1536-1663).
        if cond.op == NEQ and cond.value is None:
            return ("bsi_notnull", slot)
        if cond.op == BETWEEN:
            lo_hi = cond.int_slice_value()
            if len(lo_hi) != 2:
                raise QueryError("Row(): BETWEEN condition requires exactly "
                                 "two integer values")
            lo, hi, oor = bsig.base_value_between(*lo_hi)
            if oor:
                return ("bsi_zero", slot)
            if lo_hi[0] <= bsig.min and lo_hi[1] >= bsig.max:
                return ("bsi_notnull", slot)
            # Sign-class split of rangeBetween (fragment.go:1457).
            if lo >= 0:
                return ("bsi_between", slot, depth, "pos", pred(lo), pred(hi))
            if hi < 0:
                return ("bsi_between", slot, depth, "neg", pred(lo), pred(hi))
            return ("bsi_between", slot, depth, "cross", pred(lo), pred(hi))
        value = cond.value
        if not isinstance(value, int) or isinstance(value, bool):
            raise QueryError("Row(): conditions only support integer values")
        base_value, oor = bsig.base_value(cond.op, value)
        if oor and cond.op != NEQ:
            return ("bsi_zero", slot)
        if ((cond.op == pql_ast.LT and value > bsig.max)
                or (cond.op == pql_ast.LTE and value >= bsig.max)
                or (cond.op == pql_ast.GT and value < bsig.min)
                or (cond.op == pql_ast.GTE and value <= bsig.min)
                or (oor and cond.op == NEQ)):
            return ("bsi_notnull", slot)
        if cond.op in (pql_ast.EQ, pql_ast.NEQ):
            kind = "bsi_eq" if cond.op == pql_ast.EQ else "bsi_neq"
            return (kind, slot, depth, base_value < 0, pred(base_value))
        allow_eq = cond.op in (pql_ast.LTE, pql_ast.GTE)
        # Positive-branch predicate classes of rangeLT/rangeGT
        # (fragment.go:1332, :1404).
        branch_pos = ((base_value >= 0 and allow_eq)
                      or (base_value >= -1 and not allow_eq))
        kind = "bsi_lt" if cond.op in (pql_ast.LT, pql_ast.LTE) else "bsi_gt"
        return (kind, slot, depth, allow_eq, branch_pos, pred(base_value))

    # ------------------------------------------------------------------
    # leaf fetch: host rows → sharded [S, W] device stacks
    # ------------------------------------------------------------------

    def _pad(self, s: int) -> int:
        """Stack height for ``s`` shards. Always a multiple of
        n_devices (mesh layout contract); under the default "pow2"
        bucket policy the per-device multiple also rounds up to the
        next power of two, collapsing the space of distinct [S_pad, W]
        program shapes to O(log S). Padding rows are zero blocks —
        bit-identical results, because every consumer either sums
        popcounts (zero rows contribute 0) or slices only the real
        shard slots (execute_bitmap, the Min/Max host fold, TopN)."""
        s_pad = pad_to_multiple(s, self.n_devices)
        if self.bucket_policy == "pow2" and s_pad > 0:
            m = s_pad // self.n_devices
            s_pad = (1 << (m - 1).bit_length()) * self.n_devices
        return s_pad

    def _gens(self, index_name: str, field_name: str, view: str,
              shards: tuple) -> tuple:
        out = []
        for shard in shards:
            frag = self.holder.fragment(index_name, field_name, view, shard)
            out.append(-1 if frag is None else frag.generation)
        return tuple(out)

    def _stack_rows(self, idx: Index, field_name: str, view: str, row_id: int,
                    shards: tuple,
                    klass: str = _residency.DENSE) -> jax.Array:
        """Stack of one row across shards, device-put with the shard
        sharding; resident until any involved fragment mutates
        (parallel.stacks has the validation, the budget and the
        rendezvous with an upload in flight). ``klass`` picks the
        representation: dense [S_pad, W] uint32 planes or a packed
        [S_pad, K] int32 index stack (exec/residency) — each class is
        its own entry, under the shared budget."""
        key = StackKey(idx.name, idx.instance_id, field_name, view, row_id,
                       shards, klass)
        # The resident hit makes no closure.
        arr = self.stacks.get(key, idx.epoch.value)
        if arr is not None:
            return arr
        build = (self._build_stack_packed if klass == _residency.PACKED
                 else self._build_stack)
        return self._resident(
            idx, key,
            functools.partial(build, idx, field_name, view, row_id, shards),
            staged=True)

    def _resident(self, idx: Index, key: StackKey, build: Callable, **how):
        """``key``'s array from the store, validated against the index's
        epoch and the generations of the key's fragments; ``build`` and
        ``how`` as `StackStore.get_or_build` takes them."""
        return self.stacks.get_or_build(
            key, idx.epoch.value,
            functools.partial(self._gens, key.index, key.field, key.view,
                              key.shards),
            build, **how)

    #: rows with at most this many set bits upload as COO triplets
    #: (~12 B/word touched) instead of the 128 KiB dense block. The
    #: threshold is not measured on the current machine. What is
    #: (PERF.md, PR 28 and 29, `count-trees-oversub`): the transfer call
    #: of a 128 MiB stack returns in 0.001-0.003 s, and the host build
    #: before it, not the bytes on the link, set the cold and
    #: oversubscribed query rate (0.43 s a stack, 0.05 s built in place).
    SPARSE_UPLOAD_MAX_BITS = 2048

    def _sparse_upload_enabled(self) -> bool:
        """Sparse COO uploads pay off where host->device transfers are
        expensive (a real accelerator); on the CPU test mesh a
        device_put is a memcpy and the scatter program would only add
        compiles."""
        return jax.default_backend() == "tpu"

    def _build_stack(self, idx: Index, field_name: str, view: str,
                     row_id: int,
                     shards: tuple) -> tuple[Callable[[], jax.Array], int]:
        """Materialize one row across ``shards`` on the host (the
        fragment walk: ``stack.build``) and return (upload, nbytes):
        ``upload()`` makes the transfer call that yields the device
        ``[S_pad, W]`` stack (``stack.upload``). The stack is built in
        place, in one zeroed host matrix from the recycled page pool,
        with ONE native call a stack. *Gather*, one pass over the
        shards under the interpreter lock: each fragment says, under
        its own lock, what its row is (`Fragment.row_source`: the
        set-bit count and a reference to the sorted position array,
        which nobody writes in place). *Scatter*, outside it: the
        native library ORs every referenced array into its row of the
        matrix in one call (`native.or_positions_into_rows`), so a
        build hands the interpreter lock over once, not once a shard
        beside whichever request thread runs Python (PERF.md §6, PR 32
        and 34); with no native library the same call writes the
        rows one by one through numpy. A row held as a dense block is
        written in place by writers, so it is copied under its
        fragment's lock (`Fragment.row_words_into`). With
        `_sparse_upload_enabled`, rows of at most
        `SPARSE_UPLOAD_MAX_BITS` ship as COO word triplets and scatter
        into zeros on device. ``planner.stackRows.<route>`` counts the
        non-empty rows by route (scattered, copied, numpy, coo),
        ``planner.stackBuilds.native|perRow`` the builds by whether the
        library was there to take the one call.
        Overridden by the distributed planner to assemble a global
        array from each process's local fragment rows
        (jax.make_array_from_single_device_arrays)."""
        s_pad = self._pad(len(shards))
        nbytes = _residency.dense_nbytes(s_pad)  # HBM-resident size
        coo_max = (self.SPARSE_UPLOAD_MAX_BITS
                   if self._sparse_upload_enabled() else 0)
        # (i, fragment, positions): rows that take 128 KiB; positions is
        # None for a dense row, which its fragment writes itself.
        blocks: list[tuple] = []
        coo: list[tuple] = []
        field = self.holder.field(idx.name, field_name)
        v = None if field is None else field.view(view)
        for i, shard in enumerate(shards):
            frag = None if v is None else v.fragment(shard)
            if frag is None:
                continue
            n, pos = frag.row_source(row_id)
            if not n:
                continue
            if n <= coo_max:
                coo.append((i, frag))
            else:
                blocks.append((i, frag, pos))
        # Pad the assemble program's inputs to pow2 buckets so that it
        # compiles O(log) distinct shapes, not one per leaf; padding
        # lands in a sacrificial trash row the program slices off.
        def bucket(n: int) -> int:
            return 0 if n == 0 else max(8, 1 << (n - 1).bit_length())

        # No sparse rows to scatter: the matrix is the stack, row i at
        # mat[i], and the plain host-sliced device_put beats shipping
        # the same bytes through the assemble program. Otherwise the
        # 128 KiB rows go packed: mat[k] is stack row didx[k].
        mat = _host_zeros(bucket(len(blocks)) if coo else s_pad)
        routes = Counter(coo=len(coo))
        sources: list[np.ndarray] = []
        rows: list[int] = []
        for k, (i, frag, pos) in enumerate(blocks):
            dst = k if coo else i
            if pos is None:
                routes[frag.row_words_into(row_id, mat[dst])] += 1
            else:
                sources.append(pos)
                rows.append(dst)
        one_call = native.or_positions_into_rows(sources, mat, rows)
        routes["scattered" if one_call else "numpy"] += len(sources)
        if self.stats is not None:
            # One count per route and build, not per shard. A row
            # emptied since the gather came back as None.
            self.stats.count("planner.stackBuilds."
                             + ("native" if one_call else "perRow"))
            for route, n in routes.items():
                if route is not None and n:
                    self.stats.count(f"planner.stackRows.{route}", n)
        if not coo:
            return self._put(mat), nbytes
        didx = np.full(len(mat), s_pad, dtype=np.int32)
        didx[:len(blocks)] = [i for i, _, _ in blocks]
        coo_i: list[np.ndarray] = []
        coo_w: list[np.ndarray] = []
        coo_v: list[np.ndarray] = []
        for i, frag in coo:
            pos = frag.row_positions(row_id)
            w = (pos >> np.uint64(5)).astype(np.int32)
            b = np.uint32(1) << (pos & np.uint64(31)).astype(np.uint32)
            # positions are sorted, so equal words are adjacent:
            # one reduceat OR per distinct word.
            starts = np.flatnonzero(np.diff(w, prepend=np.int32(-1)) != 0)
            coo_i.append(np.full(len(starts), i, dtype=np.int32))
            coo_w.append(w[starts])
            coo_v.append(np.bitwise_or.reduceat(b, starts))
        nnz = sum(len(x) for x in coo_i)
        n_pad = bucket(nnz)
        ci = np.full(n_pad, s_pad, dtype=np.int32)
        cw = np.zeros(n_pad, dtype=np.int32)
        cv = np.zeros(n_pad, dtype=np.uint32)
        ci[:nnz] = np.concatenate(coo_i)
        cw[:nnz] = np.concatenate(coo_w)
        cv[:nnz] = np.concatenate(coo_v)
        # The per-mesh jit scatters DIRECTLY into the sharded layout
        # (out_shardings): materializing the whole stack on one device
        # and resharding would spike that device's HBM by the full
        # stack size.
        return functools.partial(self._assemble_jit, didx, mat, ci, cw, cv,
                                 s_pad=s_pad), nbytes

    def _put(self, mat: np.ndarray) -> Callable[[], jax.Array]:
        """The deferred ``device_put`` of a host stack with the shard
        sharding."""
        return functools.partial(jax.device_put, mat, shard_spec(self.mesh))

    def _build_stack_packed(
            self, idx: Index, field_name: str, view: str, row_id: int,
            shards: tuple) -> tuple[Callable[[], jax.Array], int]:
        """Materialize one low-cardinality row as a packed [S_pad, K]
        int32 stack of sorted in-shard column indices, sentinel-padded
        (exec/residency): K is the pow2 bucket of the largest per-shard
        cardinality, so both the upload and the HBM residency cost
        ~4 B/set bit instead of the 128 KiB dense block. Rows that grew
        past the packing ceiling since plan time still build correctly
        (just bloated) — the plan revalidation drops the packed plan at
        the next epoch move."""
        s_pad = self._pad(len(shards))
        rows: list[tuple[int, np.ndarray]] = []
        max_bits = 0
        for i, shard in enumerate(shards):
            frag = self.holder.fragment(idx.name, field_name, view, shard)
            if frag is None:
                continue
            pos = frag.row_positions(row_id)
            if len(pos):
                rows.append((i, pos))
                if len(pos) > max_bits:
                    max_bits = len(pos)
        k = _residency.pack_width(max_bits)
        mat = np.full((s_pad, k), _residency.SENTINEL, dtype=np.int32)
        for i, pos in rows:
            mat[i, :len(pos)] = pos.astype(np.int32)
        return self._put(mat), _residency.packed_nbytes(s_pad, k)

    def _leaf_stack_specs(self, idx: Index, leaves: list, shards: tuple):
        """Expand leaf descriptors to the (field, view, row_id, class)
        stacks execution will fetch — the plan-wide peek that lets the
        miss path run ahead of the program. Mirrors _fetch_leaf's
        resolution (BSI exists/sign/magnitude planes, time-range view
        fan-out); zero/const/pred leaves have nothing to upload."""
        from pilosa_tpu.core.fragment import (
            BSI_EXISTS_BIT, BSI_OFFSET_BIT, BSI_SIGN_BIT,
        )
        for leaf in leaves:
            kind = leaf[0]
            if kind in ("row", "prow"):
                _, field_name, view, row_id = leaf
                klass = (_residency.PACKED if kind == "prow"
                         else _residency.DENSE)
                yield field_name, view, row_id, klass
            elif kind in ("bsi", "bsiagg"):
                _, field_name, depth = leaf
                view = view_bsi_name(field_name)
                yield field_name, view, BSI_EXISTS_BIT, _residency.DENSE
                yield field_name, view, BSI_SIGN_BIT, _residency.DENSE
                for i in range(depth):
                    yield (field_name, view, BSI_OFFSET_BIT + i,
                           _residency.DENSE)
            elif kind == "row_time":
                _, field_name, row_id, from_time, to_time, q = leaf
                f = idx.field(field_name)
                if f is None:
                    continue
                if to_time is None:
                    import datetime as dt
                    to_time = dt.datetime.now() + dt.timedelta(days=1)
                if from_time is None:
                    from_time, _ = f._time_view_bounds()
                    if from_time is None:
                        continue
                for view_name in tq.views_by_time_range(
                        VIEW_STANDARD, from_time, to_time, q):
                    if f.view(view_name) is not None:
                        yield field_name, view_name, row_id, _residency.DENSE

    def _prefetch_leaves(self, idx: Index, leaves: list,
                         shards: tuple) -> None:
        """Peek the plan's full leaf set before execution and schedule
        an upload for every stack that is not resident and current, so
        the request's later fetches wait on uploads already in flight
        instead of starting their own."""
        if not (shards and self.stacks.uploads_ahead):
            return
        epoch = idx.epoch.value
        for field_name, view, row_id, klass in self._leaf_stack_specs(
                idx, leaves, shards):
            self.stacks.schedule(
                StackKey(idx.name, idx.instance_id, field_name, view, row_id,
                         shards, klass),
                epoch, self._stack_rows, idx, field_name, view, row_id,
                shards, klass)

    def _zeros_stack(self, n_shards: int) -> jax.Array:
        s_pad = self._pad(n_shards)
        return jax.device_put(
            np.zeros((s_pad, WORDS_PER_SHARD), dtype=np.uint32),
            shard_spec(self.mesh))

    # small-output hooks: the distributed planner re-shards device
    # outputs to fully-replicated before any host read, so every process
    # of the mesh can resolve them locally.
    def _replicate_small(self, *arrays):
        return arrays

    def _fetch_leaf(self, idx: Index, leaf: tuple, shards: tuple,
                    const_rows: list | None = None):
        kind = leaf[0]
        if kind == "zero":
            return self._zeros_stack(len(shards))
        if kind == "const":
            # Host-computed Row (partial fusion) uploaded as a [S_pad, W]
            # stack; not cached — contents vary per query even when the
            # plan text doesn't.
            row = const_rows[leaf[1]]
            s_pad = self._pad(len(shards))
            mat = np.zeros((s_pad, WORDS_PER_SHARD), dtype=np.uint32)
            for i, shard in enumerate(shards):
                seg = row.segments.get(shard)
                if seg is not None:
                    mat[i] = np.asarray(seg, dtype=np.uint32)
            return jax.device_put(mat, shard_spec(self.mesh))
        if kind == "pred":
            lo, hi = bsi_ops.split_u64(leaf[1])
            return (np.uint32(lo), np.uint32(hi))
        if kind == "row":
            _, field_name, view, row_id = leaf
            return self._stack_rows(idx, field_name, view, row_id, shards)
        if kind == "prow":
            # Packed residency: [S_pad, K] sorted index stack; the
            # compiled program's pleaf node expands or counts it with
            # the class's kernel variants (exec/residency.KERNELS).
            _, field_name, view, row_id = leaf
            return self._stack_rows(idx, field_name, view, row_id, shards,
                                    klass=_residency.PACKED)
        if kind == "row_time":
            _, field_name, row_id, from_time, to_time, q = leaf
            f = idx.field(field_name)
            if to_time is None:
                import datetime as dt
                to_time = dt.datetime.now() + dt.timedelta(days=1)
            if from_time is None:
                lo, _ = f._time_view_bounds()
                if lo is None:
                    return self._fetch_leaf(idx, ("zero",), shards)
                from_time = lo
            acc = None
            for view_name in tq.views_by_time_range(VIEW_STANDARD, from_time,
                                                    to_time, q):
                if f.view(view_name) is None:
                    continue
                stack = self._stack_rows(idx, field_name, view_name, row_id,
                                         shards)
                acc = stack if acc is None else _jit_or(acc, stack)
            if acc is None:
                return self._fetch_leaf(idx, ("zero",), shards)
            return acc
        if kind == "bsi":
            _, field_name, depth = leaf
            view = view_bsi_name(field_name)
            from pilosa_tpu.core.fragment import (
                BSI_EXISTS_BIT, BSI_OFFSET_BIT, BSI_SIGN_BIT,
            )
            exists = self._stack_rows(idx, field_name, view, BSI_EXISTS_BIT,
                                      shards)
            sign = self._stack_rows(idx, field_name, view, BSI_SIGN_BIT,
                                    shards)
            bits = [self._stack_rows(idx, field_name, view,
                                     BSI_OFFSET_BIT + i, shards)
                    for i in range(depth)]
            return (exists, sign, bits)
        if kind == "bsiagg":
            # Fused-aggregate leaf: same exists/sign, but the magnitude
            # planes come as ONE cached [depth, S_pad, W] cube so the
            # fused program is exactly filter + reduce — stacking the
            # planes (the most expensive prep step) happens once per
            # (field, shards, epoch), not once per query.
            _, field_name, depth = leaf
            view = view_bsi_name(field_name)
            from pilosa_tpu.core.fragment import (
                BSI_EXISTS_BIT, BSI_SIGN_BIT,
            )
            exists = self._stack_rows(idx, field_name, view, BSI_EXISTS_BIT,
                                      shards)
            sign = self._stack_rows(idx, field_name, view, BSI_SIGN_BIT,
                                    shards)
            cube = self._stack_planes(idx, field_name, depth, shards)
            return (exists, sign, cube)
        if kind == "hll":
            # Filtered-distinct leaf: packed [S_pad, C] bucket|rho<<18
            # column plane (sketch/store), cached like any stack.
            _, field_name, depth, p = leaf
            return self._stack_hll_planes(idx, field_name, depth, p, shards)
        if kind == "hllreg":
            # Unfiltered-distinct leaf: [S_pad, 2^p] uint8 register
            # stack — 2^p bytes per shard resident instead of 4 MiB.
            _, field_name, depth, p = leaf
            return self._stack_hll_registers(idx, field_name, depth, p,
                                             shards)
        if kind == "simtopn":
            _, field_name, row_ids, r_pad = leaf
            return self._stack_row_cube(idx, field_name, row_ids, r_pad,
                                        shards)
        raise QueryError(f"unknown leaf kind {kind!r}")

    def _stack_planes(self, idx: Index, field_name: str, depth: int,
                      shards: tuple) -> jax.Array:
        """[depth, S_pad, W] cube of a BSI field's magnitude planes,
        stacked once and cached with the same two-tier (epoch, then
        per-fragment generation) validation as _stack_rows."""
        view = view_bsi_name(field_name)

        def build() -> jax.Array:
            from pilosa_tpu.core.fragment import BSI_OFFSET_BIT
            bits = [self._stack_rows(idx, field_name, view,
                                     BSI_OFFSET_BIT + i, shards)
                    for i in range(depth)]
            if bits:
                return jnp.stack(bits, axis=0)
            zero = self._fetch_leaf(idx, ("zero",), shards)
            return jnp.zeros((0,) + zero.shape, zero.dtype)

        # count_upload=False: the cube is stacked from already-uploaded
        # (and upload-counted) per-plane rows — no new link traffic.
        return self._resident(
            idx, StackKey(idx.name, idx.instance_id, field_name, view,
                          ("planes", depth), shards, _residency.DENSE),
            build, count_upload=False)

    def _hll_stack(self, idx: Index, field_name: str, tag: tuple,
                   shards: tuple, build) -> jax.Array:
        """The sketch stacks, keyed under the ``hll`` representation
        class so /debug/device accounts their HBM separately."""
        view = view_bsi_name(field_name)
        return self._resident(
            idx, StackKey(idx.name, idx.instance_id, field_name, view, tag,
                          shards, _residency.HLL),
            functools.partial(build, view))

    def _stack_hll_planes(self, idx: Index, field_name: str, depth: int,
                          p: int, shards: tuple) -> jax.Array:
        """[S_pad, SHARD_WIDTH] int32 packed bucket|rho column planes."""
        from pilosa_tpu.sketch import store as sketch_store

        def build(view: str) -> jax.Array:
            s_pad = self._pad(len(shards))
            mat = np.zeros((s_pad, SHARD_WIDTH), dtype=np.int32)
            for i, shard in enumerate(shards):
                frag = self.holder.fragment(idx.name, field_name, view,
                                            shard)
                if frag is not None:
                    mat[i] = sketch_store.plane(frag, depth, p)
            return jax.device_put(mat, shard_spec(self.mesh))

        return self._hll_stack(idx, field_name, ("hll", depth, p), shards,
                               build)

    def _stack_hll_registers(self, idx: Index, field_name: str, depth: int,
                             p: int, shards: tuple) -> jax.Array:
        """[S_pad, 2^p] uint8 per-shard register files (zero padding
        rows are the register-max identity)."""
        from pilosa_tpu.sketch import store as sketch_store

        def build(view: str) -> jax.Array:
            s_pad = self._pad(len(shards))
            mat = np.zeros((s_pad, 1 << p), dtype=np.uint8)
            for i, shard in enumerate(shards):
                frag = self.holder.fragment(idx.name, field_name, view,
                                            shard)
                if frag is not None:
                    mat[i] = sketch_store.registers(frag, depth, p)
            return jax.device_put(mat, shard_spec(self.mesh))

        return self._hll_stack(idx, field_name, ("hllreg", depth, p),
                               shards, build)

    def _stack_row_cube(self, idx: Index, field_name: str,
                        row_ids: tuple, r_pad: int,
                        shards: tuple) -> jax.Array:
        """[r_pad, S_pad, W] cube of every candidate row's dense stack
        (SimilarTopN), stacked from the per-row cached stacks and
        cached itself under the same validation; zero padding rows rank
        with overlap 0 and are sliced off in the host fold."""
        def build() -> jax.Array:
            bits = [self._stack_rows(idx, field_name, VIEW_STANDARD, rid,
                                     shards)
                    for rid in row_ids]
            zero = self._zeros_stack(len(shards))
            bits.extend(zero for _ in range(r_pad - len(bits)))
            return jnp.stack(bits, axis=0)

        # count_upload=False: stacked from already-counted row uploads.
        return self._resident(
            idx, StackKey(idx.name, idx.instance_id, field_name,
                          VIEW_STANDARD, ("simcube", row_ids, r_pad), shards,
                          _residency.DENSE),
            build, count_upload=False)

    # ------------------------------------------------------------------
    # compile: signature → jitted evaluator
    # ------------------------------------------------------------------

    def _compiled(self, full_sig: tuple, sig: tuple, n_leaves: int,
                  reduce: str | None) -> Callable:
        """Compile a signature to its jitted program. ``sig`` is the
        caller's already-walked signature — passing it (instead of
        re-walking the tree) keeps the program and the leaf list from
        ever disagreeing about a leaf's representation class. The
        program is named ``count_tree_<leaves>`` / ``bitmap_tree_<leaves>``
        (see _named)."""
        fn = self._fn_cache.get(full_sig)
        if fn is not None:
            return fn

        def evaluate(args):
            with jax.named_scope("tree_eval"):
                return _eval_node(sig, args)

        if reduce == "per_shard":
            program = _packed_count_program(sig)
            if program is None:
                def program(*args):
                    tree = evaluate(args)
                    with jax.named_scope("popcount_reduce"):
                        return bitops.count(tree)
        else:
            def program(*args):
                return evaluate(args)

        klass = "count_tree" if reduce == "per_shard" else "bitmap_tree"
        fn = self._jit_program(_named(program, f"{klass}_{n_leaves}"),
                               reduce)
        self._fn_cache[full_sig] = fn
        self._register_fn(fn, full_sig, program)
        return fn

    def _jit_program(self, program: Callable, reduce: str | None) -> Callable:
        """jit hook: the distributed planner replicates ``per_shard``
        count outputs across the mesh so any process can host-read."""
        return jax.jit(program)


def _named(program: Callable, name: str) -> Callable:
    """Name a device program by its CLASS (and leaf count), never by
    row ids or a fingerprint: XLA calls the module ``jit_<name>``, which
    is what a device trace shows, and the module's name is part of the
    persistent compile cache's key, so the set of compiled programs
    stays what the structural signatures make it."""
    program.__name__ = program.__qualname__ = name
    return program


def _packed_count_program(sig: tuple):
    """Count fast paths for packed leaves — the kernel variants the
    representation classes were built for (exec/residency.KERNELS): a
    bare packed leaf counts its indices without ever expanding
    (popcount-over-indices); a 2-leaf Intersect picks sparse∧dense or
    sparse∧sparse, so data motion tracks set bits, not shard width.
    None for every other shape — the generic expand+popcount program
    is still bit-identical, just dense-rate."""
    if sig[0] == "pleaf":
        count = _residency.kernel(_residency.PACKED, "count")
        slot = sig[1]
        return lambda *args: count(args[slot])
    if sig[0] == "intersect" and len(sig) == 2 and len(sig[1]) == 2:
        a, b = sig[1]
        if a[0] == "pleaf" and b[0] == "pleaf":
            pair = _residency.kernel(_residency.PACKED, "pair_count")
            return lambda *args: pair(args[a[1]], args[b[1]])
        if a[0] == "pleaf" and b[0] == "leaf":
            and_count = _residency.kernel(_residency.PACKED, "and_count")
            return lambda *args: and_count(args[a[1]], args[b[1]])
        if a[0] == "leaf" and b[0] == "pleaf":
            and_count = _residency.kernel(_residency.PACKED, "and_count")
            return lambda *args: and_count(args[b[1]], args[a[1]])
    return None


def _eval_node(sig: tuple, args) -> jax.Array:
    """Recursively evaluate a signature node against leaf input arrays.
    Runs under jit: everything here is traced XLA ops on [S, W] blocks."""
    kind = sig[0]
    if kind == "leaf":
        return args[sig[1]]
    if kind == "pleaf":
        # Packed leaf in a general tree: expand the [S, K] index stack
        # to dense planes INSIDE the program — HBM residency stays
        # packed, the bitmap algebra stays dense and unchanged.
        return _residency.kernel(_residency.PACKED, "expand")(args[sig[1]])
    if kind == "not":
        _, slot, child = sig
        existence = args[slot]
        return bitops.b_andnot(existence, _eval_node(child, args))
    if kind == "shift":
        _, n, child = sig
        return bitops.shift_left(_eval_node(child, args), n)
    if kind in ("intersect", "union", "xor", "difference"):
        kids = [_eval_node(k, args) for k in sig[1]]
        op = {"intersect": bitops.b_and, "union": bitops.b_or,
              "xor": bitops.b_xor, "difference": bitops.b_andnot}[kind]
        acc = kids[0]
        for k in kids[1:]:
            acc = op(acc, k)
        return acc
    # BSI nodes: the leaf slot holds (exists, sign, [bits]) tuples with each
    # array [S, W]; magnitude bits stack depth-first to [depth, S, W] so the
    # bit-serial comparators broadcast over the shard axis with no vmap.
    if kind == "bsi_notnull":
        exists, _, _ = args[sig[1]]
        return exists
    if kind == "bsi_zero":
        exists, _, _ = args[sig[1]]
        return jnp.zeros_like(exists)

    def _stacked(slot):
        exists, sign, bits = args[slot]
        if not isinstance(bits, (list, tuple)):
            return exists, sign, bits  # "bsiagg" leaf: pre-stacked cube
        stack = jnp.stack(bits, axis=0) if bits else \
            jnp.zeros((0,) + exists.shape, exists.dtype)
        return exists, sign, stack

    if kind == "bsi_eq" or kind == "bsi_neq":
        _, slot, depth, neg, pslot = sig
        exists, sign, stack = _stacked(slot)
        lo, hi = args[pslot]
        filt = (exists & sign) if neg else bitops.b_andnot(exists, sign)
        eq = bsi_ops.range_eq_unsigned_t(stack, filt, lo, hi, depth)
        if kind == "bsi_eq":
            return eq
        return bitops.b_andnot(exists, eq)  # rangeNEQ fragment.go:1317
    if kind == "bsi_lt":
        _, slot, depth, allow_eq, branch_pos, pslot = sig
        exists, sign, stack = _stacked(slot)
        lo, hi = args[pslot]
        if branch_pos:
            # All negatives, plus positives below the predicate
            # (rangeLT fragment.go:1332).
            pos = bsi_ops.range_lt_unsigned_t(
                stack, bitops.b_andnot(exists, sign), lo, hi, depth, allow_eq)
            return bitops.b_or(exists & sign, pos)
        return bsi_ops.range_gt_unsigned_t(
            stack, exists & sign, lo, hi, depth, allow_eq)
    if kind == "bsi_gt":
        _, slot, depth, allow_eq, branch_pos, pslot = sig
        exists, sign, stack = _stacked(slot)
        lo, hi = args[pslot]
        if branch_pos:
            return bsi_ops.range_gt_unsigned_t(
                stack, bitops.b_andnot(exists, sign), lo, hi, depth, allow_eq)
        # Negatives with smaller magnitude, plus all positives
        # (rangeGT fragment.go:1404).
        neg = bsi_ops.range_lt_unsigned_t(
            stack, exists & sign, lo, hi, depth, allow_eq)
        return bitops.b_or(bitops.b_andnot(exists, sign), neg)
    if kind == "bsi_between":
        _, slot, depth, case, plo, phi = sig
        exists, sign, stack = _stacked(slot)
        llo, lhi = args[plo]
        hlo, hhi = args[phi]
        if case == "pos":
            filt = bitops.b_andnot(exists, sign)
            a = bsi_ops.range_gt_unsigned_t(stack, filt, llo, lhi, depth, True)
            b = bsi_ops.range_lt_unsigned_t(stack, filt, hlo, hhi, depth, True)
            return bitops.b_and(a, b)
        if case == "neg":
            filt = exists & sign
            a = bsi_ops.range_gt_unsigned_t(stack, filt, hlo, hhi, depth, True)
            b = bsi_ops.range_lt_unsigned_t(stack, filt, llo, lhi, depth, True)
            return bitops.b_and(a, b)
        # Crossing zero (rangeBetween fragment.go:1457).
        pos = bsi_ops.range_lt_unsigned_t(
            stack, bitops.b_andnot(exists, sign), hlo, hhi, depth, True)
        neg = bsi_ops.range_lt_unsigned_t(
            stack, exists & sign, llo, lhi, depth, True)
        return bitops.b_or(pos, neg)
    raise ValueError(f"unknown signature node {kind!r}")


def _bitwise(sig: tuple) -> bool:
    """Whether a filter signature is bitwise algebra over dense leaves
    alone, so that it can be evaluated on any tile of them."""
    kind = sig[0]
    if kind == "leaf":
        return True
    if kind == "not":
        return _bitwise(sig[2])
    return (kind in ("intersect", "union", "xor", "difference")
            and all(_bitwise(k) for k in sig[1]))


def _group_totals(host, lens, r_pads) -> np.ndarray:
    """Each real group's count, in lexicographic order, from the
    program's ``[blocks, prod(r_pads)]`` int32 block sums: summed over
    the blocks in int64 (a block sum is exact in int32, a sum over more
    than 2,047 full shards would not be), the padding slots cut off."""
    totals = np.asarray(host).astype(np.int64).sum(axis=0)
    return totals.reshape(r_pads)[tuple(slice(n) for n in lens)].ravel()


def _copy_async(*arrays) -> None:
    """Kick off device->host copies for every output at once, so the
    subsequent np.asarray reads pay ~one transfer round-trip total
    instead of N sequential ones (the round-trip is not measured on the
    current machine)."""
    for a in arrays:
        try:
            a.copy_to_host_async()
        except (AttributeError, RuntimeError):  # non-jax array / backend
            pass


def _host_zeros(rows: int) -> np.ndarray:
    """Zeroed ``[rows, W]`` uint32 host matrix for a stack build, from
    the recycled page pool where there is one: a fresh 128 MiB
    allocation pays a first-touch fault on every page it writes
    (native/roaring_codec.cpp, "recycled page pool"). The chunk goes
    back to the pool when the last reference is collected, and the
    runtime holds one until the transfer has read the matrix."""
    mat = native.pool_zeros((rows, WORDS_PER_SHARD), np.uint32)
    if mat is None:
        mat = np.zeros((rows, WORDS_PER_SHARD), dtype=np.uint32)
    return mat


def _assemble_stack(didx, dmat, ci, cw, cv, s_pad: int):
    """Build a [s_pad, W] stack on device from a few dense rows plus
    COO word triplets (sparse-upload path): row s_pad is a sacrificial
    trash target for the pow2 padding, sliced off before return.
    Jitted per planner (MeshPlanner.__init__) with the mesh's shard
    sharding as out_shardings."""
    base = jnp.zeros((s_pad + 1, WORDS_PER_SHARD), dtype=jnp.uint32)
    if dmat.shape[0]:
        base = base.at[didx].set(dmat)
    if ci.shape[0]:
        base = base.at[ci, cw].set(cv)
    return base[:s_pad]


# The stepped helpers, named for the device trace like the fused
# programs (_named): time-view union, the BSI paths.
_jit_or = jax.jit(_named(lambda a, b: jnp.bitwise_or(a, b),
                         "time_views_or"))
_jit_full_like = jax.jit(_named(
    lambda a: jnp.full_like(a, jnp.uint32(0xFFFFFFFF)), "bsi_all_ones"))


@functools.partial(jax.jit, static_argnames=("depth", "is_min"))
def _agg_min_max(exists, sign, stack, filt, depth: int, is_min: bool):
    """Per-shard Min/Max fold over stacked [S, W] BSI rows.

    Returns (consider_count[S], alt_count[S], a, b) where ``a`` is the
    (lo, hi, count) of the branch taken when the sign class exists in the
    shard (negatives for Min / positives for Max, fragment.go:1146/:1189)
    and ``b`` the fallback branch; the host selects per shard.
    """
    consider = jnp.bitwise_and(exists, filt)
    cons_cnt = bitops.count(consider)
    if is_min:
        alt = jnp.bitwise_and(sign, consider)       # negatives
        a = bsi_ops._max_unsigned(stack, alt, depth)   # min = -max(|neg|)
    else:
        alt = bitops.b_andnot(consider, sign)        # positives
        a = bsi_ops._max_unsigned(stack, alt, depth)   # max = max(pos)
    alt_cnt = bitops.count(alt)
    b = bsi_ops._min_unsigned(stack, consider, depth)
    return cons_cnt, alt_cnt, a, b


def _fold_min_max(cc, ac, a, b, n_shards: int, is_min: bool):
    """Host-side smaller/larger fold shared by the stepped and fused
    Min/Max paths (fragment.go:1146/:1189 selection rule)."""
    # lo/hi stay scalar when no magnitude bit reached their half
    # (e.g. hi for depth<=32); broadcast to per-shard vectors.
    av = tuple(np.broadcast_to(np.asarray(x), cc.shape) for x in a)
    bv = tuple(np.broadcast_to(np.asarray(x), cc.shape) for x in b)
    best_val, best_cnt = 0, 0
    for s in range(n_shards):
        if cc[s] == 0:
            continue
        if ac[s] > 0:
            v = bsi_ops._join_u64(av[0][s], av[1][s])
            cnt = int(av[2][s])
            v = -v if is_min else v
        else:
            v = bsi_ops._join_u64(bv[0][s], bv[1][s])
            cnt = int(bv[2][s])
            v = v if is_min else -v
        if best_cnt == 0 or (v < best_val if is_min else v > best_val):
            best_val, best_cnt = v, cnt
    return best_val, best_cnt


