"""Distributed (multi-host) execution of the REAL executor/planner.

This is the TPU-native data plane SURVEY §2.3:115 plans: N processes
(hosts), each owning a disjoint set of shards in its local Holder, form
ONE ``jax.sharding.Mesh`` spanning every device of every process.  Leaf
stacks are assembled with ``jax.make_array_from_single_device_arrays``
from each process's local fragment rows — no host ever materializes the
whole index — and the full PQL surface (Count/Not, BSI Range/Sum/Min/
Max, GroupBy, TopN, Rows, writes) runs through the unmodified
:class:`~pilosa_tpu.exec.executor.Executor` logic: cross-shard
reductions compile to XLA collectives over ICI/DCN, and host-side
metadata merges (TopN pair merge, Rows union, GroupBy candidates) ride
a pickle-allgather over the same distributed runtime.

This replaces the reference's HTTP scatter-gather mapReduce
(executor.go:2455, remoteExec :2414) with compiler-scheduled
collectives, the way a JAX multi-controller training loop replaces a
parameter server.

SPMD discipline (the one rule everything below enforces): every process
executes the SAME queries in the SAME order, and any code path that
launches a device program over global arrays must be reached uniformly
by all processes.  Consequences:

- the executor's result cache is disabled (per-process epoch counters
  drift after ownership-gated writes, so a cache hit on one process but
  not another would desynchronize the collective schedule);
- every device output that any host will read is first re-sharded to
  fully-replicated (``_replicate_small`` / ``_jit_program``), making the
  read a purely local copy;
- per-fragment work (TopN count sweeps, host row scans) touches only
  process-local single-device arrays, so it may freely diverge between
  processes; its results are merged with ``allgather_obj``.

Writes are ownership-gated: the owning process applies the mutation,
every other process bumps the index epoch so planner/executor caches
invalidate uniformly, and the owner's result is broadcast host-side.
"""

from __future__ import annotations

import pickle
from concurrent.futures import Future
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.core.row import Row
from pilosa_tpu.core.shardset import as_shard_set
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.errors import QueryError
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.parallel.mesh import SHARD_AXIS
from pilosa_tpu.parallel.planner import MeshPlanner, _named
from pilosa_tpu.pql import Call


class SyncBatcher:
    """Drop-in TransferBatcher that resolves synchronously.

    Multi-controller execution must keep device-program order identical
    across processes; a background resolver thread's timing is not part
    of the program order, so the distributed planner resolves each pull
    inline (the arrays it pulls are fully replicated — the copy is
    local and cheap).
    """

    def submit(self, arr, postproc) -> "Future[Any]":
        fut: Future = Future()
        try:
            fut.set_result(postproc(np.asarray(arr)))
        except Exception as e:  # mirror TransferBatcher's error channel
            fut.set_exception(e)
        return fut

    def close(self) -> None:
        pass


def allgather_obj(obj: Any) -> list[Any]:
    """Exchange one picklable object per process; returns the list
    indexed by process id.  The host-metadata analog of the reference's
    HTTP reduce at the coordinator — here it rides the distributed
    runtime (two fixed-shape allgathers: sizes, then padded payloads).
    """
    from jax.experimental import multihost_utils

    if jax.process_count() == 1:
        return [obj]
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    # Pad to a coarse multiple so repeated calls reuse compiled gathers.
    step = 4096
    padded = np.zeros(((payload.size + step) // step) * step, dtype=np.uint8)
    padded[:payload.size] = payload
    sizes = np.asarray(multihost_utils.process_allgather(
        np.array([payload.size, padded.size], dtype=np.int64)))
    width = int(sizes[:, 1].max())
    if padded.size < width:
        padded = np.concatenate(
            [padded, np.zeros(width - padded.size, dtype=np.uint8)])
    bufs = np.asarray(multihost_utils.process_allgather(padded))
    return [pickle.loads(bufs[p, :int(sizes[p, 0])].tobytes())
            for p in range(bufs.shape[0])]


def _row_to_host(row: Row) -> Row:
    out = Row({s: np.asarray(seg, dtype=np.uint32)
               for s, seg in row.segments.items()})
    out.attrs, out.keys = row.attrs, row.keys
    return out


def _to_host(value: Any) -> Any:
    if isinstance(value, Row):
        return _row_to_host(value)
    return value


class DistributedMeshPlanner(MeshPlanner):
    """MeshPlanner whose leaf stacks span a multi-process mesh.

    ``owned_shards`` is this process's slice of the shard space.  Layout
    contract (multihost.py module doc): when the global query shard list
    is laid out over the mesh, every stack row that lands on this
    process's devices must be a shard this process owns (and vice
    versa) — checked per stack build, so misplacement is an error, not
    silent zeros.
    """

    #: an upload worker would build stacks on ONE process's thread and
    #: desync the collective launch order every other process expects.
    UPLOADS_AHEAD = False

    def __init__(self, holder, mesh, owned_shards, **kw):
        super().__init__(holder, mesh, **kw)
        self.owned_shards = frozenset(int(s) for s in owned_shards)
        self.batcher.close()
        self.batcher = SyncBatcher()
        # Every process must run the SAME launch schedule: coalescing
        # (thread-local batching) and fused/const programs that skip
        # _replicate_small's resharding would desync the collective
        # order, so the distributed planner keeps the stepped paths.
        self.coalesce_supported = False
        self.coalesce_vmap_supported = False
        self.fuse_aggregates_supported = False
        self.fuse_const_supported = False
        # Packed residency would need a packed variant of the global
        # per-process assembly below.
        self.residency_packed_supported = False
        # Sketch stacks (hll planes / simtopn cubes) assemble host-side
        # on one node; the distributed mesh falls back to the executor's
        # per-shard map + register-max reduce instead.
        self.sketch_supported = False
        self._pid = jax.process_index()
        flat = list(self.mesh.devices.reshape(-1))
        #: (device, global mesh position) for this process's devices.
        self._local_devs = [(d, i) for i, d in enumerate(flat)
                            if d.process_index == self._pid]
        self._replicated = NamedSharding(self.mesh, P())
        self._sharded = NamedSharding(self.mesh, P(SHARD_AXIS))
        # jit wrappers built ONCE (a fresh jax.jit per call would have an
        # empty compile cache every time).
        import jax.numpy as jnp

        from pilosa_tpu.ops import bitops
        self._replicate_jit = jax.jit(
            _named(lambda *xs: xs, "replicate_small"),
            out_shardings=self._replicated)
        self._count_jit = jax.jit(
            _named(lambda x: bitops.count(x), "groupby_count"),
            out_shardings=self._replicated)
        self._and_count_jit = jax.jit(
            _named(lambda x, y: bitops.count(jnp.bitwise_and(x, y)),
                   "groupby_and_count"),
            out_shardings=self._replicated)

    # -- ownership ------------------------------------------------------

    def owns(self, shard: int) -> bool:
        return int(shard) in self.owned_shards

    def allgather_obj(self, obj: Any) -> list[Any]:
        return allgather_obj(obj)

    # -- global stack assembly -----------------------------------------

    def _local_rows(self, s_pad: int):
        """(device, row_lo, row_hi) for each local device's stack rows."""
        per_dev = s_pad // self.n_devices
        return [(d, g * per_dev, (g + 1) * per_dev)
                for d, g in self._local_devs]

    def _build_stack(self, idx, field_name, view, row_id, shards):
        # NOTE: this override ships dense per-device blocks; the base
        # planner's sparse COO upload path (3-5x under eviction churn
        # on the bandwidth-bound single-chip rig) is NOT applied here —
        # a per-device local-scatter variant is straightforward but
        # unmeasurable without multi-process TPU hardware, so it stays
        # unclaimed until it can be measured.
        s_pad = self._pad(len(shards))
        # Layout + ownership discipline over the WHOLE shard list (not
        # just local rows): an owned shard on a remote device position
        # would silently drop data; a local fragment for a non-owned
        # shard would double count once that shard's owner contributes
        # the same rows.
        per_dev = s_pad // self.n_devices
        local_pos = {i for _, lo, hi in self._local_rows(s_pad)
                     for i in range(lo, hi)}
        for i, shard in enumerate(shards):
            if self.owns(shard):
                if i not in local_pos:
                    raise QueryError(
                        f"owned shard {shard} maps to stack row {i} on a "
                        f"remote device (per_dev={per_dev}) — shard list "
                        f"is not aligned with the ownership layout")
            elif self.holder.fragment(idx.name, field_name, view,
                                      shard) is not None:
                raise QueryError(
                    f"shard {shard} has a local fragment on process "
                    f"{self._pid} but is not owned — ownership "
                    f"discipline violated")
        blocks = []
        for dev, lo, hi in self._local_rows(s_pad):
            block = np.zeros((hi - lo, WORDS_PER_SHARD), dtype=np.uint32)
            for i in range(lo, min(hi, len(shards))):
                shard = shards[i]
                if not self.owns(shard):
                    continue  # another process's row: stays zero HERE,
                    # real data lives on that process's device.
                frag = self.holder.fragment(idx.name, field_name, view,
                                            shard)
                if frag is not None:
                    frag.row_words_into(row_id, block[i - lo])
            blocks.append((block, dev))

        def upload():
            return jax.make_array_from_single_device_arrays(
                (s_pad, WORDS_PER_SHARD), self._sharded,
                [jax.device_put(block, dev) for block, dev in blocks])

        return upload, int(sum(block.nbytes for block, _ in blocks))

    def _zeros_stack(self, n_shards: int):
        s_pad = self._pad(n_shards)
        return jax.make_array_from_callback(
            (s_pad, WORDS_PER_SHARD), self._sharded,
            lambda sl: np.zeros(
                (len(range(*sl[0].indices(s_pad))), WORDS_PER_SHARD),
                dtype=np.uint32))

    # -- replication of host-read outputs ------------------------------

    def _jit_program(self, program, reduce):
        if reduce == "per_shard":
            return jax.jit(program, out_shardings=self._replicated)
        return jax.jit(program)

    def _replicate_small(self, *arrays):
        return self._replicate_jit(*arrays)

    def _count_arr(self, a):
        return self._count_jit(a)

    def _and_count(self, a, b):
        return self._and_count_jit(a, b)

    def _replicate_stack(self, arr):
        (out,) = self._replicate_jit(arr)
        return out

    # -- result materialization ----------------------------------------

    def execute_bitmap(self, idx, c: Call, shards: list[int]) -> Row:
        """Row result: the stacked tree output is all-gathered across
        the mesh (the reference ships whole row segments to the
        coordinator over HTTP here — executor.go:2414) and handed back
        as host segments every process can read."""
        if not shards:
            return Row()
        shards = self._shards(shards)
        out = self._tree_stack(idx, c, shards)
        host = np.asarray(self._replicate_stack(out), dtype=np.uint32)
        return Row({shard: host[i] for i, shard in enumerate(shards)})

    # -- TopN -----------------------------------------------------------

    def execute_topn_counts(self, idx, field_name, view, shards,
                            filter_call, row_ids=None):
        """Local fragments' count sweeps (single-device work, free to
        diverge per process) + one metadata allgather merge."""
        allowed = (np.asarray(sorted(set(int(r) for r in row_ids)),
                              dtype=np.uint64)
                   if row_ids is not None else None)
        filt_host = None
        shards = self._shards(shards)
        if filter_call is not None:
            # Uniform global program + replication; per-fragment use
            # below is host/local-device only.
            filt = self._tree_stack(idx, filter_call, shards)
            filt_host = np.asarray(self._replicate_stack(filt),
                                   dtype=np.uint32)
        local: dict[int, tuple] = {}
        for si, shard in enumerate(shards):
            frag = self.holder.fragment(idx.name, field_name, view, shard)
            if frag is None:
                continue
            if filt_host is None:
                ids, counts = frag.top_counts()
                if allowed is not None and len(ids):
                    keep = np.isin(ids, allowed)
                    ids, counts = ids[keep], counts[keep]
                if len(ids):
                    local[shard] = (ids, counts)
                continue
            ids, _ = frag.row_counts()
            if allowed is not None and len(ids):
                ids = ids[np.isin(ids, allowed, assume_unique=True)]
            if not len(ids):
                continue
            seg_host = filt_host[si]
            seg_dev = jax.device_put(seg_host)  # local device only
            counts, parts = frag.intersection_counts_async(
                ids, seg_dev, reuse=True, seg_host=seg_host)
            for slots, dev in parts:
                counts[slots] = np.asarray(dev, dtype=np.int64)[:len(slots)]
            order = np.lexsort((ids, -counts))
            local[shard] = (ids[order], counts[order])
        merged: dict[int, tuple] = {}
        for part in allgather_obj(local):
            merged.update(part)
        return merged

    # -- GroupBy ---------------------------------------------------------

    def group_by_candidates(self, idx, field_name, shards):
        out: set[int] = set()
        for shard in shards:
            frag = self.holder.fragment(idx.name, field_name,
                                        VIEW_STANDARD, shard)
            if frag is not None:
                out.update(frag.row_ids())
        merged: set[int] = set()
        for part in allgather_obj(sorted(out)):
            merged.update(part)
        return sorted(merged)

    def execute_group_by(self, idx, fields, cands, shards, filter_call):
        res = super().execute_group_by(idx, fields, cands, shards,
                                       filter_call)
        if res is None:
            # The single-host executor falls back to a per-shard host
            # walk here; distributed, that walk would return local-only
            # counts — fail loudly instead of answering wrong.
            raise QueryError(
                "GroupBy shape exceeds the distributed planner's batched "
                "bounds (GROUP_BY_MAX_PAIRS); narrow the Rows() children")
        return res


class DistributedExecutor(Executor):
    """Executor over a multi-process mesh: same call logic, with host
    map/reduce partials merged across processes and writes gated to the
    shard owner.  Requires a :class:`DistributedMeshPlanner`."""

    def __init__(self, holder, planner: DistributedMeshPlanner, **kw):
        # Per-process epoch counters drift after ownership-gated writes,
        # so a result-cache hit on one process but not another would
        # desynchronize the collective schedule. Not optional.
        if kw.pop("result_cache", False):
            raise ValueError(
                "DistributedExecutor cannot run with result_cache=True: "
                "per-process cache hits desync the SPMD schedule")
        super().__init__(holder, planner=planner, result_cache=False, **kw)

    # -- map/reduce spine ------------------------------------------------

    def map_reduce(self, idx, shards, c, opt, map_fn, reduce_fn,
                   local_batch_fn=None):
        if local_batch_fn is not None:
            # Planner paths produce globally-correct results (device
            # collectives + internal allgathers).
            return local_batch_fn(as_shard_set(shards, self.stats))
        # Host path: run the per-shard loop over OWNED shards only (for
        # reads, remote shards contribute nothing locally; for
        # multi-shard writes — ClearRow/Store — this IS the ownership
        # discipline), then fold every process's partial.
        acc = None
        for shard in shards:
            if self.planner.owns(shard):
                acc = reduce_fn(acc, map_fn(shard))
        merged = None
        for part in allgather_obj(_to_host(acc)):
            if part is None:
                continue
            merged = part if merged is None else reduce_fn(merged, part)
        return merged

    # -- single-shard writes --------------------------------------------

    def _gated_write(self, idx, col_id: int, field_names: list[str],
                     apply_fn):
        """Owner applies; everyone else bumps the epoch (uniform cache
        invalidation); the owner's outcome — result OR error — is
        broadcast so all processes stay on the same schedule.

        An owner-side exception must not leave peers blocked in the
        allgather (they have already entered it by the time the owner
        would raise), so the owner catches, ships the error, and every
        process raises the same QueryError.  After a successful apply,
        peers mark the shard remote-available on the touched fields:
        a first write into a previously-empty shard must grow every
        process's default shard list identically, or the next
        shards=None query compiles different global shapes per process.
        """
        shard = col_id // SHARD_WIDTH
        if self.planner.owns(shard):
            try:
                outcome = ("ok", apply_fn())
            except Exception as e:
                outcome = ("err", type(e).__name__, str(e))
        else:
            idx.epoch.bump()
            outcome = None
        results = [r for r in allgather_obj(outcome) if r is not None]
        if not results:
            raise QueryError(
                f"no process owns shard {shard} (column {col_id}) — the "
                f"write cannot be applied; extend the ownership map "
                f"before writing past the partitioned shard space")
        outcome = results[0]
        if outcome[0] == "err":
            raise QueryError(f"write failed on owner: "
                             f"{outcome[1]}: {outcome[2]}")
        if not self.planner.owns(shard):
            ef = idx.existence_field()
            for name in field_names + ([ef.name] if ef is not None else []):
                f = idx.field(name)
                if f is not None:
                    f.add_remote_available_shards([shard])
        return outcome[1]

    def _execute_set(self, idx, c: Call, opt):
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError("Set() column argument 'col' required")
        return self._gated_write(
            idx, col_id, [c.field_arg()],
            lambda: super(DistributedExecutor, self)
            ._execute_set(idx, c, opt))

    def _execute_clear_bit(self, idx, c: Call, opt):
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError(
                "column argument to Clear(<COLUMN>, <FIELD>=<ROW>) required")
        return self._gated_write(
            idx, col_id, [c.field_arg()],
            lambda: super(DistributedExecutor, self)
            ._execute_clear_bit(idx, c, opt))
