"""Multi-host single-mesh execution — the DCN layer (SURVEY §2.3:115).

The HTTP scatter-gather cluster path (cluster.map_reduce) mirrors the
reference's architecture: one planner mesh per node, JSON/frames between
nodes.  This module is the TPU-NATIVE alternative SURVEY planned: N
processes (hosts) × M chips form ONE ``jax.sharding.Mesh`` via
``jax.distributed``, and the REAL executor + planner
(parallel.distributed.DistributedExecutor / DistributedMeshPlanner) run
the full PQL surface over it — leaf stacks assembled per process with
``jax.make_array_from_single_device_arrays``, cross-shard reductions as
XLA collectives over ICI/DCN, host metadata merges as pickle-allgathers
on the distributed runtime.

Layout contract: the global sorted shard list, laid out over the mesh's
``shard`` axis, must place each process's owned shards on that process's
devices — here (and in any contiguous-partition deployment) process p of
P owns shards ``[p*S/P, (p+1)*S/P)``.  DistributedMeshPlanner checks the
contract on every stack build.

Validated on CPU (``--xla_force_host_platform_device_count``) like every
other multi-device path here; on real hardware the same code drives
multi-host TPU pods (jax.distributed over the pod's coordinator).

Reference analog: executor.go:2455 mapReduce + remoteExec :2414 — the
per-node HTTP fan-out this replaces with compiler-scheduled collectives.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Sequence

import numpy as np

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def initialize(coordinator_address: str, num_processes: int,
               process_id: int) -> None:
    """jax.distributed.initialize wrapper (idempotence-guarded)."""
    import jax
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(axis: str = "shard"):
    """One mesh over every device of every process."""
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()), (axis,))


# ---------------------------------------------------------------------------
# dryrun harness: N local processes emulate N hosts on the CPU backend.
# ---------------------------------------------------------------------------


def _canon(result):
    """Comparable form of an executor result (host-only values)."""
    from pilosa_tpu.core.row import Row
    from pilosa_tpu.exec.result import (
        GroupCount, Pair, RowIdentifiers, ValCount,
    )
    if isinstance(result, Row):
        return ("row", tuple(int(c) for c in result.columns()))
    if isinstance(result, ValCount):
        return ("valcount", int(result.val), int(result.count))
    if isinstance(result, Pair):
        return ("pair", int(result.id), int(result.count))
    if isinstance(result, RowIdentifiers):
        return ("rowids", tuple(result.rows), tuple(result.keys))
    if isinstance(result, list):
        if result and isinstance(result[0], Pair):
            return tuple((int(p.id), int(p.count)) for p in result)
        if result and isinstance(result[0], GroupCount):
            return tuple(
                (tuple((fr.field, int(fr.row_id)) for fr in gc.group),
                 int(gc.count))
                for gc in result)
        return tuple(result)
    return result


#: the read surface both executors answer each phase — Count over fused
#: bitmap algebra (incl. Not/existence), BSI comparators, aggregates,
#: TopN (plain + filtered + threshold), GroupBy, Rows, and a raw Row
#: materialization.
_READ_QUERIES = (
    "Count(Intersect(Row(f=1), Not(Row(g=2))))",
    "Count(Union(Row(f=0), Row(g=0), Row(f=2)))",
    "Count(Xor(Row(f=1), Row(g=1)))",
    "Count(Row(v >= 0))",
    "Count(Row(v < -50))",
    "Count(Row(v == 7))",
    "Sum(field=v)",
    "Sum(Row(f=1), field=v)",
    "Min(field=v)",
    "Max(field=v)",
    "TopN(f, n=2)",
    "TopN(f, Row(g=1), n=3)",
    "TopN(g, threshold=2)",
    "GroupBy(Rows(f), Rows(g))",
    "GroupBy(Rows(f), Rows(g), filter=Row(v > 0))",
    "Rows(f)",
    "Row(f=2)",
)


def _worker_main(argv: Sequence[str]) -> int:
    """Body of one emulated host: a partitioned Holder owning only this
    process's shards, the REAL DistributedExecutor over the global mesh,
    and a full-dataset scalar oracle cross-checked on THIS process for
    every query and every write phase (visibility asserted on every
    process, not just the owner)."""
    _, n_procs, pid, devs = (argv[0], int(argv[1]), int(argv[2]),
                             int(argv[3]))
    import jax
    assert jax.process_count() == n_procs
    assert jax.device_count() == n_procs * devs, jax.device_count()

    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import FieldOptions, Holder
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel.distributed import (
        DistributedExecutor,
        DistributedMeshPlanner,
    )

    mesh = global_mesh()
    n_shards = 2 * n_procs * devs  # 2 stack rows per device
    per_proc = n_shards // n_procs
    my_shards = set(range(pid * per_proc, (pid + 1) * per_proc))

    # Deterministic global dataset; every process can generate it, but
    # the distributed holder imports ONLY the owned slice (the
    # cluster-node discipline); the oracle holder imports everything.
    # The LAST shard starts empty: a later write into it exercises the
    # first-fragment-in-a-new-shard metadata sync (every process's
    # default shard list must grow identically).
    rng = np.random.default_rng(42)
    n_bits = 20_000
    total_cols = (n_shards - 1) * SHARD_WIDTH
    f_rows = rng.integers(0, 3, n_bits, dtype=np.uint64)
    f_cols = rng.integers(0, total_cols, n_bits, dtype=np.uint64)
    g_rows = rng.integers(0, 3, n_bits, dtype=np.uint64)
    g_cols = rng.integers(0, total_cols, n_bits, dtype=np.uint64)
    v_cols = rng.choice(total_cols, 4000, replace=False).astype(np.uint64)
    v_vals = rng.integers(-100, 100, len(v_cols))
    exist_cols = np.arange(0, total_cols, 3, dtype=np.uint64)

    def build_holder(owned: set[int] | None):
        holder = Holder()
        idx = holder.create_index("mh")
        f = idx.create_field("f")
        g = idx.create_field("g")
        v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                               min=-100, max=100))

        def mask(cols):
            if owned is None:
                return np.ones(len(cols), dtype=bool)
            return np.isin((cols // SHARD_WIDTH).astype(np.int64),
                           sorted(owned))

        m = mask(f_cols)
        f.import_bits(f_rows[m], f_cols[m])
        m = mask(g_cols)
        g.import_bits(g_rows[m], g_cols[m])
        m = mask(v_cols)
        v.import_values(v_cols[m].tolist(), v_vals[m].tolist())
        idx.add_existence(exist_cols[mask(exist_cols)])
        if owned is not None:
            remote = set(range(n_shards)) - owned
            for fld in (f, g, v, idx.existence_field()):
                fld.add_remote_available_shards(remote)
        return holder, idx

    holder, idx = build_holder(my_shards)
    planner = DistributedMeshPlanner(holder, mesh, my_shards)
    executor = DistributedExecutor(holder, planner)

    oracle_holder, _ = build_holder(None)
    oracle = Executor(oracle_holder)  # scalar: no planner, no mesh

    def check_phase(phase: str):
        for q in _READ_QUERIES:
            (got,) = executor.execute("mh", q)
            (want,) = oracle.execute("mh", q)
            assert _canon(got) == _canon(want), (
                f"pid {pid} phase {phase}: {q!r}: "
                f"{_canon(got)!r} != {_canon(want)!r}")

    check_phase("initial")

    # Write phase: single-bit writes into a shard owned by EACH process
    # (visibility must cross the process boundary both ways), BSI write,
    # clear, and the multi-shard write paths (Store / ClearRow).  Both
    # executors run the same PQL; the distributed one gates application
    # to the owner and bumps epochs everywhere.
    col_p0 = 5                            # shard 0 → process 0
    col_p1 = (n_shards - 2) * SHARD_WIDTH + 7   # late shard → last process
    col_new = (n_shards - 1) * SHARD_WIDTH + 11  # EMPTY shard → last proc
    writes = (
        f"Set({col_p0}, f=1)",
        f"Set({col_p1}, f=1)",
        f"Set({col_p1}, g=2)",
        f"Set({col_new}, f=1)",   # first fragment in a fresh shard
        f"Set({col_p0 + 2}, v=-3)",
        f"Clear({col_p1}, g=2)",
        "Store(Row(f=1), f=9)",
    )
    for w in writes:
        (got,) = executor.execute("mh", w)
        (want,) = oracle.execute("mh", w)
        assert got == want, (pid, w, got, want)
    # Oracle sanity: the cross-process bits actually changed something.
    (after_f1,) = oracle.execute("mh", "Count(Row(f=1))")
    assert after_f1 > 0
    check_phase("after-writes")

    executor.execute("mh", "ClearRow(f=9)")
    oracle.execute("mh", "ClearRow(f=9)")
    check_phase("after-clearrow")

    print(f"multihost worker {pid}: ok "
          f"queries={len(_READ_QUERIES)}x3phases writes={len(writes) + 1} "
          f"mesh={mesh.shape} procs={n_procs} owned={sorted(my_shards)}",
          flush=True)
    return 0


def run_multiprocess_dryrun(n_procs: int = 2, devs_per_proc: int = 4,
                            timeout: float = 600.0) -> None:
    """Spawn n_procs fresh processes that form ONE jax.distributed mesh
    on the CPU backend and run the full executor surface + write phases
    over it.  Raises on any worker failure."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coord = f"127.0.0.1:{port}"

    from pilosa_tpu import cleanspawn

    procs = []
    for pid in range(n_procs):
        env = cleanspawn.scrubbed_env(devs_per_proc)
        # Backend pinning happens INSIDE the hermetic child (cleanspawn:
        # python -I, scrubbed env — no startup hook can select another
        # backend).  jax.distributed.initialize runs before the
        # backend assertion (backend init must not precede it) and
        # before importing pilosa_tpu, whose module-level jnp constants
        # would initialise the backend.
        code = (
            cleanspawn.pin_preamble(devs_per_proc, _REPO_DIR,
                                    assert_backend=False)
            + "jax.distributed.initialize(coordinator_address=sys.argv[1],\n"
            "                           num_processes=int(sys.argv[2]),\n"
            "                           process_id=int(sys.argv[3]))\n"
            "from pilosa_tpu.cleanspawn import assert_cpu_backend\n"
            "assert_cpu_backend()\n"
            "from pilosa_tpu.parallel import multihost\n"
            "sys.exit(multihost._worker_main(sys.argv[1:]))\n"
        )
        procs.append(subprocess.Popen(
            cleanspawn.command(code) + [coord, str(n_procs), str(pid),
                                        str(devs_per_proc)],
            env=env, cwd=_REPO_DIR, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    failed = []
    for pid, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            failed.append((pid, "timeout", err))
            continue
        outs.append(out)
        if p.returncode != 0 or "ok" not in out:
            failed.append((pid, p.returncode, err))
    if failed:
        detail = "\n".join(f"worker {pid} rc={rc}:\n{err[-2000:]}"
                           for pid, rc, err in failed)
        raise RuntimeError(f"multihost dryrun failed:\n{detail}")
    for out in outs:
        sys.stdout.write(out)
