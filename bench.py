"""System benchmark: the BASELINE.json workloads through the REAL stack.

Every config drives Field.import_bits/import_values -> Executor +
MeshPlanner — not a raw kernel. The served `cli server` path is driven on
the chip by chip_smoke.py (one process per chip: a bench parent that holds
the chip cannot spawn a server that needs it).
Reference analog: end-to-end PQL QPS via api.Query (api.go:135) over
executor.go's mapReduce.

Configs (BASELINE.json):
  1. star-trace     Count(Intersect(Row,Row)) over a 1B-col set index —
                    THE headline metric; pipelined QPS via a thread pool
                    + sequential p50 latency.
  2. topn           TopN over a 1M-row x 10M-col field (ranked-cache
                    analog: generation-cached exact counts) + a filtered
                    TopN (streamed device counts).
  3. bsi            Sum / Min / Range-filtered Count on an int field
                    (100M cols) through the planner's stacked BSI folds.
  4. time-quantum   Row(f, from, to) + Count over YMDH views.
  5. cluster        4-node in-process cluster (PQL-serialized node
                    boundary): GroupBy + Count over a sharded index.
  8. overload       3-node replicated cluster at 4x admission
                    oversubscription with one slow (gray) peer: admitted
                    p50/p99, shed rate, hedge fire/win rate, and breaker
                    transitions — the overload-resilience layer under
                    its design load.

CPU baseline: the reference publishes no absolute numbers and this image
has no Go toolchain, so the baseline is measured here as the strongest
honest stand-in for roaring's intersectionCountBitmapBitmap
(roaring.go:3121): the native C++ fused popcount(a & b) kernel
(-O3 -march=native POPCNT), run single-threaded AND with one thread per
core over per-shard blocks (the goroutine worker-pool analog,
executor.go:2561). vs_baseline uses the THREADED number.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Env knobs: BENCH_COLS (default 1e9), BENCH_QUERIES, BENCH_CONFIGS
(comma list / "all"), BENCH_THREADS.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

N_COLS = int(os.environ.get("BENCH_COLS", 1_000_000_000))
N_QUERIES = int(os.environ.get("BENCH_QUERIES", 256))
N_LAT = int(os.environ.get("BENCH_LAT_QUERIES", 30))
THREADS = int(os.environ.get("BENCH_THREADS", 32))
CONFIGS = os.environ.get("BENCH_CONFIGS", "all")
DENSITY = float(os.environ.get("BENCH_DENSITY", 0.05))


#: quarter-octave log buckets (1e-5 s .. ~20 s): fine enough that the
#: interpolated quantile sits within a few percent of the nearest-rank
#: value the old private lists produced, while staying O(buckets) no
#: matter how many samples a bench takes.
_BENCH_BOUNDS = tuple(1e-5 * (2 ** (i / 4)) for i in range(84))


def _hist():
    """A fresh latency histogram (seconds). Benches accumulate into
    these instead of private lists — same bounded LogHistogram the
    server's stats registry uses."""
    from pilosa_tpu.obs.histogram import LogHistogram
    return LogHistogram(_BENCH_BOUNDS)


def _p99(lat_s):
    """p99 in ms from a LogHistogram of second-latencies (an iterable
    of seconds is folded into one first)."""
    h = lat_s if hasattr(lat_s, "quantile") else _observed(lat_s)
    return h.quantile(0.99) * 1e3


def _p50(h):
    """p50 in ms from a LogHistogram of second-latencies."""
    return h.quantile(0.50) * 1e3


def _observed(lat_s):
    h = _hist()
    for v in lat_s:
        h.observe(v)
    return h


def _timer(fn, n, threads=1):
    """(qps, p50_ms, p99_ms) over n calls; threads>1 = pipelined
    throughput. Tail latency comes from the sequential sample (the
    threaded phase measures occupancy, not per-call service time)."""
    h = _hist()
    for _ in range(min(n, N_LAT)):
        t0 = time.perf_counter()
        fn()
        h.observe(time.perf_counter() - t0)
    p50 = _p50(h)
    p99 = _p99(h)
    if threads <= 1:
        qps = 1e3 / p50 if p50 else float("inf")
        return qps, p50, p99
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(lambda _: fn(), range(n)))
    dt = time.perf_counter() - t0
    return n / dt, p50, p99


def _rand_positions(rng, n_bits, n_cols):
    return rng.integers(0, n_cols, n_bits, dtype=np.uint64)


# ---------------------------------------------------------------------------
# config 1: star-trace headline — 1B cols through Executor + MeshPlanner
# ---------------------------------------------------------------------------


def bench_star_trace(extra):
    import jax

    from pilosa_tpu import native
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    n_shards = (N_COLS + SHARD_WIDTH - 1) // SHARD_WIDTH
    n_bits = int(N_COLS * DENSITY)
    rng = np.random.default_rng(7)

    # Persistent compile cache ON for the whole bench so the
    # second-boot series below measures disk-cache reloads, the same
    # thing a restarted node pays. Enabled before the first compile so
    # every program of boot 1 gets persisted.
    from pilosa_tpu.parallel import compile_cache
    cc_dir = compile_cache.resolve_dir(
        os.environ.get("PILOSA_TPU_BENCH_COMPILE_CACHE"))
    extra["compile_cache_enabled"] = compile_cache.enable(cc_dir)

    h = Holder()
    idx = h.create_index("bench")
    f = idx.create_field("f")
    g = idx.create_field("g")

    # Timed window covers import_bits only (generating 800 MB of random
    # positions is setup, not import). Row ids are broadcast views and
    # each position array is dropped after its import: resident-set
    # bloat makes every fresh page fault dramatically slower on this
    # virtualized host, which is allocator noise, not import cost.
    row1 = np.broadcast_to(np.uint64(1), n_bits)
    row2 = np.broadcast_to(np.uint64(2), n_bits)
    fpos = _rand_positions(rng, n_bits, N_COLS)
    t0 = time.perf_counter()
    f.import_bits(row1, fpos)
    import_s = time.perf_counter() - t0
    gpos = _rand_positions(rng, n_bits, N_COLS)
    t0 = time.perf_counter()
    g.import_bits(row2, gpos)
    import_s += time.perf_counter() - t0
    del gpos
    # Median of 3 like the BSI metrics: identical imports on this
    # shared vCPU swing 2x with scheduler luck, and a single-shot
    # number inherits whatever minute the host was having (observed
    # 57-122 Mbit/s for the same code). Extra trials land in throwaway
    # fields re-importing fpos; the f/g fields above stay for the
    # query benchmarks.
    rates = [2 * n_bits / import_s / 1e6]
    for t in range(2):
        ft = idx.create_field(f"imp{t}")
        t0 = time.perf_counter()
        ft.import_bits(row1, fpos)
        rates.append(n_bits / (time.perf_counter() - t0) / 1e6)
        idx.delete_field(f"imp{t}")
    del fpos
    extra["import_mbits_per_s"] = round(statistics.median(rates), 1)

    # ---- CPU baselines over the same dense blocks ----
    blocks_f = [h.fragment("bench", "f", "standard", s) for s in range(n_shards)]
    blocks_g = [h.fragment("bench", "g", "standard", s) for s in range(n_shards)]
    words_f = [fr.row_words(1) for fr in blocks_f]
    words_g = [fr.row_words(2) for fr in blocks_g]

    def cpu_shard(s):
        return native.intersection_count_words(words_f[s], words_g[s])

    t0 = time.perf_counter()
    expected = sum(cpu_shard(s) for s in range(n_shards))
    cpu1_dt = time.perf_counter() - t0
    n_cpu = os.cpu_count() or 1
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=n_cpu) as pool:
        got = sum(pool.map(cpu_shard, range(n_shards)))
    cpu_mt_dt = time.perf_counter() - t0
    assert got == expected
    cpu_qps = 1.0 / cpu_mt_dt
    extra["cpu_1thread_qps"] = round(1.0 / cpu1_dt, 2)
    extra["cpu_threaded_qps"] = round(cpu_qps, 2)
    extra["cpu_threads"] = n_cpu
    # Falsifiability (VERDICT r4 weak #5): this rig's CPU is a single
    # shared vCPU, so vs_baseline is honest for THIS host but is NOT
    # "10x a many-core server running the Go reference". The
    # load-bearing comparisons are the paired same-run ratios below
    # (executor_vs_kernel_delivered, pallas_vs_xla).
    extra["cpu_note"] = (
        f"baseline = native C++ popcount kernel on this rig's "
        f"{n_cpu}-thread shared vCPU; not a many-core reference host")

    # ---- device sync floor ----
    # ONE synchronous device->host pull costs a host<->device round-trip
    # no matter how small the array (measured right here, per run).
    # Every metric below that needs a device sync is bounded by this
    # floor; the system answers are (a) the TransferBatcher --
    # concurrent queries share one stacked transfer per wave -- and (b)
    # the epoch-invalidated result cache for repeated reads.
    import jax.numpy as jnp

    _tiny = jax.device_put(np.arange(8, dtype=np.int32))
    _sumf = jax.jit(lambda v: jnp.sum(v))
    int(_sumf(_tiny))
    floors = []
    for _ in range(3):
        t0 = time.perf_counter()
        int(_sumf(_tiny))
        floors.append(time.perf_counter() - t0)
    extra["device_sync_floor_ms"] = round(
        statistics.median(floors) * 1e3, 2)

    # ---- executor + planner path ----
    shards = list(range(n_shards))
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    q = "Count(Intersect(Row(f=1), Row(g=2)))"

    (got,) = ex.execute("bench", q, shards=shards)
    assert got == expected, (got, expected)

    # Pipelined throughput through the FULL stack (parse, cache check,
    # translate, planner, batcher), result cache bypassed so every query
    # runs its device program and delivers its count to the host.
    # Measured in blocks INTERLEAVED with the delivered-kernel baseline
    # below: a shared host's throughput drifts from minute to minute, so
    # sequential measurement makes the executor/kernel ratio an artifact
    # of WHEN each side ran, not of host overhead.
    ex.execute("bench", q, shards=shards, cache=False)  # warm async path

    def run_executor_block(n):
        t0 = time.perf_counter()
        futs = [ex.execute_async("bench", q, shards=shards, cache=False)
                for _ in range(n)]
        results = [f.result() for f in futs]
        dt = time.perf_counter() - t0
        assert all(r == [expected] for r in results)
        return n / dt

    # Sequential latency: cold (one full device round-trip per query,
    # floor-bound by the link) and cached (the system behavior for any
    # repeated read until the next write).
    h = _hist()
    for _ in range(min(N_LAT, 15)):
        t0 = time.perf_counter()
        ex.execute("bench", q, shards=shards, cache=False)
        h.observe(time.perf_counter() - t0)
    extra["executor_count_intersect_cold_p50_ms"] = round(_p50(h), 2)
    h = _hist()
    for _ in range(N_LAT):
        t0 = time.perf_counter()
        ex.execute("bench", q, shards=shards)
        h.observe(time.perf_counter() - t0)
    p50 = _p50(h)
    extra["executor_count_intersect_p50_ms"] = round(p50, 3)
    extra["cols"] = n_shards * SHARD_WIDTH

    # Raw-kernel continuity number (r1's measure): pipelined, no executor.
    a = planner._stack_rows(idx, "f", "standard", 1, tuple(shards))
    b = planner._stack_rows(idx, "g", "standard", 2, tuple(shards))

    import jax.numpy as jnp

    @jax.jit
    def kernel(x, y):
        return jnp.sum(
            jax.lax.population_count(jnp.bitwise_and(x, y)).astype(jnp.int32),
            axis=-1)

    jax.block_until_ready(kernel(a, b))
    t0 = time.perf_counter()
    outs = [kernel(a, b) for _ in range(N_QUERIES)]
    jax.block_until_ready(outs)
    extra["raw_kernel_qps"] = round(N_QUERIES / (time.perf_counter() - t0), 1)

    # Shared delivered-rate plumbing for the Pallas A/B and the
    # kernel-delivered baseline below.
    from pilosa_tpu.parallel.batcher import TransferBatcher

    bt = TransferBatcher()
    post = lambda host: int(host.astype(np.int64).sum())  # noqa: E731
    bt.submit(kernel(a, b), post).result()  # warm the batcher's
    # resolver thread + first host-pull path BEFORE any measured block
    # (a cold first block would bias whichever side runs first).

    # ---- Pallas-vs-XLA A/B on chip (VERDICT r4 weak #8) ----
    # The kernel layer's own contribution, measured: the SAME fused
    # popcount(a & b) through the Pallas grid kernel and through plain
    # XLA, as counts DELIVERED to the host through the shared batcher
    # above, fresh jit wrappers per side so neither inherits the
    # other's trace. Runs only where the Pallas path is real (TPU
    # backend); CPU interpret mode would measure the interpreter, not
    # the kernel.
    from pilosa_tpu.ops import pallas_kernels as pk
    if pk._DISABLED:
        # Operator forced the XLA path (PILOSA_TPU_NO_PALLAS=1, the
        # documented escape hatch for a broken Pallas build); never
        # override that — record why the A/B is absent instead.
        extra["pallas_ab_note"] = "skipped: PILOSA_TPU_NO_PALLAS=1"
    elif jax.default_backend() == "tpu":
        # _DISABLED is read at TRACE time: compile each side once under
        # its own setting (fresh lambdas = separate jit caches), restore
        # the flag, then alternate measurement blocks with the prebuilt
        # executables.
        old = pk._DISABLED
        try:
            pk._DISABLED = False
            pallas_fn = jax.jit(lambda x, y: pk.pair_count(x, y, "and"))
            ref = jax.block_until_ready(pallas_fn(a, b))
            assert int(np.asarray(ref).astype(np.int64).sum()) == expected
            pk._DISABLED = True
            xla_fn = jax.jit(lambda x, y: pk.pair_count(x, y, "and"))
            ref = jax.block_until_ready(xla_fn(a, b))
            assert int(np.asarray(ref).astype(np.int64).sum()) == expected
        finally:
            pk._DISABLED = old

        # DELIVERED rate through the shared batcher below (the same
        # plumbing the kernel-delivered baseline and the executor use):
        # the enqueue+block form drifts wildly with link weather
        # (recorded 1.43x and 0.53x for identical code on this rig);
        # counts-on-host is the stable, falsifiable comparison and
        # matches how the kernel is consumed in production.
        def rate(fn) -> float:
            t0 = time.perf_counter()
            futs = [bt.submit(fn(a, b), post)
                    for _ in range(N_QUERIES)]
            vals = [f.result() for f in futs]
            assert vals[0] == expected
            return N_QUERIES / (time.perf_counter() - t0)

        # Alternate sides so link weather cancels in the ratio.
        ps, xs = [], []
        for i in range(4):
            if i % 2:
                xs.append(rate(xla_fn))
                ps.append(rate(pallas_fn))
            else:
                ps.append(rate(pallas_fn))
                xs.append(rate(xla_fn))
        # The RATIO is the load-bearing number — paired blocks ride the
        # same link weather, so drift cancels.
        extra["pallas_pair_count_delivered_qps"] = round(
            statistics.median(ps), 1)
        extra["xla_pair_count_delivered_qps"] = round(
            statistics.median(xs), 1)
        extra["pallas_vs_xla"] = round(
            statistics.median(ps) / statistics.median(xs), 3)

    # raw_kernel_qps (enqueue-only, above the A/B) is NOT a query rate:
    # nothing forces each call's result off the device, so its absolute
    # value says little and drifts run to run. The
    # honest kernel ceiling is "counts delivered to the host" through
    # the same batcher the executor uses — bare kernel + transfer, zero
    # executor logic — which the Pallas A/B above also measures through
    # (the batcher was warmed before the first measured block).

    def run_kernel_block(n):
        t0 = time.perf_counter()
        futs = [bt.submit(kernel(a, b), post) for _ in range(n)]
        vals = [f.result() for f in futs]
        dt = time.perf_counter() - t0
        assert vals[0] == expected
        return n / dt

    # Paired A/B blocks: executor and bare-kernel alternate through the
    # same link weather. The executor/kernel comparison is the MEDIAN OF
    # PER-PAIR RATIOS — adjacent blocks see near-identical link state,
    # so each ratio cancels the drift that a ratio-of-medians (or r3's
    # fully sequential measurement, which shipped a phantom 0.31x "gap")
    # soaks up. Within-pair order alternates to kill the residual bias.
    # Full-size blocks: throughput scales with in-flight depth on this
    # link (64-query bursts deliver ~½ of 256-query bursts — the wave
    # pipeline amortizes the round-trip over everything in flight), so
    # undersized blocks would understate both sides.
    ex_qps, kern_qps, ratios = [], [], []
    block = N_QUERIES
    for i in range(8):
        if i % 2:
            k = run_kernel_block(block)
            e = run_executor_block(block)
        else:
            e = run_executor_block(block)
            k = run_kernel_block(block)
        ex_qps.append(e)
        kern_qps.append(k)
        ratios.append(e / k)
    qps = statistics.median(ex_qps)
    extra["executor_count_intersect_qps"] = round(qps, 1)
    extra["kernel_delivered_qps"] = round(statistics.median(kern_qps), 1)
    extra["executor_vs_kernel_delivered"] = round(
        statistics.median(ratios), 3)

    # ---- second boot (executor path): persistent compile cache ----
    # clear_caches() drops every in-memory executable — exactly what a
    # process restart loses — while the on-disk cache survives; a fresh
    # planner then re-traces the same kernels and loads them from disk
    # instead of recompiling. The hit counter (not wall clock) is the
    # proof the reload actually happened.
    cc_before = compile_cache.stats()
    jax.clear_caches()
    planner2 = MeshPlanner(h, make_mesh())
    ex2 = Executor(h, planner=planner2)
    t0 = time.perf_counter()
    (got2,) = ex2.execute("bench", q, shards=shards, cache=False)
    extra["executor_count_intersect_second_boot_first_ms"] = round(
        (time.perf_counter() - t0) * 1e3, 2)
    assert got2 == expected, (got2, expected)
    h = _hist()
    for _ in range(min(N_LAT, 15)):
        t0 = time.perf_counter()
        ex2.execute("bench", q, shards=shards, cache=False)
        h.observe(time.perf_counter() - t0)
    p50_2boot = _p50(h)
    extra["executor_count_intersect_second_boot_cold_p50_ms"] = round(
        p50_2boot, 2)
    cc_after = compile_cache.stats()
    extra["executor_compile_cache_hits"] = (
        cc_after["hits"] - cc_before["hits"])
    extra["executor_cold_vs_warm_ratio"] = round(
        p50_2boot / max(p50, 1e-3), 2)
    planner2.close()

    bt.close()
    return qps, cpu_qps


# ---------------------------------------------------------------------------
# config 2: TopN 1M rows x 10M cols
# ---------------------------------------------------------------------------


def bench_oversubscribed(extra):
    """QPS when the leaf working set EXCEEDS the planner's HBM stack
    budget (VERDICT r4 #3): the same query mix runs once fully resident
    and once with a budget holding half the leaves, so every sweep
    evicts and re-uploads under LRU churn — the two-tier hot-dense /
    cold-host story's cost, measured. Reference role: roaring mmap
    paging (roaring/roaring.go:1437 RemapRoaringStorage).

    Swept at 1x/2x/4x working-set-to-budget ratios, A/B'd dense vs the
    container-classed packed residency (exec/residency) with pipelined
    prefetch — the headline `oversubscribed_vs_resident@2x` is the
    packed leg; the `_dense@` keys keep the old cliff visible."""
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.exec import residency as _residency
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    n_shards, n_rows = 64, 16
    total = n_shards * SHARD_WIDTH
    rng = np.random.default_rng(11)
    h = Holder()
    idx = h.create_index("over")
    f = idx.create_field("f")
    for r in range(n_rows):
        cols = rng.integers(0, total, 20_000)
        f.import_bits(np.full(len(cols), r, dtype=np.uint64), cols)
    shards = list(range(n_shards))
    mesh = make_mesh()
    s_pad = ((n_shards + len(mesh.devices.reshape(-1)) - 1)
             // len(mesh.devices.reshape(-1))) * len(mesh.devices.reshape(-1))
    stack_bytes = _residency.dense_nbytes(s_pad)
    extra["oversub_stack_mb"] = round(stack_bytes / 1e6, 1)
    extra["oversub_working_set_mb"] = round(n_rows * stack_bytes / 1e6, 1)

    oracle = {}
    scalar = Executor(h)
    for r in range(n_rows):
        (oracle[r],) = scalar.execute("over", f"Count(Row(f={r}))",
                                      shards=shards)

    def sweep_qps(budget_bytes, sweeps, packed):
        os.environ["PILOSA_TPU_RESIDENCY_PACKED"] = packed
        planner = MeshPlanner(h, mesh, max_cache_bytes=budget_bytes)
        ex = Executor(h, planner=planner, result_cache=False)
        for r in range(n_rows):  # warm compile + (maybe) cache
            (got,) = ex.execute("over", f"Count(Row(f={r}))", shards=shards)
            assert got == oracle[r], (r, got, oracle[r])
        t0 = time.perf_counter()
        n = 0
        for _ in range(sweeps):
            futs = [ex.execute_async("over", f"Count(Row(f={r}))",
                                     shards=shards)
                    for r in range(n_rows)]
            for r, fut in enumerate(futs):
                assert fut.result() == [oracle[r]]
            n += n_rows
        dt = time.perf_counter() - t0
        st = planner.cache_stats()
        pf = planner.prefetcher.debug()
        planner.close()
        return n / dt, st, pf

    saved_mode = os.environ.get("PILOSA_TPU_RESIDENCY_PACKED")
    try:
        # Fully-resident dense baseline: the denominator for every ratio.
        resident_qps, _, _ = sweep_qps(2 * n_rows * stack_bytes, sweeps=3,
                                       packed="off")
        extra["resident_count_qps"] = round(resident_qps, 1)

        ws_bytes = n_rows * stack_bytes
        for x in (1, 2, 4):  # working set = x * device budget
            dense_qps, st_d, pf_d = sweep_qps(ws_bytes // x, sweeps=3,
                                              packed="off")
            packed_qps, st_p, pf_p = sweep_qps(ws_bytes // x, sweeps=3,
                                               packed="auto")
            extra[f"oversubscribed_vs_resident_dense@{x}x"] = round(
                dense_qps / resident_qps, 3)
            extra[f"oversubscribed_vs_resident@{x}x"] = round(
                packed_qps / resident_qps, 3)
            if x != 2:
                continue
            # the 2x point is the historical BENCH_r05 regime: keep the
            # legacy key (now the packed+prefetch leg) and prove the
            # dense leg really churned.
            extra["oversubscribed_vs_resident"] = (
                extra["oversubscribed_vs_resident@2x"])
            extra["oversubscribed_count_qps"] = round(dense_qps, 1)
            assert st_d["bytes"] <= st_d["budget_bytes"]
            assert st_d["entries"] <= n_rows // 2
            assert st_d["evictions"] > 0  # the metric really measured churn
            extra["oversub_evictions"] = st_d["evictions"]
            # the pipelined miss path: dense churn leg's misses are all
            # absorbed by inflight prefetch uploads.
            extra["oversub_prefetch_hits"] = pf_d["hits"]
            extra["oversub_prefetch_sync_misses"] = pf_d["sync_misses"]
            extra["oversub_prefetch_overlap_ms"] = round(
                pf_d["overlap_ms"], 1)
            # density of what a device-GB holds, per representation
            # class: SET columns of this working set per resident GB
            # (padding included) — the packed/dense ratio is the
            # compression the representation classes buy at this sparsity.
            extra["resident_columns_per_gb_dense"] = int(
                sum(oracle.values()) / (n_rows * stack_bytes) * 1e9)
            packed_bytes = st_p["class_bytes"][_residency.PACKED]
            if packed_bytes:
                extra["resident_columns_per_gb_packed"] = int(
                    sum(oracle.values()) / packed_bytes * 1e9)
    finally:
        if saved_mode is None:
            os.environ.pop("PILOSA_TPU_RESIDENCY_PACKED", None)
        else:
            os.environ["PILOSA_TPU_RESIDENCY_PACKED"] = saved_mode

    # ---- tail latency + QoS under the same churn regime ----
    # Individually-timed sync queries through a tight admission gate
    # while a batch-class flood oversubscribes it: what an admitted
    # interactive query's p50/p99 looks like when the node is saturated
    # and the queue bound is doing its job (sheds + deadline misses
    # recorded rather than unbounded queueing).
    from pilosa_tpu.qos import (AdmissionController, Deadline,
                                DeadlineExceededError, QueryShedError,
                                reset_current_deadline,
                                set_current_deadline)
    planner = MeshPlanner(h, mesh, max_cache_bytes=(n_rows // 2) * stack_bytes)
    ex = Executor(h, planner=planner, result_cache=False)
    for r in range(n_rows):  # warm compiles
        ex.execute("over", f"Count(Row(f={r}))", shards=shards)
    ctl = AdmissionController(max_concurrent=2, max_queue=4)
    sheds = misses = 0
    lat = _hist()

    def one_query(r, qos_class, deadline_s):
        nonlocal sheds, misses
        tok = set_current_deadline(Deadline(timeout=deadline_s))
        t0 = time.perf_counter()
        try:
            with ctl.admit(qos_class):
                ex.execute("over", f"Count(Row(f={r}))", shards=shards)
            return time.perf_counter() - t0
        except QueryShedError:
            sheds += 1
        except DeadlineExceededError:
            misses += 1
        finally:
            reset_current_deadline(tok)
        return None

    with ThreadPoolExecutor(max_workers=16) as pool:
        futs = []
        for i in range(n_rows * 4):
            if i % 2:  # batch flood with a tight deadline
                futs.append(pool.submit(one_query, i % n_rows, "batch", 0.5))
            else:      # the interactive stream we're protecting
                futs.append(pool.submit(one_query, i % n_rows,
                                        "interactive", 10.0))
        for i, fut in enumerate(futs):
            dt = fut.result()
            if dt is not None and i % 2 == 0:
                lat.observe(dt)
    planner.close()
    extra["oversub_qos_sheds"] = sheds
    extra["oversub_qos_deadline_misses"] = misses
    if lat.count:
        extra["oversub_admitted_p50_ms"] = round(_p50(lat), 3)
        extra["oversub_admitted_p99_ms"] = round(_p99(lat), 3)
    snap = ctl.snapshot()
    assert snap["shed"] == sheds and snap["deadlineMiss"] == misses


def bench_topn(extra):
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    n_rows = 1_000_000
    cols = 10_000_000
    n_bits = 5_000_000
    rng = np.random.default_rng(13)

    h = Holder()
    idx = h.create_index("topn")
    f = idx.create_field("f")
    g = idx.create_field("g")
    # Zipf-ish row popularity so TopN has real structure.
    rows = (np.abs(rng.standard_cauchy(n_bits)) * 1000).astype(np.uint64) % n_rows
    f.import_bits(rows, _rand_positions(rng, n_bits, cols))
    g.import_bits(np.zeros(200_000, dtype=np.uint64),
                  _rand_positions(rng, 200_000, cols))

    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    (warm,) = ex.execute("topn", "TopN(f, n=10)")
    assert len(warm) == 10

    qps, p50, _ = _timer(lambda: ex.execute("topn", "TopN(f, n=10)"), N_LAT)
    extra["topn_1m_rows_p50_ms"] = round(p50, 3)
    extra["topn_1m_rows_qps"] = round(qps, 1)
    _, p50c, _ = _timer(lambda: ex.execute("topn", "TopN(f, n=10)",
                                        cache=False), N_LAT)
    extra["topn_1m_rows_cold_p50_ms"] = round(p50c, 3)

    # Filtered TopN at 20k rows: the streamed exact device path.
    f2 = idx.create_field("f2")
    rows2 = rng.integers(0, 20_000, 400_000).astype(np.uint64)
    f2.import_bits(rows2, _rand_positions(rng, 400_000, cols))
    ex.execute("topn", "TopN(f2, Row(g=0), n=10)")  # warm
    _, p50f, _ = _timer(lambda: ex.execute("topn", "TopN(f2, Row(g=0), n=10)"),
                     max(5, N_LAT // 3))
    extra["topn_filtered_20k_rows_p50_ms"] = round(p50f, 3)
    _, p50fc, _ = _timer(lambda: ex.execute("topn", "TopN(f2, Row(g=0), n=10)",
                                         cache=False), max(5, N_LAT // 3))
    extra["topn_filtered_20k_rows_cold_p50_ms"] = round(p50fc, 3)


# ---------------------------------------------------------------------------
# config 3: BSI Sum / Min / Range
# ---------------------------------------------------------------------------


def bench_bsi(extra):
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder, FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    cols = 100_000_000
    n_vals = 2_000_000
    rng = np.random.default_rng(17)

    h = Holder()
    idx = h.create_index("bsi")
    v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                           min=-100_000, max=100_000))
    f = idx.create_field("f")
    # Timed window covers import_values only: the random-sample setup
    # (an 800MB permutation for choice-without-replacement) is test-data
    # generation, not import work.
    vc = rng.choice(cols, n_vals, replace=False).astype(np.uint64)
    vv = rng.integers(-100_000, 100_000, n_vals)
    # The FIRST import after boot additionally pays the pool's growth
    # past its boot reserve (fresh mmap + first-touch faults for the
    # 229MB plane buffer + staging) — a once-per-server-lifetime cost,
    # recorded separately so it stays visible. The headline metric is
    # the steady-state rate a warm server imports at: median of 3
    # post-warm-up trials, fresh field each (plane-buffer creation and
    # zeroing stay IN the metric; only the one-time page faulting is
    # out). The first trial's field is kept — the queries below run
    # against it.
    t0 = time.perf_counter()
    v.import_values(vc, vv)
    extra["bsi_import_first_boot_mvals_per_s"] = round(
        n_vals / (time.perf_counter() - t0) / 1e6, 2)
    rates2m = []
    for t in range(3):
        vt = idx.create_field(f"v2m{t}", FieldOptions(type=FIELD_TYPE_INT,
                                                      min=-100_000,
                                                      max=100_000))
        t0 = time.perf_counter()
        vt.import_values(vc, vv)
        rates2m.append(n_vals / (time.perf_counter() - t0) / 1e6)
        idx.delete_field(f"v2m{t}")
    extra["bsi_import_mvals_per_s"] = round(statistics.median(rates2m), 2)
    # Amortized rate at bulk-load batch size: the 2M-value batch above
    # is dominated by the one-time dense plane-buffer creation (see
    # PROFILE_import.md); 8M values over the same columns shows the
    # steady-state import rate. A STEADY-STATE metric gets the median
    # of 3 trials — single-shot numbers on this shared vCPU swing 2x
    # with scheduler/fault luck (same import: 6.6 then 13.3 Mvals/s).
    vc8 = rng.integers(0, cols, 8_000_000, dtype=np.uint64)
    vv8 = rng.integers(-100_000, 100_000, 8_000_000)
    rates = []
    for t in range(3):
        v8 = idx.create_field("v8", FieldOptions(type=FIELD_TYPE_INT,
                                                 min=-100_000, max=100_000))
        t0 = time.perf_counter()
        v8.import_values(vc8, vv8)
        rates.append(8_000_000 / (time.perf_counter() - t0) / 1e6)
        idx.delete_field("v8")
    extra["bsi_import_mvals_per_s_8m"] = round(statistics.median(rates), 2)
    del vc8, vv8
    f.import_bits(np.ones(500_000, dtype=np.uint64),
                  _rand_positions(rng, 500_000, cols))

    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    for q, key in (("Sum(field=v)", "bsi_sum_p50_ms"),
                   ("Min(field=v)", "bsi_min_p50_ms"),
                   ("Sum(Row(f=1), field=v)", "bsi_sum_filtered_p50_ms"),
                   ("Count(Row(v > 50000))", "bsi_range_count_p50_ms")):
        ex.execute("bsi", q)  # warm/compile
        _, p50, _ = _timer(lambda q=q: ex.execute("bsi", q), N_LAT)
        extra[key] = round(p50, 3)
        _, p50c, _ = _timer(lambda q=q: ex.execute("bsi", q, cache=False),
                         max(5, N_LAT // 3))
        extra[key.replace("_p50_ms", "_cold_p50_ms")] = round(p50c, 3)


# ---------------------------------------------------------------------------
# config 3b: approximate analytics (HLL distinct + SimilarTopN)
# ---------------------------------------------------------------------------


def bench_sketch(extra):
    """Sketch vs exact A/B (pilosa_tpu/sketch).

    Two series: Count(Distinct(...)) through the fused register path
    against its own exact fallback, and SimilarTopN against the
    equivalent client-side loop of N Count(Intersect(...)) queries —
    the one-dispatch claim is asserted against the planner's raw
    counter, not inferred from latency."""
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import FieldOptions, Holder
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    cols = 16 * SHARD_WIDTH
    n_vals = 2_000_000
    n_rows = 256
    rng = np.random.default_rng(29)

    h = Holder()
    idx = h.create_index("sk")
    v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                           min=0, max=10_000_000))
    f = idx.create_field("f")
    vc = rng.choice(cols, n_vals, replace=False).astype(np.uint64)
    v.import_values(vc, rng.integers(0, 10_000_000, n_vals))
    f.import_bits(rng.integers(0, n_rows, n_vals, dtype=np.uint64),
                  rng.integers(0, cols, n_vals, dtype=np.uint64))

    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner, result_cache=False)

    sketch_q = "Count(Distinct(field=v, threshold=0))"
    exact_q = "Count(Distinct(field=v, threshold=100000000))"
    (est,) = ex.execute("sk", sketch_q)          # warm/compile
    (true,) = ex.execute("sk", exact_q)
    extra["sketch_distinct_rel_err"] = round(abs(est - true) / true, 4)
    d0 = planner.dispatches
    qps, p50, _ = _timer(lambda: ex.execute("sk", sketch_q), N_LAT)
    assert (planner.dispatches - d0) == N_LAT, \
        "fused distinct must cost exactly one dispatch per query"
    extra["sketch_distinct_qps"] = round(qps, 1)
    extra["sketch_distinct_p50_ms"] = round(p50, 3)
    _, p50e, _ = _timer(lambda: ex.execute("sk", exact_q),
                        max(3, N_LAT // 5))
    extra["sketch_distinct_exact_p50_ms"] = round(p50e, 3)

    sim_q = "SimilarTopN(f, Row(f=7), n=10)"
    ex.execute("sk", sim_q)                      # warm/compile
    d0 = planner.dispatches
    qps, p50, _ = _timer(lambda: ex.execute("sk", sim_q),
                         max(5, N_LAT // 3))
    assert (planner.dispatches - d0) == max(5, N_LAT // 3), \
        "fused SimilarTopN must cost exactly one dispatch per query"
    extra["sketch_simtopn_p50_ms"] = round(p50, 3)
    extra["sketch_simtopn_qps"] = round(qps, 1)

    # the pre-sketch spelling: one Count(Intersect(...)) per candidate
    # row from the client — N round trips instead of one dispatch.
    def loop():
        for rid in range(0, n_rows, 8):   # 32 of 256 rows: a LOWER bound
            ex.execute("sk", f"Count(Intersect(Row(f=7), Row(f={rid})))")
    loop()                                       # warm/compile
    _, p50l, _ = _timer(loop, 3)
    extra["sketch_simtopn_loop32_p50_ms"] = round(p50l, 3)
    planner.close()


# ---------------------------------------------------------------------------
# config 3c: dispatch fusion + same-plan coalescing (one launch per query)
# ---------------------------------------------------------------------------


def bench_dispatch(extra):
    """Fused plan-step programs + dispatch-coalescing A/B.

    * count_dispatches_per_query — device launches for one uncached
      3-step Intersect→Count (MUST be 1: the acceptance assertion).
    * dispatch_agg_uncached_p50_ms_{on,off} — filtered BSI Range→Sum
      with fusion FORCED on vs the stepped path (filter, plane stack,
      reduce). Forced because ``auto`` steps filtered aggregates on the
      XLA CPU backend (see MeshPlanner._fuse_agg_ok); the on/off delta
      here is the CPU artifact that gate exists for.
    * dispatch_agg_plain_uncached_p50_ms_{on,off} — unfiltered Sum,
      where the cached plane cube makes the fused program win on every
      backend (this one fuses under ``auto`` too).
    * dispatch_count_uncached_p50_ms_{on,off} + coalesce_batch_width_p50
      — per-call p50 of a concurrent identical-Count storm with
      coalescing on vs off (result cache off throughout; fusion stays
      on in both, the production pairing).
    """
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder, FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    rng = np.random.default_rng(23)
    n_shards = 4
    total = n_shards * SHARD_WIDTH
    h = Holder()
    idx = h.create_index("d")
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                           min=-100_000, max=100_000))
    for field in (f, g):
        field.import_bits(rng.integers(0, 4, 2_000_000),
                          rng.integers(0, total, 2_000_000,
                                       dtype=np.uint64))
    vc = rng.choice(total, 1_000_000, replace=False).astype(np.uint64)
    v.import_values(vc, rng.integers(-100_000, 100_000, len(vc)))

    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    q = "Count(Intersect(Row(f=1), Row(g=2)))"
    ex.execute("d", q, cache=False)  # compile + warm stacks

    d0 = planner.dispatches
    ex.execute("d", q, cache=False)
    dpq = planner.dispatches - d0
    extra["count_dispatches_per_query"] = dpq
    assert dpq == 1, f"3-step Count took {dpq} dispatches, want 1"

    # Fusion A/B on the BSI aggregates (the path fusion collapsed from
    # three launches to one).
    def agg_p50(agg):
        ex.execute("d", agg, cache=False)  # warm this mode's path
        _, p50, _ = _timer(lambda: ex.execute("d", agg, cache=False),
                           max(10, N_LAT))
        return p50

    def agg_ab(agg, key, fuse_mode):
        os.environ["PILOSA_TPU_DISPATCH_FUSE"] = fuse_mode
        try:
            fused50 = agg_p50(agg)
            os.environ["PILOSA_TPU_DISPATCH_FUSE"] = "off"
            stepped50 = agg_p50(agg)
        finally:
            del os.environ["PILOSA_TPU_DISPATCH_FUSE"]
        extra[f"dispatch_{key}_uncached_p50_ms_on"] = round(fused50, 3)
        extra[f"dispatch_{key}_uncached_p50_ms_off"] = round(stepped50, 3)
        extra[f"dispatch_{key}_p50_speedup"] = round(stepped50 / fused50, 2)

    # Filtered: force fusion so the A/B measures the fused program even
    # on the CPU backend, where "auto" would route it to the stepped
    # path (the comparator+reduction single-module pathology).
    agg_ab("Sum(Row(v >< [-50000, 50000]), field=v)", "agg", "on")
    # Unfiltered: fuses under "auto" on every backend.
    agg_ab("Sum(field=v)", "agg_plain", "auto")
    extra["dispatch_agg_auto_gate"] = (
        "filtered aggs step under auto on backend=cpu; see _fuse_agg_ok")

    # Coalescing A/B: identical uncached Counts from a thread pool —
    # the repeated-dashboard-query shape coalescing targets.
    storm_threads = min(THREADS, 16)
    storm_q = max(min(N_QUERIES, 256), 128)

    def storm():
        lats = _hist()   # thread-safe: LogHistogram locks its observes

        def one(_):
            t0 = time.perf_counter()
            ex.execute("d", q, cache=False)
            lats.observe(time.perf_counter() - t0)

        with ThreadPoolExecutor(max_workers=storm_threads) as pool:
            list(pool.map(one, range(storm_q)))
        return _p50(lats)

    os.environ["PILOSA_TPU_DISPATCH_COALESCE"] = "on"
    try:
        dstart = planner.dispatches
        on50 = storm()
        n_launch = planner.dispatches - dstart
        widths = planner.batch_widths()[-n_launch:] if n_launch else [1]
        os.environ["PILOSA_TPU_DISPATCH_COALESCE"] = "off"
        off50 = storm()
    finally:
        del os.environ["PILOSA_TPU_DISPATCH_COALESCE"]
    extra["coalesce_batch_width_p50"] = statistics.median(widths)
    extra["dispatch_count_uncached_p50_ms_on"] = round(on50, 3)
    extra["dispatch_count_uncached_p50_ms_off"] = round(off50, 3)
    extra["dispatch_count_p50_speedup"] = round(off50 / on50, 2)
    planner.close()


# ---------------------------------------------------------------------------
# config 2c: key translation (ISSUE 20 — device key planes + batched
# host path)
# ---------------------------------------------------------------------------


def bench_translate(extra):
    """Keyed/id parity and the forward-translate fast paths.

    * translate_keyed_count_dispatches — device launches for one warm
      keyed Count (MUST be 1: the translation stage must stay on the
      host snapshot for small batches, never grow a second launch).
    * translate_keyed_vs_id_p50_ratio — warm keyed Count p50 over the
      identical id-addressed Count p50 (the keyed/id parity headline).
    * translate_batch_alloc_speedup_10k — batched translate_keys vs a
      per-key loop, ALLOCATING 10k fresh keys: the per-key loop pays
      one COW snapshot publish per key, the batch pays one total.
      Asserted >= 10x (measures ~100x+).
    * translate_batch_read_speedup_10k — same A/B on the all-hits read
      path (both lock-free; the batch amortizes call overhead).
    * translate_storm_keys_per_s_planes_{on,off} — 4096-key resolve
      storms through the executor's batched resolver with the device
      plane forced on vs off. On the CPU backend the plane's gather
      competes with a host dict walk, so the ratio is reported, not
      gated — the plane exists for HBM-resident deployments.
    """
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.index import IndexOptions
    from pilosa_tpu.core.translate import TranslateStore
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    rng = np.random.default_rng(29)
    n_bits, n_cols = 400_000, SHARD_WIDTH * 2
    h = Holder()
    kidx = h.create_index("tk", IndexOptions(keys=True))
    kf = kidx.create_field("f", FieldOptions(keys=True))
    oidx = h.create_index("ti")
    of = oidx.create_field("f")
    rows = rng.integers(1, 5, n_bits)
    cols = rng.integers(0, n_cols, n_bits, dtype=np.uint64)
    row_ids = kf.translate_store.translate_keys(
        [f"r{r}" for r in range(1, 5)])
    row_map = {r: row_ids[r - 1] for r in range(1, 5)}
    kf.import_bits(np.array([row_map[r] for r in rows.tolist()],
                            dtype=np.uint64), cols)
    of.import_bits(rows.astype(np.uint64), cols)

    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    kq, oq = 'Count(Row(f="r1"))', "Count(Row(f=1))"
    ex.execute("tk", kq, cache=False)
    ex.execute("tk", kq, cache=False)   # warm compile + stacks
    d0 = planner.dispatches
    ex.execute("tk", kq, cache=False)
    dpq = planner.dispatches - d0
    extra["translate_keyed_count_dispatches"] = dpq
    assert dpq == 1, f"warm keyed Count took {dpq} dispatches, want 1"

    _, keyed50, _ = _timer(lambda: ex.execute("tk", kq, cache=False),
                           max(20, N_LAT))
    ex.execute("ti", oq, cache=False)
    _, id50, _ = _timer(lambda: ex.execute("ti", oq, cache=False),
                        max(20, N_LAT))
    extra["translate_keyed_p50_ms"] = round(keyed50, 3)
    extra["translate_id_p50_ms"] = round(id50, 3)
    extra["translate_keyed_vs_id_p50_ratio"] = round(keyed50 / id50, 2)

    # Batched vs per-key host path, 10k keys (satellite a's whole point).
    n_keys = 10_000
    fresh = [f"alloc-{i}" for i in range(n_keys)]
    s_batch, s_loop = TranslateStore(), TranslateStore()
    t0 = time.perf_counter()
    s_batch.translate_keys(fresh)
    t_batch = time.perf_counter() - t0
    t0 = time.perf_counter()
    for k in fresh:
        s_loop.translate_key(k)
    t_loop = time.perf_counter() - t0
    alloc_speedup = t_loop / t_batch
    extra["translate_batch_alloc_speedup_10k"] = round(alloc_speedup, 1)
    assert alloc_speedup >= 10, \
        f"batched alloc only {alloc_speedup:.1f}x per-key, want >= 10x"
    t_read_b = min(_t_once(lambda: s_batch.translate_keys(fresh))
                   for _ in range(5))
    t_read_l = min(_t_once(lambda: [s_batch.translate_key(k)
                                    for k in fresh]) for _ in range(5))
    extra["translate_batch_read_speedup_10k"] = round(t_read_l / t_read_b, 1)

    # Resolver storm: 4096 existing keys per call, planes on vs off.
    storm_keys = [f"c{int(c)}" for c in
                  rng.choice(n_cols, 4096, replace=False)]
    kidx.translate_store.translate_keys(storm_keys)

    def storm():
        lats = _hist()
        for _ in range(30):
            t0 = time.perf_counter()
            ids = ex._resolve_keys(kidx, None, storm_keys)
            lats.observe(time.perf_counter() - t0)
        assert all(v is not None for v in ids)
        return len(storm_keys) / (_p50(lats) / 1e3)

    os.environ["PILOSA_TPU_TRANSLATE_PLANES"] = "on"
    try:
        storm()   # warm: plane build + probe compile outside the timing
        on_kps = storm()
        os.environ["PILOSA_TPU_TRANSLATE_PLANES"] = "off"
        off_kps = storm()
    finally:
        del os.environ["PILOSA_TPU_TRANSLATE_PLANES"]
    extra["translate_storm_keys_per_s_planes_on"] = round(on_kps)
    extra["translate_storm_keys_per_s_planes_off"] = round(off_kps)
    extra["translate_storm_planes_ratio"] = round(on_kps / off_kps, 2)
    extra["translate_plane_debug"] = ex.keyplanes.debug()
    planner.close()


def _t_once(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# config 3b: streaming ingestion (import stream + WAL group commit +
# ingest/query isolation)
# ---------------------------------------------------------------------------


def bench_ingest(extra):
    import tempfile
    import threading

    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.server.httpclient import HTTPInternalClient, NodeHTTPError
    from pilosa_tpu.server.node import ServerNode
    from pilosa_tpu.cluster.node import URI, Node
    from pilosa_tpu.storage.wal import WalWriter

    n = ServerNode(bind="127.0.0.1:0", use_planner=False,
                   qos_max_concurrent=8, ingest_max_inflight_mb=64)
    n.open()
    client = HTTPInternalClient(timeout=120)
    try:
        base = n.address
        peer = Node(id=f"127.0.0.1:{n.port}",
                    uri=URI(host="127.0.0.1", port=n.port))

        def post(path, body):
            import urllib.request
            r = urllib.request.Request(base + path, data=body.encode(),
                                       method="POST")
            with urllib.request.urlopen(r, timeout=60) as resp:
                return resp.read()

        post("/index/ing", "{}")
        post("/index/ing/field/v",
             json.dumps({"options": {"type": "int", "min": -100_000,
                                     "max": 100_000}}))
        post("/index/ing/field/f", "{}")
        rng = np.random.default_rng(23)
        n_shards, per_shard = 8, 250_000
        total = n_shards * per_shard
        reqs = []
        for s in range(n_shards):
            cols = (s * SHARD_WIDTH
                    + rng.choice(SHARD_WIDTH, per_shard,
                                 replace=False).astype(np.uint64))
            vals = rng.integers(-100_000, 100_000, per_shard)
            reqs.append({"kind": "field", "index": "ing", "field": "v",
                         "shard": s, "rowIDs": None, "columnIDs": cols,
                         "values": vals, "clear": False})
        # warm the apply path (fresh fields each timed trial below)
        client.send_import_stream(peer, reqs[:1])
        rates = []
        for t in range(3):
            fname = f"v{t}"
            post(f"/index/ing/field/{fname}",
                 json.dumps({"options": {"type": "int", "min": -100_000,
                                         "max": 100_000}}))
            trial = [dict(r, field=fname) for r in reqs]
            t0 = time.perf_counter()
            client.send_import_stream(peer, trial)
            rates.append(total / (time.perf_counter() - t0) / 1e6)
        extra["bsi_import_stream_mvals_per_s"] = round(
            statistics.median(rates), 2)

        # interactive p99 while the stream hammers the node
        body = json.dumps({
            "rowIDs": rng.integers(0, 8, 100_000).tolist(),
            "columnIDs": rng.integers(0, n_shards * SHARD_WIDTH,
                                      100_000).tolist()})
        post("/index/ing/field/f/import", body)

        def q99(k):
            h = _hist()
            for i in range(k):
                t0 = time.perf_counter()
                post("/index/ing/query", f"Count(Row(f={i % 8}))")
                h.observe(time.perf_counter() - t0)
            return _p99(h)

        q99(10)  # warm
        stop = threading.Event()

        def ingest():
            t = 0
            while not stop.is_set():
                fname = f"bg{t % 2}"
                try:
                    post(f"/index/ing/field/{fname}",
                         json.dumps({"options": {"type": "int",
                                                 "min": -100_000,
                                                 "max": 100_000}}))
                    client.send_import_stream(
                        peer, [dict(r, field=fname) for r in reqs])
                except (NodeHTTPError, ConnectionError, OSError):
                    pass
                t += 1

        th = threading.Thread(target=ingest, daemon=True)
        th.start()
        try:
            extra["import_while_query_p99_ms"] = round(q99(40), 3)
        finally:
            stop.set()
            th.join(timeout=120)
    finally:
        client.close()
        n.close()

    # WAL group commit: fsyncs per million values at a bulk batch size,
    # concurrent appenders sharing the flush window.
    with tempfile.TemporaryDirectory() as td:
        w = WalWriter(os.path.join(td, "g.wal"), fsync_appends=True,
                      group_window=0.002)
        n_threads, appends, batch = 8, 40, 25_000
        rows = np.ones(batch, dtype=np.uint64)
        cols = np.arange(batch, dtype=np.uint64)

        def run():
            for _ in range(appends):
                w.append("addBatch", rows, cols)

        threads = [threading.Thread(target=run) for _ in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mvals = n_threads * appends * batch / 1e6
        extra["wal_group_commit_fsyncs_per_mval"] = round(w.fsyncs / mvals, 2)
        extra["wal_group_commit_mvals_per_s"] = round(
            mvals / (time.perf_counter() - t0), 2)
        w.close()


# ---------------------------------------------------------------------------
# config 4: time-quantum views
# ---------------------------------------------------------------------------


def bench_time(extra):
    from pilosa_tpu.core import Holder, FieldOptions
    from pilosa_tpu.core.field import FIELD_TYPE_TIME
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    cols = 8_000_000
    n_bits = 120_000
    rng = np.random.default_rng(19)
    h = Holder()
    idx = h.create_index("t")
    f = idx.create_field("f", FieldOptions(type=FIELD_TYPE_TIME,
                                           time_quantum="YMDH"))
    import datetime as dt
    base = dt.datetime(2019, 1, 1)
    stamps = [base + dt.timedelta(hours=int(x))
              for x in rng.integers(0, 24 * 90, n_bits)]
    f.import_bits(np.ones(n_bits, dtype=np.uint64),
                  _rand_positions(rng, n_bits, cols), stamps)

    ex = Executor(h, planner=MeshPlanner(h, make_mesh()))
    q = ("Count(Row(f=1, from='2019-01-15T00:00', to='2019-03-15T00:00'))")
    ex.execute("t", q)
    _, p50, _ = _timer(lambda: ex.execute("t", q), N_LAT)
    extra["time_range_count_p50_ms"] = round(p50, 3)


# ---------------------------------------------------------------------------
# config 5: 4-node cluster GroupBy + Count
# ---------------------------------------------------------------------------


def bench_cluster(extra):
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    n_shards = 256  # 268M cols over 4 nodes
    cols = n_shards * SHARD_WIDTH
    rng = np.random.default_rng(23)

    lc = LocalCluster(
        4, planner_factory=lambda i: None)  # per-node planner below
    for cn in lc.nodes:
        cn.executor.planner = MeshPlanner(cn.holder, make_mesh())
    lc.create_index("c")
    lc.create_field("c", "a")
    lc.create_field("c", "b")

    # Import straight into each shard's owning node (the API's shard
    # routing, api.go:920, minus the HTTP hop).
    cl0 = lc.nodes[0].cluster
    groups = cl0.shards_by_node(cl0.nodes, "c", list(range(n_shards)))
    node_by_id = {cn.id: cn for cn in lc.nodes}
    n_bits = 4_000_000
    for fld, n_rows in (("a", 4), ("b", 8)):
        rows = rng.integers(0, n_rows, n_bits).astype(np.uint64)
        colsv = _rand_positions(rng, n_bits, cols)
        shard_of = (colsv // np.uint64(SHARD_WIDTH)).astype(np.int64)
        for node_id, shs in groups.items():
            mask = np.isin(shard_of, shs)
            node_by_id[node_id].handle_import_request(
                "c", fld, rows=rows[mask], cols=colsv[mask])

    q_count = "Count(Intersect(Row(a=1), Row(b=2)))"
    q_group = "GroupBy(Rows(a), Rows(b))"
    lc.query("c", q_count)
    lc.query("c", q_group)
    # Cached = the system behavior for any repeated read; cold bypasses
    # the coordinator's result cache so every remote node and device
    # program runs (remote nodes still use THEIR caches, as they would
    # in production — only the measured query is forced cold).
    qps, p50, _ = _timer(lambda: lc.query("c", q_count), N_LAT, threads=8)
    extra["cluster4_count_qps"] = round(qps, 1)
    extra["cluster4_count_p50_ms"] = round(p50, 3)
    # Uncached threaded fan-out: the wire/mux/device-reduce tax, with
    # the coordinator's result cache out of the way (remote nodes keep
    # theirs, as in production). This is the headline metric for the
    # distributed fan-out cost.
    qps_u, _, _ = _timer(lambda: lc.query("c", q_count, cache=False),
                         N_LAT, threads=8)
    extra["cluster4_count_uncached_qps"] = round(qps_u, 1)
    # Single-node comparator on the SAME data: how much of one node's
    # throughput the 4-node fan-out retains (1.0 = fan-out is free).
    single = LocalCluster(1, planner_factory=lambda i: None)
    single.nodes[0].executor.planner = MeshPlanner(
        single.nodes[0].holder, make_mesh())
    single.create_index("c")
    single.create_field("c", "a")
    single.create_field("c", "b")
    rng1 = np.random.default_rng(23)
    for fld, n_rows in (("a", 4), ("b", 8)):
        rows = rng1.integers(0, n_rows, n_bits).astype(np.uint64)
        colsv = _rand_positions(rng1, n_bits, cols)
        single.nodes[0].handle_import_request("c", fld, rows=rows,
                                              cols=colsv)
    single.query("c", q_count)
    qps_1, _, _ = _timer(lambda: single.query("c", q_count, cache=False),
                         N_LAT, threads=8)
    extra["single_node_count_uncached_qps"] = round(qps_1, 1)
    extra["cluster_vs_single_node_ratio"] = round(
        qps_u / qps_1, 3) if qps_1 else 0.0
    # Device-sync link floor inside the cluster series: the fixed
    # device round-trip every uncached fan-out leg pays at least once.
    import jax
    import jax.numpy as jnp
    _tiny = jax.device_put(np.arange(8, dtype=np.int32))
    _sumf = jax.jit(lambda v: jnp.sum(v))
    int(_sumf(_tiny))
    floors = []
    for _ in range(3):
        t0 = time.perf_counter()
        int(_sumf(_tiny))
        floors.append(time.perf_counter() - t0)
    extra["cluster4_device_sync_floor_ms"] = round(
        statistics.median(floors) * 1e3, 2)
    _, p50c, _ = _timer(lambda: lc.query("c", q_count, cache=False),
                     max(5, N_LAT // 3))
    extra["cluster4_count_cold_p50_ms"] = round(p50c, 3)
    _, p50g, _ = _timer(lambda: lc.query("c", q_group), max(5, N_LAT // 3))
    extra["cluster4_groupby_p50_ms"] = round(p50g, 3)
    _, p50gc, _ = _timer(lambda: lc.query("c", q_group, cache=False),
                      max(5, N_LAT // 3))
    extra["cluster4_groupby_cold_p50_ms"] = round(p50gc, 3)
    extra["cluster4_cols"] = cols


# ---------------------------------------------------------------------------
# config 6b: plan-keyed result cache — hit/miss economics + dashboard qps
# ---------------------------------------------------------------------------


def bench_cache(extra):
    """Result-cache economics on the repeated-dashboard workload: a
    fixed panel of read queries re-served by a 2-node cluster while a
    writer churns ONE shard. Hits must be order(s)-of-magnitude cheaper
    than the cold path, and selective (per-shard) invalidation must
    keep the hit ratio high despite the write churn."""
    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    n_shards = 64
    cols = n_shards * SHARD_WIDTH
    rng = np.random.default_rng(31)
    lc = LocalCluster(2, planner_factory=lambda i: None)
    for cn in lc.nodes:
        cn.executor.planner = MeshPlanner(cn.holder, make_mesh())
    lc.create_index("d")
    lc.create_field("d", "a")
    lc.create_field("d", "b")
    cl0 = lc.nodes[0].cluster
    groups = cl0.shards_by_node(cl0.nodes, "d", list(range(n_shards)))
    node_by_id = {cn.id: cn for cn in lc.nodes}
    n_bits = 2_000_000
    for fld, n_rows in (("a", 4), ("b", 8)):
        rows = rng.integers(0, n_rows, n_bits).astype(np.uint64)
        colsv = _rand_positions(rng, n_bits, cols)
        shard_of = (colsv // np.uint64(SHARD_WIDTH)).astype(np.int64)
        for node_id, shs in groups.items():
            mask = np.isin(shard_of, shs)
            node_by_id[node_id].handle_import_request(
                "d", fld, rows=rows[mask], cols=colsv[mask])
    for cn in lc.nodes:
        cn.dirty.flush_now()

    panel = [
        "Count(Row(a=1))",
        "Count(Intersect(Row(a=1), Row(b=2)))",
        "TopN(a, n=5)",
        "Count(Union(Row(a=0), Row(b=3)))",
        "Count(Row(b=1))",
    ]
    for q in panel:  # warm: populate coordinator + remote-leg caches
        lc.query("d", q)

    # hit vs miss service time on the heaviest panel query
    q = panel[1]
    _, hit_p50, _ = _timer(lambda: lc.query("d", q), N_LAT)
    _, miss_p50, _ = _timer(lambda: lc.query("d", q, cache=False),
                            max(5, N_LAT // 3))
    extra["cache_hit_p50_ms"] = round(hit_p50, 4)
    extra["cache_miss_p50_ms"] = round(miss_p50, 3)
    extra["cache_hit_speedup"] = round(miss_p50 / max(hit_p50, 1e-9), 1)

    # repeated dashboard, cached vs cold, same workload both times
    def dashboard():
        for qq in panel:
            lc.query("d", qq)

    def dashboard_cold():
        for qq in panel:
            lc.query("d", qq, cache=False)

    qps, _, _ = _timer(dashboard, N_LAT, threads=4)
    extra["cache_dashboard_qps"] = round(qps * len(panel), 1)
    qps_c, _, _ = _timer(dashboard_cold, max(5, N_LAT // 3), threads=4)
    extra["cache_dashboard_cold_qps"] = round(qps_c * len(panel), 1)
    extra["cache_dashboard_qps_gain"] = round(qps / max(qps_c, 1e-9), 1)

    extra["cache_bytes"] = lc[0].executor.result_cache.total_bytes

    assert extra["cache_hit_speedup"] >= 10, \
        f"hit p50 must be >=10x faster than miss: {extra['cache_hit_speedup']}"
    assert qps > qps_c, "cached dashboard qps must beat the cold path"

    # churn-under-storm half, re-expressed as the ``dashboard_storm``
    # loadgen scenario: a bursty repeated dashboard panel with a churn
    # ingest trickle invalidating shards underneath it. Selective
    # (per-shard) invalidation is what keeps the report's hit ratio
    # high despite the writes.
    from pilosa_tpu.loadgen import get_scenario, run_scenario

    sc = get_scenario("dashboard_storm")
    sc.duration_s = float(os.environ.get("BENCH_SCENARIO_SECONDS", "12"))
    rep = run_scenario(sc)
    extra["cache_storm_scenario"] = sc.name
    extra["cache_storm_qps"] = rep["arrivals"]["rateAchieved"]
    extra["cache_storm_p50_ms"] = \
        rep["perClass"]["interactive"]["client"]["p50Ms"]
    extra["cache_storm_p99_ms"] = \
        rep["perClass"]["interactive"]["client"]["p99Ms"]
    extra["cache_storm_hit_ratio"] = rep["cache"]["hitRatio"]
    assert rep["cache"]["hitRatio"] >= 0.5, \
        f"churned dashboard hit ratio collapsed: {rep['cache']['hitRatio']}"


# ---------------------------------------------------------------------------
# config 7: backup / restore throughput
# ---------------------------------------------------------------------------


def bench_backup(extra):
    """Backup + restore MB/s through the real subsystem: a 2-node
    cluster with durable stores is captured into a LocalDirArchive and
    rebuilt onto a fresh 2-node cluster."""
    import shutil
    import tempfile

    from pilosa_tpu.backup import BackupWriter, LocalDirArchive, RestoreJob
    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.config import SHARD_WIDTH

    tmp = tempfile.mkdtemp(prefix="pilosa-bench-backup-")
    try:
        n_shards = 8
        rng = np.random.default_rng(7)
        dirs = [os.path.join(tmp, f"src{i}") for i in range(2)]
        lc = LocalCluster(2, replica_n=1, data_dirs=dirs)
        lc.create_index("bk")
        lc.create_field("bk", "f")
        n_bits = 1_000_000
        rows = rng.integers(0, 64, n_bits).astype(np.uint64)
        cols = _rand_positions(rng, n_bits, n_shards * SHARD_WIDTH)
        shard_of = (cols // np.uint64(SHARD_WIDTH)).astype(np.int64)
        cl0 = lc.nodes[0].cluster
        groups = cl0.shards_by_node(cl0.nodes, "bk", list(range(n_shards)))
        node_by_id = {cn.id: cn for cn in lc.nodes}
        for node_id, shs in groups.items():
            mask = np.isin(shard_of, shs)
            node_by_id[node_id].handle_import_request(
                "bk", "f", rows=rows[mask], cols=cols[mask])
        for cn in lc.nodes:
            cn.store.flush()

        archive = LocalDirArchive(os.path.join(tmp, "archive"))
        n0 = lc[0]
        w = BackupWriter(n0.holder, n0.cluster, lc.client, n0.store,
                         archive)
        t0 = time.perf_counter()
        manifest = w.run()
        dt = time.perf_counter() - t0
        stored = sum(e["size"] for e in manifest["files"])
        extra["backup_mb"] = round(stored / 1e6, 2)
        extra["backup_mb_s"] = round(stored / 1e6 / dt, 1)

        dirs2 = [os.path.join(tmp, f"dst{i}") for i in range(2)]
        lc2 = LocalCluster(2, replica_n=1, data_dirs=dirs2)
        n = lc2[0]
        t0 = time.perf_counter()
        res = RestoreJob(n.holder, n.cluster, lc2.client, archive,
                         manifest["id"], store=n.store).run()
        dt = time.perf_counter() - t0
        extra["restore_mb_s"] = round(res["bytes"] / 1e6 / dt, 1)
        for cn in lc.nodes + lc2.nodes:
            cn.store.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# config: elastic resize — grow + shrink under a live query loop
# ---------------------------------------------------------------------------


def bench_elastic(extra):
    """Serve-through resize re-expressed as a thin loadgen scenario: a
    replica_n=2 cluster serving an open-loop mixed read stream while a
    node joins mid-run and a member is removed later (the ``elastic``
    scenario's chaos timeline). Queries must serve through both
    cutovers with zero client-visible failures, and the report's
    resize counters show the volume migrated over the PTS1 stream."""
    from pilosa_tpu.loadgen import ManagedTarget, get_scenario, run_scenario

    sc = get_scenario("elastic")
    # Never truncate past the chaos timeline — both resizes must fire.
    sc.duration_s = max(
        float(os.environ.get("BENCH_SCENARIO_SECONDS", "20")),
        max(c.at_s for c in sc.chaos) + 4.0)
    # Own the target so the coordinator's /debug/vars (where the resize
    # job counts its streamed volume) is still readable after the run.
    target = ManagedTarget(n_nodes=sc.nodes, replica_n=sc.replica_n,
                           node_opts=sc.node_opts)
    try:
        rep = run_scenario(sc, target=target)
        # The resize job counts its volume on whichever node held the
        # coordinator role — sum across the surviving members.
        dvars = {}
        for i in range(len(target.nodes)):
            for k, v in target.debug_vars(i).get("counters", {}).items():
                dvars[k] = dvars.get(k, 0) + v
    finally:
        target.close()
    inter = rep["perClass"]["interactive"]
    failures = sum(v["counts"]["error"] for v in rep["perClass"].values())
    chaos_ok = [c for c in rep["chaos"] if c["ok"]]
    extra["elastic_scenario"] = sc.name
    extra["elastic_ops"] = rep["arrivals"]["dispatched"]
    extra["elastic_query_failures"] = failures
    extra["elastic_p50_ms"] = inter["client"]["p50Ms"]
    extra["elastic_p99_ms"] = inter["client"]["p99Ms"]
    extra["elastic_chaos_applied"] = len(chaos_ok)
    extra["elastic_bytes_streamed_mb"] = round(
        dvars.get("cluster.resize.bytesStreamed", 0) / 1e6, 2)
    extra["elastic_shards_migrated"] = int(
        dvars.get("cluster.resize.shardsMigrated", 0))
    assert failures == 0, f"{failures} queries failed across the resizes"
    assert len(chaos_ok) == len(rep["chaos"]) == 2, \
        f"resize chaos actions did not all apply: {rep['chaos']}"


# ---------------------------------------------------------------------------
# config 8: overload resilience — 4x oversubscription with a slow peer
# ---------------------------------------------------------------------------


def bench_overload(extra):
    """The overload-resilience drill, re-expressed as a thin loadgen
    scenario config: an oversubscribed open-loop arrival stream into a
    3-node replica_n=2 cluster whose node1 turns gray mid-run (slower
    than the deadline) and later heals. Admission must shed the excess
    (not queue it), the slow peer's breaker must open, hedged reads
    must absorb it, and no query may surface a hard failure. The
    measurement machinery (arrivals, mix, SLO report) all lives in
    pilosa_tpu/loadgen — this function only maps report fields onto
    the bench's historical keys."""
    from pilosa_tpu.loadgen import get_scenario, run_scenario

    sc = get_scenario("overload")
    sc.duration_s = float(os.environ.get("BENCH_SCENARIO_SECONDS", "15"))
    rep = run_scenario(sc)
    inter = rep["perClass"]["interactive"]
    failures = sum(v["counts"]["error"] for v in rep["perClass"].values())
    extra["overload_scenario"] = sc.name
    extra["overload_ops"] = rep["arrivals"]["dispatched"]
    extra["overload_admitted"] = inter["counts"]["ok"]
    extra["overload_shed"] = rep["rates"]["shed"]
    extra["overload_shed_rate"] = inter["shedRate"]
    extra["overload_deadline_misses"] = rep["rates"]["deadlineMiss"]
    extra["overload_failures"] = failures
    extra["overload_admitted_p50_ms"] = inter["client"]["p50Ms"]
    extra["overload_admitted_p99_ms"] = inter["client"]["p99Ms"]
    extra["overload_hedge_fired"] = rep["rates"]["hedgeFired"]
    extra["overload_hedge_won"] = rep["rates"]["hedgeWon"]
    if rep["rates"]["hedgeFired"]:
        extra["overload_hedge_win_rate"] = round(
            rep["rates"]["hedgeWon"] / rep["rates"]["hedgeFired"], 3)
    extra["overload_breaker_opens"] = rep["rates"]["breakerOpens"]
    extra["overload_cache_hit_ratio"] = rep["cache"]["hitRatio"]
    # The layer's contract, enforced: the slow peer never surfaces as a
    # client-visible failure, and its breaker actually opened.
    assert failures == 0, f"{failures} queries failed via the slow peer"
    assert rep["rates"]["breakerOpens"] >= 1, \
        "slow peer's breaker never opened"
    assert rep["rates"]["hedgeFired"] >= 1, \
        "hedge never fired against the slow peer"


# ---------------------------------------------------------------------------
# config 9: observability overhead — profiled vs unprofiled query storm
# ---------------------------------------------------------------------------


def bench_obs(extra):
    """Observability overhead A/B (the profiling-cost acceptance): an
    identical concurrent Count storm with per-query profiling ON (a
    QueryProfile activated around every call, exactly what the served
    ``?profile=true`` path does) vs OFF (every hook degenerates to one
    None contextvar read). The storm p50 must not move more than 3%.

    Methodology: the work unit is a device-bound TopN (per-query cost
    ~1 ms of dispatch, not pure-Python parse), so the fixed per-query
    bookkeeping cost is measured against a realistic denominator rather
    than a degenerate micro-query where GIL queueing amplifies any µs
    of extra service time into a p50 cliff. Rounds alternate OFF/ON so
    machine drift lands on both modes equally, and each mode's p50 is
    the min across rounds — the standard noise-robust estimator."""
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import profile as obs_profile
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    rng = np.random.default_rng(29)
    total = 8 * SHARD_WIDTH
    h = Holder()
    idx = h.create_index("ob")
    f = idx.create_field("f")
    f.import_bits(rng.integers(0, 64, 4_000_000),
                  rng.integers(0, total, 4_000_000, dtype=np.uint64))
    planner = MeshPlanner(h, make_mesh())
    ex = Executor(h, planner=planner)
    q = "TopN(f, n=8)"
    ex.execute("ob", q, cache=False)  # compile + warm stacks

    storm_threads = min(THREADS, 8)
    storm_q = max(min(N_QUERIES, 192), 96)

    def storm(profiled):
        lats = _hist()

        def one(i):
            tok = None
            if profiled:
                tok = obs_profile.activate(obs_profile.QueryProfile(
                    f"bench-{i}", query=q, index="ob"))
            t0 = time.perf_counter()
            try:
                ex.execute("ob", q, cache=False)
            finally:
                dt = time.perf_counter() - t0
                if tok is not None:
                    prof = obs_profile.current()
                    obs_profile.deactivate(tok)
                    prof.finish()
            lats.observe(dt)

        with ThreadPoolExecutor(max_workers=storm_threads) as pool:
            list(pool.map(one, range(storm_q)))
        return _p50(lats)

    storm(False)
    storm(True)  # warm both code paths before measuring
    off_rounds: list[float] = []
    on_rounds: list[float] = []
    for _ in range(4):
        off_rounds.append(storm(False))
        on_rounds.append(storm(True))
    on50 = min(on_rounds)
    off50 = min(off_rounds)
    overhead = (on50 - off50) / off50
    extra["obs_storm_p50_ms_profile_on"] = round(on50, 3)
    extra["obs_storm_p50_ms_profile_off"] = round(off50, 3)
    extra["obs_profile_overhead_pct"] = round(overhead * 100, 2)
    planner.close()
    assert overhead <= 0.03, \
        f"profiling overhead {overhead * 100:.2f}% > 3%"


# ---------------------------------------------------------------------------


def main() -> None:
    import jax

    want = (set(c.strip() for c in CONFIGS.split(","))
            if CONFIGS != "all"
            else {"star", "topn", "bsi", "sketch", "dispatch", "translate",
                  "ingest", "time", "cluster", "cache", "oversub", "backup",
                  "overload", "obs", "elastic"})
    backend = jax.default_backend()
    # A speed measured off the chip is not a speed of this system: any
    # platform but tpu is fatal, unless the caller itself asked for the
    # CPU (a smoke of the bench's own plumbing; the output says so).
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: JAX backend is {backend!r}, not 'tpu'; set "
            "JAX_PLATFORMS=cpu to run the plumbing on the CPU on purpose")
    extra: dict = {"backend": backend, "devices": len(jax.devices())}

    # Boot-time buffer-pool reserve, exactly as `pilosa-tpu server` does
    # (config import-pool-mb): fault the import block/staging pages once,
    # before any timed window, so imports measure the import — not this
    # hypervisor's first-touch fault rate (~0.7-2 GB/s vs 8 GB/s warm;
    # THP is unavailable here: AnonHugePages stays 0 under madvise).
    from pilosa_tpu import native as _native
    extra["pool_reserved_mb"] = _native.pool_reserve(1024 << 20) >> 20

    # Host-speed canary: every import metric is bound by this shared
    # vCPU, whose effective speed swings >2x hour to hour (observed
    # cpu_threaded_qps 9.3-27.9 and import 54-122 Mbit/s for identical
    # code). A fixed memset rate recorded in the same run lets a reader
    # normalize import numbers across runs instead of attributing host
    # weather to the code.
    buf = np.empty(1 << 28, dtype=np.uint8)
    buf[:] = 1  # fault pages outside the timed window
    t0 = time.perf_counter()
    for v in (2, 3, 4):
        buf[:] = v
    extra["host_canary_memset_gbps"] = round(
        3 * buf.nbytes / (time.perf_counter() - t0) / 1e9, 2)
    del buf

    qps = cpu_qps = None
    t_all = time.perf_counter()
    if "star" in want:
        qps, cpu_qps = bench_star_trace(extra)
    for name, fn in (("topn", bench_topn), ("bsi", bench_bsi),
                     ("sketch", bench_sketch),
                     ("dispatch", bench_dispatch),
                     ("translate", bench_translate),
                     ("ingest", bench_ingest),
                     ("time", bench_time), ("cluster", bench_cluster),
                     ("cache", bench_cache),
                     ("oversub", bench_oversubscribed),
                     ("backup", bench_backup),
                     ("overload", bench_overload),
                     ("obs", bench_obs),
                     ("elastic", bench_elastic)):
        if name in want:
            t0 = time.perf_counter()
            try:
                fn(extra)
            except Exception as e:  # pragma: no cover
                extra[f"{name}_error"] = repr(e)
            extra[f"{name}_setup_plus_bench_s"] = round(
                time.perf_counter() - t0, 1)
    extra["total_s"] = round(time.perf_counter() - t_all, 1)

    if qps is None:  # star config skipped: report first available metric
        print(json.dumps({"metric": "bench_subset", "value": 0,
                          "unit": "n/a", "vs_baseline": 0, "extra": extra}))
        _fail_on_errors(extra)
        return
    print(json.dumps({
        "metric": "count_intersect_qps_1b_cols_executor",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / cpu_qps, 2),
        "extra": extra,
    }))
    _fail_on_errors(extra)


def _fail_on_errors(extra: dict) -> None:
    """CI-style guard (VERDICT r2 #3): a config crash must be LOUD — the
    JSON line above still prints, but the process exits non-zero so a
    shipped bench run can never silently carry a *_error key."""
    errors = {k: v for k, v in extra.items() if k.endswith("_error")}
    if errors:
        print(f"BENCH FAILED: {errors}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
