"""Is a pooled stack matrix ever reused under its own transfer?

`MeshPlanner._build_stack` writes a row stack into a host matrix from
the recycled page pool and drops its reference right after the transfer
call; the chunk goes back to the pool when the runtime lets go of the
matrix. If the runtime let go before it had read the matrix, the next
build would zero and refill the chunk under the transfer and the device
would hold a mix of two rows.

This builds an index in process, gives the planner room for ``--fit``
stacks, and has two threads (the prefetcher's worker count) fetch rows
round-robin through `_stack_rows`, so every fetch is a build, an upload
and an eviction, back to back with nothing waiting for a transfer. Every
stack is then read back from the device and compared with a host rebuild
(`Fragment.row_words`, the route that shares no code with the build).

    chiprun -- python scripts/stack_readback_check.py          # 954 shards
    python scripts/stack_readback_check.py --shards 5 --uploads 24

One JSON line; exit 1 on a mismatch, on fewer evictions than uploads
less ``--fit``, or when the pool recycled nothing (nothing was tested).
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np


def run(shards: int, rows: int, bits: int, uploads: int, fit: int) -> dict:
    import jax

    from pilosa_tpu import native
    from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import residency
    from pilosa_tpu.obs.stats import MemoryStats
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    rng = np.random.default_rng(29)
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    base = np.arange(shards, dtype=np.uint64) * np.uint64(SHARD_WIDTH)
    for row in range(rows):
        # Row 0 is kept as dense words (the copy route), the others as
        # positions (the native scatter); duplicates just merge.
        n = SHARD_WIDTH // 20 if row == 0 else bits
        cols = (rng.integers(0, SHARD_WIDTH, (shards, n), dtype=np.uint64)
                + base[:, None]).reshape(-1)
        f.import_bits(np.full(len(cols), row, dtype=np.uint64), cols)
    shard_ids = tuple(range(shards))
    stats = MemoryStats()
    planner = MeshPlanner(h, make_mesh(), stats=stats)
    s_pad = planner._pad(shards)
    planner.max_cache_bytes = fit * residency.dense_nbytes(s_pad)

    def rebuild(row: int) -> np.ndarray:
        want = np.zeros((s_pad, WORDS_PER_SHARD), dtype=np.uint32)
        for i, shard in enumerate(shard_ids):
            want[i] = h.fragment("i", "f", "standard", shard).row_words(row)
        return want

    want = [rebuild(row) for row in range(rows)]
    pool0 = native.pool_stats() or {}
    built: queue.Queue = queue.Queue()

    def worker(k: int) -> None:
        for n in range(k, uploads, 2):
            row = n % rows
            built.put((row, planner._stack_rows(idx, "f", "standard", row,
                                                shard_ids)))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    mismatches = []
    for n in range(uploads):
        row, arr = built.get(timeout=600)
        if not np.array_equal(np.asarray(arr), want[row]):
            mismatches.append(n)
    for t in threads:
        t.join(timeout=600)
    seconds = time.perf_counter() - t0
    pool1 = native.pool_stats() or {}
    cache = planner.cache_stats()
    planner.close()
    return {
        "platform": jax.devices()[0].platform,
        "shards": shards, "rows": rows, "fit": fit, "seconds": seconds,
        "uploads": cache["uploads"], "evictions": cache["evictions"],
        "readbacks": uploads, "mismatches": mismatches,
        "pool_recycled": (pool1.get("recycled_allocs", 0)
                          - pool0.get("recycled_allocs", 0)),
        "pool_fresh": (pool1.get("fresh_mmaps", 0)
                       - pool0.get("fresh_mmaps", 0)),
        "stackRows": {r: stats.counter_value(f"planner.stackRows.{r}")
                      for r in ("scattered", "copied", "coo", "numpy")},
        "stackBuilds": {r: stats.counter_value(f"planner.stackBuilds.{r}")
                        for r in ("native", "perRow")},
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", type=int, default=954)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--bits", type=int, default=5243,
                    help="set bits a shard of a positions row")
    ap.add_argument("--uploads", type=int, default=140)
    ap.add_argument("--fit", type=int, default=3,
                    help="stacks the planner's budget holds")
    a = ap.parse_args(argv)
    if a.rows % 2 or a.rows // 2 <= a.fit:
        # Each thread walks every other row: its own cycle alone has to
        # overflow the budget, or a thread that runs ahead only hits.
        ap.error("--rows has to be even and more than twice --fit")
    out = run(a.shards, a.rows, a.bits, a.uploads, a.fit)
    ok = (not out["mismatches"] and out["pool_recycled"] > 0
          and out["evictions"] >= a.uploads - a.fit - 2)
    print(json.dumps({"ok": ok, **out}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
