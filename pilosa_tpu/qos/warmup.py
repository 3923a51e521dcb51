"""Kernel warmup: precompile the canonical XLA programs at node start
so steady-state traffic never pays the cold 100+ ms compile/link cost.

The MeshPlanner's program cache (``_fn_cache``) is keyed by the query's
*structural* signature — leaf slots, not field or index names — and XLA
itself caches per input shape (``s_pad`` = shard count padded to the
device mesh). So running canonical query shapes against a throwaway
schema-only index warms exactly the programs real traffic will hit, for
every configured shard-count bucket.

The scratch index lives in a *private* Holder: nothing is broadcast to
peers, written to disk, or visible in the schema, and since the node's
planner finds no fragments for it, the leaf stacks are all-zeros — leaf
*content* never shapes a compile, only structure and shard count do.
After the run we drop the scratch entries from the planner's stack/plan
caches (``MeshPlanner.drop_index``); the compiled programs stay.
"""

from __future__ import annotations

import logging
import threading
import time

from pilosa_tpu.core.field import FIELD_TYPE_INT, FieldOptions
from pilosa_tpu.core.holder import Holder

logger = logging.getLogger("pilosa_tpu.qos")

SCRATCH_INDEX = "qos-warmup-scratch"

#: canonical kernel families: the query classes whose first request
#: would otherwise pay a compile.
KIND_COUNT = "count"
KIND_TOPN = "topn"
KIND_BSI = "bsi"
DEFAULT_KINDS = (KIND_COUNT, KIND_TOPN, KIND_BSI)

DEFAULT_SHARD_COUNTS = (1, 8, 32)

#: BSI compiles are depth-shaped, so warm one common depth (values
#: up to ~1e6).
_INT_MAX = 1 << 20

_QUERIES = {
    KIND_COUNT: (
        "Count(Row(f=1))",
        "Count(Intersect(Row(f=1), Row(g=1)))",
        "Count(Union(Row(f=1), Row(g=1)))",
        "Count(Difference(Row(f=1), Row(g=1)))",
    ),
    KIND_TOPN: (
        "TopN(f, n=10)",
        "TopN(f, Row(g=1), n=10)",
        "TopN(f, Intersect(Row(f=1), Row(g=1)), n=10)",
    ),
    KIND_BSI: (
        "Sum(field=v)",
        "Sum(Row(f=1), field=v)",
        "Count(Row(v > 0))",
        "Count(Row(v >< [0, 100]))",
        "Min(field=v)",
        "Max(field=v)",
    ),
}


class WarmupService:
    """Runs canonical query shapes through a planner at node start.

    ``planner`` is the node's live MeshPlanner (its program cache is the
    thing being warmed); the queries execute via a throwaway standalone
    Executor over a private Holder so warmup can never fan out to peers
    or touch the node's real schema/storage.
    """

    def __init__(self, planner, kinds=DEFAULT_KINDS,
                 shard_counts=DEFAULT_SHARD_COUNTS, stats=None,
                 observed=None, observed_schema=None):
        self.planner = planner
        self.kinds = tuple(k for k in kinds if k in DEFAULT_KINDS)
        self.shard_counts = tuple(sorted({int(s) for s in shard_counts
                                          if int(s) > 0})) or (1,)
        self._stats = stats
        #: query shapes observed by the previous incarnation's planner
        #: (warmup.json entries: index/query/shards) replayed after the
        #: canonical set, over ``observed_schema`` — the persisted
        #: schema, so field structure (BSI depth, keys) compiles the
        #: same programs live traffic will hit.
        self.observed = list(observed or [])
        self.observed_schema = list(observed_schema or [])
        self.programs_compiled = 0
        self.queries_run = 0
        self.replayed = 0
        self.errors = 0
        self.seconds = 0.0
        #: persistent-compile-cache hits observed DURING this warmup —
        #: on a second boot this is the canonical+replayed program set
        #: loading from disk instead of compiling (the deterministic
        #: signal the cold-start CI job asserts on).
        self.cache_hits = 0
        self.done = threading.Event()

    def run(self) -> dict:
        """Synchronous warmup; always safe to call (per-query failures
        are counted, never raised — a broken warmup query must not take
        down node start)."""
        t0 = time.perf_counter()
        try:
            from pilosa_tpu.parallel import compile_cache
            hits_before = compile_cache.stats()["hits"]
        except Exception:
            hits_before = None
        try:
            self._run_queries()
        except Exception:
            self.errors += 1
            logger.exception("kernel warmup aborted")
        finally:
            self.seconds = time.perf_counter() - t0
            if hits_before is not None:
                try:
                    from pilosa_tpu.parallel import compile_cache
                    self.cache_hits = \
                        compile_cache.stats()["hits"] - hits_before
                except Exception:
                    pass
            self.done.set()
            if self._stats is not None:
                self._stats.count("qos.warmupRuns", 1)
                self._stats.count("qos.warmupPrograms", self.programs_compiled)
                # Failures above are caught and logged so a node still
                # boots; the counter is how a caller sees them.
                self._stats.count("qos.warmupErrors", self.errors)
                if self.replayed:
                    self._stats.count("qos.warmupReplayed", self.replayed)
                if self.cache_hits:
                    self._stats.count("qos.warmupCacheHits", self.cache_hits)
                self._stats.timing("qos.warmupSeconds", self.seconds)
            logger.info(
                "kernel warmup: %d programs compiled (%d queries, %d errors)"
                " over shard buckets %s in %.2fs (%d compile-cache hits)",
                self.programs_compiled, self.queries_run, self.errors,
                self.shard_counts, self.seconds, self.cache_hits)
        return {"programs": self.programs_compiled,
                "queries": self.queries_run,
                "errors": self.errors, "seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits}

    def start(self, name: str = "qos-warmup") -> threading.Thread:
        t = threading.Thread(target=self.run, name=name, daemon=True)
        t.start()
        return t

    def _run_queries(self) -> None:
        from pilosa_tpu.exec.executor import Executor

        if self.planner is None:
            return
        scratch = Holder()
        idx = scratch.create_index(SCRATCH_INDEX)
        idx.create_field("f")
        idx.create_field("g")
        idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT,
                                           min=0, max=_INT_MAX))
        ex = Executor(scratch, planner=self.planner, result_cache=False)
        before = len(getattr(self.planner, "_fn_cache", {}))
        try:
            for n in self.shard_counts:
                shards = list(range(n))
                for kind in self.kinds:
                    for q in _QUERIES[kind]:
                        try:
                            ex.execute(SCRATCH_INDEX, q, shards=shards)
                            self.queries_run += 1
                        except Exception:
                            self.errors += 1
                            logger.exception("warmup query failed: %s "
                                             "(shards=%d)", q, n)
        finally:
            # Scratch leaf stacks / plans out of the live planner's
            # caches; compiled programs are what we came for and stay.
            drop = getattr(self.planner, "drop_index", None)
            if drop is not None:
                drop(SCRATCH_INDEX)
        self._replay_observed()
        self.programs_compiled = \
            len(getattr(self.planner, "_fn_cache", {})) - before

    def _replay_observed(self) -> None:
        """Replay the previous incarnation's observed traffic shapes
        (warmup.json) through the planner: same private-Holder trick as
        the canonical set, but over the persisted schema, so a restarted
        node precompiles the programs its OWN workload runs."""
        from pilosa_tpu.exec.executor import Executor

        if not self.observed or self.planner is None:
            return
        replay = Holder()
        try:
            replay.apply_schema(self.observed_schema)
        except Exception:
            logger.exception("warmup replay: persisted schema unusable")
            return
        ex = Executor(replay, planner=self.planner, result_cache=False)
        names = set()
        try:
            for entry in self.observed:
                try:
                    iname = entry["index"]
                    query = entry["query"]
                    n = max(1, int(entry.get("shards", 1)))
                except (KeyError, TypeError, ValueError):
                    continue
                if replay.index(iname) is None:
                    continue
                names.add(iname)
                try:
                    ex.execute(iname, query, shards=list(range(n)))
                    self.queries_run += 1
                    self.replayed += 1
                except Exception:
                    self.errors += 1
                    logger.exception("warmup replay failed: %s (%s, "
                                     "shards=%d)", query, iname, n)
        finally:
            drop = getattr(self.planner, "drop_index", None)
            if drop is not None:
                for iname in names:
                    drop(iname)
