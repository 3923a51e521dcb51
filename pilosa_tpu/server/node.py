"""ServerNode — one full pilosa-tpu node process.

Reference: server.go (Server :46 wires holder+cluster+executor,
receiveMessage :569-663) and server/server.go (Command :60, SetupServer
:222). Assembles Holder + Cluster + Executor(+MeshPlanner) + API +
HTTPServer, wires the control-plane message and import handlers, and
runs the anti-entropy ticker.
"""

from __future__ import annotations

import threading

from pilosa_tpu.cluster.cluster import STATE_NORMAL, Cluster
from pilosa_tpu.cluster.event import EVENT_UPDATE
from pilosa_tpu.cluster.harness import handle_cluster_message
from pilosa_tpu.cluster.node import URI, Node
from pilosa_tpu.cluster.sync import HolderSyncer
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.server.api import API
from pilosa_tpu.server.httpclient import HTTPInternalClient
from pilosa_tpu.server.httpd import HTTPServer


class ServerNode:
    """A runnable node (reference `pilosa server`, cmd/server.go:64)."""

    #: default repair cadence, seconds (repair must be ON by default —
    #: a killed-and-restarted node converges with no operator
    #: action). The reference's default is 10 minutes
    #: (server.go antiEntropyInterval); ours is short because repairs
    #: are cheap host diffs.
    DEFAULT_ANTI_ENTROPY_INTERVAL = 10.0
    #: failure-detector sweep cadence, seconds (reference: memberlist's
    #: SWIM probes + confirmNodeDown cluster.go:1724).
    DEFAULT_CHECK_NODES_INTERVAL = 5.0
    #: buffer-pool top-up check cadence, seconds (imports adopt pool
    #: chunks as permanent fragment storage; the pool re-faults the
    #: deficit in the background).
    POOL_TOPUP_INTERVAL = 30.0
    #: background scrub cadence, seconds: re-verify on-disk snapshot
    #: CRCs and repair quarantined fragments from replica consensus.
    #: Longer than anti-entropy — a scrub re-reads every snapshot file.
    DEFAULT_SCRUB_INTERVAL = 60.0

    def __init__(self, bind: str = "127.0.0.1:10101",
                 peers: list[str] | None = None,
                 replica_n: int = 1,
                 use_planner: bool = True,
                 anti_entropy_interval: float | None = None,
                 check_nodes_interval: float | None = None,
                 scrub_interval: float | None = None,
                 backup_interval: float = 0.0,
                 archive_url: str | None = None,
                 backup_full_every: int = 8,
                 backup_keep_chains: int = 2,
                 max_op_n: int | None = None,
                 join: str | None = None,
                 data_dir: str | None = None,
                 tls_cert: str | None = None,
                 tls_key: str | None = None,
                 tls_ca_cert: str | None = None,
                 tls_skip_verify: bool | None = None,
                 trace_endpoint: str | None = None,
                 import_pool_mb: int = 0,
                 qos_max_concurrent: int = 0,
                 qos_max_queue: int = 64,
                 qos_internal_reserve: int = 4,
                 qos_class_weights: dict[str, int] | None = None,
                 qos_default_deadline: float = 0.0,
                 qos_slow_query_ms: float = 500.0,
                 qos_warmup: str = "",
                 qos_warmup_shards: str = "1,8,32",
                 quarantine_keep_n: int = 0,
                 qos_adaptive: bool = False,
                 qos_tenant_rate: float = 0.0,
                 qos_tenant_burst: float = 0.0,
                 breaker_threshold: int = 0,
                 breaker_cooldown: float = 5.0,
                 hedge: bool = False,
                 hedge_delay_ms: float = 0.0,
                 hedge_budget_pct: float = 5.0,
                 chaos_faults: bool = False,
                 fence_stale_reads: bool = False,
                 compile_cache_dir: str | None = None,
                 plan_buckets: str = "pow2",
                 result_cache_mb: int = 64,
                 result_cache_ttl: float = 0.0,
                 device_reduce: str = "auto",
                 multiplex: bool = True,
                 ingest_transpose: str = "auto",
                 wal_group_commit_ms: float = 0.0,
                 ingest_max_inflight_mb: int = 0,
                 dispatch_fuse: str = "auto",
                 dispatch_coalesce: str = "auto",
                 dispatch_coalesce_us: float = 150.0,
                 inline_transfer: str = "auto",
                 residency_packed: str = "auto",
                 translate_planes: str = "auto",
                 sketch_precision: int = 12,
                 sketch_exact_threshold: int = 1024,
                 profile_ring_n: int = 64,
                 profile_queries: bool = True):
        host, _, port = bind.partition(":")
        self.host, self.port = host or "127.0.0.1", int(port or 10101)
        # Node identity IS the address — member ids are built the same
        # way, so local_id always matches its ring entry.
        self.id = f"{self.host}:{self.port}"
        self.data_dir = data_dir
        #: address of a running cluster member to join on open()
        #: (dynamic membership: the coordinator runs a ResizeJob and
        #: broadcasts the new topology back to us).
        self.join_addr = join

        # Membership: boot peer list (each "host:port" becomes a Node);
        # joins/leaves after boot go through the coordinator's resize
        # flow (handle_join / resize below). A TLS node assumes a
        # uniformly-TLS cluster (the reference's model too): every peer
        # URI gets the https scheme and internal RPC skips verification
        # (operators deploying internal CAs can front their own certs).
        scheme = "https" if tls_cert else "http"
        members = []
        all_addrs = sorted(set((peers or []) + [f"{self.host}:{self.port}"]))
        for i, addr in enumerate(all_addrs):
            h, _, p = addr.partition(":")
            members.append(Node(id=addr,
                                uri=URI(scheme=scheme, host=h, port=int(p)),
                                is_coordinator=(i == 0 and join is None)))
        self.cluster = None
        if len(members) > 1 or join is not None:
            self.cluster = Cluster(local_id=self.id, nodes=members,
                                   replica_n=replica_n,
                                   client=HTTPInternalClient(
                                       ca_cert=tls_ca_cert,
                                       skip_verify=tls_skip_verify))
            self.cluster.set_state(STATE_NORMAL)
            if join is not None:
                # A fresh joiner owns NO topology: start BELOW version 0
                # so even a cluster still on its boot ring (version 0 —
                # no resize ever committed) can hand us its status
                # through the strictly-newer adoption gate. Found by the
                # chaos soak: a joiner whose address was already in the
                # boot ring wedged solo because the re-admission status
                # carried version 0 and 0 <= 0 read as stale. A
                # persisted topology (restart of an admitted joiner)
                # overrides this below.
                self.cluster.topology_version = -1
        self._scheme = scheme

        from pilosa_tpu.obs import MemoryStats
        self.stats = MemoryStats()
        from pilosa_tpu.obs.logger import StandardLogger
        self.logger = StandardLogger()
        self.tracer = None
        if trace_endpoint:
            # Concrete exporter behind the Tracer protocol (reference
            # tracing/opentracing Jaeger glue): spans from this node
            # stream to the OTLP collector at the given endpoint.
            from pilosa_tpu.obs import OTLPTracer, set_tracer
            self.tracer = OTLPTracer(endpoint=trace_endpoint,
                                     service_name=f"pilosa-tpu:{self.id}")
            set_tracer(self.tracer)
        self.dirty = None
        index_listener = None
        if self.cluster is not None:
            from pilosa_tpu.cluster.dirty import DirtyBroadcaster
            self.dirty = DirtyBroadcaster(self.cluster)
            index_listener = self.dirty.attach
        self.holder = Holder(fragment_listener=self._broadcast_shard,
                             index_listener=index_listener)
        # Persistent XLA compilation cache: pointed at disk BEFORE the
        # planner exists, so its very first jit compile already reads
        # through the cache — a restarted node reuses every kernel
        # prior runs compiled. The directory is part of the cache key,
        # so it must not move between boots: JAX_COMPILATION_CACHE_DIR
        # wins where the caller set it, then an explicit
        # compile_cache_dir, then one fixed path in the checkout
        # (compile_cache.resolve_dir). "off" disables explicitly.
        self.compile_cache_dir = ""
        planner = None
        if use_planner:
            from pilosa_tpu.parallel import compile_cache
            self.compile_cache_dir = compile_cache.resolve_dir(
                compile_cache_dir)
            compile_cache.enable(self.compile_cache_dir, stats=self.stats)
            # A planner that was asked for and cannot be built is a
            # start-up error: serving every query from the per-shard
            # host path instead would hide that the device is gone.
            # use_planner=False (--no-planner) is the explicit way to
            # run without one.
            from pilosa_tpu.parallel import MeshPlanner
            try:
                planner = MeshPlanner(self.holder,
                                      bucket_policy=plan_buckets,
                                      stats=self.stats,
                                      coalesce_window_us=dispatch_coalesce_us)
            except Exception as e:
                self.logger.printf("planner start-up failed: %r", e)
                raise
        # Plan-keyed result cache (pilosa_tpu.cache): byte-bounded,
        # tenant-partitioned, shared by every consumer on this node.
        # <= 0 MB disables (the executor then runs every query).
        self.result_cache = None
        if result_cache_mb > 0:
            from pilosa_tpu.cache import ResultCache
            self.result_cache = ResultCache(
                max_bytes=int(result_cache_mb) << 20,
                ttl=result_cache_ttl, stats=self.stats)
        self.executor = Executor(self.holder, cluster=self.cluster,
                                 node_id=self.id, planner=planner,
                                 stats=self.stats,
                                 result_cache=self.result_cache)
        if self.cluster is not None:
            # Remote legs report their shard-epoch vectors back here
            # (cluster.run_remote → RemoteEpochTable) so coordinator
            # cache stamps stay consistent across nodes.
            self.cluster.epoch_sink = self.executor.remote_epochs.observe
        self.api = API(self.holder, self.executor, cluster=self.cluster)
        # Handler hooks used by the HTTP router's /internal routes.
        self.api.message_handler = self.handle_message
        self.api.import_handler = self.handle_internal_import
        self.api.resize_handler = self.resize
        # QoS front: admission gate + default deadline + slow-query log.
        # max_concurrent=0 (the constructor default) leaves the gate
        # open — metrics/slow-log only — so embedded/test nodes keep the
        # old dispatch behavior unless explicitly configured.
        from pilosa_tpu.qos import (
            AdaptiveLimit,
            AdmissionController,
            SlowQueryLog,
            TenantQuotas,
        )
        adaptive = None
        if qos_adaptive and qos_max_concurrent > 0:
            # qos-max-concurrent becomes the CEILING; the operative
            # limit follows goodput (probe one step, keep what served
            # more; multiplicative back-off when goodput falls).
            adaptive = AdaptiveLimit(ceiling=qos_max_concurrent,
                                     stats=self.stats)
        self.qos = AdmissionController(
            max_concurrent=qos_max_concurrent,
            max_queue=qos_max_queue,
            internal_reserve=qos_internal_reserve,
            weights=qos_class_weights,
            default_deadline=qos_default_deadline,
            stats=self.stats,
            slow_log=SlowQueryLog(threshold_ms=qos_slow_query_ms,
                                  stats=self.stats),
            adaptive=adaptive)
        self.api.qos = self.qos
        # Per-query cost profiles: retain the slowest N at
        # /debug/queries; profile_queries=False limits profiling to
        # explicit ?profile=true requests (the zero-overhead posture —
        # every hook degenerates to one None contextvar read).
        self.profile_ring = None
        if profile_ring_n > 0:
            from pilosa_tpu.obs import ProfileRing
            self.profile_ring = ProfileRing(capacity=profile_ring_n)
        self.api.profile_ring = self.profile_ring
        self.api.profile_default = bool(profile_queries)
        # Per-tenant token buckets above class admission (429 vs the
        # gate's 503: "you are over YOUR limit" vs "I am over mine").
        self.quotas = None
        if qos_tenant_rate > 0:
            self.quotas = TenantQuotas(rate_per_s=qos_tenant_rate,
                                       burst=qos_tenant_burst or None,
                                       stats=self.stats)
        self.api.quotas = self.quotas
        # Overload plumbing on the inter-node path: per-peer circuit
        # breakers in the transport, hedged read legs in map_reduce.
        if self.cluster is not None:
            if breaker_threshold > 0:
                from pilosa_tpu.cluster.breaker import BreakerRegistry
                self.cluster.client.breakers = BreakerRegistry(
                    threshold=breaker_threshold,
                    cooldown=breaker_cooldown,
                    stats=self.stats)
            if hedge and replica_n > 1:
                from pilosa_tpu.cluster.breaker import HedgePolicy
                self.cluster.hedge = HedgePolicy(
                    delay_s=hedge_delay_ms / 1000.0,
                    budget_pct=hedge_budget_pct,
                    stats=self.stats)
        #: chaos/fault hook: injected per-query latency (seconds) on
        #: this node's /query handling — the slow-peer gray failure.
        #: POST /internal/fault can only arm it when the operator
        #: opted in (chaos_faults); the route is not mounted otherwise.
        self.api.fault_slow_s = 0.0
        self.api.chaos_faults = bool(chaos_faults)
        if self.cluster is not None:
            # Quorum fencing knobs + the chaos partition fault table
            # (the table is always present; only the chaos-gated
            # /internal/fault route can arm it).
            self.cluster.fence_stale_reads = bool(fence_stale_reads)
            self.cluster.on_unfence = self._on_unfence
            from pilosa_tpu.cluster.faults import PartitionFaults
            self.cluster.client.faults = PartitionFaults()
        self._qos_warmup = qos_warmup
        self._qos_warmup_shards = qos_warmup_shards
        self.warmup = None
        self.http = HTTPServer(self.api, self.host, self.port,
                               tls_cert=tls_cert, tls_key=tls_key)
        self.port = self.http.port
        # Built AFTER the listener resolves an ephemeral bind port —
        # fragment_nodes on a standalone node must advertise an address
        # a client can actually dial (ADVICE r4 #2).
        self.api.local_node = Node(id=f"{self.host}:{self.port}",
                                   uri=URI(scheme=scheme, host=self.host,
                                           port=self.port),
                                   is_coordinator=True)

        self._import_pool_mb = int(import_pool_mb)
        self._pool_stop = threading.Event()
        self.syncer = None
        self.scrubber = None
        self._sync_timer: threading.Timer | None = None
        self._check_timer: threading.Timer | None = None
        self._scrub_timer: threading.Timer | None = None
        self._backup_timer: threading.Timer | None = None
        self._closed = False
        #: one resize job at a time (reference cluster.go:1447).
        self._resize_gate = threading.Lock()
        if self.cluster is not None:
            self.cluster.subscribe(self._on_node_event)
        self._anti_entropy_interval = (
            self.DEFAULT_ANTI_ENTROPY_INTERVAL
            if anti_entropy_interval is None else anti_entropy_interval)
        self._check_nodes_interval = (
            self.DEFAULT_CHECK_NODES_INTERVAL
            if check_nodes_interval is None else check_nodes_interval)
        self._scrub_interval = (
            self.DEFAULT_SCRUB_INTERVAL
            if scrub_interval is None else scrub_interval)
        #: unattended-DR knobs: with both --backup-interval and
        #: --archive-url set, open() starts a BackupScheduler ticking
        #: periodic incrementals into the archive (scheduler.py).
        self._backup_interval = float(backup_interval or 0.0)
        self._archive_url = archive_url
        self._backup_full_every = int(backup_full_every)
        self._backup_keep_chains = int(backup_keep_chains)
        self.backup_scheduler = None
        self.backup_archive = None
        # Device-side fold of remote bitmap legs (exec/device_reduce);
        # the PILOSA_TPU_DEVICE_REDUCE env var still overrides per-run.
        from pilosa_tpu.exec import device_reduce as _device_reduce
        _device_reduce.set_mode(device_reduce)
        # Device-side BSI bit-plane transpose for bulk value imports
        # (exec/ingest_transpose); PILOSA_TPU_INGEST_TRANSPOSE overrides.
        from pilosa_tpu.exec import ingest_transpose as _ingest_transpose
        _ingest_transpose.set_mode(ingest_transpose)
        # Query-dispatch knobs (README "Query dispatch"): fused one-
        # program-per-query plans, same-plan dispatch coalescing, and
        # inline transfer resolution. Env vars PILOSA_TPU_DISPATCH_FUSE /
        # _DISPATCH_COALESCE / _INLINE_TRANSFER override per-run.
        from pilosa_tpu.exec import fuse as _dispatch_fuse
        _dispatch_fuse.set_mode(dispatch_fuse)
        from pilosa_tpu.parallel import coalesce as _dispatch_coalesce
        _dispatch_coalesce.set_mode(dispatch_coalesce)
        from pilosa_tpu.parallel import batcher as _transfer_batcher
        _transfer_batcher.set_inline_mode(inline_transfer)
        # Device-residency knob (README "Device residency"):
        # container-classed packed leaf stacks. Env var
        # PILOSA_TPU_RESIDENCY_PACKED overrides per-run.
        from pilosa_tpu.exec import residency as _residency
        _residency.set_mode(residency_packed)
        # Key-translation planes (README "Key translation"); env var
        # PILOSA_TPU_TRANSLATE_PLANES overrides per-run.
        from pilosa_tpu.exec import keyplane as _keyplane
        _keyplane.set_mode(translate_planes)
        # Approximate-analytics knobs (README "Approximate analytics");
        # PILOSA_TPU_SKETCH_PRECISION / _SKETCH_EXACT_THRESHOLD
        # override per-run.
        from pilosa_tpu import sketch as _sketch
        _sketch.set_precision(sketch_precision)
        _sketch.set_exact_threshold(sketch_exact_threshold)
        # In-flight byte budget for the /internal/import-stream pipeline
        # (0 = unbounded); trips 429 + Retry-After, never queues.
        from pilosa_tpu.qos import IngestGate
        self.ingest_gate = IngestGate(
            max_inflight_bytes=int(ingest_max_inflight_mb) << 20)
        self.api.ingest_gate = self.ingest_gate
        if self.cluster is not None:
            self.cluster.stats = self.stats
            self.cluster.client.stats = self.stats
            self.cluster.client.multiplex = multiplex
            self.syncer = HolderSyncer(self.holder, self.cluster,
                                       self.cluster.client)
            # Coordinator-primary key allocation (translate.go:93 model):
            # every keyed allocation routes to the coordinator.
            from pilosa_tpu.cluster.translate_sync import ClusterKeyTranslator
            translator = ClusterKeyTranslator(self.holder, self.cluster,
                                              self.cluster.client)
            self.executor.translator = translator
            self.api.translator = translator

        if data_dir:
            from pilosa_tpu.storage.diskstore import DiskStore
            kw = {} if max_op_n is None else {"max_op_n": max_op_n}
            self.store = DiskStore(data_dir, self.holder, stats=self.stats,
                                   quarantine_keep_n=quarantine_keep_n,
                                   wal_group_window=wal_group_commit_ms
                                   / 1000.0,
                                   **kw)
            self.store.open()
        else:
            self.store = None
        self.api.store = self.store
        if self.store is not None and self.cluster is not None:
            self._wire_topology_persistence(data_dir)
        if self.store is not None:
            from pilosa_tpu.cluster.scrub import (
                Scrubber,
                route_quarantined_to_replicas,
            )
            if self.cluster is not None:
                # Placement must not hand quarantined shards to this
                # node; route their reads to replicas instead.
                self.cluster.blocked_shards_fn = \
                    self.store.quarantine.blocked_shards
                route_quarantined_to_replicas(self.holder, self.cluster,
                                              self.store, stats=self.stats)
            self.scrubber = Scrubber(
                self.holder, self.cluster,
                self.cluster.client if self.cluster is not None else None,
                self.store, stats=self.stats, logger=self.logger,
                admission=self.qos)
        # Backup/restore driver hooks (POST /backup, /restore). One run
        # of each at a time; jobs run off the request thread and
        # /backup/status, /restore/status read their live progress.
        self._backup_gate = threading.Lock()
        self._restore_gate = threading.Lock()
        self._backup_writer = None
        self._restore_job = None
        if self.store is not None:
            self.api.backup_handler = self.handle_backup
            self.api.backup_status_handler = self.backup_status
            self.api.restore_handler = self.handle_restore
            self.api.restore_status_handler = self.restore_status
        self.api.backup_debug_handler = self.backup_debug

    def _wire_topology_persistence(self, data_dir: str) -> None:
        """Durable topology (reference .topology file, cluster.go:1657):
        every committed nodes/version change is written to
        topology.json, and boot resumes from it. Without this, a
        restarted coordinator's in-memory version resets to 0, its next
        commit broadcasts "version 1", and every peer holding a higher
        version silently rejects the committed ring as stale — a forked
        cluster."""
        import json as _json
        import os as _os

        path = _os.path.join(data_dir, "topology.json")
        save_lock = threading.Lock()
        last_saved = [-1]

        def save() -> None:
            with self.cluster._lock:
                doc = {"version": self.cluster.topology_version,
                       "replicaN": self.cluster.replica_n,
                       "partitionN": self.cluster.partition_n,
                       "nodes": [n.to_json() for n in self.cluster.nodes]}
            # Serialize + version-guard the replace: two concurrent
            # savers (a status RPC and a sweep) must not interleave
            # writes in one tmp, and the one holding the OLDER snapshot
            # must not win the replace — a restart would restore the
            # older ring and fork the cluster (the bug this file
            # exists to prevent). Same pattern as DiskStore.save_schema.
            with save_lock:
                if doc["version"] < last_saved[0]:
                    return
                tmp = f"{path}.{_os.getpid()}.{threading.get_ident()}.tmp"
                with open(tmp, "w") as f:
                    _json.dump(doc, f)
                _os.replace(tmp, path)
                last_saved[0] = doc["version"]

        self.cluster.save_hook = save
        # Sweep tmps a crashed saver stranded (see DiskStore.open).
        try:
            for fn in _os.listdir(data_dir):
                if fn.startswith("topology.json.") and fn.endswith(".tmp"):
                    _os.remove(_os.path.join(data_dir, fn))
        except OSError:
            pass
        try:
            with open(path) as f:
                doc = _json.load(f)
            version = int(doc.get("version", 0))
            saved = [Node.from_json(n) for n in doc.get("nodes", [])]
        except Exception:
            # Best-effort restore: a torn/hand-edited file must fall
            # back to the boot peer list, never crash the boot.
            return
        if version <= self.cluster.topology_version or not saved:
            return
        if not any(n.id == self.id for n in saved):
            # The durable ring excludes US: we were removed while down.
            # Keep the boot list; rejoining is the operator's call.
            return
        self.cluster.nodes = sorted(saved, key=lambda n: n.id)
        self.cluster.topology_version = version
        # Settings adopted from broadcasts are part of the ring: a
        # restart that reverted to boot-config replicaN would compute
        # different placement and the cleaner would GC live replicas.
        if doc.get("replicaN"):
            self.cluster.replica_n = int(doc["replicaN"])
        if doc.get("partitionN"):
            self.cluster.partition_n = int(doc["partitionN"])
        last_saved[0] = version

    # -- lifecycle ---------------------------------------------------------

    def open(self) -> None:
        self.http.serve_background()
        if self._import_pool_mb > 0:
            # Fault the import buffer pool off the serving path — boot
            # keeps serving while pages warm (native recycled page pool;
            # the analog of the reference's mmap page cache being warm
            # for re-imported fragments, fragment.go:311). Then keep it
            # topped up: dense imports ADOPT pool-backed block arrays as
            # permanent fragment storage, permanently draining the
            # freelist, so a one-shot reserve would go cold after a few
            # bulk loads. The top-up loop re-faults the deficit in the
            # background whenever the free level falls below half the
            # configured size.
            def _warm(mb: int = self._import_pool_mb) -> None:
                from pilosa_tpu import native
                target = mb << 20
                native.pool_reserve(target)
                while not self._pool_stop.wait(self.POOL_TOPUP_INTERVAL):
                    stats = native.pool_stats()
                    if stats is None:
                        return
                    deficit = target - stats["free_bytes"]
                    if deficit > target // 2:
                        native.pool_reserve(deficit)
            threading.Thread(target=_warm, daemon=True,
                             name="pool-warm").start()
        if self.join_addr is not None:
            self._send_join()
        if self.syncer is not None and self._anti_entropy_interval > 0:
            self._schedule_sync()
        if self.cluster is not None and self._check_nodes_interval > 0:
            self._schedule_check_nodes()
        if self.scrubber is not None and self._scrub_interval > 0:
            self._schedule_scrub()
        if (self._backup_interval > 0 and self._archive_url
                and self.store is not None):
            from pilosa_tpu.backup import BackupScheduler, open_archive
            self.backup_archive = open_archive(self._archive_url,
                                               stats=self.stats)
            self.backup_scheduler = BackupScheduler(
                holder=self.holder, cluster=self.cluster,
                client=(self.cluster.client
                        if self.cluster is not None else None),
                store=self.store, archive=self.backup_archive,
                interval=self._backup_interval, node_id=self.id,
                stats=self.stats, logger=self.logger, admission=self.qos,
                full_every=self._backup_full_every,
                keep_chains=self._backup_keep_chains)
            self._schedule_backup()
        from pilosa_tpu.obs.runtime import RuntimeMonitor
        self.runtime_monitor = RuntimeMonitor(self.stats,
                                              self.executor.planner,
                                              qos=self.qos)
        self.runtime_monitor.start()
        if self._qos_warmup and self.executor.planner is not None:
            # Precompile the canonical kernel shapes in the background
            # (the planner's program cache is structural, so these
            # compiles serve real traffic); node start never blocks on
            # XLA.
            from pilosa_tpu.qos import WarmupService
            kinds = [k.strip() for k in self._qos_warmup.split(",")
                     if k.strip()]
            shard_counts = [int(s) for s in
                            str(self._qos_warmup_shards).split(",")
                            if s.strip()]
            observed, observed_schema = self._load_observed_traffic()
            self.warmup = WarmupService(self.executor.planner, kinds=kinds,
                                        shard_counts=shard_counts,
                                        observed=observed,
                                        observed_schema=observed_schema,
                                        stats=self.stats)
            self.warmup.start()

    #: join announcement retry schedule (seconds between attempts);
    #: after JOIN_RETRIES fast attempts the announcer drops to the slow
    #: cadence but never stops (a solo joiner has no other path in).
    JOIN_RETRY_DELAY = 1.0
    JOIN_RETRIES = 30
    JOIN_SLOW_RETRY_DELAY = 5.0

    def _send_join(self) -> None:
        """Announce to a running member in the background, retrying —
        the seed may still be booting (the reference's gossip join
        retries the same way, gossip/gossip.go:65). The member forwards
        to the coordinator, which resizes us in and broadcasts the
        topology back (cluster.go:1796)."""
        h, _, p = self.join_addr.partition(":")
        seed = Node(id=self.join_addr,
                    uri=URI(scheme=self._scheme, host=h, port=int(p)))

        def announce():
            import sys
            import time
            attempts = 0
            while not self._closed:
                # Success = this node appears in the ring (the topology
                # broadcast landed), NOT merely a delivered announce —
                # the coordinator's resize runs asynchronously and can
                # fail after accepting.
                if len(self.cluster.nodes) > 1:
                    return
                try:
                    self.cluster.client.send_message(
                        seed, {"type": "node-join", "addr": self.id})
                except Exception:
                    # A paused/overloaded seed times out (OSError, not
                    # ConnectionError); ANY failure here must not kill
                    # the announce thread — it is a solo joiner's only
                    # path into the ring.
                    pass
                attempts += 1
                if attempts == self.JOIN_RETRIES:
                    # Never give up outright: a solo joiner has no peers
                    # to discover the ring through, so announcing IS its
                    # only path in (the seed may be mid-resize, paused,
                    # or restarting for minutes). Drop to a slow cadence
                    # and warn.
                    print(f"join: cluster at {self.join_addr} did not "
                          f"admit us after {self.JOIN_RETRIES} attempts; "
                          f"retrying every "
                          f"{self.JOIN_SLOW_RETRY_DELAY:.0f}s",
                          file=sys.stderr)
                time.sleep(self.JOIN_RETRY_DELAY
                           if attempts < self.JOIN_RETRIES
                           else self.JOIN_SLOW_RETRY_DELAY)

        t = threading.Thread(target=announce, name="join-announce",
                             daemon=True)
        t.start()

    def _jitter(self, interval: float) -> float:
        import random
        return interval * random.uniform(0.8, 1.2)

    def _timer_tick_error(self, timer: str, err: BaseException) -> None:
        """A background sweep (anti-entropy, scrub, backup, liveness)
        blew up. The tick must survive — the next one retries — but a
        wedged sweep has to be VISIBLE: silent passes here turn 'the
        failure detector died an hour ago' into an unexplained outage."""
        self.stats.count("node.timerTickError")
        self.logger.printf("%s timer tick failed: %s: %s",
                           timer, type(err).__name__, err)

    def _on_unfence(self) -> None:
        """Fence lifted (the liveness sweep sees a majority again):
        this node just rejoined from a minority partition, so its data
        AND caches may be behind the majority's writes. Kick an
        immediate dirty-sync — schema adoption + fragment anti-entropy
        — and flush epoch-validated result caches, off the sweep
        thread (same shape as the READY-event repair)."""
        if self._closed:
            return
        self.logger.printf("quorum regained: un-fenced, starting "
                           "rejoin dirty-sync")

        def resync():
            try:
                for iname in self.holder.index_names():
                    idx = self.holder.index(iname)
                    if idx is not None:
                        # Local caches validated against pre-partition
                        # epochs would serve stale reads until the next
                        # write; bump first so repaired bits are seen.
                        idx.epoch.bump(notify=False)
                if self.cluster is not None:
                    self._sync_schema()
                if self.syncer is not None:
                    self.syncer.sync_holder()
            except Exception:
                pass  # the anti-entropy ticker retries
        threading.Thread(target=resync, name="unfence-resync",
                         daemon=True).start()

    def _on_node_event(self, ev) -> None:
        """NodeEvent consumer (reference ReceiveEvent, cluster.go:1754):
        count the stream, and when a peer comes BACK, kick an immediate
        repair pass instead of waiting out the anti-entropy ticker."""
        self.stats.with_tags(f"event:{ev.type}").count("nodeEvents")
        if (ev.type == EVENT_UPDATE and ev.state == "READY"
                and self.syncer is not None and not self._closed):
            def repair():
                try:
                    self._sync_schema()
                    self.syncer.sync_holder()
                except Exception:
                    pass  # ticker retries
            threading.Thread(target=repair, name="event-repair",
                             daemon=True).start()
        if (ev.type == EVENT_UPDATE and ev.state == "READY"
                and self.cluster is not None and not self._closed):
            # A rejoined peer missed every index-dirty broadcast while
            # it was (or merely LOOKED) down — its epoch-validated
            # result caches would serve stale reads until the next
            # write. Push it a full invalidation sweep; and flush our
            # own caches too, since the asymmetric case (it was serving
            # writes we never heard about) leaves OUR caches stale.
            def invalidate(node_id=ev.node_id):
                node = self.cluster.node_by_id(node_id)
                for iname in self.holder.index_names():
                    idx = self.holder.index(iname)
                    if idx is not None:
                        idx.epoch.bump(notify=False)
                    if node is None:
                        continue
                    try:
                        self.cluster.client.send_message(
                            node, {"type": "index-dirty", "index": iname})
                    except (ConnectionError, RuntimeError, LookupError):
                        pass  # next sweep's READY flap retries
            threading.Thread(target=invalidate, name="rejoin-invalidate",
                             daemon=True).start()

    def _sync_schema(self) -> None:
        """Adopt any peer schema this node is missing (a restarted
        member without its data dir re-learns indexes/fields before the
        fragment syncer can repair their bits) AND merge peers' shard
        availability — the additive half of the reference's NodeStatus
        merge (server.go:640: schema + availableShards). Without the
        availability half, a node that missed create-shard broadcasts
        while down answers queries without those shards forever (found
        by the chaos soak: permanent undercounts after rejoin)."""
        for node in self.cluster.nodes:
            if node.id == self.id or node.state == "DOWN":
                continue
            try:
                self.holder.apply_schema(self.cluster.client.schema(node))
            except (ConnectionError, RuntimeError, LookupError, KeyError):
                continue
            try:
                avail = self.cluster.client.availability(node)
                for index, fields in (avail or {}).items():
                    idx = self.holder.index(index)
                    if idx is None:
                        continue
                    for field, shards in fields.items():
                        f = idx.field(field)
                        if f is not None and shards:
                            f.add_remote_available_shards(shards)
            except (ConnectionError, RuntimeError, LookupError, KeyError,
                    AttributeError):
                continue

    def _schedule_sync(self) -> None:
        def tick():
            try:
                from pilosa_tpu.cluster.translate_sync import sync_translation
                self._sync_schema()
                applied = sync_translation(self.holder, self.cluster,
                                           self.cluster.client)
                repaired = self.syncer.sync_holder()
                self.clean_holder()  # ownership GC backstop
                if applied:
                    self.stats.count("antiEntropyTranslateApplied", applied)
                if repaired:
                    self.stats.count("antiEntropyRepaired", repaired)
                self.stats.count("antiEntropyPasses")
            except Exception as e:
                # Next tick retries; repairs must never kill the node —
                # but the failure must be visible, not swallowed.
                self._timer_tick_error("anti-entropy", e)
            finally:
                if not self._closed:
                    self._schedule_sync()
        self._sync_timer = threading.Timer(
            self._jitter(self._anti_entropy_interval), tick)
        self._sync_timer.daemon = True
        self._sync_timer.start()

    def _schedule_scrub(self) -> None:
        def tick():
            try:
                res = self.scrubber.scrub_pass()
                if res.get("mismatch"):
                    self.stats.count("integrity.scrubMismatchFragments",
                                     res["mismatch"])
            except Exception as e:
                # Next tick retries; the scrub must never kill the node.
                self._timer_tick_error("scrub", e)
            finally:
                if not self._closed:
                    self._schedule_scrub()
        self._scrub_timer = threading.Timer(
            self._jitter(self._scrub_interval), tick)
        self._scrub_timer.daemon = True
        self._scrub_timer.start()

    def _schedule_backup(self) -> None:
        # Tick at half the backup interval so a missed coordinator
        # handoff costs at most half a cycle; the scheduler's own
        # due/backoff gating makes extra ticks free.
        def tick():
            try:
                if self._backup_gate.acquire(blocking=False):
                    try:
                        self.backup_scheduler.tick()
                    finally:
                        self._backup_gate.release()
            except Exception as e:
                # scheduler.tick never raises; belt and braces.
                self._timer_tick_error("backup", e)
            finally:
                if not self._closed:
                    self._schedule_backup()
        self._backup_timer = threading.Timer(
            self._jitter(max(0.05, self._backup_interval / 2.0)), tick)
        self._backup_timer.daemon = True
        self._backup_timer.start()

    #: membership push/pull piggybacks on every Nth liveness sweep
    #: (full-ring pulls each sweep would double detector traffic).
    DISCOVER_EVERY_N_SWEEPS = 5

    def _schedule_check_nodes(self) -> None:
        def tick():
            try:
                from pilosa_tpu.cluster.resize import check_nodes
                self._sweep_n = getattr(self, "_sweep_n", 0) + 1
                changed = check_nodes(
                    self.cluster, self.cluster.client,
                    discover=(self._sweep_n %
                              self.DISCOVER_EVERY_N_SWEEPS == 0))
                if changed:
                    self.stats.count("checkNodesChanged", len(changed))
            except Exception as e:
                # A dead failure detector is the worst silent failure:
                # DOWN peers never get marked, writes hang on them.
                self._timer_tick_error("check-nodes", e)
            finally:
                if not self._closed:
                    self._schedule_check_nodes()
        self._check_timer = threading.Timer(
            self._jitter(self._check_nodes_interval), tick)
        self._check_timer.daemon = True
        self._check_timer.start()

    def close(self) -> None:
        self._closed = True
        self._pool_stop.set()
        if self.tracer is not None:
            from pilosa_tpu.obs import NopTracer, get_tracer, set_tracer
            if get_tracer() is self.tracer:
                set_tracer(NopTracer())  # don't leave a closed exporter
            self.tracer.close()
        if self.dirty is not None:
            self.dirty.close()
        if self.cluster is not None:
            self.cluster.close()
        # Stop accepting NEW connections first; handler threads are
        # daemons and may outlive this (the batcher resolves
        # synchronously after close for exactly that race).
        self.http.close()
        if self._sync_timer is not None:
            self._sync_timer.cancel()
        if self._check_timer is not None:
            self._check_timer.cancel()
        if self._scrub_timer is not None:
            self._scrub_timer.cancel()
        if self._backup_timer is not None:
            self._backup_timer.cancel()
        if self.backup_archive is not None:
            try:
                self.backup_archive.close()
            except Exception:
                pass
        if getattr(self, "runtime_monitor", None) is not None:
            self.runtime_monitor.close()
        if self.executor.planner is not None:
            self._save_observed_traffic()
            self.executor.planner.close()
        # The compile-cache counter sink holds a reference to our stats
        # object; drop it so short-lived embedded/test nodes don't pile
        # up in the module-level sink list.
        try:
            from pilosa_tpu.parallel import compile_cache
            compile_cache.detach(self.stats)
        except Exception:
            pass
        if self.store is not None:
            self.store.close()

    @property
    def address(self) -> str:
        return self.http.address

    # -- control plane -----------------------------------------------------

    def _broadcast_shard(self, index: str, field: str, view: str, shard: int):
        if self.cluster is None:
            return
        msg = {"type": "create-shard", "index": index, "field": field,
               "shard": shard}
        for node in self.cluster.nodes:
            if node.id == self.id or node.state == "DOWN":
                continue
            try:
                self.cluster.client.send_message(node, msg)
            except (ConnectionError, RuntimeError):
                pass

    def handle_message(self, message: dict) -> None:
        t = message.get("type")
        if t == "resize-instruction" and self.cluster is not None:
            from pilosa_tpu.cluster.resize import handle_resize_instruction
            handle_resize_instruction(self.holder, self.cluster.client,
                                      self.cluster, message, self.id)
        elif t == "resize-instruction-complete":
            from pilosa_tpu.cluster.resize import deliver_completion
            deliver_completion(message)
        elif t == "index-dirty":
            if (self.cluster is not None
                    and not self.cluster.check_fencing_token(message)):
                return  # stale coordinator's dirty coordination
            from pilosa_tpu.cluster.dirty import apply_index_dirty
            apply_index_dirty(self.holder, message,
                              self.executor.remote_epochs)
        elif t == "cluster-status" and self.cluster is not None:
            from pilosa_tpu.cluster.resize import apply_cluster_status
            apply_cluster_status(self.cluster, message["nodes"],
                                 holder=self.holder,
                                 availability=message.get("availability"),
                                 replica_n=message.get("replicaN"),
                                 partition_n=message.get("partitionN"),
                                 version=message.get("version"))
            # Topology changed: GC fragments this node no longer owns
            # (holderCleaner, holder.go:1126) off the RPC thread.
            threading.Thread(target=self.clean_holder,
                             name="holder-cleaner", daemon=True).start()
        elif t == "cluster-state" and self.cluster is not None:
            from pilosa_tpu.cluster.resize import apply_cluster_state
            apply_cluster_state(self.cluster, message["state"])
        elif t == "resize-begin" and self.cluster is not None:
            from pilosa_tpu.cluster.resize import apply_resize_begin
            apply_resize_begin(self.cluster, message)
        elif t == "resize-end" and self.cluster is not None:
            from pilosa_tpu.cluster.resize import apply_resize_end
            apply_resize_end(self.cluster, message)
        elif t == "resize-push" and self.cluster is not None:
            from pilosa_tpu.cluster.resize import handle_resize_push
            return handle_resize_push(self.holder, self.cluster.client,
                                      self.cluster, message)
        elif t == "resize-shard-cutover":
            from pilosa_tpu.cluster.resize import deliver_cutover
            deliver_cutover(message, self.cluster)
        elif t == "resize-dual-write-failed":
            from pilosa_tpu.cluster.resize import deliver_dual_write_failed
            deliver_dual_write_failed(message)
        elif t in ("delete-index", "delete-field", "delete-view"):
            # Apply to the holder (shared handler), then unlink the
            # on-disk tree: a peer that kept the stale files would
            # resurrect the deleted data into a recreated same-name
            # index/field/view on restart.
            handle_cluster_message(self.holder, message)
            if self.store is not None:
                prefix = [message["index"]]
                if t != "delete-index":
                    prefix.append(message["field"])
                if t == "delete-view":
                    prefix.append(message["view"])
                self.store.delete_subtree_files(*prefix)
        elif t == "node-join" and self.cluster is not None:
            self.handle_join(message["addr"])
        else:
            handle_cluster_message(self.holder, message)

    def handle_join(self, addr: str) -> str:
        """A node announced itself. Non-coordinators forward; the
        coordinator runs the add-resize (stream fragments, then commit +
        broadcast the topology — the joiner learns the ring from the
        cluster-status broadcast). Reference: eventReceiver -> nodeJoin
        -> resize job (gossip/gossip.go:364, cluster.go:1796)."""
        coord = self.cluster.coordinator()
        if coord is not None and coord.id == addr:
            # The flagged coordinator is announcing itself as a JOINER:
            # its process restarted without cluster state, so the node
            # every peer would forward this join to is precisely the one
            # that cannot handle it (found by the chaos soak — a
            # leaderless wedge where the solo ex-coordinator announced
            # into a ring that kept forwarding the announce back to it).
            # Deterministic handover: the first surviving member acts,
            # takes the flag, and the commit broadcast carries it.
            survivors = sorted(
                (n for n in self.cluster.nodes if n.id != addr),
                key=lambda n: (n.state == "DOWN", n.id))  # live first
            if not survivors:
                raise RuntimeError(
                    "no surviving member to take over the join")
            coord = survivors[0]
            if coord.id == self.id:
                # The handover is a TOPOLOGY CHANGE, not a local note:
                # bump the version, persist, and broadcast, or peers
                # (whose strictly-newer gate rejects same-version
                # views) would keep forwarding joins to the stateless
                # ex-coordinator and a restart would restore its flag
                # from the old topology.json.
                with self.cluster._lock:
                    for n in self.cluster.nodes:
                        n.is_coordinator = (n.id == self.id)
                    self.cluster.topology_version += 1
                    status = {"type": "cluster-status",
                              "nodes": [n.to_json()
                                        for n in self.cluster.nodes],
                              "replicaN": self.cluster.replica_n,
                              "partitionN": self.cluster.partition_n,
                              "version": self.cluster.topology_version}
                self.cluster.notify_topology()
                for n in self.cluster.nodes:
                    if n.id != self.id and n.state != "DOWN":
                        try:
                            self.cluster.client.send_message(n, status)
                        except Exception:
                            pass  # discovery pulls converge them later
                coord = self.cluster.node_by_id(self.id)
        if coord is None:
            raise RuntimeError("no coordinator to handle join")
        if coord.id != self.id:
            self.cluster.client.send_message(
                coord, {"type": "node-join", "addr": addr})
            return "FORWARDED"
        member = self.cluster.node_by_id(addr)
        if member is not None:
            # Idempotent re-admission: a joiner that is already in OUR
            # ring but keeps announcing missed the commit broadcast (it
            # is sitting solo, and a solo node has no peers to discover
            # the ring through). Re-send the committed topology so a
            # lost commit can never wedge a member outside the ring it
            # belongs to (found by the chaos soak, seed 104).
            from pilosa_tpu.cluster.resize import holder_availability
            status = {"type": "cluster-status",
                      "nodes": [n.to_json() for n in self.cluster.nodes],
                      "replicaN": self.cluster.replica_n,
                      "partitionN": self.cluster.partition_n,
                      "version": self.cluster.topology_version,
                      "availability": holder_availability(self.holder)}
            try:
                self.cluster.client.send_message(member, status)
            except (ConnectionError, RuntimeError):
                pass
            return "ALREADY_MEMBER"
        # Run the (possibly long) data-moving resize OFF the request
        # thread: the joiner's announce would otherwise time out on big
        # transfers and its retry would race a second job. The gate
        # makes duplicate/overlapping announces no-ops.
        if self._resize_gate.locked():
            return "RESIZING"

        def run():
            try:
                self.resize("add", addr=addr)
            except (RuntimeError, ConnectionError, ValueError):
                pass  # joiner keeps announcing; next attempt retries

        threading.Thread(target=run, name="join-resize",
                         daemon=True).start()
        return "STARTED"

    def resize(self, action: str, node_id: str | None = None,
               addr: str | None = None) -> str:
        """Coordinator-driven membership change (api.go RemoveNode :1220;
        node addition = reference's join-triggered resize). ONE job at a
        time (the reference's single-job state machine,
        cluster.go:1447): a second request while one runs is rejected."""
        if self.cluster is None:
            raise RuntimeError("standalone node cannot resize")
        # Resizes RUN on the flagged coordinator: the stuck-RESIZING
        # recovery heuristic consults the coordinator's state as the
        # resize authority, so a job running anywhere else would make
        # that heuristic (a) never recover if this node died mid-job,
        # or (b) falsely reopen peer gates while the job lives.
        # Non-coordinators REFUSE with the coordinator's address, like
        # the reference (cluster.go:1870) — forwarding fire-and-forget
        # would hide failures from the operator, and divergent
        # coordinator views could ping-pong the message forever.
        coord = self.cluster.coordinator()
        if coord is not None and coord.id != self.id:
            raise RuntimeError(
                "node removal requests are only valid on the coordinator "
                f"node: {coord.id}")
        from pilosa_tpu.cluster.node import URI, Node
        from pilosa_tpu.cluster.resize import ResizeJob
        new_nodes = [Node(id=n.id, uri=n.uri, is_coordinator=n.is_coordinator)
                     for n in self.cluster.nodes]
        if action == "remove":
            new_nodes = [n for n in new_nodes if n.id != node_id]
            if new_nodes and not any(n.is_coordinator for n in new_nodes):
                # Never commit a leaderless ring (joins would have no
                # authority to land on): hand the flag to this node —
                # the one running the job — else the first LIVE
                # survivor (a dead coordinator would route every
                # join/resize at a corpse).
                keep = next(
                    (n for n in new_nodes if n.id == self.id),
                    min(new_nodes,
                        key=lambda n: (n.state == "DOWN", n.id)))
                keep.is_coordinator = True
        elif action == "add":
            h, _, p = (addr or "").partition(":")
            new_nodes.append(Node(id=addr,
                                  uri=URI(scheme=self._scheme,
                                          host=h, port=int(p))))
        else:
            raise ValueError(f"unknown resize action {action!r}")
        if not self._resize_gate.acquire(blocking=False):
            raise RuntimeError("resize already in progress")
        try:
            job = ResizeJob(self.cluster, self.holder, self.cluster.client,
                            store=self.store)
            self.api.resize_job = job
            return job.run(new_nodes)
        finally:
            self._resize_gate.release()

    def clean_holder(self) -> int:
        """holderCleaner (holder.go:1126): drop fragments this node no
        longer owns; also runs as an anti-entropy backstop."""
        if self.cluster is None:
            return 0
        from pilosa_tpu.cluster.cleaner import clean_holder
        try:
            n = clean_holder(self.holder, self.cluster, store=self.store)
        except Exception:
            return 0  # GC must never take down the node
        if n:
            self.stats.count("holderCleanerRemoved", n)
        return n

    def handle_internal_import(self, req: dict) -> None:
        """/internal/import payloads: fragment-level (anti-entropy
        diff push) or field-level (routed import). Gated by cluster
        state like the public import surface (reference api.Import
        validates on the RECEIVING node too): a forwarded write must
        not land on a RESIZING owner whose fragments are mid-move.
        internal=True: peer-forwarded writes (replica fan-out legs,
        anti-entropy pushes, dual-apply) must land even on a FENCED
        receiver — they are how a minority heals, and the SENDER's
        fence already gated the client-facing write."""
        self.api._validate("import", internal=True)
        index, field = req["index"], req["field"]
        f = self.holder.field(index, field)
        if f is None:
            raise LookupError(f"field not found: {index}/{field}")
        if req.get("kind") == "fragment":
            v = f.create_view_if_not_exists(req["view"])
            frag = v.create_fragment_if_not_exists(req["shard"])
            frag.bulk_import(req["rowIDs"], req["columnIDs"],
                             clear=req.get("clear", False))
        elif req.get("values") is not None:
            f.import_values(req["columnIDs"], req["values"],
                            clear=req.get("clear", False))
            self.holder.index(index).add_existence(req["columnIDs"])
        else:
            from pilosa_tpu.core import timequantum as tq
            ts = None
            if req.get("timestamps") is not None:
                ts = [tq.parse_time(t) if t else None
                      for t in req["timestamps"]]
            f.import_bits(req["rowIDs"], req["columnIDs"], ts,
                          clear=req.get("clear", False))
            self.holder.index(index).add_existence(req["columnIDs"])

    # -- backup / restore --------------------------------------------------

    def handle_backup(self, req: dict) -> dict:
        """POST /backup: start a cluster backup into the archive named
        in the request (directory path or object-store URL); returns
        the backup id immediately (poll /backup/status)."""
        from pilosa_tpu.backup import (
            BackupError,
            BackupWriter,
            new_backup_id,
            open_archive,
        )
        req = req or {}
        root = req.get("archive")
        if not root:
            raise BackupError(
                "backup: 'archive' (directory path or URL) is required")
        parent = req.get("parent") or None
        archive = open_archive(root, stats=self.stats)
        if parent and not archive.has_manifest(parent):
            raise BackupError(
                f"backup: parent {parent!r} not found in archive")
        if not self._backup_gate.acquire(blocking=False):
            raise BackupError("backup already in progress")
        backup_id = new_backup_id("incremental" if parent else "full")
        writer = BackupWriter(
            self.holder, self.cluster,
            self.cluster.client if self.cluster is not None else None,
            self.store, archive, stats=self.stats, admission=self.qos)
        writer.progress = {"state": "starting", "id": backup_id}
        self._backup_writer = writer

        def run():
            try:
                writer.run(backup_id=backup_id, parent=parent)
            except Exception:
                pass  # progress carries state=failed + the error text
            finally:
                self._backup_gate.release()

        threading.Thread(target=run, name="backup", daemon=True).start()
        return {"id": backup_id, "state": "started"}

    def backup_status(self) -> dict:
        w = self._backup_writer
        return dict(w.progress) if w is not None else {"state": "idle"}

    def handle_restore(self, req: dict) -> dict:
        """POST /restore: rebuild the backed-up indexes onto THIS
        cluster (any size) from the archive; returns immediately (poll
        /restore/status). ``id`` defaults to the newest complete backup;
        ``pitrOps`` caps WAL replay for point-in-time recovery."""
        import time as _time

        from pilosa_tpu.backup import (
            BackupError,
            RestoreJob,
            open_archive,
            select_backup_at,
        )
        req = req or {}
        root = req.get("archive")
        if not root:
            raise BackupError(
                "restore: 'archive' (directory path or URL) is required")
        archive = open_archive(root, stats=self.stats)
        backup_id = req.get("id")
        if not backup_id:
            m = select_backup_at(archive, _time.time())
            if m is None:
                raise BackupError(
                    "restore: no complete backup in archive")
            backup_id = m["id"]
        elif not archive.has_manifest(backup_id):
            raise BackupError(
                f"restore: backup {backup_id!r} not found in archive")
        pitr = req.get("pitrOps")
        if not self._restore_gate.acquire(blocking=False):
            raise BackupError("restore already in progress")
        job = RestoreJob(
            self.holder, self.cluster,
            self.cluster.client if self.cluster is not None else None,
            archive, backup_id, store=self.store, stats=self.stats,
            force=bool(req.get("force")),
            pitr_ops=int(pitr) if pitr is not None else None)
        job.progress = {"state": "starting", "id": backup_id}
        self._restore_job = job

        def run():
            try:
                job.run()
            except Exception:
                pass  # progress carries state=failed + the error text
            finally:
                self._restore_gate.release()

        threading.Thread(target=run, name="restore", daemon=True).start()
        return {"id": backup_id, "state": "started"}

    def restore_status(self) -> dict:
        j = self._restore_job
        return dict(j.progress) if j is not None else {"state": "idle"}

    def backup_debug(self) -> dict:
        """GET /debug/backup: the scheduler's health document, or a
        stub when unattended backups aren't configured on this node."""
        if self.backup_scheduler is None:
            return {"enabled": False}
        doc = self.backup_scheduler.status()
        doc["enabled"] = True
        doc["archive"] = self._archive_url
        return doc

    # -- warmup-from-observed-traffic --------------------------------------

    def _save_observed_traffic(self) -> None:
        """Persist the planner's observed structural query shapes (plus
        the schema they compile against) so the next boot's warmup
        precompiles what THIS node's traffic actually ran."""
        import json as _json
        import os as _os
        planner = self.executor.planner
        if not self.data_dir or planner is None:
            return
        observed = getattr(planner, "observed_traffic", lambda: [])()
        if not observed:
            return
        path = _os.path.join(self.data_dir, "warmup.json")
        try:
            tmp = f"{path}.{_os.getpid()}.tmp"
            with open(tmp, "w") as f:
                _json.dump({"version": 1, "entries": observed,
                            "schema": self.holder.schema()}, f)
            _os.replace(tmp, path)
        except OSError:
            pass  # warmup hints are best-effort; never block shutdown

    def _load_observed_traffic(self) -> tuple[list, list]:
        import json as _json
        import os as _os
        if not self.data_dir:
            return [], []
        try:
            with open(_os.path.join(self.data_dir, "warmup.json")) as f:
                doc = _json.load(f)
            return (list(doc.get("entries", [])),
                    list(doc.get("schema", [])))
        except (OSError, ValueError):
            return [], []
