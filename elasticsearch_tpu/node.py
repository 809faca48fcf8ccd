"""Node — the composition root + HTTP server.

Reference: `node/Node` + `http/` (SURVEY.md §2.1#2/9, §3.1): constructs
every service, wires the REST controller, serves JSON over HTTP. The
reference's Netty pipeline becomes a stdlib ThreadingHTTPServer — the
data path's heavy work is on-device, so the host HTTP layer only needs to
parse/route (SURVEY.md §7.1: host is control plane).

Run: python -m elasticsearch_tpu.node --port 9200 --data-path /tmp/data
"""

from __future__ import annotations

import argparse
import json
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional
from urllib.parse import parse_qs, urlparse

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.indices.service import IndexService, IndicesService
from elasticsearch_tpu.rest.controller import RestController


class Node:
    def __init__(self, data_path: str, *,
                 node_name: str = "node-1",
                 cluster_name: str = "elasticsearch-tpu",
                 settings: Optional[Settings] = None):
        # everything `_open` builds is here to stay (indices opened,
        # segments loaded, translog replayed, packs re-attained): it is
        # collected once and frozen, so no later full collection walks it
        with tracing.HEAP.opening():
            self._open(data_path, node_name, cluster_name, settings)

    def _open(self, data_path: str, node_name: str, cluster_name: str,
              settings: Optional[Settings]) -> None:
        # private copy — dynamic cluster settings mutate node.settings
        # and must never write through to the caller's object or the
        # shared EMPTY singleton
        self.settings = Settings((settings or Settings.EMPTY)
                                 .get_as_dict())
        # the node-config baseline dynamic settings recompute against:
        # clearing a cluster setting (null) reverts to this, not to
        # whatever value happened to be live
        self._base_settings = dict(self.settings.get_as_dict())
        # logging is part of node construction, not the CLI: embedded
        # users (tests, Python API) get the same handlers/levels.
        # Owner-scoped so two embedded nodes don't reset each other.
        from elasticsearch_tpu.common.logging import configure
        configure(self.settings, owner=id(self))
        # plugins load BEFORE any service that consults their
        # registries (queries, processors, analyzers, engine factory)
        from elasticsearch_tpu.plugins import REGISTRY as _plugins
        _plugins.load_from_settings(self.settings)
        self.plugins = _plugins
        self.node_name = node_name
        self.node_id = _load_or_create_node_id(data_path, node_name)
        self.cluster_name = cluster_name
        self.cluster_uuid = uuid.uuid4().hex[:20]
        self.http_port = 0
        # cluster mode (multi-node over the transport layer); None ⇒ the
        # single-node paths in the REST actions
        self.cluster = None
        self.indices = IndicesService(data_path)
        from elasticsearch_tpu.tasks import TaskManager
        self.task_manager = TaskManager(self.node_id)
        from elasticsearch_tpu.search.contexts import SearchContextManager
        self.search_contexts = SearchContextManager()
        from elasticsearch_tpu.ingest import IngestService
        self.ingest = IngestService()
        self._load_ingest_pipelines(data_path)
        import os as _os

        from elasticsearch_tpu.snapshots import RepositoriesService
        self.repositories = RepositoriesService(
            _os.path.join(data_path, "_state", "repositories.json"))
        from elasticsearch_tpu.templates import TemplateService
        self.templates = TemplateService(
            _os.path.join(data_path, "_state", "index_templates.json"))
        # single-node dynamic cluster settings (cluster mode keeps them
        # in the published ClusterState instead); persistent ones
        # survive restart via the gateway file
        self.transient_settings: Dict[str, Any] = {}
        self.persistent_settings: Dict[str, Any] = \
            self._load_persistent_settings(data_path)
        if self.persistent_settings:
            # full recompute so persisted logger.* overrides are applied
            # to the logging config too, not just the settings map
            self.recompute_settings()
        # the TPU serving path: resident packs + micro-batched kernel
        # (disable with search.tpu_serving.enabled=false — the planner
        # path then serves everything)
        self.tpu_search = None
        if self.settings.get_bool("search.tpu_serving.enabled", True):
            from elasticsearch_tpu.common.breaker import \
                HierarchyCircuitBreakerService
            from elasticsearch_tpu.search.tpu_service import TpuSearchService
            self.breakers = HierarchyCircuitBreakerService(
                total_limit_bytes=self.settings.get_int(
                    "indices.breaker.total.limit_bytes", 8 << 30))
            self.tpu_search = TpuSearchService(
                breaker=self.breakers.breakers["hbm"],
                window_s=self.settings.get_float(
                    "search.tpu_serving.batch_window_seconds", 0.01),
                max_batch=self.settings.get_int(
                    "search.tpu_serving.max_batch", 128),
                batch_timeout_s=self.settings.get_float(
                    "search.tpu_serving.batch_timeout_seconds", 30.0),
                plan_cache_size=self.settings.get_int(
                    "search.tpu_serving.plan_cache_size", 2048),
                prewarm_concurrency=self.settings.get_int(
                    "search.tpu_serving.prewarm_concurrency", 4),
                # persistent XLA compile cache (restart = cache replay,
                # not recompilation). Never under data_path: the
                # directory is part of jax's cache key, so it stays put
                # (JAX_COMPILATION_CACHE_DIR, this setting, or
                # <checkout>/.jax_cache)
                compile_cache_dir=self.settings.get(
                    "search.tpu_serving.compile_cache_dir"),
                # packed-key device kernels: single
                # uint32 sort key + hierarchical top-k, with automatic
                # per-launch exact-f32 fallback when the pack/batch
                # overflows the packed layout
                packed_sort=self.settings.get_bool(
                    "search.tpu_serving.kernel.packed_sort", True),
                # compressed resident packs: 16-bit
                # impact/doc/rank streams + residual tables + block-max
                # metadata + delta doc stream; ~3x fewer HBM bytes/doc
                # at identical result bits. Default ON since PR 15;
                # chip_smoke.py holds it to the numpy reference on the
                # chip. Incompressible packs fall back to raw residency
                compressed_pack=self.settings.get_bool(
                    "search.tpu_serving.kernel.compressed_pack", True),
                # supervision: dispatches overdue past this deadline are
                # failed typed and trip batcher recovery (0 disables)
                launch_deadline_ms=self.settings.get_float(
                    "search.tpu_serving.launch_deadline_ms", 120_000.0),
                # device fault domains: wedge attribution → micro-probe
                # quarantine → partial-mesh N-1 serving → flap-damped
                # reintroduction
                device_health={
                    "enabled": self.settings.get_bool(
                        "search.tpu_serving.device_health.enabled", True),
                    "suspect_after": self.settings.get_int(
                        "search.tpu_serving.device_health.suspect_after",
                        2),
                    "probe_deadline_ms": self.settings.get_float(
                        "search.tpu_serving.device_health"
                        ".probe_deadline_ms", 5_000.0),
                    "reprobe_interval_seconds": self.settings.get_float(
                        "search.tpu_serving.device_health"
                        ".reprobe_interval_seconds", 30.0),
                    "hold_down_seconds": self.settings.get_float(
                        "search.tpu_serving.device_health"
                        ".hold_down_seconds", 60.0),
                    "reintroduce_after": self.settings.get_int(
                        "search.tpu_serving.device_health"
                        ".reintroduce_after", 3),
                    "drain_window_seconds": self.settings.get_float(
                        "search.tpu_serving.device_health"
                        ".drain_window_seconds", 2.0),
                    "shed_retry_after_seconds": self.settings.get_float(
                        "search.tpu_serving.device_health"
                        ".shed_retry_after_seconds", 5.0),
                },
                # pack-replica placement across device fault domains:
                # groups=1 (the default) keeps today's whole-mesh serving
                # byte-identical; groups>1 partitions the mesh and places
                # each resident pack on `replicas` distinct groups so a
                # chip loss fails over instead of shedding
                placement={
                    "groups": self.settings.get_int(
                        "search.tpu_serving.placement.groups", 1),
                    "replicas": self.settings.get_int(
                        "search.tpu_serving.placement.replicas", 1),
                },
                # streaming delta packs: append-only refreshes ride as
                # small device-resident deltas unioned into results; a
                # background compactor folds chains back into the
                # compressed base (disabled automatically under
                # placement — replica groups must stay byte-identical)
                delta={
                    "enabled": self.settings.get_bool(
                        "search.tpu_serving.delta.enabled", True),
                    "max_packs": self.settings.get_int(
                        "search.tpu_serving.delta.max_packs", 4),
                    "max_docs": self.settings.get_int(
                        "search.tpu_serving.delta.max_docs", 50_000),
                })
            # recovery's eager re-residency resolves index names through
            # the live indices service
            self.tpu_search.index_resolver = \
                lambda name: self.indices.indices.get(name)
        from elasticsearch_tpu.common.threadpool import ThreadPools
        self.thread_pools = ThreadPools(self.settings)
        # overload protection: memory-accounted write admission shared
        # by every replication stage, plus coordinator-side search load
        # shedding (reference: IndexingPressure + search backpressure)
        from elasticsearch_tpu.common.pressure import (
            IndexingPressure, SearchBackpressureService)
        self.indexing_pressure = IndexingPressure(self.settings)
        self.search_backpressure = SearchBackpressureService(
            self.settings, pressure=self.indexing_pressure,
            thread_pools=self.thread_pools,
            task_manager=self.task_manager)
        # per-tenant QoS: weighted shares carved from the SAME budgets
        # the node-level guards enforce. The default search budget is a
        # multiple of the search pool, so an unconfigured node (every
        # request the default tenant, share 1.0) behaves exactly as
        # before the carve existed.
        from elasticsearch_tpu.common.tenancy import TenantQuotaService
        search_pool = self.thread_pools.get("search")
        self.tenants = TenantQuotaService(
            self.settings,
            write_limit_bytes=self.indexing_pressure.limit,
            search_slots=max(
                32, 4 * (search_pool.size if search_pool is not None
                         else 8)))
        self.indexing_pressure.tenants = self.tenants
        self.search_backpressure.tenants = self.tenants
        if self.tpu_search is not None:
            self.tpu_search.batcher.tenants = self.tenants
        self.controller = RestController()
        self.controller.thread_pools = self.thread_pools
        # tracing: per-request root spans + propagation through the
        # coordinator fan-out and the TPU batch pipeline (sample_rate=0,
        # the default, keeps the hostpath allocation-free)
        # full collections stop every Python thread of the node: counted
        # (/_tpu/stats → runtime.gc) and annotated on profiler traces;
        # what they walk is settled when the constructor ends
        self.gc_watch = tracing.GcWatch()
        self.gc_watch.install()
        self.tracer = tracing.Tracer(
            sample_rate=self.settings.get_float(
                "search.tracing.sample_rate", 0.0),
            max_spans=self.settings.get_int(
                "search.tracing.max_spans", 4096),
            slow_threshold_ms=self.settings.get_float(
                "search.tracing.slow_threshold_ms", 3000.0),
            node_name=node_name)
        self.controller.tracer = self.tracer
        # host/device profiling: continuous low-overhead flamegraph
        # sampler + bounded device trace sessions (ISSUE 6). Constructed
        # unconditionally so endpoints/metrics keep their shape; the
        # sampler thread only spawns when search.profiler.enabled.
        import os as _os

        from elasticsearch_tpu.common.profiler import Profiler
        self.profiler = Profiler(
            enabled=self.settings.get_bool("search.profiler.enabled",
                                           False),
            hz=self.settings.get_float("search.profiler.hz", 20.0),
            retention_s=self.settings.get_float(
                "search.profiler.retention_s", 300.0),
            device_dir=_os.path.join(data_path, "profile_sessions"))
        if self.tpu_search is not None:
            # read through the service each tick: supervision may swap
            # the batcher object on recovery
            self.profiler.sampler.timeline_source = \
                lambda: self.tpu_search.batcher.queue_depths()
        self.profiler.start()
        # flight recorder: process-wide causal event journal + incident
        # snapshots (ISSUE 18). Installed as the module-level recorder so
        # every subsystem's events.emit() lands here; off ⇒ near-free.
        from elasticsearch_tpu.common import events as _events
        self.flight_recorder = None
        if self.settings.get_bool("search.flight_recorder.enabled", True):
            self.flight_recorder = _events.FlightRecorder(
                _os.path.join(data_path, "flight"),
                max_events=self.settings.get_int(
                    "search.flight_recorder.max_events", 4096),
                disk_retention=self.settings.get_int(
                    "search.flight_recorder.disk_retention", 4),
                incident_dir=self.settings.get(
                    "search.flight_recorder.incident_dir",
                    _os.path.join(data_path, "flight", "incidents")),
                snapshot_events=self.settings.get_int(
                    "search.flight_recorder.snapshot_events", 256))
            _events.set_recorder(self.flight_recorder)
            self._wire_snapshot_sources()
            _events.emit("node.start", node=node_name,
                         node_id=self.node_id)
        # the multi-process serving front (started explicitly via
        # start_serving_fronts(); None ⇒ single-process serving)
        self.serving_front = None
        # off-interpreter coordinator merge: deferred k-way merges run
        # on the serving fronts when they exist, else on this node-local
        # worker pool; merge_pool_size=0 (the default) keeps the merge
        # inline on the dispatch thread
        from elasticsearch_tpu.search import merge as _merge
        self.merge_stats = _merge.MergeStats()
        self.merge_pool = None
        _pool_size = self.settings.get_int(
            "search.tpu_serving.merge_pool_size", 0)
        if _pool_size > 0:
            self.merge_pool = _merge.MergePool(_pool_size,
                                               stats=self.merge_stats)
        from elasticsearch_tpu.common.metrics import MetricsRegistry
        self.metrics = MetricsRegistry()
        self._register_metrics()
        self._register_actions()
        self._refresh_interval = self.settings.get_float(
            "index.refresh_interval_seconds", 1.0)
        self._sync_interval = self.settings.get_float(
            "index.translog.sync_interval_seconds", 5.0)
        self._refresher: Optional[threading.Timer] = None
        self._syncer: Optional[threading.Timer] = None
        self._closed = False

    def _wire_snapshot_sources(self) -> None:
        """Attach bounded context captures to the flight recorder:
        incident snapshots embed serving stats, degraded-mesh info and
        (when the sampler is live) the hottest folded stacks."""
        rec = self.flight_recorder

        def _tpu_stats():
            if self.tpu_search is None:
                return None
            return self.tpu_search.stats()

        def _degraded():
            if self.tpu_search is None:
                return None
            return self.tpu_search.degraded_info()

        def _stacks():
            s = self.profiler.sampler
            if not s.running:
                return None
            return [{"stack": stack, "count": count}
                    for stack, count in s.folded(top=15)]

        def _merge_pool():
            # merge-pool state rides every incident snapshot (a batcher
            # death with a backed-up merge queue is a different story
            # than one with an idle pool)
            pool = getattr(self, "merge_pool", None)
            if pool is not None:
                return pool.status()
            stats = getattr(self, "merge_stats", None)
            return stats.to_dict() if stats is not None else None

        rec.add_snapshot_source("tpu_stats", _tpu_stats)
        rec.add_snapshot_source("degraded_info", _degraded)
        rec.add_snapshot_source("profile_stacks", _stacks)
        rec.add_snapshot_source("merge_pool", _merge_pool)

    def _ingest_state_path(self) -> str:
        import os
        return os.path.join(self.indices.data_path, "_state",
                            "ingest_pipelines.json")

    def _cluster_settings_path(self) -> str:
        import os
        return os.path.join(self.indices.data_path, "_state",
                            "cluster_settings.json")

    def _load_ingest_pipelines(self, data_path: str) -> None:
        import logging
        try:
            with open(self._ingest_state_path(), "rb") as f:
                bodies = json.loads(f.read().decode("utf-8"))
        except FileNotFoundError:
            return
        except (OSError, json.JSONDecodeError) as e:
            logging.getLogger("elasticsearch_tpu.ingest").error(
                "could not read persisted ingest pipelines: %s", e)
            return
        if not isinstance(bodies, dict):
            logging.getLogger("elasticsearch_tpu.ingest").error(
                "persisted ingest pipelines file is not an object; "
                "ignoring it")
            return
        # lenient per pipeline: a bad entry quarantines itself (persist
        # keeps its body), never prevents startup or drops siblings
        self.ingest.sync(bodies)

    def persist_ingest_pipelines(self) -> None:
        import os

        from elasticsearch_tpu.index.translog import write_atomic
        p = self._ingest_state_path()
        os.makedirs(os.path.dirname(p), exist_ok=True)
        write_atomic(p, json.dumps(self.ingest.bodies(),
                                   sort_keys=True).encode("utf-8"))

    def _load_persistent_settings(self, data_path: str
                                  ) -> Dict[str, Any]:
        try:
            with open(self._cluster_settings_path(), "rb") as f:
                return json.loads(f.read().decode("utf-8"))
        except (OSError, json.JSONDecodeError):
            return {}

    def recompute_settings(self, persistent: Optional[dict] = None,
                           transient: Optional[dict] = None) -> None:
        """node.settings := base config + persistent + transient
        (reference precedence). Called on every dynamic change —
        including removals, which thereby revert to the base value."""
        if persistent is None:
            persistent = self.persistent_settings
        if transient is None:
            transient = self.transient_settings
        target = dict(self._base_settings)
        target.update(persistent)
        target.update(transient)
        self.settings.replace_all(target)
        from elasticsearch_tpu.common.logging import configure
        configure(self.settings, owner=id(self))

    def update_cluster_settings_local(self, persistent: dict,
                                      transient: dict) -> dict:
        """Single-node _cluster/settings PUT (reference semantics:
        validate against the dynamic registry, transient wins)."""
        import os

        from elasticsearch_tpu.cluster.service import (
            DYNAMIC_CLUSTER_PREFIXES, DYNAMIC_CLUSTER_SETTINGS)
        from elasticsearch_tpu.common.errors import IllegalArgumentException
        from elasticsearch_tpu.index.translog import write_atomic
        flat_p = Settings._flatten(persistent)
        flat_t = Settings._flatten(transient)
        for key in list(flat_p) + list(flat_t):
            if key in DYNAMIC_CLUSTER_SETTINGS or any(
                    key.startswith(p) for p in DYNAMIC_CLUSTER_PREFIXES):
                continue
            raise IllegalArgumentException(
                f"setting [{key}] is not dynamically updateable")
        for store, changes in ((self.persistent_settings, flat_p),
                               (self.transient_settings, flat_t)):
            for k, v in changes.items():
                if v is None:
                    store.pop(k, None)
                else:
                    store[k] = v
        self.recompute_settings()
        p = self._cluster_settings_path()
        os.makedirs(os.path.dirname(p), exist_ok=True)
        write_atomic(p, json.dumps(self.persistent_settings,
                                   sort_keys=True).encode("utf-8"))
        return {"acknowledged": True,
                "persistent": dict(self.persistent_settings),
                "transient": dict(self.transient_settings)}

    def start_cluster(self, *, host: str = "127.0.0.1",
                      transport_port: int = 0,
                      seed_hosts=None, initial_master_nodes=None) -> None:
        """Join/bootstrap a multi-node cluster (reference: discovery +
        coordination startup in Node#start)."""
        from elasticsearch_tpu.cluster.service import ClusterService
        # the gateway eagerly reopened every local shard as a primary;
        # in cluster mode the routing table decides which copies live
        # here and with which role — drop the objects (files stay) and
        # let the state applier recreate the right ones
        for svc in self.indices.indices.values():
            for shard in list(svc.shards.values()):
                shard.close()
            svc.shards.clear()
        self.cluster = ClusterService(
            self, host=host, transport_port=transport_port,
            seed_hosts=seed_hosts,
            initial_master_names=initial_master_nodes)
        self.cluster.start()

    def start_serving_fronts(self, *, host: str = "127.0.0.1",
                             count: Optional[int] = None) -> list:
        """Spawn the multi-process serving front: N HTTP front processes
        handing plan-signed requests to this (batcher) process over
        shared memory (serving/front.py). Returns the front HTTP ports;
        [] when search.tpu_serving.front_processes is 0 (the default —
        single-process serving via serve())."""
        if self.serving_front is not None:
            return self.serving_front.ports
        n = count if count is not None else self.settings.get_int(
            "search.tpu_serving.front_processes", 0)
        if n <= 0:
            return []
        profile_hz = 0.0
        if self.settings.get_bool("search.profiler.enabled", False):
            profile_hz = self.settings.get_float(
                "search.profiler.hz", 20.0)
        from elasticsearch_tpu.serving.front import FrontSupervisor
        self.serving_front = FrontSupervisor(
            self, n, host=host,
            slots=self.settings.get_int(
                "search.tpu_serving.front_slots", 64),
            slot_bytes=self.settings.get_int(
                "search.tpu_serving.front_slot_bytes", 256 << 10),
            timeout_s=self.settings.get_float(
                "search.tpu_serving.front_timeout_seconds", 45.0),
            wedge_timeout_s=self.settings.get_float(
                "search.tpu_serving.front_wedge_timeout_seconds", 30.0),
            profile_hz=profile_hz,
            memo_size=self.settings.get_int(
                "search.tpu_serving.plan_memo_size", 4096),
            hb_interval_s=self.settings.get_float(
                "search.tpu_serving.batcher_heartbeat_seconds", 1.0),
            batcher_stale_s=self.settings.get_float(
                "search.tpu_serving.batcher_stale_seconds", 5.0),
            orphan_grace_s=self.settings.get_float(
                "search.tpu_serving.front_orphan_grace_seconds", 10.0))
        return self.serving_front.ports

    def replicate(self, op: str, index: str, shard_num: int, doc_id: str,
                  source, result) -> None:
        """Primary→replica fan-out seam; no-op single-node (the write
        executors call this after every primary-phase apply)."""
        if self.cluster is not None:
            self.cluster.replicate_op(op, index, shard_num, doc_id,
                                      source, result)

    def _register_metrics(self) -> None:
        """Register every subsystem's metrics with the node-wide
        registry (scraped by GET /_prometheus/metrics). Dynamic families
        — per-pool, per-breaker, per-stage, per-shard — go through
        collectors so members created later still show up."""
        reg = self.metrics
        reg.set_help("threadpool.active",
                     "Requests currently executing in the pool")
        reg.set_help("threadpool.queue", "Requests waiting for a slot")
        reg.set_help("search.plan_cache.hits",
                     "Lowered-plan cache lookups served from cache")
        reg.set_help("transport.retries",
                     "Transport sends retried after a retryable failure")
        reg.set_help("kernel.variant",
                     "Device-kernel launches by (kernel, variant)")
        reg.set_help("pack.hbm_bytes",
                     "Resident-pack HBM bytes by (index, field, "
                     "component)")
        reg.set_help("pack.compression_ratio",
                     "Resident bytes / uncompressed-format bytes per "
                     "(index, field) pack")

        def _threadpools():
            for name, pool in self.thread_pools.pools.items():
                st = pool.stats()
                lb = {"pool": name}
                yield ("threadpool.threads", lb, st["threads"], "gauge")
                yield ("threadpool.queue_capacity", lb,
                       st["queue_size"], "gauge")
                yield ("threadpool.active", lb, st["active"], "gauge")
                yield ("threadpool.queue", lb, st["queue"], "gauge")
                yield ("threadpool.rejected", lb, st["rejected"],
                       "counter")
                yield ("threadpool.completed", lb, st["completed"],
                       "counter")
        reg.add_collector(_threadpools)

        def _breakers():
            svc = getattr(self, "breakers", None)
            if svc is None:
                return
            for name, st in svc.stats().items():
                lb = {"breaker": name}
                yield ("breaker.limit_bytes", lb,
                       st["limit_size_in_bytes"], "gauge")
                yield ("breaker.estimated_bytes", lb,
                       st["estimated_size_in_bytes"], "gauge")
                yield ("breaker.tripped", lb, st["tripped"], "counter")
        reg.add_collector(_breakers)

        def _tpu():
            svc = self.tpu_search
            if svc is None:
                return
            nl = {}
            yield ("search.tpu.served", nl, svc.served, "counter")
            yield ("search.tpu.fallback", nl, svc.fallback, "counter")
            yield ("search.tpu.timeouts", nl, svc.timeouts, "counter")
            yield ("search.tpu.kernel_breaker_open", nl,
                   1 if svc._tripped else 0, "gauge")
            yield ("search.tpu.batches_executed", nl,
                   svc.batcher.batches_executed, "counter")
            yield ("search.tpu.batched_queries", nl,
                   svc.batcher.queries_executed, "counter")
            plans = svc.plans.stats()
            yield ("search.plan_cache.size", nl, plans["size"], "gauge")
            for key in ("hits", "misses", "evictions", "invalidations"):
                yield (f"search.plan_cache.{key}", nl, plans[key],
                       "counter")
            packs = svc.packs.stats()
            yield ("search.pack_cache.resident", nl, packs["resident"],
                   "gauge")
            for key in ("hits", "misses", "stale_served"):
                yield (f"search.pack_cache.{key}", nl, packs[key],
                       "counter")
            # per-(index,field) resident-pack HBM breakdown: the
            # compressed-pack capacity win, scrapeable. `component`
            # splits the charge (resident = what the breaker holds,
            # raw = the uncompressed-format equivalent, block_meta /
            # residual = the pruning + exact-decode overheads).
            for pk, det in packs.get("packs", {}).items():
                index, _, field = pk.partition("/")
                lb = {"index": index, "field": field}
                for comp, key in (("resident", "hbm_bytes"),
                                  ("raw", "raw_bytes"),
                                  ("block_meta", "block_meta_bytes"),
                                  ("residual", "residual_bytes"),
                                  ("doc_base", "doc_base_bytes")):
                    yield ("pack.hbm_bytes", {**lb, "component": comp},
                           det.get(key, 0), "gauge")
                yield ("pack.compression_ratio", lb,
                       det.get("compression_ratio", 1.0), "gauge")
                # the bytes-war scoreboard (PR 15 acceptance: compressed
                # + delta packs sit at ≤ 6 B/posting)
                yield ("pack.hbm_bytes_per_posting", lb,
                       det.get("hbm_bytes_per_posting", 0.0), "gauge")
                yield ("pack.doc_delta", lb,
                       1 if det.get("doc_delta") else 0, "gauge")
            with svc._prewarm_lock:
                warm = dict(svc._prewarm_progress)
            yield ("search.tpu.prewarm_total", nl, warm["total"], "gauge")
            yield ("search.tpu.prewarm_done", nl, warm["done"], "gauge")
            depths = svc.batcher.queue_depths()
            yield ("search.tpu.queue_pending", nl, depths["pending"],
                   "gauge")
            yield ("search.tpu.queue_inflight", nl, depths["inflight"],
                   "gauge")
            yield ("search.tpu.pack_queues", nl, depths["queues"],
                   "gauge")
            from elasticsearch_tpu.search.tpu_service import (
                CROSS_CHIP_COUNTS, EXACT_ENTRY_COUNTS, EXACT_PIN_COUNTS,
                EXACT_RESULT_COUNTS, FULL_ENTRY_COUNTS, HEADS_COUNTS,
                HOLD_EXIT_COUNTS, KERNEL_CONFIG, KERNEL_VARIANT_COUNTS,
                LAUNCH_COUNTS, OPERAND_COUNTS, ROUTE_COUNTS)
            from elasticsearch_tpu.parallel.distributed import (
                TERM_TABLE_COUNTS)
            yield ("search.tpu.kernel_packed_sort", nl,
                   1 if KERNEL_CONFIG["packed_sort"] else 0, "gauge")
            yield ("search.tpu.kernel_compressed_pack", nl,
                   1 if KERNEL_CONFIG["compressed_pack"] else 0, "gauge")
            # per-(kernel, variant) launch counts:
            # es_tpu_kernel_variant_total{kernel=...,variant=...}
            for labels, counter in KERNEL_VARIANT_COUNTS.items():
                yield ("kernel.variant", labels, counter)
            # device programs dispatched, by launch path:
            # es_tpu_kernel_launches_total{path=...}
            for labels, counter in LAUNCH_COUNTS.items():
                yield ("kernel.launches", labels, counter)
            # queries by the way the launch routing sent them, and the
            # exact and the full-postings launches' posting entries, real
            # and as dispatched:
            # es_tpu_kernel_route_total{route=...},
            # es_tpu_kernel_exact_entries_total{kind=...},
            # es_tpu_kernel_full_entries_total{kind=...}
            for labels, counter in ROUTE_COUNTS.items():
                yield ("kernel.route", labels, counter)
            for labels, counter in EXACT_ENTRY_COUNTS.items():
                yield ("kernel.exact_entries", labels, counter)
            for labels, counter in FULL_ENTRY_COUNTS.items():
                yield ("kernel.full_entries", labels, counter)
            # an exact launch's queries and those below its slot pin;
            # the exact kernel's answers and the empty ones:
            # es_tpu_kernel_exact_pin_total{kind=...},
            # es_tpu_kernel_exact_results_total{kind=...}
            for labels, counter in EXACT_PIN_COUNTS.items():
                yield ("kernel.exact_pin", labels, counter)
            for labels, counter in EXACT_RESULT_COUNTS.items():
                yield ("kernel.exact_results", labels, counter)
            # query terms resolved for launches' operands, and the columns
            # the packs' term tables built:
            # es_tpu_kernel_term_table_total{kind=...}
            for labels, counter in TERM_TABLE_COUNTS.items():
                yield ("kernel.term_table", labels, counter)
            # full-postings launches by the builder of their operand:
            # es_tpu_kernel_operands_total{builder=...}
            for labels, counter in OPERAND_COUNTS.items():
                yield ("kernel.operands", labels, counter)
            # the heads route's queries, launches, head terms and head
            # block bytes: es_tpu_kernel_heads_total{kind=...}
            for labels, counter in HEADS_COUNTS.items():
                yield ("kernel.heads", labels, counter)
            # launches on a mesh of several devices, their rows and
            # devices: es_tpu_kernel_cross_chip_total{kind=...}
            for labels, counter in CROSS_CHIP_COUNTS.items():
                yield ("kernel.cross_chip", labels, counter)
            # trains by the reason the launch thread's hold ended:
            # es_tpu_batcher_hold_exit_total{hold_exit=...}
            for labels, counter in HOLD_EXIT_COUNTS.items():
                yield ("batcher.hold_exit", labels, counter)
            # hits blocks by the path that rendered them, and the hits
            # returned with a `_source` and its bytes:
            # es_tpu_response_render_total{path=...},
            # es_tpu_response_fetch_total{kind=...}
            from elasticsearch_tpu.search.serializer import (
                FETCH_COUNTS, RENDER_COUNTS)
            for labels, counter in RENDER_COUNTS.items():
                yield ("response.render", labels, counter)
            for labels, counter in FETCH_COUNTS.items():
                yield ("response.fetch", labels, counter)
            for stage, seconds, count, ring, cpu in \
                    svc.stages.metrics_view():
                lb = {"stage": stage}
                yield ("search.tpu.stage_seconds", lb, seconds, "counter")
                if cpu is not None:
                    yield ("search.tpu.stage_cpu_seconds", lb, cpu,
                           "counter")
                yield ("search.tpu.stage_operations", lb, count,
                       "counter")
                if ring is not None:
                    yield ("search.tpu.stage_latency_seconds", lb, ring,
                           "summary")
            # batcher supervision: launch watchdog + wedge/crash
            # recovery (metric OBJECTS yield so the completeness
            # traversal sees them as registered)
            wd = svc.watchdog
            yield ("watchdog.launches", nl, wd.c_launches, "counter")
            yield ("watchdog.wedges", nl, wd.c_wedges, "counter")
            yield ("watchdog.inflight", nl, wd.inflight(), "gauge")
            yield ("watchdog.deadline_ms", nl,
                   round(wd.deadline_s * 1e3, 1), "gauge")
            sup = svc.supervisor
            from elasticsearch_tpu.search.tpu_service import \
                _SUPERVISION_STATES
            yield ("recovery.recoveries", nl, sup.c_recoveries, "counter")
            yield ("recovery.degraded_served", nl, sup.c_degraded_served,
                   "counter")
            yield ("recovery.state", nl,
                   _SUPERVISION_STATES.get(sup.state, -1), "gauge")
            yield ("recovery.last_duration_seconds", nl,
                   sup.last_duration_s, "gauge")
            # device fault domains: per-device health state plus the
            # quarantine/probe/remesh lifecycle (metric OBJECTS yield
            # for the completeness traversal, same as the watchdog's)
            yield ("device.mesh_active", nl, sup.mesh_device_count,
                   "gauge")
            yield ("device.mesh_total", nl, sup.full_device_count,
                   "gauge")
            yield ("device.remeshes", nl, sup.c_remeshes, "counter")
            yield ("device.remesh_duration_seconds", nl,
                   sup.last_remesh_duration_s, "gauge")
            yield ("device.shed_packs", nl, len(svc.shed_keys()),
                   "gauge")
            health = svc.health
            if health is not None:
                yield ("device.probes", nl, health.c_probes, "counter")
                yield ("device.probe_failures", nl,
                       health.c_probe_failures, "counter")
                yield ("device.quarantines", nl, health.c_quarantines,
                       "counter")
                yield ("device.reintroductions", nl,
                       health.c_reintroductions, "counter")
                # es_tpu_device_health_state{device=} 0=healthy,
                # 1=suspect, 2=quarantined
                for dev_id, code in health.state_codes().items():
                    yield ("device.health_state",
                           {"device": str(dev_id)}, code, "gauge")
                # es_tpu_device_wedges_total{device=}: attributable
                # wedge counts per chip
                for labels, counter in health.c_device_wedges.items():
                    yield ("device.wedges", labels, counter)
            pl = svc.placement
            if pl is not None:
                # es_tpu_placement_*: fault-domain placement — group
                # inventory, replica failovers vs. shed (the drill's
                # zero-shed proof reads these two counters)
                yield ("placement.groups", nl, pl.num_groups, "gauge")
                yield ("placement.replicas", nl, pl.replicas, "gauge")
                yield ("placement.devices_active", nl,
                       pl.devices_active(), "gauge")
                yield ("placement.failovers", nl, pl.c_failovers,
                       "counter")
                yield ("placement.replacements", nl, pl.c_replacements,
                       "counter")
                yield ("placement.packs_shed", nl, pl.c_shed, "counter")
                for g in pl.groups():
                    gl = {"group": str(g.gid)}
                    yield ("placement.group_devices", gl,
                           len(g.active_ids), "gauge")
                    cache = svc.group_caches.get(g.gid)
                    yield ("placement.group_packs", gl,
                           len(cache.resident_keys())
                           if cache is not None else 0, "gauge")
                    yield ("placement.group_hbm_bytes", gl,
                           g.breaker.used, "gauge")
        reg.add_collector(_tpu)

        def _transport():
            # zeros when single-node: the family names stay stable
            # whether or not the node ever joined a cluster
            transport = getattr(self.cluster, "transport", None) \
                if self.cluster is not None else None
            nl = {}
            yield ("transport.rx", nl,
                   transport.rx_count if transport else 0, "counter")
            yield ("transport.tx", nl,
                   transport.tx_count if transport else 0, "counter")
            yield ("transport.retries", nl,
                   transport.retry_count if transport else 0, "counter")
            yield ("transport.evictions", nl,
                   transport.evict_count if transport else 0, "counter")
        reg.add_collector(_transport)

        def _search_failures():
            for (index, shard), counter in \
                    self.indices.search_failure_metrics():
                yield ("search.shard_failures",
                       {"index": index, "shard": shard}, counter)
        reg.add_collector(_search_failures)

        reg.set_help("indexing_pressure.current_bytes",
                     "In-flight write bytes held at a replication stage")
        reg.set_help("indexing_pressure.stage_bytes",
                     "Write bytes ever charged at a replication stage")
        reg.set_help("indexing_pressure.rejections",
                     "Write operations rejected by indexing pressure")
        reg.set_help("search.backpressure.shed",
                     "Stale search tasks cancelled under node duress")
        reg.set_help("search.backpressure.declined",
                     "Expensive searches declined under node duress")

        def _pressure():
            p = self.indexing_pressure
            current = p.current()
            totals = {"coordinating": (p.coordinating_total,
                                       p.coordinating_rejections),
                      "primary": (p.primary_total, p.primary_rejections),
                      "replica": (p.replica_total, p.replica_rejections)}
            for stage, (total, rejections) in totals.items():
                lb = {"stage": stage}
                yield ("indexing_pressure.current_bytes", lb,
                       current[stage], "gauge")
                yield ("indexing_pressure.stage_bytes", lb, total)
                yield ("indexing_pressure.rejections", lb, rejections)
            yield ("indexing_pressure.limit_bytes", {}, p.limit, "gauge")
            yield ("indexing_pressure.replica_limit_bytes", {},
                   p.replica_limit, "gauge")
            sb = self.search_backpressure
            yield ("search.backpressure.shed", {}, sb.shed)
            yield ("search.backpressure.declined", {}, sb.declined)
        reg.add_collector(_pressure)

        reg.set_help("tenant.search_inflight",
                     "Searches a tenant currently holds admission for")
        reg.set_help("tenant.search_admitted",
                     "Searches admitted under a tenant's share")
        reg.set_help("tenant.search_rejections",
                     "Searches 429'd by a tenant's admission share")
        reg.set_help("tenant.write_bytes_inflight",
                     "In-flight coordinating write bytes held per tenant")
        reg.set_help("tenant.write_bytes",
                     "Coordinating write bytes ever charged per tenant")
        reg.set_help("tenant.write_rejections",
                     "Writes 429'd by a tenant's indexing-pressure share")
        reg.set_help("tenant.weight", "Configured tenant admission weight")

        def _tenants():
            tq = self.tenants
            for tenant, use in tq.usage().items():
                lb = {"tenant": tenant}
                yield ("tenant.search_inflight", lb,
                       use["search_inflight"], "gauge")
                yield ("tenant.write_bytes_inflight", lb,
                       use["write_bytes"], "gauge")
                yield ("tenant.weight", lb, tq.weight(tenant), "gauge")
                yield ("tenant.search_cap", lb, tq.search_cap(tenant),
                       "gauge")
                yield ("tenant.write_cap_bytes", lb,
                       tq.write_cap_bytes(tenant), "gauge")
            for family, name in (
                    (tq.search_admitted, "tenant.search_admitted"),
                    (tq.search_rejections, "tenant.search_rejections"),
                    (tq.write_bytes_total, "tenant.write_bytes"),
                    (tq.write_rejections, "tenant.write_rejections")):
                for labels, metric in family.items():
                    yield (name, labels, metric)
        reg.add_collector(_tenants)
        reg.set_help("profiler.samples",
                     "Host sampling-profiler stack samples collected")
        reg.set_help("profiler.overhead_ratio",
                     "Fraction of wall time the sampler thread is busy")

        def _profiler():
            # plain-int/float gauges (no metric objects): the family
            # shape is stable whether or not the sampler is running
            s = self.profiler.sampler
            yield ("profiler.enabled", {}, 1 if s.running else 0, "gauge")
            yield ("profiler.samples", {}, s.samples_total, "counter")
            yield ("profiler.ticks", {}, s.ticks_total, "counter")
            yield ("profiler.retained_samples", {}, len(s._samples),
                   "gauge")
            yield ("profiler.overhead_ratio", {},
                   s.overhead_fraction(), "gauge")
            dev = self.profiler.device
            yield ("profiler.device_sessions", {}, dev.sessions_total,
                   "counter")
            yield ("profiler.device_active", {},
                   1 if dev.info()["active"] else 0, "gauge")

        reg.add_collector(_profiler)
        reg.set_help("events",
                     "Flight-recorder events emitted, by event type")
        reg.set_help("incidents",
                     "Incident snapshots captured, by trigger")
        reg.set_help("events.dropped",
                     "Flight-recorder events lost to emit failures")

        def _events():
            rec = self.flight_recorder
            if rec is None:
                return
            for labels, metric in rec.c_events.items():
                yield ("events", labels, metric, "counter")
            for labels, metric in rec.c_incidents.items():
                yield ("incidents", labels, metric, "counter")
            yield ("events.dropped", {}, rec.c_dropped, "counter")
            yield ("events.ring_size", {}, rec.ring_len(), "gauge")
        reg.add_collector(_events)
        reg.set_help("serving.fronts",
                     "Serving front processes currently alive")
        reg.set_help("serving.plan_memo.hits",
                     "Batcher body parses skipped via plan-signature memo")
        reg.set_help("serving.slots_reclaimed",
                     "Shared-memory slots reclaimed from dead fronts")

        def _serving():
            # supervisor counters + every front's shm-published registry
            # snapshot, each row tagged with its process role
            sup = self.serving_front
            if sup is None:
                return
            yield from sup.metric_rows()
        reg.add_collector(_serving)
        reg.set_help("merge.merges",
                     "Deferred k-way merges completed (pool or inline)")
        reg.set_help("merge.queue_depth",
                     "Merge-pool jobs queued and not yet picked up")
        reg.set_help("merge.latency",
                     "Merge execution seconds (k-way reduce only)")
        reg.set_help("merge.worker_restarts",
                     "Merge-pool workers respawned after dying")
        reg.set_help("merge.fallbacks",
                     "Pool merges that fell back to an inline merge")

        def _merge():
            # always present (zero-valued without a pool) so the
            # es_tpu_merge_* families never vanish from a scrape
            stats = self.merge_stats
            pool = self.merge_pool
            yield ("merge.merges", {}, stats.merges, "counter")
            yield ("merge.inline_merges", {}, stats.inline, "counter")
            yield ("merge.fallbacks", {}, stats.fallbacks, "counter")
            yield ("merge.worker_restarts", {}, stats.worker_restarts,
                   "counter")
            yield ("merge.latency", {}, stats.latency, "summary")
            yield ("merge.queue_depth", {},
                   pool.queue_depth() if pool is not None else 0, "gauge")
            yield ("merge.pool_size", {},
                   pool.size if pool is not None else 0, "gauge")
        reg.add_collector(_merge)
        reg.set_help("delta.packs",
                     "Device-resident delta packs currently chained")
        reg.set_help("delta.bytes",
                     "HBM bytes held by resident delta packs")
        reg.set_help("delta.appends",
                     "Delta packs built from append-only refreshes")
        reg.set_help("delta.compactions",
                     "Delta chains folded back into their base pack")
        reg.set_help("delta.compaction_failures",
                     "Compactions that failed (chain kept serving)")
        reg.set_help("delta.replayed_ops",
                     "Translog ops replayed for search visibility")
        reg.set_help("delta.search_visible_lag_seconds",
                     "Worst current indexed-to-searchable lag across shards")

        def _deltas():
            # always present (zero-valued with the delta path off) so
            # the es_tpu_delta_* families never vanish from a scrape
            svc = self.tpu_search
            ds = svc.delta_stats if svc is not None else None
            packs, nbytes = (svc.packs.delta_totals()
                             if svc is not None else (0, 0))
            replayed = ds.replayed_ops if ds is not None else 0
            lag = 0.0
            for index_service in self.indices.indices.values():
                for shard in index_service.shards.values():
                    replayed += shard.engine.replayed_ops
                    lag = max(lag, shard.engine.last_visible_lag_s)
            yield ("delta.packs", {}, packs, "gauge")
            yield ("delta.bytes", {}, nbytes, "gauge")
            yield ("delta.appends", {},
                   ds.appends if ds is not None else 0, "counter")
            yield ("delta.compactions", {},
                   ds.compactions if ds is not None else 0, "counter")
            yield ("delta.compaction_failures", {},
                   ds.compaction_failures if ds is not None else 0,
                   "counter")
            yield ("delta.replayed_ops", {}, replayed, "counter")
            yield ("delta.search_visible_lag_seconds", {}, lag, "gauge")
        reg.add_collector(_deltas)

    def _register_actions(self) -> None:
        from elasticsearch_tpu.rest.actions import (admin, aliases, cluster,
                                                    document, ingest,
                                                    introspect, search,
                                                    snapshots, tasks,
                                                    templates)
        for module in (document, search, admin, cluster, tasks, ingest,
                       snapshots, aliases, templates, introspect):
            module.register(self.controller, self)
        self.plugins.install_rest_handlers(self.controller, self)

    # ---------------- index helpers ----------------

    def create_index(self, name: str, settings: Settings,
                     mappings: Optional[dict]) -> IndexService:
        """Index creation applies the best-matching index template's
        defaults underneath the request (reference:
        MetadataCreateIndexService template application)."""
        from elasticsearch_tpu.templates import \
            compose_and_validate_creation
        flat, merged_mappings, aliases = compose_and_validate_creation(
            self.templates.templates, name, settings.get_as_dict(),
            mappings, self.indices.indices)
        svc = self.indices.create_index(name, Settings(flat),
                                        merged_mappings)
        for alias, props in aliases.items():
            self.indices.put_alias(name, alias, props)
        return svc

    def get_or_autocreate_index(self, name: str) -> IndexService:
        """Reference: auto-create on first doc (action.auto_create_index,
        default on) — templates apply to auto-created indices too."""
        if not self.indices.has_index(name):
            if not self.settings.get_bool("action.auto_create_index", True):
                from elasticsearch_tpu.common.errors import IndexNotFoundException
                raise IndexNotFoundException(f"no such index [{name}] and "
                                             f"auto-create is disabled")
            from elasticsearch_tpu.common.errors import \
                IndexAlreadyExistsException
            try:
                return self.create_index(name, Settings.EMPTY, None)
            except IndexAlreadyExistsException:
                # concurrent first-writes raced; the other one won
                return self.indices.index(name)
        return self.indices.index(name)

    # ---------------- background refresh (NRT cycle) ----------------

    def start_refresher(self) -> None:
        """The 1s refresh cycle (reference: IndexService#refreshTask §3.2)."""
        # refresh=wait_for blocks on the visibility checkpoint only when
        # this cycle is running (otherwise nothing would ever refresh —
        # the handler forces a refresh instead)
        self.refresher_active = True

        def tick():
            if self._closed:
                return
            for svc in list(self.indices.indices.values()):
                try:
                    svc.refresh()
                except Exception:  # noqa: BLE001 — background task
                    pass
            try:  # expire scroll/PIT contexts so idle nodes don't pin
                self.search_contexts.reap()
            except Exception:  # noqa: BLE001 — background task
                pass
            self._refresher = threading.Timer(self._refresh_interval, tick)
            self._refresher.daemon = True
            self._refresher.start()
        self._refresher = threading.Timer(self._refresh_interval, tick)
        self._refresher.daemon = True
        self._refresher.start()

        # the async-durability fsync cycle (reference: 5s translog sync
        # timer) — advances the persisted checkpoint for durability=async
        # shards and bounds the unpersisted-seqno backlog
        last_sync: Dict[str, float] = {}

        def sync_delay() -> float:
            # tick at the finest configured cadence so a per-index
            # index.translog.sync_interval_seconds SHORTER than the node
            # default is honored, not just longer ones
            delay = self._sync_interval
            for svc in list(self.indices.indices.values()):
                per = getattr(svc, "sync_interval_s", -1.0)
                if per > 0:
                    delay = min(delay, per)
            return max(0.05, delay)

        def sync_tick():
            if self._closed:
                return
            try:
                now = time.monotonic()
                for svc in list(self.indices.indices.values()):
                    per = getattr(svc, "sync_interval_s", -1.0)
                    interval = per if per > 0 else self._sync_interval
                    if now - last_sync.get(svc.name, 0.0) < interval - 1e-3:
                        continue
                    last_sync[svc.name] = now
                    for shard in list(svc.shards.values()):
                        try:
                            shard.engine.sync_translog()
                        except Exception:  # noqa: BLE001 — background task
                            pass
            finally:  # the cycle must survive any error
                self._syncer = threading.Timer(sync_delay(), sync_tick)
                self._syncer.daemon = True
                self._syncer.start()
        self._syncer = threading.Timer(sync_delay(), sync_tick)
        self._syncer.daemon = True
        self._syncer.start()

    def release_index(self, name: str) -> None:
        """`name` was closed or deleted: its resident packs and lowered
        plans go (HBM breaker bytes, pinned readers), and what it held
        of the frozen heap (segments, sources, packs) is thawed and
        collected, so that no cycle among them outlives the index."""
        if self.tpu_search is not None:
            self.tpu_search.invalidate_index(name)
        tracing.HEAP.settle(replaced=True)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.gc_watch.remove()
        self.refresher_active = False
        if self._refresher:
            self._refresher.cancel()
        if self._syncer:
            self._syncer.cancel()
        if self.serving_front is not None:
            # fronts stop accepting before the device path tears down
            self.serving_front.close()
            self.serving_front = None
        if self.merge_pool is not None:
            self.merge_pool.close()
            self.merge_pool = None
        if self.cluster is not None:
            self.cluster.close()
        if self.profiler is not None:
            self.profiler.close()
        if self.tpu_search is not None:
            self.tpu_search.close()
        if self.flight_recorder is not None:
            from elasticsearch_tpu.common import events as _events
            if _events.get_recorder() is self.flight_recorder:
                _events.set_recorder(None)
            self.flight_recorder.close()
        ccs_client = getattr(self, "_ccs_transport", None)
        if ccs_client is not None:
            ccs_client.close()
        self.indices.close()
        # what this node held goes with it; the last node of the process
        # hands the whole heap back to the collector
        tracing.HEAP.node_closed()

    # ---------------- in-process dispatch (tests + http) ----------------

    def handle(self, method: str, path: str,
               params: Optional[Dict[str, str]] = None,
               body: Any = None, raw_body: bytes = b""):
        if body is None and raw_body:
            text = raw_body.decode("utf-8", errors="replace")
            if path.endswith(("/_bulk", "/_msearch")):
                body = text  # NDJSON bodies parse per line downstream
            elif text.strip():
                from elasticsearch_tpu.common.errors import ParsingException
                try:
                    body = json.loads(text)
                except json.JSONDecodeError as e:
                    return 400, {"error": {"type": "parsing_exception",
                                           "reason": str(e)}, "status": 400}
        pool = self.merge_pool
        if pool is None:
            return self.controller.dispatch(method, path, params, body,
                                            raw_body)
        # merge pool active: the dispatch may hand back a deferred
        # k-way merge descriptor; resolve it off this interpreter
        from elasticsearch_tpu.search import merge as merge_mod
        with merge_mod.deferring(True):
            status, payload = self.controller.dispatch(
                method, path, params, body, raw_body)
        if isinstance(payload, merge_mod.DeferredMerge):
            payload = pool.merge(payload.descriptor)
        return status, payload

    def merge_status(self) -> Dict[str, Any]:
        """The /_tpu/stats merge block: where deferred merges run and
        what they cost."""
        pool = self.merge_pool
        if pool is not None:
            return {"mode": "pool", **pool.status()}
        mode = "front" if self.serving_front is not None else "inline"
        return {"mode": mode, **self.merge_stats.to_dict()}


class _Handler(BaseHTTPRequestHandler):
    node: Node = None  # set by serve()
    protocol_version = "HTTP/1.1"

    def _do(self):
        # wall and (sampled) CPU seconds of the whole request on its
        # thread, from after the headers are read to after the response
        # is written (stages of the TPU serving path; none without it)
        tpu = self.node.tpu_search
        stages = tpu.stages if tpu is not None else None
        with tracing.stage(stages, "rest_request", annotate=False,
                           cpu=stages is not None
                           and stages.sample_cpu("rest_request")):
            self._respond(stages)

    def _respond(self, stages):
        parsed = urlparse(self.path)
        params = {k: v[0] if v else "" for k, v in
                  parse_qs(parsed.query, keep_blank_values=True).items()}
        # trace context arrives as an HTTP header; the controller reads
        # it from params (header wins over a query-param duplicate)
        traceparent = self.headers.get("traceparent")
        if traceparent:
            params["traceparent"] = traceparent
        # tenant identity arrives the same way (header wins; the
        # controller validates and binds it to the dispatch thread)
        tenant = self.headers.get("X-Tenant-Id")
        if tenant:
            params["tenant_id"] = tenant
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        status, payload = self.node.handle(self.command, parsed.path, params,
                                           None, raw)
        extra_headers = (payload.pop("_headers", None)
                         if isinstance(payload, dict) else None)
        if isinstance(payload, dict) and "_cat" in payload and len(payload) == 1:
            data = payload["_cat"].encode("utf-8")
            ctype = "text/plain; charset=UTF-8"
        elif isinstance(payload, str):
            # text endpoints (_nodes/hot_threads) respond as plain text
            data = payload.encode("utf-8")
            ctype = "text/plain; charset=UTF-8"
        else:
            # dumps_response_bytes renders embedded ColumnarHits blocks
            # from their device-result columns (the metadata-only shape,
            # and that shape with each hit's whole `_source`, in one
            # native call with the GIL released, no per-hit Python);
            # plain payloads serialize as before
            from elasticsearch_tpu.search.serializer import \
                dumps_response_bytes
            with tracing.stage(stages, "rest_render", annotate=False,
                               cpu=False):
                data = dumps_response_bytes(payload, stages)
            ctype = "application/json; charset=UTF-8"
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.send_header("X-elastic-product", "Elasticsearch-TPU")
        for name, value in (extra_headers or {}).items():
            self.send_header(name, value)
        self.end_headers()
        if self.command != "HEAD":
            self.wfile.write(data)

    do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _do

    def log_message(self, fmt, *args):  # quiet by default
        pass


def serve(node: Node, host: str = "127.0.0.1", port: int = 9200
          ) -> ThreadingHTTPServer:
    handler = type("BoundHandler", (_Handler,), {"node": node})
    server = ThreadingHTTPServer((host, port), handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server


def _load_or_create_node_id(data_path: str, node_name: str) -> str:
    """A node's identity must survive restarts (reference: NodeEnvironment
    node id persistence) so the cluster state keeps referring to it."""
    import os
    p = os.path.join(data_path, "_state", "node_id")
    try:
        with open(p, "r", encoding="utf-8") as f:
            return f.read().strip()
    except OSError:
        pass
    nid = uuid.uuid4().hex[:20]
    try:
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "w", encoding="utf-8") as f:
            f.write(nid)
    except OSError:
        pass
    return nid


def _parse_hostport(s: str) -> tuple:
    s = s.strip()
    host, sep, port = s.rpartition(":")
    if not sep or not port.isdigit():
        raise SystemExit(
            f"--seed-hosts entry [{s}] must be host:port (e.g. "
            f"127.0.0.1:9300)")
    return (host or "127.0.0.1", int(port))


def main() -> None:
    parser = argparse.ArgumentParser(description="elasticsearch-tpu node")
    parser.add_argument("--port", type=int, default=9200)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--data-path", default="./data")
    parser.add_argument("--node-name", default="node-1")
    parser.add_argument("--transport-port", type=int, default=None,
                        help="enable cluster mode on this TCP port "
                             "(0 = ephemeral)")
    parser.add_argument("--seed-hosts", default="",
                        help="comma-separated host:port transport "
                             "addresses of seed nodes")
    parser.add_argument("--initial-master-nodes", default="",
                        help="comma-separated node NAMES forming the "
                             "bootstrap voting configuration")
    parser.add_argument("-E", action="append", default=[], metavar="K=V",
                        dest="settings", help="node setting override")
    args = parser.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.settings)
    node = Node(args.data_path, node_name=args.node_name,
                settings=Settings.of(overrides))
    node.http_port = args.port
    if args.transport_port is not None or args.seed_hosts:
        seeds = [_parse_hostport(s) for s in args.seed_hosts.split(",")
                 if s.strip()]
        masters = [m.strip() for m in args.initial_master_nodes.split(",")
                   if m.strip()] or [args.node_name]
        node.start_cluster(host=args.host,
                           transport_port=args.transport_port or 0,
                           seed_hosts=seeds, initial_master_nodes=masters)
        print(f"[{args.node_name}] transport on "
              f"{args.host}:{node.cluster.transport.port}")
    node.start_refresher()
    server = serve(node, args.host, args.port)
    print(f"[{args.node_name}] listening on http://{args.host}:{args.port}")
    front_ports = node.start_serving_fronts(host=args.host)
    if front_ports:
        print(f"[{args.node_name}] serving fronts on "
              + ", ".join(f"http://{args.host}:{p}" for p in front_ports))
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        node.close()


if __name__ == "__main__":
    main()
