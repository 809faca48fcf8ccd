"""ClusterService — wires Coordinator + TransportService + allocation
into a running node: state application, shard lifecycle, and the
request-routing layer REST actions use in cluster mode.

Reference analogs (SURVEY.md §2.1 #12-18, #32, §3.4/§3.5):
  - ClusterApplierService: committed states reconcile local shards on a
    dedicated applier thread (create/remove/promote), then notify the
    master shard-started (ShardStateAction).
  - MasterService task batching lives in Coordinator.submit_state_update;
    this class adds the master-side actions (create/delete index, put
    mapping, shard-started) and the reroute-on-change loop.
  - TransportService action handlers for the data plane: doc ops, bulk
    sub-batches, and the search query/fetch group hop.

Design notes (tpu-first): the node-level data plane stays host-side
control traffic — JSON over TCP on the DCN tier — while all scoring math
stays on-device behind the per-node TpuSearchService. A cross-node
search is: route shards → each node runs its LOCAL query phase (kernel
fast path when eligible) → coordinator merges small top-k windows. The
heavy arrays never cross the host network (SURVEY §2.4 two-tier comms).
"""

from __future__ import annotations

import base64
import heapq
import itertools
import json
import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from elasticsearch_tpu.cluster.allocation import AllocationService
from elasticsearch_tpu.cluster.coordination import Coordinator
from elasticsearch_tpu.cluster.state import (INITIALIZING, STARTED,
                                             ClusterState, DiscoveryNode,
                                             IndexMeta, ShardRouting)
from elasticsearch_tpu.common.errors import (EsException,
                                             EsRejectedExecutionException,
                                             IllegalArgumentException,
                                             IndexNotFoundException,
                                             NoShardAvailableActionException,
                                             shard_failure_entry)
from elasticsearch_tpu.common.pressure import operation_bytes
from elasticsearch_tpu.common import events, tracing
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.translog import write_atomic
from elasticsearch_tpu.transport.retry import (RetryPolicy, is_retryable,
                                               send_with_retry)
from elasticsearch_tpu.transport.service import (ConnectTransportException,
                                                 RemoteTransportException,
                                                 TransportService)

logger = logging.getLogger("elasticsearch_tpu.cluster")

# data-plane actions (reference: indices:data/write/*, indices:data/read/*)
ACTION_DOC_OP = "indices/data/doc_op"
ACTION_REPLICA_OP = "indices/data/replica_op"
# peer recovery (reference: internal:index/shard/recovery/*)
ACTION_RECOVERY_START = "indices/recovery/start"
ACTION_RECOVERY_FILE = "indices/recovery/file_chunk"
ACTION_RECOVERY_OPS = "indices/recovery/translog_ops"
ACTION_RECOVERY_FINISH = "indices/recovery/finish"
ACTION_STORE_FOUND = "cluster/shard/store_found"
ACTION_BULK = "indices/data/bulk_group"
ACTION_QUERY_GROUP = "indices/data/search_group"
ACTION_KNN_GROUP = "indices/data/knn_group"
ACTION_COUNT_GROUP = "indices/data/count_group"
# master-plane actions (reference: cluster:admin/*, internal:cluster/shard/*)
ACTION_MAINTENANCE = "indices/data/maintenance"
ACTION_CREATE_INDEX = "cluster/admin/create_index"
ACTION_DELETE_INDEX = "cluster/admin/delete_index"
ACTION_CLOSE_INDEX = "cluster/admin/close_index"
ACTION_OPEN_INDEX = "cluster/admin/open_index"
ACTION_PUT_MAPPING = "cluster/admin/put_mapping"
ACTION_UPDATE_INDEX_SETTINGS = "cluster/admin/update_index_settings"
ACTION_UPDATE_CLUSTER_SETTINGS = "cluster/admin/update_cluster_settings"
ACTION_UPDATE_ALIASES = "cluster/admin/update_aliases"
ACTION_PUT_TEMPLATE = "cluster/admin/put_template"
ACTION_DELETE_TEMPLATE = "cluster/admin/delete_template"
ACTION_PUT_PIPELINE = "cluster/admin/put_pipeline"
ACTION_DELETE_PIPELINE = "cluster/admin/delete_pipeline"

# cluster-wide settings this build can apply at runtime (reference:
# ClusterSettings registry of Dynamic-flagged settings)
DYNAMIC_CLUSTER_SETTINGS = ("action.auto_create_index",)
DYNAMIC_CLUSTER_PREFIXES = ("logger.", "cluster.remote.")
ACTION_SHARD_STARTED = "cluster/shard/started"
ACTION_SHARD_FAILED = "cluster/shard/failed"

from elasticsearch_tpu.ccs import ACTION_REMOTE_SEARCH  # noqa: E402

_RECOVERY_CHUNK = 1 << 20  # 1MB file-copy chunks


class MasterNotDiscoveredException(EsException):
    pass


class ThreadScheduler:
    """Single-threaded delayed-task scheduler (Coordinator's scheduler
    seam for real deployments; tests use DeterministicTaskQueue)."""

    class _Handle:
        __slots__ = ("cancelled",)

        def __init__(self):
            self.cancelled = False

        def cancel(self):
            self.cancelled = True

    def __init__(self):
        self._heap: List[Tuple[float, int, Any, Callable]] = []
        self._seq = itertools.count()
        self._cv = threading.Condition()
        self._stopped = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="cluster-scheduler")
        self._thread.start()

    def schedule(self, delay_s: float, fn: Callable[[], None]):
        handle = self._Handle()
        with self._cv:
            heapq.heappush(self._heap,
                           (time.monotonic() + max(0.0, delay_s),
                            next(self._seq), handle, fn))
            self._cv.notify()
        return handle

    def _run(self):
        while True:
            with self._cv:
                while not self._stopped and (
                        not self._heap
                        or self._heap[0][0] > time.monotonic()):
                    if self._stopped:
                        return
                    timeout = (self._heap[0][0] - time.monotonic()
                               if self._heap else None)
                    self._cv.wait(timeout=timeout)
                if self._stopped:
                    return
                _, _, handle, fn = heapq.heappop(self._heap)
            if handle.cancelled:
                continue
            try:
                fn()
            except Exception:  # noqa: BLE001 — scheduled task bug
                logger.exception("scheduled task failed")

    def close(self):
        with self._cv:
            self._stopped = True
            self._cv.notify_all()


class FilePersisted:
    """Durable coordination state (reference: GatewayMetaState — the
    term/vote/accepted-state triple must survive restart)."""

    def __init__(self, path: str):
        self.path = path

    def load(self) -> Optional[dict]:
        if not os.path.exists(self.path):
            return None
        try:
            with open(self.path, "rb") as f:
                return json.loads(f.read().decode("utf-8"))
        except (OSError, json.JSONDecodeError):
            return None

    def store(self, data: dict) -> None:
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        write_atomic(self.path,
                     json.dumps(data, sort_keys=True).encode("utf-8"))


class _CoordTransport:
    """Adapts TransportService's Future API to the Coordinator's
    callback seam."""

    def __init__(self, ts: TransportService):
        self.ts = ts

    def register(self, action: str, handler) -> None:
        self.ts.register_handler(action, handler)

    def send(self, address, action: str, payload: Dict[str, Any],
             on_done: Callable[[bool, Any], None]) -> None:
        fut = self.ts.send_request_async(tuple(address), action, payload)

        def cb(f: Future) -> None:
            exc = f.exception()
            if exc is not None:
                if is_retryable(exc) and not isinstance(
                        exc, RemoteTransportException):
                    # a dead pooled connection must not poison the
                    # coordinator's resend — next attempt dials fresh.
                    # A remote rejection (429 pushback) travelled over a
                    # HEALTHY connection; keep it pooled.
                    self.ts.evict(tuple(address))
                on_done(False, None)
            else:
                on_done(True, f.result())

        fut.add_done_callback(cb)


class ClusterService:
    """The cluster-mode brain of one node."""

    def __init__(self, node, *, host: str = "127.0.0.1",
                 transport_port: int = 0,
                 seed_hosts: Optional[List[Tuple[str, int]]] = None,
                 initial_master_names: Optional[List[str]] = None):
        self.node = node
        self.transport = TransportService(host=host, port=transport_port)
        self.transport.start()
        self.local_node = DiscoveryNode(
            node_id=node.node_id, name=node.node_name, host=host,
            port=self.transport.port, http_port=getattr(node, "http_port", 0))
        self.transport.local_node = self.local_node.to_json()
        self.scheduler = ThreadScheduler()
        seeds = list(seed_hosts or [])
        if self.local_node.address not in seeds:
            seeds.append(self.local_node.address)
        self.allocation = AllocationService()
        self.coordinator = Coordinator(
            self.local_node,
            transport=_CoordTransport(self.transport),
            scheduler=self.scheduler,
            persisted=FilePersisted(os.path.join(
                node.indices.data_path, "_state", "coordination.json")),
            on_commit=self._on_commit,
            seed_addresses=seeds,
            initial_master_names=(initial_master_names
                                  or [node.node_name]),
            cluster_uuid=node.cluster_uuid)

        # applier thread: reconcile runs off the coordinator lock
        self._applied = ClusterState.empty(node.cluster_uuid)
        self._apply_cv = threading.Condition()
        self._pending_state: Optional[ClusterState] = None
        self._applier_stop = False
        self._applier = threading.Thread(target=self._applier_loop,
                                         daemon=True,
                                         name="cluster-applier")
        # shard copies this node reported started, keyed by allocation_id
        self._started_sent: Set[str] = set()
        # ARS-lite (reference: ResponseCollectorService +
        # OperationRouting#searchShards adaptive replica selection,
        # SURVEY.md §2.1#19/P2): EWMA of recent search-group latency per
        # node; _route_shards ranks STARTED copies by it, round-robin
        # among the unmeasured, so replicas actually serve reads
        self._ars_lock = threading.Lock()
        self._node_ewma: Dict[str, float] = {}
        self._ars_rr = 0
        # index uuids this applier has seen in a committed state; only
        # those may be deleted when they later disappear from the state.
        # Pre-existing local data the cluster never knew about (e.g. a
        # single-node data dir restarted with --transport-port) is left
        # untouched — the reference's dangling-index safety.
        self._seen_index_uuids: Set[str] = set()

        for action, handler in (
                (ACTION_DOC_OP, self._handle_doc_op),
                (ACTION_BULK, self._handle_bulk_group),
                (ACTION_QUERY_GROUP, self._handle_query_group),
                (ACTION_KNN_GROUP, self._handle_knn_group),
                (ACTION_REMOTE_SEARCH, self._handle_remote_search),
                (ACTION_MAINTENANCE, self._handle_maintenance),
                (ACTION_COUNT_GROUP, self._handle_count_group),
                (ACTION_CREATE_INDEX, self._handle_create_index),
                (ACTION_DELETE_INDEX, self._handle_delete_index),
                (ACTION_CLOSE_INDEX, self._handle_close_index),
                (ACTION_OPEN_INDEX, self._handle_open_index),
                (ACTION_PUT_MAPPING, self._handle_put_mapping),
                (ACTION_UPDATE_INDEX_SETTINGS,
                 self._handle_update_index_settings),
                (ACTION_UPDATE_CLUSTER_SETTINGS,
                 self._handle_update_cluster_settings),
                (ACTION_UPDATE_ALIASES, self._handle_update_aliases),
                (ACTION_PUT_TEMPLATE, self._handle_put_template),
                (ACTION_DELETE_TEMPLATE, self._handle_delete_template),
                (ACTION_PUT_PIPELINE, self._handle_put_pipeline),
                (ACTION_DELETE_PIPELINE, self._handle_delete_pipeline),
                (ACTION_SHARD_STARTED, self._handle_shard_started),
                (ACTION_SHARD_FAILED, self._handle_shard_failed),
                (ACTION_REPLICA_OP, self._handle_replica_op),
                (ACTION_RECOVERY_START, self._handle_recovery_start),
                (ACTION_RECOVERY_FILE, self._handle_recovery_file),
                (ACTION_RECOVERY_OPS, self._handle_recovery_ops),
                (ACTION_RECOVERY_FINISH, self._handle_recovery_finish),
                (ACTION_STORE_FOUND, self._handle_store_found)):
            self.transport.register_handler(action, handler)
        from elasticsearch_tpu.tasks import register_transport_handlers
        register_transport_handlers(node, self.transport)
        # replica recoveries in flight on this node, keyed (index, shard)
        self._recovering: Set[Tuple[str, int]] = set()
        self._recovering_lock = threading.Lock()
        # recoveries this node is SOURCING, keyed (index, shard, aid):
        # {release (translog retention), address, expires}. The primary
        # fans live ops out to these targets from registration onward —
        # the reference's replication-group tracking during recovery —
        # and holds their translog ops against trim.
        self._recovery_sources: Dict[Tuple[str, int, str],
                                     Dict[str, Any]] = {}
        self._recovery_sources_lock = threading.Lock()

    def start(self) -> None:
        self._applier.start()
        self.coordinator.start()

        def sweep():
            self._expire_recovery_sources()
            self.scheduler.schedule(60.0, sweep)

        self.scheduler.schedule(60.0, sweep)

    def close(self) -> None:
        self.coordinator.stop()
        with self._apply_cv:
            self._applier_stop = True
            self._apply_cv.notify_all()
        self.scheduler.close()
        self.transport.close()

    # ------------------------------------------------------------------
    # state application
    # ------------------------------------------------------------------

    def _on_commit(self, state: ClusterState) -> None:
        # called under the coordinator lock — hand off, never block
        with self._apply_cv:
            self._pending_state = state
            self._apply_cv.notify_all()

    def _applier_loop(self) -> None:
        while True:
            with self._apply_cv:
                while self._pending_state is None and not self._applier_stop:
                    self._apply_cv.wait()
                if self._applier_stop:
                    return
                state, self._pending_state = self._pending_state, None
            try:
                self._reconcile(state)
                self._apply_cluster_settings(state)
                self._prune_recovery_sources(state)
                self._report_local_stores(state)
            except Exception:  # noqa: BLE001 — applier bug must not die
                logger.exception("[%s] state reconcile failed",
                                 self.local_node.name)
            with self._apply_cv:
                self._applied = state
                self._apply_cv.notify_all()
            self._maybe_reroute(state)

    def applied_state(self) -> ClusterState:
        with self._apply_cv:
            return self._applied

    def wait_for_applied(self, predicate: Callable[[ClusterState], bool],
                         timeout: float = 10.0) -> Optional[ClusterState]:
        deadline = time.monotonic() + timeout
        with self._apply_cv:
            while True:
                if predicate(self._applied):
                    return self._applied
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._apply_cv.wait(timeout=remaining)

    def _reconcile(self, state: ClusterState) -> None:
        """Make local shards match the routing table (reference:
        IndicesClusterStateService#applyClusterState)."""
        indices = self.node.indices
        local_id = self.local_node.node_id

        # delete local indices that no longer exist in the state — but
        # ONLY indices the cluster state once owned (matching uuid seen
        # in a prior committed state); anything else is dangling local
        # data that must never be rmtree'd by a state that merely
        # doesn't know it
        for meta in state.indices.values():
            self._seen_index_uuids.add(meta.uuid)
        for name in [n for n in list(indices.indices)
                     if n not in state.indices
                     and indices.index(n).index_uuid
                     in self._seen_index_uuids]:
            try:
                indices.delete_index(name)
                self.node.release_index(name)
            except EsException:
                pass

        for name, meta in state.indices.items():
            local_copies = [c for c in
                            (c for sh in state.routing.get(name, {}).values()
                             for c in sh)
                            if c.node_id == local_id]
            if not indices.has_index(name):
                if not local_copies:
                    continue
                indices.create_index(
                    name, Settings.of(meta.settings), meta.mapping,
                    index_uuid=meta.uuid, create_shards=False)
            svc = indices.index(name)
            # closed indices: shut local shards via the empty `wanted`
            # below; the flag makes direct access raise
            # IndexClosedException, not ShardNotFound
            was_closed = svc.closed
            svc.closed = (getattr(meta, "state", "open") == "close")
            if svc.closed and not was_closed:
                # release the closed index's resident packs (HBM breaker
                # bytes + device arrays)
                self.node.release_index(name)
            if was_closed and svc.closed:
                continue  # already reconciled closed; nothing to do
            if meta.mapping:
                try:  # idempotent merge keeps local mappers current
                    svc.mapper.merge(meta.mapping)
                except EsException:
                    pass
            # sync dynamic index settings from the cluster metadata —
            # including REMOVALS (a key cleared on the master must clear
            # here too), and only when something actually changed (this
            # runs on every state publish)
            def _is_dyn(k):
                return k in svc.DYNAMIC_KEYS or any(
                    k.startswith(p) for p in svc.DYNAMIC_PREFIXES)
            dyn = {k: None for k in svc.settings.get_as_dict()
                   if _is_dyn(k) and k not in meta.settings}
            dyn.update({k: v for k, v in meta.settings.items()
                        if _is_dyn(k)})
            dyn["index.number_of_replicas"] = meta.number_of_replicas
            current = {k: svc.settings.get(k) for k in dyn}
            if any(current[k] != v for k, v in dyn.items()):
                svc.apply_dynamic_settings(dyn)
            wanted = {c.shard: c for c in local_copies}
            # remove shards no longer assigned here
            for shard_num in [s for s in list(svc.shards) if s not in wanted]:
                shard = svc.shards.pop(shard_num)
                try:  # keep the store current before shutting the copy
                    shard.flush()
                except EsException:
                    pass
                shard.close()
            # create/promote assigned copies. Primaries open from the
            # local store immediately; replicas run peer recovery from
            # their primary (file sync + translog replay) BEFORE they
            # report started (reference: IndexShard#startRecovery →
            # PeerRecoveryTargetService).
            for shard_num, copy in wanted.items():
                shard = svc.shards.get(shard_num)
                if shard is not None and copy.primary and not shard.primary:
                    shard.promote_to_primary(shard.primary_term + 1)
                    self._write_shard_state(svc, shard_num,
                                            copy.allocation_id,
                                            primary=True)
                if copy.state == STARTED and shard is None:
                    # node bounced fast enough to keep its assignment:
                    # reopen from the local store (primary) or catch up
                    # from the primary (replica; idempotent replay)
                    if copy.primary:
                        if self._open_primary_shard(
                                svc, name, shard_num, copy) is None:
                            continue
                        self._write_shard_state(svc, shard_num,
                                                copy.allocation_id,
                                                primary=True)
                    else:
                        self._start_replica_recovery(name, shard_num,
                                                     copy, state)
                    continue
                if copy.state != INITIALIZING \
                        or copy.allocation_id in self._started_sent:
                    continue
                if copy.primary:
                    if shard is None and self._open_primary_shard(
                            svc, name, shard_num, copy) is None:
                        continue
                    self._write_shard_state(svc, shard_num,
                                            copy.allocation_id,
                                            primary=True)
                    self._started_sent.add(copy.allocation_id)
                    self._send_to_master(ACTION_SHARD_STARTED, {
                        "index": name, "shard": shard_num,
                        "allocation_id": copy.allocation_id})
                else:
                    self._start_replica_recovery(name, shard_num, copy,
                                                 state)

    def _open_primary_shard(self, svc, name: str, shard_num: int, copy):
        """Open a primary copy from the local store, failing it TYPED
        on a corrupt store instead of letting CorruptIndexException
        kill the state applier: the copy is reported shard-failed to
        the master, whose reroute promotes/reassigns it — bounded by
        `index.allocation.max_retries` with backoff (reference: a
        corrupted shard fails its copy and the MaxRetryAllocationDecider
        stops the crash-loop; `failed_allocations` surfaces the streak
        in `_nodes/stats`)."""
        from elasticsearch_tpu.index.store import CorruptIndexException
        try:
            return svc.create_shard(shard_num, primary=True,
                                    allocation_id=copy.allocation_id)
        except CorruptIndexException as exc:
            logger.error("[%s] corrupt store opening %s[%d]: %s — "
                         "failing the shard copy",
                         self.local_node.name, name, shard_num, exc)
            # a partially-constructed copy must not linger
            broken = svc.shards.pop(shard_num, None)
            if broken is not None:
                try:
                    broken.close()
                except EsException:
                    pass
            self._send_to_master(ACTION_SHARD_FAILED, {
                "index": name, "shard": shard_num,
                "allocation_id": copy.allocation_id})
            return None

    @staticmethod
    def _write_shard_state(svc, shard_num: int, allocation_id: str,
                           primary: bool) -> None:
        """Persist the shard copy's identity next to its store so a
        restarted node can prove it holds an in-sync copy (reference:
        ShardStateMetadata on disk)."""
        p = os.path.join(svc.data_path, str(shard_num), "_shard_state.json")
        os.makedirs(os.path.dirname(p), exist_ok=True)
        write_atomic(p, json.dumps(
            {"allocation_id": allocation_id,
             "primary": primary}).encode("utf-8"))

    @staticmethod
    def _read_shard_state(svc, shard_num: int) -> Optional[Dict[str, Any]]:
        p = os.path.join(svc.data_path, str(shard_num), "_shard_state.json")
        try:
            with open(p, "rb") as f:
                return json.loads(f.read().decode("utf-8"))
        except (OSError, json.JSONDecodeError):
            return None

    def _prune_recovery_sources(self, state: ClusterState) -> None:
        """Release source-side recovery registrations once the target
        copy is STARTED in the routing table (live fan-out now reaches
        it via the normal replica path) or gone from it entirely."""
        done = []
        with self._recovery_sources_lock:
            for (index, shard_num, aid), entry in \
                    list(self._recovery_sources.items()):
                copies = state.shard_copies(index, shard_num)
                match = next((c for c in copies
                              if c.allocation_id == aid), None)
                if match is None or match.state == STARTED:
                    done.append(self._recovery_sources.pop(
                        (index, shard_num, aid)))
        for entry in done:
            entry["release"]()

    def _report_local_stores(self, state: ClusterState) -> None:
        """Red-primary repair path: if this node's disk holds an in-sync
        copy of a shard whose primary is unassigned, offer it to the
        master (reference: the PrimaryShardAllocator's store fetch —
        TransportNodesListGatewayStartedShards — inverted to a push)."""
        indices = self.node.indices
        for name, meta in state.indices.items():
            if not indices.has_index(name):
                continue
            svc = indices.index(name)
            if svc.index_uuid != meta.uuid:
                continue  # a different incarnation of the name
            for shard_num in range(meta.number_of_shards):
                primary = state.primary(name, shard_num)
                if primary is None or primary.node_id is not None:
                    continue
                in_sync = meta.in_sync.get(str(shard_num)) or []
                disk = self._read_shard_state(svc, shard_num)
                if disk and disk.get("allocation_id") in in_sync:
                    self._send_to_master(ACTION_STORE_FOUND, {
                        "index": name, "shard": shard_num,
                        "allocation_id": disk["allocation_id"],
                        "node": self.local_node.to_json()})

    def _handle_store_found(self, payload, from_node) -> Dict[str, Any]:
        index, shard_num = payload["index"], int(payload["shard"])
        aid = payload["allocation_id"]
        node = DiscoveryNode.from_json(payload["node"])

        def update(state: ClusterState) -> ClusterState:
            meta = state.indices.get(index)
            primary = state.primary(index, shard_num)
            if (meta is None or primary is None
                    or primary.node_id is not None
                    or node.node_id not in state.nodes
                    or aid not in (meta.in_sync.get(str(shard_num)) or [])):
                return state  # raced another assignment — ignore
            if self.allocation.allocation_exhausted(index, shard_num, meta):
                # a corrupt store would otherwise crash-loop through
                # store-found → open → CorruptIndexException → failed →
                # store-found forever; after max_retries the copy stays
                # unassigned (red, visible) until a manual reroute
                return state
            routing = {idx: {s: list(c) for s, c in sh.items()}
                       for idx, sh in state.routing.items()}
            copies = routing[index][shard_num]
            for i, c in enumerate(copies):
                if c.primary:
                    copies[i] = ShardRouting(index, shard_num,
                                             node.node_id, True,
                                             INITIALIZING, aid)
            return state.with_updates(routing=routing)

        self._run_master_update(
            update, source=f"store-found[{index}][{shard_num}]")
        return {"acknowledged": True}

    def _apply_cluster_settings(self, state: ClusterState) -> None:
        """Every node recomputes base config + published persistent +
        transient (reference precedence) — removals revert to the base
        node config, never to a stale live value."""
        pair = (dict(state.persistent_settings),
                dict(state.transient_settings))
        if pair != getattr(self, "_last_applied_settings", None):
            self._last_applied_settings = pair
            self.node.recompute_settings(state.persistent_settings,
                                         state.transient_settings)
        if state.ingest_pipelines != getattr(
                self, "_last_applied_pipelines", None):
            self._last_applied_pipelines = dict(state.ingest_pipelines)
            try:
                self.node.ingest.sync(state.ingest_pipelines)
            except Exception:  # noqa: BLE001 — a bad pipeline body in
                logger.exception("pipeline sync failed")  # state
        if state.index_templates != getattr(
                self, "_last_applied_templates", None):
            self._last_applied_templates = dict(state.index_templates)
            self.node.templates.sync(state.index_templates)

    def _maybe_reroute(self, state: ClusterState) -> None:
        """Master-side convergence loop: if a reroute would change the
        routing table (unassigned copies placeable, dead-node copies to
        fail over), submit it (reference: the reroute after every
        join/leave/create)."""
        if not self.coordinator.is_master():
            return
        new = self.allocation.reroute(state)
        if new.routing == state.routing:
            return

        def update(base: ClusterState) -> ClusterState:
            rerouted = self.allocation.reroute(base)
            if rerouted.routing == base.routing:
                return base
            return rerouted

        self.coordinator.submit_state_update(update, source="reroute")

    # ------------------------------------------------------------------
    # master-side actions
    # ------------------------------------------------------------------

    def _master_address(self) -> Tuple[str, int]:
        master = self.coordinator.master_node()
        if master is None:
            raise MasterNotDiscoveredException("master not discovered")
        return master.address

    def _send_to_master(self, action: str, payload: Dict[str, Any]) -> None:
        """Fire-and-forget with one retry (shard-started etc.)."""
        try:
            addr = self._master_address()
        except MasterNotDiscoveredException:
            self.scheduler.schedule(
                1.0, lambda: self._send_to_master(action, payload))
            return
        fut = self.transport.send_request_async(addr, action, payload)

        def cb(f: Future) -> None:
            if f.exception() is not None:
                self.scheduler.schedule(
                    1.0, lambda: self._send_to_master(action, payload))

        fut.add_done_callback(cb)

    def _run_master_update(self, update, source: str,
                           timeout: float = 15.0) -> None:
        """Submit on the local coordinator (must be master) and wait."""
        done: "Future[None]" = Future()

        def on_done(err: Optional[Exception]) -> None:
            if err is not None:
                done.set_exception(err)
            else:
                done.set_result(None)

        self.coordinator.submit_state_update(update, source=source,
                                             on_done=on_done)
        done.result(timeout=timeout)

    def _handle_create_index(self, payload, from_node) -> Dict[str, Any]:
        name = payload["name"]
        from elasticsearch_tpu.indices.service import _validate_index_name
        _validate_index_name(name)
        import uuid as uuid_mod
        index_uuid = uuid_mod.uuid4().hex[:20]

        def update(state: ClusterState) -> ClusterState:
            if name in state.indices:
                from elasticsearch_tpu.common.errors import \
                    IndexAlreadyExistsException
                raise IndexAlreadyExistsException(
                    f"index [{name}] already exists")
            # template defaults compose UNDER the request, read from the
            # authoritative state inside the update (so template puts
            # racing this create serialize through the master queue)
            from elasticsearch_tpu.templates import \
                compose_and_validate_creation
            norm, mapping, aliases = compose_and_validate_creation(
                state.index_templates, name,
                payload.get("settings") or {}, payload.get("mapping"),
                state.indices)
            flat = Settings(norm)
            n_shards = flat.get_int("index.number_of_shards", 1)
            n_replicas = flat.get_int("index.number_of_replicas", 0)
            norm["index.number_of_shards"] = n_shards
            norm["index.number_of_replicas"] = n_replicas
            if "index.creation_date" not in norm:  # rollover max_age
                norm["index.creation_date"] = int(time.time() * 1000)
            meta = IndexMeta(
                name=name, uuid=index_uuid, settings=norm,
                mapping=mapping, number_of_shards=n_shards,
                number_of_replicas=n_replicas, aliases=aliases)
            new_indices = dict(state.indices)
            new_indices[name] = meta
            return self.allocation.reroute(
                state.with_updates(indices=new_indices))

        self._run_master_update(update, source=f"create-index[{name}]")
        return {"acknowledged": True, "index": name}

    def _handle_close_index(self, payload, from_node) -> Dict[str, Any]:
        """Reference: MetadataIndexStateService#closeIndices — the meta
        flips to CLOSE and the index's routing is dropped; appliers shut
        local shards (data stays on disk)."""
        name = payload["name"]

        def update(state: ClusterState) -> ClusterState:
            meta = state.indices.get(name)
            if meta is None:
                raise IndexNotFoundException(f"no such index [{name}]")
            import dataclasses as _dc
            new_indices = dict(state.indices)
            new_indices[name] = _dc.replace(meta, state="close")
            new_routing = {k: v for k, v in state.routing.items()
                           if k != name}
            return state.with_updates(indices=new_indices,
                                      routing=new_routing)

        self._run_master_update(update, source=f"close-index[{name}]")
        return {"acknowledged": True, "indices": {name: {"closed": True}}}

    def _handle_open_index(self, payload, from_node) -> Dict[str, Any]:
        """Reference: MetadataIndexStateService#openIndices — meta back
        to OPEN; reroute re-allocates primaries onto the nodes holding
        their stores (the store-found machinery)."""
        name = payload["name"]

        def update(state: ClusterState) -> ClusterState:
            meta = state.indices.get(name)
            if meta is None:
                raise IndexNotFoundException(f"no such index [{name}]")
            import dataclasses as _dc
            new_indices = dict(state.indices)
            new_indices[name] = _dc.replace(meta, state="open")
            return self.allocation.reroute(
                state.with_updates(indices=new_indices))

        self._run_master_update(update, source=f"open-index[{name}]")
        return {"acknowledged": True, "shards_acknowledged": True}

    def close_index_admin(self, name: str) -> Dict[str, Any]:
        result = self._call_master(ACTION_CLOSE_INDEX, {"name": name})
        self.wait_for_applied(
            lambda s: name in s.indices
            and s.indices[name].state == "close", timeout=10.0)
        return result

    def open_index_admin(self, name: str) -> Dict[str, Any]:
        result = self._call_master(ACTION_OPEN_INDEX, {"name": name})
        self.wait_for_applied(
            lambda s: name in s.indices
            and s.indices[name].state == "open"
            and all(s.primary(name, i) is not None
                    and s.primary(name, i).state == STARTED
                    for i in range(s.indices[name].number_of_shards)),
            timeout=15.0)
        return result

    def _handle_delete_index(self, payload, from_node) -> Dict[str, Any]:
        name = payload["name"]

        def update(state: ClusterState) -> ClusterState:
            if name not in state.indices:
                raise IndexNotFoundException(f"no such index [{name}]")
            new_indices = {k: v for k, v in state.indices.items()
                           if k != name}
            return state.with_updates(indices=new_indices)

        self._run_master_update(update, source=f"delete-index[{name}]")
        return {"acknowledged": True}

    def _handle_put_mapping(self, payload, from_node) -> Dict[str, Any]:
        name = payload["index"]
        mapping = payload.get("mapping") or {}

        def update(state: ClusterState) -> ClusterState:
            meta = state.indices.get(name)
            if meta is None:
                raise IndexNotFoundException(f"no such index [{name}]")
            import dataclasses
            merged = _merge_mapping(meta.mapping, mapping)
            new_meta = dataclasses.replace(meta, mapping=merged)
            new_indices = dict(state.indices)
            new_indices[name] = new_meta
            return state.with_updates(indices=new_indices)

        self._run_master_update(update, source=f"put-mapping[{name}]")
        return {"acknowledged": True}

    def _handle_update_index_settings(self, payload, from_node
                                      ) -> Dict[str, Any]:
        name = payload["index"]
        changes = Settings._flatten(payload.get("settings") or {})
        from elasticsearch_tpu.indices.service import IndexService
        IndexService.validate_dynamic_settings(changes)

        def update(state: ClusterState) -> ClusterState:
            meta = state.indices.get(name)
            if meta is None:
                raise IndexNotFoundException(f"no such index [{name}]")
            import dataclasses as _dc
            new_settings = dict(meta.settings)
            for k, v in changes.items():
                if v is None:
                    new_settings.pop(k, None)
                else:
                    new_settings[k] = v
            replicas = int(new_settings.get("index.number_of_replicas",
                                            meta.number_of_replicas))
            new_meta = _dc.replace(meta, settings=new_settings,
                                   number_of_replicas=replicas)
            new_indices = dict(state.indices)
            new_indices[name] = new_meta
            # replica-count changes re-place copies immediately
            return self.allocation.reroute(
                state.with_updates(indices=new_indices))

        self._run_master_update(update,
                                source=f"update-settings[{name}]")
        return {"acknowledged": True}

    def _handle_update_cluster_settings(self, payload, from_node
                                        ) -> Dict[str, Any]:
        persistent = Settings._flatten(payload.get("persistent") or {})
        transient = Settings._flatten(payload.get("transient") or {})
        for key in list(persistent) + list(transient):
            if key in DYNAMIC_CLUSTER_SETTINGS or any(
                    key.startswith(p) for p in DYNAMIC_CLUSTER_PREFIXES):
                continue
            raise IllegalArgumentException(
                f"setting [{key}] is not dynamically updateable")

        def update(state: ClusterState) -> ClusterState:
            def merged(base, changes):
                out = dict(base)
                for k, v in changes.items():
                    if v is None:
                        out.pop(k, None)
                    else:
                        out[k] = v
                return out
            return state.with_updates(
                persistent_settings=merged(state.persistent_settings,
                                           persistent),
                transient_settings=merged(state.transient_settings,
                                          transient))

        self._run_master_update(update, source="cluster-settings")
        state = self.coordinator.state()
        return {"acknowledged": True,
                "persistent": state.persistent_settings,
                "transient": state.transient_settings}

    def _handle_update_aliases(self, payload, from_node
                               ) -> Dict[str, Any]:
        from elasticsearch_tpu.indices.service import parse_alias_action
        parsed = [parse_alias_action(a)
                  for a in (payload.get("actions") or [])]

        def update(state: ClusterState) -> ClusterState:
            import dataclasses as _dc
            import fnmatch as _fn
            new_indices = dict(state.indices)
            for kind, idx_expr, alias, props in parsed:
                matched = ([n for n in new_indices
                            if _fn.fnmatchcase(n, idx_expr)]
                           if ("*" in idx_expr or "?" in idx_expr)
                           else [idx_expr])
                for name in matched:
                    meta = new_indices.get(name)
                    if meta is None:
                        raise IndexNotFoundException(
                            f"no such index [{name}]")
                    aliases = dict(meta.aliases)
                    if kind == "add":
                        if alias in new_indices:
                            raise IllegalArgumentException(
                                f"alias [{alias}] clashes with an "
                                f"index name")
                        aliases[alias] = dict(props)
                    else:  # remove
                        if alias not in aliases:
                            from elasticsearch_tpu.common.errors import \
                                ResourceNotFoundException
                            raise ResourceNotFoundException(
                                f"aliases [{alias}] missing on "
                                f"[{name}]")
                        del aliases[alias]
                    new_indices[name] = _dc.replace(meta,
                                                    aliases=aliases)
            return state.with_updates(indices=new_indices)

        self._run_master_update(update, source="update-aliases")
        return {"acknowledged": True}

    def update_aliases(self, actions: List[dict]) -> dict:
        from elasticsearch_tpu.indices.service import parse_alias_action
        parsed = [parse_alias_action(a) for a in actions]
        result = self._call_master(ACTION_UPDATE_ALIASES,
                                   {"actions": actions})

        def applied(state: ClusterState) -> bool:
            # semantic read-your-writes: each exact-name action is
            # observable in the applied metadata (wildcards pass — the
            # master already validated and committed them)
            view = self._StateView(state)
            for kind, idx_expr, alias, _props in parsed:
                if "*" in idx_expr or "?" in idx_expr:
                    continue
                targets = view.aliases.get(alias, {})
                if kind == "add" and idx_expr not in targets:
                    return False
                if kind == "remove" and idx_expr in targets:
                    return False
            return True

        self.wait_for_applied(applied, timeout=10.0)
        return result

    def _handle_put_template(self, payload, from_node) -> Dict[str, Any]:
        from elasticsearch_tpu.templates import validate_template
        name = payload["name"]
        validated = validate_template(name, payload["body"])

        def update(state: ClusterState) -> ClusterState:
            templates = dict(state.index_templates)
            templates[name] = validated
            return state.with_updates(index_templates=templates)

        self._run_master_update(update, source=f"put-template[{name}]")
        return {"acknowledged": True}

    def _handle_delete_template(self, payload, from_node
                                ) -> Dict[str, Any]:
        name = payload["name"]

        def update(state: ClusterState) -> ClusterState:
            if name not in state.index_templates:
                from elasticsearch_tpu.common.errors import \
                    ResourceNotFoundException
                raise ResourceNotFoundException(
                    f"index template matching [{name}] not found")
            templates = {k: v for k, v in state.index_templates.items()
                         if k != name}
            return state.with_updates(index_templates=templates)

        self._run_master_update(update,
                                source=f"delete-template[{name}]")
        return {"acknowledged": True}

    def put_template(self, name: str, body: dict) -> dict:
        from elasticsearch_tpu.templates import validate_template
        validated = validate_template(name, body)
        result = self._call_master(ACTION_PUT_TEMPLATE,
                                   {"name": name, "body": body})
        # value equality, not mere presence: an UPDATE must wait for the
        # new body to be the one visible locally
        self.wait_for_applied(
            lambda s: s.index_templates.get(name) == validated,
            timeout=10.0)
        return result

    def delete_template(self, name: str) -> dict:
        result = self._call_master(ACTION_DELETE_TEMPLATE,
                                   {"name": name})
        self.wait_for_applied(
            lambda s: name not in s.index_templates, timeout=10.0)
        return result

    def _handle_put_pipeline(self, payload, from_node) -> Dict[str, Any]:
        pipeline_id = payload["id"]
        body = payload["body"]
        from elasticsearch_tpu.ingest import Pipeline
        Pipeline(pipeline_id, body)  # validate before publishing

        def update(state: ClusterState) -> ClusterState:
            pipelines = dict(state.ingest_pipelines)
            pipelines[pipeline_id] = body
            return state.with_updates(ingest_pipelines=pipelines)

        self._run_master_update(update,
                                source=f"put-pipeline[{pipeline_id}]")
        return {"acknowledged": True}

    def _handle_delete_pipeline(self, payload, from_node
                                ) -> Dict[str, Any]:
        pipeline_id = payload["id"]

        def update(state: ClusterState) -> ClusterState:
            if pipeline_id not in state.ingest_pipelines:
                from elasticsearch_tpu.common.errors import \
                    ResourceNotFoundException
                raise ResourceNotFoundException(
                    f"pipeline [{pipeline_id}] does not exist")
            pipelines = {k: v for k, v in state.ingest_pipelines.items()
                         if k != pipeline_id}
            return state.with_updates(ingest_pipelines=pipelines)

        self._run_master_update(update,
                                source=f"delete-pipeline[{pipeline_id}]")
        return {"acknowledged": True}

    def put_pipeline(self, pipeline_id: str, body: dict) -> dict:
        result = self._call_master(ACTION_PUT_PIPELINE,
                                   {"id": pipeline_id, "body": body})
        # read-your-writes: wait until THIS node's applier installed it,
        # so an immediate GET / ?pipeline= use succeeds
        self.wait_for_applied(
            lambda s: s.ingest_pipelines.get(pipeline_id) == body,
            timeout=10.0)
        return result

    def delete_pipeline(self, pipeline_id: str) -> dict:
        result = self._call_master(ACTION_DELETE_PIPELINE,
                                   {"id": pipeline_id})
        self.wait_for_applied(
            lambda s: pipeline_id not in s.ingest_pipelines,
            timeout=10.0)
        return result

    def update_index_settings(self, name: str,
                              settings: Dict[str, Any]) -> Dict[str, Any]:
        return self._call_master(ACTION_UPDATE_INDEX_SETTINGS,
                                 {"index": name, "settings": settings})

    def update_cluster_settings(self, persistent: Dict[str, Any],
                                transient: Dict[str, Any]
                                ) -> Dict[str, Any]:
        return self._call_master(ACTION_UPDATE_CLUSTER_SETTINGS,
                                 {"persistent": persistent,
                                  "transient": transient})

    def _handle_shard_started(self, payload, from_node) -> Dict[str, Any]:
        index, shard = payload["index"], int(payload["shard"])
        aid = payload["allocation_id"]

        def update(state: ClusterState) -> ClusterState:
            return AllocationService.shard_started(state, index, shard, aid)

        # a started copy ends its failed-allocation streak (the bounded
        # max_retries counter guards crash-looping opens, not recoveries
        # that eventually succeed)
        self.allocation.reset_allocation_failures(index, shard)
        self._run_master_update(update,
                                source=f"shard-started[{index}][{shard}]")
        return {"acknowledged": True}

    # ------------------------------------------------------------------
    # admin routing (REST → master)
    # ------------------------------------------------------------------

    def create_index(self, name: str, settings: Dict[str, Any],
                     mapping: Optional[dict]) -> Dict[str, Any]:
        result = self._call_master(ACTION_CREATE_INDEX, {
            "name": name, "settings": settings, "mapping": mapping})
        # wait until this node has applied a state with started primaries
        self.wait_for_applied(
            lambda s: name in s.indices and all(
                s.primary(name, i) is not None
                and s.primary(name, i).state == STARTED
                for i in range(s.indices[name].number_of_shards)),
            timeout=15.0)
        return result

    def delete_index(self, name: str) -> Dict[str, Any]:
        result = self._call_master(ACTION_DELETE_INDEX, {"name": name})
        self.wait_for_applied(lambda s: name not in s.indices, timeout=10.0)
        return result

    def put_mapping(self, name: str, mapping: dict) -> Dict[str, Any]:
        return self._call_master(ACTION_PUT_MAPPING,
                                 {"index": name, "mapping": mapping})

    def _call_master(self, action: str, payload: Dict[str, Any],
                     timeout: float = 20.0) -> Dict[str, Any]:
        """Master-channel request with handoff tolerance: during an
        election window (no master yet / the old master just died) the
        request WAITS and retries instead of failing — the reference's
        MasterNodeRequest + cluster-state-observer retry."""
        from elasticsearch_tpu.cluster.coordination import (
            FailedToCommitException, NotMasterException)
        deadline = time.monotonic() + timeout
        last: Optional[Exception] = None
        while True:
            try:
                return self._call_master_once(action, payload, timeout)
            except (MasterNotDiscoveredException,
                    ConnectTransportException,
                    NotMasterException, FailedToCommitException) as e:
                # all of these mean the update was definitively NOT
                # applied (no master yet / connect failed before send /
                # the publication didn't commit) — safe to retry even
                # for non-idempotent actions
                last = e
            except (ConnectionError, OSError) as e:
                # AMBIGUOUS: the master may have committed before the
                # link died; a blind re-send of a non-idempotent action
                # (create/delete) would report the duplicate's error for
                # an operation that actually succeeded
                raise MasterNotDiscoveredException(
                    f"connection to the master failed mid-request for "
                    f"[{action}]; the update may or may not have been "
                    f"applied: {e}") from e
            except RemoteTransportException as e:
                if e.error_type not in ("NotMasterException",
                                        "FailedToCommitException"):
                    raise _rehydrate_error(e) from e
                last = e  # stale master view: wait for the new one
            if time.monotonic() >= deadline:
                raise MasterNotDiscoveredException(
                    f"master not discovered within {timeout}s "
                    f"for [{action}]: {last}")
            time.sleep(0.2)

    def _call_master_once(self, action: str, payload: Dict[str, Any],
                          timeout: float = 20.0) -> Dict[str, Any]:
        addr = self._master_address()
        if addr == self.local_node.address:
            handler = {ACTION_CREATE_INDEX: self._handle_create_index,
                       ACTION_DELETE_INDEX: self._handle_delete_index,
                       ACTION_CLOSE_INDEX: self._handle_close_index,
                       ACTION_OPEN_INDEX: self._handle_open_index,
                       ACTION_PUT_MAPPING: self._handle_put_mapping,
                       ACTION_UPDATE_INDEX_SETTINGS:
                           self._handle_update_index_settings,
                       ACTION_UPDATE_CLUSTER_SETTINGS:
                           self._handle_update_cluster_settings,
                       ACTION_PUT_PIPELINE: self._handle_put_pipeline,
                       ACTION_DELETE_PIPELINE:
                           self._handle_delete_pipeline,
                       ACTION_UPDATE_ALIASES:
                           self._handle_update_aliases,
                       ACTION_PUT_TEMPLATE: self._handle_put_template,
                       ACTION_DELETE_TEMPLATE:
                           self._handle_delete_template}[action]
            return handler(payload, self.local_node.to_json())
        # raw RemoteTransportException surfaces to _call_master, which
        # retries master-handoff errors and rehydrates the rest
        return self.transport.send_request(addr, action, payload,
                                           timeout=timeout)

    # ------------------------------------------------------------------
    # document routing (REST → shard owner)
    # ------------------------------------------------------------------

    def _ensure_index(self, index: str) -> IndexMeta:
        state = self.applied_state()
        meta = state.indices.get(index)
        if meta is not None:
            return meta
        if not self.node.settings.get_bool("action.auto_create_index", True):
            raise IndexNotFoundException(
                f"no such index [{index}] and auto-create is disabled")
        from elasticsearch_tpu.common.errors import \
            IndexAlreadyExistsException
        try:
            self.create_index(index, {}, None)
        except IndexAlreadyExistsException:
            pass
        state = self.wait_for_applied(lambda s: index in s.indices,
                                      timeout=15.0)
        if state is None:
            raise MasterNotDiscoveredException(
                f"timed out waiting for index [{index}] creation to apply")
        return state.indices[index]

    def _primary_node(self, index: str, shard: int
                      ) -> Tuple[ShardRouting, DiscoveryNode]:
        state = self.wait_for_applied(
            lambda s: (s.primary(index, shard) is not None
                       and s.primary(index, shard).state == STARTED
                       and s.primary(index, shard).node_id in s.nodes),
            timeout=10.0)
        if state is None:
            raise EsException(
                f"primary shard [{index}][{shard}] is not active")
        primary = state.primary(index, shard)
        return primary, state.nodes[primary.node_id]

    def route_doc_op(self, op: str, index: str, doc_id: Optional[str],
                     body, params: Dict[str, str]) -> Tuple[int, Dict]:
        from elasticsearch_tpu.indices.service import shard_for
        index = self.resolve_write_index(index)
        if op in ("index", "create", "update"):
            meta = self._ensure_index(index)
        else:
            # reads/deletes never auto-create (reference: only write ops
            # trigger action.auto_create_index)
            meta = self.applied_state().indices.get(index)
            if meta is None:
                raise IndexNotFoundException(f"no such index [{index}]")
        if doc_id is None:
            import uuid as uuid_mod
            doc_id = uuid_mod.uuid4().hex[:20]
        shard = shard_for(params.get("routing") or doc_id,
                          meta.number_of_shards)
        # retry loop: a dead primary is not a request failure — the
        # coordinating node waits for the routing table to fail over and
        # re-dispatches (reference: TransportReplicationAction's
        # cluster-state-observer retry)
        deadline = time.monotonic() + 30.0
        last_exc: Optional[Exception] = None
        while True:
            _primary, target = self._primary_node(index, shard)
            if target.node_id == self.local_node.node_id:
                return self._exec_doc_op(op, index, doc_id, body, params,
                                         shard)
            try:
                result = self.transport.send_request(
                    target.address, ACTION_DOC_OP,
                    {"op": op, "index": index, "id": doc_id, "body": body,
                     "params": params, "shard": shard})
                return result["status"], result["body"]
            except RemoteTransportException as e:
                if e.error_type != "ShardNotFoundException":
                    raise _rehydrate_error(e) from e
                last_exc = e  # routing raced a relocation — retry
            except ConnectTransportException as e:
                last_exc = e  # connect failed: nothing was sent — retry
            except (ConnectionError, OSError) as e:
                # AMBIGUOUS: the op may have applied before the link
                # died. index/update/delete re-dispatch is last-write-
                # wins with identical payload (at-least-once, reference
                # bulk retry semantics); a re-sent create could 409 a
                # write that actually succeeded, so surface the error
                if op == "create":
                    raise EsException(
                        f"connection to primary for [{index}][{shard}] "
                        f"failed mid-request; create not retried "
                        f"(result unknown): {e}") from e
                last_exc = e
            if time.monotonic() >= deadline:
                raise EsException(
                    f"primary for [{index}][{shard}] unreachable and no "
                    f"failover within timeout: {last_exc}")
            observed = target.node_id
            self.wait_for_applied(
                lambda s: (s.primary(index, shard) is None
                           or s.primary(index, shard).node_id != observed
                           or observed not in s.nodes),
                timeout=min(2.0, max(0.1, deadline - time.monotonic())))

    def _exec_doc_op(self, op: str, index: str, doc_id: str, body,
                     params: Dict[str, str], shard: int) -> Tuple[int, Dict]:
        from elasticsearch_tpu.rest.actions import document as doc_mod
        params = dict(params or {})
        if op in ("index", "create"):
            return doc_mod.exec_index_doc(self.node, index, doc_id, body,
                                          params, op_type=op,
                                          shard_num=shard)
        if op == "get":
            return doc_mod.exec_get_doc(self.node, index, doc_id, params,
                                        shard_num=shard)
        if op == "delete":
            return doc_mod.exec_delete_doc(self.node, index, doc_id, params,
                                           shard_num=shard)
        if op == "update":
            return doc_mod.exec_update_doc(self.node, index, doc_id, body,
                                           params, shard_num=shard)
        raise IllegalArgumentException(f"unknown doc op [{op}]")

    def _handle_doc_op(self, payload, from_node) -> Dict[str, Any]:
        status, body = self._exec_doc_op(
            payload["op"], payload["index"], payload["id"],
            payload.get("body"), payload.get("params") or {},
            int(payload["shard"]))
        return {"status": status, "body": body}

    # ------------------------------------------------------------------
    # bulk routing
    # ------------------------------------------------------------------

    def route_bulk(self, ops: List[Dict[str, Any]], *,
                   refresh: bool = False) -> List[Dict[str, Any]]:
        from elasticsearch_tpu.indices.service import shard_for
        from elasticsearch_tpu.rest.actions import document as doc_mod
        from elasticsearch_tpu.rest.controller import error_status

        # resolve each op's target node; group preserving positions.
        # Coordinating-stage admission happens HERE, per op, before any
        # dispatch: a rejected op becomes a per-item 429 without ever
        # leaving this node, its siblings still fan out (reference:
        # TransportBulkAction charges IndexingPressure per bulk op)
        groups: Dict[str, List[Tuple[int, Dict[str, Any]]]] = {}
        items: List[Optional[Dict[str, Any]]] = [None] * len(ops)
        addr_of: Dict[str, Tuple[str, int]] = {}
        alias_view = self._StateView(self.applied_state())
        pressure = getattr(self.node, "indexing_pressure", None)
        releases: List[Any] = []
        try:
            for pos, entry in enumerate(ops):
                try:
                    if pressure is not None:
                        releases.append(pressure.mark_coordinating(
                            operation_bytes(entry.get("source"))))
                    index = entry["index"]
                    if index is None:
                        raise IllegalArgumentException("_index is missing")
                    index = self.resolve_write_index(index, alias_view)
                    entry = dict(entry, index=index)
                    meta = self._ensure_index(index)
                    shard = shard_for(entry.get("routing") or entry["id"],
                                      meta.number_of_shards)
                    _primary, target = self._primary_node(index, shard)
                    entry = dict(entry, shard=shard)
                    groups.setdefault(target.node_id, []).append(
                        (pos, entry))
                    addr_of[target.node_id] = target.address
                except EsException as exc:
                    items[pos] = {entry["op"]: {
                        "_index": entry.get("index"),
                        "_id": entry.get("id"),
                        "status": error_status(exc),
                        "error": {"type": type(exc).__name__,
                                  "reason": str(exc)}}}

            # dispatch every remote group first so their work overlaps the
            # local apply, then run the local group in this thread
            futures: List[Tuple[List[int], Future]] = []
            local_group: Optional[List[Tuple[int, Dict[str, Any]]]] = None
            for node_id, group in groups.items():
                if node_id == self.local_node.node_id:
                    local_group = group
                    continue
                positions = [pos for pos, _ in group]
                sub_ops = [entry for _, entry in group]
                fut = self.transport.send_request_async(
                    addr_of[node_id], ACTION_BULK,
                    {"ops": sub_ops, "refresh": refresh})
                futures.append((positions, fut))
            if local_group is not None:
                positions = [pos for pos, _ in local_group]
                sub_ops = [entry for _, entry in local_group]
                fut = Future()
                try:
                    # this node's coordinating admission covers the local
                    # primary work: accounted as primary, not re-checked
                    fut.set_result({"items": doc_mod.apply_bulk_ops(
                        self.node, sub_ops, refresh=refresh,
                        pressure_stage="primary_local")})
                except Exception as e:  # noqa: BLE001
                    fut.set_exception(e)
                futures.append((positions, fut))

            for positions, fut in futures:
                try:
                    sub_items = fut.result(timeout=60.0)["items"]
                    for pos, item in zip(positions, sub_items):
                        items[pos] = item
                except Exception as exc:  # noqa: BLE001 — node failure
                    for pos in positions:
                        op = ops[pos]["op"]
                        items[pos] = {op: {
                            "_index": ops[pos].get("index"),
                            "_id": ops[pos].get("id"), "status": 503,
                            "error": {
                                "type": "unavailable_shards_exception",
                                "reason": str(exc)}}}
            return [it for it in items if it is not None]
        finally:
            for release in releases:
                release()

    def _handle_bulk_group(self, payload, from_node) -> Dict[str, Any]:
        from elasticsearch_tpu.rest.actions import document as doc_mod
        # a remote coordinating node admitted these ops against ITS
        # budget; this node re-checks them against its own primary budget
        return {"items": doc_mod.apply_bulk_ops(
            self.node, payload["ops"], refresh=bool(payload.get("refresh")),
            pressure_stage="primary")}

    # ------------------------------------------------------------------
    # search routing (query_then_fetch across nodes)
    # ------------------------------------------------------------------

    class _StateView:
        """Duck-typed shim so coordinator.resolve_targets works over the
        CLUSTER metadata exactly as it does over a local registry."""

        def __init__(self, state: ClusterState):
            self.indices = state.indices
            self.aliases: Dict[str, Dict[str, Dict[str, Any]]] = {}
            for name, meta in state.indices.items():
                for alias, props in (meta.aliases or {}).items():
                    self.aliases.setdefault(alias, {})[name] = props

    def resolve_targets(self, expression: Optional[str]
                        ) -> Tuple[List[str], Dict[str, List[dict]]]:
        from elasticsearch_tpu.search.coordinator import resolve_targets
        return resolve_targets(self._StateView(self.applied_state()),
                               expression)

    def resolve_indices(self, expression: Optional[str]) -> List[str]:
        return self.resolve_targets(expression)[0]

    def resolve_write_index(self, name: str, view=None) -> str:
        """Pass a prebuilt _StateView on hot loops (bulk) so the alias
        inversion is built once per request, not per op."""
        from elasticsearch_tpu.indices.service import select_write_index
        if view is None:
            view = self._StateView(self.applied_state())
        entry = view.aliases.get(name)
        if entry is None:
            return name
        return select_write_index(entry, name)

    def record_node_latency(self, node_id: str, seconds: float) -> None:
        """Feed the ARS EWMA (alpha 0.3, the reference's
        ExponentiallyWeightedMovingAverage default for response times)."""
        with self._ars_lock:
            old = self._node_ewma.get(node_id)
            self._node_ewma[node_id] = (seconds if old is None
                                        else 0.7 * old + 0.3 * seconds)

    def _route_shards(self, names: List[str]
                      ) -> Tuple[Dict[str, List[Tuple[str, int]]],
                                 Dict[str, Tuple[str, int]],
                                 List[Tuple[str, int]],
                                 Dict[Tuple[str, int], List[str]]]:
        """→ (node_id → [(index, shard)], node_id → address,
        unassigned [(index, shard)] with no live copy,
        (index, shard) → ARS-ranked node_ids of EVERY live copy).
        Any STARTED copy may serve a read — replicas included — ranked
        by the node-latency EWMA (ARS-lite: OperationRouting#
        searchShards + ResponseCollectorService, SURVEY.md §2.1#19);
        copies on unmeasured nodes rotate round-robin so load spreads
        until measurements exist. The full ranked list backs per-shard
        failover: a failed copy retries on the next-ranked one."""
        state = self.applied_state()
        by_node: Dict[str, List[Tuple[str, int]]] = {}
        addr: Dict[str, Tuple[str, int]] = {}
        unassigned: List[Tuple[str, int]] = []
        ranked_copies: Dict[Tuple[str, int], List[str]] = {}
        with self._ars_lock:
            ewma = dict(self._node_ewma)
            self._ars_rr += 1
            rr = self._ars_rr
        for name in names:
            meta = state.indices.get(name)
            if meta is None:
                raise IndexNotFoundException(f"no such index [{name}]")
            for shard in range(meta.number_of_shards):
                copies = [c for c in state.shard_copies(name, shard)
                          if c.state == STARTED and c.node_id in state.nodes]
                if not copies:
                    unassigned.append((name, shard))
                    continue
                def ars_rank(ic):
                    i, c = ic
                    e = ewma.get(c.node_id)
                    # 10ms latency buckets: similar nodes rotate (no
                    # herding onto one fast node); unmeasured nodes rank
                    # first so they get measured
                    bucket = -1 if e is None else int(e * 100)
                    return (bucket, (i + rr) % len(copies))

                order = sorted(enumerate(copies), key=ars_rank)
                ranked = []
                for _i, c in order:
                    if c.node_id not in ranked:
                        ranked.append(c.node_id)
                    addr[c.node_id] = state.nodes[c.node_id].address
                ranked_copies[(name, shard)] = ranked
                by_node.setdefault(ranked[0], []).append((name, shard))
        return by_node, addr, unassigned, ranked_copies

    #: failover fan-out retry budget: a dead peer burns at most this
    #: many seconds of backoff before its shards move to another copy
    FANOUT_RETRY = RetryPolicy(initial_delay=0.05, max_delay=0.5,
                               deadline=2.0)

    def _run_shard_group(self, node_id: str, addr: Dict[str, Tuple[str, int]],
                         targets: List[Tuple[str, int]],
                         body, params, alias_filters,
                         retry: bool = False) -> Dict[str, Any]:
        """Execute one query group on `node_id` — inline for the local
        node, over transport otherwise (with bounded backoff retries on
        connection faults when `retry` is set)."""
        from elasticsearch_tpu.search import coordinator as coord
        if node_id == self.local_node.node_id:
            l0 = time.perf_counter()
            out = coord.search_shard_group(
                self.node.indices, targets, body, params,
                tpu_search=self.node.tpu_search,
                index_filters=alias_filters)
            self.record_node_latency(node_id, time.perf_counter() - l0)
            return out
        payload = {"targets": targets, "body": body, "params": params,
                   "index_filters": alias_filters}
        r0 = time.perf_counter()
        with tracing.child_span("transport.fanout", node=node_id,
                                shards=len(targets), retry=retry) as span:
            tracing.inject_context(payload, span)
            if retry:
                out = send_with_retry(self.transport, addr[node_id],
                                      ACTION_QUERY_GROUP, payload,
                                      policy=self.FANOUT_RETRY)
            else:
                out = self.transport.send_request(
                    addr[node_id], ACTION_QUERY_GROUP, payload,
                    timeout=60.0)
        self.record_node_latency(node_id, time.perf_counter() - r0)
        return out

    def route_search(self, index_expr: Optional[str],
                     body: Optional[Dict[str, Any]],
                     params: Optional[Dict[str, str]] = None,
                     task=None) -> Dict[str, Any]:
        from elasticsearch_tpu.search import coordinator as coord
        t0 = time.perf_counter()
        names, alias_filters = self.resolve_targets(index_expr)
        # validates the body once on the coordinating node (400 before
        # any fan-out, reference behavior)
        coord.parse_search_body(body or {})
        by_node, addr, unassigned, ranked_copies = self._route_shards(names)
        failures: List[Dict[str, Any]] = [
            shard_failure_entry(n, s, NoShardAvailableActionException(
                f"no active shard copy for [{n}][{s}]"))
            for n, s in unassigned]
        for n, s in unassigned:  # terminal by definition: no copy exists
            self.node.indices.count_search_failure(n, s)
        knn_failed = 0
        if body and body.get("knn") is not None:
            body, knn_failed = self._resolve_knn_phase(
                body, by_node, addr, alias_filters)

        futures: List[Tuple[str, Any]] = []
        local_targets: Optional[List[Tuple[str, int]]] = None
        # one fanout child span per remote node, spanning dispatch →
        # gather; the trace context rides in the payload so the remote
        # handler continues the same trace
        root_span = tracing.current_span()
        fanout_spans: Dict[str, Any] = {}
        for node_id, targets in sorted(by_node.items()):
            if node_id == self.local_node.node_id:
                local_targets = targets
                continue
            payload = {"targets": targets, "body": body, "params": params,
                       "index_filters": alias_filters}
            if root_span is not None:
                span = root_span.tracer.start_span(
                    "transport.fanout", parent=root_span,
                    attributes={"node": node_id, "shards": len(targets)})
                tracing.inject_context(payload, span)
                fanout_spans[node_id] = span
            fut = self.transport.send_request_async(
                addr[node_id], ACTION_QUERY_GROUP, payload)
            futures.append((node_id, fut))

        # gather; a failed copy — whole group OR single shard inside a
        # group response — goes to the failover queue instead of
        # counting failed outright (reference:
        # AbstractSearchAsyncAction#performPhaseOnShard retries the
        # next copy from the shard iterator)
        groups: List[Dict[str, Any]] = []
        retry_q: Dict[Tuple[str, int], Dict[str, Any]] = {}  # → failure
        tried: Dict[Tuple[str, int], Set[str]] = {}          # → node_ids

        def absorb(group: Dict[str, Any], node_id: str) -> None:
            """Keep a group's surviving partial result; its per-shard
            failures queue for failover on another copy."""
            for f in group.pop("failures", []):
                key = (f["index"], int(f["shard"]))
                tried.setdefault(key, set()).add(node_id)
                retry_q[key] = dict(f, node=node_id)
            groups.append(group)

        def group_failed(node_id: str, targets, exc: Exception) -> None:
            # a failed/slow node ranks last until it recovers; a dead
            # pooled connection must not poison the retry
            self.record_node_latency(node_id, 60.0)
            if is_retryable(exc):
                self.transport.evict(addr[node_id])
            for name, shard in targets:
                key = (name, int(shard))
                tried.setdefault(key, set()).add(node_id)
                retry_q[key] = shard_failure_entry(
                    name, int(shard), exc, node=node_id)

        if local_targets is not None:
            absorb(self._run_shard_group(
                self.local_node.node_id, addr, local_targets, body,
                params, alias_filters), self.local_node.node_id)
        for node_id, fut in futures:
            if task is not None:
                task.ensure_not_cancelled()
            r0 = time.perf_counter()
            span = fanout_spans.pop(node_id, None)
            try:
                absorb(fut.result(timeout=60.0), node_id)
                self.record_node_latency(node_id,
                                         time.perf_counter() - r0)
            except Exception as exc:  # noqa: BLE001 — shard-group failure
                logger.warning("search group on [%s] failed: %s",
                               node_id, exc)
                if span is not None:
                    span.set_attribute("error",
                                       f"{type(exc).__name__}: {exc}")
                group_failed(node_id, by_node.get(node_id, []), exc)
            finally:
                if span is not None:
                    span.end()

        # failover rounds: each still-failed shard moves to its best
        # untried copy until copies run out (tried sets grow every
        # round, so this terminates)
        while retry_q:
            if task is not None:
                task.ensure_not_cancelled()
            round_nodes: Dict[str, List[Tuple[str, int]]] = {}
            for key, entry in list(retry_q.items()):
                cands = [nid for nid in ranked_copies.get(key, [])
                         if nid not in tried.get(key, set())]
                if not cands:
                    # TERMINAL: every copy tried and failed — this is
                    # the failure the response reports, so it's the one
                    # the per-shard counter records
                    self.node.indices.count_search_failure(key[0], key[1])
                    tracing.add_event("shard.failed", index=key[0],
                                      shard=key[1],
                                      reason=entry.get("reason", {}))
                    failures.append(entry)
                    del retry_q[key]
                    continue
                # local copy first (no network), then ARS rank
                nid = (self.local_node.node_id
                       if self.local_node.node_id in cands else cands[0])
                tried.setdefault(key, set()).add(nid)
                round_nodes.setdefault(nid, []).append(key)
            for node_id, targets in sorted(round_nodes.items()):
                try:
                    group = self._run_shard_group(
                        node_id, addr, targets, body, params,
                        alias_filters, retry=True)
                except Exception as exc:  # noqa: BLE001 — next copy
                    group_failed(node_id, targets, exc)
                    continue
                for key in targets:
                    retry_q.pop(key, None)
                absorb(group, node_id)
                events.emit("shard.failover", severity="warning",
                            node=node_id, shards=len(targets))
                logger.info("failover: %d shard(s) retried on [%s]",
                            len(targets), node_id)

        check = getattr(coord, "check_shard_failures", None)
        if check is not None:
            successful = sum(g.get("shards", 0) for g in groups)
            check(failures, successful,
                  coord.allow_partial_results(params))
        # off-interpreter merge: when the dispatch opted in (serving
        # front or node merge pool owns the reduce) and the body is
        # defer-eligible, hand back the columnar descriptor instead of
        # merging on this interpreter — the batcher's steady-state work
        # ends at the columns handoff
        from elasticsearch_tpu.search import merge as merge_mod
        if merge_mod.defer_active() and merge_mod.can_defer(body):
            return merge_mod.DeferredMerge(merge_mod.build_descriptor(
                groups, body, params, t0, failed_shards=knn_failed,
                failures=failures))
        return coord.merge_group_responses(groups, body, params, t0,
                                           failed_shards=knn_failed,
                                           failures=failures)

    def _handle_remote_search(self, payload, from_node) -> Dict[str, Any]:
        """CCS target side (reference: the remote half of
        TransportSearchAction's cross-cluster fan-out)."""
        from elasticsearch_tpu import ccs
        return ccs.handle_remote_search(self.node, payload, from_node)

    def _resolve_knn_phase(self, body, by_node, addr, alias_filters
                           ) -> Tuple[Dict[str, Any], int]:
        """Cluster-level knn candidate phase (reference: the knn half
        of DfsQueryPhase): fan ACTION_KNN_GROUP to every shard group,
        reduce to the GLOBAL top k per clause, ship the winners in the
        `_knn_docs` body key. NOTE: candidates and the query phase
        acquire separate readers; a refresh between the two phases can
        drop a winner (same read-consistency window as the reference's
        two-phase search without PIT)."""
        from elasticsearch_tpu.search import coordinator as coord
        from elasticsearch_tpu.search import knn as knn_mod
        specs = knn_mod.parse_knn(body["knn"])
        payload_body = {"knn": body["knn"],
                        "index_filters": alias_filters}
        futures = []
        results = []
        failed = 0
        local_targets = None
        for node_id, targets in sorted(by_node.items()):
            if node_id == self.local_node.node_id:
                local_targets = targets
                continue
            fut = self.transport.send_request_async(
                addr[node_id], ACTION_KNN_GROUP,
                {"targets": targets, **payload_body})
            futures.append((node_id, fut))
        if local_targets is not None:
            # local matmuls AFTER the async sends: overlap with remote RTT
            results.append(self._knn_group_local(
                local_targets, specs, alias_filters))
        for node_id, fut in futures:
            try:
                results.append(fut.result(timeout=60.0))
            except Exception as exc:  # noqa: BLE001
                failed += len(by_node.get(node_id, []))
                logger.warning("knn candidates on [%s] failed: %s",
                               node_id, exc)
        # reduce: per clause, merge every shard's candidates → global k
        knn_wrap: Dict[Tuple[str, int], list] = {}
        for ci, spec in enumerate(specs):
            per_shard = {}
            for group in results:
                for key, clause_lists in group.items():
                    name, _, shard_s = key.rpartition("#")
                    cands = [(float(s), seg, int(o), d)
                             for s, seg, o, d in clause_lists[ci]]
                    per_shard[(name, int(shard_s))] = cands
            grouped = knn_mod.global_topk(per_shard, spec.k)
            for shard_key, seg_map in grouped.items():
                knn_wrap.setdefault(shard_key, []).append(
                    (seg_map, spec.boost))
        out_body = {k: v for k, v in body.items() if k != "knn"}
        out_body["_knn_docs"] = coord.encode_knn_docs(knn_wrap)
        return out_body, failed

    def _knn_group_local(self, targets, specs, alias_filters
                         ) -> Dict[str, Any]:
        """Run the candidate phase over local shards → {"index#shard":
        [per-clause [(score, seg, ord, doc_id), ...]]}."""
        from elasticsearch_tpu.search import knn as knn_mod
        from elasticsearch_tpu.search.coordinator import \
            with_alias_filters
        from elasticsearch_tpu.search import dsl
        import dataclasses as _dc
        out: Dict[str, Any] = {}
        for name, shard_num in targets:
            svc = self.node.indices.index(name)
            reader = svc.shard(int(shard_num)).acquire_searcher()
            clause_lists = []
            for spec in specs:
                eff = spec
                afilts = (alias_filters or {}).get(name)
                if afilts:
                    base = spec.filter_query or dsl.MatchAllQuery()
                    eff = _dc.replace(spec, filter_query=
                                      with_alias_filters(base, afilts))
                cands = knn_mod.shard_candidates(reader, eff)
                clause_lists.append(
                    [[s, seg, o, d] for s, seg, o, d in cands])
            out[f"{name}#{int(shard_num)}"] = clause_lists
        return out

    def _handle_knn_group(self, payload, from_node) -> Dict[str, Any]:
        from elasticsearch_tpu.search import knn as knn_mod
        specs = knn_mod.parse_knn(payload["knn"])
        targets = [(t[0], int(t[1])) for t in payload["targets"]]
        return self._knn_group_local(targets, specs,
                                     payload.get("index_filters"))

    def _handle_query_group(self, payload, from_node) -> Dict[str, Any]:
        from elasticsearch_tpu.search import coordinator as coord
        targets = [(t[0], int(t[1])) for t in payload["targets"]]
        # continue the coordinating node's trace on this shard node: the
        # payload carries the fanout span's context, so the per-shard
        # query + TPU stage spans recorded here share its trace id
        ctx = tracing.extract_context(payload)
        span = self.node.tracer.start_span(
            "shard_group", parent=ctx,
            attributes={"from": (from_node or {}).get("name"),
                        "shards": len(targets)})
        with span, tracing.use_span(span):
            return coord.search_shard_group(
                self.node.indices, targets, payload.get("body"),
                payload.get("params"),
                tpu_search=self.node.tpu_search,
                index_filters=payload.get("index_filters"))

    def route_count(self, index_expr: Optional[str],
                    body: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        from elasticsearch_tpu.search import dsl
        names, alias_filters = self.resolve_targets(index_expr)
        dsl.parse_query((body or {}).get("query") or {"match_all": {}})
        by_node, addr, unassigned, _copies = self._route_shards(names)
        failed = len(unassigned)
        total = 0
        ok_shards = 0
        futures = []
        local_targets = None
        for node_id, targets in sorted(by_node.items()):
            if node_id == self.local_node.node_id:
                local_targets = targets
                continue
            futures.append((len(targets), self.transport.send_request_async(
                addr[node_id], ACTION_COUNT_GROUP,
                {"targets": targets, "body": body,
                 "index_filters": alias_filters})))
        if local_targets is not None:
            res = self._handle_count_group(
                {"targets": local_targets, "body": body,
                 "index_filters": alias_filters},
                self.local_node.to_json())
            total += res["count"]
            ok_shards += res["shards"]
        for n_targets, fut in futures:
            try:
                res = fut.result(timeout=60.0)
                total += res["count"]
                ok_shards += res["shards"]
            except Exception as exc:  # noqa: BLE001 — partial results
                failed += n_targets
                logger.warning("count group failed: %s", exc)
        return {"count": total,
                "_shards": {"total": ok_shards + failed,
                            "successful": ok_shards, "skipped": 0,
                            "failed": failed}}

    def _handle_count_group(self, payload, from_node) -> Dict[str, Any]:
        from elasticsearch_tpu.search import dsl
        from elasticsearch_tpu.search.coordinator import \
            with_alias_filters
        from elasticsearch_tpu.search.query_phase import execute_query
        query = dsl.parse_query(
            (payload.get("body") or {}).get("query") or {"match_all": {}})
        index_filters = payload.get("index_filters") or {}
        total = 0
        n = 0
        for name, shard_num in [(t[0], int(t[1]))
                                for t in payload["targets"]]:
            shard = self.node.indices.index(name).shard(shard_num)
            eff = with_alias_filters(query, index_filters.get(name))
            res = execute_query(shard.acquire_searcher(), eff, size=0)
            total += res.total_hits
            n += 1
        return {"count": total, "shards": n}

    # ------------------------------------------------------------------
    # peer recovery (reference: RecoverySourceHandler#recoverToTarget /
    # PeerRecoveryTargetService, SURVEY.md §2.1#34, §3.5: phase 1 file
    # sync by manifest diff, phase 2 translog-tail replay)
    # ------------------------------------------------------------------

    def _start_replica_recovery(self, index: str, shard_num: int,
                                copy: ShardRouting,
                                state: ClusterState) -> None:
        key = (index, shard_num)
        with self._recovering_lock:
            if key in self._recovering:
                return
            self._recovering.add(key)
        threading.Thread(
            target=self._recover_replica,
            args=(index, shard_num, copy),
            daemon=True,
            name=f"recovery-{index}-{shard_num}").start()

    def _recover_replica(self, index: str, shard_num: int,
                         copy: ShardRouting) -> None:
        key = (index, shard_num)
        try:
            primary_state = self.wait_for_applied(
                lambda s: (s.primary(index, shard_num) is not None
                           and s.primary(index, shard_num).state == STARTED
                           and s.primary(index, shard_num).node_id
                           in s.nodes),
                timeout=30.0)
            if primary_state is None:
                return  # no live primary; a later reroute retries
            primary = primary_state.primary(index, shard_num)
            src = primary_state.nodes[primary.node_id].address
            svc = self.node.indices.index(index)
            shard_path = os.path.join(svc.data_path, str(shard_num))
            os.makedirs(shard_path, exist_ok=True)

            # ---- phase 1: file sync (manifest diff by size+sha256) ----
            # a remote ShardNotFound here is transient (the primary node
            # may not have reconciled its shard object yet, e.g. at
            # whole-cluster restart) — wait and retry, don't fail the copy
            start = None
            start_deadline = time.monotonic() + 30.0
            while True:
                try:
                    start = self.transport.send_request(
                        src, ACTION_RECOVERY_START,
                        {"index": index, "shard": shard_num,
                         "allocation_id": copy.allocation_id,
                         "target_node": self.local_node.to_json()},
                        timeout=60.0)
                    break
                except RemoteTransportException as e:
                    if (e.error_type != "ShardNotFoundException"
                            or time.monotonic() >= start_deadline):
                        raise
                    time.sleep(0.5)
            import hashlib
            for rel, info in start["files"].items():
                dst = os.path.join(shard_path, rel)
                if os.path.exists(dst):
                    with open(dst, "rb") as f:
                        local = f.read()
                    if (len(local) == info["size"]
                            and hashlib.sha256(local).hexdigest()
                            == info["sha256"]):
                        continue
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                # binary chunk frames (raw bytes — no base64 inflation),
                # streamed with a bounded window of concurrent requests
                # (reference: MultiChunkTransfer's maxConcurrentChunks;
                # VERDICT r3 weak #5)
                n_chunks = max(1, -(-info["size"] // _RECOVERY_CHUNK))
                chunks: List[Optional[bytes]] = [None] * n_chunks
                window = 4
                futs = {}
                nxt = 0
                while nxt < n_chunks or futs:
                    while nxt < n_chunks and len(futs) < window:
                        futs[nxt] = self.transport.send_request_async(
                            src, ACTION_RECOVERY_FILE,
                            {"index": index, "shard": shard_num,
                             "path": rel,
                             "offset": nxt * _RECOVERY_CHUNK,
                             "length": _RECOVERY_CHUNK})
                        nxt += 1
                    ci = next(iter(futs))
                    part = futs.pop(ci).result(timeout=60.0)
                    chunks[ci] = part.get("_blob", b"")
                blob = b"".join(c for c in chunks if c)
                if hashlib.sha256(blob).hexdigest() != info["sha256"]:
                    raise IOError(f"recovery checksum mismatch on {rel}")
                write_atomic(dst, blob)
            # the commit manifest goes last: the engine opens from it,
            # so it must only ever reference files already on disk
            write_atomic(os.path.join(shard_path, "commit.json"),
                         base64.b64decode(start["commit"]))

            # ---- open the engine from the synced store ----
            shard = self.node.indices.index(index).shards.get(shard_num)
            if shard is not None:
                shard.close()
                self.node.indices.index(index).shards.pop(shard_num, None)
            shard = svc.create_shard(shard_num, primary=False,
                                     allocation_id=copy.allocation_id)

            # ---- phase 2: translog-tail replay until caught up ----
            # live replica ops are already flowing (the source registered
            # this target for fan-out at RECOVERY_START) and the engine's
            # per-doc seqno check makes duplicate/stale delivery a no-op.
            # The copy may ONLY report started once a replay round comes
            # back empty — an incomplete copy in the in-sync set would
            # lose acked writes on promotion.
            converged = False
            for _round in range(100):
                from_seq = shard.local_checkpoint + 1
                ops = self.transport.send_request(
                    src, ACTION_RECOVERY_OPS,
                    {"index": index, "shard": shard_num,
                     "from_seq_no": from_seq}, timeout=60.0)["ops"]
                for op in ops:
                    self._apply_replica_op_dict(shard, op)
                if not ops:
                    converged = True
                    break
                if shard.local_checkpoint + 1 == from_seq:
                    raise IOError(
                        f"replay made no progress at seq {from_seq}")
            if not converged:
                raise IOError("translog replay did not converge")

            self._write_shard_state(svc, shard_num, copy.allocation_id,
                                    primary=False)
            self._started_sent.add(copy.allocation_id)
            self._send_to_master(ACTION_SHARD_STARTED, {
                "index": index, "shard": shard_num,
                "allocation_id": copy.allocation_id})
            events.emit("replica.recovered", index=index,
                        shard=shard_num, source=primary.node_id,
                        node=self.local_node.name)
            logger.info("[%s] recovered replica %s[%d] from %s",
                        self.local_node.name, index, shard_num,
                        primary.node_id)
            # NOTE: the source's fan-out registration stays live until it
            # sees this copy STARTED in a committed state (pruned in
            # _reconcile) — releasing it now would open a window where
            # writes land between the last replay round and the routing
            # update without reaching this copy.
        except Exception:  # noqa: BLE001 — recovery retries via reroute
            logger.exception("[%s] replica recovery %s[%d] failed",
                             self.local_node.name, index, shard_num)
            self._send_to_master(ACTION_SHARD_FAILED, {
                "index": index, "shard": shard_num,
                "allocation_id": copy.allocation_id})
            # tell the source to drop its retention lock + registration
            try:
                primary_state = self.applied_state()
                primary = primary_state.primary(index, shard_num)
                if primary is not None and primary.node_id \
                        in primary_state.nodes:
                    self.transport.send_request_async(
                        primary_state.nodes[primary.node_id].address,
                        ACTION_RECOVERY_FINISH,
                        {"index": index, "shard": shard_num,
                         "allocation_id": copy.allocation_id})
            except Exception:  # noqa: BLE001 — TTL expiry is the backstop
                pass
        finally:
            with self._recovering_lock:
                self._recovering.discard(key)

    @staticmethod
    def _apply_replica_op_dict(shard, op: Dict[str, Any]) -> None:
        kind = op.get("kind", "index")
        if kind == "index":
            shard.apply_index_on_replica(
                op["id"], op.get("source") or {}, seq_no=int(op["seq_no"]),
                primary_term=int(op["primary_term"]),
                version=int(op.get("version") or 1))
        elif kind == "delete":
            shard.apply_delete_on_replica(
                op["id"], seq_no=int(op["seq_no"]),
                primary_term=int(op["primary_term"]))
        # no_op entries only advance checkpoints
        elif kind == "no_op":
            shard.engine.no_op(int(op["seq_no"]), int(op["primary_term"]),
                               op.get("reason") or "replay")

    # ---- source side ----

    def _local_shard(self, index: str, shard_num: int):
        from elasticsearch_tpu.common.errors import ShardNotFoundException
        svc = self.node.indices.index(index)
        shard = svc.shards.get(shard_num)
        if shard is None:
            raise ShardNotFoundException(
                f"shard [{index}][{shard_num}] not on this node")
        return svc, shard

    def _handle_recovery_start(self, payload, from_node) -> Dict[str, Any]:
        import hashlib
        index, shard_num = payload["index"], int(payload["shard"])
        svc, shard = self._local_shard(index, shard_num)
        # register the target BEFORE the flush: from here on (a) live
        # writes fan out to it and (b) its translog ops are pinned
        # against trim, so no op can fall between file copy and replay
        aid = payload.get("allocation_id", "")
        target = payload.get("target_node")
        if aid and target:
            release = shard.engine.translog.acquire_retention_lock()
            with self._recovery_sources_lock:
                old = self._recovery_sources.pop((index, shard_num, aid),
                                                 None)
                self._recovery_sources[(index, shard_num, aid)] = {
                    "release": release,
                    "address": tuple(DiscoveryNode.from_json(target)
                                     .address),
                    "expires": time.monotonic() + 600.0}
            if old is not None:
                old["release"]()
        shard.flush()  # commit the current state; ops after this stay in
        # the translog and are shipped in phase 2
        shard_path = os.path.join(svc.data_path, str(shard_num))
        commit_path = os.path.join(shard_path, "commit.json")
        with open(commit_path, "rb") as f:
            commit_bytes = f.read()
        commit = json.loads(commit_bytes.decode("utf-8"))
        files: Dict[str, Dict[str, Any]] = {}
        seg_dir = os.path.join(shard_path, "segments")
        for seg_name in commit.get("segments", []):
            for ext in (".npz", ".json"):
                rel = os.path.join("segments", seg_name + ext)
                p = os.path.join(shard_path, rel)
                if os.path.exists(p):
                    with open(p, "rb") as f:
                        blob = f.read()
                    files[rel] = {
                        "size": len(blob),
                        "sha256": hashlib.sha256(blob).hexdigest()}
        return {"files": files,
                "commit": base64.b64encode(commit_bytes).decode("ascii"),
                "max_seq_no": commit.get("max_seq_no", -1)}

    def _handle_recovery_file(self, payload, from_node) -> Dict[str, Any]:
        index, shard_num = payload["index"], int(payload["shard"])
        svc, _shard = self._local_shard(index, shard_num)
        rel = payload["path"]
        if os.path.isabs(rel) or ".." in rel.split(os.sep):
            raise IllegalArgumentException(f"illegal recovery path [{rel}]")
        p = os.path.join(svc.data_path, str(shard_num), rel)
        with open(p, "rb") as f:
            f.seek(int(payload["offset"]))
            data = f.read(int(payload["length"]))
        # raw bytes ride a binary frame (transport kind 1), not base64
        return {"_blob": data}

    def _handle_recovery_finish(self, payload, from_node) -> Dict[str, Any]:
        key = (payload["index"], int(payload["shard"]),
               payload.get("allocation_id", ""))
        with self._recovery_sources_lock:
            entry = self._recovery_sources.pop(key, None)
        if entry is not None:
            entry["release"]()
        return {"acknowledged": True}

    def _expire_recovery_sources(self) -> None:
        """Drop abandoned source registrations (target died mid-recovery
        and never sent finish) so retention locks can't leak forever."""
        now = time.monotonic()
        expired = []
        with self._recovery_sources_lock:
            for key, entry in list(self._recovery_sources.items()):
                if entry["expires"] < now:
                    expired.append(self._recovery_sources.pop(key))
        for entry in expired:
            entry["release"]()

    def _handle_recovery_ops(self, payload, from_node) -> Dict[str, Any]:
        index, shard_num = payload["index"], int(payload["shard"])
        _svc, shard = self._local_shard(index, shard_num)
        from_seq = int(payload["from_seq_no"])
        ops = []
        for op in shard.engine.translog.snapshot(from_seq_no=from_seq):
            ops.append({"kind": op.op_type, "seq_no": op.seq_no,
                        "primary_term": op.primary_term, "id": op.doc_id,
                        "source": op.source, "version": op.version,
                        "reason": op.reason})
            if len(ops) >= 5000:
                break
        return {"ops": ops}

    # ------------------------------------------------------------------
    # maintenance broadcast (refresh/flush/forcemerge across nodes)
    # ------------------------------------------------------------------

    def broadcast_maintenance(self, op: str, index_expr: Optional[str]
                              ) -> Dict[str, Any]:
        """Reference: the broadcast-by-shard TransportBroadcastAction
        shape (RestRefreshAction et al) collapsed to one hop per node."""
        names = self.resolve_indices(index_expr)
        state = self.applied_state()
        # every node holding any copy of any target index
        node_ids: Set[str] = set()
        n_shards = 0
        for name in names:
            for shards in state.routing.get(name, {}).values():
                for c in shards:
                    if c.node_id in state.nodes and c.state == STARTED:
                        node_ids.add(c.node_id)
                        n_shards += 1
        futures = []
        for nid in sorted(node_ids):
            if nid == self.local_node.node_id:
                self._handle_maintenance({"op": op, "indices": names},
                                         self.local_node.to_json())
            else:
                futures.append(self.transport.send_request_async(
                    state.nodes[nid].address, ACTION_MAINTENANCE,
                    {"op": op, "indices": names}))
        failed = 0
        for fut in futures:
            try:
                fut.result(timeout=30.0)
            except Exception:  # noqa: BLE001 — per-node failure counts
                failed += 1
        return {"_shards": {"total": n_shards,
                            "successful": n_shards - failed,
                            "failed": failed}}

    def _handle_maintenance(self, payload, from_node) -> Dict[str, Any]:
        op = payload["op"]
        for name in payload.get("indices") or []:
            if not self.node.indices.has_index(name):
                continue
            svc = self.node.indices.index(name)
            if op == "refresh":
                svc.refresh()
            elif op == "flush":
                svc.flush()
            elif op == "forcemerge":
                for shard in svc.shards.values():
                    shard.engine.force_merge()
        return {"acknowledged": True}

    # ------------------------------------------------------------------
    # replication seam (task: primary→replica fan-out; wired by the
    # write executors via node.replicate)
    # ------------------------------------------------------------------

    def replicate_op(self, op: str, index: str, shard: int, doc_id: str,
                     source: Optional[dict], result) -> None:
        """Primary→replica fan-out, called synchronously after every
        primary-phase apply (reference: ReplicationOperation#execute —
        the client ack means every in-sync copy has the op). Fans out to
        STARTED and INITIALIZING copies: a recovering replica that
        already opened its engine applies live ops directly (the per-doc
        seqno check drops duplicates vs the translog replay); one that
        hasn't yet raises ShardNotFound remotely, which is fine — the op
        is in the primary translog the replay will ship."""
        state = self.applied_state()
        copies = [c for c in state.shard_copies(index, shard)
                  if not c.primary and c.node_id
                  and c.node_id != self.local_node.node_id
                  and c.node_id in state.nodes
                  and c.state in (STARTED, INITIALIZING)]
        targets: List[Tuple[Optional[ShardRouting], Tuple[str, int]]] = [
            (c, state.nodes[c.node_id].address) for c in copies]
        # plus recovery targets registered at RECOVERY_START — they may
        # not be in this node's applied routing view yet (the reference
        # tracks them in the primary's ReplicationGroup)
        seen_addrs = {addr for _, addr in targets}
        with self._recovery_sources_lock:
            for (r_index, r_shard, aid), entry in \
                    self._recovery_sources.items():
                if (r_index, r_shard) == (index, shard) \
                        and entry["address"] not in seen_addrs:
                    targets.append((None, entry["address"]))
                    seen_addrs.add(entry["address"])
        if not targets:
            return
        payload = {"index": index, "shard": shard, "op": op, "id": doc_id,
                   "source": source, "seq_no": result.seq_no,
                   "primary_term": result.primary_term,
                   "version": result.version}
        futures = []
        for c, addr in targets:
            futures.append((c, addr, self.transport.send_request_async(
                addr, ACTION_REPLICA_OP, payload)))
        for c, addr, fut in futures:
            try:
                fut.result(timeout=30.0)
            except RemoteTransportException as e:
                if e.error_type == "ShardNotFoundException":
                    continue  # recovery will replay from the translog
                if e.error_type == "EsRejectedExecutionException":
                    # the replica is ALIVE but shedding load (indexing
                    # pressure pushback) — a transient condition, not a
                    # broken copy. Retry with bounded backoff before
                    # giving up and failing the shard; the seqno dedup
                    # on the replica makes a re-send idempotent.
                    try:
                        send_with_retry(
                            self.transport, addr, ACTION_REPLICA_OP,
                            payload, policy=RetryPolicy(deadline=3.0))
                        continue
                    except Exception as retry_exc:  # noqa: BLE001
                        e = retry_exc
                if c is not None:
                    self._fail_replica(index, shard, c, e)
            except Exception as e:  # noqa: BLE001 — replica unreachable
                if c is not None:
                    self._fail_replica(index, shard, c, e)
                # a pure recovery target failing is the recovery's
                # problem (its replay/restart covers it), not the ack's

    def _fail_replica(self, index: str, shard: int, copy: ShardRouting,
                      exc: Exception) -> None:
        """An unreachable/broken replica must leave the replication
        group BEFORE the write is acked — this blocks until the master
        commits the shard-failed update (reference: the primary fails
        the shard via the master and only then responds). If the master
        can't be reached the write must not be acked either."""
        events.emit("replica.failed", severity="error", index=index,
                    shard=shard, node=copy.node_id, error=str(exc))
        logger.warning("[%s] failing replica %s[%d] on %s: %s",
                       self.local_node.name, index, shard, copy.node_id,
                       exc)
        payload = {"index": index, "shard": shard,
                   "allocation_id": copy.allocation_id}
        deadline = time.monotonic() + 30.0
        last: Optional[Exception] = None
        while time.monotonic() < deadline:
            try:
                addr = self._master_address()
                if addr == self.local_node.address:
                    self._handle_shard_failed(payload,
                                              self.local_node.to_json())
                else:
                    self.transport.send_request(addr, ACTION_SHARD_FAILED,
                                                payload, timeout=10.0)
                return
            except Exception as e:  # noqa: BLE001 — retry until deadline
                last = e
                time.sleep(0.5)
        raise EsException(
            f"could not fail replica {index}[{shard}] on master: {last}")

    def _handle_replica_op(self, payload, from_node) -> Dict[str, Any]:
        from elasticsearch_tpu.common.errors import ShardNotFoundException
        index, shard_num = payload["index"], int(payload["shard"])
        svc = self.node.indices.index(index)
        shard = svc.shards.get(shard_num)
        if shard is None:
            raise ShardNotFoundException(
                f"shard [{index}][{shard_num}] not on this node")
        op = {"kind": "delete" if payload["op"] == "delete" else "index",
              "seq_no": payload["seq_no"],
              "primary_term": payload["primary_term"],
              "id": payload["id"], "source": payload.get("source"),
              "version": payload.get("version")}
        # replica-stage admission (1.5× budget): a saturated replica
        # pushes back on its primary with a typed 429 BEFORE applying —
        # the primary retries with backoff rather than silently queueing
        pressure = getattr(self.node, "indexing_pressure", None)
        if pressure is not None:
            with pressure.replica(operation_bytes(payload.get("source"))):
                self._apply_replica_op_dict(shard, op)
        else:
            self._apply_replica_op_dict(shard, op)
        return {"acknowledged": True}

    def _handle_shard_failed(self, payload, from_node) -> Dict[str, Any]:
        index, shard = payload["index"], int(payload["shard"])
        aid = payload["allocation_id"]
        events.emit("shard.failed", severity="error", index=index,
                    shard=shard, allocation_id=aid)

        def update(state: ClusterState) -> ClusterState:
            return AllocationService.shard_failed(state, index, shard, aid)

        # bump the bounded-retry streak (backoff, then max_retries cap)
        # BEFORE rerouting, so the reroute this update triggers already
        # sees the throttle
        self.allocation.record_failed_allocation(index, shard)
        self._run_master_update(update,
                                source=f"shard-failed[{index}][{shard}]")
        return {"acknowledged": True}

    # ------------------------------------------------------------------
    # health / introspection
    # ------------------------------------------------------------------

    def health(self) -> Dict[str, Any]:
        state = self.applied_state()
        active_primary = active = initializing = unassigned = 0
        red = yellow = False
        for name, meta in state.indices.items():
            for shard in range(meta.number_of_shards):
                copies = state.shard_copies(name, shard)
                primary_ok = False
                for c in copies:
                    if c.state == STARTED and c.node_id in state.nodes:
                        active += 1
                        if c.primary:
                            active_primary += 1
                            primary_ok = True
                    elif c.state == INITIALIZING:
                        initializing += 1
                    else:
                        unassigned += 1
                if not primary_ok:
                    red = True
                if any(c.state != STARTED for c in copies):
                    yellow = True
        status = "red" if red else ("yellow" if yellow else "green")
        total = active + initializing + unassigned
        return {
            "cluster_name": self.node.cluster_name,
            "status": status,
            "timed_out": False,
            "number_of_nodes": len(state.nodes),
            "number_of_data_nodes": len(state.nodes),
            "active_primary_shards": active_primary,
            "active_shards": active,
            "relocating_shards": 0,
            "initializing_shards": initializing,
            "unassigned_shards": unassigned,
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number":
                (100.0 * active / total) if total else 100.0,
        }

    def state_json(self) -> Dict[str, Any]:
        state = self.applied_state()
        out = state.to_json()
        out["cluster_name"] = self.node.cluster_name
        out["master_node"] = state.master_node_id
        return out


def _merge_mapping(base: Optional[dict], update: dict) -> dict:
    out = dict(base or {})
    for k, v in (update or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge_mapping(out[k], v)
        else:
            out[k] = v
    return out


def _rehydrate_error(e: RemoteTransportException) -> EsException:
    """Map a remote error back to the typed local exception so REST
    status codes survive the hop (reference: wire exception
    serialization)."""
    from elasticsearch_tpu.common import errors as err_mod
    cls = getattr(err_mod, e.error_type, None)
    if cls is not None and isinstance(cls, type) \
            and issubclass(cls, EsException):
        return cls(e.reason)
    if e.error_type == "MasterNotDiscoveredException":
        return MasterNotDiscoveredException(e.reason)
    if e.error_type in ("NotMasterException", "FailedToCommitException"):
        return EsException(e.reason)
    return EsException(f"[{e.error_type}] {e.reason}")
