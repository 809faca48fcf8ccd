"""Cluster-level + _cat REST actions (reference: RestClusterHealthAction,
rest/action/cat/* — SURVEY.md §2.1#47/56). Single-node health semantics:
green when every shard is assigned (they always are locally), yellow
reserved for unassigned replicas once the cluster layer lands."""

from __future__ import annotations

import time
from typing import Any, Dict, List

from elasticsearch_tpu.rest.controller import RestController, RestRequest
from elasticsearch_tpu.search.coordinator import resolve_indices
from elasticsearch_tpu.version import __version__ as VERSION


def _parse_time_s(value: str) -> float:
    """Reference TimeValue grammar subset: "500ms" | "30s" | "1m" |
    bare seconds."""
    v = value.strip().lower()
    try:
        for suffix, scale in (("ms", 0.001), ("s", 1.0), ("m", 60.0),
                              ("h", 3600.0)):
            if v.endswith(suffix):
                return float(v[:-len(suffix)]) * scale
        return float(v)
    except ValueError:
        return 30.0


def _cat_table(req, headers: List[str], rows: List[List[Any]]):
    """The _cat text-table renderer shared by every cat endpoint."""
    if req.param_bool("v"):
        all_rows = [headers] + [[str(c) for c in r] for r in rows]
    else:
        all_rows = [[str(c) for c in r] for r in rows]
    widths = [max((len(r[i]) for r in all_rows), default=0)
              for i in range(len(headers))]
    lines = [" ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in all_rows]
    return 200, {"_cat": "\n".join(lines) + "\n"}


def register(controller: RestController, node) -> None:
    indices = node.indices

    def root(req: RestRequest):
        return 200, {
            "name": node.node_name,
            "cluster_name": node.cluster_name,
            "cluster_uuid": node.cluster_uuid,
            "version": {"number": VERSION,
                        "build_flavor": "tpu",
                        "lucene_version": "n/a (XLA kernels)"},
            "tagline": "You Know, for Search — on TPUs",
        }

    def health(req: RestRequest):
        if node.cluster is not None:
            out = node.cluster.health()
            want = req.params.get("wait_for_status")
            if want in ("green", "yellow"):
                import time as _time
                rank = {"green": 0, "yellow": 1, "red": 2}
                deadline = _time.monotonic() + _parse_time_s(
                    req.params.get("timeout", "30s"))
                while (rank[out["status"]] > rank[want]
                       and _time.monotonic() < deadline):
                    _time.sleep(0.1)
                    out = node.cluster.health()
                out["timed_out"] = rank[out["status"]] > rank[want]
            return 200, out
        n_shards = sum(svc.num_shards for svc in indices.indices.values())
        return 200, {
            "cluster_name": node.cluster_name,
            "status": "green",
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "active_primary_shards": n_shards,
            "active_shards": n_shards,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": 0,
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": 100.0,
        }

    def cluster_stats(req: RestRequest):
        total_docs = sum(svc.stats()["docs"]["count"]
                         for svc in indices.indices.values())
        return 200, {
            "cluster_name": node.cluster_name,
            "status": "green",
            "indices": {"count": len(indices.indices),
                        "docs": {"count": total_docs}},
            "nodes": {"count": {"total": 1, "data": 1, "master": 1}},
        }

    def nodes_stats(req: RestRequest):
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        out = {"_nodes": {"total": 1, "successful": 1},
               "cluster_name": node.cluster_name,
               "nodes": {node.node_id: {
                   "name": node.node_name,
                   "indices": indices.stats(),
                   "process": {"max_rss_bytes": ru.ru_maxrss * 1024},
                   "jvm": None,
               }}}
        if node.tpu_search is not None:
            out["nodes"][node.node_id]["tpu_search"] = \
                node.tpu_search.stats()
        if getattr(node, "thread_pools", None) is not None:
            out["nodes"][node.node_id]["thread_pool"] = \
                node.thread_pools.stats()
        if getattr(node, "breakers", None) is not None:
            # the service's own stats() — includes the PARENT breaker,
            # the signal the hierarchy exists for
            out["nodes"][node.node_id]["breakers"] = \
                node.breakers.stats()
        if getattr(node, "indexing_pressure", None) is not None:
            # per-stage current/total/rejection byte accounting
            # (reference: the 7.9+ `indexing_pressure` stats section)
            out["nodes"][node.node_id]["indexing_pressure"] = \
                node.indexing_pressure.stats()
        if getattr(node, "search_backpressure", None) is not None:
            out["nodes"][node.node_id]["search_backpressure"] = \
                node.search_backpressure.stats()
        if getattr(node, "tenants", None) is not None:
            # per-tenant QoS: weights, caps, in-flight and rejections
            out["nodes"][node.node_id]["tenants"] = node.tenants.stats()
        # bounded-retry allocation visibility: total shard-copy
        # allocation failures (corrupt store opens, failed recoveries)
        # plus the currently-throttled streaks per [index][shard]
        alloc = getattr(getattr(node, "cluster", None), "allocation", None)
        out["nodes"][node.node_id]["allocations"] = {
            "failed_allocations":
                alloc.c_failed_allocations.count if alloc else 0,
            "failed_streaks":
                {f"{i}[{s}]": n for (i, s), n in
                 sorted(alloc.failed_allocations.items())} if alloc
                else {},
        }
        return 200, out

    # ---------------- _cat ----------------

    _maybe_table = _cat_table

    def cat_indices(req: RestRequest):
        rows = []
        for name in resolve_indices(indices, req.param("index")):
            svc = indices.index(name)
            st = svc.stats()
            rows.append(["green", "open", name, svc.index_uuid,
                         svc.num_shards, svc.num_replicas,
                         st["docs"]["count"], 0])
        return _maybe_table(req, ["health", "status", "index", "uuid", "pri",
                                  "rep", "docs.count", "docs.deleted"], rows)

    def cat_health(req: RestRequest):
        return _maybe_table(req, ["epoch", "timestamp", "cluster", "status",
                                  "node.total", "shards"],
                            [[int(time.time()),
                              time.strftime("%H:%M:%S"),
                              node.cluster_name, "green", 1,
                              sum(s.num_shards
                                  for s in indices.indices.values())]])

    def cat_count(req: RestRequest):
        from elasticsearch_tpu.search import coordinator
        c = coordinator.count(indices, req.param("index"), None)
        return _maybe_table(req, ["epoch", "timestamp", "count"],
                            [[int(time.time()), time.strftime("%H:%M:%S"),
                              c["count"]]])

    def cat_shards(req: RestRequest):
        if node.cluster is not None:
            state = node.cluster.applied_state()
            rows = []
            for name in node.cluster.resolve_indices(req.param("index")):
                for s, copies in sorted(
                        state.routing.get(name, {}).items()):
                    for c in copies:
                        node_name = (state.nodes[c.node_id].name
                                     if c.node_id in state.nodes else "-")
                        rows.append([name, s, "p" if c.primary else "r",
                                     c.state, "-", node_name])
            return _maybe_table(req, ["index", "shard", "prirep", "state",
                                      "docs", "node"], rows)
        rows = []
        for name in resolve_indices(indices, req.param("index")):
            svc = indices.index(name)
            for num, shard in sorted(svc.shards.items()):
                rows.append([name, num, "p" if shard.primary else "r",
                             "STARTED", shard.engine.num_docs(),
                             node.node_name])
        return _maybe_table(req, ["index", "shard", "prirep", "state",
                                  "docs", "node"], rows)

    def get_cluster_settings(req: RestRequest):
        if node.cluster is not None:
            state = node.cluster.applied_state()
            return 200, {"persistent": dict(state.persistent_settings),
                         "transient": dict(state.transient_settings)}
        return 200, {"persistent": dict(node.persistent_settings),
                     "transient": dict(node.transient_settings)}

    def put_cluster_settings(req: RestRequest):
        body = req.body or {}
        persistent = body.get("persistent") or {}
        transient = body.get("transient") or {}
        if not persistent and not transient:
            from elasticsearch_tpu.common.errors import \
                IllegalArgumentException
            raise IllegalArgumentException(
                "no settings to update: provide [persistent] and/or "
                "[transient]")
        if node.cluster is not None:
            return 200, node.cluster.update_cluster_settings(persistent,
                                                             transient)
        return 200, node.update_cluster_settings_local(persistent,
                                                       transient)

    def cluster_state(req: RestRequest):
        if node.cluster is not None:
            return 200, node.cluster.state_json()
        return 200, {"cluster_name": node.cluster_name,
                     "cluster_uuid": node.cluster_uuid,
                     "master_node": node.node_id,
                     "nodes": {node.node_id: {"name": node.node_name}}}

    def cat_nodes(req: RestRequest):
        if node.cluster is not None:
            state = node.cluster.applied_state()
            rows = []
            for n in state.data_nodes():
                role = "m" if n.node_id == state.master_node_id else "-"
                rows.append([n.host, n.port, role, n.name])
            return _maybe_table(req, ["host", "port", "master", "name"],
                                rows)
        return _maybe_table(req, ["host", "port", "master", "name"],
                            [["127.0.0.1", 9200, "m", node.node_name]])

    def cat_root(req: RestRequest):
        paths = ["/_cat/aliases", "/_cat/allocation", "/_cat/count",
                 "/_cat/health", "/_cat/indices", "/_cat/master",
                 "/_cat/nodes", "/_cat/plugins", "/_cat/recovery",
                 "/_cat/shards", "/_cat/tasks"]
        return 200, {"_cat": "=^.^=\n" + "\n".join(paths) + "\n"}

    def cat_aliases(req: RestRequest):
        from elasticsearch_tpu.rest.actions.aliases import _alias_map
        rows = []
        for alias, targets in sorted(_alias_map(node).items()):
            for index, props in sorted(targets.items()):
                rows.append([alias, index,
                             "*" if props.get("filter") else "-",
                             "true" if props.get("is_write_index")
                             else "-"])
        return _maybe_table(req, ["alias", "index", "filter",
                                  "is_write_index"], rows)

    def cat_master(req: RestRequest):
        if node.cluster is not None:
            master = node.cluster.coordinator.master_node()
            if master is None:
                return _maybe_table(req, ["id", "host", "node"], [])
            return _maybe_table(req, ["id", "host", "node"],
                                [[master.node_id, master.host,
                                  master.name]])
        return _maybe_table(req, ["id", "host", "node"],
                            [[node.node_id, "127.0.0.1",
                              node.node_name]])

    def cat_allocation(req: RestRequest):
        rows = []
        if node.cluster is not None:
            state = node.cluster.applied_state()
            per_node = {nid: 0 for nid in state.nodes}
            for shards in state.routing.values():
                for copies in shards.values():
                    for c in copies:
                        if c.node_id in per_node:
                            per_node[c.node_id] += 1
            for nid, count in sorted(per_node.items()):
                n = state.nodes[nid]
                rows.append([count, n.host, n.name])
        else:
            total = sum(len(svc.shards)
                        for svc in indices.indices.values())
            rows.append([total, "127.0.0.1", node.node_name])
        return _maybe_table(req, ["shards", "host", "node"], rows)

    def cat_recovery(req: RestRequest):
        rows = []
        for name in resolve_indices(indices, req.param("index")):
            svc = indices.index(name)
            for num, shard in sorted(svc.shards.items()):
                rows.append([name, num, "done",
                             "existing_store" if shard.primary
                             else "peer", node.node_name])
        return _maybe_table(req, ["index", "shard", "stage", "type",
                                  "node"], rows)

    def cat_plugins(req: RestRequest):
        rows = [[node.node_name, mod, "-"]
                for mod in node.plugins.loaded_modules]
        return _maybe_table(req, ["name", "component", "version"], rows)

    def cat_tasks(req: RestRequest):
        rows = [[t.action, t.full_id, "transport",
                 t.start_time_millis, t.description]
                for t in node.task_manager.list()]
        return _maybe_table(req, ["action", "task_id", "type",
                                  "start_time", "description"], rows)

    controller.register("GET", "/_cat", cat_root)
    controller.register("GET", "/_cat/aliases", cat_aliases)
    controller.register("GET", "/_cat/master", cat_master)
    controller.register("GET", "/_cat/allocation", cat_allocation)
    controller.register("GET", "/_cat/recovery", cat_recovery)
    controller.register("GET", "/_cat/recovery/{index}", cat_recovery)
    controller.register("GET", "/_cat/plugins", cat_plugins)
    controller.register("GET", "/_cat/tasks", cat_tasks)
    controller.register("GET", "/", root)
    controller.register("GET", "/_cluster/settings", get_cluster_settings)
    controller.register("PUT", "/_cluster/settings", put_cluster_settings)
    controller.register("GET", "/_cluster/state", cluster_state)
    controller.register("GET", "/_cat/nodes", cat_nodes)
    controller.register("GET", "/_cluster/health", health)
    controller.register("GET", "/_cluster/stats", cluster_stats)
    controller.register("GET", "/_nodes/stats", nodes_stats)
    controller.register("GET", "/_cat/indices", cat_indices)
    controller.register("GET", "/_cat/indices/{index}", cat_indices)
    controller.register("GET", "/_cat/health", cat_health)
    controller.register("GET", "/_cat/count", cat_count)
    controller.register("GET", "/_cat/count/{index}", cat_count)
    controller.register("GET", "/_cat/shards", cat_shards)
    controller.register("GET", "/_cat/shards/{index}", cat_shards)
