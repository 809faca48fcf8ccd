"""Index administration REST actions: create/delete/get index, mappings,
settings, refresh/flush/forcemerge, open/close stubs (reference:
`action/admin/indices/**` + `RestCreateIndexAction` etc., SURVEY.md
§2.1#49)."""

from __future__ import annotations

from typing import Any, Dict

from elasticsearch_tpu.common.errors import IndexNotFoundException
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.rest.controller import RestController, RestRequest
from elasticsearch_tpu.search.coordinator import resolve_indices


def register(controller: RestController, node) -> None:
    indices = node.indices

    def create_index(req: RestRequest):
        body = req.body or {}
        mappings = body.get("mappings")
        name = req.param("index")
        if node.cluster is not None:
            node.cluster.create_index(name, body.get("settings") or {},
                                      mappings)
        else:
            node.create_index(name, Settings(
                Settings.normalize_index_settings(
                    body.get("settings"))), mappings)
        return 200, {"acknowledged": True, "shards_acknowledged": True,
                     "index": name}

    def delete_index(req: RestRequest):
        from elasticsearch_tpu.search.coordinator import \
            resolve_concrete_indices
        if node.cluster is not None:
            view = node.cluster._StateView(node.cluster.applied_state())
            for name in resolve_concrete_indices(view,
                                                 req.param("index")):
                node.cluster.delete_index(name)
            return 200, {"acknowledged": True}
        for name in resolve_concrete_indices(indices,
                                             req.param("index")):
            indices.delete_index(name)
            node.release_index(name)  # resident packs + HBM accounting
        return 200, {"acknowledged": True}

    def close_index(req: RestRequest):
        from elasticsearch_tpu.search.coordinator import \
            resolve_concrete_indices
        if node.cluster is not None:
            out = None
            for name in resolve_concrete_indices(
                    node.cluster._StateView(node.cluster.applied_state()),
                    req.param("index")):
                out = node.cluster.close_index_admin(name)
            return 200, out or {"acknowledged": True}
        closed = {}
        for name in resolve_concrete_indices(indices, req.param("index")):
            indices.close_index(name)
            closed[name] = {"closed": True}
            node.release_index(name)
        return 200, {"acknowledged": True, "shards_acknowledged": True,
                     "indices": closed}

    def open_index(req: RestRequest):
        from elasticsearch_tpu.search.coordinator import \
            resolve_concrete_indices
        if node.cluster is not None:
            out = None
            for name in resolve_concrete_indices(
                    node.cluster._StateView(node.cluster.applied_state()),
                    req.param("index")):
                out = node.cluster.open_index_admin(name)
            return 200, out or {"acknowledged": True}
        for name in resolve_concrete_indices(indices, req.param("index")):
            indices.open_index(name)
        return 200, {"acknowledged": True, "shards_acknowledged": True}

    def rollover(req: RestRequest):
        from elasticsearch_tpu import lifecycle
        return 200, lifecycle.rollover(
            node, req.param("index"), req.body,
            new_index=req.params.get("new_index") or None,
            dry_run=req.params.get("dry_run") in ("", "true", True))

    def rollover_named(req: RestRequest):
        from elasticsearch_tpu import lifecycle
        return 200, lifecycle.rollover(
            node, req.param("index"), req.body,
            new_index=req.param("new_index"),
            dry_run=req.params.get("dry_run") in ("", "true", True))

    def shrink_index(req: RestRequest):
        from elasticsearch_tpu import lifecycle
        return 200, lifecycle.shrink(node, req.param("index"),
                                     req.param("target"), req.body)

    def split_index(req: RestRequest):
        from elasticsearch_tpu import lifecycle
        return 200, lifecycle.split(node, req.param("index"),
                                    req.param("target"), req.body)

    def get_index(req: RestRequest):
        if node.cluster is not None:
            state = node.cluster.applied_state()
            out = {}
            for name in node.cluster.resolve_indices(req.param("index")):
                meta = state.indices[name]
                out[name] = {
                    "aliases": dict(meta.aliases),
                    "mappings": meta.mapping or {},
                    "settings": {"index": {
                        "number_of_shards": str(meta.number_of_shards),
                        "number_of_replicas": str(meta.number_of_replicas),
                        "uuid": meta.uuid}},
                }
            if not out:
                raise IndexNotFoundException(
                    f"no such index [{req.param('index')}]")
            return 200, out
        out = {}
        for name in resolve_indices(indices, req.param("index")):
            svc = indices.index(name)
            out[name] = {
                "aliases": {a: p for a, tgts in indices.aliases.items()
                            for i, p in tgts.items() if i == name},
                "mappings": svc.mapper.to_mapping(),
                "settings": {"index": {
                    "number_of_shards": str(svc.num_shards),
                    "number_of_replicas": str(svc.num_replicas),
                    "uuid": svc.index_uuid,
                    **{k[len("index."):]: v for k, v in
                       svc.settings.get_as_dict().items()
                       if k.startswith("index.") and k not in
                       ("index.number_of_shards", "index.number_of_replicas")},
                }},
            }
        if not out:
            raise IndexNotFoundException(
                f"no such index [{req.param('index')}]")
        return 200, out

    def head_index(req: RestRequest):
        if node.cluster is not None:
            names = node.cluster.resolve_indices(req.param("index"))
            return (200, {}) if names else (404, {})
        names = resolve_indices(indices, req.param("index"))
        return (200, {}) if names else (404, {})

    def put_mapping(req: RestRequest):
        tpu = getattr(node, "tpu_search", None)
        if node.cluster is not None:
            for name in node.cluster.resolve_indices(req.param("index")):
                node.cluster.put_mapping(name, req.body or {})
                if tpu is not None:
                    tpu.invalidate_plans(name)
            return 200, {"acknowledged": True}
        for name in resolve_indices(indices, req.param("index")):
            indices.index(name).mapper.merge(req.body or {})
            if tpu is not None:
                # lowered plans key on the mapping generation; purge the
                # now-unreachable entries so the LRU doesn't carry them
                tpu.invalidate_plans(name)
        indices.persist_metadata()  # mapping is part of gateway state
        return 200, {"acknowledged": True}

    def get_mapping(req: RestRequest):
        if node.cluster is not None:
            state = node.cluster.applied_state()
            return 200, {
                name: {"mappings": state.indices[name].mapping or {}}
                for name in node.cluster.resolve_indices(
                    req.param("index"))}
        out = {}
        for name in resolve_indices(indices, req.param("index")):
            out[name] = {"mappings": indices.index(name).mapper.to_mapping()}
        return 200, out

    def put_settings(req: RestRequest):
        body = req.body or {}
        # accepted spellings (all reference forms): {"index": {...}},
        # {"settings": {...}}, flat dotted keys ("index.x" / "x")
        changes = Settings.normalize_index_settings(
            body.get("settings", body))
        if node.cluster is not None:
            for name in node.cluster.resolve_indices(req.param("index")):
                node.cluster.update_index_settings(name, changes)
            return 200, {"acknowledged": True}
        from elasticsearch_tpu.indices.service import IndexService
        IndexService.validate_dynamic_settings(changes)
        for name in resolve_indices(indices, req.param("index")):
            indices.index(name).apply_dynamic_settings(changes)
        indices.persist_metadata()
        return 200, {"acknowledged": True}

    def get_settings(req: RestRequest):
        out = {}
        for name in resolve_indices(indices, req.param("index")):
            svc = indices.index(name)
            out[name] = {"settings": {"index": {
                "number_of_shards": str(svc.num_shards),
                "number_of_replicas": str(svc.num_replicas),
                "uuid": svc.index_uuid}}}
        return 200, out

    def refresh(req: RestRequest):
        if node.cluster is not None:
            return 200, node.cluster.broadcast_maintenance(
                "refresh", req.param("index"))
        n = 0
        for name in resolve_indices(indices, req.param("index")):
            indices.index(name).refresh()
            n += indices.index(name).num_shards
        return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}

    def flush(req: RestRequest):
        if node.cluster is not None:
            return 200, node.cluster.broadcast_maintenance(
                "flush", req.param("index"))
        n = 0
        for name in resolve_indices(indices, req.param("index")):
            indices.index(name).flush()
            n += indices.index(name).num_shards
        return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}

    def forcemerge(req: RestRequest):
        if node.cluster is not None:
            return 200, node.cluster.broadcast_maintenance(
                "forcemerge", req.param("index"))
        n = 0
        for name in resolve_indices(indices, req.param("index")):
            svc = indices.index(name)
            for shard in svc.shards.values():
                shard.engine.force_merge()
                n += 1
        return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}

    def index_stats(req: RestRequest):
        names = resolve_indices(indices, req.param("index"))
        out_indices = {}
        total_docs = 0
        total_segments = 0
        for name in names:
            svc = indices.index(name)
            st = svc.stats()
            total_docs += st["docs"]["count"]
            segs = sum(p["segments"] for p in st["per_shard"])
            total_segments += segs
            out_indices[name] = {
                "primaries": {"docs": {"count": st["docs"]["count"]},
                              "segments": {"count": segs}},
                "total": {"docs": {"count": st["docs"]["count"]},
                          "segments": {"count": segs}},
            }
        return 200, {
            "_shards": {"total": sum(indices.index(n).num_shards for n in names)},
            "_all": {"primaries": {"docs": {"count": total_docs},
                                   "segments": {"count": total_segments}}},
            "indices": out_indices,
        }

    controller.register("PUT", "/{index}", create_index)
    controller.register("DELETE", "/{index}", delete_index)
    controller.register("POST", "/{index}/_close", close_index)
    controller.register("POST", "/{index}/_open", open_index)
    controller.register("POST", "/{index}/_rollover", rollover)
    controller.register("POST", "/{index}/_rollover/{new_index}",
                        rollover_named)
    controller.register("PUT", "/{index}/_shrink/{target}", shrink_index)
    controller.register("POST", "/{index}/_shrink/{target}", shrink_index)
    controller.register("PUT", "/{index}/_split/{target}", split_index)
    controller.register("POST", "/{index}/_split/{target}", split_index)
    controller.register("GET", "/{index}", get_index)
    controller.register("HEAD", "/{index}", head_index)
    controller.register("PUT", "/{index}/_mapping", put_mapping)
    controller.register("GET", "/{index}/_mapping", get_mapping)
    controller.register("GET", "/_mapping", get_mapping)
    controller.register("GET", "/{index}/_settings", get_settings)
    controller.register("GET", "/_settings", get_settings)
    controller.register("PUT", "/{index}/_settings", put_settings)
    controller.register("POST", "/{index}/_refresh", refresh)
    controller.register("POST", "/_refresh", refresh)
    controller.register("GET", "/{index}/_refresh", refresh)
    controller.register("POST", "/{index}/_flush", flush)
    controller.register("POST", "/_flush", flush)
    controller.register("POST", "/{index}/_forcemerge", forcemerge)
    controller.register("GET", "/{index}/_stats", index_stats)
    controller.register("GET", "/_stats", index_stats)
