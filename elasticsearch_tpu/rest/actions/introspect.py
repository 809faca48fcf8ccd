"""Introspection / debugging APIs: _field_caps, _validate/query,
_explain, _termvectors, _nodes/hot_threads, _cluster/allocation/explain
(reference: FieldCapabilities*, TransportValidateQueryAction,
TransportExplainAction, TermVectorsService, HotThreads,
ClusterAllocationExplainAction — SURVEY.md §2.1#40/47/49/56, §5.1).
"""

from __future__ import annotations

import collections
import sys
import time
from typing import Any, Dict, List, Optional

from elasticsearch_tpu.common.errors import (DocumentMissingException,
                                             IllegalArgumentException,
                                             IndexNotFoundException,
                                             ResourceNotFoundException)
from elasticsearch_tpu.rest.controller import RestController, RestRequest

# field types that aggregate via doc-values columns
_AGGREGATABLE = {"keyword", "long", "integer", "short", "byte", "double",
                 "float", "half_float", "date", "boolean", "ip",
                 "rank_feature", "geo_point"}
_SEARCHABLE_EXTRA = {"dense_vector", "rank_feature", "geo_point"}


def field_caps(node, index_expr: Optional[str],
               fields_param: Optional[str]) -> Dict[str, Any]:
    """→ the _field_caps response: per field, per type, searchable /
    aggregatable, with the contributing indices listed (reference:
    FieldCapabilitiesResponse)."""
    import fnmatch

    from elasticsearch_tpu.search.coordinator import resolve_targets
    names, _filters = resolve_targets(node.indices, index_expr)
    patterns = [p.strip() for p in (fields_param or "*").split(",")
                if p.strip()]
    per_field: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for name in names:
        svc = node.indices.index(name)
        for path, ft in svc.mapper.mapper.fields.items():
            if not any(fnmatch.fnmatchcase(path, p) for p in patterns):
                continue
            t = ft.type_name
            entry = per_field.setdefault(path, {}).setdefault(t, {
                "type": t,
                "metadata_field": False,
                "searchable": bool(getattr(ft, "is_indexed", True))
                or t in _SEARCHABLE_EXTRA,
                "aggregatable": t in _AGGREGATABLE,
                "indices": []})
            entry["indices"].append(name)
    out_fields: Dict[str, Any] = {}
    for path, types in per_field.items():
        out: Dict[str, Any] = {}
        for t, entry in types.items():
            # `indices` is only reported when the field does NOT span
            # every target index (reference behavior)
            if len(entry["indices"]) == len(names):
                entry = {k: v for k, v in entry.items()
                         if k != "indices"}
            out[t] = entry
        out_fields[path] = out
    return {"indices": sorted(names), "fields": out_fields}


def validate_query(node, index_expr: Optional[str],
                   body: Optional[Dict[str, Any]],
                   explain: bool) -> Dict[str, Any]:
    from elasticsearch_tpu.search import dsl
    from elasticsearch_tpu.search.coordinator import resolve_targets
    names, _ = resolve_targets(node.indices, index_expr)
    spec = (body or {}).get("query") or {"match_all": {}}
    try:
        parsed = dsl.parse_query(spec)
    except Exception as exc:  # noqa: BLE001 — the point is to report it
        out = {"valid": False,
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if explain:
            out["error"] = str(exc)
        return out
    out = {"valid": True,
           "_shards": {"total": 1, "successful": 1, "failed": 0}}
    if explain:
        out["explanations"] = [
            {"index": name, "valid": True,
             "explanation": parsed.query_name()} for name in names]
    return out


def explain_doc(node, index: str, doc_id: str,
                body: Optional[Dict[str, Any]],
                params: Dict[str, str]) -> Dict[str, Any]:
    """GET /{index}/_explain/{id}: does the query match this doc, and
    with what score (reference: TransportExplainAction; the Lucene
    explanation tree is summarized — scores here come from one fused
    kernel, not a per-clause scorer walk)."""
    import numpy as np

    from elasticsearch_tpu.search import dsl
    from elasticsearch_tpu.search.planner import SegmentQueryExecutor
    spec = (body or {}).get("query")
    if spec is None:
        raise IllegalArgumentException("[_explain] requires a [query]")
    query = dsl.parse_query(spec)
    svc = node.indices.index(index)
    shard_num = svc.shard_for_id(doc_id, params.get("routing"))
    reader = svc.shard(shard_num).acquire_searcher()
    for view_idx, view in enumerate(reader.views):
        ord_ = view.segment.id_to_ord.get(doc_id)
        if ord_ is None or not view.live_mask[ord_]:
            continue
        mask, score = SegmentQueryExecutor(reader, view_idx).execute(
            query)
        matched = bool(np.asarray(mask)[ord_])
        value = float(np.asarray(score)[ord_]) if matched else 0.0
        desc = f"score({query.query_name()})" if matched else \
            "no matching clause"
        return {"_index": index, "_id": doc_id, "matched": matched,
                "explanation": {"value": value, "description": desc,
                                "details": []}}
    raise DocumentMissingException(f"[{doc_id}]: document missing")


def termvectors(node, index: str, doc_id: str,
                body: Optional[Dict[str, Any]],
                params: Dict[str, str]) -> Dict[str, Any]:
    """GET /{index}/_termvectors/{id}: per text field, the doc's terms
    with frequencies and positions (re-derived from _source through the
    field's analyzer — the realtime flavor of TermVectorsService)."""
    from elasticsearch_tpu.mapping.types import TextFieldType
    body = body or {}
    svc = node.indices.index(index)
    shard_num = svc.shard_for_id(doc_id, params.get("routing"))
    doc = svc.shard(shard_num).get(doc_id)
    if doc is None:
        return {"_index": index, "_id": doc_id, "found": False}
    source = doc.get("_source") or {}
    want = body.get("fields") or params.get("fields")
    if isinstance(want, str):
        want = [f.strip() for f in want.split(",") if f.strip()]
    from elasticsearch_tpu.ingest import get_field
    reader = svc.shard(shard_num).acquire_searcher()
    tv: Dict[str, Any] = {}
    for path, ft in svc.mapper.mapper.fields.items():
        if not isinstance(ft, TextFieldType):
            continue
        if want and path not in want:
            continue
        # dotted traversal: object-mapped fields live nested in _source;
        # multi-fields (title.en) read their parent's value
        value = get_field(source, path)
        if value is None and "." in path:
            value = get_field(source, path.rsplit(".", 1)[0])
        if value is None:
            continue
        values = value if isinstance(value, list) else [value]
        term_stats: Dict[str, Dict[str, Any]] = {}
        pos_base = 0
        for v in values:
            tokens = ft.analyzer.analyze(str(v))
            for tok in tokens:
                entry = term_stats.setdefault(
                    tok.term, {"term_freq": 0, "tokens": []})
                entry["term_freq"] += 1
                entry["tokens"].append(
                    {"position": pos_base + tok.position})
            pos_base += 100 + len(tokens)
        if not term_stats:
            continue
        doc_count, avgdl = reader.field_stats(path)
        field_block: Dict[str, Any] = {
            "field_statistics": {
                "sum_doc_freq": sum(
                    reader.doc_freq(path, t) for t in term_stats),
                "doc_count": doc_count,
                "sum_ttf": int(avgdl * doc_count)},
            "terms": {}}
        want_stats = (str(params.get("term_statistics",
                                     body.get("term_statistics",
                                              "false"))).lower()
                      == "true")
        for term in sorted(term_stats):
            entry = dict(term_stats[term])
            if want_stats:
                entry["doc_freq"] = reader.doc_freq(path, term)
            field_block["terms"][term] = entry
        tv[path] = field_block
    return {"_index": index, "_id": doc_id, "found": True,
            "took": 0, "term_vectors": tv}


def hot_threads(node, params: Dict[str, str]) -> str:
    """_nodes/hot_threads: sample every Python thread's stack N times
    with the profiler's frame walker and report each busy thread's most
    common sampled stack — real stack dumps, not just queue counts
    (reference: monitor/jvm/HotThreads — a text report, not JSON)."""
    import threading

    from elasticsearch_tpu.common.profiler import walk_frames

    snapshots = int(params.get("snapshots", 3))
    interval_s = 0.05
    threads = int(params.get("threads", 3))
    counts: Dict[str, int] = collections.Counter()
    # per thread: how often each distinct stack was observed
    stacks: Dict[str, collections.Counter] = {}
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    for i in range(snapshots):
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            stack = tuple(walk_frames(frame, 16))  # leaf-first
            if not stack:
                continue
            key = names.get(ident, str(ident))
            counts[key] += 1
            stacks.setdefault(key, collections.Counter())[stack] += 1
        if i + 1 < snapshots:
            time.sleep(interval_s)
    lines = [f"::: {{{node.node_name}}}",
             f"   Hot threads at {time.strftime('%Y-%m-%dT%H:%M:%S')}, "
             f"interval={int(interval_s * 1000)}ms, busiestThreads="
             f"{threads}, ignoreIdleThreads=true:"]
    for name, cnt in counts.most_common(threads):
        share = 100.0 * cnt / max(snapshots, 1)
        lines.append(f"   {share:.1f}% sampled usage by thread "
                     f"'{name}'")
        top = stacks.get(name, collections.Counter()).most_common(1)
        if top:
            stack, seen = top[0]
            lines.append(f"     {seen}/{cnt} snapshots in:")
            for fr in stack:
                fname, _, func = fr.partition(":")
                lines.append(f"       {func} ({fname})")
    # per-pool admission state rides along so stall diagnosis (is the
    # pool saturated or is one thread wedged?) is one call, not two
    pools = getattr(node, "thread_pools", None)
    if pools is not None:
        lines.append("   Thread pools:")
        for pname, st in sorted(pools.stats().items()):
            lines.append(
                f"   [{pname}] active={st['active']}/{st['threads']} "
                f"queue={st['queue']}/{st['queue_size']} "
                f"rejected={st['rejected']} completed={st['completed']}")
    return "\n".join(lines) + "\n"


def allocation_explain(node, body: Optional[Dict[str, Any]]
                       ) -> Dict[str, Any]:
    """_cluster/allocation/explain (reference:
    ClusterAllocationExplainAction): where one shard is and why, or —
    with an empty body — the first unassigned shard found."""
    body = body or {}
    cluster = node.cluster
    if cluster is None:
        # single-node: explain against the local registry
        index = body.get("index")
        names = [index] if index else sorted(node.indices.indices)
        shard_num = int(body.get("shard", 0))
        for name in names:
            try:
                svc = node.indices.index(name)
            except IndexNotFoundException:
                raise
            if shard_num not in svc.shards:
                continue
            return {"index": name, "shard": shard_num,
                    "primary": bool(body.get("primary", True)),
                    "current_state": "started",
                    "current_node": {"id": node.node_name,
                                     "name": node.node_name},
                    "explanation": "shard is started on the only node"}
        raise IllegalArgumentException(
            "unable to find any shards to explain "
            f"[{body}] in the routing table")
    state = cluster.applied_state()
    targets = []
    if body.get("index") is not None:
        targets.append((str(body["index"]), int(body.get("shard", 0)),
                        bool(body.get("primary", True))))
    else:
        # first unassigned shard, as the reference defaults
        for name, meta in state.indices.items():
            for s in range(meta.number_of_shards):
                copies = state.shard_copies(name, s)
                started = [c for c in copies if c.state == "STARTED"]
                if len(started) < 1 + meta.number_of_replicas:
                    targets.append((name, s, len(started) == 0))
                    break
    if not targets:
        raise IllegalArgumentException(
            "unable to find any unassigned shards to explain; specify "
            "the target shard [index/shard/primary] in the request")
    name, shard_num, primary = targets[0]
    meta = state.indices.get(name)
    if meta is None:
        raise IndexNotFoundException(f"no such index [{name}]")
    copies = state.shard_copies(name, shard_num)
    started = [c for c in copies if c.state == "STARTED"]
    out: Dict[str, Any] = {"index": name, "shard": shard_num,
                           "primary": primary}
    if started:
        c = started[0]
        nname = state.nodes[c.node_id].name \
            if c.node_id in state.nodes else c.node_id
        out["current_state"] = "started"
        out["current_node"] = {"id": c.node_id, "name": nname}
        out["explanation"] = (
            f"shard has {len(started)} started "
            f"{'copies' if len(started) > 1 else 'copy'} of "
            f"{1 + meta.number_of_replicas} wanted")
    else:
        out["current_state"] = "unassigned"
        out["unassigned_info"] = {"reason": "NODE_LEFT" if copies
                                  else "INDEX_CREATED"}
        out["explanation"] = (
            "cannot allocate because no node holds an in-sync copy "
            "of the shard" if copies else
            "the shard has never been assigned")
    return out


def register(controller: RestController, node) -> None:
    def do_field_caps(req: RestRequest):
        fields = req.params.get("fields")
        if fields is None and isinstance(req.body, dict):
            f = req.body.get("fields")
            fields = ",".join(f) if isinstance(f, list) else f
        return 200, field_caps(node, req.param("index"), fields)

    def do_validate(req: RestRequest):
        explain = str(req.params.get("explain", "false")).lower() == \
            "true"
        return 200, validate_query(node, req.param("index"),
                                   req.body or {}, explain)

    def do_explain(req: RestRequest):
        return 200, explain_doc(node, req.param("index"),
                                req.param("id"), req.body or {},
                                req.params)

    def do_termvectors(req: RestRequest):
        return 200, termvectors(node, req.param("index"),
                                req.param("id"), req.body or {},
                                req.params)

    def do_hot_threads(req: RestRequest):
        return 200, hot_threads(node, req.params)

    def do_alloc_explain(req: RestRequest):
        return 200, allocation_explain(node, req.body or {})

    def do_tpu_stats(req: RestRequest):
        # serving-path observability: stage timers (totals + per-query
        # p50/p95/p99), plan/pack cache hit rates, prewarm progress and
        # the kernel-path breaker state — the production view of what
        # bench logs show offline
        tpu = getattr(node, "tpu_search", None)
        profiler = getattr(node, "profiler", None)
        if tpu is None:
            out: Dict[str, Any] = {"enabled": False}
        else:
            out = {"enabled": True}
            out.update(tpu.stats())
        merge_status = getattr(node, "merge_status", None)
        if merge_status is not None:
            # where deferred k-way merges run (inline / front / pool)
            # and what they cost
            out["merge"] = merge_status()
        if profiler is not None:
            out["profiler"] = profiler.info()
        gc_watch = getattr(node, "gc_watch", None)
        if gc_watch is not None:
            # what stops every Python thread of the process at once
            out["runtime"] = {"gc": gc_watch.stats()}
        return 200, out

    def do_tpu_traces(req: RestRequest):
        # recent finished spans (newest first), filterable by trace id /
        # minimum duration — the query surface for the tracing layer
        tracer = getattr(node, "tracer", None)
        if tracer is None:
            return 200, {"sample_rate": 0.0, "total": 0, "spans": []}
        trace_id = req.params.get("trace_id")
        tenant = req.params.get("tenant") or None
        min_ms = float(req.params.get("min_duration_ms", 0) or 0)
        limit = int(req.params.get("limit", 200) or 200)
        if trace_id:
            spans = [s for s in tracer.trace(trace_id)
                     if (s["duration_ms"] or 0) >= min_ms
                     and (tenant is None
                          or s.get("attributes", {}).get("tenant")
                          == tenant)]
        else:
            spans = tracer.spans(min_duration_ms=min_ms, limit=limit,
                                 tenant=tenant)
        return 200, {"sample_rate": tracer.sample_rate,
                     "slow_threshold_ms": tracer.slow_threshold_ms,
                     "total": len(spans), "spans": spans}

    def do_profile_flamegraph(req: RestRequest):
        # folded stacks from the continuous host sampler. Default
        # format is folded text (str payload → text/plain — paste
        # straight into flamegraph.pl / speedscope); format=json returns
        # structured stacks. ?trace_id= filters to samples taken while
        # that trace was live on the sampled thread.
        sampler = node.profiler.sampler
        trace_id = req.params.get("trace_id") or None
        pool = req.params.get("pool") or None
        top = int(req.params.get("top", 0) or 0) or None
        fmt = str(req.params.get("format", "folded")).lower()
        # multi-process merge: when serving fronts exist, every line is
        # prefixed with its process role (batcher; / front-N;) and the
        # fronts' shm-published folded stacks join the scrape. With no
        # fronts the output stays byte-identical to single-process.
        supervisor = getattr(node, "serving_front", None)
        front_folded = supervisor.front_folded() if supervisor else {}
        if fmt == "json":
            stacks = [{"stack": line.split(";"), "count": count}
                      for line, count in sampler.folded(
                          trace_id=trace_id, top=top, pool=pool)]
            if supervisor is not None:
                for s in stacks:
                    s["stack"].insert(0, sampler.role)
                for role, folded in front_folded.items():
                    for line in folded.splitlines():
                        stack, _, count = line.rpartition(" ")
                        if stack and count.isdigit():
                            stacks.append(
                                {"stack": [role] + stack.split(";"),
                                 "count": int(count)})
            return 200, {"enabled": sampler.running,
                         **sampler.stats(), "stacks": stacks}
        if not sampler.running and not sampler.samples_total \
                and not front_folded:
            return 200, {"enabled": False,
                         "reason": "search.profiler.enabled is false"}
        text = sampler.folded_text(trace_id=trace_id, top=top, pool=pool)
        if supervisor is not None:
            lines = [f"{sampler.role};{line}"
                     for line in text.splitlines()]
            for role, folded in front_folded.items():
                lines.extend(f"{role};{line}"
                             for line in folded.splitlines())
            text = "\n".join(lines) + ("\n" if lines else "")
        return 200, text

    def do_profile_timeline(req: RestRequest):
        # queue-depth / in-flight occupancy gauges sampled on the
        # profiler's tick — batching behavior over time, not totals
        sampler = node.profiler.sampler
        limit = int(req.params.get("limit", 0) or 0)
        return 200, {"enabled": sampler.running,
                     "interval_s": round(1.0 / sampler.hz, 4),
                     "points": sampler.timeline(limit=limit)}

    def do_device_start(req: RestRequest):
        name = req.params.get("name")
        if name is None and isinstance(req.body, dict):
            name = req.body.get("name")
        out = node.profiler.device.start(name)
        return (200 if out.get("started") else 409), out

    def do_device_stop(req: RestRequest):
        out = node.profiler.device.stop()
        return (200 if out.get("stopped") else 409), out

    def do_tpu_events(req: RestRequest):
        # the flight-recorder query surface: filtered view of the
        # bounded event ring (oldest-first; causal order by seq)
        from elasticsearch_tpu.common import events as ev
        rec = ev.get_recorder()
        if rec is None:
            return 200, {"enabled": False, "events": []}
        since = req.params.get("since_seq")
        out = rec.events(
            etype=req.params.get("type") or None,
            severity=req.params.get("severity") or None,
            since_seq=int(since) if since else None,
            trace_id=req.params.get("trace_id") or None,
            tenant=req.params.get("tenant") or None,
            limit=int(req.params.get("limit", 256) or 256))
        return 200, {"enabled": True, "last_seq": rec.last_seq,
                     "dropped": rec.c_dropped.count,
                     "total": len(out), "events": out}

    def do_tpu_incidents(req: RestRequest):
        from elasticsearch_tpu.common import events as ev
        rec = ev.get_recorder()
        if rec is None:
            return 200, {"enabled": False, "incidents": []}
        incidents = rec.list_incidents()
        return 200, {"enabled": True, "total": len(incidents),
                     "incidents": incidents}

    def do_tpu_incident_get(req: RestRequest):
        from elasticsearch_tpu.common import events as ev
        rec = ev.get_recorder()
        inc_id = req.param("incident_id")
        snap = rec.get_incident(inc_id) if rec is not None else None
        if snap is None:
            raise ResourceNotFoundException(
                f"no such incident [{inc_id}]")
        return 200, snap

    def do_prometheus(req: RestRequest):
        # text exposition (str payload → text/plain at the HTTP layer);
        # the overload-protection families
        # (es_tpu_indexing_pressure_*, es_tpu_search_backpressure_*)
        # scrape here, mirroring the `indexing_pressure` and
        # `search_backpressure` sections of _nodes/stats
        return 200, node.metrics.prometheus_text()

    controller.register("GET", "/_field_caps", do_field_caps)
    controller.register("POST", "/_field_caps", do_field_caps)
    controller.register("GET", "/{index}/_field_caps", do_field_caps)
    controller.register("POST", "/{index}/_field_caps", do_field_caps)
    controller.register("GET", "/{index}/_validate/query", do_validate)
    controller.register("POST", "/{index}/_validate/query", do_validate)
    controller.register("GET", "/_validate/query", do_validate)
    controller.register("POST", "/_validate/query", do_validate)
    controller.register("GET", "/{index}/_explain/{id}", do_explain)
    controller.register("POST", "/{index}/_explain/{id}", do_explain)
    controller.register("GET", "/{index}/_termvectors/{id}",
                        do_termvectors)
    controller.register("POST", "/{index}/_termvectors/{id}",
                        do_termvectors)
    controller.register("GET", "/_nodes/hot_threads", do_hot_threads)
    controller.register("GET", "/_nodes/{node_id}/hot_threads",
                        do_hot_threads)
    controller.register("GET", "/_cluster/allocation/explain",
                        do_alloc_explain)
    controller.register("POST", "/_cluster/allocation/explain",
                        do_alloc_explain)
    controller.register("GET", "/_tpu/stats", do_tpu_stats)
    controller.register("GET", "/_tpu/traces", do_tpu_traces)
    controller.register("GET", "/_tpu/events", do_tpu_events)
    controller.register("GET", "/_tpu/incidents", do_tpu_incidents)
    controller.register("GET", "/_tpu/incidents/{incident_id}",
                        do_tpu_incident_get)
    controller.register("GET", "/_tpu/profile/flamegraph",
                        do_profile_flamegraph)
    controller.register("GET", "/_tpu/profile/timeline",
                        do_profile_timeline)
    controller.register("POST", "/_tpu/profile/device/start",
                        do_device_start)
    controller.register("POST", "/_tpu/profile/device/stop",
                        do_device_stop)
    controller.register("GET", "/_prometheus/metrics", do_prometheus)
