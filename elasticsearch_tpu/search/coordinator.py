"""Search coordinator — query_then_fetch across shards.

Reference: `action/search/TransportSearchAction` +
`SearchPhaseController` (SURVEY.md §2.1#35, §3.3): resolve indices →
query phase on every shard → merge top-k (score desc, tie toward lower
shard ordinal then doc order) → fetch phase only on shards owning
winners → reduce aggs → one response. This module is the LOCAL-node
coordinator (all shards in-process); the mesh-distributed BM25 fast path
lives in parallel/distributed.py and federation over hosts arrives with
the transport layer.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import logging

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.errors import (CircuitBreakingException,
                                             EsRejectedExecutionException,
                                             IllegalArgumentException,
                                             IndexNotFoundException,
                                             SearchPhaseExecutionException,
                                             TaskCancelledException,
                                             shard_failure_entry)
from elasticsearch_tpu.indices.service import IndicesService
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.aggregations import (AggregatorFactories,
                                                   parse_aggregations)
from elasticsearch_tpu.search.query_phase import (ShardHit, execute_fetch,
                                                  execute_query, fault_check)

logger = logging.getLogger("elasticsearch_tpu.search.coordinator")

#: failures that must abort the whole request rather than degrade to a
#: per-shard failure: cancellation is the caller's decision, and breaker
#: / executor rejections must surface as 429s (reference: the breaker
#: trips BEFORE work is admitted, it is not a shard fault)
_NON_DEGRADABLE = (TaskCancelledException, CircuitBreakingException,
                   EsRejectedExecutionException)


def allow_partial_results(params: Optional[Dict[str, str]]) -> bool:
    """`allow_partial_search_results` query param (reference default:
    true — a search survives individual shard failures and reports them
    in `_shards.failures`)."""
    raw = (params or {}).get("allow_partial_search_results", "true")
    return str(raw).lower() not in ("false", "0", "no")


def check_shard_failures(failures: List[Dict[str, Any]], successful: int,
                         allow_partial: bool, phase: str = "query") -> None:
    """Reference AbstractSearchAsyncAction#onPhaseFailure semantics:
    every shard failing — or any shard failing when partial results are
    disallowed — raises SearchPhaseExecutionException (503) instead of
    returning a degraded 200."""
    if not failures:
        return
    if successful == 0:
        raise SearchPhaseExecutionException(phase, "all shards failed",
                                            failures)
    if not allow_partial:
        raise SearchPhaseExecutionException(
            phase, "Search rejected due to failed shards "
            "[allow_partial_search_results=false]", failures)


def _is_closed(entry) -> bool:
    """Closed-index check over both registry kinds: a local IndexService
    (`closed` flag) or cluster IndexMeta (`state` field)."""
    return (getattr(entry, "closed", False)
            or getattr(entry, "state", "open") == "close")


def resolve_targets(indices: IndicesService, expression: Optional[str]
                    ) -> Tuple[List[str], Dict[str, List[dict]]]:
    """Wildcard/CSV resolution over index AND alias names (reference:
    IndexNameExpressionResolver — no date math yet).

    → (index names, {index: [alias filter json, ...]}). An index reached
    directly (or through an unfiltered alias) in the same expression is
    unfiltered; multiple filtered aliases OR together. Closed indices:
    wildcard/_all expansion skips them (expand_wildcards=open default);
    naming one directly raises IndexClosedException (reference:
    IndicesOptions.strictExpandOpen)."""
    from elasticsearch_tpu.common.errors import IndexClosedException
    idx_names = sorted(indices.indices.keys())
    alias_map = getattr(indices, "aliases", {})
    alias_names = sorted(alias_map.keys())
    out: List[str] = []
    filters: Dict[str, List[dict]] = {}
    unfiltered: set = set()

    def closed(name: str) -> bool:
        return _is_closed(indices.indices.get(name))

    def add_index(name: str, filt: Optional[dict]) -> None:
        if name not in out:
            out.append(name)
        if filt is None:
            unfiltered.add(name)
            filters.pop(name, None)
        elif name not in unfiltered:
            filters.setdefault(name, []).append(filt)

    def add_part(part: str) -> None:
        if part in idx_names:
            if closed(part):
                raise IndexClosedException(f"closed index [{part}]")
            add_index(part, None)
            return
        if part in alias_names:
            for idx, props in sorted(alias_map[part].items()):
                if idx in indices.indices and not closed(idx):
                    add_index(idx, props.get("filter"))
            return
        raise IndexNotFoundException(f"no such index [{part}]")

    if expression in (None, "", "_all", "*"):
        for n in idx_names:
            if not closed(n):
                add_index(n, None)
        return out, filters
    for part in expression.split(","):
        part = part.strip()
        if not part:
            continue
        if "*" in part or "?" in part:
            for m in fnmatch.filter(idx_names, part):
                if not closed(m):
                    add_index(m, None)
            for m in fnmatch.filter(alias_names, part):
                add_part(m)
        else:
            add_part(part)
    return out, filters


def resolve_indices(indices: IndicesService,
                    expression: Optional[str]) -> List[str]:
    """Index-name resolution ignoring alias filters (admin APIs)."""
    return resolve_targets(indices, expression)[0]


def resolve_concrete_indices(indices: IndicesService,
                             expression: Optional[str]) -> List[str]:
    """Destructive admin APIs (delete index) must name CONCRETE indices
    — addressing one through an alias is rejected, never silently
    expanded onto the backing index (reference: DestructiveOperations +
    IndexNameExpressionResolver concrete-only resolution)."""
    alias_map = getattr(indices, "aliases", {})
    if expression:
        for part in expression.split(","):
            part = part.strip()
            if part in alias_map:
                raise IllegalArgumentException(
                    f"The provided expression [{part}] matches an alias; "
                    f"this operation requires concrete index names")
    names = sorted(indices.indices.keys())
    if expression in (None, "", "_all", "*"):
        return names
    out: List[str] = []
    for part in expression.split(","):
        part = part.strip()
        if not part:
            continue
        if "*" in part or "?" in part:
            out.extend(m for m in fnmatch.filter(names, part)
                       if m not in out)
        elif part not in names:
            raise IndexNotFoundException(f"no such index [{part}]")
        elif part not in out:
            out.append(part)
    return out


def with_alias_filters(query: dsl.QueryNode,
                       filts: Optional[List[dict]]) -> dsl.QueryNode:
    """Wrap the request query with the matched aliases' filters
    (reference: the alias filter joins the shard-level query as a
    FILTER clause; several filtered aliases OR together)."""
    if not filts:
        return query
    parsed = [dsl.parse_query(f) for f in filts]
    if len(parsed) == 1:
        filt: dsl.QueryNode = parsed[0]
    else:
        filt = dsl.BoolQuery(should=parsed, minimum_should_match=1)
    return dsl.BoolQuery(must=[query], filter=[filt])


def parse_search_body(body: Optional[Dict[str, Any]]):
    body = body or {}
    # unimplemented keys get a 400, never silently ignored: a
    # sorted/highlighted query must not return wrong results with a 200
    unsupported = set(body) & {"script_fields"}
    if unsupported:
        raise IllegalArgumentException(
            f"search body keys {sorted(unsupported)} are not supported "
            f"yet by this engine")
    unknown = set(body) - {"query", "aggs", "aggregations", "size", "from",
                           "_source", "min_score", "track_total_hits",
                           "sort", "search_after", "timeout", "pit",
                           "profile", "highlight", "suggest",
                           "version", "seq_no_primary_term",
                           "rescore", "collapse", "knn", "_knn_docs"}
    if unknown:
        raise IllegalArgumentException(
            f"unknown search body keys {sorted(unknown)}")
    if body.get("knn") is not None:
        from elasticsearch_tpu.search.knn import parse_knn
        parse_knn(body["knn"])  # validate at parse time (400s)
        if body.get("sort") is not None or body.get("collapse"):
            raise IllegalArgumentException(
                "[knn] cannot be combined with [sort]/[collapse]: knn "
                "results are relevance-ranked")
    query = dsl.parse_query(body.get("query") or {"match_all": {}})
    aggs_spec = body.get("aggs") or body.get("aggregations")
    aggs = parse_aggregations(aggs_spec) if aggs_spec else None
    if body.get("rescore") is not None:
        from elasticsearch_tpu.search.rescore import parse_rescore
        parse_rescore(body["rescore"])  # validate at parse time (400s)
    if body.get("collapse") is not None:
        spec = body["collapse"]
        if not isinstance(spec, dict) or not spec.get("field"):
            raise IllegalArgumentException("[collapse] requires [field]")
        if spec.get("inner_hits") is not None:
            raise IllegalArgumentException(
                "[collapse] inner_hits is not supported yet")
        if body.get("sort") is not None or body.get("rescore") is not None:
            # keep the supported surface honest: collapse composes with
            # relevance ranking only for now
            raise IllegalArgumentException(
                "[collapse] cannot be combined with [sort]/[rescore] yet")
    return query, aggs, body


def encode_knn_docs(knn_wrap: Dict[Tuple[str, int], List[Tuple[Any, float]]]
                    ) -> Dict[str, Any]:
    """Per-shard knn winners → JSON-serializable `_knn_docs` body key
    (the wire form route_search ships to shard groups; reference: the
    coordinator's per-shard ScoreDoc lists after the knn phase)."""
    out: Dict[str, Any] = {}
    for (name, shard_num), sets in knn_wrap.items():
        entry = []
        for seg_map, boost in sets:
            entry.append({
                "boost": boost,
                "segments": {seg: [list(map(int, ords)),
                                   list(map(float, scores))]
                             for seg, (ords, scores) in seg_map.items()}})
        out[f"{name}#{shard_num}"] = entry
    return out


def decode_knn_docs(encoded: Dict[str, Any]
                    ) -> Dict[Tuple[str, int], List[Tuple[Any, float]]]:
    import numpy as np
    out: Dict[Tuple[str, int], List[Tuple[Any, float]]] = {}
    for key, sets in encoded.items():
        name, _, shard_s = key.rpartition("#")
        decoded = []
        for entry in sets:
            seg_map = {
                seg: (np.asarray(ords, dtype=np.int64),
                      np.asarray(scores, dtype=np.float32))
                for seg, (ords, scores) in entry["segments"].items()}
            decoded.append((seg_map, float(entry["boost"])))
        out[(name, int(shard_s))] = decoded
    return out


def parse_timeout_s(body: Dict[str, Any],
                    params: Dict[str, str]) -> Optional[float]:
    """`timeout` body key / query param → seconds (reference: TimeValue
    grammar; a search past its timeout returns partial results with
    "timed_out": true)."""
    raw = params.get("timeout", body.get("timeout"))
    if raw is None:
        return None
    from elasticsearch_tpu.common.units import TimeValue
    seconds = TimeValue.parse(raw).seconds
    if seconds < 0:
        return None  # -1 is the reference's "no timeout" sentinel
    return seconds


def search(indices: IndicesService, index_expr: Optional[str],
           body: Optional[Dict[str, Any]],
           params: Optional[Dict[str, str]] = None,
           tpu_search=None, task=None,
           pinned: Optional[Dict[Tuple[str, int], Any]] = None,
           names_override: Optional[List[str]] = None) -> Dict[str, Any]:
    """pinned: (index, shard) → ShardReader snapshot (scroll/PIT
    contexts); when set the kernel fast path is skipped — resident packs
    track the LIVE readers, not the snapshot."""
    from elasticsearch_tpu.search.query_phase import SearchContext
    t0 = time.perf_counter()
    params = params or {}
    if names_override is not None:
        names, alias_filters = list(names_override), {}
    else:
        names, alias_filters = resolve_targets(indices, index_expr)
    # partial-mesh shed check: an index whose resident pack was shed
    # for N-1 HBM headroom answers a TYPED 503 + Retry-After (load
    # shedding, not failure) until a fuller mesh readmits the pack
    if tpu_search is not None:
        shed_info = getattr(tpu_search, "shed_info", None)
        if callable(shed_info):
            for name in names:
                info = shed_info(name)
                if info:
                    from elasticsearch_tpu.common.errors import \
                        PackShedException
                    raise PackShedException(
                        f"index [{name}] shed from device residency "
                        f"during partial-mesh recovery; retry after "
                        f"capacity returns", index=name,
                        retry_after_s=float(
                            info.get("retry_after_s", 5.0)))
    query, aggs, body = parse_search_body(body)
    ctx = SearchContext(parse_timeout_s(body, params), task)
    size = int(params.get("size", body.get("size", 10)))
    from_ = int(params.get("from", body.get("from", 0)))
    min_score = body.get("min_score")
    source = body.get("_source", True)
    from elasticsearch_tpu.search import sort as sort_mod
    sort_specs = sort_mod.parse_sort(body.get("sort"))
    search_after = body.get("search_after")
    if search_after is not None and not sort_specs:
        raise IllegalArgumentException(
            "[search_after] requires a [sort] specification")
    highlight_spec = None
    fetch_source = source
    if body.get("highlight") is not None:
        from elasticsearch_tpu.search.highlight import HighlightSpec
        highlight_spec = HighlightSpec(body["highlight"])
        # the highlighter reads stored fields even when the response
        # suppresses _source
        fetch_source = True if source is False else source

    rescore_specs = None
    if body.get("rescore") is not None:
        from elasticsearch_tpu.search.rescore import parse_rescore
        rescore_specs = parse_rescore(body["rescore"])
    collapse_field = (body.get("collapse") or {}).get("field") \
        if body.get("collapse") else None

    # ---- knn candidate phase (reference: DfsQueryPhase for knn) ----
    # Resolve each knn clause to its GLOBAL top-k winners up front,
    # pinning one reader per shard so the query phase scores the same
    # point-in-time view the candidates came from.
    knn_wrap: Optional[Dict[Tuple[str, int], List[Tuple[Any, float]]]] = None
    knn_only = False
    if body.get("_knn_docs") is not None:
        # pre-resolved by a cluster-level coordinator (route_search)
        knn_wrap = decode_knn_docs(body["_knn_docs"])
        knn_only = "query" not in body
    elif body.get("knn") is not None:
        from elasticsearch_tpu.search import knn as knn_mod
        knn_specs = knn_mod.parse_knn(body["knn"])
        knn_only = "query" not in body
        if pinned is None:
            pinned = {}
            for name in names:
                svc = indices.index(name)
                for shard_num, shard in sorted(svc.shards.items()):
                    pinned[(name, shard_num)] = shard.acquire_searcher()
        knn_wrap = {}
        for spec in knn_specs:
            per_shard = {}
            for (name, shard_num), reader in pinned.items():
                if name not in names:
                    continue
                eff_spec = spec
                afilts = alias_filters.get(name)
                if afilts:
                    base_filt = spec.filter_query or dsl.MatchAllQuery()
                    eff_spec = dataclasses.replace(
                        spec, filter_query=with_alias_filters(
                            base_filt, afilts))
                per_shard[(name, shard_num)] = knn_mod.shard_candidates(
                    reader, eff_spec)
            grouped = knn_mod.global_topk(per_shard, spec.k)
            for shard_key, seg_map in grouped.items():
                knn_wrap.setdefault(shard_key, []).append(
                    (seg_map, spec.boost))

    # ---- TPU fast path: micro-batched kernel over resident packs ----
    # (the batched pipeline IS the serving path for the queries it can
    # express; everything else falls through to the planner below,
    # unchanged.)
    profile = bool(body.get("profile"))
    if (tpu_search is not None and aggs is None and pinned is None
            and knn_wrap is None  # knn runs the two-phase planner path
            and not alias_filters  # filtered aliases run the planner
            and not any(k in body for k in ("sort", "search_after",
                                            "highlight", "suggest",
                                            "rescore", "collapse"))):
        # `profile: true` stays ON the kernel path (it used to force the
        # reference scorer — profiling a path we never serve with): the
        # response gains a TPU section next to the usual shard tree.
        try:
            fast = _search_fast(indices, names, query, tpu_search,
                                size=size, from_=from_,
                                min_score=min_score,
                                source=source, t0=t0,
                                version=bool(body.get("version")),
                                seq_no_primary_term=bool(
                                    body.get("seq_no_primary_term")),
                                ctx=ctx, profile=profile)
        except _NON_DEGRADABLE:
            raise
        except Exception:  # noqa: BLE001 — degrade to the planner path
            # a kernel-path fault must not kill the request: the planner
            # below re-runs it with per-shard failure capture
            logger.warning("kernel fast path failed; falling back to "
                           "the planner", exc_info=True)
            fast = None
        if fast is not None:
            # N-1 serving: even kernel-served answers carry the
            # structured degraded reason while the mesh is partial
            _stamp_degraded(fast, tpu_search, names)
            return fast

    # ---- query phase: every shard of every target index ----
    # each shard executes under failure capture (reference:
    # AbstractSearchAsyncAction#onShardFailure) — one copy throwing
    # degrades to a `_shards.failures[]` entry, never a lost request
    shard_results = []   # (index_name, shard_num, reader, QuerySearchResult)
    failures: List[Dict[str, Any]] = []
    allow_partial = allow_partial_results(params)
    total = 0
    timed_out = False
    skipped = 0
    if pinned is not None:
        # scroll/PIT accounting is over the SNAPSHOT's shards: copies
        # that left the registry since the context opened are not
        # "expected", copies missing from the snapshot are failures
        name_set = set(names)
        n_shards_expected = sum(1 for (n, _s) in pinned if n in name_set)
    else:
        n_shards_expected = sum(len(indices.index(n).shards)
                                for n in names)
    query_nanos: Dict[Tuple[str, int], int] = {}
    from elasticsearch_tpu.search.can_match import can_match
    for name in names:
        svc = indices.index(name)
        eff_query = with_alias_filters(query, alias_filters.get(name))
        for shard_num, shard in sorted(svc.shards.items()):
            if ctx.should_stop():
                timed_out = True
                break
            if pinned is not None:
                reader = pinned.get((name, shard_num))
                if reader is None:
                    continue  # shard not part of the pinned snapshot
            try:
                fault_check(name, shard_num, "query")
                if pinned is None:
                    reader = shard.acquire_searcher()
                if knn_wrap is not None:
                    # union the shard's pinned knn winners with the text
                    # query (None base when the request had knn only)
                    sets = knn_wrap.get((name, shard_num), [])
                    if knn_only and not sets:
                        skipped += 1  # nothing can match on this shard
                        continue
                    from elasticsearch_tpu.search.knn import wrap_query
                    shard_query = wrap_query(
                        None if knn_only else eff_query, sets)
                else:
                    shard_query = eff_query
                    if not can_match(reader, eff_query, svc.mapper):
                        skipped += 1  # disjoint range stats: skip
                        continue
                q0 = time.perf_counter()
                # the rescore window may exceed the response window
                k_shard = size + from_
                if rescore_specs:
                    k_shard = max(k_shard,
                                  max(s.window_size
                                      for s in rescore_specs))
                if collapse_field:
                    # exact grouped top-N per shard (no candidate-depth
                    # cap; a dominating key can't starve later groups)
                    from elasticsearch_tpu.search.collapse import \
                        collapse_top_groups
                    from elasticsearch_tpu.search.query_phase import \
                        QuerySearchResult
                    pairs, total_sh = collapse_top_groups(
                        reader, shard_query, collapse_field, size + from_)
                    res = QuerySearchResult(
                        [h for h, _ in pairs], total_sh,
                        pairs[0][0].score if pairs else None)
                    if aggs is not None:
                        res.aggregations = execute_query(
                            reader, shard_query, size=0, aggs=aggs,
                            ctx=ctx).aggregations
                else:
                    res = execute_query(reader, shard_query, size=k_shard,
                                        from_=0,
                                        min_score=min_score, aggs=aggs,
                                        sort_specs=sort_specs or None,
                                        search_after=search_after,
                                        ctx=ctx)
                if rescore_specs:
                    from elasticsearch_tpu.search.rescore import \
                        rescore_shard_hits
                    res.hits = rescore_shard_hits(reader, res.hits,
                                                  rescore_specs)
            except _NON_DEGRADABLE:
                raise
            except Exception as e:  # noqa: BLE001 — per-shard capture
                logger.debug("shard [%s][%d] query phase failed",
                             name, shard_num, exc_info=True)
                indices.count_search_failure(name, shard_num)
                tracing.add_event("shard.query_failed", index=name,
                                  shard=shard_num,
                                  error=f"{type(e).__name__}: {e}")
                failures.append(shard_failure_entry(name, shard_num, e))
                continue
            elapsed = time.perf_counter() - q0
            query_nanos[(name, shard_num)] = int(elapsed * 1e9)
            tracing.record_stage("shard.query", elapsed, index=name,
                                 shard=shard_num)
            if svc.search_slowlog.enabled:
                svc.search_slowlog.maybe_log(elapsed, shard_num,
                                             source=body,
                                             total_hits=res.total_hits)
            timed_out = timed_out or res.timed_out
            shard_results.append((name, shard_num, reader, res))
            total += res.total_hits
        if timed_out:
            break
    check_shard_failures(failures, len(shard_results) + skipped,
                         allow_partial, "query")

    # ---- merge top-k: by sort key when sorting, else score desc; ties
    # toward lower index/shard order then rank (reference merge order) ----
    merged: List[Tuple[Any, int, int, ShardHit]] = []
    for si, (name, shard_num, _reader, res) in enumerate(shard_results):
        for rank, hit in enumerate(res.hits):
            if sort_specs:
                key = sort_mod.sort_key(sort_specs, hit.sort_values or [])
            else:
                key = -hit.score
            merged.append((key, si, rank, hit))
    merged.sort(key=lambda t: (t[0], t[1], t[2]))
    if collapse_field:
        # field collapsing (reference: CollapseBuilder): keep the best
        # hit per key walking the merged ranking; missing-key docs are
        # not collapsed together
        seen_keys = set()
        collapsed = []
        hit_keys: Dict[int, Any] = {}
        for entry in merged:
            _, si, _, hit = entry
            reader = shard_results[si][2]
            key = _collapse_key(reader, hit, collapse_field)
            if key is not None:
                if key in seen_keys:
                    continue
                seen_keys.add(key)
            hit_keys[id(hit)] = key
            collapsed.append(entry)
            if len(collapsed) >= from_ + size:
                break
        window = collapsed[from_: from_ + size]
    else:
        window = merged[from_: from_ + size]

    # ---- fetch phase: group winners by shard ----
    by_shard: Dict[int, List[ShardHit]] = {}
    for _, si, _, hit in window:
        by_shard.setdefault(si, []).append(hit)
    fetched: Dict[Tuple[int, str], Dict[str, Any]] = {}
    want_version = bool(body.get("version"))
    want_seqno = bool(body.get("seq_no_primary_term"))
    fetch_nanos: Dict[Tuple[str, int], int] = {}
    fetch_failed: set = set()
    for si, hits in by_shard.items():
        # fetch against the SAME reader the query phase scored on —
        # a refresh in between must not remap doc ordinals
        name, shard_num, reader, _ = shard_results[si]
        f0 = time.perf_counter()
        try:
            fault_check(name, shard_num, "fetch")
            for hit, doc in zip(hits, execute_fetch(
                    reader, hits, fetch_source, version=want_version,
                    seq_no_primary_term=want_seqno)):
                doc["_index"] = name
                if highlight_spec is not None:
                    from elasticsearch_tpu.search.highlight import \
                        build_highlights
                    # highlight the REQUEST query only — alias filters
                    # select docs, they are not something the user
                    # searched
                    hl = build_highlights(query, doc.get("_source"),
                                          highlight_spec)
                    if hl:
                        doc["highlight"] = hl
                    if source is False:
                        doc.pop("_source", None)
                fetched[(si, hit.doc_id)] = doc
        except _NON_DEGRADABLE:
            raise
        except Exception as e:  # noqa: BLE001 — per-shard capture
            logger.debug("shard [%s][%d] fetch phase failed",
                         name, shard_num, exc_info=True)
            indices.count_search_failure(name, shard_num)
            tracing.add_event("shard.fetch_failed", index=name,
                              shard=shard_num,
                              error=f"{type(e).__name__}: {e}")
            failures.append(shard_failure_entry(name, shard_num, e))
            fetch_failed.add(si)
            fetched = {k: v for k, v in fetched.items() if k[0] != si}
            continue
        f_elapsed = time.perf_counter() - f0
        fetch_nanos[(name, shard_num)] = int(f_elapsed * 1e9)
        tracing.record_stage("shard.fetch", f_elapsed, index=name,
                             shard=shard_num)
    if fetch_failed:
        # a shard that lost its fetch phase contributes NO hits and
        # counts failed, even though its query phase ran
        window = [e for e in window if e[1] not in fetch_failed]
        check_shard_failures(
            failures, len(shard_results) - len(fetch_failed) + skipped,
            allow_partial, "fetch")
    hits_json = []
    for _key, si, _, hit in window:
        doc = fetched.get((si, hit.doc_id), {"_id": hit.doc_id})
        doc["_score"] = None if (sort_specs and hit.sort_values) else hit.score
        if hit.sort_values is not None:
            doc["sort"] = hit.sort_values
        if collapse_field:
            key = hit_keys.get(id(hit))
            if key is not None:
                doc["fields"] = {collapse_field: [key]}
        hits_json.append(doc)

    if sort_specs:
        # max_score is null under field sort (reference behavior)
        only_score = all(s.field == "_score" for s in sort_specs)
        max_score = (max((h.score for _, _, _, h in merged), default=None)
                     if only_score else None)
        if only_score:
            for doc, (_, _, _, hit) in zip(hits_json, window):
                doc["_score"] = hit.score
    else:
        max_score = -merged[0][0] if merged else None
    shards_json: Dict[str, Any] = {
        "total": n_shards_expected,
        "successful": len(shard_results) - len(fetch_failed) + skipped,
        "skipped": skipped,
        "failed": len(failures)}
    if failures:
        shards_json["failures"] = failures
    out: Dict[str, Any] = {
        "took": int((time.perf_counter() - t0) * 1000),
        "timed_out": timed_out,
        # total reflects every targeted shard even when the deadline
        # stopped the scan early (successful = actually visited; skipped
        # shards count as successful, reference can_match semantics)
        "_shards": shards_json,
        "hits": {"total": {"value": total,
                           "relation": "gte" if timed_out else "eq"},
                 "max_score": max_score,
                 "hits": hits_json},
    }

    # ---- agg reduce across shards (+ pipeline aggs on the final
    # reduced tree) ----
    if aggs:
        from elasticsearch_tpu.search.aggregations import build_response
        parts = [res.aggregations for _, _, _, res in shard_results
                 if res.aggregations is not None]
        reduced = AggregatorFactories.reduce(parts) if parts else aggs.empty()
        out["aggregations"] = build_response(aggs, reduced)

    if profile:
        out["profile"] = {"shards": build_profile(
            query, shard_results, query_nanos, fetch_nanos)}
    if body.get("suggest") is not None:
        from elasticsearch_tpu.search.suggest import run_suggest
        out["suggest"] = run_suggest(indices, names, body["suggest"])
    _stamp_degraded(out, tpu_search, names)
    return out


def _stamp_degraded(out: Dict[str, Any], tpu_search,
                    names: Optional[List[str]] = None) -> None:
    """Mark answers produced while the kernel path is degraded —
    batcher down/recovering (planner served this) or serving on a
    partial mesh (N-1 capacity) — with a structured reason clients
    can type against (reference: a yellow cluster keeps answering,
    and says so). A target index whose pack is being served by a
    surviving placement replica group carries the more specific
    `failed_over` reason — degraded but ANSWERED, the opposite of
    `shed` (which never reaches here: shed indexes 503 up front)."""
    if tpu_search is None:
        return
    info = None
    if names:
        failover_info = getattr(tpu_search, "failover_info", None)
        if callable(failover_info):
            for name in names:
                fo = failover_info(name)
                if fo:
                    info = {"reason": "failed_over",
                            "index": fo.get("index"),
                            "from_group": fo.get("from_group"),
                            "to_group": fo.get("to_group")}
                    break
    if info is None:
        info = getattr(tpu_search, "degraded_info", None)
    if info is None and getattr(tpu_search, "degraded_active", False):
        info = {"reason": "recovering"}
    if info:
        out["degraded"] = True
        out["degraded_reason"] = dict(info)


def _collapse_key(reader, hit, field: str):
    """The collapse key of one hit: first doc value of `field` (None =
    missing → the hit is not collapsed with anything)."""
    for v in reader.views:
        if v.segment.name == hit.ref.segment:
            col = v.segment.doc_values.get(field)
            if col is None:
                return None
            raw = col.values[hit.ref.ord]
            if col.kind == "ord":
                return None if raw < 0 else col.ord_terms[int(raw)]
            from elasticsearch_tpu.index.segment import MISSING_I64
            if col.kind == "i64":
                return None if raw == MISSING_I64 else int(raw)
            import math
            return None if math.isnan(raw) else float(raw)
    return None


def build_profile(query, shard_results, query_nanos, fetch_nanos
                  ) -> List[Dict[str, Any]]:
    """Reference-shaped per-shard profile section (search/profile/**):
    one entry per shard with the query tree timing and the fetch phase.
    The dense-mask engine runs the whole query as one kernel program per
    segment, so the breakdown reports that single executed node."""
    shards = []
    for name, shard_num, _reader, res in shard_results:
        qn = query_nanos.get((name, shard_num), 0)
        shards.append({
            "id": f"[{name}][{shard_num}]",
            "searches": [{
                "query": [{
                    "type": type(query).__name__,
                    "description": query.query_name(),
                    "time_in_nanos": qn,
                    "breakdown": {
                        "score": qn, "build_scorer": 0,
                        "create_weight": 0, "next_doc": 0, "advance": 0,
                        "match": 0,
                    },
                }],
                "rewrite_time": 0,
                "collector": [{
                    "name": "DenseMaskTopK",
                    "reason": "search_top_hits",
                    "time_in_nanos": qn,
                }],
            }],
            "aggregations": [],
            "fetch": {
                "type": "fetch",
                "description": "",
                "time_in_nanos": fetch_nanos.get((name, shard_num), 0),
            },
        })
    return shards


def _tpu_profile_section(tpu_search, sink: Dict[str, Any]
                         ) -> Dict[str, Any]:
    """The kernel-side profile story for one (index, query): what
    try_search measured for THIS query (variant, plan-cache outcome,
    host stage millis incl. the batch_wait split) reconciled with the
    service-wide device-stage distributions from StageTimes (per-query
    device time is not separable inside a shared train — the recent
    ring percentiles are the honest view)."""
    out = dict(sink)
    stages = getattr(tpu_search, "stages", None)
    if stages is not None:
        snap = stages.snapshot()
        out["device_stages"] = {
            name: st for name, st in snap.items()
            if "device_wait" in name or name == "batch_decode"}
    return out


def build_kernel_profile_shard(query, name: str, elapsed_s: float,
                               tpu: Dict[str, Any]) -> Dict[str, Any]:
    """One profile-tree shard entry for the kernel fast path, shaped
    like the planner's `build_profile` entries so tooling that walks
    `profile.shards` keeps working, plus the TPU section under "tpu"."""
    qn = int(elapsed_s * 1e9)
    return {
        "id": f"[{name}][kernel]",
        "searches": [{
            "query": [{
                "type": type(query).__name__,
                "description": query.query_name(),
                "time_in_nanos": qn,
                "breakdown": {"score": qn, "build_scorer": 0,
                              "next_doc": 0},
            }],
            "rewrite_time": 0,
            "collector": [{
                "name": "TpuKernelTopK",
                "reason": "search_top_hits",
                "time_in_nanos": qn,
            }],
        }],
        "aggregations": [],
        "fetch": {"type": "fetch", "description": "", "time_in_nanos": 0},
        "tpu": tpu,
    }


def _search_fast(indices: IndicesService, names: List[str],
                 query: dsl.QueryNode, tpu_search, *, size: int, from_: int,
                 min_score, source, t0: float,
                 version: bool = False,
                 seq_no_primary_term: bool = False,
                 ctx=None, profile: bool = False
                 ) -> Optional[Dict[str, Any]]:
    """Kernel-path query phase + columnar response assembly. Returns None
    when any target index's query can't lower (the whole request then
    runs on the planner so merge semantics stay uniform).

    Hit assembly is vectorized, because a per-hit object costs the
    request thread Python under the interpreter lock at every hit of a
    1000-hit window: external ids resolve via one fancy-index over the
    pack's id table, stored fields read straight off the pinned segments
    — no per-hit ShardHit/fetch-phase objects on the hot path."""
    import numpy as np

    k = from_ + size
    if k <= 0:
        return None
    if min_score is not None:
        # the kernel path counts totals before min_score filtering; the
        # planner applies it to the match set — decline so hits.total is
        # consistent across paths (ADVICE r2 low #3)
        return None
    per_index = []
    profile_entries: List[Dict[str, Any]] = []
    n_shards_total = 0
    for name in names:
        svc = indices.index(name)
        n_shards_total += len(svc.shards)
        q0 = time.perf_counter()
        sink: Optional[Dict[str, Any]] = {} if profile else None
        res = tpu_search.try_search(
            svc, query, k=k,
            timeout_s=ctx.remaining_s() if ctx is not None else None,
            profile_sink=sink)
        if res is None:
            return None
        q_elapsed = time.perf_counter() - q0
        tracing.record_stage("kernel.search", q_elapsed, index=name)
        if svc.search_slowlog.enabled:
            svc.search_slowlog.maybe_log(
                q_elapsed, "kernel",
                source={"query": query.query_name()},
                total_hits=res.total_hits)
        if profile:
            profile_entries.append(build_kernel_profile_shard(
                query, name, q_elapsed, _tpu_profile_section(
                    tpu_search, sink or {})))
        per_index.append((name, svc, res))

    t_asm = time.perf_counter()
    total = sum(r.total_hits for _, _, r in per_index)
    relation = ("gte" if any(r.total_relation == "gte"
                             for _, _, r in per_index) else "eq")
    if len(per_index) == 1:
        # single-index (the dominant case): the kernel result is already
        # merged best-first — the response window is a pair of array
        # slices, no merge pass at all. The hits block stays COLUMNAR
        # (a lazy ColumnarHits view): the REST layer serializes it
        # straight from the arrays, and no per-hit dict exists unless an
        # in-process consumer actually indexes into it.
        from elasticsearch_tpu.search.serializer import ColumnarHits
        name, svc, res = per_index[0]
        scores = res.scores[from_: from_ + size]
        rows = res.rows[from_: from_ + size]
        ords = res.ords[from_: from_ + size]
        if res.resident is None or len(scores) == 0:
            hits_json: Any = []
        else:
            hits_json = ColumnarHits(name, res.resident, scores, rows,
                                     ords, source, version,
                                     seq_no_primary_term)
        max_score = float(res.scores[0]) if len(res.scores) else None
    else:
        # cross-index merge: (score desc, index order, kernel rank) — the
        # same tie order as the planner path's merge, one lexsort
        all_scores = np.concatenate([r.scores for _, _, r in per_index]) \
            if per_index else np.empty(0, dtype=np.float32)
        tags = np.concatenate([np.full(len(r.scores), ii, dtype=np.int32)
                               for ii, (_, _, r) in enumerate(per_index)])
        ranks = np.concatenate([np.arange(len(r.scores), dtype=np.int32)
                                for _, _, r in per_index])
        order = np.lexsort((ranks, tags, -all_scores))
        window = order[from_: from_ + size]
        # assemble per index in one batched call each, then restore the
        # merged order (per-hit 1-element assembly re-creates the python
        # overhead this path removes)
        win_tags = tags[window]
        win_ranks = ranks[window]
        assembled: Dict[int, List[Dict[str, Any]]] = {}
        for ii, (name, svc, res) in enumerate(per_index):
            sel = win_ranks[win_tags == ii]
            if len(sel):
                assembled[ii] = _assemble_hits(
                    name, res.resident, res.scores[sel], res.rows[sel],
                    res.ords[sel], source, version, seq_no_primary_term)
        cursors = {ii: 0 for ii in assembled}
        merged: List[Dict[str, Any]] = []
        for ii in win_tags.tolist():
            merged.append(assembled[ii][cursors[ii]])
            cursors[ii] += 1
        # merged hits are materialized dicts, but their serialization
        # still batches through the response splicer (SplicedHits wraps,
        # dumps_response splices)
        from elasticsearch_tpu.search.serializer import SplicedHits
        hits_json = SplicedHits(merged)
        max_score = float(all_scores[order[0]]) if len(order) else None
    stages = getattr(tpu_search, "stages", None)
    if stages is not None:
        stages.add("assemble", time.perf_counter() - t_asm)
    out = {
        "took": int((time.perf_counter() - t0) * 1000),
        "timed_out": False,
        "_shards": {"total": n_shards_total, "successful": n_shards_total,
                    "skipped": 0, "failed": 0},
        "hits": {"total": {"value": total, "relation": relation},
                 "max_score": max_score,
                 "hits": hits_json},
    }
    if profile:
        out["profile"] = {
            "shards": profile_entries,
            "tpu": [e["tpu"] for e in profile_entries],
        }
    return out


def _assemble_hits(name: str, resident, scores, rows, ords, source,
                   version: bool, seq_no_primary_term: bool
                   ) -> List[Dict[str, Any]]:
    """Columnar window → response hit dicts. ids via one fancy-index;
    stored fields (when requested) read directly from the pinned
    segments the pack was scored against (same snapshot contract as the
    fetch phase). Materialized form — callers that mutate hits (the
    shard-group path tags `__shard`) or ship them over transport use
    this; the local REST fast path uses the lazy ColumnarHits view."""
    from elasticsearch_tpu.search.serializer import assemble_hits_list
    return assemble_hits_list(name, resident, scores, rows, ords, source,
                              version, seq_no_primary_term)


# ----------------------------------------------------------------------
# cross-node query_then_fetch (reference: the shard-level
# SearchTransportService hops — query + fetch executed on the node that
# owns each shard, merged by the coordinating node, SURVEY.md §3.3)
# ----------------------------------------------------------------------

def search_shard_group(indices: IndicesService,
                       targets: List[Tuple[str, int]],
                       body: Optional[Dict[str, Any]],
                       params: Optional[Dict[str, str]] = None,
                       tpu_search=None,
                       index_filters: Optional[Dict[str, List[dict]]]
                       = None) -> Dict[str, Any]:
    """Execute the query phase (+ eager fetch of the local window) over
    an explicit list of LOCAL (index, shard) targets, returning a
    JSON-serializable partial result the coordinating node merges with
    `merge_group_responses`. Aggregation partials travel as a pickled
    blob — inter-node RPC is a trusted channel exactly like the
    reference's native transport serialization."""
    from elasticsearch_tpu.search.query_phase import SearchContext
    params = params or {}
    query, aggs, body = parse_search_body(body or {})
    # the timeout travels with the body; each node enforces it locally
    # (coordinator-side cancellation bans are not propagated yet)
    ctx = SearchContext(parse_timeout_s(body, params))
    size = int(params.get("size", body.get("size", 10)))
    from_ = int(params.get("from", body.get("from", 0)))
    k = size + from_
    min_score = body.get("min_score")
    source = body.get("_source", True)
    from elasticsearch_tpu.search import sort as sort_mod
    sort_specs = sort_mod.parse_sort(body.get("sort"))
    search_after = body.get("search_after")
    want_version = bool(body.get("version"))
    want_seqno = bool(body.get("seq_no_primary_term"))
    highlight_spec = None
    fetch_source = source
    if body.get("highlight") is not None:
        from elasticsearch_tpu.search.highlight import HighlightSpec
        highlight_spec = HighlightSpec(body["highlight"])
        fetch_source = True if source is False else source

    by_index: Dict[str, List[int]] = {}
    for name, shard_num in targets:
        by_index.setdefault(name, []).append(shard_num)

    # knn winners resolved by route_search's candidate phase arrive as
    # the _knn_docs body key; wrap per shard exactly like search()
    group_knn: Optional[Dict[Tuple[str, int], List[Tuple[Any, float]]]] = None
    group_knn_only = False
    if body.get("_knn_docs") is not None:
        group_knn = decode_knn_docs(body["_knn_docs"])
        group_knn_only = "query" not in body

    # TPU fast path per index when the group covers every local shard of
    # that index (cluster allocation puts whole local shard sets in one
    # group, so this is the common case)
    shard_results = []
    agg_parts = []   # one partial per executed shard, hits or not
    group_failures: List[Dict[str, Any]] = []
    group_skipped = 0
    group_query_nanos: Dict[Tuple[str, int], int] = {}
    group_fetch_nanos: Dict[Tuple[str, int], int] = {}
    group_profile_entries: List[Tuple] = []
    fast_profile_entries: List[Dict[str, Any]] = []
    total = 0
    relation = "eq"
    for name, shard_nums in sorted(by_index.items()):
        svc = indices.index(name)
        eff_query = with_alias_filters(
            query, (index_filters or {}).get(name))
        used_fast = False
        if (tpu_search is not None and aggs is None and not sort_specs
                and search_after is None and k > 0 and min_score is None
                and group_knn is None
                and not body.get("rescore") and not body.get("collapse")
                and not (index_filters or {}).get(name)
                and set(shard_nums) == set(svc.shards.keys())):
            group_profile = bool(body.get("profile"))
            sink: Optional[Dict[str, Any]] = {} if group_profile else None
            q_fast0 = time.perf_counter()
            try:
                res = tpu_search.try_search(svc, query, k=k,
                                            timeout_s=ctx.remaining_s(),
                                            profile_sink=sink)
            except _NON_DEGRADABLE:
                raise
            except Exception:  # noqa: BLE001 — degrade to planner
                logger.warning("group kernel path failed; falling back "
                               "to the planner", exc_info=True)
                res = None
            if res is not None:
                used_fast = True
                if group_profile:
                    fast_profile_entries.append(build_kernel_profile_shard(
                        query, name, time.perf_counter() - q_fast0,
                        _tpu_profile_section(tpu_search, sink or {})))
                total += res.total_hits
                if getattr(res, "total_relation", "eq") == "gte":
                    relation = "gte"
                docs = _assemble_hits(name, res.resident, res.scores,
                                      res.rows, res.ords, source,
                                      want_version, want_seqno)
                shard_nums = (res.resident.row_shard[res.rows].tolist()
                              if docs else [])
                for rank, (doc, sn) in enumerate(zip(docs, shard_nums)):
                    doc["__shard"] = sn
                    shard_results.append(("__fast__", name, sn, rank, doc))
        if not used_fast:
            from elasticsearch_tpu.search.can_match import can_match
            group_rescore = None
            if body.get("rescore") is not None:
                from elasticsearch_tpu.search.rescore import parse_rescore
                group_rescore = parse_rescore(body["rescore"])
            group_collapse = (body.get("collapse") or {}).get("field") \
                if body.get("collapse") else None
            for shard_num in sorted(shard_nums):
                try:
                    fault_check(name, shard_num, "query")
                    shard = svc.shard(shard_num)
                    reader = shard.acquire_searcher()
                    if group_knn is not None:
                        sets = group_knn.get((name, shard_num), [])
                        if group_knn_only and not sets:
                            group_skipped += 1
                            continue
                        from elasticsearch_tpu.search.knn import \
                            wrap_query
                        shard_query = wrap_query(
                            None if group_knn_only else eff_query, sets)
                    else:
                        shard_query = eff_query
                        if not can_match(reader, eff_query, svc.mapper):
                            group_skipped += 1
                            continue
                    q0 = time.perf_counter()
                    k_shard = k
                    if group_rescore:
                        k_shard = max(k_shard, max(s.window_size
                                                   for s in group_rescore))
                    if group_collapse:
                        from elasticsearch_tpu.search.collapse import \
                            collapse_top_groups
                        from elasticsearch_tpu.search.query_phase import \
                            QuerySearchResult
                        pairs, total_sh = collapse_top_groups(
                            reader, shard_query, group_collapse, k)
                        res = QuerySearchResult(
                            [h for h, _ in pairs], total_sh,
                            pairs[0][0].score if pairs else None)
                        if aggs is not None:
                            res.aggregations = execute_query(
                                reader, shard_query, size=0, aggs=aggs,
                                ctx=ctx).aggregations
                    else:
                        res = execute_query(reader, shard_query,
                                            size=k_shard, from_=0,
                                            min_score=min_score,
                                            aggs=aggs,
                                            sort_specs=sort_specs or None,
                                            search_after=search_after,
                                            ctx=ctx)
                    if group_rescore:
                        from elasticsearch_tpu.search.rescore import \
                            rescore_shard_hits
                        res.hits = rescore_shard_hits(reader, res.hits,
                                                      group_rescore)
                    elapsed = time.perf_counter() - q0
                    fault_check(name, shard_num, "fetch")
                    f0 = time.perf_counter()
                    fetched = execute_fetch(reader, res.hits,
                                            fetch_source,
                                            version=want_version,
                                            seq_no_primary_term=want_seqno)
                except _NON_DEGRADABLE:
                    raise
                except Exception as e:  # noqa: BLE001 — captured per shard
                    logger.debug("group shard [%s][%d] failed",
                                 name, shard_num, exc_info=True)
                    indices.count_search_failure(name, shard_num)
                    tracing.add_event("shard.query_failed", index=name,
                                      shard=shard_num,
                                      error=f"{type(e).__name__}: {e}")
                    group_failures.append(
                        shard_failure_entry(name, shard_num, e))
                    continue
                group_query_nanos[(name, shard_num)] = int(elapsed * 1e9)
                tracing.record_stage("shard.query", elapsed, index=name,
                                     shard=shard_num)
                group_profile_entries.append((name, shard_num, None, res))
                if svc.search_slowlog.enabled:
                    svc.search_slowlog.maybe_log(
                        elapsed, shard_num, source=body,
                        total_hits=res.total_hits)
                total += res.total_hits
                if aggs is not None and res.aggregations is not None:
                    agg_parts.append(res.aggregations)
                group_fetch_nanos[(name, shard_num)] = int(
                    (time.perf_counter() - f0) * 1e9)
                for rank, (hit, doc) in enumerate(zip(res.hits, fetched)):
                    doc["_index"] = name
                    doc["_score"] = hit.score
                    if hit.sort_values is not None:
                        doc["sort"] = hit.sort_values
                    if group_collapse:
                        ck = _collapse_key(reader, hit, group_collapse)
                        if ck is not None:
                            doc["fields"] = {group_collapse: [ck]}
                    if highlight_spec is not None:
                        from elasticsearch_tpu.search.highlight import \
                            build_highlights
                        hl = build_highlights(query,
                                              doc.get("_source"),
                                              highlight_spec)
                        if hl:
                            doc["highlight"] = hl
                        if source is False:
                            doc.pop("_source", None)
                    doc["__shard"] = shard_num
                    shard_results.append((res, name, shard_num, rank, doc))

    # local pre-merge: keep only the node-level top-k (the coordinator
    # re-merges, so shipping more than k per node is pure waste)
    entries = []
    for res, name, shard_num, rank, doc in shard_results:
        if sort_specs:
            key = sort_mod.sort_key(sort_specs, doc.get("sort") or [])
        else:
            key = -(doc.get("_score") or 0.0)
        entries.append((key, name, shard_num, rank, doc))
    entries.sort(key=lambda t: t[:4])
    # under collapse, each shipped hit is already its shard's best per
    # key (collapse_top_groups), so k per node suffices
    hits = []
    for key, name, shard_num, rank, doc in entries[:k]:
        hits.append(doc)

    out: Dict[str, Any] = {
        "hits": hits, "total": total, "relation": relation,
        "timed_out": ctx.timed_out,
        "skipped": group_skipped,
        # shards counts only the copies that EXECUTED; failed copies
        # travel in "failures" so the coordinator can retry them on
        # another copy before counting them failed
        "shards": (len({(n, s) for n, s in targets})
                   - len(group_failures)),
        "max_score": (max((d.get("_score") or float("-inf")
                           for d in hits), default=None)
                      if not sort_specs and hits else None),
    }
    if group_failures:
        out["failures"] = group_failures
    if aggs:
        import base64
        import pickle
        out["aggs_blob"] = base64.b64encode(
            pickle.dumps(agg_parts)).decode("ascii")
    if body.get("profile"):
        out["profile_shards"] = build_profile(
            query, group_profile_entries, group_query_nanos,
            group_fetch_nanos) + fast_profile_entries
    if body.get("suggest") is not None:
        from elasticsearch_tpu.search.suggest import run_suggest
        # restrict to the group's ASSIGNED shards: unselected local
        # copies must not double-count in the cross-node merge
        out["suggest"] = run_suggest(
            indices, sorted(by_index.keys()), body["suggest"],
            shard_filter=by_index)
    return out


def merge_group_responses(groups: List[Dict[str, Any]],
                          body: Optional[Dict[str, Any]],
                          params: Optional[Dict[str, str]],
                          t0: float,
                          failed_shards: int = 0,
                          failures: Optional[List[Dict[str, Any]]] = None
                          ) -> Dict[str, Any]:
    """Coordinator-side reduce of `search_shard_group` partials into one
    reference-shaped _search response.

    `failures`: consolidated `_shards.failures[]` entries for copies
    that stayed failed AFTER the coordinator's failover attempts (the
    caller owns retry; this function only reports). `failed_shards`
    additionally counts failures with no entry (legacy callers)."""
    params = params or {}
    body = body or {}
    failures = list(failures or [])
    n_failed = failed_shards + len(failures)
    size = int(params.get("size", body.get("size", 10)))
    from_ = int(params.get("from", body.get("from", 0)))
    from elasticsearch_tpu.search import sort as sort_mod
    sort_specs = sort_mod.parse_sort(body.get("sort"))

    merged = []
    total = 0
    relation = "eq"
    n_shards = n_failed
    n_skipped = 0
    timed_out = False
    for gi, g in enumerate(groups):
        total += g["total"]
        n_shards += g.get("shards", 0)
        n_skipped += g.get("skipped", 0)
        if g.get("timed_out"):
            timed_out = True
        if g.get("relation") == "gte":
            relation = "gte"
        for rank, doc in enumerate(g["hits"]):
            if sort_specs:
                key = sort_mod.sort_key(sort_specs, doc.get("sort") or [])
            else:
                key = -(doc.get("_score") or 0.0)
            merged.append((key, doc.get("_index", ""),
                           doc.pop("__shard", 0), rank, doc))
    merged.sort(key=lambda t: t[:4])
    collapse_field = (body.get("collapse") or {}).get("field") \
        if body.get("collapse") else None
    if collapse_field:
        seen_keys = set()
        picked = []
        for entry in merged:
            doc = entry[4]
            key_vals = (doc.get("fields") or {}).get(collapse_field)
            if key_vals:
                if key_vals[0] in seen_keys:
                    continue
                seen_keys.add(key_vals[0])
            picked.append(doc)
            if len(picked) >= from_ + size:
                break
        window = picked[from_: from_ + size]
    else:
        window = [doc for _, _, _, _, doc in merged[from_: from_ + size]]

    if sort_specs:
        only_score = all(s.field == "_score" for s in sort_specs)
        max_score = None
        if only_score and merged:
            max_score = max((d.get("_score") or float("-inf")
                             for *_id, d in merged), default=None)
        if not only_score:
            for doc in window:
                doc["_score"] = None
    else:
        max_score = max((g.get("max_score") for g in groups
                         if g.get("max_score") is not None),
                        default=None)

    shards_json: Dict[str, Any] = {"total": n_shards,
                                   "successful": n_shards - n_failed,
                                   "skipped": n_skipped,
                                   "failed": n_failed}
    if failures:
        shards_json["failures"] = failures
    out: Dict[str, Any] = {
        "took": int((time.perf_counter() - t0) * 1000),
        "timed_out": timed_out,
        "_shards": shards_json,
        "hits": {"total": {"value": total, "relation": relation},
                 "max_score": max_score,
                 "hits": window},
    }

    if body.get("suggest") is not None:
        from elasticsearch_tpu.search.suggest import (merge_suggest,
                                                      parse_suggest)
        specs = parse_suggest(body["suggest"])
        out["suggest"] = merge_suggest(
            specs, [g.get("suggest") for g in groups
                    if g.get("suggest") is not None])

    aggs_spec = body.get("aggs") or body.get("aggregations")
    if aggs_spec:
        import base64
        import pickle

        from elasticsearch_tpu.search.aggregations import build_response
        aggs = parse_aggregations(aggs_spec)
        parts = []
        for g in groups:
            blob = g.get("aggs_blob")
            if blob:
                parts.extend(pickle.loads(base64.b64decode(blob)))
        reduced = (AggregatorFactories.reduce(parts) if parts
                   else aggs.empty())
        out["aggregations"] = build_response(aggs, reduced)
    if body.get("profile"):
        shards = [s for g in groups for s in g.get("profile_shards", [])]
        out["profile"] = {"shards": shards}
        tpu = [s["tpu"] for s in shards if "tpu" in s]
        if tpu:
            out["profile"]["tpu"] = tpu
    return out


def count(indices: IndicesService, index_expr: Optional[str],
          body: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    names, alias_filters = resolve_targets(indices, index_expr)
    query = dsl.parse_query((body or {}).get("query") or {"match_all": {}})
    total = 0
    n_shards = 0
    for name in names:
        svc = indices.index(name)
        eff_query = with_alias_filters(query, alias_filters.get(name))
        for shard_num, shard in sorted(svc.shards.items()):
            reader = shard.acquire_searcher()
            res = execute_query(reader, eff_query, size=0)
            total += res.total_hits
            n_shards += 1
    return {"count": total,
            "_shards": {"total": n_shards, "successful": n_shards,
                        "skipped": 0, "failed": 0}}
