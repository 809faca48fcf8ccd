"""Standing check that the served BM25 path starts and answers on the chip.

Drives the main path once through the entry points a user calls —
`Node(...)`, `serve()`, REST `_bulk` → `_refresh` → `_forcemerge`,
`node.tpu_search.prewarm`, REST `_search` — at one chip's share of the
north-star deployment (BASELINE.json config 3: MS MARCO passage, 8.8M
docs, BM25 top-1000, v5e-8 → 8,847,360 / 8 = 1,105,920 docs per chip,
2 shards per chip), with default node settings, and fails if anything
was answered by a fallback that hides the device. The default run is half
that share (`reduced` in the result says so): from a cold compile cache
the full share took 968 s of the 1200 s a run may take, which is too
little room on a shared host; `--docs 1105920` runs it.

    python chip_smoke.py [--seed N] [--docs N] [--shards N]

`main()` refuses any backend but a TPU; nothing overrides that. The work
is `run(...)`, which takes sizes so tier-1 can rehearse it at toy size on
the CPU mesh (tests/test_chip_smoke.py). Every jax import lives inside a
function: fronts and merge-pool workers are `spawn` children that
re-import `__main__`, and a child that loads jax would ask for the chip
its parent holds.

Steps, each followed by the no-hidden-fallback check on `/_tpu/stats`:
  1. large index (raw pack: segments ≥ 65,536 docs): 64 OR `match`
     queries at size 1000 (→ _launch_pruned), 16 `operator: and`
     (→ _launch_exact);
  2. small index, first 32,768 docs in one shard (d_pad < 65,536 →
     compressed pack): 16 queries at size 10, 16 at size 1000;
  3. `_bulk` 2,000 new docs into the large index, `_refresh`, 8 queries:
     the refresh must ride a delta pack and an appended doc must come back.
Every response is compared with `ops/reference_impl.score_match_query`
run per statistics group (one per shard; after the append, one per
(pack, shard), which is how a delta pack scores until compaction folds
it): `hits.total` equal, scores within 1e-5 relative, ids equal position
by position except swaps between docs whose reference scores differ by
less than 1e-5 relative.

stdout carries one line, last: `{"ok": true, "device": {"platform": …,
"kind": …, "count": …}}`, the device as jax reports it, and nothing when
the run fails. Progress and the set-up facts go to stderr; the facts also
to `chiprun_out/chip_smoke_facts.json`.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: BASELINE.json config 3 (MS MARCO passage, 8,847,360 docs on a v5e-8)
#: cut to one chip, and the layout of BENCH_r05_scale.json (16 shards / 8)
SHARE_DOCS_PER_CHIP = 8_847_360 // 8
SHARDS_PER_CHIP = 2
#: the default run is half a share. From a cold compile cache the full
#: share ran in 968 s and half of it in 811-831 s on one chip (PERF.md
#: "Bring-up on the chip"); most of either is XLA compiling on a shared
#: host (552-713 s between runs), and a run may take 1200 s
DEFAULT_DOCS_PER_CHIP = SHARE_DOCS_PER_CHIP // 2
#: the only smaller size with a chip record (BENCH_r05.json, 4 shards);
#: below it a shard's segment drops under 65,536 docs and the large index
#: would no longer be a raw pack
FLOOR_DOCS = 262_144
SMALL_DOCS = 32_768
APPEND_DOCS = 2_000
REL_TOL = 1e-5
FIELD = "body"
#: git-ignored; what a chip-tool call writes here is brought back
FACTS_DIR = "chiprun_out"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SmokeFailure(Exception):
    """A requirement of the smoke did not hold."""


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# HTTP client side
# ---------------------------------------------------------------------------

def http_json(conn: http.client.HTTPConnection, method: str, path: str,
              body: Any = None) -> Any:
    """One request on a keep-alive connection → parsed JSON; any status
    but 200 fails the smoke."""
    if body is not None and not isinstance(body, (str, bytes)):
        body = json.dumps(body)
    conn.request(method, path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    require(resp.status == 200,
            f"{method} {path} → HTTP {resp.status}: {data[:400]!r}")
    return json.loads(data)


def run_clients(port: int, n_clients: int, jobs: Sequence[Any],
                work) -> List[Any]:
    """`work(conn, job)` for every job from `n_clients` threads, each on
    its own connection; results in job order. The first failure is
    re-raised on the caller's thread."""
    results: List[Any] = [None] * len(jobs)
    failures: List[BaseException] = []
    cursor = iter(range(len(jobs)))
    lock = threading.Lock()

    def client() -> None:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            while not failures:
                with lock:
                    i = next(cursor, None)
                if i is None:
                    return
                results[i] = work(conn, jobs[i])
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            failures.append(exc)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, name=f"smoke-client-{ci}")
               for ci in range(min(n_clients, max(1, len(jobs))))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        raise failures[0]
    return results


def bulk_index(port: int, index: str, docs: Sequence[Tuple[str, str]],
               clients: int, bulk_size: int = 4000) -> None:
    """(id, text) pairs through REST `_bulk`, NDJSON, a few concurrent
    clients: the standard load-driver shape."""
    def send(conn, chunk):
        lines = []
        for doc_id, text in chunk:
            lines.append(json.dumps({"index": {"_id": doc_id}}))
            lines.append(json.dumps({FIELD: text}))
        resp = http_json(conn, "POST", f"/{index}/_bulk",
                         "\n".join(lines) + "\n")
        require(not resp["errors"], f"_bulk into [{index}] had item "
                f"errors: {str(resp['items'][:2])[:400]}")

    chunks = [docs[i:i + bulk_size] for i in range(0, len(docs), bulk_size)]
    run_clients(port, clients, chunks, send)


def match_body(text: str, size: int, operator: str = "or") -> Dict[str, Any]:
    query: Any = text if operator == "or" else {"query": text,
                                                "operator": operator}
    return {"query": {"match": {FIELD: query}}, "size": size,
            "_source": False}


# ---------------------------------------------------------------------------
# reference: plain numpy BM25 per statistics group, merged by score
# ---------------------------------------------------------------------------

def reference_topk(groups: Sequence[Sequence[Any]], terms: Sequence[str],
                   k: int, require_all: bool
                   ) -> Tuple[int, List[str], Any]:
    """→ (total hits, ids, scores) best-first. `groups` are lists of
    segments that share statistics (idf, avgdl). The list runs past k
    through every doc within 10·REL_TOL of the k-th score, so a tie at
    the cut can be told from a wrong doc. `require_all` keeps a doc only
    if every term alone scores it above 0 (`operator: and`)."""
    import numpy as np

    from elasticsearch_tpu.ops import reference_impl as oracle

    seg_scores, seg_ids = [], []
    for segments in groups:
        per_seg = oracle.score_match_query(segments, FIELD, list(terms))
        masks = [s > 0 for s in per_seg]
        if require_all:
            for term in terms:
                alone = oracle.score_match_query(segments, FIELD, [term])
                masks = [m & (a > 0) for m, a in zip(masks, alone)]
        for seg, scores, mask in zip(segments, per_seg, masks):
            docs = np.flatnonzero(mask)
            seg_scores.append(scores[docs])
            seg_ids.append((seg, docs))
    scores = (np.concatenate(seg_scores) if seg_scores
              else np.empty(0, dtype=np.float32))
    total = int(scores.shape[0])
    if total == 0:
        return 0, [], scores
    keep = np.arange(total)
    if total > k:
        kth = np.partition(scores, total - k)[total - k]
        keep = np.flatnonzero(scores >= kth * (1.0 - 10 * REL_TOL))
    order = keep[np.argsort(-scores[keep], kind="stable")]
    bounds = np.cumsum([0] + [len(s) for s in seg_scores])
    ids = []
    for j in order.tolist():
        si = int(np.searchsorted(bounds, j, side="right") - 1)
        seg, docs = seg_ids[si]
        ids.append(seg.doc_ids[int(docs[j - bounds[si]])])
    return total, ids, scores[order]


def compare_response(resp: Dict[str, Any], ref: Tuple[int, List[str], Any],
                     k: int, what: str) -> int:
    """Hold one `_search` response to the reference. Returns the number
    of positions whose id differs inside a tolerated near-tie."""
    total, ref_ids, ref_scores = ref
    hits = resp["hits"]
    require(resp["_shards"]["failed"] == 0 and not resp["timed_out"],
            f"{what}: shard failures or timeout: {resp['_shards']}")
    got_total = hits["total"]
    # a prefix-tier launch may report a lower bound; an exact count must
    # match exactly
    if got_total["relation"] == "eq":
        require(got_total["value"] == total,
                f"{what}: hits.total {got_total} != reference {total}")
    else:
        require(got_total["value"] <= total,
                f"{what}: hits.total {got_total} > reference {total}")
    served = hits["hits"]
    want = min(k, total)
    require(len(served) == want,
            f"{what}: {len(served)} hits returned, reference has {want}")
    ids = [h["_id"] for h in served]
    require(len(set(ids)) == len(ids), f"{what}: duplicate ids in hits")
    pos_of = {doc_id: j for j, doc_id in enumerate(ref_ids)}
    swaps = 0
    for i, hit in enumerate(served):
        r = float(ref_scores[i])
        tol = REL_TOL * abs(r)
        require(abs(hit["_score"] - r) <= tol,
                f"{what}: score at rank {i} is {hit['_score']!r}, "
                f"reference {r!r}")
        if hit["_id"] != ref_ids[i]:
            j = pos_of.get(hit["_id"])
            require(j is not None
                    and abs(float(ref_scores[j]) - r) <= tol,
                    f"{what}: id at rank {i} is {hit['_id']!r}, reference "
                    f"{ref_ids[i]!r} (not a near-tie: reference rank {j})")
            swaps += 1
    return swaps


# ---------------------------------------------------------------------------
# the no-hidden-fallback check
# ---------------------------------------------------------------------------

def require_served_by_kernel(stats: Dict[str, Any], sent: int, chips: int,
                             platform: str, what: str) -> None:
    """`/_tpu/stats` after a step: every request so far was answered by
    the kernel on the full mesh of the expected platform."""
    dev = stats["devices"]
    checks = [
        ("served", stats["served"], sent),
        ("fallback", stats["fallback"], 0),
        ("timeouts", stats["timeouts"], 0),
        ("tripped", stats["tripped"], False),
        ("last_error", stats["last_error"], None),
        ("devices.platform", dev["platform"], platform),
        ("devices.mesh_devices", dev["mesh_devices"], chips),
        ("devices.mesh_devices_full", dev["mesh_devices_full"], chips),
        ("devices.degraded", dev["degraded"], None),
        ("devices.shed_packs", dev["shed_packs"], []),
        ("devices.health.quarantines", dev["health"]["quarantines"], 0),
        ("watchdog.wedges", stats["watchdog"]["wedges"], 0),
        ("supervision.state", stats["supervision"]["state"], "serving"),
        ("supervision.recoveries", stats["supervision"]["recoveries"], 0),
    ]
    bad = [f"{name}={got!r} (want {want!r})"
           for name, got, want in checks if got != want]
    require(not bad, f"{what}: not served by the kernel path: "
            + "; ".join(bad))


def variant_delta(before: Dict[str, int], after: Dict[str, int]
                  ) -> Dict[str, int]:
    return {key: n - before.get(key, 0) for key, n in after.items()
            if n - before.get(key, 0)}


def require_variants(delta: Dict[str, int], compressed: bool,
                     exact_only: bool, what: str) -> None:
    """Launches of a step ran the kernels the pack format implies: a raw
    pack serves OR queries through the full/pruned kernels and AND
    through exact,{packed,ref}; a compressed pack serves everything
    through exact,compressed*."""
    kernels = {key.split(",")[0] for key in delta}
    variants = {key.split(",")[1] for key in delta}
    if compressed:
        ok = kernels == {"exact"} and variants and variants <= {
            "compressed", "compressed_exact"}
    elif exact_only:
        ok = kernels == {"exact"} and variants and variants <= {
            "packed", "ref"}
    else:
        ok = bool(kernels) and kernels <= {"full", "pruned"}
    require(bool(ok), f"{what}: unexpected kernel launches {delta} for a "
            f"{'compressed' if compressed else 'raw'} pack")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(docs: int, shards: int, *, seed: int, data_path: str,
        small_docs: int = SMALL_DOCS, append_docs: int = APPEND_DOCS,
        n_or: int = 64, n_and: int = 16, n_small: int = 16,
        n_append: int = 8, k_large: int = 1000, clients: int = 8,
        bulk_clients: int = 4) -> Dict[str, Any]:
    """Index, warm, query and check at the given sizes on whatever
    backend jax has; returns the set-up facts. Raises SmokeFailure (or
    whatever the failing step raised) — no step is wrapped."""
    import jax

    from elasticsearch_tpu import native
    from elasticsearch_tpu.benchmark import corpus as corpus_gen
    from elasticsearch_tpu.node import Node, serve

    devices = jax.devices()
    chips = len(devices)
    platform = devices[0].platform
    facts: Dict[str, Any] = {"docs": docs, "shards": shards, "seed": seed}

    # XLA compilations, counted from jax's own monitoring events: every
    # one after the end of prewarm is a signature the warmer missed
    compiles: List[Tuple[str, float]] = []
    cache_misses = 0

    def on_duration(event: str, duration: float, **kw: Any) -> None:
        if event == BACKEND_COMPILE_EVENT:
            compiles.append((str(kw.get("fun_name")), duration))

    def on_event(event: str, **kw: Any) -> None:
        nonlocal cache_misses
        if event == CACHE_MISS_EVENT:
            cache_misses += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    if os.path.isdir(data_path):
        shutil.rmtree(data_path)  # the smoke's own directory, last run's
    os.makedirs(data_path)
    node = Node(data_path)
    server = serve(node, port=0)
    port = server.server_address[1]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        tpu = node.tpu_search
        require(tpu is not None, "node has no TPU serving path")
        # a missing `cc` drops both to Python with a log line only
        for lib in ("fast_tokenize", "response_splice"):
            require(native.load(lib) is not None,
                    f"native library [{lib}] did not build/load from its "
                    f"checked-in .c file")
        cache_dir = jax.config.jax_compilation_cache_dir
        require(bool(cache_dir), "no persistent compile cache directory "
                "is configured")
        facts["compile_cache_dir"] = cache_dir
        facts["compile_cache_dir_from_env"] = bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR"))

        def cache_files() -> int:
            return sum(len(names) for _r, _d, names in os.walk(cache_dir))

        def tpu_stats() -> Dict[str, Any]:
            return http_json(conn, "GET", "/_tpu/stats")

        t0 = time.perf_counter()
        n_queries = max(n_or + n_and, 2 * n_small, n_append)
        corpus = corpus_gen.generate(docs, num_queries=n_queries, seed=seed)
        facts["corpus_seconds"] = round(time.perf_counter() - t0, 2)
        log(f"corpus: {docs} docs ({facts['corpus_seconds']}s)")

        def query_terms(qi: int) -> List[str]:
            return [corpus.vocab[t] for t in corpus.queries[qi]]

        def create_index(name: str, n_shards: int) -> Any:
            http_json(conn, "PUT", f"/{name}", {
                "settings": {"index": {"number_of_shards": n_shards}},
                "mappings": {"properties": {FIELD: {"type": "text"}}}})
            return node.indices.index(name)

        def load_and_merge(name: str, n: int) -> Dict[str, float]:
            t0 = time.perf_counter()
            bulk_index(port, name,
                       [(str(i), corpus.doc_text(i)) for i in range(n)],
                       bulk_clients)
            http_json(conn, "POST", f"/{name}/_refresh")
            t1 = time.perf_counter()
            # settle to one segment per shard before warm-up (Rally's
            # force-merge step): a merge landing mid-run would swap
            # readers and rebuild the pack under traffic
            http_json(conn, "POST", f"/{name}/_forcemerge")
            http_json(conn, "POST", f"/{name}/_refresh")
            t2 = time.perf_counter()
            log(f"[{name}] indexed {n} docs in {t1 - t0:.1f}s "
                f"({n / (t1 - t0):.0f} docs/s), forcemerge {t2 - t1:.1f}s")
            return {"index_seconds": round(t1 - t0, 2),
                    "index_docs_per_s": round(n / (t1 - t0), 1),
                    "forcemerge_seconds": round(t2 - t1, 2)}

        def shard_groups(svc: Any) -> List[List[Any]]:
            return [[v.segment for v in shard.acquire_searcher().views]
                    for _num, shard in sorted(svc.shards.items())]

        def prewarm(svc: Any, name: str) -> Dict[str, Any]:
            files0, n0, miss0 = cache_files(), len(compiles), cache_misses
            warm = tpu.prewarm(svc, FIELD)
            failed = [e for e in warm["compiled"] if "error" in e]
            require(not failed, f"[{name}] prewarm could not compile "
                    f"{len(failed)} of {len(warm['compiled'])} signatures: "
                    f"{failed[:3]}")
            slowest = max(warm["compiled"], key=lambda e: e["seconds"])
            out = {"pack_build_seconds": warm["pack_seconds"],
                   "prewarm_seconds": warm["total_seconds"],
                   "signatures": len(warm["compiled"]),
                   "signature_seconds_sum": round(
                       sum(e["seconds"] for e in warm["compiled"]), 1),
                   "slowest_signature": slowest,
                   "compiled": warm["compiled"],
                   "xla_compilations": len(compiles) - n0,
                   "cache_misses": cache_misses - miss0,
                   "cache_files_before": files0,
                   "cache_files_after": cache_files()}
            log(f"[{name}] prewarm: "
                f"{ {k: v for k, v in out.items() if k != 'compiled'} }")
            return out

        sent = 0
        # compilations while requests were in flight: (step, function,
        # seconds) — each is a signature the warmer missed
        late: List[Tuple[str, str, float]] = []

        def search_step(name: str, bodies: List[Dict[str, Any]],
                        refs: List[Any], what: str,
                        n_clients: int = clients) -> int:
            """Send, compare every response with its reference, then
            require that the kernel path answered all of them."""
            nonlocal sent
            n0 = len(compiles)
            resps = run_clients(
                port, n_clients, bodies,
                lambda c, body: http_json(c, "POST", f"/{name}/_search",
                                          body))
            sent += len(bodies)
            late.extend((what, fn, round(secs, 2))
                        for fn, secs in compiles[n0:])
            swaps = sum(compare_response(resp, ref, body["size"],
                                         f"{what} query {qi}")
                        for qi, (resp, ref, body)
                        in enumerate(zip(resps, refs, bodies)))
            require_served_by_kernel(tpu_stats(), sent, chips, platform,
                                     what)
            log(f"{what}: {len(bodies)} responses equal the reference "
                f"({swaps} near-tie swaps)")
            return swaps

        def variants() -> Dict[str, int]:
            return tpu_stats()["kernel"]["variants"]

        # ---- step 1: the large index --------------------------------
        large = create_index("bench", shards)
        facts["large"] = load_and_merge("bench", docs)
        facts["large"].update(prewarm(large, "bench"))
        pack = tpu_stats()["pack_cache"]["packs"][f"bench/{FIELD}"]
        facts["large"]["pack"] = pack
        groups = shard_groups(large)
        or_q, and_q = range(n_or), range(n_or, n_or + n_and)
        v0 = variants()
        facts["large"]["or_swaps"] = search_step(
            "bench",
            [match_body(corpus.query_text(qi), k_large) for qi in or_q],
            [reference_topk(groups, query_terms(qi), k_large, False)
             for qi in or_q], "large OR")
        v1 = variants()
        require_variants(variant_delta(v0, v1), pack["compressed"], False,
                         "large OR")
        facts["large"]["and_swaps"] = search_step(
            "bench",
            [match_body(corpus.query_text(qi), k_large, "and")
             for qi in and_q],
            [reference_topk(groups, query_terms(qi), k_large, True)
             for qi in and_q], "large AND")
        v2 = variants()
        require_variants(variant_delta(v1, v2), pack["compressed"], True,
                         "large AND")
        facts["large"]["launches"] = variant_delta(v0, v2)

        # every device of the mesh holds its part of the resident pack
        resident = tpu.packs.peek(("bench", FIELD))
        per_device = {d.id: 0 for d in devices}
        for arr in (tuple(resident.device_arrays)
                    + tuple(resident.imp_device_arrays or ())):
            for shard in arr.addressable_shards:
                per_device[shard.device.id] += int(shard.data.nbytes)
        require(all(n > 0 for n in per_device.values()),
                f"a device holds no pack bytes: {per_device}")
        facts["pack_bytes_per_device"] = per_device

        # ---- step 2: the small (compressed) index -------------------
        small = create_index("small", 1)
        facts["small"] = load_and_merge("small", small_docs)
        facts["small"].update(prewarm(small, "small"))
        small_pack = tpu_stats()["pack_cache"]["packs"][f"small/{FIELD}"]
        facts["small"]["pack"] = small_pack
        groups_small = shard_groups(small)
        v0 = variants()
        swaps = 0
        for size, qis in ((10, range(n_small)),
                          (k_large, range(n_small, 2 * n_small))):
            swaps += search_step(
                "small",
                [match_body(corpus.query_text(qi), size) for qi in qis],
                [reference_topk(groups_small, query_terms(qi), size, False)
                 for qi in qis], f"small size={size}")
        facts["small"]["swaps"] = swaps
        facts["small"]["launches"] = variant_delta(v0, variants())
        require_variants(facts["small"]["launches"],
                         small_pack["compressed"], False, "small")

        # ---- step 3: append → refresh → delta pack ------------------
        # the first n_append new docs carry one query's terms each, so
        # each of those queries must bring its appended doc back
        t0 = time.perf_counter()
        new_docs = []
        for i in range(append_docs):
            text = corpus.doc_text(i)
            if i < n_append:
                text += (" " + corpus.query_text(i)) * 4
            new_docs.append((f"a{i}", text))
        bulk_index(port, "bench", new_docs, bulk_clients)
        http_json(conn, "POST", "/bench/_refresh")
        facts["append"] = {"docs": append_docs, "bulk_refresh_seconds":
                           round(time.perf_counter() - t0, 2)}
        # statistics groups after the append: the base pack's segments
        # and the delta's, per shard — a delta pack scores with its own
        # idf/avgdl until compaction folds it into the base
        base_names = {seg.name for g in groups for seg in g}
        groups_after = []
        for segs in shard_groups(large):
            groups_after.append([s for s in segs if s.name in base_names])
            groups_after.append([s for s in segs
                                 if s.name not in base_names])
        groups_after = [g for g in groups_after if g]
        bodies = [match_body(corpus.query_text(qi), k_large)
                  for qi in range(n_append)]
        refs = [reference_topk(groups_after, query_terms(qi), k_large,
                               False) for qi in range(n_append)]
        # the first search after a refresh builds the delta pack; one
        # racing it may be answered from the previous chain
        # (stale-while-rebuild), which this exact comparison would
        # reject — so the first goes alone
        t0 = time.perf_counter()
        search_step("bench", bodies[:1], refs[:1], "append first", 1)
        facts["append"]["first_search_seconds"] = round(
            time.perf_counter() - t0, 2)
        search_step("bench", bodies[1:], refs[1:], "append rest")
        appended_back = sum(
            1 for ref in refs
            if any(i.startswith("a") for i in ref[1][:k_large]))
        require(appended_back >= 1,
                "no query's reference top-k holds an appended doc")
        stats = tpu_stats()
        deltas = stats["deltas"]
        require(deltas["enabled"] and deltas["appends"] >= 1
                and deltas["packs"] + deltas["compactions"] >= 1
                and deltas["compaction_failures"] == 0,
                f"the refresh did not ride a delta pack: {deltas}")
        facts["append"].update(queries_returning_appended=appended_back,
                               deltas=deltas)

        # ---- set-up facts to record ---------------------------------
        facts["xla_compilations_total"] = len(compiles)
        facts["xla_compilations_while_serving"] = len(late)
        facts["xla_compile_seconds_while_serving"] = round(
            sum(secs for _w, _f, secs in late), 2)
        facts["xla_compiled_while_serving"] = late
        facts["requests_sent"] = sent
        facts["kernel_variants"] = stats["kernel"]["variants"]
        facts["stages"] = {
            name: {key: ring[key] for key in ("seconds", "count")}
            for name, ring in stats["stages"].items()
            if name.count(".") <= 1}
        node_stats = http_json(conn, "GET", "/_nodes/stats")
        hbm = next(iter(node_stats["nodes"].values()))["breakers"]["hbm"]
        facts["hbm_breaker"] = {"used": hbm["estimated_size_in_bytes"],
                                "limit": hbm["limit_size_in_bytes"],
                                "tripped": hbm["tripped"]}
        facts["device_memory"] = []
        for d in devices:
            mem = d.memory_stats() or {}
            facts["device_memory"].append({
                "id": d.id,
                "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
                "bytes_in_use": mem.get("bytes_in_use"),
                "bytes_limit": mem.get("bytes_limit")})
        facts["devices"] = {key: stats["devices"][key] for key in (
            "platform", "device_kind", "jax", "jaxlib", "libtpu",
            "mesh_devices")}
        return facts
    finally:
        conn.close()
        server.shutdown()
        server.server_close()
        node.close()
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        log(f"no TPU: jax reports {device}; refusing to run")
        return 2

    from elasticsearch_tpu.search.tpu_service import device_stamp
    log(f"device: {device} versions: {device_stamp(devices)}")

    chips = len(devices)
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=21)
    parser.add_argument("--docs", type=int,
                        default=DEFAULT_DOCS_PER_CHIP * chips)
    parser.add_argument("--shards", type=int,
                        default=SHARDS_PER_CHIP * chips)
    args = parser.parse_args(argv)
    require(args.docs >= FLOOR_DOCS,
            f"--docs {args.docs} is under the floor of {FLOOR_DOCS}")
    reduced = []
    if args.docs < SHARE_DOCS_PER_CHIP * chips:
        reduced.append({"what": "docs", "deployment":
                        SHARE_DOCS_PER_CHIP * chips, "run": args.docs})
    if args.shards != SHARDS_PER_CHIP * chips:
        reduced.append({"what": "shards", "deployment":
                        SHARDS_PER_CHIP * chips, "run": args.shards})
    log(f"docs={args.docs} shards={args.shards} reduced={reduced}")

    t0 = time.perf_counter()
    facts = run(args.docs, args.shards, seed=args.seed,
                data_path=os.path.join("data", "chip_smoke"))
    # the deployment's formats, not whatever the sizes happened to give
    # (run() already held /_tpu/stats to jax's platform and chip count)
    require(not facts["large"]["pack"]["compressed"],
            "the large index is not a raw pack")
    require(facts["small"]["pack"]["compressed"],
            "the small index is not a compressed pack")
    facts["total_seconds"] = round(time.perf_counter() - t0, 1)
    facts["reduced"] = reduced
    # set-up facts: stderr, and a file the chip tool brings back. stdout
    # carries the result line and nothing else
    log("facts: " + json.dumps(facts))
    os.makedirs(FACTS_DIR, exist_ok=True)
    with open(os.path.join(FACTS_DIR, "chip_smoke_facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
