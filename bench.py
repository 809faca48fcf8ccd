"""Headline benchmark: end-to-end `_search` throughput THROUGH the REST
layer, on a Zipf-realistic corpus, with a MEASURED CPU baseline and
nDCG@10 quality parity (BASELINE.md obligations; VERDICT r1 #4).

What runs:
  1. Generate a synthetic MS-MARCO-shaped corpus (Zipf words, log-normal
     lengths, planted graded relevance — elasticsearch_tpu/benchmark/).
  2. Index it into a real Node (engine + translog + segments).
  3. Fire concurrent match queries through the REST dispatch layer
     (`node.handle` → RestController → coordinator → micro-batched
     TPU kernel path); measure QPS.
  4. Measure the CPU baseline: the exact numpy BM25 oracle
     (ops/reference_impl.py) over the same corpus/queries, single-thread,
     scaled by host core count (a perfect-scaling, favorable-to-CPU
     stand-in for the 32-vCPU reference node that no-network prevents
     running; BASELINE.md documents this substitution).
  5. Verify quality: nDCG@10 of the TPU path vs the oracle on the
     planted judgments — parity means the speed is not bought with
     ranking drift.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Env knobs: ES_TPU_BENCH_{DOCS,SHARDS,VOCAB,QUERIES,CLIENTS,K,SECONDS}.
ES_TPU_BENCH_KERNEL_COMPARE=1 additionally reruns a short load phase once
per device-kernel variant (packed single-key sort vs two-operand ref vs
compressed u16 resident streams vs the fused Pallas kernel) and emits a
"kernel_compare" block with per-variant device p50/p99,
device_ms_per_query, the resident pack's hbm_bytes_per_doc /
hbm_bytes_per_posting / compression_ratio, and the compressed phases'
host-mirrored block-max skip rate (PERF.md rounds 8, 11 and 12).

Timing note: every REST response here materializes hit ids from device
buffers (a host readback), so a client-side time includes the device's
work and not only its enqueue.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np


def _env(name: str, default: int) -> int:
    return int(os.environ.get(f"ES_TPU_BENCH_{name}", default))


def _slowest_trace(tracer):
    """Per-stage breakdown of the slowest sampled _search trace: where
    did the worst query's time actually go (batch wait vs kernel vs
    assembly), not just the total."""
    if tracer is None:
        return None
    roots = [s for s in tracer.spans(limit=0)
             if s["parent_id"] is None and s["name"].endswith("_search")]
    if not roots:
        return None
    worst = max(roots, key=lambda s: s["duration_ms"])
    stages_ms = {}
    for s in tracer.trace(worst["trace_id"]):
        if s["span_id"] == worst["span_id"]:
            continue
        stages_ms[s["name"]] = round(
            stages_ms.get(s["name"], 0.0) + s["duration_ms"], 3)
    return {"trace_id": worst["trace_id"],
            "total_ms": round(worst["duration_ms"], 3),
            "stages_ms": stages_ms}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax

    from elasticsearch_tpu.benchmark import corpus as corpus_gen
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.ops import reference_impl as oracle
    from elasticsearch_tpu.search import rank_eval

    on_tpu = jax.default_backend() == "tpu"
    n_docs = _env("DOCS", 262144 if on_tpu else 2048)
    n_shards = _env("SHARDS", 4 if on_tpu else 2)
    vocab = _env("VOCAB", 30_000 if on_tpu else 2000)
    n_queries = _env("QUERIES", 256 if on_tpu else 16)
    # the serving path batches up to 128 queries per launch; the load
    # driver must offer ~2 trains of concurrency to keep the pipeline
    # full (Rally-style closed-loop clients)
    clients = _env("CLIENTS", 256 if on_tpu else 4)
    k = _env("K", 1000 if on_tpu else 32)
    seconds = _env("SECONDS", 20 if on_tpu else 3)

    t0 = time.perf_counter()
    corpus = corpus_gen.generate(n_docs, vocab_size=vocab,
                                 num_queries=n_queries, seed=42)
    log(f"corpus: {n_docs} docs, {vocab} vocab "
        f"({time.perf_counter() - t0:.1f}s)")

    # ---- index into a real node ----
    # PRODUCTION serving config — no batch-timeout crutch: the pack build
    # + XLA compiles happen in the explicit prewarm step below (the
    # reference's index-warmer seam).
    # trace a small sample of load queries so the result line can show
    # WHERE the slowest query's time went (0 disables entirely)
    trace_sample = float(os.environ.get("ES_TPU_BENCH_TRACE_SAMPLE",
                                        "0.05"))
    # ES_TPU_BENCH_PROFILE=1: run the continuous host sampler through
    # the load phase and emit the batch_wait decomposition + top folded
    # stacks in the JSON (the attribution ledger for host-path PRs)
    profile_on = _env("PROFILE", 0) == 1
    node_settings = {
        "index": {"translog": {"durability": "async"}},
        "search": {
            "tracing": {"sample_rate": trace_sample},
            "profiler": {"enabled": profile_on},
            # every closed-loop client can have one request
            # in flight per front — ring sized to match so
            # the rest_qps phase measures throughput, not
            # 429 churn
            "tpu_serving": {
                "front_slots": max(64, clients)}}}
    if _env("SLO", 0) == 1:
        # weighted tenants for the SLO phase; the default tenant keeps a
        # 1/4 share = exactly the search pool size, so the single-tenant
        # phases above never hit the carve
        node_settings["tenancy"] = {"weight": {"victim": 2,
                                               "aggressor": 1}}
    if _env("SLO_DEVICE_LOSS", 0) == 1:
        # the chip-loss drill runs under replicated pack placement so it
        # PROVES zero-shed failover: each pack on R=2 distinct
        # fault-domain groups — losing a chip fails its group over to
        # the surviving replica instead of shedding
        node_settings["search"]["tpu_serving"]["placement"] = {
            "groups": _env("PLACEMENT_GROUPS", 2),
            "replicas": _env("PLACEMENT_REPLICAS", 2)}
        # detection must land INSIDE the drill window (default deadline
        # is 120s — the loss would heal before the watchdog ever calls
        # it wedged): deadline above a hot CPU launch (~4s), one wedge
        # suffices to probe, and the probe verdict is forced by the
        # DeviceLoss scheme anyway
        node_settings["search"]["tpu_serving"]["launch_deadline_ms"] = \
            _env("LAUNCH_DEADLINE_MS", 8000)
        node_settings["search"]["tpu_serving"]["device_health"] = {
            "suspect_after": 1, "reprobe_interval_seconds": 2,
            "hold_down_seconds": 5}
    node = Node(tempfile.mkdtemp(prefix="es_tpu_bench_"),
                settings=Settings.of(node_settings))
    t0 = time.perf_counter()  # bulk ingest + refresh-to-searchable
    idx = node.create_index(
        "bench", Settings.of({"index": {
            "number_of_shards": n_shards,
            "translog": {"durability": "async"}}}),
        {"properties": {"body": {"type": "text"}}})
    # the production write path: REST _bulk (NDJSON) from a few
    # concurrent clients (the standard ES load-driver shape), grouped per
    # shard through the engine's batched path (VERDICT r3 #4). Analysis
    # runs native code that releases the GIL, so clients overlap.
    bulk_sz = 4000
    bulk_clients = _env("BULK_CLIENTS", 2)
    starts = list(range(0, corpus.num_docs, bulk_sz))
    bulk_errors = []

    def bulk_client(ci: int) -> None:
        for si in range(ci, len(starts), bulk_clients):
            start = starts[si]
            lines = []
            for i in range(start, min(start + bulk_sz, corpus.num_docs)):
                lines.append(json.dumps({"index": {"_id": str(i)}}))
                lines.append(json.dumps({"body": corpus.doc_text(i)}))
            s, resp = node.handle("POST", "/bench/_bulk", {},
                                  "\n".join(lines) + "\n")
            if s != 200 or resp.get("errors"):
                bulk_errors.append(str(resp)[:500])
                return

    bulk_threads = [threading.Thread(target=bulk_client, args=(ci,))
                    for ci in range(bulk_clients)]
    [t.start() for t in bulk_threads]
    [t.join() for t in bulk_threads]
    assert not bulk_errors, bulk_errors[:1]
    idx.refresh()
    index_dt = time.perf_counter() - t0
    log(f"indexing: {corpus.num_docs} docs in {index_dt:.1f}s "
        f"({corpus.num_docs / index_dt:.0f} docs/s)")
    # settle to a quiescent segment set BEFORE warmup (Rally's
    # force-merge step for read benchmarks): a background merge landing
    # mid-measurement would otherwise swap readers and trigger a pack
    # rebuild during traffic
    t0 = time.perf_counter()
    s, _ = node.handle("POST", "/bench/_forcemerge", {}, None)
    assert s == 200
    idx.refresh()
    log(f"forcemerge: {time.perf_counter() - t0:.1f}s")

    # retrieval-benchmark shape (MS MARCO top-k): ids + scores, no
    # stored-field materialization in the response
    query_bodies = [
        {"query": {"match": {"body": corpus.query_text(qi)}}, "size": k,
         "_source": False}
        for qi in range(len(corpus.queries))
    ]

    # ---- warm the serving path: pack build + every steady-state jit
    # signature, via the explicit warmer API (reference: IndicesWarmer).
    # A later run on the same machine replays the compiles from the
    # persistent cache (<checkout>/.jax_cache or JAX_COMPILATION_CACHE_DIR;
    # cold and warm prewarm seconds on the chip: PERF.md "Bring-up") ----
    t0 = time.perf_counter()
    # ES_TPU_BENCH_PREWARM=0 skips the full signature table (CPU smoke
    # runs on small machines: each signature costs a real XLA compile
    # and only the traffic-reachable ones matter there; the serving
    # path compiles those lazily on first hit)
    if node.tpu_search and os.environ.get(
            "ES_TPU_BENCH_PREWARM", "1") != "0":
        warm = node.tpu_search.prewarm(idx, "body")
        log(f"prewarm (pack build + compiles): {warm}")
    # first post-prewarm search = first-train latency: any residual cold
    # dispatch (a signature the warmer missed) shows up HERE, not as a
    # throughput-loop stall
    t_first = time.perf_counter()
    status, first = node.handle("POST", "/bench/_search", {},
                                dict(query_bodies[0]))
    first_train_s = time.perf_counter() - t_first
    warmup_s = time.perf_counter() - t0
    log(f"warmup total: {warmup_s:.1f}s "
        f"(first train: {first_train_s:.2f}s)")

    # cold-start numbers are IN the emitted JSON from here on, even if
    # the measurement below stalls or errors — a scale run that dies
    # mid-throughput must still record its warmup in BENCH_* trajectories
    out = {
        "metric": "rest_search_qps",
        "value": None,
        "unit": f"queries/s through REST (D={n_docs}x{n_shards}sh, "
                f"k={k}, clients={clients}, {jax.default_backend()})",
        "index_docs_per_s": round(corpus.num_docs / index_dt, 1),
        "warmup_seconds": round(warmup_s, 1),
        "first_train_seconds": round(first_train_s, 3),
    }
    if status != 200:
        out["error"] = f"first search failed: {str(first)[:300]}"
        if node.tpu_search:
            out["stages"] = node.tpu_search.stats().get("stages")
        node.close()
        print(json.dumps(out))
        sys.exit(1)

    # ---- throughput through REST with concurrent clients ----
    errors = []

    def load_phase(phase_seconds: float):
        """Closed-loop client load for phase_seconds → (queries, dt)."""
        stop_at = time.perf_counter() + phase_seconds
        counts = [0] * clients

        def client(ci: int) -> None:
            qi = ci
            while time.perf_counter() < stop_at:
                body = dict(query_bodies[qi % len(query_bodies)])
                s, resp = node.handle("POST", "/bench/_search", {}, body)
                if s != 200:
                    errors.append(resp)
                    return
                counts[ci] += 1
                qi += clients

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        return sum(counts), time.perf_counter() - t0

    total_queries, dt = load_phase(seconds)
    qps = total_queries / dt
    st = node.tpu_search.stats() if node.tpu_search else {}
    out["stages"] = st.get("stages")
    out["slowest_trace"] = _slowest_trace(getattr(node, "tracer", None))
    if errors:
        out["error"] = f"search errors during load: {str(errors[0])[:300]}"
        out["value"] = round(qps, 2)
        node.close()
        print(json.dumps(out))
        sys.exit(1)
    log(f"REST throughput: {total_queries} queries in {dt:.1f}s = "
        f"{qps:.1f} QPS (kernel-served: {st.get('served')}, "
        f"batches: {st.get('batches')})")
    log(f"stage breakdown: {st.get('stages')}")

    # ---- batch_wait attribution + host flamegraph (PROFILE=1) ----
    if profile_on:
        stages = st.get("stages") or {}
        legacy = stages.get("batch_wait", {})
        split = {}
        split_sum = 0.0
        for part in ("queue", "window", "dispatch", "completion"):
            s_part = stages.get(f"batch_wait.{part}")
            if s_part:
                split[part] = {"seconds": round(s_part["seconds"], 3),
                               "count": s_part["count"],
                               "p50_ms": s_part.get("p50_ms"),
                               "p99_ms": s_part.get("p99_ms")}
                split_sum += s_part["seconds"]
        legacy_s = legacy.get("seconds", 0.0)
        sampler = node.profiler.sampler
        out["profile"] = {
            "batch_wait_seconds": round(legacy_s, 3),
            "batch_wait_split": split,
            "split_sum_seconds": round(split_sum, 3),
            "split_vs_total": (round(split_sum / legacy_s, 4)
                               if legacy_s > 0 else None),
            "sampler": sampler.stats(),
            "top_stacks": [{"stack": line, "count": cnt}
                           for line, cnt in sampler.folded(top=15)],
        }
        log(f"batch_wait attribution: total={legacy_s:.1f}s split_sum="
            f"{split_sum:.1f}s ({out['profile']['split_vs_total']}) "
            f"parts={ {p: v['seconds'] for p, v in split.items()} }")

    # ---- kernel-variant A/B/C (ES_TPU_BENCH_KERNEL_COMPARE=1): rerun a
    # short load phase once per device-kernel variant (packed single-key
    # sort vs two-operand ref vs compressed resident streams, PERF.md
    # rounds 8/11). Device time per variant comes from the variant-tagged
    # stage rings — *_device_wait.packed only ever accumulates packed
    # launches, so diffing (seconds, count) across the phase isolates
    # each variant's device floor. ----
    if _env("KERNEL_COMPARE", 0) == 1 and node.tpu_search is not None:
        from elasticsearch_tpu.ops import sparse as _sparse
        from elasticsearch_tpu.parallel import distributed as _dist

        tpu = node.tpu_search

        def compressed_skip_rate(sample: int = 16, k_probe: int = 0):
            """Host mirror of the kernel's block-max skip decision over a
            sample of bench queries (the device-side mask isn't
            observable from outside the jit): fraction of valid 128-lane
            groups a totals-free launch at this k would eliminate."""
            resident = tpu.packs._cache.get(("bench", "body"))
            if resident is None or resident.comp_streams is None:
                return None
            streams = resident.comp_streams
            qs = [corpus.query_text(qi).split()
                  for qi in range(min(sample, len(corpus.queries)))]
            batch = _dist.prepare_query_batch(resident.pack, qs,
                                              compressed=streams)
            kp = min(k_probe or k, batch.max_len)
            blksz = _sparse.COMPRESSED_BLOCK
            n_grp = (batch.max_len + blksz - 1) // blksz
            skipped = valid_n = 0
            for si in range(resident.pack.num_shards):
                st, ln = batch.starts[si], batch.lengths[si]
                w, sterm = batch.weights[si], batch.slot_terms[si]
                code16, bmax = streams.flat_code16[si], streams.block_max[si]
                blk = st // blksz
                r, t = st.shape
                bm = np.zeros((r, t, n_grp + 1), np.uint16)
                for ri in range(r):
                    for ti in range(t):
                        s0 = min(int(blk[ri, ti]), bmax.size - (n_grp + 1))
                        bm[ri, ti] = bmax[s0:s0 + n_grp + 1]
                grp_code = np.maximum(bm[..., :-1],
                                      bm[..., 1:]).astype(np.uint32)
                ub = ((np.minimum(grp_code + 1, 0x7F80) << 16)
                      .view(np.float32).reshape(grp_code.shape))
                g_valid = ((np.arange(n_grp) * blksz)[None, None, :]
                           < ln[:, :, None])
                grp_ub = np.where(g_valid & (w[:, :, None] > 0),
                                  w[:, :, None] * ub, 0.0)
                slot_ub = grp_ub.max(axis=2)
                eq = sterm[:, :, None] == sterm[:, None, :]
                term_ub = np.where(eq, slot_ub[:, None, :], 0.0).max(axis=2)
                tri = np.tril(np.ones((t, t), bool), k=-1)
                first = ~np.any(eq & tri[None], axis=2)
                others = (np.where(first, term_ub, 0.0)
                          .sum(axis=1, keepdims=True) - term_ub)
                thr = np.full(r, -np.inf, np.float32)
                for ri in range(r):
                    if int(batch.min_count[ri % batch.min_count.size]) > 1:
                        continue
                    for ti in range(t):
                        n = int(ln[ri, ti])
                        if n >= kp:
                            s0 = int(st[ri, ti])
                            q = w[ri, ti] * (
                                (code16[s0:s0 + n].astype(np.uint32) << 16)
                                .view(np.float32))
                            thr[ri] = max(thr[ri],
                                          np.partition(q, -kp)[-kp])
                skip = (grp_ub + others[:, :, None]) < thr[:, None, None]
                skipped += int((skip & g_valid).sum())
                valid_n += int(g_valid.sum())
            return round(skipped / valid_n, 4) if valid_n else 0.0

        original = tpu.kernel_packed_sort
        original_comp = tpu.kernel_compressed_pack
        original_pallas = tpu.kernel_pallas
        compare_s = max(2, seconds // 2)
        out["kernel_compare"] = {}
        for label, packed_on, comp_on, pallas_on in (
                ("packed", True, False, False),
                ("ref", False, False, False),
                ("compressed", True, True, False),
                ("pallas", True, True, True)):
            tpu.set_kernel_packed_sort(packed_on)
            tpu.set_kernel_pallas(pallas_on)
            if comp_on != tpu.kernel_compressed_pack:
                # residency format is decided at BUILD time: flip the
                # knob, then drop the pack so the phase's first search
                # rebuilds it in the new format
                tpu.set_kernel_compressed_pack(comp_on)
                tpu.packs.invalidate("bench")
            before = tpu.stats().get("stages") or {}
            nq, pdt = load_phase(compare_s)
            after = tpu.stats().get("stages") or {}
            dev_s = 0.0
            stage_detail = {}
            # compressed packs route every launch through the exact
            # path, whose rings tag the per-launch variant — both the
            # packable and the fallback-exact flavors belong to this
            # phase's device time (the pallas phase also counts its
            # "compressed" launches: the typed fallback when Pallas is
            # unavailable in this jaxlib)
            if pallas_on:
                suffixes = ("pallas", "compressed", "compressed_exact")
            elif comp_on:
                suffixes = ("compressed", "compressed_exact")
            else:
                suffixes = (label,)
            for base in ("batch_device_wait", "exact_device_wait",
                         "batch_dispatch", "exact_dispatch"):
                for suffix in suffixes:
                    name = f"{base}.{suffix}"
                    a, b = after.get(name), before.get(name)
                    if not a:
                        continue
                    secs = a["seconds"] - (b["seconds"] if b else 0.0)
                    cnt = a["count"] - (b["count"] if b else 0)
                    if cnt <= 0:
                        continue
                    if base.endswith("_device_wait"):
                        dev_s += secs
                    entry = {"count": cnt,
                             "ms_per_call": round(1000.0 * secs / cnt, 4)}
                    for pk in ("p50_ms", "p99_ms"):
                        if pk in a:
                            entry[pk] = a[pk]
                    stage_detail[name] = entry
            dev_ms_q = round(1000.0 * dev_s / max(1, nq), 4)
            phase = {
                "qps": round(nq / pdt, 2),
                "queries": nq,
                "device_ms_per_query": dev_ms_q,
                "stages": stage_detail,
            }
            det = (tpu.stats().get("pack_cache", {})
                   .get("packs", {}).get("bench/body"))
            if det:
                phase["pack"] = {pk: det[pk] for pk in (
                    "compressed", "hbm_bytes", "raw_bytes",
                    "compression_ratio", "hbm_bytes_per_doc",
                    "doc_delta", "doc_base_bytes", "postings",
                    "hbm_bytes_per_posting") if pk in det}
            if comp_on:
                phase["block_skip_rate"] = compressed_skip_rate()
                # the deep-pruning regime: top-10 raises the threshold
                # far above most blocks' maxima on long skewed postings
                phase["block_skip_rate_k10"] = compressed_skip_rate(
                    k_probe=10)
            out["kernel_compare"][label] = phase
            log(f"kernel_compare[{label}]: {nq} queries in {pdt:.1f}s, "
                f"device {dev_ms_q} ms/query"
                + (f", skip_rate {phase.get('block_skip_rate')}"
                   if comp_on else ""))
        tpu.set_kernel_packed_sort(original)
        tpu.set_kernel_pallas(original_pallas)
        if tpu.kernel_compressed_pack != original_comp:
            tpu.set_kernel_compressed_pack(original_comp)
            tpu.packs.invalidate("bench")

    # ---- true end-to-end REST QPS over real HTTP sockets: the
    # single-process server vs the multi-process serving front (ISSUE
    # 7). Unlike the in-process `node.handle` loop above, this pays
    # socket accept, HTTP parse, and response write — the costs the
    # front processes exist to take off the batcher's interpreter.
    # ES_TPU_BENCH_FRONTS=0 skips the phase. ----
    n_fronts = _env("FRONTS", 2)
    if n_fronts > 0:
        import http.client

        from elasticsearch_tpu.node import serve

        def http_load_phase(ports, phase_seconds):
            """Closed-loop keep-alive HTTP clients round-robined over
            `ports` → (queries, dt, rejected_429s, errors)."""
            stop_at = time.perf_counter() + phase_seconds
            counts = [0] * clients
            rejected = [0] * clients
            herrors = []

            def client(ci: int) -> None:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", ports[ci % len(ports)], timeout=120)
                qi = ci
                try:
                    while time.perf_counter() < stop_at:
                        body = json.dumps(
                            query_bodies[qi % len(query_bodies)])
                        conn.request(
                            "POST", "/bench/_search", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        data = resp.read()
                        if resp.status == 429:
                            # shedding under overload is expected — back
                            # off briefly and keep driving
                            rejected[ci] += 1
                            time.sleep(0.005)
                            continue
                        if resp.status != 200:
                            herrors.append(data[:300].decode(
                                "utf-8", "replace"))
                            return
                        counts[ci] += 1
                        qi += clients
                except OSError as e:
                    herrors.append(f"{type(e).__name__}: {e}")
                finally:
                    conn.close()

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(ci,))
                       for ci in range(clients)]
            [t.start() for t in threads]
            [t.join() for t in threads]
            return (sum(counts), time.perf_counter() - t0,
                    sum(rejected), herrors)

        phase_s = max(2, seconds // 2)
        server = serve(node, port=0)
        base_port = server.server_address[1]
        nq1, dt1, rej1, herr1 = http_load_phase([base_port], phase_s)
        server.shutdown()
        server.server_close()
        single_qps = nq1 / dt1 if dt1 > 0 else 0.0
        log(f"rest_qps single-process: {nq1} queries in {dt1:.1f}s = "
            f"{single_qps:.1f} QPS ({rej1} x 429)")
        front_ports = node.start_serving_fronts(count=n_fronts)
        nq2, dt2, rej2, herr2 = http_load_phase(front_ports, phase_s)
        front_qps = nq2 / dt2 if dt2 > 0 else 0.0
        sup = node.serving_front
        log(f"rest_qps {n_fronts} fronts: {nq2} queries in {dt2:.1f}s = "
            f"{front_qps:.1f} QPS ({rej2} x 429, plan-memo hits: "
            f"{sup.c_memo_hits.count})")
        out["rest_qps"] = {
            "single_process": round(single_qps, 2),
            "fronts": round(front_qps, 2),
            "front_processes": n_fronts,
            "speedup": (round(front_qps / single_qps, 3)
                        if single_qps > 0 else None),
            "rejected_429": {"single": rej1, "fronts": rej2},
            "plan_memo_hits": sup.c_memo_hits.count,
        }
        if herr1 or herr2:
            out["rest_qps"]["errors"] = (herr1 + herr2)[:3]

    # ---- multi-tenant SLO phase (ES_TPU_BENCH_SLO=1): sustained
    # mixed-tenant read/write traffic with one aggressor at max rate and
    # a BatcherKill cycle mid-run; emits per-tenant
    # {p50,p99,qps,rejects,lost_acks}. Like the warmup phase, the key is
    # ALWAYS populated — a stalled or crashed run still reports. ----
    if _env("SLO", 0) == 1:
        from elasticsearch_tpu.testing.disruption import (batcher_kill,
                                                          device_loss)
        from elasticsearch_tpu.testing.slo import run_slo
        slo_s = _env("SLO_SECONDS", max(4, seconds // 2))
        # ES_TPU_BENCH_SLO_DEVICE_LOSS=1 swaps the mid-run disruption
        # from a batcher kill to a chip-loss drill (quarantine → N-1
        # remesh); the emitted degraded_fraction / time_at_n_minus_1_s
        # measure the window either way
        drill_device = _env("SLO_DEVICE_LOSS", 0) == 1
        out["slo"] = {"error": None}
        try:
            def slo_chaos():
                if node.tpu_search is None:
                    return
                time.sleep(slo_s * 0.3)
                window = (device_loss if drill_device else batcher_kill)
                with window(node):
                    # the device drill must hold the fault PAST the
                    # launch deadline + probe round trip or quarantine
                    # (and therefore the failover being proven) never
                    # fires; the batcher kill is detected instantly
                    time.sleep(min(12.0, slo_s * 0.5) if drill_device
                               else min(1.5, slo_s * 0.2))
                # the rest of the run covers the recovery window

            slo = run_slo(
                node, index="bench", duration_s=slo_s,
                search_body=query_bodies[0],
                ports=(front_ports if n_fronts > 0
                       and node.serving_front is not None else None),
                tenants=[
                    {"tenant": "victim", "readers": 2, "writers": 1,
                     "think_time_s": 0.005},
                    {"tenant": "aggressor", "readers": 4,
                     "aggressor": True},
                ],
                during=slo_chaos)
            slo["error"] = None
            out["slo"] = slo
            vic = slo["tenants"].get("victim", {})
            agg = slo["tenants"].get("aggressor", {})
            deg = slo.get("degraded", {})
            log(f"slo: victim p50={vic.get('p50_ms')}ms "
                f"p99={vic.get('p99_ms')}ms qps={vic.get('qps')} "
                f"lost_acks={vic.get('lost_acks')}; aggressor "
                f"qps={agg.get('qps')} rejects={agg.get('rejects')}; "
                f"degraded_fraction={deg.get('degraded_fraction')} "
                f"time_at_n_minus_1={deg.get('time_at_n_minus_1_s')}s")
            if drill_device and node.tpu_search is not None:
                # the zero-shed proof: under replicated placement the
                # chip-loss window must fail over (failovers > 0,
                # packs_shed == 0); under groups=1 these report the
                # legacy shed path for comparison
                pl = node.tpu_search.placement
                slo["placement"] = {
                    "groups": pl.num_groups if pl is not None else 1,
                    "replicas": pl.replicas if pl is not None else 1,
                    "failovers": (pl.c_failovers.count
                                  if pl is not None else 0),
                    "replacements": (pl.c_replacements.count
                                     if pl is not None else 0),
                    "packs_shed": (pl.c_shed.count if pl is not None
                                   else len(node.tpu_search.shed_keys())),
                }
                log(f"slo device-loss drill: "
                    f"failovers={slo['placement']['failovers']} "
                    f"packs_shed={slo['placement']['packs_shed']} "
                    f"(groups={slo['placement']['groups']} "
                    f"replicas={slo['placement']['replicas']})")
        except Exception as e:  # noqa: BLE001 — the phase must emit
            out["slo"]["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            log(f"slo phase failed: {out['slo']['error']}")

    # ---- streaming-ingest phase (ES_TPU_BENCH_BULK_SUSTAINED=1):
    # sustained _bulk writers against a FRESH index under live read
    # traffic, with the NRT refresh cycle running so append-only
    # refreshes ride the device delta-pack path. Emits sustained
    # docs/s, p99 search-visible lag, and the compactor's duty cycle.
    # Like slo, the key is ALWAYS populated. ----
    if _env("BULK_SUSTAINED", 0) == 1:
        out["bulk_sustained"] = {"error": None}
        try:
            bs_s = _env("BULK_SUSTAINED_SECONDS", max(6, seconds))
            bs_writers = _env("BULK_SUSTAINED_WRITERS", bulk_clients)
            bs_batch = _env("BULK_SUSTAINED_BATCH", 1000)
            sidx = node.create_index(
                "bench_stream", Settings.of({"index": {
                    "number_of_shards": n_shards,
                    "translog": {"durability": "async"}}}),
                {"properties": {"body": {"type": "text"}}})
            if not getattr(node, "refresher_active", False):
                node.start_refresher()  # visibility rides the NRT cycle
            ds = (node.tpu_search.delta_stats
                  if node.tpu_search else None)
            compact_s0 = ds.compact_seconds if ds else 0.0
            acked = [0] * bs_writers
            bs_errors = []
            stop_at = time.perf_counter() + bs_s
            stream_q = {"query": {"match": {"body": corpus.query_text(0)}},
                        "size": 10, "_source": False}

            def bs_writer(ci: int) -> None:
                n = 0
                while time.perf_counter() < stop_at and not bs_errors:
                    lines = []
                    for j in range(bs_batch):
                        di = (n + j) % corpus.num_docs
                        lines.append(json.dumps(
                            {"index": {"_id": f"w{ci}-{n + j}"}}))
                        lines.append(json.dumps(
                            {"body": corpus.doc_text(di)}))
                    s, resp = node.handle("POST", "/bench_stream/_bulk",
                                          {}, "\n".join(lines) + "\n")
                    if s != 200 or resp.get("errors"):
                        bs_errors.append(str(resp)[:300])
                        return
                    n += bs_batch
                    acked[ci] = n

            def bs_reader() -> None:
                while time.perf_counter() < stop_at:
                    node.handle("POST", "/bench_stream/_search", {},
                                dict(stream_q))
                    time.sleep(0.05)

            t0 = time.perf_counter()
            workers = ([threading.Thread(target=bs_writer, args=(ci,))
                        for ci in range(bs_writers)]
                       + [threading.Thread(target=bs_reader)
                          for _ in range(2)])
            [t.start() for t in workers]
            [t.join() for t in workers]
            dt = time.perf_counter() - t0
            lag_p99 = 0.0
            for shard in sidx.shards.values():
                lag = shard.engine.stats().get(
                    "search_visible_lag_seconds", {})
                lag_p99 = max(lag_p99, float(lag.get("p99") or 0.0))
            if bs_errors:
                raise RuntimeError(f"bulk errors: {bs_errors[0]}")
            out["bulk_sustained"] = {
                "error": None,
                "docs_per_s": round(sum(acked) / dt, 1),
                "seconds": round(dt, 1),
                "writers": bs_writers,
                "batch_docs": bs_batch,
                "p99_visible_lag_s": round(lag_p99, 3),
                "compaction_duty_cycle": round(
                    ((ds.compact_seconds - compact_s0) / dt)
                    if ds else 0.0, 4),
                "deltas": (node.tpu_search.stats().get("deltas")
                           if node.tpu_search else None),
            }
            log(f"bulk_sustained: "
                f"{out['bulk_sustained']['docs_per_s']} docs/s over "
                f"{out['bulk_sustained']['seconds']}s, p99 visible lag "
                f"{out['bulk_sustained']['p99_visible_lag_s']}s, "
                f"compaction duty "
                f"{out['bulk_sustained']['compaction_duty_cycle']}")
        except Exception as e:  # noqa: BLE001 — the phase must emit
            out["bulk_sustained"]["error"] = \
                f"{type(e).__name__}: {str(e)[:300]}"
            log(f"bulk_sustained phase failed: "
                f"{out['bulk_sustained']['error']}")

    # ---- CPU oracle baseline on the same corpus/queries ----
    segments = []
    for shard in idx.shards.values():
        reader = shard.acquire_searcher()
        segments.extend(v.segment for v in reader.views)
    oracle_queries = min(len(query_bodies), 32 if on_tpu else 8)
    oracle_dt = float("inf")
    # best of 2 passes — run-to-run noise must not flatter the TPU side
    for _attempt in range(2):
        t0 = time.perf_counter()
        oracle_topk = []
        for qi in range(oracle_queries):
            terms = [corpus.vocab[t] for t in corpus.queries[qi]]
            per_seg = oracle.score_match_query(segments, "body", terms)
            offsets = np.cumsum([0] + [s.num_docs for s in segments[:-1]])
            dense = np.concatenate(per_seg)
            top = oracle.topk_from_scores(dense, k)
            # map concatenated ordinal back to external _id via segments
            ids = []
            for doc, score in top:
                si = int(np.searchsorted(offsets, doc, side="right") - 1)
                ids.append(segments[si].doc_ids[doc - int(offsets[si])])
            oracle_topk.append(ids)
        oracle_dt = min(oracle_dt, time.perf_counter() - t0)
    oracle_qps_1t = oracle_queries / oracle_dt
    ncpu = os.cpu_count() or 1
    cpu_baseline_qps = oracle_qps_1t * ncpu  # perfect-scaling assumption
    log(f"oracle: {oracle_queries} queries in {oracle_dt:.1f}s = "
        f"{oracle_qps_1t:.2f} QPS 1-thread x {ncpu} cores = "
        f"{cpu_baseline_qps:.1f} QPS baseline")

    # ---- quality parity: nDCG@10 TPU vs oracle on planted judgments ----
    ndcg_tpu, ndcg_oracle = [], []
    for qi in range(oracle_queries):
        s, resp = node.handle("POST", "/bench/_search", {},
                              dict(query_bodies[qi]))
        tpu_ids = [h["_id"] for h in resp["hits"]["hits"][:10]]
        qrel = {str(d): r for d, r in corpus.qrels[qi].items()}
        pool = list(qrel.values())
        ndcg_tpu.append(rank_eval.ndcg_at_k(
            [qrel.get(i) for i in tpu_ids], 10, pool))
        ndcg_oracle.append(rank_eval.ndcg_at_k(
            [qrel.get(i) for i in oracle_topk[qi][:10]], 10, pool))
    m_tpu = sum(ndcg_tpu) / len(ndcg_tpu)
    m_oracle = sum(ndcg_oracle) / len(ndcg_oracle)
    log(f"nDCG@10: tpu={m_tpu:.4f} oracle={m_oracle:.4f} "
        f"(diff {abs(m_tpu - m_oracle):.5f})")

    out.update({
        "value": round(qps, 2),
        "vs_baseline": round(qps / cpu_baseline_qps, 3),
        "cpu_baseline_qps": round(cpu_baseline_qps, 2),
        "cpu_baseline_note": f"numpy oracle {oracle_qps_1t:.2f} QPS/thread "
                             f"x {ncpu} cores, perfect scaling assumed",
        "ndcg10_tpu": round(m_tpu, 4),
        "ndcg10_oracle": round(m_oracle, 4),
        "stages": (node.tpu_search.stats().get("stages")
                   if node.tpu_search else None),
    })
    node.close()
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "compare":
        # regression gate: diff the two newest BENCH_r*.json rounds
        from elasticsearch_tpu.benchmark.compare import main as _compare
        sys.exit(_compare(sys.argv[2:]))
    main()
