"""MS MARCO questions as users type them, stop-words kept, at toy size (the
benchmark's configuration `msmarco-stop-1chip` and its cell
`msmarco-stop-1chip.or1000-closed384-heads`): the configuration's
`stopmix` law in 2 shards on one device, `match` OR served over REST and
held to the program's two plain references, the numpy oracle
`ops/reference_impl.py` and the dense jax.numpy BM25 of
`ops/reference_dense.py`: ids equal but for near-tie swaps, scores within
1e-5 relative, `hits.total` exact with relation `eq`.

A query that holds one of the pack's head terms (`dist.head_terms`: df at
least a quarter of the docs, postings past HEAD_MIN_SLOTS slots on a shard
row) takes the heads route: the head terms scored as one dense block by a
matmul, the other terms (the tail) through the full-postings merge at
their rung (`dist.make_heads_search`). The deployment's rows hold 552,960
docs, where the least frequent head term fills 35 slots; the toy rows hold
5,000, so the tests lower HEAD_MIN_SLOTS to one slot, and the pack's head
terms are the law's three most frequent words. The tail's 32- and
128-slot rungs need terms of several slots, which only a row past 16,384
docs holds: a second index of one shard of short documents has them. The
node sees one device, as the deployment's does, and its packs are raw
(`compressed_pack` off), as the deployment's are.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from esbench import compare, corpus  # noqa: E402

from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.node import Node, serve  # noqa: E402
from elasticsearch_tpu.ops import reference_dense, reference_impl  # noqa: E402
from elasticsearch_tpu.parallel import distributed as dist  # noqa: E402
from elasticsearch_tpu.parallel import mesh as mesh_mod  # noqa: E402
from elasticsearch_tpu.search import dsl, tpu_service  # noqa: E402

from test_tpu_serving import svc  # noqa: E402,F401 (fixture)

#: the configuration's own law (`benchmarks/configs/msmarco-stop-1chip.json`)
#: with the corpus and the query set cut to a CPU's size
GENERATOR = {"docs": 10000, "vocab_size": 30000, "zipf_s": 1.07,
             "mean_length": 55, "corpus_seed": 23, "num_queries": 300,
             "query_terms_min": 2, "query_terms_max": 12,
             "query_band_lo": 20, "query_band_hi": 3000,
             "query_law": "stopmix", "query_terms_mean": 6,
             "query_stop_share": 0.3}
SHARDS = 2
INDEX = "msmarco"
FIELD = "body"
#: the wide index: one shard of short docs, a head word and sixteen words
#: of three slots each (a quarter of the docs less a little: not heads)
WIDE = "wide"
WIDE_DOCS = 40000
WIDE_TAIL = [f"t{i}" for i in range(16)]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Http:
    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)

    def request(self, method, path, body=None):
        raw = body if isinstance(body, (bytes, type(None))) \
            else json.dumps(body).encode("utf-8")
        self.conn.request(method, path, body=raw,
                          headers={"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        return resp.status, json.loads(resp.read())

    def search(self, index, words, size):
        """The cell's request: `match` OR, `_source` false."""
        status, body = self.request("POST", f"/{index}/_search", {
            "query": {"match": {FIELD: " ".join(words)}},
            "size": size, "_source": False})
        assert status == 200, body
        return body

    def stats(self):
        status, body = self.request("GET", "/_tpu/stats")
        assert status == 200
        return body

    def bulk(self, index, docs):
        lines = []
        for doc_id, text in docs:
            lines.append(json.dumps({"index": {"_index": index,
                                               "_id": doc_id}}))
            lines.append(json.dumps({FIELD: text}))
        status, res = self.request("POST", "/_bulk",
                                   ("\n".join(lines) + "\n").encode("utf-8"))
        assert status == 200 and not res["errors"]


def _wide_docs():
    """Short docs: `h` in three of five, each of WIDE_TAIL in 24% (9,600
    postings: three slots, under a quarter of the docs), a filler word or
    two; a word repeats now and then, so that scores spread."""
    rng = np.random.default_rng(4602)
    docs = []
    for i in range(WIDE_DOCS):
        words = [f"f{int(f)}" for f in rng.integers(0, 500, rng.integers(1, 3))]
        if rng.random() < 0.6:
            words += ["h"] * int(rng.integers(1, 3))
        words += [t for t in WIDE_TAIL if rng.random() < 0.24]
        if rng.random() < 0.2:
            words.append(words[0])
        docs.append((f"w{i}", " ".join(words)))
    return docs


@pytest.fixture(scope="module")
def stop(tmp_path_factory):
    """One node over one device; the law's corpus in 2 shards and the
    wide index in 1, both through REST `_bulk`."""
    saved = dict(tpu_service.KERNEL_CONFIG)
    corp = corpus.generate_corpus(GENERATOR)
    words = [corpus.word(i) for i in range(corp.vocab_size)]
    one = jax.devices()[:1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tpu_service, "_n_local_devices", lambda: 1)
        patch.setattr(
            tpu_service, "make_mesh",
            lambda devices=None, shape=None: mesh_mod.make_mesh(
                one if devices is None else devices, shape))
        node = Node(str(tmp_path_factory.mktemp("stop")), settings=Settings.of({
            "search.tpu_serving.kernel.compressed_pack": False}))
    server = serve(node, port=0)
    http_ = _Http(server.server_address[1])
    heads_patch = pytest.MonkeyPatch()
    heads_patch.setattr(dist, "HEAD_MIN_SLOTS", 1)
    try:
        for index, shards in ((INDEX, SHARDS), (WIDE, 1)):
            status, _ = http_.request("PUT", f"/{index}", {
                "settings": {"number_of_shards": shards},
                "mappings": {"properties": {FIELD: {"type": "text"}}}})
            assert status == 200
        http_.bulk(INDEX, [(corpus.doc_id(i), corpus.doc_text(corp, i, words))
                           for i in range(corp.num_docs)])
        http_.bulk(WIDE, _wide_docs())
        for index in (INDEX, WIDE):
            assert http_.request("POST", f"/{index}/_refresh")[0] == 200
            http_.search(index, ["f0"], 10)
        yield {"node": node, "http": http_, "port": server.server_address[1],
               "queries": corpus.generate_queries(GENERATOR)}
    finally:
        heads_patch.undo()
        http_.conn.close()
        server.shutdown()
        server.server_close()
        node.close()
        tpu_service.KERNEL_CONFIG.update(saved)


def _segments(node, index):
    """Each shard's segments, shard by shard, as the pack's rows hold them."""
    svc = node.indices.indices[index]
    return [[view.segment for view in svc.shards[n].acquire_searcher().views]
            for n in sorted(svc.shards)]


def _resident(node, index):
    resident = node.tpu_search.packs.peek((index, FIELD))
    assert resident is not None and resident.comp_streams is None
    return resident


def _with_ties(hits, k):
    """Best-first (id, score) → the first k and every hit within the
    near-tie of the k-th after them, as the benchmark's reference lists."""
    if len(hits) > k:
        kth = hits[k - 1][1]
        while k < len(hits) and hits[k][1] >= kth * (1 - 10 * compare.REL_TOL):
            k += 1
    return [h[0] for h in hits[:k]], [h[1] for h in hits[:k]]


def _impl_reference(segments, terms, k):
    """`reference_impl`: per-shard statistics, every segment scored."""
    hits = []
    for si, segs in enumerate(segments):
        for gi, scores in enumerate(reference_impl.score_match_query(
                segs, FIELD, terms)):
            hits += [(segs[gi].doc_ids[d], float(s), si, gi, d)
                     for d in np.flatnonzero(scores > 0).tolist()
                     for s in (scores[d],)]
    hits.sort(key=lambda h: (-h[1], h[2], h[3], h[4]))
    return (len(hits), *_with_ties(hits, k))


def _dense_reference(segments, terms, k):
    total, hits = reference_dense.search(segments, FIELD, terms, k + 64)
    return (total, *_with_ties(
        [(segments[si][gi].doc_ids[d], s) for si, gi, d, s in hits], k))


def _hold(resp, segments, terms, k):
    """The response against both references → the widest score gap."""
    assert resp["hits"]["total"]["relation"] == "eq"
    gap = 0.0
    for total, ids, scores in (_impl_reference(segments, terms, k),
                               _dense_reference(segments, terms, k)):
        compare.compare_response(resp, total, ids, scores, k)
        gap = max(gap, compare.score_gap(resp, scores))
    assert gap <= compare.REL_TOL
    return gap


def _rise(after, before, block):
    return {key: after[block][key] - before[block].get(key, 0)
            for key in after[block]
            if after[block][key] != before[block].get(key, 0)}


class _Compiles:
    """Backend compile events while the block runs."""

    def __enter__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def _listen(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append(kw.get("fun_name"))

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


#: queries by frequency rank: the toy pack's head terms are ranks 0-2
QUERIES = {
    "no_head": [20, 57, 300],
    "one_head": [0, 57, 300, 1200],
    "several_heads": [0, 1, 2, 44, 900],
    "heads_only": [0, 1, 2],
    "one_head_only": [2],
    "long_tail": [1, 21, 33, 48, 77, 150, 260, 420, 700, 1500],
    "dedupe": [2, 3, 4, 5],
}


def test_the_toy_pack_s_head_terms_are_the_law_s_three_most_frequent(stop):
    pack = _resident(stop["node"], INDEX).pack
    assert dist.head_terms(pack) == [corpus.word(r) for r in range(3)]
    assert list(pack.term_table.head_index(pack)) == dist.head_terms(pack)


@pytest.mark.parametrize("size", [10, 1000])
@pytest.mark.parametrize("case", sorted(QUERIES))
def test_every_query_equals_both_references(stop, case, size):
    """One request of the case's words: the answer is both references'
    and the route the one its head terms give, with no fallback."""
    terms = [corpus.word(r) for r in QUERIES[case]]
    http_ = stop["http"]
    before = http_.stats()
    resp = http_.search(INDEX, terms, size)
    after = http_.stats()
    _hold(resp, _segments(stop["node"], INDEX), terms, size)
    routed = _rise(after, before, "route")
    if case == "no_head":
        assert list(routed) in (["pruned_full_s16"], ["pruned_full_s32"])
    else:
        assert routed == {"heads": 1}
        assert _rise(after, before, "heads")["queries"] == 1
    assert after["fallback"] == before["fallback"]


def test_the_dedupe_case_has_tail_docs_among_the_head_only_best(stop):
    """The `dedupe` case is one: docs of the head-only top 1024 also hold
    a tail term, so the kernel's two lists share docs and each must keep
    its larger copy (the answer equals the references above)."""
    segments = _segments(stop["node"], INDEX)
    head, tail = [corpus.word(2)], [corpus.word(r) for r in (3, 4, 5)]
    _, best, _ = _impl_reference(segments, head, 1024)
    _, with_tail, _ = _impl_reference(segments, tail, 10 ** 6)
    assert len(set(best) & set(with_tail)) > 100


@pytest.mark.parametrize("size", [10, 1000])
@pytest.mark.parametrize("n_tail, need, launch", [
    (4, 12, "heads_b64_s16"), (6, 18, "heads_b8_s128"),
    (16, 48, "heads_b8_s128")])
def test_a_tail_of_several_slots_takes_its_rung(stop, n_tail, need, launch,
                                                size):
    """The wide index: `h` is its head term, each t<i> three slots. A tail
    of four needs 12 slots (the 16-slot rung, whose least launch is 64
    rows), one of six 18 (past the 16-slot rung: the full ladder's 32,
    which the heads route leaves to its widest) and one of sixteen 48
    (the 128-slot rung, at eight rows). The launch is that rung's, and
    the answer both references'."""
    resident = _resident(stop["node"], WIDE)
    assert dist.head_terms(resident.pack) == ["h"]
    terms = ["h"] + WIDE_TAIL[:n_tail]
    tail = tuple(WIDE_TAIL[:n_tail])
    assert tpu_service._slots_of(resident, tail) == need
    flat = tpu_service.FlatQuery(FIELD, terms, 1.0, 1)
    assert tpu_service._heads_plan(resident, flat) == (
        16 if need <= 16 else 128, tail)
    http_ = stop["http"]
    before = http_.stats()
    resp = http_.search(WIDE, terms, size)
    after = http_.stats()
    _hold(resp, _segments(stop["node"], WIDE), terms, size)
    assert _rise(after, before, "launches") == {launch: 1}


def _search_all(port, index, queries, size):
    """One client a query, connected one after the other (the server's
    listen queue is short), then all sent at once, so that the batcher
    forms a train of them → the responses in the queries' order."""
    clients = [_Http(port) for _ in queries]
    try:
        for client in clients:
            client.conn.connect()
        with ThreadPoolExecutor(max_workers=len(queries)) as pool:
            return list(pool.map(lambda c, q: c.search(index, q, size),
                                 clients, queries))
    finally:
        for client in clients:
            client.conn.close()


def test_the_law_s_questions_in_trains_equal_the_references(stop):
    """24 of the configuration's own questions at once: those that hold a
    head term take the heads route in one train, the others the ladder or
    the exact path; every answer is the references', nothing falls back,
    and a second pass compiles nothing."""
    resident = _resident(stop["node"], INDEX)
    heads = resident.pack.term_table.head_index(resident.pack)
    queries = [[corpus.word(t) for t in q] for q in stop["queries"][:24]]
    n_heads = sum(any(t in heads for t in q) for q in queries)
    assert 0 < n_heads < len(queries)
    http_ = stop["http"]
    _search_all(stop["port"], INDEX, queries, 1000)
    before = http_.stats()
    with _Compiles() as compiles:
        responses = _search_all(stop["port"], INDEX, queries, 1000)
    after = http_.stats()
    assert compiles.events == []
    segments = _segments(stop["node"], INDEX)
    for terms, resp in zip(queries, responses):
        _hold(resp, segments, terms, 1000)
    routed = _rise(after, before, "route")
    assert routed["heads"] == n_heads
    assert after["fallback"] == before["fallback"]
    assert _rise(after, before, "heads")["queries"] == n_heads


def test_the_stage_counter_route_and_programs_reach_stats_and_prometheus(stop):
    """The head block was built once (stage `heads_block`), its bytes are
    the pack's `heads_block` and the counter's; the programs the first
    heads launch readied are `heads_programs`; the counter and the route
    are Prometheus families."""
    http_ = stop["http"]
    for size in (10, 1000):
        http_.search(INDEX, [corpus.word(0), corpus.word(30)], size)
    http_.search(WIDE, ["h", "t0"], 10)
    stats = http_.stats()
    resident = _resident(stop["node"], INDEX)
    block = resident.heads_block
    assert block is not None and block.terms == tuple(
        corpus.word(r) for r in range(3))
    assert stats["stages"]["heads_block"]["count"] == 2  # both indexes
    detail = stats["pack_cache"]["packs"][f"{INDEX}/{FIELD}"]["heads_block"]
    assert detail == {"terms": 3, "rows": 8, "shard_rows": SHARDS,
                      "docs_per_row": block.width, "bytes": block.nbytes}
    assert block.nbytes == 4 * 8 * SHARDS * block.width
    assert stats["heads"]["block_bytes"] >= block.nbytes
    assert stats["heads"]["launches"] >= 1
    labels = ["heads_b128_s16", "heads_b64_s16", "heads_b8_s128"]
    assert stats["heads_programs"][f"{INDEX}/{FIELD}"] == labels
    # a set for each k bucket the pack has served (k_out 128 and 1024)
    ready = resident.heads_ready
    assert {k_out for _mesh, k_out, _rows in ready} == {128, 1024}
    for programs in ready.values():
        assert sorted(dist.heads_program_name(rows, slots)
                      for slots, rows in programs) == labels
    prom = stop["node"].metrics.prometheus_text()
    for kind in ("queries", "launches", "terms", "block_bytes"):
        assert f'es_tpu_kernel_heads_total{{kind="{kind}"}}' in prom
    assert 'es_tpu_kernel_route_total{route="heads"}' in prom


def _dot_generals(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_generals(sub)


def test_every_matmul_of_the_heads_program_runs_at_highest_precision(stop):
    """The CPU cannot tell a bfloat16 product from a float32 one by value,
    so the program is held to it by its jaxpr: every `dot_general` of
    `jit_heads_*` carries `Precision.HIGHEST`."""
    resident = _resident(stop["node"], INDEX)
    mesh = stop["node"].tpu_search.packs.mesh
    block = resident.heads_block
    program = tpu_service.HeadsProgram(8, 128, 1024)
    fn = tpu_service._make_heads_search(resident, mesh, program)
    ops = np.zeros((SHARDS, 8, 3 * 128 + 1), np.float32)
    w = np.zeros((8, block.h_pad), np.float32)
    jaxpr = jax.make_jaxpr(fn)(*resident.imp_device_arrays, block.array,
                               ops, w)
    dots = list(_dot_generals(jaxpr.jaxpr))
    assert dots
    for eqn in dots:
        assert eqn.params["precision"] == (jax.lax.Precision.HIGHEST,
                                           jax.lax.Precision.HIGHEST)


def test_the_configuration_is_the_standing_deployments_data():
    """`msmarco-stop-1chip` serves `msmarco-1chip`'s corpus and shards,
    key for key, and differs from it in the query law alone; this file's
    toy law is its generator block with the docs and queries cut."""
    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs")

    def load(name):
        with open(os.path.join(configs, name + ".json"), encoding="utf-8") as f:
            return json.load(f)

    ours, theirs = load("msmarco-stop-1chip"), load("msmarco-1chip")
    for key in ("index", "node_settings", "chips", "reduced"):
        assert ours[key] == theirs[key], key
    law = {"query_law", "query_terms_max", "query_terms_mean",
           "query_stop_share"}
    assert {k: v for k, v in ours["generator"].items() if k not in law} \
        == {k: v for k, v in theirs["generator"].items() if k not in law}
    assert ours["generator"]["query_law"] == "stopmix"
    assert {k: v for k, v in ours["generator"].items()
            if k not in ("docs", "num_queries")} == \
        {k: v for k, v in GENERATOR.items() if k not in ("docs", "num_queries")}


def test_the_deployment_s_head_terms_are_ranks_0_to_17_and_no_band_word():
    """The head rule at the deployment's size, from the configuration's
    own law (Zipf weights and log-normal lengths of `esbench/corpus.py`):
    a word's expected df share on a shard row of 552,960 docs is at least
    a quarter, with postings past HEAD_MIN_SLOTS slots, for ranks 0-17
    and for no rank of the band law (20-2999), so no query of
    `msmarco-1chip`, `msmarco-and-1chip` or `msmarco-default-1chip` holds
    a head term."""
    gen = dict(GENERATOR, docs=1105920)
    rng = np.random.default_rng(0)
    mu = np.log(gen["mean_length"]) - corpus.LENGTH_SIGMA ** 2 / 2
    lengths = np.clip(rng.lognormal(mu, corpus.LENGTH_SIGMA, 200000).astype(
        np.int64), corpus.MIN_LENGTH, 6 * gen["mean_length"])
    p = corpus.zipf_probs(gen["vocab_size"], gen["zipf_s"])[:3000]
    share = np.array([np.mean(1.0 - (1.0 - pr) ** lengths) for pr in p])
    row_docs = gen["docs"] // 2
    head = ((dist.HEAD_DF_DIVISOR * share >= 1.0)
            & (share * row_docs > dist.HEAD_MIN_SLOTS * dist.CHUNK_CAP))
    assert np.flatnonzero(head).tolist() == list(range(18))
    assert not head[gen["query_band_lo"]:].any()


def test_a_delta_chain_routes_each_pack_by_its_own_head_terms(
        svc, seeded_np, monkeypatch):  # noqa: F811
    """An append-only refresh rides as a delta pack chained on the base;
    each pack reads its own head terms (the delta's few docs have none),
    so the base answers a head query on the heads route and the delta on
    today's, and the union equals `reference_impl` under the chain's
    statistics: each pack's own, shard by shard."""
    monkeypatch.setattr(dist, "HEAD_MIN_SLOTS", 1)
    idx = svc.create_index(
        "chain", Settings.of({"index": {"number_of_shards": 1}}),
        {"properties": {FIELD: {"type": "text"}}})
    shard = idx.shard(0)
    others = ["beta", "gamma", "delta", "epsilon", "zeta", "eta"]

    def add(lo, hi):
        for i in range(lo, hi):
            words = ["alpha"] * int(seeded_np.integers(1, 3)) + [
                others[int(w)] for w in seeded_np.integers(0, len(others), 3)]
            shard.apply_index_on_primary(f"d{i}", {FIELD: " ".join(words)})
        idx.refresh()

    add(0, 4500)
    tpu = tpu_service.TpuSearchService(
        window_s=0.0, batch_timeout_s=300.0, compressed_pack=False,
        delta={"enabled": True})
    try:
        query = dsl.MatchQuery(field=FIELD, query="alpha beta")
        assert tpu.try_search(idx, query, k=50) is not None
        add(4500, 4540)
        before = tpu.stats()
        result = tpu.try_search(idx, query, k=50)
        after = tpu.stats()
        chain = tpu.packs.get_chain(idx, FIELD)
        assert len(chain.deltas) == 1
        assert dist.head_terms(chain.base.pack) == ["alpha"]
        assert dist.head_terms(chain.deltas[0].pack) == []
        assert _rise(after, before, "route") == {"heads": 1,
                                                 "pruned_full_s32": 1}
        hits = []
        for pack in (chain.base, chain.deltas[0]):
            segments = [s for s in pack.row_segments if s is not None]
            stats = [s.field_stats[FIELD] for s in segments]
            doc_count = sum(st.doc_count for st in stats)
            avgdl = sum(st.sum_total_term_freq for st in stats) / doc_count
            dfs = {t: reference_impl.shard_doc_freq(segments, FIELD, t)
                   for t in ("alpha", "beta")}
            for seg in segments:
                scores = reference_impl.score_segment(
                    seg, FIELD, ["alpha", "beta"], doc_count=doc_count,
                    avgdl=avgdl, doc_freqs=dfs)
                hits += [(seg.doc_ids[d], float(scores[d]))
                         for d in np.flatnonzero(scores > 0).tolist()]
        hits.sort(key=lambda h: -h[1])
        ids, scores = _with_ties(hits, 50)
        resp = {"_shards": {"failed": 0}, "hits": {
            "total": {"value": result.total_hits,
                      "relation": result.total_relation},
            "hits": [{"_id": h[4], "_score": h[0]} for h in result.hits]}}
        compare.compare_response(resp, len(hits), ids, scores, 50)
    finally:
        tpu.close()
        tpu_service.KERNEL_CONFIG["compressed_pack"] = True


@pytest.mark.parametrize("case", ["heads_only", "one_head", "long_tail"])
def test_the_python_operand_builders_answer_alike(stop, case, monkeypatch):
    """Without the native operand builder (a process where the library
    did not build) the heads launch's tail operand comes from the Python
    builders, an empty tail too, and the answer is the references'."""
    monkeypatch.setattr(dist, "native_operand_builder", lambda: None)
    terms = [corpus.word(r) for r in QUERIES[case]] + (
        [] if case == "heads_only" else ["zz-no-such-word"])
    http_ = stop["http"]
    before = http_.stats()
    resp = http_.search(INDEX, terms, 1000)
    after = http_.stats()
    _hold(resp, _segments(stop["node"], INDEX), terms, 1000)
    assert _rise(after, before, "route") == {"heads": 1}
