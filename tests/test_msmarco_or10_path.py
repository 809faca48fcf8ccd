"""The default search request on the standing deployment at toy size
(the benchmark's configuration `msmarco-default-1chip`, `msmarco-1chip`'s
data and shards, and its cell `msmarco-default-1chip.or10-closed384`):
MS MARCO's law in the configuration's 2 shards on one device, `match` OR
at `size` 10
with `_source` returned, served over REST and held to the benchmark's
own plain numpy reference (`benchmarks/esbench/reference.py`, per-shard
statistics and ES routing, importing nothing of the program) by the rule
that decides the cell's `correct` (`esbench/compare.py`): ids and scores
equal (1e-5 relative, near-tie swaps only), `hits.total` exact; every
hit's `_source` is the document that was indexed, rendered by the native
renderer from the pack's source table; every full-path launch is a member
of `full_program_set(pack, 10)` (the k_out-128 family), made ready ahead
of time by the first answer, so that no train compiles after it; and the
no-hidden-fallback counters read 0.

The node sees one device, as the deployment's does (tier-1 shows jax
eight virtual ones and the node has no setting for fewer, so the test
steers it as `tests/test_msmarco_4chip_path.py` does). The toy pack would
fit the compressed format, which the deployment's does not, so
`compressed_pack` is off: the pack is raw and has the full path.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from esbench import compare, corpus, reference  # noqa: E402

from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.node import Node, serve  # noqa: E402
from elasticsearch_tpu.parallel import mesh as mesh_mod  # noqa: E402
from elasticsearch_tpu.search import tpu_service  # noqa: E402

#: the configuration's own law (`benchmarks/configs/msmarco-default-1chip.json`,
#: `msmarco-1chip.json`'s generator block) with the corpus and the query set
#: cut to a CPU's size
GENERATOR = {"docs": 6000, "vocab_size": 30000, "zipf_s": 1.07,
             "mean_length": 55, "corpus_seed": 23, "num_queries": 160,
             "query_terms_min": 2, "query_terms_max": 5,
             "query_band_lo": 20, "query_band_hi": 3000}
SHARDS = 2
SIZE = 10
INDEX = "msmarco"
FIELD = "body"
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

    def search(self, terms):
        """The cell's request: the traffic file's `size` 10 and
        `"_source": true`."""
        status, body = self.request("POST", f"/{INDEX}/_search", {
            "query": {"match": {FIELD: corpus.query_text(terms)}},
            "size": SIZE, "_source": True})
        assert status == 200, body
        return body

    def stats(self):
        status, body = self.request("GET", "/_tpu/stats")
        assert status == 200
        return body


@pytest.fixture(scope="module")
def msmarco(tmp_path_factory):
    """One node over one device, the corpus indexed through REST `_bulk`
    in 2 shards, warmed by one answer of the cell's shape; the reference's
    own index of the corpus and each doc's indexed source."""
    saved = dict(tpu_service.KERNEL_CONFIG)
    corp = corpus.generate_corpus(GENERATOR)
    queries = corpus.generate_queries(GENERATOR)
    words = [corpus.word(i) for i in range(corp.vocab_size)]
    sources, lines = {}, []
    for i in range(corp.num_docs):
        sources[corpus.doc_id(i)] = {FIELD: corpus.doc_text(corp, i, words)}
        lines.append(json.dumps({"index": {"_index": INDEX,
                                           "_id": corpus.doc_id(i)}}))
        lines.append(json.dumps(sources[corpus.doc_id(i)]))
    one = jax.devices()[:1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tpu_service, "_n_local_devices", lambda: 1)
        patch.setattr(
            tpu_service, "make_mesh",
            lambda devices=None, shape=None: mesh_mod.make_mesh(
                one if devices is None else devices, shape))
        node = Node(str(tmp_path_factory.mktemp("or10")), settings=Settings.of({
            "search.tpu_serving.kernel.compressed_pack": False}))
    server = serve(node, port=0)
    http_ = _Http(server.server_address[1])
    try:
        status, _ = http_.request("PUT", f"/{INDEX}", {
            "settings": {"number_of_shards": SHARDS},
            "mappings": {"properties": {FIELD: {"type": "text"}}}})
        assert status == 200
        status, res = http_.request("POST", "/_bulk",
                                    ("\n".join(lines) + "\n").encode("utf-8"))
        assert status == 200 and not res["errors"]
        assert http_.request("POST", f"/{INDEX}/_refresh")[0] == 200
        # the first answer places the pack, makes the k_out-128 programs
        # ready and builds the source table: the cell's warm-up in small
        http_.search(queries[0])
        resident = node.tpu_search.packs.peek((INDEX, FIELD))
        assert resident is not None and resident.comp_streams is None
        assert resident.pack.num_shards == SHARDS
        assert node.tpu_search.packs.mesh.devices.shape == (1, 1)
        shards = reference.build_shard_indexes(
            corp.flat, corp.offsets, SHARDS,
            sorted({t for q in queries for t in q}))
        yield {"node": node, "http": http_, "port": server.server_address[1],
               "resident": resident, "mesh": node.tpu_search.packs.mesh,
               "queries": queries, "shards": shards, "sources": sources}
    finally:
        http_.conn.close()
        server.shutdown()
        server.server_close()
        node.close()
        tpu_service.KERNEL_CONFIG.update(saved)


def _of_terms(queries, n_terms, n=8):
    mine = [q for q in queries if len(q) == n_terms][:n]
    assert len(mine) == n, (n_terms, len(mine))
    return mine


def _search_all(port, queries):
    """One client a query, all at once, so that the batcher forms a
    train of them → the responses in the queries' order."""
    def one(q):
        client = _Http(port)
        try:
            return client.search(q)
        finally:
            client.conn.close()

    with ThreadPoolExecutor(max_workers=len(queries)) as pool:
        return list(pool.map(one, queries))


def _rise(after, before, block):
    return {key: after[block][key] - before[block].get(key, 0)
            for key in after[block]}


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


@pytest.mark.parametrize("n_terms", [2, 3, 4, 5])
def test_every_search_equals_the_reference_with_its_source(msmarco, n_terms):
    """ids, scores within 1e-5 relative and `hits.total` as the reference's
    OR top-10; every hit's `_source` the document indexed, the block
    rendered natively; no fallback, no compile."""
    http_ = msmarco["http"]
    mine = _of_terms(msmarco["queries"], n_terms)
    before = http_.stats()
    with _Compiles() as compiles:
        responses = _search_all(msmarco["port"], mine)
    after = http_.stats()
    assert compiles.events == []
    gap = 0.0
    for q, resp in zip(mine, responses):
        total, docs, scores = reference.reference_topk(
            msmarco["shards"], q, SIZE)
        assert total > SIZE
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
        gap = max(gap, compare.score_gap(resp, scores.tolist()))
        assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
        for hit in resp["hits"]["hits"]:
            assert list(hit) == ["_index", "_id", "_score", "_source"]
            assert hit["_source"] == msmarco["sources"][hit["_id"]]
    assert gap <= compare.REL_TOL
    rendered = _rise(after, before, "render")
    assert rendered == {"native": len(mine), "python": 0}
    fetched = _rise(after, before, "fetch")
    assert fetched["hits"] == SIZE * len(mine)
    assert fetched["source_bytes"] == sum(
        len(json.dumps(h["_source"], separators=(",", ":")))
        for resp in responses for h in resp["hits"]["hits"])
    routed = _rise(after, before, "route")
    assert sum(v for r, v in routed.items() if r.startswith("pruned_full_")) \
        == len(mine), routed
    assert compare.failures(compare.kernel_checks(
        before, after, len(mine), 1, jax.devices()[0].platform)) == []


@pytest.mark.parametrize("fill, launched", [
    (1, {"full_s32": [8]}),
    (9, {"full_s32": [8, 8]}),
    (40, {"full_s16": [64]}),
    (128, {"full_s16": [128]})])
def test_every_launch_is_a_member_of_the_k10_program_set(msmarco, fill,
                                                          launched):
    """A train of any fill at k 10 calls one of `full_program_set(pack,
    10)`'s executables, which the first answer compiled: no backend
    compile, and the answers are the reference's."""
    resident, mesh = msmarco["resident"], msmarco["mesh"]
    programs = tpu_service.full_program_set(resident, SIZE)
    assert {p.k_out for p in programs} == {128}
    members = {(p.label, p.rows, p.k_out, p.variant) for p in programs}
    train = (msmarco["queries"] * 2)[:fill]
    flats = [tpu_service.FlatQuery(FIELD, [corpus.word(t) for t in q], 1.0, 1)
             for q in train]
    before = msmarco["http"].stats()
    with _Compiles() as compiles:
        results = tpu_service.execute_flat_batch(resident, flats, SIZE, mesh)
    after = msmarco["http"].stats()
    assert compiles.events == []
    assert {path: n for path, n in _rise(after, before, "launches").items()
            if n} == {path: len(rows) for path, rows in launched.items()}
    # every program this pack has launched, the fixture's and the REST
    # trains' too, is a member of the set at k 10
    full = {(path, rows, k_out, variant)
            for path, rows, k_out, variant, _mesh in resident.launched
            if path.startswith("full_")}
    assert full and {(f"{path}_b{rows}", rows, k_out, variant)
                     for path, rows, k_out, variant in full} <= members
    assert len(results) == fill
    for q, res in list(zip(train, results))[::8]:
        total, docs, scores = reference.reference_topk(
            msmarco["shards"], q, SIZE)
        resp = {"_shards": {"failed": 0}, "hits": {
            "total": {"value": res.total_hits, "relation": res.total_relation},
            "hits": [{"_id": h[-1], "_score": h[0]} for h in res.hits]}}
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)


def test_the_pack_holds_one_source_table_for_every_doc(msmarco):
    """The source table was built once, by the first `_source` answer, as
    the stage `source_table`; it covers every doc of the pack, and each
    literal is the doc's source as the Python path writes it."""
    resident = msmarco["resident"]
    table = resident.source_json
    assert table is not None
    assert len(table.offsets) == len(resident.id_cat) + 1
    stats = msmarco["http"].stats()
    assert stats["stages"]["source_table"]["count"] == 1
    assert stats["pack_cache"]["packs"][f"{INDEX}/{FIELD}"][
        "source_table_bytes"] == table.blob.nbytes + table.offsets.nbytes
    blob = table.blob.tobytes()
    for at in (0, 1, len(resident.id_cat) // 2, len(resident.id_cat) - 1):
        literal = blob[table.offsets[at]:table.offsets[at + 1]]
        assert json.loads(literal) == msmarco["sources"][resident.id_cat[at]]
        assert literal.decode("ascii") == json.dumps(
            json.loads(literal), separators=(",", ":"))


def test_the_configuration_is_the_standing_deployments_data():
    """`msmarco-default-1chip` serves `msmarco-1chip`'s corpus, shards and
    queries to the byte (one law, one seed): only its source, deployment,
    guarantees and assumptions speak of the request; this file's toy law
    is that block with the docs and queries cut."""
    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs")

    def load(name):
        with open(os.path.join(configs, name + ".json"), encoding="utf-8") as f:
            return json.load(f)

    ours, theirs = load("msmarco-default-1chip"), load("msmarco-1chip")
    for key in ("generator", "index", "node_settings", "chips", "reduced"):
        assert ours[key] == theirs[key], key
    assert ours["source"] != theirs["source"]
    assert {k: v for k, v in ours["generator"].items()
            if k not in ("docs", "num_queries")} == \
        {k: v for k, v in GENERATOR.items() if k not in ("docs", "num_queries")}


def test_the_band_law_pack_builds_no_head_block(msmarco):
    """The heads route leaves the band law alone: the toy pack has no
    head term (`dist.head_terms`), its answers above built no head block,
    readied no `jit_heads_*` program, and `/_tpu/stats` lists none."""
    from elasticsearch_tpu.parallel import distributed as dist

    resident = msmarco["resident"]
    assert dist.head_terms(resident.pack) == []
    assert resident.pack.term_table.head_index(resident.pack) == {}
    stats = msmarco["http"].stats()
    assert stats["heads_programs"] == {"msmarco/body": []}
    assert resident.heads_block is None and resident.heads_ready == {}
    assert not any(path.startswith("heads_") for path, *_ in resident.launched)
    assert "heads_block" not in stats["pack_cache"]["packs"]["msmarco/body"]
