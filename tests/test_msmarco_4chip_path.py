"""The `msmarco-4chip` deployment at toy size (ISSUE 33): MS MARCO's law
in 8 shards over a 1×4 mesh, two shard rows a device, served over REST at
`size` 1000 and held to the benchmark's own plain numpy reference
(`benchmarks/esbench/reference.py`, per-shard statistics and ES routing,
importing nothing of the program); the same index served by one device
answers alike to the bit (the cross-chip merge's tie rule `(-score, gid)`
makes the devices' shares add up to the whole); the `cross_chip` counter
and the `batch_put` ring say what the mesh adds; and the ahead-of-time
executables of `full_program_set`, compiled for four devices, are the
programs a train launches.

The node builds its mesh from every device jax shows (eight virtual CPU
devices under tier-1) and has no setting for fewer, so the test steers
it: `make_mesh` and `_n_local_devices` as the service sees them are cut
to the first four devices, or to one.
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

from esbench import compare, corpus, layers, reference  # noqa: E402

from elasticsearch_tpu import native  # noqa: E402
from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.node import Node, serve  # noqa: E402
from elasticsearch_tpu.parallel import distributed as dist  # noqa: E402
from elasticsearch_tpu.parallel import mesh as mesh_mod  # noqa: E402
from elasticsearch_tpu.search import tpu_service  # noqa: E402

#: the configuration's own law (`benchmarks/configs/msmarco-4chip.json`)
#: with the corpus and the query set cut to a CPU's size
GENERATOR = {"docs": 6000, "vocab_size": 30000, "zipf_s": 1.07,
             "mean_length": 55, "corpus_seed": 23, "num_queries": 160,
             "query_terms_min": 2, "query_terms_max": 5,
             "query_band_lo": 20, "query_band_hi": 3000}
SHARDS = 8
SIZE = 1000
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
        status, body = self.request("POST", f"/{INDEX}/_search", {
            "query": {"match": {FIELD: corpus.query_text(terms)}},
            "size": SIZE, "_source": False})
        assert status == 200, body
        return body

    def stats(self):
        status, body = self.request("GET", "/_tpu/stats")
        assert status == 200
        return body


def _serve_on(n_devices: int, data_path: str, bulk: bytes):
    """A node whose service sees the first `n_devices` devices alone,
    with the corpus indexed through REST `_bulk` in 8 shards."""
    devices = jax.devices()[:n_devices]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tpu_service, "_n_local_devices", lambda: n_devices)
        patch.setattr(
            tpu_service, "make_mesh",
            lambda devices_=None, shape=None: mesh_mod.make_mesh(
                devices if devices_ is None else devices_, shape))
        node = Node(data_path, settings=Settings.of({
            "search.tpu_serving.kernel.compressed_pack": False}))
        server = serve(node, port=0)
        http_ = _Http(server.server_address[1])
        status, _ = http_.request("PUT", f"/{INDEX}", {
            "settings": {"number_of_shards": SHARDS},
            "mappings": {"properties": {FIELD: {"type": "text"}}}})
        assert status == 200
        status, res = http_.request("POST", "/_bulk", bulk)
        assert status == 200 and not res["errors"]
        assert http_.request("POST", f"/{INDEX}/_refresh")[0] == 200
        # the first search builds and places the pack on the cut mesh
        http_.search([25, 400])
    resident = node.tpu_search.packs.peek((INDEX, FIELD))
    assert resident is not None and resident.comp_streams is None
    assert node.tpu_search.packs.mesh.devices.shape == (1, n_devices)
    return {"node": node, "server": server, "http": http_,
            "resident": resident, "mesh": node.tpu_search.packs.mesh}


@pytest.fixture(scope="module")
def hosts(tmp_path_factory):
    """The same index on one device and on the 1×4 mesh, and the
    reference's own index of the corpus."""
    saved = dict(tpu_service.KERNEL_CONFIG)
    corp = corpus.generate_corpus(GENERATOR)
    queries = corpus.generate_queries(GENERATOR)
    words = [corpus.word(i) for i in range(corp.vocab_size)]
    lines = []
    for i in range(corp.num_docs):
        lines.append(json.dumps({"index": {"_index": INDEX,
                                           "_id": corpus.doc_id(i)}}))
        lines.append(json.dumps({FIELD: corpus.doc_text(corp, i, words)}))
    bulk = ("\n".join(lines) + "\n").encode("utf-8")
    served = {}
    try:
        for n in (1, 4):
            served[n] = _serve_on(n, str(tmp_path_factory.mktemp(f"host{n}")),
                                  bulk)
        shards = reference.build_shard_indexes(
            corp.flat, corp.offsets, SHARDS,
            sorted({t for q in queries for t in q}))
        yield {"served": served, "queries": queries, "shards": shards}
    finally:
        for host in served.values():
            host["http"].conn.close()
            host["server"].shutdown()
            host["server"].server_close()
            host["node"].close()
        tpu_service.KERNEL_CONFIG.update(saved)


def _of_terms(queries, n_terms, n=8):
    mine = [q for q in queries if len(q) == n_terms][:n]
    assert len(mine) == n, (n_terms, len(mine))
    return mine


def _search_all(host, queries):
    """One client a query, all at once, so that the batcher forms a
    train of them (a launch costs the CPU as much for one row as for
    eight) → the responses in the queries' order."""
    port = host["server"].server_address[1]

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


# ---------------------------------------------------------------------------
# the served path against the reference, on one device and on the mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_terms", [2, 3, 4, 5])
@pytest.mark.parametrize("n_devices", [1, 4], ids=["one_device", "mesh_1x4"])
def test_every_search_equals_the_numpy_reference(hosts, n_devices, n_terms):
    """ids, scores within 1e-5 relative, `hits.total` equal, no fallback,
    on the mesh the node reports."""
    host = hosts["served"][n_devices]
    before = host["http"].stats()
    mine = _of_terms(hosts["queries"], n_terms)
    gap = 0.0
    for q, resp in zip(mine, _search_all(host, mine)):
        total, docs, scores = reference.reference_topk(hosts["shards"], q, SIZE)
        assert total > 0
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
        gap = max(gap, compare.score_gap(resp, scores.tolist()))
    after = host["http"].stats()
    assert gap <= compare.REL_TOL
    assert after["fallback"] == before["fallback"]
    assert after["served"] - before["served"] == len(mine)
    assert after["devices"]["mesh_devices"] == n_devices
    assert after["devices"]["mesh_devices_full"] == n_devices
    assert host["resident"].pack.num_shards == SHARDS
    routed = _rise(after, before, "route")
    assert sum(v for r, v in routed.items() if r.startswith("pruned_full_")) \
        == len(mine), routed


@pytest.mark.parametrize("n_terms", [2, 3, 4, 5])
def test_one_device_and_the_mesh_answer_alike(hosts, n_terms):
    """Every query: the same ids in the same order, the same scores to
    the bit, the same `hits.total`. Each device ranks its own shard rows
    and the merge orders the gathered candidates by `(-score, gid)`, the
    order one device gives all eight rows: the shares add up to the whole."""
    mine = _of_terms(hosts["queries"], n_terms)
    for a, b in zip(_search_all(hosts["served"][1], mine),
                    _search_all(hosts["served"][4], mine)):
        a, b = a["hits"], b["hits"]
        assert a["total"] == b["total"] and a["hits"]
        assert [h["_id"] for h in a["hits"]] == [h["_id"] for h in b["hits"]]
        assert [h["_score"] for h in a["hits"]] == \
            [h["_score"] for h in b["hits"]]


# ---------------------------------------------------------------------------
# what the mesh adds: the counter, the ring, the scope
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 4], ids=["one_device", "mesh_1x4"])
def test_cross_chip_counts_the_mesh_and_nothing_on_one_device(hosts, n_devices):
    """`cross_chip` `{launches, rows, devices}` rises by one launch, its
    rows as dispatched and the mesh's devices for every program sent to
    the 1×4 mesh, and by nothing for one device; the `batch_put` ring
    times the operand copy of every pruned launch on either."""
    host = hosts["served"][n_devices]
    before = host["http"].stats()
    mine = _of_terms(hosts["queries"], 3, n=2)
    for q in mine:
        host["http"].search(q)
    after = host["http"].stats()
    launches = sum(_rise(after, before, "launches").values())
    assert launches == len(mine)      # one client: a train of one each
    rise = _rise(after, before, "cross_chip")
    if n_devices == 1:
        assert rise == {"launches": 0, "rows": 0, "devices": 0}
    else:
        # a train of one rides at the 8-row bucket
        assert rise == {"launches": launches, "rows": 8 * launches,
                        "devices": 4 * launches}
    put, prep = after["stages"]["batch_put"], after["stages"]["batch_prep"]
    assert put["count"] - before["stages"].get("batch_put", {}).get("count", 0) \
        == launches
    assert put["count"] == prep["count"]
    assert 0.0 < put["seconds"] <= after["stages"]["batch_dispatch"]["seconds"]
    prom = host["node"].metrics.prometheus_text()
    assert 'es_tpu_kernel_cross_chip_total{kind="rows"}' in prom


@pytest.mark.parametrize("n_devices", [1, 4], ids=["one_device", "mesh_1x4"])
def test_term_table_counts_a_query_term_once_a_launch(hosts, n_devices):
    """`term_table.lookups` rises by the terms of the queries launched
    (one client: a launch a query), though the pack has eight shard rows
    on either host: a launch's operands resolve a term once a launch,
    not once a row. `/_tpu/stats` and the exposition carry the family."""
    host = hosts["served"][n_devices]
    mine = _of_terms(hosts["queries"], 4, n=2)
    before = host["http"].stats()
    for q in mine:
        host["http"].search(q)
    after = host["http"].stats()
    assert host["resident"].pack.num_shards == SHARDS
    assert sum(_rise(after, before, "launches").values()) == len(mine)
    rise = _rise(after, before, "term_table")
    assert rise["lookups"] == sum(len(q) for q in mine)
    assert 0 <= rise["columns"] <= rise["lookups"]
    prom = host["node"].metrics.prometheus_text()
    for kind in ("lookups", "columns"):
        assert f'es_tpu_kernel_term_table_total{{kind="{kind}"}}' in prom


@pytest.mark.parametrize("n_devices", [1, 4], ids=["one_device", "mesh_1x4"])
def test_the_python_builders_serve_where_the_library_is_absent(
        hosts, n_devices, monkeypatch):
    """A train of full-path launches with the native operand builder,
    then with `native.bind` giving None, as in a process where the
    library did not build: the same answers to the bit, the reference's,
    each launch counted `operands.native` and then `operands.python`,
    read by `operands_native_pct` as 100 and then 0 (and as nothing on a
    program without the counter); `/_tpu/stats` and the exposition carry
    the family."""
    host = hosts["served"][n_devices]
    resident, mesh = host["resident"], host["mesh"]
    train = _of_terms(hosts["queries"], 2) + _of_terms(hosts["queries"], 5)
    flats = [tpu_service.FlatQuery(FIELD, [corpus.word(t) for t in q], 1.0, 1)
             for q in train]
    reader = layers.find_reader("operands_native_pct.closed")
    assert reader({"window.term_table.lookups": 9.0}) is None  # a parent
    answers = {}
    for builder, other, share in (("native", "python", 100.0),
                                  ("python", "native", 0.0)):
        if builder == "python":
            monkeypatch.setattr(native, "bind", lambda *a, **k: None)
            monkeypatch.setattr(dist, "_OPERANDS_TRIED", False)
            monkeypatch.setattr(dist, "_OPERANDS_FN", None)
        before = host["http"].stats()
        results = tpu_service.execute_flat_batch(resident, flats, SIZE, mesh)
        after = host["http"].stats()
        launched = sum(n for path, n in _rise(after, before, "launches").items()
                       if path.startswith("full_s"))
        assert launched >= 1
        assert _rise(after, before, "operands") == {builder: launched,
                                                    other: 0}
        facts = layers.difference(layers.flatten(after, "s", {}),
                                  layers.flatten(before, "s", {}), "s",
                                  "window")
        assert reader(facts) == share
        answers[builder] = [(r.total_hits, r.total_relation, list(r.hits))
                            for r in results]
    assert dist.native_operand_builder() is None
    assert answers["native"] == answers["python"]
    for q, (total_hits, relation, hits) in zip(train, answers["python"]):
        total, docs, scores = reference.reference_topk(hosts["shards"], q, SIZE)
        resp = {"_shards": {"failed": 0}, "hits": {
            "total": {"value": total_hits, "relation": relation},
            "hits": [{"_id": h[-1], "_score": h[0]} for h in hits]}}
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
    prom = host["node"].metrics.prometheus_text()
    for builder in ("native", "python"):
        assert f'es_tpu_kernel_operands_total{{builder="{builder}"}}' in prom


@pytest.mark.parametrize("make", ["pruned", "exact"])
def test_the_merge_carries_its_scope(hosts, make):
    """`cross_chip_merge` names the collectives and the merge of the
    gathered candidates in both sharded programs' op metadata."""
    host = hosts["served"][4]
    pack, mesh = host["resident"].pack, host["mesh"]
    batch = dist.prepare_query_batch(
        pack, [[corpus.word(25), corpus.word(400)]], pad_batch_to=8,
        pad_t_slots=16, pad_max_len=dist.CHUNK_CAP)
    if make == "pruned":
        fn = tpu_service._make_full_search(host["resident"], mesh, 16, 1024,
                                           "ref")
        t = dist.prepare_term_ranges(pack, batch,
                                     pad_terms=tpu_service.PRUNE_MAX_TERMS)
        ops = dist.pack_pruned_operands(batch, *t)
        text = fn.lower(*host["resident"].imp_device_arrays[:2],
                        *host["resident"].device_arrays[:2], ops
                        ).as_text(debug_info=True)
    else:
        fn = dist.make_distributed_search(
            mesh, max_len=batch.max_len, d_pad=pack.d_pad, p_pad=pack.p_pad,
            k=1024, t_window=8)
        text = fn.lower(*host["resident"].device_arrays[:2], batch.starts,
                        batch.lengths, batch.weights, batch.min_count
                        ).as_text(debug_info=True)
    assert "cross_chip_merge" in text
    assert "all_gather" in text or "all-gather" in text


# ---------------------------------------------------------------------------
# the executables compiled for four devices are the programs launched
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fill, launched", [
    (1, {"full_s32": [8]}),
    # nine ride cheaper in two chunks of eight than in 64 rows
    (9, {"full_s32": [8, 8]}),
    (40, {"full_s16": [64]}),
    (128, {"full_s16": [128]})])
def test_after_the_first_full_path_answer_no_train_compiles_on_the_mesh(
        hosts, fill, launched):
    """`full_program_set`'s three programs were compiled ahead of time
    from the pack's own shardings over four devices by the first
    full-path answer (the fixture's), and a train of any fill calls one
    of them: no backend compile, and the answers are the reference's."""
    host = hosts["served"][4]
    resident, mesh = host["resident"], host["mesh"]
    assert host["http"].stats()["full_programs"][f"{INDEX}/{FIELD}"] == [
        "full_s16_b128", "full_s16_b64", "full_s32_b8"]
    train = (hosts["queries"] * 2)[:fill]
    flats = [tpu_service.FlatQuery(FIELD, [corpus.word(t) for t in q], 1.0, 1)
             for q in train]
    assert max(tpu_service._slots_needed(resident, f) for f in flats) <= 16
    events = []

    def listener(event, duration, **kw):
        if event == COMPILE_EVENT:
            events.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    before = host["http"].stats()
    try:
        results = tpu_service.execute_flat_batch(resident, flats, SIZE, mesh)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    after = host["http"].stats()
    assert events == []
    assert {path: n for path, n in _rise(after, before, "launches").items()
            if n} == {path: len(rows) for path, rows in launched.items()}
    n_launches = sum(len(rows) for rows in launched.values())
    assert _rise(after, before, "cross_chip") == {
        "launches": n_launches, "devices": 4 * n_launches,
        "rows": sum(sum(rows) for rows in launched.values())}
    assert len(results) == fill
    for q, res in list(zip(train, results))[::16]:
        total, docs, scores = reference.reference_topk(hosts["shards"], q, SIZE)
        resp = {"_shards": {"failed": 0}, "hits": {
            "total": {"value": res.total_hits, "relation": res.total_relation},
            "hits": [{"_id": h[-1], "_score": h[0]} for h in res.hits]}}
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
