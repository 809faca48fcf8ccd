"""The `beir-quora-1chip` deployment at toy size (ISSUE 29): questions of
7-12 terms against short documents in one shard, served over REST and
held to the benchmark's own plain numpy reference
(`benchmarks/esbench/reference.py`, which imports nothing of the
program), on both sides of PRUNE_MAX_TERMS; and the closed set of the
exact kernel's programs that such questions compile
(`tpu_service.exact_program_set`), with the names and counters the
launch routing keeps of them.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from esbench import compare, corpus, reference  # noqa: E402

from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.node import Node, serve  # noqa: E402
from elasticsearch_tpu.parallel import distributed as dist  # noqa: E402
from elasticsearch_tpu.search import tpu_service  # noqa: E402

#: the configuration's own law (`benchmarks/configs/beir-quora-1chip.json`)
#: with the corpus and the query set cut to a CPU's size
GENERATOR = {"docs": 4000, "vocab_size": 30000, "zipf_s": 1.07,
             "mean_length": 11.4, "corpus_seed": 23, "num_queries": 200,
             "query_terms_min": 7, "query_terms_max": 12,
             "query_band_lo": 20, "query_band_hi": 3000}
SIZE = 1000
INDEX = "quora"
FIELD = "body"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module", autouse=True)
def _restore_kernel_knobs():
    """The toy node turns `compressed_pack` off and a test toggles
    `packed_sort`; the knobs are process-global."""
    saved = dict(tpu_service.KERNEL_CONFIG)
    yield
    tpu_service.KERNEL_CONFIG.update(saved)


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

    def search(self, terms, size=SIZE, operator="or"):
        text = corpus.query_text(terms)
        query = text if operator == "or" else {"query": text,
                                               "operator": operator}
        status, body = self.request("POST", f"/{INDEX}/_search", {
            "query": {"match": {FIELD: query}}, "size": size,
            "_source": False})
        assert status == 200, body
        return body

    def stats(self):
        status, body = self.request("GET", "/_tpu/stats")
        assert status == 200
        return body


@pytest.fixture(scope="module")
def quora(tmp_path_factory):
    """One node, one shard, a raw pack (the deployment's d_pad of 524,288
    is past the compressed format's 2^16, so its pack is raw and its
    questions of up to 8 terms take the pruned path), the corpus indexed
    through REST `_bulk`, and the reference's own index of it."""
    corp = corpus.generate_corpus(GENERATOR)
    queries = corpus.generate_queries(GENERATOR)
    node = Node(str(tmp_path_factory.mktemp("quora")), settings=Settings.of({
        "search.tracing.sample_rate": 1.0,
        "search.tpu_serving.kernel.compressed_pack": False}))
    server = serve(node, port=0)
    http_ = _Http(server.server_address[1])
    status, _ = http_.request("PUT", f"/{INDEX}", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {FIELD: {"type": "text"}}}})
    assert status == 200
    words = [corpus.word(i) for i in range(corp.vocab_size)]
    lines = []
    for i in range(corp.num_docs):
        lines.append(json.dumps({"index": {"_index": INDEX,
                                           "_id": corpus.doc_id(i)}}))
        lines.append(json.dumps({FIELD: corpus.doc_text(corp, i, words)}))
    status, res = http_.request("POST", "/_bulk",
                                ("\n".join(lines) + "\n").encode("utf-8"))
    assert status == 200 and not res["errors"]
    assert http_.request("POST", f"/{INDEX}/_refresh")[0] == 200
    shards = reference.build_shard_indexes(
        corp.flat, corp.offsets, 1, sorted({t for q in queries for t in q}))
    http_.search(queries[0])  # builds and places the pack
    resident = node.tpu_search.packs.peek((INDEX, FIELD))
    assert resident is not None and resident.comp_streams is None
    try:
        yield {"node": node, "http": http_, "queries": queries,
               "shards": shards, "resident": resident,
               "mesh": node.tpu_search.packs.mesh}
    finally:
        http_.conn.close()
        server.shutdown()
        server.server_close()
        node.close()


def _flats(queries):
    return [tpu_service.FlatQuery(FIELD, [corpus.word(t) for t in q], 1.0, 1)
            for q in queries]


def _postings_under(shards, terms) -> int:
    return sum(int(shards[0].postings[t][0].shape[0]) for t in terms)


# ---------------------------------------------------------------------------
# the served path against the reference, both sides of PRUNE_MAX_TERMS
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed_sort", [True, False],
                         ids=["packed_sort", "ref_sort"])
@pytest.mark.parametrize("n_terms", [7, 8, 9, 12])
def test_every_search_equals_the_numpy_reference(quora, n_terms, packed_sort):
    """ids, scores within 1e-5 relative, `hits.total` equal, no fallback;
    with `packed_sort` off the exact kernel is the `ref` variant, the one
    the deployment's pack (d_pad past 2^16) runs."""
    http_, svc = quora["http"], quora["node"].tpu_search
    svc.set_kernel_packed_sort(packed_sort)
    mine = [q for q in quora["queries"] if len(q) == n_terms][:12]
    assert len(mine) == 12
    before = http_.stats()
    gap = 0.0
    for q in mine:
        total, docs, scores = reference.reference_topk(quora["shards"], q, SIZE)
        resp = http_.search(q)
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
        gap = max(gap, compare.score_gap(resp, scores.tolist()))
    after = http_.stats()
    assert gap < compare.REL_TOL
    assert after["fallback"] == before["fallback"]
    assert after["served"] - before["served"] == len(mine)
    route = {r: after["route"][r] - before["route"][r] for r in after["route"]}
    want = "exact_terms" if n_terms > tpu_service.PRUNE_MAX_TERMS \
        else "pruned_full_s32"
    assert route.pop(want) == len(mine)
    assert not any(route.values()), route


# ---------------------------------------------------------------------------
# the closed set
# ---------------------------------------------------------------------------

class _Dispatched:
    """What `_launch_exact` hands to `distributed_search_raw`."""

    def __init__(self, monkeypatch):
        self.seen = []
        inner = dist.distributed_search_raw

        def spy(pack, batch, k, mesh, **kw):
            self.seen.append(tpu_service.ExactProgram(
                int(batch.starts.shape[1]), int(batch.t_slots),
                int(kw["t_window"]), bool(batch.need_counts), int(k),
                kw["variant"]))
            return inner(pack, batch, k, mesh, **kw)

        monkeypatch.setattr(dist, "distributed_search_raw", spy)


class _Compiles:
    def __init__(self):
        import jax
        self.jax = jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append(str(kw.get("fun_name")))

    def close(self):
        self.jax.monitoring.unregister_event_duration_listener(self._on)


def _batches(queries):
    """The exact-routed questions, alone, 48 and 128 a batch."""
    long_ = [q for q in queries if len(q) > tpu_service.PRUNE_MAX_TERMS]
    assert len(long_) >= 128
    return [long_[i:i + 1] for i in range(0, 24)] \
        + [long_[i:i + 48] for i in range(0, 96, 48)] + [long_[:128]]


def test_what_the_query_set_dispatches_is_in_the_set_and_compiles_no_more(
        quora, monkeypatch):
    resident, mesh = quora["resident"], quora["mesh"]
    quora["node"].tpu_search.set_kernel_packed_sort(True)
    listed = tpu_service.exact_program_set(resident, SIZE, max_batch=128,
                                           max_terms=12)
    assert len(set(listed)) == len(listed)
    # one pass over the list: the members a question without clause
    # counts can reach
    for program in listed:
        if not program.with_counts:
            tpu_service.run_exact_program(resident, program, FIELD, mesh)
    spy = _Dispatched(monkeypatch)
    compiles = _Compiles()
    try:
        for batch in _batches(quora["queries"]):
            results = tpu_service.execute_flat_batch(
                resident, _flats(batch), SIZE, mesh)
            assert len(results) == len(batch)
    finally:
        compiles.close()
    assert spy.seen and set(spy.seen) <= set(listed), \
        set(spy.seen) - set(listed)
    assert {p.rows for p in spy.seen} == {8, 64, 128}
    assert {p.t_window for p in spy.seen} == {16}
    assert compiles.events == []


def test_the_set_is_a_function_of_the_pack_and_the_constants(quora):
    resident = quora["resident"]
    listed = tpu_service.exact_program_set(resident, SIZE, max_batch=128)
    # a toy pack's rows fit one chunk each: a question of t terms needs t
    # slots, so 8 for up to 8 terms and the first full width past them
    assert {(p.slots, p.t_window) for p in listed} == {(8, 8), (32, 16)}
    assert {p.rows for p in listed} == {8, 64, 128}
    assert {p.k_kernel for p in listed} == {1024}
    assert {p.variant for p in listed} == {"packed", "ref"}
    assert tpu_service.exact_program_set(resident, SIZE, max_batch=128) \
        == listed
    # hot terms widen it by whole pins, never by a value of its own
    wide = tpu_service.exact_program_set(resident, 10, max_batch=8,
                                         max_terms=40)
    assert {p.rows for p in wide} == {8}
    assert {p.k_kernel for p in wide} == {128}
    assert {(p.slots, p.t_window) for p in wide} == {
        (8, 8), (32, 16), (32, 32), (64, 64)}
    # and the node lists it
    names = quora["http"].stats()["exact_programs"][f"{INDEX}/{FIELD}"]
    assert names == sorted({p.label for p in listed})
    assert "exact_ref_b128_s32_w16" in names


@pytest.mark.parametrize("variant", ["ref", "packed"])
def test_pinning_the_window_changes_no_bit(quora, variant):
    """`segmented_run_sum` doubles its step while it is below the
    window: 12 and 16 run the same four steps."""
    resident, mesh = quora["resident"], quora["mesh"]
    twelve = [q for q in quora["queries"] if len(q) == 12][:8]
    batch = dist.prepare_query_batch(
        resident.pack, [[corpus.word(t) for t in q] for q in twelve],
        pad_batch_to=8, pad_t_slots=16, pad_max_len=dist.CHUNK_CAP)
    assert batch.window == 12
    outs = [dist.distributed_search_raw(
        resident.pack, batch, 1024, mesh, device_arrays=resident.device_arrays,
        t_window=w, variant=variant) for w in (12, 16)]
    for a, b in zip(*outs):
        assert np.array_equal(a, b)
    assert int(outs[0][2].min()) > 0          # totals, and not of nothing


def test_route_entries_and_launch_labels_count_what_was_sent(quora):
    http_, shards = quora["http"], quora["shards"]
    quora["node"].tpu_search.set_kernel_packed_sort(False)
    by_terms = {n: [q for q in quora["queries"] if len(q) == n]
                for n in (7, 9, 12)}
    before = http_.stats()
    sent_exact = by_terms[9][:3] + by_terms[12][:2]
    for q in sent_exact:
        http_.search(q)
    http_.search(by_terms[7][0])
    http_.search(by_terms[7][1][:3], operator="and")
    http_.search(by_terms[7][2], size=tpu_service.PRUNE_MAX_K + 500)
    after = http_.stats()

    def rise(block):
        return {key: after[block][key] - before[block].get(key, 0)
                for key in after[block]
                if after[block][key] != before[block].get(key, 0)}

    # a train of one rides at 32 slots: the narrow rung has no program
    # of eight rows
    assert rise("route") == {"exact_terms": 5, "pruned_full_s32": 1,
                             "exact_min_count": 1, "exact_k": 1}
    assert rise("launches") == {"exact_ref_b8_s32_w16": 5, "full_s32": 1,
                                "exact_ref_b8_s8_w8": 2}
    assert rise("full_entries") == {
        "real": _postings_under(shards, by_terms[7][0]),
        "padded": (8 * 32 * dist.CHUNK_CAP
                   * quora["resident"].pack.num_shards)}
    real = sum(_postings_under(shards, q) for q in sent_exact) \
        + _postings_under(shards, by_terms[7][1][:3]) \
        + _postings_under(shards, by_terms[7][2])
    assert rise("exact_entries") == {
        "real": real,
        "padded": ((5 * 8 * 32 + 2 * 8 * 8) * dist.CHUNK_CAP
                   * quora["resident"].pack.num_shards)}
    prom = quora["node"].metrics.prometheus_text()
    assert 'es_tpu_kernel_route_total{route="exact_terms"}' in prom
    assert 'es_tpu_kernel_exact_entries_total{kind="padded"}' in prom
    assert 'es_tpu_kernel_full_entries_total{kind="real"}' in prom
    assert 'es_tpu_kernel_launches_total{path="exact_ref_b8_s32_w16"}' in prom


@pytest.mark.parametrize("n", [5, 48, 128])
def test_a_train_of_long_questions_is_one_launch(quora, n):
    """ISSUE 37's split by slot pin leaves this deployment alone: every
    exact question has more than 8 terms, so its own pin is the 32-slot
    floor, which is the launch's: one group, one launch at the train's
    row bucket, nothing under its pin."""
    resident, mesh = quora["resident"], quora["mesh"]
    quora["node"].tpu_search.set_kernel_packed_sort(False)
    long_ = [q for q in quora["queries"]
             if len(q) > tpu_service.PRUNE_MAX_TERMS][:n]
    flats = _flats(long_)
    shard_rows = max(1, resident.pack.num_shards
                     // mesh.shape[tpu_service.SHARD_AXIS])
    assert tpu_service._split_exact_train(
        resident, flats, range(n), shard_rows) == [list(range(n))]
    before = quora["http"].stats()
    results = tpu_service.execute_flat_batch(resident, flats, SIZE, mesh)
    after = quora["http"].stats()
    rows = tpu_service._serving_bucket(n)
    def rise(block):
        return {key: after[block][key] - before[block].get(key, 0)
                for key in after[block]}

    assert {p: n_ for p, n_ in rise("launches").items() if n_} \
        == {f"exact_ref_b{rows}_s32_w16": 1}
    assert rise("exact_pin") == {
        "rows": n, "rows_under": 0, "trains": 1, "launches": 1}
    assert [r.total_hits > 0 for r in results] == [True] * n


def test_an_exact_launchs_states_carry_its_path_and_its_train(quora):
    http_, node = quora["http"], quora["node"]
    node.tpu_search.set_kernel_packed_sort(False)
    nine = [q for q in quora["queries"] if len(q) == 9][5]
    n0 = len(node.tracer.spans(limit=0))
    decodes_before = http_.stats()["stages"]["completer.decode"]["count"]
    http_.search(nine)
    # the request thread closes its spans after the client has the answer
    deadline = time.monotonic() + 5.0
    while True:
        spans = node.tracer.spans(limit=0)     # newest first
        spans = spans[:len(spans) - n0]
        if any(s["name"].startswith("rest ") for s in spans) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    launch = next(s for s in spans if s["name"] == "tpu.batch_launch")
    train = launch["attributes"]["train"]
    assert train >= 1
    label = "exact_ref_b8_s32_w16"
    preps = [s for s in spans if s["name"] == "tpu.batcher.prep"]
    assert any(s["attributes"].get("path") == label
               and s["attributes"].get("rows") == 8 for s in preps)
    assert all(s["attributes"]["train"] == train for s in preps)
    call = next(s for s in spans if s["name"] == "tpu.batcher.call")
    assert call["attributes"] == {"train": train, "path": label, "rows": 8}
    waits = [s for s in spans if s["name"] == "tpu.completer.device_wait"]
    assert waits and all(s["attributes"]["train"] == train for s in waits)
    # `decode` closes after the train's span has ended: its stage counts it
    stages = http_.stats()["stages"]
    assert stages["completer.decode"]["count"] > decodes_before


def test_at_most_pipeline_depth_trains_are_launched_and_unfinished(
        quora, monkeypatch):
    """A train holds its results on the device from its dispatch until
    the completer has them; the worker dispatches the next only below
    PIPELINE_DEPTH, so that memory has a ceiling that three full trains
    reach (with the queue's bound alone a slow completer let five run)."""
    depth = tpu_service._PackQueue.PIPELINE_DEPTH
    lock = threading.Lock()
    live, high, trains = [0], [0], []

    def launch(resident, flats, k, mesh=None, stages=None, max_batch=128):
        with lock:
            live[0] += 1
            high[0] = max(high[0], live[0])
            trains.append(len(flats))
        return {"n": len(flats)}

    def finish(st):
        time.sleep(0.05)                  # the device, and a slow decode
        with lock:
            live[0] -= 1
        return [tpu_service.FlatQueryResult.empty()] * st["n"]

    monkeypatch.setattr(tpu_service, "launch_flat_batch", launch)
    monkeypatch.setattr(tpu_service, "finish_flat_batch", finish)
    batcher = quora["node"].tpu_search.batcher
    flat = _flats(quora["queries"][:1])[0]
    futures = [batcher.submit(quora["resident"], flat, SIZE)
               for _ in range(6 * batcher.max_batch)]
    for f in futures:
        assert f.result(timeout=30) is not None
    assert sum(trains) == len(futures) and len(trains) >= 6
    assert high[0] == depth


def test_a_program_is_named_after_its_static_shape():
    import jax

    from elasticsearch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(shape=(1, 1), devices=jax.devices()[:1])
    name = dist.exact_program_name("ref", 8, 16, 16)
    assert name == "exact_ref_b8_s16_w16"
    fn = dist.make_distributed_search(
        mesh, max_len=128, d_pad=64, p_pad=256, k=8, t_window=16,
        variant="ref", name=name)
    f32, i32 = np.float32, np.int32
    args = (jax.ShapeDtypeStruct((1, 256), i32),
            jax.ShapeDtypeStruct((1, 256), f32),
            jax.ShapeDtypeStruct((1, 8, 16), i32),
            jax.ShapeDtypeStruct((1, 8, 16), i32),
            jax.ShapeDtypeStruct((1, 8, 16), f32),
            jax.ShapeDtypeStruct((8,), i32))
    assert f"module @jit_{name} " in fn.lower(*args).as_text()
