"""Conjunctive retrieval on the `msmarco-1chip` deployment at toy size
(ISSUE 36; the benchmark's configuration `msmarco-and-1chip`): MS MARCO's law in the configuration's 2 shards, `match` with
`"operator": "and"` served over REST at `size` 1000 and at a small `size`
and held to the benchmark's own plain numpy reference
(`benchmarks/esbench/reference.py`: `reference_topk(..., operator="and")`,
per-shard statistics and ES routing, importing nothing of the program) by
the rule that decides the cell's `correct` (`esbench/compare.py`): ids and
scores equal (1e-5 relative, near-tie swaps only), `hits.total` exact with
relation `eq`, every answer the kernel's (route `exact_min_count`, no
fallback); what a train that mixes slot pins, operators or row buckets
answers, bit for bit, as one launch and split by slot pin into several
(ISSUE 37: `_split_exact_train`); and the counters the deployment added
to `/_tpu/stats` (`exact_pin`, `exact_results`).

The corpus is 20,000 docs, not the 6,000 of the other toy deployments: a
slot holds 4,096 postings, so only a word that more than 4,096 docs of a
shard hold takes several, and the pins of the exact ladder (8, 16, 32
slots) differ only between queries that hold such words. The band law
(ranks 20-2999) has none at this size; the wide queries here are made of
the ranks above the band (`w0`...`w7`), as a query that needs 16 or 32
slots is made of mid-band words at the deployment's 552,960 docs a shard.
The node sees one device, as the deployment's does (tier-1 shows jax
eight virtual ones and the node has no setting for fewer, so the test
steers it as `tests/test_msmarco_4chip_path.py` does): two shard rows on
a device. Its settings are the deployment's but one: the toy pack would fit
the compressed format (d_pad below 2^16), which the deployment's does not
(d_pad 2^20), so `compressed_pack` is off and `packed_sort` is off for all
but one test: the exact kernel is the `ref` variant, the one the cell runs.

The rule on a repeated word that the program and the reference share: a
word is a clause each time it is written. `w25 w25 w400` asks for three
clauses, a doc that holds both words matches all three (the program's
count of a doc is the slots that hold it, the reference's the postings
that name it) and `w25`'s weight is added twice.
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from esbench import compare, corpus, reference  # noqa: E402

from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.node import Node, serve  # noqa: E402
from elasticsearch_tpu.parallel import distributed as dist  # noqa: E402
from elasticsearch_tpu.parallel import mesh as mesh_mod  # noqa: E402
from elasticsearch_tpu.search import tpu_service  # noqa: E402

#: the configuration's own law (`benchmarks/configs/msmarco-and-1chip.json`,
#: `msmarco-1chip.json`'s generator block)
#: with the corpus and the query set cut to a CPU's size
GENERATOR = {"docs": 20000, "vocab_size": 30000, "zipf_s": 1.07,
             "mean_length": 55, "corpus_seed": 23, "num_queries": 200,
             "query_terms_min": 2, "query_terms_max": 5,
             "query_band_lo": 20, "query_band_hi": 3000}
SHARDS = 2
SIZE = 1000
SMALL = 10
INDEX = "msmarco"
FIELD = "body"
#: a word no doc holds (the vocabulary ends at 29,999)
NOWHERE = 99999
#: 11 slots on the heavier shard (3 + 3 + 3 + 2 chunks): pin 16
WIDE16 = [0, 1, 2, 3]
#: 19 slots: pin 32, in eight terms, so still under the 8-slot floor's
#: window (nine would start the ladder at 32)
WIDE32 = [0, 1, 2, 3, 4, 5, 6, 7]


@pytest.fixture(scope="module", autouse=True)
def _restore_kernel_knobs():
    """The toy node turns `compressed_pack` and `packed_sort` off; the
    knobs are process-global."""
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

    def search(self, terms, size=SIZE, operator="and"):
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
def msmarco(tmp_path_factory):
    """One node, the corpus indexed through REST `_bulk` in 2 shards, a
    raw pack on one device; the reference's own index of the corpus; and
    the queries the cases share, chosen by the reference alone."""
    corp = corpus.generate_corpus(GENERATOR)
    one = jax.devices()[:1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tpu_service, "_n_local_devices", lambda: 1)
        patch.setattr(
            tpu_service, "make_mesh",
            lambda devices=None, shape=None: mesh_mod.make_mesh(
                one if devices is None else devices, shape))
        node = Node(str(tmp_path_factory.mktemp("and")), settings=Settings.of({
            "search.tpu_serving.kernel.compressed_pack": False}))
    server = serve(node, port=0)
    http_ = _Http(server.server_address[1])
    status, _ = http_.request("PUT", f"/{INDEX}", {
        "settings": {"number_of_shards": SHARDS},
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
    rare = range(15000, 15400)
    shards = reference.build_shard_indexes(
        corp.flat, corp.offsets, SHARDS, list(range(3000)) + list(rare))
    node.tpu_search.set_kernel_packed_sort(False)
    http_.search([25, 400])  # builds and places the pack
    resident = node.tpu_search.packs.peek((INDEX, FIELD))
    assert resident is not None and resident.comp_streams is None
    assert resident.pack.num_shards == SHARDS
    assert node.tpu_search.packs.mesh.devices.shape == (1, 1)
    try:
        yield {"node": node, "http": http_, "shards": shards,
               "port": server.server_address[1], "resident": resident,
               "mesh": node.tpu_search.packs.mesh,
               "band": corpus.generate_queries(GENERATOR),
               "held": _queries_some_doc_holds(corp),
               "one_shard": _word_of_one_shard(shards, corp, rare)}
    finally:
        http_.conn.close()
        server.shutdown()
        server.server_close()
        node.close()


def _queries_some_doc_holds(corp) -> dict:
    """{n: 12 queries of n distinct band words that one doc holds
    together}: a conjunction with a hit, which two to five words drawn
    apart seldom are (the cell's own limit: PERF.md section 4)."""
    rng = np.random.default_rng(36)
    lo, hi = GENERATOR["query_band_lo"], GENERATOR["query_band_hi"]
    out = {n: [] for n in (2, 3, 4, 5)}
    for d in rng.permutation(corp.num_docs).tolist():
        mine = np.unique(corp.doc_words(d))
        mine = mine[(mine >= lo) & (mine < hi)]
        n = min(out, key=lambda n_: len(out[n_]))
        if len(out[n]) == 12:
            return out
        if mine.shape[0] >= n:
            out[n].append(rng.choice(mine, size=n, replace=False).tolist())
    raise AssertionError("the corpus ran out of docs")


def _word_of_one_shard(shards, corp, candidates):
    """→ (a word that shard 0 holds and shard 1 lacks, a band word of a
    doc that holds it)."""
    for w in candidates:
        docs0, docs1 = shards[0].postings[w][0], shards[1].postings[w][0]
        if docs0.shape[0] and not docs1.shape[0]:
            mine = np.unique(corp.doc_words(int(docs0[0])))
            return w, int(mine[(mine >= 20) & (mine < 3000)][0])
    raise AssertionError("every candidate is in both shards or in none")


def _ref(env, terms, size=SIZE, operator="and"):
    total, docs, scores = reference.reference_topk(env["shards"], terms, size,
                                                   operator)
    return total, [corpus.doc_id(d) for d in docs.tolist()], scores.tolist()


def _held_to_reference(env, resp, terms, size=SIZE, operator="and") -> int:
    """ids, scores, `hits.total` exact → the reference's total."""
    total, ids, scores = _ref(env, terms, size, operator)
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    compare.compare_response(resp, total, ids, scores, size)
    assert compare.score_gap(resp, scores) <= compare.REL_TOL
    return total


def _rise(after, before, block):
    return {key: after[block][key] - before[block].get(key, 0)
            for key in after[block]
            if after[block][key] != before[block].get(key, 0)}


def _served_by_the_kernel(env, before, n, route="exact_min_count"):
    after = env["http"].stats()
    assert after["served"] - before["served"] == n
    assert after["fallback"] == before["fallback"]
    assert after["timeouts"] == before["timeouts"]
    assert _rise(after, before, "route") == {route: n}
    return after


def _flats(queries, operator="and"):
    return [tpu_service.FlatQuery(
        FIELD, [corpus.word(t) for t in q], 1.0,
        len(q) if operator == "and" else 1) for q in queries]


def _as_response(res):
    return {"_shards": {"failed": 0}, "hits": {
        "total": {"value": res.total_hits, "relation": res.total_relation},
        "hits": [{"_id": h[-1], "_score": h[0]} for h in res.hits]}}


def _same_bits(a, b):
    assert a.total_hits == b.total_hits and a.total_relation == b.total_relation
    assert np.array_equal(a.scores, b.scores)
    assert np.array_equal(a.rows, b.rows) and np.array_equal(a.ords, b.ords)


def _search_all(env, queries, operators):
    """One client a query, all at once, so that the batcher may form a
    train of them → the responses in the queries' order."""
    def one(job):
        client = _Http(env["port"])
        try:
            return client.search(job[0], operator=job[1])
        finally:
            client.conn.close()

    with ThreadPoolExecutor(max_workers=len(queries)) as pool:
        return list(pool.map(one, zip(queries, operators)))


# ---------------------------------------------------------------------------
# the served path against the AND reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [SIZE, SMALL], ids=["size1000", "size10"])
@pytest.mark.parametrize("n_terms", [2, 3, 4, 5])
def test_every_and_search_equals_the_and_reference(msmarco, n_terms, size):
    """Queries one doc holds (a hit at the least) and queries of the
    configuration's own law (empty more often than not, as the cell's
    are): every answer the reference's, every one the exact kernel's."""
    band = [q for q in msmarco["band"] if len(q) == n_terms][:6]
    mine = msmarco["held"][n_terms][:6] + band
    assert len(mine) == 12
    before = msmarco["http"].stats()
    totals = [_held_to_reference(msmarco, msmarco["http"].search(q, size), q,
                                 size) for q in mine]
    assert all(t >= 1 for t in totals[:6])
    after = _served_by_the_kernel(msmarco, before, len(mine))
    assert _rise(after, before, "exact_results") == {
        "queries": len(mine), **({"empty": totals.count(0)}
                                 if totals.count(0) else {})}
    # a train of one: eight rows at the narrowest pin
    label = "exact_ref_b8_s8_w8"
    assert _rise(after, before, "launches") == {label: len(mine)}


def test_a_conjunction_no_doc_holds_answers_nothing(msmarco):
    """Every word is in both shards; no doc has them all."""
    shards = msmarco["shards"]
    q = next(q for q in msmarco["band"]
             if all(sh.postings[t][0].shape[0] for sh in shards for t in q)
             and _ref(msmarco, q)[0] == 0)
    before = msmarco["http"].stats()
    resp = msmarco["http"].search(q)
    assert resp["hits"]["total"] == {"value": 0, "relation": "eq"}
    assert resp["hits"]["hits"] == [] and resp["hits"]["max_score"] is None
    after = _served_by_the_kernel(msmarco, before, 1)
    assert _rise(after, before, "exact_results") == {"queries": 1, "empty": 1}
    # the control: under `or` the same words have hits
    assert msmarco["http"].search(q, operator="or")["hits"]["total"]["value"] > 0


def test_a_conjunction_one_doc_holds_answers_that_doc(msmarco):
    q = next(q for n in (4, 5, 3) for q in msmarco["held"][n]
             if _ref(msmarco, q)[0] == 1)
    before = msmarco["http"].stats()
    resp = msmarco["http"].search(q)
    assert _held_to_reference(msmarco, resp, q) == 1
    assert len(resp["hits"]["hits"]) == 1
    assert resp["hits"]["max_score"] == resp["hits"]["hits"][0]["_score"]
    _served_by_the_kernel(msmarco, before, 1)


@pytest.mark.parametrize("absent_from", ["one_shard", "both_shards"])
def test_a_word_that_a_shard_lacks_leaves_that_shard_empty(msmarco, absent_from):
    """The slot of a word a shard row lacks holds no posting, and the
    count a doc must reach stays the number of the query's words: that
    shard answers nothing, the other what it holds."""
    word, beside = msmarco["one_shard"]
    q = [beside, word] if absent_from == "one_shard" else [beside, NOWHERE]
    before = msmarco["http"].stats()
    resp = msmarco["http"].search(q)
    total = _held_to_reference(msmarco, resp, q)
    _served_by_the_kernel(msmarco, before, 1)
    if absent_from == "one_shard":
        shard_of = reference.shard_of_digit_ids(
            np.asarray([int(h["_id"]) for h in resp["hits"]["hits"]]), SHARDS)
        assert total >= 1 and not shard_of.any()
    else:
        assert total == 0 and resp["hits"]["hits"] == []


def test_a_repeated_word_is_a_clause_each_time_it_is_written(msmarco):
    """`a a b` under `and`: three clauses; a doc with `a` and `b` holds
    all three and `a` scores twice, in the program and in the reference
    alike. So it matches what `a b` matches, with other scores."""
    a, b = msmarco["held"][2][0]
    before = msmarco["http"].stats()
    twice = msmarco["http"].search([a, a, b])
    total = _held_to_reference(msmarco, twice, [a, a, b])
    once = msmarco["http"].search([a, b])
    assert total == _held_to_reference(msmarco, once, [a, b]) >= 1
    assert twice["hits"]["max_score"] > once["hits"]["max_score"]
    _served_by_the_kernel(msmarco, before, 2)
    # the control: the reference of the distinct words is another answer
    _t, ids, scores = _ref(msmarco, [a, b])
    with pytest.raises(compare.Mismatch, match="score at rank 0"):
        compare.compare_response(twice, total, ids, scores, SIZE)


@pytest.mark.parametrize("hits", ["fewer_than_size", "more_than_size"])
def test_hits_on_either_side_of_size(msmarco, hits):
    """Fewer hits than `size`: all of them, best first. More: the best
    `size` of them, and `hits.total` counts every one all the same."""
    q = msmarco["held"][2][1] if hits == "fewer_than_size" else WIDE16
    before = msmarco["http"].stats()
    resp = msmarco["http"].search(q)
    total = _held_to_reference(msmarco, resp, q)
    if hits == "fewer_than_size":
        assert 1 <= total < SIZE and len(resp["hits"]["hits"]) == total
    else:
        assert total > 5 * SIZE and len(resp["hits"]["hits"]) == SIZE
    after = _served_by_the_kernel(msmarco, before, 1)
    want = "exact_ref_b8_s8_w8" if hits == "fewer_than_size" \
        else "exact_ref_b8_s16_w8"
    assert _rise(after, before, "launches") == {want: 1}


@pytest.mark.parametrize("n_terms", [2, 5])
def test_the_or_reference_is_another_answer(msmarco, n_terms):
    """The control of `correct`: the same responses held to the `or`
    reference of the same words differ wherever the conjunction has a
    hit; where it has none the two differ too, unless no doc holds any
    of the words."""
    for q in msmarco["held"][n_terms][:6]:
        resp = msmarco["http"].search(q)
        _held_to_reference(msmarco, resp, q)
        total, ids, scores = _ref(msmarco, q, operator="or")
        assert total > resp["hits"]["total"]["value"] >= 1
        with pytest.raises(compare.Mismatch, match="hits.total"):
            compare.compare_response(resp, total, ids, scores, SIZE)


def test_the_packed_variant_answers_alike(msmarco):
    """With `packed_sort` on (the default) a launch on this toy pack may
    pick the `packed` variant, which the deployment's pack is too wide
    for; the answers are the reference's either way."""
    svc = msmarco["node"].tpu_search
    svc.set_kernel_packed_sort(True)
    try:
        before = msmarco["http"].stats()
        mine = [msmarco["held"][n][2] for n in (2, 3, 4, 5)] + [WIDE16]
        for q in mine:
            _held_to_reference(msmarco, msmarco["http"].search(q), q)
        after = _served_by_the_kernel(msmarco, before, len(mine))
        assert all(label.startswith(("exact_packed_", "exact_ref_"))
                   for label in _rise(after, before, "launches"))
    finally:
        svc.set_kernel_packed_sort(False)


# ---------------------------------------------------------------------------
# trains: mixed pins, mixed operators, both row buckets
# ---------------------------------------------------------------------------

def test_a_train_that_mixes_slot_pins_answers_each_query_as_alone(msmarco):
    """An exact launch takes the pin of its widest query: five queries of
    8 slots and one of 16 ride at 32 beside one that needs 19 (a train
    this short is not split: eight rows of lanes cost less than a launch).
    Each answers what it answers alone (a launch of its own, at its own
    pin), bit for bit, and what the reference answers; `exact_pin` counts
    the seven and the six that rode above their own pin."""
    resident, mesh = msmarco["resident"], msmarco["mesh"]
    narrow = [msmarco["held"][n][3] for n in (2, 3, 4, 5)] + [msmarco["band"][0]]
    train = narrow + [WIDE16, WIDE32]
    assert [tpu_service._slots_needed(resident, f) for f in _flats(train)] \
        == [2, 3, 4, 5, len(msmarco["band"][0]), 11, 19]
    before = msmarco["http"].stats()
    alone = [tpu_service.execute_flat_batch(resident, _flats([q]), SIZE, mesh)[0]
             for q in train]
    middle = msmarco["http"].stats()
    assert _rise(middle, before, "launches") == {
        "exact_ref_b8_s8_w8": 5, "exact_ref_b8_s16_w8": 1,
        "exact_ref_b8_s32_w8": 1}
    assert _rise(middle, before, "exact_pin") == {       # none under
        "rows": 7, "trains": 7, "launches": 7}
    together = tpu_service.execute_flat_batch(resident, _flats(train), SIZE, mesh)
    after = msmarco["http"].stats()
    assert _rise(after, middle, "launches") == {"exact_ref_b8_s32_w8": 1}
    assert _rise(after, middle, "exact_pin") == {
        "rows": 7, "rows_under": 6, "trains": 1, "launches": 1}
    assert _rise(after, middle, "route") == {"exact_min_count": 7}
    padded = 8 * 32 * dist.CHUNK_CAP * SHARDS
    assert _rise(after, middle, "exact_entries")["padded"] == padded
    for q, a, b in zip(train, alone, together):
        _same_bits(a, b)
        total, ids, scores = _ref(msmarco, q)
        compare.compare_response(_as_response(b), total, ids, scores, SIZE)


def test_concurrent_clients_of_different_pins_answer_as_alone(msmarco):
    """The same over HTTP, however the batcher forms its trains: seven
    clients at once, each answer the one its query gets alone."""
    train = [msmarco["held"][n][4] for n in (2, 3, 4, 5)] \
        + [msmarco["band"][1], WIDE16, WIDE32]
    alone = [msmarco["http"].search(q) for q in train]
    before = msmarco["http"].stats()
    together = _search_all(msmarco, train, ["and"] * len(train))
    after = _served_by_the_kernel(msmarco, before, len(train))
    assert _rise(after, before, "exact_pin")["rows"] == len(train)
    assert _rise(after, before, "exact_results")["queries"] == len(train)
    for q, a, b in zip(train, alone, together):
        assert a["hits"] == b["hits"]
        _held_to_reference(msmarco, b, q)


def test_a_train_is_split_by_pin_and_answers_as_one_launch(msmarco):
    """ISSUE 37: a train's exact queries go as several launches, each at
    the pin of its own widest query. Twenty queries of 8 slots with one of
    16 and one of 32 among them: the twenty in a launch of 64 rows at 8
    slots, the two wide ones together in one of 8 rows at 32 (the pin-16
    query rides: that launch is made anyway). Each query answers, at its
    own place in the train, what one launch of all 22 at the widest pin
    answers, to the bit; every launch is a member of `exact_program_set`;
    the counters count one train, two launches and the one row that rode."""
    resident, mesh = msmarco["resident"], msmarco["mesh"]
    narrow = msmarco["band"][30:50]
    train = narrow[:7] + [WIDE32] + narrow[7:15] + [WIDE16] + narrow[15:]
    flats = _flats(train)
    own = [tpu_service._exact_slot_pin(
        tpu_service._slots_needed(resident, f),
        tpu_service._exact_window(len(f.terms))) for f in flats]
    assert sorted(set(own)) == [8, 16, 32] and own.count(8) == 20
    split = tpu_service._split_exact_train(
        resident, flats, range(len(flats)), SHARDS)
    assert sorted(i for idxs in split for i in idxs) == list(range(len(flats)))
    assert [[own[i] for i in idxs] for idxs in split] == [[8] * 20, [16, 32]]
    whole = tpu_service._execute_exact(resident, flats, SIZE, mesh)
    before = msmarco["http"].stats()
    served = tpu_service.finish_flat_batch(
        tpu_service.launch_flat_batch(resident, flats, SIZE, mesh))
    after = msmarco["http"].stats()
    launched = _rise(after, before, "launches")
    assert launched == {"exact_ref_b64_s8_w8": 1, "exact_ref_b8_s32_w8": 1}
    assert set(launched) <= {
        p.label for p in tpu_service.exact_program_set(resident, SIZE)}
    assert _rise(after, before, "exact_pin") == {
        "rows": 22, "rows_under": 1, "trains": 1, "launches": 2}
    assert _rise(after, before, "route") == {"exact_min_count": 22}
    assert _rise(after, before, "exact_results")["queries"] == 22
    assert len(served) == len(train)
    for q, a, b in zip(train, whole, served):
        _same_bits(a, b)
        total, ids, scores = _ref(msmarco, q)
        compare.compare_response(_as_response(b), total, ids, scores, SIZE)


def test_a_split_train_over_http_answers_each_query_as_alone(msmarco):
    """The same through the batcher and the completer: 22 clients at
    once, each answer the one its query gets alone, whatever trains the
    batcher forms and however each is split."""
    narrow = msmarco["band"][50:70]
    train = narrow[:3] + [WIDE16] + narrow[3:12] + [WIDE32] + narrow[12:]
    alone = [msmarco["http"].search(q) for q in train]
    before = msmarco["http"].stats()
    together = _search_all(msmarco, train, ["and"] * len(train))
    after = _served_by_the_kernel(msmarco, before, len(train))
    pins = _rise(after, before, "exact_pin")
    assert pins["rows"] == len(train)
    assert pins["launches"] >= pins["trains"] >= 1
    for q, a, b in zip(train, alone, together):
        assert a["hits"] == b["hits"]
        _held_to_reference(msmarco, b, q)


def test_a_train_of_both_operators_answers_each_by_its_own(msmarco):
    """`and` goes to the exact kernel and `or` to the full-postings
    ladder, out of one train: each answer is its own operator's
    reference, and the other's differs."""
    queries = msmarco["held"][3][5:9] + msmarco["held"][3][5:9]
    operators = ["and"] * 4 + ["or"] * 4
    before = msmarco["http"].stats()
    responses = _search_all(msmarco, queries, operators)
    after = msmarco["http"].stats()
    for q, op, resp in zip(queries, operators, responses):
        _held_to_reference(msmarco, resp, q, operator=op)
    for under_and, under_or in zip(responses[:4], responses[4:]):
        assert under_and["hits"]["total"]["value"] \
            < under_or["hits"]["total"]["value"]
    assert after["served"] - before["served"] == 8
    assert after["fallback"] == before["fallback"]
    routed = _rise(after, before, "route")
    assert routed.pop("exact_min_count") == 4
    assert sum(routed.values()) == 4 and all(
        r.startswith("pruned_full_") for r in routed)
    # the pruned path's answers are not the exact kernel's to count
    assert _rise(after, before, "exact_results")["queries"] == 4
    assert _rise(after, before, "exact_pin")["rows"] == 4


def test_the_same_query_under_both_row_buckets(msmarco):
    """A train of up to 8 queries launches at 8 rows, one of 17 to 64 at
    64 (one of 9 to 16 as two launches of 8 rows): another program, the
    same answer to the bit."""
    resident, mesh = msmarco["resident"], msmarco["mesh"]
    q = msmarco["held"][4][9]
    fill = [q] + msmarco["band"][10:26]
    before = msmarco["http"].stats()
    short = tpu_service.execute_flat_batch(resident, _flats([q]), SIZE, mesh)
    tall = tpu_service.execute_flat_batch(resident, _flats(fill), SIZE, mesh)
    after = msmarco["http"].stats()
    assert _rise(after, before, "launches") == {
        "exact_ref_b8_s8_w8": 1, "exact_ref_b64_s8_w8": 1}
    assert _rise(after, before, "exact_pin") == {
        "rows": 1 + len(fill), "trains": 2, "launches": 2}
    _same_bits(short[0], tall[0])
    total, ids, scores = _ref(msmarco, q)
    assert total >= 1
    compare.compare_response(_as_response(tall[0]), total, ids, scores, SIZE)


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------

def test_the_counters_read_nought_before_a_query():
    """Every kind of the two families is on `/_tpu/stats` and the
    exposition from the start, at 0 (a process of its own: this one has
    served)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json; from elasticsearch_tpu.search import tpu_service as t; "
         "print(json.dumps([t.EXACT_PIN_COUNTS.counts(), "
         "t.EXACT_RESULT_COUNTS.counts()]))"],
        capture_output=True, text=True, timeout=120, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert json.loads(out.stdout.splitlines()[-1]) == [
        {"rows": 0, "rows_under": 0, "trains": 0, "launches": 0},
        {"queries": 0, "empty": 0}]


def test_stats_and_prometheus_report_the_two_families(msmarco):
    stats = msmarco["http"].stats()
    assert set(stats["exact_pin"]) == {"rows", "rows_under", "trains",
                                       "launches"}
    assert 0 < stats["exact_pin"]["trains"] < stats["exact_pin"]["launches"]
    assert set(stats["exact_results"]) == {"queries", "empty"}
    assert stats["exact_pin"]["rows"] == stats["exact_results"]["queries"] > 0
    assert 0 < stats["exact_pin"]["rows_under"] < stats["exact_pin"]["rows"]
    assert 0 < stats["exact_results"]["empty"] < stats["exact_results"]["queries"]
    prom = msmarco["node"].metrics.prometheus_text()
    for family, kind in (("exact_pin", "rows"), ("exact_pin", "rows_under"),
                         ("exact_pin", "trains"), ("exact_pin", "launches"),
                         ("exact_results", "queries"), ("exact_results", "empty")):
        assert f'es_tpu_kernel_{family}_total{{kind="{kind}"}}' in prom


@pytest.mark.parametrize("t_pin, want", [(8, 0), (16, 2), (32, 3), (64, 5)])
def test_rows_under_pin_counts_the_queries_below_the_launchs_pin(t_pin, want):
    """Slots that hold postings on the heavier shard row, a slot a term
    at the least, the long-query floor of 32 for more than eight terms;
    padding rows are not queries."""
    lengths = np.zeros((2, 8, 64), dtype=np.int32)
    needs = [(3, 2), (0, 0), (9, 12), (17, 4), (1, 1)]   # by shard row
    for row, per_shard in enumerate(needs):
        for shard, n in enumerate(per_shard):
            lengths[shard, row, :n] = 100
    lengths[:, 5:, :40] = 7          # rows past the queries: never counted
    terms = [["a"] * 3, ["a"] * 2, ["a"] * 4, ["a"] * 5, ["a"] * 9]
    # own pins: 8, 8 (two terms, no posting), 16, 32, 32 (nine terms)
    assert tpu_service._rows_under_pin(lengths, terms, t_pin) == want


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rows_under_pin_equals_the_pin_functions_row_by_row(seed):
    """The count is taken with numpy on the launch thread; the loop over
    `_exact_slot_pin` and `_exact_window`, a query at a time, is its
    reference."""
    rng = np.random.default_rng(seed)
    terms = [["t"] * int(n) for n in rng.integers(1, 13, size=50)]
    lengths = np.zeros((2, 64, 128), dtype=np.int32)
    for row in range(len(terms)):
        for shard in range(2):
            lengths[shard, row, :int(rng.integers(0, 100))] = 1
    for t_pin in (8, 16, 32, 64, 128):
        want = 0
        for row, mine in enumerate(terms):
            need = max(int(np.count_nonzero(lengths[:, row], axis=1).max()),
                       len(mine))
            own = tpu_service._exact_slot_pin(
                need, tpu_service._exact_window(len(mine)))
            want += own < t_pin
        assert tpu_service._rows_under_pin(lengths, terms, t_pin) == want
