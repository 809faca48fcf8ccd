"""Correctness of the columnar response serializer: the fast metadata-only
JSON path must be byte-level-safe for hostile ids (quotes, commas,
backslashes, unicode), fall back to materialized hits for richer shapes,
and honor consumer mutations (ccs rewrites `_index` in place)."""

import json
import types

import numpy as np
import pytest

from elasticsearch_tpu import native
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.indices.service import IndicesService
from elasticsearch_tpu.search import coordinator, serializer
from elasticsearch_tpu.search.serializer import (RENDER_COUNTS, ColumnarHits,
                                                 JsonLiterals, SpliceColumns,
                                                 assemble_hits_list,
                                                 dumps_response,
                                                 dumps_response_bytes,
                                                 encode_wire_response)
from elasticsearch_tpu.search.tpu_service import TpuSearchService

EVIL_IDS = ['plain', 'has"quote', 'has,comma', 'has","both', 'back\\slash',
            'unié中', 'tab\there', '{"j":1}', "'single'",
            '":","']


@pytest.fixture
def corpus(tmp_path):
    svc = IndicesService(str(tmp_path))
    idx = svc.create_index(
        "corpus", Settings.of({"index": {"number_of_shards": 1}}),
        {"properties": {"body": {"type": "text"}}})
    for i, doc_id in enumerate(EVIL_IDS):
        idx.shard(idx.shard_for_id(doc_id)).apply_index_on_primary(
            doc_id, {"body": "alpha " * (i + 1)})
    idx.refresh()
    yield svc, idx
    svc.close()


def _search(svc, tpu, body):
    return coordinator.search(svc, "corpus", dict(body), tpu_search=tpu)


BODY = {"query": {"match": {"body": "alpha"}}, "size": 20,
        "_source": False}


def test_fast_json_hostile_ids_round_trip(corpus):
    svc, idx = corpus
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        resp = _search(svc, tpu, BODY)
        hits = resp["hits"]["hits"]
        assert isinstance(hits, ColumnarHits)
        assert tpu.served == 1
        fast = json.loads(hits.to_json())
        slow = assemble_hits_list(
            hits.name, hits.resident, hits.scores, hits.rows, hits.ords,
            False, False, False)
        assert fast == json.loads(json.dumps(slow))
        assert sorted(h["_id"] for h in fast) == sorted(EVIL_IDS)
    finally:
        tpu.close()


def test_columnar_block_equals_per_hit_dicts_over_two_shards(tmp_path,
                                                             seeded_np):
    """100 hits from two shards: the columnar block parses to the hits
    the per-hit dict path gives, in the same order."""
    words = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
             "theta", "iota", "kappa", "lamda", "mu"]
    svc = IndicesService(str(tmp_path))
    idx = svc.create_index(
        "corpus", Settings.of({"index": {"number_of_shards": 2}}),
        {"properties": {"body": {"type": "text"}}})
    for i in range(300):
        picks = seeded_np.integers(0, len(words),
                                   int(seeded_np.integers(4, 14)))
        doc_id = f"d{i}"
        idx.shard(idx.shard_for_id(doc_id)).apply_index_on_primary(
            doc_id, {"body": " ".join(words[int(w)] for w in picks)})
    idx.refresh()
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        resp = _search(svc, tpu, {
            "query": {"match": {"body": "alpha beta gamma delta"}},
            "size": 100, "_source": False})
        hits = resp["hits"]["hits"]
        assert isinstance(hits, ColumnarHits)
        assert len(hits) > 20
        assert len({int(r) for r in hits.rows}) == 2  # both shards
        args = ("corpus", hits.resident, hits.scores, hits.rows,
                hits.ords, False, False, False)
        fast = json.loads(ColumnarHits(*args).to_json())
        slow = json.loads(json.dumps(assemble_hits_list(*args)))
        assert [h["_id"] for h in fast] == [h["_id"] for h in slow]
        assert [h["_score"] for h in fast] == \
               pytest.approx([h["_score"] for h in slow])
    finally:
        tpu.close()
        svc.close()


def test_dumps_response_matches_plain_dumps(corpus):
    svc, idx = corpus
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        resp = _search(svc, tpu, BODY)
        assert isinstance(resp["hits"]["hits"], ColumnarHits)
        fast_payload = json.loads(dumps_response(resp))
        # reference: force-materialize and use stock json
        resp["hits"]["hits"] = list(resp["hits"]["hits"])
        ref_payload = json.loads(json.dumps(resp))
        assert fast_payload == ref_payload
    finally:
        tpu.close()


def test_source_shape_falls_back_to_materialized(corpus):
    svc, idx = corpus
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        body = dict(BODY)
        body["_source"] = True
        resp = _search(svc, tpu, body)
        hits = resp["hits"]["hits"]
        assert isinstance(hits, ColumnarHits)
        assert hits._fast_json() is None  # not the metadata-only shape
        parsed = json.loads(hits.to_json())
        assert all("_source" in h and "body" in h["_source"]
                   for h in parsed)
    finally:
        tpu.close()


def test_mutations_survive_serialization(corpus):
    svc, idx = corpus
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        resp = _search(svc, tpu, BODY)
        hits = resp["hits"]["hits"]
        assert isinstance(hits, ColumnarHits)
        hits[0]["_index"] = "remote:corpus"  # what ccs does
        parsed = json.loads(dumps_response(resp))
        assert parsed["hits"]["hits"][0]["_index"] == "remote:corpus"
    finally:
        tpu.close()


def test_empty_hits_fast_path():
    empty = np.empty(0, dtype=np.float32)
    rows = np.empty(0, dtype=np.int32)
    h = ColumnarHits("i", None, empty, rows, rows, False, False, False)
    assert h.to_json() == "[]"
    assert len(h) == 0 and list(h) == []


def test_dumps_response_without_columnar_is_plain_json():
    payload = {"took": 1, "hits": {"total": {"value": 0, "relation": "eq"},
                                   "hits": []}}
    assert json.loads(dumps_response(payload)) == payload


# ---------------------------------------------------------------------------
# the native renderer (es_render_hits): byte parity with json.dumps, and
# every shape it must leave to the Python path
# ---------------------------------------------------------------------------


@pytest.fixture
def native_render(monkeypatch):
    monkeypatch.setattr(serializer, "_SPLICE_TRIED", False)
    monkeypatch.delenv("ES_TPU_NO_NATIVE_SPLICE", raising=False)
    if serializer._native_render() is None:
        pytest.skip("native renderer unavailable (no C toolchain)")


def _resident(id_lists, table=True):
    """What the serializer reads of a ResidentPack: one row per list."""
    sizes = [len(ids) for ids in id_lists]
    id_cat = np.empty(sum(sizes), dtype=object)
    id_cat[:] = [i for ids in id_lists for i in ids]
    row_offset = np.zeros(len(sizes), dtype=np.int64)
    np.cumsum(sizes[:-1], out=row_offset[1:])
    res = types.SimpleNamespace(
        id_cat=id_cat, row_offset=row_offset,
        id_json=JsonLiterals.build(id_lists) if table else None)
    res.resolve_ids = lambda rows, ords: id_cat[row_offset[rows] + ords]
    return res


def _reference(name, res, scores, rows, ords):
    """The block as plain json.dumps of the hit dicts renders it."""
    hits = assemble_hits_list(name, res, scores, rows, ords,
                              False, False, False)
    return json.dumps(hits, separators=(",", ":")).encode("utf-8")


def _counts():
    c = RENDER_COUNTS.counts()
    return c["native"], c["python"]


def _float32_bits(rng, n):
    bits = rng.integers(0, 2 ** 32, n, dtype=np.uint64).astype(np.uint32)
    vals = bits.view(np.float32)
    return vals[np.isfinite(vals)]


def _neighbours(values):
    a = np.asarray(values, dtype=np.float32)
    with np.errstate(over="ignore"):
        out = np.concatenate([a, np.nextafter(a, np.float32(np.inf)),
                              np.nextafter(a, np.float32(-np.inf))])
    return out[np.isfinite(out)]


F32_MAX = float(np.finfo(np.float32).max)
SCORE_CASES = {
    # 220,000 seeded values: what BM25 gives, then anything a float32 holds
    "uniform_0_30": lambda: np.random.default_rng(26).uniform(
        0, 30, 110_000).astype(np.float32),
    "random_bits": lambda: _float32_bits(np.random.default_rng(27), 110_000),
    "zeros_and_integers": lambda: np.array(
        [0.0, -0.0, 1.0, 3.0, -3.0, 10.0, 100.0, 123456.0, 16777216.0]
        + list(range(0, 2000)), dtype=np.float32),
    "short_fractions": lambda: np.concatenate(
        [np.arange(1, 4000, dtype=np.float32) / np.float32(8),
         np.arange(1, 4000, dtype=np.float32) / np.float32(1000)]),
    "powers_of_two": lambda: np.array(
        [s * 2.0 ** k for k in range(-149, 128) for s in (1, -1)],
        dtype=np.float32),
    "subnormals": lambda: np.concatenate(
        [np.arange(1, 2000, dtype=np.uint32).view(np.float32),
         _neighbours([1.17549435e-38, 1e-40, 1e-45])]),
    "around_1e-4_and_1e16": lambda: _neighbours(
        [1e-4, 9.999e-5, 1.0001e-4, 1e-5, 1e-3, 1e16, 9.99e15, 1.0001e16,
         1e15, 1e17, 1e22, 1e23]),
    "powers_of_ten": lambda: _neighbours(
        [10.0 ** k for k in range(-45, 39)]),
    "float32_max": lambda: _neighbours([F32_MAX, -F32_MAX]),
}


@pytest.mark.parametrize("case", sorted(SCORE_CASES))
def test_native_scores_match_json_dumps(native_render, case):
    scores = np.ascontiguousarray(SCORE_CASES[case](), dtype=np.float32)
    assert len(scores) and np.isfinite(scores).all()
    res = _resident([["a"]])
    zeros = np.zeros(len(scores), dtype=np.int32)
    got = ColumnarHits("i", res, scores, zeros, zeros).render_native()
    assert got is not None
    want = _reference("i", res, scores, zeros, zeros)
    if got != want:  # name the first score that differs, not 2 MB of bytes
        for g, w, s in zip(got.split(b'"_score":')[1:],
                           want.split(b'"_score":')[1:], scores):
            assert g == w, float(s)
    assert got == want


ID_CASES = {
    "quotes": ['has"quote', '"', '""', "'single'", '":","', 'has","both'],
    "backslashes": ["back\\slash", "\\", "\\\\", '\\"', "\\u0041", "a\\"],
    "control": ["tab\there", "nl\nnl", "\x00", "\x1f\x7f", "\r\b\f"],
    "non_ascii": ["unié中", "é", "\U0001f600", "\ud800", "ÿ "],
    "empty_id": ["", "x", ""],
    "json_looking": ['{"j":1}', "[1,2]", "curly}brace{", "null", "1e5"],
}


@pytest.mark.parametrize("case", sorted(ID_CASES))
def test_native_ids_match_json_dumps(native_render, case):
    ids = ID_CASES[case]
    res = _resident([ids])
    n = len(ids)
    rows = np.zeros(n, dtype=np.int32)
    ords = np.arange(n, dtype=np.int32)[::-1].copy()
    scores = np.linspace(9, 1, n).astype(np.float32)
    for name in ("idx", 'na"me\\é'):
        got = ColumnarHits(name, res, scores, rows, ords).render_native()
        assert got == _reference(name, res, scores, rows, ords)
        assert [h["_id"] for h in json.loads(got)] == ids[::-1]


@pytest.mark.parametrize("n", [0, 1, 1000, 10_000])
def test_native_block_sizes(native_render, n):
    rng = np.random.default_rng(n)
    ids = [f"doc-{i}" for i in range(max(n, 1) * 2)]
    res = _resident([ids])
    rows = np.zeros(n, dtype=np.int32)
    ords = rng.integers(0, len(ids), n).astype(np.int32)
    scores = np.sort(rng.uniform(0, 30, n).astype(np.float32))[::-1].copy()
    block = ColumnarHits("msmarco", res, scores, rows, ords)
    payload = {"took": 3, "timed_out": False,
               "hits": {"total": {"value": n, "relation": "eq"},
                        "max_score": None, "hits": block}}
    native0, python0 = _counts()
    got = dumps_response_bytes(payload)
    assert _counts() == (native0 + 1, python0)
    assert got == dumps_response(payload).encode("utf-8")
    parsed = json.loads(got)
    assert parsed["hits"]["hits"] == json.loads(
        _reference("msmarco", res, scores, rows, ords))
    assert len(parsed["hits"]["hits"]) == n


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_native_two_shards_row_offset(native_render, dtype):
    # row 1's ordinals start where row 0's ids end, row 2 is padding
    res = _resident([[f"a{i}" for i in range(7)],
                     [f"b{i}" for i in range(5)], []])
    rows = np.array([1, 0, 1, 0, 1], dtype=dtype)
    ords = np.array([4, 6, 0, 0, 2], dtype=dtype)
    scores = np.array([5, 4, 3, 2, 1], dtype=np.float32)
    # a window sliced out of a longer result, as the coordinator slices it
    block = ColumnarHits("i", res, scores[1:4], rows[1:4], ords[1:4])
    got = block.render_native()
    assert [h["_id"] for h in json.loads(got)] == ["a6", "b0", "a0"]
    assert got == _reference("i", res, scores[1:4], rows[1:4], ords[1:4])


def test_encoded_ids_concat_is_the_table_of_the_concatenation():
    parts = [["a", 'q"'], [], ["é", "", "zz"]]
    whole = JsonLiterals.build([[i for p in parts for i in p]])
    chained = JsonLiterals.concat([JsonLiterals.build([p]) for p in parts])
    assert chained.blob.tobytes() == whole.blob.tobytes()
    assert chained.offsets.tolist() == whole.offsets.tolist()
    assert chained.max_len == whole.max_len == len('"\\u00e9"')
    assert JsonLiterals.concat([whole, None]) is None
    assert JsonLiterals.build([["a"], [7]]) is None


def _block(**kw):
    ids = kw.pop("ids", ["a", "b", "c"])
    scores = np.asarray(kw.pop("scores", [3.0, 2.5, 1.0]), dtype=np.float32)
    res = _resident([ids], table=kw.pop("table", True))
    rows = np.zeros(len(scores), dtype=np.int32)
    ords = np.arange(len(scores), dtype=np.int32)
    if kw:  # stored fields come from the row's segment
        seg = types.SimpleNamespace(
            stored_source=[{"f": i} for i in range(len(ids))],
            doc_versions=[4] * len(ids), seq_nos=[9] * len(ids),
            primary_terms=[1] * len(ids))
        res.row_segments = [seg]
    return ColumnarHits("i", res, scores, rows, ords, **kw)


OLD_PATH_CASES = {
    # a `_source` filter: the native renderer writes whole sources only
    "source": lambda: _block(source=["f"]),
    "version": lambda: _block(version=True),
    "seq_no_primary_term": lambda: _block(seq_no_primary_term=True),
    "non_string_ids": lambda: _block(ids=["a", 7, "c"]),
    "nan_score": lambda: _block(scores=[3.0, float("nan"), 1.0]),
    "inf_score": lambda: _block(scores=[float("inf"), 2.0, 1.0]),
    "resident_without_table": lambda: _block(table=False),
    "float64_scores": lambda: ColumnarHits(
        "i", _resident([["a"]]), np.array([0.1]), np.zeros(1, np.int32),
        np.zeros(1, np.int32)),
    "ordinal_outside_the_table": lambda: ColumnarHits(
        "i", _resident([["a", "b"]]), np.ones(1, np.float32),
        np.zeros(1, np.int32), np.array([-1], np.int32)),
}


@pytest.mark.parametrize("case", sorted(OLD_PATH_CASES))
def test_old_path_shapes_render_in_python(native_render, case):
    block = OLD_PATH_CASES[case]()
    payload = {"took": 1, "hits": {"max_score": 3.0, "hits": block}}
    native0, python0 = _counts()
    got = dumps_response_bytes(payload)
    assert _counts() == (native0, python0 + 1)
    want = json.dumps({"took": 1, "hits": {
        "max_score": 3.0, "hits": assemble_hits_list(
            "i", block.resident, block.scores, block.rows, block.ords,
            block.source, block.version, block.seq_no_primary_term)}})
    assert json.loads(got) == json.loads(want)
    assert got == dumps_response(payload).encode("utf-8")


def test_materialized_block_honours_the_edited_dicts(native_render):
    block = _block()
    block[0]["_index"] = "remote:i"  # what ccs does
    payload = {"hits": {"hits": block}}
    native0, python0 = _counts()
    got = dumps_response_bytes(payload)
    assert _counts() == (native0, python0 + 1)
    assert json.loads(got)["hits"]["hits"][0]["_index"] == "remote:i"


@pytest.mark.parametrize("how", ["env", "missing_library"])
def test_fallback_gives_the_same_bytes(monkeypatch, how):
    rng = np.random.default_rng(5)
    ids = ["plain", 'q"uote', "back\\slash", "unié中", ""] * 40
    scores = np.sort(rng.uniform(0, 30, 200).astype(np.float32))[::-1].copy()
    rows = np.zeros(200, dtype=np.int32)
    ords = rng.permutation(200).astype(np.int32)

    def payload():
        return {"took": 2, "hits": {"max_score": float(scores[0]),
                                    "hits": ColumnarHits(
                                        "i", _resident([ids]), scores, rows,
                                        ords)}}
    monkeypatch.setattr(serializer, "_SPLICE_TRIED", False)
    monkeypatch.delenv("ES_TPU_NO_NATIVE_SPLICE", raising=False)
    with_native = dumps_response_bytes(payload())
    monkeypatch.setattr(serializer, "_SPLICE_TRIED", False)
    monkeypatch.setattr(serializer, "_SPLICE_FN", None)
    monkeypatch.setattr(serializer, "_RENDER_FN", None)
    if how == "env":
        monkeypatch.setenv("ES_TPU_NO_NATIVE_SPLICE", "1")
    else:
        monkeypatch.setattr(native, "load", lambda name: None)
    native0, python0 = _counts()
    without = dumps_response_bytes(payload())
    assert serializer._native_render() is None
    assert serializer._native_splice() is None
    assert _counts() == (native0, python0 + 1)
    assert without == with_native
    assert json.loads(without) == json.loads(json.dumps(
        {"took": 2, "hits": {"max_score": float(scores[0]),
                             "hits": list(payload()["hits"]["hits"])}}))


def test_wire_form_for_the_fronts_is_unchanged(native_render):
    ids = ["a", 'q"', "é"]
    scores = np.array([2.5, 1.25, 0.1], dtype=np.float32)
    block = ColumnarHits("i", _resident([ids]), scores,
                         np.zeros(3, np.int32), np.arange(3, dtype=np.int32))
    payload = {"took": 1, "hits": {"max_score": 2.5, "hits": block}}
    before = _counts()
    parts, columns = encode_wire_response(payload)
    assert _counts() == before  # shipped as columns, rendered by the front
    assert parts == ['{"took": 1, "hits": {"max_score": 2.5, "hits": ', "}}"]
    assert columns == [SpliceColumns(
        3, '["a","q\\"","\\u00e9"]',
        "[2.5,1.25,0.10000000149011612]", '["i"]', [0, 0, 0])]
    assert (serializer.splice_wire(parts, columns).encode("utf-8")
            == dumps_response_bytes(payload))


def test_pack_build_gives_the_table_and_stats_count_the_path(
        corpus, native_render):
    svc, idx = corpus
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        before = tpu.stats()["render"]
        assert set(before) == {"native", "python"}
        resp = _search(svc, tpu, BODY)
        block = resp["hits"]["hits"]
        table = block.resident.id_json
        assert table is not None
        assert len(table.offsets) == len(block.resident.id_cat) + 1
        got = dumps_response_bytes(resp)
        assert got == dumps_response(resp).encode("utf-8")
        after = tpu.stats()["render"]
        assert after["native"] == before["native"] + 1
        assert after["python"] == before["python"] + 1  # dumps_response
        assert sorted(h["_id"] for h in json.loads(got)["hits"]["hits"]) \
            == sorted(EVIL_IDS)
    finally:
        tpu.close()
