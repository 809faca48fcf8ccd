"""Hits blocks that return each hit's whole `_source`, rendered by the
native renderer: `es_render_hits` writes `,"_source":<literal>` after
`_score` from the pack's source table (`ResidentPack.source_literals`,
`JsonLiterals` of `encode_source`), built once per pack by the first
block that asks for it. The bytes must be the Python path's to the byte
(`ColumnarHits.to_json`, through `dumps_response`) for every kind of
source a doc can hold, on a base pack and on a base + delta chain; the
shapes the native renderer does not write still render in Python; the
`render` and `fetch` counters and the `fetch` and `source_table` stages
count what happened; and a pack that never serves `_source` never builds
the table."""

import json
import types

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.indices.service import IndicesService
from elasticsearch_tpu.search import coordinator, serializer
from elasticsearch_tpu.search.serializer import (FETCH_COUNTS, RENDER_COUNTS,
                                                 ColumnarHits, JsonLiterals,
                                                 dumps_response,
                                                 dumps_response_bytes,
                                                 encode_source)
from elasticsearch_tpu.search.tpu_service import StageTimes, TpuSearchService

DOCS = 24


@pytest.fixture
def native_render(monkeypatch):
    monkeypatch.setattr(serializer, "_SPLICE_TRIED", False)
    monkeypatch.delenv("ES_TPU_NO_NATIVE_SPLICE", raising=False)
    if serializer._native_render() is None:
        pytest.skip("native renderer unavailable (no C toolchain)")


@pytest.fixture
def indices(tmp_path):
    svc = IndicesService(str(tmp_path))
    yield svc
    svc.close()


#: doc i's source by case; every one matches `alpha`. `empty_object` and
#: `no_stored_source` are stored as `plain` and set to {} / None on the
#: segments before the first `_source` block builds the table.
SOURCES = {
    "plain": lambda i: {"body": f"alpha plain words {i}", "title": "t"},
    "non_ascii": lambda i: {"body": "alpha unié中 \U0001f600 grüße",
                            "title": "Ω" * (i % 4), "n": i},
    "escapes": lambda i: {"body": 'alpha "quoted" back\\slash',
                          "raw": "tab\there nl\nnl \x00\x1f \ud800",
                          "json_looking": '{"j":1},[',
                          "n": i},
    "nested_numbers": lambda i: {
        "body": "alpha nested",
        "meta": {"tags": ["x", {"deep": [1, 2.5, [], {}]}], "int": i,
                 "float": 0.1 * i, "big": 1e22, "tiny": 5e-324,
                 "neg": -0.0, "f32": float(np.float32(0.1)),
                 "flag": i % 2 == 0, "none": None}},
    "empty_object": lambda i: {"body": f"alpha {i}"},
    "no_stored_source": lambda i: {"body": f"alpha {i}"},
}
STORED_AS = {"empty_object": {}, "no_stored_source": None}


def _index(svc, name, docs):
    idx = svc.create_index(
        name, Settings.of({"index": {"number_of_shards": 2}}),
        {"dynamic": "false", "properties": {"body": {"type": "text"}}})
    _add(idx, docs)
    return idx


def _add(idx, docs):
    for doc_id, src in docs:
        idx.shard(idx.shard_for_id(doc_id)).apply_index_on_primary(
            doc_id, src)
    idx.refresh()


def _search(svc, tpu, name, **body):
    return coordinator.search(svc, name, {
        "query": {"match": {"body": "alpha"}}, "size": 100, **body},
        tpu_search=tpu)


def _store(resident, case, expected):
    """Replace the stored source of every other doc as `case` asks, on
    the segments the pack was built from."""
    for seg in resident.row_segments:
        if seg is None:
            continue
        for o, doc_id in enumerate(seg.doc_ids):
            if int(doc_id[1:]) % 2 == 0:
                seg.stored_source[o] = STORED_AS[case]
                expected[doc_id] = STORED_AS[case]


def _counts():
    return dict(RENDER_COUNTS.counts()), dict(FETCH_COUNTS.counts())


@pytest.mark.parametrize("layout", ["base", "base_and_delta"])
@pytest.mark.parametrize("case", sorted(SOURCES))
def test_native_bytes_are_the_python_paths(indices, native_render, case,
                                           layout):
    docs = [(f"d{i}", SOURCES[case](i)) for i in range(DOCS)]
    expected = dict(docs)
    delta = layout == "base_and_delta"
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                           delta={"enabled": True} if delta else None)
    try:
        idx = _index(indices, "src", docs[:DOCS // 2] if delta else docs)
        first = _search(indices, tpu, "src", _source=False)["hits"]["hits"]
        if delta:
            _add(idx, docs[DOCS // 2:])
        resp = _search(indices, tpu, "src", _source=True)
        block = resp["hits"]["hits"]
        assert isinstance(block, ColumnarHits) and len(block) == DOCS
        resident = block.resident
        if delta:
            assert tpu.delta_stats.appends == 1
            assert len(resident.packs) == 2 and resident.source_json is None
        else:
            assert resident is first.resident
        if case in STORED_AS:
            _store(resident, case, expected)
        render0, fetch0 = _counts()
        got = dumps_response_bytes(resp)
        render1, fetch1 = _counts()
        assert render1["native"] == render0["native"] + 1
        assert render1["python"] == render0["python"]
        want_bytes = sum(len(encode_source(expected[d])) for d in expected)
        assert fetch1["hits"] - fetch0["hits"] == DOCS
        assert fetch1["source_bytes"] - fetch0["source_bytes"] == want_bytes
        table = resident.source_json
        assert table is not None
        assert len(table.offsets) == len(resident.id_cat) + 1
        # the Python path's bytes, from the same block, materialized now
        assert got == dumps_response(resp).encode("utf-8")
        hits = json.loads(got)["hits"]["hits"]
        assert {h["_id"]: h["_source"] for h in hits} == expected
        assert all(list(h) == ["_index", "_id", "_score", "_source"]
                   for h in hits)
    finally:
        tpu.close()


def test_a_pack_builds_its_table_once_and_only_for_source(indices,
                                                          native_render):
    """Metadata-only blocks leave the pack without a source table and
    record no `fetch` or `source_table`; the first `_source` block builds
    it in the stage `source_table` and is the stage `fetch`, CPU read the
    first time; the next reuses it. `/_tpu/stats` shows the counter and
    the table's host bytes."""
    _index(indices, "src", [(f"d{i}", SOURCES["plain"](i))
                            for i in range(DOCS)])
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    stages = StageTimes()
    try:
        for _ in range(2):
            resp = _search(indices, tpu, "src", _source=False)
            dumps_response_bytes(resp, stages)
        resident = resp["hits"]["hits"].resident
        assert resident.source_json is None
        assert "fetch" not in stages.snapshot()
        assert "source_table" not in stages.snapshot()
        pack_stats = tpu.stats()["pack_cache"]["packs"]["src/body"]
        assert pack_stats["source_table_bytes"] == 0
        for n in (1, 2):
            resp = _search(indices, tpu, "src", _source=True)
            assert resp["hits"]["hits"].resident is resident
            dumps_response_bytes(resp, stages)
            snap = stages.snapshot()
            assert snap["fetch"]["count"] == n
            assert snap["fetch"]["cpu_count"] == 1
            assert snap["source_table"]["count"] == 1
            assert "cpu_seconds" not in snap["source_table"]
            assert snap["source_table"]["seconds"] <= snap["fetch"]["seconds"]
        table = resident.source_json
        stats = tpu.stats()
        assert stats["pack_cache"]["packs"]["src/body"][
            "source_table_bytes"] == table.blob.nbytes + table.offsets.nbytes
        assert set(stats["fetch"]) == {"hits", "source_bytes"}
        assert stats["fetch"]["hits"] >= 2 * DOCS
    finally:
        tpu.close()


PYTHON_SHAPES = {
    "source_filter": {"_source": ["title"]},
    "version": {"_source": True, "version": True},
    "seq_no_primary_term": {"_source": True, "seq_no_primary_term": True},
}


@pytest.mark.parametrize("shape", sorted(PYTHON_SHAPES))
def test_other_shapes_render_in_python(indices, native_render, shape):
    """A `_source` filter, `version` and `seq_no_primary_term` are not a
    shape the native renderer writes: the Python path renders them,
    counted under `render.python` and `fetch`, as the stage `fetch`."""
    _index(indices, "src", [(f"d{i}", SOURCES["nested_numbers"](i))
                            for i in range(DOCS)])
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    stages = StageTimes()
    try:
        resp = _search(indices, tpu, "src", **PYTHON_SHAPES[shape])
        block = resp["hits"]["hits"]
        assert isinstance(block, ColumnarHits)
        render0, fetch0 = _counts()
        got = dumps_response_bytes(resp, stages)
        render1, fetch1 = _counts()
        assert render1["python"] == render0["python"] + 1
        assert render1["native"] == render0["native"]
        assert fetch1["hits"] - fetch0["hits"] == DOCS
        assert fetch1["source_bytes"] - fetch0["source_bytes"] == sum(
            len(encode_source(h["_source"])) for h in block)
        assert stages.snapshot()["fetch"]["count"] == 1
        assert block.resident.source_json is None
        assert got == dumps_response(resp).encode("utf-8")
    finally:
        tpu.close()


def test_non_string_ids_render_in_python(native_render):
    """A pack with an id that is not a string has no id table, so its
    `_source` blocks render in Python, as its metadata-only ones do."""
    ids, sources = ["a", 7, "c"], [{"f": i} for i in range(3)]
    seg = types.SimpleNamespace(stored_source=sources)
    res = types.SimpleNamespace(
        id_cat=np.array(ids, dtype=object),
        row_offset=np.zeros(1, dtype=np.int64), row_segments=[seg],
        id_json=JsonLiterals.build([ids]),
        source_literals=lambda stages=None: JsonLiterals.build(
            [sources], encode=encode_source))
    res.resolve_ids = lambda rows, ords: res.id_cat[res.row_offset[rows]
                                                     + ords]
    assert res.id_json is None
    block = ColumnarHits("i", res, np.array([3.0, 2.0, 1.0], np.float32),
                         np.zeros(3, np.int32), np.arange(3, dtype=np.int32),
                         source=True)
    assert block.render_native() is None
    render0, _ = _counts()
    got = dumps_response_bytes({"hits": {"hits": block}})
    assert _counts()[0]["python"] == render0["python"] + 1
    assert json.loads(got)["hits"]["hits"] == [
        {"_index": "i", "_id": i, "_score": s, "_source": src}
        for i, s, src in zip(ids, [3.0, 2.0, 1.0], sources)]


def test_source_literals_are_the_python_paths_values():
    """`JsonLiterals.build` with `encode_source` holds each value as
    json.dumps writes it in a hit, refuses what json cannot write, and
    a chain's table is the table of the concatenation."""
    parts = [[{"a": "é"}, None], [], [{}, [1, 2.5, "x"], "s", 0.1]]
    whole = JsonLiterals.build([[v for p in parts for v in p]],
                               encode=encode_source)
    chained = JsonLiterals.concat([JsonLiterals.build([p],
                                                      encode=encode_source)
                                   for p in parts])
    flat = [v for p in parts for v in p]
    blob = whole.blob.tobytes().decode("ascii")
    assert [blob[a:b] for a, b in zip(whole.offsets[:-1], whole.offsets[1:])
            ] == [json.dumps(v, separators=(",", ":")) for v in flat]
    assert chained.blob.tobytes() == whole.blob.tobytes()
    assert chained.offsets.tolist() == whole.offsets.tolist()
    assert JsonLiterals.build([[{"bad": object()}]], encode=encode_source) \
        is None
    assert JsonLiterals.build([[{1.5j: 1}]], encode=encode_source) is None


def test_prometheus_carries_render_and_fetch(tmp_path, native_render):
    from elasticsearch_tpu.node import Node
    node = Node(str(tmp_path))
    try:
        prom = node.metrics.prometheus_text()
        for labels in ('path="native"', 'path="python"'):
            assert f"es_tpu_response_render_total{{{labels}}}" in prom
        for labels in ('kind="hits"', 'kind="source_bytes"'):
            assert f"es_tpu_response_fetch_total{{{labels}}}" in prom
    finally:
        node.close()
