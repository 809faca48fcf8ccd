"""TPU serving path tests: DSL lowering, pack residency, micro-batching,
and — the load-bearing part — exact equivalence between the kernel fast
path and the planner path on randomized corpora (the reference's pattern
of testing a new engine implementation against the existing one)."""

import threading

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.indices.service import IndicesService
from elasticsearch_tpu.search import coordinator, dsl
from elasticsearch_tpu.search.tpu_service import (TpuSearchService,
                                                  lower_query)

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa", "lamda", "mu"]


@pytest.fixture
def svc(tmp_path):
    s = IndicesService(str(tmp_path))
    yield s
    s.close()


def make_corpus(svc, seeded_np, *, name="corpus", shards=2, docs=120,
                flush_some=True):
    idx = svc.create_index(
        name, Settings.of({"index": {"number_of_shards": shards}}),
        {"properties": {"body": {"type": "text"},
                        "tag": {"type": "keyword"}}})
    for i in range(docs):
        n_words = int(seeded_np.integers(3, 12))
        words = [WORDS[int(w)] for w in
                 seeded_np.integers(0, len(WORDS), n_words)]
        doc_id = f"d{i}"
        shard = idx.shard(idx.shard_for_id(doc_id))
        shard.apply_index_on_primary(
            doc_id, {"body": " ".join(words), "tag": f"t{i % 3}"})
        if flush_some and i == docs // 2:
            idx.flush()  # split into multiple segments per shard
    idx.refresh()
    return idx


def both_paths(svc, name, body):
    """Run the same search through the kernel path and the planner path."""
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        fast = coordinator.search(svc, name, dict(body), tpu_search=tpu)
        assert tpu.served > 0, "query did not take the kernel path"
    finally:
        tpu.close()
    slow = coordinator.search(svc, name, dict(body), tpu_search=None)
    return fast, slow


def assert_equivalent(fast, slow):
    assert fast["hits"]["total"]["value"] == slow["hits"]["total"]["value"]
    fh, sh = fast["hits"]["hits"], slow["hits"]["hits"]
    assert [h["_id"] for h in fh] == [h["_id"] for h in sh]
    for a, b in zip(fh, sh):
        assert a["_score"] == pytest.approx(b["_score"], rel=1e-5, abs=1e-6)
        assert a.get("_source") == b.get("_source")
    if fast["hits"]["max_score"] is None:
        assert slow["hits"]["max_score"] is None
    else:
        assert fast["hits"]["max_score"] == pytest.approx(
            slow["hits"]["max_score"], rel=1e-5, abs=1e-6)


class TestLowering:
    def setup_method(self):
        from elasticsearch_tpu.mapping import MapperService
        self.mapper = MapperService(Settings.EMPTY, {"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"}}})

    def test_match_or(self):
        f = lower_query(dsl.MatchQuery(field="body", query="Alpha beta"),
                        self.mapper)
        assert f.terms == ["alpha", "beta"] and f.min_count == 1

    def test_match_and(self):
        f = lower_query(dsl.MatchQuery(field="body", query="alpha beta",
                                       operator="and"), self.mapper)
        assert f.min_count == 2

    def test_match_msm(self):
        f = lower_query(dsl.MatchQuery(field="body",
                                       query="alpha beta gamma",
                                       minimum_should_match=2), self.mapper)
        assert f.min_count == 2

    def test_term_on_keyword_falls_back(self):
        assert lower_query(dsl.TermQuery(field="tag", value="t1"),
                           self.mapper) is None

    def test_bool_should_same_field(self):
        f = lower_query(dsl.BoolQuery(should=[
            dsl.TermQuery(field="body", value="alpha"),
            dsl.TermQuery(field="body", value="beta")]), self.mapper)
        assert f.terms == ["alpha", "beta"]

    def test_bool_with_must_falls_back(self):
        assert lower_query(dsl.BoolQuery(must=[
            dsl.TermQuery(field="body", value="alpha")]),
            self.mapper) is None

    def test_phrase_falls_back(self):
        assert lower_query(dsl.MatchPhraseQuery(field="body",
                                                query="alpha beta"),
                           self.mapper) is None


class TestEquivalence:
    """Kernel path == planner path: scores, order, totals, sources."""

    @pytest.mark.parametrize("q", [
        {"match": {"body": "alpha"}},
        {"match": {"body": "alpha beta gamma"}},
        {"match": {"body": {"query": "alpha beta", "operator": "and"}}},
        {"match": {"body": {"query": "alpha beta gamma delta",
                            "minimum_should_match": 3}}},
        {"terms": {"body": ["zeta", "kappa"]}},
        {"bool": {"should": [{"term": {"body": "mu"}},
                             {"term": {"body": "iota"}}]}},
    ])
    def test_query_shapes(self, svc, seeded_np, q):
        make_corpus(svc, seeded_np)
        fast, slow = both_paths(svc, "corpus", {"query": q, "size": 30})
        assert_equivalent(fast, slow)

    def test_multi_shard_multi_segment(self, svc, seeded_np):
        make_corpus(svc, seeded_np, shards=3, docs=200)
        fast, slow = both_paths(
            svc, "corpus", {"query": {"match": {"body": "alpha beta"}},
                            "size": 50})
        assert_equivalent(fast, slow)

    def test_after_deletes(self, svc, seeded_np):
        idx = make_corpus(svc, seeded_np, docs=80)
        for i in range(0, 80, 7):
            shard = idx.shard(idx.shard_for_id(f"d{i}"))
            shard.apply_delete_on_primary(f"d{i}")
        idx.refresh()
        fast, slow = both_paths(
            svc, "corpus", {"query": {"match": {"body": "alpha"}},
                            "size": 100})
        assert_equivalent(fast, slow)

    def test_pagination(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        fast, slow = both_paths(
            svc, "corpus", {"query": {"match": {"body": "alpha"}},
                            "from": 5, "size": 7})
        assert_equivalent(fast, slow)

    def test_min_score_falls_back_with_consistent_totals(self, svc,
                                                         seeded_np):
        """min_score queries decline the kernel path (its totals count
        pre-filter) and the planner applies min_score to the MATCH SET,
        so totals agree with the sorted path (ADVICE r2 low #3)."""
        make_corpus(svc, seeded_np)
        body = {"query": {"match": {"body": "alpha beta"}},
                "min_score": 1.0, "size": 10_000}
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            res = coordinator.search(svc, "corpus", dict(body),
                                     tpu_search=tpu)
            assert tpu.served == 0  # declined before any kernel submit
        finally:
            tpu.close()
        # every reported hit honors the floor...
        assert all(h["_score"] >= 1.0 for h in res["hits"]["hits"])
        # ...and the total equals the filtered hit count (size covers
        # the full match set here) and matches the sorted path's total
        assert res["hits"]["total"]["value"] == len(res["hits"]["hits"])
        sorted_res = coordinator.search(
            svc, "corpus", dict(body, sort=[{"_score": "desc"}]),
            tpu_search=None)
        assert sorted_res["hits"]["total"]["value"] == \
            res["hits"]["total"]["value"]

    def test_boost(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        fast, slow = both_paths(
            svc, "corpus",
            {"query": {"match": {"body": {"query": "alpha", "boost": 2.5}}},
             "size": 20})
        assert_equivalent(fast, slow)


class TestPerPackQueues:
    def test_slow_pack_does_not_block_other_packs(self, monkeypatch):
        """Pack A's kernel launch (e.g. a compile stall) must not delay
        pack B's queries — each pack batches on its own worker."""
        import threading
        import time as _time

        from elasticsearch_tpu.search import tpu_service as svc_mod

        batcher = svc_mod.MicroBatcher(window_s=0.0, max_batch=8)
        slow_started = threading.Event()
        release_slow = threading.Event()

        class _FakePack:
            pass

        pack_a, pack_b = _FakePack(), _FakePack()

        def fake_launch(resident, flats, k, mesh=None, stages=None,
                        max_batch=128):
            if resident is pack_a:
                slow_started.set()
                assert release_slow.wait(timeout=10.0)
            return {"results": [f"res-{id(resident)}" for _ in flats]}

        monkeypatch.setattr(svc_mod, "launch_flat_batch", fake_launch)
        monkeypatch.setattr(svc_mod, "finish_flat_batch",
                            lambda st: st["results"])
        try:
            fut_a = batcher.submit(pack_a, flat=None, k=1)
            assert slow_started.wait(timeout=5.0)
            # pack A's launch is in flight and blocked; pack B must
            # still complete
            fut_b = batcher.submit(pack_b, flat=None, k=1)
            assert fut_b.result(timeout=5.0) == f"res-{id(pack_b)}"
            assert not fut_a.done()
            release_slow.set()
            assert fut_a.result(timeout=5.0) == f"res-{id(pack_a)}"
            assert batcher.batches_executed == 2
        finally:
            release_slow.set()
            batcher.close()

    def test_same_pack_queries_coalesce(self, monkeypatch):
        """Deterministic (no wall-clock reliance): the first launch is
        held open until all four queries are queued, so the remainder
        MUST share the second launch."""
        import threading

        from elasticsearch_tpu.search import tpu_service as svc_mod

        batcher = svc_mod.MicroBatcher(window_s=0.0, max_batch=8)
        calls = []
        release = threading.Event()
        all_submitted = threading.Event()

        def fake_launch(resident, flats, k, mesh=None, stages=None,
                        max_batch=128):
            if not calls:  # hold the FIRST launch open
                calls.append(len(flats))
                assert release.wait(timeout=10.0)
            else:
                assert all_submitted.is_set()
                calls.append(len(flats))
            return {"results": ["r"] * len(flats)}

        monkeypatch.setattr(svc_mod, "launch_flat_batch", fake_launch)
        monkeypatch.setattr(svc_mod, "finish_flat_batch",
                            lambda st: st["results"])
        pack = object()
        try:
            futs = [batcher.submit(pack, flat=i, k=1) for i in range(4)]
            all_submitted.set()
            release.set()
            for f in futs:
                f.result(timeout=5.0)
            assert sum(calls) == 4
            assert batcher.queries_executed == 4
            # whatever didn't make launch 1 coalesced into launch 2
            assert batcher.batches_executed == len(calls) <= 2
        finally:
            release.set()
            batcher.close()


class TestFallback:
    def test_unsupported_shapes_use_planner(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            out = coordinator.search(
                svc, "corpus",
                {"query": {"match_phrase": {"body": "alpha beta"}}},
                tpu_search=tpu)
            assert tpu.served == 0 and tpu.fallback > 0
            assert "hits" in out
            # aggs force the planner path
            out = coordinator.search(
                svc, "corpus",
                {"query": {"match": {"body": "alpha"}},
                 "aggs": {"tags": {"terms": {"field": "tag"}}}},
                tpu_search=tpu)
            assert tpu.served == 0
            assert "aggregations" in out
        finally:
            tpu.close()

    def test_pack_rebuilds_after_refresh(self, svc, seeded_np):
        idx = make_corpus(svc, seeded_np, docs=40)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            r1 = tpu.packs.get(idx, "body")
            r2 = tpu.packs.get(idx, "body")
            assert r1 is r2  # cached while reader unchanged
            shard = idx.shard(idx.shard_for_id("new-doc"))
            shard.apply_index_on_primary("new-doc", {"body": "alpha omega"})
            idx.refresh()
            r3 = tpu.packs.get(idx, "body")
            assert r3 is not r1
        finally:
            tpu.close()


class TestMicroBatching:
    def test_concurrent_queries_coalesce(self, svc, seeded_np):
        make_corpus(svc, seeded_np, docs=60)
        tpu = TpuSearchService(window_s=0.05, max_batch=32)
        try:
            idx = svc.index("corpus")
            # prime the pack (build outside the timed window)
            tpu.packs.get(idx, "body")
            results = [None] * 8
            def run(i):
                results[i] = tpu.try_search(
                    idx, dsl.MatchQuery(field="body", query="alpha"), k=10)
            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(8)]
            [t.start() for t in threads]
            [t.join() for t in threads]
            assert all(r is not None for r in results)
            # all 8 queries ran in fewer launches than queries
            assert tpu.batcher.queries_executed == 8
            assert tpu.batcher.batches_executed < 8
            # identical queries → identical results
            for r in results[1:]:
                assert [h[4] for h in r.hits] == [h[4] for h in results[0].hits]
                assert r.total_hits == results[0].total_hits
        finally:
            tpu.close()


class TestReviewFindings:
    """Regression tests for the r2 code-review findings on this path."""

    def test_msm_above_term_count_matches_nothing(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        fast, slow = both_paths(
            svc, "corpus",
            {"query": {"match": {"body": {"query": "alpha beta",
                                          "minimum_should_match": 3}}},
             "size": 20})
        assert fast["hits"]["total"]["value"] == 0
        assert_equivalent(fast, slow)

    def test_bool_msm_multiterm_clause_falls_back(self, svc, seeded_np):
        """msm counts clauses; a multi-term match clause breaks the
        clause==term identity, so the planner must serve it."""
        make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            coordinator.search(
                svc, "corpus",
                {"query": {"bool": {
                    "should": [{"match": {"body": "alpha beta"}},
                               {"term": {"body": "gamma"}}],
                    "minimum_should_match": 2}}},
                tpu_search=tpu)
            assert tpu.served == 0 and tpu.fallback > 0
        finally:
            tpu.close()

    def test_bool_msm_single_term_clauses_equivalent(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        fast, slow = both_paths(
            svc, "corpus",
            {"query": {"bool": {
                "should": [{"term": {"body": "alpha"}},
                           {"term": {"body": "beta"}},
                           {"term": {"body": "gamma"}}],
                "minimum_should_match": 2}}, "size": 50})
        assert_equivalent(fast, slow)

    def test_delete_index_releases_pack(self, svc, seeded_np):
        from elasticsearch_tpu.common.breaker import CircuitBreaker
        idx = make_corpus(svc, seeded_np, name="todelete", docs=30)
        breaker = CircuitBreaker("hbm", 1 << 30)
        tpu = TpuSearchService(window_s=0.0, breaker=breaker)
        try:
            tpu.try_search(idx, dsl.MatchQuery(field="body", query="alpha"),
                           k=5)
            assert breaker.used > 0
            svc.delete_index("todelete")
            tpu.invalidate_index("todelete")
            assert breaker.used == 0
        finally:
            tpu.close()

    def test_submit_after_close_falls_back(self, svc, seeded_np):
        idx = make_corpus(svc, seeded_np, docs=20)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        tpu.close()
        import time as _t
        _t.sleep(0.05)
        res = tpu.try_search(idx, dsl.MatchQuery(field="body", query="alpha"),
                             k=5)
        assert res is None and tpu.fallback > 0

    def test_kernel_error_falls_back_not_500(self, svc, seeded_np,
                                             monkeypatch):
        """An accelerator bug degrades to the planner path, never to an
        error surfaced at the API (EnginePlugin seam contract)."""
        from elasticsearch_tpu.search import tpu_service
        make_corpus(svc, seeded_np, docs=30)
        monkeypatch.setattr(
            tpu_service, "launch_flat_batch",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            out = coordinator.search(
                svc, "corpus", {"query": {"match": {"body": "alpha"}}},
                tpu_search=tpu)
            assert tpu.served == 0 and tpu.fallback > 0
            assert "boom" in (tpu.last_error or "")
            assert out["hits"]["total"]["value"] >= 0  # planner served it
        finally:
            tpu.close()

    def test_timeout_trips_breaker_and_probes(self, svc, seeded_np,
                                              monkeypatch):
        """After a batch-wait timeout the kernel breaker routes queries to
        the planner immediately; one probe per cooldown re-tests the path."""
        from concurrent.futures import Future
        idx = make_corpus(svc, seeded_np, docs=20)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            q = dsl.MatchQuery(field="body", query="alpha")
            hung: Future = Future()  # never resolved → FuturesTimeout
            monkeypatch.setattr(tpu.batcher, "submit",
                                lambda *a, **k: hung)
            monkeypatch.setattr(
                "elasticsearch_tpu.search.tpu_service.FuturesTimeout",
                TimeoutError)
            orig_result = Future.result
            monkeypatch.setattr(
                Future, "result",
                lambda self, timeout=None: (_ for _ in ()).throw(
                    TimeoutError()) if self is hung
                else orig_result(self, timeout))
            assert tpu.try_search(idx, q, k=5) is None
            assert tpu.timeouts == 1 and tpu.stats()["tripped"]
            # within cooldown: immediate fallback, no submit
            calls = []
            monkeypatch.setattr(tpu.batcher, "submit",
                                lambda *a, **k: calls.append(1) or hung)
            assert tpu.try_search(idx, q, k=5) is None
            assert calls == []  # breaker short-circuited
            # after cooldown: one probe goes through
            tpu._next_probe = 0.0
            assert tpu.try_search(idx, q, k=5) is None
            assert calls == [1]
        finally:
            tpu.close()


class TestBlockMaxPruning:
    """Block-max/WAND-analog tests: force truncation with a tiny prefix
    cap and assert the pruned path returns the SAME top-k as the planner
    (validity bound + exact host re-score), with gte totals."""

    def _dense_corpus(self, svc, seeded_np, docs=400):
        """Corpus where one term is very common (big postings row)."""
        from elasticsearch_tpu.common.settings import Settings
        idx = svc.create_index(
            "dense", Settings.of({"index": {"number_of_shards": 2}}),
            {"properties": {"body": {"type": "text"}}})
        for i in range(docs):
            words = ["common"] * int(seeded_np.integers(1, 4))
            if i % 3 == 0:
                words += ["rare"] * int(seeded_np.integers(1, 3))
            words += [WORDS[int(w)] for w in
                      seeded_np.integers(0, 6, 4)]
            shard = idx.shard(idx.shard_for_id(f"d{i}"))
            shard.apply_index_on_primary(f"d{i}", {"body": " ".join(words)})
        idx.refresh()
        return idx

    @pytest.mark.parametrize("cap", [64, 128])
    def test_truncated_equivalence(self, svc, seeded_np, cap, monkeypatch):
        from elasticsearch_tpu.search import tpu_service
        self._dense_corpus(svc, seeded_np)
        monkeypatch.setattr(tpu_service, "PREFIX_CAP", cap)
        body = {"query": {"match": {"body": "common rare"}}, "size": 20}
        tpu = tpu_service.TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            fast = coordinator.search(svc, "dense", dict(body),
                                      tpu_search=tpu)
            assert tpu.served > 0
        finally:
            tpu.close()
        slow = coordinator.search(svc, "dense", dict(body), tpu_search=None)
        # hits must be identical even though postings were truncated
        assert ([h["_id"] for h in fast["hits"]["hits"]]
                == [h["_id"] for h in slow["hits"]["hits"]])
        for a, b in zip(fast["hits"]["hits"], slow["hits"]["hits"]):
            assert a["_score"] == pytest.approx(b["_score"], rel=1e-5)
        # totals: pruned mode reports a lower bound with gte
        assert fast["hits"]["total"]["relation"] in ("eq", "gte")
        assert (fast["hits"]["total"]["value"]
                <= slow["hits"]["total"]["value"])

    def test_validity_failure_falls_back_exact(self, svc, seeded_np,
                                               monkeypatch):
        """A cap so small the bound can't hold → exact rerun, correct
        results, relation eq."""
        from elasticsearch_tpu.search import tpu_service
        self._dense_corpus(svc, seeded_np)
        monkeypatch.setattr(tpu_service, "PREFIX_CAP", 1)
        body = {"query": {"match": {"body": "common"}}, "size": 300}
        tpu = tpu_service.TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            fast = coordinator.search(svc, "dense", dict(body),
                                      tpu_search=tpu)
        finally:
            tpu.close()
        slow = coordinator.search(svc, "dense", dict(body), tpu_search=None)
        assert ([h["_id"] for h in fast["hits"]["hits"]]
                == [h["_id"] for h in slow["hits"]["hits"]])
        assert (fast["hits"]["total"]["value"]
                == slow["hits"]["total"]["value"])

    def test_impact_sorted_layout(self, svc, seeded_np):
        from elasticsearch_tpu.parallel import distributed as dist
        idx = self._dense_corpus(svc, seeded_np, docs=100)
        from elasticsearch_tpu.search.tpu_service import TpuSearchService
        # the impact-sorted copy only exists in the RAW resident format
        # (compressed packs route everything to the exact kernel)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                               compressed_pack=False)
        try:
            resident = tpu.packs.get(idx, "body")
            pack = resident.pack
            imp_docs, imp_impacts = resident.imp_host
            for si in range(pack.num_shards):
                rstart = pack.row_starts[si]
                vocab = pack.vocabs[si]
                for term, r in vocab.items():
                    a, b = int(rstart[r]), int(rstart[r + 1])
                    seg = imp_impacts[si, a:b]
                    assert (np.diff(seg) <= 1e-7).all(), \
                        f"impacts not descending for {term}"
                    # same multiset of (doc, impact) as the doc-sorted copy
                    assert sorted(imp_docs[si, a:b].tolist()) == \
                        pack.flat_docs[si, a:b].tolist()
        finally:
            tpu.close()
            from elasticsearch_tpu.search.tpu_service import KERNEL_CONFIG
            KERNEL_CONFIG["compressed_pack"] = True


def test_grouped_phase_a_many_segments(svc, seeded_np):
    """> FUSE_ROWS segment rows exercise the lax.map-grouped phase A
    (HBM-bounded fusion at MS-MARCO scale); results stay exact."""
    idx = svc.create_index(
        "grouped", Settings.of({"index": {"number_of_shards": 1}}),
        {"properties": {"body": {"type": "text"}}})
    for i in range(120):
        n_words = int(seeded_np.integers(3, 10))
        words = [WORDS[int(w)] for w in
                 seeded_np.integers(0, len(WORDS), n_words)]
        shard = idx.shard(idx.shard_for_id(f"d{i}"))
        shard.apply_index_on_primary(f"d{i}", {"body": " ".join(words)})
        if i % 11 == 10:
            idx.flush()  # many small segments → many pack rows
    idx.refresh()
    reader = idx.shard(0).acquire_searcher()
    assert len(reader.views) > 8, "fixture must exceed FUSE_ROWS"
    fast, slow = both_paths(
        svc, "grouped",
        {"query": {"match": {"body": "alpha beta"}}, "size": 40})
    assert_equivalent(fast, slow)


class TestKernelVariant:
    """The packed-sort knob: lowering-time variant choice, the
    runtime toggle, and the stats surface."""

    def test_choose_kernel_variant_gates(self):
        from elasticsearch_tpu.ops.sparse import PACKED_DOC_LIMIT
        from elasticsearch_tpu.search.planner import choose_kernel_variant
        ok_w = np.array([0.5, 2.0], dtype=np.float32)
        assert choose_kernel_variant(1000, ok_w) == "packed"
        # doc ids past the 16-bit field → exact-f32 fallback
        assert choose_kernel_variant(PACKED_DOC_LIMIT, ok_w) == "ref"
        # hostile weights → fallback (negative / non-finite / huge)
        assert choose_kernel_variant(1000, np.array([-1.0])) == "ref"
        assert choose_kernel_variant(1000, np.array([np.inf])) == "ref"
        assert choose_kernel_variant(1000, np.array([1e31])) == "ref"
        # setting off → fallback regardless of packability
        assert choose_kernel_variant(1000, ok_w, enabled=False) == "ref"

    def test_choose_kernel_variant_compressed(self):
        from elasticsearch_tpu.search.planner import choose_kernel_variant
        ok_w = np.array([0.5, 2.0], dtype=np.float32)
        # compressed pack: packable weights → quantized-sort variant,
        # hostile weights → decode-everything exact variant (no "ref" —
        # a compressed pack has no raw f32 image to fall back to)
        assert choose_kernel_variant(1000, ok_w,
                                     compressed=True) == "compressed"
        assert choose_kernel_variant(
            1000, np.array([1e31]), compressed=True) == "compressed_exact"
        assert choose_kernel_variant(
            1000, np.array([-1.0]), compressed=True) == "compressed_exact"

    @staticmethod
    def _moved(before, after, variant):
        """Launch-counter keys ("kernel,variant") that incremented."""
        return [key for key, n in after.items()
                if key.split(",")[1] == variant
                and n > before.get(key, 0)]

    def test_variant_selected_counted_and_equivalent(self, svc,
                                                     seeded_np):
        """Packed on → packed launches; toggled off at runtime → ref
        launches; both bit-compatible with the planner path."""
        from elasticsearch_tpu.search import tpu_service as svc_mod
        make_corpus(svc, seeded_np)
        body = {"query": {"match": {"body": {
                    "query": "alpha beta gamma",
                    "minimum_should_match": 2}}},
                "size": 20, "_source": False}
        slow = coordinator.search(svc, "corpus", dict(body),
                                  tpu_search=None)
        # packed/ref are only reachable from the RAW resident format
        # (compressed packs serve the compressed variant pair)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                               packed_sort=True, compressed_pack=False)
        try:
            for expect in ("packed", "ref"):
                before = dict(svc_mod.KERNEL_VARIANT_COUNTS.counts())
                fast = coordinator.search(svc, "corpus", dict(body),
                                          tpu_search=tpu)
                assert tpu.served > 0
                assert_equivalent(fast, slow)
                stats = tpu.stats()
                assert stats["kernel"]["packed_sort"] is \
                    (expect == "packed")
                after = stats["kernel"]["variants"]
                assert self._moved(before, after, expect), \
                    (expect, before, after)
                other = "ref" if expect == "packed" else "packed"
                assert not self._moved(before, after, other), \
                    (expect, before, after)
                tpu.set_kernel_packed_sort(False)
                assert tpu.kernel_packed_sort is False
        finally:
            tpu.close()
            # the knobs are process-global (jit cache + prewarm are too):
            # restore the defaults for the rest of the suite
            svc_mod.KERNEL_CONFIG["packed_sort"] = True
            svc_mod.KERNEL_CONFIG["compressed_pack"] = True
