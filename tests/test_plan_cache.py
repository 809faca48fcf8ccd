"""Lowered-plan cache coherence: repeated query shapes skip re-lowering,
and every invalidation seam (mapping update, pack rebuild mid-traffic,
index delete) evicts or revalidates the cached plan — a FlatQuery must
never run against a resident pack it wasn't validated on."""

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.indices.service import IndicesService
from elasticsearch_tpu.search import coordinator, dsl
from elasticsearch_tpu.search import tpu_service as svc_mod
from elasticsearch_tpu.search.tpu_service import (NOT_LOWERABLE, PlanCache,
                                                  TpuSearchService,
                                                  plan_key)

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa", "lamda", "mu"]


@pytest.fixture
def svc(tmp_path):
    s = IndicesService(str(tmp_path))
    yield s
    s.close()


def make_corpus(svc, seeded_np, *, name="corpus", shards=2, docs=80):
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
    idx.refresh()
    return idx


BODY = {"query": {"match": {"body": "alpha beta"}}, "size": 10,
        "_source": False}


class TestPlanKey:
    def test_equal_bodies_equal_keys(self):
        a = plan_key(dsl.MatchQuery(field="body", query="x y"))
        b = plan_key(dsl.MatchQuery(field="body", query="x y"))
        assert a == b and hash(a) == hash(b)

    def test_different_bodies_differ(self):
        a = plan_key(dsl.MatchQuery(field="body", query="x"))
        b = plan_key(dsl.MatchQuery(field="body", query="y"))
        c = plan_key(dsl.TermQuery(field="body", value="x"))
        assert a != b and a != c

    def test_nested_trees(self):
        q = dsl.BoolQuery(should=[dsl.TermQuery(field="body", value="a"),
                                  dsl.TermQuery(field="body", value="b")])
        q2 = dsl.BoolQuery(should=[dsl.TermQuery(field="body", value="a"),
                                   dsl.TermQuery(field="body", value="b")])
        assert plan_key(q) == plan_key(q2)

    def test_unhashable_payload_uncacheable(self):
        q = dsl.TermsQuery(field="body", values=[{"nested": set()}])
        assert plan_key(q) is None


class TestPlanCacheLru:
    def test_lru_bound_and_counters(self):
        pc = PlanCache(max_entries=4)
        for i in range(10):
            pc.put(("i", 0, i), i)
        assert len(pc) == 4
        s = pc.stats()
        assert s["evictions"] == 6 and s["size"] == 4
        assert pc.get(("i", 0, 9)) == 9
        assert pc.get(("i", 0, 0)) is None  # evicted
        s = pc.stats()
        assert s["hits"] == 1 and s["misses"] == 1

    def test_invalidate_index_only_touches_that_index(self):
        pc = PlanCache()
        pc.put(("a", 0, 1), 1)
        pc.put(("b", 0, 1), 2)
        pc.invalidate_index("a")
        assert pc.get(("a", 0, 1)) is None
        assert pc.get(("b", 0, 1)) == 2


class TestServingCacheCoherence:
    def test_repeat_query_hits_cache(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            r1 = coordinator.search(svc, "corpus", dict(BODY),
                                    tpu_search=tpu)
            misses_after_first = tpu.plans.stats()["misses"]
            r2 = coordinator.search(svc, "corpus", dict(BODY),
                                    tpu_search=tpu)
            st = tpu.plans.stats()
            assert st["hits"] >= 1
            assert st["misses"] == misses_after_first  # no re-lowering
            assert [h["_id"] for h in r1["hits"]["hits"]] == \
                   [h["_id"] for h in r2["hits"]["hits"]]
            assert tpu.served >= 2
        finally:
            tpu.close()

    def test_cached_repeat_is_a_hit_that_lowers_nothing(self, svc,
                                                        seeded_np,
                                                        monkeypatch):
        """What the plan cache and the slot memo save is counted, not
        timed: the repeat of a shape is one hit, no miss, no call of
        `lower_query`, and `_slots_needed` answers from the memo
        without walking a vocab."""
        idx = make_corpus(svc, seeded_np, docs=300)
        lowered = []
        real_lower = svc_mod.lower_query

        def counting_lower(q, mapper):
            lowered.append(q)
            return real_lower(q, mapper)

        monkeypatch.setattr(svc_mod, "lower_query", counting_lower)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            first = dict(tpu.plans.stats())
            n_lowered = len(lowered)
            assert first["misses"] == 1 and n_lowered >= 1
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            repeat = tpu.plans.stats()
            assert repeat["hits"] == first["hits"] + 1
            assert repeat["misses"] == first["misses"]
            assert len(lowered) == n_lowered
            assert tpu.stats()["stages"]["lower"]["count"] == 2

            resident = tpu.packs.get(idx, "body")
            key = ("corpus", idx.mapper.generation,
                   plan_key(dsl.MatchQuery(field="body",
                                           query="alpha beta")))
            flat, rk = tpu.plans.get(key)
            assert rk == resident.reader_key
            slots = svc_mod._slots_needed(resident, flat)
            memo_key = tuple(flat.terms)
            assert resident.slots_memo[memo_key] == slots
            # the memo is what answers a repeat: a marked entry comes
            # back as it was put
            resident.slots_memo[memo_key] = slots + 7
            assert svc_mod._slots_needed(resident, flat) == slots + 7
        finally:
            tpu.close()

    def test_mapping_update_changes_generation_key(self, svc, seeded_np):
        idx = make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            gen0 = idx.mapper.generation
            size0 = len(tpu.plans)
            assert size0 >= 1
            idx.mapper.merge(
                {"properties": {"extra": {"type": "keyword"}}})
            assert idx.mapper.generation == gen0 + 1
            # the REST seam also purges the now-unreachable entries
            tpu.invalidate_plans("corpus")
            assert len(tpu.plans) == 0
            # re-search lowers fresh under the new generation and serves
            misses0 = tpu.plans.stats()["misses"]
            r = coordinator.search(svc, "corpus", dict(BODY),
                                   tpu_search=tpu)
            assert tpu.plans.stats()["misses"] > misses0
            assert r["hits"]["total"]["value"] >= 0
        finally:
            tpu.close()

    def test_pack_rebuild_revalidates_entry(self, svc, seeded_np):
        idx = make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            resident0 = tpu.packs.get(idx, "body")
            # a write + refresh swaps the shard readers → next lookup
            # rebuilds the pack; the cached plan must be revalidated
            # against the NEW pack, and the new doc must be visible
            shard = idx.shard(idx.shard_for_id("fresh"))
            shard.apply_index_on_primary(
                "fresh", {"body": "alpha alpha alpha alpha alpha beta"})
            idx.refresh()
            fast = coordinator.search(svc, "corpus", dict(BODY),
                                      tpu_search=tpu)
            resident1 = tpu.packs.get(idx, "body")
            assert resident1 is not resident0
            assert resident1.reader_key != resident0.reader_key
            ids = [h["_id"] for h in fast["hits"]["hits"]]
            assert "fresh" in ids
            # and the kernel path still agrees with the planner path
            slow = coordinator.search(svc, "corpus", dict(BODY),
                                      tpu_search=None)
            assert ids == [h["_id"] for h in slow["hits"]["hits"]]
        finally:
            tpu.close()

    def test_index_delete_evicts_plans_and_packs(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            assert len(tpu.plans) >= 1
            tpu.invalidate_index("corpus")
            assert len(tpu.plans) == 0
            assert tpu.packs.stats()["resident"] == 0
        finally:
            tpu.close()

    def test_not_lowerable_is_cached(self, svc, seeded_np):
        idx = make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            phrase = dsl.MatchPhraseQuery(field="body",
                                          query="alpha beta")
            assert tpu.try_search(idx, phrase, k=10) is None
            assert tpu.try_search(idx, phrase, k=10) is None
            st = tpu.plans.stats()
            assert st["hits"] >= 1  # second probe hit the negative entry
            assert tpu.fallback == 2
            key = ("corpus", idx.mapper.generation, plan_key(phrase))
            assert tpu.plans.get(key) is NOT_LOWERABLE
        finally:
            tpu.close()

    def test_kernel_error_still_retried_with_cached_plan(
            self, svc, seeded_np, monkeypatch):
        """The plan cache memoizes LOWERING, not kernel outcomes: a
        kernel failure must not be replayed from cache — the next
        identical query attempts the kernel path again."""
        idx = make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)

        def boom(resident, flats, k, mesh=None, stages=None,
                 max_batch=128):
            raise RuntimeError("injected kernel failure")

        monkeypatch.setattr(svc_mod, "launch_flat_batch", boom)
        try:
            q = dsl.MatchQuery(field="body", query="alpha")
            assert tpu.try_search(idx, q, k=10) is None
            assert tpu.try_search(idx, q, k=10) is None
            assert tpu.fallback == 2 and tpu.served == 0
            assert tpu.plans.stats()["hits"] >= 1
            assert "injected kernel failure" in (tpu.last_error or "")
        finally:
            tpu.close()


class TestColdStartGrace:
    def test_warming_declines_to_planner(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            tpu._warming = True
            r = coordinator.search(svc, "corpus", dict(BODY),
                                   tpu_search=tpu)
            assert tpu.served == 0 and tpu.fallback >= 1
            assert r["hits"]["total"]["value"] >= 0  # planner answered
            tpu._warming = False
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            assert tpu.served >= 1
        finally:
            tpu.close()

    def test_prewarm_dedupes_and_reports_progress(self, svc, seeded_np,
                                                  monkeypatch):
        idx = make_corpus(svc, seeded_np)
        monkeypatch.setattr(svc_mod, "_execute_pruned",
                            lambda *a, **kw: ([], []))
        monkeypatch.setattr(svc_mod, "_execute_exact",
                            lambda *a, **kw: [])
        # raw-format pack on purpose: this test pins the round-8 warm
        # table (packed + ref, pruned-path signatures included); the
        # compressed default routes everything to the exact variants
        # and has no pruned tier to warm
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                               compressed_pack=False)
        try:
            warm = tpu.prewarm(idx, "body", concurrency=3)
            assert not tpu._warming  # cleared even on the happy path
            prog = tpu.stats()["prewarm"]
            assert prog["state"] == "done"
            assert prog["done"] == prog["total"] == len(warm["compiled"])
            # deduped: every warmed entry maps to a distinct canonical
            # jit signature (the kernel variant is part of the signature
            # since round 8 — packed and ref compile separately)
            sigs = []
            for e in warm["compiled"]:
                if e.get("exact"):
                    sigs.append((e["batch"], "exact",
                                 svc_mod._candidate_k(e["k"]),
                                 e.get("variant")))
                else:
                    sigs.append((e["batch"], svc_mod._candidate_k(e["k"]),
                                 e["slots"], e["prefix"],
                                 e.get("variant")))
            assert len(sigs) == len(set(sigs))
            # with packed_sort on (the default) the small corpus is
            # packable, so both variants appear in the warm table
            assert {e.get("variant") for e in warm["compiled"]} == \
                {"packed", "ref"}
            assert not any(e.get("error") for e in warm["compiled"])
        finally:
            tpu.close()
            # the knob is process-global; restore the default for the
            # rest of the suite
            svc_mod.KERNEL_CONFIG["compressed_pack"] = True

    def test_prewarm_async_sets_done_state(self, svc, seeded_np,
                                           monkeypatch):
        idx = make_corpus(svc, seeded_np)
        monkeypatch.setattr(svc_mod, "_execute_pruned",
                            lambda *a, **kw: ([], []))
        monkeypatch.setattr(svc_mod, "_execute_exact",
                            lambda *a, **kw: [])
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            t = tpu.prewarm_async(idx, "body")
            t.join(timeout=60)
            assert not t.is_alive()
            assert tpu.stats()["prewarm"]["state"] == "done"
        finally:
            tpu.close()


class TestStatsExposure:
    def test_service_stats_shape(self, svc, seeded_np):
        make_corpus(svc, seeded_np)
        tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
        try:
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            coordinator.search(svc, "corpus", dict(BODY), tpu_search=tpu)
            st = tpu.stats()
            assert st["plan_cache"]["hits"] >= 1
            assert st["pack_cache"]["resident"] == 1
            assert st["prewarm"]["state"] == "idle"
            lower = st["stages"]["lower"]
            assert {"seconds", "count", "p50_ms", "p95_ms",
                    "p99_ms"} <= set(lower)
        finally:
            tpu.close()

    def test_rest_tpu_stats_endpoint(self, tmp_path):
        from elasticsearch_tpu.node import Node
        node = Node(str(tmp_path / "n0"), settings=Settings.EMPTY)
        try:
            status, body = node.handle("GET", "/_tpu/stats", {}, None)
            assert status == 200
            assert body["enabled"] is True
            assert "plan_cache" in body and "pack_cache" in body
            assert "prewarm" in body and "stages" in body
            # serializes cleanly through the REST layer
            import json
            json.dumps(body)
        finally:
            node.close()
