"""Streaming delta-pack path (ISSUE 20 tentpole): an append-only
refresh rides as a small device-resident delta pack chained on the base
instead of a full re-residency; search unions base + deltas as extra
operands; a compactor folds the chain back into the compressed base.

Covered here: chain eligibility (appends chain, tombstones force a full
rebuild), exact HBM breaker accounting across append/compact/evict (the
PR 8/10 drains-to-exactly-zero invariant extended to deltas), synchronous
compaction correctness against a delta-disabled full build, deterministic
bit-identity between two independently built chains, and the delta
lifecycle flight-recorder events. The chaos tier lives in
test_chaos_streaming.py.
"""

import numpy as np
import pytest

from elasticsearch_tpu.common import events as events_mod
from elasticsearch_tpu.common.breaker import CircuitBreaker
from elasticsearch_tpu.common.events import FlightRecorder
from elasticsearch_tpu.search import coordinator, dsl
from elasticsearch_tpu.search.tpu_service import (COMPACTION_FAULT_HOOKS,
                                                  TpuSearchService)

from test_tpu_serving import make_corpus, svc  # noqa: F401 (fixture)

pytestmark = pytest.mark.streaming


def _tpu(breaker=None, **delta_kw):
    delta = {"enabled": True}
    delta.update(delta_kw)
    return TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                            breaker=breaker, delta=delta)


def _append(idx, lo, hi, text="alpha sigma"):
    for i in range(lo, hi):
        doc_id = f"s{i}"
        shard = idx.shard(idx.shard_for_id(doc_id))
        shard.apply_index_on_primary(doc_id, {"body": text, "tag": "t9"})


def _ids(result):
    return [h[4] for h in result.hits]


def test_append_only_refresh_rides_a_delta(svc, seeded_np):  # noqa: F811
    idx = make_corpus(svc, seeded_np, name="dp", docs=60)
    tpu = _tpu(breaker=CircuitBreaker("hbm", 1 << 30))
    try:
        q = dsl.MatchQuery(field="body", query="alpha sigma")
        r0 = tpu.try_search(idx, q, k=100)
        assert r0 is not None and tpu.packs.misses == 1

        _append(idx, 0, 25)
        idx.refresh()
        r1 = tpu.try_search(idx, q, k=100)
        assert r1 is not None
        # no full rebuild happened — the refresh rode a delta
        assert tpu.packs.misses == 1
        assert tpu.delta_stats.appends == 1
        st = tpu.stats()["deltas"]
        assert st["packs"] == 1 and st["bytes"] > 0
        # the appended docs are actually searchable through the union
        assert r1.total_hits > r0.total_hits
        got = set(_ids(r1))
        assert {f"s{i}" for i in range(25)} <= got
        # totals agree with the planner (set-level equivalence; scores
        # bake per-(pack, shard) stats — see README Freshness section)
        slow = coordinator.search(
            svc, "dp", {"query": {"match": {"body": "alpha sigma"}},
                        "size": 100}, tpu_search=None)
        assert r1.total_hits == slow["hits"]["total"]["value"]
    finally:
        tpu.close()


def test_a_teardown_under_a_delta_build_orphans_the_delta(
        svc, seeded_np, monkeypatch):  # noqa: F811
    """The supervisor's teardown takes no build lock: where it drops the
    base while a reader builds a delta on it, the reader is answered
    from a full rebuild, and the breaker holds that base and no more."""
    idx = make_corpus(svc, seeded_np, name="dp1b", docs=60)
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = _tpu(breaker=breaker)
    try:
        q = dsl.MatchQuery(field="body", query="alpha sigma")
        assert tpu.try_search(idx, q, k=100) is not None
        _append(idx, 0, 25)
        idx.refresh()
        build_delta = tpu.packs._build_delta

        def teardown_then_build(*args):
            assert tpu.packs.invalidate_all() == [("dp1b", "body")]
            assert breaker.used == 0
            return build_delta(*args)

        monkeypatch.setattr(tpu.packs, "_build_delta", teardown_then_build)
        r1 = tpu.try_search(idx, q, k=100)
        assert r1 is not None
        assert {f"s{i}" for i in range(25)} <= set(_ids(r1))
        assert tpu.packs.misses == 2 and tpu.delta_stats.appends == 0
        st = tpu.stats()
        assert st["deltas"]["packs"] == 0
        resident = tpu.packs.peek(("dp1b", "body"))
        assert breaker.used == resident.hbm_bytes > 0
    finally:
        tpu.close()


def test_tombstones_force_full_rebuild(svc, seeded_np):  # noqa: F811
    idx = make_corpus(svc, seeded_np, name="dp2", docs=40)
    tpu = _tpu()
    try:
        q = dsl.MatchQuery(field="body", query="alpha")
        assert tpu.try_search(idx, q, k=10) is not None
        assert tpu.packs.misses == 1
        # a delete mutates committed live masks → live_version bumps →
        # the chain is ineligible and the image fully rebuilds
        shard = idx.shard(idx.shard_for_id("d0"))
        shard.apply_delete_on_primary("d0")
        idx.refresh()
        assert tpu.try_search(idx, q, k=10) is not None
        assert tpu.packs.misses == 2
        assert tpu.delta_stats.appends == 0
        assert tpu.stats()["deltas"]["packs"] == 0
    finally:
        tpu.close()


def test_breaker_drains_to_exactly_zero_across_delta_lifecycle(
        svc, seeded_np):  # noqa: F811
    idx = make_corpus(svc, seeded_np, name="dp3", docs=50)
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = _tpu(breaker=breaker)
    try:
        q = dsl.MatchQuery(field="body", query="alpha sigma")
        assert tpu.try_search(idx, q, k=10) is not None
        base_bytes = breaker.used
        assert base_bytes > 0

        _append(idx, 0, 15)
        idx.refresh()
        assert tpu.try_search(idx, q, k=10) is not None
        st = tpu.stats()["deltas"]
        assert st["packs"] == 1
        # the delta's charge is exactly its own accounting of itself
        assert breaker.used == base_bytes + st["bytes"]

        # synchronous fold: old base + delta released exactly, only the
        # new base remains charged
        assert tpu.packs.compact(("dp3", "body")) is True
        st = tpu.stats()["deltas"]
        assert st["packs"] == 0 and st["bytes"] == 0
        assert st["compactions"] == 1
        detail = tpu.packs.stats()["packs"]["dp3/body"]
        assert breaker.used == detail["hbm_bytes"] > 0

        # evict: the drain must be exact, not merely "close"
        svc.delete_index("dp3")
        tpu.invalidate_index("dp3")
        assert breaker.used == 0
    finally:
        tpu.close()


def test_compaction_matches_delta_disabled_full_build(svc, seeded_np):  # noqa: F811
    """After a fold the chain is ONE pack over all segments with the
    same per-shard row groups a classic full build uses — so a folded
    image must be bit-identical to a delta-disabled service's."""
    idx = make_corpus(svc, seeded_np, name="dp4", docs=60)
    tpu = _tpu()
    ref = TpuSearchService(window_s=0.0, batch_timeout_s=300.0)
    try:
        q = dsl.MatchQuery(field="body", query="alpha sigma")
        assert tpu.try_search(idx, q, k=10) is not None
        _append(idx, 0, 20)
        idx.refresh()
        assert tpu.try_search(idx, q, k=10) is not None
        assert tpu.stats()["deltas"]["packs"] == 1
        assert tpu.packs.compact(("dp4", "body")) is True

        a = tpu.try_search(idx, q, k=50)
        b = ref.try_search(idx, q, k=50)
        assert a is not None and b is not None
        assert _ids(a) == _ids(b)
        np.testing.assert_array_equal(a.scores, b.scores)
        assert a.total_hits == b.total_hits
    finally:
        tpu.close()
        ref.close()


def test_chain_bit_identical_to_independent_rebuild(svc, seeded_np):  # noqa: F811
    """Two services driven through the SAME refresh history build their
    device images independently (separate builds, separate device
    arrays) yet must answer bit-identically — the full-rebuild oracle
    with a matching row-group partition (stats bake per (pack, shard)
    at build time, so the oracle must partition rows the same way)."""
    idx = make_corpus(svc, seeded_np, name="dp5", docs=60)
    a = _tpu()
    b = _tpu()
    try:
        q = dsl.MatchQuery(field="body", query="alpha sigma")
        for lo, hi in ((0, 0), (0, 18), (18, 40)):
            if hi > lo:
                _append(idx, lo, hi)
                idx.refresh()
            ra = a.try_search(idx, q, k=50)
            rb = b.try_search(idx, q, k=50)
            assert ra is not None and rb is not None
            assert _ids(ra) == _ids(rb)
            np.testing.assert_array_equal(ra.scores, rb.scores)
            assert ra.total_hits == rb.total_hits
        assert a.delta_stats.appends == b.delta_stats.appends == 2
    finally:
        a.close()
        b.close()


def test_compaction_failure_keeps_chain_serving(svc, seeded_np):  # noqa: F811
    idx = make_corpus(svc, seeded_np, name="dp6", docs=40)
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = _tpu(breaker=breaker)

    def boom(key):
        raise RuntimeError("injected compaction fault")

    rec = FlightRecorder(max_events=256, incident_settle_s=0.0)
    prev = events_mod.get_recorder()
    events_mod.set_recorder(rec)
    COMPACTION_FAULT_HOOKS.append(boom)
    try:
        q = dsl.MatchQuery(field="body", query="alpha sigma")
        assert tpu.try_search(idx, q, k=10) is not None
        _append(idx, 0, 10)
        idx.refresh()
        assert tpu.try_search(idx, q, k=10) is not None
        used_before = breaker.used
        assert tpu.packs.compact(("dp6", "body")) is False
        assert tpu.delta_stats.compaction_failures == 1
        # nothing charged or released by the failed fold; the chain
        # keeps serving (the appended docs are still in the results)
        assert breaker.used == used_before
        r = tpu.try_search(idx, q, k=50)
        assert r is not None and "s0" in _ids(r)
        # the incident trigger fired
        rec.flush_incidents()
        assert any(i["trigger"] == "compaction_failure"
                   for i in rec.list_incidents())
        # with the hook gone the fold succeeds
        COMPACTION_FAULT_HOOKS.remove(boom)
        assert tpu.packs.compact(("dp6", "body")) is True
        etypes = [e["type"] for e in rec.events()]
        for wanted in ("delta.append", "delta.seal", "compaction.begin",
                       "compaction.end"):
            assert wanted in etypes
    finally:
        if boom in COMPACTION_FAULT_HOOKS:
            COMPACTION_FAULT_HOOKS.remove(boom)
        events_mod.set_recorder(prev)
        tpu.close()


def test_delta_chain_renders_natively_from_the_concatenated_id_table(
        svc, seeded_np, monkeypatch):  # noqa: F811
    """A chain's view carries the packs' encoded ids concatenated, so a
    metadata-only response over base + delta is rendered by the native
    renderer, byte for byte what the Python path gives."""
    import json

    from elasticsearch_tpu.search import serializer
    monkeypatch.setattr(serializer, "_SPLICE_TRIED", False)
    monkeypatch.delenv("ES_TPU_NO_NATIVE_SPLICE", raising=False)
    if serializer._native_render() is None:
        pytest.skip("native renderer unavailable (no C toolchain)")
    idx = make_corpus(svc, seeded_np, name="dpr", docs=40)
    tpu = _tpu()
    body = {"query": {"match": {"body": "alpha sigma"}}, "size": 100,
            "_source": False}
    try:
        assert coordinator.search(svc, "dpr", dict(body), tpu_search=tpu)
        new_ids = ['s"0', "s\\1", "sé2"]
        for doc_id in new_ids:
            idx.shard(idx.shard_for_id(doc_id)).apply_index_on_primary(
                doc_id, {"body": "alpha sigma", "tag": "t9"})
        idx.refresh()
        resp = coordinator.search(svc, "dpr", dict(body), tpu_search=tpu)
        assert tpu.delta_stats.appends == 1
        view = resp["hits"]["hits"].resident
        assert len(view.packs) == 2 and view.id_json is not None
        assert len(view.id_json.offsets) == len(view.id_cat) + 1
        native0 = serializer.RENDER_COUNTS.counts()["native"]
        got = serializer.dumps_response_bytes(resp)
        assert serializer.RENDER_COUNTS.counts()["native"] == native0 + 1
        assert got == serializer.dumps_response(resp).encode("utf-8")
        assert set(new_ids) <= {h["_id"]
                                for h in json.loads(got)["hits"]["hits"]}
    finally:
        tpu.close()
