"""HBM breaker accounting across the resident-pack lifecycle.

The `hbm` breaker must end at EXACTLY zero after every pack is gone —
a single leaked charge compounds across refresh cycles until the
breaker trips on an empty device (the reference's breaker tests assert
the same drain-to-zero invariant for request/fielddata). Exercised for
both resident formats: the raw doc-sorted + impact-sorted image and
the compressed u16 streams (multi-array charge, so a partial release
would leave a nonzero remainder that this test catches).
"""

import threading

import pytest

from elasticsearch_tpu.common.breaker import CircuitBreaker
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.tpu_service import TpuSearchService

from test_tpu_serving import make_corpus, svc  # noqa: F401 (fixture)


@pytest.mark.parametrize("compressed", [False, True],
                         ids=["raw_pack", "compressed_pack"])
def test_hbm_drains_to_zero_across_pack_lifecycle(svc, seeded_np,  # noqa: F811
                                                  compressed):
    idx = make_corpus(svc, seeded_np, name="acct", docs=80)
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                           breaker=breaker, compressed_pack=compressed)
    try:
        q = dsl.MatchQuery(field="body", query="alpha beta")

        # -- build: exactly one pack charged, and the charge is the
        # pack's own accounting of itself
        assert breaker.used == 0
        assert tpu.try_search(idx, q, k=10) is not None
        detail = tpu.packs.stats()["packs"]["acct/body"]
        assert detail["compressed"] is compressed
        assert breaker.used == detail["hbm_bytes"] > 0
        if compressed:
            # the tentpole claim, at serving granularity: the streams
            # cost at most half the raw image they replace
            assert detail["hbm_bytes"] <= 0.5 * detail["raw_bytes"]

        # -- rebuild under concurrent search: a refresh swaps the
        # reader identity; racing searches either rebuild, wait, or
        # serve the stale pack — whatever interleaving happens, the
        # old charge must be released exactly once and only the new
        # pack may remain charged
        for i in range(80, 110):
            shard = idx.shard(idx.shard_for_id(f"d{i}"))
            shard.apply_index_on_primary(f"d{i}", {"body": "alpha gamma",
                                                   "tag": "t0"})
        idx.refresh()
        errs = []

        def hammer():
            try:
                for _ in range(3):
                    tpu.try_search(idx, q, k=10)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        detail2 = tpu.packs.stats()["packs"]["acct/body"]
        assert breaker.used == detail2["hbm_bytes"] > 0

        # -- evict: the drain must be exact, not merely "close"
        svc.delete_index("acct")
        tpu.invalidate_index("acct")
        assert tpu.packs.stats()["packs"] == {}
        assert breaker.used == 0
    finally:
        tpu.close()


def test_delta_doc_stream_bytes_and_drain(svc, seeded_np):  # noqa: F811
    """ISSUE 17 ("finish the bytes war"): on a delta-eligible corpus
    the resident doc stream drops to u8 deltas + u16 block bases and
    the per-posting resident cost lands at ≤ 6 bytes (docs8 1B +
    code16 2B + rank16 2B + amortized block/base/residual metadata).
    The multi-array charge (now one array more) must still drain to
    EXACTLY zero on eviction."""
    idx = make_corpus(svc, seeded_np, name="delta", docs=90)
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                           breaker=breaker, compressed_pack=True)
    try:
        q = dsl.MatchQuery(field="body", query="alpha beta")
        assert tpu.try_search(idx, q, k=10) is not None
        detail = tpu.packs.stats()["packs"]["delta/body"]
        assert detail["compressed"] is True
        # small doc axis → every 128-lane block spans ≤ 255 doc ids →
        # the builder must have picked the delta format
        assert detail["doc_delta"] is True
        assert detail["doc_base_bytes"] > 0
        assert detail["postings"] > 0
        # the gauge is honest about slack: total resident bytes (incl.
        # the CHUNK_CAP sentinel tail, which dwarfs a 90-doc corpus)
        # over real postings — the ≤6 B/posting acceptance is asserted
        # at serving scale in test_delta_bytes_per_posting_at_scale
        assert detail["hbm_bytes_per_posting"] == pytest.approx(
            detail["hbm_bytes"] / detail["postings"])
        assert breaker.used == detail["hbm_bytes"] > 0
        # delta results must be the same bits the raw pack serves
        raw = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                               compressed_pack=False)
        try:
            a = tpu.try_search(idx, q, k=10)
            b = raw.try_search(idx, q, k=10)
            import numpy as np
            np.testing.assert_array_equal(
                a.scores.view(np.uint32), b.scores.view(np.uint32))
            np.testing.assert_array_equal(a.rows, b.rows)
            np.testing.assert_array_equal(a.ords, b.ords)
            assert a.total_hits == b.total_hits
        finally:
            raw.close()
            # the knob is process-global; the raw service flipped it
            from elasticsearch_tpu.search.tpu_service import KERNEL_CONFIG
            KERNEL_CONFIG["compressed_pack"] = True

        svc.delete_index("delta")
        tpu.invalidate_index("delta")
        assert tpu.packs.stats()["packs"] == {}
        assert breaker.used == 0
    finally:
        tpu.close()


def test_delta_bytes_per_posting_at_scale():
    """The bytes-war acceptance number, at a size where the CHUNK_CAP
    slack amortizes: a serving-scale delta-eligible pack must place at
    ≤ 6 B/posting (u8 deltas 1 + code16 2 + rank16 2 + amortized
    block-max/base/residual metadata), where the plain u16 doc stream
    sits above 6. nbytes_device is exactly what hbm_detail divides, so
    this pins hbm_bytes_per_posting at scale without a slow corpus."""
    import numpy as np
    from elasticsearch_tpu.parallel import distributed as dist

    # df is a COMPRESSED_BLOCK multiple so no 128-lane block straddles
    # a term boundary (a straddler would span doc 3967 → doc 0)
    n_terms, df, d_pad, slack = 10, 3968, 4096, 4352
    postings = n_terms * df
    p_pad = postings + slack
    flat_docs = np.full((1, p_pad), d_pad, dtype=np.int32)
    flat_imp = np.zeros((1, p_pad), dtype=np.float32)
    rng = np.random.default_rng(7)
    for t in range(n_terms):
        # consecutive doc ids: every 128-lane block spans ≤ 127 → delta
        # eligible; quantized impacts keep the residual tables realistic
        flat_docs[0, t * df:(t + 1) * df] = np.arange(df, dtype=np.int32)
        flat_imp[0, t * df:(t + 1) * df] = (
            rng.integers(1, 65, size=df).astype(np.float32) / 64.0)
    row_starts = [np.arange(0, postings + 1, df, dtype=np.int64)]
    pack = dist.StackedShardPack(
        field="body", num_shards=1, d_pad=d_pad, p_pad=p_pad,
        flat_docs=flat_docs, flat_impact=flat_imp,
        flat_tfs=np.zeros_like(flat_imp), live=np.ones((1, d_pad), bool),
        vocabs=[{}], row_starts=row_starts, shard_num_docs=[d_pad],
        shard_doc_ids=[[]], total_doc_count=d_pad, avgdl=8.0, df={})

    assert dist.delta_pack_reason(pack) is None
    streams = dist.build_compressed_streams(pack)
    assert streams.delta
    assert streams.nbytes_device() / postings <= 6.0
    plain = dist.build_compressed_streams(pack, delta=False)
    assert not plain.delta
    assert plain.nbytes_device() / postings > 6.0
    assert streams.nbytes_device() < plain.nbytes_device()


def test_build_failure_refunds_charge(svc, seeded_np,  # noqa: F811
                                      monkeypatch):
    """A device_put that dies mid-build must refund the whole charge —
    the compressed path places several arrays, so the refund has to be
    the single pre-computed total, not a per-array unwind."""
    from elasticsearch_tpu.parallel import distributed as dist

    idx = make_corpus(svc, seeded_np, name="boom", docs=40)
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                           breaker=breaker, compressed_pack=True)
    try:
        monkeypatch.setattr(
            dist, "device_put_compressed",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("hbm oom")))
        q = dsl.MatchQuery(field="body", query="alpha")
        with pytest.raises(RuntimeError, match="hbm oom"):
            tpu.try_search(idx, q, k=5)
        assert breaker.used == 0
        # and the cache recovers once placement works again: exactly
        # one fresh charge, no residue from the failed attempt
        monkeypatch.undo()
        assert tpu.try_search(idx, q, k=5) is not None
        detail = tpu.packs.stats()["packs"]["boom/body"]
        assert breaker.used == detail["hbm_bytes"] > 0
    finally:
        tpu.close()


def test_a_head_block_is_charged_with_its_pack_and_drains(
        svc, seeded_np, monkeypatch):  # noqa: F811
    """A pack's head block (the heads route: `dist.head_terms`, built by
    the first query that holds a head term) is charged to the `hbm`
    breaker beside the pack's own bytes, shown as the pack's
    `heads_block`, and released with the pack: the drain is still exact.
    A toy shard row holds too few docs for a head term at the
    deployment's HEAD_MIN_SLOTS, so the test lowers it to one slot."""
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.parallel import distributed as dist
    from elasticsearch_tpu.search.tpu_service import KERNEL_CONFIG

    monkeypatch.setattr(dist, "HEAD_MIN_SLOTS", 1)
    idx = svc.create_index(
        "heads", Settings.of({"index": {"number_of_shards": 1}}),
        {"properties": {"body": {"type": "text"}}})
    shard = idx.shard(0)
    others = ["beta", "gamma", "delta", "epsilon", "zeta", "eta"]
    for i in range(4500):  # "alpha" in every doc: 4,500 postings, 2 slots
        words = ["alpha"] + [others[int(w)] for w in
                             seeded_np.integers(0, len(others), 2)]
        shard.apply_index_on_primary(f"d{i}", {"body": " ".join(words)})
    idx.refresh()
    breaker = CircuitBreaker("hbm", 1 << 30)
    tpu = TpuSearchService(window_s=0.0, batch_timeout_s=300.0,
                           breaker=breaker, compressed_pack=False)
    try:
        assert tpu.try_search(idx, dsl.MatchQuery(field="body", query="beta"),
                              k=10) is not None
        detail = tpu.packs.stats()["packs"]["heads/body"]
        assert "heads_block" not in detail  # no head term was asked for
        assert breaker.used == detail["hbm_bytes"] > 0

        result = tpu.try_search(
            idx, dsl.MatchQuery(field="body", query="alpha beta"), k=10)
        assert result is not None and result.total_hits == 4500
        assert tpu.stats()["route"]["heads"] >= 1
        detail = tpu.packs.stats()["packs"]["heads/body"]
        block = detail["heads_block"]
        assert block["terms"] == 1 and block["bytes"] == (
            4 * block["rows"] * block["shard_rows"] * block["docs_per_row"])
        assert breaker.used == detail["hbm_bytes"] + block["bytes"]

        svc.delete_index("heads")
        tpu.invalidate_index("heads")
        assert tpu.packs.stats()["packs"] == {}
        assert breaker.used == 0
    finally:
        tpu.close()
        KERNEL_CONFIG["compressed_pack"] = True
