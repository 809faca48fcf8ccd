"""The full-postings ladder (ISSUE 32): a train's full-path queries are
split over the rungs of `FULL_SLOT_BUCKETS`, at the row buckets each rung
has a program for (`FULL_ROW_BUCKETS`), by modelled device time
(`_split_full_train`); a query's answer is the same at every rung that
holds it; and the programs of the rungs up to `FULL_READY_SLOTS` are
compiled by the node before it serves from them (`full_program_set`), so
that no train compiles whatever mix of needs and whatever fill it has.
The same split over the exact kernel's ladder (ISSUE 37): the slot pins a
train holds at the row buckets of `_serving_bucket`.
"""

from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from esbench import compare, corpus, reference  # noqa: E402

from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.node import Node  # noqa: E402
from elasticsearch_tpu.parallel import distributed as dist  # noqa: E402
from elasticsearch_tpu.search import tpu_service  # noqa: E402
from elasticsearch_tpu.search.tpu_service import (  # noqa: E402
    FULL_ROW_BUCKETS, FULL_SLOT_BUCKETS, FlatQuery, _serving_bucket,
    _serving_buckets, _split_full_train)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# the split: launches and row buckets for a train's group sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes, shard_rows, launches", [
    # MS MARCO (two shard rows a chip). Its typical train: the residue
    # rides in the 128-slot launch that is made anyway
    ((122, 5, 1), 2, [(16, 128), (128, 8)]),
    ((120, 7, 0), 2, [(16, 128), (32, 8)]),
    # a residue past eight rows goes in chunks of eight at 32 slots:
    # never 64 rows at 128 slots, nor a taller program at 32
    ((110, 18, 0), 2, [(16, 128), (32, 8), (32, 8), (32, 8)]),
    ((118, 9, 1), 2, [(16, 128), (32, 8), (32, 8), (128, 8)]),
    ((60, 5, 0), 2, [(16, 64), (32, 8)]),
    # the narrow rung has no program of eight rows: a short train rides
    # at 32 slots, where width costs little
    ((5, 0, 0), 2, [(32, 8)]),
    ((0, 0, 3), 2, [(128, 8)]),
    ((0, 0, 0), 2, []),
    # Quora (one shard row): its full-path share of a train stays one
    # launch, and a rare 17-slot query adds one of eight rows
    ((43, 0, 0), 1, [(16, 64)]),
    ((42, 1, 0), 1, [(16, 64), (32, 8)]),
    # the rule of before as a case of this one: a small group rides in
    # the wider launch that is made anyway, a large one does not
    ((0, 5, 2), 2, [(128, 8)]),
    ((3, 0, 1), 1, [(128, 8)]),
    ((6, 20, 0), 1, [(32, 8), (32, 8), (32, 8), (32, 8)]),
    ((128, 0, 0), 1, [(16, 128)]),
    ((0, 0, 60), 2, [(128, 64)]),
])
def test_a_train_is_split_by_modelled_device_time(sizes, shard_rows, launches):
    groups, at = {}, 0
    for b, n in zip(FULL_SLOT_BUCKETS, sizes):
        groups[b] = list(range(at, at + n))
        at += n
    split = _split_full_train(groups, shard_rows)
    assert [(b, next(r for r in FULL_ROW_BUCKETS[b] if r >= len(idxs)))
            for b, idxs in split] == launches
    # every query is launched once, at a rung that holds it
    assert sorted(i for _b, idxs in split for i in idxs) == list(range(at))
    for b, idxs in split:
        assert idxs and not any(i in groups[g] for i in idxs
                                for g in FULL_SLOT_BUCKETS if g > b)


@pytest.mark.parametrize("sizes, shard_rows, max_batch, launches", [
    # the AND cell's mean train (ISSUE 37's reckoning; two shard rows a
    # chip): the many at 8 slots in one tall launch, the pin-16 queries
    # in two of eight rows, and the pin-32 ones in the 64-slot launch of
    # eight rows that the one pin-64 query needs anyway
    ({8: 109, 16: 13, 32: 5, 64: 1}, 2, 128,
     [(8, 128, 109), (16, 8, 8), (16, 8, 5), (64, 8, 6)]),
    # no pin-64 query in the train: the pin-32 ones fill up the second
    # launch of the pin-16 ones (whose first holds none wider than 16)
    ({8: 112, 16: 12, 32: 4}, 2, 128,
     [(8, 128, 112), (16, 8, 8), (32, 8, 8)]),
    ({8: 120, 16: 7, 32: 1}, 2, 128, [(8, 128, 120), (32, 8, 8)]),
    ({8: 115, 16: 8, 32: 5}, 2, 128,
     [(8, 128, 115), (16, 8, 8), (32, 8, 5)]),
    # past eight rows a second launch of eight costs less than 64 rows
    ({8: 118, 32: 10}, 2, 128, [(8, 128, 118), (32, 8, 8), (32, 8, 2)]),
    ({8: 128}, 2, 128, [(8, 128, 128)]),
    ({8: 60, 16: 4}, 2, 128, [(8, 64, 60), (16, 8, 4)]),
    # 9 to 16 queries go in two launches of eight rows, 17 and more in
    # one of 64
    ({8: 9}, 2, 128, [(8, 8, 8), (8, 8, 1)]),
    ({8: 17}, 2, 128, [(8, 64, 17)]),
    # a short train of mixed pins stays one launch at its widest: lanes
    # of eight rows cost less than a launch
    ({8: 5, 16: 1, 32: 1}, 2, 128, [(32, 8, 7)]),
    ({64: 3}, 2, 128, [(64, 8, 3)]),
    ({}, 2, 128, []),
    # Quora (one shard row; every exact query past 8 terms, so at the
    # 32-slot floor): one group, one launch, as before the split
    ({32: 86}, 1, 128, [(32, 128, 86)]),
    ({32: 128}, 1, 128, [(32, 128, 128)]),
    ({32: 40}, 1, 128, [(32, 64, 40)]),
    ({32: 5}, 1, 128, [(32, 8, 5)]),
    # a ladder that reaches 512 slots is searched over the pins present
    ({8: 100, 512: 1}, 2, 128, [(8, 128, 100), (512, 8, 1)]),
    # trains taller than 128 (a node set to them) have a taller bucket
    ({8: 200, 16: 3}, 2, 256, [(8, 256, 200), (16, 8, 3)]),
])
def test_an_exact_train_is_split_by_slot_pin(sizes, shard_rows, max_batch,
                                             launches):
    """`_split_full_train` over the exact kernel's ladder: the pins the
    train holds, each at the row buckets of `_serving_bucket`. A launch
    is (its widest query's pin, its row bucket, its queries)."""
    groups, own, at = {}, {}, 0
    for pin, n in sizes.items():
        groups[pin] = list(range(at, at + n))
        own.update(dict.fromkeys(groups[pin], pin))
        at += n
    row_buckets = _serving_buckets(max_batch)
    split = _split_full_train(groups, shard_rows,
                              {pin: row_buckets for pin in groups})
    # `_launch_exact` pins a launch by its widest query and its length
    assert [(max(own[i] for i in idxs), _serving_bucket(len(idxs)), len(idxs))
            for _pin, idxs in split] == launches
    # every query in exactly one launch, at a pin that holds it, and every
    # launch a (rows, pin) that the closed set lists
    assert sorted(i for _pin, idxs in split for i in idxs) == list(range(at))
    for pin, idxs in split:
        assert pin in sizes and all(own[i] <= pin for i in idxs)
        assert _serving_bucket(len(idxs)) in row_buckets


# ---------------------------------------------------------------------------
# a toy deployment whose queries need 2 to 32 slots
# ---------------------------------------------------------------------------

#: 9,000 short documents: eight common words, each in nineteen of twenty
#: (postings of three chunks of CHUNK_CAP, so a query of six to eight of
#: them needs 18 to 24 slots), and five of 2,000 rare ones (a slot each)
DOCS, COMMON, RARE = 9000, 8, 2000
INDEX, FIELD, SIZE = "ladder", "body", 1000


def _corpus() -> corpus.Corpus:
    rng = np.random.default_rng(32)
    docs = []
    for _ in range(DOCS):
        common = np.flatnonzero(rng.random(COMMON) < 0.95)
        rare = COMMON + rng.integers(0, RARE, size=5)
        docs.append(np.concatenate([common, rare, rare[:rng.integers(0, 3)]]))
    offsets = np.zeros(DOCS + 1, dtype=np.int64)
    np.cumsum([len(d) for d in docs], out=offsets[1:])
    return corpus.Corpus(np.concatenate(docs).astype(np.uint16), offsets,
                         COMMON + RARE)


@pytest.fixture(scope="module")
def ladder(tmp_path_factory):
    saved = dict(tpu_service.KERNEL_CONFIG)
    corp = _corpus()
    node = Node(str(tmp_path_factory.mktemp("ladder")), settings=Settings.of({
        "search.tpu_serving.kernel.compressed_pack": False}))
    try:
        svc = node.indices.create_index(
            INDEX, Settings.of({"index": {"number_of_shards": 1,
                                          "translog.durability": "async"}}),
            {"properties": {FIELD: {"type": "text"}}})
        words = [corpus.word(i) for i in range(corp.vocab_size)]
        shard = svc.shard(0)
        for i in range(corp.num_docs):
            shard.apply_index_on_primary(
                corpus.doc_id(i), {FIELD: corpus.doc_text(corp, i, words)})
        svc.refresh()
        resident = node.tpu_search.packs.get(svc, FIELD)
        assert resident is not None and resident.comp_streams is None

        def flat(terms):
            return FlatQuery(FIELD, [corpus.word(t) for t in terms], 1.0, 1)

        light = [[COMMON + 3 * i, COMMON + 700 + 5 * i, COMMON + 1400 + i
                  ][:2 + i % 2] for i in range(128)]
        heavy = [[t for t in range(COMMON) if t not in skip]
                 for skip in ((), (0,), (1,), (2,), (3,), (4,), (5,), (6,),
                              (7,), (0, 1), (2, 3), (4, 5))]
        needs = [tpu_service._slots_needed(resident, flat(q))
                 for q in light + heavy]
        assert max(needs[:128]) <= 4 and min(needs[128:]) > 16 \
            and max(needs) <= 32, needs
        shards = reference.build_shard_indexes(
            corp.flat, corp.offsets, 1,
            sorted({t for q in light + heavy for t in q}))
        yield {"node": node, "resident": resident, "flat": flat,
               "mesh": node.tpu_search.packs.mesh, "light": light,
               "heavy": heavy, "shards": shards}
    finally:
        node.close()
        tpu_service.KERNEL_CONFIG.update(saved)


def _as_response(res):
    return {"_shards": {"failed": 0}, "hits": {
        "total": {"value": res.total_hits, "relation": res.total_relation},
        "hits": [{"_id": h[-1], "_score": h[0]} for h in res.hits]}}


def test_one_query_at_every_rung_is_the_same_to_the_bit(ladder):
    """The full path is exact at any width that holds the query: the same
    run totals, the same tie rule, and the reference's answer."""
    resident, mesh = ladder["resident"], ladder["mesh"]
    for q in (ladder["light"][1],):
        outs = []
        for slots in FULL_SLOT_BUCKETS:
            results, invalid = tpu_service._execute_pruned(
                resident, [ladder["flat"](q)], SIZE, mesh, full_slots=slots)
            assert not invalid
            outs.append(results[0])
        for other in outs[1:]:
            assert np.array_equal(outs[0].scores, other.scores)
            assert np.array_equal(outs[0].rows, other.rows)
            assert np.array_equal(outs[0].ords, other.ords)
            assert outs[0].total_hits == other.total_hits
        total, docs, scores = reference.reference_topk(ladder["shards"], q, SIZE)
        assert total > 0
        resp = _as_response(outs[0])
        compare.compare_response(resp, total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
        assert compare.score_gap(resp, scores.tolist()) < compare.REL_TOL


def test_a_rung_forced_on_a_query_it_does_not_hold_still_answers(ladder):
    """The routing never does it; prewarm and the tests can: the launch
    then runs the jitted program at the query's own width, not the
    executable compiled for the rung's."""
    resident, mesh = ladder["resident"], ladder["mesh"]
    heavy = ladder["flat"](ladder["heavy"][0])
    at_32, _ = tpu_service._execute_pruned(resident, [heavy] * 9, SIZE, mesh,
                                           full_slots=32)
    forced, _ = tpu_service._execute_pruned(resident, [heavy] * 9, SIZE, mesh,
                                            full_slots=16)
    assert np.array_equal(forced[0].scores, at_32[0].scores)
    assert np.array_equal(forced[0].ords, at_32[0].ords)


def test_after_the_first_full_path_search_no_train_compiles(ladder):
    """Any mix of needs up to FULL_READY_SLOTS at any fill: the three
    programs were compiled, and are called, ahead of `jax.jit`'s cache."""
    node, resident, mesh = ladder["node"], ladder["resident"], ladder["mesh"]
    flat, light, heavy = ladder["flat"], ladder["light"], ladder["heavy"]
    svc = node.tpu_search
    tpu_service.execute_flat_batch(resident, [flat(light[5])], SIZE, mesh)
    key = f"{INDEX}/{FIELD}"
    assert svc.stats()["full_programs"][key] == [
        "full_s16_b128", "full_s16_b64", "full_s32_b8"]
    assert [p.label for p in tpu_service.full_program_set(
        resident, SIZE, max_batch=8)] == ["full_s32_b8"]
    events = []

    def listener(event, duration, **kw):
        if event == COMPILE_EVENT:
            events.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    before = svc.stats()
    try:
        # fills 1 and 9 at either need, 65 and 128 mixed: every member
        # of the set is launched, the two at 16 slots for the first time
        trains = [light[:1], heavy[:1], light[:9], heavy[:9],
                  light[:63] + heavy[:2], light[:120] + heavy[:8]]
        for train in trains:
            results = tpu_service.execute_flat_batch(
                resident, [flat(q) for q in train], SIZE, mesh)
            assert len(results) == len(train)
            assert all(r.total_hits > 0 for r in results)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert events == []
    after = svc.stats()

    def rise(block):
        return {key: after[block][key] - before[block].get(key, 0)
                for key in after[block]
                if after[block][key] != before[block].get(key, 0)}

    # a short train rides at 32 slots, nine heavy queries go in two
    # chunks of eight rows, the mixed trains make a launch at each rung
    assert rise("launches") == {"full_s16": 3, "full_s32": 6}
    assert rise("route") == {"pruned_full_s16": 9 + 63 + 120,
                             "pruned_full_s32": 1 + 1 + 9 + 2 + 8}
    rows = [(8, 32), (8, 32), (64, 16), (8, 32), (8, 32), (64, 16),
            (8, 32), (128, 16), (8, 32)]
    entries = rise("full_entries")
    assert entries["padded"] == sum(
        r * s for r, s in rows) * dist.CHUNK_CAP * resident.pack.num_shards
    assert 0 < entries["real"] < entries["padded"]
    # the same trains answered as the reference answers them
    for q in (heavy[0], light[8]):
        res = tpu_service.execute_flat_batch(resident, [flat(q)], SIZE,
                                             mesh)[0]
        total, docs, scores = reference.reference_topk(ladder["shards"], q, SIZE)
        compare.compare_response(_as_response(res), total,
                                 [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), SIZE)
