"""The dense jax.numpy reference (`ops/reference_dense.py`) held to the
numpy oracle (`ops/reference_impl.py`): the same corpus scored both
ways, per-shard statistics over several segments a shard, gives the same
totals, the same scores within 1e-5 relative, and the same docs but for
near-tie swaps."""

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.segment import SegmentWriter
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops import reference_dense, reference_impl

VOCAB = [f"w{i}" for i in range(60)]
REL_TOL = 1e-5


def _segment(rng, mapper, name, n_docs):
    writer = SegmentWriter(name)
    for i in range(n_docs):
        words = [VOCAB[min(int(rng.zipf(1.3)) - 1, len(VOCAB) - 1)]
                 for _ in range(int(rng.integers(1, 40)))]
        writer.add_document(mapper.parse_document(
            f"{name}-d{i}", {"body": " ".join(words)}), {})
    return writer.freeze()


@pytest.fixture(scope="module")
def shards():
    rng = np.random.default_rng(4601)
    mapper = MapperService(Settings.EMPTY,
                           {"properties": {"body": {"type": "text"}}})
    return [[_segment(rng, mapper, f"s{s}g{g}", int(rng.integers(40, 160)))
             for g in range(3)] for s in range(2)]


def _oracle(shards, terms, k):
    """reference_impl's per-shard scores → (total, [(score, shard, seg, doc)]
    best first, ties by shard, segment, doc)."""
    hits = []
    for si, segments in enumerate(shards):
        for gi, scores in enumerate(reference_impl.score_match_query(
                segments, "body", terms)):
            hits += [(float(s), si, gi, d) for d, s in enumerate(scores)
                     if s > 0]
    hits.sort(key=lambda h: (-h[0], h[1], h[2], h[3]))
    return len(hits), hits[:k]


@pytest.mark.parametrize("terms, k", [
    (["w0"], 10),
    (["w0", "w1", "w5"], 50),
    (["w3", "w3", "w40"], 20),          # a repeated term counts twice
    (["w2", "w7", "w11", "w19", "w33", "w59"], 1000),
    (["w58", "absent"], 10)])
def test_dense_reference_equals_the_numpy_oracle(shards, terms, k):
    total, hits = reference_dense.search(shards, "body", terms, k)
    want_total, want = _oracle(shards, terms, k)
    assert total == want_total
    assert len(hits) == len(want)
    for (si, gi, d, score), (w_score, w_si, w_gi, w_d) in zip(hits, want):
        assert abs(score - w_score) <= REL_TOL * abs(w_score)
        if (si, gi, d) != (w_si, w_gi, w_d):
            # a near-tie swap: the oracle scores the dense pick alike
            alt = reference_impl.score_match_query(
                shards[si], "body", terms)[gi][d]
            assert abs(float(alt) - w_score) <= REL_TOL * abs(w_score)


def test_nothing_matches_an_absent_term(shards):
    assert reference_dense.search(shards, "body", ["absent"], 10) == (0, [])
