"""Plain dense BM25 in jax.numpy float32: the test suite's second reference.

Every doc of every shard is scored, one query at a time: the query's term
weights (boost · idf · (k1+1), per shard) times a dense [terms, docs]
matrix of each term's tf / (tf + k1·(1 − b + b·dl/avgdl)), one matrix
product a shard under `jax.default_matmul_precision("highest")` (on a TPU
a float32 product otherwise runs in bfloat16), then `lax.top_k` over
all shards' docs. Statistics are per shard (doc count, avgdl, df over the
shard's segments), the doc length is the 1-byte norm's, as in
`reference_impl`, to which its own test holds it. No batching, no
postings layout, no kernel, no head block.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.index.segment import Segment
from elasticsearch_tpu.ops.smallfloat import LENGTH_TABLE

#: a hit: (shard, segment index in the shard, doc ordinal, score)
Hit = Tuple[int, int, int, float]


def _shard_scores(segments: Sequence[Segment], field: str,
                  terms: Sequence[str], boost: float, k1: float,
                  b: float) -> jax.Array:
    """float32 scores of every doc of one shard's segments, in order."""
    doc_count = sum_ttf = 0
    for seg in segments:
        st = seg.field_stats.get(field)
        if st:
            doc_count += st.doc_count
            sum_ttf += st.sum_total_term_freq
    avgdl = sum_ttf / doc_count if doc_count else 1.0
    n_docs = sum(seg.num_docs for seg in segments)
    tf = np.zeros((len(terms), n_docs), dtype=np.float32)
    dl = np.zeros(n_docs, dtype=np.float32)
    at = 0
    for seg in segments:
        norms = seg.norms.get(field)
        if norms is not None:
            dl[at:at + seg.num_docs] = LENGTH_TABLE[norms.astype(np.int64)]
        for t, term in enumerate(terms):
            entry = seg.postings.get(field, {}).get(term)
            if entry is not None:
                docs, tfs = entry
                tf[t, at + docs] = tfs
        at += seg.num_docs
    df = jnp.asarray([sum(seg.doc_freq(field, term) for seg in segments)
                      for term in terms], dtype=jnp.float32)
    idf = jnp.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))
    weights = jnp.where(df > 0, boost * idf * (k1 + 1.0), 0.0)
    tf = jnp.asarray(tf)
    denom = k1 * (1.0 - b + b * jnp.asarray(dl) / avgdl)
    impact = jnp.where(tf > 0, tf / (tf + denom[None, :]), 0.0)
    with jax.default_matmul_precision("highest"):
        return jnp.matmul(weights, impact)


def search(shards: Sequence[Sequence[Segment]], field: str,
           terms: Sequence[str], k: int, boost: float = 1.0,
           k1: float = 1.2, b: float = 0.75) -> Tuple[int, List[Hit]]:
    """An OR of `terms` over `shards` (each a list of segments) → (the
    docs with a score above 0, the top `k` of them best first, ties by
    shard, segment and doc)."""
    if not terms:
        return 0, []
    where: List[Tuple[int, int, int]] = []
    parts = []
    for si, segments in enumerate(shards):
        if not segments:
            continue
        parts.append(_shard_scores(segments, field, terms, boost, k1, b))
        for gi, seg in enumerate(segments):
            where.extend((si, gi, d) for d in range(seg.num_docs))
    if not parts:
        return 0, []
    scores = jnp.concatenate(parts)
    total = int(jnp.sum(scores > 0.0))
    if total == 0 or k <= 0:
        return total, []
    vals, pos = jax.lax.top_k(scores, min(k, total))
    return total, [(*where[p], float(v))
                   for v, p in zip(np.asarray(vals).tolist(),
                                   np.asarray(pos).tolist())]
