"""The benchmark's own reference: plain numpy Lucene BM25, per shard.

Independent of the code under test (it imports none of it). Given the
corpus as flat token arrays it reproduces what the served path promises:

  shard(doc)  = floorMod(murmur3_x86_32(utf16le(_id), seed 0), shards)
  per shard:  N = docs in the shard, avgdl = Σ lengths / N, n_t = df of t
  idf(t)      = ln(1 + (N - n_t + 0.5) / (n_t + 0.5))
  dl(doc)     = byte4ToInt(intToByte4(length))        # lossy 1-byte norm
  score(doc)  = Σ_t idf(t)·(k1+1)·tf / (tf + f32(k1·(1-b+b·dl/avgdl)))
  hits        = every doc with score > 0 (operator `and`: that holds
                every term), best first; top k returned

(`elasticsearch_tpu/ops/reference_impl.py` is the program's copy of the
same arithmetic; this one is the benchmark's and stays put.) The list a
query gets runs past k through every doc within 10·REL_TOL of the k-th
score, so that a tie at the cut can be told from a wrong doc.
stdlib + numpy only.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

K1 = 1.2
B = 0.75
REL_TOL = 1e-5
#: the operators of a `match` query that have a reference
OPERATORS = ("or", "and")
#: `sum_by_doc` walks the doc axis where the postings pass this share of it
DENSE_SHARE = 4


# ---------------------------------------------------------------------------
# routing: murmur3_x86_32 over the UTF-16-LE bytes of a decimal id
# ---------------------------------------------------------------------------

def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return ((x << np.uint64(r)) | (x >> np.uint64(32 - r))) & np.uint64(0xFFFFFFFF)


def _mul(x: np.ndarray, c: int) -> np.ndarray:
    return (x * np.uint64(c)) & np.uint64(0xFFFFFFFF)


def murmur3_of_digit_ids(ids: np.ndarray) -> np.ndarray:
    """murmur3_x86_32(seed 0) of str(i).encode('utf-16-le') for each
    non-negative i, as signed int32 — vectorized by number of digits. A
    digit is one UTF-16 code unit (its ASCII byte, then 0), so two digits
    make one 4-byte block and an odd last digit is a 2-byte tail."""
    ids = np.asarray(ids, dtype=np.int64)
    out = np.empty(ids.shape[0], dtype=np.int64)
    n_digits = np.ones(ids.shape[0], dtype=np.int64)
    for d in range(1, 19):
        n_digits += ids >= 10 ** d
    c1, c2 = 0xCC9E2D51, 0x1B873593
    for nd in np.unique(n_digits).tolist():
        sel = np.flatnonzero(n_digits == nd)
        v = ids[sel]
        digits = [(v // 10 ** (nd - 1 - p)) % 10 + 48 for p in range(nd)]
        h = np.zeros(sel.shape[0], dtype=np.uint64)
        for blk in range(nd // 2):
            k = (digits[2 * blk] | (digits[2 * blk + 1] << 16)).astype(np.uint64)
            k = _mul(_rotl(_mul(k, c1), 15), c2)
            h ^= k
            h = (_rotl(h, 13) * np.uint64(5) + np.uint64(0xE6546B64)) \
                & np.uint64(0xFFFFFFFF)
        if nd % 2:
            k = digits[nd - 1].astype(np.uint64)  # tail bytes: digit, 0
            h ^= _mul(_rotl(_mul(k, c1), 15), c2)
        h ^= np.uint64(2 * nd)
        h ^= h >> np.uint64(16)
        h = _mul(h, 0x85EBCA6B)
        h ^= h >> np.uint64(13)
        h = _mul(h, 0xC2B2AE35)
        h ^= h >> np.uint64(16)
        out[sel] = h.astype(np.int64)
    return np.where(out >= 1 << 31, out - (1 << 32), out)


def shard_of_digit_ids(ids: np.ndarray, shards: int) -> np.ndarray:
    return np.mod(murmur3_of_digit_ids(ids), shards)  # floorMod


# ---------------------------------------------------------------------------
# the 1-byte norm (Lucene SmallFloat.intToByte4 / byte4ToInt)
# ---------------------------------------------------------------------------

def _byte4_to_int(b: int) -> int:
    bits, shift = b & 0x07, (b >> 3) - 1
    return bits if shift == -1 else (bits | 0x08) << shift


_LENGTH_TABLE = np.array([_byte4_to_int(b) for b in range(256)], dtype=np.int64)


def quantized_lengths(lengths: np.ndarray) -> np.ndarray:
    """Field length → the length the scorer sees after the norm byte."""
    v = np.maximum(np.asarray(lengths, dtype=np.int64), 0)
    _, nbits = np.frexp(v.astype(np.float64))
    shift = np.maximum(nbits - 4, 0).astype(np.int64)
    enc = np.where(nbits < 4, v, ((v >> shift) & 0x07) | ((shift + 1) << 3))
    return _LENGTH_TABLE[enc]


# ---------------------------------------------------------------------------
# postings of the terms a query set needs, and the scoring
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardIndex:
    """What the reference needs of one shard."""
    doc_count: int
    avgdl: float
    denom_add: np.ndarray                 # f64 [docs in corpus]; by global doc
    postings: Dict[int, Tuple[np.ndarray, np.ndarray]]  # term → (docs, tf)


def build_shard_indexes(flat: np.ndarray, offsets: np.ndarray, shards: int,
                        terms: Sequence[int]) -> List[ShardIndex]:
    """Per-shard statistics over the whole corpus and postings for `terms`
    only (global doc numbers = the decimal ids)."""
    n_docs = offsets.shape[0] - 1
    lengths = np.diff(offsets)
    shard_of = shard_of_digit_ids(np.arange(n_docs), shards)
    dl = quantized_lengths(lengths).astype(np.float64)
    lut = np.zeros(int(flat.max()) + 1 if flat.size else 1, dtype=bool)
    wanted = np.asarray(sorted(set(int(t) for t in terms)), dtype=np.int64)
    lut[wanted[wanted < lut.shape[0]]] = True
    pos = np.flatnonzero(lut[flat])
    doc_of = (np.searchsorted(offsets, pos, side="right") - 1).astype(np.int64)
    key = doc_of * np.int64(lut.shape[0]) + flat[pos].astype(np.int64)
    uniq, tf = np.unique(key, return_counts=True)
    p_doc, p_term = uniq // lut.shape[0], uniq % lut.shape[0]
    order = np.argsort(p_term, kind="stable")  # docs stay ascending
    p_doc, p_term, tf = p_doc[order], p_term[order], tf[order]
    bounds = np.searchsorted(p_term, wanted)
    ends = np.searchsorted(p_term, wanted, side="right")
    out = []
    for s in range(shards):
        mine = shard_of == s
        doc_count = int(mine.sum())
        avgdl = float(lengths[mine].sum()) / doc_count if doc_count else 1.0
        denom = K1 * (1.0 - B + B * dl / avgdl)
        denom = denom.astype(np.float32).astype(np.float64)  # Lucene's cache
        postings = {}
        for t, lo, hi in zip(wanted.tolist(), bounds.tolist(), ends.tolist()):
            docs = p_doc[lo:hi]
            keep = mine[docs]
            postings[t] = (docs[keep], tf[lo:hi][keep])
        out.append(ShardIndex(doc_count, avgdl, denom, postings))
    return out


def bm25_idf(doc_count: int, doc_freq: int) -> float:
    return float(np.log(1.0 + (doc_count - doc_freq + 0.5) / (doc_freq + 0.5)))


def sum_by_doc(docs: np.ndarray, scores: np.ndarray, n_docs: int, held: int = 1
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Postings of several terms, one term after another → (the docs that
    have at least `held` of them, ascending; each one's f64 sum of
    `scores`, added in the order given). Two routines, equal to the bit
    (either way a doc's scores are added in input order): a sort of the
    postings (`np.unique`), or, where they pass `1/DENSE_SHARE` of the
    corpus's docs, a `np.bincount` over the whole doc axis, which the half
    a million postings of a stop-word reach sooner than a sort does."""
    if docs.shape[0] * DENSE_SHARE > n_docs:
        # a mask first: nonzero() of a bool array is many times faster
        uniq = np.flatnonzero(np.bincount(docs, minlength=n_docs) >= held)
        return uniq, np.bincount(docs, weights=scores, minlength=n_docs)[uniq]
    uniq, inv = np.unique(docs, return_inverse=True)
    sums = np.bincount(inv, weights=scores, minlength=uniq.shape[0])
    if held > 1:
        every = np.bincount(inv, minlength=uniq.shape[0]) >= held
        uniq, sums = uniq[every], sums[every]
    return uniq, sums


def reference_topk(shard_indexes: Sequence[ShardIndex], terms: Sequence[int],
                   k: int, operator: str = "or"
                   ) -> Tuple[int, np.ndarray, np.ndarray]:
    """`terms` under `operator` → (total hits, global docs, f32 scores),
    best first, ties by lower doc, cut past k at the end of the near-tie at
    the cut. `or`: every doc that holds a term. `and`: the docs that hold
    every term (a term that a shard lacks leaves that shard empty), scored
    as `or` scores them: the sum over all terms by the shard's own
    statistics."""
    if operator not in OPERATORS:
        raise ValueError(f"unknown operator [{operator}]: one of {OPERATORS}")
    all_docs, all_scores = [], []
    for sh in shard_indexes:
        docs_parts, score_parts = [], []
        for t in terms:
            docs, tf = sh.postings.get(int(t), (None, None))
            if docs is None or docs.shape[0] == 0:
                if operator == "and":
                    docs_parts = []
                    break
                continue
            w = bm25_idf(sh.doc_count, int(docs.shape[0])) * (K1 + 1.0)
            tff = tf.astype(np.float64)
            docs_parts.append(docs)
            score_parts.append(w * tff / (tff + sh.denom_add[docs]))
        if not docs_parts:
            continue
        # f64 sums: their order moves the last bit, far inside REL_TOL
        # `and`: a term's postings hold a doc once, so a doc with as many
        # postings as the query has terms holds them all
        uniq, sums = sum_by_doc(np.concatenate(docs_parts),
                                np.concatenate(score_parts), sh.denom_add.shape[0],
                                held=len(terms) if operator == "and" else 1)
        all_docs.append(uniq)
        all_scores.append(sums.astype(np.float32))
    if not all_docs:
        return 0, np.empty(0, np.int64), np.empty(0, np.float32)
    docs = np.concatenate(all_docs)
    scores = np.concatenate(all_scores)
    pos = scores > 0
    if not pos.all():
        docs, scores = docs[pos], scores[pos]
    total = int(docs.shape[0])
    keep = np.arange(min(total, k))
    if total > k:
        kth = np.partition(scores, total - k)[total - k]
        keep = np.flatnonzero(scores >= kth * (1.0 - 10 * REL_TOL))
    order = keep[np.lexsort((docs[keep], -scores[keep].astype(np.float64)))]
    return total, docs[order], scores[order]


def stored_name(operator: str) -> str:
    """The file of an index directory that holds the reference top-k of
    every query under `operator`: one file an operator, `reference.npz`
    the `or` one under the name it always had."""
    if operator not in OPERATORS:
        raise ValueError(f"no reference for operator [{operator}]: one of {OPERATORS}")
    return "reference.npz" if operator == "or" else f"reference-{operator}.npz"
