"""Synthetic corpus and query set, from a configuration file's numbers.

A copy of the law in `elasticsearch_tpu/benchmark/corpus.py` (Zipf word
frequencies, log-normal passage lengths clipped to [8, 6·mean], query
terms drawn without replacement from a mid-frequency band of ranks: the
`band` law of `QUERY_LAWS`, which a configuration's `query_law` chooses
from), kept with the benchmark so that no later PR can change the yardstick. Two
departures, both on purpose: the planted relevance judgments are gone
(the benchmark holds responses to exact BM25, not to nDCG), and docs and
queries draw from separate streams of the seed, so the number of queries
does not move the corpus. Tokens are flat arrays, never per-doc lists:
1.1M docs are two numpy arrays. stdlib + numpy only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

LENGTH_SIGMA = 0.45
MIN_LENGTH = 8


@dataclasses.dataclass
class Corpus:
    flat: np.ndarray       # uint16/int32 [tokens]: word ids, doc after doc
    offsets: np.ndarray    # int64 [docs + 1]
    vocab_size: int

    @property
    def num_docs(self) -> int:
        return int(self.offsets.shape[0] - 1)

    @property
    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets)

    def doc_words(self, i: int) -> np.ndarray:
        return self.flat[self.offsets[i]:self.offsets[i + 1]]


def word(i: int) -> str:
    return f"w{i}"


def doc_id(i: int) -> str:
    return str(i)


def zipf_probs(vocab_size: int, s: float) -> np.ndarray:
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = 1.0 / ranks ** s
    return p / p.sum()


def generate_corpus(gen: Dict[str, Any]) -> Corpus:
    """`gen` is the `generator` group of a configuration file."""
    docs = int(gen["docs"])
    vocab_size = int(gen["vocab_size"])
    mean_len = float(gen["mean_length"])
    rng = np.random.default_rng([int(gen["corpus_seed"]), 0])
    mu = np.log(mean_len) - LENGTH_SIGMA ** 2 / 2
    lengths = np.clip(
        rng.lognormal(mu, LENGTH_SIGMA, docs).astype(np.int64),
        min(MIN_LENGTH, int(mean_len)), int(6 * mean_len))
    offsets = np.zeros(docs + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    # inverse-CDF draw: what rng.choice(p=...) does, without its float64
    # copy of the result
    cdf = np.cumsum(zipf_probs(vocab_size, float(gen["zipf_s"])))
    cdf[-1] = 1.0
    dtype = np.uint16 if vocab_size <= 65536 else np.int32
    flat = np.empty(int(offsets[-1]), dtype=dtype)
    step = 1 << 23
    for lo in range(0, flat.shape[0], step):
        hi = min(lo + step, flat.shape[0])
        flat[lo:hi] = np.searchsorted(cdf, rng.random(hi - lo), side="right")
    return Corpus(flat, offsets, vocab_size)


def band_queries(gen: Dict[str, Any], rng: np.random.Generator) -> List[List[int]]:
    """Each query `terms_min..terms_max` distinct word ids, uniform over
    ranks [band_lo, band_hi): no word above the band, so no stop-word."""
    band = np.arange(int(gen["query_band_lo"]),
                     min(int(gen["query_band_hi"]), int(gen["vocab_size"])))
    lo, hi = int(gen["query_terms_min"]), int(gen["query_terms_max"])
    seen, queries = set(), []
    while len(queries) < int(gen["num_queries"]):
        n = int(rng.integers(lo, hi + 1))
        terms = tuple(int(t) for t in rng.choice(band, size=n, replace=False))
        if terms not in seen:
            seen.add(terms)
            queries.append(list(terms))
    return queries


def stopmix_queries(gen: Dict[str, Any], rng: np.random.Generator) -> List[List[int]]:
    """Questions as people type them, stop-words kept: a query draws
    clip(1 + Poisson(terms_mean - 1), terms_min, terms_max) words, each
    with probability `query_stop_share` a rank of [0, band_lo) by the
    corpus's own Zipf weights over those ranks, else uniform from
    [band_lo, band_hi) as the band law draws. The query is its distinct
    words in draw order, drawn again if fewer than terms_min are left."""
    band_lo = int(gen["query_band_lo"])
    band_hi = min(int(gen["query_band_hi"]), int(gen["vocab_size"]))
    lo, hi = int(gen["query_terms_min"]), int(gen["query_terms_max"])
    mean, share = float(gen["query_terms_mean"]), float(gen["query_stop_share"])
    if not (0.0 <= share <= 1.0 and mean >= 1.0 and 1 <= band_lo < band_hi):
        raise ValueError(
            "query_law [stopmix] needs 0 <= query_stop_share <= 1, query_terms_mean "
            ">= 1 and 1 <= query_band_lo < query_band_hi (the stop-words are the "
            f"ranks above the band); got {share}, {mean}, {band_lo}, {band_hi}")
    stop_p = zipf_probs(int(gen["vocab_size"]), float(gen["zipf_s"]))[:band_lo]
    stop_p /= stop_p.sum()
    seen, queries = set(), []
    while len(queries) < int(gen["num_queries"]):
        n = int(np.clip(1 + rng.poisson(mean - 1.0), lo, hi))
        ranks = np.where(rng.random(n) < share,
                         rng.choice(band_lo, size=n, p=stop_p),
                         rng.integers(band_lo, band_hi, size=n))
        terms = tuple(dict.fromkeys(ranks.tolist()))
        if len(terms) >= lo and terms not in seen:
            seen.add(terms)
            queries.append(list(terms))
    return queries


_BAND_KEYS = ("query_terms_min", "query_terms_max", "query_band_lo", "query_band_hi")
#: law -> (function of the generator group and the query stream's rng, the
#: generator keys it reads beside vocab_size, zipf_s, corpus_seed, num_queries)
QUERY_LAWS = {
    "band": (band_queries, _BAND_KEYS),
    "stopmix": (stopmix_queries, _BAND_KEYS + ("query_terms_mean", "query_stop_share")),
}


def generate_queries(gen: Dict[str, Any]) -> List[List[int]]:
    """`num_queries` distinct queries by the configuration's `query_law`
    (absent: `band`), from the stream `[corpus_seed, 1]`: the query set is
    the configuration's, as the corpus is."""
    law = gen.get("query_law", "band")
    if law not in QUERY_LAWS:
        raise ValueError(f"unknown query_law [{law}]: one of {sorted(QUERY_LAWS)}")
    draw, keys = QUERY_LAWS[law]
    missing = [k for k in keys + ("num_queries",) if k not in gen]
    if missing:
        raise ValueError(f"query_law [{law}] needs the generator keys {missing}")
    return draw(gen, np.random.default_rng([int(gen["corpus_seed"]), 1]))


def query_text(terms: List[int]) -> str:
    return " ".join(word(t) for t in terms)


def doc_text(corpus: Corpus, i: int, words: List[str]) -> str:
    return " ".join([words[t] for t in corpus.doc_words(i).tolist()])
