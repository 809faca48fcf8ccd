"""What a later PR can add with files alone (ISSUE 35): a configuration's
`query_law`, a traffic file's `operator` with the reference stored for
it, a warm-up stratum with an upper edge; and that the three committed
configurations still build the parent's query sets and references. CPU,
seconds; the served `operator: and` test opens a toy node.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_query_laws.py -q -p no:cacheprovider
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)

import build_index  # noqa: E402
import run  # noqa: E402
from esbench import compare, corpus, reference, traffic  # noqa: E402

CONFIGS = ("msmarco-1chip", "beir-quora-1chip", "msmarco-4chip")
MSMARCO_QUERIES = ("3a44193544d6da2021da24e877be86c0f5dd481f8444ca6992d62c63a9909553",
                   "18e928f77902e5f21a55a273adf0db7d60a7347e42b6ae3473ad6aa4bdf0172e")
#: sha256 of the query set's (offsets, terms) as `queries.npz` holds them,
#: taken on the parent commit 8f85f92 at full size
PARENT_QUERY_SETS = {
    "msmarco-1chip": MSMARCO_QUERIES,
    "beir-quora-1chip": ("21f5e7c9eef6e9d99d10fc0a6272a3f98cea5aec5c3d3d9e6e22d3dde729fd5a",
                         "ce8c812728ae7739419dc377dbd8a6d36853518d92928ccf1064ca46685c0021"),
    "msmarco-4chip": MSMARCO_QUERIES,
}
#: first 16 hex of the sha256 of every array of `reference.npz` and
#: `queries.npz` at a rehearsal's size (20,000 docs, 400 queries), taken on
#: the parent commit 8f85f92 by its own build_index arithmetic
PARENT_REHEARSAL_ARRAYS = {
    "msmarco-1chip": {
        "offsets": "265eb2ef2972944d", "docs": "1a26da0fcadc1dcc",
        "scores": "0e04c1e320c6890a", "totals": "e3e6cddfd8620900",
        "q_offsets": "93e3a52e3aa02252", "q_terms": "a05143103e9babd8",
        "q_postings": "51aaf95fc42639de"},
    "beir-quora-1chip": {
        "offsets": "e997f1d78d3d3988", "docs": "e96b1994db08d0de",
        "scores": "05e1a703aee2cde5", "totals": "271f2e5b8692b153",
        "q_offsets": "dc7cbc96e1140640", "q_terms": "4c4af97e7fba7c75",
        "q_postings": "32ca3d3ff051d4fe"},
    "msmarco-4chip": {
        "offsets": "cc37ddb22ab5e6d3", "docs": "b44b7db76bb75c4f",
        "scores": "6f11d8ce85ee6acf", "totals": "e3e6cddfd8620900",
        "q_offsets": "93e3a52e3aa02252", "q_terms": "a05143103e9babd8",
        "q_postings": "96bb3de1ae5ead4e"},
}
#: ISSUE 35's scratch deployment: `msmarco-1chip`'s numbers, the real shape
STOPMIX = {"query_law": "stopmix", "query_terms_min": 2, "query_terms_max": 12,
           "query_terms_mean": 6, "query_stop_share": 0.3,
           "query_band_lo": 20, "query_band_hi": 3000}


def sha(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def config_of(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", name + ".json"), "r",
              encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# a configuration's query law
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_band_law_draws_the_parents_query_set(name):
    gen = config_of(name)["generator"]
    assert "query_law" not in gen                 # absent = band: no file edited
    queries = corpus.generate_queries(gen)
    assert queries == corpus.generate_queries(dict(gen, query_law="band"))
    offsets = np.cumsum([0] + [len(q) for q in queries]).astype(np.int64)
    terms = np.concatenate([np.asarray(q, dtype=np.int64) for q in queries])
    assert (sha(offsets), sha(terms)) == PARENT_QUERY_SETS[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_rehearsal_sized_reference_and_strata_equal_the_parents(name, tmp_path):
    """`write_reference` on the cut configuration → the arrays the parent's
    `build()` stored, to the byte."""
    config = config_of(name)
    gen = dict(config["generator"], docs=run.REHEARSE_DOCS,
               num_queries=run.REHEARSE_QUERIES)
    corp, queries = corpus.generate_corpus(gen), corpus.generate_queries(gen)
    seconds = build_index.write_reference(
        str(tmp_path), corp, queries, int(config["index"]["number_of_shards"]), "or",
        with_strata=True)
    assert seconds > 0
    assert sorted(os.listdir(tmp_path)) == ["queries.npz", "reference.npz"]
    ref, qnpz = np.load(tmp_path / "reference.npz"), np.load(tmp_path / "queries.npz")
    assert int(ref["k"]) == build_index.REFERENCE_K and ref["docs"].dtype == np.int32
    got = {key: sha(ref[key])[:16] for key in ("offsets", "docs", "scores", "totals")}
    got.update({"q_" + key: sha(qnpz[key])[:16]
                for key in ("offsets", "terms", "postings")})
    assert got == PARENT_REHEARSAL_ARRAYS[name]


def stopmix_gen(**over) -> dict:
    return {**config_of("msmarco-1chip")["generator"], **STOPMIX,
            "num_queries": 7000, **over}


def test_stopmix_is_seeded_distinct_and_keeps_its_numbers():
    gen = stopmix_gen()
    queries = corpus.generate_queries(gen)
    assert queries == corpus.generate_queries(gen)
    assert queries[:50] != corpus.generate_queries(dict(gen, corpus_seed=24))[:50]
    # more queries are the same queries and then some, as with the band law
    assert corpus.generate_queries(dict(gen, num_queries=300)) == queries[:300]
    assert len(queries) == 7000 == len({tuple(q) for q in queries})
    n = np.array([len(q) for q in queries])
    flat = np.concatenate(queries)
    assert all(len(set(q)) == len(q) for q in queries)
    assert n.min() == 2 and n.max() <= 12 and 0 <= flat.min() and flat.max() < 3000
    assert abs(n.mean() - gen["query_terms_mean"]) < 0.2          # 5.81
    # a drawn word is a stop-word with probability 0.3; among a query's
    # *distinct* words the share is lower by the repeats among 20 ranks
    # (rank 1 is 30% of their mass): 0.275
    stop = float((flat < gen["query_band_lo"]).mean())
    assert gen["query_stop_share"] - 0.03 < stop < gen["query_stop_share"]
    # the stop-words by the corpus's own Zipf weights: rank 1 before rank 2
    counts = np.bincount(flat[flat < 20], minlength=20)
    assert counts[0] > counts[1] > counts[4] > counts[19] > 0
    # and the band uniform, as the band law draws it
    band = np.bincount(flat[flat >= 20] - 20, minlength=2980)
    assert band.std() < 1.2 * np.sqrt(band.mean()) and band.max() < 4 * band.mean()
    # what the shape is for: most queries hold a stop-word, some pass 8 words
    assert 0.8 < np.mean([min(q) < 20 for q in queries]) < 0.9
    assert 0.05 < (n > 8).mean() < 0.2


def test_stopmix_at_share_nought_is_a_band_of_poisson_lengths():
    queries = corpus.generate_queries(stopmix_gen(query_stop_share=0.0,
                                                  num_queries=500))
    assert min(min(q) for q in queries) >= 20


@pytest.mark.parametrize("over,words", [
    ({"query_law": "zipf"}, ["unknown query_law [zipf]", "band", "stopmix"]),
    ({"query_stop_share": None}, ["query_law [stopmix]", "query_stop_share"]),
    ({"query_terms_mean": None}, ["query_law [stopmix]", "query_terms_mean"]),
    ({"query_law": "band", "query_band_hi": None}, ["query_law [band]", "query_band_hi"]),
    ({"query_band_lo": 0}, ["query_law [stopmix]", "query_band_lo"]),
    ({"query_stop_share": 1.5}, ["query_law [stopmix]", "query_stop_share"]),
])
def test_an_unknown_law_or_a_missing_key_fails_by_name(over, words):
    gen = {k: v for k, v in stopmix_gen(**over).items() if v is not None}
    with pytest.raises(ValueError) as exc:
        corpus.generate_queries(gen)
    assert all(w in str(exc.value) for w in words), str(exc.value)


# ---------------------------------------------------------------------------
# the reference: operator `and`, and the sum at a stop-word's weight
# ---------------------------------------------------------------------------

def sum_by_sort(docs: np.ndarray, scores: np.ndarray):
    """The parent's routine, kept as the oracle."""
    uniq, inv = np.unique(docs, return_inverse=True)
    return uniq, np.bincount(inv, weights=scores, minlength=uniq.shape[0])


@pytest.mark.parametrize("postings", [300, 2496, 2502, 6000, 30000])
def test_the_dense_sum_equals_the_sort_to_the_bit(postings):
    """On both sides of the switch (a quarter of 10,000 docs), in the
    order `reference_topk` hands postings over: term after term, docs
    ascending within a term."""
    rng = np.random.default_rng(postings)
    n_docs, n_terms = 10_000, 6
    parts = [np.sort(rng.choice(n_docs, size=postings // n_terms, replace=False))
             for _ in range(n_terms)]
    docs = np.concatenate(parts)
    scores = rng.random(docs.shape[0]) * 10.0 ** rng.integers(-3, 4, docs.shape[0])
    assert docs.shape[0] == postings and reference.DENSE_SHARE == 4
    want_docs, want_sums = sum_by_sort(docs, scores)
    got_docs, got_sums = reference.sum_by_doc(docs, scores, n_docs)
    assert np.array_equal(got_docs, want_docs)
    assert got_sums.tobytes() == want_sums.tobytes()
    # `held`: the docs that every term's postings hold
    held_docs, held_sums = reference.sum_by_doc(docs, scores, n_docs, held=n_terms)
    every = np.flatnonzero(np.bincount(docs, minlength=n_docs) == n_terms)
    assert np.array_equal(held_docs, every)
    assert held_sums.tobytes() == want_sums[np.isin(want_docs, every)].tobytes()


TOY = {"docs": 3000, "vocab_size": 400, "zipf_s": 1.07, "mean_length": 12,
       "corpus_seed": 5}


@pytest.fixture(scope="module")
def toy():
    corp = corpus.generate_corpus(TOY)
    return corp, reference.build_shard_indexes(corp.flat, corp.offsets, 2,
                                               list(range(60)))


def brute_force(corp: corpus.Corpus, shards: int, terms, operator: str):
    """Dense BM25, a doc at a time: → {doc: f32 score} of the matching docs."""
    shard_of = reference.shard_of_digit_ids(np.arange(corp.num_docs), shards)
    dl = reference.quantized_lengths(corp.lengths)
    out = {}
    for s in range(shards):
        mine = np.flatnonzero(shard_of == s)
        avgdl = float(corp.lengths[mine].sum()) / mine.shape[0]
        tf = np.array([[int((corp.doc_words(i) == t).sum()) for t in terms]
                       for i in mine.tolist()])
        df = (tf > 0).sum(axis=0)
        for row, i in zip(tf, mine.tolist()):
            held = row > 0
            if not (held.all() if operator == "and" else held.any()):
                continue
            denom = float(np.float32(reference.K1 * (
                1 - reference.B + reference.B * float(dl[i]) / avgdl)))
            score = 0.0
            for f, n in zip(row.tolist(), df.tolist()):
                if f:
                    score += (reference.bm25_idf(mine.shape[0], n)
                              * (reference.K1 + 1) * f / (f + denom))
            out[i] = np.float32(score)
    return out


@pytest.mark.parametrize("terms", [[0, 1], [2, 7, 11], [1, 30, 45], [3, 55]])
@pytest.mark.parametrize("operator", ["or", "and"])
def test_the_reference_equals_brute_force_dense_bm25_in_two_shards(toy, terms, operator):
    corp, shards = toy
    want = brute_force(corp, 2, terms, operator)
    total, docs, scores = reference.reference_topk(shards, terms, 50, operator)
    assert total == len(want) and (operator == "or" or total < len(
        brute_force(corp, 2, terms, "or")))
    assert total > 0
    best = sorted(want.items(), key=lambda kv: (-float(kv[1]), kv[0]))
    assert docs.shape[0] >= min(50, total)
    for (doc, score), d, s in zip(best, docs.tolist(), scores.tolist()):
        assert abs(s - float(score)) <= 1e-6 * float(score)
        assert d == doc or abs(float(want[d]) - float(score)) <= 1e-5 * float(score)


def test_and_with_a_term_missing_from_one_shard_leaves_that_shard_empty(toy):
    corp, shards = toy
    # a word that only one shard holds: the rarest of the first 400 ranks
    held = [[t for t in range(60) if sh.postings[t][0].shape[0]] for sh in shards]
    only_0 = [t for t in held[0] if t not in held[1]]
    if not only_0:  # plant one: drop a rare term's postings from shard 1
        t = min(range(40, 60), key=lambda t: shards[1].postings[t][0].shape[0])
        shards = [shards[0], reference.ShardIndex(
            shards[1].doc_count, shards[1].avgdl, shards[1].denom_add,
            {**shards[1].postings, t: (np.empty(0, np.int64), np.empty(0, np.int64))})]
        only_0 = [t]
    rare, common = only_0[0], 0
    total, docs, _ = reference.reference_topk(shards, [common, rare], 1000, "and")
    in_0 = set(shards[0].postings[rare][0].tolist()) & set(
        shards[0].postings[common][0].tolist())
    assert total == len(in_0) > 0 and set(docs.tolist()) == in_0
    # under `or` the other shard's docs that hold the common word are hits
    assert reference.reference_topk(shards, [common, rare], 1000, "or")[0] > total
    # a term no shard holds: nothing, and no error
    assert reference.reference_topk(shards, [common, 399_999], 10, "and")[0] == 0


def test_an_operator_without_a_reference_is_refused_by_name():
    with pytest.raises(ValueError, match=r"unknown operator \[xor\]"):
        reference.reference_topk([], [1], 10, "xor")
    with pytest.raises(ValueError, match=r"no reference for operator \[xor\]"):
        reference.stored_name("xor")
    assert reference.stored_name("or") == "reference.npz"      # the name it had
    assert reference.stored_name("and") == "reference-and.npz"


# ---------------------------------------------------------------------------
# a stratum's upper edge
# ---------------------------------------------------------------------------

def test_a_stratum_between_two_edges():
    postings = np.array([10, 65_535, 65_536, 100_000, 131_071, 131_072, 600_000])
    n_terms = np.full(7, 3)
    spec = {"warm_clients": [[1, 2]], "warm_strata": [
        {"name": "s16", "postings_max": 65_536},
        {"name": "s32", "postings_min": 65_536, "postings_max": 131_072},
        {"name": "s128", "postings_min": 131_072, "postings_max": 524_288},
        {"name": "hot", "postings_min": 524_288},
        {"name": "none", "postings_min": 200_000, "postings_max": 300_000}]}
    strata = {name: sorted(idx.tolist())
              for name, idx, _phases in traffic.warm_strata(spec, postings, n_terms)}
    assert strata == {"s16": [0, 1], "s32": [2, 3, 4], "s128": [5], "hot": [6]}


# ---------------------------------------------------------------------------
# run.py: the reference of the traffic's operator, or no run
# ---------------------------------------------------------------------------

def scratch_bench(tmp_path, operator: str) -> str:
    """BENCHMARK.json + a traffic file that differs from the committed one
    in its operator alone: what a later PR adds, and no more."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "or1000-closed384.json"), "r",
              encoding="utf-8") as f:
        spec = json.load(f)
    spec["operator"] = operator
    with open(tmp_path / f"{operator}1000-closed384.json", "w", encoding="utf-8") as f:
        json.dump(spec, f)
    bench["workloads"].append({
        "name": f"msmarco-1chip.{operator}1000-closed384", "config": "msmarco-1chip",
        "traffic": f"{operator}1000-closed384", "chips": 1, "why": "scratch"})
    path = tmp_path / "BENCHMARK.json"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return str(path)


def test_a_traffic_operator_that_has_no_reference_fails_the_run_by_name(tmp_path):
    with pytest.raises(run.BenchFailure, match=r"operator \[phrase\].*no reference"):
        run.load_cell("msmarco-1chip.phrase1000-closed384",
                      scratch_bench(tmp_path, "phrase"), str(tmp_path))
    loaded = run.load_cell("msmarco-1chip.and1000-closed384",
                           scratch_bench(tmp_path, "and"), str(tmp_path))
    assert json.loads(traffic.request_body("w1 w2", loaded["traffic"], "body")) == {
        "query": {"match": {"body": {"query": "w1 w2", "operator": "and"}}},
        "size": 1000, "_source": False}


def test_a_missing_stored_reference_is_built_or_the_run_fails_never_the_or_one(
        tmp_path, monkeypatch):
    """An index directory with the `or` reference alone: `and` is added by
    a child from the directory's own `config.json`; where that fails the
    run fails, and `reference.npz` is not what it is handed."""
    gen = {**TOY, "num_queries": 30, "query_terms_min": 2, "query_terms_max": 3,
           "query_band_lo": 0, "query_band_hi": 60}
    config = {"name": "toy", "generator": gen, "index": {"number_of_shards": 2}}
    corp, queries = corpus.generate_corpus(gen), corpus.generate_queries(gen)
    build_index.write_reference(str(tmp_path), corp, queries, 2, "or", with_strata=True)
    assert run.ensure_reference(str(tmp_path), "or") == (
        str(tmp_path / "reference.npz"), 0.0)
    # no config.json, no manifest: the child fails, and so does the run
    with pytest.raises(run.BenchFailure, match=r"no reference of operator \[and\]"):
        run.ensure_reference(str(tmp_path), "and")
    with open(tmp_path / "config.json", "w", encoding="utf-8") as f:
        json.dump(config, f)
    with open(tmp_path / "manifest.json", "w", encoding="utf-8") as f:
        json.dump({"docs": corp.num_docs, "tokens": int(corp.flat.shape[0]),
                   "queries": len(queries), "shards": 2}, f)
    path, seconds = run.ensure_reference(str(tmp_path), "and")
    assert path == str(tmp_path / "reference-and.npz") and seconds > 0
    assert run.ensure_reference(str(tmp_path), "and") == (path, 0.0)   # once
    both = {op: np.load(tmp_path / reference.stored_name(op)) for op in ("or", "and")}
    assert set(both["and"].files) == set(both["or"].files)
    assert (both["and"]["totals"] <= both["or"]["totals"]).all()
    assert (both["and"]["totals"] < both["or"]["totals"]).any()
    shards = reference.build_shard_indexes(corp.flat, corp.offsets, 2, list(range(60)))
    for i, q in enumerate(queries):
        total, docs, scores = reference.reference_topk(shards, q, 1000, "and")
        lo, hi = both["and"]["offsets"][i:i + 2]
        assert total == both["and"]["totals"][i]
        assert np.array_equal(both["and"]["docs"][lo:hi], docs)
        assert both["and"]["scores"][lo:hi].tobytes() == scores.tobytes()
    # a directory that another configuration built is refused
    with open(tmp_path / "manifest.json", "w", encoding="utf-8") as f:
        json.dump({"docs": 7, "tokens": 70, "queries": 30, "shards": 2}, f)
    os.remove(path)
    with pytest.raises(run.BenchFailure, match=r"no reference of operator \[and\]"):
        run.ensure_reference(str(tmp_path), "and")


def test_an_and_answer_held_to_the_or_reference_is_not_correct(toy):
    """What `check_samples` would have said of every AND run before there
    was a reference an operator."""
    corp, shards = toy
    terms = [2, 7, 11]
    t_and, d_and, s_and = reference.reference_topk(shards, terms, 1000, "and")
    t_or, d_or, s_or = reference.reference_topk(shards, terms, 1000, "or")
    resp = {"timed_out": False, "_shards": {"failed": 0},
            "hits": {"total": {"value": t_and, "relation": "eq"},
                     "hits": [{"_id": corpus.doc_id(d), "_score": float(s)}
                              for d, s in zip(d_and.tolist(), s_and.tolist())]}}
    ids = [corpus.doc_id(d) for d in d_and.tolist()]
    assert compare.compare_response(resp, t_and, ids, s_and.tolist(), 1000) == 0
    with pytest.raises(compare.Mismatch):
        compare.compare_response(resp, t_or, [corpus.doc_id(d) for d in d_or.tolist()],
                                 s_or.tolist(), 1000)


# ---------------------------------------------------------------------------
# served: `operator: and` over REST, held to the AND reference
# ---------------------------------------------------------------------------

SERVED = {"docs": 4000, "vocab_size": 30000, "zipf_s": 1.07, "mean_length": 55,
          "corpus_seed": 23, "num_queries": 60, "query_terms_min": 2,
          "query_terms_max": 4, "query_band_lo": 0, "query_band_hi": 60}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A toy node of the standing deployment's shape (2 shards, passages
    of mean 55 words) with queries of 2-4 of the 60 commonest words, so
    that an intersection holds docs; indexed through REST `_bulk`."""
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node, serve

    corp, queries = corpus.generate_corpus(SERVED), corpus.generate_queries(SERVED)
    node = Node(str(tmp_path_factory.mktemp("and")), settings=Settings.of({}))
    server = serve(node, port=0)
    conn = http.client.HTTPConnection("127.0.0.1", server.server_address[1],
                                       timeout=300)
    try:
        build_index.http_json(conn, "PUT", "/bench", {
            "settings": {"index": {"number_of_shards": 2}},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        build_index.bulk_all(server.server_address[1], corp)
        build_index.http_json(conn, "POST", "/bench/_refresh")
        yield {"conn": conn, "queries": queries, "shards": reference.build_shard_indexes(
            corp.flat, corp.offsets, 2, sorted({t for q in queries for t in q}))}
    finally:
        conn.close()
        server.shutdown()
        server.server_close()
        node.close()


@pytest.mark.parametrize("size", [1000, 10])
def test_operator_and_over_rest_equals_the_and_reference(served, size):
    """The first test that holds the program's `exact_min_count` route to a
    reference that imports nothing of the program."""
    conn, spec = served["conn"], {"size": size, "operator": "and", "source": False}
    before = run.get_stats(conn)
    gap, hits = 0.0, 0
    for q in served["queries"]:
        total, docs, scores = reference.reference_topk(served["shards"], q, size, "and")
        resp = build_index.http_json(conn, "POST", "/bench/_search", traffic.request_body(
            corpus.query_text(q), spec, "body"))
        compare.compare_response(resp, total, [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), size)
        assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
        gap = max(gap, compare.score_gap(resp, scores.tolist()))
        hits += total
    after = run.get_stats(conn)
    assert hits > 0 and gap < compare.REL_TOL
    assert after["fallback"] == before["fallback"]
    assert after["served"] - before["served"] == len(served["queries"])
    route = {r: after["route"][r] - before["route"][r] for r in after["route"]}
    assert route.pop("exact_min_count") == len(served["queries"])
    assert not any(route.values()), route
