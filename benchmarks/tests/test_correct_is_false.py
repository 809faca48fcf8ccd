"""`correct` has to come out false when it should: the control, and a whole
run with the timed path broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_correct_is_false.py -q -p no:cacheprovider

The control is the reference put in the program's place and computed one
precision down: the configuration states float32 BM25 scores held to 1e-5
relative, so the control's scores are the reference's rounded to bfloat16
and ranked again. The broken runs skip the look for a chip (`--rehearse`)
and drive everything else of `run.py` in this process, with one answer
altered where the program decodes the kernel's output.
"""

from __future__ import annotations

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

sys.path.insert(2, os.path.join(BENCH_DIR, "tools"))

from esbench import compare, corpus, reference  # noqa: E402
from control import response, to_bfloat16  # noqa: E402

GEN = {"docs": 4000, "vocab_size": 3000, "zipf_s": 1.07, "mean_length": 55,
       "corpus_seed": 23, "num_queries": 64, "query_terms_min": 2,
       "query_terms_max": 5, "query_band_lo": 20, "query_band_hi": 3000}
K = 100


@pytest.fixture(scope="module")
def toy():
    corp = corpus.generate_corpus(GEN)
    queries = corpus.generate_queries(GEN)
    terms = sorted({t for q in queries for t in q})
    shards = reference.build_shard_indexes(corp.flat, corp.offsets, 2, terms)
    return [(q, *reference.reference_topk(shards, q, K)) for q in queries]


def held(resp, total, docs, scores):
    try:
        compare.compare_response(resp, total, [corpus.doc_id(d) for d in docs.tolist()],
                                 scores.tolist(), K)
        return True
    except compare.Mismatch:
        return False


def test_the_reference_in_the_programs_place_is_correct(toy):
    gaps = []
    for _q, total, docs, scores in toy:
        resp = response(docs[:K].tolist(), scores[:K].tolist(), total)
        assert held(resp, total, docs, scores)
        gaps.append(compare.score_gap(resp, scores.tolist()))
    assert max(gaps) == 0.0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_in_bfloat16_is_not_correct(toy, seed):
    """The lower reading is float32 rounding (the chip's runs read 1e-7 to
    4e-7, PERF.md); the control's has to lie three times above the limit
    or more, in every sample of queries."""
    pick = np.random.default_rng(seed).choice(len(toy), size=32, replace=False)
    gaps, passed = [], 0
    for i in pick.tolist():
        _q, total, docs, scores = toy[i]
        low = to_bfloat16(scores)
        order = np.lexsort((docs, -low))[:K]
        resp = response(docs[order].tolist(), low[order].tolist(), total)
        gaps.append(compare.score_gap(resp, scores.tolist()))
        passed += held(resp, total, docs, scores)
    assert min(gaps) > 3 * compare.REL_TOL and max(gaps) < 2 ** -8
    assert passed == 0


# ---------------------------------------------------------------------------
# a whole run, the timed path broken underneath
# ---------------------------------------------------------------------------

def _scores_off_by_ten_limits(result):
    result.scores = result.scores * np.float32(1 + 10 * compare.REL_TOL)


def _best_hit_replaced(result):
    if len(result.ords) > 1:
        result.ords = result.ords.copy()
        result.rows = result.rows.copy()
        result.ords[0], result.rows[0] = result.ords[-1], result.rows[-1]


@pytest.mark.parametrize("fault, number", [
    (None, None),
    (_scores_off_by_ten_limits, "score_rel_gap_max"),
    (_best_hit_replaced, "responses_differing"),
])
def test_a_run_over_a_broken_decode_is_not_correct(fault, number, monkeypatch, capsys):
    import run
    from elasticsearch_tpu.search import tpu_service
    real = tpu_service._columnar_results

    def broken(*args, **kwargs):
        results = real(*args, **kwargs)
        for result in results:
            fault(result)
        return results

    if fault is not None:
        monkeypatch.setattr(tpu_service, "_columnar_results", broken)
    with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as f:
        cell = json.load(f)["workloads"][0]["name"]
    assert run.main(["--workload", cell, "--seed", str(2 ** 31 + 77), "--seconds", "2",
                     "--trace", "0", "--rehearse"]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    last = captured.err.strip().splitlines()[-len(line["compared"]):]
    assert all(row.startswith("compared ") for row in last)
    if fault is None:
        assert line["correct"] is True
        assert all(c["value"] <= c["limit"] if c["limit_is"] == "at_most"
                   else c["value"] >= c["limit"] for c in line["compared"].values())
        return
    assert line["correct"] is False
    assert line["compared"][number]["value"] > line["compared"][number]["limit"]
    assert line["compared"]["responses_differing"]["value"] \
        == line["compared"]["responses_sampled"]["value"] > 0
    assert "NOT CORRECT" in captured.err
