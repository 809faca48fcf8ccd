"""The control of `correct`: the reference put in the program's place, one
precision down.

The configuration states float32 BM25 scores held to 1e-5 relative, so the
control's scores are the stored reference's rounded to bfloat16 and ranked
again (ties by doc id), rendered as the `_search` responses the node would
have sent. The run's own `check_samples` has to call them not correct: here
at the cell's own size, for the 256 queries that a run of each seed samples,

    python3 benchmarks/tools/control.py <workload> <seed> [<seed> ...]

and in `tests/test_correct_is_false.py` at a toy size. Needs the
configuration's built index with the reference of the traffic's operator
(any run of the cell in this checkout leaves both) and no chip. Prints one
line a seed: the reference in the program's place (has to be correct),
then the control (has not to be).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Sequence

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from esbench import compare, corpus, reference  # noqa: E402


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 → the nearest bfloat16 (ties to even), as float32."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def response(docs: Sequence[int], scores: Sequence[float], total: int) -> Dict[str, Any]:
    return {"timed_out": False, "_shards": {"total": 1, "successful": 1, "failed": 0},
            "hits": {"total": {"value": int(total), "relation": "eq"},
                     "hits": [{"_id": corpus.doc_id(d), "_score": float(s)}
                              for d, s in zip(docs, scores)]}}


def samples(ref: Any, queries: Sequence[int], k: int, lowered: bool) -> Dict[int, bytes]:
    """The stored reference (that of the traffic's operator) of `queries`
    as response bodies: as it stands, or `lowered` to bfloat16 and ranked again."""
    out = {}
    for q in queries:
        lo, hi = int(ref["offsets"][q]), int(ref["offsets"][q + 1])
        docs, scores = ref["docs"][lo:hi], ref["scores"][lo:hi]
        if lowered:
            scores = to_bfloat16(scores)
            order = np.lexsort((docs, -scores))
            docs, scores = docs[order], scores[order]
        out[int(q)] = json.dumps(response(docs[:k].tolist(), scores[:k].tolist(),
                                          int(ref["totals"][q]))).encode("utf-8")
    return out


def main() -> int:
    import run
    loaded = run.load_cell(sys.argv[1])
    ref = np.load(os.path.join(
        run.index_dir_for(loaded["config"]),
        reference.stored_name(loaded["traffic"].get("operator", "or"))))
    k, n_queries = int(loaded["traffic"]["size"]), ref["totals"].shape[0]
    ok = True
    for seed in map(int, sys.argv[2:]):
        sample = run.sample_queries(seed, n_queries)
        for name, lowered in (("reference", False), ("control_bfloat16", True)):
            checked, _swaps, gap, bad, _bounds = run.check_samples(
                samples(ref, sample, k, lowered), ref, k)
            print(f"seed {seed} {name}: responses_sampled {checked} responses_differing "
                  f"{len(bad)} score_rel_gap_max {gap!r} limit {compare.REL_TOL!r}", flush=True)
            ok &= (len(bad) > 0 and gap > compare.REL_TOL) if lowered else not bad
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
