"""The one general traffic generator: a mix is a data file, read here.

A traffic file (`benchmarks/traffic/<name>.json`) gives the loop
(`closed` with `clients`, or `open` with `rate_per_s` and an arrival
law), the request (`size`, `operator`, `source`), the timing (`ramp_s`,
`drain_s`, `trace_s`) and how the node is warmed. Everything below is a
function of those numbers and the seed alone, so every seed sends the
same set of queries and, in an open loop, the same set of gaps, in
another order. stdlib + numpy only.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import numpy as np

LOOPS = ("closed", "open")


def load_traffic(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        spec = json.load(f)
    if spec.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    if spec["loop"] == "closed" and int(spec.get("clients", 0)) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if spec["loop"] == "open" and float(spec.get("rate_per_s", 0)) <= 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    return spec


def request_body(text: str, spec: Dict[str, Any], field: str) -> bytes:
    operator = spec.get("operator", "or")
    query: Any = text if operator == "or" else {"query": text,
                                                "operator": operator}
    return json.dumps({"query": {"match": {field: query}},
                       "size": int(spec["size"]),
                       "_source": bool(spec.get("source", False))}
                      ).encode("utf-8")


def query_order(seed: int, n_queries: int) -> np.ndarray:
    """The seeded permutation of the query set; the stream cycles it."""
    return np.random.default_rng([int(seed), 2]).permutation(n_queries)


def closed_query(order: np.ndarray, client: int, j: int, clients: int) -> int:
    """Query of closed-loop client `client`'s j-th request: position
    client + j·clients of the cycled permutation."""
    return int(order[(client + j * clients) % order.shape[0]])


def open_gaps(seed: int, rate_per_s: float, n: int, law: str) -> np.ndarray:
    """n inter-arrival gaps in seconds. `poisson`: the n quantiles
    (i + ½)/n of the exponential law, which is the same set for every
    seed, in the seed's order."""
    if law != "poisson":
        raise ValueError(f"unknown arrival law [{law}]")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    return np.random.default_rng([int(seed), 3]).permutation(gaps)


def open_schedule(seed: int, spec: Dict[str, Any], total_s: float,
                  n_queries: int) -> Tuple[np.ndarray, np.ndarray]:
    """→ (due offsets in ns from the start of the ramp, query indices) for
    every arrival of an open loop that runs `total_s`."""
    rate = float(spec["rate_per_s"])
    n = int(np.ceil(rate * total_s))
    gaps = open_gaps(seed, rate, n, spec.get("arrivals", "poisson"))
    due = np.cumsum(gaps) - gaps[0]
    order = query_order(seed, n_queries)
    return (due * 1e9).astype(np.int64), order[np.arange(n) % n_queries]


def warm_strata(spec: Dict[str, Any], postings: np.ndarray,
                n_terms: np.ndarray
                ) -> List[Tuple[str, np.ndarray, List[Tuple[int, int]]]]:
    """The warm-up's phases → (stratum, its queries, [(clients, requests
    per client)]). A stratum is a slice of the query set by `postings`
    (the heaviest shard's postings under the query's terms: by rank with
    `postings_from`/`postings_to`, by value with `postings_min` ≤ p <
    `postings_max`, the edges of a rung of the launch ladder) or by
    number of terms, driven alone in a closed loop at each client count.
    The mix reaches some launch shapes only now and then (a batch of
    nothing but wide queries, a lone 12-term query); the extremes alone
    reach them every time. Within a stratum the heaviest and lightest
    queries come first, alternating. An empty stratum is skipped, so one
    traffic file serves configurations whose queries differ."""
    rank = np.argsort(np.argsort(postings, kind="stable"), kind="stable")
    frac = (rank + 0.5) / max(1, postings.shape[0])
    default = [(int(c), int(r)) for c, r in spec.get("warm_clients", [[1, 3]])]
    out = []
    for st in spec.get("warm_strata", [{"name": "all"}]):
        keep = np.ones(postings.shape[0], dtype=bool)
        if "postings_from" in st:
            keep &= frac >= float(st["postings_from"])
        if "postings_to" in st:
            keep &= frac < float(st["postings_to"])
        if "postings_min" in st:
            keep &= postings >= int(st["postings_min"])
        if "postings_max" in st:
            keep &= postings < int(st["postings_max"])
        if "terms_min" in st:
            keep &= n_terms >= int(st["terms_min"])
        if "terms_max" in st:
            keep &= n_terms <= int(st["terms_max"])
        idx = np.flatnonzero(keep)
        if not idx.shape[0]:
            continue
        by_weight = idx[np.argsort(-postings[idx], kind="stable")]
        ends = np.empty_like(by_weight)
        half = (by_weight.shape[0] + 1) // 2
        ends[0::2] = by_weight[:half]
        ends[1::2] = by_weight[::-1][:by_weight.shape[0] - half]
        clients = [(int(c), int(r)) for c, r in st["clients"]] \
            if "clients" in st else default
        out.append((str(st["name"]), ends, clients))
    return out
