"""The comparison that decides `correct`, and the no-hidden-fallback check.

Both rules are copies of `chip_smoke.py`'s (PR 21), kept with the
benchmark. A response equals the reference when `hits.total` is equal
(or, for a lower-bound count, not above it), exactly min(k, total) hits
come back with no duplicate id, every score is within REL_TOL relative of
the reference score at its rank, and every id is the reference's at that
rank or a doc whose reference score is within REL_TOL of it (a near-tie
swap). stdlib + numpy only.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

REL_TOL = 1e-5


class Mismatch(Exception):
    """A response differs from the reference."""


def compare_response(resp: Dict[str, Any], total: int, ref_ids: Sequence[str],
                     ref_scores: Sequence[float], k: int) -> int:
    """Hold one `_search` response to the reference → number of near-tie
    swaps. `ref_ids`/`ref_scores` run best-first through the end of the
    near-tie at the cut. Raises Mismatch."""
    if resp.get("timed_out") or resp["_shards"]["failed"] != 0:
        raise Mismatch(f"shard failures or timeout: {resp['_shards']}")
    hits = resp["hits"]
    got_total = hits["total"]
    if got_total["relation"] == "eq":
        if got_total["value"] != total:
            raise Mismatch(f"hits.total {got_total} != reference {total}")
    elif got_total["value"] > total:
        raise Mismatch(f"hits.total {got_total} > reference {total}")
    served = hits["hits"]
    want = min(k, total)
    if len(served) != want:
        raise Mismatch(f"{len(served)} hits returned, reference has {want}")
    ids = [h["_id"] for h in served]
    if len(set(ids)) != len(ids):
        raise Mismatch("duplicate ids in hits")
    pos_of = {doc_id: j for j, doc_id in enumerate(ref_ids)}
    swaps = 0
    for i, hit in enumerate(served):
        r = float(ref_scores[i])
        tol = REL_TOL * abs(r)
        if abs(hit["_score"] - r) > tol:
            raise Mismatch(f"score at rank {i} is {hit['_score']!r}, "
                           f"reference {r!r}")
        if hit["_id"] != ref_ids[i]:
            j = pos_of.get(hit["_id"])
            if j is None or abs(float(ref_scores[j]) - r) > tol:
                raise Mismatch(f"id at rank {i} is {hit['_id']!r}, reference "
                               f"{ref_ids[i]!r} (not a near-tie: reference "
                               f"rank {j})")
            swaps += 1
    return swaps


def score_gap(resp: Dict[str, Any], ref_scores: Sequence[float]) -> float:
    """The widest relative gap between a served score and the reference's
    at its rank: the number `compare_response` holds to REL_TOL."""
    return max((abs(hit["_score"] - float(r)) / abs(float(r))
                for hit, r in zip(resp["hits"]["hits"], ref_scores) if float(r)),
               default=0.0)


def kernel_checks(before: Dict[str, Any], after: Dict[str, Any], sent: int,
                  chips: int, platform: str) -> List[Tuple[str, Any, Any]]:
    """`/_tpu/stats` before and after a stream of `sent` requests →
    (name, got, want) of everything that has to hold for every one of them
    to have been answered by the kernel on the full mesh of the expected
    platform."""
    dev = after["devices"]

    def delta(*path: str) -> Any:
        a, b = after, before
        for key in path:
            a, b = a[key], b[key]
        return a - b

    return [
        ("served", delta("served"), sent),
        ("fallback", delta("fallback"), 0),
        ("timeouts", delta("timeouts"), 0),
        ("tripped", after["tripped"], False),
        ("devices.platform", dev["platform"], platform),
        ("devices.mesh_devices", dev["mesh_devices"], chips),
        ("devices.mesh_devices_full", dev["mesh_devices_full"], chips),
        ("devices.degraded", dev["degraded"], None),
        ("devices.shed_packs", dev["shed_packs"], []),
        ("devices.health.quarantines",
         delta("devices", "health", "quarantines"), 0),
        ("watchdog.wedges", delta("watchdog", "wedges"), 0),
        ("supervision.state", after["supervision"]["state"], "serving"),
        ("supervision.recoveries", delta("supervision", "recoveries"), 0),
    ]


def failures(checks: Sequence[Tuple[str, Any, Any]]) -> List[str]:
    """→ what shows that not every request was answered by the kernel
    (empty = all were)."""
    return [f"{name}={got!r} (want {want!r})" for name, got, want in checks
            if got != want]
