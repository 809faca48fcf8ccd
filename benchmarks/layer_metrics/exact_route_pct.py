"""Queries that the launch routing sent to the exact kernel, % of all it
routed over the window: `route.exact_*` (more than PRUNE_MAX_TERMS terms,
AND/msm, k past the pruned path's, no impact arrays, escalated) / every
`route.*` of `/_tpu/stats`. An escalated query was counted under its
pruned route first, so it is in the numerator and once in the
denominator. A program without the counter gives nothing."""

PREFIX = "window.route."


def read(facts):
    routed = {key[len(PREFIX):]: n for key, n in facts.items()
              if key.startswith(PREFIX)}
    queries = sum(routed.values()) - routed.get("exact_escalated", 0.0)
    if queries <= 0:
        return None
    exact = sum(n for route, n in routed.items() if route.startswith("exact_"))
    return 100.0 * exact / queries
