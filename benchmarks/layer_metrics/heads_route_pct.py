"""Queries that the launch routing sent to the heads route, % of all it
routed over the window: `route.heads` / every `route.*` of `/_tpu/stats`
(an escalated query was counted under its first route, so
`exact_escalated` is left out of the denominator). A program without
the route gives nothing."""

PREFIX = "window.route."


def read(facts):
    routed = {key[len(PREFIX):]: n for key, n in facts.items()
              if key.startswith(PREFIX)}
    queries = sum(routed.values()) - routed.get("exact_escalated", 0.0)
    if "heads" not in routed or queries <= 0:
        return None
    return 100.0 * routed["heads"] / queries
