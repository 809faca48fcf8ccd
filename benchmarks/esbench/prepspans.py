"""The launch thread's `prep.*` spans of a profiler trace, laid against
the device.

The program nests three `tracing.stage` annotations in the launch
thread's `batcher.prep` state: `prep.route` (the train's routing and
split), `prep.query_batch` (`prepare_query_batch` and its pin padding, a
launch) and `prep.term_ranges` (`prepare_term_ranges` +
`pack_pruned_operands`, a pruned launch). `hostspans` reads only names
that start with `batcher.`, `completer.`, `gc.full` and `Wait for`, so
it does not see them; this module reads them beside the states they
nest in, from the same `.xplane.pb`, with `hostspans`' interval
arithmetic, and counts device idle time as `hostspans.attribute` does:
what `gc.full` and the dispatch states (`PRECEDENCE` before
`batcher.prep`) cover is theirs, the rest under `batcher.prep` is
`idle_under_prep_pct`'s, and the part of that under an operand span is
this module's. So it is never more than `idle_under_prep_pct`.
"""

from __future__ import annotations

import os
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

from esbench import hostspans, tracered

PREP = "batcher.prep"
OPERANDS = ("prep.query_batch", "prep.term_ranges")
#: the names that take an idle instant before `batcher.prep` does
BEFORE_PREP = hostspans.PRECEDENCE[:hostspans.PRECEDENCE.index(PREP)]
NAMES = frozenset(BEFORE_PREP + (PREP,) + OPERANDS)

Event = Tuple[float, float, str]


def operand_idle_s(host_lines: Sequence[Sequence[Event]],
                   device_planes: Dict[str, Dict[str, List[Event]]]
                   ) -> Optional[Dict[str, float]]:
    """→ {"window_s", "prep_idle_s", "operands_idle_s"} (idle seconds a
    device plane, averaged over the planes), or None without a device
    plane or without an operand span."""
    planes = {n: l for n, l in device_planes.items() if l.get(tracered.OPS_LINE)}
    spans = [ev for line in host_lines for ev in line if ev[2] in NAMES]
    if not planes or not any(n in OPERANDS for _s, _e, n in spans):
        return None

    def cover(names):
        return hostspans.merge([(s, e) for s, e, n in spans if n in names])

    before, prep, operands = cover(BEFORE_PREP), cover((PREP,)), cover(OPERANDS)
    lo = min(ev[0] for l in planes.values() for ev in l[tracered.OPS_LINE])
    hi = max(ev[1] for l in planes.values() for ev in l[tracered.OPS_LINE])
    prep_ns = operands_ns = 0.0
    for lines in planes.values():
        idle = hostspans.idle_intervals(lines[tracered.OPS_LINE], lo, hi)
        under_prep = hostspans.intersect(hostspans.subtract(idle, before), prep)
        prep_ns += hostspans.total(under_prep)
        operands_ns += hostspans.total(hostspans.intersect(under_prep, operands))
    n = len(planes)
    return {"window_s": (hi - lo) / 1e9, "prep_idle_s": prep_ns / 1e9 / n,
            "operands_idle_s": operands_ns / 1e9 / n}


@lru_cache(maxsize=2)
def _read(path: str, _mtime: float) -> Optional[Dict[str, float]]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: List[List[Event]] = []
    device: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if hostspans.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                events = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                                 str(ev.name))
                                for ev in line.events if str(ev.name) in NAMES)
                if events:
                    host.append(events)
        elif tracered.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == tracered.OPS_LINE:
                    device.setdefault(plane.name, {})[line.name] = sorted(
                        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                         str(ev.name)) for ev in line.events)
    return operand_idle_s(host, device)


def idle_under_operands_pct(facts: Dict[str, float],
                            run_dir: Optional[str] = None) -> Optional[float]:
    """% of the traced window; None where the run reduced no trace
    (`trace.window_s` absent), left none, or its trace has no operand span."""
    if "trace.window_s" not in facts:
        return None
    path = tracered.newest_xplane(run_dir or hostspans.RUN_DIR)
    if not path:
        return None
    out = _read(path, os.path.getmtime(path))
    if out is None or out["window_s"] <= 0:
        return None
    return 100.0 * out["operands_idle_s"] / out["window_s"]
