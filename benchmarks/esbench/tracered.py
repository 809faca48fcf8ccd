"""Reduction of a profiler trace (`*.xplane.pb`) to device facts.

Reads the trace with `jax.profiler.ProfileData` and nothing else. A device
plane is one whose name matches `/device:TPU:<n>`; on it the line
`XLA Ops` carries one event per executed HLO op (nested for `while` and
the like) and `XLA Modules` one event per executed program. Busy time is
the union of the op intervals, so nesting and overlap count once; an op's
own seconds are its duration less that of the events nested in it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]  # start, end in ns


def union_ns(intervals: Sequence[Interval]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Tuple[float, float, str]], lo: float,
            hi: float) -> List[Tuple[float, float, str]]:
    """Idle gaps of the union inside [lo, hi) → (start, end, name of the op
    that ended last before the gap)."""
    out, cur_e, last = [], lo, "window_start"
    for s, e, name in sorted(intervals):
        if s > cur_e:
            out.append((cur_e, min(s, hi), last))
        if e > cur_e:
            cur_e, last = e, name
    if hi > cur_e:
        out.append((cur_e, hi, last))
    return [(s, e, n) for s, e, n in out if e > s]


def self_seconds(events: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per-name seconds with nested events' time taken out of their parent."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [end, name, own_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def short_name(name: str, limit: int = 96) -> str:
    """An HLO op text → `%name = op(shape...` cut to something a ledger
    line can carry."""
    name = name.split(" metadata=")[0].split(", metadata")[0]
    return name[:limit]


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load_device_events(path: str) -> Dict[str, Dict[str, List[Tuple[float, float, str]]]]:
    """→ {plane name: {line name: [(start_ns, end_ns, event name)]}} for
    the device planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Tuple[float, float, str]]]] = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            # sorted once here: the reductions' own sorts then run in linear time
            lines[line.name] = sorted(
                (float(ev.start_ns), float(ev.start_ns + ev.duration_ns), str(ev.name))
                for ev in line.events)
    return out


def describe(path: str) -> List[str]:
    """Planes, lines and event counts of a trace: what to look at by hand
    before trusting the reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            first = events[0] if events else None
            rows.append(f"{plane.name} | {line.name} | {len(events)} events"
                        + (f" | first start_ns={first.start_ns} name={str(first.name)[:120]}"
                           if first else ""))
    return rows


def reduce_trace(path: str, pauses: Sequence[Tuple[float, float, str]] = ()
                 ) -> Optional[Dict[str, Any]]:
    """→ busy_s, window_s (averaged over device planes), op seconds, op
    counts, module seconds and the ten longest idle gaps, each named by
    the first host pause (`pauses`: start_ns, end_ns, name on the trace's
    clock, in the order they are to be tried) that covers most of it,
    else `unattributed`."""
    planes = load_device_events(path)
    planes = {n: l for n, l in planes.items() if l.get(OPS_LINE)}
    if not planes:
        return None
    busy, window = [], []
    op_seconds: Dict[str, float] = {}
    op_counts: Dict[str, int] = {}
    module_seconds: Dict[str, float] = {}
    module_counts: Dict[str, int] = {}
    gaps: List[Tuple[float, float, str]] = []
    lo = min(ev[0] for l in planes.values() for ev in l[OPS_LINE])
    hi = max(ev[1] for l in planes.values() for ev in l[OPS_LINE])
    for lines in planes.values():
        ops = lines[OPS_LINE]
        busy.append(union_ns([(s, e) for s, e, _n in ops]) / 1e9)
        window.append((hi - lo) / 1e9)
        for name, secs in self_seconds(ops).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + secs
        for _s, _e, name in ops:
            op_counts[name] = op_counts.get(name, 0) + 1
        for s, e, name in lines.get(MODULES_LINE, []):
            module_seconds[name] = module_seconds.get(name, 0.0) + (e - s) / 1e9
            module_counts[name] = module_counts.get(name, 0) + 1
        gaps.extend(gaps_ns(ops, lo, hi))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]

    def cause(s: float, e: float) -> str:
        return next((name for ps, pe, name in pauses
                     if min(e, pe) - max(s, ps) > 0.5 * (e - s)), "unattributed")

    n = len(planes)
    return {
        "busy_s": sum(busy) / n, "window_s": sum(window) / n,
        "t_lo_ns": lo, "t_hi_ns": hi, "device_planes": n,
        "op_seconds": {k: v / n for k, v in op_seconds.items()},
        "op_counts": op_counts,
        "module_seconds": {k: v / n for k, v in module_seconds.items()},
        "module_counts": module_counts,
        "idle_gaps": [((e - s) / 1e9, f"{cause(s, e)} after {short_name(after, 48)}")
                      for s, e, after in longest],
    }
