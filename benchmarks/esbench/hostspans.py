"""The program's own spans of a profiler trace, laid against the device.

What it reads, and nothing else: the run's `.xplane.pb`
(`tracered.newest_xplane(<bench_out>/run)`: `run.py` empties that
directory before and after every run, and the per-layer readers run
before it is removed). On the host planes (`/host:*`) every line is one
thread; the serving pipeline writes `jax.profiler.TraceAnnotation`s there
(TraceMe level 1, which is what `run.py` records): the launch thread's
states `batcher.<state>`, the completer's `completer.<state>`, and
`gc.full` around a full collection on whatever thread ran it. The
runtime's own `Wait for ...` events (donation holds, buffers) nest in the
launch thread's `batcher.call`. The device planes are read through
`tracered.load_device_events`, so "idle" here is `tracered`'s idle: the
gaps of the union of the `XLA Ops` intervals inside [first op start, last
op end). Host and device planes of one file share one clock.

What it gives (`read_trace`, memoised per file):

  idle_s        device idle seconds by what the launch thread was doing
                at the time. One name per instant, by precedence:
                gc.full > call > put > lock > prep > take > blocked >
                hold > wait; idle that no state covers is `unattributed`.
                The parts sum to the idle seconds by construction.
  window_s      last op end minus first op start (as `tracered`)
  buffer_wait_s seconds of `Wait for ...` inside `batcher.call`
  modules       {program name: (seconds, launches)} from `XLA Modules`,
                the name without its `(fingerprint)` suffix

A trace with no device plane, or with none of the annotations (a parent
commit, a CPU rehearsal), gives None: the reader returns nothing and the
metric is left out of the line. The interval arithmetic is plain
functions over sorted (start, end) lists.
"""

from __future__ import annotations

import os
import re
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence, Tuple

from esbench import tracered

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN_DIR = os.path.join(ROOT, "bench_out", "run")

HOST_PLANE = re.compile(r"^/host:")
GC_FULL = "gc.full"
CALL = "batcher.call"
WAIT_FOR = "Wait for"
#: what names an instant of device idle time, first match wins
PRECEDENCE = (GC_FULL, CALL, "batcher.put", "batcher.lock", "batcher.prep",
              "batcher.take", "batcher.blocked", "batcher.hold", "batcher.wait")
UNATTRIBUTED = "unattributed"

Interval = Tuple[float, float]
Event = Tuple[float, float, str]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union → sorted, disjoint, non-empty intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Of two merged lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """`a` less `b`, both merged."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def idle_intervals(ops: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    """The gaps of the union of the op intervals inside [lo, hi)."""
    return subtract([(lo, hi)], merge([(s, e) for s, e, _n in ops]))


def attribute(idle: Sequence[Interval], spans: Sequence[Event],
              precedence: Sequence[str] = PRECEDENCE) -> Dict[str, float]:
    """Idle ns by the name of the span that covers them; where several
    do, the first of `precedence`; where none does, `unattributed`. A gap
    that spans two states is split between them."""
    left = merge(idle)
    out: Dict[str, float] = {}
    for name in precedence:
        cover = merge([(s, e) for s, e, n in spans if n == name])
        out[name] = total(intersect(left, cover))
        left = subtract(left, cover)
    out[UNATTRIBUTED] = total(left)
    return out


def nested_seconds(inner: Sequence[Interval], outer: Sequence[Interval]) -> float:
    return total(intersect(merge(inner), merge(outer))) / 1e9


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

def load_host_lines(path: str) -> List[List[Event]]:
    """→ one list of (start_ns, end_ns, name) per host thread, holding
    the program's annotations and the runtime's `Wait for ...` events
    (read once per file: `run.py` names the gaps, then the readers come)."""
    return _host_lines(path, os.path.getmtime(path))


@lru_cache(maxsize=2)
def _host_lines(path: str, _mtime: float) -> List[List[Event]]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(("batcher.", "completer.", GC_FULL, WAIT_FOR)):
                    events.append((float(ev.start_ns),
                                   float(ev.start_ns + ev.duration_ns), name))
            if events:
                lines.append(sorted(events))
    return lines


def pauses(path: str) -> List[Event]:
    """The states of `PRECEDENCE` as `tracered.reduce_trace` wants its
    pauses: on the trace's clock, in the order of precedence, named
    without the `batcher.` prefix (`gc.full`, `call`, ..., `hold`, `wait`)."""
    rank = {name: i for i, name in enumerate(PRECEDENCE)}
    spans = [ev for line in load_host_lines(path) for ev in line if ev[2] in rank]
    spans.sort(key=lambda ev: (rank[ev[2]], ev[0]))
    return [(s, e, name.removeprefix("batcher.")) for s, e, name in spans]


def module_name(event_name: str) -> str:
    """`jit_full_s32(1234567890)` → `jit_full_s32`."""
    return event_name.split("(", 1)[0]


def reduce_spans(host_lines: Sequence[Sequence[Event]],
                 device_planes: Dict[str, Dict[str, List[Event]]]
                 ) -> Optional[Dict[str, Any]]:
    planes = {n: l for n, l in device_planes.items() if l.get(tracered.OPS_LINE)}
    spans = [ev for line in host_lines for ev in line
             if ev[2] in PRECEDENCE]
    if not planes or not any(n.startswith("batcher.") for _s, _e, n in spans):
        return None
    lo = min(ev[0] for l in planes.values() for ev in l[tracered.OPS_LINE])
    hi = max(ev[1] for l in planes.values() for ev in l[tracered.OPS_LINE])
    idle_ns: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for lines in planes.values():
        parts = attribute(idle_intervals(lines[tracered.OPS_LINE], lo, hi), spans)
        for name, ns in parts.items():
            idle_ns[name] = idle_ns.get(name, 0.0) + ns
        for s, e, name in lines.get(tracered.MODULES_LINE, []):
            entry = modules.setdefault(module_name(name), [0.0, 0])
            entry[0] += (e - s) / 1e9
            entry[1] += 1
    buffer_wait = 0.0
    for line in host_lines:  # nesting is per thread
        buffer_wait += nested_seconds(
            [(s, e) for s, e, n in line if n.startswith(WAIT_FOR)],
            [(s, e) for s, e, n in line if n == CALL])
    n = len(planes)
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": {name: ns / 1e9 / n for name, ns in idle_ns.items()},
            "buffer_wait_s": buffer_wait,
            "modules": {name: (secs, int(count))
                        for name, (secs, count) in modules.items()},
            "events": {name: sum(1 for _s, _e, nm in spans if nm == name)
                       for name in PRECEDENCE}}


@lru_cache(maxsize=4)
def _read(path: str, _mtime: float) -> Optional[Dict[str, Any]]:
    return reduce_spans(load_host_lines(path), tracered.load_device_events(path))


def read_trace(path: str) -> Optional[Dict[str, Any]]:
    return _read(path, os.path.getmtime(path))


def of_run(facts: Dict[str, float], run_dir: Optional[str] = None
           ) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace; None when the run reduced no
    trace (`trace.window_s` is `run.py`'s word for that) or left none."""
    if "trace.window_s" not in facts:
        return None
    path = tracered.newest_xplane(run_dir or RUN_DIR)
    return read_trace(path) if path else None


# ---------------------------------------------------------------------------
# what the per-layer readers return
# ---------------------------------------------------------------------------

def idle_share_pct(facts: Dict[str, float], names: Sequence[str],
                   run_dir: Optional[str] = None) -> Optional[float]:
    """Device idle seconds under the named states, % of the traced window.
    Over all of `PRECEDENCE` and `unattributed` the shares sum to
    `device_idle_pct`."""
    spans = of_run(facts, run_dir)
    if spans is None or spans["window_s"] <= 0:
        return None
    return 100.0 * sum(spans["idle_s"].get(n, 0.0) for n in names) / spans["window_s"]


def buffer_wait_ms_per_train(facts: Dict[str, float],
                             run_dir: Optional[str] = None) -> Optional[float]:
    spans = of_run(facts, run_dir)
    trains = facts.get("traced.batches", 0.0)
    if spans is None or trains <= 0:
        return None
    return 1000.0 * spans["buffer_wait_s"] / trains


def module_ms_per_launch(facts: Dict[str, float], program: str,
                         run_dir: Optional[str] = None) -> Optional[float]:
    spans = of_run(facts, run_dir)
    if spans is None or program not in spans["modules"]:
        return None
    seconds, launches = spans["modules"][program]
    return 1000.0 * seconds / launches if launches else None
