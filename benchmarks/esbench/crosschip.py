"""What a launch costs because it runs on several chips: the per-plane
reduction of a profiler trace that `tracered` and `hostspans` average away.

A sharded launch is one program on every device of the mesh. Each device
sorts its own shard rows, then the tail of the program exchanges every
device's candidates (`all_gather`s of `[rows, k]` scores and ids, a `psum`
of the totals, a `pmax` of the cut) and each device merges them to the
global top k. On a trace (`tracered.load_device_events`: one plane a
device) that shows as

  collective seconds   the `XLA Ops` events whose HLO op is a collective
                       (`all-gather`, `all-reduce`, `collective-permute`,
                       each also as `-start` / `-done`), inside the
                       `XLA Modules` events of the sharded programs: the
                       transfer and the wait for the slowest peer, since
                       a device that arrives first stands in the
                       collective until the last has arrived
  launch skew          the same launch's `XLA Modules` event starts at a
                       different time on each plane, because one host
                       thread hands the program to the devices one after
                       the other: latest start less earliest start

The merge's roofline is the chip-to-chip interconnect: the least bytes a
device must take in are the other devices' k candidates of every row, a
score (4 B) and a doc id (4 B) each.

Published peak (Google Cloud documentation, "TPU v5e", system
architecture, the same page `peaks.py` takes HBM's from): 1,600 Gbit/s of
inter-chip interconnect bandwidth a chip. A device kind that is not in
the table is an error, never a default.

A trace of one device plane, or of none, gives None: there is no other
chip, the readers return nothing and the metrics are left out of the line.
"""

from __future__ import annotations

import bisect
import os
import re
from functools import lru_cache
from typing import Any, Dict, List, Optional, Tuple

from esbench import hostspans, tracered

#: bytes a second into one chip over its inter-chip links, by `device_kind`
ICI_BYTES_PER_S: Dict[str, float] = {"TPU v5 lite": 1600e9 / 8}

CANDIDATE_BYTES = 4 + 4      # score + doc id of one gathered candidate
#: an op event's name is its HLO text: `%all-gather.3 = f32[...] all-gather(...`
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|collective-permute)(-start|-done)?[.\d]*\s*=")
#: the programs whose tail merges across chips, as `XLA Modules` names them
MERGED_PROGRAMS = "jit_full_"

Event = Tuple[float, float, str]
Planes = Dict[str, Dict[str, List[Event]]]


def ici_bytes_per_s(device_kind: str) -> float:
    if device_kind not in ICI_BYTES_PER_S:
        raise KeyError(f"no published interconnect bandwidth for device kind "
                       f"[{device_kind}]; add it to benchmarks/esbench/crosschip.py "
                       f"with its source")
    return ICI_BYTES_PER_S[device_kind]


def merge_bytes(rows: float, k: float, devices: float) -> float:
    """Least bytes into one device for the cross-chip merge of `rows`
    queries at top `k` on a 1 × `devices` mesh (shards over every device,
    the node's default): every other device's k candidates of every row."""
    return rows * k * CANDIDATE_BYTES * max(devices - 1.0, 0.0)


def is_collective(op_name: str) -> bool:
    return COLLECTIVE.match(op_name) is not None


def collective_seconds(planes: Planes, programs: str = MERGED_PROGRAMS
                       ) -> Optional[Tuple[float, float]]:
    """→ (collective seconds, launches of `programs`), each the mean over
    the device planes; the seconds are the union of the collective op
    intervals that lie inside those launches' module events."""
    seconds, launches = [], []
    for lines in planes.values():
        mine = [(s, e) for s, e, name in lines.get(tracered.MODULES_LINE, [])
                if hostspans.module_name(name).startswith(programs)]
        ops = hostspans.merge([(s, e) for s, e, name in lines[tracered.OPS_LINE]
                               if is_collective(name)])
        seconds.append(hostspans.total(
            hostspans.intersect(ops, hostspans.merge(mine))) / 1e9)
        launches.append(len(mine))
    if not planes or not sum(launches):
        return None
    return sum(seconds) / len(planes), sum(launches) / len(planes)


def launch_skews_ns(planes: Planes) -> List[float]:
    """For every program launch seen on every plane: the latest plane's
    module start less the earliest's. One launch's events are those of one
    name that overlap in time on all planes (its devices meet in the
    program's collectives); a launch that the trace's edge cut off some
    plane finds no such set and is left out."""
    by_plane = []
    for lines in planes.values():
        by_name: Dict[str, List[Tuple[float, float]]] = {}
        for s, e, name in lines.get(tracered.MODULES_LINE, []):
            by_name.setdefault(name, []).append((s, e))
        by_plane.append({name: sorted(evs) for name, evs in by_name.items()})
    if len(by_plane) < 2:
        return []
    skews = []
    first, others = by_plane[0], by_plane[1:]
    for name, events in first.items():
        if not all(name in other for other in others):
            continue
        starts = [[s for s, _e in other[name]] for other in others]
        for s, e in events:
            same = [(s, e)]
            for other, other_starts in zip(others, starts):
                i = bisect.bisect_left(other_starts, s)
                near = [j for j in (i - 1, i) if 0 <= j < len(other_starts)]
                same.append(other[name][min(near, key=lambda j: abs(other_starts[j] - s))])
            if max(a for a, _b in same) < min(b for _a, b in same):
                skews.append(max(a for a, _b in same) - min(a for a, _b in same))
    return skews


def reduce_planes(planes: Planes) -> Optional[Dict[str, Any]]:
    planes = {n: l for n, l in planes.items() if l.get(tracered.OPS_LINE)}
    if len(planes) < 2:
        return None
    merged = collective_seconds(planes)
    skews = launch_skews_ns(planes)
    return {"device_planes": len(planes),
            "collective_s": merged[0] if merged else None,
            "merged_launches": merged[1] if merged else 0.0,
            "skew_launches": len(skews),
            "skew_s": sum(skews) / 1e9}


@lru_cache(maxsize=2)
def _read(path: str, _mtime: float) -> Optional[Dict[str, Any]]:
    return reduce_planes(tracered.load_device_events(path))


def of_run(facts: Dict[str, float], run_dir: Optional[str] = None
           ) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace (found as `hostspans.of_run`
    finds it); None when the run reduced no trace or the trace shows
    fewer than two devices."""
    if "trace.window_s" not in facts:
        return None
    path = tracered.newest_xplane(run_dir or hostspans.RUN_DIR)
    return _read(path, os.path.getmtime(path)) if path else None


# ---------------------------------------------------------------------------
# what the per-layer readers return
# ---------------------------------------------------------------------------

def merge_ms_per_launch(facts: Dict[str, float], run_dir: Optional[str] = None
                        ) -> Optional[float]:
    red = of_run(facts, run_dir)
    if red is None or red["collective_s"] is None:
        return None
    return 1000.0 * red["collective_s"] / red["merged_launches"]


def launch_skew_ms(facts: Dict[str, float], run_dir: Optional[str] = None
                   ) -> Optional[float]:
    red = of_run(facts, run_dir)
    if red is None or not red["skew_launches"]:
        return None
    return 1000.0 * red["skew_s"] / red["skew_launches"]


def merge_ici_pct(facts: Dict[str, float], device_kind: Optional[str] = None,
                  run_dir: Optional[str] = None) -> Optional[float]:
    """The merge's share of its roofline: least seconds of a launch's
    exchange at the published interconnect bandwidth ÷ the collective
    seconds a launch took. Rows and devices a launch come from the node's
    `cross_chip` counter over the traced part of the window, k from the
    request, the device kind from jax (the reader runs in the process that
    holds the chips) unless given."""
    red = of_run(facts, run_dir)
    counted = facts.get("traced.cross_chip.launches", 0.0)
    if red is None or not red["collective_s"] or counted <= 0:
        return None
    least = merge_bytes(facts["traced.cross_chip.rows"] / counted,
                        facts["request.size"],
                        facts["traced.cross_chip.devices"] / counted)
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    per_launch = red["collective_s"] / red["merged_launches"]
    return 100.0 * (least / ici_bytes_per_s(device_kind)) / per_launch
