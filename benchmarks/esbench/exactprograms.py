"""The exact kernel's programs of a traced run, by their static shape.

The program names each exact launch's device program after its shape,
`jit_exact_<variant>_b<rows>_s<slots>_w<window>` (PR 29), so the trace's
`XLA Modules` line says how long each shape ran and how often
(`hostspans` reads it: {program: (seconds, launches)}). From the name
alone: rows and slots a row, hence the entries a launch sorts, rows x
slots x CHUNK_LEN for each shard a device holds (one in the cell that
lists these metrics). The bytes such a launch must move are
`roofline.sorted_merge_topk_bytes`, the same function whatever
implements the kernel. A trace whose exact programs carry no shape (a
parent commit: `jit_exact_ref`) gives no program here, and every reader
returns nothing.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from esbench import hostspans, roofline

#: entries a slot holds: the program's CHUNK_CAP (parallel/distributed.py)
CHUNK_LEN = 4096
_NAME = re.compile(r"^jit_exact_[a-z_]+_b(\d+)_s(\d+)_w(\d+)$")

#: (rows, slots, seconds on the device, launches)
Program = Tuple[int, int, float, int]


def shaped(modules: Dict[str, Tuple[float, int]]) -> List[Program]:
    out = []
    for name, (seconds, launches) in modules.items():
        m = _NAME.match(name)
        if m:
            out.append((int(m.group(1)), int(m.group(2)), float(seconds),
                        int(launches)))
    return sorted(out)


def of_run(facts: Dict[str, float], run_dir: Optional[str] = None
           ) -> List[Program]:
    spans = hostspans.of_run(facts, run_dir)
    return shaped(spans["modules"]) if spans else []


def ms_per_launch(facts: Dict[str, float], rows: int,
                  run_dir: Optional[str] = None) -> Optional[float]:
    mine = [p for p in of_run(facts, run_dir) if p[0] == rows]
    launches = sum(n for _r, _s, _secs, n in mine)
    if not launches:
        return None
    return 1000.0 * sum(secs for _r, _s, secs, _n in mine) / launches


def least_bytes(programs: List[Program], k: int) -> int:
    return sum(roofline.sorted_merge_topk_bytes(rows, slots * CHUNK_LEN, k) * n
               for rows, slots, _secs, n in programs)


def roofline_share_pct(facts: Dict[str, float], run_dir: Optional[str] = None
                       ) -> Optional[float]:
    programs = of_run(facts, run_dir)
    seconds = sum(secs for _r, _s, secs, _n in programs)
    if (not programs or seconds <= 0 or "request.size" not in facts
            or "device.peak_hbm_bytes_per_s" not in facts):
        return None
    least = least_bytes(programs, int(facts["request.size"]))
    return 100.0 * (least / facts["device.peak_hbm_bytes_per_s"]) / seconds
