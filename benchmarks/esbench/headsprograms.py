"""The heads programs of a traced run, by their static shape.

A launch on the heads route runs `jit_heads_b<rows>_s<slots>`: `rows`
queries, each with its tail terms' postings in `slots` slots of
CHUNK_LEN entries on every shard row the device holds, and the pack's
head block, `H` rows of `docs` float32 each (`/_tpu/stats` → the pack's
`heads_block`: `rows`, `shard_rows`, `docs_per_row`). The trace's `XLA
Modules` line says how long each shape ran and how often (`hostspans`).

The least time of one launch is the larger of two bounds:

  bytes  the block read once (H × docs × 4), the dense scores written
         and read once (rows × docs × 4 × 2), and the tail's lanes as
         `roofline.sorted_merge_topk_bytes` counts a sorted merge's, at
         the chip's peak bytes per second;
  flops  the head scores' matmul, 2 × rows × H × docs, at the chip's
         bfloat16 peak over 6: a float32 product at HIGHEST precision
         takes six bfloat16 passes.

The peaks are `peaks.PEAKS`' row whose bytes per second the run's facts
name (`device.peak_hbm_bytes_per_s`). A trace without such programs (a
parent commit) gives no program, and every reader returns nothing. One
chip's view: the block and the shard rows all on the traced device.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from esbench import hostspans, roofline
from esbench.peaks import PEAKS

#: entries a slot holds: the program's CHUNK_CAP (parallel/distributed.py)
CHUNK_LEN = 4096
F32_BYTES = 4
#: bfloat16 passes of a float32 matmul at HIGHEST precision
HIGHEST_PASSES = 6
_NAME = re.compile(r"^jit_heads_b(\d+)_s(\d+)$")
_BLOCK = "after.pack.heads_block."

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


def ms_per_launch(facts: Dict[str, float], run_dir: Optional[str] = None
                  ) -> Optional[float]:
    programs = of_run(facts, run_dir)
    launches = sum(n for _r, _s, _secs, n in programs)
    if not launches:
        return None
    return 1000.0 * sum(secs for _r, _s, secs, _n in programs) / launches


def least_seconds(rows: int, slots: int, h: int, shard_rows: int,
                  docs_per_row: int, k: int, bytes_per_s: float,
                  bf16_flop_per_s: float) -> float:
    """Least device seconds of one launch of `rows` queries at `slots`
    tail slots over a block of `h` rows × `shard_rows` × `docs_per_row`."""
    docs = shard_rows * docs_per_row
    moved = (h * docs * F32_BYTES + rows * docs * F32_BYTES * 2
             + roofline.sorted_merge_topk_bytes(
                 rows, slots * CHUNK_LEN * shard_rows, k))
    flops = 2 * rows * h * docs
    return max(moved / bytes_per_s,
               flops / (bf16_flop_per_s / HIGHEST_PASSES))


def roofline_share_pct(facts: Dict[str, float], run_dir: Optional[str] = None
                       ) -> Optional[float]:
    programs = of_run(facts, run_dir)
    seconds = sum(secs for _r, _s, secs, _n in programs)
    keys = [_BLOCK + key for key in ("rows", "shard_rows", "docs_per_row")]
    bw = facts.get("device.peak_hbm_bytes_per_s")
    peak = next((p for p in PEAKS.values() if p["hbm_bytes_per_s"] == bw), None)
    if (not programs or seconds <= 0 or peak is None
            or "request.size" not in facts or any(k not in facts for k in keys)):
        return None
    h, shard_rows, docs_per_row = (int(facts[k]) for k in keys)
    least = sum(n * least_seconds(rows, slots, h, shard_rows, docs_per_row,
                                  int(facts["request.size"]), bw,
                                  peak["bf16_flop_per_s"])
                for rows, slots, _secs, n in programs)
    return 100.0 * least / seconds
