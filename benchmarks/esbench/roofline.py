"""Operations and bytes of a kernel call, from its launch shape.

`sorted_merge_topk` (ops/sparse.py) merges, for each of `rows` queries,
`elems` posting entries (slots × chunk length, over the shards a device
holds) into the top `k`. The least it must move through HBM: every
entry's doc id (4 B) and impact (4 B) read once, and the (score, doc)
pairs of the result written once. Sorting is compare-exchange work on
the vector unit with no matrix-unit FLOPs to speak of, so the bound that
matters is bytes: least time = bytes / peak bytes per second.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

ENTRY_BYTES = 4 + 4      # doc id + impact of one posting entry
RESULT_BYTES = 4 + 4     # score + doc id of one hit

#: the launch's widest sort names its shape: rows × entries per row
_SORT_SHAPE = re.compile(r"sort[^\[]*[su]32\[(\d+),(\d+)\]")


def sorted_merge_topk_bytes(rows: int, elems: int, k: int) -> int:
    """Least HBM bytes of one launch over `rows` queries × `elems` entries."""
    return rows * (elems * ENTRY_BYTES + min(k, elems) * RESULT_BYTES)


def launch_shapes(op_counts: Dict[str, int]) -> List[Tuple[int, int, int]]:
    """Device-op names with their event counts → [(rows, elems, launches)]
    for each distinct 2-d integer sort (one per launch of that shape). A
    launch shape's widest sort is the merge; narrower ones of the same
    rows are its later stages and are left out."""
    widest: Dict[int, Tuple[int, int]] = {}
    for name, count in op_counts.items():
        m = _SORT_SHAPE.search(name)
        if not m:
            continue
        rows, elems = int(m.group(1)), int(m.group(2))
        if rows not in widest or elems > widest[rows][0]:
            widest[rows] = (elems, count)
    return [(rows, elems, count) for rows, (elems, count) in sorted(widest.items())]


def roofline_share_pct(op_counts: Dict[str, int], kernel_seconds: float, k: int,
                       peak_bytes_per_s: float) -> Optional[float]:
    shapes = launch_shapes(op_counts)
    if not shapes or kernel_seconds <= 0:
        return None
    least = sum(sorted_merge_topk_bytes(r, e, k) * n for r, e, n in shapes)
    return 100.0 * (least / peak_bytes_per_s) / kernel_seconds
