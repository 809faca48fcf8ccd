"""One slot pin of the exact kernel in a traced run (PR 36).

An exact launch takes the slot pin of its widest query (powers of two
from 8 slots of 4,096 postings), so a window of mixed traffic holds
launches of one row bucket at several pins, each a program of its own
(`jit_exact_<variant>_b<rows>_s<slots>_w<window>`), and
`exactprograms.ms_per_launch` is their mix. This is one pin's: what a
launch costs the device at that width, which is what a train split by
pin would pay for its narrow part.
"""

from __future__ import annotations

from typing import Dict, Optional

from esbench import exactprograms


def ms_per_launch(facts: Dict[str, float], rows: int, slots: int,
                  run_dir: Optional[str] = None) -> Optional[float]:
    """Device ms a launch of the exact programs of `rows` x `slots`,
    whatever their variant and window; nothing where the window holds no
    such launch or the programs carry no shape in their names."""
    mine = [(secs, n) for r, s, secs, n in exactprograms.of_run(facts, run_dir)
            if (r, s) == (rows, slots)]
    launches = sum(n for _secs, n in mine)
    if not launches:
        return None
    return 1000.0 * sum(secs for secs, _n in mine) / launches
