"""Device idle time, % of the traced window, while the launch thread was
inside `prep.query_batch` or `prep.term_ranges` (building a launch's
operands), counted as `idle_under_prep_pct` counts `batcher.prep`, of
which it is a part (esbench/prepspans.py). Nothing on a trace without
those annotations (a parent commit)."""

from esbench import prepspans


def read(facts):
    return prepspans.idle_under_operands_pct(facts)
