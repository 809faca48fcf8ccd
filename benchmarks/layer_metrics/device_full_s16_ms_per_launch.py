"""Device ms per launch of the full-postings program at 16 slots: the
`XLA Modules` seconds of `jit_full_s16` / its event count
(esbench/hostspans.py)."""

from esbench import hostspans


def read(facts):
    return hostspans.module_ms_per_launch(facts, "jit_full_s16")
