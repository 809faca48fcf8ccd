"""Device ms per launch of the exact kernel's programs at 128 rows: the
`XLA Modules` seconds of every `jit_exact_<variant>_b128_s<slots>_w<window>`
/ their event count (esbench/exactprograms.py over hostspans' modules). A
program that does not name its exact launches after their shape gives
nothing."""

from esbench import exactprograms


def read(facts):
    return exactprograms.ms_per_launch(facts, rows=128)
