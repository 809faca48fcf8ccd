"""Device ms per launch of the heads programs: the `XLA Modules` seconds
of every `jit_heads_b<rows>_s<slots>` / their event count
(esbench/headsprograms.py over hostspans' modules). A program without
the heads route gives nothing."""

from esbench import headsprograms


def read(facts):
    return headsprograms.ms_per_launch(facts)
