"""Device ms per launch of the exact kernel's programs at 128 rows and
32 slots a row: the `XLA Modules` seconds of every
`jit_exact_<variant>_b128_s32_w<window>` / their event count
(esbench/exactpins.py). A window with no such launch, or a program that
does not name its exact launches after their shape, gives nothing."""

from esbench import exactpins


def read(facts):
    return exactpins.ms_per_launch(facts, rows=128, slots=32)
