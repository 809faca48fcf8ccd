"""Device ms a launch spends in the cross-chip merge's collectives
(`all-gather`, `all-reduce`, `collective-permute`, also as `-start` /
`-done`) inside the `jit_full_*` programs, mean over the device planes:
the transfer plus the wait for the slowest peer (esbench/crosschip.py).
Silent on a trace of one device."""

from esbench import crosschip


def read(facts):
    return crosschip.merge_ms_per_launch(facts)
