"""For each program launch, the latest device's `XLA Modules` start
less the earliest's, mean over the launches of the traced window: the one
host thread reaching the mesh's devices one after the other
(esbench/crosschip.py). Silent on a trace of one device."""

from esbench import crosschip


def read(facts):
    return crosschip.launch_skew_ms(facts)
