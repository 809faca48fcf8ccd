"""Seconds the runtime's `Wait for ...` events (donation holds, buffers)
take inside the launch thread's `batcher.call`, per train completed in
the traced part of the window (esbench/hostspans.py)."""

from esbench import hostspans


def read(facts):
    return hostspans.buffer_wait_ms_per_train(facts)
