"""Device idle time, % of the traced window, while the launch thread's
state said: it took a train or dispatched a program (`batcher.take`, `.lock`, `.put`, `.call`).
The seven `idle_*` shares sum to `device_idle_pct` (esbench/hostspans.py)."""

from esbench import hostspans


def read(facts):
    return hostspans.idle_share_pct(facts, ("batcher.take", "batcher.lock", "batcher.put", "batcher.call"))
