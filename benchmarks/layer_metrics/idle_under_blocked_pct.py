"""Device idle time, % of the traced window, while the launch thread's
state said: it was blocked on the full in-flight queue (`batcher.blocked`).
The seven `idle_*` shares sum to `device_idle_pct` (esbench/hostspans.py)."""

from esbench import hostspans


def read(facts):
    return hostspans.idle_share_pct(facts, ("batcher.blocked",))
