"""Device idle time, % of the traced window, while the launch thread's
state said: queries were pending and it waited on purpose: window, hold while busy, refill (`batcher.hold`).
The seven `idle_*` shares sum to `device_idle_pct` (esbench/hostspans.py)."""

from esbench import hostspans


def read(facts):
    return hostspans.idle_share_pct(facts, ("batcher.hold",))
