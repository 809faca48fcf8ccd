"""Device idle time, % of the traced window, while the launch thread's
state said: no state of the launch thread covered the instant.
The seven `idle_*` shares sum to `device_idle_pct` (esbench/hostspans.py)."""

from esbench import hostspans


def read(facts):
    return hostspans.idle_share_pct(facts, (hostspans.UNATTRIBUTED,))
