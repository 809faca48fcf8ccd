"""The cross-chip merge's share of its roofline: least bytes into a
device (`crosschip.merge_bytes`: rows x k x 8 B x (devices - 1), rows and
devices from the node's `cross_chip` counter, k the request's `size`) at
the published inter-chip bandwidth / the collective seconds a launch took
(esbench/crosschip.py). Silent where the node has no such counter or the
trace shows one device."""

from esbench import crosschip


def read(facts):
    return crosschip.merge_ici_pct(facts)
