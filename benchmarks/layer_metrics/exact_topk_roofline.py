"""Share of the HBM roofline that the exact kernel's launches of the
traced window reached: the least bytes a sorted-merge top-k of their
shapes has to move (`roofline.sorted_merge_topk_bytes(rows, slots x 4096,
k)`, rows and slots read from the programs' names) / the chip's peak
bytes per second / the seconds those programs ran on the device
(esbench/exactprograms.py)."""

from esbench import exactprograms


def read(facts):
    return exactprograms.roofline_share_pct(facts)
