"""Share of the roofline that the heads programs of the traced window
reached: the least time of their launches (the larger of the bytes and
the matmul's flops at the chip's peaks, `headsprograms.least_seconds`,
rows and tail slots read from the programs' names, the block's rows and
docs from `/_tpu/stats`) / the seconds those programs ran on the device
(esbench/headsprograms.py)."""

from esbench import headsprograms


def read(facts):
    return headsprograms.roofline_share_pct(facts)
