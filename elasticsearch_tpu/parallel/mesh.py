"""Device mesh construction for distributed search.

Reference analog: the node topology over which shards are allocated
(SURVEY.md §2.3 P1: an index is N primary shards hashed over nodes). Here
the topology is a `jax.sharding.Mesh` with two named axes:

  "data"   — query micro-batch axis (throughput replication, P2/P4 analog)
  "shards" — document-partition axis (P1: each mesh slot holds a disjoint
             set of index shards; search fans out over this axis and
             reduces with collectives, P3)

The reference scatters requests over nodes via RPC; we lay shards out over
ICI so the scatter/reduce is `shard_map` + `all_gather` (SURVEY.md §5.8:
"data-plane reduce = collectives").
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
SHARD_AXIS = "shards"


def shard_map(body, *, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the kernels'
    collectives are hand-placed)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def factorize_2d(n: int) -> Tuple[int, int]:
    """(data, shards) grid for n devices: favor the shards axis (search
    scales with document partitions first), keep data as the largest
    power-of-two cofactor ≤ shards."""
    best = (1, n)
    d = 1
    while d * d <= n:
        if n % d == 0:
            best = (d, n // d)
        d *= 2
    return best


def make_mesh(devices: Optional[Sequence] = None,
              shape: Optional[Tuple[int, int]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shape is None:
        shape = factorize_2d(n)
    data, shards = shape
    if data * shards != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    grid = np.array(devices).reshape(data, shards)
    return Mesh(grid, (DATA_AXIS, SHARD_AXIS))
