"""Fused Pallas spelling of the compressed merge kernel (PR 15).

One pl.pallas_call carries the whole hot loop the XLA variants spread
over separate ops: phase-A posting gather from the compressed u16/u8
resident streams, the packed single-key merge sort, the block-max skip
branch (the running top-k threshold lives INSIDE the kernel instead of
a separate masking pass) and per-block top-k selection + exact rescore.
The kernel grids over rows — each program instance owns one (query ×
shard) row's slot table, while the flat posting streams stay resident
in device memory and are sliced per slot inside the kernel, so the
intermediate sorted-operand materialisation between gather and merge
never round-trips through HBM.

Dispatch is backend-aware: off the TPU the kernel runs under
interpret=True, which executes the exact same trace the XLA "compressed"
variant lowers from — the parity sweep (tests/test_sparse_kernel.py) pins
variant="pallas" bit-identical to variant="ref" on CPU by construction.

On a TPU backend the kernel does not compile (TPU_REFUSAL below: the one
chip run that tried it, PR 21). The row-blocked (1, T) operand blocks
are refused by the Pallas TPU lowering before Mosaic runs; behind
that check wait whole flat streams as single VMEM blocks and
lax.sort/top_k/dynamic slices in the kernel body. Serving therefore
refuses `search.tpu_serving.kernel.pallas=true` on a TPU backend at node
start (require_servable) instead of degrading every query to the planner.

Operands, outputs, gates and semantics match
sparse.sorted_merge_topk(variant="compressed") exactly; see ops/sparse.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from elasticsearch_tpu.ops import sparse

#: what the Pallas TPU lowering said when the compressed small index
#: (32,768 docs, one shard) was prewarmed with kernel.pallas=true on a
#: TPU v5 lite (jax 0.9.0, jaxlib 0.9.0, libtpu 0.0.34; PERF.md
#: "Bring-up on the chip (PR 21)")
TPU_REFUSAL = (
    "The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the "
    "overall array. Block spec for args[2] in pallas_call kernel at "
    "elasticsearch_tpu/ops/pallas_merge.py has block shape "
    "(Blocked(block_size=1), Blocked(block_size=16)), array shape (8, 16)")


def require_servable() -> None:
    """Raise where variant="pallas" cannot serve: on a TPU backend the
    kernel is refused at lowering, and the serving path would answer
    that refusal from the planner with HTTP 200 on every query."""
    if jax.default_backend() == "tpu":
        from elasticsearch_tpu.common.errors import IllegalArgumentException
        raise IllegalArgumentException(
            "search.tpu_serving.kernel.pallas=true cannot be served on a "
            "TPU backend: the kernel does not compile there. "
            + TPU_REFUSAL)


#: names and order of the optional operands the kernel may receive after
#: the six required ones; absent operands are simply not passed
_OPTIONAL_OPERANDS = ("flat_rank", "res_starts", "res_lens", "res_vals",
                      "block_max", "blk_starts", "slot_terms",
                      "doc_bases", "dbs_starts", "dlo_starts")


def fused_merge_topk(
    flat_docs: jax.Array,
    flat_impact: jax.Array,
    starts: jax.Array,
    lengths: jax.Array,
    weights: jax.Array,
    min_count: jax.Array,
    *,
    max_len: int,
    d_pad: int,
    k: int,
    t_window: int,
    with_counts: bool,
    with_totals: bool = False,
    flat_rank: Optional[jax.Array] = None,
    res_starts: Optional[jax.Array] = None,
    res_lens: Optional[jax.Array] = None,
    res_vals: Optional[jax.Array] = None,
    block_max: Optional[jax.Array] = None,
    blk_starts: Optional[jax.Array] = None,
    slot_terms: Optional[jax.Array] = None,
    doc_bases: Optional[jax.Array] = None,
    dbs_starts: Optional[jax.Array] = None,
    dlo_starts: Optional[jax.Array] = None,
) -> Tuple[jax.Array, ...]:
    """sorted_merge_topk(variant="pallas"): the compressed pipeline as
    one row-gridded Pallas kernel. Returns (scores, doc_ids[, totals])
    bit-identical to variant="compressed" on the same operands."""
    core_kw = dict(
        max_len=max_len, d_pad=d_pad, k=k, t_window=t_window,
        with_counts=with_counts, with_totals=with_totals,
        variant="compressed")
    optional = {
        "flat_rank": flat_rank, "res_starts": res_starts,
        "res_lens": res_lens, "res_vals": res_vals,
        "block_max": block_max, "blk_starts": blk_starts,
        "slot_terms": slot_terms, "doc_bases": doc_bases,
        "dbs_starts": dbs_starts, "dlo_starts": dlo_starts}
    r, t_slots = starts.shape
    kk = min(k, t_slots * max_len)

    #: [R, T]-shaped operands are row-blocked (one program instance per
    #: row); flat streams/tables are whole-array blocks every instance
    #: reads through (resident, sliced per slot inside the kernel)
    per_row = {"starts", "lengths", "weights", "res_starts", "res_lens",
               "blk_starts", "slot_terms", "dbs_starts", "dlo_starts"}

    names = ["flat_docs", "flat_impact", "starts", "lengths", "weights",
             "min_count"]
    operands = [flat_docs, flat_impact, starts, lengths, weights,
                min_count]
    for name in _OPTIONAL_OPERANDS:
        if optional[name] is not None:
            names.append(name)
            operands.append(optional[name])

    def spec_for(name, arr):
        if name == "min_count":
            return pl.BlockSpec((1,), lambda i: (i,))
        if name in per_row:
            return pl.BlockSpec((1, arr.shape[1]), lambda i: (i, 0))
        shape = arr.shape
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    in_specs = [spec_for(n, a) for n, a in zip(names, operands)]
    out_shape = [jax.ShapeDtypeStruct((r, kk), jnp.float32),
                 jax.ShapeDtypeStruct((r, kk), jnp.int32)]
    out_specs = [pl.BlockSpec((1, kk), lambda i: (i, 0)),
                 pl.BlockSpec((1, kk), lambda i: (i, 0))]
    if with_totals:
        out_shape.append(jax.ShapeDtypeStruct((r,), jnp.int32))
        out_specs.append(pl.BlockSpec((1,), lambda i: (i,)))

    def kernel(*refs):
        in_refs = refs[:len(names)]
        out_refs = refs[len(names):]
        vals = dict(zip(names, (ref[...] for ref in in_refs)))
        extras = {name: vals.get(name) for name in _OPTIONAL_OPERANDS}
        out = sparse._merge_topk_core(
            vals["flat_docs"], vals["flat_impact"], vals["starts"],
            vals["lengths"], vals["weights"], vals["min_count"],
            **core_kw, **extras)
        for ref, val in zip(out_refs, out):
            ref[...] = val

    # real kernel on TPU, interpret elsewhere: the interpreter executes
    # the same jax trace the XLA variant compiles, so CPU parity is
    # bitwise by construction rather than by tolerance
    interpret = jax.default_backend() != "tpu"
    out = pl.pallas_call(
        kernel,
        grid=(r,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return tuple(out)
