"""Impact-sorted-merge retrieval kernel — the TPU-native hot path.

Replaces the reference's per-segment postings traversal (SURVEY.md §3.3:
BulkScorer loop → BM25Scorer → TopScoreDocCollector) with a formulation
built from TPU-fast primitives only (measured on v5e: XLA scatter ≈ 10M
updates/s — unusable; sort/top_k/contiguous-slice ≈ memory-bandwidth):

  1. Eager impacts (BM25S-style, PAPERS.md): at pack-build time each
     posting stores  impact = tf / (tf + k1·(1 − b + b·dl/avgdl))  so
     query-time scoring is one multiply by the term's idf·(k1+1)·boost.
  2. Chunked slot gather: each query term's postings row is split into
     chunks of ≤ L_c (static bucket); a chunk = one (start, length, weight,
     term-id) slot. vmapped dynamic_slice → contiguous DMA, no gather.
  3. One stable sort of [R, T·L_c] by doc id — the multi-way postings merge
     (ConjunctionDISI/BooleanScorer analog) as a single sort.
  4. Windowed same-key sum: a doc appears in at most T slots, so the
     segmented sum over equal-doc runs is a T-tap shifted add — no
     associative_scan (tuple-carry scans blow up TPU compile time).
  5. run-end mask + lax.top_k over the sparse candidate axis (size T·L_c,
     NOT the doc axis) — top-1000 never touches a dense [D] array.

Semantics per row: OR-of-slots with msm support. The clause count per doc
is the equal-doc run length, which is exact because each slot holds a doc
at most once (postings rows have unique docs, and chunks of one term
partition its row). Ties break like Lucene: equal scores → smaller doc id
(sorted axis + top_k's earliest-index-wins).

Packed-key variant (variant="packed"): the merge sort
dominates kernel time and is memory-bandwidth-bound, so instead of
sorting a (docs int32, impacts f32) key+value PAIR, each lane packs
  key = doc_id << 16  |  monotone 16-bit impact code
into ONE uint32 and the sort moves half the bytes. The code is the top
16 bits of the f32 bit pattern (bf16-style truncation) — order-preserving
for non-negative floats, so run structure, run lengths (msm counts) and
totals are exact; only the impact VALUES are approximate. Top candidates
are then selected hierarchically (per-block top-k' + merge instead of one
full-width top_k over T*L_c) and re-scored in exact f32 by binary-searching
each candidate in the doc-sorted chunks — summed in the reference
variant's exact order, so returned scores, doc ids, tie-breaks and totals
are bit-identical to variant="ref". Requires packable() inputs (doc ids
< 2**16, sane non-negative weights); the serving stack checks that at
lowering time and falls back to "ref" otherwise.

Compressed-pack variants (variant="compressed"/"compressed_exact", PR 8):
the RESIDENT arrays themselves are quantized — three u16 streams
(compress_flat): doc ids, monotone VALUE codes (impact_code16 of each
impact — collisions between near-equal impacts are fine, the codes only
feed lower bounds), and per-term RANK codes (1-based index of the
posting's impact in its term's ascending distinct-impact table — 0 marks
tombstone-zeroed postings). 6 bytes/posting replaces the 16 bytes of the
doc-sorted (int32, f32) pair plus the impact-sorted copy. The exact-f32
rescore survives the f32 arrays' removal by reading each term's small
RESIDUAL TABLE (its sorted distinct positive impacts): the rank found at
a candidate's posting position indexes the bit-exact f32 impact directly.
"compressed" runs the packed single-key pipeline on the decoded
lower-bound value codes and rescores through the residual tables;
"compressed_exact" decodes every lane to exact f32 first and runs the
reference pipeline — the automatic fallback when the batch weights break
the monotone-lower-bound guarantee (packable()), exact for ANY weights.
Alongside the streams, per-128-lane BLOCK MAX codes (block-max WAND /
BM25S eager elimination) let the "compressed" kernel carry a running
top-k threshold: a 128-lane group whose maximum possible weighted
contribution (its block-max upper bound plus every other slot's window
upper bound) cannot reach the k-th best lower bound already achieved is
masked out before the sort. Skipping applies to rows with min_count ≤ 1;
totals-returning launches (a skipped doc is still a match) get their
exact TotalHits from a dedicated PRE-skip count sort — one u32 key of
(doc id << 1 | positive-code bit) — so track_total_hits queries ride
the skip path too instead of forcing full evaluation.

Delta doc stream (PR 15, the last of the bytes war): when every aligned
128-lane block of a pack's doc stream spans ≤ 255 doc ids
(delta_doc_reason), the resident u16 doc stream is replaced by a u8
DELTA stream plus one u16 per-block BASE (the block's minimum doc id),
decoded in-kernel: lane doc = base[(dlo + lane) // 128] + delta. That
takes the doc stream from 2 B to ~1.02 B per posting — resident packs
drop under 6 B/posting. The exact-rescore binary search decodes the
same way through per-slot (dbs, dlo) block cursors, so results remain
bit-identical; shards whose streams overflow the u8 span keep the plain
u16 doc format (typed per-pack gate, like compress_reason).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from itertools import chain, islice
from operator import itemgetter
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float("-inf")

#: doc-id field width of the packed sort key: doc ids (including the
#: d_pad sentinel) must be < 2**16 for the packed variant to apply
PACKED_DOC_LIMIT = 1 << 16

#: positive slot weights outside this range route to the exact-f32
#: fallback: below the floor a real match's weighted impact could
#: truncate to code 0 (dropping it from totals), above the ceiling the
#: quantized sums lose the ordering guarantees the rescore slack assumes
PACKED_WEIGHT_MIN = 1e-12
PACKED_WEIGHT_MAX = 1e30

KERNEL_VARIANTS = ("ref", "packed", "compressed", "compressed_exact")

#: variants that read the compressed resident streams (16-bit doc ids +
#: 16-bit impact codes + residual tables) instead of the raw pair
COMPRESSED_VARIANTS = ("compressed", "compressed_exact")

#: block-max metadata granularity: one max-impact code per this many
#: postings lanes (the TPU lane width — a group of lanes the sort would
#: load together anyway)
COMPRESSED_BLOCK = 128

#: per-term rank codes are u16 with 0 reserved for "no impact", so a
#: term may have at most this many distinct positive impact values
COMPRESSED_RANK_LIMIT = (1 << 16) - 1


def impact_code16(x: jax.Array) -> jax.Array:
    """Monotone 16-bit code of a non-negative finite f32: the top 16
    bits of its bit pattern (bf16-style truncation). Order-preserving —
    x <= y implies code(x) <= code(y) — and decode_code16(code(x)) is a
    lower bound of x, so quantized run totals never overshoot."""
    return jax.lax.bitcast_convert_type(x, jnp.uint32) >> 16


def decode_code16(code: jax.Array) -> jax.Array:
    """Inverse of impact_code16 up to truncation: the largest f32 whose
    code equals `code` rounds down to this value (zero low bits)."""
    return jax.lax.bitcast_convert_type(
        (code << 16).astype(jnp.uint32), jnp.float32)


def impact_code16_np(x: np.ndarray) -> np.ndarray:
    """Host-side impact_code16: uint16 codes of non-negative f32s."""
    flat = np.ascontiguousarray(x, dtype=np.float32)
    return (flat.view(np.uint32) >> 16).astype(np.uint16)


def decode_code16_np(code: np.ndarray) -> np.ndarray:
    """Host-side decode_code16: lower-bound f32 of each uint16 code."""
    return (np.asarray(code).astype(np.uint32) << 16).view(np.float32)


def _posting_terms(row_starts: np.ndarray, n: int) -> np.ndarray:
    """Term id per flat posting position. Positions past the last row
    (the CHUNK_CAP slack tail) get the one-past-the-end id — they carry
    impact 0 and never produce residual entries."""
    rs = np.asarray(row_starts, dtype=np.int64)
    counts = np.diff(rs)
    terms = np.repeat(np.arange(counts.size, dtype=np.int64), counts)
    if terms.size < n:
        terms = np.concatenate(
            [terms, np.full(n - terms.size, counts.size, dtype=np.int64)])
    return terms[:n]


def compress_reason(flat_docs: np.ndarray, flat_impact: np.ndarray,
                    row_starts: np.ndarray, d_pad: int) -> Optional[str]:
    """Why this shard's flats can NOT take the compressed resident
    format — None means compressible. The gates guarantee the u16
    streams lose nothing the kernel needs: doc ids (and the d_pad
    sentinel) must fit 16 bits, every positive impact needs a nonzero
    16-bit VALUE code (else it would vanish from quantized run totals),
    and no term may exceed the 16-bit RANK space of distinct positive
    impacts (else the exact-decode rank stream would overflow)."""
    if d_pad >= PACKED_DOC_LIMIT:
        return (f"d_pad {d_pad} does not fit the 16-bit doc stream "
                f"(limit {PACKED_DOC_LIMIT})")
    imp = np.asarray(flat_impact, dtype=np.float32)
    if imp.size == 0:
        return None
    if not np.isfinite(imp).all() or bool((imp < 0).any()):
        return "impacts must be finite and non-negative"
    codes = impact_code16_np(imp)
    pos = imp > 0
    if bool((codes[pos] == 0).any()):
        return "positive impact below the 16-bit code floor"
    terms = _posting_terms(row_starts, imp.size)
    t_p, v_p = terms[pos], imp[pos]
    if t_p.size:
        order = np.lexsort((v_p, t_p))
        t_s, v_s = t_p[order], v_p[order]
        first = np.ones(t_s.size, dtype=bool)
        first[1:] = (t_s[1:] != t_s[:-1]) | (v_s[1:] != v_s[:-1])
        per_term = np.bincount(t_s[first])
        if per_term.size and int(per_term.max()) > COMPRESSED_RANK_LIMIT:
            return (f"a term has more than {COMPRESSED_RANK_LIMIT} "
                    f"distinct impacts (rank code overflow)")
    return None


def compress_flat(flat_docs: np.ndarray, flat_impact: np.ndarray,
                  row_starts: np.ndarray, d_pad: int,
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                             np.ndarray, np.ndarray, np.ndarray]:
    """Build one shard's compressed resident streams from its doc-sorted
    flats. → (docs16 u16[P], code16 u16[P], rank16 u16[P],
    block_max u16[NB+1], res_vals f32[RC], res_row_starts i64[n_rows+1]).

    docs16/code16/rank16 replace the 16 resident bytes per posting with
    6: code16 is the monotone VALUE code (lower bounds for the quantized
    sort and block-max pruning; collisions between near-equal impacts
    are harmless there), rank16 is the 1-based index of the posting's
    impact in its term's ascending distinct-impact residual table (0 =
    tombstone-zeroed posting) — injective by construction, so the exact
    rescore recovers bit-exact f32 impacts from res_vals without a
    resident f32 copy. block_max[j] is the max value code of the
    128-lane-aligned block j, plus ONE zero slack entry so a slot
    straddling the array edge can always slice n_grp+1 entries without
    dynamic_slice clamping into earlier (wrong) blocks. Raises
    ValueError when compress_reason() is non-None; callers gate first."""
    reason = compress_reason(flat_docs, flat_impact, row_starts, d_pad)
    if reason is not None:
        raise ValueError(f"flats not compressible: {reason}")
    docs = np.asarray(flat_docs)
    imp = np.asarray(flat_impact, dtype=np.float32)
    n = imp.size
    docs16 = np.minimum(docs, d_pad).astype(np.uint16)
    code16 = impact_code16_np(imp)

    nb = (n + COMPRESSED_BLOCK - 1) // COMPRESSED_BLOCK
    padded = np.zeros(nb * COMPRESSED_BLOCK, dtype=np.uint16)
    padded[:n] = code16
    block_max = np.concatenate(
        [padded.reshape(nb, COMPRESSED_BLOCK).max(axis=1),
         np.zeros(1, dtype=np.uint16)])

    terms = _posting_terms(row_starts, n)
    n_rows = np.asarray(row_starts).size - 1
    pos = imp > 0
    t_p, v_p = terms[pos], imp[pos]
    order = np.lexsort((v_p, t_p))
    t_s, v_s = t_p[order], v_p[order]
    first = np.ones(t_s.size, dtype=bool)
    if t_s.size:
        first[1:] = (t_s[1:] != t_s[:-1]) | (v_s[1:] != v_s[:-1])
    res_row_starts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(np.bincount(t_s[first], minlength=n_rows),
              out=res_row_starts[1:])
    rank16 = np.zeros(n, dtype=np.uint16)
    if t_s.size:
        distinct_idx = np.cumsum(first) - 1
        rank_sorted = distinct_idx - res_row_starts[t_s] + 1
        rank_pos = np.empty(t_s.size, dtype=np.int64)
        rank_pos[order] = rank_sorted
        rank16[pos] = rank_pos.astype(np.uint16)
    return (docs16, code16, rank16, block_max,
            v_s[first].astype(np.float32), res_row_starts)


#: widest doc-id span an aligned 128-lane block may cover and still take
#: the u8 delta encoding (delta = doc − block min must fit one byte)
DELTA_DOC_SPAN = (1 << 8) - 1


def delta_doc_reason(flat_docs: np.ndarray, row_starts: np.ndarray,
                     ) -> Optional[str]:
    """Why this shard's doc stream can NOT take the per-block delta
    encoding — None means every aligned COMPRESSED_BLOCK-lane block of
    REAL postings (positions before row_starts[-1]; the slack tail is
    never decoded) spans ≤ DELTA_DOC_SPAN doc ids, so doc − block_min
    fits the u8 delta field. Blocks straddling a row boundary mix two
    terms' doc ids; the min-base covers that case (deltas are measured
    against the block minimum, not the first lane)."""
    rs = np.asarray(row_starts, dtype=np.int64)
    total = int(rs[-1]) if rs.size else 0
    if total == 0:
        return None
    docs = np.asarray(flat_docs[:total], dtype=np.int64)
    nb = (total + COMPRESSED_BLOCK - 1) // COMPRESSED_BLOCK
    pad = nb * COMPRESSED_BLOCK - total
    mx = np.concatenate([docs, np.full(pad, -1, dtype=np.int64)])
    mn = np.concatenate([docs, np.full(pad, 1 << 30, dtype=np.int64)])
    span = (mx.reshape(nb, COMPRESSED_BLOCK).max(axis=1)
            - mn.reshape(nb, COMPRESSED_BLOCK).min(axis=1))
    worst = int(span.max())
    if worst > DELTA_DOC_SPAN:
        return (f"a {COMPRESSED_BLOCK}-lane block spans {worst} doc ids "
                f"(u8 delta limit {DELTA_DOC_SPAN})")
    return None


def delta_encode_docs(flat_docs: np.ndarray, row_starts: np.ndarray,
                      n_bases: int) -> Tuple[np.ndarray, np.ndarray]:
    """Build one shard's delta doc stream: → (docs8 u8[P], bases
    u16[n_bases]). bases[j] is the minimum doc id of aligned block j
    (zero for blocks past the real postings — never decoded, see
    delta_doc_reason); docs8[p] = doc − bases[p // 128] for real
    positions, zero in the slack tail. n_bases must leave the kernel's
    slice slack past the last real block (callers size it
    ceil(P / 128) + 2). Raises ValueError when delta_doc_reason() is
    non-None; callers gate first."""
    reason = delta_doc_reason(flat_docs, row_starts)
    if reason is not None:
        raise ValueError(f"doc stream not delta-encodable: {reason}")
    docs = np.asarray(flat_docs, dtype=np.int64)
    rs = np.asarray(row_starts, dtype=np.int64)
    total = int(rs[-1]) if rs.size else 0
    nb = (total + COMPRESSED_BLOCK - 1) // COMPRESSED_BLOCK
    if n_bases < nb:
        raise ValueError(f"n_bases {n_bases} < {nb} real blocks")
    bases = np.zeros(n_bases, dtype=np.uint16)
    docs8 = np.zeros(docs.size, dtype=np.uint8)
    if total:
        pad = nb * COMPRESSED_BLOCK - total
        mn = np.concatenate(
            [docs[:total], np.full(pad, 1 << 30, dtype=np.int64)]
        ).reshape(nb, COMPRESSED_BLOCK).min(axis=1)
        bases[:nb] = mn.astype(np.uint16)
        docs8[:total] = (docs[:total]
                         - np.repeat(mn, COMPRESSED_BLOCK)[:total]
                         ).astype(np.uint8)
    return docs8, bases


def packable(d_pad: int, weights: Optional[np.ndarray] = None) -> bool:
    """Host-side lowering-time check: may the packed-key variant serve
    this (pack, batch)? False routes the batch to the exact-f32
    reference variant. Conditions: every doc id INCLUDING the d_pad
    sentinel must fit the 16-bit doc field, and every slot weight must
    be finite, non-negative and (when positive) inside
    [PACKED_WEIGHT_MIN, PACKED_WEIGHT_MAX] — negative weights break the
    monotone code, and out-of-range magnitudes could zero or saturate a
    real contribution's 16-bit code."""
    if d_pad >= PACKED_DOC_LIMIT:
        return False
    if weights is not None:
        w = np.asarray(weights)
        if w.size:
            if not np.isfinite(w).all() or bool((w < 0).any()):
                return False
            pos = w[w > 0]
            if pos.size and (float(pos.min()) < PACKED_WEIGHT_MIN
                             or float(pos.max()) > PACKED_WEIGHT_MAX):
                return False
    return True


def hierarchical_top_k(score: jax.Array, k: int, block: int = 4096,
                       split: Optional[bool] = None,
                       ) -> Tuple[jax.Array, jax.Array]:
    """top_k over [R, L] as per-block top-k' then a merge top-k — the
    full-width lax.top_k over T*L_c is the other half of the device
    floor at the 128-slot widths. Selection and tie-breaking are
    IDENTICAL to lax.top_k(score, k): with k' = min(k, block) a global
    winner is always inside its block's top-k', and equal values keep
    earliest-global-index preference because blocks merge in index
    order and each block's top_k is earliest-index-first among ties.
    Falls back to the flat top_k when the width doesn't split (L not a
    multiple of `block`, or k so large the merge wouldn't shrink).

    split=None picks per backend at trace time: the per-block reduction
    pays on sort-network backends (TPU lowers top_k to a bitonic sort
    of the FULL width, so blocking cuts real comparator work), while
    XLA:CPU's TopK custom call is already O(n) selection and the split
    only adds per-row dispatch overhead (measured ~5x slower at the
    32-slot serving width on the CPU).
    split=True forces the per-block path (parity tests exercise its
    merge logic on CPU); split=False forces flat."""
    r, length = score.shape
    kk = min(k, length)
    if split is None:
        split = jax.default_backend() == "tpu"
    with jax.named_scope("block_topk"):
        if not split or length <= block or kk >= block or length % block:
            return jax.lax.top_k(score, kk)
        n_blocks = length // block
        k_b = min(kk, block)
        v, p = jax.lax.top_k(score.reshape(r, n_blocks, block), k_b)
        base = (jnp.arange(n_blocks, dtype=jnp.int32)
                * block)[None, :, None]
        v = v.reshape(r, n_blocks * k_b)
        p = (p + base).reshape(r, n_blocks * k_b)
        vals, pos2 = jax.lax.top_k(v, kk)
        return vals, jnp.take_along_axis(p, pos2, axis=1)


def _rank_decode(ranks: jax.Array, r_start: jax.Array, r_len: jax.Array,
                 res_vals: jax.Array) -> jax.Array:
    """Exact f32 impact of each posting from its per-term rank code:
    rank r ≥ 1 indexes the term's ascending residual value table at
    r_start + r − 1; rank 0 (padding or a tombstone-zeroed posting)
    decodes to 0.0. ranks/r_start/r_len broadcast together (int32)."""
    ok = (ranks > 0) & (ranks <= r_len)
    at = r_start + jnp.maximum(ranks, 1) - 1
    vals = jnp.take(res_vals, at, mode="fill", fill_value=0.0)
    return jnp.where(ok, vals, 0.0)


def segmented_run_sum(sk: jax.Array, sv: jax.Array,
                      t_window: int) -> jax.Array:
    """Inclusive per-run prefix sums over a key-sorted [R, L] pair via
    Hillis-Steele doubling: after ceil(log2(t_window)) steps, each
    run-end position holds its run's full sum. Replaces the old linear
    T-tap shifted-add: work/compile now scale with
    log(T), so 32+ term queries (multi_match / fuzzy expansions) stay
    on the kernel path instead of falling off it."""
    length = sk.shape[1]
    total = sv
    step = 1
    with jax.named_scope("run_sum"):
        while step < t_window:
            shifted_t = jnp.pad(total, ((0, 0), (step, 0)))[:, :length]
            shifted_k = jnp.pad(sk, ((0, 0), (step, 0)),
                                constant_values=-1)[:, :length]
            total = total + jnp.where(shifted_k == sk, shifted_t, 0.0)
            step *= 2
    return total


@partial(jax.jit, static_argnames=("max_len", "d_pad", "k", "t_window",
                                   "with_counts", "with_totals",
                                   "variant"))
def sorted_merge_topk(
    flat_docs: jax.Array,    # int32[P_flat] doc ids (u16 when compressed,
                             # u8 deltas when doc_bases is given)
    flat_impact: jax.Array,  # f32[P_flat] impacts (u16 codes when compressed)
    starts: jax.Array,       # int32[R, T] absolute offsets into flat arrays
    lengths: jax.Array,      # int32[R, T] chunk lengths (0 = empty slot)
    weights: jax.Array,      # f32[R, T] idf·(k1+1)·boost per slot
    min_count: jax.Array,    # int32[R] minimum matched clauses (msm/AND)
    *,
    max_len: int,            # static: chunk length L_c
    d_pad: int,              # static: doc-axis pad (sentinel doc id)
    k: int,                  # static: top-k
    t_window: int,           # static: T (slot count = max same-doc entries)
    with_counts: bool,       # static: evaluate min_count (msm/AND)
    with_totals: bool = False,  # static: also return matched-doc counts
    variant: str = "ref",    # static: one of KERNEL_VARIANTS (module doc)
    flat_rank: Optional[jax.Array] = None,   # u16[P_flat] per-term ranks
    res_starts: Optional[jax.Array] = None,  # int32[R,T] residual offsets
    res_lens: Optional[jax.Array] = None,    # int32[R,T] residual lengths
    res_vals: Optional[jax.Array] = None,    # f32[RC] residual exact f32s
    block_max: Optional[jax.Array] = None,   # u16[NB+1] per-block max codes
    blk_starts: Optional[jax.Array] = None,  # int32[R,T] slot block indices
    slot_terms: Optional[jax.Array] = None,  # int32[R,T] term group id/slot
    doc_bases: Optional[jax.Array] = None,   # u16[NBD] delta block bases
    dbs_starts: Optional[jax.Array] = None,  # int32[R,T] slot base indices
    dlo_starts: Optional[jax.Array] = None,  # int32[R,T] slot offset % 128
) -> Tuple[jax.Array, ...]:
    """→ (scores f32[R, k'], doc_ids int32[R, k'][, totals int32[R]]);
    empty lanes are (-inf, d_pad). k' = min(k, T·L_c). totals (when
    with_totals) is the exact per-row count of matching docs — the
    TotalHits value of the reference's query phase. variant="packed"
    computes the same outputs bit-for-bit via the single-key sort +
    hierarchical top-k + exact rescore pipeline; callers must have
    checked packable() host-side. The compressed variants read u16
    doc/code streams plus residual tables (res_* operands required) and
    are also bit-identical to "ref" on the same postings; "compressed"
    additionally needs packable() weights, "compressed_exact" does not.
    block_max/blk_starts enable the block-max skip (compressed;
    inert when k > max_len; with_totals launches get exact totals from
    the pre-skip count sort). doc_bases/dbs_starts/dlo_starts switch the
    doc stream to the u8-delta format (delta_encode_docs)."""
    if variant not in KERNEL_VARIANTS:
        raise ValueError(f"unknown kernel variant {variant!r}")
    packed = variant == "packed"
    compressed = variant in COMPRESSED_VARIANTS
    if (packed or compressed) and d_pad >= PACKED_DOC_LIMIT:
        raise ValueError(
            f"variant {variant!r} needs d_pad < {PACKED_DOC_LIMIT}, got "
            f"{d_pad} — caller must fall back to variant='ref'")
    if compressed and (flat_rank is None or res_starts is None
                       or res_lens is None or res_vals is None):
        raise ValueError(
            "compressed variants need flat_rank/res_starts/res_lens/"
            "res_vals — build them with compress_flat()")
    if doc_bases is not None and (dbs_starts is None or dlo_starts is None):
        raise ValueError(
            "delta doc stream needs dbs_starts/dlo_starts alongside "
            "doc_bases")
    r, t_slots = starts.shape
    idx = jnp.arange(max_len, dtype=jnp.int32)

    def slice_one(s):
        return (jax.lax.dynamic_slice(flat_docs, (s,), (max_len,)),
                jax.lax.dynamic_slice(flat_impact, (s,), (max_len,)))

    with jax.named_scope("gather_streams"):
        docs, imps = jax.vmap(jax.vmap(slice_one))(starts)     # [R, T, L]
    valid = idx[None, None, :] < lengths[:, :, None]
    if compressed:
        if doc_bases is not None:
            # delta doc stream: lane doc = per-block u16 base + u8
            # delta. A slot window straddles at most max_len // 128 + 1
            # aligned blocks from its (dbs, dlo) cursor; slice one extra
            # so dynamic_slice never clamps (builders leave the slack)
            nb_slice = max_len // COMPRESSED_BLOCK + 2

            def base_slice(bs):
                return jax.lax.dynamic_slice(doc_bases, (bs,), (nb_slice,))

            bases = jax.vmap(jax.vmap(base_slice))(dbs_starts)
            lane_blk = ((dlo_starts[:, :, None] + idx[None, None, :])
                        // COMPRESSED_BLOCK)
            lane_base = jnp.take_along_axis(
                bases.astype(jnp.int32), lane_blk, axis=2)
            docs = jnp.where(
                valid, lane_base + docs.astype(jnp.int32), d_pad)
        else:
            docs = jnp.where(valid, docs.astype(jnp.int32), d_pad)
        codes = jnp.where(valid, imps.astype(jnp.uint32), 0)
        if variant == "compressed_exact":
            # decode every lane to its exact f32 through the residual
            # tables, then run the reference pipeline verbatim — exact
            # for ANY weights (the automatic fallback variant)
            def slice_rank(s):
                return jax.lax.dynamic_slice(flat_rank, (s,), (max_len,))

            ranks = jax.vmap(jax.vmap(slice_rank))(starts).astype(jnp.int32)
            ranks = jnp.where(valid, ranks, 0)
            lane_exact = _rank_decode(ranks, res_starts[:, :, None],
                                      res_lens[:, :, None], res_vals)
            imp = jnp.where(valid, weights[:, :, None] * lane_exact, 0.0)
        else:
            # lower-bound lane contributions from the decoded codes —
            # the packed pipeline's quantized values, without ever
            # materialising an f32 impact array in HBM
            imp = jnp.where(
                valid, weights[:, :, None] * decode_code16(codes), 0.0)
    else:
        docs = jnp.where(valid, docs, d_pad)
        imp = jnp.where(valid, weights[:, :, None] * imps, 0.0)

    length = t_slots * max_len
    kk = min(k, length)

    do_skip = (variant == "compressed"
               and block_max is not None and blk_starts is not None
               and k <= max_len)
    skip_totals = None
    if do_skip and with_totals:
        # exact TotalHits from the PRE-skip lanes: a skipped doc is
        # still a match, so totals cannot come from the post-skip sort.
        # One auxiliary u32 sort of (doc << 1 | positive-code bit) plus
        # the same run machinery counts exactly the docs the unskipped
        # pipeline would have counted — total > 0 there means "some
        # lane's decoded code is positive", which is precisely the
        # positive-code bit OR'd over the run
        posb = (impact_code16(imp) > 0).astype(jnp.uint32)
        ckey = jax.lax.sort(
            ((docs.astype(jnp.uint32) << 1) | posb).reshape(r, length))
        cdoc = (ckey >> 1).astype(jnp.int32)
        cpos = (ckey & 1).astype(jnp.float32)
        c_end = jnp.concatenate(
            [cdoc[:, :-1] != cdoc[:, 1:], jnp.ones((r, 1), bool)], axis=1)
        c_ok = c_end & (cdoc < d_pad) & (
            segmented_run_sum(cdoc, cpos, t_window) > 0)
        if with_counts:
            c_cnt = segmented_run_sum(cdoc, jnp.ones_like(cpos), t_window)
            c_ok = c_ok & (c_cnt >= min_count[:, None].astype(jnp.float32))
        skip_totals = jnp.sum(c_ok, axis=1, dtype=jnp.int32)
    if do_skip:
        # Block-max skip (device-side BMW/MaxScore). Threshold: within a
        # slot, lanes are DISTINCT docs, so a slot's k-th largest lane
        # value is a lower bound on the k-th best full score (each such
        # doc's full score ≥ its lane; all contributions non-negative,
        # and with min_count ≤ 1 every such doc is a real result).
        # Upper bound per 128-lane group: an unaligned group spans ≤ 2
        # aligned blocks, so max of two adjacent block codes; +1 on the
        # code is an open upper bound of any impact in the block. A group
        # is skipped only when its bound PLUS every other slot's window
        # bound stays strictly below the threshold — any doc with full
        # score ≥ thr therefore keeps all its lanes, and partially
        # skipped docs score strictly below thr even after rescore, so
        # results stay bit-identical (see module doc).
        n_grp = (max_len + COMPRESSED_BLOCK - 1) // COMPRESSED_BLOCK

        def bm_slice(bs):
            return jax.lax.dynamic_slice(block_max, (bs,), (n_grp + 1,))

        bm = jax.vmap(jax.vmap(bm_slice))(blk_starts)       # [R,T,G+1]
        grp_code = jnp.maximum(bm[..., :-1], bm[..., 1:]).astype(jnp.uint32)
        # clamp keeps the +1 from wrapping past the f32 space: anything
        # at/above the max finite code decodes to +inf (never skipped)
        ub = decode_code16(jnp.minimum(grp_code + 1, jnp.uint32(0x7F80)))
        g_base = (jnp.arange(n_grp, dtype=jnp.int32)
                  * COMPRESSED_BLOCK)[None, None, :]
        g_valid = g_base < lengths[:, :, None]
        w3 = weights[:, :, None]
        grp_ub = jnp.where(g_valid & (w3 > 0), w3 * ub, 0.0)
        slot_ub = jnp.max(grp_ub, axis=2)                    # [R,T]
        if slot_terms is not None:
            # a doc appears in at most ONE chunk of a term, so the
            # other-slots bound groups chunks by term: max over a
            # term's slots, sum over DISTINCT terms (MaxScore, not the
            # hopeless sum-over-all-slots on chunked rows)
            eq = slot_terms[:, :, None] == slot_terms[:, None, :]
            term_ub = jnp.max(
                jnp.where(eq, slot_ub[:, None, :], 0.0), axis=2)
            tri = jnp.tril(jnp.ones((t_slots, t_slots), bool), k=-1)
            first = ~jnp.any(eq & tri[None], axis=2)
            others = (jnp.sum(jnp.where(first, term_ub, 0.0),
                              axis=1, keepdims=True) - term_ub)
        else:
            others = jnp.sum(slot_ub, axis=1, keepdims=True) - slot_ub
        kth = jax.lax.top_k(imp, kk)[0][..., kk - 1]         # [R,T]
        enough = lengths >= kk
        thr = jnp.max(jnp.where(enough, kth, NEG_INF), axis=1)  # [R]
        if with_counts:
            thr = jnp.where(min_count <= 1, thr, NEG_INF)
        skip_grp = (grp_ub + others[:, :, None]) < thr[:, None, None]
        lane_skip = skip_grp[:, :, idx // COMPRESSED_BLOCK]
        docs = jnp.where(lane_skip, d_pad, docs)
        imp = jnp.where(lane_skip, 0.0, imp)

    if packed or variant == "compressed":
        # ONE uint32 sort key per lane: doc id high, impact code low —
        # half the sorted bytes of the (docs, imp) pair. Equal-doc lanes
        # stay contiguous (doc owns the high bits); padded lanes carry
        # (d_pad, code 0) and sort to the tail like the reference.
        key = ((docs.astype(jnp.uint32) << 16)
               | impact_code16(imp)).reshape(r, length)
        with jax.named_scope("merge_sort"):
            sk_key = jax.lax.sort(key)
        sk = (sk_key >> 16).astype(jnp.int32)
        # decoded codes are LOWER bounds of the exact lane impacts, so
        # total>0 tests and candidate ordering are conservative
        sv = decode_code16(sk_key & jnp.uint32(0xFFFF))
    else:
        with jax.named_scope("merge_sort"):
            sk, sv = jax.lax.sort(
                [docs.reshape(r, length), imp.reshape(r, length)],
                num_keys=1)

    total = segmented_run_sum(sk, sv, t_window)

    run_end = jnp.concatenate(
        [sk[:, :-1] != sk[:, 1:], jnp.ones((r, 1), bool)], axis=1)
    ok = run_end & (sk < d_pad) & (total > 0)

    cnt = None
    if with_counts or packed or variant == "compressed":
        # clause count per doc = run length (each slot holds a doc at most
        # once: postings rows have unique docs, chunks of one term
        # partition its row). Runs are ≤ t_window long by the same
        # argument, so the log-step scan sees the whole run. The packed
        # rescore needs it too: the run length is the matched-slot count.
        cnt = segmented_run_sum(sk, jnp.ones_like(sv), t_window)
    if with_counts:
        ok = ok & (cnt >= min_count[:, None].astype(jnp.float32))

    # totals BEFORE candidate selection: the count is a property of the
    # full sorted axis, and computing it here keeps every downstream
    # top-k shape (full-width or hierarchical) from being able to drop
    # or truncate it. When the block-max skip ran, the pre-skip count
    # sort already produced the exact value
    if not with_totals:
        totals = None
    elif skip_totals is not None:
        totals = skip_totals
    else:
        totals = jnp.sum(ok, axis=1, dtype=jnp.int32)

    score = jnp.where(ok, total, NEG_INF)
    if packed or variant == "compressed":
        res = None
        if variant == "compressed":
            res = (res_starts, res_lens, res_vals, flat_rank)
        delta = None
        if doc_bases is not None:
            delta = (doc_bases, dbs_starts, dlo_starts)
        vals, hit_docs = _packed_rescore_topk(
            flat_docs, flat_impact, starts, lengths, weights,
            sk, score, cnt, kk, max_len=max_len, d_pad=d_pad,
            t_window=t_window, res=res, delta=delta)
    else:
        with jax.named_scope("block_topk"):
            vals, pos = jax.lax.top_k(score, kk)
            hit_docs = jnp.take_along_axis(sk, pos, axis=1)
        hit_docs = jnp.where(vals > NEG_INF, hit_docs, d_pad)
    if with_totals:
        return vals, hit_docs, totals
    return vals, hit_docs


def _packed_rescore_topk(flat_docs, flat_impact, starts, lengths, weights,
                         sk, score, cnt, kk, *, max_len: int, d_pad: int,
                         t_window: int, res=None, delta=None):
    """Candidate selection + exact-f32 rescore for the packed variant.
    With res=(res_starts, res_lens, res_vals, flat_rank) the streams are
    the compressed u16 doc/code pair and each matched position's exact
    f32 comes from its rank code into the term's residual value table
    instead of from a resident f32 array.

    Selection: hierarchical top-k over the QUANTIZED run totals, with
    slack — a packed code is a lower bound within 2**-8 relative of
    its lane, so any true top-kk doc ranks above quantized-rank kk + m
    unless m+1 other docs land inside that relative band of the
    boundary. The compressed streams quantize TWICE (posting -> stored
    code at build, then w*decode(code) -> key code at sort), doubling
    the band to ~2**-7 and with it the number of docs a dense uniform
    term can pack against the boundary (~df/128 vs ~df/256), so their
    slack is doubled too. The slack makes the sweep-tested shapes
    exact in practice while the width stays a small multiple of kk
    instead of T*L_c.

    Rescore: each candidate's exact contribution per slot comes from a
    lower_bound binary search in that slot's doc-sorted chunk, then the
    matched contributions are compacted (stable, slot order — the same
    value order the reference's stable doc sort produces) and summed by
    the SAME log-step guarded scan over the same run length, so the
    f32 rounding tree is bit-identical to segmented_run_sum's and the
    returned scores equal variant="ref" exactly, not just closely.

    With delta=(doc_bases, dbs_starts, dlo_starts) the doc stream holds
    u8 block deltas (delta_encode_docs) and every random access decodes
    through the slot's block cursor: doc(pos) = bases[dbs + (dlo + pos −
    start) // 128] + delta[pos]. Positions outside the slot's window
    decode to d_pad, which also keeps the lo == end probe conservative."""
    r, t_slots = starts.shape
    length = sk.shape[1]
    slack = max(2 * kk, 256) if res is not None else max(2 * kk, 128)
    kc = min(length, kk + slack)
    a_vals, a_pos = hierarchical_top_k(score, kc)
    cand_docs = jnp.take_along_axis(sk, a_pos, axis=1)           # [R, kc]
    cand_cnt = jnp.take_along_axis(cnt, a_pos, axis=1).astype(jnp.int32)

    # exact per-slot contribution: lower_bound of the candidate doc in
    # each chunk's [start, start+len) range of the doc-sorted postings
    lo = jnp.broadcast_to(starts[:, None, :], (r, kc, t_slots))
    ln3 = jnp.broadcast_to(lengths[:, None, :], (r, kc, t_slots))
    end = lo + ln3
    hi = end
    target = cand_docs[:, :, None]
    if delta is None:
        def doc_at(pos):
            return jnp.take(flat_docs, pos, mode="fill", fill_value=d_pad)
    else:
        d_bases, dbs, dlo = delta
        st3 = starts[:, None, :]
        dbs3 = dbs[:, None, :]
        dlo3 = dlo[:, None, :]

        def doc_at(pos):
            jrel = pos - st3
            bidx = dbs3 + (dlo3 + jrel) // COMPRESSED_BLOCK
            base = jnp.take(d_bases, bidx, mode="fill",
                            fill_value=0).astype(jnp.int32)
            dd = jnp.take(flat_docs, pos, mode="fill",
                          fill_value=0).astype(jnp.int32)
            return jnp.where((jrel >= 0) & (jrel < ln3), base + dd, d_pad)
    for _ in range(max(1, int(max_len).bit_length())):
        active = lo < hi
        mid = (lo + hi) >> 1
        v = doc_at(mid)
        go = v < target
        lo = jnp.where(active & go, mid + 1, lo)
        hi = jnp.where(active & ~go, mid, hi)
    v = doc_at(lo)
    found = (ln3 > 0) & (lo < end) & (v == target) & (target < d_pad)
    if res is None:
        imp_exact = jnp.take(flat_impact, lo, mode="fill", fill_value=0.0)
    else:
        res_st, res_ln, r_vals, f_rank = res
        rank_at = jnp.take(f_rank, lo, mode="fill",
                           fill_value=0).astype(jnp.int32)
        imp_exact = _rank_decode(rank_at, res_st[:, None, :],
                                 res_ln[:, None, :], r_vals)
    contrib = jnp.where(found, weights[:, None, :] * imp_exact, 0.0)

    # compact matched slots to the front (stable ⇒ slot order preserved:
    # exactly the lane order of the reference's equal-doc run) and redo
    # the run sum with the reference's tree: the guarded log-step scan's
    # rounding order depends only on offset-in-run and step count, both
    # reproduced here, so the sums are bit-identical
    flat_rc = (r * kc, t_slots)
    comp_key, comp_val = jax.lax.sort(
        [jnp.where(found, 0, 1).astype(jnp.int32).reshape(flat_rc),
         contrib.reshape(flat_rc)], num_keys=1)
    run_pos = jnp.arange(t_slots, dtype=jnp.int32)[None, :]
    m = cand_cnt.reshape(r * kc, 1)
    scan_keys = jnp.where(run_pos < m, 0, run_pos + 1)
    scan_tot = segmented_run_sum(scan_keys, comp_val, t_window)
    gather_at = jnp.clip(m - 1, 0, t_slots - 1)
    exact = jnp.take_along_axis(scan_tot, gather_at,
                                axis=1).reshape(r, kc)
    exact = jnp.where(a_vals > NEG_INF, exact, NEG_INF)

    # final order on EXACT scores with the reference tie rule (equal
    # scores → smaller doc id); -inf lanes pinned to (+inf, d_pad) keys
    # so they tail-sort identically
    neg = jnp.where(exact > NEG_INF, -exact, jnp.inf)
    docs_key = jnp.where(exact > NEG_INF, cand_docs, d_pad)
    neg_s, docs_s = jax.lax.sort([neg, docs_key], num_keys=2)
    vals = jnp.where(jnp.isinf(neg_s[:, :kk]), NEG_INF, -neg_s[:, :kk])
    hit_docs = jnp.where(vals > NEG_INF, docs_s[:, :kk], d_pad)
    return vals, hit_docs


# ---------------------------------------------------------------------------
# host-side slot planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SlotPlan:
    """Chunked term slots for a batch of rows (query × shard pairs)."""

    starts: np.ndarray    # int32[R, T]
    lengths: np.ndarray   # int32[R, T]
    weights: np.ndarray   # f32[R, T]
    min_count: np.ndarray  # int32[R]
    max_len: int          # L_c (static bucket)
    t_slots: int          # T (static)
    window: int           # max same-doc entries per row = max terms/row
                          # (chunks of one term partition docs, so the
                          # kernel's t_window only needs to cover TERMS,
                          # not slots — far fewer taps on chunked queries)


def _len_bucket(n: int, lane: int = 128) -> int:
    b = lane
    while b < n:
        b *= 2
    return b


def _cap_bucket(cap: int, lane: int) -> int:
    """Largest lane-based power-of-two bucket that does NOT exceed cap
    (rounding the cap UP would overrun callers' flat-array slack)."""
    b = lane
    while b * 2 <= cap:
        b *= 2
    return b


def _split_row(row: Sequence[Tuple], max_len: int) -> List[Tuple]:
    out = []
    for e in row:
        if e[1] <= max_len:
            out.append(e)
        else:
            s, ln, w = e[0], e[1], e[2]
            out.extend((s + off, min(max_len, ln - off), w)
                       for off in range(0, ln, max_len))
    return out


def plan_slots(rows: Sequence[Sequence[Tuple]],
               min_counts: Sequence[int],
               chunk_cap: int = 4096,
               lane: int = 128,
               min_slots: int = 1) -> SlotPlan:
    """rows[r] = [(start, length, weight, ...), ...] — one entry per
    query term with its postings-row extent in the flat arrays (fields
    past the third are ignored). Long rows split into chunks of ≤ L_c
    where L_c = min(bucket(max row length), largest bucket ≤ chunk_cap);
    an empty extent keeps one zero-length slot, so min_count sees the
    term as present but unmatched. Returns padded static-shape slot
    tensors, T the next power of two over the widest row or `min_slots`
    if that is more. Plain Python over the entries, each tensor made once
    from a list (`dist.TermTable` says why)."""
    window = max(1, max(map(len, rows), default=0))
    longest = max(1, max(map(itemgetter(1), chain.from_iterable(rows)),
                         default=0))
    max_len = min(_len_bucket(longest, lane), _cap_bucket(chunk_cap, lane))
    if longest > max_len:
        rows = [_split_row(row, max_len)
                if any(e[1] > max_len for e in row) else row
                for row in rows]
    t_needed = max(1, max(map(len, rows), default=0))
    t_slots = 1
    while t_slots < t_needed:
        t_slots *= 2
    t_slots = max(t_slots, min_slots)

    n = len(rows) * t_slots
    starts, lengths, weights = [0] * n, [0] * n, [0.0] * n
    at = 0
    for row in rows:
        if row:
            end = at + len(row)
            starts[at:end], lengths[at:end], weights[at:end] = islice(
                zip(*row), 3)
        at += t_slots
    shape = (len(rows), t_slots)
    return SlotPlan(np.array(starts, dtype=np.int32).reshape(shape),
                    np.array(lengths, dtype=np.int32).reshape(shape),
                    np.array(weights, dtype=np.float32).reshape(shape),
                    np.array(min_counts, dtype=np.int32), max_len, t_slots,
                    window)


def eager_impacts(flat_docs: np.ndarray, flat_tfs: np.ndarray,
                  norms_u8: np.ndarray, k1: float, b: float,
                  avgdl: float) -> np.ndarray:
    """Precompute per-posting BM25 impacts (step 1 above). norms_u8 is the
    doc-axis norm column; flat_docs indexes into it (pad sentinel rows get
    impact 0 via tf==0)."""
    from elasticsearch_tpu.ops.smallfloat import LENGTH_TABLE
    d = norms_u8.shape[0]
    safe = np.minimum(flat_docs, d - 1)
    dl = LENGTH_TABLE[norms_u8[safe].astype(np.int64)].astype(np.float32)
    denom_add = (k1 * (1.0 - b + b * dl / (avgdl if avgdl > 0 else 1.0))
                 ).astype(np.float32)
    tf = flat_tfs.astype(np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        imp = tf / (tf + denom_add)
    return np.where(flat_tfs > 0, imp, 0.0).astype(np.float32)


def union_topk(scores_list, rows_list, ords_list, row_offsets, k: int):
    """Union-reduce per-pack kernel top-k columns (streaming delta path).

    The base pack and each resident delta pack run the device merge
    kernel independently; a doc lives in exactly one pack (deltas are
    append-only — an update of a committed doc forces a full rebuild),
    so the union is a pure k-way top-k over disjoint candidate sets: no
    dedup, totals add. Rows re-base into the concatenated union row
    space via ``row_offsets`` (per-pack starting row). Ties break by
    (score desc, pack order, in-pack kernel rank) so the reduce is
    deterministic and is the identity for a single operand.
    """
    scores = np.concatenate([np.asarray(s) for s in scores_list])
    rows = np.concatenate(
        [np.asarray(r, dtype=np.int64) + int(off)
         for r, off in zip(rows_list, row_offsets)])
    ords = np.concatenate([np.asarray(o) for o in ords_list])
    pack_tag = np.concatenate(
        [np.full(len(np.asarray(s)), i, dtype=np.int32)
         for i, s in enumerate(scores_list)])
    rank = np.concatenate(
        [np.arange(len(np.asarray(s)), dtype=np.int32)
         for s in scores_list])
    order = np.lexsort((rank, pack_tag, -scores))[:k]
    return scores[order], rows[order], ords[order]
