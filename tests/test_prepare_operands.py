"""A launch's query operands, built from the pack's `TermTable` (each
query term resolved once a launch), are byte for byte what the
per-(shard row, query, term) loops built before them.

The reference below is those loops as they stood (`prepare_query_batch`,
`sparse.plan_slots`, `prepare_term_ranges`, `term_weights`), kept here
so that the operands can be held to them: every array of the
`QueryBatch` and of the `prepare_term_ranges` triple equal in dtype,
shape and bytes (f32 weights and tail bounds included), and `max_len`,
`t_slots`, `window`, `truncated` and `need_counts` equal. The counter
`term_table` counts a query term once a launch, whatever the pack's
shard rows; a table holds columns only of terms its rows hold, and a new
pack builds its own.

The native builder of a full-path launch's fused operand
(`build_full_operands`, `native/launch_operands.c`) is held to the Python
builders' `pack_pruned_operands(prepare_query_batch(...),
*prepare_term_ranges(...))` byte for byte, with the plan's `t_slots`,
`max_len`, `window` and Σ lengths beside it.
"""

from __future__ import annotations

import ctypes
import dataclasses
import math
import sys
import threading
from typing import List, Tuple

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.segment import SegmentWriter
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops import sparse
from elasticsearch_tpu.parallel import distributed as dist

CHUNK_CAP = dist.CHUNK_CAP


# ---------------------------------------------------------------------------
# the reference: the loops the term table replaces
# ---------------------------------------------------------------------------

def ref_term_weights(pack, si, terms, boost=1.0):
    if pack.row_group is not None and pack.group_df is not None:
        g = pack.row_group[si]
        g_df = pack.group_df[g]
        g_docs = pack.group_doc_count[g]
    else:
        g_df = pack.df
        g_docs = pack.total_doc_count
    out = []
    for term in terms:
        dfv = g_df.get(term, 0)
        w = 0.0
        if dfv > 0:
            idf = math.log(1.0 + (g_docs - dfv + 0.5) / (dfv + 0.5))
            w = boost * idf * (pack.k1 + 1.0)
        out.append(w)
    return out


def ref_plan_slots(rows, min_counts, chunk_cap=4096, lane=128):
    longest = 1
    window = 1
    for row in rows:
        window = max(window, len(row))
        for (_, ln, _, _) in row:
            longest = max(longest, ln)
    max_len = min(sparse._len_bucket(longest, lane),
                  sparse._cap_bucket(chunk_cap, lane))
    chunked: List[List[Tuple[int, int, float, int]]] = []
    t_needed = 1
    for row in rows:
        out = []
        for (s, ln, w, tid) in row:
            off = 0
            while off < ln:
                take = min(max_len, ln - off)
                out.append((s + off, take, w, tid))
                off += take
            if ln == 0:
                out.append((s, 0, w, tid))
        chunked.append(out)
        t_needed = max(t_needed, len(out))
    t_slots = 1
    while t_slots < t_needed:
        t_slots *= 2
    r = len(rows)
    starts = np.zeros((r, t_slots), dtype=np.int32)
    lengths = np.zeros((r, t_slots), dtype=np.int32)
    weights = np.zeros((r, t_slots), dtype=np.float32)
    for ri, out in enumerate(chunked):
        for ti, (s, ln, w, _tid) in enumerate(out[:t_slots]):
            starts[ri, ti] = s
            lengths[ri, ti] = ln
            weights[ri, ti] = w
    return sparse.SlotPlan(starts, lengths, weights,
                           np.asarray(min_counts, dtype=np.int32), max_len,
                           t_slots, window)


def ref_prepare_query_batch(pack, queries, boosts=None, min_counts=None,
                            pad_batch_to=None, chunk_cap=CHUNK_CAP,
                            prefix_cap=None, imp_impacts=None,
                            pad_t_slots=None, pad_max_len=None,
                            compressed=None):
    b_real = len(queries)
    b = pad_batch_to or b_real
    s = pack.num_shards
    rows = []
    mins = []
    tail_bounds = (np.zeros((s, b), dtype=np.float32)
                   if prefix_cap is not None else None)
    truncated = False
    for si in range(s):
        vocab = pack.vocabs[si]
        rstart = pack.row_starts[si]
        for qi in range(b):
            if qi >= b_real:
                rows.append([])
                mins.append(1)
                continue
            terms = queries[qi]
            boost = boosts[qi] if boosts is not None else 1.0
            weights_r = ref_term_weights(pack, si, terms, boost)
            row = []
            for tid, term in enumerate(terms):
                w = weights_r[tid]
                r = vocab.get(term, -1)
                if r >= 0:
                    st = int(rstart[r])
                    ln = int(rstart[r + 1] - rstart[r])
                else:
                    st, ln = 0, 0
                if prefix_cap is not None and ln > prefix_cap:
                    tail_bounds[si, qi] += w * float(
                        imp_impacts[si, st + prefix_cap])
                    ln = prefix_cap
                    truncated = True
                row.append((st, ln, w, tid))
            rows.append(row)
            mins.append(int(min_counts[qi]) if min_counts is not None else 1)
    plan = ref_plan_slots(rows, mins, chunk_cap=chunk_cap)
    t_slots = plan.t_slots
    starts_a, lengths_a, weights_a = plan.starts, plan.lengths, plan.weights
    if pad_t_slots is not None and pad_t_slots > t_slots:
        pad = pad_t_slots - t_slots
        starts_a = np.pad(starts_a, ((0, 0), (0, pad)))
        lengths_a = np.pad(lengths_a, ((0, 0), (0, pad)))
        weights_a = np.pad(weights_a, ((0, 0), (0, pad)))
        t_slots = pad_t_slots
    max_len = plan.max_len
    if pad_max_len is not None and pad_max_len > max_len:
        max_len = pad_max_len
    shape3 = (s, b, t_slots)
    starts3 = starts_a.reshape(shape3)
    lengths3 = lengths_a.reshape(shape3)
    mc = plan.min_count.reshape(s, b)[0].copy()
    res_starts3 = res_lens3 = slot_terms3 = None
    if compressed is not None:
        res_starts3 = np.zeros(shape3, dtype=np.int32)
        res_lens3 = np.zeros(shape3, dtype=np.int32)
        slot_terms3 = np.zeros(shape3, dtype=np.int32)
        for si in range(s):
            rstart = pack.row_starts[si]
            n_rows = rstart.size - 1
            if n_rows <= 0:
                continue
            rr = np.searchsorted(rstart, starts3[si], side="right") - 1
            rr = np.clip(rr, 0, n_rows - 1)
            rrs = compressed.res_row_starts[si]
            slot_terms3[si] = rr.astype(np.int32)
            res_starts3[si] = rrs[rr].astype(np.int32)
            res_lens3[si] = (rrs[rr + 1] - rrs[rr]).astype(np.int32)
            zero = lengths3[si] == 0
            res_lens3[si][zero] = 0
    return dist.QueryBatch(starts3, lengths3, weights_a.reshape(shape3),
                           mc, max_len, t_slots, plan.window,
                           bool((mc > 1).any()),
                           tail_bounds=tail_bounds, truncated=truncated,
                           res_starts=res_starts3, res_lens=res_lens3,
                           slot_terms=slot_terms3)


def ref_prepare_term_ranges(pack, queries, boosts=None, pad_batch_to=None,
                            pad_terms=8):
    b_real = len(queries)
    b = pad_batch_to or b_real
    s = pack.num_shards
    starts = np.zeros((s, b, pad_terms), dtype=np.int32)
    lengths = np.zeros((s, b, pad_terms), dtype=np.int32)
    weights = np.zeros((s, b, pad_terms), dtype=np.float32)
    for si in range(s):
        vocab = pack.vocabs[si]
        rstart = pack.row_starts[si]
        for qi in range(b_real):
            terms = list(queries[qi])[:pad_terms]
            boost = boosts[qi] if boosts is not None else 1.0
            ws = ref_term_weights(pack, si, terms, boost)
            for t, term in enumerate(terms):
                r = vocab.get(term, -1)
                if r < 0:
                    continue
                starts[si, qi, t] = int(rstart[r])
                lengths[si, qi, t] = int(rstart[r + 1] - rstart[r])
                weights[si, qi, t] = ws[t]
    return starts, lengths, weights


# ---------------------------------------------------------------------------
# packs
# ---------------------------------------------------------------------------

def synthetic_pack(rng, n_rows: int, per_row_groups: bool,
                   long_rows: bool) -> dist.StackedShardPack:
    """A pack of `n_rows` shard rows over 60 terms `t0`…`t59`: each row
    holds a random subset (one row holds none), one term holds an empty
    postings row, `long_rows` gives some rows past CHUNK_CAP; the
    statistics hold `ghost`, which no row's vocabulary has."""
    vocabs, row_starts, lengths_of = [], [], []
    for si in range(n_rows):
        held = ([] if si == n_rows - 1 and n_rows > 1
                else sorted(rng.choice(60, size=int(rng.integers(20, 50)),
                                       replace=False).tolist()))
        terms = [f"t{t}" for t in held]
        if si == 0:
            terms = sorted(set(terms) | {"t7"})
        sizes = []
        for term in terms:
            if term == "t7":
                sizes.append(0)
            elif long_rows and rng.random() < 0.25:
                sizes.append(int(rng.integers(CHUNK_CAP - 50, 3 * CHUNK_CAP)))
            else:
                sizes.append(int(rng.integers(1, 900)))
        vocabs.append({t: i for i, t in enumerate(terms)})
        row_starts.append(np.concatenate([[0], np.cumsum(sizes)]).astype(
            np.int64))
        lengths_of.append(dict(zip(terms, sizes)))
    p_pad = max(int(rs[-1]) for rs in row_starts) + CHUNK_CAP
    docs_total = 40000
    groups = list(range(n_rows)) if per_row_groups else [0] * n_rows
    n_groups = max(groups) + 1
    group_df = [dict() for _ in range(n_groups)]
    df = {}
    for si, lens in enumerate(lengths_of):
        for term, n in lens.items():
            group_df[groups[si]][term] = group_df[groups[si]].get(term, 0) + n
            df[term] = df.get(term, 0) + n
    for g in range(n_groups):
        group_df[g]["ghost"] = 17 + g
    df["ghost"] = 17 * n_groups
    return dist.StackedShardPack(
        "body", n_rows, 1 << 16, p_pad,
        np.zeros((n_rows, p_pad), dtype=np.int32),
        rng.random((n_rows, p_pad)).astype(np.float32),
        np.zeros((n_rows, p_pad), dtype=np.int32),
        np.ones((n_rows, 1 << 16), dtype=bool),
        vocabs, row_starts, [docs_total] * n_rows, [[]] * n_rows,
        docs_total * n_rows, 50.0, df, k1=1.2, b=0.75,
        row_group=groups, group_df=group_df,
        group_doc_count=[docs_total * groups.count(g)
                         for g in range(n_groups)])


VOCAB = [f"w{i}" for i in range(30)]


def segment_pack(rng, n_rows: int, per_row_groups: bool):
    """A real pack: `n_rows` segments of toy documents over `w0`…`w29`."""
    ms = MapperService(Settings.EMPTY,
                       {"properties": {"body": {"type": "text"}}})
    segments = []
    for si in range(n_rows):
        w = SegmentWriter(f"seg{si}")
        for i in range(40):
            words = [VOCAB[min(int(rng.zipf(1.3)) - 1, len(VOCAB) - 1)]
                     for _ in range(int(rng.integers(1, 20)))]
            w.add_document(ms.parse_document(f"s{si}-d{i}",
                                             {"body": " ".join(words)}), {})
        segments.append(w.freeze())
    return dist.build_stacked_pack(
        segments, "body",
        row_groups=list(range(n_rows)) if per_row_groups else None)


def draw_queries(rng, pack, n, max_terms=5):
    """Terms some rows hold, `ghost` (no row, df > 0 in the statistics),
    `nowhere` (no row, no statistics), repeats, and one empty query."""
    held = sorted({t for v in pack.vocabs for t in v})
    pool = held + ["ghost", "nowhere"]
    out = []
    for qi in range(n):
        k = int(rng.integers(1, max_terms + 1))
        q = [pool[int(i)] for i in rng.integers(0, len(pool), size=k)]
        if qi % 5 == 1:
            q.append(q[0])              # a term twice in a query
        out.append(q)
    out[min(2, n - 1)] = []             # a query with no term
    return out


# ---------------------------------------------------------------------------
# byte identity
# ---------------------------------------------------------------------------

def same(a, b, what):
    if a is None or b is None:
        assert a is None and b is None, what
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, \
        (what, a.dtype, b.dtype, a.shape, b.shape)
    assert np.array_equal(a, b), what
    assert a.tobytes() == b.tobytes(), what


def same_batch(got: dist.QueryBatch, want: dist.QueryBatch):
    for f in ("starts", "lengths", "weights", "min_count", "tail_bounds",
              "res_starts", "res_lens", "slot_terms"):
        same(getattr(got, f), getattr(want, f), f)
    for f in ("max_len", "t_slots", "window", "need_counts", "truncated"):
        assert getattr(got, f) == getattr(want, f), f


CASES = {
    # name: (rows, per-row groups, long rows, queries, options)
    "one_row": (1, False, False, 9, {}),
    "two_rows": (2, False, False, 9, {}),
    "eight_rows": (8, False, False, 9, {}),
    "eight_rows_a_group_each": (8, True, False, 9, {}),
    "two_rows_a_group_each_boosted": (2, True, False, 9,
                                      {"boosts": True}),
    "long_rows_past_chunk_cap": (2, False, True, 12, {}),
    "long_rows_past_max_len": (8, True, True, 12, {"chunk_cap": 1000}),
    "and_min_counts": (8, False, True, 9, {"min_counts": True}),
    "padded_batch": (2, False, False, 5, {"pad_batch_to": 64}),
    "padded_slots": (8, False, False, 7, {"pad_t_slots": 32,
                                          "pad_max_len": CHUNK_CAP}),
    "prefix_tail_bounds": (8, True, True, 12, {"prefix_cap": 1024,
                                               "boosts": True,
                                               "pad_batch_to": 16}),
    "prefix_nothing_cut": (2, False, False, 6, {"prefix_cap": 4096}),
    "query_past_pad_terms": (2, False, False, 6, {"max_terms": 14}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_operands_equal_the_loops_byte_for_byte(seeded_np, name):
    rows, per_row, long_rows, n, opts = CASES[name]
    pack = synthetic_pack(seeded_np, rows, per_row, long_rows)
    queries = draw_queries(seeded_np, pack, n,
                           max_terms=opts.get("max_terms", 5))
    kw = {}
    if opts.get("boosts"):
        kw["boosts"] = [float(seeded_np.choice([0.5, 1.0, 2.5, 3.0]))
                        for _ in queries]
    if opts.get("min_counts"):
        kw["min_counts"] = [max(1, len(q)) for q in queries]
    for key in ("pad_batch_to", "chunk_cap", "pad_t_slots", "pad_max_len",
                "prefix_cap"):
        if key in opts:
            kw[key] = opts[key]
    if "prefix_cap" in kw:
        kw["imp_impacts"] = pack.flat_impact
    want = ref_prepare_query_batch(pack, queries, **kw)
    got = dist.prepare_query_batch(pack, queries, **kw)
    same_batch(got, want)
    if "prefix_cap" in kw and kw["prefix_cap"] < CHUNK_CAP:
        assert got.truncated and got.tail_bounds.any()
    rk = {k: kw[k] for k in ("boosts", "pad_batch_to") if k in kw}
    want_t = ref_prepare_term_ranges(pack, queries, pad_terms=8, **rk)
    got_t = dist.prepare_term_ranges(pack, got, boosts=kw.get("boosts"),
                                     pad_terms=8)
    for g, w, what in zip(got_t, want_t, ("starts", "lengths", "weights")):
        same(g, w, what)


@pytest.mark.parametrize("rows, per_row", [(1, False), (2, True), (8, False)])
def test_compressed_operands_equal_the_loops(seeded_np, rows, per_row):
    """A real pack's compressed streams: the residual extents and slot →
    term ids follow the slots' starts as they followed the loops'."""
    pack = segment_pack(seeded_np, rows, per_row)
    assert dist.compress_pack_reason(pack) is None
    streams = dist.build_compressed_streams(pack)
    queries = draw_queries(seeded_np, pack, 11)
    kw = dict(boosts=[1.0 + (i % 3) for i in range(len(queries))],
              min_counts=[1 + (i % 2) for i in range(len(queries))],
              pad_batch_to=16, pad_max_len=CHUNK_CAP, compressed=streams)
    same_batch(dist.prepare_query_batch(pack, queries, **kw),
               ref_prepare_query_batch(pack, queries, **kw))


@pytest.mark.parametrize("rows", [1, 2, 8])
def test_real_packs_equal_the_loops(seeded_np, rows):
    pack = segment_pack(seeded_np, rows, per_row_groups=rows > 1)
    queries = draw_queries(seeded_np, pack, 20)
    imp_docs, imp_impacts = dist.build_impact_sorted(pack)
    for kw in ({"pad_batch_to": 32},
               {"pad_batch_to": 32, "prefix_cap": 4,
                "imp_impacts": imp_impacts}):
        same_batch(dist.prepare_query_batch(pack, queries, **kw),
                   ref_prepare_query_batch(pack, queries, **kw))


@pytest.mark.parametrize("chunk_cap, lane", [(4096, 8), (3000, 128),
                                             (16, 8), (512, 128)])
def test_plan_slots_equals_the_loop(seeded_np, chunk_cap, lane):
    """`sparse.plan_slots` with the tuples `__graft_entry__` and the
    kernel tests give it plans as the loop did: empty rows, empty
    extents, long extents."""
    rows = []
    for r in range(12):
        row = []
        for tid in range(int(seeded_np.integers(0, 6))):
            ln = int(seeded_np.choice([0, 1, 7, 129, 2048, 5000]))
            row.append((int(seeded_np.integers(0, 10000)), ln,
                        float(seeded_np.random()) * 3.0, tid))
        rows.append(row)
    mins = [1 + (r % 3) for r in range(12)]
    got = sparse.plan_slots(rows, mins, chunk_cap=chunk_cap, lane=lane)
    want = ref_plan_slots(rows, mins, chunk_cap=chunk_cap, lane=lane)
    for f in ("starts", "lengths", "weights", "min_count"):
        same(getattr(got, f), getattr(want, f), f)
    for f in ("max_len", "t_slots", "window"):
        assert getattr(got, f) == getattr(want, f), f
    empty = sparse.plan_slots([], [], chunk_cap=chunk_cap, lane=lane)
    ref_empty = ref_plan_slots([], [], chunk_cap=chunk_cap, lane=lane)
    assert (empty.t_slots, empty.window, empty.starts.shape) == \
        (ref_empty.t_slots, ref_empty.window, ref_empty.starts.shape)


@pytest.mark.parametrize("rows, with_tail", [(1, False), (2, True),
                                             (8, False), (8, True)])
def test_fused_operands_carry_the_batch_and_its_term_ranges(
        seeded_np, rows, with_tail):
    """`pack_pruned_operands` lays the batch's slots, the term ranges
    read from its columns and the tail bounds side by side in the order
    the kernel slices them back, each the loops' to the byte."""
    pack = synthetic_pack(seeded_np, rows, False, True)
    queries = draw_queries(seeded_np, pack, 40)
    kw = dict(pad_batch_to=64, pad_t_slots=16)
    if with_tail:
        kw.update(prefix_cap=1024, imp_impacts=pack.flat_impact)
    batch = dist.prepare_query_batch(pack, queries, **kw)
    ops = dist.pack_pruned_operands(
        batch, *dist.prepare_term_ranges(pack, batch))
    want = ref_prepare_query_batch(pack, queries, **kw)
    want_t = ref_prepare_term_ranges(pack, queries, pad_batch_to=64)
    t, n = want.t_slots, want_t[0].shape[2]
    assert ops.dtype == np.float32 and ops.shape == (rows, 64, 3 * t + 3 * n + 1)
    at = 0
    for part in (want.starts, want.lengths, want.weights, *want_t):
        width = part.shape[2]
        same(ops[:, :, at:at + width].view(part.dtype), part, "ops")
        at += width
    same(ops[:, :, at], want.tail_bounds if with_tail
         else np.zeros((rows, 64), dtype=np.float32), "tail")


# ---------------------------------------------------------------------------
# the native builder of a full-path launch's fused operand
# ---------------------------------------------------------------------------

PAD_TERMS = 8


def native_full(pack, queries, boosts, rows, rung):
    assert dist.native_operand_builder() is not None
    terms = dist.resolve_launch_terms(
        pack, queries, boosts if boosts is not None else [1.0] * len(queries))
    assert terms is not None
    return dist.build_full_operands(pack, terms, rows, rung, PAD_TERMS)


def python_fused(pack, queries, boosts, rows, rung):
    """The Python builders' operand and batch, called as `_launch_pruned`
    calls them on the full path (less the pad of `max_len`)."""
    batch = dist.prepare_query_batch(pack, queries, boosts=boosts,
                                     min_counts=[1] * len(queries),
                                     pad_batch_to=rows, pad_t_slots=rung)
    t = dist.prepare_term_ranges(pack, batch, boosts=boosts,
                                 pad_terms=PAD_TERMS)
    return dist.pack_pruned_operands(batch, *t), batch


def ref_fused(pack, queries, boosts, rows, rung):
    """The loops' slots, term ranges and a zero tail side by side."""
    want = ref_prepare_query_batch(pack, queries, boosts=boosts,
                                   pad_batch_to=rows, pad_t_slots=rung)
    t = ref_prepare_term_ranges(pack, queries, boosts=boosts,
                                pad_batch_to=rows, pad_terms=PAD_TERMS)
    return np.concatenate(
        [want.starts.view(np.float32), want.lengths.view(np.float32),
         want.weights, t[0].view(np.float32), t[1].view(np.float32), t[2],
         np.zeros((pack.num_shards, rows, 1), dtype=np.float32)], axis=2)


@pytest.mark.parametrize("rows", [1, 2, 8])
@pytest.mark.parametrize("bucket", [8, 64, 128])
@pytest.mark.parametrize("rung", [16, 32, 128])
def test_native_operand_equals_the_python_builders(seeded_np, rows, bucket,
                                                   rung):
    """Boosts other than 1, terms no row holds (`nowhere`), terms some
    rows hold only, under a statistics group a row (a weight 0 where a
    row lacks the term) and under one group (the group's weight on a
    row without postings), extents past CHUNK_CAP split into chunks, an
    empty query and the rows the bucket pads: the native operand is the
    Python builders' to the byte, its `t_slots`, `max_len` and `window`
    are their batch's and its Σ lengths `lengths.sum()`; the Python
    builders, reading the columns the native call resolved first, are
    the loops'."""
    pack = synthetic_pack(seeded_np, rows, rows > 1 and rung != 32, True)
    queries = [[t for t in q if t != "ghost"]
               for q in draw_queries(seeded_np, pack, bucket - bucket // 8,
                                     max_terms=4)]
    boosts = [float(seeded_np.choice([1.0, 1.0, 0.5, 2.5])) for _ in queries]
    got = native_full(pack, queries, boosts, bucket, rung)
    want, batch = python_fused(pack, queries, boosts, bucket, rung)
    assert got.ops.shape == (rows, bucket, 3 * rung + 3 * PAD_TERMS + 1)
    same(got.ops, want, "ops")
    assert (got.t_slots, got.max_len, got.window) == \
        (batch.t_slots, batch.max_len, batch.window)
    assert got.t_slots == rung
    assert got.real == int(batch.lengths.sum()) > 0
    same(want, ref_fused(pack, queries, boosts, bucket, rung), "loops")


def test_the_native_builder_keeps_the_interpreter_lock():
    """Bound through `ctypes.PyDLL`: a launch's operand takes the call a
    fraction of a millisecond, and a call that let go of the lock would
    wait far longer than that to take it back from the request threads."""
    fn = dist.native_operand_builder()
    assert fn is not None and fn._flags_ & ctypes._FUNCFLAG_PYTHONAPI


@pytest.mark.parametrize("rows", [2, 8])
def test_a_column_the_table_does_not_keep_goes_to_the_python_builders(
        seeded_np, rows):
    """`nowhere` (no row, df 0 everywhere) reads as the all-zero row
    `ZERO_TERM`; `ghost` (no row holds it, yet the statistics give it a
    df, which no pack `build_stacked_pack` makes has) has a weight the
    table keeps no row for, so the native path declines its launch and
    counts no lookup; neither term grows the table."""
    pack = synthetic_pack(seeded_np, rows, True, False)
    held = sorted(pack.vocabs[0])[:2]
    before = _counts()
    terms = dist.resolve_launch_terms(pack, [[held[0], "nowhere"], [held[1]]],
                                      [1.0, 2.0])
    ids = terms.ids.tolist()
    assert ids[1] == dist.ZERO_TERM and dist.ZERO_TERM not in (ids[0], ids[2])
    assert terms.offsets.tolist() == [0, 2, 3]
    assert terms.boosts.tolist() == [1.0, 2.0]
    assert _rise(before) == {"lookups": 3, "columns": 2}
    assert dist.resolve_launch_terms(pack, [[held[0], "ghost"]], [1.0]) is None
    assert _rise(before) == {"lookups": 3, "columns": 2}
    assert set(pack.term_table._ids) == set(held)
    queries = [[held[0], "ghost", "nowhere"], [held[1]]]
    same_batch(dist.prepare_query_batch(pack, queries),
               ref_prepare_query_batch(pack, queries))


@pytest.mark.parametrize("rows", [1, 8])
def test_a_plan_wider_than_the_buffer_returns_the_error(seeded_np, rows):
    """Nine times a term past CHUNK_CAP on the first row need 18 or more
    slots: the call over a 16-slot buffer returns minus the plan's
    width and writes nothing, `build_full_operands` gives None, and the
    Python builders plan the launch at its own width as before."""
    pack = synthetic_pack(seeded_np, rows, False, True)
    start = pack.row_starts[0]
    long = next(t for t, r in sorted(pack.vocabs[0].items())
                if start[r + 1] - start[r] > CHUNK_CAP)
    queries = [[long] * 9, [long]]
    terms = dist.resolve_launch_terms(pack, queries, [1.0, 1.0])
    assert dist.build_full_operands(pack, terms, 8, 16, PAD_TERMS) is None
    batch = python_fused(pack, queries, None, 8, 16)[1]
    assert batch.t_slots >= 32
    out = np.full((rows, 8, 3 * 16 + 3 * PAD_TERMS + 1), 7.0, np.float32)
    info = np.zeros(4, dtype=np.int64)
    c = terms.columns
    rc = dist.native_operand_builder()(
        terms.ids.ctypes.data, terms.offsets.ctypes.data,
        terms.boosts.ctypes.data, 2, c.start.ctypes.data,
        c.length.ctypes.data, c.weight.ctypes.data, c.held.ctypes.data,
        c.idf.ctypes.data, terms.n_columns, rows, 8, 16, PAD_TERMS,
        CHUNK_CAP, 128, pack.k1 + 1.0, out.ctypes.data, info.ctypes.data)
    assert rc == -batch.t_slots
    assert (out == 7.0).all() and not info.any()
    wide = dist.build_full_operands(pack, terms, 8, batch.t_slots, PAD_TERMS)
    same(wide.ops, python_fused(pack, queries, None, 8, batch.t_slots)[0],
         "ops")


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

def _counts():
    return dict(dist.TERM_TABLE_COUNTS.counts())


def _rise(before):
    after = _counts()
    return {k: after[k] - before.get(k, 0) for k in after}


@pytest.mark.parametrize("rows", [2, 8])
def test_lookups_count_a_query_term_once_a_launch_whatever_the_rows(
        seeded_np, rows):
    """A pruned launch (`prepare_query_batch`, then `prepare_term_ranges`
    on its batch's columns) resolves B × T terms however many shard rows
    the pack has; a held term's column is built once and then kept."""
    pack = synthetic_pack(seeded_np, rows, False, False)
    held = sorted(pack.vocabs[0])[:4]
    queries = [held[:3], [held[1], "ghost"], [held[3], held[3], "nowhere"]]
    n_terms = sum(len(q) for q in queries)
    for launch in range(3):
        before = _counts()
        batch = dist.prepare_query_batch(pack, queries, pad_batch_to=8)
        dist.prepare_term_ranges(pack, batch)
        assert _rise(before) == {"lookups": n_terms,
                                 "columns": 4 if launch == 0 else 0}


@pytest.mark.parametrize("rows", [2, 8])
def test_terms_no_row_holds_never_grow_the_table(seeded_np, rows):
    """A stream of terms that no shard row's vocabulary holds (misspelt
    or junk, the statistics' `ghost` among them) keeps no column: the
    table stays at the held terms, the counter counts no column, and
    each such term still gets the weight the statistics give it."""
    pack = synthetic_pack(seeded_np, rows, True, False)
    held = sorted(pack.vocabs[0])[:2]
    dist.prepare_query_batch(pack, [held])
    kept = dict(pack.term_table._columns)
    before = _counts()
    for launch in range(20):
        queries = [[f"junk{launch}.{i}", "ghost", held[i % 2]]
                   for i in range(16)]
        same_batch(dist.prepare_query_batch(pack, queries),
                   ref_prepare_query_batch(pack, queries))
    assert pack.term_table._columns == kept
    assert _rise(before) == {"lookups": 20 * 16 * 3, "columns": 0}
    ghost = pack.term_table.resolve(pack, [["ghost"]])[0][0]
    assert [row[:2] for row in ghost] == [(0, 0)] * rows
    assert all(row[2] > 0 for row in ghost)


def test_threads_resolving_at_once_build_each_column_once(seeded_np):
    """Sixteen threads, more than the cores, build one pack's operands
    at once with the interpreter switching every microsecond, half of
    them through the native builder while the table's arrays grow under
    them: every term its rows hold gets one column (as many built as
    there are such terms, forty or more), and every batch and every
    fused operand is the loops'."""
    pack = synthetic_pack(seeded_np, 8, True, True)
    batches = [draw_queries(np.random.default_rng(i), pack, 12)
               + [[f"x{i}.{k}", "t3"] for k in range(8)] for i in range(16)]
    distinct = len({t for qs in batches for q in qs for t in q
                    if any(t in v for v in pack.vocabs)})
    errors = []

    def work(queries, native):
        try:
            for _ in range(3):
                if native:
                    # the table grows under the native builder's readers
                    qs = [[t for t in q if t != "ghost"] for q in queries]
                    same(native_full(pack, qs, None, 32, 32).ops,
                         ref_fused(pack, qs, None, 32, 32), "ops")
                else:
                    same_batch(dist.prepare_query_batch(pack, queries),
                               ref_prepare_query_batch(pack, queries))
        except Exception as exc:            # reported after the join
            errors.append(exc)

    interval = sys.getswitchinterval()
    before = _counts()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(qs, i % 2 == 1))
                   for i, qs in enumerate(batches)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert _rise(before)["columns"] == distinct
    assert len(pack.term_table._ids) == distinct >= 40
    assert len(pack.term_table._columns) <= distinct


def test_a_new_pack_never_reads_an_older_packs_columns(seeded_np):
    """A pack rebuilt from the same segments (a base generation), a delta
    pack of other segments and a `dataclasses.replace` copy each build
    their own columns, and each answers from its own rows."""
    ms = MapperService(Settings.EMPTY,
                       {"properties": {"body": {"type": "text"}}})

    def seg(name, texts):
        w = SegmentWriter(name)
        for i, text in enumerate(texts):
            w.add_document(ms.parse_document(f"{name}-{i}", {"body": text}),
                           {})
        return w.freeze()

    base_segs = [seg("a", ["red fox", "red dog"]), seg("b", ["blue fox"])]
    base = dist.build_stacked_pack(base_segs, "body")
    queries = [["red", "fox"], ["green"]]
    before = _counts()
    first = dist.prepare_query_batch(base, queries)
    assert _rise(before) == {"lookups": 3, "columns": 2}
    assert first.lengths[:, 1].sum() == 0          # no row holds "green"

    again = dist.build_stacked_pack(base_segs, "body")
    delta = dist.build_delta_pack([seg("c", ["green green", "green fox"])],
                                  "body")
    copy = dataclasses.replace(base, flat_impact=base.flat_impact.copy())
    for pack in (again, delta, copy):
        assert pack.term_table is not base.term_table
        before = _counts()
        batch = dist.prepare_query_batch(pack, queries)
        assert _rise(before) == {"lookups": 3, "columns": 2}
        same_batch(batch, ref_prepare_query_batch(pack, queries))
    assert dist.prepare_query_batch(delta, queries).lengths[0, 1].sum() == 2
    assert [row[:2] for row in base.term_table.resolve(base, [["green"]])[0][0]] \
        == [(0, 0)] * base.num_shards
