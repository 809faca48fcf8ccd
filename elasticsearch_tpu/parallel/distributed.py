"""Distributed BM25 search over a device mesh (SPMD, shard_map).

This is the TPU-native replacement for the reference's scatter-gather
search (SURVEY.md §3.3 / §2.3 P3): where the reference's coordinator fans a
query out to one copy of every shard over RPC (`AbstractSearchAsyncAction`)
and merges top-k on the coordinating node (`SearchPhaseController#
reducedQueryPhase`), here the fan-out is a `shard_map` over the "shards"
mesh axis and the merge is an `all_gather` + on-device top-k — zero host
hops inside a slice (SURVEY.md §5.8 ICI tier).

The per-device kernel is the impact-sorted-merge pipeline of
ops/sparse.py (gather chunks → sort by doc → windowed sum → top-k); this
module owns the data layout and the collective:

  StackedShardPack — S shards' postings as [S, ...] tensors with eager
    BM25 impacts, padded to common shapes, placed with NamedSharding over
    the "shards" axis. Statistics (idf, avgdl) are INDEX-level across all
    shards — the reference's dfs_query_then_fetch mode, the deterministic
    choice when doc partitioning is a mesh implementation detail.
  QueryBatch — per-(shard, query, slot) chunk tensors, sharded over
    ("shards", "data").

Global doc identity: shard s, local ordinal d → s * (d_pad + 1) + d (the
+1 keeps the kernel's d_pad sentinel lane decodable), decoded host-side by
`decode_refs` after the kernel returns (fetch resolves ordinals to _ids).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from functools import lru_cache, partial
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.metrics import LabeledCounters
from elasticsearch_tpu.index.pack import LANE, _pad_to
from elasticsearch_tpu.index.segment import Segment
from elasticsearch_tpu.ops import sparse
from elasticsearch_tpu.parallel.mesh import (DATA_AXIS, SHARD_AXIS,
                                             shard_map)

NEG_INF = float("-inf")
# One SPMD program enqueued on the shared device set at a time.
# shard_map programs carry cross-device collectives; when two threads
# (two services' batchers, or a batcher racing an abandoned wedged
# launch) dispatch concurrently, the per-device rendezvous can
# interleave in inconsistent order and wedge BOTH programs forever.
# Dispatch is async and cheap — execution is serialized by the
# hardware anyway — so holding this lock across enqueue costs nothing
# in steady state while making cross-thread launches safe.
DEVICE_DISPATCH_LOCK = threading.Lock()


def _named(fn, name: str):
    """`fn` under the name a profiler trace shows: jax.jit names a
    program `jit_<__name__>` on the device's `XLA Modules` line, so the
    makers below name what they jit after the launch path and its static
    width instead of leaving every program `jit_body`."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def exact_program_name(variant: str, rows: int, slots: int,
                       t_window: int) -> str:
    """The exact kernel's program under its static shape (batch rows,
    slots a row, run-sum window): `jit_<this>` on a trace's `XLA Modules`
    line, and the label of the launch's spans and counters."""
    return f"exact_{variant}_b{rows}_s{slots}_w{t_window}"


CHUNK_CAP = 4096  # max postings chunk per slot; flat arrays pad by this much
FUSE_ROWS = 8     # max segment rows fused into one phase-A sort pool
# phase-A gather/sort element budget per fused group (× ~8 bytes × a
# few sort buffers ≈ peak live HBM): the group size derives from this,
# so wide-slot × big-batch launches shrink their fusion instead of
# exhausting the 16G chip at MS-MARCO scale
FUSE_ELEM_BUDGET = 192 * 1024 * 1024


def fuse_group_rows(batch_b: int, t_slots: int, max_len: int) -> int:
    per_row = batch_b * t_slots * max_len
    return max(1, min(FUSE_ROWS, FUSE_ELEM_BUDGET // max(per_row, 1)))


@dataclasses.dataclass
class StackedShardPack:
    """S shards' postings for one field, stacked and padded to common shapes.

    Device tensors (sharded over the "shards" axis on a mesh):
      flat_docs   int32[S, P_pad] postings doc ids; pad sentinel = d_pad
      flat_impact f32[S, P_pad]   eager BM25 impacts (ops/sparse.py step 1)
      live        bool[S, D_pad]  live-doc masks (False = tombstone/padding)

    Host-side per shard: vocab dict, row_start offsets — plus index-level
    stats for idf/avgdl at query time. flat_tfs stays host-side only (to
    rebuild impacts when stats/k1/b change)."""

    field: str
    num_shards: int
    d_pad: int
    p_pad: int
    flat_docs: np.ndarray
    flat_impact: np.ndarray
    flat_tfs: np.ndarray
    live: np.ndarray
    vocabs: List[Dict[str, int]]
    row_starts: List[np.ndarray]
    shard_num_docs: List[int]
    shard_doc_ids: List[List[str]]
    total_doc_count: int
    avgdl: float
    df: Dict[str, int]
    k1: float = 1.2
    b: float = 0.75
    # statistics groups: row_group[i] names the stats group of row i (one
    # group per REAL index shard when rows are its segments). idf/avgdl are
    # then group-level — the reference's default query_then_fetch mode,
    # where Lucene stats are per-shard (SURVEY.md §3.3, CollectionStatistics
    # note). With one group for all rows this degrades to index-level stats
    # = the dfs_query_then_fetch mode.
    row_group: Optional[List[int]] = None
    group_df: Optional[List[Dict[str, int]]] = None
    group_doc_count: Optional[List[int]] = None
    # the query terms' columns (host memory): a pack is not mutated once
    # built, and `dataclasses.replace` gives the new pack a table of its
    # own, so no pack reads another's columns
    term_table: "TermTable" = dataclasses.field(
        init=False, repr=False, compare=False)

    def __post_init__(self):
        self.term_table = TermTable(self.num_shards)

    def nbytes_device(self) -> int:
        return (self.flat_docs.nbytes + self.flat_impact.nbytes
                + self.live.nbytes)


def _row_stats(pack: StackedShardPack, si: int) -> Tuple[Dict[str, int], int]:
    """(df by term, doc count) of pack row si's statistics group (per
    index shard → query_then_fetch parity; single group → dfs mode)."""
    if pack.row_group is not None and pack.group_df is not None:
        g = pack.row_group[si]
        return pack.group_df[g], pack.group_doc_count[g]
    return pack.df, pack.total_doc_count


def _idf(docs: int, dfv: int) -> float:
    return math.log(1.0 + (docs - dfv + 0.5) / (dfv + 0.5))


#: query terms resolved for launches' operands through their pack's
#: `TermTable` (`lookups`: the real queries' terms, once a launch whatever
#: the pack's shard rows) and the columns the tables keep (`columns`:
#: one a distinct term some shard row holds, for the pack's life)
#: → es_tpu_kernel_term_table_total
TERM_TABLE_COUNTS = LabeledCounters("kind")
for _kind in ("lookups", "columns"):
    TERM_TABLE_COUNTS.child(_kind)


#: a term's column: for each shard row, (postings start, postings length,
#: its weight at boost 1, whether the row's vocabulary holds the term,
#: the row's group idf): start and length 0 where the row lacks the term,
#: idf None and the weight 0.0 where the group's df is 0. The first three
#: are a `sparse.plan_slots` entry as they stand
Column = Tuple[Tuple[int, int, float, bool, Optional[float]], ...]


class ColumnArrays(NamedTuple):
    """A term table's columns as dense [capacity, shard rows] arrays, a
    term a row; idf NaN where a `Column` holds None."""

    start: np.ndarray    # int32
    length: np.ndarray   # int32
    weight: np.ndarray   # float64, at boost 1
    held: np.ndarray     # bool
    idf: np.ndarray      # float64

    @classmethod
    def empty(cls, capacity: int, shards: int) -> "ColumnArrays":
        shape = (capacity, shards)
        return cls(np.zeros(shape, np.int32), np.zeros(shape, np.int32),
                   np.zeros(shape, np.float64), np.zeros(shape, bool),
                   np.full(shape, np.nan))


#: the table row of every term whose column is all zero: held by no
#: shard row and of df 0 in every statistics group
ZERO_TERM = 0


class TermTable:
    """A pack's query terms, so that a launch's operands cost one dict
    lookup a query term and not one a (shard row, query term), and no
    `math.log` at all once a term is known. A column is built on its
    term's first use (`_row_stats`, `_idf`: what `term_weights`
    computes) and kept with the pack only if some shard row's
    vocabulary holds the term, so the table never outgrows the union of
    the rows' vocabularies, whatever terms users send. A term no row
    holds gets its column built anew at each use; where that column is
    all zero (df 0 in every group, as in any pack `build_stacked_pack`
    makes), it reads as the reserved row `ZERO_TERM`.

    The columns live in `ColumnArrays`, 25 B a (term, shard row), grown
    by doubling under the table's lock; a row once written never
    changes, so arrays taken under the lock stay valid for a reader
    after it lets go. `resolve_ids` gives the native operand builder
    (`build_full_operands`) a launch's term rows. `resolve` gives the
    Python builders (`prepare_query_batch`, `prepare_term_ranges`) each
    term's `Column`, a tuple view made on the term's first use by them
    and kept: they read it with plain Python and make each array once
    from a list, because numpy operations over a launch's entries each
    let go of the interpreter lock, and the launch thread then waits for
    it behind the request threads (on four chips, operand code that was
    numpy throughout waited 80 ms a train for 5 ms of work)."""

    def __init__(self, shards: int):
        self._ids: Dict[str, int] = {}
        self._columns: Dict[str, Column] = {}
        self._arrays = ColumnArrays.empty(16, shards)
        self._n = ZERO_TERM + 1
        self._heads: Optional[Dict[str, int]] = None
        self._lock = threading.Lock()

    def head_index(self, pack: StackedShardPack) -> Dict[str, int]:
        """The pack's head terms (`head_terms`), each with its row of the
        pack's head block: marked on first use, so that routing a query
        costs one dict lookup a term."""
        with self._lock:
            if self._heads is None:
                self._heads = {t: i for i, t in enumerate(head_terms(pack))}
            return self._heads

    def _add(self, pack: StackedShardPack, term: str) -> Tuple[int, Column]:
        """(the term's row, its column): a new row if some shard row
        holds the term, else `ZERO_TERM` for an all-zero column and -1
        for one the table does not keep."""
        k1p = pack.k1 + 1.0
        rows = []
        for si in range(pack.num_shards):
            r = pack.vocabs[si].get(term, -1)
            g_df, g_docs = _row_stats(pack, si)
            dfv = g_df.get(term, 0)
            idf = _idf(g_docs, dfv) if dfv > 0 else None
            # boost · idf · (k1 + 1) with the boost 1 (exact: 1 · idf = idf)
            w = 0.0 if idf is None else idf * k1p
            if r >= 0:
                rstart = pack.row_starts[si]
                rows.append((int(rstart[r]), int(rstart[r + 1] - rstart[r]),
                             w, True, idf))
            else:
                rows.append((0, 0, w, False, idf))
        column = tuple(rows)
        if not any(row[3] for row in rows):
            zero = all(row[4] is None for row in rows)
            return (ZERO_TERM if zero else -1), column
        i = self._n
        a = self._arrays
        if i == len(a.start):
            grown = ColumnArrays.empty(2 * i, pack.num_shards)
            for old, new in zip(a, grown):
                new[:i] = old
            a = self._arrays = grown
        for array, values in zip(a, zip(*rows)):
            array[i] = values  # an idf of None reads as NaN
        self._ids[term] = i
        self._n = i + 1
        return i, column

    def _column(self, pack: StackedShardPack, term: str) -> Column:
        """The Python builders' view of a term's column (under the lock)."""
        i = self._ids.get(term)
        if i is None:
            i, column = self._add(pack, term)
            if i <= ZERO_TERM:
                return column
        else:
            a = self._arrays
            column = tuple(zip(
                a.start[i].tolist(), a.length[i].tolist(),
                a.weight[i].tolist(), a.held[i].tolist(),
                [None if v != v else v for v in a.idf[i].tolist()]))
        self._columns[term] = column
        return column

    def resolve(self, pack: StackedShardPack,
                queries: Sequence[Sequence[str]]) -> List[List[Column]]:
        """Each query's terms' columns, built on first use."""
        with self._lock:
            known = self._n
            get = self._columns.get
            out = [[get(term) or self._column(pack, term) for term in q]
                   for q in queries]
            built = self._n - known
        TERM_TABLE_COUNTS.inc("lookups", n=sum(map(len, out)))
        if built:
            TERM_TABLE_COUNTS.inc("columns", n=built)
        return out

    def resolve_ids(self, pack: StackedShardPack,
                    queries: Sequence[Sequence[str]]
                    ) -> Optional[Tuple[List[int], ColumnArrays, int]]:
        """(the queries' terms' rows in query order, the arrays, the rows
        they hold), columns built on first use; None where a term's
        column is neither kept nor all zero (the Python builders read it)."""
        ids: List[int] = []
        with self._lock:
            known = self._n
            get = self._ids.get
            for q in queries:
                for term in q:
                    i = get(term)
                    if i is None:
                        i = self._add(pack, term)[0]
                    ids.append(i)
            arrays, n = self._arrays, self._n
        if n > known:
            TERM_TABLE_COUNTS.inc("columns", n=n - known)
        if min(ids, default=ZERO_TERM) < ZERO_TERM:
            return None
        TERM_TABLE_COUNTS.inc("lookups", n=len(ids))
        return ids, arrays, n


# ---------------------------------------------------------------------------
# head terms: a pack's terms of very high document frequency, scored as one
# dense block instead of through the sorted merge
# ---------------------------------------------------------------------------
#: a head term's dense column costs 4 B a doc; its postings cost some 16 B
#: each (`pack_bytes_per_posting`): the column costs no more HBM than the
#: postings where df ≥ docs ÷ HEAD_DF_DIVISOR
HEAD_DF_DIVISOR = 4
#: a head term's postings fill more than this many slots of CHUNK_CAP on
#: some shard row: more than the narrowest rung of the full-postings
#: ladder holds (`tpu_service.FULL_SLOT_BUCKETS`), so no query with it
#: shares that rung's sort, whatever its other terms
HEAD_MIN_SLOTS = 16
#: the most head terms a pack scores densely, the highest df first
HEADS_MAX = 32
#: docs a block maximum covers in the head-only top-k (one lane row)
HEAD_BLOCK_DOCS = 128


def head_terms(pack: StackedShardPack) -> List[str]:
    """The pack's head terms, highest df first (ties by term): those whose
    df over the pack's rows is at least a HEAD_DF_DIVISOR-th of its docs
    and whose postings fill more than HEAD_MIN_SLOTS slots on some shard
    row. So a pack whose rows hold at most HEAD_MIN_SLOTS · CHUNK_CAP
    docs (65,536) has no head term. At most HEADS_MAX. A function of the
    pack alone."""
    docs = sum(pack.shard_num_docs)
    if docs == 0:
        return []
    out = []
    for term, dfv in pack.df.items():
        if HEAD_DF_DIVISOR * dfv < docs:
            continue
        longest = 0
        for vocab, rstart in zip(pack.vocabs, pack.row_starts):
            r = vocab.get(term)
            if r is not None:
                longest = max(longest, int(rstart[r + 1] - rstart[r]))
        if longest > HEAD_MIN_SLOTS * CHUNK_CAP:
            out.append(term)
    out.sort(key=lambda t: (-pack.df[t], t))
    return out[:HEADS_MAX]


def build_head_block(pack: StackedShardPack, terms: Sequence[str]
                     ) -> np.ndarray:
    """float32[shard rows, h_pad, width]: row h of shard row s holds term
    h's BM25 impact at boost 1 for every doc of the row, the product a
    lane of the sorted merge computes (the term's weight at boost 1, as
    its `TermTable` column gives it, times the doc's eager impact, both
    float32), 0 where the doc lacks the term or is deleted. h_pad is the
    terms padded to 8, 16 or 32, width the longest row's docs padded to
    HEAD_BLOCK_DOCS: one shape a pack."""
    h_pad = _shape_bucket(len(terms), 8)
    width = _pad_to(max(pack.shard_num_docs, default=0), HEAD_BLOCK_DOCS)
    block = np.zeros((pack.num_shards, h_pad, width), dtype=np.float32)
    (columns,) = pack.term_table.resolve(pack, [list(terms)])
    for h, col in enumerate(columns):
        for si, (st, ln, w, held, _idf) in enumerate(col):
            if held and ln:
                docs = pack.flat_docs[si, st:st + ln]
                block[si, h, docs] = (np.float32(w)
                                      * pack.flat_impact[si, st:st + ln])
    return block


def build_stacked_pack(segments: Sequence[Segment], field: str,
                       live_docs: Optional[Sequence[Optional[np.ndarray]]] = None,
                       k1: float = 1.2, b: float = 0.75,
                       pad_shards_to: Optional[int] = None,
                       row_groups: Optional[Sequence[int]] = None,
                       pad_docs_to: Optional[int] = None,
                       pad_postings_to: Optional[int] = None
                       ) -> StackedShardPack:
    """Each segment is one doc-axis shard (SURVEY.md §2.3 P1). Shapes pad to
    the max across shards + CHUNK_CAP slack so chunk slices never clamp.

    row_groups[i] (optional) assigns segment i to a statistics group — one
    group per real index shard reproduces per-shard idf/avgdl (the
    reference's query_then_fetch). Omitted → one index-level group
    (dfs_query_then_fetch).

    pad_docs_to / pad_postings_to (optional) force the doc and posting
    axes to at least those sizes — the streaming delta path buckets
    shapes so successive small packs share compiled kernel signatures."""
    from elasticsearch_tpu.index.pack import build_field_pack

    s_real = len(segments)
    s = pad_shards_to or s_real
    if s < s_real:
        raise ValueError(
            f"pad_shards_to={s} < {s_real} segments (would drop shards)")
    d_pad = max(_pad_to(seg.num_docs) for seg in segments)
    if pad_docs_to is not None:
        if pad_docs_to < d_pad:
            raise ValueError(f"pad_docs_to={pad_docs_to} < d_pad={d_pad}")
        d_pad = pad_docs_to
    packs = [build_field_pack(seg, field, d_pad) for seg in segments]
    p_pad = max((p.flat_docs.shape[0] for p in packs if p is not None),
                default=LANE) + CHUNK_CAP
    if pad_postings_to is not None:
        if pad_postings_to < p_pad:
            raise ValueError(
                f"pad_postings_to={pad_postings_to} < p_pad={p_pad}")
        p_pad = pad_postings_to
    flat_docs = np.full((s, p_pad), d_pad, dtype=np.int32)
    flat_tfs = np.zeros((s, p_pad), dtype=np.int32)
    norms = np.zeros((s, d_pad), dtype=np.uint8)
    live = np.zeros((s, d_pad), dtype=bool)
    vocabs: List[Dict[str, int]] = []
    row_starts: List[np.ndarray] = []
    shard_num_docs: List[int] = []
    shard_doc_ids: List[List[str]] = []
    groups = list(row_groups) if row_groups is not None else [0] * s_real
    if len(groups) != s_real:
        raise ValueError(f"row_groups has {len(groups)} entries for "
                         f"{s_real} segments")
    n_groups = (max(groups) + 1) if groups else 1
    total_docs = 0
    sum_ttf = 0
    df: Dict[str, int] = {}
    group_df: List[Dict[str, int]] = [dict() for _ in range(n_groups)]
    group_doc_count = [0] * n_groups
    group_sum_ttf = [0] * n_groups
    for i, seg in enumerate(segments):
        fp = packs[i]
        g = groups[i]
        if fp is not None:
            n = fp.flat_docs.shape[0]
            flat_docs[i, :n] = fp.flat_docs
            flat_tfs[i, :n] = fp.flat_tfs
            norms[i] = fp.norms_u8
            vocabs.append(fp.vocab)
            row_starts.append(fp.row_start)
            for term, row in fp.vocab.items():
                dfv = int(fp.doc_freq[row])
                df[term] = df.get(term, 0) + dfv
                group_df[g][term] = group_df[g].get(term, 0) + dfv
        else:
            vocabs.append({})
            row_starts.append(np.zeros(1, dtype=np.int64))
        mask = (live_docs[i] if live_docs is not None and live_docs[i] is not None
                else np.ones(seg.num_docs, dtype=bool))
        live[i, : seg.num_docs] = mask
        shard_num_docs.append(seg.num_docs)
        shard_doc_ids.append(seg.doc_ids)
        st = seg.field_stats.get(field)
        if st:
            total_docs += st.doc_count
            sum_ttf += st.sum_total_term_freq
            group_doc_count[g] += st.doc_count
            group_sum_ttf[g] += st.sum_total_term_freq
    for _ in range(s_real, s):
        vocabs.append({})
        row_starts.append(np.zeros(1, dtype=np.int64))
        shard_num_docs.append(0)
        shard_doc_ids.append([])
        groups.append(0)
    avgdl = (sum_ttf / total_docs) if total_docs else 1.0
    group_avgdl = [(group_sum_ttf[g] / group_doc_count[g])
                   if group_doc_count[g] else 1.0 for g in range(n_groups)]
    flat_impact = np.zeros((s, p_pad), dtype=np.float32)
    for i in range(s_real):
        flat_impact[i] = sparse.eager_impacts(
            flat_docs[i], flat_tfs[i], norms[i], k1, b,
            group_avgdl[groups[i]])
        # tombstones bake into impacts: a dead doc's contributions all go
        # to 0, so the kernel's total>0 mask drops it (packs are derived
        # caches — a delete-refresh rebuilds them, SURVEY.md §5.4)
        safe = np.minimum(flat_docs[i], d_pad - 1)
        flat_impact[i] *= live[i][safe]
    return StackedShardPack(field, s, d_pad, p_pad, flat_docs, flat_impact,
                            flat_tfs, live, vocabs, row_starts,
                            shard_num_docs, shard_doc_ids, total_docs, avgdl,
                            df, k1, b, row_group=groups, group_df=group_df,
                            group_doc_count=group_doc_count)


def _shape_bucket(n: int, floor: int) -> int:
    """Smallest power-of-two-scaled multiple of `floor` that covers n."""
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


def build_delta_pack(segments: Sequence[Segment], field: str,
                     live_docs: Optional[Sequence[Optional[np.ndarray]]] = None,
                     k1: float = 1.2, b: float = 0.75,
                     pad_shards_to: Optional[int] = None,
                     row_groups: Optional[Sequence[int]] = None
                     ) -> StackedShardPack:
    """Small immutable pack for the streaming (LSM) delta path: identical
    format to `build_stacked_pack`, with two contracts layered on top.

    1. Shapes are padded UP to power-of-two buckets (doc axis from LANE,
       posting axis from 2*CHUNK_CAP) so a steady stream of small deltas
       reuses compiled kernel signatures — a per-delta XLA compile would
       dominate the append path and unbound the search-visible lag.
    2. Statistics partition: impacts bake `group_avgdl[row_group[i]]` at
       BUILD time, so a delta pack's scores reflect the stats of ITS OWN
       rows only (per-(delta,shard) groups). A full-rebuild oracle is
       bit-comparable to base ∪ deltas only when built with the same
       row_group partition — callers own that alignment."""
    d_raw = max(_pad_to(seg.num_docs) for seg in segments)
    from elasticsearch_tpu.index.pack import build_field_pack
    probe = [build_field_pack(seg, field, d_raw) for seg in segments]
    p_raw = max((p.flat_docs.shape[0] for p in probe if p is not None),
                default=LANE) + CHUNK_CAP
    return build_stacked_pack(
        segments, field, live_docs=live_docs, k1=k1, b=b,
        pad_shards_to=pad_shards_to, row_groups=row_groups,
        pad_docs_to=_shape_bucket(d_raw, LANE),
        pad_postings_to=_shape_bucket(p_raw, 2 * CHUNK_CAP))


@dataclasses.dataclass
class CompressedStreams:
    """Per-shard compressed resident streams (ops/sparse.compress_flat
    stacked over shards): three u16 streams replace the 8-byte
    doc-sorted pair AND the 8-byte impact-sorted copy at 6 bytes per
    posting, plus per-128-lane block-max metadata and the per-term f32
    residual tables the exact rescore reads ranks into. Shapes pad to
    common widths so the whole set device_puts with one NamedSharding
    over the "shards" axis.

    Delta-doc mode (PR 15): when every shard passes
    sparse.delta_doc_reason, the resident doc stream is the u8 DELTA
    stream (flat_docs8) plus per-aligned-block u16 bases (doc_bases) —
    ~1.02 B/posting instead of 2 — and flat_docs16 stays host-only
    (never placed). The kernel decodes lane docs and the rescore's
    random accesses through (doc_bases, dbs, dlo) cursors."""

    flat_docs16: np.ndarray   # u16[S, P_pad] doc ids (pad/sentinel = d_pad)
    flat_code16: np.ndarray   # u16[S, P_pad] monotone impact value codes
    flat_rank16: np.ndarray   # u16[S, P_pad] per-term residual ranks
    block_max: np.ndarray     # u16[S, NBp] block-max codes (+1 slack entry)
    res_vals: np.ndarray      # f32[S, RC_pad] residual tables, zero-padded
    res_row_starts: List[np.ndarray]  # per shard: i64[n_rows+1]
    flat_docs8: Optional[np.ndarray] = None  # u8[S, P_pad] block deltas
    doc_bases: Optional[np.ndarray] = None   # u16[S, NBD] block min doc ids

    @property
    def delta(self) -> bool:
        return self.doc_bases is not None

    def nbytes_device(self) -> int:
        """Exactly the bytes device_put_compressed places — the HBM
        breaker's estimate and hbm_detail's resident accounting. In
        delta mode the u16 doc stream is replaced by the u8 deltas plus
        the per-block base column."""
        doc_stream = (self.flat_docs8.nbytes + self.doc_bases.nbytes
                      if self.delta else self.flat_docs16.nbytes)
        return (doc_stream + self.flat_code16.nbytes
                + self.flat_rank16.nbytes + self.block_max.nbytes
                + self.res_vals.nbytes)


def compress_pack_reason(pack: StackedShardPack) -> Optional[str]:
    """First reason any shard of this pack can NOT take the compressed
    resident format (None = every shard compressible). Padding shard
    rows hold only sentinel/zero lanes and are always compressible."""
    for si in range(pack.num_shards):
        rstart = (pack.row_starts[si] if si < len(pack.row_starts)
                  else np.zeros(1, dtype=np.int64))
        reason = sparse.compress_reason(
            pack.flat_docs[si], pack.flat_impact[si], rstart, pack.d_pad)
        if reason is not None:
            return f"shard {si}: {reason}"
    return None


def delta_pack_reason(pack: StackedShardPack) -> Optional[str]:
    """First reason any shard's doc stream can NOT take the u8 delta
    encoding (None = the whole pack is delta-eligible). The delta gate
    is per PACK — the stacked device tensors need one uniform format —
    and failing shards keep the plain u16 doc stream for all."""
    for si in range(pack.num_shards):
        rstart = (pack.row_starts[si] if si < len(pack.row_starts)
                  else np.zeros(1, dtype=np.int64))
        reason = sparse.delta_doc_reason(pack.flat_docs[si], rstart)
        if reason is not None:
            return f"shard {si}: {reason}"
    return None


def build_compressed_streams(pack: StackedShardPack,
                             delta: Optional[bool] = None
                             ) -> CompressedStreams:
    """Run compress_flat per shard row and stack to common widths.
    Raises ValueError when compress_pack_reason() is non-None.

    delta=None auto-detects (delta_pack_reason); True forces the u8
    delta doc stream (raises if ineligible), False keeps the plain u16
    doc stream."""
    s, p_pad = pack.flat_docs.shape
    nbp = (p_pad + sparse.COMPRESSED_BLOCK - 1) // sparse.COMPRESSED_BLOCK + 1
    if delta is None:
        delta = delta_pack_reason(pack) is None
    docs16 = np.full((s, p_pad), min(pack.d_pad, (1 << 16) - 1),
                     dtype=np.uint16)
    code16 = np.zeros((s, p_pad), dtype=np.uint16)
    rank16 = np.zeros((s, p_pad), dtype=np.uint16)
    block_max = np.zeros((s, nbp), dtype=np.uint16)
    # the kernel slices max_len // 128 + 2 base entries from any slot's
    # block cursor; +2 slack past the last real block keeps that
    # dynamic_slice clamp-free (mirrors block_max's +1 slack entry)
    nbd = ((p_pad + sparse.COMPRESSED_BLOCK - 1) // sparse.COMPRESSED_BLOCK
           + 2)
    docs8 = np.zeros((s, p_pad), dtype=np.uint8) if delta else None
    doc_bases = np.zeros((s, nbd), dtype=np.uint16) if delta else None
    res_parts: List[np.ndarray] = []
    res_row_starts: List[np.ndarray] = []
    for si in range(s):
        rstart = (pack.row_starts[si] if si < len(pack.row_starts)
                  else np.zeros(1, dtype=np.int64))
        d16, c16, r16, bm, rv, rrs = sparse.compress_flat(
            pack.flat_docs[si], pack.flat_impact[si], rstart, pack.d_pad)
        docs16[si], code16[si], rank16[si] = d16, c16, r16
        block_max[si, :bm.size] = bm
        if delta:
            d8, db = sparse.delta_encode_docs(
                pack.flat_docs[si], rstart, nbd)
            docs8[si], doc_bases[si] = d8[:p_pad], db
        res_parts.append(rv)
        res_row_starts.append(rrs)
    rc_pad = _pad_to(max([rv.size for rv in res_parts] + [1]))
    res_vals = np.zeros((s, rc_pad), dtype=np.float32)
    for si, rv in enumerate(res_parts):
        res_vals[si, :rv.size] = rv
    return CompressedStreams(docs16, code16, rank16, block_max, res_vals,
                             res_row_starts, flat_docs8=docs8,
                             doc_bases=doc_bases)


def device_put_compressed(streams: CompressedStreams,
                          mesh: Optional[Mesh] = None):
    """Place the compressed tensors in HBM (sharded over "shards" when
    a mesh is given) — the compressed resident pack image. Plain mode
    places 5 arrays (docs16 first); delta mode places 6 with the u8
    delta stream in the doc slot plus the base column appended — the
    tuple LENGTH is the format discriminator downstream."""
    if streams.delta:
        arrays = (streams.flat_docs8, streams.flat_code16,
                  streams.flat_rank16, streams.block_max,
                  streams.res_vals, streams.doc_bases)
    else:
        arrays = (streams.flat_docs16, streams.flat_code16,
                  streams.flat_rank16, streams.block_max, streams.res_vals)
    if mesh is None:
        return tuple(jax.device_put(a) for a in arrays)
    sh = NamedSharding(mesh, P(SHARD_AXIS, None))
    return tuple(jax.device_put(a, sh) for a in arrays)


@dataclasses.dataclass
class QueryBatch:
    """Chunked slot tensors for B queries × S shards (ops/sparse.plan_slots
    run over all (shard, query) rows so the static (T, L_c) bucket is
    shared)."""

    starts: np.ndarray     # int32[S, B, T] relative to each shard's flat base
    lengths: np.ndarray    # int32[S, B, T]
    weights: np.ndarray    # f32[S, B, T]
    min_count: np.ndarray  # int32[B]
    max_len: int
    t_slots: int
    window: int            # max same-doc entries per row (= max terms/query)
    need_counts: bool      # any query has min_count > 1 (msm/AND)
    # pruned (block-max) mode only: per (shard,query) upper bound on the
    # score mass a doc can collect from TRUNCATED postings tails —
    # β_r = Σ_t w_t · impact_t[prefix_cap] (0 when nothing truncated)
    tail_bounds: Optional[np.ndarray] = None  # f32[S, B]
    truncated: bool = False  # any slot shorter than its full postings row
    # compressed-pack mode only (prepare_query_batch(compressed=...)):
    # per-slot residual-table extents (shard-relative) and the slot→term
    # group ids the kernel's block-max bound aggregates by
    res_starts: Optional[np.ndarray] = None   # int32[S, B, T]
    res_lens: Optional[np.ndarray] = None     # int32[S, B, T]
    slot_terms: Optional[np.ndarray] = None   # int32[S, B, T]
    # the queries' terms as the pack's TermTable resolved them: what
    # `prepare_term_ranges` reads without a lookup
    columns: Optional[List[List[Column]]] = None


def build_impact_sorted(pack: StackedShardPack
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-term impact-DESCENDING copies of the postings arrays — the
    block-max/WAND layout (SURVEY.md §5.7, §7.3#3): query time takes only
    each term's highest-impact prefix; everything it skips is bounded by
    the impact at the truncation point. Ties order by doc id so the
    layout is deterministic. Returns host (docs, impacts) [S, P_pad]."""
    s, p_pad = pack.flat_docs.shape
    imp_docs = pack.flat_docs.copy()
    imp_impacts = pack.flat_impact.copy()
    for si in range(s):
        rstart = pack.row_starts[si]
        total = int(rstart[-1])
        if total <= 1:
            continue
        # one lexsort per row: term-id primary (keeps row segments),
        # -impact secondary, doc tertiary (deterministic ties)
        term_ids = np.repeat(np.arange(len(rstart) - 1, dtype=np.int64),
                             np.diff(rstart))
        seg_doc = pack.flat_docs[si, :total]
        seg_imp = pack.flat_impact[si, :total]
        order = np.lexsort((seg_doc, -seg_imp, term_ids))
        imp_docs[si, :total] = seg_doc[order]
        imp_impacts[si, :total] = seg_imp[order]
    return imp_docs, imp_impacts


def term_weights(pack: StackedShardPack, si: int, terms: Sequence[str],
                 boost: float = 1.0) -> List[float]:
    """idf·(k1+1)·boost per term for pack row si, using the row's
    statistics group (`_row_stats`)."""
    g_df, g_docs = _row_stats(pack, si)
    out = []
    for term in terms:
        dfv = g_df.get(term, 0)
        w = 0.0
        if dfv > 0:
            w = boost * _idf(g_docs, dfv) * (pack.k1 + 1.0)
        out.append(w)
    return out


def exact_rescore(pack: StackedShardPack, candidates, terms: Sequence[str],
                  boost: float = 1.0):
    """Exact BM25 scores for candidate docs via the DOC-SORTED host
    arrays (block-max phase 2): candidates = [(row, ord), ...]. Returns
    f32 scores aligned with candidates. np.searchsorted per (row, term) —
    O(C·T·log df) host work for C ≤ a few thousand docs."""
    scores = np.zeros(len(candidates), dtype=np.float64)
    by_row: Dict[int, List[int]] = {}
    for i, (row, _ord) in enumerate(candidates):
        by_row.setdefault(row, []).append(i)
    for row, idxs in by_row.items():
        ords = np.array([candidates[i][1] for i in idxs], dtype=np.int64)
        vocab = pack.vocabs[row]
        rstart = pack.row_starts[row]
        ws = term_weights(pack, row, terms, boost)
        for t, term in enumerate(terms):
            r = vocab.get(term, -1)
            if r < 0 or ws[t] == 0.0:
                continue
            a, b_end = int(rstart[r]), int(rstart[r + 1])
            seg = pack.flat_docs[row, a:b_end]
            pos = np.searchsorted(seg, ords)
            safe = np.minimum(pos, len(seg) - 1)
            hit = (pos < len(seg)) & (seg[safe] == ords)
            contrib = ws[t] * pack.flat_impact[row, a + safe]
            scores[idxs] += np.where(hit, contrib, 0.0)
    return scores.astype(np.float32)


def prepare_query_batch(pack: StackedShardPack,
                        queries: Sequence[Sequence[str]],
                        boosts: Optional[Sequence[float]] = None,
                        min_counts: Optional[Sequence[int]] = None,
                        pad_batch_to: Optional[int] = None,
                        chunk_cap: int = CHUNK_CAP,
                        prefix_cap: Optional[int] = None,
                        imp_impacts: Optional[np.ndarray] = None,
                        pad_t_slots: Optional[int] = None,
                        pad_max_len: Optional[int] = None,
                        compressed: Optional[CompressedStreams] = None
                        ) -> QueryBatch:
    """Host-side planning: the queries' terms resolved once each through
    the pack's `TermTable`, their extents and group-level weights read
    for every shard row, chunk splitting (`sparse.plan_slots`).
    min_counts[i] = required matched clauses (1 = OR, len(terms) = AND).

    prefix_cap (block-max mode): truncate each term's slots to its top
    `prefix_cap` impact entries — valid ONLY against the impact-sorted
    arrays (`build_impact_sorted`), whose host `imp_impacts` must be given
    to read the tail bound at the truncation point.

    compressed: the pack's CompressedStreams — fills the batch's
    residual-table extents and slot→term ids so the compressed kernel
    variants can decode exact f32 impacts and aggregate block-max
    bounds per term."""
    if prefix_cap is not None and imp_impacts is None:
        raise ValueError("prefix_cap requires imp_impacts")
    b_real = len(queries)
    b = pad_batch_to or b_real
    if b < b_real:
        raise ValueError(
            f"pad_batch_to={b} < {b_real} queries (would drop queries)")
    if chunk_cap > CHUNK_CAP:
        # the pack's flat arrays carry exactly CHUNK_CAP slack; a larger
        # chunk bucket would let dynamic_slice read the next shard's rows
        raise ValueError(f"chunk_cap={chunk_cap} exceeds pack slack {CHUNK_CAP}")
    s = pack.num_shards
    columns = pack.term_table.resolve(pack, queries)
    query_boosts = list(boosts) if boosts is not None else [1.0] * b_real
    k1p = pack.k1 + 1.0
    rows: List[Sequence[Tuple]] = []
    tails: List[np.float32] = []  # prefix mode: a (row, query)'s bound
    truncated = False
    padding = [()] * (b - b_real)
    for si in range(s):
        for boost, cols in zip(query_boosts, columns):
            if boost == 1.0 and prefix_cap is None:
                # a column row's first three fields are the slot entry
                rows.append([col[si] for col in cols])
                continue
            row = []
            tail = np.float32(0.0)
            for col in cols:
                st, ln, w, _held, idf = col[si]
                if boost != 1.0:
                    w = 0.0 if idf is None else boost * idf * k1p
                if prefix_cap is not None and ln > prefix_cap:
                    # skipped tail entries all have impact ≤ the impact at
                    # the truncation point (impact-descending layout)
                    tail = tail + np.float32(
                        w * float(imp_impacts[si, st + prefix_cap]))
                    ln = prefix_cap
                    truncated = True
                row.append((st, ln, w))
            rows.append(row)
            tails.append(tail)
        rows.extend(padding)
        tails.extend([np.float32(0.0)] * (b - b_real))
    mins = ([int(m) for m in min_counts[:b_real]]
            if min_counts is not None else [1] * b_real) + [1] * (b - b_real)
    # serving stability: padding T and L_c to fixed values pins the jit
    # signature so the hot path never re-compiles (zero-length pad slots
    # cost sort lanes, not correctness)
    plan = sparse.plan_slots(rows, mins * s, chunk_cap=chunk_cap,
                             min_slots=pad_t_slots or 1)
    mc = plan.min_count[:b].copy()
    tail_bounds = (np.array(tails, dtype=np.float32).reshape(s, b)
                   if prefix_cap is not None else None)
    t_slots = plan.t_slots
    starts_a, lengths_a, weights_a = plan.starts, plan.lengths, plan.weights
    max_len = plan.max_len
    if pad_max_len is not None and pad_max_len > max_len:
        max_len = pad_max_len
    shape3 = (s, b, t_slots)
    starts3 = starts_a.reshape(shape3)
    lengths3 = lengths_a.reshape(shape3)
    res_starts3 = res_lens3 = slot_terms3 = None
    if compressed is not None:
        # per-slot term row (the chunk's start always lies inside its
        # term's postings row) → residual extents + term group ids; pad
        # slots (start 0, length 0) resolve to row 0 harmlessly
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
    return QueryBatch(starts3, lengths3,
                      weights_a.reshape(shape3),
                      mc, max_len, t_slots, plan.window,
                      bool((mc > 1).any()),
                      tail_bounds=tail_bounds, truncated=truncated,
                      res_starts=res_starts3, res_lens=res_lens3,
                      slot_terms=slot_terms3, columns=columns)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _local_body(flat_docs, flat_impact, starts, lengths, weights, min_count,
                *, max_len: int, d_pad: int, p_pad: int, k: int,
                t_window: int, with_counts: bool, shard_offset,
                variant: str = "ref", comp=None):
    """Score this device's S_l shards × B queries and return per-query
    (vals, global ids) merged over the local shards.

    flat_docs/flat_impact: [S_l, P_pad]; starts/lengths/weights:
    [S_l, B, T] (starts relative to each shard's base); min_count [B].
    Also returns totals int32[B]: exact matched-doc count over the local
    shards (the per-shard TotalHits partial).

    comp (compressed variants): (flat_rank [S_l, P_pad], block_max
    [S_l, NBp], res_vals [S_l, RC_pad], res_starts/res_lens/slot_terms
    [S_l, B, T], doc_bases [S_l, NBD] or None) — flattened here with
    per-shard offsets so the kernel's flat indices stay shard-local.
    With doc_bases present (delta doc stream) flat_docs carries u8
    deltas and each slot's base cursor (dbs = shard-relative start //
    128 offset into the flattened bases, dlo = start % 128) is derived
    here — the kernel can't recover either from the absolute starts."""
    s_l, b, t = starts.shape
    base = jnp.arange(s_l, dtype=jnp.int32) * p_pad
    starts_abs = starts + base[:, None, None]
    r = s_l * b
    extra = {}
    if comp is not None:
        (flat_rank, block_max, res_vals, res_starts, res_lens,
         slot_terms, doc_bases) = comp
        nbp = block_max.shape[1]
        rcp = res_vals.shape[1]
        sb = jnp.arange(s_l, dtype=jnp.int32)[:, None, None]
        blk = starts // sparse.COMPRESSED_BLOCK + sb * nbp
        extra = dict(flat_rank=flat_rank.reshape(-1),
                     res_starts=(res_starts + sb * rcp).reshape(r, t),
                     res_lens=res_lens.reshape(r, t),
                     res_vals=res_vals.reshape(-1),
                     block_max=block_max.reshape(-1),
                     blk_starts=blk.reshape(r, t),
                     slot_terms=slot_terms.reshape(r, t))
        if doc_bases is not None:
            nbd = doc_bases.shape[1]
            dbs = starts // sparse.COMPRESSED_BLOCK + sb * nbd
            extra.update(doc_bases=doc_bases.reshape(-1),
                         dbs_starts=dbs.reshape(r, t),
                         dlo_starts=(starts
                                     % sparse.COMPRESSED_BLOCK
                                     ).reshape(r, t))
    vals, docs, totals = sparse.sorted_merge_topk(
        flat_docs.reshape(-1), flat_impact.reshape(-1),
        starts_abs.reshape(r, t), lengths.reshape(r, t),
        weights.reshape(r, t),
        jnp.tile(min_count, s_l),
        max_len=max_len, d_pad=d_pad, k=k, t_window=t_window,
        with_counts=with_counts, with_totals=True, variant=variant,
        **extra)
    k_l = vals.shape[1]
    vals = vals.reshape(s_l, b, k_l)
    docs = docs.reshape(s_l, b, k_l)
    totals_b = jnp.sum(totals.reshape(s_l, b), axis=0)
    shard_ids = shard_offset + jnp.arange(s_l, dtype=jnp.int64)
    gids = docs.astype(jnp.int64) + (shard_ids * (d_pad + 1))[:, None, None]
    # [S_l, B, k_l] -> [B, S_l*k_l]; sentinel doc (=d_pad) keeps -inf score
    vals_b = jnp.transpose(vals, (1, 0, 2)).reshape(b, -1)
    gids_b = jnp.transpose(gids, (1, 0, 2)).reshape(b, -1)
    return vals_b, gids_b, totals_b


def _merge_topk(vals_b, gids_b, k: int, variant: str = "ref"):
    if variant in ("packed", "compressed"):
        top_vals, pos = sparse.hierarchical_top_k(
            vals_b, min(k, vals_b.shape[1]))
    else:
        top_vals, pos = jax.lax.top_k(vals_b, min(k, vals_b.shape[1]))
    top_ids = jnp.take_along_axis(gids_b, pos, axis=1)
    return top_vals, top_ids


@lru_cache(maxsize=64)
def make_local_search(*, max_len: int, d_pad: int, p_pad: int, k: int,
                      t_window: int, with_counts: bool = False,
                      variant: str = "ref"):
    """Single-device search step: S shards × B queries → global top-k.
    The compile-check entry point (`__graft_entry__.py`).
    lru_cached so repeated bucket signatures reuse the jitted step (and
    its XLA compile cache) instead of re-tracing per call."""

    if variant in sparse.COMPRESSED_VARIANTS:
        def step(flat_docs, flat_impact, flat_rank, block_max, res_vals,
                 starts, lengths, weights, res_starts, res_lens,
                 slot_terms, min_count, doc_bases=None):
            vals_b, gids_b, totals_b = _local_body(
                flat_docs, flat_impact, starts, lengths, weights, min_count,
                max_len=max_len, d_pad=d_pad, p_pad=p_pad, k=k,
                t_window=t_window, with_counts=with_counts,
                shard_offset=jnp.int64(0), variant=variant,
                comp=(flat_rank, block_max, res_vals,
                      res_starts, res_lens, slot_terms, doc_bases))
            top_vals, top_ids = _merge_topk(vals_b, gids_b, k, variant)
            return top_vals, top_ids, totals_b

        return jax.jit(_named(step, f"local_{variant}"))

    def step(flat_docs, flat_impact, starts, lengths, weights, min_count):
        vals_b, gids_b, totals_b = _local_body(
            flat_docs, flat_impact, starts, lengths, weights, min_count,
            max_len=max_len, d_pad=d_pad, p_pad=p_pad, k=k,
            t_window=t_window, with_counts=with_counts,
            shard_offset=jnp.int64(0), variant=variant)
        top_vals, top_ids = _merge_topk(vals_b, gids_b, k, variant)
        return top_vals, top_ids, totals_b

    return jax.jit(_named(step, f"local_{variant}"))


@lru_cache(maxsize=256)
def make_distributed_search(mesh: Mesh, *, max_len: int, d_pad: int,
                            p_pad: int, k: int, t_window: int,
                            with_counts: bool = False,
                            variant: str = "ref",
                            delta: bool = False,
                            name: Optional[str] = None):
    """SPMD search step over a (data, shards) mesh: local sorted-merge
    per device, then all_gather over "shards" + final top-k on device
    (SURVEY.md §5.8: the P3 reduce rides ICI). lru_cached by (mesh, bucket
    signature) so the query path hits the jit cache instead of re-tracing
    every batch. The program is `jit_<name>` on a trace:
    `distributed_search_raw` names it after its static shape
    (`exact_program_name`), one jitted step a shape; without a name it
    is `jit_exact_<variant>` whatever it is called with."""
    name = name or f"exact_{variant}"

    def tail(vals_b, gids_b, totals_b):
        with jax.named_scope("cross_chip_merge"):
            all_vals = jax.lax.all_gather(vals_b, SHARD_AXIS, axis=1,
                                          tiled=True)
            all_ids = jax.lax.all_gather(gids_b, SHARD_AXIS, axis=1,
                                         tiled=True)
            totals = jax.lax.psum(totals_b, SHARD_AXIS)  # TotalHits reduce
            top_vals, top_ids = _merge_topk(all_vals, all_ids, k, variant)
        return top_vals, top_ids, totals

    spec_post = P(SHARD_AXIS, None)
    spec_sbt = P(SHARD_AXIS, DATA_AXIS, None)
    out_specs = (P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS))

    if variant in sparse.COMPRESSED_VARIANTS:
        # delta mode appends the per-block doc-base column as a 6th
        # postings-sharded operand (the static `delta` flag keys the
        # lru cache so plain and delta packs get distinct programs)
        def body(flat_docs, flat_impact, flat_rank, block_max, res_vals,
                 starts, lengths, weights, res_starts, res_lens,
                 slot_terms, min_count, *maybe_bases):
            doc_bases = maybe_bases[0] if delta else None
            s_l = flat_docs.shape[0]
            my = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int64)
            vals_b, gids_b, totals_b = _local_body(
                flat_docs, flat_impact, starts, lengths, weights, min_count,
                max_len=max_len, d_pad=d_pad, p_pad=p_pad, k=k,
                t_window=t_window, with_counts=with_counts,
                shard_offset=my * s_l, variant=variant,
                comp=(flat_rank, block_max, res_vals,
                      res_starts, res_lens, slot_terms, doc_bases))
            return tail(vals_b, gids_b, totals_b)

        in_specs = ((spec_post,) * 5 + (spec_sbt,) * 6 + (P(DATA_AXIS),)
                    + ((spec_post,) if delta else ()))
        mapped = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
        return jax.jit(_named(mapped, name))

    def body(flat_docs, flat_impact, starts, lengths, weights, min_count):
        s_l = flat_docs.shape[0]
        my = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int64)
        vals_b, gids_b, totals_b = _local_body(
            flat_docs, flat_impact, starts, lengths, weights, min_count,
            max_len=max_len, d_pad=d_pad, p_pad=p_pad, k=k,
            t_window=t_window, with_counts=with_counts,
            shard_offset=my * s_l, variant=variant)
        return tail(vals_b, gids_b, totals_b)

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(spec_post, spec_post, spec_sbt, spec_sbt, spec_sbt,
                  P(DATA_AXIS)),
        out_specs=out_specs)
    return jax.jit(_named(mapped, name))


def prepare_term_ranges(pack: StackedShardPack, batch: QueryBatch,
                        boosts: Optional[Sequence[float]] = None,
                        pad_terms: int = 8):
    """Per-TERM (unchunked) postings ranges for the device-side exact
    re-score of `batch`'s queries, read from the columns
    `prepare_query_batch` resolved for it: (starts, lengths, weights)
    int32/f32[S, B, T_terms], B the batch's, a query's first `pad_terms`
    terms, 0 on a row whose vocabulary lacks the term."""
    s, b = batch.starts.shape[:2]
    query_boosts = (list(boosts) if boosts is not None
                    else [1.0] * len(batch.columns))
    k1p = pack.k1 + 1.0
    n = s * b * pad_terms
    starts, lengths, weights = [0] * n, [0] * n, [0.0] * n
    for si in range(s):
        at = si * b * pad_terms
        for boost, cols in zip(query_boosts, batch.columns):
            for i, col in enumerate(cols[:pad_terms], at):
                st, ln, w, held, idf = col[si]
                if held:
                    starts[i], lengths[i] = st, ln
                    weights[i] = (w if boost == 1.0 else
                                  0.0 if idf is None else boost * idf * k1p)
            at += pad_terms
    shape = (s, b, pad_terms)
    return (np.array(starts, dtype=np.int32).reshape(shape),
            np.array(lengths, dtype=np.int32).reshape(shape),
            np.array(weights, dtype=np.float32).reshape(shape))


def pack_pruned_operands(batch: QueryBatch, t_starts: np.ndarray,
                         t_lengths: np.ndarray, t_weights: np.ndarray
                         ) -> np.ndarray:
    """Fuse the 7 per-launch query tensors into ONE [S, B, W] f32 array
    (ints bitcast): the batch ships as a single host→device transfer and
    the kernel slices/bitcasts it back, so a launch pays one transfer's
    fixed cost instead of seven (per-transfer cost unmeasured on today's
    machine)."""
    tail = (batch.tail_bounds[:, :, None] if batch.tail_bounds is not None
            else np.zeros(batch.starts.shape[:2] + (1,),
                          dtype=np.float32))
    parts = [batch.starts.view(np.float32), batch.lengths.view(np.float32),
             batch.weights,
             t_starts.view(np.float32), t_lengths.view(np.float32),
             t_weights, tail]
    return np.concatenate(parts, axis=2)


_OPERANDS_FN = None
_OPERANDS_TRIED = False


def native_operand_builder():
    """`es_pruned_operands` (native/launch_operands.c), bound on first
    use; None where the library did not build (the Python builders
    serve every launch)."""
    global _OPERANDS_FN, _OPERANDS_TRIED
    if not _OPERANDS_TRIED:
        import ctypes

        from elasticsearch_tpu import native
        p, i32, i64 = ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64
        _OPERANDS_FN = native.bind(
            "launch_operands", "es_pruned_operands", i64,
            [p, p, p, i32, p, p, p, p, p, i32, i32, i32, i32, i32, i64, i64,
             ctypes.c_double, p, p], hold_gil=True)
        _OPERANDS_TRIED = True
    return _OPERANDS_FN


class LaunchTerms(NamedTuple):
    """A launch's query terms as rows of its pack's term table."""

    ids: np.ndarray      # int32[Σ terms]: the rows, in query order
    offsets: np.ndarray  # int32[queries + 1]: query q's are [o[q], o[q+1])
    boosts: np.ndarray   # float64[queries]
    columns: ColumnArrays
    n_columns: int       # the rows `columns` held when the ids were read


def resolve_launch_terms(pack: StackedShardPack,
                         queries: Sequence[Sequence[str]],
                         boosts: Sequence[float]) -> Optional[LaunchTerms]:
    """The queries' terms resolved through the pack's `TermTable`, once
    each, into the arrays `build_full_operands` reads; None where a term's
    column is not in the table (`TermTable.resolve_ids`)."""
    resolved = pack.term_table.resolve_ids(pack, queries)
    if resolved is None:
        return None
    ids, columns, n_columns = resolved
    offsets = [0]
    for q in queries:
        offsets.append(offsets[-1] + len(q))
    return LaunchTerms(np.array(ids, dtype=np.int32),
                       np.array(offsets, dtype=np.int32),
                       np.array(boosts, dtype=np.float64), columns, n_columns)


class FullOperands(NamedTuple):
    ops: np.ndarray  # float32[S, B, 3·t_slots + 3·pad_terms + 1]
    t_slots: int
    max_len: int     # L_c of the slot plan, before any pad
    window: int
    real: int        # Σ of the slots' lengths


def build_full_operands(pack: StackedShardPack, terms: LaunchTerms,
                        rows: int, t_slots: int, pad_terms: int,
                        chunk_cap: int = CHUNK_CAP, lane: int = 128
                        ) -> Optional[FullOperands]:
    """A full-postings launch's fused operand from one native call that
    holds no Python object: `pack_pruned_operands(
    prepare_query_batch(pack, queries, boosts, pad_batch_to=rows,
    pad_t_slots=t_slots), *prepare_term_ranges(...))` byte for byte,
    with the slot plan's L_c, window and Σ lengths beside it, and no
    numpy operation over the launch's entries on the calling thread.
    None where the plan needs more than `t_slots` slots (the caller's
    Python builders then plan it at its own width)."""
    fn = native_operand_builder()
    c = terms.columns
    if (c.start.shape != (len(c.start), pack.num_shards)
            or not len(c.start) >= terms.n_columns > ZERO_TERM
            or len(terms.offsets) != len(terms.boosts) + 1):
        raise ValueError("launch terms that are not this pack's table's")
    out = np.empty((pack.num_shards, rows, 3 * t_slots + 3 * pad_terms + 1),
                   dtype=np.float32)
    info = np.empty(4, dtype=np.int64)
    rc = fn(terms.ids.ctypes.data, terms.offsets.ctypes.data,
            terms.boosts.ctypes.data, len(terms.boosts),
            c.start.ctypes.data, c.length.ctypes.data, c.weight.ctypes.data,
            c.held.ctypes.data, c.idf.ctypes.data, terms.n_columns,
            pack.num_shards, rows, t_slots, pad_terms, chunk_cap, lane,
            pack.k1 + 1.0, out.ctypes.data, info.ctypes.data)
    if rc == -1:
        raise ValueError("es_pruned_operands: an input out of range")
    if rc < 0:
        return None
    return FullOperands(out, *map(int, info))


def _row_groups(starts, lengths, weights, max_len: int, p_pad: int):
    """A device's slots [S_l, B, T] in groups of shard rows that the merge
    fuses into one sort pool a query (sort cost is row-count-bound on a
    TPU, so fusing rows pays; G rows a group by `fuse_group_rows`, so that
    one group's lanes are live at a time) → (G, (starts made absolute,
    lengths, weights, each slot's row), [n_groups, B, G·T] each)."""
    s_l, b, t = starts.shape
    row_of_slot = jnp.broadcast_to(
        jnp.arange(s_l, dtype=jnp.int32)[:, None, None], starts.shape)
    starts_abs = starts + row_of_slot * p_pad
    g = min(fuse_group_rows(b, t, max_len), s_l)
    n_groups = (s_l + g - 1) // g
    pad_rows = n_groups * g - s_l

    def grouped(a):
        if pad_rows:
            a = jnp.concatenate(
                [a, jnp.zeros((pad_rows,) + a.shape[1:], dtype=a.dtype)],
                axis=0)
        return jnp.transpose(
            a.reshape(n_groups, g, b, t), (0, 2, 1, 3)
        ).reshape(n_groups, b, g * t)

    return g, tuple(grouped(a) for a in (starts_abs, lengths, weights,
                                         row_of_slot))


def _lane_streams(flat_docs, flat_imps, starts, lengths, weights,
                  max_len: int):
    """The merge's lanes of slots [B, T] over flat postings: each slot's
    `max_len` doc ids and weighted impacts, and which of its lanes hold a
    posting (impact 0 on the others) → three [B, T, max_len] arrays."""
    idx = jnp.arange(max_len, dtype=jnp.int32)

    def slice_one(s):
        return (jax.lax.dynamic_slice(flat_docs, (s,), (max_len,)),
                jax.lax.dynamic_slice(flat_imps, (s,), (max_len,)))

    with jax.named_scope("gather_streams"):
        docs, imps = jax.vmap(jax.vmap(slice_one))(starts)
    valid = idx[None, None, :] < lengths[:, :, None]
    return docs, jnp.where(valid, weights[:, :, None] * imps, 0.0), valid


def _merge_sort(docs, imp, valid, rows, d_pad: int):
    """The lanes sorted by their gid key, row·(d_pad+1)+doc (distinct
    docs across rows never merge; an empty lane takes the row's sentinel
    doc d_pad and impact 0) → (keys, impacts), [B, T·max_len] each."""
    b = docs.shape[0]
    gid = rows[:, :, None] * (d_pad + 1) + jnp.where(valid, docs, d_pad)
    with jax.named_scope("merge_sort"):
        return jax.lax.sort([gid.reshape(b, -1), imp.reshape(b, -1)],
                            num_keys=1)


def _run_totals(sk, sv, t_window: int):
    """Each run of equal keys' sum at its last lane, and which lanes end
    a run with a sum above 0 (a doc that matched)."""
    total = sparse.segmented_run_sum(sk, sv, t_window)
    run_end = jnp.concatenate(
        [sk[:, :-1] != sk[:, 1:], jnp.ones((sk.shape[0], 1), bool)], axis=1)
    return total, run_end & (total > 0.0)


@lru_cache(maxsize=32)
def make_pruned_search(mesh: Mesh, *, max_len: int, d_pad: int, p_pad: int,
                       c_cand: int, k_out: int, t_window: int,
                       t_terms: int, search_iters: Optional[int] = None,
                       c_local: Optional[int] = None,
                       with_rescore: bool = True,
                       variant: str = "ref",
                       pack_keys: bool = False,
                       name: str = "pruned"):
    """Block-max serving step, ONE fused launch (SURVEY.md §5.7/§7.3#3).
    `name`: the launch path and its static width as the launch site
    knows them (`full_s32`, `hot_c<prefix_cap>`); the program is
    `jit_<name>` on a trace's `XLA Modules` line.

      phase A  candidate generation over impact-sorted postings prefixes
               (the small sorted-merge) → global top-c_cand via
               all_gather + top_k;
      phase B  exact re-score of every candidate ON DEVICE: vectorized
               binary search in the doc-sorted postings (each device
               scores its local rows, psum over the shards axis), so
               scores are exact BM25 while only [B, k_out] leaves the
               device — the device→host link never carries the candidate
               pool.

    Returns (exact_vals [B,k_out], gids [B,k_out], totals [B],
    cutoff [B], beta [B]); the caller checks the WAND validity bound
    `exact_kth ≥ (cutoff if full else 0) + beta` host-side with its
    actual k and falls back to the exact kernel when it fails.

    pack_keys=True (variant="packed" + rescore tiers only) packs each
    phase-A lane's GROUP-RELATIVE gid and 16-bit impact code into ONE
    u32 sort key when the group's gid range fits 16 bits — halving the
    sort operands like the exact packed kernel. The caller must have
    verified sparse.packable(d_pad, t_weights) host-side; phase-A run
    totals become quantized LOWER bounds (match counts stay exact, and
    phase B re-scores exactly), so the returned cutoff is inflated by
    the quantization slack to keep the host validity check conservative.
    Groups whose gid range overflows 16 bits keep the two-operand sort."""
    if search_iters is None:
        # a postings row is at most d_pad docs long
        search_iters = max(1, math.ceil(math.log2(d_pad + 1)))
    if c_local is None:
        # per-DEVICE candidate cut (phase A fuses this device's rows
        # into one pool): the full c_cand, so a single hot device can
        # still supply every global candidate; the device cutoff folds
        # into the validity bound regardless
        c_local = c_cand

    def body(fd_imp, fi_imp, fd_ds, fi_ds, ops):
        # unpack the fused operand (pack_pruned_operands): one
        # host→device transfer instead of seven
        t = (ops.shape[2] - 3 * t_terms - 1) // 3

        def bc(a):
            return jax.lax.bitcast_convert_type(a, jnp.int32)

        starts = bc(ops[:, :, 0:t])
        lengths = bc(ops[:, :, t:2 * t])
        weights = ops[:, :, 2 * t:3 * t]
        t_starts = bc(ops[:, :, 3 * t:3 * t + t_terms])
        t_lengths = bc(ops[:, :, 3 * t + t_terms:3 * t + 2 * t_terms])
        t_weights = ops[:, :, 3 * t + 2 * t_terms:3 * t + 3 * t_terms]
        tail_bound = ops[:, :, 3 * t + 3 * t_terms]
        s_l, b = starts.shape[0], starts.shape[1]
        my = jax.lax.axis_index(SHARD_AXIS)

        # ---- phase A, FUSED over local rows in GROUPS: rows merge
        # into [b, G·t·L] sorts per query on shard-offset gid keys —
        # sort cost is ROW-count-bound on TPU (measured: 4x wider at
        # 1/4 the rows ≈ same sort time), so fusing is ~1.5x on phase
        # A. Groups of ≤ FUSE_ROWS sequence through lax.map so only ONE
        # group's gather/sort intermediates are live — all-rows fusion
        # at 16 rows × B=128 OOM'd 24G of 16G HBM at MS-MARCO scale.
        flat_imp_docs = fd_imp.reshape(-1)
        flat_imp_imps = fi_imp.reshape(-1)
        g, (g_starts, g_lengths, g_weights, g_rows) = _row_groups(
            starts, lengths, weights, max_len, p_pad)
        n_groups = g_starts.shape[0]
        width = g * t * max_len
        k_dev = min(c_local, width)
        # single-key sort applies only when a group's relative gid range
        # (g rows × (d_pad+1) ords) fits the 16 high bits of a u32 key;
        # the no-rescore tier is excluded — ITS phase-A totals ARE the
        # returned scores, and quantizing them would change results
        use_pack = (pack_keys and variant == "packed" and with_rescore
                    and g * (d_pad + 1) <= sparse.PACKED_DOC_LIMIT)

        def one_group(opnds):
            f_starts, f_lengths, f_weights, f_rows = opnds
            docs, imp, valid = _lane_streams(
                flat_imp_docs, flat_imp_imps, f_starts, f_lengths,
                f_weights, max_len)
            if use_pack:
                # group-relative gid in the high 16 bits, impact code in
                # the low 16: ONE u32 sort operand. Padded rows (zeros
                # from grouped()) clamp to grel 0 / doc d_pad — the
                # first row's sentinel run, impact 0, dropped by total>0
                # exactly like the two-operand path. The group's first
                # slot is never a pad row, so f_rows[0, 0] is the
                # group's base row.
                row0 = f_rows[0, 0]
                grel = jnp.maximum(f_rows - row0, 0)
                gid_p = (grel[:, :, None] * (d_pad + 1)
                         + jnp.where(valid, docs, d_pad)).astype(jnp.uint32)
                key = (gid_p << 16) | sparse.impact_code16(imp)
                with jax.named_scope("merge_sort"):
                    skp = jax.lax.sort(key.reshape(b, width))
                sk = ((skp >> 16).astype(jnp.int32)
                      + row0 * (d_pad + 1))
                sv = sparse.decode_code16(skp & 0xFFFF)
            else:
                sk, sv = _merge_sort(docs, imp, valid, f_rows, d_pad)
            total, ok = _run_totals(sk, sv, t_window)
            score = jnp.where(ok, total, NEG_INF)
            totals_g = jnp.sum(ok, axis=1).astype(jnp.int32)
            # when the single-key sort doesn't apply (gid range overflows
            # 16 bits, no-rescore tier, or pack_keys off) the pruned path
            # still takes the hierarchical top-k half of the packed
            # variant; selection and tie-breaks are provably identical
            # to lax.top_k
            with jax.named_scope("block_topk"):
                if variant == "packed":
                    vals_g, pos = sparse.hierarchical_top_k(score, k_dev)
                else:
                    vals_g, pos = jax.lax.top_k(score, k_dev)
                gid_g = jnp.take_along_axis(sk, pos, axis=1)
            return vals_g, gid_g, totals_g

        if n_groups == 1:
            vals_g, gid_g, totals_g = one_group(
                (g_starts[0], g_lengths[0], g_weights[0], g_rows[0]))
            vals_b, gid_local, totals_b = vals_g, gid_g, totals_g
            cut_local = vals_b[:, -1]
        else:
            vals_gs, gid_gs, totals_gs = jax.lax.map(
                one_group, (g_starts, g_lengths, g_weights, g_rows))
            # [n_groups, B, k_dev] → [B, n_groups·k_dev]
            vals_b = jnp.transpose(vals_gs, (1, 0, 2)).reshape(b, -1)
            gid_local = jnp.transpose(gid_gs, (1, 0, 2)).reshape(b, -1)
            totals_b = jnp.sum(totals_gs, axis=0)
            # a doc cut in ANY group fell below ITS group's k_dev-th
            cut_local = jnp.max(vals_gs[:, :, -1], axis=0)
        # local gid → global gid (row offset by this device's first row)
        gids_b = (gid_local.astype(jnp.int64)
                  + (my * s_l).astype(jnp.int64) * (d_pad + 1))
        gids_b = jnp.where(vals_b > NEG_INF, gids_b, 0)

        # per-device/group approx cutoff: docs cut THERE are bounded by
        # it in the validity check
        # the cross-chip merge: every device's candidates to every
        # device, then the global pool's top c on each
        with jax.named_scope("cross_chip_merge"):
            row_cut = jax.lax.pmax(cut_local, SHARD_AXIS)
            all_vals = jax.lax.all_gather(vals_b, SHARD_AXIS, axis=1,
                                          tiled=True)
            all_gids = jax.lax.all_gather(gids_b, SHARD_AXIS, axis=1,
                                          tiled=True)
            totals = jax.lax.psum(totals_b, SHARD_AXIS)
            c = min(c_cand, all_vals.shape[1])
            if variant == "packed":
                cand_vals, pos = sparse.hierarchical_top_k(all_vals, c)
            else:
                cand_vals, pos = jax.lax.top_k(all_vals, c)
            cand_gids = jnp.take_along_axis(all_gids, pos, axis=1)  # [B, C]

        if with_rescore:
            # ---- phase B: exact re-score of candidates,
            # TERM-VECTORIZED: one [B, C, T] take per search iteration
            # instead of T separate [B, C] takes (fewer, larger
            # gathers — measured ~1.5x) ----
            gid32 = cand_gids.astype(jnp.int32)
            row = gid32 // (d_pad + 1)
            ord_ = gid32 % (d_pad + 1)
            local_row = row - (my * s_l).astype(jnp.int32)
            in_local = (local_row >= 0) & (local_row < s_l)
            lr = jnp.clip(local_row, 0, s_l - 1)
            flat_ds = fd_ds.reshape(-1)
            flat_imp = fi_ds.reshape(-1)
            qsel = jnp.arange(b, dtype=jnp.int32)[:, None]
            st = t_starts[lr, qsel]                     # [B, C, T]
            ln = t_lengths[lr, qsel]
            w = t_weights[lr, qsel]
            lo = (lr * p_pad)[:, :, None] + st
            hi = lo + ln
            ord3 = ord_[:, :, None]
            end = hi  # region end: a lower_bound landing here ran off
            #           the term's postings into the NEXT term's region
            for _ in range(search_iters):  # lower_bound binary search
                mid = (lo + hi) >> 1
                v = jnp.take(flat_ds, mid, mode="fill", fill_value=d_pad)
                go = v < ord3
                lo = jnp.where(go, mid + 1, lo)
                hi = jnp.where(go, hi, mid)
            v = jnp.take(flat_ds, lo, mode="fill", fill_value=d_pad)
            found = (ln > 0) & (v == ord3) & (lo < end)
            imp_f = jnp.take(flat_imp, lo, mode="fill", fill_value=0.0)
            exact_local = jnp.sum(
                jnp.where(found & in_local[:, :, None], w * imp_f, 0.0),
                axis=2)
            exact = jax.lax.psum(exact_local, SHARD_AXIS)
            exact = jnp.where(cand_vals > NEG_INF, exact, NEG_INF)
        else:
            # tail-free tier (every term's postings fit inside the
            # prefix): phase-A run totals ARE the exact BM25 scores, so
            # the rescore is skipped entirely — the easy-traffic train
            # is phase A alone (tpu_service routes by per-term df)
            exact = cand_vals

        # final order: (-exact, gid) — same tie rule as the exact kernel
        neg = jnp.where(exact > NEG_INF, -exact, jnp.inf)
        sk, sg = jax.lax.sort([neg, cand_gids], num_keys=2)
        k_keep = min(k_out, c)
        out_vals = jnp.where(jnp.isinf(sk[:, :k_keep]), NEG_INF,
                             -sk[:, :k_keep])
        out_gids = sg[:, :k_keep]

        # validity ingredients (checked host-side at the caller's k):
        # a doc outside the candidates was cut either at the global pool
        # (≤ cand_vals[:, -1]) or at its row's local top-c_local
        # (≤ row_cut) — the effective cutoff is the max of the two
        cutoff = jnp.maximum(cand_vals[:, -1], row_cut)
        if use_pack:
            # packed phase-A totals are quantized LOWER bounds (16-bit
            # code truncation keeps ≤7 mantissa bits, relative error
            # < 2^-7 per lane, hence < 2^-7 on the sum of lower bounds);
            # a cut doc's TRUE phase-A score may exceed its quantized
            # score by that factor, so inflate the cutoff to keep the
            # host WAND validity check conservative (-inf = pool not
            # full stays -inf)
            cutoff = jnp.where(cutoff > 0.0, cutoff * (1.0 + 2.0 ** -6),
                               cutoff)
        beta = jax.lax.pmax(jnp.max(tail_bound, axis=0), SHARD_AXIS)
        # ONE packed f32 output [B, 2k+3]: every extra output array is a
        # separate device→host fetch, so the whole result crosses in a
        # single transfer
        with jax.named_scope("pack_out"):
            gids_f32 = jax.lax.bitcast_convert_type(
                out_gids.astype(jnp.int32), jnp.float32)
            packed = jnp.concatenate(
                [out_vals, gids_f32, totals[:, None].astype(jnp.float32),
                 cutoff[:, None], beta[:, None]], axis=1)
        return packed

    spec_post = P(SHARD_AXIS, None)
    spec_sbt = P(SHARD_AXIS, DATA_AXIS, None)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(spec_post, spec_post, spec_post, spec_post, spec_sbt),
        out_specs=P(DATA_AXIS, None))
    return jax.jit(_named(mapped, name))


@lru_cache(maxsize=256)
def _compiled(fn, structs):
    return fn.lower(*structs).compile()


def compile_pruned_program(fn, mesh: Mesh, arrays: Sequence[Any], rows: int,
                           width: int):
    """`fn` (of `make_pruned_search`) compiled ahead of time for the
    resident `arrays` it is called with and an operand of `rows` queries
    × `width` columns (`pack_pruned_operands`): the executable, kept for
    the process. Nothing runs on the device, and `jax.jit`'s own cache
    does not learn of it: a launch that must never compile calls what
    this returns, with arrays of exactly these shapes and shardings."""
    sbt = NamedSharding(mesh, P(SHARD_AXIS, DATA_AXIS, None))
    structs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                    for a in arrays)
    ops = jax.ShapeDtypeStruct((arrays[0].shape[0], rows, width), jnp.float32,
                               sharding=sbt)
    return _compiled(fn, structs + (ops,))


def heads_program_name(rows: int, slots: int) -> str:
    """The heads program under its static shape (batch rows, tail slots a
    row): `jit_<this>` on a trace's `XLA Modules` line."""
    return f"heads_b{rows}_s{slots}"


@lru_cache(maxsize=32)
def make_heads_search(mesh: Mesh, *, d_pad: int, p_pad: int, k_out: int,
                      t_window: int, name: str):
    """The heads step, ONE fused launch: OR queries that hold some of the
    pack's head terms (`head_terms`), exact. Its operands are the pack's
    impact-sorted postings, its head block [S, H, W] (`build_head_block`,
    sharded like the postings), the tail terms' fused operand [S, B,
    3·T + 1] (`build_full_operands` with no rescore terms) and each
    query's weight of every head row at boost 1, [B, H].

      head scores  [B, H] @ [H, W] for every shard row on the matrix
                   unit at HIGHEST precision (a float32 product; at the
                   default precision it is rounded to bfloat16);
      tail         the tail terms' full postings through the sorted merge
                   of the full-postings path, each matched doc's head
                   score added by a gather from the dense row;
      top-k        the maxima of HEAD_BLOCK_DOCS-doc blocks of the dense
                   rows, the k_out best blocks, their docs' top k_out:
                   every doc of the head-only top k_out lies in one of
                   those blocks (fewer than k_out blocks hold a doc above
                   it), and a doc of the final top k that holds no tail
                   term is in the head-only top k (no doc above it in the
                   head-only ranking scores less in the final one). With
                   the tail's top k_out, a doc in both keeps its larger
                   copy, the one with its tail terms: exact.

    `hits.total` is exact too: the docs whose head score is above 0 and
    the tail docs whose head score is 0 (a matching doc never scores 0).
    The output is the full-postings path's [B, 2k+3] with beta 0."""
    hb = HEAD_BLOCK_DOCS

    def body(fd_imp, fi_imp, block, ops, w):
        t = (ops.shape[2] - 1) // 3

        def bc(a):
            return jax.lax.bitcast_convert_type(a, jnp.int32)

        starts, lengths = bc(ops[:, :, 0:t]), bc(ops[:, :, t:2 * t])
        weights = ops[:, :, 2 * t:3 * t]
        s_l, b = starts.shape[0], starts.shape[1]
        width = block.shape[2]
        my = jax.lax.axis_index(SHARD_AXIS)
        with jax.named_scope("heads_matmul"):
            dense = jnp.einsum("bh,shd->bsd", w, block,
                               precision=jax.lax.Precision.HIGHEST)
        flat_dense = dense.reshape(b, s_l * width)
        head_docs = jnp.sum(flat_dense > 0.0, axis=1).astype(jnp.int32)

        # ---- the tail: the full-postings merge of its terms' slots, in
        # groups of rows as `make_pruned_search` runs it
        g, opnds = _row_groups(starts, lengths, weights, CHUNK_CAP, p_pad)
        k_tail = min(k_out, g * t * CHUNK_CAP)
        flat_imp_docs, flat_imp_imps = fd_imp.reshape(-1), fi_imp.reshape(-1)
        dense_flat = dense.reshape(-1)
        q_base = jnp.arange(b, dtype=jnp.int32)[:, None] * s_l

        def one_group(opnds):
            f_starts, f_lengths, f_weights, f_rows = opnds
            docs, imp, valid = _lane_streams(
                flat_imp_docs, flat_imp_imps, f_starts, f_lengths,
                f_weights, CHUNK_CAP)
            sk, sv = _merge_sort(docs, imp, valid, f_rows, d_pad)
            total, ok = _run_totals(sk, sv, t_window)
            with jax.named_scope("heads_merge"):
                row, doc = sk // (d_pad + 1), sk % (d_pad + 1)
                at = jnp.where(doc < width, (q_base + row) * width + doc,
                               dense_flat.shape[0])
                head = jnp.take(dense_flat, at, mode="fill", fill_value=0.0)
                extra = jnp.sum(ok & (head == 0.0), axis=1).astype(jnp.int32)
                vals, pos = sparse.hierarchical_top_k(
                    jnp.where(ok, total + head, NEG_INF), k_tail)
            return vals, jnp.take_along_axis(sk, pos, axis=1), extra

        if opnds[0].shape[0] == 1:
            vals_t, gid_t, extra = one_group(tuple(a[0] for a in opnds))
        else:
            vals_gs, gid_gs, extra_gs = jax.lax.map(one_group, opnds)
            vals_t = jnp.transpose(vals_gs, (1, 0, 2)).reshape(b, -1)
            gid_t = jnp.transpose(gid_gs, (1, 0, 2)).reshape(b, -1)
            extra = jnp.sum(extra_gs, axis=0)

        # ---- the head-only top k_out, through the blocks' maxima
        with jax.named_scope("heads_blockmax"):
            n_blocks = s_l * width // hb
            blocks = flat_dense.reshape(b, n_blocks, hb)
            k_blocks = min(k_out, n_blocks)
            _, best = jax.lax.top_k(jnp.max(blocks, axis=2), k_blocks)
            cand = jnp.take_along_axis(blocks, best[:, :, None], axis=1)
            vals_h, pos = sparse.hierarchical_top_k(
                cand.reshape(b, k_blocks * hb), min(k_out, k_blocks * hb))
            at = jnp.take_along_axis(best, pos // hb, axis=1) * hb + pos % hb
            gid_h = (at // width) * (d_pad + 1) + at % width
            vals_h = jnp.where(vals_h > 0.0, vals_h, NEG_INF)

        # ---- one pool a device: a doc in both lists keeps its larger copy
        with jax.named_scope("heads_merge"):
            pool_g = jnp.concatenate([gid_h, gid_t], axis=1)
            pool_v = jnp.concatenate([vals_h, vals_t], axis=1)
            neg = jnp.where(pool_v > NEG_INF, -pool_v, jnp.inf)
            sg, sn = jax.lax.sort([pool_g, neg], num_keys=2)
            again = jnp.concatenate(
                [jnp.zeros((b, 1), bool), sg[:, 1:] == sg[:, :-1]], axis=1)
            vals_b = jnp.where(again | jnp.isinf(sn), NEG_INF, -sn)
            gids_b = (sg.astype(jnp.int64)
                      + (my * s_l).astype(jnp.int64) * (d_pad + 1))
            gids_b = jnp.where(vals_b > NEG_INF, gids_b, 0)

        with jax.named_scope("cross_chip_merge"):
            all_vals = jax.lax.all_gather(vals_b, SHARD_AXIS, axis=1,
                                          tiled=True)
            all_gids = jax.lax.all_gather(gids_b, SHARD_AXIS, axis=1,
                                          tiled=True)
            totals = jax.lax.psum(head_docs + extra, SHARD_AXIS)
            c = min(k_out, all_vals.shape[1])
            cand_vals, pos = sparse.hierarchical_top_k(all_vals, c)
            cand_gids = jnp.take_along_axis(all_gids, pos, axis=1)

        # final order: (-score, gid), the full-postings path's
        neg = jnp.where(cand_vals > NEG_INF, -cand_vals, jnp.inf)
        sk, sg = jax.lax.sort([neg, cand_gids], num_keys=2)
        out_vals = jnp.where(jnp.isinf(sk), NEG_INF, -sk)
        with jax.named_scope("pack_out"):
            zero = jnp.zeros((b, 1), jnp.float32)
            return jnp.concatenate(
                [out_vals, jax.lax.bitcast_convert_type(
                    sg.astype(jnp.int32), jnp.float32),
                 totals[:, None].astype(jnp.float32), zero, zero], axis=1)

    spec_post = P(SHARD_AXIS, None)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(spec_post, spec_post, P(SHARD_AXIS, None, None),
                  P(SHARD_AXIS, DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS, None))
    return jax.jit(_named(mapped, name))


def compile_heads_program(fn, mesh: Mesh, arrays: Sequence[Any], rows: int,
                          slots: int, h_pad: int):
    """`fn` (of `make_heads_search`) compiled ahead of time for the
    resident `arrays` (impact-sorted postings, head block) and a launch of
    `rows` queries at `slots` tail slots: the executable, kept for the
    process. Nothing runs on the device."""
    structs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                    for a in arrays)
    ops = jax.ShapeDtypeStruct(
        (arrays[0].shape[0], rows, 3 * slots + 1), jnp.float32,
        sharding=NamedSharding(mesh, P(SHARD_AXIS, DATA_AXIS, None)))
    w = jax.ShapeDtypeStruct((rows, h_pad), jnp.float32,
                             sharding=NamedSharding(mesh, P(DATA_AXIS, None)))
    return _compiled(fn, structs + (ops, w))


def unpack_pruned(packed: np.ndarray, k_keep: Optional[int] = None):
    """Host-side split of make_pruned_search's packed output →
    (vals [B,k], gids int32 [B,k], totals [B], cutoff [B], beta [B]).
    k_keep is derived from the packed width [B, 2k+3] — the kernel may
    clamp k_out to the candidate-pool width, so callers must not guess."""
    derived = (packed.shape[1] - 3) // 2
    if packed.shape[1] != 2 * derived + 3:
        raise ValueError(
            f"packed width {packed.shape[1]} is not of the form 2k+3")
    if k_keep is None:
        k_keep = derived
    elif k_keep != derived:
        raise ValueError(
            f"packed width {packed.shape[1]} implies k_keep={derived}, "
            f"caller passed {k_keep}")
    vals = packed[:, :k_keep]
    gids = np.ascontiguousarray(packed[:, k_keep:2 * k_keep]
                                ).view(np.int32)
    totals = packed[:, 2 * k_keep].astype(np.int64)
    cutoff = packed[:, 2 * k_keep + 1]
    beta = packed[:, 2 * k_keep + 2]
    return vals, gids, totals, cutoff, beta


def device_put_pack(pack: StackedShardPack, mesh: Optional[Mesh] = None):
    """Place the postings tensors in HBM (sharded over "shards" when a mesh
    is given) — the resident pack image (SURVEY.md §7.1 table)."""
    if mesh is None:
        return (jax.device_put(pack.flat_docs),
                jax.device_put(pack.flat_impact))
    sh = NamedSharding(mesh, P(SHARD_AXIS, None))
    return (jax.device_put(pack.flat_docs, sh),
            jax.device_put(pack.flat_impact, sh))


def distributed_search_raw(pack: StackedShardPack, batch: QueryBatch,
                           k: int, mesh: Mesh, device_arrays=None,
                           with_counts: Optional[bool] = None,
                           t_window: Optional[int] = None,
                           materialize: bool = True,
                           variant: str = "ref"):
    """One distributed query step, RAW outputs: numpy (vals [B,k'],
    gids int64 [B,k'], totals [B]) with no per-hit host decoding — the
    serving path decodes the whole batch vectorized.
    materialize=False returns the jax arrays of the ASYNC dispatch
    without blocking (pipelined serving; np.asarray them to wait).

    Compressed variants take a 5-tuple device_arrays (docs16, code16,
    rank16, block_max, res_vals) from device_put_compressed — or the
    6-tuple delta form (docs8, code16, rank16, block_max, res_vals,
    doc_bases); tuple length selects the format — and a batch prepared
    with compressed=streams (res_starts/res_lens/slot_terms)."""
    compressed = variant in sparse.COMPRESSED_VARIANTS
    if device_arrays is None:
        if compressed:
            device_arrays = device_put_compressed(
                build_compressed_streams(pack), mesh)
        else:
            device_arrays = device_put_pack(pack, mesh)
    if with_counts is None:
        with_counts = batch.need_counts
    if t_window is None:
        t_window = batch.window
    elif t_window < batch.window:
        raise ValueError(f"t_window={t_window} < needed {batch.window}")
    delta = compressed and len(device_arrays) == 6
    rows = int(batch.starts.shape[1])
    name = exact_program_name(variant, rows, batch.t_slots, t_window)
    fn = make_distributed_search(
        mesh, max_len=batch.max_len, d_pad=pack.d_pad, p_pad=pack.p_pad,
        k=k, t_window=t_window, with_counts=with_counts, variant=variant,
        delta=delta, name=name)
    sbt = NamedSharding(mesh, P(SHARD_AXIS, DATA_AXIS, None))
    db = NamedSharding(mesh, P(DATA_AXIS))
    if compressed and batch.res_starts is None:
        raise ValueError(
            "compressed variant needs a batch prepared with "
            "compressed= streams (res_starts/res_lens/slot_terms)")
    # on a batcher's launch thread: the same three-way split of dispatch
    # as the pruned path's
    states = tracing.current_states()
    states.switch("lock")
    with DEVICE_DISPATCH_LOCK:
        states.switch("put")
        query_arrays = [batch.starts, batch.lengths, batch.weights]
        if compressed:
            query_arrays += [batch.res_starts, batch.res_lens,
                             batch.slot_terms]
        query_ops = [jax.device_put(a, sbt) for a in query_arrays]
        query_ops.append(jax.device_put(batch.min_count, db))
        # the delta form's per-block doc bases (a 6th pack array) go
        # after the query operands
        n_pack = 5 if compressed else 2
        states.switch("call", path=name, rows=rows)
        vals, ids, totals = fn(*device_arrays[:n_pack], *query_ops,
                               *device_arrays[n_pack:])
    states.switch("prep")
    if not materialize:
        return vals, ids, totals
    return np.asarray(vals), np.asarray(ids), np.asarray(totals)


def distributed_search(pack: StackedShardPack, batch: QueryBatch, k: int,
                       mesh: Mesh, device_arrays=None,
                       with_counts: Optional[bool] = None,
                       t_window: Optional[int] = None):
    """Run one distributed query step. Returns (scores [B,k'], refs,
    totals [B]) where refs[q] = [(score, shard, local_ord), ...] decoded
    host-side and totals[q] is the exact matched-doc count.
    with_counts defaults to the batch's own need (any min_count > 1).
    t_window (≥ batch.window) can be pinned for jit-signature stability."""
    vals, ids, totals = distributed_search_raw(
        pack, batch, k, mesh, device_arrays=device_arrays,
        with_counts=with_counts, t_window=t_window)
    vals, refs = decode_refs(pack, vals, ids)
    return vals, refs, totals


def decode_refs(pack: StackedShardPack, vals: np.ndarray, ids: np.ndarray):
    refs = []
    for qi in range(vals.shape[0]):
        row = []
        for v, gid in zip(vals[qi], ids[qi]):
            if v == NEG_INF:
                continue
            shard, ord_ = divmod(int(gid), pack.d_pad + 1)
            if ord_ >= pack.d_pad:
                continue  # sentinel lane
            row.append((float(v), shard, ord_))
        refs.append(row)
    return vals, refs


def resolve_hits(pack: StackedShardPack,
                 refs: List[List[Tuple[float, int, int]]]):
    """(score, shard, ord) → [{'_id', '_score'}] via the host doc-id maps."""
    out = []
    for row in refs:
        hits = []
        for score, shard, ord_ in row:
            if shard < len(pack.shard_doc_ids) and ord_ < len(pack.shard_doc_ids[shard]):
                hits.append({"_id": pack.shard_doc_ids[shard][ord_],
                             "_score": score})
        out.append(hits)
    return out


# ----------------------------------------------------------------------
# distributed kNN: brute-force matmul top-k over the docs axis
# ----------------------------------------------------------------------

@dataclasses.dataclass
class StackedVectorPack:
    """S doc-axis shards of one dense_vector field as a [S, D_pad, dims]
    f32 tensor (NaN rows = missing docs), sharded over the "shards"
    mesh axis (SURVEY.md §7.2.9 / §2.3 P1 applied to vectors). The
    matmul [D_pad, dims] @ [dims, B] per device is the MXU-native
    replacement for the reference's per-query HNSW graph walk — exact
    instead of approximate, batched instead of sequential."""

    field: str
    num_shards: int
    d_pad: int
    dims: int
    vectors: np.ndarray          # f32[S, D_pad, dims]
    live: np.ndarray             # bool[S, D_pad]
    shard_doc_ids: List[List[str]]
    similarity: str = "cosine"


def build_stacked_vector_pack(segments: Sequence[Segment], field: str,
                              live_docs: Optional[Sequence[Optional[np.ndarray]]] = None,
                              similarity: str = "cosine",
                              pad_shards_to: Optional[int] = None
                              ) -> StackedVectorPack:
    """Each segment is one doc-axis shard; shapes pad to the max."""
    from elasticsearch_tpu.index.pack import _pad_to as pad_to
    dims = 0
    for seg in segments:
        col = seg.doc_values.get(field)
        if col is not None and col.kind == "vec":
            dims = max(dims, col.values.shape[1])
    if dims == 0:
        raise ValueError(f"no dense_vector column [{field}] in segments")
    d_pad = pad_to(max((s.num_docs for s in segments), default=1))
    s = len(segments)
    s_pad = max(pad_shards_to or s, s)
    vectors = np.full((s_pad, d_pad, dims), np.nan, dtype=np.float32)
    live = np.zeros((s_pad, d_pad), dtype=bool)
    doc_ids: List[List[str]] = []
    for i, seg in enumerate(segments):
        col = seg.doc_values.get(field)
        if col is not None and col.kind == "vec":
            vectors[i, : seg.num_docs, : col.values.shape[1]] = col.values
        if live_docs is not None and live_docs[i] is not None:
            live[i, : seg.num_docs] = live_docs[i]
        else:
            live[i, : seg.num_docs] = True
        doc_ids.append(list(seg.doc_ids))
    return StackedVectorPack(field, s_pad, d_pad, dims, vectors, live,
                             doc_ids, similarity)


def _knn_local_body(vectors, live, queries, *, similarity: str, k: int,
                    d_pad: int, first_shard):
    """Per-device scores over an [s_l, D_pad, dims] block (s_l = shards
    resident on this device): one flattened [B, s_l·D] matmul → local
    top-k with global ids (same id scheme as the BM25 kernel:
    shard · (d_pad+1) + ord)."""
    s_l = vectors.shape[0]
    flat = vectors.reshape(s_l * d_pad, -1)              # [N, dims]
    safe = jnp.nan_to_num(flat)
    present = ~jnp.isnan(flat[:, 0])
    q = queries.astype(jnp.float32)                      # [B, dims]
    if similarity == "l2_norm":
        # ||d - q||^2 = ||d||^2 - 2 d.q + ||q||^2, one matmul
        d2 = (jnp.sum(safe * safe, axis=1)[None, :]
              - 2.0 * (q @ safe.T)
              + jnp.sum(q * q, axis=1)[:, None])
        scores = 1.0 / (1.0 + jnp.maximum(d2, 0.0))
    elif similarity == "dot_product":
        scores = (1.0 + q @ safe.T) / 2.0
    else:  # cosine
        dn = jnp.sqrt(jnp.sum(safe * safe, axis=1))      # [N]
        qn = jnp.sqrt(jnp.sum(q * q, axis=1))            # [B]
        cos = (q @ safe.T) / jnp.maximum(qn[:, None] * dn[None, :],
                                         1e-12)
        scores = (1.0 + cos) / 2.0
    ok = present & live.reshape(s_l * d_pad)
    scores = jnp.where(ok[None, :], scores, NEG_INF)     # [B, N]
    vals, flat_idx = jax.lax.top_k(scores, min(k, s_l * d_pad))
    j = (flat_idx // d_pad).astype(jnp.int64)
    ords = (flat_idx % d_pad).astype(jnp.int64)
    gids = (first_shard + j) * (d_pad + 1) + ords
    gids = jnp.where(vals == NEG_INF, -1, gids)
    return vals, gids


@lru_cache(maxsize=32)
def make_distributed_knn(mesh: Mesh, *, d_pad: int, dims: int, k: int,
                         similarity: str):
    """SPMD kNN step over the (data, shards) mesh: local matmul top-k
    per device, all_gather over "shards", global top-k on device — the
    identical collective shape as make_distributed_search, so BM25 and
    kNN share the serving geometry (hybrid search reuses both)."""

    def body(vectors, live, queries):
        my = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int64)
        s_l = vectors.shape[0]   # shards resident on this device
        vals_b, gids_b = _knn_local_body(
            vectors, live, queries, similarity=similarity, k=k,
            d_pad=d_pad, first_shard=my * s_l)
        all_vals = jax.lax.all_gather(vals_b, SHARD_AXIS, axis=1,
                                      tiled=True)
        all_ids = jax.lax.all_gather(gids_b, SHARD_AXIS, axis=1,
                                     tiled=True)
        return _merge_topk(all_vals, all_ids, k)

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None),
                  P(None, None)),
        out_specs=(P(None, None), P(None, None)))
    return jax.jit(_named(mapped, f"knn_{similarity}"))


def distributed_knn(pack: StackedVectorPack, queries: np.ndarray, k: int,
                    mesh: Optional[Mesh] = None,
                    device_arrays: Optional[Tuple] = None):
    """Batched exact kNN: queries [B, dims] → (scores [B, k], refs
    [[(score, shard, ord), ...]]). Single-device fallback when mesh is
    None (one chip: plain vmap-free matmul, same math)."""
    q = np.asarray(queries, dtype=np.float32)
    if q.ndim == 1:
        q = q[None, :]
    if mesh is not None:
        step = make_distributed_knn(mesh, d_pad=pack.d_pad,
                                    dims=pack.dims, k=k,
                                    similarity=pack.similarity)
        if device_arrays is not None:
            vectors, live = device_arrays
        else:
            vectors, live = device_put_vector_pack(pack, mesh)
        with DEVICE_DISPATCH_LOCK:
            vals, gids = step(vectors, live, jnp.asarray(q))
    else:
        vals, gids = _knn_local_body(
            jnp.asarray(pack.vectors), jnp.asarray(pack.live),
            jnp.asarray(q), similarity=pack.similarity, k=k,
            d_pad=pack.d_pad, first_shard=jnp.int64(0))
        vals, gids = _merge_topk(vals, gids, k)
    vals = np.asarray(vals)
    gids = np.asarray(gids)
    refs = []
    for qi in range(vals.shape[0]):
        row = []
        for v, gid in zip(vals[qi], gids[qi]):
            if v == NEG_INF or gid < 0:
                continue
            shard, ord_ = divmod(int(gid), pack.d_pad + 1)
            row.append((float(v), shard, ord_))
        refs.append(row)
    return vals, refs


def device_put_vector_pack(pack: StackedVectorPack, mesh: Mesh):
    """Place the vector tensor with NamedSharding over "shards"."""
    sh = NamedSharding(mesh, P(SHARD_AXIS, None, None))
    sh2 = NamedSharding(mesh, P(SHARD_AXIS, None))
    return (jax.device_put(pack.vectors, sh),
            jax.device_put(pack.live, sh2))


# ----------------------------------------------------------------------
# term-axis sharding (TP-analog) + oversized-row doc-split (CP-analog)
# ----------------------------------------------------------------------
# SURVEY.md §5.7 / §2.3 last row: the reference has no tensor/sequence
# parallelism; these are the NEW first-class designs the TPU build adds.
# Both answer "what when one device cannot hold the axis":
#   - term_sharded_search: the TERM axis of a query (vocab side) shards
#     over the mesh — each device scores only ITS terms' postings into
#     a dense partial-score vector, `psum` combines (exactly how TP
#     combines per-device partial matmul products).
#   - split_row_topk: ONE oversized postings row (a stopword-scale
#     term whose postings exceed a device's slot budget) splits along
#     the DOC axis across devices; each device top-k's its block and an
#     all_gather + merge produces the exact global top-k (the
#     ring/blockwise trick: never materialize the full axis anywhere).


def make_term_sharded_search(mesh: Mesh, *, n_docs_pad: int, k: int):
    """SPMD over the "shards" axis interpreted as TERM groups: operands
    are per-device [T_l, L] postings (docs/impacts over ONE shared doc
    space) + per-device term weights. Each device scatter-adds its
    terms' contributions into a dense [B, D] partial score, psum over
    the axis gives exact BM25 for ALL terms — the term count a query
    may use is now bounded by the MESH, not by one device's slots."""

    def body(term_docs, term_imps, weights, valid):
        # term_docs/imps: [1?, T_l, L] block per device (leading mesh
        # dim collapsed); weights [1?, B, T_l]
        td = term_docs[0]                      # [T_l, L]
        ti = term_imps[0]
        w = weights[0]                         # [B, T_l]
        va = valid[0]
        contrib = jnp.where(va, ti, 0.0)       # [T_l, L]
        scatter_idx = jnp.where(va, td, n_docs_pad)
        b = w.shape[0]
        dense = jnp.zeros((b, n_docs_pad + 1), dtype=jnp.float32)
        # one scatter-add per query row over this device's terms
        flat_idx = scatter_idx.reshape(-1)     # [T_l*L]
        per_term = contrib.reshape(-1)
        for qi in range(b):  # B is small/static for this path
            wq = jnp.repeat(w[qi], td.shape[1])
            dense = dense.at[qi].add(
                jnp.zeros(n_docs_pad + 1,
                          dtype=jnp.float32).at[flat_idx].add(
                    (wq * per_term).astype(jnp.float32)))
        full = jax.lax.psum(dense, SHARD_AXIS)[:, :n_docs_pad]
        vals, docs = jax.lax.top_k(full, min(k, n_docs_pad))
        vals = jnp.where(vals > 0.0, vals, NEG_INF)
        docs = jnp.where(vals > NEG_INF, docs, n_docs_pad)
        return vals, docs

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None),
                  P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None)),
        out_specs=(P(None, None), P(None, None)))
    return jax.jit(_named(mapped, "term_sharded"))


def term_sharded_search(mesh: Mesh, term_docs: np.ndarray,
                        term_imps: np.ndarray, term_lens: np.ndarray,
                        weights: np.ndarray, n_docs: int, k: int):
    """Host wrapper: term rows [T, L] (padded), weights [B, T] → exact
    (scores [B, k], doc ids [B, k]) with terms sharded over the mesh.
    T must divide over the "shards" axis (pad with zero-weight rows)."""
    n_dev = mesh.shape[SHARD_AXIS]
    t, l = term_docs.shape
    t_pad = ((t + n_dev - 1) // n_dev) * n_dev
    from elasticsearch_tpu.index.pack import _pad_to as pad_to
    d_pad = pad_to(n_docs)

    def pad_rows(a, fill):
        out = np.full((t_pad, l), fill, dtype=a.dtype)
        out[:t] = a
        return out

    docs_p = pad_rows(term_docs.astype(np.int32), d_pad)
    imps_p = pad_rows(term_imps.astype(np.float32), 0.0)
    valid = (np.arange(l)[None, :]
             < term_lens.astype(np.int64)[:, None])
    valid_p = pad_rows(valid, False)
    b = weights.shape[0]
    w_p = np.zeros((t_pad, b), dtype=np.float32)
    w_p[:t] = weights.T.astype(np.float32)

    # reshape to [n_dev, T_l, ...] blocks over the mesh axis
    t_l = t_pad // n_dev
    fn = make_term_sharded_search(mesh, n_docs_pad=d_pad, k=k)
    import jax as _jax
    from jax.sharding import NamedSharding
    sh3 = NamedSharding(mesh, P(SHARD_AXIS, None, None))
    args = (docs_p.reshape(n_dev, t_l, l),
            imps_p.reshape(n_dev, t_l, l),
            np.transpose(w_p.reshape(n_dev, t_l, b), (0, 2, 1)),
            valid_p.reshape(n_dev, t_l, l))
    vals, docs = fn(*(_jax.device_put(a, sh3) for a in args))
    return np.asarray(vals), np.asarray(docs)


def make_split_row_topk(mesh: Mesh, *, block: int, k: int,
                        d_pad: int):
    """ONE oversized postings row split into per-device doc blocks:
    local top-k per block + all_gather + global top-k = exact, with no
    device ever holding the full row (the CP/ring-analog)."""

    def body(docs, imps, valid):
        d = docs[0]                 # [block]
        v = jnp.where(valid[0], imps[0], NEG_INF)
        k_l = min(k, block)
        vals, pos = jax.lax.top_k(v, k_l)
        ids = jnp.take(d, pos)
        all_vals = jax.lax.all_gather(vals, SHARD_AXIS, axis=0,
                                      tiled=True)
        all_ids = jax.lax.all_gather(ids, SHARD_AXIS, axis=0,
                                     tiled=True)
        out_v, out_pos = jax.lax.top_k(all_vals, min(k, all_vals.shape[0]))
        out_ids = jnp.take(all_ids, out_pos)
        out_ids = jnp.where(out_v > NEG_INF, out_ids, d_pad)
        return out_v, out_ids

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                  P(SHARD_AXIS, None)),
        out_specs=(P(None), P(None)))
    return jax.jit(_named(mapped, "split_row_topk"))


def split_row_topk(mesh: Mesh, row_docs: np.ndarray,
                   row_imps: np.ndarray, k: int, d_pad: int):
    """Host wrapper: an arbitrary-length postings row (doc ids +
    weighted impacts) → exact top-k over the mesh. The row is blocked
    across devices; blocks pad to a common static size."""
    n_dev = mesh.shape[SHARD_AXIS]
    n = len(row_docs)
    block = ((n + n_dev - 1) // n_dev + 127) // 128 * 128
    docs_p = np.full((n_dev, block), d_pad, dtype=np.int32)
    imps_p = np.zeros((n_dev, block), dtype=np.float32)
    valid = np.zeros((n_dev, block), dtype=bool)
    for dv in range(n_dev):
        lo = dv * block
        hi = min(n, lo + block)
        if hi > lo:
            docs_p[dv, : hi - lo] = row_docs[lo:hi]
            imps_p[dv, : hi - lo] = row_imps[lo:hi]
            valid[dv, : hi - lo] = True
    import jax as _jax
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, P(SHARD_AXIS, None))
    fn = make_split_row_topk(mesh, block=block, k=k, d_pad=d_pad)
    vals, ids = fn(*(_jax.device_put(a, sh)
                     for a in (docs_p, imps_p, valid)))
    return np.asarray(vals), np.asarray(ids)
