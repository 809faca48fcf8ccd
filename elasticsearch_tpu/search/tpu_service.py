"""TPU serving path for `_search` — resident packs + micro-batched kernel.

This wires the batched kernel pipeline (parallel/distributed.py) into the
live search path, replacing the per-query/per-segment host loop for the
queries that dominate serving traffic. Reference seam being replaced:
`search/query/QueryPhase#executeInternal`'s per-segment BulkScorer loop
(SURVEY.md §3.3 ⚙⚙) — here a whole micro-batch of queries crosses all
shards in ONE kernel launch (SURVEY.md §2.3 P4: TPUs want batches, not
threads).

Three pieces:

  IndexPackCache — per (index, field) StackedShardPack built from the
    union of every shard's current reader (one pack row per segment, one
    statistics GROUP per shard so idf/avgdl match the per-shard planner
    path exactly — the reference's query_then_fetch statistics scope).
    Packs are derived caches (SURVEY.md §5.4): rebuilt when any shard's
    reader changes, HBM-accounted via the `hbm` circuit breaker.

  lowering — QueryNode → FlatQuery(terms, boost, min_count) for the query
    shapes the kernel serves: match (or/and/msm), term/terms on one text
    field, and single-field bool should-of-term/match. Everything else
    (phrase, ranges, aggs, multi-field bools...) returns None and falls
    back to the planner path — same contract split as the reference's
    `EnginePlugin#getEngineFactory` seam: the fast engine serves what it
    can, behavior elsewhere is unchanged.

  MicroBatcher — coalesces concurrent queries for ~2ms (or until the
    batch cap) and executes them as one kernel call; callers block on
    futures. Batch sizes pad to power-of-two buckets so the jit cache is
    hit, not re-traced.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import math
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from elasticsearch_tpu.common import events, profiler, tenancy, tracing
from elasticsearch_tpu.common.metrics import CounterMetric, LabeledCounters
from elasticsearch_tpu.mapping.types import TextFieldType
from elasticsearch_tpu.ops import sparse
from elasticsearch_tpu.parallel import distributed as dist
from elasticsearch_tpu.parallel.mesh import SHARD_AXIS, make_mesh
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.serializer import (FETCH_COUNTS, RENDER_COUNTS,
                                                 JsonLiterals, encode_source)

logger = logging.getLogger("elasticsearch_tpu.tpu_service")


class StageTimes:
    """Accumulated per-stage wall time on the serving path (measure
    where the time goes before optimizing it). Reported via
    TpuSearchService.stats()["stages"] and the profile/_nodes/stats trees.

    Besides the running (seconds, count) totals, each stage keeps a
    bounded ring of recent per-call samples and reports p50/p95/p99
    latency. The totals alone mislead for queue-style stages: batch_wait
    sums each query's wait even though a whole train waits CONCURRENTLY,
    so "5087 s total" can describe a 20 s run. The percentiles are the
    per-query truth."""

    RING_SIZE = 512
    #: per-request stages read the thread's CPU clock for one request in
    #: this many. The wall clock is read in user space; the CPU clock is
    #: a system call, and under a sandboxed kernel each one cost about
    #: 0.5% of `qps` at saturation when every request made it (PERF.md,
    #: Findings, PR 25). Per-train stages read it every time.
    CPU_SAMPLE_EVERY = 16

    def __init__(self):
        from elasticsearch_tpu.common.metrics import SampleRing
        self._ring_cls = SampleRing
        self._lock = threading.Lock()
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        #: thread CPU seconds (`time.thread_time()` differences of the
        #: thread that did the work) of the stages that report them
        self.cpu_seconds: Dict[str, float] = {}
        #: how many of a stage's `counts` came with a CPU reading
        self.cpu_counts: Dict[str, int] = {}
        self._cpu_ticks: Dict[str, Any] = {}
        self._rings: Dict[str, Any] = {}

    def sample_cpu(self, stage: str) -> bool:
        """Whether this occurrence of a per-request `stage` should read
        the CPU clock: the first, then one in CPU_SAMPLE_EVERY. No lock:
        `setdefault` and `next` on a count are each one atomic step."""
        ticks = self._cpu_ticks.get(stage)
        if ticks is None:
            ticks = self._cpu_ticks.setdefault(stage, itertools.count())
        return next(ticks) % self.CPU_SAMPLE_EVERY == 0

    def add(self, stage: str, dt: float, n: int = 1,
            cpu: Optional[float] = None,
            attributes: Optional[Dict[str, Any]] = None) -> None:
        with self._lock:
            self.seconds[stage] = self.seconds.get(stage, 0.0) + dt
            self.counts[stage] = self.counts.get(stage, 0) + n
            if cpu is not None:
                self.cpu_seconds[stage] = \
                    self.cpu_seconds.get(stage, 0.0) + cpu
                self.cpu_counts[stage] = self.cpu_counts.get(stage, 0) + n
            ring = self._rings.get(stage)
            if ring is None:
                ring = self._rings[stage] = self._ring_cls(self.RING_SIZE)
        # stage exemplar: the ring remembers the trace_id of its slowest
        # recent traced sample (the metrics→trace pivot in /_tpu/stats)
        span = tracing.current_span()
        ring.add(dt / n if n > 1 else dt,
                 exemplar=span.trace_id if span is not None else None)
        # the same dt the stats ring keeps also lands on the active trace
        # (no-op — one thread-local read — when the request isn't traced)
        if span is not None:
            tracing.record_stage("tpu." + stage, dt, n=n,
                                 **(attributes or {}))

    def add_many(self, samples: Sequence[Tuple[str, float]]) -> None:
        """`add` of one occurrence of each (stage, dt), under one taking
        of the lock: what a request records at once (`batch_wait` and
        its split) costs one acquisition, not one a stage."""
        rings = []
        with self._lock:
            for stage, dt in samples:
                self.seconds[stage] = self.seconds.get(stage, 0.0) + dt
                self.counts[stage] = self.counts.get(stage, 0) + 1
                ring = self._rings.get(stage)
                if ring is None:
                    ring = self._rings[stage] = self._ring_cls(
                        self.RING_SIZE)
                rings.append(ring)
        span = tracing.current_span()
        exemplar = span.trace_id if span is not None else None
        for ring, (stage, dt) in zip(rings, samples):
            ring.add(dt, exemplar=exemplar)
            if span is not None:
                tracing.record_stage("tpu." + stage, dt)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            stages = sorted(self.seconds)
            out = {s: {"seconds": round(self.seconds[s], 4),
                       "count": self.counts[s]}
                   for s in stages}
            for s, cpu in self.cpu_seconds.items():
                out[s]["cpu_seconds"] = round(cpu, 4)
                out[s]["cpu_count"] = self.cpu_counts[s]
            rings = {s: self._rings.get(s) for s in stages}
        for s, ring in rings.items():
            if ring is None:
                continue
            pcts = ring.percentiles((50.0, 95.0, 99.0))
            if pcts:
                out[s]["p50_ms"] = round(pcts[50.0] * 1000.0, 3)
                out[s]["p95_ms"] = round(pcts[95.0] * 1000.0, 3)
                out[s]["p99_ms"] = round(pcts[99.0] * 1000.0, 3)
            # metrics→trace pivot: the slowest recent traced sample's
            # trace_id (key absent when nothing traced is in-window)
            exemplar = ring.exemplar_trace_id
            if exemplar is not None:
                out[s]["exemplar_trace_id"] = exemplar
        return out

    def metrics_view(self) -> List[Tuple[str, float, int, Any,
                                         Optional[float]]]:
        """(stage, total_seconds, count, ring, cpu_seconds or None) rows
        for the metrics registry — the live ring OBJECTS, so the
        Prometheus summary exports current quantiles and the
        completeness check can see every ring is registered."""
        with self._lock:
            return [(s, self.seconds[s], self.counts.get(s, 0),
                     self._rings.get(s), self.cpu_seconds.get(s))
                    for s in sorted(self.seconds)]


# ---------------------------------------------------------------------------
# DSL lowering
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FlatQuery:
    """A query the kernel can serve directly: weighted-OR over one text
    field's terms with a minimum-match count (1 = OR, len(terms) = AND)."""

    field: str
    terms: List[str]
    boost: float
    min_count: int


def lower_query(query: dsl.QueryNode, mapper) -> Optional[FlatQuery]:
    """QueryNode → FlatQuery, or None when this query needs the planner.
    `mapper`: the index's MapperService (analysis for match queries)."""
    if isinstance(query, dsl.MatchQuery):
        ft = mapper.field_type(query.field)
        if not isinstance(ft, TextFieldType):
            return None
        terms = ft.search_terms(query.query)
        if not terms:
            return None
        msm = len(terms) if query.operator == "and" else 1
        if query.minimum_should_match is not None and query.operator == "or":
            # unclamped: msm > len(terms) matches nothing, like the planner
            msm = query.minimum_should_match
        return FlatQuery(query.field, terms, query.boost, msm)
    if isinstance(query, dsl.TermQuery):
        ft = mapper.field_type(query.field)
        if not isinstance(ft, TextFieldType):
            return None  # keyword/numeric terms: norms differ — planner
        return FlatQuery(query.field, [str(query.value)], query.boost, 1)
    if isinstance(query, dsl.TermsQuery):
        ft = mapper.field_type(query.field)
        if not isinstance(ft, TextFieldType):
            return None
        terms = [str(v) for v in query.values]
        if not terms:
            return None
        return FlatQuery(query.field, terms, query.boost, 1)
    if isinstance(query, dsl.BoolQuery):
        # single-field should-only bool of term/match clauses = weighted OR
        if query.must or query.must_not or query.filter:
            return None
        subs = [lower_query(q, mapper) for q in query.should]
        if not subs or any(s is None for s in subs):
            return None
        fields = {s.field for s in subs}
        if len(fields) != 1:
            return None
        if any(s.min_count != 1 for s in subs):
            return None  # nested AND semantics ≠ flat msm
        boosts = {s.boost for s in subs}
        if len(boosts) != 1:
            return None  # per-clause boosts need per-slot weights; planner
        msm = query.minimum_should_match or 1
        if msm > 1 and any(len(s.terms) != 1 for s in subs):
            # msm counts CLAUSES; flat min_count counts TERMS — only
            # identical when every clause is a single term
            return None
        terms: List[str] = []
        for s in subs:
            terms.extend(s.terms)
        return FlatQuery(fields.pop(), terms, query.boost * subs[0].boost,
                         msm)
    return None


# ---------------------------------------------------------------------------
# lowered-plan cache
# ---------------------------------------------------------------------------

def plan_key(query: dsl.QueryNode) -> Optional[Tuple]:
    """Canonical hashable key for a parsed query tree, or None when the
    tree holds something unhashable (scripts, callables) — those queries
    are simply not plan-cached. Two requests with the same query body
    parse to equal dataclass trees, so the key captures "same shape +
    same values" exactly; Zipf-distributed real traffic repeats shapes
    constantly, which is what makes memoizing lower_query worth it."""
    try:
        key = _plan_key_node(query)
        hash(key)
        return key
    except TypeError:
        return None


def _plan_key_node(value: Any) -> Any:
    if isinstance(value, dsl.QueryNode):
        parts = [type(value).__name__]
        for f in dataclasses.fields(value):
            parts.append(_plan_key_node(getattr(value, f.name)))
        return tuple(parts)
    if isinstance(value, (list, tuple)):
        return tuple(_plan_key_node(v) for v in value)
    if isinstance(value, dict):
        return tuple(sorted((k, _plan_key_node(v))
                            for k, v in value.items()))
    return value


#: cached marker for "this query lowers to None" — caching the negative
#: is as valuable as the positive (the planner-path traffic re-probes
#: lowering on every request otherwise)
NOT_LOWERABLE = object()


class PlanCache:
    """LRU memo of lower_query results keyed on (index, mapping
    generation, canonical query body). Entries remember the reader_key
    of the resident pack they were validated against so a pack rebuild
    (refresh/merge mid-traffic) re-lowers instead of trusting stale
    routing; a mapping update changes the generation component, making
    every old entry unreachable (and explicitly purged via the
    invalidation seams)."""

    def __init__(self, max_entries: int = 2048):
        from collections import OrderedDict
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0

    def get(self, key: Tuple) -> Any:
        """→ FlatQuery | NOT_LOWERABLE | None (miss)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry

    def put(self, key: Tuple, value: Any) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1

    def invalidate_index(self, index_name: str) -> None:
        with self._lock:
            stale = [k for k in self._entries if k[0] == index_name]
            for k in stale:
                del self._entries[k]
            self.invalidations += len(stale)
        if stale:
            events.emit("plan_cache.invalidate", index=index_name,
                        entries=len(stale))

    def clear(self) -> None:
        with self._lock:
            dropped = len(self._entries)
            self.invalidations += dropped
            self._entries.clear()
        if dropped:
            events.emit("plan_cache.invalidate", entries=dropped,
                        reason="clear")

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"size": len(self._entries),
                    "max_entries": self.max_entries,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "invalidations": self.invalidations}


# ---------------------------------------------------------------------------
# pack residency
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ResidentPack:
    """One (index, field) pack + its device arrays + provenance."""

    pack: dist.StackedShardPack
    device_arrays: Tuple
    # row → (shard_num, segment_name): resolves kernel hits back to the
    # owning IndexShard for the fetch phase
    row_origin: List[Tuple[int, str]]
    reader_key: Tuple  # identity of the readers this pack was built from
    hbm_bytes: int
    # pinned point-in-time readers per shard (the ReaderContext analog:
    # the fetch phase resolves _source against the same snapshot the
    # query phase scored, SURVEY.md §3.3)
    readers: Dict[int, Any] = dataclasses.field(default_factory=dict)
    # block-max layout (SURVEY.md §5.7): impact-descending copies of the
    # postings, host + device — pruned mode scores only each term's top
    # PREFIX_CAP entries and bounds what it skipped
    imp_host: Optional[Tuple[np.ndarray, np.ndarray]] = None
    imp_device_arrays: Optional[Tuple] = None
    # vectorized hit resolution: one fancy-index resolves
    # a whole [B, k] kernel result to external ids/shards — no per-hit
    # Python on the serving path
    row_shard: Optional[np.ndarray] = None    # int32[S_pad], -1 = padding
    row_offset: Optional[np.ndarray] = None   # int64[S_pad] into id_cat
    id_cat: Optional[np.ndarray] = None       # object[total_docs] ext ids
    # the same ids as JSON literals, indexed like id_cat, encoded once
    # here so that rendering a response encodes none (host memory; None
    # when an id is not a string: the serializer then renders in Python)
    id_json: Optional[JsonLiterals] = None
    row_segments: Optional[List[Any]] = None  # row → Segment (pinned)
    # the docs' stored sources as JSON literals, indexed like id_cat:
    # built by `source_literals` when the first hits block with `_source`
    # renders from this pack (host memory, some 300 B a doc for MS
    # MARCO's passages), so a pack that never serves `_source` holds none
    source_json: Optional[JsonLiterals] = None
    source_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    # terms-tuple → _slots_needed result. The slot count depends only on
    # this pack's postings lengths, so the memo lives (and dies) with the
    # pack — a rebuild starts fresh, no invalidation protocol needed.
    slots_memo: Dict[Tuple[str, ...], int] = dataclasses.field(
        default_factory=dict)
    # number of terms → _widest_slots result, on the same terms
    widest_slots_memo: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    # terms-tuple → `_heads_plan`'s result, memoised as `slots_memo` is
    heads_memo: Dict[Tuple[str, ...], Optional[Tuple[int, Tuple[str, ...]]]] = \
        dataclasses.field(default_factory=dict)
    # the head block on the device (`_head_block`): built, under
    # `heads_lock`, by the first launch on the heads route; None on a pack
    # that never served a query holding a head term
    heads_block: Optional["HeadBlock"] = None
    heads_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    # (mesh, k bucket, max_batch) → the heads programs' executables, as
    # futures, by (tail slots, rows): `_ready_heads_programs`
    heads_ready: Dict[Tuple, Dict[Tuple[int, int], Future]] = \
        dataclasses.field(default_factory=dict)
    # the cache that placed the pack and charges what it adds to the
    # `hbm` breaker (`IndexPackCache.charge`)
    cache: Optional["IndexPackCache"] = dataclasses.field(
        default=None, repr=False, compare=False)
    # (mesh, k bucket, variant, max_batch) → the executables of
    # `full_program_set`, by (slots, rows): `_ready_full_programs`
    full_ready: Dict[Tuple, Dict[Tuple[int, int], Any]] = dataclasses.field(
        default_factory=dict)
    # the programs this pack has launched, by what names them: a
    # program's first launch compiled it (`_freeze_first_launch`)
    launched: set = dataclasses.field(default_factory=set)
    # compressed resident format: host-side 16-bit
    # streams + residual tables. When set, device_arrays is the 5-tuple
    # from device_put_compressed (6-tuple with the delta doc stream's
    # base column, PR 15), there is no f32 posting copy on device and no
    # impact-sorted copy at all (imp_host/imp_device_arrays stay None →
    # every query routes to the exact kernel in a compressed variant)
    comp_streams: Optional[dist.CompressedStreams] = None
    # per-pack HBM accounting detail for /_tpu/stats and the Prometheus
    # pack families: raw vs resident bytes, ratio, block metadata, docs
    hbm_detail: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # placement (fault-domain) residency: the replica group whose cache
    # built this pack (None = single-group serving)
    group_id: Optional[int] = None
    # the mesh the arrays were placed on. A remesh takes no build lock,
    # so a build it overtakes ends on the mesh of before: such a pack is
    # never swapped in (`IndexPackCache._swap_in_locked`)
    mesh: Optional[Any] = None

    @property
    def group_mesh(self) -> Optional[Any]:
        """A group-placed pack's arrays live on its group's sub-mesh, a
        strict subset of the full mesh: launches MUST use it. None =
        single-group serving, launches use the batcher's mesh."""
        return self.mesh if self.group_id is not None else None

    @property
    def compressed(self) -> bool:
        return self.comp_streams is not None

    def resolve_ids(self, rows: np.ndarray, ords: np.ndarray) -> np.ndarray:
        """(pack row, local ordinal) → external _id, vectorized."""
        if len(rows) == 0:
            return np.empty(0, dtype=object)
        return self.id_cat[self.row_offset[rows] + ords]

    def source_literals(self, stages: Optional["StageTimes"] = None
                        ) -> Optional[JsonLiterals]:
        """The docs' stored sources as literals (`encode_source` of
        each, as the Python path writes a hit's `_source`), built at the
        first call, under this pack's lock, as the stage `source_table`
        of `stages`. None when a source is one json cannot write."""
        with self.source_lock:
            if self.source_json is None:
                with tracing.stage(stages, "source_table", annotate=False,
                                   cpu=False):
                    self.source_json = JsonLiterals.build(
                        (self.row_segments[row].stored_source
                         for row, ids in enumerate(self.pack.shard_doc_ids)
                         if len(ids)), encode=encode_source)
            return self.source_json


# -- streaming delta chain (LSM resident path) ------------------------------
#
# Append-only refreshes build a SMALL delta pack from only the new
# segments instead of re-placing the whole (index, field) image; searches
# run the kernel on base + each delta and union the per-pack top-ks
# host-side (ops/sparse.union_topk). A background compactor folds the
# chain back into one full (compressed) base pack. A doc lives in exactly
# one pack: an update/delete of a committed doc mutates a live mask,
# which bumps the engine's live_version and forces a full rebuild — the
# delta path is append-only by construction.

#: chaos seam (tests): each hook is called with the (index, field) key at
#: the top of every compaction and may block or raise — "kill lands
#: mid-compaction" is a hook that parks until the batcher dies.
COMPACTION_FAULT_HOOKS: List[Any] = []


@dataclasses.dataclass
class DeltaStats:
    """Node-wide delta lifecycle counters (rendered by node.py as the
    ``es_tpu_delta_*`` Prometheus families)."""

    appends: int = 0              # delta packs built
    seals: int = 0                # delta packs made immutable on device
    compactions: int = 0
    compaction_failures: int = 0
    replayed_ops: int = 0         # via supervisor recovery replay
    compact_seconds: float = 0.0  # cumulative wall time folding chains


@dataclasses.dataclass
class _ChainMeta:
    """What the delta chain currently covers, per shard: the chain serves
    exactly `reader_key`; a new reader is delta-eligible iff every
    shard's covered segments are a PREFIX of its segments and its
    live_version is unchanged."""

    reader_key: Tuple
    covered: Dict[int, Tuple[str, ...]]
    live_versions: Dict[int, int]
    union: Optional["_UnionView"] = None


@dataclasses.dataclass
class PackChain:
    """Resolved residency for one (index, field): the base pack, the
    delta packs chained on it, and the row-space view results resolve
    against (`base` itself when the chain is empty)."""

    base: ResidentPack
    deltas: Tuple[ResidentPack, ...]
    view: Any
    reader_key: Tuple


class _UnionView:
    """Read-only facade over base + delta packs presenting ONE
    concatenated row/id space to the fetch phase. Pack i's kernel rows
    re-base by ``offsets[i]`` (running sum of padded row counts); id
    ordinals re-base via concatenated row_offset/id_cat/id_json tables.
    Exposes exactly the members the serializer and columnar fetch consume
    (resolve_ids / id_json / row_origin / row_segments / row_shard /
    readers)."""

    def __init__(self, packs: List[ResidentPack]):
        self.packs = tuple(packs)
        offsets: List[int] = []
        off = 0
        id_off = 0
        row_origin: List[Tuple[int, str]] = []
        row_segments: List[Any] = []
        shard_parts, off_parts, id_parts = [], [], []
        for p in self.packs:
            offsets.append(off)
            s_pad = p.pack.num_shards
            ro = list(p.row_origin)
            ro += [(-1, "")] * (s_pad - len(ro))
            row_origin.extend(ro)
            rs = list(p.row_segments or ())
            rs += [None] * (s_pad - len(rs))
            row_segments.extend(rs)
            shard_parts.append(p.row_shard)
            off_parts.append(p.row_offset + id_off)
            id_parts.append(p.id_cat)
            id_off += len(p.id_cat)
            off += s_pad
        self.offsets = tuple(offsets)
        self.row_origin = row_origin
        self.row_segments = row_segments
        self.row_shard = np.concatenate(shard_parts)
        self.row_offset = np.concatenate(off_parts)
        self.id_cat = np.concatenate(id_parts)
        self.id_json = JsonLiterals.concat([p.id_json for p in self.packs])
        self.source_json: Optional[JsonLiterals] = None
        self.source_lock = threading.Lock()
        base = self.packs[0]
        self.pack = base.pack          # stats consumers see the base
        self.readers = base.readers
        self.reader_key = base.reader_key  # kept current by the chain
        self.hbm_bytes = sum(int(p.hbm_bytes) for p in self.packs)
        self.hbm_detail = dict(base.hbm_detail)
        self.comp_streams = None
        self.group_mesh = base.group_mesh
        self.group_id = base.group_id

    @property
    def compressed(self) -> bool:
        return False

    def resolve_ids(self, rows: np.ndarray, ords: np.ndarray) -> np.ndarray:
        if len(rows) == 0:
            return np.empty(0, dtype=object)
        return self.id_cat[self.row_offset[rows] + ords]

    def source_literals(self, stages: Optional["StageTimes"] = None
                        ) -> Optional[JsonLiterals]:
        """Each pack's source table (built where it was not), joined
        in the chain's order, once a view."""
        with self.source_lock:
            if self.source_json is None:
                tables = [p.source_literals(stages) for p in self.packs]
                with tracing.stage(stages, "source_table", annotate=False,
                                   cpu=False):
                    self.source_json = JsonLiterals.concat(tables)
            return self.source_json


class IndexPackCache:
    """Builds and caches the StackedShardPack for an (index, field).

    The cache key is the tuple of per-shard reader identities: engine
    refresh/merge swaps the reader object, so identity equality is exactly
    "segments or live-docs changed". HBM bytes are charged to the `hbm`
    breaker before device placement and released on eviction."""

    def __init__(self, mesh=None, breaker=None, group_id=None):
        self._mesh = mesh
        self._lock = threading.Lock()
        self._cache: Dict[Tuple[str, str], ResidentPack] = {}
        self._breaker = breaker
        # fault-domain placement: a group-scoped cache stamps its id and
        # sub-mesh onto every pack it builds so launches route to the
        # group's devices (None = the classic whole-mesh cache)
        self.group_id = group_id
        # per-key build serialization: a refresh-triggered rebuild of one
        # (index, field) pack must not block fast-path lookups of every
        # other key on the node (ADVICE r2 low #4)
        self._build_locks: Dict[Tuple[str, str], threading.Lock] = {}
        # on_evict(old_resident): set by TpuSearchService so eviction
        # also retires the pack's micro-batch queue (its strong ref
        # would otherwise pin the freed device arrays)
        self.on_evict = None
        self.hits = 0          # lookups served by the current pack
        self.misses = 0        # lookups that (re)built a pack
        self.stale_served = 0  # lookups served stale during a rebuild
        # warmth (last-access stamp) and last-known HBM cost per key.
        # Both SURVIVE invalidate_all: partial-mesh recovery orders
        # re-residency warmest-first and projects bytes against the
        # shrunken headroom before rebuilding anything.
        self._heat: Dict[Tuple[str, str], float] = {}
        self._last_bytes: Dict[Tuple[str, str], int] = {}
        # -- streaming delta chain state -------------------------------
        self.delta_enabled = False
        self.delta_max_packs = 4       # chain length that requests a fold
        self.delta_max_docs = 50_000   # total delta docs that request one
        self.delta_stats: Optional[DeltaStats] = None
        self.on_compact_needed = None  # callable(key), set by the service
        self._deltas: Dict[Tuple[str, str], List[ResidentPack]] = {}
        self._chain_meta: Dict[Tuple[str, str], _ChainMeta] = {}
        self._services: Dict[Tuple[str, str], Any] = {}  # compactor's map

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            # per-(index,field) HBM breakdown: raw vs resident bytes,
            # ratio, block metadata — the /_tpu/stats + Prometheus view
            # of the compressed-pack capacity win
            packs = {f"{idx}/{field}": {
                **entry.hbm_detail,
                # host bytes of the pack's source table (0 until a
                # `_source` block renders from it)
                "source_table_bytes": (entry.source_json.nbytes
                                       if entry.source_json is not None
                                       else 0)}
                     for (idx, field), entry in self._cache.items()}
            deltas = {
                f"{idx}/{field}": {
                    "packs": len(lst),
                    "bytes": sum(int(p.hbm_bytes) for p in lst),
                    "docs": sum(int(p.hbm_detail.get("docs", 0))
                                for p in lst)}
                for (idx, field), lst in self._deltas.items() if lst}
            return {"resident": len(self._cache), "hits": self.hits,
                    "misses": self.misses,
                    "stale_served": self.stale_served,
                    "packs": packs, "deltas": deltas}

    def delta_totals(self) -> Tuple[int, int]:
        """(resident delta packs, resident delta bytes) on this cache."""
        with self._lock:
            n = sum(len(lst) for lst in self._deltas.values())
            b = sum(int(p.hbm_bytes) for lst in self._deltas.values()
                    for p in lst)
            return n, b

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_mesh(shape=(1, _n_local_devices()))
        return self._mesh

    def set_mesh(self, mesh) -> None:
        """Re-target future builds at a different mesh (partial-mesh
        recovery). Only sound on an EMPTY cache — existing packs were
        placed with the old sharding — so callers invalidate first."""
        with self._lock:
            if self._cache:
                raise RuntimeError("set_mesh on a non-empty pack cache; "
                                   "invalidate_all first")
            self._mesh = mesh

    def heat_of(self, key: Tuple[str, str]) -> float:
        with self._lock:
            return self._heat.get(key, 0.0)

    def peek(self, key: Tuple[str, str]) -> Optional[ResidentPack]:
        """Current resident for `key` without building (placement's
        live-replica check)."""
        with self._lock:
            return self._cache.get(tuple(key))

    def resident_keys(self) -> List[Tuple[str, str]]:
        with self._lock:
            return sorted(self._cache)

    def residents(self) -> List[ResidentPack]:
        with self._lock:
            return list(self._cache.values())

    def bytes_of(self, key: Tuple[str, str]) -> int:
        with self._lock:
            return self._last_bytes.get(key, 0)

    def get(self, index_service, field: str) -> Optional[ResidentPack]:
        readers = []
        for shard_num, shard in sorted(index_service.shards.items()):
            readers.append((shard_num, shard.acquire_searcher()))
        reader_key = tuple(id(r) for _, r in readers)
        key = (index_service.name, field)
        with self._lock:
            self._heat[key] = time.monotonic()
            entry = self._cache.get(key)
            if entry is not None and entry.reader_key == reader_key:
                self.hits += 1
                return entry
            build_lock = self._build_locks.setdefault(key,
                                                      threading.Lock())
        # STALE-WHILE-REBUILD (the reference serves the old reader while
        # a refresh opens the new one): if another thread is already
        # rebuilding this key, serve the previous pack instead of
        # queueing behind a minutes-long build — a background merge
        # completing mid-traffic must not stall every search into the
        # batch timeout (observed at 2.6M docs: ~150s pack build →
        # timeout storm → kernel breaker trip). Staleness is bounded by
        # one refresh lag, the same window the reference exposes.
        if not build_lock.acquire(blocking=False):
            with self._lock:
                entry = self._cache.get(key)
                if entry is not None:
                    self.stale_served += 1
            if entry is not None:
                return entry
            build_lock.acquire()  # no old pack — must wait for a build
        try:
            with self._lock:
                entry = self._cache.get(key)
                if entry is not None and entry.reader_key == reader_key:
                    self.hits += 1
                    return entry
            return self._build_and_swap(key, readers, field, reader_key)
        finally:
            build_lock.release()

    # -- streaming delta chain -----------------------------------------

    def _drop_deltas_locked(self, key) -> List[ResidentPack]:
        """Release every delta chained on `key` (caller holds _lock and
        runs on_evict after dropping it)."""
        dropped = self._deltas.pop(key, [])
        for p in dropped:
            if self._breaker is not None:
                self._breaker.release(p.hbm_bytes)
        meta = self._chain_meta.get(key)
        if meta is not None:
            meta.union = None
        return dropped

    def _set_chain_meta_locked(self, key, readers, reader_key) -> None:
        if not self.delta_enabled:
            return
        self._chain_meta[key] = _ChainMeta(
            reader_key=reader_key,
            covered={num: tuple(v.segment.name for v in r.views)
                     for num, r in readers},
            live_versions={num: getattr(r, "live_version", 0)
                           for num, r in readers})

    def _chain_locked(self, key) -> Optional[PackChain]:
        base = self._cache.get(key)
        meta = self._chain_meta.get(key)
        if base is None or meta is None:
            return None
        deltas = tuple(self._deltas.get(key, ()))
        if not deltas:
            return PackChain(base, (), base, meta.reader_key)
        return PackChain(base, deltas, meta.union, meta.reader_key)

    def _delta_eligible(self, meta: _ChainMeta, readers):
        """Append-only check, per shard: the chain's covered segments
        must be a PREFIX of the new reader's and its live_version
        unchanged (an update/delete of a committed doc bumps it).
        Returns {shard_num: [uncovered SegmentViews]} or None → full
        rebuild."""
        new = dict(readers)
        if set(new) != set(meta.covered):
            return None
        fresh: Dict[int, List[Any]] = {}
        for num, r in new.items():
            names = tuple(v.segment.name for v in r.views)
            old = meta.covered[num]
            if names[:len(old)] != old:
                return None
            if getattr(r, "live_version", 0) != meta.live_versions.get(
                    num, 0):
                return None
            fresh[num] = list(r.views[len(old):])
        return fresh

    def get_chain(self, index_service, field: str) -> Optional[PackChain]:
        """Chain-aware residency: like get(), but an append-only refresh
        builds a small delta pack over only the NEW segments instead of
        re-placing the whole image."""
        if not self.delta_enabled:
            entry = self.get(index_service, field)
            return None if entry is None else PackChain(
                entry, (), entry, entry.reader_key)
        readers = []
        for shard_num, shard in sorted(index_service.shards.items()):
            readers.append((shard_num, shard.acquire_searcher()))
        reader_key = tuple(id(r) for _, r in readers)
        key = (index_service.name, field)
        with self._lock:
            self._heat[key] = time.monotonic()
            self._services[key] = index_service
            chain = self._chain_locked(key)
            if chain is None:
                # base resident but never chained (built via get())
                entry = self._cache.get(key)
                if entry is not None and entry.reader_key == reader_key:
                    self._set_chain_meta_locked(key, readers, reader_key)
                    chain = self._chain_locked(key)
            if chain is not None and chain.reader_key == reader_key:
                self.hits += 1
                return chain
            build_lock = self._build_locks.setdefault(key,
                                                      threading.Lock())
        # stale-while-rebuild applies to the chain exactly as to get()
        if not build_lock.acquire(blocking=False):
            with self._lock:
                chain = self._chain_locked(key)
                if chain is not None:
                    self.stale_served += 1
            if chain is not None:
                return chain
            build_lock.acquire()
        try:
            with self._lock:
                chain = self._chain_locked(key)
                if chain is not None and chain.reader_key == reader_key:
                    self.hits += 1
                    return chain
                base = self._cache.get(key)
                meta = self._chain_meta.get(key)
            fresh = None
            if base is not None and meta is not None:
                fresh = self._delta_eligible(meta, readers)
            if fresh is None:
                entry = self._build_and_swap(key, readers, field,
                                             reader_key)
                return None if entry is None else PackChain(
                    entry, (), entry, reader_key)
            return self._append_delta(key, base, fresh, readers, field,
                                      reader_key)
        finally:
            build_lock.release()

    def _build_and_swap(self, key, readers, field,
                        reader_key) -> Optional[ResidentPack]:
        """Full build + swap, chain reset. Caller holds the build lock."""
        with self._lock:
            self.misses += 1
        while True:
            entry = self._build(readers, field, reader_key)
            with self._lock:
                rebuild = key in self._cache
                evicted = self._swap_in_locked(key, entry, readers,
                                               reader_key)
            if evicted is not None:
                break  # else: a remesh overtook the build; build again
        if entry is not None:
            events.emit("pack.build", index=key[0], field=key[1],
                        hbm_bytes=int(entry.hbm_bytes),
                        compressed=entry.compressed, rebuild=rebuild,
                        group=self.group_id)
        if self.on_evict is not None:
            for stale in evicted:
                self.on_evict(stale)
        # a base build takes seconds and no `_search` waits on it (the
        # generation before serves meanwhile): the place for the full
        # collection that keeps garbage cycles out of the frozen heap,
        # the one it replaced thawed first
        tracing.HEAP.settle(replaced=bool(evicted))
        return entry

    def _swap_in_locked(self, key, entry: Optional[ResidentPack], readers,
                        reader_key) -> Optional[List[ResidentPack]]:
        """Make a full build the resident of `key`: the pack it replaces
        and every delta chained on it are released (a full build covers
        everything the chain did, so they drain to exactly zero) and
        returned for the caller's `on_evict`. None, with the entry's own
        charge released, where a remesh swapped the mesh under the
        build: its arrays sit on the mesh of before. Caller holds
        `_lock`."""
        if entry is None:
            return []
        if entry.mesh is not self._mesh:
            if self._breaker is not None:
                self._breaker.release(entry.hbm_bytes)
            return None
        evicted: List[ResidentPack] = []
        old = self._cache.get(key)
        if old is not None:
            if self._breaker is not None:
                self._breaker.release(old.hbm_bytes)
            evicted.append(old)
        self._cache[key] = entry
        self._last_bytes[key] = int(entry.hbm_bytes)
        evicted += self._drop_deltas_locked(key)
        self._set_chain_meta_locked(key, readers, reader_key)
        return evicted

    def _append_delta(self, key, base: ResidentPack, fresh, readers,
                      field: str, reader_key) -> Optional[PackChain]:
        """Build one immutable delta pack from the uncovered segments
        and chain it on the base. Caller holds the build lock."""
        docs = sum(v.segment.num_docs for views in fresh.values()
                   for v in views
                   if field in v.segment.postings)
        events.emit("delta.append", index=key[0], field=field,
                    docs=int(docs),
                    segments=sum(len(v) for v in fresh.values()))
        delta = self._build_delta(readers, fresh, field, reader_key)
        want_compact = False
        chain = None
        with self._lock:
            # a teardown (`invalidate_all`, `invalidate`) does not take
            # the build lock: where it dropped the base while the delta
            # was built, the delta covers nothing that is resident
            orphaned = self._cache.get(key) is not base
            if orphaned:
                if delta is not None and self._breaker is not None:
                    self._breaker.release(delta.hbm_bytes)
            else:
                if delta is not None:
                    self._deltas.setdefault(key, []).append(delta)
                # even a field-less delta advances coverage: the chain now
                # answers for this reader set
                self._set_chain_meta_locked(key, readers, reader_key)
                meta = self._chain_meta[key]
                deltas = list(self._deltas.get(key, ()))
                if deltas:
                    meta.union = _UnionView([base] + deltas)
                    meta.union.reader_key = reader_key
                    total_docs = sum(
                        int(p.hbm_detail.get("docs", 0)) for p in deltas)
                    want_compact = (len(deltas) > self.delta_max_packs
                                    or total_docs > self.delta_max_docs)
                chain = self._chain_locked(key)
        if orphaned:
            if delta is not None and self.on_evict is not None:
                self.on_evict(delta)
            entry = self._build_and_swap(key, readers, field, reader_key)
            return None if entry is None else PackChain(
                entry, (), entry, reader_key)
        if delta is not None:
            if self.delta_stats is not None:
                self.delta_stats.appends += 1
                self.delta_stats.seals += 1
            events.emit("delta.seal", index=key[0], field=field,
                        hbm_bytes=int(delta.hbm_bytes),
                        chain_len=len(chain.deltas))
        if want_compact and self.on_compact_needed is not None:
            self.on_compact_needed(key)
        return chain

    def _build_delta(self, readers, fresh, field: str,
                     reader_key) -> Optional[ResidentPack]:
        segments, live, groups = [], [], []
        row_origin: List[Tuple[int, str]] = []
        row_segments: List[Any] = []
        for group_idx, (shard_num, _reader) in enumerate(readers):
            for view in fresh.get(shard_num, ()):
                if field not in view.segment.postings:
                    continue
                segments.append(view.segment)
                n = view.segment.num_docs
                live.append(view.live_mask[:n].copy())
                groups.append(group_idx)
                row_origin.append((shard_num, view.segment.name))
                row_segments.append(view.segment)
        if not segments:
            return None
        k1 = readers[0][1].k1
        b = readers[0][1].b
        mesh = self.mesh  # read once: a remesh may swap it under us
        n_sh = mesh.shape[SHARD_AXIS]
        s_pad = ((len(segments) + n_sh - 1) // n_sh) * n_sh
        pack = dist.build_delta_pack(segments, field, live_docs=live,
                                     k1=k1, b=b, pad_shards_to=s_pad,
                                     row_groups=groups)
        return self._place_pack(pack, field, readers, reader_key,
                                row_origin, row_segments, mesh,
                                label=f"delta[{field}]",
                                compressible=False)

    def compact(self, key) -> bool:
        """Fold the delta chain into a fresh full (compressed) base pack.
        Releases the old base + every delta exactly (the drain-to-zero
        invariant covers compaction too); on failure the chain keeps
        serving and a `compaction_failure` incident is opened."""
        index_service = self._services.get(key)
        if index_service is None:
            return False
        field = key[1]
        with self._lock:
            build_lock = self._build_locks.setdefault(key,
                                                      threading.Lock())
        with build_lock:
            with self._lock:
                deltas = list(self._deltas.get(key, ()))
            if not deltas:
                return False
            delta_bytes = sum(int(p.hbm_bytes) for p in deltas)
            t0 = time.monotonic()
            events.emit("compaction.begin", index=key[0], field=field,
                        delta_packs=len(deltas),
                        delta_bytes=delta_bytes)
            try:
                for hook in list(COMPACTION_FAULT_HOOKS):
                    hook(key)  # chaos seam: may park or raise
                readers = []
                for shard_num, shard in sorted(
                        index_service.shards.items()):
                    readers.append((shard_num, shard.acquire_searcher()))
                reader_key = tuple(id(r) for _, r in readers)
                entry = self._build(readers, field, reader_key)
            except Exception as exc:  # noqa: BLE001 — chain keeps serving
                if self.delta_stats is not None:
                    self.delta_stats.compaction_failures += 1
                events.emit("compaction.end", severity="error",
                            index=key[0], field=field, error=str(exc),
                            duration_s=round(time.monotonic() - t0, 6))
                events.incident("compaction_failure", index=key[0],
                                field=field, error=str(exc))
                return False
            with self._lock:
                evicted = self._swap_in_locked(key, entry, readers,
                                               reader_key)
            if evicted is None:
                # a remesh overtook the fold: its teardown dropped the
                # chain, and recovery re-attains residency
                return False
            if self.on_evict is not None:
                for stale in evicted:
                    self.on_evict(stale)
            tracing.HEAP.settle(replaced=True)
            dur = time.monotonic() - t0
            if self.delta_stats is not None:
                self.delta_stats.compactions += 1
                self.delta_stats.compact_seconds += dur
            events.emit("compaction.end", index=key[0], field=field,
                        duration_s=round(dur, 6),
                        reclaimed_bytes=delta_bytes,
                        hbm_bytes=(int(entry.hbm_bytes)
                                   if entry is not None else 0))
            return entry is not None

    def _build(self, readers, field: str,
               reader_key: Tuple) -> Optional[ResidentPack]:
        segments = []
        live = []
        groups = []
        row_origin: List[Tuple[int, str]] = []
        row_segments: List[Any] = []
        for group_idx, (shard_num, reader) in enumerate(readers):
            for view in reader.views:
                if field not in view.segment.postings:
                    continue
                segments.append(view.segment)
                n = view.segment.num_docs
                live.append(view.live_mask[:n].copy())
                groups.append(group_idx)
                row_origin.append((shard_num, view.segment.name))
                row_segments.append(view.segment)
        if not segments:
            return None
        k1 = readers[0][1].k1
        b = readers[0][1].b
        # pad rows to a multiple of the mesh's shards axis (the mesh is
        # read once: a remesh may swap it under the build)
        mesh = self.mesh
        n_sh = mesh.shape[SHARD_AXIS]
        s_pad = ((len(segments) + n_sh - 1) // n_sh) * n_sh
        pack = dist.build_stacked_pack(segments, field, live_docs=live,
                                       k1=k1, b=b, pad_shards_to=s_pad,
                                       row_groups=groups)
        return self._place_pack(pack, field, readers, reader_key,
                                row_origin, row_segments, mesh,
                                label=f"pack[{field}]", compressible=True)

    def _place_pack(self, pack, field: str, readers, reader_key: Tuple,
                    row_origin, row_segments, mesh, *, label: str,
                    compressible: bool) -> ResidentPack:
        """Charge the breaker, place `pack` on `mesh`, build resolution
        tables. `compressible=False` (delta packs) forces the raw format:
        deltas are small and short-lived — compaction folds them into
        the compressed base, so per-delta stream compression would buy
        bytes at the cost of append latency."""
        # what the uncompressed resident image costs: doc-sorted pack +
        # the impact-sorted copy (same two arrays re-ordered) — the
        # baseline /_tpu/stats' compression_ratio compares against
        raw_bytes = (pack.nbytes_device() + pack.flat_docs.nbytes
                     + pack.flat_impact.nbytes)
        n_docs = int(sum(len(ids) for ids in pack.shard_doc_ids))
        streams = None
        comp_reason = None
        if compressible and KERNEL_CONFIG["compressed_pack"]:
            comp_reason = dist.compress_pack_reason(pack)
            if comp_reason is None:
                streams = dist.build_compressed_streams(pack)
            else:
                logger.info("pack[%s] not compressible (%s); resident "
                            "in raw format", field, comp_reason)
        if streams is not None:
            # compressed residency: the 16-bit streams + block metadata +
            # residual tables are the WHOLE device image — no f32 copy,
            # no impact-sorted copy, no pruned path
            hbm = streams.nbytes_device()
            if self._breaker is not None:
                self._breaker.add_estimate_bytes_and_maybe_break(
                    hbm, label=label)
            try:
                arrays = dist.device_put_compressed(streams, mesh)
            except Exception:
                if self._breaker is not None:
                    self._breaker.release(hbm)
                raise
            imp_docs = imp_impacts = None
            imp_arrays = None
        else:
            imp_docs, imp_impacts = dist.build_impact_sorted(pack)
            hbm = (pack.nbytes_device() + imp_docs.nbytes
                   + imp_impacts.nbytes)
            if self._breaker is not None:
                self._breaker.add_estimate_bytes_and_maybe_break(
                    hbm, label=label)
            try:
                arrays = dist.device_put_pack(pack, mesh)
                imp_arrays = dist.device_put_pack(
                    dataclasses.replace(pack, flat_docs=imp_docs,
                                        flat_impact=imp_impacts), mesh)
            except Exception:
                if self._breaker is not None:  # undo the charge on failure
                    self._breaker.release(hbm)
                raise
        n_postings = int(sum(int(rs[-1]) for rs in pack.row_starts))
        hbm_detail = {
            "compressed": streams is not None,
            "hbm_bytes": int(hbm),
            "raw_bytes": int(raw_bytes),
            "compression_ratio": (float(hbm) / raw_bytes if raw_bytes
                                  else 1.0),
            "block_meta_bytes": (int(streams.block_max.nbytes)
                                 if streams is not None else 0),
            "residual_bytes": (int(streams.res_vals.nbytes)
                               if streams is not None else 0),
            # delta doc stream (PR 15): u8 block-relative deltas + u16
            # per-block bases instead of the u16 doc stream — the bytes
            # the "≤ 6 B/posting" acceptance is accounted against
            "doc_delta": streams is not None and streams.delta,
            "doc_base_bytes": (int(streams.doc_bases.nbytes)
                               if streams is not None and streams.delta
                               else 0),
            "docs": n_docs,
            "hbm_bytes_per_doc": (float(hbm) / n_docs if n_docs else 0.0),
            "postings": n_postings,
            "hbm_bytes_per_posting": (float(hbm) / n_postings
                                      if n_postings else 0.0),
        }
        if comp_reason is not None:
            hbm_detail["compress_reason"] = comp_reason
        # vectorized-resolution tables: row → owning shard, row → offset
        # into one concatenated external-id array (object dtype: fancy
        # indexing is C-speed, the per-hit Python lookup is gone)
        s_pad = pack.num_shards
        row_shard = np.full(s_pad, -1, dtype=np.int32)
        row_shard[: len(row_origin)] = [sn for sn, _ in row_origin]
        sizes = [len(ids) for ids in pack.shard_doc_ids]
        row_offset = np.zeros(s_pad, dtype=np.int64)
        np.cumsum(sizes[:-1], out=row_offset[1:len(sizes)])
        id_cat = np.empty(int(sum(sizes)), dtype=object)
        off = 0
        for ids in pack.shard_doc_ids:
            id_cat[off: off + len(ids)] = ids
            off += len(ids)
        resident = ResidentPack(
            pack, arrays, row_origin, reader_key, hbm,
            readers={num: r for num, r in readers},
            imp_host=(None if imp_docs is None
                      else (imp_docs, imp_impacts)),
            imp_device_arrays=imp_arrays,
            row_shard=row_shard, row_offset=row_offset,
            id_cat=id_cat,
            id_json=JsonLiterals.build(pack.shard_doc_ids),
            row_segments=row_segments,
            comp_streams=streams, hbm_detail=hbm_detail,
            group_id=self.group_id, mesh=mesh, cache=self)
        # the pack, its id table and resolution tables are here to stay:
        # out of the collector's sight, under traffic too (microseconds)
        tracing.HEAP.freeze()
        return resident

    def charge(self, resident: ResidentPack, nbytes: int,
               label: str = "") -> None:
        """Charge `nbytes` more device memory of `resident` (its head
        block) to the `hbm` breaker, released with the pack's own bytes
        on eviction; a negative `nbytes` gives it back. Raises where the
        pack is no longer resident here (its charge is already released)
        or the breaker trips."""
        with self._lock:
            key = next((key for key, entry in self._cache.items()
                        if entry is resident), None)
            if key is None and not any(
                    p is resident for lst in self._deltas.values()
                    for p in lst):
                raise RuntimeError(f"{label}: the pack is not resident")
            if self._breaker is not None:
                if nbytes > 0:
                    self._breaker.add_estimate_bytes_and_maybe_break(
                        nbytes, label=label)
                else:
                    self._breaker.release(-nbytes)
            resident.hbm_bytes += nbytes
            if key is not None:
                self._last_bytes[key] = int(resident.hbm_bytes)

    def invalidate(self, index_name: str) -> None:
        evicted = []
        with self._lock:
            for key in [k for k in self._cache if k[0] == index_name]:
                entry = self._cache.pop(key)
                if self._breaker is not None:
                    self._breaker.release(entry.hbm_bytes)
                evicted.append(entry)
            for key in [k for k in self._deltas if k[0] == index_name]:
                evicted.extend(self._drop_deltas_locked(key))
            # deliberate eviction forgets the key entirely (unlike
            # invalidate_all, whose keys recovery re-attains)
            for key in [k for k in self._heat if k[0] == index_name]:
                self._heat.pop(key, None)
                self._last_bytes.pop(key, None)
                self._chain_meta.pop(key, None)
                self._services.pop(key, None)
        if evicted:
            events.emit("pack.evict", index=index_name,
                        packs=len(evicted),
                        hbm_bytes=sum(int(e.hbm_bytes) for e in evicted),
                        group=self.group_id)
        if self.on_evict is not None:
            for entry in evicted:
                self.on_evict(entry)

    def invalidate_all(self) -> List[Tuple[str, str]]:
        """Crash-recovery drop of EVERY resident pack (the batcher
        supervisor's respawn path): each pack's full charge is released,
        so afterwards the `hbm` breaker reads EXACTLY zero — the same
        drain-to-zero invariant the per-index lifecycle tests assert.
        Returns the dropped (index, field) keys so recovery can
        re-attain residency eagerly."""
        dropped: List[ResidentPack] = []
        with self._lock:
            entries = list(self._cache.items())
            self._cache.clear()
            for _key, entry in entries:
                if self._breaker is not None:
                    self._breaker.release(entry.hbm_bytes)
            for key in list(self._deltas):
                dropped.extend(self._drop_deltas_locked(key))
            # chain coverage died with the packs; recovery re-attains
            # residency through a full rebuild which re-stamps it
            self._chain_meta.clear()
        if self.on_evict is not None:
            for _key, entry in entries:
                self.on_evict(entry)
            for entry in dropped:
                self.on_evict(entry)
        if entries:
            tracing.HEAP.settle(replaced=True)
        return [key for key, _entry in entries]


# ---------------------------------------------------------------------------
# micro-batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Pending:
    flat: FlatQuery
    k: int
    future: Future
    # the submitting request's span (None when untraced): batch workers
    # parent their launch/device spans under the FIRST traced query of
    # the train so a trace shows which batch served it
    trace_span: Any = None
    # batch_wait decomposition marks (perf_counter). The launch thread
    # stamps cycle/take/launched, the completer ready (its launch's
    # `device_wait` ended), decoded (the train's `decode` ended) and set
    # (just before its `set_result`); the REQUEST thread reads them back
    # after `future.result()` so the sub-stages sum exactly to
    # `batch_wait` measured on the same thread.
    t_submit: float = 0.0
    t_cycle: float = 0.0
    t_take: float = 0.0
    t_launched: float = 0.0
    t_ready: float = 0.0
    t_decoded: float = 0.0
    t_set: float = 0.0
    # the queue's sequence number of the train that took this query: the
    # launch thread's and the completer's stages and annotations of that
    # train, and a traced request's batch_launch/batch_finish spans,
    # carry the same number
    train: int = 0
    # owning tenant (stamped on the request thread): batch composition
    # takes weighted round-robin across tenant lanes so one tenant's
    # burst can't monopolize batch slots ahead of tenants already
    # waiting — the starved lane's cost would show up as batch_wait.queue
    tenant: str = tenancy.DEFAULT_TENANT


#: a request's `batch_wait` split → its stage names
_BATCH_WAIT_PARTS = {name: f"batch_wait.{name}" for name in (
    "queue", "window", "dispatch", "completion",
    "device", "decode", "deliver", "wake")}


def _batch_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _take_fair(pendings: List[_Pending], cap: int,
               weight_of) -> Tuple[List[_Pending], List[_Pending]]:
    """Compose one batch train of up to `cap` queries from `pendings`
    by weighted round-robin across tenant lanes → (taken, remaining).

    Each waiting tenant gets a quota proportional to its weight (never
    below 1 slot, so no lane starves); lanes are drained one query at a
    time in rotation, FIFO within a lane. Leftover capacity after every
    quota is met is filled ignoring quotas — a full train always beats
    strict proportionality (padding is already paid). The overwhelmingly
    common single-tenant case returns a plain slice."""
    if len(pendings) <= cap:
        return pendings, []
    first = pendings[0].tenant
    if all(p.tenant == first for p in pendings):
        return pendings[:cap], pendings[cap:]
    lanes: Dict[str, List[_Pending]] = {}
    order: List[str] = []
    for p in pendings:
        lane = lanes.get(p.tenant)
        if lane is None:
            lanes[p.tenant] = lane = []
            order.append(p.tenant)
        lane.append(p)
    weights = {t: max(1e-6, float(weight_of(t))) for t in order}
    total = sum(weights.values())
    quota = {t: max(1, int(cap * weights[t] / total)) for t in order}
    taken: List[_Pending] = []
    cursor = {t: 0 for t in order}
    enforce_quota = True
    while len(taken) < cap:
        progressed = False
        for t in order:
            if len(taken) >= cap:
                break
            i = cursor[t]
            if i >= len(lanes[t]) or (enforce_quota and i >= quota[t]):
                continue
            taken.append(lanes[t][i])
            cursor[t] = i + 1
            progressed = True
        if not progressed:
            if enforce_quota:
                enforce_quota = False
                continue
            break
    taken_ids = {id(p) for p in taken}
    # remainder keeps the original arrival order (lane concatenation
    # would distort the next train's rotation and the queue-wait marks)
    remaining = [p for p in pendings if id(p) not in taken_ids]
    return taken, remaining


class _PackQueue:
    """One pack's pending queries + a launch worker + a completion
    thread. Packs batch independently, so pack A's kernel launch
    (including a first-compile stall) never delays pack B's queries.
    Launch and completion are SPLIT so batch N+1 is prepped and
    dispatched while batch N still executes on device — JAX async
    dispatch double-buffers the kernel.

    The backpressure is `n_inflight`, the trains launched and not yet
    finished, and a train is formed as late as the device allows
    (`_hold`): none while `PIPELINE_DEPTH` are unfinished, and while the
    device still has a train queued behind the one it runs the pending
    queries stay in the open queue and grow to `max_batch`. A query held
    there loses nothing: launched early it would have waited in the
    device's queue instead, in a train frozen at the size it had."""

    IDLE_EXIT_S = 60.0
    PIPELINE_DEPTH = 3

    def __init__(self, batcher: "MicroBatcher", resident: ResidentPack):
        import queue as _queue
        self.batcher = batcher
        self.resident = resident
        self.cv = threading.Condition()
        self.pendings: List[_Pending] = []
        self.closed = False
        # launched-but-not-finished batches; inflight.qsize() is NOT a
        # busy signal (the completer dequeues before materializing)
        self.n_inflight = 0
        self.inflight: Any = _queue.Queue(maxsize=self.PIPELINE_DEPTH)
        self.train_seq = 0
        # each thread's time, partitioned into named states (stages
        # `batcher.*` / `completer.*` and profiler annotations)
        self.launch_states = tracing.ThreadStates(batcher.stages, "batcher")
        self.complete_states = tracing.ThreadStates(batcher.stages,
                                                    "completer")
        self.completer = threading.Thread(target=self._complete,
                                          daemon=True,
                                          name="micro-batcher-complete")
        self.completer.start()
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="micro-batcher-pack")
        self.thread.start()

    def submit(self, pending: _Pending) -> bool:
        with self.cv:
            if self.closed:
                return False
            self.pendings.append(pending)
            self.cv.notify_all()
            return True

    def launch_mesh(self):
        """The mesh this queue's launches run on: the resident's
        placement-group sub-mesh when the pack is group-placed, else
        the batcher-wide mesh (single-group serving, unchanged)."""
        return getattr(self.resident, "group_mesh", None) \
            or self.batcher.mesh

    def close(self) -> None:
        with self.cv:
            self.closed = True
            self.cv.notify_all()

    def _hold(self) -> str:
        """With `cv` held and queries pending: wait until a train is due
        → why the hold ended (a `HOLD_EXITS` label). A launch costs the
        device about the same whatever it carries, so the train is
        formed as late as the device allows and the queue stays open
        until then:

        - while `PIPELINE_DEPTH` trains are launched and unfinished
          nothing is taken (state `blocked`). A train holds its results
          on the device from its dispatch until the completer has copied
          them, so this bounds that memory too, at a level a full
          pipeline reaches in every run;
        - `full`: `max_batch` are pending and a slot is free: at once;
        - while two or more are unfinished the device has a train queued
          behind the one it runs, so it cannot run dry before the next
          completion: keep accumulating (the completer notifies);
        - `backlog_low`: only the train the device is running remains,
          and the next must be prepared now. It goes with half a train,
          or, after having waited on a busy device, one REFILL window
          later, so that the just-released cohort (still assembling its
          responses under the GIL) makes this train instead of
          fragmenting into the next one;
        - `idle_window`: nothing is in flight: `window_s` and no more —
          no refill, no latency floor."""
        batcher, states = self.batcher, self.launch_states
        half_train = max(8, batcher.max_batch // 2)
        deadline = time.monotonic() + batcher.window_s
        waited_busy = False
        # (`fail_pending` may empty the queue under a hold: nothing to take)
        while self.pendings and not self.closed:
            if self.n_inflight >= self.PIPELINE_DEPTH:
                if states.state != "blocked":
                    states.switch("blocked")
                self.cv.wait(timeout=0.25)
                continue
            if states.state != "hold":
                states.switch("hold")
            pending = len(self.pendings)
            if pending >= batcher.max_batch:
                break
            now = time.monotonic()
            if self.n_inflight >= 2 or (
                    now >= deadline and self.n_inflight > 0
                    and pending < half_train):
                waited_busy = True
                self.cv.wait(timeout=0.25)
            elif now < deadline:
                self.cv.wait(timeout=deadline - now)
            elif waited_busy:
                waited_busy = False
                deadline = now + max(0.05, batcher.window_s)
            else:
                break
        if len(self.pendings) >= batcher.max_batch:
            return "full"
        return "backlog_low" if self.n_inflight else "idle_window"

    def _run(self) -> None:
        batcher = self.batcher
        states = self.launch_states
        try:
            while True:
                retire = False
                taken: List[_Pending] = []
                states.train = train = 0
                with self.cv:
                    idle_deadline = time.monotonic() + self.IDLE_EXIT_S
                    if not self.pendings and not self.closed:
                        states.switch("wait")
                    while not self.pendings and not self.closed:
                        remaining = idle_deadline - time.monotonic()
                        if remaining <= 0:
                            # idle: retire this queue (a fresh one spawns
                            # on the next query; stale queues don't leak)
                            self.closed = True
                            retire = True
                            break
                        self.cv.wait(timeout=remaining)
                    if not retire:
                        if self.closed and not self.pendings:
                            return
                        t_cycle = time.perf_counter()
                        hold_exit = self._hold()
                        states.note(pending=len(self.pendings),
                                    exit=hold_exit)
                        states.switch("take")
                        taken, self.pendings = _take_fair(
                            self.pendings, batcher.max_batch,
                            batcher.tenant_weight)
                        if taken:
                            HOLD_EXIT_COUNTS.inc(hold_exit)
                        self.train_seq += 1
                        states.train = train = self.train_seq
                        t_take = time.perf_counter()
                        for p in taken:
                            p.t_cycle = t_cycle
                            p.t_take = t_take
                            p.train = train
                if retire:
                    # NEVER hold cv while taking the batcher lock
                    # (submit's get/create path holds it before us)
                    batcher._retire(self)
                    return
                if not taken:
                    continue
                trace_parent = next(
                    (p.trace_span for p in taken if p.trace_span), None)
                try:
                    profiler.tag_stage("batch_launch")
                    # deadline-stamped dispatch: if this launch wedges,
                    # the watchdog fails `taken` typed and trips the
                    # supervisor instead of hanging the micro-batcher
                    wd = batcher.watchdog
                    mesh = self.launch_mesh()
                    token = (wd.begin("launch", taken,
                                      devices=_mesh_device_ids(mesh))
                             if wd is not None else None)
                    try:
                        with tracing.span_under(trace_parent,
                                                "tpu.batch_launch",
                                                queries=len(taken),
                                                train=train):
                            st = launch_flat_batch(
                                self.resident, [p.flat for p in taken],
                                k=max(p.k for p in taken),
                                mesh=mesh,
                                stages=batcher.stages,
                                max_batch=batcher.max_batch)
                    finally:
                        if wd is not None:
                            wd.end(token)
                except Exception as exc:  # noqa: BLE001 — per query
                    for p in taken:
                        if not p.future.done():
                            p.future.set_exception(exc)
                else:
                    states.switch("blocked")
                    t_launched = time.perf_counter()
                    for p in taken:
                        p.t_launched = t_launched
                    with self.cv:
                        self.n_inflight += 1
                    # never blocks: fewer than PIPELINE_DEPTH were in
                    # flight when this train was taken (the state keeps
                    # its name: the stage is then there to be read even
                    # where `_hold` never met a full pipeline)
                    self.inflight.put((st, taken))
                finally:
                    profiler.tag_stage(None)
        finally:
            states.close()
            self.inflight.put(None)  # stop the completer

    def _complete(self) -> None:
        batcher = self.batcher
        states = self.complete_states
        while True:
            states.train = 0
            states.switch("wait")
            item = self.inflight.get()
            if item is None:
                states.close()
                return
            st, taken = item
            states.train = train = taken[0].train
            trace_parent = next(
                (p.trace_span for p in taken if p.trace_span), None)
            try:
                profiler.tag_stage("batch_finish")
                wd = batcher.watchdog
                token = (wd.begin("finish", taken,
                                  devices=_mesh_device_ids(
                                      self.launch_mesh()))
                         if wd is not None else None)
                try:
                    with tracing.span_under(trace_parent,
                                            "tpu.batch_finish",
                                            queries=len(taken),
                                            train=train):
                        results = finish_flat_batch(st)
                    # each query's launch's `device_wait` end (a stubbed
                    # launch state has none: no marks, no split)
                    ready = ((isinstance(st, dict) and st.get("t_ready"))
                             or [0.0] * len(taken))
                finally:
                    if wd is not None:
                        wd.end(token)
            except Exception as exc:  # noqa: BLE001 — per query
                item = st = None
                states.switch("deliver", queries=len(taken))
                for p in taken:
                    if not p.future.done():
                        p.future.set_exception(exc)
                with self.cv:
                    self.n_inflight -= 1
                    self.cv.notify_all()
                profiler.tag_stage(None)
                continue
            # the train's device results have been copied: let them go
            # before the worker is told that it may launch the next
            item = st = None
            t_decoded = states.switch("deliver", queries=len(taken))
            with batcher._lock:
                batcher.batches_executed += 1
                batcher.queries_executed += len(taken)
            for p, res, t_ready in zip(taken, results, ready):
                # the watchdog may have failed this future already (an
                # overdue launch that eventually returned)
                if not p.future.done():
                    p.t_ready, p.t_decoded = t_ready, t_decoded
                    p.t_set = time.perf_counter()
                    p.future.set_result(res)
            with self.cv:  # batch finished — the worker may launch now
                self.n_inflight -= 1
                self.cv.notify_all()
            profiler.tag_stage(None)


class MicroBatcher:
    """Coalesces concurrent queries per resident pack into single kernel
    launches (SURVEY.md §2.3 P4). On an idle device queries arriving
    within `window_s` (or until `max_batch`) share a launch; on a busy
    one a train is formed as late as the device allows
    (`_PackQueue._hold`); k pads to the max requested. Each pack has its
    own queue + worker, so launches for different packs overlap."""

    def __init__(self, window_s: float = 0.01, max_batch: int = 128):
        self.window_s = window_s
        self.max_batch = max_batch
        self._lock = threading.Lock()
        self._queues: Dict[int, _PackQueue] = {}
        self._closed = False
        self.batches_executed = 0
        self.queries_executed = 0

    def close(self) -> None:
        with self._lock:
            self._closed = True
            queues = list(self._queues.values())
            self._queues.clear()
        for q in queues:
            q.close()

    def _retire(self, queue: _PackQueue) -> None:
        with self._lock:
            if self._queues.get(id(queue.resident)) is queue:
                del self._queues[id(queue.resident)]

    def fail_pending(self, exc: BaseException) -> int:
        """Fail every not-yet-launched query with `exc` (typed batcher
        death): the supervisor calls this before detaching a dead or
        wedged batcher so no request waits out the full batch timeout.
        Queries already taken into a launch are the watchdog's to fail."""
        with self._lock:
            queues = list(self._queues.values())
        failed = 0
        for q in queues:
            with q.cv:
                pendings, q.pendings = q.pendings, []
                q.cv.notify_all()
            for p in pendings:
                if not p.future.done():
                    p.future.set_exception(exc)
                    failed += 1
        return failed

    def fail_pack_pending(self, resident: ResidentPack,
                          exc: BaseException) -> int:
        """Fail ONE pack's not-yet-launched queries typed and retire
        its queue (group failover: the pack's home group lost a device
        — waiting queries must not launch onto, or wait out a deadline
        against, the dead chip; the caller re-routes retries to a
        surviving replica group)."""
        with self._lock:
            queue = self._queues.pop(id(resident), None)
        if queue is None:
            return 0
        with queue.cv:
            pendings, queue.pendings = queue.pendings, []
            queue.closed = True
            queue.cv.notify_all()
        failed = 0
        for p in pendings:
            if not p.future.done():
                p.future.set_exception(exc)
                failed += 1
        return failed

    def retire_pack(self, resident: ResidentPack) -> None:
        """Called when the pack cache evicts/replaces a pack: drop its
        queue NOW so the queue's strong reference can't keep the evicted
        device arrays alive past the breaker release (the worker drains
        any in-flight pendings, then exits)."""
        with self._lock:
            queue = self._queues.pop(id(resident), None)
        if queue is not None:
            queue.close()

    def submit(self, resident: ResidentPack, flat: FlatQuery,
               k: int) -> Future:
        """The entry point the serving path (and fault-injection tests)
        hook; the `_Pending` with its batch_wait decomposition marks
        rides on the returned future as `.pending`."""
        return self.submit_pending(resident, flat, k).future

    def submit_pending(self, resident: ResidentPack, flat: FlatQuery,
                       k: int) -> _Pending:
        fut: Future = Future()
        # capture on the REQUEST thread — the batch workers have no
        # request thread-local to read
        pending = _Pending(flat, k, fut, tracing.current_span(),
                           t_submit=time.perf_counter(),
                           tenant=tenancy.current_tenant())
        fut.pending = pending  # type: ignore[attr-defined]
        while True:
            with self._lock:
                if self._closed:
                    raise RuntimeError("micro-batcher is closed")
                queue = self._queues.get(id(resident))
                if queue is None:
                    queue = _PackQueue(self, resident)
                    self._queues[id(resident)] = queue
            if queue.submit(pending):
                return pending
            # raced the queue's idle retirement — loop and respawn

    def queue_depths(self) -> Dict[str, int]:
        """Instantaneous queue gauges for the profiler timeline and the
        metrics registry (lock-light: len/int reads are GIL-atomic)."""
        with self._lock:
            queues = list(self._queues.values())
        return {
            "queues": len(queues),
            "pending": sum(len(q.pendings) for q in queues),
            "inflight": sum(q.n_inflight for q in queues),
        }

    # set by the owning TpuSearchService so batches reuse the mesh the
    # pack arrays were placed with (no per-batch mesh construction)
    mesh = None
    stages: Optional[StageTimes] = None
    # launch watchdog (None = unmonitored): workers stamp a deadline on
    # every device dispatch through it
    watchdog: Optional["LaunchWatchdog"] = None
    # set by the node: TenantQuotaService supplying lane weights for
    # fair batch composition (None ⇒ equal weights)
    tenants = None

    def tenant_weight(self, tenant: str) -> float:
        quotas = self.tenants
        if quotas is None:
            return 1.0
        return quotas.weight(tenant)


@dataclasses.dataclass
class FlatQueryResult:
    """Per-query kernel result, COLUMNAR: parallel numpy arrays best-first
    (scores f32[n], pack rows int32[n], local ordinals int32[n]). The
    serving path consumes the columns directly — external ids resolve via
    one fancy-index (`resident.resolve_ids`), never per-hit Python.
    `hits` is the legacy tuple view for cold paths."""

    scores: np.ndarray
    rows: np.ndarray
    ords: np.ndarray
    total_hits: int
    max_score: Optional[float]
    resident: Optional[ResidentPack] = None  # for the fetch phase
    total_relation: str = "eq"  # "gte" when block-max pruning stopped
                                # counting (the reference's WAND behavior)
    variant: Optional[str] = None  # kernel variant that produced this
    _hits: Optional[List[Tuple[float, int, str, int, str]]] = None

    @classmethod
    def empty(cls) -> "FlatQueryResult":
        z = np.empty(0, dtype=np.int32)
        return cls(np.empty(0, dtype=np.float32), z, z, 0, None)

    def __len__(self) -> int:
        return len(self.scores)

    @property
    def hits(self) -> List[Tuple[float, int, str, int, str]]:
        """[(score, shard_num, segment_name, local_ord, doc_id)]."""
        if self._hits is None:
            r = self.resident
            if r is None or len(self.rows) == 0:
                self._hits = []
            else:
                ids = r.resolve_ids(self.rows, self.ords)
                self._hits = [
                    (float(s), *r.row_origin[row], int(o), i)
                    for s, row, o, i in zip(
                        self.scores.tolist(), self.rows.tolist(),
                        self.ords.tolist(), ids.tolist())]
        return self._hits


# block-max serving knobs: per-term impact prefix taken on device, and
# the candidate slack that absorbs approximate-order error before the
# exact host re-score. The pruned path pins every jit-signature dimension
# (T slots, window, chunk len, batch bucket, candidate k) to a handful of
# values so steady-state serving NEVER re-compiles.
#
# r5 routing (replaces r4's try-then-retry tiering, whose ~1-per-train
# validity retries each cost a full launch): the HOST knows every
# term's postings length at lowering time, so each query routes to the
# smallest FULL-POSTINGS sort width that holds ALL its terms' rows —
# phase-A run totals are then EXACT BM25 (no prefixes, no rescore, no
# validity bound, nothing to escalate). Only queries too hot for the
# widest bucket (Σ slots > max(FULL_SLOT_BUCKETS) on some shard row)
# take the prefix+rescore path at PREFIX_CAP2, escalating PREFIX_CAP3 →
# exact on validity failures. Measured at 2.6M docs: exact-at-width
# ≈ prefix-at-the-same-width minus the whole rescore phase, and the
# 23%-invalid escalation storm of prefix@16k disappears.
FULL_SLOT_BUCKETS = (16, 32, 128)   # sort widths 65k / 131k / 524k a shard row
#: the row buckets a rung launches at. Every loaded program keeps several
#: MB of HBM for its code (PERF.md section 5), so the ladder has a
#: program only where it pays: the narrow rung where a launch is tall,
#: the middle one where it is short (a taller group goes in chunks of
#: eight rows: a launch costs the device about as much as the lanes it
#: sorts), and the widest wherever its few queries fall, as before
FULL_ROW_BUCKETS = {16: (64, 128), 32: (8,), 128: (8, 64, 128)}
#: the rungs whose programs a node compiles before it serves from them
#: (`full_program_set`); the widest compiles on first use
FULL_READY_SLOTS = 32
#: what a launch costs beyond its lanes, in slot-rows of one shard row
#: (the unit of `_split_full_train`'s model: a row of the batch bucket ×
#: a slot of the rung × a shard row the device holds): fixed device time
#: (2.5 ms) and the launch thread's host time (6 ms) ÷ the device time
#: of a slot-row (11-18 µs), from the chip table of PERF.md section 5.
#: The exact kernel's ladder is split with the same constant: its own
#: table gives 4.3 ms + 4.2 ms ÷ 11-23 µs, 380-790 (same section)
FULL_LAUNCH_SLOT_ROWS = 512
PREFIX_CAP = 4096               # base prefix for ad-hoc prefix runs
PREFIX_CAP2 = 16384             # hot-tier prefix (queries over-width)
PREFIX_CAP3 = 65536             # escalation prefix
PRUNE_MAX_K = 1000
PRUNE_MAX_TERMS = 8          # > 8 query terms → exact path
_PRUNE_WINDOW = 8
#: the most tail terms (the terms that are not the pack's head terms) of a
#: query on the heads route: the heads programs' run-sum window
HEADS_MAX_TAIL_TERMS = 16
#: the heads route's ladder for the tail: rung → the row buckets it
#: launches at. The full-postings ladder's narrowest rung at its own
#: buckets, and its widest at eight rows, which every tail fits: a taller
#: bucket there pays, by `_split_full_train`'s model, only past 52 queries
#: of that rung in one train (a chunk of eight costs 8·128·2 + 512
#: slot-rows on two shard rows, a 64-row launch 64·128·2 + 512), and the
#: 32-slot rung's 8 rows would add a program for a twentieth of the
#: queries. Three programs a pack, so that the warm-up that serves a query
#: of each kind has launched every one of them
HEADS_ROW_BUCKETS = {16: (64, 128), 128: (8,)}

# device-kernel variant selection. packed_sort=True routes launches
# through the single-packed-key sort + hierarchical top-k kernels;
# choose_kernel_variant still falls back to "ref" per-launch whenever the pack/batch overflows the 16-bit packed layout
# (the setting is the ceiling, packability is the floor). Process-wide
# because the jitted kernels and their prewarmed signatures are too
# (`search.tpu_serving.kernel.packed_sort`).
KERNEL_CONFIG = {"packed_sort": True,
                 # compressed_pack=True builds RESIDENT packs in the
                 # 16-bit stream format: ~2.7× fewer
                 # HBM bytes/doc, exact scores via residual tables,
                 # device-side block-max pruning. Default ON since PR 15
                 # (two rounds of parity sweeps + the SLO harness behind
                 # it; chip_smoke.py holds its two variants to the numpy
                 # reference on the chip). Build-time:
                 # toggling only affects packs built afterwards.
                 # Incompressible packs (d_pad ≥ 2^16, non-finite
                 # impacts, > 65535 distinct impacts per term) silently
                 # stay in the raw format
                 # (`search.tpu_serving.kernel.compressed_pack`).
                 "compressed_pack": True}

#: per-(kernel, variant) launch counters → es_tpu_kernel_variant_total
KERNEL_VARIANT_COUNTS = LabeledCounters("kernel", "variant")

#: device programs dispatched, by launch path and static shape
#: (`full_s32`, `hot_c<prefix_cap>`,
#: `exact_<variant>_b<rows>_s<slots>_w<window>`: the names the programs
#: carry on a profiler trace) → es_tpu_kernel_launches_total
LAUNCH_COUNTS = LabeledCounters("path")

#: queries by the way `launch_flat_batch` sent them: the pruned groups as
#: launched (after folding), the exact kernel by the first reason that
#: holds, and `exact_escalated` for a pruned query whose validity bound
#: failed twice (counted under its pruned route before)
#: → es_tpu_kernel_route_total
ROUTES = ("pruned_full_s16", "pruned_full_s32", "pruned_full_s128",
          "pruned_hot", "heads",
          "exact_terms", "exact_min_count", "exact_k", "exact_no_impacts",
          "exact_escalated")
ROUTE_COUNTS = LabeledCounters("route")
#: posting entries under the exact launches' slots (`real`) and the
#: entries their static shape sorts, rows × slots × chunk length
#: (`padded`) → es_tpu_kernel_exact_entries_total
EXACT_ENTRY_COUNTS = LabeledCounters("kind")
#: real queries in the exact launches (`rows`) and those of them whose
#: own slot pin lies below their launch's (`rows_under`: a launch takes
#: the pin of its widest query, so they ride in lanes a launch of their
#: own pin would not sort: what `_split_exact_train` left on the table);
#: trains that held an exact query (`trains`) and the exact launches
#: dispatched for them (`launches`: `launches` ÷ `trains` is how far a
#: train was split by pin) → es_tpu_kernel_exact_pin_total
EXACT_PIN_COUNTS = LabeledCounters("kind")
#: queries the exact kernel answered (`queries`) and those of them with
#: no hit (`empty`: total 0) → es_tpu_kernel_exact_results_total
EXACT_RESULT_COUNTS = LabeledCounters("kind")
#: the same pair for the full-postings launches (`full_s<slots>`): Σ of
#: the slots' lengths, and rows × slots × chunk length × shard rows
#: dispatched → es_tpu_kernel_full_entries_total
FULL_ENTRY_COUNTS = LabeledCounters("kind")
#: full-postings launches by the builder of their fused operand: `native`
#: (`dist.build_full_operands`, one C call) or `python` (the library
#: absent, or a launch it does not take) → es_tpu_kernel_operands_total
OPERAND_COUNTS = LabeledCounters("builder")
#: launches dispatched on a mesh of more than one device, the query rows
#: they carried as dispatched and the devices they ran on (one count a
#: launch, so `devices` ÷ `launches` is the mesh's size): what a reader
#: needs to reckon the bytes of the cross-chip top-k merge. All three
#: stay 0 on one device → es_tpu_kernel_cross_chip_total
CROSS_CHIP_COUNTS = LabeledCounters("kind")
#: the heads route (`_launch_heads`): its queries, its launches, the
#: head terms those queries scored through the head block (one count a
#: query term) and the bytes of the head blocks built
#: → es_tpu_kernel_heads_total
HEADS_COUNTS = LabeledCounters("kind")
#: trains by the reason their hold ended (`_PackQueue._hold`): one count
#: a train taken → es_tpu_batcher_hold_exit_total
HOLD_EXITS = ("full", "backlog_low", "idle_window")
HOLD_EXIT_COUNTS = LabeledCounters("hold_exit")
for _label in ROUTES:
    ROUTE_COUNTS.child(_label)  # all read 0, not absent, before a query
for _label in HOLD_EXITS:
    HOLD_EXIT_COUNTS.child(_label)
for _label in ("real", "padded"):
    EXACT_ENTRY_COUNTS.child(_label)
    FULL_ENTRY_COUNTS.child(_label)
for _label in ("launches", "rows", "devices"):
    CROSS_CHIP_COUNTS.child(_label)
for _label in ("native", "python"):
    OPERAND_COUNTS.child(_label)
for _label in ("rows", "rows_under", "trains", "launches"):
    EXACT_PIN_COUNTS.child(_label)
for _label in ("queries", "empty"):
    EXACT_RESULT_COUNTS.child(_label)
for _label in ("queries", "launches", "terms", "block_bytes"):
    HEADS_COUNTS.child(_label)


def _count_cross_chip(mesh, rows: int) -> None:
    """One launch of `rows` query rows dispatched on `mesh`."""
    n_devices = int(mesh.devices.size)
    if n_devices > 1:
        CROSS_CHIP_COUNTS.inc("launches")
        CROSS_CHIP_COUNTS.inc("rows", n=rows)
        CROSS_CHIP_COUNTS.inc("devices", n=n_devices)


def _freeze_first_launch(resident: ResidentPack, *program: Any) -> None:
    """Called when a launch has returned: where it was the first of
    `program` on this pack, it has just been compiled (by `jax.jit`, or
    ahead of time with the rest of `full_program_set`), and what a
    compilation leaves (jaxprs, executables, cache entries) lives as
    long as the pack does: out of the collector's sight."""
    if program not in resident.launched:
        resident.launched.add(program)
        tracing.HEAP.freeze()


def _choose_exact_variant(resident: ResidentPack, batch) -> str:
    """Lowering-time variant pick for one exact-kernel launch (the
    planner owns the decision rule; this just feeds it the pack's doc
    axis and the prepared batch's slot weights)."""
    from elasticsearch_tpu.search.planner import choose_kernel_variant
    return choose_kernel_variant(resident.pack.d_pad, batch.weights,
                                 enabled=KERNEL_CONFIG["packed_sort"],
                                 compressed=resident.comp_streams
                                 is not None)


def _pruned_variant() -> str:
    """Under variant="packed" the pruned kernel always takes the
    hierarchical top-k half (unconditionally safe); whether a launch
    ALSO packs (gid, impact code) into one sort key is a separate
    per-launch gate (pack_keys in _launch_pruned: the group's gid range
    must fit 16 bits and the batch weights must be packable)."""
    return "packed" if KERNEL_CONFIG["packed_sort"] else "ref"


def _prune_t_slots(prefix_cap: int) -> int:
    from elasticsearch_tpu.parallel.distributed import CHUNK_CAP
    return PRUNE_MAX_TERMS * max(1, prefix_cap // CHUNK_CAP)


def _candidate_k(k: int) -> int:
    """Static candidate-count buckets (k + slack, few jit signatures)."""
    return 128 if k <= 64 else 2048


def _serving_bucket(n: int, cap: int = 128) -> int:
    """Three batch buckets (8 / 64 / 128) — trains launch at whatever
    fill the host managed, so the mid bucket avoids ~2x padding when
    GIL-bound clients can't refill to 128 in one device cycle; every
    bucket×width×k signature is prewarmed."""
    if n <= 8:
        return 8
    if n <= 64:
        return 64
    if n <= cap:
        return cap
    return _batch_bucket(n, 1024)


def _serving_buckets(max_batch: int = 128) -> List[int]:
    """Every bucket `_serving_bucket` gives a train of up to `max_batch`
    queries: the batch rows its launches compile for."""
    return sorted({_serving_bucket(n) for n in (1, 9, 65, max_batch)
                   if n <= max_batch})


def _slots_needed(resident: ResidentPack, flat: FlatQuery) -> int:
    return _slots_of(resident, tuple(flat.terms))


def _slots_of(resident: ResidentPack, terms: Tuple[str, ...]) -> int:
    """Max over shard rows of Σ_terms ceil(row_len/CHUNK): the slot
    count a FULL-postings sorted-merge of this query needs. Terms
    MISSING from a row still cost one (zero-length) slot — plan_slots
    keeps them for msm semantics, so the routed width must count them
    or the prepared batch lands on an unprewarmed jit signature.

    Memoized per pack by terms tuple: the scan walks EVERY shard row's
    vocab, which at many segments is the costliest host step per query
    — and repeated query shapes hit the same terms constantly."""
    cached = resident.slots_memo.get(terms)
    if cached is not None:
        return cached
    pack = resident.pack
    worst = 0
    for si in range(len(pack.vocabs)):
        vocab = pack.vocabs[si]
        rstart = pack.row_starts[si]
        n = 0
        for t in terms:
            r = vocab.get(t)
            if r is None:
                n += 1  # zero-length slot
                continue
            ln = int(rstart[r + 1] - rstart[r])
            n += max(1, (ln + dist.CHUNK_CAP - 1) // dist.CHUNK_CAP)
        worst = max(worst, n)
    result = max(worst, 1)
    if len(resident.slots_memo) < 65536:  # bound pathological cardinality
        resident.slots_memo[terms] = result
    return result


def _full_bucket(slots: int) -> Optional[int]:
    for b in FULL_SLOT_BUCKETS:
        if slots <= b:
            return b
    return None


def _group_launches(n: int, slots: int, shard_rows: int,
                    row_buckets: Sequence[int]) -> Tuple[int, List[int]]:
    """`n` queries at one rung → (modelled cost, the rows of each launch):
    chunks of one of the rung's `row_buckets`, whichever costs least by
    Σ launches (rows × slots × `shard_rows` + FULL_LAUNCH_SLOT_ROWS)."""
    return min(
        (chunks * (rows * slots * shard_rows + FULL_LAUNCH_SLOT_ROWS),
         [rows] * chunks)
        for rows in row_buckets
        for chunks in (-(-n // rows),))


def _split_full_train(groups: Dict[int, List[int]], shard_rows: int = 1,
                      ladder: Mapping[int, Sequence[int]] = FULL_ROW_BUCKETS
                      ) -> List[Tuple[int, List[int]]]:
    """A train's queries of one launch path, grouped by the narrowest
    rung of `ladder` that holds each → the launches, as (slots, queries).
    `ladder` gives the rungs the split may launch at and the row buckets
    each has programs at: the full-postings ladder whole
    (FULL_ROW_BUCKETS, the default), or the exact kernel's slot pins
    that this train holds (`_split_exact_train`). A group launches at
    its own rung or rides at a wider one (always correct: wider holds
    everything), and a rung's queries go in chunks of one of its row
    buckets (`_group_launches`); of the few such splits, the one that
    costs least by the model: a launch takes the device about as long
    as the lanes it sorts on the shard rows a device holds, whatever it
    carries, and a constant besides, FULL_LAUNCH_SLOT_ROWS on either
    ladder (PERF.md section 5). The first of equals keeps a group at its
    own rung."""
    rungs = sorted(ladder)
    best_cost, best = math.inf, []
    for ride in itertools.product(*(rungs[i:] for i in range(len(rungs)))):
        merged: Dict[int, List[int]] = {}
        for own, at in zip(rungs, ride):
            if groups.get(own):
                merged[at] = merged.get(at, []) + groups[own]
        plans = [(b, idxs, *_group_launches(len(idxs), b, shard_rows,
                                            ladder[b]))
                 for b, idxs in sorted(merged.items())]
        cost = sum(plan[2] for plan in plans)
        if cost < best_cost:
            best_cost, best = cost, plans
    launches = []
    for b, idxs, _cost, chunks in best:
        at = 0
        for rows in chunks:
            launches.append((b, idxs[at:at + rows]))
            at += rows
    return launches


# ---------------------------------------------------------------------------
# the full-postings ladder's programs: a closed set, compiled before use
# ---------------------------------------------------------------------------
# A train's split depends on the needs of the queries it happens to hold,
# so a rung × row bucket that no earlier train met can be the next one's:
# a compile there would stall a train under load (seconds on the chip,
# a cache replay included). The first full-path launch on a pack at a k
# bucket therefore compiles every program of the rungs up to
# FULL_READY_SLOTS ahead of time and keeps the executables, which is what
# the launches call: nothing is executed to make them, and `jax.jit`'s
# own cache, which would compile on a first call, is not on the path.

@dataclasses.dataclass(frozen=True)
class FullProgram:
    """One compiled signature of the full-postings program on a pack."""

    rows: int
    slots: int
    k_out: int
    variant: str

    @property
    def label(self) -> str:
        return f"full_s{self.slots}_b{self.rows}"


def _pruned_k_out(k: int) -> int:
    return 128 if _candidate_k(k) == 128 else 1024


def full_program_set(resident: ResidentPack, k: int, max_batch: int = 128,
                     variant: Optional[str] = None) -> List[FullProgram]:
    """Every program of the rungs up to FULL_READY_SLOTS that
    `launch_flat_batch` can dispatch on this pack at `k` for trains of up
    to `max_batch` queries: a function of the pack (one that has the
    pruned path at all), `k` and the node's constants alone."""
    if resident.imp_device_arrays is None:
        return []
    reach = _serving_buckets(max_batch)
    return [FullProgram(rows, slots, _pruned_k_out(k),
                        variant or _pruned_variant())
            for slots in FULL_SLOT_BUCKETS if slots <= FULL_READY_SLOTS
            for rows in FULL_ROW_BUCKETS[slots] if rows in reach]


def _make_full_search(resident: ResidentPack, mesh, slots: int, k_out: int,
                      variant: str):
    pack = resident.pack
    return dist.make_pruned_search(
        mesh, max_len=dist.CHUNK_CAP, d_pad=pack.d_pad, p_pad=pack.p_pad,
        c_cand=k_out, k_out=k_out, t_window=_PRUNE_WINDOW,
        t_terms=PRUNE_MAX_TERMS, with_rescore=False, variant=variant,
        pack_keys=False, name=f"full_s{slots}")


_FULL_READY_LOCK = threading.Lock()


def _ready_full_programs(resident: ResidentPack, mesh, k: int,
                         max_batch: int, variant: str
                         ) -> Dict[Tuple[int, int], Any]:
    """(slots, rows) → the executable, for every member of
    `full_program_set`: compiled at the first call for (pack, mesh, k
    bucket, variant), on as many threads as there are members (XLA
    compiles with the GIL released), remembered with the pack after."""
    key = (mesh, _pruned_k_out(k), variant, max_batch)
    with _FULL_READY_LOCK:
        ready = resident.full_ready.get(key)
        if ready is not None:
            return ready
        programs = full_program_set(resident, k, max_batch, variant)
        arrays = resident.imp_device_arrays + tuple(resident.device_arrays)
        fns = {p.slots: _make_full_search(resident, mesh, p.slots, p.k_out,
                                          variant) for p in programs}

        def compile_one(program: FullProgram):
            return dist.compile_pruned_program(
                fns[program.slots], mesh, arrays, program.rows,
                3 * program.slots + 3 * PRUNE_MAX_TERMS + 1)

        with ThreadPoolExecutor(max_workers=len(programs),
                                thread_name_prefix="tpu-full-ready") as pool:
            ready = resident.full_ready[key] = dict(zip(
                ((p.slots, p.rows) for p in programs),
                pool.map(compile_one, programs)))
        return ready


# ---------------------------------------------------------------------------
# the heads route: a pack's head terms scored as one dense block
# ---------------------------------------------------------------------------
# A term whose postings span a good share of a shard row's docs costs the
# sorted merge more lanes than the row has docs (a stop-word of MS MARCO:
# 134 slots of 4,096 on a 552,960-doc row). Such terms, the pack's head
# terms (`dist.head_terms`, read from the pack), are scored by a matmul
# over a dense block of their impacts, and the query's other terms, its
# tail, go through the full-postings merge at the rung of the ladder that
# holds them (`dist.make_heads_search`). A query of min_count 1 that holds
# a head term takes this route whatever its number of terms; a pack with
# no head term, and a query without one, route as before.

@dataclasses.dataclass(frozen=True)
class HeadBlock:
    """A pack's head block on the device (`dist.build_head_block`)."""

    terms: Tuple[str, ...]
    h_pad: int        # block rows: the head terms, padded
    width: int        # docs a shard row
    array: Any        # float32[shard rows, h_pad, width], sharded as the pack
    nbytes: int


@dataclasses.dataclass(frozen=True)
class HeadsProgram:
    """One compiled signature of the heads program on a pack."""

    rows: int
    slots: int
    k_out: int

    @property
    def label(self) -> str:
        return dist.heads_program_name(self.rows, self.slots)


def heads_program_set(resident: ResidentPack, k: int,
                      max_batch: int = 128) -> List[HeadsProgram]:
    """Every heads program `launch_flat_batch` can dispatch on this pack
    at `k` for trains of up to `max_batch` queries: HEADS_ROW_BUCKETS'
    rungs, for the tail, at their row buckets. Empty where the pack has
    no head term or no full-postings path."""
    pack = resident.pack
    if (resident.imp_device_arrays is None
            or not pack.term_table.head_index(pack)):
        return []
    reach = _serving_buckets(max_batch)
    return [HeadsProgram(rows, slots, _pruned_k_out(k))
            for slots, buckets in sorted(HEADS_ROW_BUCKETS.items())
            for rows in buckets if rows in reach]


def _heads_plan(resident: ResidentPack, flat: FlatQuery
                ) -> Optional[Tuple[int, Tuple[str, ...]]]:
    """(the tail's rung, the tail's terms) where `flat` takes the heads
    route on this pack: it holds a head term, its tail has at most
    HEADS_MAX_TAIL_TERMS terms and fits a rung of HEADS_ROW_BUCKETS (an
    empty tail fits the narrowest); else None. Memoised with the pack."""
    memo_key = tuple(flat.terms)
    if memo_key in resident.heads_memo:
        return resident.heads_memo[memo_key]
    pack = resident.pack
    heads = pack.term_table.head_index(pack)
    plan = None
    if any(t in heads for t in flat.terms):
        tail = tuple(t for t in flat.terms if t not in heads)
        need = _slots_of(resident, tail)
        rung = next((b for b in sorted(HEADS_ROW_BUCKETS) if need <= b), None)
        if rung is not None and len(tail) <= HEADS_MAX_TAIL_TERMS:
            plan = (rung, tail)
    if len(resident.heads_memo) < 65536:
        resident.heads_memo[memo_key] = plan
    return plan


def _head_block(resident: ResidentPack, mesh,
                stages: Optional[StageTimes]) -> HeadBlock:
    """The pack's head block, built at the first call (the stage
    `heads_block` of `stages`, under the pack's `heads_lock`) from the
    pack's host postings and placed as its postings are; its bytes are
    charged to the `hbm` breaker with the pack's (`IndexPackCache.charge`)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    with resident.heads_lock:
        if resident.heads_block is None:
            pack = resident.pack
            terms = tuple(pack.term_table.head_index(pack))
            with tracing.stage(stages, "heads_block", annotate=False,
                               cpu=False):
                host = dist.build_head_block(pack, terms)
                label = f"heads[{pack.field}]"
                if resident.cache is not None:
                    resident.cache.charge(resident, host.nbytes, label)
                try:
                    array = jax.device_put(host, NamedSharding(
                        mesh, P(SHARD_AXIS, None, None)))
                except Exception:
                    if resident.cache is not None:
                        resident.cache.charge(resident, -host.nbytes, label)
                    raise
            _, h_pad, width = host.shape
            resident.heads_block = HeadBlock(terms, h_pad, width, array,
                                             host.nbytes)
            resident.hbm_detail = {**resident.hbm_detail, "heads_block": {
                "terms": len(terms), "rows": h_pad,
                "shard_rows": pack.num_shards, "docs_per_row": width,
                "bytes": host.nbytes}}
            HEADS_COUNTS.inc("block_bytes", n=host.nbytes)
        return resident.heads_block


#: heads programs compiled at once after the first (`_ready_heads_programs`)
HEADS_READY_WORKERS = 2


def _ready_heads_programs(resident: ResidentPack, mesh, k: int,
                          max_batch: int, first: HeadsProgram
                          ) -> Dict[Tuple[int, int], Future]:
    """(tail slots, rows) → the future executable of each member of
    `heads_program_set`: all submitted at the first call for (pack, mesh,
    k bucket), which comes with the head block, and compiled ahead of
    time on threads that are not the launch thread: `first` (the calling
    launch's) alone, then the others, HEADS_READY_WORKERS at a time. A
    launch waits for its own program alone, so that the first one waits
    for one compile and not for the set, well inside the batch timeout.
    Remembered with the pack."""
    key = (mesh, _pruned_k_out(k), max_batch)
    with _FULL_READY_LOCK:
        ready = resident.heads_ready.get(key)
        if ready is not None:
            return ready
        block = resident.heads_block
        arrays = tuple(resident.imp_device_arrays) + (block.array,)

        def compile_one(program: HeadsProgram, after: Optional[Future]):
            if after is not None:
                after.result()
            return dist.compile_heads_program(
                _make_heads_search(resident, mesh, program), mesh, arrays,
                program.rows, program.slots, block.h_pad)

        def log_failure(fut: Future) -> None:
            if fut.exception() is not None:
                logger.warning("heads program compile failed: %s",
                               fut.exception())

        pool = ThreadPoolExecutor(max_workers=HEADS_READY_WORKERS,
                                  thread_name_prefix="tpu-heads-ready")
        lead = pool.submit(compile_one, first, None)
        ready = resident.heads_ready[key] = {
            (p.slots, p.rows): (lead if p == first
                                else pool.submit(compile_one, p, lead))
            for p in heads_program_set(resident, k, max_batch)}
        for fut in ready.values():
            fut.add_done_callback(log_failure)
        pool.shutdown(wait=False)
        return ready


def _make_heads_search(resident: ResidentPack, mesh, program: HeadsProgram):
    pack = resident.pack
    return dist.make_heads_search(
        mesh, d_pad=pack.d_pad, p_pad=pack.p_pad, k_out=program.k_out,
        t_window=HEADS_MAX_TAIL_TERMS, name=program.label)


# ---------------------------------------------------------------------------
# the exact kernel's programs: a closed set
# ---------------------------------------------------------------------------
# Every static dimension of an exact launch is pinned to a member of a
# small ladder, so the programs a pack can meet are few and can be listed
# before any query arrives (`exact_program_set`): batch rows by
# `_serving_bucket`, slots a row by `_exact_slot_pin`, the run-sum window
# by `_exact_window`, the kernel's k by `_exact_k_kernel`, the chunk
# length at CHUNK_CAP. `_launch_exact` pins with the same three functions
# that the listing is made of.
EXACT_MIN_SLOTS = 8
#: where the ladder starts under a query of more than PRUNE_MAX_TERMS terms
EXACT_LONG_MIN_SLOTS = 32
#: the listing covers queries of up to this many terms
EXACT_MAX_TERMS = 16


def _exact_window(window: int) -> int:
    """The run-sum window an exact launch compiles for: the next power
    of two from _PRUNE_WINDOW that holds the widest query's terms.
    `segmented_run_sum` doubles its step while it is below the window,
    so every window in (w/2, w] runs the very same steps: the pin costs
    nothing and changes no bit of the result."""
    return dist._shape_bucket(window, _PRUNE_WINDOW)


def _exact_slot_pin(t_slots: int, t_window: int) -> int:
    """The slots a row an exact launch compiles for: powers of two from
    EXACT_MIN_SLOTS; a launch that holds a query of more than
    PRUNE_MAX_TERMS terms (window past _PRUNE_WINDOW) starts at
    EXACT_LONG_MIN_SLOTS instead, so that such queries meet one slot
    count where their terms would give two."""
    return dist._shape_bucket(
        t_slots, EXACT_MIN_SLOTS if t_window <= _PRUNE_WINDOW
        else EXACT_LONG_MIN_SLOTS)


def _rows_under_pin(lengths: np.ndarray, terms: Sequence[Sequence[str]],
                    t_pin: int) -> int:
    """How many of an exact launch's queries would launch narrower
    alone: those whose own `_exact_slot_pin` lies below `t_pin`, the pin
    of the launch's widest. `lengths` [shard rows, rows, slots] is the
    batch's; its first `len(terms)` rows are the queries. A query needs
    the slots that hold postings on its heaviest shard row, and one a
    term at the least (a term a row lacks keeps a slot without postings).
    Every pin is a floor times a power of two, so a pin is below
    `t_pin` exactly where need and floor fit `t_pin` / 2."""
    n_terms = np.fromiter(map(len, terms), np.int64, len(terms))
    need = np.maximum(
        np.count_nonzero(lengths[:, :len(terms)], axis=2).max(axis=0),
        n_terms)
    floor = np.where(n_terms > _PRUNE_WINDOW, EXACT_LONG_MIN_SLOTS,
                     EXACT_MIN_SLOTS)
    return int(np.count_nonzero(2 * np.maximum(need, floor) <= t_pin))


def _exact_k_kernel(k: int) -> int:
    return 128 if k <= 128 else (1024 if k <= 1024
                                 else _batch_bucket(k, 16384))


def _exact_variants(resident: ResidentPack) -> Tuple[str, ...]:
    """The exact kernel's variants a launch on this pack can pick
    (`_choose_exact_variant` per launch: the setting is the ceiling,
    the batch's weights decide between the two of a pair)."""
    if resident.comp_streams is not None:
        return ("compressed", "compressed_exact")
    if KERNEL_CONFIG["packed_sort"] and sparse.packable(resident.pack.d_pad):
        return ("packed", "ref")
    return ("ref",)


@dataclasses.dataclass(frozen=True)
class ExactProgram:
    """One compiled signature of the exact kernel on a pack."""

    rows: int
    slots: int
    t_window: int
    with_counts: bool
    k_kernel: int
    variant: str

    @property
    def label(self) -> str:
        return dist.exact_program_name(self.variant, self.rows, self.slots,
                                       self.t_window)


def _widest_slots(resident: ResidentPack, n_terms: int) -> int:
    """The most slots a query of `n_terms` distinct terms can need on
    this pack: the `n_terms` longest postings rows of a shard row, each
    in chunks of CHUNK_CAP (`_slots_needed` of the worst such query).
    Memoized with the pack, like `_slots_needed`: `/_tpu/stats` asks on
    every call."""
    cached = resident.widest_slots_memo.get(n_terms)
    if cached is not None:
        return cached
    worst = n_terms
    for rstart in resident.pack.row_starts:
        lens = np.diff(np.asarray(rstart))
        if lens.size == 0:
            continue
        slots = np.maximum(1, -(-lens // dist.CHUNK_CAP))
        top = min(n_terms, slots.size)
        worst = max(worst, int(np.partition(slots, slots.size - top)[-top:].sum())
                    + (n_terms - top))
    resident.widest_slots_memo[n_terms] = worst
    return worst


def exact_program_set(resident: ResidentPack, k: int, max_batch: int = 128,
                      max_terms: int = EXACT_MAX_TERMS,
                      max_slots: Optional[int] = None
                      ) -> List[ExactProgram]:
    """Every program `_launch_exact` can dispatch on this pack at `k`
    for trains of up to `max_batch` queries of up to `max_terms` terms
    (and, with `max_slots`, of at most so many slots): a function of the
    pack, `k` and the node's constants alone. Serving compiles nothing
    outside it; `prewarm` compiles members of it."""
    rows_set = _serving_buckets(max_batch)
    k_kernel = _exact_k_kernel(k)
    variants = _exact_variants(resident)
    by_window: Dict[int, int] = {}   # window pin → the most terms under it
    for n_terms in range(1, max_terms + 1):
        by_window[_exact_window(n_terms)] = n_terms
    out: List[ExactProgram] = []
    fewest = 1
    for t_window, most in sorted(by_window.items()):
        widest = _widest_slots(resident, most)
        if max_slots is not None:
            widest = max(fewest, min(widest, max_slots))
        pin = _exact_slot_pin(fewest, t_window)
        last = _exact_slot_pin(widest, t_window)
        while pin <= last:
            out.extend(ExactProgram(rows, pin, t_window, with_counts,
                                    k_kernel, variant)
                       for rows in rows_set
                       for with_counts in (False, True)
                       for variant in variants)
            pin *= 2
        fewest = most + 1
    return out


def _exact_reason(flat: FlatQuery, k: int, can_prune: bool) -> Optional[str]:
    """Why a query goes to the exact kernel (its ROUTE_COUNTS label, the
    first that holds), or None for the pruned path."""
    if flat.min_count != 1:
        return "exact_min_count"
    if k > PRUNE_MAX_K:
        return "exact_k"
    if len(flat.terms) > PRUNE_MAX_TERMS:
        return "exact_terms"
    if not can_prune:
        return "exact_no_impacts"
    return None


def _split_exact_train(resident: ResidentPack, flats: Sequence[FlatQuery],
                       exact_idx: Sequence[int], shard_rows: int,
                       max_batch: int = 128) -> List[List[int]]:
    """A train's exact queries → the queries of each exact launch. A
    query's own pin is the one it would launch at alone
    (`_exact_slot_pin` of the slots it needs, `_slots_needed`, under the
    window of its terms); the train is split as the full-postings path
    splits its own (`_split_full_train`), over the pins it holds at the
    row buckets of `_serving_bucket`: a narrow query rides at a wider
    pin only where that launch is made anyway and the lanes it adds
    cost less than a launch of its own. `_launch_exact` pins each launch
    by its widest query, so a launch is a member of `exact_program_set`
    whatever the split."""
    groups: Dict[int, List[int]] = {}
    for i in exact_idx:
        pin = _exact_slot_pin(_slots_needed(resident, flats[i]),
                              _exact_window(len(flats[i].terms)))
        groups.setdefault(pin, []).append(i)
    row_buckets = _serving_buckets(max_batch)
    return [idxs for _pin, idxs in _split_full_train(
        groups, shard_rows, {pin: row_buckets for pin in groups})]


def launch_flat_batch(resident: ResidentPack, flats: Sequence[FlatQuery],
                      k: int, mesh=None,
                      stages: Optional[StageTimes] = None,
                      max_batch: int = 128) -> Dict[str, Any]:
    """Phase 1 of a micro-batch: host prep + ASYNC kernel dispatch for
    the tier-E pruned subset (rescore-free), the tier-H pruned subset,
    and the exact subset (msm/AND, big k, many terms). Each subset but
    the tier-H one goes as several launches: the full-postings queries
    split over the rungs of their ladder, the exact ones by slot pin
    (`_split_full_train`, `_split_exact_train`). Returns an
    opaque launch state for finish_flat_batch. JAX dispatch is
    asynchronous, so the caller can launch batch N+1 while batch N
    executes on device (double-buffered serving).
    On a batcher's launch thread the time spent here is its states
    `prep`, then `lock`/`put`/`call` around each program's dispatch.
    `max_batch`: the most queries a train of this caller holds (it
    bounds the programs made ready: `full_program_set`)."""
    tracing.current_states().switch("prep", queries=len(flats))
    if mesh is None:
        mesh = make_mesh(shape=(1, _n_local_devices()))
    # fault seam: DeviceWedge blocks here — BEFORE any lock or device
    # work — so a "wedged" launch holds nothing the watchdog needs
    _dispatch_fault_point(mesh)
    can_prune = resident.imp_device_arrays is not None
    # the routing, on the launch thread's clock (`prep.route`, inside
    # `batcher.prep`): a path for each query, then each path's launches
    with tracing.stage(stages, "prep.route"):
        pruned_idx: List[int] = []
        exact_idx: List[int] = []
        heads_groups: Dict[int, List[int]] = {b: []
                                              for b in HEADS_ROW_BUCKETS}
        tails: Dict[int, Tuple[str, ...]] = {}
        for i, f in enumerate(flats):
            plan = (_heads_plan(resident, f)
                    if can_prune and f.min_count == 1 and k <= PRUNE_MAX_K
                    else None)
            if plan is not None:
                heads_groups[plan[0]].append(i)
                tails[i] = plan[1]
                continue
            reason = _exact_reason(f, k, can_prune)
            if reason is None:
                pruned_idx.append(i)
            else:
                exact_idx.append(i)
                ROUTE_COUNTS.inc(reason)
        # route each query to the smallest exact-sort width that holds
        # its FULL postings; overflow goes to the prefix+rescore path
        full_groups: Dict[int, List[int]] = {b: []
                                             for b in FULL_SLOT_BUCKETS}
        hot_idx: List[int] = []
        for i in pruned_idx:
            b = _full_bucket(_slots_needed(resident, flats[i]))
            if b is None:
                hot_idx.append(i)
            else:
                full_groups[b].append(i)
        shard_rows = max(1,
                         resident.pack.num_shards // mesh.shape[SHARD_AXIS])
        full_split = _split_full_train(full_groups, shard_rows)
        heads_split = (_split_full_train(heads_groups, shard_rows,
                                         HEADS_ROW_BUCKETS)
                       if tails else [])
        exact_split = (_split_exact_train(resident, flats, exact_idx,
                                          shard_rows, max_batch)
                       if exact_idx else [])
    full_launches = []
    for b, idxs in full_split:
        ROUTE_COUNTS.inc(f"pruned_full_s{b}", n=len(idxs))
        full_launches.append((idxs, _launch_pruned(
            resident, [flats[i] for i in idxs], k, mesh,
            stages=stages, full_slots=b, max_batch=max_batch)))
    # the heads launches answer as the full-postings ones do (exact, the
    # same output), so they finish with them
    for b, idxs in heads_split:
        ROUTE_COUNTS.inc("heads", n=len(idxs))
        full_launches.append((idxs, _launch_heads(
            resident, [flats[i] for i in idxs], [tails[i] for i in idxs],
            k, mesh, b, stages=stages, max_batch=max_batch)))
    st: Dict[str, Any] = {"resident": resident, "flats": flats, "k": k,
                          "mesh": mesh, "stages": stages,
                          "full_launches": full_launches,
                          "hot_idx": hot_idx, "exact_launches": []}
    if hot_idx:
        ROUTE_COUNTS.inc("pruned_hot", n=len(hot_idx))
        st["hot_launch"] = _launch_pruned(
            resident, [flats[i] for i in hot_idx], k, mesh,
            prefix_cap=PREFIX_CAP2, stages=stages)
    if exact_idx:
        st["exact_launches"] = [
            (idxs, _launch_exact(resident, [flats[i] for i in idxs], k, mesh,
                                 stages=stages))
            for idxs in exact_split]
        EXACT_PIN_COUNTS.inc("trains")
        EXACT_PIN_COUNTS.inc("launches", n=len(st["exact_launches"]))
    return st


def finish_flat_batch(st: Dict[str, Any]) -> List[FlatQueryResult]:
    """Phase 2: materialize device results, launch by launch, each
    launch's answers to its queries' places in the train; residual tier-H
    validity failures escalate to the deeper PREFIX_CAP3 prefix, then
    exact.
    On a batcher's completer thread the time spent here is its states
    `device_wait` and `decode`, once per program (an escalation launches
    from this thread, so its `prep`/`lock`/`put`/`call` are the
    completer's too)."""
    resident, flats, k, mesh, stages = (st["resident"], st["flats"],
                                        st["k"], st["mesh"], st["stages"])
    out: List[Optional[FlatQueryResult]] = [None] * len(flats)
    # each query's launch's `device_wait` end (an escalated query keeps
    # its first launch's): the completer stamps it on the query's
    # `_Pending` as `t_ready`
    ready = st["t_ready"] = [0.0] * len(flats)
    tier3_idx: List[int] = []
    escalate: List[int] = []
    for idxs, launch in st["full_launches"]:
        results, invalid = _finish_pruned(launch, stages=stages)
        t_ready = launch["t_ready"]
        for j, i in enumerate(idxs):
            out[i] = results[j]
            ready[i] = t_ready
        # full-postings runs are exact ⇒ beta 0 ⇒ no invalids; if the
        # invariant ever breaks, escalate rather than crash serving
        escalate.extend(idxs[j] for j in invalid)
    if st["hot_idx"]:
        hot_idx = st["hot_idx"]
        results, invalid = _finish_pruned(st["hot_launch"],
                                          stages=stages)
        t_ready = st["hot_launch"]["t_ready"]
        for j, i in enumerate(hot_idx):
            out[i] = results[j]
            ready[i] = t_ready
        escalate.extend(hot_idx[j] for j in invalid)
    if escalate:
        retry_idx = escalate
        if stages is not None:
            stages.add("pruned_invalid_t2", 0.0, n=len(retry_idx))
        results2, invalid2 = _execute_pruned(
            resident, [flats[i] for i in retry_idx], k, mesh,
            stages=stages, prefix_cap=PREFIX_CAP3)
        for j, i in enumerate(retry_idx):
            out[i] = results2[j]
        tier3_idx = [retry_idx[j] for j in invalid2]
    for idxs, launch in st["exact_launches"]:
        results = _finish_exact(launch, stages=stages)
        t_ready = launch["t_ready"]
        for j, i in enumerate(idxs):
            out[i] = results[j]
            ready[i] = t_ready
    if tier3_idx:
        ROUTE_COUNTS.inc("exact_escalated", n=len(tier3_idx))
        results = _execute_exact(resident,
                                 [flats[i] for i in tier3_idx], k, mesh,
                                 stages=stages)
        for j, i in enumerate(tier3_idx):
            out[i] = results[j]
    return out  # type: ignore[return-value]


def execute_flat_batch(resident: ResidentPack, flats: Sequence[FlatQuery],
                       k: int, mesh=None,
                       stages: Optional[StageTimes] = None
                       ) -> List[FlatQueryResult]:
    """Run one micro-batch synchronously. OR-queries (min_count == 1,
    k ≤ 1000) go through the block-max pruned pipeline (tier E or H by
    per-term df); msm/AND queries and pruned queries whose validity
    bound fails escalate (64k prefix, then exact kernel)."""
    return finish_flat_batch(launch_flat_batch(resident, flats, k, mesh,
                                               stages=stages))


def _columnar_results(resident: ResidentPack, vals: np.ndarray,
                      gids: np.ndarray, totals: np.ndarray,
                      n_queries: int, relation_fn,
                      k_cap: Optional[int] = None,
                      variant: Optional[str] = None
                      ) -> List[FlatQueryResult]:
    """Decode a whole batch's [B, k'] kernel output into columnar results
    with vectorized numpy — the only per-query work is slicing views.
    Sentinel lanes (score -inf / ordinal == d_pad / padding rows) are
    dropped; they always sort to the tail, so each query's valid hits are
    a prefix."""
    pack = resident.pack
    d1 = pack.d_pad + 1
    rows = (gids // d1).astype(np.int32)
    ords = (gids - rows.astype(np.int64) * d1).astype(np.int32)
    valid = ((vals > dist.NEG_INF) & (ords < pack.d_pad)
             & (rows < len(resident.row_origin)))
    # prefix lengths (guard against non-prefix validity: stop at first 0)
    n_valid = np.where(valid.all(axis=1), valid.shape[1],
                       valid.argmin(axis=1))
    out = []
    for qi in range(n_queries):
        m = int(n_valid[qi])
        if k_cap is not None and m > k_cap:
            m = k_cap
        sc = vals[qi, :m]
        out.append(FlatQueryResult(
            sc, rows[qi, :m], ords[qi, :m], int(totals[qi]),
            float(sc[0]) if m else None, resident=resident,
            total_relation=relation_fn(qi), variant=variant))
    return out


def _launch_exact(resident: ResidentPack, flats: Sequence[FlatQuery],
                  k: int, mesh,
                  stages: Optional[StageTimes] = None,
                  variant: Optional[str] = None,
                  program: Optional[ExactProgram] = None) -> Dict[str, Any]:
    """Full-postings kernel, async dispatch: exact scores, exact totals
    (tier 1 for msm/AND, big k and queries of more than PRUNE_MAX_TERMS
    terms; tier 3 for OR queries whose validity bounds failed twice).
    Every jit dimension is pinned to a member of a ladder: batch rows
    (`_serving_bucket`: 8/64/128), kernel k (`_exact_k_kernel`:
    128/1024/pow2), slots a row (`_exact_slot_pin`: powers of two from
    8, from 32 under long queries), run-sum window (`_exact_window`:
    powers of two from 8), chunk length (CHUNK_CAP). So the programs a
    pack can meet are the members of `exact_program_set`, each compiled
    once ever and kept by the compilation cache. A launch takes the pin
    of its widest query, so `launch_flat_batch` hands a train's exact
    queries over in groups of like pins (`_split_exact_train`), a launch
    a group; an escalation, prewarm and the tests hand theirs over
    whole. `program` (prewarm, the tests) raises the pins to that
    member's."""
    t_prep = time.perf_counter()
    states = tracing.current_states()
    states.switch("prep", queries=len(flats))
    pack = resident.pack
    rows = _serving_bucket(len(flats))
    if program is not None:
        rows, variant = max(rows, program.rows), program.variant
    terms = [f.terms for f in flats]
    # the query operands, pinned (`prep.query_batch`, inside `batcher.prep`)
    with tracing.stage(stages, "prep.query_batch"):
        batch = dist.prepare_query_batch(
            pack, terms,
            boosts=[f.boost for f in flats],
            min_counts=[f.min_count for f in flats],
            pad_batch_to=rows,
            pad_max_len=dist.CHUNK_CAP,
            compressed=resident.comp_streams)
        t_window = _exact_window(batch.window)
        t_pin = _exact_slot_pin(batch.t_slots, t_window)
        if program is not None:
            t_window = max(t_window, program.t_window)
            t_pin = max(t_pin, program.slots)
        if t_pin > batch.t_slots:
            s, b, t = batch.starts.shape
            pad = ((0, 0), (0, 0), (0, t_pin - t))
            extra = {}
            if batch.res_starts is not None:
                # zero-padded slots: length 0 ⇒ inert in grouping/rescore
                extra = dict(res_starts=np.pad(batch.res_starts, pad),
                             res_lens=np.pad(batch.res_lens, pad),
                             slot_terms=np.pad(batch.slot_terms, pad))
            batch = dataclasses.replace(
                batch, starts=np.pad(batch.starts, pad),
                lengths=np.pad(batch.lengths, pad),
                weights=np.pad(batch.weights, pad), t_slots=t_pin, **extra)
    if variant is None:
        variant = _choose_exact_variant(resident, batch)
    # the launch's static shape: the device program's name (`jit_<label>`
    # on the trace's XLA Modules line) and the label of its spans and
    # counters
    label = dist.exact_program_name(variant, rows, t_pin, t_window)
    states.note(path=label, rows=rows)
    KERNEL_VARIANT_COUNTS.inc("exact", variant)
    LAUNCH_COUNTS.inc(label)
    EXACT_ENTRY_COUNTS.inc("real", n=int(batch.lengths.sum()))
    EXACT_ENTRY_COUNTS.inc("padded", n=batch.lengths.size * batch.max_len)
    EXACT_PIN_COUNTS.inc("rows", n=len(flats))
    EXACT_PIN_COUNTS.inc("rows_under",
                         n=_rows_under_pin(batch.lengths, terms, t_pin))
    _count_cross_chip(mesh, rows)
    t_disp = time.perf_counter()
    vals, gids, totals = dist.distributed_search_raw(
        pack, batch, _exact_k_kernel(k), mesh,
        device_arrays=resident.device_arrays,
        t_window=t_window, materialize=False, variant=variant)
    _freeze_first_launch(resident, label, _exact_k_kernel(k), mesh)
    if stages is not None:
        stages.add("exact_prep", t_disp - t_prep)
        stages.add(f"exact_dispatch.{variant}",
                   time.perf_counter() - t_disp)
    return {"resident": resident, "n": len(flats), "k": k,
            "vals": vals, "gids": gids, "totals": totals,
            "variant": variant, "label": label}


def _finish_exact(launch: Dict[str, Any],
                  stages: Optional[StageTimes] = None
                  ) -> List[FlatQueryResult]:
    states = tracing.current_states()
    states.switch("device_wait")
    t_dev = time.perf_counter()
    vals = np.asarray(launch["vals"])
    gids = np.asarray(launch["gids"])
    totals = np.asarray(launch["totals"])
    launch["t_ready"] = states.switch("decode")
    if stages is not None:
        # variant-tagged, like `exact_dispatch.<variant>`
        stages.add(f"exact_device_wait.{launch['variant']}",
                   time.perf_counter() - t_dev)
    n = launch["n"]
    EXACT_RESULT_COUNTS.inc("queries", n=n)
    EXACT_RESULT_COUNTS.inc("empty", n=n - int(np.count_nonzero(totals[:n])))
    return _columnar_results(launch["resident"], vals, gids, totals,
                             n, lambda qi: "eq",
                             k_cap=launch["k"],
                             variant=launch.get("variant"))


def _execute_exact(resident: ResidentPack, flats: Sequence[FlatQuery],
                   k: int, mesh, stages: Optional[StageTimes] = None,
                   variant: Optional[str] = None,
                   program: Optional[ExactProgram] = None
                   ) -> List[FlatQueryResult]:
    return _finish_exact(_launch_exact(resident, flats, k, mesh,
                                       stages=stages, variant=variant,
                                       program=program),
                         stages=stages)


def run_exact_program(resident: ResidentPack, program: ExactProgram,
                      field: str, mesh) -> None:
    """Dispatch `program` once on this pack and wait for it: what
    compiles it (prewarm, the tests of the closed set). The queries are
    one term the pack lacks, twice (clause counts need two clauses): a
    slot each, no posting, whatever the pack holds; `_launch_exact`
    raises slots and window to the program's."""
    flat = FlatQuery(field, ["\x00warm"] * 2, 1.0,
                     2 if program.with_counts else 1)
    _execute_exact(resident, [flat] * program.rows, program.k_kernel, mesh,
                   program=program)


def _native_full_operands(pack: dist.StackedShardPack,
                          queries: Sequence[Sequence[str]],
                          boosts: Sequence[float], rows: int, slots: int,
                          pad_terms: int, stages: Optional[StageTimes]
                          ) -> Optional[dist.FullOperands]:
    """A full-postings launch's fused operand from one native call
    (`dist.build_full_operands`): the terms resolved
    (`prep.query_batch`), then the slot plan, the term ranges and the
    array in one C call that keeps the interpreter lock, far shorter than
    the wait to take it back (`prep.term_ranges`). None where the library
    did not build, a term's column is not in the table, or the plan needs
    more than `slots`: the Python builders then build the launch's
    operand as before."""
    if dist.native_operand_builder() is None:
        return None
    with tracing.stage(stages, "prep.query_batch"):
        terms = dist.resolve_launch_terms(pack, queries, boosts)
    if terms is None:
        return None
    with tracing.stage(stages, "prep.term_ranges"):
        return dist.build_full_operands(pack, terms, rows, slots, pad_terms)


def _launch_pruned(resident: ResidentPack, flats: Sequence[FlatQuery],
                   k: int, mesh, prefix_cap: int = PREFIX_CAP,
                   stages: Optional[StageTimes] = None,
                   with_rescore: bool = True,
                   full_slots: Optional[int] = None,
                   variant: Optional[str] = None,
                   max_batch: int = 128) -> Dict[str, Any]:
    """One fused ASYNC launch. Two modes:
    - full_slots=N: FULL-postings sorted-merge at the N-slot width —
      run totals are exact BM25, no rescore (SURVEY.md §5.7 applied as
      width buckets instead of prefixes); a rung up to FULL_READY_SLOTS
      calls its executable of `_ready_full_programs`;
    - prefix mode (block-max, §7.3#3): candidate generation over
      impact-sorted prefixes + EXACT on-device re-score (binary search
      in the doc-sorted postings). Only [B, k] crosses device→host."""
    import jax

    t_prep = time.perf_counter()
    pack = resident.pack
    imp_docs, imp_impacts = resident.imp_host
    k_cand = _candidate_k(k)
    k_out = _pruned_k_out(k)
    # a rung launches at its own row buckets (a batch taller than them
    # all at the general ones, compiled on first use, as before)
    b_bucket = next((rows for rows in FULL_ROW_BUCKETS.get(full_slots, ())
                     if rows >= len(flats)), _serving_bucket(len(flats)))
    # the launch path and its static width: the device program's name
    # (`jit_<path>` on the trace's XLA Modules line) and the label of
    # this launch's spans and counters
    path = (f"full_s{full_slots}" if full_slots is not None
            else f"hot_c{prefix_cap}")
    states = tracing.current_states()
    states.switch("prep", queries=len(flats), path=path, rows=b_bucket)
    if full_slots is not None:
        with_rescore = False
        k_cand = k_out  # exact totals: the candidate pool IS the result
    # the query operands (`prep.query_batch`), then the rescore terms'
    # ranges and the one array that carries them all (`prep.term_ranges`),
    # each inside `batcher.prep`: on the full path from one native call
    # where it can, else by the Python builders
    full = (_native_full_operands(pack, [f.terms for f in flats],
                                  [f.boost for f in flats], b_bucket,
                                  full_slots, PRUNE_MAX_TERMS, stages)
            if full_slots is not None else None)
    if full is not None:
        OPERAND_COUNTS.inc("native")
        ops = full.ops
    else:
        with tracing.stage(stages, "prep.query_batch"):
            if full_slots is not None:
                batch = dist.prepare_query_batch(
                    pack, [f.terms for f in flats],
                    boosts=[f.boost for f in flats],
                    min_counts=[1] * len(flats),
                    pad_batch_to=b_bucket,
                    pad_t_slots=full_slots, pad_max_len=dist.CHUNK_CAP)
            else:
                batch = dist.prepare_query_batch(
                    pack, [f.terms for f in flats],
                    boosts=[f.boost for f in flats],
                    min_counts=[1] * len(flats),
                    pad_batch_to=b_bucket,
                    prefix_cap=prefix_cap, imp_impacts=imp_impacts,
                    pad_t_slots=_prune_t_slots(prefix_cap),
                    pad_max_len=dist.CHUNK_CAP)
        with tracing.stage(stages, "prep.term_ranges"):
            t_starts, t_lengths, t_weights = dist.prepare_term_ranges(
                pack, batch, boosts=[f.boost for f in flats],
                pad_terms=PRUNE_MAX_TERMS)
            ops = dist.pack_pruned_operands(batch, t_starts, t_lengths,
                                            t_weights)
        if full_slots is not None:
            full = dist.FullOperands(ops, batch.t_slots, batch.max_len,
                                     batch.window, int(batch.lengths.sum()))
            OPERAND_COUNTS.inc("python")
    if variant is None:
        variant = _pruned_variant()
    KERNEL_VARIANT_COUNTS.inc("full" if full_slots is not None
                              else "pruned", variant)
    LAUNCH_COUNTS.inc(path)
    _count_cross_chip(mesh, b_bucket)
    if full_slots is not None:
        FULL_ENTRY_COUNTS.inc("real", n=full.real)
        # rows × slots × the chunk length dispatched (`pad_max_len`)
        FULL_ENTRY_COUNTS.inc("padded", n=ops.shape[0] * ops.shape[1]
                              * full.t_slots * max(full.max_len,
                                                   dist.CHUNK_CAP))
        # (a caller that forces a rung its queries do not fit, which the
        # routing never does, gets the jitted program at their width)
        ready = (_ready_full_programs(resident, mesh, k, max_batch, variant)
                 if full_slots <= FULL_READY_SLOTS
                 and full.t_slots == full_slots else {})
        fn = ready.get((full_slots, b_bucket)) or _make_full_search(
            resident, mesh, full_slots, k_out, variant)
    else:
        # single-key phase-A sort (PR 15): only when the batch's slot AND
        # rescore-term weights keep the 16-bit impact code monotone — the
        # group-size fit check is static inside make_pruned_search
        pack_keys = (variant == "packed" and with_rescore
                     and sparse.packable(pack.d_pad, batch.weights)
                     and sparse.packable(pack.d_pad, t_weights))
        fn = dist.make_pruned_search(
            mesh, max_len=batch.max_len, d_pad=pack.d_pad, p_pad=pack.p_pad,
            c_cand=k_cand, k_out=k_out,
            t_window=max(_PRUNE_WINDOW, batch.window),
            t_terms=PRUNE_MAX_TERMS, with_rescore=with_rescore,
            variant=variant, pack_keys=pack_keys, name=path)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from elasticsearch_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS
    sbt = NamedSharding(mesh, P(SHARD_AXIS, DATA_AXIS, None))
    # dispatch, three ways: the wait for the process-wide dispatch lock,
    # the host→device copy of the operands, and the jitted call (host
    # dispatch plus whatever the runtime blocks on: donation holds, a
    # full queue). `batch_dispatch` stays their sum; `batch_put` is the
    # copy alone, the part that grows with the mesh's devices.
    t_disp = states.switch("lock")
    with dist.DEVICE_DISPATCH_LOCK:
        t_put = states.switch("put")
        ops_dev = jax.device_put(ops, sbt)
        t_call = states.switch("call", path=path, rows=b_bucket)
        packed = fn(
            resident.imp_device_arrays[0], resident.imp_device_arrays[1],
            resident.device_arrays[0], resident.device_arrays[1],
            ops_dev)
    t_dev = states.switch("prep")
    _freeze_first_launch(resident, path, b_bucket, k_cand, variant, mesh)
    if stages is not None:
        stages.add("batch_prep", t_disp - t_prep)
        stages.add("batch_dispatch", t_dev - t_disp)
        stages.add("batch_put", t_call - t_put)
    return {"resident": resident, "flats": flats, "k": k,
            "packed": packed, "variant": variant}


def _launch_heads(resident: ResidentPack, flats: Sequence[FlatQuery],
                  tails: Sequence[Sequence[str]], k: int, mesh, slots: int,
                  stages: Optional[StageTimes] = None,
                  max_batch: int = 128) -> Dict[str, Any]:
    """One ASYNC launch of the heads program (`dist.make_heads_search`):
    queries holding the pack's head terms, their tails (`tails`, the
    terms that are not head terms) at the `slots` rung. Its operands: the
    tails' fused operand (one native call where it can, as the full path
    builds its own, with no rescore terms) and the head weights [rows,
    h_pad], each head row's sum of the query's boosts. The first launch
    on a pack builds its head block (`_head_block`) and submits every
    member of `heads_program_set` for compilation; the launch waits for
    its own. Finished by `_finish_pruned`: the output is the full path's."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticsearch_tpu.parallel.mesh import DATA_AXIS

    t_prep = time.perf_counter()
    pack = resident.pack
    rows = next((r for r in HEADS_ROW_BUCKETS[slots] if r >= len(flats)),
                _serving_bucket(len(flats)))
    label = dist.heads_program_name(rows, slots)
    states = tracing.current_states()
    states.switch("prep", queries=len(flats), path=label, rows=rows)
    block = _head_block(resident, mesh, stages)
    heads = pack.term_table.head_index(pack)
    boosts = [f.boost for f in flats]
    w = np.zeros((rows, block.h_pad), dtype=np.float32)
    n_terms = 0
    for q, f in enumerate(flats):
        for term in f.terms:
            h = heads.get(term)
            if h is not None:
                w[q, h] += f.boost
                n_terms += 1
    full = _native_full_operands(pack, tails, boosts, rows, slots, 0, stages)
    if full is not None:
        ops = full.ops
    else:
        with tracing.stage(stages, "prep.query_batch"):
            batch = dist.prepare_query_batch(
                pack, tails, boosts=boosts, pad_batch_to=rows,
                pad_t_slots=slots, pad_max_len=dist.CHUNK_CAP)
        with tracing.stage(stages, "prep.term_ranges"):
            none = np.zeros(batch.starts.shape[:2] + (0,), dtype=np.int32)
            ops = dist.pack_pruned_operands(batch, none, none,
                                            none.view(np.float32))
    if ops.shape[2] != 3 * slots + 1:
        raise ValueError(f"{label}: tails that need more than {slots} slots")
    HEADS_COUNTS.inc("queries", n=len(flats))
    HEADS_COUNTS.inc("launches")
    HEADS_COUNTS.inc("terms", n=n_terms)
    LAUNCH_COUNTS.inc(label)
    _count_cross_chip(mesh, rows)
    program = HeadsProgram(rows, slots, _pruned_k_out(k))
    ready = _ready_heads_programs(resident, mesh, k, max_batch, program)
    fn = (ready[(slots, rows)].result() if (slots, rows) in ready
          else _make_heads_search(resident, mesh, program))
    sbt = NamedSharding(mesh, P(SHARD_AXIS, DATA_AXIS, None))
    t_disp = states.switch("lock")
    with dist.DEVICE_DISPATCH_LOCK:
        t_put = states.switch("put")
        ops_dev = jax.device_put(ops, sbt)
        w_dev = jax.device_put(w, NamedSharding(mesh, P(DATA_AXIS, None)))
        t_call = states.switch("call", path=label, rows=rows)
        packed = fn(resident.imp_device_arrays[0],
                    resident.imp_device_arrays[1], block.array, ops_dev,
                    w_dev)
    t_dev = states.switch("prep")
    _freeze_first_launch(resident, label, program.k_out, mesh)
    if stages is not None:
        stages.add("batch_prep", t_disp - t_prep)
        stages.add("batch_dispatch", t_dev - t_disp)
        stages.add("batch_put", t_call - t_put)
    return {"resident": resident, "flats": flats, "k": k,
            "packed": packed, "variant": "heads"}


def _finish_pruned(launch: Dict[str, Any],
                   stages: Optional[StageTimes] = None
                   ) -> Tuple[List[FlatQueryResult], List[int]]:
    """Materialize a pruned launch and check the WAND validity bound —
    any doc outside the candidates scores below (approx cutoff + Σ
    skipped-tail maxima); failures escalate. Returns (results, invalid
    indices)."""
    resident, flats, k = (launch["resident"], launch["flats"],
                          launch["k"])
    # one device→host transfer; split host-side (k derived from the
    # packed width — the kernel clamps k_out to its candidate pool)
    states = tracing.current_states()
    states.switch("device_wait")
    t_dev = time.perf_counter()
    packed = np.asarray(launch["packed"])
    t_decode = launch["t_ready"] = time.perf_counter()
    states.switch("decode")
    vals, gids, totals, cutoff, beta = dist.unpack_pruned(packed)
    if stages is not None:
        stages.add("batch_device_wait", t_decode - t_dev)

    # vectorized batch decode: clamp each query to its first
    # min(n_valid, k) entries, then check the WAND validity bound
    # with scalar numpy reads — no per-hit Python
    decoded = _columnar_results(
        resident, vals, gids.astype(np.int64), totals, len(flats),
        lambda qi: "gte" if beta[qi] > 0.0 else "eq",
        variant=launch.get("variant"))
    results: List[FlatQueryResult] = []
    invalid: List[int] = []
    for qi, res in enumerate(decoded):
        b_q = float(beta[qi])
        n = len(res.scores)
        if n > k:
            res = dataclasses.replace(res, scores=res.scores[:k],
                                      rows=res.rows[:k], ords=res.ords[:k])
            n = k
        if b_q > 0.0:
            # validity at the caller's k: docs outside the candidate set
            # score below cutoff+β (cut candidates) or β (tail-only)
            kth = float(res.scores[k - 1]) if n >= k else float("-inf")
            c_q = float(cutoff[qi])
            threshold = (c_q + b_q) if c_q > dist.NEG_INF else b_q
            if kth < threshold or n < k:
                results.append(None)  # type: ignore[arg-type]
                invalid.append(qi)
                continue
        results.append(res)
    if stages is not None:
        stages.add("batch_decode", time.perf_counter() - t_decode)
    return results, invalid


def _execute_pruned(resident: ResidentPack, flats: Sequence[FlatQuery],
                    k: int, mesh, stages: Optional[StageTimes] = None,
                    prefix_cap: int = PREFIX_CAP,
                    with_rescore: bool = True,
                    full_slots: Optional[int] = None,
                    variant: Optional[str] = None
                    ) -> Tuple[List[FlatQueryResult], List[int]]:
    """Synchronous pruned execution (escalations, prewarm, dryrun)."""
    return _finish_pruned(
        _launch_pruned(resident, flats, k, mesh, prefix_cap=prefix_cap,
                       stages=stages, with_rescore=with_rescore,
                       full_slots=full_slots, variant=variant),
        stages=stages)


def _n_local_devices() -> int:
    import jax
    return len(jax.devices())


def device_stamp(devices: Sequence[Any]) -> Dict[str, Any]:
    """What is serving: platform and device_kind as jax reports them for
    `devices`, plus the versions a chip record is only comparable
    within. libtpu is None where the wheel is not installed."""
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


# ---------------------------------------------------------------------------
# batcher supervision: launch watchdog + wedge/crash recovery
# ---------------------------------------------------------------------------

class DeviceWedgedError(RuntimeError):
    """A device dispatch exceeded its launch deadline (or the batcher
    was torn down underneath a queued query). Typed so try_search can
    decline to the planner without tripping the generic error path."""


# fault-injection seam: DeviceWedge/DeviceLoss append a blocking
# callable here; launch_flat_batch calls through before doing ANY
# device work, so a "wedged" launch holds no locks the watchdog or
# supervisor need. Hooks receive the launch mesh so device-scoped
# faults (DeviceLoss) only fire for launches touching the lost chip.
DISPATCH_FAULT_HOOKS: List[Any] = []


def _dispatch_fault_point(mesh=None) -> None:
    for hook in list(DISPATCH_FAULT_HOOKS):
        hook(mesh)


def _mesh_device_ids(mesh) -> Tuple[int, ...]:
    """Device ids a launch on `mesh` implicates — watchdog attribution."""
    if mesh is None:
        return ()
    try:
        return tuple(int(d.id) for d in mesh.devices.flat)
    except Exception:  # noqa: BLE001 — attribution is best-effort
        return ()


class LaunchWatchdog:
    """Deadline-stamps every device dispatch. Workers bracket each
    launch/finish with begin()/end(); a scan thread fails any dispatch
    still open past `deadline_ms` with a typed DeviceWedgedError and
    fires `on_wedge` — a wedged SPMD launch trips supervision within
    the deadline instead of hanging the micro-batcher until the batch
    timeout. deadline_ms <= 0 disables monitoring (no scan thread)."""

    def __init__(self, deadline_ms: float = 120_000.0, on_wedge=None):
        self.deadline_s = max(0.0, float(deadline_ms)) / 1e3
        self.on_wedge = on_wedge
        self.c_launches = CounterMetric()
        self.c_wedges = CounterMetric()
        self.last_wedge: Optional[Dict[str, Any]] = None
        self._lock = threading.Lock()
        self._entries: Dict[int, Dict[str, Any]] = {}
        self._next_token = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if self.deadline_s > 0:
            self._thread = threading.Thread(target=self._scan_loop,
                                            daemon=True,
                                            name="tpu-launch-watchdog")
            self._thread.start()

    def begin(self, label: str, pendings,
              devices: Tuple[int, ...] = ()) -> Optional[int]:
        """Open a monitored dispatch; returns the token end() takes
        (None when monitoring is off). The pendings list is what the
        scan thread fails if the dispatch goes overdue. `devices` is
        the launch's mesh device-id set — a wedge carries it so health
        scoring can attribute the fault per chip."""
        if self.deadline_s <= 0:
            return None
        self.c_launches.inc()
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._entries[token] = {"label": label, "t0": time.monotonic(),
                                    "pendings": list(pendings),
                                    "devices": tuple(devices)}
        return token

    def end(self, token: Optional[int]) -> None:
        if token is None:
            return
        with self._lock:
            self._entries.pop(token, None)

    def inflight(self) -> int:
        with self._lock:
            return len(self._entries)

    def _scan_loop(self) -> None:
        # scan often enough that detection lands within the deadline
        # even for sub-second deadlines (the chaos tests run ~300ms)
        interval = max(0.01, min(0.25, self.deadline_s / 4))
        while not self._stop.wait(interval):
            now = time.monotonic()
            overdue = []
            with self._lock:
                for token in [t for t, e in self._entries.items()
                              if now - e["t0"] > self.deadline_s]:
                    overdue.append(self._entries.pop(token))
            for e in overdue:
                age_ms = (now - e["t0"]) * 1e3
                self.c_wedges.inc()
                wedge = {"label": e["label"],
                         "age_ms": round(age_ms, 1),
                         "devices": list(e.get("devices", ())),
                         "queries": len(e["pendings"]),
                         # launch attribution: trace ids of the traced
                         # requests riding the wedged dispatch
                         "trace_ids": [p.trace_span.trace_id
                                       for p in e["pendings"]
                                       if getattr(p, "trace_span", None)
                                       is not None]}
                self.last_wedge = wedge
                exc = DeviceWedgedError(
                    f"device dispatch ({e['label']}) exceeded its "
                    f"{self.deadline_s * 1e3:.0f}ms launch deadline "
                    f"after {age_ms:.0f}ms")
                for p in e["pendings"]:
                    if not p.future.done():
                        p.future.set_exception(exc)
                if self.on_wedge is not None:
                    try:
                        # full attribution dict: label, age_ms, the
                        # launch's device-id set, query count
                        self.on_wedge(wedge)
                    except Exception:  # noqa: BLE001 — scan must survive
                        logger.exception("watchdog on_wedge failed")

    def stats(self) -> Dict[str, Any]:
        return {"deadline_ms": round(self.deadline_s * 1e3, 1),
                "launches": self.c_launches.count,
                "wedges": self.c_wedges.count,
                "inflight": self.inflight(),
                "last_wedge": self.last_wedge}

    def close(self) -> None:
        self._stop.set()


# recovery.state gauge encoding (Prometheus can't carry strings)
_SUPERVISION_STATES = {"serving": 0, "down": 1, "recovering": 2}


class BatcherSupervisor:
    """Crash/wedge recovery for the device-owning batcher. trigger()
    tears the current batcher down — queued queries fail typed, every
    resident pack drops so the HBM breaker drains to EXACTLY zero (the
    lifecycle invariant) — and flips the service to degraded planner
    serving. maybe_recover() respawns a fresh MicroBatcher
    single-flight and eagerly re-attains residency for every dropped
    pack through IndexPackCache (re-charging the breaker), after which
    the kernel path resumes."""

    def __init__(self, svc: "TpuSearchService"):
        self.svc = svc
        self.state = "serving"
        self.c_recoveries = CounterMetric()
        self.c_degraded_served = CounterMetric()
        self.c_remeshes = CounterMetric()
        self.last_reason: Optional[str] = None
        self.last_duration_s = 0.0
        self.last_remesh_duration_s = 0.0
        # device topology of the batcher currently serving: recovery
        # rebuilds the mesh over the health registry's survivors, so
        # these shrink to N-1 on quarantine and restore on readmission
        self._mesh_ids: Tuple[int, ...] = _mesh_device_ids(svc.batcher.mesh)
        self.full_device_count = len(self._mesh_ids)
        self.mesh_device_count = len(self._mesh_ids)
        # breaker bytes observed after EVERY teardown drain — the chaos
        # suite asserts each entry is exactly zero (the invalidate_all
        # exact-zero invariant extended across remeshes)
        self.teardown_breaker_bytes: List[int] = []
        # disruption schemes hold recovery open so tests can observe
        # the degraded window; heal() lifts the hold and recovers
        self.hold_recovery = False
        self._lock = threading.Lock()
        self._dropped_keys: List[Tuple[str, str]] = []
        self._recover_thread: Optional[threading.Thread] = None

    @property
    def degraded_active(self) -> bool:
        return self.state != "serving"

    def trigger(self, reason: str) -> None:
        """Batcher is dead or wedged: tear it down and go degraded.
        Idempotent while already down/recovering."""
        with self._lock:
            self.last_reason = reason
            if self.state != "serving":
                return
            self.state = "down"
        logger.error("batcher supervision tripped (%s): serving degraded "
                     "planner results while recovering", reason)
        events.emit("supervisor.state", severity="error",
                    from_state="serving", to_state="down", reason=reason)
        events.incident("batcher_death", reason=reason)
        self._tear_down(reason)
        self.maybe_recover()

    def _tear_down(self, reason: str) -> None:
        svc = self.svc
        old = svc.batcher
        exc = DeviceWedgedError(f"batcher down: {reason}")
        try:
            old.fail_pending(exc)
        except Exception:  # noqa: BLE001 — teardown must complete
            logger.exception("failing pending queries during teardown")
        try:
            old.close()
        except Exception:  # noqa: BLE001
            logger.exception("closing dead batcher")
        dropped = svc.packs.invalidate_all()
        breaker = svc.packs._breaker
        if breaker is not None:
            # drain audit: invalidate_all released every pack's charge,
            # so this MUST read zero — recorded so the chaos suite can
            # assert the invariant held across every remesh
            self.teardown_breaker_bytes.append(
                int(getattr(breaker, "used", 0)))
            events.emit("hbm.drain",
                        severity=("info" if self.teardown_breaker_bytes[-1]
                                  == 0 else "error"),
                        bytes=self.teardown_breaker_bytes[-1],
                        packs_dropped=len(dropped), reason=reason)
        if svc.placement is not None:
            # full teardown under placement drains every group cache
            # too, with the SAME exact-zero audit per group
            for gid, cache in sorted(svc.group_caches.items()):
                cache.invalidate_all()
                gb = svc.placement.group(gid).breaker
                if gb is not None:
                    svc.placement.record_drain(gid, int(gb.used))
        with self._lock:
            self._dropped_keys = dropped

    def maybe_recover(self) -> None:
        with self._lock:
            # single-flight: only the caller that flips down→recovering
            # spawns the thread (a live-thread check would race the
            # window between releasing this lock and t.start())
            if self.state != "down" or self.hold_recovery:
                return
            self.state = "recovering"
            t = threading.Thread(target=self._recover, daemon=True,
                                 name="batcher-recovery")
            self._recover_thread = t
        events.emit("supervisor.state", severity="warning",
                    from_state="down", to_state="recovering")
        t.start()

    def _recover(self) -> None:
        svc = self.svc
        t0 = time.monotonic()
        try:
            if svc.placement is not None:
                self._recover_placement(t0)
                return
            old = svc.batcher
            # partial-mesh topology: rebuild over the health registry's
            # surviving devices. With every device healthy this is the
            # original full mesh (same jax.Mesh — jit caches keyed on
            # it stay hot); with quarantines it's a fresh N-k grid
            # (factorize_2d handles odd counts: 7 → 1×7).
            health = svc.health
            full_ids = _mesh_device_ids(svc.full_mesh)
            active = health.active_devices() if health is not None else None
            if active is not None and not active:
                with self._lock:
                    self.state = "down"
                events.emit("supervisor.state", severity="error",
                            from_state="recovering", to_state="down",
                            reason="every device quarantined")
                logger.error("every device is quarantined; staying on "
                             "degraded planner serving")
                return
            if active is None or len(active) == len(full_ids):
                mesh = svc.full_mesh
                mesh_ids = full_ids
            else:
                mesh = make_mesh(devices=active)
                mesh_ids = tuple(int(d.id) for d in active)
            remeshed = tuple(sorted(mesh_ids)) != tuple(
                sorted(self._mesh_ids))
            if remeshed:
                events.emit("remesh.begin", severity="warning",
                            from_devices=sorted(self._mesh_ids),
                            to_devices=sorted(mesh_ids))
            # anything rebuilt since teardown (a racing prewarm) was
            # placed on the OLD mesh — drop it and fold its keys in so
            # set_mesh sees an empty cache and re-residency covers it
            stragglers = svc.packs.invalidate_all()
            with self._lock:
                for key in stragglers:
                    if key not in self._dropped_keys:
                        self._dropped_keys.append(key)
                keys = list(self._dropped_keys)
            svc.packs.set_mesh(mesh)
            fresh = MicroBatcher(window_s=old.window_s,
                                 max_batch=old.max_batch)
            # counters carry over so scrape monotonicity survives respawn
            fresh.batches_executed = old.batches_executed
            fresh.queries_executed = old.queries_executed
            fresh.mesh = mesh
            fresh.stages = svc.stages
            fresh.watchdog = svc.watchdog
            # quota enforcement and fair lanes stay active through the
            # degraded → recovering → serving transitions
            fresh.tenants = old.tenants
            svc.batcher = fresh
            svc.packs.on_evict = fresh.retire_pack
            # HBM headroom: a partial mesh has proportionally less HBM
            # than the breaker limit was sized for — admit re-residency
            # warmest-first against the shrunken budget and SHED the
            # coldest packs (typed 503 + Retry-After) instead of
            # overcommitting the survivors
            keys.sort(key=svc.packs.heat_of, reverse=True)
            breaker = svc.packs._breaker
            budget = None
            if (breaker is not None and full_ids
                    and len(mesh_ids) < len(full_ids)):
                budget = int(getattr(breaker, "limit", 0)
                             * len(mesh_ids) / len(full_ids))
            rebuild: List[Tuple[str, str]] = []
            shed: List[Tuple[str, str]] = []
            projected = 0
            for key in keys:
                est = svc.packs.bytes_of(key)
                if budget is not None and rebuild \
                        and projected + est > budget:
                    shed.append(key)
                    continue
                projected += est
                rebuild.append(key)
            svc.set_shed(shed)
            # eager re-residency: rebuild every admitted pack through
            # the cache (re-charging the breaker) before traffic
            # returns — jit caches live on module functions, so a
            # full-mesh respawn pays no recompile
            resolver = svc.index_resolver
            rebuilt = 0
            replayed_indices: set = set()
            if resolver is not None:
                for index_name, field in rebuild:
                    try:
                        index_service = resolver(index_name)
                    except Exception:  # noqa: BLE001 — index may be gone
                        index_service = None
                    if index_service is None:
                        continue
                    # translog-gated visibility: before re-attaining the
                    # device image, replay each index's translog tail
                    # above its last refresh checkpoint so every acked
                    # write is in the reader the rebuild snapshots —
                    # the kill→recover→replay→checkpoint chain the
                    # chaos drill asserts (zero lost acked writes)
                    if index_name not in replayed_indices:
                        replayed_indices.add(index_name)
                        try:
                            r = index_service.replay_visibility(
                                reason="supervisor recovery")
                            if svc.delta_stats is not None:
                                svc.delta_stats.replayed_ops += \
                                    r.get("scanned", 0)
                        except Exception:  # noqa: BLE001 — best effort
                            logger.exception("visibility replay for %s",
                                             index_name)
                    try:
                        if svc.packs.get(index_service, field) is not None:
                            rebuilt += 1
                    except Exception:  # noqa: BLE001 — best effort
                        logger.exception("re-attaining residency for %s/%s",
                                         index_name, field)
            with self._lock:
                self.state = "serving"
                self.last_duration_s = time.monotonic() - t0
                self._mesh_ids = mesh_ids
                self.mesh_device_count = len(mesh_ids)
                if remeshed:
                    self.last_remesh_duration_s = self.last_duration_s
            if remeshed:
                self.c_remeshes.inc()
                events.emit("remesh.end", severity="warning",
                            devices=sorted(mesh_ids),
                            devices_total=len(full_ids) or len(mesh_ids),
                            duration_s=round(self.last_duration_s, 4))
            self.c_recoveries.inc()
            events.emit("supervisor.state", from_state="recovering",
                        to_state="serving",
                        duration_s=round(self.last_duration_s, 4),
                        devices=len(mesh_ids), rebuilt=rebuilt,
                        shed=len(shed))
            svc._tripped = False
            logger.warning("batcher recovered in %.2fs on %d/%d device(s) "
                           "(%d/%d packs re-resident, %d shed)",
                           self.last_duration_s, len(mesh_ids),
                           len(full_ids) or len(mesh_ids), rebuilt,
                           len(rebuild), len(shed))
            # a device readmitted (or lost) while this recovery ran:
            # converge onto the now-current active set
            if health is not None:
                want = tuple(sorted(health.active_ids()))
                if want != tuple(sorted(mesh_ids)):
                    self.trigger("device set changed during recovery")
        except Exception:  # noqa: BLE001 — stay degraded, stay alive
            with self._lock:
                self.state = "down"
            events.emit("supervisor.state", severity="error",
                        from_state="recovering", to_state="down",
                        reason="recovery failed")
            logger.exception("batcher recovery failed; staying degraded")

    def _recover_placement(self, t0: float) -> None:
        """Full-teardown recovery under fault-domain placement: respawn
        the batcher and remesh EACH group over its own survivors (a
        group's mesh never spans another group's devices), then
        eagerly re-attain residency for every placed replica. Group-
        scoped failover (one quarantined chip) never comes through
        here — it runs without a teardown at all."""
        svc = self.svc
        pl = svc.placement
        old = svc.batcher
        health = svc.health
        active = (set(health.active_ids()) if health is not None
                  else None)
        for gid, cache in sorted(svc.group_caches.items()):
            # stragglers built since teardown were placed on the old
            # group mesh — drop them before remeshing
            cache.invalidate_all()
            if active is not None:
                g = pl.group(gid)
                for i in g.active_ids:
                    if i not in active:
                        pl.on_device_lost(i)
                for i in g.device_ids:
                    if i in active and i not in pl.group(gid).active_ids:
                        pl.on_device_restored(i)
            g = pl.group(gid)
            if g.alive:
                cache.set_mesh(g.mesh)
        fresh = MicroBatcher(window_s=old.window_s,
                             max_batch=old.max_batch)
        fresh.batches_executed = old.batches_executed
        fresh.queries_executed = old.queries_executed
        fresh.mesh = svc.full_mesh
        fresh.stages = svc.stages
        fresh.watchdog = svc.watchdog
        fresh.tenants = old.tenants
        svc.batcher = fresh
        svc.packs.on_evict = fresh.retire_pack
        # eager re-residency of every placed replica (lazy rebuild on
        # first traffic when no resolver is wired)
        for key in pl.keys():
            for gid in pl.groups_of(key):
                if (pl.group(gid).alive
                        and svc.group_caches[gid].peek(key) is None):
                    svc._eager_rebuild(key, gid)
        mesh_ids = tuple(sorted(i for g in pl.groups()
                                for i in g.active_ids))
        with self._lock:
            self.state = "serving"
            self.last_duration_s = time.monotonic() - t0
            remeshed = mesh_ids != tuple(sorted(self._mesh_ids))
            self._mesh_ids = mesh_ids
            self.mesh_device_count = len(mesh_ids)
            if remeshed:
                self.last_remesh_duration_s = self.last_duration_s
        if remeshed:
            self.c_remeshes.inc()
            events.emit("remesh.end", severity="warning",
                        devices=sorted(mesh_ids),
                        devices_total=self.full_device_count,
                        duration_s=round(self.last_duration_s, 4),
                        placement_groups=pl.num_groups)
        self.c_recoveries.inc()
        events.emit("supervisor.state", from_state="recovering",
                    to_state="serving",
                    duration_s=round(self.last_duration_s, 4),
                    devices=len(mesh_ids))
        svc._tripped = False
        logger.warning("batcher recovered in %.2fs over %d placement "
                       "group(s), %d/%d device(s)", self.last_duration_s,
                       pl.num_groups, len(mesh_ids),
                       self.full_device_count)

    def schedule_full_remesh(self, reason: str) -> None:
        """A quarantined device proved healthy again: recover onto the
        restored device set inside a DRAIN WINDOW — wait (bounded by
        `svc.drain_window_s`) for pending/in-flight work to drain so
        the remesh interrupts as little traffic as possible, then
        trigger a respawn that maps onto the registry's active set."""
        def run() -> None:
            svc = self.svc
            deadline = time.monotonic() + max(0.0, svc.drain_window_s)
            while time.monotonic() < deadline:
                depths = svc.batcher.queue_depths()
                wd = svc.watchdog
                if (depths["pending"] == 0 and depths["inflight"] == 0
                        and (wd is None or wd.inflight() == 0)):
                    break
                time.sleep(0.02)
            health = svc.health
            want = (tuple(sorted(health.active_ids()))
                    if health is not None else ())
            with self._lock:
                have = tuple(sorted(self._mesh_ids))
            if want == have:
                return  # already serving on this device set
            self.trigger(reason)
        threading.Thread(target=run, daemon=True,
                         name="device-full-remesh").start()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"state": self.state,
                    "state_code": _SUPERVISION_STATES.get(self.state, -1),
                    "recoveries": self.c_recoveries.count,
                    "degraded_served": self.c_degraded_served.count,
                    "last_reason": self.last_reason,
                    "last_duration_seconds": round(self.last_duration_s, 4),
                    "remeshes": self.c_remeshes.count,
                    "last_remesh_duration_seconds":
                        round(self.last_remesh_duration_s, 4),
                    "mesh_devices": self.mesh_device_count,
                    "mesh_devices_full": self.full_device_count}


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------

class TpuSearchService:
    """Facade the coordinator calls: eligibility check, pack lookup,
    micro-batched execution. One instance per node."""

    def __init__(self, breaker=None, mesh=None, window_s: float = 0.01,
                 max_batch: int = 128, batch_timeout_s: float = 30.0,
                 plan_cache_size: int = 2048,
                 prewarm_concurrency: int = 4,
                 compile_cache_dir: Optional[str] = None,
                 packed_sort: bool = True,
                 compressed_pack: bool = True,
                 launch_deadline_ms: float = 120_000.0,
                 device_health: Optional[Dict[str, Any]] = None,
                 placement: Optional[Dict[str, Any]] = None,
                 delta: Optional[Dict[str, Any]] = None):
        _ensure_compile_cache(compile_cache_dir)
        KERNEL_CONFIG["packed_sort"] = bool(packed_sort)
        KERNEL_CONFIG["compressed_pack"] = bool(compressed_pack)
        self.packs = IndexPackCache(mesh=mesh, breaker=breaker)
        self.plans = PlanCache(max_entries=plan_cache_size)
        self.batch_timeout_s = batch_timeout_s
        self.prewarm_concurrency = max(1, prewarm_concurrency)
        self.batcher = MicroBatcher(window_s=window_s, max_batch=max_batch)
        # pack eviction retires the pack's batch queue immediately
        self.packs.on_evict = self.batcher.retire_pack
        self.batcher.mesh = self.packs.mesh
        # the healthy-topology mesh: partial-mesh recovery shrinks
        # packs.mesh/batcher.mesh, full-mesh recovery restores THIS
        self.full_mesh = self.packs.mesh
        self._device_stamp = device_stamp(list(self.full_mesh.devices.flat))
        self.stages = StageTimes()
        self.batcher.stages = self.stages
        # device fault domains: per-device wedge scoring, micro-probe
        # quarantine, and flap-damped reintroduction (disable with
        # device_health={"enabled": False})
        hcfg = dict(device_health or {})
        self.health: Optional["DeviceHealthRegistry"] = None
        self.drain_window_s = float(hcfg.get("drain_window_seconds", 2.0))
        if hcfg.get("enabled", True):
            from elasticsearch_tpu.parallel.health import \
                DeviceHealthRegistry
            self.health = DeviceHealthRegistry(
                list(self.full_mesh.devices.flat),
                suspect_after=int(hcfg.get("suspect_after", 2)),
                probe_deadline_ms=float(
                    hcfg.get("probe_deadline_ms", 5_000.0)),
                reprobe_interval_s=float(
                    hcfg.get("reprobe_interval_seconds", 30.0)),
                hold_down_s=float(hcfg.get("hold_down_seconds", 60.0)),
                reintroduce_after=int(hcfg.get("reintroduce_after", 3)),
                on_quarantine=self._on_device_quarantine,
                on_reintroduce=self._on_device_reintroduced)
        # packs shed during a partial-mesh recovery: (index, field) →
        # shed info; try_search declines them and the coordinator
        # answers a typed 503 + Retry-After instead of silently
        # rebuilding into HBM the survivors don't have
        self._shed: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._shed_lock = threading.Lock()
        self.shed_retry_after_s = float(
            hcfg.get("shed_retry_after_seconds", 5.0))
        # pack-replica placement across device fault domains: partition
        # the mesh into `placement.groups` device groups and place each
        # pack's shard groups onto `placement.replicas` of them — a
        # quarantined chip then FAILS ITS GROUP OVER to a surviving
        # replica group instead of shedding. groups=1 (the default)
        # keeps the classic whole-mesh path byte-identical: placement
        # is None and every existing seam behaves exactly as before.
        pcfg = dict(placement or {})
        self.placement = None
        self.group_caches: Dict[int, "IndexPackCache"] = {}
        n_groups = int(pcfg.get("groups", 1))
        if n_groups > 1:
            from elasticsearch_tpu.parallel.placement import \
                PlacementService
            self.placement = PlacementService(
                list(self.full_mesh.devices.flat), n_groups,
                int(pcfg.get("replicas", 1)), breaker=breaker)
            for g in self.placement.groups():
                cache = IndexPackCache(mesh=g.mesh, breaker=g.breaker,
                                       group_id=g.gid)
                # route through self.batcher so a supervisor respawn
                # re-targets eviction at the live batcher automatically
                cache.on_evict = \
                    lambda r: self.batcher.retire_pack(r)
                self.group_caches[g.gid] = cache
        # (index, field) keys currently served by a surviving replica
        # group because their home group lost a device — the coordinator
        # stamps these responses `failed_over` (degraded but answered,
        # NEVER shed while any replica lives)
        self._failed_over: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._placement_lock = threading.RLock()
        # supervision: the watchdog deadline-stamps every dispatch and
        # trips the supervisor on a wedge; the supervisor respawns the
        # batcher (over the surviving devices) and re-attains residency
        self.watchdog = LaunchWatchdog(deadline_ms=launch_deadline_ms,
                                       on_wedge=self._on_wedge)
        self.batcher.watchdog = self.watchdog
        self.supervisor = BatcherSupervisor(self)
        # set by the node: index name → live IndexService (recovery's
        # eager re-residency path); None = rebuild lazily on traffic
        self.index_resolver = None
        self.served = 0      # queries answered by the kernel path
        self.fallback = 0    # queries declined to the planner path
        self.timeouts = 0    # kernel waits that hit the deadline
        self.last_error: Optional[str] = None  # most recent kernel failure
        # kernel-path breaker: after a batch-wait timeout the batcher
        # thread may be wedged (stuck XLA compile) — route everything to
        # the planner immediately, letting one probe through per cooldown
        # to detect recovery
        self._tripped = False
        self._next_probe = 0.0
        self.probe_cooldown_s = 30.0
        # while prewarm compiles run, try_search declines to the planner
        # (graceful cold start: early traffic must never stall a train
        # behind a cold XLA compile and trip the breaker)
        self._warming = False
        self._prewarm_lock = threading.Lock()
        self._prewarm_progress: Dict[str, Any] = {
            "state": "idle", "total": 0, "done": 0, "seconds": 0.0}
        # -- streaming delta chain (LSM resident path) -----------------
        # append-only refreshes chain small delta packs on the base
        # image; a background compactor folds them back in. Placement
        # group caches keep the classic full-rebuild path (replica
        # groups must stay byte-identical to each other).
        # opt-in: a bare TpuSearchService() keeps the classic
        # rebuild-on-refresh contract (tests and embedders rely on a
        # pack's bytes being the whole charge); Node passes the config
        # dict, so the serving stack runs with the chain on by default
        dcfg = dict(delta or {})
        self.delta_stats = DeltaStats()
        self.packs.delta_stats = self.delta_stats
        self.packs.delta_enabled = (delta is not None
                                    and bool(dcfg.get("enabled", True))
                                    and self.placement is None)
        self.packs.delta_max_packs = int(dcfg.get("max_packs", 4))
        self.packs.delta_max_docs = int(dcfg.get("max_docs", 50_000))
        self.packs.on_compact_needed = self._request_compaction
        self._compact_pending: set = set()
        self._compact_wakeup = threading.Event()
        self._compact_closed = False
        self._compact_thread: Optional[threading.Thread] = None

    # -- background compaction -----------------------------------------

    def _request_compaction(self, key) -> None:
        """Pack-cache callback: the delta chain for `key` crossed its
        fold threshold. Compaction runs on ONE background thread (a
        full pack build is minutes at scale — never on a serving
        thread), started lazily on first demand."""
        with self._prewarm_lock:
            self._compact_pending.add(tuple(key))
            if self._compact_thread is None and not self._compact_closed:
                self._compact_thread = threading.Thread(
                    target=self._compact_loop, daemon=True,
                    name="delta-compactor")
                self._compact_thread.start()
        self._compact_wakeup.set()

    def _compact_loop(self) -> None:
        while not self._compact_closed:
            self._compact_wakeup.wait(timeout=1.0)
            self._compact_wakeup.clear()
            while True:
                with self._prewarm_lock:
                    if self._compact_closed or not self._compact_pending:
                        break
                    key = self._compact_pending.pop()
                if self.degraded_active:
                    # a teardown is in flight — the chain dies with the
                    # residency drop; recovery rebuilds the full image
                    continue
                try:
                    self.packs.compact(key)
                except Exception:  # noqa: BLE001 — compact() reports
                    logger.exception("delta compaction for %s", key)

    def _on_wedge(self, wedge: Dict[str, Any]) -> None:
        """Watchdog callback (scan thread): an overdue dispatch means
        the device path is wedged — score the implicated devices
        (probing suspects synchronously, so recovery sees the updated
        quarantine set), then trip supervision."""
        label = wedge.get("label", "?")
        age_ms = float(wedge.get("age_ms", 0.0))
        self.last_error = (f"device_wedged: {label} overdue "
                           f"after {age_ms:.0f}ms")
        events.emit("watchdog.wedge", severity="error", label=label,
                    age_ms=age_ms, devices=wedge.get("devices", ()),
                    queries=wedge.get("queries", 0),
                    trace_ids=wedge.get("trace_ids", ()))
        events.incident("wedge", label=label, age_ms=age_ms,
                        devices=wedge.get("devices", ()),
                        trace_ids=wedge.get("trace_ids", ()))
        if self.health is not None:
            try:
                self.health.record_wedge(wedge.get("devices", ()),
                                         label=label)
            except Exception:  # noqa: BLE001 — supervision must trip
                logger.exception("device health scoring failed")
        if self.placement is not None and wedge.get("devices"):
            # group-attributed wedge under placement: any confirmed-bad
            # chip already failed its group over (the quarantine
            # callback ran synchronously inside record_wedge) — the
            # batcher itself is healthy, so a full teardown would
            # needlessly drop every OTHER group's residency. A wedge
            # whose probes all passed was transient: the watchdog
            # failed its cohort typed and serving continues.
            return
        self.supervisor.trigger(f"device wedge ({label}, {age_ms:.0f}ms)")

    def _on_device_quarantine(self, device_id: int) -> None:
        """Health-registry callback: a confirmed-bad chip left the
        active set. With placement, fail over ONLY the chip's group;
        classic path: respawn the whole batcher onto the survivors
        (idempotent while a wedge-triggered teardown is in flight)."""
        if self.placement is not None:
            self._group_failover(device_id,
                                 f"device {device_id} quarantined")
            return
        self.supervisor.trigger(f"device {device_id} quarantined")

    def _on_device_reintroduced(self, device_id: int) -> None:
        """Health-registry callback: a quarantined chip passed its
        consecutive-healthy-probe bar — schedule a drain-window
        recovery back onto the fuller mesh (placement: remesh only
        the chip's group and restore full placement)."""
        if self.placement is not None:
            self._schedule_group_restore(device_id)
            return
        self.supervisor.schedule_full_remesh(
            f"device {device_id} reintroduced")

    @property
    def degraded_active(self) -> bool:
        """True while the batcher is down or recovering: queries serve
        through the planner path with a degraded marker."""
        return self.supervisor.degraded_active

    @property
    def degraded_info(self) -> Optional[Dict[str, Any]]:
        """Structured degraded reason for responses/fronts/stats: None
        at full health; {"reason": "partial_mesh"|"recovering"|..,
        "devices": n, "devices_total": m} otherwise."""
        sup = self.supervisor
        total = sup.full_device_count
        if sup.degraded_active:
            return {"reason": sup.state if sup.state != "down"
                    else "batcher_down",
                    "devices": sup.mesh_device_count,
                    "devices_total": total}
        if self.placement is not None:
            active = self.placement.devices_active()
            p_total = self.placement.devices_total()
            if active < p_total:
                return {"reason": "partial_mesh",
                        "devices": active,
                        "devices_total": p_total}
        if sup.mesh_device_count < total:
            return {"reason": "partial_mesh",
                    "devices": sup.mesh_device_count,
                    "devices_total": total}
        return None

    # -- shed packs (N-1 HBM headroom) ---------------------------------

    def set_shed(self, keys: List[Tuple[str, str]],
                 retry_after_s: Optional[float] = None) -> None:
        """Replace the shed set (supervisor recovery): every listed
        (index, field) answers typed 503 + Retry-After until a fuller
        mesh re-admits it. An empty list clears the state."""
        retry = (self.shed_retry_after_s if retry_after_s is None
                 else float(retry_after_s))
        with self._shed_lock:
            self._shed = {tuple(k): {"retry_after_s": retry,
                                     "since": time.monotonic()}
                          for k in keys}
        if keys:
            logger.error("HBM headroom on the partial mesh cannot hold "
                         "%d pack(s): %s shed (503 + Retry-After %.0fs)",
                         len(keys), sorted(keys), retry)
            events.emit("pack.shed", severity="error",
                        keys=sorted(keys), retry_after_s=retry,
                        reason="partial_mesh_headroom")
            events.incident("pack_shed", keys=sorted(keys),
                            reason="partial_mesh_headroom")

    def shed_keys(self) -> List[Tuple[str, str]]:
        with self._shed_lock:
            return sorted(self._shed)

    def shed_info(self, index_name: str) -> Optional[Dict[str, Any]]:
        """Shed metadata when ANY field of `index_name` is shed (the
        coordinator's typed-503 check), else None."""
        with self._shed_lock:
            for (idx, field), info in self._shed.items():
                if idx == index_name:
                    return {"index": idx, "field": field, **info}
        return None

    def add_shed(self, keys: List[Tuple[str, str]],
                 retry_after_s: Optional[float] = None) -> None:
        """Add keys to the shed set without replacing it (placement
        failover sheds ONLY packs whose every replica is lost)."""
        retry = (self.shed_retry_after_s if retry_after_s is None
                 else float(retry_after_s))
        with self._shed_lock:
            for k in keys:
                self._shed[tuple(k)] = {"retry_after_s": retry,
                                        "since": time.monotonic()}
        if keys:
            logger.error("no placement group can hold %d pack(s): %s "
                         "shed (503 + Retry-After %.0fs)",
                         len(keys), sorted(tuple(k) for k in keys), retry)
            events.emit("pack.shed", severity="error",
                        keys=sorted(tuple(k) for k in keys),
                        retry_after_s=retry, reason="no_replica_group")
            events.incident("pack_shed",
                            keys=sorted(tuple(k) for k in keys),
                            reason="no_replica_group")

    def remove_shed(self, key: Tuple[str, str]) -> None:
        with self._shed_lock:
            self._shed.pop(tuple(key), None)

    # -- fault-domain placement (pack replicas across device groups) ---

    def failover_info(self, index_name: str) -> Optional[Dict[str, Any]]:
        """Failover metadata when ANY field of `index_name` is being
        served by a surviving replica group (the coordinator's
        `failed_over` degraded stamp), else None."""
        with self._placement_lock:
            for (idx, field), info in self._failed_over.items():
                if idx == index_name:
                    return {"index": idx, "field": field, **info}
        return None

    def _bytes_hint(self, key: Tuple[str, str]) -> int:
        """Best-known HBM cost of `key` across every group cache (0
        when never built — placement then admits and the build's own
        breaker charge is the backstop)."""
        return max((c.bytes_of(key) for c in self.group_caches.values()),
                   default=0)

    def _grouped_get(self, index_service,
                     field: str) -> Tuple[Optional[ResidentPack],
                                          Optional[int]]:
        """Placement-routed pack lookup: resolve (or create) the key's
        replica placement, route to the least-loaded healthy replica
        group, and serve from THAT group's cache. Replicas on the
        other placed groups build lazily (first access) and refresh
        whenever the routed copy observed newer readers — so a
        failover target is at most one refresh behind, and its own
        `get` re-validates against the live readers anyway."""
        pl = self.placement
        key = (index_service.name, field)
        with self._placement_lock:
            gids = pl.groups_of(key)
            if not gids:
                gids = tuple(pl.place(key,
                                      est_bytes=self._bytes_hint(key)))
        if not gids:
            return None, None
        gid = pl.route(key)
        if gid is None:
            return None, None
        resident = self.group_caches[gid].get(index_service, field)
        if resident is None:
            return None, gid
        # replica maintenance: the OTHER placed groups build/refresh
        # toward the routed copy's reader snapshot
        for g in gids:
            if g == gid or not pl.group(g).alive:
                continue
            cache = self.group_caches[g]
            peek = cache.peek(key)
            if peek is not None and peek.reader_key == resident.reader_key:
                continue
            try:
                cache.get(index_service, field)
            except Exception:  # noqa: BLE001 — a replica build failing
                # (group breaker full, transient) must not fail the
                # routed query; the key simply has one fewer warm copy
                logger.warning("replica build for %s on group %d failed",
                               key, g, exc_info=True)
        return resident, gid

    def _group_failover(self, device_id: int, reason: str) -> None:
        """A chip in one placement group was quarantined: fail over
        that group's packs to their surviving replica groups, remesh
        ONLY the affected group over its survivors, re-place only what
        has no live replica, and shed (typed 503) only packs whose
        every replica is lost."""
        pl = self.placement
        with self._placement_lock:
            gid = pl.on_device_lost(device_id)
            if gid is None:
                return
            group = pl.group(gid)
            cache = self.group_caches[gid]
            exc = DeviceWedgedError(
                f"placement group {gid} lost device {device_id} "
                f"({reason})")
            # queued queries on this group's replicas must not wait out
            # a deadline against the dead chip — fail them typed; the
            # NEXT request routes to a surviving replica group
            for resident in cache.residents():
                self.batcher.fail_pack_pending(resident, exc)
            dropped = cache.invalidate_all()
            if group.breaker is not None:
                # per-group exact-zero drain audit (the chaos suite
                # asserts every entry is exactly zero)
                pl.record_drain(gid, int(group.breaker.used))
            if group.alive:
                # remesh ONLY the affected group: the other groups'
                # meshes (and their jit caches) are untouched
                cache.set_mesh(group.mesh)
            heat = {key: cache.heat_of(key) for key in dropped}
            failed_over: List[Tuple[Tuple[str, str], int]] = []
            orphans: List[Tuple[str, str]] = []
            for key in dropped:
                pl.drop_replica(key, gid)
                live = [g for g in pl.groups_of(key) if pl.group(g).alive]
                built = [g for g in live
                         if self.group_caches[g].peek(key) is not None]
                if live:
                    failed_over.append((key, (built or live)[0]))
                else:
                    orphans.append(key)
            now = time.monotonic()
            for key, to_gid in failed_over:
                pl.c_failovers.inc()
                self._failed_over[key] = {
                    "reason": "failed_over", "from_group": gid,
                    "to_group": to_gid, "device": int(device_id),
                    "since": now}
            # re-place ONLY what has no live replica, warmest-first
            # under per-group headroom; what fits nowhere is shed
            orphans.sort(key=lambda k: heat.get(k, 0.0), reverse=True)
            shed: List[Tuple[str, str]] = []
            for key in orphans:
                placed = pl.place(key, est_bytes=self._bytes_hint(key),
                                  want=1)
                if placed:
                    pl.c_replacements.inc()
                    self._eager_rebuild(key, placed[-1])
                else:
                    pl.c_shed.inc()
                    shed.append(key)
        if shed:
            self.add_shed(shed)
        events.emit("placement.failover", severity="error", group=gid,
                    device=int(device_id), reason=reason,
                    failed_over=[k for k, _g in failed_over],
                    replaced=len(orphans) - len(shed), shed=len(shed))
        logger.error("placement failover for group %d (%s): %d pack(s) "
                     "failed over, %d re-placed, %d shed",
                     gid, reason, len(failed_over),
                     len(orphans) - len(shed), len(shed))

    def _eager_rebuild(self, key: Tuple[str, str], gid: int) -> None:
        """Best-effort eager re-residency of `key` on group `gid`
        through the index resolver; without a resolver (or on any
        build failure) the placement entry stands and the next access
        rebuilds lazily."""
        resolver = self.index_resolver
        if resolver is None:
            return
        index_name, field = key
        try:
            index_service = resolver(index_name)
        except Exception:  # noqa: BLE001 — index may be gone
            index_service = None
        if index_service is None:
            return
        try:
            self.group_caches[gid].get(index_service, field)
        except Exception:  # noqa: BLE001 — lazy rebuild remains
            logger.exception("re-attaining residency for %s/%s on "
                             "group %d", index_name, field, gid)

    def _schedule_group_restore(self, device_id: int) -> None:
        """Reintroduction under placement: wait out a drain window
        (bounded by `drain_window_s`) so the remesh interrupts as
        little in-flight work as possible, then restore the chip's
        group to full membership and the table to full placement."""
        def run() -> None:
            deadline = time.monotonic() + max(0.0, self.drain_window_s)
            while time.monotonic() < deadline:
                depths = self.batcher.queue_depths()
                wd = self.watchdog
                if (depths["pending"] == 0 and depths["inflight"] == 0
                        and (wd is None or wd.inflight() == 0)):
                    break
                time.sleep(0.02)
            try:
                self._group_restore(device_id)
            except Exception:  # noqa: BLE001 — restore must not die
                logger.exception("placement group restore failed")
        threading.Thread(target=run, daemon=True,
                         name="placement-group-restore").start()

    def _group_restore(self, device_id: int) -> None:
        pl = self.placement
        with self._placement_lock:
            gid = pl.on_device_restored(device_id)
            if gid is None:
                return
            group = pl.group(gid)
            cache = self.group_caches[gid]
            # packs resident on the group's PARTIAL mesh drop (their
            # arrays were placed with the old sharding) and rebuild on
            # the restored mesh — exact-zero drain per group, audited
            exc = DeviceWedgedError(
                f"placement group {gid} remeshing after device "
                f"{device_id} readmission")
            for resident in cache.residents():
                self.batcher.fail_pack_pending(resident, exc)
            cache.invalidate_all()
            if group.breaker is not None:
                pl.record_drain(gid, int(group.breaker.used))
            cache.set_mesh(group.mesh)
            # return to FULL placement: shed keys re-admit first
            # (they've been answering 503s), then every short placement
            # tops back up to R replicas
            for key in self.shed_keys():
                if pl.place(key, est_bytes=self._bytes_hint(key)):
                    self.remove_shed(key)
                    pl.c_replacements.inc()
            for key in pl.keys():
                if len(pl.groups_of(key)) < pl.replicas:
                    pl.place(key, est_bytes=self._bytes_hint(key))
            # failover stamps clear once a key's placement is whole
            # again (bounded by how many healthy groups exist)
            target = min(pl.replicas, len(pl.healthy_gids()))
            for key in list(self._failed_over):
                live = [g for g in pl.groups_of(key)
                        if pl.group(g).alive]
                if len(live) >= target:
                    self._failed_over.pop(key, None)
            # eager re-residency of everything placed on this group
            for key in pl.keys():
                if gid in pl.groups_of(key) and cache.peek(key) is None:
                    self._eager_rebuild(key, gid)
        events.emit("placement.restore", severity="warning", group=gid,
                    device=int(device_id),
                    devices_active=pl.devices_active(),
                    devices_total=pl.devices_total())
        logger.warning("placement group %d restored after device %d "
                       "readmission (%d/%d devices active)", gid,
                       device_id, pl.devices_active(),
                       pl.devices_total())

    def kill(self, reason: str = "killed") -> None:
        """Simulate batcher-process death (BatcherKill disruption, ops
        drills): tears down the batcher exactly as a wedge trip does."""
        self.supervisor.trigger(reason)

    def set_kernel_packed_sort(self, enabled: bool) -> None:
        """Flip the packed-sort kernel variant at runtime: the seam
        through which tests reach "ref" on a pack small enough to pack
        (per-launch packability fallback still applies when enabling)."""
        KERNEL_CONFIG["packed_sort"] = bool(enabled)

    @property
    def kernel_packed_sort(self) -> bool:
        return KERNEL_CONFIG["packed_sort"]

    def invalidate_index(self, index_name: str) -> None:
        """Drop resident packs AND lowered plans of a deleted/closed
        index (releases HBM breaker bytes and pinned readers)."""
        self.packs.invalidate(index_name)
        self.plans.invalidate_index(index_name)

    def invalidate_plans(self, index_name: str) -> None:
        """Drop only the lowered-plan entries for an index (mapping
        updates: the pack may still be valid, the lowering isn't — and
        the generation key change has already made the old entries
        unreachable; this purge keeps the LRU from carrying them)."""
        self.plans.invalidate_index(index_name)

    def try_search(self, index_service, query: dsl.QueryNode, *,
                   k: int,
                   timeout_s: Optional[float] = None,
                   profile_sink: Optional[Dict[str, Any]] = None
                   ) -> Optional[FlatQueryResult]:
        """Returns the kernel result, or None → caller uses the planner.
        k = from + size (top window the coordinator needs). timeout_s
        bounds the batch wait (a request deadline); the service cap
        applies regardless. profile_sink (a `profile: true` search)
        receives the kernel-side story: variant, plan-cache outcome,
        and this query's per-stage host timings."""
        if k <= 0 or k > 10_000:
            self.fallback += 1
            return None
        if self._warming:
            # cold-start grace: prewarm compiles are in flight — first
            # traffic routes to the planner instead of stalling behind a
            # cold compile (the 8.8M-doc first-train stall + breaker trip)
            self.fallback += 1
            return None
        if self.supervisor.degraded_active:
            # batcher down or recovering: degraded-mode serving — the
            # planner answers (with a degraded marker) instead of
            # queueing behind a dead batcher
            self.fallback += 1
            self.supervisor.c_degraded_served.inc()
            self.supervisor.maybe_recover()
            return None
        # `lower` keeps the request thread's CPU seconds beside its wall
        # seconds (sampled): lowering never blocks, so wall minus CPU is
        # time spent waiting for the GIL or the scheduler
        cpu_clock = (time.thread_time if self.stages.sample_cpu("lower")
                     else None)
        t0 = time.perf_counter()
        c0 = cpu_clock() if cpu_clock else 0.0
        pkey = plan_key(query)
        cache_key = None
        if pkey is not None:
            gen = getattr(index_service.mapper, "generation", 0)
            cache_key = (index_service.name, gen, pkey)
        cached = self.plans.get(cache_key) if cache_key is not None else None
        if cached is NOT_LOWERABLE:
            cpu = cpu_clock() - c0 if cpu_clock else None
            self.stages.add("lower", time.perf_counter() - t0, cpu=cpu)
            self.fallback += 1
            return None
        cached_rk = None
        if cached is not None:
            flat, cached_rk = cached
        else:
            flat = lower_query(query, index_service.mapper)
            if flat is None:
                if cache_key is not None:
                    self.plans.put(cache_key, NOT_LOWERABLE)
                cpu = cpu_clock() - c0 if cpu_clock else None
                self.stages.add("lower", time.perf_counter() - t0, cpu=cpu)
                self.fallback += 1
                return None
        lower_cpu = cpu_clock() - c0 if cpu_clock else None
        t1 = time.perf_counter()
        with self._shed_lock:
            is_shed = (index_service.name, flat.field) in self._shed
        if is_shed:
            # the partial mesh shed this pack: never rebuild it here
            # (that would overcommit the survivors' HBM) — the
            # coordinator answers the typed 503 + Retry-After
            self.fallback += 1
            return None
        route_gid: Optional[int] = None
        chain: Optional[PackChain] = None
        if self.placement is not None:
            resident, route_gid = self._grouped_get(index_service,
                                                    flat.field)
            if resident is None and route_gid is None:
                # no healthy replica group right now — planner serves
                self.fallback += 1
                return None
        else:
            # chain-aware residency: an append-only refresh rides as a
            # small delta pack unioned into the result instead of a full
            # rebuild; with deltas disabled this degenerates to get()
            chain = self.packs.get_chain(index_service, flat.field)
            resident = None if chain is None else chain.base
        t2 = time.perf_counter()
        self.stages.add("lower", t1 - t0, cpu=lower_cpu)
        self.stages.add("pack_get", t2 - t1)
        if resident is None:
            # field has no postings anywhere → zero hits, kernel-free
            self.served += 1
            if profile_sink is not None:
                profile_sink["empty_pack"] = True
            return FlatQueryResult.empty()
        # plans validate against the CHAIN's reader key when one exists:
        # the base pack keeps its (older) key while deltas cover the new
        # segments, and a plan is valid for exactly that reader set
        rkey = chain.reader_key if chain is not None else resident.reader_key
        plan_outcome = ("uncacheable" if cache_key is None
                        else "hit" if cached is not None else "miss")
        if cache_key is not None:
            if cached is None:
                self.plans.put(cache_key, (flat, rkey))
            elif cached_rk != rkey:
                plan_outcome = "revalidated"
                # the resident pack was rebuilt since this plan was
                # cached (refresh/merge mid-traffic): re-lower so no
                # plan ever runs against a pack it wasn't validated
                # on, then re-pin the entry to the live pack
                flat = lower_query(query, index_service.mapper)
                if flat is None:
                    self.plans.put(cache_key, NOT_LOWERABLE)
                    self.fallback += 1
                    return None
                self.plans.put(cache_key, (flat, rkey))
        if self._tripped:
            now = time.monotonic()
            if now < self._next_probe:
                self.fallback += 1
                return None
            self._next_probe = now + self.probe_cooldown_s  # one probe
        # The kernel path is an optional accelerator: any failure here
        # must degrade to the planner, never surface as an error
        # (EnginePlugin seam contract — an engine swap preserves behavior).
        try:
            t_sub = time.perf_counter()
            # go through submit() — the seam fault-injection tests hook —
            # and read the decomposition marks back off the future (a
            # mocked future simply has no marks: split degrades to None)
            fut = self.batcher.submit(resident, flat, k)
            if route_gid is not None:
                # per-group load accounting: route() balances launches
                # across a key's replica groups by in-flight count
                self.placement.note_submit(route_gid)
                fut.add_done_callback(
                    lambda _f, g=route_gid: self.placement.note_done(g))
            # the delta chain's packs are extra operands of the SAME
            # lowered query: each delta batches independently (its own
            # micro-batch queue keyed by pack identity) and the columns
            # merge host-side — disjoint row spaces, totals add
            delta_futs = []
            if chain is not None and chain.deltas:
                delta_futs = [self.batcher.submit(d, flat, k)
                              for d in chain.deltas]
            pending = getattr(fut, "pending", None)
            # the batch wait is bounded: the service cap (default 30s —
            # the FIRST batch on a signature pays XLA compile; if it
            # exceeds the cap the query plans instead and the compiled
            # kernel serves later probes) further tightened by the
            # request's own deadline. A stalled kernel must never pin an
            # HTTP thread for minutes.
            wait = self.batch_timeout_s
            deadline_limited = (timeout_s is not None
                                and timeout_s < self.batch_timeout_s)
            if deadline_limited:
                wait = max(0.05, timeout_s)
            result = fut.result(timeout=wait)
            if delta_futs:
                # one SHARED deadline across the union: the base wait
                # already consumed part of it, the deltas get the rest
                deadline = t_sub + wait
                parts = [result]
                for df in delta_futs:
                    remaining = max(0.01, deadline - time.perf_counter())
                    parts.append(df.result(timeout=remaining))
                result = self._union_results(parts, chain, k)
        except FuturesTimeout:
            self.fallback += 1
            self.timeouts += 1
            if deadline_limited:
                # the REQUEST's deadline expired, which says nothing
                # about batcher health — fall back without tripping the
                # node-wide breaker
                self.last_error = "request deadline during kernel batch"
                return None
            # the full service cap elapsed: the batcher may be wedged
            # (stuck XLA compile) — trip the kernel-path breaker so
            # subsequent queries plan immediately
            self._tripped = True
            self._next_probe = time.monotonic() + self.probe_cooldown_s
            self.last_error = "timeout waiting for kernel batch"
            logger.error("tpu kernel batch timed out; tripping kernel "
                         "breaker (probe every %.0fs)", self.probe_cooldown_s)
            return None
        except DeviceWedgedError as exc:
            # typed wedge/teardown failure: the watchdog/supervisor
            # already handled the batcher — just degrade this query
            self.fallback += 1
            self.last_error = f"device_wedged: {exc}"
            return None
        except Exception as exc:  # noqa: BLE001 — degrade, never 500
            self.fallback += 1
            self.last_error = f"{type(exc).__name__}: {exc}"
            logger.exception("tpu kernel path failed; falling back")
            return None
        self._tripped = False  # a completed batch proves the path is live
        self.served += 1
        t_done = time.perf_counter()
        split = self._record_batch_wait_split(pending, t_sub, t_done)
        if profile_sink is not None:
            profile_sink.update({
                "variant": result.variant
                or ("packed" if KERNEL_CONFIG["packed_sort"] else "ref"),
                "plan_cache": plan_outcome,
                "stages_ms": {
                    "lower": round((t1 - t0) * 1e3, 4),
                    "pack_get": round((t2 - t1) * 1e3, 4),
                    "batch_wait": round((t_done - t_sub) * 1e3, 4),
                },
            })
            if split:
                profile_sink["stages_ms"]["batch_wait_split"] = {
                    name: round(dt * 1e3, 4) for name, dt in split.items()}
        return result

    def _record_batch_wait_split(self, pending, t_sub: float,
                                 t_done: float
                                 ) -> Optional[Dict[str, float]]:
        """Record one query's `batch_wait` and its split, in one
        `add_many`, → the split. `queue` (submit → the worker's train
        cycle), `window` (batching window), `dispatch` (host-side
        staging inside launch) and `completion` (launched → the request
        thread running again); `completion` again as `device` (→ its
        launch's results ready), `decode` (→ the train decoded),
        `deliver` (→ its future set, behind the train's other
        deliveries) and `wake` (→ this thread running after
        `fut.result()`). Each is measured from marks the workers stamped
        on the `_Pending`, anchored to the same request-thread clock as
        `batch_wait`, so the first four parts sum to it exactly, by
        construction, and so do the first three with the last four."""
        samples = [("batch_wait", t_done - t_sub)]
        split = None
        if pending is not None:  # a mocked/foreign future carries no marks
            t_c, t_t, t_l = (pending.t_cycle, pending.t_take,
                             pending.t_launched)
            if t_t and t_l:
                split = {
                    "queue": max(0.0, t_c - t_sub),
                    "window": max(0.0, t_t - max(t_sub, t_c)),
                    "dispatch": max(0.0, t_l - t_t),
                    "completion": max(0.0, t_done - t_l),
                }
                t_r, t_d, t_s = (pending.t_ready, pending.t_decoded,
                                 pending.t_set)
                if t_r and t_d and t_s:
                    split["device"] = max(0.0, t_r - t_l)
                    split["decode"] = max(0.0, t_d - t_r)
                    split["deliver"] = max(0.0, t_s - t_d)
                    split["wake"] = max(0.0, t_done - t_s)
                samples += [(_BATCH_WAIT_PARTS[name], dt)
                            for name, dt in split.items()]
        self.stages.add_many(samples)
        return split

    @staticmethod
    def _union_results(parts: List["FlatQueryResult"], chain: PackChain,
                       k: int) -> "FlatQueryResult":
        """Merge base + delta kernel results into one top-k over the
        chain's concatenated row space. The operands score DISJOINT doc
        sets (deltas cover only segments the base doesn't), so totals
        add and no dedup is needed; ties prefer the base pack, then
        in-pack kernel rank (stable across chain growth)."""
        scores, rows, ords = sparse.union_topk(
            [p.scores for p in parts],
            [p.rows for p in parts],
            [p.ords for p in parts],
            chain.view.offsets, k)
        max_score = None
        candidates = [p.max_score for p in parts if p.max_score is not None]
        if candidates:
            max_score = float(max(candidates))
        return FlatQueryResult(
            scores=scores, rows=rows, ords=ords,
            total_hits=sum(int(p.total_hits) for p in parts),
            max_score=max_score,
            resident=chain.view,
            total_relation=("gte" if any(p.total_relation == "gte"
                                         for p in parts) else "eq"),
            variant=parts[0].variant)

    def prewarm(self, index_service, field: str,
                concurrency: Optional[int] = None) -> Dict[str, Any]:
        """Build the (index, field) resident pack and compile every
        steady-state serving signature NOW, instead of on the first
        query (the reference's index-warmer seam, `IndicesWarmer` /
        `index.warmer`: first-compile must not stall or degrade
        production traffic). Returns timing info.

        The signature table is DEDUPED by canonical jit signature
        (batch bucket × candidate-k bucket × width/prefix) — the raw
        k values 10 and 1000 collapse into the same compiled kernel
        whenever they share a candidate bucket — and the compiles run
        on `concurrency` worker threads (XLA compilation releases the
        GIL). Traffic arriving mid-warm degrades to the planner via
        `_warming` instead of stalling a train. With the persistent
        compilation cache this whole pass is cache-replay fast after
        the first-ever run on a machine."""
        t0 = time.perf_counter()
        workers = max(1, concurrency or self.prewarm_concurrency)
        with self._prewarm_lock:
            self._prewarm_progress = {"state": "warming", "total": 0,
                                      "done": 0, "seconds": 0.0}
        self._warming = True
        try:
            replicas: List[ResidentPack] = []
            if self.placement is not None:
                # warm the copies serving will actually use: the routed
                # replica plus every other placed replica (a failover
                # target that is resident-but-cold would compile on its
                # first post-failover hit — exactly the stall the warmer
                # exists to prevent). The legacy full-mesh cache is NOT
                # touched: nothing serves from it under placement.
                resident, _gid = self._grouped_get(index_service, field)
                if resident is not None:
                    key = (index_service.name, field)
                    for g in self.placement.groups_of(key):
                        peek = self.group_caches[g].peek(key)
                        if peek is not None and peek is not resident:
                            replicas.append(peek)
            else:
                resident = self.packs.get(index_service, field)
            t_pack = time.perf_counter() - t0
            compiled: List[Dict[str, Any]] = []
            if resident is not None:
                for r in [resident] + replicas:
                    self._compile_signatures(r, field, compiled,
                                             workers)
            return {"pack_seconds": round(t_pack, 2),
                    "compiled": compiled,
                    "total_seconds": round(time.perf_counter() - t0, 2)}
        finally:
            self._warming = False
            with self._prewarm_lock:
                self._prewarm_progress["state"] = "done"
                self._prewarm_progress["seconds"] = round(
                    time.perf_counter() - t0, 2)

    def prewarm_async(self, index_service, field: str,
                      concurrency: Optional[int] = None) -> threading.Thread:
        """Kick prewarm off the caller's thread (node startup / first
        index of traffic). try_search degrades to the planner until the
        warm completes; progress is visible in stats()["prewarm"]."""
        t = threading.Thread(
            target=lambda: self.prewarm(index_service, field,
                                        concurrency=concurrency),
            daemon=True, name="tpu-prewarm")
        t.start()
        return t

    def _compile_signatures(self, resident: ResidentPack, field: str,
                            compiled: List[Dict[str, Any]],
                            workers: int) -> None:
        # a placement-group replica compiles against its group's
        # sub-mesh — warming it on the full mesh would populate a jit
        # cache serving never reads
        mesh = getattr(resident, "group_mesh", None) or self.packs.mesh
        terms = []
        for v in resident.pack.vocabs:
            if v:
                terms = [next(iter(v))]
                break
        flat = FlatQuery(field, terms or ["_warm_"], 1.0, 1)
        # the full-postings rungs are warmed by a term the pack lacks: a
        # zero-length slot, which fits every rung (a real term may need
        # more slots than the rung it is forced to here)
        fits = FlatQuery(field, ["\x00warm"], 1.0, 1)
        buckets = [8, 64, _serving_bucket(self.batcher.max_batch)]
        buckets = sorted(set(buckets))
        # (batch, k, slots|None, prefix|None). The full-postings rungs at
        # the row buckets they launch at (FULL_ROW_BUCKETS): the first
        # run of one up to FULL_READY_SLOTS compiles the whole of
        # `full_program_set` ahead of time, as serving's first launch
        # does; each member is then run once here, like the rest
        table = []
        for b_bucket in buckets:
            for k in (10, PRUNE_MAX_K):
                for slots in FULL_SLOT_BUCKETS:
                    if b_bucket in FULL_ROW_BUCKETS[slots]:
                        table.append((b_bucket, k, slots, None))
                table.append((b_bucket, k, None, PREFIX_CAP2))
        # the PREFIX_CAP3 escalation runs inline in the batch
        # completer with clients waiting — it must NEVER compile
        # there (a cold compile at multi-million-doc shapes blows
        # the batch timeout and trips the kernel breaker); BOTH
        # k-bucket signatures (k_cand 128 and 2048) are reachable
        for b_bucket in buckets:
            for k in (10, PRUNE_MAX_K):
                table.append((b_bucket, k, None, PREFIX_CAP3))
        # both kernel variants warm when packed sorting is on: "ref"
        # stays reachable (per-launch packability fallback, the runtime
        # toggle) and must never cold-compile inside the batch completer. Pruned kernels never pack their gid keys, so
        # their "packed" variant differs only in the top-k reduction.
        # compressed packs have no impact-sorted copy — the pruned table
        # is unreachable, and the exact kernel runs the compressed pair
        # (`_exact_variants`: both reachable, per-launch weight fallback
        # picks the exact decode variant)
        if resident.comp_streams is not None:
            pruned_variants: Tuple[str, ...] = ()
        elif KERNEL_CONFIG["packed_sort"]:
            pruned_variants = ("packed", "ref")
        else:
            pruned_variants = ("ref",)
        # dedupe to canonical jit signatures: the kernel is compiled per
        # (batch bucket, candidate-k bucket, width|prefix, variant) —
        # requested k values that bucket identically would recompile
        # NOTHING, so warming them again just serializes the warmer
        seen = set()
        jobs = []  # (entry, run)
        for b_bucket, k, slots, cap in table:
            for variant in pruned_variants:
                sig = (b_bucket, _candidate_k(k), slots, cap, variant)
                if sig in seen:
                    continue
                seen.add(sig)
                jobs.append(({"batch": b_bucket, "k": k, "slots": slots,
                              "prefix": cap, "variant": variant},
                             lambda b_bucket=b_bucket, k=k, slots=slots,
                             cap=cap, variant=variant: _execute_pruned(
                                 resident,
                                 [flat if slots is None else fits] * b_bucket,
                                 k, mesh,
                                 prefix_cap=cap or PREFIX_CAP2,
                                 full_slots=slots, variant=variant)))
        # exact kernel: the members of the pack's closed set
        # (`exact_program_set`) that default traffic meets first, msm/AND
        # of few terms and slots (clause counts on) at the small and the
        # mid batch bucket. The set's other members (long queries, hot
        # terms' slot counts, the full bucket) compile on first use,
        # once ever, and persist in the compilation cache.
        for b_bucket, k in ((8, 10), (64, PRUNE_MAX_K)):
            for program in exact_program_set(
                    resident, k, max_batch=b_bucket,
                    max_terms=PRUNE_MAX_TERMS, max_slots=EXACT_MIN_SLOTS):
                if program.rows != b_bucket or not program.with_counts:
                    continue
                jobs.append(({"batch": b_bucket, "k": k, "exact": True,
                              "variant": program.variant,
                              "program": program.label},
                             lambda program=program: run_exact_program(
                                 resident, program, field, mesh)))
        with self._prewarm_lock:
            self._prewarm_progress["total"] += len(jobs)
        # prewarm is BEST-EFFORT per signature: one kernel that the
        # backend cannot compile at this pack's shapes (observed: the
        # compile helper dying on the exact kernel at MS-MARCO scale)
        # must not abort the warmer — serving degrades that one path to
        # the planner, the rest stay kernel-served. A run of failures
        # (>= 3 with no success in between) is systemic: skip the rest.
        fail_lock = threading.Lock()
        consecutive_failures = [0]

        def warm_one(entry, run):
            with fail_lock:
                if consecutive_failures[0] >= 3:
                    entry["error"] = "skipped: systemic prewarm failure"
                    compiled.append(entry)
                    with self._prewarm_lock:
                        self._prewarm_progress["done"] += 1
                    return
            t1 = time.perf_counter()
            try:
                run()
                with fail_lock:
                    consecutive_failures[0] = 0
            except Exception as exc:  # noqa: BLE001 — record, go on
                entry["error"] = f"{type(exc).__name__}: {exc}"[:160]
                with fail_lock:
                    consecutive_failures[0] += 1
                logger.warning("prewarm %s failed: %s", entry, exc)
            finally:
                # failures carry their cost too (a 90s compile that
                # dies is exactly what the warmer must surface)
                entry["seconds"] = round(time.perf_counter() - t1, 2)
            compiled.append(entry)
            with self._prewarm_lock:
                self._prewarm_progress["done"] += 1

        if workers <= 1 or len(jobs) <= 1:
            for entry, run in jobs:
                warm_one(entry, run)
            return
        with ThreadPoolExecutor(max_workers=workers,
                                thread_name_prefix="tpu-prewarm") as pool:
            futs = [pool.submit(warm_one, entry, run)
                    for entry, run in jobs]
            for f in futs:
                f.result()

    def stats(self) -> Dict[str, Any]:
        with self._prewarm_lock:
            prewarm = dict(self._prewarm_progress)
        d_packs, d_bytes = self.packs.delta_totals()
        ds = self.delta_stats
        return {"served": self.served, "fallback": self.fallback,
                "timeouts": self.timeouts, "tripped": self._tripped,
                "last_error": self.last_error,
                "batches": self.batcher.batches_executed,
                "batched_queries": self.batcher.queries_executed,
                "plan_cache": self.plans.stats(),
                "pack_cache": self.packs.stats(),
                "deltas": {"enabled": self.packs.delta_enabled,
                           "packs": d_packs, "bytes": d_bytes,
                           "appends": ds.appends, "seals": ds.seals,
                           "compactions": ds.compactions,
                           "compaction_failures": ds.compaction_failures,
                           "replayed_ops": ds.replayed_ops,
                           "compact_seconds": round(ds.compact_seconds, 4)},
                "prewarm": prewarm,
                "kernel": {"packed_sort": KERNEL_CONFIG["packed_sort"],
                           "compressed_pack":
                               KERNEL_CONFIG["compressed_pack"],
                           "variants": KERNEL_VARIANT_COUNTS.counts()},
                "launches": LAUNCH_COUNTS.counts(),
                "route": ROUTE_COUNTS.counts(),
                "hold_exit": HOLD_EXIT_COUNTS.counts(),
                "exact_entries": EXACT_ENTRY_COUNTS.counts(),
                "full_entries": FULL_ENTRY_COUNTS.counts(),
                "cross_chip": CROSS_CHIP_COUNTS.counts(),
                "exact_pin": EXACT_PIN_COUNTS.counts(),
                "term_table": dist.TERM_TABLE_COUNTS.counts(),
                "operands": OPERAND_COUNTS.counts(),
                "exact_results": EXACT_RESULT_COUNTS.counts(),
                "heads": HEADS_COUNTS.counts(),
                "exact_programs": self.exact_programs(),
                "full_programs": self.full_programs(),
                "heads_programs": self.heads_programs(),
                "render": RENDER_COUNTS.counts(),
                "fetch": FETCH_COUNTS.counts(),
                "queue": self.batcher.queue_depths(),
                "supervision": self.supervisor.stats(),
                "watchdog": self.watchdog.stats(),
                "devices": self.device_stats(),
                "stages": self.stages.snapshot()}

    def _programs(self, program_set, k: int) -> Dict[str, List[str]]:
        caches = [self.packs] + list(getattr(self, "group_caches", {}).values())
        out: Dict[str, List[str]] = {}
        for cache in caches:
            for key in cache.resident_keys():
                resident = cache.peek(key)
                if resident is not None:
                    out[f"{key[0]}/{key[1]}"] = sorted({
                        p.label for p in program_set(
                            resident, k, self.batcher.max_batch)})
        return out

    def exact_programs(self, k: int = PRUNE_MAX_K) -> Dict[str, List[str]]:
        """The /_tpu/stats `exact_programs` block: for each resident
        pack, the names of the exact kernel's programs that serving can
        compile on it at `k` (`exact_program_set`; a name stands for its
        members with and without clause counts)."""
        return self._programs(exact_program_set, k)

    def full_programs(self, k: int = PRUNE_MAX_K) -> Dict[str, List[str]]:
        """The /_tpu/stats `full_programs` block: for each resident pack,
        the full-postings programs that its first full-path launch at
        `k` compiles before it is answered (`full_program_set`)."""
        return self._programs(full_program_set, k)

    def heads_programs(self, k: int = PRUNE_MAX_K) -> Dict[str, List[str]]:
        """The /_tpu/stats `heads_programs` block: for each resident pack,
        the heads programs its first launch on the heads route at `k`
        submits for compilation (`heads_program_set`; none where the pack
        has no head term)."""
        return self._programs(heads_program_set, k)

    def device_stats(self) -> Dict[str, Any]:
        """The /_tpu/stats `devices` block: the device stamp (platform,
        device_kind, versions), health registry view plus the
        supervisor's mesh topology and shed set."""
        sup = self.supervisor
        out: Dict[str, Any] = {
            **self._device_stamp,
            "mesh_devices": sup.mesh_device_count,
            "mesh_devices_full": sup.full_device_count,
            "remeshes": sup.c_remeshes.count,
            "last_remesh_duration_seconds":
                round(sup.last_remesh_duration_s, 4),
            "shed_packs": [f"{i}/{f}" for i, f in self.shed_keys()],
            "degraded": self.degraded_info,
        }
        if self.health is not None:
            out["health"] = self.health.stats()
        if self.placement is not None:
            placement = self.placement.stats()
            with self._placement_lock:
                placement["failed_over"] = {
                    f"{i}/{f}": dict(info)
                    for (i, f), info in self._failed_over.items()}
            placement["group_packs"] = {
                str(gid): cache.resident_keys()
                for gid, cache in sorted(self.group_caches.items())}
            out["placement"] = placement
        return out

    def close(self) -> None:
        self._compact_closed = True
        self._compact_wakeup.set()
        t = self._compact_thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)
        self.watchdog.close()
        if self.health is not None:
            self.health.close()
        self.batcher.close()


_cache_configured = False


def _ensure_compile_cache(path: Optional[str] = None) -> None:
    """Persistent XLA compilation cache: a process restart replays every
    serving-kernel compile instead of paying it again. Where
    JAX_COMPILATION_CACHE_DIR is set jax reads the directory from it and
    none is set here; otherwise the caller's `path` (a node passes
    `search.tpu_serving.compile_cache_dir`), else `<checkout>/.jax_cache`.
    First caller wins — jax holds ONE cache dir per process."""
    global _cache_configured
    if _cache_configured:
        return
    _cache_configured = True
    import os

    import jax

    # shared with the seed_compile_cache exporter/importer so "the dir
    # the node compiles into" and "the dir the seeder packs/unpacks"
    # can never drift apart
    from elasticsearch_tpu.tools.seed_compile_cache import (
        CACHE_DIR_ENV, compile_cache_dir)
    if not os.environ.get(CACHE_DIR_ENV):
        path = compile_cache_dir(path)
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as exc:  # read-only checkout: serve uncached
            logger.warning("persistent compile cache unavailable: %s", exc)
            return
        jax.config.update("jax_compilation_cache_dir", path)
    # persist anything over ~100ms: at small corpus scales individual
    # serving signatures compile in 0.3-0.9s but the full prewarm
    # table of them still costs minutes — all of it cacheable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
