"""Query planner/executor: DSL AST → kernel programs per segment.

Reference analog: index/query/QueryShardContext#toQuery + the per-segment
execution in search/query/QueryPhase#executeInternal (SURVEY.md §3.3). The
reference walks postings doc-at-a-time through BooleanScorer/ConjunctionDISI;
here every node of the query tree evaluates densely over the segment's
padded doc axis:

  node → (match_mask bool[d_pad], score f32[d_pad])

with the invariant that `score` is already zeroed outside `match_mask`.
Parent nodes combine children by mask algebra + score addition, which
reproduces Lucene's boolean scoring semantics (sum of matched scoring
clauses) without per-doc control flow — and makes nested conjunctive
subtrees in should-context safe by construction (SURVEY.md §7.3#7).

Scoring leaves launch one score_and_mask kernel per leaf (terms padded to
power-of-two buckets to bound the jit cache, §7.3#1). Phrase verification
is host-side over the candidate docs (postings positions live on host).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.common.errors import QueryShardException
from elasticsearch_tpu.index.reader import SegmentView, ShardReader
from elasticsearch_tpu.index.segment import MISSING_I64
from elasticsearch_tpu.mapping.types import (
    FieldType,
    IpFieldType,
    KeywordFieldType,
    NumberFieldType,
    RangeFieldType,
    TextFieldType,
)
from elasticsearch_tpu.ops import bm25, sparse
from elasticsearch_tpu.ops.smallfloat import bm25_norm_cache
from elasticsearch_tpu.search import dsl
# re-exported for batcher-side callers; the implementation lives in the
# import-light plan_sig module because the serving-front processes (which
# must never pull in JAX) sign request bodies with the same function
from elasticsearch_tpu.search.plan_sig import (  # noqa: F401
    canonical_body, wire_plan_signature)

MAX_SLOTS_PER_PASS = 32


def choose_kernel_variant(d_pad: int,
                          weights: Optional[np.ndarray] = None,
                          enabled: bool = True,
                          compressed: bool = False) -> str:
    """Pick the device-kernel variant for one lowered pack/batch.

    Lowering-time decision: "packed" — the single
    uint32-key sort + hierarchical top-k + exact-f32 rescore — whenever
    the pack's doc axis and the batch's slot weights fit the 16-bit
    packed layout (sparse.packable); otherwise the exact-f32 reference
    kernel. The fallback conditions are the documented overflow cases:
    d_pad ≥ 2^16 chunk-local doc ids, non-finite/negative weights, or
    weight magnitudes outside [1e-12, 1e30] (where the monotone 16-bit
    impact code could turn a positive contribution into code 0 and
    perturb TotalHits).

    compressed=True (the resident pack holds only the 16-bit streams):
    the same packable() predicate decides between
    "compressed" (quantized sort keys + block-max pruning, needs the
    monotone lower-bound guarantee on weights) and "compressed_exact"
    (per-lane residual-table decode then the exact-f32 pipeline — the
    automatic fallback for weights that would violate the bound). A
    compressed pack has no f32 posting copy, so "ref"/"packed" are not
    reachable from it. Never errors."""
    if compressed:
        if sparse.packable(d_pad, weights):
            return "compressed"
        return "compressed_exact"
    if enabled and sparse.packable(d_pad, weights):
        return "packed"
    return "ref"


def _edit_distance_lte(a: str, b: str, k: int) -> bool:
    """Damerau-Levenshtein (adjacent transposition = 1) ≤ k, banded with
    early exit (reference: Lucene's LevenshteinAutomata accept set for
    fuzziness ≤ 2)."""
    if k == 0:
        return a == b
    if abs(len(a) - len(b)) > k:
        return False
    prev2: Optional[List[int]] = None
    prev = list(range(len(b) + 1))
    for i in range(1, len(a) + 1):
        cur = [i] + [0] * len(b)
        row_min = i
        for j in range(1, len(b) + 1):
            cost = 0 if a[i - 1] == b[j - 1] else 1
            d = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + cost)
            if (prev2 is not None and i > 1 and j > 1
                    and a[i - 1] == b[j - 2] and a[i - 2] == b[j - 1]):
                d = min(d, prev2[j - 2] + 1)
            cur[j] = d
            row_min = min(row_min, d)
        if row_min > k:
            return False
        prev2, prev = prev, cur
    return prev[len(b)] <= k


def _bucket(n: int, minimum: int = 1) -> int:
    """Round up to a power of two (jit-cache bounding, SURVEY.md §7.3#1)."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _analyzed_terms(ft, text) -> list:
    """Analyze `text` through the field's search analyzer, memoized on
    the FieldType instance. One query over an index re-analyzes the same
    string once per segment view (and repeated query shapes re-analyze
    it once per request); the memo collapses that to one analyzer run.
    It lives on the FieldType, so a mapping update (which swaps the
    FieldType) naturally drops it. Returns a fresh list — callers may
    mutate their copy."""
    text = str(text)
    memo = getattr(ft, "_terms_memo", None)
    if memo is None:
        memo = {}
        try:
            ft._terms_memo = memo
        except AttributeError:  # slotted/frozen field type: no memo
            return ft.search_terms(text)
    hit = memo.get(text)
    if hit is None:
        hit = ft.search_terms(text)
        if len(memo) < 4096:  # bound pathological query cardinality
            memo[text] = hit
    return list(hit)


class SegmentQueryExecutor:
    """Evaluates one parsed query against one segment view."""

    def __init__(self, reader: ShardReader, view_idx: int):
        self.reader = reader
        self.view_idx = view_idx
        self.view: SegmentView = reader.views[view_idx]
        self.d_pad = self.view.pack.d_pad

    # -------------- public --------------

    def execute(self, node: dsl.QueryNode) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """→ (mask bool[d_pad], score f32[d_pad]); score zero off-mask."""
        return self._eval(node, scoring=True)

    # -------------- recursive eval --------------

    def _eval(self, node: dsl.QueryNode, scoring: bool):
        if isinstance(node, dsl.MatchAllQuery):
            mask = jnp.ones(self.d_pad, dtype=bool)
            score = jnp.full(self.d_pad, node.boost if scoring else 0.0,
                             dtype=jnp.float32)
            return mask, score
        if isinstance(node, dsl.MatchQuery):
            return self._eval_match(node, scoring)
        if isinstance(node, dsl.TermQuery):
            try:
                ft = self._field_type(node.field)
            except _UnmappedField:
                ft = None
            if isinstance(ft, IpFieldType) and "/" in str(node.value):
                # CIDR term → address range (reference: IpFieldMapper
                # term queries accept networks)
                lo, hi = IpFieldType.cidr_bounds(node.value)
                return self._eval_ip_range(node.field, lo, hi, node.boost)
            if isinstance(ft, RangeFieldType):
                v = ft.parse_bound(node.value)
                return self._eval_range_field(
                    dsl.RangeQuery(field=node.field, gte=v, lte=v,
                                   boost=node.boost), ft)
            return self._eval_terms(node.field, [node.value], node.boost,
                                    scoring, "or", 1)
        if isinstance(node, dsl.TermsQuery):
            return self._eval_terms(node.field, node.values, node.boost,
                                    scoring, "or", 1)
        if isinstance(node, dsl.RangeQuery):
            return self._eval_range(node)
        if isinstance(node, dsl.ExistsQuery):
            mask = jnp.asarray(self.reader.has_field_mask(self.view_idx, node.field))
            return mask, jnp.where(mask, node.boost if scoring else 0.0, 0.0).astype(jnp.float32)
        if isinstance(node, dsl.IdsQuery):
            mask = jnp.asarray(self.reader.resolve_ids(self.view_idx, node.values))
            return mask, jnp.where(mask, node.boost if scoring else 0.0, 0.0).astype(jnp.float32)
        if isinstance(node, dsl.MatchPhraseQuery):
            return self._eval_phrase(node, scoring)
        if isinstance(node, dsl.ConstantScoreQuery):
            mask, _ = self._eval(node.filter_query, scoring=False)
            return mask, jnp.where(mask, node.boost if scoring else 0.0, 0.0).astype(jnp.float32)
        if isinstance(node, dsl.BoolQuery):
            return self._eval_bool(node, scoring)
        if isinstance(node, dsl.MultiMatchQuery):
            return self._eval_multi_match(node, scoring)
        if isinstance(node, dsl.PrefixQuery):
            return self._eval_expanded_terms(
                node.field, self._expand_prefix(node.field, node.value),
                node.boost, scoring, constant=True)
        if isinstance(node, dsl.WildcardQuery):
            return self._eval_expanded_terms(
                node.field, self._expand_wildcard(node), node.boost,
                scoring, constant=True)
        if isinstance(node, dsl.FuzzyQuery):
            return self._eval_expanded_terms(
                node.field, self._expand_fuzzy(node), node.boost,
                scoring, constant=False)
        if isinstance(node, dsl.FunctionScoreQuery):
            return self._eval_function_score(node, scoring)
        if isinstance(node, dsl.ScriptScoreQuery):
            return self._eval_script_score(node, scoring)
        if isinstance(node, dsl.KnnScoreDocQuery):
            return self._eval_knn_score_doc(node, scoring)
        if isinstance(node, dsl.RankFeatureQuery):
            return self._eval_rank_feature(node, scoring)
        if isinstance(node, dsl.GeoDistanceQuery):
            return self._eval_geo_distance(node)
        if isinstance(node, dsl.GeoBoundingBoxQuery):
            return self._eval_geo_bbox(node)
        if isinstance(node, dsl.NestedQuery):
            return self._eval_nested(node, scoring)
        if isinstance(node, dsl.PercolateQuery):
            return self._eval_percolate(node, scoring)
        if hasattr(node, "evaluate"):
            # plugin-registered query types evaluate themselves against
            # the executor (SearchPlugin#getQueries seam)
            return node.evaluate(self, scoring)
        raise QueryShardException(f"unsupported query [{node.query_name()}]")

    def _eval_multi_match(self, node: dsl.MultiMatchQuery, scoring: bool):
        """best_fields: per doc, the best field's score (+ tie_breaker ×
        the rest); most_fields: sum. Mask is the OR of the field masks
        (reference: DisjunctionMaxQuery vs a should-bool)."""
        per_field = []
        for field, fboost in node.fields:
            sub = dsl.MatchQuery(
                field=field, query=node.query, operator=node.operator,
                minimum_should_match=node.minimum_should_match,
                boost=fboost)
            per_field.append(self._eval_match(sub, scoring))
        if not per_field:
            return self._none()
        mask = per_field[0][0]
        for m, _ in per_field[1:]:
            mask = mask | m
        scores = jnp.stack([s for _, s in per_field])
        if node.type == "most_fields":
            score = jnp.sum(scores, axis=0)
        else:  # best_fields
            best = jnp.max(scores, axis=0)
            score = best + node.tie_breaker * (jnp.sum(scores, axis=0)
                                               - best)
        score = jnp.where(mask, score * node.boost, 0.0)
        return mask, score

    # ---- multi-term expansion (reference: MultiTermQuery rewrites) ----

    _MAX_EXPANSIONS = 1024  # reference: indices.query.bool.max_clause_count

    def _field_vocab(self, field: str):
        fp = self.view.pack.fields.get(field)
        return fp.vocab if fp is not None else {}

    def _expand_prefix(self, field: str, prefix: str) -> List[str]:
        terms = [t for t in self._field_vocab(field)
                 if t.startswith(prefix)]
        self._check_expansion(terms, "prefix")
        return terms

    def _expand_wildcard(self, node: dsl.WildcardQuery) -> List[str]:
        import fnmatch
        pattern = node.value.lower() if node.case_insensitive \
            else node.value
        # fnmatchcase: only * and ? are wildcards in the reference
        # grammar; [] must match literally
        pattern = pattern.replace("[", "[[]")
        out = []
        for t in self._field_vocab(node.field):
            probe = t.lower() if node.case_insensitive else t
            if fnmatch.fnmatchcase(probe, pattern):
                out.append(t)
        self._check_expansion(out, "wildcard")
        return out

    def _expand_fuzzy(self, node: dsl.FuzzyQuery) -> List[str]:
        value = node.value
        if node.fuzziness == "AUTO" or (
                isinstance(node.fuzziness, str)):
            n = len(value)
            max_d = 0 if n < 3 else (1 if n < 6 else 2)
        else:
            max_d = int(node.fuzziness)
        pl = node.prefix_length
        prefix = value[:pl]
        out = []
        for t in self._field_vocab(node.field):
            if abs(len(t) - len(value)) > max_d:
                continue
            if pl and not t.startswith(prefix):
                continue
            if _edit_distance_lte(value, t, max_d):
                out.append(t)
            if len(out) >= node.max_expansions:
                break
        return out

    def _check_expansion(self, terms: List[str], kind: str) -> None:
        if len(terms) > self._MAX_EXPANSIONS:
            raise QueryShardException(
                f"[{kind}] query expands to {len(terms)} terms, more "
                f"than the {self._MAX_EXPANSIONS} clause limit")

    def _eval_expanded_terms(self, field: str, terms: List[str],
                             boost: float, scoring: bool, *,
                             constant: bool):
        """OR over an expanded term set. constant=True → the reference's
        constant-score rewrite (prefix/wildcard score = boost); else
        BM25-scored like a terms disjunction (fuzzy)."""
        if not terms:
            return self._none()
        mask, score = self._eval_terms(field, terms, boost,
                                       scoring and not constant, "or", 1,
                                       pre_analyzed=True)
        if constant and scoring:
            score = jnp.where(mask, boost, 0.0).astype(jnp.float32)
        return mask, score

    def _eval_function_score(self, node: dsl.FunctionScoreQuery,
                             scoring: bool):
        mask, score = self._eval(node.query, scoring)
        if not scoring:
            return mask, score
        if not node.functions:
            # max_boost only caps function output; with no functions the
            # query-level boost still applies
            return mask, jnp.where(mask, score * node.boost, 0.0)
        factors = []
        applies = []   # per function: which docs its filter matches
        for fn in node.functions:
            factor = jnp.ones(self.d_pad, dtype=jnp.float32)
            if fn.field_value_factor is not None:
                factor = factor * self._field_value_factor(
                    fn.field_value_factor)
            if fn.script_score is not None:
                factor = factor * self._run_score_script(
                    fn.script_score, score)
            if fn.weight is not None:
                factor = factor * fn.weight
            if fn.filter_query is not None:
                fmask, _ = self._eval(fn.filter_query, scoring=False)
            else:
                fmask = jnp.ones(self.d_pad, dtype=bool)
            factors.append(factor)
            applies.append(fmask)
        stacked = jnp.stack(factors)
        applied = jnp.stack(applies)
        n_applied = jnp.sum(applied, axis=0)
        # only MATCHING functions combine (reference:
        # FunctionScoreQuery#score — non-matching functions are absent
        # from the combination, and a doc matching none scores neutral 1)
        if node.score_mode == "multiply":
            combined = jnp.prod(jnp.where(applied, stacked, 1.0), axis=0)
        elif node.score_mode == "sum":
            combined = jnp.sum(jnp.where(applied, stacked, 0.0), axis=0)
        elif node.score_mode == "avg":
            combined = (jnp.sum(jnp.where(applied, stacked, 0.0), axis=0)
                        / jnp.maximum(n_applied, 1))
        elif node.score_mode == "max":
            combined = jnp.max(
                jnp.where(applied, stacked, -jnp.inf), axis=0)
        else:  # min
            combined = jnp.min(
                jnp.where(applied, stacked, jnp.inf), axis=0)
        combined = jnp.where(n_applied > 0, combined, 1.0)
        if node.max_boost is not None:
            combined = jnp.minimum(combined, node.max_boost)
        if node.boost_mode == "multiply":
            final = score * combined
        elif node.boost_mode == "sum":
            final = score + combined
        elif node.boost_mode == "replace":
            final = combined
        elif node.boost_mode == "avg":
            final = (score + combined) / 2.0
        elif node.boost_mode == "max":
            final = jnp.maximum(score, combined)
        else:  # min
            final = jnp.minimum(score, combined)
        return mask, jnp.where(mask, final * node.boost, 0.0)

    def _eval_knn_score_doc(self, node: dsl.KnnScoreDocQuery,
                            scoring: bool):
        """Union of the base query with pinned knn winners: a doc
        matches if the query matches OR it is a knn winner; its score
        is query_score + Σ knn_score·boost (reference hybrid rule)."""
        seg_name = self.view.segment.name
        knn_mask = np.zeros(self.d_pad, dtype=bool)
        knn_score = np.zeros(self.d_pad, dtype=np.float32)
        for doc_set, boost in zip(node.doc_sets, node.boosts):
            entry = doc_set.get(seg_name)
            if entry is None:
                continue
            ords, scores = entry
            knn_mask[ords] = True
            knn_score[ords] += scores * boost
        kmask = jnp.asarray(knn_mask)
        kscore = jnp.asarray(knn_score)
        if node.query is None:
            return kmask, (kscore if scoring
                           else jnp.zeros_like(kscore))
        bmask, bscore = self._eval(node.query, scoring)
        mask = bmask | kmask
        if not scoring:
            return mask, jnp.zeros_like(kscore)
        return mask, jnp.where(bmask, bscore, 0.0) + kscore

    def _eval_rank_feature(self, node: dsl.RankFeatureQuery,
                           scoring: bool):
        """Feature-value scoring on the f64 column (reference:
        RankFeatureQuery; the impact-postings trick becomes plain
        column math on device). Missing docs don't match."""
        vals, present = self._dv_column(node.field)
        mask = present
        if not scoring:
            return mask, jnp.zeros(self.d_pad, dtype=jnp.float32)
        from elasticsearch_tpu.mapping.types import RankFeatureFieldType
        ft = self.reader.mapper.field_type(node.field)
        if ft is not None and isinstance(ft, RankFeatureFieldType) \
                and not ft.positive_score_impact:
            # negative impact: smaller values score higher — the
            # reference inverts inside the same saturation shape
            vals = jnp.where(present, 1.0 / jnp.maximum(vals, 1e-9),
                             0.0)
        x = jnp.where(present, vals, 0.0).astype(jnp.float32)
        if node.function == "linear":
            score = x
        elif node.function == "log":
            score = jnp.log(jnp.maximum(
                node.scaling_factor + x, 1e-9))
        elif node.function == "sigmoid":
            xp = jnp.power(x, node.exponent)
            score = xp / (xp + jnp.power(node.pivot, node.exponent))
        else:  # saturation
            pivot = node.pivot
            if pivot is None:
                # index-derived default pivot: geometric mean of the
                # shard's feature values (reference computes an
                # approximate geometric mean from the impacts)
                pivot = self._rank_feature_default_pivot(node.field)
            score = x / (x + pivot)
        return mask, jnp.where(mask, score * node.boost,
                               0.0).astype(jnp.float32)

    def _rank_feature_default_pivot(self, field: str) -> float:
        cache = getattr(self.reader, "_rf_pivot_cache", None)
        if cache is None:
            cache = {}
            self.reader._rf_pivot_cache = cache
        if field in cache:
            return cache[field]
        logs, count = 0.0, 0
        for v in self.reader.views:
            col = v.segment.doc_values.get(field)
            if col is None or col.kind != "f64":
                continue
            vals = col.values
            ok = ~np.isnan(vals) & (vals > 0)
            if ok.any():
                logs += float(np.log(vals[ok]).sum())
                count += int(ok.sum())
        pivot = float(np.exp(logs / count)) if count else 1.0
        cache[field] = pivot
        return pivot

    _EARTH_R_M = 6371008.7714  # mean earth radius, as Lucene uses

    def _geo_columns(self, field: str):
        from elasticsearch_tpu.mapping.types import GeoPointFieldType
        pack = self.view.pack
        lat = pack.dv_f64.get(field + GeoPointFieldType.LAT_SUFFIX)
        lon = pack.dv_f64.get(field + GeoPointFieldType.LON_SUFFIX)
        if lat is None or lon is None:
            return None, None, jnp.zeros(self.d_pad, dtype=bool)
        lat = jnp.asarray(lat)
        lon = jnp.asarray(lon)
        present = ~jnp.isnan(lat)
        return lat, lon, present

    def _eval_geo_distance(self, node: dsl.GeoDistanceQuery):
        """Vectorized haversine over the segment's lat/lon columns —
        one fused elementwise pass (no BKD tree)."""
        lat, lon, present = self._geo_columns(node.field)
        if lat is None:
            return self._none()
        rad = jnp.pi / 180.0
        dlat = (lat - node.lat) * rad
        dlon = (lon - node.lon) * rad
        a = jnp.sin(dlat / 2) ** 2 + jnp.cos(lat * rad) * \
            jnp.cos(node.lat * rad) * jnp.sin(dlon / 2) ** 2
        dist = 2 * self._EARTH_R_M * jnp.arcsin(
            jnp.sqrt(jnp.clip(a, 0.0, 1.0)))
        mask = present & (dist <= node.distance_m)
        return mask, jnp.where(mask, node.boost, 0.0).astype(jnp.float32)

    def _eval_geo_bbox(self, node: dsl.GeoBoundingBoxQuery):
        lat, lon, present = self._geo_columns(node.field)
        if lat is None:
            return self._none()
        lat_ok = (lat <= node.top) & (lat >= node.bottom)
        if node.left <= node.right:
            lon_ok = (lon >= node.left) & (lon <= node.right)
        else:
            # box crossing the antimeridian (reference behavior)
            lon_ok = (lon >= node.left) | (lon <= node.right)
        mask = present & lat_ok & lon_ok
        return mask, jnp.where(mask, node.boost, 0.0).astype(jnp.float32)

    def _eval_percolate(self, node: dsl.PercolateQuery, scoring: bool):
        """Evaluate every live stored query of this segment against the
        percolated document(s) (search/percolator.py; reference:
        PercolateQuery with MemoryIndex verification — here without
        the term-extraction pre-filter, see module docstring). Score =
        boost for matching stored queries (the reference scores 1.0
        filter-style unless the inner query scores)."""
        from elasticsearch_tpu.search import percolator as perc
        ft = self.reader.mapper.field_type(node.field)
        from elasticsearch_tpu.mapping.types import PercolatorFieldType
        if ft is None or not isinstance(ft, PercolatorFieldType):
            raise QueryShardException(
                f"[percolate] field [{node.field}] is not a "
                f"[percolator] field")
        # one tiny in-memory index of the documents per REQUEST, keyed
        # by the index's mapper (a multi-index search re-parses the
        # documents per index — each index's own analyzers/types apply)
        readers = getattr(node, "_doc_readers", None)
        if readers is None:
            readers = {}
            node._doc_readers = readers
        cached = readers.get(id(self.reader.mapper))
        if cached is None:
            cached = perc.build_doc_reader(self.reader.mapper,
                                           node.documents)
            readers[id(self.reader.mapper)] = cached
        queries = perc.segment_parsed_queries(self.view.segment,
                                              node.field)
        doc_exec = SegmentQueryExecutor(cached, 0)
        doc_live = cached.views[0].live_mask
        live = self.view.live_mask  # skip tombstoned stored queries
        mask = np.zeros(self.d_pad, dtype=bool)
        for ord_, q in queries.items():
            if not live[ord_]:
                continue
            try:
                qmask, _ = doc_exec._eval(q, scoring=False)
            except Exception:  # noqa: BLE001 — one poisonous stored
                continue  # query (e.g. type mismatch vs the document's
                #           dynamic fields) must not break the search
            if bool((np.asarray(qmask)[: len(doc_live)]
                     & doc_live).any()):
                mask[ord_] = True
        m = jnp.asarray(mask)
        score = jnp.where(m, node.boost if scoring else 0.0,
                          0.0).astype(jnp.float32)
        return m, score

    def _dv_column(self, field: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Numeric doc-values column → (values_f32, present_mask); the
        one extraction both score scripts and field_value_factor use."""
        pack = self.view.pack
        if field in pack.dv_f64:
            vals = jnp.asarray(pack.dv_f64[field], dtype=jnp.float32)
            present = ~jnp.isnan(vals)
        elif field in pack.dv_i64:
            raw = pack.dv_i64[field]
            present = jnp.asarray(raw != MISSING_I64)
            vals = jnp.asarray(raw, dtype=jnp.float32)
        else:
            present = jnp.zeros(self.d_pad, dtype=bool)
            vals = jnp.zeros(self.d_pad, dtype=jnp.float32)
        return vals, present

    def _script_resolver(self, field: str):
        """doc['field'] in a score script → FieldColumn over this
        view's doc-values (numeric; missing = 0 with .empty mask —
        lang-expression semantics, see script module docstring)."""
        from elasticsearch_tpu.script import FieldColumn
        vals, present = self._dv_column(field)
        return FieldColumn(jnp.where(present, vals, 0.0), present)

    def _vec_column(self, field: str) -> jnp.ndarray:
        """dense_vector matrix f32[d_pad, dims] for score scripts
        (cosineSimilarity et al.); unknown field → 400."""
        mat = self.view.pack.dv_vec.get(field)
        if mat is None:
            from elasticsearch_tpu.script import ScriptException
            raise ScriptException(
                f"[{field}] is not a dense_vector field")
        return jnp.asarray(mat)

    def _run_score_script(self, script, base_score) -> jnp.ndarray:
        from elasticsearch_tpu.script import ScriptException
        try:
            return script.score_vector(self._script_resolver, base_score,
                                       vec_resolver=self._vec_column)
        except ScriptException:
            raise
        except Exception as e:  # noqa: BLE001 — surface as a 400
            from elasticsearch_tpu.script import ScriptException as SE
            raise SE(f"runtime error in score script "
                     f"[{script.source[:80]}]: {e}") from None

    def _eval_script_score(self, node: dsl.ScriptScoreQuery,
                           scoring: bool):
        # min_score prunes MATCHES, so it must run even in filter
        # context (a filter-placed script_score matches the same docs
        # as a query-placed one)
        needs_script = scoring or node.min_score is not None
        mask, score = self._eval(node.query, scoring or needs_script)
        if not needs_script:
            return mask, score
        scripted = self._run_score_script(node.script, score)
        # the reference rejects negative script scores (since 7.x)
        scripted = jnp.maximum(scripted, 0.0)
        if node.min_score is not None:
            mask = mask & (scripted >= node.min_score)
        if not scoring:
            return mask, jnp.zeros_like(scripted)
        return mask, jnp.where(mask, scripted * node.boost,
                               0.0).astype(jnp.float32)

    def _field_value_factor(self, fvf: dict) -> jnp.ndarray:
        """Per-doc factor from a doc-values column (reference:
        FieldValueFactorFunction)."""
        field = fvf["field"]
        factor = float(fvf.get("factor", 1.0))
        missing = fvf.get("missing")
        vals, present = self._dv_column(field)
        if missing is None:
            # the reference errors on missing values without [missing];
            # a dense kernel can't throw per-doc, so treat as 0
            fill = 0.0
        else:
            fill = float(missing)
        vals = jnp.where(present, vals, fill) * factor
        mod = fvf.get("modifier", "none")
        if mod == "log":
            vals = jnp.where(vals > 0, jnp.log10(jnp.maximum(vals, 1e-9)),
                             0.0)
        elif mod == "log1p":
            vals = jnp.log10(jnp.maximum(vals, 0.0) + 1.0)
        elif mod == "log2p":
            vals = jnp.log10(jnp.maximum(vals, 0.0) + 2.0)
        elif mod == "ln":
            vals = jnp.where(vals > 0, jnp.log(jnp.maximum(vals, 1e-9)),
                             0.0)
        elif mod == "ln1p":
            vals = jnp.log(jnp.maximum(vals, 0.0) + 1.0)
        elif mod == "ln2p":
            vals = jnp.log(jnp.maximum(vals, 0.0) + 2.0)
        elif mod == "square":
            vals = vals * vals
        elif mod == "sqrt":
            vals = jnp.sqrt(jnp.maximum(vals, 0.0))
        elif mod == "reciprocal":
            vals = jnp.where(vals != 0, 1.0 / vals, 0.0)
        return vals.astype(jnp.float32)

    def _eval_bool(self, node: dsl.BoolQuery, scoring: bool):
        mask = jnp.ones(self.d_pad, dtype=bool)
        score = jnp.zeros(self.d_pad, dtype=jnp.float32)
        for child in node.must:
            cmask, cscore = self._eval(child, scoring)
            mask = mask & cmask
            score = score + cscore
        for child in node.filter:
            cmask, _ = self._eval(child, scoring=False)
            mask = mask & cmask
        for child in node.must_not:
            cmask, _ = self._eval(child, scoring=False)
            mask = mask & ~cmask
        if node.should:
            msm = node.minimum_should_match
            if msm is None:
                # the reference default: 1 when there is nothing mandatory,
                # else 0 (should becomes purely score-boosting)
                msm = 0 if (node.must or node.filter) else 1
            count = jnp.zeros(self.d_pad, dtype=jnp.int32)
            for child in node.should:
                cmask, cscore = self._eval(child, scoring)
                count = count + cmask.astype(jnp.int32)
                score = score + cscore
            if msm > 0:
                mask = mask & (count >= msm)
        score = jnp.where(mask, score * node.boost, 0.0)
        return mask, score

    # -------------- leaves --------------

    def _field_type(self, field: str) -> FieldType:
        ft = self.reader.mapper.field_type(field)
        if ft is None:
            # unmapped fields match nothing (reference: unmapped term queries
            # return MatchNoDocsQuery under lenient resolution)
            raise _UnmappedField(field)
        return ft

    def _eval_match(self, node: dsl.MatchQuery, scoring: bool):
        try:
            ft = self._field_type(node.field)
        except _UnmappedField:
            return self._none()
        if isinstance(ft, TextFieldType):
            terms = _analyzed_terms(ft, node.query)
        else:
            # match on keyword/numeric behaves like a term query
            terms = [ft.normalize_term(node.query)]
        if not terms:
            return self._none()
        msm = 1 if node.operator == "or" else len(terms)
        if node.minimum_should_match is not None and node.operator == "or":
            msm = node.minimum_should_match
        return self._eval_terms(node.field, terms, node.boost, scoring,
                                node.operator, msm, pre_analyzed=True)

    def _eval_terms(self, field: str, values: Sequence, boost: float,
                    scoring: bool, operator: str, msm: int,
                    pre_analyzed: bool = False):
        try:
            ft = self._field_type(field)
        except _UnmappedField:
            return self._none()
        if pre_analyzed:
            terms = [str(v) for v in values]
        elif isinstance(ft, TextFieldType):
            # term/terms queries are NOT analyzed (reference: TermQueryBuilder
            # compares raw bytes even on text fields)
            terms = [str(v) for v in values]
        else:
            terms = [ft.normalize_term(v) for v in values]
        fp = self.view.pack.fields.get(field)
        if fp is None:
            return self._none()
        k1, b = self.reader.k1, self.reader.b
        doc_count, avgdl = self.reader.field_stats(field)
        cache = bm25_norm_cache(k1, b, avgdl)

        total_mask = None
        total_count = jnp.zeros(self.d_pad, dtype=jnp.int32)
        total_score = jnp.zeros(self.d_pad, dtype=jnp.float32)
        # chunk terms into ≤32-slot kernel passes
        for chunk_start in range(0, len(terms), MAX_SLOTS_PER_PASS):
            chunk = terms[chunk_start: chunk_start + MAX_SLOTS_PER_PASS]
            t_pad = _bucket(len(chunk))
            starts = np.zeros((1, t_pad), dtype=np.int32)
            lengths = np.zeros((1, t_pad), dtype=np.int32)
            idf_boost = np.zeros((1, t_pad), dtype=np.float32)
            max_len = 1
            for t, term in enumerate(chunk):
                row = fp.term_row(term)
                s, ln = fp.row_slice(row)
                df = self.reader.doc_freq(field, term)
                starts[0, t], lengths[0, t] = s, ln
                if scoring and df > 0:
                    idf = math.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))
                    idf_boost[0, t] = boost * idf * (k1 + 1.0)
                max_len = max(max_len, ln)
            max_len = _bucket(max_len, 128)
            scores, termmask = bm25.score_and_mask(
                jnp.asarray(fp.flat_docs), jnp.asarray(fp.flat_tfs),
                jnp.asarray(fp.norms_u8), jnp.asarray(cache),
                jnp.asarray(starts), jnp.asarray(lengths), jnp.asarray(idf_boost),
                max_len=max_len, d_pad=self.d_pad)
            tm = termmask[0, : self.d_pad]
            total_score = total_score + scores[0, : self.d_pad]
            # per-slot presence → per-chunk match count
            bits = jnp.asarray([1 << t for t in range(len(chunk))], dtype=jnp.int32)
            present = (tm[None, :] & bits[:, None]) != 0
            total_count = total_count + jnp.sum(present, axis=0).astype(jnp.int32)
        if operator == "and":
            mask = total_count >= len(terms)
        else:
            mask = total_count >= max(1, msm)
        score = jnp.where(mask, total_score, 0.0)
        return mask, score

    def _eval_nested(self, node: dsl.NestedQuery, scoring: bool):
        """Per-OBJECT matching over the segment's nested store
        (reference: NestedQueryBuilder joins hidden sub-documents via
        BitSetProducer; here each object is evaluated directly). Child
        scores are constant (1·boost per matching object, filter-like);
        score_mode combines them: sum → count, avg/min/max → 1, none → 0."""
        store = self.view.segment.nested_store.get(node.path)
        if not store:
            return self._none()
        mapper = self.reader.mapper
        if hasattr(mapper, "mapper"):  # MapperService → DocumentMapper
            mapper = mapper.mapper
        mask = np.zeros(self.d_pad, dtype=bool)
        score = np.zeros(self.d_pad, dtype=np.float32)
        for ord_, objs in store.items():
            n_matched = 0
            for obj in objs:
                if _nested_object_matches(node.query, obj, mapper,
                                          node.path):
                    n_matched += 1
            if n_matched:
                mask[ord_] = True
                if scoring and node.score_mode != "none":
                    child = float(node.boost)
                    score[ord_] = (child * n_matched
                                   if node.score_mode == "sum" else child)
        return jnp.asarray(mask), jnp.asarray(score)

    def _eval_ip_range(self, field: str, lo128: int, hi128: int,
                       boost: float):
        """[lo128, hi128] inclusive over the ip field's split (hi, lo)
        signed-offset i64 columns — a 128-bit compare as two 64-bit
        lexicographic compares (IpFieldType docstring)."""
        pack = self.view.pack
        h = pack.dv_i64.get(field + IpFieldType.HI_SUFFIX)
        l = pack.dv_i64.get(field + IpFieldType.LO_SUFFIX)
        if h is None or l is None or lo128 > hi128:
            return self._none()
        lo_h, lo_l = IpFieldType.split128(lo128)
        hi_h, hi_l = IpFieldType.split128(hi128)
        # presence via the exists mask, NOT the i64 sentinel: an
        # IPv4-mapped address has hi == 0, which collides with MISSING_I64
        # after the signed offset
        present = self.reader.has_field_mask(self.view_idx, field)
        ge = (h > lo_h) | ((h == lo_h) & (l >= lo_l))
        le = (h < hi_h) | ((h == hi_h) & (l <= hi_l))
        mask = jnp.asarray(present & ge & le)
        score = jnp.where(mask, jnp.float32(boost), 0.0).astype(jnp.float32)
        return mask, score

    def _eval_range_field(self, node: dsl.RangeQuery, ft: RangeFieldType):
        """Interval-vs-interval matching on a range FIELD (reference:
        RangeFieldMapper; relation intersects|within|contains, default
        intersects)."""
        pack = self.view.pack
        cols = pack.dv_i64 if ft.bound_kind == "i64" else pack.dv_f64
        g = cols.get(node.field + RangeFieldType.GTE_SUFFIX)
        l = cols.get(node.field + RangeFieldType.LTE_SUFFIX)
        if g is None or l is None:
            return self._none()
        q_lo, q_hi = ft.parse_range({k: v for k, v in
                                     (("gt", node.gt), ("gte", node.gte),
                                      ("lt", node.lt), ("lte", node.lte))
                                     if v is not None})
        if ft.bound_kind == "i64":
            present = g != MISSING_I64
        else:
            present = ~np.isnan(g)
        relation = (node.relation or "intersects").lower()
        if relation == "within":
            hit = (g >= q_lo) & (l <= q_hi)
        elif relation == "contains":
            hit = (g <= q_lo) & (l >= q_hi)
        elif relation == "intersects":
            hit = (g <= q_hi) & (l >= q_lo)
        else:
            raise QueryShardException(
                f"[range] unknown relation [{relation}]")
        mask = jnp.asarray(present & hit)
        score = jnp.where(mask, jnp.float32(node.boost),
                          0.0).astype(jnp.float32)
        return mask, score

    def _eval_range(self, node: dsl.RangeQuery):
        try:
            ft = self._field_type(node.field)
        except _UnmappedField:
            return self._none()
        if isinstance(ft, IpFieldType):
            lo = 0
            hi = (1 << 128) - 1
            if node.gte is not None:
                lo = ft.parse_ip(node.gte)
            elif node.gt is not None:
                lo = ft.parse_ip(node.gt) + 1
            if node.lte is not None:
                hi = ft.parse_ip(node.lte)
            elif node.lt is not None:
                hi = ft.parse_ip(node.lt) - 1
            return self._eval_ip_range(node.field, lo, hi, node.boost)
        if isinstance(ft, RangeFieldType):
            return self._eval_range_field(node, ft)
        if isinstance(ft, (TextFieldType, KeywordFieldType)):
            raise QueryShardException(
                f"range query on [{ft.type_name}] field [{node.field}] is not supported")
        lo_raw = node.gte if node.gte is not None else node.gt
        hi_raw = node.lte if node.lte is not None else node.lt
        pack = self.view.pack
        if node.field in pack.dv_i64:
            col = pack.dv_i64[node.field]
            lo = -(2**62) if lo_raw is None else int(ft.normalize_range_bound(lo_raw))
            hi = 2**62 if hi_raw is None else int(ft.normalize_range_bound(hi_raw))
            if node.gt is not None and node.gte is None:
                lo += 1
            if node.lt is not None and node.lte is None:
                hi -= 1
            mask = bm25.range_mask_i64(
                jnp.asarray(col), jnp.asarray([lo], dtype=jnp.int64),
                jnp.asarray([hi], dtype=jnp.int64))[0]
        elif node.field in pack.dv_f64:
            col = pack.dv_f64[node.field]
            lo = -np.inf if lo_raw is None else float(ft.normalize_range_bound(lo_raw))
            hi = np.inf if hi_raw is None else float(ft.normalize_range_bound(hi_raw))
            mask = bm25.range_mask_f64(
                jnp.asarray(col), jnp.asarray([lo], dtype=jnp.float64),
                jnp.asarray([hi], dtype=jnp.float64))[0]
            if node.gt is not None and node.gte is None:
                mask = mask & (jnp.asarray(col) != lo)
            if node.lt is not None and node.lte is None:
                mask = mask & (jnp.asarray(col) != hi)
        else:
            return self._none()
        # constant_score semantics: ranges don't score (reference wraps range
        # in filter context scoring = 1*boost when in scoring context)
        score = jnp.where(mask, jnp.float32(node.boost), 0.0).astype(jnp.float32)
        return mask, score

    def _eval_phrase(self, node: dsl.MatchPhraseQuery, scoring: bool):
        try:
            ft = self._field_type(node.field)
        except _UnmappedField:
            return self._none()
        if not isinstance(ft, TextFieldType):
            return self._eval_terms(node.field, [node.query], node.boost,
                                    scoring, "and", 1)
        terms = _analyzed_terms(ft, node.query)
        if not terms:
            return self._none()
        seg = self.view.segment
        positions = seg.positions.get(node.field, {})
        # candidates: docs containing all terms (host intersection over the
        # postings — phrase verification is host-side round 1)
        doc_sets = []
        for t in terms:
            entry = seg.postings.get(node.field, {}).get(t)
            if entry is None:
                return self._none()
            doc_sets.append(set(int(d) for d in entry[0]))
        candidates = sorted(set.intersection(*doc_sets))
        if not candidates:
            return self._none()
        k1, b = self.reader.k1, self.reader.b
        doc_count, avgdl = self.reader.field_stats(node.field)
        dfs = [self.reader.doc_freq(node.field, t) for t in terms]
        idf_sum = sum(math.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))
                      for df in dfs if df > 0)
        from elasticsearch_tpu.ops.smallfloat import LENGTH_TABLE
        mask = np.zeros(self.d_pad, dtype=bool)
        score = np.zeros(self.d_pad, dtype=np.float32)
        for d in candidates:
            plists = [positions.get(t, {}).get(d) for t in terms]
            if any(p is None for p in plists):
                continue
            freq = _phrase_freq(plists, node.slop)
            if freq <= 0:
                continue
            mask[d] = True
            if scoring:
                dl = float(LENGTH_TABLE[seg.norms[node.field][d]])
                denom = freq + k1 * (1 - b + b * dl / (avgdl or 1.0))
                score[d] = node.boost * idf_sum * (k1 + 1.0) * freq / denom
        return jnp.asarray(mask), jnp.asarray(score)

    def _none(self):
        return (jnp.zeros(self.d_pad, dtype=bool),
                jnp.zeros(self.d_pad, dtype=jnp.float32))


class _UnmappedField(Exception):
    def __init__(self, field: str):
        self.field = field


def _phrase_freq(plists: List[np.ndarray], slop: int) -> int:
    """Exact phrase count (slop=0): positions p_i with p_i = p_0 + i.
    For slop>0 uses a simple window check (approximation of sloppy freq)."""
    first = plists[0]
    count = 0
    for p0 in first:
        ok = True
        for i, pl in enumerate(plists[1:], start=1):
            target = p0 + i
            if slop == 0:
                if target not in pl:
                    ok = False
                    break
            else:
                if not ((np.abs(pl - target) <= slop).any()):
                    ok = False
                    break
        if ok:
            count += 1
    return count


def _nested_object_matches(q: dsl.QueryNode, obj: Dict[str, list],
                           doc_mapper, path: str) -> bool:
    """Evaluate an inner nested query against ONE object's flat
    {absolute subfield path: [raw values]} map — the per-sub-document
    match the reference gets from indexing each nested object as its own
    Lucene doc. Field types normalize both sides."""
    if isinstance(q, dsl.MatchAllQuery):
        return True
    if isinstance(q, dsl.BoolQuery):
        for c in list(q.must) + list(q.filter):
            if not _nested_object_matches(c, obj, doc_mapper, path):
                return False
        for c in q.must_not:
            if _nested_object_matches(c, obj, doc_mapper, path):
                return False
        if q.should:
            msm = q.minimum_should_match
            if msm is None:
                msm = 0 if (q.must or q.filter) else 1
            if msm > 0:
                n = sum(1 for c in q.should
                        if _nested_object_matches(c, obj, doc_mapper, path))
                if n < msm:
                    return False
        return True
    if isinstance(q, dsl.ConstantScoreQuery):
        return _nested_object_matches(q.filter_query, obj, doc_mapper, path)
    if isinstance(q, dsl.NestedQuery):
        raise QueryShardException(
            "[nested] within [nested] is not supported yet")
    if isinstance(q, dsl.ExistsQuery):
        return bool(obj.get(q.field))
    if isinstance(q, (dsl.TermQuery, dsl.TermsQuery)):
        ft = doc_mapper.fields.get(q.field)
        vals = obj.get(q.field)
        if ft is None or not vals:
            return False
        wants = ([q.value] if isinstance(q, dsl.TermQuery)
                 else list(q.values))
        try:
            want_norm = {ft.normalize_term(w) for w in wants}
            return any(ft.normalize_term(v) in want_norm for v in vals)
        except Exception:
            return False
    if isinstance(q, dsl.MatchQuery):
        ft = doc_mapper.fields.get(q.field)
        vals = obj.get(q.field)
        if ft is None or not vals:
            return False
        if isinstance(ft, TextFieldType):
            q_terms = _analyzed_terms(ft, q.query)
            if not q_terms:
                return False
            doc_terms = set()
            for v in vals:
                doc_terms.update(ft.analyzer.terms(str(v)))
            hits = sum(1 for t in q_terms if t in doc_terms)
            if q.operator == "and":
                return hits == len(q_terms)
            need = q.minimum_should_match or 1
            return hits >= need
        try:
            want = ft.normalize_term(q.query)
            return any(ft.normalize_term(v) == want for v in vals)
        except Exception:
            return False
    if isinstance(q, dsl.RangeQuery):
        ft = doc_mapper.fields.get(q.field)
        vals = obj.get(q.field)
        if ft is None or not vals:
            return False
        try:
            for v in vals:
                dv = ft.doc_value(v) if ft.has_doc_values \
                    else ft.normalize_range_bound(v)
                if q.gt is not None and \
                        not dv > ft.normalize_range_bound(q.gt):
                    continue
                if q.gte is not None and \
                        not dv >= ft.normalize_range_bound(q.gte):
                    continue
                if q.lt is not None and \
                        not dv < ft.normalize_range_bound(q.lt):
                    continue
                if q.lte is not None and \
                        not dv <= ft.normalize_range_bound(q.lte):
                    continue
                return True
        except Exception:
            return False
        return False
    raise QueryShardException(
        f"[nested] unsupported inner query [{q.query_name()}]")
