"""Vectorized response assembly for the TPU serving path.

The reference builds a SearchHit object per hit and serializes it
field-by-field; at k=1000 that is ~1000 dict constructions + ~1000
per-hit dumps per response, all of it Python under the interpreter lock
on the request thread. Here the hot response shape —
metadata-only hits (`"_source": false`), the shape high-QPS serving
traffic uses — is serialized COLUMNAR: external ids resolve via one
fancy-index over the pack's id table, ids and scores are JSON-encoded as
whole arrays in single C-level `json.dumps` calls, and the hits block is
assembled from the encoded fragments without ever constructing a per-hit
dict (BM25S, arXiv 2407.03618: lexical serving throughput is won by
moving per-item Python into batch array work).

The fragment assembly itself is the **response splicer**
(`native/response_splice.c`): the columns ship as whole encoded arrays
and the C side splits them into elements and concatenates the per-hit
objects. `_py_splice` is the automatic byte-identical fallback when the
`.so` is absent (same element scanner, same concatenation), so a missing
toolchain degrades speed, never bytes. The `SpliceColumns` wire form is
also how the batcher process hands result columns to the serving-front
processes (`serving/front.py`): `encode_wire_response` splits the
envelope around each hits block so the front splices the final bytes on
its own core.

The REST layer of the node itself goes one step further
(`dumps_response_bytes`): the metadata-only block, and the block that
also returns each hit's whole `_source`, is written by ONE native call
(`es_render_hits`) from the kernel's result columns and the pack's
`JsonLiterals` tables (its ids, and its stored sources once a `_source`
block has asked for them), with the GIL released and no Python object
per hit: no id or source is gathered or encoded and no score becomes a
Python float on a request. Which path a block takes is decided from
what the block is (shape flags, score dtype, tables present, finite
scores), and `RENDER_COUNTS` says how many took each; `FETCH_COUNTS`
counts the hits returned with a `_source` and its bytes.

`ColumnarHits` is a lazy Sequence: in-process consumers (tests, ccs,
rank_eval) that index or iterate it see ordinary hit dicts — built once,
on first touch, via the same assembly loop the planner path uses — while
the REST layer serializes it straight from the columns via
`dumps_response` without materializing anything. `SplicedHits` wraps
already-materialized hit dicts (the multi-index merge path) so their
rendering goes through the splicer too.
"""

from __future__ import annotations

import ctypes
import dataclasses
import json
import os
from collections.abc import Sequence
from json.encoder import encode_basestring_ascii
from typing import Any, Dict, Iterable, List, Optional, Tuple

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.metrics import LabeledCounters

__all__ = ["ColumnarHits", "SplicedHits", "SpliceColumns", "JsonLiterals",
           "RENDER_COUNTS", "FETCH_COUNTS", "assemble_hits_list",
           "dumps_response", "dumps_response_bytes", "encode_source",
           "hits_columns_from_dicts", "splice_hits_bytes",
           "encode_wire_response", "splice_wire"]

_COMPACT = (",", ":")

#: one value → the bytes the Python path writes for it inside a hit:
#: `json.dumps(value, separators=(",", ":"))`, ensure_ascii and all
encode_source = json.JSONEncoder(separators=_COMPACT).encode

#: hits blocks rendered to their final bytes in this process, by path:
#: `native` (es_render_hits: GIL released, no Python object per hit) or
#: `python` (encoded columns + splicer, or plain json.dumps) →
#: /_tpu/stats `render`
RENDER_COUNTS = LabeledCounters("path")
for _path in ("native", "python"):
    RENDER_COUNTS.child(_path)  # both read 0, not absent, before a render

#: kernel-path hits rendered with a `_source`, on either path: `hits`,
#: and `source_bytes`, the bytes of their `_source` values →
#: /_tpu/stats `fetch`
FETCH_COUNTS = LabeledCounters("kind")
for _kind in ("hits", "source_bytes"):
    FETCH_COUNTS.child(_kind)


def assemble_hits_list(name: str, resident, scores, rows, ords, source,
                       version: bool, seq_no_primary_term: bool
                       ) -> List[Dict[str, Any]]:
    """Columnar window → response hit dicts (the materialized form).
    ids via one fancy-index; stored fields (when requested) read
    directly from the pinned segments the pack was scored against (same
    snapshot contract as the fetch phase)."""
    if resident is None or len(scores) == 0:
        return []
    ids = resident.resolve_ids(rows, ords).tolist()
    scores_l = scores.tolist()
    if source is False and not version and not seq_no_primary_term:
        return [{"_index": name, "_id": i, "_score": s}
                for i, s in zip(ids, scores_l)]
    from elasticsearch_tpu.search.query_phase import filter_source
    segs = resident.row_segments
    rows_l = rows.tolist()
    ords_l = ords.tolist()
    out = []
    for i, s, row, o in zip(ids, scores_l, rows_l, ords_l):
        doc: Dict[str, Any] = {"_index": name, "_id": i, "_score": s}
        seg = segs[row]
        if source is not False:
            src = seg.stored_source[o]
            if isinstance(source, (list, tuple)):
                src = filter_source(src or {}, list(source))
            doc["_source"] = src
        if version:
            doc["_version"] = int(seg.doc_versions[o])
        if seq_no_primary_term:
            doc["_seq_no"] = int(seg.seq_nos[o])
            doc["_primary_term"] = int(seg.primary_terms[o])
        out.append(doc)
    return out


# ---------------------------------------------------------------------------
# the response splicer: pre-encoded columns → final hits-array bytes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpliceColumns:
    """Wire form of a hits block: whole-array json.dumps encodings.

    Every byte of the final output comes from one of these strings, so
    splicing (C or Python) is byte-identical to per-hit json.dumps with
    compact separators. Picklable — this is also the shape the batcher
    process ships to the serving fronts."""

    n: int
    ids_json: str                      # '["a","b"]'
    scores_json: str                   # '[1.5,null]'
    names_json: str                    # '["idx"]' (deduped _index names)
    name_idx: List[int]                # per-hit index into names_json
    extras_json: Optional[str] = None  # '[{...},{}]' residual fields


_SPLICE_FN = None
_RENDER_FN = None
_SPLICE_TRIED = False


def _native_splice():
    global _SPLICE_FN, _RENDER_FN, _SPLICE_TRIED
    if not _SPLICE_TRIED:
        _SPLICE_TRIED = True
        if not os.environ.get("ES_TPU_NO_NATIVE_SPLICE"):
            from elasticsearch_tpu import native
            _SPLICE_FN = native.bind(
                "response_splice", "es_splice_hits", ctypes.c_long,
                [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
                 ctypes.POINTER(ctypes.c_int32), ctypes.c_char_p,
                 ctypes.c_int32, ctypes.c_char_p, ctypes.c_long])
            _RENDER_FN = native.bind(
                "response_splice", "es_render_hits", ctypes.c_long,
                [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                 ctypes.c_void_p, ctypes.c_void_p,
                 ctypes.c_void_p, ctypes.c_int64,
                 ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                 ctypes.c_int32, ctypes.c_char_p, ctypes.c_long,
                 ctypes.c_void_p, ctypes.c_long])
    return _SPLICE_FN


def _native_render():
    """es_render_hits, under the same switch as the splicer: None when
    ES_TPU_NO_NATIVE_SPLICE is set or the library did not build."""
    return _RENDER_FN if _native_splice() is not None else None


def splice_hits_bytes(cols: SpliceColumns) -> str:
    """Columns → the hits-array JSON text, via the C splicer when the
    native library is available, else the byte-identical Python path."""
    if cols.n == 0:
        return "[]"
    fn = _native_splice()
    if fn is not None:
        ids_b = cols.ids_json.encode("ascii", "replace")
        scores_b = cols.scores_json.encode("ascii", "replace")
        names_b = cols.names_json.encode("ascii", "replace")
        extras_b = (cols.extras_json.encode("ascii", "replace")
                    if cols.extras_json is not None else None)
        idx = (ctypes.c_int32 * cols.n)(*cols.name_idx)
        cap = (len(ids_b) + len(scores_b) + (len(extras_b or b""))
               + cols.n * (len(names_b) + 32) + 16)
        for _ in range(2):
            buf = ctypes.create_string_buffer(cap)
            rc = fn(ids_b, scores_b, names_b, idx, extras_b, cols.n,
                    buf, cap)
            if rc >= 0:
                return buf.raw[:rc].decode("ascii")
            if rc != -1:
                break  # malformed input — let Python decide
            cap *= 4
    return _py_splice(cols)


def _scan_elements(s: str) -> Optional[List[str]]:
    """Split a compact JSON array into its top-level element strings —
    the Python twin of the C scanner (string-escape + depth aware)."""
    if not s or s[0] != "[":
        return None
    if s.startswith("[]"):
        return []
    out: List[str] = []
    depth = 0
    in_str = esc = False
    start = 1
    for i in range(1, len(s)):
        c = s[i]
        if in_str:
            if esc:
                esc = False
            elif c == "\\":
                esc = True
            elif c == '"':
                in_str = False
            continue
        if c == '"':
            in_str = True
        elif c in "{[":
            depth += 1
        elif c == "}":
            depth -= 1
        elif c == "]":
            if depth == 0:
                out.append(s[start:i])
                return out
            depth -= 1
        elif c == "," and depth == 0:
            out.append(s[start:i])
            start = i + 1
    return None


def _py_splice(cols: SpliceColumns) -> str:
    """Pure-Python splice — same element spans, same concatenation, so
    bytes match the native path exactly."""
    ids = _scan_elements(cols.ids_json)
    scores = _scan_elements(cols.scores_json)
    names = _scan_elements(cols.names_json)
    extras = (_scan_elements(cols.extras_json)
              if cols.extras_json is not None else None)
    if (ids is None or scores is None or not names
            or len(ids) != cols.n or len(scores) != cols.n
            or (extras is not None and len(extras) != cols.n)):
        raise ValueError("malformed splice columns")
    frags = []
    for i in range(cols.n):
        hit = ('{"_index":' + names[cols.name_idx[i]]
               + ',"_id":' + ids[i] + ',"_score":' + scores[i])
        if extras is not None and len(extras[i]) > 2:
            hit += "," + extras[i][1:-1]
        frags.append(hit + "}")
    return "[" + ",".join(frags) + "]"


_META_KEYS = ["_index", "_id", "_score"]


def hits_columns_from_dicts(hits: List[Dict[str, Any]]
                            ) -> Optional[SpliceColumns]:
    """Materialized hit dicts → splice columns, or None when the hits
    don't lead with the canonical (_index, _id, _score) key order (the
    caller then falls back to plain json.dumps)."""
    if not hits:
        return SpliceColumns(0, "[]", "[]", "[]", [])
    names: List[str] = []
    name_pos: Dict[str, int] = {}
    name_idx: List[int] = []
    ids: List[Any] = []
    scores: List[Any] = []
    extras: List[Dict[str, Any]] = []
    any_extra = False
    for h in hits:
        if not isinstance(h, dict):
            return None
        keys = list(h)
        if keys[:3] != _META_KEYS:
            return None
        name = h["_index"]
        if not isinstance(name, str):
            return None
        pos = name_pos.get(name)
        if pos is None:
            pos = name_pos[name] = len(names)
            names.append(name)
        name_idx.append(pos)
        ids.append(h["_id"])
        scores.append(h["_score"])
        extra = {k: h[k] for k in keys[3:]}
        if extra:
            any_extra = True
        extras.append(extra)
    try:
        return SpliceColumns(
            len(hits),
            json.dumps(ids, separators=_COMPACT),
            json.dumps(scores, separators=_COMPACT),
            json.dumps(names, separators=_COMPACT),
            name_idx,
            json.dumps(extras, separators=_COMPACT) if any_extra else None)
    except (TypeError, ValueError):
        return None  # unserializable value — plain dumps raises the same


@dataclasses.dataclass
class JsonLiterals:
    """A JSON literal for each doc of a pack, encoded once per pack: of
    its external ids (`ResidentPack.id_json`) and, once a block with
    `_source` has rendered from the pack, of its stored sources
    (`ResidentPack.source_literals`).

    `blob` holds the literal of every value back to back (uint8, ASCII:
    both encoders escape what is not), `offsets` (int64[n + 1]) where
    each starts, indexed like the pack's `id_cat`; `max_len` is the
    longest literal, for sizing an output buffer without reading the
    table. Host memory only. numpy is imported where it is used: the
    serving fronts import this module and nothing but the standard
    library."""

    blob: Any
    offsets: Any
    max_len: int

    @classmethod
    def build(cls, value_lists: Iterable[Sequence],
              encode=encode_basestring_ascii) -> Optional["JsonLiterals"]:
        """One table over the concatenation of `value_lists` (a pack's
        per-row lists, in row order), each value written by `encode`: a
        string's literal for ids, `encode_source` for sources. None when
        `encode` refuses a value (an id that is not a string, a source
        json cannot write): such a pack renders through the Python
        path."""
        import numpy as np
        parts: List[str] = []
        try:
            for values in value_lists:
                parts.extend(map(encode, values))
        except (TypeError, ValueError):
            return None
        lengths = np.fromiter(map(len, parts), dtype=np.int64,
                              count=len(parts))
        offsets = np.zeros(len(parts) + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        blob = np.frombuffer("".join(parts).encode("ascii"), dtype=np.uint8)
        return cls(blob, offsets, int(lengths.max(initial=0)))

    @property
    def nbytes(self) -> int:
        return int(self.blob.nbytes + self.offsets.nbytes)

    @classmethod
    def concat(cls, tables: Sequence[Optional["JsonLiterals"]]
               ) -> Optional["JsonLiterals"]:
        """The table of a chain of packs, or None when one has none."""
        import numpy as np
        if any(t is None for t in tables):
            return None
        offsets, end = [tables[0].offsets], tables[0].offsets[-1]
        for t in tables[1:]:
            offsets.append(t.offsets[1:] + end)
            end += t.offsets[-1]
        return cls(np.concatenate([t.blob for t in tables]),
                   np.concatenate(offsets), max(t.max_len for t in tables))


class ColumnarHits(Sequence):
    """Lazy hits block over kernel result columns.

    Reads like a list of hit dicts (len / index / slice / iterate);
    materializes that list at most once and caches it, so consumers that
    MUTATE hits (ccs rewrites `_index`) keep their edits visible to a
    later serialization. `to_json()` renders the block via the response
    splicer; for the metadata-only shape it never touches per-hit Python
    at all."""

    __slots__ = ("name", "resident", "scores", "rows", "ords", "source",
                 "version", "seq_no_primary_term", "_hits")

    def __init__(self, name: str, resident, scores, rows, ords,
                 source=False, version: bool = False,
                 seq_no_primary_term: bool = False):
        self.name = name
        self.resident = resident
        self.scores = scores
        self.rows = rows
        self.ords = ords
        self.source = source
        self.version = version
        self.seq_no_primary_term = seq_no_primary_term
        self._hits: Optional[List[Dict[str, Any]]] = None

    # ---- list protocol --------------------------------------------------

    def _materialize(self) -> List[Dict[str, Any]]:
        if self._hits is None:
            self._hits = assemble_hits_list(
                self.name, self.resident, self.scores, self.rows,
                self.ords, self.source, self.version,
                self.seq_no_primary_term)
        return self._hits

    def __len__(self) -> int:
        return len(self.scores)

    def __getitem__(self, i):
        return self._materialize()[i]

    def __iter__(self):
        return iter(self._materialize())

    def __eq__(self, other):
        if isinstance(other, (ColumnarHits, SplicedHits)):
            other = list(other)
        if isinstance(other, list):
            return self._materialize() == other
        return NotImplemented

    def __repr__(self) -> str:
        return f"ColumnarHits(n={len(self.scores)}, index={self.name!r})"

    # ---- serialization --------------------------------------------------

    def splice_columns(self) -> Optional[SpliceColumns]:
        """This block as splice columns (None ⇒ caller must dumps)."""
        if self._hits is not None:
            # already materialized (possibly mutated) — honor the dicts
            return hits_columns_from_dicts(self._hits)
        cols = self._fast_columns()
        if cols is not None:
            return cols
        return hits_columns_from_dicts(self._materialize())

    def _metadata_only(self) -> bool:
        return (self.source is False and not self.version
                and not self.seq_no_primary_term)

    def _fast_columns(self) -> Optional[SpliceColumns]:
        """Columns straight from the kernel result arrays — the
        metadata-only shape, no per-hit dict ever exists. None when this
        block needs the materialized path (_source / _version / seq_no,
        or non-string ids)."""
        if not self._metadata_only():
            return None
        if self.resident is None or len(self.scores) == 0:
            return SpliceColumns(0, "[]", "[]", "[]", [])
        ids = self.resident.resolve_ids(self.rows, self.ords).tolist()
        if not all(type(i) is str for i in ids):
            return None
        n = len(ids)
        return SpliceColumns(
            n, json.dumps(ids, separators=_COMPACT),
            json.dumps(self.scores.tolist(), separators=_COMPACT),
            "[" + json.dumps(self.name) + "]", [0] * n)

    def _fast_json(self) -> Optional[str]:
        """Single-pass serialization of the metadata-only shape, or None
        when this block needs the materialized path."""
        cols = self._fast_columns()
        if cols is None:
            return None
        return splice_hits_bytes(cols)

    def to_json(self) -> str:
        RENDER_COUNTS.inc("python")
        cols = self.splice_columns()
        text = (splice_hits_bytes(cols) if cols is not None
                else json.dumps(self._materialize(), separators=_COMPACT))
        if self.source is not False:
            with_source = [h["_source"] for h in self._materialize()
                           if "_source" in h]
            FETCH_COUNTS.inc("hits", n=len(with_source))
            FETCH_COUNTS.inc("source_bytes",
                             n=sum(len(encode_source(s)) for s in with_source))
        return text

    def render_native(self, stages=None) -> Optional[bytes]:
        """The bytes of `to_json()` from one native call that runs with
        the GIL released and touches no Python object per hit: ids come
        from the resident's `id_json`, each hit's whole `_source` (for
        `source is True`) from its `source_literals` (built on this first
        need, a stage `source_table` in `stages`), scores are formatted
        in C. None when the block is neither the metadata-only shape nor
        that shape with the whole `_source`, is not over float32 scores,
        was materialized (a consumer may have edited the dicts), its
        resident has no id or source table, the library is absent or
        switched off, or the C side refuses an input (a score that is not
        finite, a row outside the tables): the caller renders it through
        `to_json`."""
        if (self._hits is not None
                or not (self.source is False or self.source is True)
                or self.version or self.seq_no_primary_term):
            return None
        table = getattr(self.resident, "id_json", None)
        fn = _native_render()
        if table is None or fn is None:
            return None
        import numpy as np
        scores = self.scores
        if scores.dtype != np.float32:
            return None
        scores = np.ascontiguousarray(scores)
        rows = np.ascontiguousarray(self.rows, dtype=np.int32)
        ords = np.ascontiguousarray(self.ords, dtype=np.int32)
        row_offset = self.resident.row_offset
        n = len(scores)
        if (len(rows) != n or len(ords) != n
                or row_offset.dtype != np.int64
                or not row_offset.flags.c_contiguous):
            return None
        sources = None
        if self.source is True:
            build = getattr(self.resident, "source_literals", None)
            sources = build(stages) if build is not None else None
            if sources is None or len(sources.offsets) != len(table.offsets):
                return None
        name = encode_basestring_ascii(self.name).encode("ascii")
        # per hit: {"_index": ,"_id": ,"_score": } and a comma are 29
        # bytes, a score is 25 at most; ,"_source": is 11
        cap = 2 + n * (54 + len(name) + table.max_len)
        if sources is not None:
            cap += n * (11 + sources.max_len)
        out = np.empty(cap, dtype=np.uint8)
        rc = fn(table.blob.ctypes.data, table.offsets.ctypes.data,
                len(table.offsets) - 1,
                None if sources is None else sources.blob.ctypes.data,
                None if sources is None else sources.offsets.ctypes.data,
                row_offset.ctypes.data, len(row_offset), rows.ctypes.data,
                ords.ctypes.data, scores.ctypes.data, n, name, len(name),
                out.ctypes.data, cap)
        if rc < 0:
            return None
        RENDER_COUNTS.inc("native")
        if sources is not None:
            at = row_offset[rows] + ords
            FETCH_COUNTS.inc("hits", n=n)
            FETCH_COUNTS.inc("source_bytes", n=int(
                (sources.offsets[at + 1] - sources.offsets[at]).sum()))
        return out[:rc].tobytes()


class SplicedHits(Sequence):
    """Materialized hit dicts whose JSON rendering goes through the
    response splicer (the multi-index merge path: hits already exist as
    dicts, but per-hit serialization is still worth batching)."""

    __slots__ = ("_hits",)

    def __init__(self, hits: List[Dict[str, Any]]):
        self._hits = hits

    def __len__(self) -> int:
        return len(self._hits)

    def __getitem__(self, i):
        return self._hits[i]

    def __iter__(self):
        return iter(self._hits)

    def __eq__(self, other):
        if isinstance(other, (ColumnarHits, SplicedHits)):
            other = list(other)
        if isinstance(other, list):
            return self._hits == other
        return NotImplemented

    def __repr__(self) -> str:
        return f"SplicedHits(n={len(self._hits)})"

    def append(self, hit: Dict[str, Any]) -> None:
        self._hits.append(hit)

    def splice_columns(self) -> Optional[SpliceColumns]:
        return hits_columns_from_dicts(self._hits)

    def to_json(self) -> str:
        RENDER_COUNTS.inc("python")
        cols = self.splice_columns()
        if cols is not None:
            return splice_hits_bytes(cols)
        return json.dumps(self._hits, separators=_COMPACT)


_HITS_BLOCKS = (ColumnarHits, SplicedHits)


def _tokenize(payload: Any) -> Tuple[str, Dict[str, Any]]:
    """json.dumps with every hits block replaced by a unique placeholder
    token; blocks come back keyed by token in document order."""
    blocks: Dict[str, Any] = {}

    def default(obj):
        if isinstance(obj, _HITS_BLOCKS):
            token = f"\x00columnar:{id(obj)}\x00"
            blocks[token] = obj
            return token
        raise TypeError(
            f"Object of type {type(obj).__name__} is not JSON serializable")

    return json.dumps(payload, default=default), blocks


def dumps_response(payload: Any) -> str:
    """json.dumps that renders embedded hits blocks via the response
    splicer. Works at any nesting depth (plain search, msearch
    `responses`, ...): the encoder emits a unique placeholder token per
    block, then the tokens are spliced with the real JSON."""
    text, blocks = _tokenize(payload)
    for token, block in blocks.items():
        text = text.replace(json.dumps(token), block.to_json())
    return text


def _block_bytes(block: Any, stages: Any) -> bytes:
    data = (block.render_native(stages) if isinstance(block, ColumnarHits)
            else None)
    return block.to_json().encode("utf-8") if data is None else data


def dumps_response_bytes(payload: Any, stages: Any = None) -> bytes:
    """`dumps_response(payload).encode()`, byte for byte, for the REST
    layer: a block that `ColumnarHits.render_native` takes is written by
    the native renderer, and the envelope is joined around the blocks as
    bytes once, so no 60 KB body is decoded, searched and encoded again
    under the GIL. Any other block renders through `to_json`. In
    `stages` (a StageTimes, or None), the rendering of a kernel-path
    block that returns `_source` is the stage `fetch`, on either path
    (wall, and thread CPU one time in `CPU_SAMPLE_EVERY`)."""
    text, blocks = _tokenize(payload)
    if not blocks:
        return text.encode("utf-8")
    out: List[bytes] = []
    tail = text
    for token, block in blocks.items():
        pre, _, tail = tail.partition(json.dumps(token))
        out.append(pre.encode("utf-8"))
        if isinstance(block, ColumnarHits) and block.source is not False:
            with tracing.stage(stages, "fetch", annotate=False,
                               cpu=stages is not None
                               and stages.sample_cpu("fetch")):
                out.append(_block_bytes(block, stages))
        else:
            out.append(_block_bytes(block, stages))
    out.append(tail.encode("utf-8"))
    return b"".join(out)


def encode_wire_response(payload: Any
                         ) -> Tuple[List[str], List[SpliceColumns]]:
    """Batcher→front wire form: envelope parts + splice columns, where
    the final bytes are parts[0] + splice(columns[0]) + parts[1] + ...
    (len(parts) == len(columns) + 1). Blocks that can't column-encode
    are rendered batcher-side into the envelope, so the front's splice
    loop needs no special cases."""
    text, blocks = _tokenize(payload)
    if not blocks:
        return [text], []
    parts: List[str] = []
    columns: List[SpliceColumns] = []
    pending = ""
    tail = text
    for token, block in blocks.items():
        pre, _, tail = tail.partition(json.dumps(token))
        cols = block.splice_columns()
        if cols is None:
            pending += pre + block.to_json()
        else:
            parts.append(pending + pre)
            columns.append(cols)
            pending = ""
    parts.append(pending + tail)
    return parts, columns


def splice_wire(parts: List[str], columns: List[SpliceColumns]) -> str:
    """Front-side inverse of encode_wire_response — where the C splicer
    actually runs on the serving front's own core."""
    out = [parts[0]]
    for cols, part in zip(columns, parts[1:]):
        out.append(splice_hits_bytes(cols))
        out.append(part)
    return "".join(out)
