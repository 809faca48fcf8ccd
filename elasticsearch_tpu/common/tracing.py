"""Distributed tracing — spans, context propagation, a bounded ring of
finished spans per node.

Reference analog: the `tracing/` Task/APM layer (SURVEY.md §2.1#47-ish):
the REST layer opens (or adopts, via a W3C `traceparent`-style header) a
root span per request; the coordinator attaches the trace context to
every transport fan-out payload; shard-side handlers continue the span;
the TPU serving pipeline reports its stage boundaries as child spans.

Design constraints:

  * **Zero overhead when disabled.** `search.tracing.sample_rate = 0`
    (the default) must add nothing measurable to the hostpath: every
    instrumentation helper's disabled path is one thread-local read plus
    a None check, allocating nothing.
  * **Bounded memory.** Finished spans land in a deque ring
    (`search.tracing.max_spans`); old traces fall off the end.
  * **Head sampling.** The root makes the sampling decision; the
    decision travels in the `traceparent` flags byte, so a fan-out child
    never re-rolls the dice (one trace is complete or absent, never
    partial by chance).

Slow traces: a root span finishing above
`search.tracing.slow_threshold_ms` is emitted through the slowlog
channel (`elasticsearch_tpu.trace.slowlog`) with its per-stage
breakdown, same spirit as the per-shard search slowlog.
"""

from __future__ import annotations

import contextlib
import gc
import logging
import random
import sys
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

slowlog = logging.getLogger("elasticsearch_tpu.trace.slowlog")

#: wire context: (trace_id, parent span_id, sampled)
WireContext = Tuple[str, str, bool]

_tls = threading.local()


# ---------------------------------------------------------------------------
# traceparent encoding (W3C trace-context shaped: 00-<trace>-<span>-<flags>)
# ---------------------------------------------------------------------------

def format_traceparent(trace_id: str, span_id: str,
                       sampled: bool = True) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def parse_traceparent(header: Optional[str]) -> Optional[WireContext]:
    """→ (trace_id, span_id, sampled), or None for anything malformed
    (a bad header must never fail the request it rode in on)."""
    if not header or not isinstance(header, str):
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    _ver, trace_id, span_id, flags = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    return trace_id, span_id, flags == "01"


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class Span:
    """One timed operation. Mutated only by the thread that runs the
    operation; `end()` hands the finished record to the tracer ring."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "start", "_start_pc", "duration_ms", "attributes",
                 "events", "root", "_ended")

    is_recording = True

    def __init__(self, tracer: "Tracer", trace_id: str, span_id: str,
                 parent_id: Optional[str], name: str,
                 attributes: Optional[Dict[str, Any]] = None,
                 root: bool = False,
                 start: Optional[float] = None,
                 duration_s: Optional[float] = None):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start = time.time() if start is None else start
        self._start_pc = time.perf_counter()
        self.duration_ms: Optional[float] = (
            None if duration_s is None else duration_s * 1000.0)
        self.attributes: Dict[str, Any] = dict(attributes) if attributes \
            else {}
        self.events: List[Dict[str, Any]] = []
        self.root = root
        self._ended = False

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def add_event(self, name: str, **attributes: Any) -> None:
        self.events.append({"name": name, "time": time.time(),
                            **attributes})

    def context(self) -> WireContext:
        return self.trace_id, self.span_id, True

    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id, True)

    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        if self.duration_ms is None:
            self.duration_ms = (time.perf_counter()
                                - self._start_pc) * 1000.0
        self.tracer._finish(self)

    # context-manager form: exceptions annotate the span, then reraise
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.set_attribute("error", f"{type(exc).__name__}: {exc}")
        self.end()
        return False

    def to_dict(self) -> Dict[str, Any]:
        out = {"trace_id": self.trace_id, "span_id": self.span_id,
               "parent_id": self.parent_id, "name": self.name,
               "start": self.start,
               "duration_ms": round(self.duration_ms or 0.0, 3),
               "node": self.tracer.node_name}
        if self.attributes:
            out["attributes"] = dict(self.attributes)
        if self.events:
            out["events"] = list(self.events)
        return out


class _NoopSpan:
    """Shared do-nothing span: the disabled/unsampled path. All mutators
    are no-ops and `is_recording` is False so callers can skip work."""

    __slots__ = ()
    is_recording = False
    trace_id = span_id = parent_id = name = ""
    attributes: Dict[str, Any] = {}
    events: List[Dict[str, Any]] = []

    def set_attribute(self, key: str, value: Any) -> None:
        pass

    def add_event(self, name: str, **attributes: Any) -> None:
        pass

    def context(self) -> None:
        return None

    def end(self) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class Tracer:
    """Per-node span factory + bounded ring of finished spans."""

    def __init__(self, sample_rate: float = 0.0, max_spans: int = 4096,
                 slow_threshold_ms: Optional[float] = None,
                 node_name: str = "",
                 rng: Optional[random.Random] = None):
        self.sample_rate = max(0.0, min(1.0, float(sample_rate)))
        self.slow_threshold_ms = slow_threshold_ms
        self.node_name = node_name
        self._rng = rng or random.Random()
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=max(1, int(max_spans)))

    @property
    def enabled(self) -> bool:
        return self.sample_rate > 0.0

    def start_span(self, name: str,
                   parent: Any = None,
                   attributes: Optional[Dict[str, Any]] = None,
                   root: bool = False,
                   start: Optional[float] = None,
                   duration_s: Optional[float] = None):
        """`parent`: a live Span (local child), a WireContext tuple
        (continuation of a remote span — the remote sampling decision
        wins, even over a local sample_rate of 0), or None (a new root,
        subject to this tracer's sample_rate)."""
        if isinstance(parent, Span):
            trace_id, parent_id = parent.trace_id, parent.span_id
        elif isinstance(parent, _NoopSpan):
            return NOOP_SPAN
        elif isinstance(parent, tuple):
            trace_id, parent_id, sampled = parent
            if not sampled:
                return NOOP_SPAN
        elif parent is None:
            if self.sample_rate <= 0.0 or (
                    self.sample_rate < 1.0
                    and self._rng.random() >= self.sample_rate):
                return NOOP_SPAN
            trace_id, parent_id = uuid.uuid4().hex, None
        else:
            return NOOP_SPAN
        return Span(self, trace_id, uuid.uuid4().hex[:16], parent_id,
                    name, attributes, root=root, start=start,
                    duration_s=duration_s)

    def _finish(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)
        if (span.root and self.slow_threshold_ms is not None
                and span.duration_ms is not None
                and span.duration_ms >= self.slow_threshold_ms
                and slowlog.isEnabledFor(logging.WARNING)):
            self._emit_slow(span)

    def _emit_slow(self, span: Span) -> None:
        children = sorted(
            (s for s in self.spans(trace_id=span.trace_id, limit=0)
             if s["span_id"] != span.span_id),
            key=lambda s: -s["duration_ms"])[:8]
        breakdown = ", ".join(f"{s['name']}={s['duration_ms']:.1f}ms"
                              for s in children) or "no child spans"
        tenant = span.attributes.get("tenant")
        if tenant:
            breakdown = f"tenant=[{tenant}] {breakdown}"
        slowlog.warning(
            "slow trace [%s] [%s] took %.1fms (threshold %.0fms): %s",
            span.trace_id, span.name, span.duration_ms,
            self.slow_threshold_ms, breakdown)

    def spans(self, trace_id: Optional[str] = None,
              min_duration_ms: float = 0.0,
              limit: int = 200,
              tenant: Optional[str] = None) -> List[Dict[str, Any]]:
        """Finished spans, NEWEST first. limit=0 → no cap. A tenant
        filter matches the `tenant` attribute root spans are stamped
        with (per-tenant slow-query forensics)."""
        with self._lock:
            snap = list(self._spans)
        out = []
        for span in reversed(snap):
            if trace_id is not None and span.trace_id != trace_id:
                continue
            if min_duration_ms and (span.duration_ms or 0.0) \
                    < min_duration_ms:
                continue
            if tenant is not None and \
                    span.attributes.get("tenant") != tenant:
                continue
            out.append(span.to_dict())
            if limit and len(out) >= limit:
                break
        return out

    def trace(self, trace_id: str) -> List[Dict[str, Any]]:
        """Every retained span of one trace, in start order."""
        got = self.spans(trace_id=trace_id, limit=0)
        got.sort(key=lambda s: s["start"])
        return got

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


# ---------------------------------------------------------------------------
# thread-local current span + instrumentation helpers
#
# The helpers below are the only API instrumented code needs: they read
# the CURRENT span from a thread-local, so deep call stacks (coordinator
# → planner → kernel service) need no tracer plumbing, and a node's
# handler threads never mix spans across concurrent requests. Every
# disabled-path costs one getattr + None check.
# ---------------------------------------------------------------------------

def current_span() -> Optional[Span]:
    """The thread's current RECORDING span, or None."""
    span = getattr(_tls, "span", None)
    if span is None or not span.is_recording:
        return None
    return span


@contextlib.contextmanager
def use_span(span) -> Iterator[Any]:
    """Make `span` current for the block. Does NOT end the span — the
    owner ends it (lets a span outlive the block that populated it)."""
    prev = getattr(_tls, "span", None)
    _tls.span = span
    try:
        yield span
    finally:
        _tls.span = prev


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return NOOP_SPAN

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP_CTX = _NoopCtx()


class _ChildCtx:
    """Starts a child of `parent`, makes it current, ends it on exit."""

    __slots__ = ("span", "_prev")

    def __init__(self, span: Span):
        self.span = span

    def __enter__(self) -> Span:
        self._prev = getattr(_tls, "span", None)
        _tls.span = self.span
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        _tls.span = self._prev
        if exc is not None:
            self.span.set_attribute("error",
                                    f"{type(exc).__name__}: {exc}")
        self.span.end()
        return False


def child_span(name: str, **attributes: Any):
    """Context manager: a child span of the thread's current span
    (no-op — shared singleton, zero allocation — when not tracing)."""
    cur = getattr(_tls, "span", None)
    if cur is None or not cur.is_recording:
        return _NOOP_CTX
    return _ChildCtx(cur.tracer.start_span(
        name, parent=cur, attributes=attributes or None))


def span_under(parent: Optional[Span], name: str, **attributes: Any):
    """Like `child_span` but under an EXPLICIT parent — for work that
    hops threads (micro-batcher workers) where the thread-local of the
    submitting request is unavailable."""
    if parent is None or not parent.is_recording:
        return _NOOP_CTX
    return _ChildCtx(parent.tracer.start_span(
        name, parent=parent, attributes=attributes or None))


def record_stage(name: str, seconds: float, n: int = 1,
                 **attributes: Any) -> None:
    """Record an ALREADY-MEASURED duration as a completed child span of
    the current span (start back-dated by the duration). This is how
    stage timers (StageTimes) reconcile with traces: the span duration
    is the same dt the stats ring recorded."""
    cur = getattr(_tls, "span", None)
    if cur is None or not cur.is_recording:
        return
    if n > 1:
        attributes = dict(attributes or {})
        attributes["count"] = n
    span = cur.tracer.start_span(
        name, parent=cur, attributes=attributes or None,
        start=time.time() - seconds, duration_s=seconds)
    span.end()


def add_event(name: str, **attributes: Any) -> None:
    """Attach an event to the current span (no-op when not tracing)."""
    cur = getattr(_tls, "span", None)
    if cur is None or not cur.is_recording:
        return
    cur.add_event(name, **attributes)


def inject_context(payload: Dict[str, Any],
                   span: Optional[Span] = None) -> Dict[str, Any]:
    """Attach the trace context to a transport payload (in place) so the
    remote handler can continue the trace. No-op when not tracing."""
    if span is None:
        span = getattr(_tls, "span", None)
    if span is not None and span.is_recording:
        payload["_trace"] = span.traceparent()
    return payload


def extract_context(payload: Optional[Dict[str, Any]]
                    ) -> Optional[WireContext]:
    """Wire context out of a transport payload, or None."""
    if not payload:
        return None
    return parse_traceparent(payload.get("_trace"))


# ---------------------------------------------------------------------------
# stage timing on the profiler's clock
#
# One primitive for the serving pipeline's own time: wall seconds and the
# thread's CPU seconds into a StageTimes (which lands the same dt on the
# request's Span through `record_stage`), and a profiler annotation around
# the block. Annotations are TraceMe events: they land on the host plane
# of the same `.xplane.pb` that carries the device's `XLA Ops` line, so a
# `jax.profiler` session shows the program's stages on the device's
# clock. With no session an annotation checks one flag.
# ---------------------------------------------------------------------------

def _annotate(name: str, meta: Dict[str, Any]) -> Any:
    """An entered `jax.profiler.TraceAnnotation`, or None in a process
    that never imported jax (serving fronts): no session can run there,
    and this module must not be what imports it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    annotation = jax.profiler.TraceAnnotation(name, **meta)
    annotation.__enter__()
    return annotation


class stage:
    """Context manager: time the block on this thread's wall clock and,
    unless `cpu=False`, its CPU clock into `stages` (anything with
    StageTimes' `add`; None records nothing) under `name`, inside a
    profiler annotation carrying `meta` (`annotate=False`: stage only,
    for per-request blocks). `seconds` and `cpu_seconds` hold the
    block's times after exit. Wall minus CPU of a block that never
    blocks is time spent waiting for the GIL or the scheduler. The CPU
    clock is a system call where the wall clock is not: a block that
    runs once per request and needs no CPU reading leaves it out."""

    __slots__ = ("stages", "name", "meta", "annotate", "cpu", "seconds",
                 "cpu_seconds", "_t0", "_c0", "_annotation")

    def __init__(self, stages: Any, name: str, annotate: bool = True,
                 cpu: bool = True, **meta: Any):
        self.stages = stages
        self.name = name
        self.meta = meta
        self.annotate = annotate
        self.cpu = cpu
        self.seconds = 0.0
        self.cpu_seconds: Optional[float] = None

    def __enter__(self) -> "stage":
        self._annotation = (_annotate(self.name, self.meta)
                            if self.annotate else None)
        # the CPU reading inside the wall reading at both ends, so that
        # cpu_seconds <= seconds whatever the clocks' own cost
        self._t0 = time.perf_counter()
        if self.cpu:
            self._c0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.cpu:
            self.cpu_seconds = time.thread_time() - self._c0
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self.stages is not None:
            self.stages.add(self.name, self.seconds, cpu=self.cpu_seconds,
                            attributes=self.meta or None)
        return False


class ThreadStates:
    """The named states of ONE thread: `switch` closes the current state
    and opens the next at the same clock reading, so the states
    partition the thread's time with no gap by construction. Each state
    is a stage (`<prefix>.<state>`: wall, CPU, count) and a profiler
    annotation. Only the owning thread may call it (its CPU clock is
    the thread's own); from its first `switch` to `close` it is that
    thread's `current_states()`, so code deep in the thread's call
    stack switches its state without being handed it."""

    __slots__ = ("stages", "prefix", "train", "state", "opened_at",
                 "closed_at", "_meta", "_t0", "_c0", "_annotation")

    def __init__(self, stages: Any, prefix: str):
        self.stages = stages
        self.prefix = prefix
        #: the sequence number of the train the thread works on, 0 for
        #: none: its owner sets it, and every state opened while it is
        #: set carries it as `train`
        self.train = 0
        #: the open state's name, None before the first switch and
        #: after close()
        self.state: Optional[str] = None
        #: perf_counter of the first switch and of close(): the span the
        #: states partition
        self.opened_at: Optional[float] = None
        self.closed_at: Optional[float] = None
        self._meta: Dict[str, Any] = {}
        self._annotation = None
        self._t0 = self._c0 = 0.0

    def switch(self, state: Optional[str], **meta: Any) -> float:
        """→ the `perf_counter` reading that closed the old state and
        opened the new one (a caller that keeps a stage of its own over
        the same boundary uses it, and the two agree exactly)."""
        now, cpu = time.perf_counter(), time.thread_time()
        if self.state is not None:
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
            if self.stages is not None:
                self.stages.add(f"{self.prefix}.{self.state}",
                                now - self._t0, cpu=cpu - self._c0,
                                attributes=self._meta or None)
        elif self.opened_at is None:
            self.opened_at = now
        if self.train:
            meta["train"] = self.train
        self.state, self._meta, self._t0, self._c0 = state, meta, now, cpu
        if state is None:
            self.closed_at = now
            _tls.states = None
        else:
            _tls.states = self
            self._annotation = _annotate(f"{self.prefix}.{state}", meta)
        return now

    def note(self, **meta: Any) -> None:
        """Facts known only at the end of the current state (how many
        queries a hold gathered): onto its annotation and its span."""
        self._meta = {**self._meta, **meta}
        if self._annotation is not None:
            self._annotation.set_metadata(**meta)

    def close(self) -> None:
        self.switch(None)


class _NoStates:
    """What code that may run off a batcher thread (prewarm, escalation,
    the synchronous path) switches: nothing."""

    __slots__ = ()
    state = None
    train = 0

    def switch(self, state: Optional[str], **meta: Any) -> float:
        return time.perf_counter()

    def note(self, **meta: Any) -> None:
        pass

    def close(self) -> None:
        pass


NO_STATES = _NoStates()


def current_states() -> Any:
    """The calling thread's open ThreadStates, or NO_STATES."""
    return getattr(_tls, "states", None) or NO_STATES


class StandingHeap:
    """The process's collector policy, the one thing of the collector
    that is tuned: what a node builds to keep (segments, stored sources,
    id tables, resident packs, compiled programs) is moved out of the
    collector's sight with `gc.freeze()` once it is built, so that a
    full collection walks what requests made and not the index. Frozen
    objects are still freed by their reference counts; only a cycle
    among them stays until the next `settle(replaced=True)`, which
    thaws, collects and freezes again where long-lived state was
    dropped. One object a process, because the freeze is process-wide:
    it counts the open nodes, does nothing while there is none, and
    thaws when the last one closes."""

    def __init__(self) -> None:
        # reentrant: `opening` and `node_closed` settle under it
        self._lock = threading.RLock()
        self.nodes = 0
        self.freezes = 0
        self.resettles = 0

    def freeze(self) -> None:
        """Something long-lived was just built (a pack became resident,
        a program compiled): a splice of three lists, microseconds, so
        it may run under traffic. Nothing is collected first."""
        with self._lock:
            if self.nodes:
                gc.freeze()
                self.freezes += 1

    def settle(self, replaced: bool = False) -> None:
        """Collect, then freeze, so that no garbage cycle is frozen: a
        full collection, for where no `_search` waits on it (a node has
        opened, a base pack was built). `replaced`: long-lived state was
        dropped too (a base pack generation, an index, a node), so what
        was frozen is thawed first and its dead cycles go."""
        with self._lock:
            if not self.nodes:
                return
            if replaced:
                gc.unfreeze()
                self.resettles += 1
            gc.collect()
            gc.freeze()
            self.freezes += 1

    @contextlib.contextmanager
    def opening(self) -> Iterator[None]:
        """Around a node's construction: when it has ended, the node
        counts and what it built is collected once and frozen. Where no
        other node of the process is serving meanwhile, automatic
        collection pauses for the span: one thread builds millions of
        objects to keep, and every collection on the way would walk all
        it has built so far for nothing."""
        with self._lock:
            paused = not self.nodes and gc.isenabled()
            if paused:
                gc.disable()
        try:
            yield
            with self._lock:
                self.nodes += 1
                self.settle()
        finally:
            if paused:
                gc.enable()

    def node_closed(self) -> None:
        with self._lock:
            self.nodes -= 1
            if self.nodes:
                self.settle(replaced=True)
            else:
                gc.unfreeze()
                gc.collect()
                # (a collection parks the interpreter's immortal objects
                # in the permanent generation: thawed too, so that the
                # count of frozen objects says 0 when nothing is frozen)
                gc.unfreeze()

    def stats(self) -> Dict[str, int]:
        return {"freezes": self.freezes, "resettles": self.resettles,
                "frozen_objects": gc.get_freeze_count()}


HEAP = StandingHeap()


class GcWatch:
    """Full (generation 2) collections of this process: a `gc.callbacks`
    entry that returns at once for generations 0 and 1 and, for a full
    collection, wraps it in a `gc.full` profiler annotation and counts
    it, however short. A full collection stops every Python thread for
    as long as it runs; this is where the program itself says so. What
    a full collection walks is `HEAP`'s to decide (the node's standing
    heap is frozen, so it is what requests made); `stats` reports both,
    what was paused and how often the policy engaged."""

    def __init__(self) -> None:
        self.full_collections = 0
        self.full_pause_seconds = 0.0
        self.longest_pause_seconds = 0.0
        self._t0 = 0.0
        self._annotation: Any = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._annotation = _annotate("gc.full", {})
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self.full_collections += 1
        self.full_pause_seconds += dt
        self.longest_pause_seconds = max(self.longest_pause_seconds, dt)

    def install(self) -> None:
        gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def stats(self) -> Dict[str, Any]:
        return {"full_collections": self.full_collections,
                "full_pause_seconds": round(self.full_pause_seconds, 4),
                "longest_pause_ms":
                    round(self.longest_pause_seconds * 1000.0, 3),
                **HEAP.stats()}
