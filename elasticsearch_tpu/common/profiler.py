"""Host/device profiling layer.

Three coordinated pieces (ISSUE 6):

1. ``HostSampler`` — a continuous low-overhead sampling profiler over
   ``sys._current_frames()``.  Each sample tags the thread with its pool
   (REST threads are tagged by the controller at admission, batcher /
   prewarm threads are recognised by name) and — when a traced request
   is live on that thread — the trace id, so the flamegraph endpoint can
   filter samples down to a single slow trace.  Samples aggregate into
   folded stacks (``pool;thread;frame;... count``) served at
   ``GET /_tpu/profile/flamegraph``.

2. A timeline ring: every sampler tick also polls a gauge source (the
   micro-batcher queue depths) into a bounded ring served at
   ``GET /_tpu/profile/timeline`` — queue depth / device occupancy over
   time, not just totals.

3. ``DeviceProfiler`` — bounded on-disk device trace sessions wrapping
   ``jax.profiler.start_trace`` / ``stop_trace`` behind
   ``POST /_tpu/profile/device/{start,stop}``.

The whole module is built around one invariant: **when no sampler is
running, request threads pay nothing**.  ``tag_thread`` et al. are a
single module-global read + early return — no allocation, no lock.
"""

from __future__ import annotations

import os
import shutil
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------
# thread tag registry (cross-thread: thread-locals are invisible to the
# sampler thread, so taggable state lives in a shared ident-keyed map)
# ---------------------------------------------------------------------

# ident -> [pool, trace_id, stage]; values mutated in place (GIL-atomic
# list item writes) so re-tagging a stage never allocates a new entry.
_TAGS: Dict[int, list] = {}
# samplers currently running in this process; emptiness is THE hot-path
# gate.  A set (not a bool) so two nodes in one test process compose.
_RUNNING: set = set()


def active() -> bool:
    return bool(_RUNNING)


def tag_thread(pool: str, trace_id: Optional[str] = None) -> None:
    """Tag the calling thread for the sampler. No-op while sampler off."""
    if not _RUNNING:
        return
    _TAGS[threading.get_ident()] = [pool, trace_id, None]


def tag_stage(stage: Optional[str]) -> None:
    """Record the calling thread's current trace stage (cheap re-tag)."""
    if not _RUNNING:
        return
    ident = threading.get_ident()
    tag = _TAGS.get(ident)
    if tag is None:
        _TAGS[ident] = [None, None, stage]
    else:
        tag[2] = stage


def untag_thread() -> None:
    if not _TAGS:
        return
    _TAGS.pop(threading.get_ident(), None)


# Pools recognised by thread-name prefix (threads we own but that never
# pass through REST admission).
_NAME_POOLS: Tuple[Tuple[str, str], ...] = (
    ("micro-batcher-pack", "tpu_batcher"),
    ("micro-batcher-complete", "tpu_completer"),
    ("tpu-prewarm", "tpu_prewarm"),
    ("MainThread", "main"),
)


def _pool_for_name(name: str) -> str:
    for prefix, pool in _NAME_POOLS:
        if name.startswith(prefix):
            return pool
    return "other"


# ---------------------------------------------------------------------
# frame walker — shared by the sampler and hot_threads
# ---------------------------------------------------------------------

def walk_frames(frame: Any, limit: int = 64) -> List[str]:
    """Leaf-first ``file.py:func`` frames via raw ``f_back`` traversal.

    Deliberately avoids ``traceback.extract_stack`` (which touches
    linecache and allocates FrameSummary objects) — this runs at
    sampling frequency against every live thread.
    """
    out: List[str] = []
    f = frame
    while f is not None and len(out) < limit:
        code = f.f_code
        fname = code.co_filename
        i = fname.rfind("/")
        out.append((fname[i + 1:] if i >= 0 else fname)
                   + ":" + code.co_name)
        f = f.f_back
    return out


class HostSampler:
    """Continuous sampling profiler over ``sys._current_frames()``.

    Keeps individual samples (not pre-folded counts) in a bounded deque
    so the flamegraph endpoint can slice by retention window and by
    trace id after the fact.
    """

    MAX_SAMPLES = 200_000
    TIMELINE_POINTS = 4096

    def __init__(self, hz: float = 20.0, retention_s: float = 300.0,
                 max_depth: int = 64, role: str = "batcher"):
        # which process this sampler runs in ("batcher" or "front-N");
        # folded lines stay role-free — the flamegraph merge prefixes
        # roles only when serving fronts exist, so single-process
        # output is byte-stable
        self.role = role
        self.hz = max(0.5, min(250.0, float(hz)))
        self.retention_s = max(1.0, float(retention_s))
        self.max_depth = max_depth
        # sample := (ts, pool, thread_name, stage, stack_tuple, trace_id)
        self._samples: deque = deque(maxlen=self.MAX_SAMPLES)
        self._timeline: deque = deque(maxlen=self.TIMELINE_POINTS)
        self.timeline_source: Optional[Callable[[], Dict[str, float]]] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.samples_total = 0
        self.ticks_total = 0
        self._busy_s = 0.0
        self._started_at = 0.0
        self._names: Dict[int, str] = {}

    # -- lifecycle ----------------------------------------------------

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> None:
        if self.running:
            return
        self._stop.clear()
        self._started_at = time.perf_counter()
        self._busy_s = 0.0
        self._thread = threading.Thread(
            target=self._loop, name="host-profiler", daemon=True)
        _RUNNING.add(id(self))
        self._thread.start()

    def stop(self) -> None:
        _RUNNING.discard(id(self))
        if not _RUNNING:
            _TAGS.clear()
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
        self._thread = None

    # -- sampling loop ------------------------------------------------

    def _loop(self) -> None:
        interval = 1.0 / self.hz
        me = threading.get_ident()
        while not self._stop.wait(interval):
            t0 = time.perf_counter()
            try:
                self._tick(me)
            except Exception:  # never kill the sampler on a bad tick
                pass
            self._busy_s += time.perf_counter() - t0

    def _tick(self, me: int) -> None:
        now = time.time()
        frames = sys._current_frames()
        names = self._names
        refresh = any(ident not in names for ident in frames)
        if refresh:
            self._names = names = {
                t.ident: t.name for t in threading.enumerate()
                if t.ident is not None}
        self.ticks_total += 1
        append = self._samples.append
        for ident, frame in frames.items():
            if ident == me:
                continue
            stack = tuple(reversed(walk_frames(frame, self.max_depth)))
            tag = _TAGS.get(ident)
            name = names.get(ident, "?")
            if tag is not None and tag[0]:
                pool, trace_id, stage = tag[0], tag[1], tag[2]
            else:
                pool = _pool_for_name(name)
                trace_id = tag[1] if tag else None
                stage = tag[2] if tag else None
            append((now, pool, name, stage, stack, trace_id))
            self.samples_total += 1
        src = self.timeline_source
        if src is not None:
            try:
                gauges = src()
                if gauges:
                    self._timeline.append((now, gauges))
            except Exception:
                pass
        self._expire(now)

    def _expire(self, now: float) -> None:
        cutoff = now - self.retention_s
        samples = self._samples
        while samples and samples[0][0] < cutoff:
            samples.popleft()
        timeline = self._timeline
        while timeline and timeline[0][0] < cutoff:
            timeline.popleft()

    # -- views --------------------------------------------------------

    def folded(self, trace_id: Optional[str] = None,
               top: Optional[int] = None,
               pool: Optional[str] = None) -> List[Tuple[str, int]]:
        """Aggregated folded stacks, hottest first.

        Line format: ``pool;thread[;stage];frame;...;leaf_frame``.
        """
        counts: Dict[str, int] = {}
        for ts, p, name, stage, stack, tid in list(self._samples):
            if trace_id is not None and tid != trace_id:
                continue
            if pool is not None and p != pool:
                continue
            head = p + ";" + name + ((";" + stage) if stage else "")
            key = head + ";" + ";".join(stack) if stack else head
            counts[key] = counts.get(key, 0) + 1
        ranked = sorted(counts.items(), key=lambda kv: -kv[1])
        return ranked[:top] if top else ranked

    def folded_text(self, **kw: Any) -> str:
        return "".join(f"{line} {count}\n"
                       for line, count in self.folded(**kw))

    def timeline(self, limit: int = 0) -> List[Dict[str, Any]]:
        points = list(self._timeline)
        if limit:
            points = points[-limit:]
        return [dict(gauges, t=ts) for ts, gauges in points]

    def overhead_fraction(self) -> float:
        wall = time.perf_counter() - self._started_at
        if wall <= 0.0 or not self._started_at:
            return 0.0
        return self._busy_s / wall

    def stats(self) -> Dict[str, Any]:
        return {
            "running": self.running,
            "role": self.role,
            "hz": self.hz,
            "retention_s": self.retention_s,
            "samples_total": self.samples_total,
            "ticks_total": self.ticks_total,
            "retained_samples": len(self._samples),
            "timeline_points": len(self._timeline),
            "overhead_fraction": round(self.overhead_fraction(), 6),
        }


# ---------------------------------------------------------------------
# device profiling sessions
# ---------------------------------------------------------------------

class DeviceProfiler:
    """Bounded on-disk device trace sessions around jax.profiler.

    At most ``max_sessions`` session directories are kept under
    ``base_dir``; starting a new one evicts the oldest.  Failures to
    import or start the backend profiler are reported, not raised —
    the serving path never depends on profiler availability.
    """

    def __init__(self, base_dir: str, max_sessions: int = 4):
        self.base_dir = base_dir
        self.max_sessions = max(1, int(max_sessions))
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._started_at = 0.0
        self.sessions_total = 0
        self.last_error: Optional[str] = None

    def start(self, name: Optional[str] = None) -> Dict[str, Any]:
        with self._lock:
            if self._active_dir is not None:
                return {"started": False, "error": "session already running",
                        "dir": self._active_dir}
            session = name or f"session-{self.sessions_total:04d}-{int(time.time())}"
            session = session.replace("/", "_").replace("..", "_")
            target = os.path.join(self.base_dir, session)
            try:
                os.makedirs(target, exist_ok=True)
                self._evict_beyond(keep=self.max_sessions - 1,
                                   protect=target)
                import jax
                # the Python tracer off, TraceMe level 1 on (as the
                # benchmark records): a session on a loaded node holds
                # the serving pipeline's own annotations (batcher.*,
                # completer.*, gc.full) beside the device's ops, not
                # every Python call of every request thread
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(target, profiler_options=options)
            except Exception as exc:
                self.last_error = f"{type(exc).__name__}: {exc}"
                return {"started": False, "error": self.last_error}
            self._active_dir = target
            self._started_at = time.perf_counter()
            self.sessions_total += 1
            return {"started": True, "dir": target}

    def stop(self) -> Dict[str, Any]:
        with self._lock:
            if self._active_dir is None:
                return {"stopped": False, "error": "no session running"}
            target, dt = self._active_dir, \
                time.perf_counter() - self._started_at
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as exc:
                self.last_error = f"{type(exc).__name__}: {exc}"
                self._active_dir = None
                return {"stopped": False, "error": self.last_error,
                        "dir": target}
            self._active_dir = None
            return {"stopped": True, "dir": target,
                    "seconds": round(dt, 3)}

    def _evict_beyond(self, keep: int, protect: str) -> None:
        try:
            entries = [os.path.join(self.base_dir, e)
                       for e in os.listdir(self.base_dir)]
            dirs = sorted((d for d in entries
                           if os.path.isdir(d) and d != protect),
                          key=os.path.getmtime)
            for stale in dirs[:max(0, len(dirs) - keep)]:
                shutil.rmtree(stale, ignore_errors=True)
        except OSError:
            pass

    def info(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "active": self._active_dir is not None,
            "base_dir": self.base_dir,
            "max_sessions": self.max_sessions,
            "sessions_total": self.sessions_total,
        }
        if self._active_dir is not None:
            out["dir"] = self._active_dir
            out["seconds"] = round(
                time.perf_counter() - self._started_at, 3)
        if self.last_error:
            out["last_error"] = self.last_error
        return out


# ---------------------------------------------------------------------
# node-facing facade
# ---------------------------------------------------------------------

class Profiler:
    """Per-node facade: the host sampler + device session manager.

    Constructed unconditionally (so endpoints and metrics stay shaped
    the same) but ``start()`` only spawns the sampler thread when
    ``search.profiler.enabled`` is on.
    """

    def __init__(self, *, enabled: bool = False, hz: float = 20.0,
                 retention_s: float = 300.0,
                 device_dir: str = "profile_sessions"):
        self.enabled = bool(enabled)
        self.sampler = HostSampler(hz=hz, retention_s=retention_s)
        self.device = DeviceProfiler(device_dir)

    def start(self) -> None:
        if self.enabled:
            self.sampler.start()

    def close(self) -> None:
        self.sampler.stop()

    def info(self) -> Dict[str, Any]:
        return {"enabled": self.enabled,
                "sampler": self.sampler.stats(),
                "device": self.device.info()}
