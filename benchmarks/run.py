"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <config>.<traffic> --seed <n>
                             --seconds <run_seconds> --trace <0|1>

The cell's configuration (`benchmarks/configs/<config>.json`), traffic mix
(`benchmarks/traffic/<traffic>.json`) and per-layer readers
(`benchmarks/layer_metrics/`) are found by the names in BENCHMARK.json;
adding a cell adds files and an entry there and edits nothing here.

What a run does, in order: refuse anything but a TPU; build the
configuration's index if this checkout has none (a child process, once);
open it in a node as `python -m elasticsearch_tpu.node` would, on an
ephemeral port; start the load generator processes (`esbench/loadgen.py`,
no jax); warm the launch shapes by driving the query set's strata at a
few client counts; then ramp, window, drain as one running stream, with
this process doing nothing but serve between ramp and drain; then hold a
sample of the window's responses to the stored reference and the node's
counters to the no-hidden-fallback rule; print one JSON line.

Builder's options, ignored by the contract's run: `--probe` (several
windows after one set-up, a line each on stderr) and `--rehearse` (any
backend, toy size, no device metric: proves the command, measures
nothing).
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import base64  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import http.client  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

from esbench import (compare, corpus, hostspans, layers, reference, tracered,  # noqa: E402
                     traffic, window)
from esbench.loadgen import now_ns, sleep_until  # noqa: E402
from esbench.peaks import peaks_for  # noqa: E402

OUT_DIR = os.path.join(ROOT, "bench_out")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}
SAMPLE_QUERIES = 256
#: a rehearsal's toy size: enough docs for 1000 hits, few enough clients
#: for a CPU backend to answer
REHEARSE_DOCS = 20_000
REHEARSE_QUERIES = 400
REHEARSE_CLIENTS = 32
REHEARSE_RATE = 20.0


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T_PROCESS:.1f}s] {msg}", file=sys.stderr,
          flush=True)


class BenchFailure(Exception):
    """The run cannot produce a result line."""


# ---------------------------------------------------------------------------
# the cell: BENCHMARK.json → configuration, traffic, metrics
# ---------------------------------------------------------------------------

def load_cell(workload: str, bench_path: Optional[str] = None,
              traffic_dir: Optional[str] = None) -> Dict[str, Any]:
    """The cell's files, found by the names in BENCHMARK.json alone."""
    with open(bench_path or os.path.join(ROOT, "BENCHMARK.json"), "r",
              encoding="utf-8") as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise BenchFailure(f"no workload [{workload}] in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"]), "r", encoding="utf-8") as f:
        config = json.load(f)
    spec = traffic.load_traffic(os.path.join(
        traffic_dir or os.path.join(HERE, "traffic"), cell["traffic"] + ".json"))
    if spec.get("operator", "or") not in reference.OPERATORS:
        raise BenchFailure(f"traffic [{cell['traffic']}] sends operator "
                           f"[{spec['operator']}], which has no reference: "
                           f"esbench/reference.py knows {reference.OPERATORS}")

    def metrics_of(kind: str) -> List[Dict[str, Any]]:
        return [m for m in bench[kind]
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config, "traffic": spec,
            "end_to_end": metrics_of("end_to_end"),
            "per_layer": metrics_of("per_layer")}


# ---------------------------------------------------------------------------
# the index: built once per checkout by a child, reopened by every run
# ---------------------------------------------------------------------------

def index_dir_for(config: Dict[str, Any]) -> str:
    key = json.dumps({"generator": config["generator"], "index": config["index"]},
                     sort_keys=True)
    digest = hashlib.sha256(key.encode("utf-8")).hexdigest()[:12]
    return os.path.join(OUT_DIR, "index", f"{config['name']}-{digest}")


def ensure_index(config: Dict[str, Any]) -> Tuple[str, float]:
    """→ (directory, seconds spent building: 0.0 when it was there)."""
    final = index_dir_for(config)
    if os.path.isfile(os.path.join(final, "manifest.json")):
        return final, 0.0
    t0 = time.monotonic()
    shutil.rmtree(final, ignore_errors=True)  # an unfinished build
    os.makedirs(final)
    cfg_path = os.path.join(final, "config.json")
    with open(cfg_path, "w", encoding="utf-8") as f:
        json.dump(config, f, indent=1)
    log(f"building the index of [{config['name']}] in {final}")
    rc = build_child("--config", cfg_path, "--out", final)
    if rc != 0:
        raise BenchFailure(f"build_index.py exited {rc}")
    return final, time.monotonic() - t0


def build_child(*args: str) -> int:
    """build_index.py in a process of its own, off the chip → its exit code."""
    return subprocess.run([sys.executable, os.path.join(HERE, "build_index.py"), *args],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          stdout=sys.stderr, check=False).returncode


def ensure_reference(index_dir: str, operator: str) -> Tuple[str, float]:
    """The stored reference of the traffic's operator → (file, seconds
    spent making it). An index directory is built with the `or` one; that
    of another operator is added by the first run that asks for it, from
    the seed and without indexing again. Responses are never held to the
    top-k of another operator: no file, no run."""
    path = os.path.join(index_dir, reference.stored_name(operator))
    if os.path.isfile(path):
        return path, 0.0
    t0 = time.monotonic()
    log(f"adding the reference of operator [{operator}] to {index_dir}")
    rc = build_child("--config", os.path.join(index_dir, "config.json"), "--out",
                     index_dir, "--reference-only", "--operator", operator)
    if rc != 0 or not os.path.isfile(path):
        raise BenchFailure(f"build_index.py --reference-only exited {rc}: "
                           f"no reference of operator [{operator}]")
    return path, time.monotonic() - t0


# ---------------------------------------------------------------------------
# load generator processes
# ---------------------------------------------------------------------------

class Generators:
    """The loadgen children: one command to each, one reply from each."""

    def __init__(self, n: int):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # nothing of jax's concerns them
        self.procs = [subprocess.Popen(
            [sys.executable, os.path.join(HERE, "esbench", "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
            for _ in range(n)]

    def __len__(self) -> int:
        return len(self.procs)

    def share(self, clients: int, p: int) -> List[int]:
        return list(range(p, clients, len(self.procs)))

    def send(self, cmds: Sequence[Dict[str, Any]]) -> None:
        for proc, cmd in zip(self.procs, cmds):
            proc.stdin.write(json.dumps(cmd) + "\n")
            proc.stdin.flush()

    def receive(self) -> List[Dict[str, Any]]:
        replies = []
        for proc in self.procs:
            line = proc.stdout.readline()
            if not line:
                raise BenchFailure("a load generator process died")
            reply = json.loads(line)
            if reply.get("error") or reply.get("imported_jax"):
                raise BenchFailure(f"load generator: {reply}")
            replies.append(reply)
        return replies

    def call(self, cmds: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
        self.send(cmds)
        return self.receive()

    def close(self) -> None:
        for proc in self.procs:
            try:
                if proc.poll() is None:
                    proc.stdin.write('{"cmd": "quit"}\n')
                    proc.stdin.flush()
            except OSError:
                pass
        for proc in self.procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            proc.stdin.close()
            proc.stdout.close()


# ---------------------------------------------------------------------------
# the serving process's side
# ---------------------------------------------------------------------------

class CompileLog:
    """Every backend compile (a cache replay counts: it stalls a launch
    just the same) and every persistent-cache hit and miss, by
    jax.monitoring."""

    def __init__(self) -> None:
        import jax
        self.jax = jax
        self.events: List[Tuple[int, str, float]] = []
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw: Any) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.events.append((now_ns(), str(kw.get("fun_name")), duration))

    def _on_event(self, event: str, **kw: Any) -> None:
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1

    def since(self, t_ns: int) -> List[Tuple[int, str, float]]:
        return [e for e in self.events if e[0] >= t_ns]

    def close(self) -> None:
        self.jax.monitoring.unregister_event_duration_listener(self._on_duration)
        self.jax.monitoring.unregister_event_listener(self._on_event)


class GcTimer:
    """Collector pauses of the serving process, on the monotonic clock. A
    `gc.callbacks` entry: it observes and changes nothing. Installed in
    traced runs (to name the trace's idle gaps) and in the builder's probe
    (to see where in a stream the full collections fall)."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[int, int, str]] = []
        self._start = 0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._start = now_ns()
        else:
            self.pauses.append((self._start, now_ns(), f"gc_gen{info.get('generation')}"))

    def long_pauses(self, since_ns: int, min_s: float = 0.02
                    ) -> List[Tuple[float, float, str]]:
        """→ (seconds after `since_ns`, seconds long, generation)."""
        return [((s - since_ns) / 1e9, (e - s) / 1e9, name)
                for s, e, name in self.pauses
                if s >= since_ns and e - s >= min_s * 1e9]


def settle_heap() -> float:
    """One full collection of the serving process before the stream starts →
    its seconds. When the next one falls depends on how many objects
    survived since the last; after this call that count starts from zero
    in every run, so the full collections fall at the same points of the
    stream instead of anywhere. Since PR 34 the node keeps its standing
    heap frozen (`tracing.StandingHeap`), so this walks what the warm-up's
    requests left, in milliseconds, and a full collection under load is
    tens of milliseconds, three seconds apart, where it was a second that
    stopped every Python thread (PERF.md, Findings, PR 23 and PR 34). The
    benchmark disables and tunes nothing of the collector: every pause
    inside the window is in the window's numbers."""
    t0 = time.monotonic()
    gc.collect()
    return time.monotonic() - t0


def get_stats(conn: http.client.HTTPConnection) -> Dict[str, Any]:
    conn.request("GET", "/_tpu/stats")
    resp = conn.getresponse()
    data = resp.read()
    if resp.status != 200:
        raise BenchFailure(f"GET /_tpu/stats -> HTTP {resp.status}")
    return json.loads(data)


def memory_peak_bytes(devices: Sequence[Any]) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one measured stream: ramp, window, drain
# ---------------------------------------------------------------------------

def measure(gens: Generators, conn: http.client.HTTPConnection, spec: Dict[str, Any],
            seed: int, seconds: float, n_queries: int, sample: List[int],
            run_dir: str, trace_dir: Optional[str], devices: Sequence[Any],
            compiles: CompileLog) -> Dict[str, Any]:
    """Drive one stream and return the raw material of the metrics."""
    ramp_s, drain_s = float(spec["ramp_s"]), float(spec["drain_s"])
    trace_s = min(float(spec.get("trace_s", 5.0)), seconds / 2)
    loop = spec["loop"]
    clients = int(spec["clients"]) if loop == "closed" else int(spec["workers"])
    before = get_stats(conn)
    settle_s = settle_heap()
    t_start = now_ns() + int(0.5e9)
    t0 = t_start + int(ramp_s * 1e9)
    t1 = t0 + int(seconds * 1e9)
    t_stop = t1 + int(drain_s * 1e9)
    common = {"cmd": loop, "seed": seed, "t_start_ns": t_start, "t0_ns": t0,
              "t1_ns": t1, "t_stop_ns": t_stop, "sample": sample,
              "total_clients": clients, "procs": len(gens), "spec": spec,
              "stagger_s": float(spec.get("stagger_s", 0.5))}
    gens.send([{**common, "proc": p, "clients": gens.share(clients, p),
                "out": os.path.join(run_dir, f"gen_{p}.npz")}
               for p in range(len(gens))])
    out: Dict[str, Any] = {"t_start_ns": t_start, "t0_ns": t0, "t1_ns": t1,
                           "before": before, "loop": loop, "settle_s": settle_s}
    sleep_until(t0)
    out["setup_s"] = time.monotonic() - _T_PROCESS
    if trace_dir is None:
        # nothing of the benchmark's runs here until the window is over
        sleep_until(t1)
    else:
        import jax
        out["s0"] = get_stats(conn)
        sleep_until(t1 - int((trace_s + 1.0) * 1e9))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        # the trace's clock starts with the session, at this call or within
        # the ~0.1 s it takes: close enough to lay second-long pauses on it
        out["trace_zero_ns"] = now_ns()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        out["sa"] = get_stats(conn)
        out["ta_ns"] = now_ns()
        sleep_until(t1)
        out["s1"] = get_stats(conn)
        jax.profiler.stop_trace()
    out["memory_peak_bytes"] = memory_peak_bytes(devices)
    gens.receive()
    out["after"] = get_stats(conn)
    out["late_compiles"] = compiles.since(t_start)
    parts = [np.load(os.path.join(run_dir, f"gen_{p}.npz")) for p in range(len(gens))]
    for key in ("due_ns", "send_ns", "done_ns", "query", "ok", "status", "nbytes"):
        out[key] = np.concatenate([part[key] for part in parts])
    out["gen_extra"] = [json.loads(str(part["extra"])) for part in parts]
    kept: Dict[int, Tuple[int, bytes]] = {}
    for part in parts:
        blob, offs = part["sample_bytes"].tobytes(), part["sample_offsets"]
        for i, (q, done) in enumerate(zip(part["sample_query"].tolist(),
                                          part["sample_done_ns"].tolist())):
            if q not in kept or done < kept[q][0]:
                kept[q] = (done, blob[offs[i]:offs[i + 1]])
    out["samples"] = {q: body for q, (_done, body) in kept.items()}
    return out


def sample_queries(seed: int, n_queries: int) -> List[int]:
    """The queries whose first in-window response a run of `seed` keeps."""
    return np.random.default_rng([seed, 4]).choice(
        n_queries, size=min(SAMPLE_QUERIES, n_queries), replace=False).tolist()


def check_samples(samples: Dict[int, bytes], ref: Any, k: int
                  ) -> Tuple[int, int, float, List[str], int]:
    """→ (checked, near-tie swaps, widest relative score gap, mismatches,
    responses whose `hits.total` is a lower bound: `compare_response`
    admits one that is not above the reference's count). `ref` is the
    stored reference of the operator the samples were sent under."""
    ref_k = int(ref["k"])
    if k > ref_k:
        raise BenchFailure(f"size {k} is beyond the stored reference's {ref_k}")
    swaps, gap, bad, bounds = 0, 0.0, [], 0
    offsets, docs, scores, totals = (ref["offsets"], ref["docs"], ref["scores"],
                                     ref["totals"])
    for q, body in sorted(samples.items()):
        lo, hi = int(offsets[q]), int(offsets[q + 1])
        ref_scores = scores[lo:hi].tolist()
        try:
            resp = json.loads(body)
            bounds += resp["hits"]["total"]["relation"] != "eq"
            gap = max(gap, compare.score_gap(resp, ref_scores))
            swaps += compare.compare_response(
                resp, int(totals[q]),
                [corpus.doc_id(d) for d in docs[lo:hi].tolist()], ref_scores, k)
        except (compare.Mismatch, KeyError, ValueError, TypeError) as exc:
            bad.append(f"query {q}: {exc}")
    return len(samples), swaps, gap, bad, bounds


def facts_of(m: Dict[str, Any], seconds: float, setup: Dict[str, float],
             manifest: Dict[str, Any], spec: Dict[str, Any],
             reduced: Optional[Dict[str, Any]], device_kind: str
             ) -> Dict[str, float]:
    """Everything the per-layer readers may read, as flat numbers."""
    t0, t1 = m["t0_ns"], m["t1_ns"]
    facts: Dict[str, float] = {f"setup.{k}": v for k, v in setup.items()}
    after = layers.flatten(m["after"], "after", {})
    facts.update(after)
    pack = m["after"]["pack_cache"]["packs"].get(
        f"{manifest['index']}/{manifest['field']}", {})
    layers.flatten(pack, "after.pack", facts)
    if "s0" in m:
        s0 = layers.flatten(m["s0"], "s", {})
        s1 = layers.flatten(m["s1"], "s", {})
        sa = layers.flatten(m["sa"], "s", {})
        facts.update(layers.difference(s1, s0, "s", "window"))
        facts.update(layers.difference(s1, sa, "s", "traced"))
    lat = window.latencies_ms(m["due_ns"], m["done_ns"], m["ok"], t0, t1)
    for q in (50, 95, 99):
        value = window.percentile(lat, q)
        if value is not None:
            facts[f"gen.latency_p{q}_ms"] = value
    due = window.due_in_window(m["due_ns"], t0, t1)
    if m["loop"] == "open" and due.any():
        facts["gen.late_p99_ms"] = float(np.percentile(
            (m["send_ns"][due] - m["due_ns"][due]) / 1e6, 99))
    facts["gen.window_s"] = seconds
    facts["gen.cpu_s"] = sum(e.get("gen_cpu_s", 0.0) for e in m["gen_extra"])
    if m["memory_peak_bytes"] is not None:
        facts["device.memory_peak_bytes"] = float(m["memory_peak_bytes"])
    facts["device.docs"] = float(manifest["docs"])
    facts["request.size"] = float(spec["size"])
    if reduced is not None:
        facts["trace.busy_s"] = reduced["busy_s"]
        facts["trace.window_s"] = reduced["window_s"]
        facts["trace.idle_s"] = reduced["window_s"] - reduced["busy_s"]
        facts["trace.module_s"] = sum(reduced["module_seconds"].values())
        for name, count in reduced["op_counts"].items():
            facts[f"trace.op_count.{name}"] = float(count)
        facts["device.peak_hbm_bytes_per_s"] = peaks_for(device_kind)["hbm_bytes_per_s"]
    return facts


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def warm_up(gens: Generators, conn: http.client.HTTPConnection, spec: Dict[str, Any],
            postings: np.ndarray, n_terms: np.ndarray, compiles: CompileLog
            ) -> Dict[str, float]:
    """Open the connections, then drive every stratum alone at every client
    count, until a pass is answered by the kernel alone → set-up facts."""
    strata = traffic.warm_strata(spec, postings, n_terms)
    most = max([int(spec.get("clients", 0)), int(spec.get("workers", 0))]
               + [c for _name, _idx, phases in strata for c, _r in phases])
    replies = gens.call([{"cmd": "connect", "clients": gens.share(most, p)}
                         for p in range(len(gens))])
    if sum(r["connected"] for r in replies) != most:
        raise BenchFailure(f"only {sum(r['connected'] for r in replies)} of "
                           f"{most} connections opened")
    t_warm = time.monotonic()
    stats = get_stats(conn)
    pack_s = None
    for attempt in range(3):
        t_pass, n_pass, before = time.monotonic(), len(compiles.events), stats
        for name, idx, phases in strata:
            for n_clients, per_client in phases:
                n0 = len(compiles.events)
                replies = gens.call([{
                    "cmd": "warm", "queries": idx.tolist(), "total_clients": n_clients,
                    "clients": gens.share(n_clients, p),
                    "requests_per_client": per_client} for p in range(len(gens))])
                compiled: Dict[str, List[float]] = {}  # program → its compiles' seconds
                for _t, fun, secs in compiles.events[n0:]:
                    compiled.setdefault(fun, []).append(secs)
                log(f"warm [{name}] {len(idx)} queries x{n_clients} clients: "
                    f"{sum(r['sent'] for r in replies)} sent, "
                    f"{sum(r['failed'] for r in replies)} failed, "
                    f"{max(r['seconds'] for r in replies):.1f}s, "
                    f"{len(compiles.events) - n0} compile events"
                    + "".join(f" {fun} x{len(secs)} max {max(secs):.1f}s"
                              for fun, secs in sorted(compiled.items())[:16])
                    + "".join(f"; failure {f}" for r in replies for f in r["failures"][:1]))
                if pack_s is None:  # the first request built and placed the pack
                    pack_s = float(get_stats(conn)["stages"].get(
                        "pack_get", {}).get("seconds", 0.0))
        stats = get_stats(conn)
        # a cold compile can outlast the node's 30 s batch timeout: the
        # waiting requests then fall to the planner and the kernel path
        # trips until a probe succeeds. Go round again until a pass is
        # answered by the kernel alone
        fell = stats["fallback"] - before["fallback"]
        log(f"warm pass {attempt}: {len(compiles.events) - n_pass} compile events, "
            f"{fell} fallbacks, tripped={stats['tripped']}, "
            f"{time.monotonic() - t_pass:.1f}s")
        if fell == 0 and not stats["tripped"]:
            break
    else:
        raise BenchFailure(f"warm-up never ran on the kernel alone: {stats['last_error']}")
    setup = {"pack_s": pack_s or 0.0,
             "warm_s": time.monotonic() - t_warm - (pack_s or 0.0),
             "compilations": float(len(compiles.events)),
             "cache_hits": float(compiles.cache["cache_hits"]),
             "cache_misses": float(compiles.cache["cache_misses"])}
    log(f"warm-up {setup}; compile events: "
        + json.dumps([(n, round(s, 2)) for _t, n, s in compiles.events]))
    return setup


def probe_plans(probe: str, spec: Dict[str, Any]) -> List[Tuple[Dict[str, Any], float]]:
    """`--probe 384:20,256:45:2` → [(traffic with that client count or rate
    and ramp, seconds)]."""
    key = "clients" if spec["loop"] == "closed" else "rate_per_s"
    plans = []
    for item in probe.split(","):
        parts = item.split(":")
        plan = dict(spec)
        plan[key] = int(parts[0]) if key == "clients" else float(parts[0])
        if len(parts) > 2:
            plan["ramp_s"] = float(parts[2])
        plans.append((plan, float(parts[1])))
    return plans


def rehearsal_traffic(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The same mix with few clients: every phase and path of the command,
    at a load a CPU backend answers in seconds."""
    def few(phases: List[List[int]]) -> List[List[int]]:
        return [[min(int(c), REHEARSE_CLIENTS), int(r)] for c, r in phases]

    spec = json.loads(json.dumps(spec))
    for key in ("clients", "workers"):
        if key in spec:
            spec[key] = min(int(spec[key]), REHEARSE_CLIENTS)
    if "rate_per_s" in spec:
        spec["rate_per_s"] = min(float(spec["rate_per_s"]), REHEARSE_RATE)
    if "warm_clients" in spec:
        spec["warm_clients"] = few(spec["warm_clients"])
    for stratum in spec.get("warm_strata", []):
        if "clients" in stratum:
            stratum["clients"] = few(stratum["clients"])
    return spec


def parse_args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--probe", default="",
                        help="builder only: comma list of clients_or_rate:seconds"
                             "[:ramp_s] windows to run after one set-up")
    parser.add_argument("--dump", default="",
                        help="builder only: directory for the facts, the reduced "
                             "trace and a description of the trace's planes")
    parser.add_argument("--rehearse", action="store_true",
                        help="any backend, toy size, no device metric")
    return parser.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    loaded = load_cell(args.workload)
    cell, config, spec = loaded["cell"], loaded["config"], dict(loaded["traffic"])

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < int(cell["chips"])):
        log(f"cell [{args.workload}] needs {cell['chips']} TPU chip(s); "
            f"jax reports {device}: refusing to measure")
        return 2
    if args.rehearse:
        config = json.loads(json.dumps(config))
        config["generator"]["docs"] = min(REHEARSE_DOCS, config["generator"]["docs"])
        config["generator"]["num_queries"] = min(REHEARSE_QUERIES,
                                                 config["generator"]["num_queries"])
        spec = rehearsal_traffic(spec)
    log(f"device {device}; cell {args.workload} seed {args.seed}")

    index_dir, index_s = ensure_index(config)
    ref_path, ref_s = ensure_reference(index_dir, spec.get("operator", "or"))
    with open(os.path.join(index_dir, "manifest.json"), "r", encoding="utf-8") as f:
        manifest = json.load(f)
    qnpz = np.load(os.path.join(index_dir, "queries.npz"))
    n_queries = int(qnpz["postings"].shape[0])
    queries = [qnpz["terms"][qnpz["offsets"][i]:qnpz["offsets"][i + 1]].tolist()
               for i in range(n_queries)]
    bodies = [traffic.request_body(corpus.query_text(q), spec, manifest["field"])
              for q in queries]
    run_dir = os.path.join(OUT_DIR, "run", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    trace_dir = os.path.join(run_dir, "trace") if args.trace else None

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node, serve

    compiles = CompileLog()
    gc_timer = GcTimer()
    gens: Optional[Generators] = None
    t_load = time.monotonic()
    node = Node(os.path.join(index_dir, "data"),
                settings=Settings.of(config.get("node_settings", {})))
    node.start_refresher()
    server = serve(node, port=0)
    conn = http.client.HTTPConnection("127.0.0.1", server.server_address[1],
                                      timeout=600)
    setup = {"index_s": index_s + ref_s, "load_s": time.monotonic() - t_load}
    try:
        if node.tpu_search is None:
            raise BenchFailure("the node has no TPU serving path")
        gens = Generators(int(spec.get("generator_processes", 4)))
        gens.call([{"cmd": "init", "port": server.server_address[1],
                    "path": f"/{manifest['index']}/_search",
                    "bodies": [base64.b64encode(b).decode("ascii") for b in bodies]}
                   ] * len(gens))

        setup.update(warm_up(gens, conn, spec, qnpz["postings"],
                             np.diff(qnpz["offsets"]), compiles))

        # ---- the stream(s) -------------------------------------------------
        sample = sample_queries(args.seed, n_queries)
        plans = probe_plans(args.probe, spec) if args.probe else [(spec, args.seconds)]
        if args.trace or args.probe:
            gc.callbacks.append(gc_timer)
        ref = np.load(ref_path)
        line: Dict[str, Any] = {}
        for i, (plan_spec, seconds) in enumerate(plans):
            m = measure(gens, conn, plan_spec, args.seed + i, seconds, n_queries,
                        sample, run_dir, trace_dir, devices, compiles)
            line = result_line(m, loaded, plan_spec, seconds, setup, manifest, ref,
                               device, trace_dir, gc_timer, args)
            if args.probe:
                full = [secs for at, secs, name in gc_timer.long_pauses(m["t0_ns"])
                        if name == "gc_gen2" and at < seconds]
                log("probe " + json.dumps({
                    "plan": [plan_spec.get("clients"), plan_spec.get("rate_per_s"),
                             seconds, plan_spec["ramp_s"]],
                    "settle_s": m["settle_s"],
                    "gc_full_in_window": [len(full), sum(full)],
                    "gc_pauses_after_start": gc_timer.long_pauses(m["t_start_ns"]),
                    **line}))
    finally:
        if gc_timer in gc.callbacks:
            gc.callbacks.remove(gc_timer)
        if gens is not None:
            gens.close()
        conn.close()
        server.shutdown()
        server.server_close()
        node.close()
        compiles.close()
        shutil.rmtree(run_dir, ignore_errors=True)
    for name, pair in line["compared"].items():
        print(f"compared {name} {pair['value']!r} {pair['limit_is']} {pair['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def result_line(m: Dict[str, Any], loaded: Dict[str, Any], spec: Dict[str, Any],
                seconds: float, setup: Dict[str, float], manifest: Dict[str, Any],
                ref: Any, device: Dict[str, Any], trace_dir: Optional[str],
                gc_timer: GcTimer, args: argparse.Namespace) -> Dict[str, Any]:
    """Checks, metrics and breakdown of one measured stream → the line."""
    t0, t1 = m["t0_ns"], m["t1_ns"]
    # every request answered 200 was answered by the kernel, and the node
    # served no other: a refused or broken request never reached it
    sent, answered = int(m["ok"].shape[0]), int(m["ok"].sum())
    kernel = compare.kernel_checks(m["before"], m["after"], answered,
                                   int(loaded["cell"]["chips"]), device["platform"])
    problems = compare.failures(kernel)
    checked, swaps, gap, bad, bounds = check_samples(m["samples"], ref,
                                                     int(spec["size"]))
    if checked == 0:
        problems.append("no sampled response to check")
    problems += bad[:5]
    if m["late_compiles"]:
        problems.append("compilations after the ramp began: " + json.dumps(
            [(n, round(s, 2)) for _t, n, s in m["late_compiles"]]))
    counts = window.attempted_failed(m["due_ns"], m["done_ns"], m["ok"], t0, t1,
                                     m["loop"])
    for p in problems:
        log(f"NOT CORRECT: {p}")
    # every number compared, beside its limit: (value, limit, which side of
    # the limit holds); an exact comparison's limit is 0
    served = next(got for name, got, _want in kernel if name == "served")
    counters = {name: got for name, got, want in kernel
                if type(want) is int and want == 0}
    compared = {
        "responses_sampled": (checked, 1, "at_least"),
        "responses_differing": (len(bad), 0, "at_most"),
        "score_rel_gap_max": (gap, compare.REL_TOL, "at_most"),
        "answered_not_served": (answered - served, 0, "at_most"),
        **{name: (got, 0, "at_most") for name, got in counters.items()},
        "other_kernel_rules_broken": (sum(
            got != want for name, got, want in kernel
            if name != "served" and name not in counters), 0, "at_most"),
        "compiles_after_ramp": (len(m["late_compiles"]), 0, "at_most"),
    }

    reduced = None
    if trace_dir is not None:
        path = tracered.newest_xplane(trace_dir)
        # the launch thread's states name the idle gaps (they share the
        # trace's clock); the harness's own collector clock, laid on it to
        # ~0.1 s, names what a program without annotations leaves
        pauses = [(at * 1e9, (at + secs) * 1e9, name) for at, secs, name
                  in gc_timer.long_pauses(m["trace_zero_ns"], 0.005)]
        reduced = (tracered.reduce_trace(path, hostspans.pauses(path) + pauses)
                   if path else None)
        if reduced is None and not args.rehearse:
            raise BenchFailure("the trace holds no device operation")
    facts = facts_of(m, seconds, setup, manifest, spec, reduced, device["kind"])
    facts["setup.total_s"] = m["setup_s"]
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        stem = os.path.join(args.dump, f"{args.workload}.trace{args.trace}")
        with open(stem + ".facts.json", "w", encoding="utf-8") as f:
            json.dump(facts, f, indent=1, sort_keys=True)
        if trace_dir is not None and tracered.newest_xplane(trace_dir):
            with open(stem + ".planes.txt", "w", encoding="utf-8") as f:
                f.write("\n".join(tracered.describe(tracered.newest_xplane(trace_dir))))
            with open(stem + ".reduced.json", "w", encoding="utf-8") as f:
                json.dump({"reduced": reduced,
                           "gc_pauses": gc_timer.long_pauses(m["t_start_ns"], 0.0),
                           "trace_zero_after_start_s":
                               (m["trace_zero_ns"] - m["t_start_ns"]) / 1e9,
                           "ta_after_trace_zero_s": (m["ta_ns"] - m["trace_zero_ns"]) / 1e9},
                          f, indent=1)

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:  # a traced probe reports both kinds: one stream, every number
        for metric in loaded["per_layer"]:
            reader = layers.find_reader(metric["name"])
            value = reader(facts) if reader is not None else None
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    if not args.trace or args.probe:
        lat = window.latencies_ms(m["due_ns"], m["done_ns"], m["ok"], t0, t1)
        values = {
            "qps": window.completed_per_s(m["done_ns"], m["ok"], t0, t1),
            "latency_p50_ms": window.percentile(lat, 50),
            "latency_p95_ms": window.percentile(lat, 95),
            "hbm_bytes_per_doc": (m["memory_peak_bytes"] / manifest["docs"]
                                  if m["memory_peak_bytes"] else None),
            "setup_s": m["setup_s"],
        }
        for metric in loaded["end_to_end"]:
            if values.get(metric["name"]) is not None:
                metrics[metric["name"]] = {"value": values[metric["name"]],
                                           "unit": metric["unit"]}
    out_device = dict(device, memory_peak_bytes=m["memory_peak_bytes"])
    log(f"stream: {sent} sent, {answered} answered 200, {checked} sampled responses "
        f"held to the reference ({swaps} near-tie swaps, {bounds} with hits.total a "
        f"lower bound), window {counts}")
    slices = np.histogram(m["done_ns"][m["ok"]],
                          bins=np.arange(t0, t1 + 1, int(5e9)))[0]
    log("completions a second in each 5 s of the window: "
        + " ".join(f"{n / 5:.0f}" for n in slices))
    line: Dict[str, Any] = {"correct": not problems, **counts, "metrics": metrics,
                            "device": out_device}
    if reduced is not None:
        out_device["busy_s"] = reduced["busy_s"]
        out_device["window_s"] = reduced["window_s"]
        top = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {
            "device_ops": [[tracered.short_name(n), s] for n, s in top],
            "idle_gaps": [[name, secs] for secs, name in reduced["idle_gaps"]]}
    line["compared"] = {name: {"value": value, "limit": limit, "limit_is": side}
                        for name, (value, limit, side) in compared.items()}
    if args.rehearse:
        # a rehearsal proves the command; nothing it timed is a measurement
        log("rehearsal, not a measurement: " + json.dumps(
            {k: v["value"] for k, v in metrics.items()}))
        line["metrics"] = {}
        line["device"] = dict(device, memory_peak_bytes=None)
        line.pop("breakdown", None)
    return line


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as exc:
        log(f"FAILED: {exc}")
        sys.exit(1)
