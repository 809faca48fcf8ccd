"""The host-span readers (esbench/hostspans.py and the per-layer metrics
that read it or the new stages): interval arithmetic by hand, the recorded
annotated trace against its hand count, silence where there is nothing to
read, and the arithmetic of the `.json` readers on made-up facts.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_hostspans.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from esbench import hostspans, layers, tracered  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)

TESTDATA = os.path.join(BENCH_DIR, "testdata")
IDLE_METRICS = ["idle_under_wait_pct", "idle_under_hold_pct", "idle_under_prep_pct",
                "idle_under_dispatch_pct", "idle_under_blocked_pct",
                "idle_under_gc_pct", "idle_unattributed_pct"]
TRACE_METRICS = IDLE_METRICS + ["dispatch_buffer_wait_ms",
                                "device_full_s32_ms_per_launch",
                                "device_full_s128_ms_per_launch"]
STAGE_METRICS = ["batcher_launch_pct", "batcher_hold_pct", "batcher_blocked_pct",
                 "dispatch_lock_wait_ms", "dispatch_call_ms", "launch_cpu_ms",
                 "finish_cpu_ms", "lower_cpu_ms_per_q", "rest_cpu_ms_per_q",
                 "render_ms_per_q", "gil_busy_pct", "gc_full_pause_pct"]
NEW_METRICS = TRACE_METRICS + STAGE_METRICS


# ---------------------------------------------------------------------------
# interval arithmetic, by hand
# ---------------------------------------------------------------------------

def test_merge_intersect_subtract():
    assert hostspans.merge([(5, 7), (0, 2), (1, 3), (3, 3), (9, 8)]) == [(0, 3), (5, 7)]
    assert hostspans.total([(0, 3), (5, 7)]) == 5
    a, b = [(0, 10), (20, 30)], [(5, 22), (25, 26), (40, 50)]
    assert hostspans.intersect(a, b) == [(5, 10), (20, 22), (25, 26)]
    assert hostspans.subtract(a, b) == [(0, 5), (22, 25), (26, 30)]
    assert hostspans.subtract(a, []) == a
    assert hostspans.subtract([(0, 10)], [(0, 10)]) == []
    assert hostspans.idle_intervals([(2, 4, "a"), (3, 6, "b"), (8, 9, "c")], 2, 9) == \
        [(6, 8)]


def test_idle_goes_to_the_state_that_covers_it_and_a_gap_is_split():
    idle = [(10, 20), (30, 40)]
    spans = [(8, 14, "batcher.hold"), (14, 16, "batcher.take"),
             (16, 33, "batcher.prep"), (33, 36, "batcher.call")]
    out = hostspans.attribute(idle, spans)
    assert out["batcher.hold"] == 4            # 10..14 of the first gap
    assert out["batcher.take"] == 2            # 14..16
    assert out["batcher.prep"] == 4 + 3        # 16..20, then 30..33 of the second
    assert out["batcher.call"] == 3            # 33..36
    assert out[hostspans.UNATTRIBUTED] == 4    # 36..40: no state
    assert sum(out.values()) == hostspans.total(idle)


def test_precedence_gc_over_call_over_the_waiting_states():
    idle = [(0, 100)]
    spans = [(0, 100, "batcher.wait"),         # another queue's launch thread idles
             (10, 60, "batcher.call"), (20, 30, hostspans.GC_FULL),
             (50, 70, "batcher.blocked"), (65, 80, "batcher.hold")]
    out = hostspans.attribute(idle, spans)
    assert out[hostspans.GC_FULL] == 10
    assert out["batcher.call"] == 40           # 10..60 less the collection
    assert out["batcher.blocked"] == 10        # 60..70: call wins 50..60
    assert out["batcher.hold"] == 10           # 70..80: blocked wins 65..70
    assert out["batcher.wait"] == 30           # what nothing else covers
    assert out[hostspans.UNATTRIBUTED] == 0
    assert sum(out.values()) == 100


def _made_up_planes():
    ops = [(0.0, 10e6, "%sort.1"), (30e6, 40e6, "%sort.1"), (90e6, 100e6, "%concatenate.3")]
    modules = [(0.0, 10e6, "jit_full_s32(111)"), (30e6, 40e6, "jit_full_s32(111)"),
               (90e6, 100e6, "jit_full_s128(222)")]
    return {"/device:TPU:0": {tracered.OPS_LINE: ops, tracered.MODULES_LINE: modules}}


def test_reduce_spans_shares_sum_to_the_idle_share():
    launch = [(0.0, 12e6, "batcher.hold"), (12e6, 20e6, "batcher.prep"),
              (20e6, 35e6, "batcher.call"), (22e6, 26e6, "Wait for donation holds"),
              (35e6, 50e6, "batcher.blocked"), (70e6, 95e6, "batcher.wait")]
    other = [(55e6, 60e6, hostspans.GC_FULL), (28e6, 29e6, "Wait for something"),
             (0.0, 100e6, "completer.device_wait")]
    out = hostspans.reduce_spans([launch, other], _made_up_planes())
    assert out["window_s"] == pytest.approx(0.1)
    idle = out["idle_s"]
    assert idle["batcher.hold"] == pytest.approx(0.002)       # 10..12
    assert idle["batcher.prep"] == pytest.approx(0.008)       # 12..20
    assert idle["batcher.call"] == pytest.approx(0.010)       # 20..30
    assert idle["batcher.blocked"] == pytest.approx(0.010)    # 40..50
    assert idle[hostspans.GC_FULL] == pytest.approx(0.005)    # 55..60
    assert idle["batcher.wait"] == pytest.approx(0.020)       # 70..90
    assert idle[hostspans.UNATTRIBUTED] == pytest.approx(0.015)  # 50..55, 60..70
    assert sum(idle.values()) == pytest.approx(0.070)         # window less 30 ms busy
    # only the wait nested in the launch thread's own call counts
    assert out["buffer_wait_s"] == pytest.approx(0.004)
    assert out["modules"] == {"jit_full_s32": (pytest.approx(0.020), 2),
                              "jit_full_s128": (pytest.approx(0.010), 1)}
    assert out["events"]["batcher.call"] == 1


def test_reduce_spans_is_none_without_a_device_plane_or_an_annotation():
    spans = [[(0.0, 5e6, "batcher.prep")]]
    assert hostspans.reduce_spans(spans, {}) is None
    assert hostspans.reduce_spans([], _made_up_planes()) is None
    assert hostspans.reduce_spans([[(0.0, 5e6, "completer.decode")]],
                                  _made_up_planes()) is None
    assert hostspans.reduce_spans(spans, _made_up_planes()) is not None


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _trace_dir(tmp_path, name):
    run_dir = tmp_path / "run"
    (run_dir / "cell" / "trace").mkdir(parents=True)
    shutil.copy(os.path.join(TESTDATA, name), run_dir / "cell" / "trace" / name)
    return str(run_dir)


def _trace_facts(path, trains=4.0):
    reduced = tracered.reduce_trace(path)
    return reduced, {"trace.window_s": reduced["window_s"],
                     "trace.idle_s": reduced["window_s"] - reduced["busy_s"],
                     "traced.batches": trains}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_has_a_reader_that_is_silent_on_empty_facts(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name + ".closed")
    # the cell they were added for lists them, whatever cells came after
    assert "msmarco-1chip.or1000-closed384" in entry["workloads"]
    assert set(entry["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    assert entry["moves"] == "qps"
    reader = layers.find_reader(entry["name"])
    assert reader is not None
    assert reader({}) is None


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_trace_readers_are_silent_on_a_trace_without_annotations(name, tmp_path,
                                                                 monkeypatch):
    """The parent commit's trace: device ops, `jit_step`, no annotation."""
    monkeypatch.setattr(hostspans, "RUN_DIR", _trace_dir(tmp_path, "small_trace.xplane.pb"))
    path = tracered.newest_xplane(hostspans.RUN_DIR)
    _reduced, facts = _trace_facts(path)
    assert hostspans.of_run(facts) is None
    assert layers.find_reader(name + ".closed")(facts) is None


def test_trace_readers_read_no_file_when_the_run_reduced_no_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(hostspans, "RUN_DIR",
                        _trace_dir(tmp_path, "annotated_trace.xplane.pb"))
    assert hostspans.of_run({"traced.batches": 4.0}) is None      # untraced, rehearsal
    monkeypatch.setattr(hostspans, "RUN_DIR", str(tmp_path / "nothing_here"))
    assert hostspans.of_run({"trace.window_s": 1.0}) is None


def test_the_recorded_annotated_trace_gives_its_hand_count(tmp_path, monkeypatch):
    """`tools/record_annotated_trace.py`: a toy node's pruned path under a
    session on the chip; the hand count is a boundary sweep that shares
    nothing with hostspans' interval functions."""
    with open(os.path.join(TESTDATA, "annotated_trace.json"), "r", encoding="utf-8") as f:
        known = json.load(f)
    path = os.path.join(TESTDATA, "annotated_trace.xplane.pb")
    assert os.path.getsize(path) < 100_000
    spans = hostspans.read_trace(path)
    assert spans["window_s"] == pytest.approx(known["window_s"], rel=1e-9)
    for name, secs in known["idle_s"].items():
        assert spans["idle_s"][name] == pytest.approx(secs, rel=1e-6, abs=1e-9), name
    for name, (secs, count) in known["modules"].items():
        assert spans["modules"][name] == (pytest.approx(secs, rel=1e-9), count)
    assert spans["events"]["batcher.call"] == known["span_events"]["batcher.call"]
    assert "jit_full_s32" in spans["modules"] and "jit_body" not in spans["modules"]
    # the states the recording went through all took some of the idle time
    for name in ("batcher.wait", "batcher.prep", "batcher.call", hostspans.GC_FULL):
        assert spans["idle_s"][name] > 0, name
    # the seven shares sum to the idle share that tracered reduces
    monkeypatch.setattr(hostspans, "RUN_DIR",
                        _trace_dir(tmp_path, "annotated_trace.xplane.pb"))
    reduced, facts = _trace_facts(path)
    assert reduced["busy_s"] == pytest.approx(known["busy_s"], rel=1e-6)
    shares = {name: layers.find_reader(name + ".closed")(facts) for name in IDLE_METRICS}
    assert all(v is not None and v >= 0.0 for v in shares.values()), shares
    idle_pct = layers.find_reader("device_idle_pct.closed")(facts)
    assert sum(shares.values()) == pytest.approx(idle_pct, abs=1e-6)
    assert shares["idle_unattributed_pct"] < 0.25 * idle_pct
    per_launch = layers.find_reader("device_full_s32_ms_per_launch.closed")(facts)
    secs, count = known["modules"]["jit_full_s32"]
    assert per_launch == pytest.approx(1000.0 * secs / count)
    assert layers.find_reader("dispatch_buffer_wait_ms.closed")(facts) >= 0.0


def test_the_breakdowns_idle_gaps_carry_the_launch_threads_state():
    """What `run.py` hands `tracered.reduce_trace` as `pauses`: the
    ledger's `breakdown.idle_gaps` then name a state, not `unattributed`."""
    path = os.path.join(TESTDATA, "annotated_trace.xplane.pb")
    pauses = hostspans.pauses(path)
    states = [n.removeprefix("batcher.") for n in hostspans.PRECEDENCE]
    ranks = [states.index(n) for _s, _e, n in pauses]
    assert ranks == sorted(ranks) and ranks[0] == 0      # precedence order, gc.full first
    gaps = tracered.reduce_trace(path, pauses)["idle_gaps"]
    assert len(gaps) == 10
    assert [s for s, _n in gaps] == sorted((s for s, _n in gaps), reverse=True)
    secs, name = gaps[0]
    # the one long gap: 77 ms, most of it under the idle queue's `wait`
    assert secs == pytest.approx(0.07700777) and name.startswith("wait after %")
    assert {n.split(" after ")[0] for _s, n in gaps} <= set(states) | {"unattributed"}
    bare = tracered.reduce_trace(path)["idle_gaps"]
    assert [s for s, _n in bare] == [s for s, _n in gaps]
    assert all(n.startswith("unattributed after ") for _s, n in bare)


def _stage(seconds, count, cpu=None, cpu_count=None):
    out = {"seconds": seconds, "count": count}
    if cpu is not None:
        out["cpu_seconds"] = cpu
        out["cpu_count"] = count if cpu_count is None else cpu_count
    return out


def test_stage_readers_on_made_up_facts():
    window = {
        "batches": 100, "batched_queries": 9000,
        "runtime": {"gc": {"full_collections": 1, "full_pause_seconds": 0.9,
                           "longest_pause_ms": 900.0}},
        "stages": {
            "batcher.wait": _stage(1.0, 5, 0.0), "batcher.hold": _stage(9.0, 100, 0.1),
            "batcher.take": _stage(0.1, 100, 0.1), "batcher.prep": _stage(10.0, 300, 6.0),
            "batcher.lock": _stage(0.5, 200, 0.0), "batcher.put": _stage(0.4, 200, 0.3),
            "batcher.call": _stage(7.0, 200, 1.5), "batcher.blocked": _stage(17.0, 100, 0.0),
            "completer.wait": _stage(5.0, 100, 0.0),
            "completer.device_wait": _stage(30.0, 200, 0.2),
            "completer.decode": _stage(8.0, 200, 5.0),
            "completer.deliver": _stage(2.0, 100, 0.8),
            # per-request stages read the CPU clock for one request in 16
            "lower": _stage(13.5, 9000, 4.5 / 15, cpu_count=600),
            "rest_request": _stage(9000.0, 9003, 18.0 / 3, cpu_count=3001),
            "rest_render": _stage(9.0, 9003),
            "batch_prep": _stage(10.0, 200), "batch_dispatch": _stage(7.9, 200),
        }}
    facts = layers.flatten(window, "window", {})
    facts["gen.window_s"] = 45.0

    def read(name):
        return layers.find_reader(name + ".closed")(facts)

    assert read("batcher_launch_pct") == pytest.approx(100 * 18.0 / 45)
    assert read("batcher_hold_pct") == pytest.approx(20.0)
    assert read("batcher_blocked_pct") == pytest.approx(100 * 17.0 / 45)
    wait_pct = 100 * 1.0 / 45
    assert (read("batcher_launch_pct") + read("batcher_hold_pct")
            + read("batcher_blocked_pct") + wait_pct) == pytest.approx(100.0)
    assert read("dispatch_lock_wait_ms") == pytest.approx(5.0)
    assert read("dispatch_call_ms") == pytest.approx(70.0)
    assert read("dispatch_lock_wait_ms") + read("dispatch_call_ms") <= read("launch_host_ms")
    assert read("launch_cpu_ms") == pytest.approx(80.0)
    assert read("finish_cpu_ms") == pytest.approx(60.0)
    assert read("lower_cpu_ms_per_q") == pytest.approx(0.5)
    assert read("lower_cpu_ms_per_q") <= read("lower_ms_per_q")
    assert read("rest_cpu_ms_per_q") == pytest.approx(1000 * 18.0 / 9003)
    assert read("render_ms_per_q") == pytest.approx(1000 * 9.0 / 9003)
    assert read("gil_busy_pct") == pytest.approx(100 * (18.0 + 8.0 + 6.0) / 45)
    assert read("gc_full_pause_pct") == pytest.approx(2.0)


def test_the_new_entries_only_add_to_the_benchmark():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[16:16 + len(NEW_METRICS)] == [n + ".closed" for n in NEW_METRICS]
    assert len(names) >= 16 + len(NEW_METRICS) and len(names) == len(set(names))
    layers_named = {m["layer"] for m in BENCH["per_layer"]}
    assert "host (all Python threads)" in layers_named
    # later PRs add cells and configurations; none takes the first away
    assert BENCH["workloads"][0]["name"] == "msmarco-1chip.or1000-closed384"
    assert BENCH["configs"][0]["name"] == "msmarco-1chip"
