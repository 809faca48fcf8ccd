"""The six readers ISSUE 38 adds (`wake_ms_per_q`, `deliver_ms_per_q`,
`prep_query_batch_cpu_ms`, `prep_term_ranges_cpu_ms`, `prep_offcpu_ms`,
`idle_under_operands_pct`): their arithmetic on made-up facts and a
made-up trace, and silence where a parent commit gives nothing to read.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_host_waits_readers.py -q -p no:cacheprovider
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

from esbench import hostspans, layers, prepspans, tracered  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)

ALL = [w["name"] for w in BENCH["workloads"]]
OR = [w for w in ALL if w != "msmarco-and-1chip.and1000-closed384-pins"]
NEW = {  # name: (unit, source, layer, workloads)
    "wake_ms_per_q.closed": ("ms", "program_span", "HTTP + REST", ALL),
    "deliver_ms_per_q.closed": ("ms", "program_span", "launch routing", ALL),
    "prep_query_batch_cpu_ms.closed": ("ms", "program_span", "launch routing", ALL),
    "prep_term_ranges_cpu_ms.closed": ("ms", "program_span", "launch routing", OR),
    "prep_offcpu_ms.closed": ("ms", "program_span", "host (all Python threads)", ALL),
    "idle_under_operands_pct.closed": ("%", "device_trace", "device", ALL),
}


def _stage(seconds, count, cpu=None):
    out = {"seconds": seconds, "count": count}
    if cpu is not None:
        out["cpu_seconds"], out["cpu_count"] = cpu, count
    return out


def _facts():
    window = {"batches": 200, "stages": {
        "batch_wait": _stage(3600.0, 9000),
        "batch_wait.completion": _stage(2700.0, 9000),
        "batch_wait.device": _stage(1500.0, 9000),
        "batch_wait.decode": _stage(900.0, 9000),
        "batch_wait.deliver": _stage(180.0, 9000),
        "batch_wait.wake": _stage(120.0, 9000),
        "batcher.prep": _stage(10.0, 600, 4.0),
        "prep.route": _stage(0.6, 200, 0.3),
        "prep.query_batch": _stage(5.0, 400, 2.0),
        "prep.term_ranges": _stage(3.0, 400, 1.2),
    }}
    return layers.flatten(window, "window", {"gen.window_s": 45.0})


def read(name, facts):
    return layers.find_reader(name)(facts)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_listed_where_it_reads_and_silent_on_empty_facts(name):
    unit, source, layer, workloads = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "qps", "workloads": workloads}
    assert read(name, {}) is None
    # the parent commit's facts: every stage it had, none of the new ones
    parent = {k: v for k, v in _facts().items()
              if not k.startswith(("window.stages.prep.", "window.stages.batch_wait.wake",
                                   "window.stages.batch_wait.deliver"))}
    assert read(name, parent) is None


def test_the_new_entries_only_add_to_the_benchmark():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    layers_named = {m["layer"] for m in BENCH["per_layer"][:-len(NEW)]}
    assert {NEW[n][2] for n in NEW} <= layers_named


def test_stage_readers_on_made_up_facts():
    facts = _facts()
    assert read("wake_ms_per_q.closed", facts) == pytest.approx(1000 * 120.0 / 9000)
    assert read("deliver_ms_per_q.closed", facts) == pytest.approx(1000 * 180.0 / 9000)
    assert read("prep_query_batch_cpu_ms.closed", facts) == pytest.approx(10.0)
    assert read("prep_term_ranges_cpu_ms.closed", facts) == pytest.approx(6.0)
    off = (0.6 - 0.3) + (5.0 - 2.0) + (3.0 - 1.2)
    assert read("prep_offcpu_ms.closed", facts) == pytest.approx(1000 * off / 200)
    # the AND cell: no pruned launch, no term ranges; the rest still reads
    no_pruned = {k: v for k, v in facts.items()
                 if not k.startswith("window.stages.prep.term_ranges")}
    assert read("prep_term_ranges_cpu_ms.closed", no_pruned) is None
    assert read("prep_offcpu_ms.closed", no_pruned) == pytest.approx(
        1000 * ((0.6 - 0.3) + (5.0 - 2.0)) / 200)
    assert read("prep_offcpu_ms.closed", {**facts, "window.batches": 0.0}) is None


def _planes():
    # busy 0..10, 40..50, 90..100 ms of a 100 ms window: idle 10..40, 50..90
    ops = [(0.0, 10e6, "%sort"), (40e6, 50e6, "%sort"), (90e6, 100e6, "%sort")]
    return {"/device:TPU:0": {tracered.OPS_LINE: ops},
            "/device:TPU:1": {tracered.OPS_LINE: list(ops)}}


def _lines():
    launch = [(0.0, 12e6, "batcher.hold"), (12e6, 38e6, "batcher.prep"),
              (12e6, 14e6, "prep.route"), (14e6, 30e6, "prep.query_batch"),
              (30e6, 34e6, "prep.term_ranges"),
              (38e6, 45e6, "batcher.call"), (45e6, 88e6, "batcher.prep"),
              (46e6, 80e6, "prep.query_batch")]
    other = [(20e6, 25e6, hostspans.GC_FULL), (0.0, 100e6, "completer.device_wait")]
    return [sorted(launch), other]


def test_operand_idle_is_the_part_of_prep_idle_under_an_operand_span():
    out = prepspans.operand_idle_s(_lines(), _planes())
    assert out["window_s"] == pytest.approx(0.1)
    # under batcher.prep and idle: 12..38 less gc 20..25 → 12..20, 25..38
    # (21 ms); 50..88 (38 ms); 59 ms in all
    assert out["prep_idle_s"] == pytest.approx(0.059)
    # of which under an operand span: 14..20, 25..34, 50..80
    assert out["operands_idle_s"] == pytest.approx(0.045)
    # hostspans counts the same idle under batcher.prep, the nested spans
    # or not
    spans = hostspans.reduce_spans(_lines(), _planes())
    assert spans["idle_s"]["batcher.prep"] == pytest.approx(out["prep_idle_s"])
    assert out["operands_idle_s"] <= spans["idle_s"]["batcher.prep"]


def test_operand_idle_is_none_without_an_operand_span_or_a_device():
    bare = [[ev for ev in line if not ev[2].startswith("prep.")] for line in _lines()]
    assert prepspans.operand_idle_s(bare, _planes()) is None
    assert prepspans.operand_idle_s(_lines(), {}) is None


def _trace_dir(tmp_path, name):
    run_dir = tmp_path / "run"
    (run_dir / "cell" / "trace").mkdir(parents=True)
    shutil.copy(os.path.join(BENCH_DIR, "testdata", name), run_dir / "cell" / "trace" / name)
    return str(run_dir)


def test_the_reader_is_silent_on_a_parent_commits_trace(tmp_path, monkeypatch):
    """The recorded annotated trace holds `batcher.*` and device ops and no
    `prep.*` span, as a parent commit's does."""
    monkeypatch.setattr(hostspans, "RUN_DIR",
                        _trace_dir(tmp_path, "annotated_trace.xplane.pb"))
    reader = layers.find_reader("idle_under_operands_pct.closed")
    assert reader({"traced.batches": 4.0}) is None        # the run reduced no trace
    assert reader({"trace.window_s": 1.0}) is None         # no operand span in it
    assert layers.find_reader("idle_under_prep_pct.closed")({"trace.window_s": 1.0}) > 0


def test_the_reader_reads_a_made_up_run(tmp_path, monkeypatch):
    monkeypatch.setattr(prepspans, "_read",
                        lambda path, _mtime: prepspans.operand_idle_s(_lines(), _planes()))
    run_dir = tmp_path / "run" / "cell" / "trace"
    run_dir.mkdir(parents=True)
    (run_dir / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(hostspans, "RUN_DIR", str(tmp_path / "run"))
    got = layers.find_reader("idle_under_operands_pct.closed")({"trace.window_s": 0.1})
    assert got == pytest.approx(45.0)
