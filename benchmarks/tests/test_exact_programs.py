"""The exact path's four per-layer readers (PR 29): `exact_route_pct`,
`exact_fill_pct`, `device_exact_ms_per_launch`, `exact_topk_roofline`.
They read a recorded facts file of the cell that lists them
(`testdata/quora_exact_facts.json`: the counters' rise over the window of
a traced run of `beir-quora-1chip.or1000-closed384` on the chip, and the
`XLA Modules` seconds and launches of its trace as `hostspans` reduced
them), with the values worked out by hand beside them; they are silent on
a program that has no such counters or names its exact programs without
their shape; and the roofline share cannot pass 100.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_exact_programs.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from esbench import exactprograms, hostspans, layers, peaks, roofline  # noqa: E402

CELL = "beir-quora-1chip.or1000-closed384"
NEW = {"exact_route_pct.closed": ("%", "higher", "program_counter", "launch routing"),
       "exact_fill_pct.closed": ("%", "higher", "program_counter", "kernels"),
       "device_exact_ms_per_launch.closed": ("ms", "lower", "device_trace", "kernels"),
       "exact_topk_roofline.closed": ("%", "higher", "device_trace", "kernels")}

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)
with open(os.path.join(BENCH_DIR, "testdata", "quora_exact_facts.json"), "r",
          encoding="utf-8") as _f:
    RECORDED = json.load(_f)


@pytest.fixture
def recorded(monkeypatch):
    """The recorded run's facts, with its trace's modules where
    `hostspans.of_run` would read them from the run's `.xplane.pb`."""
    modules = {name: (secs, int(n)) for name, (secs, n) in RECORDED["modules"].items()}
    monkeypatch.setattr(hostspans, "of_run",
                        lambda facts, run_dir=None: {"modules": modules}
                        if "trace.window_s" in facts else None)
    return dict(RECORDED["facts"])


def read(name, facts):
    return layers.find_reader(name)(facts)


def test_the_counter_readers_on_the_recorded_facts(recorded):
    hand = RECORDED["by_hand"]
    f = recorded
    exact = sum(n for key, n in f.items() if key.startswith("window.route.exact_"))
    routed = sum(n for key, n in f.items() if key.startswith("window.route."))
    assert f["window.route.exact_escalated"] == 0
    assert exact == hand["exact_queries"] and routed == hand["routed_queries"]
    assert read("exact_route_pct.closed", f) == pytest.approx(
        100.0 * hand["exact_queries"] / hand["routed_queries"])
    assert 66.0 < read("exact_route_pct.closed", f) < 68.0
    assert read("exact_fill_pct.closed", f) == pytest.approx(
        100.0 * hand["entries_real"] / hand["entries_padded"])
    # every exact launch of the window is one of the shaped programs, and
    # what they dispatched is rows x slots x 4096 each
    padded = 0
    for key, n in f.items():
        if key.startswith("window.launches.exact_"):
            rows, slots = (int(part[1:]) for part in key.split("_")[-3:-1])
            padded += n * rows * slots * exactprograms.CHUNK_LEN
    assert padded == f["window.exact_entries.padded"] == hand["entries_padded"]


def test_the_trace_readers_on_the_recorded_modules(recorded):
    hand = RECORDED["by_hand"]
    programs = exactprograms.of_run(recorded)
    assert [(r, s, n) for r, s, _secs, n in programs] == \
        [tuple(p) for p in hand["programs_rows_slots_launches"]]
    assert read("device_exact_ms_per_launch.closed", recorded) == pytest.approx(
        1000.0 * hand["seconds_at_128_rows"] / hand["launches_at_128_rows"])
    k = int(recorded["request.size"])
    least = sum(rows * (slots * 4096 * 8 + k * 8) * n
                for rows, slots, n in hand["programs_rows_slots_launches"])
    assert exactprograms.least_bytes(programs, k) == least == hand["least_bytes"]
    share = read("exact_topk_roofline.closed", recorded)
    assert share == pytest.approx(
        100.0 * least / peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
        / hand["exact_seconds"])
    assert 0.0 < share < 100.0
    # the pruned path's programs are not the exact kernel's
    assert "jit_full_s32" in RECORDED["modules"]
    assert all(r in (8, 64, 128) for r, _s, _secs, _n in programs)


@pytest.mark.parametrize("name", sorted(NEW))
def test_silent_where_there_is_nothing_to_read(name, monkeypatch):
    reader = layers.find_reader(name)
    assert reader is not None
    assert reader({}) is None
    # the parent: no `route`, no `exact_entries`, and `jit_exact_ref`
    # whatever its shape
    monkeypatch.setattr(hostspans, "of_run", lambda facts, run_dir=None: {
        "modules": {"jit_exact_ref": (1.5, 30), "jit_full_s32": (2.0, 40)}})
    parent = {"trace.window_s": 5.0, "request.size": 1000.0,
              "device.peak_hbm_bytes_per_s": 819e9,
              "window.launches.exact_ref": 300.0, "window.batches": 300.0}
    assert reader(parent) is None
    assert reader({**parent, "window.route.exact_terms": 0.0,
                   "window.exact_entries.padded": 0.0}) is None


@pytest.mark.parametrize("rows", [8, 64, 128])
@pytest.mark.parametrize("slots", [8, 16, 32, 64, 128, 256])
@pytest.mark.parametrize("k", [10, 1000])
def test_the_roofline_share_cannot_pass_100(rows, slots, k, monkeypatch):
    """At the least seconds the bytes allow it reads 100, and less at
    any time a launch can really take."""
    peak = peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    least_s = roofline.sorted_merge_topk_bytes(rows, slots * 4096, k) / peak
    facts = {"trace.window_s": 5.0, "request.size": float(k),
             "device.peak_hbm_bytes_per_s": peak}
    name = f"jit_exact_ref_b{rows}_s{slots}_w16"
    for launches, stretch in ((1, 1.0), (7, 1.0), (7, 1.0001), (3, 40.0)):
        monkeypatch.setattr(hostspans, "of_run", lambda f, run_dir=None: {
            "modules": {name: (launches * least_s * stretch, launches),
                        "jit_full_s32": (0.001, 1)}})
        share = read("exact_topk_roofline.closed", facts)
        assert share == pytest.approx(100.0 / stretch)
        assert share <= 100.0 + 1e-9


def test_program_names_are_read_by_their_shape_alone():
    got = exactprograms.shaped({
        "jit_exact_ref_b128_s32_w16": (0.5, 10),
        "jit_exact_compressed_exact_b8_s8_w8": (0.25, 5),
        "jit_exact_ref": (9.0, 9), "jit_full_s32": (9.0, 9),
        "jit_exact_ref_b128_s32": (9.0, 9)})
    assert got == [(8, 8, 0.25, 5), (128, 32, 0.5, 10)]


def test_declared_in_benchmark_json_for_the_cell_that_takes_the_exact_path():
    """Whatever cells and entries later PRs add around them."""
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(names) == len(by_name)
    cells = {w["name"] for w in BENCH["workloads"]}
    for name, (unit, better, source, layer) in NEW.items():
        entry = dict(by_name[name])
        workloads = entry.pop("workloads")
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": source, "layer": layer, "moves": "qps"}
        assert CELL in workloads and set(workloads) <= cells
    assert [n for n in names if n in NEW] == list(NEW)       # in the order added
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert cell["config"] == "beir-quora-1chip" and cell["traffic"] == "or1000-closed384"
    for m in BENCH["end_to_end"]:
        assert CELL in m.get("workloads", [CELL])
    # no query of the cell needs the 128-slot bucket
    assert CELL not in by_name["device_full_s128_ms_per_launch.closed"]["workloads"]
    config = next(c for c in BENCH["configs"] if c["name"] == "beir-quora-1chip")
    assert config["reduced"] == []
