"""The four readers of the heads route (`heads_route_pct.closed`,
`device_heads_ms_per_launch.closed`, `heads_roofline.closed`,
`setup_heads_block_s`) and `esbench/headsprograms.py`: their entries,
their arithmetic on made-up facts and modules, and silence where a parent
commit gives nothing to read.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_heads_readers.py -q -p no:cacheprovider
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

from esbench import headsprograms, hostspans, layers, roofline  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)

CELL = "msmarco-stop-1chip.or1000-closed384-heads"
NEW = {  # name: (unit, better, source, layer, moves)
    "heads_route_pct.closed": ("%", "higher", "program_counter", "launch routing", "qps"),
    "device_heads_ms_per_launch.closed": ("ms", "lower", "device_trace", "kernels", "qps"),
    "heads_roofline.closed": ("%", "higher", "device_trace", "kernels", "qps"),
    "setup_heads_block_s": ("s", "lower", "program_span", "set-up", "setup_s"),
}
BLOCK = {"after.pack.heads_block.rows": 32.0,
         "after.pack.heads_block.shard_rows": 2.0,
         "after.pack.heads_block.docs_per_row": 553344.0}
MODULES = {"jit_heads_b128_s16": (0.9, 10), "jit_heads_b8_s128": (0.4, 10),
           "jit_full_s16": (0.5, 7)}


def read(name, facts):
    return layers.find_reader(name)(facts)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_metric_is_listed_on_the_new_cell_alone_and_silent_on_empty_facts(name):
    unit, better, source, layer, moves = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": moves, "workloads": [CELL]}
    assert read(name, {}) is None


def test_the_cell_and_its_configuration_are_appended():
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "msmarco-stop-1chip"
    for metric in BENCH["end_to_end"]:
        if "workloads" in metric:
            assert metric["workloads"][-1] == CELL


def test_the_route_share_and_a_parent_without_the_route():
    facts = {"window.route.heads": 830.0, "window.route.pruned_full_s16": 160.0,
             "window.route.exact_terms": 10.0, "window.route.exact_escalated": 4.0}
    assert read("heads_route_pct.closed", facts) == pytest.approx(83.0)
    del facts["window.route.heads"]
    assert read("heads_route_pct.closed", facts) is None


def test_the_block_stage():
    facts = {"after.stages.heads_block.seconds": 1.25}
    assert read("setup_heads_block_s", facts) == pytest.approx(1.25)


def test_programs_by_name_and_their_time_a_launch(monkeypatch):
    monkeypatch.setattr(hostspans, "of_run", lambda facts, run_dir=None: {
        "modules": MODULES})
    assert headsprograms.of_run({}) == [(8, 128, 0.4, 10), (128, 16, 0.9, 10)]
    assert headsprograms.ms_per_launch({}) == pytest.approx(1000 * 1.3 / 20)
    monkeypatch.setattr(hostspans, "of_run", lambda facts, run_dir=None: {
        "modules": {"jit_full_s16": (0.5, 7)}})
    assert headsprograms.ms_per_launch({}) is None


def test_the_least_time_is_the_larger_bound():
    docs = 2 * 553344
    moved = (32 * docs * 4 + 128 * docs * 8
             + roofline.sorted_merge_topk_bytes(128, 16 * 4096 * 2, 1000))
    got = headsprograms.least_seconds(128, 16, 32, 2, 553344, 1000, 819e9, 197e12)
    assert got == pytest.approx(moved / 819e9)
    assert got > 2 * 128 * 32 * docs / (197e12 / 6)  # bytes bound this shape
    # a block of thousands of head rows is bound by the matmul's flops
    flops = headsprograms.least_seconds(128, 16, 4096, 1, 10 ** 6, 10, 819e9, 197e12)
    assert flops == pytest.approx(2 * 128 * 4096 * 10 ** 6 / (197e12 / 6))


def test_the_roofline_share_reads_the_block_and_the_peaks(monkeypatch):
    monkeypatch.setattr(hostspans, "of_run", lambda facts, run_dir=None: {
        "modules": MODULES})
    facts = dict(BLOCK, **{"request.size": 1000.0,
                           "device.peak_hbm_bytes_per_s": 819e9})
    least = (10 * headsprograms.least_seconds(128, 16, 32, 2, 553344, 1000, 819e9, 197e12)
             + 10 * headsprograms.least_seconds(8, 128, 32, 2, 553344, 1000, 819e9, 197e12))
    assert read("heads_roofline.closed", facts) == pytest.approx(100 * least / 1.3)
    for missing in list(BLOCK) + ["request.size", "device.peak_hbm_bytes_per_s"]:
        assert read("heads_roofline.closed",
                    {k: v for k, v in facts.items() if k != missing}) is None
    # a device whose peaks the table does not hold gives nothing
    assert read("heads_roofline.closed",
                dict(facts, **{"device.peak_hbm_bytes_per_s": 1.0})) is None
