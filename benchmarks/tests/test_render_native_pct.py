"""`render_native_pct`: the share of hits blocks that the serving process
rendered through its native renderer, from the `render` counters of
`/_tpu/stats`; silent on a program that has no such counters.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_render_native_pct.py -q -p no:cacheprovider
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

from esbench import layers  # noqa: E402

NAME = "render_native_pct.closed"


@pytest.mark.parametrize("facts,value", [
    ({"window.render.native": 16470.0, "window.render.python": 0.0}, 100.0),
    ({"window.render.native": 300.0, "window.render.python": 100.0}, 75.0),
    ({"window.render.native": 0.0, "window.render.python": 50.0}, 0.0),
    ({"window.render.native": 12.0}, 100.0),
])
def test_share_of_blocks_rendered_natively(facts, value):
    assert layers.find_reader(NAME)(facts) == pytest.approx(value)


@pytest.mark.parametrize("facts", [
    {},                                               # the parent: no counter
    {"window.stages.rest_render.count": 100.0},       # other facts only
    {"window.render.native": 0.0, "window.render.python": 0.0},  # no render
])
def test_silent_where_there_is_nothing_to_read(facts):
    assert layers.find_reader(NAME)(facts) is None


def test_facts_come_from_the_stats_difference():
    before = layers.flatten({"render": {"native": 5, "python": 2}}, "s", {})
    after = layers.flatten({"render": {"native": 105, "python": 2}}, "s", {})
    facts = layers.difference(after, before, "s", "window")
    assert layers.find_reader(NAME)(facts) == pytest.approx(100.0)


def test_declared_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1                            # wherever later entries put it
    workloads = entry[0].pop("workloads")
    assert entry[0] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "HTTP + REST", "moves": "qps"}
    assert "msmarco-1chip.or1000-closed384" in workloads
    assert set(workloads) <= {w["name"] for w in bench["workloads"]}
