"""CPU rehearsal of every cell at toy size: the whole command, no
measurement. Slow (about a minute a cell): each builds a toy index in a
child, opens it, starts the generator processes, warms, ramps, cuts a 2 s
window, drains and checks a sample against the reference.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_rehearsal.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)

CELLS = [w["name"] for w in BENCH["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(workload: str, *extra: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", workload, "--seed", str(2**31 + 12345), "--seconds", "2", *extra]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_the_whole_command_and_prints_no_device_metric(workload, trace):
    proc = run_cell(workload, "--trace", trace, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS                 # exactly the contract's keys
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}                  # a rehearsal measures nothing
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] is None
    assert "compilations after the ramp began" not in proc.stderr


def test_without_a_tpu_the_command_refuses_and_prints_nothing():
    proc = run_cell(CELLS[0], "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refusing to measure" in proc.stderr
