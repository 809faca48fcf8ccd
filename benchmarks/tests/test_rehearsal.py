"""CPU rehearsal of every cell at toy size: the whole command, no
measurement. Slow (about a minute a cell): each builds a toy index in a
child, opens it, starts the generator processes, warms, ramps, cuts a 2 s
window, drains and checks a sample against the reference. And of two
scratch cells in a copy of the tree that differs from it in files added
and entries added alone (`add_scratch_cells`): what the next
`model_config` PRs add, a configuration with the `stopmix` query law and
a traffic file with `operator: and`.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_rehearsal.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import shutil
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


STANDING = "msmarco-1chip.or1000-closed384"
STOP_CELL = "scratch-stop.or1000-closed384-rungs"
AND_CELL = "msmarco-1chip.and1000-closed384"


def run_cell(workload: str, *extra: str, root: str = ROOT) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", workload, "--seed", str(2**31 + 12345), "--seconds", "2", *extra]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True, text=True,
                          timeout=900)


def add_scratch_cells(root: str) -> None:
    """Into a copy of the tree at `root`: ISSUE 35's two scratch cells, by
    files added and entries added to its BENCHMARK.json, nothing edited.
    `scratch-stop`: `msmarco-1chip`'s numbers under the `stopmix` law (mean
    6 words, 2-12, three in ten of them stop-words of ranks 0-19), with
    warm-up strata at the edges of the launch ladder's rungs (65,536 /
    131,072 / 524,288 postings on the heaviest shard). `and1000-closed384`:
    the committed mix with `operator: and`. The chip rehearsal of PR 35
    ran on a `git archive` of the change with this laid over it."""
    bench_dir = os.path.join(root, "benchmarks")

    def load(*parts: str):
        with open(os.path.join(bench_dir, *parts), "r", encoding="utf-8") as f:
            return json.load(f)

    def add(obj, *parts: str) -> None:
        path = os.path.join(bench_dir, *parts)
        assert not os.path.exists(path), f"{path} is there: a file would be edited"
        with open(path, "w", encoding="utf-8") as f:
            json.dump(obj, f, indent=1)

    config = load("configs", "msmarco-1chip.json")
    config["name"] = "scratch-stop"
    config["generator"].update(
        query_law="stopmix", query_terms_min=2, query_terms_max=12,
        query_terms_mean=6, query_stop_share=0.3, query_band_lo=20, query_band_hi=3000)
    add(config, "configs", "scratch-stop.json")
    mix = load("traffic", "or1000-closed384.json")
    add(dict(mix, operator="and"), "traffic", "and1000-closed384.json")
    pruned = {"terms_max": 8}  # nine terms and more take the exact path
    add(dict(mix, warm_strata=[
        {"name": "s16", "postings_max": 65536, **pruned},
        {"name": "s32", "postings_min": 65536, "postings_max": 131072, **pruned},
        {"name": "s128", "postings_min": 131072, "postings_max": 524288, **pruned},
        {"name": "hot", "postings_min": 524288, **pruned},
        *[st for st in mix["warm_strata"] if st["name"].startswith("terms")]]),
        "traffic", "or1000-closed384-rungs.json")
    with open(os.path.join(root, "BENCHMARK.json"), "r", encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({**bench["configs"][0], "name": "scratch-stop",
                             "file": "benchmarks/configs/scratch-stop.json"})
    for name, cfg, mix_name in ((STOP_CELL, "scratch-stop", "or1000-closed384-rungs"),
                                (AND_CELL, "msmarco-1chip", "and1000-closed384")):
        bench["workloads"].append({"name": name, "config": cfg, "traffic": mix_name,
                                   "chips": 1, "why": "scratch (ISSUE 35)"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            # whatever a one-chip cell may report: the readers say what is there
            if {STANDING, "beir-quora-1chip.or1000-closed384"} & set(
                    metric.get("workloads", [])):
                metric["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)


@pytest.fixture(scope="module")
def scratch_tree(tmp_path_factory):
    """The benchmark's files copied, the program linked, the cells added."""
    root = str(tmp_path_factory.mktemp("tree"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "elasticsearch_tpu"),
               os.path.join(root, "elasticsearch_tpu"))
    add_scratch_cells(root)
    return root


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


@pytest.mark.parametrize("workload,trace", [(STOP_CELL, "0"), (AND_CELL, "1")])
def test_rehearsal_of_a_cell_that_only_files_added(scratch_tree, workload, trace):
    proc = run_cell(workload, "--trace", trace, "--rehearse", root=scratch_tree)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["responses_differing"]["value"] == 0
    index = os.path.join(scratch_tree, "bench_out", "index")
    (built,) = [d for d in os.listdir(index) if d.startswith(workload.split(".")[0])]
    files = set(os.listdir(os.path.join(index, built)))
    with open(os.path.join(index, built, "manifest.json"), "r", encoding="utf-8") as f:
        assert json.load(f)["reference_seconds"] > 0
    if workload == AND_CELL:
        # the reference of its operator was added by the run, without
        # indexing again, beside the `or` one that the build stores
        assert {"reference.npz", "reference-and.npz"} <= files
        assert "adding the reference of operator [and]" in proc.stderr
        assert proc.stderr.count("indexed ") == 1
    else:
        assert "reference-and.npz" not in files
        # 20,000 docs hold no query past the first rung: the other strata
        # are empty and skipped, as `heavy` is on four chips
        assert "warm [s16]" in proc.stderr and "warm [hot]" not in proc.stderr


def test_without_a_tpu_the_command_refuses_and_prints_nothing():
    proc = run_cell(CELLS[0], "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refusing to measure" in proc.stderr
