"""The four-chip cell's per-layer readers (PR 33): `cross_chip_merge_ms_per_launch`,
`cross_chip_merge_ici_pct`, `launch_skew_ms` (the per-plane reduction of
`esbench/crosschip.py`) and `put_ms_per_train` (a stage ring). A small
synthetic event set of four device planes, with the values worked out by
hand beside it; every reader is silent on empty facts, on a trace of one
device plane (the recorded one-chip traces of `testdata/`) and on a node
that has no `cross_chip` counter or `batch_put` ring (the parent commit).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_crosschip.py -q -p no:cacheprovider
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
TESTDATA = os.path.join(BENCH_DIR, "testdata")
sys.path.insert(0, BENCH_DIR)

from esbench import crosschip, hostspans, layers, tracered  # noqa: E402

CELL = "msmarco-4chip.or1000-closed384"
NEW = {"cross_chip_merge_ms_per_launch.closed": ("ms", "lower", "device_trace", "kernels"),
       "cross_chip_merge_ici_pct.closed": ("%", "higher", "device_trace", "kernels"),
       "launch_skew_ms.closed": ("ms", "lower", "device_trace", "launch routing"),
       "put_ms_per_train.closed": ("ms", "lower", "program_span", "launch routing")}

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)

MS = 1e6  # ns
FULL = "jit_full_s16(123)"
GATHER = "%all-gather.7 = f32[128,4096]{1,0:T(8,128)} all-gather(f32[128,1024] %x)"
REDUCE_START = "%all-reduce-start.2 = s32[128]{0} all-reduce-start(s32[128] %t)"
REDUCE_DONE = "%all-reduce-done.2 = s32[128]{0} all-reduce-done(s32[128] %s)"
SORT = "%sort.3 = (s32[128,131072]{1,0}, f32[128,131072]{1,0}) sort(%a, %b)"


def four_planes():
    """Two launches of `jit_full_s16` and one of another program on four
    devices. Launch 1 starts at 0, 1, 2, 3 ms on devices 0-3 and takes 80
    ms on each; the device that starts first stands longest in the
    gather: 4, 3, 2, 1 ms, then 1 ms of all-reduce (start 0.2, done 0.8)
    on every device. Launch 2 starts at 100 ms + 0, 0.5, 0.5, 2 and
    gathers for 2 ms on every device. The other program (`jit_probe`, no
    collective counted: outside `jit_full_*`) starts at 200 ms + 0, 0, 0,
    0.4 and holds a collective-permute of 5 ms. A fourth launch of
    `jit_full_s16` is cut by the trace's edge: device 3 never shows it."""
    planes = {}
    for d in range(4):
        ops, modules = [], []
        s1 = [0, 1, 2, 3][d] * MS
        modules.append((s1, s1 + 80 * MS, FULL))
        ops.append((s1, s1 + 60 * MS, SORT))
        g = [4, 3, 2, 1][d] * MS
        ops.append((s1 + 60 * MS, s1 + 60 * MS + g, GATHER))
        ops.append((s1 + 70 * MS, s1 + 70.2 * MS, REDUCE_START))
        ops.append((s1 + 70.2 * MS, s1 + 71 * MS, REDUCE_DONE))
        s2 = (100 + [0, 0.5, 0.5, 2][d]) * MS
        modules.append((s2, s2 + 80 * MS, FULL))
        ops.append((s2, s2 + 60 * MS, SORT))
        ops.append((s2 + 60 * MS, s2 + 62 * MS, GATHER))
        s3 = (200 + [0, 0, 0, 0.4][d]) * MS
        modules.append((s3, s3 + 10 * MS, "jit_probe(9)"))
        ops.append((s3 + 1 * MS, s3 + 6 * MS,
                    "%collective-permute.1 = f32[8]{0} collective-permute(f32[8] %p)"))
        if d < 3:
            s4 = 300 * MS
            modules.append((s4, s4 + 5 * MS, FULL))
            ops.append((s4, s4 + 5 * MS, SORT))
        planes[f"/device:TPU:{d}"] = {tracered.OPS_LINE: sorted(ops),
                                      tracered.MODULES_LINE: sorted(modules)}
    return planes


# collective seconds inside `jit_full_*`, a device: launch 1's gather + 1 ms
# of all-reduce, launch 2's 2 ms → 7, 6, 5, 4 ms, mean 5.5 ms; launches a
# device 3, 3, 3, 2 → 2.75
HAND_COLLECTIVE_S = 5.5e-3
HAND_LAUNCHES = 2.75
# skews: 3 ms, 2 ms, 0.4 ms over the three launches that every plane shows
HAND_SKEW_MS = (3.0 + 2.0 + 0.4) / 3


def test_merge_bytes_is_the_other_devices_candidates():
    assert crosschip.merge_bytes(128, 1000, 4) == 128 * 1000 * 8 * 3
    assert crosschip.merge_bytes(128, 1000, 1) == 0
    assert crosschip.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(KeyError):
        crosschip.ici_bytes_per_s("TPU v9 imaginary")


@pytest.mark.parametrize("name, collective", [
    (GATHER, True), (REDUCE_START, True), (REDUCE_DONE, True),
    ("%collective-permute-done.4 = f32[8]{0} collective-permute-done(%s)", True),
    ("all-gather = f32[8]{0} all-gather(%x)", True),
    (SORT, False),
    ("%fusion.12 = f32[8]{0} fusion(%all-gather.7), kind=kLoop", False),
    ("%all-gather_fusion = f32[8]{0} fusion(%x)", False)])
def test_a_collective_is_known_by_the_op_its_name_opens_with(name, collective):
    assert crosschip.is_collective(name) is collective


def test_the_four_plane_set_gives_its_hand_count():
    red = crosschip.reduce_planes(four_planes())
    assert red["device_planes"] == 4
    assert red["collective_s"] == pytest.approx(HAND_COLLECTIVE_S)
    assert red["merged_launches"] == pytest.approx(HAND_LAUNCHES)
    assert red["skew_launches"] == 3
    assert 1000.0 * red["skew_s"] / 3 == pytest.approx(HAND_SKEW_MS)


@pytest.fixture
def four_chip_run(monkeypatch):
    """Facts of a traced run whose trace is the synthetic set, with the
    node's counters over the traced part: 3 launches of 128 rows on 4
    devices, 3 trains whose operand copies took 6 ms in all."""
    red = crosschip.reduce_planes(four_planes())
    monkeypatch.setattr(crosschip, "of_run",
                        lambda facts, run_dir=None: red
                        if "trace.window_s" in facts else None)
    return {"trace.window_s": 0.305, "request.size": 1000.0,
            "traced.cross_chip.launches": 3.0, "traced.cross_chip.rows": 384.0,
            "traced.cross_chip.devices": 12.0,
            "window.stages.batch_put.seconds": 0.006, "window.batches": 3.0}


def test_the_readers_on_the_four_plane_set(four_chip_run):
    facts = four_chip_run

    def read(name):
        return layers.find_reader(name + ".closed")(facts)

    per_launch_s = HAND_COLLECTIVE_S / HAND_LAUNCHES
    assert read("cross_chip_merge_ms_per_launch") == pytest.approx(1000 * per_launch_s)
    assert read("launch_skew_ms") == pytest.approx(HAND_SKEW_MS)
    assert read("put_ms_per_train") == pytest.approx(2.0)
    # 128 rows x 1000 x 8 B x 3 peers = 3,072,000 B a launch; at 200 GB/s
    # 15.36 us; of the 2 ms of collectives a launch, 0.768%
    least_s = 128 * 1000 * 8 * 3 / 200e9
    assert crosschip.merge_ici_pct(facts, "TPU v5 lite") == pytest.approx(
        100 * least_s / per_launch_s)
    assert crosschip.merge_ici_pct(facts, "TPU v5 lite") < 1.0
    with pytest.raises(KeyError):
        crosschip.merge_ici_pct(facts, "TPU v9 imaginary")
    # the parent's node: the trace reads, the counter and the ring do not
    parent = {k: v for k, v in facts.items()
              if "cross_chip" not in k and "batch_put" not in k}
    assert crosschip.merge_ici_pct(parent, "TPU v5 lite") is None
    assert layers.find_reader("put_ms_per_train.closed")(parent) is None
    assert layers.find_reader("launch_skew_ms.closed")(parent) is not None


@pytest.mark.parametrize("name", sorted(NEW))
def test_declared_for_the_four_chip_cell_and_silent_on_empty_facts(name):
    """(A later cell on several chips may join the lists: nothing here
    pins them to this one.)"""
    entry = dict(next(m for m in BENCH["per_layer"] if m["name"] == name))
    unit, better, source, layer = NEW[name]
    assert CELL in entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": "qps"}
    reader = layers.find_reader(name)
    assert reader is not None
    assert reader({}) is None
    assert reader({"trace.window_s": 1.0, "request.size": 1000.0}) is None  # no trace file


@pytest.mark.parametrize("trace", ["small_trace.xplane.pb", "annotated_trace.xplane.pb"])
@pytest.mark.parametrize("name", sorted(n for n in NEW if NEW[n][2] == "device_trace"))
def test_silent_on_a_trace_of_one_device(name, trace, tmp_path, monkeypatch):
    run_dir = tmp_path / "run"
    (run_dir / "cell" / "trace").mkdir(parents=True)
    shutil.copy(os.path.join(TESTDATA, trace), run_dir / "cell" / "trace" / trace)
    monkeypatch.setattr(hostspans, "RUN_DIR", str(run_dir))
    facts = {"trace.window_s": 1.0, "request.size": 1000.0,
             "traced.cross_chip.launches": 2.0, "traced.cross_chip.rows": 16.0,
             "traced.cross_chip.devices": 8.0}
    assert crosschip.of_run(facts) is None
    assert layers.find_reader(name)(facts) is None


def test_one_plane_and_no_plane_reduce_to_nothing():
    planes = four_planes()
    assert crosschip.reduce_planes({}) is None
    assert crosschip.reduce_planes({"/device:TPU:0": planes["/device:TPU:0"]}) is None
    two = {n: planes[n] for n in ("/device:TPU:0", "/device:TPU:3")}
    red = crosschip.reduce_planes(two)
    assert red["device_planes"] == 2 and red["skew_launches"] == 3
    assert 1000.0 * red["skew_s"] == pytest.approx(3.0 + 2.0 + 0.4)
