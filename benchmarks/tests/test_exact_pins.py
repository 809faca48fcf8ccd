"""What PR 36 added for the cell `msmarco-and-1chip.and1000-closed384-pins`:
its configuration (`msmarco-1chip`'s data and queries, held to conjunction),
its traffic file (the committed mix with `operator: and`, and warm-up
strata that each launch at one slot pin of the exact kernel's ladder),
its entries in BENCHMARK.json, and four per-layer readers:
`exact_rows_under_pin_pct`, `exact_empty_pct` (counters of `/_tpu/stats`)
and `device_exact_s32_ms_per_launch`, `device_exact_s64_ms_per_launch`
(`esbench/exactpins.py` over the trace's `XLA Modules`). The readers are
silent on a program that lacks the counters or names its exact programs
without their shape, as the parent commit does.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_exact_pins.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from esbench import exactpins, exactprograms, hostspans, layers, traffic  # noqa: E402

CELL = "msmarco-and-1chip.and1000-closed384-pins"
STANDING = "msmarco-1chip.or1000-closed384"
QUORA = "beir-quora-1chip.or1000-closed384"
#: name → (unit, source, layer, the cells whose every run has something to read)
NEW = {
    "exact_rows_under_pin_pct.closed": ("%", "program_counter", "launch routing", [QUORA, CELL]),
    "exact_empty_pct.closed": ("%", "program_counter", "launch routing", [QUORA, CELL]),
    "device_exact_s32_ms_per_launch.closed": ("ms", "device_trace", "kernels", [QUORA, CELL]),
    "device_exact_s64_ms_per_launch.closed": ("ms", "device_trace", "kernels", [CELL]),
}
#: read by launches this cell never makes (the pruned ladder, the mesh)
NOT_THIS_CELLS = {"full_fill_pct.closed", "device_full_s16_ms_per_launch.closed",
                  "device_full_s32_ms_per_launch.closed",
                  "device_full_s128_ms_per_launch.closed",
                  "sorted_merge_topk_roofline.closed",
                  "cross_chip_merge_ms_per_launch.closed",
                  "cross_chip_merge_ici_pct.closed", "launch_skew_ms.closed",
                  "put_ms_per_train.closed"}
#: the modules of the traced rehearsal of this traffic (PERF.md section 5,
#: PR 35: `b128_s32` 174.3 ms x 10, `b128_s64` 360.6 ms x 12, 275.9 in all)
MODULES = {"jit_exact_ref_b128_s32_w8": (1.743, 10),
           "jit_exact_ref_b128_s64_w8": (4.3272, 12),
           "jit_exact_ref_b64_s32_w8": (0.07, 1),
           "jit_full_s16": (0.5, 7)}

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)


def read(name, facts):
    return layers.find_reader(name)(facts)


@pytest.fixture
def traced(monkeypatch):
    """`hostspans.of_run` as it answers for a traced run whose `XLA
    Modules` line holds `modules` (set by the test)."""
    holder = {"modules": dict(MODULES)}
    monkeypatch.setattr(hostspans, "of_run",
                        lambda facts, run_dir=None: {"modules": holder["modules"]}
                        if "trace.window_s" in facts else None)
    return holder


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------

def config_file(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json"), "r",
              encoding="utf-8") as f:
        return json.load(f)


def test_the_cell_runs_the_configuration_this_pr_added():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "msmarco-and-1chip", "and1000-closed384-pins", 1)
    assert BENCH["workloads"][-1] is cell          # added at the end
    assert BENCH["configs"][-1]["name"] == "msmarco-and-1chip"
    assert BENCH["configs"][-1]["reduced"] == []   # nothing cut for it
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(set(sources)) == len(sources)       # a deployment of its own


def test_the_configuration_is_the_standing_ones_data_held_to_conjunction():
    """Same corpus, shards, queries and node settings as `msmarco-1chip`
    (so what the two cells read differs by the operator alone); the
    guarantees are the standing ones' with conjunction stated."""
    ours, theirs = config_file("msmarco-and-1chip"), config_file("msmarco-1chip")
    for key in ("generator", "index", "node_settings", "chips", "reduced"):
        assert ours[key] == theirs[key], key
    assert ours["name"] == "msmarco-and-1chip" and ours["source"] != theirs["source"]
    assert ours["source"] == BENCH["configs"][-1]["source"]
    assert set(theirs["guarantees"]) - set(ours["guarantees"]) == {
        g for g in theirs["guarantees"] if g.startswith(("exact top-1000", "hits.total"))}
    said = " ".join(ours["guarantees"])
    for word in ("every term", "intersection", "relation eq", "no planner fallback"):
        assert word in said, word
    import run
    assert os.path.basename(run.index_dir_for(ours)).split("-")[-1] == \
        os.path.basename(run.index_dir_for(theirs)).split("-")[-1]   # one corpus


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_has_its_entry_and_its_reader(name):
    unit, source, layer, cells = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "qps", "workloads": cells}
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    assert layers.find_reader(name) is not None
    assert read(name, {}) is None                  # nothing to read: nothing said


def test_the_cell_is_on_the_lists_whose_readers_read_it_and_on_no_other():
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"qps", "hbm_bytes_per_doc"} <= listed
    assert not listed & NOT_THIS_CELLS
    # what the standing cell reports of the shared path, the exact path's
    # four of Quora's cell, and the four new ones
    shared = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if STANDING in m.get("workloads", [])} - NOT_THIS_CELLS
    quoras = {"exact_route_pct.closed", "exact_fill_pct.closed",
              "device_exact_ms_per_launch.closed", "exact_topk_roofline.closed"}
    assert listed == shared | quoras | set(NEW)
    # appended: the lists' older members keep their places
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]


# ---------------------------------------------------------------------------
# the traffic file
# ---------------------------------------------------------------------------

def spec_of(name):
    return traffic.load_traffic(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def test_the_mix_is_the_committed_one_with_the_other_operator():
    ours, theirs = spec_of("and1000-closed384-pins"), spec_of("or1000-closed384")
    assert ours["operator"] == "and" and theirs["operator"] == "or"
    for key in set(ours) | set(theirs):
        if key not in ("what", "operator", "warm_strata"):
            assert ours[key] == theirs[key], key
    assert ours["warm_clients"][-1][0] == ours["clients"] == 384
    for stratum in ours["warm_strata"]:            # each at 1, 48 and 384 clients
        clients = stratum.get("clients", ours["warm_clients"])
        assert [c for c, _r in clients] == [1, 48, 384], stratum["name"]


def test_every_query_of_a_stratum_launches_at_the_strata_pin():
    """A term's postings take ceil(p / 4096) slots, so a query of T terms
    with P postings on its heaviest shard needs between P / 4096 and
    P / 4096 + T slots: a stratum [lo, hi) lies inside one pin of the
    ladder (powers of two from 8) where lo / 4096 is past half the pin
    (the narrowest pin has no lower edge) and hi / 4096 + T stays within
    it. T is the configuration's `query_terms_max`."""
    most_terms = config_file("msmarco-and-1chip")["generator"]["query_terms_max"]
    pins = []
    for stratum in spec_of("and1000-closed384-pins")["warm_strata"]:
        assert set(stratum) <= {"name", "postings_min", "postings_max", "clients"}
        pin = int(stratum["name"][len("pin"):])
        lo, hi = stratum.get("postings_min", 0), stratum["postings_max"]
        assert hi / exactprograms.CHUNK_LEN + most_terms <= pin, stratum
        if pin > 8:
            assert lo / exactprograms.CHUNK_LEN > pin // 2, stratum
        pins.append(pin)
    assert pins == [8, 16, 32, 64]


def test_the_strata_select_by_value_and_skip_what_is_empty():
    spec = spec_of("and1000-closed384-pins")
    postings = np.array([100, 12287, 12288, 40000, 45056, 70000, 120000, 131073])
    strata = traffic.warm_strata(spec, postings, np.full(8, 3))
    assert [(name, sorted(idx.tolist())) for name, idx, _c in strata] == [
        ("pin8", [0, 1]), ("pin16", [3]), ("pin32", [5]), ("pin64", [7])]
    # a rehearsal's 20,000 docs hold no query past the first pin
    small = traffic.warm_strata(spec, np.array([50, 900]), np.full(2, 2))
    assert [name for name, _idx, _c in small] == ["pin8"]


# ---------------------------------------------------------------------------
# the counter readers
# ---------------------------------------------------------------------------

def test_the_counter_readers_are_ratios_of_the_windows_rise():
    facts = {"window.exact_pin.rows": 20480.0, "window.exact_pin.rows_under": 19988.0,
             "window.exact_results.queries": 20480.0, "window.exact_results.empty": 14500.0}
    assert read("exact_rows_under_pin_pct.closed", facts) == pytest.approx(
        100.0 * 19988 / 20480)
    assert read("exact_empty_pct.closed", facts) == pytest.approx(100.0 * 14500 / 20480)
    # Quora's exact launches all stand at 32 slots and none is empty: 0, said
    nought = dict(facts, **{"window.exact_pin.rows_under": 0.0,
                            "window.exact_results.empty": 0.0})
    assert read("exact_rows_under_pin_pct.closed", nought) == 0.0
    assert read("exact_empty_pct.closed", nought) == 0.0
    # no exact launch in the window: no share of nothing
    none = {key: 0.0 for key in facts}
    assert read("exact_rows_under_pin_pct.closed", none) is None
    assert read("exact_empty_pct.closed", none) is None
    # the parent's `/_tpu/stats` has neither family
    parent = {"window.exact_entries.real": 5.0, "window.exact_entries.padded": 50.0}
    assert read("exact_rows_under_pin_pct.closed", parent) is None
    assert read("exact_empty_pct.closed", parent) is None


# ---------------------------------------------------------------------------
# the trace readers
# ---------------------------------------------------------------------------

def test_each_pin_reads_its_own_programs_and_the_mix_is_both(traced):
    facts = {"trace.window_s": 6.07}
    assert read("device_exact_s32_ms_per_launch.closed", facts) == pytest.approx(174.3)
    assert read("device_exact_s64_ms_per_launch.closed", facts) == pytest.approx(360.6)
    assert read("device_exact_ms_per_launch.closed", facts) == pytest.approx(
        (1743.0 + 4327.2) / 22)
    assert exactpins.ms_per_launch(facts, rows=64, slots=32) == pytest.approx(70.0)
    assert exactpins.ms_per_launch(facts, rows=128, slots=16) is None
    # variants and windows of one shape are one pin
    traced["modules"]["jit_exact_packed_b128_s32_w16"] = (0.257, 10)
    assert read("device_exact_s32_ms_per_launch.closed", facts) == pytest.approx(100.0)


def test_a_window_without_the_pin_or_without_shapes_says_nothing(traced):
    facts = {"trace.window_s": 6.07}
    del traced["modules"]["jit_exact_ref_b128_s64_w8"]
    assert read("device_exact_s64_ms_per_launch.closed", facts) is None
    assert read("device_exact_s32_ms_per_launch.closed", facts) == pytest.approx(174.3)
    traced["modules"] = {"jit_exact_ref": (3.0, 20), "jit_full_s16": (0.5, 7)}
    assert read("device_exact_s32_ms_per_launch.closed", facts) is None
    # and an untraced run has no modules at all
    assert read("device_exact_s32_ms_per_launch.closed", {}) is None
    assert read("device_exact_s64_ms_per_launch.closed", {}) is None


def test_two_pins_in_a_window_add_up_in_the_roofline_share(traced):
    """`exact_topk_roofline` sums the least bytes over the programs by the
    shape in their names, so a window at two pins is counted at both."""
    facts = {"trace.window_s": 6.07, "request.size": 1000.0,
             "device.peak_hbm_bytes_per_s": 819e9}
    programs = exactprograms.of_run(facts)
    assert [(r, s, n) for r, s, _secs, n in programs] == [
        (64, 32, 1), (128, 32, 10), (128, 64, 12)]
    one_pin = exactprograms.least_bytes([p for p in programs if p[1] == 32], 1000)
    assert exactprograms.least_bytes(programs, 1000) > 2 * one_pin
    share = read("exact_topk_roofline.closed", facts)
    assert 0.0 < share < 1.0                       # percent: far under the roofline
