"""Tests of the benchmark's own code. Run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of tier-1 (`pytest tests/`): this PR may add no file
outside the benchmark's directory. The rehearsals of whole cells are in
`test_rehearsal.py`.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from esbench import compare, corpus, layers, peaks, reference, roofline  # noqa: E402
from esbench import tracered, traffic, window  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), "r", encoding="utf-8") as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# ---------------------------------------------------------------------------
# traffic: functions of the seed alone
# ---------------------------------------------------------------------------

def test_query_order_is_a_function_of_the_seed_alone():
    a, b = traffic.query_order(3_000_000_019, 6980), traffic.query_order(3_000_000_019, 6980)
    other = traffic.query_order(7, 6980)
    assert (a == b).all()
    assert (a != other).any()
    assert sorted(a.tolist()) == sorted(other.tolist()) == list(range(6980))


def test_closed_clients_share_the_cycled_permutation():
    order = traffic.query_order(5, 10)
    sent = [traffic.closed_query(order, c, j, 4) for j in range(5) for c in range(4)]
    assert sent == [int(order[i % 10]) for i in range(20)]


def test_open_schedule_same_gaps_for_every_seed_in_another_order():
    spec = {"rate_per_s": 180.0, "arrivals": "poisson"}
    due_a, q_a = traffic.open_schedule(11, spec, 51.5, 6980)
    due_b, q_b = traffic.open_schedule(11, spec, 51.5, 6980)
    due_c, q_c = traffic.open_schedule(2**31 + 5, spec, 51.5, 6980)
    assert (due_a == due_b).all() and (q_a == q_b).all()
    assert due_a.shape == due_c.shape and (due_a != due_c).any()
    gaps_a, gaps_c = np.diff(due_a), np.diff(due_c)
    # the same stratified set of exponential gaps (the first gap is the
    # origin of each schedule, so compare all but the extremes' rounding)
    assert abs(np.sort(gaps_a).sum() - np.sort(gaps_c).sum()) / gaps_a.sum() < 0.01
    n = due_a.shape[0]
    assert n == int(np.ceil(180.0 * 51.5))
    assert abs(due_a[-1] / 1e9 - 51.5) < 0.2          # the mean gap is 1/rate
    gaps = traffic.open_gaps(11, 180.0, 9270, "poisson")
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.02  # exponential: cv 1
    assert sorted(gaps.tolist()) == sorted(traffic.open_gaps(12, 180.0, 9270, "poisson").tolist())


def test_warm_strata_extremes_first_and_empty_strata_skipped():
    postings = np.array([10, 500, 30, 200000, 40, 150000, 60, 70, 80, 90])
    n_terms = np.array([2, 3, 4, 5, 2, 3, 4, 5, 2, 3])
    spec = {"warm_clients": [[1, 8], [48, 3]],
            "warm_strata": [{"name": "light", "postings_to": 0.5},
                            {"name": "heavy", "postings_min": 131073, "clients": [[1, 4]]},
                            {"name": "terms12", "terms_min": 12}]}
    strata = traffic.warm_strata(spec, postings, n_terms)
    assert [s[0] for s in strata] == ["light", "heavy"]
    light, heavy = strata
    assert sorted(light[1].tolist()) == [0, 2, 4, 6, 7]
    assert light[1][0] == 7 and light[1][1] == 0       # heaviest, then lightest
    assert light[2] == [(1, 8), (48, 3)]
    assert sorted(heavy[1].tolist()) == [3, 5] and heavy[2] == [(1, 4)]


def test_request_body():
    body = json.loads(traffic.request_body("w1 w2", {"size": 1000}, "body"))
    assert body == {"query": {"match": {"body": "w1 w2"}}, "size": 1000, "_source": False}
    body = json.loads(traffic.request_body("w1 w2", {"size": 10, "operator": "and",
                                                     "source": True}, "body"))
    assert body["query"]["match"]["body"] == {"query": "w1 w2", "operator": "and"}
    assert body["_source"] is True


# ---------------------------------------------------------------------------
# window arithmetic
# ---------------------------------------------------------------------------

def _stream():
    """One completion every 10 ms from -5 s (ramp) to 12 s (drain), with a
    2 s stall inside the window at [4 s, 6 s) that completes nothing."""
    done = np.arange(-5.0, 12.0, 0.01)
    done = done[(done < 4.0) | (done >= 6.0)]
    done_ns = np.round(done * 1e9).astype(np.int64)
    return done_ns - int(0.5e9), done_ns  # each took 0.5 s


def test_window_excludes_ramp_and_drain_and_counts_a_stall():
    due_ns, done_ns = _stream()
    ok = np.ones(done_ns.shape[0], dtype=bool)
    t0, t1 = 0, int(10e9)
    # 10 s window, 8 s of completions at 100/s: the stall is in the rate
    assert window.completed_per_s(done_ns, ok, t0, t1) == pytest.approx(80.0)
    counts = window.attempted_failed(due_ns, done_ns, ok, t0, t1, "closed")
    assert counts == {"attempted": 800, "failed": 0}
    # failures count as attempted and complete nothing
    ok[done_ns == int(1e9)] = False
    assert window.completed_per_s(done_ns, ok, t0, t1) == pytest.approx(79.9)
    assert window.attempted_failed(due_ns, done_ns, ok, t0, t1, "closed")["failed"] == 1


def test_latency_belongs_to_the_window_its_request_was_due_in():
    due_ns = np.array([-1, 0, 5, 9, 10]) * int(1e9)
    done_ns = due_ns + np.array([1, 2, 3, 4, 5]) * int(1e8)
    ok = np.array([True, True, True, True, True])
    lat = window.latencies_ms(due_ns, done_ns, ok, 0, int(10e9))
    assert lat.tolist() == [200.0, 300.0, 400.0]      # due at 0, 5, 9 s
    assert window.percentile(lat, 50) == 300.0
    assert window.percentile(np.empty(0), 50) is None
    assert window.attempted_failed(due_ns, done_ns, ok, 0, int(10e9), "open") == {
        "attempted": 3, "failed": 0}


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_union_gaps_and_self_time():
    assert tracered.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert tracered.union_ns([]) == 0
    gaps = tracered.gaps_ns([(0, 10, "a"), (5, 15, "b"), (20, 30, "c")], 0, 40)
    assert gaps == [(15, 20, "b"), (30, 40, "c")]
    own = tracered.self_seconds([(0, 100, "while"), (10, 30, "sort"), (40, 50, "sort"),
                                 (200, 210, "x")])
    assert own == pytest.approx({"while": 70e-9, "sort": 30e-9, "x": 10e-9})


SMALL_TRACE = os.path.join(BENCH_DIR, "testdata", "small_trace.xplane.pb")


def test_reduction_of_the_recorded_trace_gives_the_known_idle_share():
    """`testdata/small_trace.xplane.pb` was recorded on a v5e by
    `tools/record_small_trace.py`: five launches of one small program
    with 20 ms sleeps between. Its numbers, worked out once by hand from
    the events (`testdata/small_trace.json`), are what the reduction has
    to give, and a brute-force raster of the same events has to agree."""
    with open(os.path.join(BENCH_DIR, "testdata", "small_trace.json"), "r",
              encoding="utf-8") as f:
        known = json.load(f)
    reduced = tracered.reduce_trace(SMALL_TRACE)
    assert reduced["device_planes"] == known["device_planes"]
    assert reduced["busy_s"] == pytest.approx(known["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(known["window_s"], rel=1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(known["idle_share"], rel=1e-9)
    assert sum(reduced["module_counts"].values()) == known["module_events"]
    # brute force: mark every 100 ns cell an op covers
    planes = tracered.load_device_events(SMALL_TRACE)
    ops = next(iter(planes.values()))[tracered.OPS_LINE]
    lo = min(s for s, _e, _n in ops)
    cells = np.zeros(int((max(e for _s, e, _n in ops) - lo) / 100) + 1, dtype=bool)
    for s, e, _n in ops:
        cells[int((s - lo) / 100):int(np.ceil((e - lo) / 100))] = True
    assert cells.sum() * 100 / 1e9 == pytest.approx(reduced["busy_s"], rel=0.02)
    assert len(reduced["idle_gaps"]) >= 4 and reduced["idle_gaps"][0][0] > 0.015


# ---------------------------------------------------------------------------
# the comparison rule
# ---------------------------------------------------------------------------

def _resp(ids, scores, total, relation="eq"):
    return {"timed_out": False, "_shards": {"total": 2, "successful": 2, "failed": 0},
            "hits": {"total": {"value": total, "relation": relation},
                     "hits": [{"_id": i, "_score": s} for i, s in zip(ids, scores)]}}


def test_compare_equal_and_near_tie_swap():
    ref_ids, ref_scores = ["1", "2", "3", "4"], [4.0, 3.0, 3.0 * (1 + 5e-6), 1.0]
    assert compare.compare_response(_resp(["1", "2", "3"], [4.0, 3.0, 3.0], 9),
                                    9, ref_ids, ref_scores, 3) == 0
    # 2 and 3 are within 1e-5 relative: swapped is a near-tie, not an error
    assert compare.compare_response(_resp(["1", "3", "2"], [4.0, 3.0, 3.0], 9),
                                    9, ref_ids, ref_scores, 3) == 2
    # the reference list runs past k through the tie at the cut
    assert compare.compare_response(_resp(["1", "3"], [4.0, 3.0], 9),
                                    9, ref_ids, ref_scores, 2) == 1


@pytest.mark.parametrize("resp, why", [
    (_resp(["1", "4", "3"], [4.0, 3.0, 3.0], 9), "not a near-tie"),
    (_resp(["1", "2", "3"], [4.0, 3.0, 3.001], 9), "score at rank 2"),
    (_resp(["1", "2", "3"], [4.0, 3.0, 3.0], 8), "hits.total"),
    (_resp(["1", "2"], [4.0, 3.0], 9), "hits returned"),
    (_resp(["1", "2", "2"], [4.0, 3.0, 3.0], 9), "duplicate"),
    (_resp(["1", "2", "3"], [4.0, 3.0, 3.0], 10, "gte"), "hits.total"),
])
def test_compare_rejects(resp, why):
    with pytest.raises(compare.Mismatch, match=why):
        compare.compare_response(resp, 9, ["1", "2", "3", "4"],
                                 [4.0, 3.0, 3.0 * (1 + 5e-6), 1.0], 3)


def test_compare_accepts_a_lower_bound_total():
    assert compare.compare_response(_resp(["1"], [4.0], 5, "gte"), 9, ["1"], [4.0], 1) == 0


def _stats(served=0, fallback=0, timeouts=0, tripped=False, platform="tpu"):
    return {"served": served, "fallback": fallback, "timeouts": timeouts,
            "tripped": tripped,
            "devices": {"platform": platform, "mesh_devices": 1, "mesh_devices_full": 1,
                        "degraded": None, "shed_packs": [], "health": {"quarantines": 0}},
            "watchdog": {"wedges": 0},
            "supervision": {"state": "serving", "recoveries": 0}}


def _served_by_kernel(*args):
    return compare.failures(compare.kernel_checks(*args))


def test_no_hidden_fallback_rule():
    before = _stats(served=100, fallback=3)            # warm-up's own history
    assert _served_by_kernel(before, _stats(served=150, fallback=3), 50, 1, "tpu") == []
    bad = _served_by_kernel(before, _stats(served=149, fallback=4), 50, 1, "tpu")
    assert any(b.startswith("served=49") for b in bad) and any("fallback=1" in b for b in bad)
    assert _served_by_kernel(before, _stats(served=150, fallback=3, tripped=True),
                                    50, 1, "tpu")
    assert _served_by_kernel(before, _stats(served=150, fallback=3, platform="cpu"),
                                    50, 1, "tpu")


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def test_murmur3_of_digit_ids_known_vectors():
    # murmur3_x86_32(seed 0) over UTF-16-LE, as Elasticsearch routes ids
    ids = np.array([0, 1, 7, 10, 42, 99, 100, 12345, 999999, 1105919, 1234567, 98765432])
    want = [384918240, -126235597, -1167431322, -1518128929, 767888706, -479628595,
            -1711464004, 2063592929, 2042457193, -84389047, -1330070713, 2006949447]
    assert reference.murmur3_of_digit_ids(ids).tolist() == want
    assert reference.shard_of_digit_ids(ids, 2).tolist() == [w % 2 for w in want]


def test_quantized_lengths_are_lucenes_byte4():
    got = reference.quantized_lengths(np.array([0, 1, 7, 8, 15, 16, 17, 55, 56, 63, 64, 330]))
    assert got.tolist() == [0, 1, 7, 8, 15, 16, 16, 52, 56, 60, 64, 320]


def test_reference_topk_by_hand():
    # 4 docs, 1 shard; term 5 in docs 0 (tf 2), 2 (tf 1); term 6 in doc 2, 3
    flat = np.array([5, 5, 1, 1,  1, 1, 1, 1,  5, 6, 1, 1,  6, 1, 1, 1], dtype=np.uint16)
    offsets = np.array([0, 4, 8, 12, 16])
    shards = reference.build_shard_indexes(flat, offsets, 1, [5, 6])
    assert shards[0].doc_count == 4 and shards[0].avgdl == 4.0
    total, docs, scores = reference.reference_topk(shards, [5, 6], 10)
    idf = np.log(1 + (4 - 2 + 0.5) / (2 + 0.5))
    norm = np.float32(1.2 * (1 - 0.75 + 0.75 * 4 / 4.0))

    def s(tf):
        return idf * 2.2 * tf / (tf + float(norm))
    want = {0: s(2), 2: s(1) + s(1), 3: s(1)}
    assert total == 3 and docs.tolist() == [2, 0, 3]
    assert scores.tolist() == pytest.approx([want[2], want[0], want[3]], rel=1e-6)
    # the cut keeps the tie at the cut: k=1 of scores (a, b, b) keeps one
    total, docs, _ = reference.reference_topk(shards, [6], 1)
    assert total == 2 and docs.tolist() == [2, 3]      # both tie at the cut


def test_per_shard_statistics_differ_from_global():
    rng = np.random.default_rng(0)
    lengths = rng.integers(8, 40, 400)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    flat = rng.integers(0, 50, int(offsets[-1])).astype(np.uint16)
    two = reference.build_shard_indexes(flat, offsets, 2, [3, 4])
    assert sum(sh.doc_count for sh in two) == 400
    shard_of = reference.shard_of_digit_ids(np.arange(400), 2)
    for s, sh in enumerate(two):
        assert sh.doc_count == int((shard_of == s).sum())
        assert (shard_of[sh.postings[3][0]] == s).all()


def test_corpus_is_a_function_of_its_configuration():
    gen = {"docs": 500, "vocab_size": 300, "zipf_s": 1.07, "mean_length": 55,
           "corpus_seed": 23, "num_queries": 40, "query_terms_min": 2,
           "query_terms_max": 5, "query_band_lo": 20, "query_band_hi": 3000}
    a, b = corpus.generate_corpus(gen), corpus.generate_corpus(gen)
    assert (a.flat == b.flat).all() and (a.offsets == b.offsets).all()
    assert a.num_docs == 500 and 8 <= a.lengths.min() and a.lengths.max() <= 330
    qa = corpus.generate_queries(gen)
    assert qa == corpus.generate_queries(gen)
    assert len({tuple(q) for q in qa}) == 40
    assert all(2 <= len(q) <= 5 and len(set(q)) == len(q) and min(q) >= 20 for q in qa)
    # more queries do not move the corpus or the first queries
    more = dict(gen, num_queries=60)
    assert (corpus.generate_corpus(more).flat == a.flat).all()
    assert corpus.generate_queries(more)[:40] == qa


# ---------------------------------------------------------------------------
# roofline, peaks
# ---------------------------------------------------------------------------

def test_bytes_of_one_launch_shape():
    # 128 queries x (2 shards x 32 slots x 4096) entries, top 1000:
    # 128 x (262,144 x 8 + 1000 x 8) bytes
    assert roofline.sorted_merge_topk_bytes(128, 262144, 1000) == 128 * (262144 * 8 + 8000)
    assert roofline.sorted_merge_topk_bytes(8, 512, 1000) == 8 * (512 * 8 + 512 * 8)
    ops = {"%sort.25 = (s32[128,262144]{1,0}, f32[128,262144]{1,0}) sort(...)": 7,
           "%sort.11 = (f32[128,64,4096]{2,1,0}) sort(...)": 7,
           "%sort.3 = (s32[128,65536]{1,0}) sort(...)": 7,
           "%sort.9 = (s32[8,1048576]{1,0}) sort(...)": 2,
           "%fusion.1 = f32[128,2051] fusion(...)": 7}
    assert roofline.launch_shapes(ops) == [(8, 1048576, 2), (128, 262144, 7)]
    least = 7 * 128 * (262144 * 8 + 8000) + 2 * 8 * (1048576 * 8 + 8000)
    share = roofline.roofline_share_pct(ops, 2.0, 1000, 819e9)
    assert share == pytest.approx(100 * least / 819e9 / 2.0)
    assert roofline.roofline_share_pct({}, 2.0, 1000, 819e9) is None


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_names_and_units_hold_only_the_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            names.append(entry["name"])
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in SOURCES
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for root, _dirs, files in os.walk(BENCH_DIR):
        if "__pycache__" in root:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(root, name), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_workload_resolves_to_files_that_exist():
    import run
    for w in BENCH["workloads"]:
        loaded = run.load_cell(w["name"])
        assert loaded["config"]["name"] == w["config"]
        assert loaded["config"]["chips"] == w["chips"]
        assert loaded["traffic"]["loop"] in traffic.LOOPS
        e2e = {m["name"] for m in loaded["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert loaded["per_layer"]
        for m in loaded["per_layer"]:
            # a per-layer metric moves an end-to-end metric of the same cell
            assert m["moves"] in e2e, (w["name"], m["name"], m["moves"])
            assert layers.find_reader(m["name"]) is not None, m["name"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"]), "r", encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and cfg["assumed"]


def test_every_list_names_a_cell_and_every_cell_has_its_files():
    """What a PR that adds a cell is refused for: a metric other than
    `setup_s` with no `workloads` list, a list that names a cell that is
    gone, a closed mix never warmed at its own client count."""
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] == "setup_s":      # every cell's: the driver refuses a list on it
            assert "workloads" not in m
            continue
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) <= cells, m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
    files = {c["name"]: c["file"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, files[w["config"]])), w["name"]
        path = os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(path), w["name"]
        spec = traffic.load_traffic(path)
        if spec["loop"] == "closed":
            assert spec["warm_clients"][-1][0] == spec["clients"], w["traffic"]


def test_a_fourth_cell_is_one_traffic_file_and_one_entry(tmp_path):
    """A later PR adds a cell with a traffic file and an entry in
    `workloads`, and edits no file that is there."""
    import run
    traffic_dir = tmp_path / "traffic"
    shutil.copytree(os.path.join(BENCH_DIR, "traffic"), traffic_dir)
    (traffic_dir / "or10-closed64.json").write_text(json.dumps({
        "loop": "closed", "clients": 64, "size": 10, "operator": "or", "source": True,
        "ramp_s": 2.0, "drain_s": 1.0, "warm_clients": [[1, 4], [64, 3]]}))
    bench = json.loads(json.dumps(BENCH))
    name = "msmarco-1chip.or10-closed64"
    bench["workloads"].append({"name": name, "config": "msmarco-1chip",
                               "traffic": "or10-closed64", "chips": 1, "why": "throw-away"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "msmarco-1chip.or1000-closed384" in m.get("workloads", []):
            m["workloads"].append(name)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    loaded = run.load_cell(name, str(path), str(traffic_dir))
    assert loaded["traffic"]["clients"] == 64 and loaded["traffic"]["size"] == 10
    assert {m["name"] for m in loaded["end_to_end"]} == {"qps", "hbm_bytes_per_doc", "setup_s"}
    assert all(layers.find_reader(m["name"]) for m in loaded["per_layer"])
    strata = traffic.warm_strata(loaded["traffic"], np.arange(10), np.full(10, 3))
    assert [(s[0], s[2]) for s in strata] == [("all", [(1, 4), (64, 3)])]
    with pytest.raises(run.BenchFailure):
        run.load_cell("no-such.cell", str(path), str(traffic_dir))


# ---------------------------------------------------------------------------
# per-layer readers
# ---------------------------------------------------------------------------

def test_json_reader_ratio_and_missing():
    read = layers.find_reader("lower_ms_per_q.closed")
    assert read({"window.stages.lower.seconds": 3.0, "window.stages.lower.count": 1500.0}) \
        == pytest.approx(2.0)
    assert read({}) is None                               # nothing to read
    assert read({"window.stages.lower.seconds": 3.0, "window.stages.lower.count": 0.0}) is None
    launch = layers.find_reader("launch_host_ms.open")
    facts = {"window.stages.batch_prep.seconds": 1.0, "window.stages.exact_prep.seconds": 0.5,
             "window.batches": 100.0}
    assert launch(facts) == pytest.approx(15.0)           # absent stages count 0
    assert layers.find_reader("no_such_metric.closed") is None


def test_flatten_and_difference():
    before = layers.flatten({"served": 10, "stages": {"lower": {"seconds": 1.5}},
                             "tripped": False, "last_error": None}, "s", {})
    after = layers.flatten({"served": 25, "stages": {"lower": {"seconds": 4.0}},
                            "tripped": False}, "s", {})
    assert before == {"s.served": 10.0, "s.stages.lower.seconds": 1.5, "s.tripped": 0.0}
    assert layers.difference(after, before, "s", "window") == {
        "window.served": 15.0, "window.stages.lower.seconds": 2.5, "window.tripped": 0.0}


def test_roofline_reader_reads_trace_facts():
    read = layers.find_reader("sorted_merge_topk_roofline.closed")
    facts = {"trace.op_count.%sort.1 = (s32[128,262144]) sort()": 10.0,
             "trace.module_s": 1.0, "request.size": 1000.0,
             "device.peak_hbm_bytes_per_s": 819e9}
    want = 100 * 10 * 128 * (262144 * 8 + 8000) / 819e9
    assert read(facts) == pytest.approx(want)
    assert read({"request.size": 1000.0}) is None


# ---------------------------------------------------------------------------
# the generator stays off jax
# ---------------------------------------------------------------------------

def test_the_generator_module_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "from esbench import loadgen, traffic, window, corpus, reference, compare; "
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'jaxlib'))"
            " or m.startswith('elasticsearch_tpu')]; "
            "print(bad); sys.exit(1 if bad else 0)" % BENCH_DIR)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_loadgen_speaks_its_protocol_without_a_server():
    proc = subprocess.Popen([sys.executable, os.path.join(BENCH_DIR, "esbench", "loadgen.py")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        proc.stdin.write(json.dumps({"cmd": "warm"}) + "\n")
        proc.stdin.write(json.dumps({"cmd": "init", "port": 1, "path": "/x/_search",
                                     "bodies": []}) + "\n")
        proc.stdin.write(json.dumps({"cmd": "nonsense"}) + "\n")
        proc.stdin.write(json.dumps({"cmd": "quit"}) + "\n")
        proc.stdin.flush()
        replies = [json.loads(proc.stdout.readline()) for _ in range(3)]
        assert proc.wait(timeout=30) == 0
    finally:
        proc.kill()
        proc.stdin.close()
        proc.stdout.close()
    assert replies[0]["error"] == "init first"
    assert replies[1]["ready"] is True and replies[1]["imported_jax"] is False
    assert "unknown command" in replies[2]["error"]


# ---------------------------------------------------------------------------
# run.py's own arithmetic
# ---------------------------------------------------------------------------

def test_probe_plans_and_rehearsal_traffic():
    import run
    spec = traffic.load_traffic(os.path.join(BENCH_DIR, "traffic", "or1000-closed384.json"))
    plans = run.probe_plans("384:20,256:45:2", spec)
    assert [(p["clients"], p["ramp_s"], s) for p, s in plans] == [(384, 5.0, 20.0),
                                                                  (256, 2.0, 45.0)]
    assert spec["clients"] == 384                      # the cell's own file is untouched
    open_spec = traffic.load_traffic(os.path.join(BENCH_DIR, "traffic", "or1000-open180.json"))
    assert run.probe_plans("90.5:10", open_spec)[0][0]["rate_per_s"] == 90.5
    toy = run.rehearsal_traffic(open_spec)
    assert toy["rate_per_s"] == run.REHEARSE_RATE and toy["workers"] == run.REHEARSE_CLIENTS
    assert max(c for c, _r in toy["warm_clients"]) == run.REHEARSE_CLIENTS
    assert all(c <= run.REHEARSE_CLIENTS for st in toy["warm_strata"]
               for c, _r in st.get("clients", []))
    assert open_spec["rate_per_s"] == 180.0


def test_gc_timer_keeps_long_pauses_relative_to_a_start():
    import run
    timer = run.GcTimer()
    timer.pauses = [(1_000_000_000, 1_001_000_000, "gc_gen0"),
                    (2_000_000_000, 3_100_000_000, "gc_gen2"),
                    (5_000_000_000, 5_030_000_000, "gc_gen1")]
    assert timer.long_pauses(1_500_000_000) == [(0.5, 1.1, "gc_gen2"), (3.5, 0.03, "gc_gen1")]
    assert timer.long_pauses(0, 0.5) == [(2.0, 1.1, "gc_gen2")]
    timer("start", {"generation": 2})
    timer("stop", {"generation": 2})
    assert timer.pauses[-1][2] == "gc_gen2" and timer.pauses[-1][1] >= timer.pauses[-1][0]


def test_trace_gaps_are_named_by_the_pause_that_covers_them():
    events = {"/device:TPU:0": {tracered.OPS_LINE: [(0.0, 1e9, "%a"), (2.2e9, 3e9, "%b"),
                                                    (3.5e9, 4e9, "%c")],
                                tracered.MODULES_LINE: [(0.0, 1e9, "jit_f(1)")]}}
    real = tracered.load_device_events
    tracered.load_device_events = lambda path: events
    try:
        reduced = tracered.reduce_trace("unused", [(1.05e9, 2.15e9, "gc_gen2")])
    finally:
        tracered.load_device_events = real
    assert reduced["busy_s"] == pytest.approx(2.3) and reduced["window_s"] == pytest.approx(4.0)
    assert reduced["idle_gaps"][0] == (pytest.approx(1.2), "gc_gen2 after %a")
    assert reduced["idle_gaps"][1] == (pytest.approx(0.5), "unattributed after %b")
    assert reduced["op_counts"] == {"%a": 1, "%b": 1, "%c": 1}
