"""The host's waits, measured where they happen (ISSUE 38): a request's
`batch_wait.completion` split into `device`, `decode`, `deliver` and
`wake` from marks the completer stamps on its `_Pending`, the whole split
recorded under one taking of `StageTimes`' lock, and `batcher.prep` split
by the function that builds a launch (`prep.route`, `prep.query_batch`,
`prep.term_ranges`: `tracing.stage` blocks nested in the state).

The contract with the benchmark's readers is the names: the new stages
sit outside the `batcher.`, `completer.` and `rest_request.` prefixes, so
every reader that was there reads what it read at the parent commit.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import threading

import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import tpu_service
from elasticsearch_tpu.search.tpu_service import StageTimes, _Pending

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from esbench import hostspans, layers  # noqa: E402

SEVEN = ("queue", "window", "dispatch", "device", "decode", "deliver", "wake")
COMPLETION = ("device", "decode", "deliver", "wake")
PREP = ("prep.route", "prep.query_batch", "prep.term_ranges")
CLIENTS = 12


@pytest.fixture(scope="module", autouse=True)
def _restore_kernel_knobs():
    saved = dict(tpu_service.KERNEL_CONFIG)
    yield
    tpu_service.KERNEL_CONFIG.update(saved)


def _handle(node, method, path, params=None, body=None):
    raw = json.dumps(body).encode("utf-8") if body is not None else b""
    return node.handle(method, path, params, None, raw)


def _toy_node(path, **settings):
    """2 shards, a raw pack: OR queries take the pruned full-postings
    path, `operator: and` the exact kernel."""
    node = Node(str(path), settings=Settings.of({
        "search.tpu_serving.kernel.compressed_pack": False, **settings}))
    assert _handle(node, "PUT", "/toy", body={
        "settings": {"number_of_shards": 2},
        "mappings": {"properties": {"body": {"type": "text"}}}})[0] == 200
    lines = []
    for i in range(1200):
        lines.append(json.dumps({"index": {"_index": "toy", "_id": str(i)}}))
        lines.append(json.dumps({"body": " ".join(
            f"w{(i * 7 + j * j) % 61}" for j in range(10))}))
    status, res = node.handle("POST", "/_bulk", None, None,
                              ("\n".join(lines) + "\n").encode("utf-8"))
    assert status == 200 and not res["errors"]
    assert _handle(node, "POST", "/toy/_refresh")[0] == 200
    return node


def _search(node, i, operator="or"):
    status, body = _handle(node, "POST", "/toy/_search", body={
        "query": {"match": {"body": {
            "query": f"w{i % 61} w{(3 * i + 1) % 61}", "operator": operator}}},
        "size": 10})
    assert status == 200, body
    return body


def _concurrently(node, operator, per_client=4):
    def client(c):
        for j in range(per_client):
            _search(node, c + CLIENTS * j, operator)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A toy node serving OR and AND trains from twelve threads, with
    every `add_many` call of its stages recorded: one call a request."""
    node = _toy_node(tmp_path_factory.mktemp("served"))
    calls = {"or": [], "and": []}
    phase = ["warm"]
    stages = node.tpu_search.stages
    add_many = stages.add_many

    def recording(samples):
        if phase[0] in calls:
            calls[phase[0]].append(dict(samples))
        add_many(samples)

    stages.add_many = recording
    try:
        for operator in ("or", "and"):   # compile every program first
            _search(node, 0, operator)
            _concurrently(node, operator, per_client=1)
        before = stages.snapshot()
        for operator in ("or", "and"):
            phase[0] = operator
            _concurrently(node, operator)
        phase[0] = "done"
        after = stages.snapshot()
    finally:
        node.close()
    yield {"calls": calls, "before": before, "after": after}


@pytest.mark.parametrize("operator", ["or", "and"])
def test_every_served_query_splits_its_wait_into_seven_parts(served, operator):
    calls = served["calls"][operator]
    assert len(calls) == CLIENTS * 4
    for samples in calls:
        total = samples["batch_wait"]
        assert set(samples) == {"batch_wait"} | {
            f"batch_wait.{p}" for p in SEVEN + ("completion",)}
        parts = [samples[f"batch_wait.{p}"] for p in SEVEN]
        assert all(dt >= 0.0 for dt in parts), samples
        assert sum(parts) == pytest.approx(total, abs=1e-6)
        assert sum(samples[f"batch_wait.{p}"] for p in COMPLETION) == \
            pytest.approx(samples["batch_wait.completion"], abs=1e-6)
    # a train held more than one query: some waited behind another's delivery
    assert any(s["batch_wait.deliver"] > 0.0 for s in calls)


def test_the_split_takes_the_stages_lock_once():
    class CountingLock:
        def __init__(self):
            self.lock, self.taken = threading.Lock(), 0

        def __enter__(self):
            self.taken += 1
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    svc = tpu_service.TpuSearchService.__new__(tpu_service.TpuSearchService)
    svc.stages = StageTimes()
    svc.stages._lock = counting = CountingLock()
    pending = _Pending(None, 10, None, t_submit=1.0, t_cycle=1.5, t_take=2.0,
                       t_launched=3.0, t_ready=5.0, t_decoded=5.5, t_set=6.0)
    split = svc._record_batch_wait_split(pending, 1.0, 6.25)
    assert counting.taken == 1
    assert split == {"queue": 0.5, "window": 0.5, "dispatch": 1.0,
                     "completion": 3.25, "device": 2.0, "decode": 0.5,
                     "deliver": 0.5, "wake": 0.25}
    snap = svc.stages.snapshot()
    assert snap["batch_wait"]["seconds"] == 5.25
    assert {k for k in snap if k.startswith("batch_wait")} == {
        "batch_wait"} | {f"batch_wait.{p}" for p in split}
    # a future with no completer marks (a stub) gives the four parts, and
    # one with no marks at all only `batch_wait`: one taking either way
    bare = _Pending(None, 10, None, t_take=2.0, t_launched=3.0)
    assert set(svc._record_batch_wait_split(bare, 1.0, 4.0)) == {
        "queue", "window", "dispatch", "completion"}
    assert svc._record_batch_wait_split(None, 1.0, 4.0) is None
    assert counting.taken == 4          # the snapshot took it once too
    assert svc.stages.snapshot()["batch_wait"]["count"] == 3


def test_prep_stages_nest_in_batcher_prep(served):
    before, after = served["before"], served["after"]

    def delta(name, field):
        return after[name].get(field, 0.0) - before.get(name, {}).get(field, 0.0)

    for name in PREP:
        assert delta(name, "count") > 0, name
        assert after[name]["cpu_seconds"] <= after[name]["seconds"] + 2e-4, name
        assert after[name]["cpu_count"] == after[name]["count"], name
    # one routing a train, one operand build a launch
    assert delta("prep.query_batch", "count") == delta("batcher.call", "count")
    assert delta("prep.route", "count") <= delta("prep.query_batch", "count")
    # the AND trains made no pruned launch: fewer term-range builds
    assert delta("prep.term_ranges", "count") < delta("prep.query_batch", "count")
    for field in ("seconds", "cpu_seconds"):
        assert sum(delta(n, field) for n in PREP) <= \
            delta("batcher.prep", field) + 4e-4, field


READERS = ["gil_busy_pct", "launch_cpu_ms", "finish_cpu_ms", "batch_wait_ms_per_q",
           "batcher_launch_pct", "launch_host_ms", "dispatch_call_ms"]


@pytest.mark.parametrize("name", READERS)
def test_the_existing_readers_read_as_at_the_parent(served, name):
    """The change's stage snapshot, and the same snapshot without what this
    change adds (what the parent records), give every existing reader the
    same number: a nested stage is counted by none of them."""
    new = {k: v for k, v in served["after"].items()
           if k in PREP or k in {f"batch_wait.{p}" for p in COMPLETION}}
    assert len(new) == 7
    parent = {k: v for k, v in served["after"].items() if k not in new}
    facts = {"gen.window_s": 45.0, "window.batches": 10.0}
    reader = layers.find_reader(name + ".closed")
    got = reader(layers.flatten({"stages": served["after"]}, "window", dict(facts)))
    want = reader(layers.flatten({"stages": parent}, "window", dict(facts)))
    assert want is not None
    assert got == want


def test_hostspans_reads_the_same_idle_with_the_nested_spans():
    planes = {"/device:TPU:0": {"XLA Ops": [(0.0, 10e6, "%a"), (40e6, 50e6, "%b")]}}
    launch = [(0.0, 12e6, "batcher.hold"), (12e6, 35e6, "batcher.prep"),
              (35e6, 45e6, "batcher.call")]
    nested = launch + [(12e6, 14e6, "prep.route"), (14e6, 30e6, "prep.query_batch"),
                       (30e6, 34e6, "prep.term_ranges")]
    assert hostspans.reduce_spans([sorted(nested)], planes) == \
        hostspans.reduce_spans([launch], planes)


def test_a_profiler_session_nests_each_prep_span_in_batcher_prep(tmp_path):
    """The CPU backend writes a host plane too: each `prep.*` annotation
    lies inside a `batcher.prep` annotation on the launch thread's line."""
    from jax.profiler import ProfileData

    node = _toy_node(tmp_path / "session")
    try:
        _search(node, 0)
        _search(node, 0, "and")
        status, out = _handle(node, "POST", "/_tpu/profile/device/start",
                              params={"name": "s1"})
        assert status == 200 and out["started"], out
        for i in range(4):
            _search(node, i)
            _search(node, i, "and")
        status, out = _handle(node, "POST", "/_tpu/profile/device/stop")
        assert status == 200 and out["stopped"], out
        paths = glob.glob(os.path.join(out["dir"], "**", "*.xplane.pb"),
                          recursive=True)
    finally:
        node.close()
    if not paths:
        pytest.skip("this build's CPU profiler wrote no .xplane.pb")
    seen = {name: 0 for name in PREP}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, str(ev.name))
                      for ev in line.events]
            preps = [(s, e) for s, e, n in events if n == "batcher.prep"]
            for s, e, name in events:
                if name in seen:
                    seen[name] += 1
                    assert any(ps <= s and e <= pe for ps, pe in preps), name
    if not any(seen.values()):
        pytest.skip("this build's CPU profiler wrote no host TraceMe events")
    # four trains of each operator: a route each, an OR launch's two
    # operand builds, an AND launch's one
    assert seen == {"prep.route": 8, "prep.query_batch": 8, "prep.term_ranges": 4}
