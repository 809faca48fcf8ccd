"""One timeline for host and device (common/tracing.py `stage`,
`ThreadStates`, `GcWatch`; `StageTimes` CPU seconds; named device
programs; the batcher threads' states).

The contract with the benchmark's readers is the NAMES: the launch
thread's states `batcher.*` and the completer's `completer.*` partition
each thread's time, `batch_dispatch` stays `lock + put + call`, the
request thread reports `rest_request` and `lower` with (sampled) CPU
seconds and `rest_render` on the wall clock, a full collection is counted
and annotated, and the jitted programs are named after their launch path.
"""

from __future__ import annotations

import gc
import glob
import http.client
import json
import os
import threading
import time
import weakref

import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node, serve
from elasticsearch_tpu.search import tpu_service
from elasticsearch_tpu.search.tpu_service import StageTimes

BATCHER_STATES = ("wait", "hold", "take", "prep", "lock", "put", "call",
                  "blocked")
COMPLETER_STATES = ("wait", "device_wait", "decode", "deliver")
CLIENTS = 16
LOAD_SECONDS = 2.0


@pytest.fixture(scope="module", autouse=True)
def _restore_kernel_knobs():
    """The toy nodes turn `compressed_pack` off, and the knobs are
    process-global: restore them for the rest of the suite."""
    saved = dict(tpu_service.KERNEL_CONFIG)
    yield
    tpu_service.KERNEL_CONFIG.update(saved)


class _Http:
    """Counts what it sends: `rest_request.count` must equal it."""

    def __init__(self, port: int):
        self.port = port
        self.sent = 0
        self._lock = threading.Lock()

    def request(self, method, path, body=None, conn=None):
        own = conn is None
        conn = conn or http.client.HTTPConnection("127.0.0.1", self.port,
                                                  timeout=120)
        raw = body if isinstance(body, (bytes, type(None))) \
            else json.dumps(body).encode("utf-8")
        conn.request(method, path, body=raw,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        with self._lock:
            self.sent += 1
        if own:
            conn.close()
        return resp.status, json.loads(data)


def _toy_node(path, sample_rate: float):
    """2 shards, raw pack (compressed packs route to the exact kernel:
    the pruned full-postings path is the one a chip-scale pack serves)."""
    node = Node(str(path), settings=Settings.of({
        "search.tracing.sample_rate": sample_rate,
        "search.tpu_serving.kernel.compressed_pack": False}))
    server = serve(node, port=0)
    http_ = _Http(server.server_address[1])
    status, _ = http_.request("PUT", "/toy", {
        "settings": {"number_of_shards": 2},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    assert status == 200
    lines = []
    for i in range(1500):
        lines.append(json.dumps({"index": {"_index": "toy", "_id": str(i)}}))
        lines.append(json.dumps({"body": " ".join(
            f"w{(i * 7 + j * j) % 61}" for j in range(10))}))
    status, res = http_.request("POST", "/_bulk",
                                ("\n".join(lines) + "\n").encode("utf-8"))
    assert status == 200 and not res["errors"]
    assert http_.request("POST", "/toy/_refresh")[0] == 200
    return node, server, http_


def _search(http_, i: int, conn=None):
    status, body = http_.request("POST", "/toy/_search", {
        "query": {"match": {"body": f"w{i % 61} w{(3 * i + 1) % 61}"}},
        "size": 10}, conn=conn)
    assert status == 200 and body["hits"]["hits"], body
    return body


def _closed_loop(http_, clients: int, seconds: float) -> None:
    stop = time.monotonic() + seconds

    def client(c: int) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", http_.port,
                                          timeout=120)
        i = c
        while time.monotonic() < stop:
            _search(http_, i, conn=conn)
            i += clients
        conn.close()

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """A toy node over real HTTP: warmed, driven by 16 closed-loop
    clients for 2 s, then closed so that both batcher threads have
    closed their last state. Yields what the tests read."""
    node, server, http_ = _toy_node(tmp_path_factory.mktemp("loaded"), 1.0)
    try:
        _search(http_, 0)                       # the 8-row program
        _closed_loop(http_, CLIENTS, 0.5)       # the 64-row program
        _search(http_, 5)                       # a request alone
        _closed_loop(http_, CLIENTS, LOAD_SECONDS)
        status, stats = http_.request("GET", "/_tpu/stats")
        assert status == 200
        prom = node.metrics.prometheus_text()
        queues = list(node.tpu_search.batcher._queues.values())
        assert len(queues) == 1
        queue = queues[0]
        spans = node.tracer.spans(limit=0)
        # the request threads add `rest_request` after the client has its
        # response: wait for the last ones
        stages = node.tpu_search.stages
        deadline = time.monotonic() + 5.0
        while (stages.snapshot().get("rest_request", {}).get("count", 0)
               < http_.sent and time.monotonic() < deadline):
            time.sleep(0.01)
        answered = http_.sent
        rest_count = stages.snapshot()["rest_request"]["count"]
    finally:
        server.shutdown()
        server.server_close()
        node.close()
    queue.thread.join(timeout=10)
    queue.completer.join(timeout=10)
    yield {"stats": stats, "final": node.tpu_search.stages.snapshot(),
           "queue": queue, "spans": spans,
           "answered": answered, "rest_count": rest_count, "prom": prom}


def _sum(stages, prefix, states, field="seconds"):
    return sum(stages.get(f"{prefix}.{s}", {}).get(field, 0.0) for s in states)


@pytest.mark.parametrize("prefix,states,attr", [
    ("batcher", BATCHER_STATES, "launch_states"),
    ("completer", COMPLETER_STATES, "complete_states"),
])
def test_a_batcher_threads_states_partition_its_lifetime(loaded, prefix,
                                                         states, attr):
    thread_states = getattr(loaded["queue"], attr)
    assert thread_states.state is None          # closed with the thread
    lifetime = thread_states.closed_at - thread_states.opened_at
    assert lifetime > LOAD_SECONDS
    total = _sum(loaded["final"], prefix, states)
    assert total == pytest.approx(lifetime, rel=0.02)
    # and no state outside the contract's names
    assert {k for k in loaded["final"] if k.startswith(prefix + ".")} <= {
        f"{prefix}.{s}" for s in states}
    # the states the load must have visited
    for s in ("prep", "lock", "put", "call") if prefix == "batcher" \
            else COMPLETER_STATES:
        assert loaded["final"][f"{prefix}.{s}"]["count"] > 0, s


def test_cpu_seconds_never_exceed_wall_seconds(loaded):
    both = {k: v for k, v in loaded["final"].items() if "cpu_seconds" in v}
    assert {"lower", "rest_request", "batcher.prep", "batcher.call",
            "completer.decode"} <= set(both)
    # rendering is timed on the wall clock alone (the CPU clock is a
    # system call, and no metric reads it there)
    assert "cpu_seconds" not in loaded["final"]["rest_render"]
    for name, v in both.items():
        # snapshot rounds both to 1e-4
        assert v["cpu_seconds"] <= v["seconds"] + 2e-4, (name, v)
        assert 0 < v["cpu_count"] <= v["count"], (name, v)
    # per-train stages read the CPU clock every time, per-request stages
    # the first time and then once in CPU_SAMPLE_EVERY
    assert both["batcher.call"]["cpu_count"] == both["batcher.call"]["count"]
    every = StageTimes.CPU_SAMPLE_EVERY
    for name in ("lower", "rest_request"):
        assert both[name]["cpu_count"] == -(-both[name]["count"] // every)
    # a thread that sleeps on a condition burns no CPU there
    wait = loaded["final"]["completer.wait"]
    assert wait["cpu_seconds"] < 0.5 * wait["seconds"]


def test_batch_dispatch_stays_lock_plus_put_plus_call(loaded):
    st = loaded["final"]
    parts = _sum(st, "batcher", ("lock", "put", "call"))
    assert parts == pytest.approx(st["batch_dispatch"]["seconds"], rel=0.01,
                                  abs=2e-4)
    assert st["batcher.call"]["count"] == st["batch_dispatch"]["count"]


def test_rest_request_counts_the_requests_answered(loaded):
    assert loaded["rest_count"] == loaded["answered"]
    st = loaded["final"]
    # rendering happens inside the request, lowering inside searches only
    assert st["rest_render"]["count"] <= st["rest_request"]["count"]
    assert st["rest_render"]["seconds"] <= st["rest_request"]["seconds"]
    assert 0 < st["lower"]["count"] < st["rest_request"]["count"]


def test_stats_gain_keys_and_nothing_else_changes_shape(loaded):
    stats = loaded["stats"]
    gc_stats = stats["runtime"]["gc"]
    assert set(gc_stats) == {
        "full_collections", "full_pause_seconds", "longest_pause_ms",
        "freezes", "resettles", "frozen_objects"}
    # the node froze what it had built when it opened, and again when the
    # pack became resident and its programs compiled; nothing was replaced
    assert gc_stats["freezes"] >= 3
    assert gc_stats["frozen_objects"] > 0
    # a train of up to eight rides at 32 slots, a taller one of light
    # queries launches at 16 (`FULL_ROW_BUCKETS`)
    assert stats["launches"].get("full_s32", 0) > 0
    assert stats["launches"]["full_s32"] \
        + stats["launches"].get("full_s16", 0) == \
        stats["stages"]["batcher.call"]["count"]
    for old in ("batch_prep", "batch_dispatch", "batch_device_wait",
                "batch_decode", "batch_wait", "lower", "pack_get"):
        assert old in stats["stages"], old
    assert "cpu_seconds" in stats["stages"]["lower"]
    # the Prometheus view follows
    assert 'es_tpu_search_tpu_stage_cpu_seconds_total{stage="batcher.prep"}' \
        in loaded["prom"]
    assert 'es_tpu_kernel_launches_total{path="full_s32"}' in loaded["prom"]
    # trains by the reason their hold ended (process-wide, as `launches`)
    assert set(stats["hold_exit"]) == set(tpu_service.HOLD_EXITS)
    assert sum(stats["hold_exit"].values()) >= stats["batches"] > 0
    assert 'es_tpu_batcher_hold_exit_total{hold_exit="idle_window"}' \
        in loaded["prom"]


def test_a_traced_request_holds_batcher_prep_with_its_train(loaded):
    spans = loaded["spans"]
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    # a request that travelled alone is the first traced query of its
    # train: the batch workers' spans hang under it
    mine = next(t for t in by_trace.values()
                if any(s["name"] == "tpu.batch_launch"
                       and s["attributes"]["queries"] == 1 for s in t))
    launch = next(s for s in mine if s["name"] == "tpu.batch_launch")
    train = launch["attributes"]["train"]
    assert train >= 1
    preps = [s for s in mine if s["name"] == "tpu.batcher.prep"]
    assert preps and all(s["attributes"]["train"] == train for s in preps)
    assert any(s["attributes"].get("path") == "full_s32" for s in preps)
    assert all(s["parent_id"] == launch["span_id"] for s in preps)
    call = next(s for s in mine if s["name"] == "tpu.batcher.call")
    assert call["attributes"] == {"train": train, "path": "full_s32",
                                  "rows": 8}
    finish = next(s for s in mine if s["name"] == "tpu.batch_finish")
    assert finish["attributes"]["train"] == train
    waits = [s for s in mine if s["name"] == "tpu.completer.device_wait"]
    assert waits and waits[0]["attributes"]["train"] == train


def test_gc_watch_counts_full_collections_only(tmp_data_path):
    node = Node(str(tmp_data_path), settings=Settings.of({}))
    try:
        assert node.gc_watch in gc.callbacks
        before = node.gc_watch.stats()["full_collections"]
        gc.collect(0)
        gc.collect(1)
        assert node.gc_watch.stats()["full_collections"] == before
        gc.collect()
        after = node.gc_watch.stats()
        assert after["full_collections"] == before + 1
        assert after["full_pause_seconds"] > 0
        assert after["longest_pause_ms"] > 0
        status, stats = node.handle("GET", "/_tpu/stats")
        assert stats["runtime"]["gc"]["full_collections"] >= before + 1
    finally:
        node.close()
    assert node.gc_watch not in gc.callbacks


@pytest.fixture
def heap(monkeypatch):
    """A policy object of the test's own: a node that another test of this
    process left open is not among the nodes it counts."""
    own = tracing.StandingHeap()
    monkeypatch.setattr(tracing, "HEAP", own)
    yield own
    gc.unfreeze()


def _gc_stats(node):
    status, stats = node.handle("GET", "/_tpu/stats")
    assert status == 200
    return stats["runtime"]["gc"]


def _index_toy(node, name="toy", docs=40):
    assert node.handle("PUT", f"/{name}", body={
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"body": {"type": "text"}}}})[0] == 200
    for i in range(docs):
        assert node.handle("PUT", f"/{name}/_doc/{i}", body={
            "body": f"w{i % 7} w{i % 3} common"})[0] in (200, 201)
    assert node.handle("POST", f"/{name}/_refresh")[0] == 200


def _search_toy(node, name="toy"):
    status, body = node.handle("POST", f"/{name}/_search", body={
        "query": {"match": {"body": "w1 common"}}, "size": 5})
    assert status == 200 and body["hits"]["hits"], body


def test_an_open_node_has_frozen_its_heap(tmp_data_path, heap):
    assert heap.stats()["freezes"] == 0
    node = Node(str(tmp_data_path), settings=Settings.of({}))
    try:
        assert gc.get_freeze_count() > 0
        stats = _gc_stats(node)
        assert stats["freezes"] >= 1 and stats["resettles"] == 0
        assert stats["frozen_objects"] == gc.get_freeze_count()
    finally:
        node.close()
    assert gc.get_freeze_count() == 0


def test_without_a_node_nothing_is_frozen(heap):
    gc.unfreeze()
    heap.freeze()
    heap.settle()
    heap.settle(replaced=True)
    assert gc.get_freeze_count() == 0
    assert heap.stats() == {"freezes": 0, "resettles": 0,
                            "frozen_objects": 0}


def test_placing_a_pack_and_compiling_its_programs_raise_freezes(
        tmp_data_path, heap):
    node = Node(str(tmp_data_path), settings=Settings.of({}))
    try:
        _index_toy(node)
        opened = _gc_stats(node)
        _search_toy(node)           # builds the pack, compiles a program
        placed = _gc_stats(node)
        # `_place_pack`, the base build's settle, the program's first launch
        assert placed["freezes"] >= opened["freezes"] + 3
        assert placed["resettles"] == opened["resettles"]
        assert node.tpu_search.served >= 1
        _search_toy(node)           # the same program again: nothing new
        assert _gc_stats(node)["freezes"] == placed["freezes"]
    finally:
        node.close()


@pytest.mark.parametrize("event", ["pack_replaced", "index_deleted",
                                   "index_closed"])
def test_dropping_long_lived_state_resettles(tmp_data_path, heap, event):
    node = Node(str(tmp_data_path), settings=Settings.of({}))
    try:
        _index_toy(node)
        _search_toy(node)
        before = _gc_stats(node)
        if event == "pack_replaced":
            # a delete changes the live docs: the base pack is built anew
            assert node.handle("DELETE", "/toy/_doc/1")[0] == 200
            assert node.handle("POST", "/toy/_refresh")[0] == 200
            _search_toy(node)
        elif event == "index_deleted":
            assert node.handle("DELETE", "/toy")[0] == 200
        else:
            assert node.handle("POST", "/toy/_close")[0] == 200
        after = _gc_stats(node)
        assert after["resettles"] >= before["resettles"] + 1
        assert after["frozen_objects"] > 0
    finally:
        node.close()


def test_two_nodes_share_one_frozen_heap(tmp_path, heap):
    first = Node(str(tmp_path / "a"), settings=Settings.of({}))
    second = Node(str(tmp_path / "b"), node_name="node-2",
                  settings=Settings.of({}))
    assert heap.nodes == 2
    second.close()
    # the node that is left still serves from a frozen heap
    assert heap.nodes == 1 and gc.get_freeze_count() > 0
    assert _gc_stats(first)["resettles"] == 1
    second.close()                  # closing twice counts once
    assert heap.nodes == 1
    first.close()
    assert heap.nodes == 0 and gc.get_freeze_count() == 0


def test_collection_pauses_while_the_first_node_of_a_process_opens(heap):
    assert gc.isenabled()
    with heap.opening():
        assert not gc.isenabled()    # one thread builds, nobody serves
    assert gc.isenabled() and heap.nodes == 1 and gc.get_freeze_count() > 0
    with heap.opening():
        assert gc.isenabled()        # the first node may be serving
    assert heap.nodes == 2
    heap.node_closed()
    heap.node_closed()
    # a node that fails to open is not counted, and collection is back on
    with pytest.raises(RuntimeError):
        with heap.opening():
            assert not gc.isenabled()
            raise RuntimeError("no such data path")
    assert gc.isenabled() and heap.nodes == 0
    assert gc.get_freeze_count() == 0


def test_the_policy_counts_every_call_of_many_threads(heap):
    import sys
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with heap.opening():
            pass
        opened = heap.stats()

        def worker():
            for i in range(40):
                heap.freeze()
                if i % 10 == 0:
                    heap.settle(replaced=i % 20 == 0)

        threads = [threading.Thread(target=worker) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        stats = heap.stats()
        assert stats["freezes"] == opened["freezes"] + 16 * 44
        assert stats["resettles"] == 16 * 2
        assert gc.get_freeze_count() > 0
    finally:
        sys.setswitchinterval(old)
        heap.node_closed()
    assert gc.get_freeze_count() == 0


class _Knot:
    def __init__(self):
        self.me = self


def test_a_frozen_cycle_lives_until_a_resettle(tmp_data_path, heap):
    node = Node(str(tmp_data_path), settings=Settings.of({}))
    try:
        knot = _Knot()
        alive = weakref.ref(knot)
        heap.freeze()
        del knot
        gc.collect()
        assert alive() is not None   # no collection walks frozen objects
        heap.settle()                # nor does a settle that replaced nothing
        assert alive() is not None
        heap.settle(replaced=True)
        assert alive() is None
        # what reference counts free needs no collection, frozen or not
        plain = _Knot()
        plain.me = None
        gone = weakref.ref(plain)
        heap.freeze()
        del plain
        assert gone() is None
    finally:
        node.close()


def test_gc_watch_counts_a_collection_of_a_frozen_heap(tmp_data_path, heap):
    node = Node(str(tmp_data_path), settings=Settings.of({}))
    try:
        assert gc.get_freeze_count() > 0
        before = node.gc_watch.stats()["full_collections"]
        gc.collect()
        assert node.gc_watch.stats()["full_collections"] == before + 1
        heap.settle(replaced=True)   # the policy's own collections count too
        assert node.gc_watch.stats()["full_collections"] == before + 2
    finally:
        node.close()


def test_stage_without_sampling_allocates_no_span(tmp_path, monkeypatch):
    made = []
    real_init = tracing.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(a[4] if len(a) > 4 else kw.get("name"))
        real_init(self, *a, **kw)

    monkeypatch.setattr(tracing.Span, "__init__", counting_init)
    node, server, http_ = _toy_node(tmp_path / "unsampled", 0.0)
    try:
        for i in range(3):
            _search(http_, i)
        with tracing.stage(node.tpu_search.stages, "probe", train=1):
            pass
        snap = node.tpu_search.stages.snapshot()
        assert snap["probe"]["count"] == 1
        assert snap["batcher.call"]["count"] >= 1
        assert made == []
        assert node.tracer.spans(limit=0) == []
    finally:
        server.shutdown()
        server.server_close()
        node.close()


def test_thread_states_partition_by_construction():
    stages = StageTimes()
    states = tracing.ThreadStates(stages, "t")
    states.switch("a")
    time.sleep(0.01)
    states.switch("b", train=7)
    states.note(pending=3)
    x = 0
    for i in range(20000):
        x += i
    states.switch("a")
    states.close()
    assert states.state is None
    total = sum(stages.seconds.values())
    # the same clock reading closes one state and opens the next
    assert total == pytest.approx(states.closed_at - states.opened_at,
                                  abs=1e-9)
    assert stages.counts == {"t.a": 2, "t.b": 1}
    assert stages.cpu_seconds["t.a"] < 0.005 < stages.seconds["t.a"]
    # the null object code off a batcher thread switches
    assert tracing.current_states() is tracing.NO_STATES
    tracing.NO_STATES.switch("prep", train=1)
    tracing.NO_STATES.note(pending=1)
    tracing.NO_STATES.close()


def test_stage_records_wall_and_cpu_and_passes_meta_to_the_span():
    stages = StageTimes()
    tracer = tracing.Tracer(sample_rate=1.0)
    root = tracer.start_span("root", root=True)
    with tracing.use_span(root):
        with tracing.stage(stages, "work", train=4, path="full_s32") as st:
            time.sleep(0.005)
    root.end()
    assert st.seconds >= 0.005 > st.cpu_seconds >= 0.0
    snap = stages.snapshot()["work"]
    assert snap["count"] == 1 and snap["cpu_seconds"] <= snap["seconds"]
    child = next(s for s in tracer.spans(limit=0) if s["name"] == "tpu.work")
    assert child["attributes"] == {"train": 4, "path": "full_s32"}
    assert child["duration_ms"] == pytest.approx(st.seconds * 1e3, abs=1e-2)
    # a stage with nowhere to record still times its block
    with tracing.stage(None, "nowhere", annotate=False, cpu=False) as st2:
        pass
    assert st2.seconds >= 0.0 and st2.cpu_seconds is None


@pytest.mark.parametrize("path", ["full_s32", "hot_c65536", "exact_ref"])
def test_device_programs_are_named_after_their_launch_path(path):
    import jax
    import numpy as np

    from elasticsearch_tpu.parallel import distributed as dist
    from elasticsearch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(shape=(1, 1), devices=jax.devices()[:1])
    f32, i32 = np.float32, np.int32
    s, b, p_pad, max_len = 1, 8, 256, 128
    posts = (jax.ShapeDtypeStruct((s, p_pad), i32),
             jax.ShapeDtypeStruct((s, p_pad), f32))
    if path.startswith("exact_"):
        fn = dist.make_distributed_search(
            mesh, max_len=max_len, d_pad=64, p_pad=p_pad, k=8, t_window=8,
            variant=path[len("exact_"):])
        t = 8
        args = posts + (jax.ShapeDtypeStruct((s, b, t), i32),
                        jax.ShapeDtypeStruct((s, b, t), i32),
                        jax.ShapeDtypeStruct((s, b, t), f32),
                        jax.ShapeDtypeStruct((b,), i32))
    else:
        t, t_terms = 8, 8
        fn = dist.make_pruned_search(
            mesh, max_len=max_len, d_pad=64, p_pad=p_pad, c_cand=128,
            k_out=128, t_window=8, t_terms=t_terms,
            with_rescore=not path.startswith("full_"), name=path)
        args = posts + posts + (
            jax.ShapeDtypeStruct((s, b, 3 * t + 3 * t_terms + 1), f32),)
    assert fn.__name__ == path
    text = fn.lower(*args).as_text()
    assert f"module @jit_{path} " in text, text[:200]
    assert "jit_body" not in text.split("\n", 1)[0]


def test_a_profiler_session_carries_the_batcher_annotations(tmp_path):
    """The CPU backend writes a host plane too: a session started by
    REST around ten requests holds the program's own spans."""
    from jax.profiler import ProfileData

    node, server, http_ = _toy_node(tmp_path / "session", 0.0)
    try:
        _search(http_, 0)
        status, out = http_.request("POST",
                                    "/_tpu/profile/device/start?name=s1")
        assert status == 200 and out["started"], out
        for i in range(10):
            _search(http_, i)
        gc.collect()
        status, out = http_.request("POST", "/_tpu/profile/device/stop")
        assert status == 200 and out["stopped"], out
        paths = glob.glob(os.path.join(out["dir"], "**", "*.xplane.pb"),
                          recursive=True)
    finally:
        server.shutdown()
        server.server_close()
        node.close()
    if not paths:
        pytest.skip("this build's CPU profiler wrote no .xplane.pb")
    names = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(("batcher.", "completer.", "gc.full")):
                    names.setdefault(name, []).append(dict(ev.stats))
    if not names:
        pytest.skip("this build's CPU profiler wrote no host TraceMe events")
    assert {"batcher.prep", "batcher.call", "completer.device_wait",
            "gc.full"} <= set(names), sorted(names)
    assert all(st.get("path") in ("full_s16", "full_s32")
               and st.get("train", 0) >= 1 for st in names["batcher.call"])
    # the Python tracer is off: the session holds the program's spans,
    # not every Python call of the request threads
    assert len(names["batcher.call"]) >= 10
