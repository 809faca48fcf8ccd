"""Host/device profiling layer (common/profiler.py + its REST surface).

Covers the PR-6 acceptance bars: the sampler is a strict no-op while
disabled, stays under its overhead budget while on, the batch_wait
decomposition sums back to the legacy aggregate, and a profiler-enabled
node serves /_tpu/profile/flamegraph, /_tpu/profile/timeline and a clean
/_prometheus/metrics scrape (the tier-1 smoke).
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from elasticsearch_tpu.common import profiler
from elasticsearch_tpu.common.profiler import HostSampler
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node


def _handle(node, method, path, params=None, body=None):
    raw = json.dumps(body).encode("utf-8") if body is not None else b""
    return node.handle(method, path, params, None, raw)


def _spin_ms(ms: float) -> None:
    """Burn CPU (not sleep) so the sampler sees a live stack."""
    end = time.perf_counter() + ms / 1e3
    x = 0
    while time.perf_counter() < end:
        x += 1


class TestSamplerOff:
    """search.profiler.enabled defaults to false: zero threads, zero
    hot-path allocations."""

    def test_disabled_node_has_no_sampler_thread(self, tmp_data_path):
        n = Node(str(tmp_data_path), settings=Settings.of({}))
        try:
            assert not n.profiler.sampler.running
            assert not any(t.name == "host-profiler"
                           for t in threading.enumerate())
        finally:
            n.close()

    def test_tagging_is_noop_while_off(self):
        assert not profiler.active()
        profiler.tag_thread("search", "deadbeef")
        profiler.tag_stage("query_phase")
        # the shared ident map must not have grown: tags allocate
        # nothing unless a sampler is running
        assert not profiler._TAGS
        profiler.untag_thread()  # must not raise either

    def test_disabled_endpoints_respond(self, tmp_data_path):
        n = Node(str(tmp_data_path), settings=Settings.of({}))
        try:
            status, body = _handle(n, "GET", "/_tpu/profile/flamegraph")
            assert status == 200
            assert body["enabled"] is False
            status, body = _handle(n, "GET", "/_tpu/profile/timeline")
            assert status == 200
            assert body["enabled"] is False and body["points"] == []
        finally:
            n.close()


class TestHostSampler:
    def test_samples_tagged_threads(self):
        s = HostSampler(hz=100.0, retention_s=30.0)
        s.start()
        try:
            profiler.tag_thread("search", "abc123")
            profiler.tag_stage("query_phase")
            _spin_ms(120)
        finally:
            profiler.untag_thread()
            s.stop()
        assert s.samples_total > 0
        folded = s.folded()
        assert folded, "sampler captured no stacks"
        mine = [line for line, _ in folded if line.startswith("search;")]
        assert mine, f"no search-pool samples in {folded[:3]}"
        # pool;thread;stage;frames... — stage tag rides in the fold
        assert any(";query_phase;" in line for line in mine)
        # trace_id filter narrows to this request's samples
        assert s.folded(trace_id="abc123")
        assert not s.folded(trace_id="no-such-trace")

    def test_stop_clears_shared_state(self):
        s = HostSampler(hz=100.0)
        s.start()
        profiler.tag_thread("get")
        s.stop()
        assert not profiler.active()
        assert not profiler._TAGS
        assert not any(t.name == "host-profiler"
                       for t in threading.enumerate())

    def test_default_hz_bounds_the_ticks_and_overhead_is_exported(self):
        # what the sampler costs is its rate: at most hz x duration
        # ticks, and its own busy share is exported for whoever reads a
        # speed (a cell on the chip; never a CPU test)
        s = HostSampler(hz=20.0)  # default search.profiler.hz
        t0 = time.perf_counter()
        s.start()
        try:
            time.sleep(0.6)
        finally:
            s.stop()
        elapsed = time.perf_counter() - t0
        stats = s.stats()
        assert 1 <= stats["ticks_total"] <= 20.0 * elapsed + 1
        assert stats["samples_total"] >= stats["ticks_total"]
        assert 0.0 <= stats["overhead_fraction"] <= 1.0

    def test_retention_expires_old_samples(self):
        # retention clamps to >= 1s, so drive _expire directly against
        # synthetic timestamps instead of sleeping the window out
        s = HostSampler(hz=20.0, retention_s=10.0)
        now = time.time()
        stack = ("a.py:f",)
        s._samples.append((now - 60.0, "search", "old", None, stack, None))
        s._samples.append((now - 1.0, "search", "new", None, stack, None))
        s._timeline.append((now - 60.0, {"pending": 1}))
        s._timeline.append((now - 1.0, {"pending": 2}))
        s._expire(now)
        assert len(s._samples) == 1 and s._samples[0][2] == "new"
        assert s.timeline() == [{"pending": 2, "t": now - 1.0}]


@pytest.fixture(scope="module")
def profiled_node(tmp_path_factory):
    """Tier-1 smoke fixture: a node with the sampling profiler ON and
    the TPU serving path enabled (default), with data and traffic."""
    path = tmp_path_factory.mktemp("profiled_node")
    n = Node(str(path), settings=Settings.of({
        "search": {"profiler": {"enabled": "true", "hz": "100"},
                   "tracing": {"sample_rate": "1.0"}}}))
    _handle(n, "PUT", "/prof", body={
        "mappings": {"properties": {"title": {"type": "text"}}}})
    for i in range(16):
        _handle(n, "PUT", f"/prof/_doc/{i}",
                body={"title": f"sampled document {i}"})
    _handle(n, "POST", "/prof/_refresh")
    for _ in range(8):
        status, res = _handle(n, "POST", "/prof/_search", body={
            "query": {"match": {"title": "sampled"}}})
        assert status == 200, res
    time.sleep(0.1)  # a few sampler ticks past the last query
    yield n
    n.close()


class TestProfiledNodeSmoke:
    def test_sampler_is_running(self, profiled_node):
        assert profiled_node.profiler.sampler.running
        assert any(t.name == "host-profiler" for t in threading.enumerate())

    def test_flamegraph_folded_text(self, profiled_node):
        status, text = _handle(profiled_node, "GET",
                               "/_tpu/profile/flamegraph")
        assert status == 200
        assert isinstance(text, str) and text
        for line in text.splitlines():
            stack, _, count = line.rpartition(" ")
            assert stack and int(count) > 0
        # batcher threads are attributed to their pools by name even
        # when no request tagged them
        assert "tpu_batcher;" in text or "tpu_completer;" in text

    def test_flamegraph_json_and_filters(self, profiled_node):
        status, body = _handle(profiled_node, "GET",
                               "/_tpu/profile/flamegraph",
                               params={"format": "json", "top": "5"})
        assert status == 200
        assert body["enabled"] is True
        assert body["samples_total"] > 0
        assert 0 < len(body["stacks"]) <= 5
        for entry in body["stacks"]:
            assert isinstance(entry["stack"], list) and entry["count"] > 0
        # unknown trace_id filters everything out but stays a 200
        status, text = _handle(profiled_node, "GET",
                               "/_tpu/profile/flamegraph",
                               params={"trace_id": "not-a-trace"})
        assert status == 200 and text == ""

    def test_timeline_carries_queue_gauges(self, profiled_node):
        status, body = _handle(profiled_node, "GET",
                               "/_tpu/profile/timeline")
        assert status == 200 and body["enabled"] is True
        assert body["points"], "no timeline points recorded"
        point = body["points"][-1]
        assert {"queues", "pending", "inflight", "t"} <= set(point)

    def test_batch_wait_split_sums_to_aggregate(self, profiled_node):
        stages = profiled_node.tpu_search.stages.snapshot()
        total = stages["batch_wait"]["seconds"]
        assert total > 0
        parts = sum(stages[f"batch_wait.{p}"]["seconds"]
                    for p in ("queue", "window", "dispatch", "completion"))
        # same-thread clock anchors: parts sum to the aggregate (5% is
        # the acceptance bar; the construction makes it ~exact)
        assert parts == pytest.approx(total, rel=0.05)
        # the four parts, and no per-variant sibling of any of them
        assert {k for k in stages if k.startswith("batch_wait.")} == {
            f"batch_wait.{p}"
            for p in ("queue", "window", "dispatch", "completion")
        }, sorted(stages)

    def test_stats_and_prometheus_scrape(self, profiled_node):
        status, stats = _handle(profiled_node, "GET", "/_tpu/stats")
        assert status == 200
        assert stats["profiler"]["sampler"]["running"] is True
        assert stats["profiler"]["sampler"]["samples_total"] > 0
        assert "queue" in stats
        status, text = _handle(profiled_node, "GET",
                               "/_prometheus/metrics")
        assert status == 200
        assert "# TYPE es_tpu_profiler_samples_total counter" in text
        sample = [l for l in text.splitlines()
                  if l.startswith("es_tpu_profiler_samples_total ")]
        assert sample and float(sample[0].split(" ")[1]) > 0
        assert "es_tpu_profiler_overhead_ratio" in text
        assert "es_tpu_search_tpu_queue_pending" in text
        # batch_wait sub-stages surface through the stage families
        assert 'stage="batch_wait.queue"' in text

    def test_hot_threads_reports_stacks(self, profiled_node):
        status, text = _handle(profiled_node, "GET", "/_nodes/hot_threads",
                               params={"snapshots": "3", "interval": "10ms"})
        assert status == 200 and isinstance(text, str)
        assert "Hot threads at" in text
        assert "snapshots in:" in text
        assert "(threading.py)" in text or "(tpu_service.py)" in text

    def test_device_profile_lifecycle(self, profiled_node):
        status, body = _handle(profiled_node, "POST",
                               "/_tpu/profile/device/start",
                               params={"name": "t1"})
        if not body.get("started"):
            # jax profiler can be unavailable in stripped builds; the
            # endpoint must degrade to a structured error, not a 500
            assert status == 409 and "error" in body
            return
        assert status == 200 and "t1" in body["dir"]
        # second start while one is live conflicts
        status2, body2 = _handle(profiled_node, "POST",
                                 "/_tpu/profile/device/start")
        assert status2 == 409
        status3, body3 = _handle(profiled_node, "POST",
                                 "/_tpu/profile/device/stop")
        assert status3 == 200 and body3["stopped"]
        # stop with nothing running conflicts too
        status4, _ = _handle(profiled_node, "POST",
                             "/_tpu/profile/device/stop")
        assert status4 == 409
        _, stats = _handle(profiled_node, "GET", "/_tpu/stats")
        assert stats["profiler"]["device"]["sessions_total"] >= 1
