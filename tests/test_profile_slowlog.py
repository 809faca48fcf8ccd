"""Profile API + search slow log (reference: search/profile/**,
SearchSlowLog — SURVEY.md §5.1, §2.1#48)."""

from __future__ import annotations

import json
import logging

import pytest

from elasticsearch_tpu.common.logging import SEARCH_SLOWLOG, SlowLog, configure
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node


def _handle(node, method, path, params=None, body=None):
    raw = json.dumps(body).encode("utf-8") if body is not None else b""
    return node.handle(method, path, params, None, raw)


@pytest.fixture
def node(tmp_data_path):
    n = Node(str(tmp_data_path),
             settings=Settings.of({"search.tpu_serving.enabled": "false"}))
    yield n
    n.close()


class TestProfile:
    def _seed(self, node, index="p"):
        for i in range(8):
            _handle(node, "PUT", f"/{index}/_doc/{i}",
                    params={"refresh": "true"},
                    body={"msg": "profiled query text", "n": i})

    def test_profile_shape(self, node):
        self._seed(node)
        status, res = _handle(node, "POST", "/p/_search", body={
            "query": {"match": {"msg": "profiled"}}, "profile": True})
        assert status == 200, res
        shards = res["profile"]["shards"]
        assert len(shards) == len(node.indices.index("p").shards)
        for entry in shards:
            assert entry["id"].startswith("[p][")
            search = entry["searches"][0]
            q = search["query"][0]
            assert q["type"] == "MatchQuery"
            assert q["time_in_nanos"] >= 0
            assert "breakdown" in q
            assert search["collector"][0]["reason"] == "search_top_hits"
            assert entry["fetch"]["time_in_nanos"] >= 0

    def test_profile_false_omits_section(self, node):
        self._seed(node)
        status, res = _handle(node, "POST", "/p/_search", body={
            "query": {"match_all": {}}})
        assert "profile" not in res

    def test_profile_keeps_kernel_path(self, tmp_data_path):
        """`profile: true` no longer exiles the query to the reference
        scorer (PR 6): the kernel path serves it and the profile carries
        a `tpu` section with the variant, plan-cache outcome and the
        batch_wait decomposition instead of per-shard Lucene timings."""
        from elasticsearch_tpu.search import tpu_service as svc_mod

        n = Node(str(tmp_data_path), settings=Settings.of({}))
        try:
            self._seed(n)
            served_before = n.tpu_search.served
            variants_before = dict(svc_mod.KERNEL_VARIANT_COUNTS.counts())
            status, res = _handle(n, "POST", "/p/_search", body={
                "query": {"match": {"msg": "profiled"}}, "profile": True})
            assert status == 200, res
            assert res["hits"]["total"]["value"] == 8
            # the kernel actually served it — no silent fallback
            assert n.tpu_search.served == served_before + 1
            shards = res["profile"]["shards"]
            assert len(shards) == 1 and shards[0]["id"] == "[p][kernel]"
            assert shards[0]["searches"][0]["collector"][0]["name"] == \
                "TpuKernelTopK"
            tpu = shards[0]["tpu"]
            # any serving variant is fine (compressed since the pack
            # format default flipped); what matters is it's reported
            from elasticsearch_tpu.ops import sparse
            assert tpu["variant"] in sparse.KERNEL_VARIANTS
            assert tpu["plan_cache"] in (
                "hit", "miss", "revalidated", "uncacheable")
            split = tpu["stages_ms"]["batch_wait_split"]
            # four parts, and `completion` again in four (ISSUE 38)
            assert set(split) == {
                "queue", "window", "dispatch", "completion",
                "device", "decode", "deliver", "wake"}
            for parts in (("queue", "window", "dispatch", "completion"),
                          ("queue", "window", "dispatch", "device",
                           "decode", "deliver", "wake")):
                assert sum(split[p] for p in parts) == pytest.approx(
                    tpu["stages_ms"]["batch_wait"], rel=0.05, abs=0.05)
            assert res["profile"]["tpu"] == [tpu]
            # taking the path counts against the served variant (keys
            # are "kernel,variant" pairs)
            after = dict(svc_mod.KERNEL_VARIANT_COUNTS.counts())
            assert any(key.split(",")[1] == tpu["variant"]
                       and count > variants_before.get(key, 0)
                       for key, count in after.items()), \
                (tpu["variant"], variants_before, after)
        finally:
            n.close()


class TestSlowLog:
    def test_threshold_tiers(self):
        s = Settings.of({
            "index.search.slowlog.threshold.query.warn": "1s",
            "index.search.slowlog.threshold.query.info": "100ms",
            "index.search.slowlog.threshold.query.debug": "0ms"})
        sl = SlowLog("idx", s)
        assert sl.enabled
        assert sl.maybe_log(2.0, 0) == "warn"
        assert sl.maybe_log(0.5, 0) == "info"
        assert sl.maybe_log(0.01, 0) == "debug"

    def test_disabled_without_thresholds(self):
        sl = SlowLog("idx", Settings.EMPTY)
        assert not sl.enabled
        assert sl.maybe_log(100.0, 0) is None

    def test_slow_query_logged_through_search(self, node, caplog):
        _handle(node, "PUT", "/slow", body={"settings": {
            "index.search.slowlog.threshold.query.warn": "0ms"}})
        for i in range(3):
            _handle(node, "PUT", f"/slow/_doc/{i}",
                    params={"refresh": "true"}, body={"m": "hello"})
        with caplog.at_level(logging.WARNING, logger=SEARCH_SLOWLOG):
            status, res = _handle(node, "POST", "/slow/_search", body={
                "query": {"match": {"m": "hello"}}})
        assert status == 200
        records = [r for r in caplog.records if r.name == SEARCH_SLOWLOG]
        assert records, "no slowlog record emitted"
        msg = records[0].getMessage()
        assert "[slow][0]" in msg
        assert "took_millis[" in msg
        assert "source[" in msg and "hello" in msg

    def test_fast_queries_not_logged(self, node, caplog):
        _handle(node, "PUT", "/quick", body={"settings": {
            "index.search.slowlog.threshold.query.warn": "10s"}})
        _handle(node, "PUT", "/quick/_doc/1", params={"refresh": "true"},
                body={"m": "hi"})
        with caplog.at_level(logging.DEBUG, logger=SEARCH_SLOWLOG):
            _handle(node, "POST", "/quick/_search",
                    body={"query": {"match": {"m": "hi"}}})
        assert not [r for r in caplog.records
                    if r.name == SEARCH_SLOWLOG]


class TestLoggingConfig:
    def test_logger_level_overrides(self):
        configure(Settings.of({
            "logger.elasticsearch_tpu.test_channel": "DEBUG"}))
        assert logging.getLogger(
            "elasticsearch_tpu.test_channel").level == logging.DEBUG
        configure(Settings.of({
            "logger.elasticsearch_tpu.test_channel": "WARNING"}))
        assert logging.getLogger(
            "elasticsearch_tpu.test_channel").level == logging.WARNING

    def test_es_level_names_accepted(self):
        # ES-style names must not crash startup; TRACE maps to DEBUG
        configure(Settings.of({
            "logger.elasticsearch_tpu.trace_channel": "trace"}))
        assert logging.getLogger(
            "elasticsearch_tpu.trace_channel").level == logging.DEBUG
        from elasticsearch_tpu.common.errors import \
            IllegalArgumentException
        with pytest.raises(IllegalArgumentException):
            configure(Settings.of({"logger.x": "LOUD"}))

    def test_debug_tier_actually_emits(self, caplog):
        """A configured debug threshold must produce records even though
        the package root sits at INFO (the channel opens itself up)."""
        configure()
        s = Settings.of({
            "index.search.slowlog.threshold.query.debug": "0ms"})
        sl = SlowLog("dbg", s)
        assert sl.logger.isEnabledFor(logging.DEBUG)
        with caplog.at_level(logging.DEBUG, logger=SEARCH_SLOWLOG):
            assert sl.maybe_log(0.5, 0) == "debug"
        assert any(r.levelno == logging.DEBUG for r in caplog.records
                   if r.name == SEARCH_SLOWLOG)

    def test_root_handler_installed_once(self):
        configure()
        configure()
        root = logging.getLogger("elasticsearch_tpu")
        handlers = [h for h in root.handlers
                    if isinstance(h, logging.StreamHandler)]
        assert len(handlers) == 1
