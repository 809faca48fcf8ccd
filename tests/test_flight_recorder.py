"""Flight recorder suite (ISSUE 18) — ring bounds and seq
monotonicity, journal rotation/retention on disk, incident snapshot
capture with debounce and deterministic flush, context stamping
(trace_id/tenant), the near-free recorder-off path, the REST query
surface (`/_tpu/events`, `/_tpu/incidents`), SampleRing exemplars in
`/_tpu/stats`, and byte-compatibility of the new payloads across the
serving-front wire path."""

from __future__ import annotations

import json
import os
import time

import pytest

from elasticsearch_tpu.common import events as events_mod
from elasticsearch_tpu.common import tenancy, tracing
from elasticsearch_tpu.common.events import FlightRecorder
from elasticsearch_tpu.common.metrics import SampleRing, stats_to_xcontent
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node


def do(node, method, path, body=None, **params):
    raw = json.dumps(body).encode() if body is not None else b""
    return node.handle(method, path,
                       {k: str(v) for k, v in params.items()}, None, raw)


@pytest.fixture(autouse=True)
def _reset_global_recorder():
    """Every test restores the module-level facade it found (the
    module-scoped node fixture owns it for the REST tests; unit tests
    must not leak theirs into later files)."""
    prev = events_mod.get_recorder()
    yield
    events_mod.set_recorder(prev)


# ---------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------

def test_seq_monotonic_and_ring_bounded():
    rec = FlightRecorder(max_events=64)
    seqs = [rec.emit("unit.test", i=i) for i in range(200)]
    assert seqs == list(range(1, 201))  # dense, monotonic, 1-based
    assert rec.ring_len() == 64
    evs = rec.events(limit=0)
    assert len(evs) == 64
    # the ring kept the NEWEST events, still in seq order
    assert [e["seq"] for e in evs] == list(range(137, 201))
    assert rec.last_seq == 200
    assert rec.c_events.counts() == {"unit.test": 200}


def test_event_shape_and_filters():
    rec = FlightRecorder()
    rec.emit("a.one", severity="info", x=1)
    rec.emit("a.two", severity="error", device=3)
    rec.emit("a.one", severity="warning", trace_id="t-123",
             tenant="acme", x=2)
    e = rec.events(etype="a.two")[0]
    assert e["type"] == "a.two" and e["severity"] == "error"
    assert e["attrs"] == {"device": 3}
    assert "trace_id" not in e and "tenant" not in e
    assert [e["seq"] for e in rec.events(etype="a.one")] == [1, 3]
    assert [e["seq"] for e in rec.events(severity="error")] == [2]
    assert [e["seq"] for e in rec.events(since_seq=2)] == [3]
    assert [e["seq"] for e in rec.events(trace_id="t-123")] == [3]
    assert [e["seq"] for e in rec.events(tenant="acme")] == [3]
    assert [e["seq"] for e in rec.events(limit=2)] == [2, 3]


def test_attrs_are_json_sanitized():
    rec = FlightRecorder()
    rec.emit("unit.jsonable", devices=(3, 1), who={2, 0},
             err=ValueError("boom"), nested={"t": (1, 2)})
    attrs = rec.events()[0]["attrs"]
    assert attrs["devices"] == [3, 1]
    assert attrs["who"] == [0, 2]  # sets render sorted
    assert attrs["err"] == "boom"
    assert attrs["nested"] == {"t": [1, 2]}
    json.dumps(attrs)  # round-trips


def test_context_stamping_trace_and_tenant():
    rec = FlightRecorder()
    events_mod.set_recorder(rec)
    tracer = tracing.Tracer(sample_rate=1.0)
    span = tracer.start_span("req", root=True)
    prev = tenancy.bind_tenant("acme")
    try:
        with tracing.use_span(span):
            events_mod.emit("unit.ctx")
    finally:
        tenancy.bind_tenant(prev)
        span.end()
    e = rec.events()[0]
    assert e["trace_id"] == span.trace_id
    assert e["tenant"] == "acme"
    # the default tenant is never stamped
    events_mod.emit("unit.ctx2")
    assert "tenant" not in rec.events(etype="unit.ctx2")[0]


# ---------------------------------------------------------------------
# journal rotation / retention
# ---------------------------------------------------------------------

def test_journal_rotation_and_retention(tmp_path):
    flight = str(tmp_path / "flight")
    rec = FlightRecorder(flight, max_file_bytes=4096, disk_retention=2)
    blob = "x" * 400
    for i in range(60):
        rec.emit("unit.rotate", i=i, pad=blob)
    rec.close()
    names = sorted(n for n in os.listdir(flight)
                   if n.startswith("events-") and n.endswith(".jsonl"))
    assert 1 <= len(names) <= 2, names  # retention pruned old files
    assert names[-1] != "events-000000.jsonl"  # rotation happened
    # the newest journal file holds valid JSONL with monotonic seqs
    lines = [json.loads(l) for l in
             open(os.path.join(flight, names[-1]), encoding="utf-8")]
    seqs = [e["seq"] for e in lines]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the in-memory ring is unaffected by disk rotation
    assert rec.last_seq == 60


def test_journal_resumes_numbering_across_restart(tmp_path):
    flight = str(tmp_path / "flight")
    rec = FlightRecorder(flight)
    rec.emit("unit.first")
    rec.close()
    rec2 = FlightRecorder(flight)
    rec2.emit("unit.second")
    rec2.close()
    text = open(os.path.join(flight, "events-000000.jsonl"),
                encoding="utf-8").read()
    assert '"unit.first"' in text and '"unit.second"' in text


# ---------------------------------------------------------------------
# incident snapshots
# ---------------------------------------------------------------------

def test_incident_snapshot_capture_and_fetch(tmp_path):
    rec = FlightRecorder(str(tmp_path / "flight"), snapshot_events=8,
                         incident_settle_s=0.0)
    rec.add_snapshot_source("greeting", lambda: {"hello": "world"})
    rec.add_snapshot_source("broken", lambda: 1 / 0)
    for i in range(20):
        rec.emit("unit.pre", i=i)
    inc_id = rec.incident("wedge", label="launch-3")
    assert inc_id is not None
    listed = rec.list_incidents()
    assert [i["id"] for i in listed] == [inc_id]
    snap = rec.get_incident(inc_id)
    assert snap["trigger"] == "wedge"
    assert snap["attrs"] == {"label": "launch-3"}
    # the bounded tail of the ring, incident.open event included
    assert len(snap["events"]) == 8
    assert snap["events"][-1]["type"] == "incident.open"
    assert snap["sources"]["greeting"] == {"hello": "world"}
    assert "error" in snap["sources"]["broken"]  # partial > none
    assert rec.c_incidents.counts()["wedge"] == 1
    # path traversal never resolves
    assert rec.get_incident("../../etc/passwd") is None
    assert rec.get_incident("inc-999999-none") is None


def test_incident_settle_window_captures_the_cascade():
    rec = FlightRecorder(incident_settle_s=0.2, incident_debounce_s=0.0)
    rec.incident("wedge", label="l")
    # the cascade lands AFTER the trigger but BEFORE the snapshot
    rec.emit("device.quarantine", device=3)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not rec.list_incidents():
        time.sleep(0.02)
    (summary,) = rec.list_incidents()
    snap = rec.get_incident(summary["id"])
    types = [e["type"] for e in snap["events"]]
    assert types.index("incident.open") < types.index("device.quarantine")


def test_incident_debounce_and_flush():
    rec = FlightRecorder(incident_settle_s=600.0, incident_debounce_s=60.0)
    first = rec.incident("quarantine", device=1)
    assert first is not None
    assert rec.incident("quarantine", device=2) is None  # debounced
    assert rec.incident("pack_shed") is not None  # other triggers free
    assert rec.list_incidents() == []  # nothing captured yet (settling)
    rec.flush_incidents()  # deterministic capture, timers become no-ops
    assert {i["trigger"] for i in rec.list_incidents()} == \
        {"quarantine", "pack_shed"}


def test_incident_retention_cap(tmp_path):
    rec = FlightRecorder(str(tmp_path / "flight"), incident_retention=3,
                         incident_settle_s=0.0, incident_debounce_s=0.0)
    ids = [rec.incident("wedge", n=i) for i in range(6)]
    listed = rec.list_incidents()
    assert len(listed) == 3
    assert [i["id"] for i in listed] == list(reversed(ids[-3:]))
    assert rec.get_incident(ids[0]) is None  # pruned


# ---------------------------------------------------------------------
# off-is-near-free
# ---------------------------------------------------------------------

def test_recorder_off_emit_is_near_free():
    assert events_mod.get_recorder() is None
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        events_mod.emit("unit.off", device=3, reason="x")
    dt = time.perf_counter() - t0
    # one global read + None check; generous CI bound (< 5µs/call —
    # state-transition sites fire a handful of times per incident, so
    # even this bound is orders of magnitude below 1% of a request)
    assert dt < n * 5e-6, f"recorder-off emit too slow: {dt:.3f}s/{n}"
    assert events_mod.incident("wedge") is None


def test_emit_never_raises(monkeypatch):
    rec = FlightRecorder()
    monkeypatch.setattr(rec, "_ring", None)  # force an internal failure
    assert rec.emit("unit.broken") == 0  # swallowed, counted
    assert rec.c_dropped.count == 1


# ---------------------------------------------------------------------
# REST surface + exemplars on a live node
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = Node(str(tmp_path_factory.mktemp("data")),
             settings=Settings.of({"search.tracing.sample_rate": 1.0}))
    status, body = do(n, "PUT", "/books", body={
        "settings": {"index": {"number_of_shards": 1}},
        "mappings": {"properties": {"title": {"type": "text"}}}})
    assert status == 200, body
    for i in range(8):
        do(n, "PUT", f"/books/_doc/{i}", body={"title": f"beta doc {i}"})
    do(n, "POST", "/books/_refresh")
    status, resp = do(n, "POST", "/books/_search",
                      body={"query": {"match": {"title": "beta"}}})
    assert status == 200, resp
    yield n
    n.close()


def test_node_installs_recorder_and_events_endpoint(node):
    rec = node.flight_recorder
    assert rec is not None and events_mod.get_recorder() is rec
    # journal landed under <data_path>/flight/
    assert os.path.isdir(os.path.join(node.indices.data_path, "flight"))
    status, out = do(node, "GET", "/_tpu/events")
    assert status == 200 and out["enabled"]
    types = [e["type"] for e in out["events"]]
    assert "node.start" in types  # construction journaled
    assert "pack.build" in types  # the warm search built residency
    assert out["last_seq"] >= len(out["events"])
    seqs = [e["seq"] for e in out["events"]]
    assert seqs == sorted(seqs)
    # filters narrow
    status, one = do(node, "GET", "/_tpu/events", type="node.start")
    assert [e["type"] for e in one["events"]] == ["node.start"]
    status, none = do(node, "GET", "/_tpu/events",
                      since_seq=out["last_seq"])
    assert none["events"] == []
    status, lim = do(node, "GET", "/_tpu/events", limit=2)
    assert len(lim["events"]) == 2


def test_incident_endpoints_and_404(node):
    rec = node.flight_recorder
    inc_id = rec.incident("batcher_death", reason="drill")
    rec.flush_incidents()
    status, out = do(node, "GET", "/_tpu/incidents")
    assert status == 200 and out["enabled"]
    assert any(i["id"] == inc_id for i in out["incidents"])
    status, snap = do(node, "GET", f"/_tpu/incidents/{inc_id}")
    assert status == 200
    assert snap["trigger"] == "batcher_death"
    assert any(e["type"] == "incident.open" for e in snap["events"])
    # node-wired snapshot sources rode along
    assert "tpu_stats" in snap["sources"]
    assert "degraded_info" in snap["sources"]
    assert "profile_stacks" in snap["sources"]
    status, body = do(node, "GET", "/_tpu/incidents/inc-000099-none")
    assert status == 404, body


def test_stats_exemplar_trace_id(node):
    # traced searches ran in the fixture (sample_rate=1.0): the stage
    # rings' slowest recent sample carries its trace for drill-down
    do(node, "POST", "/books/_search",
       body={"query": {"match": {"title": "beta"}}})
    status, out = do(node, "GET", "/_tpu/stats")
    assert status == 200
    stages = out["stages"]
    exemplars = [v["exemplar_trace_id"] for v in stages.values()
                 if isinstance(v, dict) and "exemplar_trace_id" in v]
    assert exemplars, f"no stage exemplar in {list(stages)}"
    # the exemplar points at a real retained trace
    status, traces = do(node, "GET", "/_tpu/traces",
                        trace_id=exemplars[0])
    assert status == 200 and traces["total"] >= 1


def test_traces_tenant_filter(node):
    status, resp = do(node, "POST", "/books/_search",
                      body={"query": {"match": {"title": "beta"}}},
                      tenant_id="acme")
    assert status == 200, resp
    status, out = do(node, "GET", "/_tpu/traces", tenant="acme")
    assert status == 200 and out["total"] >= 1
    assert all(s["attributes"]["tenant"] == "acme" for s in out["spans"]
               if s["parent_id"] is None)
    # default-tenant requests are unstamped → excluded by the filter
    status, other = do(node, "GET", "/_tpu/traces", tenant="nosuch")
    assert other["total"] == 0


def test_tenant_events_stamped_through_rest(node):
    do(node, "POST", "/books/_search",
       body={"query": {"match": {"title": "beta"}}}, tenant_id="acme")
    rec = node.flight_recorder
    rec.emit("unit.noop")  # plain emit on this (default-tenant) thread
    # tenant-scoped event querying works end to end
    status, out = do(node, "GET", "/_tpu/events", tenant="acme")
    assert status == 200
    assert all(e.get("tenant") == "acme" for e in out["events"])


def test_recorder_disabled_by_setting(tmp_path):
    # the facade is process-global: clear any other node's recorder so
    # the endpoints answer for THIS (disabled) node
    events_mod.set_recorder(None)
    n = Node(str(tmp_path / "data"),
             settings=Settings.of({"search.flight_recorder.enabled":
                                   False}))
    try:
        assert n.flight_recorder is None
        status, out = do(n, "GET", "/_tpu/events")
        assert status == 200 and out == {"enabled": False, "events": []}
        status, out = do(n, "GET", "/_tpu/incidents")
        assert status == 200 and not out["enabled"]
        status, _ = do(n, "GET", "/_tpu/incidents/inc-000001-wedge")
        assert status == 404
    finally:
        n.close()


def test_node_close_uninstalls_recorder(tmp_path):
    n = Node(str(tmp_path / "data"), settings=Settings.of({}))
    rec = n.flight_recorder
    assert events_mod.get_recorder() is rec
    n.close()
    assert events_mod.get_recorder() is None
    # post-close emits are silent no-ops, not crashes
    events_mod.emit("unit.after_close")


# ---------------------------------------------------------------------
# SampleRing exemplars (unit)
# ---------------------------------------------------------------------

def test_sample_ring_exemplar_tracks_slowest():
    ring = SampleRing(size=8)
    ring.add(0.5, exemplar="t-slow")
    ring.add(0.1, exemplar="t-fast")
    assert ring.exemplar_trace_id == "t-slow"
    ring.add(0.9, exemplar="t-slower")  # new max replaces
    assert ring.exemplar_trace_id == "t-slower"
    out = stats_to_xcontent({"lat": ring})
    assert out["lat"]["exemplar_trace_id"] == "t-slower"
    assert {"p50", "p95", "p99"} <= set(out["lat"])


def test_sample_ring_exemplar_ages_out():
    ring = SampleRing(size=4)
    ring.add(9.0, exemplar="t-old")
    for _ in range(5):  # a full ring of newer, faster, untraced samples
        ring.add(0.1)
    assert ring.exemplar_trace_id is None  # aged past the window
    out = stats_to_xcontent({"lat": ring})
    assert "exemplar_trace_id" not in out["lat"]  # shape unchanged
    ring.add(0.2, exemplar="t-new")  # any traced sample re-seeds
    assert ring.exemplar_trace_id == "t-new"


def test_sample_ring_without_exemplars_unchanged():
    ring = SampleRing(size=8)
    for v in range(10):
        ring.add(float(v))
    assert ring.exemplar_trace_id is None
    out = stats_to_xcontent({"lat": ring})
    assert set(out["lat"]) == {"p50", "p95", "p99"}


# ---------------------------------------------------------------------
# front wire path byte-compatibility
# ---------------------------------------------------------------------

def _roundtrip(payload):
    from elasticsearch_tpu.search.serializer import (dumps_response,
                                                     splice_wire)
    from elasticsearch_tpu.serving.front import FrontSupervisor
    wire = FrontSupervisor._encode(200, json.loads(json.dumps(payload)))
    assert wire["ctype"] == "json"
    return splice_wire(wire["parts"], wire["columns"]), \
        dumps_response(payload)


def test_front_wire_events_payload_byte_compatible():
    payload = {"enabled": True, "last_seq": 17, "dropped": 0, "total": 2,
               "events": [
                   {"seq": 16, "ts": 1.5, "type": "watchdog.wedge",
                    "severity": "error", "trace_id": "t1",
                    "attrs": {"devices": [3], "trace_ids": ["t1"]}},
                   {"seq": 17, "ts": 1.6, "type": "device.quarantine",
                    "severity": "error", "attrs": {"device": 3}}]}
    spliced, direct = _roundtrip(payload)
    assert spliced == direct


def test_front_wire_incident_and_exemplar_payloads_byte_compatible():
    incident = {"id": "inc-000001-wedge", "trigger": "wedge", "ts": 2.0,
                "events": [{"seq": 1, "ts": 1.0, "type": "incident.open",
                            "severity": "error"}],
                "sources": {"tpu_stats": {"stages": {
                    "kernel": {"p50": 1.0, "p95": 2.0, "p99": 3.0,
                               "exemplar_trace_id": "t-abc"}}},
                    "degraded_info": None}}
    spliced, direct = _roundtrip(incident)
    assert spliced == direct
    stats = {"enabled": True, "stages": {
        "assemble": {"p50": 0.1, "p95": 0.2, "p99": 0.3,
                     "exemplar_trace_id": "t-xyz"}}}
    spliced, direct = _roundtrip(stats)
    assert spliced == direct
