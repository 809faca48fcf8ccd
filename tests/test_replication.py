"""Replication + peer recovery: primary→replica fan-out on every write,
file+translog peer recovery for new replicas, replica promotion on
primary loss with zero acked-write loss.

Reference analogs (SURVEY.md §2.1#32/#34, §4.3): ReplicationOperation,
RecoverySourceHandler/PeerRecoveryTargetService, and the
ClusterDisruptionIT#testAckedIndexing shape (every acked write survives
the failover)."""

from __future__ import annotations

import json
import socket
import time

import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node

NODE_NAMES = ["rep-0", "rep-1", "rep-2"]


def _free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _handle(node, method, path, params=None, body=None):
    if isinstance(body, str):
        return node.handle(method, path, params, None, body.encode("utf-8"))
    raw = json.dumps(body).encode("utf-8") if body is not None else b""
    return node.handle(method, path, params, None, raw)


def _make_cluster(tmp_path, names=NODE_NAMES):
    ports = _free_ports(len(names))
    seeds = [("127.0.0.1", p) for p in ports]
    nodes = []
    for i, name in enumerate(names):
        data = tmp_path / f"data-{name}"
        data.mkdir(parents=True, exist_ok=True)
        node = Node(str(data), node_name=name,
                    settings=Settings.of(
                        {"search.tpu_serving.enabled": "false"}))
        node.start_cluster(transport_port=ports[i], seed_hosts=seeds,
                           initial_master_nodes=list(names))
        nodes.append(node)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if all(n.cluster.health()["number_of_nodes"] == len(names)
               for n in nodes):
            return nodes
        time.sleep(0.2)
    raise AssertionError("cluster did not form")


def _wait_green(node, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        h = node.cluster.health()
        if h["status"] == "green":
            return h
        time.sleep(0.1)
    raise AssertionError(f"not green: {node.cluster.health()}")


@pytest.fixture
def cluster(tmp_path):
    nodes = _make_cluster(tmp_path)
    yield nodes
    for n in nodes:
        try:
            n.close()
        except Exception:
            pass


def _find_copy_holders(nodes, index, shard):
    state = nodes[0].cluster.applied_state()
    primary = state.primary(index, shard)
    replicas = [c for c in state.shard_copies(index, shard)
                if not c.primary and c.node_id]
    by_id = {n.node_id: n for n in nodes}
    return (by_id[primary.node_id],
            [by_id[c.node_id] for c in replicas if c.node_id in by_id])


def test_translog_retention_lock_survives_flush(tmp_path):
    """A recovery source's retention lock must keep translog ops
    fetchable across a concurrent flush (which otherwise trims them) —
    the phase-2 replay depends on it."""
    from elasticsearch_tpu.index.engine import EngineConfig, InternalEngine
    from elasticsearch_tpu.mapping import MapperService
    from elasticsearch_tpu.common.settings import Settings as S

    eng = InternalEngine(EngineConfig(
        path=str(tmp_path / "shard"), mapper=MapperService(S.EMPTY, None),
        primary_term=1))
    try:
        for i in range(5):
            eng.index(f"d{i}", {"n": i})
        release = eng.translog.acquire_retention_lock()
        eng.flush()   # would trim all replayed generations without a lock
        for i in range(5, 8):
            eng.index(f"d{i}", {"n": i})
        ops = list(eng.translog.snapshot(from_seq_no=0))
        assert {o.seq_no for o in ops} == set(range(8)), \
            sorted(o.seq_no for o in ops)
        release()
        eng.flush()
        ops = list(eng.translog.snapshot(from_seq_no=0))
        # after release + flush the old generations may go
        assert all(o.seq_no >= 5 or o.seq_no in () for o in ops) or ops == []
    finally:
        eng.close()


def test_write_fans_out_to_replica(cluster):
    status, body = _handle(cluster[0], "PUT", "/rep", body={
        "settings": {"number_of_shards": 1, "number_of_replicas": 1}})
    assert status == 200, body
    _wait_green(cluster[0])

    status, body = _handle(cluster[1], "PUT", "/rep/_doc/x",
                           body={"v": 1})
    assert status == 201, body

    primary_node, replica_nodes = _find_copy_holders(cluster, "rep", 0)
    assert len(replica_nodes) == 1
    # the acked write is physically present on BOTH copies, unrefleshed
    for holder in [primary_node] + replica_nodes:
        shard = holder.indices.index("rep").shards[0]
        got = shard.get("x")
        assert got is not None and got["_source"] == {"v": 1}, holder.node_name
    # and deletes fan out too
    status, _ = _handle(cluster[2], "DELETE", "/rep/_doc/x")
    assert status == 200
    for holder in [primary_node] + replica_nodes:
        assert holder.indices.index("rep").shards[0].get("x") is None


def test_peer_recovery_ships_files_and_translog(cluster):
    # replicas=0 first: build real segment files on the primary only
    status, body = _handle(cluster[0], "PUT", "/pr", body={
        "settings": {"number_of_shards": 1, "number_of_replicas": 1}})
    assert status == 200, body
    _wait_green(cluster[0])
    primary_node, replica_nodes = _find_copy_holders(cluster, "pr", 0)
    assert len(replica_nodes) == 1
    replica_node = replica_nodes[0]

    # write through flushes (files) and keep a translog tail (no flush)
    for i in range(20):
        status, _ = _handle(cluster[0], "PUT", f"/pr/_doc/d{i}",
                            body={"n": i})
        assert status == 201
    _handle(cluster[0], "POST", "/pr/_flush")
    for i in range(20, 30):
        status, _ = _handle(cluster[0], "PUT", f"/pr/_doc/d{i}",
                            body={"n": i})
        assert status == 201

    # kill the replica holder → copy fails over to the third node,
    # which must peer-recover all 30 docs (files + translog tail)
    state = cluster[0].cluster.applied_state()
    third = next(n for n in cluster
                 if n.node_id not in (primary_node.node_id,
                                      replica_node.node_id))
    replica_node.close()
    live = [n for n in cluster if n is not replica_node]
    # wait until the failure detector removed the dead node AND the
    # copy finished recovering on the third node
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        state = live[0].cluster.applied_state()
        copy = next((c for c in state.shard_copies("pr", 0)
                     if c.node_id == third.node_id
                     and c.state == "STARTED"), None)
        if copy is not None and len(state.nodes) == 2:
            break
        time.sleep(0.1)
    state = live[0].cluster.applied_state()
    holder_ids = {c.node_id for c in state.shard_copies("pr", 0)}
    assert third.node_id in holder_ids, state.shard_copies("pr", 0)
    shard = third.indices.index("pr").shards[0]
    for i in range(30):
        got = shard.get(f"d{i}")
        assert got is not None and got["_source"] == {"n": i}, f"d{i}"


def test_kill_primary_mid_writes_no_acked_loss(cluster):
    status, body = _handle(cluster[0], "PUT", "/ha", body={
        "settings": {"number_of_shards": 1, "number_of_replicas": 1}})
    assert status == 200, body
    _wait_green(cluster[0])
    primary_node, replica_nodes = _find_copy_holders(cluster, "ha", 0)
    coordinator = next(n for n in cluster
                       if n.node_id not in (primary_node.node_id,
                                            replica_nodes[0].node_id))

    acked = []
    killed = False
    for i in range(60):
        if i == 25 and not killed:
            primary_node.close()   # hard kill mid-stream
            killed = True
        try:
            status, body = _handle(coordinator, "PUT", f"/ha/_doc/k{i}",
                                   body={"i": i})
            if status in (200, 201):
                acked.append(f"k{i}")
        except Exception:
            pass  # un-acked writes may fail during failover — allowed
    assert killed
    assert len(acked) > 30, "failover never completed; writes kept failing"

    # the replica must have been promoted
    live = [n for n in cluster if n is not primary_node]
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        state = live[0].cluster.applied_state()
        p = state.primary("ha", 0)
        if p is not None and p.state == "STARTED" \
                and p.node_id != primary_node.node_id:
            break
        time.sleep(0.1)
    state = live[0].cluster.applied_state()
    p = state.primary("ha", 0)
    assert p is not None and p.node_id != primary_node.node_id

    # zero acked-write loss: every 2xx write is readable after failover
    for doc_id in acked:
        status, body = _handle(coordinator, "GET", f"/ha/_doc/{doc_id}")
        assert status == 200, f"acked write {doc_id} lost: {body}"


def test_red_primary_reassigned_when_data_node_rejoins(cluster, tmp_path):
    """The store-based allocator: a red primary (sole copy's node died)
    heals when the node holding the in-sync data rejoins — assigned back
    by allocation id, never as a fresh empty shard."""
    status, body = _handle(cluster[0], "PUT", "/comeback", body={
        "settings": {"number_of_shards": 1, "number_of_replicas": 0}})
    assert status == 200, body
    _wait_green(cluster[0])
    for i in range(5):
        _handle(cluster[0], "PUT", f"/comeback/_doc/c{i}", body={"i": i})
    state = cluster[0].cluster.applied_state()
    holder = next(n for n in cluster
                  if n.node_id == state.primary("comeback", 0).node_id)
    holder_data = holder.indices.data_path
    holder_name = holder.node_name
    holder_port = holder.cluster.transport.port
    seeds = [("127.0.0.1", n.cluster.transport.port) for n in cluster]
    holder.close()

    live = [n for n in cluster if n is not holder]
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        h = live[0].cluster.health()
        if h["status"] == "red" and h["number_of_nodes"] == 2:
            break
        time.sleep(0.1)
    assert live[0].cluster.health()["status"] == "red"

    # restart a node on the same data path (same persisted node id)
    reborn = Node(holder_data, node_name=holder_name,
                  settings=Settings.of(
                      {"search.tpu_serving.enabled": "false"}))
    try:
        reborn.start_cluster(transport_port=holder_port, seed_hosts=seeds,
                             initial_master_nodes=NODE_NAMES)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            state = reborn.cluster.applied_state()
            p = state.primary("comeback", 0)
            if (p is not None and p.state == "STARTED"
                    and reborn.cluster.health()["status"] == "green"):
                break
            time.sleep(0.2)
        # the data is back — not a fresh empty primary
        state = reborn.cluster.applied_state()
        p = state.primary("comeback", 0)
        assert p is not None and p.state == "STARTED", p
        assert p.node_id == reborn.node_id
        for i in range(5):
            status, body = _handle(live[0], "GET", f"/comeback/_doc/c{i}")
            assert status == 200, (i, body)
    finally:
        reborn.close()


def test_lost_primary_without_replica_goes_red_not_empty(cluster):
    status, body = _handle(cluster[0], "PUT", "/frag", body={
        "settings": {"number_of_shards": 1, "number_of_replicas": 0}})
    assert status == 200, body
    _wait_green(cluster[0])
    state = cluster[0].cluster.applied_state()
    holder = next(n for n in cluster
                  if n.node_id == state.primary("frag", 0).node_id)
    _handle(cluster[0], "PUT", "/frag/_doc/1", body={"a": 1})
    holder_id = holder.node_id
    holder.close()
    live = [n for n in cluster if n is not holder]
    # the shard must go red (unassigned), never a fresh empty primary.
    # The master removes the node in one state update and fails the
    # node's copies in the next: wait for both (a liveness bound: fault
    # detection plus, where the holder was the master, an election)
    def primary():
        return live[0].cluster.applied_state().primary("frag", 0)

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        h = live[0].cluster.health()
        if (h["status"] == "red" and h["number_of_nodes"] == 2
                and primary().node_id != holder_id):
            break
        time.sleep(0.1)
    h = live[0].cluster.health()
    assert h["status"] == "red" and h["number_of_nodes"] == 2, h
    p = primary()
    assert p.node_id is None or p.state != "STARTED", p


def test_replica_reads_spread_and_fail_over(cluster):
    """ARS-lite (SURVEY.md §2.1#19/P2): with 1 shard × 2 replicas every
    copy is STARTED on some node — reads must spread over copies (not
    pin the primary) and keep succeeding when the chosen replica's node
    dies."""
    status, body = _handle(cluster[0], "PUT", "/ars", body={
        "settings": {"number_of_shards": 1, "number_of_replicas": 2},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    assert status == 200, body
    _wait_green(cluster[0])
    for i in range(12):
        _handle(cluster[0], "PUT", f"/ars/_doc/{i}",
                body={"body": f"alpha doc {i}"})
    _handle(cluster[0], "POST", "/ars/_refresh")

    # routing spreads across copies (round-robin over unmeasured nodes,
    # then EWMA-ranked); collect the chosen owner over repeated routes
    chosen = set()
    for _ in range(9):
        by_node, _addr, unassigned, _c = \
            cluster[0].cluster._route_shards(["ars"])
        assert not unassigned
        chosen.update(by_node.keys())
        s, resp = _handle(cluster[0], "POST", "/ars/_search",
                          body={"query": {"match": {"body": "alpha"}},
                                "size": 20})
        assert s == 200 and resp["hits"]["total"]["value"] == 12, resp
    assert len(chosen) >= 2, f"reads pinned to {chosen}"

    # kill a non-coordinating holder; reads keep working off live copies
    state = cluster[0].cluster.applied_state()
    victim_id = next(nid for nid in chosen
                     if nid != cluster[0].node_id)
    victim = next(n for n in cluster if n.node_id == victim_id)
    victim.close()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if cluster[0].cluster.health()["number_of_nodes"] == 2:
            break
        time.sleep(0.1)
    # EWMA ranks the dead node out after one failure; route + search
    ok = 0
    for _ in range(6):
        s, resp = _handle(cluster[0], "POST", "/ars/_search",
                          body={"query": {"match": {"body": "alpha"}},
                                "size": 20})
        if s == 200 and resp["hits"]["total"]["value"] == 12:
            ok += 1
        by_node, _addr, _u, _c = cluster[0].cluster._route_shards(["ars"])
        assert victim_id not in by_node
    assert ok >= 5, f"only {ok}/6 searches succeeded after failover"
