"""Device fault domains (ISSUE 14): per-device health registry unit
behavior (wedge scoring → probe confirmation → quarantine → flap-damped
reintroduction), partial-mesh factorization (odd survivor counts like
1×7), the shed-pack typed-503 contract, and the structured degraded
reason clients type against."""

import json
import time
from types import SimpleNamespace

import pytest

from elasticsearch_tpu.common.errors import PackShedException
from elasticsearch_tpu.parallel.health import (DeviceHealthRegistry,
                                               PROBE_FAULT_HOOKS)
from elasticsearch_tpu.parallel.mesh import (DATA_AXIS, SHARD_AXIS,
                                             factorize_2d, make_mesh)
from elasticsearch_tpu.rest.controller import rejection_headers

pytestmark = pytest.mark.device_loss


def _wait(predicate, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def _registry(n=4, **kw):
    # fake devices: the forced-probe hooks below keep _real_probe (which
    # needs a live jax device) out of the picture
    return DeviceHealthRegistry([SimpleNamespace(id=i) for i in range(n)],
                                **kw)


@pytest.fixture
def probe_hooks():
    """Install/remove PROBE_FAULT_HOOKS entries with guaranteed cleanup."""
    added = []

    def install(hook):
        PROBE_FAULT_HOOKS.append(hook)
        added.append(hook)
        return hook

    yield install
    for hook in added:
        PROBE_FAULT_HOOKS.remove(hook)


# ---------------------------------------------------------------------
# wedge scoring → suspicion → probe confirmation
# ---------------------------------------------------------------------

class TestWedgeScoring:
    def test_single_wedge_scores_but_does_not_quarantine(self):
        reg = _registry(suspect_after=2)
        try:
            # one wedged launch implicates the whole mesh — suspicion,
            # not a verdict: nobody crosses suspect_after=2
            assert reg.record_wedge([0, 1, 2, 3], label="launch") == []
            st = reg.stats()
            assert st["active"] == 4 and st["quarantined"] == []
            assert st["wedge_scores"] == {"0": 1, "1": 1, "2": 1, "3": 1}
            assert st["probes"] == 0  # below threshold: no probe fired
        finally:
            reg.close()

    def test_unknown_device_ids_are_ignored(self):
        reg = _registry(n=2, suspect_after=1)
        try:
            assert reg.record_wedge([99], label="launch") == []
            assert reg.stats()["active"] == 2
        finally:
            reg.close()

    def test_probe_failure_quarantines_and_fires_callback(self, probe_hooks):
        events = []
        reg = _registry(suspect_after=1, on_quarantine=events.append)
        probe_hooks(lambda i: True if i == 3 else None)  # force-fail id 3
        try:
            assert reg.record_wedge([3], label="launch") == [3]
            assert events == [3]
            assert reg.active_ids() == [0, 1, 2]
            assert reg.quarantined_ids() == [3]
            assert reg.state_codes()[3] == 2  # quarantined gauge code
            st = reg.stats()
            assert st["quarantines"] == 1 and st["probe_failures"] == 1
            # an already-quarantined device doesn't re-quarantine
            assert reg.record_wedge([3], label="launch") == []
            assert reg.stats()["quarantines"] == 1
        finally:
            reg.close()

    def test_passing_probe_clears_suspect_back_to_healthy(self, probe_hooks):
        reg = _registry(suspect_after=1)
        probe_hooks(lambda i: False)  # force every probe to PASS
        try:
            # the probe acquits the suspect: healthy, score reset
            assert reg.record_wedge([2], label="finish") == []
            st = reg.stats()
            assert st["states"]["2"] == "healthy"
            assert st["wedge_scores"] == {}
            assert st["probes"] == 1 and st["probe_failures"] == 0
        finally:
            reg.close()

    def test_real_probe_answers_on_a_live_cpu_device(self):
        import jax
        reg = DeviceHealthRegistry(jax.devices(), suspect_after=1)
        try:
            assert reg.probe(int(jax.devices()[0].id)) is True
            assert reg.probe(9_999) is False  # unknown device = fail
        finally:
            reg.close()


# ---------------------------------------------------------------------
# reintroduction: hold-down flap damping, consecutive-healthy streaks
# ---------------------------------------------------------------------

class TestReintroduction:
    def test_hold_down_blocks_readmission(self, probe_hooks):
        verdicts = {0: True}  # confirmation probe fails once
        probe_hooks(lambda i: verdicts.pop(0, False))
        reg = _registry(n=2, suspect_after=1, reprobe_interval_s=0.02,
                        hold_down_s=60.0, reintroduce_after=1)
        try:
            assert reg.record_wedge([0]) == [0]
            time.sleep(0.3)  # many reprobe ticks inside the hold-down
            # probes would pass now, but flap damping holds the device out
            assert reg.quarantined_ids() == [0]
            assert reg.stats()["reintroductions"] == 0
        finally:
            reg.close()

    def test_reintroduced_after_consecutive_healthy_probes(self, probe_hooks):
        # script: confirm-fail → reprobe-fail (streak reset) → pass ×2
        script = [True, True, False, False]
        probe_hooks(lambda i: script.pop(0) if script else False)
        events = []
        reg = _registry(n=2, suspect_after=1, reprobe_interval_s=0.02,
                        hold_down_s=0.0, reintroduce_after=2,
                        on_reintroduce=events.append)
        try:
            assert reg.record_wedge([0]) == [0]
            assert _wait(lambda: events == [0], timeout=5.0)
            assert reg.active_ids() == [0, 1]
            st = reg.stats()
            assert st["reintroductions"] == 1
            assert st["states"]["0"] == "healthy"
            # the failed reprobe reset the streak: reintroduction took
            # (at least) confirm + fail + 2 consecutive passes
            assert st["probes"] >= 4
        finally:
            reg.close()


# ---------------------------------------------------------------------
# partial-mesh factorization + build (satellite: factorize_2d audit)
# ---------------------------------------------------------------------

class TestPartialMeshFactorization:
    @pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7, 8, 12])
    def test_grid_covers_n_with_power_of_two_data_axis(self, n):
        d, s = factorize_2d(n)
        assert d * s == n
        assert d >= 1 and (d & (d - 1)) == 0  # data axis: power of two
        assert d <= s                          # shards axis favored

    def test_known_grids(self):
        # the N-1 case the remesh hits on an 8-chip host: 7 → 1×7
        assert factorize_2d(7) == (1, 7)
        assert factorize_2d(8) == (2, 4)
        assert factorize_2d(12) == (2, 6)
        assert factorize_2d(1) == (1, 1)

    def test_make_mesh_over_seven_device_subset(self):
        import jax
        survivors = jax.devices()[:7]
        mesh = make_mesh(devices=survivors)
        assert mesh.axis_names == (DATA_AXIS, SHARD_AXIS)
        assert mesh.devices.shape == (1, 7)
        assert [d.id for d in mesh.devices.flat] == \
            [d.id for d in survivors]

    def test_make_mesh_rejects_mismatched_shape(self):
        import jax
        with pytest.raises(ValueError, match="mesh shape"):
            make_mesh(devices=jax.devices()[:7], shape=(2, 4))


# ---------------------------------------------------------------------
# shed-pack contract: typed 503 + Retry-After, structured degraded reason
# ---------------------------------------------------------------------

def _do(node, method, path, body=None, **params):
    raw = json.dumps(body).encode() if body is not None else b""
    return node.handle(method, path,
                       {k: str(v) for k, v in params.items()}, None, raw)


@pytest.fixture()
def node(tmp_path):
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node
    n = Node(str(tmp_path / "data"), settings=Settings.of({}))
    status, _ = _do(n, "PUT", "/lib", body={
        "settings": {"index": {"number_of_shards": 2}},
        "mappings": {"properties": {"title": {"type": "text"}}}})
    assert status == 200
    for i in range(6):
        _do(n, "PUT", f"/lib/_doc/{i}", body={"title": f"gamma doc {i}"})
    _do(n, "POST", "/lib/_refresh")
    yield n
    n.close()


def test_a_remesh_under_a_pack_build_builds_again_on_the_new_mesh(
        node, monkeypatch):
    """The supervisor's remesh takes no build lock. A build it overtakes
    ends on the mesh it started on: that pack is released, not swapped
    in, and the key is built again on the mesh that serves."""
    import jax

    from elasticsearch_tpu.common.breaker import CircuitBreaker
    from elasticsearch_tpu.parallel import distributed as dist
    from elasticsearch_tpu.search.tpu_service import IndexPackCache

    breaker = CircuitBreaker("hbm", 1 << 30)
    cache = IndexPackCache(breaker=breaker)
    full = cache.mesh
    survivors = make_mesh(devices=jax.devices()[:7])
    build = dist.build_stacked_pack
    remeshes = []

    def remesh_then_build(*args, **kw):
        if not remeshes:  # between the build's reading of the mesh
            remeshes.append(cache.invalidate_all())  # and its placing
            cache.set_mesh(survivors)
        return build(*args, **kw)

    monkeypatch.setattr(dist, "build_stacked_pack", remesh_then_build)
    idx = node.indices.indices["lib"]
    entry = cache.get(idx, "title")
    assert remeshes == [[]] and cache.misses == 1
    assert entry.mesh is survivors and entry.mesh is not full
    assert entry.pack.num_shards % 7 == 0
    assert cache.peek(("lib", "title")) is entry
    assert breaker.used == entry.hbm_bytes > 0
    assert cache.invalidate_all() == [("lib", "title")]
    assert breaker.used == 0


class TestShedContract:
    def test_exception_shape_and_retry_after_header(self):
        exc = PackShedException("pack shed for N-1 headroom",
                                index="lib", retry_after_s=7.0)
        assert exc.status == 503
        assert exc.index == "lib" and exc.retry_after_s == 7.0
        assert rejection_headers(exc, 503) == {"Retry-After": "7"}

    def test_shed_index_answers_typed_503_until_cleared(self, node):
        svc = node.tpu_search
        body = {"query": {"match": {"title": "gamma"}}}
        status, _ = _do(node, "POST", "/lib/_search", body=body)
        assert status == 200

        svc.set_shed([("lib", "title")], retry_after_s=7.0)
        try:
            assert svc.shed_keys() == [("lib", "title")]
            info = svc.shed_info("lib")
            assert info["field"] == "title"
            assert info["retry_after_s"] == 7.0
            status, resp = _do(node, "POST", "/lib/_search", body=body)
            assert status == 503
            assert resp["error"]["type"] == "pack_shed_exception"
            assert "shed" in resp["error"]["reason"]
            # other indices are untouched by lib's shed
            assert svc.shed_info("other") is None
            # shed packs surface in the /_tpu/stats devices block
            status, st = _do(node, "GET", "/_tpu/stats")
            assert status == 200
            assert st["devices"]["shed_packs"] == ["lib/title"]
        finally:
            svc.set_shed([])
        status, _ = _do(node, "POST", "/lib/_search", body=body)
        assert status == 200

    def test_degraded_reason_shapes(self, node):
        svc = node.tpu_search
        assert svc.degraded_info is None  # full health: no reason
        st = svc.device_stats()
        assert st["mesh_devices"] == st["mesh_devices_full"] == 8
        assert st["degraded"] is None
        assert st["health"]["active"] == 8
