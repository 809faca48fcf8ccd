"""Test harness configuration.

The whole suite runs on a virtual 8-device CPU mesh (the sandbox has no
accelerator; the chip is only reached through `chip_smoke.py`). The
platform and device count must be in the environment before jax is first
imported anywhere in the test process.

Also ports the reference's ESTestCase seeded-randomness idea (SURVEY.md
§4.1): every test gets a reproducible RNG; set TESTS_SEED to reproduce.
"""

import hashlib
import os
import random

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest

_SEED = int(os.environ.get("TESTS_SEED", "0")) or random.SystemRandom().randint(1, 2**31)


def pytest_report_header(config):
    return f"tests seed: {_SEED} (reproduce with TESTS_SEED={_SEED})"


def _test_seed(nodeid: str) -> int:
    # stable across processes (hash() is salted per-process; sha256 is not)
    digest = hashlib.sha256(nodeid.encode()).hexdigest()
    return (_SEED ^ int(digest[:8], 16)) & 0x7FFFFFFF


@pytest.fixture
def seeded_random(request):
    """Per-test deterministic RNG derived from the suite seed + test id."""
    return random.Random(_test_seed(request.node.nodeid))


@pytest.fixture
def seeded_np(request):
    return np.random.default_rng(_test_seed(request.node.nodeid))


@pytest.fixture
def tmp_data_path(tmp_path):
    p = tmp_path / "data"
    p.mkdir()
    return p


# -- multiprocess test guard rails ------------------------------------
#
# Tests marked `multiprocess` spawn serving-front child processes. Two
# failure modes would otherwise poison tier-1: a wedged child blocking
# the parent forever (pipe recv with no timeout), and orphaned children
# surviving a failed test to interfere with the next one. A SIGALRM
# hard timeout bounds each marked test; orphan reaping happens at
# MODULE teardown (after module-scoped node fixtures have closed their
# supervisors — per-test reaping would kill fronts that legitimately
# live across the tests of one module).

MULTIPROCESS_TEST_TIMEOUT_S = int(
    os.environ.get("ES_TPU_MULTIPROCESS_TEST_TIMEOUT_S", "120"))


@pytest.fixture(autouse=True)
def _multiprocess_timeout(request):
    # supervision tests (watchdog/recovery/chaos) park threads in fault
    # hooks and spawn recovery threads — same wedge risk, same guard;
    # device_loss/placement tests additionally park probe/reprobe and
    # group-restore threads
    if (request.node.get_closest_marker("multiprocess") is None
            and request.node.get_closest_marker("supervision") is None
            and request.node.get_closest_marker("device_loss") is None
            and request.node.get_closest_marker("placement") is None
            and request.node.get_closest_marker("merge_pool") is None
            and request.node.get_closest_marker("streaming") is None):
        yield
        return
    import signal

    def _alarm(signum, frame):
        raise TimeoutError(
            f"multiprocess/supervision test exceeded its "
            f"{MULTIPROCESS_TEST_TIMEOUT_S}s hard timeout")

    prior = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(MULTIPROCESS_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prior)


# -- compressed-pack slack guard --------------------------------------
#
# Tests marked `compressed_pack` drive the compressed kernel variants,
# which (like every sorted_merge_topk variant) slice `max_len` lanes
# from each slot start with dynamic_slice. dynamic_slice CLAMPS
# out-of-bounds starts, so a corpus whose flat arrays lack CHUNK_CAP
# slack past the last posting doesn't crash — it silently shifts the
# last term's read window onto earlier postings and the parity assert
# chases a phantom miscompare (the trap PR 4's make_flat NOTE
# documents). Fail fast with the real cause instead.


@pytest.fixture(autouse=True)
def _compressed_pack_slack_guard(request, monkeypatch):
    if request.node.get_closest_marker("compressed_pack") is None:
        yield
        return
    from elasticsearch_tpu.ops import sparse as _sparse

    real = _sparse.sorted_merge_topk

    def checked(flat_docs, flat_impact, starts, lengths, weights,
                min_count, *, max_len, **kw):
        p = int(np.shape(flat_docs)[0])
        worst = int(np.max(np.asarray(starts))) + max_len
        if worst > p:
            pytest.fail(
                f"compressed-pack corpus lacks CHUNK_CAP slack: a slot "
                f"start + max_len bucket reads to lane {worst} but the "
                f"flats end at {p}. dynamic_slice would CLAMP the "
                f"window onto earlier postings (silent wrong results) "
                f"— pad the flat arrays by the max_len bucket "
                f"(make_flat's slack covers chunk_cap=4096).")
        return real(flat_docs, flat_impact, starts, lengths, weights,
                    min_count, max_len=max_len, **kw)

    monkeypatch.setattr(_sparse, "sorted_merge_topk", checked)
    yield


@pytest.fixture(scope="module", autouse=True)
def _multiprocess_orphan_reaper(request):
    yield
    mod_id = request.node.nodeid
    marked = any(item.get_closest_marker("multiprocess") is not None
                 or item.get_closest_marker("supervision") is not None
                 or item.get_closest_marker("device_loss") is not None
                 or item.get_closest_marker("placement") is not None
                 or item.get_closest_marker("merge_pool") is not None
                 or item.get_closest_marker("streaming") is not None
                 for item in request.session.items
                 if item.nodeid.startswith(mod_id))
    if not marked:
        return
    import multiprocessing
    for child in multiprocessing.active_children():
        child.terminate()
        child.join(timeout=5.0)
        if child.is_alive():
            child.kill()
            child.join(timeout=5.0)
