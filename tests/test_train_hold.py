"""A train is formed as late as the device allows (`_PackQueue._hold`).

The device is a stub: `launch_flat_batch` records the train and hands back
an event, `finish_flat_batch` returns when the test sets it, so "launched
and unfinished" is exactly what the test says it is. The closed-loop model
at the end gives the stub a serial device with a clock of its own.
"""

from __future__ import annotations

import threading
import time

import pytest

from elasticsearch_tpu.search import tpu_service
from elasticsearch_tpu.search.tpu_service import (HOLD_EXIT_COUNTS, HOLD_EXITS,
                                                  MicroBatcher, _PackQueue)

MAX_BATCH = 16
DEPTH = _PackQueue.PIPELINE_DEPTH
#: long enough for a launch thread that was going to take a train at once to
#: have taken it (a refill window is 0.05 s), short enough to wait out often
SETTLE_S = 0.15


def _exits_since(before):
    """`hold_exit` counts are the process's: a test reads their rise."""
    after = HOLD_EXIT_COUNTS.counts()
    return {k: after[k] - before.get(k, 0) for k in HOLD_EXITS}


class _Stages:
    """What `ThreadStates` hands a `StageTimes`: kept, so that a test can
    read a state's notes."""

    def __init__(self):
        self.added = []

    def add(self, stage, dt, n=1, cpu=None, attributes=None):
        self.added.append((stage, dict(attributes or {})))


class _Device:
    """Every train launched, in order; train `i` is unfinished until
    `finish(i)` (the completer takes them in order, as the device does)."""

    def __init__(self, monkeypatch, window_s=0.0, max_batch=MAX_BATCH):
        self.sizes = []
        self.flats = []
        self.launched_at = []
        self.done = []
        self._lock = threading.Lock()
        monkeypatch.setattr(tpu_service, "launch_flat_batch", self._launch)
        monkeypatch.setattr(tpu_service, "finish_flat_batch", self._finish)
        self.batcher = MicroBatcher(window_s=window_s, max_batch=max_batch)
        self.batcher.stages = self.stages = _Stages()
        self.pack = object()
        self.exits_before = HOLD_EXIT_COUNTS.counts()

    def _launch(self, resident, flats, k, mesh=None, stages=None,
                max_batch=128):
        with self._lock:
            self.sizes.append(len(flats))
            self.flats.append(list(flats))
            self.launched_at.append(time.monotonic())
            self.done.append(threading.Event())
            return {"train": len(self.sizes) - 1, "n": len(flats)}

    def _finish(self, st):
        assert self.done[st["train"]].wait(timeout=10.0)
        return ["r"] * st["n"]

    def submit(self, n, tag="q"):
        return [self.batcher.submit(self.pack, flat=(tag, i), k=1)
                for i in range(n)]

    def wait_trains(self, n, timeout=5.0):
        deadline = time.monotonic() + timeout
        while len(self.sizes) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(self.sizes) >= n, self.sizes

    def finish(self, train, futures=()):
        self.done[train].set()
        for f in futures:
            assert f.result(timeout=5.0) == "r"

    def unfinished(self):
        return self.batcher.queue_depths()["inflight"]

    def fill(self, trains):
        """`trains` full trains launched and unfinished → their futures."""
        futures = []
        for i in range(trains):
            futures.append(self.submit(self.batcher.max_batch, tag=f"t{i}"))
            self.wait_trains(i + 1)
        deadline = time.monotonic() + 5.0
        while self.unfinished() < trains and time.monotonic() < deadline:
            time.sleep(0.005)
        assert self.unfinished() == trains
        return futures

    def exits(self):
        return _exits_since(self.exits_before)

    def close(self):
        for e in self.done:
            e.set()
        self.batcher.close()


@pytest.fixture
def device(monkeypatch):
    d = _Device(monkeypatch)
    yield d
    d.close()


def test_with_two_unfinished_only_a_full_train_is_taken(device):
    device.fill(2)
    device.submit(MAX_BATCH - 1)          # past half a train: not enough
    time.sleep(SETTLE_S)
    assert device.sizes == [MAX_BATCH] * 2
    t0 = time.monotonic()
    device.submit(1)                      # the max_batch-th: at once
    device.wait_trains(3)
    assert device.launched_at[2] - t0 < 0.1
    assert device.sizes == [MAX_BATCH] * 3
    assert device.exits() == {"full": 3, "backlog_low": 0, "idle_window": 0}


@pytest.mark.parametrize("pending,taken_at_one", [
    (MAX_BATCH // 2, True),               # half a train: the refill window
    (MAX_BATCH // 2 - 1, False),          # less: the device must go idle
])
def test_when_one_remains_unfinished_todays_rule_takes_the_train(
        device, pending, taken_at_one):
    first, second = device.fill(2)
    futures = device.submit(pending)
    time.sleep(SETTLE_S)
    assert len(device.sizes) == 2
    device.finish(0, first)               # the device runs its last train
    if taken_at_one:
        device.wait_trains(3, timeout=1.0)
        assert device.exits()["backlog_low"] == 1
        device.finish(1, second)
    else:
        time.sleep(SETTLE_S)
        assert len(device.sizes) == 2
        device.finish(1, second)          # nothing in flight
        device.wait_trains(3, timeout=1.0)
        assert device.exits()["idle_window"] == 1
    assert device.sizes[2] == pending
    device.finish(2, futures)


def test_nothing_is_taken_while_pipeline_depth_are_unfinished(device):
    first, *rest = device.fill(DEPTH)
    early = device.submit(MAX_BATCH // 2, tag="early")
    time.sleep(SETTLE_S / 2)
    late = device.submit(MAX_BATCH // 2, tag="late")   # a full train pending
    time.sleep(SETTLE_S)
    assert len(device.sizes) == DEPTH
    assert device.batcher.queue_depths()["pending"] == MAX_BATCH
    device.finish(0, first)               # a slot frees
    device.wait_trains(DEPTH + 1)
    # the queue stayed open: both cohorts ride the one train
    assert sorted(tag for tag, _ in device.flats[DEPTH]) == \
        ["early"] * (MAX_BATCH // 2) + ["late"] * (MAX_BATCH // 2)
    for train, futures in enumerate(rest + [early + late], start=1):
        device.finish(train, futures)
    # the wait was the state `blocked`, and the hold's note says how it ended
    states = [stage for stage, _ in device.stages.added]
    assert "batcher.blocked" in states
    notes = [a for stage, a in device.stages.added
             if stage == "batcher.hold" and "exit" in a]
    assert notes[-1]["exit"] == "full" and notes[-1]["pending"] == MAX_BATCH


def test_a_lone_query_on_an_idle_queue_pays_window_s(monkeypatch):
    window_s = 0.05
    device = _Device(monkeypatch, window_s=window_s)
    try:
        for _ in range(3):                # the first spawns the threads
            t0 = time.monotonic()
            futures = device.submit(1)
            train = len(device.sizes)
            device.wait_trains(train + 1)
            waited = device.launched_at[train] - t0
            assert window_s * 0.9 <= waited < window_s + 0.2, waited
            device.finish(train, futures)
            while device.unfinished():
                time.sleep(0.005)
        assert device.sizes == [1, 1, 1]
        assert device.exits() == {"full": 0, "backlog_low": 0,
                                  "idle_window": 3}
    finally:
        device.close()


@pytest.mark.parametrize("max_batch", [16, 32, 48])
def test_a_closed_loop_of_three_trains_callers_rides_full_trains(
        monkeypatch, max_batch):
    """3 x max_batch callers, each back `return_s` after its answer, on a
    serial device that takes 20 x as long for a train whatever it carries:
    taken early, the callers spread over four trains (three launched, one
    taken and waiting: fill 0.75); taken as late as the device allows they
    ride full ones."""
    device_s, return_s, run_s = 0.2, 0.01, 3.0
    lock = threading.Lock()
    free_at = [0.0]
    trains = []

    def launch(resident, flats, k, mesh=None, stages=None, max_batch=128):
        with lock:
            now = time.monotonic()
            free_at[0] = max(free_at[0], now) + device_s
            trains.append((now, len(flats)))
            return {"ready_at": free_at[0], "n": len(flats)}

    def finish(st):
        while (left := st["ready_at"] - time.monotonic()) > 0:
            time.sleep(min(left, 0.05))
        return ["r"] * st["n"]

    monkeypatch.setattr(tpu_service, "launch_flat_batch", launch)
    monkeypatch.setattr(tpu_service, "finish_flat_batch", finish)
    batcher = MicroBatcher(window_s=0.0, max_batch=max_batch)
    exits_before = HOLD_EXIT_COUNTS.counts()
    pack = object()
    go = threading.Event()
    stop_at = [0.0]

    def caller():
        go.wait()
        while time.monotonic() < stop_at[0]:
            assert batcher.submit(pack, None, 1).result(timeout=10.0) == "r"
            time.sleep(return_s)

    callers = [threading.Thread(target=caller)
               for _ in range(DEPTH * max_batch)]
    try:
        for t in callers:
            t.start()
        # the clock starts with every caller alive: on a loaded machine
        # 144 threads take longer to start than the ramp allows them
        t0 = time.monotonic()
        stop_at[0] = t0 + run_s
        go.set()
        for t in callers:
            t.join(timeout=30.0)
        # the ramp (the first trains go as the idle rule sends them) and the
        # drain (callers leaving) are not the closed loop
        steady = [n for at, n in trains
                  if t0 + 4 * device_s <= at < stop_at[0] - 2 * device_s]
        assert len(steady) >= 5, trains
        assert sum(steady) / len(steady) >= 0.95 * max_batch, steady
        # one reason a train, and the three sum to the trains executed
        rise = _exits_since(exits_before)
        assert sum(rise.values()) == len(trains) == batcher.batches_executed
        assert rise["full"] >= len(steady) * 0.9
    finally:
        batcher.close()


def test_hold_exit_is_in_the_stats_and_sums_to_batches(monkeypatch):
    monkeypatch.setattr(
        tpu_service, "launch_flat_batch",
        lambda resident, flats, k, mesh=None, stages=None, max_batch=128:
        len(flats))
    monkeypatch.setattr(tpu_service, "finish_flat_batch",
                        lambda n: ["r"] * n)
    svc = tpu_service.TpuSearchService(window_s=0.0, batch_timeout_s=30.0)
    try:
        before = svc.stats()
        assert set(before["hold_exit"]) == set(HOLD_EXITS)
        pack = object()
        for burst in (1, svc.batcher.max_batch, 3):
            for f in [svc.batcher.submit(pack, None, 1) for _ in range(burst)]:
                assert f.result(timeout=5.0) == "r"
        after = svc.stats()
        assert after["hold_exit"] == HOLD_EXIT_COUNTS.counts()
        rise = _exits_since(before["hold_exit"])
        assert sum(rise.values()) == after["batches"] - before["batches"] >= 3
        assert after["batched_queries"] - before["batched_queries"] == \
            svc.batcher.max_batch + 4
    finally:
        svc.close()
