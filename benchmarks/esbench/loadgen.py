"""Load generator: a child process that never imports jax.

Started by `run.py` as `python benchmarks/esbench/loadgen.py`, a few of
them sharing the clients. It reads one JSON command per line on stdin and
answers each with one JSON line on stdout:

  init    port, path, request bodies (base64) → {"ready": true}
  connect this process's clients open their connections, one by one
  warm    clients, queries, requests_per_client: a closed loop over the
          given queries only, a fixed number of requests, no clock
          (`clients` are this process's share of `total_clients` ids; a
          client keeps its connection from phase to phase)
  closed  the measured closed loop: this process's clients, from t_start
          (ramp) past t1 to t_stop (drain), all on CLOCK_MONOTONIC
  open    the measured open loop: this process's share of the arrivals
  quit

Why out of the server's process: client threads, response reads and
bookkeeping are Python, and inside the server they would take the GIL
from the batcher (PR 22's closed cell lost qps, and its steadiness, to
that). Here they cost another core. During a measured phase the process
checks status and length of every response, keeps the raw bytes of the
first in-window response of each sampled query, and parses nothing.
stdlib + numpy only; `imported_jax` in every result says so.
"""

from __future__ import annotations

import base64
import gc
import http.client
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from esbench import traffic  # noqa: E402

HEADERS = {"Content-Type": "application/json"}
REQUEST_TIMEOUT_S = 120.0


def now_ns() -> int:
    return time.clock_gettime_ns(time.CLOCK_MONOTONIC)


def sleep_until(t_ns: int) -> None:
    while True:
        left = t_ns - now_ns()
        if left <= 0:
            return
        time.sleep(left / 1e9)


class Client:
    """One keep-alive connection; reconnects after a failure."""

    def __init__(self, port: int, path: str):
        self.port, self.path = port, path
        self.conn: Optional[http.client.HTTPConnection] = None

    def connect(self) -> None:
        """Open the connection if there is none, and have the node answer
        on it once. The node listens with a backlog of 5
        (ThreadingHTTPServer's default): a SYN that finds it full is
        dropped and sent again a second later, and connects made faster
        than the node accepts them fill it (512 of them took 63 s, every
        sixth a second long: PERF.md, PR 28). An answer shows that this
        connection has been accepted, so a process that connects one by
        one never has more than one waiting. A refused connect is tried
        again, spaced, for up to ~10 s. Connecting is not part of any
        request's time."""
        for _attempt in range(200):
            if self.conn is not None:
                return
            conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                              timeout=REQUEST_TIMEOUT_S)
            try:
                conn.request("GET", "/")
                conn.getresponse().read()
                self.conn = conn
            except (OSError, http.client.HTTPException):
                conn.close()
                time.sleep(0.05)

    def send(self, body: bytes) -> Tuple[bool, int, int, bytes]:
        """→ (ok, status, length, raw body); never raises."""
        try:
            if self.conn is None:
                raise OSError("not connected")
            self.conn.request("POST", self.path, body=body, headers=HEADERS)
            resp = self.conn.getresponse()
            data = resp.read()
            return (resp.status == 200 and len(data) > 0, resp.status,
                    len(data), data)
        except (OSError, http.client.HTTPException) as exc:
            self.close()
            return False, 0, 0, repr(exc).encode("utf-8")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Recorder:
    """Per-thread request records, merged after the phase."""

    def __init__(self, sample: List[int], t0_ns: int, t1_ns: int):
        self.rows: List[List[Tuple[int, int, int, int, bool, int, int]]] = []
        self.sample = set(sample)
        self.t0_ns, self.t1_ns = t0_ns, t1_ns
        self.kept: Dict[int, Tuple[int, bytes]] = {}
        self.lock = threading.Lock()

    def thread_rows(self) -> List[Tuple[int, int, int, int, bool, int, int]]:
        rows: List[Tuple[int, int, int, int, bool, int, int]] = []
        with self.lock:
            self.rows.append(rows)
        return rows

    def keep(self, q: int, send_ns: int, done_ns: int, data: bytes) -> None:
        if q in self.sample and self.t0_ns <= send_ns < self.t1_ns:
            with self.lock:
                if q not in self.kept:
                    self.kept[q] = (done_ns, data)

    def save(self, path: str, extra: Dict[str, Any]) -> None:
        rows = [r for part in self.rows for r in part]
        cols = list(zip(*rows)) if rows else [[] for _ in range(7)]
        qs = sorted(self.kept)
        blobs = [self.kept[q][1] for q in qs]
        offsets = np.cumsum([0] + [len(b) for b in blobs])
        np.savez(
            path,
            due_ns=np.asarray(cols[0], dtype=np.int64),
            send_ns=np.asarray(cols[1], dtype=np.int64),
            done_ns=np.asarray(cols[2], dtype=np.int64),
            query=np.asarray(cols[3], dtype=np.int64),
            ok=np.asarray(cols[4], dtype=bool),
            status=np.asarray(cols[5], dtype=np.int64),
            nbytes=np.asarray(cols[6], dtype=np.int64),
            sample_query=np.asarray(qs, dtype=np.int64),
            sample_done_ns=np.asarray([self.kept[q][0] for q in qs],
                                      dtype=np.int64),
            sample_offsets=offsets.astype(np.int64),
            sample_bytes=np.frombuffer(b"".join(blobs), dtype=np.uint8),
            extra=np.asarray(json.dumps(extra)))


class Generator:
    def __init__(self, port: int, path: str, bodies: List[bytes]):
        self.port, self.path, self.bodies = port, path, bodies
        self.clients: Dict[int, Client] = {}

    def client(self, cid: int) -> Client:
        if cid not in self.clients:
            self.clients[cid] = Client(self.port, self.path)
        return self.clients[cid]

    def close(self) -> None:
        for c in self.clients.values():
            c.close()

    def connect(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        """Open this process's connections one after another, before any
        load: a burst of connects overflows the node's listen backlog."""
        for cid in cmd["clients"]:
            self.client(cid).connect()
        return {"connected": sum(c.conn is not None for c in self.clients.values())}

    # -- warm-up: a closed loop over given queries, a fixed count ---------

    def warm(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        queries: List[int] = cmd["queries"]
        n_clients, per_client = int(cmd["total_clients"]), int(cmd["requests_per_client"])
        counts: Dict[str, Any] = {"sent": 0, "failed": 0, "failures": []}
        lock = threading.Lock()

        def loop(ci: int) -> None:
            client = self.client(ci)
            client.connect()
            sent = failed = 0
            for j in range(per_client):
                q = queries[(ci + j * n_clients) % len(queries)]
                ok, status, _n, data = client.send(self.bodies[q])
                sent += 1
                failed += not ok
                if not ok:
                    if len(counts["failures"]) < 3:  # what a failure looks like
                        counts["failures"].append([status, data[:700].decode("utf-8", "replace")])
                    client.connect()
            with lock:
                counts["sent"] += sent
                counts["failed"] += failed

        t0 = now_ns()
        run_threads([threading.Thread(target=loop, args=(ci,))
                     for ci in cmd["clients"]])
        return {**counts, "seconds": (now_ns() - t0) / 1e9}

    # -- measured phases ----------------------------------------------------

    def _cpu_sampler(self, t0_ns: int, t1_ns: int, out: Dict[str, Any]
                     ) -> threading.Thread:
        """This process's CPU seconds over the window."""
        def sample() -> None:
            sleep_until(t0_ns)
            cpu0 = time.process_time_ns()
            sleep_until(t1_ns)
            out["gen_cpu_s"] = (time.process_time_ns() - cpu0) / 1e9
        return threading.Thread(target=sample)

    def closed(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        total = int(cmd["total_clients"])
        order = traffic.query_order(int(cmd["seed"]), len(self.bodies))
        t_start, t_stop = int(cmd["t_start_ns"]), int(cmd["t_stop_ns"])
        rec = Recorder(cmd["sample"], int(cmd["t0_ns"]), int(cmd["t1_ns"]))
        stagger_ns = int(float(cmd.get("stagger_s", 0.5)) * 1e9)

        def loop(cid: int) -> None:
            client, rows = self.client(cid), rec.thread_rows()
            client.connect()
            sleep_until(t_start + stagger_ns * cid // total)
            j = 0
            while True:
                t_send = now_ns()
                if t_send >= t_stop:
                    return
                q = traffic.closed_query(order, cid, j, total)
                ok, status, n, data = client.send(self.bodies[q])
                t_done = now_ns()
                rows.append((t_send, t_send, t_done, q, ok, status, n))
                if ok:
                    rec.keep(q, t_send, t_done, data)
                else:
                    client.connect()
                j += 1

        return self._measure(cmd, rec, [threading.Thread(target=loop, args=(cid,))
                                        for cid in cmd["clients"]])

    def open(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        t_start, t_stop = int(cmd["t_start_ns"]), int(cmd["t_stop_ns"])
        due, query = traffic.open_schedule(
            int(cmd["seed"]), cmd["spec"], (t_stop - t_start) / 1e9,
            len(self.bodies))
        mine = np.arange(due.shape[0]) % int(cmd["procs"]) == int(cmd["proc"])
        mine &= due < t_stop - t_start
        due_abs = (due[mine] + t_start).tolist()
        query_l = query[mine].tolist()
        rec = Recorder(cmd["sample"], int(cmd["t0_ns"]), int(cmd["t1_ns"]))
        cursor = iter(range(len(due_abs)))
        lock = threading.Lock()

        def loop(wid: int) -> None:
            client, rows = self.client(wid), rec.thread_rows()
            while True:
                client.connect()
                with lock:
                    i = next(cursor, None)
                if i is None:
                    return
                sleep_until(due_abs[i])
                t_send = now_ns()
                ok, status, n, data = client.send(self.bodies[query_l[i]])
                t_done = now_ns()
                rows.append((due_abs[i], t_send, t_done, query_l[i], ok,
                             status, n))
                if ok:
                    rec.keep(query_l[i], due_abs[i], t_done, data)

        return self._measure(cmd, rec, [threading.Thread(target=loop, args=(w,))
                                        for w in cmd["clients"]])

    def _measure(self, cmd: Dict[str, Any], rec: Recorder,
                 threads: List[threading.Thread]) -> Dict[str, Any]:
        extra: Dict[str, Any] = {"imported_jax": "jax" in sys.modules}
        sampler = self._cpu_sampler(int(cmd["t0_ns"]), int(cmd["t1_ns"]), extra)
        gc.collect()
        gc.disable()  # no collector pause inside the window
        try:
            run_threads(threads + [sampler])
        finally:
            gc.enable()
        rec.save(cmd["out"], extra)
        return {"file": cmd["out"]}


def run_threads(threads: List[threading.Thread]) -> None:
    for t in threads:
        t.daemon = True
        t.start()
    for t in threads:
        t.join()


def main() -> int:
    gen: Optional[Generator] = None
    for line in sys.stdin:
        cmd = json.loads(line)
        kind = cmd["cmd"]
        if kind == "quit":
            break
        if kind == "init":
            gen = Generator(int(cmd["port"]), cmd["path"],
                            [base64.b64decode(b) for b in cmd["bodies"]])
            reply: Dict[str, Any] = {"ready": True}
        elif gen is None:
            reply = {"error": "init first"}
        elif kind in ("connect", "warm", "closed", "open"):
            reply = getattr(gen, kind)(cmd)
        else:
            reply = {"error": f"unknown command [{kind}]"}
        reply["imported_jax"] = "jax" in sys.modules
        sys.stdout.write(json.dumps(reply) + "\n")
        sys.stdout.flush()
    if gen is not None:
        gen.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
