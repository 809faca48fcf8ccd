"""Record the annotated trace that the host-span tests read.

    chiprun -- python benchmarks/tools/record_annotated_trace.py
    python benchmarks/tools/record_annotated_trace.py --slim SRC.xplane.pb DST.xplane.pb

A toy node (3,000 docs in 2 shards, raw pack, so the pruned full-postings
path serves) answers two rounds of concurrent searches under a
profiler session recorded as `run.py` records (`host_tracer_level` 1, no
Python tracer), with a sleep and one `gc.collect()` between the rounds so
that `batcher.wait` and `gc.full` cover idle time too. Leaves the
`.xplane.pb` in `chiprun_out/annotated_trace/` with a description of its
planes and `annotated_trace.json`: the shares by a hand count (a sweep
over every boundary of every interval, written here and sharing nothing
with `esbench/hostspans.py`). Needs the chip.

`--slim` (anywhere `tensorflow` brings `xplane_pb2`) keeps what the
readers read — the device planes' `XLA Ops` and `XLA Modules` lines and
the host lines' annotations and `Wait for` events, without their stats —
so that the committed copy in `benchmarks/testdata/` stays small. The
hand count is taken again from the slimmed file: it must not change.
"""

import gc
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

OUT = os.path.join("chiprun_out", "annotated_trace")
KEEP_HOST = ("batcher.", "completer.", "gc.full", "Wait for")


def hand_count(path: str) -> dict:
    """Idle seconds by launch-thread state, by brute force: cut the window
    at every boundary of every op and span; a piece is idle if no op
    covers it and belongs to the first state of the precedence whose span
    covers it."""
    from esbench import hostspans, tracered
    ops = []
    planes = tracered.load_device_events(path)
    for lines in planes.values():
        ops += lines.get(tracered.OPS_LINE, [])
    assert len(planes) == 1, "the hand count is written for one device plane"
    spans = [ev for line in hostspans.load_host_lines(path) for ev in line]
    lo, hi = min(s for s, _e, _n in ops), max(e for _s, e, _n in ops)
    cuts = sorted({lo, hi} | {t for s, e, _n in ops + spans for t in (s, e)
                              if lo < t < hi})
    out = {name: 0.0 for name in hostspans.PRECEDENCE + (hostspans.UNATTRIBUTED,)}
    busy = 0.0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e, _n in ops):
            busy += b - a
            continue
        names = {n for s, e, n in spans if s <= mid < e}
        state = next((n for n in hostspans.PRECEDENCE if n in names),
                     hostspans.UNATTRIBUTED)
        out[state] += b - a
    modules = {}
    for lines in planes.values():
        for s, e, name in lines.get(tracered.MODULES_LINE, []):
            entry = modules.setdefault(hostspans.module_name(name), [0.0, 0])
            entry[0] += (e - s) / 1e9
            entry[1] += 1
    return {
        "how": "window cut at every boundary of every op and span; a piece is "
               "idle if no op covers its midpoint and goes to the first state "
               "of the precedence whose span covers it",
        "device_planes": len(planes), "op_events": len(ops),
        "span_events": {n: sum(1 for _s, _e, nm in spans if nm == n)
                        for n in sorted({nm for _s, _e, nm in spans})},
        "window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
        "idle_s": {n: v / 1e9 for n, v in out.items()},
        "modules": modules,
    }


def slim(src: str, dst: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    kept = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        out = kept.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = [ev for ev in line.events if device or
                      plane.event_metadata[ev.metadata_id].name.startswith(KEEP_HOST)]
            if not events:
                continue
            new = out.lines.add(id=line.id, name=line.name,
                                timestamp_ns=line.timestamp_ns)
            for ev in events:
                new.events.add(metadata_id=ev.metadata_id, offset_ps=ev.offset_ps,
                               duration_ps=ev.duration_ps)
                used.add(ev.metadata_id)
        for mid in used:
            meta = plane.event_metadata[mid]
            out.event_metadata[mid].id = meta.id
            # the head of the op's own text: its name, shape and layout
            out.event_metadata[mid].name = meta.name.split(", metadata=")[0][:40]
    with open(dst, "wb") as f:
        f.write(kept.SerializeToString())
    counted = hand_count(dst)
    with open(os.path.splitext(os.path.splitext(dst)[0])[0] + ".json", "w",
              encoding="utf-8") as f:
        json.dump(counted, f, indent=1)
    print(dst, os.path.getsize(dst), json.dumps(counted["idle_s"]))


def record() -> int:
    import jax

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node
    from esbench import tracered

    os.makedirs(OUT, exist_ok=True)
    node = Node(tempfile.mkdtemp(prefix="annotated_trace_"), settings=Settings.of(
        {"search.tpu_serving.kernel.compressed_pack": False}))
    try:
        node.handle("PUT", "/toy", body={
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        lines = []
        for i in range(3000):
            lines.append(json.dumps({"index": {"_index": "toy", "_id": str(i)}}))
            lines.append(json.dumps({"body": " ".join(
                f"w{(i * 7 + j * j) % 97}" for j in range(12))}))
        node.handle("POST", "/_bulk", raw_body=("\n".join(lines) + "\n").encode())
        node.handle("POST", "/toy/_refresh")

        def search(c: int, r: int) -> None:
            status, body = node.handle("POST", "/toy/_search", body={
                "query": {"match": {"body": f"w{(3 * c + r) % 97} w{(5 * c + 2 * r + 1) % 97}"}},
                "size": 10})
            assert status == 200 and body["hits"]["hits"], (status, body)

        def one_round(r: int, clients: int = 6) -> None:
            threads = [threading.Thread(target=search, args=(c, r))
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        for r in range(3):  # compile the launch shapes
            one_round(r)
            search(0, r)
        before = node.tpu_search.stats()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(OUT, profiler_options=options)
        for r in range(2):  # ~3,000 device ops a launch: two keep the file small
            one_round(10 + r)
            time.sleep(0.03)
            if r == 0:
                gc.collect()
        jax.profiler.stop_trace()
        after = node.tpu_search.stats()
    finally:
        node.close()
    path = tracered.newest_xplane(OUT)
    with open(os.path.join(OUT, "planes.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(tracered.describe(path)))
    counted = hand_count(path)
    counted["launches"] = {k: v - before["launches"].get(k, 0)
                           for k, v in after["launches"].items()}
    counted["fallback"] = after["fallback"] - before["fallback"]
    with open(os.path.join(OUT, "annotated_trace.raw.json"), "w",
              encoding="utf-8") as f:
        json.dump(counted, f, indent=1)
    print(path, os.path.getsize(path), json.dumps(counted))
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--slim":
        slim(sys.argv[2], sys.argv[3])
        sys.exit(0)
    sys.exit(record())
