"""Build one configuration's index, once per checkout (a child of run.py).

    python benchmarks/build_index.py --config <file> --out <dir>

Generates the configuration's corpus, indexes it through the node's own
REST surface (`PUT index`, `_bulk`, `_refresh`, `_forcemerge`, `_flush`
over HTTP on `serve()`), checks the per-shard doc counts against the
reference's routing, and writes beside the data directory what later runs
need and must not recompute: every query's reference top-k under
`match`'s default operator (`reference.npz`) and the query set's strata
for the warm-up (`queries.npz`). `manifest.json` is written last; a
directory without it is not an index.

    python benchmarks/build_index.py --config <file> --out <dir>
                                     --reference-only --operator and

adds the reference of another operator (`reference-and.npz`, the file
`esbench.reference.stored_name` names) to a directory that is an index
already: the corpus and the query set come from the seed again, the node
is not opened and nothing is indexed. run.py asks for it when a cell's
traffic sends an operator whose reference the directory lacks.

This process runs with JAX_PLATFORMS=cpu and the TPU serving path off:
run.py holds the chip while it waits, and nothing here needs a device.
The serving process opens the directory afresh on every run, so its heap
is the same whether or not this run built the index.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from esbench import corpus as corpus_gen  # noqa: E402
from esbench import reference  # noqa: E402

INDEX = "bench"
FIELD = "body"
BULK_DOCS = 4000
BULK_CLIENTS = 4
REFERENCE_K = 1000


def log(msg: str) -> None:
    print(f"[build_index] {msg}", file=sys.stderr, flush=True)


def http_json(conn: http.client.HTTPConnection, method: str, path: str,
              body: Any = None) -> Any:
    if body is not None and not isinstance(body, (str, bytes)):
        body = json.dumps(body)
    conn.request(method, path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    if resp.status != 200:
        raise RuntimeError(f"{method} {path} -> HTTP {resp.status}: {data[:400]!r}")
    return json.loads(data)


def bulk_all(port: int, corpus: corpus_gen.Corpus) -> None:
    words = [corpus_gen.word(i) for i in range(corpus.vocab_size)]
    chunks = iter(range(0, corpus.num_docs, BULK_DOCS))
    lock = threading.Lock()
    failures: List[BaseException] = []

    def client() -> None:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            while not failures:
                with lock:
                    lo = next(chunks, None)
                if lo is None:
                    return
                lines = []
                for i in range(lo, min(lo + BULK_DOCS, corpus.num_docs)):
                    lines.append('{"index":{"_id":"%s"}}' % corpus_gen.doc_id(i))
                    # words are [a-z0-9]+: nothing to escape
                    lines.append('{"%s":"%s"}' % (
                        FIELD, corpus_gen.doc_text(corpus, i, words)))
                resp = http_json(conn, "POST", f"/{INDEX}/_bulk",
                                 "\n".join(lines) + "\n")
                if resp["errors"]:
                    raise RuntimeError(f"_bulk item errors: {str(resp['items'][:2])[:400]}")
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            failures.append(exc)
        finally:
            conn.close()

    threads = [threading.Thread(target=client) for _ in range(BULK_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        raise failures[0]


def build(config: Dict[str, Any], out: str) -> None:
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node, serve

    gen, shards = config["generator"], int(config["index"]["number_of_shards"])
    os.makedirs(out, exist_ok=True)
    t0 = time.monotonic()
    corpus = corpus_gen.generate_corpus(gen)
    queries = corpus_gen.generate_queries(gen)
    log(f"corpus: {corpus.num_docs} docs, {corpus.flat.shape[0]} tokens, "
        f"{len(queries)} queries ({time.monotonic() - t0:.1f}s)")

    node = Node(os.path.join(out, "data"), settings=Settings.of(
        {"search.tpu_serving.enabled": "false"}))
    server = serve(node, port=0)
    port = server.server_address[1]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=1800)
    try:
        http_json(conn, "PUT", f"/{INDEX}", {
            "settings": {"index": {"number_of_shards": shards}},
            "mappings": {"properties": {FIELD: {"type": "text"}}}})
        t1 = time.monotonic()
        bulk_all(port, corpus)
        http_json(conn, "POST", f"/{INDEX}/_refresh")
        t2 = time.monotonic()
        log(f"indexed {corpus.num_docs} docs in {t2 - t1:.1f}s "
            f"({corpus.num_docs / (t2 - t1):.0f} docs/s)")
        # one segment per shard, as Rally's force-merge step leaves it
        http_json(conn, "POST", f"/{INDEX}/_forcemerge")
        http_json(conn, "POST", f"/{INDEX}/_refresh")
        http_json(conn, "POST", f"/{INDEX}/_flush")
        log(f"forcemerge + flush {time.monotonic() - t2:.1f}s")
        conn.request("GET", f"/_cat/shards/{INDEX}")
        cat = conn.getresponse().read().decode("utf-8")
    finally:
        conn.close()
        server.shutdown()
        server.server_close()
        node.close()

    # the reference routes by its own murmur3: the node must agree
    shard_of = reference.shard_of_digit_ids(np.arange(corpus.num_docs), shards)
    want = np.bincount(shard_of, minlength=shards).tolist()
    got = [0] * shards
    for row in cat.splitlines():
        cols = row.split()
        if len(cols) >= 5 and cols[0] == INDEX and cols[2] == "p":
            got[int(cols[1])] = int(cols[4])
    if got != want:
        raise RuntimeError(f"docs per shard {got} != reference routing {want}")

    ref_s = write_reference(out, corpus, queries, shards, "or", with_strata=True)
    manifest = {"config": config["name"], "docs": corpus.num_docs,
                "tokens": int(corpus.flat.shape[0]), "shards": shards,
                "docs_per_shard": want, "queries": len(queries),
                "index": INDEX, "field": FIELD,
                "index_seconds": round(t2 - t1, 1),
                "reference_seconds": round(ref_s, 1),
                "build_seconds": round(time.monotonic() - t0, 1)}
    with open(os.path.join(out, "manifest.json"), "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1)
    log(f"done: {manifest}")


def write_reference(out: str, corpus: corpus_gen.Corpus, queries: List[List[int]],
                    shards: int, operator: str, with_strata: bool = False) -> float:
    """Every query's reference top-k under `operator` → the directory's
    file for it (and `queries.npz`: the query set with, for the warm-up's
    strata, each query's postings on its heaviest shard) → its seconds."""
    t0 = time.monotonic()
    terms = sorted({t for q in queries for t in q})
    shard_indexes = reference.build_shard_indexes(
        corpus.flat, corpus.offsets, shards, terms)
    offsets, docs, scores, totals = [0], [], [], []
    for q in queries:
        total, d, s = reference.reference_topk(shard_indexes, q, REFERENCE_K, operator)
        totals.append(total)
        docs.append(d.astype(np.int32))
        scores.append(s)
        offsets.append(offsets[-1] + d.shape[0])
    # under a name np.savez leaves alone, then renamed: a file is whole or absent
    final = os.path.join(out, reference.stored_name(operator))
    np.savez(final + ".tmp.npz",
             offsets=np.asarray(offsets, dtype=np.int64),
             docs=np.concatenate(docs), scores=np.concatenate(scores),
             totals=np.asarray(totals, dtype=np.int64),
             k=np.asarray(REFERENCE_K))
    os.replace(final + ".tmp.npz", final)
    if with_strata:
        heaviest = [max(sum(sh.postings[t][0].shape[0] for t in q)
                        for sh in shard_indexes) for q in queries]
        np.savez(os.path.join(out, "queries.npz"),
                 offsets=np.cumsum([0] + [len(q) for q in queries]),
                 terms=np.concatenate([np.asarray(q, dtype=np.int64) for q in queries]),
                 postings=np.asarray(heaviest, dtype=np.int64))
    seconds = time.monotonic() - t0
    log(f"reference [{operator}] for {len(queries)} queries {seconds:.1f}s "
        f"({int(np.count_nonzero(totals))} with a hit)")
    return seconds


def add_reference(config: Dict[str, Any], out: str, operator: str) -> None:
    """`--reference-only`: the reference of `operator` beside an index
    that stands, from the seed and not from the node."""
    with open(os.path.join(out, "manifest.json"), "r", encoding="utf-8") as f:
        manifest = json.load(f)
    corpus = corpus_gen.generate_corpus(config["generator"])
    queries = corpus_gen.generate_queries(config["generator"])
    if (corpus.num_docs, int(corpus.flat.shape[0]), len(queries)) != (
            manifest["docs"], manifest["tokens"], manifest["queries"]):
        raise RuntimeError(f"[{out}] was not built from this configuration: "
                           f"its manifest says {manifest}")
    write_reference(out, corpus, queries, manifest["shards"], operator)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--reference-only", action="store_true",
                        help="index nothing: add the reference of --operator")
    parser.add_argument("--operator", default="or", choices=reference.OPERATORS)
    args = parser.parse_args()
    with open(args.config, "r", encoding="utf-8") as f:
        config = json.load(f)
    if args.reference_only:
        add_reference(config, args.out, args.operator)
    else:
        build(config, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
