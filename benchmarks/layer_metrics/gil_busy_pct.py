"""Thread CPU seconds of every Python thread of the serving path
(`rest_request`, `batcher.*`, `completer.*` stages) over the window, % of
its length. A stage that reads the CPU clock for a sample of its
occurrences (`cpu_count` of `count`) is scaled up to all of them. One
interpreter runs one thread at a time: were all of it under the GIL, near
100 would mean the interpreter, not the device, sets `qps`."""

PREFIXES = ("window.stages.rest_request.", "window.stages.batcher.",
            "window.stages.completer.")


def read(facts):
    total, found = 0.0, False
    for key, cpu in facts.items():
        if not (key.startswith(PREFIXES) and key.endswith(".cpu_seconds")):
            continue
        stage = key[:-len("cpu_seconds")]
        sampled = facts.get(stage + "cpu_count", 0.0)
        if sampled > 0:
            total += cpu * facts.get(stage + "count", sampled) / sampled
            found = True
    if not found or not facts.get("gen.window_s"):
        return None
    return 100.0 * total / facts["gen.window_s"]
