"""Wall less thread CPU seconds of the three `prep.*` stages (the launch
thread's routing, query operands and rescore-term operands, nested in
`batcher.prep`) per train: the launch thread waiting for the GIL or a
core inside the work it must do. Nothing when the program records none
of the three (a parent commit)."""

STAGES = ("prep.route", "prep.query_batch", "prep.term_ranges")


def read(facts):
    off, found = 0.0, False
    for name in STAGES:
        key = f"window.stages.{name}."
        if key + "cpu_seconds" in facts:
            off += facts.get(key + "seconds", 0.0) - facts[key + "cpu_seconds"]
            found = True
    if not found or not facts.get("window.batches"):
        return None
    return 1000.0 * off / facts["window.batches"]
