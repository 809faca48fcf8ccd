"""Per-layer metrics: one small reader per metric, found by its name.

`layer_metrics/<name>.json` or `.py`, where `<name>` is the metric's name
in BENCHMARK.json or, failing that, the name without its last `.suffix`
(`lower_ms_per_q.closed` and `.open` share `lower_ms_per_q.json`). A
reader is fed the run's facts, a flat dict of numbers with dotted keys:

  window.<path>   `/_tpu/stats` after the window minus before it
  traced.<path>   the same over the traced part of the window
  after.<path>    `/_tpu/stats` after the window (gauges)
  gen.*           the load generator's own clocks and CPU seconds
  trace.*         the reduced profiler trace
  setup.*  device.*   harness clocks, jax.monitoring, memory_stats()

A `.json` reader is `scale · Σ num / Σ den` over fact keys (`den` may be
left out); a `.py` reader is `read(facts) -> float | None`. A reader that
finds none of its `num` keys, or a zero `den`, returns nothing and the
metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, Optional

READERS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layer_metrics")


def flatten(tree: Any, prefix: str, out: Dict[str, float]) -> Dict[str, float]:
    """Numeric leaves of a JSON tree → `prefix.path.to.leaf` keys."""
    if isinstance(tree, dict):
        for key, value in tree.items():
            flatten(value, f"{prefix}.{key}", out)
    elif isinstance(tree, bool):
        out[prefix] = float(tree)
    elif isinstance(tree, (int, float)):
        out[prefix] = float(tree)
    return out


def difference(after: Dict[str, float], before: Dict[str, float], old: str,
               new: str) -> Dict[str, float]:
    """Keys `old.*` of two flattened snapshots → `new.*` differences."""
    return {new + key[len(old):]: value - before.get(key, 0.0)
            for key, value in after.items() if key.startswith(old + ".")}


def _json_reader(spec: Dict[str, Any]) -> Callable[[Dict[str, float]], Optional[float]]:
    def read(facts: Dict[str, float]) -> Optional[float]:
        found = [facts[key] for key in spec["num"] if key in facts]
        if not found:
            return None
        den = 1.0
        if "den" in spec:
            den = sum(facts.get(key, 0.0) for key in spec["den"])
            if den == 0:
                return None
        return float(spec.get("scale", 1.0)) * sum(found) / den
    return read


def find_reader(name: str, readers_dir: str = READERS_DIR
                ) -> Optional[Callable[[Dict[str, float]], Optional[float]]]:
    bases = [name] + ([name.rsplit(".", 1)[0]] if "." in name else [])
    for base in bases:
        path = os.path.join(readers_dir, base + ".json")
        if os.path.isfile(path):
            with open(path, "r", encoding="utf-8") as f:
                return _json_reader(json.load(f))
        path = os.path.join(readers_dir, base + ".py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                "layer_metric_" + base.replace(".", "_").replace("-", "_"), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    return None
