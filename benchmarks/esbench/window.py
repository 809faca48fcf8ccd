"""Window arithmetic on a stream of completions. stdlib + numpy only.

Traffic starts before t0 and runs on past t1; the window is cut out of
the running stream afterwards. A rate counts the completions whose
timestamp lies in [t0, t1) over t1 - t0, whatever happened inside (a
stall inside the window is counted: it completes nothing). A latency
belongs to the window when the request was *due* in it, and runs from due
time to completion, so the wait a stall imposes on later arrivals counts.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def completed_per_s(done_ns: np.ndarray, ok: np.ndarray, t0_ns: int,
                    t1_ns: int) -> float:
    inside = (done_ns >= t0_ns) & (done_ns < t1_ns) & ok
    return float(inside.sum()) / ((t1_ns - t0_ns) / 1e9)


def due_in_window(due_ns: np.ndarray, t0_ns: int, t1_ns: int) -> np.ndarray:
    return (due_ns >= t0_ns) & (due_ns < t1_ns)


def latencies_ms(due_ns: np.ndarray, done_ns: np.ndarray, ok: np.ndarray,
                 t0_ns: int, t1_ns: int) -> np.ndarray:
    """Latencies of the successful requests due in the window."""
    sel = due_in_window(due_ns, t0_ns, t1_ns) & ok
    return (done_ns[sel] - due_ns[sel]) / 1e6


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    if values.shape[0] == 0:
        return None
    return float(np.percentile(values, q))


def attempted_failed(due_ns: np.ndarray, done_ns: np.ndarray, ok: np.ndarray,
                     t0_ns: int, t1_ns: int, loop: str) -> Dict[str, int]:
    """Requests that belong to the window: completed in it (closed loop)
    or due in it (open loop); `failed` are those not answered 200 whole."""
    if loop == "open":
        sel = due_in_window(due_ns, t0_ns, t1_ns)
    else:
        sel = (done_ns >= t0_ns) & (done_ns < t1_ns)
    return {"attempted": int(sel.sum()), "failed": int((sel & ~ok).sum())}
