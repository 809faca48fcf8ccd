"""Record the small device trace that the trace-reduction test reads.

    chiprun -- python benchmarks/tools/record_small_trace.py

Runs a tiny jitted program a few times with sleeps between, under the
profiler, and leaves the `.xplane.pb` in `chiprun_out/small_trace/` with
a description of its planes. Needs the chip; the committed copy lives in
`benchmarks/testdata/`.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from esbench import tracered

    out = os.path.join("chiprun_out", "small_trace")
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def step(x):
        return jnp.sort(x @ x, axis=-1)[:, -8:]

    x = jnp.ones((512, 512), jnp.float32)
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(5):
        step(x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    path = tracered.newest_xplane(out)
    with open(os.path.join(out, "planes.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(tracered.describe(path)))
    reduced = tracered.reduce_trace(path)
    print(path, os.path.getsize(path), {k: reduced[k] for k in ("busy_s", "window_s")}
          if reduced else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
