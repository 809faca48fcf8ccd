"""The benchmark's own code: generator, reference, load, trace reduction.

Nothing here is imported by the program, and only `run.py` and
`build_index.py` import the program. `corpus`, `traffic`, `loadgen`,
`window`, `reference` and `compare` are stdlib + numpy only."""
