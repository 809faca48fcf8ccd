"""The benchmark's own tests of what a cell's `correct` rests on, under
tier-1 (ISSUE 36; ROADMAP D11): the configurations' query laws, the plain
reference under `or` and `and` against brute-force dense BM25, the stored
reference of a traffic file's operator, a warm-up stratum between two
edges, and `operator: and` over REST against the AND reference on a node
with default settings (a compressed pack, where
`tests/test_msmarco_and_path.py` runs the deployment's raw one).

The cases are `benchmarks/tests/test_query_laws.py`'s, all 35, each still
a case: that file is the benchmark's and a program PR may not edit it, so
this module collects its tests and fixtures by name instead of keeping a
second copy that could drift from the yardstick.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests"))

pytest.register_assert_rewrite("test_query_laws")

from test_query_laws import *  # noqa: E402,F401,F403
