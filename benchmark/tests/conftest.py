"""Tests of the benchmark's own code. They sit outside tier-1's `tests/`
(run them with `python -m pytest benchmark/tests -q`, PERF.md §7) and
import nothing of the program; the rehearsals start it as a child."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
