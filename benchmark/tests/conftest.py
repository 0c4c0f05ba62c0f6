"""Tests of the yardstick. Run on the CPU, apart from the repo's own
tier-1 tests (`pytest tests/` does not collect this directory):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)
