"""Tests of the yardstick. Run on the CPU, apart from the repo's own
tier-1 tests (`pytest tests/` does not collect this directory):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)


def rehearsal_configs() -> dict:
    """{committee kind: its rehearsal configuration}, found by the
    `committee` each `configs/rehearsal-*.json` names (the default kind
    where it names none): a later PR's kind brings its own as a file."""
    import committees

    found = {}
    for name in sorted(os.listdir(os.path.join(BENCH_DIR, "configs"))):
        if name.startswith("rehearsal-"):
            with open(os.path.join(BENCH_DIR, "configs", name)) as f:
                config = json.load(f)
            found.setdefault(
                config.get("committee", committees.DEFAULT), config
            )
    return found
