"""The records held to the files: every bound in BENCHMARK.json is the
one PERF.md section 2's table gives."""

import json
import os
import re

from conftest import ROOT


def perf_section(number: int) -> str:
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    start = text.index(f"\n## {number}. ")
    end = text.find("\n## ", start + 1)
    return text[start : end if end > 0 else None]


def test_every_bound_is_the_one_perf_md_gives():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rows = {}
    for line in perf_section(2).splitlines():
        m = re.match(r"\| `([\w.\-]+)` \|.*\| ([0-9.]+) \|$", line)
        if m:
            rows[m.group(1)] = float(m.group(2))
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert rows == bounds
