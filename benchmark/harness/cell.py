"""Finding a cell's files by the names in BENCHMARK.json.

    configs/<config>.json     the deployment, as run; `committee` names
                              committees/<kind>.py (or the default kind)
    traffic/<traffic>.json    the mix's parameters; `generator` names
                              generators/<kind>.py
    cells/<cell>.json         optional: this cell's own values for keys
                              of the mix (its offered rate, its pool)
    metrics/<metric>.json     a per-layer metric: `reader` names
                              readers/<reader>.py, the rest are the
                              reader's parameters

Nothing here knows a cell, a mix, a metric or a committee kind by name.
"""

from __future__ import annotations

import importlib
import json
import os

import committees

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


class Cell:
    def __init__(self, name: str, bench_file: str | None = None):
        """`bench_file` is the tests' seam: a file of rehearsal
        `workloads`, each reporting the metrics of the cell it is
        `like`; its data files are still under benchmark/."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        if bench_file:
            with open(bench_file) as f:
                bench["workloads"] = json.load(f)["workloads"]
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json "
                f"(has: {', '.join(sorted(entries))})"
            )
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = _load("configs", self.entry["config"] + ".json")
        self.traffic = _load("traffic", self.entry["traffic"] + ".json")
        own = os.path.join(BENCH_DIR, "cells", name + ".json")
        if os.path.exists(own):
            self.traffic.update(_load("cells", name + ".json"))
        like = self.entry.get("like", name)
        self.end_to_end = [
            m for m in bench["end_to_end"] if _lists(m, like)
        ]
        self.per_layer = [m for m in bench["per_layer"] if _lists(m, like)]

    def generator(self):
        return importlib.import_module(
            "generators." + self.traffic["generator"]
        )

    def committee_kind(self):
        """The module that says what this configuration's validators
        are, how they sign and what the reference answers."""
        return committees.load(self.config)


def _lists(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metric_reader(name: str):
    """(read function, parameters) of one per-layer metric."""
    spec = _load("metrics", name + ".json")
    module = importlib.import_module("readers." + spec["reader"])
    return module.read, spec
