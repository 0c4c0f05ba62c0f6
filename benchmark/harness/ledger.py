"""Deltas of two `GET /dump_dispatch_ledger` summaries taken around the
timed window: the ledger's totals are cumulative and never cap.

`submissions` counts a submission once in every round that carries a
slice of it, so a 65,536-row window cut into four rounds counts four.
"""

from __future__ import annotations

KEYS = (
    "rounds", "rows_requested", "rows_dispatched",
    "device_seconds", "queue_wait_seconds", "host_prep_seconds",
)


def _submissions(summary: dict) -> int:
    return summary.get("per_engine", {}).get("sig", {}).get("submissions", 0)


def delta(before: dict, after: dict) -> dict:
    out = {k: after[k] - before[k] for k in KEYS}
    out["submissions"] = _submissions(after) - _submissions(before)
    return out
