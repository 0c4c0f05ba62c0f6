"""The ledger-delta arithmetic and the readers over it."""

import pytest

from harness import ledger
from harness.stats import percentile
from readers import latency_percentile, ledger_ratio, window_rate


def summary(rounds, req, disp, dev, wait, prep, subs):
    return {
        "rounds": rounds, "rows_requested": req, "rows_dispatched": disp,
        "device_seconds": dev, "queue_wait_seconds": wait,
        "host_prep_seconds": prep,
        "per_engine": {"sig": {"submissions": subs}},
    }


def test_delta_and_ratios():
    before = summary(3, 1000, 2048, 1.0, 0.5, 0.25, 3)
    after = summary(7, 1000 + 4 * 128, 2048 + 3 * 128 + 256, 1.4, 0.9, 0.45, 8)
    d = ledger.delta(before, after)
    assert d["rounds"] == 4 and d["submissions"] == 5
    ctx = {"ledger": d}
    fill = {"numerator": "rows_requested", "denominator": "rows_dispatched",
            "scale": 100}
    assert ledger_ratio.read(ctx, fill) == pytest.approx(100 * 512 / 640)
    wall = {"numerator": "device_seconds", "denominator": "rounds",
            "scale": 1000}
    assert ledger_ratio.read(ctx, wall) == pytest.approx(100.0)
    ctx["ledger"] = ledger.delta(before, before)
    assert ledger_ratio.read(ctx, wall) is None


def test_nearest_rank_percentile():
    values = list(range(1, 101))
    assert percentile(values, 95) == 95
    assert percentile(values, 50) == 50
    assert percentile([7.0], 95) == 7.0


def test_rate_is_all_work_over_the_whole_window():
    ctx = {
        "window": {"t_start": 10.0, "t_end": 14.0},
        "requests": [
            {"commits": 64, "failed": False},
            {"commits": 64, "failed": True},
            {"commits": 64, "failed": False},
        ],
    }
    assert window_rate.read(ctx, {"count": "commits"}) == 32.0


def test_a_failed_request_misses_the_tail():
    ok = {"failed": False, "t_due": 0.0, "t_done": 0.010}
    ctx = {"requests": [ok] * 9 + [dict(ok, failed=True)]}
    assert latency_percentile.read(ctx, {"percentile": 50}) == pytest.approx(10)
    assert latency_percentile.read(ctx, {"percentile": 95}) == (
        latency_percentile.MISS_MS
    )
