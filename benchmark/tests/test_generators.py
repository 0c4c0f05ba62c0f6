"""The two generator kinds: what a seed fixes, and the window's end."""

import json
import os

import pytest

from generators import closed_windows, open_arrivals
from harness import fixtures
from harness.cell import BENCH_DIR


def traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_end_rule_counts_no_partial_window():
    assert not closed_windows.window_closed(39.99, 40.0)
    assert closed_windows.window_closed(40.0, 40.0)
    assert closed_windows.window_closed(42.3, 40.0)


def test_no_height_twice_and_pool_sized_from_seconds():
    t = traffic("catchup-w64")
    units = closed_windows.plan(t, 128, 5, 10.0)
    heights = [h for u in units["warm"] + units["pool"] for h, _ in u]
    assert len(heights) == len(set(heights))
    assert all(len(u) == 64 for u in units["pool"])
    assert len(units["pool"]) == -(-int(t["pool_commits_per_s"] * 10) // 64)


@pytest.mark.parametrize("n", [8, 128, 1024])
def test_window_plan_is_build_windows(n):
    plans = fixtures.plan_window(9, 3, 64, n)
    assert plans == fixtures.plan_window(9, 3, 64, n)
    sizes = sorted(len(p) for p in plans if p)
    assert sizes[-1] == n // 3 + 1  # one commit loses its quorum
    assert sum(sizes[:-1]) == 4  # four bad rows elsewhere
    kept = n - (2 * n // 3 + 1)
    assert all(s <= kept for s in sizes[:-1])


def test_schedule_is_fixed_by_the_seed():
    t = dict(traffic("live-commit"), rate_per_s=50)
    a = open_arrivals.schedule(t, 7, 500)
    assert a == open_arrivals.schedule(t, 7, 500)
    assert a != open_arrivals.schedule(t, 8, 500)
    assert a == sorted(a) and len(a) == 500
    # every seed deals out the same jitters, in another order
    gaps = lambda due: sorted(  # noqa: E731
        round(d * 50 - i, 9) for i, d in enumerate(sorted(
            due, key=lambda d: round(d * 50)))
    )
    t0 = dict(t, jitter=0.4)
    assert gaps(open_arrivals.schedule(t0, 7, 500)) == gaps(
        open_arrivals.schedule(t0, 8, 500)
    )
    assert abs(a[-1] - 499 / 50) <= 0.5 / 50
    units = open_arrivals.plan(t, 128, 7, 10.0)
    assert len(units["pool"]) == 500
    bad = [i for i, u in enumerate(units["pool"]) if u[0][1]]
    assert bad == list(range(15, 500, 16))
    assert all(sorted(u[0][1].values()) == sorted(fixtures.BAD_KINDS)
               for i, u in enumerate(units["pool"]) if i in bad)
