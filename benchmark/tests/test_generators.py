"""The two generator kinds: what a seed fixes, and the window's end."""

import asyncio
import json
import math
import os
import types

import numpy as np
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


class _Tracer:
    """A stub tracer: where in the pool it was started and stopped."""

    def __init__(self, done):
        self.done, self.words = done, []

    async def start(self):
        self.words.append(("start", len(self.done)))

    async def stop(self):
        self.words.append(("stop", len(self.done)))


def drive(monkeypatch, windows, window_s, seconds=30.0, trace_seconds=3.0):
    """A Session whose `request` is a stub on a stepped clock: each
    window takes `window_s` and nothing else takes any time."""
    clock = [0.0]
    monkeypatch.setattr(
        closed_windows, "time",
        types.SimpleNamespace(perf_counter=lambda: clock[0]),
    )
    stub = types.SimpleNamespace(
        validator_set=lambda committee: None, classed=lambda name: None
    )
    session = closed_windows.Session(
        {"class": "blocksync", "trace_seconds": trace_seconds},
        None, stub, stub,
    )
    served = []

    async def request(entries):
        clock[0] = (len(served) + 1) * window_s
        served.append(entries)
        return {"t_done": clock[0]}

    session.request = request
    tracer = _Tracer(served)
    out = asyncio.run(session.drive(list(range(windows)), seconds, tracer))
    return out["requests"], tracer.words


@pytest.mark.parametrize(
    "windows,window_s", [(188, 0.187), (25, 1.34)],
    ids=["c128.catchup", "c1024.catchup"],
)
def test_a_pool_that_outlasts_the_window_is_traced_from_the_clock(
    monkeypatch, windows, window_s
):
    """The accepted cells' shapes: the request that opens the traced
    span is the first that starts 3 s or less before `seconds`, as
    under the rule that read the clock alone."""
    done, words = drive(monkeypatch, windows, window_s)
    clock_alone = math.ceil(27.0 / window_s)
    assert len(done) < windows  # the clock closed the window, not the pool
    assert words == [("start", clock_alone), ("stop", len(done))]
    assert [r["traced"] for r in done] == (
        [False] * clock_alone + [True] * (len(done) - clock_alone)
    )


@pytest.mark.parametrize(
    "windows,dry_at", [(188, 25.0), (188, 10.0), (25, 22.1)],
    ids=["dry-at-25s", "dry-at-10s", "c1024-dry-at-22s"],
)
def test_a_pool_that_runs_dry_is_traced_over_its_last_seconds(
    monkeypatch, windows, dry_at
):
    window_s = dry_at / windows
    done, words = drive(monkeypatch, windows, window_s)
    assert len(done) == windows and done[-1]["t_done"] < 30.0
    assert [w for w, _ in words] == ["start", "stop"]
    assert words[1] == ("stop", windows)
    traced = [r["traced"] for r in done]
    assert traced[-1] and traced == sorted(traced)  # one span, at the end
    assert 3.0 - window_s < sum(traced) * window_s <= 3.0


def test_a_two_window_pool_has_its_last_window_traced(monkeypatch):
    done, words = drive(monkeypatch, 2, 0.5)
    assert [r["traced"] for r in done] == [False, True]
    assert words == [("start", 1), ("stop", 2)]


def test_the_spy_keeps_every_call_of_a_window(monkeypatch):
    """A window submitted in four chunks: the bitmaps joined in call
    order and the seconds summed; the next window starts empty."""
    clock = iter(range(100))
    monkeypatch.setattr(
        closed_windows, "time",
        types.SimpleNamespace(perf_counter=lambda: next(clock) * 0.25),
    )
    inner = types.SimpleNamespace(verify=lambda items: [i > 0 for i in items])
    spy = closed_windows._Spy(inner)
    chunks = [[1, 0], [0, 0, 1], [1], [0, 1]]
    assert [spy.verify(c) for c in chunks] == [
        [i > 0 for i in c] for c in chunks
    ]
    inner_s, bits = spy.take()
    assert inner_s == 4 * 0.25
    assert bits.dtype == np.bool_
    assert bits.tolist() == [i > 0 for c in chunks for i in c]
    assert spy.take() == (0.0, None)
    spy.verify([1])
    assert spy.take()[1].tolist() == [True]


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
