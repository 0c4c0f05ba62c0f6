"""The two generator kinds: what a seed fixes, and the window's end."""

import asyncio
import hashlib
import json
import math
import os
import struct
import types

import numpy as np
import pytest

import committees
from conftest import rehearsal_configs
from generators import closed_windows, open_arrivals
from harness import fixtures
from harness.cell import BENCH_DIR

MIXED = rehearsal_configs()["mixed_keys"]


def committee_of(n, seed=5, config=None):
    config = config or {"validators": n}
    return committees.load(config).Committee(seed, config)


def traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_end_rule_counts_no_partial_window():
    assert not closed_windows.window_closed(39.99, 40.0)
    assert closed_windows.window_closed(40.0, 40.0)
    assert closed_windows.window_closed(42.3, 40.0)


def test_no_height_twice_and_pool_sized_from_seconds():
    t = traffic("catchup-w64")
    units = closed_windows.plan(t, committee_of(128), 5, 10.0)
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
    stub = types.SimpleNamespace(classed=lambda name: None)
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


def test_a_window_is_verified_against_the_set_of_its_first_height():
    """`load` asks the committee for the validators at each window's
    first height and builds the program's set once for every tuple it
    is handed."""
    built = []
    objects = types.SimpleNamespace(
        validator_set=lambda validators: built.append(validators) or
        ("set of", validators),
        entry=lambda committee, rec: ("entry", rec[0]),
    )
    old, new = ("a", "b"), ("b", "c")
    committee = types.SimpleNamespace(
        validators=lambda height: old if height < 5 else new
    )
    session = closed_windows.Session(
        {"class": "blocksync"}, committee,
        types.SimpleNamespace(classed=lambda name: None), objects,
    )
    units = [[(h, [], {}), (h + 1, [], {})] for h in (1, 3, 5, 7)]
    loaded = session.load(units)
    assert built == [old, new]
    assert [vs for vs, _ in loaded] == [
        ("set of", old), ("set of", old), ("set of", new), ("set of", new)
    ]
    assert loaded[2][1] == [("entry", 5), ("entry", 6)]


@pytest.mark.parametrize("n", [8, 128, 1024])
def test_window_plan_is_build_windows(n):
    committee = committee_of(n)
    heights = list(range(1, 65))
    plans = fixtures.plan_window(committee, 9, 3, heights)
    assert plans == fixtures.plan_window(committee, 9, 3, heights)
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
    committee = committee_of(128)
    units = open_arrivals.plan(t, committee, 7, 10.0)
    assert len(units["pool"]) == 500
    bad = [i for i, u in enumerate(units["pool"]) if u[0][1]]
    assert bad == list(range(15, 500, 16))
    assert all(sorted(u[0][1].values()) == sorted(committee.bad_kinds(1, 0))
               for i, u in enumerate(units["pool"]) if i in bad)


# SHA-256 over the due times as little-endian doubles, computed on the
# parent commit (c7235fd) before the generator learnt to keep its own
# schedule: `c128.live`'s and `c1024.live`'s schedules of 30 s
PARENT_SCHEDULES = {
    (56, 1680, 7):
        "1fc586d5d37f87e73cd8c454fec55f32f2bca7250b55e09f800f1418b34c8b65",
    (12.8, 384, 2147483999):
        "0b041e7cc3e15fc91b1af25ac447b350beed7b3f9c5f714662164d6279965bd3",
}


@pytest.mark.parametrize("rate,count,seed", sorted(PARENT_SCHEDULES))
def test_the_due_times_of_a_seed_are_the_parents_byte_for_byte(
    rate, count, seed
):
    t = dict(traffic("live-commit"), rate_per_s=rate)
    due = open_arrivals.schedule(t, seed, count)
    assert hashlib.sha256(
        struct.pack("<%dd" % count, *due)
    ).hexdigest() == PARENT_SCHEDULES[rate, count, seed]


def test_the_open_loop_keeps_its_own_schedule():
    """A request is due at the scheduled instant and goes out within
    half a millisecond of it: the generator does not leave the last
    stretch to a timer, and a slow answer delays no later request."""
    t = dict(traffic("live-commit"), rate_per_s=100, trace_seconds=0)

    class Remote:
        async def submit(self, items, cls):
            await asyncio.sleep(0.015)  # longer than an interval
            return [True] * len(items)

    session = open_arrivals.Session(
        t, types.SimpleNamespace(seed=7), Remote(), None
    )
    out = asyncio.run(session.drive([[0]] * 200, 2.0, None))
    due = open_arrivals.schedule(t, 7, 200)
    rs = out["requests"]
    assert [r["t_due"] - out["t_start"] for r in rs] == pytest.approx(
        due, abs=1e-9
    )
    late = sorted(r["t_sent"] - r["t_due"] for r in rs)
    assert late[0] >= 0.0
    # a timer alone is late by a millisecond at the median; this box is
    # shared, so the test reads the 80th percentile and not the tail
    assert late[len(late) // 2] < 0.0002
    assert late[int(0.8 * len(late)) - 1] < 0.0005
    assert all(r["error"] is None and len(r["bits"]) == 1 for r in rs)


def test_the_planners_ask_the_kind_which_kinds_a_row_has():
    """In a mixed committee a row's bad kind is one its validator's key
    type has, dealt out in the same turn as in an all-ed25519 one."""
    mixed = committee_of(8, 5, MIXED)
    plain = committee_of(8, 5)
    heights = [1, 2, 3, 4]
    types_ = [v.key_type for v in mixed.validators(1)]
    turn = {"s_ge_L": "high_s"}
    for window in range(6):
        a = fixtures.plan_window(plain, 5, window, heights)
        b = fixtures.plan_window(mixed, 5, window, heights)
        assert [sorted(p) for p in a] == [sorted(p) for p in b]
        for pa, pb in zip(a, b):
            for row, kind in pa.items():
                secp = types_[row] == "secp256k1"
                assert pb[row] == (turn.get(kind, kind) if secp else kind)
    request = fixtures.plan_request(mixed, 5, 3, 9, 4)
    assert sorted(request) == sorted(fixtures.plan_request(plain, 5, 3, 9, 4))
    assert all(
        kind in mixed.bad_kinds(9, row) for row, kind in request.items()
    )
    assert fixtures.plan_request(mixed, 5, 2, 9, 4) == {}
