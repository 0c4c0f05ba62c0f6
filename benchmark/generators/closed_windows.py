"""Generator kind `closed_windows`: one catching-up node, one window of
commits outstanding.

Each request is the call blocksync makes for a window,
`ValidatorSet.verify_commits_light(chain_id, entries, verifier=
remote.classed(<class>))`, over `window_commits` fresh commits of the
configuration's validator set (the committee kind's, at the window's
first height); the next window goes out when the verdicts of the last
came back. Bad rows as `fixtures.plan_window` plants them. The pool
of signed commits is made during set-up, sized
`pool_commits_per_s` x seconds; if the program runs it dry the timed
window ends there, and the rate is still all work over all time.

The timed window starts at a window's submission and ends at the
completion of the first window that finishes after `seconds`, or of
the pool's last. The traced span is the last `trace_seconds` of the
timed window, wherever that ends (`trace_due`): it opens at the first
request boundary `trace_seconds` before `seconds`, or, where the pool
will be dry sooner, `trace_seconds` before that by the pace so far.

Parameters (traffic file, a cell's own file over it): window_commits,
class, warm_windows, pool_commits_per_s, trace_seconds.
"""

from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from harness import fixtures

LOOP = "closed"


def plan(traffic: dict, committee, seed: int, seconds: float,
         first_height: int = 1) -> dict:
    """Units of (height, bad-row plan), one unit per window: the warm-up
    windows, then the pool."""
    w = int(traffic["window_commits"])
    warm = int(traffic["warm_windows"])
    pool = max(2, math.ceil(traffic["pool_commits_per_s"] * seconds / w))
    units = []
    for k in range(warm + pool):
        heights = [first_height + k * w + c for c in range(w)]
        units.append(list(zip(
            heights, fixtures.plan_window(committee, seed, k, heights)
        )))
    return {"warm": units[:warm], "pool": units[warm:]}


def window_closed(elapsed: float, seconds: float) -> bool:
    """The end rule: the first window that finishes after `seconds`
    closes the timed window, so none is counted in part or dropped."""
    return elapsed >= seconds


def trace_due(elapsed: float, done: int, pool: int, seconds: float,
              trace_seconds: float) -> bool:
    """Whether the traced span opens before the next request: the clock
    is `trace_seconds` short of `seconds`; or the windows left, at the
    mean pace of those done, take `trace_seconds` or less; or only the
    pool's last window is left, so that a traced run holds one."""
    left = pool - done
    return (
        elapsed >= seconds - trace_seconds
        or left <= 1
        or (done >= 1 and left * elapsed / done <= trace_seconds)
    )


class _Spy:
    """The classed verifier, with the benchmark's span around its
    `verify`: what the client spends around it is its own gather and
    tally, and the row bitmap it returns is what `correct` compares.
    Every call of a window is kept: the bitmaps joined in call order,
    the seconds summed. A client that submits a window's chunks out of
    row order fails `rows_wrong`."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list = []

    def verify(self, items):
        t0 = time.perf_counter()
        ok = self.inner.verify(items)
        self.calls.append(
            (time.perf_counter() - t0, np.asarray(ok, dtype=bool))
        )
        return ok

    def take(self) -> tuple:
        """(seconds inside `verify`, the window's bitmap or None), and
        the next window starts empty."""
        calls, self.calls = self.calls, []
        if not calls:
            return 0.0, None
        return (
            sum(s for s, _ in calls),
            np.concatenate([bits for _, bits in calls]),
        )


class Session:
    def __init__(self, traffic: dict, committee, remote, objects):
        self.traffic = traffic
        self.committee = committee
        self.objects = objects
        self.sets: dict = {}
        self.spy = _Spy(remote.classed(traffic["class"]))

    def validator_set(self, height: int):
        """The program's validator set at a height, built once for
        every tuple of validators the committee hands out."""
        validators = self.committee.validators(height)
        if validators not in self.sets:
            self.sets[validators] = self.objects.validator_set(validators)
        return self.sets[validators]

    def load(self, units: list) -> list:
        """Commit records -> what one request submits: the validator
        set of the window's first height, and its entries."""
        return [
            (
                self.validator_set(unit[0][0]),
                [self.objects.entry(self.committee, rec) for rec in unit],
            )
            for unit in units
        ]

    async def request(self, window: tuple) -> dict:
        """One window through the program; never raises."""
        vs, entries = window
        loop = asyncio.get_running_loop()
        t0 = time.perf_counter()
        try:
            verdicts = await loop.run_in_executor(
                None,
                lambda: vs.verify_commits_light(
                    fixtures.CHAIN_ID, entries, verifier=self.spy
                ),
            )
            error = None
        except Exception as e:  # a degrade raises out of the tripwire
            verdicts, error = None, repr(e)
        t1 = time.perf_counter()
        inner_s, bits = self.spy.take()
        return {
            "t_due": t0, "t_sent": t0, "t_done": t1, "error": error,
            "inner_s": inner_s, "verdicts": verdicts, "bits": bits,
        }

    async def drive(self, requests: list, seconds: float, tracer) -> dict:
        trace_seconds = float(self.traffic["trace_seconds"])
        tracing = False
        done = []
        t_start = time.perf_counter()
        for window in requests:
            if tracer and not tracing and trace_due(
                time.perf_counter() - t_start, len(done), len(requests),
                seconds, trace_seconds,
            ):
                await tracer.start()
                tracing = True
            r = await self.request(window)
            r["traced"] = tracing
            done.append(r)
            if window_closed(r["t_done"] - t_start, seconds):
                break
        t_end = done[-1]["t_done"]
        if tracing:
            await tracer.stop()
        return {"t_start": t_start, "t_end": t_end, "requests": done}
