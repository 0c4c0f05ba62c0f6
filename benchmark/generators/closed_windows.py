"""Generator kind `closed_windows`: one catching-up node, one window of
commits outstanding.

Each request is the call blocksync makes for a window,
`ValidatorSet.verify_commits_light(chain_id, entries, verifier=
remote.classed(<class>))`, over `window_commits` fresh commits of the
configuration's validator set; the next window goes out when the
verdicts of the last came back. Bad rows as `fixtures.plan_window`
plants them. The pool of signed commits is made during set-up, sized
`pool_commits_per_s` x seconds; if the program runs it dry the timed
window ends there, and the rate is still all work over all time.

The timed window starts at a window's submission and ends at the
completion of the first window that finishes after `seconds`.

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


def plan(traffic: dict, n: int, seed: int, seconds: float,
         first_height: int = 1) -> dict:
    """Units of (height, bad-row plan), one unit per window: the warm-up
    windows, then the pool."""
    w = int(traffic["window_commits"])
    warm = int(traffic["warm_windows"])
    pool = max(2, math.ceil(traffic["pool_commits_per_s"] * seconds / w))
    units = [
        [
            (first_height + k * w + c, p)
            for c, p in enumerate(fixtures.plan_window(seed, k, w, n))
        ]
        for k in range(warm + pool)
    ]
    return {"warm": units[:warm], "pool": units[warm:]}


def window_closed(elapsed: float, seconds: float) -> bool:
    """The end rule: the first window that finishes after `seconds`
    closes the timed window, so none is counted in part or dropped."""
    return elapsed >= seconds


class _Spy:
    """The classed verifier, with the benchmark's span around its
    `verify`: what the client spends around it is its own gather and
    tally, and the row bitmap it returns is what `correct` compares."""

    def __init__(self, inner):
        self.inner = inner
        self.last = None

    def verify(self, items):
        t0 = time.perf_counter()
        ok = self.inner.verify(items)
        self.last = (time.perf_counter() - t0, np.asarray(ok, dtype=bool))
        return ok


class Session:
    def __init__(self, traffic: dict, committee, remote, objects):
        self.traffic = traffic
        self.committee = committee
        self.vs = objects.validator_set(committee)
        self.objects = objects
        self.spy = _Spy(remote.classed(traffic["class"]))

    def load(self, units: list) -> list:
        """Commit records -> what one request submits."""
        return [
            [self.objects.entry(self.committee, rec) for rec in unit]
            for unit in units
        ]

    async def request(self, entries: list) -> dict:
        """One window through the program; never raises."""
        loop = asyncio.get_running_loop()
        self.spy.last = None
        t0 = time.perf_counter()
        try:
            verdicts = await loop.run_in_executor(
                None,
                lambda: self.vs.verify_commits_light(
                    fixtures.CHAIN_ID, entries, verifier=self.spy
                ),
            )
            error = None
        except Exception as e:  # a degrade raises out of the tripwire
            verdicts, error = None, repr(e)
        t1 = time.perf_counter()
        inner_s, bits = self.spy.last or (0.0, None)
        return {
            "t_due": t0, "t_sent": t0, "t_done": t1, "error": error,
            "inner_s": inner_s, "verdicts": verdicts, "bits": bits,
        }

    async def drive(self, requests: list, seconds: float, tracer) -> dict:
        trace_from = seconds - float(self.traffic["trace_seconds"])
        tracing = False
        done = []
        t_start = time.perf_counter()
        for entries in requests:
            if tracer and not tracing and (
                time.perf_counter() - t_start >= trace_from
            ):
                await tracer.start()
                tracing = True
            r = await self.request(entries)
            r["traced"] = tracing
            done.append(r)
            if window_closed(r["t_done"] - t_start, seconds):
                break
        t_end = done[-1]["t_done"]
        if tracing:
            await tracer.stop()
        return {"t_start": t_start, "t_end": t_end, "requests": done}
