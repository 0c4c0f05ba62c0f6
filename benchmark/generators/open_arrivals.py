"""Generator kind `open_arrivals`: one connection, requests on a
schedule whatever the system does.

Request i is due at (i + j) / rate_per_s, with j one of `count` evenly
spaced values in +-jitter: every seed deals out the same set of
jitters, in another order, so no seed has an easier schedule than
another. A request is one fresh commit's rows as one
`remote.submit(rows, <class>)`, the submission a node makes when a
block's LastCommit or a batched vote chunk arrives. Every
`bad_every`-th request carries one bad row of each kind. Latency runs
from the instant a request was DUE, so a stall is charged to every
request it delays, and the generator's own lateness is reported beside
it. The schedule is fixed by the seed: rate_per_s x seconds requests,
all sent, all waited for (a minute past the close if need be). The
generator keeps that schedule itself: it sleeps to `SPIN_S` short of a
due time and then yields to the loop, turn by turn, until the instant
has come, so replies are served while it waits and a timer's overshoot
is not charged to the request as latency.

Parameters (traffic file, a cell's own file over it): rate_per_s,
jitter, bad_every, class, warm_commits (rows of that many commits as
one warm-up request, once for each entry), trace_seconds.
"""

from __future__ import annotations

import asyncio
import random
import time

import numpy as np

from harness import fixtures

LOOP = "open"
LATE_ANSWER_S = 60.0
# a timer wakes late: on the chip's host by 1.3 ms at the median, 2.4 at
# the 95th percentile and 4.3 at the most in 1,680 (PR 34), so sleeping
# stops this far short of a due time
SPIN_S = 0.005


def plan(traffic: dict, committee, seed: int, seconds: float,
         first_height: int = 1) -> dict:
    """One unit per request. A warm-up unit is several commits whose
    rows go out as ONE submission, to load the program a coalesced
    round of that size would use."""
    h = first_height
    warm = []
    for commits in traffic["warm_commits"]:
        warm.append([(h + c, {}) for c in range(commits)])
        h += commits
    count = max(1, round(traffic["rate_per_s"] * seconds))
    pool = [
        [(h + i, fixtures.plan_request(
            committee, seed, i, h + i, traffic["bad_every"]
        ))]
        for i in range(count)
    ]
    return {"warm": warm, "pool": pool}


def schedule(traffic: dict, seed: int, count: int) -> list:
    """Due times, seconds from the window's start, in order: the same
    set of jitters for every seed, dealt out in the seed's order."""
    interval = 1.0 / traffic["rate_per_s"]
    j = float(traffic["jitter"])
    jitters = [j * (2 * (k + 0.5) / count - 1) for k in range(count)]
    random.Random(seed * 1_000_003 + 17).shuffle(jitters)
    return sorted(
        max(0.0, (i + jitters[i]) * interval) for i in range(count)
    )


class Session:
    def __init__(self, traffic: dict, committee, remote, objects):
        self.traffic = traffic
        self.committee = committee
        self.remote = remote
        self.objects = objects

    def load(self, units: list) -> list:
        return [
            [
                item
                for rec in unit
                for item in self.objects.sig_items(self.committee, rec)
            ]
            for unit in units
        ]

    async def request(self, items: list, t_due: float | None = None) -> dict:
        t_sent = time.perf_counter()
        try:
            bits = np.asarray(
                await self.remote.submit(items, self.traffic["class"]),
                dtype=bool,
            )
            error = None
        except Exception as e:  # a degrade raises out of the tripwire
            bits, error = None, repr(e)
        return {
            "t_due": t_sent if t_due is None else t_due, "t_sent": t_sent,
            "t_done": time.perf_counter(), "error": error,
            "inner_s": 0.0, "verdicts": None, "bits": bits,
        }

    async def drive(self, requests: list, seconds: float, tracer) -> dict:
        due = schedule(self.traffic, self.committee.seed, len(requests))
        trace_from = seconds - float(self.traffic["trace_seconds"])
        starting = None  # the trace starts beside the schedule, not in it
        tasks = []
        t_start = time.perf_counter()
        for items, d in zip(requests, due):
            if tracer and starting is None and d >= trace_from:
                starting = asyncio.ensure_future(tracer.start())
            delay = t_start + d - time.perf_counter()
            if delay > SPIN_S:
                await asyncio.sleep(delay - SPIN_S)
            while time.perf_counter() < t_start + d:
                await asyncio.sleep(0)
            tasks.append(
                (t_start + d,
                 asyncio.ensure_future(self.request(items, t_start + d)))
            )
            await asyncio.sleep(0)  # the request goes out now, not later
        _, pending = await asyncio.wait(
            [t for _, t in tasks], timeout=LATE_ANSWER_S
        )
        traced_from = float("inf")
        if starting is not None:
            await starting
            traced_from = tracer.marks[-1]["client_pc"]
        done = []
        for t_due, t in tasks:
            if t in pending:  # never came
                t.cancel()
                now = time.perf_counter()
                r = {"t_due": t_due, "t_sent": t_due, "t_done": now,
                     "error": "no answer a minute past the close",
                     "inner_s": 0.0, "verdicts": None, "bits": None}
            else:
                r = t.result()
            r["traced"] = r["t_sent"] >= traced_from
            done.append(r)
        t_end = max([r["t_done"] for r in done] + [t_start])
        if starting is not None:
            await tracer.stop()
        return {"t_start": t_start, "t_end": t_end, "requests": done}
