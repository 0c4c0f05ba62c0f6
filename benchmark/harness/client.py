"""The load child: a CPU process beside the chip's owner.

It attaches to the verify service through RemoteVerifyScheduler (the
local fallback is a tripwire: a degrade is a failed operation, never a
verdict), builds the cell's pool of signed commits on the host's cores
while the service warms, drives the timed window with the cell's
generator, then holds every answer the window returned against the
plain reference. It writes one report for the parent and prints
nothing the driver reads.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import multiprocessing
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

from harness import fixtures  # noqa: E402
from harness.procs import service_get  # noqa: E402
from harness.stats import percentile  # noqa: E402


class NoLocalVerify:
    """The client's local verifier (chip_smoke.py's tripwire)."""

    def verify(self, items):
        raise RuntimeError(
            f"degraded: {len(items)} rows fell back to local verify"
        )


class TraceControl:
    """The traced run's line to `service_host.py`: only the process that
    holds the chip can trace it."""

    def __init__(self, path: str):
        self.path = path
        self.marks: list = []

    async def _call(self, word: str) -> dict:
        reader, writer = await asyncio.open_unix_connection(self.path)
        try:
            writer.write(word.encode() + b"\n")
            await writer.drain()
            reply = json.loads(await reader.readline())
        finally:
            writer.close()
        reply["client_pc"] = time.perf_counter()
        return reply

    async def start(self) -> None:
        self.marks.append(await self._call("start"))

    async def stop(self) -> None:
        self.marks.append(await self._call("stop"))


async def attach(socket_path: str):
    from tendermint_tpu.parallel.verify_service import RemoteVerifyScheduler

    remote = RemoteVerifyScheduler(socket_path, verifier=NoLocalVerify())
    await remote.start()
    deadline = time.monotonic() + 60.0
    while not remote.connected and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
    if not remote.connected:
        raise SystemExit("client never attached to the service")
    return remote


def workers() -> int:
    return max(1, min(len(os.sched_getaffinity(0)) - 1, 12))


def answers(pool, commits: list, control: str = "") -> dict:
    """{height: [bool per row]} from the reference or, with one of the
    kind's guarantees named, from the control that drops it, on the
    pool's workers."""
    per = len(commits) // (4 * workers()) + 1
    parts = pool.map(
        fixtures.reference_unit,
        [(commits[i : i + per], control)
         for i in range(0, len(commits), per)],
        1,
    )
    return {
        h: a for (h, _), a in zip(commits, [a for p in parts for a in p])
    }


async def sweep(args, session, gen, traffic, pool, first_height) -> list:
    """The one-off search for the highest rate an open-loop cell
    sustains: one set-up, the rates stepped inside this process."""
    rows = []
    for rate in [float(r) for r in args.sweep.split(",")]:
        session.traffic = dict(traffic, rate_per_s=rate)
        units = gen.plan(
            session.traffic, session.committee, args.seed, args.seconds,
            first_height,
        )["pool"]
        first_height = units[-1][-1][0] + 1
        requests = session.load(pool.map(fixtures.build_unit, units, 1))
        before = service_get(args.stats_port, "dump_dispatch_ledger")
        out = await session.drive(requests, args.seconds, None)
        after = service_get(args.stats_port, "dump_dispatch_ledger")
        lat = [(r["t_done"] - r["t_due"]) * 1e3 for r in out["requests"]]
        half = len(out["requests"]) // 2
        mean = lambda rs: sum(  # noqa: E731
            (r["t_done"] - r["t_due"]) * 1e3 for r in rs
        ) / max(1, len(rs))
        rows.append({
            "rate": rate, "requests": len(lat),
            "failed": sum(r["bits"] is None for r in out["requests"]),
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "max_ms": max(lat),
            "mean_first_half_ms": mean(out["requests"][:half]),
            "mean_second_half_ms": mean(out["requests"][half:]),
            "drain_s": out["t_end"] - out["t_start"] - args.seconds,
            "buckets": {
                b: v["rounds"] - before["summary"]["by_bucket"].get(
                    b, {"rounds": 0})["rounds"]
                for b, v in after["summary"]["by_bucket"].items()
            },
        })
        print("sweep", json.dumps(rows[-1]), flush=True)
    return rows


async def run(args) -> dict:
    from tendermint_tpu.libs.device import pin_cpu

    pin_cpu()  # before this process's first JAX call
    from harness import correct, program_objects
    from harness.cell import Cell

    cell = Cell(args.workload, args.benchmark_file or None)
    traffic = dict(cell.traffic)
    if args.rate:
        traffic["rate_per_s"] = args.rate
    gen = cell.generator()
    committee = cell.committee_kind().Committee(args.seed, cell.config)
    units = gen.plan(traffic, committee, args.seed, args.seconds)

    # the pool is signed on the host's cores while the service loads
    # its programs for the warm-up requests
    pool = multiprocessing.get_context("spawn").Pool(
        workers(), initializer=fixtures.init_worker,
        initargs=(args.seed, cell.config),
    )
    try:
        warm_job = pool.map_async(fixtures.build_unit, units["warm"], 1)
        t_pool = time.perf_counter()
        signed_s: list = []  # pool start -> last unit signed, by callback
        pool_job = pool.map_async(
            fixtures.build_unit, units["pool"], 1,
            callback=lambda _: signed_s.append(time.perf_counter() - t_pool),
        )
        remote = await attach(args.socket)
        tracer = TraceControl(args.control) if args.control else None
        try:
            session = gen.Session(traffic, committee, remote, program_objects)
            t0 = time.perf_counter()
            for request in session.load(warm_job.get()):
                r = await session.request(request)
                if r["error"]:
                    raise SystemExit("a warm-up request failed: " + r["error"])
            warm_s = time.perf_counter() - t0
            pool_recs = pool_job.get()
            requests = session.load(pool_recs)
            if args.sweep:
                rows = await sweep(
                    args, session, gen, traffic, pool,
                    units["pool"][-1][-1][0] + 1,
                )
                return {"sweep": rows}
            if tracer:  # the profiler's own first start is slow: spend it here
                await tracer.start()
                await tracer.stop()
                tracer.marks.clear()
            dump0 = service_get(args.stats_port, "dump_dispatch_ledger")
            ipc0 = remote.ipc_stats()
            window_wall = time.time()
            out = await session.drive(requests, args.seconds, tracer)
            ipc1 = remote.ipc_stats()
            dump1 = service_get(args.stats_port, "dump_dispatch_ledger")
            spans = (
                service_get(args.stats_port, "dump_traces") if tracer else None
            )
        finally:
            await remote.stop()

        # the window has closed and the service's memory has been read:
        # now the reference, over every commit the window submitted
        done = out["requests"]
        served = [
            (recs, r["bits"], r["verdicts"])
            for recs, r in zip(pool_recs, done)
        ]
        t0 = time.perf_counter()
        commits = [(h, sigs) for recs, _, _ in served for h, sigs, _ in recs]
        reference = answers(pool, commits)
        if args.control_guarantee:
            # the control: the reference without the one guarantee
            # named, put in the program's place and judged like the
            # program
            control = answers(pool, commits, args.control_guarantee)
            served = [
                (
                    recs,
                    [ok for h, _, _ in recs for ok in control[h]],
                    verdicts and [
                        committee.quorum(h, control[h]) for h, _, _ in recs
                    ],
                )
                for recs, _, verdicts in served
            ]
        numbers = correct.judge(served, reference, committee)
        numbers.update(committee.cross_check(
            correct.sample_rows(served, args.seed), reference
        ))
        reference_s = time.perf_counter() - t0
    finally:
        pool.close()
        pool.join()

    comp0 = dump0["service"]["compile"]
    comp1 = dump1["service"]["compile"]
    numbers["compiles_in_window"] = (
        comp1["compilations"] - comp0["compilations"]
    )
    numbers["degrades"] = ipc1["degrades"]
    numbers["error_frames"] = dump1["service"]["error_frames"]
    return {
        "loop": gen.LOOP,
        "window": {
            "wall_start": window_wall,
            "t_start": out["t_start"], "t_end": out["t_end"],
            "seconds": args.seconds,
        },
        "requests": [
            {
                "t_due": r["t_due"], "t_sent": r["t_sent"],
                "t_done": r["t_done"], "failed": r["bits"] is None,
                "error": r["error"], "inner_s": r["inner_s"],
                "traced": r["traced"],
                "rows": 0 if r["bits"] is None else len(r["bits"]),
                "rows_by_key_type": {} if r["bits"] is None else
                fixtures.rows_by_key_type(
                    committee, [h for h, _, _ in recs]
                ),
                "commits": len(recs),
            }
            for recs, r in zip(pool_recs, done)
        ],
        "pool_requests": len(requests),
        "numbers": numbers,
        "setup": {"warm_s": warm_s, "signed_s": signed_s[0],
                  "reference_s": reference_s,
                  "rows_judged": sum(len(a) for a in reference.values())},
        "ledger0": dump0["summary"], "ledger1": dump1["summary"],
        "compile": comp1,
        "shapes": dump1["shape_registry"],
        "ipc0": ipc0, "ipc1": ipc1,
        "service": dump1["service"],
        "trace_marks": tracer.marks if tracer else [],
        "spans": spans,
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--socket", required=True)
    p.add_argument("--stats-port", type=int, required=True)
    p.add_argument("--control", default="")
    p.add_argument("--control-guarantee", default="")
    p.add_argument("--rate", type=float, default=0.0)
    p.add_argument("--sweep", default="")
    p.add_argument("--benchmark-file", default="")
    p.add_argument("--report", required=True)
    args = p.parse_args()
    report = asyncio.run(run(args))
    with open(args.report, "w") as f:
        json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
