"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

This parent never imports JAX. It starts the chip's owner (the
`verify-service` entry point; with --trace 1 the same service under
harness/service_host.py), checks what device the service resolved,
starts the CPU-pinned load child, and turns the child's report into
the result line: with --trace 0 the cell's end-to-end metrics, with
--trace 1 its per-layer metrics. Which files make up a cell is in
harness/cell.py and README.md; nothing here knows one by name.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from harness import ledger, procs, trace as trace_mod  # noqa: E402
from harness.cell import ROOT, Cell, metric_reader  # noqa: E402
from harness.correct import verdict  # noqa: E402

RUN_LIMIT = 1100.0  # a first run compiles; the driver allows it 1200 s
WORK = ".bench_work"


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # not for the driver: the control of `correct` (one stated guarantee
    # broken in the reference, put in the program's place; the cell's
    # committee kind says which it has), a rate for the one-off sweep,
    # and another BENCHMARK.json for the tests
    p.add_argument("--control-guarantee", default="")
    p.add_argument("--rate", type=float, default=0.0)
    p.add_argument("--sweep", default="")
    p.add_argument("--benchmark-file", default="")
    return p.parse_args(argv)


def cpu_env() -> dict:
    """The load child runs BESIDE the chip's owner: keep it off the chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_cell(args, service_command=procs.service_command) -> dict:
    """Start the processes, wait for the load child's report, stop
    everything. `service_command` is the tests' seam for a service with
    the timed path broken underneath."""
    work = os.path.join(WORK, args.workload)
    shutil.rmtree(os.path.join(ROOT, work), ignore_errors=True)
    os.makedirs(os.path.join(ROOT, work))
    running = procs.Procs()
    try:
        _, sock, port, _ = procs.start_service(
            running, work,
            lambda w, s, fd: service_command(w, s, fd, bool(args.trace)),
        )
        service = procs.service_get(port)["service"]
        procs.require_chip(service, args.cell.chips)
        report = os.path.join(ROOT, work, "report.json")
        log = os.path.join(ROOT, work, "client.log")
        cmd = [
            sys.executable, os.path.join(BENCH_DIR, "harness", "client.py"),
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--socket", sock,
            "--stats-port", str(port), "--report", report,
            "--control-guarantee", args.control_guarantee,
            "--rate", str(args.rate), "--sweep", args.sweep,
            "--benchmark-file", args.benchmark_file,
        ]
        if args.trace:
            cmd += ["--control", os.path.join(work, "ctl.sock")]
        child = running.spawn(cmd, log, env=cpu_env())
        try:
            rc = child.wait(
                timeout=RUN_LIMIT - (time.time() - T_PROCESS_START)
            )
        except subprocess.TimeoutExpired:
            raise SystemExit("the load child ran past the run's limit")
        if rc != 0:
            raise SystemExit(
                f"the load child exited {rc}:\n" + procs.tail(log)
            )
        with open(report) as f:
            return json.load(f)
    finally:
        running.stop_all()


def trace_marks(report: dict) -> dict:
    return {m["word"]: m for m in report["trace_marks"]}


def window_of(report: dict) -> dict:
    """What the run was, for the line: the timed window as driven, and
    where in it the traced span lay."""
    w = report["window"]
    seconds = w["t_end"] - w["t_start"]
    start = trace_marks(report).get("start")
    return {
        "seconds": seconds,
        "requests": len(report["requests"]),
        "pool_requests": report["pool_requests"],
        # an open loop sends its whole schedule by design; a closed one
        # whose pool ends before `seconds` was cut short by its pool
        "pool_ran_dry": report["loop"] == "closed"
        and len(report["requests"]) == report["pool_requests"]
        and seconds < w["seconds"],
        "trace_from_s": start and start["client_pc"] - w["t_start"],
        "traced_requests": sum(r["traced"] for r in report["requests"]),
    }


def require_traced_span(report: dict, w: dict, trace_seconds: float) -> None:
    """A traced run in which tracing never began has nothing to say
    per layer: it ends here, not as a line that lacks the trace. `w`
    is the report's `window_of`."""
    missing = [
        f"no {word} mark" for word in ("start", "stop")
        if word not in trace_marks(report)
    ] + ["no traced request"] * (w["traced_requests"] == 0)
    if missing:
        raise SystemExit(
            f"--trace 1, and the report holds {', '.join(missing)}: the "
            f"timed window ended after {w['requests']} requests of "
            f"pool_requests {w['pool_requests']} at window.seconds "
            f"{w['seconds']:.3f} of {report['window']['seconds']}, before "
            f"the generator opened its traced span of trace_seconds "
            f"{trace_seconds}"
        )


def traced(report: dict, work: str) -> dict | None:
    """The traced span reduced, with its length on the service's clock;
    None where the trace holds no device plane (a CPU rehearsal)."""
    marks = trace_marks(report)
    path = trace_mod.newest_xplane(os.path.join(ROOT, work, "trace"))
    if not path:
        return None
    out = trace_mod.reduce(trace_mod.read_planes(path))
    if out is None:
        return None
    out["window_s"] = (
        marks["stop"]["perf_counter"] - marks["start"]["perf_counter"]
    )
    out["wall_ns"] = (
        marks["start"]["wall_ns_before"], marks["stop"]["wall_ns"]
    )
    return out


def service_spans(report: dict) -> list:
    """The service's own ring (`GET /dump_traces`, traced runs), each
    span with its start in wall nanoseconds from the ring's anchor."""
    dump = report["spans"] or {"records": [], "epoch_wall_ns": 0}
    return [
        {**s, "t0_wall_ns": dump["epoch_wall_ns"] + s["t0"] * 1e9}
        for s in dump["records"]
    ]


def context(report: dict, trace: dict | None) -> dict:
    service = report["service"]
    ipc0, ipc1 = report["ipc0"], report["ipc1"]
    traced = [
        r for r in report["requests"] if r["traced"] and not r["failed"]
    ]
    by_key_type: collections.Counter = collections.Counter()
    for r in traced:
        by_key_type.update(r["rows_by_key_type"])
    return {
        "window": report["window"],
        "requests": report["requests"],
        "setup_s": report["window"]["wall_start"] - T_PROCESS_START,
        "ledger": ledger.delta(report["ledger0"], report["ledger1"]),
        "ipc": {
            k: ipc1[k] - ipc0[k] for k in ("rtt_sum_s", "rtt_count")
        },
        "service": service,
        "device": {
            "platform": service["platform"],
            "kind": service["device_kind"],
            "count": service["device_count"],
        },
        "trace": trace,
        "spans": service_spans(report),
        "traced_rows": sum(r["rows"] for r in traced),
        "traced_rows_by_key_type": dict(by_key_type),
    }


def breakdown(trace: dict, service_spans: list) -> dict:
    """The device programs and operations that took most time, and the
    longest idle gaps by what the service was doing in them (its own
    spans, moved onto the trace's clock: best effort)."""
    ops = {"module " + k: v[1] for k, v in trace["modules"].items()}
    ops.update(trace["ops"])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    w0, w1 = trace["wall_ns"]
    zero = trace_mod.zero_wall_ns(
        trace["module_starts"],
        [
            s["t0_wall_ns"] for s in service_spans
            if s["name"] == "crypto.device_execute"
            and w0 <= s["t0_wall_ns"] <= w1
        ],
        w0,
    )
    spans = [
        (s["t0_wall_ns"] - zero, s["t0_wall_ns"] - zero + s["dur"] * 1e9,
         s["name"])
        for s in service_spans
    ]
    return {
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": trace_mod.label_gaps(trace["gaps"], spans),
    }


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "tendermint_tpu")):
        raise SystemExit("no program beside the benchmark: nothing to run")
    args.cell = Cell(args.workload, args.benchmark_file or None)
    if args.control_guarantee:
        controls = args.cell.committee_kind().CONTROLS
        if args.control_guarantee not in controls:
            raise SystemExit(
                f"--control-guarantee {args.control_guarantee!r}: this "
                f"cell's committee kind has {', '.join(controls)}"
            )
    report = run_cell(args)
    if args.sweep:
        print(json.dumps(report["sweep"], indent=1))
        return 0
    window = window_of(report)
    trace = None
    if args.trace:
        require_traced_span(
            report, window, args.cell.traffic["trace_seconds"]
        )
        trace = traced(report, os.path.join(WORK, args.workload))
    ctx = context(report, trace)
    wanted = args.cell.per_layer if args.trace else args.cell.end_to_end
    metrics = {}
    for m in wanted:
        read, spec = metric_reader(m["name"])
        value = read(ctx, spec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, checks = verdict(report["numbers"])
    device = dict(
        ctx["device"],
        memory_peak_bytes=ctx["service"].get("peak_bytes_in_use", 0),
    )
    line = {
        "correct": correct,
        "attempted": sum(r["commits"] for r in report["requests"]),
        "failed": sum(
            r["commits"] for r in report["requests"] if r["failed"]
        ),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = breakdown(trace, ctx["spans"])
    line["window"] = dict(
        window,
        compile={k: report["compile"][k] for k in
                 ("compilations", "seconds", "cache_hits", "cache_misses")},
        setup=report["setup"],
    )
    if window["pool_ran_dry"]:
        print(
            "pool ran dry: the timed window ended at "
            f"{window['seconds']:.3f} s of {args.seconds} with all "
            f"{report['pool_requests']} pool requests served",
            file=sys.stderr,
        )
    line["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    for k, v, lim in checks:
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
