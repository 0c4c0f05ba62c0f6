"""From a profiler trace (`*.xplane.pb`) to what the metrics read.

Read with `jax.profiler.ProfileData`, nothing else. A device is a plane
named `/device:TPU:<n>`; its line `XLA Ops` holds one event per
operation that ran there and `XLA Modules` one per program execution.

    busy_s     union of the op intervals (nested and overlapping events
               counted once), averaged over the device planes
    gaps       the idle intervals between them, longest first
    modules    {program name: [executions, seconds]} over the planes
    ops        {operation name: seconds}; a loop's event spans its
               body's, so read it as "time under this name"

Times are nanoseconds on the trace's own clock. Kept as code with the
benchmark and checked on a recorded trace (tests/test_trace.py), so
every PR computes the same numbers the same way.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(found, key=os.path.getmtime) if found else None


def merge(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(merged: list) -> list:
    """(start, end) of the idle stretches between busy ones."""
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def program_name(event_name: str) -> str:
    """`jit__verify_cached_big(1234567)` -> `jit__verify_cached_big`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def read_planes(path: str) -> list:
    """[{name, ops: [(start, end, name)], modules: [...]}] per device.
    A path ending in `.xz` is a recording kept compressed."""
    from jax.profiler import ProfileData

    if path.endswith(".xz"):
        import lzma

        with lzma.open(path) as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {OPS_LINE: [], MODULES_LINE: []}
        for line in plane.lines:
            if line.name in lines:
                lines[line.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                ]
        planes.append(
            {"name": plane.name, "ops": lines[OPS_LINE],
             "modules": lines[MODULES_LINE]}
        )
    return planes


def reduce(planes: list) -> dict | None:
    """The numbers above, or None where no device plane holds an op."""
    planes = [p for p in planes if p["ops"] or p["modules"]]
    if not planes:
        return None
    busy = 0.0
    idle: list = []
    modules: dict = {}
    ops: dict = {}
    for p in planes:
        merged = merge([(s, e) for s, e, _ in p["ops"] or p["modules"]])
        busy += sum(e - s for s, e in merged) / 1e9
        idle.extend(gaps(merged))
        for s, e, name in p["modules"]:
            row = modules.setdefault(program_name(name), [0, 0.0])
            row[0] += 1
            row[1] += (e - s) / 1e9
        for s, e, name in p["ops"]:
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "chips": len(planes),
        "busy_s": busy / len(planes),
        "gaps": idle,
        "modules": modules,
        "ops": ops,
        "module_starts": sorted(
            (s, program_name(name)) for s, _, name in planes[0]["modules"]
        ),
    }


def op_name(event_name: str) -> str:
    """An op's event is named by its whole HLO line: keep what stands
    before ` = `, without the `%`."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:64]


def zero_wall_ns(module_starts: list, executes: list, fallback: int) -> int:
    """The wall-clock nanosecond at which the trace's clock reads 0.
    The trace counts from its session's start, which no host clock
    marks. Where the service's `crypto.device_execute` spans of the
    traced span (wall starts, in order) are as many as the programs the
    device ran, pair them in order and take the median difference; else
    `fallback`, the wall clock just before `start_trace` was called."""
    if not executes or len(executes) != len(module_starts):
        return fallback
    diffs = sorted(w - s for w, (s, _) in zip(sorted(executes), module_starts))
    return int(diffs[len(diffs) // 2])


UMBRELLA = ("verify.service", "verify.queue", "scheduler.queue_wait")


def label_gaps(idle: list, spans: list, top: int = 10) -> list:
    """[[label, seconds]] for the longest gaps, each by what the service
    was doing for most of it. `spans` are (start_ns, end_ns, name) of
    the service's own ring on the trace's clock. Of the span names that
    cover half of a gap or more, the innermost (the one whose spans are
    shortest) is the label. The spans that only say a request was
    inside the service or queued (UMBRELLA) give `in the service, no
    span`; a gap that mostly no span touches is `no request in the
    service` (the client was gathering, encoding or reading, or nothing
    was due)."""
    out = []
    for g0, g1 in idle[:top]:
        laps: dict = {}
        for s0, s1, name in spans:
            if min(g1, s1) > max(g0, s0):
                laps.setdefault(name, []).append(
                    (max(g0, s0), min(g1, s1), s1 - s0)
                )
        half = (g1 - g0) / 2
        covered = lambda rows: sum(  # noqa: E731
            e - s for s, e in merge([(a, b) for a, b, _ in rows])
        )
        inner = [
            (sum(d for _, _, d in rows) / len(rows), name)
            for name, rows in laps.items()
            if name not in UMBRELLA and covered(rows) >= half
        ]
        if inner:
            label = min(inner)[1]
        elif covered([r for rows in laps.values() for r in rows]) >= half:
            label = "in the service, no span"
        else:
            label = "no request in the service"
        out.append([label, (g1 - g0) / 1e9])
    return out
