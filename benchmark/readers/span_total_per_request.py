"""Summed duration, in milliseconds, of the spans named `span` on the
service's own ring (`GET /dump_traces`, traced runs) that start inside
the timed window, over the window's requests that did not fail: what a
step that is not one a request costs each of them, such as the
collector's passes (`runtime.gc`).

Parameters: `span`. Left out where the ring holds no such span in the
window, as with a program that does not record it, or where every
request failed.
"""


def read(ctx: dict, spec: dict):
    window = ctx["window"]
    start = window["wall_start"] * 1e9
    end = start + (window["t_end"] - window["t_start"]) * 1e9
    durs = [
        s["dur"] for s in ctx["spans"]
        if s["name"] == spec["span"] and start <= s["t0_wall_ns"] <= end
    ]
    served = sum(1 for r in ctx["requests"] if not r["failed"])
    if not durs or not served:
        return None
    return sum(durs) / served * 1e3
