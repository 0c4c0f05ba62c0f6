"""Mean duration, in milliseconds, of the spans named `span` on the
service's own ring (`GET /dump_traces`, traced runs) that start inside
the timed window: the program's own account of one of its steps, one
span a submission (the `verify.*` names) or a round (the `crypto.*`
names).

Parameters: `span`. Left out where the ring holds no such span in the
window, as with a program that does not record it.
"""


def read(ctx: dict, spec: dict):
    window = ctx["window"]
    start = window["wall_start"] * 1e9
    end = start + (window["t_end"] - window["t_start"]) * 1e9
    durs = [
        s["dur"] for s in ctx["spans"]
        if s["name"] == spec["span"] and start <= s["t0_wall_ns"] <= end
    ]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e3
