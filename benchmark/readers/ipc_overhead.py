"""What the wire costs a submission, in milliseconds: the client's mean
round trip minus the service's mean `verify.service` span (a frame
decoded -> its verdicts encoded, from the service's own ring), both
over the same stretch of the window. The ring keeps the newest 8,192
spans, so the stretch runs from the oldest `verify.service` span it
still holds to the window's end, and the client's side is the
benchmark's own clock around the same requests (sent in that stretch).
In a catch-up window the client's span is the `verify` call inside
`verify_commits_light`. Left out where the ring holds no such span."""


def read(ctx: dict, spec: dict):
    window = ctx["window"]
    wall = lambda t: (window["wall_start"] + t - window["t_start"]) * 1e9  # noqa: E731
    served = [
        s for s in ctx["spans"]
        if s["name"] == "verify.service"
        and s["t0_wall_ns"] >= window["wall_start"] * 1e9
    ]
    if not served:
        return None
    since = min(s["t0_wall_ns"] for s in served)
    trips = [
        r["inner_s"] or r["t_done"] - r["t_sent"]
        for r in ctx["requests"]
        if not r["failed"] and wall(r["t_sent"]) >= since - 1e6
    ]
    if not trips:
        return None
    return (
        sum(trips) / len(trips)
        - sum(s["dur"] for s in served) / len(served)
    ) * 1e3
