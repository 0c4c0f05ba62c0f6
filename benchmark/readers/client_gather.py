"""The client's own share of a catch-up window, in milliseconds per
window: the wall of `verify_commits_light` minus the `verify` call
inside it (the benchmark's span around the classed verifier). What is
left is the shape checks, the sign-bytes gather and the tally."""


def read(ctx: dict, spec: dict):
    own = [
        (r["t_done"] - r["t_sent"] - r["inner_s"]) * 1e3
        for r in ctx["requests"]
        if not r["failed"] and r["inner_s"] > 0
    ]
    return sum(own) / len(own) if own else None
