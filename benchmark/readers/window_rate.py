"""Work whose answer came back, over the whole timed window.

Parameters: `count` = `commits` or `rows`. All the work over all the
time: from the first submission to the completion that closed the
window, failed requests counting for nothing.
"""


def read(ctx: dict, spec: dict):
    window = ctx["window"]
    elapsed = window["t_end"] - window["t_start"]
    done = sum(
        r[spec["count"]] for r in ctx["requests"] if not r["failed"]
    )
    return done / elapsed if elapsed > 0 and done else None
