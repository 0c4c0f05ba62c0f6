"""How late the load generator itself ran: a percentile, in
milliseconds, of (sent - due) over all requests. A starved generator
must not be read as a fast server.

Parameters: `percentile`.
"""

from harness.stats import percentile


def read(ctx: dict, spec: dict):
    late = [(r["t_sent"] - r["t_due"]) * 1e3 for r in ctx["requests"]]
    return percentile(late, spec["percentile"]) if late else None
