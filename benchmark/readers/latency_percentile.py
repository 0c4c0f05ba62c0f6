"""A percentile, in milliseconds, of due time -> verdicts at the client,
over ALL requests of the window. A failed request misses: it counts as
`miss_ms`, longer than any answer waited for.

Parameters: `percentile`.
"""

from harness.stats import percentile

MISS_MS = 120_000.0


def read(ctx: dict, spec: dict):
    if not ctx["requests"]:
        return None
    return percentile(
        [
            MISS_MS if r["failed"] else (r["t_done"] - r["t_due"]) * 1e3
            for r in ctx["requests"]
        ],
        spec["percentile"],
    )
