"""Device time of the verify programs, in milliseconds per execution:
the `XLA Modules` events of the traced span whose program name matches
`programs` (a regular expression), summed and divided by their count.
"""

import re


def matching(ctx: dict, spec: dict) -> tuple:
    """(executions, seconds) of the matching programs."""
    trace = ctx["trace"]
    if not trace:
        return 0, 0.0
    pattern = re.compile(spec["programs"])
    rows = [v for k, v in trace["modules"].items() if pattern.search(k)]
    return sum(c for c, _ in rows), sum(s for _, s in rows)


def read(ctx: dict, spec: dict):
    count, seconds = matching(ctx, spec)
    return seconds / count * 1e3 if count else None
