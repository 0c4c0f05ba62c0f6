"""One total of the service's dispatch ledger over another, both as
deltas of the two dumps around the timed window, times `scale`.

Parameters: `numerator`, `denominator` (keys of harness/ledger.py's
delta), `scale`. Left out where the denominator did not move.
"""


def read(ctx: dict, spec: dict):
    ledger = ctx["ledger"]
    den = ledger[spec["denominator"]]
    if not den:
        return None
    return spec["scale"] * ledger[spec["numerator"]] / den
