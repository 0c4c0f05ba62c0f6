"""The comparison that decides `correct`.

What the timed window itself returned (every request's row bitmap, and
for a catch-up window its per-commit verdicts) against the plain
reference's answers for the same rows. Every number is an exact count
and its limit is 0.

    requests_failed    requests that never got verdicts: a degrade, an
                       error frame, no answer a minute past the close
    rows_wrong         rows whose served bit differs from the reference
    commits_wrong      commits whose served verdict differs from the
                       reference's tally over its own row verdicts
    plan_vs_reference  commits where the rows the generator made bad
                       are not exactly the rows the reference rejects
                       (a fault of the yardstick, not of the program)
    <the kind's cross-checks>   rows of a seeded sample on which an
                       independent implementation and the window-wide
                       reference disagree (the same); the committee
                       kind names them
    compiles_in_window programs the service compiled or loaded between
                       the two dumps around the window
    degrades, error_frames   the client's and the service's own counts
    rows_resubmitted   rows of a height that was already submitted
"""

from __future__ import annotations

import random

SAMPLE_ROWS = 24


def judge(served: list, reference: dict, committee) -> dict:
    """served: [(commit records, bits or None, verdicts or None)], one
    per request. reference: {height: [bool per row]}. The committee
    says whether a commit stands on given row verdicts."""
    failed = rows_wrong = commits_wrong = plan_wrong = resubmitted = 0
    seen: set = set()
    for recs, bits, verdicts in served:
        for height, _, plan in recs:
            resubmitted += len(reference[height]) if height in seen else 0
            seen.add(height)
            rejected = [i for i, ok in enumerate(reference[height]) if not ok]
            plan_wrong += rejected != sorted(plan)
        if bits is None:
            failed += 1
            continue
        want = [ok for height, _, _ in recs for ok in reference[height]]
        if len(bits) != len(want):
            rows_wrong += len(want)
        else:
            rows_wrong += sum(
                bool(b) != w for b, w in zip(bits, want)
            )
        if verdicts is not None:
            for (height, _, _), got in zip(recs, verdicts):
                stands = committee.quorum(height, reference[height])
                commits_wrong += bool(got) != stands
            commits_wrong += abs(len(verdicts) - len(recs))
    return {
        "requests_failed": failed,
        "rows_wrong": rows_wrong,
        "commits_wrong": commits_wrong,
        "plan_vs_reference": plan_wrong,
        "rows_resubmitted": resubmitted,
    }


def sample_rows(served: list, seed: int) -> list:
    """[(commit record, row)]: a seeded sample of served rows for the
    kind's cross-check, one bad row of each kind among them where the
    window served one."""
    rng = random.Random(seed * 1_000_003 + 29)
    recs = [rec for unit, _, _ in served for rec in unit]
    if not recs:
        return []
    sample = {}
    for rec in rng.sample(recs, len(recs)):
        for i, kind in rec[2].items():
            sample.setdefault(kind, (rec, i))
    rows = list(sample.values())
    while len(rows) < SAMPLE_ROWS:
        rec = rng.choice(recs)
        rows.append((rec, rng.randrange(len(rec[1]))))
    return rows


def verdict(numbers: dict) -> tuple:
    """(correct, [(name, value, limit)]): every limit is 0."""
    checks = [(name, int(value), 0) for name, value in numbers.items()]
    return all(v <= lim for _, v, lim in checks), checks
