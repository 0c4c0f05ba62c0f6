"""Blocks, sign-bytes, planted bad rows and the pool workers, all from
`--seed`.

Taken from chip_smoke.py's fixtures (signed_commit, build_window,
build_live) and rebuilt to sign a million rows in seconds. What a
committee IS (its key types, how it signs, what makes a row bad, what
the plain reference answers) belongs to the cell's committee kind
(`committees/<kind>.py`); here is what every kind shares: the block a
height commits, the sign-bytes by the benchmark's own encoder
(`reference/canonical_vote`), which rows of a window or a request go
bad, and the workers that sign and judge. Commits are kept as plain
records until the client turns them into the program's types. Nothing
here imports the program or JAX, so pool workers and the reference
start fast and stay independent of the code under test.

A commit record is `(height, sigs, plan)`: one signature per signer in
validator-set order, and the rows the plan made bad, {row: kind}. A
height is used once in a run, so no row is ever submitted twice.
"""

from __future__ import annotations

import collections
import hashlib
import random

import committees
from reference import canonical_vote

CHAIN_ID = "bench-chain"
T0_NS = 1_700_000_000_000_000_000

Validator = collections.namedtuple(
    "Validator", "key_type pub address power"
)


def block_hash(seed: int, height: int) -> bytes:
    return hashlib.sha256(b"bench|%d|block|%d" % (seed, height)).digest()


def timestamp_ns(height: int, i: int) -> int:
    return T0_NS + height * 1_000_000_000 + i


def sign_bytes(seed: int, height: int, signers) -> list:
    """The sign-bytes of one commit, one per signer (validator indices):
    round 0, one part, the part-set hash equal to the block hash."""
    bh = block_hash(seed, height)
    prefix, suffix = canonical_vote.commit_parts(
        CHAIN_ID, height, 0, bh, 1, bh
    )
    return [
        canonical_vote.sign_bytes(prefix, suffix, timestamp_ns(height, i))
        for i in signers
    ]


def plan_window(committee, seed: int, window: int, heights: list) -> list:
    """The bad rows of one catch-up window, as chip_smoke's build_window
    plants them: one seeded commit loses its quorum (more than a third
    of its rows bad, the row's kinds in turn), and four other rows are
    bad in commits that keep theirs. One {row: kind} per commit."""
    rng = random.Random(seed * 1_000_003 + window)
    commits = len(heights)
    rows = [len(committee.signers(h)) for h in heights]
    plans: list = [{} for _ in range(commits)]

    def kind(c: int, i: int, j: int) -> str:
        kinds = committee.bad_kinds(heights[c], i)
        return kinds[j % len(kinds)]

    bad_commit = rng.randrange(commits)
    n = rows[bad_commit]
    for j, i in enumerate(rng.sample(range(n), n // 3 + 1)):
        plans[bad_commit][i] = kind(bad_commit, i, j)
    good = [c for c in range(commits) if c != bad_commit]
    # bad rows a good commit tolerates
    keep_quorum = [r - (2 * r // 3 + 1) for r in rows]
    for j in range(min(4, sum(keep_quorum[c] for c in good))):
        c = rng.choice([c for c in good if len(plans[c]) < keep_quorum[c]])
        i = rng.choice([x for x in range(rows[c]) if x not in plans[c]])
        plans[c][i] = kind(c, i, j)
    return plans


def plan_request(committee, seed: int, request: int, height: int,
                 bad_every: int) -> dict:
    """The bad rows of one live request: one of each kind in every
    `bad_every`-th request, none in the others."""
    if bad_every <= 0 or request % bad_every != bad_every - 1:
        return {}
    rng = random.Random(seed * 1_000_003 + request)
    n = len(committee.signers(height))
    victims = rng.sample(range(n), min(4, n // 2))
    return {
        i: committee.bad_kinds(height, i)[j] for j, i in enumerate(victims)
    }


def rows_by_key_type(committee, heights) -> dict:
    """How many rows of each key type the commits at `heights` hold:
    the load generator's own tally."""
    tally: collections.Counter = collections.Counter()
    for h in heights:
        validators = committee.validators(h)
        tally.update(validators[i].key_type for i in committee.signers(h))
    return dict(tally)


# --- pool workers (spawned; each holds its own committee) --------------------

_committee = None


def init_worker(seed: int, config: dict) -> None:
    global _committee
    _committee = committees.load(config).Committee(seed, config)


def build_unit(specs: list) -> list:
    """Worker: [(height, plan)] -> commit records."""
    return [_committee.sign_commit(h, plan) for h, plan in specs]


def reference_unit(args: tuple) -> list:
    """Worker: the reference's (or, with a guarantee named, the
    control's) row verdicts for [(height, sigs)], one list of bools per
    commit."""
    commits, control = args
    return _committee.reference(commits, control)
