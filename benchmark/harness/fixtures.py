"""Committees, signed commits and planted bad rows, all from `--seed`.

Taken from chip_smoke.py's fixtures (make_committee, signed_commit,
corrupt, build_window, build_live) and rebuilt to sign a million rows
in seconds: keys and signatures by OpenSSL through `cryptography`,
sign-bytes by the benchmark's own encoder (`reference/canonical_vote`),
commits kept as plain records until the client turns them into the
program's types. Nothing here imports the program or JAX, so pool
workers and the reference start fast and stay independent of the code
under test.

A commit record is `(height, sigs, plan)`: one signature per validator
in validator-set order, and the rows the plan made bad, {index: kind}. A
height is used once in a run, so no row is ever submitted twice.
"""

from __future__ import annotations

import hashlib
import random

from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)

from reference import canonical_vote, ed25519_plain

CHAIN_ID = "bench-chain"
T0_NS = 1_700_000_000_000_000_000
POWER = 10
BAD_KINDS = ("flipped_bit", "wrong_key", "s_ge_L", "short_sig")


class Committee:
    """n validators of equal power, in validator-set order (sorted by
    address = first 20 bytes of SHA-256 of the public key)."""

    def __init__(self, seed: int, n: int):
        keys = [
            Ed25519PrivateKey.from_private_bytes(
                hashlib.sha256(b"bench|%d|val|%d" % (seed, i)).digest()
            )
            for i in range(n)
        ]
        rows = sorted(
            (hashlib.sha256(pub).digest()[:20], pub, key)
            for key in keys
            for pub in [key.public_key().public_bytes_raw()]
        )
        self.seed = seed
        self.n = n
        self.addresses = [a for a, _, _ in rows]
        self.pubs = [p for _, p, _ in rows]
        self.keys = [k for _, _, k in rows]
        self.powers = [POWER] * n


def block_hash(seed: int, height: int) -> bytes:
    return hashlib.sha256(b"bench|%d|block|%d" % (seed, height)).digest()


def timestamp_ns(height: int, i: int) -> int:
    return T0_NS + height * 1_000_000_000 + i


def messages(seed: int, height: int, n: int) -> list:
    """The n sign-bytes of one commit: round 0, one part, the part-set
    hash equal to the block hash."""
    bh = block_hash(seed, height)
    prefix, suffix = canonical_vote.commit_parts(
        CHAIN_ID, height, 0, bh, 1, bh
    )
    return [
        canonical_vote.sign_bytes(prefix, suffix, timestamp_ns(height, i))
        for i in range(n)
    ]


def corrupt(genuine: list, i: int, kind: str) -> bytes:
    """Validator i's signature made one of the four bad rows."""
    sig = genuine[i]
    if kind == "flipped_bit":
        return bytes([sig[0] ^ 0x04]) + sig[1:]
    if kind == "wrong_key":  # valid, but under the next validator's key
        return genuine[(i + 1) % len(genuine)]
    if kind == "s_ge_L":  # s + L is the same scalar mod L, out of range
        s = int.from_bytes(sig[32:], "little") + ed25519_plain.L
        return sig[:32] + s.to_bytes(32, "little")
    if kind == "short_sig":
        return sig[:63]
    raise ValueError(kind)


def sign_commit(committee: Committee, height: int, plan: dict) -> tuple:
    """One commit record: every validator precommits the block, then
    the rows in `plan` ({index: kind}) are made bad."""
    msgs = messages(committee.seed, height, committee.n)
    genuine = [k.sign(m) for k, m in zip(committee.keys, msgs)]
    sigs = list(genuine)
    for i, kind in plan.items():
        sigs[i] = corrupt(genuine, i, kind)
    return height, sigs, plan


def plan_window(seed: int, window: int, commits: int, n: int) -> list:
    """The bad rows of one catch-up window, as chip_smoke's build_window
    plants them: one seeded commit loses its quorum (more than a third
    of the power bad, the four kinds in turn), and four other rows are
    bad in commits that keep theirs. One {index: kind} per commit."""
    rng = random.Random(seed * 1_000_003 + window)
    plans: list = [{} for _ in range(commits)]
    bad_commit = rng.randrange(commits)
    for j, i in enumerate(rng.sample(range(n), n // 3 + 1)):
        plans[bad_commit][i] = BAD_KINDS[j % 4]
    good = [c for c in range(commits) if c != bad_commit]
    keep_quorum = n - (2 * n // 3 + 1)  # bad rows a good commit tolerates
    for j in range(min(4, keep_quorum * len(good))):
        c = rng.choice([c for c in good if len(plans[c]) < keep_quorum])
        i = rng.choice([x for x in range(n) if x not in plans[c]])
        plans[c][i] = BAD_KINDS[j % 4]
    return plans


def plan_request(seed: int, request: int, n: int, bad_every: int) -> dict:
    """The bad rows of one live request: one of each kind in every
    `bad_every`-th request, none in the others."""
    if bad_every <= 0 or request % bad_every != bad_every - 1:
        return {}
    rng = random.Random(seed * 1_000_003 + request)
    victims = rng.sample(range(n), min(4, n // 2))
    return dict(zip(victims, BAD_KINDS))


# --- pool workers (spawned; each holds its own committee) --------------------

_committee: Committee | None = None


def init_worker(seed: int, n: int) -> None:
    global _committee
    _committee = Committee(seed, n)


def build_unit(specs: list) -> list:
    """Worker: [(height, plan)] -> commit records."""
    return [sign_commit(_committee, h, plan) for h, plan in specs]


def reference_unit(args: tuple) -> list:
    """Worker: the reference's (or, with s_range False, the control's)
    row verdicts for [(height, sigs)], one list of bools per commit."""
    commits, s_range = args
    c = _committee
    return [
        [
            ed25519_plain.verify(pub, msg, sig, s_range)
            for pub, msg, sig in zip(c.pubs, messages(c.seed, h, c.n), sigs)
        ]
        for h, sigs in commits
    ]
