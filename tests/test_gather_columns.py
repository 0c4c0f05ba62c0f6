"""The commit gather by columns: `CanonicalVoteEncoder.votes_from_parts`
against the row encoder, and the four commit-verify entry points against
the per-row gather and tally they replaced (restated below as `ref_*`),
item for item, verdict for verdict, message for message."""

from __future__ import annotations

import random

import pytest

from tendermint_tpu.crypto.batch_verifier import SigItem
from tendermint_tpu.obs.tracer import Tracer, default_tracer, set_default_tracer
from tendermint_tpu.types.block import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.canonical import (
    COLUMN_MIN_ROWS,
    CanonicalVoteEncoder,
    canonical_block_id,
)
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.validator import Validator, pubkey_from_type
from tendermint_tpu.types.validator_set import (
    MAX_TOTAL_VOTING_POWER,
    ValidatorSet,
)

CHAIN = "test-chain"
T0 = 1_700_000_000_000_000_000
NS = 1_000_000_000

# --- the column encoder -------------------------------------------------

# the first value of each varint length, 1 to 5 bytes, and the last of
# each, for the nanos (< 1e9) and the seconds (< 2**63 ns)
_EDGES = [1, 2**7 - 1, 2**7, 2**14 - 1, 2**14, 2**21 - 1, 2**21, 2**28 - 1]
_NANOS = _EDGES + [2**28, NS - 1]
_SECONDS = _EDGES + [2**28, 2**33, (2**63 - 1) // NS]

TIMESTAMP_CASES = {
    "zero": [0],
    "seconds_zero": [n for n in _NANOS],
    "nanos_zero": [s * NS for s in _SECONDS],
    "nanos_each_length": [T0 // NS * NS + n for n in _NANOS],
    "seconds_each_length": [s * NS + 5 for s in _SECONDS],
    "lengths_crossed": [s * NS + n for s in _SECONDS for n in _NANOS],
    "near_2_63": [2**63 - 1, 2**63 - NS, (2**63 - 1) // NS * NS],
    "negative": [-1, -NS, -NS - 1, -(2**63), T0],
    "at_or_above_2_63": [2**63, 2**63 + 1, 2**64, 10**30, T0],
    "random": [T0 + random.Random(5).randrange(10**12) for _ in range(64)],
}


def _parts(chain_id: str, nil: bool, height: int = 7):
    bid = b"" if nil else canonical_block_id(b"\x11" * 32, 1, b"\x22" * 32)
    return CanonicalVoteEncoder.vote_parts(2, height, 0, bid, chain_id)


def _column(values: list, n: int) -> list:
    """`values` repeated to n rows, so that a short case still takes
    the column path (COLUMN_MIN_ROWS)."""
    return [values[j % len(values)] for j in range(n)]


def _outside(ts: list) -> int:
    return sum(1 for t in ts if not 0 <= t < 2**63)


@pytest.mark.parametrize("case", sorted(TIMESTAMP_CASES))
@pytest.mark.parametrize("nil", [False, True], ids=["for_block", "nil"])
def test_votes_from_parts_equals_rows(case, nil):
    parts = _parts(CHAIN, nil)
    ts = _column(TIMESTAMP_CASES[case], max(COLUMN_MIN_ROWS, 48))
    msgs, fallback = CanonicalVoteEncoder.votes_from_parts([parts], ts)
    assert msgs == [CanonicalVoteEncoder.vote_from_parts(*parts, t) for t in ts]
    assert fallback == _outside(ts)


@pytest.mark.parametrize("n", [0, 1, COLUMN_MIN_ROWS - 1, COLUMN_MIN_ROWS])
def test_votes_from_parts_short_columns(n):
    """Empty, one-row and crossover-sized columns: below COLUMN_MIN_ROWS
    every row is built one at a time and counted so."""
    parts = _parts(CHAIN, False)
    ts = _column(TIMESTAMP_CASES["random"], n)
    msgs, fallback = CanonicalVoteEncoder.votes_from_parts([parts], ts)
    assert msgs == [CanonicalVoteEncoder.vote_from_parts(*parts, t) for t in ts]
    assert fallback == (n if n < COLUMN_MIN_ROWS else 0)


@pytest.mark.parametrize("chain_len", [0, 1, 120, 300])
def test_votes_from_parts_long_delimited_length(chain_len):
    """A chain id that takes the delimited length (and the suffix's own
    length) past one varint byte; an empty one, after which a zero
    timestamp leaves the row ending in a zero byte."""
    parts = _parts("c" * chain_len, False)
    ts = _column(TIMESTAMP_CASES["lengths_crossed"] + [0], 100)
    msgs, _ = CanonicalVoteEncoder.votes_from_parts([parts], ts)
    assert msgs == [CanonicalVoteEncoder.vote_from_parts(*parts, t) for t in ts]
    if chain_len >= 120:
        assert all(m[0] & 0x80 for m in msgs)  # two-byte delimited length


def test_votes_from_parts_many_parts_in_one_call():
    """Rows of several commits, for-block and nil parts of two chain
    ids (so parts of different lengths), interleaved by part index."""
    rng = random.Random(11)
    parts = [
        _parts(chain, nil, height)
        for height in (1, 2**40)
        for chain in (CHAIN, "x" * 130)
        for nil in (False, True)
    ]
    pool = sum(TIMESTAMP_CASES.values(), [])
    ts = [rng.choice(pool) for _ in range(500)]
    part_of_row = [rng.randrange(len(parts)) for _ in ts]
    msgs, fallback = CanonicalVoteEncoder.votes_from_parts(
        parts, ts, part_of_row
    )
    assert msgs == [
        CanonicalVoteEncoder.vote_from_parts(*parts[p], t)
        for p, t in zip(part_of_row, ts)
    ]
    assert fallback == _outside(ts)


def test_vote_from_parts_is_vote():
    """The row encoder still composes `vote` (both read the same field
    constants)."""
    bid = canonical_block_id(b"\x33" * 32, 2, b"\x44" * 32)
    for t in (0, 5, NS, T0 + 17, -3):
        assert CanonicalVoteEncoder.vote(
            2, 9, 1, bid, t, CHAIN
        ) == CanonicalVoteEncoder.vote_from_parts(
            *CanonicalVoteEncoder.vote_parts(2, 9, 1, bid, CHAIN), t
        )


# --- the gather and tally, against the per-row code they replaced ------


def ref_gather(vs, chain_id, commit, only_for_block):
    items, idxs = [], []
    parts_for = commit._sign_bytes_parts(chain_id, True)
    parts_nil = None
    for i, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        if cs.for_block():
            prefix, suffix = parts_for
        elif only_for_block:
            continue
        else:
            if parts_nil is None:
                parts_nil = commit._sign_bytes_parts(chain_id, False)
            prefix, suffix = parts_nil
        val = vs.validators[i]
        items.append(
            SigItem(
                val.pub_key.data,
                CanonicalVoteEncoder.vote_from_parts(
                    prefix, suffix, cs.timestamp_ns
                ),
                cs.signature,
                key_type=getattr(val.pub_key, "type_name", "ed25519"),
            )
        )
        idxs.append(i)
    return items, idxs


def ref_verify_commits_light(vs, chain_id, entries, verifier):
    all_items, spans = [], []
    for block_id, height, commit in entries:
        try:
            if commit is None:
                raise ValueError("nil commit")
            vs._check_commit_shape(block_id, height, commit)
        except ValueError:
            spans.append((len(all_items), None))
            continue
        items, idxs = ref_gather(vs, chain_id, commit, True)
        spans.append((len(all_items), idxs))
        all_items.extend(items)
    ok = verifier.verify(all_items) if all_items else []
    out = []
    for start, idxs in spans:
        if idxs is None:
            out.append(False)
            continue
        tallied = sum(
            vs.validators[i].voting_power
            for valid, i in zip(ok[start : start + len(idxs)], idxs)
            if valid
        )
        try:
            vs._check_maj23(tallied)
            out.append(True)
        except ValueError:
            out.append(False)
    return out


def ref_verify_commit(vs, chain_id, block_id, height, commit, verifier):
    vs._check_commit_shape(block_id, height, commit)
    items, idxs = ref_gather(vs, chain_id, commit, False)
    ok = verifier.verify(items)
    tallied = 0
    for valid, i in zip(ok, idxs):
        if not valid:
            raise ValueError(f"wrong signature at index {i}")
        if commit.signatures[i].for_block():
            tallied += vs.validators[i].voting_power
    vs._check_maj23(tallied)


def ref_verify_commit_light(vs, chain_id, block_id, height, commit, verifier):
    vs._check_commit_shape(block_id, height, commit)
    items, idxs = ref_gather(vs, chain_id, commit, True)
    ok = verifier.verify(items)
    tallied = sum(
        vs.validators[i].voting_power for valid, i in zip(ok, idxs) if valid
    )
    vs._check_maj23(tallied)


def ref_verify_commit_light_trusting(vs, chain_id, commit, verifier):
    items, powers = [], []
    seen = set()
    prefix, suffix = commit._sign_bytes_parts(chain_id, True)
    for cs in commit.signatures:
        if not cs.for_block():
            continue
        idx, val = vs.get_by_address(cs.validator_address)
        if idx < 0 or val is None:
            continue
        if val.address in seen:
            raise ValueError("double vote from validator")
        seen.add(val.address)
        items.append(
            SigItem(
                val.pub_key.data,
                CanonicalVoteEncoder.vote_from_parts(
                    prefix, suffix, cs.timestamp_ns
                ),
                cs.signature,
                key_type=getattr(val.pub_key, "type_name", "ed25519"),
            )
        )
        powers.append(val.voting_power)
    ok = verifier.verify(items)
    tallied = sum(p for valid, p in zip(ok, powers) if valid)
    needed = vs.total_voting_power() // 3
    if tallied <= needed:
        raise ValueError(
            f"insufficient trusted voting power: {tallied} <= {needed}"
        )


class Recording:
    """A verifier that keeps every batch it is handed and rejects a
    signature that starts with b"bad"."""

    def __init__(self):
        self.batches: list = []

    def verify(self, items):
        self.batches.append(list(items))
        return [not it.sig.startswith(b"bad") for it in items]


def outcome(fn, *args):
    """(what fn returned or raised, the batches its verifier saw)."""
    verifier = Recording()
    try:
        result = ("ok", fn(*args, verifier))
    except ValueError as e:
        result = ("raised", str(e))
    return result, verifier.batches


def make_set(powers, secp_every: int = 0, seed: int = 1) -> ValidatorSet:
    rng = random.Random(seed)
    vals = []
    for j, p in enumerate(powers):
        if secp_every and j % secp_every == 0:
            pub = pubkey_from_type("secp256k1", b"\x02" + rng.randbytes(32))
        else:
            pub = pubkey_from_type("ed25519", rng.randbytes(32))
        vals.append(Validator(pub, p))
    return ValidatorSet(vals)


def make_commit(vs, height, rng, flags=None, bad=(), timestamps=None):
    """A commit of `vs` at `height`: flags per validator (COMMIT where
    None), signatures that start with b"bad" at the indices in `bad`."""
    bh = bytes([height % 256]) * 32
    bid = BlockID(bh, PartSetHeader(1, bh))
    sigs = []
    for i, v in enumerate(vs.validators):
        flag = BlockIDFlag.COMMIT if flags is None else flags[i]
        if flag == BlockIDFlag.ABSENT:
            sigs.append(CommitSig.absent())
            continue
        ts = (
            timestamps[i]
            if timestamps is not None
            else T0 + height * NS + rng.choice([0, i, rng.randrange(NS)])
        )
        sig = (b"bad" if i in bad else b"sig") + rng.randbytes(61)
        sigs.append(CommitSig(flag, v.address, ts, sig))
    return bid, height, Commit(height, 0, bid, sigs)


def mixed_flags(n, rng, absent=0.2, nil=0.2):
    out = []
    for _ in range(n):
        r = rng.random()
        out.append(
            BlockIDFlag.ABSENT
            if r < absent
            else BlockIDFlag.NIL
            if r < absent + nil
            else BlockIDFlag.COMMIT
        )
    return out


def commit_case(name, n):
    """(vs, (block_id, height, commit)) for one named commit shape."""
    rng = random.Random(f"{name}/{n}")
    vs = make_set([10] * n, secp_every=7 if name == "key_types" else 0)
    if name == "all_commit":
        return vs, make_commit(vs, 3, rng)
    if name == "mixed_flags":
        return vs, make_commit(vs, 4, rng, flags=mixed_flags(n, rng))
    if name == "bad_rows":
        flags = mixed_flags(n, rng, absent=0.1, nil=0.1)
        bad = {i for i in range(n) if rng.random() < 0.15}
        return vs, make_commit(vs, 5, rng, flags=flags, bad=bad)
    if name == "no_quorum":
        return vs, make_commit(vs, 6, rng, flags=mixed_flags(n, rng, 0.5))
    if name == "fallback_timestamps":
        ts = [T0 + i for i in range(n)]
        ts[0], ts[-1] = -5, 2**63 + 7
        return vs, make_commit(vs, 7, rng, timestamps=ts)
    if name == "key_types":
        return vs, make_commit(vs, 8, rng, flags=mixed_flags(n, rng))
    raise KeyError(name)


COMMIT_CASES = [
    "all_commit", "mixed_flags", "bad_rows", "no_quorum",
    "fallback_timestamps", "key_types",
]
SIZES = [4, COLUMN_MIN_ROWS + 8]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("case", COMMIT_CASES)
def test_verify_commit_as_rows(case, n):
    vs, (bid, h, commit) = commit_case(case, n)
    got = outcome(vs.verify_commit, CHAIN, bid, h, commit)
    want = outcome(
        lambda *a: ref_verify_commit(vs, *a), CHAIN, bid, h, commit
    )
    assert got == want


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("case", COMMIT_CASES)
def test_verify_commit_light_as_rows(case, n):
    vs, (bid, h, commit) = commit_case(case, n)
    got = outcome(vs.verify_commit_light, CHAIN, bid, h, commit)
    want = outcome(
        lambda *a: ref_verify_commit_light(vs, *a), CHAIN, bid, h, commit
    )
    assert got == want


def test_verify_commit_names_the_first_wrong_signature():
    vs = make_set([10] * 40)
    rng = random.Random(3)
    flags = mixed_flags(40, rng)
    flags[17], flags[30] = BlockIDFlag.NIL, BlockIDFlag.COMMIT
    bid, h, commit = make_commit(vs, 9, rng, flags=flags, bad={17, 30})
    with pytest.raises(ValueError, match="wrong signature at index 17$"):
        vs.verify_commit(CHAIN, bid, h, commit, Recording())


@pytest.mark.parametrize("n", SIZES)
def test_verify_commits_light_as_rows(n):
    """Good, no-quorum and bad-row commits among a nil commit and
    commits of the wrong height, block id and size."""
    vs = make_set([10] * n)
    rng = random.Random(n)
    entries = []
    for k, case in enumerate(
        ["all_commit", "mixed_flags", "bad_rows", "no_quorum"] * 3
    ):
        flags = None if case == "all_commit" else mixed_flags(
            n, rng, 0.5 if case == "no_quorum" else 0.2
        )
        bad = {1, 2, 3} if case == "bad_rows" else ()
        entries.append(make_commit(vs, 10 + k, rng, flags=flags, bad=bad))
    bid, h, commit = entries[1]
    entries.insert(2, (bid, h, None))
    entries.insert(4, (bid, h + 1, commit))
    entries.insert(6, (entries[0][0], h, commit))
    small = make_set([10] * (n - 1))
    entries.insert(7, make_commit(small, h, rng))
    got = outcome(vs.verify_commits_light, CHAIN, entries)
    want = outcome(
        lambda *a: ref_verify_commits_light(vs, *a), CHAIN, entries
    )
    assert got == want
    assert got[0][1].count(False) >= 4  # the malformed ones at least
    assert len(got[1]) == 1  # one batch for the whole window


def test_verify_commits_light_with_nothing_to_verify():
    vs = make_set([10] * 4)
    bid, h, commit = make_commit(vs, 2, random.Random(0))
    entries = [(bid, h, None), (bid, h + 1, commit)]
    assert outcome(vs.verify_commits_light, CHAIN, entries) == (
        ("ok", [False, False]),
        [],
    )


def _trusting_case(name, n):
    """(trusted set, commit of another set that shares part of it)."""
    rng = random.Random(n + len(name))
    old = make_set([10 + i for i in range(n)], seed=2)
    keep = [v for i, v in enumerate(old.validators) if i % 3 != 0]
    extra = make_set([7] * (n // 3), seed=3).validators
    new = ValidatorSet([v.copy() for v in keep + list(extra)])
    flags = mixed_flags(new.size(), rng, 0.1, 0.1)
    bad = {i for i in range(new.size()) if rng.random() < 0.1}
    if name == "low_overlap":
        flags = [
            BlockIDFlag.COMMIT if old.has_address(v.address) and i % 4 == 0
            else BlockIDFlag.ABSENT
            for i, v in enumerate(new.validators)
        ]
        bad = ()
    bid, h, commit = make_commit(new, 20, rng, flags=flags, bad=bad)
    if name == "double_vote":
        j = next(
            i for i, cs in enumerate(commit.signatures)
            if cs.for_block() and old.has_address(cs.validator_address)
        )
        commit.signatures.append(commit.signatures[j])
    return old, commit


@pytest.mark.parametrize("n", [6, 3 * COLUMN_MIN_ROWS])
@pytest.mark.parametrize("case", ["overlap", "low_overlap", "double_vote"])
def test_verify_commit_light_trusting_as_rows(case, n):
    old, commit = _trusting_case(case, n)
    got = outcome(old.verify_commit_light_trusting, CHAIN, commit, 1, 3)
    want = outcome(
        lambda c, v: ref_verify_commit_light_trusting(old, CHAIN, c, v),
        commit,
    )
    assert got == want
    if case == "double_vote":
        assert got == (("raised", "double vote from validator"), [])


# --- the tally at the power limit --------------------------------------

_NEEDED = MAX_TOTAL_VOTING_POWER * 2 // 3


def _limit_set(n: int) -> ValidatorSet:
    """Validator 0 holds exactly 2/3 of MAX_TOTAL_VOTING_POWER (rounded
    down), validator 1 holds 1, the rest share what is left: the total
    is the maximum."""
    rest = MAX_TOTAL_VOTING_POWER - _NEEDED - 1
    share, extra = divmod(rest, n - 2)
    powers = [_NEEDED, 1] + [share + (j < extra) for j in range(n - 2)]
    assert sum(powers) == MAX_TOTAL_VOTING_POWER
    vs = make_set(powers, seed=9)
    return vs


@pytest.mark.parametrize("n", [5, COLUMN_MIN_ROWS + 2])
@pytest.mark.parametrize("with_one", [False, True], ids=["needed", "needed+1"])
@pytest.mark.parametrize(
    "entry", ["verify_commit", "verify_commit_light", "verify_commits_light"]
)
def test_tally_at_the_two_thirds_boundary(entry, with_one, n):
    """Tallied == needed fails and needed + 1 passes, exactly, at a total
    of MAX_TOTAL_VOTING_POWER (2**60)."""
    vs = _limit_set(n)
    assert vs.total_voting_power() * 2 // 3 == _NEEDED
    big = next(i for i, v in enumerate(vs.validators) if v.voting_power == _NEEDED)
    one = next(i for i, v in enumerate(vs.validators) if v.voting_power == 1)
    signers = {big, one} if with_one else {big}
    flags = [
        BlockIDFlag.COMMIT if i in signers else BlockIDFlag.ABSENT
        for i in range(n)
    ]
    bid, h, commit = make_commit(vs, 30, random.Random(n), flags=flags)
    if entry == "verify_commits_light":
        got = outcome(vs.verify_commits_light, CHAIN, [(bid, h, commit)])
        want = outcome(
            lambda *a: ref_verify_commits_light(vs, *a),
            CHAIN, [(bid, h, commit)],
        )
        assert got[0] == ("ok", [with_one])
    else:
        ref = {
            "verify_commit": ref_verify_commit,
            "verify_commit_light": ref_verify_commit_light,
        }[entry]
        got = outcome(getattr(vs, entry), CHAIN, bid, h, commit)
        want = outcome(lambda *a: ref(vs, *a), CHAIN, bid, h, commit)
        if with_one:
            assert got[0] == ("ok", None)
        else:
            assert got[0] == (
                "raised", f"insufficient voting power: {_NEEDED} <= {_NEEDED}"
            )
    assert got == want


def test_trusting_tally_at_the_limit():
    """All but validator 1 sign: the tally is MAX - 1, far past the
    trust level, and read exactly."""
    n = COLUMN_MIN_ROWS + 2
    vs = _limit_set(n)
    one = next(i for i, v in enumerate(vs.validators) if v.voting_power == 1)
    flags = [
        BlockIDFlag.ABSENT if i == one else BlockIDFlag.COMMIT
        for i in range(n)
    ]
    _, _, commit = make_commit(vs, 31, random.Random(1), flags=flags)
    got = outcome(vs.verify_commit_light_trusting, CHAIN, commit, 1, 1)
    want = ("raised", f"insufficient trusted voting power: "
            f"{MAX_TOTAL_VOTING_POWER - 1} <= {MAX_TOTAL_VOTING_POWER}")
    assert got[0] == want


# --- the types.gather span ---------------------------------------------


@pytest.fixture
def armed():
    before = default_tracer()
    tracer = set_default_tracer(Tracer(enabled=True))
    yield tracer
    set_default_tracer(before)


def _gathers(tracer) -> list:
    return [r.fields for r in tracer.records() if r.name == "types.gather"]


def test_gather_span_counts_columns_and_fallback(armed):
    n = COLUMN_MIN_ROWS + 8
    vs, (bid, h, commit) = commit_case("fallback_timestamps", n)
    vs.verify_commit_light(CHAIN, bid, h, commit, Recording())
    small, (sbid, sh, scommit) = commit_case("all_commit", 4)
    small.verify_commit(CHAIN, sbid, sh, scommit, Recording())
    assert _gathers(armed) == [
        {"rows": n, "columnar": n - 2, "fallback": 2},
        {"rows": 4, "columnar": 0, "fallback": 4},
    ]


def test_gather_span_one_per_call(armed):
    n = COLUMN_MIN_ROWS
    vs = make_set([10] * n)
    rng = random.Random(4)
    entries = [make_commit(vs, 40 + k, rng) for k in range(3)]
    entries.append((entries[0][0], 99, None))
    vs.verify_commits_light(CHAIN, entries, Recording())
    old, commit = _trusting_case("overlap", n)
    old.verify_commit_light_trusting(CHAIN, commit, verifier=Recording())
    rows = sum(1 for cs in commit.signatures if cs.for_block()
               and old.has_address(cs.validator_address))
    spans = _gathers(armed)
    assert spans[0] == {"rows": 3 * n, "columnar": 3 * n, "fallback": 0}
    assert spans[1]["rows"] == rows and len(spans) == 2


def test_gather_span_silent_when_disarmed():
    before = default_tracer()
    tracer = set_default_tracer(Tracer(enabled=False))
    try:
        vs, (bid, h, commit) = commit_case("all_commit", 40)
        vs.verify_commit_light(CHAIN, bid, h, commit, Recording())
        assert len(tracer) == 0
    finally:
        set_default_tracer(before)
