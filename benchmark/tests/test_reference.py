"""The plain reference and its control."""

import pytest

from harness import fixtures
from reference import canonical_vote, ed25519_plain


@pytest.fixture(scope="module")
def committee():
    return fixtures.Committee(11, 8)


def test_encoder_is_what_the_program_rebuilds(committee):
    from harness import program_objects

    rec = fixtures.sign_commit(committee, 7, {})
    _, _, commit = program_objects.entry(committee, rec)
    msgs = fixtures.messages(committee.seed, 7, committee.n)
    assert [
        commit.vote_sign_bytes(fixtures.CHAIN_ID, i) for i in range(8)
    ] == msgs
    assert {len(m) for m in msgs} <= {116, 117, 118}


def test_uvarint():
    assert canonical_vote.uvarint(0) == b"\x00"
    assert canonical_vote.uvarint(300) == b"\xac\x02"


@pytest.mark.parametrize("kind", fixtures.BAD_KINDS)
def test_both_verifiers_reject_each_bad_kind(committee, kind):
    height, sigs, plan = fixtures.sign_commit(committee, 3, {2: kind})
    msgs = fixtures.messages(committee.seed, height, committee.n)
    for i, (pub, msg, sig) in enumerate(zip(committee.pubs, msgs, sigs)):
        want = i != 2
        assert ed25519_plain.verify(pub, msg, sig) is want
        assert ed25519_plain.verify_rfc8032(pub, msg, sig) is want


def test_control_drops_exactly_the_s_range_rule(committee):
    plan = dict(zip(range(4), fixtures.BAD_KINDS))
    height, sigs, _ = fixtures.sign_commit(committee, 5, plan)
    msgs = fixtures.messages(committee.seed, height, committee.n)
    got = [
        ed25519_plain.verify(pub, msg, sig, s_range=False)
        for pub, msg, sig in zip(committee.pubs, msgs, sigs)
    ]
    accepted_bad = [plan[i] for i in plan if got[i]]
    assert accepted_bad == ["s_ge_L"]
    assert all(got[4:])


def test_quorum_is_more_than_two_thirds():
    powers = [10] * 9
    assert ed25519_plain.quorum([True] * 7 + [False] * 2, powers)
    assert not ed25519_plain.quorum([True] * 6 + [False] * 3, powers)
