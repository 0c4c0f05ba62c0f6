"""The plain references and their controls."""

import random

import pytest
from cryptography.hazmat.primitives.asymmetric import ec

from committees import ed25519_equal, mixed_keys
from conftest import rehearsal_configs
from harness import fixtures
from reference import canonical_vote, ed25519_plain, secp256k1_plain

MIXED = rehearsal_configs()["mixed_keys"]


@pytest.fixture(scope="module")
def committee():
    return ed25519_equal.Committee(11, {"validators": 8})


@pytest.fixture(scope="module")
def mixed():
    return mixed_keys.Committee(11, MIXED)


@pytest.mark.parametrize("which", ["committee", "mixed"])
def test_encoder_is_what_the_program_rebuilds(which, request):
    from harness import program_objects

    c = request.getfixturevalue(which)
    rec = c.sign_commit(7, {})
    _, _, commit = program_objects.entry(c, rec)
    msgs = c.sign_bytes(7)
    assert [
        commit.vote_sign_bytes(fixtures.CHAIN_ID, i) for i in range(8)
    ] == msgs
    assert {len(m) for m in msgs} <= {116, 117, 118}
    # the program orders the set as the kind did, and takes each key
    # for the type the kind gave it
    vs = program_objects.validator_set(c.validators(7))
    assert [v.pub_key.type_name for v in vs.validators] == [
        v.key_type for v in c.validators(7)
    ]
    items = program_objects.sig_items(c, rec)
    assert [it.key_type for it in items] == [
        v.key_type for v in c.validators(7)
    ]
    assert all(
        v.pub_key.verify(it.msg, it.sig)
        for v, it in zip(vs.validators, items)
    )


def test_a_validator_that_does_not_sign_is_absent_in_the_commit(committee):
    """A kind whose signers are not everyone is a new file: the harness
    already hands the program the signers' rows and leaves the others
    absent."""
    from harness import program_objects

    class SevenSign(ed25519_equal.Committee):
        def signers(self, height):
            return [i for i in range(8) if i != 3]

        def sign_bytes(self, height):
            return fixtures.sign_bytes(self.seed, height, self.signers(height))

        def sign_commit(self, height, plan):
            msgs = self.sign_bytes(height)
            keys = [self.keys[i] for i in self.signers(height)]
            return height, [k.sign(m) for k, m in zip(keys, msgs)], plan

    c = SevenSign(11, {"validators": 8})
    rec = c.sign_commit(9, {})
    _, _, commit = program_objects.entry(c, rec)
    assert [s.is_absent() for s in commit.signatures] == [
        i == 3 for i in range(8)
    ]
    items = program_objects.sig_items(c, rec)
    assert [it.pubkey for it in items] == [
        v.pub for i, v in enumerate(c.validators(9)) if i != 3
    ]
    assert [it.msg for it in items] == [
        commit.vote_sign_bytes(fixtures.CHAIN_ID, i) for i in c.signers(9)
    ]
    assert fixtures.rows_by_key_type(c, [9, 10]) == {"ed25519": 14}


def test_uvarint():
    assert canonical_vote.uvarint(0) == b"\x00"
    assert canonical_vote.uvarint(300) == b"\xac\x02"


@pytest.mark.parametrize("kind", ed25519_equal.BAD_KINDS["ed25519"])
def test_both_verifiers_reject_each_bad_kind(committee, kind):
    height, sigs, plan = committee.sign_commit(3, {2: kind})
    msgs = committee.sign_bytes(height)
    pubs = [v.pub for v in committee.validators(height)]
    for i, (pub, msg, sig) in enumerate(zip(pubs, msgs, sigs)):
        want = i != 2
        assert ed25519_plain.verify(pub, msg, sig) is want
        assert ed25519_plain.verify_rfc8032(pub, msg, sig) is want


def test_control_drops_exactly_the_s_range_rule(committee):
    plan = dict(zip(range(4), ed25519_equal.BAD_KINDS["ed25519"]))
    height, sigs, _ = committee.sign_commit(5, plan)
    (got,) = committee.reference([(height, sigs)], "s_range")
    accepted_bad = [plan[i] for i in plan if got[i]]
    assert accepted_bad == ["s_ge_L"]
    assert all(got[4:])


def test_quorum_is_more_than_two_thirds():
    powers = [10] * 9
    assert ed25519_plain.quorum([True] * 7 + [False] * 2, powers)
    assert not ed25519_plain.quorum([True] * 6 + [False] * 3, powers)


# --- secp256k1 ---------------------------------------------------------------


def secp_rows(seed: int, count: int) -> list:
    """Seeded (pub, msg, sig, bad kind or None): keys, messages and
    signatures by the mixed kind's own signer, every fifth row made one
    of the four bad kinds in turn."""
    rng = random.Random(seed)
    keys = [
        ec.derive_private_key(rng.randrange(1, secp256k1_plain.N),
                              ec.SECP256K1())
        for _ in range(8)
    ]
    pubs = [mixed_keys.compressed(k) for k in keys]
    rows = []
    for j in range(count):
        msgs = [rng.randbytes(rng.randrange(1, 200)) for _ in keys]
        genuine = [mixed_keys.secp_sign(k, m) for k, m in zip(keys, msgs)]
        i = j % 8
        kind = (
            mixed_keys.BAD_KINDS["secp256k1"][(j // 5) % 4]
            if j % 5 == 4 else None
        )
        sig = (
            mixed_keys.corrupt(genuine, i, kind, "secp256k1")
            if kind else genuine[i]
        )
        rows.append((pubs[i], msgs[i], sig, kind))
    return rows


def test_secp256k1_plain_agrees_with_openssl_on_200_seeded_rows():
    rows = secp_rows(41, 200)
    assert {k for _, _, _, k in rows} == {
        None, *mixed_keys.BAD_KINDS["secp256k1"]
    }
    for pub, msg, sig, kind in rows:
        want = kind is None
        assert secp256k1_plain.verify_plain(pub, msg, sig) is want, kind
        assert secp256k1_plain.verify(pub, msg, sig) is want, kind
        # the control differs from the reference on high_s alone
        assert secp256k1_plain.verify(pub, msg, sig, low_s=False) is (
            want or kind == "high_s"
        )


def test_secp256k1_plain_refuses_what_the_reference_node_refuses():
    pub, msg, sig, _ = secp_rows(43, 1)[0]
    n = secp256k1_plain.N
    r, s = sig[:32], sig[32:]
    high_s = r + (n - int.from_bytes(s, "big")).to_bytes(32, "big")
    off_curve = next(  # an x with no point on the curve
        b"\x02" + x.to_bytes(32, "big") for x in range(1, 50)
        if secp256k1_plain.decode_key(b"\x02" + x.to_bytes(32, "big")) is None
    )
    refused = {
        "high_s": (pub, high_s),
        "r = 0": (pub, bytes(32) + s),
        "s = 0": (pub, r + bytes(32)),
        "s = N": (pub, r + n.to_bytes(32, "big")),
        "r = N": (pub, n.to_bytes(32, "big") + s),
        "63-byte signature": (pub, sig[:63]),
        "65-byte signature": (pub, sig + b"\x00"),
        "32-byte key": (pub[1:], sig),
        "uncompressed prefix": (b"\x04" + pub[1:], sig),
        "off-curve key": (off_curve, sig),
        "x >= p": (b"\x02" + b"\xff" * 32, sig),
    }
    assert secp256k1_plain.verify_plain(pub, msg, sig)
    assert secp256k1_plain.verify(pub, msg, sig)
    for why, (k, g) in refused.items():
        assert not secp256k1_plain.verify_plain(k, msg, g), why
        assert not secp256k1_plain.verify(k, msg, g), why
        if why != "high_s":
            assert not secp256k1_plain.verify(k, msg, g, low_s=False), why


def test_the_programs_own_signer_agrees_as_a_third_party(mixed):
    """`tendermint_tpu/crypto/secp256k1.py` (RFC 6979, low S) signs the
    same bytes for the same key, derives the same key and address, and
    its verifier takes the kind's signatures: a witness, not the
    definition."""
    from tendermint_tpu.crypto import secp256k1 as program

    seen = 0
    sigs = mixed.sign_commit(3, {})[1]
    for v, key, msg, sig in zip(
        mixed.validators(3), mixed.keys, mixed.sign_bytes(3), sigs
    ):
        if v.key_type != "secp256k1":
            continue
        seen += 1
        priv = program.PrivKey(key.private_numbers().private_value)
        assert priv.public_key().data == v.pub
        assert priv.public_key().address() == v.address
        assert v.address == secp256k1_plain.address(v.pub)
        assert priv.sign(msg) == sig
        assert program.PubKey(v.pub).verify(msg, sig)
    assert seen == 2
    for pub, msg, sig, kind in secp_rows(47, 40):
        assert program.PubKey(pub).verify(msg, sig) is (kind is None)


@pytest.mark.parametrize("kind", mixed_keys.BAD_KINDS["secp256k1"])
def test_a_mixed_committee_rejects_each_bad_kind_of_each_key_type(mixed, kind):
    types = [v.key_type for v in mixed.validators(3)]
    row = {t: types.index(t) for t in ("ed25519", "secp256k1")}
    position = mixed_keys.BAD_KINDS["secp256k1"].index(kind)
    plan = {i: mixed.bad_kinds(3, i)[position] for i in row.values()}
    assert plan[row["secp256k1"]] == kind
    height, sigs, _ = mixed.sign_commit(3, plan)
    (got,) = mixed.reference([(height, sigs)])
    assert got == [i not in plan for i in range(8)]
    rows = [((height, sigs, plan), i) for i in range(8)]
    assert mixed.cross_check(rows, {height: got}) == {
        "rfc8032_vs_openssl": 0, "secp256k1_plain_vs_openssl": 0,
    }
    # each control lets through the one kind it is named for, on the
    # key type that has it
    for control, let in (("s_range", "s_ge_L"), ("low_s", "high_s")):
        (loose,) = mixed.reference([(height, sigs)], control)
        assert [i for i in plan if loose[i]] == [
            i for i in plan if plan[i] == let
        ]
