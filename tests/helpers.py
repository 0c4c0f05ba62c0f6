"""Shared test factories — the analog of the reference's internal/test
builders (internal/test/block.go, vote.go, ...)."""

from __future__ import annotations

import time

import numpy as np

from tendermint_tpu.types.block import Block, Commit
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.priv_validator import MockPV
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote, VoteType
from tendermint_tpu.types.vote_set import VoteSet

CHAIN_ID = "test-chain"
T0 = 1_700_000_000_000_000_000


def make_validators(n: int, power: int = 10, seed: bytes = b"val"):
    """(ValidatorSet, [MockPV]) with privvals ordered to match the set."""
    pvs = [MockPV.from_secret(seed + b"%d" % i) for i in range(n)]
    vs = ValidatorSet([Validator(pv.get_pub_key(), power) for pv in pvs])
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    return vs, [by_addr[v.address] for v in vs.validators]


def make_weighted_validators(powers, seed: bytes = b"val"):
    """Like make_validators but with per-validator voting powers; the
    returned privvals are ordered to match the SORTED set, so pvs[i] is
    validator index i (a quorum-attribution test needs one validator
    whose vote every 2/3 requires)."""
    pvs = [MockPV.from_secret(seed + b"%d" % i) for i in range(len(powers))]
    vs = ValidatorSet(
        [Validator(pv.get_pub_key(), p) for pv, p in zip(pvs, powers)]
    )
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    return vs, [by_addr[v.address] for v in vs.validators]


def make_genesis(vs: ValidatorSet, chain_id: str = CHAIN_ID) -> GenesisDoc:
    doc = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=T0,
        validators=[
            GenesisValidator(
                "ed25519", v.pub_key.data, v.voting_power,
                bls_pub_key=v.bls_pub_key,
            )
            for v in vs.validators
        ],
    )
    doc.validate_and_complete()
    return doc


def make_qc_validators(n: int, power: int = 10, seed: bytes = b"val"):
    """(ValidatorSet, [MockPV], {address: bls_priv}) — a QC-capable
    committee: every validator carries a BLS key committed into the set
    hash, and the returned scalar map signs QC contributions.
    Deterministic in `seed` (BLS scalars derive from it, not from
    generate_priv_key), so two calls build the same committee."""
    from tendermint_tpu.crypto import bls_signatures as bls
    from tendermint_tpu.crypto.bls12_381 import R

    pvs = [MockPV.from_secret(seed + b"%d" % i) for i in range(n)]
    vals, privs = [], {}
    for i, pv in enumerate(pvs):
        import hashlib

        scalar = (
            int.from_bytes(
                hashlib.sha256(seed + b"bls%d" % i).digest(), "big"
            )
            % (R - 1)
            + 1
        )
        pub = bls.pubkey_from_priv(scalar)
        addr = pv.get_pub_key().address()
        privs[addr] = scalar
        vals.append(
            Validator(
                pv.get_pub_key(), power,
                bls_pub_key=bls.g2_to_bytes(pub.key),
            )
        )
    vs = ValidatorSet(vals)
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    return vs, [by_addr[v.address] for v in vs.validators], privs


def sign_commit(
    vs: ValidatorSet,
    pvs: list,
    height: int,
    round_: int,
    block_id: BlockID,
    chain_id: str = CHAIN_ID,
    time_ns: int = T0,
    bls_privs: dict | None = None,
) -> Commit:
    """All validators precommit block_id; returns the Commit. With
    `bls_privs` (make_qc_validators' scalar map) every vote also
    carries a QC dual-signature, so the commit compresses via
    assemble_qc."""
    votes = VoteSet(chain_id, height, round_, VoteType.PRECOMMIT, vs)
    qc_msg = None
    if bls_privs is not None:
        from tendermint_tpu.types.quorum_cert import qc_sign_bytes

        qc_msg = qc_sign_bytes(chain_id, height, round_, block_id)
    for i, pv in enumerate(pvs):
        v = Vote(
            type=VoteType.PRECOMMIT,
            height=height,
            round=round_,
            block_id=block_id,
            timestamp_ns=time_ns + i,
            validator_address=pv.get_pub_key().address(),
            validator_index=i,
        )
        pv.sign_vote(chain_id, v)
        if qc_msg is not None:
            from tendermint_tpu.crypto import bls_signatures as bls

            v.qc_signature = bls.g1_to_bytes(
                bls.sign(bls_privs[v.validator_address], qc_msg)
            )
        votes.add_vote(v, verified=True)
    return votes.make_commit()


class KeyedVerifier:
    """Stub rounds (verdict = sig starts with b'1') whose table store
    takes in every key it has not seen, as the verifier's stores do."""

    def __init__(self):
        self.keys: set = set()

    def verify(self, items):
        self.keys.update(it.pubkey for it in items)
        return np.array([it.sig[:1] == b"1" for it in items], dtype=bool)

    def table_store_stats(self):
        return {"small": {"keys_resident": len(self.keys)}}


class FakeGC:
    """A stand-in for the `gc` module, for a collector policy under test:
    its calls land here in order, and the process's own collector keeps
    its thresholds and freeze count."""

    def __init__(self, threshold=(700, 10, 10)):
        self.threshold = tuple(threshold)
        self.calls: list[str] = []
        self.frozen = 0

    def get_threshold(self):
        return self.threshold

    def set_threshold(self, *threshold):
        self.calls.append("set_threshold")
        self.threshold = threshold

    def collect(self):
        self.calls.append("collect")
        return 0

    def freeze(self):
        self.calls.append("freeze")
        self.frozen += 1000

    def get_freeze_count(self):
        return self.frozen

    def get_count(self):
        return (0, 0, 0)

    def get_stats(self):
        return [{"collections": 0, "collected": 0, "uncollectable": 0}] * 3
