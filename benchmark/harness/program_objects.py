"""Commit records turned into the program's own types: the only place
the load generators touch `tendermint_tpu.types`. What the program then
does with them (shape checks, sign-bytes gather, batching) is the timed
path."""

from __future__ import annotations

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.crypto.batch_verifier import SigItem
from tendermint_tpu.types.block import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

from harness import fixtures


def validator_set(committee: fixtures.Committee) -> ValidatorSet:
    vs = ValidatorSet(
        [
            Validator(ed25519.PubKey(pub), power)
            for pub, power in zip(committee.pubs, committee.powers)
        ]
    )
    if [v.pub_key.data for v in vs.validators] != committee.pubs:
        raise RuntimeError("validator-set order differs from the fixture's")
    return vs


def entry(committee: fixtures.Committee, rec: tuple) -> tuple:
    """(block_id, height, Commit) as blocksync hands it to
    verify_commits_light."""
    height, sigs, _ = rec
    bh = fixtures.block_hash(committee.seed, height)
    bid = BlockID(bh, PartSetHeader(1, bh))
    commit = Commit(
        height, 0, bid,
        [
            CommitSig(
                BlockIDFlag.COMMIT, addr,
                fixtures.timestamp_ns(height, i), sig,
            )
            for i, (addr, sig) in enumerate(zip(committee.addresses, sigs))
        ],
    )
    return bid, height, commit


def sig_items(committee: fixtures.Committee, rec: tuple) -> list:
    """One commit's rows as a node submits them to the scheduler."""
    height, sigs, _ = rec
    msgs = fixtures.messages(committee.seed, height, committee.n)
    return [
        SigItem(pub, msg, sig)
        for pub, msg, sig in zip(committee.pubs, msgs, sigs)
    ]
