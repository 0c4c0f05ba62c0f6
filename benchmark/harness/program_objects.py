"""Commit records turned into the program's own types: the only place
the load generators touch `tendermint_tpu.types`. What the program then
does with them (shape checks, sign-bytes gather, batching) is the timed
path. Key types are the committee kind's: a public key is built through
the program's own `pubkey_from_type`."""

from __future__ import annotations

from tendermint_tpu.crypto.batch_verifier import SigItem
from tendermint_tpu.types.block import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.validator import Validator, pubkey_from_type
from tendermint_tpu.types.validator_set import ValidatorSet

from harness import fixtures


def validator_set(validators: tuple) -> ValidatorSet:
    """The program's set of a committee's `validators(height)`."""
    vs = ValidatorSet(
        [
            Validator(pubkey_from_type(v.key_type, v.pub), v.power)
            for v in validators
        ]
    )
    if [(v.pub_key.data, v.address) for v in vs.validators] != [
        (v.pub, v.address) for v in validators
    ]:
        raise RuntimeError("validator-set order differs from the fixture's")
    return vs


def entry(committee, rec: tuple) -> tuple:
    """(block_id, height, Commit) as blocksync hands it to
    verify_commits_light: a signature for each signer, the others
    absent."""
    height, sigs, _ = rec
    bh = fixtures.block_hash(committee.seed, height)
    bid = BlockID(bh, PartSetHeader(1, bh))
    validators = committee.validators(height)
    commit_sigs = [CommitSig.absent()] * len(validators)
    for i, sig in zip(committee.signers(height), sigs):
        commit_sigs[i] = CommitSig(
            BlockIDFlag.COMMIT, validators[i].address,
            fixtures.timestamp_ns(height, i), sig,
        )
    return bid, height, Commit(height, 0, bid, commit_sigs)


def sig_items(committee, rec: tuple) -> list:
    """One commit's rows as a node submits them to the scheduler."""
    height, sigs, _ = rec
    validators = committee.validators(height)
    return [
        SigItem(validators[i].pub, msg, sig, validators[i].key_type)
        for i, msg, sig in zip(
            committee.signers(height), committee.sign_bytes(height), sigs
        )
    ]
