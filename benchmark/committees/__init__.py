"""Committee kinds: what a configuration's validators are, how they
sign, and what the plain reference answers for their rows.

`configs/<name>.json` may carry `"committee": "<kind>"`; the kind is
`committees/<kind>.py`. A configuration without the key gets `DEFAULT`.
The harness, the generators and `run.py` name no key type, signature
scheme, bad-row kind or guarantee: they ask the cell's kind. A later PR
brings a new kind as a new file; this is the contract it is held to
(`tests/test_committees.py` holds every file here to it).

A kind is a module with

    CONTROLS    the names `--control-guarantee` takes: each is one
                guarantee of the configuration that `reference` can drop
    BAD_KINDS   {key type: the kinds of bad row a validator of that type
                can be given}, for the record; `Committee.bad_kinds` is
                what the planners ask
    Committee(seed, config)   the committee of a run. `config` is the
                whole configuration file, `seed` is `--seed`: the same
                two give the same keys, order, sign-bytes, signatures

Every question to a `Committee` is asked per height, so that absent
signers, skewed power or a second validator set are a new kind and not
an edit of the harness. A "row" is a position among the signers of one
commit, which is the order the program is handed them in.

    seed
    validators(height)   tuple of `fixtures.Validator(key_type, pub,
                address, power)` in validator-set order, the order the
                program's own `ValidatorSet` must arrive at. The same
                tuple object for heights that share a set
    signers(height)      indices into `validators(height)`, ascending:
                who signs the commit at that height
    sign_bytes(height)   what each signer signs, in that order
    bad_kinds(height, row)   the kinds that exist for that row, in the
                order the planners deal them out
    sign_commit(height, plan)   -> (height, sigs, plan): one signature
                a signer, the rows in `plan` ({row: kind}) made bad
    reference(commits, control="")   the plain reference's verdicts for
                [(height, sigs)], one list of bools per commit; with
                `control` one of CONTROLS, the same without that one
                guarantee (the CONTROL of `correct`, never a reference)
    quorum(height, valid)    whether the commit stands on those verdicts
    cross_check(rows, reference)   {check name: rows that disagree}: an
                independent implementation held against `reference`'s
                answers on [(commit record, row)], a seeded sample

Nothing in a kind imports the program or JAX: pool workers build one
`Committee` each and sign and judge on the host's cores.
"""

from __future__ import annotations

import importlib

DEFAULT = "ed25519_equal"


def load(config: dict):
    """The kind a configuration names, as a module."""
    return importlib.import_module(
        "committees." + config.get("committee", DEFAULT)
    )


# --- what kinds share ---------------------------------------------------------


def check_control(control: str, controls: tuple) -> None:
    if control and control not in controls:
        raise ValueError(
            f"no control {control!r}: this kind has {', '.join(controls)}"
        )


def cross_check_rows(committee, rows: list, reference: dict,
                     key_type: str, verify) -> int:
    """Rows of `key_type` among [(commit record, row)] on which `verify`
    (an independent implementation) disagrees with `reference`."""
    wrong = 0
    for (height, sigs, _), i in rows:
        v = committee.validators(height)[committee.signers(height)[i]]
        if v.key_type != key_type:
            continue
        msg = committee.sign_bytes(height)[i]
        wrong += verify(v.pub, msg, sigs[i]) != reference[height][i]
    return wrong
