"""The seam between the harness and a committee kind: the default kind
is the code it replaced, byte for byte; every kind keeps the contract
of `committees/__init__.py`; the harness names no key type."""

import hashlib
import inspect
import json
import os
import re

import pytest

import committees
from conftest import rehearsal_configs
from harness import fixtures
from harness.cell import BENCH_DIR

KINDS = sorted(
    f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, "committees"))
    if f.endswith(".py") and f != "__init__.py"
)
CONFIGS = sorted(os.listdir(os.path.join(BENCH_DIR, "configs")))
REHEARSAL = rehearsal_configs()

# computed on the parent commit (c7235fd, `harness/fixtures.py` as it
# was: Committee, plan_window, plan_request, build_unit, reference_unit
# with s_range True then False), before anything was moved
PARENT_DIGESTS = {
    (1, 8): "066bdfe6eb9d3e825edc4d63d8e2150b9fe2c67564fcdd0d98d7a1832b0f9c42",
    (1, 128): "82128704039feb4f82fced2e85267cc90bf4dca4a158bd4e5da1ab54fc2c2077",
    (2, 8): "baf8043aa6f7b5a5a9ce7a25ab7e20b9af70410672b980cdd024c8adeddcee41",
    (2, 128): "52c7cd66820d1c003c6d75345c46bf2141486d33618c5bec226c88214880210c",
}


def digest(seed: int, config: dict) -> str:
    """SHA-256 over the public keys and addresses, the signatures and
    plans of one 4-commit `plan_window` window and of sixteen
    `plan_request` requests (every fourth bad), and the verdicts of the
    reference and of each control, through the pool worker's calls."""
    fixtures.init_worker(seed, config)
    c = committees.load(config).Committee(seed, config)
    h = hashlib.sha256()
    for v in c.validators(1):
        h.update(v.pub + v.address)
    heights = [1, 2, 3, 4]
    specs = list(zip(heights, fixtures.plan_window(c, seed, 0, heights)))
    specs += [
        (101 + r, fixtures.plan_request(c, seed, r, 101 + r, 4))
        for r in range(16)
    ]
    recs = fixtures.build_unit(specs)
    for height, sigs, plan in recs:
        h.update(b"%d|" % height)
        for sig in sigs:
            h.update(bytes([len(sig)]) + sig)
        h.update(json.dumps(sorted(plan.items())).encode())
    commits = [(height, sigs) for height, sigs, _ in recs]
    for control in ("",) + tuple(committees.load(config).CONTROLS):
        for verdicts in fixtures.reference_unit((commits, control)):
            h.update(bytes(verdicts))
    return h.hexdigest()


@pytest.mark.parametrize("seed,n", sorted(PARENT_DIGESTS))
def test_the_default_kind_is_the_parents_code_byte_for_byte(seed, n):
    assert committees.DEFAULT == "ed25519_equal"
    assert digest(seed, {"validators": n}) == PARENT_DIGESTS[seed, n]


def test_a_seed_fixes_a_mixed_committee_too():
    config = REHEARSAL["mixed_keys"]
    assert digest(5, config) == digest(5, config)
    assert digest(5, config) != digest(6, config)


@pytest.mark.parametrize("name", CONFIGS)
def test_every_committee_a_configuration_names_exists(name):
    with open(os.path.join(BENCH_DIR, "configs", name)) as f:
        config = json.load(f)
    assert config.get("committee", committees.DEFAULT) in KINDS
    assert committees.load(config).Committee


@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_keeps_the_contract(kind):
    """What `committees/__init__.py` lists, with the arguments it
    lists, and answers of the shapes the harness reads."""
    module = committees.load({"committee": kind})
    assert isinstance(module.CONTROLS, tuple) and module.CONTROLS
    assert all(isinstance(k, tuple) for k in module.BAD_KINDS.values())
    wanted = {
        "validators": ["height"], "signers": ["height"],
        "sign_bytes": ["height"], "bad_kinds": ["height", "row"],
        "sign_commit": ["height", "plan"],
        "reference": ["commits", "control"],
        "quorum": ["height", "valid"],
        "cross_check": ["rows", "reference"],
    }
    for name, args in wanted.items():
        method = getattr(module.Committee, name)
        assert list(inspect.signature(method).parameters)[1:] == args
    assert list(inspect.signature(module.Committee).parameters) == [
        "seed", "config"
    ]
    assert kind in REHEARSAL, "give the kind a configs/rehearsal-*.json"
    c = module.Committee(3, REHEARSAL[kind])
    assert c.seed == 3
    validators = c.validators(7)
    assert validators is c.validators(7) and hash(validators)
    assert [v.address for v in validators] == sorted(
        v.address for v in validators
    )
    assert all(v.key_type in module.BAD_KINDS for v in validators)
    signers = c.signers(7)
    assert signers == sorted(set(signers))
    assert len(c.sign_bytes(7)) == len(signers)
    for row in range(len(signers)):
        kinds = c.bad_kinds(7, row)
        assert kinds == module.BAD_KINDS[validators[signers[row]].key_type]
    plan = {0: c.bad_kinds(7, 0)[0]}
    height, sigs, planned = c.sign_commit(7, plan)
    assert (height, planned, len(sigs)) == (7, plan, len(signers))
    (good,) = c.reference([(7, sigs)])
    assert good == [False] + [True] * (len(signers) - 1)
    assert c.quorum(7, good) and not c.quorum(7, [False] * len(good))
    for control in module.CONTROLS:
        assert len(c.reference([(7, sigs)], control)[0]) == len(signers)
    with pytest.raises(ValueError):
        c.reference([(7, sigs)], "no_such_guarantee")
    checks = c.cross_check([((height, sigs, plan), 0)], {7: good})
    assert checks and all(v == 0 for v in checks.values())


WORDS = re.compile(r"ed25519|secp256k1|s_range")


def test_the_harness_names_no_key_type_scheme_or_guarantee():
    """`grep -rnE "ed25519|secp256k1|s_range" benchmark/harness
    benchmark/generators benchmark/run.py` prints nothing."""
    files = [os.path.join(BENCH_DIR, "run.py")]
    for d in ("harness", "generators"):
        files += [
            os.path.join(BENCH_DIR, d, f)
            for f in os.listdir(os.path.join(BENCH_DIR, d))
            if f.endswith(".py")
        ]
    assert len(files) > 10
    found = []
    for path in files:
        with open(path) as f:
            for k, line in enumerate(f, 1):
                if WORDS.search(line):
                    found.append(f"{os.path.relpath(path, BENCH_DIR)}:{k}")
    assert found == []
