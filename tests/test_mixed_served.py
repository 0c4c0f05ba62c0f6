"""Mixed-key commits (BASELINE.json configs[3]: ed25519 and secp256k1
validators in one set) through the served path: RemoteVerifyScheduler
-> VerifyServiceServer -> VerifyScheduler -> BatchVerifier, whose
`_verify_mixed` sends the ed25519 rows to the device batch and the
secp256k1 rows to the host engine inside the same round.

Row bitmaps and per-commit verdicts are held to the benchmark's plain
references (`benchmark/reference/ed25519_plain.py`,
`secp256k1_plain.py`, which import nothing of the program), with every
bad-row kind of both key types planted. Also pinned: the spans of the
secp256k1 share (`crypto.secp_verify` around `crypto.secp_prep`), the
ed25519 rows' `crypto.ed_prep`, and the dispatch ledger's booking of a
mixed round against an ed25519 one.
No secp256k1 device program is compiled here.
"""

from __future__ import annotations

import asyncio
import hashlib
import importlib.util
import os
import random

import pytest

from tendermint_tpu.crypto import ed25519, secp256k1
from tendermint_tpu.crypto.batch_verifier import (
    SECP_PREPARED_MIN,
    BatchVerifier,
    SigItem,
)
from tendermint_tpu.crypto.shape_registry import default_shape_registry
from tendermint_tpu.obs import tracer as tracer_mod
from tendermint_tpu.obs.ledger import DispatchLedger
from tendermint_tpu.obs.tracer import Tracer
from tendermint_tpu.parallel.scheduler import VerifyScheduler
from tendermint_tpu.parallel.verify_service import ServiceThread
from tendermint_tpu.types.block import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

from .test_verify_service import connect

pytestmark = pytest.mark.verify_service

REF_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "reference",
)


def _reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{name}", os.path.join(REF_DIR, name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ed_ref = _reference("ed25519_plain")
secp_ref = _reference("secp256k1_plain")

CHAIN = "mixed-chain"
VALIDATORS = 24
SECP_SHARE = 0.25
POWER = 10
T0 = 1_700_000_000_000_000_000
ED, SECP = "ed25519", "secp256k1"

# height -> {row: bad kind}: heights 1 and 2 keep their quorum with one
# bad row of each kind of both key types; height 3 loses it (9 of 24
# rows bad, more than a third of the power); height 4 is clean
PLANTS = {
    1: {ED: ("flipped_bit", "s_ge_L"), SECP: ("high_s", "wrong_key")},
    2: {ED: ("wrong_key", "short_sig"), SECP: ("flipped_bit", "short_sig")},
    3: {ED: ("flipped_bit", "s_ge_L", "wrong_key", "short_sig", "s_ge_L"),
        SECP: ("high_s", "high_s", "flipped_bit", "wrong_key")},
    4: {ED: (), SECP: ()},
}


class Committee:
    """VALIDATORS seeded keys, SECP_SHARE of them secp256k1, equal
    power, in the program's validator-set order."""

    def __init__(self, seed: int = 38):
        rng = random.Random(seed)
        secp = set(
            rng.sample(range(VALIDATORS), round(SECP_SHARE * VALIDATORS))
        )
        privs = []
        for i in range(VALIDATORS):
            secret = hashlib.sha256(b"mixed|%d|%d" % (seed, i)).digest()
            privs.append(
                secp256k1.PrivKey.from_secret(secret) if i in secp
                else ed25519.PrivKey.from_secret(secret)
            )
        self.vs = ValidatorSet(
            [Validator(p.public_key(), POWER) for p in privs]
        )
        by_addr = {p.public_key().address(): p for p in privs}
        self.privs = [by_addr[v.address] for v in self.vs.validators]
        self.types = [
            SECP if isinstance(p, secp256k1.PrivKey) else ED
            for p in self.privs
        ]
        self.pubs = [v.pub_key.data for v in self.vs.validators]
        self.rng = rng

    def commit(self, height: int):
        """(entry for verify_commits_light, sign-bytes, sigs, plan)."""
        bh = hashlib.sha256(b"block|%d" % height).digest()
        bid = BlockID(bh, PartSetHeader(1, bh))

        def build(sigs):
            return Commit(height, 0, bid, [
                CommitSig(
                    BlockIDFlag.COMMIT, v.address,
                    T0 + height * 10**9 + i, s,
                )
                for i, (v, s) in enumerate(zip(self.vs.validators, sigs))
            ])

        blank = build([b""] * VALIDATORS)
        msgs = [blank.vote_sign_bytes(CHAIN, i) for i in range(VALIDATORS)]
        sigs = [p.sign(m) for p, m in zip(self.privs, msgs)]
        plan = {}
        for key_type, kinds in PLANTS[height].items():
            rows = [i for i, t in enumerate(self.types) if t == key_type]
            for row, kind in zip(self.rng.sample(rows, len(kinds)), kinds):
                plan[row] = kind
                sigs[row] = self.corrupt(row, kind, msgs[row], sigs[row])
        return (bid, height, build(sigs)), msgs, sigs, plan

    def corrupt(self, row: int, kind: str, msg: bytes, sig: bytes) -> bytes:
        if kind == "flipped_bit":
            return sig[:5] + bytes([sig[5] ^ 0x10]) + sig[6:]
        if kind == "short_sig":
            return sig[:63]
        if kind == "wrong_key":
            other = next(
                p for j, p in enumerate(self.privs)
                if j != row and self.types[j] == self.types[row]
            )
            return other.sign(msg)
        if kind == "s_ge_L":  # the malleable twin (R, s + L)
            s = int.from_bytes(sig[32:], "little") + ed_ref.L
            return sig[:32] + s.to_bytes(32, "little")
        if kind == "high_s":  # valid ECDSA, refused as malleable
            s = secp_ref.N - int.from_bytes(sig[32:], "big")
            return sig[:32] + s.to_bytes(32, "big")
        raise ValueError(kind)

    def reference(self, pubs, msgs, sigs, types) -> list:
        return [
            (secp_ref if t == SECP else ed_ref).verify(p, m, s)
            for p, m, s, t in zip(pubs, msgs, sigs, types)
        ]


class _Spy:
    """The classed verifier, keeping the row bitmap it returned."""

    def __init__(self, inner):
        self.inner = inner
        self.bits = []

    def verify(self, items):
        out = self.inner.verify(items)
        self.bits.extend(bool(b) for b in out)
        return out


@pytest.fixture(scope="module")
def committee():
    return Committee()


@pytest.fixture(scope="module")
def window(committee):
    return [committee.commit(h) for h in sorted(PLANTS)]


@pytest.fixture
def ring(monkeypatch):
    ring = Tracer(enabled=True)
    monkeypatch.setattr(tracer_mod, "_default", ring)
    return ring


def served(tmp_path, ledger) -> ServiceThread:
    """A verify service over the real BatchVerifier: the ed25519 rows
    of every round on the (CPU) device program, the secp256k1 rows on
    the host engine."""
    os.makedirs(str(tmp_path), exist_ok=True)
    svc = ServiceThread(
        os.path.join(str(tmp_path), "verify.sock"),
        scheduler=VerifyScheduler(
            verifier=BatchVerifier(min_device_batch=0), ledger=ledger
        ),
    )
    svc.start()
    return svc


def run_window(svc, committee, window):
    """One catch-up window through verify_commits_light on the remote
    scheduler: (per-commit verdicts, row bitmap)."""

    async def run():
        remote = await connect(svc.server.path)
        try:
            spy = _Spy(remote.classed("blocksync"))
            loop = asyncio.get_running_loop()
            verdicts = await loop.run_in_executor(
                None,
                lambda: committee.vs.verify_commits_light(
                    CHAIN, [w[0] for w in window], verifier=spy
                ),
            )
            return verdicts, spy.bits
        finally:
            await remote.stop()

    return asyncio.run(run())


def submit(svc, items):
    async def run():
        remote = await connect(svc.server.path)
        try:
            return (await remote.submit(items, "blocksync")).tolist()
        finally:
            await remote.stop()

    return asyncio.run(run())


def test_mixed_window_matches_the_plain_references(
    tmp_path, committee, window, ring
):
    """Every row of a 4-commit window, and every commit's quorum, as
    the references decide them; each bad-row kind of both key types is
    refused, and the secp256k1 share is one crypto.secp_verify on the
    host around its crypto.secp_prep."""
    ledger = DispatchLedger()
    svc = served(tmp_path, ledger)
    try:
        verdicts, bits = run_window(svc, committee, window)
    finally:
        svc.stop()
    want_rows, want_commits = [], []
    for _, msgs, sigs, plan in window:
        ref = committee.reference(committee.pubs, msgs, sigs, committee.types)
        assert [i for i, ok in enumerate(ref) if not ok] == sorted(plan)
        want_rows += ref
        want_commits.append(ed_ref.quorum(ref, [POWER] * VALIDATORS))
    assert bits == want_rows
    assert verdicts == want_commits == [True, True, False, True]
    kinds = {k for _, _, _, plan in window for k in plan.values()}
    assert kinds == {
        "flipped_bit", "wrong_key", "s_ge_L", "high_s", "short_sig"
    }

    secp_rows = [
        ok for ok, t in zip(want_rows, committee.types * len(window))
        if t == SECP
    ]
    spans = {r.name: r for r in ring.records()}
    verify, prep = spans["crypto.secp_verify"], spans["crypto.secp_prep"]
    assert [r.name for r in ring.records()].count("crypto.secp_verify") == 1
    assert verify.fields["rows"] == len(secp_rows) == 4 * 6
    assert verify.fields["engine"] == "host"
    assert verify.fields["rejected"] == secp_rows.count(False)
    assert prep.fields["parent"] == "crypto.secp_verify"
    assert prep.fields["rows"] == len(secp_rows)
    assert verify.t0 <= prep.t0
    assert prep.t0 + prep.dur <= verify.t0 + verify.dur


@pytest.mark.parametrize("secp_copies", [1, 2], ids=["host", "prepared"])
def test_mixed_round_prepares_its_ed25519_rows_under_one_span(
    tmp_path, committee, window, ring, secp_copies
):
    """One `crypto.ed_prep` (`rows`: its ed25519 rows) a mixed round,
    inside the round's `scheduler.host_prep`, whether its secp256k1
    rows take one host call (fewer than SECP_PREPARED_MIN) or are
    prepared with the round; none in a round of ed25519 rows alone."""
    rows = []
    for _, msgs, sigs, _ in window:
        ref = committee.reference(committee.pubs, msgs, sigs, committee.types)
        rows += [
            (SigItem(p, m, s, t), ok)
            for p, m, s, t, ok in zip(
                committee.pubs, msgs, sigs, committee.types, ref
            )
        ]
    ed = [r for r in rows if r[0].key_type == ED]
    secp = [r for r in rows if r[0].key_type == SECP]
    mixed = ed + secp * secp_copies
    assert (len(secp) * secp_copies >= SECP_PREPARED_MIN) == (
        secp_copies == 2
    )
    ledger = DispatchLedger()
    svc = served(tmp_path, ledger)
    try:
        got = submit(svc, [it for it, _ in mixed])
        submit(svc, [it for it, _ in ed])
    finally:
        svc.stop()
    assert got == [ok for _, ok in mixed]
    assert ledger.totals()["rounds"] == 2
    recs = ring.records()
    (prep,) = [r for r in recs if r.name == "crypto.ed_prep"]
    assert prep.fields["rows"] == len(ed)
    round_prep = min(
        (r for r in recs if r.name == "scheduler.host_prep"),
        key=lambda r: r.t0,
    )
    assert round_prep.t0 <= prep.t0
    assert prep.t0 + prep.dur <= round_prep.t0 + round_prep.dur
    (secp_prep,) = [r for r in recs if r.name == "crypto.secp_prep"]
    assert (secp_prep.fields.get("parent") == "crypto.secp_verify") == (
        secp_copies == 1
    )


def test_mixed_round_books_its_ed25519_rows_against_their_bucket(
    tmp_path, committee, window
):
    """A mixed round's fill is the device's: its ed25519 rows over the
    bucket they were padded to, its secp256k1 rows under host_rows. An
    ed25519 round of the same rows books what it always booked."""
    ed_items = [
        SigItem(p, m, s)
        for _, msgs, sigs, _ in window
        for p, m, s, t in zip(committee.pubs, msgs, sigs, committee.types)
        if t == ED
    ]
    n_ed, n_secp = len(ed_items), 4 * VALIDATORS - len(ed_items)
    bucket = default_shape_registry().bucket_for(n_ed)
    ledger = DispatchLedger()
    svc = served(tmp_path, ledger)
    try:
        run_window(svc, committee, window)
        mixed = ledger.totals()
        submit(svc, ed_items)
        dump = svc.server.dump(entries=4)
    finally:
        svc.stop()
    assert (n_ed, n_secp) == (72, 24)
    assert (
        mixed["rounds"], mixed["rows_requested"], mixed["rows_dispatched"],
        mixed["host_rows"],
    ) == (1, n_ed, bucket, n_secp)
    total = dump["summary"]
    assert (
        total["rounds"], total["rows_requested"], total["rows_dispatched"],
        total["host_rows"],
    ) == (2, 2 * n_ed, 2 * bucket, n_secp)
    booked = [
        (e["requested"], e["dispatched"], e["host_rows"])
        for e in dump["entries"]
    ]
    assert booked == [(n_ed, bucket, n_secp), (n_ed, bucket, 0)]
    first, second = dump["entries"]
    assert first["rows"] == {"blocksync": n_ed + n_secp}
    assert first["fill"] == second["fill"] == round(n_ed / bucket, 4)


def _malformed_keys() -> dict:
    """33-byte secp256k1 keys that name no point: a prefix that is
    neither 02 nor 03, and an x with no y on the curve."""
    x = next(
        x for x in range(5, 100)
        if pow((x**3 + 7) % secp_ref.P, (secp_ref.P - 1) // 2, secp_ref.P)
        != 1
    )
    return {
        "prefix_05": b"\x05" + b"\x11" * 32,
        "x_off_curve": b"\x02" + x.to_bytes(32, "big"),
    }


@pytest.mark.parametrize("name", sorted(_malformed_keys()))
def test_malformed_secp256k1_key_is_refused_in_a_mixed_round(
    tmp_path, committee, window, name
):
    """A row whose 33-byte key is no point is refused, and the rows
    around it keep their verdicts (the window's rows, with the clean
    commit's first secp256k1 row copied under the bad key)."""
    bad_key = _malformed_keys()[name]
    assert secp_ref.decode_key(bad_key) is None
    items = [
        SigItem(p, m, s, t)
        for _, msgs, sigs, _ in window
        for p, m, s, t in zip(committee.pubs, msgs, sigs, committee.types)
    ]
    row = 3 * VALIDATORS + committee.types.index(SECP)
    good = items[row]
    assert committee.reference([good.pubkey], [good.msg], [good.sig], [SECP])
    items.insert(row, SigItem(bad_key, good.msg, good.sig, SECP))
    want = committee.reference(
        [it.pubkey for it in items], [it.msg for it in items],
        [it.sig for it in items], [it.key_type for it in items],
    )
    assert not want[row]
    assert want[:row] + want[row + 1:] == committee.reference(
        committee.pubs * 4,
        [m for _, msgs, _, _ in window for m in msgs],
        [s for _, _, sigs, _ in window for s in sigs],
        committee.types * 4,
    )
    svc = served(tmp_path, DispatchLedger())
    try:
        assert submit(svc, items) == want
    finally:
        svc.stop()
