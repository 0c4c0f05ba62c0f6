"""Quorum-certificate plane: codec goldens, aggregate verify vs the
N-sig path (bit-for-bit verdict agreement incl. forged-aggregate and
sub-quorum rejections), the qc_verify engine in BOTH scheduler runtimes
(in-proc fn lane + verify-service wire), per-engine ledger accounting /
fn fill honesty, QC-compressed light proofs, and mixed-mode blocksync
interop (a legacy consumer syncs a QC chain; a QC consumer verifies one
pairing per block)."""

import asyncio
import os
import threading

import pytest

from tendermint_tpu.crypto import bls_signatures as bls
from tendermint_tpu.libs.bits import BitArray
from tendermint_tpu.types.block import Block, BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.quorum_cert import (
    QuorumCertificate,
    assemble_qc,
    qc_sign_bytes,
)
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote

from .helpers import CHAIN_ID, make_genesis, make_qc_validators, sign_commit

pytestmark = pytest.mark.qc


def _bid(tag: int) -> BlockID:
    return BlockID(bytes([tag]) * 32, PartSetHeader(1, bytes([tag + 1]) * 32))


@pytest.fixture(scope="module")
def committee():
    """(valset, privvals, bls_privs) — 4 QC-capable validators."""
    return make_qc_validators(4, seed=b"qcplane")


@pytest.fixture(scope="module")
def qc_commit(committee):
    vs, pvs, privs = committee
    bid = _bid(7)
    commit = sign_commit(vs, pvs, 5, 0, bid, bls_privs=privs)
    return bid, commit


# --- wire codec -------------------------------------------------------------


def test_qc_codec_roundtrip_golden():
    """Bit-for-bit wire stability: the QC encoding is a cross-process
    contract (blocks, store records, RPC proofs), pinned by a golden."""
    qc = QuorumCertificate(
        height=9,
        round=1,
        block_id=_bid(3),
        signers=BitArray.from_indices(5, [0, 2, 4]),
        agg_signature=bytes(range(96)),
    )
    enc = qc.encode()
    back = QuorumCertificate.decode(enc)
    assert back == qc
    assert back.encode() == enc
    # height=9, round+1=2, block_id message, size=5, bitset 0b10101,
    # then the 96 aggregate bytes — the cross-process wire golden
    golden = (
        "080910021a480a20" + "03" * 32 + "122408011220" + "04" * 32
        + "20052a011532" + "60" + bytes(range(96)).hex()
    )
    assert enc.hex() == golden
    assert back.signers.ones() == [0, 2, 4]
    assert back.num_signers() == 3


def test_vote_commit_block_wire_carry_qc(qc_commit, committee):
    vs, pvs, privs = committee
    bid, commit = qc_commit
    # votes round-trip the qc signature (field 10)
    v = Vote.decode(
        Vote(
            type=2, height=5, round=0, block_id=bid,
            timestamp_ns=1, validator_address=b"a" * 20,
            validator_index=0, signature=b"s" * 64,
            qc_signature=b"q" * 96,
        ).encode()
    )
    assert v.qc_signature == b"q" * 96
    # commit sigs retained the contributions (the assemble-on-demand
    # source) and survive the codec
    assert all(
        cs.qc_signature for cs in commit.signatures if cs.for_block()
    )
    c2 = Commit.decode(commit.encode())
    assert [cs.qc_signature for cs in c2.signatures] == [
        cs.qc_signature for cs in commit.signatures
    ]
    # blocks carry last_qc next to the commit; legacy blocks (no field
    # 5) decode to last_qc=None
    qc = assemble_qc(CHAIN_ID, commit, vs)
    from tendermint_tpu.types.block import Data, Header

    blk = Block(
        header=Header(chain_id=CHAIN_ID, height=6, validators_hash=b"v" * 32),
        data=Data(),
        last_commit=commit,
        last_qc=qc,
    )
    b2 = Block.decode(blk.encode())
    assert b2.last_qc is not None
    assert b2.last_qc.encode() == qc.encode()
    legacy = Block(
        header=Header(chain_id=CHAIN_ID, height=6, validators_hash=b"v" * 32),
        data=Data(),
        last_commit=commit,
    )
    assert Block.decode(legacy.encode()).last_qc is None


def test_bls_key_in_validator_hash_and_legacy_hash_stable(committee):
    vs, _, _ = committee
    # a set WITHOUT bls keys hashes exactly as before the field existed
    bare = ValidatorSet(
        [Validator(v.pub_key, v.voting_power) for v in vs.validators]
    )
    stripped = ValidatorSet(
        [Validator(v.pub_key, v.voting_power, bls_pub_key=b"")
         for v in vs.validators]
    )
    assert bare.hash() == stripped.hash()
    # adding the key changes membership identity (it is committed)
    assert vs.hash() != bare.hash()
    # and survives the set codec
    vs2 = ValidatorSet.decode(vs.encode())
    assert vs2.hash() == vs.hash()
    assert all(v.bls_pub_key for v in vs2.validators)
    assert vs2.qc_capable()


# --- assemble + verify ------------------------------------------------------


def test_qc_agrees_with_commit_light(qc_commit, committee):
    """Same commit, both planes: the N-sig verdict and the one-pairing
    QC verdict must agree."""
    vs, _, _ = committee
    bid, commit = qc_commit
    vs.verify_commit_light(CHAIN_ID, bid, 5, commit)  # N-sig path
    qc = assemble_qc(CHAIN_ID, commit, vs)
    assert qc is not None and qc.num_signers() == 4
    vs.verify_commit_qc(CHAIN_ID, bid, 5, qc)  # one pairing
    # bulk: one engine submission for many entries
    assert vs.verify_commits_qc(
        CHAIN_ID, [(bid, 5, qc), (bid, 5, qc)]
    ) == [True, True]
    # trusting (the skipping-verification half): same set overlap
    vs.verify_commit_qc_trusting(CHAIN_ID, qc, vs)


def test_forged_aggregate_rejected(qc_commit, committee):
    vs, _, _ = committee
    bid, commit = qc_commit
    qc = assemble_qc(CHAIN_ID, commit, vs)
    forged = QuorumCertificate.decode(qc.encode())
    forged.agg_signature = bls.g1_to_bytes(
        bls.sign(12345, qc.sign_bytes(CHAIN_ID))
    )
    with pytest.raises(ValueError, match="aggregate"):
        vs.verify_commit_qc(CHAIN_ID, bid, 5, forged)
    assert vs.verify_commits_qc(CHAIN_ID, [(bid, 5, forged)]) == [False]
    # garbage bytes are a False verdict, not an engine error
    forged.agg_signature = b"\xff" * 96
    assert vs.verify_commits_qc(CHAIN_ID, [(bid, 5, forged)]) == [False]


def test_sub_quorum_bitset_rejected(qc_commit, committee):
    vs, _, _ = committee
    bid, commit = qc_commit
    qc = assemble_qc(CHAIN_ID, commit, vs)
    sub = QuorumCertificate.decode(qc.encode())
    sub.signers = BitArray.from_indices(4, [0, 1])  # 20/40 <= 2/3
    with pytest.raises(ValueError, match="voting power"):
        vs.verify_commit_qc(CHAIN_ID, bid, 5, sub)
    # a wrong-size bitset (different committee) is a shape error
    sub.signers = BitArray.from_indices(5, [0, 1, 2, 3, 4])
    with pytest.raises(ValueError, match="bitset size"):
        vs.verify_commit_qc(CHAIN_ID, bid, 5, sub)


def test_assemble_isolates_corrupt_contribution(committee):
    """A byzantine validator's garbage qc_signature (its ed25519 vote
    was fine) is bisected out; the QC ships with the surviving 3/4."""
    vs, pvs, privs = committee
    bid = _bid(9)
    commit = sign_commit(vs, pvs, 7, 0, bid, bls_privs=privs)
    commit.signatures[1] = CommitSig(
        block_id_flag=commit.signatures[1].block_id_flag,
        validator_address=commit.signatures[1].validator_address,
        timestamp_ns=commit.signatures[1].timestamp_ns,
        signature=commit.signatures[1].signature,
        qc_signature=bls.g1_to_bytes(bls.sign(999, b"wrong message")),
    )
    qc = assemble_qc(CHAIN_ID, commit, vs)
    assert qc is not None
    assert qc.num_signers() == 3 and not qc.signers.get(1)
    vs.verify_commit_qc(CHAIN_ID, bid, 7, qc)
    # two corrupt contributions push the survivors to 2/4 <= 2/3: no QC
    commit.signatures[2] = CommitSig(
        block_id_flag=commit.signatures[2].block_id_flag,
        validator_address=commit.signatures[2].validator_address,
        timestamp_ns=commit.signatures[2].timestamp_ns,
        signature=commit.signatures[2].signature,
        qc_signature=b"\x00" * 95,  # unparseable
    )
    assert assemble_qc(CHAIN_ID, commit, vs) is None


def test_non_capable_set_refuses_qc(qc_commit, committee):
    vs, pvs, privs = committee
    bid, commit = qc_commit
    qc = assemble_qc(CHAIN_ID, commit, vs)
    bare = ValidatorSet(
        [Validator(v.pub_key, v.voting_power) for v in vs.validators]
    )
    assert not bare.qc_capable()
    with pytest.raises(ValueError, match="bls key"):
        bare.verify_commit_qc(CHAIN_ID, bid, 5, qc)
    # a legacy commit (no qc signatures) cannot assemble
    plain = sign_commit(vs, pvs, 5, 0, bid)
    assert assemble_qc(CHAIN_ID, plain, vs) is None


# --- the qc_verify engine in both runtimes ----------------------------------


def _qc_item(vs, qc, chain_id=CHAIN_ID):
    keys = b"".join(
        vs.validators[i].bls_pub_key for i in qc.signers.ones()
    )
    return (qc.sign_bytes(chain_id), qc.agg_signature, keys)


def test_qc_engine_direct_and_batch(qc_commit, committee):
    from tendermint_tpu.crypto.bls_signatures import verify_qc_items

    vs, _, _ = committee
    bid, commit = qc_commit
    qc = assemble_qc(CHAIN_ID, commit, vs)
    good = _qc_item(vs, qc)
    bad = (good[0], bls.g1_to_bytes(bls.sign(4, good[0])), good[2])
    unparseable = (good[0], b"\x11" * 96, good[2])
    # the whole round is one RLC multi-pairing; bisect isolates bads
    assert verify_qc_items([good, bad, good, unparseable]) == [
        True, False, True, False,
    ]


def test_qc_engine_in_scheduler_fn_lane(qc_commit, committee):
    """submit_wire_fn_sync('qc_verify') coalesces through the in-proc
    scheduler and books a per-engine ledger row."""
    from tendermint_tpu.obs.ledger import DispatchLedger
    from tendermint_tpu.parallel.scheduler import VerifyScheduler

    vs, _, _ = committee
    bid, commit = qc_commit
    qc = assemble_qc(CHAIN_ID, commit, vs)
    ledger = DispatchLedger()
    sched = VerifyScheduler(ledger=ledger)

    async def run():
        await sched.start()
        loop = asyncio.get_running_loop()

        def worker():
            return sched.submit_wire_fn_sync(
                "qc_verify", [_qc_item(vs, qc)], "blocksync"
            )

        res = await loop.run_in_executor(None, worker)
        await sched.stop()
        return res

    assert asyncio.run(run()) == [True]
    summ = ledger.summary()
    eng = summ["per_engine"]["qc_verify"]
    assert eng["rounds"] == 1 and eng["rows_requested"] == 1
    assert eng["requests_per_dispatch"] == 1.0
    # unknown engines take the fallback, not an exception
    sched2 = VerifyScheduler()
    assert sched2.submit_wire_fn_sync(
        "nope", [()], "light", fallback=lambda: ["fb"]
    ) == ["fb"]


def test_qc_engine_on_verify_service_wire(tmp_path, qc_commit, committee):
    """The cross-process half: qc_verify in the service's wire-engine
    table, verdicts over the UDS."""
    from tendermint_tpu.parallel.verify_service import (
        RemoteVerifyScheduler,
        ServiceThread,
    )

    vs, _, _ = committee
    bid, commit = qc_commit
    qc = assemble_qc(CHAIN_ID, commit, vs)
    good = _qc_item(vs, qc)
    bad = (good[0], bls.g1_to_bytes(bls.sign(4, good[0])), good[2])
    path = os.path.join(str(tmp_path), "qc.sock")
    svc = ServiceThread(path)
    svc.start()
    try:

        async def run():
            remote = RemoteVerifyScheduler(path, retry_base=0.02)
            await remote.start()
            deadline = asyncio.get_running_loop().time() + 15
            while not remote.connected:
                await asyncio.sleep(0.01)
                assert asyncio.get_running_loop().time() < deadline
            res = await remote.submit_wire_fn(
                "qc_verify", [good, bad], "blocksync"
            )
            await remote.stop()
            return res

        assert asyncio.run(run()) == [True, False]
        # the service's ledger billed the round under its engine name
        summ = svc.server.scheduler.ledger.summary()
        assert "qc_verify" in summ["per_engine"]
    finally:
        svc.stop()


# --- ledger satellites ------------------------------------------------------


def test_ledger_per_engine_rpd_and_fn_fill():
    """Satellites 1+2: requests_per_dispatch broken out per engine
    (the global number is diluted by one-submission fn rounds), and fn
    rounds book their TRUE internal bucket — on the fn axis, never
    blended into the sig fill distribution."""
    from tendermint_tpu.obs.ledger import DispatchLedger

    led = DispatchLedger()
    mark = led.mark()
    # sig plane: 2 rounds, 3 submissions -> rpd 1.5
    led.record_round(
        1.0, class_rows={"consensus": 90}, requested=90, dispatched=128,
        submissions=2, device_s=0.2,
    )
    led.record_round(
        2.0, class_rows={"blocksync": 50}, requested=50, dispatched=64,
        submissions=1, device_s=0.1,
    )
    # fn plane: one 150-item bls_agg round padding internally to 256
    led.record_round(
        3.0, class_rows={"consensus": 150}, requested=150, dispatched=256,
        submissions=1, device_s=0.05, engine="bls_agg",
    )
    # qc plane: 8 aggregate checks, no padding
    led.record_round(
        4.0, class_rows={"blocksync": 8}, requested=8, dispatched=8,
        submissions=1, device_s=0.01, engine="qc_verify",
    )
    for summ in (led.summary(), led.summary(since=mark)):
        eng = summ["per_engine"]
        assert eng["sig"]["requests_per_dispatch"] == 1.5
        assert eng["sig"]["rows_dispatched"] == 192
        assert eng["bls_agg"]["fill_ratio"] == round(150 / 256, 4)
        assert eng["qc_verify"]["fill_ratio"] == 1.0
        # the sig-plane distribution excludes every fn engine
        assert summ["fill_ratio_p50"] >= 0.70
        # honest fn bucket never leaks into the sig padding totals
        assert summ["padding_rows"] == (128 - 90) + (64 - 50)
    # totals (the health seam) stay sig-only too
    t = led.totals()
    assert t["rows_requested"] == 140 and t["rows_dispatched"] == 192


def test_scheduler_books_fn_internal_bucket(qc_commit, committee):
    """A real fn round through the scheduler lands in the ledger with
    the engine's internal_rows bucket and sets the per-engine gauge."""
    from tendermint_tpu.libs.metrics import Registry, SchedulerMetrics
    from tendermint_tpu.obs.ledger import DispatchLedger
    from tendermint_tpu.parallel.engines import _engine_bls_agg
    from tendermint_tpu.parallel.scheduler import VerifyScheduler

    h = b"h" * 32
    items = []
    for i in range(3):
        priv = 7001 + i
        items.append(
            (
                bls.public_key_to_bytes(bls.pubkey_from_priv(priv)),
                h,
                bls.signer_for(priv)(h),
            )
        )
    reg = Registry()
    ledger = DispatchLedger()
    sched = VerifyScheduler(
        ledger=ledger, metrics=SchedulerMetrics(reg)
    )

    async def run():
        await sched.start()
        res = await sched.submit_fn(
            items, _engine_bls_agg, "consensus", engine="bls_agg"
        )
        await sched.stop()
        return res

    assert asyncio.run(run()) == [True, True, True]
    (entry,) = ledger.entries()
    assert entry["engine"] == "bls_agg"
    assert entry["requested"] == 3
    assert entry["dispatched"] == 8  # 3-signer group pads to the 8 rung
    assert 'tm_scheduler_fn_fill_ratio{engine="bls_agg"} 0.375' in (
        reg.render()
    )


# --- light plane ------------------------------------------------------------


def _light_chain(n_vals, heights, seed=b"lq"):
    """QC-capable chain of LightBlocks (commit + qc both attached)."""
    from tendermint_tpu.light.types import LightBlock
    from tendermint_tpu.types.block import Data, Header

    vs, pvs, privs = make_qc_validators(n_vals, seed=seed)
    out = []
    prev_bid = BlockID()
    t0 = 1_700_000_000_000_000_000
    for h in heights:
        header = Header(
            chain_id=CHAIN_ID,
            height=h,
            time_ns=t0 + h * 1_000_000_000,
            last_block_id=prev_bid,
            validators_hash=vs.hash(),
            next_validators_hash=vs.hash(),
            data_hash=Data().hash(),
        )
        bid = BlockID(header.hash(), PartSetHeader(1, bytes([h % 251]) * 32))
        commit = sign_commit(
            vs, pvs, h, 0, bid, time_ns=t0 + h * 1_000_000_000,
            bls_privs=privs,
        )
        qc = assemble_qc(CHAIN_ID, commit, vs)
        assert qc is not None
        out.append(LightBlock(header, commit, vs, qc=qc))
        prev_bid = bid
    return vs, out, t0


def test_light_verify_qc_compressed_proofs():
    """verify_adjacent + verify (skipping) accept qc-only light blocks
    (commit=None) — and reject a tampered aggregate."""
    from tendermint_tpu.light import verifier as lv
    from tendermint_tpu.light.types import LightBlock

    vs, chain, t0 = _light_chain(4, [1, 2, 5])
    period = 3600 * 10**9
    now = t0 + 6 * 10**9

    def compressed(lb):
        return LightBlock(lb.header, None, lb.validators, qc=lb.qc)

    lv.verify_adjacent(chain[0], compressed(chain[1]), period, now)
    lv.verify(chain[0], compressed(chain[2]), period, now)  # skipping
    # verdict parity with the commit path
    lv.verify_adjacent(chain[0], chain[1], period, now)
    # tampered aggregate on the compressed proof: rejected
    bad = compressed(chain[1])
    bad.qc = QuorumCertificate.decode(bad.qc.encode())
    bad.qc.agg_signature = bls.g1_to_bytes(bls.sign(3, b"zzz"))
    with pytest.raises(lv.VerificationError):
        lv.verify_adjacent(chain[0], bad, period, now)
    # a compressed proof with NO qc is unverifiable, not silently ok
    naked = compressed(chain[1])
    naked.qc = None
    with pytest.raises((lv.VerificationError, ValueError)):
        lv.verify_adjacent(chain[0], naked, period, now)


@pytest.mark.slow
def test_qc_proof_size_compression_at_100():
    """Acceptance: light_block proof bytes reduced >= 5x at 100
    validators (the full-commit payload vs the qc-compressed one)."""
    from tendermint_tpu.light.types import LightBlock

    vs, chain, _ = _light_chain(100, [1], seed=b"lq100")
    lb = chain[0]
    full = LightBlock(lb.header, lb.commit, lb.validators).proof_bytes()
    qc_only = LightBlock(
        lb.header, None, lb.validators, qc=lb.qc
    ).proof_bytes()
    assert full / qc_only >= 5.0, (full, qc_only)
    # and the compressed proof still verifies
    vs.verify_commit_qc(CHAIN_ID, lb.qc.block_id, 1, lb.qc)


def test_lightserve_serves_qc_proofs():
    """The cache attaches the canonical QC (block h+1's last_qc) and
    get_compressed drops the CommitSigs; the serve verifier keys qc and
    commit proofs separately."""
    from tendermint_tpu.lightserve.cache import LightBlockCache

    vs, chain, _ = _light_chain(4, [1, 2, 3])

    class Meta:
        def __init__(self, lb):
            self.header = lb.header

    class FakeBlockStore:
        height = 3

        def load_block_meta(self, h):
            return Meta(chain[h - 1]) if 1 <= h <= 3 else None

        def load_block_commit(self, h):
            return chain[h - 1].commit if 1 <= h <= 2 else None

        def load_seen_commit(self, h):
            return chain[h - 1].commit if h == 3 else None

        def load_block_qc(self, h):
            return chain[h - 1].qc if 1 <= h <= 2 else None

    class FakeStateStore:
        def load_validators(self, h):
            return vs

    cache = LightBlockCache(FakeBlockStore(), FakeStateStore(), CHAIN_ID)
    lb = cache.get(1)
    assert lb.qc is not None and lb.commit is not None
    comp = cache.get_compressed(1)
    assert comp.commit is None and comp.qc is not None
    assert comp.proof_bytes() < lb.proof_bytes() / 2
    comp.validate_basic(CHAIN_ID)
    # the tip has no canonical QC: compressed falls back to the full proof
    tip = cache.get_compressed(3)
    assert tip.commit is not None and tip.qc is None


# --- live consensus + mixed-mode blocksync ----------------------------------


def _qc_node(vs, pv, genesis, privs, qc=True):
    from tendermint_tpu.consensus.state_machine import ConsensusConfig

    from .test_consensus import make_node

    cfg = ConsensusConfig.test_config()
    cfg.quorum_certificates = qc
    addr = pv.get_pub_key().address()
    cs, app, l2, bs, ss = make_node(
        vs, pv, genesis,
        config=cfg,
        bls_signer=bls.signer_for(privs[addr]),
    )
    cs.executor.qc_enabled = qc
    return cs, app, l2, bs, ss


def test_live_chain_produces_and_stores_qcs():
    """A QC-enabled single-validator chain: every committed block past
    the first carries last_qc, the store serves the canonical QC, and
    replayed validation rides the QC path."""
    vs, pvs, privs = make_qc_validators(1, seed=b"live1")
    genesis = make_genesis(vs)

    async def run():
        cs, app, l2, bs, ss = _qc_node(vs, pvs[0], genesis, privs)
        await cs.start()
        await cs.wait_for_height(4, timeout=30)
        await cs.stop()
        return bs

    bs = asyncio.run(run())
    for h in range(2, 4):
        blk = bs.load_block(h + 1)
        assert blk.last_qc is not None, f"height {h+1} shipped without qc"
        assert blk.last_qc.height == h
        stored = bs.load_block_qc(h)
        assert stored is not None and stored.encode() == blk.last_qc.encode()
        # the stored QC verifies against the committed set
        vs.verify_commit_qc(
            CHAIN_ID, blk.last_qc.block_id, h, blk.last_qc
        )


def _sync_consumer(vs, pvs, privs, genesis, src_bs, n_heights, qc_enabled):
    """Drive a BlocksyncReactor's pool directly (no p2p) over the source
    chain; returns the reactor after it applied everything."""
    from tendermint_tpu.blocksync.reactor import BlocksyncReactor

    async def run():
        cs, app, l2, bs, ss = _qc_node(
            vs, pvs[0], genesis, privs, qc=qc_enabled
        )
        reactor = BlocksyncReactor(
            cs.state, cs.executor, bs, l2, qc_enabled=qc_enabled
        )
        reactor.pool.set_peer_range("src", 0, n_heights)
        reactor.pool.make_requests()
        for h in range(1, n_heights + 1):
            assert reactor.pool.add_block(
                "src", src_bs.load_block(h), size=1024
            )
        while reactor.pool.height <= n_heights - 1:
            before = reactor.pool.height
            await reactor._process_ready_blocks()
            if reactor.pool.height == before:
                break  # no progress: fail below
        return reactor

    return asyncio.run(run())


def test_mixed_mode_interop_legacy_and_qc_consumers():
    """Acceptance: a legacy peer (quorum_certificates off) syncs a chain
    produced by QC-capable proposers via the N-sig path, and a
    QC-capable peer syncs the same chain verifying aggregates."""
    vs, pvs, privs = make_qc_validators(1, seed=b"mixed")
    genesis = make_genesis(vs)
    heights = 6

    async def produce():
        cs, app, l2, bs, ss = _qc_node(vs, pvs[0], genesis, privs)
        await cs.start()
        await cs.wait_for_height(heights, timeout=40)
        await cs.stop()
        return bs

    src_bs = asyncio.run(produce())
    assert src_bs.load_block(heights).last_qc is not None

    legacy = _sync_consumer(
        vs, pvs, privs, genesis, src_bs, heights - 1, qc_enabled=False
    )
    assert legacy.blocks_applied == heights - 2
    assert legacy.qc_verified_blocks == 0

    qc_peer = _sync_consumer(
        vs, pvs, privs, genesis, src_bs, heights - 1, qc_enabled=True
    )
    assert qc_peer.blocks_applied == heights - 2
    # every applied block was proven by its aggregate, not N sigs
    assert qc_peer.qc_verified_blocks >= heights - 2


def test_tampered_qc_in_transit_changes_block_id():
    """A relay that rewrites a block's QC rewrote the block BYTES: the
    re-encoded part set no longer matches the BlockID the committee
    signed, so the tamper is caught by the existing commit shape check
    (redo + peer punishment), never by trusting the bad aggregate."""
    vs, pvs, privs = make_qc_validators(1, seed=b"corrupt")
    genesis = make_genesis(vs)

    async def produce():
        cs, app, l2, bs, ss = _qc_node(vs, pvs[0], genesis, privs)
        await cs.start()
        await cs.wait_for_height(4, timeout=40)
        await cs.stop()
        return bs

    src_bs = asyncio.run(produce())
    from tendermint_tpu.types.block_id import BlockID

    blk = src_bs.load_block(3)  # carries the qc for height 2
    victim = src_bs.load_block(2)
    fid = BlockID(victim.hash(), victim.make_part_set().header)
    blk.last_qc = QuorumCertificate.decode(blk.last_qc.encode())
    blk.last_qc.agg_signature = bls.g1_to_bytes(bls.sign(13, b"garbage"))
    blk._part_set = None  # the tampered relay re-frames the bytes
    tampered_id = BlockID(blk.hash(), blk.make_part_set().header)
    # same header hash (qc is not header-hashed), DIFFERENT part bytes:
    # the signed BlockID pins the original proof
    assert tampered_id.hash == src_bs.load_block(3).hash()
    assert tampered_id != src_bs.load_block(3).block_id()
    # and the bad aggregate itself never verifies
    with pytest.raises(ValueError):
        vs.verify_commit_qc(CHAIN_ID, fid, 2, blk.last_qc)


def test_window_falls_back_when_qc_verdicts_fail(monkeypatch):
    """The windowed fallback: if the qc_verify engine rejects (or is
    unavailable), the window re-judges on the N-sig path instead of
    stalling — the full commit is authoritative."""
    vs, pvs, privs = make_qc_validators(1, seed=b"fb")
    genesis = make_genesis(vs)
    heights = 5

    async def produce():
        cs, app, l2, bs, ss = _qc_node(vs, pvs[0], genesis, privs)
        await cs.start()
        await cs.wait_for_height(heights, timeout=40)
        await cs.stop()
        return bs

    src_bs = asyncio.run(produce())
    monkeypatch.setattr(
        ValidatorSet,
        "verify_commits_qc",
        lambda self, chain_id, entries, engine=None: [False] * len(entries),
    )
    consumer = _sync_consumer(
        vs, pvs, privs, genesis, src_bs, heights - 1, qc_enabled=True
    )
    assert consumer.blocks_applied == heights - 2
    assert consumer.qc_verified_blocks == 0  # every window re-judged


def test_rpc_light_block_qc_param(qc_commit, committee):
    """The light_block route's proof=qc negotiation (handler-level):
    compressed shape drops the commit, carries the qc, and unknown
    formats are -32602."""
    from tendermint_tpu.lightserve.cache import LightBlockCache
    from tendermint_tpu.rpc.core import RPCCore
    from tendermint_tpu.rpc.server import RPCError

    vs, chain, _ = _light_chain(4, [1, 2, 3], seed=b"rpcqc")

    class Meta:
        def __init__(self, lb):
            self.header = lb.header

    class FakeBlockStore:
        height = 3

        def load_block_meta(self, h):
            return Meta(chain[h - 1]) if 1 <= h <= 3 else None

        def load_block_commit(self, h):
            return chain[h - 1].commit if 1 <= h <= 2 else None

        def load_seen_commit(self, h):
            return chain[h - 1].commit if h == 3 else None

        def load_block_qc(self, h):
            return chain[h - 1].qc if 1 <= h <= 2 else None

    class FakeStateStore:
        def load_validators(self, h):
            return vs

    class FakePlane:
        cache = LightBlockCache(FakeBlockStore(), FakeStateStore(), CHAIN_ID)

    class FakeNode:
        lightserve = FakePlane()

    core = RPCCore.__new__(RPCCore)
    core.node = FakeNode()
    full = core.light_block(height=1)["light_block"]
    assert full["signed_header"]["commit"] is not None
    assert "qc" in full  # full proofs on QC chains carry it alongside
    comp = core.light_block(height=1, proof="qc")["light_block"]
    assert comp["signed_header"]["commit"] is None
    assert comp["qc"]["agg_signature"]
    # provider-side parse round-trips the compressed proof
    from tendermint_tpu.rpc.light_provider import (
        header_from_json,
        qc_from_json,
        validators_from_json,
    )

    qc = qc_from_json(comp["qc"])
    assert qc.encode() == chain[0].qc.encode()
    hdr = header_from_json(comp["signed_header"]["header"])
    assert hdr.hash() == chain[0].header.hash()
    vals = validators_from_json(comp["validator_set"]["validators"])
    assert vals.hash() == vs.hash()
    assert vals.qc_capable()
    with pytest.raises(RPCError):
        core.light_block(height=1, proof="zstd")


def test_legacy_chain_syncs_on_qc_consumer():
    """The other direction of mixed mode: a QC-enabled consumer syncs a
    chain whose proposers never attached QCs — transparent fallback to
    the N-sig window."""
    vs, pvs, privs = make_qc_validators(1, seed=b"legacysrc")
    genesis = make_genesis(vs)
    heights = 5

    async def produce():
        cs, app, l2, bs, ss = _qc_node(
            vs, pvs[0], genesis, privs, qc=False
        )
        await cs.start()
        await cs.wait_for_height(heights, timeout=40)
        await cs.stop()
        return bs

    src_bs = asyncio.run(produce())
    assert src_bs.load_block(heights).last_qc is None
    consumer = _sync_consumer(
        vs, pvs, privs, genesis, src_bs, heights - 1, qc_enabled=True
    )
    assert consumer.blocks_applied == heights - 2
    assert consumer.qc_verified_blocks == 0


def test_l2_rotation_carries_bls_key_into_next_qc_bitset():
    """Satellite regression (PERF_ANALYSIS §22): a BLS pubkey riding an
    L2 validator update (state/execution 4-column val_updates) reaches
    the stored set, flips it QC-capable, and the rotated-keyed member
    lands in the next quorum certificate's signer bitset."""
    from tendermint_tpu.consensus.state_machine import ConsensusConfig
    from tendermint_tpu.l2node.mock import MockL2Node

    from .test_consensus import make_node, wire_net

    vs, pvs, privs = make_qc_validators(4, seed=b"rotate")
    rot_idx = 2
    key_backfill = vs.validators[rot_idx].bls_pub_key
    # strip one member's BLS key from genesis: the set starts NOT
    # qc_capable, so no height can carry a QC until the rotation lands.
    # That member holds 20 of 50: no 2/3 closes without it, so it signs
    # every commit and no certificate can be assembled without it,
    # whichever precommit reaches the next proposer last
    vs = ValidatorSet(
        [
            Validator(v.pub_key, 20, bls_pub_key=b"")
            if i == rot_idx
            else Validator(v.pub_key, 10, bls_pub_key=v.bls_pub_key)
            for i, v in enumerate(vs.validators)
        ]
    )
    bare = vs.validators[rot_idx]
    genesis = make_genesis(vs)
    rotate_h, last_h = 3, 9
    # the update applied at rotate_h becomes next_validators(rotate_h+1)
    # = validators(rotate_h+2): first QC-capable height
    capable_h = rotate_h + 2

    async def run():
        nodes = []
        cfg = ConsensusConfig.test_config()
        cfg.quorum_certificates = True
        for pv in pvs:
            l2 = MockL2Node()
            # every replica delivers the same rotation: the sitting
            # member's ed25519 identity + unchanged power, now with its
            # BLS key in the 4th column
            l2.validator_updates[rotate_h] = [
                ("ed25519", bare.pub_key.data, bare.voting_power,
                 key_backfill)
            ]
            addr = pv.get_pub_key().address()
            cs, app, _, bs, ss = make_node(
                vs, pv, genesis, l2=l2, config=cfg,
                bls_signer=bls.signer_for(privs[addr]),
            )
            cs.executor.qc_enabled = True
            nodes.append((cs, bs, ss))
        css = [n[0] for n in nodes]
        wire_net(css)
        for cs in css:
            await cs.start()
        await asyncio.gather(
            *(cs.wait_for_height(last_h, timeout=60) for cs in css)
        )
        for cs in css:
            await cs.stop()
        return nodes[0][1], nodes[0][2]

    bs, ss = asyncio.run(run())
    # pre-rotation heights can never carry a QC (set not capable)
    for h in range(2, capable_h):
        blk = bs.load_block(h + 1)
        assert blk.last_qc is None, f"height {h} got a QC pre-rotation"
    # post-rotation: some height in [capable_h, last_h) carries one
    # (round-0 proposer assembly is best-effort, so scan the window)
    carried = [
        bs.load_block(h + 1).last_qc
        for h in range(capable_h, last_h - 1)
        if bs.load_block(h + 1) and bs.load_block(h + 1).last_qc
    ]
    assert carried, "no QC produced after the rotation landed"
    qc = carried[0]
    set_at = ss.load_validators(qc.height)
    assert set_at is not None and set_at.qc_capable()
    assert set_at.validators[rot_idx].bls_pub_key == key_backfill
    assert qc.signers.get(rot_idx), (
        "rotated-keyed validator missing from the QC bitset"
    )
    set_at.verify_commit_qc(CHAIN_ID, qc.block_id, qc.height, qc)
