"""Sequencer mode: BlockV2 production, signed gossip, sync catchup.

Mirrors the reference's sequencer suite (sequencer/state_v2_test.go,
block_cache_test.go — 27 tests) plus an end-to-end net over real p2p,
and the PR 10 streaming-plane suite: event-driven apply/sync (no
polling-tick reliance), windowed catchup with request expiry,
encode-once backpressure-aware fan-out, coalesced off-loop signature
verification, and the live upgrade-height crossing under chaos.
"""

import asyncio

import pytest

from tendermint_tpu.crypto import secp256k1

pytestmark = pytest.mark.sequencer
from tendermint_tpu.l2node.mock import MockL2Node
from tendermint_tpu.p2p.key import NodeKey
from tendermint_tpu.p2p.node_info import NodeInfo
from tendermint_tpu.p2p.switch import Switch
from tendermint_tpu.p2p.transport import MultiplexTransport, NetAddress
from tendermint_tpu.sequencer import (
    BlockBroadcastReactor,
    BlockRingBuffer,
    HashSet,
    LocalSigner,
    PendingBlockCache,
    StateV2,
    StaticSequencerVerifier,
)
from tendermint_tpu.types.block_v2 import BlockV2

NETWORK = "seq-chain"


# --- caches ----------------------------------------------------------------


def test_ring_buffer_eviction():
    rb = BlockRingBuffer(capacity=3)
    for n in range(5):
        rb.add(BlockV2(number=n, hash=bytes([n]) * 32))
    assert rb.get_by_height(1) is None
    assert rb.get_by_height(4).number == 4
    assert len(rb) == 3


def test_hash_set_dedup_and_eviction():
    s = HashSet(capacity=2)
    assert not s.add(b"a")
    assert s.add(b"a")  # duplicate
    s.add(b"b")
    s.add(b"c")  # evicts "a"
    assert b"a" not in s
    assert b"c" in s


def test_pending_cache_longest_chain():
    c = PendingBlockCache()
    root = b"\x00" * 32

    def blk(n, h, parent):
        return BlockV2(number=n, hash=h * 32, parent_hash=parent)

    # two forks off root: [a1] and [b1 <- b2]
    a1 = blk(1, b"\x0a", root)
    b1 = blk(1, b"\x0b", root)
    b2 = blk(2, b"\x0c", b1.hash)
    for b in (a1, b1, b2):
        assert c.add(b, local_height=0)
    chain = c.get_longest_chain(root)
    assert [b.number for b in chain] == [1, 2]
    assert chain[0].hash == b1.hash
    c.prune_below(1)
    assert c.get(a1.hash) is None and c.get(b1.hash) is None
    assert c.get(b2.hash) is not None


def test_pending_cache_height_window():
    c = PendingBlockCache()
    far = BlockV2(number=500, hash=b"\x01" * 32, parent_hash=b"\x02" * 32)
    assert not c.add(far, local_height=10)  # too far ahead
    assert c.add(far, local_height=450)


# --- BlockV2 signature semantics -------------------------------------------


def test_block_v2_sign_recover_roundtrip():
    key = secp256k1.PrivKey.from_secret(b"seq-key")
    signer = LocalSigner(key)
    l2 = MockL2Node()
    block, _ = l2.request_block_data_v2(l2.get_latest_block_v2().hash)
    block.signature = signer.sign(block.hash)
    assert block.recover_signer() == signer.address()
    # wire roundtrip preserves recoverability
    rt = BlockV2.decode(block.encode())
    assert rt.recover_signer() == signer.address()
    assert rt.transactions == block.transactions
    # a flipped signature byte recovers a different (or no) signer
    bad = BlockV2.decode(block.encode())
    bad.signature = bytes([block.signature[0] ^ 1]) + block.signature[1:]
    assert bad.recover_signer() != signer.address()


# --- StateV2 production -----------------------------------------------------


def test_state_v2_produces_signed_blocks():
    key = secp256k1.PrivKey.from_secret(b"producer")
    signer = LocalSigner(key)
    l2 = MockL2Node()
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        sv = StateV2(l2, block_interval=0.01, signer=signer, verifier=verifier)
        await sv.start()
        b1 = await sv.produce_block()
        b2 = await sv.produce_block()
        await sv.stop()
        return b1, b2

    b1, b2 = asyncio.run(run())
    assert b2.parent_hash == b1.hash
    assert b1.recover_signer() == signer.address()
    assert l2.get_latest_block_v2().hash == b2.hash


# --- end-to-end over p2p ----------------------------------------------------


def _build_seq_node(
    signer, verifier, *, wait_sync=False, l2=None, intervals=0.1
):
    l2 = l2 or MockL2Node()
    sv = StateV2(l2, block_interval=0.05, signer=signer, verifier=verifier)
    nk = NodeKey.generate()
    transport = None
    sw = None

    def node_info():
        return NodeInfo(
            node_id=nk.id,
            listen_addr=f"127.0.0.1:{transport.listen_port}",
            network=NETWORK,
            channels=sw.channels() if sw else b"",
        )

    transport = MultiplexTransport(nk, node_info)
    sw = Switch(transport)
    reactor = BlockBroadcastReactor(
        sv,
        verifier,
        wait_sync=wait_sync,
        apply_interval=intervals,
        sync_interval=intervals,
    )
    sw.add_reactor("sequencer", reactor)
    return sv, reactor, nk, transport, sw


async def _start_and_connect(nodes):
    for _, _, _, t, sw in nodes:
        await t.listen()
        await sw.start()
    for i, (_, _, nk_i, t_i, sw_i) in enumerate(nodes):
        for j, (_, _, nk_j, t_j, _) in enumerate(nodes):
            if j <= i:
                continue
            await sw_i.dial_peer(NetAddress(nk_j.id, "127.0.0.1", t_j.listen_port))


def test_sequencer_gossip_and_follower_apply():
    key = secp256k1.PrivKey.from_secret(b"seq-e2e")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        seq = _build_seq_node(signer, verifier)
        fol = _build_seq_node(None, verifier)
        nodes = [seq, fol]
        await _start_and_connect(nodes)
        for _, r, *_ in nodes:
            await r.on_start()
        # wait until the follower applied a few gossiped blocks
        for _ in range(100):
            await asyncio.sleep(0.05)
            if fol[0].latest_height() >= 3:
                break
        seq_h = seq[0].latest_height()
        fol_h = fol[0].latest_height()
        assert fol_h >= 3, f"follower stuck at {fol_h} (seq at {seq_h})"
        assert (
            fol[0].latest_block.recover_signer() == signer.address()
        )
        for _, r, _, _, sw in nodes:
            await r.on_stop()
            await sw.stop()

    asyncio.run(run())


def test_follower_rejects_wrong_signer():
    seq_key = secp256k1.PrivKey.from_secret(b"real-seq")
    rogue_key = secp256k1.PrivKey.from_secret(b"rogue")
    signer = LocalSigner(rogue_key)  # rogue signs blocks
    verifier = StaticSequencerVerifier(
        [LocalSigner(seq_key).address()]
    )  # ...but only real-seq is allowed

    async def run():
        seq = _build_seq_node(signer, verifier)
        fol = _build_seq_node(None, verifier)
        nodes = [seq, fol]
        await _start_and_connect(nodes)
        for _, r, *_ in nodes:
            await r.on_start()
        await asyncio.sleep(0.5)
        h = fol[0].latest_height()
        for _, r, _, _, sw in nodes:
            await r.on_stop()
            await sw.stop()
        return h

    assert asyncio.run(run()) == 0, "follower applied a rogue-signed block"


def test_sync_gap_catchup():
    """A follower joining far behind fetches blocks over the sync channel
    (reference checkSyncGap + requestMissingBlocks :351-383)."""
    key = secp256k1.PrivKey.from_secret(b"seq-gap")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        seq = _build_seq_node(signer, verifier)
        # pre-produce 30 blocks (> SMALL_GAP_THRESHOLD) before follower joins
        await seq[0].start()
        for _ in range(30):
            await seq[0].produce_block()
        fol = _build_seq_node(None, verifier)
        nodes = [seq, fol]
        await _start_and_connect(nodes)
        seq[1].sequencer_started = True  # StateV2 already started above
        seq[1]._tasks.append(
            asyncio.create_task(seq[1]._broadcast_routine())
        )
        await fol[1].on_start()
        for _ in range(200):
            await asyncio.sleep(0.05)
            if fol[0].latest_height() >= 30:
                break
        h = fol[0].latest_height()
        for _, r, _, _, sw in nodes:
            await r.on_stop()
            await sw.stop()
        return h

    assert asyncio.run(run()) >= 30


def test_bft_upgrade_hands_off_to_sequencer():
    """A BFT chain crossing upgrade_height switches to sequencer mode and
    keeps producing BlockV2s (reference node.go:1612-1632
    switchToSequencerMode wired from consensus/state.go:1921-1938)."""
    from .helpers import make_genesis, make_validators
    from .test_consensus import make_node

    vs, pvs = make_validators(1)
    genesis = make_genesis(vs)
    key = secp256k1.PrivKey.from_secret(b"upgrade-seq")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])
    l2 = MockL2Node()

    async def run():
        sv = StateV2(l2, block_interval=999, signer=signer, verifier=verifier)
        produced = []

        async def on_upgrade(state):
            # mirror switchToSequencerMode: seed L2 to the BFT height and
            # start StateV2 production
            l2.seed_v2_height(state.last_block_height)
            await sv.start()
            produced.append(await sv.produce_block())
            produced.append(await sv.produce_block())

        cs, app, _, bs, ss = make_node(
            vs, pvs[0], genesis, l2=l2, upgrade_height=2, on_upgrade=on_upgrade
        )
        await cs.start()
        await cs.wait_for_height(2, timeout=30)
        await asyncio.sleep(0.2)
        await cs.stop()
        await sv.stop()
        return produced

    produced = asyncio.run(run())
    assert len(produced) == 2
    assert produced[0].number == 3  # continues above the BFT chain
    assert produced[1].number == 4
    assert produced[0].recover_signer() == signer.address()


def test_out_of_order_blocks_buffered_in_pending_cache():
    """Future blocks land in the pending cache and apply once the gap
    closes (reference onBlockV2 future-block caching + tryApplyFromCache)."""
    key = secp256k1.PrivKey.from_secret(b"seq-ooo")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        l2 = MockL2Node()
        sv = StateV2(l2, block_interval=999, signer=None, verifier=verifier)
        await sv.start()
        reactor = BlockBroadcastReactor(sv, verifier)

        # build a 3-block signed chain out-of-band
        src_l2 = MockL2Node()
        chain = []
        parent = src_l2.get_latest_block_v2().hash
        for _ in range(3):
            b, _ = src_l2.request_block_data_v2(parent)
            b.signature = signer.sign(b.hash)
            src_l2.apply_block_v2(b)
            chain.append(b)
            parent = b.hash

        class FakePeer:
            id = "fake-peer"

            def try_send(self, ch, msg):
                return True

        peer = FakePeer()
        # deliver 3, 2 (buffered), then 1 (applies; cache drains the rest)
        await reactor._on_block_v2(chain[2], peer, verify_sig=True)
        await reactor._on_block_v2(chain[1], peer, verify_sig=True)
        assert sv.latest_height() == 0
        assert reactor.pending_cache.size() == 2
        await reactor._on_block_v2(chain[0], peer, verify_sig=True)
        assert sv.latest_height() == 3
        await sv.stop()

    asyncio.run(run())


# --- PR 10: event-driven streaming plane ------------------------------------


def _signed_chain(signer, n, l2=None):
    """n signed linked blocks from a fresh mock chain (+ the source l2)."""
    src = l2 or MockL2Node()
    chain = []
    parent = src.get_latest_block_v2().hash
    for _ in range(n):
        b, _ = src.request_block_data_v2(parent)
        b.signature = signer.sign(b.hash)
        src.apply_block_v2(b)
        chain.append(b)
        parent = b.hash
    return chain, src


class _FakePeer:
    """try_send-only peer double with an adjustable send-queue headroom
    (None = no queue_headroom attribute semantics: always send)."""

    def __init__(self, pid="fake-peer", headroom=None):
        self.id = pid
        self._headroom = headroom
        self.sent: list[tuple[int, bytes]] = []

    def try_send(self, ch, msg):
        if self._headroom is not None and self._headroom <= 0:
            return False
        self.sent.append((ch, msg))
        return True

    def queue_headroom(self, ch):
        return 1000 if self._headroom is None else self._headroom


class _FakeSwitch:
    def __init__(self, peers):
        self.peers = {p.id: p for p in peers}


def test_event_driven_apply_no_polling_tick():
    """With the apply/sync fallback tick cranked to 60 s, gossiped
    blocks must still apply promptly — receipt wakes the plane, the
    interval is only a fallback (the polled original would sit for up
    to 10 s)."""
    key = secp256k1.PrivKey.from_secret(b"seq-event")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        seq = _build_seq_node(signer, verifier, intervals=60.0)
        fol = _build_seq_node(None, verifier, intervals=60.0)
        nodes = [seq, fol]
        await _start_and_connect(nodes)
        for _, r, *_ in nodes:
            await r.on_start()
        import time as _time

        t0 = _time.perf_counter()
        for _ in range(200):
            await asyncio.sleep(0.02)
            if fol[0].latest_height() >= 3:
                break
        wall = _time.perf_counter() - t0
        h = fol[0].latest_height()
        lats = list(fol[1].apply_latencies)
        for _, r, _, _, sw in nodes:
            await r.on_stop()
            await sw.stop()
        return h, wall, lats

    h, wall, lats = asyncio.run(run())
    assert h >= 3, f"follower stuck at {h} with 60 s fallback ticks"
    # 3 blocks at 0.05 s production cadence: event-driven apply keeps
    # pace with production, nowhere near even ONE fallback tick
    assert wall < 10.0, f"took {wall:.1f}s — rode the fallback tick?"
    assert lats and max(lats) < 2.0, f"apply latencies {lats[:5]}..."


def test_windowed_catchup_event_driven():
    """A follower joining 30+ blocks behind catches up through the
    0x51 window without polling ticks: each landed response refills the
    request window (sync_interval is 60 s — the polled original needed
    >= 2 ten-second cycles for a 30-block gap)."""
    key = secp256k1.PrivKey.from_secret(b"seq-window")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        seq = _build_seq_node(signer, verifier, intervals=60.0)
        await seq[0].start()
        for _ in range(30):
            await seq[0].produce_block()
        fol = _build_seq_node(None, verifier, intervals=60.0)
        nodes = [seq, fol]
        await _start_and_connect(nodes)
        seq[1].sequencer_started = True  # StateV2 already started above
        seq[1]._tasks.append(
            asyncio.create_task(seq[1]._broadcast_routine())
        )
        await fol[1].on_start()
        import time as _time

        t0 = _time.perf_counter()
        for _ in range(400):
            await asyncio.sleep(0.02)
            if fol[0].latest_height() >= 30:
                break
        wall = _time.perf_counter() - t0
        h = fol[0].latest_height()
        outstanding = len(fol[1].requested_heights)
        for _, r, _, _, sw in nodes:
            await r.on_stop()
            await sw.stop()
        return h, wall, outstanding

    h, wall, outstanding = asyncio.run(run())
    assert h >= 30, f"follower caught up only to {h}"
    assert wall < 8.0, f"catchup took {wall:.1f}s with 60 s sync ticks"
    # landed heights left the window (satellite: no lifetime accumulation)
    assert outstanding <= 5, f"{outstanding} stale requested heights"


def test_requested_heights_expire():
    """Satellite: requested_heights entries answered by NoBlockResponse
    or belonging to a departed peer expire instead of accumulating for
    the life of the node (and a TTL covers silent peers)."""
    key = secp256k1.PrivKey.from_secret(b"seq-expire")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        sv = StateV2(MockL2Node(), signer=None, verifier=verifier)
        await sv.start()
        reactor = BlockBroadcastReactor(sv, verifier, sync_interval=0.1)
        p1 = _FakePeer("p1")
        p2 = _FakePeer("p2")
        reactor.switch = _FakeSwitch([p1, p2])
        reactor.peer_heights = {"p1": 100, "p2": 100}
        await reactor._request_missing_blocks(1, 100)
        assert len(reactor.requested_heights) == reactor.catchup_window
        # NoBlockResponse from the asked peer expires that height
        h0 = next(iter(reactor.requested_heights))
        asked = reactor.requested_heights[h0][0]
        reactor._on_no_block(h0, p1 if asked == "p1" else p2)
        assert h0 not in reactor.requested_heights
        # ...and clamps the peer's advertised height below the miss
        assert reactor.peer_heights[asked] == h0 - 1
        # a departed peer's in-flight requests expire with it
        victim = p1 if any(
            pid == "p1" for pid, _ in reactor.requested_heights.values()
        ) else p2
        await reactor.remove_peer(victim, "bye")
        assert all(
            pid != victim.id
            for pid, _ in reactor.requested_heights.values()
        )
        # TTL: silent peers' entries age out on the next sync pass
        import time as _time

        stale_t = _time.monotonic() - reactor.request_ttl - 1
        old = {
            h: (pid, stale_t)
            for h, (pid, _t) in reactor.requested_heights.items()
        }
        reactor.requested_heights = dict(old)
        await reactor.check_sync_gap()
        # expired entries were dropped and immediately RE-requested with
        # fresh timestamps (the event-driven window refills itself)
        assert all(
            t > stale_t for _pid, t in reactor.requested_heights.values()
        ), "TTL-expired requests survived the sync pass"
        await sv.stop()

    asyncio.run(run())


def test_encode_once_fanout_many_peers():
    """Tentpole: gossiping one block to N subscriber peers costs ONE
    BlockV2 serialization (memoized encode shared by every framed
    send), and serving the same block on the sync channel reuses it."""
    from tendermint_tpu.types import block_v2 as bv2

    key = secp256k1.PrivKey.from_secret(b"seq-encode-once")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        sv = StateV2(MockL2Node(), signer=None, verifier=verifier)
        await sv.start()
        reactor = BlockBroadcastReactor(sv, verifier)
        peers = [_FakePeer(f"p{i}") for i in range(8)]
        reactor.switch = _FakeSwitch(peers)
        chain, _src = _signed_chain(signer, 1)
        block = chain[0]
        before = bv2.serializations()
        reactor._gossip_block(block, from_peer="")
        assert bv2.serializations() - before == 1
        sent = [p for p in peers if p.sent]
        assert len(sent) == 8
        # all eight sends share the identical framed message object/bytes
        msgs = {p.sent[0][1] for p in peers}
        assert len(msgs) == 1
        # a sync-channel serve of the same block is another cache hit
        reactor.recent_blocks.add(block)
        await reactor._on_block_request(block.number, peers[0])
        assert bv2.serializations() - before == 1
        # mutation invalidates: a re-signed block re-serializes once
        block.signature = signer.sign(block.hash)
        block.encode()
        assert bv2.serializations() - before == 2
        await sv.stop()

    asyncio.run(run())


def test_backpressure_skips_and_revisits_slow_subscriber():
    """Tentpole: a peer with a full 0x50 send queue is skipped (fan-out
    never blocks behind it) and revisited once its queue drains; the
    healthy peers get the block immediately."""
    key = secp256k1.PrivKey.from_secret(b"seq-backpressure")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])

    async def run():
        sv = StateV2(MockL2Node(), signer=None, verifier=verifier)
        await sv.start()
        reactor = BlockBroadcastReactor(sv, verifier)
        slow = _FakePeer("slow", headroom=0)
        fast = _FakePeer("fast")
        reactor.switch = _FakeSwitch([slow, fast])
        chain, _src = _signed_chain(signer, 1)
        block = chain[0]
        reactor._gossip_block(block, from_peer="")
        assert fast.sent and not slow.sent
        assert "slow" in reactor._fanout_pending
        # queue drains -> the revisit task delivers without a re-gossip
        slow._headroom = 10
        for _ in range(100):
            await asyncio.sleep(0.02)
            if slow.sent:
                break
        assert slow.sent, "deferred block never revisited"
        assert not reactor._fanout_pending
        # bookkeeping: the slow peer is now marked sent (no duplicate)
        reactor._gossip_block(block, from_peer="")
        assert len(slow.sent) == 1 and len(fast.sent) == 1
        # teardown the lazily-spawned revisit task
        await reactor.on_stop()

    asyncio.run(run())


def test_verify_batcher_coalesces_burst_into_one_round():
    """Tentpole: a burst of follower-side ECDSA checks coalesces into
    fn-lane scheduler rounds under the `sequencer` class instead of one
    on-loop recover per block."""
    from tendermint_tpu.obs.ledger import DispatchLedger
    from tendermint_tpu.parallel.scheduler import (
        CLASS_ORDER,
        VerifyScheduler,
        set_default_scheduler,
    )

    # lane position: directly below live consensus, above every backfill
    assert CLASS_ORDER.index("sequencer") == CLASS_ORDER.index("consensus") + 1

    key = secp256k1.PrivKey.from_secret(b"seq-batcher")
    signer = LocalSigner(key)
    verifier = StaticSequencerVerifier([signer.address()])
    chain, _src = _signed_chain(signer, 16)
    forged = BlockV2.decode(chain[0].encode())
    forged.signature = bytes([chain[0].signature[0] ^ 1]) + chain[0].signature[1:]

    async def run():
        sched = VerifyScheduler(ledger=DispatchLedger())
        await sched.start()
        set_default_scheduler(sched)
        try:
            from tendermint_tpu.sequencer import SequencerVerifyBatcher

            batcher = SequencerVerifyBatcher(verifier)
            verdicts = await batcher.submit_items(chain + [forged])
            batcher.stop()
            rounds = [
                d for d in sched.ledger.entries()
                if d["engine"] != "sig" and d["classes"] == ["sequencer"]
            ]
            return verdicts, rounds
        finally:
            set_default_scheduler(None)
            await sched.stop()

    verdicts, rounds = asyncio.run(run())
    assert verdicts[:16] == [True] * 16
    assert verdicts[16] is False
    # 17 checks -> a handful of coalesced fn rounds (first may dispatch
    # alone while the rest accumulate), every one under `sequencer`
    assert rounds and len(rounds) <= 3
    assert sum(d["requested"] for d in rounds) == 17


@pytest.mark.chaos
def test_upgrade_crossing_partitioned_follower_heals_via_sync(tmp_path):
    """Satellite: a live in-proc full-Node net (1 sequencer validator +
    2 subscriber followers) crosses UpgradeBlockHeight; one follower is
    then partitioned while the net streams past the small-gap
    threshold, and after heal it must catch back up via the 0x51 sync
    channel's windowed requests."""
    import time as _time

    from tendermint_tpu.chaos import ChaosNetwork, NodeHandle
    from tendermint_tpu.crypto import secp256k1 as _secp
    from tendermint_tpu.libs.metrics import (
        SequencerMetrics,
        default_metrics,
    )
    from tendermint_tpu.node import init_files as _init
    from tendermint_tpu.p2p.transport import NetAddress as _Addr
    from tendermint_tpu.sequencer.broadcast_reactor import (
        SMALL_GAP_THRESHOLD,
    )
    from tendermint_tpu.config import Config
    from tools.loadtime import _build_stream_node, _wait

    switch_height = 2
    seq_key = _secp.PrivKey.from_secret(b"chaos-upgrade-seq")
    seq_addr_hex = "0x" + LocalSigner(seq_key).address().hex()
    seq_home = str(tmp_path / "seq")
    seq_cfg = Config.test_config()
    seq_cfg.root_dir = seq_home
    seq_cfg.base.db_backend = "memory"
    seq_cfg.rpc.laddr = ""
    seq_cfg.p2p.laddr = "tcp://127.0.0.1:0"
    genesis = _init(seq_cfg)

    async def run():
        seq_node, _seq_l2 = _build_stream_node(
            seq_home,
            genesis,
            switch_height=switch_height,
            block_interval=0.05,
            seq_key_hex=seq_key.bytes().hex(),
        )
        followers = []
        for i in range(2):
            node, _ = _build_stream_node(
                str(tmp_path / f"f{i}"),
                genesis,
                switch_height=switch_height,
                block_interval=0.05,
                seq_addr_hex=seq_addr_hex,
            )
            followers.append(node)
        nodes = [seq_node] + followers
        names = ["seq", "f0", "f1"]
        net = ChaosNetwork(seed=3)
        for name, node in zip(names, nodes):
            net.install(
                NodeHandle(
                    name=name,
                    cs=node.consensus,
                    node_key=node.node_key,
                    transport=node.transport,
                    switch=node.switch,
                    block_store=node.block_store,
                )
            )
        try:
            for node in nodes:
                await node.start()
            port = seq_node.transport.listen_port
            for f in followers:
                f.switch.dial_peers_async(
                    [_Addr(seq_node.node_key.id, "127.0.0.1", port)],
                    persistent=True,
                )
            # cross the upgrade: every node switches to sequencer mode
            await _wait(
                lambda: all(
                    n.sequencer_reactor.sequencer_started for n in nodes
                ),
                90.0,
                "all nodes to cross UpgradeBlockHeight",
            )
            lagger = followers[1]
            healthy = followers[0]
            await net.partition("cut", [["seq", "f0"], ["f1"]])
            cut_at = lagger.state_v2.latest_height()
            # build a backlog past the small-gap threshold
            await _wait(
                lambda: healthy.state_v2.latest_height()
                >= cut_at + SMALL_GAP_THRESHOLD + 10,
                90.0,
                "a post-partition backlog past the small-gap threshold",
            )
            assert lagger.state_v2.latest_height() <= cut_at + 2, (
                "partitioned follower kept advancing"
            )
            reqs0 = default_metrics(SequencerMetrics).catchup_requests.value()
            await net.heal("cut")
            t0 = _time.perf_counter()
            await _wait(
                lambda: lagger.state_v2.latest_height()
                >= healthy.state_v2.latest_height() - SMALL_GAP_THRESHOLD,
                90.0,
                "the healed follower to catch up over 0x51",
            )
            wall = _time.perf_counter() - t0
            reqs = (
                default_metrics(SequencerMetrics).catchup_requests.value()
                - reqs0
            )
            # the catch-up rode the windowed sync channel, event-driven:
            # well under one 10 s polling cycle for the whole gap
            assert reqs > 0, "no 0x51 catchup requests after heal"
            assert wall < 30.0, f"catchup took {wall:.1f}s"
        finally:
            for node in nodes:
                try:
                    await node.stop()
                except Exception:
                    pass

    asyncio.run(run())


def test_prewarm_sequencer_family_coverage():
    """Satellite: the `sequencer` scheduler class is a first-class
    prewarm family — manifests record covering it, and --verify fails
    a requirement against a manifest whose recorded coverage predates
    the class (even though its reachable ladder-tier set is empty:
    host-native ECDSA rides the fn lane, not the ladder)."""
    from tools.prewarm import FAMILY_TIERS, check_families

    assert FAMILY_TIERS["sequencer"] == ()
    entries = [
        {"tier": "small", "bucket": 8},
        {"tier": "big", "bucket": 8192},
    ]
    covering = {"entries": entries, "families": sorted(FAMILY_TIERS)}
    assert check_families(covering, families=["sequencer"]) == []
    # a manifest built before the class existed recorded its coverage
    # without `sequencer` -> the requirement fails loudly
    legacy = {
        "entries": entries,
        "families": ["blocksync", "consensus", "evidence", "light",
                     "lightserve"],
    }
    problems = check_families(legacy, families=["sequencer"])
    assert problems and "not covered by this manifest build" in problems[0]
    # a pre-coverage manifest (no `families` key at all) cannot
    # vacuously pass an empty-tier family: there is no tier evidence
    nokey = {"entries": entries}
    problems = check_families(nokey, families=["sequencer"])
    assert problems and "records no family coverage" in problems[0]
    # ...while tier-backed families keep the legacy tier-evidence path
    assert check_families(nokey, families=["lightserve"]) == []
    # unknown names still fail (typo guard unchanged)
    typo = check_families(covering, families=["sequencerr"])
    assert typo and "not a known verify class" in typo[0]
