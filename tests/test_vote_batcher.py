"""Vote micro-batcher: batching dynamics, ordering, consensus integration."""

import asyncio

import numpy as np

from tendermint_tpu.consensus.vote_batcher import VoteBatcher
from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.batch_verifier import BatchVerifier


class SlowStubVerifier:
    """Deterministic stand-in: records batch sizes, adds device-ish
    latency so queued submissions coalesce into the next batch."""

    def __init__(self, delay=0.02):
        self.delay = delay
        self.batches = []

    def verify(self, items):
        import time

        time.sleep(self.delay)  # runs in the executor thread
        self.batches.append(len(items))
        return np.array([it.sig != b"BAD" * 21 + b"B" for it in items])


def test_batches_coalesce_under_load():
    """While one device call is in flight, arriving votes form the next
    batch — ≥8-vote batches must emerge from 32 rapid submissions
    (VERDICT round-1 item 4's 'demonstrably runs in batches >= 8')."""
    stub = SlowStubVerifier()
    batcher = VoteBatcher(verifier=stub)

    async def run():
        subs = [
            asyncio.create_task(
                batcher.submit(b"\x01" * 32, b"msg%d" % i, b"\x02" * 64)
            )
            for i in range(32)
        ]
        results = await asyncio.gather(*subs)
        batcher.stop()
        return results

    results = asyncio.run(run())
    assert all(results)
    assert max(stub.batches) >= 8, f"batches never coalesced: {stub.batches}"
    assert sum(stub.batches) == 32


def test_latency_adapts_with_concurrency():
    """The adaptivity contract (SURVEY §7.3 hard part 3), measured: at
    concurrency 1 a vote rides a batch of 1; at concurrency 256 batches
    grow to the verifier's appetite and the p99 per-vote latency stays
    FAR below the serial-drain model (256 sequential verifier calls).
    This pins the mechanism with a deterministic stub."""
    import time

    stub = SlowStubVerifier(delay=0.02)
    batcher = VoteBatcher(verifier=stub)
    lat: dict[int, list] = {}

    async def one(i):
        t0 = time.monotonic()
        ok = await batcher.submit(b"\x01" * 32, b"m%d" % i, b"\x02" * 64)
        assert ok
        return time.monotonic() - t0

    async def run():
        # concurrency 1
        lat[1] = [await one(0) for _ in range(4)]
        single_max_batch = max(batcher.batch_sizes)
        # concurrency 256
        lat[256] = await asyncio.gather(*(one(i) for i in range(256)))
        batcher.stop()
        return single_max_batch

    single_max_batch = asyncio.run(run())
    assert single_max_batch == 1, "light load must ride batches of 1"
    assert max(batcher.batch_sizes) >= 64, (
        f"batch telemetry never adapted: {list(batcher.batch_sizes)}"
    )
    p99 = sorted(lat[256])[int(0.99 * 255)]
    serial_drain = 256 * stub.delay  # 5.12s if votes were verified 1-by-1
    assert p99 < serial_drain / 4, (
        f"p99 {p99:.3f}s not amortized vs serial {serial_drain:.2f}s"
    )


def test_results_resolve_in_submission_order():
    stub = SlowStubVerifier(delay=0.01)
    batcher = VoteBatcher(verifier=stub)
    order = []

    async def submit_one(i):
        sig = b"BAD" * 21 + b"B" if i % 3 == 0 else b"\x02" * 64
        ok = await batcher.submit(b"\x01" * 32, b"m%d" % i, sig)
        order.append((i, ok))

    async def run():
        await asyncio.gather(*(submit_one(i) for i in range(24)))
        batcher.stop()

    asyncio.run(run())
    assert [i for i, _ in order] == list(range(24))
    for i, ok in order:
        assert ok == (i % 3 != 0)


def test_real_signatures_through_batcher():
    """End-to-end with the real BatchVerifier (host fast path: the device
    kernel is covered by test_batch_verifier)."""
    verifier = BatchVerifier(min_device_batch=1 << 30)
    batcher = VoteBatcher(verifier=verifier)
    keys = [host.PrivKey.from_secret(b"vb%d" % i) for i in range(6)]

    async def run():
        tasks = []
        for i, k in enumerate(keys):
            msg = b"vote-%d" % i
            sig = k.sign(msg) if i != 3 else b"\x00" * 64
            tasks.append(
                asyncio.create_task(
                    batcher.submit(k.public_key().data, msg, sig)
                )
            )
        out = await asyncio.gather(*tasks)
        batcher.stop()
        return out

    out = asyncio.run(run())
    assert out == [True, True, True, False, True, True]


def test_consensus_net_with_batcher_over_p2p():
    """The reactor's vote path routes through the micro-batcher and the
    4-node net still reaches consensus with pre-verified inserts."""
    from .test_consensus_reactor import build_p2p_node, connect_full_mesh
    from .helpers import make_genesis, make_validators

    vs, pvs = make_validators(4)
    genesis = make_genesis(vs)

    async def run():
        nodes = [build_p2p_node(vs, pv, genesis) for pv in pvs]
        for cs, nk, t, sw in nodes:
            await t.listen()
            await sw.start()
        await connect_full_mesh(nodes)
        for cs, *_ in nodes:
            await cs.start()
        await asyncio.gather(
            *(cs.wait_for_height(2, timeout=60) for cs, *_ in nodes)
        )
        # every node's reactor ran votes through its batcher
        sizes = []
        for _, _, _, sw in nodes:
            r = sw.reactors["consensus"]
            sizes.extend(r.vote_batcher.batch_sizes)
        hashes = {cs.block_store.load_block(2).hash() for cs, *_ in nodes}
        for cs, nk, t, sw in nodes:
            await cs.stop()
            await sw.stop()
        return sizes, hashes

    sizes, hashes = asyncio.run(run())
    assert len(hashes) == 1, "nodes disagree"
    assert sum(sizes) > 0, "no votes flowed through the micro-batcher"
