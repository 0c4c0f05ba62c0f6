"""loadtime — sustained/burst tx load generation + streaming harness.

Reference: test/loadtime/ (tm-load-test based `load` + `report` reading
the blockstore, test/loadtime/README.md) and test/e2e/runner/benchmark.go
:13-76 (block-interval stats over an N-block window).

Same measurement design as the reference: each generated tx embeds its
send-time; the report walks committed blocks and computes per-tx latency
as (block time - embedded send time), plus block-interval min/avg/stddev/
max. The morph fork has no mempool — load enters through the L2 node's
block-data feed (l2node inject), which is where production txs come from
too (SURVEY.md §3.2).

Beyond the original burst tool, this grows two sustained-load pieces
(PERF_ANALYSIS §17):

- `SustainedLoadGenerator` — paced injection at a target tx/s into an
  L2 node's pending feed (the `request_block_data_v2` pull path), so a
  sequencer produces wire-rate blocks instead of one synthetic burst;
- `run_sequencer_stream` — a full-Node in-proc net (1 sequencer
  validator + N subscriber followers, star topology) that crosses
  `UpgradeBlockHeight` under load and measures blocks/s + MB/s through
  both planes (BFT gossip pre-upgrade, BlockV2 streaming post-upgrade),
  event-driven apply latency, encode-once fan-out, a chaos-shaped slow
  subscriber, and partition/heal catchup over the 0x51 sync channel.

Usage:
    python tools/loadtime.py run     # in-proc node, burst load, report
    python tools/loadtime.py report --home <dir>   # report over a store
    python tools/loadtime.py stream --subscribers 8 --tx-rate 2000
"""

from __future__ import annotations

import argparse
import asyncio
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TX_PREFIX = b"loadtime:"


def make_tx(seq: int, size: int = 128) -> bytes:
    """Payload embeds the send timestamp, as the reference's loadtime
    payload proto does (test/loadtime/payload/)."""
    head = TX_PREFIX + str(time.time_ns()).encode() + b":" + str(seq).encode()
    return head + b":" + b"x" * max(0, size - len(head) - 1)


def parse_tx_time(tx: bytes) -> int | None:
    if not tx.startswith(TX_PREFIX):
        return None
    try:
        return int(tx.split(b":", 3)[1])
    except (IndexError, ValueError):
        return None


def report_from_store(block_store, first: int = 1, last: int = 0) -> dict:
    """Latency + block-interval stats (benchmark.go:22-76 shape)."""
    last = last or block_store.height
    latencies_ms: list[float] = []
    intervals_s: list[float] = []
    n_txs = 0
    prev_time = None
    for h in range(max(first, block_store.base), last + 1):
        block = block_store.load_block(h)
        if block is None:
            continue
        bt = block.header.time_ns
        if prev_time is not None:
            intervals_s.append((bt - prev_time) / 1e9)
        prev_time = bt
        for tx in block.data.txs:
            n_txs += 1
            sent = parse_tx_time(tx)
            if sent is not None:
                latencies_ms.append((bt - sent) / 1e6)

    def stats(xs):
        if not xs:
            return {"min": 0, "avg": 0, "stddev": 0, "max": 0}
        return {
            "min": round(min(xs), 2),
            "avg": round(statistics.fmean(xs), 2),
            "stddev": round(statistics.pstdev(xs), 2) if len(xs) > 1 else 0,
            "max": round(max(xs), 2),
        }

    dur_s = (
        sum(intervals_s) if intervals_s else 0.0
    )
    return {
        "blocks": len(intervals_s) + 1 if prev_time is not None else 0,
        "txs": n_txs,
        "tx_per_s": round(n_txs / dur_s, 1) if dur_s else 0.0,
        "block_interval_s": stats(intervals_s),
        "tx_latency_ms": stats(latencies_ms),
    }


async def run_load(
    blocks: int = 10, rate: int = 50, tx_size: int = 128
) -> dict:
    """In-proc single-validator node under tx load; returns the report."""
    import tempfile

    from tendermint_tpu.config import Config
    from tendermint_tpu.l2node.mock import MockL2Node
    from tendermint_tpu.node import Node, init_files

    with tempfile.TemporaryDirectory() as home:
        cfg = Config.test_config()
        cfg.root_dir = home
        cfg.base.db_backend = "memory"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        init_files(cfg)
        l2 = MockL2Node(txs_per_block=0)
        node = Node(cfg, l2_node=l2)
        await node.start()
        seq = 0
        try:
            target = node.consensus.state.last_block_height + blocks
            # one burst per committed height: block cadence varies wildly
            # across machines, so pacing by wall clock makes the number
            # of committed txs (and the report) timing-dependent — pacing
            # by height guarantees >= (blocks-1)*rate txs land in blocks
            injected_at = None
            while node.consensus.state.last_block_height < target:
                h = node.consensus.state.last_block_height
                if h != injected_at:
                    burst = [make_tx(seq + i, tx_size) for i in range(rate)]
                    seq += rate
                    l2.inject_txs(burst)
                    injected_at = h
                await asyncio.sleep(0.02)
            return report_from_store(node.block_store)
        finally:
            await node.stop()


class SustainedLoadGenerator:
    """Paced tx injection at a target rate (tx/s) into an L2 node's
    pending feed — the sustained analog of the one-shot bursts above.
    Injection rides a fixed tick so the pending queue sees a steady
    arrival process instead of per-block bursts; `injected` counts
    everything fed so the harness can report offered vs committed."""

    def __init__(self, l2, rate: int, tx_size: int = 256, tick: float = 0.05):
        self.l2 = l2
        self.rate = max(1, int(rate))
        self.tx_size = tx_size
        self.tick = tick
        self.injected = 0
        self._task = None
        self._carry = 0.0

    async def _run(self) -> None:
        while True:
            self._carry += self.rate * self.tick
            n = int(self._carry)
            self._carry -= n
            if n:
                self.l2.inject_txs(
                    [
                        make_tx(self.injected + i, self.tx_size)
                        for i in range(n)
                    ]
                )
                self.injected += n
            await asyncio.sleep(self.tick)

    def start(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass
            self._task = None


# --- sequencer streaming harness (ISSUE 10 / ROADMAP item 3) ---------------


def _pct(xs, q):
    """Shared percentile rule (obs.report.pct): the sequencer_stream
    rows use the same index semantics as every other report."""
    from tendermint_tpu.obs.report import pct

    return pct(list(xs), q)


def _build_stream_node(
    home: str,
    genesis,
    *,
    switch_height: int,
    block_interval: float,
    seq_key_hex: str = "",
    seq_addr_hex: str = "",
    max_block_txs: int = 0,
):
    """One full Node for the streaming net: memory stores, no RPC/PEX,
    consensus-direct start (no configured peers — the harness dials),
    the default 10 s apply/sync fallback ticks UNTOUCHED (the plane must
    stream event-driven, not because the bench tightened the polling)."""
    import os as _os

    from tendermint_tpu.config import Config
    from tendermint_tpu.l2node.mock import MockL2Node
    from tendermint_tpu.node import Node, init_files

    cfg = Config.test_config()
    cfg.root_dir = home
    cfg.base.db_backend = "memory"
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    cfg.consensus.switch_height = switch_height
    cfg.sequencer.block_interval = block_interval
    if seq_key_hex:
        _os.makedirs(_os.path.join(home, "config"), exist_ok=True)
        with open(_os.path.join(home, "config", "sequencer_key"), "w") as f:
            f.write(seq_key_hex)
        cfg.sequencer.sequencer_key_file = "config/sequencer_key"
    if seq_addr_hex:
        cfg.sequencer.sequencer_addresses = seq_addr_hex
    init_files(cfg)
    # identical deterministic mocks across the net: the seeded V2 chains
    # must agree or followers reject the sequencer's parent hashes
    l2 = MockL2Node(txs_per_block=0, max_block_txs=max_block_txs)
    return Node(cfg, l2_node=l2, genesis=genesis), l2


async def _wait(cond, timeout: float, what: str) -> None:
    deadline = time.perf_counter() + timeout
    while not cond():
        if time.perf_counter() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        await asyncio.sleep(0.02)


async def _stream_net(
    n_followers: int,
    switch_height: int,
    stream_blocks: int,
    tx_rate: int,
    tx_size: int,
    block_interval: float,
    max_block_txs: int,
    chaos_latency_s: float,
    timeout: float,
) -> dict:
    import tempfile

    from tendermint_tpu.chaos import ChaosNetwork, LinkPolicy, NodeHandle
    from tendermint_tpu.config import Config
    from tendermint_tpu.crypto import secp256k1
    from tendermint_tpu.node import init_files as _init
    from tendermint_tpu.sequencer import LocalSigner
    from tendermint_tpu.sequencer.broadcast_reactor import (
        SMALL_GAP_THRESHOLD,
    )
    from tendermint_tpu.types import block_v2 as bv2

    with tempfile.TemporaryDirectory() as root:
        # --- assemble: 1 sequencer validator + N subscriber followers --
        seq_key = secp256k1.PrivKey.from_secret(b"stream-bench-sequencer")
        seq_addr_hex = "0x" + LocalSigner(seq_key).address().hex()
        seq_home = os.path.join(root, "seq")
        os.makedirs(seq_home, exist_ok=True)
        # the sequencer's init_files generates the shared genesis (its
        # privval is the single validator)
        seq_cfg = Config.test_config()
        seq_cfg.root_dir = seq_home
        seq_cfg.base.db_backend = "memory"
        seq_cfg.rpc.laddr = ""
        seq_cfg.p2p.laddr = "tcp://127.0.0.1:0"
        genesis = _init(seq_cfg)
        seq_node, seq_l2 = _build_stream_node(
            seq_home,
            genesis,
            switch_height=switch_height,
            block_interval=block_interval,
            seq_key_hex=seq_key.bytes().hex(),
            max_block_txs=max_block_txs,
        )
        followers = []
        for i in range(n_followers):
            home = os.path.join(root, f"f{i}")
            os.makedirs(home, exist_ok=True)
            node, _l2 = _build_stream_node(
                home,
                genesis,
                switch_height=switch_height,
                block_interval=block_interval,
                seq_addr_hex=seq_addr_hex,
                max_block_txs=max_block_txs,
            )
            followers.append(node)
        nodes = [seq_node] + followers
        names = ["seq"] + [f"f{i}" for i in range(n_followers)]
        net = ChaosNetwork(seed=11)
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
        gen = SustainedLoadGenerator(seq_l2, rate=tx_rate, tx_size=tx_size)
        out: dict = {
            "n_followers": n_followers,
            "switch_height": switch_height,
            "tx_rate": tx_rate,
            "tx_size": tx_size,
            "block_interval": block_interval,
        }
        try:
            for node in nodes:
                await node.start()
            gen.start()
            from tendermint_tpu.p2p.transport import NetAddress

            seq_port = seq_node.transport.listen_port
            for node in followers:
                # persistent: the chaos heal in phase 5 reconnects via
                # the switch's persistent-redial machinery
                node.switch.dial_peers_async(
                    [NetAddress(seq_node.node_key.id, "127.0.0.1", seq_port)],
                    persistent=True,
                )
            await _wait(
                lambda: all(len(f.switch.peers) > 0 for f in followers),
                timeout,
                "followers to connect to the sequencer",
            )

            # --- phase 1: BFT plane to the upgrade height -------------
            t0 = time.perf_counter()
            await _wait(
                lambda: all(
                    f.consensus.state.last_block_height >= switch_height
                    for f in followers
                ),
                timeout,
                "followers to reach the upgrade height over BFT gossip",
            )
            pre_wall = time.perf_counter() - t0
            pre_bytes = 0
            for h in range(1, switch_height + 1):
                blk = seq_node.block_store.load_block(h)
                if blk is not None:
                    pre_bytes += len(blk.encode())
            out["pre_upgrade"] = {
                "blocks": switch_height,
                "wall_s": round(pre_wall, 3),
                "blocks_per_s": round(switch_height / pre_wall, 2),
                "mb_per_s": round(pre_bytes / pre_wall / 1e6, 3),
                "bytes": pre_bytes,
                "commit_pipeline": bool(seq_node.commit_pipeline),
            }

            # --- phase 2: the upgrade switch ---------------------------
            await _wait(
                lambda: all(
                    n.sequencer_reactor.sequencer_started for n in nodes
                ),
                timeout,
                "every node to switch to sequencer mode",
            )

            # --- phase 3: clean streaming window (encode-once + apply
            # latency + post-upgrade throughput) ------------------------
            for f in followers:
                f.sequencer_reactor.apply_latencies.clear()
            h0 = max(
                f.state_v2.latest_height() for f in followers
            )
            target = h0 + stream_blocks
            ser0 = bv2.serializations()
            bc0 = seq_node.sequencer_reactor.metrics.blocks_broadcast.value()
            t0 = time.perf_counter()
            await _wait(
                lambda: all(
                    f.state_v2.latest_height() >= target for f in followers
                ),
                timeout,
                f"{stream_blocks} streamed BlockV2s on every follower",
            )
            post_wall = time.perf_counter() - t0
            ser_delta = bv2.serializations() - ser0
            bcast = (
                seq_node.sequencer_reactor.metrics.blocks_broadcast.value()
                - bc0
            )
            post_bytes = 0
            for h in range(h0 + 1, target + 1):
                blk = seq_l2.get_block_by_number(h)
                if blk is not None:
                    post_bytes += len(blk.encode())
            lats = [
                lat
                for f in followers
                for lat in f.sequencer_reactor.apply_latencies
            ]
            out["post_upgrade"] = {
                "blocks": stream_blocks,
                "wall_s": round(post_wall, 3),
                "blocks_per_s": round(stream_blocks / post_wall, 2),
                "mb_per_s": round(post_bytes / post_wall / 1e6, 3),
                "fanout_mb_per_s": round(
                    post_bytes * n_followers / post_wall / 1e6, 3
                ),
                "bytes": post_bytes,
                "apply_latency_p50_ms": round(_pct(lats, 0.5) * 1e3, 2),
                "apply_latency_p95_ms": round(_pct(lats, 0.95) * 1e3, 2),
                "apply_latency_samples": len(lats),
                # one BlockV2 serialization per broadcast block is the
                # encode-once contract (star topology: nobody relays)
                "block_serializations": int(ser_delta),
                "blocks_broadcast": int(bcast),
                "encodes_per_broadcast_block": round(
                    ser_delta / max(1.0, bcast), 3
                ),
            }

            # --- phase 4: chaos slow subscriber ------------------------
            if chaos_latency_s > 0 and n_followers >= 2:
                slow = followers[0]
                healthy = followers[1:]
                net.set_link_policy(
                    "seq",
                    "f0",
                    LinkPolicy(latency_s=chaos_latency_s),
                    reverse=LinkPolicy(latency_s=chaos_latency_s),
                )
                h1 = max(f.state_v2.latest_height() for f in healthy)
                target = h1 + stream_blocks
                t0 = time.perf_counter()
                await _wait(
                    lambda: all(
                        f.state_v2.latest_height() >= target
                        for f in healthy
                    ),
                    timeout,
                    "healthy followers to stream past the shaped link",
                )
                chaos_wall = time.perf_counter() - t0
                out["chaos_slow_subscriber"] = {
                    "link_latency_ms": chaos_latency_s * 1e3,
                    "blocks": stream_blocks,
                    "healthy_wall_s": round(chaos_wall, 3),
                    "healthy_blocks_per_s": round(
                        stream_blocks / chaos_wall, 2
                    ),
                    "slow_follower_behind": int(
                        target - slow.state_v2.latest_height()
                    ),
                    "clean_blocks_per_s": out["post_upgrade"][
                        "blocks_per_s"
                    ],
                }
                net.set_link_policy(
                    "seq", "f0", LinkPolicy(), reverse=LinkPolicy()
                )

            # --- phase 5: partition + heal -> 0x51 windowed catchup ----
            lagger = followers[-1]
            await net.partition(
                "lag", [[n for n in names if n != names[-1]], [names[-1]]]
            )
            gap_from = lagger.state_v2.latest_height()
            target_gap = gap_from + SMALL_GAP_THRESHOLD + stream_blocks
            # the producer's own chain is the head; with >= 2 followers
            # also require the healthy ones to keep streaming (a lone
            # follower IS the lagger — `rest` may be empty)
            rest = [f for f in followers if f is not lagger] or [seq_node]
            await _wait(
                lambda: all(
                    f.state_v2.latest_height() >= target_gap for f in rest
                ),
                timeout,
                "a catchup backlog beyond the small-gap threshold",
            )
            lagger.sequencer_reactor.apply_latencies.clear()
            await net.heal("lag")
            await _wait(
                lambda: len(lagger.switch.peers) > 0,
                timeout,
                "the healed follower to redial the sequencer",
            )
            t0 = time.perf_counter()
            head = lambda: max(  # noqa: E731
                f.state_v2.latest_height() for f in rest
            )
            await _wait(
                lambda: lagger.state_v2.latest_height()
                >= head() - SMALL_GAP_THRESHOLD,
                timeout,
                "the healed follower to catch up over the sync channel",
            )
            catchup_wall = time.perf_counter() - t0
            clats = list(lagger.sequencer_reactor.apply_latencies)
            out["catchup_after_heal"] = {
                "blocks_behind": int(target_gap - gap_from),
                "wall_s": round(catchup_wall, 3),
                "apply_latency_p50_ms": round(_pct(clats, 0.5) * 1e3, 2),
                "apply_latency_p95_ms": round(_pct(clats, 0.95) * 1e3, 2),
                "requested_outstanding": len(
                    lagger.sequencer_reactor.requested_heights
                ),
                # the event-driven plane vs the reference's fixed tick:
                # a 10 s polling loop needs ceil(gap/window) cycles
                "polling_floor_s": 10.0,
            }
            out["injected_txs"] = gen.injected
        finally:
            await gen.stop()
            for node in nodes:
                try:
                    await node.stop()
                except Exception:
                    pass
    return out


def run_sequencer_stream(
    n_followers: int = 8,
    switch_height: int = 3,
    stream_blocks: int = 25,
    tx_rate: int = 2000,
    tx_size: int = 256,
    block_interval: float = 0.08,
    max_block_txs: int = 256,
    chaos_latency_s: float = 0.25,
    timeout: float = 240.0,
) -> dict:
    """Entry point for the `stream` CLI below. Returns the stats dict
    of _stream_net."""
    os.environ.setdefault("TM_TPU_SKIP_WARM", "1")
    return asyncio.run(
        _stream_net(
            n_followers=n_followers,
            switch_height=switch_height,
            stream_blocks=stream_blocks,
            tx_rate=tx_rate,
            tx_size=tx_size,
            block_interval=block_interval,
            max_block_txs=max_block_txs,
            chaos_latency_s=chaos_latency_s,
            timeout=timeout,
        )
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("run", help="in-proc node + burst load + report")
    rp.add_argument("--blocks", type=int, default=10)
    rp.add_argument("--rate", type=int, default=50)
    rp.add_argument("--size", type=int, default=128)
    gp = sub.add_parser("report", help="report over an existing home dir")
    gp.add_argument("--home", required=True)
    sp = sub.add_parser(
        "stream",
        help="sequencer streaming net: sustained load through the "
        "upgrade-height switch, N subscribers, chaos rows",
    )
    sp.add_argument("--subscribers", type=int, default=8)
    sp.add_argument("--switch-height", type=int, default=3)
    sp.add_argument("--stream-blocks", type=int, default=25)
    sp.add_argument("--tx-rate", type=int, default=2000)
    sp.add_argument("--tx-size", type=int, default=256)
    sp.add_argument("--block-interval", type=float, default=0.08)
    sp.add_argument("--chaos-latency-ms", type=float, default=250.0)
    args = ap.parse_args()

    import json

    if args.cmd == "run":
        rep = asyncio.run(
            run_load(blocks=args.blocks, rate=args.rate, tx_size=args.size)
        )
    elif args.cmd == "stream":
        rep = run_sequencer_stream(
            n_followers=args.subscribers,
            switch_height=args.switch_height,
            stream_blocks=args.stream_blocks,
            tx_rate=args.tx_rate,
            tx_size=args.tx_size,
            block_interval=args.block_interval,
            chaos_latency_s=args.chaos_latency_ms / 1e3,
        )
    else:
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.kv import SqliteKV

        bs = BlockStore(
            SqliteKV(os.path.join(args.home, "data", "blockstore.db"))
        )
        rep = report_from_store(bs)
    print(json.dumps(rep, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
