"""Chaos subsystem: seeded determinism, partition/heal liveness,
kill/restart recovery, retry jitter/backoff, and the strict device rule
that replaced backend-outage degradation.

Deterministic by construction (every random draw comes from the scenario
seed), so the whole module stays inside the tier-1 `not slow` budget.
Select with `-m chaos`.
"""

from __future__ import annotations

import asyncio
import json
import random
import sys

import pytest

from tendermint_tpu.chaos import (
    ChaosConn,
    ChaosNetwork,
    FaultTrace,
    LinkPolicy,
    Scenario,
    ScenarioRunner,
    Step,
    link_rng,
)
from tendermint_tpu.chaos.scenario import random_scenario

pytestmark = pytest.mark.chaos


# --- link model (unit) ------------------------------------------------------


class _SinkConn:
    """Fake SecretConnection capturing written frames."""

    def __init__(self):
        self.frames: list[bytes] = []
        self.closed = False

    async def write(self, data: bytes) -> None:
        self.frames.append(data)

    async def read(self) -> bytes:  # pragma: no cover - never used
        await asyncio.sleep(3600)

    def close(self) -> None:
        self.closed = True


def _packets(n_msgs: int, ch: int = 0x20, payload: bytes = b"x" * 40):
    """n single-packet mconn messages on one channel."""
    return [bytes([ch, 1]) + payload + b"%03d" % i for i in range(n_msgs)]


async def _drive(policy: LinkPolicy, seed: int, n_msgs: int = 40):
    sink = _SinkConn()
    conn = ChaosConn(
        sink, policy, link_rng(seed, "a", "b"), link_id="a>b"
    )
    for pkt in _packets(n_msgs):
        await conn.write(pkt)
    # wait until everything scheduled has been pumped out
    deadline = asyncio.get_running_loop().time() + 10.0
    expected = sum(
        1 + e[6] for e in conn.trace.entries if e[3] == "deliver"
    )
    while len(sink.frames) < expected:
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError("chaos pump stalled")
        await asyncio.sleep(0.01)
    conn.close()
    return sink, conn


def test_link_trace_deterministic():
    """Same seed + same message sequence => byte-identical fault trace;
    a different seed diverges."""
    policy = LinkPolicy(
        latency_s=0.001, jitter_s=0.004, drop=0.25, duplicate=0.15
    )

    async def run(seed):
        _, conn = await _drive(policy, seed)
        return conn.trace.to_jsonl()

    t1 = asyncio.run(run(7))
    t2 = asyncio.run(run(7))
    t3 = asyncio.run(run(8))
    assert t1 == t2, "same-seed fault traces diverged"
    assert t1 != t3, "different seeds produced identical traces"
    # and the trace actually contains both outcomes at a 25% drop rate
    kinds = {json.loads(line)[3] for line in t1.splitlines()}
    assert kinds == {"drop", "deliver"}


def test_link_drop_all_and_duplicate_all():
    async def run():
        sink_drop, _ = await _drive(LinkPolicy(drop=1.0), seed=1, n_msgs=10)
        assert sink_drop.frames == []
        sink_dup, _ = await _drive(
            LinkPolicy(duplicate=1.0), seed=1, n_msgs=10
        )
        assert len(sink_dup.frames) == 20
        # FIFO preserved under latency+jitter when reorder is off
        sink_fifo, _ = await _drive(
            LinkPolicy(latency_s=0.002, jitter_s=0.01), seed=3, n_msgs=15
        )
        assert sink_fifo.frames == _packets(15)

    asyncio.run(run())


def test_link_multiplexed_messages_stay_coherent():
    """Interleaved multi-packet messages on two channels keep per-message
    packet runs contiguous per channel (reassembly-safe shaping)."""

    async def run():
        sink = _SinkConn()
        conn = ChaosConn(
            sink,
            LinkPolicy(latency_s=0.001, jitter_s=0.003),
            link_rng(5, "a", "b"),
        )
        # channel 0x20 message in two packets, interleaved with a
        # channel 0x30 single-packet message
        await conn.write(bytes([0x20, 0]) + b"part1")
        await conn.write(bytes([0x30, 1]) + b"other")
        await conn.write(bytes([0x20, 1]) + b"part2")
        while len(sink.frames) < 3:
            await asyncio.sleep(0.01)
        conn.close()
        # the 0x20 frames must be adjacent (one scheduling unit)
        idx = [i for i, f in enumerate(sink.frames) if f[0] == 0x20]
        assert idx[1] == idx[0] + 1
        assert sink.frames[idx[0]][2:] == b"part1"
        assert sink.frames[idx[1]][2:] == b"part2"

    asyncio.run(run())


def test_link_policy_updates_apply_to_live_conn():
    """set_link/set_default_policy mid-scenario must reshape connections
    that are ALREADY established: ChaosConn re-resolves its policy per
    message through policy_fn."""

    async def run():
        sink = _SinkConn()
        policies = {"cur": LinkPolicy()}
        conn = ChaosConn(
            sink,
            policies["cur"],
            link_rng(1, "a", "b"),
            policy_fn=lambda: policies["cur"],
        )
        pkts = _packets(3)
        await conn.write(pkts[0])  # noop: passes straight through
        assert sink.frames == [pkts[0]]
        policies["cur"] = LinkPolicy(drop=1.0)
        await conn.write(pkts[1])  # dropped by the NEW policy, same conn
        policies["cur"] = LinkPolicy()
        await conn.write(pkts[2])
        assert sink.frames == [pkts[0], pkts[2]]
        conn.close()

    asyncio.run(run())


# --- dial retry jitter (p2p/switch.py satellite) ----------------------------


class _DeadTransport:
    """Transport whose dials always fail and that never accepts."""

    def __init__(self):
        self.listen_port = 0
        self.dials = 0

    async def accept(self):
        await asyncio.sleep(3600)

    async def dial(self, addr):
        self.dials += 1
        raise ConnectionError("unreachable")

    async def close(self):
        pass

    def _node_info_fn(self):  # pragma: no cover - never reached
        raise AssertionError


class _RecordingRng(random.Random):
    def __init__(self, seed):
        super().__init__(seed)
        self.ceilings: list[float] = []

    def uniform(self, a, b):
        self.ceilings.append(b)
        return 0.001  # keep the test fast; the draw itself is recorded


def test_dial_retry_full_jitter_cap_and_gave_up_event():
    from tendermint_tpu.p2p.switch import (
        EVENT_PEER_DIAL_GAVE_UP,
        Switch,
    )
    from tendermint_tpu.p2p.transport import NetAddress

    async def run():
        transport = _DeadTransport()
        rng = _RecordingRng(42)
        sw = Switch(transport, max_dial_attempts=6, dial_rng=rng)
        gave_up = []
        sw.events.add_listener(
            "t", EVENT_PEER_DIAL_GAVE_UP, gave_up.append
        )
        await sw.start()
        addr = NetAddress("deadbeef", "127.0.0.1", 1)
        await sw._dial_with_retry(addr)
        await sw.stop()
        return transport, rng, gave_up, addr

    transport, rng, gave_up, addr = asyncio.run(run())
    assert transport.dials == 6, "attempt cap not enforced"
    # full-jitter ceilings: 0.2·2ⁿ capped at 10 — and the sleep is a
    # uniform draw below the ceiling, not the fixed lockstep schedule
    assert rng.ceilings == [
        min(10.0, 0.2 * 2**n) for n in range(1, 6)
    ]
    assert gave_up == [addr], "terminal gave-up event not fired"


# --- statesync chunk backoff + rotation -------------------------------------


def test_chunk_retry_backoff_and_last_sender():
    from tendermint_tpu.statesync.chunks import ChunkQueue

    now = [0.0]
    q = ChunkQueue(2, now=lambda: now[0])
    assert q.allocate() == 0
    assert q.allocate() == 1
    q.note_request(0, "pA")
    q.retry(0)
    # immediately after a failure the chunk is backing off
    assert q.allocate() is None
    assert q.last_sender(0) == "pA"
    assert q.retries(0) == 1
    now[0] = 0.11  # past the 0.1s first backoff
    assert q.allocate() == 0
    q.retry(0, "pB")
    assert q.last_sender(0) == "pB"
    now[0] = 0.25  # second backoff doubles to 0.2s: not yet elapsed
    assert q.allocate() is None
    now[0] = 0.45
    assert q.allocate() == 0


def test_chunk_fetch_rotates_away_from_failing_peer():
    from tendermint_tpu.statesync.chunks import ChunkQueue
    from tendermint_tpu.statesync.syncer import Syncer, _DiscoveredSnapshot

    class _Peer:
        def __init__(self, pid):
            self.id = pid

    class _Snap:
        height, format, chunks, hash = 5, 1, 1, b"h"

    requests = []

    async def run():
        syncer = Syncer(
            app_snapshot_conn=None,
            state_provider=None,
            request_chunk=lambda peer, h, f, i: requests.append(
                (peer.id, i)
            ),
        )
        d = _DiscoveredSnapshot(_Snap())
        d.peers = [_Peer("pA"), _Peer("pB")]
        q = ChunkQueue(1)
        # chunk 0 was fetched from pA and failed
        assert q.allocate() == 0
        q.note_request(0, "pA")
        q.retry(0, "pA")
        task = asyncio.create_task(syncer._fetch_chunks(d, q))
        deadline = asyncio.get_running_loop().time() + 5.0
        while not requests:
            if asyncio.get_running_loop().time() > deadline:
                break
            await asyncio.sleep(0.02)
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, Exception):
            pass

    asyncio.run(run())
    assert requests, "refetch never happened"
    assert requests[0][0] == "pB", "refetch did not rotate off the failing peer"


# --- the strict device rule --------------------------------------------------
# A run that finds no chip fails: no fallback that hides the device.
# (chip_smoke.py and the benchmark hold the same rule in their own tests.)


def _env_without_platform() -> dict:
    """This sandbox has no accelerator; with JAX_PLATFORMS unset JAX
    looks for a TPU, finds none and falls back to the CPU silently."""
    import os

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    return env


def test_require_chip_rule(monkeypatch):
    from tendermint_tpu.libs.device import device_info, require_chip

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    require_chip("tpu")
    with pytest.raises(SystemExit) as e:
        require_chip("cpu")  # the silent fallback
    assert e.value.code not in (0, None) and "no accelerator" in str(e.value)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    require_chip("cpu")  # asked for explicitly
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit):
        require_chip("cpu")
    info = device_info()
    assert info["platform"] == "cpu" and info["device_count"] >= 1
    assert info["device_kind"]


def test_multichip_capture_exits_nonzero_without_a_chip():
    import subprocess

    proc = subprocess.run(
        [sys.executable, "tools/multichip_capture.py", "4", "--no-dryrun"],
        capture_output=True,
        text=True,
        timeout=300,
        env=_env_without_platform(),
        cwd="/root/repo",
    )
    assert proc.returncode != 0, proc.stdout
    assert "no accelerator" in proc.stderr
    assert not proc.stdout.strip()  # no artifact, no fallback row


# --- scenario e2e on a 4-validator mesh -------------------------------------


def _mesh():
    from tests.chaos_harness import build_chaos_handles

    return build_chaos_handles(4)


def _run_storm(seed: int, until: int):
    from tests.chaos_harness import chain_hashes, start_mesh, stop_mesh

    scenario = Scenario(
        seed=seed,
        steps=[
            Step(
                at_height=2,
                action="clock_skew",
                params={"node": "n3", "scale": 1.2},
            ),
        ],
        default_policy=LinkPolicy(
            latency_s=0.005, jitter_s=0.01, drop=0.02, duplicate=0.02
        ),
    )

    async def run():
        handles = _mesh()
        runner = ScenarioRunner(handles, scenario)
        await start_mesh(handles)
        try:
            heights = await runner.run(until_height=until, timeout=120)
            hashes = await chain_hashes(handles, until - 1)
        finally:
            await stop_mesh(handles)
        return runner.plan_jsonl(), heights, hashes

    return asyncio.run(run())


def test_scenario_determinism_latency_drop_storm():
    """Same seed => byte-identical scenario plan trace and identical
    committed-height sequences up to the target on a real 4-validator
    p2p mesh under a latency+drop+duplicate storm."""
    until = 4
    plan1, heights1, hashes1 = _run_storm(seed=7, until=until)
    plan2, heights2, hashes2 = _run_storm(seed=7, until=until)
    assert plan1 == plan2, "same-seed scenario plans diverged"
    want = list(range(1, until + 1))
    for heights in (heights1, heights2):
        for name, seq in heights.items():
            assert seq[:until] == want, f"{name} missed heights: {seq}"
    assert len(hashes1) == 1 and len(hashes2) == 1, "chains diverged"
    # different seed => different plan bytes (seed is recorded)
    plan3, _, _ = _run_storm(seed=8, until=2)
    assert plan1 != plan3


def test_partition_heal_liveness():
    """2|2 split: neither half can commit (no 2/3 of 4); after heal all
    four reconverge on one chain and resume committing."""
    from tests.chaos_harness import chain_hashes, start_mesh, stop_mesh

    async def run():
        handles = _mesh()
        net = ChaosNetwork(seed=11)
        for h in handles:
            net.install(h)
        await start_mesh(handles)
        try:
            await asyncio.gather(
                *(h.cs.wait_for_height(2, timeout=60) for h in handles)
            )
            await net.partition(
                "split", [["n0", "n1"], ["n2", "n3"]]
            )
            # cross-group links must be down
            for h in handles:
                for peer_id in h.switch.peers:
                    other = net._name_for(peer_id)
                    assert net.allowed(h.name, other), (
                        f"live cross-partition conn {h.name}<->{other}"
                    )
            await asyncio.sleep(1.0)  # let in-flight commits settle
            stalled = [h.block_store.height for h in handles]
            await asyncio.sleep(2.0)
            assert [
                h.block_store.height for h in handles
            ] == stalled, "a 2|2 partition committed blocks"

            await net.heal("split")
            target = max(stalled) + 2
            await asyncio.gather(
                *(
                    h.cs.wait_for_height(target, timeout=90)
                    for h in handles
                )
            )
            hashes = await chain_hashes(handles, target)
            assert len(hashes) == 1, "nodes diverged after heal"
        finally:
            await stop_mesh(handles)

    asyncio.run(run())


def test_kill_restart_scenario_recovers():
    """Seeded kill/restart timeline: node n3 dies at height 2, restarts
    4s in with fresh p2p around the same state, and the whole mesh
    (including n3) reaches the target on one chain."""
    from tests.chaos_harness import chain_hashes, start_mesh, stop_mesh

    scenario = Scenario(
        seed=13,
        steps=[
            Step(at_height=2, action="kill", params={"node": "n3"}),
            # after=0: never restart before the kill has fired, even if
            # the mesh takes >4s to reach height 2
            Step(
                at_time=4.0,
                action="restart",
                params={"node": "n3"},
                after=0,
            ),
        ],
    )

    async def run():
        handles = _mesh()
        runner = ScenarioRunner(handles, scenario)
        await start_mesh(handles)
        try:
            heights = await runner.run(until_height=4, timeout=120)
            assert all(seq[:4] == [1, 2, 3, 4] for seq in heights.values())
            hashes = await chain_hashes(handles, 3)
            assert len(hashes) == 1, "chains diverged after kill/restart"
        finally:
            await stop_mesh(handles)

    asyncio.run(run())


def test_random_scenario_is_seed_stable():
    names = ["n0", "n1", "n2", "n3"]
    s1 = random_scenario(99, names)
    s2 = random_scenario(99, names)
    s3 = random_scenario(100, names)
    as_plan = lambda s: [st.resolved(i) for i, st in enumerate(s.steps)] + [
        s.default_policy
    ]
    assert as_plan(s1) == as_plan(s2)
    assert as_plan(s1) != as_plan(s3)
