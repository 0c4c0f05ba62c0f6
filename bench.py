"""Benchmark: batched ed25519 verification throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference verifies votes serially via Go x/crypto ed25519 —
~50-70 µs/verify single-core (SURVEY.md §6; crypto/ed25519/bench_test.go is
the reference harness, no stored numbers), i.e. ~15,000 sigs/s. The
BASELINE.json north-star targets >50k sigs/s/chip. vs_baseline is measured
sigs/s divided by the 15k serial-CPU figure.

The reported metric is the STEADY-STATE vote-verification path: cached
per-validator window tables (the consensus workload re-verifies the same
validator set every height — SURVEY.md §3.3 — so the framework builds each
pubkey's table once; table build cost is measured separately and amortizes
to ~zero over a validator's lifetime). The generic path (fresh pubkeys,
in-batch decompression) is also measured and printed to stderr.

The device suite needs the chip: unless JAX_PLATFORMS=cpu is set
explicitly, a resolved platform other than `tpu` ends the run non-zero
(libs/device.require_chip), and a run in which any metric block raised
exits non-zero too.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# persistent XLA compile cache (same dir the test conftest uses): the deep
# crypto programs compile once per machine, not once per bench round
from tendermint_tpu.libs.jax_cache import configure_compile_cache

configure_compile_cache()

BASELINE_SERIAL_SIGS_PER_S = 15_000.0


def _meta_block() -> dict:
    """Artifact provenance stamp: which device produced the numbers
    (platform, device_kind, device_count as JAX reports them)."""
    from tendermint_tpu.libs.device import device_stamp

    return device_stamp()


def _phase_failed(failed: list, name: str, e: Exception) -> None:
    """A metric block raised: later blocks still run, the run ends
    non-zero (main)."""
    print(f"# {name} failed: {e!r}", file=sys.stderr)
    failed.append(name)


def _reg_snapshot() -> dict:
    """Shape-registry snapshot; paired with _shape_stats around each
    metric so the JSON artifact carries per-metric
    distinct_program_shapes / device_dispatch_count (PERF_ANALYSIS §10:
    shape churn and dispatch counts were only visible via cProfile)."""
    from tendermint_tpu.crypto.shape_registry import default_shape_registry

    return default_shape_registry().snapshot()


def _shape_stats(before: dict) -> dict:
    from tendermint_tpu.crypto.shape_registry import (
        ShapeRegistry,
        default_shape_registry,
    )

    return ShapeRegistry.delta(
        before, default_shape_registry().snapshot()
    )


def _record_direct(tier: str, bucket: int, count: int = 1) -> None:
    """Registry accounting for dispatches the bench drives through raw
    jitted kernels (the headline path bypasses BatchVerifier._dispatch,
    so it self-reports under bench_* tiers)."""
    from tendermint_tpu.crypto.shape_registry import default_shape_registry

    reg = default_shape_registry()
    for _ in range(count):
        reg.record_dispatch(tier, bucket)


def _ledger_mark() -> dict:
    """Device-cost ledger position (obs/ledger.py); paired with
    _device_cost_block so every artifact carries the family's per-class
    device-seconds, fill-ratio p50/p95 and padding-waste rows next to
    the shape-registry deltas. Schedulers record into the process
    default ledger, so one mark brackets every scheduler a family
    builds. Rounds the family drives OUTSIDE a scheduler (the headline
    suite's raw jitted kernels) are invisible here by design — the
    block accounts the scheduler plane, the registry delta accounts
    raw dispatch counts."""
    from tendermint_tpu.obs.ledger import default_ledger

    return default_ledger().mark()


def _device_cost_block(mark: dict) -> dict:
    from tendermint_tpu.obs.ledger import default_ledger

    return default_ledger().summary(since=mark)


# bulk-tier batch: the scheduler's max_batch and the ladder's top rung.
# (It was chosen as the throughput knee of the earlier executor; not
# re-measured on the current chip — ROADMAP S2.)
BATCH = 16384
ITERS = 3


def _build_args(batch: int):
    import jax.numpy as jnp

    from __graft_entry__ import _make_batch

    n_unique = min(batch, 128)  # realistic validator-set size
    pub, rb, sb, kb, s_ok = _make_batch(n_unique)
    reps = (batch + n_unique - 1) // n_unique

    def tile(x):
        return np.tile(x, (reps,) + (1,) * (x.ndim - 1))[:batch]

    return tuple(
        jnp.asarray(t) for t in (tile(pub), tile(rb), tile(sb), tile(kb), tile(s_ok))
    )


def _time_best(fn, *args) -> float:
    import jax

    out = np.asarray(fn(*args))  # compile + warm
    assert out.all(), "benchmark batch failed to verify"
    best = float("inf")
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = np.asarray(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _time_pipelined(fn, *args, depth: int = 8) -> float:
    """Steady-state throughput: enqueue `depth` batches, then sync them all.

    This is the shape of the bulk workloads (blocksync replay streams many
    blocks' commit batches at the device — SURVEY.md §3.4); dispatch is
    async, so the fixed host↔device round-trip latency amortizes across the
    pipeline instead of taxing every batch. Returns seconds per batch."""
    np.asarray(fn(*args))  # warm
    best = float("inf")
    for _ in range(ITERS):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(depth)]
        for o in outs:
            assert np.asarray(o).all(), "pipelined batch failed to verify"
        best = min(best, (time.perf_counter() - t0) / depth)
    return best


def main() -> None:
    ap = argparse.ArgumentParser(description="tpu-tendermint bench")
    ap.add_argument(
        "--family",
        default="",
        choices=(
            "",
            "consensus_pipeline",
            "consensus_pacing",
            "lightserve",
            "committee_scale",
            "sequencer_stream",
            "verify_service",
            "qc_catchup",
        ),
        help="run ONE named bench family instead of the device "
        "throughput suite. 'consensus_pacing' measures wall-per-height "
        "static vs adaptive timeouts on the 4-validator harness; "
        "'lightserve' drives an N-thousand light-client swarm through "
        "the serving plane (tools/lightserve_bench.py); "
        "'committee_scale' sweeps 100+-validator in-proc p2p nets over "
        "the batched vote-gossip plane; 'sequencer_stream' drives the "
        "post-upgrade BlockV2 streaming plane (tools/loadtime.py) "
        "through a 1-sequencer + N-subscriber net crossing "
        "UpgradeBlockHeight under sustained load; 'verify_service' "
        "spawns ONE device-owning verify-service process + N node "
        "processes submitting real ed25519+BLS committee rounds over "
        "UDS IPC (tools/verify_service_bench.py) — the first honest "
        "committee-crypto rows above 32 validators; 'qc_catchup' "
        "verifies the same real-signature chain segment as N-sig "
        "commits vs one-pairing QuorumCertificates per committee size "
        "(tools/qc_bench.py) — the aggregate round-compression claim. "
        "All are wall-clock families, valid on the CPU backend.",
    )
    ap.add_argument(
        "--clients",
        type=int,
        default=1000,
        help="lightserve family: simulated light clients in the swarm",
    )
    ap.add_argument(
        "--sizes",
        default="",
        help="committee sizes to sweep (committee_scale default "
        "4,32,100,200; verify_service default 4,32,100)",
    )
    ap.add_argument(
        "--straggler-ms",
        type=float,
        default=50.0,
        help="committee_scale family: chaos link delay for the "
        "straggler scenario (0 disables it)",
    )
    ap.add_argument(
        "--live-max",
        type=int,
        default=100,
        help="committee_scale family: largest committee to run as a "
        "LIVE in-proc net (larger sizes still get the dissemination "
        "and BLS metrics; a 200-node single-process net is minutes "
        "per height on one CPU)",
    )
    ap.add_argument(
        "--service-max-batch",
        type=int,
        default=2048,
        help="verify_service family: the service's scheduler max_batch "
        "(capped at 2048 by default — on the CPU harness the bulk "
        "buckets past that cost multi-minute cold compiles and add no "
        "signal; raise on real silicon)",
    )
    ap.add_argument(
        "--max-procs",
        type=int,
        default=8,
        help="verify_service family: node processes the committee is "
        "split across (each hosts ceil(n/procs) node submission loops "
        "with their OWN service connections)",
    )
    ap.add_argument(
        "--subscribers",
        type=int,
        default=8,
        help="sequencer_stream family: follower peers subscribed to "
        "the BlockV2 broadcast plane",
    )
    ap.add_argument(
        "--tx-rate",
        type=int,
        default=2000,
        help="sequencer_stream family: sustained injection rate (tx/s) "
        "into the sequencer's L2 pull path",
    )
    ap.add_argument(
        "--tx-size",
        type=int,
        default=256,
        help="sequencer_stream family: synthetic tx payload bytes",
    )
    ap.add_argument(
        "--stream-blocks",
        type=int,
        default=25,
        help="sequencer_stream family: streamed BlockV2s per "
        "measurement window",
    )
    args = ap.parse_args()

    if args.family == "consensus_pacing":
        # wall-clock family: no device requirement, no backend probe —
        # the verify path rides the host fast lane either way and both
        # variants pay it identically
        print(json.dumps(_bench_consensus_pacing()))
        return
    if args.family == "consensus_pipeline":
        # wall-clock family, same CPU-validity argument as pacing: both
        # variants share one verify path; the DELTA is the overlap
        print(json.dumps(_bench_consensus_pipeline()))
        return
    if args.family == "lightserve":
        print(json.dumps(_bench_lightserve(n_clients=args.clients)))
        return
    if args.family == "committee_scale":
        sizes = tuple(
            int(s)
            for s in (args.sizes or "4,32,100,200").split(",")
            if s.strip()
        )
        print(
            json.dumps(
                _bench_committee_scale(
                    sizes=sizes,
                    straggler_s=args.straggler_ms / 1e3,
                    live_max=args.live_max,
                )
            )
        )
        return

    if args.family == "verify_service":
        # this family's default sweep stops at 100 (200 x 200 rows of
        # real crypto per height is minutes/height on the CPU harness
        # for no extra signal); an explicit --sizes always wins
        sizes = tuple(
            int(s)
            for s in (args.sizes or "4,32,100").split(",")
            if s.strip()
        )
        print(
            json.dumps(
                _bench_verify_service(
                    sizes=sizes,
                    max_procs=args.max_procs,
                    service_max_batch=args.service_max_batch,
                )
            )
        )
        return

    if args.family == "qc_catchup":
        sizes = tuple(
            int(s)
            for s in (args.sizes or "4,32,100").split(",")
            if s.strip()
        )
        print(json.dumps(_bench_qc_catchup(sizes=sizes)))
        return

    if args.family == "sequencer_stream":
        print(
            json.dumps(
                _bench_sequencer_stream(
                    subscribers=args.subscribers,
                    tx_rate=args.tx_rate,
                    tx_size=args.tx_size,
                    stream_blocks=args.stream_blocks,
                )
            )
        )
        return

    import jax
    import jax.numpy as jnp

    from tendermint_tpu.libs.device import require_chip
    from tendermint_tpu.ops.ed25519_batch import (
        neg_pubkey_bigtable,
        verify_prehashed,
        verify_prehashed_bigcache,
    )

    # this process owns the device from here on; no probe child first
    # (a child that opened the chip would have to release it in time)
    require_chip(jax.devices()[0].platform)
    failed: list[str] = []

    pub, rb, sb, kb, s_ok = _build_args(BATCH)
    before_headline = _reg_snapshot()
    ledger_mark = _ledger_mark()

    # one-time validator fixed-window table build (amortized over the
    # validator's life; the BatchVerifier caches these device-resident)
    t0 = time.perf_counter()
    tables, valid_u = jax.jit(neg_pubkey_bigtable)(pub[:128])
    tables = jax.block_until_ready(tables)
    np.asarray(valid_u)
    build_t = time.perf_counter() - t0
    reps = (BATCH + 127) // 128
    idx = jnp.asarray(np.tile(np.arange(128, dtype=np.int32), reps)[:BATCH])
    valid = jnp.tile(valid_u, (reps,))[:BATCH]

    cached_fn = jax.jit(verify_prehashed_bigcache)
    dt_lat = _time_best(cached_fn, tables, valid, idx, rb, sb, kb, s_ok)
    dt_cached = _time_pipelined(
        cached_fn, tables, valid, idx, rb, sb, kb, s_ok
    )
    # headline dispatches bypass BatchVerifier: self-report them
    # (warm+ITERS latency rounds, warm+ITERS*8 pipelined, 1 table build)
    _record_direct("bench_build", 128)
    _record_direct("bench_big", BATCH, count=2 + ITERS + ITERS * 8)
    cached_rate = BATCH / dt_cached
    print(
        f"# cached-table path: {cached_rate:,.0f} sigs/s pipelined "
        f"({dt_cached*1e3:.0f} ms/{BATCH}); single-batch latency "
        f"{dt_lat*1e3:.0f} ms ({BATCH/dt_lat:,.0f} sigs/s); table build "
        f"(128 keys, incl. compile): {build_t:.1f}s",
        file=sys.stderr,
    )

    # generic path (fresh pubkeys) — informational: a failure here does
    # not lose the headline measurement, it fails the run at the end
    generic_rate = None
    before_generic = _reg_snapshot()
    try:
        generic_fn = jax.jit(verify_prehashed)
        dt_generic = _time_best(generic_fn, pub, rb, sb, kb, s_ok)
        _record_direct("bench_generic", BATCH, count=1 + ITERS)
        generic_rate = BATCH / dt_generic
        print(
            f"# generic path: {generic_rate:,.0f} sigs/s "
            f"({dt_generic*1e3:.0f} ms/{BATCH})",
            file=sys.stderr,
        )
    except Exception as e:
        _phase_failed(failed, "generic path measurement", e)
    # run the in-proc net once; the attribution ships as the breakdown
    # and the quorum-close lags join the bench family as scalars
    height_attribution = _bench_height_attribution(failed)
    conservation = (
        height_attribution.pop("wall_conservation", None)
        if height_attribution
        else None
    )
    _finish(
        {
            "metric": "ed25519_vote_verify_throughput",
            "value": round(cached_rate, 1),
            "unit": "sigs/s/chip",
            "vs_baseline": round(
                cached_rate / BASELINE_SERIAL_SIGS_PER_S, 3
            ),
            "meta": _meta_block(),
            "device_cost": _device_cost_block(ledger_mark),
            **_shape_stats(before_headline),
            # the rest of the bench family (VERDICT r2 weak #7: one
            # recorded metric left regressions in the other paths
            # invisible); each entry is metric/value/unit/vs_baseline
            "extra_metrics": (
                [
                    # fresh-pubkey (validator-churn) path — recorded so
                    # the driver sees regressions in the uncached edge
                    # (VERDICT r2 weak #2)
                    {
                        "metric": "ed25519_generic_verify_throughput",
                        "value": round(generic_rate, 1),
                        "unit": "sigs/s/chip",
                        "vs_baseline": round(
                            generic_rate / BASELINE_SERIAL_SIGS_PER_S, 3
                        ),
                        **_shape_stats(before_generic),
                    }
                ]
                if generic_rate
                else []
            )
            + _extra_metrics(
                cached_fn, tables, valid, idx, rb, sb, kb, s_ok, failed
            )
            + _quorum_lag_metrics(height_attribution),
            # where a height's wall time goes (p50/p95 per consensus
            # step + WAL/store/verify spans) — the scalar above finally
            # ships with its breakdown
            "latency_attribution": height_attribution,
            # the exhaustive per-height bucket decomposition; buckets
            # must sum to measured wall (bench_trend rejects rows
            # that violate it) and dark_time is gated
            "wall_conservation": conservation,
        },
        failed,
    )


def _finish(artifact: dict, failed: list) -> None:
    """Print the artifact; a run in which any block raised names the
    blocks in it and exits non-zero."""
    if failed:
        artifact["failed_phases"] = list(failed)
    print(json.dumps(artifact))
    if failed:
        raise SystemExit(1)


def _bench_consensus_pacing(heights: int = 10, warm: int = 4) -> dict:
    """consensus_pacing family: wall-per-height on the 4-validator
    in-proc net, static reference-default timeouts vs adaptive pacing
    ([consensus] adaptive_timeouts, consensus/pacing.py), with the
    timeout-floor share of wall from the trace attribution
    (obs.wall_attribution). Wall-clock family: the CPU backend measures
    it faithfully (PERF_ANALYSIS §14) — vote verify cost is the same in
    both variants and the DELTA is the floors.

    Static config = the reference defaults (timeout_commit=1.0 s etc.,
    skip_timeout_commit=false): exactly the floor a default-configured
    committee pays per height regardless of how fast it actually
    closes quorums. The adaptive variant learns the live arrival tail
    and pays (tail * margin) instead, ceiling-clamped to those same
    statics."""
    import asyncio

    from tendermint_tpu import obs
    from tendermint_tpu.consensus.state_machine import ConsensusConfig
    from tests.helpers import make_genesis, make_validators
    from tests.test_consensus import make_node, wire_net

    def run_variant(adaptive: bool) -> dict:
        cfg = ConsensusConfig(
            # reference defaults, straggler wait ON (the default)
            timeout_propose=3.0,
            timeout_propose_delta=0.5,
            timeout_prevote=1.0,
            timeout_prevote_delta=0.5,
            timeout_precommit=1.0,
            timeout_precommit_delta=0.5,
            timeout_commit=1.0,
            skip_timeout_commit=False,
            adaptive_timeouts=adaptive,
            # learn fast enough to converge inside the warmup heights
            adaptive_window=64,
            adaptive_min_samples=4,
            adaptive_recover_step=0.25,
            adaptive_tail_quantile=0.95,
            adaptive_min_factor=0.02,
        )
        tracer = obs.Tracer(enabled=True, ring_size=65536)

        async def run():
            vs, pvs = make_validators(4)
            genesis = make_genesis(vs)
            nodes = [
                make_node(
                    vs,
                    pv,
                    genesis,
                    config=cfg,
                    # node 0 records; sharing one ring across nodes
                    # would overlap their height windows in attribution
                    tracer=(
                        tracer if i == 0 else obs.Tracer(enabled=False)
                    ),
                )
                for i, pv in enumerate(pvs)
            ]
            css = [n[0] for n in nodes]
            wire_net(css)
            for cs in css:
                await cs.start()
            await asyncio.gather(
                *(cs.wait_for_height(warm, timeout=120) for cs in css)
            )
            tracer.clear()
            t0 = time.perf_counter()
            await asyncio.gather(
                *(
                    cs.wait_for_height(warm + heights, timeout=600)
                    for cs in css
                )
            )
            wall = (time.perf_counter() - t0) / heights
            snap = css[0].pacing.snapshot() if css[0].pacing else None
            for cs in css:
                await cs.stop()
            return wall, snap

        wall, snap = asyncio.run(run())
        recs = [r.to_json() for r in tracer.records()]
        att = obs.wall_attribution(recs)
        return {
            "wall_ms": round(wall * 1e3, 1),
            "floor_share": (att["aggregate"] or {}).get("floor_share"),
            "pacing": snap,
            "conservation": obs.wall_conservation(recs),
        }

    ledger_mark = _ledger_mark()
    static = run_variant(False)
    adaptive = run_variant(True)
    commit_eff = None
    if adaptive["pacing"]:
        commit_eff = round(
            adaptive["pacing"]["steps"]["commit"]["effective_s"] * 1e3, 1
        )
    return {
        "metric": "consensus_pacing_wall_per_height",
        "value": adaptive["wall_ms"],
        "unit": (
            f"ms/height adaptive (static {static['wall_ms']} ms at "
            f"reference-default timeouts; 4 validators, in-proc, "
            f"wall-clock)"
        ),
        "vs_baseline": round(
            static["wall_ms"] / max(adaptive["wall_ms"], 0.01), 2
        ),
        "meta": _meta_block(),
        "device_cost": _device_cost_block(ledger_mark),
        "wall_conservation": adaptive["conservation"],
        "extra_metrics": [
            {
                "metric": "consensus_pacing_timeout_floor_share_static",
                "value": static["floor_share"],
                "unit": "fraction of wall in timeout-floor steps",
            },
            {
                "metric": "consensus_pacing_timeout_floor_share_adaptive",
                "value": adaptive["floor_share"],
                "unit": "fraction of wall in timeout-floor steps",
            },
            {
                "metric": "consensus_pacing_commit_wait_adaptive",
                "value": commit_eff,
                "unit": "ms effective commit wait (static 1000)",
            },
        ],
    }


def _bench_consensus_pipeline(heights: int = 12, warm: int = 4) -> dict:
    """consensus_pipeline family (PERF_ANALYSIS §22): effective
    wall-per-height on the 4-validator in-proc net with QC-chained
    height pipelining — enter H+1's propose when H's precommit quorum
    closes, chain H's apply/save/fsync behind the durability barrier in
    the background — against the identical adaptive-pacing config run
    serially. Wall-clock family: both variants share one verify path
    and one host crypto plane; the DELTA is the overlap.

    The conservation block comes from the PIPELINED variant: buckets
    exceed the wall exactly by the booked pipeline_overlap_ms (height
    H's background finalization attributed under H while H+1's steps
    own the shared wall), dark_time stays 0 — the decomposition remains
    exhaustive under overlap (obs.report.wall_conservation)."""
    import asyncio

    from tendermint_tpu import obs
    from tendermint_tpu.consensus.state_machine import ConsensusConfig
    from tests.helpers import make_genesis, make_validators
    from tests.test_consensus import make_node, wire_net

    def run_variant(pipelined: bool) -> dict:
        cfg = ConsensusConfig(
            # the consensus_pacing adaptive config, unchanged — r14's
            # 454.8 ms/height baseline is this exact schedule serial
            timeout_propose=3.0,
            timeout_propose_delta=0.5,
            timeout_prevote=1.0,
            timeout_prevote_delta=0.5,
            timeout_precommit=1.0,
            timeout_precommit_delta=0.5,
            timeout_commit=1.0,
            skip_timeout_commit=False,
            adaptive_timeouts=True,
            adaptive_window=64,
            adaptive_min_samples=4,
            adaptive_recover_step=0.25,
            adaptive_tail_quantile=0.95,
            adaptive_min_factor=0.02,
            pipelined_heights=pipelined,
        )
        tracer = obs.Tracer(enabled=True, ring_size=65536)

        async def run():
            vs, pvs = make_validators(4)
            genesis = make_genesis(vs)
            nodes = [
                make_node(
                    vs,
                    pv,
                    genesis,
                    config=cfg,
                    tracer=(
                        tracer if i == 0 else obs.Tracer(enabled=False)
                    ),
                )
                for i, pv in enumerate(pvs)
            ]
            css = [n[0] for n in nodes]
            wire_net(css)
            for cs in css:
                await cs.start()
            await asyncio.gather(
                *(cs.wait_for_height(warm, timeout=120) for cs in css)
            )
            tracer.clear()
            t0 = time.perf_counter()
            await asyncio.gather(
                *(
                    cs.wait_for_height(warm + heights, timeout=600)
                    for cs in css
                )
            )
            wall = (time.perf_counter() - t0) / heights
            app_hashes = {cs.state.app_hash for cs in css}
            for cs in css:
                await cs.stop()
            assert len(app_hashes) == 1, "variant diverged"
            return wall

        wall = asyncio.run(run())
        recs = [r.to_json() for r in tracer.records()]
        return {
            "wall_ms": round(wall * 1e3, 1),
            "conservation": obs.wall_conservation(recs),
        }

    ledger_mark = _ledger_mark()
    serial = run_variant(False)
    piped = run_variant(True)
    agg = piped["conservation"].get("aggregate", {})
    return {
        "metric": "consensus_pipeline_wall_per_height",
        "value": piped["wall_ms"],
        "unit": (
            f"ms effective/height pipelined (serial "
            f"{serial['wall_ms']} ms same run+config; 4 validators, "
            f"in-proc, wall-clock)"
        ),
        "vs_baseline": round(
            serial["wall_ms"] / max(piped["wall_ms"], 0.01), 2
        ),
        "meta": _meta_block(),
        "device_cost": _device_cost_block(ledger_mark),
        "wall_conservation": piped["conservation"],
        "extra_metrics": [
            {
                "metric": "consensus_pipeline_serial_wall_per_height",
                "value": serial["wall_ms"],
                "unit": "ms/height, same adaptive config, no overlap",
            },
            {
                "metric": "consensus_pipeline_overlap_share",
                "value": agg.get("pipeline_overlap_share"),
                "unit": (
                    "booked background-finalization overlap as a "
                    "fraction of pipelined wall"
                ),
            },
            {
                "metric": "consensus_pipeline_floor_share",
                "value": agg.get("floor_share"),
                "unit": "fraction of pipelined wall in timeout floors",
            },
            {
                "metric": "consensus_pipeline_commit_pipeline_share",
                "value": agg.get("commit_pipeline_share"),
                "unit": (
                    "apply/save/QC-assembly share of pipelined wall "
                    "(mostly overlap-credited)"
                ),
            },
            {
                "metric": "consensus_pipeline_dark_fraction",
                "value": agg.get("dark_fraction"),
                "unit": "unattributed share of pipelined wall",
            },
        ],
    }


def _bench_qc_catchup(sizes=(4, 32, 100), blocks: int = 8) -> dict:
    """qc_catchup family (PERF_ANALYSIS §21): the same real-signature
    chain segment verified both ways per committee size — the N-sig
    commit window (the blocksync baseline, cost linear in committee
    size) vs one QuorumCertificate pairing check per block through the
    qc_verify engine (cost ~flat: 2 pairings + one G2 MSM per block,
    one RLC multi-pairing per window). Wall-clock family, CPU-valid —
    the pairing plane is host-native either way; what the artifact
    claims is the SHAPE of the curves, and the light-proof compression
    ratio measured on the same chain."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.qc_bench import run_qc_catchup

    ledger_mark = _ledger_mark()
    stats = run_qc_catchup(sizes=sizes, blocks=blocks)
    rows = stats["rows"]
    by_n = {r["validators"]: r for r in rows}
    head_n = max(sizes)
    head = by_n[head_n]
    return {
        "metric": f"blocksync_commits_per_s@{head_n}",
        "value": head["qc_commits_per_s"],
        "unit": (
            f"commits/s ({head_n} validators, {head['blocks']}-block "
            f"QC windows, one RLC multi-pairing per window; N-sig "
            f"baseline {head['baseline_commits_per_s']} commits/s in "
            f"the same artifact)"
        ),
        "vs_baseline": round(
            head["qc_commits_per_s"]
            / max(head["baseline_commits_per_s"], 1e-9),
            2,
        ),
        "meta": _meta_block(),
        "device_cost": _device_cost_block(ledger_mark),
        "qc_flatness_4_to_max": stats["qc_flatness"],
        "baseline_growth_4_to_max": stats["baseline_growth"],
        "extra_metrics": [
            {
                "metric": f"qc_verify_wall_per_block_n{r['validators']}",
                "value": r["qc_wall_per_block_ms"],
                "unit": (
                    f"ms/block (baseline "
                    f"{r['baseline_wall_per_block_ms']} ms/block over "
                    f"{r['validators']} ed25519 rows)"
                ),
            }
            for r in rows
        ]
        + [
            {
                "metric": f"qc_proof_compression_n{r['validators']}",
                "value": r["proof_compression"],
                "unit": (
                    f"x smaller ({r['proof_bytes_full']} commit bytes "
                    f"-> {r['proof_bytes_qc']} qc bytes)"
                ),
            }
            for r in rows
        ],
        "rows": rows,
    }


def _bench_lightserve(n_clients: int = 1000, heights: int = 8) -> dict:
    """lightserve family: N simulated light clients sync a 4-validator
    net through the serving plane (tendermint_tpu/lightserve via
    tools/lightserve_bench.run_swarm). Wall-clock family, CPU-valid —
    the point is the AMORTIZATION: cache hit-rate, verify dedup, and
    device-dispatch counts sublinear in the client count, plus the
    divergent-witness scenario landing LightClientAttackEvidence in
    the evidence pool. vs_baseline is the dedup factor: verifications
    the swarm REQUESTED over verifications actually executed (a
    serverless swarm executes every one)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.lightserve_bench import run_swarm

    ledger_mark = _ledger_mark()
    stats = run_swarm(n_clients=n_clients, heights=heights)
    verify = stats["verify"]
    cache = stats["cache"]
    scenarios = stats.get("scenarios", {})
    dedup_factor = verify["requests"] / max(1, verify["executed"])
    return {
        "metric": "lightserve_swarm_sync",
        "value": stats["clients_per_s"],
        "unit": (
            f"clients/s ({stats['synced']}/{stats['n_clients']} clients "
            f"synced to height {stats['target_height']} of a "
            f"{stats['n_validators']}-validator net, "
            f"{stats['wall_s']}s wall)"
        ),
        "vs_baseline": round(dedup_factor, 1),
        "meta": _meta_block(),
        "device_cost": _device_cost_block(ledger_mark),
        **stats["registry_delta"],
        "extra_metrics": [
            {
                "metric": "lightserve_cache_hit_rate",
                "value": cache["hit_rate"],
                "unit": (
                    f"fraction ({cache['hits']} hits / "
                    f"{cache['misses']} misses, {cache['assembled']} "
                    f"assemblies)"
                ),
            },
            {
                "metric": "lightserve_verify_dedup_rate",
                "value": verify["dedup_rate"],
                "unit": (
                    f"fraction ({verify['requests']} requests -> "
                    f"{verify['executed']} executed)"
                ),
            },
            {
                "metric": "lightserve_requests_per_device_dispatch",
                "value": stats["requests_per_device_dispatch"],
                "unit": (
                    f"verify requests/device dispatch "
                    f"({stats['registry_delta']['device_dispatch_count']}"
                    f" dispatches, {stats['scheduler_rounds']} scheduler "
                    f"rounds, for {stats['n_clients']} clients — "
                    f"sublinearity of device work in swarm size)"
                ),
            },
            {
                "metric": "lightserve_attack_evidence_pool_size",
                "value": (
                    scenarios.get("divergent_witness", {}).get(
                        "evidence_pool_size", 0
                    )
                ),
                "unit": (
                    "LightClientAttackEvidence accepted by the pool "
                    "(divergent-witness scenario)"
                ),
            },
        ],
        "scenarios": scenarios,
    }


def _bench_sequencer_stream(
    subscribers: int = 8,
    tx_rate: int = 2000,
    tx_size: int = 256,
    stream_blocks: int = 25,
) -> dict:
    """sequencer_stream family (PERF_ANALYSIS §17): a 1-sequencer +
    N-subscriber full-Node net crosses UpgradeBlockHeight under
    sustained tx load (tools/loadtime.run_sequencer_stream). Rows:
    blocks/s + MB/s through the BFT plane pre-upgrade (the PR 4 commit
    pipeline absorbing the write load) and the BlockV2 streaming plane
    post-upgrade, event-driven apply latency p50/p95 (receipt ->
    applied; the reference polls at a fixed 10 s tick), encode-once
    fan-out (exactly one BlockV2 serialization per broadcast block,
    counter-backed), a chaos-shaped slow subscriber that must not stall
    the healthy fan-out, and partition/heal catchup over the 0x51 sync
    window. vs_baseline is the polling-floor replacement: 10 s over the
    measured p95 apply latency."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.loadtime import run_sequencer_stream

    ledger_mark = _ledger_mark()
    stats = run_sequencer_stream(
        n_followers=subscribers,
        tx_rate=tx_rate,
        tx_size=tx_size,
        stream_blocks=stream_blocks,
    )
    pre = stats["pre_upgrade"]
    post = stats["post_upgrade"]
    chaos = stats.get("chaos_slow_subscriber") or {}
    catchup = stats.get("catchup_after_heal") or {}
    p95_s = max(post["apply_latency_p95_ms"], 0.01) / 1e3
    extra = [
        {
            "metric": "sequencer_stream_pre_upgrade_blocks_per_s",
            "value": pre["blocks_per_s"],
            "unit": (
                f"blocks/s over BFT gossip ({pre['blocks']} blocks to "
                f"the upgrade height, {pre['mb_per_s']} MB/s, commit "
                f"pipeline {'on' if pre['commit_pipeline'] else 'off'})"
            ),
        },
        {
            "metric": "sequencer_stream_mb_per_s",
            "value": post["mb_per_s"],
            "unit": (
                f"MB/s of BlockV2 payload applied per subscriber "
                f"({post['fanout_mb_per_s']} MB/s aggregate across "
                f"{subscribers} subscribers)"
            ),
        },
        {
            "metric": "sequencer_apply_latency_p95",
            "value": post["apply_latency_p95_ms"],
            "unit": (
                f"ms receipt->applied (p50 "
                f"{post['apply_latency_p50_ms']} ms, "
                f"{post['apply_latency_samples']} samples; the polled "
                f"reference floor is 10000 ms)"
            ),
            "vs_baseline": round(10.0 / p95_s, 1),
        },
        {
            "metric": "sequencer_encodes_per_broadcast_block",
            "value": post["encodes_per_broadcast_block"],
            "unit": (
                f"BlockV2 serializations per broadcast block "
                f"({post['block_serializations']} serializations / "
                f"{post['blocks_broadcast']} blocks to {subscribers} "
                f"subscribers — encode-once fan-out)"
            ),
        },
    ]
    if chaos:
        extra.append(
            {
                "metric": "sequencer_stream_chaos_slow_subscriber",
                "value": chaos["healthy_blocks_per_s"],
                "unit": (
                    f"healthy-subscriber blocks/s with one "
                    f"{chaos['link_latency_ms']:.0f} ms shaped link "
                    f"(clean {chaos['clean_blocks_per_s']}; shaped "
                    f"follower {chaos['slow_follower_behind']} blocks "
                    f"behind at window end — fan-out wall bounded by "
                    f"the healthy peers)"
                ),
            }
        )
    if catchup:
        extra.append(
            {
                "metric": "sequencer_catchup_after_heal_wall",
                "value": catchup["wall_s"],
                "unit": (
                    f"s for a healed follower {catchup['blocks_behind']}"
                    f" blocks behind to re-enter the small-gap window "
                    f"over 0x51 (windowed requests; the 10 s polled "
                    f"loop needed >= 1 cycle per "
                    f"{_small_gap_threshold()} heights)"
                ),
            }
        )
    return {
        "metric": "sequencer_stream_blocks_per_s",
        "value": post["blocks_per_s"],
        "unit": (
            f"BlockV2/s applied by every one of {subscribers} "
            f"subscribers post-upgrade ({post['blocks']} blocks, "
            f"{stats['tx_rate']} tx/s offered load, wall "
            f"{post['wall_s']} s)"
        ),
        "vs_baseline": round(10.0 / p95_s, 1),
        "meta": _meta_block(),
        "device_cost": _device_cost_block(ledger_mark),
        "stats": stats,
        "extra_metrics": extra,
    }


def _small_gap_threshold() -> int:
    from tendermint_tpu.sequencer.broadcast_reactor import (
        SMALL_GAP_THRESHOLD,
    )

    return SMALL_GAP_THRESHOLD


def _committee_config(n: int):
    """Static timeouts generous enough that a CPU-backed in-proc
    committee never advances rounds on verify latency — the bench
    measures the gossip plane, not timeout churn. Adaptive pacing off:
    one variable at a time."""
    from tendermint_tpu.consensus.state_machine import ConsensusConfig

    scale = 1.0 + n / 25.0
    return ConsensusConfig(
        timeout_propose=10.0 * scale,
        timeout_propose_delta=2.0,
        timeout_prevote=10.0 * scale,
        timeout_prevote_delta=2.0,
        timeout_precommit=10.0 * scale,
        timeout_precommit_delta=2.0,
        timeout_commit=0.05,
        skip_timeout_commit=True,
    )


def _run_committee_net(
    n: int,
    heights: int = 2,
    warm: int = 1,
    batch: bool = True,
    straggler_s: float = 0.0,
    stub_verify=None,
) -> dict:
    """One committee-scale measurement: an n-validator in-proc net over
    REAL encrypted p2p (tests/chaos_harness) with zipf-weighted powers,
    ring+chords topology past the full-mesh knee, and a process-wide
    VerifyScheduler so every node's vote chunks coalesce into shared
    dispatch rounds. batch=False builds legacy one-vote-per-tick
    reactors (the baseline variant — only run at small sizes; at 100+
    the one-vote wire is exactly the pathology this family measures).
    straggler_s > 0 delays one heavy-validator link after warmup
    (chaos straggler regime). stub_verify (default: auto, n > 32)
    replaces signature verification with an all-accept stub: a shared
    single-process event loop cannot absorb 100+ nodes' device
    verifies (each blocks every node at once), so committee-scale live
    walls measure the gossip/consensus plane and are labeled as such —
    real-crypto dispatch accounting comes from the n <= 32 runs."""
    import asyncio
    import contextlib

    from tendermint_tpu import obs
    from tendermint_tpu.chaos import ChaosNetwork, LinkPolicy
    from tendermint_tpu.parallel.scheduler import (
        VerifyScheduler,
        set_default_scheduler,
    )
    from tests.chaos_harness import (
        AllTrueVerifier,
        build_chaos_handles,
        start_mesh,
        stop_mesh,
        stub_default_verifier,
        zipf_powers,
    )

    if stub_verify is None:
        stub_verify = n > 32
    tracer = obs.Tracer(enabled=True, ring_size=65536)
    handles = build_chaos_handles(
        powers=zipf_powers(n),
        config=_committee_config(n),
        vote_batch=batch,
        verifier_factory=AllTrueVerifier if stub_verify else None,
        # node 0 records quorum attribution; per-node rings at 200
        # validators would be ~all of the bench's memory for no signal
        tracer_factory=lambda name: (
            tracer if name == "n0" else obs.Tracer(enabled=False)
        ),
        ping_interval=30.0,
    )
    degree = 0 if n <= 8 else 4
    timeout = 120 + n * 3 * (warm + heights)
    stub_ctx = (
        stub_default_verifier() if stub_verify else contextlib.nullcontext()
    )

    async def run():
        sched = VerifyScheduler()
        await sched.start()
        set_default_scheduler(sched)
        net = None
        if straggler_s > 0:
            net = ChaosNetwork(seed=7)
            for h in handles:
                net.install(h)
        try:
            await start_mesh(handles, peer_degree=degree)
            await asyncio.gather(
                *(
                    h.cs.wait_for_height(warm, timeout=timeout)
                    for h in handles
                )
            )
            if net is not None:
                # delay every link OUT of the last-index validator: at
                # zipf powers it is the lightest, so quorum never stalls
                # on it but its votes are the measured stragglers
                lagger = handles[-1].name
                for other in handles[:-1]:
                    net.set_link_policy(
                        lagger,
                        other.name,
                        LinkPolicy(latency_s=straggler_s),
                        reverse=LinkPolicy(),
                    )
            for h in handles:
                r = h.switch.reactors["consensus"]
                r.gossip_ticks = 0
                r.gossip_idle_ticks = 0
                r.gossip_votes_sent = 0
                r.gossip_batches_sent = 0
            tracer.clear()
            before = _reg_snapshot()
            t0 = time.perf_counter()
            await asyncio.gather(
                *(
                    h.cs.wait_for_height(warm + heights, timeout=timeout)
                    for h in handles
                )
            )
            wall = time.perf_counter() - t0
            ticks = votes = idle = batches = 0
            for h in handles:
                r = h.switch.reactors["consensus"]
                ticks += r.gossip_ticks
                votes += r.gossip_votes_sent
                idle += r.gossip_idle_ticks
                batches += r.gossip_batches_sent
            return wall, ticks, votes, idle, batches, _shape_stats(before)
        finally:
            await stop_mesh(handles)
            set_default_scheduler(None)
            await sched.stop()

    with stub_ctx:
        wall, ticks, votes, idle, batches, reg = asyncio.run(run())
    # quorum-close lag on node 0's ring (the same sketch rule the
    # pacing controllers and prior BENCH artifacts use)
    from tendermint_tpu.obs import StreamingQuantile

    sketch = StreamingQuantile(window=4096)
    sketch.extend(
        float((r.get("fields") or {}).get("lag_ms", 0.0))
        for r in (rec.to_json() for rec in tracer.records())
        if r.get("name") == "quorum.close"
        and (r.get("fields") or {}).get("type") == "precommit"
    )
    out = {
        "n": n,
        "heights": heights,
        "variant": "batched" if batch else "one_vote_per_tick",
        "sig_verify": "stubbed" if stub_verify else "real",
        "peer_degree": degree or (n - 1),
        "wall_ms_per_height": round(wall / heights * 1e3, 1),
        "gossip_ticks": ticks,
        "gossip_idle_ticks": idle,
        "gossip_votes_sent": votes,
        "gossip_batches_sent": batches,
        "votes_per_gossip_tick": round(votes / ticks, 2) if ticks else 0.0,
        **reg,
    }
    if straggler_s > 0:
        out["straggler_ms"] = straggler_s * 1e3
    if len(sketch):
        out["quorum_close_lag_p50_ms"] = round(sketch.quantile(0.5), 3)
        out["quorum_close_lag_p95_ms"] = round(sketch.quantile(0.95), 3)
    return out


def _bench_bls_committee(n_signers: int = 150) -> dict:
    """Batch-point BLS aggregation at committee scale: n_signers real
    BLS12-381 dual-signs over ONE batch hash, submitted to the
    BLSBatcher as one chunk — must verify as O(1) fn-lane dispatch
    rounds (one aggregate, 2 pairings) regardless of committee size."""
    import asyncio

    from tendermint_tpu.consensus.bls_batcher import BLSBatcher
    from tendermint_tpu.crypto import bls_signatures as bls
    from tendermint_tpu.l2node.mock import MockL2Node

    registry = bls.BLSKeyRegistry()
    tm_keys = []
    sigs = []
    batch_hash = b"committee-batch-point-hash-32b!!"
    for i in range(n_signers):
        priv = 50021 + i
        tm_pk = b"tmkey-%03d" % i + b"\x00" * 23
        registry.register(tm_pk, bls.pubkey_from_priv(priv))
        tm_keys.append(tm_pk)
        sigs.append(bls.signer_for(priv)(batch_hash))
    l2 = MockL2Node(
        bls_verifier=registry.verifier(),
        bls_batch_verifier=registry.batch_verifier(),
    )
    batcher = BLSBatcher(l2)
    before = _reg_snapshot()

    async def run():
        t0 = time.perf_counter()
        verdicts = await batcher.submit_many(
            list(zip(tm_keys, [batch_hash] * n_signers, sigs))
        )
        dt = time.perf_counter() - t0
        rounds = len(batcher.batch_sizes)
        batcher.stop()
        return verdicts, dt, rounds

    verdicts, dt, rounds = asyncio.run(run())
    assert all(v is True for v in verdicts), "committee BLS batch rejected"
    return {
        "metric": "bls_batch_point_committee",
        "value": round(dt * 1e3, 1),
        "unit": (
            f"ms for {n_signers} dual-signs over one batch hash "
            f"({rounds} fn-lane dispatch round(s))"
        ),
        "vs_baseline": rounds,  # O(1) rounds per batch point
        **_shape_stats(before),
    }


def _bench_round_dissemination(sizes) -> list:
    """Controlled per-round gossip cost (tests/chaos_harness
    round_dissemination_ticks): node A holds a full n-validator
    prevote round, real-p2p peer B holds none; count A's gossip send
    events until B's set is full, batched vs the one-vote-per-tick
    baseline. Deterministic — the emergent live-net number below is
    arrival-rate-bound, this one isolates the wire model."""
    import asyncio

    from tests.chaos_harness import round_dissemination_ticks

    out = []
    for n in sizes:
        batched = asyncio.run(round_dissemination_ticks(n, True))
        base = asyncio.run(round_dissemination_ticks(n, False))
        out.append({"batched": batched, "baseline": base})
    return out


def _bench_committee_scale(
    sizes=(4, 32, 100, 200),
    heights: int = 2,
    straggler_s: float = 0.05,
    live_max: int = 100,
) -> dict:
    """committee_scale family (PERF_ANALYSIS §16), three layers:

    1. round dissemination (headline): gossip ticks to ship one full
       n-validator vote round to a peer, batched vs one-vote-per-tick,
       at every requested size — vs_baseline is the tick ratio at the
       largest size >= 100 (the ISSUE's '>=10x fewer gossip ticks').
    2. live sweep: in-proc real-p2p committee nets (zipf powers,
       ring+chords degree 4) closing heights — wall-per-height,
       emergent votes-per-gossip-tick, quorum-close lag, and
       device-dispatch counts per size. Sizes above `live_max` skip
       the live net by default (a 200-node single-process net is
       minutes per height on one CPU; pass --sizes to force).
    3. BLS committee batch point: 150 dual-signs, one batch hash, one
       fn-lane round.

    The one-vote-per-tick live baseline runs at sizes <= 32."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    ledger_mark = _ledger_mark()
    try:
        dissemination = _bench_round_dissemination(sizes)
    except Exception as e:
        print(f"# dissemination metric failed: {e!r}", file=sys.stderr)
        dissemination = []
    sweep = []
    for n in (s for s in sizes if s <= live_max):
        hts = heights if n < 100 else max(1, heights - 1)
        try:
            sweep.append(_run_committee_net(n, heights=hts))
        except Exception as e:
            print(f"# committee size {n} failed: {e!r}", file=sys.stderr)
            sweep.append({"n": n, "error": repr(e)})
    baseline = []
    for n in (s for s in sizes if s <= 32):
        try:
            baseline.append(
                _run_committee_net(n, heights=heights, batch=False)
            )
        except Exception as e:
            print(f"# baseline size {n} failed: {e!r}", file=sys.stderr)
            baseline.append({"n": n, "error": repr(e)})
    straggler = None
    if straggler_s > 0:
        try:
            straggler = _run_committee_net(
                32, heights=heights, straggler_s=straggler_s
            )
        except Exception as e:
            print(f"# straggler scenario failed: {e!r}", file=sys.stderr)
            straggler = {"error": repr(e)}
    # headline: dissemination tick ratio at the largest complete size
    # (preferring committee scale >= 100)
    ratio = 0.0
    head_n = None
    complete = [
        d
        for d in dissemination
        if d["batched"].get("complete") and d["baseline"].get("complete")
    ]
    committee = [d for d in complete if d["batched"]["n"] >= 100]
    pool = committee or complete
    if pool:
        pick = max(pool, key=lambda d: d["batched"]["n"])
        head_n = pick["batched"]["n"]
        ratio = pick["baseline"]["gossip_ticks"] / max(
            1, pick["batched"]["gossip_ticks"]
        )
    extra = [
        {
            "metric": f"committee_round_ticks_n{d['batched']['n']}",
            "value": d["batched"]["gossip_ticks"],
            "unit": (
                f"gossip ticks to disseminate one "
                f"{d['batched']['n']}-validator round (baseline "
                f"{d['baseline']['gossip_ticks']}; "
                f"{d['batched']['wall_ms']} ms wall)"
            ),
            "vs_baseline": round(
                d["baseline"]["gossip_ticks"]
                / max(1, d["batched"]["gossip_ticks"]),
                1,
            ),
        }
        for d in dissemination
        if d["batched"].get("complete")
    ] + [
        {
            "metric": f"committee_wall_per_height_n{s['n']}",
            "value": s["wall_ms_per_height"],
            "unit": (
                f"ms/height ({s['variant']}, degree {s['peer_degree']}, "
                f"votes/tick {s['votes_per_gossip_tick']}, "
                f"quorum close p95 "
                f"{s.get('quorum_close_lag_p95_ms', 'n/a')} ms, "
                f"{s['device_dispatch_count']} device dispatches)"
            ),
        }
        for s in sweep
        if "error" not in s
    ]
    try:
        extra.append(_bench_bls_committee())
    except Exception as e:
        print(f"# bls committee metric failed: {e!r}", file=sys.stderr)
    return {
        "metric": "committee_round_gossip_tick_reduction",
        "value": round(ratio, 1),
        "unit": (
            f"x fewer gossip ticks per {head_n}-validator round vs the "
            f"one-vote-per-tick baseline (batched chunks of 64)"
        ),
        "vs_baseline": round(ratio, 1),
        "meta": _meta_block(),
        "device_cost": _device_cost_block(ledger_mark),
        "dissemination": dissemination,
        "sweep": sweep,
        "baseline": baseline,
        "straggler": straggler,
        "extra_metrics": extra,
    }


def _bench_verify_service(
    sizes=(4, 32, 100),
    max_procs: int = 8,
    service_max_batch: int = 2048,
) -> dict:
    """verify_service family (PERF_ANALYSIS §20): one standalone
    verify-service process (python -m tendermint_tpu verify-service)
    owns the device plane; the committee's node submission loops spread
    across real OS processes and drive REAL ed25519 + BLS rounds
    through it over UDS IPC — wall-per-height, cross-process
    requests-per-dispatch, fill, and IPC round-trip overhead at each
    size. No stubbed verify anywhere: this is the first honest
    committee-crypto measurement above 32 validators on this stack
    (the committee_scale family stubs there because one event loop
    cannot absorb the device work — the service process is the fix)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.verify_service_bench import run_family

    out = run_family(
        sizes=sizes,
        max_procs=max_procs,
        service_max_batch=service_max_batch,
    )
    out["meta"] = _meta_block()
    # the device rounds live in the SERVICE process's ledger (the
    # parent's default ledger never saw them): the headline size's
    # service-side summary IS this artifact's device_cost block, so
    # device_report/bench_trend read cross-process fill like any other
    # family's
    ok = [r for r in out["sizes"] if "error" not in r]
    head = next((r for r in ok if r["n"] == 32), ok[-1] if ok else None)
    if head is not None:
        out["device_cost"] = head["service_ledger"]
    out["extra_metrics"] = [
        {
            "metric": f"verify_service_wall_per_height_n{r['n']}",
            "value": r["wall_ms_per_height"],
            "unit": (
                f"ms/height ({r['n']} validators, {r['processes']} "
                f"node processes, reqs/dispatch "
                f"{r['requests_per_dispatch']}, rtt "
                f"{r['ipc_rtt_mean_ms']} ms, degrades {r['degrades']})"
            ),
        }
        for r in ok
    ] + [
        {
            "metric": f"verify_service_requests_per_dispatch_n{r['n']}",
            "value": r["requests_per_dispatch"],
            "unit": "submissions amortized per padded device round "
            "(cross-process coalescing when > 1)",
        }
        for r in ok
    ] + [
        {
            "metric": f"verify_service_ipc_rtt_ms_n{r['n']}",
            "value": r["ipc_rtt_mean_ms"],
            "unit": "mean submit->verdict IPC round trip, ms",
        }
        for r in ok
    ]
    return out


def _quorum_lag_metrics(att) -> list:
    """Quorum-close lag scalars for the bench family: first precommit of
    the round to the vote that closed 2/3 (the committee-spread slice of
    height latency the cluster tracer attributes per validator)."""
    q = (att or {}).get("quorum_close") or {}
    if not q.get("count"):
        return []
    return [
        {
            "metric": "quorum_close_lag_p50",
            "value": q["p50_ms"],
            "unit": "ms",
        },
        {
            "metric": "quorum_close_lag_p95",
            "value": q["p95_ms"],
            "unit": "ms",
        },
    ]


def _bench_commit_path() -> list:
    """Commit-path family (PERF_ANALYSIS §12): drive the same
    single-validator chain serially and pipelined ([commit_pipeline])
    over a REAL on-disk WAL, and report per-height finalize
    critical-path ms and fsyncs-per-height before/after.

    Serial `consensus_commit_seconds` covers save → end-height fsync →
    apply (all on the critical path); pipelined covers save enqueue +
    WAL barrier only — apply runs in the background finalization task,
    which is exactly the slice the node stops paying before it may
    enter H+1. vs_baseline is serial/pipelined (the speedup).

    Blocks carry ~256 KB of txs (4-5 parts): the serial WAL fsyncs once
    per internally-gossiped part, the group-commit path writes
    proposal + all parts and shares one fsync — the 2-tx test-net shape
    would hide exactly the cost production blocks pay."""
    import asyncio
    import tempfile

    heights = 8

    def run_variant(pipelined: bool) -> dict:
        from tendermint_tpu.abci.client import LocalClient
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.consensus.state_machine import (
            ConsensusConfig,
            ConsensusState,
        )
        from tendermint_tpu.consensus.wal import WAL, GroupCommitWAL
        from tendermint_tpu.consensus.commit_pipeline import CommitPipeline
        from tendermint_tpu.l2node.mock import MockL2Node
        from tendermint_tpu.libs.metrics import ConsensusMetrics, Registry
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.state import State
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store.block_store import (
            BlockStore,
            WriteBehindBlockStore,
        )
        from tendermint_tpu.store.kv import MemKV
        from tests.helpers import make_genesis, make_validators

        vs, pvs = make_validators(1)
        genesis = make_genesis(vs)
        metrics = ConsensusMetrics(
            Registry("bench_" + ("piped" if pipelined else "serial"))
        )
        import shutil

        wal_dir = tempfile.mkdtemp(prefix="bench_commit_wal_")
        wal_path = os.path.join(wal_dir, "wal")

        class _FatL2(MockL2Node):
            """Deterministic ~256 KB blocks (4-5 parts each)."""

            def request_block_data(self, height):
                from tendermint_tpu.l2node.l2node import BlockData

                bd = super().request_block_data(height)
                txs = [
                    b"fat-%d-%d=" % (height, i) + b"v" * 65200
                    for i in range(4)
                ]
                return BlockData(txs=txs, l2_block_meta=bd.l2_block_meta)

        async def run():
            app = KVStoreApplication()
            l2 = _FatL2()
            state_store = StateStore(MemKV())
            state = State.from_genesis(genesis)
            state_store.bootstrap(state)
            if pipelined:
                bs = WriteBehindBlockStore(MemKV(), metrics=metrics)
                wal = GroupCommitWAL(wal_path, metrics=metrics)
                pipe = CommitPipeline(metrics=metrics)
            else:
                bs = BlockStore(MemKV())
                wal = WAL(wal_path, metrics=metrics)
                pipe = None
            ex = BlockExecutor(state_store, bs, LocalClient(app), l2)
            cs = ConsensusState(
                ConsensusConfig.test_config(),
                state,
                ex,
                bs,
                l2,
                priv_validator=pvs[0],
                wal=wal,
                metrics=metrics,
                commit_pipeline=pipe,
            )
            await cs.start()
            t0 = time.perf_counter()
            await cs.wait_for_height(heights, timeout=120)
            wall = time.perf_counter() - t0
            await cs.stop()
            bs.stop()
            fsyncs = wal.fsync_count
            wal.close()
            commit_hist = metrics.commit_seconds._series.get(())
            return {
                "finalize_ms": round(
                    commit_hist.sum / commit_hist.total * 1e3, 3
                ),
                "fsyncs_per_height": round(fsyncs / heights, 2),
                "wall_ms_per_height": round(wall / heights * 1e3, 1),
            }

        try:
            return asyncio.run(run())
        finally:
            shutil.rmtree(wal_dir, ignore_errors=True)

    out = []
    serial = run_variant(False)
    piped = run_variant(True)
    out.append(
        {
            "metric": "commit_finalize_critical_path",
            "value": piped["finalize_ms"],
            "unit": (
                f"ms/height pipelined (serial "
                f"{serial['finalize_ms']} ms; save+apply overlapped "
                f"with height H+1)"
            ),
            "vs_baseline": round(
                serial["finalize_ms"] / piped["finalize_ms"], 2
            )
            if piped["finalize_ms"]
            else 0.0,
        }
    )
    out.append(
        {
            "metric": "wal_fsyncs_per_height",
            "value": piped["fsyncs_per_height"],
            "unit": (
                f"fsyncs/height pipelined (serial "
                f"{serial['fsyncs_per_height']}; group commit)"
            ),
            "vs_baseline": round(
                serial["fsyncs_per_height"]
                / max(piped["fsyncs_per_height"], 0.01),
                2,
            ),
        }
    )
    out.append(
        {
            "metric": "commit_height_wall",
            "value": piped["wall_ms_per_height"],
            "unit": (
                f"ms/height wall pipelined (serial "
                f"{serial['wall_ms_per_height']}; incl. "
                f"timeout_commit floor)"
            ),
            "vs_baseline": round(
                serial["wall_ms_per_height"]
                / max(piped["wall_ms_per_height"], 0.01),
                2,
            ),
        }
    )
    return out


def _bench_height_attribution(failed: list):
    """Per-height latency attribution: drive an in-proc 4-validator net
    for a few heights with the flight recorder on and report p50/p95 per
    step (tendermint_tpu/obs). A failure lands in `failed` like every
    extra metric's."""
    try:
        import asyncio

        from tendermint_tpu import obs
        from tests.helpers import make_genesis, make_validators
        from tests.test_consensus import make_node, wire_net

        tracer = obs.default_tracer()
        was_enabled = tracer.enabled
        tracer.enabled = True
        tracer.clear()

        async def run():
            vs, pvs = make_validators(4)
            genesis = make_genesis(vs)
            nodes = [make_node(vs, pv, genesis) for pv in pvs]
            css = [n[0] for n in nodes]
            wire_net(css)
            for cs in css:
                await cs.start()
            await asyncio.gather(
                *(cs.wait_for_height(3, timeout=60) for cs in css)
            )
            for cs in css:
                await cs.stop()

        try:
            asyncio.run(run())
            recs = [r.to_json() for r in tracer.records()]
            att = obs.attribution(recs)
            # per-height quorum-close lag (height_vote_set.py events):
            # the committee-spread baseline BENCH artifacts track —
            # through the SAME sketch the pacing controllers learn from
            # (obs/quantile.py), so the bench percentile and the
            # controller's view of the tail can never disagree
            from tendermint_tpu.obs import StreamingQuantile

            sketch = StreamingQuantile(window=4096)
            sketch.extend(
                float((r.get("fields") or {}).get("lag_ms", 0.0))
                for r in recs
                if r.get("name") == "quorum.close"
                and (r.get("fields") or {}).get("type") == "precommit"
            )
            if len(sketch):
                att["quorum_close"] = {
                    "count": sketch.count,
                    "p50_ms": round(sketch.quantile(0.5), 3),
                    "p95_ms": round(sketch.quantile(0.95), 3),
                }
            # the conservation audit over the same capture: every
            # height's wall decomposed into exhaustive named buckets,
            # residue = dark_time (tools/bench_trend.py validates the
            # sum and gates on the dark fraction)
            att["wall_conservation"] = obs.wall_conservation(recs)
            return att
        finally:
            tracer.enabled = was_enabled
    except Exception as e:
        _phase_failed(failed, "latency attribution", e)
        return None


def _extra_metrics(
    cached_fn, tables, valid, idx, rb, sb, kb, s_ok, failed: list
) -> list:
    """Secondary measurements; a block that raises is recorded in
    `failed` (the run then exits non-zero) and the later blocks still
    run."""
    out = []

    # --- 10k-validator commit latency (BASELINE config 2: <5 ms target) --
    try:
        import jax.numpy as jnp

        B10 = 10240
        reps = (B10 + BATCH - 1) // BATCH

        def tile10(x):
            return jnp.concatenate([x] * reps, axis=0)[:B10]

        before = _reg_snapshot()
        args10 = tuple(tile10(a) for a in (idx, rb, sb, kb, s_ok))
        lat = _time_best(
            cached_fn, tables, tile10(valid), *args10
        )
        _record_direct("bench_big", B10, count=1 + ITERS)
        out.append(
            {
                "metric": "ed25519_commit10k_latency",
                "value": round(lat * 1e3, 1),
                "unit": "ms p50 (target 5)",
                "vs_baseline": round(5.0 / (lat * 1e3), 4),
                **_shape_stats(before),
            }
        )
    except Exception as e:
        _phase_failed(failed, "10k latency metric", e)

    # --- BLS 1k-member aggregate verify (BASELINE config 3) -------------
    try:
        from tendermint_tpu.crypto import bls_signatures as bls
        from tendermint_tpu.crypto import bls12_381 as c

        n = 1000
        msg = b"bench-batch-hash"
        privs = list(range(100001, 100001 + n))
        pubs = [
            bls.new_trusted_public_key(bls._g2_mul_point(c.G2_GEN, p))
            for p in privs
        ]
        h = bls.hash_to_g1(msg)
        sigs = [bls._g1_mul_point(h, p) for p in privs]
        agg = bls.aggregate_signatures(sigs)
        # warm once (first call loads the native .so and its pairing
        # tables — measured ~2x the steady-state cost), then best-of-3
        # like every other latency metric
        assert bls.verify_aggregated_same_message(agg, msg, pubs)
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            assert bls.verify_aggregated_same_message(agg, msg, pubs)
            dt = min(dt, time.perf_counter() - t0)
        # reference shape: Go kilic, 2 pairings + n-1 G2 adds
        # (blssignatures/bls_signatures.go:129-171) — ~2.5 ms total on a
        # server core (kilic pairing ~1.1 ms); vs_baseline is ref/ours
        out.append(
            {
                "metric": "bls_aggregate_verify_1k",
                "value": round(dt * 1e3, 1),
                "unit": "ms",
                "vs_baseline": round(2.5 / (dt * 1e3), 3),
            }
        )
    except Exception as e:
        _phase_failed(failed, "BLS config-3 metric", e)

    # --- secp256k1 native batch verify (the secp rows of config 4) ------
    try:
        from tendermint_tpu.crypto import secp256k1 as secp
        from tendermint_tpu.crypto import secp_native

        ns = 256
        privs = [secp.PrivKey.from_secret(b"bench%d" % i) for i in range(ns)]
        msgs = [b"bench-msg-%d" % i for i in range(ns)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        pubs = [p.public_key().data for p in privs]
        assert all(secp_native.verify_msgs_batch(pubs, msgs, sigs))  # warm
        t0 = time.perf_counter()
        assert all(secp_native.verify_msgs_batch(pubs, msgs, sigs))
        rate = ns / (time.perf_counter() - t0)
        # reference: btcec ~20k verifies/s/core; serial-python ~130/s
        out.append(
            {
                "metric": "secp256k1_verify_throughput",
                "value": round(rate, 1),
                "unit": "sigs/s",
                "vs_baseline": round(rate / 137.0, 1),  # vs pure-python
            }
        )
    except Exception as e:
        _phase_failed(failed, "secp metric", e)

    # --- SHA-256 device kernel (merkle leaf path) -----------------------
    try:
        import jax
        import jax.numpy as jnp
        import numpy as _np

        from tendermint_tpu.ops import sha256 as dsha

        nb = 2048
        msgs = [b"leaf-%d" % i + b"x" * 48 for i in range(nb)]
        buf, counts = dsha.pad_messages(msgs)
        fn = dsha.sha256_batch_jit
        _ = _np.asarray(fn(jnp.asarray(buf), jnp.asarray(counts)))
        t0 = time.perf_counter()
        _ = _np.asarray(fn(jnp.asarray(buf), jnp.asarray(counts)))
        rate = nb / (time.perf_counter() - t0)
        out.append(
            {
                "metric": "sha256_kernel_throughput",
                "value": round(rate, 1),
                "unit": "hashes/s",
                "vs_baseline": round(rate / 1_000_000.0, 4),  # vs hashlib/core
            }
        )
    except Exception as e:
        _phase_failed(failed, "sha256 metric", e)

    # --- blocksync bulk replay (BASELINE config 4, tools/bench_replay) ---
    try:
        from tests.helpers import (
            CHAIN_ID,
            make_validators,
            sign_commit,
        )
        from tendermint_tpu.crypto.batch_verifier import BatchVerifier
        from tendermint_tpu.types.block_id import BlockID
        from tendermint_tpu.types.part_set import PartSetHeader

        n_blocks, n_vals = 48, 128
        vs_r, pvs_r = make_validators(n_vals)
        entries = []
        for h in range(1, n_blocks + 1):
            hb = h.to_bytes(4, "big") * 8
            bid = BlockID(hb, PartSetHeader(1, hb))
            entries.append((bid, h, sign_commit(vs_r, pvs_r, h, 0, bid)))
        before = _reg_snapshot()
        verifier = BatchVerifier()
        verifier.warm([v.pub_key.data for v in vs_r.validators], bulk=True)
        assert all(
            vs_r.verify_commits_light(CHAIN_ID, entries, verifier=verifier)
        )  # warm the bucket
        t0 = time.perf_counter()
        assert all(
            vs_r.verify_commits_light(CHAIN_ID, entries, verifier=verifier)
        )
        dt = time.perf_counter() - t0
        # commits/s, not sigs/s (ROADMAP item 3d): now that the QC
        # plane verifies a commit as ONE aggregate, sigs/s stopped
        # being the unit replay throughput is bought in — the N-sig
        # row here prices the LEGACY path in the same commits/s unit
        # the qc_catchup family's blocksync_commits_per_s reports, so
        # the two are directly comparable. vs_baseline keeps the
        # serial-CPU reference, also converted to commits/s.
        rate = n_blocks / dt
        out.append(
            {
                "metric": "blocksync_replay_commits_per_s",
                "value": round(rate, 1),
                "unit": f"commits/s ({n_vals}-validator N-sig path, "
                "windowed multi-commit)",
                "vs_baseline": round(
                    rate / (BASELINE_SERIAL_SIGS_PER_S / n_vals), 3
                ),
                **_shape_stats(before),
            }
        )
    except Exception as e:
        _phase_failed(failed, "blocksync replay metric", e)

    # --- light-client bisection (BASELINE config 5) ----------------------
    try:
        before = _reg_snapshot()
        rate, n_sigs, dt = _bench_light_bisection()
        out.append(
            {
                "metric": "light_bisection_throughput",
                "value": round(rate, 1),
                "unit": f"sigs/s ({n_sigs} sigs, {dt*1e3:.0f} ms skip-verify)",
                "vs_baseline": round(rate / BASELINE_SERIAL_SIGS_PER_S, 3),
                **_shape_stats(before),
            }
        )
    except Exception as e:
        _phase_failed(failed, "light bisection metric", e)

    # --- light bisection at 1/10 of the BASELINE config-5 shape ----------
    try:
        before = _reg_snapshot()
        rate, reqs, dt = _bench_light_bisection_1k()
        out.append(
            {
                "metric": "light_bisection_1k",
                "value": round(rate, 1),
                "unit": (
                    f"sigs/s (1024h x 1024v rotating chain, {reqs} light "
                    f"blocks fetched, {dt:.1f} s)"
                ),
                "vs_baseline": round(rate / BASELINE_SERIAL_SIGS_PER_S, 3),
                **_shape_stats(before),
            }
        )
    except Exception as e:
        _phase_failed(failed, "light bisection 1k metric", e)

    # --- table-build cost per key: cold bulk warm vs cache hit -----------
    try:
        # per-metric shape stats are computed INSIDE the helper at the
        # cold/hit boundary (a wrapper snapshot here would stamp both
        # metrics with the same cumulative delta)
        for m in _bench_table_build():
            out.append(m)
    except Exception as e:
        _phase_failed(failed, "table build metric", e)

    # --- sustained throughput under validator-set churn ------------------
    try:
        before = _reg_snapshot()
        rate, dt = _bench_churn_throughput()
        out.append(
            {
                "metric": "ed25519_churn_throughput",
                "value": round(rate, 1),
                "unit": (
                    "sigs/s (20 heights x 512 sigs, 25% key churn at "
                    "height 11, rotation warm+build inside the clock, "
                    "XLA programs pre-loaded)"
                ),
                "vs_baseline": round(rate / BASELINE_SERIAL_SIGS_PER_S, 3),
                **_shape_stats(before),
            }
        )
    except Exception as e:
        _phase_failed(failed, "churn metric", e)

    # --- vote-path latency through the micro-batcher ---------------------
    try:
        # stats computed inside, per concurrency level
        for m in _bench_vote_latency():
            out.append(m)
    except Exception as e:
        _phase_failed(failed, "vote latency metric", e)

    # --- commit path: serial vs pipelined over a real on-disk WAL --------
    try:
        out.extend(_bench_commit_path())
    except Exception as e:
        _phase_failed(failed, "commit-path family", e)

    return out


def _bench_light_bisection():
    """Distant-header skip-verify over a generated chain: the bisection
    shape of BASELINE config 5 (reference light/client_benchmark_test.go
    runs the same in-proc mock-provider harness, no stored numbers)."""
    import asyncio

    from tests.test_light import make_chain, make_client

    chain = make_chain(32, n_vals=128)

    async def run():
        c = make_client(chain)
        lb = await c.verify_light_block_at_height(32)
        assert lb.height == 32
        return len(c.primary.requests)

    # warm (compile the commit-verify bucket), then measure a fresh client
    asyncio.run(run())
    t0 = time.perf_counter()
    requests = asyncio.run(run())
    dt = time.perf_counter() - t0
    # each verified light block costs one 128-signature commit verify
    n_sigs = requests * 128
    return n_sigs / dt, n_sigs, dt


def _bench_table_build() -> list:
    """Per-key cost of the fixed-window table build, cold vs cache hit
    (VERDICT r4 weak #3: the generic tier matters exactly when tables
    must be (re)built, and nothing priced that). Cold is a bulk warm of
    128 fresh keys through BatchVerifier (including the one-time compile
    only if this machine never built the bucket — the persistent cache
    usually absorbs it); hit is the same warm again (a lock + dict pass,
    no device work). vs_baseline compares against ONE serial-CPU verify
    (~65 us): the factor says how many reference verifies one build
    costs, i.e. the reuse count where the table pays for itself."""
    from tendermint_tpu.crypto import ed25519 as hosted
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier

    pubs = [
        hosted.PrivKey.from_secret(b"warmkey%d" % i).public_key().data
        for i in range(128)
    ]
    v = BatchVerifier(min_device_batch=0, bigtable_min=8)
    before_cold = _reg_snapshot()
    t0 = time.perf_counter()
    v.warm(pubs, bulk=True)
    cold_ms = (time.perf_counter() - t0) * 1e3 / 128
    cold_stats = _shape_stats(before_cold)
    before_hit = _reg_snapshot()
    t0 = time.perf_counter()
    v.warm(pubs, bulk=True)
    hit_ms = (time.perf_counter() - t0) * 1e3 / 128
    hit_stats = _shape_stats(before_hit)
    serial_ms = 1e3 / BASELINE_SERIAL_SIGS_PER_S
    return [
        {
            "metric": "ed25519_table_build_cold_per_key",
            "value": round(cold_ms, 3),
            "unit": "ms/key (128-key bulk warm)",
            "vs_baseline": round(serial_ms / cold_ms, 5) if cold_ms else 0.0,
            **cold_stats,
        },
        {
            "metric": "ed25519_table_build_hit_per_key",
            "value": round(hit_ms, 4),
            "unit": "ms/key (re-warm of cached keys)",
            "vs_baseline": round(serial_ms / hit_ms, 2) if hit_ms else 0.0,
            **hit_stats,
        },
    ]


def _bench_churn_throughput():
    """Sustained verification across a validator-set rotation: 20
    heights x 512 sigs over 128 validators, 25% of the keys replaced at
    height 11 (the scenario where PERF_ANALYSIS §4's 'churn is bounded'
    claim actually bites — the ROTATION's table builds and generic-tier
    work land INSIDE the measured window). Host-side signing and the
    per-process XLA program loads happen outside the clock (see the
    pre-clock block below); the 20 height verifies and the height-11
    rebuild are inside."""
    from tendermint_tpu.crypto import ed25519 as hosted
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem

    nv, heights, per_h = 128, 20, 512
    keys = [hosted.PrivKey.from_secret(b"churn0-%d" % i) for i in range(nv)]
    eras = {1: list(keys)}
    rotated = list(keys)
    for i in range(nv // 4):  # 25% churn
        rotated[i] = hosted.PrivKey.from_secret(b"churn1-%d" % i)
    eras[11] = rotated

    batches = {}
    active = eras[1]
    pubs = {id(k): k.public_key().data for k in set(eras[1] + eras[11])}
    for h in range(1, heights + 1):
        active = eras.get(h, active)
        items = []
        for i in range(per_h):
            k = active[i % nv]
            msg = b"churn-vote-%d-%d" % (h, i)
            items.append(SigItem(pubs[id(k)], msg, k.sign(msg)))
        batches[h] = items

    v = BatchVerifier(min_device_batch=0, bigtable_min=8)
    # pre-clock: load every XLA program the loop dispatches — the 512-row
    # verify, the 128-key build bucket, AND the rotation-size build
    # bucket (32 new keys pad to a smaller bucket = a different program).
    # Program compile/load is a per-process cost and a node pays it once
    # at assembly on the warm thread, not per rotation — the ROTATION's
    # table builds and generic-tier work stay inside the clock.
    v.warm([pubs[id(k)] for k in eras[1]], bulk=True)
    throwaway = [
        hosted.PrivKey.from_secret(b"preload-%d" % i).public_key().data
        for i in range(nv // 4)
    ]
    v.warm(throwaway, bulk=True)
    assert np.asarray(v.verify(batches[1])).all()
    active = eras[1]
    t0 = time.perf_counter()
    for h in range(1, heights + 1):
        if h in eras:
            active = eras[h]
            v.warm([pubs[id(k)] for k in active], bulk=True)
        out = np.asarray(v.verify(batches[h]))
        assert out.all(), f"churn bench verify failed at height {h}"
    dt = time.perf_counter() - t0
    return heights * per_h / dt, dt


def _make_lazy_light_chain(n_heights, n_vals, rotate_every):
    """A light-block chain generated ON DEMAND — the BASELINE config-5
    shape (reference light/client.go:706-775 bisection over distant
    headers) without materializing n_heights x n_vals host signatures:
    bisection touches O(log H) heights, so only those are signed.

    The validator set rotates 50% at every `rotate_every` boundary in
    two alternating halves, so sets two regions apart share NO keys:
    a direct trust-period jump past two boundaries fails the 1/3
    overlap rule and the client must bisect into every region — the
    log-bisection x 2-commit shape the bench is after."""
    from tests.test_light import BLOCK_NS, CHAIN_ID as LCID, T0
    from tendermint_tpu.light import LightBlock
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.part_set import PartSetHeader
    from tendermint_tpu.types.priv_validator import MockPV
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType
    from tendermint_tpu.types.vote_set import VoteSet

    pv_cache: dict = {}
    set_cache: dict = {}
    block_cache: dict = {}

    def pv_for(i: int, generation: int):
        key = (i, generation)
        if key not in pv_cache:
            pv_cache[key] = MockPV.from_secret(b"lazy-%d-%d" % key)
        return pv_cache[key]

    def vals(region: int):
        if region not in set_cache:
            pvs = []
            for i in range(n_vals):
                group = (2 * i) // n_vals  # two alternating halves
                generation = sum(
                    1 for s in range(1, region + 1) if s % 2 == group % 2
                )
                pvs.append(pv_for(i, generation))
            vs = ValidatorSet(
                [Validator(pv.get_pub_key(), 10) for pv in pvs]
            )
            by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
            ordered = [by_addr[v.address] for v in vs.validators]
            set_cache[region] = (vs, ordered)
        return set_cache[region]

    def block(h: int):
        if h in block_cache:
            return block_cache[h]
        region = (h - 1) // rotate_every
        region_next = min(h, n_heights - 1) // rotate_every
        vs, ordered = vals(region)
        vs_next, _ = vals(region_next)
        header = Header(
            chain_id=LCID,
            height=h,
            time_ns=T0 + h * BLOCK_NS,
            last_block_id=BlockID(),
            validators_hash=vs.hash(),
            next_validators_hash=vs_next.hash(),
            app_hash=b"lazy-app-%d" % h,
            proposer_address=vs.validators[0].address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
        votes = VoteSet(LCID, h, 0, VoteType.PRECOMMIT, vs)
        for i, pv in enumerate(ordered):
            v = Vote(
                type=VoteType.PRECOMMIT,
                height=h,
                round=0,
                block_id=bid,
                timestamp_ns=header.time_ns,
                validator_address=pv.get_pub_key().address(),
                validator_index=i,
            )
            pv.sign_vote(LCID, v)
            votes.add_vote(v, verified=True)
        lb = LightBlock(header, votes.make_commit(), vs)
        block_cache[h] = lb
        return lb

    return block


class _LazyProvider:
    def __init__(self, block_fn, latest: int, name="primary"):
        self.block_fn = block_fn
        self.latest = latest
        self.name = name
        self.requests: list = []
        # wall time spent GENERATING blocks (host-side signing of
        # n_vals sigs per fetched height — bench-harness data setup, not
        # client work; profiled r5 at ~200 s of the 1k run). The bench
        # subtracts this from its clock so the metric prices the
        # client's verification, as a real RPC provider would.
        self.gen_seconds = 0.0

    async def light_block(self, height: int):
        if height == 0:
            height = self.latest
        self.requests.append(height)
        t0 = time.perf_counter()
        try:
            return self.block_fn(height)
        finally:
            self.gen_seconds += time.perf_counter() - t0

    def id(self):
        return self.name


def _bench_light_bisection_1k(
    n_heights: int = 1024, n_vals: int = 1024, rotate_every: int = 128
):
    """Bisection at 1/10 the BASELINE config-5 scale (VERDICT r4 weak
    #4: the 32x128 metric priced two dispatch floors, not amortization).
    Forces the small-table tier (bigtable_min=inf) so the measurement is
    the bisection's batched commit verifies, not 8 GiB of fixed-window
    table builds. Returns (sigs/s, light-blocks fetched, seconds)."""
    import asyncio

    from tests.test_light import CHAIN_ID as LCID, PERIOD, T0, BLOCK_NS
    from tendermint_tpu.crypto import batch_verifier as bv
    from tendermint_tpu.light import LightClient, TrustOptions
    from tendermint_tpu.light.store import LightStore
    from tendermint_tpu.store.kv import MemKV

    block_fn = _make_lazy_light_chain(n_heights, n_vals, rotate_every)

    def make_client():
        primary = _LazyProvider(block_fn, n_heights)
        witness = _LazyProvider(block_fn, n_heights, name="witness-0")
        return (
            LightClient(
                LCID,
                TrustOptions(PERIOD, 1, block_fn(1).header.hash()),
                primary,
                [witness],
                LightStore(MemKV()),
                now_ns=lambda: T0 + (n_heights + 10) * BLOCK_NS,
            ),
            primary,
            witness,
        )

    saved = bv._default
    bv._default = bv.BatchVerifier(min_device_batch=0, bigtable_min=1 << 30)
    try:
        # warm pass (same methodology as the 32-height metric above):
        # materializes the fetched blocks (host signing, ~200 s — a real
        # provider serves stored blocks) and loads the ~44 op-shape XLA
        # programs the run dispatches. The clocked pass is a FRESH
        # client + store bisecting the same chain, so it prices fetches
        # + commit verification.
        warm_client, _, _ = make_client()
        assert asyncio.run(
            warm_client.verify_light_block_at_height(n_heights)
        ).height == n_heights
        client, primary, witness = make_client()
        t0 = time.perf_counter()
        lb = asyncio.run(client.verify_light_block_at_height(n_heights))
        dt = time.perf_counter() - t0
    finally:
        bv._default = saved
    assert lb.height == n_heights
    # residual lazy-generation wall (cache misses on heights the warm
    # pass didn't touch) is still excluded from the clock
    dt = max(dt - primary.gen_seconds - witness.gen_seconds, 1e-9)
    fetches = len(primary.requests)
    n_sigs = fetches * n_vals
    return n_sigs / dt, fetches, dt


def _bench_vote_latency():
    """p50/p99 single-vote latency through the adaptive VoteBatcher at
    1/64/512 concurrent submissions (SURVEY §7.3 hard part 3: consensus
    wants latency, the device wants batches). vs_baseline is the serial
    single-core drain model: c votes x ~65 us each."""
    import asyncio

    from tendermint_tpu.consensus.vote_batcher import VoteBatcher
    from tendermint_tpu.crypto import ed25519 as hosted

    pv = hosted.PrivKey.generate()
    pub = pv.public_key().data
    votes = [(b"vote-%d" % i, pv.sign(b"vote-%d" % i)) for i in range(512)]
    batcher = VoteBatcher()
    lat: dict[int, list] = {}
    stats: dict[int, dict] = {}  # per-concurrency shape/dispatch deltas

    async def one(i):
        t0 = time.perf_counter()
        ok = await batcher.submit(pub, votes[i][0], votes[i][1])
        assert ok
        return time.perf_counter() - t0

    async def run():
        for c in (1, 64, 512):
            before = _reg_snapshot()
            # throwaway round first: each concurrency lands in a new
            # batch bucket whose one-time compile must not pollute p99
            await asyncio.gather(*(one(i) for i in range(c)))
            lat[c] = list(
                await asyncio.gather(*(one(i) for i in range(c)))
            )
            stats[c] = _shape_stats(before)
        batcher.stop()

    asyncio.run(run())
    serial_us = 1e6 / BASELINE_SERIAL_SIGS_PER_S  # ~65 us/verify

    def pct(xs, q):
        return sorted(xs)[min(len(xs) - 1, int(q * len(xs)))]

    out = []
    for c, q, name in ((1, 0.5, "p50"), (64, 0.99, "p99"), (512, 0.99, "p99")):
        v = pct(lat[c], q) * 1e3
        baseline_ms = c * serial_us / 1e3
        out.append(
            {
                "metric": f"vote_latency_{name}_c{c}",
                "value": round(v, 1),
                "unit": "ms",
                "vs_baseline": round(baseline_ms / v, 3) if v else 0.0,
                **stats[c],
            }
        )
    return out


if __name__ == "__main__":
    main()
