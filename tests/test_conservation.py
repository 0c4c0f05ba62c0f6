"""Wall-clock conservation + cross-process causal tracing (PR 15).

Covers: the exhaustive per-height bucket decomposition
(obs.report.wall_conservation — buckets sum to measured wall by
construction, residue = dark_time), the dark_time health detector and
its tracer pull seam, the UDS trace-context propagation (client stamps
span context on each verify submission; the service records
queue/device sub-spans under it into its own ring with a dump
endpoint), the cluster merge of service dumps alongside validator dumps
(wall-anchor fallback for nodes outside the NTP peer graph), and the
4-validator acceptance: attribution buckets cover >= 95% of measured
wall per height on a live net with tracing on."""

import asyncio
import json
from types import SimpleNamespace

import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.obs.health import (
    CRITICAL,
    OK,
    BurnRateSLO,
    DarkTimeDetector,
    HealthMonitor,
)
from tendermint_tpu.obs.report import (
    CONSERVATION_BUCKETS,
    check_conservation,
    conservation_table,
    wall_conservation,
)

from .helpers import make_genesis, make_validators
from .test_consensus import make_node, wire_net

pytestmark = pytest.mark.conservation


def _span(name, t0, dur, height=0, round_=0, **fields):
    return {
        "name": name,
        "t0": t0,
        "dur": dur,
        "height": height,
        "round": round_,
        "kind": "span",
        "fields": fields,
    }


def _height_records(h, base):
    """One height's step spans tiling [base, base+1.0] exactly."""
    return [
        _span("cs.new_height", base + 0.0, 0.3, height=h),
        _span("cs.propose", base + 0.3, 0.3, height=h),
        _span("cs.prevote", base + 0.6, 0.2, height=h),
        _span("cs.commit", base + 0.8, 0.2, height=h),
    ]


# --- the conservation invariant --------------------------------------------


def test_conservation_buckets_sum_to_wall():
    recs = _height_records(5, 0.0) + [
        # WAL fsync inside cs.commit: carved out of compute
        _span("wal.fsync", 0.85, 0.05),
        # verify round trip inside cs.prevote, with the device slice
        # nested inside it — the sweep must NOT double-count the
        # overlap (device claims its segment, ipc keeps the rest)
        _span("verify.ipc", 0.62, 0.1, height=5),
        _span("scheduler.device_round", 0.65, 0.05),
    ]
    cons = wall_conservation(recs)
    row = cons["heights"][5]
    assert row["wall_ms"] == pytest.approx(1000.0)
    assert row["verify_device_ms"] == pytest.approx(50.0)
    assert row["verify_ipc_ms"] == pytest.approx(50.0)  # 100 - 50 overlap
    assert row["wal_fsync_ms"] == pytest.approx(50.0)
    assert row["compute_ms"] == pytest.approx(150.0)  # 200 - 50 fsync
    assert row["gossip_ms"] == pytest.approx(400.0)  # propose + prevote...
    assert row["dark_time_ms"] == pytest.approx(0.0)
    covered = sum(row[f"{b}_ms"] for b in CONSERVATION_BUCKETS)
    assert covered == pytest.approx(row["wall_ms"], abs=1e-6)
    assert cons["aggregate"]["conserved"] is True
    assert cons["aggregate"]["dark_fraction"] == 0.0
    assert check_conservation(cons) == []


def test_conservation_dark_residue_named():
    # a 200 ms hole between prevote and commit that NO span owns —
    # exactly the latency class the audit exists to surface
    recs = [
        _span("cs.new_height", 0.0, 0.3, height=9),
        _span("cs.propose", 0.3, 0.3, height=9),
        _span("cs.prevote", 0.6, 0.2, height=9),
        _span("cs.commit", 1.0, 0.2, height=9),  # gap [0.8, 1.0]
    ]
    cons = wall_conservation(recs)
    row = cons["heights"][9]
    assert row["wall_ms"] == pytest.approx(1200.0)
    assert row["dark_time_ms"] == pytest.approx(200.0)
    assert row["dark_fraction"] == pytest.approx(200.0 / 1200.0, abs=1e-3)
    assert cons["aggregate"]["dark_fraction_max"] == row["dark_fraction"]


def test_conservation_carves_clip_to_window():
    # a bulk blocksync device round half outside the height window only
    # bills the overlapping slice; a fully-disjoint one bills nothing
    recs = _height_records(3, 10.0) + [
        _span("scheduler.device_round", 10.9, 0.4),  # 0.1 inside
        _span("scheduler.device_round", 12.0, 1.0),  # disjoint
    ]
    cons = wall_conservation(recs)
    row = cons["heights"][3]
    assert row["verify_device_ms"] == pytest.approx(100.0)
    covered = sum(row[f"{b}_ms"] for b in CONSERVATION_BUCKETS)
    assert covered == pytest.approx(row["wall_ms"], abs=1e-6)


def test_check_conservation_rejects_bad_sum():
    cons = wall_conservation(_height_records(2, 0.0))
    assert check_conservation(cons) == []
    cons["heights"][2]["gossip_ms"] += 300.0  # bucket no longer sums
    errs = check_conservation(cons)
    assert errs and "height 2" in errs[0]
    assert check_conservation({"nope": 1}) == ["wall_conservation.aggregate missing"]
    # empty capture (no step spans) is valid — nothing to conserve
    assert check_conservation(wall_conservation([])) == []


def test_conservation_table_renders():
    text = conservation_table(wall_conservation(_height_records(4, 0.0)))
    assert "dark" in text and "wall_ms" in text and "4" in text
    assert "(no step spans" in conservation_table(wall_conservation([]))


# --- the dark_time detector -------------------------------------------------


def test_dark_time_detector_floor_and_burn():
    det = DarkTimeDetector(
        BurnRateSLO("dark_time", objective=0.9, min_events=4), floor=0.05
    )
    for i in range(8):
        det.observe_height(float(i), 0.01)  # conserved heights: ok
    assert det.verdict(8.0) == OK
    for i in range(8, 16):
        det.observe_height(float(i), 0.5)  # half the wall is unowned
    # 8 bad of 16 against a 10% budget = 5x burn: warn, not yet page
    assert det.verdict(16.0) == pytest.approx(1)  # WARN
    for i in range(16, 48):
        det.observe_height(float(i), 0.5)
    # sustained: 40/48 bad = 8.3x burn on both windows -> critical
    assert det.verdict(48.0) == CRITICAL
    assert det.last_bad == 0.5
    assert det.last_threshold == 0.05


def test_monitor_conservation_pull_seam():
    tnow = [100.0]
    mon = HealthMonitor(clock=lambda: tnow[0], dark_time_floor=0.05)
    tracer = obs.Tracer(enabled=True)
    base = tracer.epoch
    # heights 1-2 complete and conserved; height 2 carries a dark gap;
    # height 3 is the tip (in progress — must not be judged)
    for r in (
        _height_records(1, 0.0)
        + [
            _span("cs.new_height", 1.0, 0.2, height=2),
            _span("cs.commit", 1.5, 0.5, height=2),  # gap [1.2, 1.5]
        ]
        + [_span("cs.new_height", 2.0, 0.1, height=3)]
    ):
        tracer.add_span(
            r["name"], base + r["t0"], r["dur"], height=r["height"]
        )
    mon.bind_tracer(tracer)
    mon.sample()
    slo = mon.dark_time.slo
    assert slo._total == 2  # heights 1 and 2, never the tip
    assert mon.dark_time.last_bad == pytest.approx(0.3, abs=1e-3)
    mon.sample()
    assert slo._total == 2  # already-judged heights are not re-fed
    # a disabled tracer is a no-op seam
    mon2 = HealthMonitor(clock=lambda: tnow[0])
    mon2.bind_tracer(obs.Tracer(enabled=False))
    mon2.sample()
    assert mon2.dark_time.slo._total == 0


# --- wire trace-context codec ----------------------------------------------


def test_wire_trace_ctx_codec_and_legacy_frames():
    from tendermint_tpu.crypto.batch_verifier import SigItem
    from tendermint_tpu.parallel.verify_service import (
        _HDR,
        _Cursor,
        decode_submit,
        decode_submit_fn,
        decode_trace_ctx,
        encode_submit,
        encode_submit_fn,
    )

    items = [SigItem(b"\x01" * 32, b"m" * 32, b"\x02" * 64, "ed25519")]
    # traced frame round-trips the ctx
    frame = encode_submit(7, items, "consensus", ctx=(42, 1, "nodeA"))
    cur = _Cursor(frame)
    _typ, req_id = _HDR.unpack(cur.take(_HDR.size))
    out_items, klass, _uniform = decode_submit(cur)
    ctx = decode_trace_ctx(cur, req_id)
    assert klass == "consensus" and len(out_items) == 1
    assert ctx == (42, 1, "nodeA", 7)
    # legacy frame (no trailer): ctx is None, decode unchanged
    cur = _Cursor(encode_submit(8, items, "blocksync"))
    _HDR.unpack(cur.take(_HDR.size))
    _, klass, _uniform = decode_submit(cur)
    assert klass == "blocksync"
    assert decode_trace_ctx(cur, 8) is None
    # fn lane carries the same trailer
    cur = _Cursor(
        encode_submit_fn(
            9, "bls_agg", [(b"a" * 32, b"b" * 32)], "consensus",
            ctx=(5, 0, "w1"),
        )
    )
    _HDR.unpack(cur.take(_HDR.size))
    engine, fn_items, klass = decode_submit_fn(cur)
    assert engine == "bls_agg" and len(fn_items) == 1
    assert decode_trace_ctx(cur, 9) == (5, 0, "w1", 9)


# --- cross-process propagation e2e ------------------------------------------


class _AllTrueVerifier:
    def verify(self, items):
        return np.ones(len(items), dtype=bool)


def test_service_records_client_span_context_e2e(tmp_path):
    """The acceptance path minus the consensus net: a node-side client
    stamps span context on a UDS submission, the SERVICE process's ring
    records queue/device sub-spans under it, its dump endpoint serves
    them, and the cluster merge lands them in the per-height timeline
    next to the client's own records — with the service rebased through
    the raw-wall-anchor fallback (it has no NTP peer table)."""
    import urllib.request

    from tendermint_tpu.crypto.batch_verifier import SigItem
    from tendermint_tpu.parallel.verify_service import (
        RemoteVerifyScheduler,
        ServiceThread,
    )

    svc_tracer = obs.Tracer(enabled=True)
    cli_tracer = obs.Tracer(enabled=True)
    path = str(tmp_path / "vs.sock")
    svc = ServiceThread(
        path, verifier=_AllTrueVerifier(), tracer=svc_tracer, stats_port=0
    )
    svc.start()
    try:

        async def run():
            client = RemoteVerifyScheduler(
                path,
                verifier=_AllTrueVerifier(),
                tracer=cli_tracer,
                origin="nodeA",
            )
            await client.start()
            for _ in range(200):
                if client.connected:
                    break
                await asyncio.sleep(0.02)
            assert client.connected, "client never attached"
            obs.set_height_hint(42, 1)
            items = [
                SigItem(b"\x01" * 32, b"m" * 32, b"\x02" * 64, "ed25519")
            ] * 3
            verdicts = await client.submit(items, "consensus")
            assert verdicts.all()
            await client.stop()

        asyncio.run(run())
        port = svc.server.stats_port
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/dump_traces", timeout=10
        ) as resp:
            svc_dump = json.load(resp)
    finally:
        svc.stop()
        obs.set_height_hint(0, 0)

    # client side: the round trip under the stamped height
    cli_recs = [r.to_json() for r in cli_tracer.records()]
    ipc = [r for r in cli_recs if r["name"] == "verify.ipc"]
    assert ipc and ipc[0]["height"] == 42 and ipc[0]["round"] == 1
    assert ipc[0]["fields"]["origin"] == "nodeA"

    # service side: queue/device sub-spans recorded by the service
    # process under the SAME context
    by_name = {r["name"]: r for r in svc_dump["records"]}
    for want in ("verify.device", "verify.service"):
        assert want in by_name, sorted(by_name)
        assert by_name[want]["height"] == 42
        assert by_name[want]["fields"]["origin"] == "nodeA"
        assert by_name[want]["fields"]["req"] == ipc[0]["fields"]["req"]
    assert svc_dump["node_id"].startswith("verify-service-")

    # cluster merge: validator dump + service dump on one timeline
    node_dump = obs.normalize_dump(
        {
            "node_id": "AAAA",
            "moniker": "nodeA",
            "epoch_wall_ns": cli_tracer.epoch_wall_ns,
            "records": cli_recs,
            "peer_clock": {},
        }
    )
    sdump = obs.normalize_dump(svc_dump)
    ref, offsets, merged = obs.merge_records([node_dump, sdump])
    assert offsets[sdump["node_id"]]["source"] == "wall_anchor"
    merged_h42 = {
        r["name"] for r in merged if r.get("height") == 42
    }
    assert {"verify.ipc", "verify.device", "verify.service"} <= merged_h42

    # the causal join: RTT >= service handle time; wire overhead named
    flow = obs.verify_flow(merged)
    assert flow["joined"] == 1
    row = flow["heights"]["42"]
    assert row["rows"] == 3
    assert row["ipc_ms"] >= row["device_ms"]
    assert row["wire_ms"] >= 0.0
    # and the cluster report carries/renders the section
    report = obs.cluster_report([node_dump, sdump])
    assert report["verify_flow"]["joined"] == 1
    assert "verify flow" in obs.report_text(report)


def test_multi_round_submission_sums_not_overwrites():
    """A submission larger than max_batch dispatches as several device
    rounds, each recording queue/device sub-spans under the SAME
    (origin, req): verify_flow must accumulate them, and the rounds'
    queue spans must not re-bill earlier rounds' device time — the
    summed sub-spans stay inside the client-observed elapsed."""
    from tendermint_tpu.crypto.batch_verifier import SigItem
    from tendermint_tpu.parallel.scheduler import VerifyScheduler

    tracer = obs.Tracer(enabled=True)
    sched = VerifyScheduler(
        verifier=_AllTrueVerifier(), max_batch=2, tracer=tracer
    )

    async def run():
        await sched.start()
        items = [
            SigItem(b"\x01" * 32, b"m" * 32, b"\x02" * 64, "ed25519")
        ] * 5
        t0 = asyncio.get_running_loop().time()
        verdicts = await sched.submit(
            items, "consensus", ctx=(11, 0, "nodeA", 99)
        )
        elapsed = asyncio.get_running_loop().time() - t0
        await sched.stop()
        return verdicts, elapsed

    verdicts, elapsed = asyncio.run(run())
    assert verdicts.all() and len(verdicts) == 5
    recs = [r.to_json() for r in tracer.records()]
    devs = [r for r in recs if r["name"] == "verify.device"]
    queues = [r for r in recs if r["name"] == "verify.queue"]
    assert len(devs) == 3  # 5 items / max_batch 2
    assert all(r["fields"]["req"] == 99 for r in devs)
    # no queue span overlaps any device span of the same submission
    # (tolerance: to_json rounds t0/dur to microseconds, so adjacent
    # spans can appear to overlap by up to ~2 us)
    for q in queues:
        for d in devs:
            assert (
                q["t0"] + q["dur"] <= d["t0"] + 5e-6
                or d["t0"] + d["dur"] <= q["t0"] + 5e-6
            ), (q, d)
    # the summed sub-spans fit inside the observed elapsed (the
    # conservation property verify_flow's join relies on)
    total = sum(r["dur"] for r in devs + queues)
    assert total <= elapsed + 1e-5  # durs are us-rounded in to_json

    # verify_flow accumulates the rounds instead of keeping the last
    merged = [dict(r, node="svc", node_id="S") for r in recs] + [
        dict(
            _span(
                "verify.ipc", 0.0, elapsed, height=11,
                origin="nodeA", req=99, n=5,
            ),
            node="nodeA",
            node_id="A",
        )
    ]
    flow = obs.verify_flow(merged)
    row = flow["heights"]["11"]
    assert row["device_ms"] == pytest.approx(
        sum(r["dur"] for r in devs) * 1e3, rel=1e-6
    )
    assert row["queue_ms"] == pytest.approx(
        sum(r["dur"] for r in queues) * 1e3, rel=1e-6
    )


# --- cluster offsets under a partitioned peer graph (satellite) -------------


def _dump(node_id, records=(), epoch_wall_ns=0, peer_clock=None, name=""):
    return obs.normalize_dump(
        {
            "node_id": node_id,
            "moniker": name or node_id,
            "epoch_wall_ns": epoch_wall_ns,
            "records": list(records),
            "peer_clock": peer_clock or {},
        }
    )


def test_partitioned_peer_graph_falls_back_to_wall_anchor():
    """Satellite: offset estimation when the NTP peer graph is
    partitioned — an island with no path to the reference must ride its
    raw wall anchor, and the merge must still rebase its records
    correctly through the epoch difference."""
    # island 1: A <-> B via NTP (B's clock +100 ms)
    a = _dump(
        "A",
        [_span("cs.propose", 1.0, 0.1, height=7)],
        epoch_wall_ns=1_000_000_000,
        peer_clock={"B": {"offset_s": 0.1, "rtt_s": 0.002, "samples": 4}},
    )
    b = _dump("B", epoch_wall_ns=1_100_000_000)
    # island 2: C has NO peer table and nobody measures it; its wall
    # anchor is 2.0 s ahead of A's, and its record at local t0=1.0
    # happened at the same wall instant as A's t0=3.0
    c = _dump(
        "C",
        [_span("verify.device", 1.0, 0.05, height=7)],
        epoch_wall_ns=3_000_000_000,
    )
    offsets = obs.estimate_offsets([a, b, c])
    assert offsets["A"]["source"] == "reference"
    assert offsets["B"]["source"] == "ntp_graph"
    assert offsets["B"]["offset_s"] == pytest.approx(0.1)
    assert offsets["C"]["source"] == "wall_anchor"
    assert offsets["C"]["offset_s"] == 0.0

    _, _, merged = obs.merge_records([a, b, c])
    t_by_node = {m["node"]: m["t0"] for m in merged}
    # C's record rebased purely via the anchors: 1.0 + (3.0 - 1.0)
    assert t_by_node["C"] == pytest.approx(3.0, abs=1e-9)
    assert t_by_node["A"] == pytest.approx(1.0, abs=1e-9)
    # the report builds over the partitioned merge without error
    report = obs.cluster_report([a, b, c])
    assert report["offsets"]["C"]["source"] == "wall_anchor"


# --- RPC surface ------------------------------------------------------------


def test_dump_traces_conservation_and_injected_empty_tracer():
    from tendermint_tpu.rpc.core import RPCCore

    # an injected-but-EMPTY tracer is falsy (Tracer has __len__): the
    # route must still dump THIS ring, not the process default (the
    # PR 4 falsy-tracer bug class, swept per the PR 15 satellite)
    tracer = obs.Tracer(enabled=True)
    core = RPCCore(SimpleNamespace(tracer=tracer))
    dump = core.dump_traces()
    assert dump["enabled"] is True and dump["records"] == []

    base = tracer.epoch
    for r in _height_records(6, 0.0):
        tracer.add_span(r["name"], base + r["t0"], r["dur"], height=6)
    dump = core.dump_traces()
    cons = dump["conservation"]
    assert cons["schema"] == obs.CONSERVATION_SCHEMA
    assert cons["heights"]["6"]["dark_time_ms"] == pytest.approx(0.0)
    assert json.loads(json.dumps(dump))  # artifact-grade JSON


# --- the 4-validator acceptance ---------------------------------------------


def test_four_validator_conservation_acceptance():
    """ISSUE 15 acceptance: on the 4-validator net with tracing on,
    the attribution buckets sum to >= 95% of measured wall per height
    (dark_time <= 5%), judged from one node's ring (sharing a ring
    across nodes would overlap their height windows)."""
    vs, pvs = make_validators(4)
    genesis = make_genesis(vs)
    tracer = obs.Tracer(enabled=True, ring_size=1 << 15)
    prev_default = obs.default_tracer()
    obs.set_default_tracer(tracer)

    async def run():
        nodes = [
            make_node(
                vs,
                pv,
                genesis,
                tracer=(tracer if i == 0 else obs.Tracer(enabled=False)),
            )
            for i, pv in enumerate(pvs)
        ]
        css = [n[0] for n in nodes]
        wire_net(css)
        for cs in css:
            await cs.start()
        await asyncio.gather(
            *(cs.wait_for_height(4, timeout=60) for cs in css)
        )
        for cs in css:
            await cs.stop()

    try:
        asyncio.run(run())
    finally:
        obs.set_default_tracer(prev_default)

    recs = [r.to_json() for r in tracer.records()]
    cons = wall_conservation(recs)
    agg = cons["aggregate"]
    assert agg["n_heights"] >= 3
    assert agg["conserved"] is True
    assert check_conservation(cons) == []
    # judge completed heights (the tip's window may still be open at
    # stop time); every one must be >= 95% explained
    tip = max(cons["heights"])
    complete = {
        h: v for h, v in cons["heights"].items() if h < tip
    }
    assert complete
    for h, row in complete.items():
        assert row["dark_fraction"] <= 0.05, (
            f"height {h}: {row['dark_fraction']:.1%} of "
            f"{row['wall_ms']:.1f} ms wall is dark: {row}"
        )
    # every height row carries the full bucket schema (the harness
    # runs a NilWAL, so the wal_fsync column exists but stays 0 here;
    # the carve plumbing itself is pinned by the synthetic tests)
    for row in cons["heights"].values():
        for b in CONSERVATION_BUCKETS:
            assert f"{b}_ms" in row
