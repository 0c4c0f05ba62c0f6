"""Device-cost ledger plane (PR 12): DispatchLedger accounting,
scheduler wiring, fill-efficiency health detector, on-demand profiling
hooks, the dump/profile RPC routes, and tools/device_report rendering.

The acceptance contracts pinned here:
- ledger totals reconcile with the shape-registry dispatch counters
  when a real BatchVerifier drives the rounds (same totals);
- recording overhead is far under 2% of the ~60-100 ms dispatch floor;
- the profiler-unavailable path is a STRUCTURED RPC error, not a crash.
"""

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem
from tendermint_tpu.crypto.shape_registry import ShapeRegistry
from tendermint_tpu.libs.metrics import Registry, SchedulerMetrics
from tendermint_tpu.obs.health import OK, WARN, BurnRateSLO, HealthMonitor
from tendermint_tpu.obs.ledger import DispatchLedger
from tendermint_tpu.obs.profiler import ProfileCapture, ProfilerUnavailable
from tendermint_tpu.parallel.scheduler import VerifyScheduler
from tendermint_tpu.rpc.core import RPCCore
from tendermint_tpu.rpc.server import RPCError

pytestmark = pytest.mark.ledger

BAD = b"\x00" * 64


def _item(i: int, ok: bool = True) -> SigItem:
    return SigItem(b"\x01" * 32, b"m%d" % i, b"\x02" * 64 if ok else BAD)


class StubVerifier:
    def __init__(self, delay: float = 0.0):
        self.delay = delay
        self.batches = []

    def verify(self, items):
        if self.delay:
            time.sleep(self.delay)
        self.batches.append(list(items))
        return np.array([it.sig != BAD for it in items])


def _sched(stub=None, ledger=None, **kw) -> VerifyScheduler:
    return VerifyScheduler(
        verifier=stub or StubVerifier(),
        metrics=SchedulerMetrics(Registry("test")),
        ledger=ledger or DispatchLedger(),
        **kw,
    )


# --- DispatchLedger accounting ----------------------------------------------


def test_ledger_totals_and_per_class_attribution():
    led = DispatchLedger()
    # round 1: two classes share a 64-bucket round, 48 rows requested
    led.record_round(
        1.0,
        class_rows={"consensus": 32, "blocksync": 16},
        requested=48,
        dispatched=64,
        submissions=2,
        class_subs={"consensus": 1, "blocksync": 1},
        queue_wait_s=0.004,
        class_queue_wait={"consensus": 0.001, "blocksync": 0.003},
        host_prep_s=0.002,
        device_s=0.100,
    )
    # round 2: single-class full bucket
    led.record_round(
        2.0,
        class_rows={"consensus": 64},
        requested=64,
        dispatched=64,
        submissions=4,
        device_s=0.060,
    )
    # fn-lane round: books whole, no bucket padding attributable
    led.record_round(
        3.0,
        class_rows={"sequencer": 17},
        requested=17,
        dispatched=17,
        submissions=1,
        device_s=0.010,
        engine="fn",
    )
    s = led.summary()
    assert s["rounds"] == 3
    assert s["fn_rounds"] == 1
    assert s["rows_requested"] == 112  # sig rounds only
    assert s["rows_dispatched"] == 128
    assert s["fn_rows"] == 17
    assert s["padding_rows"] == 16
    assert s["fill_ratio"] == round(112 / 128, 4)
    assert s["device_seconds"] == pytest.approx(0.170)
    # device time attributed by row share: consensus got 32/48 of round
    # 1 plus all of round 2; fn round books whole to sequencer
    pc = s["per_class"]
    assert pc["consensus"]["device_seconds"] == pytest.approx(
        0.100 * (32 / 48) + 0.060, abs=1e-6
    )
    assert pc["blocksync"]["device_seconds"] == pytest.approx(
        0.100 * (16 / 48), abs=1e-6
    )
    assert pc["sequencer"]["device_seconds"] == pytest.approx(0.010)
    # shares sum to ~1.0 over the whole ledger
    assert sum(v["device_share"] for v in pc.values()) == pytest.approx(
        1.0, abs=0.01
    )
    # single-class rounds credit submissions without class_subs
    assert pc["consensus"]["submissions"] == 1 + 4
    assert pc["blocksync"]["queue_wait_seconds"] == pytest.approx(0.003)
    # amortization curve: the 64 bucket saw 2 rounds, 6 submissions
    assert s["by_bucket"]["64"] == {
        "rounds": 2, "rows_requested": 112, "submissions": 6,
    }
    assert s["requests_per_dispatch"] == pytest.approx(7 / 3, abs=1e-3)


@pytest.mark.parametrize("since", [False, True], ids=["whole", "span"])
def test_ledger_books_host_rows_apart_from_the_bucket(since):
    """A mixed-key round's rows of other key types are host_rows: in
    no bucket and no fill, but in each class's share of the round's
    time. A round of host rows alone books no bucket and no fill."""
    led = DispatchLedger()
    mark = led.mark()
    led.record_round(
        1.0,
        class_rows={"blocksync": 48, "consensus": 16},
        requested=48,
        dispatched=64,
        host_rows=16,
        submissions=2,
        class_subs={"blocksync": 1, "consensus": 1},
        device_s=0.200,
    )
    led.record_round(
        2.0,
        class_rows={"consensus": 8},
        requested=0,
        dispatched=0,
        host_rows=8,
        device_s=0.040,
    )
    s = led.summary(since=mark if since else None)
    assert led.totals()["host_rows"] == s["host_rows"] == 24
    assert (s["rows_requested"], s["rows_dispatched"]) == (48, 64)
    assert s["fill_ratio"] == s["fill_ratio_p50"] == 0.75
    assert list(s["by_bucket"]) == ["64"]
    assert [e["host_rows"] for e in led.entries()] == [16, 8]
    pc = s["per_class"]
    assert pc["blocksync"]["device_seconds"] == pytest.approx(0.150)
    assert pc["consensus"]["device_seconds"] == pytest.approx(0.090)
    assert sum(v["device_share"] for v in pc.values()) == pytest.approx(1.0)


def test_ledger_fill_percentiles_and_entry_ring():
    led = DispatchLedger(max_entries=8)
    for i in range(20):
        # fill alternates 0.25 / 1.0
        req = 16 if i % 2 else 64
        led.record_round(
            float(i), class_rows={"consensus": req}, requested=req,
            dispatched=64, device_s=0.001,
        )
    s = led.summary()
    # totals are exact despite the 8-entry ring...
    assert s["rounds"] == 20
    assert s["rows_dispatched"] == 20 * 64
    # ...while the fill window honestly flags the truncation
    assert s["fill_window_truncated"] is True
    assert len(led.entries()) == 8
    # percentiles over retained entries: half at 0.25, half at 1.0
    assert s["fill_ratio_p50"] in (0.25, 1.0)
    assert s["fill_ratio_p95"] == 1.0
    # entries() respects since_seq and limit
    assert [e["seq"] for e in led.entries(since_seq=18)] == [18, 19]
    assert len(led.entries(limit=3)) == 3


def test_ledger_mark_and_span_summary():
    led = DispatchLedger()
    led.record_round(
        1.0, class_rows={"light": 8}, requested=8, dispatched=8,
        device_s=0.5,
    )
    mark = led.mark()
    led.record_round(
        2.0, class_rows={"consensus": 24}, requested=24, dispatched=64,
        submissions=3, device_s=0.2,
    )
    s = led.summary(since=mark)
    # the span covers only the post-mark round
    assert s["rounds"] == 1
    assert s["rows_requested"] == 24
    assert s["padding_rows"] == 40
    assert s["device_seconds"] == pytest.approx(0.2)
    assert list(s["per_class"]) == ["consensus"]
    assert s["per_class"]["consensus"]["device_share"] == pytest.approx(
        1.0
    )
    # the span rebuild carries submissions and queue wait, not just
    # rows/device time — a single-class round's submissions belong to
    # its class even without an explicit class_subs map
    assert s["per_class"]["consensus"]["submissions"] == 3
    assert s["fill_window_truncated"] is False
    # ...and explicit per-class wait survives the span view too
    led.record_round(
        3.0, class_rows={"consensus": 4, "light": 4}, requested=8,
        dispatched=8, submissions=2,
        class_subs={"consensus": 1, "light": 1},
        class_queue_wait={"consensus": 0.002, "light": 0.005},
        device_s=0.1,
    )
    s2 = led.summary(since=mark)
    assert s2["per_class"]["light"]["queue_wait_seconds"] == pytest.approx(
        0.005
    )
    assert s2["per_class"]["light"]["submissions"] == 1


def test_ledger_thread_safety_under_concurrent_records():
    led = DispatchLedger()

    def hammer(klass):
        for i in range(500):
            led.record_round(
                float(i), class_rows={klass: 4}, requested=4,
                dispatched=8, device_s=0.001,
            )

    threads = [
        threading.Thread(target=hammer, args=(k,))
        for k in ("a", "b", "c")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = led.summary()
    assert s["rounds"] == 1500
    assert s["rows_requested"] == 6000
    # seq ids never collide: the ring's newest entries are distinct
    seqs = [e["seq"] for e in led.entries()]
    assert len(seqs) == len(set(seqs))


# --- scheduler wiring --------------------------------------------------------


def test_scheduler_records_sig_and_fn_rounds():
    led = DispatchLedger()
    stub = StubVerifier(delay=0.02)
    s = _sched(stub, ledger=led)

    async def run():
        await s.start()
        first = asyncio.create_task(s.submit([_item(0)], "consensus"))
        await asyncio.sleep(0.005)
        await asyncio.gather(
            s.submit([_item(1), _item(2)], "consensus"),
            s.submit([_item(3)], "blocksync"),
            first,
        )
        await s.submit_fn(
            list(range(5)), lambda xs: [True] * len(xs), "sequencer"
        )
        await s.stop()

    asyncio.run(run())
    summ = led.summary()
    assert summ["rounds"] == 3  # solo round + coalesced round + fn
    assert summ["fn_rounds"] == 1
    assert summ["fn_rows"] == 5
    # the coalesced round carries both classes with their real rows
    coalesced = [
        e for e in led.entries()
        if e["engine"] == "sig" and len(e["classes"]) == 2
    ]
    assert len(coalesced) == 1
    assert coalesced[0]["rows"] == {"consensus": 2, "blocksync": 1}
    assert coalesced[0]["submissions"] == 2
    assert coalesced[0]["queue_wait_s"] > 0
    assert coalesced[0]["device_s"] > 0
    # tm_* accounting surface: device seconds per class, padding counter
    per_class = summ["per_class"]
    assert s.metrics.device_seconds.value(
        klass="consensus"
    ) == pytest.approx(per_class["consensus"]["device_seconds"], rel=0.05)
    assert s.metrics.padding_rows.value() == summ["padding_rows"]


def test_scheduler_ledger_reconciles_with_shape_registry():
    """Acceptance: ledger totals reconcile with the shape-registry
    dispatch counters — in steady state (key tables warm) every
    scheduler sig round is exactly one registry-recorded device
    dispatch, and the padded bucket the ledger booked is the bucket the
    verifier dispatched. (A COLD run records extra registry dispatches
    for the table-build programs — real device work that is not a
    scheduler round; warming first makes the comparison exact.)"""
    reg = ShapeRegistry()
    bv = BatchVerifier(min_device_batch=0, shape_registry=reg)
    led = DispatchLedger()
    s = VerifyScheduler(
        verifier=bv,
        metrics=SchedulerMetrics(Registry("test")),
        ledger=led,
    )
    k = host.PrivKey.from_secret(b"ledger-reconcile")
    pub = k.public_key().data

    def items(n, tag):
        return [
            SigItem(pub, b"%s-%d" % (tag, i), k.sign(b"%s-%d" % (tag, i)))
            for i in range(n)
        ]

    async def run():
        await s.start()
        # warm: builds the key's device table (its own registry
        # dispatch) and compiles the 8-bucket program
        assert (await s.submit(items(2, b"warm"), "consensus")).all()
        before = reg.snapshot()
        mark = led.mark()
        assert (await s.submit(items(5, b"a"), "consensus")).all()
        assert (await s.submit(items(11, b"b"), "blocksync")).all()
        await s.stop()
        return before, mark

    before, mark = asyncio.run(run())
    after = reg.snapshot()
    summ = led.summary(since=mark)
    sig_rounds = summ["rounds"] - summ["fn_rounds"]
    dispatches = (
        after["device_dispatch_count"] - before["device_dispatch_count"]
    )
    assert sig_rounds == dispatches == 2
    assert summ["rows_requested"] == 16
    # the ledger's dispatched rows are the verifier's padded buckets
    assert summ["rows_dispatched"] == sum(
        reg.bucket_for(n) for n in (5, 11)
    )
    assert summ["padding_rows"] == summ["rows_dispatched"] - 16


def test_ledger_recording_overhead_microbench():
    """Acceptance: ledger recording adds <2% to dispatch wall time. The
    dispatch floor is ~60-100 ms (PERF_ANALYSIS §10); 2% is >=1.2 ms
    per round. One record_round must land orders of magnitude under
    that — pin <=120 us/call mean so even a 60 ms round pays <0.2%."""
    led = DispatchLedger()
    class_rows = {"consensus": 48, "blocksync": 16}
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        led.record_round(
            float(i),
            class_rows=class_rows,
            requested=64,
            dispatched=64,
            submissions=2,
            class_subs={"consensus": 1, "blocksync": 1},
            queue_wait_s=0.001,
            class_queue_wait={"consensus": 0.001, "blocksync": 0.002},
            host_prep_s=0.001,
            device_s=0.06,
        )
    per_call = (time.perf_counter() - t0) / n
    assert led.summary()["rounds"] == n
    assert per_call < 120e-6, (
        f"record_round {per_call * 1e6:.1f} us/call — ledger recording "
        "must stay noise against the ~60 ms dispatch floor"
    )


# --- fill-efficiency health detector ----------------------------------------


def test_fill_efficiency_detector_floor_and_min_rows():
    from tendermint_tpu.obs.health import FillEfficiencyDetector

    def slo():
        return BurnRateSLO(
            "fill", objective=0.8, short_window=30.0, long_window=300.0
        )

    det = FillEfficiencyDetector(slo(), floor=0.1, min_rows=256)
    # tiny intervals are never judged: a small committee's padded vote
    # rounds are a latency choice, not pageable waste
    t = 0.0
    for _ in range(20):
        t += 1.0
        det.observe_interval(t, 1.0, 64.0)  # fill 0.016 but 64 rows
    assert det.verdict(t) == OK
    # sustained 5%-full buckets at volume flags
    det2 = FillEfficiencyDetector(slo(), floor=0.1, min_rows=256)
    t = 0.0
    for _ in range(20):
        t += 1.0
        det2.observe_interval(t, 100.0, 2048.0)
    assert det2.verdict(t) >= WARN
    # healthy fill at volume stays OK
    det3 = FillEfficiencyDetector(slo(), floor=0.1, min_rows=256)
    t = 0.0
    for _ in range(20):
        t += 1.0
        det3.observe_interval(t, 1800.0, 2048.0)
    assert det3.verdict(t) == OK


def test_monitor_ledger_seam_flags_fill_floor():
    led = DispatchLedger()
    mon = HealthMonitor(
        tracer=obs.Tracer(enabled=True), fill_floor=0.1, fill_min_rows=256
    )
    mon.bind_ledger(led)
    t = 0.0
    for i in range(20):
        t += 1.0
        # each tick moves 2048 dispatched rows at 5% fill
        led.record_round(
            t, class_rows={"blocksync": 102}, requested=102,
            dispatched=2048, device_s=0.01,
        )
        mon.sample(t)
    assert mon.detectors["fill_efficiency"].verdict(t) >= WARN
    assert mon.subsystem_verdicts(t)["scheduler"] >= WARN
    # the verdict document names the detector
    doc = mon.verdict(t)
    assert "fill_efficiency" in doc["subsystems"]["scheduler"]["detectors"]


# --- profiling hooks ---------------------------------------------------------


def test_profile_capture_session_lifecycle(tmp_path):
    cap = ProfileCapture(str(tmp_path), sample_interval_s=0.002)
    assert cap.active is False
    started = cap.start(label="test", device=False)
    assert cap.active is True
    assert started["id"] == "profile_0001"
    assert started["device_trace"] == {"enabled": False}
    # a second start is the structured profiler-unavailable error
    with pytest.raises(ProfilerUnavailable):
        cap.start()
    # give the sampler a few ticks on this (busy) thread
    deadline = time.monotonic() + 0.2
    while time.monotonic() < deadline:
        sum(range(100))
    session = cap.stop()
    assert cap.active is False
    assert session["duration_s"] >= 0.0
    lp = session["loop_profile"]
    assert lp["samples"] >= 1
    assert os.path.exists(lp["path"])
    with open(lp["path"]) as f:
        doc = json.load(f)
    assert doc["samples"] == lp["samples"]
    assert doc["stacks"] and doc["stacks"][0]["count"] >= 1
    # stop with nothing running is the same structured error
    with pytest.raises(ProfilerUnavailable):
        cap.stop()
    # ids are monotonic across sessions
    assert cap.start(device=False)["id"] == "profile_0002"
    cap.stop()


def test_profile_capture_device_trace_guarded(tmp_path):
    """device=True must never raise out of start/stop: on a backend or
    environment where the jax profiler can't run, unavailability is a
    structured field inside device_trace."""
    cap = ProfileCapture(str(tmp_path), sample_interval_s=0.005)
    started = cap.start(device=True)
    assert "device_trace" in started
    assert isinstance(started["device_trace"].get("enabled"), bool)
    session = cap.stop()
    dt = session["device_trace"]
    if not dt["enabled"]:
        assert "error" in dt  # degraded structurally, not thrown


# --- RPC routes --------------------------------------------------------------


class _StubSched:
    def __init__(self, ledger):
        self.ledger = ledger


class _StubNode:
    class config:
        class rpc:
            unsafe = False

    def __init__(self, ledger=None, profiler=None):
        if ledger is not None:
            self.verify_scheduler = _StubSched(ledger)
        else:
            self.verify_scheduler = None
        if profiler is not None:
            self.profiler = profiler


def test_dump_dispatch_ledger_route(tmp_path):
    led = DispatchLedger()
    led.record_round(
        1.0, class_rows={"consensus": 6, "light": 2}, requested=8,
        dispatched=8, submissions=2, device_s=0.004,
    )
    core = RPCCore(_StubNode(ledger=led))
    out = core.dump_dispatch_ledger()
    assert out["enabled"] is True
    assert out["summary"]["rounds"] == 1
    assert out["summary"]["per_class"]["consensus"]["rows"] == 6
    assert len(out["entries"]) == 1
    assert "device_dispatch_count" in out["shape_registry"]
    # entries param caps the detail view
    for i in range(5):
        led.record_round(
            2.0 + i, class_rows={"consensus": 8}, requested=8,
            dispatched=8, device_s=0.001,
        )
    assert len(core.dump_dispatch_ledger(entries=3)["entries"]) == 3
    # entries=0 means summary-only, not "the whole ring"
    assert core.dump_dispatch_ledger(entries=0)["entries"] == []
    with pytest.raises(RPCError) as ei:
        core.dump_dispatch_ledger(entries="nope")
    assert ei.value.code == -32602


def test_profile_rpc_routes_and_structured_errors(tmp_path):
    cap = ProfileCapture(str(tmp_path), sample_interval_s=0.005)
    core = RPCCore(_StubNode(profiler=cap))
    routes = core.routes()
    assert "profile_start" in routes and "profile_stop" in routes
    # stop with no session: the profiler-unavailable structured error
    with pytest.raises(RPCError) as ei:
        core.profile_stop()
    assert ei.value.code == -32000
    assert "profiler unavailable" in str(ei.value.message)
    started = core.profile_start(label="rpc", device=False)
    assert started["started"] is True
    # double start: same structured error class
    with pytest.raises(RPCError) as ei:
        core.profile_start()
    assert ei.value.code == -32000
    time.sleep(0.03)
    stopped = core.profile_stop()
    assert stopped["stopped"] is True
    assert "loop_profile" in stopped
    # a node assembled WITHOUT a profiler does not expose the routes
    bare = RPCCore(_StubNode())
    assert "profile_start" not in bare.routes()
    assert "dump_dispatch_ledger" in bare.routes()
    # ...and its ledger dump reports the scheduler-less state honestly
    assert bare.dump_dispatch_ledger()["enabled"] is False


# --- tools/device_report -----------------------------------------------------


def _sample_summary():
    led = DispatchLedger()
    led.record_round(
        1.0, class_rows={"consensus": 48, "blocksync": 16},
        requested=64, dispatched=64, submissions=3, device_s=0.12,
        queue_wait_s=0.002,
    )
    led.record_round(
        2.0, class_rows={"lightserve": 100}, requested=100,
        dispatched=512, submissions=40, device_s=0.05,
    )
    return led.summary()


def test_device_report_extracts_every_supported_shape():
    from tools.device_report import extract_summary

    summary = _sample_summary()
    rpc_doc = {"result": {"enabled": True, "summary": summary}}
    bench_doc = {"metric": "x", "device_cost": summary}
    for doc in (rpc_doc, bench_doc, summary):
        assert extract_summary(doc)["rounds"] == 2
    with pytest.raises(ValueError):
        extract_summary({"metric": "x"})
    with pytest.raises(ValueError):
        extract_summary({"device_cost": {"no_rounds_key": 1}})


def test_device_report_renders_tables():
    from tools.device_report import report_text

    text = report_text(_sample_summary(), name="unit")
    assert "device-cost ledger: unit" in text
    # per-class table sorted by device share, lightserve's padding shows
    for token in (
        "consensus", "blocksync", "lightserve", "amortization curve",
        "fill p50",
    ):
        assert token in text
    # padding called out: 512-bucket round was 100/512 full
    assert "412 rows" in text
    # empty summary renders honestly
    assert "no scheduler rounds" in report_text(
        DispatchLedger().summary()
    )


def test_device_report_cli_roundtrip(tmp_path, capsys, monkeypatch):
    from tools import device_report

    art = {"metric": "bench", "device_cost": _sample_summary()}
    p = tmp_path / "BENCH_x.json"
    p.write_text(json.dumps(art))
    monkeypatch.setattr(
        "sys.argv", ["device_report.py", str(p), "--json"]
    )
    rc = device_report.main()
    assert rc == 0
    out = capsys.readouterr().out
    assert json.loads(out)["BENCH_x.json"]["rounds"] == 2
    # a document with no device-cost block is a clean nonzero exit
    bad = tmp_path / "nope.json"
    bad.write_text("{}")
    monkeypatch.setattr("sys.argv", ["device_report.py", str(bad)])
    assert device_report.main() == 1
