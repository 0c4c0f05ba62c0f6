"""A submission's way in and out on the verify service's own ring: the
client's clock stamps in the wire trailer, the `verify.*` spans the
service records from them, the verifier's `crypto.table_lookup` /
`crypto.table_build`, the size of the service's ring, and the device
profiler behind the stats port.
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem
from tendermint_tpu.obs import tracer as tracer_mod
from tendermint_tpu.parallel import verify_service as vs
from tendermint_tpu.parallel.verify_service import (
    RemoteVerifyScheduler,
    ServiceThread,
    _Cursor,
    _HDR,
    _STAMPS,
    decode_submit,
    decode_submit_fn,
    decode_submit_legacy,
    decode_trace_ctx,
    decode_trace_stamps,
    encode_submit,
    encode_submit_fn,
)

from .wire_legacy import CODEC_CASES, encode_submit_legacy, submit_raw

pytestmark = pytest.mark.verify_service

WAY = (
    "verify.ingress", "verify.client_encode", "verify.wire_in",
    "verify.frame_decode", "verify.service", "verify.reply",
)


class SigTagVerifier:
    """Verdict = sig starts with b'1': per item, so it shows whether a
    frame's items arrived as they were sent."""

    def verify(self, items):
        return np.array([it.sig[:1] == b"1" for it in items], dtype=bool)


def sig_items(n: int) -> list[SigItem]:
    return [
        SigItem(
            b"p" * 32, b"m%06d" % i + b"\x00" * 26,
            (b"1" if i % 3 else b"0") + b"s" * 63,
        )
        for i in range(n)
    ]


WANT = np.array([bool(i % 3) for i in range(12)])


@pytest.fixture
def svc(tmp_path):
    """(service, its ring): a ServiceThread over the stub verifier."""
    ring = obs.Tracer(enabled=True)
    thread = ServiceThread(
        str(tmp_path / "vs.sock"), verifier=SigTagVerifier(), tracer=ring,
        stats_port=0,
    )
    thread.start()
    try:
        yield thread, ring
    finally:
        thread.stop()


async def submit_through_client(path: str, items, origin="nodeA"):
    client = RemoteVerifyScheduler(
        path, verifier=SigTagVerifier(), retry_base=0.02, origin=origin,
        tracer=obs.Tracer(enabled=False),
    )
    await client.start()
    deadline = time.monotonic() + 15
    while not client.connected and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert client.connected, "client never attached"
    try:
        return await client.submit(items, "consensus")
    finally:
        await client.stop()


def way_of(ring) -> dict:
    """{name: record} of the one submission's `verify.*` spans, in time."""
    time.sleep(0.05)  # verify.reply lands after the client has its answer
    recs = [r for r in ring.records() if r.name in WAY]
    return {r.name: r for r in sorted(recs, key=lambda r: r.t0)}


# --- (a) the trailer --------------------------------------------------------


def _encode_fn(req_id, items, klass, ctx=None):
    return encode_submit_fn(
        req_id, "bls_agg", [(b"a" * 32, b"b" * 32)], klass, ctx=ctx
    )


FRAMES = {
    "cols": (encode_submit, decode_submit),
    "v1": (encode_submit_legacy, decode_submit_legacy),
    "fn": (_encode_fn, decode_submit_fn),
}


@pytest.mark.parametrize("kind", FRAMES)
@pytest.mark.parametrize("trailer", ["stamps", "old", "none"])
def test_trailer_round_trips_and_older_frames_decode(trailer, kind):
    assert vs.SHARED_CLOCK, time.get_clock_info("perf_counter")
    encode, decode = FRAMES[kind]
    items = sig_items(3)
    before = time.perf_counter()
    ctx = {
        "stamps": (42, 1, "nodeA", time.perf_counter_ns()),
        "old": (42, 1, "nodeA"),
        "none": None,
    }[trailer]
    frame = encode(7, items, "consensus", ctx=ctx)
    cur = _Cursor(frame)
    _, req_id = _HDR.unpack(cur.take(_HDR.size))
    decoded = decode(cur)
    if kind != "fn":
        assert decoded[:2] == (items, "consensus")
    got_ctx = decode_trace_ctx(cur, req_id)
    stamps = decode_trace_stamps(cur)
    assert cur.off == len(frame)
    if trailer == "none":
        assert got_ctx is None and stamps is None
        return
    assert got_ctx == (42, 1, "nodeA", 7)
    if trailer == "old":
        assert stamps is None
        return
    t_submit, t_encoded = stamps
    assert before <= t_submit <= t_encoded <= time.perf_counter()
    # 16 bytes a submission, and only those
    old = encode(7, items, "consensus", ctx=ctx[:3])
    assert len(frame) - len(old) == _STAMPS.size == 16


# --- (b) one submission, one chain of spans ---------------------------------


def test_one_submission_leaves_its_way_in_and_out_on_the_service_ring(svc):
    thread, ring = svc
    obs.set_height_hint(42, 1)
    try:
        verdicts = asyncio.run(
            submit_through_client(thread.server.path, sig_items(12))
        )
    finally:
        obs.set_height_hint(0, 0)
    assert (verdicts == WANT).all()
    way = way_of(ring)
    assert tuple(way) == WAY  # each once, in this order by start
    assert len({r.fields["req"] for r in way.values()}) == 1
    for r in way.values():
        assert r.fields["origin"] == "nodeA"
        assert (r.height, r.round) == (42, 1)
        assert r.fields["n"] == 12 and r.fields["klass"] == "consensus"
        assert r.fields["bytes"] > 0
        assert r.dur >= 0.0
    end = lambda r: r.t0 + r.dur  # noqa: E731
    chain = [way[n] for n in WAY[1:]]
    for a, b in zip(chain, chain[1:]):
        assert abs(b.t0 - end(a)) < 1e-3, (a.name, b.name)
    ingress = way["verify.ingress"]
    for name in WAY[1:4]:
        child = way[name]
        assert child.fields["parent"] == "verify.ingress"
        assert ingress.t0 <= child.t0 and end(child) <= end(ingress) + 1e-9
    assert ingress.t0 == way["verify.client_encode"].t0
    assert abs(end(ingress) - way["verify.service"].t0) < 1e-6
    assert "parent" not in way["verify.service"].fields
    # which frame the decode read is on its span, and on no other
    decode = way.pop("verify.frame_decode").fields
    assert (decode["frame"], decode["uniform"]) == ("cols", True)
    assert not any("frame" in r.fields for r in way.values())
    # the frame in is the items, the frame out a bitmap
    assert way["verify.wire_in"].fields["bytes"] > 12 * 128
    assert way["verify.reply"].fields["bytes"] == _HDR.size + 4 + 2


# --- (c) frames of other clients: served the same, never failed -------------


def stamped(
    t_submit_s: float, t_encoded_s: float, encode=encode_submit
) -> bytes:
    return encode(
        9, sig_items(12), "consensus", ctx=(5, 0, "w1")
    ) + _STAMPS.pack(int(t_submit_s * 1e9), int(t_encoded_s * 1e9))


@pytest.mark.parametrize(
    "frame, names",
    [
        # no trailer: no req to join spans by, none recorded
        (lambda now: encode_submit(9, sig_items(12), "consensus"), ()),
        # an older client's three-field trailer, and stamps of another
        # clock: the client's spans are dropped, the service's stay
        (
            lambda now: encode_submit(
                9, sig_items(12), "consensus", ctx=(5, 0, "w1")
            ),
            WAY[3:],
        ),
        (lambda now: stamped(now + 5.0, now + 5.001), WAY[3:]),
        (lambda now: stamped(now - 61.0, now - 60.9), WAY[3:]),
        (lambda now: stamped(now - 0.001, now - 0.002), WAY[3:]),
        # and one whose stamps are sound, sent the same way
        (lambda now: stamped(now - 0.002, now - 0.001), WAY),
        # the per-item frame of an older client, with each trailer it
        # may carry
        (
            lambda now: encode_submit_legacy(9, sig_items(12), "consensus"),
            (),
        ),
        (
            lambda now: encode_submit_legacy(
                9, sig_items(12), "consensus", ctx=(5, 0, "w1")
            ),
            WAY[3:],
        ),
        (
            lambda now: stamped(
                now - 0.002, now - 0.001, encode_submit_legacy
            ),
            WAY,
        ),
    ],
    ids=["no-trailer", "old-trailer", "future", "60s-old", "backwards",
         "sound", "v1-no-trailer", "v1-old-trailer", "v1-sound"],
)
def test_other_frames_are_served_alike_and_bad_stamps_drop_client_spans(
    svc, frame, names
):
    thread, ring = svc
    verdicts = asyncio.run(
        submit_raw(thread.server.path, frame(time.perf_counter()))
    )
    assert (verdicts == WANT).all()
    way = way_of(ring)
    assert tuple(way) == tuple(names)
    if "verify.frame_decode" in way and names != WAY:
        assert "parent" not in way["verify.frame_decode"].fields
    assert thread.server.error_frames == 0


@pytest.mark.parametrize(
    "encode, case, want",
    [
        (encode_submit, "uniform-ed25519", ("cols", True)),
        (encode_submit, "one-item", ("cols", True)),
        (encode_submit, "uniform-keys-varying-msgs", ("cols", False)),
        (encode_submit, "mixed-key-types", ("cols", False)),
        (encode_submit, "empty-fields", ("cols", False)),
        (encode_submit_legacy, "uniform-ed25519", ("v1", False)),
        (encode_submit_legacy, "mixed-key-types", ("v1", False)),
    ],
    ids=lambda v: v if isinstance(v, str) else getattr(v, "__name__", None),
)
def test_frame_decode_span_says_which_frame_it_read(svc, encode, case, want):
    """`verify.frame_decode` carries `frame` (`cols` or `v1`) and
    `uniform`; the dump counts the submit frames of each kind."""
    thread, ring = svc
    items, _ = CODEC_CASES[case]
    verdicts = asyncio.run(
        submit_raw(
            thread.server.path,
            encode(9, items, "consensus", ctx=(5, 0, "w1")),
        )
    )
    assert verdicts.tolist() == [it.sig[:1] == b"1" for it in items]
    fields = way_of(ring)["verify.frame_decode"].fields
    assert (fields["frame"], fields["uniform"]) == want
    assert fields["n"] == len(items)
    counts = {"cols": 0, "v1": 0, want[0]: 1}
    assert thread.server.dump()["service"]["submit_frames"] == counts


# --- (d) tracer off ---------------------------------------------------------


def test_tracer_off_same_verdicts_empty_ring(tmp_path):
    ring = obs.Tracer(enabled=False)
    thread = ServiceThread(
        str(tmp_path / "vs.sock"), verifier=SigTagVerifier(), tracer=ring
    )
    thread.start()
    try:
        verdicts = asyncio.run(
            submit_through_client(thread.server.path, sig_items(12))
        )
    finally:
        thread.stop()
    assert (verdicts == WANT).all()
    assert len(ring) == 0


# --- (e) the verifier's way to its tables -----------------------------------


@pytest.fixture
def default_ring(monkeypatch):
    ring = obs.Tracer(enabled=True)
    monkeypatch.setattr(tracer_mod, "_default", ring)
    return ring


def test_table_build_is_inside_the_lookup_that_caused_it(default_ring):
    from tendermint_tpu.crypto import ed25519

    keys = [
        ed25519.PrivKey.from_secret(b"trace" + bytes([i])) for i in range(3)
    ]
    items = []
    for i in range(8):
        key = keys[i % 3]
        msg = b"vote-%02d" % i
        items.append(SigItem(key.public_key().data, msg, key.sign(msg)))
    verifier = BatchVerifier(min_device_batch=1)
    assert verifier.verify(items).all()
    assert verifier.verify(items).all()
    recs = default_ring.records()
    lookups = [r for r in recs if r.name == "crypto.table_lookup"]
    builds = [r for r in recs if r.name == "crypto.table_build"]
    assert [r.fields["built"] for r in lookups] == [3, 0]
    assert all(
        r.fields["n"] == 8 and r.fields["tier"] == "small" for r in lookups
    )
    assert len(builds) == 1
    build, first = builds[0], lookups[0]
    assert build.fields["keys"] == 3 and build.fields["tier"] == "small"
    assert build.fields["parent"] == "crypto.table_lookup"
    assert first.t0 <= build.t0
    assert build.t0 + build.dur <= first.t0 + first.dur
    # the jitted call keeps its span, after the lookup, and not under it
    calls = [
        r for r in recs
        if r.name in ("crypto.jit_compile", "crypto.device_execute")
    ]
    assert len(calls) == 2 and calls[1].name == "crypto.device_execute"
    assert calls[0].t0 >= first.t0 + first.dur
    assert "parent" not in calls[1].fields
    assert calls[1].fields["tier"] == "small"


# --- (f) the rings' sizes ---------------------------------------------------


def test_service_ring_holds_the_window_and_the_nodes_default_stays(
    tmp_path, monkeypatch
):
    from tendermint_tpu.config.config import InstrumentationConfig

    class Caught(Exception):
        pass

    seen = {}

    def server(path, **kw):
        seen.update(kw)
        raise Caught

    monkeypatch.setattr(tracer_mod, "_default", None)  # restored after
    monkeypatch.setattr(vs, "VerifyServiceServer", server)
    monkeypatch.delenv("TM_TPU_TRACE", raising=False)
    with pytest.raises(Caught):
        vs.run_service(str(tmp_path / "vs.sock"), trace=True)
    ring = seen["tracer"]
    assert ring.enabled and ring is obs.default_tracer()
    for i in range(65536 + 10):
        ring.event("x")
    assert len(ring) == 65536 == vs.SERVICE_RING_SIZE
    assert tracer_mod.DEFAULT_RING_SIZE == 8192
    assert InstrumentationConfig().trace_ring_size == 8192
    assert obs.Tracer(enabled=True)._ring.maxlen == 8192


# --- the device profiler behind the stats port ------------------------------


def get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/{path}", timeout=60
        ) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def test_profile_routes_trace_the_service_while_it_serves(svc):
    thread, ring = svc
    port = thread.server.stats_port
    status, doc = get(port, "profile_stop")
    assert status == 409 and doc["last"] is None
    status, started = get(port, "profile_start?seconds=20&label=t")
    assert status == 200 and started["started"] and started["seconds"] == 20
    assert started["dir"].startswith(thread.server.path + ".profiles")
    status, _ = get(port, "profile_start")
    assert status == 409  # one session at a time
    # the service serves while the session is open
    verdicts = asyncio.run(
        submit_through_client(thread.server.path, sig_items(12))
    )
    assert (verdicts == WANT).all()
    status, session = get(port, "profile_stop")
    assert status == 200 and session["id"] == started["id"]
    assert session["stop_s"] >= 0.0 and session["duration_s"] > 0.0
    # the event loop's thread was sampled, not the profiler's own
    assert session["loop_profile"]["samples"] >= 1
    assert any(
        "run_forever" in frame
        for row in session["loop_profile"]["top_stacks"]
        for frame in row["stack"]
    )
    if session["device_trace"]["enabled"]:
        assert glob.glob(
            os.path.join(
                session["dir"], "plugins", "profile", "*", "*.xplane.pb"
            )
        )
    events = {r.name: r for r in ring.records() if r.kind == "event"}
    assert events["profiler.start"].fields["session"] == started["id"]
    assert events["profiler.stop"].fields["dir"] == session["dir"]
    # and after it
    verdicts = asyncio.run(
        submit_through_client(thread.server.path, sig_items(12))
    )
    assert (verdicts == WANT).all()
    assert get(port, "profile_start?seconds=x")[0] == 400


def test_profile_session_stops_by_itself(svc):
    thread, ring = svc
    port = thread.server.stats_port
    status, started = get(port, "profile_start?seconds=0.2")
    assert status == 200 and started["seconds"] == 0.2
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(r.name == "profiler.stop" for r in ring.records()):
            break
        time.sleep(0.05)
    status, doc = get(port, "profile_stop")
    assert status == 409 and doc["last"]["id"] == started["id"]
    assert get(port, "profile_start?seconds=99")[1]["seconds"] == 30.0
    assert get(port, "profile_stop")[0] == 200
