"""A submission's way in and out on the verify service's own ring: the
client's clock stamps in the wire trailer, the `verify.*` spans the
service records from them (the caller's gather and the reply's way back
among them), the collector's `runtime.gc`, the verifier's
`crypto.table_lookup` / `crypto.table_build`, the size of the service's
ring, and the device profiler behind the stats port.
"""

from __future__ import annotations

import asyncio
import gc
import glob
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem
from tendermint_tpu.obs import tracer as tracer_mod
from tendermint_tpu.parallel import verify_service as vs
from tendermint_tpu.parallel.verify_service import (
    RemoteVerifyScheduler,
    ServiceThread,
    _Cursor,
    _HDR,
    _LINKS,
    _STAMPS,
    decode_submit,
    decode_submit_fn,
    decode_submit_legacy,
    decode_trace_ctx,
    decode_trace_stamps,
    encode_submit,
    encode_submit_fn,
)
from tendermint_tpu.types.block import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

from .wire_legacy import CODEC_CASES, encode_submit_legacy, submit_raw

pytestmark = pytest.mark.verify_service

WAY = (
    "verify.ingress", "verify.client_encode", "verify.wire_in",
    "verify.frame_decode", "verify.service", "verify.reply",
)
GATHER, WIRE_OUT = "verify.client_gather", "verify.wire_out"


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
    recs = [r for r in ring.records() if r.name in WAY + (GATHER, WIRE_OUT)]
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
@pytest.mark.parametrize(
    "trailer", ["links", "no-links", "stamps", "old", "none"]
)
def test_trailer_round_trips_and_older_frames_decode(trailer, kind):
    """The trailer as this tree's client writes it (`links`: a gather's
    instant and the request finished last; `no-links`: a submission
    from no gather, before any reply), and as older clients wrote it:
    16 bytes of stamps, three fields, nothing."""
    assert vs.SHARED_CLOCK, time.get_clock_info("perf_counter")
    encode, decode = FRAMES[kind]
    items = sig_items(3)
    before = time.perf_counter()
    gathered = time.perf_counter_ns()
    done = time.perf_counter_ns()
    ctx = {
        "links": (42, 1, "nodeA", time.perf_counter_ns(), gathered, 6, done),
        "no-links": (42, 1, "nodeA", time.perf_counter_ns(), 0, 0, 0),
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
    t_submit, t_encoded, t_gather, prev_req, t_done = stamps
    assert before <= t_submit <= t_encoded <= time.perf_counter()
    old = encode(7, items, "consensus", ctx=ctx[:3])
    if trailer == "stamps":
        # an older client's 16 bytes: no gather, no request finished
        assert (t_gather, prev_req, t_done) == (None, 0, None)
        assert len(frame) - len(old) == _STAMPS.size == 16
        return
    # 40 bytes a submission, and only those
    assert len(frame) - len(old) == _STAMPS.size + _LINKS.size == 40
    if trailer == "no-links":
        assert (t_gather, prev_req, t_done) == (None, 0, None)
        return
    assert t_gather == gathered * 1e-9 and prev_req == 6
    assert t_done == done * 1e-9 and before <= t_gather <= t_done


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
    t_submit_s: float, t_encoded_s: float, encode=encode_submit,
    t_gather_s=None, req_id=9, prev=(0, 0.0),
) -> bytes:
    """A frame with hand-set stamps: the 16 bytes of an older client,
    or, given `t_gather_s` or `prev` (the request finished last and
    when), the 40 of this tree's."""
    frame = encode(
        req_id, sig_items(12), "consensus", ctx=(5, 0, "w1")
    ) + _STAMPS.pack(int(t_submit_s * 1e9), int(t_encoded_s * 1e9))
    if t_gather_s is None and not prev[0]:
        return frame
    return frame + _LINKS.pack(
        int((t_gather_s or 0) * 1e9), prev[0], int(prev[1] * 1e9)
    )


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
        # a caller's gather before the submit: its span beside the rest;
        # a gather stamp out of order or of another clock drops that
        # span alone, and stamps of another clock drop it with theirs
        (
            lambda now: stamped(now - 0.002, now - 0.001,
                                t_gather_s=now - 0.003),
            (GATHER,) + WAY,
        ),
        (
            lambda now: stamped(now - 0.002, now - 0.001,
                                t_gather_s=now - 0.0015),
            WAY,
        ),
        (
            lambda now: stamped(now - 0.002, now - 0.001,
                                t_gather_s=now - 61.0),
            WAY,
        ),
        (
            lambda now: stamped(now + 5.0, now + 5.001,
                                t_gather_s=now + 4.9),
            WAY[3:],
        ),
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
         "sound", "gather", "gather-after-submit", "gather-60s-old",
         "gather-future", "v1-no-trailer", "v1-old-trailer", "v1-sound"],
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
    if "verify.frame_decode" in way and "verify.ingress" not in names:
        assert "parent" not in way["verify.frame_decode"].fields
    if GATHER in way:
        gather = way[GATHER]
        assert "parent" not in gather.fields  # beside ingress, not in it
        assert abs(gather.t0 + gather.dur - way["verify.ingress"].t0) < 1e-6
        assert gather.fields["req"] == way["verify.ingress"].fields["req"]
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


# --- (c2) the caller's gather and the reply's way back ----------------------

CHAIN = "trace-chain"


def commit_windows(windows: int = 2, heights: int = 3):
    """(validator set, `windows` lists of verify_commits_light entries):
    four ed25519 validators whose every signature starts with b"1", so
    that the stub verifier passes them all."""
    keys = [
        ed25519.PrivKey.from_secret(b"gather%d" % i).public_key()
        for i in range(4)
    ]
    vset = ValidatorSet([Validator(k, 10) for k in keys])
    out = []
    for w in range(windows):
        entries = []
        for h in range(w * heights + 1, (w + 1) * heights + 1):
            bh = bytes([h]) * 32
            bid = BlockID(bh, PartSetHeader(1, bh))
            sigs = [
                CommitSig(
                    BlockIDFlag.COMMIT, v.address, 10**18 + h * 10**9 + i,
                    b"1" + bytes([h, i]) * 31 + b"s",
                )
                for i, v in enumerate(vset.validators)
            ]
            entries.append((bid, h, Commit(h, 0, bid, sigs)))
        out.append(entries)
    return vset, out


async def gather_through_client(path: str, vset, windows) -> list:
    """Each window through `verify_commits_light` on a worker thread,
    over the remote scheduler's classed verifier, one after another."""
    client = RemoteVerifyScheduler(
        path, verifier=SigTagVerifier(), retry_base=0.02, origin="nodeA",
        tracer=obs.Tracer(enabled=False),
    )
    await client.start()
    deadline = time.monotonic() + 15
    while not client.connected and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert client.connected, "client never attached"
    loop = asyncio.get_running_loop()
    classed = client.classed("blocksync")
    try:
        return [
            await loop.run_in_executor(
                None,
                lambda e=entries: vset.verify_commits_light(
                    CHAIN, e, verifier=classed
                ),
            )
            for entries in windows
        ]
    finally:
        await client.stop()


def test_gather_and_the_reply_way_back_land_under_their_requests(svc):
    """Two catch-up windows from one client: each submission's
    `verify.client_gather` (the gather began -> its submit, beside its
    `verify.ingress`), and the first one's `verify.wire_out` (the
    service began writing its reply -> the client had decoded it),
    recorded when the second names it and under the first's `req`."""
    thread, ring = svc
    vset, windows = commit_windows()
    t0 = time.perf_counter()
    verdicts = asyncio.run(
        gather_through_client(thread.server.path, vset, windows)
    )
    assert verdicts == [[True] * 3, [True] * 3]
    time.sleep(0.05)
    recs = [r for r in ring.records() if r.name.startswith("verify.")]
    by = {}
    for r in recs:
        by.setdefault((r.name, r.fields["req"]), []).append(r)
    reqs = sorted({req for _, req in by})
    assert len(reqs) == 2
    first, second = reqs
    end = lambda r: r.t0 + r.dur  # noqa: E731
    for req in reqs:
        (gather,) = by[GATHER, req]
        (ingress,) = by["verify.ingress", req]
        assert "parent" not in gather.fields
        assert gather.fields["origin"] == "nodeA"
        assert gather.fields["n"] == 12
        assert ring.epoch + gather.t0 >= t0
        assert abs(end(gather) - ingress.t0) < 1e-6
    (wire_out,) = by[WIRE_OUT, first]
    assert (WIRE_OUT, second) not in by  # nobody has named it yet
    (service,) = by["verify.service", first]
    (reply,) = by["verify.reply", first]
    assert abs(wire_out.t0 - end(service)) < 1e-6
    assert wire_out.t0 == reply.t0
    assert end(wire_out) <= end(by[GATHER, second][0])  # <= its submit
    assert wire_out.fields["bytes"] == reply.fields["bytes"]
    assert wire_out.fields["origin"] == "nodeA"


async def two_frames(path: str, done_stamp) -> np.ndarray:
    """Frame 9, its reply, then frame 10 naming request
    `done_stamp(t_sent, t_done)[0]` as finished at `[1]`, on one
    connection; the second reply's verdicts."""
    from tendermint_tpu.parallel.verify_service import (
        decode_verdicts, read_frame, write_frame,
    )

    reader, writer = await asyncio.open_unix_connection(path)
    try:
        t_sent = time.perf_counter()
        write_frame(writer, stamped(t_sent, t_sent, t_gather_s=t_sent))
        await writer.drain()
        await asyncio.wait_for(read_frame(reader), 10)
        t_done = time.perf_counter()
        now = time.perf_counter()
        write_frame(
            writer,
            stamped(now, now, req_id=10, prev=done_stamp(t_sent, t_done)),
        )
        await writer.drain()
        cur = _Cursor(await asyncio.wait_for(read_frame(reader), 10))
        cur.take(_HDR.size)
        return decode_verdicts(cur)
    finally:
        writer.close()


@pytest.mark.parametrize(
    "done_stamp, recorded",
    [
        (lambda sent, done: (9, done), True),
        # a request this connection was never answered
        (lambda sent, done: (8, done), False),
        # before the service began writing the reply, or in its future
        (lambda sent, done: (9, sent - 0.001), False),
        (lambda sent, done: (9, done + 5.0), False),
    ],
    ids=["sound", "unknown-req", "before-the-reply", "future"],
)
def test_reply_way_back_needs_a_sound_done_stamp(svc, done_stamp, recorded):
    thread, ring = svc
    verdicts = asyncio.run(two_frames(thread.server.path, done_stamp))
    assert (verdicts == WANT).all()
    time.sleep(0.05)
    out = [r for r in ring.records() if r.name == WIRE_OUT]
    assert len(out) == recorded
    if recorded:
        assert out[0].fields["req"] == 9
    # the rest of both ways stands either way, and nothing failed
    assert [r.name for r in ring.records()].count("verify.ingress") == 2
    assert thread.server.error_frames == 0


# --- (d) tracer off, and the collector --------------------------------------


def test_tracer_off_same_verdicts_empty_ring(tmp_path):
    hooks = list(gc.callbacks)
    ring = obs.Tracer(enabled=False)
    thread = ServiceThread(
        str(tmp_path / "vs.sock"), verifier=SigTagVerifier(), tracer=ring
    )
    thread.start()
    try:
        assert gc.callbacks == hooks  # no collector hook with it off
        verdicts = asyncio.run(
            submit_through_client(thread.server.path, sig_items(12))
        )
        vset, windows = commit_windows()
        asyncio.run(gather_through_client(thread.server.path, vset, windows))
        gc.collect()
    finally:
        thread.stop()
    assert (verdicts == WANT).all()
    assert len(ring) == 0
    assert gc.callbacks == hooks


def test_collector_is_a_span_while_the_ring_is_armed(svc):
    """One `runtime.gc` a collection, whichever thread ran it, with
    `generation`, `collected` and `uncollectable`; the hook goes with
    the service."""
    thread, ring = svc
    assert thread.server._gc_hook in gc.callbacks
    t0 = time.perf_counter()
    garbage = [[] for _ in range(10)]
    for a, b in zip(garbage, garbage[1:] + garbage[:1]):
        a.append(b)  # a cycle only a collection frees
    del garbage, a, b
    gc.collect()
    t1 = time.perf_counter()
    full = [
        r for r in ring.records()
        if r.name == "runtime.gc" and t0 <= ring.epoch + r.t0 <= t1
        and r.fields["generation"] == 2
    ]
    span = full[-1]  # gc.collect()'s, the last thing in the interval
    assert span.fields["collected"] >= 10
    assert span.fields["uncollectable"] == 0
    assert "parent" not in span.fields
    assert 0.0 <= span.dur <= t1 - t0
    hook = thread.server._gc_hook
    thread.stop()
    assert hook not in gc.callbacks


REENTRY = """
import gc, sys, threading, time
from tendermint_tpu import obs

ring = obs.Tracer(enabled=True, ring_size=100000)
t0 = {}

def hook(phase, info):
    if phase == "start":
        t0["t"] = time.perf_counter()
    else:
        ring.add_span("runtime.gc", t0["t"], 0.0)

gc.callbacks.append(hook)
gc.set_threshold(1, 1, 1)  # a collection at every tracked allocation
for i in range(3000):
    ring.add_span("x", 0.0, 0.0, i=i)
    if i % 100 == 0:
        ring.event("e")
        ring.records()
print(sum(r.name == "runtime.gc" for r in ring.records()))
"""


def test_a_collection_inside_a_record_is_recorded_not_a_deadlock():
    """A collection can start while a thread holds the ring's lock (the
    record it is making is an allocation); the hook's span then comes
    from inside that hold."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", REENTRY], cwd=root, capture_output=True,
        text=True, timeout=60,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert int(done.stdout) > 1000


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
    # the routes' replies say which session and where; the ring holds
    # no record of it
    assert session["dir"] == started["dir"]
    assert not any(r.name.startswith("profiler.") for r in ring.records())
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
    assert started["device_trace"]["enabled"]
    # the session's own trace, written as it stops by itself
    xplanes = os.path.join(
        started["dir"], "plugins", "profile", "*", "*.xplane.pb"
    )
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not glob.glob(xplanes):
        time.sleep(0.05)
    assert glob.glob(xplanes)
    status, doc = get(port, "profile_stop")
    assert status == 409 and doc["last"]["id"] == started["id"]
    assert get(port, "profile_start?seconds=99")[1]["seconds"] == 30.0
    assert get(port, "profile_stop")[0] == 200
