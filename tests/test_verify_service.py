"""Verify-as-a-service (parallel/verify_service.py): the split-brain
deployment where one device-owning scheduler process serves a whole
committee over UDS IPC.

Covers the wire protocol, cross-CLIENT round coalescing (the in-proc
proof of the cross-PROCESS design), per-client FIFO, the wire fn lanes
(bls_agg / secp_recover), the degradation contract (socket death
mid-flight resolves every pending submission through the LOCAL verifier
with a structured event — never a hang, never a dropped verdict),
reconnect-with-backoff, the service's stats/dump surface, node assembly
under `[scheduler] remote_socket`, the ipc_round_trip health detector,
the chaos kill/restart liveness property, and the satellite tooling
(testnet generator flag, device-report tenant table). One test crosses
a REAL process boundary via the `python -m tendermint_tpu
verify-service` entrypoint.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
import tracemalloc

import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.crypto.batch_verifier import SigItem
from tendermint_tpu.parallel.scheduler import (
    VerifyScheduler,
    set_default_scheduler,
)
from tendermint_tpu.parallel.verify_service import (
    COL_LENGTHS,
    COL_WIDTH,
    MSG_ERROR,
    MSG_STATS,
    MSG_STATS_RESULT,
    MSG_SUBMIT,
    MSG_SUBMIT_LEGACY,
    RemoteVerifyScheduler,
    ServiceThread,
    WireError,
    _COL,
    _Cursor,
    _HDR,
    _U32,
    decode_fn_results,
    decode_submit,
    decode_submit_fn,
    decode_submit_legacy,
    decode_trace_ctx,
    decode_trace_stamps,
    decode_verdicts,
    encode_error,
    encode_fn_results,
    encode_submit,
    encode_submit_fn,
    encode_verdicts,
    read_frame,
    write_frame,
)

from .wire_legacy import CODEC_CASES, encode_submit_legacy, submit_raw

pytestmark = pytest.mark.verify_service


class SigTagVerifier:
    """Deterministic stub: verdict = sig starts with b'1' (per-item, so
    alignment bugs across coalesced slices are visible)."""

    def verify(self, items):
        return np.array(
            [it.sig[:1] == b"1" for it in items], dtype=bool
        )


class GateVerifier:
    """Blocks every round on an externally-held gate: submissions
    arriving while a round is in flight must coalesce into the next."""

    def __init__(self):
        self.gate = threading.Event()

    def verify(self, items):
        assert self.gate.wait(30), "gate never released"
        return np.ones(len(items), dtype=bool)


def sig_items(n: int, good=lambda i: True) -> list[SigItem]:
    return [
        SigItem(
            b"p" * 32,
            b"m%06d" % i + b"\x00" * 26,
            (b"1" if good(i) else b"0") + b"s" * 63,
        )
        for i in range(n)
    ]


def service(tmp_path, verifier=None, **kw) -> ServiceThread:
    """ServiceThread on a fresh socket. verifier None = the SigTag
    stub (protocol tests); False = the real process verifier (live-net
    tests, whose votes carry genuine signatures)."""
    os.makedirs(str(tmp_path), exist_ok=True)
    path = os.path.join(str(tmp_path), "verify.sock")
    if verifier is None:
        verifier = SigTagVerifier()
    sched = (
        VerifyScheduler()
        if verifier is False
        else VerifyScheduler(verifier=verifier)
    )
    svc = ServiceThread(path, scheduler=sched, **kw)
    svc.start()
    return svc


async def connect(path, **kw) -> RemoteVerifyScheduler:
    remote = RemoteVerifyScheduler(path, retry_base=0.02, **kw)
    await remote.start()
    deadline = time.monotonic() + 15
    while not remote.connected and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert remote.connected, "client never attached"
    return remote


# --- wire protocol ----------------------------------------------------------


def test_wire_codec_roundtrips():
    items = [
        SigItem(b"p" * 32, b"m" * 40, b"s" * 64),
        SigItem(b"q" * 33, b"", b"t" * 64, "secp256k1"),
    ]
    cur = _Cursor(encode_submit(7, items, "blocksync"))
    typ, req = _HDR.unpack(cur.take(_HDR.size))
    assert (typ, req) == (MSG_SUBMIT, 7) == (10, 7)
    got, klass, uniform = decode_submit(cur)
    assert klass == "blocksync" and not uniform
    assert [
        (i.pubkey, i.msg, i.sig, i.key_type) for i in got
    ] == [(i.pubkey, i.msg, i.sig, i.key_type) for i in items]

    verdicts = np.array([True, False, True, True, False] * 3)
    cur = _Cursor(encode_verdicts(9, verdicts))
    cur.take(_HDR.size)
    assert decode_verdicts(cur).tolist() == verdicts.tolist()
    cur = _Cursor(encode_verdicts(9, np.zeros(0, dtype=bool)))
    cur.take(_HDR.size)
    assert decode_verdicts(cur).size == 0

    fn_items = [(b"a" * 96, b"h" * 32, b"c" * 96), (b"d" * 32,)]
    cur = _Cursor(encode_submit_fn(3, "bls_agg", fn_items, "consensus"))
    cur.take(_HDR.size)
    engine, got_fn, klass = decode_submit_fn(cur)
    assert (engine, klass) == ("bls_agg", "consensus")
    assert got_fn == fn_items

    results = [True, False, None, b"addr-bytes"]
    cur = _Cursor(encode_fn_results(4, results))
    cur.take(_HDR.size)
    assert decode_fn_results(cur) == results

    cur = _Cursor(encode_error(5, "boom"))
    typ, req = _HDR.unpack(cur.take(_HDR.size))
    assert (typ, req) == (MSG_ERROR, 5)
    assert cur.bytes32() == b"boom"


def test_wire_codec_rejects_malformed():
    # truncated frame, of either kind
    for encode, decode in (
        (encode_submit, decode_submit),
        (encode_submit_legacy, decode_submit_legacy),
    ):
        cur = _Cursor(encode(1, sig_items(2), "consensus")[:-3])
        cur.take(_HDR.size)
        with pytest.raises(WireError):
            decode(cur)
    # unknown fn-result tag
    cur = _Cursor(_HDR.pack(4, 1) + b"\x00\x00\x00\x01\x09")
    cur.take(_HDR.size)
    with pytest.raises(WireError):
        decode_fn_results(cur)


# --- the columnar submit frame ----------------------------------------------

# "stamped" also carries t_submit, taken from the clock when the test
# runs: the encoder stamps t_encoded from the same clock, which counts
# from boot, so a constant would lie in a fresh machine's future
TRAILERS = {
    "no-trailer": None,
    "old-trailer": (42, 1, "nodeA"),
    "stamped": (42, 1, "nodeA"),
}


def body_of(frame: bytes, typ: int) -> _Cursor:
    cur = _Cursor(frame)
    assert _HDR.unpack(cur.take(_HDR.size)) == (typ, 7)
    return cur


@pytest.mark.parametrize("trailer", TRAILERS)
@pytest.mark.parametrize("case", CODEC_CASES)
def test_submit_codec_round_trips(case, trailer):
    """decode(encode(items)) == items, row for row, with and without
    the trailer behind the columns, for every list the per-item frame
    could carry; and the per-item frame of the same list decodes to the
    same."""
    items, uniform = CODEC_CASES[case]
    ctx = TRAILERS[trailer]
    if trailer == "stamped":
        t_submit_ns = time.perf_counter_ns()
        ctx += (t_submit_ns,)
    frame = encode_submit(7, items, "blocksync", ctx=ctx)
    cur = body_of(frame, MSG_SUBMIT)
    assert decode_submit(cur) == (items, "blocksync", uniform)
    legacy = body_of(
        encode_submit_legacy(7, items, "blocksync", ctx=ctx),
        MSG_SUBMIT_LEGACY,
    )
    assert decode_submit_legacy(legacy) == (items, "blocksync")
    for c in (cur, legacy):
        got_ctx = decode_trace_ctx(c, 7)
        stamps = decode_trace_stamps(c)
        assert c.off == len(c.buf)
        assert got_ctx == (None if ctx is None else (42, 1, "nodeA", 7))
        if trailer == "stamped":
            assert stamps[0] == pytest.approx(t_submit_ns * 1e-9, abs=1e-9)
            assert stamps[0] <= stamps[1] <= time.perf_counter()
        else:
            assert stamps is None
    for it in decode_submit(body_of(frame, MSG_SUBMIT))[0]:
        assert type(it.pubkey) is type(it.msg) is type(it.sig) is bytes


def test_submit_codec_takes_any_buffer_and_defaults_the_key_type():
    """Rows held as bytearray or memoryview are encoded as their bytes,
    and an empty key type still reads as ed25519."""
    items = [
        SigItem(bytearray(b"p" * 32), memoryview(b"m" * 9), b"1" * 64, ""),
        SigItem(b"q" * 32, bytearray(b"n" * 9), memoryview(b"0" * 64)),
    ]
    want = [
        SigItem(b"p" * 32, b"m" * 9, b"1" * 64, "ed25519"),
        SigItem(b"q" * 32, b"n" * 9, b"0" * 64, "ed25519"),
    ]
    for encode, decode, typ in (
        (encode_submit, decode_submit, MSG_SUBMIT),
        (encode_submit_legacy, decode_submit_legacy, MSG_SUBMIT_LEGACY),
    ):
        got = decode(body_of(encode(7, items, "light"), typ))
        assert got[:2] == (want, "light")


def test_columnar_frame_is_smaller_and_says_uniform_once():
    """64 uniform rows: three 9-byte column headers and one key-type
    name, against 16 bytes a row."""
    items = sig_items(64)
    frame = encode_submit(7, items, "consensus")
    legacy = encode_submit_legacy(7, items, "consensus")
    rows = 64 * (32 + 33 + 64)
    head = _HDR.size + 1 + len("consensus") + 4
    assert len(frame) == head + (1 + 1 + len("ed25519")) + 3 * 9 + rows
    assert len(legacy) == head + 64 * (1 + len("ed25519") + 2 + 4 + 2) + rows


def column_ends(frame: bytes, n: int) -> list[tuple[str, int]]:
    """(name, offset) of every boundary of a columnar frame with no
    trailer, walked by the grammar of the module's docstring."""
    off = _HDR.size
    off += 1 + frame[off]
    marks = [("klass", off)]
    off += 4
    marks.append(("n", off))
    k = frame[off]
    off += 1
    for _ in range(k):
        off += 1 + frame[off]
    marks.append(("key-type-names", off))
    if k > 1:
        off += n
        marks.append(("key-type-codes", off))
    for col in ("pubkeys", "msgs", "sigs"):
        form, blob_len = _COL.unpack_from(frame, off)
        off += _COL.size
        marks.append((col + "-head", off))
        off += 4 if form == COL_WIDTH else 4 * n
        marks.append((col + "-sizes", off))
        marks.append((col + "-mid-blob", off + blob_len // 2))
        off += blob_len
        marks.append((col + "-blob", off))
    assert off == len(frame)
    return marks[:-1]  # the last boundary is the whole frame


CUT_FRAME = encode_submit(7, CODEC_CASES["mixed-key-types"][0], "evidence")
CUT_UNIFORM = encode_submit(7, sig_items(6), "evidence")


@pytest.mark.parametrize(
    "frame, name, off",
    [("mixed", name, off) for name, off in column_ends(CUT_FRAME, 20)]
    + [("uniform", name, off) for name, off in column_ends(CUT_UNIFORM, 6)],
    ids=lambda v: str(v),
)
def test_columnar_frame_cut_at_a_boundary_is_a_wire_error(frame, name, off):
    whole = CUT_FRAME if frame == "mixed" else CUT_UNIFORM
    assert 0 < off < len(whole)
    with pytest.raises(WireError):
        decode_submit(body_of(whole[:off], MSG_SUBMIT))


def test_every_prefix_of_a_columnar_frame_is_a_wire_error():
    for off in range(_HDR.size, len(CUT_FRAME)):
        with pytest.raises(WireError):
            decode_submit(body_of(CUT_FRAME[:off], MSG_SUBMIT))


def hostile(n: int, key_types: bytes, *columns: bytes) -> bytes:
    """A columnar frame said by hand, padded to 64 bytes."""
    frame = b"".join(
        (_HDR.pack(MSG_SUBMIT, 7), b"\x01c", _U32.pack(n), key_types)
        + columns
    )
    return frame.ljust(64, b"\x00")


def width_col(blob_len: int, width: int, blob: bytes = b"") -> bytes:
    return _COL.pack(COL_WIDTH, blob_len) + _U32.pack(width) + blob


def lengths_col(blob_len: int, lens: list, blob: bytes = b"") -> bytes:
    return (
        _COL.pack(COL_LENGTHS, blob_len)
        + b"".join(map(_U32.pack, lens))
        + blob
    )


ONE_TYPE = b"\x01\x07ed25519"
BIG = 2**32 - 1

HOSTILE = {
    # n rows of no bytes at all: the one way n could outgrow the frame
    "huge-n-zero-width": hostile(
        BIG, ONE_TYPE, width_col(0, 0), width_col(0, 0), width_col(0, 0)
    ),
    "huge-n-width-form": hostile(BIG, ONE_TYPE, width_col(BIG, 1)),
    "huge-n-lengths-form": hostile(BIG, ONE_TYPE, lengths_col(8, [4, 4])),
    "huge-n-key-type-codes": hostile(BIG, b"\x02\x01a\x01b" + b"\x00" * 40),
    "huge-n-no-key-type": hostile(BIG, b"\x00"),
    "width-times-n-is-not-the-blob": hostile(
        2, ONE_TYPE, width_col(5, 2, b"aaaaa")
    ),
    "width-blob-past-the-frame": hostile(2, ONE_TYPE, width_col(400, 200)),
    "lengths-sum-under-the-blob": hostile(
        2, ONE_TYPE, lengths_col(5, [2, 2], b"aaaaa")
    ),
    "lengths-sum-over-the-blob": hostile(
        2, ONE_TYPE, lengths_col(3, [2, 2], b"aaa")
    ),
    "lengths-sum-wraps-u32": hostile(
        2, ONE_TYPE, lengths_col(1, [BIG, 2], b"a")
    ),
    "unknown-column-form": hostile(
        1, ONE_TYPE, _COL.pack(2, 1) + _U32.pack(1) + b"a"
    ),
    "key-type-code-out-of-range": hostile(
        2, b"\x02\x01a\x01b\x00\x02", width_col(2, 1, b"pp"),
        width_col(2, 1, b"mm"), width_col(2, 1, b"ss"),
    ),
    "bad-utf8-key-type": hostile(
        1, b"\x01\x02\xff\xfe", width_col(1, 1, b"p"),
        width_col(1, 1, b"m"), width_col(1, 1, b"s"),
    ),
}


@pytest.mark.parametrize("name", HOSTILE)
def test_hostile_sizes_are_wire_errors_before_anything_is_built(name):
    """`n` and every length are outside input: a size the frame does
    not bear out is a WireError, and nothing of n's size is allocated
    on the way to it."""
    frame = HOSTILE[name]
    assert len(frame) == 64
    cur = body_of(frame, MSG_SUBMIT)
    tracemalloc.start()
    try:
        with pytest.raises(WireError):
            decode_submit(cur)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 64 * 1024, peak


@pytest.mark.parametrize(
    "case", [c for c, (items, _) in CODEC_CASES.items() if items]
)
def test_legacy_frame_is_served_like_the_columnar_one(tmp_path, case):
    """An older client's per-item frame is decoded and served: the same
    verdicts as the columnar frame of the same items, and the dump
    counts one frame of each kind."""
    items, _ = CODEC_CASES[case]
    svc = service(tmp_path)
    try:
        ctx = (3, 0, "old-node")

        async def run():
            path = svc.server.path
            v1 = await submit_raw(
                path, encode_submit_legacy(7, items, "consensus", ctx=ctx)
            )
            cols = await submit_raw(
                path, encode_submit(7, items, "consensus", ctx=ctx)
            )
            return v1, cols

        v1, cols = asyncio.run(run())
        want = [it.sig[:1] == b"1" for it in items]
        assert v1.tolist() == cols.tolist() == want
        dump = svc.server.dump()
        assert dump["service"]["submit_frames"] == {"cols": 1, "v1": 1}
        assert dump["service"]["error_frames"] == 0
        assert [c["rows"] for c in dump["per_client"].values()] == [
            len(items), len(items),
        ]
    finally:
        svc.stop()


def test_client_sends_the_columnar_frame_and_no_other(tmp_path):
    svc = service(tmp_path)
    try:

        async def run():
            remote = await connect(svc.server.path)
            out = [
                await remote.submit(items, "consensus")
                for items, _ in CODEC_CASES.values()
            ]
            await remote.stop()
            return out

        for got, (items, _) in zip(asyncio.run(run()), CODEC_CASES.values()):
            assert got.tolist() == [it.sig[:1] == b"1" for it in items]
        sent = sum(1 for items, _ in CODEC_CASES.values() if items)
        assert svc.server.submit_frames == {"cols": sent, "v1": 0}
    finally:
        svc.stop()


def test_client_that_meets_an_older_service_verifies_locally(tmp_path):
    """A service from before the columnar frame answers it with its
    `unknown frame type` ERROR frame; the client degrades that
    submission to its local verifier, as on any ERROR frame."""
    path = os.path.join(str(tmp_path), "old.sock")

    async def old_service(reader, writer):
        while (frame := await read_frame(reader)) is not None:
            typ, req_id = _HDR.unpack(frame[: _HDR.size])
            assert typ == MSG_SUBMIT
            write_frame(
                writer, encode_error(req_id, f"unknown frame type {typ}")
            )
            await writer.drain()
        writer.close()

    async def run():
        server = await asyncio.start_unix_server(old_service, path=path)
        remote = await connect(path, verifier=SigTagVerifier())
        try:
            got = await asyncio.wait_for(
                remote.submit(
                    sig_items(6, good=lambda i: i != 2), "consensus"
                ),
                15,
            )
            return got, remote.ipc_stats()
        finally:
            await remote.stop()
            server.close()
            await server.wait_closed()

    got, stats = asyncio.run(run())
    assert got.tolist() == [i != 2 for i in range(6)]
    assert stats["degrades"] == 1


def test_read_frame_caps_oversized(tmp_path):
    """An over-cap length prefix errors the connection instead of
    allocating the attacker's buffer."""

    async def run():
        path = os.path.join(str(tmp_path), "x.sock")

        async def handler(reader, writer):
            try:
                await read_frame(reader)
            except WireError:
                writer.write(b"CAPPED")
                await writer.drain()
            writer.close()

        server = await asyncio.start_unix_server(handler, path=path)
        reader, writer = await asyncio.open_unix_connection(path)
        writer.write((1 << 31).to_bytes(4, "big"))
        await writer.drain()
        got = await asyncio.wait_for(reader.read(16), 10)
        writer.close()
        server.close()
        await server.wait_closed()
        return got

    assert asyncio.run(run()) == b"CAPPED"


# --- submit path ------------------------------------------------------------


def test_submit_verdict_alignment(tmp_path):
    """Per-item verdicts come back aligned to the submission order."""
    svc = service(tmp_path)
    try:

        async def run():
            remote = await connect(svc.server.path)
            v = await remote.submit(
                sig_items(10, good=lambda i: i % 2 == 0), "consensus"
            )
            await remote.stop()
            return v

        v = asyncio.run(run())
        assert v.tolist() == [i % 2 == 0 for i in range(10)]
    finally:
        svc.stop()


def test_cross_client_coalescing(tmp_path):
    """Submissions from DIFFERENT client connections land in one padded
    device round — the cross-process design, proven in-proc: round 1
    blocks on the gate, clients B and C submit meanwhile, and the
    service's ledger shows a round carrying >= 2 submissions."""
    gate = GateVerifier()
    svc = service(tmp_path, verifier=gate)
    try:

        async def run():
            a = await connect(svc.server.path)
            b = await connect(svc.server.path)
            c = await connect(svc.server.path)
            fut_a = asyncio.ensure_future(
                a.submit(sig_items(4), "consensus")
            )
            # wait until A's round is in flight server-side, then land
            # B and C while the gate holds it
            deadline = time.monotonic() + 10
            while (
                sum(
                    s["submissions"]
                    for s in svc.server.client_stats.values()
                )
                < 1
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.01)
            fut_b = asyncio.ensure_future(
                b.submit(sig_items(3), "consensus")
            )
            fut_c = asyncio.ensure_future(
                c.submit(sig_items(5), "consensus")
            )
            deadline = time.monotonic() + 10
            while (
                sum(
                    s["submissions"]
                    for s in svc.server.client_stats.values()
                )
                < 3
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.01)
            gate.gate.set()
            va, vb, vc = await asyncio.wait_for(
                asyncio.gather(fut_a, fut_b, fut_c), 30
            )
            for r in (a, b, c):
                await r.stop()
            return va, vb, vc

        va, vb, vc = asyncio.run(run())
        assert va.all() and vb.all() and vc.all()
        assert len(va) == 4 and len(vb) == 3 and len(vc) == 5
        entries = svc.server.scheduler.ledger.entries()
        coalesced = [e for e in entries if e["submissions"] >= 2]
        assert coalesced, f"no cross-client round in {entries}"
        # three tenants in the bill
        assert len(svc.server.client_stats) == 3
        assert all(
            s["rows"] > 0 for s in svc.server.client_stats.values()
        )
    finally:
        svc.stop()


def test_per_client_fifo(tmp_path):
    """One client's submissions resolve in submission order even when
    the first round blocks and the rest queue behind it."""
    gate = GateVerifier()
    svc = service(tmp_path, verifier=gate)
    try:

        async def run():
            remote = await connect(svc.server.path)
            order = []

            async def one(i):
                await remote.submit(sig_items(2 + i), "consensus")
                order.append(i)

            tasks = [asyncio.ensure_future(one(i)) for i in range(5)]
            await asyncio.sleep(0.2)
            gate.gate.set()
            await asyncio.wait_for(asyncio.gather(*tasks), 30)
            await remote.stop()
            return order

        assert asyncio.run(run()) == [0, 1, 2, 3, 4]
    finally:
        svc.stop()


# --- wire fn lanes ----------------------------------------------------------


def test_fn_lane_bls_agg_real_keys(tmp_path):
    from tendermint_tpu.crypto import bls_signatures as bls

    svc = service(tmp_path)
    try:
        h = b"h" * 32
        items = []
        for i in range(3):
            priv = 6007 + i
            items.append(
                (
                    bls.public_key_to_bytes(bls.pubkey_from_priv(priv)),
                    h,
                    bls.signer_for(priv)(h),
                )
            )
        # forged: valid point, wrong signer for this key
        items.append((items[0][0], h, items[1][2]))

        async def run():
            remote = await connect(svc.server.path)
            res = await remote.submit_wire_fn(
                "bls_agg", items, "consensus"
            )
            await remote.stop()
            return res

        assert asyncio.run(run()) == [True, True, True, False]
    finally:
        svc.stop()


def test_fn_lane_secp_recover(tmp_path):
    import hashlib

    from tendermint_tpu.crypto import secp256k1 as secp

    svc = service(tmp_path)
    try:
        key = secp.PrivKey.from_secret(b"vs-sequencer-key")
        digest = hashlib.sha256(b"blockv2-sign-bytes").digest()
        sig = secp.eth_sign(digest, key.secret)
        addr = secp.eth_address(
            secp.decompress_point(key.public_key().data)
        )

        async def run():
            remote = await connect(svc.server.path)
            res = await remote.submit_wire_fn(
                "secp_recover",
                [(digest, sig), (digest, b"\x00" * 65)],
                "sequencer",
            )
            await remote.stop()
            return res

        got = asyncio.run(run())
        assert got[0] == addr
        assert got[1] == b""
    finally:
        svc.stop()


def test_unknown_fn_engine_degrades_to_fallback(tmp_path):
    svc = service(tmp_path)
    try:

        async def run():
            tracer = obs.Tracer(enabled=True)
            remote = await connect(svc.server.path, tracer=tracer)
            res = await asyncio.wait_for(
                remote.submit_wire_fn(
                    "no_such_engine",
                    [(b"x" * 32,)],
                    "consensus",
                    fallback=lambda: ["local"],
                ),
                15,
            )
            stats = remote.ipc_stats()
            await remote.stop()
            events = [
                r
                for r in tracer.records()
                if r.name == "verify_service.degrade"
            ]
            return res, stats, events

        res, stats, events = asyncio.run(run())
        assert res == ["local"]
        assert stats["degrades"] == 1
        assert events and "service error" in (
            events[0].to_json()["fields"]["reason"]
        )
    finally:
        svc.stop()


# --- degradation contract ---------------------------------------------------


class LocalZeroVerifier:
    """Local fallback with a distinguishable verdict (all-False)."""

    def verify(self, items):
        return np.zeros(len(items), dtype=bool)


def test_kill_mid_flight_degrades_then_reattaches(tmp_path):
    """The acceptance property: a client-side fault (service dies with
    submissions in flight) degrades to local verify with a structured
    event — never a hang, never a dropped verdict — and the client
    re-attaches when the service returns."""
    gate = GateVerifier()
    svc = service(tmp_path, verifier=gate)
    path = svc.server.path
    tracer = obs.Tracer(enabled=True)

    async def run():
        remote = await connect(
            path, verifier=LocalZeroVerifier(), tracer=tracer
        )
        fut = asyncio.ensure_future(
            remote.submit(sig_items(3), "consensus")
        )
        deadline = time.monotonic() + 10
        while (
            not svc.server.client_stats
            or not any(
                s["submissions"]
                for s in svc.server.client_stats.values()
            )
        ) and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        svc.stop()  # mid-flight: the gate still holds the round
        v = await asyncio.wait_for(fut, 15)
        assert v.tolist() == [False, False, False]  # LOCAL verdicts
        stats1 = remote.ipc_stats()
        # while down, submissions run local without waiting
        v2 = await asyncio.wait_for(
            remote.submit(sig_items(2), "consensus"), 15
        )
        assert v2.tolist() == [False, False]
        # service returns on the same socket -> transparent re-attach
        svc2 = ServiceThread(
            path, scheduler=VerifyScheduler(verifier=SigTagVerifier())
        )
        svc2.start()
        try:
            deadline = time.monotonic() + 15
            while (
                not remote.connected and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.02)
            assert remote.connected, "never re-attached"
            v3 = await asyncio.wait_for(
                remote.submit(sig_items(2), "consensus"), 15
            )
            assert v3.tolist() == [True, True]  # REMOTE verdicts again
            stats2 = remote.ipc_stats()
        finally:
            await remote.stop()
            svc2.stop()
        return stats1, stats2

    stats1, stats2 = asyncio.run(run())
    assert stats1["degrades"] == 1 and not stats1["connected"]
    assert stats2["degrades"] == 2
    assert stats2["reconnects"] == 2
    assert stats2["remote_submissions"] > stats1["remote_submissions"]
    events = [
        r.to_json()
        for r in tracer.records()
        if r.name == "verify_service.degrade"
    ]
    assert len(events) == 2
    assert events[0]["fields"]["reason"] == "connection lost mid-flight"
    assert events[1]["fields"]["reason"] == "service unreachable"


def test_unreachable_service_runs_local_without_hang(tmp_path):
    path = os.path.join(str(tmp_path), "never-exists.sock")

    async def run():
        remote = RemoteVerifyScheduler(
            path, verifier=LocalZeroVerifier(), retry_base=0.02
        )
        await remote.start()
        v = await asyncio.wait_for(
            remote.submit(sig_items(4), "consensus"), 10
        )
        stats = remote.ipc_stats()
        await remote.stop()
        return v, stats

    v, stats = asyncio.run(run())
    assert v.tolist() == [False] * 4
    assert stats["degrades"] == 1 and stats["remote_submissions"] == 0


def test_sync_surface_from_worker_thread(tmp_path):
    """submit_sync / the classed adapter route worker-thread callers
    over the wire (the VoteBatcher/blocksync shape); on-loop callers
    degrade to direct local dispatch like the in-proc scheduler."""
    svc = service(tmp_path)
    try:

        async def run():
            remote = await connect(
                svc.server.path, verifier=LocalZeroVerifier()
            )
            loop = asyncio.get_running_loop()
            classed = remote.classed("evidence")
            v_thread = await loop.run_in_executor(
                None, classed.verify, sig_items(3)
            )
            # ON the loop thread: must not block the loop -> local path
            v_loop = remote.submit_sync(sig_items(2), "consensus")
            stats = remote.ipc_stats()
            await remote.stop()
            return v_thread, v_loop, stats

        v_thread, v_loop, stats = asyncio.run(run())
        assert v_thread.tolist() == [True] * 3  # remote stub verdicts
        assert v_loop.tolist() == [False] * 2  # local zero verifier
        assert stats["remote_submissions"] == 1
        per_class = svc.server.scheduler.ledger.summary()["per_class"]
        assert "evidence" in per_class
    finally:
        svc.stop()


# --- stats / dump surface ---------------------------------------------------


def test_stats_frame_and_http_surface(tmp_path):
    svc = service(tmp_path, stats_port=0)
    try:
        port = svc.server.stats_port
        assert port and port > 0

        async def run():
            remote = await connect(svc.server.path)
            await remote.submit(sig_items(5), "consensus")
            # raw STATS frame
            reader, writer = await asyncio.open_unix_connection(
                svc.server.path
            )
            write_frame(writer, _HDR.pack(MSG_STATS, 42))
            await writer.drain()
            frame = await asyncio.wait_for(read_frame(reader), 10)
            cur = _Cursor(frame)
            typ, req = _HDR.unpack(cur.take(_HDR.size))
            assert (typ, req) == (MSG_STATS_RESULT, 42)
            dump = json.loads(cur.bytes32())
            writer.close()

            # HTTP: /metrics + /dump_dispatch_ledger + 404
            async def http_get(target):
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(
                    f"GET {target} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
                )
                await w.drain()
                data = await asyncio.wait_for(r.read(), 10)
                w.close()
                head, _, body = data.partition(b"\r\n\r\n")
                return head.split(b" ", 2)[1], body

            code_m, metrics_body = await http_get("/metrics")
            code_d, dump_body = await http_get("/dump_dispatch_ledger")
            code_404, _ = await http_get("/nope")
            await remote.stop()
            return dump, code_m, metrics_body, code_d, dump_body, code_404

        dump, code_m, metrics_body, code_d, dump_body, code_404 = (
            asyncio.run(run())
        )
        assert dump["summary"]["rows_requested"] >= 5
        assert dump["per_client"]  # tenant table rides the dump
        assert dump["service"]["pid"] == os.getpid()
        assert code_m == b"200" and b"# TYPE" in metrics_body
        assert code_d == b"200"
        http_dump = json.loads(dump_body)
        assert http_dump["summary"]["rows_requested"] >= 5
        assert code_404 == b"404"
    finally:
        svc.stop()


def test_tenant_table_bounded(tmp_path):
    """A flapping client that never submits leaves no entry; past
    max_client_stats the oldest CLOSED billable rows fold into one
    `_closed` aggregate, so the table (and every dump) stays bounded
    while no tenant's spend ever leaves the bill."""
    svc = service(tmp_path)
    try:
        svc.server.max_client_stats = 4

        async def run():
            # 5 idle connect/disconnect cycles: no residue
            for _ in range(5):
                reader, writer = await asyncio.open_unix_connection(
                    svc.server.path
                )
                writer.close()
            await asyncio.sleep(0.2)
            # (the fifth connection may arrive before the first four are
            # seen closed and open the `_closed` aggregate, empty)
            idle_entries = len(
                [k for k in svc.server.client_stats if k != "_closed"]
            )
            # 8 sequential submitting clients: table stays bounded
            for _ in range(8):
                remote = await connect(svc.server.path)
                await remote.submit(sig_items(2), "consensus")
                await remote.stop()
                await asyncio.sleep(0.05)
            await asyncio.sleep(0.2)
            return idle_entries

        idle_entries = asyncio.run(run())
        assert idle_entries == 0
        table = svc.server.client_stats
        assert len(table) <= svc.server.max_client_stats + 2
        agg = table.get("_closed")
        live_rows = sum(
            v["rows"] for k, v in table.items() if k != "_closed"
        )
        folded = agg["rows"] if agg else 0
        assert live_rows + folded == 16  # 8 clients x 2 rows, all billed
        if agg:
            assert agg["clients"] >= 1
    finally:
        svc.stop()


# --- node assembly ----------------------------------------------------------


def test_node_assembly_remote_socket(tmp_path):
    """A full Node under `[scheduler] remote_socket` builds the client,
    binds the ipc health seam, commits heights against the shared
    service, and its verify plane answers over the wire."""
    from tendermint_tpu.node.node import Node, init_files

    from .test_node import make_test_config

    # REAL verifier: the node's votes carry genuine signatures and the
    # service must accept them for the net to advance
    svc = service(tmp_path / "svc", verifier=False)
    try:
        cfg = make_test_config(tmp_path / "node")
        cfg.scheduler.remote_socket = svc.server.path
        init_files(cfg)
        node = Node(cfg)
        assert isinstance(node.verify_scheduler, RemoteVerifyScheduler)
        assert (
            node.health_monitor._remote_scheduler
            is node.verify_scheduler
        )

        from tendermint_tpu.crypto import ed25519

        pk = ed25519.PrivKey.from_secret(b"node-remote-e2e")
        msg = b"explicit-item" + b"\x00" * 19
        good = SigItem(pk.public_key().data, msg, pk.sign(msg))
        forged = SigItem(pk.public_key().data, msg, b"\x00" * 64)

        async def run():
            await node.start()
            try:
                await node.consensus.wait_for_height(2, timeout=90)
                v = await asyncio.wait_for(
                    node.verify_scheduler.submit(
                        [good, forged, good], "consensus"
                    ),
                    60,
                )
                stats = node.verify_scheduler.ipc_stats()
            finally:
                await node.stop()
            return v, stats

        v, stats = asyncio.run(run())
        assert v.tolist() == [True, False, True]  # real verdicts, wire
        assert stats["remote_submissions"] >= 1
        assert stats["connected"]
    finally:
        set_default_scheduler(None)
        svc.stop()


# --- ipc_round_trip health detector -----------------------------------------


def test_ipc_detector_learns_then_flags_drift():
    from tendermint_tpu.obs.health import (
        OK,
        WARN,
        BurnRateSLO,
        IpcRoundTripDetector,
    )

    det = IpcRoundTripDetector(
        BurnRateSLO(
            "ipc_round_trip",
            objective=0.8,
            short_window=30.0,
            long_window=300.0,
        )
    )
    t = 0.0
    for _ in range(16):  # learn a ~2 ms baseline
        t += 1.0
        det.observe_interval(t, mean_rtt_s=0.002)
    assert det.verdict(t) == OK
    assert det.threshold() == pytest.approx(0.008)
    for _ in range(12):  # 10x the learned median, sustained
        t += 1.0
        det.observe_interval(t, mean_rtt_s=0.02)
    assert det.verdict(t) >= WARN
    assert det.last_threshold == pytest.approx(0.008)
    # drifted samples never taught the baseline
    assert det.threshold() == pytest.approx(0.008)


def test_ipc_detector_pages_on_degrades():
    from tendermint_tpu.obs.health import (
        WARN,
        BurnRateSLO,
        IpcRoundTripDetector,
    )

    det = IpcRoundTripDetector(
        BurnRateSLO(
            "ipc_round_trip",
            objective=0.8,
            short_window=30.0,
            long_window=300.0,
        )
    )
    t = 0.0
    for _ in range(8):  # every interval saw local-degrade fallbacks
        t += 1.0
        det.observe_interval(t, mean_rtt_s=None, degrades=3)
    assert det.verdict(t) >= WARN


def test_monitor_remote_scheduler_seam():
    """bind_remote_scheduler pulls ipc_stats() deltas: first sample is
    baseline-only, then interval means + degrades feed the detector and
    the verdict document carries it under the scheduler subsystem."""
    from tendermint_tpu.obs.health import HealthMonitor, WARN

    class FakeRemote:
        def __init__(self):
            self.stats = {
                "rtt_count": 0,
                "rtt_sum_s": 0.0,
                "remote_submissions": 0,
                "degrades": 0,
                "reconnects": 1,
                "connected": True,
            }

        def ipc_stats(self):
            return dict(self.stats)

    mon = HealthMonitor(tracer=obs.Tracer(enabled=True))
    remote = FakeRemote()
    mon.bind_remote_scheduler(remote)
    t = 0.0
    mon.sample(t)  # first sample: baseline only
    det = mon.detectors["ipc_round_trip"]
    assert det.subsystem == "scheduler"
    for _ in range(16):  # healthy 2 ms intervals
        t += 1.0
        remote.stats["rtt_count"] += 10
        remote.stats["rtt_sum_s"] += 10 * 0.002
        mon.sample(t)
    assert mon.subsystem_verdicts(t)["scheduler"] == 0
    for _ in range(12):  # service wedges: degrades + drifted RTT
        t += 1.0
        remote.stats["rtt_count"] += 10
        remote.stats["rtt_sum_s"] += 10 * 0.05
        remote.stats["degrades"] += 4
        mon.sample(t)
    assert mon.detectors["ipc_round_trip"].verdict(t) >= WARN
    assert mon.subsystem_verdicts(t)["scheduler"] >= WARN
    doc = mon.verdict(t)
    assert "ipc_round_trip" in doc["subsystems"]["scheduler"]["detectors"]


def test_health_config_ipc_knob():
    from tendermint_tpu.config.config import HealthConfig
    from tendermint_tpu.obs.health import HealthMonitor

    hc = HealthConfig(ipc_drift_factor=7.0)
    hc.validate_basic()
    mon = HealthMonitor.from_config(hc, stall_ceiling_s=10.0)
    assert mon.ipc_round_trip.drift_factor == 7.0
    with pytest.raises(ValueError):
        HealthConfig(ipc_drift_factor=0.0).validate_basic()


# --- chaos: kill/restart the service under a live net -----------------------


@pytest.mark.chaos
def test_chaos_net_survives_service_kill_and_restart(tmp_path):
    """The liveness property: a 4-validator net whose verify plane
    rides a shared service keeps committing when the service is killed
    mid-net (every node degrades to local verify with structured
    events) and re-attaches when it returns."""
    from tests.chaos_harness import (
        ChaosVerifyService,
        build_chaos_handles,
        start_mesh,
        stop_mesh,
    )

    # REAL verifier: the net's votes carry genuine signatures
    chaos_svc = ChaosVerifyService(
        os.path.join(str(tmp_path), "svc.sock"),
        scheduler=VerifyScheduler(),
    )
    chaos_svc.start()
    tracer = obs.Tracer(enabled=True)
    handles = build_chaos_handles(4)

    async def run():
        remote = await connect(
            chaos_svc.path, verifier=None, tracer=tracer
        )
        set_default_scheduler(remote)
        try:
            await start_mesh(handles)
            # generous: the first service dispatch may pay a bucket
            # compile, and every vote chunk round-trips the socket
            await asyncio.gather(
                *(h.cs.wait_for_height(2, timeout=180) for h in handles)
            )
            sub_before = remote.ipc_stats()["remote_submissions"]
            assert sub_before > 0, "net never verified over the wire"
            # kill mid-net: liveness must not depend on the service
            chaos_svc.kill()
            base = max(h.cs.rs.height for h in handles)
            await asyncio.gather(
                *(
                    h.cs.wait_for_height(base + 2, timeout=90)
                    for h in handles
                )
            )
            stats_down = remote.ipc_stats()
            # service returns: the clients re-attach and resume
            chaos_svc.restart()
            deadline = time.monotonic() + 30
            while (
                not remote.connected and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.05)
            assert remote.connected, "client never re-attached"
            base = max(h.cs.rs.height for h in handles)
            await asyncio.gather(
                *(
                    h.cs.wait_for_height(base + 2, timeout=90)
                    for h in handles
                )
            )
            stats_up = remote.ipc_stats()
            return stats_down, stats_up
        finally:
            await stop_mesh(handles)
            set_default_scheduler(None)
            await remote.stop()

    try:
        stats_down, stats_up = asyncio.run(run())
    finally:
        chaos_svc.kill()
    assert stats_down["degrades"] > 0, "kill never exercised degrade"
    assert stats_up["reconnects"] >= 2
    assert (
        stats_up["remote_submissions"]
        > stats_down["remote_submissions"]
    ), "no remote submissions after re-attach"
    events = [
        r for r in tracer.records() if r.name == "verify_service.degrade"
    ]
    assert events, "degrades left no structured event"


# --- real process boundary ---------------------------------------------------


def test_cli_service_process_end_to_end(tmp_path):
    """`python -m tendermint_tpu verify-service` across a REAL process
    boundary: readiness handshake, real ed25519 verdicts over the wire,
    and the service-side dump."""
    from tendermint_tpu.crypto import ed25519
    from tools.verify_service_bench import _service_dump, _spawn_service

    sock = os.path.join(str(tmp_path), "cli.sock")
    proc = _spawn_service(sock, max_batch=256, timeout=120)
    try:

        async def run():
            remote = await connect(sock)
            pk = ed25519.PrivKey.from_secret(b"cli-e2e")
            msg = b"vote-bytes" + b"\x00" * 22
            good = SigItem(pk.public_key().data, msg, pk.sign(msg))
            bad = SigItem(pk.public_key().data, msg, b"\x00" * 64)
            v = await asyncio.wait_for(
                remote.submit([good, bad, good], "consensus"), 240
            )
            dump = await _service_dump(sock)
            await remote.stop()
            return v, dump

        v, dump = asyncio.run(run())
        assert v.tolist() == [True, False, True]
        assert dump["summary"]["rows_requested"] >= 3
        assert dump["per_client"]
        assert dump["service"]["pid"] == proc.pid
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# --- satellites: tooling -----------------------------------------------------


def test_testnet_generator_stamps_remote_socket(tmp_path):
    import socket as socket_mod

    from tendermint_tpu.config import Config
    from tools.testnet_generator import generate_manifest, materialize

    def free_ports(k):
        socks, ports = [], []
        for _ in range(k):
            s = socket_mod.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        return ports

    manifest = generate_manifest(11, n_validators=2)
    layout = materialize(
        manifest,
        str(tmp_path / "net"),
        free_ports,
        verify_service="shared/verify.sock",
    )
    assert layout
    expect = os.path.abspath("shared/verify.sock")
    for spec in layout.values():
        cfg = Config.load(spec["home"])
        assert cfg.scheduler.remote_socket == expect


def test_device_report_renders_tenant_table():
    from tools.device_report import extract_summary, report_text

    dump = {
        "enabled": True,
        "service": {"socket": "/tmp/v.sock", "pid": 1},
        "summary": {
            "rounds": 10,
            "fn_rounds": 2,
            "sharded_rounds": 0,
            "rows_requested": 90,
            "rows_dispatched": 128,
            "padding_rows": 38,
            "fill_ratio_p50": 0.7,
            "fill_ratio_p95": 0.9,
            "requests_per_dispatch": 2.5,
            "device_seconds": 0.5,
            "queue_wait_seconds": 0.1,
            "host_prep_seconds": 0.01,
            "per_class": {
                "consensus": {
                    "rows": 90,
                    "device_seconds": 0.5,
                    "device_share": 1.0,
                    "rounds": 10,
                    "submissions": 25,
                    "queue_wait_seconds": 0.1,
                }
            },
            "by_bucket": {},
        },
        "per_client": {
            "client-1": {
                "submissions": 20,
                "rows": 70,
                "fn_submissions": 2,
                "fn_items": 8,
            },
            "client-2": {
                "submissions": 5,
                "rows": 20,
                "fn_submissions": 0,
                "fn_items": 0,
            },
        },
    }
    summary = extract_summary(dump)
    assert summary["per_client"]
    text = report_text(summary, name="service")
    assert "tenants (2 clients" in text
    assert "client-1" in text and "client-2" in text
    # biggest tenant first
    assert text.index("client-1") < text.index("client-2")


# --- the multi-process harness itself ----------------------------------------


@pytest.mark.slow
def test_verify_service_bench_harness_smoke():
    """run_size across real OS processes at a tiny committee: real
    ed25519 + BLS verdicts, zero degrades, service ledger attached."""
    from tools.verify_service_bench import run_size

    row = run_size(2, heights=1, warm=1, max_procs=2)
    assert "error" not in row, row
    assert row["sig_verify"] == "real"
    assert row["processes"] == 2
    assert row["degrades"] == 0
    # measured window only (the warm height is excluded by design)
    assert row["remote_submissions"] >= 4  # 2 nodes x 2 lanes x 1 h
    assert row["service_ledger"]["rows_requested"] >= 8  # incl. warm
    assert row["per_client_tenants"] >= 2
