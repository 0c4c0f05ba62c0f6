"""The per-item submit frame (SUBMIT_LEGACY, type 1) as clients older
than the columnar frame wrote it. The program only decodes that frame
now; this encoder crafts it for the tests that hold the service to it.
Beside it, the item lists the codec tests run both frames over.
"""

from __future__ import annotations

import asyncio

import numpy as np

from tendermint_tpu.crypto.batch_verifier import SigItem
from tendermint_tpu.parallel.verify_service import (
    MSG_SUBMIT_LEGACY,
    MSG_VERDICTS,
    WireError,
    _Cursor,
    _HDR,
    _U16,
    _U32,
    _put_bytes32,
    _put_str8,
    _put_trace_ctx,
    decode_verdicts,
    read_frame,
    write_frame,
)


def _put_bytes16(out: list, b: bytes) -> None:
    if len(b) > 0xFFFF:
        raise WireError(f"bytes16 too long: {len(b)}")
    out.append(_U16.pack(len(b)))
    out.append(b)


def encode_submit_legacy(
    req_id: int, items: list[SigItem], klass: str, ctx=None
) -> bytes:
    out = [_HDR.pack(MSG_SUBMIT_LEGACY, req_id)]
    _put_str8(out, klass)
    out.append(_U32.pack(len(items)))
    for it in items:
        _put_str8(out, it.key_type)
        _put_bytes16(out, bytes(it.pubkey))
        _put_bytes32(out, bytes(it.msg))
        _put_bytes16(out, bytes(it.sig))
    _put_trace_ctx(out, ctx)
    return b"".join(out)


async def submit_raw(path: str, payload: bytes) -> np.ndarray:
    """One hand-made frame over the socket; the verdicts of the reply."""
    reader, writer = await asyncio.open_unix_connection(path)
    try:
        write_frame(writer, payload)
        await writer.drain()
        cur = _Cursor(await asyncio.wait_for(read_frame(reader), 10))
        typ, _ = _HDR.unpack(cur.take(_HDR.size))
        assert typ == MSG_VERDICTS
        return decode_verdicts(cur)
    finally:
        writer.close()


def _sig(i: int) -> bytes:
    # the stub verifiers of the service tests read a verdict off the
    # signature's first byte
    return (b"1" if i % 3 else b"0") + bytes([i % 251]) * 63


# name -> (items, whether every byte column takes the single-width form)
CODEC_CASES = {
    "uniform-ed25519": (
        [
            SigItem(bytes([i % 7]) * 32, b"vote-%04d" % i + b"\x00" * 101,
                    _sig(i))
            for i in range(40)
        ],
        True,
    ),
    "uniform-keys-varying-msgs": (
        [
            SigItem(bytes([i % 7]) * 32, b"m" * (110 + i % 7), _sig(i))
            for i in range(40)
        ],
        False,
    ),
    "mixed-key-types": (
        [
            SigItem(b"\x02" + bytes([i]) * 32, b"tx-%d" % i, _sig(i),
                    "secp256k1")
            if i % 3 == 0
            else SigItem(bytes([i]) * 32, b"vote-%d" % i, _sig(i))
            for i in range(20)
        ],
        False,
    ),
    "empty-fields": (
        [
            SigItem(b"", b"m" * 40, _sig(0)),
            SigItem(b"p" * 32, b"", _sig(1)),
            SigItem(b"p" * 32, b"m" * 40, b""),
            SigItem(b"", b"", b""),
            SigItem(b"p" * 32, b"m" * 40, _sig(4)),
        ],
        False,
    ),
    "all-fields-empty": ([SigItem(b"", b"", b"")] * 5, False),
    "short-fields": (
        [
            SigItem(b"p" * 31, b"m", _sig(0)),
            SigItem(b"p" * 32, b"mm", _sig(1)[:63]),
            SigItem(b"p", b"mmm", b"1"),
        ],
        False,
    ),
    "over-long-fields": (
        [
            SigItem(b"p" * 32, b"m" * 40, _sig(0)),
            SigItem(b"p" * 65535, b"m" * 70000, _sig(1) * 1023),
            SigItem(b"p" * 34, b"m" * 40, _sig(2) + b"x"),
        ],
        False,
    ),
    "zero-items": ([], True),
    "one-item": ([SigItem(b"p" * 32, b"m" * 113, _sig(1))], True),
    "three-key-types": (
        [
            SigItem(b"k" * 32, b"m" * 8, _sig(i), kt)
            for i, kt in enumerate(
                ["sr25519", "ed25519", "secp256k1", "ed25519", "sr25519"]
            )
        ],
        True,
    ),
}
