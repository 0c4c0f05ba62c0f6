"""The canonical precommit sign-bytes, written out plainly.

A validator signs `uvarint(len(body)) || body`, where body is the
protobuf encoding of

    1: type      varint   (2 = precommit)
    2: height    sfixed64
    3: round     sfixed64 (this system writes it at round 0 too)
    4: block_id  message {1: hash bytes, 2: {1: total varint, 2: hash bytes}}
    5: timestamp message {1: seconds varint, 2: nanos varint}
    6: chain_id  bytes

with proto3's rule that a zero varint or empty bytes field is left out.
Within one commit only field 5 differs from signer to signer.

This is the benchmark's own encoder: the load generator signs these
bytes, the reference verifies over them, and neither asks the program
how it encodes a vote. A program whose encoder drifts from this one
rejects every honest signature, and `correct` comes out false.
"""

from __future__ import annotations

import struct

PRECOMMIT = 2


def uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _varint_field(num: int, value: int) -> bytes:
    return uvarint(num << 3) + uvarint(value) if value else b""


def _bytes_field(num: int, value: bytes) -> bytes:
    return uvarint(num << 3 | 2) + uvarint(len(value)) + value


def commit_parts(
    chain_id: str, height: int, round_: int, block_hash: bytes,
    parts_total: int, parts_hash: bytes,
) -> tuple[bytes, bytes]:
    """(prefix, suffix) of the body around the timestamp field."""
    block_id = _bytes_field(1, block_hash) + _bytes_field(
        2, _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    )
    prefix = (
        _varint_field(1, PRECOMMIT)
        + uvarint(2 << 3 | 1) + struct.pack("<q", height)
        + uvarint(3 << 3 | 1) + struct.pack("<q", round_)
        + _bytes_field(4, block_id)
    )
    return prefix, _bytes_field(6, chain_id.encode())


def sign_bytes(prefix: bytes, suffix: bytes, timestamp_ns: int) -> bytes:
    seconds, nanos = divmod(timestamp_ns, 1_000_000_000)
    body = (
        prefix
        + _bytes_field(5, _varint_field(1, seconds) + _varint_field(2, nanos))
        + suffix
    )
    return uvarint(len(body)) + body
