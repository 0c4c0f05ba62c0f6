"""The plain reference: what one ed25519 verification and one commit's
quorum tally have to answer.

Semantics (the configurations' `guarantees`): cofactorless ed25519 as
RFC 8032 section 5.1.7 decides it, with the encoded-point comparison
`encode([s]B - [k]A) == R`; a public key that is not 32 bytes or a
signature that is not 64 bytes is rejected; s >= L is rejected; a commit
stands when the power of its valid signatures is more than two thirds
of the total.

Two implementations of the curve equation, held against each other by
`tests/test_reference.py` and, in every run, on a seeded sample:

- `verify_rfc8032`: pure Python, written after the RFC's own sample
  code. About 4 ms a signature, so it checks a sample.
- `verify`: the length and range rules here in Python, the curve
  equation by OpenSSL through `cryptography`. About 0.1 ms a signature,
  so it checks every row a window served.

`s_range=False` is the CONTROL, not a reference: it drops the s < L
rule (one guarantee broken), which accepts the malleable twin
(R, s + L) of a valid signature. `correct` has to come out false when
its answers stand in the program's place.

Nothing here imports the program.
"""

from __future__ import annotations

import hashlib

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)


def _add(a, b):
    x1, y1, z1, t1 = a
    x2, y2, z2, t2 = b
    aa = (y1 - x1) * (y2 - x2) % P
    bb = (y1 + x1) * (y2 + x2) % P
    cc = 2 * t1 * t2 * D % P
    dd = 2 * z1 * z2 % P
    e, f, g, h = bb - aa, dd - cc, dd + cc, bb + aa
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _mul(s: int, pt):
    q = (0, 1, 1, 0)
    while s > 0:
        if s & 1:
            q = _add(q, pt)
        pt = _add(pt, pt)
        s >>= 1
    return q


def _decode(b: bytes):
    y = int.from_bytes(b, "little")
    sign = y >> 255
    y &= (1 << 255) - 1
    if y >= P:
        return None
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    if x2 == 0:
        return None if sign else (0, y, 1, 0)
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * SQRT_M1 % P
    if (x * x - x2) % P != 0:
        return None
    if (x & 1) != sign:
        x = P - x
    return (x, y, 1, x * y % P)


def _encode(pt) -> bytes:
    x, y, z, _ = pt
    zi = pow(z, P - 2, P)
    x, y = x * zi % P, y * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


_GY = 4 * pow(5, P - 2, P) % P
BASE = _decode(_GY.to_bytes(32, "little"))


def verify_rfc8032(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(pub) != 32 or len(sig) != 64:
        return False
    a = _decode(pub)
    if a is None:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    k = int.from_bytes(
        hashlib.sha512(sig[:32] + pub + msg).digest(), "little"
    ) % L
    neg_a = (P - a[0], a[1], a[2], P - a[3])
    return _encode(_add(_mul(s, BASE), _mul(k, neg_a))) == sig[:32]


def verify(pub: bytes, msg: bytes, sig: bytes, s_range: bool = True) -> bool:
    if len(pub) != 32 or len(sig) != 64:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        if s_range:
            return False
        sig = sig[:32] + (s % L).to_bytes(32, "little")
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


def quorum(valid: list, powers: list) -> bool:
    """More than two thirds of the total power signed validly."""
    tallied = sum(p for ok, p in zip(valid, powers) if ok)
    return tallied > sum(powers) * 2 // 3
