"""The plain reference for a secp256k1 validator's row: what one ECDSA
verification has to answer, as the reference node decides it
(crypto/secp256k1/secp256k1.go: VerifySignature, Address).

Semantics (a mixed configuration's `guarantees`): the signature is the
64 bytes `R || S`, big-endian, over SHA-256 of the sign-bytes; the key
is the 33-byte compressed point. Rejected: a key that is not 33 bytes,
does not start with 02 or 03 or is not on the curve; a signature that
is not 64 bytes; `r` or `s` outside [1, N); `s > N/2` (the malleable
twin `(r, N - s)` of a valid signature is itself valid ECDSA, and the
reference node refuses it). The address is RIPEMD160(SHA256(key)).

Two implementations of the curve equation, held against each other by
`tests/test_reference.py` and, in every run, on a seeded sample:

- `verify_plain`: pure Python, affine arithmetic over Python integers,
  written from SEC 1 section 4.1.4. About 10 ms a signature, so it
  checks a sample.
- `verify`: the length and range rules here in Python, the curve
  equation by OpenSSL through `cryptography`. About 0.1 ms a signature,
  so it checks every row a window served.

`low_s=False` is the CONTROL, not a reference: it drops the `s <= N/2`
rule (one guarantee broken). `correct` has to come out false when its
answers stand in the program's place.

Nothing here imports the program.
"""

from __future__ import annotations

import hashlib

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    encode_dss_signature,
)

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
B = 7
G = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def _add(a, b):
    """Affine addition on y^2 = x^3 + 7; None is the point at infinity."""
    if a is None:
        return b
    if b is None:
        return a
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        m = 3 * x1 * x1 * pow(2 * y1, P - 2, P) % P
    else:
        m = (y2 - y1) * pow(x2 - x1, P - 2, P) % P
    x3 = (m * m - x1 - x2) % P
    return x3, (m * (x1 - x3) - y1) % P


def _mul(k: int, pt):
    q = None
    while k > 0:
        if k & 1:
            q = _add(q, pt)
        pt = _add(pt, pt)
        k >>= 1
    return q


def decode_key(pub: bytes):
    """The point of a 33-byte compressed key, or None."""
    if len(pub) != 33 or pub[0] not in (2, 3):
        return None
    x = int.from_bytes(pub[1:], "big")
    if x >= P:
        return None
    y2 = (x * x * x + B) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    return x, (y if y & 1 == pub[0] & 1 else P - y)


def _scalars(sig: bytes, low_s: bool):
    """(r, s) of a 64-byte `R || S` that passes the range rules."""
    if len(sig) != 64:
        return None
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (1 <= r < N and 1 <= s < N):
        return None
    if low_s and s > N // 2:
        return None
    return r, s


def verify_plain(pub: bytes, msg: bytes, sig: bytes) -> bool:
    q = decode_key(pub)
    rs = _scalars(sig, True)
    if q is None or rs is None:
        return False
    r, s = rs
    z = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    w = pow(s, N - 2, N)
    pt = _add(_mul(z * w % N, G), _mul(r * w % N, q))
    return pt is not None and pt[0] % N == r


def verify(pub: bytes, msg: bytes, sig: bytes, low_s: bool = True) -> bool:
    rs = _scalars(sig, low_s)
    if rs is None or len(pub) != 33 or pub[0] not in (2, 3):
        return False
    try:
        key = ec.EllipticCurvePublicKey.from_encoded_point(
            ec.SECP256K1(), pub
        )
        key.verify(
            encode_dss_signature(*rs), msg, ec.ECDSA(hashes.SHA256())
        )
    except (InvalidSignature, ValueError):
        return False
    return True


def address(pub: bytes) -> bytes:
    return hashlib.new("ripemd160", hashlib.sha256(pub).digest()).digest()
