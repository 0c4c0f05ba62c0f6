"""Batched secp256k1 ECDSA verification (BASELINE config 4).

The reference verifies secp256k1 validator signatures through native btcec
(crypto/secp256k1/secp256k1.go:190-215); the framework's pure-Python path
(crypto/secp256k1.py) is correct but ~8 ms per signature. A batch is
verified in two halves:

- `prep_digest_batch`, the consensus rules by columns: signature parse,
  r/s range and low-S, the key's point from a `KeyCache` (decompressed
  once per key), u1 = z/s and u2 = r/s mod n with one inversion for the
  whole batch. Its output is what the verifying half reads.
- `start`, the verifying half: R = u1*G + u2*Q per row in
  native/secp256k1.cpp, in chunks on the caller's pool of threads (a
  ctypes call releases the GIL), or the pure-Python ladder where no
  compiler is available.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from ..obs import default_tracer
from ._native_build import NativeLoader
from .secp256k1 import N, _HALF_N, _double_mul, decompress_point

_loader = NativeLoader(
    "_tmsecp.so", "secp256k1.cpp", funcs=("tmsecp_shamir_batch",)
)

# keys a verifier's KeyCache holds: a validator set's keys many times
# over, at about 200 bytes of host memory each
KEY_CACHE_CAPACITY = 16384

# threads a pool for `start` takes (a verifier owns one), and the
# fewest rows worth a thread of their own: a 4,096-row batch is eight
# chunks of 512
HOST_THREADS = min(8, len(os.sched_getaffinity(0)))
CHUNK_ROWS_MIN = 64


def native_lib() -> Optional[ctypes.CDLL]:
    return _loader.get()


class KeyCache:
    """Compressed key -> the point it names as 64 bytes (affine x || y,
    big-endian, as the native step reads it), or None for a key that
    names no point. Bounded: past `capacity` keys the oldest entry gives
    way. A key already held costs one dict lookup; thread-safe (a miss
    inserts under a lock)."""

    def __init__(self, capacity: int = KEY_CACHE_CAPACITY):
        self._capacity = max(1, capacity)
        self._points: dict[bytes, Optional[bytes]] = {}
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._points)

    def decompress(self, pub: bytes) -> Optional[bytes]:
        """The point of a key not held yet: decompressed and kept."""
        pt = decompress_point(pub)
        q = None if pt is None else (
            pt[0].to_bytes(32, "big") + pt[1].to_bytes(32, "big")
        )
        with self._lock:
            if pub not in self._points:
                while len(self._points) >= self._capacity:
                    del self._points[next(iter(self._points))]
                self._points[pub] = q
        return q


_MISSING = object()


@dataclass
class SecpBatch:
    """The prepared rows of a batch of `n`: `rows` are the indices of
    the rows the rules let through, and row j of each array is row
    `rows[j]`'s: `q` [m, 64] the key's point, `u1`, `u2`, `r` [m, 32],
    all big-endian bytes. A row not in `rows` is refused. `cached` and
    `decompressed` count the rows whose key the cache held or had to
    decompress."""

    n: int
    rows: np.ndarray
    q: np.ndarray
    u1: np.ndarray
    u2: np.ndarray
    r: np.ndarray
    cached: int
    decompressed: int


def _column(chunks: list, width: int) -> np.ndarray:
    return np.frombuffer(b"".join(chunks), dtype=np.uint8).reshape(-1, width)


def prep_digest_batch(
    pub33s: list[bytes],
    digests: list[bytes],
    sigs: list[bytes],
    keys: Optional[KeyCache] = None,
) -> SecpBatch:
    """The consensus-critical host half, by columns: signature parse,
    r/s range + low-S malleability check (reference
    crypto/secp256k1/secp256k1.go:199-210), the key's point (from
    `keys`, decompressed on a miss; a fresh cache without one), and
    u1/u2. ONE implementation — both of `start`'s ways and every caller
    read it. The s of the rows that pass are inverted together
    (Montgomery's trick: one `pow` a batch, three products a row). u1 =
    u2 = 0 (R at infinity) cannot pass: u2 = r/s with 1 <= r < n prime
    is never 0."""
    if keys is None:
        keys = KeyCache()
    held = keys._points.get
    from_bytes = int.from_bytes
    at: list[int] = []
    qs: list[bytes] = []
    rb: list[bytes] = []
    rs: list[int] = []
    ss: list[int] = []
    zs: list[int] = []
    cached = decompressed = 0
    for i, sig in enumerate(sigs):
        if len(sig) != 64:
            continue
        r = from_bytes(sig[:32], "big")
        s = from_bytes(sig[32:], "big")
        if not (1 <= r < N and 1 <= s <= _HALF_N):
            continue
        pub = pub33s[i]
        q = held(pub, _MISSING)
        if q is _MISSING:
            decompressed += 1
            q = keys.decompress(pub)
        else:
            cached += 1
        if q is None:
            continue
        at.append(i)
        qs.append(q)
        rb.append(sig[:32])
        rs.append(r)
        ss.append(s)
        zs.append(from_bytes(digests[i], "big"))
    m = len(ss)
    prefix = [0] * m
    acc = 1
    for j, s in enumerate(ss):
        prefix[j] = acc
        acc = acc * s % N
    inv = pow(acc, -1, N)
    u1s = [b""] * m
    u2s = [b""] * m
    for j in range(m - 1, -1, -1):
        si = inv * prefix[j] % N
        inv = inv * ss[j] % N
        u1s[j] = (zs[j] * si % N).to_bytes(32, "big")
        u2s[j] = (rs[j] * si % N).to_bytes(32, "big")
    return SecpBatch(
        n=len(sigs),
        rows=np.array(at, dtype=np.intp),
        q=_column(qs, 64),
        u1=_column(u1s, 32),
        u2=_column(u2s, 32),
        r=_column(rb, 32),
        cached=cached,
        decompressed=decompressed,
    )


def prep_digest_item(pub33: bytes, digest: bytes, sig: bytes):
    """One row of `prep_digest_batch`: (r, (x, y), u1, u2) as ints, or
    None for a row that is definitively invalid."""
    b = prep_digest_batch([pub33], [digest], [sig])
    if not len(b.rows):
        return None
    q = b.q[0].tobytes()
    return (
        int.from_bytes(b.r[0].tobytes(), "big"),
        (int.from_bytes(q[:32], "big"), int.from_bytes(q[32:], "big")),
        int.from_bytes(b.u1[0].tobytes(), "big"),
        int.from_bytes(b.u2[0].tobytes(), "big"),
    )


def prep_msgs(
    pub33s: list[bytes],
    msgs: list[bytes],
    sigs: list[bytes],
    keys: Optional[KeyCache] = None,
) -> SecpBatch:
    """`prep_digest_batch` over SHA-256(msg) (PubKey.verify semantics),
    the rules traced as `crypto.secp_prep` (`rows`; `cached`,
    `decompressed`: rows whose key the cache held or had to
    decompress)."""
    sha256 = hashlib.sha256
    digests = [sha256(m).digest() for m in msgs]
    with default_tracer().span("crypto.secp_prep", rows=len(sigs)) as span:
        batch = prep_digest_batch(pub33s, digests, sigs, keys)
        span.set(cached=batch.cached, decompressed=batch.decompressed)
    return batch


def _python_ladder(batch: SecpBatch) -> np.ndarray:
    out = np.zeros(batch.n, dtype=bool)
    for j, i in enumerate(batch.rows):
        q = batch.q[j].tobytes()
        pt = _double_mul(
            int.from_bytes(batch.u1[j].tobytes(), "big"),
            int.from_bytes(batch.u2[j].tobytes(), "big"),
            (int.from_bytes(q[:32], "big"), int.from_bytes(q[32:], "big"), 1),
        )
        out[i] = pt is not None and pt[0] % N == int.from_bytes(
            batch.r[j].tobytes(), "big"
        )
    return out


def _shamir(lib, batch: SecpBatch, lo: int, hi: int):
    """x(u1*G + u2*Q) of rows lo..hi as [hi - lo, 33] (a leading 1, or 0
    at infinity), or None where the native step refused its input."""
    k = hi - lo
    out = ctypes.create_string_buffer(33 * k)
    rc = lib.tmsecp_shamir_batch(
        batch.q[lo:hi].tobytes(), batch.u1[lo:hi].tobytes(),
        batch.u2[lo:hi].tobytes(), out, k,
    )
    if rc != 0:
        return None
    return np.frombuffer(out.raw, dtype=np.uint8).reshape(k, 33)


def start(
    batch: SecpBatch, pool: Optional[ThreadPoolExecutor] = None
) -> Callable[[], np.ndarray]:
    """Start verifying a prepared batch; returns the call that waits for
    its [n] bool verdicts, x(u1*G + u2*Q) mod n == r per row that passed
    the rules. With a `pool`, the native step runs in chunks of at least
    CHUNK_ROWS_MIN rows on its threads, so the caller can run other work
    (the round's device program) until it waits; without one, or for a
    batch of one chunk, it runs when it is waited for."""
    m = len(batch.rows)
    lib = native_lib() if m else None
    if lib is None:
        return lambda: _python_ladder(batch)
    step = max(CHUNK_ROWS_MIN, -(-m // HOST_THREADS))
    bounds = [(lo, min(lo + step, m)) for lo in range(0, m, step)]
    if pool is None or len(bounds) == 1:
        parts = [
            lambda lo=lo, hi=hi: _shamir(lib, batch, lo, hi)
            for lo, hi in bounds
        ]
    else:
        parts = [
            pool.submit(_shamir, lib, batch, lo, hi).result
            for lo, hi in bounds
        ]

    def wait() -> np.ndarray:
        chunks = [part() for part in parts]
        if any(c is None for c in chunks):
            # a coordinate out of range slipped through: python
            return _python_ladder(batch)
        res = np.concatenate(chunks)
        finite = res[:, 0] == 1
        ok = finite & (res[:, 1:] == batch.r).all(axis=1)
        # x in [n, p): x mod n == r is x - n == r (about 2^-128 of
        # genuine signatures; otherwise a row that fails)
        for j in np.flatnonzero(finite & ~ok):
            x = int.from_bytes(res[j, 1:].tobytes(), "big")
            ok[j] = x - N == int.from_bytes(batch.r[j].tobytes(), "big")
        out = np.zeros(batch.n, dtype=bool)
        out[batch.rows] = ok
        return out

    return wait


def verify_msgs_batch(
    pub33s: list[bytes],
    msgs: list[bytes],
    sigs: list[bytes],
    keys: Optional[KeyCache] = None,
) -> list[bool]:
    """Per-item verdicts for (compressed pubkey, message, 64-byte R||S)
    triples — PubKey.verify semantics (sha256 digest, low-S enforced)."""
    return start(prep_msgs(pub33s, msgs, sigs, keys))().tolist()
