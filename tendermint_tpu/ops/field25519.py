"""GF(2^255-19) arithmetic for TPU: batched, radix-2^8 limbs, int32 lanes.

Design notes (TPU-first, not a port — the reference uses x/crypto's 64-bit
assembly field ops, crypto/ed25519/ed25519.go:148-162 in /root/reference):

- A field element is ``[..., 32, B] int32``: 32 little-endian limbs of 8
  bits on the second-minor axis, the batch on the minor-most one. The
  batch is what fills a vector register's 128 lanes; everything an
  operation does *along the limbs* (the shifted partial products of `mul`,
  the shift of a carry pass) is then a move between rows of a register
  tile and nothing crosses lanes. With the limbs minor-most (the layout
  until PR 32) a quarter of the lanes held data and every such move was a
  lane shuffle or an update-slice: 2.6-6.7x the device time of a verify
  program on a TPU v5e (PERF.md §6, PR 32).
  A constant is ``[32, 1]`` and broadcasts over the batch.
- Radix 2^8 is chosen so that (a) encoded byte strings ARE the limb
  vector (`from_bytes` / `to_bytes` are where a row-major ``[B, 32]`` byte
  operand is turned onto the lanes and back), (b) limb products fit
  comfortably in int32 (no 64-bit multiplies — TPUs have no native
  int64), and (c) a future Pallas kernel can feed the limbs to the MXU as
  int8 operands with int32 accumulation.
- "Loose" invariant: every public op accepts and returns limbs in [0, 2^9).
  Products then satisfy: conv term < 2^18, 32-term column sum < 2^23, and
  after the fold by 38 (2^256 ≡ 38 mod p) columns stay < 39*2^23 < 2^28.3,
  inside int32.
- Carries are vectorized shift-add passes (4 passes restore the loose
  invariant after a multiply — see bound chain in `_carry_pass`); the exact
  sequential carry (lax.scan over the 32 limbs) is reserved for
  canonicalization, which only happens at batch boundaries.
- No data-dependent control flow: everything is select/mask based, so the
  whole verifier jits to one XLA program.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

NLIMBS = 32
P = 2**255 - 19
_LIMB_AXIS = -2  # the batch is axis -1
_LANES = 128  # a vector register's width


def _column(limbs) -> np.ndarray:
    """Host limb vector [32] -> the [32, 1] constant that broadcasts over
    the batch."""
    return np.asarray(limbs, dtype=np.int32).reshape(NLIMBS, 1)


# canonical limbs of p: [237, 255 x30, 127]
P_LIMBS = np.array(
    [int(b) for b in P.to_bytes(32, "little")], dtype=np.int32
)
_P_COLUMN = _column(P_LIMBS)
# 8p = 2^258 - 152 decomposed non-canonically as [872, 1020 x31]:
#   872 + 1020 * (2^256 - 2^8)/255 = 2^258 - 152.
# Used as the additive bias in `sub` so limb-wise differences stay
# non-negative for any loose (< 2^9 ≤ 1020/2) subtrahend.
_BIAS_8P = np.full(NLIMBS, 1020, dtype=np.int32)
_BIAS_8P[0] = 872
assert sum(int(v) << (8 * i) for i, v in enumerate(_BIAS_8P)) % P == 0
_BIAS_8P = _column(_BIAS_8P)


def from_int(x: int) -> np.ndarray:
    """Host helper: Python int -> limb vector [32] (numpy, canonical)."""
    return np.array(
        [int(b) for b in (x % P).to_bytes(32, "little")], dtype=np.int32
    )


def to_int(limbs) -> int:
    """Host helper: limb vector [32] -> Python int (no reduction)."""
    arr = np.asarray(limbs, dtype=np.int64)
    return int(sum(int(v) << (8 * i) for i, v in enumerate(arr.tolist())))


def constant(x: int) -> jnp.ndarray:
    """A Python-int field constant as [32, 1] limbs, which broadcast
    wherever an operation meets a batch."""
    return jnp.asarray(_column(from_int(x)))


def zeros() -> jnp.ndarray:
    return constant(0)


def ones() -> jnp.ndarray:
    return constant(1)


def _limbs(x: jnp.ndarray, start: int, stop: int, step: int = 1):
    """x[..., start:stop:step, :] — a slice along the limb axis."""
    return jax.lax.slice_in_dim(x, start, stop, step, axis=_LIMB_AXIS)


def _pad_limbs(x: jnp.ndarray, below: int, above: int) -> jnp.ndarray:
    """Zero limbs below and above x's own along the limb axis."""
    cfg = [(0, 0, 0)] * x.ndim
    cfg[_LIMB_AXIS] = (below, above, 0)
    return jax.lax.pad(x, jnp.int32(0), cfg)


def _carry_pass(x: jnp.ndarray) -> jnp.ndarray:
    """One vectorized carry pass with the mod-p wrap (2^256 ≡ 38).

    Bound chain after `mul`'s fold (columns < 2^28.3):
      pass1: limbs < 2^20.4 (limb0 < 2^25.6)
      pass2: limbs < 2^17.7
      pass3: limbs < 2^10.3
      pass4: limbs < 294 < 2^9   -> loose invariant restored.
    """
    c = x >> 8
    r = x - (c << 8)
    wrap = jnp.concatenate(
        [_limbs(c, 31, 32) * 38, _limbs(c, 0, 31)], axis=_LIMB_AXIS
    )
    return r + wrap


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b; loose in, loose out (sum < 2^10, one pass -> < 370)."""
    return _carry_pass(a + b)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b via the 8p bias; loose in, loose out (< 446 after one pass)."""
    return _carry_pass(a + jnp.asarray(_BIAS_8P) - b)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry_pass(jnp.asarray(_BIAS_8P) - a)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """16x32 mixed-radix limb convolution + fold by 38 + carry passes.

    One operand is repacked on the fly into 16 limbs of 16 bits
    (a16_i = a_{2i} + 256*a_{2i+1}), halving the multiply count vs the
    straight 32x32 schoolbook while every product still fits int32:
      a16_i < 2^9 + 256*(2^9-1) < 2^17.01 (loose 8-bit limbs < 2^9)
      a16_i * b_j < 2^26.01, column sum of <=16 terms < 2^30.01 < int32.
    The 63 columns are the sum of sixteen partial products, each padded
    to its place along the limb axis: rows of a register tile shifted,
    no lane crossed and no update-slice. On the chip, in this layout,
    sixteen `.at[].add` into one accumulator read 2.6x slower (PR 30's
    chip runs), and slices of one padded operand, a carry by `roll` and
    the 16-bit limbs on a leading axis each 7-19% slower (PR 32's;
    PERF.md §6).
    A plain (wrap-free) carry pass brings columns under 2^22.4 so the
    fold by 38 (2^256 = 38 mod p) stays in int32; the standard 4-pass
    chain then restores the loose invariant (fold < 2^27.7, below the
    2^28.3 the chain was verified for).
    """
    a16 = _limbs(a, 0, NLIMBS, 2) + (_limbs(a, 1, NLIMBS, 2) << 8)
    out = None
    for i in range(16):
        part = _pad_limbs(_limbs(a16, i, i + 1) * b, 2 * i, 31 - 2 * i)
        out = part if out is None else out + part
    # wrap-free carry: conv columns end at 2*15+31 = 61, so the carry out
    # of column 61 lands in the zero column 62 and nothing is lost
    c = out >> 8
    r = out - (c << 8)
    out = r + _pad_limbs(_limbs(c, 0, 62), 1, 0)
    x = _limbs(out, 0, NLIMBS) + _pad_limbs(_limbs(out, NLIMBS, 63) * 38, 0, 1)
    for _ in range(4):
        x = _carry_pass(x)
    return x


def sqr(x: jnp.ndarray) -> jnp.ndarray:
    return mul(x, x)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a * k for small non-negative int k.

    Bound: loose input (< 2^9) * k must survive three carry passes back to
    the loose invariant, which holds for k <= 2^17 (products < 2^26, well
    inside int32; pass chain verified numerically at the worst case).
    """
    assert 0 <= k <= 1 << 17, "mul_small constant out of verified range"
    x = a * k
    x = _carry_pass(x)
    x = _carry_pass(x)
    return _carry_pass(x)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """cond ? a : b, limb-wise; cond is [..., B] bool broadcast over limbs."""
    return jnp.where(cond[..., None, :], a, b)


def _sqr_n(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """x^(2^n) via lax.fori_loop (keeps the traced graph small)."""
    return jax.lax.fori_loop(0, n, lambda _, v: mul(v, v), x)


def _pow_2_250_minus_1(z: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """z^(2^250 - 1) — shared prefix of the inversion/sqrt chains (ref10)."""
    z2 = sqr(z)
    z9 = mul(sqr(sqr(z2)), z)
    z11 = mul(z9, z2)
    z2_5_0 = mul(sqr(z11), z9)  # z^(2^5-1)
    z2_10_0 = mul(_sqr_n(z2_5_0, 5), z2_5_0)
    z2_20_0 = mul(_sqr_n(z2_10_0, 10), z2_10_0)
    z2_40_0 = mul(_sqr_n(z2_20_0, 20), z2_20_0)
    z2_50_0 = mul(_sqr_n(z2_40_0, 10), z2_10_0)
    z2_100_0 = mul(_sqr_n(z2_50_0, 50), z2_50_0)
    z2_200_0 = mul(_sqr_n(z2_100_0, 100), z2_100_0)
    z2_250_0 = mul(_sqr_n(z2_200_0, 50), z2_50_0)
    return z2_250_0, z11


def invert(z: jnp.ndarray) -> jnp.ndarray:
    """z^(p-2) = z^(2^255 - 21). Returns 0 for z = 0."""
    z2_250_0, z11 = _pow_2_250_minus_1(z)
    return mul(_sqr_n(z2_250_0, 5), z11)


def pow22523(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3), used by sqrt-ratio in decompression."""
    z2_250_0, _ = _pow_2_250_minus_1(z)
    return mul(_sqr_n(z2_250_0, 2), z)


def invert_many(z: jnp.ndarray) -> jnp.ndarray:
    """Batched inversion of [32, B] via Montgomery's trick.

    Parallel prefix/suffix product scans along the batch axis + ONE Fermat
    inversion of the total product:
    inv(z_i) = prefix_{i-1} * suffix_{i+1} * inv(total).
    ~7 batch-muls of work instead of the 265 of per-element `invert`
    (the compress stage's cost drops accordingly). Rows equal to zero
    invert to 0, matching `invert` — and are masked to 1 inside the
    product chain so one zero row cannot poison the whole batch.
    """
    zero_mask = is_zero(z)
    one = ones()
    safe = select(zero_mask, one, z)
    prefix = jax.lax.associative_scan(mul, safe, axis=1)
    suffix = jax.lax.associative_scan(mul, safe, axis=1, reverse=True)
    # The one inversion runs on a register's width of copies of the total:
    # XLA lays a one-lane element out with its limbs on the lanes, where
    # every move along the limbs is a lane shuffle again. A squaring of
    # the chain then read 9.75 us on the chip, against 0.35 us this way:
    # 2.4 ms of every execution of every program (PERF.md §6, PR 32).
    total = jnp.broadcast_to(prefix[:, -1:], (NLIMBS, _LANES))
    total_inv = invert(total)[:, :1]
    excl_p = jnp.concatenate([one, prefix[:, :-1]], axis=-1)
    excl_s = jnp.concatenate([suffix[:, 1:], one], axis=-1)
    inv = mul(mul(excl_p, excl_s), total_inv)
    return select(zero_mask, zeros(), inv)


def _scan_carry(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential carry over the limb axis (no wrap).

    Returns (strict limbs in [0, 255], top carry = value >> 256).
    Works for signed inputs too (borrows propagate as negative carries).
    """
    xt = jnp.moveaxis(x, _LIMB_AXIS, 0)  # [32, ..., B]

    def step(carry, limb):
        v = limb + carry
        c = v >> 8
        return c, v - (c << 8)

    top, limbs = jax.lax.scan(step, jnp.zeros_like(xt[0]), xt)
    return jnp.moveaxis(limbs, 0, _LIMB_AXIS), top


def lt_p(limbs: jnp.ndarray) -> jnp.ndarray:
    """[..., B] bool: strict limbs (each in [0, 255]) read a value < p.
    The most-significant limb that differs from p's decides."""
    diff = limbs - jnp.asarray(_P_COLUMN)
    nz = diff != 0
    # index of the highest nonzero difference (0 if none)
    idx = (NLIMBS - 1) - jnp.argmax(jnp.flip(nz, _LIMB_AXIS), axis=_LIMB_AXIS)
    at = jnp.arange(NLIMBS, dtype=jnp.int32).reshape(NLIMBS, 1)
    ms = jnp.sum(jnp.where(at == idx[..., None, :], diff, 0), axis=_LIMB_AXIS)
    return ms < 0  # all equal: ms == 0, the value is p, not below it


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Freeze a loose element to its canonical limbs in [0, p).

    Only used at batch boundaries (encoding, equality); costs a few
    lax.scan passes over the 32 limbs.
    """
    # 1. exact carry; fold top carry K (V = K*2^256 + V0 ≡ V0 + 38K).
    limbs, top = _scan_carry(x)
    limbs = limbs.at[..., 0, :].add(top * 38)
    limbs, top = _scan_carry(limbs)  # top == 0 now (V0 + 38K < 2^256 + 114)
    limbs = limbs.at[..., 0, :].add(top * 38)
    # 2. fold bit 255: V = q*2^255 + W ≡ W + 19q.
    q = limbs[..., 31, :] >> 7
    limbs = limbs.at[..., 31, :].add(-(q << 7))
    limbs = limbs.at[..., 0, :].add(q * 19)
    limbs, _ = _scan_carry(limbs)
    q = limbs[..., 31, :] >> 7
    limbs = limbs.at[..., 31, :].add(-(q << 7))
    limbs = limbs.at[..., 0, :].add(q * 19)  # cannot ripple: W < 134 here if q=1
    # 3. now V < 2^255; subtract p once if V >= p (equal -> 0).
    geq = ~lt_p(limbs)
    limbs = limbs - jnp.asarray(_P_COLUMN) * geq[..., None, :]
    limbs, _ = _scan_carry(limbs)
    return limbs


def to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """Canonical little-endian 32-byte encoding, row-major again:
    [..., 32, B] limbs -> [B, ..., 32] uint8 (narrowed, then turned)."""
    return jnp.moveaxis(canonical(x).astype(jnp.uint8), -1, 0)


def from_bytes(b: jnp.ndarray) -> jnp.ndarray:
    """Row-major [B, ..., 32] little-endian bytes -> loose limbs
    [..., 32, B] int32: the bytes ARE the limbs, turned onto the lanes.
    Every row-major operand of a program (an encoding, a scalar, a table
    entry gathered from a store) enters the field code through here."""
    return jnp.moveaxis(b, 0, -1).astype(jnp.int32)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Canonical equality: [..., B] bool."""
    return jnp.all(canonical(a) == canonical(b), axis=_LIMB_AXIS)


def is_zero(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(x) == 0, axis=_LIMB_AXIS)


def parity(x: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical value (the ed25519 sign bit source)."""
    return canonical(x)[..., 0, :] & 1
