"""Batched secp256k1 ECDSA verification on TPU (SURVEY §2.2 row
"secp256k1 verify").

The device side of the split crypto/secp_native.py already uses for the
native host path: the host does the cheap bignum work (signature
parsing, low-S check, u1 = z/s, u2 = r/s mod n, pubkey decompression —
each pubkey's affine coordinates cacheable per validator) and the
device verifies B signatures at once by computing R_i = u1_i*G + u2_i*Q_i
as a joint Straus ladder and checking x(R_i) mod n == r_i, all as one
straight-line XLA program with mask-based control flow — the same shape
as the ed25519 kernel (ops/ed25519_batch.py).

Field arithmetic comes from ops/vecfield.py (radix-2^8 int32 limbs,
p = 2^256 - 2^32 - 977); the curve is y^2 = x^3 + 7 (a = 0), Jacobian
coordinates, dbl-2009-l / add-2007-bl formulas matching the host oracle
(crypto/secp256k1.py) limb-for-limb after canonicalization.

No served path runs it: on a TPU v5 lite an 8,192-row execution reads
1,096.6 ms and the program takes 100 s to load from the compile cache,
where the host's native step on eight threads verifies a round's 4,096
rows in about 200 ms (PERF.md section 6). It is held to the host
oracle by tests/test_ops_secp.py until a layout with the batch on the
lanes (ROADMAP S18) makes it worth a round.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import vecfield

P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

fe = vecfield.make_field(P, 32)
NLIMBS = fe.NLIMBS

# n as byte limbs for the mod-n comparison (n < p < 2n, so
# x mod n ∈ {x, x - n})
_N_LIMBS = np.array([int(b) for b in N.to_bytes(32, "little")], dtype=np.int32)


# --- Jacobian group law (a = 0) -------------------------------------------


def identity(shape=()) -> jnp.ndarray:
    z = np.zeros((*shape, 3, NLIMBS), dtype=np.int32)
    z[..., 1, 0] = 1  # (0, 1, 0)
    return jnp.asarray(z)


def from_affine_host(x: int, y: int) -> np.ndarray:
    return np.stack([fe.from_int(x), fe.from_int(y), fe.from_int(1)])


def is_inf(p: jnp.ndarray) -> jnp.ndarray:
    return fe.is_zero(p[..., 2, :])


def double(p: jnp.ndarray) -> jnp.ndarray:
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    a = fe.sqr(x)
    b = fe.sqr(y)
    c = fe.sqr(b)
    xb = fe.add(x, b)
    d = fe.mul_small(fe.sub(fe.sub(fe.sqr(xb), a), c), 2)
    e = fe.mul_small(a, 3)
    f = fe.sqr(e)
    x3 = fe.sub(f, fe.mul_small(d, 2))
    y3 = fe.sub(fe.mul(e, fe.sub(d, x3)), fe.mul_small(c, 8))
    z3 = fe.mul_small(fe.mul(y, z), 2)
    bad = fe.is_zero(y) | fe.is_zero(z)
    out = jnp.stack([x3, y3, z3], axis=-2)
    return jnp.where(bad[..., None, None], identity(p.shape[:-2]), out)


def add_points(p: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Complete masked addition (add-2007-bl + doubling/infinity masks,
    mirroring ops/bls_g1.g1_add)."""
    x1, y1, z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    x2, y2, z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]
    z1z1 = fe.sqr(z1)
    z2z2 = fe.sqr(z2)
    u1 = fe.mul(x1, z2z2)
    u2 = fe.mul(x2, z1z1)
    s1 = fe.mul(fe.mul(y1, z2), z2z2)
    s2 = fe.mul(fe.mul(y2, z1), z1z1)
    h = fe.sub(u2, u1)
    r = fe.mul_small(fe.sub(s2, s1), 2)
    same_x = fe.is_zero(h)
    same_y = fe.is_zero(fe.sub(s2, s1))
    i = fe.sqr(fe.mul_small(h, 2))
    j = fe.mul(h, i)
    v = fe.mul(u1, i)
    x3 = fe.sub(fe.sub(fe.sqr(r), j), fe.mul_small(v, 2))
    y3 = fe.sub(
        fe.mul(r, fe.sub(v, x3)), fe.mul_small(fe.mul(s1, j), 2)
    )
    z3 = fe.mul_small(fe.mul(fe.mul(z1, z2), h), 2)
    gen = jnp.stack([x3, y3, z3], axis=-2)
    p_inf = is_inf(p)
    q_inf = is_inf(q)
    dbl = double(p)
    out = jnp.where((same_x & same_y)[..., None, None], dbl, gen)
    out = jnp.where(
        (same_x & ~same_y & ~p_inf & ~q_inf)[..., None, None],
        identity(out.shape[:-2]),
        out,
    )
    out = jnp.where(p_inf[..., None, None], q, out)
    out = jnp.where(q_inf[..., None, None], p, out)
    return out


# --- scalar digits ---------------------------------------------------------


def nibbles(scalar_bytes: jnp.ndarray) -> jnp.ndarray:
    """[..., 32] u8 big-endian scalar -> [..., 64] radix-16 digits,
    most-significant first."""
    s = scalar_bytes.astype(jnp.int32)
    hi = s >> 4
    lo = s & 15
    return jnp.stack([hi, lo], axis=-1).reshape(*s.shape[:-1], 64)


# --- G table (host, once) --------------------------------------------------

_G_TABLE_NP: np.ndarray | None = None


def _g_table() -> np.ndarray:
    """T[d] = [d]G affine-as-jacobian for d in 0..15 ([16, 3, 32]); the
    shared doubling chain of the ladder supplies the 16^j weights."""
    global _G_TABLE_NP
    if _G_TABLE_NP is None:
        from ..crypto import secp256k1 as host

        rows = [np.zeros((3, NLIMBS), dtype=np.int32)]
        rows[0][1][0] = 1  # identity (0,1,0)
        for d in range(1, 16):
            x, y = host._to_affine(host._jmul(d, (GX, GY, 1)))
            rows.append(from_affine_host(x, y))
        _G_TABLE_NP = np.stack(rows)
    return _G_TABLE_NP


def _select_entry(table: jnp.ndarray, dig: jnp.ndarray) -> jnp.ndarray:
    """table: [..., 16, 3, 32]; dig: [...] in [0, 16)."""
    return jnp.take_along_axis(
        table, dig[..., None, None, None], axis=-3
    ).squeeze(-3)


# --- the verify kernel -----------------------------------------------------


def verify_prehashed(
    qx: jnp.ndarray,  # [B, 32] i32 limbs: pubkey affine x
    qy: jnp.ndarray,  # [B, 32] i32 limbs: pubkey affine y
    u1: jnp.ndarray,  # [B, 32] u8 big-endian: z/s mod n
    u2: jnp.ndarray,  # [B, 32] u8 big-endian: r/s mod n
    r_bytes: jnp.ndarray,  # [B, 32] u8 big-endian signature r
    ok_in: jnp.ndarray,  # [B] bool host-side pre-checks (parse, low-S)
) -> jnp.ndarray:
    """[B] bool accept bitmap: x(u1*G + u2*Q) mod n == r."""
    B = qx.shape[0]
    q = jnp.stack([qx, qy, jnp.broadcast_to(fe.ones(), qx.shape)], axis=-2)
    # per-element radix-16 window table of Q: even entries by doubling
    # (cheaper and a shallower dependency chain than a 14-deep add
    # chain), odd entries by one add each
    entries: list = [None] * 16
    entries[0] = identity((B,))
    entries[1] = q
    for d in range(2, 16):
        if d % 2 == 0:
            entries[d] = double(entries[d // 2])
        else:
            entries[d] = add_points(entries[d - 1], q)
    qtab = jnp.stack(entries, axis=-3)  # [B, 16, 3, 32]
    gtab = jnp.asarray(_g_table())  # [16, 3, 32]

    d1 = nibbles(u1)  # G digits, MSB first
    d2 = nibbles(u2)  # Q digits

    def body(i, acc):
        acc = double(double(double(double(acc))))
        acc = add_points(acc, _select_entry(qtab, d2[..., i]))
        acc = add_points(acc, jnp.take(gtab, d1[..., i], axis=0))
        return acc

    rpt = jax.lax.fori_loop(0, 64, body, identity((B,)))
    # x(R) = X / Z^2; batched inversion via the Montgomery trick
    zinv = fe.invert_many(rpt[..., 2, :])
    x_aff = fe.canonical(fe.mul(rpt[..., 0, :], fe.sqr(zinv)))
    # mod n: x < p < 2n, so x mod n is x or x - n. The wrapped branch
    # must require x >= n (scan borrow top == 0), or a pattern match on
    # the 2^256-wrapped negative difference could false-accept.
    r_le = r_bytes[..., ::-1].astype(jnp.int32)  # to little-endian limbs
    direct = jnp.all(x_aff == r_le, axis=-1)
    x_min_n, borrow = fe._scan_carry(x_aff - jnp.asarray(_N_LIMBS))
    wrapped = (borrow == 0) & jnp.all(x_min_n == r_le, axis=-1)
    # reject R at infinity (Z == 0 -> zinv == 0 -> x_aff == 0 could
    # false-match r == 0, but r >= 1 is host-checked; still mask it)
    return ok_in & ~is_inf(rpt) & (direct | wrapped)


verify_prehashed_jit = jax.jit(verify_prehashed)
