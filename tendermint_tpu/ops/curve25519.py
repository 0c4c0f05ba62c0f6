"""Batched edwards25519 group arithmetic on TPU (JAX, limb vectors).

Role (SURVEY.md §2.2 row "ed25519 verify"): the reference verifies votes one
at a time through golang.org/x/crypto ed25519 (crypto/ed25519/ed25519.go:148-162
in /root/reference). Here the whole group layer is data-parallel: a point is a
``[..., 4, 32, B] int32`` array (X, Y, Z, T extended homogeneous coordinates
stacked on a leading axis, each a radix-2^8 field element from
``ops.field25519``: limbs on the second-minor axis, the batch on the lanes),
and every operation maps over the batch. No data-dependent control flow:
failures (bad decompression, wrong sign) come back as boolean masks, so a
batch of signatures is one straight-line XLA program that `vmap`/`shard_map`
can tile across a TPU mesh.

Row-major at the edges, lane-major inside: encodings and scalars arrive as
``[B, 32]`` uint8 and the key-table stores stay ``[cap, ..., 4, 32]`` uint8
(their layout, installers and build outputs are `crypto/batch_verifier.py`'s
and did not move with the field code). `fe.from_bytes` turns such an operand,
or a ``[B, 4, 32]`` entry gathered from a store, onto the lanes; `compress`
and `fe.to_bytes` turn results back. A table built *inside* a program
(`window_table`, `big_window_table`) is lane-major like every other value:
``[16, 4, 32, B]``.

v2 structure (this file's key TPU-first trick): every group operation packs
its four independent field multiplications into ONE batched `fe.mul` over a
stacked [..., 4, 32, B] operand — the backend sees 4x fewer, 4x larger ops
(dispatch/compile cost drops ~4x; the arithmetic is identical). Addends use
ref10's *cached* form (Y-X, Y+X, 2d*T, 2Z) so a complete addition is exactly
2 packed multiplications:

    add:    [A,B,C,D] = mul([Y1-X1, Y1+X1, T1, Z1], cached)
            [X3,Y3,Z3,T3] = mul([E,G,F,E], [F,H,G,H])
    double: [XX,YY,ZZ,AA] = sqr([X, Y, Z, X+Y])
            [X3,Y3,Z3,T3] = mul([x,y,z,x], [t,z,t,y])

Formula provenance: add-2008-hwcd-3 (complete, a=-1) and ref10 ge_p2_dbl.

The scalar multiplications are loops of cached additions over the whole
batch (`_accumulate`). A batch larger than `_TILE` lanes runs each step tile
by tile, so that a step's operands stay on chip; which it is is read from
the operand's static shape, i.e. from the bucket the program is compiled
for. A tile takes every n-th group of 128 lanes, so that a batch sharded
over a mesh is still sharded inside each tile.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import field25519 as fe
from ..crypto import ed25519 as host

NLIMBS = fe.NLIMBS

_D = host.D
_D2 = (2 * host.D) % host.P
_SQRT_M1 = host.SQRT_M1

# Lanes one step of a scalar-multiplication loop works on at a time. Chosen
# on the chip (TPU v5 lite, `big@16384` over a 1,024-key store): untiled
# 69.9 ms, tiles of 1,024 / 2,048 / 4,096 lanes 38.6 / 37.4 / 37.2 ms (PR 32's
# chip runs, before the 2.4 ms of `fe.invert_many` went; PR 30 read 66.3,
# 37.8 / 35.5 / 36.8 and settled on 2,048; PERF.md §6).
_TILE = 2048
# Lanes that stay together when a batch is cut into tiles: a register's width.
_GROUP = 128
assert _TILE % _GROUP == 0


# --- representation -------------------------------------------------------


def _coords(p: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
    """The four coordinates [..., 32, B] of a point [..., 4, 32, B]."""
    return tuple(p[..., j, :, :] for j in range(4))


def _point(x, y, z, t) -> jnp.ndarray:
    return jnp.stack([x, y, z, t], axis=-3)


_IDENTITY = np.zeros((4, NLIMBS, 1), dtype=np.int32)
_IDENTITY[1, 0] = 1  # Y = 1
_IDENTITY[2, 0] = 1  # Z = 1


def identity(batch: int = 1) -> jnp.ndarray:
    """The neutral element (0, 1, 1, 0) as [4, 32, batch]: one column,
    broadcast (a program holds no constant the size of its batch)."""
    return jnp.broadcast_to(jnp.asarray(_IDENTITY), (4, NLIMBS, batch))


def from_host_point(p: host.Point) -> np.ndarray:
    """Host helper: python-int extended point -> [4, 32] limbs (stack a
    batch of them on a last axis)."""
    return np.stack([fe.from_int(c) for c in p])


def from_host_point_cached(p: host.Point) -> np.ndarray:
    """Host helper: python-int extended point -> cached [4, 32] limbs."""
    x, y, z, t = p
    P = host.P
    return np.stack(
        [
            fe.from_int((y - x) % P),
            fe.from_int((y + x) % P),
            fe.from_int(t * _D2 % P),
            fe.from_int(2 * z % P),
        ]
    )


def neg(p: jnp.ndarray) -> jnp.ndarray:
    """-(X, Y, Z, T) = (-X, Y, Z, -T)."""
    x, y, z, t = _coords(p)
    return _point(fe.neg(x), y, z, fe.neg(t))


# the cached form's two constant factors, stacked for one packed mul
_D2_AND_2 = np.stack([fe.from_int(_D2), fe.from_int(2)])[..., None]


def to_cached(p: jnp.ndarray) -> jnp.ndarray:
    """Extended -> cached (Y-X, Y+X, 2d*T, 2Z); one packed mul.

    The packed mul computes [2d*T, 2*Z] alongside nothing else (2 of 4
    slots) — callers converting whole tables amortize it over the entry
    axis instead.
    """
    x, y, z, t = _coords(p)
    td2_z2 = fe.mul(jnp.stack([t, z], axis=-3), jnp.asarray(_D2_AND_2))
    return _point(
        fe.sub(y, x), fe.add(y, x), td2_z2[..., 0, :, :], td2_z2[..., 1, :, :]
    )


# --- group law (packed) ---------------------------------------------------


def add_cached(p: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Complete unified addition p + c with c in cached form.

    2 packed muls (add-2008-hwcd-3 with the 2d*T / 2Z factors folded into
    the cached operand, as ref10 ge_add)."""
    x1, y1, z1, t1 = _coords(p)
    a, b, cc, d = _coords(
        fe.mul(_point(fe.sub(y1, x1), fe.add(y1, x1), t1, z1), c)
    )
    e = fe.sub(b, a)
    f = fe.sub(d, cc)
    g = fe.add(d, cc)
    h = fe.add(b, a)
    return fe.mul(_point(e, g, f, e), _point(f, h, g, h))


def add(p: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Complete unified addition of two extended points."""
    return add_cached(p, to_cached(q))


def double(p: jnp.ndarray) -> jnp.ndarray:
    """Dedicated doubling (ref10 ge_p2_dbl shape); 2 packed muls."""
    x1, y1, z1, _ = _coords(p)
    sq_in = _point(x1, y1, z1, fe.add(x1, y1))
    xx, yy, zz, aa = _coords(fe.mul(sq_in, sq_in))
    y3 = fe.add(yy, xx)
    z3 = fe.sub(yy, xx)
    x3 = fe.sub(aa, y3)
    t3 = fe.sub(fe.mul_small(zz, 2), z3)
    return fe.mul(_point(x3, y3, z3, x3), _point(t3, z3, t3, y3))


# --- encoding -------------------------------------------------------------


def compress(p: jnp.ndarray) -> jnp.ndarray:
    """Canonical 32-byte encoding: y with the sign(x) bit on top, row-major
    again: [..., 4, 32, B] -> [B, ..., 32] u8.

    For a plain batch of points ([4, 32, B]) the Z inversions use
    Montgomery's trick (`fe.invert_many`): one Fermat inversion for the
    whole batch instead of one per element."""
    x, y, z, _ = _coords(p)
    zinv = fe.invert_many(z) if p.ndim == 3 else fe.invert(z)
    xy = fe.canonical(
        fe.mul(jnp.stack([x, y], axis=-3), zinv[..., None, :, :])
    )
    sign = xy[..., 0, 0, :] & 1
    ya = xy[..., 1, :, :].at[..., 31, :].add(sign << 7)
    return jnp.moveaxis(ya.astype(jnp.uint8), -1, 0)


def decompress(b: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched point decompression.

    b: [B, 32] uint8. Returns (point [4, 32, B], valid [B] bool).
    Rejects (mask False): y >= p (non-canonical), x^2 with no square root,
    x = 0 with sign bit set. Mirrors the host oracle `_recover_x`
    (crypto/ed25519 semantics of the reference, crypto/ed25519/ed25519.go).
    """
    y = fe.from_bytes(b)
    sign = y[31] >> 7
    y = y.at[31].add(-(sign << 7))  # clear bit 255
    y_lt_p = fe.lt_p(y)  # canonical check

    one = fe.ones()
    yy = fe.sqr(y)
    u = fe.sub(yy, one)  # y^2 - 1
    v = fe.add(fe.mul(yy, fe.constant(_D)), one)  # d y^2 + 1
    # x = u v^3 (u v^7)^((p-5)/8)  — one exponentiation, then fixups.
    v3 = fe.mul(fe.sqr(v), v)
    v7 = fe.mul(fe.sqr(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow22523(fe.mul(u, v7)))
    vx2 = fe.mul(v, fe.sqr(x))
    ok_direct = fe.eq(vx2, u)
    ok_flipped = fe.eq(vx2, fe.neg(u))
    x = fe.select(ok_flipped, fe.mul(x, fe.constant(_SQRT_M1)), x)
    has_root = ok_direct | ok_flipped

    x_is_zero = fe.is_zero(x)
    sign_ok = ~(x_is_zero & (sign == 1))
    # conditional negate to match the sign bit
    x = fe.select((fe.parity(x) != sign) & ~x_is_zero, fe.neg(x), x)

    valid = y_lt_p & has_root & sign_ok
    return _point(x, y, jnp.broadcast_to(one, y.shape), fe.mul(x, y)), valid


# --- scalars --------------------------------------------------------------


def nibbles(scalar_bytes: jnp.ndarray) -> jnp.ndarray:
    """[B, 32] u8 little-endian scalar -> [64, B] int32 radix-16 digits
    (least-significant first), the batch on the lanes."""
    s = fe.from_bytes(scalar_bytes)
    return jnp.stack([s & 15, s >> 4], axis=1).reshape(64, -1)


# --- window tables --------------------------------------------------------


def _multiples(p: jnp.ndarray) -> jnp.ndarray:
    """[4, 32, B] -> the extended points 0, P, 2P, ..., 15P: [16, 4, 32, B]."""
    entries = [identity(p.shape[-1]), p]
    for _ in range(14):
        entries.append(add(entries[-1], p))
    return jnp.stack(entries)


def window_table(p: jnp.ndarray) -> jnp.ndarray:
    """Per-element radix-16 window table in cached form:
    [16, 4, 32, B] = cached(0, P, 2P, ..., 15P).

    Built with 14 adds + one packed to_cached over the entry axis; this is
    also the unit the BatchVerifier caches per validator pubkey (the same
    validators sign every height — SURVEY.md §3.3), there turned row-major
    by `fe.to_bytes`."""
    return to_cached(_multiples(p))


def _select_entry(table: jnp.ndarray, dig: jnp.ndarray) -> jnp.ndarray:
    """table: [16, 4, 32, B] cached; dig: [B] in [0, 16). Each lane takes
    its own entry: four rounds of halving by a digit bit, no gather.

    Accepts narrow-dtype tables (a store's rows are canonical uint8 limbs);
    the widening to int32 comes after the selection."""
    for bit in range(4):
        odd = ((dig >> bit) & 1).astype(bool)
        table = jnp.where(odd, table[1::2], table[0::2])
    return table[0].astype(jnp.int32)


# --- the loops ------------------------------------------------------------


def _accumulate(steps: int, step, lanes, shared=lambda i: None) -> jnp.ndarray:
    """identity, then `steps` times acc = step(i, acc, shared(i), *lanes).

    `lanes` are the operands that are per signature: arrays whose last
    axis is the batch. `shared(i)` is what every signature of step i reads
    (a table row): taken once a step, outside the tiles. A batch of more
    than `_TILE` lanes (and a whole number of tiles) is cut into tiles and
    each step maps over them one after the other, so that what a step
    reads and writes stays on chip instead of streaming the whole batch's
    accumulator through memory for each of its fused operations; each
    signature's result is bit for bit the untiled one. Which it is is
    static: the batch size of the bucket being compiled."""
    batch = lanes[0].shape[-1]
    if batch <= _TILE or batch % _TILE:

        def body(i, acc):
            return step(i, acc, shared(i), *lanes)

        return jax.lax.fori_loop(0, steps, body, identity(batch))

    # Tile j holds every n-th group of `_GROUP` lanes, from group j on: a
    # batch that is sharded over a mesh in contiguous blocks then stays
    # sharded *inside* every tile, and the map over tiles runs on every chip
    # at once. (Tiles of contiguous lanes put the mesh on the mapped axis:
    # compiled for four chips, each all-gathered the operands and computed
    # every tile.) Whole groups move, so no lane changes its place in a
    # register, and on one chip it costs nothing (37.40 against 37.43 ms);
    # the order of the rows decides nothing.
    n = batch // _TILE
    groups = _TILE // _GROUP

    def tiles(x):  # [..., B] -> [n, ..., T]
        x = x.reshape(*x.shape[:-1], groups, n, _GROUP)
        return jnp.moveaxis(x, -2, 0).reshape(n, *x.shape[:-3], _TILE)

    tiled = tuple(tiles(x) for x in lanes)

    def tiled_body(i, acc):
        row = shared(i)
        return jax.lax.map(
            lambda a: step(i, a[0], row, *a[1:]), (acc, *tiled)
        )

    init = jnp.broadcast_to(identity(_TILE), (n, 4, NLIMBS, _TILE))
    acc = jax.lax.fori_loop(0, steps, tiled_body, init)  # [n, 4, 32, T]
    acc = jnp.moveaxis(acc.reshape(n, 4, NLIMBS, groups, _GROUP), 0, -2)
    return acc.reshape(4, NLIMBS, batch)


# --- fixed-base table (basepoint) -----------------------------------------

_BASE_TABLE_NP: np.ndarray | None = None


def _base_table() -> np.ndarray:
    """T[i, j] = cached([j * 256^i]B) as [32, 256, 4, 32] uint8 (host,
    once). Radix-256: the scalar's bytes ARE the digits, and [s]B is 32
    cached adds (vs 64 for radix-16) — the table is host-precomputed so
    the wider window costs only one-time build and 1 MiB of constants."""
    global _BASE_TABLE_NP
    if _BASE_TABLE_NP is None:
        rows = []
        base = host.BASEPOINT
        for _ in range(32):
            row = [host.IDENTITY]
            for _ in range(255):
                row.append(host.point_add(row[-1], base))
            rows.append([from_host_point_cached(p) for p in row])
            for _ in range(8):
                base = host.point_double(base)
        # canonical host values < 256: uint8 storage (1 MiB, not 4)
        _BASE_TABLE_NP = np.asarray(rows, dtype=np.uint8)
    return _BASE_TABLE_NP


def scalar_mult_base(scalar_bytes: jnp.ndarray) -> jnp.ndarray:
    """[s]B for s: [B, 32] u8 (little-endian, < 2^256) -> [4, 32, B]. No
    doublings: sum over the 32 byte-digit rows of the precomputed basepoint
    table, which ships to the device as uint8 (its limbs are canonical);
    each step gathers [B, 4, 32] entries and turns them onto the lanes."""
    table = jnp.asarray(_base_table())  # [32, 256, 4, 32] uint8

    def step(i, acc, row, digs):
        entry = jnp.take(row, digs[i], axis=0)  # [B, 4, 32] u8
        return add_cached(acc, fe.from_bytes(entry))

    return _accumulate(
        32,
        step,
        (fe.from_bytes(scalar_bytes),),  # [32, B] LSB-first byte digits
        lambda i: jax.lax.dynamic_index_in_dim(table, i, keepdims=False),
    )


def big_window_table(p: jnp.ndarray) -> jnp.ndarray:
    """Per-element fixed-window table T[i, j] = cached([j * 16^i]P):
    [4, 32, B] -> [64, 16, 4, 32, B] int32 (512 KiB per element in loose
    form; the persistent caches store it canonicalized and row-major as
    uint8, 128 KiB/key).

    The doubling-free analogue of `_base_table` for a *variable* base: with
    it, [k]P is 64 cached adds and zero doublings (`scalar_mult_var_bigtable`)
    — the same shape the reference's serial verify can never reach because it
    processes one signature at a time (crypto/ed25519/ed25519.go:148-162 in
    /root/reference). Build cost (≈63×4 packed doublings over the 16-entry
    axis) amortizes over a validator's lifetime: consensus re-verifies the
    same pubkeys every height (SURVEY.md §3.3).
    """

    # rows[i] = [16^i] * row (63 scan steps; the last row is emitted
    # without paying a final wasted doubling round)
    def scan_body(row, _):
        nxt = double(double(double(double(row))))
        return nxt, to_cached(row)

    last, rows = jax.lax.scan(scan_body, _multiples(p), None, length=63)
    return jnp.concatenate([rows, to_cached(last)[None]], axis=0)


def scalar_mult_var_bigtable(
    scalar_bytes: jnp.ndarray, table: jnp.ndarray
) -> jnp.ndarray:
    """[s]P from a prebuilt fixed-window table ([64, 16, 4, 32, B]).

    64 cached adds, no doublings — 2 packed muls per digit vs the 10 of
    `scalar_mult_var_table`."""

    def step(i, acc, _, digs, table):
        row = jax.lax.dynamic_index_in_dim(table, i, keepdims=False)
        return add_cached(acc, _select_entry(row, digs[i]))

    return _accumulate(64, step, (nibbles(scalar_bytes), table))


def scalar_mult_var_bigcache(
    scalar_bytes: jnp.ndarray,  # [B, 32] u8
    tables_cache: jnp.ndarray,  # [cap, 64, 16, 4, 32] fixed-window tables
    idx: jnp.ndarray,  # [B] int32 row index into the cache
) -> jnp.ndarray:
    """[s]·T[idx] against a shared device-resident table cache.

    Gathers one window-row slice per iteration ([cap, 16, 4, 32] sliced,
    then a [B]-gather of the selected digit entries, turned onto the
    lanes) so the full per-key tables are never materialized per batch
    element. The slice copies a 64th of the store in each step, which
    over a 16,384-row store is 25.8 ms an execution (PERF.md §7c): the
    store's layout is the other half of ROADMAP S11.

    Measured dead end on the earlier executor (r3; not measured on the
    chip): splitting the 64 sequential window-adds into C independent
    chains + a log-tree merge REGRESSED 3x there, because the per-step
    multi-axis gather tables[idx, w, dig] over [B, C] lowered to a
    generalized gather far costlier than this loop's slice + single-axis
    gather."""

    def step(i, acc, row, digs, idx):
        return add_cached(acc, fe.from_bytes(row[idx, digs[i]]))

    return _accumulate(
        64,
        step,
        (nibbles(scalar_bytes), idx),
        lambda i: jax.lax.dynamic_index_in_dim(
            tables_cache, i, axis=1, keepdims=False
        ),  # [cap, 16, 4, 32]
    )


def scalar_mult_var_bigcache_mxu(
    scalar_bytes: jnp.ndarray,  # [B, 32] u8
    tables_cache: jnp.ndarray,  # [cap, 64, 16, 4, 32] fixed-window tables
    idx: jnp.ndarray,  # [B] int32 row index into the cache
) -> jnp.ndarray:
    """scalar_mult_var_bigcache with the per-window gather recast as a
    ONE-HOT MATMUL — the MXU-native formulation of a table lookup.

    Per window w, the selected entry is
        onehot[b, idx[b]*16 + digs[b,w]] @ tables[:, w].reshape(cap*16, 128)
    i.e. a [B, cap*16] x [cap*16, 128] f32 matmul whose left operand has
    one 1 per row. Exactness: persistent-cache tables are canonical uint8
    limbs (< 256) — any value < 2^24 is exact in f32; bf16 would NOT be
    safe. Slower than the gather on the earlier executor, not measured on
    the chip (ROADMAP D4), so BatchVerifier selects it only when
    TM_TPU_MXU_GATHER=1. Verified bit-identical to the gather path in
    tests/test_ops_curve25519.py.
    """
    cap = tables_cache.shape[0]
    flat = tables_cache.astype(jnp.float32).reshape(cap, 64, 16, 128)

    def step(i, acc, tab_w, digs, idx):
        sel = idx * 16 + digs[i]  # [B] combined row index
        onehot = (
            sel[:, None] == jnp.arange(cap * 16, dtype=jnp.int32)[None, :]
        ).astype(jnp.float32)
        ent = jnp.dot(onehot, tab_w, precision=jax.lax.Precision.HIGHEST)
        return add_cached(acc, fe.from_bytes(ent.reshape(-1, 4, NLIMBS)))

    return _accumulate(
        64,
        step,
        (nibbles(scalar_bytes), idx),
        lambda i: jax.lax.dynamic_index_in_dim(
            flat, i, axis=1, keepdims=False
        ).reshape(cap * 16, 128),
    )


def scalar_mult_var_table(
    scalar_bytes: jnp.ndarray, table: jnp.ndarray
) -> jnp.ndarray:
    """[s]P from a prebuilt cached window table ([16, 4, 32, B]).

    64 iterations of (4 doublings + select + add_cached), MSB-first —
    10 packed muls per iteration."""

    def step(i, acc, _, digs, table):
        acc = double(double(double(double(acc))))
        return add_cached(acc, _select_entry(table, digs[63 - i]))

    return _accumulate(64, step, (nibbles(scalar_bytes), table))


def scalar_mult_var(scalar_bytes: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """[s]P batched variable-base (builds the window table first)."""
    return scalar_mult_var_table(scalar_bytes, window_table(p))


def double_scalar_mult_base(
    s_bytes: jnp.ndarray, k_bytes: jnp.ndarray, a: jnp.ndarray
) -> jnp.ndarray:
    """[s]B + [k]A — the ed25519 verification combination."""
    return add(scalar_mult_base(s_bytes), scalar_mult_var(k_bytes, a))


def double_scalar_mult_base_table(
    s_bytes: jnp.ndarray, k_bytes: jnp.ndarray, a_table: jnp.ndarray
) -> jnp.ndarray:
    """[s]B + [k]A with A's window table prebuilt ([16, 4, 32, B]; the
    cached-pubkey hot path: no decompression, no table build — SURVEY.md
    §3.3's workload re-verifies the same validators every height)."""
    return add(
        scalar_mult_base(s_bytes),
        scalar_mult_var_table(k_bytes, a_table),
    )
