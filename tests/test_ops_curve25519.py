"""Differential tests: batched TPU curve ops vs the pure-python host oracle.

A batch of points is [4, 32, N]: coordinates on the leading axis, limbs on
the second-minor one, the batch on the lanes (ops/curve25519.py); encodings
and scalars are row-major [N, 32] bytes."""

import functools
import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.ops import curve25519 as curve
from tendermint_tpu.ops import field25519 as fe

# jit everything once — eager dispatch of these deep graphs is pathologically
# slow on the CPU test platform, and jit also exercises the real path.
_add = jax.jit(curve.add)
_double = jax.jit(curve.double)
_compress = jax.jit(curve.compress)
_decompress = jax.jit(curve.decompress)
_smul_base = jax.jit(curve.scalar_mult_base)
_smul_var = jax.jit(curve.scalar_mult_var)


def _rand_points(n, seed=0):
    """n pseudorandom curve points (as host points) via hashing to scalars."""
    pts = []
    for i in range(n):
        s = int.from_bytes(hashlib.sha512(bytes([seed, i])).digest(), "little")
        pts.append(host.scalar_mult(s % host.L, host.BASEPOINT))
    return pts


def _to_batch(pts):
    return jnp.asarray(
        np.stack([curve.from_host_point(p) for p in pts], axis=-1)
    )


def _scalar_bytes(scalars):
    return jnp.asarray(
        np.stack(
            [
                np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)
                for s in scalars
            ]
        )
    )


def _assert_points_equal(dev_pts, host_pts):
    enc = np.asarray(_compress(dev_pts))
    for i, hp in enumerate(host_pts):
        assert bytes(enc[i].tobytes()) == host.point_compress(hp), f"idx {i}"


def test_add_double_match_host():
    ps = _rand_points(4, seed=1)
    qs = _rand_points(4, seed=2)
    dev_sum = _add(_to_batch(ps), _to_batch(qs))
    _assert_points_equal(dev_sum, [host.point_add(p, q) for p, q in zip(ps, qs)])
    dev_dbl = _double(_to_batch(ps))
    _assert_points_equal(dev_dbl, [host.point_add(p, p) for p in ps])


def test_add_identity_and_self():
    ps = _rand_points(2, seed=3)
    batch = _to_batch(ps)
    _assert_points_equal(_add(batch, curve.identity(2)), ps)
    # unified add must handle P+P (completeness)
    _assert_points_equal(_add(batch, batch), [host.point_add(p, p) for p in ps])


def test_compress_decompress_roundtrip():
    ps = _rand_points(4, seed=4)
    enc = np.stack(
        [np.frombuffer(host.point_compress(p), dtype=np.uint8) for p in ps]
    )
    pt, valid = _decompress(jnp.asarray(enc))
    assert np.asarray(valid).all()
    _assert_points_equal(pt, ps)


def test_decompress_rejects_bad_encodings():
    bad = np.zeros((3, 32), dtype=np.uint8)
    # y = p (non-canonical encoding of 0)
    bad[0] = np.frombuffer(host.P.to_bytes(32, "little"), dtype=np.uint8)
    # y = 2 is not on the curve (x^2 = (y^2-1)/(dy^2+1) is non-square for y=2)
    bad[1, 0] = 2
    # x=0 point (y=1) with sign bit set
    bad[2, 0] = 1
    bad[2, 31] = 0x80
    _, valid = _decompress(jnp.asarray(bad))
    valid = np.asarray(valid)
    assert not valid[0]
    assert not valid[2]
    # row 1: mirror the host oracle
    assert valid[1] == (host.point_decompress(bytes(bad[1].tobytes())) is not None)


def test_scalar_mult_base_matches_host():
    scalars = [0, 1, 2, host.L - 1, 2**256 - 1]
    sb = np.stack(
        [
            np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)
            for s in scalars
        ]
    )
    out = _smul_base(jnp.asarray(sb))
    _assert_points_equal(
        out, [host.scalar_mult(s, host.BASEPOINT) for s in scalars]
    )


def test_scalar_mult_var_matches_host():
    pts = _rand_points(3, seed=5)
    scalars = [7, host.L - 2, 2**255 + 12345]
    sb = np.stack(
        [
            np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)
            for s in scalars
        ]
    )
    out = _smul_var(jnp.asarray(sb), _to_batch(pts))
    _assert_points_equal(
        out, [host.scalar_mult(s, p) for s, p in zip(scalars, pts)]
    )


def test_scalar_mult_var_bigtable_matches_host():
    """Fixed-window (doubling-free) variable-base path, both table forms."""
    pts = _rand_points(3, seed=9)
    scalars = [0, host.L - 1, 2**256 - 19]
    sb = jnp.asarray(
        np.stack(
            [
                np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)
                for s in scalars
            ]
        )
    )
    tables = jax.jit(curve.big_window_table)(_to_batch(pts))
    assert tables.shape == (64, 16, 4, 32, 3)
    expected = [host.scalar_mult(s, p) for s, p in zip(scalars, pts)]

    out = jax.jit(curve.scalar_mult_var_bigtable)(sb, tables)
    _assert_points_equal(out, expected)

    # cache form: rows permuted, gathered back by index
    idx = jnp.asarray(np.array([2, 0, 1], dtype=np.int32))
    # a store is row-major bytes: [cap, 64, 16, 4, 32] uint8
    store = jax.jit(fe.to_bytes)(tables)
    assert store.shape == (3, 64, 16, 4, 32) and store.dtype == jnp.uint8
    cache = jnp.take(store, idx, axis=0)  # cache[j] = store[idx[j]]
    inv = jnp.asarray(np.array([1, 2, 0], dtype=np.int32))
    out2 = jax.jit(curve.scalar_mult_var_bigcache)(sb, cache, inv)
    _assert_points_equal(out2, expected)


def test_bigcache_mxu_matches_gather_path():
    """The one-hot-matmul (MXU) formulation of the fixed-window lookup
    must be bit-identical to the gather path for valid and invalid rows
    (it is selected on real silicon via TM_TPU_MXU_GATHER=1)."""
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _make_batch
    from tendermint_tpu.ops.ed25519_batch import (
        neg_pubkey_bigtable,
        verify_prehashed_bigcache,
        verify_prehashed_bigcache_mxu,
    )

    n = 8
    pub, rb, sb, kb, s_ok = _make_batch(n)
    sb[2] ^= 1  # corrupt one row
    tables, valid = jax.jit(neg_pubkey_bigtable)(jnp.asarray(pub))
    idx = jnp.arange(n, dtype=jnp.int32)
    args = (
        tables,
        valid,
        idx,
        jnp.asarray(rb),
        jnp.asarray(sb),
        jnp.asarray(kb),
        jnp.asarray(s_ok),
    )
    out_g = np.asarray(jax.jit(verify_prehashed_bigcache)(*args))
    out_m = np.asarray(jax.jit(verify_prehashed_bigcache_mxu)(*args))
    assert (out_g == out_m).all()
    assert out_g[0] and not out_g[2]


# --- the loops, tile by tile ------------------------------------------------

# curve._TILE and curve._GROUP patched to these: the CPU run stays in
# seconds, and a tile still interleaves groups of lanes
_TILE, _GROUP = 4, 2


_MAX_N = 12


@functools.cache
def _loop_operands():
    """The per-signature operands of the four loops for a batch of
    `_MAX_N`, built once; a case takes the first n lanes."""
    points = _to_batch(_rand_points(_MAX_N, seed=11))
    big = jax.jit(curve.big_window_table)(points)
    store = jax.jit(fe.to_bytes)(big[..., :3])  # a 3-key row-major store
    return {
        "base": (curve.scalar_mult_base, ()),
        "var_table": (
            curve.scalar_mult_var_table,
            (jax.jit(curve.window_table)(points),),
        ),
        "var_bigtable": (curve.scalar_mult_var_bigtable, (big,)),
        "var_bigcache": (
            # rows of the store, read out of order
            lambda sb, idx: curve.scalar_mult_var_bigcache(sb, store, idx),
            (jnp.asarray(np.arange(_MAX_N)[::-1] % 3),),
        ),
    }


@pytest.mark.parametrize("n", [3, 4, 12, 10])
@pytest.mark.parametrize(
    "loop", ["base", "var_table", "var_bigtable", "var_bigcache"]
)
def test_loops_tiled_match_untiled(loop, n, monkeypatch):
    """Below one tile, at one tile, at three tiles, and at a batch that is
    no whole number of tiles (which runs untiled): bit for bit the untiled
    accumulator."""
    fn, lanes = _loop_operands()[loop]
    lanes = tuple(x[..., :n] for x in lanes)
    sb = _scalar_bytes(
        [
            int.from_bytes(hashlib.sha512(b"s%d" % i).digest(), "little")
            % 2**256
            for i in range(n)
        ]
    )
    monkeypatch.setattr(curve, "_TILE", 1 << 30)
    whole = np.asarray(jax.jit(lambda *a: fn(*a))(sb, *lanes))
    monkeypatch.setattr(curve, "_TILE", _TILE)
    monkeypatch.setattr(curve, "_GROUP", _GROUP)
    tiled = np.asarray(jax.jit(lambda *a: fn(*a))(sb, *lanes))
    assert whole.shape == (4, 32, n)
    assert (tiled == whole).all()


def test_tiling_is_read_from_the_batch(monkeypatch):
    """More than one tile puts a map over tiles inside each step; one tile
    or less, or a ragged batch, does not."""
    monkeypatch.setattr(curve, "_TILE", _TILE)
    monkeypatch.setattr(curve, "_GROUP", _GROUP)

    def loops(n):
        sb = jax.ShapeDtypeStruct((n, 32), jnp.uint8)
        return str(jax.make_jaxpr(curve.scalar_mult_base)(sb)).count("scan[")

    assert loops(3) == loops(4) == loops(10) == 1  # the steps
    assert loops(8) == loops(12) == 2  # the steps, and the tiles of one


# --- the programs on a batch with every kind of bad row ----------------------


def _mixed_batch():
    """16 rows: valid ones, a forged s, a tampered challenge, a
    non-canonical R (y >= p), an R that is no point, an invalid key (no
    point on the curve), a key whose encoding is not canonical, an s >= L
    flagged by the host mask; the last three padding rows (idx = -1 in
    the cached programs)."""
    from __graft_entry__ import _make_batch

    n = 16
    pub, rb, sb, kb, s_ok = _make_batch(n)
    sb[1] ^= 1
    kb[2, 0] ^= 1
    rb[3] = np.frombuffer((host.P + 1).to_bytes(32, "little"), dtype=np.uint8)
    rb[4] = 0
    rb[4, 0] = 2
    pub[5] = 0
    pub[5, 0] = 2  # y = 2: x^2 has no root
    assert host.point_decompress(bytes(pub[5].tobytes())) is None
    pub[6] = np.frombuffer(host.P.to_bytes(32, "little"), dtype=np.uint8)
    s_ok[7] = False
    idx = np.arange(n, dtype=np.int32)
    idx[13:] = -1
    return pub, rb, sb, kb, s_ok, idx


def _host_verdicts(pub, rb, sb, kb, s_ok):
    """The cofactorless equation on the host: encode([s]B + [k](-A)) == R."""
    out = []
    for i in range(len(pub)):
        a = host.point_decompress(bytes(pub[i].tobytes()))
        if a is None or not s_ok[i]:
            out.append(False)
            continue
        s = int.from_bytes(sb[i].tobytes(), "little")
        k = int.from_bytes(kb[i].tobytes(), "little")
        neg_a = (-a[0] % host.P, a[1], a[2], -a[3] % host.P)
        q = host.point_add(
            host.scalar_mult(s, host.BASEPOINT), host.scalar_mult(k, neg_a)
        )
        out.append(host.point_compress(q) == bytes(rb[i].tobytes()))
    return out


@pytest.mark.parametrize("program", ["generic", "small", "big"])
def test_programs_match_host_on_bad_rows(program):
    from tendermint_tpu.crypto import batch_verifier as bv
    from tendermint_tpu.ops import ed25519_batch as eb

    pub, rb, sb, kb, s_ok, idx = _mixed_batch()
    want = _host_verdicts(pub, rb, sb, kb, s_ok)
    assert want[0] and want[8] and not any(want[1:8])
    J = jnp.asarray
    if program == "generic":
        got = jax.jit(eb.verify_prehashed)(J(pub), J(rb), J(sb), J(kb), J(s_ok))
    else:
        build, verify = {
            "small": (eb.neg_pubkey_table, bv._verify_cached_small),
            "big": (eb.neg_pubkey_bigtable, bv._verify_cached_big),
        }[program]
        tables, valid = jax.jit(build)(J(pub))
        assert np.asarray(valid).tolist() == [
            host.point_decompress(bytes(p.tobytes())) is not None for p in pub
        ]
        got = jax.jit(verify)(
            tables, valid, J(idx), J(rb), J(sb), J(kb), J(s_ok)
        )
        want = [w and i >= 0 for w, i in zip(want, idx)]
    assert np.asarray(got).tolist() == want
