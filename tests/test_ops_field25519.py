"""Differential tests: JAX GF(2^255-19) limb arithmetic vs Python bigints.

A batch of field elements is [32, N]: limbs on the second-minor axis, the
batch on the lanes (ops/field25519.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from tendermint_tpu.ops import field25519 as fe

import functools
import jax


@functools.cache
def _j(f):
    return jax.jit(f)

P = fe.P
rng = np.random.default_rng(1234)


def rand_ints(n):
    vals = [int.from_bytes(rng.bytes(32), "little") % P for _ in range(n)]
    # include edge cases
    vals[:6] = [0, 1, 2, P - 1, P - 19, P // 2]
    return [v % P for v in vals]


def pack(vals):
    return jnp.asarray(np.stack([fe.from_int(v) for v in vals], axis=-1))


def unpack_canonical(limbs):
    arr = np.asarray(limbs)
    return [fe.to_int(arr[:, i]) for i in range(arr.shape[1])]


N = 16
A_INTS = rand_ints(N)
B_INTS = rand_ints(N)[::-1]
A = pack(A_INTS)
B = pack(B_INTS)
A_BYTES = np.stack(
    [np.frombuffer(a.to_bytes(32, "little"), dtype=np.uint8) for a in A_INTS]
)


def assert_loose(x):
    arr = np.asarray(x)
    assert arr.min() >= 0 and arr.max() < 512, (arr.min(), arr.max())


def test_roundtrip():
    assert unpack_canonical(_j(fe.canonical)(A)) == [a % P for a in A_INTS]


def test_add():
    out = _j(fe.add)(A, B)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [
        (a + b) % P for a, b in zip(A_INTS, B_INTS)
    ]


def test_sub():
    out = _j(fe.sub)(A, B)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [
        (a - b) % P for a, b in zip(A_INTS, B_INTS)
    ]


def test_neg():
    out = _j(fe.neg)(A)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [(-a) % P for a in A_INTS]


def test_mul():
    out = _j(fe.mul)(A, B)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [
        (a * b) % P for a, b in zip(A_INTS, B_INTS)
    ]


def test_mul_loose_inputs():
    # worst-case loose inputs: all limbs 511
    x = jnp.full((32, 4), 511, dtype=jnp.int32)
    out = _j(fe.mul)(x, x)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [(V511 * V511) % P] * 4


# the loose invariant's worst case: every limb 511
V511 = fe.to_int(np.full(32, 511, dtype=np.int64))
_WORST = {
    "mul": (fe.mul, lambda a, b: a * b),
    "add": (fe.add, lambda a, b: a + b),
    "sub": (fe.sub, lambda a, b: a - b),
    "sub_from_small": (lambda a, b: fe.sub(b, a), lambda a, b: b - a),
}


@pytest.mark.parametrize("name", sorted(_WORST))
def test_worst_case_loose_operands(name):
    """Every limb of one operand at 511, against all-511, canonical and
    zero partners: the result is loose and matches the oracle."""
    op, ref = _WORST[name]
    partners = [V511, P - 1, 1, 0]
    a = jnp.full((32, len(partners)), 511, dtype=jnp.int32)
    b = jnp.concatenate(
        [jnp.full((32, 1), 511, dtype=jnp.int32), pack(partners[1:])], axis=1
    )
    out = _j(op)(a, b)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [
        ref(V511, v) % P for v in partners
    ]


def test_worst_case_canonical():
    x = jnp.full((32, 3), 511, dtype=jnp.int32)
    out = _j(fe.canonical)(x)
    assert np.asarray(out).max() < 256
    assert unpack_canonical(out) == [V511 % P] * 3
    assert np.asarray(_j(fe.to_bytes)(x)).tolist() == [
        list((V511 % P).to_bytes(32, "little"))
    ] * 3


def test_worst_case_invert_many():
    vals = [V511, 3, V511, 0, P - 1]
    x = pack(vals).at[:, 0].set(511).at[:, 2].set(511)
    got = unpack_canonical(_j(fe.canonical)(_j(fe.invert_many)(x)))
    assert got == [pow(v % P, P - 2, P) for v in vals]


def test_sqr_chain():
    # repeated squaring keeps the invariant and matches bigint
    x = A
    ref = list(A_INTS)
    for _ in range(8):
        x = _j(fe.sqr)(x)
        ref = [(v * v) % P for v in ref]
        assert_loose(x)
    assert unpack_canonical(_j(fe.canonical)(x)) == ref


def test_mul_small():
    out = fe.mul_small(A, 121666)
    assert_loose(out)
    assert unpack_canonical(_j(fe.canonical)(out)) == [
        (a * 121666) % P for a in A_INTS
    ]


def test_invert():
    out = _j(fe.invert)(A)
    got = unpack_canonical(_j(fe.canonical)(out))
    for a, g in zip(A_INTS, got):
        if a == 0:
            assert g == 0
        else:
            assert g == pow(a, P - 2, P)


def test_pow22523():
    out = _j(fe.pow22523)(A)
    got = unpack_canonical(_j(fe.canonical)(out))
    for a, g in zip(A_INTS, got):
        assert g == pow(a, (P - 5) // 8, P)


@pytest.mark.parametrize(
    "v",
    [0, 1, 19, P - 1, P, P + 1, 2 * P - 1, 2 * P, 2**255 - 1, 2**256 - 1],
)
def test_canonical_edge_values(v):
    # feed raw (possibly >= p, >= 2^255) limb encodings of v
    limbs = np.array(
        [int(b) for b in (v % 2**256).to_bytes(32, "little")], dtype=np.int32
    )
    out = _j(fe.canonical)(jnp.asarray(limbs)[:, None])
    assert unpack_canonical(out) == [(v % 2**256) % P]


def test_eq_and_parity():
    assert bool(np.asarray(_j(fe.eq)(A, A)).all())
    assert not bool(np.asarray(_j(fe.eq)(A, B)).any())
    par = np.asarray(_j(fe.parity)(A))
    assert par.tolist() == [a % 2 for a in A_INTS]


def test_select():
    cond = jnp.asarray([True, False] * (N // 2))
    out = fe.select(cond, A, B)
    got = unpack_canonical(_j(fe.canonical)(out))
    want = [a if i % 2 == 0 else b for i, (a, b) in enumerate(zip(A_INTS, B_INTS))]
    assert got == [w % P for w in want]


def test_bytes_turn_onto_the_lanes_and_back():
    """from_bytes takes row-major [B, 32] bytes to [32, B] limbs; to_bytes
    returns the canonical encoding row-major; leading axes keep their
    order ([B, 4, 32] <-> [4, 32, B])."""
    raw = rng.integers(0, 256, size=(5, 32), dtype=np.uint8)
    limbs = fe.from_bytes(jnp.asarray(raw))
    assert limbs.shape == (32, 5) and limbs.dtype == jnp.int32
    assert (np.asarray(limbs) == raw.T).all()
    back = np.asarray(_j(fe.to_bytes)(limbs))
    assert back.shape == (5, 32) and back.dtype == np.uint8
    want = [
        (int.from_bytes(r.tobytes(), "little") % 2**256 % P) for r in raw
    ]
    assert [int.from_bytes(r.tobytes(), "little") for r in back] == want
    ent = rng.integers(0, 256, size=(5, 4, 32), dtype=np.uint8)
    assert fe.from_bytes(jnp.asarray(ent)).shape == (4, 32, 5)
    assert (np.asarray(_j(fe.to_bytes)(fe.from_bytes(jnp.asarray(A_BYTES)))) == A_BYTES).all()


def test_invert_many_matches_invert():
    vals = rand_ints(9)
    vals[3] = 0  # zero row must invert to 0 without poisoning the batch
    x = pack(vals)
    got = unpack_canonical(_j(fe.canonical)(_j(fe.invert_many)(x)))
    want = [pow(v, P - 2, P) if v else 0 for v in vals]
    assert got == want
