"""Device secp256k1 kernel vs the host oracle (crypto/secp256k1.py).

Mirrors the test strategy of test_ops_bls_g1.py: field bounds pinned by
randomized + worst-case stress against python ints, group ops checked
limb-for-limb against the host Jacobian oracle, and the full verify
kernel differentially tested on real signatures (valid, corrupted,
cross-key) — including the x >= n wrapped mod-n comparison branch's
guard."""

import hashlib
import random

import numpy as np

import jax
import jax.numpy as jnp

from tendermint_tpu.crypto import secp256k1 as host
from tendermint_tpu.ops import secp256k1_kernel as k

fe = k.fe
P = k.P
rng = random.Random(42)

# jitted helpers: eager per-op dispatch makes the limb arithmetic
# pathologically slow on CPU; one compiled program per shape instead
_mulc = jax.jit(lambda a, b: fe.canonical(fe.mul(a, b)))
_mul = jax.jit(fe.mul)
_addc = jax.jit(lambda a, b: fe.canonical(fe.add(a, b)))
_subc = jax.jit(lambda a, b: fe.canonical(fe.sub(a, b)))
_negc = jax.jit(lambda a: fe.canonical(fe.neg(a)))
_invmanyc = jax.jit(lambda a: fe.canonical(fe.invert_many(a)))
_addpts = jax.jit(k.add_points)
_dbl = jax.jit(k.double)
_canon = jax.jit(fe.canonical)
_isinf = jax.jit(k.is_inf)


def _rand_fe():
    return rng.randrange(P)


# --- field -----------------------------------------------------------------


def test_field_mul_random_and_worst_case():
    for _ in range(25):
        a, b = _rand_fe(), _rand_fe()
        got = fe.to_int(
            np.asarray(
                _mulc(jnp.asarray(fe.from_int(a)), jnp.asarray(fe.from_int(b)))
            )
        )
        assert got == a * b % P
    # worst case: every limb at the loose bound (2^11 - 1)
    worst = jnp.full((fe.NLIMBS,), (1 << 11) - 1, dtype=jnp.int32)
    wv = fe.to_int(np.asarray(worst))
    got = fe.to_int(np.asarray(_mulc(worst, worst)))
    assert got == wv * wv % P
    # the loose invariant survives a mul chain at the bound
    x = worst
    val = wv
    for _ in range(6):
        x = _mul(x, x)
        val = val * val % P
        assert int(np.asarray(x).max()) < (1 << 11), "loose bound violated"
    assert fe.to_int(np.asarray(_canon(x))) == val


def test_field_add_sub_neg_invert():
    for _ in range(10):
        a, b = _rand_fe(), _rand_fe()
        ja, jb = jnp.asarray(fe.from_int(a)), jnp.asarray(fe.from_int(b))
        assert fe.to_int(np.asarray(_addc(ja, jb))) == (a + b) % P
        assert fe.to_int(np.asarray(_subc(ja, jb))) == (a - b) % P
        assert fe.to_int(np.asarray(_negc(ja))) == (-a) % P
    # batched inversion (the Montgomery trick + one Fermat chain)
    vals = [_rand_fe() for _ in range(7)] + [0]
    arr = jnp.asarray(np.stack([fe.from_int(v) for v in vals]))
    inv = np.asarray(_invmanyc(arr))
    for v, row in zip(vals, inv):
        got = fe.to_int(row)
        assert got == (pow(v, P - 2, P) if v else 0)


# --- group law -------------------------------------------------------------


def _host_affine(pt_jac_limbs):
    arr = np.asarray(_canon(jnp.asarray(pt_jac_limbs)))
    x, y, z = (fe.to_int(arr[i]) for i in range(3))
    if z == 0:
        return None
    return host._to_affine((x, y, z))


def test_group_ops_match_host_oracle():
    pts = []
    for _ in range(6):
        d = rng.randrange(1, host.N)
        pts.append(host._to_affine(host._jmul(d, (k.GX, k.GY, 1))))
    for a in pts[:3]:
        for b in pts[3:]:
            ja = jnp.asarray(k.from_affine_host(*a))
            jb = jnp.asarray(k.from_affine_host(*b))
            got = _host_affine(_addpts(ja, jb))
            want = host._to_affine(host._jadd((*a, 1), (*b, 1)))
            assert got == want
    # doubling, doubling-by-add, infinity identities
    ja = jnp.asarray(k.from_affine_host(*pts[0]))
    assert _host_affine(_dbl(ja)) == host._to_affine(
        host._jdouble((*pts[0], 1))
    )
    assert _host_affine(_addpts(ja, ja)) == host._to_affine(
        host._jdouble((*pts[0], 1))
    )
    inf = k.identity(())
    assert _host_affine(_addpts(ja, inf)) == pts[0]
    assert _host_affine(_addpts(inf, ja)) == pts[0]
    # P + (-P) = infinity
    negp = (pts[0][0], P - pts[0][1])
    jn_ = jnp.asarray(k.from_affine_host(*negp))
    assert bool(np.asarray(_isinf(_addpts(ja, jn_))))


# --- full verify -----------------------------------------------------------


def _kernel_operands(batch, b: int):
    """The ladder's operands from the host half's prepared rows, over a
    bucket of b rows: qx, qy [b, 32] int32 little-endian byte limbs, u1,
    u2, r [b, 32] uint8 big-endian, ok [b]; every other row zero."""
    qx = np.zeros((b, 32), dtype=np.int32)
    qy = np.zeros((b, 32), dtype=np.int32)
    u1, u2, rb = (np.zeros((b, 32), dtype=np.uint8) for _ in range(3))
    ok = np.zeros(b, dtype=bool)
    at = batch.rows
    qx[at] = batch.q[:, 31::-1]
    qy[at] = batch.q[:, :31:-1]
    u1[at], u2[at], rb[at] = batch.u1, batch.u2, batch.r
    ok[at] = True
    return qx, qy, u1, u2, rb, ok


def test_verify_kernel_differential_via_batch_verifier():
    """The device joint ladder over the operands the host half
    (secp_native.prep_digest_batch: parse/low-S/u1-u2, the key's point)
    builds must agree with the host verify on valid, corrupted,
    wrong-message, cross-key, and malformed rows."""
    from tendermint_tpu.crypto import secp_native

    privs = [host.PrivKey.from_secret(b"dev%d" % i) for i in range(7)]
    rows = []
    expect = []
    for i, pv in enumerate(privs):
        msg = b"msg%d" % i
        sig = pv.sign(msg)
        pub = pv.public_key().data
        rows.append((pub, msg, sig))
        expect.append(True)
        bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        rows.append((pub, msg, bad))
        expect.append(
            host.verify_digest(
                hashlib.sha256(msg).digest(),
                bad,
                host.decompress_point(pub),
            )
        )
        rows.append((pub, b"other", sig))
        expect.append(False)
        other = privs[(i + 1) % 7].public_key().data
        rows.append((other, msg, sig))
        expect.append(False)
    # malformed rows: short signature, garbage pubkey
    rows.append((privs[0].public_key().data, b"m", b"\x01" * 10))
    expect.append(False)
    rows.append((b"\x02" + b"\x00" * 32, b"m", privs[0].sign(b"m")))
    expect.append(False)
    rows += [rows[0], rows[1]]
    expect += [expect[0], expect[1]]
    assert len(rows) == 32  # one bucket, no padding row
    batch = secp_native.prep_digest_batch(
        [r[0] for r in rows],
        [hashlib.sha256(r[1]).digest() for r in rows],
        [r[2] for r in rows],
    )
    got = np.asarray(
        k.verify_prehashed_jit(
            *(jnp.asarray(a) for a in _kernel_operands(batch, 32))
        )
    )
    assert got.tolist() == expect, (
        f"device/host divergence: {got.tolist()} vs {expect}"
    )
    assert secp_native.start(batch)().tolist() == expect


def test_verify_wrapped_mod_n_guard():
    """x(R) in [n, p) exercises the wrapped comparison; and a forged
    r = (x - n + 2^256) pattern with x < n must NOT be accepted (the
    borrow guard)."""
    # craft: pick k until x(kG) >= n (probability ~ (p-n)/p is tiny for
    # secp256k1, so instead verify the guard logic directly on the
    # comparison path with synthetic x values)
    x_small = 5  # x < n
    fake_r = (x_small - host.N) % (1 << 256)  # the wrap-around pattern
    x_aff = jnp.asarray(fe.from_int(x_small))[None, :]
    r_le = jnp.asarray(
        np.frombuffer(fake_r.to_bytes(32, "big"), np.uint8)[::-1].astype(
            np.int32
        )
    )[None, :]
    x_min_n, borrow = fe._scan_carry(x_aff - jnp.asarray(k._N_LIMBS))
    wrapped = (np.asarray(borrow) == 0) & bool(
        np.asarray(jnp.all(x_min_n == r_le, axis=-1))[0]
    )
    assert not bool(np.asarray(wrapped)[0] if np.ndim(wrapped) else wrapped), (
        "borrow guard failed: negative difference matched forged r"
    )
    # positive side: x in [n, p) with r = x - n must take the wrapped
    # branch (a break here would fail genuine x >= n signatures on the
    # device only — a cross-backend consensus split no real signature
    # would surface, P(x >= n) ~ 2^-128)
    x_big = host.N + 12345
    assert x_big < P
    r_true = x_big - host.N
    x_aff2 = jnp.asarray(fe.from_int(x_big))[None, :]
    r_le2 = jnp.asarray(
        np.frombuffer(r_true.to_bytes(32, "big"), np.uint8)[::-1].astype(
            np.int32
        )
    )[None, :]
    d2 = bool(np.asarray(jnp.all(x_aff2 == r_le2, axis=-1))[0])
    xmn2, borrow2 = fe._scan_carry(x_aff2 - jnp.asarray(k._N_LIMBS))
    w2 = (int(np.asarray(borrow2)[0]) == 0) and bool(
        np.asarray(jnp.all(xmn2 == r_le2, axis=-1))[0]
    )
    assert not d2 and w2, "wrapped accept path broken for x >= n"
