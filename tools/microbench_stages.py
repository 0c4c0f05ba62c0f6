"""Per-stage timing of the current ed25519 verify kernel on the device.

Stages: decompress, scalar_mult_base, scalar_mult_var, compress, plus
isolated primitives (double, add, window-table gather) to find the
pathological op.
"""

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from tendermint_tpu.ops import curve25519 as curve
from tendermint_tpu.ops import field25519 as fe

B = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
ITERS = 5


def timeit(name, fn, *args, want_out=False):
    # Reduce the output to scalars INSIDE the jit and fetch only those: the
    # tiny transfer is the synchronization point, and compute is not
    # drowned in device->host transfer time.
    def reduced(*a):
        return jax.tree.map(
            lambda x: x.sum() if hasattr(x, "sum") else x, fn(*a)
        )

    def fetch(o):
        return jax.tree.map(np.asarray, o)

    fn_r = jax.jit(reduced)
    t0 = time.perf_counter()
    fetch(fn_r(*args))
    compile_t = time.perf_counter() - t0
    best = float("inf")
    for _ in range(ITERS):
        t0 = time.perf_counter()
        fetch(fn_r(*args))
        best = min(best, time.perf_counter() - t0)
    print(f"{name:28s} compile {compile_t:7.2f}s  run {best*1e3:9.2f} ms  ({B/best/1e3:9.1f} Ksig-equiv/s)")
    if want_out:
        return jax.jit(fn)(*args)  # second compile, only when consumed
    return None


def main():
    print(f"backend={jax.default_backend()} B={B}")
    rng = np.random.default_rng(0)
    from __graft_entry__ import _make_batch

    pub, rb, sb, kb, s_ok = _make_batch(min(B, 64))
    reps = (B + pub.shape[0] - 1) // pub.shape[0]
    tile = lambda x: jnp.asarray(np.tile(x, (reps,) + (1,) * (x.ndim - 1))[:B])
    pub, rb, sb, kb = tile(pub), tile(rb), tile(sb), tile(kb)

    timeit("noop roundtrip", lambda x: x.astype(jnp.int32) + 1, s_ok_dev := jnp.asarray(np.ones(8, bool)))
    pt, ok = timeit("decompress", curve.decompress, pub, want_out=True)
    timeit("scalar_mult_base", curve.scalar_mult_base, sb)
    timeit("scalar_mult_var", curve.scalar_mult_var, kb, pt)
    timeit("compress", curve.compress, pt)
    timeit("double x1", curve.double, pt)
    timeit("add x1", curve.add, pt, pt)

    def dbl16(p):
        for _ in range(16):
            p = curve.double(p)
        return p

    timeit("double x16 unrolled", dbl16, pt)

    def dbl16_loop(p):
        return jax.lax.fori_loop(0, 16, lambda _, v: curve.double(v), p)

    timeit("double x16 fori", dbl16_loop, pt)

    # window-table gather pattern from scalar_mult_var
    entries = [curve.identity((B,)), pt]
    for _ in range(2):
        entries.append(curve.add(entries[-1], pt))
    table4 = jnp.stack(entries, axis=-3)  # [B, 4, 4, 32]
    digs = jnp.asarray(rng.integers(0, 4, (B,), dtype=np.int32))

    def gather_one(t, d):
        return jnp.take_along_axis(
            t, d[..., None, None, None], axis=-3
        ).squeeze(-3)

    timeit("table gather x1", gather_one, table4, digs)

    def onehot_select(t, d):
        mask = (d[:, None] == jnp.arange(4)[None, :]).astype(jnp.int32)
        return jnp.einsum("bk,bkcl->bcl", mask, t)

    timeit("onehot select x1", onehot_select, table4, digs)


if __name__ == "__main__":
    main()
