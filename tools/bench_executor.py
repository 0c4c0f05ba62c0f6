"""Executor microbenchmark — the evidence base for PERF_ANALYSIS.md.

Measures the harness accelerator's cost model directly (call overhead,
per-op cost at trivial/realistic widths, sequential tiny-op chains,
batch-size scaling of the generic ed25519 verifier) so that every
below-baseline number in bench.py can be attributed to a measured
executor characteristic rather than asserted away.

Run on an idle box (background load corrupts every number):

    python tools/bench_executor.py            # the default device
    JAX_PLATFORMS=cpu python tools/bench_executor.py   # host XLA

Prints one JSON object; PERF_ANALYSIS.md quotes a stored run.
"""

from __future__ import annotations

import json
import time

import numpy as np


def _best(fn, *args, n=4):
    import jax

    r = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0][:1])
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        r = fn(*args)
        np.asarray(jax.tree_util.tree_leaves(r)[0][:1])
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    import jax
    import jax.numpy as jnp

    import tendermint_tpu.ops.field25519 as fe
    from tendermint_tpu.crypto import ed25519 as hosted
    from tendermint_tpu.ops import ed25519_batch as ed

    out: dict = {"platform": jax.devices()[0].platform}

    # 1. fixed per-call overhead: trivial op + result transfer
    triv = jax.jit(lambda x: x + 1)
    out["call_overhead_ms"] = round(
        _best(triv, jnp.zeros((8192, 32), jnp.int32)) * 1e3, 1
    )

    # 2. one packed field multiplication at verifier width
    m = jax.jit(fe.mul)
    a = jnp.ones((8192, 4, 32), jnp.int32)
    out["packed_fe_mul_standalone_ms"] = round(_best(m, a, a) * 1e3, 1)

    # 3. sequential tiny-op chain: single-element Fermat inversion
    #    (~265 dependent [32]-wide muls inside ONE jit); per-op cost is
    #    net of the fixed dispatch overhead measured above
    inv1 = jax.jit(fe.invert)
    x1 = jnp.asarray(fe.from_int(12345678901234567890))
    dt = _best(inv1, x1)
    out["tiny_chain_265_ops_ms"] = round(dt * 1e3, 1)
    net = max(0.0, dt - out["call_overhead_ms"] / 1e3)
    out["tiny_op_in_graph_us"] = round(net / 265 * 1e6, 1)

    # 4. in-graph marginal fe.mul cost (chain lengths 5 vs 50)
    def chain(n):
        def f(x):
            for _ in range(n):
                x = fe.mul(x, x)
            return x

        return jax.jit(f)

    rng = np.random.default_rng(1)
    ar = jnp.asarray(rng.integers(0, 256, (8192, 4, 32)), dtype=jnp.int32)
    t5, t50 = _best(chain(5), ar), _best(chain(50), ar)
    out["marginal_fe_mul_in_graph_ms"] = round((t50 - t5) / 45 * 1e3, 2)

    # 5. per-loop-iteration cost with a table gather: the 64-iteration
    #    window ladder, net of dispatch overhead — what fori_loop bodies
    #    that gather actually pay (the verifier's dominant term)
    from tendermint_tpu.ops import curve25519 as curve

    rng2 = np.random.default_rng(2)
    kb = jnp.asarray(
        rng2.integers(0, 256, (8192, 32)).astype(np.uint8)
    )
    pkb = np.tile(
        np.frombuffer(hosted.PrivKey.generate().public_key().data, np.uint8),
        (8192, 1),
    )
    apt, _ = jax.jit(curve.decompress)(jnp.asarray(pkb))
    tab = jax.jit(curve.window_table)(curve.neg(apt))
    dt = _best(jax.jit(curve.scalar_mult_var_table), kb, tab)
    net = max(0.0, dt - out["call_overhead_ms"] / 1e3)
    out["window_ladder_64iter_net_ms"] = round(net * 1e3, 1)
    out["loop_iter_with_gather_ms"] = round(net / 64 * 1e3, 2)

    # 6. generic verifier batch scaling (linear => volume-bound,
    #    flat => dispatch-bound)
    p1 = hosted.PrivKey.generate().public_key()
    full = jax.jit(ed.verify_prehashed)
    scaling = {}
    for B in (4096, 8192, 16384):
        pk = np.tile(np.frombuffer(p1.data, np.uint8), (B, 1))
        rb = rng.integers(0, 256, (B, 32)).astype(np.uint8)
        sb = rng.integers(0, 128, (B, 32)).astype(np.uint8)
        kb = rng.integers(0, 256, (B, 32)).astype(np.uint8)
        sok = np.ones(B, bool)
        args = tuple(jnp.asarray(v) for v in (pk, rb, sb, kb, sok))
        dt = _best(full, *args, n=3)
        scaling[str(B)] = {
            "ms": round(dt * 1e3, 1),
            "sigs_per_s": round(B / dt),
        }
    out["generic_verify_scaling"] = scaling

    print(json.dumps(out))


if __name__ == "__main__":
    main()
