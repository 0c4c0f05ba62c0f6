"""Batched ed25519 verification kernel (JAX → XLA → TPU).

The TPU replacement for the reference's serial per-vote loop
(types/vote_set.go:205 → crypto/ed25519/ed25519.go:148-162 in
/root/reference): one straight-line program that verifies B signatures at
once and returns an accept bitmap. No early exit, no branches — rejects are
masks, which is the TPU-friendly replacement for the reference's
``return false`` paths.

Two paths:
- `verify_prehashed`: generic — decompresses each pubkey and builds its
  window table in-batch.
- `verify_prehashed_table`: the consensus hot path — takes prebuilt cached
  window tables for the pubkeys (the same validators sign every height, so
  the BatchVerifier builds each validator's table once and re-uses it;
  skips decompression + table construction, ~40% of the generic work).

The kernel takes *prehashed* challenges: k = SHA-512(R || A || M) mod L is
computed by the caller (host today — the per-vote message is ragged while
everything in here is fixed-shape). The s < L range check is likewise a
host-computed input mask (`s_ok`).

Verification equation (cofactorless, matching Go x/crypto semantics):
    [s]B == R + [k]A   ⇔   encode([s]B + [k](-A)) == R_bytes

Layout: the programs' operands and results are row-major (``[B, 32]`` uint8
encodings and scalars, ``[B]`` verdicts, the key-table stores ``[cap, ..., 4,
32]`` uint8), which is what the host assembles, the scheduler shards on the
batch axis and the stores hold. Inside, every field element has the batch on
the minor-most axis (``ops/field25519.py``): the operands are turned onto the
lanes where they enter (`fe.from_bytes`) and the encodings turned back where
they leave (`curve.compress`, `fe.to_bytes`).

The stages of the cached programs carry `jax.named_scope` names that a
trace viewer shows and a refactor keeps: `base_mult` ([s]B), `key_mult`
([k](-A) from the big cache), `double_mult` (the small tier's fused
pair) and `compress` (the final add and the encoding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import curve25519 as curve
from . import field25519 as fe


def verify_prehashed(
    pubkeys: jnp.ndarray,  # [B, 32] uint8
    r_bytes: jnp.ndarray,  # [B, 32] uint8 (first half of each signature)
    s_bytes: jnp.ndarray,  # [B, 32] uint8 (second half; caller checks < L)
    k_bytes: jnp.ndarray,  # [B, 32] uint8 (SHA-512(R||A||M) mod L)
    s_ok: jnp.ndarray,  # [B] bool (host-side s < L check)
) -> jnp.ndarray:
    """Returns [B] bool accept bitmap."""
    a_point, a_valid = curve.decompress(pubkeys)
    q = curve.double_scalar_mult_base(s_bytes, k_bytes, curve.neg(a_point))
    encoded = curve.compress(q)
    r_match = jnp.all(encoded == r_bytes, axis=-1)
    return a_valid & s_ok & r_match


def neg_pubkey_table(pubkeys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Build cached window tables for -A per pubkey.

    pubkeys: [N, 32] u8 -> (tables [N, 16, 4, 32] u8, valid [N] bool).
    One-time per validator; the verify path then runs table-only. Entries
    are canonicalized so the persistent cache stores uint8 limbs — 4x
    less cache memory and gather traffic than loose int32, bit-exact
    (canonicalization is value-preserving mod p; the group ops accept
    any loose input)."""
    a_point, a_valid = curve.decompress(pubkeys)
    table = curve.window_table(curve.neg(a_point))  # [16, 4, 32, N]
    return fe.to_bytes(table), a_valid


def verify_prehashed_table(
    tables: jnp.ndarray,  # [B, 16, 4, 32] cached window tables of -A
    table_valid: jnp.ndarray,  # [B] bool (pubkey decompressed OK)
    r_bytes: jnp.ndarray,  # [B, 32] uint8
    s_bytes: jnp.ndarray,  # [B, 32] uint8
    k_bytes: jnp.ndarray,  # [B, 32] uint8
    s_ok: jnp.ndarray,  # [B] bool
) -> jnp.ndarray:
    """Returns [B] bool accept bitmap (cached-pubkey hot path)."""
    with jax.named_scope("double_mult"):
        # the gathered rows' bytes go onto the lanes once, still uint8
        q = curve.double_scalar_mult_base_table(
            s_bytes, k_bytes, jnp.moveaxis(tables, 0, -1)
        )
    with jax.named_scope("compress"):
        encoded = curve.compress(q)
    r_match = jnp.all(encoded == r_bytes, axis=-1)
    return table_valid & s_ok & r_match


def neg_pubkey_bigtable(
    pubkeys: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-window tables for -A per pubkey: doubling-free verification.

    pubkeys: [N, 32] u8 -> (tables [N, 64, 16, 4, 32] u8, valid [N] bool).
    128 KiB per key (canonical uint8 limbs — see neg_pubkey_table); built
    once per validator (SURVEY.md §3.3 — the same validators sign every
    height), after which each verify is 128 cached adds and zero
    doublings.
    """
    a_point, a_valid = curve.decompress(pubkeys)
    table = curve.big_window_table(curve.neg(a_point))  # [64, 16, 4, 32, N]
    return fe.to_bytes(table), a_valid


def verify_prehashed_bigcache(
    tables_cache: jnp.ndarray,  # [cap, 64, 16, 4, 32] shared table cache
    table_valid: jnp.ndarray,  # [B] bool (row's pubkey decompressed OK)
    idx: jnp.ndarray,  # [B] int32 row index into the cache
    r_bytes: jnp.ndarray,  # [B, 32] uint8
    s_bytes: jnp.ndarray,  # [B, 32] uint8
    k_bytes: jnp.ndarray,  # [B, 32] uint8
    s_ok: jnp.ndarray,  # [B] bool
) -> jnp.ndarray:
    """The BatchVerifier steady-state path: doubling-free, cache-resident."""
    with jax.named_scope("base_mult"):
        base = curve.scalar_mult_base(s_bytes)
    with jax.named_scope("key_mult"):
        key = curve.scalar_mult_var_bigcache(k_bytes, tables_cache, idx)
    with jax.named_scope("compress"):
        encoded = curve.compress(curve.add(base, key))
    r_match = jnp.all(encoded == r_bytes, axis=-1)
    return table_valid & s_ok & r_match


def verify_prehashed_bigcache_mxu(
    tables_cache: jnp.ndarray,
    table_valid: jnp.ndarray,
    idx: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_bytes: jnp.ndarray,
    k_bytes: jnp.ndarray,
    s_ok: jnp.ndarray,
) -> jnp.ndarray:
    """verify_prehashed_bigcache with the table lookups as one-hot MXU
    matmuls (curve.scalar_mult_var_bigcache_mxu) — the real-silicon
    variant; select via TM_TPU_MXU_GATHER=1 (see the kernel docstring)."""
    with jax.named_scope("base_mult"):
        base = curve.scalar_mult_base(s_bytes)
    with jax.named_scope("key_mult"):
        key = curve.scalar_mult_var_bigcache_mxu(k_bytes, tables_cache, idx)
    with jax.named_scope("compress"):
        encoded = curve.compress(curve.add(base, key))
    r_match = jnp.all(encoded == r_bytes, axis=-1)
    return table_valid & s_ok & r_match


def verify_msgs_bigcache(
    tables_cache: jnp.ndarray,  # [cap, 64, 16, 4, 32] shared table cache
    table_valid: jnp.ndarray,  # [B] bool
    idx: jnp.ndarray,  # [B] int32 row index into the cache
    r_bytes: jnp.ndarray,  # [B, 32] uint8
    s_bytes: jnp.ndarray,  # [B, 32] uint8
    msg_buf: jnp.ndarray,  # [B, NBLK*128] uint8 prepadded R||A||M
    n_blocks: jnp.ndarray,  # [B] int32 SHA-512 block counts
    s_ok: jnp.ndarray,  # [B] bool
) -> jnp.ndarray:
    """Fully-fused bulk path: the challenge k = SHA-512(R||A||M) mod L is
    computed on device (ops/sha512.challenge_batch) instead of on one host
    thread — the bulk-replay shape (SURVEY.md §3.4) where per-sig host
    hashing would cap throughput."""
    from . import sha512

    k_bytes = sha512.challenge_batch(msg_buf, n_blocks)
    return verify_prehashed_bigcache(
        tables_cache, table_valid, idx, r_bytes, s_bytes, k_bytes, s_ok
    )


verify_prehashed_jit = jax.jit(verify_prehashed)
verify_prehashed_table_jit = jax.jit(verify_prehashed_table)
