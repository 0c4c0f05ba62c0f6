"""Device-plane kernels (JAX/XLA; no Pallas kernel).

The reference's compute-heavy primitives (SURVEY.md §2.2) re-designed for TPU:
batched ed25519 verification (field/curve arithmetic over 2^255-19, SHA-512,
double-scalar multiplication), batched SHA-2, BLS12-381. Everything operates
on fixed-shape batches, is `jit`/`vmap`/`shard_map` friendly, and uses int32
lane arithmetic (radix-2^8 limbs) so it compiles natively on TPU (no 64-bit
integer ops).
"""
