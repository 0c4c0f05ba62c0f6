"""tendermint-tpu: a TPU-native BFT state-machine-replication framework.

A from-scratch re-design of the capabilities of morph-l2/tendermint (the Morph
L2 fork of Tendermint Core v0.34.x) for TPU hardware:

- host plane: deterministic consensus state machine, stores, WAL, p2p, RPC —
  idiomatic Python (asyncio) with C++ where the reference leans on native code;
- device plane: the signature-verification hot path (vote ingestion, commit
  verification, blocksync replay, light-client bisection, BLS aggregation) as
  batched JAX/XLA programs sharded over a `jax.sharding.Mesh`.

Layout (mirrors SURVEY.md §1-2 of this repo):
    crypto/    host reference crypto (ed25519, merkle, hashes) + verifier API
    ops/       JAX/TPU kernels: field/curve arithmetic, SHA-2, batch verify
    parallel/  device mesh, shard_map-sharded verification, collectives
    models/    end-to-end verification "models" (commit verifier, replay
               pipeline) — the jittable computation graphs fed to the mesh
    types/     core chain types: Block/Vote/Commit/ValidatorSet, sign-bytes
    consensus/ BFT state machine, WAL, timeout ticker
    state/     block executor + state store
    store/     block store
    l2node/    L2 execution-node port (no mempool — txs pulled from L2)
    abci/      application port (ABCI semantics) + example kvstore
    privval/   validator signing with double-sign protection
    libs/      service lifecycle, events, bit arrays, misc runtime
    utils/     bytes/varint/hex helpers
"""

__version__ = "0.1.0"
