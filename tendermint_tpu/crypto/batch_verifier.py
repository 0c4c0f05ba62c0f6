"""Host orchestration for TPU batch signature verification.

This is the framework's `crypto.BatchVerifier` — the interface the upstream
reference only grew in v0.35 and this fork lacks entirely (SURVEY.md: "no
crypto.BatchVerifier interface anywhere in this fork"). Call sites that the
reference serializes one verify at a time (types/vote_set.go:205,
types/validator_set.go:693-715, blocksync/reactor.go:553, light/verifier.go:58
in /root/reference) instead push (pubkey, msg, sig) triples here and get an
accept bitmap back.

Responsibilities:
- per-item host work: SHA-512 challenge k = H(R||A||M) mod L (arbitrary
  message length lives here, not in the fixed-shape kernel) and the s < L
  range check; bulk batches instead fuse the challenge hashing into the
  device program (ops/sha512.challenge_batch);
- shape discipline: batches are padded up to a small set of bucket sizes so
  XLA compiles a handful of programs, not one per batch size;
- the validator-table cache, in two tiers. Consensus re-verifies the SAME
  pubkeys every height (2N sigs/height from one validator set — SURVEY.md
  §3.3), so each pubkey's decompressed negated table is built once and kept
  device-resident. Small (latency-sensitive, vote-sized) batches use radix-16
  window tables (2 KiB/key as canonical uint8 limbs, cheap to build inline);
  bulk batches (blocksync/light replay) use doubling-free fixed-window tables
  (128 KiB/key, ~64x the build cost — amortized over thousands of reuses,
  2.5x faster to verify);
- mixed key types: a round's secp256k1 rows verify on the host's
  cores (crypto/secp_native.py) while the ed25519 batch runs on the
  device; sr25519 rows verify on host;
- optional mesh sharding: with a `jax.sharding.Mesh`, batches of at least
  `mesh_min_rows` rows are row-sharded across the mesh devices
  (`NamedSharding` over every mesh axis) so one coalesced scheduler round
  spreads over ICI — the "data-parallel batch sharding" strategy of
  SURVEY.md §2.3. Rounds below the threshold run the REPLICATED program
  family instead (every device computes the whole small batch — no
  collective traffic, single-chip latency), so live consensus rounds
  never pay shard/gather overhead just because a mesh is configured.
  Uneven tails are handled by padding: the sharded bucket is rounded up
  to a multiple of the device count and the pad rows are verdict-inert
  (all-zero rows with s_ok False), so every device receives an equal row
  slab and the gathered bitmap is bit-identical to the single-device
  path.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from itertools import compress

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import default_tracer
from ..ops import ed25519_batch
from . import secp_native
from .ed25519 import L
from .shape_registry import (
    DEFAULT_BUCKET_LADDER,
    ShapeRegistry,
    default_shape_registry,
)

# Bucket sizes: small buckets for consensus latency (votes trickle in),
# large for blocksync/light-client bulk replay. The canonical ladder now
# lives in crypto/shape_registry (one process-wide source so the
# scheduler, the prewarmer and every verifier agree); this alias keeps
# the historical name importable.
BUCKETS = DEFAULT_BUCKET_LADDER

# keys of a device-resident table store where the backend keeps no
# memory statistics (XLA:CPU: the tests, a node beside a verify service);
# on a device that reports `bytes_limit` the capacity is derived from it
# (_device_table_capacity). Small tier: radix-16 window tables, 2 KiB/key.
# Big tier: fixed-window tables, 128 KiB/key as canonical uint8 limbs.
# Both stores allocate lazily and grow in power-of-two row counts, so a
# capacity only bounds the worst case. Read on a TPU v5 lite (PERF.md
# sections 4 to 6, PR 27): `bytes_limit` 16,909,336,064, so 16,384 keys;
# the big store takes exactly its data (XLA lays the row dimension out
# minor-most, so nothing pads): 134,218,752 bytes at 1,024 rows,
# 2,147,500,032 at the 16,384 a 10,000-key committee allocates; a
# 512-key build chunk takes 0.13 s once the build program is loaded
# (19-22 s from the compile cache); with the three big-tier programs a
# 10,000-row request can reach loaded, 2,451,370,496 bytes are in use
# (304 MB beside the store); a `big@16384` execution holds 153 MB of
# temporaries whatever the key count (the compiler's count, not a reading).
TABLE_CACHE_CAPACITY = 4096

# one big-tier table: 64 windows x 16 entries x 4 coordinates x 32 limbs
BIG_TABLE_BYTES = 64 * 16 * 4 * 32

# the share of a device's memory the big store may grow to. A quarter
# leaves three for the loaded programs (0.3 GB read), a round's operands
# and temporaries, the copy a growth or an install beside a round in
# flight makes (at most one more store), and whatever else the process
# keeps there.
TABLE_STORE_SHARE = 0.25

# batches >= this bucket size use the big (doubling-free) tier; smaller
# batches are latency-sensitive (live votes) and must not stall on the big
# tier's expensive one-time table build
BIGTABLE_MIN = 512

# from this many secp256k1 rows a mixed round prepares them with its
# ed25519 rows and verifies them beside its device program; fewer take
# one host call inside the round
SECP_PREPARED_MIN = 32

# batches below this row count stay on ONE device even under a mesh:
# a sharded dispatch pays shard + all-gather overhead that only
# amortizes on bulk rounds, while consensus rounds (O(validators) rows)
# want raw latency. 1024 keeps every vote-path bucket (8..512)
# unsharded and shards the bulk rungs (2048+) where the throughput knee
# lives. Override via [scheduler] mesh_min_rows / TM_TPU_MESH_MIN_ROWS.
DEFAULT_MESH_MIN_ROWS = 1024

# initial allocated rows of the lazy table stores
TABLE_ROWS_MIN = 128

# unseen keys are built this many at a time: big-tier tables are 128 KiB
# each, so building thousands of keys at once would transiently hold GiBs
TABLE_BUILD_CHUNK = 512


@contextlib.contextmanager
def _traced(name: str, **fields):
    """With the tracer on: a span `name` on its ring and, for whenever
    a profiler session is open, a `jax.profiler.TraceAnnotation` of the
    same name in the profiler's own trace, on its clock. Yields whether
    the tracer is on. Off: nothing but the attribute read."""
    tracer = default_tracer()
    if not tracer.enabled:
        yield False
        return
    with tracer.span(name, **fields), jax.profiler.TraceAnnotation(name):
        yield True


@dataclass(frozen=True)
class SigItem:
    pubkey: bytes  # 32 bytes (ed25519) or 33 bytes (secp256k1 compressed)
    msg: bytes
    sig: bytes  # 64 bytes
    key_type: str = "ed25519"


class SigBatch(list):
    """The SigItems of one gather, with the instant the gather began
    (`t_gather_ns`, `time.perf_counter_ns()`): a remote scheduler's
    `submit_sync` puts it on the submission's wire trailer, and the
    service times the caller's gather from it. Every verifier takes it
    as the list it is."""

    __slots__ = ("t_gather_ns",)

    def __init__(self, items=(), t_gather_ns: int = 0):
        super().__init__(items)
        self.t_gather_ns = t_gather_ns


# L's 32 bytes, most significant first
_L_BE = np.frombuffer(L.to_bytes(32, "big"), dtype=np.uint8)


def _column(chunks: list, width: int) -> np.ndarray:
    """[len(chunks), width] uint8 over one join of `chunks`, each
    `width` bytes (any buffer)."""
    return np.frombuffer(b"".join(chunks), dtype=np.uint8).reshape(-1, width)


def _rows32(b: int, at: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """A [b, 32] uint8 operand: batch row `at[j]` is `rows[j]`, every
    other row (malformed, or padding up to the bucket) zero."""
    out = np.zeros((b, 32), dtype=np.uint8)
    out[at] = rows
    return out


def _below_l(s: np.ndarray) -> np.ndarray:
    """s < L for each row of `s` ([m, 32] uint8, little-endian scalars):
    the most significant byte that differs from L's decides, and s == L
    (no byte differs: argmax reads 0, where both are equal) is False."""
    s_be = s[:, ::-1]
    first = (s_be != _L_BE).argmax(axis=1)
    return s_be[np.arange(len(s)), first] < _L_BE[first]


def _challenge_rows(b: int, at: np.ndarray, good: list) -> np.ndarray:
    """The [b, 32] operand of challenges k = SHA-512(R || A || M) mod L
    (crypto.ed25519.challenge, little-endian) of the well-formed items
    `good`, at batch rows `at`. hashlib's hash, the reduction and
    `to_bytes` are what a row costs on the host."""
    sha512, from_bytes, cat = hashlib.sha512, int.from_bytes, b"".join
    ks = [
        (
            from_bytes(
                sha512(cat((it.sig[:32], it.pubkey, it.msg))).digest(),
                "little",
            )
            % L
        ).to_bytes(32, "little")
        for it in good
    ]
    return _rows32(b, at, _column(ks, 32))


class _PreparedBatch:
    """Host-assembled batch whose device dispatch is deferred. `run()`
    blocks for the verdict bitmap (len == n). The prepare/run split is
    what lets parallel/scheduler overlap the next batch's host assembly
    with the current batch's device round. `devices` is the mesh shard
    count the dispatch will use (1 = unsharded — the scheduler stamps
    its device_round span with it). `host_rows` counts the rows of
    other key types, verified beside the ed25519 device batch (the
    ledger books them apart from that batch's padded bucket)."""

    __slots__ = ("n", "run", "devices", "host_rows")

    def __init__(self, n: int, run, devices: int = 1, host_rows: int = 0):
        self.n = n
        self.run = run
        self.devices = devices
        self.host_rows = host_rows


def _verify_cached_small(tables, tvalid, idx, rb, sb, kb, s_ok):
    """Small tier: gather each row's radix-16 window table and verify."""
    t = jnp.take(tables, jnp.maximum(idx, 0), axis=0)
    tv = jnp.take(tvalid, jnp.maximum(idx, 0), axis=0) & (idx >= 0)
    return ed25519_batch.verify_prehashed_table(t, tv, rb, sb, kb, s_ok)


def _use_mxu_gather() -> bool:
    """TM_TPU_MXU_GATHER=1 swaps the big tier's per-window gathers for
    one-hot MXU matmuls (ops/curve25519.scalar_mult_var_bigcache_mxu).
    Slower on the earlier executor, not measured on the current chip
    (ROADMAP S5/D4). Read ONCE at BatchVerifier construction: the
    selection must not depend on when each shape bucket happens to
    trace."""
    import os

    return os.environ.get("TM_TPU_MXU_GATHER") == "1"


def _verify_cached_big(tables, tvalid, idx, rb, sb, kb, s_ok):
    """Big tier: doubling-free fixed-window verify against the shared
    cache (the kernel gathers per-window slices internally so the 128 KiB
    per-key tables are never materialized per batch row)."""
    tv = jnp.take(tvalid, jnp.maximum(idx, 0), axis=0) & (idx >= 0)
    return ed25519_batch.verify_prehashed_bigcache(
        tables, tv, jnp.maximum(idx, 0), rb, sb, kb, s_ok
    )


def _verify_cached_big_mxu(tables, tvalid, idx, rb, sb, kb, s_ok):
    """_verify_cached_big with the MXU one-hot gather (see _use_mxu_gather)."""
    tv = jnp.take(tvalid, jnp.maximum(idx, 0), axis=0) & (idx >= 0)
    return ed25519_batch.verify_prehashed_bigcache_mxu(
        tables, tv, jnp.maximum(idx, 0), rb, sb, kb, s_ok
    )


def _verify_cached_msgs(tables, tvalid, idx, rb, sb, msg_buf, n_blocks, s_ok):
    """Big tier + SHA-512 challenges fused on device (one jit)."""
    tv = jnp.take(tvalid, jnp.maximum(idx, 0), axis=0) & (idx >= 0)
    return ed25519_batch.verify_msgs_bigcache(
        tables, tv, jnp.maximum(idx, 0), rb, sb, msg_buf, n_blocks, s_ok
    )


def _jit_program_family(big_impl, mesh: Mesh | None, sharded: bool) -> dict:
    """One compiled family of the four verify programs.

    mesh=None: plain single-device jit (the meshless verifier).
    mesh + sharded=False: every operand replicated over the mesh — each
    device computes the whole batch, no collective traffic, wall time of
    one device. This is what rounds below `mesh_min_rows` dispatch, so a
    configured mesh never taxes tiny consensus rounds.
    mesh + sharded=True: the batch axis row-sharded over EVERY mesh axis
    (major-to-minor — ("batch",) single-host meshes and ("dcn", "batch")
    cross-host meshes both collapse onto dim 0), table operands
    replicated, verdict bitmap fully replicated on exit (an implicit
    all-gather riding ICI).
    """
    if mesh is None:
        jit = jax.jit
        return {
            "generic": jit(ed25519_batch.verify_prehashed),
            "small": jit(_verify_cached_small),
            "big": jit(big_impl),
            "msgs": jit(_verify_cached_msgs),
        }
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(tuple(mesh.axis_names))) if sharded else rep
    return {
        "generic": jax.jit(
            ed25519_batch.verify_prehashed,
            in_shardings=(sh, sh, sh, sh, sh),
            out_shardings=rep,
        ),
        # table caches stay replicated; the batch axis shards
        "small": jax.jit(
            _verify_cached_small,
            in_shardings=(rep, rep, sh, sh, sh, sh, sh),
            out_shardings=rep,
        ),
        "big": jax.jit(
            big_impl,
            in_shardings=(rep, rep, sh, sh, sh, sh, sh),
            out_shardings=rep,
        ),
        "msgs": jax.jit(
            _verify_cached_msgs,
            in_shardings=(rep, rep, sh, sh, sh, sh, sh, sh),
            out_shardings=rep,
        ),
    }


def _device_table_capacity(device) -> int:
    """Keys a table store may hold on `device`: TABLE_STORE_SHARE of the
    memory the backend says it may use, counted in big-tier tables and
    rounded down to the power-of-two rows `_grow` allocates (a v5e's 16
    GB gives 16,384 or 32,768 by what `bytes_limit` reads). Where the
    backend keeps no memory statistics (XLA:CPU) TABLE_CACHE_CAPACITY
    stands."""
    limit = int((device.memory_stats() or {}).get("bytes_limit", 0))
    keys = int(limit * TABLE_STORE_SHARE) // BIG_TABLE_BYTES
    if keys < 1:
        return TABLE_CACHE_CAPACITY
    return max(TABLE_ROWS_MIN, 1 << (keys.bit_length() - 1))


# A build chunk's way into the store. On the TPU the store's rows are its
# minor-most dimension, and XLA's scatter (`.at[rows].set`) transposes
# the whole store there and back: two more stores of temporaries and a
# third for the result, for every chunk (compiled for a v5e at 16,384
# rows: 4.4 GB of temporaries beside 2 GiB in and 2 GiB out). A
# dynamic-update-slice of a donated store is written in place with no
# temporary at all, so the two installs below are made of those.


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _install_block(tables, valid, start, new_tables, new_valid):
    """The whole chunk (its padding too) at rows start, start+1, ...:
    fresh rows, which nothing reads before a key is given them."""
    return (
        jax.lax.dynamic_update_slice_in_dim(tables, new_tables, start, 0),
        jax.lax.dynamic_update_slice_in_dim(valid, new_valid, start, 0),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _install_rows(tables, valid, rows, n, new_tables, new_valid):
    """The chunk's first `n` entries, entry j into row rows[j]: rows
    taken over from evicted keys lie anywhere."""

    def one(j, stores):
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                store, jax.lax.dynamic_slice_in_dim(new, j, 1), rows[j], 0
            )
            for store, new in zip(stores, (new_tables, new_valid))
        )

    return jax.lax.fori_loop(0, n, one, (tables, valid))


@functools.partial(jax.jit, static_argnums=2)
def _grown(tables, valid, rows: int):
    """The store at `rows` rows: one new allocation beside the old one
    (zeros then `.at[:cur].set` held three)."""
    pad = [(0, rows - tables.shape[0])] + [(0, 0)] * (tables.ndim - 1)
    return jnp.pad(tables, pad), jnp.pad(valid, pad[:1])


class _TableCache:
    """One device-resident table store (pubkey -> row), lazily grown up
    to `capacity` rows. Keys that no longer fit take the rows of the
    least recently used ones; the store never clears itself, so a hot
    committee stays resident while validator sets rotate past it. A
    round with more distinct keys than `capacity` gets no tables
    (`lookup` returns None, counted in `fallback_rounds`) and is
    answered by the generic program.

    A snapshot in flight keeps its arrays: `tables` and `valid` are
    replaced, never written, once `lookup` or `arrays` has handed them
    out (`_lent`), so the round that holds an old pair still reads the
    rows its `idx` names, whatever was installed or evicted since. Only
    arrays nothing else holds are donated to the next install.

    Thread-safety: all methods take the store's lock — the vote
    micro-batcher calls verify() from an executor thread while the event
    loop verifies serially, and a warm runs on a thread of its own. A
    build holds the lock, so `lookup` finds and snapshots under one hold
    and nothing can evict a round's rows in between."""

    def __init__(
        self, lock, build_fn, entry_shape, capacity, nshards, registry=None,
        tier="small", sharding=None,
    ):
        self._lock = lock
        self._build_fn = build_fn
        self._entry_shape = entry_shape  # per-key table dims after the row
        self._capacity = max(1, capacity)
        self._nshards = nshards
        self._registry = registry or default_shape_registry()
        self._sharding = sharding  # replicated over the mesh, or None
        self.tier = tier
        self._idx: dict[bytes, int] = {}
        self._keys: list[bytes] = []  # row -> pubkey, for the rows in use
        # row -> the last lookup (or warm) that named its key; one slot
        # more, at the end, for a key without a row (-1) to stamp
        self._used = np.zeros(self._capacity + 1, dtype=np.int64)
        self._tick = 0
        self._lent = False
        self.tables: jnp.ndarray | None = None
        self.valid: jnp.ndarray | None = None
        self.built = 0  # keys a table was built for, ever
        self.evictions = 0  # keys that gave their row to another, ever
        self.fallback_rounds = 0  # lookups the store could not hold, ever

    def _grow(self, needed_rows: int) -> None:
        rows = TABLE_ROWS_MIN
        while rows < needed_rows:
            rows *= 2
        rows = min(rows, self._capacity)
        cur = 0 if self.tables is None else self.tables.shape[0]
        if rows <= cur:
            return
        if cur:
            self.tables, self.valid = _grown(self.tables, self.valid, rows)
        else:
            # canonical uint8 limbs (neg_pubkey_table): 128 KiB/key big tier
            self.tables = jnp.zeros(
                (rows, *self._entry_shape), dtype=jnp.uint8,
                device=self._sharding,
            )
            self.valid = jnp.zeros(rows, dtype=bool, device=self._sharding)
        self._lent = False

    def _build(self, new: list[bytes], abort=None) -> int:
        """Build + install tables for `new` (distinct, none resident; at
        most `capacity` with the keys of this tick), a chunk at a time,
        each chunk into fresh rows first and then into the rows of the
        least recently used keys. Returns the keys evicted. Caller holds
        the lock."""
        in_use = len(self._keys)
        self._grow(in_use + len(new))
        spill = len(new) - (self._capacity - in_use)
        victims = []
        if spill > 0:
            # rows no key of this tick names, the longest unused first
            idle = np.flatnonzero(self._used[:in_use] < self._tick)
            order = np.argsort(self._used[idle], kind="stable")
            victims = idle[order[:spill]].tolist()
        evicted = 0
        for lo in range(0, len(new), TABLE_BUILD_CHUNK):
            if abort is not None and abort.is_set():
                break  # partial warm is fine; a later ensure finishes it
            chunk = new[lo : lo + TABLE_BUILD_CHUNK]
            b = self._registry.bucket_for(
                len(chunk), multiple_of=self._nshards
            )
            # builds always shard over the full mesh (batch_verifier
            # compiles the build fns with sharded inputs)
            self._registry.record_dispatch(
                "build_" + self.tier, b, devices=self._nshards
            )
            arr = np.zeros((b, 32), dtype=np.uint8)
            for i, pk in enumerate(chunk):
                arr[i] = np.frombuffer(pk, dtype=np.uint8)
            with _traced(
                "crypto.table_build",
                keys=len(chunk), bucket=b, tier=self.tier,
            ):
                tables, valid = self._build_fn(jnp.asarray(arr))
                first = len(self._keys)
                rows = np.zeros(b, dtype=np.int32)
                for i, pk in enumerate(chunk):
                    if len(self._keys) < self._capacity:
                        row = len(self._keys)
                        self._keys.append(pk)
                    else:
                        row = victims[evicted]
                        evicted += 1
                        del self._idx[self._keys[row]]
                        self._keys[row] = pk
                    self._idx[pk] = row
                    rows[i] = row
                self._used[rows[: len(chunk)]] = self._tick
                if self._lent:
                    # a round may hold these arrays: they stay its own,
                    # the install is written into a copy
                    self.tables = jnp.copy(self.tables)
                    self.valid = jnp.copy(self.valid)
                    self._lent = False
                if first + b <= self.tables.shape[0]:
                    self.tables, self.valid = _install_block(
                        self.tables, self.valid, first, tables, valid
                    )
                else:
                    self.tables, self.valid = _install_rows(
                        self.tables, self.valid, jnp.asarray(rows),
                        len(chunk), tables, valid,
                    )
                self.built += len(chunk)
                # the span is the build, not its enqueue, and the next
                # chunk's output is not allocated while this one runs:
                # enqueued back to back, 20 chunks held 0.9 GB more
                # (3.35 against 2.45 GB while 10,000 keys were built)
                self.tables.block_until_ready()
        self.evictions += evicted
        return evicted

    def _rows_of(self, pubkeys: list[bytes]) -> np.ndarray:
        """Each key's row, -1 without one; the rows found are stamped
        with this tick. Caller holds the lock. One numpy call a step:
        each one may hand the GIL to the host-prep thread for a whole
        switch interval (`table_lookup_ms.catchup` read 17.4 ms with
        seven of them where the parent's per-row loop read 13.5)."""
        get = self._idx.get
        found = np.array([get(pk, -1) for pk in pubkeys], dtype=np.int32)
        self._used[found] = self._tick
        return found

    def _find_or_build(self, pubkeys: list[bytes], abort=None):
        """(each key's row or None where the distinct keys exceed the
        capacity, keys of `pubkeys` without a table on entry, keys
        evicted). Caller holds the lock."""
        self._tick += 1
        found = self._rows_of(pubkeys)
        if not len(found) or found.min() >= 0:
            return found, 0, 0
        missing = np.flatnonzero(found < 0)
        new = list(dict.fromkeys(pubkeys[j] for j in missing))
        if len(self._idx) + len(new) > self._capacity and (
            len(set(pubkeys)) > self._capacity
        ):
            return None, len(missing), 0
        evicted = self._build(new, abort)
        return self._rows_of(pubkeys), len(missing), evicted

    def ensure(self, pubkeys: list[bytes], abort=None) -> bool:
        """Build + install tables for the unseen keys among `pubkeys`
        (a warm). Returns False, and builds nothing, when the keys alone
        exceed the capacity. `abort` (threading.Event) stops between
        chunks — shutdown must not wait for a multi-chunk build."""
        with self._lock:
            return self._find_or_build(pubkeys, abort)[0] is not None

    def lookup(self, pubkeys: list[bytes], positions: list[int], b: int):
        """One round's tables: `pubkeys[j]` is the key of batch row
        `positions[j]`. Returns ((tables, valid, idx[b]) or None where
        the round's distinct keys exceed the capacity, rows whose key
        had no table when the lookup began, keys evicted). Finding,
        building and the snapshot happen under one hold of the lock."""
        with self._lock:
            found, missing, evicted = self._find_or_build(pubkeys)
            if found is None:
                self.fallback_rounds += 1
                return None, missing, 0
            idx = np.full(b, -1, dtype=np.int32)
            if positions[-1] == len(positions) - 1:  # every row well formed
                idx[: len(positions)] = found
            else:
                idx[positions] = found
            self._lent = True
            return (self.tables, self.valid, idx), missing, evicted

    def arrays(self):
        """(tables, valid) as they stand, for a caller that runs a
        program over them (the prewarm), or None before the first key."""
        with self._lock:
            if self.tables is None:
                return None
            self._lent = True
            return self.tables, self.valid

    def stats(self) -> dict:
        """What the store holds and what it has done, for the service
        dump's `table_store` block. Takes no lock: a dump must not wait
        for a build."""
        tables = self.tables
        rows = 0 if tables is None else int(tables.shape[0])
        return {
            "rows_allocated": rows,
            "keys_resident": len(self._idx),
            "bytes": rows * (int(np.prod(self._entry_shape)) + 1),
            "evictions": self.evictions,
            "fallback_rounds": self.fallback_rounds,
        }


class BatchVerifier:
    """Batched ed25519 verifier over one device or a device mesh.

    mesh=None: single-device jit (the real-TPU single-chip path).
    mesh=Mesh(..., ('batch',)): batches of >= mesh_min_rows rows shard
    the batch axis over the mesh (accept bitmap fully replicated on exit
    — an implicit all-gather riding ICI); smaller batches run the
    replicated program family at single-chip latency.
    """

    def __init__(
        self,
        mesh: Mesh | None = None,
        min_device_batch: int = 8,
        table_cache_capacity: int | None = None,
        device_challenge_min: int | None = None,
        bigtable_min: int = BIGTABLE_MIN,
        shape_registry: ShapeRegistry | None = None,
        mesh_min_rows: int | None = None,
    ):
        """min_device_batch: below this size the host CPU verifies serially
        — a device round-trip costs more than a handful of host verifies
        (the adaptive micro-batching tradeoff, SURVEY.md §7.3 hard part 3).
        Set to 0 to force everything onto the device.

        table_cache_capacity: keys each table store holds before the
        least recently used give way. None (default) derives it from the
        device's memory (_device_table_capacity).

        device_challenge_min: batches >= this size compute the SHA-512
        challenges on device (fused into the verify program) instead of on
        the host thread. None (default) keeps hashing on the host: hashlib
        sustains ~600k sigs/s on one core, so host hashing only becomes the
        bottleneck when the device outruns the host hasher — enable this
        (e.g. 2048) there. The fused program verifies correctly; its
        throughput has not been measured on the current chip (ROADMAP
        D4), and the straight-line SHA form the TPU gets
        (ops/sha512.py) has never been compiled by a test.

        bigtable_min: batches >= this bucket size use doubling-free
        fixed-window tables (2.5x faster steady-state, ~64x build cost);
        smaller batches use cheap-to-build radix-16 tables so live vote
        verification never stalls behind a table build.

        shape_registry: where (tier, bucket, devices) program shapes +
        dispatch counts are recorded; defaults to the process-wide
        registry so bench/test shape budgets see every verifier in the
        process.

        mesh_min_rows: under a mesh, batches below this row count stay
        unsharded (replicated) for latency; None reads
        TM_TPU_MESH_MIN_ROWS, defaulting to DEFAULT_MESH_MIN_ROWS.
        Ignored without a mesh."""
        self._mesh = mesh
        self._min_device_batch = min_device_batch
        self._registry = shape_registry or default_shape_registry()
        self._device_challenge_min = device_challenge_min
        self._bigtable_min = bigtable_min
        if mesh_min_rows is None:
            import os

            # unset OR "0" both mean "use the built-in default" (node
            # assembly always exports a real value)
            raw = os.environ.get("TM_TPU_MESH_MIN_ROWS", "")
            mesh_min_rows = (
                int(raw) if raw.strip() and int(raw) > 0
                else DEFAULT_MESH_MIN_ROWS
            )
        self._mesh_min_rows = max(1, int(mesh_min_rows))
        big_impl = (
            _verify_cached_big_mxu if _use_mxu_gather() else _verify_cached_big
        )
        # process-shutdown flag: the DEFAULT abort for every warm on this
        # verifier (incl. the executor-threaded bulk warms) — a thread
        # force-terminated mid-XLA-compile takes the process down, and a
        # non-daemon one would hold exit for the whole build. Set by the
        # node on stop, cleared on start (the default verifier is shared
        # process-wide).
        self.shutdown_event = threading.Event()
        if table_cache_capacity is None:
            table_cache_capacity = _device_table_capacity(
                jax.devices()[0] if mesh is None else mesh.devices.flat[0]
            )
        rep = None  # the stores' sharding: replicated over the mesh
        if mesh is None:
            self._nshards = 1
            # device count -> program family; meshless has only the
            # single-device family
            self._progs = {1: _jit_program_family(big_impl, None, False)}
            build_small = jax.jit(ed25519_batch.neg_pubkey_table)
            build_big = jax.jit(ed25519_batch.neg_pubkey_bigtable)
        else:
            self._nshards = mesh.devices.size
            # two families: replicated (rounds < mesh_min_rows dispatch
            # at single-chip latency) and row-sharded (bulk rounds
            # spread over every chip). prepare() picks per batch via
            # shards_for().
            self._progs = {
                1: _jit_program_family(big_impl, mesh, sharded=False),
                self._nshards: _jit_program_family(
                    big_impl, mesh, sharded=True
                ),
            }
            # table builds always shard over the full mesh (bulk warm
            # throughput work; tables come back replicated for both
            # verify families)
            sh = NamedSharding(mesh, P(tuple(mesh.axis_names)))
            rep = NamedSharding(mesh, P())
            build_small = jax.jit(
                ed25519_batch.neg_pubkey_table,
                in_shardings=(sh,),
                out_shardings=(rep, rep),
            )
            build_big = jax.jit(
                ed25519_batch.neg_pubkey_bigtable,
                in_shardings=(sh,),
                out_shardings=(rep, rep),
            )
        # (tier, bucket, rows, devices) shapes whose program has already
        # traced through XLA — the first dispatch of a shape is
        # jit-compile + execute, later ones pure device execute; the
        # tracer splits them so a height's latency table doesn't blame
        # compilation on consensus
        self._seen_shapes: set[tuple[str, int, int, int]] = set()
        # independent locks: a big-tier build (seconds of device work for a
        # bulk replay) must not stall small-tier vote-path verifies
        self._small = _TableCache(
            threading.Lock(),
            build_small,
            (16, 4, 32),
            table_cache_capacity,
            self._nshards,
            registry=self._registry,
            tier="small",
            sharding=rep,
        )
        self._big = _TableCache(
            threading.Lock(),
            build_big,
            (64, 16, 4, 32),
            table_cache_capacity,
            self._nshards,
            registry=self._registry,
            tier="big",
            sharding=rep,
        )
        # secp256k1 keys' points, decompressed once per key, and the
        # threads of their native step (none start before a chunk)
        self._secp_keys = secp_native.KeyCache()
        self._secp_threads = ThreadPoolExecutor(
            secp_native.HOST_THREADS, thread_name_prefix="secp-native"
        )

    # --- mesh topology -----------------------------------------------------

    @property
    def mesh_devices(self) -> int:
        """Devices in the verifier's mesh (1 = meshless)."""
        return self._nshards

    def shards_for(self, n: int) -> int:
        """Devices a batch of `n` rows shards over: the full mesh for
        rounds >= mesh_min_rows, else 1 — the round runs the replicated
        family so tiny consensus rounds keep single-chip latency. The
        dispatch scheduler calls this to stamp rounds `sharded` and the
        prewarmer to enumerate reachable program variants."""
        if self._mesh is None or self._nshards <= 1:
            return 1
        if n < self._mesh_min_rows:
            return 1
        return self._nshards

    # --- table cache -------------------------------------------------------

    def warm(
        self,
        pubkeys: list[bytes],
        bulk: bool = False,
        key_types: list[str] | None = None,
        abort=None,
    ) -> None:
        """Pre-build tables for a validator set (e.g. at height change).
        bulk=True also warms the big (fixed-window) tier ahead of a known
        replay workload so its one-time build cost lands here.

        key_types (aligned with pubkeys) filters to ed25519 rows; without
        it the 32-byte length heuristic is used, which cannot distinguish
        sr25519 ristretto encodings — pass types for mixed sets so garbage
        tables are never built for non-edwards keys.

        `abort` (threading.Event) stops the build between chunks: a warm
        running on a background thread must be interruptible at shutdown
        — a thread force-terminated mid-XLA-compile takes the process
        down with it (SIGSEGV/SIGABRT at interpreter exit, found r4)."""
        if key_types is not None:
            eds = [
                pk
                for pk, t in zip(pubkeys, key_types)
                if t == "ed25519" and len(pk) == 32
            ]
        else:
            eds = [pk for pk in pubkeys if len(pk) == 32]
        if abort is None:
            abort = self.shutdown_event
        self._small.ensure(eds, abort=abort)
        if bulk and not abort.is_set():
            self._big.ensure(eds, abort=abort)

    def prewarm_buckets(
        self,
        buckets=None,
        tiers: tuple[str, ...] = ("small", "big", "generic"),
        abort=None,
    ) -> list[dict]:
        """Ahead-of-time compile/load the verify programs for the
        canonical bucket ladder, so a (re)started node pays the
        per-shape XLA program cost at assembly on the warm thread
        instead of mid-height (PERF_ANALYSIS §10: a cold bisect run
        loaded 44 distinct shapes). Each program executes once with
        fully-rejected padded lanes (all-zero rows, s_ok False —
        verdict-inert by
        construction), the exact shapes steady state dispatches: the
        small/big tier split follows `bigtable_min`, and the table
        operand uses the stores' initial row allocation.

        Run AFTER the validator-table warm (the node's warm thread does):
        the cached tiers' programs are also shaped by the table-store row
        allocation, so prewarming against the LIVE stores compiles the
        exact operand shapes steady state dispatches — stores grown by a
        later rotation past the next power-of-two row rung recompile
        those shapes once, a bounded ladder of their own. Known gap: the
        big_msgs tier (device_challenge_min > 0) is additionally shaped
        by the batch's message-length class and cannot be prewarmed
        ahead of knowing it.

        Under a mesh the ladder is AOT-loaded PER DEVICE VARIANT: each
        rung prewarms the replicated (devices=1) program when a batch
        below mesh_min_rows can land in it, and the row-sharded
        (devices=N) program when one at/above the threshold can — the
        exact reachable set, so neither family compiles mid-height.

        Returns one {tier, bucket, rows, devices, seconds} entry per
        program executed (tools/prewarm.py persists these as the
        prewarm manifest). `abort` (threading.Event, default the
        verifier shutdown flag) stops between programs — shutdown must
        not wait out the ladder.
        """
        if abort is None:
            abort = self.shutdown_event
        ladder = tuple(buckets) if buckets else self._registry.ladder
        # the live stores where they exist (every lane is rejected, so
        # what the tables hold is never read into a verdict): a second
        # store of zeros would hold 2 GiB beside a 10,000-key committee
        small_tables, tvalid_small = self._small.arrays() or (
            jnp.zeros((TABLE_ROWS_MIN, 16, 4, 32), dtype=jnp.uint8),
            jnp.zeros(TABLE_ROWS_MIN, dtype=bool),
        )
        big_tables, tvalid_big = self._big.arrays() or (
            jnp.zeros((TABLE_ROWS_MIN, 64, 16, 4, 32), dtype=jnp.uint8),
            jnp.zeros(TABLE_ROWS_MIN, dtype=bool),
        )
        rows_small = int(small_tables.shape[0])
        rows_big = int(big_tables.shape[0])
        out: list[dict] = []
        seen_prog: set[tuple[str, int, int]] = set()
        rungs = sorted({int(b) for b in ladder})
        for i, raw_b in enumerate(rungs):
            prev_rung = rungs[i - 1] if i else 0
            # reachable device variants for this rung: a batch of n rows
            # lands here when prev_rung < n <= raw_b, so the unsharded
            # family is reachable iff some such n < mesh_min_rows and
            # the sharded one iff some such n >= mesh_min_rows
            variants = []
            if self._nshards <= 1 or prev_rung + 1 < self._mesh_min_rows:
                variants.append(1)
            if self._nshards > 1 and raw_b >= self._mesh_min_rows:
                variants.append(self._nshards)
            for devs in variants:
                b = self._registry.bucket_for(raw_b, multiple_of=devs)
                zeros32 = np.zeros((b, 32), dtype=np.uint8)
                idx = jnp.asarray(np.zeros(b, dtype=np.int32))
                s_ok = jnp.asarray(np.zeros(b, dtype=bool))
                family = self._progs.get(devs) or self._progs[1]
                bucket_tier = "big" if b >= self._bigtable_min else "small"
                for tier in tiers:
                    if abort is not None and abort.is_set():
                        return out
                    if tier in ("small", "big") and tier != bucket_tier:
                        continue  # steady state never runs this shape
                    if (tier, b, devs) in seen_prog:
                        continue  # rungs that collapse after rounding
                    seen_prog.add((tier, b, devs))
                    t0 = time.perf_counter()
                    if tier == "small":
                        rows = rows_small
                        self._dispatch(
                            family["small"], "small", b, b,
                            small_tables, tvalid_small, idx,
                            zeros32, zeros32, zeros32, s_ok,
                            devices=devs,
                        )
                    elif tier == "big":
                        rows = rows_big
                        self._dispatch(
                            family["big"], "big", b, b,
                            big_tables, tvalid_big, idx,
                            zeros32, zeros32, zeros32, s_ok,
                            devices=devs,
                        )
                    elif tier == "generic":
                        rows = 0
                        self._dispatch(
                            family["generic"], "generic", b, b,
                            zeros32, zeros32, zeros32, zeros32, s_ok,
                            devices=devs,
                        )
                    else:
                        raise ValueError(
                            f"unknown prewarm tier {tier!r}"
                        )
                    out.append(
                        {
                            "tier": tier,
                            "bucket": int(b),
                            "rows": rows,
                            "devices": devs,
                            "seconds": round(
                                time.perf_counter() - t0, 3
                            ),
                        }
                    )
        return out

    # --- verification ------------------------------------------------------

    def _dispatch(
        self, fn, tier: str, b: int, n: int, *args, devices: int = 1
    ) -> np.ndarray:
        """Run one jitted verify program and block for the result, tracing
        the wall time as `crypto.jit_compile` on a shape's first dispatch
        (compile + execute) and `crypto.device_execute` afterwards.
        `devices` is the mesh shard count of this round's batch axis (1 =
        unsharded/replicated) — part of the program's shape identity."""
        # cached tiers' programs are also shaped by the table-store row
        # allocation (arg 0; _TableCache grows it in powers of two) — a
        # grown store is a NEW program even at the same batch bucket
        rows = (
            int(args[0].shape[0])
            if tier in ("small", "big", "big_msgs")
            else 0
        )
        key = (tier, b, rows, devices)
        first = key not in self._seen_shapes
        self._seen_shapes.add(key)
        self._registry.record_dispatch(tier, b, rows, devices=devices)
        with _traced(
            "crypto.jit_compile" if first else "crypto.device_execute",
            batch=n, bucket=b, tier=tier, devices=devices,
        ):
            return np.asarray(fn(*args))  # blocks until device-ready

    def _table_lookup(self, cache, items, rows, b: int, n: int):
        """One round's way to its tables: (tables, valid, idx[b]) for
        the well-formed `rows` of `items` from `cache`, or None where
        the round has more distinct keys than the store holds (the
        caller then runs the generic program). Traced as
        `crypto.table_lookup`, around the `crypto.table_build` of every
        chunk it had to build: `built` keys without a table, `resident`
        rows of the round that needed none built (their key had one when
        the lookup began; a malformed row has no key to look up),
        `evicted` keys that gave their rows up, `fallback`."""
        built = cache.built
        with default_tracer().span(
            "crypto.table_lookup", n=n, tier=cache.tier
        ) as span:
            snap, missing, evicted = cache.lookup(
                [items[i].pubkey for i in rows], rows, b
            )
            span.set(
                built=cache.built - built, resident=n - missing,
                evicted=evicted, fallback=snap is None,
            )
        return snap

    def table_store_stats(self) -> dict:
        """Per tier: rows_allocated, keys_resident, bytes, evictions,
        fallback_rounds (the last two cumulative)."""
        return {c.tier: c.stats() for c in (self._small, self._big)}

    def verify(self, items: list[SigItem]) -> np.ndarray:
        """Returns a bool accept bitmap aligned with `items`.

        Mixed-key commits (BASELINE config 4; reference allows ed25519 and
        secp256k1 validators side by side, crypto/secp256k1/secp256k1.go:192)
        are partitioned per key type (`_prepare_mixed`) and the bitmap is
        re-interleaved.
        """
        return self.prepare(items).run()

    def _prepare_mixed(self, items: list[SigItem], kinds: list[str]):
        """A mixed-key batch, prepared like an ed25519 one: the ed25519
        rows' own `prepare` (traced as `crypto.ed_prep`, `rows`) and,
        from `SECP_PREPARED_MIN` secp256k1 rows, their prep
        (secp_native.prep_msgs over the verifier's KeyCache, traced as
        `crypto.secp_prep`). `run` starts their native step on the
        host's threads, runs the ed25519 batch on the device meanwhile
        and waits for both; the secp256k1 share is traced as
        `crypto.secp_verify` (`rows`, `rejected`, `engine`), from its
        start to its verdicts. Fewer secp256k1 rows take one host call
        after the device batch, their prep inside it. Other key types
        verify on host; the bitmap is re-interleaved."""
        n = len(items)
        ed_idx = [i for i, k in enumerate(kinds) if k == "ed25519"]
        secp_idx = [i for i, k in enumerate(kinds) if k == "secp256k1"]
        other_idx = [
            i for i, k in enumerate(kinds) if k not in ("ed25519", "secp256k1")
        ]
        ed = None
        if ed_idx:
            with _traced("crypto.ed_prep", rows=len(ed_idx)):
                ed = self.prepare([items[i] for i in ed_idx])
        secp = [items[i] for i in secp_idx]
        m = len(secp)
        cols = (
            [it.pubkey for it in secp],
            [it.msg for it in secp],
            [it.sig for it in secp],
        )
        prepared = (
            secp_native.prep_msgs(*cols, self._secp_keys)
            if m >= SECP_PREPARED_MIN else None
        )

        def _run() -> np.ndarray:
            out = np.zeros(n, dtype=bool)
            tracer = default_tracer()
            if prepared is not None:
                t0 = time.perf_counter()
                wait = secp_native.start(prepared, self._secp_threads)
            if ed is not None:
                out[ed_idx] = ed.run()
            if prepared is not None:
                verdicts = wait()
                tracer.add_span(
                    "crypto.secp_verify", t0, time.perf_counter() - t0,
                    rows=m, engine="host",
                    rejected=m - int(np.count_nonzero(verdicts)),
                )
                out[secp_idx] = verdicts
            elif m:
                with tracer.span(
                    "crypto.secp_verify", rows=m, engine="host"
                ) as span:
                    verdicts = secp_native.verify_msgs_batch(
                        *cols, self._secp_keys
                    )
                    span.set(rejected=m - int(np.count_nonzero(verdicts)))
                out[secp_idx] = verdicts
            for i in other_idx:
                out[i] = self._verify_host_other(items[i])
            return out

        return _PreparedBatch(
            n, _run, devices=ed.devices if ed else 1,
            host_rows=n - len(ed_idx),
        )

    def prepare(self, items: list[SigItem]) -> "_PreparedBatch":
        """Host-side assembly of one batch: partition decisions, bucket
        padding, array fills and sign-bytes challenge hashing. The
        operands are built by columns: one join and one array a column,
        the well-formed rows scattered by index, and per row only a
        field, a length and (`_challenge_rows`) the hash and its
        reduction. On the chip's host a 10,000-row round read 62.2 ms,
        6.2 us a row, while a Python loop filled the arrays row by row
        (PERF_LEDGER.jsonl, PR 27, `host_prep_ms.live50` of
        `c10k.live`) and reads 22.7 ms, 2.3 us a row, by columns, of
        which 1.6 are hashlib's SHA-512, `% L` and `to_bytes` (PERF.md
        section 6, PR 28). Returns a handle whose `run()` performs the device
        dispatch (cache ensure/snapshot + jitted program) and blocks for
        the verdicts. `verify()` is `prepare(items).run()`; the dispatch
        scheduler splits the two so batch N+1's host assembly overlaps
        batch N's device execution."""
        n = len(items)
        if n == 0:
            return _PreparedBatch(0, lambda: np.zeros(0, dtype=bool))
        kinds = [it.key_type for it in items]
        if kinds.count("ed25519") != n:
            return self._prepare_mixed(items, kinds)
        if n < self._min_device_batch:

            def _run_host() -> np.ndarray:
                from . import ed25519 as host

                return np.array(
                    [
                        host.verify(it.pubkey, it.msg, it.sig)
                        for it in items
                    ],
                    dtype=bool,
                )

            return _PreparedBatch(n, _run_host)
        # a malformed row stays zeroed with s_ok False -> reject
        ok = [len(it.pubkey) == 32 and len(it.sig) == 64 for it in items]
        well_formed = list(compress(range(n), ok))
        if not well_formed:
            # nothing to verify on device (malformed pubkey/sig lengths);
            # also keeps the lazy table stores untouched
            return _PreparedBatch(n, lambda: np.zeros(n, dtype=bool))
        good = list(compress(items, ok))
        # mesh decision: bulk rounds shard over every device (bucket
        # rounded up so the row slab divides evenly — the uneven tail is
        # verdict-inert padding), small rounds keep devices=1
        devs = self.shards_for(n)
        b = self._registry.bucket_for(n, multiple_of=devs)
        big = b >= self._bigtable_min
        device_hash = (
            big
            and self._device_challenge_min is not None
            and n >= self._device_challenge_min
            # one oversized message would pad EVERY row's hash buffer to
            # its length class (pad_messages pads batch-wide); cap the
            # device-hash path at 2 KiB messages — vote/commit sign-bytes
            # are ~200 bytes, so the cap only excludes pathological rows
            and max(len(it.msg) for it in good) + 64 <= 2048
        )
        at = np.array(well_formed, dtype=np.intp)
        sig = _column([it.sig for it in good], 64)
        rb = _rows32(b, at, sig[:, :32])
        sb = _rows32(b, at, sig[:, 32:])
        s_ok = np.zeros(b, dtype=bool)
        s_ok[at] = _below_l(sig[:, 32:])
        if device_hash:
            # challenge k = SHA-512(R||A||M) computed on device, fused
            # into the verify program (bulk-replay path)
            from ..ops import sha512 as dev_sha512

            pad = [b""] * (b - n)
            msg_buf, n_blocks = dev_sha512.pad_messages(
                [it.msg if k else b"" for it, k in zip(items, ok)] + pad,
                prefix_pairs=[
                    it.sig[:32] + it.pubkey if k else b""
                    for it, k in zip(items, ok)
                ]
                + pad,
            )
            kb = None
        else:
            msg_buf = n_blocks = None
            kb = _challenge_rows(b, at, good)

        family = self._progs.get(devs) or self._progs[1]

        def _run_device() -> np.ndarray:
            snap = self._table_lookup(
                self._big if big else self._small, items, well_formed, b, n
            )
            if snap is not None:
                tables, tvalid, idx = snap
                if device_hash:
                    out = self._dispatch(
                        family["msgs"],
                        "big_msgs",
                        b,
                        n,
                        tables,
                        tvalid,
                        jnp.asarray(idx),
                        rb,
                        sb,
                        jnp.asarray(msg_buf),
                        jnp.asarray(n_blocks),
                        jnp.asarray(s_ok),
                        devices=devs,
                    )
                elif big:
                    out = self._dispatch(
                        family["big"], "big", b, n,
                        tables, tvalid, jnp.asarray(idx), rb, sb, kb,
                        jnp.asarray(s_ok),
                        devices=devs,
                    )
                else:
                    out = self._dispatch(
                        family["small"], "small", b, n,
                        tables, tvalid, jnp.asarray(idx), rb, sb, kb,
                        jnp.asarray(s_ok),
                        devices=devs,
                    )
                return out[:n]

            # more distinct keys than the store holds: the generic
            # program (decompress in-batch; host challenges), exact and
            # counted in the store's fallback_rounds
            pub = _rows32(b, at, _column([it.pubkey for it in good], 32))
            out = self._dispatch(
                family["generic"], "generic", b, n, pub, rb, sb,
                _challenge_rows(b, at, good) if kb is None else kb,
                jnp.asarray(s_ok),
                devices=devs,
            )
            return out[:n]

        return _PreparedBatch(n, _run_device, devices=devs)

    @staticmethod
    def _verify_host_other(it: SigItem) -> bool:
        """Host verify of one row of a key type that has no batch
        engine (sr25519); any other type is refused."""
        if it.key_type == "sr25519":
            from . import sr25519

            return sr25519.PubKey(it.pubkey).verify(it.msg, it.sig)
        return False

    def verify_one(self, pubkey: bytes, msg: bytes, sig: bytes) -> bool:
        return bool(self.verify([SigItem(pubkey, msg, sig)])[0])


_default: BatchVerifier | None = None


def default_verifier() -> BatchVerifier:
    """Process-wide single-device verifier (lazy; shares the jit cache).

    TM_TPU_DEVICE_CHALLENGE_MIN (also settable via config
    [consensus].device_challenge_min, which node assembly exports to this
    env var) enables the fused on-device SHA-512 challenge path for
    batches >= the given size — for deployments where the device
    outruns the single host hashing thread (VERDICT r2 weak #6).
    Unset/0 keeps host hashing."""
    global _default
    if _default is None:
        import os

        dcm = int(os.environ.get("TM_TPU_DEVICE_CHALLENGE_MIN", "0") or 0)
        # TM_TPU_MIN_DEVICE_BATCH raises the host/device crossover — set
        # it very large to force pure-host verification (CPU-only
        # deployments and subprocess tests where a JAX compile would
        # dominate the workload)
        mdb = int(os.environ.get("TM_TPU_MIN_DEVICE_BATCH", "8") or 8)
        # [tpu] mesh axes (exported by node assembly): a config change
        # alone turns on sharded verification — VERDICT r4 missing #2
        from ..parallel import mesh_from_env

        _default = BatchVerifier(
            mesh=mesh_from_env(),
            min_device_batch=mdb,
            device_challenge_min=dcm if dcm > 0 else None,
        )
    return _default


def is_default_verifier(verifier) -> bool:
    """True iff `verifier` is the process-wide default instance (or was
    never constructed — None). The dispatch scheduler only takes over
    callers bound to the shared verifier; an explicitly-injected one
    (tests, bench isolation) keeps its private path."""
    return verifier is None or verifier is _default


def warm_validator_sets_in_executor(
    validator_sets, logger=None, verifier: BatchVerifier | None = None
):
    """Bulk-warm the big-tier verify tables for validator sets, off the
    event loop (blocksync start/rotation + light-client bisection entry;
    VERDICT r2 weak #3: the fixed-window build must never run inline in a
    verify pipeline). Returns the executor future, or None if there was
    nothing to warm. Failures are logged and leave no poisoned state —
    the table cache's ensure() is idempotent, so a later retry re-warms.
    """
    import asyncio
    import os

    if os.environ.get("TM_TPU_SKIP_WARM"):
        # test harnesses kill processes mid-compile; a daemon thread dying
        # inside XLA aborts noisily at teardown (see tests/conftest.py)
        return None
    verifier = verifier or default_verifier()
    pubkeys: list[bytes] = []
    key_types: list[str] = []
    for vals in validator_sets:
        if vals is None:
            continue
        for v in vals.validators:
            pubkeys.append(v.pub_key.data)
            key_types.append(getattr(v.pub_key, "type_name", "ed25519"))
    if not pubkeys:
        return None

    def _warm():
        try:
            verifier.warm(pubkeys, bulk=True, key_types=key_types)
        except Exception as e:  # warming is best-effort
            if logger is not None:
                logger.error("table warm failed", err=repr(e))
            raise

    fut = asyncio.get_running_loop().run_in_executor(None, _warm)
    # swallow the re-raise above: it exists so callers awaiting the future
    # see failures; fire-and-forget callers must not crash the loop
    fut.add_done_callback(lambda f: f.exception())
    return fut
