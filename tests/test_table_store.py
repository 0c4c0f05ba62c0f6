"""The key-table stores of `crypto/batch_verifier.py`: a capacity from
the device's memory, least-recently-used eviction in place of a clear,
a counted fall to the generic program for a round the store cannot
hold, the `crypto.table_lookup` fields and the service dump's
`table_store` block that say which of these a round met, and one
request cut across two scheduler rounds.

Verdicts are held against the host serial verifier
(`crypto.ed25519.verify`), which shares no code with the device
programs or the stores.
"""

from __future__ import annotations

import asyncio
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.crypto import batch_verifier as bv
from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem
from tendermint_tpu.libs.metrics import Registry, SchedulerMetrics
from tendermint_tpu.obs import tracer as tracer_mod
from tendermint_tpu.obs.ledger import DispatchLedger
from tendermint_tpu.parallel.scheduler import VerifyScheduler
from tendermint_tpu.parallel.verify_service import ServiceThread

BAD_KINDS = ("flipped_bit", "wrong_key", "s_ge_L", "short_sig")


def signed(keys: range, tag: bytes, bad: dict | None = None) -> list[SigItem]:
    """One row a key, in order; `bad` is {position: kind}, the four bad
    rows of the benchmark's fixtures."""
    privs = [host.PrivKey.from_secret(b"store-%d" % k) for k in keys]
    msgs = [b"%s-%d" % (tag, k) for k in keys]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i, kind in (bad or {}).items():
        sig = sigs[i]
        if kind == "flipped_bit":
            sigs[i] = bytes([sig[0] ^ 0x04]) + sig[1:]
        elif kind == "wrong_key":
            sigs[i] = privs[(i + 1) % len(privs)].sign(msgs[i])
        elif kind == "s_ge_L":
            s = int.from_bytes(sig[32:], "little") + host.L
            sigs[i] = sig[:32] + s.to_bytes(32, "little")
        elif kind == "short_sig":
            sigs[i] = sig[:63]
    return [
        SigItem(p.public_key().data, m, s)
        for p, m, s in zip(privs, msgs, sigs)
    ]


def oracle(items: list[SigItem]) -> list[bool]:
    return [host.verify(it.pubkey, it.msg, it.sig) for it in items]


@pytest.fixture
def ring(monkeypatch):
    ring = obs.Tracer(enabled=True)
    monkeypatch.setattr(tracer_mod, "_default", ring)
    return ring


def lookups(ring) -> list[dict]:
    return [
        r.fields for r in ring.records() if r.name == "crypto.table_lookup"
    ]


# --- (a) a committee the store cannot hold ----------------------------------


@pytest.mark.parametrize("tier", ["small", "big"])
def test_more_keys_than_the_store_holds_is_exact_and_counted(ring, tier):
    v = BatchVerifier(
        table_cache_capacity=16, min_device_batch=0,
        bigtable_min=8 if tier == "big" else bv.BIGTABLE_MIN,
    )
    items = signed(range(24), b"wide", dict(zip((3, 9, 14, 20), BAD_KINDS)))
    want = oracle(items)
    assert want.count(False) == 4
    assert v.verify(items).tolist() == want
    assert v.verify(items).tolist() == want
    stats = v.table_store_stats()
    assert stats[tier]["fallback_rounds"] == 2
    assert stats[tier]["keys_resident"] == 0 and stats[tier]["evictions"] == 0
    other = "big" if tier == "small" else "small"
    assert stats[other]["fallback_rounds"] == 0
    assert [
        (f["tier"], f["fallback"], f["built"], f["evicted"])
        for f in lookups(ring)
    ] == [(tier, True, 0, 0)] * 2
    # 23 well-formed rows looked for a table and found none
    assert all(f["n"] == 24 and f["resident"] == 1 for f in lookups(ring))


# --- (b) rotation past the capacity -----------------------------------------


def test_rotating_sets_evict_each_other_and_never_the_hot_set(ring):
    v = BatchVerifier(table_cache_capacity=16, min_device_batch=0)
    hot = signed(range(12), b"hot", {5: "flipped_bit"})
    want_hot = oracle(hot)
    assert v.verify(hot).tolist() == want_hot
    for s in range(6):
        rot = signed(
            range(100 + 4 * s, 104 + 4 * s), b"rot", {s % 4: BAD_KINDS[s % 3]}
        )
        assert v.verify(rot).tolist() == oracle(rot)
        assert v.verify(hot).tolist() == want_hot
    fields = lookups(ring)
    assert [f["built"] for f in fields] == [12] + [4, 0] * 6
    # the first set fills the store; each later one takes the rows of
    # the set before it, the longest unused
    assert [f["evicted"] for f in fields] == [0, 0, 0] + [4, 0] * 5
    assert [f["resident"] for f in fields] == [0] + [0, 12] * 6
    assert not any(f["fallback"] for f in fields)
    stats = v.table_store_stats()["small"]
    assert stats == {
        "rows_allocated": 16, "keys_resident": 16,
        "bytes": 16 * (16 * 4 * 32 + 1), "evictions": 20,
        "fallback_rounds": 0,
    }
    assert v._small.built == 12 + 6 * 4
    held = set(v._small._idx)
    assert {it.pubkey for it in hot} <= held
    assert {it.pubkey for it in rot} <= held


# --- the store itself, over a build that only copies the key ---------------


def toy_store(capacity: int) -> bv._TableCache:
    """A store whose 'table' of a key is the key's first 4 bytes: what
    a row holds says whose it is."""

    def build(arr):
        return arr[:, :4], jnp.ones(arr.shape[0], dtype=bool)

    return bv._TableCache(threading.Lock(), build, (4,), capacity, 1)


def toy_keys(lo: int, hi: int) -> list[bytes]:
    return [bytes([k]) * 32 for k in range(lo, hi)]


def held_by(snap, j: int) -> int:
    tables, valid, idx = snap
    assert bool(np.asarray(valid)[idx[j]])
    return int(np.asarray(tables)[idx[j]][0])


def test_a_snapshot_in_flight_keeps_its_rows_through_an_eviction():
    store = toy_store(8)
    first = toy_keys(1, 9)
    snap, missing, evicted = store.lookup(first, list(range(8)), 8)
    assert (missing, evicted) == (8, 0)
    # eight other keys take every row while the round holds `snap`
    snap2, missing, evicted = store.lookup(toy_keys(11, 19), list(range(8)), 8)
    assert (missing, evicted) == (8, 8)
    assert [held_by(snap, j) for j in range(8)] == list(range(1, 9))
    assert [held_by(snap2, j) for j in range(8)] == list(range(11, 19))
    assert set(store._idx) == set(toy_keys(11, 19))


def test_a_store_no_round_holds_is_written_in_place_and_grows_once():
    store = toy_store(1024)
    store.ensure(toy_keys(1, 5))
    assert store.tables.shape[0] == bv.TABLE_ROWS_MIN
    before = store.tables
    store.ensure(toy_keys(5, 9))
    assert before.is_deleted()  # donated: no second store beside it
    lent = store.arrays()[0]
    store.ensure(toy_keys(9, 13))
    assert not lent.is_deleted()  # handed out: copied, not donated
    # 200 keys in one call: one allocation at the final size
    store.ensure(toy_keys(20, 220))
    assert store.tables.shape[0] == 256
    snap, missing, _ = store.lookup(toy_keys(1, 13), list(range(12)), 16)
    assert missing == 0 and snap[2][12:].tolist() == [-1] * 4
    assert [held_by(snap, j) for j in range(12)] == list(range(1, 13))


def test_partial_overlap_evicts_only_keys_the_round_does_not_name():
    store = toy_store(8)
    store.ensure(toy_keys(1, 9))
    store.ensure(toy_keys(1, 5))  # 5..8 are now the longest unused
    batch = toy_keys(3, 5) + toy_keys(21, 24)  # 2 resident, 3 new
    snap, missing, evicted = store.lookup(batch, list(range(5)), 8)
    assert (missing, evicted) == (3, 3)
    assert [held_by(snap, j) for j in range(5)] == [3, 4, 21, 22, 23]
    assert set(store._idx) == set(toy_keys(1, 5) + toy_keys(8, 9) + batch)
    # nine distinct keys cannot be held at once: nothing built or evicted
    snap, missing, evicted = store.lookup(toy_keys(31, 40), list(range(9)), 16)
    assert snap is None and (missing, evicted) == (9, 0)
    assert store.fallback_rounds == 1 and store.evictions == 3
    assert not store.ensure(toy_keys(31, 40))
    assert store.built == 8 + 3


# --- (c) the capacity comes from the device ---------------------------------


class StubDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


GIB = 1 << 30


@pytest.mark.parametrize(
    "stats, keys",
    [
        (None, bv.TABLE_CACHE_CAPACITY),  # XLA:CPU keeps none
        ({"bytes_in_use": 5}, bv.TABLE_CACHE_CAPACITY),
        ({"bytes_limit": 16 * GIB}, 32768),
        ({"bytes_limit": int(15.75 * GIB)}, 16384),
        ({"bytes_limit": 32 * GIB}, 65536),
        ({"bytes_limit": 1 << 20}, bv.TABLE_ROWS_MIN),
    ],
)
def test_capacity_is_a_share_of_what_the_device_may_use(
    monkeypatch, stats, keys
):
    assert bv._device_table_capacity(StubDevice(stats)) == keys
    if stats and "bytes_limit" in stats:
        assert keys * bv.BIG_TABLE_BYTES <= max(
            stats["bytes_limit"] * bv.TABLE_STORE_SHARE,
            bv.TABLE_ROWS_MIN * bv.BIG_TABLE_BYTES,
        )
    monkeypatch.setattr(jax, "devices", lambda *a: [StubDevice(stats)])
    v = BatchVerifier()
    assert v._big._capacity == v._small._capacity == keys
    assert BatchVerifier(table_cache_capacity=16)._big._capacity == 16


# --- (d) the service's own account -------------------------------------------


def test_service_dump_and_lookup_spans_say_what_a_round_met(tmp_path, ring):
    thread = ServiceThread(
        str(tmp_path / "vs.sock"), tracer=ring,
        verifier=BatchVerifier(table_cache_capacity=16, min_device_batch=0),
    )
    thread.start()
    try:
        sched = thread.server.scheduler
        empty = thread.server.dump(entries=0)["service"]["table_store"]
        assert empty["small"]["keys_resident"] == 0
        items = signed(range(12), b"svc", {7: "short_sig"})
        for _ in range(2):
            got = sched.submit_sync(items, "consensus")
            assert got.tolist() == oracle(items)
        store = thread.server.dump(entries=0)["service"]["table_store"]
    finally:
        thread.stop()
    assert store["small"] == {
        "rows_allocated": 16, "keys_resident": 11,
        "bytes": 16 * 2049, "evictions": 0, "fallback_rounds": 0,
    }
    assert store["big"]["rows_allocated"] == 0
    fields = lookups(ring)
    # the short signature's row has no key to look up: it needs no
    # table, before the build and after it
    assert [
        (f["n"], f["built"], f["resident"], f["evicted"], f["fallback"])
        for f in fields
    ] == [(12, 11, 1, 0, False), (12, 0, 12, 0, False)]


def test_a_verifier_without_stores_dumps_an_empty_block(tmp_path):
    class Plain:
        def verify(self, items):
            return np.ones(len(items), dtype=bool)

    thread = ServiceThread(str(tmp_path / "vs.sock"), verifier=Plain())
    thread.start()
    try:
        assert thread.server.dump(entries=0)["service"]["table_store"] == {}
    finally:
        thread.stop()


# --- (e) one request cut across two rounds ----------------------------------


class PatternVerifier:
    """Verdict = the message's last byte is odd: position-free, so a
    bitmap out of row order shows."""

    def verify(self, items):
        return np.array([it.msg[-1] % 2 == 1 for it in items], dtype=bool)


@pytest.mark.parametrize("kind", ["pattern", "device"])
def test_a_request_cut_across_two_rounds_returns_one_bitmap_in_row_order(
    kind,
):
    if kind == "pattern":
        verifier = PatternVerifier()
        items = [
            SigItem(b"k" * 32, bytes([i * 7 % 251]), b"s" * 64)
            for i in range(24)
        ]
        want = [(i * 7 % 251) % 2 == 1 for i in range(24)]
    else:
        # 16 keys fill the store in the first round; the second round's
        # 7 (its short signature looks no key up) take the rows of the
        # first's longest unused
        verifier = BatchVerifier(table_cache_capacity=16, min_device_batch=0)
        items = signed(
            range(24), b"cut", dict(zip((2, 15, 16, 23), BAD_KINDS))
        )
        want = oracle(items)
    ledger = DispatchLedger()
    sched = VerifyScheduler(
        verifier=verifier, max_batch=16, ledger=ledger,
        metrics=SchedulerMetrics(Registry("test")),
    )

    async def run():
        await sched.start()
        try:
            return await sched.submit(items, "consensus")
        finally:
            await sched.stop()

    got = asyncio.run(run())
    assert got.tolist() == want and len(got) == 24
    summary = ledger.summary()
    assert summary["rounds"] == 2
    assert summary["rows_requested"] == 24
    assert summary["per_engine"]["sig"]["submissions"] == 2
    if kind == "device":
        store = verifier.table_store_stats()["small"]
        assert store["evictions"] == 7 and store["fallback_rounds"] == 0
