"""The collector of the process that owns the chip (`CollectorPolicy`):
generation 0 sized from the rows a submission brings, the warm heap
frozen once set-up has grown it, and an embedded service that leaves
its host's collector alone. The policy's own tests hand it a stand-in
for `gc`; the one process that retunes its real collector is a service
of its own, started through `run_service`.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from tendermint_tpu import obs
from tendermint_tpu.crypto import secp256k1
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem
from tendermint_tpu.parallel.verify_service import (
    ROW_OBJECTS,
    SUBMISSIONS_IN_FLIGHT,
    YOUNG_CAP,
    CollectorPolicy,
    RemoteVerifyScheduler,
    ServiceThread,
    _Cursor,
    _HDR,
    decode_submit,
    encode_submit,
)

from .helpers import FakeGC, KeyedVerifier

pytestmark = pytest.mark.verify_service

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rows(n: int, key: bytes = b"p") -> list[SigItem]:
    return [
        SigItem(
            key * 32, b"m%06d" % i + b"\x00" * 26,
            (b"1" if i % 3 else b"0") + b"s" * 63,
        )
        for i in range(n)
    ]


async def submit_all(path: str, batches) -> list:
    client = RemoteVerifyScheduler(
        path, verifier=KeyedVerifier(), retry_base=0.02,
        tracer=obs.Tracer(enabled=False),
    )
    await client.start()
    deadline = time.monotonic() + 15
    while not client.connected and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert client.connected, "client never attached"
    try:
        return [await client.submit(b, "consensus") for b in batches]
    finally:
        await client.stop()


def policy(threshold=(700, 10, 10)):
    """(policy over a stand-in gc, the stand-in, the heap mark it reads)"""
    fake = FakeGC(threshold)
    pol = CollectorPolicy(gc_module=fake)
    mark = {"now": (0, 0)}
    pol.watch(lambda: mark["now"])
    return pol, fake, mark


def serve(pol: CollectorPolicy, n: int) -> None:
    pol.begin(n)
    pol.end()


# --- the young generation ----------------------------------------------------


PER_ROW = ROW_OBJECTS * SUBMISSIONS_IN_FLIGHT


@pytest.mark.parametrize("sizes, want", [
    ((1,), 700),
    ((128,), 700),  # a live commit of 128 rows keeps the default
    ((700 // PER_ROW + 1,), PER_ROW * (700 // PER_ROW + 1)),
    ((65536,), PER_ROW * 65536),  # a catch-up window
    ((65536, 128, 1024), PER_ROW * 65536),  # never falls back
    ((128, 10000, 1024), PER_ROW * 10000),
    ((10**7,), YOUNG_CAP),
])
def test_young_threshold_follows_the_largest_submission(sizes, want):
    pol, fake, _ = policy()
    for n in sizes:
        serve(pol, n)
    assert fake.threshold == (want, 10, 10)
    assert 700 <= fake.threshold[0] <= YOUNG_CAP
    assert pol.largest == max(sizes)


def test_young_threshold_starts_from_what_the_process_had():
    pol, fake, _ = policy(threshold=(2000, 5, 7))
    serve(pol, 128)
    assert fake.threshold == (2000, 5, 7)
    serve(pol, 5000)
    assert fake.threshold == (PER_ROW * 5000, 5, 7)


def test_the_cap_bounds_a_young_generation_of_tens_of_mb():
    # 104 bytes a SigItem, the largest of the small tracked objects
    # measured for the cap (PERF.md section 6)
    assert YOUNG_CAP * 104 < 32 * 2**20
    assert YOUNG_CAP >= PER_ROW * 65536  # a catch-up window fits


# --- the freeze --------------------------------------------------------------


def test_no_freeze_after_a_submission_that_grew_nothing():
    pol, fake, mark = policy()
    serve(pol, 8)
    assert pol.freezes == 0 and "collect" not in fake.calls
    mark["now"] = (0, 1024)  # a round installed key tables
    serve(pol, 8)
    assert pol.freezes == 1
    serve(pol, 8)
    assert pol.freezes == 1  # nothing new since
    mark["now"] = (3, 1024)  # a round compiled
    serve(pol, 8)
    assert pol.freezes == 2
    mark["now"] = (3, 512)  # a store evicted: nothing grew
    serve(pol, 8)
    mark["now"] = (3, 1024)  # and took keys back in, up to its high mark
    serve(pol, 8)
    assert pol.freezes == 2
    mark["now"] = (3, 1025)  # past it
    serve(pol, 8)
    assert pol.freezes == 3


def test_no_freeze_while_another_submission_is_in_flight():
    pol, fake, mark = policy()
    pol.begin(8)
    pol.begin(8)
    mark["now"] = (1, 0)
    pol.end()
    assert pol.freezes == 0 and "freeze" not in fake.calls
    pol.end()
    assert pol.freezes == 1 and pol.in_flight == 0


def test_a_freeze_is_preceded_by_a_collection():
    pol, fake, mark = policy()
    mark["now"] = (1, 0)
    assert pol.settle()  # after a prewarm: nothing in flight
    mark["now"] = (2, 0)
    serve(pol, 8)
    froze = [c for c in fake.calls if c in ("collect", "freeze")]
    assert froze == ["collect", "freeze"] * 2
    assert pol.snapshot()["freezes"] == 2
    assert pol.snapshot()["frozen"] == 2000


def test_a_freeze_comes_before_the_reply_and_only_when_the_heap_grew(
    tmp_path,
):
    """Through the server: the first submission's keys grow the store,
    so it freezes before its verdicts go back; the same keys again
    freeze nothing; new keys do."""
    fake = FakeGC()
    path = str(tmp_path / "vs.sock")
    thread = ServiceThread(
        path, verifier=KeyedVerifier(),
        collector=CollectorPolicy(gc_module=fake),
    )
    thread.start()
    try:
        got = asyncio.run(
            submit_all(path, [rows(12), rows(12), rows(3000, b"q")])
        )
        gc_block = thread.server.dump()["service"]["gc"]
    finally:
        thread.stop()
    assert [g.tolist() for g in got] == [
        [bool(i % 3) for i in range(n)] for n in (12, 12, 3000)
    ]
    assert gc_block["freezes"] == 2
    assert gc_block["threshold"] == [PER_ROW * 3000, 10, 10]
    assert thread.server.collector.in_flight == 0


# --- k, measured -------------------------------------------------------------


def served_rows(n: int, secp: int) -> list[SigItem]:
    """Well-formed rows as a catch-up window brings them: 110-116-byte
    sign-bytes, the first `secp` of them signed by secp256k1 keys."""
    rng = np.random.default_rng(7)
    keys = [secp256k1.PrivKey.from_secret(b"gc%d" % i) for i in range(8)]
    out = []
    for i in range(n):
        msg = b"m" * (110 + i % 7) + i.to_bytes(4, "big")
        if i < secp:
            key = keys[i % len(keys)]
            out.append(SigItem(
                key.public_key().data, msg, key.sign(msg), "secp256k1"
            ))
        else:
            out.append(SigItem(
                rng.bytes(32), msg, rng.bytes(64), "ed25519"
            ))
    return out


@pytest.mark.parametrize("secp", [0, 1024], ids=["ed25519", "mixed"])
def test_a_served_row_allocates_row_objects_tracked_objects(secp):
    """k, ROW_OBJECTS: the young count's growth over a submission's
    decode and its round's prepare, a row at a time."""
    n = 4096
    frame = encode_submit(1, served_rows(n, secp), "blocksync")
    verifier = BatchVerifier()
    cur = _Cursor(frame)
    cur.take(_HDR.size)
    enabled = gc.isenabled()
    gc.disable()  # no collection may reset the count in between
    try:
        before = gc.get_count()[0]
        items, _, _ = decode_submit(cur)
        prepared = verifier.prepare(items)
        grown = gc.get_count()[0] - before
    finally:
        if enabled:
            gc.enable()
    assert prepared is not None and len(items) == n
    assert ROW_OBJECTS <= grown / n < ROW_OBJECTS + 0.05


# --- the process's own collector ----------------------------------------------


def test_an_embedded_service_leaves_its_hosts_collector_alone(tmp_path):
    before = (gc.get_threshold(), gc.get_freeze_count())
    path = str(tmp_path / "vs.sock")
    thread = ServiceThread(path, verifier=KeyedVerifier())
    thread.start()
    try:
        asyncio.run(submit_all(path, [rows(12), rows(5000, b"q")]))
        dump = thread.server.dump()
    finally:
        thread.stop()
    assert thread.server.collector is None
    assert dump["service"]["gc"] is None
    assert (gc.get_threshold(), gc.get_freeze_count()) == before


SERVICE = r"""
import sys

import numpy as np

from tendermint_tpu.parallel import scheduler
from tendermint_tpu.parallel.verify_service import run_service


class CompilingVerifier:
    # its first round compiles a program, as a fresh service's first
    # round does; every round leaves two lists in a cycle a row
    def __init__(self):
        self.program = None

    def verify(self, items):
        import jax
        import jax.numpy as jnp

        if self.program is None:
            self.program = jax.jit(lambda x: x + 1)
            self.program(jnp.zeros(8)).block_until_ready()
        for _ in items:
            a = []
            a.append([a])
        return np.array([it.sig[:1] == b"1" for it in items], dtype=bool)


scheduler.default_verifier = CompilingVerifier
sys.exit(run_service(sys.argv[1], stats_port=0, ready_fd=int(sys.argv[2])))
"""


def test_run_service_freezes_after_set_up_and_still_collects_cycles(
    tmp_path,
):
    """A CPU service through `run_service`: the warm-up submission
    compiles, so the heap is frozen; the next submissions compile
    nothing, size generation 0 from their rows, and the cycles their
    rounds leave behind are still collected."""
    path = str(tmp_path / "vs.sock")
    rfd, wfd = os.pipe()
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", SERVICE, path, str(wfd)],
        cwd=ROOT, env=env, pass_fds=(wfd,), stderr=subprocess.PIPE,
    )
    os.close(wfd)
    try:
        with os.fdopen(rfd, "rb") as ready:
            line = ready.read()
        assert line, proc.stderr.read().decode()[-2000:]
        port = json.loads(line)["stats_port"]

        def gc_block():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/dump_dispatch_ledger", timeout=30
            ) as resp:
                return json.loads(resp.read())["service"]["gc"]

        n = 3000
        asyncio.run(submit_all(path, [rows(8)]))
        warm = gc_block()
        asyncio.run(submit_all(path, [rows(n, b"q") for _ in range(4)]))
        after = gc_block()
    finally:
        proc.terminate()
        proc.wait(timeout=30)
    assert warm["freezes"] == 1 and warm["frozen"] > 0
    assert warm["threshold"][0] == 700  # max(700, 2 x 8)
    assert after["freezes"] == 1  # nothing compiled since
    assert after["threshold"] == [PER_ROW * n] + warm["threshold"][1:]
    # four rounds left 2 x 4 x 3000 cyclic objects: collections ran
    # and freed them, and the young count stayed under its threshold
    assert after["collections"][0] > warm["collections"][0]
    freed = sum(after["collected"]) - sum(warm["collected"])
    assert freed >= 2 * 3 * n
    assert after["count"][0] <= after["threshold"][0]
