"""Mesh-sharded verify plane on the forced-host virtual CPU mesh.

The conftest forces `--xla_force_host_platform_device_count=8` with
JAX_PLATFORMS=cpu, so a 4-device mesh here is the ISSUE-6 forced-host
topology without TPU hardware. `perf`-marked (and slow: device
compiles) like test_prewarm — the acceptance suite for the multi-chip
dispatch rounds:

- sharded verdicts bit-identical to the single-device path for EVERY
  ladder bucket (pad/shard/gather round-trip is verdict-inert);
- uneven tails (n not divisible by the device count) pad per-device
  and never flip a verdict;
- `mesh_min_rows` keeps small rounds single-device (replicated — no
  shard/gather latency tax on live consensus);
- the registry's per-mesh shape count stays within the program budget;
- a coalesced scheduler round dispatches as ONE sharded round with the
  `sharded`/`devices` telemetry;
- tools/multichip_capture.py drives this same path end-to-end in a
  4-device child process.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

pytestmark = [pytest.mark.perf, pytest.mark.slow]

N_DEV = 4
N_KEYS = 64


def _mesh4():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices("cpu")[:N_DEV]), ("batch",))


_BASE: list = []


def _base_items():
    """Signed base rows, built lazily so tier-1 collection (which
    imports but deselects this module) never pays the host signing."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.crypto.batch_verifier import SigItem

    if not _BASE:
        for i in range(N_KEYS):
            sk = ed25519.PrivKey.from_secret(b"meshshard-%d" % i)
            msg = b"mesh-vote-%d" % i
            _BASE.append(
                SigItem(sk.public_key().data, msg, sk.sign(msg))
            )
    return _BASE


def _items(n: int, corrupt=()):
    """n rows tiled from the signed base set, with chosen rows' sigs
    bit-flipped (well-formed length, invalid signature)."""
    from tendermint_tpu.crypto.batch_verifier import SigItem

    base = _base_items()
    reps = (n + N_KEYS - 1) // N_KEYS
    out = list((base * reps)[:n])
    for i in corrupt:
        it = out[i]
        bad = it.sig[:50] + bytes([it.sig[50] ^ 1]) + it.sig[51:]
        out[i] = SigItem(it.pubkey, it.msg, bad)
    return out


@pytest.fixture(scope="module")
def regs_and_verifiers():
    """One meshless and one always-sharding mesh verifier, each with an
    isolated registry; module-scoped so the ladder's programs compile
    once."""
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier
    from tendermint_tpu.crypto.shape_registry import ShapeRegistry

    reg_solo, reg_mesh = ShapeRegistry(), ShapeRegistry()
    v_solo = BatchVerifier(
        min_device_batch=0, bigtable_min=1 << 30, shape_registry=reg_solo
    )
    v_mesh = BatchVerifier(
        mesh=_mesh4(),
        min_device_batch=0,
        bigtable_min=1 << 30,
        shape_registry=reg_mesh,
        mesh_min_rows=1,  # shard every bucket: the round-trip under test
    )
    return reg_solo, v_solo, reg_mesh, v_mesh


def test_sharded_bit_identical_every_ladder_bucket(regs_and_verifiers):
    """For every rung of the canonical ladder, the 4-way sharded round
    and the single-device round produce bit-identical verdict bitmaps,
    equal to the constructed truth (corrupted rows rejected)."""
    reg_solo, v_solo, reg_mesh, v_mesh = regs_and_verifiers
    for b in reg_mesh.ladder:
        n = b  # fill the bucket exactly
        corrupt = sorted({1 % n, n // 3, n - 1})
        items = _items(n, corrupt=corrupt)
        want = [i not in corrupt for i in range(n)]
        got_mesh = np.asarray(v_mesh.verify(items))
        got_solo = np.asarray(v_solo.verify(items))
        assert got_mesh.tolist() == want, f"mesh verdicts wrong at {b}"
        assert (got_mesh == got_solo).all(), (
            f"sharded verdicts diverge from single-device at bucket {b}"
        )
    # every bulk dispatch actually sharded (devices=4 shapes recorded)
    small = reg_mesh.shapes_by_tier()["small"]
    assert {d for _, _, d in small} == {N_DEV}
    assert reg_mesh.sharded_dispatch_count() >= len(reg_mesh.ladder)


def test_uneven_tail_pads_per_device(regs_and_verifiers):
    """n not divisible by the device count: the bucket is rounded up to
    a multiple of 4, the tail rows are verdict-inert padding, and no
    real verdict moves. Runs sizes straddling rung boundaries."""
    reg_solo, v_solo, reg_mesh, v_mesh = regs_and_verifiers
    for n in (13, 129, 510, 2043):
        corrupt = sorted({0, n // 2, n - 1})
        items = _items(n, corrupt=corrupt)
        want = [i not in corrupt for i in range(n)]
        got = np.asarray(v_mesh.verify(items))
        assert got.tolist() == want, f"uneven tail flipped verdicts at n={n}"
        assert len(got) == n
        # the padded bucket divides evenly across devices
        b = reg_mesh.bucket_for(n, multiple_of=N_DEV)
        assert b % N_DEV == 0 and b >= n


def test_mesh_min_rows_keeps_small_rounds_single_device():
    """Rounds below mesh_min_rows prepare with devices=1 (replicated —
    single-chip latency), at/above with devices=N; no dispatch needed
    to decide, so this pins the routing logic itself."""
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier
    from tendermint_tpu.crypto.shape_registry import ShapeRegistry

    v = BatchVerifier(
        mesh=_mesh4(),
        min_device_batch=0,
        bigtable_min=1 << 30,
        shape_registry=ShapeRegistry(),
        mesh_min_rows=1024,
    )
    assert v.mesh_devices == N_DEV
    assert v.shards_for(1) == 1
    assert v.shards_for(1023) == 1
    assert v.shards_for(1024) == N_DEV
    assert v.prepare(_items(16)).devices == 1
    assert v.prepare(_items(1024)).devices == N_DEV
    # env default wiring: None reads TM_TPU_MESH_MIN_ROWS
    os.environ["TM_TPU_MESH_MIN_ROWS"] = "64"
    try:
        v2 = BatchVerifier(
            mesh=_mesh4(),
            shape_registry=ShapeRegistry(),
        )
        assert v2.shards_for(63) == 1 and v2.shards_for(64) == N_DEV
    finally:
        del os.environ["TM_TPU_MESH_MIN_ROWS"]
    # UNSET env must land on the built-in default, not shard-everything
    # (regression: `get(.., "0") or default` kept the truthy "0")
    from tendermint_tpu.crypto.batch_verifier import DEFAULT_MESH_MIN_ROWS

    assert "TM_TPU_MESH_MIN_ROWS" not in os.environ
    v3 = BatchVerifier(mesh=_mesh4(), shape_registry=ShapeRegistry())
    assert v3._mesh_min_rows == DEFAULT_MESH_MIN_ROWS
    assert v3.shards_for(16) == 1


def test_per_mesh_shape_count_within_budget(regs_and_verifiers):
    """After the full-ladder sweep, the registry stays within the
    program budget per (tier, device-variant) — the mesh doubles the
    reachable families, not the per-family ladder."""
    reg_solo, _, reg_mesh, _ = regs_and_verifiers
    for reg in (reg_solo, reg_mesh):
        for tier, shapes in reg.shapes_by_tier().items():
            by_dev: dict[int, int] = {}
            for _, _, d in shapes:
                by_dev[d] = by_dev.get(d, 0) + 1
            for d, count in by_dev.items():
                assert count <= 8, (
                    f"tier {tier} devices={d} exceeded the shape "
                    f"budget: {shapes}"
                )


def test_scheduler_round_dispatches_sharded(regs_and_verifiers):
    """Coalesced submissions from two classes ride ONE sharded round:
    the ledger's entry and device_round telemetry carry sharded/devices,
    and the verify_mesh_devices gauge reflects the mesh."""
    import asyncio

    from tendermint_tpu.libs.metrics import Registry, SchedulerMetrics
    from tendermint_tpu.obs.ledger import DispatchLedger
    from tendermint_tpu.parallel.scheduler import VerifyScheduler

    _, _, reg_mesh, v_mesh = regs_and_verifiers
    metrics = SchedulerMetrics(Registry("mesh_test"))
    s = VerifyScheduler(
        v_mesh, max_batch=16384, metrics=metrics, ledger=DispatchLedger()
    )
    items_a = _items(96)
    items_b = _items(32, corrupt=(3,))

    async def run():
        await s.start()
        # occupy the device so the next two coalesce into one round
        first = asyncio.create_task(s.submit(_items(8), "consensus"))
        await asyncio.sleep(0.01)
        a, b = await asyncio.gather(
            s.submit(items_a, "consensus"),
            s.submit(items_b, "blocksync"),
        )
        await first
        await s.stop()
        return a, b

    a, b = asyncio.run(run())
    assert np.asarray(a).all()
    assert np.asarray(b).tolist() == [i != 3 for i in range(32)]
    assert metrics.mesh_devices.value() == N_DEV
    sharded = [d for d in s.ledger.entries() if d["sharded"]]
    assert sharded, f"no sharded round in {s.ledger.entries()}"
    assert sharded[-1]["devices"] == N_DEV
    assert metrics.dispatch_sharded.value() >= 1


def test_multichip_capture_forced_host_4dev(tmp_path):
    """tools/multichip_capture.py end-to-end in a child process forced
    to 4 host devices: the artifact's series covers 1/2/4 devices from
    the scheduler dispatch path, sharded rounds recorded, meta stamps
    the cpu platform (the explicit JAX_PLATFORMS=cpu is what lets the
    capture run without a chip at all)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        " ".join(
            f for f in flags.split()
            if "xla_force_host_platform_device_count" not in f
        )
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(root, "tools", "multichip_capture.py"),
            "4",
            "--bucket", "128",
            "--mesh-min-rows", "8",
            "--mesh-backend", "cpu",
            "--no-dryrun",
        ],
        capture_output=True,
        text=True,
        timeout=560,
        env=env,
        cwd=root,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    art = json.loads(r.stdout.strip().splitlines()[-1])
    assert art["ok"], art
    assert art["meta"]["platform"] == "cpu"
    assert art["meta"]["device_kind"]
    assert art["meta"]["device_count"] == 4
    devs = [s["devices"] for s in art["series"]]
    assert devs == [1, 2, 4]
    multi = [s for s in art["series"] if s["devices"] > 1]
    assert all(s["sharded"] and s["sharded_dispatches"] > 0 for s in multi)
    assert set(art["scaling_vs_1chip"]) == {"2", "4"}
