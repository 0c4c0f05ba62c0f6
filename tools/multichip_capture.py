"""Multichip capture: the sharded dispatch path, per device count.

Two stages, one artifact:

1. **dryrun** — `__graft_entry__.dryrun_multichip(n)`
   compile-checks the sharded verification step on an n-device CPU
   mesh, in this process.
2. **sharded throughput** (`sharded_capture`) — drives the SAME
   dispatch path the node runs: SigItem batches submitted to a
   `VerifyScheduler` over a `BatchVerifier` built on a
   `parallel.build_mesh` mesh, measured per device count on the bulk
   bucket. No ad-hoc pmap loop — MULTICHIP and BENCH numbers come from
   the scheduler/verifier code path itself, so a scaling number here is
   a scaling number in the node.

The artifact line:

    {"n_devices", "ok", "elapsed_s",
     "meta": {platform, device_kind, device_count},
     "series": [{"devices", "sigs_per_s", "sharded_dispatches"}...],
     "scaling_vs_1chip": {...}}

The capture needs the chip: unless JAX_PLATFORMS=cpu is set explicitly,
a resolved platform other than `tpu` ends the run non-zero
(libs/device.require_chip) — same rule as chip_smoke.py.
A stage that raises ends the run with its traceback and a non-zero
exit; there is no fallback row.

Usage: python tools/multichip_capture.py [n_devices]
           [--bucket 16384] [--mesh-backend cpu] [--mesh-min-rows N]
           [--no-dryrun]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.libs.jax_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()


def _make_items(n: int, n_unique: int = 128) -> list:
    """n SigItems from n_unique distinct signers (realistic validator
    set; rows repeat like a multi-height replay batch)."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.crypto.batch_verifier import SigItem

    base = []
    for i in range(min(n, n_unique)):
        sk = ed25519.PrivKey.from_secret(b"multichip-%d" % i)
        msg = b"multichip-vote-%d" % i
        base.append(SigItem(sk.public_key().data, msg, sk.sign(msg)))
    reps = (n + len(base) - 1) // len(base)
    return (base * reps)[:n]


def _measure_devices(
    items: list,
    devices: int,
    bucket: int,
    mesh_backend: str = "",
    mesh_min_rows: int | None = None,
    iters: int = 3,
    depth: int = 4,
) -> dict:
    """Throughput of the scheduler's dispatch path on a `devices`-chip
    mesh: warm the verify tables and the program, then best-of-iters
    over `depth` pipelined scheduler rounds of the full bucket."""
    import asyncio

    import numpy as np

    from tendermint_tpu.crypto.batch_verifier import BatchVerifier
    from tendermint_tpu.crypto.shape_registry import ShapeRegistry
    from tendermint_tpu.parallel import build_mesh
    from tendermint_tpu.parallel.scheduler import VerifyScheduler

    mesh = (
        build_mesh(ici_parallelism=devices, mesh_backend=mesh_backend)
        if devices > 1
        else None
    )
    reg = ShapeRegistry()
    verifier = BatchVerifier(
        mesh=mesh,
        min_device_batch=0,
        shape_registry=reg,
        mesh_min_rows=mesh_min_rows,
    )
    verifier.warm(
        list({it.pubkey for it in items}), bulk=True
    )  # table build outside the clock, like a running node

    async def run() -> float:
        sched = VerifyScheduler(verifier, max_batch=bucket)
        await sched.start()
        out = await sched.submit(items)  # warm: program compile/load
        assert np.asarray(out).all(), "multichip warm batch failed"
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            outs = await asyncio.gather(
                *(sched.submit(items) for _ in range(depth))
            )
            dt = time.perf_counter() - t0
            for o in outs:
                assert np.asarray(o).all(), "multichip batch failed"
            best = min(best, dt / depth)
        await sched.stop()
        return best

    dt = asyncio.run(run())
    return {
        "devices": devices,
        "sigs_per_s": round(len(items) / dt, 1),
        "ms_per_round": round(dt * 1e3, 1),
        "sharded_dispatches": reg.sharded_dispatch_count(),
        "sharded": verifier.shards_for(len(items)) > 1,
    }


def sharded_capture(
    max_devices: int,
    bucket: int = 16384,
    mesh_backend: str = "",
    mesh_min_rows: int | None = None,
) -> dict:
    """Measure the scheduler dispatch path at 1, 2, 4, ... devices up
    to min(max_devices, visible). Returns {series, scaling_vs_1chip}."""
    import jax

    avail = len(jax.devices(mesh_backend or None))
    counts = [1]
    d = 2
    while d <= min(max_devices, avail):
        counts.append(d)
        d *= 2
    top = min(max_devices, avail)
    if top > 1 and top not in counts:
        counts.append(top)
    items = _make_items(bucket)
    series = [
        _measure_devices(
            items, d, bucket,
            mesh_backend=mesh_backend, mesh_min_rows=mesh_min_rows,
        )
        for d in counts
    ]
    base = series[0]["sigs_per_s"] or 1.0
    return {
        "bucket": bucket,
        "metric": "ed25519_vote_verify_throughput_multichip",
        "unit": "sigs/s",
        "series": series,
        "scaling_vs_1chip": {
            str(s["devices"]): round(s["sigs_per_s"] / base, 3)
            for s in series
            if s["devices"] > 1
        },
        "devices_visible": avail,
    }


def main() -> int:
    ap = argparse.ArgumentParser(
        description="multichip sharded-dispatch capture"
    )
    ap.add_argument("n_devices", nargs="?", type=int, default=8)
    ap.add_argument("--bucket", type=int, default=16384)
    ap.add_argument("--mesh-backend", default="")
    ap.add_argument("--mesh-min-rows", type=int, default=0)
    ap.add_argument(
        "--no-dryrun",
        action="store_true",
        help="skip the compile dryrun stage",
    )
    args = ap.parse_args()
    n = args.n_devices

    from __graft_entry__ import dryrun_multichip, force_host_devices
    from tendermint_tpu.libs.device import device_stamp, require_chip

    if not args.no_dryrun:
        force_host_devices(n)  # before device_stamp() creates the backends
    meta = device_stamp()
    require_chip(meta["platform"])
    t0 = time.perf_counter()
    art = {"n_devices": n, "ok": True}
    if not args.no_dryrun:
        dryrun_multichip(n)
    # dryrun compiled: measure the real scheduler dispatch path
    art.update(
        sharded_capture(
            n,
            bucket=args.bucket,
            mesh_backend=args.mesh_backend,
            mesh_min_rows=args.mesh_min_rows or None,
        )
    )
    art["meta"] = meta
    art["elapsed_s"] = round(time.perf_counter() - t0, 1)
    print(json.dumps(art))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
