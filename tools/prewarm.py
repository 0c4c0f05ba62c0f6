#!/usr/bin/env python
"""Build + verify the verification-program prewarm manifest.

PERF_ANALYSIS §10: XLA program loads are a per-process cost, and a cold
bisect-1k run loaded 44 distinct op-shape programs. The fix is
two-sided: the
canonical bucket ladder (crypto/shape_registry) bounds how many
programs exist, and this tool loads them ahead of time so the
persistent compile cache holds every shape a node dispatches —
a restarted node then pays zero per-shape loads mid-height.

Under a device mesh the ladder is AOT-loaded PER DEVICE VARIANT
(PERF_ANALYSIS §13): each rung's replicated (devices=1) and/or
row-sharded (devices=N) program, exactly the reachable set given
mesh_min_rows. The manifest records the topology (`device_count`,
`mesh_min_rows`) it was built for, and --verify fails loudly when the
live mesh disagrees — a node warm-started on a different topology
would otherwise recompile every sharded program on the hot path.

Modes:

  python tools/prewarm.py                      # build the manifest
  python tools/prewarm.py --devices 4          # build for a 4-device mesh
  python tools/prewarm.py --verify             # re-run the manifest's
                                               # ladder ON ITS TOPOLOGY;
                                               # report per-bucket load
                                               # times, fail on budget
                                               # breach or device-count
                                               # mismatch with the live
                                               # mesh

Build executes every (tier, bucket, devices) verify program once with
verdict-inert padded lanes (BatchVerifier.prewarm_buckets — the same
routine the node's warm thread runs under [scheduler] prewarm=true) and
writes {created_unix, ladder, device_count, entries:[{tier,bucket,rows,
devices,seconds}]} JSON. Verify re-executes the manifest's ladder in a
warmed-cache process: any entry slower than --reload-threshold seconds
means the persistent cache is NOT absorbing that shape (regression),
and the distinct-shape count must stay within --budget per tier.
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

DEFAULT_MANIFEST = "prewarm_manifest.json"

# scheduler verify class -> the verifier tiers its dispatches reach.
# Every commit-verify class — including the lightserve serving plane's
# shared bisection rounds, which coalesce arbitrary swarm sizes onto
# the same ladder — runs the cached small/big tier split; a manifest
# built without those tiers leaves that class compiling on the hot
# path, so --verify checks coverage per family.
FAMILY_TIERS = {
    "consensus": ("small", "big"),
    "evidence": ("small", "big"),
    "blocksync": ("small", "big"),
    "light": ("small", "big"),
    "lightserve": ("small", "big"),
    # the sequencer streaming plane's signature checks are host-native
    # ECDSA recovers riding the scheduler's fn lane — no ladder verify
    # programs are reachable, so its tier set is empty. It is still a
    # first-class verify family: manifests record covering it, and
    # --verify --families sequencer fails against a manifest whose
    # recorded coverage predates the class (see check_families).
    "sequencer": (),
}

# committee-scale bucket rungs (PERF_ANALYSIS §16): batched vote gossip
# ships VOTE_BATCH_MAX-vote chunks (pad to 128) and whole-committee
# commit verifies at 100-200 validators land on 128/256 — a manifest
# missing these rungs leaves a committee-scale node compiling its vote
# path mid-height
COMMITTEE_BUCKETS = (128, 256)


def check_committee_rungs(manifest: dict) -> list[str]:
    """Committee-rung coverage violations (empty = pass): the manifest's
    entries must include every COMMITTEE_BUCKETS rung for at least one
    cached tier. Explicitly-partial ladders (--ladder without the
    rungs) fail here, which is the point — a committee-scale node warm-
    started from them compiles the vote path on the hot path."""
    built = {
        e["bucket"]
        for e in manifest.get("entries", ())
        if e["tier"] in ("small", "big")
    }
    missing = [b for b in COMMITTEE_BUCKETS if b not in built]
    if missing:
        return [
            f"committee-scale rung(s) {missing} not in the manifest "
            f"(built cached-tier buckets: {sorted(built)})"
        ]
    return []


def _build_mesh(devices: int, backend: str = ""):
    """Mesh over `devices` chips of the backend (0 = all visible; 1 or
    a 1-device backend = no mesh)."""
    if devices == 1:
        return None
    from tendermint_tpu.parallel import build_mesh

    return build_mesh(ici_parallelism=devices, mesh_backend=backend)


def live_device_count(backend: str = "") -> int:
    """Devices visible to the backend the node would mesh over."""
    import jax

    return len(jax.devices(backend or None))


def build_manifest(
    ladder=None,
    tiers=("small", "big", "generic"),
    devices: int = 1,
    mesh_backend: str = "",
    mesh_min_rows: int | None = None,
) -> dict:
    """Run the ladder prewarm on a fresh verifier + registry; returns
    the manifest dict (entries carry per-program wall seconds — on a
    cold cache that is compile+load, on a warm cache just load).
    `devices` > 1 (or 0 = all visible) builds the mesh verifier and
    prewarms both program families."""
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier
    from tendermint_tpu.crypto.shape_registry import (
        DEFAULT_BUCKET_LADDER,
        ShapeRegistry,
    )

    ladder = tuple(ladder) if ladder else DEFAULT_BUCKET_LADDER
    registry = ShapeRegistry(ladder)
    mesh = _build_mesh(devices, mesh_backend)
    verifier = BatchVerifier(
        mesh=mesh,
        min_device_batch=0,
        shape_registry=registry,
        mesh_min_rows=mesh_min_rows,
    )
    t0 = time.perf_counter()
    entries = verifier.prewarm_buckets(buckets=ladder, tiers=tiers)
    return {
        "created_unix": int(time.time()),
        "ladder": list(registry.ladder),
        "tiers": list(tiers),
        # the scheduler verify classes this build covers (see
        # FAMILY_TIERS); --verify fails when any class a node
        # dispatches — incl. the lightserve serving plane — finds its
        # reachable tiers missing from the built entries
        "families": sorted(
            f
            for f, req in FAMILY_TIERS.items()
            if all(t in tiers for t in req)
        ),
        "device_count": verifier.mesh_devices,
        "mesh_min_rows": verifier._mesh_min_rows,
        # the backend the mesh was built on: --verify must count live
        # devices of (and rebuild against) the SAME backend, or the
        # topology check compares apples to oranges
        "mesh_backend": mesh_backend,
        "entries": entries,
        "total_seconds": round(time.perf_counter() - t0, 3),
        "shapes_by_tier": registry.shapes_by_tier(),
    }


def check_budget(manifest: dict, budget: int) -> list[str]:
    """Per-tier distinct-shape budget violations (empty = pass). A
    program's shape is (bucket, rows, devices): the cached tiers'
    programs vary with the table-store row allocation, and a mesh
    verifier's sharded family doubles the bulk rungs."""
    problems = []
    by_tier: dict[str, set] = {}
    for e in manifest["entries"]:
        by_tier.setdefault(e["tier"], set()).add(
            (e["bucket"], e.get("rows", 0), e.get("devices", 1))
        )
    for tier, shapes in sorted(by_tier.items()):
        if len(shapes) > budget:
            problems.append(
                f"tier {tier}: {len(shapes)} distinct shapes > budget "
                f"{budget}: {sorted(shapes)}"
            )
    return problems


def check_families(manifest: dict, families=None) -> list[str]:
    """Per-family tier coverage violations (empty = pass): every verify
    class the manifest claims to cover must find its reachable tiers
    among the built entries — a `--tiers generic` manifest covers NO
    commit-verify class, and a node trusting it would compile the
    lightserve swarm's shared rounds (or any commit verify) on the hot
    path."""
    problems = []
    built_tiers = {e["tier"] for e in manifest.get("entries", ())}
    claimed = manifest.get("families")
    for family in families or claimed or ():
        required = FAMILY_TIERS.get(family)
        if required is None:
            # an unknown name (operator typo in --families) must FAIL,
            # not silently report coverage that was never checked
            problems.append(
                f"family {family!r} is not a known verify class "
                f"(known: {sorted(FAMILY_TIERS)})"
            )
            continue
        if claimed is not None and family not in claimed:
            # the manifest recorded its coverage and this class is not
            # in it — a build predating the class (e.g. `sequencer`) or
            # an explicitly partial one must fail the requirement even
            # when the class has no reachable ladder tiers
            problems.append(
                f"family {family}: not covered by this manifest build "
                f"(recorded coverage: {sorted(claimed)})"
            )
            continue
        if claimed is None and not required:
            # a family with NO reachable ladder tiers (sequencer) has
            # no tier evidence to check — only recorded coverage can
            # demonstrate it, so a legacy manifest without a `families`
            # key cannot vacuously pass the requirement
            problems.append(
                f"family {family}: manifest records no family coverage "
                f"and the class has no ladder tiers to check — rebuild "
                f"with a coverage-recording prewarm"
            )
            continue
        missing = [t for t in required if t not in built_tiers]
        if missing:
            problems.append(
                f"family {family}: reachable tier(s) {missing} not in "
                f"the manifest (built tiers: {sorted(built_tiers)})"
            )
    return problems


def check_topology(
    manifest: dict,
    live_devices: int,
    expected_min_rows: int | None = None,
) -> list[str]:
    """Mismatches between the manifest's mesh topology and the live
    one (empty = pass). A manifest built for N devices prewarmed the
    devices=N sharded programs; a node now meshing over M != N would
    compile every sharded shape on the hot path — fail loudly
    instead. `expected_min_rows` (the node's configured mesh_min_rows,
    when known) must also match: it decides WHICH rungs got the
    replicated vs sharded variant, so a drifted threshold silently
    changes the reachable program set even at the same device count."""
    problems = []
    built = int(manifest.get("device_count", 1))
    if built != live_devices:
        problems.append(
            f"manifest built for {built} device(s), live mesh has "
            f"{live_devices} — sharded programs would recompile on the "
            "hot path; rebuild the manifest on this topology"
        )
    if expected_min_rows is not None:
        built_rows = manifest.get("mesh_min_rows")
        if built_rows is not None and int(built_rows) != int(
            expected_min_rows
        ):
            problems.append(
                f"manifest built with mesh_min_rows={built_rows}, "
                f"expected {expected_min_rows} — the replicated/sharded "
                "variant split differs; rebuild the manifest"
            )
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--out", default=DEFAULT_MANIFEST, help="manifest path"
    )
    ap.add_argument(
        "--ladder",
        default="",
        help="comma-separated bucket ladder (default: built-in)",
    )
    ap.add_argument(
        "--tiers",
        default="small,big,generic",
        help="comma-separated tiers to prewarm",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=1,
        help="mesh device count to prewarm for (0 = all visible "
        "devices of --mesh-backend; 1 = no mesh)",
    )
    ap.add_argument(
        "--mesh-backend",
        default="",
        help="jax backend for the mesh ('' = default; 'cpu' = host "
        "virtual devices)",
    )
    ap.add_argument(
        "--mesh-min-rows",
        type=int,
        default=0,
        help="rounds below this stay unsharded (0 = built-in default)",
    )
    ap.add_argument(
        "--budget",
        type=int,
        default=8,
        help="max distinct program shapes per tier",
    )
    ap.add_argument(
        "--families",
        default="",
        help="--verify: comma-separated scheduler verify classes whose "
        "reachable tiers the manifest must cover (e.g. "
        "'light,lightserve'); default: the manifest's recorded "
        "coverage, or every known class for manifests without one",
    )
    ap.add_argument(
        "--verify",
        action="store_true",
        help="re-run an existing manifest's ladder on its recorded "
        "topology; fail on budget breach, slow reloads, or live "
        "device-count mismatch",
    )
    ap.add_argument(
        "--reload-threshold",
        type=float,
        default=60.0,
        help="--verify: per-program seconds above which the persistent "
        "cache is judged to not be absorbing the shape",
    )
    args = ap.parse_args()

    ladder = (
        tuple(int(x) for x in args.ladder.split(",") if x.strip())
        if args.ladder.strip()
        else None
    )
    tiers = tuple(t.strip() for t in args.tiers.split(",") if t.strip())
    devices = args.devices
    mesh_min_rows = args.mesh_min_rows or None
    mesh_backend = args.mesh_backend

    rc = 0
    if args.verify:
        if not os.path.exists(args.out):
            print(f"no manifest at {args.out}; run without --verify first")
            return 1
        with open(args.out) as f:
            prior = json.load(f)
        ladder = ladder or tuple(prior["ladder"])
        tiers = tuple(prior.get("tiers", tiers))
        devices = int(prior.get("device_count", 1))
        # an explicit --mesh-min-rows is the node's configured value:
        # check it against what the manifest was built with; otherwise
        # re-run on the manifest's own threshold
        expected_rows = mesh_min_rows
        mesh_min_rows = prior.get("mesh_min_rows") or mesh_min_rows
        # re-run on the manifest's recorded backend (CLI flag as the
        # pre-mesh_backend-manifest fallback): the live device count and
        # the rebuilt programs must come from the SAME backend the
        # manifest was built on
        mesh_backend = prior.get("mesh_backend", args.mesh_backend)
        # topology check BEFORE the rebuild: the re-run must load the
        # manifest's programs, and a mesh of a different size can't
        live = live_device_count(mesh_backend) if devices != 1 else 1
        for p in check_topology(
            prior,
            live if devices != 1 else devices,
            expected_min_rows=expected_rows,
        ):
            print(f"TOPOLOGY MISMATCH: {p}")
            rc = 1
        if devices != 1 and live < devices:
            # can't even construct the mesh; report and bail non-zero
            return 1
        if rc:
            # a drifted threshold means the rebuild below would load a
            # DIFFERENT program set than the manifest promises — the
            # mismatch is the verdict
            return rc

    manifest = build_manifest(
        ladder=ladder,
        tiers=tiers,
        devices=devices,
        mesh_backend=mesh_backend,
        mesh_min_rows=mesh_min_rows,
    )
    for e in manifest["entries"]:
        print(
            f"  {e['tier']:>8s}  bucket {e['bucket']:>6d}  "
            f"rows {e.get('rows', 0):>5d}  "
            f"devs {e.get('devices', 1):>3d}  {e['seconds']:7.2f}s"
        )
    print(
        f"{len(manifest['entries'])} programs, "
        f"{manifest['total_seconds']:.1f}s total, "
        f"{manifest['device_count']} device(s)"
    )

    problems = check_budget(manifest, args.budget)
    for p in problems:
        print(f"BUDGET VIOLATION: {p}")
        rc = 1
    if args.verify:
        # family coverage: an explicit --families is the operator's
        # requirement; a manifest that recorded its coverage is checked
        # against that intent (an explicitly partial --tiers build
        # stays partial); a node-built / legacy manifest without the
        # key must cover EVERY class the node dispatches — including
        # the lightserve serving plane's shared rounds
        if args.families.strip():
            required = [
                f.strip() for f in args.families.split(",") if f.strip()
            ]
        elif "families" in prior:
            required = prior["families"]
        else:
            required = sorted(FAMILY_TIERS)
        family_problems = check_families(manifest, families=required)
        for p in family_problems:
            print(f"FAMILY COVERAGE: {p}")
            rc = 1
        problems = problems + family_problems
        committee_problems = check_committee_rungs(manifest)
        for p in committee_problems:
            print(f"COMMITTEE COVERAGE: {p}")
            rc = 1
        problems = problems + committee_problems

    if args.verify:
        slow = [
            e
            for e in manifest["entries"]
            if e["seconds"] > args.reload_threshold
        ]
        for e in slow:
            print(
                f"RELOAD REGRESSION: {e['tier']}/{e['bucket']}"
                f"/devs{e.get('devices', 1)} took "
                f"{e['seconds']:.1f}s > {args.reload_threshold:.0f}s — "
                "persistent cache is not absorbing this shape"
            )
            rc = 1
        if not slow and not problems and rc == 0:
            print("verify OK: every ladder program loads within threshold")
    else:
        with open(args.out, "w") as f:
            json.dump(manifest, f, indent=1)
        print(f"wrote {args.out}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
