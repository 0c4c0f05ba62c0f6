"""chip_smoke.py — does the system still start on the chip?

Drives the program's device path, the verify plane, once through the
entry points a user calls, at the committee size BASELINE.json configs 2
and 4 name, and checks what comes back against the host verifier:

  native       build + load native/*.cpp (a silent pure-Python fallback
               is orders of magnitude slower for BLS)
  service      `python -m tendermint_tpu verify-service` owns the chip; a
               CPU-pinned client drives it through RemoteVerifyScheduler:
               a 128-vote live round, a 16 x 1,024 = 16,384-row catch-up
               window (first through ValidatorSet.verify_commits_light,
               then the same rows directly, warm), a 128-signer bls_agg
               group. Every submission carries seeded bad rows.
  node         `init` + `start` on the chip host, 5 commits, SIGTERM
  client-node  the service again + the same node with
               [scheduler] remote_socket set: 5 commits, 0 degrades, and
               the node never opens the chip

A pass is decided by evidence from the device side (platform, the
service's shape-registry and ledger counts, error frames, degrades,
bitmaps), never by "commits continued". This parent process never
initialises a JAX backend: a chip belongs to one process at a time, so
the stages' chip owners run strictly one after the other.

    python chip_smoke.py                      # the chip run, full size
    JAX_PLATFORMS=cpu python chip_smoke.py --validators 8 --commits 2 \\
        --live 8                              # debugging run on the CPU

The full size needs the chip: with no accelerator the script exits
non-zero and prints no result. Timings it prints are a smoke's
readings, not benchmark numbers. The last line of stdout is
{"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
CHAIN_ID = "chip-smoke"
T0_NS = 1_700_000_000_000_000_000
FULL = {"validators": 1024, "commits": 16, "live": 128}
STAGES = ("native", "service", "node", "client-node")
FAULTS = ("flip-verdict", "kill-service")
NODE_COMMITS = 5
STAGE_TIMEOUT = 900.0  # every wait on a child: exit, ready, commits
BAD_KINDS = ("flipped_bit", "wrong_key", "s_ge_L", "short_sig")
HOST_SAMPLE = 256


def say(msg: str) -> None:
    print(msg, flush=True)


# --- the client child: fixtures ----------------------------------------------


def make_committee(seed: int, n: int):
    """(ValidatorSet, [MockPV] in set order) from the seed."""
    from tendermint_tpu.types.priv_validator import MockPV
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    pvs = [
        MockPV.from_secret(b"chip-smoke|%d|val|%d" % (seed, i))
        for i in range(n)
    ]
    vs = ValidatorSet([Validator(pv.get_pub_key(), 10) for pv in pvs])
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    return vs, [by_addr[v.address] for v in vs.validators]


def signed_commit(vs, pvs, height: int, seed: int):
    """(block_id, Commit): every validator precommits the block."""
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.part_set import PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType
    from tendermint_tpu.types.vote_set import VoteSet

    hb = hashlib.sha256(b"chip-smoke|%d|block|%d" % (seed, height)).digest()
    bid = BlockID(hb, PartSetHeader(1, hb))
    votes = VoteSet(CHAIN_ID, height, 0, VoteType.PRECOMMIT, vs)
    for i, pv in enumerate(pvs):
        v = Vote(
            type=VoteType.PRECOMMIT,
            height=height,
            round=0,
            block_id=bid,
            timestamp_ns=T0_NS + height * 1_000_000_000 + i,
            validator_address=pv.get_pub_key().address(),
            validator_index=i,
        )
        pv.sign_vote(CHAIN_ID, v)
        votes.add_vote(v, verified=True)
    return bid, votes.make_commit()


def corrupt(commit, i: int, kind: str) -> None:
    """Make validator i's commit signature one of the four bad rows."""
    from tendermint_tpu.crypto.ed25519 import L

    sigs = commit.signatures
    sig = sigs[i].signature
    if kind == "flipped_bit":
        bad = bytes([sig[0] ^ 0x04]) + sig[1:]
    elif kind == "wrong_key":  # valid, but under the next validator's key
        bad = sigs[(i + 1) % len(sigs)].signature
    elif kind == "s_ge_L":  # s + L is the same scalar mod L, out of range
        s = int.from_bytes(sig[32:], "little") + L
        bad = sig[:32] + s.to_bytes(32, "little")
    elif kind == "short_sig":
        bad = sig[:63]
    else:
        raise ValueError(kind)
    sigs[i].signature = bad


def commit_rows(vs, commit) -> list:
    """One row per signature, in validator order, over the sign-bytes
    the commit reconstructs for each signer: what verify_commits_light
    batches for a commit nobody is absent from."""
    from tendermint_tpu.crypto.batch_verifier import SigItem

    return [
        SigItem(
            vs.validators[i].pub_key.data,
            commit.vote_sign_bytes(CHAIN_ID, i),
            cs.signature,
        )
        for i, cs in enumerate(commit.signatures)
    ]


def build_window(seed: int, vs, pvs, n_commits: int):
    """The catch-up window: n_commits commits over one validator set.
    One seeded commit carries bad signatures for more than a third of
    the power (must come back False); the others carry a sprinkle of bad
    rows small enough to keep their quorum. Returns (entries,
    expected_per_commit, items, expected_per_row, bad_row_indices)."""
    rng = random.Random(seed)
    n = vs.size()
    entries = [
        (bid, h, commit)
        for h in range(1, n_commits + 1)
        for bid, commit in [signed_commit(vs, pvs, h, seed)]
    ]
    bad_commit = rng.randrange(n_commits)
    plan = [
        (bad_commit, i, BAD_KINDS[j % 4])
        for j, i in enumerate(rng.sample(range(n), n // 3 + 1))
    ]
    good = [c for c in range(n_commits) if c != bad_commit]
    keep_quorum = n - (2 * n // 3 + 1)  # bad rows a good commit tolerates
    for j in range(min(4, keep_quorum * len(good))):
        c = good[j % len(good)]
        taken = {i for pc, i, _ in plan if pc == c}
        i = rng.choice([x for x in range(n) if x not in taken])
        plan.append((c, i, BAD_KINDS[j % 4]))
    # wrong_key copies the neighbour's signature: apply those first,
    # while every neighbour is still genuine
    for c, i, kind in sorted(plan, key=lambda p: p[2] != "wrong_key"):
        corrupt(entries[c][2], i, kind)
    bad = {(c, i) for c, i, _ in plan}
    items, expected = [], []
    for c, (_, _, commit) in enumerate(entries):
        items.extend(commit_rows(vs, commit))
        expected.extend((c, i) not in bad for i in range(n))
    per_commit = [c != bad_commit for c in range(n_commits)]
    bad_rows = [r for r, ok in enumerate(expected) if not ok]
    return entries, per_commit, items, expected, bad_rows


def build_live(seed: int, n: int):
    """The live round: one n-validator commit's precommits (config 1's
    committee) with one bad row of each kind. (items, expected)."""
    vs, pvs = make_committee(seed, n)
    _, commit = signed_commit(vs, pvs, 1, seed)
    rng = random.Random(seed + 1)
    victims = rng.sample(range(n), min(4, n // 2))
    # wrong_key first, while its neighbour's signature is still genuine
    for i, kind in sorted(
        zip(victims, BAD_KINDS), key=lambda p: p[1] != "wrong_key"
    ):
        corrupt(commit, i, kind)
    return commit_rows(vs, commit), [i not in victims for i in range(n)]


def build_bls_group(seed: int, n: int):
    """n signers dual-signing one batch hash; two seeded bad
    signatures. (wire items, expected)."""
    from tendermint_tpu.crypto import bls_signatures as bls

    msg = hashlib.sha256(b"chip-smoke|%d|batch-point" % seed).digest()
    rng = random.Random(seed + 2)
    bad = set(rng.sample(range(n), min(2, n // 2)))
    items = []
    for i in range(n):
        priv = 70001 + seed * 100003 + i
        signed = msg if i not in bad else b"another message"
        items.append(
            (
                bls.public_key_to_bytes(bls.pubkey_from_priv(priv)),
                msg,
                bls.signer_for(priv)(signed),
            )
        )
    return items, [i not in bad for i in range(n)]


def bucket(n: int) -> int:
    from tendermint_tpu.crypto.shape_registry import DEFAULT_BUCKET_LADDER

    return next(b for b in DEFAULT_BUCKET_LADDER if b >= n)


def expected_dispatches(live_items, window_items) -> tuple[dict, int]:
    """The shape-registry contents the service must report, from the
    sizes and the verifier's own thresholds: (shapes_by_tier, dispatch
    count). A submission of n rows pads to the ladder bucket; buckets
    >= BIGTABLE_MIN use the big tier; unseen keys build in chunks of
    TABLE_BUILD_CHUNK; the table store holds TABLE_ROWS_MIN rows,
    doubled until the keys fit."""
    from tendermint_tpu.crypto.batch_verifier import (
        BIGTABLE_MIN,
        TABLE_BUILD_CHUNK,
        TABLE_ROWS_MIN,
    )

    shapes: dict[str, set] = {}
    count = 0
    cached: dict[str, set] = {"small": set(), "big": set()}

    def dispatch(tier, b, rows=0):
        nonlocal count
        shapes.setdefault(tier, set()).add((b, rows, 1))
        count += 1

    for items in (live_items, window_items, window_items):
        b = bucket(len(items))
        tier = "big" if b >= BIGTABLE_MIN else "small"
        new = list(
            dict.fromkeys(
                it.pubkey for it in items
                if len(it.sig) == 64 and it.pubkey not in cached[tier]
            )
        )
        for lo in range(0, len(new), TABLE_BUILD_CHUNK):
            dispatch(
                "build_" + tier, bucket(len(new[lo : lo + TABLE_BUILD_CHUNK]))
            )
        cached[tier].update(new)
        rows = TABLE_ROWS_MIN
        while rows < len(cached[tier]):
            rows *= 2
        dispatch(tier, b, rows)
    return (
        {t: sorted(list(k) for k in s) for t, s in shapes.items()},
        count,
    )


# --- the client child: driving the service ----------------------------------


class NoLocalVerify:
    """The client's local fallback verifier is a tripwire: a degrade is
    a failure of the smoke, not something to absorb on the host."""

    def verify(self, items):
        raise RuntimeError(
            f"degraded: {len(items)} rows fell back to local verify — "
            "the service dropped a round"
        )


def service_dump(port: int) -> dict:
    """The service's own account of itself: device, compiles, shape
    registry, ledger, error frames."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/dump_dispatch_ledger", timeout=60.0
    ) as resp:
        return json.loads(resp.read())


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


async def attach(socket_path: str):
    from tendermint_tpu.parallel.verify_service import RemoteVerifyScheduler

    remote = RemoteVerifyScheduler(socket_path, verifier=NoLocalVerify())
    await remote.start()
    deadline = time.monotonic() + 60.0
    while not remote.connected and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
    check(remote.connected, "client never attached to the service")
    return remote


async def client_service_stage(args) -> dict:
    """Stage `service`, client side. Returns the report."""
    import numpy as np

    from tendermint_tpu.crypto import ed25519

    t0 = time.monotonic()
    live_items, live_expected = build_live(args.seed, args.live)
    vs, pvs = make_committee(args.seed, args.validators)
    entries, commit_expected, items, expected, bad_rows = build_window(
        args.seed, vs, pvs, args.commits
    )
    bls_items, bls_expected = build_bls_group(args.seed, args.live)
    say(
        f"  fixtures from seed {args.seed}: {len(live_items)} live rows, "
        f"{args.commits} commits x {args.validators} validators = "
        f"{len(items)} window rows ({len(bad_rows)} bad), "
        f"{len(bls_items)} bls signers "
        f"({time.monotonic() - t0:.1f} s on the host)"
    )
    if args.fault == "flip-verdict":
        expected[bad_rows[0]] = True

    remote = await attach(args.socket)
    loop = asyncio.get_running_loop()
    try:
        # live round: consensus class, small tier, with its table build
        t0 = time.monotonic()
        got_live = await remote.submit(live_items, "consensus")
        live_wall = time.monotonic() - t0
        check(
            np.asarray(got_live).tolist() == live_expected,
            "live round bitmap differs from the expectation",
        )
        dump0 = service_dump(args.stats_port)

        # window, first pass: the call blocksync makes, cold (compiles
        # and table builds inside the wall)
        if args.fault == "kill-service":
            loop.call_later(0.05, os.kill, args.service_pid, signal.SIGKILL)
        classed = remote.classed("blocksync")
        t0 = time.monotonic()
        got_commits = await loop.run_in_executor(
            None,
            lambda: vs.verify_commits_light(
                CHAIN_ID, entries, verifier=classed
            ),
        )
        cold_wall = time.monotonic() - t0
        check(
            got_commits == commit_expected,
            f"per-commit verdicts {got_commits} != {commit_expected}",
        )
        dump1 = service_dump(args.stats_port)

        # window, second pass: the same rows directly, warm
        t0 = time.monotonic()
        got_rows = np.asarray(await remote.submit(items, "blocksync"))
        warm_wall = time.monotonic() - t0
        check(
            got_rows.tolist() == expected,
            "window bitmap differs from the expectation at rows "
            f"{np.flatnonzero(got_rows != np.asarray(expected))[:8].tolist()}",
        )
        dump2 = service_dump(args.stats_port)

        # fn lane: host C++ behind the same scheduler
        t0 = time.monotonic()
        got_bls = await remote.submit_wire_fn(
            "bls_agg", bls_items, "consensus"
        )
        bls_wall = time.monotonic() - t0
        check(
            [bool(v) for v in got_bls] == bls_expected,
            "bls_agg verdicts differ from the expectation",
        )
        ipc = remote.ipc_stats()
    finally:
        await remote.stop()

    # the host serial verifier agrees: every bad row + a seeded sample
    rng = random.Random(args.seed + 3)
    good_rows = [r for r, ok in enumerate(expected) if ok]
    sample = bad_rows + rng.sample(
        good_rows, min(HOST_SAMPLE, len(good_rows))
    )
    for rows, verdicts in (
        ([(r, items[r]) for r in sample], got_rows),
        (list(enumerate(live_items)), np.asarray(got_live)),
    ):
        for r, it in rows:
            check(
                ed25519.verify(it.pubkey, it.msg, it.sig) == bool(verdicts[r]),
                f"host verifier disagrees with the device at row {r}",
            )
    say(
        f"  bitmaps equal the expectation; host serial verifier agrees on "
        f"{len(sample)} window rows ({len(bad_rows)} bad) and "
        f"{len(live_items)} live rows"
    )

    # device-side evidence
    final = service_dump(args.stats_port)
    shapes, count = expected_dispatches(live_items, items)
    shapes["bls_agg"] = [[bucket(len(bls_items)), 0, 1]]
    reg = final["shape_registry"]
    check(
        reg["shapes_by_tier"] == shapes,
        f"shape registry {reg['shapes_by_tier']} != expected {shapes}",
    )
    check(
        reg["device_dispatch_count"] == count + 1,
        f"{reg['device_dispatch_count']} dispatches, expected {count + 1}",
    )
    summary = final["summary"]
    by_bucket = {
        int(b): (v["rounds"], v["rows_requested"])
        for b, v in summary["by_bucket"].items()
    }
    want_rounds: dict[int, tuple] = {}
    for n in (len(live_items), len(items), len(items)):
        b = bucket(n)
        r, q = want_rounds.get(b, (0, 0))
        want_rounds[b] = (r + 1, q + n)
    check(
        by_bucket == want_rounds,
        f"ledger rounds by bucket {by_bucket} != expected {want_rounds}",
    )
    check(
        summary["per_engine"].get("bls_agg", {}).get("rounds") == 1,
        "ledger shows no bls_agg round",
    )
    check(
        final["service"]["error_frames"] == 0,
        f"{final['service']['error_frames']} error frames",
    )
    check(
        ipc["degrades"] == 0 and ipc["reconnects"] == 1,
        f"client degraded or re-attached: {ipc}",
    )
    warm_compiles = (
        dump2["service"]["compile"]["compilations"]
        - dump1["service"]["compile"]["compilations"]
    )
    check(
        warm_compiles == 0,
        f"{warm_compiles} compilations in the warm pass",
    )
    say(
        f"  registry {shapes} / {count + 1} dispatches, ledger rounds "
        f"{want_rounds} + 1 bls_agg, 0 error frames, 0 degrades, "
        "0 compilations in the warm pass"
    )
    return {
        "service": final["service"],
        "rows": {"live": len(live_items), "window": len(items),
                 "bad": len(bad_rows), "bls": len(bls_items)},
        "walls": {"live": live_wall, "window_cold": cold_wall,
                  "window_warm": warm_wall, "bls_agg": bls_wall},
        "memory": {
            "before_window": dump0["service"].get("bytes_in_use"),
            "after_window": dump2["service"].get("bytes_in_use"),
        },
    }


async def client_reload_stage(args) -> dict:
    """Stage `client-node`, client side: a NEW service process loads
    the live round's programs — from the compile cache the service
    stage filled."""
    import numpy as np

    live_items, live_expected = build_live(args.seed, args.live)
    remote = await attach(args.socket)
    try:
        got = await remote.submit(live_items, "consensus")
        ipc = remote.ipc_stats()
    finally:
        await remote.stop()
    check(
        np.asarray(got).tolist() == live_expected,
        "live round bitmap differs from the expectation (reload)",
    )
    check(ipc["degrades"] == 0, f"client degraded: {ipc}")
    final = service_dump(args.stats_port)["service"]
    comp = final["compile"]
    check(
        comp["cache_hits"] >= 1 and comp["cache_misses"] == 0,
        f"the second service process missed the compile cache: {comp}",
    )
    return {"service": final}


def child_native(args) -> dict:
    """Per library: did the native path build and load here?"""
    from tendermint_tpu.crypto import aead, bls_native, secp_native

    libs = {
        "bls12_381": bls_native.native_lib(),
        "secp256k1": secp_native.native_lib(),
        "chacha20poly1305": aead._native_lib(),
    }
    for name, lib in libs.items():
        check(
            lib is not None,
            f"native/{name}.cpp did not build or load; the pure-Python "
            "fallback would run instead",
        )
    say("  native: " + ", ".join(f"{n} loaded" for n in libs))
    return {"native": sorted(libs)}


def child_main(args) -> int:
    from tendermint_tpu.libs.jax_cache import configure_compile_cache

    configure_compile_cache()
    if args.child == "native":
        report = child_native(args)
    elif args.child == "service-client":
        report = asyncio.run(client_service_stage(args))
    else:
        report = asyncio.run(client_reload_stage(args))
    with open(args.report, "w") as f:
        json.dump(report, f)
    return 0


# --- the parent: processes, strictly in sequence ------------------------------


class Procs:
    """Every process the smoke starts, so that all of them are stopped
    whatever happens."""

    def __init__(self):
        self.live: list[subprocess.Popen] = []

    def spawn(self, cmd, log_path=None, **kw) -> subprocess.Popen:
        if log_path is None:
            proc = subprocess.Popen(cmd, cwd=ROOT, **kw)
        else:
            with open(log_path, "ab") as log:
                proc = subprocess.Popen(
                    cmd, cwd=ROOT, stdout=log, stderr=log, **kw
                )
        self.live.append(proc)
        return proc

    def kill_all(self) -> None:
        for p in self.live:
            if p.poll() is None:
                p.kill()
        for p in self.live:
            p.wait()


def cpu_env() -> dict:
    """A child that runs BESIDE a chip owner is pinned to the CPU."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_child(procs: Procs, args, work: str, child: str, extra=()) -> dict:
    report = os.path.join(work, f"{child}.json")
    cmd = [
        sys.executable, os.path.join(ROOT, "chip_smoke.py"),
        "--child", child, "--report", report,
        "--seed", str(args.seed),
        "--validators", str(args.validators),
        "--commits", str(args.commits),
        "--live", str(args.live),
        *extra,
    ]
    proc = procs.spawn(cmd, env=cpu_env())
    rc = proc.wait(timeout=STAGE_TIMEOUT)
    check(rc == 0, f"{child} child exited {rc}")
    with open(report) as f:
        return json.load(f)


def start_service(procs: Procs, work: str, tag: str):
    """The chip-owning process, environment inherited. Returns
    (proc, socket path, stats port) once it signalled ready."""
    sock = os.path.join(work, f"{tag}.sock")
    rfd, wfd = os.pipe()
    proc = procs.spawn(
        [
            sys.executable, "-m", "tendermint_tpu", "verify-service",
            "--socket", sock, "--stats-port", "0",
            "--ready-fd", str(wfd),
        ],
        log_path=os.path.join(work, f"{tag}.log"),
        pass_fds=(wfd,),
    )
    os.close(wfd)
    os.set_blocking(rfd, False)
    ready = b""
    deadline = time.monotonic() + STAGE_TIMEOUT
    try:
        while not ready and time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            try:
                ready = os.read(rfd, 4096)
            except BlockingIOError:
                time.sleep(0.05)
    finally:
        os.close(rfd)
    check(
        bool(ready),
        f"verify service never signalled ready (rc={proc.poll()}):\n"
        + tail(os.path.join(work, f"{tag}.log")),
    )
    return proc, sock, json.loads(ready)["stats_port"]


def require_platform(args, port: int) -> dict:
    """The strict rule, applied by the parent (whose environment is the
    one the user set) to what the SERVICE resolved; the full size is a
    chip run whatever JAX_PLATFORMS says. Returns the service block."""
    from tendermint_tpu.libs.device import require_chip

    service = service_dump(port)["service"]
    require_chip(service["platform"])
    full = all(getattr(args, k) == v for k, v in FULL.items())
    check(
        service["platform"] == "tpu" or not full,
        f"the full size needs the chip, the service resolved "
        f"{service['platform']!r} (reduce --validators/--commits/--live "
        "for a CPU debugging run)",
    )
    say(
        f"  service device: platform {service['platform']}, "
        f"device_kind {service['device_kind']}, "
        f"device_count {service['device_count']}"
    )
    return service


def node_device(log_path: str, service_platform: str) -> dict:
    """The device the node logged at assembly, held to the same rule as
    the service's. JAX falls back to the CPU with a warning when the
    chip is not free yet, and such a node commits all the same."""
    from tendermint_tpu.libs.device import require_chip

    device = log_fields(log_path, "node device")
    require_chip(device["platform"])
    check(
        device["platform"] == service_platform,
        f"the node opened platform {device['platform']!r}, the service "
        f"before it {service_platform!r}",
    )
    return device


def stop_clean(proc: subprocess.Popen, log_path: str, what: str) -> None:
    """SIGTERM, exit code 0, no traceback in the log."""
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=300)
    with open(log_path, errors="replace") as f:
        log = f.read()
    check(rc == 0, f"{what} exited {rc} on SIGTERM:\n{log[-3000:]}")
    check("Traceback" not in log, f"{what} logged a traceback:\n{log[-3000:]}")


def tail(path: str, n: int = 3000) -> str:
    with open(path, errors="replace") as f:
        return f.read()[-n:]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def node_rpc(port: int, method: str, **params) -> dict:
    body = json.dumps(
        {"jsonrpc": "2.0", "method": method, "params": params, "id": 1}
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10.0) as resp:
        out = json.loads(resp.read())
    check(not out.get("error"), f"rpc {method}: {out.get('error')}")
    return out["result"]


def run_node(procs: Procs, home: str, log_path: str):
    """`start` with the config `init` wrote (ports aside), wait for
    NODE_COMMITS more commits over RPC `status`. Returns (proc, port)."""
    rpc, p2p = free_port(), free_port()
    env = dict(os.environ)
    env.pop("TM_TPU_SKIP_WARM", None)  # the warm thread is under test
    proc = procs.spawn(
        [
            sys.executable, "-m", "tendermint_tpu", "--home", home, "start",
            "--rpc.laddr", f"tcp://127.0.0.1:{rpc}",
            "--p2p.laddr", f"tcp://127.0.0.1:{p2p}",
        ],
        log_path=log_path,
        env=env,
    )
    base = None
    deadline = time.monotonic() + STAGE_TIMEOUT
    while time.monotonic() < deadline:
        check(
            proc.poll() is None,
            f"node exited {proc.poll()} before {NODE_COMMITS} commits:\n"
            + tail(log_path),
        )
        try:
            status = node_rpc(rpc, "status")
            h = int(status["sync_info"]["latest_block_height"])
        except OSError:
            h = None  # RPC not up yet
        if h is not None:
            base = h if base is None else base
            if h >= base + NODE_COMMITS:
                return proc, rpc
        time.sleep(0.5)
    raise SystemExit(
        f"chip_smoke FAILED: node made no {NODE_COMMITS} commits in "
        f"{STAGE_TIMEOUT:.0f} s:\n" + tail(log_path)
    )


def log_fields(log_path: str, message: str) -> dict:
    """key=value fields of the first log line carrying `message`."""
    with open(log_path, errors="replace") as f:
        for line in f:
            if message in line:
                rest = line.split(message, 1)[1]
                return dict(
                    kv.split("=", 1) for kv in rest.split() if "=" in kv
                )
    raise SystemExit(
        f"chip_smoke FAILED: no {message!r} line in {log_path}:\n"
        + tail(log_path)
    )


def stage_service(procs: Procs, args, work: str) -> dict:
    say("stage service: verify-service owns the device, a CPU client drives it")
    svc, sock, port = start_service(procs, work, "service")
    require_platform(args, port)
    extra = ["--socket", sock, "--stats-port", str(port)]
    if args.fault:
        extra += ["--fault", args.fault, "--service-pid", str(svc.pid)]
    report = run_child(procs, args, work, "service-client", extra)
    stop_clean(svc, os.path.join(work, "service.log"), "verify service")
    return report


def stage_node(procs: Procs, work: str, service_platform: str) -> dict:
    say("stage node: init + start on this host, no JAX_PLATFORMS override")
    home = os.path.join(work, "home")
    with open(os.path.join(work, "init.log"), "wb") as log:
        subprocess.run(
            [sys.executable, "-m", "tendermint_tpu", "--home", home, "init"],
            cwd=ROOT, check=True, stdout=log, stderr=log,
        )
    log_path = os.path.join(work, "node.log")
    proc, _ = run_node(procs, home, log_path)
    stop_clean(proc, log_path, "node")
    device = node_device(log_path, service_platform)
    warm = log_fields(log_path, "validator-table warm complete")
    say(
        f"  {NODE_COMMITS} commits, exit 0 on SIGTERM, no traceback; node "
        f"platform {device['platform']}; validator-table warm: {warm}"
    )
    return {"device": device, "warm": warm}


def stage_client_node(procs: Procs, args, work: str) -> dict:
    say("stage client-node: the service again + the node as its client")
    home = os.path.join(work, "home")
    svc, sock, port = start_service(procs, work, "service2")
    require_platform(args, port)
    cfg_path = os.path.join(home, "config", "config.toml")
    with open(cfg_path) as f:
        cfg = f.read()
    check(cfg.count('remote_socket = ""') == 1, "no remote_socket line")
    with open(cfg_path, "w") as f:
        f.write(cfg.replace('remote_socket = ""', f'remote_socket = "{sock}"'))
    log_path = os.path.join(work, "client-node.log")
    proc, rpc = run_node(procs, home, log_path)
    reload = run_child(
        procs, args, work, "reload-client",
        ["--socket", sock, "--stats-port", str(port)],
    )
    dump = service_dump(port)
    ipc = node_rpc(rpc, "dump_dispatch_ledger", entries=0)["ipc"]
    stop_clean(proc, log_path, "client node")
    stop_clean(svc, os.path.join(work, "service2.log"), "verify service")
    device = log_fields(log_path, "node device")
    check(
        device["platform"] == "cpu",
        f"the client node opened platform {device['platform']!r}",
    )
    log = tail(log_path, 1 << 20)
    check("verify-service attached" in log, "client node never attached")
    check(
        "verify-service connection lost" not in log,
        "client node lost the service",
    )
    check(
        ipc["remote_submissions"] > 0
        and ipc["degrades"] == 0
        and ipc["reconnects"] == 1,
        f"the node's rounds did not all go through the service: {ipc}",
    )
    check(
        dump["service"]["error_frames"] == 0,
        f"{dump['service']['error_frames']} error frames",
    )
    comp = reload["service"]["compile"]
    say(
        f"  {NODE_COMMITS} commits beside a live service, exit 0, node "
        f"platform cpu, {ipc['remote_submissions']} rounds through the "
        f"service, 0 degrades, 0 error frames; the second service process loaded "
        f"its programs with cache_hits {comp['cache_hits']}, cache_misses "
        f"{comp['cache_misses']}"
    )
    return {"device": device, "reload": reload["service"]}


def print_summary(args, results: dict) -> dict:
    svc = results["service"]["service"]
    on_chip = svc["platform"] == "tpu"
    say("chip_smoke summary (a smoke's readings, not benchmark numbers)")
    say(
        f"  platform: {svc['platform']}  device_kind: {svc['device_kind']}"
        f"  device_count: {svc['device_count']}"
    )
    r = results["service"]
    say(
        f"  rows: live {r['rows']['live']}, window {r['rows']['window']} "
        f"({r['rows']['bad']} bad), bls {r['rows']['bls']}"
    )
    if on_chip:
        w = r["walls"]
        say(
            f"  window wall, first pass (compiles + table builds): "
            f"{w['window_cold']:.2f} s; second pass (warm): "
            f"{w['window_warm']:.3f} s; live round (cold): "
            f"{w['live']:.2f} s; bls_agg: {w['bls_agg']:.3f} s"
        )
        for name, p in svc["compile"]["programs"].items():
            if p["seconds"] >= 0.5:
                say(f"  compile {name}: {p['seconds']:.1f} s x{p['count']}")
        say(
            f"  compile total {svc['compile']['seconds']:.1f} s over "
            f"{svc['compile']['compilations']} programs, cache_hits "
            f"{svc['compile']['cache_hits']}, cache_misses "
            f"{svc['compile']['cache_misses']}"
        )
        mem = r["memory"]
        say(f"  peak_bytes_in_use: {svc.get('peak_bytes_in_use')}")
        say(
            f"  bytes_in_use after the window minus before: "
            f"{mem['after_window'] - mem['before_window']} (the "
            f"{args.validators}-key table store and the programs the "
            "window loaded)"
        )
        if "client-node" in results:
            for name, p in results["client-node"]["reload"]["compile"][
                "programs"
            ].items():
                if name in svc["compile"]["programs"] and (
                    svc["compile"]["programs"][name]["seconds"] >= 0.5
                ):
                    say(
                        f"  reload {name}: {p['seconds']:.2f} s "
                        "(from the compile cache)"
                    )
    else:
        say("  device readings: not measured (this run was held to the CPU)")
    return {
        "platform": svc["platform"],
        "kind": svc["device_kind"],
        "count": svc["device_count"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    for k, v in FULL.items():
        ap.add_argument(f"--{k}", type=int, default=v)
    ap.add_argument("--stages", default=",".join(STAGES))
    ap.add_argument(
        "--work", default=None,
        help="keep logs, homes and reports in this directory "
        "(default: a temporary one, removed at the end)",
    )
    ap.add_argument(
        "--fault", choices=FAULTS, default=None,
        help="self-test: the smoke must exit non-zero under this fault",
    )
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--report", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--socket", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--stats-port", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--service-pid", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return child_main(args)

    stages = [s for s in args.stages.split(",") if s]
    check(
        set(stages) <= set(STAGES) and "service" in stages,
        f"--stages takes {STAGES} and always includes service",
    )
    procs = Procs()
    if args.work:
        os.makedirs(args.work)
    work = os.path.abspath(args.work or tempfile.mkdtemp(prefix="chip-smoke-"))
    results: dict = {}
    try:
        if "native" in stages:
            say("stage native: build and load native/*.cpp")
            results["native"] = run_child(procs, args, work, "native")
        results["service"] = stage_service(procs, args, work)
        if "node" in stages:
            results["node"] = stage_node(
                procs, work, results["service"]["service"]["platform"]
            )
        if "client-node" in stages:
            check("node" in stages, "client-node reuses the node stage's home")
            results["client-node"] = stage_client_node(procs, args, work)
    finally:
        procs.kill_all()
        if not args.work:
            shutil.rmtree(work, ignore_errors=True)
    device = print_summary(args, results)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
