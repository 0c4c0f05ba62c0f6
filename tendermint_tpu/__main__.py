"""CLI — `python -m tendermint_tpu <command>`.

Reference: cmd/tendermint/main.go:16-48 (cobra command tree): init, start,
testnet, rollback, reset, gen-validator, gen-node-key, show-node-id,
show-validator, version.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys

from .config import Config
from .libs import cli as libs_cli
from .version import (
    BLOCK_PROTOCOL_VERSION,
    P2P_PROTOCOL_VERSION,
    TMCORE_SEM_VER,
)


def _load_config(args) -> Config:
    cfg = Config.load(args.home)
    cfg.root_dir = args.home
    return cfg


def cmd_init(args) -> int:
    from .node import init_files

    cfg = _load_config(args)
    if args.chain_id:
        cfg.base.chain_id = args.chain_id
    init_files(cfg)
    cfg.save()
    print(f"initialized node in {args.home}")
    return 0


def cmd_start(args) -> int:
    from .node import Node

    cfg = _load_config(args)
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.persistent_peers:
        cfg.p2p.persistent_peers = args.persistent_peers
    if args.switch_height:
        cfg.consensus.switch_height = args.switch_height
    node = Node(cfg)

    async def run():
        import signal

        # handlers first: a signal that arrives while the node is still
        # starting stops it cleanly once it has started
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await node.start()
        stopped = loop.create_task(stop.wait())
        try:
            # run until a signal asks for a clean stop, or a background
            # part of startup (the verifier warm) fails
            await asyncio.wait(
                {stopped, node.failed},
                return_when=asyncio.FIRST_COMPLETED,
            )
        finally:
            stopped.cancel()
            await node.stop()
        if node.failed.done():
            raise node.failed.exception()

    asyncio.run(run())
    return 0


def cmd_testnet(args) -> int:
    """Generate a local N-validator testnet layout
    (reference cmd/tendermint/commands/testnet.go)."""
    import time

    from .p2p.key import NodeKey
    from .privval.file_pv import FilePV
    from .types.genesis import GenesisDoc, GenesisValidator

    n = args.v
    base = args.output
    os.makedirs(base, exist_ok=True)
    nodes = []
    for i in range(n):
        home = os.path.join(base, f"node{i}")
        cfg = Config()
        cfg.root_dir = home
        cfg.ensure_dirs()
        nk = NodeKey.load_or_generate(cfg.node_key_file)
        pv = FilePV.load_or_generate(
            cfg.priv_validator_key_file, cfg.priv_validator_state_file
        )
        nodes.append((home, cfg, nk, pv))
    doc = GenesisDoc(
        chain_id=args.chain_id or "testnet-%06x" % (int(time.time()) & 0xFFFFFF),
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator("ed25519", pv.get_pub_key().data, 10)
            for _, _, _, pv in nodes
        ],
    )
    doc.validate_and_complete()
    peers = ",".join(
        f"{nk.id}@127.0.0.1:{26656 + 10 * i}"
        for i, (_, _, nk, _) in enumerate(nodes)
    )
    for i, (home, cfg, nk, pv) in enumerate(nodes):
        doc.save_as(cfg.genesis_file)
        cfg.p2p.laddr = f"tcp://127.0.0.1:{26656 + 10 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{26657 + 10 * i}"
        cfg.p2p.persistent_peers = peers
        cfg.save()
    print(f"wrote {n}-node testnet to {base} (chain {doc.chain_id})")
    return 0


def cmd_rollback(args) -> int:
    """Roll back one height of state (reference rollback.go)."""
    cfg = _load_config(args)
    from .store.kv import SqliteKV
    from .state.store import StateStore
    from .store.block_store import BlockStore

    ss = StateStore(SqliteKV(os.path.join(cfg.db_dir, "state.db")))
    bs = BlockStore(SqliteKV(os.path.join(cfg.db_dir, "blockstore.db")))
    state = ss.rollback(bs)
    if args.hard:
        # remove the rolled-back block too (prune_blocks_since removes
        # blocks ABOVE the argument)
        bs.prune_blocks_since(state.last_block_height)
    print(
        f"rolled back to height {state.last_block_height} "
        f"(app hash {state.app_hash.hex()})"
    )
    return 0


def cmd_reset(args) -> int:
    """unsafe-reset-all: wipe data, keep config (reference reset.go)."""
    cfg = _load_config(args)
    data = cfg.db_dir
    if os.path.isdir(data):
        shutil.rmtree(data)
    os.makedirs(data, exist_ok=True)
    # reset privval state (keep the key)
    st = cfg.priv_validator_state_file
    if os.path.exists(st):
        os.remove(st)
    print(f"reset {data}")
    return 0


def cmd_gen_validator(args) -> int:
    from .crypto import ed25519

    k = ed25519.PrivKey.generate()
    print(
        json.dumps(
            {
                "pub_key": k.public_key().data.hex(),
                "priv_key_seed": k.seed.hex(),
                "address": k.public_key().address().hex(),
            },
            indent=2,
        )
    )
    return 0


def cmd_gen_node_key(args) -> int:
    from .p2p.key import NodeKey

    nk = NodeKey.generate()
    print(json.dumps({"id": nk.id}, indent=2))
    return 0


def cmd_show_node_id(args) -> int:
    from .p2p.key import NodeKey

    cfg = _load_config(args)
    nk = NodeKey.load_or_generate(cfg.node_key_file)
    print(nk.id)
    return 0


def cmd_show_validator(args) -> int:
    from .privval.file_pv import FilePV

    cfg = _load_config(args)
    pv = FilePV.load_or_generate(
        cfg.priv_validator_key_file, cfg.priv_validator_state_file
    )
    pub = pv.get_pub_key()
    print(
        json.dumps(
            {"pub_key": pub.data.hex(), "address": pub.address().hex()}
        )
    )
    return 0


def cmd_light(args) -> int:
    """Run a light-client proxy (reference cmd light.go + light/proxy)."""
    from .crypto._native_build import preload_in_background
    from .light.client import LightClient, TrustOptions
    from .light.proxy import LightProxy
    from .light.store import LightStore
    from .rpc.light_provider import RPCProvider
    from .store.kv import SqliteKV

    # warm the native crypto libs off-thread: first-use otherwise pays
    # a synchronous g++ compile inline on the verify path
    preload_in_background()

    os.makedirs(args.home, exist_ok=True)
    store = LightStore(SqliteKV(os.path.join(args.home, "light.db")))
    trust = None
    if args.trusted_height and args.trusted_hash:
        trust = TrustOptions(
            int(args.trust_period * 1e9),
            args.trusted_height,
            bytes.fromhex(args.trusted_hash),
        )
    lc = LightClient(
        args.chain_id,
        trust,
        RPCProvider(args.chain_id, args.primary),
        [RPCProvider(args.chain_id, w) for w in args.witnesses.split(",") if w],
        store,
        sequential=args.sequential,
    )
    host, _, port = args.laddr.removeprefix("tcp://").rpartition(":")
    proxy = LightProxy(lc, args.primary, host or "127.0.0.1", int(port))

    async def run():
        await proxy.start()
        print(f"light proxy for {args.chain_id} on {args.laddr}")
        try:
            await asyncio.Event().wait()
        finally:
            await proxy.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_replay(args) -> int:
    """Print (and in --console mode, step through) the consensus WAL
    (reference replay_file.go: RunReplayFile)."""
    from .consensus.wal import WAL

    cfg = _load_config(args)
    wal_path = cfg.wal_file
    if not os.path.exists(wal_path):
        print(f"no WAL at {wal_path}")
        return 1
    wal = WAL(wal_path)
    msgs = wal.search_for_end_height(0) or []
    count = 0
    for rec in msgs:
        count += 1
        print(f"#{count} {rec!r}")
        if args.console:
            input("  <enter> for next> ")
    print(f"replayed {count} WAL records")
    return 0


def cmd_rewind(args) -> int:
    """Rewind state + blocks to --height (reference rewind.go)."""
    cfg = _load_config(args)
    from .state.store import StateStore
    from .store.block_store import BlockStore
    from .store.kv import SqliteKV

    ss = StateStore(SqliteKV(os.path.join(cfg.db_dir, "state.db")))
    bs = BlockStore(SqliteKV(os.path.join(cfg.db_dir, "blockstore.db")))
    state = ss.load()
    if state is None:
        print("no state to rewind")
        return 1
    target = args.height
    while state.last_block_height > target:
        state = ss.rollback(bs)
    bs.prune_blocks_since(state.last_block_height)
    print(f"rewound to height {state.last_block_height}")
    return 0


def cmd_compact(args) -> int:
    """VACUUM the sqlite stores (reference compact.go's goleveldb
    compaction)."""
    import sqlite3

    cfg = _load_config(args)
    for name in ("state.db", "blockstore.db", "evidence.db", "tx_index.db"):
        path = os.path.join(cfg.db_dir, name)
        if os.path.exists(path):
            before = os.path.getsize(path)
            conn = sqlite3.connect(path)
            conn.execute("VACUUM")
            conn.close()
            print(f"{name}: {before} -> {os.path.getsize(path)} bytes")
    return 0


def cmd_reindex_event(args) -> int:
    """Rebuild the tx/block index from stored blocks + ABCI responses
    (reference reindex_event.go)."""
    cfg = _load_config(args)
    from .state.execution import ABCIResponses
    from .state.store import StateStore
    from .state.txindex import KVIndexer, TxResult
    from .store.block_store import BlockStore
    from .store.kv import SqliteKV

    ss = StateStore(SqliteKV(os.path.join(cfg.db_dir, "state.db")))
    bs = BlockStore(SqliteKV(os.path.join(cfg.db_dir, "blockstore.db")))
    ix = KVIndexer(SqliteKV(os.path.join(cfg.db_dir, "tx_index.db")))
    start = args.start_height or bs.base
    end = args.end_height or bs.height
    n_tx = 0
    for h in range(start, end + 1):
        blk = bs.load_block(h)
        if blk is None:
            continue
        raw = ss.load_abci_responses(h)
        results = ABCIResponses.decode(raw).deliver_txs if raw else []
        # block-level (begin/end-block) events are not persisted in
        # ABCIResponses, so only tx events can be rebuilt offline
        for i, tx in enumerate(blk.data.txs):
            res = results[i] if i < len(results) else None
            ix.index_tx(
                TxResult(
                    height=h,
                    index=i,
                    tx=tx,
                    code=res.code if res else 0,
                    log=res.log if res else "",
                    events=[
                        (e.type, e.attributes) for e in res.events
                    ] if res else [],
                )
            )
            n_tx += 1
    print(f"reindexed heights [{start},{end}]: {n_tx} txs")
    return 0


def cmd_debug_dump(args) -> int:
    """Snapshot node debug state to a directory (reference
    cmd/tendermint/commands/debug: dump)."""
    from .rpc.light_provider import RPCClient

    os.makedirs(args.output, exist_ok=True)

    async def run() -> int:
        rpc = RPCClient(args.rpc_laddr)
        for method in (
            "status",
            "net_info",
            "consensus_state",
            "dump_consensus_state",
        ):
            try:
                res = await rpc.call(method)
            except Exception as e:
                res = {"error": str(e)}
            with open(os.path.join(args.output, f"{method}.json"), "w") as f:
                json.dump(res, f, indent=2)
        if args.pprof_laddr:
            host, _, port = (
                args.pprof_laddr.removeprefix("tcp://").rpartition(":")
            )
            for route in ("goroutine", "heap"):
                try:
                    reader, writer = await asyncio.open_connection(
                        host or "127.0.0.1", int(port)
                    )
                    writer.write(
                        f"GET /debug/pprof/{route} HTTP/1.1\r\n"
                        f"Host: x\r\n\r\n".encode()
                    )
                    await writer.drain()
                    data = await reader.read()
                    writer.close()
                    body = data.split(b"\r\n\r\n", 1)[-1]
                    with open(
                        os.path.join(args.output, f"{route}.txt"), "wb"
                    ) as f:
                        f.write(body)
                except (ConnectionError, OSError) as e:
                    print(f"pprof {route}: {e}")
        return 0

    rc = asyncio.run(run())
    print(f"wrote debug dump to {args.output}")
    return rc


def cmd_probe_upnp(args) -> int:
    """Discover the UPnP gateway and exercise a full map/unmap round
    trip on a probe port (reference probe_upnp.go)."""
    from .p2p.upnp import UPnPError, discover

    try:
        gw = discover()
    except UPnPError as e:
        print(f"no UPnP gateway found ({e})")
        return 1
    print(f"UPnP gateway: {gw.service_type} at {gw.control_url}")
    try:
        print(f"external IP: {gw.get_external_ip()}")
        probe_port = 26699
        gw.add_port_mapping(probe_port, probe_port)
        print(f"mapped probe port {probe_port} -> OK")
        gw.delete_port_mapping(probe_port)
        print("unmapped probe port -> OK")
    except UPnPError as e:
        print(f"gateway found but mapping failed: {e}")
        return 1
    return 0


def cmd_probe_tpu(args) -> int:
    """Show the device plane as the node would see it: backend, device
    inventory, and the mesh the [tpu] config section resolves to —
    the operator's first stop when sharded verification doesn't engage."""
    from .config import Config

    cfg = Config.load(args.home)
    t = cfg.tpu
    print(
        f"[tpu] ici_parallelism={t.ici_parallelism} "
        f"dcn_parallelism={t.dcn_parallelism} "
        f"mesh_backend={t.mesh_backend or '(default)'}"
    )
    import jax

    try:
        devs = jax.devices(t.mesh_backend or None)
    except Exception as e:
        print(f"backend unavailable: {e}")
        return 1
    print(f"backend: {jax.default_backend()}, {len(devs)} device(s)")
    for d in devs[:16]:
        print(f"  {d.id}: {d.device_kind} (process {d.process_index})")
    if len(devs) > 16:
        print(f"  ... and {len(devs) - 16} more")
    from .parallel import build_mesh

    try:
        mesh = build_mesh(
            t.ici_parallelism, t.dcn_parallelism, t.mesh_backend
        )
    except ValueError as e:
        print(f"mesh: UNSATISFIABLE ({e})")
        return 1
    if mesh is None:
        print("mesh: none (single-device verification path)")
    else:
        print(
            f"mesh: axes {dict(mesh.shape)} -> batch dim shards over "
            f"{mesh.devices.size} devices"
        )
    return 0


def cmd_verify_service(args) -> int:
    """Run the standalone verify-service process: one device-owning
    scheduler serving a whole committee over UDS IPC
    (parallel/verify_service.py, ROADMAP verify-as-a-service)."""
    from .libs.log import default_logger
    from .parallel.verify_service import run_service

    return run_service(
        args.socket,
        max_batch=args.max_batch,
        stats_port=args.stats_port if args.stats_port >= 0 else None,
        prewarm=args.prewarm,
        logger=default_logger(),
        ready_fd=args.ready_fd if args.ready_fd >= 0 else None,
        trace=args.trace,
    )


def cmd_version(args) -> int:
    print(
        f"tendermint-tpu {TMCORE_SEM_VER} "
        f"(block protocol {BLOCK_PROTOCOL_VERSION}, "
        f"p2p protocol {P2P_PROTOCOL_VERSION})"
    )
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tendermint_tpu",
        description="TPU-native tendermint (morph fork capabilities)",
    )
    p.add_argument(
        "--home", default=libs_cli.default_home(), help="node home directory"
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("init", help="initialize config/genesis/keys")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("start", help="run the node")
    sp.add_argument("--rpc.laddr", dest="rpc_laddr", default="")
    sp.add_argument("--p2p.laddr", dest="p2p_laddr", default="")
    sp.add_argument(
        "--p2p.persistent_peers", dest="persistent_peers", default=""
    )
    sp.add_argument(
        "--consensus.switchHeight",
        dest="switch_height",
        type=int,
        default=0,
        help="sequencer-mode upgrade height (reference upgrade/upgrade.go)",
    )
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("testnet", help="generate a local testnet")
    sp.add_argument("--v", type=int, default=4)
    sp.add_argument("--output", default="./mytestnet")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_testnet)

    sp = sub.add_parser("rollback", help="roll back one height")
    sp.add_argument("--hard", action="store_true")
    sp.set_defaults(fn=cmd_rollback)

    sp = sub.add_parser("unsafe-reset-all", help="wipe chain data")
    sp.set_defaults(fn=cmd_reset)

    sp = sub.add_parser("gen-validator", help="generate a validator key")
    sp.set_defaults(fn=cmd_gen_validator)

    sp = sub.add_parser("gen-node-key", help="generate a node key")
    sp.set_defaults(fn=cmd_gen_node_key)

    sp = sub.add_parser("show-node-id", help="print this node's p2p id")
    sp.set_defaults(fn=cmd_show_node_id)

    sp = sub.add_parser("show-validator", help="print this node's validator")
    sp.set_defaults(fn=cmd_show_validator)

    sp = sub.add_parser("light", help="run a light-client proxy")
    sp.add_argument("chain_id")
    sp.add_argument("-p", "--primary", required=True,
                    help="primary RPC addr")
    sp.add_argument("-w", "--witnesses", default="",
                    help="comma-separated witness RPC addrs")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument("--trusted-height", type=int, default=0)
    sp.add_argument("--trusted-hash", default="")
    sp.add_argument("--trust-period", type=float, default=168 * 3600.0,
                    help="seconds")
    sp.add_argument("--sequential", action="store_true")
    sp.set_defaults(fn=cmd_light)

    sp = sub.add_parser("replay", help="print the consensus WAL")
    sp.set_defaults(fn=cmd_replay, console=False)

    sp = sub.add_parser(
        "replay-console", help="step through the consensus WAL"
    )
    sp.set_defaults(fn=cmd_replay, console=True)

    from .abci.cli import register as register_abci_cli

    register_abci_cli(sub)

    sp = sub.add_parser("rewind", help="rewind state+blocks to a height")
    sp.add_argument("--height", type=int, required=True)
    sp.set_defaults(fn=cmd_rewind)

    sp = sub.add_parser("compact", help="compact the sqlite stores")
    sp.set_defaults(fn=cmd_compact)

    sp = sub.add_parser(
        "reindex-event", help="rebuild the tx/block event index"
    )
    sp.add_argument("--start-height", type=int, default=0)
    sp.add_argument("--end-height", type=int, default=0)
    sp.set_defaults(fn=cmd_reindex_event)

    sp = sub.add_parser("debug", help="debug utilities")
    dsub = sp.add_subparsers(dest="debug_cmd", required=True)
    dp = dsub.add_parser("dump", help="snapshot node state to a dir")
    dp.add_argument("--rpc-laddr", default="tcp://127.0.0.1:26657")
    dp.add_argument("--pprof-laddr", default="")
    dp.add_argument("--output", default="./debug-dump")
    dp.set_defaults(fn=cmd_debug_dump)

    sp = sub.add_parser("probe-upnp", help="probe for a UPnP gateway")
    sp.set_defaults(fn=cmd_probe_upnp)

    sp = sub.add_parser(
        "probe-tpu", help="show devices + the [tpu] config mesh"
    )
    sp.set_defaults(fn=cmd_probe_tpu)

    sp = sub.add_parser(
        "verify-service",
        help="run a standalone verify-service process (the device-"
        "owning scheduler N nodes submit to over a unix socket; point "
        "nodes at it with [scheduler] remote_socket)",
    )
    sp.add_argument(
        "--socket", required=True, help="unix-domain socket path to serve"
    )
    sp.add_argument("--max-batch", type=int, default=16384)
    sp.add_argument(
        "--stats-port",
        type=int,
        default=-1,
        help="TCP port for GET /metrics + /dump_dispatch_ledger "
        "(0 = ephemeral, -1 = disabled)",
    )
    sp.add_argument(
        "--prewarm",
        action="store_true",
        help="AOT-load the bucket-ladder verify programs before serving",
    )
    sp.add_argument(
        "--ready-fd",
        type=int,
        default=-1,
        help="fd that gets one JSON readiness line once the socket "
        "accepts (harness use)",
    )
    sp.add_argument(
        "--trace",
        action="store_true",
        help="record queue/dispatch/device sub-spans for traced client "
        "submissions into a service-side flight ring (served at "
        "GET /dump_traces on --stats-port; TM_TPU_TRACE=1 also enables)",
    )
    sp.set_defaults(fn=cmd_verify_service)

    sp = sub.add_parser("version", help="print version")
    sp.set_defaults(fn=cmd_version)

    args = p.parse_args(argv)
    # one compile-cache policy for every subcommand that compiles
    # (start, verify-service, light, probe-tpu): set before the first
    from .libs.jax_cache import configure_compile_cache

    configure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
