"""Node — dependency-injection assembly of every service.

Reference: node/node.go:775-1038 (NewNode wiring order: DBs → state →
proxyApp → eventBus+indexer → privval → handshake → evidence → blockExec →
blocksync → consensus → statesync → transport/switch/addrbook/PEX →
sequencer components), OnStart :1041-1109 (RPC → prometheus → transport →
switch → dial peers → statesync), OnStop :1112, sequencer switch :1612.
"""

from __future__ import annotations

import asyncio
import os
from typing import Optional

from ..abci.client import LocalClient
from ..blocksync.reactor import BlocksyncReactor
from ..config import Config
from ..consensus.reactor import ConsensusReactor
from ..consensus.state_machine import ConsensusState
from ..consensus.wal import WAL
from ..crypto import secp256k1
from ..evidence import EvidencePool, EvidenceReactor
from ..libs.log import Logger, default_logger
from ..libs.service import Service
from ..p2p.key import NodeKey
from ..p2p.node_info import NodeInfo
from ..p2p.pex import AddrBook, PEXReactor
from ..p2p.switch import Switch
from ..p2p.transport import MultiplexTransport, NetAddress
from ..privval.file_pv import FilePV
from ..proxy.multi_app_conn import AppConns
from ..sequencer import (
    BlockBroadcastReactor,
    LocalSigner,
    StateV2,
    StaticSequencerVerifier,
)
from ..state.execution import BlockExecutor
from ..state.state import State
from ..state.store import StateStore
from ..statesync import StateSyncReactor
from ..store.block_store import BlockStore
from ..store.kv import MemKV, SqliteKV
from ..types.event_bus import EventBus
from ..types.genesis import GenesisDoc


def init_files(config: Config, logger: Optional[Logger] = None) -> GenesisDoc:
    """`tendermint init` (reference cmd/tendermint/commands/init.go):
    generate node key, privval files, and a single-validator genesis."""
    logger = logger or default_logger()
    config.ensure_dirs()
    nk = NodeKey.load_or_generate(config.node_key_file)
    pv = FilePV.load_or_generate(
        config.priv_validator_key_file, config.priv_validator_state_file
    )
    from ..crypto import bls_signatures as bls

    bls.load_or_gen_bls_key(config.bls_key_file)
    gen_path = config.genesis_file
    if os.path.exists(gen_path):
        doc = GenesisDoc.from_file(gen_path)
        logger.info("found existing genesis", path=gen_path)
    else:
        from ..types.genesis import GenesisValidator
        import time

        doc = GenesisDoc(
            chain_id=config.base.chain_id or "test-chain-%06x" % (
                int.from_bytes(os.urandom(3), "big")
            ),
            genesis_time_ns=time.time_ns(),
            validators=[
                GenesisValidator(
                    "ed25519", pv.get_pub_key().data, 10
                )
            ],
        )
        doc.validate_and_complete()
        doc.save_as(gen_path)
        logger.info("generated genesis", path=gen_path, chain_id=doc.chain_id)
    logger.info("node id", id=nk.id)
    return doc


class _ConnProxy:
    """Delegates the Application call surface to one named AppConns
    connection (available after proxy_app.start()); the delegated
    methods are async client methods — every consumer in the tree
    (executor, handshaker, syncer, statesync reactor, rpc core) awaits
    coroutine results."""

    def __init__(self, conns, name: str):
        self._conns = conns
        self._name = name

    def __getattr__(self, item):
        conn = getattr(self._conns, self._name)
        if conn is None:
            raise RuntimeError(
                f"proxy app connection {self._name!r} not started"
            )
        return getattr(conn, item)


class Node(Service):
    """One running node over a local ABCI app + (mock or real) L2 node."""

    def __init__(
        self,
        config: Config,
        app=None,
        l2_node=None,
        genesis: Optional[GenesisDoc] = None,
        logger: Optional[Logger] = None,
    ):
        logger = logger or default_logger()
        super().__init__("node", logger)
        self.config = config
        config.ensure_dirs()

        # --- identity / keys (node.go:100-129) ---
        self.node_key = NodeKey.load_or_generate(config.node_key_file)
        self.priv_validator = FilePV.load_or_generate(
            config.priv_validator_key_file, config.priv_validator_state_file
        )

        # --- BLS dual-signing key (node.go:106-113: the reference loads
        # blssignatures.KeyFile at startup and refuses to run without it).
        # Loaded (or generated, like the other key files) so the assembled
        # node actually dual-signs batch-point precommits.
        from ..crypto import aead, bls_native, secp_native
        from ..crypto import bls_signatures as bls

        # build/load the native crypto NOW, not on the event loop
        # mid-consensus (the first call may invoke g++ for seconds);
        # aead backs every p2p secret-connection frame
        bls_native.native_lib()
        secp_native.native_lib()
        aead._native_lib()
        # export the fused device-SHA-512 knob before the first
        # default_verifier() constructs the process-wide verifier
        if config.base.device_challenge_min > 0:
            os.environ.setdefault(
                "TM_TPU_DEVICE_CHALLENGE_MIN",
                str(config.base.device_challenge_min),
            )
        # multi-host runtime: join the jax distributed service so
        # jax.devices() is global and the dcn mesh axis can span hosts
        # (the XLA-collective analog of the reference's cross-host NCCL/
        # MPI plane; SURVEY §2.3 / §5 distributed comm backend)
        if config.tpu.coordinator_address:
            try:
                import jax as _jax2

                _jax2.distributed.initialize(
                    coordinator_address=config.tpu.coordinator_address,
                    num_processes=config.tpu.num_processes,
                    process_id=config.tpu.process_id,
                )
            except Exception as e:
                # a single-host deployment with a stale coordinator line
                # must still boot — the mesh then covers local devices
                self.logger.error(
                    f"jax.distributed.initialize failed: {e}; "
                    "continuing single-process"
                )
        # one process for each chip: with [scheduler] remote_socket set
        # the verify service owns the device, so THIS process's local
        # fallback verifier (and its warm thread) is a CPU verifier —
        # pinned before the first JAX call opens the default backend
        from ..libs.device import device_info, pin_cpu

        if config.scheduler.enable and config.scheduler.remote_socket:
            pin_cpu()
        self.logger.info("node device", **device_info())
        # [tpu] mesh axes -> env, so the process-wide default_verifier()
        # (constructed lazily by whichever reactor first verifies) builds
        # the sharded verifier per config (parallel/mesh.py).
        # [scheduler] mesh_enable is the one-knob version: shard the
        # verify plane over ALL local devices (ici=0); explicit [tpu]
        # axes win when both are set (setdefault ordering below).
        if config.tpu.ici_parallelism != 1 or config.tpu.dcn_parallelism != 1:
            os.environ.setdefault(
                "TM_TPU_ICI_PARALLELISM", str(config.tpu.ici_parallelism)
            )
            os.environ.setdefault(
                "TM_TPU_DCN_PARALLELISM", str(config.tpu.dcn_parallelism)
            )
            if config.tpu.mesh_backend:
                os.environ.setdefault(
                    "TM_TPU_MESH_BACKEND", config.tpu.mesh_backend
                )
        if config.scheduler.mesh_enable:
            os.environ.setdefault("TM_TPU_ICI_PARALLELISM", "0")
            if config.tpu.mesh_backend:
                os.environ.setdefault(
                    "TM_TPU_MESH_BACKEND", config.tpu.mesh_backend
                )
        # mesh_min_rows governs the sharded/replicated split of every
        # mesh verifier in the process (latency floor for tiny rounds)
        os.environ.setdefault(
            "TM_TPU_MESH_MIN_ROWS", str(config.scheduler.mesh_min_rows)
        )
        self.bls_key = bls.load_or_gen_bls_key(config.bls_key_file)
        self.bls_signer = bls.signer_for(
            bls.priv_key_from_bytes(self.bls_key.priv_key)
        )

        # --- genesis + state (node.go:797-805) ---
        self.genesis = genesis or GenesisDoc.from_file(config.genesis_file)

        def make_kv(name: str):
            if config.base.db_backend == "memory":
                return MemKV()
            return SqliteKV(os.path.join(config.db_dir, f"{name}.db"))

        # --- observability handles (node.go:1062; needed by the stores
        # below, so built before them) ---
        from ..libs.metrics import ConsensusMetrics, default_registry
        from .. import obs

        self.metrics_registry = default_registry()
        # flight recorder: installed as the process default so every seam
        # without an explicit handle (batch verifier, p2p conns, chaos)
        # lands in the SAME timeline as the consensus step spans
        self.tracer = obs.set_default_tracer(
            obs.Tracer(
                enabled=(
                    config.instrumentation.trace
                    or os.environ.get("TM_TPU_TRACE") == "1"
                ),
                ring_size=config.instrumentation.trace_ring_size,
            )
        )
        consensus_metrics = ConsensusMetrics(self.metrics_registry)
        # on-demand profiling hooks (obs/profiler.py): armed over the
        # profile_start/profile_stop RPC routes, artifacts land in
        # data/profiles — a live TPU session is minable without a
        # redeploy. Construction is free; nothing runs until armed.
        self.profiler = obs.ProfileCapture(
            config.path("data/profiles"), logger=self.logger
        )

        # --- live health plane (obs/health.py): streaming detectors
        # over the seams below; built BEFORE consensus so the arrival-
        # lag/commit push feeds wire straight in. Pull seams bind after
        # their owners exist; the sampling loop starts in on_start.
        self.health_monitor = None
        if config.health.enable:
            from ..libs.metrics import (
                HealthMetrics,
                ProcessMetrics,
                default_metrics,
            )

            # the static round-0 schedule is the stall ceiling's base:
            # adaptive pacing only ever tightens BELOW it
            static_round0 = (
                config.consensus.timeout_propose
                + config.consensus.timeout_prevote
                + config.consensus.timeout_precommit
                + config.consensus.timeout_commit
            )
            self.health_monitor = obs.HealthMonitor.from_config(
                config.health,
                stall_ceiling_s=config.health.stall_factor * static_round0,
                tracer=self.tracer,
                metrics=default_metrics(HealthMetrics),
                process_metrics=default_metrics(ProcessMetrics),
                logger=self.logger,
            )
            self.health_monitor.bind_wal(
                consensus_metrics.wal_fsync_seconds
            )
            # wall-clock conservation: the dark_time detector audits
            # the flight ring per committed height (no-op while the
            # tracer is disabled)
            self.health_monitor.bind_tracer(self.tracer)

        self.state_store = StateStore(make_kv("state"))
        if config.commit_pipeline.enable:
            # write-behind persistence: saves ride a worker thread
            from ..store.block_store import WriteBehindBlockStore

            self.block_store = WriteBehindBlockStore(
                make_kv("blockstore"),
                max_inflight=config.commit_pipeline.max_inflight,
                metrics=consensus_metrics,
                tracer=self.tracer,
            )
        else:
            self.block_store = BlockStore(make_kv("blockstore"))
        state = self.state_store.load()
        if state is None:
            state = State.from_genesis(self.genesis)
            self.state_store.bootstrap(state)

        # --- app + L2 (PROCESS BOUNDARY in production; in-proc here) ---
        if app is None:
            from ..abci.kvstore import KVStoreApplication

            app = KVStoreApplication()
        if l2_node is None:
            from ..l2node.mock import MockL2Node

            l2_node = MockL2Node()
        self.l2_node = l2_node
        if config.base.proxy_app:
            # external app process (reference node.go proxy.DefaultClient
            # Creator): socket or grpc per config.base.abci. ALL app
            # traffic rides the three named proxy connections — the
            # executor/handshake on `consensus`, rpc queries on `query`,
            # statesync serving on `snapshot` (reference
            # proxy/multi_app_conn.go:24-28).
            addr = config.base.proxy_app.removeprefix("tcp://")
            host, _, port_s = addr.rpartition(":")
            if not host or not port_s.isdigit():
                raise ValueError(
                    f"proxy_app must be [tcp://]host:port, got "
                    f"{config.base.proxy_app!r}"
                )
            if config.base.abci == "grpc":
                from ..abci.grpc_transport import grpc_client_creator

                creator = grpc_client_creator(host, int(port_s))
            else:
                from ..proxy.multi_app_conn import remote_client_creator

                creator = remote_client_creator(host, int(port_s))
            self.proxy_app = AppConns(creator)
            self.app = _ConnProxy(self.proxy_app, "query")
            self.app_client = _ConnProxy(self.proxy_app, "consensus")
            self._snapshot_app = _ConnProxy(self.proxy_app, "snapshot")
        else:
            from ..proxy.multi_app_conn import local_client_creator

            self.app = app
            self.app_client = LocalClient(app)
            self._snapshot_app = app
            self.proxy_app = AppConns(local_client_creator(app))

        # --- event bus + indexer (node.go:287-347) ---
        self.event_bus = EventBus()
        self.indexer_service = None
        if config.tx_index.indexer == "kv":
            try:
                from ..state.txindex import IndexerService, KVIndexer

                self.indexer = KVIndexer(make_kv("txindex"))
                self.indexer_service = IndexerService(
                    self.indexer, self.event_bus
                )
            except ImportError:
                self.indexer = None

        # --- evidence (node.go:403) ---
        self.evidence_pool = EvidencePool(
            make_kv("evidence"), self.state_store, self.block_store
        )

        # --- light-client serving plane (tendermint_tpu/lightserve) ---
        # cached light_block/signed_header/validator_set proof routes
        # over the node's own stores + the shared-round ServeVerifier;
        # rpc/core.py exposes the routes iff this exists
        self.lightserve = None
        if config.lightserve.enable:
            from ..lightserve import LightServePlane

            self.lightserve = LightServePlane(
                self.block_store,
                self.state_store,
                self.genesis.chain_id,
                cache_size=config.lightserve.cache_size,
                dedup_window_ns=int(config.lightserve.dedup_window * 1e9),
                logger=self.logger,
            )
            if self.health_monitor is not None:
                self.health_monitor.bind_lightserve(
                    self.lightserve.cache.metrics
                )

        # --- executor (node.go:883) ---
        self.block_executor = BlockExecutor(
            self.state_store,
            self.block_store,
            self.app_client,
            l2_node,
            event_bus=self.event_bus,
            evidence_pool=self.evidence_pool,
            logger=self.logger,
            qc_enabled=config.consensus.quorum_certificates,
        )

        # --- sequencer components (node.go:1007-1032) ---
        seq_signer = None
        if config.sequencer.sequencer_key_file:
            with open(config.path(config.sequencer.sequencer_key_file)) as f:
                key = secp256k1.PrivKey.from_bytes(
                    bytes.fromhex(f.read().strip())
                )
            seq_signer = LocalSigner(key)
        allowed = [
            bytes.fromhex(a.strip().removeprefix("0x"))
            for a in config.sequencer.sequencer_addresses.split(",")
            if a.strip()
        ]
        if seq_signer and not allowed:
            allowed = [seq_signer.address()]
        self.sequencer_verifier = StaticSequencerVerifier(allowed)
        self.state_v2 = StateV2(
            l2_node,
            block_interval=config.sequencer.block_interval,
            signer=seq_signer,
            verifier=self.sequencer_verifier,
            logger=self.logger,
        )
        self.sequencer_reactor = BlockBroadcastReactor(
            self.state_v2, self.sequencer_verifier, wait_sync=True,
            logger=self.logger,
            apply_interval=config.sequencer.apply_interval,
            sync_interval=config.sequencer.sync_interval,
            catchup_window=config.sequencer.catchup_window,
            tracer=self.tracer,
        )
        if self.health_monitor is not None:
            self.health_monitor.bind_sequencer(
                self.sequencer_reactor.metrics.apply_latency
            )

        # --- consensus (node.go:460-501) ---
        # unified verification dispatch scheduler: every subsystem's
        # device-verify path funnels through parallel/scheduler's
        # default_dispatch(), so installing one here captures the vote
        # batcher, blocksync replay, light bisection and evidence checks.
        # The bucket-ladder override must land BEFORE the first
        # default_verifier() dispatch (the registry owns pad sizes).
        self.verify_scheduler = None
        # the ladder governs pad buckets for EVERY verifier through the
        # process shape registry, scheduler routing or not — apply it
        # outside the enable gate
        ladder = config.scheduler.ladder()
        if ladder is not None:
            from ..crypto.shape_registry import configure_default

            configure_default(ladder)
        if config.scheduler.enable and config.scheduler.remote_socket:
            # split-brain deployment ([scheduler] remote_socket): a
            # standalone verify-service process owns the device plane;
            # this node is a CLIENT whose submissions coalesce with the
            # rest of the rack's (parallel/verify_service.py). The
            # device-side fill/saturation seams live on the SERVICE
            # (its own /metrics + dump_dispatch_ledger); this node's
            # health plane watches the IPC round trip + degrades
            # instead.
            from ..parallel.scheduler import set_default_scheduler
            from ..parallel.verify_service import RemoteVerifyScheduler

            self.verify_scheduler = set_default_scheduler(
                RemoteVerifyScheduler(
                    config.path(config.scheduler.remote_socket),
                    logger=self.logger,
                    tracer=self.tracer,
                    # wire trace context names this node as the
                    # submitter in the service's sub-spans
                    origin=self.node_key.id[:16],
                )
            )
            self.logger.info(
                "verify plane: remote service client",
                socket=config.path(config.scheduler.remote_socket),
            )
            if self.health_monitor is not None:
                self.health_monitor.bind_remote_scheduler(
                    self.verify_scheduler
                )
        elif config.scheduler.enable:
            from ..parallel.scheduler import (
                VerifyScheduler,
                set_default_scheduler,
            )

            self.verify_scheduler = set_default_scheduler(
                VerifyScheduler(
                    max_batch=config.scheduler.max_batch,
                    logger=self.logger,
                )
            )
            if self.health_monitor is not None:
                self.health_monitor.bind_scheduler(
                    self.verify_scheduler.metrics
                )
                # fill-efficiency floor reads the device-cost ledger
                self.health_monitor.bind_ledger(
                    self.verify_scheduler.ledger
                )
        # commit pipeline (consensus/commit_pipeline.py): group-commit
        # WAL + write-behind block store + background apply. All three
        # are wired together — replay semantics are designed for the
        # trio, and half a pipeline buys latency without the overlap.
        self.commit_pipeline = None
        if config.commit_pipeline.enable:
            from ..consensus.commit_pipeline import CommitPipeline
            from ..consensus.wal import GroupCommitWAL

            wal = GroupCommitWAL(
                config.wal_file,
                metrics=consensus_metrics,
                tracer=self.tracer,
                flush_interval=config.commit_pipeline.flush_interval,
            )
            self.commit_pipeline = CommitPipeline(
                metrics=consensus_metrics,
                tracer=self.tracer,
                logger=self.logger,
            )
        else:
            wal = WAL(
                config.wal_file, metrics=consensus_metrics,
                tracer=self.tracer,
            )
        self.wal = wal
        # adaptive pacing (consensus/pacing.py): the node owns the
        # controller so the debug/RPC surface can snapshot it; the
        # state machine would self-construct an identical one from the
        # config, but explicit wiring keeps ownership visible alongside
        # the commit pipeline and scheduler
        sm_config = config.consensus.to_state_machine_config()
        self.pacing = None
        if config.consensus.adaptive_timeouts:
            from ..consensus.pacing import PacingController

            self.pacing = PacingController.from_config(
                sm_config, metrics=consensus_metrics, tracer=self.tracer
            )
            # learned tails live next to the WAL: same durability
            # domain, wiped by the same data reset
            self.pacing.persist_path = config.wal_file + ".pacing.json"
            self.logger.info(
                "adaptive consensus pacing enabled",
                tail_q=config.consensus.adaptive_tail_quantile,
                min_factor=config.consensus.adaptive_min_factor,
            )
        self.consensus = ConsensusState(
            sm_config,
            state,
            self.block_executor,
            self.block_store,
            l2_node,
            priv_validator=self.priv_validator,
            bls_signer=self.bls_signer,
            event_bus=self.event_bus,
            wal=wal,
            upgrade_height=config.consensus.switch_height,
            on_upgrade=self._switch_to_sequencer_mode,
            evidence_pool=self.evidence_pool,
            metrics=consensus_metrics,
            tracer=self.tracer,
            logger=self.logger,
            commit_pipeline=self.commit_pipeline,
            pacing=self.pacing,
            health=self.health_monitor,
        )
        self.consensus_reactor = ConsensusReactor(
            self.consensus,
            logger=self.logger,
            vote_batch=config.consensus.vote_batch_gossip,
            vote_batch_max=config.consensus.vote_batch_max,
            digest_interval=config.consensus.digest_interval,
            vote_forward_fanout=config.consensus.vote_forward_fanout,
        )

        # --- blocksync (node.go:435-458) ---
        self.blocksync_reactor = BlocksyncReactor(
            state,
            self.block_executor,
            self.block_store,
            l2_node,
            on_caught_up=self._switch_to_consensus,
            upgrade_height=config.consensus.switch_height,
            on_upgrade=self._switch_to_sequencer_mode,
            logger=self.logger,
            active=False,  # started explicitly when peers are configured
            qc_enabled=config.consensus.quorum_certificates,
        )

        # --- statesync reactor (node.go:916) ---
        self.statesync_reactor = StateSyncReactor(
            self._snapshot_app, syncer=None, logger=self.logger
        )

        # --- p2p (node.go:929-967) ---
        transport = None
        sw = None

        def node_info() -> NodeInfo:
            return NodeInfo(
                node_id=self.node_key.id,
                listen_addr=self._listen_addr(),
                network=self.genesis.chain_id,
                channels=sw.channels() if sw else b"",
                moniker=config.base.moniker,
            )

        transport = MultiplexTransport(self.node_key, node_info)
        sw = Switch(
            transport,
            logger=self.logger,
            send_rate=config.p2p.send_rate,
            recv_rate=config.p2p.recv_rate,
            ping_interval=config.p2p.ping_interval,
        )
        self.transport = transport
        self.switch = sw
        sw.add_reactor("consensus", self.consensus_reactor)
        sw.add_reactor("blocksync", self.blocksync_reactor)
        sw.add_reactor("evidence", EvidenceReactor(self.evidence_pool, self.logger))
        sw.add_reactor("statesync", self.statesync_reactor)
        sw.add_reactor("sequencer", self.sequencer_reactor)
        if config.p2p.pex:
            self.addr_book = AddrBook(
                config.addr_book_file, our_id=self.node_key.id
            )
            sw.add_reactor("pex", PEXReactor(self.addr_book))
        if self.health_monitor is not None:
            self.health_monitor.bind_switch(sw)

        # --- rpc + metrics ---
        self.rpc_server = None
        self.metrics_server = None
        self.debug_server = None

    # --- helpers ------------------------------------------------------------

    def _listen_addr(self) -> str:
        host, port = self._parse_laddr(self.config.p2p.laddr)
        lp = getattr(self.transport, "listen_port", None) or port
        return f"{host}:{lp}"

    @staticmethod
    def _parse_laddr(laddr: str) -> tuple[str, int]:
        s = laddr.removeprefix("tcp://")
        host, _, port = s.rpartition(":")
        return host or "127.0.0.1", int(port or 0)

    # --- mode switches (node.go:1612-1632) -----------------------------------

    async def _switch_to_sequencer_mode(self, state) -> None:
        self.logger.info(
            "switching to sequencer mode", height=state.last_block_height
        )
        if hasattr(self.l2_node, "seed_v2_height"):
            # the mock L2 needs its v2 chain aligned to the BFT height;
            # a real geth already is
            self.l2_node.seed_v2_height(state.last_block_height)
        await self.sequencer_reactor.start_sequencer_routines()

    async def _switch_to_consensus(self, state) -> None:
        self.logger.info(
            "blocksync caught up; starting consensus",
            height=state.last_block_height,
        )
        self.consensus.state = state
        try:
            # skip WAL catchup ONLY when blocksync actually advanced state
            # past the WAL's last end-height barrier (reference
            # SwitchToConsensus(state, blocksSynced > 0)); a restart that
            # synced nothing must still replay in-flight WAL messages —
            # that replay restores the POL lock that prevents double-signs
            synced = self.blocksync_reactor.blocks_applied > 0
            await self.consensus.start(skip_wal_catchup=synced)
        except Exception as e:
            # the switch-over runs inside blocksync's pool task — an
            # exception here must not die silently (that failure mode
            # presented as a live-looking node that never participates)
            self.logger.error(
                "consensus start failed after blocksync", err=repr(e)
            )
            raise

    # --- lifecycle (node.go:1041-1112) ---------------------------------------

    def _fail(self, exc: BaseException) -> None:
        if not self.failed.done():
            self.failed.set_exception(exc)

    async def on_start(self) -> None:
        # resolves (with the exception) when a background part of
        # startup fails after on_start returned; `start` waits on it
        # beside the stop signal
        self.failed = asyncio.get_running_loop().create_future()
        # (re)arm table warms for this process lifetime (the default
        # verifier — and its shutdown flag — is shared process-wide)
        ev = getattr(self.consensus.verifier, "shutdown_event", None)
        if ev is not None:
            ev.clear()
        # verification dispatch service first: the moment any reactor
        # verifies, its classed dispatch should coalesce (until started,
        # default_dispatch degrades to direct dispatch — still correct)
        if self.verify_scheduler is not None:
            await self.verify_scheduler.start()
        if self.health_monitor is not None:
            await self.health_monitor.start()
        await self.proxy_app.start()
        if self.indexer_service is not None:
            await self.indexer_service.start()
        # handshake/replay: sync app + L2 with the block store
        from ..consensus.replay import Handshaker

        hs = Handshaker(
            self.state_store,
            self.block_store,
            self.genesis,
            self.block_executor,
            logger=self.logger,
        )
        state = await hs.handshake(self.consensus.state)
        self.consensus.state = state
        self.blocksync_reactor.state = state

        # rpc
        if self.config.rpc.laddr:
            from ..rpc.server import RPCServer

            host, port = self._parse_laddr(self.config.rpc.laddr)
            self.rpc_server = RPCServer(self, host, port)
            await self.rpc_server.start()
        # pprof/debug (reference node.go:969-975)
        if self.config.rpc.pprof_laddr:
            from .debug import DebugServer

            host, port = self._parse_laddr(self.config.rpc.pprof_laddr)
            self.debug_server = DebugServer(
                host or "127.0.0.1",
                port,
                trace_dir=os.path.join(self.config.root_dir, "traces"),
            )
            await self.debug_server.start()
        # metrics
        if self.config.instrumentation.prometheus:
            from ..libs.metrics import MetricsServer

            host, port = self._parse_laddr(
                self.config.instrumentation.prometheus_listen_addr
            )
            self.metrics_server = MetricsServer(
                self.metrics_registry, host or "0.0.0.0", port
            )
            await self.metrics_server.start()

        # pre-build the validator table cache off the critical path (the
        # steady-state vote path then never pays decompression/table cost)
        vals = self.consensus.state.validators
        if (
            vals is not None
            and hasattr(self.consensus.verifier, "warm")
            and not os.environ.get("TM_TPU_SKIP_WARM")
        ):
            pubs = [v.pub_key.data for v in vals.validators]
            ktypes = [
                getattr(v.pub_key, "type_name", "ed25519")
                for v in vals.validators
            ]
            # NON-daemon thread with an abort flag: a daemon thread
            # force-terminated mid-XLA-compile at interpreter exit
            # crashes the process (SIGSEGV/SIGABRT — found r4 driving a
            # short-lived node). on_stop sets the flag and joins; the
            # interpreter then waits out at most one chunk compile. The
            # verifier-level shutdown_event also covers the bulk warms
            # blocksync/light launch via the executor.
            import threading as _threading

            self._warm_abort = self.consensus.verifier.shutdown_event

            loop = asyncio.get_running_loop()

            def _warm_startup(
                verifier=self.consensus.verifier,
                abort=self._warm_abort,
            ):
                from ..libs.jax_cache import compile_log

                clog = compile_log()
                verifier.warm(pubs, key_types=ktypes, abort=abort)
                # what the warm cost: few seconds with cache_hits > 0 is
                # a restart on a machine that kept its compiles
                # (libs/jax_cache.py)
                self.logger.info(
                    "validator-table warm complete",
                    keys=len(pubs),
                    **clog.totals(),
                )
                # ahead-of-time bucket-ladder prewarm (the §10 fix for
                # per-shape program loads landing mid-height): compile/
                # load every verify program the ladder dispatches, then
                # persist the manifest so operators can see what a
                # restart pays (tools/prewarm.py builds/verifies the
                # same artifact standalone)
                if not self.config.scheduler.prewarm or abort.is_set():
                    return
                entries = verifier.prewarm_buckets(abort=abort)
                from ..crypto.shape_registry import (
                    default_shape_registry,
                )
                import json as _json
                import time as _time

                manifest = {
                    "created_unix": int(_time.time()),
                    "ladder": list(default_shape_registry().ladder),
                    # the mesh topology the ladder was loaded for:
                    # tools/prewarm.py --verify fails loudly when a
                    # restarted node's live mesh disagrees (a wrong
                    # topology would recompile on the hot path)
                    "device_count": getattr(verifier, "mesh_devices", 1),
                    "mesh_min_rows": getattr(
                        verifier, "_mesh_min_rows", 0
                    ),
                    "mesh_backend": os.environ.get(
                        "TM_TPU_MESH_BACKEND", ""
                    ),
                    "entries": entries,
                }
                path = self.config.path(
                    self.config.scheduler.prewarm_manifest
                )
                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path, "w") as f:
                    _json.dump(manifest, f, indent=1)
                self.logger.info(
                    "verify-program prewarm complete",
                    programs=len(entries),
                    seconds=round(sum(e["seconds"] for e in entries), 1),
                    manifest=path,
                )

            def _warm_or_fail():
                # a device that cannot build the validator's table or
                # load the ladder is a failed start, not a log line:
                # the node stops and `start` exits non-zero
                try:
                    _warm_startup()
                except Exception as e:
                    self.logger.error(
                        "verifier warm failed; stopping node", err=repr(e)
                    )
                    loop.call_soon_threadsafe(self._fail, e)

            self._warm_thread = _threading.Thread(
                target=_warm_or_fail,
                name="verifier-warm",
            )
            self._warm_thread.start()

        try:
            # p2p
            host, port = self._parse_laddr(self.config.p2p.laddr)
            await self.transport.listen(host, port)
            if self.config.p2p.upnp:
                # best-effort NAT mapping of the real listen port
                # (reference node.go getUPNPExternalAddress); failure
                # leaves the node listening unmapped
                from ..p2p import upnp as _upnp

                self._upnp_gateway = await _upnp.map_listen_port(
                    self.transport.listen_port, logger=self.logger
                )
            await self.switch.start()
            peers = [
                NetAddress.parse(p)
                for p in self.config.p2p.peer_list(
                    self.config.p2p.persistent_peers
                )
            ]
            if peers:
                self.switch.dial_peers_async(peers, persistent=True)

            # consensus (blocksync/statesync first when configured)
            if self.config.statesync.enable:
                self.spawn(self._run_statesync())
            elif peers and self.config.blocksync.enable:
                self.blocksync_reactor.start_sync()
            else:
                await self.consensus.start()
        except BaseException:
            # failed startup (busy p2p port, bad peer string, ...):
            # Service.start will not call on_stop, and the non-daemon
            # warm thread would otherwise hold the interpreter open for
            # the whole multi-chunk build at exit
            ev = getattr(self.consensus.verifier, "shutdown_event", None)
            if ev is not None:
                ev.set()
            if self.verify_scheduler is not None:
                await self.verify_scheduler.stop()
            if self.health_monitor is not None:
                await self.health_monitor.stop()
            raise

    async def _run_statesync(self) -> None:
        """Bootstrap from a snapshot, then hand off to consensus
        (node.go:1088-1106 startStateSync)."""
        from ..statesync.syncer import Syncer
        from ..statesync.stateprovider import LightClientStateProvider
        from ..light.client import LightClient, TrustOptions
        from ..light.store import LightStore
        from ..rpc.light_provider import RPCProvider

        servers = [
            s.strip()
            for s in self.config.statesync.rpc_servers.split(",")
            if s.strip()
        ]
        providers = [RPCProvider(self.genesis.chain_id, s) for s in servers]
        lc = LightClient(
            self.genesis.chain_id,
            TrustOptions(
                int(self.config.statesync.trust_period * 1e9),
                self.config.statesync.trust_height,
                bytes.fromhex(self.config.statesync.trust_hash),
            ),
            providers[0],
            providers[1:],
            LightStore(MemKV()),
            logger=self.logger,
        )
        provider = LightClientStateProvider(
            lc, consensus_params=self.consensus.state.consensus_params
        )
        syncer = Syncer(
            self._snapshot_app,
            provider,
            self.statesync_reactor.request_chunk,
            logger=self.logger,
        )
        self.statesync_reactor.syncer = syncer
        state, commit = await syncer.sync_any(
            discovery_time=self.config.statesync.discovery_time
        )
        self.statesync_reactor.syncer = None
        self.state_store.bootstrap(state)
        self.block_store.save_seen_commit(state.last_block_height, commit)
        self.consensus.state = state
        # statesync jumped state far past any WAL content (same skipWAL
        # rationale as the blocksync switch-over)
        await self.consensus.start(skip_wal_catchup=True)

    async def on_stop(self) -> None:
        # stop ALL in-flight table warms (the startup thread AND the
        # bulk warms blocksync/light run in the executor) — see
        # BatchVerifier.shutdown_event
        ev = getattr(self.consensus.verifier, "shutdown_event", None)
        if ev is not None:
            ev.set()
        t = getattr(self, "_warm_thread", None)
        if t is not None and t.is_alive():
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, t.join, 120.0)
        if self.consensus.is_running:
            await self.consensus.stop()
        if self.sequencer_reactor.sequencer_started:
            await self.sequencer_reactor.on_stop()
        await self.switch.stop()
        # pipeline teardown AFTER the reactors: a still-active blocksync
        # may save/apply right up to switch.stop — only once nothing can
        # write do we drain the write-behind save queue and stop the WAL
        # flush thread
        self.block_store.stop()
        # unconditional: the plain WAL's close is flush+fd-close; the
        # group WAL's additionally drains and joins its flush thread
        self.wal.close()
        # after the reactors: queued verify work drains (futures resolve),
        # then later submissions degrade to direct dispatch
        if self.verify_scheduler is not None:
            await self.verify_scheduler.stop()
        if self.health_monitor is not None:
            await self.health_monitor.stop()
        # an armed profile session must not outlive the node: stop it so
        # the loop-profile artifact lands and the sampler thread exits
        if getattr(self, "profiler", None) is not None and self.profiler.active:
            try:
                self.profiler.stop()
            except Exception as e:
                self.logger.error("profile stop at shutdown failed",
                                  err=repr(e))
        if self.rpc_server is not None:
            await self.rpc_server.stop()
        if self.metrics_server is not None:
            await self.metrics_server.stop()
        if self.debug_server is not None:
            await self.debug_server.stop()
        if self.indexer_service is not None:
            await self.indexer_service.stop()
        if getattr(self, "_upnp_gateway", None) is not None:
            from ..p2p import upnp as _upnp

            await _upnp.unmap_listen_port(
                self._upnp_gateway, self.transport.listen_port,
                logger=self.logger,
            )
        await self.proxy_app.stop()
