"""Config tree: one root struct, per-section defaults/validation, TOML io.

Reference: config/config.go:63-110 (Config root + 8 sections with
Default*/Test* presets and ValidateBasic), config/toml.go (TOML template
render). Sections here: base (:162), rpc (:322), p2p (:534), statesync
(:703), blocksync (:793), consensus (:826), tx_index (:1026),
instrumentation (:1057), plus the morph-specific [sequencer] knobs
(upgrade height / sequencer keys — reference wires these via
--consensus.switchHeight into upgrade.SetUpgradeBlockHeight).
"""

from __future__ import annotations

import os

try:
    import tomllib
except ModuleNotFoundError:  # Python < 3.11
    import tomli as tomllib
from dataclasses import asdict, dataclass, field, fields
from typing import Optional


@dataclass
class BaseConfig:
    moniker: str = "tendermint-tpu-node"
    chain_id: str = ""  # resolved from the genesis doc
    db_backend: str = "sqlite"  # sqlite | memory
    genesis_file: str = "config/genesis.json"
    priv_validator_key_file: str = "config/priv_validator_key.json"
    priv_validator_state_file: str = "data/priv_validator_state.json"
    priv_validator_laddr: str = ""  # remote signer listen addr
    node_key_file: str = "config/node_key.json"
    bls_key_file: str = "config/bls_key.json"
    # batches >= this size compute SHA-512 vote challenges ON DEVICE
    # (fused into the verify program) instead of on the host hashing
    # thread. 0 = host hashing. Enable (e.g. 2048) on real silicon where
    # the device outruns one CPU core's hashlib (~600k sigs/s).
    device_challenge_min: int = 0
    # external ABCI app: "" = in-process kvstore; "host:port" connects
    # out via the transport named by `abci` (reference config ProxyApp)
    proxy_app: str = ""
    abci: str = "socket"  # socket | grpc (reference config ABCI)

    def validate_basic(self) -> None:
        if self.db_backend not in ("sqlite", "memory"):
            raise ValueError(f"unknown db_backend {self.db_backend!r}")
        if self.device_challenge_min < 0:
            raise ValueError("device_challenge_min must be >= 0")
        if self.abci not in ("socket", "grpc"):
            raise ValueError(f"unknown abci transport {self.abci!r}")


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    max_open_connections: int = 900
    max_subscription_clients: int = 100
    max_subscriptions_per_client: int = 5
    timeout_broadcast_tx_commit: float = 10.0
    pprof_laddr: str = ""
    # expose the unsafe routes (dial_seeds/dial_peers — reference
    # rpc/core/routes.go:46-50); off by default like the reference
    unsafe: bool = False

    def validate_basic(self) -> None:
        if self.max_open_connections < 0:
            raise ValueError("rpc.max_open_connections cannot be negative")


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    seeds: str = ""  # comma-separated id@host:port
    persistent_peers: str = ""
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    pex: bool = True
    seed_mode: bool = False
    addr_book_file: str = "config/addrbook.json"
    handshake_timeout: float = 20.0
    dial_timeout: float = 3.0
    # per-connection rate caps, bytes/s (reference config SendRate/
    # RecvRate, default 5120000); 0 disables throttling
    send_rate: int = 5120000
    recv_rate: int = 5120000
    # keepalive cadence (reference PingInterval); also the sampling rate
    # of the per-peer NTP clock-offset estimate cluster tracing rebases
    # merged timelines with (p2p/mconn.py)
    ping_interval: float = 10.0
    # NAT traversal: map the listen port on the UPnP gateway at start
    # (reference config UPNP, default false)
    upnp: bool = False

    def validate_basic(self) -> None:
        if self.max_num_inbound_peers < 0:
            raise ValueError("p2p.max_num_inbound_peers cannot be negative")
        if self.max_num_outbound_peers < 0:
            raise ValueError("p2p.max_num_outbound_peers cannot be negative")
        if self.send_rate < 0 or self.recv_rate < 0:
            raise ValueError("p2p rate caps cannot be negative")
        if self.ping_interval <= 0:
            raise ValueError("p2p.ping_interval must be > 0")

    def peer_list(self, s: str) -> list[str]:
        return [p.strip() for p in s.split(",") if p.strip()]


@dataclass
class StateSyncConfig:
    enable: bool = False
    rpc_servers: str = ""  # >=2 comma-separated light-provider endpoints
    trust_height: int = 0
    trust_hash: str = ""
    trust_period: float = 168 * 3600.0  # one week, seconds
    discovery_time: float = 15.0
    chunk_fetch_timeout: float = 10.0

    def validate_basic(self) -> None:
        if not self.enable:
            return
        if self.trust_height <= 0:
            raise ValueError("statesync.trust_height is required")
        if len(self.trust_hash) != 64:
            raise ValueError("statesync.trust_hash must be 32 hex bytes")


@dataclass
class BlockSyncConfig:
    enable: bool = True

    def validate_basic(self) -> None:
        pass


@dataclass
class ConsensusTimeoutsConfig:
    """Reference ConsensusConfig (config.go:826-877) — wall-clock knobs;
    maps onto consensus.state_machine.ConsensusConfig."""

    wal_file: str = "data/cs.wal"
    timeout_propose: float = 3.0
    timeout_propose_delta: float = 0.5
    timeout_prevote: float = 1.0
    timeout_prevote_delta: float = 0.5
    timeout_precommit: float = 1.0
    timeout_precommit_delta: float = 0.5
    timeout_commit: float = 1.0
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    # morph: the sequencer-mode switch height (upgrade/upgrade.go; flag
    # --consensus.switchHeight in the reference)
    switch_height: int = 0
    # --- adaptive pacing (consensus/pacing.py) ----------------------------
    # learn live arrival-tail distributions from the quorum-lag sensors
    # and drive round-0 timeouts between adaptive_min_factor * static
    # (floor of last resort) and the static timeout_* values (hard
    # ceiling), with AIMD back-off on fired timeouts / rounds > 0
    adaptive_timeouts: bool = False
    adaptive_tail_quantile: float = 0.99
    adaptive_safety_margin: float = 1.25
    adaptive_headroom: float = 0.002
    adaptive_min_factor: float = 0.05
    adaptive_window: int = 256
    adaptive_min_samples: int = 8
    adaptive_backoff_step: float = 0.5
    adaptive_recover_step: float = 0.1
    # --- quorum certificates (types/quorum_cert.py) -----------------------
    # one BLS aggregate per commit instead of N ed25519 sigs for every
    # downstream consumer: precommits dual-sign the canonical QC
    # message, proposers carry the aggregated certificate next to the
    # full commit, and blocksync/light/replay verify ONE pairing.
    # Requires BLS keys registered for every genesis validator
    # (bls_pub_key); legacy peers interoperate — they ignore the QC and
    # keep verifying the full commit.
    quorum_certificates: bool = False
    # --- QC-chained height pipelining (consensus/state_machine.py) --------
    # enter H+1's propose on H's quorum close instead of waiting out
    # timeout_commit, chain the QC assembly and the end-height fsync
    # behind the commit, and buffer one height of early peer traffic.
    # Non-pipelined peers keep following the chain (gossip catchup
    # serves them); a pipelined node restarted mid-boundary replays
    # without double-sign or height skip (tests/test_pipeline.py).
    pipelined_heights: bool = False
    # --- committee-scale vote gossip (consensus/reactor.py) ---------------
    # ship all votes a peer is missing per gossip tick in bounded
    # VoteBatchMessage chunks (peers negotiate via the advertised
    # VOTE_BATCH_CHANNEL; legacy peers keep the one-vote-per-tick wire).
    # Reactor knobs, not state-machine fields.
    vote_batch_gossip: bool = True
    vote_batch_max: int = 64
    # gossip-plane pacing knobs (consensus/reactor.py module constants
    # until PR 11): HasVotes possession-digest broadcast cadence, and
    # how many batch-capable peers a freshly-accepted vote chunk
    # eagerly relays to (0 disables eager relay; the paced pull plane
    # still covers dissemination).
    digest_interval: float = 0.2
    vote_forward_fanout: int = 3

    # every timeout/adaptive knob to_state_machine_config() carries over;
    # a field added to the state-machine ConsensusConfig MUST be listed
    # here or config files silently lose it (round-trip test pins this)
    _SM_FIELDS = (
        "timeout_propose",
        "timeout_propose_delta",
        "timeout_prevote",
        "timeout_prevote_delta",
        "timeout_precommit",
        "timeout_precommit_delta",
        "timeout_commit",
        "skip_timeout_commit",
        "create_empty_blocks",
        "adaptive_timeouts",
        "adaptive_tail_quantile",
        "adaptive_safety_margin",
        "adaptive_headroom",
        "adaptive_min_factor",
        "adaptive_window",
        "adaptive_min_samples",
        "adaptive_backoff_step",
        "adaptive_recover_step",
        "quorum_certificates",
        "pipelined_heights",
    )

    def validate_basic(self) -> None:
        for f in (
            "timeout_propose",
            "timeout_prevote",
            "timeout_precommit",
            "timeout_commit",
        ):
            if getattr(self, f) < 0:
                raise ValueError(f"consensus.{f} cannot be negative")
        if self.vote_batch_max < 1:
            raise ValueError("consensus.vote_batch_max must be >= 1")
        if self.digest_interval <= 0:
            raise ValueError("consensus.digest_interval must be > 0")
        if self.vote_forward_fanout < 0:
            raise ValueError(
                "consensus.vote_forward_fanout cannot be negative"
            )
        if self.adaptive_timeouts:
            # the controller's own validation, surfaced at config load
            # instead of node assembly; from_knobs is the ONE mapping
            # the controller constructor also uses, so the values
            # validated here are the values the node will run
            from ..consensus.pacing import PacingConfig

            try:
                PacingConfig.from_knobs(self).validate()
            except ValueError as e:
                raise ValueError(f"consensus.{e}") from e

    def to_state_machine_config(self):
        from ..consensus.state_machine import ConsensusConfig as SMC

        return SMC(**{f: getattr(self, f) for f in self._SM_FIELDS})


@dataclass
class SequencerConfig:
    """Morph sequencer-mode settings (reference sequencer key mgmt +
    node.go:1007-1032 createSequencerComponents) plus the streaming-
    plane knobs of the event-driven broadcast reactor
    (sequencer/broadcast_reactor.py, PERF_ANALYSIS §17)."""

    block_interval: float = 3.0
    sequencer_key_file: str = ""  # secp256k1 key -> this node produces
    sequencer_addresses: str = ""  # comma-separated 0x… allowed signers
    # follower apply/sync FALLBACK tick, seconds: the reactor wakes on
    # block receipt / pending insertion / peer status edges, so these
    # only bound staleness after a missed edge (the reference polls at
    # a hard 10 s cadence — keep 10.0 to mirror it)
    apply_interval: float = 10.0
    sync_interval: float = 10.0
    # catchup: missing-height requests kept in flight on the 0x51 sync
    # channel (each response refills the window)
    catchup_window: int = 64

    def validate_basic(self) -> None:
        if self.block_interval <= 0:
            raise ValueError("sequencer.block_interval must be > 0")
        if self.apply_interval <= 0 or self.sync_interval <= 0:
            raise ValueError(
                "sequencer.apply_interval/sync_interval must be > 0"
            )
        if self.catchup_window < 1:
            raise ValueError("sequencer.catchup_window must be >= 1")


@dataclass
class TpuConfig:
    """Device-mesh parallelism (SURVEY §2.3): the batch axis of every
    verification kernel shards over a jax.sharding.Mesh built from these
    axes. ici_parallelism spans the chips of one host/slice (collectives
    ride ICI); dcn_parallelism spans hosts (requires jax.distributed to
    be initialized so jax.devices() is global). 1/1 (default) keeps the
    single-device path; ici_parallelism=0 means "all local devices"."""

    ici_parallelism: int = 1
    dcn_parallelism: int = 1
    # "" = the default jax backend; "cpu" = host virtual devices (tests /
    # CI use 8 via --xla_force_host_platform_device_count)
    mesh_backend: str = ""
    # multi-host (DCN) runtime: when coordinator_address is set, node
    # assembly calls jax.distributed.initialize(coordinator_address,
    # num_processes, process_id) before any jax use, making
    # jax.devices() global so the dcn mesh axis can span hosts
    coordinator_address: str = ""  # host:port of process 0
    num_processes: int = 1
    process_id: int = 0

    def validate_basic(self) -> None:
        if self.ici_parallelism < 0:
            raise ValueError("ici_parallelism must be >= 0")
        if self.dcn_parallelism < 1:
            raise ValueError("dcn_parallelism must be >= 1")
        if self.num_processes < 1:
            raise ValueError("num_processes must be >= 1")
        if not (0 <= self.process_id < self.num_processes):
            raise ValueError("process_id must be in [0, num_processes)")
        if self.dcn_parallelism > 1 and self.num_processes > 1:
            if not self.coordinator_address:
                raise ValueError(
                    "dcn_parallelism over multiple processes needs "
                    "coordinator_address"
                )


@dataclass
class SchedulerConfig:
    """Unified verification dispatch scheduler (parallel/scheduler.py):
    one process-wide service coalescing every subsystem's signature
    verification into shape-bucketed, priority-classed, pipelined
    device dispatches. Priority classes are fixed:
    consensus > evidence > blocksync > light > lightserve."""

    enable: bool = True
    # UDS path of a standalone verify-service process
    # (`python -m tendermint_tpu verify-service`): when set, node
    # assembly builds a RemoteVerifyScheduler CLIENT instead of an
    # in-proc VerifyScheduler — this node's verify submissions coalesce
    # with every other attached node's on the service's device plane
    # (cross-PROCESS rounds), degrading to local dispatch whenever the
    # socket is unreachable (parallel/verify_service.py). Relative
    # paths resolve against the node home, so a rack of generated homes
    # shares one absolute socket (tools/testnet_generator.py
    # --verify-service).
    remote_socket: str = ""
    # max signature items coalesced into one device round (the measured
    # bulk-tier throughput knee, PERF_ANALYSIS §10)
    max_batch: int = 16384
    # comma-separated canonical pad buckets, e.g. "8,64,512,2048,8192";
    # "" = the built-in ladder (crypto/shape_registry)
    bucket_ladder: str = ""
    # shard coalesced rounds across ALL local devices (parallel/mesh.py
    # over every visible chip of the backend): the data-parallel
    # multi-chip verify plane (PERF_ANALYSIS §13). Equivalent to
    # [tpu] ici_parallelism = 0 but scoped to the scheduler knob set;
    # explicit [tpu] axes take precedence. No-op on 1 device.
    mesh_enable: bool = False
    # rounds below this row count stay single-device even under a mesh
    # — shard + all-gather overhead only amortizes on bulk rounds, and
    # live consensus rounds (O(validators) rows) want raw latency
    mesh_min_rows: int = 1024
    # ahead-of-time compile/load the ladder's verify programs on the
    # node's warm thread at startup (~6 programs/tier; zero per-shape
    # loads mid-height afterwards) and persist the manifest below.
    # Off by default: short-lived/test nodes shouldn't pay the ladder.
    prewarm: bool = False
    prewarm_manifest: str = "data/prewarm_manifest.json"

    def validate_basic(self) -> None:
        if self.max_batch < 1:
            raise ValueError("scheduler.max_batch must be >= 1")
        if self.mesh_min_rows < 1:
            raise ValueError("scheduler.mesh_min_rows must be >= 1")
        ladder = self.ladder()
        if ladder is not None and (not ladder or min(ladder) < 1):
            raise ValueError(
                f"scheduler.bucket_ladder must be positive ints, got "
                f"{self.bucket_ladder!r}"
            )

    def ladder(self):
        """Parsed bucket ladder, or None for the built-in default."""
        s = self.bucket_ladder.strip()
        if not s:
            return None
        try:
            return tuple(int(x) for x in s.split(",") if x.strip())
        except ValueError as e:
            raise ValueError(
                f"scheduler.bucket_ladder must be comma-separated ints: {e}"
            ) from e


@dataclass
class CommitPipelineConfig:
    """Pipelined commit path (consensus/commit_pipeline.py): overlap
    WAL group-commit, write-behind block persistence and the ABCI/L2
    apply with next-height consensus. Off: the serial reference
    finalize (save → end-height fsync → apply → state save on the
    critical path)."""

    enable: bool = True
    # extra group-commit coalescing window, seconds: how long the WAL
    # flush thread waits for more records before the shared fsync.
    # 0 (default) = natural group commit only — records arriving during
    # an in-flight fsync ride the next one at no added latency; > 0
    # trades barrier latency for fewer fsyncs (high-latency disks)
    flush_interval: float = 0.0
    # bound of the write-behind store's save queue (backpressure above
    # it). The consensus/blocksync paths self-limit to ~1 pending save
    # (apply barriers on block durability before the app commit), so
    # this is headroom for deeper pipelining, not a steady-state knob.
    max_inflight: int = 8

    def validate_basic(self) -> None:
        if self.flush_interval < 0:
            raise ValueError(
                "commit_pipeline.flush_interval cannot be negative"
            )
        if self.flush_interval > 1.0:
            raise ValueError(
                "commit_pipeline.flush_interval > 1s would stall "
                "every durability barrier"
            )
        if self.max_inflight < 1:
            raise ValueError("commit_pipeline.max_inflight must be >= 1")


@dataclass
class LightServeConfig:
    """Light-client serving plane (tendermint_tpu/lightserve): cached
    `light_block`/`signed_header`/`validator_set` proof routes over the
    node's stores plus the shared-round ServeVerifier that dedupes and
    coalesces concurrent client bisection verifies under the scheduler's
    `lightserve` lane."""

    enable: bool = True
    # LRU capacity of the LightBlockCache (one assembled proof per
    # height; entries admit only below the durable store height)
    cache_size: int = 1024
    # seconds a completed hop verdict is reusable for clients whose
    # `now` lands within the window; 0 = dedupe in-flight requests only
    dedup_window: float = 60.0

    def validate_basic(self) -> None:
        if self.cache_size < 1:
            raise ValueError("lightserve.cache_size must be >= 1")
        if self.dedup_window < 0:
            raise ValueError("lightserve.dedup_window cannot be negative")


@dataclass
class HealthConfig:
    """Live health plane (tendermint_tpu/obs/health.py): streaming
    detectors over the metric/trace seams rolled into per-subsystem
    SLO burn-rate verdicts, served by the `health`/`dump_health` RPCs
    and the tm_health_status{subsystem=} gauges. Default on — the
    monitor is a sampling loop plus a heartbeat task, not a hot path."""

    enable: bool = True
    # sampling cadence of the pull seams (scheduler/WAL/sequencer/
    # lightserve/p2p), seconds
    interval: float = 1.0
    # event-loop lag probe cadence; lag is measured as the probe's
    # scheduling overshoot
    heartbeat_interval: float = 0.25
    # multiwindow burn-rate windows (seconds): warn/critical require
    # BOTH windows over threshold, so short confirms "still happening"
    short_window: float = 30.0
    long_window: float = 300.0
    # quorum-lag anomaly: arrivals later than max(floor, margin *
    # baseline_p95) behind the round's first vote are bad events. The
    # first 32 samples are learning-only — gossip-tick trickle gives
    # even a clean committee a genuine arrival spread (~100 ms p95 on
    # the in-proc harness), so the baseline must exist before anything
    # is judged against it; margin 2x that learned tail is the anomaly
    # bar
    quorum_lag_floor: float = 0.025
    quorum_lag_margin: float = 2.0
    # verify-scheduler queue depth that counts as saturated when the
    # sampling interval also shows full/no dispatch progress
    scheduler_depth_floor: int = 256
    # dispatch fill-efficiency floor (obs/ledger.py seam): ticks whose
    # interval fill (rows-requested / rows-dispatched) falls under
    # fill_floor are bad events, judged only when the interval moved at
    # least fill_min_rows dispatched rows — a saturated scheduler
    # running 10%-full buckets is a ladder/mesh_min_rows
    # misconfiguration worth paging on; a small committee's tiny padded
    # vote rounds are not
    fill_floor: float = 0.1
    fill_min_rows: int = 256
    # WAL fsync drift: interval-mean latency beyond this multiple of
    # the learned good-sample median flags
    fsync_drift_factor: float = 4.0
    # verify-service IPC drift ([scheduler] remote_socket deployments):
    # interval-mean submit->verdict round trip beyond this multiple of
    # the learned good-sample median flags; any local-degrade fallback
    # in the interval is a bad event outright
    ipc_drift_factor: float = 4.0
    # sequencer receipt->applied SLO target (PR 10 measured 96 ms p95;
    # snapped up to the nearest apply-latency histogram bucket, 0.1 s)
    sequencer_apply_target: float = 0.1
    # lightserve proof-cache hit-rate floor (the SLO objective)
    cache_hit_floor: float = 0.9
    # event-loop lag above this is a bad event (PR 9: loop-bound nets)
    loop_lag_warn: float = 0.05
    # wall-clock conservation (obs.report.wall_conservation over the
    # flight ring, tracing on): a committed height whose dark_time
    # residue — wall not claimed by ANY named bucket — exceeds this
    # fraction is a bad event; sustained dark time means latency with
    # no instrumented owner
    dark_time_floor: float = 0.05
    # stalled-round ceiling = this factor x the static round-0 timeout
    # schedule (propose + prevote + precommit + commit waits)
    stall_factor: float = 3.0

    def validate_basic(self) -> None:
        if self.interval <= 0 or self.heartbeat_interval <= 0:
            raise ValueError(
                "health.interval/heartbeat_interval must be > 0"
            )
        if not (0 < self.short_window <= self.long_window):
            raise ValueError(
                "health windows must satisfy 0 < short_window <= "
                "long_window"
            )
        if not (0.0 < self.cache_hit_floor < 1.0):
            raise ValueError("health.cache_hit_floor must be in (0, 1)")
        if not (0.0 < self.fill_floor < 1.0):
            raise ValueError("health.fill_floor must be in (0, 1)")
        if not (0.0 < self.dark_time_floor < 1.0):
            raise ValueError("health.dark_time_floor must be in (0, 1)")
        if self.fill_min_rows < 1:
            raise ValueError("health.fill_min_rows must be >= 1")
        for f in (
            "quorum_lag_floor",
            "quorum_lag_margin",
            "fsync_drift_factor",
            "ipc_drift_factor",
            "sequencer_apply_target",
            "loop_lag_warn",
            "stall_factor",
        ):
            if getattr(self, f) <= 0:
                raise ValueError(f"health.{f} must be > 0")
        if self.scheduler_depth_floor < 1:
            raise ValueError("health.scheduler_depth_floor must be >= 1")


@dataclass
class TxIndexConfig:
    indexer: str = "kv"  # kv | null

    def validate_basic(self) -> None:
        if self.indexer not in ("kv", "null"):
            raise ValueError(f"unknown indexer {self.indexer!r}")


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    namespace: str = "tendermint"
    # span tracer + flight recorder (tendermint_tpu/obs): when on, the
    # node records per-step consensus spans, WAL fsyncs, device verify
    # calls and chaos faults into a fixed-size ring served by the
    # `dump_traces` RPC. TM_TPU_TRACE=1 enables it too.
    trace: bool = False
    trace_ring_size: int = 8192
    flight_heights: int = 16

    def validate_basic(self) -> None:
        if self.trace_ring_size <= 0:
            raise ValueError("instrumentation.trace_ring_size must be > 0")
        if self.flight_heights <= 0:
            raise ValueError("instrumentation.flight_heights must be > 0")


_SECTIONS = {
    "rpc": RPCConfig,
    "p2p": P2PConfig,
    "statesync": StateSyncConfig,
    "blocksync": BlockSyncConfig,
    "consensus": ConsensusTimeoutsConfig,
    "sequencer": SequencerConfig,
    "tpu": TpuConfig,
    "scheduler": SchedulerConfig,
    "commit_pipeline": CommitPipelineConfig,
    "lightserve": LightServeConfig,
    "health": HealthConfig,
    "tx_index": TxIndexConfig,
    "instrumentation": InstrumentationConfig,
}


@dataclass
class Config:
    root_dir: str = "."
    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    blocksync: BlockSyncConfig = field(default_factory=BlockSyncConfig)
    consensus: ConsensusTimeoutsConfig = field(
        default_factory=ConsensusTimeoutsConfig
    )
    sequencer: SequencerConfig = field(default_factory=SequencerConfig)
    tpu: TpuConfig = field(default_factory=TpuConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    commit_pipeline: CommitPipelineConfig = field(
        default_factory=CommitPipelineConfig
    )
    lightserve: LightServeConfig = field(default_factory=LightServeConfig)
    health: HealthConfig = field(default_factory=HealthConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(
        default_factory=InstrumentationConfig
    )

    # --- presets ------------------------------------------------------------

    @classmethod
    def default(cls) -> "Config":
        return cls()

    @classmethod
    def test_config(cls) -> "Config":
        c = cls()
        c.base.db_backend = "memory"
        c.consensus.timeout_propose = 0.4
        c.consensus.timeout_propose_delta = 0.1
        c.consensus.timeout_prevote = 0.2
        c.consensus.timeout_prevote_delta = 0.1
        c.consensus.timeout_precommit = 0.2
        c.consensus.timeout_precommit_delta = 0.1
        c.consensus.timeout_commit = 0.05
        c.consensus.skip_timeout_commit = True
        return c

    # --- paths --------------------------------------------------------------

    def path(self, rel: str) -> str:
        return rel if os.path.isabs(rel) else os.path.join(self.root_dir, rel)

    @property
    def genesis_file(self) -> str:
        return self.path(self.base.genesis_file)

    @property
    def node_key_file(self) -> str:
        return self.path(self.base.node_key_file)

    @property
    def priv_validator_key_file(self) -> str:
        return self.path(self.base.priv_validator_key_file)

    @property
    def priv_validator_state_file(self) -> str:
        return self.path(self.base.priv_validator_state_file)

    @property
    def bls_key_file(self) -> str:
        return self.path(self.base.bls_key_file)

    @property
    def wal_file(self) -> str:
        return self.path(self.consensus.wal_file)

    @property
    def addr_book_file(self) -> str:
        return self.path(self.p2p.addr_book_file)

    @property
    def db_dir(self) -> str:
        return self.path("data")

    def ensure_dirs(self) -> None:
        for d in ("config", "data"):
            os.makedirs(os.path.join(self.root_dir, d), exist_ok=True)

    # --- validation ----------------------------------------------------------

    def validate_basic(self) -> None:
        self.base.validate_basic()
        for name in _SECTIONS:
            getattr(self, name if name != "tx_index" else "tx_index").validate_basic()

    # --- TOML ----------------------------------------------------------------

    def to_toml(self) -> str:
        """Render the config file (reference config/toml.go template)."""

        def render_value(v):
            if isinstance(v, bool):
                return "true" if v else "false"
            if isinstance(v, (int, float)):
                return repr(v)
            return '"%s"' % str(v).replace("\\", "\\\\").replace('"', '\\"')

        out = [
            "# tendermint-tpu node configuration",
            "# (shape mirrors the reference config/config.go sections)",
            "",
        ]
        for f in fields(BaseConfig):
            out.append(f"{f.name} = {render_value(getattr(self.base, f.name))}")
        for section, typ in _SECTIONS.items():
            out.append("")
            out.append(f"[{section}]")
            obj = getattr(self, section)
            for f in fields(typ):
                out.append(
                    f"{f.name} = {render_value(getattr(obj, f.name))}"
                )
        return "\n".join(out) + "\n"

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path("config/config.toml")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_toml())
        return path

    @classmethod
    def load(cls, root_dir: str) -> "Config":
        """Load <root>/config/config.toml (defaults for missing keys)."""
        cfg = cls()
        cfg.root_dir = root_dir
        path = os.path.join(root_dir, "config", "config.toml")
        if not os.path.exists(path):
            return cfg
        with open(path, "rb") as f:
            data = tomllib.load(f)
        for f_ in fields(BaseConfig):
            if f_.name in data:
                setattr(cfg.base, f_.name, data[f_.name])
        for section, typ in _SECTIONS.items():
            if section not in data:
                continue
            obj = getattr(cfg, section)
            for f_ in fields(typ):
                if f_.name in data[section]:
                    setattr(obj, f_.name, data[section][f_.name])
        cfg.validate_basic()
        return cfg
