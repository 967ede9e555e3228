"""Config — one struct with per-module sections (ref: config/config.go).

Defaults mirror the reference (consensus timeouts config.go:573-580; test
configs shrink to ~10-40ms, :592-594).  Durations are seconds (float).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class BaseConfig:
    root_dir: str = ""
    chain_id: str = ""
    moniker: str = "anonymous"
    fast_sync: bool = True
    db_backend: str = "sqlite"  # role of goleveldb in the reference
    db_dir: str = "data"
    log_level: str = "info"
    genesis_file: str = "config/genesis.json"
    priv_validator_file: str = "config/priv_validator.json"
    # remote signer listen address (tcp://host:port or unix://path) — when
    # set, the node listens here for the external signer's dial-in and uses
    # it as its PrivValidator (node.go:225-242 TCPVal/IPCVal)
    priv_validator_laddr: str = ""
    # optional pin: hex ed25519 pubkey the signer must authenticate its
    # SecretConnection with; empty = accept any dialer (reference behavior)
    priv_validator_signer_pubkey: str = ""
    node_key_file: str = "config/node_key.json"
    abci: str = "socket"
    proxy_app: str = "tcp://127.0.0.1:26658"
    prof_laddr: str = ""
    filter_peers: bool = False

    def genesis_path(self) -> str:
        return os.path.join(self.root_dir, self.genesis_file)

    def priv_validator_path(self) -> str:
        return os.path.join(self.root_dir, self.priv_validator_file)

    def node_key_path(self) -> str:
        return os.path.join(self.root_dir, self.node_key_file)

    def db_path(self) -> str:
        return os.path.join(self.root_dir, self.db_dir)


@dataclass
class RPCConfig:
    laddr: str = "tcp://0.0.0.0:26657"
    grpc_laddr: str = ""
    grpc_max_open_connections: int = 900
    unsafe: bool = False
    max_open_connections: int = 900
    # load-shedding budget for broadcast_tx_* : at most this many in-flight
    # submissions across async+sync+commit before new ones are rejected
    # with a fast mempool-overloaded error. 0 = unbounded (old behavior).
    broadcast_max_in_flight: int = 256


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    seeds: str = ""
    persistent_peers: str = ""
    upnp: bool = False
    addr_book_file: str = "config/addrbook.json"
    addr_book_strict: bool = True
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    flush_throttle_timeout: float = 0.1  # 100ms (config.go:408)
    max_packet_msg_payload_size: int = 1024
    send_rate: int = 5120000
    recv_rate: int = 5120000
    pex: bool = True
    seed_mode: bool = False
    private_peer_ids: str = ""
    allow_duplicate_ip: bool = False
    handshake_timeout: float = 20.0
    dial_timeout: float = 3.0
    test_fuzz: bool = False

    def addr_book_path(self, root: str) -> str:
        return os.path.join(root, self.addr_book_file)


@dataclass
class MempoolConfig:
    recheck: bool = True
    broadcast: bool = True
    wal_path: str = ""
    size: int = 5000
    cache_size: int = 10000
    # -- per-peer QoS (mempool/qos.py). Rates are tokens/s with a burst
    # allowance; rate <= 0 disables that bucket. Defaults are generous:
    # honest gossip never notices them, a flooder does.
    qos_enabled: bool = True
    qos_peer_tx_rate: float = 1000.0
    qos_peer_tx_burst: float = 2000.0
    qos_peer_byte_rate: float = float(1 << 20)  # 1 MiB/s
    qos_peer_byte_burst: float = float(2 << 20)
    qos_global_tx_rate: float = 0.0  # aggregate cap across peers; 0 = off
    qos_global_tx_burst: float = 0.0  # 0 = 2x rate
    # repeat-offender demotion: after `mute_after` violations the peer is
    # muted for mute_base_s * 2^offenses (capped at mute_max_s); a clean
    # stretch of forgive_s after a mute expires resets the offense count
    qos_mute_after: int = 50
    qos_mute_base_s: float = 1.0
    qos_mute_max_s: float = 60.0
    qos_forgive_s: float = 30.0
    # fairness under a contended global bucket: peers above
    # slack * (window grants / n_peers) shed first; under-share peers may
    # overdraft up to fair_reserve tokens (0 = global burst)
    qos_fair_window_s: float = 1.0
    qos_fair_slack: float = 1.5
    qos_fair_reserve: float = 0.0
    # -- priority lanes: ascending priority thresholds; a tx with
    # priority >= lane_bounds[i] rides lane i+1. () = single lane
    # (reference behavior: full mempool rejects instead of evicting).
    lane_bounds: tuple = (1, 1024)
    # -- micro-batching: coalesce up to `checktx_batch` CheckTx submissions
    # into one app-conn flush window (1 = flush per tx, the reference
    # behavior); recheck_batch chunks post-commit rechecks (0 = one window
    # for the whole round).
    checktx_batch: int = 1
    recheck_batch: int = 0
    # -- batched signature ingest: when > 0 and the app exposes a
    # `tx_sig_extractor`, CheckTx/recheck windows pre-verify tx signatures
    # on a planner TxFeed dispatch (mempool/tx_verify.py) instead of one
    # serial verify per tx inside the app.  window_ms bounds how long the
    # feed may coalesce rows from concurrent callers; rows caps txs per
    # lane row.  0 disables (reference behavior: app verifies serially).
    tx_batch_window_ms: float = 0.0
    tx_batch_rows: int = 64


@dataclass
class ConsensusConfig:
    wal_path: str = "data/cs.wal/wal"
    # base timeouts (s) + per-round delta (config.go:573-580)
    timeout_propose: float = 3.0
    timeout_propose_delta: float = 0.5
    timeout_prevote: float = 1.0
    timeout_prevote_delta: float = 0.5
    timeout_precommit: float = 1.0
    timeout_precommit_delta: float = 0.5
    timeout_commit: float = 1.0
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval: float = 0.0
    peer_gossip_sleep_duration: float = 0.1
    peer_query_maj23_sleep_duration: float = 2.0
    blocktime_iota: float = 1.0  # min time between blocks (s)

    def propose(self, round: int) -> float:
        return self.timeout_propose + self.timeout_propose_delta * round

    def prevote(self, round: int) -> float:
        return self.timeout_prevote + self.timeout_prevote_delta * round

    def precommit(self, round: int) -> float:
        return self.timeout_precommit + self.timeout_precommit_delta * round

    def commit(self, t: float) -> float:
        """Deadline for starting the next height given commit time t."""
        return t + self.timeout_commit

    def wait_for_txs(self) -> bool:
        return not self.create_empty_blocks or self.create_empty_blocks_interval > 0

    def min_valid_vote_time_ns(self, block_time_ns: int) -> int:
        return block_time_ns + int(self.blocktime_iota * 1e9)

    def wal_file(self, root: str) -> str:
        return os.path.join(root, self.wal_path)


@dataclass
class StateSyncConfig:
    """State sync (snapshot restore + production). `enable` turns on the
    restore state machine for an empty node; snapshot_interval > 0 turns on
    snapshot production on any node whose app supports it. The trust root
    (trust_height + trust_hash, hex of the header hash at that height) comes
    from social consensus — a block explorer, another operator — exactly as
    in the reference's [statesync] section."""

    enable: bool = False
    trust_height: int = 0
    trust_hash: str = ""
    discovery_time: float = 1.0  # between snapshot-offer broadcasts (s)
    chunk_fetch_timeout: float = 10.0  # per chunk/light-block request (s)
    chunk_retries: int = 3  # attempts per chunk before giving up
    backfill_blocks: int = 16  # trailing commit window after restore
    chunk_send_rate: int = 0  # serving-side bytes/s cap; 0 = unlimited
    # producer side
    snapshot_interval: int = 0  # take a snapshot every N heights; 0 = off
    snapshot_chunk_size: int = 65536
    snapshot_keep_recent: int = 3
    # wire format for produced snapshots: 1 = raw chunks (reference),
    # 2 = per-chunk zlib (statesync/chunker.py SNAPSHOT_FORMAT_ZLIB).
    # Restoring nodes negotiate: an app that rejects a format with
    # REJECT_FORMAT makes the syncer retry the next advertised format.
    snapshot_format: int = 1


@dataclass
class VerifyConfig:
    """[verify] — fault tolerance for the device verification path
    (libs/breaker.py).  Mirrors GuardConfig field names so the node
    composition root can pass this section straight to
    configure_device_guard."""

    # consecutive device failures before the breaker opens
    breaker_threshold: int = 3
    # first open backoff (s); doubles per re-open up to breaker_backoff_max
    breaker_backoff: float = 1.0
    breaker_backoff_max: float = 60.0
    # wall-clock deadline per device dispatch (s); <= 0 disables the
    # supervising worker thread (a hung device then hangs the caller)
    dispatch_deadline: float = 30.0
    # fraction of device lanes cross-checked against the host oracle per
    # window; a mismatch quarantines the device path (operator reset).
    # 0 disables the audit, 1.0 re-verifies every lane on the host.
    audit_sample_rate: float = 0.05
    audit_seed: int = 0
    # retries after a failed device dispatch before host fallback
    retries: int = 1
    # device verify strategy: "ladder" (per-signature double-scalar
    # ladder, one lane per row) or "msm" (random-linear-combination
    # check — ONE Pippenger multi-scalar multiplication verifies the
    # whole window; rejected windows localize via chunk RLCs and exact
    # ladder re-runs, so accept/reject stays bit-identical).
    # TM_ED25519_PATH env overrides.
    ed25519_path: str = "ladder"
    # WindowPipeline depth: packed windows allowed in flight ahead of the
    # device (host SHA-512/decompress/pack for windows N+1..N+k overlaps
    # window N's dispatch).  2 = the classic double buffer; deeper keeps
    # the chips fed when pack time fluctuates across mixed window sizes.
    pipeline_depth: int = 2
    # multi-window superdispatch budget: how many independent small
    # windows the planner may fold into one lane tile PER MESH DEVICE
    # (parallel/planner.windows_per_dispatch = this × device count)
    windows_per_device: int = 4
    # where per-device partial segment tallies reduce: "device" (replicated
    # segment_sum inside the sharded step) or "host" (psum-free — the step
    # returns only lane-sharded verdicts and int64 tallies fold on host).
    # Bit-identical either way; "host" avoids the cross-device collective.
    planner_reduce: str = "device"
    # live-vote micro-batcher (parallel/planner.VoteFeed): hold arriving
    # consensus votes up to this many milliseconds and verify them as one
    # lane-packed planner dispatch.  0 disables batching — every vote
    # verifies serially on the host inside VoteSet.add_vote, the reference
    # behavior.  Quorum-completing votes flush immediately regardless.
    vote_batch_window_ms: float = 0.0
    # vote-set rows per window of a vote-batch flush (windows fold into one
    # superdispatch via plan_windows, windows_per_device applies)
    vote_batch_rows: int = 64


@dataclass
class FrontendConfig:
    """[frontend] — the multi-client light-client serving frontend
    (frontend/ package).  When enabled the node runs a `LiteFrontend`
    over its own block store (NodeProvider source) and, if `laddr` is
    set, serves the lite-proxy HTTP surface (/verify_commit,
    /light_block, ...) from it."""

    enable: bool = False
    # listen address for the HTTP surface, host:port; "" = frontend is
    # built (RPC frontend_status works) but no socket is opened
    laddr: str = ""
    # aggregation window: how long a flush waits for more client rows
    batch_window_s: float = 0.002
    # rows per planner dispatch (one row = one commit's signature batch)
    batch_max_rows: int = 64
    # verified-header LRU entries
    cache_size: int = 4096
    # run batched dispatches on the accelerator (subject to [verify]
    # breaker/guard); False = host path
    use_device: bool = False
    # optional social-consensus trust pin; 0/"" = trust-on-first-use
    trusted_height: int = 0
    trusted_hash: str = ""


@dataclass
class TxIndexConfig:
    indexer: str = "kv"  # "kv" | "null"
    index_tags: str = ""
    index_all_tags: bool = False


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    max_open_connections: int = 3
    namespace: str = "tendermint"
    # consensus flight recorder (consensus/flight.py); TM_FLIGHT=1 also works
    flight_recorder: bool = False
    # liveness watchdog (libs/watchdog.py): stall when no height/round
    # progress for stall_factor × block-interval EWMA (floored at
    # watchdog_min_stall_seconds)
    watchdog: bool = True
    watchdog_interval: float = 1.0
    watchdog_stall_factor: float = 5.0
    watchdog_min_stall_seconds: float = 10.0
    # crash-safe telemetry spool (libs/telemetry.py): a background flusher
    # appends one checksummed snapshot every N heights or T seconds to a
    # rotating segment group under the node root
    telemetry_spool: bool = False
    telemetry_spool_path: str = "data/telemetry/spool"
    telemetry_spool_interval_heights: int = 20
    telemetry_spool_interval_seconds: float = 5.0
    telemetry_spool_head_size_limit: int = 10 * 1024 * 1024
    telemetry_spool_total_size_limit: int = 256 * 1024 * 1024
    telemetry_spool_ring_capacity: int = 256


@dataclass
class Config:
    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    verify: VerifyConfig = field(default_factory=VerifyConfig)
    frontend: FrontendConfig = field(default_factory=FrontendConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(default_factory=InstrumentationConfig)

    def set_root(self, root: str) -> "Config":
        self.base.root_dir = root
        return self


def default_config() -> Config:
    return Config()


def test_config() -> Config:
    """Shrunken timeouts for tests (ref config.go:592-594 TestConsensusConfig)."""
    c = Config()
    c.base.fast_sync = False
    c.consensus.timeout_propose = 0.5
    c.consensus.timeout_propose_delta = 0.1
    c.consensus.timeout_prevote = 0.1
    c.consensus.timeout_prevote_delta = 0.05
    c.consensus.timeout_precommit = 0.1
    c.consensus.timeout_precommit_delta = 0.05
    c.consensus.timeout_commit = 0.1
    c.consensus.skip_timeout_commit = True
    c.consensus.peer_gossip_sleep_duration = 0.005
    c.consensus.peer_query_maj23_sleep_duration = 0.25
    c.consensus.blocktime_iota = 0.0
    return c
