"""Node — composition root wiring every service (ref: node/node.go:152-567).

NewNode order mirrored: stores → proxyApp (3 ABCI conns) → handshake/replay →
mempool → evidence → BlockExecutor → consensus → eventBus → indexer → RPC.
P2P attaches through the switch when networking is enabled; a single-validator
node runs the full consensus loop without it (node.go:246-252 fastSync=false
single-val path).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config.config import Config
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.evidence.pool import EvidencePool
from tendermint_tpu.libs.db.kv import new_db
from tendermint_tpu.libs.service import BaseService
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.privval.file_pv import FilePV
from tendermint_tpu.proxy.app_conn import (
    ClientCreator,
    MultiAppConn,
    default_client_creator,
)
from tendermint_tpu.state import store as sm_store
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.txindex.kv import KVTxIndexer, NullTxIndexer, TxIndexerService
from tendermint_tpu.types import GenesisDoc
from tendermint_tpu.types.events import EventBus


class Node(BaseService):
    def __init__(
        self,
        config: Config,
        priv_validator: Optional[FilePV] = None,
        client_creator: Optional[ClientCreator] = None,
        genesis_doc: Optional[GenesisDoc] = None,
        db_provider=None,
        logger=None,
    ):
        super().__init__("Node", logger)
        self.config = config
        root = config.base.root_dir

        def _db(name: str):
            if db_provider is not None:
                return db_provider(name)
            return new_db(name, config.base.db_backend, config.base.db_path())

        # stores
        self.block_store_db = _db("blockstore")
        self.block_store = BlockStore(self.block_store_db)
        self.state_db = _db("state")

        # genesis (cached in stateDB like node.go:831-856)
        if genesis_doc is None:
            raw = self.state_db.get(b"genesisDoc")
            if raw is not None:
                genesis_doc = GenesisDoc.from_json(raw.decode())
            else:
                genesis_doc = GenesisDoc.from_file(config.base.genesis_path())
        self.state_db.set(b"genesisDoc", genesis_doc.to_json().encode())
        self.genesis_doc = genesis_doc

        state = sm_store.load_state_from_db_or_genesis(self.state_db, genesis_doc)

        # app connections
        creator = client_creator or default_client_creator(
            config.base.proxy_app, config.base.proxy_app
        )
        self.proxy_app = MultiAppConn(creator)
        self.proxy_app.start()

        # state-sync snapshot store (serves restoring peers; feeds the
        # producer when snapshot_interval > 0)
        self.snapshot_store = None
        if config.statesync.enable or config.statesync.snapshot_interval > 0:
            from tendermint_tpu.statesync import SnapshotStore

            self.snapshot_store = SnapshotStore(_db("snapshots"))
            app = getattr(creator, "_app", None)
            if config.statesync.snapshot_interval > 0 and hasattr(
                app, "configure_snapshots"
            ):
                snap_kwargs = {}
                if config.statesync.snapshot_format != 1:
                    # only apps that know about alternative wire formats
                    # accept the kwarg; format 1 keeps the 4-arg call shape
                    snap_kwargs["snapshot_format"] = config.statesync.snapshot_format
                app.configure_snapshots(
                    self.snapshot_store,
                    config.statesync.snapshot_interval,
                    config.statesync.snapshot_chunk_size,
                    config.statesync.snapshot_keep_recent,
                    **snap_kwargs,
                )

        # handshake: sync app with store/state
        handshaker = Handshaker(
            self.state_db, state, self.block_store, genesis_doc
        )
        state = handshaker.handshake(self.proxy_app)
        sm_store.save_state(self.state_db, state)

        # priv validator — remote signer endpoint when configured
        # (node.go:225-242: TCPVal/IPCVal listen for the signer's dial-in)
        self.signer_endpoint = None
        if config.base.priv_validator_laddr:
            from tendermint_tpu.crypto.keys import PubKeyEd25519
            from tendermint_tpu.privval.remote_signer import (
                SignerValidatorEndpoint,
            )

            expected = None
            if config.base.priv_validator_signer_pubkey:
                if config.base.priv_validator_laddr.startswith("unix"):
                    # the pin authenticates the SecretConnection handshake,
                    # which unix sockets don't do — with a pin set, every
                    # signer would be silently rejected forever
                    raise ValueError(
                        "priv_validator_signer_pubkey requires a tcp:// "
                        "priv_validator_laddr (unix sockets have no "
                        "authenticated handshake to pin)"
                    )
                expected = PubKeyEd25519(
                    bytes.fromhex(config.base.priv_validator_signer_pubkey)
                )
            self.signer_endpoint = SignerValidatorEndpoint(
                config.base.priv_validator_laddr,
                expected_signer_pubkey=expected,
            )
            self.signer_endpoint.start()
            if not self.signer_endpoint.wait_for_signer():
                # tear the endpoint down before raising: __init__ failure
                # means stop() can never run, and a zombie accept loop would
                # hold the port (and greet late dialers) forever
                try:
                    self.signer_endpoint.stop()
                except Exception:
                    pass
                raise RuntimeError(
                    "no remote signer dialed "
                    f"{config.base.priv_validator_laddr} before the deadline"
                )
            priv_validator = self.signer_endpoint
        self.priv_validator = priv_validator

        # event bus + indexer
        self.event_bus = EventBus()
        if config.tx_index.indexer == "kv":
            self.tx_indexer = KVTxIndexer(_db("tx_index"))
        else:
            self.tx_indexer = NullTxIndexer()
        self.indexer_service = TxIndexerService(self.tx_indexer, self.event_bus)

        # metrics (consensus/p2p/mempool/state families; node.go:100-113
        # MetricsProvider + the Prometheus server at node.go:698 — here the
        # registry is scraped at the RPC server's /metrics route)
        from tendermint_tpu.libs.metrics import NodeMetrics

        self.metrics = NodeMetrics() if config.instrumentation.prometheus else None

        # device dispatch guard: breaker thresholds, dispatch deadline and
        # the silent-corruption audit rate come from the [verify] section
        from tendermint_tpu.libs.breaker import configure_device_guard

        configure_device_guard(config.verify)

        from tendermint_tpu.crypto.batch import (
            check_removed_options,
            set_default_ed25519_path,
        )

        # a [verify] section written for an earlier build may still name an
        # option this one removed: refused here, with the reason
        check_removed_options(config.verify)
        # [verify] ed25519_path: per-row ladder vs one-MSM-per-window RLC
        set_default_ed25519_path(getattr(config.verify, "ed25519_path", None))

        # [verify] planner knobs: pipeline depth, multi-window superdispatch
        # budget and the tally reduction side (parallel/planner.py)
        from tendermint_tpu.parallel.planner import configure_planner

        configure_planner(config.verify)

        # the batch verifier is chosen ONCE, here, from the environment
        # (TM_BATCH_VERIFIER, then jax.devices() under JAX_PLATFORMS) — not
        # lazily at the first commit.  A [verify] / TM_* choice that cannot
        # be served raises now, with the reason; a chipless host gets the
        # host verifier and a logged no_tpu.  cmd/tendermint prints the
        # line beside "Node started"; /status serves verifier_info().
        from tendermint_tpu.crypto.batch import (
            describe_verifier,
            get_batch_verifier,
        )

        self.verifier_description = describe_verifier(get_batch_verifier())

        if self.metrics is not None:
            # slow-subscriber drop accounting (libs/pubsub.py)
            m = self.metrics
            self.event_bus.set_on_drop(
                lambda client_id: m.pubsub_dropped.add(1.0, (client_id,))
            )

        # mempool + evidence (optional mempool WAL, mempool.go:223 InitWAL)
        mempool_wal = None
        if root and config.mempool.wal_path:
            from tendermint_tpu.libs.autofile import Group

            mempool_wal = Group(os.path.join(root, config.mempool.wal_path))
        self.mempool = Mempool(
            self.proxy_app.mempool,
            height=state.last_block_height,
            size=config.mempool.size,
            cache_size=config.mempool.cache_size,
            recheck=config.mempool.recheck,
            wal_group=mempool_wal,
            metrics=self.metrics,
            lane_bounds=config.mempool.lane_bounds,
            checktx_batch=config.mempool.checktx_batch,
            recheck_batch=config.mempool.recheck_batch,
        )
        if config.consensus.wait_for_txs():
            self.mempool.enable_txs_available()
        self.evidence_pool = EvidencePool(self.state_db, _db("evidence"), state)

        # block executor + consensus
        self.block_exec = BlockExecutor(
            self.state_db,
            self.proxy_app.consensus,
            self.mempool,
            self.evidence_pool,
            self.event_bus,
            metrics=self.metrics,
        )
        wal_file = (
            config.consensus.wal_file(root)
            if root and config.consensus.wal_path
            else None
        )
        wal = WAL(wal_file, metrics=self.metrics) if wal_file else None
        self.consensus_state = ConsensusState(
            config.consensus,
            state.copy(),
            self.block_exec,
            self.block_store,
            self.mempool,
            self.evidence_pool,
            wal=wal,
            metrics=self.metrics,
        )
        self.consensus_state.set_event_bus(self.event_bus)
        # [verify] vote_batch_window_ms > 0: live peer votes verify through
        # the deadline-bounded vote micro-batcher instead of one-at-a-time
        # inside VoteSet.add_vote.  No mesh in the node composition root —
        # the feed rides the planner's host batch path (verify_generic),
        # and the [verify] breaker/guard wraps any device executor a test
        # or bench injects.
        self.vote_feed = None
        if getattr(config.verify, "vote_batch_window_ms", 0.0) > 0:
            from tendermint_tpu.parallel.planner import VoteFeed

            self.vote_feed = VoteFeed(
                window_s=config.verify.vote_batch_window_ms / 1000.0,
                max_rows=config.verify.vote_batch_rows,
            )
            self.consensus_state.set_vote_feed(self.vote_feed)
        # [mempool] tx_batch_window_ms > 0: CheckTx/recheck windows pre-verify
        # tx signatures on a planner TxFeed dispatch when the app publishes a
        # `tx_sig_extractor` (e.g. SignedKVStoreApp).  Same chipless backend
        # and guard story as the vote feed above.
        self.tx_feed = None
        if getattr(config.mempool, "tx_batch_window_ms", 0.0) > 0:
            extractor = getattr(
                getattr(creator, "_app", None), "tx_sig_extractor", None
            )
            if extractor is not None:
                from tendermint_tpu.mempool.tx_verify import BatchTxVerifier
                from tendermint_tpu.parallel.planner import TxFeed

                self.tx_feed = TxFeed(
                    window_s=config.mempool.tx_batch_window_ms / 1000.0,
                    max_rows=config.mempool.tx_batch_rows,
                )
                self.tx_verifier = BatchTxVerifier(
                    self.tx_feed, extractor, height_fn=self.mempool.height
                )
                self.mempool.set_batch_check_hook(self.tx_verifier, verdicts=True)
        if priv_validator is not None:
            self.consensus_state.set_priv_validator(priv_validator)
        # flight recorder identity + config gate (env TM_FLIGHT may have
        # enabled it already; _build_p2p upgrades node_id to the p2p id)
        self.consensus_state.flight.node_id = config.base.moniker
        if config.instrumentation.flight_recorder:
            self.consensus_state.flight.enable()
        self.watchdog = None
        # crash-safe telemetry spool (libs/telemetry.py): built here so the
        # torn-tail recovery truncate runs before anything else appends;
        # the flusher thread starts in on_start
        self.telemetry_spool = None
        if config.instrumentation.telemetry_spool:
            from tendermint_tpu.libs.telemetry import (
                TelemetrySpool,
                node_sources,
            )

            inst = config.instrumentation
            spool_path = inst.telemetry_spool_path
            if not os.path.isabs(spool_path):
                spool_path = os.path.join(config.base.root_dir, spool_path)
            self.telemetry_spool = TelemetrySpool(
                spool_path,
                node_id=config.base.moniker,
                interval_heights=inst.telemetry_spool_interval_heights,
                interval_seconds=inst.telemetry_spool_interval_seconds,
                head_size_limit=inst.telemetry_spool_head_size_limit,
                total_size_limit=inst.telemetry_spool_total_size_limit,
                ring_capacity=inst.telemetry_spool_ring_capacity,
                metrics=(
                    self.metrics.telemetry
                    if self.metrics is not None
                    else None
                ),
                height_fn=lambda: self.consensus_state.rs.height,
            )
            for name, fn in node_sources(self).items():
                self.telemetry_spool.set_source(name, fn)
            self.telemetry_spool.set_source(
                "spool", self.telemetry_spool.status
            )

        # p2p: transport + switch + reactors (node.go:372-471). Disabled
        # (single-node) when p2p.laddr is empty — node.go:246-252's
        # fastSync=false single-val path.
        self.switch = None
        self.consensus_reactor = None
        self.blockchain_reactor = None
        self.statesync_reactor = None
        if config.p2p.laddr:
            self._build_p2p(config, state)

        self.rpc_server = None
        self.grpc_broadcast = None
        self._rpc_env = None

        # [frontend]: multi-client light-client serving over this node's
        # own stores (lite/proxy.py LiteProxy + frontend/ package)
        self.frontend = None
        self.lite_server = None
        if config.frontend.enable:
            from tendermint_tpu.lite.proxy import LiteProxy

            fe = config.frontend
            pin_h = fe.trusted_height if fe.trusted_height > 0 else None
            pin_hash = bytes.fromhex(fe.trusted_hash) if fe.trusted_hash else None
            self.frontend = LiteProxy(
                self.genesis_doc.chain_id,
                trust_db=_db("lite_trust"),
                trusted_height=pin_h,
                trusted_hash=pin_hash,
                block_store=self.block_store,
                state_db=self.state_db,
                batch_window_s=fe.batch_window_s,
                batch_max_rows=fe.batch_max_rows,
                cache_size=fe.cache_size,
                use_device=fe.use_device,
            )

    def _build_p2p(self, config: Config, state) -> None:
        from tendermint_tpu.blockchain.reactor import BlockchainReactor
        from tendermint_tpu.consensus.reactor import ConsensusReactor
        from tendermint_tpu.evidence.reactor import EvidenceReactor
        from tendermint_tpu.mempool.reactor import MempoolReactor
        from tendermint_tpu.p2p import (
            MConnConfig,
            MultiplexTransport,
            NetAddress,
            NodeInfo,
            NodeKey,
            ProtocolVersion,
            Switch,
            SwitchConfig,
        )

        self.node_key = NodeKey.load_or_generate(config.base.node_key_path())
        self.consensus_state.flight.node_id = self.node_key.id()
        fast_sync = config.base.fast_sync
        # Never fast-sync when the only validator is us (node.go:246-252):
        # there is no one to sync from, and waiting for peers stalls a
        # freshly initialized single-validator chain forever.
        if fast_sync and state.validators.size == 1 and self.priv_validator is not None:
            only_val = state.validators.validators[0]
            if self.priv_validator.get_pub_key().address() == only_val.address:
                fast_sync = False
        # State sync restores only a node with NO history: with blocks on
        # disk the regular fast-sync path is strictly safer (and a restored
        # height below ours would be a rollback).
        restoring = config.statesync.enable and state.last_block_height == 0
        # While restoring, consensus defers (as in fast sync) and the
        # blockchain reactor must NOT start its pool at height 1 — the
        # statesync hand-off rebases it above the snapshot height.
        self.consensus_reactor = ConsensusReactor(
            self.consensus_state, fast_sync=fast_sync or restoring
        )
        self.blockchain_reactor = BlockchainReactor(
            state.copy(),
            self.block_exec,
            self.block_store,
            fast_sync=fast_sync and not restoring,
            consensus_reactor=self.consensus_reactor,
            metrics=self.metrics,
        )
        if config.statesync.enable or config.statesync.snapshot_interval > 0:
            from tendermint_tpu.statesync import StateSyncReactor, StateSyncer

            syncer = None
            if restoring:
                syncer = StateSyncer(
                    config.statesync,
                    self.genesis_doc.chain_id,
                    self.genesis_doc,
                    self.proxy_app.query,
                    self.state_db,
                    self.block_store,
                )
            self.statesync_reactor = StateSyncReactor(
                config.statesync,
                app_query=self.proxy_app.query,
                snapshot_store=self.snapshot_store,
                block_store=self.block_store,
                state_db=self.state_db,
                syncer=syncer,
                on_synced=self._on_statesync_complete,
            )
        # kept on self: dump_mempool_qos serves its per-peer admission ledger
        self.mempool_reactor = mem_reactor = MempoolReactor(
            self.mempool,
            peer_height_lookup=self.consensus_reactor.peer_height,
            config=config.mempool,
            metrics=self.metrics,
        )
        ev_reactor = EvidenceReactor(
            self.evidence_pool,
            peer_height_lookup=self.consensus_reactor.peer_height,
        )

        pex_reactor = None
        if config.p2p.pex:
            from tendermint_tpu.p2p.pex import AddrBook, PEXReactor

            self.addr_book = AddrBook(
                config.p2p.addr_book_path(config.base.root_dir)
                if config.base.root_dir
                else None,
                strict=config.p2p.addr_book_strict,
            )
            seeds = [
                NetAddress.parse(s)
                for s in config.p2p.seeds.split(",")
                if s.strip()
            ]
            pex_reactor = PEXReactor(
                self.addr_book, seeds=seeds, seed_mode=config.p2p.seed_mode
            )

        mconfig = MConnConfig(
            send_rate=config.p2p.send_rate,
            recv_rate=config.p2p.recv_rate,
            max_packet_msg_payload_size=config.p2p.max_packet_msg_payload_size,
            flush_throttle=config.p2p.flush_throttle_timeout,
        )
        # NodeInfo advertises every reactor channel incl. PEX's 0x00
        # (makeNodeInfo node.go:785) — peers drop traffic on unadvertised
        # channels, so an omission here silently kills that protocol
        reactors = [
            self.consensus_reactor, self.blockchain_reactor, mem_reactor,
            ev_reactor,
        ]
        if self.statesync_reactor is not None:
            reactors.append(self.statesync_reactor)
        if pex_reactor is not None:
            reactors.append(pex_reactor)
        channels = bytes(
            d.id for reactor in reactors for d in reactor.get_channels()
        )
        laddr = config.p2p.laddr
        listen_hp = laddr[len("tcp://"):] if laddr.startswith("tcp://") else laddr
        node_info = NodeInfo(
            protocol_version=ProtocolVersion(),
            id=self.node_key.id(),
            listen_addr=listen_hp,
            network=self.genesis_doc.chain_id,
            version="tpu-0.1.0",
            channels=channels,
            moniker=config.base.moniker,
        )
        # ABCI peer filtering (node.go:383-421): the app vetoes peers by
        # address at connection time and by authenticated node ID after the
        # handshake, via /p2p/filter/... queries — OK code admits
        conn_filters = []
        peer_filters = []
        if config.base.filter_peers:
            from tendermint_tpu.abci import types as abci_t

            FILTER_TIMEOUT = 5.0  # node.go filterTimeout: a stalled app
            # query must not wedge the accept loop — time out and reject

            def _abci_filter(path_prefix: str):
                def f(value: str):
                    import queue as _q
                    import threading as _t

                    out: "_q.Queue" = _q.Queue(1)

                    def run():
                        try:
                            out.put(self.proxy_app.query.query_sync(
                                abci_t.RequestQuery(
                                    path=f"{path_prefix}/{value}"
                                )
                            ))
                        except Exception as e:  # surfaced as rejection
                            out.put(e)

                    _t.Thread(target=run, daemon=True,
                              name="abci-peer-filter").start()
                    try:
                        res = out.get(timeout=FILTER_TIMEOUT)
                    except _q.Empty:
                        return "filter query timed out"
                    if isinstance(res, Exception):
                        return f"filter query failed: {res}"
                    if res.code != abci_t.CODE_TYPE_OK:
                        return f"rejected by app (code {res.code})"
                    return None

                return f

            conn_filters.append(_abci_filter("/p2p/filter/addr"))
            peer_filters.append(_abci_filter("/p2p/filter/id"))

        transport = MultiplexTransport(
            node_info, self.node_key, conn_filters=conn_filters
        )
        self.switch = Switch(
            transport,
            SwitchConfig(
                max_num_inbound_peers=config.p2p.max_num_inbound_peers,
                max_num_outbound_peers=config.p2p.max_num_outbound_peers,
                allow_duplicate_ip=config.p2p.allow_duplicate_ip,
            ),
            mconfig,
            peer_filters=peer_filters,
            metrics=self.metrics,
        )
        self.switch.add_reactor("consensus", self.consensus_reactor)
        self.switch.add_reactor("blockchain", self.blockchain_reactor)
        self.switch.add_reactor("mempool", mem_reactor)
        self.switch.add_reactor("evidence", ev_reactor)
        if self.statesync_reactor is not None:
            self.switch.add_reactor("statesync", self.statesync_reactor)
        if pex_reactor is not None:
            self.switch.add_reactor("pex", pex_reactor)

    def _on_statesync_complete(self, state, height: int) -> None:
        """Snapshot restore finished: the syncer persisted state + backfill;
        hand the reconstructed state to fast sync, which catches the trailing
        blocks and switches to consensus as usual."""
        self.logger.info("state sync restored height %d — starting fast sync", height)
        try:
            self.mempool.update(height, [])
        except Exception:
            self.logger.exception("mempool height update after restore failed")
        if self.blockchain_reactor is not None:
            self.blockchain_reactor.start_from_statesync(state)

    # lifecycle -------------------------------------------------------------
    def on_start(self) -> None:
        self.event_bus.start()
        self.indexer_service.start()
        if self.metrics is not None:
            from tendermint_tpu.types.events import EVENT_NEW_BLOCK, query_for_event

            sub = self.event_bus.subscribe(
                "node-metrics", query_for_event(EVENT_NEW_BLOCK), maxsize=100
            )

            def _pump():
                import queue as _q

                while self.is_running or not self._quit.is_set():
                    try:
                        msg = sub.get(timeout=0.2)
                    except _q.Empty:
                        if self._quit.is_set():
                            return
                        continue
                    try:
                        rs = self.consensus_state.get_round_state()
                        # rounds gauge is set at enter_new_round (the
                        # reference site) — not here, where it would read
                        # the NEXT height's round
                        self.metrics.record_block(msg.data.block, rs.validators)
                    except Exception:
                        pass

            threading.Thread(target=_pump, name="metrics-pump", daemon=True).start()
        if self.config.rpc.laddr:
            from tendermint_tpu.rpc.server import RPCServer
            from tendermint_tpu.rpc.core.env import RPCEnv

            self._rpc_env = RPCEnv(self)
            self.rpc_server = RPCServer(self.config.rpc.laddr, self._rpc_env)
            self.rpc_server.start()
        if self.frontend is not None and self.config.frontend.laddr:
            from tendermint_tpu.lite.proxy import serve_proxy

            self.lite_server = serve_proxy(
                self.frontend, self.config.frontend.laddr
            )
            threading.Thread(
                target=self.lite_server.serve_forever,
                name="lite-frontend",
                daemon=True,
            ).start()
        if self.config.rpc.grpc_laddr:
            from tendermint_tpu.abci.grpc import BroadcastAPIServer

            self.grpc_broadcast = BroadcastAPIServer(
                self.config.rpc.grpc_laddr, self
            )
            self.grpc_broadcast.start()
        if self.switch is not None:
            # the consensus reactor starts (or fast-sync defers) the
            # consensus state; dial persistent peers after listening
            laddr = self.config.p2p.laddr
            self.switch.transport.listen(
                laddr[len("tcp://"):] if laddr.startswith("tcp://") else laddr
            )
            self.switch.start()
            if self.config.p2p.persistent_peers:
                from tendermint_tpu.p2p import NetAddress

                addrs = [
                    NetAddress.parse(a)
                    for a in self.config.p2p.persistent_peers.split(",")
                    if a.strip()
                ]
                addrs = [a for a in addrs if a.id != self.node_key.id()]
                self.switch.dial_peers_async(addrs, persistent=True)
            if self.metrics is not None:
                threading.Thread(
                    target=self._p2p_metrics_pump, name="p2p-metrics", daemon=True
                ).start()
        else:
            self.consensus_state.start()
        if self.config.instrumentation.watchdog:
            from tendermint_tpu.libs.watchdog import LivenessWatchdog

            inst = self.config.instrumentation
            self.watchdog = LivenessWatchdog(
                self.consensus_state,
                switch=self.switch,
                metrics=self.metrics,
                interval=inst.watchdog_interval,
                stall_factor=inst.watchdog_stall_factor,
                min_stall_seconds=inst.watchdog_min_stall_seconds,
                logger=self.logger,
            )
            self.watchdog.start()
        if self.telemetry_spool is not None:
            self.telemetry_spool.start()
        self.logger.info("node started chain_id=%s", self.genesis_doc.chain_id)

    def _p2p_metrics_pump(self) -> None:
        import time as _t

        while not self._quit.is_set():
            try:
                self.metrics.peers.set(self.switch.peers.size())
                for peer in self.switch.peers.list():
                    self.metrics.set_peer_pending(
                        peer.id, peer.pending_send_bytes()
                    )
                if self.blockchain_reactor is not None:
                    self.metrics.fast_syncing.set(
                        1 if self.blockchain_reactor.fast_sync else 0
                    )
            except Exception:
                pass
            _t.sleep(1.0)

    def on_stop(self) -> None:
        # spool first while the analyzers are still live: its stop() writes
        # one final "shutdown" snapshot closing the run's last leg
        services = [self.telemetry_spool, self.watchdog]
        services += [self.switch] if self.switch is not None else [self.consensus_state]
        services += [self.rpc_server, self.grpc_broadcast, self.indexer_service,
                     self.event_bus, self.proxy_app, self.signer_endpoint]
        for svc in services:
            if svc is None:
                continue
            try:
                svc.stop()
            except Exception:
                pass
        if self.lite_server is not None:
            try:
                self.lite_server.shutdown()
                self.lite_server.server_close()
            except Exception:
                pass
        if self.frontend is not None:
            try:
                self.frontend.close()
            except Exception:
                pass
        if self.vote_feed is not None:
            try:
                self.vote_feed.close()
            except Exception:
                pass
        if self.tx_feed is not None:
            try:
                self.tx_feed.close()
            except Exception:
                pass

    # info -------------------------------------------------------------------
    def status(self) -> dict:
        from tendermint_tpu.crypto.batch import verifier_info

        rs = self.consensus_state.get_round_state()
        latest_height = self.block_store.height()
        meta = self.block_store.load_block_meta(latest_height) if latest_height else None
        pub = (
            self.priv_validator.get_pub_key() if self.priv_validator else None
        )
        return {
            "node_info": {
                "network": self.genesis_doc.chain_id,
                "moniker": self.config.base.moniker,
                "version": "tpu-0.1.0",
            },
            "sync_info": {
                "latest_block_height": latest_height,
                "latest_block_hash": (
                    meta.block_id.hash.hex().upper() if meta else ""
                ),
                "latest_app_hash": (
                    meta.header.app_hash.hex().upper() if meta else ""
                ),
                "latest_block_time_ns": meta.header.time_ns if meta else 0,
                "catching_up": (
                    self.blockchain_reactor.fast_sync
                    if self.blockchain_reactor is not None
                    else False
                ),
            },
            "validator_info": {
                "address": pub.address().hex().upper() if pub else "",
                "voting_power": (
                    self.consensus_state.rs.validators.get_by_address(pub.address())[1].voting_power
                    if pub and self.consensus_state.rs.validators.has_address(pub.address())
                    else 0
                ),
            },
            "consensus_state": {
                "height": rs.height,
                "round": rs.round,
                "step": rs.step.name,
            },
            # which verify backend on which device, with its dispatch /
            # fallback / audit counters and breaker state — not
            # unsafe-gated: an operator must be able to tell a device run
            # from a quiet host run
            "verifier_info": verifier_info(),
        }
