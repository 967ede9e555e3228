"""SimNode — one full validator node assembled for the simulator.

Everything is REAL: ConsensusState + ConsensusReactor, Mempool +
MempoolReactor, EvidencePool + EvidenceReactor, BlockExecutor over a
kvstore ABCI app, per-node in-memory stores.  Only the transport is
simulated (`p2p/inproc.py` over a `SimNet` fabric) and the wall clock is
injectable (`sim/clock.py`).

This intentionally mirrors `tests/consensus_harness.py`'s builders — the
sim package is importable from production code and scripts, so it cannot
reach into `tests/`.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from tendermint_tpu.abci.examples.kvstore import KVStoreApp
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config.config import test_config
from tendermint_tpu.consensus.messages import VoteMessage, encode_msg
from tendermint_tpu.consensus.reactor import VOTE_CHANNEL, ConsensusReactor
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.evidence.pool import EvidencePool
from tendermint_tpu.evidence.reactor import EvidenceReactor
from tendermint_tpu.libs.db.kv import MemDB
from tendermint_tpu.libs.metrics import NodeMetrics
from tendermint_tpu.libs.watchdog import LivenessWatchdog
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p.inproc import InProcSwitch
from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
from tendermint_tpu.sim.byzantine import EquivocatingPV
from tendermint_tpu.sim.clock import SimClock
from tendermint_tpu.sim.simnet import SimNet
from tendermint_tpu.state import store as sm_store
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state_types import state_from_genesis
from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV
from tendermint_tpu.types.events import EventBus

SIM_CHAIN_ID = "sim-chain"
SIM_GENESIS_TIME_NS = 1_700_000_000_000_000_000


def make_sim_genesis(n_vals: int, power: int = 10):
    """Deterministic genesis: seeded keys, fixed genesis time — identical
    across runs so commit hashes are comparable run-to-run."""
    pvs = [MockPV(PrivKeyEd25519.generate(bytes([i + 1]) * 32))
           for i in range(n_vals)]
    doc = GenesisDoc(
        chain_id=SIM_CHAIN_ID,
        genesis_time_ns=SIM_GENESIS_TIME_NS,
        validators=[GenesisValidator(pv.get_pub_key(), power) for pv in pvs],
    )
    doc.validate_and_complete()
    return doc, pvs


class SimNode:
    """One simulated validator: real consensus stack over the fabric."""

    def __init__(self, index: int, node_id: str, doc: GenesisDoc, pv,
                 fabric: SimNet, config=None, app=None,
                 clock: Optional[SimClock] = None,
                 state_db=None, block_store=None,
                 wal_path: Optional[str] = None, handshake: bool = False):
        self.index = index
        self.node_id = node_id
        self.doc = doc
        self.pv = pv
        self.fabric = fabric
        self.clock = clock or SimClock()
        self.config = cfg = config or test_config()
        self.wal_path = wal_path

        # crash_restart hands back the dead node's stores: rebuild state
        # from the DB instead of genesis, and let the ABCI handshake
        # re-apply any blocks the (fresh) app is missing.
        if state_db is not None:
            self.state_db = state_db
            st = sm_store.load_state_from_db_or_genesis(self.state_db, doc)
        else:
            st = state_from_genesis(doc)
            self.state_db = MemDB()
            sm_store.save_state(self.state_db, st)
        self.block_store = (block_store if block_store is not None
                            else BlockStore(MemDB()))

        self.app = app or KVStoreApp()
        self.conn = MultiAppConn(LocalClientCreator(self.app))
        self.conn.start()
        self.handshake_blocks = 0
        if handshake:
            from tendermint_tpu.consensus.replay import Handshaker

            hs = Handshaker(self.state_db, st, self.block_store, doc)
            st = hs.handshake(self.conn)
            sm_store.save_state(self.state_db, st)
            self.handshake_blocks = hs.n_blocks
        # per-node registry so scenarios can assert on QoS/lane counters
        self.metrics = NodeMetrics()
        self.mempool = Mempool(
            self.conn.mempool,
            size=cfg.mempool.size,
            cache_size=cfg.mempool.cache_size,
            recheck=cfg.mempool.recheck,
            metrics=self.metrics,
            lane_bounds=cfg.mempool.lane_bounds,
            checktx_batch=cfg.mempool.checktx_batch,
            recheck_batch=cfg.mempool.recheck_batch,
        )
        self.evpool = EvidencePool(self.state_db, MemDB(), st.copy())

        self.bus = EventBus()
        self.bus.start()
        block_exec = BlockExecutor(
            self.state_db, self.conn.consensus, self.mempool, self.evpool,
            self.bus,
        )
        wal = None
        if wal_path:
            from tendermint_tpu.consensus.wal import WAL

            wal = WAL(wal_path, metrics=self.metrics)
        self.cs = ConsensusState(
            cfg.consensus, st.copy(), block_exec, self.block_store,
            self.mempool, self.evpool, wal=wal, metrics=self.metrics,
        )
        # [verify] vote_batch_window_ms > 0: batched live-vote verification
        # (same wiring as node/node.py; exposed so scenarios can assert the
        # feed actually engaged)
        self.vote_feed = None
        if getattr(cfg.verify, "vote_batch_window_ms", 0.0) > 0:
            from tendermint_tpu.parallel.planner import VoteFeed

            self.vote_feed = VoteFeed(
                window_s=cfg.verify.vote_batch_window_ms / 1000.0,
                max_rows=cfg.verify.vote_batch_rows,
                # ticket stamps share the node's (possibly skewed) clock so
                # flush spans fuse onto the node's flight-record timeline
                now_ns=self.clock,
            )
            self.cs.set_vote_feed(self.vote_feed)
        # [mempool] tx_batch_window_ms > 0: batched CheckTx signature
        # ingest when the app publishes a tx_sig_extractor (same wiring as
        # node/node.py; exposed so scenarios can assert dispatch counts)
        self.tx_feed = None
        self.tx_verifier = None
        _extractor = getattr(self.app, "tx_sig_extractor", None)
        if getattr(cfg.mempool, "tx_batch_window_ms", 0.0) > 0 and _extractor:
            from tendermint_tpu.mempool.tx_verify import BatchTxVerifier
            from tendermint_tpu.parallel.planner import TxFeed

            self.tx_feed = TxFeed(
                window_s=cfg.mempool.tx_batch_window_ms / 1000.0,
                max_rows=cfg.mempool.tx_batch_rows,
            )
            self.tx_verifier = BatchTxVerifier(
                self.tx_feed, _extractor, height_fn=self.mempool.height
            )
            self.mempool.set_batch_check_hook(self.tx_verifier, verdicts=True)
        self.cs.set_event_bus(self.bus)
        self.cs.set_priv_validator(pv)
        self.cs.now_ns = self.clock
        self.cs.flight.now_ns = self.clock
        self.cs.flight.node_id = node_id
        self.cs.flight.enable()

        self.reactor = ConsensusReactor(self.cs)
        self.mempool_reactor = MempoolReactor(
            self.mempool, peer_height_lookup=self.reactor.peer_height,
            config=cfg.mempool, metrics=self.metrics, now_ns=self.clock,
        )
        self.evidence_reactor = EvidenceReactor(
            self.evpool, peer_height_lookup=self.reactor.peer_height
        )
        self.switch = InProcSwitch(node_id, fabric)
        self.switch.add_reactor("consensus", self.reactor)
        self.switch.add_reactor("mempool", self.mempool_reactor)
        self.switch.add_reactor("evidence", self.evidence_reactor)
        fabric.register(self.switch)

        self.watchdog: Optional[LivenessWatchdog] = None
        self._equiv_thread: Optional[threading.Thread] = None
        self._equiv_stop = threading.Event()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        self.switch.start()

    def stop(self) -> None:
        self._equiv_stop.set()
        if self.watchdog is not None:
            self.watchdog.stop()
        try:
            if self.switch.is_running:
                self.switch.stop()  # stops reactors, which stop the cs
        except Exception:
            pass
        try:
            self.bus.stop()
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

    def crash(self) -> None:
        """Kill the node mid-flight, keeping its durable state (state_db,
        block_store, WAL file) for a replacement SimNode to rebuild from.
        Every WAL write already flushed (see WAL.write), so the file on
        disk is exactly what a kill -9 would leave behind."""
        self.stop()
        try:
            self.conn.stop()
        except Exception:
            pass

    def start_watchdog(self, **kwargs) -> LivenessWatchdog:
        self.watchdog = LivenessWatchdog(
            self.cs, switch=self.switch, now_ns=self.clock, **kwargs
        )
        self.watchdog.start()
        return self.watchdog

    def start_equivocation_pump(self, interval: float = 0.02) -> None:
        """Broadcast the EquivocatingPV's double-signed votes to all peers
        on the consensus VOTE channel — honest nodes mint the evidence."""
        if not isinstance(self.pv, EquivocatingPV):
            raise TypeError("node's priv validator is not an EquivocatingPV")

        def pump():
            while not self._equiv_stop.is_set():
                for vote in self.pv.drain_conflicting():
                    self.switch.broadcast(
                        VOTE_CHANNEL, encode_msg(VoteMessage(vote))
                    )
                time.sleep(interval)

        self._equiv_thread = threading.Thread(
            target=pump, name=f"equiv-pump-{self.node_id}", daemon=True
        )
        self._equiv_thread.start()

    # -- inspection ----------------------------------------------------------
    @property
    def height(self) -> int:
        return self.cs.rs.height

    def committed_hashes(self) -> Dict[int, str]:
        """height -> block hash hex for every block in our store."""
        out = {}
        base = max(1, self.block_store.base())
        for h in range(base, self.block_store.height() + 1):
            meta = self.block_store.load_block_meta(h)
            if meta is not None:
                out[h] = meta.block_id.hash.hex().upper()
        return out

    def commit_rounds(self) -> Dict[int, int]:
        """height -> round the commit formed at.  Any round > 0 means a
        real-time timeout fired (host load), which is exactly the case
        where same-seed runs may legitimately diverge."""
        out = {}
        base = max(1, self.block_store.base())
        for h in range(base, self.block_store.height() + 1):
            commit = self.block_store.load_seen_commit(h)
            if commit is not None:
                out[h] = commit.round()
        return out

    def committed_evidence_heights(self) -> List[int]:
        """Heights of blocks in our store that carry committed evidence."""
        out = []
        base = max(1, self.block_store.base())
        for h in range(base, self.block_store.height() + 1):
            block = self.block_store.load_block(h)
            if block is not None and block.evidence.evidence:
                out.append(h)
        return out


def build_sim_net(
    n_vals: int,
    seed: int = 0,
    config=None,
    app_factory: Optional[Callable[[int], object]] = None,
    clock_factory: Optional[Callable[[int], SimClock]] = None,
    byzantine: Optional[Dict[int, Callable]] = None,
):
    """N-node full-mesh simulated net.  `byzantine` maps a validator index
    (in sorted valset order) to a PrivValidator wrapper, e.g.
    ``{3: lambda pv: EquivocatingPV(pv)}``.  Returns (fabric, nodes);
    neither is started."""
    # Pin the commit verifier to the host backend before the first commit
    # verify: default selection imports jax and discovers devices under the
    # process-wide verifier lock (seconds on a CPU host), which blocks every
    # node's receive routine mid-consensus and forces timeout-driven round
    # bumps that destroy run-to-run hash determinism.
    # An explicit TM_BATCH_VERIFIER or an already-installed verifier wins.
    import os

    from tendermint_tpu.crypto import batch as _batch

    if _batch._default is None and not os.environ.get("TM_BATCH_VERIFIER"):
        _batch.set_batch_verifier(_batch.HostBatchVerifier())

    fabric = SimNet(seed=seed)
    doc, pvs = make_sim_genesis(n_vals)
    st = state_from_genesis(doc)
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    sorted_pvs = [by_addr[v.address] for v in st.validators.validators]

    nodes = []
    for i in range(n_vals):
        pv = sorted_pvs[i]
        if byzantine and i in byzantine:
            pv = byzantine[i](pv)
        nodes.append(
            SimNode(
                index=i,
                node_id=f"sim{i}",
                doc=doc,
                pv=pv,
                fabric=fabric,
                config=config,
                app=app_factory(i) if app_factory is not None else None,
                clock=clock_factory(i) if clock_factory is not None else None,
            )
        )
    fabric.connect_full_mesh()
    return fabric, nodes
