"""Fast sync: BlockPool, windowed batch verification, reactor sync loop,
and the full late-joiner flow (ref: blockchain/pool_test.go, reactor_test.go,
and the verify→apply loop at blockchain/reactor.go:216-327).
"""

import dataclasses
import threading
import time

import pytest

from tendermint_tpu.blockchain.pool import BlockPool
from tendermint_tpu.blockchain.reactor import (
    BlockchainReactor,
    verify_block_window,
)
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.libs.db.kv import MemDB
from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
from tendermint_tpu.abci.examples.kvstore import KVStoreApp
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.state import store as sm_store
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state_types import state_from_genesis
from tendermint_tpu.testutil.chain import build_chain

from tests.consensus_harness import make_cs_from_genesis, wait_for


# ---------------------------------------------------------------------------
# verify_block_window
# ---------------------------------------------------------------------------


class TestVerifyBlockWindow:
    @pytest.fixture(scope="class")
    def fx(self):
        return build_chain(n_vals=4, n_heights=12, chain_id="vbw-chain")

    def _blocks(self, fx):
        return [fx.block_store.load_block(h) for h in range(1, fx.height + 1)]

    def test_valid_window_verifies_all(self, fx):
        st = state_from_genesis(fx.genesis)
        blocks = self._blocks(fx)
        n_ok, err = verify_block_window(st, blocks)
        assert err is None
        assert n_ok == len(blocks) - 1

    def test_tampered_signature_detected_at_offset(self, fx):
        st = state_from_genesis(fx.genesis)
        blocks = self._blocks(fx)  # load_block returns fresh objects
        pc = blocks[5].last_commit.precommits[0]
        blocks[5].last_commit.precommits[0] = dataclasses.replace(
            pc, signature=b"\x00" * 64
        )
        n_ok, err = verify_block_window(st, blocks)
        assert n_ok == 4
        assert err is not None and err.bad_index == 4

    def test_commit_for_wrong_block_rejected(self, fx):
        st = state_from_genesis(fx.genesis)
        blocks = self._blocks(fx)
        # point block 3's commit at a bogus block id
        blocks[3].last_commit.block_id = dataclasses.replace(
            blocks[3].last_commit.block_id, hash=b"\xde" * 32
        )
        n_ok, err = verify_block_window(st, blocks)
        assert n_ok == 2
        assert err is not None and err.bad_index == 2

    def test_insufficient_quorum_rejected(self, fx):
        st = state_from_genesis(fx.genesis)
        blocks = self._blocks(fx)
        # keep only 2 of 4 precommits (20 of 40 power: not > 2/3)
        pcs = blocks[8].last_commit.precommits
        pcs[2] = None
        pcs[3] = None
        n_ok, err = verify_block_window(st, blocks)
        assert n_ok == 7
        assert err is not None and "voting power" in str(err)

    def test_single_block_window_verifies_nothing(self, fx):
        st = state_from_genesis(fx.genesis)
        blocks = self._blocks(fx)[:1]
        n_ok, err = verify_block_window(st, blocks)
        assert (n_ok, err) == (0, None)

    def test_window_truncates_at_valset_change_and_full_chain_applies(self):
        """Fast-sync through validator-set churn: a window spanning a valset
        change must truncate at the boundary (not fail), and the verify→
        apply pipeline must walk the whole chain — re-verifying post-change
        heights under the NEW set (reactor.go:306 semantics across sets)."""
        import base64

        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.crypto.keys import PrivKeyEd25519
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.types import BlockID, MockPV

        joiners = [MockPV(PrivKeyEd25519.generate(bytes([77 + i]) * 32))
                   for i in range(2)]

        def on_height(h, st):
            if h == 5:  # takes effect at h7 (height + 2)
                return [
                    b"val:" + base64.b64encode(pv.get_pub_key().bytes())
                    + b"!50"
                    for pv in joiners
                ]
            return []

        fx = build_chain(
            n_vals=4, n_heights=12, chain_id="churn-sync",
            app_factory=PersistentKVStoreApp, on_height=on_height,
            extra_pvs=joiners,
        )
        blocks = [fx.block_store.load_block(h) for h in range(1, 13)]

        # fresh executor from genesis, one big window over everything
        st = state_from_genesis(fx.genesis)
        db = MemDB()
        sm_store.save_state(db, st)
        conn = MultiAppConn(LocalClientCreator(PersistentKVStoreApp()))
        conn.start()
        block_exec = BlockExecutor(db, conn.consensus)

        applied = 0
        pos = 0
        rounds = 0
        while pos < len(blocks) - 1:
            window = blocks[pos:]
            parts_list = []
            n_ok, err = verify_block_window(
                st, window, parts_out=parts_list
            )
            assert err is None, f"round {rounds}: {err}"
            assert n_ok > 0
            if pos == 0:
                # the valset changes at height 7: the first window (heights
                # 1..12) must truncate to exactly 6 verified blocks
                assert n_ok == 6, n_ok
            for i in range(n_ok):
                block = window[i]
                block_id = BlockID(
                    hash=block.hash(), parts_header=parts_list[i].header()
                )
                st = block_exec.apply_block(
                    st, block_id, block, trusted_last_commit=True
                )
                applied += 1
            pos += n_ok
            rounds += 1
        assert applied == 11  # the final block's commit lives in block 13
        assert st.validators.size == 6  # churn really happened
        assert rounds >= 2  # pipeline crossed the valset boundary


    # -- what ended a window's run of heights: one count a call, and the
    # precheck span says the same

    @pytest.mark.parametrize("case,want_n,want_cut", [
        ("whole", 11, "none"),
        ("valset_change", 6, "valset_change"),
        ("behind_a_cut", 0, "valset_change"),
        ("structural", 2, "structural"),
    ])
    def test_window_cut_is_counted_by_reason(
            self, fx, tracing, verify_counters, case, want_n, want_cut):
        import base64

        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.crypto.keys import PrivKeyEd25519
        from tendermint_tpu.types import MockPV

        def cuts():
            return {r: verify_counters("tendermint_verify_window_cut_total",
                                       {"reason": r})
                    for r in ("valset_change", "structural", "none")}

        if case in ("valset_change", "behind_a_cut"):
            joiner = MockPV(PrivKeyEd25519.generate(bytes([93]) * 32))
            churn = build_chain(
                n_vals=4, n_heights=12, chain_id="cut-churn",
                app_factory=PersistentKVStoreApp, extra_pvs=[joiner],
                on_height=lambda h, st: [b"val:" + base64.b64encode(
                    joiner.get_pub_key().bytes()) + b"!50"] if h == 5 else [])
            st = state_from_genesis(churn.genesis)
            blocks = [churn.block_store.load_block(h) for h in range(1, 13)]
            if case == "behind_a_cut":
                blocks = blocks[6:]  # height 7 on: the next set's, not the state's
        else:
            st = state_from_genesis(fx.genesis)
            blocks = self._blocks(fx)
            if case == "structural":
                blocks[3].last_commit.block_id = dataclasses.replace(
                    blocks[3].last_commit.block_id, hash=b"\xde" * 32)
        tracing.reset()
        before = cuts()
        n_ok, err = verify_block_window(st, blocks)
        assert n_ok == want_n
        assert (err is None) == (case in ("whole", "valset_change"))
        grown = {k: v - before[k] for k, v in cuts().items()}
        assert grown == {r: float(r == want_cut) for r in grown}
        (pre,) = [e for e in tracing.export() if e.get("name") == "fastsync.precheck"]
        assert pre["args"]["cut"] == want_cut and pre["args"]["n"] == want_n


# ---------------------------------------------------------------------------
# BlockPool
# ---------------------------------------------------------------------------


class _FakeBlock:
    def __init__(self, height):
        self.height = height


class TestBlockPool:
    def _pool(self, start=1, timeout=0.3):
        requests = []
        errors = []
        pool = BlockPool(
            start_height=start,
            request_cb=lambda h, p: requests.append((h, p)),
            error_cb=lambda p, r: errors.append((p, r)),
            request_timeout=timeout,
        )
        pool.start()
        return pool, requests, errors

    def test_requests_fan_out_and_blocks_flow(self):
        pool, requests, errors = self._pool()
        try:
            pool.set_peer_height("peerA", 10)
            assert wait_for(lambda: len(requests) >= 10, timeout=5)
            assert {h for h, _ in requests} == set(range(1, 11))
            for h, peer in requests:
                assert pool.add_block(peer, _FakeBlock(h))
            window = pool.peek_window(100)
            assert [b.height for b in window] == list(range(1, 11))
            for _ in range(10):
                pool.pop_first()
            assert pool.is_caught_up()
            assert not errors
        finally:
            pool.stop()

    def test_a_pass_is_spanned_only_if_it_sent_or_failed_something(self, tracing):
        """An idle pass (100 a second on a node at the tip) records nothing;
        a sending one draws one ``pool.schedule`` with its ``sends``."""
        requests = []
        pool = BlockPool(
            start_height=1, request_cb=lambda h, p: requests.append((h, p)),
            error_cb=lambda p, r: None)  # not started: passes made by hand
        for _ in range(50):
            pool._schedule_pass()  # no peer, nothing to ask for
        assert requests == [] and len(tracing.get_tracer()) == 0
        pool.set_peer_height("peerA", 7)
        pool._schedule_pass()
        assert sorted(h for h, _ in requests) == list(range(1, 8))
        for _ in range(50):
            pool._schedule_pass()  # all seven are in flight: idle again
        (ev,) = [e for e in tracing.export() if e.get("ph") == "X"]
        assert ev["name"] == "pool.schedule"
        assert (ev["args"]["sends"], ev["args"]["errors"]) == (7, 0)
        assert ev["args"]["parent_id"] is None and "cpu_ms" in ev["args"]

    def test_unsolicited_block_rejected(self):
        pool, requests, _ = self._pool()
        try:
            pool.set_peer_height("peerA", 5)
            assert wait_for(lambda: len(requests) >= 5, timeout=5)
            assert not pool.add_block("stranger", _FakeBlock(1))
            assert not pool.add_block("peerA", _FakeBlock(99))
        finally:
            pool.stop()

    def test_timeout_reassigns_and_reports_peer(self):
        pool, requests, errors = self._pool(timeout=0.2)
        try:
            pool.set_peer_height("slow", 3)
            assert wait_for(lambda: len(requests) >= 3, timeout=5)
            # never respond; a second peer appears
            pool.set_peer_height("fast", 3)
            assert wait_for(
                lambda: any(p == "slow" for p, _ in errors), timeout=5
            ), "slow peer never reported"
            assert wait_for(
                lambda: any(p == "fast" for _, p in requests), timeout=5
            ), "requests never reassigned"
        finally:
            pool.stop()

    def test_redo_request_identifies_bad_peer(self):
        pool, requests, _ = self._pool()
        try:
            pool.set_peer_height("badpeer", 2)
            assert wait_for(lambda: len(requests) >= 2, timeout=5)
            assert pool.add_block("badpeer", _FakeBlock(1))
            assert pool.redo_request(1) == "badpeer"
            assert pool.peek_window(10) == []
        finally:
            pool.stop()


# ---------------------------------------------------------------------------
# Full fast-sync integration: late joiner catches a live single-val chain
# ---------------------------------------------------------------------------


def _make_serving_node(fx):
    """A node that serves fx's chain over the blockchain channel (its own
    consensus idle — the chain's validators aren't running)."""
    state_db = MemDB()
    sm_store.save_state(state_db, fx.state)
    conn = MultiAppConn(LocalClientCreator(KVStoreApp()))
    conn.start()
    block_exec = BlockExecutor(state_db, conn.consensus)
    return BlockchainReactor(
        fx.state.copy(), block_exec, fx.block_store, fast_sync=False
    )


def _make_syncing_node(genesis):
    st = state_from_genesis(genesis)
    state_db = MemDB()
    sm_store.save_state(state_db, st)
    conn = MultiAppConn(LocalClientCreator(KVStoreApp()))
    conn.start()
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.state.services import MockEvidencePool
    from tendermint_tpu.types.events import EventBus
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.consensus.state import ConsensusState

    mempool = Mempool(conn.mempool)
    evpool = MockEvidencePool()
    store = BlockStore(MemDB())
    bus = EventBus()
    bus.start()
    block_exec = BlockExecutor(state_db, conn.consensus, mempool, evpool, bus)
    cs = ConsensusState(
        test_config().consensus, st.copy(), block_exec, store, mempool, evpool
    )
    cs.set_event_bus(bus)
    cons_reactor = ConsensusReactor(cs, fast_sync=True)
    bc_reactor = BlockchainReactor(
        st.copy(), block_exec, store, fast_sync=True, consensus_reactor=cons_reactor
    )
    return bc_reactor, cons_reactor, store


def _patient_mconfig():
    """The test switches' connection settings with a keepalive that a loaded
    machine can meet.  ``MConnConfig.test_config()`` drops a connection whose
    pong is 0.35 s late; both nodes of these tests share one interpreter with
    two consensus states and a sync loop, under six test workers, and nothing
    redials: a joiner that has lost its only peer never reads "caught up" and
    stays in fast sync for good (the driver's run of PR 32's tree lost
    ``test_live_producer_late_joiner_follows`` that way, at its first 60 s
    wait; a pong timeout of 0.5 ms reproduces the same line).  What the tests
    assert is fast sync and the hand-over to consensus, not the keepalive."""
    from tendermint_tpu.p2p.conn.connection import MConnConfig

    return dataclasses.replace(MConnConfig.test_config(), pong_timeout=30.0)


class TestFastSyncIntegration:
    def test_late_joiner_syncs_chain_and_switches_to_consensus(self):
        from tendermint_tpu.p2p.test_util import make_connected_switches

        fx = build_chain(n_vals=4, n_heights=30, chain_id="sync-chain")
        server = _make_serving_node(fx)
        bc, cons, store = _make_syncing_node(fx.genesis)

        reactors = [
            lambda sw: sw.add_reactor("blockchain", server),
            lambda sw: (sw.add_reactor("blockchain", bc), sw.add_reactor("consensus", cons)),
        ]
        switches = make_connected_switches(
            2, lambda i, sw: (reactors[i](sw), sw)[1], network="sync-chain",
            mconfig=_patient_mconfig(),
        )
        try:
            # syncs 29 of 30 blocks (the tip's commit lives in the future),
            # then flips to consensus mode
            assert wait_for(lambda: store.height() >= 29, timeout=60), store.height()
            assert wait_for(lambda: not bc.fast_sync, timeout=30)
            assert wait_for(lambda: cons.cons.is_running, timeout=30)
            assert cons.cons.get_round_state().height == 30
            assert bc.blocks_synced >= 29
            # synced chain matches the source chain byte for byte
            assert (
                store.load_block(29).hash() == fx.block_store.load_block(29).hash()
            )
        finally:
            for sw in switches:
                if sw.is_running:
                    sw.stop()

    def test_live_producer_late_joiner_follows(self):
        """Producer keeps committing while the joiner syncs; after switching
        to consensus the joiner follows new heights via consensus gossip."""
        from tendermint_tpu.p2p.test_util import make_connected_switches
        from tests.consensus_harness import make_genesis

        from tendermint_tpu.config.config import test_config

        doc, pvs = make_genesis(1)
        # producer: real single-validator consensus + serving blockchain
        # reactor, paced at ~5 blocks/s (a solo skip_timeout_commit producer
        # outruns any follower by orders of magnitude)
        cfg = test_config()
        cfg.consensus.skip_timeout_commit = False
        cfg.consensus.timeout_commit = 0.2
        st0 = state_from_genesis(doc)
        by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
        sorted_pvs = [by_addr[v.address] for v in st0.validators.validators]
        prod_cs, prod_bus = make_cs_from_genesis(doc, sorted_pvs[0], config=cfg)
        prod_cons = ConsensusReactor(prod_cs)
        prod_bc = BlockchainReactor(
            prod_cs.get_state(), prod_cs.block_exec, prod_cs.block_store,
            fast_sync=False,
        )
        # joiner
        bc, cons, store = _make_syncing_node(doc)

        builders = [
            lambda sw: (sw.add_reactor("consensus", prod_cons),
                        sw.add_reactor("blockchain", prod_bc)),
            lambda sw: (sw.add_reactor("consensus", cons),
                        sw.add_reactor("blockchain", bc)),
        ]
        switches = make_connected_switches(
            2, lambda i, sw: (builders[i](sw), sw)[1], network=doc.chain_id,
            mconfig=_patient_mconfig(),
        )
        try:
            # producer commits on its own
            assert wait_for(
                lambda: prod_cs.get_round_state().height >= 8, timeout=60
            )
            # joiner syncs and then follows the live chain
            assert wait_for(lambda: not bc.fast_sync, timeout=60)
            assert wait_for(lambda: cons.cons.is_running, timeout=30)
            target = prod_cs.get_round_state().height + 3
            assert wait_for(
                lambda: store.height() >= target - 1, timeout=60
            ), (store.height(), prod_cs.get_round_state().height)
        finally:
            for sw in switches:
                if sw.is_running:
                    sw.stop()
            prod_bus.stop()


class TestPipelinedVerify:
    """SURVEY §2.4 pipelining: window N+1's verify dispatch runs on the
    reactor's worker while window N is being applied — observed here by
    gating the second verify call and watching the store advance past
    window N while the gate is still closed."""

    def _direct_reactor(self, fx, window, verifier, app_factory=KVStoreApp):
        st = state_from_genesis(fx.genesis)
        db = MemDB()
        sm_store.save_state(db, st)
        conn = MultiAppConn(LocalClientCreator(app_factory()))
        conn.start()
        store = BlockStore(MemDB())
        bc = BlockchainReactor(
            st, BlockExecutor(db, conn.consensus), store,
            verifier=verifier, verify_window=window,
        )
        # hand the pool every block directly (no switch needed to exercise
        # the sync loop synchronously from this thread)
        from tendermint_tpu.blockchain.pool import _Request

        for h in range(1, fx.height + 1):
            bc.pool._requests[h] = _Request(
                height=h, block=fx.block_store.load_block(h)
            )
        return bc, store

    def test_speculative_verify_overlaps_apply(self):
        fx = build_chain(n_vals=4, n_heights=12, chain_id="pipe-chain")

        class GatedVerifier:
            """Call 1 passes through; call 2 (the speculative window)
            blocks until released."""

            def __init__(self):
                self.calls = 0
                self.started2 = threading.Event()
                self.release2 = threading.Event()

            def verify_ed25519(self, items):
                import numpy as np

                self.calls += 1
                if self.calls == 2:
                    self.started2.set()
                    assert self.release2.wait(20), "never released"
                return np.ones((len(items),), dtype=bool)

            verify_secp256k1 = verify_ed25519

        gv = GatedVerifier()
        bc, store = self._direct_reactor(fx, window=4, verifier=gv)
        # pass 1: verifies blocks 1..4, dispatches speculation for 5..8,
        # then applies 1..4 — all while call 2 sits at the gate
        bc._try_sync_window()
        assert gv.started2.wait(10), "speculative verify never dispatched"
        assert store.height() >= 4, (
            "apply did not proceed while the speculative verify was in "
            f"flight (store at {store.height()})"
        )
        assert bc._spec is not None
        gv.release2.set()
        # pass 2 harvests the speculation (no third verify needed for it)
        bc._try_sync_window()
        assert store.height() >= 8
        # drain the rest of the chain
        for _ in range(4):
            bc._try_sync_window()
        assert store.height() == fx.height - 1  # tip's commit is in the future
        bc.on_stop()

    def test_speculation_discarded_on_valset_change(self):
        """A valset change during window N invalidates the speculative
        window N+1 result — it must be re-verified, never punished off the
        stale 'wrong validators_hash' verdict."""
        import base64

        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.crypto.keys import PrivKeyEd25519
        from tendermint_tpu.types import MockPV

        joiner = MockPV(PrivKeyEd25519.generate(bytes([91]) * 32))

        def on_height(h, st):
            if h == 4:  # takes effect at h6 (height + 2) — mid window 2
                return [
                    b"val:" + base64.b64encode(joiner.get_pub_key().bytes())
                    + b"!50"
                ]
            return []

        fx = build_chain(
            n_vals=4, n_heights=12, chain_id="pipe-churn",
            app_factory=PersistentKVStoreApp, on_height=on_height,
            extra_pvs=[joiner],
        )

        class CountingVerifier:
            calls = 0

            def verify_ed25519(self, items):
                import numpy as np

                CountingVerifier.calls += 1
                return np.ones((len(items),), dtype=bool)

            verify_secp256k1 = verify_ed25519

        punished = []
        bc, store = self._direct_reactor(
            fx, window=4, verifier=CountingVerifier(),
            app_factory=PersistentKVStoreApp,
        )
        bc._stop_peer_by_id = lambda pid, reason: punished.append((pid, reason))
        for _ in range(8):
            bc._try_sync_window()
        assert store.height() == fx.height - 1
        assert punished == []  # stale speculation never punished anyone
        bc.on_stop()

    # -- what the sync loop says about itself: how each look ended (counter),
    # how long a harvest stood waiting and what a discarded speculation cost
    # (spans, per window)

    class _AcceptAll:
        def verify_ed25519(self, items):
            import numpy as np

            return np.ones((len(items),), dtype=bool)

        verify_secp256k1 = verify_ed25519

    def test_ticks_and_harvest_spans(self, tracing, verify_counters):
        def ticks():
            return {r: verify_counters("tendermint_verify_sync_ticks_total",
                                       {"result": r})
                    for r in ("window", "harvest", "empty")}

        fx = build_chain(n_vals=4, n_heights=12, chain_id="tick-chain")
        bc, store = self._direct_reactor(fx, window=4, verifier=self._AcceptAll())
        tracing.reset()  # building the chain verified a commit a block
        before = ticks()
        for _ in range(5):
            bc._try_sync_window()
        assert store.height() == fx.height - 1
        bc.on_stop()
        grown = {k: v - before[k] for k, v in ticks().items()}
        # 1..4 in line, 5..8 and 9..11 from the worker, then nothing to do
        assert grown == {"window": 1.0, "harvest": 2.0, "empty": 2.0}
        spans = [e for e in tracing.export() if e.get("ph") == "X"]
        harvests = [e for e in spans if e["name"] == "fastsync.harvest"]
        assert [e["args"]["h0"] for e in harvests] == [5, 9]
        assert all(e["args"]["hit"] is True for e in harvests)
        assert not [e for e in spans if e["name"] == "fastsync.discard"]
        # per window, never per block: 11 blocks applied, a handful of spans
        assert len([e for e in spans if e["name"] == "fastsync.precheck"]) == 3
        assert len(spans) <= 3 * 16

    def test_one_cycle_tree_a_look_that_did_something(
            self, tracing, verify_counters, monkeypatch):
        """``_sync_cycle`` (a turn of the pool routine) draws one
        ``fastsync.cycle`` a non-empty look, with its ``fastsync.tick``, and
        the loop's own spans of that look are its descendants."""
        from tendermint_tpu.blockchain import reactor as reactor_mod

        monkeypatch.setattr(reactor_mod, "TRY_SYNC_INTERVAL", 0.0)
        fx = build_chain(n_vals=4, n_heights=12, chain_id="cycle-chain")
        bc, store = self._direct_reactor(fx, window=4, verifier=self._AcceptAll())
        tracing.reset()
        for _ in range(5):  # 1..4 in line, 5..8 and 9..11 harvested, 2 empty
            assert bc._sync_cycle() is True
        assert store.height() == fx.height - 1
        bc.on_stop()
        spans = [e for e in tracing.export() if e.get("ph") == "X"]
        by_id = {e["args"]["span_id"]: e for e in spans}
        cycles = [e for e in spans if e["name"] == "fastsync.cycle"]
        assert [(c["args"]["result"], c["args"]["n"]) for c in cycles] == [
            ("window", 4), ("harvest", 4), ("harvest", 3)]
        assert all(c["args"]["parent_id"] is None for c in cycles)

        def children(parent, name=None):
            return [e for e in spans
                    if e["args"]["parent_id"] == parent["args"]["span_id"]
                    and name in (None, e["name"])]

        for c in cycles:
            (tick,) = children(c, "fastsync.tick")
            assert tick["args"]["after"] == c["args"]["result"]
            (apply_,) = children(c, "fastsync.apply")
            assert apply_["args"]["n"] == c["args"]["n"]
            (release,) = children(c, "fastsync.release")
            assert apply_["ts"] + apply_["dur"] <= release["ts"] <= tick["ts"]
            # whatever this thread drew inside the look belongs to its cycle
            assert all(e["args"]["root_id"] == c["args"]["span_id"]
                       for e in spans if e["tid"] == c["tid"]
                       and c["ts"] <= e["ts"] < c["ts"] + c["dur"])
        first, second, third = cycles
        (peek,) = children(first, "fastsync.peek")
        assert peek["args"]["n"] == 4
        (window,) = children(first, "fastsync.window")
        assert window["args"]["mode"] == "sync" and not children(first, "fastsync.take")
        for c, h0 in ((second, 5), (third, 9)):
            (take,) = children(c, "fastsync.take")
            (harvest,) = children(take, "fastsync.harvest")
            assert harvest["args"]["h0"] == h0 and not children(c, "fastsync.peek")
        # the first two looks start the next speculation, the last finds none
        assert [children(c, "fastsync.speculate")[0]["args"]["started"]
                for c in cycles] == [1, 1, 0]
        # the speculative windows stay roots on their own thread
        spec = [e for e in spans if e["name"] == "fastsync.window"
                and e["args"]["mode"] == "speculative"]
        assert len(spec) == 2 and all(e["args"]["parent_id"] is None for e in spec)
        assert {e["tid"] for e in spec}.isdisjoint({c["tid"] for c in cycles})
        # nothing else is a root on the loop's thread: every span has a
        # recorded parent or is a cycle or a speculative window
        assert all(e["args"]["parent_id"] in by_id or e in cycles or e in spec
                   for e in spans)

    def test_empty_looks_record_nothing_and_are_counted(
            self, tracing, verify_counters, monkeypatch):
        from tendermint_tpu.blockchain import reactor as reactor_mod

        monkeypatch.setattr(reactor_mod, "TRY_SYNC_INTERVAL", 0.0)
        fx = build_chain(n_vals=4, n_heights=3, chain_id="idle-chain")
        bc, _store = self._direct_reactor(fx, window=4, verifier=self._AcceptAll())
        bc.pool._requests.clear()  # a node at the tip: nothing ready
        tracing.reset()
        family = ("tendermint_verify_sync_ticks_total", {"result": "empty"})
        before = verify_counters(*family)
        for _ in range(200):
            assert bc._sync_cycle() is True
        assert verify_counters(*family) - before == 200
        assert len(tracing.get_tracer()) == 0 and tracing.dropped() == 0

    def test_the_loop_with_tracing_off_builds_no_span_and_reads_no_cpu_clock(
            self, no_tracing, monkeypatch):
        from tendermint_tpu.blockchain import reactor as reactor_mod

        monkeypatch.setattr(reactor_mod, "TRY_SYNC_INTERVAL", 0.0)
        fx = build_chain(n_vals=4, n_heights=12, chain_id="off-loop")
        bc, store = self._direct_reactor(fx, window=4, verifier=self._AcceptAll())
        for _ in range(5):
            assert bc._sync_cycle() is True
        bc.pool._schedule_pass()
        assert store.height() == fx.height - 1
        bc.on_stop()

    @pytest.mark.parametrize("traced", [False, True])
    def test_block_intake_is_observed_once_a_block_received(
            self, traced, verify_counters, request):
        """``tendermint_verify_block_intake_seconds``: one observation a
        ``BlockResponseMessage`` in ``receive``, tracing on or off, and none
        for any other message."""
        from tendermint_tpu.blockchain.messages import (
            BlockResponseMessage, StatusResponseMessage, encode_msg)

        if traced:
            request.getfixturevalue("tracing")
        fx = build_chain(n_vals=4, n_heights=6, chain_id="intake-chain")
        bc, _store = self._direct_reactor(fx, window=4, verifier=self._AcceptAll())
        bc.pool._requests.clear()

        class Peer:
            id = "peerA"

        family = "tendermint_verify_block_intake_seconds"
        before = (verify_counters(family + "_count"), verify_counters(family + "_sum"))
        bc.receive(0x40, Peer(), encode_msg(StatusResponseMessage(6)))
        for h in range(1, 6):
            bc.receive(0x40, Peer(), encode_msg(
                BlockResponseMessage(fx.block_store.load_block(h))))
        assert verify_counters(family + "_count") - before[0] == 5
        assert verify_counters(family + "_sum") > before[1]

    def test_discarded_speculation_is_spanned(self, tracing):
        import base64

        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.crypto.keys import PrivKeyEd25519
        from tendermint_tpu.types import MockPV

        joiner = MockPV(PrivKeyEd25519.generate(bytes([92]) * 32))

        def on_height(h, st):
            if h == 4:  # in force from height 6: inside the second window
                return [b"val:" + base64.b64encode(
                    joiner.get_pub_key().bytes()) + b"!50"]
            return []

        fx = build_chain(
            n_vals=4, n_heights=12, chain_id="tick-churn",
            app_factory=PersistentKVStoreApp, on_height=on_height,
            extra_pvs=[joiner],
        )
        bc, store = self._direct_reactor(
            fx, window=4, verifier=self._AcceptAll(),
            app_factory=PersistentKVStoreApp,
        )
        for _ in range(8):
            bc._try_sync_window()
        assert store.height() == fx.height - 1
        bc.on_stop()
        discards = [e for e in tracing.export()
                    if e.get("name") == "fastsync.discard"]
        assert discards, "the valset change voided no speculation"
        for e in discards:
            assert e["args"]["slots"] >= 1 and e["args"]["heights"] >= 1

    def test_valset_change_is_spanned_and_counted(self, tracing, verify_counters):
        """A block whose apply changed the state's set draws one
        ``fastsync.valset_change`` saying who joined, left and changed power,
        and the speculation it voids says why it went and that it drained."""
        import base64

        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.crypto.keys import PrivKeyEd25519
        from tendermint_tpu.types import MockPV

        joiner = MockPV(PrivKeyEd25519.generate(bytes([94]) * 32))

        def val(pub, power):
            return b"val:" + base64.b64encode(pub.bytes()) + b"!%d" % power

        def on_height(h, st):
            sitting = [v.pub_key for v in st.next_validators.validators]
            if h == 4:  # in force from height 6
                return [val(joiner.get_pub_key(), 50)]
            if h == 7:  # in force from height 9: one leaves, one re-powered
                leaver = next(p for p in sitting if p != joiner.get_pub_key())
                return [val(leaver, 0), val(joiner.get_pub_key(), 20)]
            return []

        fx = build_chain(
            n_vals=4, n_heights=12, chain_id="change-span",
            app_factory=PersistentKVStoreApp, on_height=on_height,
            extra_pvs=[joiner],
        )
        bc, store = self._direct_reactor(
            fx, window=4, verifier=self._AcceptAll(),
            app_factory=PersistentKVStoreApp,
        )
        tracing.reset()
        before = (verify_counters("tendermint_verify_valset_changes_total"),
                  verify_counters("tendermint_verify_speculative_total",
                                  {"outcome": "miss"}))
        for _ in range(10):
            bc._try_sync_window()
        assert store.height() == fx.height - 1
        bc.on_stop()
        assert verify_counters("tendermint_verify_valset_changes_total") - before[0] == 2
        spans = [e for e in tracing.export() if e.get("ph") == "X"]
        changes = [e["args"] for e in spans if e["name"] == "fastsync.valset_change"]
        assert [(a["h"], a["added"], a["removed"], a["repowered"]) for a in changes] == [
            (5, 1, 0, 0), (8, 0, 1, 1)]
        discards = [e["args"] for e in spans if e["name"] == "fastsync.discard"]
        assert discards and all(
            a["reason"] == "valset_change" and a["drained"] is True for a in discards)
        missed = verify_counters("tendermint_verify_speculative_total",
                                 {"outcome": "miss"}) - before[1]
        assert missed == sum(a["slots"] for a in discards)

    def test_discard_does_not_wait_for_ever_for_a_speculation(
            self, tracing, monkeypatch, caplog):
        """ROADMAP D13: a thrown-away speculation whose verify never returns
        (a wedged device) holds the sync loop for DRAIN_TIMEOUT and no
        longer; the loop says so and verifies the window itself."""
        from tendermint_tpu.blockchain import reactor as reactor_mod

        monkeypatch.setattr(reactor_mod, "DRAIN_TIMEOUT", 0.3, raising=False)
        fx = build_chain(n_vals=4, n_heights=12, chain_id="wedged-chain")

        class Wedged(self._AcceptAll):
            """Call 2, the speculation, never answers."""

            def __init__(self):
                self.calls = 0
                self.started2 = threading.Event()
                self.release = threading.Event()

            def verify_ed25519(self, items):
                self.calls += 1
                if self.calls == 2:
                    self.started2.set()
                    self.release.wait(60)
                return super().verify_ed25519(items)

            verify_secp256k1 = verify_ed25519

        wv = Wedged()
        bc, store = self._direct_reactor(fx, window=4, verifier=wv)
        try:
            bc._try_sync_window()  # verifies and applies 1..4, speculates on 5..8
            assert wv.started2.wait(10) and store.height() == 4
            # the pool is no longer where the speculation starts
            first_h, *rest = bc._spec[0]
            bc._spec[0] = (first_h + 1, *rest)
            done = threading.Event()

            def look():
                bc._try_sync_window()
                done.set()

            t0 = time.monotonic()
            threading.Thread(target=look, daemon=True).start()
            assert done.wait(10), "the sync loop is still waiting for the speculation"
            assert 0.3 <= time.monotonic() - t0 < 5.0
            assert store.height() == 8  # verified in line and applied
            (discard,) = [e["args"] for e in tracing.export()
                          if e.get("name") == "fastsync.discard"]
            assert discard["reason"] == "height" and discard["drained"] is False
            assert any("did not drain" in r.getMessage() for r in caplog.records)
        finally:
            wv.release.set()
            bc.on_stop()


class TestVerifyBlockWindowSharded:
    """The mesh path: the same window flows through parallel/commit_verify,
    sharded (heights × validators) over the virtual 8-device mesh — the
    multi-chip production path fast sync runs with `mesh=` configured."""

    @pytest.fixture(scope="class")
    def mesh(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devs = np.array(jax.devices("cpu"))
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        return Mesh(devs[:8].reshape(2, 4), ("height", "val"))

    @pytest.fixture(scope="class")
    def fx(self):
        return build_chain(n_vals=4, n_heights=10, chain_id="vbw-mesh")

    def test_matches_flat_path_on_valid_chain(self, fx, mesh):
        st = state_from_genesis(fx.genesis)
        blocks = [fx.block_store.load_block(h) for h in range(1, 11)]
        parts_flat, parts_mesh = [], []
        flat = verify_block_window(st, blocks, parts_out=parts_flat)
        sharded = verify_block_window(st, blocks, parts_out=parts_mesh, mesh=mesh)
        assert flat[0] == sharded[0] == 9
        assert flat[1] is None and sharded[1] is None
        assert [p.header() for p in parts_flat] == [p.header() for p in parts_mesh]

    def test_detects_tamper_like_flat_path(self, fx, mesh):
        st = state_from_genesis(fx.genesis)
        blocks = [fx.block_store.load_block(h) for h in range(1, 11)]
        pc = blocks[4].last_commit.precommits[1]
        blocks[4].last_commit.precommits[1] = dataclasses.replace(
            pc, signature=b"\x00" * 64
        )
        n_ok, err = verify_block_window(st, blocks, mesh=mesh)
        assert n_ok == 3 and err is not None and err.bad_index == 3

    def test_quorum_failure_detected(self, fx, mesh):
        st = state_from_genesis(fx.genesis)
        blocks = [fx.block_store.load_block(h) for h in range(1, 11)]
        pcs = blocks[6].last_commit.precommits
        pcs[0] = None
        pcs[1] = None
        n_ok, err = verify_block_window(st, blocks, mesh=mesh)
        assert n_ok == 5 and err is not None and "voting power" in str(err)
