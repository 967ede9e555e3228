"""Fast sync of a chain that carries transactions, from genesis, by a fresh
``BlockchainReactor`` again and again for the window.

``reactor_sync``'s peers, clock, warm-up, loop and checks, with what the
deployment ``fastsync-64v-full`` changes: the in-process app is the
program's ``UpstreamKVStoreApp`` (the reference's Commit: the app hash is
the varint of the number of txs delivered), the chain is
``benchmark/chaingen_full``'s (every block ``txs_per_block`` txs of
``tx_bytes`` bytes, each header held to ``benchmark/kvstore_reference``), and
a whole sync also answers for what the transactions left behind: the app's
``size`` and hash, seeded keys through its Query, the saved ABCI responses
and the stored blocks of seeded heights.  This file loads a ``reactor_sync``
of its own and puts its ``Sync`` and its forged-precommit sync (the same
forgery, waited for longer) in that copy's place; the accepted cells' copies
are other module objects and are not touched.

Traffic parameters: ``blocks``, ``txs_per_block``, ``tx_bytes`` and
``reactor_sync``'s ``warmup_window_heights``, ``warmup_syncs``,
``sync_timeout_s``, ``forged_timeout_s``.
"""

from __future__ import annotations

import gc
import os
import time
from collections import Counter

from benchmark import chaingen, chaingen_full
from benchmark import kvstore_reference as ref
from benchmark.harness import (
    Bench,
    check_equal,
    counter_sum,
    counters_delta,
    counters_snapshot,
    guard_events,
)

base = Bench(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))).module("drivers", "reactor_sync")

STAGES = "tendermint_state_block_stage_seconds"
DELIVERED = "tendermint_state_txs_delivered_total"

# what a whole sync is asked about its transactions (``FullChain.misses``)
ASKED = ("app_size_and_hash", "query_keys", "abci_responses", "stored_blocks")


class Sync(base.Sync):
    """One sync: fresh state, app, stores, reactor and peers."""

    def __init__(self, ctx, chain, responses=None, target=None):
        from tendermint_tpu.abci.examples.kvstore import UpstreamKVStoreApp
        from tendermint_tpu.blockchain.reactor import BlockchainReactor
        from tendermint_tpu.blockchain.store import BlockStore
        from tendermint_tpu.libs.db.kv import MemDB
        from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
        from tendermint_tpu.state import store as sm_store
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.state_types import state_from_genesis

        responses = chain.responses if responses is None else responses
        st = state_from_genesis(chain.genesis())
        self.state_db = MemDB()
        sm_store.save_state(self.state_db, st)
        self.app = UpstreamKVStoreApp()
        self.conn = MultiAppConn(LocalClientCreator(self.app))
        self.conn.start()
        self.store = BlockStore(MemDB())
        self.target = len(responses) - 1 if target is None else target
        self.exec = base.TimedExecutor(
            BlockExecutor(self.state_db, self.conn.consensus), self.store,
            self.target)
        self.reactor = BlockchainReactor(st, self.exec, self.store, fast_sync=True)
        self.net = base.PeerNet(
            self.reactor, responses, tip=len(responses),
            n_peers=int(ctx.config["peers"]))
        self.reactor.set_switch(self.net)
        self.t_start = 0.0

    def verify_final(self, chain) -> int:
        """``reactor_sync``'s misses (height, app hash, validators hash,
        stored before applied) and what the transactions left behind,
        against the reference's answers."""
        from tendermint_tpu.abci import types as abci
        from tendermint_tpu.state import store as sm_store

        miss = Counter()
        info = self.conn.query.info_sync(abci.RequestInfo())
        miss["app_size_and_hash"] = (
            int(self.app.size != chain.size)
            + int(info.last_block_app_hash != chain.app_hash_reference)
            + int(info.last_block_height != chain.final_height))
        for key, value in chain.queries:
            res = self.conn.query.query_sync(
                abci.RequestQuery(path="/store", data=key))
            miss["query_keys"] += int(res.code != 0 or res.value != value)
        for h in chain.probe_heights:
            try:
                results = sm_store.load_abci_responses(self.state_db, h).deliver_tx
            except sm_store.NoABCIResponsesForHeightError:
                results = []
            miss["abci_responses"] += int(
                len(results) != chain.txs_per_block
                or any(r.code != 0 for r in results))
            block = self.store.load_block(h)
            miss["stored_blocks"] += int(
                block is None
                or block.marshal() != ref.block_bytes(chain.responses[h - 1]))
        chain.misses.update(miss)
        return super().verify_final(chain) + sum(miss.values())


base.Sync = Sync  # reactor_sync's loop and warm-up build this one

window = base.window


def warmup(ctx, state):
    """``reactor_sync.warmup``, then the heap frozen once more.  The harness
    freezes what set-up left BEFORE warm-up, so what warm-up leaves (the nine
    window programs' traces: 2.67 M objects on the chip) stays in the old
    generation, and every full collection walks it for 0.9-1.1 s.  Empty
    blocks promote next to nothing, so ``sync64-empty`` triggers none inside
    a sync; 1,000 txs a block trigger about two a sync, and whether a 51 s
    window holds nine or eleven of them is most of its run-to-run spread
    (0.052 and 0.147 in two sets, PERF.md 6).  Frozen here, warm-up's
    leftovers are never walked again, as the harness has it for set-up's;
    the garbage a sync makes is collected as usual, in many short full
    collections where it was a few long ones."""
    base.warmup(ctx, state)
    gc.collect()
    gc.freeze()


def setup(ctx):
    chain = chaingen_full.cached_chain(
        ctx.cache_dir, ctx.cell.config_name, ctx.cell.workload["traffic"],
        ctx.config, ctx.traffic, ctx.seed, ctx.log)
    return {"chain": chain, "final_misses": 0, "syncs": 0}


def _stopped_sync(ctx, chain, responses, height, what):
    """A sync of ``responses``, which carry a forgery that must stop it
    below ``height``: misses against "a supplier punished for block
    ``height``, the store at ``height - 1`` and holding nothing of
    ``height``, the app at the txs of the blocks below".  ``reactor_sync``'s
    forged-precommit sync with more patience: the verified blocks below the
    forgery are applied AFTER the punishment, and up to 126 full blocks take
    seconds where 126 empty ones take a tenth of one."""
    n = len(responses)
    sync = Sync(ctx, chain, responses=responses, target=n - 1)
    sync.start()
    patience = float(ctx.traffic["forged_timeout_s"])
    punished = sync.net.punished.wait(patience)
    t_end = time.perf_counter() + patience
    while sync.store.height() < height - 1 and time.perf_counter() < t_end:
        time.sleep(0.01)
    time.sleep(0.1)  # anything applied past the forgery would show by now
    sync.stop()
    applied = sync.store.height()
    stopped = list(sync.net.stopped)
    ctx.log(f"check: {what} {height} of {n}: store at {applied}, "
            f"app size {sync.app.size}, punished {stopped[:2]}")
    miss = int(not punished) + int(applied != height - 1)
    miss += int(not any(r.endswith(f"bad block {height}") for _p, r in stopped))
    miss += int(sync.store.load_block(height) is not None)
    miss += int(sync.app.size != chain.txs_per_block * (height - 1))
    return miss


def _seeded_height(ctx, chain, rng) -> int:
    """Among the heights the pool asks for at once."""
    return int(rng.integers(
        2, min(int(ctx.config["reactor"]["request_window"]),
               len(chain.responses)) - 1))


def _forged_sync(ctx, state, rng):
    """``reactor_sync._forged_sync``: the same seeded height and the same
    forged precommit (the commit for ``height`` travels in the next block),
    through ``_stopped_sync``."""
    chain = state["chain"]
    height = _seeded_height(ctx, chain, rng)
    responses = list(chain.responses)
    responses[height] = chaingen.forge_precommit(chain, height, rng)
    return _stopped_sync(ctx, chain, responses, height,
                         "forged precommit at height")


def _forged_tx_sync(ctx, chain, rng):
    """One bit of one byte of one tx altered in one block's bytes."""
    height = _seeded_height(ctx, chain, rng)
    responses = list(chain.responses)
    responses[height - 1] = chaingen_full.forge_tx(
        chain, ctx.seed, ctx.traffic, height, rng)
    return _stopped_sync(ctx, chain, responses, height, "forged tx in block")


base._forged_sync = _forged_sync


def check(ctx, state, win, data):
    """``reactor_sync``'s seven checks (its final-state check also holds
    what ``Sync.verify_final`` asks here), what each question missed over
    the whole syncs, the forged transaction, and the txs delivered a block
    applied in the window."""
    chain = state["chain"]
    checks = list(base.check(ctx, state, win, data))
    checks += [check_equal(f"syncs.{name}_vs_reference", chain.misses[name])
               for name in ASKED]
    before = counters_snapshot()
    checks.append(check_equal(
        "forged_tx.stops_and_punishes",
        _forged_tx_sync(ctx, chain, ctx.rng(3))))
    checks.append(check_equal(
        "forged_tx.fallbacks_and_audit_mismatches",
        guard_events(counters_delta(before, counters_snapshot()))))
    blocks = counter_sum(data.counters, STAGES + "_count", {"stage": "deliver"})
    txs = counter_sum(data.counters, DELIVERED)
    checks.append(check_equal(
        f"window.txs_delivered_a_block_applied_is_{chain.txs_per_block}",
        0 if blocks and txs == chain.txs_per_block * blocks else 1))
    return checks
