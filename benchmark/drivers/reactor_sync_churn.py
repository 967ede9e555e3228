"""Fast sync of a chain whose validator set changes, from genesis, by a fresh
``BlockchainReactor`` again and again for the window.

``reactor_sync``'s peers, clock, warm-up, loop and checks, with what the
deployment ``fastsync-64v-churn`` changes: the in-process app is the
program's ``PersistentKVStoreApp`` (InitChain with the genesis set, as a
node's handshake gives it), the chains are ``benchmark/chaingen_churn``'s,
signed set by set as ``benchmark/valset_reference`` says, and a sync also
answers for the next set it ends with and for the heights at which its set
changed.  This file loads a ``reactor_sync`` of its own and puts its ``Sync``
and its ``_one_sync`` in that copy's place; the accepted cell's copy is
another module object and is not touched.

A change made in block H binds H + 2, so the set of the window after a
change is not known before the window before it is applied: every window
ends at a change, verify and apply are serial, and the speculation the
reactor starts behind a cut is thrown away.

The syncs take the traffic's ``chains`` chains in turn, so that no sync
finds its windows in the program's valset caches (``chaingen_churn``).

Traffic parameters: ``blocks``, ``change_interval``, ``join_power``,
``repowers``, ``power_range``, ``chains``, and ``reactor_sync``'s
``warmup_window_heights`` (none longer than the genesis set's run),
``warmup_syncs``, ``sync_timeout_s``, ``forged_timeout_s``.
"""

from __future__ import annotations

import os
import time

from benchmark import chaingen_churn
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

# counter families of the program that a whole sync is asked about
PER_SYNC = {
    "valset_changes": "tendermint_verify_valset_changes_total",
    "valset_cache_clears": "tendermint_verify_valset_cache_clears_total",
}


class ChurnExecutor(base.TimedExecutor):
    """``TimedExecutor`` that also notes each height from which another set
    binds: the height after a block whose apply changed the state's set."""

    def __init__(self, inner, store, target: int):
        super().__init__(inner, store, target)
        self.changed = []

    def apply_block(self, state, block_id, block, trusted_last_commit=False):
        new_state = super().apply_block(
            state, block_id, block, trusted_last_commit=trusted_last_commit)
        if new_state.validators.hash() != state.validators.hash():
            self.changed.append(block.height + 1)
        return new_state


class Sync(base.Sync):
    """One sync: fresh state, app, stores, reactor and peers."""

    def __init__(self, ctx, chain, responses=None, target=None):
        from tendermint_tpu.abci import types as abci
        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.blockchain.reactor import BlockchainReactor
        from tendermint_tpu.blockchain.store import BlockStore
        from tendermint_tpu.libs.db.kv import MemDB
        from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
        from tendermint_tpu.state import store as sm_store
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.state_types import state_from_genesis

        responses = chain.responses if responses is None else responses
        st = state_from_genesis(chain.genesis())
        db = MemDB()
        sm_store.save_state(db, st)
        self.conn = MultiAppConn(LocalClientCreator(PersistentKVStoreApp()))
        self.conn.start()
        self.conn.consensus.init_chain_sync(abci.RequestInitChain(
            chain_id=chain.chain_id,
            validators=[abci.ValidatorUpdate("ed25519", p, w)
                        for p, w in chain.validators]))
        self.store = BlockStore(MemDB())
        self.target = len(responses) - 1 if target is None else target
        self.exec = ChurnExecutor(
            BlockExecutor(db, self.conn.consensus), self.store, self.target)
        self.reactor = BlockchainReactor(st, self.exec, self.store, fast_sync=True)
        self.net = base.PeerNet(
            self.reactor, responses, tip=len(responses),
            n_peers=int(ctx.config["peers"]))
        self.reactor.set_switch(self.net)
        self.t_start = 0.0

    def verify_final(self, chain) -> int:
        """``reactor_sync``'s misses (height, app hash, validators hash,
        stored before applied) and the two that only this chain has."""
        st = self.reactor.state
        return (super().verify_final(chain)
                + int(st.next_validators.hash() != chain.next_validators_hash)
                + int(self.exec.changed != chain.change_heights))


def setup(ctx):
    chains = []
    for k in range(int(ctx.traffic["chains"])):
        chain = chaingen_churn.build_chain(ctx.config, ctx.traffic, [ctx.seed, k])
        chains.append(chain)
        ctx.log(f"setup.generate: chain {k}: {chain.seconds['total']:.3f}s, "
                f"{len(chain.change_heights)} set changes at "
                f"{chain.change_heights[:2]}..{chain.change_heights[-1:]}, "
                f"{chain.keys} keys")
    return {"chains": chains, "chain": chains[0], "final_misses": 0, "syncs": 0,
            "started": 0, "per_sync": {k: 0.0 for k in PER_SYNC}}


def _one_sync(ctx, state, deadline=None):
    """``reactor_sync._one_sync`` over the next chain of the ring; a whole
    sync also adds what the program counted in it to ``per_sync``."""
    state["chain"] = state["chains"][state["started"] % len(state["chains"])]
    state["started"] += 1
    before = counters_snapshot()
    marks, t_start, finished = _base_one_sync(ctx, state, deadline)
    if finished:
        grown = counters_delta(before, counters_snapshot())
        for key, family in PER_SYNC.items():
            if any(k.partition("{")[0] == family for k in grown):
                state["per_sync"][key] += counter_sum(grown, family)
            else:  # a program without the family: nothing to report
                state["per_sync"].pop(key, None)
    return marks, t_start, finished


_base_one_sync = base._one_sync
base.Sync, base._one_sync = Sync, _one_sync

warmup = base.warmup  # the window programs from chain 0's genesis, then syncs


def window(ctx, state, seconds):
    """``reactor_sync.window``, and what the program counted over the
    window's whole syncs beside how many those were:
    ``valset_changes_per_sync.churn`` and its like divide the two."""
    syncs = state["syncs"]
    state["per_sync"] = {k: 0.0 for k in state["per_sync"]}
    win = base.window(ctx, state, seconds)
    win.totals["whole_syncs"] = state["syncs"] - syncs
    for key, value in state["per_sync"].items():
        win.totals[key + "_in_whole_syncs"] = value
    return win


def _stale_signer_sync(ctx, chain):
    """A sync of the chain whose first commit by a changed set carries the
    signature of the validator that left in the place of the one that
    joined: it has to stop at that height, punish the supplier and apply
    nothing past it."""
    height, responses = chaingen_churn.stale_signer(chain)
    sync = Sync(ctx, chain, responses=responses, target=len(responses) - 1)
    sync.start()
    punished = sync.net.punished.wait(float(ctx.traffic["forged_timeout_s"]))
    t_end = time.perf_counter() + 5.0
    while sync.store.height() < height - 1 and time.perf_counter() < t_end:
        time.sleep(0.01)
    time.sleep(0.1)  # anything applied past the height would show by now
    sync.stop()
    applied = sync.store.height()
    stopped = list(sync.net.stopped)
    ctx.log(f"check: the validator that left signing for the one that joined "
            f"at height {height} of {len(responses)}: store at {applied}, "
            f"punished {stopped[:2]}")
    miss = int(not punished) + int(applied != height - 1)
    miss += int(not any(r.endswith(f"bad block {height}") for _p, r in stopped))
    return miss


def check(ctx, state, win, data):
    """``reactor_sync``'s checks (its final-state check now also holds the
    next set and the heights the set changed at; its forged precommit runs
    on chain 0), then the stale signer."""
    state["chain"] = state["chains"][0]
    checks = list(base.check(ctx, state, win, data))
    before = counters_snapshot()
    checks.append(check_equal(
        "stale_signer.stops_and_punishes",
        _stale_signer_sync(ctx, state["chain"])))
    checks.append(check_equal(
        "stale_signer.fallbacks_and_audit_mismatches",
        guard_events(counters_delta(before, counters_snapshot()))))
    return checks
