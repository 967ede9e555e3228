"""Fast sync of a chain from genesis by a fresh ``BlockchainReactor``, again
and again for the window, from peers that hold nothing but encoded bytes.

A peer answers a ``BlockRequest`` synchronously, inside the reactor's
``request_cb``, by handing the ``BlockResponseMessage`` bytes to
``BlockchainReactor.receive``: no feeder thread, no sleep, nothing of the
harness on the GIL during the window, and the decode is inside the
measurement, where a node pays it.

Every request is answered at once, so the windows are the program's own:
the reactor verifies whatever run of consecutive blocks it finds ready when
its tick comes, and speculates on the rest while it applies.  Their sizes
are ragged and differ from sync to sync, and each lane bucket is a program
of its own, so warm-up first drives ``verify_block_window`` (the call the
reactor makes) over one window of each size in ``warmup_window_heights``,
one per bucket the sizes 1..request_window-1 can reach, and then runs
``warmup_syncs`` whole syncs.

Traffic parameters: ``blocks``, ``txs_per_block``, ``tx_bytes`` (with txs),
``warmup_window_heights``, ``warmup_syncs``, ``sync_timeout_s`` and
``forged_timeout_s`` (patience with the forged-precommit control sync).
"""

from __future__ import annotations

import gc
import math
import threading
import time

from benchmark import chaingen
from benchmark.harness import (
    Window,
    check_equal,
    counter_sum,
    counters_delta,
    counters_snapshot,
    guard_events,
)

CHANNEL = 0x40
# the threads a started BlockchainReactor owns (reactor.py, pool.py)
PROGRAM_THREADS = ("bc-pool", "bc-verify", "blockpool-sched")


class Peer:
    def __init__(self, pid: str, net: "PeerNet"):
        self.id = pid
        self._net = net

    def try_send(self, chan_id: int, msg_bytes: bytes) -> bool:
        return self._net.on_message(self, msg_bytes)


class PeerNet:
    """The peers and the little of a Switch the reactor calls."""

    def __init__(self, reactor, responses, tip: int, n_peers: int):
        from tendermint_tpu.blockchain import messages as m

        self._m = m
        self.reactor = reactor
        self.responses = responses
        self.peers = {f"peer{i}": Peer(f"peer{i}", self) for i in range(n_peers)}
        self.stopped = []  # (peer id, reason) in the order the reactor punished
        self.punished = threading.Event()
        self.down = False
        self._status = m.encode_msg(m.StatusResponseMessage(tip))

    # -- Switch surface -----------------------------------------------------
    def broadcast(self, chan_id: int, msg_bytes: bytes) -> None:
        for peer in list(self.peers.values()):
            self.on_message(peer, msg_bytes)

    def stop_peer_for_error(self, peer, reason) -> None:
        self.stopped.append((peer.id, str(reason)))
        self.down = True  # the control sync ends at the first punishment
        self.peers.pop(peer.id, None)
        self.reactor.remove_peer(peer, reason)
        self.punished.set()

    # -- the peers' side ------------------------------------------------------
    def announce(self) -> None:
        for peer in list(self.peers.values()):
            self.reactor.receive(CHANNEL, peer, self._status)

    def on_message(self, peer, msg_bytes: bytes) -> bool:
        if self.down:
            return True
        msg = self._m.unmarshal_msg(msg_bytes)
        if isinstance(msg, self._m.StatusRequestMessage):
            self.reactor.receive(CHANNEL, peer, self._status)
        elif isinstance(msg, self._m.BlockRequestMessage):
            self.reactor.receive(CHANNEL, peer, self.responses[msg.height - 1])
        return True


class TimedExecutor:
    """The reactor's BlockExecutor with a clock on it: when each block was
    applied, that it was in the store first, and an event at the target."""

    def __init__(self, inner, store, target: int):
        self._inner = inner
        self._store = store
        self._target = target
        self.marks = []  # perf_counter at the end of each apply
        self.unstored = 0
        self.done = threading.Event()

    def apply_block(self, state, block_id, block, trusted_last_commit=False):
        if self._store.height() < block.height:
            self.unstored += 1
        new_state = self._inner.apply_block(
            state, block_id, block, trusted_last_commit=trusted_last_commit)
        self.marks.append(time.perf_counter())
        if block.height >= self._target:
            self.done.set()
        return new_state

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Sync:
    """One sync: fresh state, app, stores, reactor and peers."""

    def __init__(self, ctx, chain, responses=None, target=None):
        from tendermint_tpu.abci.examples.kvstore import KVStoreApp
        from tendermint_tpu.blockchain.reactor import BlockchainReactor
        from tendermint_tpu.blockchain.store import BlockStore
        from tendermint_tpu.libs.db.kv import MemDB
        from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
        from tendermint_tpu.state import store as sm_store
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.state_types import state_from_genesis

        cfg = ctx.config
        responses = chain.responses if responses is None else responses
        st = state_from_genesis(chain.genesis())
        db = MemDB()
        sm_store.save_state(db, st)
        self.conn = MultiAppConn(LocalClientCreator(KVStoreApp()))
        self.conn.start()
        self.store = BlockStore(MemDB())
        self.target = len(responses) - 1 if target is None else target
        self.exec = TimedExecutor(
            BlockExecutor(db, self.conn.consensus), self.store, self.target)
        self.reactor = BlockchainReactor(st, self.exec, self.store, fast_sync=True)
        self.net = PeerNet(
            self.reactor, responses, tip=len(responses),
            n_peers=int(cfg["peers"]))
        self.reactor.set_switch(self.net)
        self.t_start = 0.0

    def start(self):
        self.net.announce()
        self.t_start = time.perf_counter()
        self.reactor.start()

    def stop(self):
        """Stop the reactor and wait for its threads: a window still being
        verified or applied would otherwise run on into the next sync, and
        its counters would land after the window's snapshot."""
        try:
            self.reactor.stop()
            for t in threading.enumerate():
                if t.name in PROGRAM_THREADS or t.name.startswith("supervised-"):
                    t.join(30.0)
        finally:
            self.conn.stop()

    def verify_final(self, chain) -> int:
        """Misses against what the generator's chain ends at."""
        st = self.reactor.state
        miss = 0
        miss += self.store.height() != chain.final_height
        miss += st.last_block_height != chain.final_height
        miss += st.app_hash != chain.app_hash_reference
        miss += st.validators.hash() != chain.validators_hash
        miss += self.exec.unstored
        miss += sum(self.store.load_block_meta(h) is None
                    for h in range(1, chain.final_height + 1))
        return int(miss)


def setup(ctx):
    t0 = time.perf_counter()
    path = chaingen.chain_cache_path(
        ctx.cache_dir, ctx.cell.config_name, ctx.cell.workload["traffic"],
        ctx.config, ctx.traffic, ctx.seed)
    chain = chaingen.load_chain(path)
    if chain is None:
        chain = chaingen.build_chain_bytes(ctx.config, ctx.traffic, ctx.seed)
        chaingen.save_chain(path, chain)
        ctx.log("setup.generate: " + " ".join(
            f"{k}={v:.3f}s" for k, v in chain.seconds.items()))
    else:
        ctx.log(f"setup.generate: chain cache hit "
                f"({time.perf_counter() - t0:.3f}s): {path}")
    return {"chain": chain, "final_misses": 0, "syncs": 0}


def _one_sync(ctx, state, deadline=None):
    """(blocks applied, seconds from start to the last applied block,
    marks, finished) of one sync, stopped at ``deadline`` if it comes."""
    chain = state["chain"]
    sync = Sync(ctx, chain)
    with ctx.spans.span("bench.sync"):
        sync.start()
        timeout = None if deadline is None else max(0.0, deadline - time.perf_counter())
        finished = sync.exec.done.wait(
            timeout if timeout is not None else float(ctx.traffic["sync_timeout_s"]))
        sync.stop()
    marks = list(sync.exec.marks)
    if deadline is not None:
        marks = [t for t in marks if t <= deadline] if not finished else marks
    if finished:
        state["final_misses"] += sync.verify_final(chain)
        state["syncs"] += 1
    t_start = sync.t_start
    del sync
    gc.collect()
    return marks, t_start, finished


def _warm_window_programs(ctx, chain):
    """One ``verify_block_window`` from genesis for each listed size: the
    reactor's own call on the chain's own blocks, so every lane bucket the
    ragged windows can reach is traced, lowered and loaded before the
    window.  Misses: a window that did not verify whole."""
    from tendermint_tpu.blockchain.messages import unmarshal_msg
    from tendermint_tpu.blockchain.reactor import verify_block_window
    from tendermint_tpu.state.state_types import state_from_genesis

    sizes = sorted({min(int(h), chain.final_height)
                    for h in ctx.traffic["warmup_window_heights"]})
    blocks = [unmarshal_msg(r).block for r in chain.responses[: sizes[-1] + 1]]
    st = state_from_genesis(chain.genesis())
    miss, took = 0, []
    for h in sizes:
        t0 = time.perf_counter()
        n_ok, err = verify_block_window(st, blocks[: h + 1])
        miss += int(n_ok != h or err is not None)
        took.append(f"{h}:{time.perf_counter() - t0:.2f}s")
    ctx.log("warmup: window programs by heights " + " ".join(took))
    return miss


def warmup(ctx, state):
    state["final_misses"] += _warm_window_programs(ctx, state["chain"])
    for _ in range(int(ctx.traffic["warmup_syncs"])):
        marks, t_start, finished = _one_sync(ctx, state)
        if finished:
            ctx.log(f"warmup: one sync of {len(marks)} blocks in "
                    f"{marks[-1] - t_start:.3f}s")
        else:  # shows as a failed check; the window still runs
            state["final_misses"] += 1
            ctx.log(f"warmup: sync stalled at {len(marks)} of "
                    f"{state['chain'].final_height} blocks")


def window(ctx, state, seconds):
    t0 = time.perf_counter()
    deadline = t0 + seconds
    runs = []  # (t_start, marks)
    while time.perf_counter() < deadline:
        marks, t_start, _finished = _one_sync(ctx, state, deadline)
        runs.append((t_start, marks))
    elapsed = time.perf_counter() - t0

    def totals(lo, hi):
        """Blocks applied in [lo, hi) and the seconds a sync ran in it: from
        each sync's start to its last applied block, clipped to the slice."""
        blocks, secs = 0, 0.0
        for t_start, marks in runs:
            inside = [t for t in marks if lo <= t < hi]
            if not inside:
                continue
            blocks += len(inside)
            secs += inside[-1] - max(t_start, lo)
        return {"blocks_applied": blocks, "sync_seconds": secs}

    whole = totals(t0, float("inf"))
    mid = t0 + seconds / 2
    halves = [{"samples": {}, "totals": totals(t0, mid)},
              {"samples": {}, "totals": totals(mid, float("inf"))}]
    full = state["chain"].final_height
    done = sum(1 for _t, marks in runs if len(marks) == full)
    return Window(
        attempted=len(runs), failed=int(not whole["blocks_applied"]),
        seconds=elapsed,
        totals=whole, halves=halves,
        notes=[f"window: {len(runs)} syncs started, {done} whole, "
               f"{whole['blocks_applied']} blocks in "
               f"{whole['sync_seconds']:.3f}s of sync ({elapsed:.3f}s wall)",
               # every sync in order, so a step or a drift inside a run shows
               "syncs_blocks_per_s: " + " ".join(
                   f"{len(marks) / (marks[-1] - t_start):.1f}"
                   for t_start, marks in runs if marks)],
    )


def _forged_sync(ctx, state, rng):
    """A short sync of a chain with one forged precommit: it has to stop
    at the forged height, punish the supplier and apply nothing past it."""
    chain = state["chain"]
    n = len(chain.responses)
    # among the heights the pool asks for at once
    height = int(rng.integers(
        2, min(int(ctx.config["reactor"]["request_window"]), n) - 1))
    responses = list(chain.responses)
    responses[height] = chaingen.forge_precommit(chain, height, rng)
    sync = Sync(ctx, chain, responses=responses, target=n - 1)
    sync.start()
    punished = sync.net.punished.wait(float(ctx.traffic["forged_timeout_s"]))
    t_end = time.perf_counter() + 5.0
    while sync.store.height() < height - 1 and time.perf_counter() < t_end:
        time.sleep(0.01)
    time.sleep(0.1)  # anything applied past the forgery would show by now
    sync.stop()
    applied = sync.store.height()
    stopped = list(sync.net.stopped)
    ctx.log(f"check: forged precommit at height {height} of {n}: "
            f"store at {applied}, punished {stopped[:2]}")
    miss = int(not punished) + int(applied != height - 1)
    miss += int(not any(r.endswith(f"bad block {height}") for _p, r in stopped))
    return miss


def check(ctx, state, win, data):
    chain = state["chain"]
    c = data.counters
    checks = [
        check_equal("window.device_fallback_total", int(counter_sum(
            c, "tendermint_verify_device_fallback_total"))),
        check_equal("window.audit_mismatch", int(counter_sum(
            c, "tendermint_verify_device_audit_total", {"outcome": "mismatch"}))),
        check_equal("window.compiles", int(c.get("compile.programs", 0))),
    ]
    # every dispatch audits ceil(rate x its lanes), and the windows are ragged
    rate = float(ctx.config["verify"]["audit_sample_rate"])
    dispatches = counter_sum(c, "tendermint_verify_calls_total")
    sigs = counter_sum(c, "tendermint_verify_sigs_total")
    audited = counter_sum(c, "tendermint_verify_device_audit_total")
    lo = math.ceil(sigs * rate)  # one dispatch of everything
    hi = lo + dispatches  # each dispatch rounds up by less than one lane
    checks.append(check_equal(
        f"window.audited_lanes_in_{lo:g}_to_{hi:g}",
        0 if dispatches and lo <= audited <= hi else 1))
    checks.append(check_equal(
        f"syncs.final_state_vs_generator_over_{state['syncs']}",
        state["final_misses"] + (0 if state["syncs"] else 1)))
    before = counters_snapshot()
    checks.append(check_equal(
        "forged_precommit.stops_and_punishes",
        _forged_sync(ctx, state, ctx.rng(2))))
    # a forged lane that the device passed, the audit caught and the host
    # put right stops the sync as it should: it shows here
    checks.append(check_equal(
        "forged_precommit.fallbacks_and_audit_mismatches",
        guard_events(counters_delta(before, counters_snapshot()))))
    return checks
