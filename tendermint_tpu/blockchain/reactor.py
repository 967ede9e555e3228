"""BlockchainReactor — fast sync with batched multi-height commit
verification (ref: blockchain/reactor.go:216-327).

The reference's pool routine peeks TWO blocks and serially verifies one
commit per iteration (reactor.go:289-306 — ★ THE loop this framework exists
to replace). Here the pool yields a whole run of consecutive blocks and all
their commits are verified in ONE planned dispatch — every
(height, validator) signature of the window in a single device call
(`verify_block_window`).  Packing, dispatch, and the +2/3 quorum tallies
live in parallel/planner.py (lane-packed, compile-bucketed), shared with
state sync's backfill; with a mesh the lane axis shards across devices.

Verified blocks then apply sequentially with ``trusted_last_commit=True`` so
the executor does not re-verify signatures the window already covered.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import CancelledError, Future
from concurrent.futures import TimeoutError as FutureTimeout
from typing import List, Optional, Tuple

from tendermint_tpu.blockchain.messages import (
    BlockRequestMessage,
    BlockResponseMessage,
    NoBlockResponseMessage,
    StatusRequestMessage,
    StatusResponseMessage,
    encode_msg,
    unmarshal_msg,
)
from tendermint_tpu.blockchain.pool import BlockPool
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.p2p.base_reactor import Reactor
from tendermint_tpu.p2p.conn.connection import ChannelDescriptor
from tendermint_tpu.types import BlockID

BLOCKCHAIN_CHANNEL = 0x40
MAX_MSG_SIZE = 104857600  # 100 MB protocol block ceiling (types/params.go:11)

TRY_SYNC_INTERVAL = 0.01  # reference trySyncTicker 10ms
STATUS_UPDATE_INTERVAL = 2.0  # reference 10s; shrunk for test nets
SWITCH_TO_CONSENSUS_INTERVAL = 0.5  # reference 1s
DRAIN_TIMEOUT = 30.0  # the longest a thrown-away speculation is waited for
# Heights verified per device dispatch. Two regimes
# (scripts/bench_fastsync.py --sweep): the HOST pipeline alone is
# window-size-insensitive up to ~128 and degrades slightly beyond (cache
# pressure in the packing loop), while the DEVICE dispatch wants the
# largest window that fits — one host->device copy and one kernel launch
# amortized over window×valset signatures. 512 favors the device regime
# this framework exists for; auto_verify_window shrinks it for huge
# valsets so a window's signature tensor stays within device memory.
VERIFY_WINDOW = 512
MAX_WINDOW_SIGS = 512 * 1024  # |window| × |valset| ceiling per dispatch


def auto_verify_window(n_validators: int, window: int = VERIFY_WINDOW) -> int:
    """Window size bounded so window × valset ≤ MAX_WINDOW_SIGS (a 10k-val
    set still gets ~52-height batches; a 64-val set the full default)."""
    if n_validators <= 0:
        return window
    return max(1, min(window, MAX_WINDOW_SIGS // max(1, n_validators)))


class WindowVerifyError(Exception):
    def __init__(self, bad_index: int, reason: str):
        super().__init__(f"block window invalid at offset {bad_index}: {reason}")
        self.bad_index = bad_index


class FatalSyncError(Exception):
    """A block with a valid +2/3 commit failed state validation/application.
    Retrying can never succeed (the same window would re-verify and re-fail
    forever — a silent livelock); the reference deliberately panics here
    (blockchain/reactor.go:327 via ApplyBlock panic). We halt fast sync
    loudly instead of looping."""


def verify_block_window(
    state,
    blocks: List,
    verifier=None,
    parts_out: Optional[List] = None,
    mesh=None,
) -> Tuple[int, Optional[WindowVerifyError]]:
    """Verify commits for blocks[0..n-2] (block i's commit is
    blocks[i+1].last_commit, signed by the valset whose hash block i carries
    — reactor.go:306's VerifyCommit, across the whole window at once).

    Per-precommit validity rules + power collection are shared with the
    single-commit path (ValidatorSet.collect_commit_sigs); packing, verify
    dispatch, and the +2/3 quorum tallies all live in `parallel/planner` —
    the ONE implementation shared with state sync's backfill, so the
    verifiers cannot drift apart.

    Without a mesh the planner routes lanes through the BatchVerifier
    boundary (ed25519 rides the device batch; other key types fall back to
    host inside verify_generic).  With ``mesh`` (and an all-ed25519 valset)
    the window's votes are lane-packed and the quorum tallies ride the mesh
    as segment reductions — the multi-chip path of SURVEY §5.

    Returns (n_verified, err): the first n_verified blocks' commits are
    fully verified; err is set if block n_verified is *invalid* (vs merely
    belonging to a future valset, which just truncates the window).
    If `parts_out` is given, it receives each usable block's PartSet so the
    apply loop doesn't rebuild it (block marshal + merkle per block).
    """
    from tendermint_tpu.parallel import planner
    from tendermint_tpu.types.validator_set import CommitError

    valset = state.validators
    chain_id = state.chain_id
    n = len(blocks) - 1
    if n <= 0:
        return 0, None

    # 1. host prechecks + truncation at the first valset change
    usable = 0
    structural: Optional[WindowVerifyError] = None
    votes_rows: List[list] = []
    power_rows: List[list] = []
    local_parts: List = []
    cut = "none"  # what ended the run of heights: window_cut_total's label
    # one span for the loop: per window, never per block
    with trace.span("fastsync.precheck", n=n) as sp:
        for i in range(n):
            block, next_block = blocks[i], blocks[i + 1]
            if block.header.validators_hash != valset.hash():
                cut = "valset_change"
                if i == 0:
                    # offset 0 is always OUR current valset; a mismatch there
                    # is a bad block, not a future valset — punishable, else
                    # the same block livelocks the sync loop forever
                    structural = WindowVerifyError(0, "wrong validators_hash")
                break  # valset changed: verify the rest after applying these
            commit = next_block.last_commit
            parts = block.make_part_set()
            block_id = BlockID(hash=block.hash(), parts_header=parts.header())
            try:
                # the ONE home of the per-precommit rules; its aligned outputs
                # (non-nil precommits in index order) feed the planner row
                pubkeys, msgs, sigs, powers = valset.collect_commit_sigs(
                    chain_id, block_id, block.height, commit
                )
            except CommitError as e:
                structural = WindowVerifyError(i, str(e))
                cut = "structural"
                break
            vrow, prow = planner.rows_from_commit(
                commit.precommits, pubkeys, msgs, sigs, powers
            )
            votes_rows.append(vrow)
            power_rows.append(prow)
            local_parts.append(parts)
            usable += 1
        sp.set(n=usable, cut=cut)
    get_verify_metrics().window_cut.add(1.0, (cut,))

    if usable == 0:
        return 0, structural

    # 2. ONE planned dispatch for the whole window; quorum math lives in
    # the planner's WindowVerdict (mixed-key valsets fall back to the
    # verifier path inside execute_plan, keeping the caller's verifier)
    total = valset.total_voting_power()
    from tendermint_tpu.libs.profile import get_profiler

    with get_profiler().window(blocks[0].height, heights=usable):
        verdict = planner.verify_window(
            votes_rows, power_rows, [total] * usable,
            mesh=mesh, verifier=verifier, use_device=mesh is not None,
        )

    # 3. translate the per-height verdict; stop at the first invalid commit
    for i in range(usable):
        # any invalid signature fails the whole commit (verify_commit
        # parity) — sigs_ok already counts host-precheck failures as bad
        if not bool(verdict.sigs_ok[i]):
            if parts_out is not None:
                parts_out.extend(local_parts[:i])
            return i, WindowVerifyError(i, "invalid signature in commit")
        if not bool(verdict.committed[i]):
            if parts_out is not None:
                parts_out.extend(local_parts[:i])
            return i, WindowVerifyError(i, "insufficient voting power")
    if parts_out is not None:
        parts_out.extend(local_parts[:usable])
    return usable, structural


class BlockchainReactor(Reactor):
    def __init__(
        self,
        state,  # sm.State — the sync starting point
        block_exec,  # BlockExecutor
        block_store,
        fast_sync: bool = True,
        consensus_reactor=None,  # .switch_to_consensus(state, n) when caught up
        verifier=None,  # BatchVerifier for the window dispatches
        verify_window: Optional[int] = None,  # None → auto by valset size
        mesh=None,  # device mesh: shard windows via parallel/commit_verify
        metrics=None,  # NodeMetrics — fast_syncing gauge + block-timer reset
    ):
        super().__init__(name="BlockchainReactor")
        self.metrics = metrics
        self.initial_state = state
        self.state = state.copy()
        self.block_exec = block_exec
        self.store = block_store
        self.fast_sync = fast_sync
        self.consensus_reactor = consensus_reactor
        self.verifier = verifier
        # explicit window is fixed; None → auto-sized per dispatch (the
        # valset can grow/shrink DURING sync, and the MAX_WINDOW_SIGS
        # device-memory ceiling must hold for the set actually being
        # verified, not the one at construction)
        self._fixed_window = verify_window
        self.mesh = mesh
        self.pool = BlockPool(
            start_height=self.store.height() + 1,
            request_cb=self._send_block_request,
            error_cb=self._stop_peer_by_id,
        )
        self.blocks_synced = 0
        self._trusted_commit_heights: set = set()
        self._switched = threading.Event()
        # when the pool routine last asked for statuses and last looked
        # whether it has caught up (monotonic seconds)
        self._last_status = self._last_switch_check = 0.0
        # pipelined speculative verify (SURVEY §2.4): while the apply loop
        # walks window N, windows N+1..N+k verify on daemon worker threads
        # — the device wait releases the GIL, so verify and apply genuinely
        # overlap, and a wedged device can never block interpreter exit (a
        # ThreadPoolExecutor's non-daemon workers would, via
        # concurrent.futures' atexit join).  k = [verify] pipeline_depth - 1
        # (planner.pipeline_depth()); the default depth 2 keeps exactly ONE
        # window in flight — the classic double buffer.  Each slot:
        # (first_height, valset hash the speculation assumed, future,
        # parts, blocks); slots chain consecutively, so a harvest mismatch
        # at the head invalidates every slot behind it too.
        self._spec: list = []

    # -- Reactor interface --------------------------------------------------------
    def get_channels(self):
        return [
            ChannelDescriptor(
                id=BLOCKCHAIN_CHANNEL, priority=10, send_queue_capacity=1000,
                recv_message_capacity=MAX_MSG_SIZE,
            )
        ]

    def on_start(self) -> None:
        if self.fast_sync:
            if self.metrics is not None:
                self.metrics.fast_syncing.set(1)
            self.pool.start()
            threading.Thread(
                target=self._pool_routine, name="bc-pool", daemon=True
            ).start()

    def on_stop(self) -> None:
        if self.pool.is_running:
            try:
                self.pool.stop()
            except Exception:
                pass
        specs, self._spec = self._spec, []  # snapshot: pool routine races
        for spec in specs:
            spec[2].cancel()  # not-yet-started work never runs

    def start_from_statesync(self, state) -> None:
        """Hand-off from a snapshot restore: adopt the reconstructed state
        and begin fast-syncing from the restore height (the reactor was
        composed with fast_sync=False so its pool never started from
        height 1). The pool is rebuilt because its start height was fixed at
        construction, before the snapshot landed blocks in the store."""
        self.initial_state = state
        self.state = state.copy()
        self.fast_sync = True
        self._switched.clear()
        self.pool = BlockPool(
            start_height=self.store.height() + 1,
            request_cb=self._send_block_request,
            error_cb=self._stop_peer_by_id,
        )
        if self.metrics is not None:
            self.metrics.fast_syncing.set(1)
        self.pool.start()
        threading.Thread(
            target=self._pool_routine, name="bc-pool", daemon=True
        ).start()
        # peers that connected while we were restoring never got a status
        # exchange on this channel's sync path — ask for heights now
        if self.switch is not None:
            self.switch.broadcast(
                BLOCKCHAIN_CHANNEL,
                encode_msg(StatusRequestMessage(self.store.height())),
            )

    def add_peer(self, peer) -> None:
        peer.try_send(
            BLOCKCHAIN_CHANNEL, encode_msg(StatusResponseMessage(self.store.height()))
        )

    def remove_peer(self, peer, reason) -> None:
        self.pool.remove_peer(peer.id)

    def receive(self, chan_id: int, peer, msg_bytes: bytes) -> None:
        t0 = time.perf_counter()
        msg = unmarshal_msg(msg_bytes)
        if isinstance(msg, BlockRequestMessage):
            block = self.store.load_block(msg.height)
            if block is not None:
                peer.try_send(BLOCKCHAIN_CHANNEL, encode_msg(BlockResponseMessage(block)))
            else:
                peer.try_send(
                    BLOCKCHAIN_CHANNEL, encode_msg(NoBlockResponseMessage(msg.height))
                )
        elif isinstance(msg, BlockResponseMessage):
            self.pool.add_block(peer.id, msg.block)
            # a block's decode and its way into the pool, on whichever
            # thread received it: per block, so a histogram and no span
            vm = get_verify_metrics()
            vm.block_intake_seconds.observe(time.perf_counter() - t0)
            vm.block_intake_bytes.add(float(len(msg_bytes)))
        elif isinstance(msg, NoBlockResponseMessage):
            self.pool.no_block(peer.id, msg.height)
        elif isinstance(msg, StatusRequestMessage):
            peer.try_send(
                BLOCKCHAIN_CHANNEL, encode_msg(StatusResponseMessage(self.store.height()))
            )
        elif isinstance(msg, StatusResponseMessage):
            self.pool.set_peer_height(peer.id, msg.height)
        else:
            self.logger.error("unknown blockchain msg %r", type(msg))

    # -- pool callbacks --------------------------------------------------------------
    def _send_block_request(self, height: int, peer_id: str) -> None:
        peer = self.switch.peers.get(peer_id) if self.switch else None
        if peer is None:
            self.pool.remove_peer(peer_id)
            return
        peer.try_send(BLOCKCHAIN_CHANNEL, encode_msg(BlockRequestMessage(height)))

    def _stop_peer_by_id(self, peer_id: str, reason: str) -> None:
        peer = self.switch.peers.get(peer_id) if self.switch else None
        if peer is not None:
            self.switch.stop_peer_for_error(peer, reason)
        else:
            self.pool.remove_peer(peer_id)

    # -- the sync loop ---------------------------------------------------------------
    def _pool_routine(self) -> None:
        """reactor.go:216 poolRoutine — with windowed verify→apply."""
        self._last_status = self._last_switch_check = 0.0
        while not self._quit.is_set() and self._sync_cycle():
            pass

    def _sync_cycle(self) -> bool:
        """One turn of the pool routine: the status and switch checks, one
        look (``_try_sync_window``) and the wait after it.  False: the loop
        is over (switched to consensus, or halted).

        One ``fastsync.cycle`` tree a turn whose look did something.  An
        empty look takes its span back: a node idling at the tip looks 100
        times a second, and ``sync_ticks_total{result="empty"}`` counts
        those."""
        with trace.span("fastsync.cycle") as cycle:
            now = time.monotonic()
            if now - self._last_status > STATUS_UPDATE_INTERVAL:
                self._last_status = now
                if self.switch is not None:
                    self.switch.broadcast(
                        BLOCKCHAIN_CHANNEL,
                        encode_msg(StatusRequestMessage(self.store.height())),
                    )
            if now - self._last_switch_check > SWITCH_TO_CONSENSUS_INTERVAL:
                self._last_switch_check = now
                if self.pool.is_caught_up() and self.pool.num_peers() > 0:
                    cycle.drop()
                    self._switch_to_consensus()
                    return False
            result, n = "error", 0
            try:
                result, n = self._try_sync_window()
            except FatalSyncError:
                self.logger.error(
                    "FATAL: fast sync halted — verified block failed to "
                    "apply; manual intervention required (reference panics "
                    "here)", exc_info=True,
                )
                try:
                    self.pool.stop()
                except Exception:
                    pass
                cycle.set(result="error")
                return False
            except Exception:
                self.logger.exception("fast sync window failed")
            if result == "empty":
                cycle.drop()
                self._quit.wait(TRY_SYNC_INTERVAL)
            else:
                cycle.set(result=result, n=n)
                with trace.span("fastsync.tick", after=result):
                    self._quit.wait(TRY_SYNC_INTERVAL)
        return True

    @property
    def verify_window(self) -> int:
        if self._fixed_window is not None:
            return self._fixed_window
        return auto_verify_window(self.state.validators.size)

    # -- speculative (double-buffered) verify --------------------------------------
    def _drain(self, fut: Future, where: str) -> bool:
        """Wait, BOUNDED, for a speculative verify that could not be
        cancelled: a wedged device must hold neither the sync loop nor the
        switch to consensus hostage (the daemon worker dies with the process
        either way).  False: it did not finish in DRAIN_TIMEOUT."""
        try:
            fut.result(timeout=DRAIN_TIMEOUT)
        except FutureTimeout:
            self.logger.warning(
                "speculative verify did not drain %s (wedged device "
                "dispatch?)", where)
            return False
        except Exception:
            pass  # cancelled, or the verify's own failure: it has finished
        return True

    def _discard_speculation(self, slots, reason: str) -> None:
        """Cancel-or-drain invalidated slots.  A running verify must drain —
        letting it race a fresh synchronous verify would double-dispatch
        its window through the device.  ``reason``: what voided them,
        ``valset_change`` (the apply changed the set they assumed) or
        ``height`` (the pool is no longer where they start)."""
        with trace.span(
            "fastsync.discard", slots=len(slots),
            heights=sum(len(blocks) - 1 for *_, blocks in slots),
            reason=reason,
        ) as sp:
            drained = True
            for _, _, fut, _, _ in slots:
                get_verify_metrics().speculative.add(1.0, ("miss",))
                if not fut.cancel():
                    drained = self._drain(fut, "before its window was "
                                          "verified again") and drained
            sp.set(drained=drained)

    def _take_speculative(self) -> Optional[tuple]:
        """Harvest the in-flight window N+1 verification, if it still
        applies: same start height, and the valset the speculation assumed
        survived window N's apply (an EndBlock valset change invalidates the
        whole speculation — including any 'wrong validators_hash' verdict it
        produced, which must never punish a peer).  A head mismatch voids
        every chained slot behind it too: they all assumed the heights and
        valset the head promised."""
        if not self._spec:
            return None
        # its own time: the pop and the two comparisons before the harvest
        with trace.span("fastsync.take", slots=len(self._spec)):
            head = self._spec.pop(0)
            first_h, vhash, fut, parts_list, blocks = head
            if (first_h != self.pool.height
                    or self.state.validators.hash() != vhash):
                rest, self._spec = self._spec, []
                self._discard_speculation(
                    [head] + rest,
                    "height" if first_h != self.pool.height
                    else "valset_change")
                return None
            # how long the apply loop stood waiting for the bc-verify worker
            with trace.span("fastsync.harvest", h0=first_h, hit=False) as sp:
                try:
                    n_ok, err = fut.result()
                except CancelledError:
                    # on_stop cancelled the slot from another thread
                    # mid-harvest
                    get_verify_metrics().speculative.add(1.0, ("miss",))
                    return None
                sp.set(hit=True)
            get_verify_metrics().speculative.add(1.0, ("hit",))
            return blocks, parts_list, n_ok, err

    def _start_speculative(self, offset: int) -> None:
        """Top the speculation chain up to depth while window N applies.

        Depth is [verify] pipeline_depth - 1 slots (planner.pipeline_depth)
        — the default double buffer dispatches exactly one window ahead,
        deeper keeps more windows in flight so the mesh stays fed between
        harvests.  Chained slots start where the previous slot's window
        ends; any partial apply shows up as a head mismatch at harvest and
        voids the chain."""
        from tendermint_tpu.parallel import planner as _planner

        depth = max(1, _planner.pipeline_depth() - 1)
        while len(self._spec) < depth:
            if self._spec:
                last_first, _, _, _, last_blocks = self._spec[-1]
                offset = (last_first - self.pool.height) + len(last_blocks) - 1
            nxt = self.pool.peek_window(
                self.verify_window + 1, start_offset=offset)
            if len(nxt) < 2:
                return
            st = self.state  # CoW valsets: apply never mutates this snapshot
            parts_list: list = []
            fut: Future = Future()

            def _run(nxt=nxt, st=st, parts_list=parts_list, fut=fut):
                # honor a cancel that lands before the thread gets
                # scheduled; once running, fut.cancel() returns False and
                # harvest/discard paths drain instead of racing a second
                # dispatch
                if not fut.set_running_or_notify_cancel():
                    return
                try:
                    with trace.span(
                        "fastsync.window", h0=nxt[0].height, n=len(nxt) - 1,
                        mode="speculative",
                    ):
                        fut.set_result(
                            verify_block_window(
                                st, nxt, self.verifier, parts_list, self.mesh
                            )
                        )
                except BaseException as e:
                    fut.set_exception(e)

            threading.Thread(target=_run, name="bc-verify", daemon=True).start()
            self._spec.append(
                (nxt[0].height, st.validators.hash(), fut, parts_list, nxt))

    def _try_sync_window(self) -> Tuple[str, int]:
        """One look.  Returns what it did, for ``fastsync.cycle``: ``harvest``
        or ``window`` and the heights applied; ``discard`` for a look that
        threw a speculation away and then found under two blocks; ``empty``
        for one that did nothing at all (and so recorded no span)."""
        ticks = get_verify_metrics().sync_ticks
        had_spec = bool(self._spec)
        spec = self._take_speculative()
        if spec is not None:
            ticks.add(1.0, ("harvest",))
            result = "harvest"
            blocks, parts_list, n_ok, err = spec
        else:
            # the walk under the pool's mutex, which the scheduler and
            # add_block also take
            with trace.span("fastsync.peek") as sp:
                blocks = self.pool.peek_window(self.verify_window + 1)
                sp.set(n=len(blocks) - 1)
                if len(blocks) < 2:
                    sp.drop()
                    ticks.add(1.0, ("empty",))
                    return ("discard" if had_spec else "empty"), 0
            ticks.add(1.0, ("window",))
            result = "window"
            parts_list = []
            with trace.span(
                "fastsync.window", h0=blocks[0].height, n=len(blocks) - 1,
                mode="sync",
            ):
                n_ok, err = verify_block_window(
                    self.state, blocks, verifier=self.verifier,
                    parts_out=parts_list, mesh=self.mesh,
                )
        try:
            get_verify_metrics().window_heights.observe(float(n_ok))
        except Exception:
            pass
        for i in range(n_ok):
            self._trusted_commit_heights.add(blocks[i].height)
        if err is not None:
            bad = blocks[err.bad_index]
            self.logger.error("invalid block %d in sync: %s", bad.height, err)
            # punish whoever supplied the bad block and its commit source
            for h in (bad.height, bad.height + 1):
                peer_id = self.pool.redo_request(h)
                if peer_id:
                    self._stop_peer_by_id(peer_id, f"sent bad block {h}")
        elif n_ok > 0:
            # pipeline: verify window N+1 on the worker while the loop
            # below applies window N (its device wait releases the GIL).
            # The span: what the loop pays before it can begin applying (the
            # peek under the pool's mutex, the snapshot, a thread start a slot)
            with trace.span("fastsync.speculate") as sp:
                before = len(self._spec)
                self._start_speculative(offset=n_ok)
                sp.set(started=len(self._spec) - before)
        # apply the verified prefix
        if n_ok > 0:
            with trace.span(
                "fastsync.apply", h0=blocks[0].height, n=n_ok
            ) as sp:
                txs, size = self._apply_verified(blocks, parts_list, n_ok)
                sp.set(txs=txs, bytes=size)
        # the window's blocks and part sets die here (the stores kept their
        # bytes): freeing a hundred blocks' objects is a millisecond or two,
        # which would otherwise be the cycle's own time at this return
        with trace.span("fastsync.release", n=len(blocks) - 1):
            del blocks, parts_list, spec
        return result, n_ok

    def _note_valset_change(self, height: int, old, new) -> None:
        """One span and one count a block whose apply changed the set that
        binds at ``height + 1``: who joined, left or changed power."""
        with trace.span("fastsync.valset_change", h=height) as sp:
            before = {v.address: v.voting_power for v in old.validators}
            after = {v.address: v.voting_power for v in new.validators}
            sp.set(
                added=len(after.keys() - before.keys()),
                removed=len(before.keys() - after.keys()),
                repowered=sum(1 for a, p in after.items()
                              if before.get(a, p) != p),
            )
        get_verify_metrics().valset_changes.add(1.0)

    def _apply_verified(self, blocks, parts_list, n_ok: int):
        """Store and apply the verified prefix; returns the transactions and
        the encoded bytes of the blocks applied (``fastsync.apply``'s args)."""
        save_block_seconds = get_verify_metrics().block_stage_seconds.labels(
            "save_block")
        txs = size = 0
        for i in range(n_ok):
            block = blocks[i]
            parts = parts_list[i]
            block_id = BlockID(hash=block.hash(), parts_header=parts.header())
            t0 = time.perf_counter()
            self.store.save_block(block, parts, blocks[i + 1].last_commit)
            # the seventh stage of a block's apply; apply_block reads its six
            save_block_seconds.observe(time.perf_counter() - t0)
            try:
                # the first synced block's own LastCommit predates our
                # batches — its membership check below is False, forcing
                # the full verify
                new_state = self.block_exec.apply_block(
                    self.state, block_id, block,
                    trusted_last_commit=block.height - 1
                    in self._trusted_commit_heights,
                )
            except Exception as e:
                # commit was valid but the block won't apply: punish the
                # supplier for the record, then halt — retrying loops forever
                peer_id = self.pool.redo_request(block.height)
                if peer_id:
                    self._stop_peer_by_id(
                        peer_id, f"sent unappliable block {block.height}"
                    )
                raise FatalSyncError(
                    f"verified block {block.height} failed to apply: {e}"
                ) from e
            if new_state.validators.hash() != self.state.validators.hash():
                self._note_valset_change(
                    block.height, self.state.validators, new_state.validators)
            self.state = new_state
            self.pool.pop_first()
            self.blocks_synced += 1
            txs += len(block.data.txs)
            size += len(block.marshal())  # a decoded block's wire buffer
            self._trusted_commit_heights.discard(block.height - 2)
            if self.blocks_synced % 100 == 0:
                self.logger.info(
                    "fast sync at height %d (%d peers)",
                    self.pool.height, self.pool.num_peers(),
                )
        return txs, size

    def _switch_to_consensus(self) -> None:
        if self._switched.is_set():
            return
        self._switched.set()
        self.logger.info(
            "caught up (height %d, synced %d) — switching to consensus",
            self.store.height(), self.blocks_synced,
        )
        self.fast_sync = False
        if self.metrics is not None:
            self.metrics.fast_syncing.set(0)
            # the monotonic block timer predates the fast-synced blocks —
            # without a reset the first consensus block records a bogus
            # interval spanning the whole sync
            self.metrics.reset_block_timer()
        if self.pool.is_running:
            try:
                self.pool.stop()
            except Exception:
                pass
        specs, self._spec = self._spec, []
        for spec in specs:
            if not spec[2].cancel():
                # drain: the device should be idle before consensus starts
                # its own commit verifies
                self._drain(spec[2], "before consensus switchover")
        if self.consensus_reactor is not None:
            self.consensus_reactor.switch_to_consensus(
                self.state.copy(), self.blocks_synced
            )
