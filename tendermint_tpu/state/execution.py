"""BlockExecutor — validates, executes (ABCI), commits and persists blocks
(ref: state/execution.go).

apply_block is THE state transition of the system: validate (batched
signature check) → stream DeliverTx to the app → EndBlock valset/params
updates → app Commit under mempool lock → save state → fire events.
fail_point() kill-sites mirror the reference's crash-consistency test hooks
(execution.go:102-106, state.go:1284-1341).
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

from tendermint_tpu.abci import types as abci
from tendermint_tpu.libs import fail
from tendermint_tpu.libs.db.kv import DB
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.state import store
from tendermint_tpu.state.state_types import State
from tendermint_tpu.state.validation import validate_block
from tendermint_tpu.types import Block, BlockID, Validator, ValidatorSet
from tendermint_tpu.types.events import EventBus
from tendermint_tpu.crypto.keys import PubKeyEd25519, PubKeySecp256k1


# block_stage_seconds' label values in the order apply_block passes them,
# as the tuples observe() takes
_STAGES = tuple((stage,) for stage in (
    "validate", "deliver", "save_responses", "update_state", "commit",
    "save_state"))


class InvalidBlockError(Exception):
    pass


class ProxyAppConnError(Exception):
    pass


class BlockExecutor:
    def __init__(
        self,
        state_db: DB,
        proxy_app,  # AppConnConsensus
        mempool=None,
        evpool=None,
        event_bus: Optional[EventBus] = None,
        verifier=None,
        metrics=None,
        logger=None,
    ):
        from tendermint_tpu.state.services import MockEvidencePool, MockMempool

        self.db = state_db
        self.proxy_app = proxy_app
        self.mempool = mempool if mempool is not None else MockMempool()
        self.evpool = evpool if evpool is not None else MockEvidencePool()
        self.event_bus = event_bus
        self.verifier = verifier  # BatchVerifier for commit checks
        self.metrics = metrics
        import logging

        self.logger = logger or logging.getLogger("tm.state")

    def validate_block(
        self, state: State, block: Block, trusted_last_commit: bool = False
    ) -> None:
        validate_block(
            self.db, state, block, verifier=self.verifier,
            trusted_last_commit=trusted_last_commit,
        )

    def apply_block(
        self,
        state: State,
        block_id: BlockID,
        block: Block,
        trusted_last_commit: bool = False,
    ) -> State:
        """execution.go:88 — returns the new state or raises; the caller dies
        on failure (consensus halts deliberately).

        trusted_last_commit: fast sync's batched window verify already checked
        this block's LastCommit signatures — skip re-verifying them."""
        clock = time.perf_counter
        t_start = clock()
        try:
            self.validate_block(state, block, trusted_last_commit=trusted_last_commit)
        except Exception as e:
            raise InvalidBlockError(str(e)) from e

        t_validated = clock()
        abci_responses = exec_block_on_proxy_app(
            self.proxy_app, block, state.last_validators, self.db, self.logger
        )
        t_delivered = clock()
        if self.metrics is not None:
            self.metrics.block_processing_time.observe(t_delivered - t_validated)

        fail.fail_point()

        store.save_abci_responses(self.db, block.height, abci_responses)
        t_responses = clock()

        fail.fail_point()

        state = update_state(state, block_id, block.header, abci_responses)
        t_updated = clock()

        # lock mempool, commit app, update mempool
        app_hash = self.commit(state, block)

        self.evpool.update(block, state)
        t_committed = clock()

        fail.fail_point()

        state.app_hash = app_hash
        store.save_state(self.db, state)
        t_saved = clock()

        fail.fail_point()

        # one observation a block and stage, tracing on or off: a span a
        # block would be 600 a second in a sync of empty blocks
        vm = get_verify_metrics()
        observe = vm.block_stage_seconds.observe
        stamps = (t_start, t_validated, t_delivered, t_responses, t_updated,
                  t_committed, t_saved)
        for stage, begun, ended in zip(_STAGES, stamps, stamps[1:]):
            observe(ended - begun, stage)
        vm.txs_delivered.add(float(len(block.data.txs)))

        if self.event_bus is not None:
            fire_events(self.event_bus, block, abci_responses)
        return state

    def commit(self, state: State, block: Block) -> bytes:
        """Mempool locked across app Commit + mempool update
        (execution.go:145-192)."""
        self.mempool.lock()
        try:
            self.mempool.flush_app_conn()
            res = self.proxy_app.commit_sync()
            self.logger.info(
                "committed state height=%d txs=%d app_hash=%s",
                block.height, len(block.data.txs), res.data.hex(),
            )
            self.mempool.update(block.height, block.data.txs)
            return res.data
        finally:
            self.mempool.unlock()

    def create_proposal_block(
        self, height: int, state: State, commit, proposer_address: bytes
    ) -> Tuple[Block, "object"]:
        """Reap mempool + evidence into the next proposal
        (ref execution.go CreateProposalBlock)."""
        max_bytes = state.consensus_params.block_size.max_bytes
        max_gas = state.consensus_params.block_size.max_gas
        evidence = self.evpool.pending_evidence(max_bytes // 10)
        txs = self.mempool.reap_max_bytes_max_gas(max_bytes * 9 // 10, max_gas)
        block = state.make_block(
            height, txs, commit, evidence, proposer_address
        )
        return block, block.make_part_set()


def exec_block_on_proxy_app(
    proxy_app, block: Block, last_val_set: ValidatorSet, state_db: DB, logger
) -> store.ABCIResponses:
    """BeginBlock → DeliverTxAsync×N (pipelined) → EndBlock
    (execution.go:194-264)."""
    deliver_txs: List[Optional[abci.ResponseDeliverTx]] = [None] * len(block.data.txs)
    counted = [0]
    app_err: List[Optional[str]] = [None]

    def on_response(req, res):
        if isinstance(res, abci.ResponseException) and isinstance(
            req, abci.RequestDeliverTx
        ):
            # app crashed on a tx: the block must fail, not silently shift
            # the results array (state-divergence hazard)
            app_err[0] = res.error
            counted[0] += 1
        elif isinstance(res, abci.ResponseDeliverTx):
            deliver_txs[counted[0]] = res
            if res.code != abci.CODE_TYPE_OK:
                logger.debug("invalid tx code=%d log=%s", res.code, res.log)
            counted[0] += 1

    proxy_app.set_response_callback(on_response)

    commit_info, byz_vals = _get_begin_block_validator_info(
        block, last_val_set, state_db
    )

    bb = proxy_app.begin_block_sync(
        abci.RequestBeginBlock(
            hash=block.hash() or b"",
            header=_abci_header(block),
            last_commit_info=commit_info,
            byzantine_validators=byz_vals,
        )
    )
    if isinstance(bb, abci.ResponseException):
        raise ProxyAppConnError(bb.error)

    for tx in block.data.txs:
        proxy_app.deliver_tx_async(bytes(tx))
        err = proxy_app.error()
        if err:
            raise ProxyAppConnError(str(err))

    eb = proxy_app.end_block_sync(abci.RequestEndBlock(height=block.height))
    if isinstance(eb, abci.ResponseException):
        raise ProxyAppConnError(eb.error)

    # end_block_sync flushed the pipeline: every DeliverTx must be accounted for
    if app_err[0] is not None:
        raise ProxyAppConnError(f"DeliverTx failed: {app_err[0]}")
    if counted[0] != len(block.data.txs) or any(r is None for r in deliver_txs):
        raise ProxyAppConnError(
            f"DeliverTx responses missing: got {counted[0]}/{len(block.data.txs)}"
        )

    return store.ABCIResponses(
        deliver_tx=list(deliver_txs),
        end_block=eb,
        begin_block=bb,
    )


def _abci_header(block: Block) -> abci.ABCIHeader:
    h = block.header
    return abci.ABCIHeader(
        chain_id=h.chain_id,
        height=h.height,
        time_ns=h.time_ns,
        num_txs=h.num_txs,
        total_txs=h.total_txs,
        app_hash=h.app_hash,
        proposer_address=h.proposer_address,
    )


def _get_begin_block_validator_info(
    block: Block, last_val_set: ValidatorSet, state_db: DB
):
    votes = []
    if block.height > 1:
        precommits = block.last_commit.precommits
        n_pc = len(precommits)
        # read validators in place — get_by_index's defensive copy is pure
        # allocation on this per-block loop (positional args: this builds
        # |valset| objects per applied block)
        _vi = abci.VoteInfo
        votes = [
            _vi(
                val.address,
                val.voting_power,
                i < n_pc and precommits[i] is not None,
            )
            for i, val in enumerate(last_val_set.validators)
        ]
    byz = []
    for ev in block.evidence.evidence:
        try:
            valset = store.load_validators(state_db, ev.height)
            _, val = valset.get_by_address(ev.address)
            power = val.voting_power if val else 0
            total = valset.total_voting_power()
        except store.NoValSetForHeightError:
            power, total = 0, 0
        byz.append(
            abci.ABCIEvidence(
                type="duplicate/vote",
                validator_address=ev.address,
                validator_power=power,
                height=ev.height,
                total_voting_power=total,
            )
        )
    return abci.LastCommitInfo(round=block.last_commit.round(), votes=votes), byz


def update_validators(current_set: ValidatorSet, updates: List[abci.ValidatorUpdate]) -> None:
    """Apply EndBlock deltas: power 0 removes, unknown adds, known updates
    (execution.go:318)."""
    from tendermint_tpu.types.validator_set import _MAX_TOTAL_POWER

    for vu in updates:
        if vu.power < 0:
            raise ValueError(f"voting power can't be negative: {vu}")
        if vu.power > _MAX_TOTAL_POWER:
            # the set's arithmetic clips at this bound and its codec packs
            # powers as int64 — an app granting more must be rejected here,
            # not crash the node at the next save_state
            raise ValueError(f"voting power {vu.power} exceeds maximum")
        if vu.pub_key_type == "ed25519":
            pub = PubKeyEd25519(vu.pub_key)
        elif vu.pub_key_type == "secp256k1":
            pub = PubKeySecp256k1(vu.pub_key)
        else:
            raise ValueError(f"unknown pubkey type {vu.pub_key_type!r}")
        address = pub.address()
        _, val = current_set.get_by_address(address)
        if vu.power == 0:
            if current_set.remove(address) is None:
                raise ValueError(f"failed to remove validator {address.hex()}")
        elif val is None:
            if not current_set.add(Validator(pub, vu.power)):
                raise ValueError("failed to add new validator")
        else:
            if not current_set.update(Validator(pub, vu.power)):
                raise ValueError("failed to update validator")


def update_state(
    state: State,
    block_id: BlockID,
    header,
    abci_responses: store.ABCIResponses,
) -> State:
    """execution.go:356 — the pure state transition."""
    n_val_set = state.next_validators.copy()

    last_height_vals_changed = state.last_height_validators_changed
    if abci_responses.end_block and abci_responses.end_block.validator_updates:
        update_validators(n_val_set, abci_responses.end_block.validator_updates)
        # change applies to the height after next
        last_height_vals_changed = header.height + 1 + 1

    n_val_set.increment_accum(1)

    next_params = state.consensus_params
    last_height_params_changed = state.last_height_consensus_params_changed
    if abci_responses.end_block and abci_responses.end_block.consensus_param_updates:
        next_params = state.consensus_params.update(
            abci_responses.end_block.consensus_param_updates
        )
        next_params.validate()
        last_height_params_changed = header.height + 1

    return State(
        chain_id=state.chain_id,
        version=state.version,
        last_block_height=header.height,
        last_block_total_tx=state.last_block_total_tx + header.num_txs,
        last_block_id=block_id,
        last_block_time_ns=header.time_ns,
        next_validators=n_val_set,
        validators=state.next_validators.copy(),
        last_validators=state.validators.copy(),
        last_height_validators_changed=last_height_vals_changed,
        consensus_params=next_params,
        last_height_consensus_params_changed=last_height_params_changed,
        last_results_hash=abci_responses.results_hash(),
        app_hash=b"",  # filled after Commit
    )


def fire_events(event_bus: EventBus, block: Block, abci_responses: store.ABCIResponses) -> None:
    """NewBlock, NewBlockHeader, one TxEvent per tx (execution.go:421)."""
    event_bus.publish_event_new_block(block, abci_responses)
    event_bus.publish_event_new_block_header(block.header)
    for i, tx in enumerate(block.data.txs):
        res = (
            abci_responses.deliver_tx[i]
            if i < len(abci_responses.deliver_tx)
            else None
        )
        event_bus.publish_event_tx(block.height, i, bytes(tx), res)
