"""The readings PR 43 put on a block's apply: one observation a block and
stage of ``tendermint_state_block_stage_seconds``, tracing on or off, the
two counters beside it, and what ``fastsync.apply`` says of its blocks."""

import time

import pytest

from tendermint_tpu.abci.examples.kvstore import UpstreamKVStoreApp, put_varint
from tendermint_tpu.blockchain.reactor import BlockchainReactor
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.libs.db.kv import MemDB
from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
from tendermint_tpu.state import store as sm_store
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state_types import state_from_genesis
from tendermint_tpu.testutil.chain import build_chain

FAMILY = "tendermint_state_block_stage_seconds"
IN_APPLY_BLOCK = ("validate", "deliver", "save_responses", "update_state",
                  "commit", "save_state")
STAGES = IN_APPLY_BLOCK + ("save_block",)
TXS = 5


class _AcceptAll:
    def verify_ed25519(self, items):
        import numpy as np

        return np.ones((len(items),), dtype=bool)

    verify_secp256k1 = verify_ed25519


def _reactor(fx):
    st = state_from_genesis(fx.genesis)
    db = MemDB()
    sm_store.save_state(db, st)
    conn = MultiAppConn(LocalClientCreator(UpstreamKVStoreApp()))
    conn.start()
    store = BlockStore(MemDB())
    bc = BlockchainReactor(st, BlockExecutor(db, conn.consensus), store,
                           verifier=_AcceptAll(), verify_window=4)
    from tendermint_tpu.blockchain.pool import _Request

    for h in range(1, fx.height + 1):
        bc.pool._requests[h] = _Request(height=h, block=fx.block_store.load_block(h))
    return bc, store, db


@pytest.fixture(scope="module")
def chain():
    return build_chain(n_vals=4, n_heights=9, chain_id="stage-chain",
                       txs_per_block=TXS, app_factory=UpstreamKVStoreApp)


@pytest.mark.parametrize("traced", [False, True])
def test_every_stage_is_observed_once_a_block(chain, traced, verify_counters, request):
    if traced:
        request.getfixturevalue("tracing")
    bc, store, _db = _reactor(chain)
    count = {s: verify_counters(FAMILY + "_count", {"stage": s}) for s in STAGES}
    total = {s: verify_counters(FAMILY + "_sum", {"stage": s}) for s in STAGES}
    txs = verify_counters("tendermint_state_txs_delivered_total")
    t0 = time.perf_counter()
    for _ in range(4):
        bc._try_sync_window()
    wall = time.perf_counter() - t0
    bc.on_stop()
    applied = store.height()
    assert applied == chain.height - 1
    spent = 0.0
    for s in STAGES:
        assert verify_counters(FAMILY + "_count", {"stage": s}) - count[s] == applied, s
        took = verify_counters(FAMILY + "_sum", {"stage": s}) - total[s]
        assert took > 0, s
        spent += took
    # the stages lie inside the applies, which lie inside the looks
    assert spent <= wall
    assert verify_counters("tendermint_state_txs_delivered_total") - txs == TXS * applied
    # the family shows no other stage
    from tendermint_tpu.libs.metrics import get_verify_metrics

    seen = {line.split('stage="')[1].split('"')[0]
            for line in get_verify_metrics().registry.expose_text().splitlines()
            if line.startswith(FAMILY + "_count")}
    assert seen == set(STAGES)


def test_apply_block_alone_reads_its_six_stages(chain, verify_counters):
    """Consensus applies a block through the same ``apply_block``: six
    stages a block there, and no ``save_block`` (the reactor's own)."""
    st = state_from_genesis(chain.genesis)
    db = MemDB()
    sm_store.save_state(db, st)
    conn = MultiAppConn(LocalClientCreator(UpstreamKVStoreApp()))
    conn.start()
    ex = BlockExecutor(db, conn.consensus)
    before = {s: verify_counters(FAMILY + "_count", {"stage": s}) for s in STAGES}
    for h in (1, 2, 3):
        block = chain.block_store.load_block(h)
        meta = chain.block_store.load_block_meta(h)
        st = ex.apply_block(st, meta.block_id, block)
    conn.stop()
    grown = {s: verify_counters(FAMILY + "_count", {"stage": s}) - before[s]
             for s in STAGES}
    assert grown == dict({s: 3 for s in IN_APPLY_BLOCK}, save_block=0)
    assert st.app_hash == put_varint(3 * TXS)


def test_a_block_that_does_not_validate_observes_nothing(chain, verify_counters):
    from tendermint_tpu.state.execution import InvalidBlockError

    st = state_from_genesis(chain.genesis)
    db = MemDB()
    sm_store.save_state(db, st)
    conn = MultiAppConn(LocalClientCreator(UpstreamKVStoreApp()))
    conn.start()
    before = verify_counters(FAMILY + "_count")
    block = chain.block_store.load_block(2)  # height 2 on a state at height 0
    with pytest.raises(InvalidBlockError):
        BlockExecutor(db, conn.consensus).apply_block(
            st, chain.block_store.load_block_meta(2).block_id, block)
    conn.stop()
    assert verify_counters(FAMILY + "_count") == before


def test_the_intake_counts_the_bytes_of_block_responses_alone(chain, verify_counters):
    from tendermint_tpu.blockchain.messages import (
        BlockResponseMessage, StatusResponseMessage, encode_msg)

    bc, _store, _db = _reactor(chain)
    bc.pool._requests.clear()

    class Peer:
        id = "peerA"

    family = "tendermint_verify_block_intake_bytes_total"
    before = verify_counters(family)
    bc.receive(0x40, Peer(), encode_msg(StatusResponseMessage(9)))
    sent = 0
    for h in range(1, 5):
        msg = encode_msg(BlockResponseMessage(chain.block_store.load_block(h)))
        sent += len(msg)
        bc.receive(0x40, Peer(), msg)
    assert verify_counters(family) - before == sent
    bc.on_stop()


def test_the_apply_span_says_its_txs_and_bytes(chain, tracing):
    bc, store, _db = _reactor(chain)
    for _ in range(4):
        bc._try_sync_window()
    bc.on_stop()
    applies = [e for e in tracing.export() if e.get("name") == "fastsync.apply"]
    assert sum(e["args"]["n"] for e in applies) == store.height() == chain.height - 1
    for e in applies:
        h0, n = e["args"]["h0"], e["args"]["n"]
        assert e["args"]["txs"] == TXS * n
        assert e["args"]["bytes"] == sum(
            len(chain.block_store.load_block(h).marshal()) for h in range(h0, h0 + n))
    # per window, never per block: no span is named for a stage
    assert not [e for e in tracing.export() if "stage" in str(e.get("name", ""))]
