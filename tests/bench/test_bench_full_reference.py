"""``benchmark/kvstore_reference`` against the program: the app with the
reference's Commit, the varint, and the three hashes a block of transactions
puts into a chain's headers.  ``KVStoreApp``'s own hashes are held where
they were."""

import numpy as np
import pytest

from benchmark import chaingen
from benchmark import kvstore_reference as ref
from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.examples.kvstore import (
    KVStoreApp,
    PersistentKVStoreApp,
    UpstreamKVStoreApp,
    put_varint,
)

# Go's binary.PutVarint into make([]byte, 8), worked by hand: zigzag, then
# seven bits a byte from the low end
VARINTS = {
    0: "0000000000000000",
    1: "0200000000000000",
    63: "7e00000000000000",
    64: "8001000000000000",
    8191: "fe7f000000000000",
    8192: "8080010000000000",
    255000: "b0901f0000000000",
}


@pytest.mark.parametrize("size", sorted(VARINTS))
def test_the_varint_is_gos(size):
    assert ref.put_varint(size).hex() == VARINTS[size]
    assert put_varint(size) == ref.put_varint(size)
    app = UpstreamKVStoreApp()
    app.size = size
    assert app.commit(abci.RequestCommit()).data == ref.put_varint(size)
    assert app.info(abci.RequestInfo()).last_block_app_hash == ref.put_varint(size)


def test_a_varint_that_does_not_fit_eight_bytes_is_refused():
    with pytest.raises(OverflowError):
        ref.put_varint(1 << 55)
    with pytest.raises(OverflowError):
        put_varint(1 << 55)
    assert put_varint((1 << 55) - 1) == ref.put_varint((1 << 55) - 1)


def _seeded_txs(seed):
    """Distinct keys, then the awkward ones: a key written again, a tx
    without '=', an empty value, an empty key, a value that holds '='."""
    rng = np.random.default_rng(seed)
    txs = chaingen.make_txs(rng, 30, 40)
    again = txs[3].partition(b"=")[0]
    txs += [again + b"=second", b"noequals", again + b"=", b"=onlyvalue",
            b"k=v=w", txs[5], b"noequals"]
    return [txs[int(i)] for i in rng.permutation(len(txs))] + [again + b"=last"]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_app_is_the_references_state_machine(seed):
    app, kv = UpstreamKVStoreApp(), ref.KVStore()
    txs = _seeded_txs(seed)
    for n, tx in enumerate(txs, 1):
        res = app.deliver_tx(abci.RequestDeliverTx(tx=tx))
        kv.deliver(tx)
        assert res.code == abci.CODE_TYPE_OK and not res.data
        assert res.tags[0].value == tx.partition(b"=")[0]
        assert app.size == kv.size == n
        if n % 7 == 0:
            assert app.commit(abci.RequestCommit()).data == kv.app_hash()
    assert app.state == kv.state and len(kv.state) < kv.size
    for key in list(kv.state) + [b"never-written"]:
        got = app.query(abci.RequestQuery(path="/store", data=key))
        assert (got.code, got.value) == (0, kv.query(key))
    assert app.commit(abci.RequestCommit()).data == kv.app_hash() == ref.put_varint(len(txs))
    assert app.info(abci.RequestInfo()).last_block_app_hash == kv.app_hash()
    assert UpstreamKVStoreApp().info(abci.RequestInfo()).last_block_app_hash == b""


def test_commit_does_no_pass_over_the_state():
    """The hash is a function of ``size`` alone: an app whose state cannot
    be walked commits all the same."""

    class Unwalkable(dict):
        def items(self):
            raise AssertionError("Commit walked the state")

        __iter__ = keys = values = items

    app = UpstreamKVStoreApp()
    app.state = Unwalkable()
    for tx in (b"a=1", b"b=2"):
        app.deliver_tx(abci.RequestDeliverTx(tx=tx))
    assert app.commit(abci.RequestCommit()).data == ref.put_varint(2)
    assert app.info(abci.RequestInfo()).last_block_app_hash == ref.put_varint(2)


@pytest.mark.parametrize("make", [KVStoreApp, PersistentKVStoreApp])
def test_the_merkle_apps_keep_their_hashes(make):
    """``KVStoreApp`` and ``PersistentKVStoreApp`` hash the sorted state at
    Commit as before (the five accepted cells' generators pin it), and their
    DeliverTx, which now splits a tx once, answers as it did."""
    app = make()
    assert app.commit(abci.RequestCommit()).data == chaingen.merkle_root([])
    txs = [b"b=2", b"a=1", b"plain", b"a=3", b"e=", b"k=v=w"]
    for tx in txs:
        res = app.deliver_tx(abci.RequestDeliverTx(tx=tx))
        assert [(t.key, t.value) for t in res.tags] == [
            (b"app.key", tx.partition(b"=")[0]), (b"app.creator", b"kvstore")]
    want = chaingen.merkle_root(
        [b"a=3", b"b=2", b"e=", b"k=v=w", b"plain=plain"])
    assert app.commit(abci.RequestCommit()).data == want
    # as the parent of PR 43 computed it (git archive e414df7, the same txs)
    assert want.hex() == (
        "103b33dd797c416d557980fd9acd3107831a9d37b06d227673764d20bad3c7d4")
    assert app.size == len(txs) and app.state[b"a"] == b"3"


@pytest.mark.parametrize("n_txs,size", [(0, 0), (1, 250), (7, 33), (1000, 250)])
def test_the_blocks_hashes_are_the_programs(n_txs, size):
    from tendermint_tpu.types.tx import Tx, Txs

    rng = np.random.default_rng([n_txs, size])
    txs = chaingen.make_txs(rng, n_txs, size)
    assert all(len(tx) == size for tx in txs)
    assert ref.data_hash(txs) == Txs(Tx(t) for t in txs).hash()


@pytest.mark.parametrize("results", [0, 1, 40, 1000])
def test_the_results_hash_is_the_programs(results):
    from tendermint_tpu.state.store import ABCIResponses

    responses = ABCIResponses(deliver_tx=[
        abci.ResponseDeliverTx(code=abci.CODE_TYPE_OK,
                               tags=[abci.KVPair(key=b"app.key", value=b"k")])
        for _ in range(results)])
    assert responses.results_hash() == ref.results_hash(results)


@pytest.mark.parametrize("length", [0, 1, 65535, 65536, 65537, 262800])
def test_the_part_set_header_is_the_programs(length):
    from tendermint_tpu.types.part_set import PartSet

    data = np.random.default_rng(length).bytes(length)
    header = PartSet.from_data(data).header()
    assert ref.part_set_header(data) == (header.total, header.hash)


def test_a_responses_block_bytes_are_what_the_program_encoded():
    from tendermint_tpu.blockchain.messages import BlockResponseMessage, encode_msg
    from tendermint_tpu.testutil.chain import build_chain

    fx = build_chain(n_vals=4, n_heights=3, chain_id="bytes-chain", txs_per_block=3)
    block = fx.block_store.load_block(2)
    response = encode_msg(BlockResponseMessage(block))
    assert ref.block_bytes(response) == block.marshal()
    with pytest.raises(ValueError):
        ref.block_bytes(response + b"\x00")
