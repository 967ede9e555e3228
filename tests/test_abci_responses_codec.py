"""The stored form of ``state/store.ABCIResponses`` (PR 44): version 1 of the
record written with the stores' binary codec, the JSON records an earlier tree
left behind, and the three readers (``load_abci_responses``, the handshake's
replay of recorded responses, ``block_results``) over both."""

import base64
import hashlib
import subprocess
import sys
from types import SimpleNamespace

import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.examples.kvstore import KVStoreApp
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.encoding import codec
from tendermint_tpu.libs.db.kv import MemDB
from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
from tendermint_tpu.rpc.core.env import RPCEnv
from tendermint_tpu.state import store
from tendermint_tpu.state.state_types import State
from tendermint_tpu.state.store import ABCIResponses
from tendermint_tpu.testutil.chain import build_chain

V1 = b"\x01"
BYTES_FAMILY = "tendermint_state_abci_responses_bytes_total"


def _key(height):
    return b"abciResponsesKey:%d" % height


def _b64(b):
    return base64.b64encode(b).decode()


def _json_record(resp):
    """The record as the tree before PR 44 wrote it."""
    return abci.msg_to_json([resp.deliver_tx, resp.end_block, resp.begin_block])


def _tags(n, salt=b""):
    return [abci.KVPair(b"k%d" % i + salt, b"v" * i) for i in range(n)]


def _cell_results(n=1000):
    """sync64-full's results: code 0, the kvstore's two tags, a 124-byte key."""
    return [
        abci.ResponseDeliverTx(code=0, tags=[
            abci.KVPair(b"app.key", hashlib.sha512(b"%d" % i).hexdigest()[:124].encode()),
            abci.KVPair(b"app.creator", b"kvstore"),
        ])
        for i in range(n)
    ]


def _updates():
    return [
        abci.ValidatorUpdate("ed25519", b"\x11" * 32, 10),
        abci.ValidatorUpdate("secp256k1", b"\x02" + b"\x22" * 32, 7),
        abci.ValidatorUpdate("ed25519", b"\x33" * 32, 0),  # a removal
    ]


def _params(block_size, evidence, validator):
    return abci.ConsensusParams(
        block_size=abci.BlockSizeParams(max_bytes=22_020_096, max_gas=-1)
        if block_size else None,
        evidence=abci.EvidenceParams(max_age=100_000) if evidence else None,
        validator=abci.ValidatorParams(pub_key_types=["ed25519", "secp256k1"])
        if validator else None,
    )


def _full_end_block(groups=(True, True, True)):
    return abci.ResponseEndBlock(validator_updates=_updates(),
                                 consensus_param_updates=_params(*groups),
                                 tags=_tags(3, b"-end"))


CASES = {
    "cell_shape_1000_results_2_tags": lambda: ABCIResponses(
        _cell_results(), abci.ResponseEndBlock(), abci.ResponseBeginBlock()),
    "empty_block": lambda: ABCIResponses(
        [], abci.ResponseEndBlock(), abci.ResponseBeginBlock()),
    "nothing_at_all": lambda: ABCIResponses([], None, None),
    "nonzero_codes": lambda: ABCIResponses(
        [abci.ResponseDeliverTx(code=c) for c in (1, 127, 128, 2**32 - 1, -3)]),
    "data_log_info": lambda: ABCIResponses([
        abci.ResponseDeliverTx(code=4, data=bytes(range(256)),
                               log="nonce déjà vu: 取引 ✗", info="codespace/sdk"),
        abci.ResponseDeliverTx(data=b"\x00", log="", info="ï"),
    ]),
    "gas_both_ways": lambda: ABCIResponses([
        abci.ResponseDeliverTx(gas_wanted=200_000, gas_used=41_337),
        abci.ResponseDeliverTx(gas_wanted=-1, gas_used=0),
        abci.ResponseDeliverTx(gas_wanted=2**63 - 1, gas_used=-(2**63)),
    ]),
    "no_tags_and_many_tags": lambda: ABCIResponses([
        abci.ResponseDeliverTx(tags=[]),
        abci.ResponseDeliverTx(tags=_tags(300)),
        abci.ResponseDeliverTx(tags=[abci.KVPair(b"", b"")]),
    ]),
    "end_block_none_begin_block_empty": lambda: ABCIResponses(
        _cell_results(3), None, abci.ResponseBeginBlock()),
    "end_block_empty_begin_block_none": lambda: ABCIResponses(
        _cell_results(3), abci.ResponseEndBlock(), None),
    "end_block_updates_without_params": lambda: ABCIResponses(
        [], abci.ResponseEndBlock(validator_updates=_updates()), None),
    "end_block_empty_params": lambda: ABCIResponses(
        [], abci.ResponseEndBlock(consensus_param_updates=abci.ConsensusParams())),
    "end_block_no_key_types": lambda: ABCIResponses(
        [], abci.ResponseEndBlock(consensus_param_updates=abci.ConsensusParams(
            validator=abci.ValidatorParams(pub_key_types=[])))),
    "begin_block_full": lambda: ABCIResponses(
        _cell_results(2), _full_end_block(),
        abci.ResponseBeginBlock(tags=_tags(5, b"-begin"))),
}
# each ConsensusParams group present and absent, under a full EndBlock
for _groups in [(b, e, v) for b in (0, 1) for e in (0, 1) for v in (0, 1)]:
    CASES["end_block_full_params_%d%d%d" % _groups] = (
        lambda g=_groups: ABCIResponses(_cell_results(1), _full_end_block(g), None))


@pytest.fixture(params=sorted(CASES))
def case(request):
    return CASES[request.param]


def test_round_trip_is_equal(case):
    record = case().marshal()
    assert type(record) is bytes and record[:1] == V1
    back = ABCIResponses.unmarshal(record)
    assert back == case()
    assert back.marshal() == record


def test_a_record_in_the_json_form_still_unmarshals(case):
    old = _json_record(case())
    assert old[:1] == b"["
    assert ABCIResponses.unmarshal(old) == case()


def test_both_codec_backends_write_and_read_the_same_record(case, monkeypatch):
    served = case().marshal()
    monkeypatch.setattr(store, "Writer", codec._PyWriter)
    monkeypatch.setattr(store, "Reader", codec._PyReader)
    assert case().marshal() == served
    assert ABCIResponses.unmarshal(served) == case()


def test_the_cell_shape_is_a_third_of_the_json_form():
    resp = CASES["cell_shape_1000_results_2_tags"]()
    record = resp.marshal()
    # a result: six one-byte fields, the tag count, 1+7 + 1+124 + 1+11 + 1+7
    assert len(record) == 1 + 2 + 1000 * 160 + 3 + 3
    assert len(_json_record(resp)) > 2.5 * len(record)
    back = ABCIResponses.unmarshal(record)
    assert len(back.deliver_tx) == 1000
    assert all(r.code == 0 and len(r.tags) == 2 for r in back.deliver_tx)


@pytest.mark.parametrize("head", [b"\x00", b"\x02", b"{", b"\x5a", b"\xff"])
def test_an_unknown_version_byte_raises(head):
    record = CASES["begin_block_full"]().marshal()
    with pytest.raises(ValueError, match="unknown version"):
        ABCIResponses.unmarshal(head + record[1:])


def test_an_empty_record_raises():
    with pytest.raises(ValueError, match="unknown version"):
        ABCIResponses.unmarshal(b"")


@pytest.mark.parametrize("backend", ["served", "python"])
def test_every_truncated_tail_raises_and_returns_no_short_list(backend, monkeypatch):
    if backend == "python":
        monkeypatch.setattr(store, "Reader", codec._PyReader)
    record = CASES["begin_block_full"]().marshal()
    for cut in range(1, len(record)):
        with pytest.raises((EOFError, ValueError)):
            ABCIResponses.unmarshal(record[:cut])
    # the cell's shape cut inside its last results and just before its end
    record = CASES["cell_shape_1000_results_2_tags"]().marshal()
    for cut in (len(record) - 1, len(record) - 6, len(record) - 200, 500):
        with pytest.raises((EOFError, ValueError)):
            ABCIResponses.unmarshal(record[:cut])


def test_left_over_bytes_raise():
    record = CASES["begin_block_full"]().marshal()
    with pytest.raises(ValueError, match="left over"):
        ABCIResponses.unmarshal(record + b"\x00")


def test_a_count_larger_than_the_record_raises():
    w = codec.Writer().raw(V1).uvarint(2**40)
    with pytest.raises(EOFError):
        ABCIResponses.unmarshal(w.build())


# ---------------------------------------------------------------------------
# what save_abci_responses leaves in the store
# ---------------------------------------------------------------------------


def test_the_store_holds_the_records_bytes(case):
    db = MemDB()
    resp = case()
    store.save_abci_responses(db, 7, resp)
    raw = db.get(_key(7))
    assert type(raw) is bytes and raw[:1] == V1
    assert raw == case().marshal()
    # no shared object: the original is gone before the record is read
    resp.deliver_tx.clear()
    resp.end_block = resp.begin_block = None
    del resp
    loaded = store.load_abci_responses(db, 7)
    assert loaded == case()
    assert store.load_abci_responses(db, 7) is not loaded


def test_the_records_bytes_decode_in_a_fresh_process(tmp_path):
    """What a file-backed DB would put on disk: another interpreter, given
    the bytes alone, reads all the results with their tags."""
    resp = CASES["begin_block_full"]()
    resp.deliver_tx.extend(_cell_results(1000))
    db = MemDB()
    store.save_abci_responses(db, 3, resp)
    path = tmp_path / "record.bin"
    path.write_bytes(db.get(_key(3)))
    child = (
        "import hashlib, sys\n"
        "from tendermint_tpu.abci import types as abci\n"
        "from tendermint_tpu.state.store import ABCIResponses\n"
        "r = ABCIResponses.unmarshal(open(sys.argv[1], 'rb').read())\n"
        "doc = abci.msg_to_json([r.deliver_tx, r.end_block, r.begin_block])\n"
        "print(len(r.deliver_tx), hashlib.sha256(doc).hexdigest())\n"
    )
    out = subprocess.run([sys.executable, "-c", child, str(path)], check=True,
                         capture_output=True, text=True, timeout=60).stdout.split()
    assert out == ["1002", hashlib.sha256(_json_record(resp)).hexdigest()]


def test_the_counter_grows_by_the_records_length(case, verify_counters):
    db = MemDB()
    before = verify_counters(BYTES_FAMILY)
    store.save_abci_responses(db, 1, case())
    assert verify_counters(BYTES_FAMILY) - before == len(db.get(_key(1)))
    store.save_abci_responses(db, 2, case())
    assert verify_counters(BYTES_FAMILY) - before == 2 * len(case().marshal())


def test_the_counter_is_exposed_from_zero():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    series = [line for line in VerifyMetrics().registry.expose_text().splitlines()
              if line.startswith(BYTES_FAMILY + " ")]
    assert [float(line.split()[-1]) for line in series] == [0.0]


def test_a_missing_height_still_raises_its_own_error():
    with pytest.raises(store.NoABCIResponsesForHeightError):
        store.load_abci_responses(MemDB(), 5)


# ---------------------------------------------------------------------------
# the readers, over a store written today and one an earlier tree wrote
# ---------------------------------------------------------------------------

N = 3
FORMS = ("binary", "json")


def _chain():
    """A 1-validator chain of N blocks through the kvstore, the state before
    each height kept, every block with results that carry tags."""
    states = {}

    def txs(h, st):
        states[h - 1] = st.marshal()
        return [b"k%d-%d=v%d" % (h, j, h) for j in range(h + 1)]

    fx = build_chain(n_vals=1, n_heights=N, chain_id="responses-chain",
                     on_height=txs, app_factory=KVStoreApp)
    states[N] = fx.state.marshal()
    return fx, states


def _as_stored_by(form, fx, heights=range(1, N + 1)):
    """Leaves the records at ``heights`` in ``form``; returns what they hold."""
    held = {}
    for h in heights:
        held[h] = store.load_abci_responses(fx.state_db, h)
        if form == "json":
            fx.state_db.set(_key(h), _json_record(held[h]))
        assert fx.state_db.get(_key(h))[:1] == (b"[" if form == "json" else V1)
    return held


@pytest.mark.parametrize("form", FORMS)
def test_apply_block_persists_what_update_state_hashed(form):
    """tests/test_state.py::TestBlockExecutor::test_abci_responses_persisted,
    for both forms."""
    fx, states = _chain()
    _as_stored_by(form, fx)
    for h in range(1, N + 1):
        resp = store.load_abci_responses(fx.state_db, h)
        assert len(resp.deliver_tx) == h + 1
        assert all(r.code == abci.CODE_TYPE_OK for r in resp.deliver_tx)
        assert [r.tags[0].value for r in resp.deliver_tx] == [
            b"k%d-%d" % (h, j) for j in range(h + 1)]
        assert State.unmarshal(states[h]).last_results_hash == resp.results_hash()


@pytest.mark.parametrize("form", FORMS)
def test_handshake_replays_recorded_responses(form):
    """The crash between save_abci_responses and save_state: the app has run
    Commit for block N, the state is at N - 1, and the handshake applies N
    from the record through ``_MockAppConnConsensus``."""
    fx, states = _chain()
    recorded = _as_stored_by(form, fx, heights=[N])[N]
    app = KVStoreApp()
    for h in range(1, N + 1):
        for tx in fx.block_store.load_block(h).data.txs:
            app.deliver_tx(abci.RequestDeliverTx(tx=bytes(tx)))
        app.commit(abci.RequestCommit())
    size = app.size
    conn = MultiAppConn(LocalClientCreator(app))
    conn.start()
    hs = Handshaker(fx.state_db, State.unmarshal(states[N - 1]), fx.block_store,
                    fx.genesis)
    state = hs.handshake(conn)
    conn.stop()
    want = State.unmarshal(states[N])
    assert state.last_block_height == N and hs.n_blocks == 1
    assert state.app_hash == want.app_hash
    assert state.last_results_hash == want.last_results_hash
    assert state.marshal() == states[N]
    assert store.load_state(fx.state_db).marshal() == states[N]
    assert (app.height, app.size) == (N, size)  # nothing delivered twice
    # the replay saved the record again: only the new form is ever written
    assert fx.state_db.get(_key(N))[:1] == V1
    assert store.load_abci_responses(fx.state_db, N) == recorded


def test_block_results_answers_the_same_for_both_forms():
    answers = {}
    for form in FORMS:
        fx, _states = _chain()
        held = _as_stored_by(form, fx)
        env = RPCEnv(SimpleNamespace(block_store=fx.block_store,
                                     state_db=fx.state_db))
        answers[form] = [env.block_results(h) for h in range(1, N + 1)]
        assert env.block_results()["height"] == N
    assert answers["binary"] == answers["json"]
    for h, got in enumerate(answers["binary"], start=1):
        dtxs = got["results"]["DeliverTx"]
        assert got["height"] == h and len(dtxs) == h + 1
        assert all(d["code"] == 0 and len(d["tags"]) == 2 for d in dtxs)
        assert dtxs == [
            {"code": r.code, "data": "", "log": r.log, "gas_wanted": 0,
             "gas_used": 0,
             "tags": [{"key": _b64(kv.key), "value": _b64(kv.value)}
                      for kv in r.tags]}
            for r in held[h].deliver_tx]
