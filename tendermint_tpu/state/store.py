"""State persistence (ref: state/store.go:29-300).

Keys mirror the reference schema: the State snapshot under 'stateKey',
per-height validator sets ('validatorsKey:<H>'), per-height consensus params
('consensusParamsKey:<H>'), and per-height ABCIResponses ('abciResponsesKey:<H>').
Validator/params records are only written at change heights; lookups chase the
'last changed' pointer exactly like the reference.

Every record is written with `encoding/codec.Writer`.  The layout of an
`ABCIResponses` record (`abciResponsesKey:<H>`), version 1, fields in order
(uvarint = LEB128, svarint = zig-zag LEB128 of an int64, bytes = uvarint length
then the bytes, string = bytes of UTF-8, bool = one byte 0 | 1):

    byte      0x01                      the version
    uvarint   n                         DeliverTx results, then n times:
      svarint code; bytes data; string log; string info;
      svarint gas_wanted; svarint gas_used; TAGS
    bool      end_block is present, and if so:
      uvarint m, then m times:          validator updates
        string pub_key_type; bytes pub_key; svarint power
      bool    consensus_param_updates is present, and if so:
        bool  block_size is present     [svarint max_bytes; svarint max_gas]
        bool  evidence is present       [svarint max_age]
        bool  validator is present      [uvarint k, then k times string pub_key_type]
      TAGS
    bool      begin_block is present    [TAGS]
    TAGS  =   uvarint t, then t times:  bytes key; bytes value

Nothing follows the last field: a reader refuses left-over bytes as it refuses
a short record.  Both singletons are written field by field like the results,
so a record depends on no other codec.  Records written before version 1 are
the ABCI socket transport's JSON (`abci.msg_to_json` of the three fields as a
list) and start with `[` (0x5B); `ABCIResponses.unmarshal` reads them by that
first byte and nothing ever writes them again.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from tendermint_tpu.abci import types as abci
from tendermint_tpu.encoding.codec import Reader, Writer
from tendermint_tpu.libs.db.kv import DB
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.state.state_types import State, state_from_genesis
from tendermint_tpu.types import ConsensusParams, GenesisDoc, ValidatorSet

_STATE_KEY = b"stateKey"

# an ABCIResponses record's first byte: its version, or the `[` of the JSON
# list an earlier tree stored
_RECORD_V1 = b"\x01"
_RECORD_JSON = b"["


def _validators_key(height: int) -> bytes:
    return b"validatorsKey:%d" % height


def _params_key(height: int) -> bytes:
    return b"consensusParamsKey:%d" % height


def _abci_responses_key(height: int) -> bytes:
    return b"abciResponsesKey:%d" % height


class NoValSetForHeightError(Exception):
    pass


class NoABCIResponsesForHeightError(Exception):
    pass


@dataclass
class ABCIResponses:
    """Responses from executing a block, persisted for replay/indexing
    (ref state.go ABCIResponses)."""

    deliver_tx: List[abci.ResponseDeliverTx] = field(default_factory=list)
    end_block: Optional[abci.ResponseEndBlock] = None
    begin_block: Optional[abci.ResponseBeginBlock] = None

    def results_hash(self) -> bytes:
        from tendermint_tpu.types import ABCIResults

        return ABCIResults.from_deliver_txs(self.deliver_tx).hash()

    def marshal(self) -> bytes:
        """The stored record (layout in the module's docstring).  One Writer
        and one pass: a block of 1,000 results is 12,000 calls into it."""
        w = Writer()
        w.raw(_RECORD_V1)
        w.uvarint(len(self.deliver_tx))
        svarint, put_bytes, string = w.svarint, w.bytes, w.string
        for r in self.deliver_tx:
            svarint(r.code)
            put_bytes(r.data)
            string(r.log)
            string(r.info)
            svarint(r.gas_wanted)
            svarint(r.gas_used)
            _write_tags(w, r.tags)
        eb = self.end_block
        w.bool(eb is not None)
        if eb is not None:
            w.uvarint(len(eb.validator_updates))
            for vu in eb.validator_updates:
                w.string(vu.pub_key_type).bytes(vu.pub_key).svarint(vu.power)
            _write_param_updates(w, eb.consensus_param_updates)
            _write_tags(w, eb.tags)
        bb = self.begin_block
        w.bool(bb is not None)
        if bb is not None:
            _write_tags(w, bb.tags)
        return w.build()

    @classmethod
    def unmarshal(cls, data: bytes) -> "ABCIResponses":
        """Reads a record by its first byte: version 1, or the JSON an
        earlier version stored.  A short record raises EOFError, an unknown
        version or left-over bytes ValueError; nothing partial is returned."""
        head = data[:1]
        if head == _RECORD_JSON:
            dtxs, eb, bb = abci.msg_from_json(data)
            return cls(deliver_tx=dtxs, end_block=eb, begin_block=bb)
        if head != _RECORD_V1:
            raise ValueError(f"ABCIResponses record of unknown version {head!r}")
        r = Reader(data)
        r.raw(1)
        svarint, get_bytes, string = r.svarint, r.bytes, r.string
        result = abci.ResponseDeliverTx
        dtxs = [
            result(svarint(), get_bytes(), string(), string(), svarint(),
                   svarint(), _read_tags(r))
            for _ in range(r.uvarint())
        ]
        eb = bb = None
        if r.bool():
            updates = [
                abci.ValidatorUpdate(r.string(), r.bytes(), r.svarint())
                for _ in range(r.uvarint())
            ]
            eb = abci.ResponseEndBlock(updates, _read_param_updates(r),
                                       _read_tags(r))
        if r.bool():
            bb = abci.ResponseBeginBlock(_read_tags(r))
        if not r.at_end():
            raise ValueError(
                f"ABCIResponses record has {r.remaining()} bytes left over")
        return cls(deliver_tx=dtxs, end_block=eb, begin_block=bb)


def _write_tags(w: Writer, tags: List[abci.KVPair]) -> None:
    w.uvarint(len(tags))
    put_bytes = w.bytes
    for kv in tags:
        put_bytes(kv.key)
        put_bytes(kv.value)


def _read_tags(r: Reader) -> List[abci.KVPair]:
    get_bytes, pair = r.bytes, abci.KVPair
    return [pair(get_bytes(), get_bytes()) for _ in range(r.uvarint())]


def _write_param_updates(w: Writer, params: Optional[abci.ConsensusParams]) -> None:
    w.bool(params is not None)
    if params is None:
        return
    w.bool(params.block_size is not None)
    if params.block_size is not None:
        w.svarint(params.block_size.max_bytes).svarint(params.block_size.max_gas)
    w.bool(params.evidence is not None)
    if params.evidence is not None:
        w.svarint(params.evidence.max_age)
    w.bool(params.validator is not None)
    if params.validator is not None:
        w.uvarint(len(params.validator.pub_key_types))
        for key_type in params.validator.pub_key_types:
            w.string(key_type)


def _read_param_updates(r: Reader) -> Optional[abci.ConsensusParams]:
    if not r.bool():
        return None
    params = abci.ConsensusParams()
    if r.bool():
        params.block_size = abci.BlockSizeParams(r.svarint(), r.svarint())
    if r.bool():
        params.evidence = abci.EvidenceParams(r.svarint())
    if r.bool():
        params.validator = abci.ValidatorParams(
            [r.string() for _ in range(r.uvarint())])
    return params


# ---------------------------------------------------------------------------
# load/save
# ---------------------------------------------------------------------------


def load_state(db: DB) -> Optional[State]:
    raw = db.get(_STATE_KEY)
    return State.unmarshal(raw) if raw else None


def save_state(db: DB, state: State) -> None:
    """Persist the snapshot + the next height's validators/params records
    (ref store.go saveState)."""
    next_height = state.last_block_height + 1
    if next_height == 1:
        # bootstrap: genesis validators recorded for height 1 (the params
        # record for height 1 is written by the unconditional save below)
        save_validators_info(db, next_height, state.last_height_validators_changed,
                             state.validators)
    save_validators_info(db, next_height + 1, state.last_height_validators_changed,
                         state.next_validators)
    save_consensus_params_info(
        db, next_height, state.last_height_consensus_params_changed,
        state.consensus_params,
    )
    db.set_sync(_STATE_KEY, state.marshal())


def load_state_from_db_or_genesis(db: DB, genesis: GenesisDoc) -> State:
    state = load_state(db)
    if state is None or state.is_empty():
        state = state_from_genesis(genesis)
    return state


# validators per height ------------------------------------------------------


def save_validators_info(
    db: DB, height: int, last_changed: int, vals: Optional[ValidatorSet]
) -> None:
    """Write a record at `height`; the full set is stored only at change
    heights, otherwise just the pointer (ref store.go:149-170)."""
    w = Writer()
    w.svarint(last_changed)
    if height == last_changed and vals is not None:
        w.bool(True)
        vals.encode(w)
    else:
        w.bool(False)
    db.set(_validators_key(height), w.build())


def load_validators(db: DB, height: int) -> ValidatorSet:
    raw = db.get(_validators_key(height))
    if raw is None:
        raise NoValSetForHeightError(height)
    r = Reader(raw)
    last_changed = r.svarint()
    if r.bool():
        return ValidatorSet.decode(r)
    # chase the pointer to the change height
    raw2 = db.get(_validators_key(last_changed))
    if raw2 is None:
        raise NoValSetForHeightError(height)
    r2 = Reader(raw2)
    r2.svarint()
    if not r2.bool():
        raise NoValSetForHeightError(height)
    return ValidatorSet.decode(r2)


# consensus params per height ------------------------------------------------


def save_consensus_params_info(
    db: DB, height: int, last_changed: int, params: ConsensusParams
) -> None:
    w = Writer()
    w.svarint(last_changed)
    if height == last_changed:
        w.bool(True)
        params.encode(w)
    else:
        w.bool(False)
    db.set(_params_key(height), w.build())


def load_consensus_params(db: DB, height: int) -> ConsensusParams:
    raw = db.get(_params_key(height))
    if raw is None:
        raise NoValSetForHeightError(f"params @ {height}")
    r = Reader(raw)
    last_changed = r.svarint()
    if r.bool():
        return ConsensusParams.decode(r)
    raw2 = db.get(_params_key(last_changed))
    if raw2 is None:
        raise NoValSetForHeightError(f"params @ {height}")
    r2 = Reader(raw2)
    r2.svarint()
    if not r2.bool():
        raise NoValSetForHeightError(f"params @ {height}")
    return ConsensusParams.decode(r2)


# ABCI responses -------------------------------------------------------------


def save_abci_responses(db: DB, height: int, responses: ABCIResponses) -> None:
    record = responses.marshal()
    db.set(_abci_responses_key(height), record)
    get_verify_metrics().abci_responses_bytes.add(float(len(record)))


def load_abci_responses(db: DB, height: int) -> ABCIResponses:
    raw = db.get(_abci_responses_key(height))
    if raw is None:
        raise NoABCIResponsesForHeightError(height)
    return ABCIResponses.unmarshal(raw)
