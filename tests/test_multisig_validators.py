"""A validator set keyed k-of-n (PubKeyMultisigThreshold) on the served path:
the host rules against the benchmark's plain reference, verify_commit through
the guarded batch with its lane and group counters, and the round trips a
node needs to reload such a set."""

import json

import numpy as np
import pytest

from benchmark import chaingen_multisig as gen
from benchmark import oracle_multisig as oracle
from benchmark.drivers.commit_stream_multisig import (
    _case,
    _program_key as _key,
    _with_templates,
)
from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto.keys import (
    PubKeyEd25519,
    PubKeySecp256k1,
    PrivKeySecp256k1,
    pubkey_from_json_obj,
)
from tendermint_tpu.crypto.multisig import (
    CompactBitArray,
    Multisignature,
    PubKeyMultisigThreshold,
)
from tendermint_tpu.libs import breaker, trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.types.validator_set import CommitError, Validator, ValidatorSet

CHAIN = "msig-test"
CONFIG = {"validators": 8, "voting_power": 10, "key_type": "multisig_threshold",
          "multisig": {"k": 2, "n": 3, "sub_key_type": "ed25519"}}
TRAFFIC = {"ring": 2, "first_height": 7, "signer_counts": {"2": 0.5, "3": 0.5}}
KINDS = ("bad_subsignature", "subsigs_swapped", "under_threshold", "too_many_sigs",
         "wrong_size", "flag_without_sig", "unflagged_signer")


@pytest.fixture(scope="module")
def inputs():
    ks = gen.make_keyset(CONFIG, 2**31 + 3)
    heights = _with_templates(gen.make_heights(TRAFFIC, 2**31 + 3), CHAIN)
    return ks, gen.sign_ring(ks, heights, TRAFFIC, 2**31 + 3)


def _commit(pre, valset):
    """(the block id to ask about, the program's Commit) of a generator's."""
    case = _case(pre, valset, CHAIN)
    return case.block_id, case.commit


@pytest.fixture()
def guarded():
    breaker.reset_device_guard()
    v = batch.GuardedBatchVerifier(batch.HostBatchVerifier())
    yield v
    breaker.reset_device_guard()


# -- the host rules against the reference ------------------------------------


def test_the_generators_encoding_is_the_programs(inputs):
    ks, ring = inputs
    for v in range(len(ks.keys)):
        assert _key(ks, v).bytes() == ks.keys[v]
    size, elems, subs = oracle.parse_signature(ring[0].sigs[0])
    ms = Multisignature.unmarshal(ring[0].sigs[0])
    assert (ms.bitarray.bits, bytes(ms.bitarray.elems), ms.sigs) == (size, elems, subs)
    assert ms.marshal() == ring[0].sigs[0]


@pytest.mark.parametrize("kind", ("valid",) + KINDS)
def test_verify_bytes_and_flatten_agree_with_the_reference(inputs, kind):
    ks, ring = inputs
    for seed in range(6):
        pre, v = ring[seed % 2], seed % len(ks.keys)
        if kind != "valid":
            pre, v = gen.tamper(pre, ks, kind, np.random.default_rng(seed))
        want = oracle.verify_bytes(pre.keys[v], pre.msgs[v], pre.sigs[v])
        assert want.ok == (kind == "valid")
        key = _key(ks, v)
        assert key.verify_bytes(pre.msgs[v], pre.sigs[v]) is want.ok, want.rule
        flat = key.flatten(pre.msgs[v], pre.sigs[v])
        if want.lanes and len(want.lanes) >= ks.k:
            # what rides the device is what the reference walks, in its order
            assert flat == want.lanes
        else:
            assert flat is None or len(flat) < ks.k


def test_more_signatures_than_keys_are_refused(inputs):
    """ROADMAP D13 (threshold_pubkey.go:46): n + 1 signatures beside k set
    bits whose signatures verify.  Fails at the parent of PR 39, where both
    ``verify_bytes`` and ``flatten`` took them."""
    ks, ring = inputs
    pre, v = gen.tamper(ring[0], ks, "too_many_sigs", np.random.default_rng(1))
    ms = Multisignature.unmarshal(pre.sigs[v])
    assert len(ms.sigs) == ks.n + 1 and ms.bitarray.count() >= ks.k
    key = _key(ks, v)
    assert key.verify_bytes(pre.msgs[v], pre.sigs[v]) is False
    assert key.flatten(pre.msgs[v], pre.sigs[v]) is None
    # n signatures beside k set bits stay accepted: the Go never looks at
    # the ones no bit points to
    flagged = [i for i in range(ks.n) if ms.bitarray.get_index(i)]
    full = gen.encode_signature(ks.n, flagged, ms.sigs[: ks.n])
    assert key.verify_bytes(pre.msgs[v], full) is oracle.verify_bytes(
        pre.keys[v], pre.msgs[v], full).ok is True


@pytest.mark.parametrize("cut", ["truncated", "left_over", "short_bit_array"])
def test_bytes_marshal_cannot_have_written_are_no_signature(inputs, cut):
    ks, ring = inputs
    sig = ring[0].sigs[0]
    bad = {"truncated": sig[:-1], "left_over": sig + b"\x00",
           "short_bit_array": sig[:3]}[cut]
    with pytest.raises(ValueError):
        Multisignature.unmarshal(bad)
    assert _key(ks, 0).verify_bytes(ring[0].msgs[0], bad) is False
    assert _key(ks, 0).flatten(ring[0].msgs[0], bad) is None
    assert oracle.verify_bytes(ks.keys[0], ring[0].msgs[0], bad).ok is False


# -- verify_commit through the guarded batch ---------------------------------


def _counters():
    m = get_verify_metrics()
    return (sum(m.multisig_groups.snapshot().values()),
            sum(m.multisig_lanes.snapshot().values()),
            m.host_fallback.snapshot().get(("multisig_structural",), 0.0),
            sum(v for k, v in m.calls.snapshot().items() if k[1] == "ed25519"))


@pytest.mark.parametrize("kind", ("valid",) + KINDS + gen.SCHEME_FREE)
def test_verify_commit_decides_as_the_reference(inputs, guarded, kind):
    ks, ring = inputs
    pre, v = ring[1], -1
    if kind != "valid":
        pre, v = gen.tamper(pre, ks, kind, np.random.default_rng(5))
    valset = ValidatorSet([Validator(_key(ks, i), p) for i, p in enumerate(pre.powers)])
    assert [x.pub_key.bytes() for x in valset.validators] == ks.keys
    verdicts, stands = gen.reference_verdicts(pre)
    asked, commit = _commit(pre, valset)
    block_id = commit.block_id
    g0, l0, h0, c0 = _counters()
    if stands:
        valset.verify_commit(CHAIN, asked, pre.at.height, commit, verifier=guarded)
    else:
        with pytest.raises(CommitError):
            valset.verify_commit(CHAIN, asked, pre.at.height, commit, verifier=guarded)
    g1, l1, h1, c1 = _counters()
    if not pre.structural_ok:  # refused before any signature
        assert (g1, l1, h1, c1) == (g0, l0, h0, c0)
        return
    # validator for validator, and the counters: one group a flattened
    # validator, one lane a sub-signature the reference walks, one host
    # decision a validator that cannot be flattened, ONE ed25519 dispatch
    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        CHAIN, block_id, pre.at.height, commit)
    g1, l1, h1, c1 = _counters()
    got = batch.verify_generic(pubkeys, msgs, sigs, verifier=guarded)
    g2, l2, h2, c2 = _counters()
    present = [x for x in verdicts if x is not None]
    assert [bool(x) for x in got] == [x.ok for x in present]
    on_device = [x for x in present if x.lanes and len(x.lanes) >= ks.k]
    assert g2 - g1 == len(on_device)
    assert l2 - l1 == sum(len(x.lanes) for x in on_device)
    assert h2 - h1 == len(present) - len(on_device)
    assert c2 - c1 == 1


def test_the_flatten_and_the_group_verdict_have_spans(inputs, guarded):
    ks, ring = inputs
    pre = ring[0]
    valset = ValidatorSet([Validator(_key(ks, i), p) for i, p in enumerate(pre.powers)])
    block_id, commit = _commit(pre, valset)
    trace.reset(1 << 12)
    trace.enable()
    try:
        valset.verify_commit(CHAIN, block_id, pre.at.height, commit, verifier=guarded)
        ed = ValidatorSet([Validator(PubKeyEd25519(ks.signers[i][0].pub), 10)
                           for i in range(4)])
        msgs = [b"m%d" % i for i in range(4)]
        by_pub = {s[0].pub: s[0] for s in ks.signers[:4]}
        batch.verify_generic(
            [x.pub_key for x in ed.validators], msgs,
            [by_pub[x.pub_key.bytes()].sign(m) for x, m in zip(ed.validators, msgs)],
            verifier=guarded)
        spans = [e for e in trace.export() if e.get("ph") == "X"]
    finally:
        trace.disable()
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e["args"])
    generic = by_name["verify.generic"]
    assert [a["keys"] for a in generic] == ["mixed", "ed25519"]
    (flat,), (red,) = by_name["multisig.flatten"], by_name["multisig.reduce"]
    assert flat["validators"] == 8 and flat["lanes"] == pre.lanes()
    assert flat["host_decided"] == 0 and red["groups"] == 8
    assert flat["parent_id"] == red["parent_id"] == generic[0]["span_id"]


# -- what a node needs to reload such a set ----------------------------------


def test_a_multisig_key_round_trips_through_its_bytes_and_json(inputs):
    ks, _ = inputs
    key = _key(ks, 3)
    again = PubKeyMultisigThreshold.from_bytes(key.bytes())
    assert again == key and again.k == 2 and again.address() == key.address()
    assert pubkey_from_json_obj(json.loads(json.dumps(key.to_json_obj()))) == key
    mixed = PubKeyMultisigThreshold(1, (
        PubKeyEd25519(ks.signers[0][0].pub),
        PrivKeySecp256k1.generate(b"\x07" * 32).pub_key()))
    again = PubKeyMultisigThreshold.from_bytes(mixed.bytes())
    assert again == mixed and isinstance(again.pubkeys[1], PubKeySecp256k1)
    for bad in (key.bytes()[:-1], key.bytes() + b"\x00", b"\x00" * 7,
                key.bytes().replace(b"PubKeyEd25519", b"PubKeyEd25518")):
        with pytest.raises(ValueError):
            PubKeyMultisigThreshold.from_bytes(bad)


def test_a_validator_set_and_a_genesis_with_multisig_members_round_trip(inputs):
    ks, ring = inputs
    members = [Validator(_key(ks, i), 10 + i) for i in range(len(ks.keys))]
    members.append(Validator(PubKeyEd25519(ks.signers[0][0].pub), 5))
    valset = ValidatorSet(members)
    valset.increment_accum(3)
    again = ValidatorSet.unmarshal(valset.marshal())
    assert again.hash() == valset.hash() and again.marshal() == valset.marshal()
    assert [(v.pub_key, v.voting_power, v.accum) for v in again.validators] == \
        [(v.pub_key, v.voting_power, v.accum) for v in valset.validators]
    assert again.get_proposer().address == valset.get_proposer().address

    doc = GenesisDoc(chain_id=CHAIN, genesis_time_ns=1, validators=[
        GenesisValidator(v.pub_key, v.voting_power, name=f"v{i}")
        for i, v in enumerate(members)])
    back = GenesisDoc.from_json(doc.to_json())
    assert [(g.pub_key, g.power, g.name) for g in back.validators] == \
        [(g.pub_key, g.power, g.name) for g in doc.validators]
    assert back.validator_hash() == doc.validator_hash() == valset.hash()
    # and the set read back verifies a commit its members signed
    only = ValidatorSet.unmarshal(ValidatorSet(
        [Validator(g.pub_key, 10) for g in back.validators[:-1]]).marshal())
    block_id, commit = _commit(ring[0], only)
    only.verify_commit(CHAIN, block_id, ring[0].at.height, commit,
                       verifier=batch.HostBatchVerifier())


def test_compact_bit_array_round_trip_is_strict():
    ba = CompactBitArray(5)
    ba.set_index(1, True)
    ba.set_index(4, True)
    assert CompactBitArray.from_bytes(ba.to_bytes()) == ba
    with pytest.raises(ValueError):
        CompactBitArray.from_bytes(ba.to_bytes()[:4])
