"""The multisig arm of ``verify_generic`` in column form: ``multisig.
flatten_columns`` reads the marshalled signatures in place, and what it
flattens, in which order, and what it leaves to the host is held here to
three independent readings of the same rules: the host's ``verify_bytes``,
the benchmark's plain reference, and the walk over ``Multisignature``
objects that the batch path made before (kept below as ``_by_objects``)."""

import numpy as np
import pytest

from benchmark import chaingen_multisig as gen
from benchmark import oracle_multisig as oracle
from benchmark.drivers.commit_stream_multisig import (
    _program_key as _key,
    _with_templates,
)
from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto.keys import (
    PrivKeyEd25519,
    PrivKeySecp256k1,
    PubKeyEd25519,
)
from tendermint_tpu.crypto.multisig import (
    Multisignature,
    PubKeyMultisigThreshold,
    flatten_columns,
)
from tendermint_tpu.libs import breaker, trace
from tendermint_tpu.libs.metrics import VerifyMetrics, get_verify_metrics
from tendermint_tpu.types import BlockID, Commit, SignedMsgType, Vote
from tendermint_tpu.types.core import PartSetHeader
from tendermint_tpu.types.validator_set import CommitError, Validator, ValidatorSet

CHAIN = "msig-columns"
CONFIG = {"validators": 8, "voting_power": 10, "key_type": "multisig_threshold",
          "multisig": {"k": 3, "n": 5, "sub_key_type": "ed25519"}}
TRAFFIC = {"ring": 2, "first_height": 7,
           "signer_counts": {"3": 0.5, "4": 0.25, "5": 0.25}}
KINDS = ("bad_subsignature", "subsigs_swapped", "under_threshold", "too_many_sigs",
         "wrong_size", "flag_without_sig", "unflagged_signer") + gen.SCHEME_FREE
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def inputs():
    ks = gen.make_keyset(CONFIG, SEED)
    heights = _with_templates(gen.make_heights(TRAFFIC, SEED), CHAIN)
    return ks, gen.sign_ring(ks, heights, TRAFFIC, SEED)


@pytest.fixture()
def guarded():
    breaker.reset_device_guard()
    v = batch.GuardedBatchVerifier(batch.HostBatchVerifier())
    yield v
    breaker.reset_device_guard()


def _by_objects(key, msg, sig):
    """The lanes of one member as the batch path found them before it read
    the bytes in place: unmarshal into objects, the size rules, the walk
    over ``get_index``.  None: the member is the host's."""
    try:
        ms = Multisignature.unmarshal(sig)
    except ValueError:
        return None
    n = len(key.pubkeys)
    if ms.bitarray.bits != n or not key.k <= len(ms.sigs) <= n:
        return None
    if ms.bitarray.count() > len(ms.sigs):
        return None
    out = []
    for i in range(n):
        if ms.bitarray.get_index(i):
            sub = ms.sigs[len(out)]
            if not isinstance(key.pubkeys[i], PubKeyEd25519) or len(sub) != 64:
                return None
            out.append((key.pubkeys[i].bytes(), msg, sub))
    return out if len(out) >= key.k else None


def _columns(keys, msgs, sigs):
    """(groups, the lanes of each flattened member by its place) of one call
    over every member."""
    pubs, lane_msgs, lane_sigs = [], [], []
    g = flatten_columns(keys, msgs, sigs, range(len(keys)), pubs, lane_msgs, lane_sigs)
    assert len(pubs) == len(lane_msgs) == len(lane_sigs) == int(g.lanes.sum())
    lanes = {}
    for i, start, count in zip(g.member.tolist(), g.start.tolist(), g.lanes.tolist()):
        lanes[i] = list(zip(pubs[start:start + count], lane_msgs[start:start + count],
                            lane_sigs[start:start + count]))
    # the runs lie end to end in the members' order, none empty
    assert g.start.tolist() == np.cumsum([0] + g.lanes.tolist())[:-1].tolist()
    assert sorted(lanes) == g.member.tolist() and all(lanes.values())
    assert sorted(list(lanes) + g.host) == list(range(len(keys)))
    return g, lanes


def _hold(keys, msgs, sigs, reference_keys=None):
    """Every member of the call against the three readings.  Returns the
    places of the members flattened."""
    g, lanes = _columns(keys, msgs, sigs)
    for i, key in enumerate(keys):
        want = _by_objects(key, msgs[i], sigs[i])
        assert lanes.get(i) == want, (i, sigs[i].hex())
        assert key.flatten(msgs[i], sigs[i]) == want
        if want is None:
            verdict = key.verify_bytes(msgs[i], sigs[i])
        else:
            verdict = all(ed.verify(p, m, s) for p, m, s in want)
            assert verdict is key.verify_bytes(msgs[i], sigs[i])
        if reference_keys is not None:
            ref = oracle.verify_bytes(reference_keys[i], msgs[i], sigs[i])
            assert verdict is ref.ok, (i, ref.rule)
            if want is not None:  # what rides the device is what the Go walks
                assert want == ref.lanes
            else:
                assert not ref.lanes or any(len(s) != 64 for _, _, s in ref.lanes)
    return g.member.tolist()


# -- (i) the traffic's tampers and seeded fuzz of the marshalled bytes --------


@pytest.mark.parametrize("kind", ("valid",) + KINDS)
def test_the_columns_are_the_references_walk_over_each_tamper(inputs, kind):
    ks, ring = inputs
    keys = [_key(ks, v) for v in range(len(ks.keys))]
    for seed in range(4):
        pre, v = ring[seed % 2], -1
        if kind != "valid":
            pre, v = gen.tamper(pre, ks, kind, np.random.default_rng(seed))
        present = [i for i, s in enumerate(pre.sigs) if s is not None]
        flattened = _hold(
            [keys[i] for i in present], [pre.msgs[i] for i in present],
            [pre.sigs[i] for i in present], [pre.keys[i] for i in present])
        on_host = [present[j] for j in range(len(present)) if j not in flattened]
        structural = kind in ("under_threshold", "too_many_sigs", "wrong_size",
                              "flag_without_sig")
        assert on_host == ([v] if structural else [])


def _mutations(sig, kind, rng):
    """Marshalled bytes round ``sig``, a valid signature whose sub-signatures
    are all 64 bytes long."""
    size, elems, subs = oracle.parse_signature(sig)
    nbytes = len(elems)
    flagged = [i for i in range(size) if oracle.get_index(elems, size, i)]
    count_at = 4 + nbytes
    if kind == "truncated":  # every truncation point
        return [sig[:cut] for cut in range(len(sig))]
    if kind == "appended":
        return [sig + bytes([b]) for b in (0, 1, 0x40, 0xFF)]
    if kind == "length_fields":  # each of them one more and one less
        out = []
        fields = [(0, 4), (count_at, 2)] + [
            (count_at + 2 + 66 * j, 2) for j in range(len(subs))]
        for at, width in fields:
            value = int.from_bytes(sig[at:at + width], "big")
            for other in (value - 1, value + 1):
                out.append(sig[:at] + other.to_bytes(width, "big") + sig[at + width:])
        return out
    if kind == "more_bits_than_sigs":
        unset = [i for i in range(size) if i not in flagged]
        out = [gen.encode_signature(size, list(range(size)), subs[:-1])]
        if unset:
            out.append(gen.encode_signature(size, sorted(flagged + unset[:1]), subs))
        return out
    if kind in ("subsig_63", "subsig_65"):  # each flagged one in turn
        out = []
        for j in range(len(subs)):
            odd = list(subs)
            odd[j] = subs[j][:63] if kind == "subsig_63" else subs[j] + b"\x00"
            out.append(gen.encode_signature(size, flagged, odd))
        return out
    if kind == "unflagged_trailing":
        # signatures no bit points to, of any length: accepted while the
        # list holds at most n, as the Go never looks at them
        return [gen.encode_signature(size, flagged, subs + [extra] * m)
                for extra in (b"", b"\x05" * 63, b"\x05" * 64, b"\x05" * 65)
                for m in range(1, size - len(subs) + 2)]
    if kind == "pad_bits":  # the last byte's bits past n are not bits
        pad = (1 << (-size % 8)) - 1
        return [sig[:count_at - 1] + bytes([sig[count_at - 1] | p]) + sig[count_at:]
                for p in {pad, pad & 1, pad & 0b101}]
    if kind == "random_bytes":  # one to three bytes, half of them in the header
        out = []
        for _ in range(200):
            bad = bytearray(sig)
            for _ in range(int(rng.integers(1, 4))):
                span = count_at + 4 if rng.random() < 0.5 else len(sig)
                bad[int(rng.integers(0, span))] = int(rng.integers(0, 256))
            out.append(bytes(bad))
        return out
    raise ValueError(kind)


FUZZ = ("truncated", "appended", "length_fields", "more_bits_than_sigs",
        "subsig_63", "subsig_65", "unflagged_trailing", "pad_bits", "random_bytes")


@pytest.mark.parametrize("kind", FUZZ)
def test_fuzzed_bytes_flatten_as_the_host_and_the_reference_decide(inputs, kind):
    ks, ring = inputs
    rng = np.random.default_rng([SEED, FUZZ.index(kind)])
    keys, ref_keys, msgs, sigs = [], [], [], []
    for v in range(len(ks.keys)):  # 3, 4 and 5 signers among them
        for bad in _mutations(ring[0].sigs[v], kind, rng):
            keys.append(_key(ks, v))
            ref_keys.append(ks.keys[v])
            msgs.append(ring[0].msgs[v])
            sigs.append(bad)
    counts = {len(oracle.parse_signature(s)[2]) for s in ring[0].sigs}
    assert counts == {3, 4, 5}
    flattened = _hold(keys, msgs, sigs, ref_keys)
    if kind in ("unflagged_trailing", "pad_bits"):
        # still valid: the extra signatures fit in n, pad bits flag nobody
        want = [len(oracle.parse_signature(s)[2]) <= ks.n for s in sigs]
        assert [i in flattened for i in range(len(sigs))] == want
        assert all(keys[i].verify_bytes(msgs[i], sigs[i]) for i in flattened)
    elif kind != "random_bytes":
        ok = [i for i in flattened if keys[i].verify_bytes(msgs[i], sigs[i])]
        # a count of one less that still holds every flagged signature and
        # leaves whole signatures over is no signature: nothing here verifies
        assert ok == []


# -- (ii) any k of any n -------------------------------------------------------


def _threshold_key(k, n, seed=0):
    privs = [PrivKeyEd25519.generate(bytes([seed, i]) * 16) for i in range(n)]
    return privs, PubKeyMultisigThreshold(k, tuple(p.pub_key() for p in privs))


def _sign(privs, flagged, msg, n=None):
    return gen.encode_signature(
        len(privs) if n is None else n, flagged, [privs[i].sign(msg) for i in flagged])


@pytest.mark.parametrize("n", (1, 5, 8, 9, 16))
def test_k_of_n_over_one_and_two_bit_array_bytes(n):
    rng = np.random.default_rng(n)
    keys, ref_keys, msgs, sigs, valid = [], [], [], [], []
    for k in sorted({1, (n + 1) // 2, n}):
        privs, key = _threshold_key(k, n, seed=k)
        ref_key = gen.encode_pubkey(k, [p.bytes() for p in key.pubkeys])
        assert ref_key == key.bytes()
        for signers in range(max(1, k - 1), n + 1):
            flagged = sorted(rng.permutation(n)[:signers].tolist())
            msg = b"k-of-n|%d|%d|%d" % (k, n, signers)
            sig = _sign(privs, flagged, msg)
            nbytes = (n + 7) // 8
            padded = bytearray(sig)
            padded[4 + nbytes - 1] |= (1 << (-n % 8)) - 1  # every pad bit
            for variant in (sig, bytes(padded)):
                keys.append(key)
                ref_keys.append(ref_key)
                msgs.append(msg)
                sigs.append(variant)
                valid.append(signers >= k)
    flattened = _hold(keys, msgs, sigs, ref_keys)
    assert [i in flattened for i in range(len(keys))] == valid
    assert [keys[i].verify_bytes(msgs[i], sigs[i]) for i in range(len(keys))] == valid


# -- (iii)–(vi) through verify_generic and verify_commit -----------------------


class Counting:
    """A verifier that counts what it is asked, in which form."""

    name = "counting"

    def __init__(self, column_form=True):
        self.inner = batch.HostBatchVerifier()
        self.asked = []
        if column_form:
            self.verify_ed25519_raw = self._raw

    def _raw(self, pubs, msgs, sigs):
        assert all(type(c) is list for c in (pubs, msgs, sigs))
        assert {len(p) for p in pubs} == {32} and {len(s) for s in sigs} == {64}
        self.asked.append(("ed25519_raw", len(pubs)))
        return self.inner.verify_ed25519_raw(pubs, msgs, sigs)

    def verify_ed25519(self, items):
        assert all(isinstance(it, batch.SigItem) for it in items)
        self.asked.append(("ed25519_items", len(items)))
        return self.inner.verify_ed25519(items)

    def verify_secp256k1(self, items):
        self.asked.append(("secp256k1", len(items)))
        return self.inner.verify_secp256k1(items)


def _counters():
    m = get_verify_metrics()
    return {
        "groups": sum(m.multisig_groups.snapshot().values()),
        "lanes": sum(m.multisig_lanes.snapshot().values()),
        "structural": m.host_fallback.snapshot()[("multisig_structural",)],
        "ed25519": sum(v for k, v in m.calls.snapshot().items() if k[1] == "ed25519"),
        "secp256k1": sum(v for k, v in m.calls.snapshot().items()
                         if k[1] == "secp256k1"),
    }


def _grown(before):
    return {k: v - before[k] for k, v in _counters().items()}


@pytest.fixture(scope="module")
def mixed():
    """A set of eleven: four 3-of-5 and one 2-of-9 multisig members, one
    2-of-3 with a secp256k1 sub-key, three plain ed25519 and two secp256k1
    members, in the order a validator set keeps."""
    signers = {}
    members = []
    for v in range(4):
        privs, key = _threshold_key(3, 5, seed=10 + v)
        signers[key.address()] = ("multisig", privs, [0, 2, 3, 4][: 3 + v % 3])
        members.append(key)
    privs, key = _threshold_key(2, 9, seed=20)
    signers[key.address()] = ("multisig", privs, [1, 8])
    members.append(key)
    sub = [PrivKeyEd25519.generate(b"\x31" * 32), PrivKeySecp256k1.generate(b"\x32" * 32),
           PrivKeyEd25519.generate(b"\x33" * 32)]
    key = PubKeyMultisigThreshold(2, tuple(p.pub_key() for p in sub))
    signers[key.address()] = ("multisig", sub, [0, 1])
    members.append(key)
    for v in range(3):
        priv = PrivKeyEd25519.generate(bytes([0x40 + v]) * 32)
        signers[priv.pub_key().address()] = ("plain", priv, None)
        members.append(priv.pub_key())
    for v in range(2):
        priv = PrivKeySecp256k1.generate(bytes([0x50 + v]) * 32)
        signers[priv.pub_key().address()] = ("plain", priv, None)
        members.append(priv.pub_key())
    return ValidatorSet([Validator(k, 10) for k in members]), signers


def _mixed_commit(valset, signers, absent=(), height=9, spoil=None):
    """Every member but ``absent`` precommits; ``spoil`` maps a place to a
    function of that member's signature."""
    block_id = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x08" * 32))
    votes = []
    for i, val in enumerate(valset.validators):
        if i in absent:
            votes.append(None)
            continue
        fields = dict(vote_type=SignedMsgType.PRECOMMIT, height=height, round=0,
                      timestamp_ns=1_700_000_000_000_000_000 + i, block_id=block_id,
                      validator_address=val.address, validator_index=i)
        msg = Vote(signature=b"", **fields).sign_bytes(CHAIN)
        kind, priv, flagged = signers[val.address]
        if kind == "plain":
            sig = priv.sign(msg)
        else:
            sig = gen.encode_signature(
                len(priv), flagged, [priv[j].sign(msg) for j in flagged])
        if spoil and i in spoil:
            sig = spoil[i](sig)
        votes.append(Vote(signature=sig, **fields))
    return block_id, Commit(block_id, votes)


def _places(valset, signers, kind, column_form=None):
    out = []
    for i, val in enumerate(valset.validators):
        k, priv, _ = signers[val.address]
        if k != kind:
            continue
        all_ed = kind == "multisig" and all(
            isinstance(p, PrivKeyEd25519) for p in priv)
        if column_form is None or all_ed == column_form:
            out.append(i)
    return out


@pytest.mark.parametrize("column_form", (True, False),
                         ids=("columns", "items_for_a_verifier_without_them"))
def test_a_mixed_set_rides_one_ed25519_dispatch(mixed, column_form):
    valset, signers = mixed
    on_columns = _places(valset, signers, "multisig", True)
    on_host = _places(valset, signers, "multisig", False)
    assert (len(on_columns), len(on_host)) == (5, 1)
    absent = (on_columns[1], next(
        i for i, v in enumerate(valset.validators)
        if isinstance(v.pub_key, PubKeyEd25519)))
    # the last sub-signature of one member spoiled: that member alone fails
    bad = on_columns[2]
    def flip(sig):
        return sig[:-1] + bytes([sig[-1] ^ 1])

    for spoil in (None, {bad: flip}):
        block_id, commit = _mixed_commit(valset, signers, absent, spoil=spoil)
        verifier = Counting(column_form)
        before = _counters()
        if spoil is None:
            valset.verify_commit(CHAIN, block_id, 9, commit, verifier=verifier)
        else:
            with pytest.raises(CommitError):
                valset.verify_commit(CHAIN, block_id, 9, commit, verifier=verifier)
        present = [i for i in range(len(valset.validators)) if i not in absent]
        multisig_lanes = sum(
            len(signers[valset.validators[i].address][2])
            for i in on_columns if i in present)
        ed_plain = [i for i in present if isinstance(
            valset.validators[i].pub_key, PubKeyEd25519)]
        form = "ed25519_raw" if column_form else "ed25519_items"
        assert verifier.asked == [(form, len(ed_plain) + multisig_lanes),
                                  ("secp256k1", 2)]
        assert _grown(before) == {
            "groups": 4, "lanes": multisig_lanes, "structural": 1, "ed25519": 1, "secp256k1": 1}
        # validator for validator
        pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(CHAIN, block_id, 9, commit)
        got = batch.verify_generic(pubkeys, msgs, sigs, verifier=Counting(column_form))
        want = [pk.verify_bytes(m, s) for pk, m, s in zip(pubkeys, msgs, sigs)]
        assert got.dtype == bool and got.tolist() == want
        assert want == [i != bad or spoil is None for i in present]


def test_a_flagged_secp256k1_sub_key_is_the_hosts(mixed):
    valset, signers = mixed
    (place,) = _places(valset, signers, "multisig", False)
    key = valset.validators[place].pub_key
    _, sub, _ = signers[key.address()]
    msg = b"one secp256k1 sub-key"
    flagged_it = _sign(sub, [0, 1], msg)
    left_it_out = _sign(sub, [0, 2], msg)
    # 64 bytes in the secp256k1 sub-key's place pass every size rule: only
    # the sub-key's type keeps them off the ed25519 columns
    sized_as_ed25519 = gen.encode_signature(3, [0, 1], [sub[0].sign(msg), b"\x09" * 64])
    assert key.flatten(msg, flagged_it) is None
    assert key.flatten(msg, sized_as_ed25519) is None
    assert [len(s) for _, _, s in key.flatten(msg, left_it_out)] == [64, 64]
    before = _counters()
    sigs = [flagged_it, left_it_out, sized_as_ed25519]
    got = batch.verify_generic([key] * 3, [msg] * 3, sigs, verifier=Counting())
    assert got.tolist() == [True, True, False] == [
        key.verify_bytes(msg, s) for s in sigs]
    assert _grown(before) == {"groups": 1, "lanes": 2, "structural": 2,
                              "ed25519": 1, "secp256k1": 0}


def test_the_counters_are_exposed_from_zero_and_the_spans_keep_their_arguments(
        inputs, guarded):
    # how a call's multisig members were taken: flattened into the columns
    # (groups_total) or left to verify_bytes (host_fallback_total)
    text = VerifyMetrics().registry.expose_text()
    for series in ("multisig_groups_total", "multisig_lanes_total",
                   'host_fallback_total{reason="multisig_structural"}'):
        assert f"tendermint_verify_{series} 0" in text
    ks, ring = inputs
    pre, v = gen.tamper(ring[0], ks, "wrong_size", np.random.default_rng(3))
    keys = [_key(ks, i) for i in range(len(ks.keys))]
    trace.reset(1 << 10)
    trace.enable()
    try:
        before = _counters()
        got = batch.verify_generic(keys, pre.msgs, pre.sigs, verifier=guarded)
        spans = {e["name"]: e["args"] for e in trace.export() if e.get("ph") == "X"}
    finally:
        trace.disable()
    assert got.tolist() == [i != v for i in range(len(keys))]
    flat, red = spans["multisig.flatten"], spans["multisig.reduce"]
    lanes = pre.lanes() - len(oracle.parse_signature(pre.sigs[v])[2])
    assert (flat["validators"], flat["host_decided"], flat["lanes"]) == (8, 1, lanes)
    assert red["groups"] == 7
    assert flat["parent_id"] == red["parent_id"] == spans["verify.generic"]["span_id"]
    # the guard audits rows of the columns: the dispatch is one, in column form
    assert spans["verify.dispatch"]["n"] == lanes
    assert _grown(before) == {"groups": 7, "lanes": lanes, "structural": 1,
                              "ed25519": 1, "secp256k1": 0}


# -- (vii) the group verdict ---------------------------------------------------


@pytest.mark.parametrize("where", ("first", "middle", "last"))
@pytest.mark.parametrize("member", (0, 3, 7), ids=("head", "inside", "tail"))
def test_one_bad_lane_fails_its_validator_alone(inputs, guarded, where, member):
    ks, ring = inputs
    pre = ring[1]
    keys = [_key(ks, i) for i in range(len(ks.keys))]
    size, elems, subs = oracle.parse_signature(pre.sigs[member])
    flagged = [i for i in range(size) if oracle.get_index(elems, size, i)]
    j = {"first": 0, "middle": len(subs) // 2, "last": len(subs) - 1}[where]
    subs[j] = subs[j][:10] + bytes([subs[j][10] ^ 4]) + subs[j][11:]
    sigs = list(pre.sigs)
    sigs[member] = gen.encode_signature(size, flagged, subs)
    got = batch.verify_generic(keys, pre.msgs, sigs, verifier=guarded)
    assert got.tolist() == [i != member for i in range(len(keys))]
    assert keys[member].verify_bytes(pre.msgs[member], sigs[member]) is False
