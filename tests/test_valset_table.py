"""A membership a table: the lanes of a call whose caller knows them as rows
of a key array it keeps (``crypto/batch.ValsetRows`` with slots: a commit
with absent slots) are gathered by slot from what the kernel's host wrapper
derives ONCE from that array, and decided as the whole-array path decides
them, lane for lane.

No chip here, and the interpreted kernels take minutes a launch, so the two
programs are stood in for by the host oracle OVER THE ARRAYS THEY ARE HANDED:
``_device_verify_packed`` (the packed path, ``call_jit`` stood in for; the
row gather itself runs, on the CPU) and ``_device_verify`` (the
interpret-mode reference path).  A lane is true iff its signature verifies
under the key bytes it was handed AND the limbs it was handed are that key's:
a lane gathered from the wrong row shows."""

import sys
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto.keys import PrivKeyEd25519, PrivKeySecp256k1
from tendermint_tpu.crypto.multisig import Multisignature, PubKeyMultisigThreshold
from tendermint_tpu.ops import ed25519_pallas as ep
from tendermint_tpu.ops import ed25519_verify as xla
from tendermint_tpu.types.block import Commit
from tendermint_tpu.types.core import BlockID, PartSetHeader, SignedMsgType
from tendermint_tpu.types.validator_set import CommitError, Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote

CACHE = "tendermint_verify_valset_cache_total"
L = (1 << 252) + 27742317777372353535851937790883648493


# ---------------------------------------------------------------------------
# The two programs, stood in for
# ---------------------------------------------------------------------------


def _lane_ok(key: bytes, msg: bytes, sig: bytes, negax_row, ay_row) -> bool:
    dec = xla._decompress_neg_cached(key)
    if dec is None:
        return False
    if not (np.array_equal(dec[0], negax_row) and np.array_equal(dec[1], ay_row)):
        return False
    return ed.verify(key, msg, sig)


def _padded_inputs_ok(padded, negax, ay, sig_words) -> np.ndarray:
    """``padded`` (b, nblocks * 128) uint8: R || A || M || 0x80 .. length."""
    sigs = np.ascontiguousarray(sig_words).astype("<u4").view(np.uint8)
    out = np.zeros((padded.shape[0],), dtype=bool)
    for i, row in enumerate(padded):
        total = int.from_bytes(row[-16:].tobytes(), "big") // 8
        out[i] = _lane_ok(row[32:64].tobytes(), row[64:total].tobytes(),
                          sigs[i].tobytes(), negax[i], ay[i])
    return out


def _oracle_packed(negax, ay, pub_words, sig_words, tmpl, vidx, vwords):
    """``_device_verify_packed`` on the host, from its own seven arrays."""
    b = negax.shape[0]
    mw = np.broadcast_to(tmpl, (b, tmpl.shape[0])).copy()
    mw[:, vidx] = vwords
    padded = mw.astype(">u4").view(np.uint8).reshape(b, -1)
    padded[:, 0:32] = sig_words[:, :8].astype("<u4").view(np.uint8).reshape(b, 32)
    padded[:, 32:64] = pub_words.astype("<u4").view(np.uint8).reshape(b, 32)
    return _padded_inputs_ok(padded, negax, ay, sig_words)


def _oracle_reference(negax, ay, sig_words, msg_words, **_static):
    """``_device_verify`` (the interpret-mode path) on the host."""
    args = [np.asarray(a) for a in (negax, ay, sig_words, msg_words)]
    padded = args[3].astype(">u4").view(np.uint8).reshape(args[0].shape[0], -1)
    return _padded_inputs_ok(padded, args[0], args[1], args[2])


@pytest.fixture
def programs(monkeypatch):
    """Both programs stood in for, the caches and the tables empty; every
    packed launch and every gather recorded."""
    seen = SimpleNamespace(launches=[], gathers=[])

    def fake_call_jit(fn, *args, **static):
        if fn is ep._gather_valset_rows:
            seen.gathers.append(np.asarray(args[1]))
            return fn(*args)
        assert fn is ep._device_verify_packed
        host = [np.asarray(a) for a in args]
        seen.launches.append(host)
        return _oracle_packed(*host)

    monkeypatch.setattr(ep, "call_jit", fake_call_jit)
    monkeypatch.setattr(ep, "_device_verify", _oracle_reference)
    monkeypatch.setattr(ep, "_valset_cache", {})
    monkeypatch.setattr(ep, "_dev_valset_cache", {})
    monkeypatch.setattr(ep, "_valset_tables", {})
    return seen


def _lookups(verify_counters):
    return {(cache, result): verify_counters(CACHE, {"cache": cache, "result": result})
            for cache in ("host", "device", "table") for result in ("hit", "miss")}


def _moved(after, before):
    return {k: after[k] - before[k] for k in before if after[k] != before[k]}


# ---------------------------------------------------------------------------
# verify_batch: the table path against the whole-array path
# ---------------------------------------------------------------------------

N = 40
BAD_KEY = 7  # the member whose 32 bytes are no curve point


def _no_point() -> bytes:
    for i in range(2, 256):
        raw = bytes([i]) + b"\x00" * 31
        if ed._decompress_xy(raw) is None:
            return raw
    raise AssertionError("no such bytes")


def _membership():
    seeds = np.random.default_rng(4646).bytes(32 * N)
    privs = [ed.gen_privkey(seeds[32 * i:32 * (i + 1)]) for i in range(N)]
    raw = [p[32:] for p in privs]
    raw[BAD_KEY] = _no_point()
    keys = np.frombuffer(b"".join(raw), dtype=np.uint8).reshape(N, 32)
    return privs, keys


PRIVS, KEYS = _membership()
LONG, SHORT = 110, 70  # a block precommit's sign-bytes, a nil precommit's


def _message(slot, ln):
    slot = int(slot)
    return bytes([slot]) * 17 + (1_700_000_000 + slot).to_bytes(8, "little") \
        + bytes([ln]) * (ln - 25)


def _signed(slots, lengths):
    msgs = [_message(s, ln) for s, ln in zip(slots, lengths)]
    sigs = np.frombuffer(b"".join(
        ed.sign(PRIVS[int(s)], m) for s, m in zip(slots, msgs)), dtype=np.uint8
    ).reshape(len(slots), 64).copy()
    return msgs, sigs


def _plus_l(sig_row):
    """s + L in place of s: another encoding of the same scalar, which Go's
    range check (the top three bits) lets through."""
    s = int.from_bytes(sig_row[32:].tobytes(), "little") + L
    assert s < 1 << 253
    sig_row[32:] = np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)


def _seeded_subset(present=(), absent=()):
    rng = np.random.default_rng(46)
    slots = set(rng.choice(N, size=27, replace=False).tolist())
    return np.array(sorted((slots | set(present)) - set(absent)))


def _case(name):
    """(slots, msgs, sigs, the lanes that must come out false)"""
    if name == "seeded_subset":
        slots = _seeded_subset(absent=[BAD_KEY])
        return (slots, *_signed(slots, [LONG] * len(slots)), [])
    if name == "no_point_key_present":
        slots = _seeded_subset(present=[BAD_KEY])
        return (slots, *_signed(slots, [LONG] * len(slots)),
                [int(np.nonzero(slots == BAD_KEY)[0][0])])
    if name == "no_point_key_absent":
        slots = _seeded_subset(absent=[BAD_KEY])
        msgs, sigs = _signed(slots, [LONG] * len(slots))
        sigs[4, 3] ^= 1
        return slots, msgs, sigs, [4]
    if name == "high_s_and_a_bad_signature_on_nil":
        slots = _seeded_subset(absent=[BAD_KEY])
        lengths = [SHORT if i % 5 == 1 else LONG for i in range(len(slots))]
        msgs, sigs = _signed(slots, lengths)
        _plus_l(sigs[2])            # accepted: the Go accept set
        sigs[3, 63] |= 0x20         # refused by the range check, on the host
        sigs[6, 40] ^= 0x10         # lane 6 is for nil: a stray is verified
        assert lengths[6] == SHORT
        return slots, msgs, sigs, [3, 6]
    if name == "two_lengths":
        slots = _seeded_subset(present=[BAD_KEY])
        lengths = [SHORT if i % 3 == 0 else LONG for i in range(len(slots))]
        return (slots, *_signed(slots, lengths),
                [int(np.nonzero(slots == BAD_KEY)[0][0])])
    if name == "one_lane":
        return (np.array([N - 1]), *_signed([N - 1], [LONG]), [])
    raise KeyError(name)


CASES = ["seeded_subset", "no_point_key_present", "no_point_key_absent",
         "high_s_and_a_bad_signature_on_nil", "two_lengths", "one_lane"]


@pytest.mark.parametrize("interpret", [False, True], ids=["packed", "reference"])
@pytest.mark.parametrize("case", CASES)
def test_the_table_paths_verdicts_are_the_whole_array_paths(
        case, interpret, programs, verify_counters):
    slots, msgs, sigs, refused = _case(case)
    pubs = KEYS[slots]
    rows = batch.ValsetRows(batch.valset_key(KEYS), KEYS, slots)
    want = ep.verify_batch(pubs, msgs, sigs, interpret=interpret)
    launches = len(programs.launches)
    assert programs.gathers == []
    before = _lookups(verify_counters)

    got = ep.verify_batch(pubs, msgs, sigs, interpret=interpret, valset=rows)

    assert got.tolist() == want.tolist()
    assert got.tolist() == [
        i not in refused and ed.verify(pubs[i].tobytes(), msgs[i], sigs[i].tobytes())
        for i in range(len(slots))]
    assert [i for i, ok in enumerate(got) if not ok] == refused
    # one lookup of the table a call and none of the whole-array caches
    assert _moved(_lookups(verify_counters), before) == {("table", "miss"): 1}
    groups = len(set(map(len, msgs)))
    if interpret:
        assert programs.gathers == [] and ep._valset_tables[rows.key_id].device is None
    else:
        # an index a launch went up, and the launch was handed the arrays
        # the whole-array path's launch was
        assert len(programs.gathers) == groups
        assert len(programs.launches) == launches + groups
        for whole, table in zip(programs.launches[:launches],
                                programs.launches[launches:]):
            for w, t in zip(whole, table):
                assert w.dtype == t.dtype and w.tobytes() == t.tobytes()
    # another subset of the same membership: a hit, nothing filled
    again = _lookups(verify_counters)
    ep.verify_batch(pubs[1:], msgs[1:], sigs[1:], interpret=interpret,
                    valset=rows._replace(slots=slots[1:]))
    assert _moved(_lookups(verify_counters), again) == (
        {("table", "hit"): 1} if len(slots) > 1 else {})


@pytest.mark.parametrize("interpret", [False, True], ids=["packed", "reference"])
def test_slots_none_is_the_whole_array_path_under_the_sets_identity(
        interpret, programs, verify_counters, monkeypatch):
    slots = np.arange(N)
    msgs, sigs = _signed(slots, [LONG] * N)
    key_id = batch.valset_key(KEYS)
    hashed = []
    monkeypatch.setattr(ep, "_valset_key", lambda keys: hashed.append(1) or b"x")
    before = _lookups(verify_counters)
    got = ep.verify_batch(KEYS, msgs, sigs, interpret=interpret,
                          valset=batch.ValsetRows(key_id, KEYS, None))
    assert [i for i, ok in enumerate(got) if not ok] == [BAD_KEY]
    assert hashed == [] and ep._valset_tables == {} and programs.gathers == []
    assert key_id in ep._valset_cache
    assert _moved(_lookups(verify_counters), before) == (
        {("host", "miss"): 1} if interpret
        else {("host", "miss"): 1, ("device", "miss"): 1})


@pytest.mark.parametrize("wrong", ["another_slot", "a_negative_slot",
                                   "one_slot_too_few", "past_the_set",
                                   "no_slots_and_fewer_lanes"])
def test_slots_that_do_not_name_the_lanes_keys_are_refused(wrong, programs):
    slots = np.array([1, 2, 3, 5])
    msgs, sigs = _signed(slots, [LONG] * 4)
    said = {"another_slot": np.array([1, 2, 4, 5]),
            "a_negative_slot": np.array([1, 2, 3, 5 - N]),
            "one_slot_too_few": slots[:3],
            "past_the_set": np.array([1, 2, 3, N]),
            "no_slots_and_fewer_lanes": None}[wrong]
    rows = batch.ValsetRows(batch.valset_key(KEYS), KEYS, said)
    with pytest.raises((ValueError, IndexError)):
        ep.verify_batch(KEYS[slots], msgs, sigs, valset=rows)
    assert programs.launches == [] and ep._valset_tables == {}
    assert ep._valset_cache == {}


# ---------------------------------------------------------------------------
# The gather and the residency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("slots,b", [
    ([0, 3, 4, 9, 38], 8), ([39], 8), (list(range(N)), 128),
    ([5, 5, 2], 16),  # any rows: the helper asks nothing of their order
    ([], 8),
])
def test_the_gather_is_the_padded_rows_of_the_membership(slots, b, programs):
    slots = np.array(slots, dtype=np.int64)
    table = ep._valset_table(batch.ValsetRows(b"id", KEYS, slots))
    device = ep._table_on_device(table)
    assert device.shape == (N + 1, 48) and device.dtype == np.uint32
    assert not np.asarray(device)[N].any()
    idx = ep._table_index(slots, N, b)
    assert idx.dtype == np.int32 and idx.shape == (b,)
    got = ep._gather_valset_rows(device, idx)
    full = (table.neg_ax, table.ay, KEYS.view("<u4").astype(np.uint32))
    for g, whole in zip(got, full):
        want = ep._pad_rows(whole[slots], b)
        g = np.asarray(g)
        assert g.dtype == want.dtype and g.shape == want.shape
        assert g.tobytes() == want.tobytes()
        assert not g[len(slots):].any()
    # the member with no point is a row of zero limbs and valid False
    assert not table.valid[BAD_KEY] and table.valid.sum() == N - 1
    assert not table.neg_ax[BAD_KEY].any() and not table.ay[BAD_KEY].any()


def _random_membership(seed, n=6):
    keys = np.random.default_rng(seed).integers(0, 256, size=(n, 32), dtype=np.uint8)
    return batch.ValsetRows(batch.valset_key(keys), keys, np.arange(n))


def test_the_least_recently_used_table_goes_alone(programs, verify_counters, monkeypatch):
    monkeypatch.setattr(ep, "_VALSET_TABLES_MAX", 3)
    a, b, c, d = (_random_membership(s) for s in range(4))
    before = _lookups(verify_counters)
    tables = {m.key_id: ep._valset_table(m) for m in (a, b, c)}
    assert ep._valset_table(a) is tables[a.key_id]       # a: the newest again
    ep._valset_table(d)                                  # b goes, alone
    assert list(ep._valset_tables) == [c.key_id, a.key_id, d.key_id]
    assert _moved(_lookups(verify_counters), before) == {
        ("table", "miss"): 4, ("table", "hit"): 1}
    again = _lookups(verify_counters)
    for m in (a, c, d):
        ep._valset_table(m)
    assert ep._valset_table(c) is tables[c.key_id]
    assert _moved(_lookups(verify_counters), again) == {("table", "hit"): 4}
    ep._valset_table(b)
    assert _moved(_lookups(verify_counters), again) == {
        ("table", "hit"): 4, ("table", "miss"): 1}
    assert len(ep._valset_tables) == 3 and a.key_id not in ep._valset_tables


def test_a_fill_is_spanned_as_a_miss_of_the_whole_membership(programs, tracing):
    slots = np.array([0, 1, 2, 3, 10])
    msgs, sigs = _signed(slots, [LONG] * 5)
    rows = batch.ValsetRows(batch.valset_key(KEYS), KEYS, slots)
    for _ in range(2):
        ep.verify_batch(KEYS[slots], msgs, sigs, valset=rows)
    spans = [e for e in tracing.export() if e.get("ph") == "X"]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e["args"])
    # the first call's fill: the host's rows under prepare, the upload under
    # launch; the second call draws neither
    assert {(m["cache"], m["lanes"], m["bytes"]) for m in by["valset.miss"]} == {
        ("host", N, 32 * N), ("device", N, 4 * 48 * (N + 1))}
    parents = {m["cache"]: m["parent_id"] for m in by["valset.miss"]}
    assert parents == {"host": by["dispatch.prepare"][0]["span_id"],
                       "device": by["dispatch.launch"][0]["span_id"]}
    assert len(by["dispatch.prepare"]) == len(by["dispatch.launch"]) == 2


def test_callers_on_many_threads_share_the_tables(programs, monkeypatch):
    """More memberships than may be resident, looked up from more threads
    than cores: every lookup gets ITS membership's table and no more than
    the bound are resident."""
    monkeypatch.setattr(ep, "_VALSET_TABLES_MAX", 4)
    members = [_random_membership(100 + s) for s in range(6)]
    wrong, interval = [], sys.getswitchinterval()

    def work(k):
        rng = np.random.default_rng(k)
        for _ in range(150):
            m = members[int(rng.integers(len(members)))]
            table = ep._valset_table(m)
            if table.keys is not m.keys or len(ep._valset_tables) > 4:
                wrong.append(m.key_id)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(16)]
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == [] and len(ep._valset_tables) == 4


# ---------------------------------------------------------------------------
# verify_commit: what it hands down, and a membership that changes
# ---------------------------------------------------------------------------

CHAIN = "table-chain"
HEIGHT = 46
BLOCK = BlockID(b"\xab" * 32, PartSetHeader(1, b"\x54" * 32))
OTHER = BlockID(b"\x5d" * 32, PartSetHeader(1, b"\xa2" * 32))
NIL = BlockID()


def _vote(priv, valset, i, block_id=BLOCK):
    v = Vote(vote_type=SignedMsgType.PRECOMMIT, height=HEIGHT, round=0,
             timestamp_ns=1_700_000_000_000_000_000 + 1_001 * i,
             block_id=block_id, validator_address=valset.validators[i].address,
             validator_index=i)
    return v.with_signature(priv.sign(v.sign_bytes(CHAIN)))


def _privs(n, seed=4600):
    seeds = np.random.default_rng(seed).bytes(32 * n)
    return [PrivKeyEd25519.generate(seeds[32 * i:32 * (i + 1)]) for i in range(n)]


class _Chain:
    """A set and the keys that sign for it, slot by slot."""

    def __init__(self, privs, power=10):
        self.by_address = {p.pub_key().address(): p for p in privs}
        self.valset = ValidatorSet([Validator(p.pub_key(), power) for p in privs])

    def commit(self, absent=(), nil=(), other=(), signers=None):
        vs = self.valset
        votes = []
        for i, v in enumerate(vs.validators):
            if i in absent:
                votes.append(None)
                continue
            priv = (signers or {}).get(i) or self.by_address[v.address]
            votes.append(_vote(priv, vs, i, NIL if i in nil else
                               OTHER if i in other else BLOCK))
        return Commit(BLOCK, votes)


class _Recorder:
    """A verifier that says ``column_form``, answers as the host oracle does
    and records what came with the three columns."""

    column_form = True
    backend = "fake-recorder"

    def __init__(self):
        self.seen = []
        self._host = batch.HostBatchVerifier()

    def verify_ed25519_raw(self, pubs, msgs, sigs, **kw):
        self.seen.append((pubs, kw))
        return self._host.verify_ed25519_raw(pubs, msgs, sigs)

    def verify_secp256k1(self, items):
        return self._host.verify_secp256k1(items)


class _ThreeColumns:
    """A stand-in as the benchmark's controls are: three positionals."""

    def __init__(self):
        self.calls = 0
        self._host = batch.HostBatchVerifier()

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        self.calls += 1
        return self._host.verify_ed25519_raw(pubs, msgs, sigs)


SHAPES = {
    # name: (absent, nil, other block, the form the lanes take)
    "absent_only": ({2, 11, 12, 30}, (), (), "columns"),
    "absent_and_another_block": ({0, 31}, (), {5}, "columns"),
    "absent_and_nil": ({2, 11, 12, 30}, {3, 20}, (), "lists"),
    "first_and_last_absent_and_nil": ({0, 31}, {1, 30}, {7}, "lists"),
    "nil_only": ((), {3, 20}, (), "lists"),
    "all_present": ((), (), (), "columns"),
}


@pytest.fixture(scope="module")
def chain32():
    return _Chain(_privs(32))


def _handed_down(seen):
    """The one dispatch's (pubs as an (n, 32) array, the ValsetRows or None)."""
    (pubs, kw), = seen
    assert set(kw) <= {"valset"}
    if not isinstance(pubs, np.ndarray):
        pubs = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(len(pubs), 32)
    return pubs, kw.get("valset")


@pytest.mark.parametrize("guarded", [False, True], ids=["bare", "guarded"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_verify_commit_hands_down_the_present_slots_of_its_membership(
        shape, guarded, chain32, verify_counters):
    from tendermint_tpu.libs.breaker import CircuitBreaker

    absent, nil, other, form = SHAPES[shape]
    vs = chain32.valset
    commit = chain32.commit(absent, nil, other)
    rec = _Recorder()
    verifier = batch.GuardedBatchVerifier(
        rec, breaker=CircuitBreaker(), audit_rate=0.05, audit_seed=3
    ) if guarded else rec
    forms = {f: verify_counters("tendermint_verify_commit_collect_total", {"form": f})
             for f in ("columns", "lists")}
    vs.verify_commit(CHAIN, BLOCK, HEIGHT, commit, verifier=verifier)
    assert {f: verify_counters("tendermint_verify_commit_collect_total",
                               {"form": f}) - forms[f] for f in forms} == {
        "columns": float(form == "columns"), "lists": float(form == "lists")}
    pubs, rows = _handed_down(rec.seen)
    members = vs._member_columns()
    assert isinstance(rows, batch.ValsetRows)
    assert rows.key_id == members.key_id and rows.keys is members.keys
    present = [i for i in range(32) if i not in absent]
    if absent:
        assert rows.slots.tolist() == present
        assert np.array_equal(rows.keys[rows.slots], pubs)
    else:
        assert rows.slots is None and np.array_equal(rows.keys, pubs)
    assert [r.tobytes() for r in pubs] == [
        vs.validators[i].pub_key.bytes() for i in present]


def _multisig_chain(n=6):
    privs = _privs(n, seed=4700)
    keys = [PubKeyMultisigThreshold(1, (p.pub_key(),)) for p in privs]
    valset = ValidatorSet([Validator(k, 10) for k in keys])
    by_address = {k.address(): (k, p) for k, p in zip(keys, privs)}
    votes = []
    for i, v in enumerate(valset.validators):
        key, priv = by_address[v.address]
        vote = Vote(vote_type=SignedMsgType.PRECOMMIT, height=HEIGHT, round=0,
                    timestamp_ns=1_700_000_000_000_000_000 + i, block_id=BLOCK,
                    validator_address=v.address, validator_index=i)
        ms = Multisignature.new(1)
        ms.add_signature_from_pubkey(
            priv.sign(vote.sign_bytes(CHAIN)), priv.pub_key(), key.pubkeys)
        votes.append(vote.with_signature(ms.marshal()))
    votes[2] = None
    return valset, Commit(BLOCK, votes)


def _mixed_chain(n=6):
    privs = _privs(n - 1, seed=4800) + [PrivKeySecp256k1.generate(b"\x46" * 32)]
    ch = _Chain(privs)
    return ch.valset, ch.commit(absent={1})


@pytest.mark.parametrize("make", [_multisig_chain, _mixed_chain],
                         ids=["multisig", "mixed"])
def test_a_set_without_membership_columns_hands_down_nothing(make):
    valset, commit = make()
    assert valset._member_columns() is None
    rec = _Recorder()
    valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit, verifier=rec)
    _pubs, rows = _handed_down(rec.seen)
    assert rows is None and rec.seen[0][1] == {}


@pytest.mark.parametrize("guarded", [False, True], ids=["bare", "guarded"])
@pytest.mark.parametrize("shape", ["absent_only", "absent_and_nil"])
def test_a_verifier_of_three_positionals_gets_three_columns(shape, guarded, chain32):
    from tendermint_tpu.libs.breaker import CircuitBreaker

    absent, nil, other, _form = SHAPES[shape]
    plain = _ThreeColumns()
    verifier = batch.GuardedBatchVerifier(
        plain, breaker=CircuitBreaker(), audit_rate=0.05, audit_seed=3
    ) if guarded else plain
    chain32.valset.verify_commit(
        CHAIN, BLOCK, HEIGHT, chain32.commit(absent, nil, other), verifier=verifier)
    assert plain.calls == 1
    bad = chain32.commit(absent, nil, other, signers={4: _privs(1, seed=9)[0]})
    with pytest.raises(CommitError, match="invalid signature"):
        chain32.valset.verify_commit(CHAIN, BLOCK, HEIGHT, bad, verifier=verifier)


@pytest.fixture
def pallas(programs, monkeypatch):
    """A Pallas ``TPUBatchVerifier`` with no chip, its programs stood in for."""
    from tendermint_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "accelerator", lambda: object())
    return batch.TPUBatchVerifier(backend="pallas")


@pytest.mark.parametrize("shape", ["absent_only", "absent_and_nil"])
def test_another_subset_every_height_fills_one_table(
        shape, pallas, programs, verify_counters, tracing):
    ch = _Chain(_privs(16, seed=4900))
    _absent, nil, _other, _form = SHAPES[shape]
    nil = {i for i in nil if i < 16}
    before = _lookups(verify_counters)
    for height_absent in ({2, 11}, {0, 15}, {4}, {5, 6, 7}):
        ch.valset.verify_commit(
            CHAIN, BLOCK, HEIGHT, ch.commit(height_absent, nil - height_absent),
            verifier=pallas)
    assert _moved(_lookups(verify_counters), before) == {
        ("table", "miss"): 1, ("table", "hit"): 3}
    assert len(ep._valset_tables) == 1
    misses = [e for e in tracing.export()
              if e.get("ph") == "X" and e["name"] == "valset.miss"]
    assert sorted(m["args"]["cache"] for m in misses) == ["device", "host"]
    # a lane that does not verify is found through the table as anywhere
    bad = ch.commit({2, 11}, nil, signers={9: _privs(1, seed=9)[0]})
    with pytest.raises(CommitError, match="invalid signature"):
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, bad, verifier=pallas)


@pytest.mark.parametrize("change", ["add", "remove", "update"])
def test_a_membership_change_is_another_table(
        change, pallas, programs, verify_counters):
    privs = _privs(12, seed=5000)
    ch = _Chain(privs)
    vs = ch.valset
    vs.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit({3}), verifier=pallas)
    old_id = vs._member_columns().key_id
    gone_at = 5
    gone = ch.by_address[vs.validators[gone_at].address]
    before = _lookups(verify_counters)
    if change == "add":
        new = _privs(1, seed=5001)[0]
        ch.by_address[new.pub_key().address()] = new
        assert vs.add(Validator(new.pub_key(), 10))
    elif change == "remove":
        assert vs.remove(vs.validators[gone_at].address) is not None
    else:
        # the same keys at another power: another membership for the tally,
        # the same key array for the table
        assert vs.update(Validator(gone.pub_key(), 1))
    n = vs.size
    vs.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit({n - 1}), verifier=pallas)
    same_keys = change == "update"
    assert (vs._member_columns().key_id == old_id) == same_keys
    assert _moved(_lookups(verify_counters), before) == {
        ("table", "hit" if same_keys else "miss"): 1}
    assert len(ep._valset_tables) == (1 if same_keys else 2)
    if change == "remove":
        # the slot the removed member held is another member's now: a
        # precommit there under the removed member's key is refused
        stale = ch.commit({n - 1}, signers={gone_at: gone})
        with pytest.raises(CommitError, match="invalid signature"):
            vs.verify_commit(CHAIN, BLOCK, HEIGHT, stale, verifier=pallas)
        assert len(ep._valset_tables) == 2
    if change == "update":
        # 111 of power in all; six tens and the one are not two thirds of it
        few = ch.commit({0, 1, 2, 3, 4})
        with pytest.raises(CommitError, match="insufficient voting power"):
            vs.verify_commit(CHAIN, BLOCK, HEIGHT, few, verifier=pallas)


# ---------------------------------------------------------------------------
# The gather compiles for the chip at the live cell's size
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (no chip attached, nothing runs), as
    tests/bench/test_bench_aot.py describes it: inside a fixture, so only
    the worker that runs this file loads the TPU library."""
    import os

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT executable for a described chip cannot be read back from the
    # persistent cache; keep the cache out of it, and quiet
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("lanes", [8192, 512])
def test_the_gather_compiles_for_v5e_at_10_000_members(one_chip, lanes):
    """``commit10k-absent``'s two launches: 6,667 lanes in the 8,192 bucket
    and 333 in the 512 one, out of a table of 10,000 members and the zero
    row; what comes out is what ``_device_verify_packed`` takes."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ep._gather_valset_rows.lower(
        sds((10_001, 48), jnp.uint32), sds((lanes,), jnp.int32)).compile()
    out = compiled.output_shardings
    assert len(out) == 3
    shapes = [(s.shape, s.dtype) for s in jax.eval_shape(
        ep._gather_valset_rows, sds((10_001, 48), jnp.uint32),
        sds((lanes,), jnp.int32))]
    assert shapes == [((lanes, 20), jnp.uint32), ((lanes, 20), jnp.uint32),
                      ((lanes, 8), jnp.uint32)]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 4 << 20 and mem.temp_size_in_bytes < 16 << 20
