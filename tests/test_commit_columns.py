"""``ValidatorSet.verify_commit`` in column form against the list form.

An all-ed25519 set's commit goes down as three arrays whose keys and powers
are the membership's own; any other commit as the four lists of
``collect_commit_sigs``.  The reference here is the collector and the tally
as they stood before the column form (PR 41), verbatim: the same lanes, the
same verdict, the same ``CommitError`` text for the same first offending
index, at 1, 4, 64 and 1,000 validators.  Then what reaches the device and
the guard's audit (the program stood in for, as tests/test_ed25519_pack.py
does), and the lifetime of the columns that live with the membership.
"""

import copy
import functools
import struct
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto.keys import PrivKeyEd25519, PrivKeySecp256k1
from tendermint_tpu.libs.breaker import CircuitBreaker
from tendermint_tpu.types import validator_set as vset_mod
from tendermint_tpu.types.block import Commit
from tendermint_tpu.types.core import (
    BlockID,
    PartSetHeader,
    SignedMsgType,
    canonical_vote_sign_bytes,
)
from tendermint_tpu.types.validator_set import (
    CommitError,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.types.vote import Vote

CHAIN = "columns-chain"
HEIGHT = 12
FORM = "tendermint_verify_commit_collect_total"


# ---------------------------------------------------------------------------
# The reference: types/validator_set.py as of PR 41, verbatim
# ---------------------------------------------------------------------------


def _ref_collect(valset, chain_id, block_id, height, commit):
    if valset.size != len(commit.precommits):
        raise CommitError(
            f"wrong set size: {valset.size} vs {len(commit.precommits)}"
        )
    if height != commit.height():
        raise CommitError(f"wrong height: {height} vs {commit.height()}")
    if block_id != commit.block_id:
        raise CommitError("wrong block id")

    round = commit.round()
    main_tpl = canonical_vote_sign_bytes(
        chain_id, SignedMsgType.PRECOMMIT, height, round, 0, block_id
    )
    main_head, main_tail = main_tpl[:17], main_tpl[25:]
    stray_templates = None
    _pack_ts = struct.Struct("<q").pack
    vals = valset.validators
    pubkeys, msgs, sigs, powers = [], [], [], []
    for idx, precommit in enumerate(commit.precommits):
        if precommit is None:
            continue
        if precommit.height != height:
            raise CommitError(f"precommit height {precommit.height} != {height}")
        if precommit.round != round:
            raise CommitError(f"precommit round {precommit.round} != {round}")
        if precommit.vote_type != SignedMsgType.PRECOMMIT:
            raise CommitError(f"not a precommit @ index {idx}")
        val = vals[idx]
        pubkeys.append(val.pub_key)
        key = precommit.block_id
        if key == block_id:
            msgs.append(
                main_head + _pack_ts(precommit.timestamp_ns) + main_tail
            )
            powers.append(val.voting_power)
        else:  # stray vote: counts for availability, not power
            if stray_templates is None:
                stray_templates = {}
            tpl = stray_templates.get(key)
            if tpl is None:
                tpl = canonical_vote_sign_bytes(
                    chain_id, SignedMsgType.PRECOMMIT, height, round, 0, key
                )
                stray_templates[key] = tpl
            msgs.append(
                tpl[:17] + _pack_ts(precommit.timestamp_ns) + tpl[25:]
            )
            powers.append(0)
        sigs.append(precommit.signature)
    return pubkeys, msgs, sigs, powers


def _ref_verify_commit(valset, chain_id, block_id, height, commit, verifier=None):
    pubkeys, msgs, sigs, powers = _ref_collect(
        valset, chain_id, block_id, height, commit)
    ok = batch.verify_generic(pubkeys, msgs, sigs, verifier=verifier)
    tallied = 0
    for j in range(len(pubkeys)):
        if not ok[j]:
            raise CommitError("invalid signature in commit")
        tallied += powers[j]
    if tallied * 3 <= valset.total_voting_power() * 2:
        raise CommitError(
            f"insufficient voting power: got {tallied}, "
            f"needed more than "
            f"{valset.total_voting_power() * 2 // 3}"
        )


# ---------------------------------------------------------------------------
# Chains
# ---------------------------------------------------------------------------


def _block_id(tag: int) -> BlockID:
    return BlockID(bytes([tag]) * 32, PartSetHeader(1, bytes([tag ^ 0xFF]) * 32))


BLOCK = _block_id(0xAA)
OTHER = _block_id(0x5C)


def _make_chain(privs, powers):
    valset = ValidatorSet(
        [Validator(p.pub_key(), w) for p, w in zip(privs, powers)])
    by_address = {p.pub_key().address(): p for p in privs}
    privs = [by_address[v.address] for v in valset.validators]

    def vote(i, block_id=BLOCK, height=HEIGHT, round=0,
             vote_type=SignedMsgType.PRECOMMIT):
        v = Vote(
            vote_type=vote_type, height=height, round=round,
            timestamp_ns=1_700_000_000_000_000_000 + 1_001 * i,
            block_id=block_id,
            validator_address=valset.validators[i].address,
            validator_index=i,
        )
        return v.with_signature(privs[i].sign(v.sign_bytes(CHAIN)))

    commit = Commit(BLOCK, [vote(i) for i in range(len(privs))])
    return SimpleNamespace(valset=valset, commit=commit, vote=vote,
                           n=len(privs))


@functools.lru_cache(maxsize=None)
def _chain(n, first_power=10):
    """n ed25519 validators of power 10 (the one at index 0 of the sorted
    set ``first_power``) and the commit all of them signed."""
    seeds = np.random.default_rng(4200 + n).bytes(32 * n)
    privs = [PrivKeyEd25519.generate(seeds[32 * i:32 * (i + 1)])
             for i in range(n)]
    ch = _make_chain(privs, [10] * n)
    if first_power != 10:
        first = ch.valset.validators[0].address
        ch = _make_chain(privs, [
            first_power if p.pub_key().address() == first else 10
            for p in privs])
    return ch


def _with(commit, changes):
    pcs = list(commit.precommits)
    for i, pc in changes.items():
        pcs[i] = pc
    return Commit(commit.block_id, pcs)


# a variant: chain -> (commit, block id asked for, height asked for)
def _all_present(ch):
    return ch.commit, BLOCK, HEIGHT


def _some_absent(ch):
    return _with(ch.commit, {0: None, ch.n // 2: None}), BLOCK, HEIGHT


def _all_absent(ch):
    return Commit(BLOCK, [None] * ch.n), BLOCK, HEIGHT


def _stray_block(ch):
    k = ch.n // 2
    return _with(ch.commit, {k: ch.vote(k, block_id=OTHER)}), BLOCK, HEIGHT


def _stray_and_absent(ch):
    k = ch.n - 1
    return _with(ch.commit, {k // 2: None, k: ch.vote(k, block_id=OTHER)}), BLOCK, HEIGHT


def _stray_nil(ch):
    # a precommit for nil: its sign-bytes are shorter than the others'
    k = ch.n - 1
    return _with(ch.commit, {k: ch.vote(k, block_id=BlockID())}), BLOCK, HEIGHT


def _wrong_set_size(ch):
    return Commit(BLOCK, ch.commit.precommits[:-1]), BLOCK, HEIGHT


def _wrong_height(ch):
    return ch.commit, BLOCK, HEIGHT + 1


def _wrong_block_id(ch):
    return ch.commit, OTHER, HEIGHT


def _two_bad(ch, **field):
    # two offenders: the first one's index and value are the error's
    a, b = ch.n // 3, ch.n - 1
    return _with(ch.commit, {a: ch.vote(a, **field),
                             b: ch.vote(b, **field)}), BLOCK, HEIGHT


def _precommit_height(ch):
    return _two_bad(ch, height=HEIGHT + 3)


def _precommit_round(ch):
    return _two_bad(ch, round=2)


def _precommit_type(ch):
    return _two_bad(ch, vote_type=SignedMsgType.PREVOTE)


def _sig(ch, alter):
    k = ch.n // 2
    pc = ch.commit.precommits[k]
    return _with(ch.commit, {k: replace(pc, signature=alter(pc.signature))}), BLOCK, HEIGHT


def _sig_63(ch):
    return _sig(ch, lambda s: s[:63])


def _sig_65(ch):
    return _sig(ch, lambda s: s + b"\x00")


def _sig_empty(ch):
    return _sig(ch, lambda s: b"")


def _sig_flipped(ch):
    return _sig(ch, lambda s: bytes([s[0] ^ 1]) + s[1:])


def _wire_decoded(ch):
    return Commit.unmarshal(ch.commit.marshal()), BLOCK, HEIGHT


def _wire_decoded_stray(ch):
    commit, _, _ = _stray_block(ch)
    return Commit.unmarshal(commit.marshal()), BLOCK, HEIGHT


VARIANTS = {
    "all_present": _all_present,
    "some_absent": _some_absent,
    "all_absent": _all_absent,
    "stray_block": _stray_block,
    "stray_and_absent": _stray_and_absent,
    "stray_nil": _stray_nil,
    "wrong_set_size": _wrong_set_size,
    "wrong_height": _wrong_height,
    "wrong_block_id": _wrong_block_id,
    "precommit_height": _precommit_height,
    "precommit_round": _precommit_round,
    "precommit_type": _precommit_type,
    "sig_63_bytes": _sig_63,
    "sig_65_bytes": _sig_65,
    "sig_empty": _sig_empty,
    "sig_flipped": _sig_flipped,
    "wire_decoded": _wire_decoded,
    "wire_decoded_stray": _wire_decoded_stray,
}
SIZES = (1, 4, 64, 1000)


def _outcome(call):
    try:
        return None, call()
    except CommitError as e:
        return (type(e).__name__, str(e)), None


def _error(call):
    return _outcome(call)[0]


def _form_of(lanes, block_id, height):
    """The rule for an all-ed25519 set, said apart from the code: columns
    where every signature is 64 bytes and every lane's sign-bytes are as
    long as those of a vote for the commit's block."""
    _pubkeys, msgs, sigs, _powers = lanes
    ln = len(canonical_vote_sign_bytes(
        CHAIN, SignedMsgType.PRECOMMIT, height, 0, 0, block_id))
    one_shape = {len(s) for s in sigs} == {64} and {len(m) for m in msgs} == {ln}
    return "columns" if one_shape else "lists"


def _forms(verify_counters):
    return {f: verify_counters(FORM, {"form": f}) for f in ("columns", "lists")}


def _moved(after, before):
    return {k: after[k] - before[k] for k in before if after[k] != before[k]}


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_verdict_is_the_list_forms(variant, n, verify_counters):
    ch = _chain(n)
    commit, block_id, height = VARIANTS[variant](ch)
    want = _error(lambda: _ref_verify_commit(
        ch.valset, CHAIN, block_id, height, commit))
    before = _forms(verify_counters)
    got = _error(lambda: ch.valset.verify_commit(
        CHAIN, block_id, height, commit))
    assert got == want
    # one count a call that passed the structural checks, in its form
    refused, lanes = _outcome(lambda: _ref_collect(
        ch.valset, CHAIN, block_id, height, commit))
    assert _moved(_forms(verify_counters), before) == (
        {} if refused else {_form_of(lanes, block_id, height): 1})


def _columns_of(valset, block_id, height, commit):
    """``_commit_columns`` as verify_commit calls it: (the four columns or
    None, the membership's ``ValsetRows`` that goes down with them)."""
    scan = valset._scan_commit(block_id, height, commit)
    members = valset._member_columns()
    rows = valset._valset_rows(members, scan[1])
    return valset._commit_columns(
        CHAIN, block_id, height, scan, members.powers, rows), rows


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_lanes_are_the_list_forms(variant, n):
    """Lane for lane: ``collect_commit_sigs`` against the reference's four
    lists (or its refusal), and the columns, where the commit takes them,
    row for row."""
    ch = _chain(n)
    commit, block_id, height = VARIANTS[variant](ch)
    refused, lanes = _outcome(lambda: _ref_collect(
        ch.valset, CHAIN, block_id, height, commit))
    got = _outcome(lambda: ch.valset.collect_commit_sigs(
        CHAIN, block_id, height, commit))
    assert got == (refused, lanes)
    if refused:
        return
    pubkeys, msgs, sigs, powers = lanes
    assert all(type(p) is int for p in got[1][3])

    columns, rows = _columns_of(ch.valset, block_id, height, commit)
    if _form_of(lanes, block_id, height) == "lists":
        assert columns is None
        return
    keys, m, s, pw = columns
    for a in (keys, m, s):
        assert a.dtype == np.uint8 and a.flags["C_CONTIGUOUS"]
    assert pw.dtype == np.int64
    assert keys.shape == (len(sigs), 32) and s.shape == (len(sigs), 64)
    assert [r.tobytes() for r in keys] == [pk.bytes() for pk in pubkeys]
    assert [r.tobytes() for r in m] == msgs
    assert [r.tobytes() for r in s] == sigs
    assert pw.tolist() == powers
    # the set's identity and its whole key array ride along, and the
    # present slots where the lanes are not the set's own key array
    members = ch.valset._member_columns()
    whole = len(sigs) == ch.n
    assert rows.key_id == members.key_id and rows.keys is members.keys
    assert (rows.slots is None) == whole and (keys is members.keys) == whole
    if not whole:
        assert rows.slots.tolist() == [
            i for i, pc in enumerate(commit.precommits) if pc is not None]
        assert keys.tobytes() == members.keys[rows.slots].tobytes()


@pytest.mark.parametrize("variant,n", [
    (v, n) for v in ("all_present", "some_absent", "stray_block",
                     "wire_decoded_stray")
    for n in SIZES
    if (v, n) != ("some_absent", 1)  # one absent precommit: no height
])
def test_a_row_of_the_matrix_is_the_precommits_sign_bytes(variant, n):
    ch = _chain(n)
    commit, block_id, height = VARIANTS[variant](ch)
    (_keys, m, _s, _pw), _rows = _columns_of(ch.valset, block_id, height, commit)
    present = [pc for pc in commit.precommits if pc is not None]
    assert m.shape[0] == len(present)
    for row, pc in zip(m, present):
        assert row.tobytes() == pc.sign_bytes(CHAIN)


@pytest.mark.parametrize("n", (4, 64, 1000))
@pytest.mark.parametrize("signers", ["exactly_two_thirds", "one_more"])
def test_power_at_exactly_two_thirds_is_not_enough(n, signers, verify_counters):
    # the validator at index 0 holds a third of the power, and is absent
    ch = _chain(n, first_power=5 * (n - 1))
    total = ch.valset.total_voting_power()
    assert total == 15 * (n - 1)
    commit = _with(ch.commit, {0: None})
    if signers == "one_more":  # index 0 signs, another does not
        commit = _with(ch.commit, {1: None})
    want = _error(lambda: _ref_verify_commit(
        ch.valset, CHAIN, BLOCK, HEIGHT, commit))
    before = _forms(verify_counters)
    got = _error(lambda: ch.valset.verify_commit(
        CHAIN, BLOCK, HEIGHT, commit))
    assert got == want
    assert got == (None if signers == "one_more" else (
        "CommitError",
        f"insufficient voting power: got {10 * (n - 1)}, "
        f"needed more than {total * 2 // 3}"))
    assert _moved(_forms(verify_counters), before) == {"columns": 1}


def test_a_decoded_commits_votes_each_carry_their_own_block_id():
    """What makes the two commits of the cases above differ: the identity
    test alone decides the one made in process, the field-wise compare
    the one read from the wire."""
    ch = _chain(4)
    assert all(pc.block_id is BLOCK for pc in ch.commit.precommits)
    decoded, _, _ = _wire_decoded(ch)
    ids = {id(pc.block_id) for pc in decoded.precommits}
    assert len(ids) == 4 and id(decoded.block_id) not in ids
    assert all(pc.block_id == BLOCK for pc in decoded.precommits)
    scan = ch.valset._scan_commit(BLOCK, HEIGHT, decoded)
    assert scan[4] == []  # no stray


@pytest.mark.parametrize("field,value", [
    ("hash", b"\x01" * 32),
    ("parts_hash", b"\x02" * 32),
    ("parts_total", 7),
])
def test_a_block_id_that_differs_in_one_field_is_a_stray(field, value):
    ch = _chain(4)
    parts = PartSetHeader(
        value if field == "parts_total" else BLOCK.parts_header.total,
        value if field == "parts_hash" else BLOCK.parts_header.hash)
    near = BlockID(value if field == "hash" else BLOCK.hash, parts)
    commit = _with(ch.commit, {2: ch.vote(2, block_id=near)})
    _r, _absent, _ts, _sigs, strays = ch.valset._scan_commit(
        BLOCK, HEIGHT, commit)
    assert strays == [(2, near)]
    assert ch.valset.collect_commit_sigs(CHAIN, BLOCK, HEIGHT, commit) == \
        _ref_collect(ch.valset, CHAIN, BLOCK, HEIGHT, commit)


# ---------------------------------------------------------------------------
# Sets that are not all ed25519
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _mixed_chain(kinds):
    privs = [
        (PrivKeySecp256k1 if k == "s" else PrivKeyEd25519).generate(
            bytes([40 + i]) * 32)
        for i, k in enumerate(kinds)]
    return _make_chain(privs, [10] * len(privs))


@pytest.mark.parametrize("kinds", ["ssss", "esee", "eees"])
@pytest.mark.parametrize("variant", ["all_present", "some_absent", "sig_flipped"])
def test_a_set_with_a_secp256k1_member_takes_the_lists(
        kinds, variant, verify_counters):
    ch = _mixed_chain(kinds)
    assert ch.valset._member_columns() is None
    commit, block_id, height = VARIANTS[variant](ch)
    want = _error(lambda: _ref_verify_commit(
        ch.valset, CHAIN, block_id, height, commit))
    before = _forms(verify_counters)
    got = _error(lambda: ch.valset.verify_commit(
        CHAIN, block_id, height, commit))
    assert got == want
    assert _moved(_forms(verify_counters), before) == {"lists": 1}


def test_powers_that_do_not_sum_as_int64_take_the_lists(verify_counters):
    privs = [PrivKeyEd25519.generate(bytes([90 + i]) * 32) for i in range(4)]
    ch = _make_chain(privs, [1 << 62] * 4)
    assert ch.valset._member_columns() is None
    before = _forms(verify_counters)
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit)
    assert _moved(_forms(verify_counters), before) == {"lists": 1}


# ---------------------------------------------------------------------------
# Down to the device: the program stood in for
# ---------------------------------------------------------------------------


class _CountingHashlib:
    """``hashlib`` as a module sees it, counting the SHA-256 calls over a
    whole key array (>= 100 KB)."""

    def __init__(self, counts):
        import hashlib

        self._hashlib = hashlib
        self._counts = counts

    def sha256(self, data=b""):
        if memoryview(data).nbytes >= 100_000:
            self._counts.append(memoryview(data).nbytes)
        return self._hashlib.sha256(data)


@pytest.fixture
def pallas(monkeypatch):
    """A Pallas ``TPUBatchVerifier`` with no chip: ``call_jit`` stood in for,
    each launch recorded as the host arrays it was handed; a lane's verdict
    is whether the first byte of its signature is even.  ``large_hashes``
    collects every SHA-256 of 100 KB or more, here and in the set."""
    from tendermint_tpu.ops import dispatch
    from tendermint_tpu.ops import ed25519_pallas as ep

    launches, large_hashes = [], []

    def fake_call_jit(fn, *args, **static):
        if fn is ep._gather_valset_rows:  # a membership's rows: it runs, here
            return fn(*args)
        if fn is ep._build_valset_windows:  # its window tables: not here
            return np.zeros((args[0].shape[0], ep._WINDOW_WORDS), np.uint32)
        assert fn is ep._device_verify_packed
        host = [np.asarray(a) for a in args]
        launches.append(host)
        return (host[3][:, 0] & 1) == 0

    monkeypatch.setattr(dispatch, "accelerator", lambda: object())
    monkeypatch.setattr(ep, "call_jit", fake_call_jit)
    # the one place a key array's identity is taken (batch.valset_key)
    monkeypatch.setattr(batch, "hashlib", _CountingHashlib(large_hashes))
    monkeypatch.setattr(ep, "_valset_cache", {})
    monkeypatch.setattr(ep, "_dev_valset_cache", {})
    monkeypatch.setattr(ep, "_valset_tables", {})
    device = batch.TPUBatchVerifier(backend="pallas")
    return SimpleNamespace(device=device, launches=launches,
                           large_hashes=large_hashes, ep=ep)


def _same_arrays(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("variant", [
    "all_present", "some_absent", "stray_block", "wire_decoded",
    "sig_flipped"])
def test_the_device_is_handed_the_list_paths_arrays(
        variant, pallas, verify_counters, tracing):
    ch = _chain(64)
    commit, block_id, height = VARIANTS[variant](ch)
    packs = verify_counters(
        "tendermint_verify_ed25519_pack_total", {"path": "uniform"})
    got = _error(lambda: ch.valset.verify_commit(
        CHAIN, block_id, height, commit, verifier=pallas.device))
    assert verify_counters(
        "tendermint_verify_ed25519_pack_total", {"path": "uniform"}) == packs + 1
    want = _error(lambda: _ref_verify_commit(
        ch.valset, CHAIN, block_id, height, commit, verifier=pallas.device))
    assert got == want  # the stand-in's verdicts, tallied alike
    columns, lists = pallas.launches
    # the set's own launch is a resident one (PR 47): the reference's seven
    # arrays and, an eighth, its lanes' window tables
    assert len(columns) == 8
    _same_arrays(columns[:7], lists)
    # the spans the per-layer entries read, nested as they were
    spans = [e for e in tracing.export() if e.get("ph") == "X"]
    first_call = [e for e in spans
                  if e["args"]["root_id"] == spans[0]["args"]["root_id"]]
    by_id = {e["args"]["span_id"]: e["name"] for e in first_call}
    parent_of = {e["name"]: by_id.get(e["args"].get("parent_id"))
                 for e in first_call
                 if e["name"] not in ("valset.miss", "valset.tables")}
    assert parent_of == {
        "commit.verify": None,
        "commit.collect": "commit.verify", "verify.generic": "commit.verify",
        "commit.tally": "commit.verify", "verify.dispatch": "verify.generic",
        "dispatch.prepare": "verify.dispatch", "dispatch.pack": "verify.dispatch",
        "dispatch.launch": "verify.dispatch", "dispatch.wait": "verify.dispatch",
    }
    (generic,) = [e for e in first_call if e["name"] == "verify.generic"]
    assert generic["args"]["keys"] == "ed25519"
    assert generic["args"]["n"] == sum(pc is not None for pc in commit.precommits)


def test_the_kernel_takes_a_matrix_where_it_took_a_list(pallas):
    """``ops/ed25519_pallas.verify_batch`` and ``pack_variable_words`` given
    the messages as one (n, ln) array: the same launch, the same verdicts,
    and the caller's matrix is read in place."""
    ep = pallas.ep
    rng = np.random.default_rng(8)
    n, ln = 200, 110
    pubs = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    sigs = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    m = np.tile(rng.integers(0, 256, (ln,), dtype=np.uint8), (n, 1))
    m[:, 17:25] = rng.integers(0, 256, (n, 8), dtype=np.uint8)
    rows = [r.tobytes() for r in m]
    a = ep.verify_batch(pubs, m, sigs)
    b = ep.verify_batch(pubs, rows, sigs)
    assert a.tolist() == b.tolist()
    _same_arrays(*pallas.launches)
    _same_arrays(ep.pack_variable_words(pubs, m, sigs, ln, 256),
                 ep.pack_variable_words(pubs, rows, sigs, ln, 256))
    assert ep._message_matrix(m, n, ln) is m
    with pytest.raises(ValueError):
        ep.verify_batch(pubs, m[:-1], sigs)


class _ColumnDevice:
    """A device that takes the column form and answers as the host oracle
    does, recording what it was handed."""

    column_form = True
    backend = "fake-columns"

    def __init__(self):
        self.seen = []
        self._host = batch.HostBatchVerifier()

    def verify_ed25519_raw(self, pubs, msgs, sigs, valset=None):
        self.seen.append((pubs, msgs, sigs, valset))
        return self._host.verify_ed25519_raw(pubs, msgs, sigs)


def _guard(device, samples):
    g = batch.GuardedBatchVerifier(
        device, breaker=CircuitBreaker(), audit_rate=0.05, audit_seed=42)
    submit = g._submit_audit

    def recording(algo, n, seq, rows):
        sample = submit(algo, n, seq, rows)
        samples.append((seq, sample.lanes, sample.rows))
        return sample

    g._submit_audit = recording
    return g


@pytest.mark.parametrize("n", (64, 1000))
@pytest.mark.parametrize("variant", ["all_present", "some_absent", "stray_block"])
def test_the_guard_audits_the_same_rows(variant, n, verify_counters):
    """Same seed and sequence number: the same lanes, and the oracle is
    handed the same ``bytes``, whichever form the columns came in (4 lanes
    in line, 50 on the oracle workers where the machine has the cores)."""
    ch = _chain(n)
    commit, block_id, height = VARIANTS[variant](ch)
    from_columns, from_lists = [], []
    device = _ColumnDevice()
    guard_columns = _guard(device, from_columns)
    guard_lists = _guard(_ColumnDevice(), from_lists)
    audited = verify_counters("tendermint_verify_device_audit_total",
                              {"outcome": "ok"})
    for _ in range(2):  # sequence numbers 0 and 1
        ch.valset.verify_commit(
            CHAIN, block_id, height, commit, verifier=guard_columns)
        _ref_verify_commit(
            ch.valset, CHAIN, block_id, height, commit, verifier=guard_lists)
    assert from_columns == from_lists
    assert [seq for seq, _lanes, _rows in from_lists] == [0, 1]
    lanes = len(from_lists[0][1])
    assert lanes == -(-len(device.seen[0][0]) // 20)  # ceil(5 %)
    assert all(type(x) is bytes for row in from_lists[0][2] for x in row)
    assert verify_counters("tendermint_verify_device_audit_total",
                           {"outcome": "ok"}) == audited + 4 * lanes
    # the device behind the guard got arrays, and with them the set's
    # identity, its key array and (some absent) the slots the lanes are
    pubs, msgs, sigs, rows = device.seen[0]
    assert all(isinstance(c, np.ndarray) for c in (pubs, msgs, sigs))
    members = ch.valset._member_columns()
    assert rows.key_id == members.key_id and rows.keys is members.keys
    assert (rows.slots is not None) == (variant == "some_absent")


def test_a_device_without_the_column_form_is_handed_lists():
    """The benchmark's controls and the simulator's faulty device wrap a
    device behind ``verify_ed25519_raw(pubs, msgs, sigs)``: they get rows of
    bytes and no key."""
    ch = _chain(64)

    class Plain:
        backend = "fake-plain"

        def __init__(self):
            self.seen = []

        def verify_ed25519_raw(self, pubs, msgs, sigs):
            self.seen.append((pubs, msgs, sigs))
            return batch.HostBatchVerifier().verify_ed25519_raw(pubs, msgs, sigs)

    device = Plain()
    g = batch.GuardedBatchVerifier(device, breaker=CircuitBreaker(),
                                   audit_rate=0.05, audit_seed=1)
    assert g.column_form is False
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit, verifier=g)
    (pubs, msgs, sigs), = device.seen
    assert all(type(c) is list and type(c[0]) is bytes
               for c in (pubs, msgs, sigs))
    assert msgs == [pc.sign_bytes(CHAIN) for pc in ch.commit.precommits]


def test_a_verifier_with_items_only_still_works():
    ch = _chain(4)

    class ItemsOnly:
        def verify_ed25519(self, items):
            assert all(type(it.pubkey) is bytes for it in items)
            return batch.HostBatchVerifier().verify_ed25519(items)

    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit, verifier=ItemsOnly())


def test_the_host_completes_a_failed_device_call_from_the_columns():
    ch = _chain(64)

    class Broken:
        column_form = True
        backend = "fake-broken"

        def verify_ed25519_raw(self, pubs, msgs, sigs, valset=None):
            raise RuntimeError("device lost")

    g = batch.GuardedBatchVerifier(Broken(), breaker=CircuitBreaker(),
                                   retries=0, audit_rate=0.05, audit_seed=1)
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit, verifier=g)
    commit, _, _ = _sig_flipped(ch)
    with pytest.raises(CommitError, match="invalid signature in commit"):
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit, verifier=g)


# ---------------------------------------------------------------------------
# The columns live with the membership
# ---------------------------------------------------------------------------


def _fresh(n=4):
    ch = _chain(n)
    return ValidatorSet(ch.valset.validators), ch


@pytest.mark.parametrize("how", ["copy", "copy_increment_accum",
                                 "increment_accum", "copy_then_build"])
def test_the_columns_survive_what_leaves_the_membership_alone(how):
    vs, ch = _fresh()
    if how == "copy_then_build":
        # built on the copy, found by the set it was copied from: a node
        # verifies against a copy of the set it keeps
        other = vs.copy()
        cols = other._member_columns()
        assert vs._member_columns() is cols
        return
    cols = vs._member_columns()
    if how == "copy":
        other = vs.copy()
    elif how == "copy_increment_accum":
        other = vs.copy_increment_accum(3)
    else:
        other = vs
        vs.increment_accum(5)
    assert other._member_columns() is cols
    assert cols.keys.tolist() == [list(v.pub_key.bytes()) for v in other.validators]
    assert not cols.keys.flags["WRITEABLE"]
    other.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit)


@pytest.mark.parametrize("change", ["add", "update_power", "remove"])
def test_a_membership_change_drops_them(change):
    vs, ch = _fresh(4)
    shared = vs.copy()
    old = vs._member_columns()
    if change == "add":
        newcomer = PrivKeyEd25519.generate(b"\x77" * 32)
        assert vs.add(Validator(newcomer.pub_key(), 10))
    elif change == "update_power":
        assert vs.update(Validator(vs.validators[0].pub_key, 100))
    else:
        assert vs.remove(vs.validators[3].address) is not None
    new = vs._member_columns()
    assert new is not old and shared._member_columns() is old
    assert new.keys.tolist() == [list(v.pub_key.bytes()) for v in vs.validators]
    assert new.powers.tolist() == [v.voting_power for v in vs.validators]
    assert (new.key_id == old.key_id) == (change == "update_power")
    # the next call verifies against the new keys and powers, the copy that
    # did not change against the old
    if change == "update_power":
        commit = _with(ch.commit, {0: None})
        shared.verify_commit(CHAIN, BLOCK, HEIGHT, commit)  # 30 of 40
        with pytest.raises(CommitError, match="insufficient voting power: got 30"):
            vs.verify_commit(CHAIN, BLOCK, HEIGHT, commit)  # 30 of 130
        return
    want = _error(lambda: _ref_verify_commit(vs, CHAIN, BLOCK, HEIGHT, ch.commit))
    assert want[1].startswith("wrong set size")
    assert _error(lambda: vs.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit)) == want
    shared.verify_commit(CHAIN, BLOCK, HEIGHT, ch.commit)


def test_a_removed_validators_commit_verifies_against_the_new_set():
    ch = _chain(4)
    vs = ValidatorSet(ch.valset.validators)
    vs._member_columns()
    gone = 2
    vs.remove(vs.validators[gone].address)
    pcs = [replace(pc, validator_index=i) for i, pc in enumerate(
        pc for j, pc in enumerate(ch.commit.precommits) if j != gone)]
    commit = Commit(BLOCK, pcs)
    assert _error(lambda: _ref_verify_commit(vs, CHAIN, BLOCK, HEIGHT, commit)) is None
    vs.verify_commit(CHAIN, BLOCK, HEIGHT, commit)
    # and the old set still refuses it
    with pytest.raises(CommitError, match="wrong set size"):
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit)


def test_the_sets_identity_is_the_caches_own_key():
    from tendermint_tpu.ops import ed25519_pallas as ep

    # one definition, which the set and the kernel's host wrapper both call
    assert ep._valset_key is batch.valset_key
    assert vset_mod.valset_key is batch.valset_key
    cols = _chain(64).valset._member_columns()
    assert cols.key_id == batch.valset_key(cols.keys)
    assert cols.key_id == batch.valset_key(np.asfortranarray(cols.keys))


@pytest.mark.parametrize("host", [batch.HostBatchVerifier, batch.RLCHostVerifier])
def test_the_host_verifiers_take_the_columns_as_arrays(host):
    """Verdict for verdict what they say of the same rows as lists; what
    the caller knows of the key array is the device's and they take no
    notice of it."""
    ch = _chain(64)
    commit, block_id, height = _sig_flipped(ch)
    (keys, msgs, sigs, _powers), rows = _columns_of(
        ch.valset, block_id, height, commit)
    assert host.column_form is True
    got = host().verify_ed25519_raw(keys, msgs, sigs, valset=rows)
    want = host().verify_ed25519_raw(
        *(batch._byte_rows(c) for c in (keys, msgs, sigs)))
    assert got.tolist() == want.tolist() and 0 < got.sum() < len(got)
    assert batch.verify_ed25519_columns(
        keys, msgs, sigs, verifier=host(), valset=rows
    ).tolist() == want.tolist()


N_BIG = 3200  # 102,400 bytes of keys


@pytest.fixture(scope="module")
def big_set():
    """3,200 validators whose keys are random bytes and whose precommits
    carry random signatures: enough for the host side of a dispatch."""
    rng = np.random.default_rng(3200)
    from tendermint_tpu.crypto.keys import PubKeyEd25519

    vs = ValidatorSet([
        Validator(PubKeyEd25519(rng.bytes(32)), 10) for _ in range(N_BIG)])
    pcs = [
        Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, 1_700_000_000_000 + i, BLOCK,
             v.address, i, rng.bytes(64))
        for i, v in enumerate(vs.validators)]
    return vs, Commit(BLOCK, pcs)


def test_a_memberships_keys_are_hashed_once_not_once_a_call(
        big_set, pallas, verify_counters):
    vs, commit = big_set
    vs = ValidatorSet(vs.validators)  # a membership nobody has asked yet
    caches = {
        c: verify_counters("tendermint_verify_valset_cache_total", {"cache": c})
        for c in ("host", "device", "table")}
    for k in range(3):
        with pytest.raises(CommitError, match="invalid signature"):
            (vs.copy_increment_accum(1) if k else vs).verify_commit(
                CHAIN, BLOCK, HEIGHT, commit, verifier=pallas.device)
    assert pallas.large_hashes == [32 * N_BIG]
    assert len(pallas.launches) == 3
    # the membership's table is asked once a call, under the identity the
    # set handed down; neither whole-array cache is shown the keys (PR 47)
    for c, before in caches.items():
        assert verify_counters(
            "tendermint_verify_valset_cache_total", {"cache": c}
        ) == before + (3 if c == "table" else 0)
    # the list form of the same lanes pays it every call, as it did
    lanes = vs.collect_commit_sigs(CHAIN, BLOCK, HEIGHT, commit)[:3]
    for _ in range(2):
        batch.verify_generic(*lanes, verifier=pallas.device)
    assert len(pallas.large_hashes) == 3
    # a new membership, a new identity: once more, and once only
    assert vs.update(Validator(vs.validators[5].pub_key, 11))
    for _ in range(2):
        with pytest.raises(CommitError, match="invalid signature"):
            vs.verify_commit(CHAIN, BLOCK, HEIGHT, commit, verifier=pallas.device)
    assert len(pallas.large_hashes) == 4


@pytest.mark.parametrize("n", (4, 64))
def test_nothing_is_kept_on_a_commit_or_a_vote(n, verify_counters):
    ch = _chain(n)
    commit = Commit.unmarshal(ch.commit.marshal())

    def attributes():
        return (set(vars(commit)), [set(vars(pc)) for pc in commit.precommits],
                [set(vars(pc.block_id)) for pc in commit.precommits])

    before = attributes()
    forms = _forms(verify_counters)
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit)
    assert attributes() == before
    # a commit seen for the first time takes the path the last one took
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, copy.deepcopy(commit))
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit)
    assert _moved(_forms(verify_counters), forms) == {"columns": 3}


def test_the_counter_is_exposed_from_zero():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    text = VerifyMetrics().registry.expose_text().splitlines()
    for form in ("columns", "lists"):
        assert f'{FORM}{{form="{form}"}} 0' in text
