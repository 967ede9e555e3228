"""A membership a table: the lanes of a call whose caller knows them as rows
of a key array it keeps (``crypto/batch.ValsetRows``: a commit's present
slots, or every slot) are gathered by slot from what the kernel's host
wrapper derives ONCE from that array (the key limbs and words, and since
PR 47 the window tables the ladder's resident form reads), and decided as
the whole-array path decides them, lane for lane.

No chip here, and the interpreted kernels take minutes a launch, so the
programs are stood in for by the host oracle OVER THE ARRAYS THEY ARE HANDED:
``_device_verify_packed`` (the packed path, ``call_jit`` stood in for; the
row gather itself runs, on the CPU), ``_build_valset_windows`` (a member's
window tables stood in for by its 48 device words repeated, so a lane handed
another member's tables shows) and ``_device_verify`` (the interpret-mode
reference path).  A lane is true iff its signature verifies under the key
bytes it was handed AND the limbs and the tables it was handed are that
key's: a lane gathered from the wrong row shows.  What the real build
program and the resident ladder compute is tests/test_resident_ladder.py's."""

import sys
import threading
from types import SimpleNamespace

import jax.numpy as jnp
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


def _stand_in_windows(table):
    """``_build_valset_windows`` stood in for: a member's 48 words, repeated
    up to the width of its window tables."""
    table = np.asarray(table)
    return np.tile(table, (1, ep._WINDOW_WORDS // table.shape[1]))


def _oracle_packed(negax, ay, pub_words, sig_words, tmpl, vidx, vwords,
                   tables=None):
    """``_device_verify_packed`` on the host, from its own seven arrays, and
    the lanes' window tables where the launch is a resident one."""
    b = negax.shape[0]
    if tables is not None:
        assert tables.shape == (64 * ep.K, ep.NROW, b)
        own = _stand_in_windows(np.concatenate([negax, ay, pub_words], axis=1))
        theirs = np.all(tables.reshape(-1, b).T == own, axis=1)
        return theirs & _oracle_packed(
            negax, ay, pub_words, sig_words, tmpl, vidx, vwords)
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
    """The programs stood in for, the caches and the tables empty; every
    packed launch, every gather and every build recorded."""
    seen = SimpleNamespace(launches=[], gathers=[], builds=[])

    def fake_call_jit(fn, *args, **static):
        if fn is ep._gather_valset_rows:
            seen.gathers.append(np.asarray(args[2]))
            return fn(*args)
        if fn is ep._build_valset_windows:
            seen.builds.append(args[0].shape[0])  # the program's rows
            return jnp.asarray(_stand_in_windows(*args))
        assert fn is ep._device_verify_packed
        host = [np.asarray(a) for a in args]
        seen.launches.append(host)
        return _oracle_packed(*host)

    seen.call_jit = fake_call_jit
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
ROWS = 128  # a membership's device rows: _bucket(N + 1), the zero rows last
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
        # ... and the lanes' window tables as an eighth: the resident form
        for whole, table in zip(programs.launches[:launches],
                                programs.launches[launches:]):
            assert (len(whole), len(table)) == (7, 8)
            for w, t in zip(whole, table):
                assert w.dtype == t.dtype and w.tobytes() == t.tobytes()
        assert programs.builds == [ROWS]
    # another subset of the same membership: a hit, nothing filled
    again = _lookups(verify_counters)
    ep.verify_batch(pubs[1:], msgs[1:], sigs[1:], interpret=interpret,
                    valset=rows._replace(slots=slots[1:]))
    assert _moved(_lookups(verify_counters), again) == (
        {("table", "hit"): 1} if len(slots) > 1 else {})


@pytest.mark.parametrize("interpret", [False, True], ids=["packed", "reference"])
def test_slots_none_is_every_row_of_the_memberships_one_table(
        interpret, programs, verify_counters, monkeypatch):
    """Every slot present (``commit10k-stream``): no hash of the keys a call,
    neither whole-array cache asked, the membership's one table, whose
    gather of every member in order is made once a bucket and kept; verdicts
    as before."""
    slots = np.arange(N)
    msgs, sigs = _signed(slots, [LONG] * N)
    key_id = batch.valset_key(KEYS)
    want = ep.verify_batch(KEYS, msgs, sigs, interpret=interpret)
    hashed = []
    monkeypatch.setattr(ep, "_valset_key", lambda keys: hashed.append(1) or b"x")
    monkeypatch.setattr(ep, "_valset_cache", {})
    monkeypatch.setattr(ep, "_dev_valset_cache", {})
    launches = len(programs.launches)
    rows = batch.ValsetRows(key_id, KEYS, None)
    before = _lookups(verify_counters)
    for k in range(2):
        got = ep.verify_batch(KEYS, msgs, sigs, interpret=interpret, valset=rows)
        assert got.tolist() == want.tolist()
        assert [i for i, ok in enumerate(got) if not ok] == [BAD_KEY]
    assert hashed == [] and ep._valset_cache == {} and ep._dev_valset_cache == {}
    assert list(ep._valset_tables) == [key_id]
    assert _moved(_lookups(verify_counters), before) == {
        ("table", "miss"): 1, ("table", "hit"): 1}
    if interpret:
        assert programs.gathers == [] and programs.builds == []
        return
    # one build, ONE gather for both calls (rows 0..N-1, then the zero row),
    # and both launches resident, handed the very arrays that gather made
    assert programs.builds == [ROWS] and len(programs.gathers) == 1
    assert programs.gathers[0].tolist() == list(range(N)) + [N] * (128 - N)
    first, second = programs.launches[launches:]
    assert len(first) == len(second) == 8
    # another subset of the same membership is gathered by slot from the
    # same table: no other build
    ep.verify_batch(KEYS[3:], msgs[3:], sigs[3:], valset=rows._replace(
        slots=slots[3:]))
    assert programs.builds == [ROWS] and len(programs.gathers) == 2


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
    device, none = ep._table_on_device(table, False)
    assert none is None and programs.builds == []  # an eager launch's: no build
    assert ep._table_on_device(table, True)[0] is device
    assert programs.builds == [ROWS]
    device, windows = ep._table_on_device(table, True)
    # programs are a bucket's, not a member count's: the rows are padded on
    # the host to the bucket that holds the members and the zero row
    assert ROWS == ep._bucket(N + 1)
    assert device.shape == (ROWS, 48) and device.dtype == np.uint32
    assert windows.shape == (ROWS, ep._WINDOW_WORDS) and windows.dtype == np.uint32
    assert not np.asarray(device)[N:].any()
    idx = ep._table_index(slots, N, b)
    assert idx.dtype == np.int32 and idx.shape == (b,)
    *got, tables = ep._gather_valset_rows(device, windows, idx)
    full = (table.neg_ax, table.ay, KEYS.view("<u4").astype(np.uint32))
    for g, whole in zip(got, full):
        want = ep._pad_rows(whole[slots], b)
        g = np.asarray(g)
        assert g.dtype == want.dtype and g.shape == want.shape
        assert g.tobytes() == want.tobytes()
        assert not g[len(slots):].any()
    # the window tables in the layout the kernel reads: row m of a lane's
    # tables is words NROW * m .. of its member's device row, lanes last
    tables = np.asarray(tables)
    assert tables.shape == (64 * ep.K, ep.NROW, b) and tables.dtype == np.uint32
    want = np.asarray(windows)[idx].reshape(b, 64 * ep.K, ep.NROW)
    assert tables.tobytes() == np.ascontiguousarray(want.transpose(1, 2, 0)).tobytes()
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
    # launch and the window tables' build inside it; the second call draws
    # none of the three
    assert {(m["cache"], m["lanes"], m["bytes"]) for m in by["valset.miss"]} == {
        ("host", N, 32 * N), ("device", N, 4 * 48 * ROWS)}
    parents = {m["cache"]: m["parent_id"] for m in by["valset.miss"]}
    assert parents == {"host": by["dispatch.prepare"][0]["span_id"],
                       "device": by["dispatch.launch"][0]["span_id"]}
    (build,) = by["valset.tables"]
    assert (build["members"], build["bytes"]) == (N, 4 * ep._WINDOW_WORDS * ROWS)
    (device_miss,) = [m for m in by["valset.miss"] if m["cache"] == "device"]
    assert build["parent_id"] == device_miss["span_id"]
    assert len(by["dispatch.prepare"]) == len(by["dispatch.launch"]) == 2
    assert [m["tables"] for m in by["dispatch.launch"]] == ["resident"] * 2


LANES_FORM = "tendermint_verify_ed25519_ladder_lanes_total"


def _ladder_lanes(verify_counters):
    return {form: verify_counters(LANES_FORM, {"tables": form})
            for form in ("resident", "built")}


@pytest.mark.parametrize("case,interpret,carry_mode,want", [
    # who handed a ValsetRows down takes the resident form, in padded lanes
    ("slots", False, "lazy", {"resident": 128.0}),
    ("every_slot", False, "lazy", {"resident": 128.0}),
    ("two_lengths", False, "lazy", {"resident": 256.0}),  # a launch a length
    # everyone else builds a table a lane: no identity, ...
    ("no_valset", False, "lazy", {"built": 128.0}),
    # ... the interpret-mode reference path, 8 lanes a block, ...
    ("slots", True, "lazy", {"built": 32.0}),
    # ... and eager carries, in which the resident form is not written
    ("slots", False, "eager", {"built": 128.0}),
])
def test_a_launch_counts_its_lanes_by_where_its_tables_came_from(
        case, interpret, carry_mode, want, programs, verify_counters, tracing):
    slots = _seeded_subset(absent=[BAD_KEY])
    lengths = [SHORT if case == "two_lengths" and i % 4 == 0 else LONG
               for i in range(len(slots))]
    if case == "every_slot":
        slots, lengths = np.arange(N), [LONG] * N
    msgs, sigs = _signed(slots, lengths)
    rows = None if case == "no_valset" else batch.ValsetRows(
        batch.valset_key(KEYS), KEYS, None if case == "every_slot" else slots)
    before = _ladder_lanes(verify_counters)
    got = ep.verify_batch(KEYS[slots], msgs, sigs, interpret=interpret,
                          carry_mode=carry_mode, valset=rows)
    assert [i for i, ok in enumerate(got) if not ok] == (
        [BAD_KEY] if case == "every_slot" else [])
    assert _moved(_ladder_lanes(verify_counters), before) == want
    (form,) = want
    launches = [e["args"] for e in tracing.export()
                if e.get("ph") == "X" and e["name"] == "dispatch.launch"]
    assert [m["tables"] for m in launches] == (
        [] if interpret else [form] * (2 if case == "two_lengths" else 1))
    assert all(len(launch) == (8 if form == "resident" else 7)
               for launch in programs.launches)
    # who reads no tables has none built and none gathered
    assert programs.builds == ([ROWS] if form == "resident" else [])


def test_the_tables_are_built_once_a_membership(programs, tracing):
    """Another subset every height, every slot at some: one ``valset.tables``
    span and one build program's run, at the membership's first launch."""
    rows = batch.ValsetRows(batch.valset_key(KEYS), KEYS, None)
    for absent in ([BAD_KEY], [BAD_KEY, 3, 4], None, [BAD_KEY, 0, N - 1]):
        slots = np.arange(N) if absent is None else _seeded_subset(absent=absent)
        msgs, sigs = _signed(slots, [LONG] * len(slots))
        ep.verify_batch(KEYS[slots], msgs, sigs, valset=rows._replace(
            slots=None if absent is None else slots))
    assert programs.builds == [ROWS] and len(programs.gathers) == 4
    built = [e["args"] for e in tracing.export()
             if e.get("ph") == "X" and e["name"] == "valset.tables"]
    assert [(m["members"], m["bytes"]) for m in built] == [
        (N, 4 * ep._WINDOW_WORDS * ROWS)]


def test_an_evicted_table_frees_its_device_arrays(programs, monkeypatch):
    import gc
    import weakref

    def call_jit(fn, *args, **static):
        # no host view of an operand is taken: on the CPU that is a
        # reference to the device array, and the matter here is who holds one
        if fn is ep._device_verify_packed:
            return np.ones((args[3].shape[0],), dtype=bool)
        return programs.call_jit(fn, *args, **static)

    monkeypatch.setattr(ep, "call_jit", call_jit)
    monkeypatch.setattr(ep, "_VALSET_TABLES_MAX", 2)
    members = [_random_membership(200 + s, n=9) for s in range(3)]
    held = []
    for m in members:
        # random bytes are seldom points: the verdicts are not the matter
        ep.verify_batch(m.keys, [b"m"] * 9, np.zeros((9, 64), np.uint8),
                        valset=m._replace(slots=None))
        table = ep._valset_tables[m.key_id]
        rows, windows = table.device
        (kept,) = table.whole.values()
        held.append([weakref.ref(a) for a in (rows, windows, kept[3])])
        del table, rows, windows, kept
    gc.collect()
    assert list(ep._valset_tables) == [m.key_id for m in members[1:]]
    # the first membership's rows, window tables and kept gather are gone
    # with its table; the resident two's are alive
    assert [[r() is None for r in refs] for refs in held] == [
        [True] * 3, [False] * 3, [False] * 3]
    assert programs.builds == [128, 128, 128]


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


def test_two_callers_filling_one_membership_at_once_share_one_table(
        programs, monkeypatch):
    """Both miss, both decompress, ONE table stays and both get it: its
    window tables are built once, not once a caller."""
    m = _random_membership(300)
    both_in = threading.Barrier(2, timeout=30)
    real = ep._decompress_rows

    def decompress(keys):
        both_in.wait()  # neither returns before the other has missed too
        return real(keys)

    monkeypatch.setattr(ep, "_decompress_rows", decompress)
    got = []
    threads = [threading.Thread(target=lambda: got.append(ep._valset_table(m)))
               for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(got) == 2 and got[0] is got[1]
    assert ep._valset_tables == {m.key_id: got[0]}


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
    # another key array, another build of the window tables; the same keys
    # at another power, none
    assert programs.builds == ([128] if same_keys else [128, 128])
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
    and 333 in the 512 one, out of a table of 10,000 members and its zero
    rows (10,240: the bucket); what comes out is what a resident
    ``_device_verify_packed`` takes."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((10_240, 48), jnp.uint32),
            sds((10_240, ep._WINDOW_WORDS), jnp.uint32), sds((lanes,), jnp.int32))
    compiled = ep._gather_valset_rows.lower(*args).compile()
    assert len(compiled.output_shardings) == 4
    shapes = [(s.shape, s.dtype) for s in jax.eval_shape(
        ep._gather_valset_rows, *args)]
    assert shapes == [((lanes, 20), jnp.uint32), ((lanes, 20), jnp.uint32),
                      ((lanes, 8), jnp.uint32),
                      ((64 * ep.K, ep.NROW, lanes), jnp.uint32)]
    # the gathered rows once, and once more turned lanes-last
    mem = compiled.memory_analysis()
    per_lane = 4 * ep._WINDOW_WORDS
    assert mem.argument_size_in_bytes < 260 << 20
    assert mem.temp_size_in_bytes <= per_lane * lanes + (1 << 20)
    assert mem.output_size_in_bytes <= per_lane * lanes + (2 << 20)


@pytest.mark.parametrize("program", ["resident_launch", "build"])
def test_the_resident_programs_compile_for_v5e_at_10_000_members(one_chip, program):
    """``commit10k-stream``'s launch in the ladder's resident form (10,240
    lanes, their window tables an eighth operand: a 3 MB block a grid step in
    VMEM) and the build of a 10,000-member table: what the TPU's compilers
    refuse, they refuse here."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if program == "build":
        compiled = ep._build_valset_windows.lower(sds((10_240, 48))).compile()
        (out,) = jax.tree_util.tree_leaves(jax.eval_shape(
            ep._build_valset_windows, sds((10_240, 48))))
        assert (out.shape, out.dtype) == ((10_240, ep._WINDOW_WORDS), jnp.uint32)
    else:
        lanes, rows, kpad = 10_240, 64, 2
        compiled = ep._device_verify_packed.lower(
            sds((lanes, 20)), sds((lanes, 20)), sds((lanes, 8)), sds((lanes, 16)),
            sds((rows,)), sds((kpad,), jnp.int32), sds((lanes, kpad)),
            sds((64 * ep.K, ep.NROW, lanes)), lanes=ep.LANES).compile()
        # negax and ay are not read: the tables are the lanes' keys
        assert compiled.memory_analysis().argument_size_in_bytes < (
            4 * ep._WINDOW_WORDS * lanes + (2 << 20))
    assert compiled.as_text().count("tpu_custom_call") >= 1
