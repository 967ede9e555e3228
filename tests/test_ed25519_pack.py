"""The host side of an ed25519 Pallas dispatch packs only what
``_device_verify_packed`` is handed.

Two halves.  (1) ``pack_variable_words`` and ``_sig_words`` against the
versions that built every lane's padded SHA-512 input and then kept a few
of its words, kept here verbatim as the reference: the arrays the device
is handed must be the same bytes, shapes and dtypes for every input, so
the served programs, their jit keys and the persistent cache are those of
before.  (2) ``verify_batch`` with the jitted program stood in for (no
chip here): a one-length batch goes down once as the caller's own columns,
a batch of two lengths is regrouped and launched once a length, and a
dispatch hashes its keys once.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.ops import ed25519_pallas as ep


# ---------------------------------------------------------------------------
# The reference: ops/ed25519_pallas.py as of PR 28, verbatim
# ---------------------------------------------------------------------------


def _ref_pack_variable_words(pubs, msgs, sigs, ln, b):
    n = pubs.shape[0]
    total = 64 + ln
    nblocks = (total + 1 + 16 + 127) // 128
    rows = nblocks * 32
    m = (
        np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(n, ln)
        if ln else np.zeros((n, 0), np.uint8)
    )
    # template = row 0's padded SHA input, as BE words
    pad0 = np.zeros((nblocks * 128,), dtype=np.uint8)
    pad0[:32] = sigs[0, :32]
    pad0[32:64] = pubs[0]
    pad0[64:total] = m[0]
    pad0[total] = 0x80
    pad0[-16:] = np.frombuffer((total * 8).to_bytes(16, "big"), np.uint8)
    tmpl = (
        np.ascontiguousarray(pad0.reshape(-1, 4)[:, ::-1].reshape(-1))
        .view("<u4").astype(np.uint32)
    )
    # message byte columns that differ across the batch -> padded word rows
    diff_cols = np.nonzero((m != m[0]).any(axis=0))[0]
    vrows = np.unique((64 + diff_cols) // 4).astype(np.int32)
    if vrows.size == 0:
        vrows = np.array([16], np.int32)  # row 16 always exists (rows>=32)
    k = int(vrows.size)
    k_pad = 1 << (k - 1).bit_length()
    # per-signature BE words at the varying rows
    mpad = np.zeros((b, (rows - 16) * 4), dtype=np.uint8)
    mpad[:n, : total - 64] = m
    mpad[:, total - 64] = 0x80
    mpad[:, -16:] = np.frombuffer((total * 8).to_bytes(16, "big"), np.uint8)
    mwords = (
        np.ascontiguousarray(mpad.reshape(b, -1, 4)[:, :, ::-1].reshape(b, -1))
        .view("<u4").astype(np.uint32)
    )
    vwords = mwords[:, vrows - 16]
    if k_pad > k:  # duplicate scatter rows carry identical values
        vrows = np.concatenate([vrows, np.full((k_pad - k,), vrows[0], np.int32)])
        vwords = np.concatenate(
            [vwords, np.tile(vwords[:, :1], (1, k_pad - k))], axis=1
        )
    return tmpl, vrows, vwords


def _ref_sig_words(sigs, valid):
    sig_words = np.ascontiguousarray(sigs).view("<u4").astype(np.uint32)
    sig_words[~valid] = 0
    return sig_words


def _ref_pad_rows(a, b):
    if a.shape[0] == b:
        return a
    return np.concatenate(
        [a, np.zeros((b - a.shape[0],) + a.shape[1:], dtype=a.dtype)], axis=0
    )


# ---------------------------------------------------------------------------
# Message shapes
# ---------------------------------------------------------------------------


def _keys_and_sigs(n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (n, 32), dtype=np.uint8),
            rng.integers(0, 256, (n, 64), dtype=np.uint8))


def _messages(n, ln, vary, seed=29):
    """n messages of ln bytes, one base with the byte columns ``vary``
    drawn per lane (``"all"``: every byte)."""
    rng = np.random.default_rng(seed)
    if vary == "all":
        m = rng.integers(0, 256, (n, ln), dtype=np.uint8)
    else:
        m = np.tile(rng.integers(0, 256, (ln,), dtype=np.uint8), (n, 1))
        for c in vary:
            # row 0 apart from every other lane, so the column does vary
            m[:, c] = rng.permutation(256)[np.arange(n) % 256].astype(np.uint8)
    return [r.tobytes() for r in m]


# a precommit's canonical sign-bytes: 110 bytes; in a commit only the
# fixed64 timestamp differs, in a sync window height, both hashes and the
# timestamp do
_TIMESTAMP = list(range(93, 101))
_SYNC_FIELDS = (list(range(2, 10)) + list(range(14, 46)) + list(range(50, 82))
                + _TIMESTAMP)

SHAPES = {
    "commit_like":        dict(n=300, b=384, ln=110, vary=_TIMESTAMP),
    "commit_like_full":   dict(n=256, b=256, ln=110, vary=_TIMESTAMP),
    "sync_window_like":   dict(n=200, b=256, ln=110, vary=_SYNC_FIELDS),
    "sync_window_full":   dict(n=128, b=128, ln=110, vary=_SYNC_FIELDS),
    "nothing_varies":     dict(n=40, b=128, ln=110, vary=[]),
    "nothing_varies_full": dict(n=8, b=8, ln=110, vary=[]),
    # 64 + 110 = 174 = 43 words and two bytes: the last message bytes share
    # word row 43 with the 0x80 and a zero of the padding
    "beside_the_0x80":    dict(n=40, b=128, ln=110, vary=[109]),
    "beside_the_0x80_full": dict(n=16, b=16, ln=110, vary=[108, 109]),
    "first_byte_only":    dict(n=40, b=128, ln=110, vary=[0]),
    "one_lane":           dict(n=1, b=128, ln=110, vary="all"),
    "three_blocks":       dict(n=20, b=128, ln=200, vary=[0, 101, 199]),
}
# message lengths round the block edges of R || A || M || 0x80 || length:
# 47 is the last one-block input, 48 the first of two, 175 the last of two
for _ln in (0, 1, 47, 48, 110, 111, 112, 175, 176):
    for _full in (False, True):
        SHAPES[f"ln{_ln}_all_vary{'_full' if _full else ''}"] = dict(
            n=24, b=24 if _full else 128, ln=_ln, vary="all")
    if _ln > 1:
        SHAPES[f"ln{_ln}_last_byte"] = dict(n=24, b=128, ln=_ln, vary=[_ln - 1])


def _same(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_packed_arrays_are_the_references_byte_for_byte(shape):
    s = SHAPES[shape]
    pubs, sigs = _keys_and_sigs(s["n"], seed=len(shape))
    msgs = _messages(s["n"], s["ln"], s["vary"])
    got = ep.pack_variable_words(pubs, msgs, sigs, s["ln"], s["b"])
    want = _ref_pack_variable_words(pubs, msgs, sigs, s["ln"], s["b"])
    for g, w in zip(got, want):
        _same(g, w)
    tmpl, vrows, vwords = got
    assert tmpl.dtype == vwords.dtype == np.uint32 and vrows.dtype == np.int32
    assert vwords.flags["C_CONTIGUOUS"] and vwords.shape == (s["b"], vrows.size)


@pytest.mark.parametrize("shape,k_pad,first_row", [
    ("commit_like", 4, 39),        # bytes 93..100 of the message: rows 39..41
    ("sync_window_like", 32, 16),
    ("nothing_varies", 1, 16),
    ("beside_the_0x80", 1, 43),
    ("ln0_all_vary", 1, 16),       # no message byte: nothing can vary
])
def test_the_jit_key_is_the_one_it_was(shape, k_pad, first_row):
    """``k_pad`` rides in ``vrows``' shape into the served program's key."""
    s = SHAPES[shape]
    pubs, sigs = _keys_and_sigs(s["n"], seed=7)
    msgs = _messages(s["n"], s["ln"], s["vary"])
    _tmpl, vrows, vwords = ep.pack_variable_words(
        pubs, msgs, sigs, s["ln"], s["b"])
    assert vrows.shape == (k_pad,) and vwords.shape == (s["b"], k_pad)
    assert vrows[0] == first_row


def test_real_precommits_pack_as_the_reference_packs_them():
    from tendermint_tpu.testutil.chain import build_commit

    valset, _block_id, commit = build_commit(12, height=3)
    pubs = np.frombuffer(b"".join(
        v.pub_key.bytes() for v in valset.validators), np.uint8).reshape(12, 32)
    msgs = [pc.sign_bytes("bench-chain") for pc in commit.precommits]
    sigs = np.frombuffer(b"".join(
        pc.signature for pc in commit.precommits), np.uint8).reshape(12, 64)
    ln = len(msgs[0])
    got = ep.pack_variable_words(pubs, msgs, sigs, ln, 128)
    for g, w in zip(got, _ref_pack_variable_words(pubs, msgs, sigs, ln, 128)):
        _same(g, w)
    assert got[1].size <= 4  # the fixed64 timestamp alone: at most three rows


@pytest.mark.parametrize("n,b,invalid", [
    (40, 128, []),            # every lane valid, padded
    (40, 128, [0, 17, 39]),   # invalid lanes: their words zeroed
    (24, 24, [5]),            # n == b
    (24, 24, list(range(24))),
    (1, 8, [0]),
])
def test_signature_words_are_the_references(n, b, invalid):
    _pubs, sigs = _keys_and_sigs(n, seed=n + b)
    valid = np.ones((n,), bool)
    valid[invalid] = False
    want = _ref_pad_rows(_ref_sig_words(sigs, valid), b)
    _same(ep._sig_words(sigs, valid, b), want)
    # the two-step form other callers use
    _same(ep._pad_rows(ep._sig_words(sigs, valid), b), want)
    assert not ep._sig_words(sigs, valid, b)[invalid].any()
    assert sigs.any(axis=1).all()  # the caller's array is not written to


# ---------------------------------------------------------------------------
# verify_batch, the program stood in for
# ---------------------------------------------------------------------------


class _CountingHashlib:
    """``hashlib`` as ``crypto/batch.valset_key`` sees it (the one place a
    key array's identity is taken; ops/ed25519_pallas calls it), counting
    the SHA-256 calls over a whole key array (>= 100 KB)."""

    def __init__(self):
        import hashlib

        self._hashlib = hashlib
        self.large = 0

    def sha256(self, data=b""):
        if memoryview(data).nbytes >= 100_000:
            self.large += 1
        return self._hashlib.sha256(data)


@pytest.fixture
def stood_in(monkeypatch):
    """The packed path with ``call_jit`` stood in for: each launch recorded
    as the host arrays it was handed; a lane's verdict is whether the first
    byte of its signature is even, so a lane routed to the wrong place
    shows."""
    launches = []

    def fake_call_jit(fn, *args, **static):
        assert fn is ep._device_verify_packed
        host = [np.asarray(a) for a in args]
        launches.append(host)
        return (host[3][:, 0] & 1) == 0

    packed = []
    real_pack = ep.pack_variable_words

    def recording_pack(pubs, msgs, sigs, ln, b):
        packed.append((pubs, msgs, sigs, ln, b))
        return real_pack(pubs, msgs, sigs, ln, b)

    hashes = _CountingHashlib()
    monkeypatch.setattr(ep, "call_jit", fake_call_jit)
    monkeypatch.setattr(ep, "pack_variable_words", recording_pack)
    monkeypatch.setattr(batch, "hashlib", hashes)
    monkeypatch.setattr(ep, "_valset_cache", {})
    monkeypatch.setattr(ep, "_dev_valset_cache", {})
    return launches, packed, hashes


def _want_verdicts(sigs, valid):
    return valid & ((sigs[:, 0] & 1) == 0) & ((sigs[:, 63] & 224) == 0)


def _pack_series(verify_counters):
    return {
        path: verify_counters("tendermint_verify_ed25519_pack_total",
                              {"path": path})
        for path in ("uniform", "grouped")}


def _cache_series(verify_counters):
    return {
        cache: verify_counters("tendermint_verify_valset_cache_total",
                               {"cache": cache})
        for cache in ("host", "device")}


def _moved(after, before):
    return {k: after[k] - before[k] for k in before}


N_KEYS = 3200  # 102,400 bytes of keys


@pytest.fixture(scope="module")
def big_batch():
    pubs, sigs = _keys_and_sigs(N_KEYS, seed=2929)
    sigs[:, 63] &= 31           # Go's s range check passes ...
    sigs[5, 63] |= 32           # ... but for one lane
    msgs = _messages(N_KEYS, 110, _TIMESTAMP)
    # decompressed once here, outside the counted calls (random bytes: about
    # half of them are no curve point, which is the ``valid`` column's work)
    _neg_ax, _ay, valid = ep._decompress_valset(pubs)
    return pubs, msgs, sigs, valid.copy()


def test_a_one_length_batch_goes_down_once_as_the_callers_columns(
        stood_in, big_batch, verify_counters, tracing):
    launches, packed, hashes = stood_in
    pubs, msgs, sigs, key_ok = big_batch
    packs, caches = _pack_series(verify_counters), _cache_series(verify_counters)

    ok = ep.verify_batch(pubs, msgs, sigs)

    assert len(launches) == 1
    (p_pubs, p_msgs, p_sigs, p_ln, p_b), = packed
    assert p_pubs is pubs and p_msgs is msgs and p_sigs is sigs  # no copies
    assert (p_ln, p_b) == (110, 4096)
    assert _moved(_pack_series(verify_counters), packs) == {
        "uniform": 1, "grouped": 0}
    assert _moved(_cache_series(verify_counters), caches) == {
        "host": 1, "device": 1}
    assert hashes.large == 1
    assert ok.tolist() == _want_verdicts(sigs, key_ok).tolist()
    assert ok.any() and not ok.all() and not ok[5]
    # what the device was handed is what the reference packs
    _negax, _ay, _pubw, sig_words, tmpl, vrows, vwords = launches[0]
    valid = key_ok & ((sigs[:, 63] & 224) == 0)
    _same(sig_words, _ref_pad_rows(_ref_sig_words(sigs, valid), 4096))
    for g, w in zip((tmpl, vrows, vwords),
                    _ref_pack_variable_words(pubs, msgs, sigs, 110, 4096)):
        _same(g, w)
    spans = {e["name"]: e["args"] for e in tracing.export()
             if e.get("ph") == "X"}
    assert spans["dispatch.prepare"]["groups"] == 1
    assert spans["dispatch.pack"]["vwords"] == 4


def test_a_second_call_hashes_the_keys_once_more_and_hits_both_caches(
        stood_in, big_batch, verify_counters):
    _launches, _packed, hashes = stood_in
    pubs, msgs, sigs, _key_ok = big_batch

    def hits():
        return {c: verify_counters("tendermint_verify_valset_cache_total",
                                   {"cache": c, "result": "hit"})
                for c in ("host", "device")}

    ep.verify_batch(pubs, msgs, sigs)
    before, large = hits(), hashes.large
    ep.verify_batch(pubs, msgs, sigs)
    assert _moved(hits(), before) == {"host": 1, "device": 1}
    assert hashes.large == large + 1


def test_a_two_length_batch_is_regrouped_and_launched_once_a_length(
        stood_in, big_batch, verify_counters, tracing):
    launches, packed, _hashes = stood_in
    pubs, msgs, sigs, key_ok = big_batch
    # every third lane a nil precommit's shorter sign-bytes
    msgs = [m[:70] if i % 3 == 0 else m for i, m in enumerate(msgs)]
    packs, caches = _pack_series(verify_counters), _cache_series(verify_counters)

    ok = ep.verify_batch(pubs, msgs, sigs)

    assert len(launches) == 2
    assert sorted((p[3], p[0].shape[0]) for p in packed) == [
        (70, 1067), (110, 2133)]
    assert all(p[0] is not pubs for p in packed)
    assert _moved(_pack_series(verify_counters), packs) == {
        "uniform": 0, "grouped": 1}
    # one look-up of the decompressed set a call, one of the device's copies
    # a launch
    assert _moved(_cache_series(verify_counters), caches) == {
        "host": 1, "device": 2}
    assert ok.tolist() == _want_verdicts(sigs, key_ok).tolist()
    # each launch is handed what the reference packs of its own lanes
    by_len = {p[3]: p for p in packed}
    for launch in launches:
        _negax, _ay, _pubw, sig_words, tmpl, vrows, vwords = launch
        ln = int(tmpl[-1]) // 8 - 64  # the padded input ends in its bit length
        g_pubs, g_msgs, g_sigs, _ln, b = by_len[ln]
        idx = np.array([i for i in range(N_KEYS) if (i % 3 == 0) == (ln == 70)])
        assert g_sigs.tobytes() == sigs[idx].tobytes()
        valid = (key_ok & ((sigs[:, 63] & 224) == 0))[idx]
        _same(sig_words, _ref_pad_rows(_ref_sig_words(g_sigs, valid), b))
        for g, w in zip((tmpl, vrows, vwords),
                        _ref_pack_variable_words(g_pubs, g_msgs, g_sigs, ln, b)):
            _same(g, w)
    spans = [e for e in tracing.export() if e.get("ph") == "X"]
    (prepare,) = [e for e in spans if e["name"] == "dispatch.prepare"]
    assert prepare["args"]["groups"] == 2
    assert len([e for e in spans if e["name"] == "dispatch.pack"]) == 2


def test_the_pack_counter_is_exposed_from_zero():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    text = VerifyMetrics().registry.expose_text().splitlines()
    for path in ("uniform", "grouped"):
        assert f'tendermint_verify_ed25519_pack_total{{path="{path}"}} 0' in text
