"""The secp256k1 device dispatch from inside: the five spans under
``verify.dispatch`` with one root, and the two counter families of its host
prologue.  The kernel itself is stood in for (its interpret-mode run takes
ten minutes and tests/test_ops_secp256k1.py holds its arithmetic to the
oracle); everything on the host is the dispatch's own code."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import secp256k1 as s
from tendermint_tpu.crypto.batch import SigItem, TPUBatchVerifier
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.ops import secp256k1_pallas as sp
from tendermint_tpu.ops import secp256k1_verify as xla


def _rows(n, tag=b"k"):
    rows = []
    for i in range(n):
        priv = s.gen_privkey(hashlib.sha256(tag + bytes([i])).digest())
        msg = b"precommit-%d" % i
        rows.append((s.pubkey_compressed(priv), msg,
                     s.sign(priv, hashlib.sha256(msg).digest())))
    return rows


@pytest.fixture()
def pallas_on_cpu(monkeypatch):
    """``TPUBatchVerifier``'s pallas branch without a chip: the real
    ``verify_batch`` in interpret mode, its pallas_call answered by a
    stand-in that accepts every lane it is given."""
    import jax.numpy as jnp

    calls = []

    def ladder(qx, qy, dig1, dig2, rl, rnl, rnok, **kw):
        calls.append((qx.shape, dig1.shape, kw["lanes"]))
        return jnp.ones((1, qx.shape[1]), jnp.uint32)

    real = sp.verify_batch
    monkeypatch.setattr(sp, "_ladder_call", ladder)
    monkeypatch.setattr(
        sp, "verify_batch",
        lambda p, d, g: real(p, d, g, interpret=True))
    v = TPUBatchVerifier(backend="xla")
    v.backend = "pallas"
    xla._decompress_cache.clear()
    xla.record_prologue(())  # flush what earlier tests looked up
    trace.reset(4096)
    trace.enable()
    yield v, calls
    trace.disable()


def _spans():
    return [ev for ev in trace.export() if ev.get("ph") == "X"]


def _counter(counter):
    return dict(counter.snapshot())


def test_the_five_spans_are_children_of_verify_dispatch_with_one_root(pallas_on_cpu):
    v, calls = pallas_on_cpu
    rows = _rows(11)
    with trace.span("commit.verify"):
        ok = v.verify_secp256k1([SigItem(*r) for r in rows])
    assert ok.all() and calls == [((20, 16), (64, 16), 8)]  # bucket of 11 at 8 lanes
    spans = _spans()
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev["name"], []).append(ev)
    dispatch, = by_name["verify.dispatch"]
    assert dispatch["args"]["algo"] == "secp256k1" and dispatch["args"]["n"] == 11
    parts = ["dispatch.prepare", "secp.prologue", "dispatch.pack",
             "dispatch.launch", "dispatch.wait"]
    starts = []
    for name in parts:
        ev, = by_name[name]
        assert ev["args"]["parent_id"] == dispatch["args"]["span_id"], name
        assert ev["args"]["root_id"] == dispatch["args"]["root_id"], name
        starts.append(ev["ts"])
    assert starts == sorted(starts)  # in the order a call goes through them
    root, = by_name["commit.verify"]
    assert dispatch["args"]["root_id"] == root["args"]["span_id"]
    assert by_name["secp.prologue"][0]["args"] == {
        **by_name["secp.prologue"][0]["args"], "n": 11, "forced": 0}
    assert by_name["dispatch.pack"][0]["args"]["lanes"] == 16
    # the parts cover the dispatch but for its own few lines
    covered = sum(by_name[n][0]["dur"] for n in parts)
    assert covered <= dispatch["dur"]


def test_the_device_function_has_a_name_no_ed25519_pattern_matches():
    from tendermint_tpu.ops import ed25519_pallas as ed

    assert sp._device_verify_secp256k1.__name__ == "_device_verify_secp256k1"
    assert "_device_verify_packed" not in sp._device_verify_secp256k1.__name__
    assert ed._device_verify_packed.__name__ != sp._device_verify_secp256k1.__name__


def test_host_decided_lanes_and_the_pubkey_cache_are_counted(pallas_on_cpu):
    v, _calls = pallas_on_cpu
    m = get_verify_metrics()
    rows = _rows(6, tag=b"c")
    # lane 1: DER with a needless zero (malformed); lane 2: high-s
    # (malformed); lane 4: the key of lane 0 again (a cache hit)
    lax = rows[1][2]
    lax = bytes([0x30, lax[1] + 1, 0x02, lax[3] + 1, 0x00]) + lax[4:]
    assert s.der_decode_sig(lax) is None
    rows[1] = (rows[1][0], rows[1][1], lax)
    r, sv = s.der_decode_sig(rows[2][2])
    rows[2] = (rows[2][0], rows[2][1], s.der_encode_sig(r, s.N - sv))
    priv0 = s.gen_privkey(hashlib.sha256(b"c" + bytes([0])).digest())
    rows[4] = (rows[0][0], rows[4][1],
               s.sign(priv0, hashlib.sha256(rows[4][1]).digest()))

    decided0, cache0 = _counter(m.secp256k1_host_decided), _counter(m.valset_cache)
    ok = v.verify_secp256k1([SigItem(*r) for r in rows])
    assert ok.tolist() == [True, False, False, True, True, True]
    decided1, cache1 = _counter(m.secp256k1_host_decided), _counter(m.valset_cache)
    assert decided1[("malformed",)] - decided0[("malformed",)] == 2
    assert decided1[("degenerate",)] == decided0[("degenerate",)]
    hit, miss = ("secp256k1_pubkey", "hit"), ("secp256k1_pubkey", "miss")
    assert cache1[miss] - cache0.get(miss, 0) == 5
    assert cache1[hit] - cache0.get(hit, 0) == 1
    prologue = [ev for ev in _spans() if ev["name"] == "secp.prologue"][-1]
    assert prologue["args"]["forced"] == 2

    # the same dispatch again: every key is a hit
    v.verify_secp256k1([SigItem(*r) for r in rows])
    cache2 = _counter(m.valset_cache)
    assert cache2[miss] == cache1[miss] and cache2[hit] - cache1[hit] == 6


def test_a_zero_digest_is_decided_by_the_host_oracle_as_degenerate(pallas_on_cpu):
    """u1 = e/s = 0: the ladder would degenerate, so the prologue asks the
    host oracle, whatever it says (here: a valid signature over e = 0)."""
    _v, _calls = pallas_on_cpu
    m = get_verify_metrics()
    priv = s.gen_privkey(b"\x21" * 32)
    pub = s.pubkey_compressed(priv)
    good = s.sign(priv, bytes(32))
    other = s.sign(priv, hashlib.sha256(b"x").digest())
    before = _counter(m.secp256k1_host_decided)
    ok = sp.verify_batch([pub, pub], [bytes(32), bytes(32)], [good, other])
    assert ok.tolist() == [True, False]
    after = _counter(m.secp256k1_host_decided)
    assert after[("degenerate",)] - before[("degenerate",)] == 2
    assert after[("malformed",)] == before[("malformed",)]


def test_the_xla_path_counts_its_prologue_too():
    m = get_verify_metrics()
    before = _counter(m.secp256k1_host_decided)
    pub, msg, sig = _rows(1, tag=b"x")[0]
    assert xla.prep_item(pub, hashlib.sha256(msg).digest(), b"\x30\x02\x01\x01") \
        == ("forced", 0, "malformed")
    assert xla.prep_item(pub, hashlib.sha256(msg).digest(), sig)[0] == "kernel"
    xla.record_prologue(["malformed"])
    after = _counter(m.secp256k1_host_decided)
    assert after[("malformed",)] - before[("malformed",)] == 1


def test_both_series_of_the_new_counter_are_exposed_from_zero():
    text = get_verify_metrics().registry.expose_text()
    for reason in ("malformed", "degenerate"):
        assert f'tendermint_verify_secp256k1_host_decided_total{{reason="{reason}"}}' in text


# ---------------------------------------------------------------------------
# the batch prologue: two passes round ONE modular inversion
# ---------------------------------------------------------------------------


def _fermat_prep_item(pubkey, digest, sig):
    """The per-lane prologue as it was before ``prep_batch`` (one Fermat
    ``pow`` a lane), kept here as the reference ``prep_batch`` is held to."""
    Q = xla._decompress_cached(pubkey)
    parsed = s.der_decode_sig(sig)
    if Q is None or parsed is None:
        return ("forced", 0, "malformed")
    r, sv = parsed
    if not (0 < r < s.N and 0 < sv < s.N) or sv > s._HALF_N:
        return ("forced", 0, "malformed")
    e = int.from_bytes(digest, "big")
    w = pow(sv, s.N - 2, s.N)
    u1 = e * w % s.N
    u2 = r * w % s.N
    if u1 == 0 or u2 == 0:
        return ("forced", int(s.verify(pubkey, digest, sig)), "degenerate")
    return ("kernel", Q, u1, u2, r)


def _off_curve_key():
    x = 1
    while s.decompress_pubkey(b"\x02" + x.to_bytes(32, "big")) is not None:
        x += 1
    return b"\x02" + x.to_bytes(32, "big")


# what pass one or pass two must make of each kind of lane
_KINDS = {
    "valid": "kernel", "s_zero": "malformed", "s_n": "malformed",
    "high_s": "malformed", "r_zero": "malformed", "lax_der": "malformed",
    "truncated_der": "malformed", "off_curve": "malformed",
    "zero_digest": "degenerate", "n_digest": "degenerate",
}
_CYCLE = ["valid", "s_zero", "valid", "s_n", "high_s", "valid", "r_zero",
          "lax_der", "valid", "truncated_der", "off_curve", "valid",
          "zero_digest", "valid", "n_digest"]


@pytest.fixture(scope="module")
def adversarial_rows():
    """257 seeded lanes (pubkey, digest, sig, kind): valid ones interleaved
    with every way a lane leaves the prologue early."""
    rows = []
    for i in range(257):
        kind = _CYCLE[i % len(_CYCLE)]
        priv = s.gen_privkey(hashlib.sha256(b"adv-%d" % i).digest())
        pub = s.pubkey_compressed(priv)
        digest = hashlib.sha256(b"precommit-%d" % i).digest()
        if kind == "zero_digest":  # e = 0, so u1 = 0
            digest = bytes(32)
        elif kind == "n_digest":  # e = n, which is 0 mod n: u1 = 0 again
            digest = s.N.to_bytes(32, "big")
        sig = s.sign(priv, digest)
        r, sv = s.der_decode_sig(sig)
        if kind == "s_zero":
            sig = s.der_encode_sig(r, 0)
        elif kind == "s_n":
            sig = s.der_encode_sig(r, s.N)
        elif kind == "high_s":
            sig = s.der_encode_sig(r, s.N - sv)
        elif kind == "r_zero":
            sig = s.der_encode_sig(0, sv)
        elif kind == "lax_der":  # r with a needless zero byte
            sig = bytes([0x30, sig[1] + 1, 0x02, sig[3] + 1, 0x00]) + sig[4:]
        elif kind == "truncated_der":
            sig = sig[:-3]
        elif kind == "off_curve":
            pub = _off_curve_key()
        rows.append((pub, digest, sig, kind))
    return rows


def _same_item(a, b):
    if a[0] != b[0] or a[0] == "forced":
        return a == b
    return (np.array_equal(a[1][0], b[1][0]) and np.array_equal(a[1][1], b[1][1])
            and a[2:] == b[2:])


def test_each_adversarial_lane_leaves_the_prologue_where_it_should(adversarial_rows):
    assert {k for *_, k in adversarial_rows} == set(_KINDS)
    pubs, digs, sigs, kinds = zip(*adversarial_rows)
    items, inversions = xla.prep_batch(pubs, digs, sigs)
    assert inversions == 1
    for item, kind in zip(items, kinds):
        assert (item[0] if item[0] == "kernel" else item[2]) == _KINDS[kind], kind
    # a degenerate lane is the host oracle's to decide: here both are valid
    assert all(it[1] == 1 for it in items if it[0] == "forced" and it[2] == "degenerate")


@pytest.mark.parametrize("n", [1, 2, 3, 255, 256, 257])
def test_prep_batch_equals_the_per_lane_fermat_prologue_lane_for_lane(adversarial_rows, n):
    """Same w for every lane, so the same u1, u2, limbs and verdicts: what
    reaches ``_device_verify_secp256k1`` is what reached it before."""
    pubs, digs, sigs, _kinds = zip(*adversarial_rows[257 - n:])  # tails: 257 - n starts mid-cycle
    want = [_fermat_prep_item(p, d, g) for p, d, g in zip(pubs, digs, sigs)]
    got, inversions = xla.prep_batch(pubs, digs, sigs)
    assert len(got) == n
    for lane, (a, b) in enumerate(zip(got, want)):
        assert _same_item(a, b), (lane, a, b)
    assert inversions == int(any(w[0] == "kernel" or w[2] == "degenerate" for w in want))
    # prep_item is the batch form on one lane, not a second implementation
    for lane in (0, n // 2, n - 1):
        assert _same_item(xla.prep_item(pubs[lane], digs[lane], sigs[lane]), want[lane])


def test_batch_inverse_is_one_inversion_and_exact():
    xs = [1, 2, s._HALF_N, s.N - 1] + [
        int.from_bytes(hashlib.sha256(b"x%d" % i).digest(), "big") % (s.N - 1) + 1
        for i in range(61)]
    inv = xla._batch_inverse(xs)
    assert all(x * w % s.N == 1 and 0 < w < s.N for x, w in zip(xs, inv))
    assert inv == [pow(x, s.N - 2, s.N) for x in xs]
    assert xla._batch_inverse([7]) == [pow(7, -1, s.N)]


def test_a_dispatch_inverts_once_or_not_at_all(pallas_on_cpu, adversarial_rows):
    """The counter and the span's ``inversions``: 0 where pass one refused
    every lane, 1 for any other dispatch, whatever its size."""
    m = get_verify_metrics()

    def dispatch(rows):
        before = _counter(m.secp256k1_inversions)[()]
        pubs, digs, sigs, _k = zip(*rows)
        ok = sp.verify_batch(pubs, digs, sigs)
        span = [ev for ev in _spans() if ev["name"] == "secp.prologue"][-1]
        return ok, _counter(m.secp256k1_inversions)[()] - before, span["args"]

    refused = [r for r in adversarial_rows if _KINDS[r[3]] == "malformed"][:9]
    ok, grew, args = dispatch(refused)
    assert not ok.any() and grew == 0
    assert (args["n"], args["forced"], args["inversions"]) == (9, 9, 0)

    for size in (1, 40, 257):
        rows = adversarial_rows[:size]
        ok, grew, args = dispatch(rows)
        forced = sum(_KINDS[k] != "kernel" for *_, k in rows)
        assert grew == 1, size
        assert (args["n"], args["forced"], args["inversions"]) == (size, forced, 1)
        # the stand-in kernel accepts its lanes; the host decided the others
        assert ok.tolist() == [_KINDS[k] != "malformed" for *_, k in rows]


def test_the_inversions_counter_is_exposed_from_zero():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    assert "tendermint_verify_secp256k1_inversions_total 0" in \
        VerifyMetrics().registry.expose_text().splitlines()


def test_pallas_pipeline_and_xla_path_decide_the_same_batch_alike(
        pallas_on_cpu, adversarial_rows, monkeypatch):
    """One prologue, two callers: the Pallas pipeline in interpret mode (its
    packed digits and limbs put through the XLA kernel's arithmetic, the
    interpret-mode ladder itself taking ten minutes) and the XLA path give
    the host oracle's verdict on every lane, and each inverts once."""
    import jax.numpy as jnp

    def xla_ladder(qx, qy, dig1, dig2, rl, rnl, rnok, **kw):
        b = qx.shape[1]

        def lanes(a):  # limb-major (k, b) -> lane-major, padded to the XLA bucket
            a = np.asarray(a).T
            return np.pad(a, ((0, 32 - b), (0, 0)))

        def words(dig):  # 64 4-bit digits, msb first -> 8 little-endian words
            d = lanes(dig).astype(np.uint64)[:, ::-1].reshape(32, 8, 8)
            return (d << (4 * np.arange(8, dtype=np.uint64))).sum(axis=2).astype(np.uint32)

        kernel = xla._compiled_kernel(32, None, "lazy")
        ok = kernel(lanes(qx), lanes(qy), words(dig1), words(dig2), lanes(rl),
                    lanes(rnl), lanes(rnok)[:, 0].astype(bool))
        return jnp.asarray(np.asarray(ok)[:b].astype(np.uint32))[None, :]

    monkeypatch.setattr(sp, "_ladder_call", xla_ladder)
    rows = [list(r) for r in adversarial_rows[:15]]  # one of every kind
    # two well-formed lanes the kernel itself must refuse: a bit of s
    # flipped (still low-s, still strict), and another lane's signature
    r, sv = s.der_decode_sig(rows[0][2])
    rows[0][2] = s.der_encode_sig(r, sv ^ 1)
    rows[2][2] = rows[5][2]
    pubs, digs, sigs, kinds = zip(*rows)
    want = [s.verify(p, d, g) for p, d, g in zip(pubs, digs, sigs)]
    assert want.count(True) == 6 and not want[0] and not want[2]

    m = get_verify_metrics()
    before = _counter(m.secp256k1_inversions)[()]
    from_pallas = sp.verify_batch(pubs, digs, sigs)
    from_xla = xla.verify_batch(pubs, digs, sigs)
    assert from_pallas.tolist() == from_xla.tolist() == want
    assert _counter(m.secp256k1_inversions)[()] - before == 2
