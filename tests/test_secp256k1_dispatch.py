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
        lambda p, d, g, fe_backend="vpu": real(p, d, g, interpret=True,
                                               fe_backend=fe_backend))
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
