"""The ladder's resident form (PR 47): a membership's window tables are built
once, ``[0..15] * 16^(16 (K-1-k)) * (-A)`` a member at K offsets of the
scalar, and a launch whose caller handed a ``ValsetRows`` down reads its
lanes' tables and runs ``64 / K`` rounds of 4 doublings with K additions
from the B tables and K from the members'.  Same group element as the built
form, so the same canonical encoding and the same Go accept set.

No chip here and the interpreted kernels take minutes, so, as
tests/test_pallas_interpret.py does, the pure-jnp bodies the Pallas kernels
run verbatim (``window_tables_math``, ``ladder_math``) are evaluated eagerly
with a Python loop: at a reduced window count against the built form, and at
full width against the host's bigint curve arithmetic and, through
``verify_batch``'s own table, gather and launch, against the host oracle on
the accept set's edges.  The real kernels' verdicts, lane for lane against
the host, are chip_smoke.py's."""

import hashlib
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.ops import ed25519_pallas as ep

P, K = ed.P, ep.K


def _py_loop(lo, hi, body, init):
    acc = init
    for t in range(lo, hi):
        acc = body(t, acc)
    return acc


def _to_int(col) -> int:
    return sum(int(v) << (13 * i) for i, v in enumerate(np.asarray(col)))


def _msb_digits(x: int, nwin: int) -> np.ndarray:
    return np.array([(x >> (4 * (nwin - 1 - t))) & 0xF for t in range(nwin)],
                    np.uint32)


def _window_tables(neg_ax, ay, stride):
    """(64 K, 20, n): ``window_tables_math``'s rows for the lanes' -A."""
    rows = {}
    ep.window_tables_math(
        jnp.asarray(ep._consts(stride)), jnp.asarray(neg_ax.T.copy()),
        jnp.asarray(ay.T.copy()), lambda m, r: rows.__setitem__(int(m), r),
        stride=stride, loop=_py_loop)
    assert sorted(rows) == list(range(64 * K))
    return np.stack([np.asarray(rows[m]) for m in range(64 * K)])


def _keys(n, seed):
    rng = np.random.default_rng(seed)
    privs = [ed.gen_privkey(rng.bytes(32)) for _ in range(n)]
    pubs = np.frombuffer(b"".join(p[32:] for p in privs), np.uint8).reshape(n, 32)
    return privs, pubs.copy()


def _neg_a(pub: bytes):
    x, y = ed._decompress_xy(pub)
    return ed._to_extended(((P - x) % P, y))


# ---------------------------------------------------------------------------
# The resident form against the built one, at a reduced window count
# ---------------------------------------------------------------------------


def test_the_resident_ladder_is_the_built_ladder_mod_p(nwin=2 * K):
    n, stride = 6, nwin // K
    _privs, pubs = _keys(n, seed=4700 + nwin)
    neg_ax, ay, valid = ep._decompress_rows(pubs)
    assert valid.all()
    rng = np.random.default_rng(nwin)
    digs = rng.integers(0, 16, (nwin, n)).astype(np.uint32)
    digh = rng.integers(0, 16, (nwin, n)).astype(np.uint32)
    digs[:, 0] = 0          # [0]B: identity through the niels digit-0 entry
    digh[:, 1] = 0          # [0](-A): identity through the cached digit-0 entry
    digs[:, 2] = digh[:, 2] = 15
    dj, hj = jnp.asarray(digs), jnp.asarray(digh)
    consts = jnp.asarray(ep._consts(stride))
    tables = _window_tables(neg_ax, ay, stride)

    def run(**form):
        return [np.asarray(v) for v in ep.ladder_math(
            consts, form.pop("negax", None), form.pop("ay", None),
            lambda t: dj[t:t + 1, :], lambda t: hj[t:t + 1, :],
            nwin=nwin, loop=_py_loop, **form)]

    resident = run(tables=lambda m: jnp.asarray(tables[m]))
    built = run(negax=jnp.asarray(neg_ax.T.copy()), ay=jnp.asarray(ay.T.copy()))
    B_ext = ed._to_extended((ed.B_AFFINE, ed._BY))
    for i in range(n):
        rx, ry, rz, rt = (_to_int(v[:, i]) for v in resident)
        bx, by, bz, _bt = (_to_int(v[:, i]) for v in built)
        assert rz % P and bz % P
        assert rx * bz % P == bx * rz % P and ry * bz % P == by * rz % P
        assert rt * rz % P == rx * ry % P  # the extended invariant
        s = sum(int(d) << (4 * (nwin - 1 - t)) for t, d in enumerate(digs[:, i]))
        h = sum(int(d) << (4 * (nwin - 1 - t)) for t, d in enumerate(digh[:, i]))
        ex, ey, ez, _ = ed.pt_add(ed.pt_scalar_mult(B_ext, s),
                                  ed.pt_scalar_mult(_neg_a(pubs[i].tobytes()), h))
        assert rx * ez % P == ex * rz % P and ry * ez % P == ey * rz % P


def test_the_resident_form_is_written_in_lazy_carries_for_whole_rounds():
    consts = jnp.asarray(ep._CONSTS)
    row = jnp.zeros((1, 8), jnp.uint32)
    for nwin, carry_mode in ((K + 1, "lazy"), (K, "eager")):
        with pytest.raises(ValueError, match="resident tables"):
            ep.ladder_math(consts, None, None, lambda t: row, lambda t: row,
                           nwin=nwin, loop=_py_loop, carry_mode=carry_mode,
                           tables=lambda m: None)


def test_the_b_tables_are_the_base_point_at_every_offset():
    B_ext = ed._to_extended((ed.B_AFFINE, ed._BY))
    for stride in (1, ep.NWIN // K):
        consts = ep._consts(stride)
        assert consts.shape == (20, ep._B_COL + 48 * K)
        assert np.array_equal(consts[:, :51], ep._CONSTS[:, :51])
        for k in range(K):
            for j in (0, 1, 9, 15):
                X, Y, Z, _T = ed.pt_scalar_mult(
                    B_ext, j * 16 ** (stride * (K - 1 - k))) if j else (0, 1, 1, 0)
                zi = pow(Z, P - 2, P)
                x, y = X * zi % P, Y * zi % P
                col = ep._B_COL + 48 * k + j
                assert [_to_int(consts[:, col + 16 * c]) for c in range(3)] == [
                    (y + x) % P, (y - x) % P, 2 * ed.D * x * y % P]


# ---------------------------------------------------------------------------
# At full width: the build's entries, and the accept set through verify_batch
# ---------------------------------------------------------------------------

LONG = 110


def _accept_set_lanes():
    """Keys, messages and signatures on the edges of the Go accept set."""
    privs, pubs = _keys(4, seed=4747)
    lanes = []

    def lane(pub, msg, sig):
        lanes.append((bytes(pub), msg, bytes(sig)))

    msgs = [bytes([i]) * LONG for i in range(4)]
    sigs = [ed.sign(privs[i], msgs[i]) for i in range(4)]
    lane(pubs[0].tobytes(), msgs[0], sigs[0])                   # plainly valid
    s = int.from_bytes(sigs[1][32:], "little") + ed.L           # s + L: accepted
    assert s < 1 << 253
    lane(pubs[1].tobytes(), msgs[1], sigs[1][:32] + s.to_bytes(32, "little"))
    high = bytearray(sigs[2])
    high[63] |= 0x20                                            # s with a high bit
    lane(pubs[2].tobytes(), msgs[2], high)
    flipped = bytearray(sigs[3])
    flipped[5] ^= 1                                             # another R
    lane(pubs[3].tobytes(), msgs[3], flipped)
    lane(pubs[3].tobytes(), msgs[3], sigs[3])
    # low-order keys and their non-canonical twins (y and y + p name one
    # point and hash differently), an all-zero signature under each
    small = [y for y in range(19) if ed._decompress_xy(y.to_bytes(32, "little"))]
    assert 1 in small and len(small) >= 3
    for y in small:
        for enc in (y, y + P):
            raw = enc.to_bytes(32, "little")
            # [h](-A) has a handful of values: some message makes it the
            # point a zero R names, a true equation nobody signed
            msg = next((m for m in (bytes([c]) * LONG for c in range(32))
                        if ed.verify(raw, m, b"\x00" * 64)), b"m" * LONG)
            lane(raw, msg, b"\x00" * 64)
            lane(raw, b"m" * LONG, b"\x00" * 64)
    # the identity as the key: R = identity and s = 0 is a true equation;
    # the same R written non-canonically (1 + p) is not R's bytes
    ident = (1).to_bytes(32, "little")
    lane(ident, b"i" * LONG, ident + b"\x00" * 32)
    lane(ident, b"i" * LONG, (1 + P).to_bytes(32, "little") + b"\x00" * 32)
    # bytes that are no point at all
    for y in range(2, 200):
        raw = y.to_bytes(32, "little")
        if ed._decompress_xy(raw) is None:
            lane(raw, b"n" * LONG, sigs[0])
            break
    pubs = np.frombuffer(b"".join(l[0] for l in lanes), np.uint8).reshape(-1, 32)
    sigs = np.frombuffer(b"".join(l[2] for l in lanes), np.uint8).reshape(-1, 64)
    return pubs.copy(), [l[1] for l in lanes], sigs.copy()


@pytest.fixture(scope="module")
def accept_set():
    pubs, msgs, sigs = _accept_set_lanes()
    want = [ed.verify(pubs[i].tobytes(), msgs[i], sigs[i].tobytes())
            for i in range(len(msgs))]
    # the set holds accepts and refusals of each kind, or it shows nothing
    assert want[:5] == [True, True, False, False, True]
    assert any(want[5:-3]) and not all(want[5:-3])
    assert want[-3:] == [True, False, False]
    neg_ax, ay, valid = ep._decompress_rows(pubs)
    return SimpleNamespace(
        pubs=pubs, msgs=msgs, sigs=sigs, want=want, neg_ax=neg_ax, ay=ay,
        valid=valid, tables=_window_tables(neg_ax, ay, ep.NWIN // K))


def test_the_builds_entries_are_the_multiples_of_minus_a_at_every_offset(accept_set):
    a = accept_set
    assert a.tables.shape == (64 * K, 20, len(a.msgs))
    stride = ep.NWIN // K
    for i in np.nonzero(a.valid)[0][[0, 3, 6, -1]]:
        neg_a = _neg_a(a.pubs[i].tobytes())
        for k in range(K):
            for j in range(16):
                X, Y, Z, T = ed.pt_scalar_mult(
                    neg_a, j * 16 ** (stride * (K - 1 - k))) if j else (0, 1, 1, 0)
                ypx, ymx, z, t2d = (
                    _to_int(a.tables[(4 * k + c) * 16 + j][:, i]) % P
                    for c in range(4))
                # cached-niels (Y + X, Y - X, Z, 2dT), up to the projective scale
                assert z and ypx * Z % P == (Y + X) * z % P
                assert ymx * Z % P == (Y - X) * z % P
                assert t2d * Z % P == 2 * ed.D * T * z % P
                if j == 0:
                    assert (ypx, ymx, t2d) == (z, z, 0)  # the identity


_LANE_VERDICTS = {}  # a lane's operands -> its verdict: the launch is pure


def _resident_launch(negax, ay, pub_words, sig_words, tmpl, vidx, vwords, tables):
    """``_device_verify_packed``'s resident form on the host: the prologue
    from hashlib, the ladder ``ladder_math`` itself over the tables the
    launch was handed, the encoding from bigints.  Thirteen seconds a
    launch, so a lane handed what an earlier launch's lane was handed (its
    signature, key, message AND window tables, byte for byte) is not run
    again."""
    b = sig_words.shape[0]
    mw = np.broadcast_to(tmpl, (b, tmpl.shape[0])).copy()
    mw[:, vidx] = vwords
    padded = mw.astype(">u4").view(np.uint8).reshape(b, -1)
    sig = sig_words.astype("<u4").view(np.uint8).reshape(b, 64)
    pub = pub_words.astype("<u4").view(np.uint8).reshape(b, 32)
    total = int.from_bytes(padded[0, -16:].tobytes(), "big") // 8
    lanes = [hashlib.sha256(sig[i].tobytes() + pub[i].tobytes() + padded[i].tobytes()
                            + tables[:, :, i].tobytes()).digest() for i in range(b)]
    if all(lane in _LANE_VERDICTS for lane in lanes):
        return np.array([_LANE_VERDICTS[lane] for lane in lanes])
    digs = np.zeros((ep.NWIN, b), np.uint32)
    digh = np.zeros((ep.NWIN, b), np.uint32)
    for i in range(b):
        h = hashlib.sha512(sig[i, :32].tobytes() + pub[i].tobytes()
                           + padded[i, 64:total].tobytes()).digest()
        digh[:, i] = _msb_digits(int.from_bytes(h, "little") % ed.L, ep.NWIN)
        digs[:, i] = _msb_digits(int.from_bytes(sig[i, 32:].tobytes(), "little"),
                                 ep.NWIN)
    dj, hj = jnp.asarray(digs), jnp.asarray(digh)
    X, Y, Z, _T = (np.asarray(v) for v in ep.ladder_math(
        jnp.asarray(ep._CONSTS), None, None,
        lambda t: dj[t:t + 1, :], lambda t: hj[t:t + 1, :],
        loop=_py_loop, tables=lambda m: jnp.asarray(tables[m, :ep.NLIMB, :])))
    ok = np.zeros((b,), bool)
    for i in range(b):
        zi = pow(_to_int(Z[:, i]), P - 2, P)
        x, y = _to_int(X[:, i]) * zi % P, _to_int(Y[:, i]) * zi % P
        ok[i] = (y | (x & 1) << 255).to_bytes(32, "little") == sig[i, :32].tobytes()
    _LANE_VERDICTS.update(zip(lanes, ok.tolist()))
    return ok


@pytest.mark.parametrize("slots", ["every_slot", "a_subset"])
def test_the_accept_set_through_a_memberships_tables(slots, accept_set, monkeypatch):
    """``verify_batch(..., valset=ValsetRows(...))`` with the programs stood
    in for by THE MATH THEY RUN: the table's fill, the gather and the
    launch's operands are the wrapper's own."""
    a = accept_set
    n = len(a.msgs)
    seen = SimpleNamespace(launches=0)

    def call_jit(fn, *args, **static):
        if fn is ep._gather_valset_rows:
            return fn(*args)
        if fn is ep._build_valset_windows:
            # the build program's rows as it lays them out: a row a member,
            # NROW limbs an entry, and the zero row's own tables behind
            assert np.array_equal(np.asarray(args[0])[:n, :20], a.neg_ax)
            rows = np.zeros((n + 1, 64 * K, ep.NROW), np.uint32)
            rows[:n, :, :ep.NLIMB] = a.tables.transpose(2, 0, 1)
            return jnp.asarray(rows.reshape(n + 1, ep._WINDOW_WORDS))
        assert fn is ep._device_verify_packed and len(args) == 8
        seen.launches += 1
        return _resident_launch(*(np.asarray(x) for x in args))

    monkeypatch.setattr(ep, "call_jit", call_jit)
    monkeypatch.setattr(ep, "_valset_tables", {})
    idx = np.arange(n) if slots == "every_slot" else np.array(
        [i for i in range(n) if i % 4 != 1])
    rows = batch.ValsetRows(batch.valset_key(a.pubs), a.pubs,
                            None if slots == "every_slot" else idx)
    got = ep.verify_batch(a.pubs[idx], [a.msgs[i] for i in idx], a.sigs[idx],
                          valset=rows)
    assert got.tolist() == [a.want[i] for i in idx]
    assert seen.launches == 1
