"""The point ops the chip traces, one at a time, against the host's bignum
arithmetic.

The served programs run ``ops/ed25519_pallas``'s ``pt_add`` / ``pt_madd`` /
``pt_add_cached`` / ``pt_double`` and ``ops/secp256k1_pallas``'s
``_pt_add_lazy`` under the lazy ``fe`` namespace (``fe.mul4``, ``mul_lazy``,
the one-round adds); the two interpret-mode ladder parities cover them only
as a whole.  Here each runs alone — plain ``jnp`` on the CPU, 8 lanes — over
the operand shapes a commit can put on a lane (the identity, P + (-P),
P + P through the add formulas, a small-order point the Go accept set admits
as A) and with an op's output fed back as its input, which is how the
ladder drives the operand classes ``derive_carry_plan`` certifies.  The
reference is ``crypto/ed25519`` and ``crypto/secp256k1``; results are
compared projectively, X and Y both.

The last class checks the host fill that feeds the ed25519 program
(``pack_variable_words``, ``_sig_words``) against padded SHA-512 input built
byte by byte in the test.
"""

import zlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.crypto import ed25519 as ed  # noqa: E402
from tendermint_tpu.crypto import secp256k1 as sk  # noqa: E402
from tendermint_tpu.ops import ed25519_pallas as ep  # noqa: E402
from tendermint_tpu.ops import fe_common as fc  # noqa: E402
from tendermint_tpu.ops import secp256k1_pallas as sp  # noqa: E402

NLIMB, BITS, MASK = fc.NLIMB, fc.BITS, fc.MASK
LANES = 8


def to_limbs(x: int) -> np.ndarray:
    return np.array([(x >> (BITS * i)) & MASK for i in range(NLIMB)],
                    dtype=np.uint32)


def from_limbs(col) -> int:
    return sum(int(v) << (BITS * i) for i, v in enumerate(np.asarray(col)))


def _rows(ints):
    """One coordinate of every lane -> the kernels' (NLIMB, B) layout."""
    return jnp.asarray(np.stack([to_limbs(v) for v in ints], axis=-1))


def _ints(rows):
    rows = np.asarray(rows)
    return [from_limbs(rows[:, k]) for k in range(rows.shape[1])]


def _col(limbs):
    return jnp.asarray(np.asarray(limbs, np.uint32).reshape(NLIMB, 1))


def _within(rows, bound) -> bool:
    """Every lane's limbs at or under a plan's per-row class bound."""
    return bool(
        (np.asarray(rows) <= np.asarray(bound, np.uint64)[:, None]).all())


# ---------------------------------------------------------------------------
# ed25519
# ---------------------------------------------------------------------------

EP = ed.P
ED_FE = ep._get_fe("lazy")
ED_PLAN = ED_FE.plan
D2 = _col(ep._D2_LIMBS)
ED_KSUB = _col(ep._K_SUB)
ED_KD = _col(ED_PLAN.kd)
# every lane at class C's per-row maxima
ED_CMAX = jnp.asarray(
    np.tile(np.asarray(ED_PLAN.c, np.uint32)[:, None], (1, LANES)))


def _ed_scaled(pt, z):
    """The same point with every extended coordinate scaled by z."""
    return tuple(c * z % EP for c in pt)


def _ed_neg(pt):
    X, Y, Z, T = pt
    return (-X % EP, Y, Z, -T % EP)


def _ed_affine(pt):
    X, Y, Z, _ = pt
    zi = pow(Z, EP - 2, EP)
    return X * zi % EP, Y * zi % EP


def _ed_generic(rng, n=LANES):
    """n points of the prime-order subgroup, Z != 1."""
    out = []
    for _ in range(n):
        k = int.from_bytes(rng.bytes(32), "little") % (ed.L - 1) + 1
        z = int.from_bytes(rng.bytes(31), "little") + 2
        out.append(_ed_scaled(ed.pt_scalar_mult(ed.B_EXT, k), z))
    return out


def _ed_small_order():
    """The seven points of the 8-torsion subgroup but the identity, and the
    order-8 generator again (8 lanes).  [L]Q lands there for any curve
    point Q; Go's verify admits such A."""
    y = 2
    while True:
        xy = ed._decompress_xy(y.to_bytes(32, "little"))
        y += 1
        if xy is None:
            continue
        t8 = ed.pt_scalar_mult(ed._to_extended(xy), ed.L)
        if not ed._is_identity(ed.pt_scalar_mult(t8, 4)):
            break  # order exactly 8
    assert ed._is_identity(ed.pt_scalar_mult(t8, 8))
    pts = [ed._to_extended(_ed_affine(ed.pt_scalar_mult(t8, j)))
           for j in range(1, 8)]
    return pts + [pts[0]]


def _ed_pack(points):
    return tuple(_rows([p[c] for p in points]) for c in range(4))


def _ed_niels(points):
    """Affine niels operands (y+x, y-x, 2dxy) as pt_madd takes them; the
    identity gives (1, 1, 0), the constant table's digit 0."""
    ypx, ymx, t2d = [], [], []
    for p in points:
        x, y = _ed_affine(p)
        ypx.append((y + x) % EP)
        ymx.append((y - x) % EP)
        t2d.append(2 * ed.D * x * y % EP)
    return _rows(ypx), _rows(ymx), _rows(t2d)


def _ed_check(got, want, label):
    """Projective equality of X and Y, the extended invariant T·Z = X·Y,
    and the class-C certificate of every output limb."""
    coords = [np.asarray(c) for c in got]
    X, Y, Z, T = (_ints(c) for c in coords)
    for k, w in enumerate(want):
        Xw, Yw, Zw, _ = w
        assert Z[k] % EP != 0, (label, k)
        assert X[k] * Zw % EP == Xw * Z[k] % EP, (label, "X", k)
        assert Y[k] * Zw % EP == Yw * Z[k] % EP, (label, "Y", k)
        assert T[k] * Z[k] % EP == X[k] * Y[k] % EP, (label, "T", k)
    assert all(_within(c, ED_PLAN.c) for c in coords), label


# each takes the packed accumulator and the host's points to add to it


def _op_add(acc, q):
    return ep.pt_add(acc, _ed_pack(q), D2, ED_KSUB, ED_FE, ED_KD)


def _op_madd(acc, q):
    return ep.pt_madd(acc, *_ed_niels(q), ED_KSUB, ED_FE, ED_KD)


def _op_add_cached(acc, q):
    cached = ep.pt_to_cached(_ed_pack(q), D2, ED_KSUB, ED_FE)
    return ep.pt_add_cached(acc, cached, ED_KSUB, ED_KD, ED_FE)


ED_ADDS = {"pt_add": _op_add, "pt_madd": _op_madd,
           "pt_add_cached": _op_add_cached}


def _ed_operands(scenario, rng):
    p = _ed_generic(rng)
    if scenario == "generic":
        return p, _ed_generic(rng)
    if scenario == "identity":
        # the identity on either side, Z scaled or not
        q = _ed_generic(rng)
        ident = [_ed_scaled(ed.IDENT, z) for z in (1, 1, 7, EP - 1)]
        return ident + p[4:], q[:4] + ident
    if scenario == "inverse":
        return p, [_ed_scaled(_ed_neg(x), 3 + i) for i, x in enumerate(p)]
    if scenario == "same":
        return p, [_ed_scaled(x, 5 + i) for i, x in enumerate(p)]
    if scenario == "small_order":
        s = _ed_small_order()
        return s[:4] + p[4:], p[:4] + s[4:]
    raise ValueError(scenario)


@pytest.mark.parametrize("op", list(ED_ADDS))
class TestEd25519Adds:
    @pytest.mark.parametrize(
        "scenario", ["generic", "identity", "inverse", "same", "small_order"])
    def test_against_host(self, op, scenario):
        rng = np.random.default_rng(zlib.crc32(f"{op}/{scenario}".encode()))
        p, q = _ed_operands(scenario, rng)
        want = [ed.pt_add(a, b) for a, b in zip(p, q)]
        if scenario == "inverse":
            assert all(ed._is_identity(w) for w in want)
        _ed_check(ED_ADDS[op](_ed_pack(p), q), want, (op, scenario))

    def test_output_fed_back_three_times(self, op):
        """acc <- acc + Q three times: from the second round on the
        accumulator is an op's own class-C output, as in the ladder."""
        rng = np.random.default_rng(101)
        acc_host = _ed_generic(rng)
        acc = _ed_pack(acc_host)
        for _ in range(3):
            q = _ed_generic(rng)
            acc = ED_ADDS[op](acc, q)
            acc_host = [ed.pt_add(a, b) for a, b in zip(acc_host, q)]
            _ed_check(acc, acc_host, (op, "fed back"))


class TestEd25519Double:
    @pytest.mark.parametrize("scenario",
                             ["generic", "identity", "small_order"])
    def test_against_host(self, scenario):
        rng = np.random.default_rng(211)
        if scenario == "generic":
            p = _ed_generic(rng)
        elif scenario == "identity":
            p = [_ed_scaled(ed.IDENT, z) for z in range(1, LANES + 1)]
        else:
            p = _ed_small_order()
        got = ep.pt_double(_ed_pack(p), ED_KSUB, ED_FE, ED_KD)
        _ed_check(got, [ed.pt_double(x) for x in p], ("pt_double", scenario))

    def test_output_fed_back_three_times(self):
        rng = np.random.default_rng(223)
        host = _ed_generic(rng)
        acc = _ed_pack(host)
        for _ in range(3):
            acc = ep.pt_double(acc, ED_KSUB, ED_FE, ED_KD)
            host = [ed.pt_double(x) for x in host]
            _ed_check(acc, host, ("pt_double", "fed back"))

    def test_to_cached_keeps_the_identity(self):
        """pt_to_cached's "identity-safe" claim: (0, 1, 1, 0) becomes the
        cached-niels (1, 1, 1, 0), and adding it changes nothing."""
        ident = _ed_pack([ed.IDENT] * LANES)
        c = ep.pt_to_cached(ident, D2, ED_KSUB, ED_FE)
        assert [v % EP for v in _ints(c[0])] == [1] * LANES
        assert [v % EP for v in _ints(c[1])] == [1] * LANES
        assert [v % EP for v in _ints(c[2])] == [1] * LANES
        assert [v % EP for v in _ints(c[3])] == [0] * LANES


class TestMul4:
    """``fe.mul4`` — the stacked multiply the ladder spends its time in —
    against bignum, bit-identical to four ``fe.mul`` calls, outputs inside
    class C."""

    def _check(self, pairs):
        got = ED_FE.mul4(pairs)
        for (a, b), g in zip(pairs, got):
            g = np.asarray(g)
            np.testing.assert_array_equal(g, np.asarray(ED_FE.mul(a, b)))
            va, vb = _ints(a), _ints(b)
            for k, v in enumerate(_ints(g)):
                assert v % EP == va[k] * vb[k] % EP, k
            assert _within(g, ED_PLAN.c)

    def test_all_pairs_at_the_closed_set_maxima(self):
        self._check(((ED_CMAX, ED_CMAX),) * 4)

    def _efgh(self, x, y, z, t):
        """E, F, G, H as the point ops form them: class-D products out of
        mul_lazy, folded by one-round sub (against kd) and add."""
        A = ED_FE.mul_lazy(ED_FE.sub(y, x, ED_KSUB), ED_FE.sub(y, x, ED_KSUB))
        B = ED_FE.mul_lazy(ED_FE.add_raw(y, x), ED_FE.add(y, x))
        C = ED_FE.mul_lazy(ED_FE.mul(t, D2), t)
        Dv = ED_FE.mul_lazy(ED_FE.add_raw(z, z), z)
        assert all(_within(d, ED_PLAN.d) for d in (A, B, C, Dv))
        return (ED_FE.sub(B, A, ED_KD), ED_FE.sub(Dv, C, ED_KD),
                ED_FE.add(Dv, C), ED_FE.add(B, A))

    def test_operands_out_of_mul_lazy(self):
        E, F, G, H = self._efgh(ED_CMAX, ED_CMAX, ED_CMAX, ED_CMAX)
        self._check(((E, F), (G, H), (F, G), (E, H)))

    def test_mixed(self):
        rng = np.random.default_rng(307)
        canon = _rows([0, 1, EP - 1, EP, EP + 1, (1 << 255) - 1,
                       int.from_bytes(rng.bytes(31), "little"),
                       int.from_bytes(rng.bytes(31), "little")])
        ones = jnp.full((NLIMB, LANES), MASK, jnp.uint32)
        E, F, _G, H = self._efgh(canon, ones, ED_CMAX, canon)
        self._check(((canon, ones), (ED_CMAX, E), (F, canon), (H, ones)))


# ---------------------------------------------------------------------------
# secp256k1
# ---------------------------------------------------------------------------

SP = sk.P
SP_FE = sp._get_fe("lazy")
SP_PLAN = SP_FE.plan
SP_KSUB = _col(sp._K_SUB)
SP_KD = _col(SP_PLAN.kd)
INF = (0, 1, 0)  # the kernel's identity (0 : 1 : 0)


def _sp_generic(rng, n=LANES):
    """n affine points [k]G as homogeneous (X : Y : Z), Z != 1."""
    out = []
    for _ in range(n):
        k = int.from_bytes(rng.bytes(32), "big") % (sk.N - 1) + 1
        x, y = sk._to_affine(sk._jmul(sk._G, k))
        z = int.from_bytes(rng.bytes(31), "big") + 2
        out.append((x * z % SP, y * z % SP, z))
    return out


def _sp_jacobian(pt):
    """Homogeneous (X : Y : Z) -> the host's Jacobian form (None = O)."""
    X, Y, Z = pt
    if Z % SP == 0:
        return None
    zi = pow(Z, SP - 2, SP)
    return (X * zi % SP, Y * zi % SP, 1)


def _sp_pack(points):
    return tuple(_rows([p[c] for p in points]) for c in range(3))


def _sp_add(p, q):
    return sp.pt_add(p, q, SP_KSUB, SP_FE, SP_KD)


def _sp_check(got, want, label):
    coords = [np.asarray(c) for c in got]
    X, Y, Z = (_ints(c) for c in coords)
    for k, w in enumerate(want):
        if w is None:  # infinity: Z = 0, and Y != 0 keeps (0 : Y : 0) a point
            assert Z[k] % SP == 0 and X[k] % SP == 0, (label, "O", k)
            assert Y[k] % SP != 0, (label, "O", k)
            continue
        xw, yw = sk._to_affine(w)
        assert Z[k] % SP != 0, (label, k)
        assert X[k] % SP == xw * Z[k] % SP, (label, "X", k)
        assert Y[k] % SP == yw * Z[k] % SP, (label, "Y", k)
    assert all(_within(c, SP_PLAN.c) for c in coords), label


class TestSecp256k1LazyAdd:
    """``_pt_add_lazy``, the complete RCB16 addition the chip traces."""

    @pytest.mark.parametrize(
        "scenario", ["generic", "same", "inverse", "infinity_operand",
                     "both_infinity"])
    def test_against_host(self, scenario):
        rng = np.random.default_rng(401)
        p = _sp_generic(rng)
        if scenario == "generic":
            q = _sp_generic(rng)
        elif scenario == "same":
            q = [(x * 3 % SP, y * 3 % SP, z * 3 % SP) for x, y, z in p]
        elif scenario == "inverse":
            q = [(x, -y % SP, z) for x, y, z in p]
        elif scenario == "infinity_operand":
            q = p[:4] + [INF] * 4
            p = [INF] * 4 + p[4:]
        else:
            p = q = [INF] * LANES
        want = [sk._jadd(_sp_jacobian(a), _sp_jacobian(b))
                for a, b in zip(p, q)]
        if scenario in ("inverse", "both_infinity"):
            assert want == [None] * LANES
        _sp_check(_sp_add(_sp_pack(p), _sp_pack(q)), want, scenario)

    def test_output_fed_back_three_times(self):
        rng = np.random.default_rng(409)
        host = [_sp_jacobian(x) for x in _sp_generic(rng)]
        acc = _sp_pack([(x, y, 1) for x, y, _ in host])
        for _ in range(3):
            q = _sp_generic(rng)
            acc = _sp_add(acc, _sp_pack(q))
            host = [sk._jadd(a, _sp_jacobian(b)) for a, b in zip(host, q)]
            _sp_check(acc, host, "fed back")


# ---------------------------------------------------------------------------
# The host fill of the ed25519 program
# ---------------------------------------------------------------------------


def _sha512_words(r: bytes, a: bytes, m: bytes) -> np.ndarray:
    """Padded SHA-512 input of R || A || M as big-endian 32-bit words
    (FIPS 180-4 5.1.2), byte by byte."""
    data = bytearray(r + a + m)
    bits = len(data) * 8
    data.append(0x80)
    while len(data) % 128 != 112:
        data.append(0)
    data += bits.to_bytes(16, "big")
    return np.array([int.from_bytes(data[i:i + 4], "big")
                     for i in range(0, len(data), 4)], dtype=np.uint32)


def _bswap(x):
    x = x.astype(np.uint32)
    return ((x >> 24) | ((x >> 8) & 0xFF00)
            | ((x << 8) & 0xFF0000) | (x << 24)).astype(np.uint32)


def _assembled(pubs, sigs, valid, tmpl, vrows, vwords, b):
    """The message words ``_device_verify_packed`` assembles on the device
    from what the host packed, redone in numpy: (b, rows)."""
    sig_words = ep._pad_rows(ep._sig_words(sigs, valid), b)
    pub_words = ep._pad_rows(
        np.ascontiguousarray(pubs).view("<u4").astype(np.uint32), b)
    mw = np.tile(tmpl[None, :], (b, 1))
    mw[:, 0:8] = _bswap(sig_words[:, 0:8])
    mw[:, 8:16] = _bswap(pub_words)
    mw[:, vrows] = vwords
    return mw, sig_words


class TestHostFill:
    def _batch(self, n, msgs, seed=503):
        rng = np.random.default_rng(seed)
        pubs = rng.integers(0, 256, (n, 32), dtype=np.uint8)
        sigs = rng.integers(0, 256, (n, 64), dtype=np.uint8)
        return pubs, list(msgs), sigs

    def _check(self, pubs, msgs, sigs, b, k_expected=None):
        n, ln = len(msgs), len(msgs[0])
        valid = np.ones((n,), bool)
        valid[n // 2] = False
        tmpl, vrows, vwords = ep.pack_variable_words(pubs, msgs, sigs, ln, b)
        assert tmpl.dtype == vwords.dtype == np.uint32
        assert vrows.dtype == np.int32 and (vrows >= 16).all()
        k = vrows.size
        assert k & (k - 1) == 0 and vwords.shape == (b, k)
        if k_expected is not None:
            assert len(set(vrows.tolist())) == k_expected
        mw, sig_words = _assembled(pubs, sigs, valid, tmpl, vrows, vwords, b)
        for i in range(n):
            want = _sha512_words(sigs[i, :32].tobytes(), pubs[i].tobytes(),
                                 msgs[i])
            if valid[i]:
                np.testing.assert_array_equal(mw[i], want, err_msg=f"lane {i}")
                assert sig_words[i].tobytes() == sigs[i].tobytes()
            else:  # an invalid lane's signature is zeroed, message intact
                assert not sig_words[i].any()
                np.testing.assert_array_equal(mw[i, 16:], want[16:])
        # lanes past n: no signature, no key, and a well-formed padded block
        # (lane 0's words where no lane differs, a zero message's elsewhere)
        assert not sig_words[n:].any()
        tail = _sha512_words(b"\0" * 32, b"\0" * 32, msgs[0])
        zero = _sha512_words(b"\0" * 32, b"\0" * 32, b"\0" * ln)
        tail[vrows] = zero[vrows]
        for i in range(n, b):
            np.testing.assert_array_equal(mw[i], tail)

    def test_one_template_for_all_lanes(self):
        msg = bytes(range(110))
        pubs, msgs, sigs = self._batch(8, [msg] * 8)
        self._check(pubs, msgs, sigs, 8, k_expected=1)

    def test_messages_differing_in_a_few_bytes(self):
        # a commit's sign-bytes: the timestamp's bytes differ by validator
        base = bytearray(range(110))
        msgs = []
        for i in range(8):
            m = bytearray(base)
            m[93:101] = (1_700_000_000_000 + 977 * i).to_bytes(8, "little")
            m[17] ^= i & 1
            msgs.append(bytes(m))
        pubs, _, sigs = self._batch(8, msgs)
        self._check(pubs, msgs, sigs, 8)

    def test_a_message_crossing_a_sha512_block(self):
        # 64 + 60 bytes pads to one 128-byte block with no room for the
        # length; 64 + 200 spans three blocks
        rng = np.random.default_rng(509)
        for ln in (60, 200):
            msgs = [rng.bytes(ln) for _ in range(8)]
            pubs, _, sigs = self._batch(8, msgs)
            self._check(pubs, msgs, sigs, 8)

    def test_fewer_lanes_than_the_bucket(self):
        msgs = [bytes([7]) * 109 + bytes([i]) for i in range(5)]
        pubs, _, sigs = self._batch(5, msgs)
        assert ep._bucket(5, 8) == 8
        self._check(pubs, msgs, sigs, 8, k_expected=1)
        # and at the chip's bucket for the same lanes
        assert ep._bucket(5) == ep.LANES
        self._check(pubs, msgs, sigs, ep.LANES)
