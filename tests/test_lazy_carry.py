"""Certification and exactness tests for the lazy (deferred) carry path.

Three layers, mirroring how the feature is built:

  * plan certification — fe_common.derive_carry_plan's closed-set fixed
    point, the KD/KSUB wide zeros, and the derived-vs-pinned eager round
    counts (the import-time asserts, re-run here so a failure points at
    the claim, not at an ImportError);
  * op exactness — every lazy op on both curves against Python bignum,
    driven at the certified class bounds
    (p±1, all-MASK, the class-C/D maxima rows) where overflow would hide;
  * kernel parity — the XLA verify kernels must return bit-identical
    verdicts under eager and lazy schedules, and the Pallas ladder's lazy
    output must be projectively equal to the eager one.

Runs eagerly under JAX_PLATFORMS=cpu — tier-1 except where marked slow.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.ops import fe_common as fc  # noqa: E402
from tendermint_tpu.ops import ed25519_verify as ed_xla  # noqa: E402
from tendermint_tpu.ops import secp256k1_verify as sp_xla  # noqa: E402

NLIMB, BITS, MASK = fc.NLIMB, fc.BITS, fc.MASK
U32 = 1 << 32

CURVE_P = {"ed25519": fc.ED_P, "secp256k1": fc.SECP_P}


def to_limbs(x: int) -> np.ndarray:
    return np.array([(x >> (BITS * i)) & MASK for i in range(NLIMB)],
                    dtype=np.uint32)


def from_limbs(l) -> int:
    return sum(int(v) << (BITS * i) for i, v in enumerate(np.asarray(l)))


def _lanes(cols):
    return jnp.asarray(np.stack(cols, axis=-1).astype(np.uint32))


def _limb_col(limbs):
    return jnp.asarray(np.asarray(limbs, np.uint32).reshape(NLIMB, 1))


@pytest.mark.parametrize("curve", list(CURVE_P))
class TestCarryPlan:
    def test_plan_certified(self, curve):
        plan = fc.derive_carry_plan(curve)
        p = CURVE_P[curve]
        assert plan.peak < U32
        # operand classes are a fixed point ordered C <= D, and both wide
        # zeros are actual multiples of p that dominate their class
        assert all(a <= b for a, b in zip(plan.c, plan.d))
        assert from_limbs(plan.kd) % p == 0
        assert from_limbs(plan.ksub) % p == 0
        assert all(k >= d for k, d in zip(plan.kd, plan.d))
        # single-round ops really do one wide round
        assert plan.mull_wide == 1 and plan.norm_wide == 1
        assert 1 <= plan.mulf_wide <= 4

    def test_closure_one_more_step(self, curve):
        # one more application of every chain op stays inside the classes
        plan = fc.derive_carry_plan(curve)
        C, D, KD = plan.c, plan.d, list(plan.kd)
        if curve == "ed25519":
            bm, _ = fc.bound_ed_mul_lazy(C, C, wide=plan.mulf_wide)
            bn, _ = fc.bound_ed_norm1([x + y for x, y in zip(C, C)])
            bd, _ = fc.bound_ed_mul_lazy(C, C, wide=1)
            bs, _ = fc.bound_ed_norm1([d + k for d, k in zip(D, KD)])
        else:
            bm, _ = fc.bound_secp_mul_lazy(C, C, wide=plan.mulf_wide)
            bn, _ = fc.bound_secp_norm1([x + y for x, y in zip(C, C)])
            bd, _ = fc.bound_secp_mul_lazy(C, C, wide=1, fix=(0,))
            bs, _ = fc.bound_secp_norm1([d + k for d, k in zip(D, KD)])
        assert all(x <= y for x, y in zip(bm, C))
        assert all(x <= y for x, y in zip(bn, C))
        assert all(x <= y for x, y in zip(bs, C))
        assert all(x <= y for x, y in zip(bd, D))


class TestDerivedConstants:
    def test_eager_rounds_derived_not_pinned(self):
        # satellite 1: the eager round constants are re-derived at import
        # and asserted; re-check the equalities here explicitly
        ed = fc.derive_eager_rounds("ed25519")
        assert ed["mul_tail"] == fc.ED_MUL_TAIL_ROUNDS == 2
        assert ed["add"] == ed["sub"] == fc.ED_ADD_ROUNDS == 1
        sp = fc.derive_eager_rounds("secp256k1")
        assert sp["mul_tail"] == fc.SECP_MUL_TAIL_ROUNDS == 3
        assert sp["add"] == sp["sub"] == fc.SECP_ADD_ROUNDS == 3
        assert sp["mul_small"] == fc.SECP_MUL_SMALL_ROUNDS == 3

    def test_ksub_matches_xla_kernels(self):
        # the wide zeros the lazy subs share with the verify modules
        np.testing.assert_array_equal(
            np.asarray(fc.ED_KSUB_LIMBS, np.uint32), np.asarray(ed_xla._K_SUB))
        np.testing.assert_array_equal(
            np.asarray(fc.SECP_KSUB_LIMBS, np.uint32),
            np.asarray(sp_xla._K_SUB))

    def test_normalize_carry_mode(self):
        assert fc.normalize_carry_mode(None) == "lazy"
        assert fc.normalize_carry_mode("auto") == "lazy"
        assert fc.normalize_carry_mode(" EAGER ") == "eager"
        with pytest.raises(ValueError):
            fc.normalize_carry_mode("sometimes")


@pytest.mark.parametrize("curve", list(CURVE_P))
class TestLazyOpsVsBignum:
    """Row-layout lazy ops vs Python bignum at the certified bounds."""

    def _operands(self, curve, plan, rng):
        p = CURVE_P[curve]
        vals = [0, 1, p - 1, p, p + 1]
        vals += [int(rng.integers(0, 1 << 62)) ** 4 % p for _ in range(3)]
        cols = [to_limbs(v) for v in vals]
        cols.append(np.full(NLIMB, MASK, np.uint32))
        cols.append(np.asarray(plan.c, np.uint32))  # class-C maxima
        return cols

    def test_mul_f_and_l(self, curve):
        p = CURVE_P[curve]
        plan = fc.derive_carry_plan(curve)
        fe = fc.make_fe(curve, carry_mode="lazy")
        assert fe.carry_mode == "lazy"
        rng = np.random.default_rng(31)
        cols = self._operands(curve, plan, rng)
        a, b = _lanes(cols), _lanes(cols[::-1])
        mf = np.asarray(fe.mul(a, b))
        ml = np.asarray(fe.mul_lazy(a, b))
        sq = np.asarray(fe.sq(a))
        for k in range(a.shape[1]):
            va, vb = from_limbs(cols[k]), from_limbs(cols[::-1][k])
            assert from_limbs(mf[:, k]) % p == va * vb % p, ("mulF", k)
            assert from_limbs(ml[:, k]) % p == va * vb % p, ("mulL", k)
            assert from_limbs(sq[:, k]) % p == va * va % p, ("sq", k)
            # mulF output obeys its class-C certificate exactly
            assert all(int(v) <= c for v, c in zip(mf[:, k], plan.c))
            assert all(int(v) <= d for v, d in zip(ml[:, k], plan.d))

    def test_add_sub_norm_chain(self, curve):
        p = CURVE_P[curve]
        plan = fc.derive_carry_plan(curve)
        fe = fc.make_fe(curve, carry_mode="lazy")
        rng = np.random.default_rng(37)
        cols = self._operands(curve, plan, rng)
        a, b = _lanes(cols), _lanes(cols[::-1])
        kd = _limb_col(plan.kd)
        ks = _limb_col(plan.ksub)
        d = fe.mul_lazy(a, b)  # class D
        dv = [from_limbs(np.asarray(d)[:, k]) for k in range(a.shape[1])]
        got_add = np.asarray(fe.add(d, d))
        got_sub = np.asarray(fe.sub(a, d, kd))
        got_subc = np.asarray(fe.sub(a, b, ks))
        got_raw = np.asarray(fe.add(fe.add_raw(d, d), a))
        for k in range(a.shape[1]):
            va = from_limbs(cols[k])
            vb = from_limbs(cols[::-1][k])
            assert from_limbs(got_add[:, k]) % p == 2 * dv[k] % p
            assert from_limbs(got_sub[:, k]) % p == (va - dv[k]) % p
            assert from_limbs(got_subc[:, k]) % p == (va - vb) % p
            assert from_limbs(got_raw[:, k]) % p == (2 * dv[k] + va) % p
            assert all(int(v) <= c for v, c in zip(got_add[:, k], plan.c))

    def test_mul_small_and_inv(self, curve):
        p = CURVE_P[curve]
        plan = fc.derive_carry_plan(curve)
        fe = fc.make_fe(curve, carry_mode="lazy")
        rng = np.random.default_rng(41)
        vals = [1, 2, p - 1, int(rng.integers(2, 1 << 61)) ** 4 % p]
        cols = [to_limbs(v) for v in vals]
        a = _lanes(cols)
        if curve == "secp256k1":
            ms = np.asarray(fe.mul_small(jnp.asarray(_lanes(
                [np.asarray(plan.c, np.uint32)] * 2)), fc.B3_SMALL))
            cval = from_limbs(plan.c)
            assert from_limbs(ms[:, 0]) % p == cval * fc.B3_SMALL % p
        inv = fe.inv(a)
        got = np.asarray(fe.mul(a, inv))
        for k, v in enumerate(vals):
            assert from_limbs(got[:, k]) % p == 1


class TestXlaEagerLazyParity:
    """Same verdicts, bit for bit, from the eager and lazy XLA kernels."""

    def test_ed25519(self):
        from tendermint_tpu.crypto import ed25519 as ed

        rng = np.random.default_rng(43)
        n = 5
        pubs = np.zeros((n, 32), np.uint8)
        sigs = np.zeros((n, 64), np.uint8)
        msgs = []
        for i in range(n):
            sk = ed.gen_privkey(rng.bytes(32))
            m = rng.bytes(40)
            msgs.append(m)
            pubs[i] = np.frombuffer(sk[32:], np.uint8)
            sigs[i] = np.frombuffer(ed.sign(sk, m), np.uint8)
        sigs[3, 5] ^= 1  # one corrupted signature must stay rejected
        eager = ed_xla.verify_batch(pubs, msgs, sigs, carry_mode="eager")
        lazy = ed_xla.verify_batch(pubs, msgs, sigs, carry_mode="lazy")
        assert eager.tolist() == [True, True, True, False, True]
        np.testing.assert_array_equal(lazy, eager)

    def test_secp256k1(self):
        from tendermint_tpu.crypto import secp256k1 as s

        rng = np.random.default_rng(47)
        n = 4
        pubs, digs, sigs = [], [], []
        for i in range(n):
            priv = s.gen_privkey(rng.bytes(32))
            pubs.append(s.pubkey_compressed(priv))
            d = hashlib.sha256(rng.bytes(30)).digest()
            digs.append(d)
            sigs.append(s.sign(priv, d))
        digs[2] = hashlib.sha256(b"tampered").digest()
        eager = sp_xla.verify_batch(pubs, digs, sigs, carry_mode="eager")
        lazy = sp_xla.verify_batch(pubs, digs, sigs, carry_mode="lazy")
        assert eager.tolist() == [True, True, False, True]
        np.testing.assert_array_equal(lazy, eager)


class TestPallasLadderParity:
    """Pallas ladder_math: lazy output projectively equals eager."""

    def _py_loop(self, lo, hi, body, init):
        acc = init
        for t in range(lo, hi):
            acc = body(t, acc)
        return acc

    def test_ed25519_ladder_congruent(self):
        from tendermint_tpu.ops import ed25519_pallas as ep
        from tendermint_tpu.crypto import ed25519 as ed

        n, nw = 8, 2
        rng = np.random.default_rng(53)
        pubs = np.zeros((n, 32), np.uint8)
        for i in range(n):
            pubs[i] = np.frombuffer(ed.gen_privkey(rng.bytes(32))[32:],
                                    np.uint8)
        neg_ax, ay, valid = ep._decompress_valset(pubs)
        assert valid.all()
        digs = np.zeros((nw, n), np.uint32)
        digh = np.zeros((nw, n), np.uint32)
        for i in range(n):
            s_small = 0 if i == 0 else int(rng.integers(1, 256))
            h_small = 0 if i == 1 else int(rng.integers(1, 256))
            digs[:, i] = [(s_small >> (4 * (nw - 1 - t))) & 0xF
                          for t in range(nw)]
            digh[:, i] = [(h_small >> (4 * (nw - 1 - t))) & 0xF
                          for t in range(nw)]
        consts = jnp.asarray(ep._CONSTS)
        dj, hj = jnp.asarray(digs), jnp.asarray(digh)
        out = {}
        for mode in ("eager", "lazy"):
            X, Y, Z, _T = ep.ladder_math(
                consts, jnp.asarray(neg_ax.T.copy()),
                jnp.asarray(ay.T.copy()),
                lambda t: dj[t:t + 1, :], lambda t: hj[t:t + 1, :],
                nwin=nw, loop=self._py_loop, carry_mode=mode)
            out[mode] = [np.asarray(v) for v in (X, Y, Z)]
        p = fc.ED_P
        plan = fc.derive_carry_plan("ed25519")
        for i in range(n):
            Xe, Ye, Ze = (from_limbs(out["eager"][k][:, i]) for k in range(3))
            Xl, Yl, Zl = (from_limbs(out["lazy"][k][:, i]) for k in range(3))
            assert Xe * Zl % p == Xl * Ze % p, i
            assert Ye * Zl % p == Yl * Ze % p, i
            # lazy coordinates obey the class-C certificate
            for k in range(3):
                assert all(int(v) <= c for v, c
                           in zip(out["lazy"][k][:, i], plan.c))

    def test_secp256k1_ladder_congruent(self):
        from tendermint_tpu.ops import secp256k1_pallas as sp
        from tendermint_tpu.crypto import secp256k1 as s

        n, nw = 8, 2
        rng = np.random.default_rng(59)
        qx = np.zeros((sp.NLIMB, n), np.uint32)
        qy = np.zeros((sp.NLIMB, n), np.uint32)
        d1 = np.zeros((nw, n), np.uint32)
        d2 = np.zeros((nw, n), np.uint32)
        for i in range(n):
            k = int.from_bytes(rng.bytes(32), "big") % (s.N - 1) + 1
            x, y = s._to_affine(s._jmul(s._G, k))
            qx[:, i] = sp.int_to_limbs(x)
            qy[:, i] = sp.int_to_limbs(y)
            u1 = 0 if i == 0 else int(rng.integers(0, 256))
            u2 = 0 if i == 1 else int(rng.integers(0, 256))
            d1[:, i] = [(u1 >> (4 * (nw - 1 - t))) & 0xF for t in range(nw)]
            d2[:, i] = [(u2 >> (4 * (nw - 1 - t))) & 0xF for t in range(nw)]
        consts = jnp.asarray(sp._CONSTS)
        dj1, dj2 = jnp.asarray(d1), jnp.asarray(d2)
        out = {}
        for mode in ("eager", "lazy"):
            X, Y, Z = sp.ladder_math(
                consts, jnp.asarray(qx), jnp.asarray(qy),
                lambda t: dj1[t:t + 1, :], lambda t: dj2[t:t + 1, :],
                nwin=nw, loop=self._py_loop, carry_mode=mode)
            out[mode] = [np.asarray(v) for v in (X, Y, Z)]
        p = fc.SECP_P
        for i in range(n):
            Xe, Ye, Ze = (from_limbs(out["eager"][k][:, i]) for k in range(3))
            Xl, Yl, Zl = (from_limbs(out["lazy"][k][:, i]) for k in range(3))
            assert Xe * Zl % p == Xl * Ze % p, i
            assert Ye * Zl % p == Yl * Ze % p, i


class TestCostModel:
    """The op-count model that PERF.md reports: the lazy schedule removes
    >= 30% of carry-round row-slots per signature (the ISSUE's gate)."""

    @pytest.mark.parametrize("curve,floor", [("ed25519", 0.30),
                                             ("secp256k1", 0.30)])
    def test_carry_round_drop(self, curve, floor):
        eager = fc.carry_cost_model(curve, "eager")
        lazy = fc.carry_cost_model(curve, "lazy")
        assert eager["unit"] == lazy["unit"] == "row-slots"
        drop = 1 - lazy["per_signature"] / eager["per_signature"]
        assert drop >= floor, (curve, drop)

    def test_model_reports_all_pools(self):
        for curve in CURVE_P:
            for mode in ("eager", "lazy"):
                m = fc.carry_cost_model(curve, mode)
                assert m["per_signature"] > 0
                assert m["per_window"] > 0
                assert set(m["per_op"]) >= {"mul"} or "mulF" in m["per_op"]
