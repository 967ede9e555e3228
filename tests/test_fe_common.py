"""Property tests for the shared field arithmetic in ops/fe_common.py.

Every fe op (mul / sq / add / sub / carry / inv) for both curves is
checked against a Python-bignum reference, over random limb vectors plus the adversarial patterns the
ISSUE calls out: all-ones 13-bit limbs, p-1, p, p+1, and inputs held at
the closed-set carried maxima (the largest limbs any op chain can
produce).  Runs entirely eagerly under JAX_PLATFORMS=cpu.

Two tiers: the default run keeps a fast core (edge-case lanes plus one
random lane per pattern) under ~30s; the exhaustive sweeps — full random
lane counts — carry `@pytest.mark.slow` and run with `-m slow`.

The bounds section replaces the hand-stated overflow analysis that used
to live in the ed25519_pallas header comment: fe_common.bound_*
re-derives, mechanically, that the op mix is closed and that no
intermediate reaches 2^32.  If a future edit to the carry/fold chains breaks either claim,
these tests fail instead of a comment going stale.
"""

from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.ops import fe_common as fc  # noqa: E402
from tendermint_tpu.ops import ed25519_verify as ed_xla  # noqa: E402
from tendermint_tpu.ops import secp256k1_verify as sp_xla  # noqa: E402

NLIMB, BITS, MASK = fc.NLIMB, fc.BITS, fc.MASK

CURVES = {
    "ed25519": {"p": fc.ED_P, "ksub": np.asarray(ed_xla._K_SUB)},
    "secp256k1": {"p": fc.SECP_P, "ksub": np.asarray(sp_xla._K_SUB)},
}


def to_limbs(x: int) -> np.ndarray:
    out = np.zeros(NLIMB, dtype=np.uint32)
    for i in range(NLIMB):
        out[i] = (x >> (BITS * i)) & MASK
    return out


def from_limbs(l) -> int:
    return sum(int(v) << (BITS * i) for i, v in enumerate(np.asarray(l)))


def _lanes(cols):
    """Stack 1-D limb vectors into the kernels' (NLIMB, B) row layout."""
    return jnp.asarray(np.stack(cols, axis=-1).astype(np.uint32))


def _ksub_col(curve):
    return jnp.asarray(
        CURVES[curve]["ksub"].reshape(NLIMB, 1).astype(np.uint32)
    )


# random lanes per adversarial pattern: the fast tier keeps one (edge
# cases dominate the lane mix), the slow sweep restores the full count
FAST_RANDOM = 1
SLOW_RANDOM = 6


def _inputs(curve, rng, n_random=SLOW_RANDOM):
    """Limb vectors spanning the whole legal input space: canonical
    values (random, 0, 1, p-1, p, p+1, 2^256-1), the all-ones fresh
    bound (every limb = MASK), and the closed-set carried maxima."""
    p = CURVES[curve]["p"]
    vals = [0, 1, p - 1, p, p + 1, (1 << 256) - 1]
    vals += [int(rng.integers(0, 1 << 62)) ** 5 % p for _ in range(n_random)]
    cols = [to_limbs(v) for v in vals]
    cols.append(np.full(NLIMB, MASK, dtype=np.uint32))
    ksub = CURVES[curve]["ksub"]
    bounds, _ = fc.bound_closed_set(curve, ksub=list(ksub))
    cols.append(np.asarray(bounds, dtype=np.uint32))
    # random carried-form inputs up to the closed-set bound per row
    for _ in range(n_random):
        cols.append(
            rng.integers(0, np.asarray(bounds) + 1, NLIMB).astype(np.uint32)
        )
    return cols


@pytest.mark.parametrize("curve", list(CURVES))
class TestFeOpsVsBignum:
    def test_mul_sq(self, curve):
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        rng = np.random.default_rng(7)
        cols = _inputs(curve, rng, n_random=FAST_RANDOM)
        a = _lanes(cols)
        b = _lanes(cols[::-1])
        got = np.asarray(fe.mul(a, b))
        sq = np.asarray(fe.sq(a))
        for k in range(a.shape[1]):
            va, vb = from_limbs(cols[k]), from_limbs(cols[::-1][k])
            assert from_limbs(got[:, k]) % p == (va * vb) % p, (curve, "mul", k)
            assert from_limbs(sq[:, k]) % p == (va * va) % p, (curve, "sq", k)

    def test_add_sub_carry(self, curve):
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        rng = np.random.default_rng(11)
        cols = _inputs(curve, rng, n_random=FAST_RANDOM)
        a = _lanes(cols)
        b = _lanes(cols[::-1])
        ksub = _ksub_col(curve)
        got_add = np.asarray(fe.add(a, b))
        got_sub = np.asarray(fe.sub(a, b, ksub))
        got_carry = np.asarray(fe.carry(a))
        for k in range(a.shape[1]):
            va, vb = from_limbs(cols[k]), from_limbs(cols[::-1][k])
            assert from_limbs(got_add[:, k]) % p == (va + vb) % p, (curve, "add", k)
            assert from_limbs(got_sub[:, k]) % p == (va - vb) % p, (curve, "sub", k)
            assert from_limbs(got_carry[:, k]) % p == va % p, (curve, "carry", k)

    def test_inv(self, curve):
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        vals = [1, 2, p - 1]
        cols = [to_limbs(v) for v in vals]
        got = np.asarray(fe.inv(_lanes(cols)))
        for k, v in enumerate(vals):
            assert from_limbs(got[:, k]) % p == pow(v, p - 2, p), (curve, "inv", k)

    def test_mul_small(self, curve):
        if curve != "secp256k1":
            pytest.skip("mul_small is a secp-only op (B3 = 21)")
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        rng = np.random.default_rng(17)
        cols = _inputs(curve, rng)
        got = np.asarray(fe.mul_small(_lanes(cols), 21))
        for k, c in enumerate(cols):
            assert from_limbs(got[:, k]) % p == (from_limbs(c) * 21) % p


@pytest.mark.slow
@pytest.mark.parametrize("curve", list(CURVES))
class TestFeOpsVsBignumExhaustive:
    """The full-width sweeps the fast tier trims: every adversarial
    pattern with the full random lane count, and inv on one more value."""

    def test_mul_sq_exhaustive(self, curve):
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        rng = np.random.default_rng(7)
        cols = _inputs(curve, rng, n_random=SLOW_RANDOM)
        a = _lanes(cols)
        b = _lanes(cols[::-1])
        got = np.asarray(fe.mul(a, b))
        sq = np.asarray(fe.sq(a))
        for k in range(a.shape[1]):
            va, vb = from_limbs(cols[k]), from_limbs(cols[::-1][k])
            assert from_limbs(got[:, k]) % p == (va * vb) % p, (curve, "mul", k)
            assert from_limbs(sq[:, k]) % p == (va * va) % p, (curve, "sq", k)

    def test_add_sub_carry_exhaustive(self, curve):
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        rng = np.random.default_rng(11)
        cols = _inputs(curve, rng, n_random=SLOW_RANDOM)
        a = _lanes(cols)
        b = _lanes(cols[::-1])
        ksub = _ksub_col(curve)
        got_add = np.asarray(fe.add(a, b))
        got_sub = np.asarray(fe.sub(a, b, ksub))
        got_carry = np.asarray(fe.carry(a))
        for k in range(a.shape[1]):
            va, vb = from_limbs(cols[k]), from_limbs(cols[::-1][k])
            assert from_limbs(got_add[:, k]) % p == (va + vb) % p, (curve, "add", k)
            assert from_limbs(got_sub[:, k]) % p == (va - vb) % p, (curve, "sub", k)
            assert from_limbs(got_carry[:, k]) % p == va % p, (curve, "carry", k)

    def test_inv_exhaustive(self, curve):
        p = CURVES[curve]["p"]
        fe = fc.make_fe(curve)
        rng = np.random.default_rng(13)
        vals = [1, 2, p - 1, int(rng.integers(2, 1 << 61)) ** 4 % p]
        cols = [to_limbs(v) for v in vals]
        got = np.asarray(fe.inv(_lanes(cols)))
        for k, v in enumerate(vals):
            assert from_limbs(got[:, k]) % p == pow(v, p - 2, p), (curve, "inv", k)


class TestBatchLayout:
    """The XLA kernels use the batch-leading (..., NLIMB) layout through
    their _mul_cols; its columns must be the exact schoolbook integers
    (the carry tails downstream assume identical column values)."""

    @pytest.mark.parametrize("curve", list(CURVES))
    def test_columns_match_schoolbook(self, curve):
        rng = np.random.default_rng(19)
        ksub = CURVES[curve]["ksub"]
        bounds, _ = fc.bound_closed_set(curve, ksub=list(ksub))
        hi = np.asarray(bounds, dtype=np.uint64)
        for shape in ((4, NLIMB), (2, 3, NLIMB)):
            a = rng.integers(0, hi + 1, shape).astype(np.uint32)
            b = rng.integers(0, hi + 1, shape).astype(np.uint32)
            out = 2 * NLIMB + 1
            if curve == "ed25519":
                cols = ed_xla._mul_cols(jnp.asarray(a), jnp.asarray(b), out)
            else:
                cols = sp_xla._mul_cols(jnp.asarray(a), jnp.asarray(b))
            got = np.asarray(cols).astype(np.uint64)
            want = np.zeros(shape[:-1] + (out,), dtype=np.uint64)
            for i in range(NLIMB):
                want[..., i:i + NLIMB] += (
                    a[..., i:i + 1].astype(np.uint64) * b
                )
            # columns are equal as uint32 integers (mod 2^32 — the bound
            # tests prove nothing actually wraps in the kernels' range)
            np.testing.assert_array_equal(got & 0xFFFFFFFF,
                                          want & 0xFFFFFFFF)

    def test_constant_operand_broadcasts(self, curve="ed25519"):
        # pt_add multiplies by (NLIMB, 1) constants (d2, ksub), which
        # broadcast against (NLIMB, B) operands
        p = CURVES[curve]["p"]
        rng = np.random.default_rng(23)
        a = rng.integers(0, MASK + 1, (NLIMB, 5)).astype(np.uint32)
        c = rng.integers(0, MASK + 1, (NLIMB, 1)).astype(np.uint32)
        fe = fc.make_fe(curve)
        got = np.asarray(fe.mul(jnp.asarray(a), jnp.asarray(c)))
        vc = from_limbs(c[:, 0])
        for k in range(a.shape[1]):
            assert from_limbs(got[:, k]) % p == (
                from_limbs(a[:, k]) * vc) % p, k


class TestBounds:
    """Mechanical re-proof of the overflow claims (replaces the stale
    hand-written block that used to sit atop ops/ed25519_pallas.py)."""

    @pytest.mark.parametrize("curve", list(CURVES))
    def test_closed_set_converges_below_2_32(self, curve):
        ksub = list(CURVES[curve]["ksub"])
        bounds, peak = fc.bound_closed_set(curve, ksub=ksub)
        assert peak < 1 << 32, (curve, peak)
        # closure: one more round of every op stays within the fixed point
        bm, _ = fc.bound_fe_mul(curve, bounds, bounds)
        ba, _ = fc.bound_fe_add(curve, bounds, bounds)
        bs, _ = fc.bound_fe_sub(curve, bounds, bounds, ksub)
        for nxt in (bm, ba, bs):
            assert all(x <= y for x, y in zip(nxt, bounds)), curve

    def test_ed25519_41st_product_row_required(self):
        # regression pin for the top-carry drop: no direct product reaches
        # column 40 (i + j <= 38), but near-bound inputs overflow column 38
        # and the carry ripples one row per round — a 40-limb buffer would
        # silently drop the carry out of row 39
        cols = fc.bound_mul_columns([13000] * NLIMB, [13000] * NLIMB,
                                    2 * NLIMB + 1)
        assert cols[2 * NLIMB] == 0
        bs = cols
        for _ in range(3):
            c = [b >> BITS for b in bs]
            bs = [min(b, MASK) + s for b, s in zip(bs, [0] + c[:-1])]
        assert bs[2 * NLIMB] > 0


class TestResidentRoundBounds:
    """The ladder's resident form (PR 47) runs, after 4 doublings, K mixed
    adds from the B tables and K cached adds from a member's window tables
    IN A ROW, and reads those tables from device memory where the built form
    made one in VMEM.  The same propagators that certify the lazy plan, run
    over that sequence's own chain shapes: every point op maps class C to
    class C and no intermediate reaches 2^32, so what the build program
    stores is what the ladder's adds were certified to read."""

    @staticmethod
    def _ops():
        plan = fc.derive_carry_plan("ed25519")
        C, KD, KSUB = list(plan.c), list(plan.kd), list(plan.ksub)
        peaks = []

        def seen(pair):
            bounds, peak = pair
            peaks.append(peak)
            return bounds

        def raw(*terms):
            return [sum(v) for v in zip(*terms)]

        def mul_l(a, b):
            return seen(fc.bound_ed_mul_lazy(a, b, wide=plan.mull_wide,
                                             fix=plan.mull_fix))

        def mul_f(a, b):
            return seen(fc.bound_ed_mul_lazy(a, b, wide=plan.mulf_wide,
                                             fix=plan.mulf_fix))

        def norm(r):
            return seen(fc.bound_ed_norm1(r, fix=plan.norm_fix))

        def out4(E, F, G, H):  # mul4: four mulF sharing one carry tail
            return mul_f(E, F), mul_f(G, H), mul_f(F, G), mul_f(E, H)

        def pt_double(p):
            X, Y, Z, _T = p
            A, B, ZZ = mul_l(X, X), mul_l(Y, Y), mul_l(Z, Z)
            H = norm(raw(A, B))
            xy = norm(raw(X, Y))
            E = norm(raw(H, KD))            # sub(H, mul_lazy(xy, xy), kd)
            mul_l(xy, xy)
            G = norm(raw(A, KD))            # sub(A, B, kd)
            F = norm(raw(raw(ZZ, ZZ), G))   # add(add_raw(ZZ, ZZ), G)
            return out4(E, F, G, H)

        def pt_madd(p, ypx, ymx, t2d):
            X, Y, Z, T = p
            A = mul_l(norm(raw(Y, KSUB)), ymx)
            B = mul_l(raw(Y, X), ypx)
            Cc = mul_l(T, t2d)
            Dv = raw(Z, Z)
            return out4(norm(raw(B, KD)), norm(raw(Dv, KD)),
                        norm(raw(Dv, Cc)), norm(raw(B, A)))

        def pt_add_cached(p, q):
            X, Y, Z, T = p
            ypx, ymx, Z2, t2d = q
            A = mul_l(norm(raw(Y, KSUB)), ymx)
            B = mul_l(raw(Y, X), ypx)
            Cc = mul_l(T, t2d)
            Dv = mul_l(raw(Z, Z), Z2)
            return out4(norm(raw(B, KD)), norm(raw(Dv, KD)),
                        norm(raw(Dv, Cc)), norm(raw(B, A)))

        def pt_to_cached(p):
            X, Y, Z, T = p
            return norm(raw(Y, X)), norm(raw(Y, KSUB)), Z, mul_f(T, C)

        return SimpleNamespace(
            C=C, peaks=peaks, pt_double=pt_double, pt_madd=pt_madd,
            pt_add_cached=pt_add_cached, pt_to_cached=pt_to_cached)

    def test_a_resident_round_closes_over_class_c(self):
        from tendermint_tpu.ops import ed25519_pallas as ep

        ops = self._ops()
        C = ops.C
        inside = lambda v: all(x <= c for x, c in zip(v, C))
        # a B entry is canonical limbs; a member's entry is what the build
        # stores: pt_to_cached of a class-C point.  A one-hot masked sum of
        # sixteen entries is bounded by the largest entry
        niels = ([fc.MASK] * NLIMB,) * 3
        cached = ops.pt_to_cached((C, C, C, C))
        assert all(inside(v) for v in cached)
        acc = (C, C, C, C)
        for _ in range(2):  # a round, and the round behind it
            for _ in range(4):
                acc = ops.pt_double(acc)
                assert all(inside(v) for v in acc)
            for _ in range(ep.K):
                acc = ops.pt_madd(acc, *niels)
                assert all(inside(v) for v in acc)
                acc = ops.pt_add_cached(acc, cached)
                assert all(inside(v) for v in acc)
        assert max(ops.peaks) < 1 << 32

    def test_a_members_entries_fit_the_words_they_are_stored_in(self):
        # the build program's rows are uint32 words of class-C limbs
        assert max(self._ops().C) < 1 << 16
