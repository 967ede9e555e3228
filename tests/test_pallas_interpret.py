"""Always-on (no chip, default suite) end-to-end coverage of BOTH fused
Pallas pipelines' math.

The full-width pipelines in interpret mode take ~10 min each on CPU (64
windows of field ops, eagerly dispatched or monstrous to compile), so the
default suite covers them in three layers that together execute every
kernel stage:

  * ladder parity — `ladder_math` (the pure-jnp body shared verbatim with
    the pallas kernels of ops/ed25519_pallas and ops/secp256k1_pallas) is
    CPU-jitted with a REDUCED window count derived from the digit-row shape:
    identical table build / masked selects / doublings / complete adds, 8-bit
    scalars, checked projectively against host bigint EC (compile ~40 s
    instead of ~10 min).
  * canonical/accept parity — the in-kernel scratch-ref reduction
    (`_canonical_ref`, `_seq_carry_ref`, `_fold_top_ref`) runs through real
    `pallas_call(interpret=True)` mini-kernels against bigint mod-p.
  * prologue parity — the Barrett mod-L + word/digit extraction stages are
    pure column functions, checked against bigint on synthetic SHA states.

Full-width interpret runs stay under TM_RUN_SLOW=1; the real chip runs the
full pipelines, lane for lane against the host oracle, in chip_smoke.py.

Ref anchor: /root/reference/crypto/internal/benchmarking/bench.go:46 (every
signer goes through one shared harness; here every backend must execute
even with the accelerator absent)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto import secp256k1 as s

NWIN_SMALL = 2  # 8-bit scalars: whole table selectable, MSB order exercised


def _msb_digits(x: int, nwin: int) -> np.ndarray:
    return np.array(
        [(x >> (4 * (nwin - 1 - t))) & 0xF for t in range(nwin)], np.uint32
    )


def _py_loop(lo, hi, body, init):
    """Eager stand-in for lax.fori_loop: no body compile, no simplifier
    thrash — each window's ~70 field ops dispatch as plain jnp."""
    acc = init
    for t in range(lo, hi):
        acc = body(t, acc)
    return acc


class TestEd25519LadderParity:
    def test_reduced_window_ladder_vs_host_ec(self):
        """Table build, niels + extended masked selects, 4 doublings and two
        complete adds per window — the exact kernel math — vs host EC."""
        from tendermint_tpu.ops import ed25519_pallas as ep

        n = 8
        rng = np.random.default_rng(78)
        pubs = np.zeros((n, 32), np.uint8)
        for i in range(n):
            pubs[i] = np.frombuffer(
                ed.gen_privkey(rng.bytes(32))[32:], np.uint8
            )
        neg_ax, ay, valid = ep._decompress_valset(pubs)
        assert valid.all()

        digs = np.zeros((NWIN_SMALL, n), np.uint32)
        digh = np.zeros((NWIN_SMALL, n), np.uint32)
        scalars = []
        for i in range(n):
            # lane 0: s=0 (identity through the niels digit-0 entry);
            # lane 1: h=0 (extended identity) — the complete formulas must
            # absorb both
            s_small = 0 if i == 0 else int(rng.integers(1, 256))
            h_small = 0 if i == 1 else int(rng.integers(1, 256))
            digs[:, i] = _msb_digits(s_small, NWIN_SMALL)
            digh[:, i] = _msb_digits(h_small, NWIN_SMALL)
            scalars.append((s_small, h_small))

        consts = jnp.asarray(ep._CONSTS)
        digs_j, digh_j = jnp.asarray(digs), jnp.asarray(digh)

        X, Y, Z, T = ep.ladder_math(
            consts, jnp.asarray(neg_ax.T.copy()), jnp.asarray(ay.T.copy()),
            lambda t: digs_j[t : t + 1, :],
            lambda t: digh_j[t : t + 1, :],
            nwin=NWIN_SMALL,
            loop=_py_loop,
        )
        X, Y, Z, T = (np.asarray(v) for v in (X, Y, Z, T))

        to_int = lambda col: ed25519_limbs_to_int(col)
        B_ext = ed._to_extended((ed.B_AFFINE, ed._BY))
        for i in range(n):
            s_small, h_small = scalars[i]
            ax_int, ay_int = ed._decompress_xy(pubs[i].tobytes())
            negA = ed._to_extended(((ed.P - ax_int) % ed.P, ay_int))
            e = ed.pt_add(
                ed.pt_scalar_mult(B_ext, s_small),
                ed.pt_scalar_mult(negA, h_small),
            )
            ex, ey, ez, _et = e  # host extended coordinates
            gx, gy, gz = to_int(X[:, i]), to_int(Y[:, i]), to_int(Z[:, i])
            gt = to_int(T[:, i])
            # projective equality: X/Z == ex/ez, Y/Z == ey/ez (mod p)
            assert gx * ez % ed.P == ex * gz % ed.P
            assert gy * ez % ed.P == ey * gz % ed.P
            # extended invariant T = XY/Z
            assert gt * gz % ed.P == gx * gy % ed.P


def ed25519_limbs_to_int(col) -> int:
    from tendermint_tpu.ops import ed25519_verify as k

    return sum(int(v) << (13 * i) for i, v in enumerate(np.asarray(col)))


class TestSecp256k1LadderParity:
    def test_reduced_window_ladder_vs_host_ec(self):
        """Identity-through table build, shared doublings via the complete
        a=0 law, u1-table and u2-table adds — vs host jacobian math."""
        from tendermint_tpu.ops import secp256k1_pallas as sp
        from tendermint_tpu.ops import secp256k1_verify as K

        n = 8
        rng = np.random.default_rng(79)
        qx = np.zeros((sp.NLIMB, n), np.uint32)
        qy = np.zeros((sp.NLIMB, n), np.uint32)
        d1 = np.zeros((NWIN_SMALL, n), np.uint32)
        d2 = np.zeros((NWIN_SMALL, n), np.uint32)
        expected = []
        for i in range(n):
            k = int(rng.integers(1, 1 << 60))
            Q = s._to_affine(s._jmul(s._G, k))
            qx[:, i] = sp.int_to_limbs(Q[0])
            qy[:, i] = sp.int_to_limbs(Q[1])
            if i == 7:
                expected.append(None)  # u1 = u2 = 0 -> identity (Z = 0)
                continue
            u1 = 0 if i == 0 else int(rng.integers(1, 256))
            u2 = 0 if i == 1 else int(rng.integers(1, 256))
            d1[:, i] = _msb_digits(u1, NWIN_SMALL)
            d2[:, i] = _msb_digits(u2, NWIN_SMALL)
            j = s._jadd(s._jmul(s._G, u1), s._jmul((Q[0], Q[1], 1), u2))
            expected.append(s._to_affine(j))

        consts = jnp.asarray(sp._CONSTS)
        d1_j, d2_j = jnp.asarray(d1), jnp.asarray(d2)

        X, Y, Z = (
            np.asarray(v)
            for v in sp.ladder_math(
                consts, jnp.asarray(qx), jnp.asarray(qy),
                lambda t: d1_j[t : t + 1, :],
                lambda t: d2_j[t : t + 1, :],
                nwin=NWIN_SMALL,
                loop=_py_loop,
            )
        )
        for i in range(n):
            gx = K.limbs_to_int(X[:, i]) % K.P
            gz = K.limbs_to_int(Z[:, i]) % K.P
            if expected[i] is None:
                assert gz == 0  # projective identity
                continue
            ex, ey = expected[i]
            assert gz != 0
            assert gx * pow(gz, K.P - 2, K.P) % K.P == ex
            gy = K.limbs_to_int(Y[:, i]) % K.P
            assert gy * pow(gz, K.P - 2, K.P) % K.P == ey


class TestCanonicalRefKernels:
    """The scratch-ref reduction paths only a pallas kernel can run —
    through real pallas_call(interpret=True) mini-kernels."""

    def test_ed25519_canonical_interpret(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        from tendermint_tpu.ops import ed25519_pallas as ep

        n = 8
        rng = np.random.default_rng(80)
        vals = rng.integers(0, 13000, (ep.NLIMB, n)).astype(np.uint32)
        vals[:, 1] = ep.int_to_limbs(ed.P - 1)  # boundary: p-1 stays
        vals[:, 2] = ep.int_to_limbs(ed.P)  # boundary: p reduces to 0
        # limbs at the carried bound M with a max top limb
        vals[:, 3] = 12999
        want = [
            ed25519_limbs_to_int(vals[:, i]) % ed.P for i in range(n)
        ]

        def kern(v_ref, out_ref, s1, s2):
            out_ref[:] = ep._canonical_ref(v_ref[:], s1, s2)

        spec = pl.BlockSpec(
            (ep.NLIMB, n), lambda i: (0, 0), memory_space=pltpu.VMEM
        )
        got = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((ep.NLIMB, n), jnp.uint32),
            grid=(1,),
            in_specs=[spec],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((ep.NLIMB, n), jnp.uint32)] * 2,
            interpret=True,
        )(jnp.asarray(vals))
        got = np.asarray(got)
        for i in range(n):
            assert ed25519_limbs_to_int(got[:, i]) == want[i]

    def test_secp_canonical_interpret(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        from tendermint_tpu.ops import secp256k1_pallas as sp
        from tendermint_tpu.ops import secp256k1_verify as K

        n = 8
        rng = np.random.default_rng(81)
        vals = rng.integers(0, 13000, (sp.NLIMB, n)).astype(np.uint32)
        vals[:, 1] = sp.int_to_limbs(K.P - 1)
        vals[:, 2] = sp.int_to_limbs(K.P)
        want = [K.limbs_to_int(vals[:, i]) % K.P for i in range(n)]

        def kern(v_ref, out_ref, s1, s2):
            out_ref[:] = sp._canonical_ref(v_ref[:], s1, s2)

        spec = pl.BlockSpec(
            (sp.NLIMB, n), lambda i: (0, 0), memory_space=pltpu.VMEM
        )
        got = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((sp.NLIMB, n), jnp.uint32),
            grid=(1,),
            in_specs=[spec],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((sp.NLIMB, n), jnp.uint32)] * 2,
            interpret=True,
        )(jnp.asarray(vals))
        got = np.asarray(got)
        for i in range(n):
            assert K.limbs_to_int(got[:, i]) == want[i]


class TestPrologueStages:
    def test_mod_l_and_digit_extraction_vs_bigint(self):
        """Barrett mod-L over synthetic SHA-512 states + word packing —
        the prologue's math stages against bigint."""
        from tendermint_tpu.ops import ed25519_pallas as ep

        n = 8
        rng = np.random.default_rng(82)
        digests = [rng.bytes(64) for _ in range(n)]
        # synthetic digest state: 8 (hi, lo) pairs of (1, n) uint32 rows,
        # big-endian per 64-bit word — the layout _sha512_in_kernel yields
        state = []
        for wi in range(8):
            hi = np.zeros((1, n), np.uint32)
            lo = np.zeros((1, n), np.uint32)
            for i in range(n):
                word = int.from_bytes(digests[i][8 * wi : 8 * wi + 8], "big")
                hi[0, i] = word >> 32
                lo[0, i] = word & 0xFFFFFFFF
            state.append((jnp.asarray(hi), jnp.asarray(lo)))

        limbs = ep._mod_l_device(state)
        words8 = ep._limbs_to_words8(limbs)
        for i in range(n):
            h = int.from_bytes(digests[i], "little") % ed.L
            got = sum(
                int(np.asarray(limbs[k])[0, i]) << (13 * k) for k in range(20)
            )
            assert got == h
            got_w = sum(
                int(np.asarray(words8[j])[0, i]) << (32 * j) for j in range(8)
            )
            assert got_w == h


@pytest.mark.slow
@pytest.mark.skipif(
    not os.environ.get("TM_RUN_SLOW"),
    reason="full-width interpret pipeline takes ~10 min (set TM_RUN_SLOW=1)",
)
class TestFullInterpretPipeline:
    def test_ed25519_verify_batch_interpret(self):
        from tendermint_tpu.ops import ed25519_pallas as ep

        rng = np.random.default_rng(83)
        pubs = np.zeros((4, 32), np.uint8)
        sigs = np.zeros((4, 64), np.uint8)
        msgs = []
        for i in range(4):
            priv = ed.gen_privkey(rng.bytes(32))
            m = rng.bytes(33)
            pubs[i] = np.frombuffer(priv[32:], np.uint8)
            sigs[i] = np.frombuffer(ed.sign(priv, m), np.uint8)
            msgs.append(m)
        sigs[2, 5] ^= 1
        got = ep.verify_batch(pubs, msgs, sigs, interpret=True)
        want = [ed.verify(pubs[i].tobytes(), msgs[i], sigs[i].tobytes())
                for i in range(4)]
        assert list(got) == want
