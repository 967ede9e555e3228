"""Bit-exactness of the JAX batched ed25519 kernel vs the host Go-exact oracle.

Covers the full adversarial accept/reject surface the oracle models
(tendermint_tpu/crypto/ed25519.py docstring): s-range quirk, non-canonical
encodings, decompression failures, corrupt bytes — plus the sharded path over
the 8-device CPU mesh from conftest.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.ops import ed25519_verify as kernel

# The accept/reject tests below are parametrized over the device backends
# tier-1 can run.  Tier-1 is CPU only, so that is the XLA kernel; the Pallas
# kernel's math is covered by tests/test_pallas_interpret.py and its verdicts
# on the chip, lane for lane against the host oracle, by chip_smoke.py.
BACKENDS = ["xla"]


def _verify(backend, pubs, msgs, sigs):
    assert backend == "xla"
    return kernel.verify_batch(pubs, msgs, sigs)


def _limbs_to_int(l):
    import numpy as np

    return sum(int(v) << (13 * i) for i, v in enumerate(np.asarray(l)))


class TestFieldBounds:
    """Pin the two kernels' (different!) field-arithmetic contracts.

    XLA kernel: carried limbs reach ~8800 (fe_sub's limb-0 wraparound),
    and fe_mul must hold well past that — its 41st product row guards the
    top-carry drop (same mechanism as the secp bug fixed in
    secp256k1_verify.fe_mul), which was reachable at the margin
    (top limbs 8192·8192 = 2^26 exactly).
    Pallas kernel: proven to M = 13000 in its header; checked past it."""

    def test_xla_ops_correct_well_past_carried_bound(self):
        import numpy as np
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        for bound in (8192, 8800, 13000):
            for _ in range(60):
                a = rng.integers(0, bound, (1, kernel.NLIMB)).astype(np.uint32)
                b = rng.integers(0, bound, (1, kernel.NLIMB)).astype(np.uint32)
                ia, ib = _limbs_to_int(a[0]), _limbs_to_int(b[0])
                gm = np.asarray(kernel.fe_mul(jnp.asarray(a), jnp.asarray(b)))
                ga = np.asarray(kernel.fe_add(jnp.asarray(a), jnp.asarray(b)))
                gs = np.asarray(kernel.fe_sub(jnp.asarray(a), jnp.asarray(b)))
                assert _limbs_to_int(gm[0]) % kernel.P == ia * ib % kernel.P
                assert _limbs_to_int(ga[0]) % kernel.P == (ia + ib) % kernel.P
                assert _limbs_to_int(gs[0]) % kernel.P == (ia - ib) % kernel.P

    def test_xla_fe_mul_top_carry_margin_case(self):
        """Regression for the dropped row-39 carry: top limbs 8192·8192
        hit 2^26 exactly, whose carry a 40-limb buffer silently lost."""
        import numpy as np
        import jax.numpy as jnp

        a = np.zeros((1, kernel.NLIMB), np.uint32)
        b = np.zeros((1, kernel.NLIMB), np.uint32)
        a[0, kernel.NLIMB - 1] = 8192
        b[0, kernel.NLIMB - 1] = 8192
        got = np.asarray(kernel.fe_mul(jnp.asarray(a), jnp.asarray(b)))
        want = (_limbs_to_int(a[0]) * _limbs_to_int(b[0])) % kernel.P
        assert _limbs_to_int(got[0]) % kernel.P == want

    def test_pallas_row_ops_correct_at_documented_bound(self):
        import numpy as np
        import jax.numpy as jnp

        from tendermint_tpu.ops import ed25519_pallas as ep

        rng = np.random.default_rng(6)
        ksub = jnp.asarray(ep._K_SUB[:, None].astype(np.uint32))
        for bound in (8192, 13000, 14000):
            for _ in range(40):
                a = rng.integers(0, bound, (ep.NLIMB, 4)).astype(np.uint32)
                b = rng.integers(0, bound, (ep.NLIMB, 4)).astype(np.uint32)
                gm = np.asarray(ep.fe_mul(jnp.asarray(a), jnp.asarray(b)))
                ga = np.asarray(ep.fe_add(jnp.asarray(a), jnp.asarray(b)))
                gs = np.asarray(ep.fe_sub(jnp.asarray(a), jnp.asarray(b), ksub))
                for c in range(4):
                    ia, ib = _limbs_to_int(a[:, c]), _limbs_to_int(b[:, c])
                    assert _limbs_to_int(gm[:, c]) % ep.P == ia * ib % ep.P
                    assert _limbs_to_int(ga[:, c]) % ep.P == (ia + ib) % ep.P
                    assert _limbs_to_int(gs[:, c]) % ep.P == (ia - ib) % ep.P


def _mk(n, msg_len=110, seed0=1):
    """n valid (pub, msg, sig) triples."""
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        priv = ed.gen_privkey(bytes([seed0 + i % 250]) * 32)
        msg = bytes([i % 256]) * msg_len
        pubs.append(priv[32:])
        msgs.append(msg)
        sigs.append(ed.sign(priv, msg))
    return (
        np.frombuffer(b"".join(pubs), np.uint8).reshape(n, 32).copy(),
        msgs,
        np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64).copy(),
    )


def _oracle(pubs, msgs, sigs):
    return np.array(
        [
            ed.verify(pubs[i].tobytes(), bytes(msgs[i]), sigs[i].tobytes())
            for i in range(len(msgs))
        ],
        dtype=bool,
    )


class TestFieldArithmetic:
    def test_limb_roundtrip(self):
        for v in [0, 1, 19, ed.P - 1, ed.P, 2**255 - 1, 12345678901234567890]:
            assert kernel.limbs_to_int(kernel.int_to_limbs(v)) == v % 2**260

    @pytest.mark.parametrize("op", ["add", "sub", "mul"])
    def test_ops_match_bigint(self, op):
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        vals = [int.from_bytes(rng.bytes(32), "little") % ed.P for _ in range(16)]
        a_int, b_int = vals[:8], vals[8:]
        a = jnp.asarray(np.stack([kernel.int_to_limbs(v) for v in a_int]))
        b = jnp.asarray(np.stack([kernel.int_to_limbs(v) for v in b_int]))
        got = {
            "add": kernel.fe_add,
            "sub": kernel.fe_sub,
            "mul": kernel.fe_mul,
        }[op](a, b)
        got = np.asarray(kernel.fe_canonical(got))
        for i in range(8):
            want = {
                "add": (a_int[i] + b_int[i]) % ed.P,
                "sub": (a_int[i] - b_int[i]) % ed.P,
                "mul": (a_int[i] * b_int[i]) % ed.P,
            }[op]
            assert kernel.limbs_to_int(got[i]) == want

    def test_inv(self):
        import jax.numpy as jnp

        vals = [2, 19, ed.P - 1, 2**200 + 3]
        a = jnp.asarray(np.stack([kernel.int_to_limbs(v) for v in vals]))
        got = np.asarray(kernel.fe_canonical(kernel.fe_inv(a)))
        for i, v in enumerate(vals):
            assert kernel.limbs_to_int(got[i]) == pow(v, ed.P - 2, ed.P)

    def test_canonical_reduces_above_p(self):
        import jax.numpy as jnp

        for v in [ed.P, ed.P + 1, 2**255 - 1, 2**256 - 1]:
            limbs = np.array(
                [(v >> (13 * i)) & 8191 for i in range(20)], dtype=np.uint32
            )
            got = np.asarray(kernel.fe_canonical(jnp.asarray(limbs[None])))
            assert kernel.limbs_to_int(got[0]) == v % ed.P


@pytest.mark.parametrize("backend", BACKENDS)
class TestVerifyBatch:
    def test_valid_batch(self, backend):
        pubs, msgs, sigs = _mk(9)
        assert _verify(backend, pubs, msgs, sigs).all()

    def test_corruptions_rejected(self, backend):
        pubs, msgs, sigs = _mk(8)
        for i, byte in enumerate([0, 15, 31, 32, 40, 63, 5, 20]):
            sigs[i, byte] ^= 1
        got = _verify(backend, pubs, msgs, sigs)
        assert got.tolist() == _oracle(pubs, msgs, sigs).tolist()
        assert not got.any()

    def test_wrong_message(self, backend):
        pubs, msgs, sigs = _mk(4)
        msgs[2] = msgs[2] + b"!"
        got = _verify(backend, pubs, msgs, sigs)
        assert got.tolist() == [True, True, False, True]

    def test_s_plus_L_accepted_top_bits_rejected(self, backend):
        """The Go malleability quirk must survive the device path."""
        pubs, msgs, sigs = _mk(2)
        s = int.from_bytes(sigs[0, 32:].tobytes(), "little") + ed.L
        assert s < 2**253
        sigs[0, 32:] = np.frombuffer(s.to_bytes(32, "little"), np.uint8)
        sigs[1, 63] |= 0x20  # top-bit check -> reject
        got = _verify(backend, pubs, msgs, sigs)
        assert got.tolist() == [True, False]
        assert got.tolist() == _oracle(pubs, msgs, sigs).tolist()

    def test_noncanonical_pubkey_and_R(self, backend):
        """Forge accept-cases in the non-canonical zone and check parity."""
        # find small-y decompressable points; y and y+p encode the same pubkey
        cases = []
        for y in range(19):
            if ed._decompress_xy(y.to_bytes(32, "little")) is not None:
                cases.append(y)
        assert cases
        pubs_l, msgs, sigs_l = [], [], []
        for y in cases:
            # can't sign for these (unknown dlog) — just check reject parity on
            # a zero sig, and that canonical/noncanonical twins agree
            for enc in (y, y + ed.P):
                pubs_l.append(enc.to_bytes(32, "little"))
                msgs.append(b"m")
                sigs_l.append(b"\x00" * 64)
        n = len(msgs)
        pubs = np.frombuffer(b"".join(pubs_l), np.uint8).reshape(n, 32).copy()
        sigs = np.frombuffer(b"".join(sigs_l), np.uint8).reshape(n, 64).copy()
        got = _verify(backend, pubs, msgs, sigs)
        want = _oracle(pubs, msgs, sigs)
        # NOTE: y and y+p decompress to the same point but hash differently
        # (pubkey *bytes* enter h = SHA512(R||A||M)), so twins may legitimately
        # disagree with each other — parity with the oracle is the contract.
        # (This batch even contains a genuine accept: an all-zero sig against a
        # low-order pubkey where [h](-A) happens to encode to zeros.)
        assert got.tolist() == want.tolist()

    def test_invalid_pubkey_decompression(self, backend):
        pubs, msgs, sigs = _mk(3)
        for y in range(2, 200):
            if ed._decompress_xy(y.to_bytes(32, "little")) is None:
                pubs[1] = np.frombuffer(y.to_bytes(32, "little"), np.uint8)
                break
        got = _verify(backend, pubs, msgs, sigs)
        assert got.tolist() == [True, False, True]

    def test_zero_scalar_identity_edge(self, backend):
        """s=0, h arbitrary, R=identity-encoding: match oracle exactly."""
        pubs, msgs, sigs = _mk(1)
        ident_enc = (1).to_bytes(32, "little")  # y=1, x=0 == identity point
        sigs[0, :32] = np.frombuffer(ident_enc, np.uint8)
        sigs[0, 32:] = 0
        got = _verify(backend, pubs, msgs, sigs)
        assert got.tolist() == _oracle(pubs, msgs, sigs).tolist()

    def test_mixed_large_batch_matches_oracle(self, backend):
        rng = np.random.default_rng(3)
        pubs, msgs, sigs = _mk(40, msg_len=70)
        # corrupt a random third
        for i in rng.choice(40, 13, replace=False):
            sigs[i, rng.integers(0, 64)] ^= 1 + rng.integers(0, 254)
        got = _verify(backend, pubs, msgs, sigs)
        assert got.tolist() == _oracle(pubs, msgs, sigs).tolist()

    def test_empty(self, backend):
        assert _verify(
            backend, np.zeros((0, 32), np.uint8), [], np.zeros((0, 64), np.uint8)
        ).shape == (0,)

    def test_variable_length_messages(self, backend):
        pubs, msgs, sigs = [], [], []
        for i, ln in enumerate([0, 1, 17, 1000]):
            priv = ed.gen_privkey(bytes([40 + i]) * 32)
            m = bytes(range(256)) * (ln // 256 + 1)
            m = m[:ln]
            pubs.append(priv[32:])
            msgs.append(m)
            sigs.append(ed.sign(priv, m))
        pubs = np.frombuffer(b"".join(pubs), np.uint8).reshape(4, 32).copy()
        sigs = np.frombuffer(b"".join(sigs), np.uint8).reshape(4, 64).copy()
        assert _verify(backend, pubs, msgs, sigs).all()


class TestSharded:
    def test_mesh_sharded_batch(self):
        import jax
        from jax.sharding import Mesh

        devs = np.array(jax.devices())
        mesh = Mesh(devs, ("data",))
        pubs, msgs, sigs = _mk(24)
        sigs[5, 0] ^= 1
        got = kernel.verify_batch(pubs, msgs, sigs, mesh=mesh)
        want = _oracle(pubs, msgs, sigs)
        assert got.tolist() == want.tolist()


class TestBatchVerifierBoundary:
    def test_tpu_backend_equals_host_backend(self):
        from tendermint_tpu.crypto.batch import (
            HostBatchVerifier,
            SigItem,
            TPUBatchVerifier,
        )

        pubs, msgs, sigs = _mk(6)
        sigs[3, 10] ^= 0xFF
        items = [
            SigItem(pubs[i].tobytes(), msgs[i], sigs[i].tobytes()) for i in range(6)
        ]
        host = HostBatchVerifier().verify_ed25519(items)
        tpu = TPUBatchVerifier().verify_ed25519(items)
        assert host.tolist() == tpu.tolist()

    def test_default_backend_without_a_chip_is_xla(self):
        from tendermint_tpu.crypto.batch import TPUBatchVerifier

        v = TPUBatchVerifier()
        assert v.backend == "xla"
        assert v.device["platform"] == "cpu"
