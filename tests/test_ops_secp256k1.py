"""Batched secp256k1 ECDSA device kernel — bit-exact parity with the host
oracle (crypto/secp256k1.verify), BatchVerifier integration, and a secp
validator set going through the production verify_commit path
(BASELINE config #4; ref serial path crypto/secp256k1/secp256k1.go:140).
"""

import time

import numpy as np
import pytest

from tendermint_tpu.crypto import secp256k1 as s
from tendermint_tpu.crypto.hashing import sha256
from tendermint_tpu.ops import secp256k1_verify as K


def _fixture(n=16):
    pubs, digs, sigs = [], [], []
    for i in range(n):
        priv = s.gen_privkey(bytes([i + 1]) * 32)
        pubs.append(s.pubkey_compressed(priv))
        digs.append(sha256(f"msg-{i}".encode()))
        sigs.append(s.sign(priv, digs[-1]))
    return pubs, digs, sigs


class TestFieldBounds:
    def test_fe_ops_correct_at_carried_bound(self):
        """Regression: fe_mul silently dropped the carry out of product row
        39 (the two-term 2^260 fold ripples carries one row per round), so
        inputs with limbs just above 2^13 — legal for 'carried' elements,
        which the kernel's own bound allows up to M=13000 — miscomputed
        ~20% of products. Exercise all field ops well past the bound."""
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        for bound in (8192, 13000, 20000):
            for _ in range(60):
                a = rng.integers(0, bound, (1, K.NLIMB)).astype(np.uint32)
                b = rng.integers(0, bound, (1, K.NLIMB)).astype(np.uint32)
                ia, ib = K.limbs_to_int(a[0]), K.limbs_to_int(b[0])
                got = np.asarray(K.fe_mul(jnp.asarray(a), jnp.asarray(b)))
                assert K.limbs_to_int(got[0]) % K.P == ia * ib % K.P, bound
                assert int(got.max()) <= 13000  # closed under the op set
                ga = np.asarray(K.fe_add(jnp.asarray(a), jnp.asarray(b)))
                assert K.limbs_to_int(ga[0]) % K.P == (ia + ib) % K.P
                gs = np.asarray(K.fe_sub(jnp.asarray(a), jnp.asarray(b)))
                assert K.limbs_to_int(gs[0]) % K.P == (ia - ib) % K.P


class TestKernelParity:
    def test_valid_batch_accepts(self):
        pubs, digs, sigs = _fixture(16)
        assert K.verify_batch(pubs, digs, sigs).all()

    def test_mixed_corruptions_match_oracle(self):
        pubs, digs, sigs = _fixture(32)
        cases = []
        for i in range(32):
            pub, dig, sig = pubs[i], digs[i], sigs[i]
            kind = i % 6
            if kind == 1:  # corrupted s
                r, sv = s.der_decode_sig(sig)
                sig = s.der_encode_sig(r, sv ^ 1)
            elif kind == 2:  # wrong digest
                dig = sha256(b"other")
            elif kind == 3:  # wrong key
                pub = s.pubkey_compressed(s.gen_privkey(bytes([200]) * 32))
            elif kind == 4:  # malformed DER
                sig = b"\x30\x02\x01\x01"
            elif kind == 5:  # high-s (malleated) must be rejected
                r, sv = s.der_decode_sig(sig)
                sig = s.der_encode_sig(r, s.N - sv)
            cases.append((pub, dig, sig))
        expect = [s.verify(p, d, g) for p, d, g in cases]
        got = K.verify_batch(*zip(*cases))
        assert list(got) == expect

    def test_r_s_range_rejections(self):
        pubs, digs, sigs = _fixture(1)
        bad = [
            s.der_encode_sig(0, 5),  # r = 0
            s.der_encode_sig(s.N, 5),  # r = n
            s.der_encode_sig(5, 0),  # s = 0
        ]
        for sig in bad:
            assert not K.verify_batch(pubs, digs, [sig])[0]
            assert not s.verify(pubs[0], digs[0], sig)

    def test_bad_pubkey_rejected(self):
        pubs, digs, sigs = _fixture(1)
        junk = b"\x02" + b"\x00" * 32  # x=0 is not on the curve
        assert not K.verify_batch([junk], digs, sigs)[0]

    def test_mesh_sharded(self):
        import jax
        from jax.sharding import Mesh

        devs = np.array(jax.devices("cpu"))
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = Mesh(devs[:8], ("batch",))
        pubs, digs, sigs = _fixture(8)
        r, sv = s.der_decode_sig(sigs[3])
        sigs[3] = s.der_encode_sig(r, sv ^ 1)
        got = K.verify_batch(pubs, digs, sigs, mesh=mesh)
        assert list(got) == [True] * 3 + [False] + [True] * 4


class TestPallasPipeline:
    """The fused windowed-Straus pallas path (ops/secp256k1_pallas)."""

    def test_row_field_ops_and_complete_addition(self):
        """Fast component parity for the row-layout (20, B) ops the kernel
        is built from: field ops at the carried bound, and the complete
        a=0 addition law against host jacobian math — addition, doubling,
        and the identity path (digit-0 table entries)."""
        import jax.numpy as jnp
        from tendermint_tpu.ops import secp256k1_pallas as sp

        rng = np.random.default_rng(11)
        ksub = jnp.asarray(sp._K_SUB[:, None])

        def to_rows(v):
            return jnp.asarray(sp.int_to_limbs(v)[:, None])

        def row_int(r, col=0):
            return K.limbs_to_int(np.asarray(r)[:, col])

        for bound in (8192, 13000, 20000):
            for _ in range(40):
                a = rng.integers(0, bound, (sp.NLIMB, 4)).astype(np.uint32)
                b = rng.integers(0, bound, (sp.NLIMB, 4)).astype(np.uint32)
                gm = np.asarray(sp.fe_mul(jnp.asarray(a), jnp.asarray(b)))
                gs = np.asarray(sp.fe_sub(jnp.asarray(a), jnp.asarray(b), ksub))
                for c in range(4):
                    ia, ib = K.limbs_to_int(a[:, c]), K.limbs_to_int(b[:, c])
                    assert K.limbs_to_int(gm[:, c]) % K.P == ia * ib % K.P
                    assert K.limbs_to_int(gs[:, c]) % K.P == (ia - ib) % K.P

        one, zero = to_rows(1), to_rows(0)
        ident = (zero, one, zero)
        for _ in range(8):
            k1 = int(rng.integers(1, 1 << 60))
            k2 = int(rng.integers(1, 1 << 60))
            A = s._to_affine(s._jmul(s._G, k1))
            B = s._to_affine(s._jmul(s._G, k2))
            pa = (to_rows(A[0]), to_rows(A[1]), one)
            pb = (to_rows(B[0]), to_rows(B[1]), one)
            for q, ks in ((pb, k1 + k2), (pa, 2 * k1), (ident, k1)):
                X, _Y, Z = sp.pt_add(pa, q, ksub)
                zi = pow(row_int(Z) % K.P, K.P - 2, K.P)
                assert row_int(X) * zi % K.P == s._to_affine(s._jmul(s._G, ks))[0]

    @pytest.mark.slow
    @pytest.mark.skipif(
        not __import__("os").environ.get("TM_RUN_SLOW"),
        reason="CPU jit of the full ladder takes ~10 min (set TM_RUN_SLOW=1)",
    )
    def test_ladder_math_matches_oracle(self):
        """The kernel's exact math — shared ladder_math (digit tables, 4
        doublings + two complete adds per window) jitted once on CPU over
        the whole batch; the pallas_call wrapper adds only ref plumbing."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from tendermint_tpu.ops import secp256k1_pallas as sp

        n = 5
        pubs, digs, sigs = _fixture(n)
        # corrupt one signature, wrong-digest another
        r, sv = s.der_decode_sig(sigs[1])
        sigs[1] = s.der_encode_sig(r, sv ^ 1)
        digs[3] = sha256(b"other")
        want = [s.verify(pubs[i], digs[i], sigs[i]) for i in range(n)]

        qx = np.zeros((sp.NLIMB, n), np.uint32)
        qy = np.zeros((sp.NLIMB, n), np.uint32)
        d1 = np.zeros((sp.NWIN, n), np.uint32)
        d2 = np.zeros((sp.NWIN, n), np.uint32)
        rs = [0] * n
        for i in range(n):
            item = K.prep_item(pubs[i], digs[i], sigs[i])
            assert item[0] == "kernel"  # fixture sigs all parse
            _, Q, u1, u2, r_int = item
            qx[:, i], qy[:, i] = Q[0], Q[1]
            d1[:, i] = sp._digits_msb(u1)
            d2[:, i] = sp._digits_msb(u2)
            rs[i] = r_int

        consts = jnp.asarray(sp._CONSTS)

        @jax.jit
        def run(qx, qy, d1, d2):
            return sp.ladder_math(
                consts, qx, qy,
                lambda t: lax.dynamic_slice_in_dim(d1, t, 1, axis=0),
                lambda t: lax.dynamic_slice_in_dim(d2, t, 1, axis=0),
            )

        X, _Y, Z = run(jnp.asarray(qx), jnp.asarray(qy),
                       jnp.asarray(d1), jnp.asarray(d2))
        got = []
        for i in range(n):
            z_int = K.limbs_to_int(np.asarray(Z)[:, i]) % K.P
            if z_int == 0:
                got.append(False)
                continue
            x_aff = (K.limbs_to_int(np.asarray(X)[:, i]) % K.P
                     * pow(z_int, K.P - 2, K.P)) % K.P
            got.append(
                x_aff == rs[i]
                or (rs[i] + K.N < K.P and x_aff == rs[i] + K.N)
            )
        assert got == want

    @pytest.mark.slow
    @pytest.mark.skipif(
        not __import__("os").environ.get("TM_RUN_SLOW"),
        reason="interpret-mode ladder takes ~10 min (set TM_RUN_SLOW=1)",
    )
    def test_pallas_interpret_parity(self):
        from tendermint_tpu.ops import secp256k1_pallas as sp

        pubs, digs, sigs = _fixture(6)
        r, sv = s.der_decode_sig(sigs[1])
        sigs[1] = s.der_encode_sig(r, sv ^ 1)
        got = sp.verify_batch(pubs, digs, sigs, interpret=True)
        want = [s.verify(pubs[i], digs[i], sigs[i]) for i in range(6)]
        assert list(got) == want


class TestBatchVerifierIntegration:
    def test_tpu_batch_verifier_secp_backend(self):
        from tendermint_tpu.crypto.batch import SigItem, TPUBatchVerifier

        v = TPUBatchVerifier(backend="xla")
        msgs = [f"raw-{i}".encode() for i in range(6)]
        items = []
        for i in range(6):
            priv = s.gen_privkey(bytes([i + 40]) * 32)
            sig = s.sign(priv, sha256(msgs[i]))
            if i == 2:
                sig = s.sign(priv, sha256(b"evil"))
            items.append(SigItem(s.pubkey_compressed(priv), msgs[i], sig))
        got = v.verify_secp256k1(items)
        assert list(got) == [True, True, False, True, True, True]

    def test_secp_validator_set_commit_verify(self):
        """A secp256k1 validator set through the PRODUCTION verify_commit —
        the full BASELINE 'secp256k1 validator set' config, batched."""
        from tendermint_tpu.crypto.batch import TPUBatchVerifier
        from tendermint_tpu.crypto.keys import PrivKeySecp256k1
        from tendermint_tpu.types import BlockID, PartSetHeader, SignedMsgType, Vote
        from tendermint_tpu.types.validator_set import (
            CommitError,
            Validator,
            ValidatorSet,
        )
        from tendermint_tpu.types.block import Commit

        chain = "secp-chain"
        privs = [PrivKeySecp256k1.generate(bytes([i + 1]) * 32) for i in range(8)]
        valset = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        by_addr = {p.pub_key().address(): p for p in privs}
        block_id = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))
        votes = []
        for idx, val in enumerate(valset.validators):
            v = Vote(
                vote_type=SignedMsgType.PRECOMMIT,
                height=9,
                round=0,
                timestamp_ns=1_700_000_000_000_000_000 + idx,
                block_id=block_id,
                validator_address=val.address,
                validator_index=idx,
            )
            sig = by_addr[val.address].sign(v.sign_bytes(chain))
            votes.append(v.with_signature(sig))
        commit = Commit(block_id=block_id, precommits=votes)
        verifier = TPUBatchVerifier(backend="xla")
        valset.verify_commit(chain, block_id, 9, commit, verifier=verifier)

        # tampered signature fails through the same path
        import dataclasses

        bad = dataclasses.replace(votes[5], signature=b"\x30\x02\x01\x01")
        commit_bad = Commit(block_id=block_id, precommits=votes[:5] + [bad] + votes[6:])
        with pytest.raises(CommitError):
            valset.verify_commit(chain, block_id, 9, commit_bad, verifier=verifier)
