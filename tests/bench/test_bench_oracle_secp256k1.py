"""The benchmark's own ECDSA verifier: fixed vectors, its two paths against
each other, and the program's host oracle held to it on every rule of the
accept set."""

import hashlib

import numpy as np
import pytest

from benchmark import oracle_secp256k1 as oracle
from tendermint_tpu.crypto import secp256k1 as program

# btcec's RFC 6979 vectors (signature_test.go): key, message, DER signature
VECTORS = [
    ("cca9fbcc1b41e5a95d369eaa6ddcff73b61a4efaa279cfc6567e8daa39cbaf50", b"sample",
     "3045022100af340daf02cc15c8d5d08d7735dfe6b98a474ed373bdb5fbecf7571be52b3842"
     "02205009fb27f37034a9b24b707b7c6b79ca23ddef9e25f7282e8a797efe53a8f124"),
    ("0000000000000000000000000000000000000000000000000000000000000001",
     b"Satoshi Nakamoto",
     "3045022100934b1ea10a4b3c1757e2b0c017d0b6143ce3c9a7e6a4a49860d7a6ab210ee3d8"
     "02202442ce9d2b916064108014783e923ec36b49743e2ffa1c4496f01a512aafd9e5"),
    ("f8b8af8ce3c7cca5e300d33939540c10d45ce001b8f252bfbc57ba0342904181", b"Alan Turing",
     "304402207063ae83e7f62bbb171798131b4a0564b956930092b33b07b395615d9ec7e15c"
     "022058dfcc1e00a35e1572f366ffe34ba0fc47db1e7189759b9fb233c5b05ab388ea"),
]
G_COMPRESSED = "0279be667ef9dcbbac55a06295ce870b07029bfcdb2dce28d959f2815b16f81798"


def _both(pub, msg, sig):
    """The oracle's verdict (both of its paths, which must agree) and the
    program's host oracle's."""
    a, b = oracle.verify(pub, msg, sig), oracle.verify_exact(pub, msg, sig)
    assert a == b, (pub.hex(), sig.hex())
    return a, program.verify(pub, hashlib.sha256(msg).digest(), sig)


@pytest.mark.parametrize("key,msg,sig", VECTORS)
def test_fixed_vectors(key, msg, sig):
    pub = oracle.pubkey_of(int(key, 16))
    assert _both(pub, msg, bytes.fromhex(sig)) == (True, True)
    assert _both(pub, msg + b"!", bytes.fromhex(sig)) == (False, False)
    # the program's RFC 6979 signer gives the vector; the oracle's signer,
    # handed the nonce, gives a signature of the same (r, s) shape
    assert program.sign(bytes.fromhex(key), hashlib.sha256(msg).digest()).hex() == sig
    assert oracle.parse_der(bytes.fromhex(sig)) == program.der_decode_sig(bytes.fromhex(sig))


def test_base_point_and_group_order():
    assert oracle.pubkey_of(1).hex() == G_COMPRESSED
    assert oracle.mul_base(oracle.N) is None
    assert oracle.add(oracle.mul_base(5), oracle.mul_base(oracle.N - 5)) is None
    assert oracle.mul((oracle.GX, oracle.GY), 12345) == oracle.mul_base(12345)


def _case(seed=7, n=24):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        d = int.from_bytes(rng.bytes(32), "big") % (oracle.N - 1) + 1
        msg = rng.bytes(int(rng.integers(1, 200)))
        k = int.from_bytes(rng.bytes(32), "big") % (oracle.N - 1) + 1
        rows.append((d, oracle.pubkey_of(d), msg, oracle.sign(d, msg, k)))
    return rows


def test_seeded_signatures_verify_on_both_sides_and_are_strict_low_s():
    sizes = set()
    for d, pub, msg, sig in _case():
        assert pub == program.pubkey_compressed(d.to_bytes(32, "big"))
        assert _both(pub, msg, sig) == (True, True)
        r, s = oracle.parse_der(sig)
        assert s <= oracle.HALF_N and oracle.encode_der(r, s) == sig
        sizes.add(len(sig))
    assert sizes <= {68, 69, 70, 71}


def _int_bytes(v):
    b = v.to_bytes((v.bit_length() + 7) // 8 or 1, "big")
    return b"\x00" + b if b[0] & 0x80 else b


def _der(rb, sb, total=None):
    body = b"\x02" + bytes([len(rb)]) + rb + b"\x02" + bytes([len(sb)]) + sb
    return b"\x30" + bytes([len(body) if total is None else total]) + body


def _adversarial(pub, msg, sig):
    """(name, pub, msg, sig, what the accept set says)."""
    r, s = oracle.parse_der(sig)
    rb, sb = _int_bytes(r), _int_bytes(s)
    off_curve = next(
        v for v in range(1, 50)
        if pow((v ** 3 + 7) % oracle.P, (oracle.P - 1) // 2, oracle.P) != 1)
    yield "untouched", pub, msg, sig, True
    yield "high_s", pub, msg, oracle.encode_der(r, oracle.N - s), False
    yield "s_bit_flipped", pub, msg, oracle.encode_der(r, s ^ 2), False
    yield "r_bit_flipped", pub, msg, oracle.encode_der(r ^ 2, s), False
    yield "lax_r_padded", pub, msg, _der(b"\x00" + rb, sb), False
    yield "lax_s_padded", pub, msg, _der(rb, b"\x00" + sb), False
    yield "lax_r_unpadded_sign_bit", pub, msg, _der(b"\x80" + rb[1:], sb), False
    yield "lax_long_form_length", pub, msg, b"\x30\x81" + sig[1:], False
    yield "lax_sequence_too_long", pub, msg, _der(rb, sb, total=len(sig) - 1), False
    yield "lax_sequence_too_short", pub, msg, _der(rb, sb, total=len(sig) - 3), False
    yield "wrong_integer_tag", pub, msg, sig[:2] + b"\x03" + sig[3:], False
    yield "wrong_sequence_tag", pub, msg, b"\x31" + sig[1:], False
    yield "empty_r", pub, msg, _der(b"", sb), False
    yield "truncated", pub, msg, sig[:-1], False
    yield "too_short", pub, msg, b"\x30\x02\x01\x01", False
    # bytes past the sequence are cut off, not refused (btcec's "trailing
    # crap" vector, valid since Bitcoin's signatures carry a hash type)
    yield "trailing_bytes", pub, msg, sig + b"\x01", True
    yield "trailing_bytes_two", pub, msg, sig + b"\x00\x00", True
    yield "r_zero", pub, msg, oracle.encode_der(0, s), False
    yield "s_zero", pub, msg, oracle.encode_der(r, 0), False
    yield "r_is_n", pub, msg, oracle.encode_der(oracle.N, s), False
    yield "s_is_n", pub, msg, oracle.encode_der(r, oracle.N), False
    yield "r_plus_n", pub, msg, _der(_int_bytes(r + oracle.N), sb), False
    yield "key_prefix_04", b"\x04" + pub[1:], msg, sig, False
    yield "key_prefix_05", b"\x05" + pub[1:], msg, sig, False
    yield "key_other_parity", bytes([pub[0] ^ 1]) + pub[1:], msg, sig, False
    yield "key_x_is_p", pub[:1] + oracle.P.to_bytes(32, "big"), msg, sig, False
    yield "key_x_off_curve", b"\x02" + off_curve.to_bytes(32, "big"), msg, sig, False
    yield "key_32_bytes", pub[:-1], msg, sig, False
    yield "key_65_bytes", b"\x04" + pub[1:] + bytes(32), msg, sig, False
    yield "other_message", pub, msg + b"\x00", sig, False


def test_adversarial_inputs_agree_with_the_programs_host_oracle():
    """Every rule of the accept set, on several signatures: the oracle gives
    what the rule says and the program's host oracle (which the device
    path's prologue parses with and the guard audits with) gives the same.
    No difference is left to write down."""
    seen = set()
    for _d, pub, msg, sig in _case(seed=11, n=6):
        for name, p, m, g, want in _adversarial(pub, msg, sig):
            ours, theirs = _both(p, m, g)
            assert ours == want, name
            assert theirs == ours, name
            seen.add(name)
    assert len(seen) == 30


def test_openssl_only_confirms():
    """OpenSSL takes high-s and padded integers; the oracle's fast path is
    never asked about a signature the plain rules refused."""
    _d, pub, msg, sig = _case(seed=3, n=1)[0]
    r, s = oracle.parse_der(sig)
    digest = hashlib.sha256(msg).digest()
    assert oracle._openssl_confirms(pub, digest, r, oracle.N - s)  # OpenSSL's view
    assert not oracle.verify(pub, msg, oracle.encode_der(r, oracle.N - s))
    assert not oracle._openssl_confirms(pub, digest, r, s ^ 2)
