"""The benchmark's own secp256k1 ECDSA verifier and signer: the plain
reference every device verdict of the ``commit-secp256k1-256`` deployment is
compared with.  It imports nothing of the program.

The accept set is the one the configuration states, Tendermint v0.26.2's
``PubKeySecp256k1.VerifyBytes`` (crypto/secp256k1/secp256k1.go:140) over the
btcec it vendors.  Rule by rule, each refusing when it fails:

key (``btcec.ParsePubKey``, 33 bytes)
  K1  exactly 33 bytes, the first 0x02 or 0x03 (04/05 and the hybrid 06/07
      belong to 65-byte keys, which ``PubKeySecp256k1`` cannot hold)
  K2  x = bytes 1..32 big-endian, x < p
  K3  x^3 + 7 is a square mod p (the point is on the curve); y is the root
      whose parity is the prefix's low bit

signature (``btcec.ParseDERSignature`` = ``parseSig(der=true)``)
  D1  at least 8 bytes
  D2  byte 0 is 0x30
  D3  byte 1, the sequence length L, is one byte (no long form) and L + 2
      does not pass the buffer's end.  The buffer is then CUT to L + 2 bytes:
      what follows the sequence is dropped, not refused (btcec's own test
      vector "trailing crap" is valid: Bitcoin's signatures carry a hash
      type there).  btcec adds L + 2 in a byte, so L = 254 or 255 wraps and
      its parser faults; refused here
  D4  byte 2 is 0x02; the length of r is at least 1 and leaves room for
      ``02 len s`` (rlen <= end - 4 - 3)
  D5  r has no sign bit on its first byte ("negative") and no leading zero
      that the next byte's top bit does not need ("excessively padded")
  D6  the byte after r is 0x02; the length of s is at least 1 and s ends
      exactly at the sequence's end
  D7  s: as D5
  D8  0 < r < n and 0 < s < n

VerifyBytes
  V1  s <= n/2 (``secp256k1halfN``): the high-s twin of a valid signature
      is refused
  V2  e = SHA-256(sign-bytes) as a 256-bit integer (Go's ``hashToInt``
      leaves a 32-byte hash whole for a 256-bit order)
  V3  w = s^-1 mod n, u1 = e w, u2 = r w, R = [u1]G + [u2]Q; refuse R at
      infinity; accept iff x(R) mod n = r

Departures of the program's ``crypto/secp256k1.der_decode_sig`` (what the
device path's host prologue parses with) from D1-D7, as found when this file
was written: it required L + 2 to equal the buffer's length (D3: trailing
bytes were refused, which btcec takes) and did not check D5/D7 at all (a
padded or sign-bit r or s was taken, which btcec refuses).  The same PR made
it follow the rules above; tests/bench/test_bench_oracle_secp256k1.py holds the two to
each other on every rule.

OpenSSL (the ``cryptography`` package) takes high-s and lax DER, so its
accept is NOT a subset of this one.  It is used only to confirm V3 for a
signature that K1-K3, D1-D8 and V1 have already admitted, re-encoded
strictly from the parsed (r, s); a signature it does not confirm is decided
by the exact arithmetic below.  Valid lanes, nearly all lanes, cost one
OpenSSL call.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence, Tuple

try:  # the container has it; the exact path below stands alone without it
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes as _hashes
    from cryptography.hazmat.primitives.asymmetric import ec as _ec
    from cryptography.hazmat.primitives.asymmetric import utils as _ecutils
except ImportError:  # pragma: no cover
    _ec = None

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8
HALF_N = N // 2

Affine = Optional[Tuple[int, int]]  # None is the point at infinity


# ---------------------------------------------------------------------------
# the curve y^2 = x^3 + 7 over GF(p), affine, by the textbook's formulas
# ---------------------------------------------------------------------------


def add(a: Affine, b: Affine) -> Affine:
    if a is None:
        return b
    if b is None:
        return a
    x1, y1 = a
    x2, y2 = b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, P - 2, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, P - 2, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


# Jacobian (X, Y, Z), x = X/Z^2, y = Y/Z^3, for the 256-step multiplication:
# one inversion at the end instead of one a step
def _jdouble(p):
    x, y, z = p
    if y == 0 or z == 0:
        return (0, 1, 0)
    yy = y * y % P
    s = 4 * x * yy % P
    m = 3 * x * x % P
    x3 = (m * m - 2 * s) % P
    return x3, (m * (s - x3) - 8 * yy * yy) % P, 2 * y * z % P


def _jadd_affine(p, q: Tuple[int, int]):
    x1, y1, z1 = p
    if z1 == 0:
        return q[0], q[1], 1
    zz = z1 * z1 % P
    u2 = q[0] * zz % P
    s2 = q[1] * zz * z1 % P
    if u2 == x1:
        if s2 != y1:
            return (0, 1, 0)
        return _jdouble(p)
    h = (u2 - x1) % P
    r = (s2 - y1) % P
    hh = h * h % P
    hhh = h * hh % P
    v = x1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    return x3, (r * (v - x3) - y1 * hhh) % P, h * z1 % P


def _to_affine(p) -> Affine:
    x, y, z = p
    if z == 0:
        return None
    zi = pow(z, P - 2, P)
    return x * zi * zi % P, y * zi * zi % P * zi % P


def mul(point: Affine, k: int) -> Affine:
    """[k]point by double-and-add from the top bit."""
    if point is None or k % N == 0:
        return None
    acc = (0, 1, 0)
    for bit in bin(k % N)[2:]:
        acc = _jdouble(acc)
        if bit == "1":
            acc = _jadd_affine(acc, point)
    return _to_affine(acc)


# [j * 16^i]G for the 64 nibbles of a scalar: a fixed-base multiplication is
# 64 additions (the signer makes 256 keys and 1,024 nonces a run)
_G_TABLE: List[List[Tuple[int, int]]] = []


def _g_table() -> List[List[Tuple[int, int]]]:
    if not _G_TABLE:
        base: Affine = (GX, GY)
        for _ in range(64):
            row, acc = [], None
            for _j in range(15):
                acc = add(acc, base)
                row.append(acc)
            _G_TABLE.append(row)
            base = add(acc, base)  # 16 * base
    return _G_TABLE


def mul_base(k: int) -> Affine:
    k %= N
    table = _g_table()
    acc = (0, 1, 0)
    for i in range(64):
        nib = (k >> (4 * i)) & 15
        if nib:
            acc = _jadd_affine(acc, table[i][nib - 1])
    return _to_affine(acc)


# ---------------------------------------------------------------------------
# parsing, by the rules in the module's docstring
# ---------------------------------------------------------------------------


def parse_pubkey(pub: bytes) -> Affine:
    if len(pub) != 33 or pub[0] not in (2, 3):  # K1
        return None
    x = int.from_bytes(pub[1:], "big")
    if x >= P:  # K2
        return None
    y2 = (x * x * x + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:  # K3
        return None
    if (y & 1) != (pub[0] & 1):
        y = P - y
    return x, y


def _padding_ok(b: bytes) -> bool:  # D5, D7
    if b[0] & 0x80:
        return False
    return not (len(b) > 1 and b[0] == 0 and not b[1] & 0x80)


def parse_der(sig: bytes) -> Optional[Tuple[int, int]]:
    if len(sig) < 8:  # D1
        return None
    if sig[0] != 0x30:  # D2
        return None
    end = sig[1] + 2
    if end > len(sig) or end > 255:  # D3
        return None
    sig = sig[:end]
    if sig[2] != 0x02:  # D4
        return None
    rlen = sig[3]
    if rlen < 1 or rlen > end - 4 - 3:
        return None
    rb = sig[4:4 + rlen]
    if not _padding_ok(rb):  # D5
        return None
    at = 4 + rlen
    if sig[at] != 0x02:  # D6
        return None
    slen = sig[at + 1]
    if slen < 1 or at + 2 + slen != end:
        return None
    sb = sig[at + 2:end]
    if not _padding_ok(sb):  # D7
        return None
    r, s = int.from_bytes(rb, "big"), int.from_bytes(sb, "big")
    if not (0 < r < N and 0 < s < N):  # D8
        return None
    return r, s


def _der_int(v: int) -> bytes:
    b = v.to_bytes((v.bit_length() + 7) // 8 or 1, "big")
    if b[0] & 0x80:
        b = b"\x00" + b
    return b"\x02" + bytes([len(b)]) + b


def encode_der(r: int, s: int) -> bytes:
    """The one strict encoding of (r, s)."""
    body = _der_int(r) + _der_int(s)
    return b"\x30" + bytes([len(body)]) + body


# ---------------------------------------------------------------------------
# verify
# ---------------------------------------------------------------------------


def verify_digest_exact(q: Tuple[int, int], e: int, r: int, s: int) -> bool:
    """V3 alone, on values already admitted."""
    w = pow(s, N - 2, N)
    point = add(mul_base(e * w % N), mul(q, r * w % N))
    return point is not None and point[0] % N == r


def _openssl_confirms(pub: bytes, digest: bytes, r: int, s: int) -> bool:
    if _ec is None:
        return False
    try:
        key = _ec.EllipticCurvePublicKey.from_encoded_point(_ec.SECP256K1(), pub)
        key.verify(encode_der(r, s), digest,
                   _ec.ECDSA(_ecutils.Prehashed(_hashes.SHA256())))
        return True
    except (InvalidSignature, ValueError):
        return False


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """``VerifyBytes(msg, sig)`` of the 33-byte key ``pub``: ``msg`` is the
    raw sign-bytes, hashed here (V2)."""
    q = parse_pubkey(pub)
    if q is None:
        return False
    parsed = parse_der(sig)
    if parsed is None:
        return False
    r, s = parsed
    if s > HALF_N:  # V1
        return False
    digest = hashlib.sha256(msg).digest()
    if _openssl_confirms(pub, digest, r, s):
        return True
    return verify_digest_exact(q, int.from_bytes(digest, "big"), r, s)


def verify_exact(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The same decision with no OpenSSL anywhere (the tests hold the two
    to each other)."""
    q = parse_pubkey(pub)
    parsed = parse_der(sig)
    if q is None or parsed is None or parsed[1] > HALF_N:
        return False
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    return verify_digest_exact(q, e, *parsed)


def verify_lanes(pubs: Sequence[bytes], msgs: Sequence[bytes],
                 sigs: Sequence[bytes]) -> list:
    return [verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]


# ---------------------------------------------------------------------------
# keys and signatures, functions of their arguments alone
# ---------------------------------------------------------------------------


def compress(point: Tuple[int, int]) -> bytes:
    return bytes([2 | (point[1] & 1)]) + point[0].to_bytes(32, "big")


def pubkey_of(d: int) -> bytes:
    if not 0 < d < N:
        raise ValueError("private scalar out of range")
    return compress(mul_base(d))


def sign(d: int, msg: bytes, k: int) -> bytes:
    """ECDSA over SHA-256(msg) with the nonce ``k`` the caller drew from its
    seeded generator; low-s; the strict DER encoding.  Raises where k gives
    r = 0 or s = 0 (the caller draws another)."""
    if not (0 < d < N and 0 < k < N):
        raise ValueError("scalar out of range")
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    r = mul_base(k)[0] % N
    s = pow(k, N - 2, N) * (e + r * d) % N
    if r == 0 or s == 0:
        raise ValueError("degenerate nonce")
    if s > HALF_N:
        s = N - s
    return encode_der(r, s)
