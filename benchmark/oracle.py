"""The benchmark's own ed25519 verifier: the plain reference every device
verdict is compared with.  It imports nothing of the program.

The accept set is the one the configuration states, that of
golang.org/x/crypto/ed25519.Verify as Tendermint v0.26.2 vendored it:
``sig[63] & 224 == 0`` is the only range check on ``s`` (so ``s + L`` passes
while it stays under 2**253), the public key's ``y`` is reduced mod p without
a canonicity check, and the recomputed ``R' = [s]B - [h]A`` is compared with
the signature's first 32 bytes byte for byte (cofactorless).

OpenSSL (the ``cryptography`` package) accepts a strict subset of that set,
so a signature it accepts is accepted; everything else is decided by the
slow exact arithmetic below.  Valid lanes, which are nearly all lanes, cost
one OpenSSL call.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence, Tuple

try:  # the container has it; the exact path below stands alone without it
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )
except ImportError:  # pragma: no cover
    Ed25519PublicKey = None

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

Point = Tuple[int, int, int, int]  # extended (X, Y, Z, T), x = X/Z, y = Y/Z
IDENTITY: Point = (0, 1, 1, 0)


def _add(p: Point, q: Point) -> Point:
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _mul(p: Point, k: int) -> Point:
    acc = IDENTITY
    while k:
        if k & 1:
            acc = _add(acc, p)
        p = _add(p, p)
        k >>= 1
    return acc


def _decompress(enc: bytes) -> Optional[Point]:
    """Go's ExtendedGroupElement.FromBytes: y mod p, sign bit picks x."""
    raw = int.from_bytes(enc, "little")
    sign = raw >> 255
    y = (raw & ((1 << 255) - 1)) % P
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    x = u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    if (v * x * x - u) % P:
        if (v * x * x + u) % P:
            return None
        x = x * SQRT_M1 % P
    if (x & 1) != sign:
        x = (P - x) % P
    return (x, y, 1, x * y % P)


def _compress(p: Point) -> bytes:
    x, y, z, _ = p
    zi = pow(z, P - 2, P)
    x, y = x * zi % P, y * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


_BY = 4 * pow(5, P - 2, P) % P
BASE = _decompress(_BY.to_bytes(32, "little"))


def verify_exact(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(pub) != 32 or len(sig) != 64 or sig[63] & 224:
        return False
    a = _decompress(pub)
    if a is None:
        return False
    neg_a = ((P - a[0]) % P, a[1], 1, (P - a[3]) % P)
    h = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    s = int.from_bytes(sig[32:], "little")
    return _compress(_add(_mul(neg_a, h), _mul(BASE, s))) == sig[:32]


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if Ed25519PublicKey is not None and len(pub) == 32 and len(sig) == 64:
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
            return True
        except (InvalidSignature, ValueError):
            pass
    return verify_exact(pub, msg, sig)


def verify_lanes(pubs: Sequence[bytes], msgs: Sequence[bytes],
                 sigs: Sequence[bytes]) -> list:
    return [verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]


def sign_identity_key(r: int) -> Tuple[bytes, bytes]:
    """(public key, signature) that the Go accept set takes for ANY message:
    the key is the neutral element under its non-canonical encoding
    ``y = p + 1``, so ``R' = [s]B`` and ``(R, s) = ([r]B, r)`` verifies.
    Strict verifiers refuse the encoding; the configuration's does not."""
    pub = (P + 1).to_bytes(32, "little")
    return pub, _compress(_mul(BASE, r % L)) + (r % L).to_bytes(32, "little")
