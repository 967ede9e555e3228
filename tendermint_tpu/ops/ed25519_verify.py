"""Batched Ed25519 verification as a JAX kernel — the data-plane moat.

The reference verifies one signature at a time on the host CPU
(`/root/reference/types/validator_set.go:281-296` serial loop over precommits;
single-verify at `/root/reference/crypto/ed25519/ed25519.go:151`).  Here the
whole batch — every precommit of a height, or a whole fast-sync window of
heights — is verified in ONE device dispatch.

TPU-first design, not a port:

  * Field arithmetic over GF(2^255-19) in **20 radix-2^13 uint32 limbs** so every
    partial product and every 20-term partial-product column fits a 32-bit lane
    (TPU has no native 64-bit integer multiply; the VPU is 32-bit).  All limb
    ops are elementwise over a ``(batch, 20)`` tensor → the batch axis
    vectorizes across VPU lanes and shards across the device mesh.
  * One interleaved double-scalar ladder computes ``[s]B + [h](-A)`` with
    *complete* extended-coordinate formulas (add-2008-hwcd-3 / dbl-2008-hwcd),
    so adversarial low-order points need no special-casing and there is no
    data-dependent control flow — the whole ladder is a single
    ``lax.fori_loop`` that XLA compiles once.
  * Accept/reject is bit-exact with the Go fork of golang.org/x/crypto/ed25519
    (see tendermint_tpu/crypto/ed25519.py for the quirk list): only the top 3
    bits of s are range-checked, non-canonical A/R encodings are accepted, and
    the final check compares the canonical encoding of R' against sig[:32]
    byte-for-byte (done here in limb space against the *raw* R bytes).
  * Host prologue (cheap, latency-hidden): SHA-512 of the ~110-byte sign-bytes
    via hashlib, point decompression of pubkeys with an LRU cache (validator
    keys repeat across every height of a sync window), bit-unpacking of
    scalars.  Device does all the exponent work (~6.5k field muls/signature).

Sharding: pass ``mesh=`` to shard the batch axis over ``mesh.axis_names[0]``
with jax.sharding.NamedSharding — the kernel is embarrassingly data-parallel,
collectives only appear in the commit-tally layer above
(tendermint_tpu/parallel/).
"""

from __future__ import annotations

import hashlib
import sys
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ed
from tendermint_tpu.ops import fe_common as _fc
from tendermint_tpu.ops.dispatch import call_jit

P = _ed.P
L = _ed.L
D2 = _ed.D2

NLIMB = 20
BITS = 13
MASK = (1 << BITS) - 1  # 8191
NBITS = 253  # scalars s, h < 2^253

# fold factor: 2^260 ≡ 19·2^5 (mod p)
FOLD = 19 << 5  # 608


def int_to_limbs(x: int) -> np.ndarray:
    """Python int -> 20 radix-2^13 uint32 limbs (little-endian limb order)."""
    return np.array([(x >> (BITS * i)) & MASK for i in range(NLIMB)], dtype=np.uint32)


def limbs_to_int(limbs) -> int:
    return sum(int(v) << (BITS * i) for i, v in enumerate(np.asarray(limbs)))


# K ≡ 0 (mod p) with every limb large enough that (a + K - b) never underflows
# for carried a, b:  K_i = 4·8191 = 32764 except K_0 = 32764 - 2428
# (4·(2^260-1) ≡ 2428 mod p).
_K_SUB = np.full((NLIMB,), 4 * MASK, dtype=np.uint32)
_K_SUB[0] = 4 * MASK - 2428
assert limbs_to_int(_K_SUB) % P == 0

_D2_LIMBS = int_to_limbs(D2)
_BX_LIMBS = int_to_limbs(_ed.B_AFFINE)
_BY_LIMBS = int_to_limbs(_ed._BY)
_BT_LIMBS = int_to_limbs(_ed.B_AFFINE * _ed._BY % P)

# bits of p-2 (MSB first) for Fermat inversion
_P2_BITS = np.array(
    [(P - 2) >> i & 1 for i in reversed(range(255))], dtype=np.uint32
)


# ---------------------------------------------------------------------------
# Field element ops.  A "carried" fe has every limb <= ~8800, so 20-term
# partial-product columns stay < 2^31.  All fns keep uint32 dtype.
# ---------------------------------------------------------------------------


def fe_carry(x: jnp.ndarray, rounds: int = 4) -> jnp.ndarray:
    """Parallel carry propagation with the 2^260 ≡ 608 wraparound fold."""
    for _ in range(rounds):
        c = x >> BITS
        x = (x & MASK).at[..., 1:].add(c[..., :-1]).at[..., 0].add(c[..., -1] * FOLD)
    return x


def fe_add(a, b):
    return fe_carry(a + b, rounds=2)


def fe_sub(a, b):
    return fe_carry(a + _K_SUB - b, rounds=2)


# Carry schedule for the ladder's point ops: "eager" is the full per-op
# ripple below; "lazy" defers carries per fe_common.derive_carry_plan (one
# reduction per point op). Set only via _compiled_kernel's trace-time wrapper
# (fe_common.trace_with_modes) — the jit cache is keyed on it, so each mode
# traces its own kernel; module-level fe_mul/fe_add/fe_sub are always the
# eager ops regardless.
_CARRY_MODE = "eager"

_PLAN = _fc.derive_carry_plan("ed25519")
# wide zero dominating the lazy class-D operands (plan-derived analog of
# _K_SUB, which dominates carried eager values only)
_KD_SUB = np.asarray(_PLAN.kd, dtype=np.uint32)


def fe_mul(a, b):
    """Schoolbook product via 20 shifted multiply-accumulates, then reduce.

    Bounds (audited; regression-pinned in tests/test_ops_ed25519.py and
    recomputed mechanically by fe_common.bound_* in tests/test_fe_common.py):
    carried inputs have limbs ≤ ~8800 (fe_sub's limb-0 wraparound term is
    the max — see fe_carry), and fe_mul is proven well past that (stressed
    to 13000). The 41st product row is REQUIRED: carries ripple one row
    per round, so with a 40-limb buffer the carry out of row 39 — reachable
    at the margin, e.g. top limbs 8192·8192 = 2^26 — would be silently
    dropped (the same mechanism as the secp bug fixed in
    secp256k1_verify.fe_mul). Row 40 folds as 2^520 ≡ 608² (mod p)."""
    prod = _mul_cols(a, b, 2 * NLIMB + 1)
    # local carries inside the 41-limb product (no wrap needed: value < 2^520)
    for _ in range(3):
        c = prod >> BITS
        prod = (prod & MASK).at[..., 1:].add(c[..., :-1])
    # fold limbs 20..39 down (2^(260+13j) ≡ 608·2^13j), row 40 as 608²
    lo = prod[..., :NLIMB] + prod[..., NLIMB : 2 * NLIMB] * FOLD
    lo = lo.at[..., 0].add(prod[..., 2 * NLIMB] * (FOLD * FOLD))
    return fe_carry(lo, rounds=4)


def fe_sq(a):
    return fe_mul(a, a)


# --- deferred-carry (lazy) ops: batch-leading twins of the Pallas row ops,
# used by the ladder's point ops when _CARRY_MODE == "lazy".  Operand-class
# bounds are certified at import by fe_common.derive_carry_plan.


def _mul_cols(a, b, out_cols):
    """Schoolbook product columns: 20 shifted multiply-accumulates."""
    shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    prod = jnp.zeros(shape + (out_cols,), dtype=jnp.uint32)
    for i in range(NLIMB):
        prod = prod.at[..., i : i + NLIMB].add(a[..., i : i + 1] * b)
    return prod


def fe_mul_f(a, b):
    """Full lazy multiply: fused fold, plan.mulf_wide wide rounds, fixups —
    output lands in the certified class C."""
    lo = _fc.ed_fold_fused_batch(_mul_cols(a, b, 2 * NLIMB))
    for _ in range(_PLAN.mulf_wide):
        lo = _fc.wide_carry_batch(lo, _fc.ED_WRAP)
    return _fc.fix_batch(lo, _PLAN.mulf_fix)


def fe_mul_l(a, b):
    """Lazy multiply with a single wide round: output stays in class D."""
    lo = _fc.ed_fold_fused_batch(_mul_cols(a, b, 2 * NLIMB))
    lo = _fc.wide_carry_batch(lo, _fc.ED_WRAP)
    return _fc.fix_batch(lo, _PLAN.mull_fix)


def fe_norm1(raw):
    """One wide round + fixups: raw limb sum -> class C."""
    return _fc.fix_batch(_fc.wide_carry_batch(raw, _fc.ED_WRAP), _PLAN.norm_fix)


def fe_add_l(a, b):
    return fe_norm1(a + b)


def fe_sub_l(a, b):
    # always against the class-D wide zero: dominates class-C operands too
    return fe_norm1(a + _KD_SUB - b)


def fe_mul4_f(*pairs):
    """Four mulF schedules stacked into ONE wide round (PERF.md carry-tail
    vectorization): the four output products of a point op share the exact
    same fold/wide/fixup schedule, so stacking them on a new leading axis
    runs one (4, ..., 20) reduction instead of four — bit-identical per
    slice (every fe op is elementwise over leading axes)."""
    a = jnp.stack([p[0] for p in pairs])
    b = jnp.stack([p[1] for p in pairs])
    out = fe_mul_f(a, b)
    return tuple(out[k] for k in range(len(pairs)))


def fe_inv(z):
    """z^(p-2) by square-and-multiply over the fixed bit pattern of p-2."""

    def body(acc, bit):
        acc = fe_sq(acc)
        acc = jnp.where(bit.astype(bool), fe_mul(acc, z), acc)
        return acc, None

    one = jnp.zeros_like(z).at[..., 0].set(1)
    acc, _ = lax.scan(body, one, jnp.asarray(_P2_BITS))
    return acc


def fe_canonical(x):
    """Fully reduce a carried fe into [0, p), exact limbs <= MASK."""

    def seq_carry(v):
        for i in range(NLIMB - 1):
            c = v[..., i] >> BITS
            v = v.at[..., i].set(v[..., i] & MASK).at[..., i + 1].add(c)
        return v

    def fold_top(v):
        # bits >= 255 live in limb 19 at offset 8
        q = v[..., NLIMB - 1] >> 8
        v = v.at[..., NLIMB - 1].set(v[..., NLIMB - 1] & 0xFF)
        return v.at[..., 0].add(q * 19)

    x = fe_carry(x, rounds=2)
    for _ in range(3):
        x = fold_top(seq_carry(x))
    x = seq_carry(x)  # now x < 2^255
    # conditional subtract p:  t = x + 19;  if t >= 2^255 then x - p = t - 2^255
    t = seq_carry(x.at[..., 0].add(19))
    ge = (t[..., NLIMB - 1] >> 8) > 0
    t = t.at[..., NLIMB - 1].set(t[..., NLIMB - 1] & 0xFF)
    return jnp.where(ge[..., None], t, x)


# ---------------------------------------------------------------------------
# Point ops: extended coords (X, Y, Z, T), x=X/Z, y=Y/Z, T=XY/Z.
# Complete for a=-1, d non-square — valid for ALL curve points.
# ---------------------------------------------------------------------------


def pt_add(p, q, d2):
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    if _CARRY_MODE == "lazy":
        # one full reduction per point op: operand products ride as class D,
        # E/F/G/H carry once, only the four output muls run the full mulF
        # schedule.  The inner T1*d2 must be mulF — a class-D operand would
        # overflow the product columns.
        A = fe_mul_l(fe_sub_l(Y1, X1), fe_sub_l(Y2, X2))
        B = fe_mul_l(fe_add_l(Y1, X1), fe_add_l(Y2, X2))
        C = fe_mul_l(fe_mul_f(T1, d2), T2)
        Dv = fe_mul_l(Z1 + Z1, Z2)
        E = fe_sub_l(B, A)
        F = fe_sub_l(Dv, C)
        G = fe_add_l(Dv, C)
        H = fe_add_l(B, A)
        return fe_mul4_f((E, F), (G, H), (F, G), (E, H))
    A = fe_mul(fe_sub(Y1, X1), fe_sub(Y2, X2))
    B = fe_mul(fe_add(Y1, X1), fe_add(Y2, X2))
    C = fe_mul(fe_mul(T1, d2), T2)
    Dv = fe_mul(fe_add(Z1, Z1), Z2)
    E = fe_sub(B, A)
    F = fe_sub(Dv, C)
    G = fe_add(Dv, C)
    H = fe_add(B, A)
    return fe_mul(E, F), fe_mul(G, H), fe_mul(F, G), fe_mul(E, H)


def pt_double(p):
    X1, Y1, Z1, _ = p
    if _CARRY_MODE == "lazy":
        A = fe_mul_l(X1, X1)
        B = fe_mul_l(Y1, Y1)
        ZZ = fe_mul_l(Z1, Z1)
        C = ZZ + ZZ
        H = fe_add_l(A, B)
        xy = fe_add_l(X1, Y1)
        E = fe_sub_l(H, fe_mul_l(xy, xy))
        G = fe_sub_l(A, B)
        F = fe_add_l(C, G)
        return fe_mul4_f((E, F), (G, H), (F, G), (E, H))
    A = fe_sq(X1)
    B = fe_sq(Y1)
    ZZ = fe_sq(Z1)
    C = fe_add(ZZ, ZZ)
    H = fe_add(A, B)
    xy = fe_add(X1, Y1)
    E = fe_sub(H, fe_sq(xy))
    G = fe_sub(A, B)
    F = fe_add(C, G)
    return fe_mul(E, F), fe_mul(G, H), fe_mul(F, G), fe_mul(E, H)


def pt_select(cond, p, q):
    """cond (batch,) bool: p where true else q, across all 4 coords."""
    c = cond[..., None]
    return tuple(jnp.where(c, a, b) for a, b in zip(p, q))


# ---------------------------------------------------------------------------
# The verify kernel
# ---------------------------------------------------------------------------


def _get_bit(words: jnp.ndarray, i) -> jnp.ndarray:
    """Bit i (0 = LSB) of little-endian packed (..., 8) uint32 words."""
    w = lax.dynamic_slice_in_dim(words, i // 32, 1, axis=-1)[..., 0]
    return (w >> (i % 32).astype(jnp.uint32)) & jnp.uint32(1)


def _verify_kernel(neg_ax, ay, s_words, h_words, r_limbs, r_sign):
    """Device side: R' = [s]B + [h](-A); compare enc(R') with raw R bytes.

    All inputs share an arbitrary leading batch shape (1-D for flat batches,
    (heights, validators) for sharded commit windows):
      neg_ax, ay : (..., 20) limbs of -A affine (x negated mod p)
      s_words, h_words : (..., 8) uint32 LE bit-packed scalars
      r_limbs : (..., 20) raw (unreduced) 255-bit y of sig[:32]
      r_sign  : (...)   sign bit of sig[:32]
    Returns (...) bool.
    """
    batch = neg_ax.shape[:-1]
    one = jnp.zeros(batch + (NLIMB,), jnp.uint32).at[..., 0].set(1)
    zero = jnp.zeros(batch + (NLIMB,), jnp.uint32)
    d2 = jnp.asarray(_D2_LIMBS)

    # the T coordinate must land in the lazy class C when the ladder defers
    # carries (eager-carried limbs can exceed it — limb 0 tops at ~11231)
    t_mul = fe_mul_f if _CARRY_MODE == "lazy" else fe_mul
    neg_a = (neg_ax, ay, one, t_mul(neg_ax, ay))
    b_pt = (
        jnp.broadcast_to(jnp.asarray(_BX_LIMBS), batch + (NLIMB,)),
        jnp.broadcast_to(jnp.asarray(_BY_LIMBS), batch + (NLIMB,)),
        one,
        jnp.broadcast_to(jnp.asarray(_BT_LIMBS), batch + (NLIMB,)),
    )

    def body(t, acc):
        i = NBITS - 1 - t  # MSB -> LSB
        acc = pt_double(acc)
        with_b = pt_add(acc, b_pt, d2)
        acc = pt_select(_get_bit(s_words, i).astype(bool), with_b, acc)
        with_a = pt_add(acc, neg_a, d2)
        acc = pt_select(_get_bit(h_words, i).astype(bool), with_a, acc)
        return acc

    ident = (zero, one, one, zero)
    X, Y, Z, _ = lax.fori_loop(0, NBITS, body, ident)

    zinv = fe_inv(Z)
    x = fe_canonical(fe_mul(X, zinv))
    y = fe_canonical(fe_mul(Y, zinv))
    sign = x[..., 0] & 1
    # byte-exact compare: canonical enc(R') vs raw sig[:32] (limbs + sign bit)
    return jnp.all(y == r_limbs, axis=-1) & (sign == r_sign.astype(jnp.uint32))


_kernel_cache = {}


def _compiled_kernel(batch: int, mesh=None, carry_mode: str = "eager"):
    # Mesh hashes by devices+axis_names — safe cache key (id() could be reused
    # by a new Mesh after gc and serve a stale sharding)
    key = (batch, mesh, carry_mode)
    fn = _kernel_cache.get(key)
    if fn is None:
        kernel = _fc.trace_with_modes(
            sys.modules[__name__], _verify_kernel, carry_mode
        )
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            data = NamedSharding(mesh, PS(mesh.axis_names[0]))
            fn = jax.jit(kernel, in_shardings=(data,) * 6, out_shardings=data)
        else:
            fn = jax.jit(kernel)
        _kernel_cache[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Host prologue: parse/hash/decompress/pack, then one device dispatch.
# ---------------------------------------------------------------------------

_decompress_cache: dict = {}
_DECOMPRESS_CACHE_MAX = 1 << 16


def _decompress_neg_cached(pub: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(-x, y) limb arrays for pubkey A, or None if A fails decompression.
    Validator keys repeat across heights — cache pays for itself immediately."""
    hit = _decompress_cache.get(pub, False)
    if hit is not False:
        return hit
    xy = _ed._decompress_xy(pub)
    if xy is None:
        out = None
    else:
        x, y = xy
        out = (int_to_limbs((P - x) % P), int_to_limbs(y))
    if len(_decompress_cache) >= _DECOMPRESS_CACHE_MAX:
        _decompress_cache.clear()
    _decompress_cache[pub] = out
    return out


def _bytes_to_raw_limbs(r32: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 LE -> (N, 20) raw 13-bit limbs of the low 255 bits."""
    bits = np.unpackbits(r32, axis=1, bitorder="little")  # (N, 256)
    bits[:, 255] = 0
    bits = np.pad(bits, ((0, 0), (0, NLIMB * BITS - 256)))  # 260 bits
    limbs = np.zeros((r32.shape[0], NLIMB), dtype=np.uint32)
    w = (1 << np.arange(BITS, dtype=np.uint32))
    for i in range(NLIMB):
        limbs[:, i] = bits[:, BITS * i : BITS * (i + 1)].astype(np.uint32) @ w
    return limbs


def _bucket(n: int) -> int:
    """Pad size: powers of two up to 4096, then multiples of 4096 (bounds
    recompiles while capping pad waste at large batch)."""
    b = 64
    while b < n and b < 4096:
        b *= 2
    if n <= b:
        return b
    return ((n + 4095) // 4096) * 4096


def host_prologue(
    pubs: np.ndarray, msgs: Sequence[bytes], sigs: np.ndarray
) -> Tuple[np.ndarray, ...]:
    """Flat host-side packing shared by verify_batch and the commit-window
    packer: decompress+negate pubkeys (cached), SHA-512 h mod L, bit-pack
    scalars, raw-limb R.  Returns
    (neg_ax, ay, s_words, h_words, r_limbs, r_sign, valid) with batch leading.
    """
    pubs = np.ascontiguousarray(pubs, dtype=np.uint8)
    sigs = np.ascontiguousarray(sigs, dtype=np.uint8)
    n = pubs.shape[0]

    valid = np.ones((n,), dtype=bool)
    # s range check: reject if top 3 bits set (Go checks only sig[63]&224)
    if n:
        valid &= (sigs[:, 63] & 224) == 0

    neg_ax = np.zeros((n, NLIMB), dtype=np.uint32)
    ay = np.zeros((n, NLIMB), dtype=np.uint32)
    h_bytes = np.zeros((n, 32), dtype=np.uint8)
    for i in range(n):
        if not valid[i]:
            continue
        pk = pubs[i].tobytes()
        dec = _decompress_neg_cached(pk)
        if dec is None:
            valid[i] = False
            continue
        neg_ax[i] = dec[0]
        ay[i] = dec[1]
        sig = sigs[i]
        h = (
            int.from_bytes(
                hashlib.sha512(sig[:32].tobytes() + pk + bytes(msgs[i])).digest(),
                "little",
            )
            % L
        )
        h_bytes[i] = np.frombuffer(h.to_bytes(32, "little"), dtype=np.uint8)

    s_words = np.ascontiguousarray(sigs[:, 32:]).view(np.dtype("<u4")).astype(np.uint32)
    h_words = h_bytes.view(np.dtype("<u4")).astype(np.uint32)
    # zero out scalars of invalid rows (keeps device work well-defined)
    s_words[~valid] = 0
    h_words[~valid] = 0
    r_limbs = _bytes_to_raw_limbs(np.ascontiguousarray(sigs[:, :32]))
    r_sign = (sigs[:, 31] >> 7).astype(np.uint32)
    return neg_ax, ay, s_words, h_words, r_limbs, r_sign, valid


def verify_batch(
    pubs: np.ndarray,
    msgs: Sequence[bytes],
    sigs: np.ndarray,
    mesh=None,
    carry_mode: str = "lazy",
) -> np.ndarray:
    """Batched Go-exact ed25519 verify.

    pubs (N, 32) uint8, msgs list of N byte strings, sigs (N, 64) uint8.
    Returns (N,) bool.  One device dispatch per call (padded to a size bucket
    to bound recompiles).  carry_mode "lazy" (default) defers limb carries
    between the ladder's point ops, "eager" keeps the full per-op ripple;
    both are bit-exact.
    """
    carry_mode = _fc.normalize_carry_mode(carry_mode)
    n = len(pubs)
    if n == 0:
        return np.zeros((0,), dtype=bool)
    neg_ax, ay, s_words, h_words, r_limbs, r_sign, valid = host_prologue(
        pubs, msgs, sigs
    )

    b = _bucket(n)
    if mesh is not None:
        nd = int(mesh.devices.size)
        if b % nd:
            b = ((b + nd - 1) // nd) * nd

    def pad(a):
        if a.shape[0] == b:
            return a
        return np.concatenate(
            [a, np.zeros((b - a.shape[0],) + a.shape[1:], dtype=a.dtype)], axis=0
        )

    args = [pad(a) for a in (neg_ax, ay, s_words, h_words, r_limbs, r_sign)]
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as PS

        data = NamedSharding(mesh, PS(mesh.axis_names[0]))
        args = [jax.device_put(a, data) for a in args]
    ok = np.asarray(
        call_jit(_compiled_kernel(b, mesh, carry_mode), *args)
    )[:n]
    return ok & valid


def rlc_seed(pubs: np.ndarray, sigs: np.ndarray) -> int:
    """Deterministic RLC coefficient seed: SHA-256 over the batch content.
    The coefficients must only be unpredictable *before* the signatures are
    fixed (Fiat–Shamir style), so hashing the batch keeps the 2^-128
    soundness while making audit/replay runs reproduce the same verdict
    path bit-for-bit."""
    dig = hashlib.sha256(
        b"ed25519-rlc" + pubs.tobytes() + sigs.tobytes()
    ).digest()
    return int.from_bytes(dig[:8], "little")


def rlc_verify_batch(
    pubs: np.ndarray,
    msgs: Sequence[bytes],
    sigs: np.ndarray,
    carry_mode: str = "lazy",
    seed: Optional[int] = None,
) -> np.ndarray:
    """Batched Go-exact verify via ONE device multi-scalar multiplication.

    Same contract as ``verify_batch`` (per-row verdicts, every Go edge
    honored) at a fraction of the curve work: the whole batch is accepted
    by a single random-linear-combination MSM (ops/ed25519_msm); a rejected
    batch localizes through host chunk RLCs and re-runs only the dirty rows
    on the exact per-row ladder above.  ``seed`` pins the RLC coefficients
    (default: derived from the batch content — deterministic replay)."""
    from tendermint_tpu.ops import ed25519_msm as _msm

    carry_mode = _fc.normalize_carry_mode(carry_mode)
    pubs = np.ascontiguousarray(pubs, dtype=np.uint8)
    sigs = np.ascontiguousarray(sigs, dtype=np.uint8)
    n = pubs.shape[0]
    if n == 0:
        return np.zeros((0,), dtype=bool)
    items = [(pubs[i].tobytes(), bytes(msgs[i]), sigs[i].tobytes())
             for i in range(n)]
    parsed, out = _ed._parse_batch(items)
    if seed is None:
        seed = rlc_seed(pubs, sigs)

    def ladder_fn(idx: List[int]) -> np.ndarray:
        return verify_batch(
            pubs[idx], [msgs[i] for i in idx], sigs[idx],
            carry_mode=carry_mode,
        )

    _msm.rlc_resolve(parsed, out, ladder_fn, seed=seed,
                     carry_mode=carry_mode)
    return np.asarray(out, dtype=bool)
