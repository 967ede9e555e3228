"""Fused Pallas TPU kernel for batched Ed25519 verification.

This is the performance path behind the BatchVerifier boundary (the XLA kernel
in ops/ed25519_verify.py remains the portable fallback and the multi-chip
shard_map path). The reference verifies serially on host
(`/root/reference/types/validator_set.go:281-296`,
`/root/reference/crypto/ed25519/ed25519.go:151`); here everything after point
decompression — SHA-512 of the sign-bytes, reduction mod L, scalar digit
extraction, the double-scalar ladder, and the canonical-encoding compare —
runs on device in one jit, with the ladder as a single VMEM-resident Pallas
kernel (the XLA version materializes every field-op intermediate to HBM).

Algorithm (per 128-lane block, batch on lanes, limbs on sublanes):

  * Field arithmetic over GF(2^255-19), 20 radix-2^13 uint32 limbs in a
    (20, B) layout — shared with the secp256k1 kernel via ops/fe_common.py
    (the VPU schoolbook multiplier).  Overflow bounds are not hand-stated
    here: fe_common's bound_* propagators recompute the closed set
    mechanically, and tests/test_fe_common.py asserts closure (carried
    limbs <= 13000) and that no intermediate reaches 2^32.
  * Double-scalar mult R' = [s]B + [h](-A) via 4-bit windowed Straus, in
    two forms of one algorithm (``ladder_math``), the launch's own to tell
    apart by what it was handed:
      - BUILT (no identity came with the keys: fast-sync windows, a multisig
        set's sub-keys, the vote set, ``rlc_verify_batch``): 64 MSB-first
        windows sharing 252 doublings; per window one mixed add from a
        constant niels table [0..15]B (affine, identity at digit 0) and one
        add from a per-signature table [0..15](-A) built in the lane with
        7 doublings + 7 adds.  263 doublings a signature.
      - RESIDENT (the caller handed down ``crypto/batch.ValsetRows``: a
        ``verify_commit`` of an all-ed25519 set): the lanes' WINDOW TABLES,
        [0..15] * 16^(16 (3 - k)) * (-A) at K = 4 offsets of the scalar,
        cached-niels, built ONCE A MEMBERSHIP on the device
        (``window_tables_math``, ``_ValsetTable``) and gathered by slot;
        digit 16 k + w looks up table k, so 16 rounds of 4 doublings, 4 mixed
        adds from the constant tables [0..15] * 16^(16 (3 - k)) * B and 4
        cached adds.  64 doublings a signature, 128 additions, no build;
        24.6 KB of HBM a member.
    Same group element either way.  Complete extended formulas throughout
    (adversarial low-order/identity points need no special case).
  * Accept iff canonical-enc(R') equals sig[:32] byte-for-byte — the exact
    Go accept set (see crypto/ed25519.py quirk list): s range-checked only
    on the top 3 bits (host), A decompressed with Go's non-canonical
    acceptance (host, cached per validator set), raw R bytes compared
    without reducing them (non-canonical R never matches a canonical
    encoding, matching Go).

The XLA prologue (same jit, upstream of pallas_call) emulates 64-bit SHA-512
on uint32 pairs, Barrett-reduces the 512-bit digest mod L in radix-2^13, and
unpacks scalar digits — so the host contribution is one cached decompression
lookup plus byte packing.
"""

from __future__ import annotations

import threading
from functools import lru_cache, partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.crypto import ed25519 as _ed
from tendermint_tpu.crypto.batch import ValsetRows, valset_key as _valset_key
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.ops import ed25519_verify as _xla
from tendermint_tpu.ops import fe_common as _fc
from tendermint_tpu.ops.dispatch import call_jit

P = _ed.P
L_ORDER = _ed.L
NLIMB = 20
BITS = 13
MASK = (1 << BITS) - 1
FOLD = _fc.ED_FOLD  # 2^260 = 608 (mod p)
LANES = 128  # batch lanes per pallas grid block
NWIN = 64  # 4-bit windows covering s, h < 2^256

_K_SUB = _xla._K_SUB  # 4p-aligned constant for borrow-free subtraction
_D2_LIMBS = _xla._D2_LIMBS

int_to_limbs = _xla.int_to_limbs

# Field ops live in ops/fe_common.py (one copy serves both curves); these
# module-level names keep the original surface.  Namespaces are built on
# demand per carry mode — the lazy one runs derive_carry_plan's chain
# certification on first use.
_FE = {"eager": _fc.make_fe("ed25519")}
_FE_EAGER = _FE["eager"]


def _get_fe(carry_mode: str = "eager"):
    if carry_mode not in _FE:
        _FE[carry_mode] = _fc.make_fe("ed25519", carry_mode=carry_mode)
    return _FE[carry_mode]

_shift_rows_down = _fc.shift_rows_down
fe_carry1 = _fc.ed_fe_carry1
fe_add = _fc.ed_fe_add
fe_sub = _fc.ed_fe_sub
fe_mul = _fc.ed_fe_mul
fe_sq = _fc.ed_fe_sq
fe_inv = _fc.ed_fe_inv


# ---------------------------------------------------------------------------
# Point ops — extended coordinates, complete formulas (all in (20, B) limbs)
# ---------------------------------------------------------------------------


def pt_add(p, q, d2, ksub, fe=_FE_EAGER, kd=None):
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    if fe.carry_mode == "lazy":
        # One full reduction per point op: the four operand products stay in
        # the deferred class D (mul_lazy), E/F/G/H carry once (against kd —
        # the wide zero sized for D), and only the four output muls run the
        # full mulF schedule.  The inner T1*d2 must be mulF: a class-D
        # operand would overflow the product columns.
        A = fe.mul_lazy(fe.sub(Y1, X1, ksub), fe.sub(Y2, X2, ksub))
        B = fe.mul_lazy(fe.add(Y1, X1), fe.add(Y2, X2))
        C = fe.mul_lazy(fe.mul(T1, d2), T2)
        Dv = fe.mul_lazy(fe.add_raw(Z1, Z1), Z2)
        E = fe.sub(B, A, kd)
        F = fe.sub(Dv, C, kd)
        G = fe.add(Dv, C)
        H = fe.add(B, A)
        return fe.mul4(((E, F), (G, H), (F, G), (E, H)))
    A = fe.mul(fe.sub(Y1, X1, ksub), fe.sub(Y2, X2, ksub))
    B = fe.mul(fe.add(Y1, X1), fe.add(Y2, X2))
    C = fe.mul(fe.mul(T1, d2), T2)
    Dv = fe.mul(fe.add(Z1, Z1), Z2)
    E = fe.sub(B, A, ksub)
    F = fe.sub(Dv, C, ksub)
    G = fe.add(Dv, C)
    H = fe.add(B, A)
    return fe.mul(E, F), fe.mul(G, H), fe.mul(F, G), fe.mul(E, H)


def pt_madd(p, ypx, ymx, t2d, ksub, fe=_FE_EAGER, kd=None):
    """Mixed add with a precomputed niels point (y+x, y-x, 2dxy), Z=1.
    Digit 0 maps to (1, 1, 0) and yields p unchanged (scaled) — identity-safe."""
    X1, Y1, Z1, T1 = p
    if fe.carry_mode == "lazy":
        A = fe.mul_lazy(fe.sub(Y1, X1, ksub), ymx)
        B = fe.mul_lazy(fe.add_raw(Y1, X1), ypx)
        C = fe.mul_lazy(T1, t2d)
        Dv = fe.add_raw(Z1, Z1)
        E = fe.sub(B, A, kd)
        F = fe.sub(Dv, C, kd)
        G = fe.add(Dv, C)
        H = fe.add(B, A)
        return fe.mul4(((E, F), (G, H), (F, G), (E, H)))
    A = fe.mul(fe.sub(Y1, X1, ksub), ymx)
    B = fe.mul(fe.add(Y1, X1), ypx)
    C = fe.mul(T1, t2d)
    Dv = fe.add(Z1, Z1)
    E = fe.sub(B, A, ksub)
    F = fe.sub(Dv, C, ksub)
    G = fe.add(Dv, C)
    H = fe.add(B, A)
    return fe.mul(E, F), fe.mul(G, H), fe.mul(F, G), fe.mul(E, H)


def pt_add_cached(p, c, ksub, kd, fe):
    """Lazy-only add against a cached-niels table entry (y+x, y-x, Z, 2dxy·T
    pre-scaled): the pt_madd shape plus a projective Z2, so the per-window
    table add carries once instead of the nine times the extended formula
    spent."""
    X1, Y1, Z1, T1 = p
    ypx2, ymx2, Z2, t2d2 = c
    A = fe.mul_lazy(fe.sub(Y1, X1, ksub), ymx2)
    B = fe.mul_lazy(fe.add_raw(Y1, X1), ypx2)
    C = fe.mul_lazy(T1, t2d2)
    Dv = fe.mul_lazy(fe.add_raw(Z1, Z1), Z2)
    E = fe.sub(B, A, kd)
    F = fe.sub(Dv, C, kd)
    G = fe.add(Dv, C)
    H = fe.add(B, A)
    return fe.mul4(((E, F), (G, H), (F, G), (E, H)))


def pt_to_cached(p, d2, ksub, fe):
    """Extended -> cached-niels (y+x, y-x, Z, 2d·T); identity-safe
    ((0,1,1,0) -> (1,1,1,0))."""
    X, Y, Z, T = p
    return fe.add(Y, X), fe.sub(Y, X, ksub), Z, fe.mul(T, d2)


def pt_double(p, ksub, fe=_FE_EAGER, kd=None):
    X1, Y1, Z1, _ = p
    if fe.carry_mode == "lazy":
        A = fe.mul_lazy(X1, X1)
        B = fe.mul_lazy(Y1, Y1)
        ZZ = fe.mul_lazy(Z1, Z1)
        C = fe.add_raw(ZZ, ZZ)
        H = fe.add(A, B)
        xy = fe.add(X1, Y1)
        E = fe.sub(H, fe.mul_lazy(xy, xy), kd)
        G = fe.sub(A, B, kd)
        F = fe.add(C, G)
        return fe.mul4(((E, F), (G, H), (F, G), (E, H)))
    A = fe.sq(X1)
    B = fe.sq(Y1)
    ZZ = fe.sq(Z1)
    C = fe.add(ZZ, ZZ)
    H = fe.add(A, B)
    xy = fe.add(X1, Y1)
    E = fe.sub(H, fe.sq(xy), ksub)
    G = fe.sub(A, B, ksub)
    F = fe.add(C, G)
    return fe.mul(E, F), fe.mul(G, H), fe.mul(F, G), fe.mul(E, H)


# ---------------------------------------------------------------------------
# Constant tables: [0..15]B in niels form, at every window offset
# ---------------------------------------------------------------------------

# A resident launch reads its lanes' window tables at K offsets of the
# scalar: digit t = k * (nwin // K) + w is looked up in table k, which holds
# [0..15] * 16^((nwin // K) * (K - 1 - k)) * P, so the K digits of one w
# share the 4 doublings between w and w + 1 (64 doublings a signature where
# one table serving all 64 windows needs 256).
K = 4
NROW = 24  # a resident entry's limbs on sublanes: NLIMB up to the 8-row tile
_B_COL = 52  # first column of the B tables at the K offsets in _consts()


def _build_b_niels(scale: int = 1) -> np.ndarray:
    """(16, 3, 20) uint32: (y+x, y-x, 2dxy) limbs of j*scale*B, identity at
    j=0."""
    out = np.zeros((16, 3, NLIMB), dtype=np.uint32)
    base = _ed.pt_scalar_mult(_ed._to_extended((_ed.B_AFFINE, _ed._BY)), scale)
    for j in range(16):
        if j == 0:
            x, y = 0, 1
        else:
            ext = _ed.pt_scalar_mult(base, j)
            zinv = pow(ext[2], P - 2, P)
            x, y = ext[0] * zinv % P, ext[1] * zinv % P
        out[j, 0] = int_to_limbs((y + x) % P)
        out[j, 1] = int_to_limbs((y - x) % P)
        out[j, 2] = int_to_limbs(2 * _ed.D * x * y % P)
    return out


@lru_cache(maxsize=None)
def _consts(stride: int = NWIN // K) -> np.ndarray:
    """All per-limb constants bundled into one (20, 52 + 48 K) kernel input
    (Pallas kernels cannot capture array constants): columns 0..15 = ypx of
    [j]B, 16..31 = ymx, 32..47 = t2d, 48 = 2d, 49 = the fe_sub K constant,
    50 = KD (the wide zero the lazy carry plan sizes for deferred-class
    subtraction); from _B_COL on, the same three groups of 16 for
    [j] * 16^(stride * (K - 1 - k)) * B, k = 0..K-1: the B side of a
    resident launch, whose windows are ``stride`` apart."""
    out = np.zeros((NLIMB, _B_COL + 48 * K), dtype=np.uint32)
    tables = [(0, _build_b_niels())] + [
        (_B_COL + 48 * k, _build_b_niels(16 ** (stride * (K - 1 - k))))
        for k in range(K)]
    for col, niels in tables:
        for c in range(3):
            out[:, col + 16 * c:col + 16 * (c + 1)] = niels[:, c].T
    out[:, 48] = _D2_LIMBS
    out[:, 49] = _K_SUB
    out[:, 50] = np.asarray(_fc.derive_carry_plan("ed25519").kd, np.uint32)
    return out


_B_NIELS = _build_b_niels()
_CONSTS = _consts()


# ---------------------------------------------------------------------------
# The Pallas ladder kernel
# ---------------------------------------------------------------------------


def _seq_carry_ref(ref):
    """Exact sequential carry over a (20, B) scratch ref (no wraparound)."""
    for i in range(NLIMB - 1):
        c = ref[i : i + 1, :] >> BITS
        ref[i : i + 1, :] = ref[i : i + 1, :] & MASK
        ref[i + 1 : i + 2, :] = ref[i + 1 : i + 2, :] + c


def _fold_top_ref(ref):
    """Bits >= 255 (limb 19, offset 8) wrap to limb 0 times 19."""
    q = ref[NLIMB - 1 : NLIMB, :] >> 8
    ref[NLIMB - 1 : NLIMB, :] = ref[NLIMB - 1 : NLIMB, :] & 0xFF
    ref[0:1, :] = ref[0:1, :] + q * 19


def _canonical_ref(v, s1, s2):
    """Fully reduce carried v (limbs <= M) into [0, p) using scratch refs.
    Mirrors the proven XLA fe_canonical (ed25519_verify.py:145)."""
    s1[:] = v
    for _ in range(3):
        _seq_carry_ref(s1)
        _fold_top_ref(s1)
    _seq_carry_ref(s1)  # now < 2^255
    # conditional subtract p: t = x + 19; x >= p iff t >= 2^255
    s2[:] = s1[:]
    s2[0:1, :] = s2[0:1, :] + 19
    _seq_carry_ref(s2)
    ge = (s2[NLIMB - 1 : NLIMB, :] >> 8) > 0
    s2[NLIMB - 1 : NLIMB, :] = s2[NLIMB - 1 : NLIMB, :] & 0xFF
    return jnp.where(ge, s2[:], s1[:])


def _identity(B: int):
    """The neutral element (0, 1, 1, 0) in (20, B) limbs."""
    zero = jnp.zeros((NLIMB, B), jnp.uint32)
    one = jnp.pad(jnp.ones((1, B), jnp.uint32), ((0, NLIMB - 1), (0, 0)))
    return zero, one, one, zero


def _cached_multiples(p1, d2, ksub, fe, kd):
    """[0..15]p1 for an extended point p1: evens by doubling, odds by +p1;
    under lazy in cached-niels form (one mulF + two carries an entry buys a
    pt_add_cached per window: 353 vs 457 row-slots of carry work)."""
    tbl = [_identity(p1[0].shape[1]), p1]
    for j in range(2, 16):
        tbl.append(pt_double(tbl[j // 2], ksub, fe, kd) if j % 2 == 0
                   else pt_add(tbl[j - 1], p1, d2, ksub, fe, kd))
    if fe.carry_mode == "lazy":
        tbl = [pt_to_cached(t, d2, ksub, fe) for t in tbl]
    return tbl


def _select16(entry, mask16):
    """The entry a lane's digit names: entry(j) -> (rows, B) or (rows, 1),
    mask16 a list of (1, B) uint32 one-hot masks."""
    acc = entry(0) * mask16[0]
    for j in range(1, 16):
        acc = acc + entry(j) * mask16[j]
    return acc


def ladder_math(consts, negax, ay, digs_get, digh_get, nwin: int = NWIN,
                loop=lax.fori_loop, carry_mode: str = "lazy", tables=None):
    """The windowed-Straus double-scalar multiply [s]B + [h](-A) — pure jnp,
    shared by the pallas kernel (on ref values) and the CPU parity tests
    (tests/test_pallas_interpret.py).  digs_get/digh_get: t -> (1, B)
    digit row accessors (a ref slice in-kernel, an array row in tests).
    nwin < NWIN drives the identical code with small scalars; tests also
    swap `loop` for a plain Python loop so the whole thing evaluates
    eagerly (XLA's CPU compile of these graphs runs minutes — its
    simplifier thrashes on the carry patterns).  carry_mode picks eager (one
    carry ripple per field op) or lazy (one per point op; the default).

    Two forms of one algorithm.  BUILT (``tables`` None): the table
    [0..15](-A) is made from negax/ay in every lane, and each of the nwin
    windows is 4 doublings, one add from [0..15]B and one from that table.
    RESIDENT (``tables`` given, lazy only): ``tables(m)`` -> (20, B) is row
    m = (4 k + c) * 16 + j of the lanes' window tables, coordinate c of the
    cached-niels [j] * 16^((nwin // K) * (K - 1 - k)) * (-A) as
    ``window_tables_math`` makes them (negax/ay are not read); nwin // K
    rounds of 4 doublings, K adds from the B tables at the same offsets
    (``_consts(nwin // K)``) and K from the lanes'.  Same group element.

    Returns (X, Y, Z, T) with limbs in the certified carried class of the
    active mode (congruent mod p across modes)."""
    fe = _get_fe(carry_mode)
    lazy = carry_mode == "lazy"
    ident = _identity((digs_get(0) if negax is None else negax).shape[1])
    d2 = consts[:, 48:49]
    ksub = consts[:, 49:50]
    kd = consts[:, 50:51] if lazy else None

    def one_hot(d):  # (1, B) digits -> 16 (1, B) masks
        return [(d == j).astype(jnp.uint32) for j in range(16)]

    def add_b(acc, mk_s, col):
        # constant niels entry for the B part: (20, 1) x (1, B) masked sum
        ypx, ymx, t2d = (
            _select16(lambda j: consts[:, col + 16 * c + j:col + 16 * c + j + 1],
                      mk_s) for c in range(3))
        return pt_madd(acc, ypx, ymx, t2d, ksub, fe, kd)

    if tables is None:
        # one table serving every window: the resident form at K = 1, its
        # rows the stack [0..15](-A) made here, (16, 20, B) a coordinate
        a1 = (negax, ay, ident[1], fe.mul(negax, ay))
        tbl = [jnp.stack(c) for c in zip(*_cached_multiples(a1, d2, ksub, fe, kd))]
        offsets, b_col = 1, 0
        entry = lambda k, c, j: tbl[c][j]
    else:
        if not lazy or nwin % K:
            raise ValueError("resident tables: lazy carries, nwin a multiple of K")
        offsets, b_col = K, _B_COL
        entry = lambda k, c, j: tables((4 * k + c) * 16 + j)
    stride = nwin // offsets

    def round_(w, acc):
        for _ in range(4):
            acc = pt_double(acc, ksub, fe, kd)
        for k in range(offsets):
            t = k * stride + w if k else w
            acc = add_b(acc, one_hot(digs_get(t)), b_col + 48 * k)
            mk_h = one_hot(digh_get(t))
            q = tuple(_select16(lambda j: entry(k, c, j), mk_h)
                      for c in range(4))
            acc = (pt_add_cached(acc, q, ksub, kd, fe) if lazy
                   else pt_add(acc, q, d2, ksub, fe))
        return acc

    return loop(0, stride, round_, ident)


def window_tables_math(consts, negax, ay, put, stride: int = NWIN // K,
                       loop=lax.fori_loop):
    """A member's window tables from its -A (negax/ay (20, B), lanes are
    members): ``put(m, rows)`` is handed row m = (4 k + c) * 16 + j,
    coordinate c of the cached-niels [j] * 16^(stride * (K - 1 - k)) * (-A)
    (identity at j = 0), for every k, c, j; between two offsets the point is
    doubled 4 * stride times.  ``put`` takes a traced m (a ref store
    in-kernel, a list in tests).  The point operations are the ladder's, in
    lazy carries: every entry is in the class the ladder's adds read."""
    fe = _get_fe("lazy")
    one = _identity(negax.shape[1])[1]
    d2, ksub, kd = consts[:, 48:49], consts[:, 49:50], consts[:, 50:51]

    def offset(i, p):  # the i-th offset from the scalar's low end: k = K-1-i
        for j, entry in enumerate(_cached_multiples(p, d2, ksub, fe, kd)):
            for c in range(4):
                put((4 * (K - 1 - i) + c) * 16 + j, entry[c])
        return loop(0, 4 * stride,
                    lambda _, q: pt_double(q, ksub, fe, kd), p)

    # one body for every offset: the doublings behind the last table are
    # wasted once a membership, and nothing is traced twice
    loop(0, K, offset, (negax, ay, one, fe.mul(negax, ay)))


def _ladder_kernel(consts_ref, *refs, carry_mode: str = "lazy",
                   resident: bool = False):
    # the lanes' keys come as their window tables (resident) or as -A's
    # limbs, from which the kernel builds one table itself
    if resident:
        tbl_ref, *refs = refs
        negax = ay = None
        tables = lambda m: tbl_ref[m, 0:NLIMB, :]
    else:
        negax_ref, ay_ref, *refs = refs
        negax, ay, tables = negax_ref[:], ay_ref[:], None
    digs_ref, digh_ref, rlimb_ref, rsign_ref, out_ref, s1, s2 = refs
    # window count comes from the digit rows: production always passes
    # (NWIN, B), while reduced parity tests drive the identical math with
    # fewer windows (small scalars)
    X, Y, Z, _T = ladder_math(
        consts_ref[:], negax, ay,
        lambda t: digs_ref[pl.ds(t, 1), :],
        lambda t: digh_ref[pl.ds(t, 1), :],
        nwin=digs_ref.shape[0],
        carry_mode=carry_mode,
        tables=tables,
    )

    # Under lazy, fe.inv/fe.mul run on mulF and keep the epilogue inside the
    # certified class C (max limb < M), so _canonical_ref's domain holds.
    fe = _get_fe(carry_mode)
    zinv = fe.inv(Z)
    x = _canonical_ref(fe.mul(X, zinv), s1, s2)
    y = _canonical_ref(fe.mul(Y, zinv), s1, s2)
    ok = jnp.all(y == rlimb_ref[:], axis=0, keepdims=True)
    ok = ok & ((x[0:1, :] & 1) == rsign_ref[:])
    out_ref[:] = ok.astype(jnp.uint32)


def _ladder_call(negax, ay, digs, digh, rlimb, rsign, *, interpret=False,
                 lanes=LANES, carry_mode="lazy", tables=None):
    """negax/ay/rlimb (20, N), digs/digh (nwin, N) — NWIN=64 in production,
    fewer in the reduced interpret tests — rsign (1, N); N % lanes == 0.
    ``tables`` (64 K, NROW, N): the lanes' window tables in place of
    negax/ay, the resident form of ``ladder_math``."""
    nwin, n = digs.shape
    resident = tables is not None
    consts = _consts(nwin // K) if resident else _CONSTS
    cspec = pl.BlockSpec(consts.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
    spec20 = pl.BlockSpec((NLIMB, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    spec64 = pl.BlockSpec((nwin, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    spec1 = pl.BlockSpec((1, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    tspec = pl.BlockSpec((64 * K, NROW, lanes), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM)
    keys, kspecs = ((tables,), [tspec]) if resident else (
        (negax, ay), [spec20, spec20])
    return pl.pallas_call(
        partial(_ladder_kernel, carry_mode=carry_mode, resident=resident),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.uint32),
        grid=(n // lanes,),
        in_specs=[cspec, *kspecs, spec64, spec64, spec20, spec1],
        out_specs=spec1,
        scratch_shapes=[pltpu.VMEM((NLIMB, lanes), jnp.uint32)] * 2,
        interpret=interpret,
    )(jnp.asarray(consts), *keys, digs, digh, rlimb, rsign)


def _windows_kernel(consts_ref, negax_ref, ay_ref, out_ref):
    def put(m, rows):
        out_ref[pl.ds(m, 1), :, :] = jnp.pad(
            rows, ((0, NROW - NLIMB), (0, 0)))[None]

    window_tables_math(consts_ref[:], negax_ref[:], ay_ref[:], put)


def _windows_call(negax, ay, *, interpret=False, lanes=LANES):
    """negax/ay (20, N) -> (64 K, NROW, N): every lane's window tables, rows
    as ``ladder_math`` reads them; N % lanes == 0."""
    n = negax.shape[1]
    cspec = pl.BlockSpec(_CONSTS.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
    spec20 = pl.BlockSpec((NLIMB, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    tspec = pl.BlockSpec((64 * K, NROW, lanes), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _windows_kernel,
        out_shape=jax.ShapeDtypeStruct((64 * K, NROW, n), jnp.uint32),
        grid=(n // lanes,),
        in_specs=[cspec, spec20, spec20],
        out_specs=tspec,
        interpret=interpret,
    )(jnp.asarray(_CONSTS), negax, ay)


# ---------------------------------------------------------------------------
# Device prologue (second Pallas kernel): SHA-512 on uint32 pairs, Barrett
# mod L, scalar digit extraction. A plain XLA version of the same graph ran
# ~100x slower (thousands of thin unfused uint32 ops); in Pallas the whole
# hash stays in VMEM.
# ---------------------------------------------------------------------------

_H0_PAIRS = np.array(
    [[v >> 32, v & 0xFFFFFFFF] for v in (
        0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B,
        0xA54FF53A5F1D36F1, 0x510E527FADE682D1, 0x9B05688C2B3E6C1F,
        0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179)],
    dtype=np.uint32,
)

# SHA-512 round constants (FIPS 180-4).
_K64 = np.array(
    [
        0x428A2F98D728AE22, 0x7137449123EF65CD, 0xB5C0FBCFEC4D3B2F, 0xE9B5DBA58189DBBC,
        0x3956C25BF348B538, 0x59F111F1B605D019, 0x923F82A4AF194F9B, 0xAB1C5ED5DA6D8118,
        0xD807AA98A3030242, 0x12835B0145706FBE, 0x243185BE4EE4B28C, 0x550C7DC3D5FFB4E2,
        0x72BE5D74F27B896F, 0x80DEB1FE3B1696B1, 0x9BDC06A725C71235, 0xC19BF174CF692694,
        0xE49B69C19EF14AD2, 0xEFBE4786384F25E3, 0x0FC19DC68B8CD5B5, 0x240CA1CC77AC9C65,
        0x2DE92C6F592B0275, 0x4A7484AA6EA6E483, 0x5CB0A9DCBD41FBD4, 0x76F988DA831153B5,
        0x983E5152EE66DFAB, 0xA831C66D2DB43210, 0xB00327C898FB213F, 0xBF597FC7BEEF0EE4,
        0xC6E00BF33DA88FC2, 0xD5A79147930AA725, 0x06CA6351E003826F, 0x142929670A0E6E70,
        0x27B70A8546D22FFC, 0x2E1B21385C26C926, 0x4D2C6DFC5AC42AED, 0x53380D139D95B3DF,
        0x650A73548BAF63DE, 0x766A0ABB3C77B2A8, 0x81C2C92E47EDAEE6, 0x92722C851482353B,
        0xA2BFE8A14CF10364, 0xA81A664BBC423001, 0xC24B8B70D0F89791, 0xC76C51A30654BE30,
        0xD192E819D6EF5218, 0xD69906245565A910, 0xF40E35855771202A, 0x106AA07032BBD1B8,
        0x19A4C116B8D2D0C8, 0x1E376C085141AB53, 0x2748774CDF8EEB99, 0x34B0BCB5E19B48A8,
        0x391C0CB3C5C95A63, 0x4ED8AA4AE3418ACB, 0x5B9CCA4F7763E373, 0x682E6FF3D6B2B8A3,
        0x748F82EE5DEFB2FC, 0x78A5636F43172F60, 0x84C87814A1F0AB72, 0x8CC702081A6439EC,
        0x90BEFFFA23631E28, 0xA4506CEBDE82BDE9, 0xBEF9A3F7B2C67915, 0xC67178F2E372532B,
        0xCA273ECEEA26619C, 0xD186B8C721C0C207, 0xEADA7DD6CDE0EB1E, 0xF57D4F7FEE6ED178,
        0x06F067AA72176FBA, 0x0A637DC5A2C898A6, 0x113F9804BEF90DAE, 0x1B710B35131C471B,
        0x28DB77F523047D84, 0x32CAAB7B40C72493, 0x3C9EBE0A15C9BEBC, 0x431D67C49C100D4C,
        0x4CC5D4BECB3E42B6, 0x597F299CFC657E2A, 0x5FCB6FAB3AD6FAEC, 0x6C44198C4A475817,
    ],
    dtype=np.uint64,
)

_K_PAIRS = np.stack([(_K64 >> np.uint64(32)).astype(np.uint32),
                     (_K64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)], axis=1)


def _add64(a, b):
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(jnp.uint32)
    return (a[0] + b[0] + carry, lo)


def _rotr64(a, n):
    hi, lo = a
    if n == 32:
        return (lo, hi)
    if n < 32:
        return ((hi >> n) | (lo << (32 - n)), (lo >> n) | (hi << (32 - n)))
    m = n - 32
    return ((lo >> m) | (hi << (32 - m)), (hi >> m) | (lo << (32 - m)))


def _shr64(a, n):
    hi, lo = a
    if n < 32:
        return (hi >> n, (lo >> n) | (hi << (32 - n)))
    return (jnp.zeros_like(hi), hi >> (n - 32))


def _xor64(*vs):
    hi = vs[0][0]
    lo = vs[0][1]
    for v in vs[1:]:
        hi = hi ^ v[0]
        lo = lo ^ v[1]
    return (hi, lo)


def _one_round(flat, wt, kt):
    a, b, c, d, e, f, g, h = [(flat[2 * i], flat[2 * i + 1]) for i in range(8)]
    S1 = _xor64(_rotr64(e, 14), _rotr64(e, 18), _rotr64(e, 41))
    ch = ((e[0] & f[0]) ^ (~e[0] & g[0]), (e[1] & f[1]) ^ (~e[1] & g[1]))
    t1 = _add64(_add64(h, S1), _add64(ch, _add64(kt, wt)))
    S0 = _xor64(_rotr64(a, 28), _rotr64(a, 34), _rotr64(a, 39))
    maj = ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
           (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))
    t2 = _add64(S0, maj)
    a, b, c, d, e, f, g, h = _add64(t1, t2), a, b, c, _add64(d, t1), e, f, g
    out = []
    for v in (a, b, c, d, e, f, g, h):
        out.extend(v)
    return tuple(out)


def _sha512_rounds(state, w_ref, k_ref):
    """One SHA-512 compression on (1, B)-row uint32 pairs. w_ref holds the
    80-entry message schedule (hi at row 2t, lo at 2t+1). Round 0 is peeled so
    the fori carry starts from data-dependent values — Mosaic refuses loop
    carries whose initial layout is a replicated constant."""

    def rbody(t, flat):
        wp = w_ref[pl.ds(2 * t, 2), :]
        kp = k_ref[pl.ds(t, 1), :]
        return _one_round(flat, (wp[0:1, :], wp[1:2, :]), (kp[:, 0:1], kp[:, 1:2]))

    flat = []
    for v in state:
        flat.extend(v)
    flat = tuple(flat)
    # peel 4 rounds: the state rotates one slot per round, so after 4 every
    # carry entry is a computed value (the a/e outputs of rounds 0..3)
    for t in range(4):
        flat = _one_round(
            flat,
            (w_ref[2 * t : 2 * t + 1, :], w_ref[2 * t + 1 : 2 * t + 2, :]),
            (k_ref[t : t + 1, 0:1], k_ref[t : t + 1, 1:2]),
        )
    flat = lax.fori_loop(4, 80, rbody, flat)
    vals = [(flat[2 * i], flat[2 * i + 1]) for i in range(8)]
    return [_add64(s, v) for s, v in zip(state, vals)]


def _sha512_in_kernel(msgw_ref, k_ref, w_ref, nblocks, B):
    """Full SHA-512 over (nblocks*32, B) big-endian word rows -> 8 (1,B) pairs."""
    B_ = msgw_ref.shape[1]
    state = [(jnp.full((1, B_), int(_H0_PAIRS[i, 0]), jnp.uint32),
              jnp.full((1, B_), int(_H0_PAIRS[i, 1]), jnp.uint32))
             for i in range(8)]
    for blk in range(nblocks):
        # message schedule, statically unrolled into the scratch ref
        w = []
        for t in range(16):
            hi = msgw_ref[blk * 32 + 2 * t : blk * 32 + 2 * t + 1, :]
            lo = msgw_ref[blk * 32 + 2 * t + 1 : blk * 32 + 2 * t + 2, :]
            w.append((hi, lo))
        for t in range(16, 80):
            s0 = _xor64(_rotr64(w[t - 15], 1), _rotr64(w[t - 15], 8), _shr64(w[t - 15], 7))
            s1 = _xor64(_rotr64(w[t - 2], 19), _rotr64(w[t - 2], 61), _shr64(w[t - 2], 6))
            w.append(_add64(_add64(w[t - 16], s0), _add64(w[t - 7], s1)))
        for t in range(80):
            w_ref[2 * t : 2 * t + 1, :] = w[t][0]
            w_ref[2 * t + 1 : 2 * t + 2, :] = w[t][1]
        state = _sha512_rounds(state, w_ref, k_ref)
    return state


def _digest_byte(state, m):
    """Byte m (0..63) of the digest (big-endian per 64-bit word)."""
    word, j = divmod(m, 8)
    hi, lo = state[word]
    src = hi if j < 4 else lo
    shift = 24 - 8 * (j % 4)
    return (src >> shift) & 0xFF


# Barrett constants in radix-2^13
_QL = 21
_MU_LIMBS_D = np.array(
    [( ((1 << (BITS * 40)) // L_ORDER) >> (BITS * i)) & MASK for i in range(_QL + 1)],
    dtype=np.uint32)
_L_LIMBS_D = np.array([(L_ORDER >> (BITS * i)) & MASK for i in range(NLIMB)],
                      dtype=np.uint32)
_LC_LIMBS_D = np.array(
    [(((1 << (BITS * _QL)) - L_ORDER) >> (BITS * i)) & MASK for i in range(_QL)],
    dtype=np.uint32)


def _seq_carry_cols(cols):
    """Exact sequential carry over a list of (N,) uint32 columns (radix 2^13).
    Max values stay well under 2^32 (callers bound the inputs)."""
    out = []
    carry = jnp.zeros_like(cols[0])
    for v in cols:
        v = v + carry
        out.append(v & MASK)
        carry = v >> BITS
    return out, carry


def _mul_limbs_const(cols, const_limbs):
    """Columns (list of (N,)) times a constant limb vector -> carried columns.
    Column sums <= len(cols) * 8191^2 < 2^32 for <= 64 columns."""
    al, bl = len(cols), len(const_limbs)
    prod = [jnp.zeros_like(cols[0]) for _ in range(al + bl)]
    for j in range(bl):
        cj = int(const_limbs[j])
        if cj == 0:
            continue
        for i in range(al):
            prod[i + j] = prod[i + j] + cols[i] * cj
    out, _ = _seq_carry_cols(prod)
    return out


def _mod_l_device(digest_state):
    """512-bit digest (8 uint32 hi/lo pairs, little-endian int interpretation
    of the big-endian digest bytes) -> 20 radix-2^13 columns of digest mod L."""
    # h limbs: 40 columns of 13 bits over the 64 little-endian digest bytes
    def h_limb(k):
        lo_bit = BITS * k
        byte0 = lo_bit // 8
        sh = lo_bit % 8
        v = _digest_byte(digest_state, byte0)
        if byte0 + 1 < 64:
            v = v | (_digest_byte(digest_state, byte0 + 1) << 8)
        if byte0 + 2 < 64:
            v = v | (_digest_byte(digest_state, byte0 + 2) << 16)
        return (v >> sh) & MASK

    h = [h_limb(k) for k in range(40)]
    q1 = h[NLIMB - 1 :]  # >> b^19, 21 limbs
    q2 = _mul_limbs_const(q1, _MU_LIMBS_D)
    q3 = q2[_QL:][: _QL + 1]
    q3l = _mul_limbs_const(q3, _L_LIMBS_D)[:_QL]
    # r = (h - q3*L) mod b^21 in [0, 3L)
    r = []
    borrow = jnp.zeros_like(h[0])
    for i in range(_QL):
        v = h[i] - q3l[i] - borrow
        borrow = v >> 31  # wrapped negative
        r.append(v & MASK)  # 2^32 = 0 (mod 2^13)
    for _ in range(2):  # conditional subtract L, twice
        t = [r[i] + int(_LC_LIMBS_D[i]) for i in range(_QL)]
        t, carry = _seq_carry_cols(t)
        ge = carry > 0
        r = [jnp.where(ge, t[i], r[i]) for i in range(_QL)]
    return r[:NLIMB]  # r < L < 2^253


def _limbs_to_words8(limbs20):
    """20 radix-2^13 columns -> 8 (N,) uint32 LE words (value < 2^256)."""
    words = []
    for j in range(8):
        lo_bit = 32 * j
        k0 = lo_bit // BITS
        sh = lo_bit - BITS * k0
        acc = limbs20[k0] >> sh
        pos = BITS - sh
        k = k0 + 1
        while pos < 32 and k < NLIMB:
            acc = acc | (limbs20[k] << pos)
            pos += BITS
            k += 1
        words.append(acc)
    return words


def _prologue_kernel(k_ref, msgw_ref, sigw_ref,
                     digs_ref, digh_ref, rlimb_ref, rsign_ref, w_scr):
    """SHA-512(R||A||M) -> mod L -> 4-bit digits; scalar digits + raw R limbs
    from the signature words. Layout: everything (rows, B)."""
    B = msgw_ref.shape[1]
    nblocks = msgw_ref.shape[0] // 32
    state = _sha512_in_kernel(msgw_ref, k_ref, w_scr, nblocks, B)
    h_limbs = _mod_l_device(state)  # 20 (1,B) columns
    h_words = _limbs_to_words8(h_limbs)

    s_words = [sigw_ref[8 + j : 9 + j, :] for j in range(8)]
    r_words = [sigw_ref[j : j + 1, :] for j in range(8)]

    for t in range(NWIN):  # MSB-first 4-bit windows
        k = NWIN - 1 - t
        digh_ref[t : t + 1, :] = (h_words[k // 8] >> (4 * (k % 8))) & 15
        digs_ref[t : t + 1, :] = (s_words[k // 8] >> (4 * (k % 8))) & 15

    for k in range(NLIMB):  # raw R limbs (low 255 bits), sign bit dropped
        lo_bit = BITS * k
        w0 = lo_bit // 32
        sh = lo_bit % 32
        v = r_words[w0] >> sh
        if sh + BITS > 32 and w0 + 1 < 8:
            v = v | (r_words[w0 + 1] << (32 - sh))
        rlimb_ref[k : k + 1, :] = v & (0xFF if k == NLIMB - 1 else MASK)
    rsign_ref[:] = r_words[7] >> 31


def _prologue_call(msg_words, sig_words, *, interpret=False, lanes=LANES):
    """msg_words (nblocks*32, N) BE uint32; sig_words (16, N) LE uint32."""
    rows, n = msg_words.shape
    mspec = pl.BlockSpec((rows, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((16, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((80, 2), lambda i: (0, 0), memory_space=pltpu.VMEM)
    spec64 = pl.BlockSpec((NWIN, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    spec20 = pl.BlockSpec((NLIMB, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    spec1 = pl.BlockSpec((1, lanes), lambda i: (0, i), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _prologue_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((NWIN, n), jnp.uint32),
            jax.ShapeDtypeStruct((NWIN, n), jnp.uint32),
            jax.ShapeDtypeStruct((NLIMB, n), jnp.uint32),
            jax.ShapeDtypeStruct((1, n), jnp.uint32),
        ],
        grid=(n // lanes,),
        in_specs=[kspec, mspec, sspec],
        out_specs=[spec64, spec64, spec20, spec1],
        scratch_shapes=[pltpu.VMEM((160, lanes), jnp.uint32)],
        interpret=interpret,
    )(jnp.asarray(_K_PAIRS), msg_words, sig_words)


def _device_verify(negax, ay, sig_words, msg_words, interpret=False,
                   lanes=LANES, carry_mode="lazy"):
    """negax/ay (N, 20) uint32; sig_words (N, 16) uint32 LE; msg_words
    (N, nblocks*32) uint32 BE padded SHA-512 input. Returns (N,) bool."""
    digs, digh, rlimb, rsign = _prologue_call(
        msg_words.T, sig_words.T, interpret=interpret, lanes=lanes
    )
    ok = _ladder_call(
        negax.T, ay.T, digs, digh, rlimb, rsign, interpret=interpret,
        lanes=lanes, carry_mode=carry_mode,
    )
    return ok[0].astype(bool)


# Compiled entry for the real-device path. In interpret mode the plain
# function is called eagerly instead: tracing the interpreted kernels into one
# jit graph explodes into thousands of scalar XLA ops (a 6-minute CPU compile).
_device_verify_jit = partial(
    jax.jit, static_argnames=("interpret", "lanes", "carry_mode")
)(_device_verify)


@partial(jax.jit, static_argnames=("lanes", "carry_mode"))
def _device_verify_packed(negax, ay, pub_words, sig_words, tmpl, vidx, vwords,
                          tables=None, lanes=LANES, carry_mode="lazy"):
    """Transfer-minimizing verify: the padded SHA-512 input is ASSEMBLED ON
    DEVICE instead of shipped from the host.

    Steady-state per-signature host->device transfer here is 64B of
    signature + ~16B of message words that actually differ across the batch
    (for commit verification: the fixed64 timestamp), against ~480B for the
    naive path. Pubkey limbs + compressed words are device-cached per
    validator set (_upload_valset).

    negax/ay (b, 20) u32 limbs; pub_words (b, 8) / sig_words (b, 16) LE u32;
    tmpl (rows,) BE u32 — padded SHA input of batch row 0; vidx (k,) i32 —
    word rows >= 16 whose value varies per signature; vwords (b, k) BE u32 —
    those rows' values. Rows 0..15 (R || A) always come from sig/pub words.
    ``tables`` (64 K, NROW, b): the lanes' window tables as a membership's
    table holds them; given, the ladder takes its resident form and negax/ay
    are not read.
    """
    b = negax.shape[0]
    rows = tmpl.shape[0]

    def bswap(x):
        return ((x >> 24) | ((x >> 8) & 0xFF00)
                | ((x << 8) & 0xFF0000) | (x << 24))

    mw = jnp.broadcast_to(tmpl[:, None], (rows, b))
    mw = mw.at[0:8, :].set(bswap(sig_words[:, 0:8].T))
    mw = mw.at[8:16, :].set(bswap(pub_words.T))
    mw = mw.at[vidx, :].set(vwords.T)
    digs, digh, rlimb, rsign = _prologue_call(mw, sig_words.T, lanes=lanes)
    ok = _ladder_call(negax.T, ay.T, digs, digh, rlimb, rsign, lanes=lanes,
                      carry_mode=carry_mode, tables=tables)
    return ok[0].astype(bool)


# ---------------------------------------------------------------------------
# Host wrapper: decompression cache + packing
# ---------------------------------------------------------------------------

_valset_cache: dict = {}
_VALSET_CACHE_MAX = 64


def _decompress_rows(pubs: np.ndarray):
    """(N, 32) pubkeys -> (neg_ax, ay, valid), a per-key cache lookup a row."""
    n = pubs.shape[0]
    # one span a missed set, never a lane: what a new key array costs
    with trace.span("valset.miss", cache="host", lanes=n, bytes=32 * n):
        neg_ax = np.zeros((n, NLIMB), dtype=np.uint32)
        ay = np.zeros((n, NLIMB), dtype=np.uint32)
        valid = np.ones((n,), dtype=bool)
        for i in range(n):
            dec = _xla._decompress_neg_cached(pubs[i].tobytes())
            if dec is None:
                valid[i] = False
            else:
                neg_ax[i] = dec[0]
                ay[i] = dec[1]
    return neg_ax, ay, valid


def _decompress_valset(
    pubs: np.ndarray, key: Optional[bytes] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(N, 32) pubkeys -> (neg_ax, ay, valid) with whole-set caching: commit
    verification hits the same validator-set array every height.  ``key`` is
    ``_valset_key(pubs)`` where the caller already holds it."""
    if key is None:
        key = _valset_key(pubs)
    hit = _valset_cache.get(key)
    get_verify_metrics().valset_cache.add(
        1.0, ("host", "miss" if hit is None else "hit"))
    if hit is not None:
        return hit
    neg_ax, ay, valid = _decompress_rows(pubs)
    if len(_valset_cache) >= _VALSET_CACHE_MAX:
        _valset_cache.clear()
        get_verify_metrics().valset_cache_clears.add(1.0, ("host",))
    _valset_cache[key] = (neg_ax, ay, valid)
    return neg_ax, ay, valid


def _pad_rows(a: np.ndarray, b: int) -> np.ndarray:
    if a.shape[0] == b:
        return a
    return np.concatenate(
        [a, np.zeros((b - a.shape[0],) + a.shape[1:], dtype=a.dtype)], axis=0
    )


_dev_valset_cache: dict = {}
_DEV_VALSET_CACHE_MAX = 32


def _upload_valset(pubs, neg_ax, ay, b, key: Optional[bytes] = None):
    """Device-resident (negax, ay, pub_words) padded to bucket b, cached per
    (valset, bucket). Commit verification reuses the same validator set
    every height, so after the first call the pubkey material is never
    uploaded again.  ``key`` as in ``_decompress_valset``."""
    key = (_valset_key(pubs) if key is None else key, b)
    hit = _dev_valset_cache.get(key)
    get_verify_metrics().valset_cache.add(
        1.0, ("device", "miss" if hit is None else "hit"))
    if hit is not None:
        return hit
    # the three uploads of a missed set: negax, ay and the key words of
    # every lane of the bucket
    with trace.span("valset.miss", cache="device", lanes=b,
                    bytes=4 * b * (2 * NLIMB + 8)):
        pub_words = np.ascontiguousarray(pubs).view("<u4").astype(np.uint32)
        entry = (
            jnp.asarray(_pad_rows(neg_ax, b)),
            jnp.asarray(_pad_rows(ay, b)),
            jnp.asarray(_pad_rows(pub_words, b)),
        )
    if len(_dev_valset_cache) >= _DEV_VALSET_CACHE_MAX:
        _dev_valset_cache.clear()
        get_verify_metrics().valset_cache_clears.add(1.0, ("device",))
    _dev_valset_cache[key] = entry
    return entry


# A membership a table: the lanes of a call whose caller knows them as rows
# of a key array it keeps (``ValsetRows``: a commit's present slots, another
# subset every height, or every slot) are gathered by row from what is
# derived ONCE from that array, on the host and on the device: the key limbs
# and words, and the window tables the ladder's resident form reads.  The
# two whole-array caches above know a call by its own key array, which is
# new at every height of a live chain; they serve the callers that hand
# down no identity.
_valset_tables: dict = {}  # key_id -> _ValsetTable, least recently used first
_VALSET_TABLES_MAX = 4  # a node holds the current, the next and the last set
_valset_tables_mtx = threading.Lock()
_TABLE_WORDS = 2 * NLIMB + 8  # a device row: negax | ay | key words
_WINDOW_WORDS = 64 * K * NROW  # a member's window tables, a device row


class _ValsetTable:
    """One membership's key material, a row a member."""

    __slots__ = ("keys", "neg_ax", "ay", "valid", "device", "whole")

    def __init__(self, keys, neg_ax, ay, valid):
        self.keys = keys  # (N, 32) u8, the caller's own array
        self.neg_ax, self.ay, self.valid = neg_ax, ay, valid  # (N, 20) x2, (N,)
        # on the device, None until a launch: (R, 48) u32, negax | ay | key
        # words a row, R = _bucket(N + 1) so that programs are a bucket's
        # and not a member count's, rows N.. zero (row N is the launch's
        # padding lanes'); and, from the first launch that reads them on,
        # (R, _WINDOW_WORDS) u32, the members' window tables
        self.device = None
        # bucket -> what a resident launch of every member in order was
        # handed: the gather's result, kept
        self.whole = {}


def _valset_table(rows: ValsetRows) -> _ValsetTable:
    """The table of ``rows.keys``, filled at a membership's first call.  A
    handful are resident and the least recently used one goes alone, its
    device arrays with it."""
    with _valset_tables_mtx:
        table = _valset_tables.pop(rows.key_id, None)
        if table is not None:
            _valset_tables[rows.key_id] = table  # the most recently used
    get_verify_metrics().valset_cache.add(
        1.0, ("table", "miss" if table is None else "hit"))
    if table is not None:
        return table
    keys = np.ascontiguousarray(rows.keys, dtype=np.uint8)
    table = _ValsetTable(keys, *_decompress_rows(keys))
    with _valset_tables_mtx:
        # another caller may have filled it meanwhile: one table a
        # membership, so one build of its window tables
        other = _valset_tables.get(rows.key_id)
        if other is not None:
            return other
        while len(_valset_tables) >= _VALSET_TABLES_MAX:
            del _valset_tables[next(iter(_valset_tables))]
        _valset_tables[rows.key_id] = table
    return table


@jax.jit
def _build_valset_windows(table):
    """A membership's (R, 48) device rows -> (R, _WINDOW_WORDS): every
    member's window tables, a row a member in the order the ladder reads a
    lane's (row m of ``window_tables_math``, NROW limbs each).  The zero rows
    past the members give what a key that is no point gives: no lane's
    verdict reads either."""
    limbs = table[:, :2 * NLIMB].T
    out = _windows_call(limbs[:NLIMB], limbs[NLIMB:])
    return out.reshape(_WINDOW_WORDS, table.shape[0]).T


def _table_on_device(table: _ValsetTable, windows: bool):
    """The membership's device rows and, where the launch reads them
    (``windows``), its window tables: the rows go up at the membership's
    first launch, the tables are built at its first resident one (the same
    launch, but for an eager one)."""
    n = table.valid.shape[0]

    def built(rows):
        # what a set change costs a live node, beside the upload
        with trace.span("valset.tables", members=n,
                        bytes=4 * rows.shape[0] * _WINDOW_WORDS):
            return call_jit(_build_valset_windows, rows)

    if table.device is None:
        with trace.span("valset.miss", cache="device", lanes=n,
                        bytes=4 * _bucket(n + 1) * _TABLE_WORDS):
            pub_words = table.keys.view("<u4").astype(np.uint32)
            rows = jnp.asarray(_pad_rows(np.concatenate(
                [table.neg_ax, table.ay, pub_words], axis=1), _bucket(n + 1)))
            table.device = rows, built(rows) if windows else None
    elif windows and table.device[1] is None:
        table.device = table.device[0], built(table.device[0])
    rows, tables = table.device
    return rows, tables if windows else None


@jax.jit
def _gather_valset_rows(table, windows, idx):
    """Rows ``idx`` of a membership's device arrays as what
    ``_device_verify_packed`` takes: (b, 20) negax, (b, 20) ay, (b, 8) key
    words, and, of ``windows`` where the launch reads them (else None), the
    window tables in the kernel's layout, (64 K, NROW, b)."""
    rows = jnp.take(table, idx, axis=0, mode="clip")
    tables = None if windows is None else jnp.take(
        windows, idx, axis=0, mode="clip").T.reshape(
            64 * K, NROW, idx.shape[0])
    return (rows[:, :NLIMB], rows[:, NLIMB:2 * NLIMB], rows[:, 2 * NLIMB:],
            tables)


def _table_index(slots: np.ndarray, n_members: int, b: int) -> np.ndarray:
    """A launch's gather index: the lanes' rows, then the zero row up to the
    bucket."""
    idx = np.full((b,), n_members, dtype=np.int32)
    idx[:slots.shape[0]] = slots
    return idx


class _OwnKeys(NamedTuple):
    """A launch's key limbs where the call's keys are known by themselves:
    decompressed for this key array, and on the device a copy a (key array,
    bucket).  No window tables: the ladder builds one a lane."""

    neg_ax: np.ndarray
    ay: np.ndarray
    key: Optional[bytes]  # the key array's _valset_key where it is held

    def take(self, idx):
        return _OwnKeys(self.neg_ax[idx], self.ay[idx], None)

    def on_host(self):
        return self.neg_ax, self.ay

    def on_device(self, pubs, b, _resident):
        return (*_upload_valset(pubs, self.neg_ax, self.ay, b, self.key), None)


class _TableKeys(NamedTuple):
    """A launch's key limbs and window tables as rows of a membership's
    table: an index goes up and the device gathers.  ``slots`` None: every
    member in order, whose gather is made once a bucket."""

    table: _ValsetTable
    slots: Optional[np.ndarray]

    def take(self, idx):
        return _TableKeys(
            self.table, idx if self.slots is None else self.slots[idx])

    def on_host(self):
        if self.slots is None:
            return self.table.neg_ax, self.table.ay
        return self.table.neg_ax[self.slots], self.table.ay[self.slots]

    def on_device(self, _pubs, b, resident):
        table = self.table
        n = table.valid.shape[0]
        rows, windows = _table_on_device(table, resident)
        keep = self.slots is None and windows is not None
        if keep and b in table.whole:
            return table.whole[b]
        idx = _table_index(np.arange(n) if self.slots is None else self.slots,
                           n, b)
        out = call_jit(_gather_valset_rows, rows, windows, jnp.asarray(idx))
        if keep:
            table.whole[b] = out
        return out


def _bucket(n: int, lanes: int = LANES) -> int:
    b = lanes
    while b < n and b < 4096:
        b *= 2
    if n <= b:
        return b
    # past 4096, pad at 2048 granularity: 4096-steps cost up to +25% padded
    # lanes (10k signatures padded to 12288 instead of 10240) for no
    # compile-cache benefit at these sizes
    return ((n + 2047) // 2048) * 2048


def _message_matrix(msgs, n: int, ln: int) -> np.ndarray:
    """n messages of ln bytes as an (n, ln) uint8 array: the caller's own
    where it came as one, else the list joined."""
    if isinstance(msgs, np.ndarray):
        return msgs
    if not ln:
        return np.zeros((n, 0), np.uint8)
    return np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(n, ln)


def verify_batch(pubs: np.ndarray, msgs: Sequence[bytes], sigs: np.ndarray,
                 interpret: bool = False,
                 carry_mode: str = "lazy",
                 valset: Optional[ValsetRows] = None) -> np.ndarray:
    """Go-exact batched verify on the Pallas path, on the default jax
    device. Same contract as ops.ed25519_verify.verify_batch.
    `carry_mode` picks the eager or deferred (lazy) carry schedule — both
    bit-exact at the canonical boundary.  ``msgs`` may be an (n, ln) uint8
    array (one length, known from its shape).  ``valset`` says which rows
    of a key array the caller keeps ``pubs`` are, every row in order
    (``slots`` None) or ``keys[slots]``: the lanes' limbs and window tables
    are gathered from the membership's table and the ladder runs in its
    resident form.  Without it the call's keys are known by themselves (the
    two whole-array caches) and the ladder builds a table a lane."""
    carry_mode = _fc.normalize_carry_mode(carry_mode)
    pubs = np.ascontiguousarray(pubs, dtype=np.uint8)
    sigs = np.ascontiguousarray(sigs, dtype=np.uint8)
    n = pubs.shape[0]
    if n == 0:
        return np.zeros((0,), dtype=bool)
    if isinstance(msgs, np.ndarray):
        if msgs.ndim != 2 or msgs.shape[0] != n:
            raise ValueError(f"messages {msgs.shape} for {n} keys")
        msgs = np.ascontiguousarray(msgs, dtype=np.uint8)

    # valset limbs and the length scan, before any launch: the host work that
    # is not packing.  One length (a commit, a sync window) goes down as the
    # caller's own columns; several are regrouped, one launch a length
    with trace.span("dispatch.prepare", n=n) as sp:
        if valset is not None:
            # what the device gathers is what the lanes say: a caller whose
            # slots name other keys is refused, not verified against them
            slots = None if valset.slots is None else np.asarray(valset.slots)
            named = valset.keys
            if slots is not None:
                named = named[slots] if (
                    slots.shape == (n,) and slots.min() >= 0) else None
            if named is not pubs and not np.array_equal(named, pubs):
                raise ValueError("valset.keys[valset.slots] are not the keys")
            table = _valset_table(valset)
            limbs = _TableKeys(table, slots)
            valid = table.valid if slots is None else table.valid[slots]
        else:
            key = _valset_key(pubs)  # one hash for both whole-array caches
            neg_ax, ay, valid = _decompress_valset(pubs, key)
            limbs = _OwnKeys(neg_ax, ay, key)
        valid = valid & ((sigs[:, 63] & 224) == 0)  # Go's only s range check
        lengths = ({msgs.shape[1]} if isinstance(msgs, np.ndarray)
                   else set(map(len, msgs)))
        uniform = len(lengths) == 1 and len(msgs) == n
        groups = []
        if not uniform:
            lens = np.fromiter(map(len, msgs), dtype=np.int64, count=len(msgs))
            for ln in np.unique(lens):
                idx = np.nonzero(lens == ln)[0]
                groups.append((idx, (
                    pubs[idx], [msgs[i] for i in idx], sigs[idx],
                    limbs.take(idx), valid[idx], int(ln),
                )))
        sp.set(groups=len(lengths))
    get_verify_metrics().ed25519_pack.add(
        1.0, ("uniform" if uniform else "grouped",))
    if uniform:
        (ln,) = lengths
        return _verify_uniform(pubs, msgs, sigs, limbs, valid, ln,
                               interpret, carry_mode)
    out = np.zeros((n,), dtype=bool)
    for idx, cols in groups:
        out[idx] = _verify_uniform(*cols, interpret, carry_mode)
    return out


_prologue_jit = partial(jax.jit, static_argnames=("lanes",))(_prologue_call)


def _prologue_h(pubs, msgs, sigs, interpret=False) -> list:
    """h_i = SHA-512(R || A || M) mod L for every row, computed by the
    ON-DEVICE prologue kernel: one _prologue_call per uniform-msg-length
    group, then the (NWIN, b) MSB-first 4-bit digit matrix reassembles to
    host ints for the MSM schedule builder.  This keeps the hash stage of
    the RLC path on the same kernel the ladder uses."""
    n = pubs.shape[0]
    lanes = 8 if interpret else LANES
    lens = np.array([len(m) for m in msgs]) if msgs else np.zeros((0,), int)
    hs = [0] * n
    for ln in np.unique(lens):
        idx = np.nonzero(lens == ln)[0]
        k = len(idx)
        b = _bucket(k, lanes)
        total = 64 + int(ln)
        nblocks = (total + 1 + 16 + 127) // 128
        padded = np.zeros((b, nblocks * 128), dtype=np.uint8)
        padded[:k, :32] = sigs[idx, :32]
        padded[:k, 32:64] = pubs[idx]
        if ln:
            m = np.frombuffer(
                b"".join(bytes(msgs[i]) for i in idx), np.uint8
            ).reshape(k, int(ln))
            padded[:k, 64:total] = m
        padded[:, total] = 0x80
        padded[:, -16:] = np.frombuffer((total * 8).to_bytes(16, "big"), np.uint8)
        msg_words = padded.reshape(b, -1, 4)[:, :, ::-1].reshape(b, -1)
        msg_words = np.ascontiguousarray(msg_words).view("<u4").astype(np.uint32)
        sig_words = np.ascontiguousarray(sigs[idx]).view("<u4").astype(np.uint32)
        mw = jnp.asarray(msg_words.T)
        sw = jnp.asarray(_pad_rows(sig_words, b).T)
        if interpret:
            _, digh, _, _ = _prologue_call(mw, sw, interpret=True, lanes=lanes)
        else:
            _, digh, _, _ = call_jit(_prologue_jit, mw, sw, lanes=lanes)
        digh = np.asarray(digh)
        for j, i in enumerate(idx):
            h = 0
            for t in range(NWIN):
                h = (h << 4) | int(digh[t, j])
            hs[i] = h
    return hs


def rlc_verify_batch(pubs: np.ndarray, msgs: Sequence[bytes],
                     sigs: np.ndarray, interpret: bool = False,
                     carry_mode: str = "lazy",
                     seed: Optional[int] = None) -> np.ndarray:
    """Batched Go-exact verify via ONE multi-scalar multiplication on the
    Pallas path: the SHA-512/mod-L stage runs in the existing prologue
    kernel (_prologue_h), the MSM itself in the shared device engine
    (ops/ed25519_msm), and a rejected window localizes through chunk RLCs
    down to exact rows on this module's ladder ``verify_batch``.  Same
    contract as ``verify_batch``; ``seed`` pins the RLC coefficients."""
    from tendermint_tpu.ops import ed25519_msm as _msm

    carry_mode = _fc.normalize_carry_mode(carry_mode)
    pubs = np.ascontiguousarray(pubs, dtype=np.uint8)
    sigs = np.ascontiguousarray(sigs, dtype=np.uint8)
    n = pubs.shape[0]
    if n == 0:
        return np.zeros((0,), dtype=bool)
    items = [(pubs[i].tobytes(), bytes(msgs[i]), sigs[i].tobytes())
             for i in range(n)]
    parsed, out = _ed._parse_batch(items, compute_h=False)
    if parsed:
        hs = _prologue_h(pubs, msgs, sigs, interpret=interpret)
        parsed = [(i, na, nr, int(hs[i]), s) for (i, na, nr, _h, s) in parsed]
    if seed is None:
        seed = _xla.rlc_seed(pubs, sigs)

    def ladder_fn(idx):
        return verify_batch(
            pubs[idx], [msgs[i] for i in idx], sigs[idx],
            interpret=interpret,
            carry_mode=carry_mode,
        )

    _msm.rlc_resolve(parsed, out, ladder_fn, seed=seed,
                     carry_mode=carry_mode)
    return np.asarray(out, dtype=bool)


def _varying_columns(m: np.ndarray) -> np.ndarray:
    """Indices of the byte columns of (n, ln) ``m`` in which some row differs
    from row 0.  numpy's ``any`` down the rows pays a loop start for every
    row, which at 110 bytes a row is most of its time: 16 rows are folded
    into one first."""
    n, ln = m.shape
    ne = m != m[0]
    head = n - n % 16
    folded = ne[:head].reshape(head // 16, 16 * ln).any(axis=0).reshape(16, ln)
    return np.nonzero(folded.any(axis=0) | ne[head:].any(axis=0))[0]


def pack_variable_words(pubs, msgs, sigs, ln: int, b: int):
    """Host-side packing for the transfer-minimizing dispatch: returns
    (tmpl, vrows, vwords) — the padded-SHA-input template of batch row 0,
    the word rows (>= 16) that vary across the batch, and each signature's
    values at those rows. Pure numpy (shared by _verify_uniform and the
    bench's device-resident re-dispatch timing).

    Only the bytes of the varying rows are gathered, from the messages as
    one matrix (the caller's own, or the list joined); no lane's padded
    input is built but row 0's."""
    n = pubs.shape[0]
    total = 64 + ln
    nblocks = (total + 1 + 16 + 127) // 128
    m = _message_matrix(msgs, n, ln)
    # the padded input past the message, the same in every lane: 0x80,
    # zeros, the 16-byte bit length
    tail = np.zeros((nblocks * 128 - total,), dtype=np.uint8)
    tail[0] = 0x80
    tail[-16:] = np.frombuffer((total * 8).to_bytes(16, "big"), np.uint8)
    # template = row 0's padded SHA input, as BE words
    tmpl = (
        np.concatenate([sigs[0, :32], pubs[0], m[0], tail])
        .view(">u4").astype(np.uint32)
    )
    # message byte columns that differ across the batch -> padded word rows
    vrows = np.unique((64 + _varying_columns(m)) // 4).astype(np.int32)
    if vrows.size == 0:
        vrows = np.array([16], np.int32)  # row 16 always exists (rows>=32)
    k = int(vrows.size)
    k_pad = 1 << (k - 1).bit_length()
    if k_pad > k:  # duplicate scatter rows carry identical values
        vrows = np.concatenate([vrows, np.full((k_pad - k,), vrows[0], np.int32)])
    # per-signature BE words at the varying rows: each row's four bytes, by
    # their column in the message; a column past the message reads the tail,
    # lanes n..b the all-zero message
    cols = ((vrows[:, None] - 16) * 4 + np.arange(4)).reshape(-1)
    past = cols >= ln
    vbytes = np.empty((b, 4 * k_pad), dtype=np.uint8)
    if ln:
        np.take(m, cols, axis=1, out=vbytes[:n], mode="clip")
    vbytes[n:] = 0
    vbytes[:, past] = tail[cols[past] - ln]
    return tmpl, vrows, vbytes.view(">u4").astype(np.uint32)


def _sig_words(sigs, valid, b: Optional[int] = None) -> np.ndarray:
    """(n, 64) signature bytes as (b, 16) LE words (b = n unless given), a
    fresh array; invalid rows' scalars zeroed to keep device work defined,
    rows n..b zero."""
    n = sigs.shape[0]
    sig_words = np.zeros((n if b is None else b, 16), dtype=np.uint32)
    sig_words[:n] = np.ascontiguousarray(sigs).view("<u4")
    sig_words[:n][~valid] = 0
    return sig_words


def _verify_uniform(pubs, msgs, sigs, limbs, valid, ln, interpret,
                    carry_mode="lazy"):
    """One launch: ``limbs`` (_OwnKeys or _TableKeys) gives the lanes' key
    limbs, on the device for the packed path and on the host for the
    reference path."""
    n = pubs.shape[0]
    get_verify_metrics().ed25519_launches.add(1.0)
    # interpret mode (CPU tests) has no tile-alignment constraint: shrink the
    # lane count so the eager interpreter does 16x less padded work.
    lanes = 8 if interpret else LANES
    b = _bucket(n, lanes)
    total = 64 + ln  # R || A || M
    nblocks = (total + 1 + 16 + 127) // 128
    rows = nblocks * 32

    if not interpret:
        # packed path: ship only signatures + the message words that actually
        # vary across the batch; everything else is device-cached or template.
        # One device launch; its three spans split the host's share of it
        with trace.span("dispatch.pack", n=n, lanes=b) as sp:
            sig_words = _sig_words(sigs, valid, b)
            tmpl, vrows, vwords = pack_variable_words(pubs, msgs, sigs, ln, b)
            sp.set(vwords=int(vrows.size))
        with trace.span("dispatch.launch", lanes=b) as sp:  # copies in + enqueue
            # the resident form is written in lazy carries
            *keys_d, tables = limbs.on_device(pubs, b, carry_mode == "lazy")
            form = "built" if tables is None else "resident"
            sp.set(tables=form)
            get_verify_metrics().ed25519_ladder_lanes.add(float(b), (form,))
            out = call_jit(
                _device_verify_packed,
                *keys_d,
                jnp.asarray(sig_words),
                jnp.asarray(tmpl), jnp.asarray(vrows), jnp.asarray(vwords),
                *(() if tables is None else (tables,)),
                lanes=lanes, carry_mode=carry_mode,
            )
        # the device's run, the copy back and the wake of this thread
        with trace.span("dispatch.wait", lanes=b):
            ok = np.asarray(out)[:n]
        return ok & valid

    # reference path (interpret mode): full padded input assembled on host
    sig_words = _sig_words(sigs, valid)
    padded = np.zeros((b, nblocks * 128), dtype=np.uint8)
    padded[:n, :32] = sigs[:, :32]
    padded[:n, 32:64] = pubs
    padded[:n, 64:total] = _message_matrix(msgs, n, ln)
    padded[:, total] = 0x80
    padded[:, -16:] = np.frombuffer((total * 8).to_bytes(16, "big"), np.uint8)
    # big-endian 32-bit words
    msg_words = padded.reshape(b, -1, 4)[:, :, ::-1].reshape(b, -1)
    msg_words = np.ascontiguousarray(msg_words).view("<u4").astype(np.uint32)

    neg_ax, ay = limbs.on_host()
    get_verify_metrics().ed25519_ladder_lanes.add(float(b), ("built",))
    ok = np.asarray(
        _device_verify(
            jnp.asarray(_pad_rows(neg_ax, b)),
            jnp.asarray(_pad_rows(ay, b)),
            jnp.asarray(_pad_rows(sig_words, b)),
            jnp.asarray(msg_words),
            interpret=interpret,
            lanes=lanes,
            carry_mode=carry_mode,
        )
    )[:n]
    return ok & valid
