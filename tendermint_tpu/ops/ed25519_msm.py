"""One MSM per window: device-side random-linear-combination verification.

The ladder kernels (ops/ed25519_verify.py, ops/ed25519_pallas.py) pay a full
253-bit double-scalar ladder per signature (~3,850 fe_mul).  PR 14's host
``crypto.ed25519.verify_batch`` proved the random-linear-combination
alternative bit-identical at ~110 point-op equivalents per signature: accept
the whole batch iff

    [sum z_i s_i]B  +  sum_i [(z_i h_i) mod L](-A_i)  +  sum_i [z_i](-R_i)
        ==  identity

with fresh 128-bit z_i (a false accept needs a 2^-128 collision; a clean
batch can never falsely reject — the equation is exact).  This module is the
device port: ONE Pippenger multi-scalar multiplication over the whole window,
built from the batch-leading lazy-carry point ops of ops/ed25519_verify.py,
with the ``[s_b]B`` term folded off the precomputed B-window niels table
(ops/ed25519_pallas._build_b_niels).

Making Pippenger jit-shaped
---------------------------

Pippenger's bucket accumulation is a data-dependent segmented reduction —
the digit of each (scalar, point) pair decides which bucket its point sums
into.  The host resolves all data dependence into *index schedules* so the
device graph is static:

  * pool: ``(R0, 4, 20)`` extended points, row 0 = identity, rows 1..2n =
    the -A_i / -R_i columns (Z = 1, fully carried limbs);
  * tree levels: level l is ONE batched ``pt_add(prev[ia], prev[ib])`` over
    the previous level's array (level 0 = the pool).  Entries of the same
    bucket pair up within their segment; an odd leftover passes through
    paired with the identity row 0 (the complete addition law makes
    P + identity a projective scaling of P); a segment that reaches size 1
    "finalizes" and stays parked in that level's array;
  * bucket grid: one gather from the concatenation [pool, lvl1..lvlT] with
    host-computed global indices (empty buckets gather the identity row 0);
  * bucket-weighted fold: ``lax.fori_loop`` over digits 2^c-1..1, running
    the classic run/acc double accumulation at width W (one lane per
    window) — fori keeps the XLA graph small (unrolled carry graphs explode
    XLA CPU compile times; see ed25519_pallas.ladder_math);
  * window fold: Horner from the top window — c doubles + 1 add per step;
  * ``[s_b]B``: 64 MSB-first 4-bit digits against the niels table
    (4 doubles + 1 mixed add per digit), then one final add and a
    projective identity check (canonical X == 0 and Y == Z).

Index arrays ride as DYNAMIC jit arguments, so the compile cache keys only
on shapes + carry_mode; level widths are padded to the power-of-two/1024
ladder to keep those shapes stable across RLC coefficient draws.  Scalars are sampled from a seeded ``random.Random`` so the
audit/replay paths stay deterministic.

Localization mirrors the host verifier: an MSM-rejected window re-runs
chunk RLCs (``crypto.ed25519._CHUNK`` = 32) on the host parse, then ships
all dirty-chunk rows to the exact per-row ladder in ONE device dispatch.
"""

from __future__ import annotations

import random
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ed
from tendermint_tpu.ops import ed25519_verify as _xla
from tendermint_tpu.ops import fe_common as _fc
from tendermint_tpu.ops.dispatch import call_jit

P = _ed.P
L = _ed.L
NLIMB = _xla.NLIMB

# MSB-first 4-bit digit count of s_b (s_b < L < 2^253; 64 digits = 256 bits)
_SB_WIN = 64

_IDENT_LIMBS = np.zeros((4, NLIMB), dtype=np.uint32)
_IDENT_LIMBS[1, 0] = 1  # (X, Y, Z, T) = (0, 1, 1, 0)
_IDENT_LIMBS[2, 0] = 1

_SB_NIELS = None


def _sb_niels() -> np.ndarray:
    """(16, 3, 20) niels table of [j]B — shared with the Pallas ladder's
    per-window table ([s]B off _build_b_niels; lazy import avoids a module
    cycle, ed25519_pallas imports this module for its RLC entry)."""
    global _SB_NIELS
    if _SB_NIELS is None:
        from tendermint_tpu.ops import ed25519_pallas as _pl

        _SB_NIELS = np.asarray(_pl._B_NIELS, dtype=np.uint32)
    return _SB_NIELS


def _pt_madd(p, ypx, ymx, t2d):
    """Batch-leading mixed add with a niels point (y+x, y-x, 2dxy), Z2 = 1.
    Mirror of ed25519_pallas.pt_madd in the XLA batch layout; the j=0 table
    entry (1, 1, 0) yields p unchanged up to projective scale, so digit 0
    needs no special-casing.  Branches on ops/ed25519_verify's trace-time
    carry-mode global like its pt_add/pt_double."""
    X1, Y1, Z1, T1 = p
    if _xla._CARRY_MODE == "lazy":
        A = _xla.fe_mul_l(_xla.fe_sub_l(Y1, X1), ymx)
        B = _xla.fe_mul_l(Y1 + X1, ypx)
        C = _xla.fe_mul_l(T1, t2d)
        Dv = Z1 + Z1
        E = _xla.fe_sub_l(B, A)
        F = _xla.fe_sub_l(Dv, C)
        G = _xla.fe_add_l(Dv, C)
        H = _xla.fe_add_l(B, A)
        return _xla.fe_mul4_f((E, F), (G, H), (F, G), (E, H))
    A = _xla.fe_mul(_xla.fe_sub(Y1, X1), ymx)
    B = _xla.fe_mul(_xla.fe_add(Y1, X1), ypx)
    C = _xla.fe_mul(T1, t2d)
    Dv = _xla.fe_add(Z1, Z1)
    E = _xla.fe_sub(B, A)
    F = _xla.fe_sub(Dv, C)
    G = _xla.fe_add(Dv, C)
    H = _xla.fe_add(B, A)
    return (_xla.fe_mul(E, F), _xla.fe_mul(G, H),
            _xla.fe_mul(F, G), _xla.fe_mul(E, H))


# ---------------------------------------------------------------------------
# Host-side schedule builder
# ---------------------------------------------------------------------------


def _pad_width(x: int, cap: int = 1024, floor: int = 8) -> int:
    """Power-of-two up to ``cap`` then cap-multiples — level widths stay on a
    small shape ladder so the jit cache is stable across RLC draws."""
    b = floor
    while b < x and b < cap:
        b *= 2
    if x <= b:
        return b
    return ((x + cap - 1) // cap) * cap


def _digit_matrix(scalars: Sequence[int], c: int, nwin: int) -> np.ndarray:
    """(m, nwin) c-bit digit matrix, LSB window first, vectorized."""
    m = len(scalars)
    nbytes = (nwin * c + 7) // 8
    buf = np.frombuffer(
        b"".join(int(k).to_bytes(nbytes, "little") for k in scalars), np.uint8
    ).reshape(m, nbytes)
    bits = np.unpackbits(buf, axis=1, bitorder="little")[:, : nwin * c]
    w = 1 << np.arange(c, dtype=np.uint32)
    return bits.reshape(m, nwin, c).astype(np.uint32) @ w


def _bucket_c(m: int) -> int:
    """Pippenger window width from the pair count — the host _msm ladder."""
    return 4 if m < 32 else 5 if m < 128 else 6 if m < 512 else 7 if m < 2048 else 8


class _Schedule:
    """Device-ready index schedules for one MSM (all host numpy)."""

    __slots__ = ("c", "nwin", "ias", "ibs", "bkt")

    def __init__(self, c, nwin, ias, ibs, bkt):
        self.c = c
        self.nwin = nwin
        self.ias = ias  # [(M_l,) int32] per tree level, indices into level l-1
        self.ibs = ibs
        self.bkt = bkt  # (nwin, 2^c - 1) int32 into [pool, lvl1..lvlT]


def _build_schedule(digits: np.ndarray, pool_rows: int, c: int) -> _Schedule:
    """Resolve the bucket segmented reduction into per-level pair indices.

    ``digits`` is the (m, nwin) matrix of pair digits; pair j's point lives
    at pool row j+1 (row 0 is the identity).  Returns level schedules whose
    row 0 is always the (0, 0) identity anchor that odd leftovers and pad
    rows pair against."""
    m, nwin = digits.shape
    nb = (1 << c) - 1
    pj, pw = np.nonzero(digits)
    dg = digits[pj, pw].astype(np.int64)
    bucket = pw.astype(np.int64) * nb + (dg - 1)
    order = np.argsort(bucket, kind="stable")
    bucket = bucket[order]
    src = (pj[order] + 1).astype(np.int64)
    ub, seg_start = np.unique(bucket, return_index=True)
    seg_sizes = np.diff(np.append(seg_start, len(bucket)))

    finalized: dict = {}
    active: List[Tuple[int, List[int]]] = []
    for si in range(len(ub)):
        mem = src[seg_start[si]: seg_start[si] + seg_sizes[si]].tolist()
        if len(mem) == 1:
            finalized[si] = (0, mem[0])  # lives in the pool
        else:
            active.append((si, mem))

    ias: List[np.ndarray] = []
    ibs: List[np.ndarray] = []
    lvl = 0
    while active:
        lvl += 1
        ia = [0]
        ib = [0]
        nxt = []
        for si, mem in active:
            new_rows = []
            for k in range(0, len(mem) - 1, 2):
                new_rows.append(len(ia))
                ia.append(mem[k])
                ib.append(mem[k + 1])
            if len(mem) % 2:
                # odd leftover rides through paired with the identity row
                new_rows.append(len(ia))
                ia.append(mem[-1])
                ib.append(0)
            if len(new_rows) == 1:
                finalized[si] = (lvl, new_rows[0])
            else:
                nxt.append((si, new_rows))
        width = _pad_width(len(ia))
        ia += [0] * (width - len(ia))
        ib += [0] * (width - len(ib))
        ias.append(np.asarray(ia, np.int32))
        ibs.append(np.asarray(ib, np.int32))
        active = nxt

    # global row offsets of each level inside the device concat
    offs = [pool_rows]
    for a in ias[:-1]:
        offs.append(offs[-1] + len(a))
    bkt = np.zeros((nwin, nb), np.int64)  # 0 = identity (empty bucket)
    for si, b in enumerate(ub):
        w, dm1 = divmod(int(b), nb)
        flvl, frow = finalized[si]
        bkt[w, dm1] = frow if flvl == 0 else offs[flvl - 1] + frow
    return _Schedule(c, nwin, ias, ibs, bkt.astype(np.int32))


# ---------------------------------------------------------------------------
# The device kernel
# ---------------------------------------------------------------------------


def _unpack(a):
    return tuple(a[..., k, :] for k in range(4))


def _pack(p):
    return jnp.stack(p, axis=-2)


def _msm_kernel(pool, ias, ibs, bkt_idx, sb_digs):
    """pool (R0, 4, 20) uint32; ias/ibs lists of (M_l,) int32; bkt_idx
    (nwin, 2^c - 1) int32 global rows; sb_digs (64,) uint32 MSB-first 4-bit
    digits of s_b.  Returns a () bool window verdict."""
    d2 = jnp.asarray(_xla._D2_LIMBS)
    nwin, nb = bkt_idx.shape
    c = (nb + 1).bit_length() - 1

    # segmented pairwise-reduction tree: one batched pt_add per level
    levels = [pool]
    prev = pool
    for ia, ib in zip(ias, ibs):
        prev = _pack(_xla.pt_add(_unpack(prev[ia]), _unpack(prev[ib]), d2))
        levels.append(prev)
    allrows = jnp.concatenate(levels, axis=0) if len(levels) > 1 else pool
    grid = allrows[bkt_idx]  # (nwin, nb, 4, 20)

    # bucket-weighted fold at width nwin: acc = sum_d d * bucket[d] via the
    # classic descending run/acc double accumulation
    ident_w = jnp.broadcast_to(jnp.asarray(_IDENT_LIMBS), (nwin, 4, NLIMB))

    def bucket_body(t, carry):
        run, acc = carry
        g = lax.dynamic_index_in_dim(grid, nb - 1 - t, axis=1, keepdims=False)
        run = _pack(_xla.pt_add(_unpack(run), _unpack(g), d2))
        acc = _pack(_xla.pt_add(_unpack(acc), _unpack(run), d2))
        return run, acc

    _, acc = lax.fori_loop(0, nb, bucket_body, (ident_w, ident_w))

    # Horner over the windows, top first: c doubles + 1 add per step (the
    # doubles are their own nested fori — one pt_double graph, not c copies:
    # unrolled carry graphs explode XLA CPU compile, see ladder_math)
    def dbl(_, p):
        return _xla.pt_double(p)

    tot = _unpack(lax.dynamic_slice_in_dim(acc, nwin - 1, 1, axis=0))

    def win_body(t, tot):
        tot = lax.fori_loop(0, c, dbl, tot)
        g = lax.dynamic_slice_in_dim(acc, nwin - 2 - t, 1, axis=0)
        return _xla.pt_add(tot, _unpack(g), d2)

    tot = lax.fori_loop(0, nwin - 1, win_body, tot)

    # [s_b]B off the niels window table: 4 doubles + 1 mixed add per digit
    nt = jnp.asarray(_sb_niels())
    ident1 = _unpack(jnp.asarray(_IDENT_LIMBS)[None])

    def sb_body(t, sb):
        sb = lax.fori_loop(0, 4, dbl, sb)
        ent = nt[lax.dynamic_index_in_dim(sb_digs, t, keepdims=False)]
        return _pt_madd(sb, ent[0][None], ent[1][None], ent[2][None])

    sb = lax.fori_loop(0, _SB_WIN, sb_body, ident1)

    X, Y, Z, _ = _xla.pt_add(tot, sb, d2)
    xc = _xla.fe_canonical(X)
    return (jnp.all(xc == 0)
            & jnp.all(_xla.fe_canonical(Y) == _xla.fe_canonical(Z)))


_msm_cache: dict = {}


def _compiled_msm(carry_mode: str):
    """One jitted kernel per carry mode — jax.jit's own cache keys the shape
    side (pool width, level widths, window count), so index schedules ride
    as dynamic arguments without retraces."""
    fn = _msm_cache.get(carry_mode)
    if fn is None:
        fn = jax.jit(_fc.trace_with_modes(_xla, _msm_kernel, carry_mode))
        _msm_cache[carry_mode] = fn
    return fn


# ---------------------------------------------------------------------------
# Host driver: one window RLC + chunk/ladder localization
# ---------------------------------------------------------------------------


def _device_rlc(rows, rng, carry_mode: str) -> bool:
    """One RLC over parsed rows [(neg_a, neg_r, h, s), ...] (extended-point
    int tuples) as a single device MSM dispatch.  z_i are drawn from ``rng``
    (seeded upstream — deterministic replay)."""
    n = len(rows)
    m = 2 * n
    c = _bucket_c(m)
    nwin = (253 + c - 1) // c
    s_b = 0
    scalars: List[int] = []
    pts = []
    for neg_a, neg_r, h, s in rows:
        z = rng.getrandbits(128) or 1
        s_b = (s_b + z * s) % L
        scalars.append((z * h) % L)
        pts.append(neg_a)
        scalars.append(z)
        pts.append(neg_r)
    digits = _digit_matrix(scalars, c, nwin)
    pool_rows = _pad_width(m + 1)
    sched = _build_schedule(digits, pool_rows, c)

    pool = np.zeros((pool_rows, 4, NLIMB), np.uint32)
    pool[0] = _IDENT_LIMBS
    for j, (x, y, _, t) in enumerate(pts):
        pool[j + 1, 0] = _xla.int_to_limbs(x)
        pool[j + 1, 1] = _xla.int_to_limbs(y)
        pool[j + 1, 2, 0] = 1
        pool[j + 1, 3] = _xla.int_to_limbs(t)
    sb_digs = np.asarray(
        [(s_b >> (4 * (_SB_WIN - 1 - t))) & 15 for t in range(_SB_WIN)],
        np.uint32,
    )
    ok = call_jit(
        _compiled_msm(carry_mode),
        jnp.asarray(pool),
        [jnp.asarray(a) for a in sched.ias],
        [jnp.asarray(b) for b in sched.ibs],
        jnp.asarray(sched.bkt),
        jnp.asarray(sb_digs),
    )
    return bool(ok)


def _chunk_rlc_holds(chunk, rng) -> bool:
    """Seeded host chunk RLC (crypto.ed25519._rlc_holds with our rng): the
    localization sweep stays cheap — 32-row Pippenger on the host — and
    deterministic under the window seed."""
    s_b = 0
    pairs = []
    for _, neg_a, neg_r, h, s in chunk:
        z = rng.getrandbits(128) or 1
        s_b = (s_b + z * s) % L
        pairs.append(((z * h) % L, neg_a))
        pairs.append((z, neg_r))
    acc = _ed._msm(pairs)
    return _ed._is_identity(_ed.pt_add(acc, _ed._mul_b(s_b)))


def rlc_resolve(
    parsed: list,
    out: list,
    ladder_fn: Callable[[List[int]], np.ndarray],
    *,
    seed: int,
    carry_mode: str = "lazy",
) -> None:
    """Verdict strategy for one window: device MSM accept-all on the clean
    path; on reject, host chunk RLCs (_CHUNK=32) localize the dirty spans
    and their rows ship to ``ladder_fn`` (the exact per-row device ladder)
    in ONE dispatch.  ``parsed``/``out`` as crypto.ed25519._parse_batch;
    mutates ``out`` in place."""
    if not parsed:
        return
    rng = random.Random(seed)
    rows = [(na, nr, h, s) for (_, na, nr, h, s) in parsed]
    if _device_rlc(rows, rng, carry_mode):
        for item in parsed:
            out[item[0]] = True
        return
    dirty: List[int] = []
    for lo in range(0, len(parsed), _ed._CHUNK):
        chunk = parsed[lo: lo + _ed._CHUNK]
        if len(chunk) > 4 and _chunk_rlc_holds(chunk, rng):
            for item in chunk:
                out[item[0]] = True
        else:
            dirty.extend(item[0] for item in chunk)
    if dirty:
        ok = np.asarray(ladder_fn(dirty))
        for j, i in enumerate(dirty):
            out[i] = bool(ok[j])
