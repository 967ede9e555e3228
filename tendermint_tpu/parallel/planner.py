"""Verification planner — ragged lane packing, bucketed compile cache, and a
double-buffered window pipeline.

Every window verifier in the tree ("verify the commits of H heights, each
with its own valset") routes through this module:

  * `blockchain/reactor.verify_block_window` — fast sync, flat and mesh;
  * `statesync/syncer._verify_backfill_window` — the trailing backfill;
  * `scripts/bench_fastsync.py --ragged-valsets` — the occupancy bench.

Why it exists: `parallel/commit_verify` packs a window into a dense
``(H, V)`` grid where ``V`` is the *largest* valset in the window.  On
ragged workloads (a backfill crossing valset changes, a chain mixing a
4-validator appchain epoch with a 100-validator epoch) most of that grid is
padding — lanes the device still pays full ladder cost for.  The planner
instead flattens the window into a 1-D *lane* tensor holding only real
votes, carrying a per-lane ``segment_id`` (the height each lane belongs
to), so the per-height quorum tally is a branch-free ``segment_sum``
instead of a ``val``-axis reduction over mostly-padding lanes.

Four mechanisms, one per class of waste:

  1. **Ragged lane packing** (`plan_window`): bin-pack every height's
     present votes into one lane axis; occupancy = Σ_h V_h / bucket(Σ V_h)
     instead of Σ_h V_h / (H × max_h V_h).
  2. **Shape-bucketed compilation** (`_compiled_step`): lanes pad to a
     power-of-two bucket (64..4096, then multiples of 4096 — the same
     ladder as `ops/ed25519_verify._bucket`) and segments to a power-of-two
     ≥ 8, so the jit step compiles once per ``(mesh, lane_bucket,
     seg_bucket)`` instead of once per window shape.  `compile_count()`
     exposes the exact number of compiles for tests and benches.
  3. **Pipelined dispatch** (`WindowPipeline`): the host prologue
     (SHA-512 of sign-bytes, point decompression, limb packing) for windows
     N+1..N+k runs on a worker thread while window N's device dispatch is
     in flight — JAX dispatch is async and the prologue is numpy/hashlib
     work that releases the GIL, so the two genuinely overlap
     (`planner.pack` / `planner.dispatch` trace spans make the overlap
     visible).  The depth k (`[verify] pipeline_depth`) bounds how many
     packed windows may wait in memory.
  4. **Multi-window superdispatch** (`plan_windows` / `verify_windows`):
     several *independent* windows bin-pack into ONE lane tile — the
     window id is a second segment level above the (height, valset)
     segment ids, so a single `segment_sum` pass yields per-height tallies
     for every window in the dispatch.  Small windows (RPC commit-verify
     bursts, light-frontend rows, backfill tails) stop paying a whole
     lane bucket each; on a mesh the shared tile shards across all
     devices so the pod verifies many windows per dispatch.  Per-device
     partial tallies can be reduced on host (`planner_reduce = "host"`,
     a psum-free lane-only gather) or on device (the default replicated
     `segment_sum`) — both are bit-identical int64 math.

Quorum semantics are the ONE shared implementation (`WindowVerdict`):
``committed[h] = tally[h] * 3 > totals[h] * 2`` (strict — an exact 2/3
tally must NOT commit) and ``sigs_ok[h]`` = no present vote of height h
failed verification (verify_commit parity: any invalid signature fails the
whole commit).  Callers translate the verdict into their own error types;
no quorum math lives in the callers anymore.
"""

from __future__ import annotations

import logging
import math
import queue
import random
import threading
import time
from dataclasses import dataclass
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.libs.profile import get_profiler

# (pubkey: PubKey object or raw 32-byte ed25519 key, msg, sig) or None
SigTuple = Tuple[object, bytes, bytes]

logger = logging.getLogger("tendermint_tpu.verify")

MIN_LANES = 64  # smallest lane bucket (matches ops/ed25519_verify._bucket)
MAX_POW2_LANES = 4096  # above this, buckets are multiples of 4096
MIN_SEGS = 8  # smallest segment (height) bucket


def lanes_bucket(n: int, mesh=None) -> int:
    """Lane pad size: powers of two 64..4096, then multiples of 4096; with a
    mesh, rounded up to a multiple of the device count so the lane axis
    shards evenly."""
    b = MIN_LANES
    while b < n and b < MAX_POW2_LANES:
        b *= 2
    if n > b:
        b = ((n + MAX_POW2_LANES - 1) // MAX_POW2_LANES) * MAX_POW2_LANES
    if mesh is not None:
        nd = int(mesh.devices.size)
        if b % nd:
            b = ((b + nd - 1) // nd) * nd
    return b


def segs_bucket(h: int) -> int:
    """Segment (height) pad size: power of two ≥ MIN_SEGS."""
    b = MIN_SEGS
    while b < h:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Planner configuration ([verify] section, node.configure_planner)
# ---------------------------------------------------------------------------

_DEFAULT_PIPELINE_DEPTH = 2
_DEFAULT_WINDOWS_PER_DEVICE = 4

_pipeline_depth = _DEFAULT_PIPELINE_DEPTH
_windows_per_device = _DEFAULT_WINDOWS_PER_DEVICE
_reduce_mode = "device"

REDUCE_MODES = ("device", "host")


def configure_planner(cfg=None) -> None:
    """Apply `[verify]` planner knobs (config.VerifyConfig); None restores
    the defaults.  Called from node wiring next to configure_device_guard."""
    global _pipeline_depth, _windows_per_device, _reduce_mode
    if cfg is None:
        _pipeline_depth = _DEFAULT_PIPELINE_DEPTH
        _windows_per_device = _DEFAULT_WINDOWS_PER_DEVICE
        _reduce_mode = "device"
        return
    _pipeline_depth = max(1, int(getattr(
        cfg, "pipeline_depth", _DEFAULT_PIPELINE_DEPTH)))
    _windows_per_device = max(1, int(getattr(
        cfg, "windows_per_device", _DEFAULT_WINDOWS_PER_DEVICE)))
    mode = str(getattr(cfg, "planner_reduce", "device") or "device").lower()
    if mode not in REDUCE_MODES:
        raise ValueError(
            f"planner_reduce must be one of {REDUCE_MODES}, got {mode!r}")
    _reduce_mode = mode


def pipeline_depth() -> int:
    """Configured WindowPipeline depth (packed windows in flight)."""
    return _pipeline_depth


def reduce_mode() -> str:
    """Where per-device partial segment tallies reduce: "device" (replicated
    segment_sum inside the sharded step) or "host" (the step returns only
    the lane-sharded verdicts — no cross-device collective — and the int64
    tallies fold on host; bit-identical either way)."""
    return _reduce_mode


def set_reduce_mode(mode: str) -> None:
    """Benches/tests: pick the tally reduction side directly."""
    global _reduce_mode
    if mode not in REDUCE_MODES:
        raise ValueError(
            f"planner_reduce must be one of {REDUCE_MODES}, got {mode!r}")
    _reduce_mode = mode


def windows_per_dispatch(mesh=None) -> int:
    """How many independent windows a superdispatch should fold: the
    configured per-device budget times the mesh device count — the pod's
    unit of parallelism is a window, so capacity scales with the pod."""
    nd = int(mesh.devices.size) if mesh is not None else 1
    return _windows_per_device * nd


def _pub_bytes(pk) -> bytes:
    """Raw key bytes for device packing: PubKey objects expose .bytes()."""
    b = getattr(pk, "bytes", None)
    return b() if callable(b) else bytes(pk)


@dataclass
class WindowPlan:
    """A ragged window flattened to lanes.  `coords[j] = (h, v)` maps lane j
    back to its grid cell; `seg_ids[j] = h` feeds the segment tallies.
    Malformed votes (wrong sig/pub length, undecompressable key) keep their
    lane — they must count as *failures*, not absences."""

    H: int
    V: int  # widest row (the ok-grid width)
    coords: np.ndarray  # (n, 2) int32
    seg_ids: np.ndarray  # (n,) int32, sorted ascending
    pubs: list  # lane pubkeys (PubKey objects or raw bytes)
    msgs: list
    sigs: list
    powers: np.ndarray  # (n,) int64
    wellformed: np.ndarray  # (n,) bool — ed25519-kernel-shaped (32B pub,
    # 64B sig).  A DEVICE-path precondition only: the ed25519 prologue can
    # ingest only shaped lanes, so unshaped ones auto-fail there (all lanes
    # of a device window are ed25519 by the all_ed25519 gate).  The host
    # path ignores this flag — secp256k1 (33B pubs), multisig aggregates
    # and odd sig lengths are legal there and verify_generic decides them.
    totals: np.ndarray  # (H,) int64 per-height total voting power
    dev: Optional[tuple] = None  # padded device tensors (pack_device)
    dev_shape: Optional[Tuple[int, int]] = None  # (lane bucket, seg bucket)
    pack_seconds: float = 0.0  # host plan+pack wall time (cost ledger)
    # multi-window superdispatch bookkeeping (plan_windows): the window id
    # is a second segment level ABOVE the height segment ids — heights of
    # window w occupy rows [row_offsets[w], row_offsets[w+1]), so the
    # global seg_ids stay sorted and one segment_sum pass tallies every
    # window.  window_ids maps each lane to its window; window_V keeps each
    # window's own grid width so split_verdict can hand back grids shaped
    # exactly as the flat per-window path would have.
    n_windows: int = 1
    row_offsets: Optional[np.ndarray] = None  # (n_windows+1,) int64
    window_ids: Optional[np.ndarray] = None  # (n,) int32 per-lane window id
    window_V: Optional[List[int]] = None  # per-window grid width

    @property
    def n_lanes(self) -> int:
        return len(self.pubs)

    def all_ed25519(self) -> bool:
        """True when every lane can ride the ed25519 device kernel (raw
        32-byte keys or PubKeyEd25519 objects; malformed lanes are handled
        host-side either way)."""
        from tendermint_tpu.crypto.keys import PubKey, PubKeyEd25519

        for pk in self.pubs:
            if isinstance(pk, PubKey) and not isinstance(pk, PubKeyEd25519):
                return False
        return True


@dataclass
class WindowVerdict:
    """Per-height outcome of one planned window — the single home of the
    quorum math shared by fast sync, state sync, and the benches."""

    ok: np.ndarray  # (H, V) bool — per-vote verdict grid
    tally: np.ndarray  # (H,) int64 — voting power of valid signatures
    committed: np.ndarray  # (H,) bool — tally*3 > total*2 (STRICT)
    sigs_ok: np.ndarray  # (H,) bool — no present vote failed
    lanes_present: int  # real votes dispatched
    lanes_dispatched: int  # lanes after bucket padding (0 for host path)

    @property
    def occupancy(self) -> float:
        if self.lanes_dispatched <= 0:
            return 1.0
        return self.lanes_present / self.lanes_dispatched


def plan_window(
    votes: Sequence[Sequence[Optional[SigTuple]]],
    powers: Sequence[Sequence[int]],
    totals: Sequence[int],
) -> WindowPlan:
    """Flatten ragged (height, valset) rows into lanes.  ``votes[h][v]`` is
    ``(pub, msg, sig)`` or None (absent/nil); ``powers[h][v]`` the voting
    power; ``totals[h]`` the height's total power (valsets may differ per
    height — state sync's backfill crosses valset changes)."""
    H = len(votes)
    if len(totals) != H or len(powers) != H:
        raise ValueError("votes, powers and totals must have one row per height")
    V = max((len(row) for row in votes), default=0)
    coords: List[Tuple[int, int]] = []
    pubs, msgs, sigs = [], [], []
    pw: List[int] = []
    wf: List[bool] = []
    for h, row in enumerate(votes):
        prow = powers[h]
        for v, item in enumerate(row):
            if item is None:
                continue
            pub, msg, sig = item
            coords.append((h, v))
            pubs.append(pub)
            msgs.append(bytes(msg))
            sigs.append(bytes(sig))
            pw.append(prow[v])
            wf.append(len(sig) == 64 and len(_pub_bytes(pub)) == 32)
    n = len(coords)
    coords_a = (
        np.asarray(coords, dtype=np.int32)
        if n
        else np.zeros((0, 2), dtype=np.int32)
    )
    return WindowPlan(
        H=H,
        V=V,
        coords=coords_a,
        seg_ids=np.ascontiguousarray(coords_a[:, 0]),
        pubs=pubs,
        msgs=msgs,
        sigs=sigs,
        powers=np.asarray(pw, dtype=np.int64),
        wellformed=np.asarray(wf, dtype=bool),
        totals=np.asarray(list(totals), dtype=np.int64),
    )


def plan_windows(
    specs: Sequence[Tuple[Sequence, Sequence, Sequence]],
) -> WindowPlan:
    """Bin-pack several *independent* windows into ONE lane tile.

    Each spec is a `(votes, powers, totals)` triple exactly as
    `plan_window` takes them.  Window w's height rows land at
    [row_offsets[w], row_offsets[w+1]) of the combined plan, so the
    per-lane seg_ids remain globally sorted and the existing bucketed step
    — verify kernel + one segment_sum — serves every window in a single
    dispatch.  `split_verdict` recovers the per-window verdicts, each
    bit-identical to what a flat `verify_window(spec)` would have said."""
    specs = list(specs)
    if not specs:
        raise ValueError("plan_windows needs at least one window spec")
    votes_all: List[Sequence] = []
    powers_all: List[Sequence] = []
    totals_all: List[int] = []
    row_offsets = [0]
    window_V: List[int] = []
    for votes, powers, totals in specs:
        votes_all.extend(votes)
        powers_all.extend(powers)
        totals_all.extend(list(totals))
        row_offsets.append(len(votes_all))
        window_V.append(max((len(row) for row in votes), default=0))
    plan = plan_window(votes_all, powers_all, totals_all)
    plan.n_windows = len(specs)
    plan.row_offsets = np.asarray(row_offsets, dtype=np.int64)
    plan.window_V = window_V
    if plan.seg_ids.size:
        plan.window_ids = np.searchsorted(
            plan.row_offsets[1:], plan.seg_ids, side="right"
        ).astype(np.int32)
    else:
        plan.window_ids = np.zeros((0,), dtype=np.int32)
    return plan


def split_verdict(plan: WindowPlan, verdict: WindowVerdict) -> List[WindowVerdict]:
    """Slice a superdispatch verdict back into per-window verdicts.

    Each sub-verdict's grid uses the window's OWN width (window_V), so
    callers comparing against the flat single-window path see identical
    shapes.  lanes_dispatched carries the shared lane tile's bucket: the
    windows paid for it together, so per-window occupancy is reported
    against the whole tile (the superdispatch's occupancy is the honest
    one; WindowVerdict.occupancy of a slice under-reports by design)."""
    if plan.n_windows <= 1 or plan.row_offsets is None:
        return [verdict]
    out: List[WindowVerdict] = []
    offs = plan.row_offsets
    for w in range(plan.n_windows):
        a, b = int(offs[w]), int(offs[w + 1])
        Vw = plan.window_V[w] if plan.window_V is not None else plan.V
        lanes_w = int(np.count_nonzero(plan.window_ids == w)) if (
            plan.window_ids is not None
        ) else 0
        out.append(WindowVerdict(
            ok=np.ascontiguousarray(verdict.ok[a:b, :Vw]),
            tally=verdict.tally[a:b].copy(),
            committed=verdict.committed[a:b].copy(),
            sigs_ok=verdict.sigs_ok[a:b].copy(),
            lanes_present=lanes_w,
            lanes_dispatched=verdict.lanes_dispatched,
        ))
    return out


def pack_device(plan: WindowPlan, mesh=None) -> WindowPlan:
    """Host prologue for the device path: SHA-512 + decompress + limb-pack
    every wellformed lane, padded to the (lane, segment) bucket.  This is
    the expensive host work `WindowPipeline` overlaps with dispatch."""
    from tendermint_tpu.ops import ed25519_verify as _k

    if plan.dev is not None:
        return plan
    n = plan.n_lanes
    B = lanes_bucket(n, mesh)
    S = segs_bucket(plan.H)
    z = np.zeros
    neg_ax = z((B, _k.NLIMB), np.uint32)
    ay = z((B, _k.NLIMB), np.uint32)
    s_words = z((B, 8), np.uint32)
    h_words = z((B, 8), np.uint32)
    r_limbs = z((B, _k.NLIMB), np.uint32)
    r_sign = z((B,), np.uint32)
    present = z((B,), bool)
    is_vote = z((B,), bool)
    power = z((B,), np.int64)
    # padding lanes point at the LAST segment, not segment 0: real lanes
    # end at seg ≤ H-1 ≤ S-1, so the array stays monotonically
    # non-decreasing and segment_sum's indices_are_sorted=True contract
    # holds (padding carries zero power and is_vote=False, so the S-1
    # tallies are unaffected)
    seg_ids = np.full((B,), S - 1, np.int32)
    if n:
        is_vote[:n] = True
        seg_ids[:n] = plan.seg_ids
        idx = np.flatnonzero(plan.wellformed)
        if idx.size:
            pubs_a = np.frombuffer(
                b"".join(_pub_bytes(plan.pubs[j]) for j in idx), np.uint8
            ).reshape(idx.size, 32)
            sigs_a = np.frombuffer(
                b"".join(plan.sigs[j] for j in idx), np.uint8
            ).reshape(idx.size, 64)
            msgs_l = [plan.msgs[j] for j in idx]
            nax, a_y, s_w, h_w, r_l, r_s, valid = _k.host_prologue(
                pubs_a, msgs_l, sigs_a
            )
            neg_ax[idx] = nax
            ay[idx] = a_y
            s_words[idx] = s_w
            h_words[idx] = h_w
            r_limbs[idx] = r_l
            r_sign[idx] = r_s
            present[idx] = valid
        power[:n] = np.where(present[:n], plan.powers, 0)
    totals = np.zeros((S,), np.int64)
    totals[: plan.H] = plan.totals
    plan.dev = (
        neg_ax, ay, s_words, h_words, r_limbs, r_sign,
        present, is_vote, power, seg_ids, totals,
    )
    plan.dev_shape = (B, S)
    return plan


# ---------------------------------------------------------------------------
# The bucketed device step
# ---------------------------------------------------------------------------


def _planner_step(
    neg_ax, ay, s_words, h_words, r_limbs, r_sign,
    present, is_vote, power, seg_ids, totals,
):
    """One lane-packed verify + segment-tally step.  The quorum tally is a
    segment-sum over the lane axis (sorted segment ids), so a height's
    tally costs its own lanes — not the widest valset's."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519_verify as _k

    raw = _k._verify_kernel(neg_ax, ay, s_words, h_words, r_limbs, r_sign)
    ok = raw & present
    S = totals.shape[0]
    tally = jax.ops.segment_sum(
        jnp.where(ok, power, jnp.zeros_like(power)), seg_ids,
        num_segments=S, indices_are_sorted=True,
    )
    nbad = jax.ops.segment_sum(
        (is_vote & ~ok).astype(jnp.int32), seg_ids,
        num_segments=S, indices_are_sorted=True,
    )
    committed = tally * 3 > totals * 2
    return ok, tally, committed, nbad


_step_cache: dict = {}
_compiles = 0
_cache_mtx = threading.Lock()


def compile_count() -> int:
    """Planner step compiles since process start / last reset_cache() —
    the honest compile counter the bucket design is judged by."""
    return _compiles


def reset_cache() -> None:
    """Drop the compiled-step cache and zero the compile counter (tests)."""
    global _compiles
    with _cache_mtx:
        _step_cache.clear()
        _compiles = 0


def _planner_step_lanes(
    neg_ax, ay, s_words, h_words, r_limbs, r_sign,
    present, is_vote, power, seg_ids, totals,
):
    """Host-reduction step variant: verify only, NO cross-device work.  The
    lane-sharded verdict vector is the whole output — each device touches
    just its own lane shard (psum-free), and the int64 segment tallies fold
    on host (`_host_reduce`), bit-identically to the device segment_sum."""
    from tendermint_tpu.ops import ed25519_verify as _k

    raw = _k._verify_kernel(neg_ax, ay, s_words, h_words, r_limbs, r_sign)
    return raw & present


# the carry schedule the planner's device steps trace with (the batch
# verifier's optimized schedule), and their dispatch records' label
_CARRY_MODE = "lazy"


def _compiled_step(mesh, B: int, S: int, reduce: str = "device"):
    """jit'd step for one (mesh, lane bucket, seg bucket, reduction side);
    returns (fn, compiled) where compiled marks a cache miss (a real jit
    trace — padded shapes are fixed per bucket, so key miss == recompile)."""
    global _compiles
    import jax

    from tendermint_tpu.ops import ed25519_verify as _k
    from tendermint_tpu.ops import fe_common as _fc

    key = (mesh, B, S, reduce)
    with _cache_mtx:
        fn = _step_cache.get(key)
        if fn is not None:
            return fn, False
        body = _planner_step_lanes if reduce == "host" else _planner_step
        step = _fc.trace_with_modes(_k, body, _CARRY_MODE)
        if mesh is None:
            fn = jax.jit(step)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            # lanes shard over EVERY mesh axis (the planner's lane axis is
            # the product of the caller's height × val axes); the small
            # per-segment outputs replicate
            lane = NamedSharding(mesh, PS(tuple(mesh.axis_names)))
            rep = NamedSharding(mesh, PS())
            fn = jax.jit(
                step,
                in_shardings=(lane,) * 10 + (rep,),
                out_shardings=(
                    lane if reduce == "host" else (lane, rep, rep, rep)
                ),
            )
        _step_cache[key] = fn
        _compiles += 1
        return fn, True


def _host_reduce(plan: WindowPlan, ok_l: np.ndarray):
    """Fold the lane verdicts into per-height int64 tallies on host — the
    exact integer math the device segment_sum does, minus the collective.
    Every dispatched lane [:n] is a vote, so nbad per height is simply the
    count of its failed lanes."""
    tally = np.zeros((plan.H,), dtype=np.int64)
    nbad = np.zeros((plan.H,), dtype=np.int64)
    if plan.n_lanes:
        np.add.at(tally, plan.seg_ids[ok_l], plan.powers[ok_l])
        np.add.at(nbad, plan.seg_ids[~ok_l], 1)
    committed = tally * 3 > plan.totals * 2
    return tally, committed, nbad


# whether the MSM kernel has dispatched in this process — the first dispatch
# pays the jit trace/compile
_msm_warm = False


def _execute_device_msm(plan: WindowPlan, mesh=None) -> WindowVerdict:
    """One MSM per window ([verify] ed25519_path = msm): every lane folds
    into a single random-linear-combination Pippenger multi-scalar
    multiplication (ops/ed25519_msm) instead of one ladder per lane.  The
    verdict equation has no lane axis to shard, so the mesh is not
    consulted.  A rejected window localizes inside rlc_verify_batch —
    chunk RLCs then exact ladder rows — keeping accept/reject
    bit-identical to the per-lane path, and the PR 9 guard/audit wrapping
    (_execute_device_guarded) applies unchanged."""
    global _msm_warm
    from tendermint_tpu.ops import ed25519_verify as _k

    n = plan.n_lanes
    ok_l = np.zeros((n,), dtype=bool)
    wf = np.asarray(plan.wellformed, dtype=bool)
    rows = np.nonzero(wf)[0] if n else np.zeros((0,), dtype=np.int64)
    first = not _msm_warm
    t0 = time.perf_counter()
    with trace.span(
        "planner.dispatch", backend="planner_msm", H=plan.H, lanes=n, n=n,
        windows=plan.n_windows, compiled=first,
    ):
        if rows.size:
            pubs_a = np.frombuffer(
                b"".join(_pub_bytes(plan.pubs[j]) for j in rows),
                dtype=np.uint8,
            ).reshape(rows.size, 32)
            sigs_a = np.frombuffer(
                b"".join(bytes(plan.sigs[j]) for j in rows),
                dtype=np.uint8,
            ).reshape(rows.size, 64)
            ok_l[rows] = _k.rlc_verify_batch(
                pubs_a, [plan.msgs[j] for j in rows], sigs_a,
                carry_mode=_CARRY_MODE,
            )
    _msm_warm = True
    dt = time.perf_counter() - t0
    tally, committed, nbad = _host_reduce(plan, ok_l)
    try:
        m = get_verify_metrics()
        m.record_planner(n, n, compiled=first)
        m.record_dispatch(
            "planner_msm", "ed25519", n, dt,
            rejects=int(np.count_nonzero(wf & ~ok_l)),
            first=first, carry_mode=_CARRY_MODE, ed25519_path="msm",
        )
        get_profiler().record(
            "planner_msm",
            bucket=(n, plan.H),
            lanes_present=n,
            lanes_dispatched=n,
            heights=plan.H,
            pack_seconds=plan.pack_seconds,
            run_seconds=dt,
            compiled=first,
            # upload ≈ the extended-point pool: 2 points per pair row,
            # 4 coords x 20 uint32 limbs each (schedule indices are noise)
            bytes_to_device=int(rows.size) * 2 * 4 * 20 * 4,
            carry_mode=_CARRY_MODE,
            ed25519_path="msm",
            n_windows=plan.n_windows,
            n_devices=1,
        )
    except Exception:
        pass
    ok = np.zeros((plan.H, plan.V), dtype=bool)
    if n:
        ok[plan.coords[:, 0], plan.coords[:, 1]] = ok_l
    return WindowVerdict(
        ok=ok,
        tally=tally.astype(np.int64, copy=False),
        committed=committed,
        sigs_ok=nbad == 0,
        lanes_present=n,
        lanes_dispatched=n,
    )


def _execute_device(plan: WindowPlan, mesh=None) -> WindowVerdict:
    import jax

    from tendermint_tpu.ops.dispatch import call_jit
    from tendermint_tpu.crypto.batch import _resolve_ed25519_path

    if _resolve_ed25519_path(None) == "msm":
        return _execute_device_msm(plan, mesh)
    pack_device(plan, mesh)
    B, S = plan.dev_shape
    n = plan.n_lanes

    reduce = _reduce_mode
    fn, compiled = _compiled_step(mesh, B, S, reduce)
    t0 = time.perf_counter()
    backend = "planner_mesh" if mesh is not None else "planner"
    with trace.span(
        "planner.dispatch", backend=backend, H=plan.H, lanes=B, n=n,
        windows=plan.n_windows, compiled=compiled,
    ):
        # int64 powers: same consensus-safety reasoning as commit_verify —
        # without x64 the tally silently wraps at 2^31
        with jax.enable_x64(True):
            arrs = plan.dev
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as PS

                lane = NamedSharding(mesh, PS(tuple(mesh.axis_names)))
                rep = NamedSharding(mesh, PS())
                arrs = [jax.device_put(a, lane) for a in arrs[:-1]] + [
                    jax.device_put(arrs[-1], rep)
                ]
            if reduce == "host":
                ok_l = np.asarray(call_jit(fn, *arrs))[:n]
                tally, committed, nbad = _host_reduce(plan, ok_l)
            else:
                ok_l, tally, committed, nbad = call_jit(fn, *arrs)
                ok_l = np.asarray(ok_l)[:n]
                tally = np.asarray(tally)[: plan.H]
                committed = np.asarray(committed)[: plan.H]
                nbad = np.asarray(nbad)[: plan.H]
    dt = time.perf_counter() - t0
    n_devices = int(mesh.devices.size) if mesh is not None else 1
    try:
        m = get_verify_metrics()
        m.record_planner(n, B, compiled=compiled)
        # rejects = lanes that passed the host prechecks but failed the
        # device verify (same definition as commit_verify)
        m.record_dispatch(
            backend, "ed25519", n, dt,
            rejects=int(np.count_nonzero(plan.dev[6][:n] & ~ok_l)),
            first=compiled,
            carry_mode=_CARRY_MODE,
            ed25519_path="ladder",
        )
        if mesh is not None:
            m.record_device_shards(
                (d.id for d in mesh.devices.flat), B // n_devices)
        else:
            m.record_device_shards((jax.devices()[0].id,), B)
        get_profiler().record(
            backend,
            bucket=(B, S),
            lanes_present=n,
            lanes_dispatched=B,
            heights=plan.H,
            pack_seconds=plan.pack_seconds,
            run_seconds=dt,
            compiled=compiled,
            bytes_to_device=sum(a.nbytes for a in plan.dev),
            carry_mode=_CARRY_MODE,
            ed25519_path="ladder",
            n_windows=plan.n_windows,
            n_devices=n_devices,
        )
    except Exception:
        pass
    ok = np.zeros((plan.H, plan.V), dtype=bool)
    if n:
        ok[plan.coords[:, 0], plan.coords[:, 1]] = ok_l
    return WindowVerdict(
        ok=ok,
        tally=tally.astype(np.int64, copy=False),
        committed=committed,
        sigs_ok=nbad == 0,
        lanes_present=n,
        lanes_dispatched=B,
    )


def _execute_host(plan: WindowPlan, verifier=None) -> WindowVerdict:
    """Lane verification through the BatchVerifier boundary (verify_generic
    — mixed key types, custom verifiers, the process default backend), with
    the SAME segment tallies in numpy.  int64 throughout: np.bincount would
    round powers through float64.

    EVERY present lane goes through verify_generic — secp256k1 (33-byte
    pubs, DER sigs), multisig aggregates and odd sig lengths are decided
    per key type there, not pre-filtered by the ed25519 shape check (that
    check is a device-kernel precondition, not a validity rule).  The one
    structural failure decided here: a raw (non-PubKey) key that is not 32
    bytes cannot be any key type we speak — its lane fails."""
    from tendermint_tpu.crypto.batch import verify_generic
    from tendermint_tpu.crypto.keys import PubKey, PubKeyEd25519

    t0 = time.perf_counter()
    n = plan.n_lanes
    ok_l = np.zeros((n,), dtype=bool)
    if n:
        idx: List[int] = []
        pub_objs = []
        for j in range(n):
            pk = plan.pubs[j]
            if not isinstance(pk, PubKey):
                try:
                    pk = PubKeyEd25519(bytes(pk))
                except (ValueError, TypeError):
                    continue  # wrong-length raw key: lane stays failed
            idx.append(j)
            pub_objs.append(pk)
        if idx:
            ok_l[np.asarray(idx)] = verify_generic(
                pub_objs,
                [plan.msgs[j] for j in idx],
                [plan.sigs[j] for j in idx],
                verifier=verifier,
            )
    tally = np.zeros((plan.H,), dtype=np.int64)
    nbad = np.zeros((plan.H,), dtype=np.int64)
    if n:
        np.add.at(tally, plan.seg_ids[ok_l], plan.powers[ok_l])
        np.add.at(nbad, plan.seg_ids[~ok_l], 1)
    ok = np.zeros((plan.H, plan.V), dtype=bool)
    if n:
        ok[plan.coords[:, 0], plan.coords[:, 1]] = ok_l
    try:
        # the host path is a real dispatch too (it IS the production path
        # without a mesh) — ledger it so dump_profile never comes up empty
        get_profiler().record(
            "host",
            lanes_present=n,
            lanes_dispatched=0,
            heights=plan.H,
            pack_seconds=plan.pack_seconds,
            run_seconds=time.perf_counter() - t0,
            n_windows=plan.n_windows,
        )
    except Exception:
        pass
    return WindowVerdict(
        ok=ok,
        tally=tally,
        committed=tally * 3 > plan.totals * 2,
        sigs_ok=nbad == 0,
        lanes_present=n,
        lanes_dispatched=0,
    )


# ---------------------------------------------------------------------------
# Fault-tolerant device dispatch (libs/breaker.py)
# ---------------------------------------------------------------------------

# chaos/test seam: when set, replaces the raw device executor so seeded
# fail/hang/corrupt schedules (sim/faults.FaultyDevice) can drive the guard
_device_executor = None

_audit_mtx = threading.Lock()
_audit_seq = 0


def set_device_executor(fn=None) -> None:
    """Install a replacement for `_execute_device` (same signature); None
    restores the real one.  The guard — breaker, deadline, retry, audit,
    host fallback — wraps whatever is installed, which is exactly what
    makes the fault path chaos-testable."""
    global _device_executor
    _device_executor = fn


def _note_device_fallback(reason: str, plan: WindowPlan) -> None:
    # every host completion of a device dispatch is said out loud
    logger.warning(
        "planner dispatch completed on the host: reason=%s heights=%d "
        "lanes=%d", reason, plan.H, plan.n_lanes,
    )
    try:
        get_verify_metrics().device_fallback.add(1.0, (reason,))
    except Exception:
        pass
    try:
        get_profiler().record_event(
            "device_fallback", reason=reason, backend="planner",
            heights=plan.H, lanes=plan.n_lanes,
        )
    except Exception:
        pass


def _audit_device_verdict(plan: WindowPlan, verdict: WindowVerdict) -> bool:
    """Silent-corruption audit: re-verify k seeded-sampled wellformed lanes
    on the host oracle and compare with the device verdict.  True iff any
    lane disagrees.  Only wellformed lanes are sampled — unshaped lanes
    auto-fail on the device by construction, so they carry no signal about
    kernel correctness."""
    from tendermint_tpu.libs.breaker import guard_config

    cfg = guard_config()
    rate = cfg.audit_sample_rate
    if rate <= 0 or plan.n_lanes == 0:
        return False
    cand = np.flatnonzero(plan.wellformed)
    if cand.size == 0:
        return False
    global _audit_seq
    with _audit_mtx:
        seq = _audit_seq
        _audit_seq += 1
    k = min(int(cand.size), max(1, int(math.ceil(cand.size * rate))))
    rng = random.Random((cfg.audit_seed << 20) ^ seq)
    lanes = rng.sample([int(j) for j in cand], k)
    from tendermint_tpu.crypto import ed25519 as _ed

    bad = []
    for j in lanes:
        pb = _pub_bytes(plan.pubs[j])
        host_ok = _ed.verify(pb, plan.msgs[j], plan.sigs[j])
        dev_ok = bool(verdict.ok[plan.coords[j, 0], plan.coords[j, 1]])
        if host_ok != dev_ok:
            bad.append(j)
    try:
        m = get_verify_metrics()
        if k - len(bad):
            m.device_audit.add(float(k - len(bad)), ("ok",))
        if bad:
            m.device_audit.add(float(len(bad)), ("mismatch",))
    except Exception:
        pass
    if bad:
        try:
            get_profiler().record_event(
                "audit_mismatch", backend="planner", heights=plan.H,
                sampled=k, mismatches=len(bad), lanes=bad[:8],
            )
        except Exception:
            pass
    return bool(bad)


def _execute_device_guarded(
    plan: WindowPlan, mesh=None, verifier=None
) -> WindowVerdict:
    """`_execute_device` behind the full dispatch guard: breaker gate →
    supervised deadline → bounded retry → bit-identical completion via
    `_execute_host`, plus the silent-corruption audit whose mismatch
    quarantines the device path (operator reset required).  A caller can
    always rely on getting a verdict back — never a device exception, a
    hang, or an unaudited device result."""
    from tendermint_tpu.libs import breaker as _brk

    br = _brk.get_device_breaker()
    cfg = _brk.guard_config()
    exe = _device_executor if _device_executor is not None else _execute_device
    if not br.allow():
        reason = (
            "quarantined" if br.state == _brk.QUARANTINED else "breaker_open"
        )
        _note_device_fallback(reason, plan)
        return _execute_host(plan, verifier=verifier)
    attempts = 0
    while True:
        try:
            verdict = _brk.supervised_call(
                lambda: exe(plan, mesh), cfg.dispatch_deadline,
                name="planner-window",
            )
        except Exception as e:
            reason = (
                "timeout" if isinstance(e, _brk.DispatchTimeout) else "error"
            )
            br.record_failure(reason)
            attempts += 1
            if attempts <= cfg.retries and br.allow():
                try:
                    get_verify_metrics().device_retries.add(1.0)
                except Exception:
                    pass
                continue
            _note_device_fallback(reason, plan)
            return _execute_host(plan, verifier=verifier)
        if _audit_device_verdict(plan, verdict):
            # the device returned verdicts that disagree with the host
            # oracle — a safety bug, not a perf bug.  Latch it out of
            # service and recompute the whole window on the host; the
            # sampled lanes say nothing about the unsampled ones.
            br.quarantine("audit_mismatch:planner")
            _note_device_fallback("audit_mismatch", plan)
            return _execute_host(plan, verifier=verifier)
        br.record_success()
        return verdict


def execute_plan(
    plan: WindowPlan, mesh=None, verifier=None, use_device: Optional[bool] = None
) -> WindowVerdict:
    """Run a planned window.  use_device None → device iff a mesh was given;
    True routes the jit lane kernel (falling back to the verifier path when
    a lane's key type can't ride it); False goes through the BatchVerifier
    boundary (which itself may be a device backend — pallas in production)."""
    if use_device is None:
        use_device = mesh is not None
    if use_device and plan.all_ed25519():
        return _execute_device_guarded(plan, mesh=mesh, verifier=verifier)
    # its own time: the lane loop before verify.generic and the segment
    # tallies after it (the device paths draw planner.dispatch instead)
    with trace.span("planner.execute", lanes=plan.n_lanes, H=plan.H):
        return _execute_host(plan, verifier=verifier)


def verify_window(
    votes: Sequence[Sequence[Optional[SigTuple]]],
    powers: Sequence[Sequence[int]],
    totals: Sequence[int],
    mesh=None,
    verifier=None,
    use_device: Optional[bool] = None,
) -> WindowVerdict:
    """plan + execute in one call — the synchronous entry point."""
    t0 = time.perf_counter()
    with trace.span("planner.pack", H=len(votes)):
        plan = plan_window(votes, powers, totals)
        if (use_device or (use_device is None and mesh is not None)) and (
            plan.all_ed25519()
        ):
            pack_device(plan, mesh)
    plan.pack_seconds = time.perf_counter() - t0
    return execute_plan(plan, mesh=mesh, verifier=verifier, use_device=use_device)


def _plan_and_execute_windows(
    specs: Sequence[Tuple[Sequence, Sequence, Sequence]],
    mesh=None,
    verifier=None,
    use_device: Optional[bool] = None,
) -> Tuple[WindowPlan, WindowVerdict]:
    """Superdispatch plumbing shared by verify_windows and LaneFeed: pack
    every spec into one lane tile, run it through execute_plan (the SAME
    guarded path single windows take — breaker, deadline, retry, audit and
    host fallback all engage per superdispatch), return plan + combined
    verdict."""
    t0 = time.perf_counter()
    with trace.span(
        "planner.pack",
        H=sum(len(v) for v, _, _ in specs),
        windows=len(specs),
    ):
        plan = plan_windows(specs)
        if (use_device or (use_device is None and mesh is not None)) and (
            plan.all_ed25519()
        ):
            pack_device(plan, mesh)
    plan.pack_seconds = time.perf_counter() - t0
    verdict = execute_plan(
        plan, mesh=mesh, verifier=verifier, use_device=use_device)
    return plan, verdict


def verify_windows(
    specs: Sequence[Tuple[Sequence, Sequence, Sequence]],
    mesh=None,
    verifier=None,
    use_device: Optional[bool] = None,
) -> List[WindowVerdict]:
    """Verify several independent windows in ONE superdispatch.

    Each spec is a `(votes, powers, totals)` triple as `verify_window`
    takes them; the returned list is index-aligned with `specs` and each
    verdict is bit-identical to `verify_window(*spec)` on the flat host
    path.  One lane tile, one compile bucket, one guarded dispatch — this
    is how many small windows (RPC commit bursts, frontend rows, backfill
    tails) stop paying a whole padded bucket each."""
    specs = list(specs)
    if not specs:
        return []
    plan, verdict = _plan_and_execute_windows(
        specs, mesh=mesh, verifier=verifier, use_device=use_device)
    return split_verdict(plan, verdict)


def rows_from_commit(precommits, pubkeys, msgs, sigs, powers):
    """Adapt `ValidatorSet.collect_commit_sigs` outputs (aligned, non-nil
    precommits in index order) into one planner row — shared by fast sync
    and state sync so the two can never drift."""
    vrow: List[Optional[SigTuple]] = []
    prow: List[int] = []
    j = 0
    for pc in precommits:
        if pc is None:
            vrow.append(None)
            prow.append(0)
        else:
            vrow.append((pubkeys[j], msgs[j], sigs[j]))
            prow.append(powers[j])
            j += 1
    return vrow, prow


# ---------------------------------------------------------------------------
# Double-buffered window pipeline
# ---------------------------------------------------------------------------


class WindowPipeline:
    """Overlap host packing with device dispatch across a stream of windows.

    A daemon worker thread runs `plan_window` + `pack_device` (SHA-512,
    point decompression, limb packing — the measured host slice) for
    windows N+1..N+depth while the consumer's dispatch for window N is in
    flight; a bounded queue keeps at most `depth` packed windows in
    memory.  Depth > 2 keeps the chips fed when pack time fluctuates
    (mixed window sizes) — the default comes from `[verify]
    pipeline_depth` via configure_planner.  Exceptions from the spec
    iterator or the packer re-raise at the consuming side, in order, so
    callers keep their normal error handling."""

    def __init__(self, mesh=None, verifier=None,
                 use_device: Optional[bool] = None,
                 prefetch: Optional[int] = None,
                 depth: Optional[int] = None):
        self.mesh = mesh
        self.verifier = verifier
        self.use_device = use_device
        # `depth` is the configured name; `prefetch` stays as the original
        # spelling for existing callers — both mean the same bound
        d = depth if depth is not None else prefetch
        self.prefetch = max(1, int(d) if d is not None else _pipeline_depth)

    @property
    def depth(self) -> int:
        return self.prefetch

    def _execute_one(self, plan: WindowPlan) -> WindowVerdict:
        """One window's dispatch.  A device-path exception that somehow
        escapes the guard (a guard bug, a raw executor installed without
        it) must not abandon the queued and in-flight windows behind it:
        this window completes bit-identically on the host and the stream
        keeps going.  Host-path exceptions re-raise — they are input bugs,
        not device faults, and retrying the same path cannot help."""
        try:
            return execute_plan(
                plan, mesh=self.mesh, verifier=self.verifier,
                use_device=self.use_device,
            )
        except Exception:
            dev = self.use_device if self.use_device is not None else (
                self.mesh is not None
            )
            if not (dev and plan.all_ed25519()):
                raise
            from tendermint_tpu.libs.breaker import get_device_breaker

            get_device_breaker().record_failure("pipeline_error")
            _note_device_fallback("pipeline_error", plan)
            return _execute_host(plan, verifier=self.verifier)

    def run(
        self, specs: Iterable[Tuple[Sequence, Sequence, Sequence]]
    ) -> Iterator[WindowVerdict]:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        use_device = self.use_device
        mesh = self.mesh

        def _put(item) -> bool:
            """Bounded put that gives up when the consumer is gone — a
            syncer that raises on the first bad sub-window verdict abandons
            this generator mid-stream, and a plain q.put would park the
            worker forever on the full queue (leaking the thread plus up to
            `prefetch` packed windows per rejected snapshot)."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for votes, powers, totals in specs:
                    if stop.is_set():
                        return
                    t0 = time.perf_counter()
                    with trace.span("planner.pack", H=len(votes)):
                        plan = plan_window(votes, powers, totals)
                        dev = use_device if use_device is not None else (
                            mesh is not None
                        )
                        if dev and plan.all_ed25519():
                            pack_device(plan, mesh)
                    plan.pack_seconds = time.perf_counter() - t0
                    if not _put(("plan", plan)):
                        return
            except BaseException as e:  # re-raised on the consumer side
                _put(("err", e))
            else:
                _put(("done", None))

        threading.Thread(
            target=worker, name="planner-pack", daemon=True
        ).start()
        try:
            while True:
                kind, item = q.get()
                if kind == "done":
                    return
                if kind == "err":
                    raise item
                yield self._execute_one(item)
        finally:
            # generator closed/abandoned (GeneratorExit, consumer raise,
            # normal end): release the worker promptly — signal stop, then
            # drain whatever it already parked in the queue
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass


# ---------------------------------------------------------------------------
# Long-lived lane feed (cross-caller micro-batch aggregation)
# ---------------------------------------------------------------------------


@dataclass
class RowVerdict:
    """One submitted row's slice of a flushed `LaneFeed` batch — the same
    quorum semantics as `WindowVerdict`, scoped to a single height row."""

    ok: np.ndarray  # (len(row),) bool — per-lane verdicts in row order
    tally: int  # voting power of valid present lanes
    committed: bool  # tally*3 > total*2 (STRICT)
    sigs_ok: bool  # no present lane failed verification
    batch_rows: int  # rows folded into the dispatch that served this row
    batch_lanes: int  # present lanes in that dispatch
    occupancy: float  # lane occupancy of that dispatch


class LaneTicket:
    """Handle for one submitted row; `result()` blocks until the feed's
    worker flushes the batch the row rode in."""

    __slots__ = ("_ev", "_verdict", "_err")

    def __init__(self):
        self._ev = threading.Event()
        self._verdict: Optional[RowVerdict] = None
        self._err: Optional[BaseException] = None

    def _resolve(self, verdict=None, err=None) -> None:
        self._verdict = verdict
        self._err = err
        self._ev.set()

    def result(self, timeout: Optional[float] = None) -> RowVerdict:
        if not self._ev.wait(timeout):
            raise TimeoutError("lane feed flush did not complete in time")
        if self._err is not None:
            raise self._err
        return self._verdict


class LaneFeed:
    """Long-lived lane-feed entry point — `WindowPipeline`'s dual.

    The pipeline streams *windows* one caller already holds; the feed
    serves many concurrent callers each holding ONE row (a commit's
    lanes).  `submit()` parks the row for at most `window_s` seconds; a
    daemon worker folds every row that arrived meanwhile into one
    lane-packed superdispatch (same pack/dispatch trace spans, same
    breaker + host-fallback guard) and hands each caller its row's
    verdict slice.  Rows beyond `max_rows` do NOT queue a second dispatch
    behind the first any more: the worker chunks everything pending into
    `max_rows`-row windows and `plan_windows` folds those into ONE lane
    tile — racing flushes inside the deadline window ride together
    (`windows_out` counts the folded windows, `dispatches` the actual
    device round-trips).  This is the aggregation seam the light-client
    frontend feeds — the deadline-bounded micro-batch shape the
    mempool's CheckTx batching proved."""

    def __init__(self, mesh=None, verifier=None,
                 use_device: Optional[bool] = None, window_s: float = 0.002,
                 max_rows: int = 64, profile_kind: str = "lane_feed",
                 on_flush=None):
        self.mesh = mesh
        self.verifier = verifier
        self.use_device = use_device
        self.window_s = max(0.0, float(window_s))
        self.max_rows = max(1, int(max_rows))
        self.profile_kind = profile_kind
        self.on_flush = on_flush  # (verdict, n_rows, seconds) per flush
        # observability for tests/benches: rows_in counts every submitted
        # row, dispatches every flush — their ratio is the realized batch;
        # windows_out counts the ≤max_rows windows folded into those
        # dispatches (windows_out > dispatches == superdispatch folding)
        self.dispatches = 0
        self.windows_out = 0
        self.rows_in = 0
        self.lanes_in = 0
        self._cond = threading.Condition()
        self._pending: List[tuple] = []  # (vrow, prow, total, ticket)
        self._deadline = 0.0
        self._closed = False
        self._thread: Optional[threading.Thread] = None

    def submit(
        self,
        vrow: Sequence[Optional[SigTuple]],
        prow: Sequence[int],
        total: int,
    ) -> LaneTicket:
        """Park one height row for the next flush; returns immediately."""
        ticket = LaneTicket()
        with self._cond:
            if self._closed:
                raise RuntimeError("lane feed is closed")
            if not self._pending:
                self._deadline = time.monotonic() + self.window_s
            self._pending.append((list(vrow), list(prow), int(total), ticket))
            self.rows_in += 1
            self.lanes_in += sum(1 for it in vrow if it is not None)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._worker, name="planner-lane-feed", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()
        return ticket

    def flush_now(self) -> None:
        """Collapse the current deadline: pending rows dispatch at once."""
        with self._cond:
            self._deadline = 0.0
            self._cond.notify_all()

    def close(self) -> None:
        """Stop accepting rows; pending rows still flush before the worker
        exits (their tickets resolve, never hang)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def _worker(self) -> None:
        while True:
            with self._cond:
                while not self._pending:
                    if self._closed:
                        return
                    self._cond.wait(0.1)
                # deadline-bounded collection: hold the batch open for the
                # remainder of the window unless a full superdispatch's
                # worth of rows (or close) arrived first — racing flushes
                # inside the window fold into one dispatch, they don't
                # queue behind each other
                cap = self.max_rows * windows_per_dispatch(self.mesh)
                while len(self._pending) < cap and not self._closed:
                    left = self._deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cond.wait(left)
                batch, self._pending = self._pending, []
            self._flush(batch)

    def _flush(self, batch: List[tuple]) -> None:
        # chunk everything pending into ≤max_rows windows and fold the
        # chunks into ONE superdispatch — one lane tile, one guarded
        # device round-trip, however many flushes raced into this window
        chunks = [
            batch[i: i + self.max_rows]
            for i in range(0, len(batch), self.max_rows)
        ]
        specs = [
            ([b[0] for b in chunk], [b[1] for b in chunk],
             [b[2] for b in chunk])
            for chunk in chunks
        ]
        t0 = time.perf_counter()
        try:
            plan, verdict = _plan_and_execute_windows(
                specs, mesh=self.mesh, verifier=self.verifier,
                use_device=self.use_device,
            )
            parts = split_verdict(plan, verdict)
        except BaseException as e:
            for _, _, _, ticket in batch:
                ticket._resolve(err=e)
            return
        seconds = time.perf_counter() - t0
        self.dispatches += 1
        self.windows_out += len(chunks)
        try:
            get_profiler().record(
                self.profile_kind,
                lanes_present=verdict.lanes_present,
                lanes_dispatched=verdict.lanes_dispatched,
                heights=len(batch),
                run_seconds=seconds,
                n_windows=len(chunks),
            )
        except Exception:
            pass
        if self.on_flush is not None:
            try:
                self.on_flush(verdict, len(batch), seconds)
            except Exception:
                pass
        for ci, chunk in enumerate(chunks):
            part = parts[ci]
            for i, (vrow, _, _, ticket) in enumerate(chunk):
                ticket._resolve(RowVerdict(
                    ok=np.asarray(part.ok[i, : len(vrow)], dtype=bool),
                    tally=int(part.tally[i]),
                    committed=bool(part.committed[i]),
                    sigs_ok=bool(part.sigs_ok[i]),
                    batch_rows=len(batch),
                    batch_lanes=verdict.lanes_present,
                    occupancy=verdict.occupancy,
                ))


# ---------------------------------------------------------------------------
# Long-lived vote feed (live-consensus vote micro-batching)
# ---------------------------------------------------------------------------


@dataclass
class VoteVerdict:
    """One submitted vote's outcome plus the shape of the dispatch that
    served it (for the tendermint_consensus_vote_batch_* family)."""

    ok: bool  # signature verified
    batch_rows: int  # vote-set rows folded into the dispatch
    batch_lanes: int  # present lanes (votes) in the dispatch
    occupancy: float  # lane occupancy of the dispatch
    flush_reason: str  # deadline | quorum | close


class VoteTicket:
    """Handle for one submitted vote; `result()` blocks until the feed's
    worker flushes the batch the vote rode in.  `submitted_ns`/`flushed_ns`
    (wall clock) bound the queue wait the micro-batcher added — the
    batching-vs-network split in the quorum reports."""

    __slots__ = ("_ev", "_verdict", "_err", "submitted_ns", "flushed_ns")

    def __init__(self):
        self._ev = threading.Event()
        self._verdict: Optional[VoteVerdict] = None
        self._err: Optional[BaseException] = None
        self.submitted_ns = 0
        self.flushed_ns = 0

    def _resolve(self, verdict=None, err=None) -> None:
        self._verdict = verdict
        self._err = err
        self._ev.set()

    def result(self, timeout: Optional[float] = None) -> VoteVerdict:
        if not self._ev.wait(timeout):
            raise TimeoutError("vote feed flush did not complete in time")
        if self._err is not None:
            raise self._err
        return self._verdict


class VoteFeed:
    """`LaneFeed`'s sibling for LIVE consensus votes — the deadline-bounded
    vote micro-batcher behind `VoteSet.add_vote`'s verification seam.

    Where the lane feed's unit of submission is a whole row (one commit's
    lanes), the vote feed's unit is a single vote: gossip delivers
    prevotes/precommits one at a time, and `submit()` parks each for at
    most `window_s` seconds.  Votes are keyed by their vote set — the
    `(height, round, vote_type)` group whose valset they share — and each
    group becomes ONE lane row of the flush, so concurrent vote sets (two
    rounds in flight, prevotes + precommits) ride the same superdispatch.
    Groups chunk into ≤max_rows-row windows and `plan_windows` folds the
    chunks into one lane tile — the PR-9 breaker/deadline/audit/host-
    fallback guard wraps the dispatch exactly as it wraps every other
    planner window, and non-ed25519 lanes push the whole plan down the
    host `verify_generic` path, bit-identically.

    `flush_now()` collapses the deadline — the consensus state calls it
    when a submitted vote could complete a +2/3 so a quorum never waits
    out the window.  Flushes record their trigger (deadline|quorum|close)
    into `tendermint_consensus_vote_batch_flush_total`."""

    FLUSH_RECORD_CAPACITY = 256  # flush-attribution ring (quorumtrace join)

    def __init__(self, mesh=None, verifier=None,
                 use_device: Optional[bool] = None, window_s: float = 0.002,
                 max_rows: int = 64,
                 profile_kind: str = "consensus.vote_batch", on_flush=None,
                 now_ns=None):
        self.mesh = mesh
        if verifier is None:
            # live-vote flushes default to the RLC host backend: one
            # Pippenger MSM per clean flush instead of a serial loop, with
            # accept/reject bit-identical to ed25519.verify.  This is the
            # host side only — a mesh still rides the device kernel, and
            # every guard fallback lands here.
            from tendermint_tpu.crypto.batch import RLCHostVerifier

            verifier = RLCHostVerifier()
        self.verifier = verifier
        self.use_device = use_device
        self.window_s = max(0.0, float(window_s))
        self.max_rows = max(1, int(max_rows))
        self.profile_kind = profile_kind
        self.on_flush = on_flush  # (reason, n_votes, n_rows, verdict, s)
        # observability: votes_in counts submissions, rows_out the vote-set
        # group rows they packed into, dispatches the device round-trips,
        # windows_out the ≤max_rows windows folded into them
        self.dispatches = 0
        self.windows_out = 0
        self.votes_in = 0
        self.rows_out = 0
        self.flushes: dict = {"deadline": 0, "quorum": 0, "close": 0}
        # wall-clock source for ticket submit/flush stamps; injectable so
        # the sim harness can share a node's skewed clock (stamps must live
        # in the same timeline as the node's flight records)
        self.now_ns = now_ns if now_ns is not None else time.time_ns
        self._cond = threading.Condition()
        # (group_key, pub, msg, sig, power, total, ticket)
        self._pending: List[tuple] = []
        self._deadline = 0.0
        self._urgent = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        # bounded ledger of recent flushes for batch-flush attribution
        # (scripts/quorum_report.py joins these against vote journeys by
        # group key); oldest entries fall off the ring
        self._flush_recs: List[dict] = []
        self._flush_recs_dropped = 0

    def submit(
        self,
        group_key,
        pub,
        msg: bytes,
        sig: bytes,
        power: int = 1,
        total: int = 1,
        urgent: bool = False,
    ) -> VoteTicket:
        """Park one vote for the next flush; returns immediately.  Votes
        sharing `group_key` (their vote set) pack into one lane row.
        `urgent=True` collapses the window — the quorum-completing flush."""
        ticket = VoteTicket()
        with self._cond:
            if self._closed:
                raise RuntimeError("vote feed is closed")
            if not self._pending:
                self._deadline = time.monotonic() + self.window_s
            ticket.submitted_ns = self.now_ns()
            self._pending.append(
                (group_key, pub, bytes(msg), bytes(sig), int(power),
                 int(total), ticket)
            )
            self.votes_in += 1
            if urgent:
                self._urgent = True
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._worker, name="planner-vote-feed", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()
        return ticket

    def flush_now(self) -> None:
        """Collapse the current deadline: pending votes dispatch at once
        (counted as a quorum flush — the consensus caller's trigger)."""
        with self._cond:
            self._urgent = True
            self._cond.notify_all()

    def close(self) -> None:
        """Stop accepting votes; pending votes still flush before the
        worker exits (their tickets resolve, never hang)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for the worker to drain after close() — test hygiene."""
        t = self._thread
        if t is not None:
            t.join(timeout)

    def flush_records(self) -> dict:
        """Copy of the recent-flush attribution ledger: per flush the
        trigger, shape, covered (height, round, type) groups, window-open
        and flush wall stamps, and the worst/mean ticket queue wait."""
        with self._cond:
            return {
                "capacity": self.FLUSH_RECORD_CAPACITY,
                "dropped": self._flush_recs_dropped,
                "records": [dict(r) for r in self._flush_recs],
            }

    def _worker(self) -> None:
        while True:
            with self._cond:
                while not self._pending:
                    if self._closed:
                        return
                    self._cond.wait(0.1)
                # hold the batch open for the remainder of the window
                # unless a quorum flush, close, or a full superdispatch's
                # worth of votes arrived first
                cap = self.max_rows * windows_per_dispatch(self.mesh)
                while (
                    len(self._pending) < cap
                    and not self._closed
                    and not self._urgent
                ):
                    left = self._deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cond.wait(left)
                if self._closed:
                    reason = "close"
                elif self._urgent:
                    reason = "quorum"
                else:
                    reason = "deadline"
                self._urgent = False
                batch, self._pending = self._pending, []
            self._flush(batch, reason)

    def _flush(self, batch: List[tuple], reason: str) -> None:
        # stamp the batch leaving the feed BEFORE the dispatch: queue wait
        # is submit->flush, not submit->verdict (dispatch cost is already
        # measured by the profiler/verify families)
        t_flush = self.now_ns()
        waits: List[float] = []
        for item in batch:
            ticket = item[6]
            ticket.flushed_ns = t_flush
            if ticket.submitted_ns:
                waits.append(
                    max(0.0, (t_flush - ticket.submitted_ns) / 1e9)
                )
        # one lane row per vote-set group, in first-seen order; votes keep
        # their lane position so verdicts map back per ticket
        rows: List[tuple] = []  # (vrow, prow, total, tickets)
        by_key: dict = {}
        for group_key, pub, msg, sig, power, total, ticket in batch:
            row = by_key.get(group_key)
            if row is None:
                row = ([], [], total, [])
                by_key[group_key] = row
                rows.append(row)
            row[0].append((pub, msg, sig))
            row[1].append(power)
            row[3].append(ticket)
        rec = {
            "reason": reason,
            "votes": len(batch),
            "rows": len(rows),
            "groups": [
                list(gk) if isinstance(gk, tuple) else gk for gk in by_key
            ],
            "t_open_ns": min(
                (it[6].submitted_ns for it in batch if it[6].submitted_ns),
                default=t_flush,
            ),
            "t_flush_ns": t_flush,
            "wait_max_s": max(waits) if waits else 0.0,
            "wait_mean_s": (sum(waits) / len(waits)) if waits else 0.0,
        }
        with self._cond:
            self._flush_recs.append(rec)
            if len(self._flush_recs) > self.FLUSH_RECORD_CAPACITY:
                del self._flush_recs[0]
                self._flush_recs_dropped += 1
        try:
            from tendermint_tpu.libs.metrics import get_vote_batch_metrics

            vm = get_vote_batch_metrics()
            for w in waits:
                vm.record_wait(w)
        except Exception:
            pass
        chunks = [
            rows[i: i + self.max_rows]
            for i in range(0, len(rows), self.max_rows)
        ]
        specs = [
            ([r[0] for r in chunk], [r[1] for r in chunk],
             [r[2] for r in chunk])
            for chunk in chunks
        ]
        t0 = time.perf_counter()
        try:
            plan, verdict = _plan_and_execute_windows(
                specs, mesh=self.mesh, verifier=self.verifier,
                use_device=self.use_device,
            )
            parts = split_verdict(plan, verdict)
        except BaseException as e:
            for row in rows:
                for ticket in row[3]:
                    ticket._resolve(err=e)
            return
        seconds = time.perf_counter() - t0
        self.dispatches += 1
        self.windows_out += len(chunks)
        self.rows_out += len(rows)
        self.flushes[reason] = self.flushes.get(reason, 0) + 1
        try:
            # group keys lead with the vote height ((height, round, type) —
            # state._maybe_batch_vote); annotate the ledger entry with the
            # batch's base height so the critpath analyzer can join
            # verify-dispatch cost to the height it served
            hs = sorted({
                gk[0] for gk in by_key
                if isinstance(gk, tuple) and gk and isinstance(gk[0], int)
            })
            prof = get_profiler()
            if hs:
                # entry "heights" = covered height span (profile.py window
                # semantics), NOT the row count — the per-height join
                # amortizes multi-height entries by this span
                with prof.window(hs[0], heights=hs[-1] - hs[0] + 1):
                    prof.record(
                        self.profile_kind,
                        lanes_present=verdict.lanes_present,
                        lanes_dispatched=verdict.lanes_dispatched,
                        run_seconds=seconds,
                        n_windows=len(chunks),
                    )
            else:
                prof.record(
                    self.profile_kind,
                    lanes_present=verdict.lanes_present,
                    lanes_dispatched=verdict.lanes_dispatched,
                    heights=len(rows),
                    run_seconds=seconds,
                    n_windows=len(chunks),
                )
        except Exception:
            pass
        try:
            from tendermint_tpu.libs.metrics import get_vote_batch_metrics

            get_vote_batch_metrics().record_flush(
                reason, rows=len(rows), lanes=verdict.lanes_present,
                occupancy=verdict.occupancy,
            )
        except Exception:
            pass
        if self.on_flush is not None:
            try:
                self.on_flush(reason, len(batch), len(rows), verdict, seconds)
            except Exception:
                pass
        for ci, chunk in enumerate(chunks):
            part = parts[ci]
            for ri, (vrow, _, _, tickets) in enumerate(chunk):
                for j, ticket in enumerate(tickets):
                    ticket._resolve(VoteVerdict(
                        ok=bool(part.ok[ri, j]),
                        batch_rows=len(rows),
                        batch_lanes=verdict.lanes_present,
                        occupancy=verdict.occupancy,
                        flush_reason=reason,
                    ))


# ---------------------------------------------------------------------------
# Long-lived tx feed (mempool CheckTx ingest micro-batching)
# ---------------------------------------------------------------------------


@dataclass
class TxVerdict:
    """One submitted transaction's signature verdict plus the shape of the
    dispatch that served it (the tendermint_mempool_batch_* family)."""

    ok: bool  # signature verified
    batch_rows: int  # CheckTx-window rows folded into the dispatch
    batch_lanes: int  # present lanes (txs) in the dispatch
    occupancy: float  # lane occupancy of the dispatch
    flush_reason: str  # deadline | quorum | close


class TxTicket:
    """Handle for one submitted tx; `result()` blocks until the feed's
    worker flushes the batch the tx rode in."""

    __slots__ = ("_ev", "_verdict", "_err")

    def __init__(self):
        self._ev = threading.Event()
        self._verdict: Optional[TxVerdict] = None
        self._err: Optional[BaseException] = None

    def _resolve(self, verdict=None, err=None) -> None:
        self._verdict = verdict
        self._err = err
        self._ev.set()

    def result(self, timeout: Optional[float] = None) -> TxVerdict:
        if not self._ev.wait(timeout):
            raise TimeoutError("tx feed flush did not complete in time")
        if self._err is not None:
            raise self._err
        return self._verdict


class TxFeed:
    """`VoteFeed`'s ingest sibling — the deadline-bounded transaction
    micro-batcher behind the mempool's verdict-bearing `batch_check_hook`
    (mempool/tx_verify.BatchTxVerifier).

    The unit of submission is one transaction's signature check:
    ``(pub, sign_bytes, sig)``.  Txs are keyed by the CheckTx window that
    carried them — each ``group_key`` (a ``(height, window_seq)`` pair)
    becomes ONE lane row of the flush, so concurrent windows (admission +
    recheck, several reactors' flush timers) fold into the same
    `plan_windows` superdispatch and share lane buckets (and the jit
    compile cache) with commit-verify and vote dispatches.  The PR-9
    breaker/deadline/audit/host-fallback guard wraps the dispatch exactly
    as it wraps every other planner window; with no mesh the flush rides
    `RLCHostVerifier` — one Pippenger MSM per clean batch — and
    non-ed25519 lanes (secp256k1 senders) push the whole plan down the
    host `verify_generic` path, bit-identically.

    `flush_now()` collapses the deadline — the mempool hook calls it once
    a whole CheckTx window has been submitted, so a full admission batch
    never waits out the window (counted as a quorum flush, mirroring the
    vote feed's trigger vocabulary).  Flushes record their trigger into
    ``tendermint_mempool_batch_flush_total``."""

    def __init__(self, mesh=None, verifier=None,
                 use_device: Optional[bool] = None, window_s: float = 0.002,
                 max_rows: int = 64,
                 profile_kind: str = "mempool.tx_batch", on_flush=None):
        self.mesh = mesh
        if verifier is None:
            # same chipless default as the vote feed: the RLC host backend
            # batch-verifies with accept/reject bit-identical to
            # ed25519.verify, and every guard fallback lands here
            from tendermint_tpu.crypto.batch import RLCHostVerifier

            verifier = RLCHostVerifier()
        self.verifier = verifier
        self.use_device = use_device
        self.window_s = max(0.0, float(window_s))
        self.max_rows = max(1, int(max_rows))
        self.profile_kind = profile_kind
        self.on_flush = on_flush  # (reason, n_txs, n_rows, verdict, s)
        # observability: txs_in counts submissions, rows_out the
        # CheckTx-window rows they packed into, dispatches the device
        # round-trips, windows_out the ≤max_rows windows folded into them
        self.dispatches = 0
        self.windows_out = 0
        self.txs_in = 0
        self.rows_out = 0
        self.flushes: dict = {"deadline": 0, "quorum": 0, "close": 0}
        self._cond = threading.Condition()
        # (group_key, pub, msg, sig, ticket)
        self._pending: List[tuple] = []
        self._deadline = 0.0
        self._urgent = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None

    def submit(self, group_key, pub, msg: bytes, sig: bytes,
               urgent: bool = False) -> TxTicket:
        """Park one tx signature for the next flush; returns immediately.
        Txs sharing `group_key` (their CheckTx window) pack into one lane
        row.  `urgent=True` collapses the window."""
        ticket = TxTicket()
        with self._cond:
            if self._closed:
                raise RuntimeError("tx feed is closed")
            if not self._pending:
                self._deadline = time.monotonic() + self.window_s
            self._pending.append(
                (group_key, pub, bytes(msg), bytes(sig), ticket)
            )
            self.txs_in += 1
            if urgent:
                self._urgent = True
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._worker, name="planner-tx-feed", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()
        return ticket

    def flush_now(self) -> None:
        """Collapse the current deadline: pending txs dispatch at once
        (counted as a quorum flush — the batch-complete trigger)."""
        with self._cond:
            self._urgent = True
            self._cond.notify_all()

    def close(self) -> None:
        """Stop accepting txs; pending txs still flush before the worker
        exits (their tickets resolve, never hang)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for the worker to drain after close() — test hygiene."""
        t = self._thread
        if t is not None:
            t.join(timeout)

    def _worker(self) -> None:
        while True:
            with self._cond:
                while not self._pending:
                    if self._closed:
                        return
                    self._cond.wait(0.1)
                # hold the batch open for the remainder of the window
                # unless a batch-complete flush, close, or a full
                # superdispatch's worth of txs arrived first
                cap = self.max_rows * windows_per_dispatch(self.mesh)
                while (
                    len(self._pending) < cap
                    and not self._closed
                    and not self._urgent
                ):
                    left = self._deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cond.wait(left)
                if self._closed:
                    reason = "close"
                elif self._urgent:
                    reason = "quorum"
                else:
                    reason = "deadline"
                self._urgent = False
                batch, self._pending = self._pending, []
            self._flush(batch, reason)

    def _flush(self, batch: List[tuple], reason: str) -> None:
        # one lane row per CheckTx-window group, in first-seen order; txs
        # keep their lane position so verdicts map back per ticket
        rows: List[tuple] = []  # (vrow, tickets)
        by_key: dict = {}
        for group_key, pub, msg, sig, ticket in batch:
            row = by_key.get(group_key)
            if row is None:
                row = ([], [])
                by_key[group_key] = row
                rows.append(row)
            row[0].append((pub, msg, sig))
            row[1].append(ticket)
        chunks = [
            rows[i: i + self.max_rows]
            for i in range(0, len(rows), self.max_rows)
        ]
        # quorum math is vestigial here (power 1 per lane, total = lane
        # count): only the per-lane ok grid feeds verdicts back
        specs = [
            ([r[0] for r in chunk],
             [[1] * len(r[0]) for r in chunk],
             [len(r[0]) for r in chunk])
            for chunk in chunks
        ]
        t0 = time.perf_counter()
        try:
            plan, verdict = _plan_and_execute_windows(
                specs, mesh=self.mesh, verifier=self.verifier,
                use_device=self.use_device,
            )
            parts = split_verdict(plan, verdict)
        except BaseException as e:
            for row in rows:
                for ticket in row[1]:
                    ticket._resolve(err=e)
            return
        seconds = time.perf_counter() - t0
        self.dispatches += 1
        self.windows_out += len(chunks)
        self.rows_out += len(rows)
        self.flushes[reason] = self.flushes.get(reason, 0) + 1
        try:
            # group keys lead with the mempool height ((height, window_seq)
            # — tx_verify.BatchTxVerifier); annotate the ledger entry with
            # the batch's base height so the critpath analyzer joins
            # ingest-verify cost into the verify_dispatch overlay of the
            # height it served
            hs = sorted({
                gk[0] for gk in by_key
                if isinstance(gk, tuple) and gk and isinstance(gk[0], int)
            })
            prof = get_profiler()
            if hs:
                with prof.window(hs[0], heights=hs[-1] - hs[0] + 1):
                    prof.record(
                        self.profile_kind,
                        lanes_present=verdict.lanes_present,
                        lanes_dispatched=verdict.lanes_dispatched,
                        run_seconds=seconds,
                        n_windows=len(chunks),
                    )
            else:
                prof.record(
                    self.profile_kind,
                    lanes_present=verdict.lanes_present,
                    lanes_dispatched=verdict.lanes_dispatched,
                    heights=len(rows),
                    run_seconds=seconds,
                    n_windows=len(chunks),
                )
        except Exception:
            pass
        try:
            from tendermint_tpu.libs.metrics import get_mempool_batch_metrics

            get_mempool_batch_metrics().record_flush(
                reason, rows=len(rows), lanes=verdict.lanes_present,
                occupancy=verdict.occupancy,
            )
        except Exception:
            pass
        if self.on_flush is not None:
            try:
                self.on_flush(reason, len(batch), len(rows), verdict, seconds)
            except Exception:
                pass
        for ci, chunk in enumerate(chunks):
            part = parts[ci]
            for ri, (vrow, tickets) in enumerate(chunk):
                for j, ticket in enumerate(tickets):
                    ticket._resolve(TxVerdict(
                        ok=bool(part.ok[ri, j]),
                        batch_rows=len(rows),
                        batch_lanes=verdict.lanes_present,
                        occupancy=verdict.occupancy,
                        flush_reason=reason,
                    ))
