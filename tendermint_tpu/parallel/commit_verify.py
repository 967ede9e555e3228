"""Mesh-sharded commit verification: the (heights × validators) signature tensor.

This is the TPU-native replacement for the reference's two serial loops:

  * `types/validator_set.go:273-298` — per-commit loop over validator
    precommits (one ed25519 verify each, single thread);
  * `blockchain/reactor.go:216-327` — fast sync's verify→apply loop, one
    height at a time.

Here a whole *window* of heights is packed into ``(H, V)`` tensors, sharded
over a 2-D device mesh (``height`` × ``val`` axes), verified in one dispatch,
and the per-height voting-power tally is an XLA reduction across the ``val``
axis — i.e. the +2/3 quorum check rides the ICI as a psum instead of a Go
for-loop.  SURVEY.md §5 "long-context" mapping: validator-index and height are
the shardable long axes of this system.

Only data that is per-(height, validator) lives in the tensor; vote absence /
nil votes are a ``present`` mask so the quorum math stays branch-free.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.libs.profile import get_profiler
from tendermint_tpu.ops import ed25519_verify as _k

SigTuple = Tuple[bytes, bytes, bytes]  # (pubkey32, msg, sig64)


@dataclass
class CommitWindow:
    """Packed (H, V) signature tensors + host-side validity mask."""

    neg_ax: np.ndarray  # (H, V, 20) uint32
    ay: np.ndarray  # (H, V, 20) uint32
    s_words: np.ndarray  # (H, V, 8) uint32
    h_words: np.ndarray  # (H, V, 8) uint32
    r_limbs: np.ndarray  # (H, V, 20) uint32
    r_sign: np.ndarray  # (H, V) uint32
    present: np.ndarray  # (H, V) bool — vote present AND host-side prechecks ok
    power: np.ndarray  # (H, V) int64 voting power (0 where absent)
    pack_seconds: float = 0.0  # host pack wall time (cost ledger)
    # raw signature columns (coords (n,2) int64, pubs, msgs, sigs) — kept so
    # a failed/quarantined device dispatch can complete bit-identically on
    # the host oracle, and so the corruption audit has something to check
    # against.  References into the caller's vote tuples, not copies.
    raw: Optional[tuple] = None

    @property
    def shape(self):
        return self.present.shape


def pack_commit_window(
    votes: Sequence[Sequence[Optional[SigTuple]]],
    powers: Sequence[Sequence[int]],
) -> CommitWindow:
    """votes[h][v] = (pub, msg, sig) or None (absent/nil); powers[h][v] int."""
    t_pack = time.perf_counter()
    H = len(votes)
    V = max((len(row) for row in votes), default=0)
    z = np.zeros
    win = CommitWindow(
        neg_ax=z((H, V, _k.NLIMB), np.uint32),
        ay=z((H, V, _k.NLIMB), np.uint32),
        s_words=z((H, V, 8), np.uint32),
        h_words=z((H, V, 8), np.uint32),
        r_limbs=z((H, V, _k.NLIMB), np.uint32),
        r_sign=z((H, V), np.uint32),
        present=z((H, V), bool),
        power=z((H, V), np.int64),
    )
    # flatten present votes and run the shared host prologue once
    coords, pubs_l, msgs_l, sigs_l, pows_l = [], [], [], [], []
    for h, row in enumerate(votes):
        for v, item in enumerate(row):
            if item is None:
                continue
            pub, msg, sig = item
            if len(sig) != 64 or len(pub) != 32:
                continue
            coords.append((h, v))
            pubs_l.append(bytes(pub))
            msgs_l.append(bytes(msg))
            sigs_l.append(bytes(sig))
            pows_l.append(powers[h][v])
    if coords:
        n = len(coords)
        pubs = np.frombuffer(b"".join(pubs_l), np.uint8).reshape(n, 32)
        sigs = np.frombuffer(b"".join(sigs_l), np.uint8).reshape(n, 64)
        neg_ax, ay, s_words, h_words, r_limbs, r_sign, valid = _k.host_prologue(
            pubs, msgs_l, sigs
        )
        hv = np.asarray(coords, dtype=np.int64)
        hs, vs = hv[:, 0], hv[:, 1]
        win.neg_ax[hs, vs] = neg_ax
        win.ay[hs, vs] = ay
        win.s_words[hs, vs] = s_words
        win.h_words[hs, vs] = h_words
        win.r_limbs[hs, vs] = r_limbs
        win.r_sign[hs, vs] = r_sign
        win.present[hs, vs] = valid
        win.power[hs, vs] = np.where(
            valid, np.asarray(pows_l, dtype=np.int64), 0
        )
        win.raw = (hv, pubs_l, msgs_l, sigs_l)
    win.pack_seconds = time.perf_counter() - t_pack
    return win


def _step(neg_ax, ay, s_words, h_words, r_limbs, r_sign, present, power, total_power):
    """One sharded verify+tally step.  power tally reduces over the val axis —
    under a sharded `val` mesh axis XLA lowers this to a psum over ICI."""
    ok = _k._verify_kernel(neg_ax, ay, s_words, h_words, r_limbs, r_sign)
    ok = ok & present
    tally = jnp.sum(jnp.where(ok, power, 0), axis=-1)
    committed = tally * 3 > total_power * 2
    return ok, tally, committed


_step_cache = {}
# jit re-traces per padded shape even under a cached mesh key; track
# (mesh, padded_shape) so compile-latency histograms stay honest
_compiled_shapes = set()


# the carry schedule the window step traces with, and its records' label
_CARRY_MODE = "lazy"


def _compiled_step(mesh):
    from tendermint_tpu.ops import fe_common as _fc

    # Mesh hashes by devices+axis_names; id() could be gc-reused
    fn = _step_cache.get(mesh)
    if fn is not None:
        return fn
    step = _fc.trace_with_modes(_k, _step, _CARRY_MODE)
    if mesh is None:
        fn = jax.jit(step)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as PS

        hname, vname = mesh.axis_names[0], mesh.axis_names[1]
        hv = NamedSharding(mesh, PS(hname, vname))
        h_only = NamedSharding(mesh, PS(hname))
        rep = NamedSharding(mesh, PS())
        fn = jax.jit(
            step,
            in_shardings=(hv,) * 8 + (rep,),
            out_shardings=(hv, h_only, h_only),
        )
    _step_cache[mesh] = fn
    return fn


def _pad_to(a: np.ndarray, h: int, v: int) -> np.ndarray:
    pads = [(0, h - a.shape[0]), (0, v - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
    return np.pad(a, pads)


def _verify_window_host(
    win: CommitWindow, total_power: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Bit-identical host completion of a packed window, from the retained
    raw columns: same ok/tally/committed semantics as the device step
    (accept/reject parity is the tests/test_ops_ed25519.py invariant)."""
    from tendermint_tpu.crypto import ed25519 as _ed

    H, V = win.shape
    ok = np.zeros((H, V), dtype=bool)
    if win.raw is not None:
        coords, pubs_l, msgs_l, sigs_l = win.raw
        if len(pubs_l):
            res = np.fromiter(
                (_ed.verify(p, m, s)
                 for p, m, s in zip(pubs_l, msgs_l, sigs_l)),
                dtype=bool, count=len(pubs_l),
            )
            ok[coords[:, 0], coords[:, 1]] = res
    ok &= win.present
    tally = np.sum(np.where(ok, win.power, 0), axis=-1).astype(np.int64)
    committed = tally * 3 > np.int64(total_power) * 2
    return ok, tally, committed


def _audit_window_verdict(win: CommitWindow, ok: np.ndarray) -> bool:
    """Silent-corruption audit over a window verdict: k seeded-sampled
    present lanes re-verified on the host oracle.  True iff any disagrees."""
    import math
    import random

    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.libs.breaker import guard_config

    cfg = guard_config()
    rate = cfg.audit_sample_rate
    if rate <= 0 or win.raw is None:
        return False
    coords, pubs_l, msgs_l, sigs_l = win.raw
    cand = [
        i for i in range(len(pubs_l))
        if win.present[coords[i, 0], coords[i, 1]]
    ]
    if not cand:
        return False
    global _audit_seq
    with _audit_mtx:
        seq = _audit_seq
        _audit_seq += 1
    k = min(len(cand), max(1, int(math.ceil(len(cand) * rate))))
    rng = random.Random((cfg.audit_seed << 20) ^ seq)
    lanes = rng.sample(cand, k)
    bad = []
    for i in lanes:
        host_ok = _ed.verify(pubs_l[i], msgs_l[i], sigs_l[i])
        if host_ok != bool(ok[coords[i, 0], coords[i, 1]]):
            bad.append(i)
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
                "audit_mismatch", backend="window", sampled=k,
                mismatches=len(bad), lanes=bad[:8],
            )
        except Exception:
            pass
    return bool(bad)


_audit_mtx = threading.Lock()
_audit_seq = 0


def _note_fallback(reason: str, win: CommitWindow) -> None:
    try:
        get_verify_metrics().device_fallback.add(1.0, (reason,))
    except Exception:
        pass
    try:
        get_profiler().record_event(
            "device_fallback", reason=reason, backend="window",
            heights=win.shape[0],
        )
    except Exception:
        pass


def verify_commit_window(
    win: CommitWindow, total_power: int, mesh=None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Verify a packed window; returns (ok (H,V) bool, tally (H,) int64,
    committed (H,) bool).  With a 2-D mesh, shards heights × validators.

    The device dispatch runs behind the fault guard (libs/breaker.py):
    breaker gate, supervised deadline, one bounded retry, then bit-identical
    completion on the host oracle from the window's retained raw columns;
    an audit mismatch quarantines the device path."""
    from tendermint_tpu.libs import breaker as _brk

    br = _brk.get_device_breaker()
    cfg = _brk.guard_config()
    if win.raw is None:
        # no raw columns (hand-built window): nothing to fall back to or
        # audit against — dispatch unguarded as before
        return _verify_window_device(win, total_power, mesh)
    if not br.allow():
        reason = (
            "quarantined" if br.state == _brk.QUARANTINED else "breaker_open"
        )
        _note_fallback(reason, win)
        return _verify_window_host(win, total_power)
    attempts = 0
    while True:
        try:
            out = _brk.supervised_call(
                lambda: _verify_window_device(win, total_power, mesh),
                cfg.dispatch_deadline, name="commit-window",
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
            _note_fallback(reason, win)
            return _verify_window_host(win, total_power)
        if _audit_window_verdict(win, out[0]):
            br.quarantine("audit_mismatch:window")
            _note_fallback("audit_mismatch", win)
            return _verify_window_host(win, total_power)
        br.record_success()
        return out


# whether the MSM kernel has dispatched here — the first dispatch carries
# the jit trace/compile (latency attribution)
_msm_warm = False


def _verify_window_device_msm(
    win: CommitWindow, total_power: int, mesh=None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One MSM per commit window ([verify] ed25519_path = msm): the raw
    signature columns fold into a single random-linear-combination
    Pippenger multi-scalar multiplication (ops/ed25519_msm).  Verdicts are
    bit-identical to the per-vote ladder — a rejected window localizes via
    chunk RLCs and exact ladder re-runs inside rlc_verify_batch — and the
    verify_commit_window guard/audit wrapping applies unchanged.  The MSM
    folds to one point equation, so the mesh is not consulted."""
    global _msm_warm
    H, V = win.shape
    coords, pubs_l, msgs_l, sigs_l = win.raw
    n = len(pubs_l)
    first = not _msm_warm
    _msm_warm = True
    ok = np.zeros((H, V), dtype=bool)
    t0 = time.perf_counter()
    with trace.span("verify.window_dispatch", backend="window_msm",
                    H=H, V=V, n=n):
        if n:
            pubs = np.frombuffer(b"".join(pubs_l), np.uint8).reshape(n, 32)
            sigs = np.frombuffer(b"".join(sigs_l), np.uint8).reshape(n, 64)
            res = _k.rlc_verify_batch(
                pubs, msgs_l, sigs,
                carry_mode=_CARRY_MODE,
            )
            ok[coords[:, 0], coords[:, 1]] = res
    ok &= win.present
    tally = np.sum(np.where(ok, win.power, 0), axis=-1).astype(np.int64)
    committed = tally * 3 > np.int64(total_power) * 2
    dt = time.perf_counter() - t0
    try:
        m = get_verify_metrics()
        m.record_dispatch(
            "window_msm", "ed25519", n, dt,
            rejects=int(np.count_nonzero(win.present & ~ok)), first=first,
            carry_mode=_CARRY_MODE,
            ed25519_path="msm",
        )
        get_profiler().record(
            "window_msm",
            bucket=(H, V),
            lanes_present=n,
            lanes_dispatched=n,
            heights=H,
            pack_seconds=win.pack_seconds,
            run_seconds=dt,
            compiled=first,
            # upload ≈ the extended-point pool: 2 points per pair row,
            # 4 coords x 20 uint32 limbs each
            bytes_to_device=n * 2 * 4 * 20 * 4,
            carry_mode=_CARRY_MODE,
            ed25519_path="msm",
            n_windows=1,
            n_devices=1,
        )
    except Exception:
        pass
    return ok, tally, committed


def _verify_window_device(
    win: CommitWindow, total_power: int, mesh=None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The raw (unguarded) device dispatch."""
    from tendermint_tpu.crypto.batch import _resolve_ed25519_path

    if win.raw is not None and _resolve_ed25519_path(None) == "msm":
        return _verify_window_device_msm(win, total_power, mesh)
    H, V = win.shape
    ph, pv = H, V
    if mesh is not None:
        mh, mv = mesh.devices.shape
        ph = ((H + mh - 1) // mh) * mh
        pv = ((V + mv - 1) // mv) * mv
    arrs = [
        _pad_to(getattr(win, f), ph, pv)
        for f in (
            "neg_ax",
            "ay",
            "s_words",
            "h_words",
            "r_limbs",
            "r_sign",
            "present",
            "power",
        )
    ]
    # Voting powers are int64 (reference clips at 2^60); without x64, jit
    # silently canonicalizes them to int32 and the quorum tally wraps — a
    # consensus-safety bug.  Scope the flag to this dispatch instead of
    # flipping global dtype semantics for the whole process at import time.
    backend = "window_mesh" if mesh is not None else "window"
    shape_key = (mesh, (ph, pv))
    first = shape_key not in _compiled_shapes
    _compiled_shapes.add(shape_key)
    n = int(np.count_nonzero(win.present))
    t0 = time.perf_counter()
    with trace.span("verify.window_dispatch", backend=backend, H=H, V=V, n=n):
        with jax.enable_x64(True):
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as PS

                hv = NamedSharding(mesh, PS(*mesh.axis_names[:2]))
                arrs = [jax.device_put(a, hv) for a in arrs]
            ok, tally, committed = _compiled_step(mesh)(
                *arrs, np.int64(total_power)
            )
            ok = np.asarray(ok)[:H, :V]
    dt = time.perf_counter() - t0
    n_devices = int(mesh.devices.size) if mesh is not None else 1
    try:
        # rejects = votes that passed host prechecks but failed the device
        # verify; first dispatch per mesh key carries the jit compile
        m = get_verify_metrics()
        m.record_dispatch(
            backend, "ed25519", n, dt,
            rejects=int(np.count_nonzero(win.present & ~ok)), first=first,
            carry_mode=_CARRY_MODE,
            ed25519_path="ladder",
        )
        if mesh is not None:
            m.record_device_shards(
                (d.id for d in mesh.devices.flat),
                (ph * pv) // n_devices)
        else:
            m.record_device_shards((jax.devices()[0].id,), ph * pv)
        get_profiler().record(
            backend,
            bucket=(ph, pv),
            lanes_present=n,
            lanes_dispatched=ph * pv,
            heights=H,
            pack_seconds=win.pack_seconds,
            run_seconds=dt,
            compiled=first,
            bytes_to_device=sum(a.nbytes for a in arrs),
            carry_mode=_CARRY_MODE,
            ed25519_path="ladder",
            n_windows=1,
            n_devices=n_devices,
        )
    except Exception:
        pass
    return (
        ok,
        np.asarray(tally)[:H],
        np.asarray(committed)[:H],
    )
