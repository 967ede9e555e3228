"""The BatchVerifier boundary — the seam where bulk signature verification leaves
the host control plane and lands on TPU.

The reference (v0.26.2) has NO batch interface; its one call-site shape is
``PubKey.VerifyBytes(msg, sig) bool`` (crypto/crypto.go:22-27), invoked serially
from types/validator_set.go:281-296 (commit verify), types/vote.go:102 (per-vote),
state/validation.go:102 and blockchain/reactor.go:306 (fast sync). This module
introduces the batch boundary those call sites feed (SURVEY.md north star):
callers collect (pubkey, msg, sig) tuples for a height — or a whole fast-sync
window of heights — and dispatch them in ONE call.

Backends:
  * HostBatchVerifier  — serial host loop (CPU oracle; always available).
  * TPUBatchVerifier   — device path. On a real TPU it dispatches the fused
    Pallas pipeline (ops/ed25519_pallas); on CPU or when a mesh is given it
    uses the portable XLA kernel (ops/ed25519_verify, shard_map-able).
    secp256k1 items have a device kernel of their own (verify_secp256k1).
    A k-of-n multisig member of ``verify_generic`` rides the ed25519
    dispatch, one lane a flagged sub-signature; only a member whose
    signature cannot be flattened is decided on the host (verify_bytes).

Accept/reject is bit-exact across backends (tests/test_ops_ed25519.py).
"""

from __future__ import annotations

import hashlib
import logging
import math
import os
import random
import struct
import threading
import time
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from tendermint_tpu.crypto import ed25519 as _ed
from tendermint_tpu.crypto import oracle_pool as _oracle_pool
from tendermint_tpu.crypto.keys import PubKey, PubKeyEd25519
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics

logger = logging.getLogger("tendermint_tpu.verify")


def _record_dispatch(backend: str, algo: str, n: int, t0: float, ok,
                     first: bool = False, carry_mode: str = "",
                     ed25519_path: str = "") -> None:
    """One VerifyMetrics record per batch dispatch (size, latency, rejects,
    and which carry schedule / verify strategy served the window).
    Telemetry must never take down the verify path."""
    try:
        get_verify_metrics().record_dispatch(
            backend, algo, n, time.perf_counter() - t0,
            rejects=n - int(np.count_nonzero(ok)), first=first,
            carry_mode=carry_mode,
            ed25519_path=ed25519_path,
        )
    except Exception:
        pass


class VerifyConfigError(ValueError):
    """A [verify] / TM_* choice that cannot be served as configured.  Raised
    at selection so the program stops with the reason; never folded into a
    device fault and a quiet host run."""


def check_removed_options(verify_section=None) -> None:
    """Refuse an option this version removed, where it still arrives from
    outside: ``fe_backend`` on a [verify] section written for an earlier
    build, or ``TM_FE_BACKEND`` on a launch line.  The VPU schoolbook is the
    only limb multiplier (``mxu`` and ``mxu16`` never lowered for TPU), so
    absent, empty, ``auto`` or ``vpu`` asks for what runs and is ignored;
    anything else stops the program with the reason — it never runs with a
    different multiplier than the operator wrote."""
    for where, value in (
        ("TM_FE_BACKEND", os.environ.get("TM_FE_BACKEND")),
        ("[verify] fe_backend", getattr(verify_section, "fe_backend", None)),
    ):
        if str(value or "").strip().lower() not in ("", "auto", "vpu"):
            raise VerifyConfigError(
                f"{where}={value!r}: the fe_backend option was removed in "
                "this version; the VPU limb multiplier is the only one. "
                "Drop the setting (or write vpu)."
            )


# device verify strategies (ops/ed25519_verify.verify_batch vs the
# one-MSM-per-window RLC path, ops/ed25519_msm)
_ED25519_PATHS = ("ladder", "msm")
_default_ed25519_path: Optional[str] = None


def set_default_ed25519_path(value: Optional[str]) -> None:
    """Install the process-wide [verify] ed25519_path choice (node
    composition root).  TM_ED25519_PATH still overrides per-process."""
    global _default_ed25519_path
    _default_ed25519_path = value or None


def _resolve_ed25519_path(explicit: Optional[str]) -> str:
    v = explicit or os.environ.get("TM_ED25519_PATH", "") or \
        _default_ed25519_path or "ladder"
    v = v.strip().lower()
    if v in ("", "auto"):
        return "ladder"
    if v not in _ED25519_PATHS:
        raise VerifyConfigError(
            f"ed25519_path must be one of {_ED25519_PATHS}, got {v!r}"
        )
    return v


class SigItem(NamedTuple):
    """One signature-verification work item. (NamedTuple, not dataclass:
    tens of thousands are created per fast-sync window and tuple
    construction is several times cheaper.)"""

    pubkey: bytes  # raw 32-byte ed25519 key (or PubKey for generic items)
    msg: bytes
    sig: bytes


def valset_key(keys: np.ndarray) -> bytes:
    """What the Pallas path's valset caches (ops/ed25519_pallas) know an
    (n, 32) key array by.  THE definition: a caller that keeps a key array
    (``ValidatorSet``'s membership columns) takes its identity here once and
    hands it down in a ``ValsetRows``, and the kernel's host wrapper takes it
    here where none came."""
    return hashlib.sha256(np.ascontiguousarray(keys)).digest()


class ValsetRows(NamedTuple):
    """What a caller knows of a dispatch's keys beyond their bytes: they are
    rows of a key array it keeps (``ValidatorSet``'s membership columns).
    Goes down beside the three columns as ``valset=``; the columns' own keys
    still go, and the audit, the host completion and the host verifier read
    those alone.  The Pallas path keeps what it derives from ``keys`` by
    ``key_id``, one table a membership held on the device (key limbs and
    words, and the window tables its ladder reads in place of building one
    a lane), and gathers a dispatch's lanes from it: lane i is row
    ``slots[i]`` (a commit with absent slots: another subset every height,
    the same members), or with ``slots`` None the lanes ARE ``keys``, row
    for row.  No hash of the keys a call either way."""

    key_id: bytes  # valset_key(keys)
    keys: np.ndarray  # (N, 32) uint8, every member's key
    slots: Optional[np.ndarray]  # (n,) row of keys a lane; None: all, in order


def _byte_rows(col):
    """A column's rows as ``bytes``: the rows of a 2-D uint8 array, or the
    list it already is."""
    if not isinstance(col, np.ndarray):
        return col
    data, w = col.tobytes(), col.shape[1]
    if not w:
        return [b""] * len(col)
    return [data[i:i + w] for i in range(0, len(data), w)]


class HostBatchVerifier:
    """Serial host verification — the oracle backend."""

    name = "host"
    # verify_ed25519_raw takes each column as a list of ``bytes`` or as the
    # uint8 array a caller already holds ((n, 32), (n, ln), (n, 64)), and
    # the keys' ``ValsetRows`` with them: verify_ed25519_columns and
    # verify_generic ask before they hand either to a verifier
    column_form = True

    def verify_ed25519(self, items: Sequence[SigItem]) -> np.ndarray:
        t0 = time.perf_counter()
        with trace.span("verify.dispatch", backend="host", algo="ed25519",
                        n=len(items)):
            ok = np.array(
                [_ed.verify(it.pubkey, it.msg, it.sig) for it in items],
                dtype=bool,
            )
        _record_dispatch("host", "ed25519", len(items), t0, ok)
        return ok

    def verify_ed25519_raw(self, pubs, msgs, sigs,
                           valset: Optional[ValsetRows] = None) -> np.ndarray:
        """Parallel-sequence form of verify_ed25519 — the hot callers
        (verify_generic's homogeneous fast path) already hold the three
        columns, and building |window|x|valset| SigItems was a measured
        slice of the fast-sync host ceiling.  The oracle reads ``bytes``, so
        an array is cut into its rows; ``valset`` is the device's."""
        pubs, msgs, sigs = map(_byte_rows, (pubs, msgs, sigs))
        t0 = time.perf_counter()
        verify = _ed.verify
        with trace.span("verify.dispatch", backend="host", algo="ed25519",
                        n=len(pubs)):
            ok = np.fromiter(
                (verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)),
                dtype=bool, count=len(pubs),
            )
        _record_dispatch("host", "ed25519", len(pubs), t0, ok)
        return ok

    def verify_secp256k1(self, items: Sequence[SigItem]) -> np.ndarray:
        """items carry (33B compressed pubkey, RAW msg, DER sig); the SHA-256
        premix (secp256k1.go:140) happens here."""
        from tendermint_tpu.crypto import secp256k1 as _secp
        from tendermint_tpu.crypto.hashing import sha256

        t0 = time.perf_counter()
        with trace.span("verify.dispatch", backend="host", algo="secp256k1",
                        n=len(items)):
            ok = np.array(
                [_secp.verify(it.pubkey, sha256(it.msg), it.sig) for it in items],
                dtype=bool,
            )
        _record_dispatch("host", "secp256k1", len(items), t0, ok)
        return ok


class RLCHostVerifier(HostBatchVerifier):
    """Host batch verification via the random-linear-combination check
    (ed25519.verify_batch): one Pippenger multi-scalar multiplication
    amortizes the per-signature double-scalar-mult, so a clean batch
    costs a fraction of the serial loop on hosts without the C fast
    path.  Accept/reject is bit-identical to ed25519.verify — failing
    batches are localized and re-checked per signature against the
    exact equation.  secp256k1 items still take the serial host loop."""

    name = "host_rlc"

    def verify_ed25519(self, items: Sequence[SigItem]) -> np.ndarray:
        t0 = time.perf_counter()
        with trace.span("verify.dispatch", backend="host_rlc",
                        algo="ed25519", n=len(items)):
            ok = np.array(
                _ed.verify_batch(
                    [(it.pubkey, it.msg, it.sig) for it in items]
                ),
                dtype=bool,
            ) if items else np.zeros((0,), dtype=bool)
        _record_dispatch("host_rlc", "ed25519", len(items), t0, ok)
        return ok

    def verify_ed25519_raw(self, pubs, msgs, sigs,
                           valset: Optional[ValsetRows] = None) -> np.ndarray:
        pubs, msgs, sigs = map(_byte_rows, (pubs, msgs, sigs))
        t0 = time.perf_counter()
        with trace.span("verify.dispatch", backend="host_rlc",
                        algo="ed25519", n=len(pubs)):
            ok = np.array(
                _ed.verify_batch(list(zip(pubs, msgs, sigs))), dtype=bool,
            ) if len(pubs) else np.zeros((0,), dtype=bool)
        _record_dispatch("host_rlc", "ed25519", len(pubs), t0, ok)
        return ok


# the carry schedule the device kernels trace with when called from here
# (their default, which this module never overrides): the dispatch counter's
# carry_mode label
_KERNEL_CARRY_MODE = "lazy"


class TPUBatchVerifier:
    """Batched device verification.

    backend: "pallas" (fused kernel, needs a TPU as the default jax
    backend), "xla" (portable, mesh-shardable), or None = pallas when
    ``jax.devices()[0]`` is a TPU and no mesh was requested, else xla.

    ed25519_path: "ladder" verifies one signature per lane with the
    double-scalar ladder kernel; "msm" folds the whole window into ONE
    Pippenger multi-scalar multiplication via a random linear combination
    (ops/ed25519_msm) and falls back to chunk RLCs + exact ladder rows on
    a window reject, so accept/reject stays bit-identical.  None =
    TM_ED25519_PATH env, then the [verify] ed25519_path config
    (set_default_ed25519_path), then "ladder".
    """

    name = "tpu"
    column_form = True  # as HostBatchVerifier's

    def __init__(self, mesh=None, backend: Optional[str] = None,
                 ed25519_path: Optional[str] = None):
        self.ed25519_path = _resolve_ed25519_path(ed25519_path)
        self._mesh = mesh
        # deferred import: keep jax out of pure-host users
        from tendermint_tpu.ops import dispatch as _dispatch

        tpu = _dispatch.accelerator() if mesh is None else None
        if backend is None:
            backend = "pallas" if tpu is not None else "xla"
        elif backend == "pallas" and tpu is None:
            raise RuntimeError(
                "pallas backend requires a TPU as the default jax backend; "
                f"jax.devices()[0] is {_dispatch.device_info()} "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})"
            )
        self.backend = backend
        self.device = _dispatch.device_info()
        if backend == "pallas":
            from tendermint_tpu.ops import ed25519_pallas as kernel
        else:
            from tendermint_tpu.ops import ed25519_verify as kernel
        self._kernel = kernel
        # algos that have dispatched at least once on this verifier — the
        # first dispatch pays compile/upload and lands in compile_seconds
        self._warm: set = set()

    def self_test(self) -> None:
        """Known-answer check on the smallest lane bucket: one good and one
        bit-flipped signature over a vote-sized message must come back
        [True, False].  Selection runs it once, outside the guard, so a
        chip or compiler that cannot serve the kernel fails device init —
        loudly — instead of the first commit; it also compiles (or loads
        from the persistent cache) the single-validator commit program
        before consensus needs it."""
        priv = _ed.gen_privkey(b"\x42" * 32)
        msg = bytes(range(110))
        good = _ed.sign(priv, msg)
        bad = bytes([good[0] ^ 1]) + good[1:]
        got = self.verify_ed25519_raw([priv[32:]] * 2, [msg] * 2, [good, bad])
        if got.tolist() != [True, False]:
            raise RuntimeError(
                f"{self.backend} self-test on {self.device} returned "
                f"{got.tolist()}, expected [True, False]"
            )

    def verify_ed25519(self, items: Sequence[SigItem]) -> np.ndarray:
        if len(items) == 0:
            return np.zeros((0,), dtype=bool)
        return self.verify_ed25519_raw(
            [it.pubkey for it in items],
            [it.msg for it in items],
            [it.sig for it in items],
        )

    def verify_ed25519_raw(self, pubs, msgs, sigs,
                           valset: Optional[ValsetRows] = None) -> np.ndarray:
        """Column form of verify_ed25519 (see HostBatchVerifier's note).
        Each column is a list of ``bytes`` or the array a caller already
        holds; ``valset`` says which rows of a kept key array ``pubs`` are,
        where the caller knows (the Pallas ladder's caches; no other path
        reads it)."""
        if len(pubs) == 0:
            return np.zeros((0,), dtype=bool)
        t0 = time.perf_counter()
        first = "ed25519" not in self._warm
        with trace.span("verify.dispatch", backend=self.backend,
                        algo="ed25519", n=len(pubs)):
            pubs_a = pubs if isinstance(pubs, np.ndarray) else np.frombuffer(
                b"".join(pubs), dtype=np.uint8).reshape(len(pubs), 32)
            sigs_a = sigs if isinstance(sigs, np.ndarray) else np.frombuffer(
                b"".join(sigs), dtype=np.uint8).reshape(len(sigs), 64)
            if self.backend == "pallas" and self.ed25519_path != "msm":
                ok = self._kernel.verify_batch(
                    pubs_a, msgs, sigs_a, valset=valset)
            else:
                # only the Pallas ladder reads a message matrix in place
                msgs = _byte_rows(msgs)
                if self.ed25519_path == "msm":
                    # the MSM folds the window into one point equation:
                    # there is no lane axis to shard, so no mesh either
                    ok = self._kernel.rlc_verify_batch(pubs_a, msgs, sigs_a)
                else:
                    ok = self._kernel.verify_batch(
                        pubs_a, msgs, sigs_a, mesh=self._mesh,
                    )
        ok = np.asarray(ok, dtype=bool)
        self._warm.add("ed25519")
        _record_dispatch(self.backend, "ed25519", len(pubs), t0, ok,
                         first=first, carry_mode=_KERNEL_CARRY_MODE,
                         ed25519_path=self.ed25519_path)
        return ok

    def verify_secp256k1(self, items: Sequence[SigItem]) -> np.ndarray:
        """Batched ECDSA on device. The pallas backend dispatches the fused
        windowed-Straus kernel (ops/secp256k1_pallas) on the real chip;
        otherwise the portable XLA kernel (mesh/shard_map-able) runs."""
        if len(items) == 0:
            return np.zeros((0,), dtype=bool)
        from tendermint_tpu.crypto.hashing import sha256

        t0 = time.perf_counter()
        first = "secp256k1" not in self._warm
        with trace.span("verify.dispatch", backend=self.backend,
                        algo="secp256k1", n=len(items)):
            # the SHA-256 premix (secp256k1.go:140) and the column lists
            with trace.span("dispatch.prepare", n=len(items)):
                pubs = [it.pubkey for it in items]
                digs = [sha256(it.msg) for it in items]
                sigs = [it.sig for it in items]
            if self.backend == "pallas":
                from tendermint_tpu.ops import secp256k1_pallas as _skp

                ok = _skp.verify_batch(pubs, digs, sigs)
            else:
                from tendermint_tpu.ops import secp256k1_verify as _sk

                ok = _sk.verify_batch(pubs, digs, sigs, mesh=self._mesh)
        ok = np.asarray(ok, dtype=bool)
        self._warm.add("secp256k1")
        _record_dispatch(self.backend, "secp256k1", len(items), t0, ok,
                         first=first, carry_mode=_KERNEL_CARRY_MODE)
        return ok


def _item_rows(items, lanes) -> list:
    return [(items[i].pubkey, items[i].msg, items[i].sig) for i in lanes]


def _column_rows(pubs, msgs, sigs, lanes) -> list:
    """The sampled lanes' (pubkey, msg, sig) as ``bytes``, from columns that
    are lists or arrays: the oracle workers get the same rows either way."""
    cols = (pubs, msgs, sigs)
    if not all(isinstance(c, np.ndarray) for c in cols):
        pubs, msgs, sigs = map(_byte_rows, cols)
        return [(pubs[i], msgs[i], sigs[i]) for i in lanes]
    # the sampled rows side by side, cut into three ``bytes`` a lane in one
    # pass (1,500 slices made one by one cost three times as much)
    idx = np.asarray(lanes)
    cut = np.concatenate([c[idx] for c in cols], axis=1)
    fmt = "".join(f"{c.shape[1]}s" for c in cols)
    return list(struct.iter_unpack(fmt, cut.tobytes()))


def _deadline_at(deadline, t0=None) -> Optional[float]:
    """``deadline`` seconds after ``t0`` (now, by default) on the monotonic
    clock; None where the dispatch is not supervised (deadline <= 0)."""
    if deadline is None or deadline <= 0:
        return None
    return (time.monotonic() if t0 is None else t0) + deadline


class _AuditSample(NamedTuple):
    """One dispatch's audit between submit and compare."""

    lanes: List[int]
    rows: list  # the lanes' (pubkey, msg, sig)
    ticket: Optional[_oracle_pool.Ticket]  # None: the oracle runs inline


class GuardedBatchVerifier:
    """Fault-tolerant wrapper around a device BatchVerifier.

    Every dispatch runs the full guard (libs/breaker.py):

      1. breaker gate — open/quarantined diverts straight to the host
         oracle (bit-identical verdicts, just slower);
      2. supervised deadline — a hung device call becomes a fallback,
         not a stalled consensus routine;
      3. bounded retry — one transient failure is retried before the
         window completes on the host;
      4. seeded silent-corruption audit — k sampled lanes per device
         window are re-verified on the host oracle; any disagreement
         quarantines the breaker (operator reset required) and the
         window's verdict is recomputed entirely on the host, so a
         wrong device verdict never escapes this class.  The lanes are
         drawn before the device is asked; from 8 of them up, and with 3
         or more cores, the oracle runs in worker processes
         (crypto/oracle_pool) while the device call does, and the
         verdicts are compared, all of them, once the device has answered.

    The wrapped device object only needs the BatchVerifier surface
    (verify_ed25519 / verify_ed25519_raw / verify_secp256k1), which is
    how the sim's FaultyDevice shim slots in.
    """

    name = "guarded"

    def __init__(self, device, host=None, breaker=None, deadline=None,
                 retries=None, audit_rate=None, audit_seed=None):
        from tendermint_tpu.libs import breaker as _brk

        cfg = _brk.guard_config()
        self.device = device
        self.host = host if host is not None else HostBatchVerifier()
        self.breaker = breaker if breaker is not None \
            else _brk.get_device_breaker()
        self.deadline = cfg.dispatch_deadline if deadline is None else deadline
        self.retries = cfg.retries if retries is None else int(retries)
        self.audit_rate = (
            cfg.audit_sample_rate if audit_rate is None else float(audit_rate)
        )
        self.audit_seed = cfg.audit_seed if audit_seed is None else int(audit_seed)
        self.backend = getattr(
            device, "backend", getattr(device, "name", "device")
        )
        self._mtx = threading.Lock()
        self._dispatches = 0
        self._audit_mismatches = 0

    # -- BatchVerifier surface -------------------------------------------------

    def verify_ed25519(self, items: Sequence[SigItem]) -> np.ndarray:
        return self._guard(
            "ed25519", len(items),
            lambda: self.device.verify_ed25519(items),
            lambda: self.host.verify_ed25519(items),
            lambda lanes: _item_rows(items, lanes),
        )

    @property
    def column_form(self) -> bool:
        """Whether the columns may come as arrays: the device's say."""
        return getattr(self.device, "column_form", False)

    def verify_ed25519_raw(self, pubs, msgs, sigs,
                           valset: Optional[ValsetRows] = None) -> np.ndarray:
        # a device that does not say column_form (a foreign fake) is handed
        # what it was before this parameter: three columns and nothing else
        kw = {"valset": valset} if self.column_form else {}
        return self._guard(
            "ed25519", len(pubs),
            lambda: self.device.verify_ed25519_raw(pubs, msgs, sigs, **kw),
            lambda: self.host.verify_ed25519_raw(
                *map(_byte_rows, (pubs, msgs, sigs))),
            lambda lanes: _column_rows(pubs, msgs, sigs, lanes),
        )

    def verify_secp256k1(self, items: Sequence[SigItem]) -> np.ndarray:
        return self._guard(
            "secp256k1", len(items),
            lambda: self.device.verify_secp256k1(items),
            lambda: self.host.verify_secp256k1(items),
            lambda lanes: _item_rows(items, lanes),
        )

    # -- guard machinery -------------------------------------------------------

    def _guard(self, algo, n, dev_call, host_call, rows) -> np.ndarray:
        """``rows(lanes)`` gives the sampled lanes' (pubkey, msg, sig), raw
        as the caller holds them: the audit's oracle (``oracle_worker.
        verify_rows``) may run in another process.

        Every guarded dispatch takes one audit sequence number when it
        starts, whether the device or the host completes it (breaker open,
        time-out, error), and a retry re-uses it: the sample is a pure
        function of (audit_seed, seq, n) and is drawn before the device is
        asked, never from its answer."""
        if n == 0:
            return np.zeros((0,), dtype=bool)
        from tendermint_tpu.libs import breaker as _brk

        audited = self.audit_rate > 0 and rows is not None
        seq = None
        if audited:
            with self._mtx:
                seq = self._dispatches
                self._dispatches += 1
        # guard.call's own time (less guard.submit, verify.dispatch and
        # guard.audit under it) is the worker thread's spawn, the join and
        # the bookkeeping
        with trace.span("guard.call", algo=algo, n=n, attempts=0) as sp:
            br = self.breaker
            if not br.allow():
                reason = (
                    "quarantined" if br.state == _brk.QUARANTINED
                    else "breaker_open"
                )
                self._note_fallback(reason, algo, n)
                return np.asarray(host_call(), dtype=bool)
            sample = self._submit_audit(algo, n, seq, rows) if audited else None
            try:
                attempts = 0
                while True:
                    sp.set(attempts=attempts + 1)
                    t_call = time.monotonic()
                    try:
                        ok = _brk.supervised_call(
                            dev_call, self.deadline, name=f"batch-{algo}"
                        )
                        ok = np.asarray(ok, dtype=bool)
                    except Exception as e:
                        timeout = isinstance(e, _brk.DispatchTimeout)
                        reason = "timeout" if timeout else "error"
                        br.record_failure(reason)
                        attempts += 1
                        if attempts <= self.retries and br.allow():
                            try:
                                get_verify_metrics().device_retries.add(1.0)
                            except Exception:
                                pass
                            continue
                        self._note_fallback(reason, algo, n)
                        return np.asarray(host_call(), dtype=bool)
                    if sample is not None and self._audit(
                            algo, ok, sample, t_call):
                        # the device disagrees with the host oracle: safety
                        # bug.  Quarantine (latched) and recompute the WHOLE
                        # window on the host: the sampled lanes say nothing
                        # about the rest.
                        br.quarantine(f"audit_mismatch:{algo}")
                        self._note_fallback("audit_mismatch", algo, n)
                        return np.asarray(host_call(), dtype=bool)
                    br.record_success()
                    return ok
            finally:
                # a dispatch that ended without collecting (host completion,
                # an exception on its way out): the workers' answers are
                # drained and dropped
                if sample is not None and sample.ticket is not None:
                    sample.ticket.abandon()

    def _submit_audit(self, algo, n, seq, rows) -> "_AuditSample":
        """Draw the dispatch's k seeded lanes and, where there are enough of
        them and the process has the cores, hand their rows to the oracle
        workers, which verify them while the device call runs."""
        k = min(n, max(1, int(math.ceil(n * self.audit_rate))))
        with trace.span("guard.submit", sampled=k) as sp:
            rng = random.Random((self.audit_seed << 20) ^ seq)
            lanes = rng.sample(range(n), k)
            picked = rows(lanes)
            pool = (_oracle_pool.get_oracle_pool()
                    if k >= _oracle_pool.MIN_POOL_LANES else None)
            ticket = None
            if pool is not None:
                ticket = pool.submit(algo, picked, _deadline_at(self.deadline))
            sp.set(frames=len(ticket.parts) if ticket else 0)
        return _AuditSample(lanes, picked, ticket)

    def _audit(self, algo, ok, sample, t_call) -> bool:
        """Cross-check the sampled lanes against the host oracle, after the
        device has answered: collect the workers' verdicts (or run the
        oracle here, for a small sample), compare lane by lane.  Every
        sampled lane has its oracle verdict before this returns.  Returns
        True iff any lane disagrees."""
        lanes = sample.lanes
        t0 = time.perf_counter()
        with trace.span("guard.audit", sampled=len(lanes)) as sp:
            if sample.ticket is not None:
                verdicts, lost = sample.ticket.collect(
                    _deadline_at(self.deadline, t_call))
                where = {"pool": len(lanes) - lost, "inline_after_loss": lost}
            else:
                verdicts = _oracle_pool.verify_rows(algo, sample.rows)
                where = {"inline": len(lanes)}
            bad = [i for i, v in zip(lanes, verdicts) if bool(ok[i]) != v]
            sp.set(mismatches=len(bad))
        try:
            m = get_verify_metrics()
            m.device_audit_seconds.observe(time.perf_counter() - t0)
            if len(lanes) - len(bad):
                m.device_audit.add(float(len(lanes) - len(bad)), ("ok",))
            if bad:
                m.device_audit.add(float(len(bad)), ("mismatch",))
            for label, count in where.items():
                if count:
                    m.audit_oracle.add(float(count), (label,))
        except Exception:
            pass
        if bad:
            with self._mtx:
                self._audit_mismatches += len(bad)
            try:
                from tendermint_tpu.libs.profile import get_profiler

                get_profiler().record_event(
                    "audit_mismatch", algo=algo, backend=self.backend,
                    sampled=len(lanes), mismatches=len(bad),
                    lanes=bad[:8],
                )
            except Exception:
                pass
        return bool(bad)

    def _note_fallback(self, reason, algo, n) -> None:
        # every host completion of a device dispatch is said out loud: a
        # green run must never hide that the chip was lost
        logger.warning(
            "device dispatch completed on the host: reason=%s algo=%s n=%d "
            "backend=%s breaker=%s", reason, algo, n, self.backend,
            self.breaker.state,
        )
        try:
            get_verify_metrics().device_fallback.add(1.0, (reason,))
        except Exception:
            pass
        try:
            from tendermint_tpu.libs.profile import get_profiler

            get_profiler().record_event(
                "device_fallback", reason=reason, algo=algo, n=n,
                backend=self.backend,
            )
        except Exception:
            pass

    def snapshot(self) -> dict:
        with self._mtx:
            return {
                "backend": self.backend,
                "deadline": self.deadline,
                "retries": self.retries,
                "audit_rate": self.audit_rate,
                "dispatches": self._dispatches,
                "audit_mismatches": self._audit_mismatches,
            }


_lock = threading.Lock()
_default = None
# why the default latched the host path: None (device in use or host
# explicitly installed) | "no_tpu" | "device_init_error".  Only the init
# error is considered transient — the breaker's half-open probe re-drives
# device selection for it.
_latched_reason: Optional[str] = None


def _checked_device(backend: Optional[str] = None):
    """A TPUBatchVerifier; the Pallas one has passed its known-answer test."""
    v = TPUBatchVerifier(backend=backend)
    if v.backend == "pallas":
        v.self_test()
    return v


def _try_device_default():
    """One device-selection attempt: (verifier, latch_reason).  The chip is
    whatever ``jax.devices()`` reports under JAX_PLATFORMS — one in-process
    discovery, no child process."""
    v = _checked_device()
    # no chip degrades TPUBatchVerifier to XLA — but on a CPU-only host the
    # XLA kernel is ~100x slower than the host C path, so the default only
    # keeps the device verifier when the fused pipeline is actually
    # reachable (TM_BATCH_VERIFIER=xla forces XLA instead)
    if v.backend == "pallas":
        return GuardedBatchVerifier(v), None
    dev = _device_of(v) or {}
    logger.warning(
        "no TPU (jax.devices()[0] is %s %r, JAX_PLATFORMS=%r): commit "
        "verification runs on the host verifier [no_tpu]",
        dev.get("platform"), dev.get("kind"),
        os.environ.get("JAX_PLATFORMS", ""),
    )
    return HostBatchVerifier(), "no_tpu"


def get_batch_verifier(prefer_tpu: bool = True):
    """Process-wide default verifier, selected once from the environment.

    TM_BATCH_VERIFIER=host|xla|pallas decides outright (small localnet
    validators with tiny commits want the host oracle; ``pallas`` without
    a chip raises instead of degrading).  Unset, the verifier is the
    guarded Pallas pipeline when ``jax.devices()[0]`` is a TPU and the host
    verifier otherwise — logged either way, never silent.  A host latch
    caused by a device-init error is retried when the breaker grants its
    half-open probe."""
    global _default, _latched_reason
    from tendermint_tpu.libs.breaker import get_device_breaker

    with _lock:
        if _default is None:
            check_removed_options()
            forced = os.environ.get("TM_BATCH_VERIFIER", "").lower()
            if forced == "host":
                _default = HostBatchVerifier()
            elif forced in ("xla", "pallas"):
                _default = GuardedBatchVerifier(_checked_device(forced))
            elif forced:
                raise VerifyConfigError(
                    "TM_BATCH_VERIFIER must be host, xla or pallas, "
                    f"got {forced!r}"
                )
            elif prefer_tpu:
                try:
                    _default, _latched_reason = _try_device_default()
                except VerifyConfigError:
                    raise  # a refused configuration is not a device fault
                except Exception:
                    logger.exception(
                        "device verifier init failed; commit verification "
                        "runs on the host verifier [device_init_error]"
                    )
                    _default = HostBatchVerifier()
                    _latched_reason = "device_init_error"
                    # force the breaker open so retries are paced by its
                    # exponential backoff instead of hammering init on
                    # every commit verify
                    get_device_breaker().trip("device_init_error")
                if _latched_reason is not None:
                    get_verify_metrics().host_fallback.add(
                        1.0, (_latched_reason,)
                    )
            if _default is not None:
                logger.info("batch verifier: %s", describe_verifier(_default))
        elif _latched_reason == "device_init_error" and prefer_tpu:
            # retry seam: the half-open probe budget decides when a
            # recovered device is worth another (possibly slow) init
            br = get_device_breaker()
            if br.allow():
                try:
                    v, reason = _try_device_default()
                    if reason is None:
                        _default = v
                        _latched_reason = None
                        br.record_success()
                        logger.info(
                            "batch verifier: %s", describe_verifier(v))
                    else:
                        br.record_failure("no_tpu")
                except Exception:
                    logger.exception("device verifier init failed again")
                    br.record_failure("device_init_error")
        return _default


def set_batch_verifier(v) -> None:
    global _default, _latched_reason
    with _lock:
        _default = v
        _latched_reason = None


def reprobe(force: bool = False):
    """Drop the default and re-run device selection in this process.

    ``force=False`` only clears a host latch (a previous ``no_tpu`` /
    ``device_init_error`` verdict); an explicitly installed verifier is
    left alone.  ``force=True`` re-selects unconditionally.  Selection
    reads ``jax.devices()`` of THIS process — a chip belongs to one
    process, so nothing is ever probed from a child.  Returns the
    (possibly new) default verifier."""
    global _default, _latched_reason
    with _lock:
        if _latched_reason is None and not force:
            return _default
        _default = None
        _latched_reason = None
    return get_batch_verifier()


def _device_of(v) -> Optional[dict]:
    """ops/dispatch.device_info() of the device verifier inside ``v``."""
    inner = v.device if isinstance(v, GuardedBatchVerifier) else v
    dev = getattr(inner, "device", None)
    return dev if isinstance(dev, dict) else None


def _backend_of(v) -> Optional[str]:
    return getattr(v, "backend", None) or getattr(v, "name", None)


def describe_verifier(v) -> str:
    """One line naming backend and device, or the host and why — the node
    prints it beside "Node started" and selection logs it."""
    backend = _backend_of(v)
    dev = _device_of(v)
    if dev is not None:
        return (
            f"backend={backend} platform={dev['platform']} "
            f"device_kind={dev['kind']!r} device_id={dev['id']} "
            f"devices={dev['count']}"
        )
    if os.environ.get("TM_BATCH_VERIFIER", "").lower() == "host":
        why = "TM_BATCH_VERIFIER=host"
    else:
        why = _latched_reason or "installed by caller"
    return f"backend={backend} ({why})"


def _by_label(counter) -> dict:
    return {"/".join(k): v for k, v in sorted(counter.snapshot().items())}


def verifier_info() -> dict:
    """The default verifier's identity and health, as /status and
    dump_device_health serve it: which backend on which device, why the
    host if it is the host, and the dispatch / fallback / audit counters
    and breaker state a reader needs to tell a device run from a quiet
    host run."""
    from tendermint_tpu.libs.breaker import get_device_breaker

    with _lock:
        v = _default
        reason = _latched_reason
    m = get_verify_metrics()
    info = {
        "installed": v is not None,
        "name": getattr(v, "name", None) if v is not None else None,
        "backend": _backend_of(v),
        "latched_reason": reason,
        "description": describe_verifier(v) if v is not None else None,
        "device": _device_of(v) if v is not None else None,
        "breaker_state": get_device_breaker().state,
        "dispatches": _by_label(m.calls),
        "device_fallback_total": _by_label(m.device_fallback),
        "host_fallback_total": _by_label(m.host_fallback),
        "device_audit_total": _by_label(m.device_audit),
    }
    if isinstance(v, GuardedBatchVerifier):
        info["guard"] = v.snapshot()
        # jax is loaded iff a device verifier exists — stay off it otherwise
        from tendermint_tpu.ops.dispatch import compile_stats

        info["compile"] = compile_stats()
    return info


def verify_items(items: Sequence[SigItem], verifier=None) -> np.ndarray:
    """Verify a heterogeneous batch. Ed25519 raw items go to the batch backend."""
    if verifier is None:
        verifier = get_batch_verifier()
    return verifier.verify_ed25519(items)


def verify_generic(
    pubkeys: Sequence[PubKey], msgs: Sequence[bytes], sigs: Sequence[bytes],
    verifier=None, valset: Optional[ValsetRows] = None,
) -> np.ndarray:
    """Batch-verify over PubKey objects: ed25519 and secp256k1 keys batch to
    their backends; k-of-n threshold multisig aggregates FLATTEN into the
    ed25519 batch (every flagged signer's sub-signature rides the same
    device dispatch — ref threshold_pubkey.go:41-55 loops serially); only
    structurally odd items fall back to host verify_bytes.

    The ed25519 lanes of a call go down as three columns (32-byte key,
    message, 64-byte signature) in ONE ``verify_ed25519_raw``: the plain
    ed25519 members first, then each multisig member's run of lanes, which
    ``multisig.flatten_columns`` reads in place from the marshalled
    signature (no item object a lane).  A multisig member's verdict is the
    AND of its run, taken for all members at once (``logical_and.reduceat``
    at the runs' starts).

    ``valset`` is for a caller whose ``pubkeys`` are rows of a key array it
    keeps (verify_commit, where a lane fits no column): it goes down with
    the homogeneous ed25519 batch, where lane i is ``pubkeys[i]``, and
    nowhere else."""
    # the span's own time is the key-type scan and the column lists; the
    # verifier's spans (guard.call or verify.dispatch) and, for multisig
    # members, multisig.flatten and multisig.reduce are its children
    with trace.span("verify.generic", n=len(pubkeys)) as sp:
        return _verify_generic(pubkeys, msgs, sigs, verifier, sp, valset)


def _verify_generic(pubkeys, msgs, sigs, verifier, sp, valset) -> np.ndarray:
    from tendermint_tpu.crypto.keys import PubKeySecp256k1
    from tendermint_tpu.crypto.multisig import PubKeyMultisigThreshold

    if verifier is None:
        verifier = get_batch_verifier()
    n = len(pubkeys)
    # Homogeneous ed25519 batch — every fast-sync window and almost every
    # commit in practice.  Skip the per-item dispatch bookkeeping below
    # (isinstance + three index lists over |window|×|valset| items was a
    # measurable slice of the host ms/block ceiling).
    if all(type(pk) is PubKeyEd25519 for pk in pubkeys) and all(
        len(s) == 64 for s in sigs
    ):
        sp.set(keys="ed25519")
        return _dispatch_ed25519_rows(
            verifier, [pk.bytes() for pk in pubkeys], msgs, sigs, valset
        )
    sp.set(keys="mixed")
    out = np.zeros((n,), dtype=bool)
    # the call's ed25519 lanes as three columns: the plain members first
    # (ed_idx[j] is the result index of lane j), every multisig member's run
    # of lanes behind them
    ed_idx: List[int] = []
    ed_pubs: List[bytes] = []
    ed_msgs: List[bytes] = []
    ed_sigs: List[bytes] = []
    sk_idx: List[int] = []
    sk_items: List[SigItem] = []
    ms_idx: List[int] = []
    for i, pk in enumerate(pubkeys):
        if isinstance(pk, PubKeyEd25519) and len(sigs[i]) == 64:
            ed_idx.append(i)
            ed_pubs.append(pk.bytes())
            ed_msgs.append(msgs[i])
            ed_sigs.append(sigs[i])
        elif isinstance(pk, PubKeySecp256k1):
            sk_idx.append(i)
            sk_items.append(SigItem(pk.bytes(), msgs[i], sigs[i]))
        elif isinstance(pk, PubKeyMultisigThreshold):
            ms_idx.append(i)
        else:
            try:
                get_verify_metrics().host_fallback.add(
                    1.0, ("unbatchable_key",)
                )
            except Exception:
                pass
            out[i] = pk.verify_bytes(msgs[i], sigs[i])
    groups = None
    if ms_idx:
        groups = _flatten_multisig(
            pubkeys, msgs, sigs, ms_idx, ed_pubs, ed_msgs, ed_sigs, out
        )
    if ed_pubs:
        res = _dispatch_ed25519_rows(verifier, ed_pubs, ed_msgs, ed_sigs)
        out[ed_idx] = res[: len(ed_idx)]
        if groups is not None and len(groups.member):
            # a validator's verdict from its run of lanes: all of them.  The
            # runs lie end to end up to the columns' end and none is empty
            # (a flattened member has k >= 1 lanes), which is what reduceat
            # needs to read start[i]:start[i + 1] as member i's lanes
            with trace.span("multisig.reduce", groups=len(groups.member)):
                assert groups.lanes.min() >= 1 and len(res) == (
                    groups.start[-1] + groups.lanes[-1]
                )
                out[groups.member] = np.logical_and.reduceat(res, groups.start)
    if sk_items:
        res = verifier.verify_secp256k1(sk_items)
        for j, i in enumerate(sk_idx):
            out[i] = res[j]
    return out


def verify_ed25519_columns(
    keys: np.ndarray, msgs: np.ndarray, sigs: np.ndarray, verifier=None,
    valset: Optional[ValsetRows] = None,
) -> np.ndarray:
    """verify_generic for a caller that holds an all-ed25519 batch as three
    uint8 arrays, (n, 32) keys, (n, ln) messages, (n, 64) signatures: one
    dispatch, no object a lane.  ``valset`` says which rows of a key array
    the caller keeps ``keys`` are.  A verifier that does not say
    ``column_form`` (a fake in a test, a stand-in of the benchmark's) gets
    the rows as lists of ``bytes`` and nothing else."""
    with trace.span("verify.generic", n=len(keys), keys="ed25519"):
        if verifier is None:
            verifier = get_batch_verifier()
        if getattr(verifier, "column_form", False):
            return np.asarray(
                verifier.verify_ed25519_raw(
                    keys, msgs, sigs, valset=valset),
                dtype=bool,
            )
        return _dispatch_ed25519_rows(
            verifier, *map(_byte_rows, (keys, msgs, sigs)))


def _dispatch_ed25519_rows(verifier, pubs, msgs, sigs,
                           valset: Optional[ValsetRows] = None) -> np.ndarray:
    """One ed25519 dispatch from three lists of ``bytes``, and the keys'
    ``ValsetRows`` where one came and the verifier says ``column_form``.  A
    verifier without ``verify_ed25519_raw`` (fakes in tests) gets
    ``SigItem``s."""
    raw = getattr(verifier, "verify_ed25519_raw", None)
    if raw is not None:
        kw = {}
        if valset is not None and getattr(verifier, "column_form", False):
            kw["valset"] = valset
        return np.asarray(raw(pubs, msgs, sigs, **kw), dtype=bool)
    items = [SigItem(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    return np.asarray(verifier.verify_ed25519(items), dtype=bool)


def _flatten_multisig(pubkeys, msgs, sigs, ms_idx, ed_pubs, ed_msgs, ed_sigs, out):
    """Every multisig member of one call through ``multisig.flatten_columns``:
    one lane a flagged sub-signature appended to the three ed25519 columns,
    read from the marshalled bytes in place, and the members' (result index,
    first lane, lanes) as the ``FlatGroups`` returned.  A member whose
    signature cannot be flattened (structurally invalid, a flagged sub-key
    that is not ed25519, fewer flagged signers than k) is decided here by
    the host's ``verify_bytes`` (usually False) and written to ``out``."""
    from tendermint_tpu.crypto.multisig import flatten_columns

    first = len(ed_pubs)
    with trace.span("multisig.flatten", validators=len(ms_idx)) as sp:
        groups = flatten_columns(
            pubkeys, msgs, sigs, ms_idx, ed_pubs, ed_msgs, ed_sigs
        )
        for i in groups.host:
            out[i] = pubkeys[i].verify_bytes(msgs[i], sigs[i])
        lanes = len(ed_pubs) - first
        sp.set(lanes=lanes, host_decided=len(groups.host))
    try:
        m = get_verify_metrics()
        if groups.host:
            m.host_fallback.add(float(len(groups.host)), ("multisig_structural",))
        m.multisig_groups.add(float(len(groups.member)))
        m.multisig_lanes.add(float(lanes))
    except Exception:
        pass
    return groups
