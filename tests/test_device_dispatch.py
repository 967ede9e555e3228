"""Fault-tolerant device dispatch (the guard in crypto/batch.py,
parallel/planner.py, parallel/commit_verify.py):

* GuardedBatchVerifier — fail/hang/corrupt devices complete bit-identically
  on the host path; corruption quarantines the breaker (latched);
* planner window guard + the WindowPipeline mid-stream-fault regression
  (one bad window must not abandon the stream);
* commit-window guard fallback/audit;
* the get_batch_verifier re-probe seam (regression: a transient device
  init failure used to latch the host path permanently).
"""

import threading
import time

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as batch_mod
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto.batch import GuardedBatchVerifier, HostBatchVerifier
from tendermint_tpu.libs import breaker as brk
from tendermint_tpu.sim.faults import FaultyDevice, InjectedDeviceError


@pytest.fixture(autouse=True)
def _fresh_guard():
    brk.reset_device_guard()
    yield
    brk.reset_device_guard()


def _triples(n, tag=0, forged=()):
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes([(i % 251) + 1, 7, (tag % 250) + 1]) * 16
        priv = ed.gen_privkey(seed[:32])
        msg = b"dispatch-%d-%d" % (tag, i)
        sig = ed.sign(priv, msg)
        if i in forged:
            bad = bytearray(sig)
            bad[5] ^= 1
            sig = bytes(bad)
        pubs.append(priv[32:])
        msgs.append(msg)
        sigs.append(sig)
    return pubs, msgs, sigs


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestGuardedBatchVerifier:
    def _guarded(self, dev, **kw):
        kw.setdefault("breaker", brk.CircuitBreaker(
            threshold=2, backoff_base=60.0, clock=FakeClock()))
        kw.setdefault("deadline", 5.0)
        kw.setdefault("retries", 0)
        kw.setdefault("audit_rate", 1.0)
        return GuardedBatchVerifier(dev, **kw)

    def test_failing_device_falls_back_bit_identically(self):
        pubs, msgs, sigs = _triples(8, tag=1, forged=(3,))
        expected = HostBatchVerifier().verify_ed25519_raw(pubs, msgs, sigs)
        dev = FaultyDevice(HostBatchVerifier(), fail_rate=1.0)
        g = self._guarded(dev)
        for _ in range(4):
            ok = g.verify_ed25519_raw(pubs, msgs, sigs)
            assert np.array_equal(ok, expected)
        assert g.breaker.state == brk.OPEN
        # open breaker diverts straight to host — the dead device is
        # no longer dispatched to
        calls_when_open = dev.calls
        assert np.array_equal(
            g.verify_ed25519_raw(pubs, msgs, sigs), expected
        )
        assert dev.calls == calls_when_open

    def test_transient_failure_retries_onto_the_device(self):
        pubs, msgs, sigs = _triples(4, tag=2)
        expected = HostBatchVerifier().verify_ed25519_raw(pubs, msgs, sigs)
        dev = FaultyDevice(HostBatchVerifier(), schedule=["fail", "ok"])
        g = self._guarded(dev, retries=1)
        ok = g.verify_ed25519_raw(pubs, msgs, sigs)
        assert np.array_equal(ok, expected)
        assert dev.calls == 2  # failed once, retried on the device
        assert g.breaker.state == brk.CLOSED

    def test_hung_device_times_out_to_host(self):
        pubs, msgs, sigs = _triples(4, tag=3, forged=(0,))
        expected = HostBatchVerifier().verify_ed25519_raw(pubs, msgs, sigs)
        dev = FaultyDevice(HostBatchVerifier(), hang_rate=1.0, hang_s=5.0)
        g = self._guarded(dev, deadline=0.1)
        t0 = time.monotonic()
        ok = g.verify_ed25519_raw(pubs, msgs, sigs)
        assert time.monotonic() - t0 < 4.0  # did not wait out the hang
        assert np.array_equal(ok, expected)

    def test_corruption_quarantines_and_never_escapes(self):
        pubs, msgs, sigs = _triples(8, tag=4, forged=(2, 6))
        expected = HostBatchVerifier().verify_ed25519_raw(pubs, msgs, sigs)
        dev = FaultyDevice(HostBatchVerifier(), corrupt_rate=1.0)
        g = self._guarded(dev, audit_rate=1.0)
        ok = g.verify_ed25519_raw(pubs, msgs, sigs)
        # the corrupted verdict was caught and recomputed on the host
        assert np.array_equal(ok, expected)
        assert g.breaker.state == brk.QUARANTINED
        # latched: subsequent dispatches never touch the device again
        calls = dev.calls
        for _ in range(3):
            assert np.array_equal(
                g.verify_ed25519_raw(pubs, msgs, sigs), expected
            )
        assert dev.calls == calls
        assert g.snapshot()["audit_mismatches"] > 0

    def test_operator_reset_readmits_the_device(self):
        pubs, msgs, sigs = _triples(4, tag=5)
        dev = FaultyDevice(HostBatchVerifier(), schedule=["corrupt"])
        g = self._guarded(dev, audit_rate=1.0)
        g.verify_ed25519_raw(pubs, msgs, sigs)
        assert g.breaker.state == brk.QUARANTINED
        g.breaker.reset()
        calls = dev.calls
        g.verify_ed25519_raw(pubs, msgs, sigs)  # schedule exhausted: clean
        assert dev.calls == calls + 1
        assert g.breaker.state == brk.CLOSED


def _window(sizes, tag=0, forged=()):
    """votes/powers/totals in the planner's ragged-window shape."""
    flat_pubs, flat_msgs, flat_sigs = _triples(sum(sizes), tag=tag)
    votes, powers, totals = [], [], []
    i = 0
    for h, V in enumerate(sizes):
        vrow, prow = [], []
        for v in range(V):
            sig = flat_sigs[i]
            if (h, v) in forged:
                bad = bytearray(sig)
                bad[9] ^= 1
                sig = bytes(bad)
            vrow.append((flat_pubs[i], flat_msgs[i], sig))
            prow.append((h + v) % 5 + 1)
            i += 1
        votes.append(vrow)
        powers.append(prow)
        totals.append(sum(prow))
    return votes, powers, totals


def _assert_same_verdict(a, b):
    assert np.array_equal(a.ok, b.ok)
    assert np.array_equal(a.tally, b.tally)
    assert np.array_equal(a.committed, b.committed)
    assert np.array_equal(a.sigs_ok, b.sigs_ok)


class TestPlannerGuard:
    def teardown_method(self):
        from tendermint_tpu.parallel import planner

        planner.set_device_executor(None)

    def test_raising_executor_completes_on_host(self):
        from tendermint_tpu.parallel import planner

        votes, powers, totals = _window([3, 5], tag=10, forged={(1, 2)})
        host = planner.verify_window(votes, powers, totals, use_device=False)

        def explode(plan, mesh):
            raise InjectedDeviceError("kernel crashed")

        planner.set_device_executor(explode)
        dev = planner.verify_window(votes, powers, totals, use_device=True)
        _assert_same_verdict(dev, host)
        assert brk.get_device_breaker().snapshot()["failures_total"] > 0

    def test_corrupting_executor_quarantines(self):
        from tendermint_tpu.parallel import planner

        brk.configure_device_guard(audit_sample_rate=1.0)
        votes, powers, totals = _window([4], tag=11)
        host = planner.verify_window(votes, powers, totals, use_device=False)

        def corrupt(plan, mesh):
            v = planner._execute_host(plan)
            j = int(np.flatnonzero(plan.wellformed)[0])
            h, vv = int(plan.coords[j, 0]), int(plan.coords[j, 1])
            v.ok = np.array(v.ok, copy=True)
            v.ok[h, vv] = not v.ok[h, vv]
            return v

        planner.set_device_executor(corrupt)
        dev = planner.verify_window(votes, powers, totals, use_device=True)
        _assert_same_verdict(dev, host)  # wrong verdict must not escape
        assert brk.get_device_breaker().state == brk.QUARANTINED

    def test_pipeline_survives_mid_stream_fault(self, monkeypatch):
        """Regression: one raising dispatch used to abandon every queued
        and in-flight window behind it.  The failed window must complete
        on the host and the stream must keep going."""
        from tendermint_tpu.parallel import planner

        specs = [_window([2, 3], tag=20 + i) for i in range(4)]
        hosts = [
            planner.verify_window(*s, use_device=False) for s in specs
        ]
        real = planner.execute_plan
        n_calls = {"n": 0}

        def flaky_execute(plan, **kw):
            n_calls["n"] += 1
            if n_calls["n"] == 2:
                raise InjectedDeviceError("device died mid-stream")
            return real(plan, **kw)

        monkeypatch.setattr(planner, "execute_plan", flaky_execute)
        pipe = planner.WindowPipeline(use_device=True, prefetch=2)
        verdicts = list(pipe.run(iter(specs)))
        assert len(verdicts) == len(specs)
        for got, want in zip(verdicts, hosts):
            _assert_same_verdict(got, want)
        snap = brk.get_device_breaker().snapshot()
        assert snap["failures_total"] > 0


class TestCommitWindowGuard:
    def _win(self, tag=30):
        from tendermint_tpu.parallel import commit_verify as cv

        votes, powers, totals = _window([2, 3], tag=tag, forged={(0, 1)})
        win = cv.pack_commit_window(votes, powers)
        total = max(totals)
        return cv, win, total

    def test_raising_device_completes_on_host(self, monkeypatch):
        cv, win, total = self._win(tag=30)
        want = cv._verify_window_host(win, total)

        def explode(win, total_power, mesh=None):
            raise InjectedDeviceError("dispatch failed")

        monkeypatch.setattr(cv, "_verify_window_device", explode)
        ok, tally, committed = cv.verify_commit_window(win, total)
        assert np.array_equal(ok, want[0])
        assert np.array_equal(tally, want[1])
        assert np.array_equal(committed, want[2])
        assert brk.get_device_breaker().snapshot()["failures_total"] > 0

    def test_corrupting_device_quarantines(self, monkeypatch):
        cv, win, total = self._win(tag=31)
        brk.configure_device_guard(audit_sample_rate=1.0)
        want = cv._verify_window_host(win, total)

        def corrupt(win, total_power, mesh=None):
            ok = np.array(want[0], copy=True)
            h, v = np.argwhere(win.present)[0]
            ok[h, v] = not ok[h, v]
            return ok, want[1], want[2]

        monkeypatch.setattr(cv, "_verify_window_device", corrupt)
        ok, tally, committed = cv.verify_commit_window(win, total)
        assert np.array_equal(ok, want[0])  # corrupted verdict suppressed
        assert np.array_equal(tally, want[1])
        assert brk.get_device_breaker().state == brk.QUARANTINED

    def test_quarantined_breaker_skips_the_device(self, monkeypatch):
        cv, win, total = self._win(tag=32)
        want = cv._verify_window_host(win, total)
        brk.get_device_breaker().quarantine("audit_mismatch:test")
        called = {"n": 0}

        def count(win, total_power, mesh=None):
            called["n"] += 1
            return want

        monkeypatch.setattr(cv, "_verify_window_device", count)
        ok, _, _ = cv.verify_commit_window(win, total)
        assert np.array_equal(ok, want[0])
        assert called["n"] == 0


# -- the re-probe seam (satellite-1 regression) -------------------------------


class _RaisingTPU:
    init_attempts = 0

    def __init__(self, backend=None):
        type(self).init_attempts += 1
        raise RuntimeError("device runtime refused connection")


class _HealthyTPU:
    backend = "pallas"
    name = "tpu"

    def __init__(self, backend=None):
        self._host = HostBatchVerifier()

    def self_test(self):
        pass

    def verify_ed25519(self, items):
        return self._host.verify_ed25519(items)

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        return self._host.verify_ed25519_raw(pubs, msgs, sigs)

    def verify_secp256k1(self, items):
        return self._host.verify_secp256k1(items)


@pytest.fixture()
def fresh_default(monkeypatch):
    monkeypatch.delenv("TM_BATCH_VERIFIER", raising=False)
    with batch_mod._lock:
        saved = (batch_mod._default, batch_mod._latched_reason)
        batch_mod._default = None
        batch_mod._latched_reason = None
    yield
    with batch_mod._lock:
        batch_mod._default, batch_mod._latched_reason = saved


class TestReprobeSeam:
    def test_init_failure_no_longer_latches_forever(
        self, fresh_default, monkeypatch
    ):
        """A transient device-init failure latches the host path only
        until the breaker grants its half-open probe; a recovered device
        is then picked back up.  (Previously the latch was permanent.)"""
        clock = FakeClock()
        brk.configure_device_guard(
            breaker_threshold=3, breaker_backoff=1.0, clock=clock
        )
        _RaisingTPU.init_attempts = 0
        monkeypatch.setattr(batch_mod, "TPUBatchVerifier", _RaisingTPU)
        v = batch_mod.get_batch_verifier()
        assert isinstance(v, HostBatchVerifier)
        assert batch_mod.verifier_info()["latched_reason"] == "device_init_error"
        assert brk.get_device_breaker().state == brk.OPEN
        assert _RaisingTPU.init_attempts == 1

        # breaker still open: no re-probe, init is NOT hammered per call
        for _ in range(5):
            assert isinstance(
                batch_mod.get_batch_verifier(), HostBatchVerifier
            )
        assert _RaisingTPU.init_attempts == 1

        # device recovers; backoff elapses -> the probe re-selects it
        monkeypatch.setattr(batch_mod, "TPUBatchVerifier", _HealthyTPU)
        clock.advance(2.0)
        v = batch_mod.get_batch_verifier()
        assert isinstance(v, GuardedBatchVerifier)
        assert v.backend == "pallas"
        assert batch_mod.verifier_info()["latched_reason"] is None
        assert brk.get_device_breaker().state == brk.CLOSED

    def test_failed_probe_reopens_and_backs_off(
        self, fresh_default, monkeypatch
    ):
        clock = FakeClock()
        brk.configure_device_guard(breaker_backoff=1.0, clock=clock)
        _RaisingTPU.init_attempts = 0
        monkeypatch.setattr(batch_mod, "TPUBatchVerifier", _RaisingTPU)
        batch_mod.get_batch_verifier()
        clock.advance(2.0)
        batch_mod.get_batch_verifier()  # probe fails, breaker reopens
        assert _RaisingTPU.init_attempts == 2
        assert brk.get_device_breaker().state == brk.OPEN
        batch_mod.get_batch_verifier()  # inside doubled backoff: no probe
        assert _RaisingTPU.init_attempts == 2

    def test_no_tpu_latch_needs_explicit_force_reprobe(
        self, fresh_default, monkeypatch
    ):
        """A clean 'no device' verdict is not transient — only
        reprobe(force=True) (the device_breaker_reset reprobe knob)
        re-runs selection, in this process."""
        monkeypatch.setattr(
            batch_mod, "_try_device_default",
            lambda: (HostBatchVerifier(), "no_tpu"),
        )
        v = batch_mod.get_batch_verifier()
        assert isinstance(v, HostBatchVerifier)
        assert batch_mod.verifier_info()["latched_reason"] == "no_tpu"
        # passive calls never re-select a no_tpu latch
        assert batch_mod.get_batch_verifier() is v

        monkeypatch.setattr(
            batch_mod, "_try_device_default",
            lambda: (GuardedBatchVerifier(_HealthyTPU()), None),
        )
        v2 = batch_mod.reprobe(force=True)
        assert isinstance(v2, GuardedBatchVerifier)
        assert batch_mod.verifier_info()["latched_reason"] is None
