"""The guard's audit off the critical path (crypto/oracle_pool, oracle_worker):
the sample is drawn before the dispatch and verified by the same host oracle
in worker processes while the device call runs; nothing of the audit's
contract moves (same lanes, same oracle, every verdict compared, a mismatch
quarantines and the host recomputes)."""

import math
import os
import random
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tendermint_tpu.crypto import batch, oracle_pool, oracle_worker
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto import secp256k1 as secp
from tendermint_tpu.crypto.hashing import sha256
from tendermint_tpu.libs import breaker
from tendermint_tpu.sim.faults import FaultyDevice

AUDIT_LANES = "tendermint_verify_audit_oracle_total"
AUDITED = "tendermint_verify_device_audit_total"


# -- the audit as it was before this module: the reference ---------------------


def inline_audit(audit_seed, seq, n, rate, oracle):
    """GuardedBatchVerifier._audit as PR 24 left it: the lanes it samples for
    (audit_seed, seq, n) and the host oracle's verdict on each, in order."""
    k = min(n, max(1, int(math.ceil(n * rate))))
    lanes = random.Random((audit_seed << 20) ^ seq).sample(range(n), k)
    return lanes, [bool(oracle(i)) for i in lanes]


def ed_oracle(rows):
    return lambda i: ed.verify(*rows[i])


def secp_oracle(rows):
    return lambda i: secp.verify(rows[i][0], sha256(rows[i][1]), rows[i][2])


# -- rows ----------------------------------------------------------------------


def _noncanonical_pubkeys():
    """Encodings y + p of small decompressable ys: the same point as y, but
    outside the zone where OpenSSL agrees with Go, so ``_verify_pure`` decides."""
    return [(y + ed.P).to_bytes(32, "little") for y in range(19)
            if ed._decompress_xy(y.to_bytes(32, "little")) is not None]


@pytest.fixture(scope="module")
def ed_rows():
    """1,000 rows: mostly good signatures; every 7th with s + L (Go accepts,
    OpenSSL would not: ``_verify_pure``), every 11th with a flipped bit, every
    13th a non-canonical pubkey under a zero signature, every 17th with the
    top bits of s set (rejected before any curve work)."""
    privs = [ed.gen_privkey(bytes([i]) * 32) for i in range(8)]
    twins = _noncanonical_pubkeys()
    assert twins
    rows = []
    for i in range(1000):
        priv = privs[i % len(privs)]
        msg = b"vote/%d/" % i + bytes(96)
        pub, sig = priv[32:], ed.sign(priv, msg)
        if i % 7 == 3:
            s = int.from_bytes(sig[32:], "little") + ed.L
            assert s < 2 ** 253
            sig = sig[:32] + s.to_bytes(32, "little")
        if i % 11 == 5:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        if i % 13 == 6:
            pub, sig = twins[i % len(twins)], bytes(64)
        if i % 17 == 8:
            sig = sig[:63] + bytes([sig[63] | 0x20])
        rows.append((pub, msg, sig))
    want = [ed.verify(*r) for r in rows]
    # the fixture holds what it says: accepted s + L rows, and both verdicts
    assert any(want[i] for i in range(3, 1000, 7))
    assert 100 < sum(want) < 1000
    return rows, want


@pytest.fixture(scope="module")
def secp_rows():
    privs = [secp.gen_privkey(bytes([i + 1]) * 32) for i in range(4)]
    rows = []
    for i in range(48):
        priv = privs[i % len(privs)]
        msg = b"tx/%d" % i
        sig = secp.sign(priv, sha256(msg))
        if i % 5 == 2:
            msg += b"!"
        rows.append((secp.pubkey_compressed(priv), msg, sig))
    want = [secp.verify(p, sha256(m), s) for p, m, s in rows]
    assert 0 < sum(want) < len(want)
    return rows, want


# -- a pool of two, whatever this machine's cores ------------------------------


@pytest.fixture
def pool(monkeypatch):
    p = oracle_pool.OraclePool(2)
    monkeypatch.setattr(oracle_pool, "_pool", p)
    monkeypatch.setattr(oracle_pool, "_pool_tried", True)
    yield p
    pids = p.pids()
    p.close()
    for pid in pids:
        assert not _alive(pid)


def _alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    try:  # a zombie of ours still answers kill(0): reap it if it has exited
        return os.waitpid(pid, os.WNOHANG) == (0, 0)
    except ChildProcessError:
        return False


@pytest.fixture(autouse=True)
def fresh_guard():
    breaker.reset_device_guard()
    yield
    breaker.reset_device_guard()


class TableDevice:
    """A device that answers from a table of verdicts (and may flip lanes)."""

    backend = "table"

    def __init__(self, want, flip=(), during=None):
        self.want = np.array(want, dtype=bool)
        self.flip = list(flip)
        self.during = during
        self.calls = 0

    def _answer(self, n):
        self.calls += 1
        if self.during is not None:
            self.during()
        ok = self.want[:n].copy()
        ok[self.flip] = ~ok[self.flip]
        return ok

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        return self._answer(len(pubs))

    def verify_ed25519(self, items):
        return self._answer(len(items))

    def verify_secp256k1(self, items):
        return self._answer(len(items))


def _spy_audit(monkeypatch, guard, pool):
    """Record each dispatch's sampled lanes and the verdicts collected."""
    seen = {"lanes": [], "verdicts": [], "lost": []}
    submit, collect = guard._submit_audit, pool.collect

    def spy_submit(*a):
        sample = submit(*a)
        seen["lanes"].append(list(sample.lanes))
        return sample

    def spy_collect(ticket, deadline):
        verdicts, lost = collect(ticket, deadline)
        seen["verdicts"].append(list(verdicts))
        seen["lost"].append(lost)
        return verdicts, lost

    monkeypatch.setattr(guard, "_submit_audit", spy_submit)
    monkeypatch.setattr(pool, "collect", spy_collect)
    return seen


# -- (a) same lanes, same verdicts ---------------------------------------------


class TestSameAuditAsInline:
    @pytest.mark.parametrize("n, rate", [(160, 0.05), (333, 0.05), (1000, 0.05),
                                         (64, 1.0)])
    @pytest.mark.parametrize("audit_seed", [0, 1234567])
    def test_ed25519_lanes_and_verdicts(self, monkeypatch, pool, ed_rows,
                                        n, rate, audit_seed, verify_counters):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:n]))
        g = batch.GuardedBatchVerifier(
            TableDevice(want), audit_rate=rate, audit_seed=audit_seed)
        seen = _spy_audit(monkeypatch, g, pool)
        before = verify_counters(AUDIT_LANES, {"where": "pool"})
        for seq in range(3):
            ok = g.verify_ed25519_raw(pubs, msgs, sigs)
            assert ok.tolist() == want[:n]
            lanes, verdicts = inline_audit(audit_seed, seq, n, rate, ed_oracle(rows))
            assert seen["lanes"][seq] == lanes
            assert seen["verdicts"][seq] == verdicts
        sampled = sum(map(len, seen["lanes"]))
        assert verify_counters(AUDIT_LANES, {"where": "pool"}) == before + sampled
        assert seen["lost"] == [0, 0, 0]
        assert breaker.get_device_breaker().state == breaker.CLOSED
        # the fixture's pure-Python rows were among the sampled ones
        assert any(i % 7 == 3 or i % 13 == 6 for ls in seen["lanes"] for i in ls)

    @pytest.mark.parametrize("n, rate, audit_seed", [(48, 0.4, 0), (40, 0.5, 99)])
    def test_secp256k1_lanes_and_verdicts(self, monkeypatch, pool, secp_rows,
                                          n, rate, audit_seed):
        rows, want = secp_rows
        items = [batch.SigItem(*r) for r in rows[:n]]
        g = batch.GuardedBatchVerifier(
            TableDevice(want), audit_rate=rate, audit_seed=audit_seed)
        seen = _spy_audit(monkeypatch, g, pool)
        for seq in range(2):
            assert g.verify_secp256k1(items).tolist() == want[:n]
            lanes, verdicts = inline_audit(
                audit_seed, seq, n, rate, secp_oracle(rows))
            assert seen["lanes"][seq] == lanes
            assert seen["verdicts"][seq] == verdicts

    def test_sigitem_form_samples_the_same_rows(self, monkeypatch, pool, ed_rows):
        rows, want = ed_rows
        items = [batch.SigItem(*r) for r in rows[:400]]
        g = batch.GuardedBatchVerifier(TableDevice(want), audit_seed=5)
        seen = _spy_audit(monkeypatch, g, pool)
        assert g.verify_ed25519(items).tolist() == want[:400]
        assert (seen["lanes"][0], seen["verdicts"][0]) == inline_audit(
            5, 0, 400, 0.05, ed_oracle(rows))

    def test_a_host_completion_uses_up_a_sequence_number(
            self, monkeypatch, pool, ed_rows):
        """Breaker open on dispatch 0, a retried error on dispatch 1: the
        device's next answer is audited on the lanes of seq 2."""
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:200]))
        dev = FaultyDevice(TableDevice(want), schedule=["fail", "ok", "ok"])
        g = batch.GuardedBatchVerifier(dev, retries=1, audit_seed=3)
        seen = _spy_audit(monkeypatch, g, pool)
        g.breaker.trip("test")
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:200]
        assert seen["lanes"] == [] and dev.calls == 0
        g.breaker.reset()
        g.verify_ed25519_raw(pubs, msgs, sigs)   # fails once, retried: seq 1
        g.verify_ed25519_raw(pubs, msgs, sigs)   # seq 2
        assert dev.calls == 3 and len(seen["lanes"]) == 2
        for at, seq in enumerate((1, 2)):
            assert seen["lanes"][at] == inline_audit(
                3, seq, 200, 0.05, ed_oracle(rows))[0]
        assert g.snapshot()["dispatches"] == 3


# -- (b) a wrong device verdict is caught through the pool ---------------------


class TestMismatchThroughThePool:
    def test_faulty_device_is_quarantined_and_host_recomputes(
            self, pool, ed_rows, verify_counters):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:64]))
        dev = FaultyDevice(batch.HostBatchVerifier(), seed=7, schedule=["corrupt"])
        g = batch.GuardedBatchVerifier(dev, audit_rate=1.0)
        before = verify_counters(AUDITED, {"outcome": "mismatch"})
        pooled = verify_counters(AUDIT_LANES, {"where": "pool"})
        ok = g.verify_ed25519_raw(pubs, msgs, sigs)
        assert dev.corruptions == 1
        assert ok.tolist() == want[:64]          # the whole window, from the host
        assert g.breaker.state == breaker.QUARANTINED
        assert verify_counters(AUDITED, {"outcome": "mismatch"}) == before + 1
        assert verify_counters(AUDIT_LANES, {"where": "pool"}) == pooled + 64
        assert g.snapshot()["audit_mismatches"] == 1
        # latched: the next dispatch never reaches the device
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:64]
        assert dev.calls == 1

    def test_one_flipped_sampled_lane_of_five_percent(self, pool, ed_rows):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows))
        lanes, _ = inline_audit(11, 0, 1000, 0.05, ed_oracle(rows))
        unsampled = next(i for i in range(1000) if i not in lanes)
        g = batch.GuardedBatchVerifier(
            TableDevice(want, flip=[lanes[17]]), audit_seed=11)
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want
        assert g.breaker.state == breaker.QUARANTINED
        # the audit is a sample: a flip outside it is what the rate accepts
        breaker.reset_device_guard()
        g = batch.GuardedBatchVerifier(
            TableDevice(want, flip=[unsampled]), audit_seed=11)
        got = g.verify_ed25519_raw(pubs, msgs, sigs).tolist()
        assert got[unsampled] != want[unsampled]
        assert g.breaker.state == breaker.CLOSED


# -- (c) a worker lost between submit and collect ------------------------------


class TestWorkerLoss:
    def _kill_one(self, pool, sig=signal.SIGKILL):
        victim = pool.pids()[0]

        def during():
            os.kill(victim, sig)
            if sig == signal.SIGKILL:
                deadline = time.monotonic() + 10
                while _alive(victim) and time.monotonic() < deadline:
                    time.sleep(0.005)
        return victim, during

    def test_killed_worker_lanes_are_verified_inline(
            self, monkeypatch, pool, ed_rows, verify_counters, caplog):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:800]))
        victim, during = self._kill_one(pool)
        dev = TableDevice(want, during=during)
        g = batch.GuardedBatchVerifier(dev, audit_rate=0.2, audit_seed=21)
        seen = _spy_audit(monkeypatch, g, pool)
        read = lambda where: verify_counters(AUDIT_LANES, {"where": where})
        before = {w: read(w) for w in ("pool", "inline", "inline_after_loss")}
        ok_before = verify_counters(AUDITED, {"outcome": "ok"})
        with caplog.at_level("WARNING", logger="tendermint_tpu.verify"):
            ok = g.verify_ed25519_raw(pubs, msgs, sigs)
        assert ok.tolist() == want[:800]
        # all 160 lanes audited, the lost worker's 80 on this thread
        lanes, verdicts = inline_audit(21, 0, 800, 0.2, ed_oracle(rows))
        assert seen["lanes"] == [lanes] and seen["verdicts"] == [verdicts]
        assert seen["lost"] == [80]
        assert read("inline_after_loss") == before["inline_after_loss"] + 80
        assert read("pool") == before["pool"] + 80
        assert read("inline") == before["inline"]
        assert verify_counters(AUDITED, {"outcome": "ok"}) == ok_before + 160
        assert "oracle worker" in caplog.text and "lost" in caplog.text
        assert g.breaker.state == breaker.CLOSED
        # replaced: two live workers again, the victim not among them
        pids = pool.pids()
        assert len(pids) == 2 and victim not in pids
        assert all(_alive(p) for p in pids) and not _alive(victim)
        dev.during = None
        g.verify_ed25519_raw(pubs, msgs, sigs)
        assert seen["lost"] == [80, 0]

    def test_a_mismatch_on_a_lost_workers_lane_is_still_caught(
            self, pool, ed_rows):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:800]))
        lanes, _ = inline_audit(0, 0, 800, 0.2, ed_oracle(rows))
        _victim, during = self._kill_one(pool)
        # both halves of the sample hold a flipped lane: whichever worker
        # the victim was, one of them is verified after the loss
        g = batch.GuardedBatchVerifier(
            TableDevice(want, flip=[lanes[3], lanes[133]], during=during),
            audit_rate=0.2)
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:800]
        assert g.breaker.state == breaker.QUARANTINED
        assert g.snapshot()["audit_mismatches"] == 2

    def test_worker_that_does_not_answer_by_the_deadline(
            self, monkeypatch, pool, ed_rows):
        """SIGSTOP: alive, silent.  When the dispatch's deadline is spent its
        lanes are verified here and it is killed and replaced."""
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:800]))
        victim, during = self._kill_one(pool, signal.SIGSTOP)
        g = batch.GuardedBatchVerifier(
            TableDevice(want, during=during), deadline=0.4, audit_rate=0.2,
            audit_seed=2)
        seen = _spy_audit(monkeypatch, g, pool)
        t0 = time.monotonic()
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:800]
        assert 0.3 < time.monotonic() - t0 < 5
        assert seen["lost"] == [80]
        assert seen["verdicts"] == [inline_audit(2, 0, 800, 0.2, ed_oracle(rows))[1]]
        assert victim not in pool.pids() and not _alive(victim)

    def test_short_answer_counts_as_a_loss(self, monkeypatch, pool, ed_rows):
        rows, want = ed_rows
        reply = oracle_pool._Worker.reply

        def short(self, req_id, n, deadline):
            return reply(self, req_id, n + 1, deadline)

        monkeypatch.setattr(oracle_pool._Worker, "reply", short)
        ticket = pool.submit("ed25519", rows[:32])
        verdicts, lost = pool.collect(ticket, time.monotonic() + 10)
        assert verdicts == want[:32] and lost == 32


# -- (d) small samples never touch a pipe --------------------------------------


class TestInlineThreshold:
    @pytest.mark.parametrize("n, k", [(1, 1), (20, 1), (128, 7), (140, 7)])
    def test_small_sample_runs_on_the_calling_thread(
            self, monkeypatch, ed_rows, verify_counters, n, k):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:n]))

        def no_pool():
            raise AssertionError("a %d-lane sample asked for the pool" % k)

        monkeypatch.setattr(oracle_pool, "get_oracle_pool", no_pool)
        monkeypatch.setattr(subprocess, "Popen", no_pool)
        g = batch.GuardedBatchVerifier(TableDevice(want), audit_seed=8)
        before = verify_counters(AUDIT_LANES, {"where": "inline"})
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:n]
        assert verify_counters(AUDIT_LANES, {"where": "inline"}) == before + k
        assert k < oracle_pool.MIN_POOL_LANES

    def test_first_sample_at_the_threshold_goes_to_the_pool(
            self, pool, ed_rows, verify_counters):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:141]))   # ceil(7.05) = 8
        g = batch.GuardedBatchVerifier(TableDevice(want))
        before = verify_counters(AUDIT_LANES, {"where": "pool"})
        g.verify_ed25519_raw(pubs, msgs, sigs)
        assert verify_counters(AUDIT_LANES, {"where": "pool"}) == before + 8

    def test_an_inline_mismatch_quarantines(self, ed_rows):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:100]))
        lanes, _ = inline_audit(0, 0, 100, 0.05, ed_oracle(rows))
        g = batch.GuardedBatchVerifier(TableDevice(want, flip=[lanes[4]]))
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:100]
        assert g.breaker.state == breaker.QUARANTINED

    @pytest.mark.parametrize("cores, workers", [
        (1, 0), (2, 0), (3, 1), (4, 2), (5, 3), (6, 4), (13, 4), (30, 4)])
    def test_pool_size_follows_the_cores(self, cores, workers):
        assert oracle_pool.pool_size(cores) == workers

    def test_no_pool_where_there_are_too_few_cores(self, monkeypatch, ed_rows):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows))
        monkeypatch.setattr(oracle_pool, "_pool", None)
        monkeypatch.setattr(oracle_pool, "_pool_tried", False)
        monkeypatch.setattr(os, "sched_getaffinity", lambda _pid: {0, 1})
        assert oracle_pool.get_oracle_pool() is None
        g = batch.GuardedBatchVerifier(TableDevice(want))
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want


# -- (e) the worker process ----------------------------------------------------


WORKER = [sys.executable, "-m", "tendermint_tpu.crypto.oracle_worker"]
_worker_env = oracle_pool._worker_env


class TestWorkerProcess:
    def test_imports_neither_jax_nor_numpy_and_ends_at_eof(self):
        code = (
            "import runpy, sys\n"
            "runpy.run_module('tendermint_tpu.crypto.oracle_worker',"
            " run_name='__main__')\n"
            "print(sorted(m for m in ('jax', 'jaxlib', 'numpy') if m in sys.modules))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], stdin=subprocess.DEVNULL,
            capture_output=True, text=True, timeout=60, env=_worker_env())
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "[]"

    def test_answers_frames_in_order_then_exits_when_stdin_closes(self, ed_rows):
        rows, want = ed_rows
        proc = subprocess.Popen(
            WORKER, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=_worker_env())
        try:
            for req_id, (lo, hi) in enumerate([(0, 30), (30, 31), (31, 31)], 5):
                proc.stdin.write(oracle_worker.encode_request(
                    req_id, "ed25519", rows[lo:hi]))
                proc.stdin.flush()
                size = int.from_bytes(proc.stdout.read(4), "little")
                got = oracle_worker.decode_reply(proc.stdout.read(size))
                assert got == (req_id, 0, want[lo:hi])
            # ^C in the node's terminal reaches the children too: not their cue
            proc.send_signal(signal.SIGINT)
            time.sleep(0.1)
            assert proc.poll() is None
            proc.stdin.close()
            assert proc.wait(timeout=10) == 0
            assert proc.stdout.read() == b""
        finally:
            proc.kill()
            proc.wait()

    def test_an_oracle_that_raises_is_answered_not_fatal(self, ed_rows):
        rows, want = ed_rows
        frame = oracle_worker.encode_request(1, "ed25519", rows[:2])
        bad = bytearray(frame)
        bad[4 + 8] = 9   # no such algo
        proc = subprocess.Popen(
            WORKER, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, env=_worker_env())
        try:
            out, _ = proc.communicate(bytes(bad) + frame, timeout=30)
        finally:
            proc.kill()
        first = int.from_bytes(out[:4], "little")
        assert oracle_worker.decode_reply(out[4:4 + first]) == (1, 1, [])
        assert oracle_worker.decode_reply(out[8 + first:]) == (1, 0, want[:2])

    def test_request_frames_round_trip(self, ed_rows, secp_rows):
        for algo, (rows, _want) in (("ed25519", ed_rows), ("secp256k1", secp_rows)):
            frame = oracle_worker.encode_request(2 ** 40 + 7, algo, rows[:20])
            assert int.from_bytes(frame[:4], "little") == len(frame) - 4
            assert oracle_worker.decode_request(frame[4:]) == (
                2 ** 40 + 7, algo, rows[:20])
        with pytest.raises(ValueError):
            oracle_worker.decode_request(frame[4:] + b"x")
        with pytest.raises(ValueError):
            oracle_worker.verify_rows("sr25519", [])

    def test_pool_children_end_with_the_pool(self):
        p = oracle_pool.OraclePool(2)
        pids = p.pids()
        assert all(_alive(pid) for pid in pids)
        p.close()
        assert not any(_alive(pid) for pid in pids)
        assert p.pids() == []


# -- (f) a dispatch that ends on the host leaves the pool usable ---------------


class TestHostCompletionLeavesThePoolUsable:
    @pytest.mark.parametrize("schedule, reason", [
        (["fail", "fail"], "error"), (["hang", "hang"], "timeout")])
    def test_answers_outstanding_are_drained_and_dropped(
            self, monkeypatch, pool, ed_rows, verify_counters, schedule, reason):
        rows, want = ed_rows
        pubs, msgs, sigs = (list(c) for c in zip(*rows[:600]))
        dev = FaultyDevice(TableDevice(want), schedule=list(schedule), hang_s=1.0)
        g = batch.GuardedBatchVerifier(
            dev, deadline=0.15, retries=1, audit_seed=4,
            breaker=breaker.CircuitBreaker(threshold=10))
        seen = _spy_audit(monkeypatch, g, pool)
        fallbacks = verify_counters(
            "tendermint_verify_device_fallback_total", {"reason": reason})
        pids = pool.pids()
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:600]
        assert verify_counters(
            "tendermint_verify_device_fallback_total",
            {"reason": reason}) == fallbacks + 1
        assert seen["verdicts"] == []            # nothing was collected
        # the same workers serve the next dispatch, and nothing is left over
        assert g.verify_ed25519_raw(pubs, msgs, sigs).tolist() == want[:600]
        assert pool.pids() == pids
        assert seen["lost"] == [0]
        assert seen["verdicts"] == [inline_audit(4, 1, 600, 0.05, ed_oracle(rows))[1]]
        for w in pool._workers:
            assert not w._replies and not w._dropped and not w._rbuf

    def test_abandoned_before_the_answer_is_dropped_when_it_comes(
            self, pool, ed_rows):
        rows, want = ed_rows
        slow = pool.submit("ed25519", rows[:400])
        pool.abandon(slow)                       # the workers are still on it
        ticket = pool.submit("ed25519", rows[400:560])   # a frame each again
        assert pool.collect(ticket, time.monotonic() + 30) == (want[400:560], 0)
        for w in pool._workers:
            assert not w._replies and not w._dropped

    def test_frames_follow_the_sample_size(self, ed_rows, secp_rows):
        """One frame until a second worker would get 64 lanes of its own;
        5 % of a 10,240-lane commit is four frames of 125."""
        rows, want = ed_rows
        four = oracle_pool.OraclePool(4)
        try:
            for k, sizes in [(8, [8]), (77, [77]), (127, [127]), (130, [65, 65]),
                             (330, [82, 83, 82, 83]), (500, [125] * 4),
                             (1000, [250] * 4)]:
                ticket = four.submit("ed25519", rows[:k])
                assert [hi - lo for _w, _id, lo, hi in ticket.parts] == sizes
                assert len({w for w, *_ in ticket.parts}) == len(sizes)
                assert four.collect(ticket, None) == (want[:k], 0)
            # a secp256k1 lane is 36 ed25519 lanes of oracle: spread sooner
            ticket = four.submit("secp256k1", secp_rows[0][:13])
            assert [hi - lo for _w, _id, lo, hi in ticket.parts] == [3, 3, 3, 4]
            assert four.collect(ticket, None) == (secp_rows[1][:13], 0)
        finally:
            four.close()

    def test_concurrent_dispatches_share_the_workers(self, pool, ed_rows):
        """More callers than workers, a short switch interval: every caller
        gets its own lanes' verdicts."""
        rows, want = ed_rows
        errors, done = [], []

        def caller(at):
            try:
                for rep in range(6):
                    lo = (at * 97 + rep * 31) % 600
                    n = 8 + (at + rep) % 40
                    t = pool.submit("ed25519", rows[lo:lo + n])
                    got = pool.collect(t, time.monotonic() + 30)
                    assert got == (want[lo:lo + n], 0), (at, rep)
                done.append(at)
            except BaseException as e:   # surfaced on the main thread below
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=caller, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not errors, errors
        assert sorted(done) == list(range(6))
        assert not any(t.is_alive() for t in threads)


# -- the three per-layer metrics that read the mechanism -----------------------


class TestBenchmarkMetricFiles:
    """benchmark/metrics/audit_pool_share.{commit,sync}.json and
    audit_submit_ms.commit.json, reduced as a traced run reduces them."""

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _data(self, cell_name, spans=(), counters=None):
        from benchmark import harness

        bench = harness.Bench(self.ROOT)
        return harness.RunData(
            bench=bench, cell=bench.cell(cell_name), device_kind="TPU v5 lite",
            samples={}, totals={}, spans=list(spans), counters=counters or {})

    @pytest.mark.parametrize("metric, cell", [
        ("audit_pool_share.commit", "commit10k-stream"),
        ("audit_pool_share.sync", "sync64-empty")])
    def test_pool_share_is_pool_lanes_over_all_audited_lanes(self, metric, cell):
        family = "tendermint_verify_audit_oracle_total"
        d = self._data(cell, counters={
            family + '{where="pool"}': 980.0, family + '{where="inline"}': 15.0,
            family + '{where="inline_after_loss"}': 5.0,
            'tendermint_verify_device_audit_total{outcome="ok"}': 1000.0})
        assert any(m["name"] == metric for m in d.cell.per_layer)
        assert d.cell.reduce(metric, d) == pytest.approx(0.98)
        # the parent's program has no such family: nothing to read, no raise
        assert d.cell.reduce(metric, self._data(cell, counters={
            'tendermint_verify_device_audit_total{outcome="ok"}': 1000.0})) is None

    def test_submit_ms_is_the_mean_guard_submit_span(self):
        def span(name, t0_ms, t1_ms):
            return {"name": name, "t0": t0_ms * 1e6, "t1": t1_ms * 1e6,
                    "tid": 1, "args": {}}

        d = self._data("commit10k-stream", spans=[
            span("guard.submit", 1.0, 1.8), span("guard.audit", 30.0, 30.4),
            span("guard.submit", 50.0, 50.6)])
        assert d.cell.reduce("audit_submit_ms.commit", d) == pytest.approx(0.7)
        assert d.cell.reduce("audit_submit_ms.commit", self._data(
            "commit10k-stream", spans=[span("guard.audit", 30.0, 30.4)])) is None
