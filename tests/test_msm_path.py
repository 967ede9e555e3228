"""One-MSM-per-window RLC verification ([verify] ed25519_path = msm).

The adversarial parity matrix for ops/ed25519_msm + the msm routing in
crypto/batch.py, parallel/planner.py, parallel/commit_verify.py and
rpc/core/env.py: forged signatures, mutant R, the Go malleability zone
(s+L must stay ACCEPTED), the sig[63]&224 top-bits reject and a
non-canonical R hidden inside otherwise-clean windows must localize to
the exact rows with verdicts bit-identical to the serial verifier — on
the RLC fast path AND through the chunk-RLC/ladder fallback, under the
PR-9 device guard, eager and lazy, interpret-Pallas and
XLA-CPU (the interpret and eager combos ride the slow lane).
"""

import os

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as batch_mod
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.libs import breaker as brk

# Pinned RLC coefficient seed: keeps the Pippenger schedule shapes (and
# therefore the jit cache) stable across test runs.  Soundness must not
# depend on the coefficients, so tests also cross-check a second seed.
SEED = 1234


@pytest.fixture(autouse=True)
def _fresh_guard():
    brk.reset_device_guard()
    yield
    brk.reset_device_guard()


@pytest.fixture()
def _msm_default():
    """Route device verification through the msm path for one test."""
    batch_mod.set_default_ed25519_path("msm")
    yield
    batch_mod.set_default_ed25519_path(None)


def _corpus(n, tag=0):
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes([(i % 251) + 1, 13, (tag % 250) + 1]) * 16
        priv = ed.gen_privkey(seed[:32])
        msg = b"msm-%d-%d" % (tag, i)
        pubs.append(priv[32:])
        msgs.append(msg)
        sigs.append(ed.sign(priv, msg))
    return pubs, msgs, sigs


def _adversarial_window(tag=0):
    """16 rows: 10 clean + every Go verification edge the kernels must
    honor, with the expected per-row verdicts."""
    pubs, msgs, sigs = _corpus(16, tag=tag)
    sigs = [bytearray(s) for s in sigs]
    sigs[10][40] ^= 1  # forged: one bit of s
    sigs[11][3] ^= 1  # mutant R: one bit of the R encoding
    # malleability zone: s+L is still < 2^253, so sig[63]&224 == 0 and Go
    # ACCEPTS it ([s+L]B == [s]B) — a batch path that reduces mod L or
    # range-checks s < L would wrongly reject this row
    s12 = int.from_bytes(bytes(sigs[12][32:]), "little")
    assert s12 < ed.L
    sigs[12][32:] = (s12 + ed.L).to_bytes(32, "little")
    assert sigs[12][63] & 224 == 0
    sigs[13][63] |= 0xE0  # the ONLY scalar reject Go applies
    # non-canonical R: enc(p+1) decompresses (y ≡ 1) but re-encodes
    # differently, so the R == enc(decode(R)) identity check must reject
    sigs[14][:32] = (ed.P + 1).to_bytes(32, "little")
    pubs[15] = pubs[0]  # signed under a different key
    sigs = [bytes(s) for s in sigs]
    expected = np.array(
        [True] * 10 + [False, False, True, False, False, False], dtype=bool
    )
    return pubs, msgs, sigs, expected


def _np_batch(pubs, sigs):
    p = np.frombuffer(b"".join(bytes(x) for x in pubs), np.uint8)
    s = np.frombuffer(b"".join(sigs), np.uint8)
    return p.reshape(len(pubs), 32), s.reshape(len(sigs), 64)


class TestHostReference:
    """The serial verifier is the ground truth every batch path must
    match bit-for-bit — pin its verdicts on the edge rows first."""

    def test_serial_edge_verdicts(self):
        pubs, msgs, sigs, expected = _adversarial_window(tag=1)
        got = np.array(
            [ed.verify(bytes(p), m, s) for p, m, s in zip(pubs, msgs, sigs)]
        )
        assert np.array_equal(got, expected)
        assert got[12], "s+L malleability-zone row must stay ACCEPTED"
        assert not got[13] and not got[14]

    def test_host_verify_batch_parity(self):
        pubs, msgs, sigs, expected = _adversarial_window(tag=2)
        items = [
            (bytes(p), m, s) for p, m, s in zip(pubs, msgs, sigs)
        ]
        assert np.array_equal(
            np.asarray(ed.verify_batch(items), dtype=bool), expected
        )


class TestXlaMsm:
    """XLA-CPU kernels: the RLC fast path and the chunk-RLC/ladder
    localization fallback vs the exact ladder, lazy carries in tier-1."""

    # every distinct window content/seed pair retraces the MSM schedule
    # (~10 s on a 1-core box), so tier-1 keeps only the adversarial pair
    # below — clean-window accept rides the planner parity test and the
    # slow lane covers the rest of the matrix
    @pytest.mark.slow
    def test_clean_window_accepts_fast_path(self):
        from tendermint_tpu.ops import ed25519_verify as xk

        pubs, msgs, sigs = _corpus(16, tag=3)
        p, s = _np_batch(pubs, sigs)
        ok = xk.rlc_verify_batch(p, msgs, s, carry_mode="lazy", seed=SEED)
        assert ok.all()

    def test_adversarial_localization(self):
        from tendermint_tpu.ops import ed25519_verify as xk

        pubs, msgs, sigs, expected = _adversarial_window(tag=4)
        p, s = _np_batch(pubs, sigs)
        got = xk.rlc_verify_batch(p, msgs, s, carry_mode="lazy", seed=SEED)
        assert np.array_equal(got, expected), (
            f"msm verdicts diverge from serial: "
            f"{np.nonzero(got != expected)[0].tolist()}"
        )
        # and bit-identical to the per-row ladder under the same schedule
        ladder = xk.verify_batch(p, msgs, s, carry_mode="lazy")
        assert np.array_equal(got, ladder)

    @pytest.mark.slow
    def test_verdicts_seed_independent(self):
        from tendermint_tpu.ops import ed25519_verify as xk

        pubs, msgs, sigs, expected = _adversarial_window(tag=5)
        p, s = _np_batch(pubs, sigs)
        a = xk.rlc_verify_batch(p, msgs, s, seed=SEED)
        b = xk.rlc_verify_batch(p, msgs, s, seed=0xDEAD_BEEF)
        c = xk.rlc_verify_batch(p, msgs, s)  # content-derived rlc_seed
        assert np.array_equal(a, expected)
        assert np.array_equal(a, b) and np.array_equal(a, c)

    @pytest.mark.slow
    def test_adversarial_localization_eager(self):
        from tendermint_tpu.ops import ed25519_verify as xk

        pubs, msgs, sigs, expected = _adversarial_window(tag=6)
        p, s = _np_batch(pubs, sigs)
        got = xk.rlc_verify_batch(p, msgs, s, carry_mode="eager", seed=SEED)
        assert np.array_equal(got, expected)


class TestPallasInterpretMsm:
    """Interpret-mode Pallas ladders compile for ~5 min — slow lane only
    (the convention of tests/test_pallas_interpret.py)."""

    @pytest.mark.slow
    @pytest.mark.skipif(
        not os.environ.get("TM_RUN_SLOW"),
        reason="interpret-mode pallas ladder compile takes ~5 min "
               "(set TM_RUN_SLOW=1)",
    )
    def test_interpret_adversarial_localization(self):
        from tendermint_tpu.ops import ed25519_pallas as pk

        pubs, msgs, sigs, expected = _adversarial_window(tag=7)
        p, s = _np_batch(pubs, sigs)
        got = pk.rlc_verify_batch(p, msgs, s, interpret=True, seed=SEED)
        assert np.array_equal(got, expected)

    @pytest.mark.slow
    @pytest.mark.skipif(
        not os.environ.get("TM_RUN_SLOW"),
        reason="interpret-mode pallas ladder compile takes ~5 min "
               "(set TM_RUN_SLOW=1)",
    )
    def test_interpret_clean_window(self):
        from tendermint_tpu.ops import ed25519_pallas as pk

        pubs, msgs, sigs = _corpus(16, tag=8)
        p, s = _np_batch(pubs, sigs)
        assert pk.rlc_verify_batch(p, msgs, s, interpret=True,
                                   seed=SEED).all()


class TestPathKnob:
    """[verify] ed25519_path resolution: explicit > TM_ED25519_PATH >
    config default > ladder."""

    def test_resolution_precedence(self, monkeypatch):
        r = batch_mod._resolve_ed25519_path
        monkeypatch.delenv("TM_ED25519_PATH", raising=False)
        assert r(None) == "ladder"
        assert r("msm") == "msm"
        assert r("auto") == "ladder"
        batch_mod.set_default_ed25519_path("msm")
        try:
            assert r(None) == "msm"
            monkeypatch.setenv("TM_ED25519_PATH", "ladder")
            assert r(None) == "ladder"  # env outranks the config default
            assert r("msm") == "msm"  # explicit outranks everything
        finally:
            batch_mod.set_default_ed25519_path(None)

    def test_invalid_path_rejected(self, monkeypatch):
        monkeypatch.delenv("TM_ED25519_PATH", raising=False)
        with pytest.raises(ValueError):
            batch_mod._resolve_ed25519_path("pippenger")
        # the setter stores unvalidated;
        # resolution is where a typo'd config value surfaces
        batch_mod.set_default_ed25519_path("msmm")
        try:
            with pytest.raises(ValueError):
                batch_mod._resolve_ed25519_path(None)
        finally:
            batch_mod.set_default_ed25519_path(None)

    def test_config_default_is_ladder(self):
        from tendermint_tpu.config.config import VerifyConfig

        assert VerifyConfig().ed25519_path == "ladder"


class TestPlannerMsm:
    """planner._execute_device routes whole windows through one MSM when
    the knob says so — verdicts must match the per-vote host reference
    exactly, including localization inside dirty windows."""

    def test_ragged_window_parity(self, _msm_default):
        from tendermint_tpu.parallel import planner
        from tests.test_planner import _assert_verdict_matches, _ragged_window

        votes, powers, totals = _ragged_window(
            [3, 5, 8],
            absent={(1, 4)},
            forged={(2, 2)},
            malformed={(0, 1)},
            tag=40,
        )
        verdict = planner.verify_window(votes, powers, totals,
                                        use_device=True)
        _assert_verdict_matches(verdict, votes, powers, totals)
        assert not verdict.ok[2, 2] and verdict.ok[2, 1]

    def test_clean_window_parity(self, _msm_default):
        from tendermint_tpu.parallel import planner
        from tests.test_planner import _assert_verdict_matches, _ragged_window

        votes, powers, totals = _ragged_window([4, 12], tag=41)
        verdict = planner.verify_window(votes, powers, totals,
                                        use_device=True)
        _assert_verdict_matches(verdict, votes, powers, totals)
        assert verdict.committed.all()

    def test_mixed_keys_fall_back_to_host(self, _msm_default):
        from tendermint_tpu.parallel import planner
        from tests.test_planner import TestPlannerMixedKeys

        votes, powers, totals = TestPlannerMixedKeys()._mixed_window()
        verdict = planner.verify_window(votes, powers, totals,
                                        use_device=True)
        for h, row in enumerate(votes):
            assert verdict.ok[h, : len(row)].all()
        assert verdict.committed.tolist() == [True, True, True]

    def test_quarantined_device_still_exact(self, _msm_default):
        """PR-9 guard invariance: a quarantined breaker diverts the msm
        window to the host oracle with identical verdicts."""
        from tendermint_tpu.parallel import planner
        from tests.test_planner import _assert_verdict_matches, _ragged_window

        brk.get_device_breaker().quarantine("audit_mismatch:test")
        votes, powers, totals = _ragged_window([6], forged={(0, 3)}, tag=42)
        verdict = planner.verify_window(votes, powers, totals,
                                        use_device=True)
        _assert_verdict_matches(verdict, votes, powers, totals)
        assert not verdict.ok[0, 3]


class TestCommitWindowMsm:
    """commit_verify: msm dispatch under verify_commit_window's guard."""

    def _window(self, tag, forged=()):
        from tendermint_tpu.parallel import commit_verify as cv
        from tests.test_planner import _ragged_window

        # uniform heights: one scalar total_power must be reachable by
        # every height's clean tally (3·tally > 2·total)
        votes, powers, totals = _ragged_window(
            [8, 8], forged=forged, tag=tag
        )
        win = cv.pack_commit_window(votes, powers)
        # one scalar total_power serves every height in the window —
        # the largest per-height total keeps all-clean heights committed
        return cv, win, max(totals)

    def test_guarded_msm_matches_host(self, _msm_default):
        # clean window: the MSM accept path under the guard/audit wrap
        # (dirty-window localization under the guard is covered by
        # TestPlannerMsm — both seams share rlc_verify_batch)
        cv, win, total = self._window(50)
        ok_h, tally_h, com_h = cv._verify_window_host(win, total)
        ok_d, tally_d, com_d = cv.verify_commit_window(win, total)
        assert np.array_equal(ok_d, ok_h)
        assert np.array_equal(tally_d, tally_h)
        assert np.array_equal(com_d, com_h)
        assert ok_d[win.present].all() and com_d.all()
        # the clean dispatch must leave the breaker healthy
        assert brk.get_device_breaker().state == brk.CLOSED

    @pytest.mark.slow
    def test_guarded_msm_dirty_window_localizes(self, _msm_default):
        cv, win, total = self._window(52, forged={(1, 2)})
        ok_h, tally_h, com_h = cv._verify_window_host(win, total)
        ok_d, tally_d, com_d = cv.verify_commit_window(win, total)
        assert np.array_equal(ok_d, ok_h)
        assert np.array_equal(tally_d, tally_h)
        assert np.array_equal(com_d, com_h)
        assert not ok_d[1, 2]

    def test_quarantine_skips_msm_device(self, _msm_default, monkeypatch):
        cv, win, total = self._window(51)
        calls = {"n": 0}
        orig = cv._verify_window_device

        def _counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(cv, "_verify_window_device", _counting)
        brk.get_device_breaker().quarantine("audit_mismatch:test")
        ok, tally, com = cv.verify_commit_window(win, total)
        ok_h, tally_h, com_h = cv._verify_window_host(win, total)
        assert calls["n"] == 0, "quarantined breaker must not dispatch msm"
        assert np.array_equal(ok, ok_h)
        assert np.array_equal(tally, tally_h)
        assert np.array_equal(com, com_h)


class TestObservability:
    """The ed25519_path label rides the dispatch counter, the profiler
    ledger and the tm_monitor VERIFY column."""

    def test_dispatch_counter_label(self):
        from tendermint_tpu.libs.metrics import Registry, VerifyMetrics

        vm = VerifyMetrics(Registry())
        vm.record_dispatch("planner_msm", "ed25519", 16, 0.01,
                           carry_mode="lazy", ed25519_path="msm")
        vm.record_dispatch("xla", "ed25519", 16, 0.01, carry_mode="lazy")
        text = vm.registry.expose_text()
        assert 'ed25519_path="msm"' in text
        # unlabeled dispatches default to the ladder path
        assert 'ed25519_path="ladder"' in text

    def test_profiler_ledger_paths(self):
        from tendermint_tpu.libs.profile import Profiler

        prof = Profiler()
        with prof.window(100, 2):
            prof.record("planner_msm", carry_mode="lazy",
                        ed25519_path="msm", lanes_present=16,
                        lanes_dispatched=16, run_seconds=0.01)
            prof.record("planner_msm", carry_mode="lazy",
                        ed25519_path="msm", lanes_present=16,
                        lanes_dispatched=16, run_seconds=0.01)
        rows = prof.ledger()
        assert rows and rows[-1]["ed25519_paths"] == ["msm"]

    def test_monitor_verify_path_column(self):
        from tendermint_tpu.tools.tm_monitor import _fmt_verify, _verify_path

        key = ('tendermint_verify_path_total{backend="planner_msm",'
               'carry_mode="lazy",ed25519_path="msm"}')
        assert _verify_path({key: 3.0}) == "msm"
        assert _verify_path({}) == "-"
        other = key.replace('"msm"', '"ladder"')
        assert _verify_path({key: 3.0, other: 1.0}) == "mixed"
        assert _verify_path({key: 0.0, other: 1.0}) == "ladder"
        assert _fmt_verify(12, "msm") == "12ms/msm"
        assert _fmt_verify(12, "-") == "12ms"


class TestRpcVerifiedCommit:
    """/commit?verify=1 and /validators?verify=1 re-verify the stored
    commit through the planner LaneFeed burst path (rpc/core/env.py)."""

    def test_commit_and_validators_verified(self, live_node):
        from tendermint_tpu.rpc.client import HTTPClient

        from tests.consensus_harness import wait_for

        client = HTTPClient(
            f"tcp://127.0.0.1:{live_node.rpc_server.bound_port}"
        )
        assert wait_for(
            lambda: client.status()["sync_info"]["latest_block_height"] >= 2,
            timeout=30.0,
        )
        h = 2
        out = client.call("commit", height=h, verify=1)
        ver = out["verification"]
        assert ver["verified"] is True
        assert ver["sigs_ok"] is True
        assert ver["tally"] > 0
        assert ver["tally"] * 3 > ver["total_power"] * 2
        assert ver["batch_rows"] >= 1
        vout = client.call("validators", height=h, verify=1)
        assert vout["verification"]["verified"] is True
        # without the knob the legacy shape is untouched
        assert "verification" not in client.call("commit", height=h)


# the single-validator live node + RPC server used by the ?verify=1 tests
from tests.test_ws_metrics import live_node  # noqa: E402,F401
