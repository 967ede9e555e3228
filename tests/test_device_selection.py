"""Device selection, loud fallbacks, compile grace, cache placement and the
chip smoke's verdict — the CPU-testable half of running on the chip
(the other half is `python chip_smoke.py` through the chip tool).

Selection is one in-process discovery: `jax.devices()` under JAX_PLATFORMS.
No child is ever spawned to look for a chip, nothing degrades in silence,
and an option this version removed is refused by name at selection.
"""

import logging
import os
import subprocess
import sys
import time

import pytest

from tendermint_tpu.crypto import batch as batch_mod
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto.batch import (
    GuardedBatchVerifier,
    HostBatchVerifier,
)
from tendermint_tpu.libs import breaker as brk
from tendermint_tpu.libs.metrics import get_verify_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_default(monkeypatch):
    monkeypatch.delenv("TM_BATCH_VERIFIER", raising=False)
    monkeypatch.delenv("TM_FE_BACKEND", raising=False)
    with batch_mod._lock:
        saved = (batch_mod._default, batch_mod._latched_reason)
        batch_mod._default = None
        batch_mod._latched_reason = None
    yield
    with batch_mod._lock:
        batch_mod._default, batch_mod._latched_reason = saved
    brk.reset_device_guard()


class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"
    id = 0


class TestSelection:
    def test_without_a_chip_no_subprocess_host_and_logged_no_tpu(
        self, fresh_default, monkeypatch, caplog
    ):
        def no_children(*a, **k):
            raise AssertionError("device selection spawned a subprocess")

        monkeypatch.setattr(subprocess, "Popen", no_children)
        monkeypatch.setattr(subprocess, "run", no_children)
        before = get_verify_metrics().host_fallback.snapshot().get(
            ("no_tpu",), 0.0)
        with caplog.at_level(logging.INFO, logger="tendermint_tpu.verify"):
            v = batch_mod.get_batch_verifier()
        assert isinstance(v, HostBatchVerifier)
        info = batch_mod.verifier_info()
        assert info["latched_reason"] == "no_tpu"
        assert info["backend"] == "host"
        assert "no_tpu" in info["description"]
        assert get_verify_metrics().host_fallback.snapshot()[
            ("no_tpu",)] == before + 1
        warned = [r for r in caplog.records if r.levelno == logging.WARNING]
        assert any("no TPU" in r.getMessage() and "no_tpu" in r.getMessage()
                   for r in warned)

    def test_forced_pallas_without_a_chip_raises(
        self, fresh_default, monkeypatch
    ):
        monkeypatch.setenv("TM_BATCH_VERIFIER", "pallas")
        with pytest.raises(RuntimeError, match="requires a TPU"):
            batch_mod.get_batch_verifier()
        assert batch_mod.verifier_info()["installed"] is False

    def test_unknown_forced_verifier_raises(self, fresh_default, monkeypatch):
        monkeypatch.setenv("TM_BATCH_VERIFIER", "gpu")
        with pytest.raises(ValueError, match="host, xla or pallas"):
            batch_mod.get_batch_verifier()

    def test_forced_host_says_why(self, fresh_default, monkeypatch):
        monkeypatch.setenv("TM_BATCH_VERIFIER", "host")
        v = batch_mod.get_batch_verifier()
        assert batch_mod.describe_verifier(v) == (
            "backend=host (TM_BATCH_VERIFIER=host)")

    def test_init_error_on_a_machine_with_a_chip_is_logged_at_error(
        self, fresh_default, monkeypatch, caplog
    ):
        class Broken:
            def __init__(self, backend=None):
                raise RuntimeError("libtpu: device busy")

        monkeypatch.setattr(batch_mod, "TPUBatchVerifier", Broken)
        with caplog.at_level(logging.INFO, logger="tendermint_tpu.verify"):
            v = batch_mod.get_batch_verifier()
        assert isinstance(v, HostBatchVerifier)
        assert batch_mod.verifier_info()["latched_reason"] == "device_init_error"
        errors = [r for r in caplog.records if r.levelno == logging.ERROR]
        assert errors and errors[0].exc_info is not None
        assert "libtpu: device busy" in str(errors[0].exc_info[1])


def _node(tmp_path, **verify):
    """A single-validator Node, built and not started; ``verify`` is set on
    its [verify] section as an operator's file would."""
    from tendermint_tpu.config.config import default_config, test_config
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    home = str(tmp_path / "n")
    cfg = default_config().set_root(home)
    cfg.base.proxy_app = "kvstore"
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = ""
    cfg.consensus = test_config().consensus
    cfg.consensus.wal_path = ""
    for key, value in verify.items():
        setattr(cfg.verify, key, value)
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    pv = FilePV.generate(os.path.join(home, "config", "pv.json"))
    doc = GenesisDoc(
        chain_id="removed-option-chain",
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.get_pub_key(), 10)],
    )
    doc.validate_and_complete()
    return Node(cfg, priv_validator=pv, genesis_doc=doc)


class TestRemovedFeBackendOption:
    """``fe_backend`` no longer exists; it can still arrive from a [verify]
    section or a launch line written for an earlier build."""

    @pytest.mark.parametrize("stale", ["vpu", " VPU ", "auto", ""])
    def test_a_stale_value_that_asks_for_the_vpu_is_ignored(
        self, stale, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("TM_FE_BACKEND", stale)
        node = _node(tmp_path, fe_backend=stale)
        assert node.verifier_description.startswith("backend=")

    def test_mxu_in_the_config_stops_the_node_with_the_reason(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.delenv("TM_FE_BACKEND", raising=False)
        with pytest.raises(batch_mod.VerifyConfigError) as e:
            _node(tmp_path, fe_backend="mxu")
        msg = str(e.value)
        assert "[verify] fe_backend='mxu'" in msg
        assert "removed in this version" in msg
        assert "VPU limb multiplier is the only one" in msg

    def test_env_mxu16_is_refused_at_selection_not_served_by_the_host(
        self, fresh_default, monkeypatch
    ):
        """On a machine with a chip the program stops; it neither builds a
        device verifier nor latches the host one."""
        from tendermint_tpu.ops import dispatch

        def no_verifier(*a, **k):
            raise AssertionError("a verifier was built for a refused option")

        monkeypatch.setattr(dispatch, "accelerator", lambda: _FakeTPU())
        monkeypatch.setattr(batch_mod, "TPUBatchVerifier", no_verifier)
        monkeypatch.setattr(batch_mod, "HostBatchVerifier", no_verifier)
        monkeypatch.setenv("TM_FE_BACKEND", "mxu16")
        before = dict(get_verify_metrics().host_fallback.snapshot())
        with pytest.raises(batch_mod.VerifyConfigError,
                           match="TM_FE_BACKEND='mxu16'.*removed"):
            batch_mod.get_batch_verifier()
        assert batch_mod.verifier_info()["installed"] is False
        assert dict(get_verify_metrics().host_fallback.snapshot()) == before


class _CompilingDevice:
    """First call compiles (slow, inside compile_grace); later calls are
    plain device time."""

    backend = "fake"

    def __init__(self, slow: float):
        self.slow = slow
        self.calls = 0
        self._host = HostBatchVerifier()

    def verify_ed25519(self, items):
        self.calls += 1
        if self.calls == 1:
            with brk.compile_grace():
                time.sleep(self.slow)
        else:
            time.sleep(self.slow)
        return self._host.verify_ed25519(items)


def _items(n=4):
    out = []
    for i in range(n):
        priv = ed.gen_privkey(bytes([i + 1]) * 32)
        msg = b"grace-%d" % i
        out.append(batch_mod.SigItem(priv[32:], msg, ed.sign(priv, msg)))
    return out


class TestCompileGrace:
    def teardown_method(self):
        brk.reset_device_guard()

    def test_compiling_first_call_survives_the_deadline_second_does_not(
        self, caplog
    ):
        brk.configure_device_guard(breaker_threshold=10)
        dev = _CompilingDevice(slow=0.5)
        g = GuardedBatchVerifier(dev, deadline=0.15, retries=0, audit_rate=0)
        m = get_verify_metrics()
        before = m.device_fallback.snapshot().get(("timeout",), 0.0)
        items = _items()

        assert g.verify_ed25519(items).all()  # compiled past the deadline
        assert m.device_fallback.snapshot().get(("timeout",), 0.0) == before
        assert g.breaker.snapshot()["failures_total"] == 0

        with caplog.at_level(logging.WARNING, logger="tendermint_tpu.verify"):
            assert g.verify_ed25519(items).all()  # host completed it
        assert m.device_fallback.snapshot()[("timeout",)] == before + 1
        assert any(
            "completed on the host" in r.getMessage()
            and "reason=timeout" in r.getMessage()
            for r in caplog.records
        )

    def test_grace_stops_the_clock_only_while_compiling(self):
        def work():
            with brk.compile_grace():
                time.sleep(0.3)
            time.sleep(0.4)  # device time: on the clock

        with pytest.raises(brk.DispatchTimeout):
            brk.supervised_call(work, deadline=0.2)
        # and the same compile followed by quick device work passes
        def quick():
            with brk.compile_grace():
                time.sleep(0.3)
            return "ok"

        assert brk.supervised_call(quick, deadline=0.2) == "ok"

    def test_grace_outside_a_supervised_call_is_a_no_op(self):
        with brk.compile_grace():
            pass

    def test_call_jit_graces_the_first_call_per_signature_only(
        self, monkeypatch
    ):
        import jax
        import jax.numpy as jnp

        from tendermint_tpu.ops import dispatch

        entered = []

        class Spy:
            def __enter__(self):
                entered.append(1)

            def __exit__(self, *a):
                return False

        monkeypatch.setattr(dispatch, "compile_grace", Spy)
        fn = jax.jit(lambda x, k=1: x * k, static_argnames=("k",))
        a = jnp.arange(8, dtype=jnp.uint32)
        assert int(dispatch.call_jit(fn, a, k=3)[2]) == 6
        assert int(dispatch.call_jit(fn, a, k=3)[2]) == 6
        assert len(entered) == 1
        dispatch.call_jit(fn, a, k=4)  # new static value: new program
        dispatch.call_jit(fn, jnp.arange(16, dtype=jnp.uint32), k=3)  # shape
        assert len(entered) == 3


class TestEveryHostCompletionIsLogged:
    def teardown_method(self):
        brk.reset_device_guard()

    def test_guard_error_fallback_warns(self, caplog):
        class Failing:
            backend = "fake"

            def verify_ed25519(self, items):
                raise RuntimeError("device fault")

        brk.configure_device_guard(breaker_threshold=10)
        g = GuardedBatchVerifier(Failing(), retries=1, audit_rate=0)
        with caplog.at_level(logging.WARNING, logger="tendermint_tpu.verify"):
            assert g.verify_ed25519(_items()).all()
        assert [r for r in caplog.records if "reason=error" in r.getMessage()]

    def test_planner_fallback_warns(self, caplog):
        from tendermint_tpu.parallel import planner

        priv = ed.gen_privkey(b"\x07" * 32)
        votes = [[(priv[32:], b"m", ed.sign(priv, b"m"))]]

        def boom(plan, mesh):
            raise RuntimeError("device fault")

        brk.configure_device_guard(breaker_threshold=10)
        planner.set_device_executor(boom)
        try:
            with caplog.at_level(
                logging.WARNING, logger="tendermint_tpu.verify"
            ):
                v = planner.verify_window(
                    votes, [[1]], [1], use_device=True,
                    verifier=HostBatchVerifier(),
                )
        finally:
            planner.set_device_executor(None)
        assert bool(v.committed[0])
        assert [
            r for r in caplog.records
            if "planner dispatch completed on the host" in r.getMessage()
        ]


class TestCompileCachePlacement:
    CODE = (
        "import os, tendermint_tpu, jax; "
        "print(os.environ['JAX_COMPILATION_CACHE_DIR']); "
        "print(jax.config.jax_compilation_cache_dir)"
    )

    def _run(self, env_overrides):
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(env_overrides)
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run(
            [sys.executable, "-c", self.CODE], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert res.returncode == 0, res.stderr
        return res.stdout.split()

    def test_environment_variable_wins(self, tmp_path):
        want = str(tmp_path / "elsewhere")
        assert self._run({"JAX_COMPILATION_CACHE_DIR": want}) == [want, want]

    def test_unset_means_the_fixed_checkout_path(self):
        want = os.path.join(REPO, ".jax_cache")
        assert self._run({}) == [want, want]

    def test_one_place_in_code_sets_it(self):
        """`git grep` for the setter finds the package import and nothing
        else (readers of the value do not count)."""
        import glob

        paths = glob.glob(os.path.join(REPO, "*.py"))
        for top in ("tendermint_tpu", "scripts", "tests"):
            paths += glob.glob(
                os.path.join(REPO, top, "**", "*.py"), recursive=True)
        hits = []
        for path in sorted(paths):
            if os.path.samefile(path, __file__):
                continue
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            if ('environ["JAX_COMPILATION_CACHE_DIR"]' in text
                    or '"jax_compilation_cache_dir",' in text
                    or 'setdefault(\n    "JAX_COMPILATION_CACHE_DIR"' in text):
                hits.append(os.path.relpath(path, REPO))
        assert hits == [os.path.join("tendermint_tpu", "__init__.py")]


class TestChipSmoke:
    def _report(self, **over):
        r = {
            "stage": "commit_verify", "ok": True, "error": None,
            "platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1, "backend": "pallas",
            "dispatches": {"pallas/ed25519": 2.0, "host/ed25519": 1.0},
            "device_fallback_total": {}, "host_fallback_total": {},
            "device_audit_total": {"ok": 512.0}, "breaker_state": "closed",
        }
        r.update(over)
        return r

    def test_chipless_box_exits_nonzero_in_seconds_and_runs_no_stage(self):
        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=120,
        )
        assert time.monotonic() - t0 < 60
        assert res.returncode != 0
        assert "no TPU" in res.stderr
        assert "STAGE" not in res.stdout and '"ok"' not in res.stdout

    def test_aggregator_passes_clean_reports(self):
        import chip_smoke

        reports = [self._report(stage=s) for s in
                   chip_smoke.KERNEL_STAGES + ("node",)]
        assert chip_smoke.aggregate(reports) == 0

    @pytest.mark.parametrize("over", [
        {"device_fallback_total": {"timeout": 1.0}},
        {"host_fallback_total": {"no_tpu": 1.0}},
        {"device_audit_total": {"ok": 3.0, "mismatch": 1.0}},
        {"breaker_state": "open"},
        {"backend": "host"},
        {"platform": "cpu"},
        {"dispatches": {"host/ed25519": 9.0}},
        {"ok": False, "error": "AssertionError: device != host"},
    ])
    def test_aggregator_fails_on_any_lost_chip_signal(self, over, capsys):
        import chip_smoke

        reports = [self._report(stage=s) for s in
                   chip_smoke.KERNEL_STAGES + ("node",)]
        reports[2] = self._report(stage=reports[2]["stage"], **over)
        assert chip_smoke.aggregate(reports) != 0
        assert "FAIL" in capsys.readouterr().err

    def test_aggregator_fails_when_a_stage_never_reported(self):
        import chip_smoke

        reports = [self._report(stage=s) for s in chip_smoke.KERNEL_STAGES]
        assert chip_smoke.aggregate(reports) != 0


class TestNativeBuild:
    def test_build_all_builds_the_three_extensions(self):
        from tendermint_tpu.encoding import native

        built = native.build_all()
        assert len(built) == 3 and all(os.path.exists(p) for p in built)

    def test_build_all_raises_when_cc_refuses(self, monkeypatch):
        from tendermint_tpu.encoding import native

        monkeypatch.setenv("CC", "false")
        with pytest.raises(RuntimeError, match="cc refused"):
            native.build_all()


class TestBenchScriptsDoNotDegrade:
    def test_bench_verifier_refuses_a_host_substitute(
        self, fresh_default, monkeypatch
    ):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import _bench_metrics as bm
        finally:
            sys.path.pop(0)
        monkeypatch.setattr(
            batch_mod, "_try_device_default",
            lambda: (HostBatchVerifier(), "no_tpu"),
        )
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(SystemExit, match="no device verifier"):
            bm.bench_verifier()
        # the explicit CPU switch is honoured and named
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        v, info = bm.bench_verifier()
        assert isinstance(v, HostBatchVerifier)
        assert info["latched_reason"] == "no_tpu"
