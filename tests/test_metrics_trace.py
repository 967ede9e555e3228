"""Unit tests for the observability layer: labeled Histograms + exposition
escaping (libs/metrics.py), the ring-buffer span tracer (libs/trace.py), and
the strict text-format v0.0.4 linter (scripts/metrics_lint.py).
"""

import importlib.util
import json
import os
import sys
import threading

import pytest

from tendermint_tpu.libs import trace as trace_mod
from tendermint_tpu.libs.metrics import (
    Histogram,
    NodeMetrics,
    Registry,
    VerifyMetrics,
    _escape_label_value,
    _fmt_labels,
)
from tendermint_tpu.libs.trace import Tracer, _NOOP


def _load_script(name):
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", name + ".py",
    )
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_metrics_lint():
    return _load_script("metrics_lint")


# -- labeled Histogram --------------------------------------------------------------


class TestLabeledHistogram:
    def test_per_labelset_series(self):
        h = Histogram("h", buckets=(1.0, 10.0), label_names=("backend",))
        h.observe(0.5, ("host",))
        h.observe(5.0, ("host",))
        h.observe(100.0, ("pallas",))
        lines = h.expose()
        assert 'h_bucket{backend="host",le="1"} 1' in lines
        assert 'h_bucket{backend="host",le="10"} 2' in lines
        assert 'h_bucket{backend="host",le="+Inf"} 2' in lines
        assert 'h_count{backend="host"} 2' in lines
        assert 'h_sum{backend="host"} 5.5' in lines
        assert 'h_bucket{backend="pallas",le="10"} 0' in lines
        assert 'h_bucket{backend="pallas",le="+Inf"} 1' in lines

    def test_bound_labels_helper(self):
        h = Histogram("h", buckets=(1.0,), label_names=("b",))
        h.labels("xla").observe(0.2)
        assert 'h_bucket{b="xla",le="1"} 1' in h.expose()

    def test_unlabeled_exposes_zero_series(self):
        h = Histogram("h", buckets=(1.0,))
        lines = h.expose()
        assert 'h_bucket{le="1"} 0' in lines
        assert "h_count 0" in lines

    def test_buckets_cumulative(self):
        h = Histogram("h", buckets=(1.0, 2.0, 3.0))
        for v in (0.5, 1.5, 1.7, 2.5, 9.0):
            h.observe(v)
        lines = h.expose()
        assert 'h_bucket{le="1"} 1' in lines
        assert 'h_bucket{le="2"} 3' in lines
        assert 'h_bucket{le="3"} 4' in lines
        assert 'h_bucket{le="+Inf"} 5' in lines

    def test_registry_labeled_histogram(self):
        r = Registry()
        h = r.histogram("lat", "latency", buckets=(1.0,), label_names=("x",))
        h.observe(0.1, ("a",))
        text = r.expose_text()
        assert "# TYPE tendermint_lat histogram" in text
        assert 'tendermint_lat_bucket{x="a",le="1"} 1' in text


# -- exposition escaping ------------------------------------------------------------


class TestExpositionEscaping:
    def test_label_value_escapes(self):
        assert _escape_label_value('a\\b"c\nd') == 'a\\\\b\\"c\\nd'

    def test_fmt_labels_escapes(self):
        out = _fmt_labels(("p",), ('C:\\x\n"q"',))
        assert out == '{p="C:\\\\x\\n\\"q\\""}'

    def test_counter_label_roundtrip_single_line(self):
        r = Registry()
        c = r.counter("evil", "", label_names=("v",))
        c.add(1.0, ('multi\nline "quoted" \\slash',))
        text = r.expose_text()
        # the escaped series must stay on ONE line
        lines = [l for l in text.splitlines() if l.startswith("tendermint_evil")]
        assert len(lines) == 1
        assert '\\n' in lines[0] and '\\"' in lines[0] and "\\\\" in lines[0]

    def test_help_newline_escaped(self):
        r = Registry()
        r.counter("c", "first line\nsecond line")
        text = r.expose_text()
        help_line = next(l for l in text.splitlines() if l.startswith("# HELP"))
        assert help_line == "# HELP tendermint_c first line\\nsecond line"

    def test_linted_clean(self):
        lint = _load_metrics_lint()
        r = Registry()
        c = r.counter("c", 'help \\ with\nnewline', label_names=("l",))
        c.add(2.0, ('x\\y\n"z"',))
        h = r.histogram("h", "hh", buckets=(1.0,), label_names=("b",))
        h.observe(0.5, ("k\\v",))
        assert lint.lint_text(r.expose_text()) == []


# -- NodeMetrics.record_block guards ------------------------------------------------


class _FakeBlock:
    def __init__(self, height, n_missing=0):
        from types import SimpleNamespace

        self.height = height
        self.data = SimpleNamespace(txs=[b"t1", b"t2"])
        self.evidence = SimpleNamespace(evidence=[])
        self.last_commit = SimpleNamespace(
            precommits=[None] * n_missing + ["sig"] * (3 - n_missing)
        )

    def marshal(self):
        return b"x" * 100


class _FakeValset:
    size = 3

    def total_voting_power(self):
        return 30


class TestRecordBlockGuards:
    def test_height1_does_not_publish_missing(self):
        m = NodeMetrics()
        # height-1 blocks have no real LastCommit; a full "missing" valset
        # must not be published
        m.record_block(_FakeBlock(1, n_missing=3), _FakeValset())
        assert "tendermint_consensus_missing_validators 0" in (
            m.registry.expose_text()
        )

    def test_height2_publishes_missing(self):
        m = NodeMetrics()
        m.record_block(_FakeBlock(2, n_missing=2), _FakeValset())
        assert "tendermint_consensus_missing_validators 2" in (
            m.registry.expose_text()
        )

    def test_reset_block_timer_skips_interval(self):
        m = NodeMetrics()
        m.record_block(_FakeBlock(2), _FakeValset())
        m.reset_block_timer()
        m.record_block(_FakeBlock(3), _FakeValset())
        # only after TWO post-reset observations does an interval exist
        text = m.registry.expose_text()
        assert "tendermint_consensus_block_interval_seconds_count 0" in text
        m.record_block(_FakeBlock(4), _FakeValset())
        text = m.registry.expose_text()
        assert "tendermint_consensus_block_interval_seconds_count 1" in text


# -- VerifyMetrics ------------------------------------------------------------------


class TestVerifyMetrics:
    def test_record_dispatch(self):
        vm = VerifyMetrics()
        vm.record_dispatch("host", "ed25519", 64, 0.012, rejects=3, first=True)
        vm.record_dispatch("host", "ed25519", 128, 0.002)
        text = vm.registry.expose_text()
        assert 'tendermint_verify_calls_total{backend="host",algo="ed25519"} 2' in text
        assert 'tendermint_verify_sigs_total{backend="host",algo="ed25519"} 192' in text
        assert 'tendermint_verify_rejects_total{backend="host",algo="ed25519"} 3' in text
        assert 'tendermint_verify_compile_seconds_count{backend="host"} 1' in text
        assert 'tendermint_verify_dispatch_seconds_count{backend="host"} 2' in text
        assert "tendermint_verify_batch_size_count 2" in text

    def test_host_verifier_records(self):
        from tendermint_tpu.crypto import ed25519 as ed
        from tendermint_tpu.crypto.batch import HostBatchVerifier, SigItem
        from tendermint_tpu.libs.metrics import get_verify_metrics

        vm = get_verify_metrics()
        before = vm.calls._values.get(("host", "ed25519"), 0.0)
        priv = ed.gen_privkey(b"\x07" * 32)
        msg = b"metrics-e2e"
        item = SigItem(priv[32:], msg, ed.sign(priv, msg))
        ok = HostBatchVerifier().verify_ed25519([item])
        assert bool(ok[0])
        assert vm.calls._values.get(("host", "ed25519"), 0.0) == before + 1

    def test_node_metrics_attaches_verify_family(self):
        m = NodeMetrics()
        text = m.registry.expose_text()
        assert "tendermint_verify_batch_size_bucket" in text
        assert "# TYPE tendermint_verify_dispatch_seconds histogram" in text


# -- span tracer --------------------------------------------------------------------


class TestTracer:
    def test_disabled_is_noop_singleton(self):
        t = Tracer(capacity=4)
        assert t.span("x", a=1) is _NOOP
        t.instant("y")
        assert len(t) == 0

    def test_span_records(self):
        t = Tracer(capacity=8)
        t.enable()
        with t.span("fastsync.window", h0=5, n=3):
            pass
        t.instant("consensus.step", height=1)
        assert len(t) == 2
        events = t.export()
        by_name = {e["name"]: e for e in events if e.get("ph") != "M"}
        win = by_name["fastsync.window"]
        assert win["ph"] == "X" and win["dur"] >= 0
        assert win["cat"] == "fastsync"
        args = win["args"]
        assert {k: args[k] for k in ("h0", "n")} == {"h0": 5, "n": 3}
        assert isinstance(args["span_id"], int) and args["span_id"] > 0
        assert args["parent_id"] is None and args["root_id"] == args["span_id"]
        step = by_name["consensus.step"]
        assert step["ph"] == "i" and step["s"] == "t"

    def test_ring_wraparound_keeps_newest(self):
        t = Tracer(capacity=4)
        t.enable()
        for i in range(10):
            t.instant("e", i=i)
        assert len(t) == 4
        assert t.dropped() == 6
        events = [e for e in t.export() if e.get("ph") != "M"]
        assert [e["args"]["i"] for e in events] == [6, 7, 8, 9]

    def test_reset_clears(self):
        t = Tracer(capacity=4)
        t.enable()
        t.instant("e")
        t.reset()
        assert len(t) == 0 and t.dropped() == 0
        assert t.enabled  # reset does not flip the switch

    def test_reset_resizes(self):
        t = Tracer(capacity=4)
        t.enable(capacity=16)
        assert t.capacity == 16
        t.reset(capacity=2)
        assert t.capacity == 2
        for i in range(5):
            t.instant("e", i=i)
        assert len(t) == 2

    def test_thread_safety(self):
        t = Tracer(capacity=1 << 14)
        t.enable()
        N, THREADS = 500, 8

        def work(k):
            for i in range(N):
                with t.span("w", k=k, i=i):
                    pass

        threads = [threading.Thread(target=work, args=(k,)) for k in range(THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(t) == N * THREADS
        assert t.dropped() == 0
        events = [e for e in t.export() if e.get("ph") != "M"]
        assert len(events) == N * THREADS
        # every (k, i) recorded exactly once
        seen = {(e["args"]["k"], e["args"]["i"]) for e in events}
        assert len(seen) == N * THREADS

    def test_chrome_trace_shape_and_json(self):
        t = Tracer(capacity=8)
        t.enable()
        with t.span("rpc.dispatch", method="status"):
            pass
        doc = t.chrome_trace()
        # round-trips through JSON (what the dump_trace RPC returns)
        doc2 = json.loads(json.dumps(doc))
        assert doc2["displayTimeUnit"] == "ms"
        evs = doc2["traceEvents"]
        assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
        x = next(e for e in evs if e.get("ph") == "X")
        assert {"name", "cat", "pid", "tid", "ts", "dur"} <= set(x)

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)
        t = Tracer(capacity=1)
        with pytest.raises(ValueError):
            t.enable(capacity=-3)

    def test_module_level_disabled_by_default(self):
        # TM_TRACE unset in the test env: the module tracer must be the
        # zero-alloc path
        assert trace_mod.span("x") is _NOOP


def _spans(events):
    return [e for e in events if e.get("ph") == "X"]


def _by_name(events):
    out = {}
    for e in _spans(events):
        out.setdefault(e["name"], []).append(e)
    return out


@pytest.fixture
def guarded_host():
    """The guard as a node runs it (deadline, worker thread, 5 % audit)
    around the host verifier standing in for the device."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import breaker

    breaker.reset_device_guard()
    yield batch.GuardedBatchVerifier(batch.HostBatchVerifier())
    breaker.reset_device_guard()


class TestSpanIdentity:
    def test_parent_and_root_by_thread_nesting(self):
        t = Tracer(capacity=16)
        t.enable()
        with t.span("a"):
            with t.span("b"):
                with t.span("c"):
                    pass
            with t.span("d"):
                pass
        with t.span("e"):
            pass
        ev = {e["name"]: e["args"] for e in _spans(t.export())}
        ids = [ev[k]["span_id"] for k in "abcde"]
        assert len(set(ids)) == 5
        assert ev["a"]["parent_id"] is None
        assert ev["b"]["parent_id"] == ev["a"]["span_id"]
        assert ev["c"]["parent_id"] == ev["b"]["span_id"]
        assert ev["d"]["parent_id"] == ev["a"]["span_id"]  # b was popped
        assert {ev[k]["root_id"] for k in "abcd"} == {ev["a"]["span_id"]}
        assert ev["e"]["parent_id"] is None
        assert ev["e"]["root_id"] == ev["e"]["span_id"]
        assert t.current() is None

    def test_spans_on_another_thread_do_not_nest_without_a_handoff(self):
        t = Tracer(capacity=8)
        t.enable()
        def other():
            with t.span("other"):
                pass

        with t.span("main"):
            th = threading.Thread(target=other)
            th.start()
            th.join(10)
            assert not th.is_alive()
        ev = {e["name"]: e["args"] for e in _spans(t.export())}
        assert ev["other"]["parent_id"] is None
        assert ev["other"]["root_id"] != ev["main"]["root_id"]

    def test_adopted_handle_and_late_args(self):
        t = Tracer(capacity=8)
        t.enable()
        got = {}
        with t.span("root"):
            with t.span("mid") as mid:
                got["handle"] = t.current()
                assert got["handle"] is mid

        def other():
            # mid has exited; a thread may still take it as its parent
            t.adopt(got["handle"])
            with t.span("late", hit=False) as sp:
                sp.set(hit=True, n=3)
            got["left_open"] = t.current()

        th = threading.Thread(target=other)
        th.start()
        th.join(10)
        assert not th.is_alive()
        ev = {e["name"]: e["args"] for e in _spans(t.export())}
        assert ev["late"]["parent_id"] == ev["mid"]["span_id"]
        assert ev["late"]["root_id"] == ev["root"]["span_id"]
        assert ev["late"]["hit"] is True and ev["late"]["n"] == 3
        assert got["left_open"] is got["handle"]  # the adopted parent stays
        _NOOP.set(anything=1)  # the disabled handle takes late args too

    def test_handoff_through_supervised_call(self, tracing):
        from tendermint_tpu.libs import breaker

        seen = {}

        def work():
            seen["thread"] = threading.current_thread().name
            with trace_mod.span("worker.inner"):
                with trace_mod.span("worker.leaf"):
                    pass
            return 7

        with trace_mod.span("caller.outer"):
            with trace_mod.span("caller.call"):
                assert breaker.supervised_call(work, 10.0, name="t") == 7
        assert seen["thread"] == "supervised-t"  # it did cross a thread
        ev = {e["name"]: e for e in _spans(trace_mod.export())}
        inner, call = ev["worker.inner"], ev["caller.call"]
        assert inner["tid"] != call["tid"]
        assert inner["args"]["parent_id"] == call["args"]["span_id"]
        assert ev["worker.leaf"]["args"]["parent_id"] == inner["args"]["span_id"]
        root = ev["caller.outer"]["args"]["span_id"]
        assert {e["args"]["root_id"] for e in ev.values()} == {root}

    def test_disabled_allocates_no_span_and_touches_no_thread_local(
            self, no_tracing):
        t = Tracer(capacity=4)
        t._tls = no_tracing()
        assert t.span("x", a=1) is _NOOP
        assert t.current() is None
        t.adopt(None)
        assert len(t) == 0
        # and the module-level entry points the program calls
        assert trace_mod.span("x", a=1) is _NOOP
        assert trace_mod.current() is None
        trace_mod.adopt(None)

    def test_ids_survive_chrome_trace_json_and_trace_merge(self, tracing):
        # what the benchmark's program_spans() makes of the same export is
        # in tests/bench/test_bench_span_reducers.py
        with trace_mod.span("fastsync.window", h0=1, n=2, mode="sync"):
            with trace_mod.span("planner.pack", H=2):
                pass
        doc = json.loads(json.dumps(trace_mod.chrome_trace()))
        ev = {e["name"]: e for e in _spans(doc["traceEvents"])}
        win, pack = ev["fastsync.window"]["args"], ev["planner.pack"]["args"]
        assert win["mode"] == "sync" and pack["H"] == 2
        assert win["parent_id"] is None
        assert pack["parent_id"] == win["span_id"]
        assert pack["root_id"] == win["root_id"] == win["span_id"]
        # trace_merge retags a node's dump_trace payload; args ride along
        tm = _load_script("trace_merge")
        payload = dict(doc, anchor={"wall_ns": 5_000, "perf_ns": 1_000})
        merged = {e["name"]: e for e in tm._trace_events(payload, 3, 0)
                  if e.get("ph") == "X"}
        assert merged["planner.pack"]["pid"] == 3
        assert merged["planner.pack"]["args"] == pack


class TestSpanCpuTime:
    """``cpu_ms``: the CPU time of the span's own thread, beside its wall."""

    @staticmethod
    def _burn(ms):
        import time

        c0 = time.thread_time_ns()
        while time.thread_time_ns() - c0 < ms * 1e6:
            sum(range(2000))

    def _one(self, t, name):
        (ev,) = [e for e in _spans(t.export()) if e["name"] == name]
        return ev["dur"] / 1000.0, ev["args"]["cpu_ms"]

    def test_a_busy_span_reads_its_cpu_time_and_no_more_than_its_wall(self):
        t = Tracer(capacity=8)
        t.enable()
        with t.span("busy"):
            self._burn(20)
        dur_ms, cpu_ms = self._one(t, "busy")
        assert isinstance(cpu_ms, float) and 20.0 <= cpu_ms <= dur_ms + 1.0

    def test_a_span_that_only_waits_reads_next_to_nothing(self):
        t = Tracer(capacity=8)
        t.enable()
        with t.span("wait"):
            threading.Event().wait(0.03)
        dur_ms, cpu_ms = self._one(t, "wait")
        assert dur_ms >= 25.0 and 0.0 <= cpu_ms < 5.0

    def test_another_threads_work_does_not_count(self):
        t = Tracer(capacity=8)
        t.enable()

        def child():
            with t.span("child"):
                self._burn(30)

        with t.span("parent"):
            th = threading.Thread(target=child)
            th.start()
            th.join(30)
            assert not th.is_alive()
        _, child_cpu = self._one(t, "child")
        parent_dur, parent_cpu = self._one(t, "parent")
        assert child_cpu >= 30.0 and parent_dur >= 30.0
        assert parent_cpu < 10.0  # it stood in join() while the child ran

    def test_a_dropped_span_is_never_recorded_and_leaves_the_stack(self):
        t = Tracer(capacity=8)
        t.enable()
        with t.span("kept") as kept:
            with t.span("empty.look") as sp:
                sp.drop()
            with t.span("after"):
                pass
        ev = {e["name"]: e["args"] for e in _spans(t.export())}
        assert sorted(ev) == ["after", "kept"] and len(t) == 2
        assert ev["after"]["parent_id"] == kept.span_id
        _NOOP.drop()  # the disabled path takes the same call


class TestProgramSpans:
    """The spans the two served paths draw (names as PERF.md section 3)."""

    COMMIT_NAMES = ["commit.verify", "commit.collect", "verify.generic",
                    "guard.call", "guard.submit", "guard.audit",
                    "verify.dispatch", "commit.tally"]

    def test_one_verify_commit_is_one_tree(self, tracing, guarded_host):
        from tendermint_tpu.testutil.chain import build_commit

        valset, block_id, commit = build_commit(24, height=7)
        valset.verify_commit("bench-chain", block_id, 7, commit,
                             verifier=guarded_host)
        events = _spans(trace_mod.export())
        assert len(events) <= 16
        by = _by_name(events)
        for name in self.COMMIT_NAMES:
            assert len(by.get(name, [])) == 1, (name, sorted(by))
        arg = {n: by[n][0]["args"] for n in self.COMMIT_NAMES}
        root = arg["commit.verify"]
        assert root["parent_id"] is None and root["height"] == 7
        assert {e["args"]["root_id"] for e in events} == {root["span_id"]}
        parent = {n: arg[n]["parent_id"] for n in self.COMMIT_NAMES}
        sid = {n: arg[n]["span_id"] for n in self.COMMIT_NAMES}
        assert parent["commit.collect"] == sid["commit.verify"]
        assert parent["verify.generic"] == sid["commit.verify"]
        assert parent["commit.tally"] == sid["commit.verify"]
        assert parent["guard.call"] == sid["verify.generic"]
        # the sample is drawn (and shipped) before the dispatch, compared
        # after it: both under guard.call, on the caller's thread
        assert parent["guard.submit"] == parent["guard.audit"] == sid["guard.call"]
        submit, dispatch, audit = (
            by[n][0] for n in ("guard.submit", "verify.dispatch", "guard.audit"))
        assert submit["tid"] == audit["tid"] == by["guard.call"][0]["tid"]
        assert submit["ts"] + submit["dur"] <= dispatch["ts"]
        assert dispatch["ts"] + dispatch["dur"] <= audit["ts"]
        assert arg["guard.submit"]["sampled"] == 2
        # the dispatch ran on the guard's worker thread, under guard.call
        assert parent["verify.dispatch"] == sid["guard.call"]
        assert by["verify.dispatch"][0]["tid"] != by["guard.call"][0]["tid"]
        assert arg["guard.call"]["attempts"] == 1 and arg["guard.call"]["n"] == 24
        assert arg["guard.audit"] == dict(
            arg["guard.audit"], sampled=2, mismatches=0)  # ceil(5 % of 24)

    def test_one_block_window_is_spanned_per_window_not_per_block(
            self, tracing, guarded_host):
        from tendermint_tpu.blockchain.reactor import verify_block_window
        from tendermint_tpu.state.state_types import state_from_genesis
        from tendermint_tpu.testutil.chain import build_chain

        fx = build_chain(n_vals=4, n_heights=9, chain_id="span-chain")
        blocks = [fx.block_store.load_block(h) for h in range(1, 10)]
        trace_mod.reset()
        with trace_mod.span("fastsync.window", h0=1, n=8, mode="sync"):
            n_ok, err = verify_block_window(
                state_from_genesis(fx.genesis), blocks, verifier=guarded_host)
        assert (n_ok, err) == (8, None)
        events = _spans(trace_mod.export())
        by = _by_name(events)
        counts = {n: len(v) for n, v in by.items()}
        assert counts == {
            "fastsync.window": 1, "fastsync.precheck": 1, "planner.pack": 1,
            "planner.execute": 1,
            "verify.generic": 1, "guard.call": 1, "guard.submit": 1,
            "verify.dispatch": 1, "guard.audit": 1,
        }
        assert by["fastsync.precheck"][0]["args"]["n"] == 8
        # the host path's lane loop and tally are planner.execute's own time
        execute = by["planner.execute"][0]["args"]
        assert (execute["lanes"], execute["H"]) == (32, 8)
        assert execute["parent_id"] == by["fastsync.window"][0]["args"]["span_id"]
        assert by["verify.generic"][0]["args"]["parent_id"] == execute["span_id"]
        root = by["fastsync.window"][0]["args"]["span_id"]
        assert {e["args"]["root_id"] for e in events} == {root}

    @pytest.mark.parametrize("n_vals, where", [(8, "inline"), (200, "pool")])
    def test_audit_seconds_are_observed_with_tracing_off(
            self, guarded_host, verify_counters, n_vals, where):
        """One observation a dispatch, whether the oracle ran on the calling
        thread (1 sampled lane of 8) or in the workers (10 of 200)."""
        from tendermint_tpu.crypto import oracle_pool
        from tendermint_tpu.testutil.chain import build_commit

        if where == "pool" and not oracle_pool.pool_size():
            pytest.skip("too few cores here for oracle workers")
        family = "tendermint_verify_device_audit_seconds_count"
        lanes = ("tendermint_verify_audit_oracle_total", {"where": where})
        assert not trace_mod.enabled()
        before, lanes_before = verify_counters(family), verify_counters(*lanes)
        valset, block_id, commit = build_commit(n_vals, height=3)
        valset.verify_commit("bench-chain", block_id, 3, commit,
                             verifier=guarded_host)
        assert verify_counters(family) == before + 1
        assert verify_counters(*lanes) == lanes_before + max(1, n_vals // 20)

    def test_device_launch_spans_and_valset_cache_counters(
            self, tracing, monkeypatch, verify_counters):
        """ops/ed25519_pallas._verify_uniform's packed path with the jitted
        program stood in for (no chip here): prepare, then pack, launch and
        wait around one launch, and one lookup of each valset cache."""
        import numpy as np

        from tendermint_tpu.ops import ed25519_pallas as ep
        from tendermint_tpu.testutil.chain import build_commit

        def series(cache, result):
            return verify_counters("tendermint_verify_valset_cache_total",
                                   {"cache": cache, "result": result})

        launched = []

        def fake_call_jit(fn, *args, **static):
            launched.append(args[3].shape)
            return np.ones((args[3].shape[0],), dtype=bool)

        monkeypatch.setattr(ep, "call_jit", fake_call_jit)
        monkeypatch.setattr(ep, "_valset_cache", {})
        monkeypatch.setattr(ep, "_dev_valset_cache", {})
        valset, block_id, commit = build_commit(5, height=3)
        pubs = np.frombuffer(b"".join(
            v.pub_key.bytes() for v in valset.validators), np.uint8).reshape(5, 32)
        msgs = [pc.sign_bytes("bench-chain") for pc in commit.precommits]
        sigs = np.frombuffer(b"".join(
            pc.signature for pc in commit.precommits), np.uint8).reshape(5, 64)
        before = {k: series(*k) for k in (("host", "miss"), ("host", "hit"),
                                          ("device", "miss"), ("device", "hit"))}
        for _ in range(2):
            ok = ep.verify_batch(pubs, msgs, sigs)
        assert ok.tolist() == [True] * 5 and launched == [(128, 16)] * 2
        after = {k: series(*k) for k in before}
        assert {k: after[k] - before[k] for k in before} == {
            ("host", "miss"): 1, ("host", "hit"): 1,
            ("device", "miss"): 1, ("device", "hit"): 1}
        by = _by_name(trace_mod.export())
        # the first call's two misses are spanned, one a cache; the second
        # call hits both and draws none
        assert {n: len(v) for n, v in by.items()} == {
            "dispatch.prepare": 2, "dispatch.pack": 2, "dispatch.launch": 2,
            "dispatch.wait": 2, "valset.miss": 2}
        assert by["dispatch.pack"][0]["args"]["n"] == 5
        assert {e["args"]["lanes"] for n, v in by.items() for e in v
                if n not in ("dispatch.prepare", "valset.miss")} == {128}
        assert {(e["args"]["cache"], e["args"]["lanes"], e["args"]["bytes"])
                for e in by["valset.miss"]} == {
            ("host", 5, 5 * 32), ("device", 128, 128 * 192)}
        prepare, launch = by["dispatch.prepare"][0], by["dispatch.launch"][0]
        parents = {e["args"]["cache"]: e["args"]["parent_id"] for e in by["valset.miss"]}
        assert parents == {"host": prepare["args"]["span_id"],
                           "device": launch["args"]["span_id"]}

    def test_a_full_valset_cache_is_emptied_whole_and_counted(
            self, monkeypatch, verify_counters):
        import numpy as np

        from tendermint_tpu.ops import ed25519_pallas as ep

        def clears():
            return {c: verify_counters("tendermint_verify_valset_cache_clears_total",
                                       {"cache": c}) for c in ("host", "device")}

        monkeypatch.setattr(ep, "_valset_cache", {})
        monkeypatch.setattr(ep, "_dev_valset_cache", {})
        monkeypatch.setattr(ep, "_VALSET_CACHE_MAX", 3)
        monkeypatch.setattr(ep, "_DEV_VALSET_CACHE_MAX", 2)
        before = clears()
        rng = np.random.default_rng(33)
        for _ in range(5):  # five new key arrays, as five windows of a churn chain
            pubs = rng.integers(0, 256, size=(4, 32), dtype=np.uint8)
            neg_ax, ay, _valid = ep._decompress_valset(pubs)
            ep._upload_valset(pubs, neg_ax, ay, 8)
        grown = {c: v - before[c] for c, v in clears().items()}
        assert grown == {"host": 1.0, "device": 2.0}
        assert len(ep._valset_cache) == 2 and len(ep._dev_valset_cache) == 1

    def test_first_call_of_a_program_is_named(self, tracing):
        import jax
        import jax.numpy as jnp

        from tendermint_tpu.ops.dispatch import call_jit

        @jax.jit
        def add_one_for_the_trace_test(x):
            return x + 1

        for _ in range(2):
            call_jit(add_one_for_the_trace_test, jnp.zeros((128, 3)))
        first = _by_name(trace_mod.export())["jit.first_call"]
        assert len(first) == 1  # the second call is seen and draws nothing
        args = first[0]["args"]
        assert args["fn"] == "add_one_for_the_trace_test"
        assert args["lanes"] == 128 and args["seconds"] >= 0

    def test_tracing_off_builds_no_span_on_either_path(
            self, no_tracing, guarded_host):
        """What the driver's untraced runs execute: every new call site
        takes the shared no-op."""
        from tendermint_tpu.blockchain.reactor import verify_block_window
        from tendermint_tpu.state.state_types import state_from_genesis
        from tendermint_tpu.testutil.chain import build_chain, build_commit

        valset, block_id, commit = build_commit(8, height=3)
        valset.verify_commit("bench-chain", block_id, 3, commit,
                             verifier=guarded_host)
        fx = build_chain(n_vals=4, n_heights=4, chain_id="off-chain")
        blocks = [fx.block_store.load_block(h) for h in range(1, 5)]
        n_ok, err = verify_block_window(
            state_from_genesis(fx.genesis), blocks, verifier=guarded_host)
        assert (n_ok, err) == (3, None)
        assert len(trace_mod.get_tracer()) == 0

# -- strict linter ------------------------------------------------------------------


class TestMetricsLint:
    @pytest.fixture(scope="class")
    def lint(self):
        return _load_metrics_lint()

    def test_self_check_clean(self, lint):
        assert lint._self_check() == []

    def test_catches_unescaped_quote(self, lint):
        bad = 'm{l="a"b"} 1\n'
        assert lint.lint_text(bad)

    def test_catches_duplicate_series(self, lint):
        bad = 'm{l="a"} 1\nm{l="a"} 2\n'
        errs = lint.lint_text(bad)
        assert any("duplicate series" in e for e in errs)

    def test_catches_bad_escape(self, lint):
        bad = 'm{l="a\\t"} 1\n'
        errs = lint.lint_text(bad)
        assert any("illegal escape" in e for e in errs)

    def test_catches_noncumulative_histogram(self, lint):
        bad = (
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\nh_bucket{le="+Inf"} 5\n'
            "h_sum 1\nh_count 5\n"
        )
        errs = lint.lint_text(bad)
        assert any("not cumulative" in e for e in errs)

    def test_catches_missing_inf_bucket(self, lint):
        bad = "# TYPE h histogram\n" 'h_bucket{le="1"} 5\nh_sum 1\nh_count 5\n'
        errs = lint.lint_text(bad)
        assert any("+Inf" in e for e in errs)

    def test_catches_count_mismatch(self, lint):
        bad = (
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 7\n'
        )
        errs = lint.lint_text(bad)
        assert any("_count" in e for e in errs)

    def test_catches_bad_value(self, lint):
        assert lint.lint_text("m not_a_number\n")

    def test_accepts_live_registry(self, lint):
        m = NodeMetrics()
        m.record_block(_FakeBlock(2), _FakeValset())
        assert lint.lint_text(m.registry.expose_text()) == []


# -- hot-path families (per-peer traffic, timing histograms, mempool) ---------------


class TestHotPathFamilies:
    def test_new_families_expose_and_lint(self):
        lint = _load_metrics_lint()
        m = NodeMetrics()
        m.step_duration.observe(0.01, ("NEW_ROUND",))
        m.vote_arrival_latency.observe(0.002, ("prevote",))
        m.wal_append_seconds.observe(0.0001)
        m.wal_fsync_seconds.observe(0.003)
        m.mempool_tx_size_bytes.observe(512.0)
        m.mempool_failed_txs.add(1)
        m.mempool_recheck_times.add(3)
        text = m.registry.expose_text()
        for needle in (
            '# TYPE tendermint_consensus_step_duration_seconds histogram',
            'tendermint_consensus_step_duration_seconds_count{step="NEW_ROUND"} 1',
            'tendermint_consensus_vote_arrival_latency_seconds_count{type="prevote"} 1',
            "tendermint_consensus_wal_append_seconds_count 1",
            "tendermint_consensus_wal_fsync_seconds_count 1",
            "tendermint_mempool_tx_size_bytes_count 1",
            "tendermint_mempool_failed_txs 1",
            "tendermint_mempool_recheck_times 3",
        ):
            assert needle in text, needle
        assert lint.lint_text(text) == []

    def test_peer_traffic_labels_and_forget(self):
        m = NodeMetrics()
        m.record_peer_traffic("aa" * 20, 0x40, sent=100, received=50)
        m.record_peer_traffic("aa" * 20, 0x20, sent=7)
        m.set_peer_pending("aa" * 20, 42)
        text = m.registry.expose_text()
        assert (
            'tendermint_p2p_peer_send_bytes_total{peer_id="' + "aa" * 20
            + '",chID="0x40"} 100' in text
        )
        assert (
            'tendermint_p2p_peer_receive_bytes_total{peer_id="' + "aa" * 20
            + '",chID="0x40"} 50' in text
        )
        assert (
            'tendermint_p2p_peer_pending_send_bytes{peer_id="' + "aa" * 20
            + '"} 42' in text
        )
        m.forget_peer("aa" * 20)
        text = m.registry.expose_text()
        assert "aa" * 20 not in text
        # TYPE lines survive so the scrape stays lintable
        assert "# TYPE tendermint_p2p_peer_send_bytes_total counter" in text

    def test_peer_label_cardinality_cap(self):
        m = NodeMetrics()
        for i in range(NodeMetrics.MAX_PEER_LABELS + 8):
            m.record_peer_traffic(f"{i:040x}", 0x40, sent=1)
        labels = {k[0] for k in m.peer_send_bytes._values}
        assert "overflow" in labels
        # cap + the shared overflow label bounds the series count
        assert len(labels) == NodeMetrics.MAX_PEER_LABELS + 1
        # overflow absorbed the excess peers' bytes
        assert m.peer_send_bytes._values[("overflow", "0x40")] == 8.0
        # forgetting a capped peer frees a slot for a new id
        victim = f"{0:040x}"
        m.forget_peer(victim)
        m.record_peer_traffic("ff" * 20, 0x40, sent=1)
        assert ("ff" * 20, "0x40") in m.peer_send_bytes._values

    def test_remove_matching_counts_and_ignores_unknown_label(self):
        m = NodeMetrics()
        m.record_peer_traffic("ab" * 20, 0x40, sent=1)
        m.record_peer_traffic("ab" * 20, 0x20, sent=1)
        assert m.peer_send_bytes.remove_matching("peer_id", "ab" * 20) == 2
        assert m.peer_send_bytes.remove_matching("peer_id", "ab" * 20) == 0
        assert m.peer_send_bytes.remove_matching("nope", "x") == 0


# -- dispatch-cost profiler ---------------------------------------------------------


class TestProfiler:
    def _p(self, capacity=8):
        from tendermint_tpu.libs.profile import Profiler

        return Profiler(capacity=capacity)

    def test_window_annotation_and_nesting(self):
        p = self._p()
        with p.window(100, heights=4):
            p.record("pallas", lanes_present=3, lanes_dispatched=4)
            with p.window(200):
                p.record("host")
            p.record("pallas")
        p.record("host")  # un-annotated
        es = p.entries()
        assert [e["height_base"] for e in es] == [100, 200, 100, None]
        assert es[0]["heights"] == 4
        assert es[0]["occupancy"] == 0.75

    def test_ledger_folds_by_window(self):
        p = self._p()
        with p.window(50, heights=8):
            p.record("pallas", bucket=(4, 16), lanes_present=3,
                     lanes_dispatched=4, pack_seconds=0.1, run_seconds=0.2,
                     compiled=True, bytes_to_device=1000)
            p.record("pallas", bucket=(4, 16), lanes_present=4,
                     lanes_dispatched=4, pack_seconds=0.1, run_seconds=0.05,
                     bytes_to_device=1000)
        p.record("host", run_seconds=0.01)
        rows = p.ledger()
        assert len(rows) == 2
        win = rows[0]
        assert win["height_base"] == 50
        assert win["dispatches"] == 2
        assert win["buckets"] == [[4, 16]]
        assert win["compiles"] == 1
        assert win["compile_seconds"] == pytest.approx(0.2)
        assert win["pack_seconds"] == pytest.approx(0.2)
        assert win["run_seconds"] == pytest.approx(0.25)
        assert win["bytes_to_device"] == 2000
        assert win["occupancy"] == pytest.approx(7 / 8)
        assert rows[1]["height_base"] is None
        assert rows[1]["dispatches"] == 1

    def test_ring_eviction_and_reset(self):
        p = self._p(capacity=4)
        for i in range(10):
            p.record("host")
        assert len(p.entries()) == 4
        assert p.dropped == 6
        assert [e["seq"] for e in p.entries()] == [6, 7, 8, 9]
        p.reset(capacity=2)
        assert p.entries() == []
        assert p.dropped == 0
        p.record("host"), p.record("host"), p.record("host")
        assert len(p.entries()) == 2

    def test_verify_window_records_ledger(self):
        """Acceptance: a fast-sync window verify leaves a non-empty
        per-height ledger behind (the dump_profile RPC serves exactly
        this)."""
        from tendermint_tpu.blockchain.reactor import verify_block_window
        from tendermint_tpu.libs.profile import get_profiler
        from tendermint_tpu.state.state_types import state_from_genesis
        from tendermint_tpu.testutil.chain import build_chain

        fx = build_chain(n_vals=2, n_heights=6, chain_id="prof-ledger")
        blocks = [fx.block_store.load_block(h) for h in range(1, 7)]
        st = state_from_genesis(fx.genesis)
        p = get_profiler()
        p.reset()
        n_ok, err = verify_block_window(st, blocks)
        assert err is None and n_ok == 5
        rows = p.ledger()
        assert rows, "window verify must record dispatch-cost entries"
        row = rows[0]
        assert row["height_base"] == 1
        assert row["heights"] >= 1
        assert row["dispatches"] >= 1
        assert row["run_seconds"] > 0
        assert row["pack_seconds"] >= 0
        assert "occupancy" in row and "bytes_to_device" in row
        p.reset()


# -- bench regression gate ----------------------------------------------------------


def _load_bench_check():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "bench_check.py",
    )
    spec = importlib.util.spec_from_file_location("bench_check", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_check"] = mod  # @dataclass resolves via sys.modules
    spec.loader.exec_module(mod)
    return mod


class TestBenchCheck:
    @pytest.fixture(scope="class")
    def bc(self):
        return _load_bench_check()

    @staticmethod
    def _specs(bc, *raw, threshold=0.20):
        raw = raw or (bc.DEFAULT_METRIC,)
        return [bc.MetricSpec.parse(s, threshold) for s in raw]

    @staticmethod
    def _write(tmp, n, value):
        parsed = None if value is None else {"fastsync_blocks_per_s": value}
        with open(os.path.join(tmp, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"round": n, "parsed": parsed}, f)

    @staticmethod
    def _write_parsed(tmp, n, parsed):
        with open(os.path.join(tmp, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"round": n, "parsed": parsed}, f)

    def test_ok_within_threshold(self, bc, tmp_path):
        self._write(tmp_path, 1, 100.0)
        self._write(tmp_path, 2, 90.0)
        assert bc.check(str(tmp_path), self._specs(bc)) == 0

    def test_regression_fails(self, bc, tmp_path):
        self._write(tmp_path, 1, 100.0)
        self._write(tmp_path, 2, 70.0)
        assert bc.check(str(tmp_path), self._specs(bc)) == 1

    def test_null_parsed_rounds_skipped(self, bc, tmp_path):
        self._write(tmp_path, 1, 100.0)
        self._write(tmp_path, 2, None)  # timed out round
        self._write(tmp_path, 3, 95.0)
        # r02 is skipped; r03 vs r01 is within threshold
        assert bc.check(str(tmp_path), self._specs(bc)) == 0

    def test_newest_unparsed_skips(self, bc, tmp_path):
        self._write(tmp_path, 1, 100.0)
        self._write(tmp_path, 2, None)
        assert bc.check(str(tmp_path), self._specs(bc)) == 0

    def test_no_baseline_passes(self, bc, tmp_path):
        self._write(tmp_path, 1, 100.0)
        assert bc.check(str(tmp_path), self._specs(bc)) == 0
        assert bc.check(str(tmp_path / "empty-missing"), self._specs(bc)) == 0

    def test_spec_parse(self, bc):
        s = bc.MetricSpec.parse("foo", 0.20)
        assert (s.name, s.threshold, s.higher_is_better) == ("foo", 0.20, True)
        s = bc.MetricSpec.parse("foo:0.05", 0.20)
        assert (s.threshold, s.higher_is_better) == (0.05, True)
        s = bc.MetricSpec.parse("foo:0.3:lower", 0.20)
        assert (s.threshold, s.higher_is_better) == (0.3, False)
        s = bc.MetricSpec.parse("foo::lower", 0.20)  # keep default threshold
        assert (s.threshold, s.higher_is_better) == (0.20, False)
        for bad in ("", "foo:1.5", "foo:0", "foo:0.2:sideways", "a:b:c:d"):
            with pytest.raises(ValueError):
                bc.MetricSpec.parse(bad, 0.20)

    def test_lower_is_better_direction(self, bc, tmp_path):
        # latency-style metric: a rise is the regression, a drop is fine
        self._write_parsed(tmp_path, 1, {"verify_dispatch_ms": 10.0})
        self._write_parsed(tmp_path, 2, {"verify_dispatch_ms": 14.0})
        specs = self._specs(bc, "verify_dispatch_ms:0.20:lower")
        assert bc.check(str(tmp_path), specs) == 1
        self._write_parsed(tmp_path, 2, {"verify_dispatch_ms": 7.0})
        assert bc.check(str(tmp_path), specs) == 0

    def test_multi_metric_per_threshold(self, bc, tmp_path):
        self._write_parsed(
            tmp_path, 1, {"fastsync_blocks_per_s": 100.0, "lat_ms": 10.0}
        )
        self._write_parsed(
            tmp_path, 2, {"fastsync_blocks_per_s": 95.0, "lat_ms": 13.0}
        )
        # throughput fine at 20%, latency gated separately at 10% -> fails
        specs = self._specs(
            bc, "fastsync_blocks_per_s:0.20", "lat_ms:0.10:lower"
        )
        assert bc.check(str(tmp_path), specs) == 1
        # loosen the latency gate and the same ledger passes
        specs = self._specs(
            bc, "fastsync_blocks_per_s:0.20", "lat_ms:0.50:lower"
        )
        assert bc.check(str(tmp_path), specs) == 0

    def test_metric_missing_from_round_skips(self, bc, tmp_path):
        # a spec whose metric no round carries must not gate
        self._write_parsed(tmp_path, 1, {"fastsync_blocks_per_s": 100.0})
        self._write_parsed(tmp_path, 2, {"fastsync_blocks_per_s": 95.0})
        specs = self._specs(bc, "nonexistent_metric:0.01:lower")
        assert bc.check(str(tmp_path), specs) == 0

    def test_main_default_matches_legacy_gate(self, bc, tmp_path):
        self._write(tmp_path, 1, 100.0)
        self._write(tmp_path, 2, 70.0)
        assert bc.main(["--dir", str(tmp_path)]) == 1
        assert bc.main(["--dir", str(tmp_path), "--threshold", "0.45"]) == 0
        assert bc.main(["--metric", "bogus:2.0"]) == 2  # bad spec
