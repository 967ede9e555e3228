"""Critical-path analyzer tests (libs/critpath.py).

Tiers:
  * pure-function tier: percentile, the verify-dispatch height join, and
    build_waterfall against hand-computed stamps — the reconciliation
    identity is asserted exactly, not within tolerance;
  * WAL tier: height-tagged append/fsync cost accounting on a real file
    WAL, including the keep-window eviction and the NilWAL no-op surface;
  * analyzer tier: CritPath over a real FlightRecorder with an injected
    clock — ring/limit/truncated contract, metrics observation, the
    never-raise guarantee, and deterministic critical-path flagging under
    seeded storms;
  * integration tier: a 4-validator in-proc net (flight_smoke._Net) where
    every committed height's phase sum must reconcile with its wall time,
    and trace_merge's nested waterfall slices must strict-validate as
    Chrome trace with commit-anchor skew correction.
"""

import importlib.util
import os
import random
import sys

import pytest

from tests.consensus_harness import wait_for

from tendermint_tpu.consensus.flight import FlightRecorder
from tendermint_tpu.consensus.messages import EndHeightMessage
from tendermint_tpu.consensus.wal import WAL, NilWAL
from tendermint_tpu.libs.critpath import (
    OVERLAY_PHASES,
    PHASES,
    TIMELINE_PHASES,
    CritPath,
    build_waterfall,
    percentile,
    verify_seconds_for_height,
)
from tendermint_tpu.libs.metrics import NodeMetrics

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)


def _load_script(name):
    if _SCRIPTS not in sys.path:  # scripts import siblings by module name
        sys.path.insert(0, _SCRIPTS)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# -- pure-function tier ------------------------------------------------------------


class TestPercentile:
    def test_empty_is_zero(self):
        assert percentile([], 50) == 0.0
        assert percentile([], 99) == 0.0

    def test_single_sample(self):
        assert percentile([0.7], 1) == 0.7
        assert percentile([0.7], 99) == 0.7

    def test_nearest_rank(self):
        xs = [float(i) for i in range(1, 101)]  # 1..100
        random.Random(3).shuffle(xs)
        assert percentile(xs, 50) == 50.0
        assert percentile(xs, 99) == 99.0
        assert percentile(xs, 100) == 100.0
        # q=0 still returns the smallest sample (rank floor is 1)
        assert percentile(xs, 0) == 1.0


class TestVerifyJoin:
    def test_exact_height_gets_full_cost(self):
        entries = [{"height_base": 5, "pack_seconds": 0.1,
                    "run_seconds": 0.2, "heights": 1}]
        assert verify_seconds_for_height(entries, 5) == pytest.approx(0.3)
        assert verify_seconds_for_height(entries, 4) == 0.0
        assert verify_seconds_for_height(entries, 6) == 0.0

    def test_window_amortizes_interior_heights(self):
        # window [3, 7): base gets full cost (documented imprecision),
        # interior heights get cost/span, heights outside get nothing
        entries = [{"height_base": 3, "run_seconds": 0.4, "heights": 4}]
        assert verify_seconds_for_height(entries, 3) == pytest.approx(0.4)
        for h in (4, 5, 6):
            assert verify_seconds_for_height(entries, h) == pytest.approx(0.1)
        assert verify_seconds_for_height(entries, 7) == 0.0
        assert verify_seconds_for_height(entries, 2) == 0.0

    def test_unannotated_entries_skipped(self):
        entries = [
            {"run_seconds": 99.0},  # no window annotation at all
            {"height_base": None, "run_seconds": 99.0},
            {"height_base": 5, "run_seconds": 0.25},  # heights key missing
        ]
        assert verify_seconds_for_height(entries, 5) == pytest.approx(0.25)

    def test_costs_sum_across_entries(self):
        entries = [
            {"height_base": 5, "run_seconds": 0.1},
            {"height_base": 5, "pack_seconds": 0.05},
            {"height_base": 4, "heights": 3, "run_seconds": 0.3},
        ]
        assert verify_seconds_for_height(entries, 5) == pytest.approx(
            0.1 + 0.05 + 0.1
        )


_T0 = 1_000_000_000_000  # ns


def _mk_rec(height=5, t0=_T0, prop=10, parts=30, polka=90, commit=190,
            persist=(190, 5), execspan=(195, 20)):
    """A flight record with millisecond offsets from t0 for each stamp."""
    ms = 1_000_000
    rec = {
        "height": height,
        "rounds": [{"round": 0, "t": t0}],
        "proposal": {"t": t0 + prop * ms, "round": 0, "peer": "p"},
        "block_parts": {"t": t0 + parts * ms},
        "prevote": {"first": None, "last": None, "count": 0, "by_peer": {}},
        "precommit": {"first": None, "last": None, "count": 0, "by_peer": {}},
        "polka": {"t": t0 + polka * ms, "round": 0},
        "commit": {"t": t0 + commit * ms, "round": 0, "hash": "AA"},
        "persist": None,
        "exec": None,
    }
    if persist is not None:
        rec["persist"] = {"t": t0 + persist[0] * ms, "dur_ns": persist[1] * ms}
    if execspan is not None:
        rec["exec"] = {"t": t0 + execspan[0] * ms,
                       "dur_ns": execspan[1] * ms}
    return rec


class TestBuildWaterfall:
    def test_exact_phase_cuts(self):
        wf = build_waterfall(_mk_rec())
        assert wf["height"] == 5
        assert wf["phases"]["propose_wait"] == pytest.approx(0.010)
        assert wf["phases"]["block_parts"] == pytest.approx(0.020)
        assert wf["phases"]["prevote_quorum"] == pytest.approx(0.060)
        assert wf["phases"]["precommit_quorum"] == pytest.approx(0.100)
        assert wf["phases"]["commit_persist"] == pytest.approx(0.005)
        assert wf["phases"]["abci_exec"] == pytest.approx(0.020)
        # t_end is the exec span's end: commit+25ms past round entry
        assert wf["wall_seconds"] == pytest.approx(0.215)
        assert wf["commit_seconds"] == pytest.approx(0.190)
        assert wf["critical_path"] == "precommit_quorum"

    def test_reconciliation_identity_is_exact(self):
        wf = build_waterfall(_mk_rec())
        timeline = sum(wf["phases"][p] for p in TIMELINE_PHASES)
        # identity by construction: residual below float dust, not just tol
        assert abs(wf["wall_seconds"] - (timeline + wf["other_seconds"])) \
            < 1e-12

    def test_overlay_excluded_from_reconciliation(self):
        wal_costs = {"append_seconds": 5.0, "fsync_seconds": 7.0,
                     "appends": 3, "fsyncs": 2}
        wf = build_waterfall(_mk_rec(), wal_costs, verify_seconds=11.0)
        assert wf["phases"]["wal_append"] == 5.0
        assert wf["phases"]["wal_fsync"] == 7.0
        assert wf["verify_dispatch_seconds"] == 11.0
        assert wf["wal_appends"] == 3 and wf["wal_fsyncs"] == 2
        # huge overlay costs must not disturb the timeline identity
        timeline = sum(wf["phases"][p] for p in TIMELINE_PHASES)
        assert abs(wf["wall_seconds"] - (timeline + wf["other_seconds"])) \
            < 1e-12
        assert wf["wall_seconds"] == pytest.approx(0.215)

    def test_critical_path_tie_breaks_to_earlier_phase(self):
        # wal_fsync exactly equals the dominant precommit_quorum: the
        # earlier phase in chain order must win, deterministically
        wal_costs = {"fsync_seconds": 0.100}
        wf = build_waterfall(_mk_rec(), wal_costs)
        assert wf["phases"]["wal_fsync"] == wf["phases"]["precommit_quorum"]
        assert wf["critical_path"] == "precommit_quorum"
        # strictly larger overlay does take the flag
        wf2 = build_waterfall(_mk_rec(), {"fsync_seconds": 0.200})
        assert wf2["critical_path"] == "wal_fsync"

    def test_none_without_commit_or_rounds(self):
        rec = _mk_rec()
        rec["commit"] = None
        assert build_waterfall(rec) is None
        rec2 = _mk_rec()
        rec2["rounds"] = []
        assert build_waterfall(rec2) is None

    def test_missing_milestones_collapse_to_zero_width(self):
        rec = _mk_rec(persist=None, execspan=None)
        rec["proposal"] = None
        rec["block_parts"] = None
        rec["polka"] = None
        wf = build_waterfall(rec)
        assert wf["phases"]["propose_wait"] == 0.0
        assert wf["phases"]["block_parts"] == 0.0
        assert wf["phases"]["prevote_quorum"] == 0.0
        assert wf["phases"]["precommit_quorum"] == pytest.approx(0.190)
        assert wf["phases"]["commit_persist"] == 0.0
        assert wf["phases"]["abci_exec"] == 0.0
        assert wf["other_seconds"] == pytest.approx(0.0, abs=1e-12)

    def test_inverted_stamps_clamp_no_negative_phase(self):
        # proposer stamps block parts BEFORE its own proposal acceptance;
        # skewed clocks can invert neighbors — phases must stay >= 0
        rec = _mk_rec(prop=30, parts=10)  # parts stamped before proposal
        wf = build_waterfall(rec)
        assert all(wf["phases"][p] >= 0.0 for p in PHASES)
        timeline = sum(wf["phases"][p] for p in TIMELINE_PHASES)
        assert abs(wf["wall_seconds"] - (timeline + wf["other_seconds"])) \
            < 1e-12

    def test_segments_cover_timeline(self):
        wf = build_waterfall(_mk_rec())
        by_phase = {s["phase"]: s for s in wf["segments"]}
        # the four interval segments tile [t_start, t_commit] contiguously
        chain = ["propose_wait", "block_parts", "prevote_quorum",
                 "precommit_quorum"]
        assert by_phase[chain[0]]["t0_ns"] == wf["t_start_ns"]
        for a, b in zip(chain, chain[1:]):
            assert by_phase[a]["t1_ns"] == by_phase[b]["t0_ns"]
        for name in ("commit_persist", "abci_exec"):
            seg = by_phase[name]
            assert wf["t_start_ns"] <= seg["t0_ns"] <= seg["t1_ns"] \
                <= wf["t_end_ns"]

    def test_phase_tuples_consistent(self):
        assert set(TIMELINE_PHASES) | {"wal_append", "wal_fsync"} == \
            set(PHASES)
        assert set(OVERLAY_PHASES) - {"verify_dispatch"} <= set(PHASES)
        wf = build_waterfall(_mk_rec())
        assert set(wf["phases"]) == set(PHASES)


# -- WAL height-cost tier ----------------------------------------------------------


class TestWALHeightCosts:
    def test_height_tagged_accounting(self, tmp_path):
        wal = WAL(str(tmp_path / "wal"))
        wal.start()
        try:
            wal.set_height(7)
            wal.write(EndHeightMessage(6))
            wal.write_sync(EndHeightMessage(7))  # write + fsync
            costs = wal.height_costs(7)
            assert costs is not None
            assert costs["appends"] == 2 and costs["fsyncs"] == 1
            assert costs["append_seconds"] > 0.0
            assert costs["fsync_seconds"] > 0.0
            # other heights untouched
            assert wal.height_costs(6) is None
            # pop consumes exactly once
            assert wal.pop_height_costs(7) == costs
            assert wal.pop_height_costs(7) is None
            assert wal.height_costs(7) is None
        finally:
            wal.stop()

    def test_keep_window_evicts_oldest(self, tmp_path, monkeypatch):
        monkeypatch.setattr(WAL, "HEIGHT_COST_KEEP", 4)
        wal = WAL(str(tmp_path / "wal"))
        wal.start()
        try:
            for h in range(1, 7):  # 6 heights through a keep-4 window
                wal.set_height(h)
                wal.write(EndHeightMessage(h))
            assert wal.height_costs(1) is None
            assert wal.height_costs(2) is None
            for h in range(3, 7):
                assert wal.height_costs(h)["appends"] == 1
        finally:
            wal.stop()

    def test_nil_wal_surface(self):
        nil = NilWAL()
        nil.set_height(5)  # must not raise
        assert nil.height_costs(5) is None
        assert nil.pop_height_costs(5) is None


# -- analyzer tier -----------------------------------------------------------------


class _Clock:
    """Injectable ns clock for FlightRecorder.now_ns."""

    def __init__(self, t0=_T0):
        self.t = t0

    def __call__(self):
        return self.t

    def tick(self, ms):
        self.t += ms * 1_000_000
        return self.t


class _StubWAL:
    def __init__(self, costs_by_height):
        self._costs = costs_by_height

    def pop_height_costs(self, height):
        return self._costs.pop(height, None)


def _drive_height(fr, clock, height, prop=10, parts=20, polka=60,
                  commit=100, persist=3, execspan=15):
    fr.on_new_round(height, 0)
    clock.tick(prop)
    fr.on_proposal(height, 0, "p")
    clock.tick(parts)
    fr.on_block_parts_complete(height)
    clock.tick(polka)
    fr.on_polka(height, 0)
    clock.tick(commit)
    fr.on_commit(height, 0, b"\xaa")
    t0 = clock.t
    fr.on_persist(height, t0, clock.tick(persist))
    t1 = clock.t
    fr.on_execute(height, t1, clock.tick(execspan))


class TestCritPath:
    def test_on_height_complete_fuses_all_streams(self):
        clock = _Clock()
        fr = FlightRecorder(node_id="n7", enabled=True)
        fr.now_ns = clock
        _drive_height(fr, clock, 1)
        wal = _StubWAL({1: {"append_seconds": 0.002, "fsync_seconds": 0.004,
                            "appends": 2, "fsyncs": 1}})
        entries = [{"height_base": 1, "run_seconds": 0.5, "heights": 1}]
        metrics = NodeMetrics()
        cp = CritPath(metrics=metrics, profiler_entries=lambda: entries)
        wf = cp.on_height_complete(1, fr, wal=wal)
        assert wf is not None
        assert cp.node_id == "n7"
        assert wf["phases"]["propose_wait"] == pytest.approx(0.010)
        assert wf["phases"]["precommit_quorum"] == pytest.approx(0.100)
        assert wf["phases"]["wal_fsync"] == pytest.approx(0.004)
        assert wf["verify_dispatch_seconds"] == pytest.approx(0.5)
        assert wf["critical_path"] == "precommit_quorum"
        assert len(cp) == 1
        # the WAL accumulator was consumed exactly once
        assert wal.pop_height_costs(1) is None
        # every phase landed one histogram observation
        text = metrics.registry.expose_text()
        for phase in PHASES:
            assert (
                f'tendermint_consensus_height_phase_seconds_count'
                f'{{phase="{phase}"}} 1'
            ) in text

    def test_disabled_flight_is_noop(self):
        fr = FlightRecorder(enabled=False)
        cp = CritPath(profiler_entries=list)
        assert cp.on_height_complete(1, fr) is None
        assert len(cp) == 0 and cp.analysis_errors == 0

    def test_missing_record_is_noop(self):
        fr = FlightRecorder(enabled=True)
        cp = CritPath(profiler_entries=list)
        assert cp.on_height_complete(42, fr) is None
        assert cp.analysis_errors == 0

    def test_internal_errors_counted_never_raised(self):
        clock = _Clock()
        fr = FlightRecorder(enabled=True)
        fr.now_ns = clock
        _drive_height(fr, clock, 1)

        def boom():
            raise RuntimeError("profiler exploded")

        cp = CritPath(profiler_entries=boom)
        assert cp.on_height_complete(1, fr) is None  # must not raise
        assert cp.analysis_errors == 1
        assert len(cp) == 0
        snap = cp.snapshot()
        assert snap["analysis_errors"] == 1

    def test_ring_and_snapshot_contract(self):
        clock = _Clock()
        fr = FlightRecorder(node_id="n0", enabled=True)
        fr.now_ns = clock
        cp = CritPath(capacity=3, sample_window=4, profiler_entries=list)
        for h in range(1, 6):
            _drive_height(fr, clock, h)
            assert cp.on_height_complete(h, fr) is not None
        assert len(cp) == 3
        assert [w["height"] for w in cp.records()] == [3, 4, 5]
        assert [w["height"] for w in cp.records(limit=2)] == [4, 5]
        assert cp.records(limit=0) == []
        snap = cp.snapshot()
        assert snap["total_records"] == 3
        assert snap["truncated"] is False
        assert snap["evicted"] == 2
        assert snap["node_id"] == "n0"
        cut = cp.snapshot(limit=1)
        assert cut["truncated"] is True
        assert len(cut["records"]) == 1 and cut["total_records"] == 3
        # sample_window=4 bounds the exact percentile rings below record
        # count, while the whole-run sketch keeps all 5 heights
        stats = snap["phase_stats"]
        assert stats["commit"]["window_n"] == 4
        assert all(stats[p]["window_n"] == 4 for p in PHASES)
        assert stats["commit"]["n"] == 5
        assert all(stats[p]["n"] == 5 for p in PHASES)
        assert stats["commit"]["p50_seconds"] > 0.0
        assert stats["commit"]["window_p50_seconds"] > 0.0
        assert snap["sketches"]["commit"]["count"] == 5

    def test_reset_and_resize(self):
        clock = _Clock()
        fr = FlightRecorder(enabled=True)
        fr.now_ns = clock
        cp = CritPath(capacity=8, profiler_entries=list)
        for h in (1, 2):
            _drive_height(fr, clock, h)
            cp.on_height_complete(h, fr)
        cp.reset()
        assert len(cp) == 0 and cp.capacity == 8
        cp.reset(capacity=2)
        assert cp.capacity == 2
        with pytest.raises(ValueError):
            cp.reset(capacity=0)

    def test_critical_path_deterministic_under_seeded_storm(self):
        """Two identical seeded storms (jittered phase durations across 40
        heights) must flag the identical critical-path sequence — flagging
        is a pure function of the stamps, with deterministic tie-breaks."""

        def run_storm(seed):
            rng = random.Random(seed)
            clock = _Clock()
            fr = FlightRecorder(node_id="storm", enabled=True)
            fr.now_ns = clock
            cp = CritPath(profiler_entries=list)
            flagged = []
            for h in range(1, 41):
                _drive_height(
                    fr, clock, h,
                    prop=rng.randrange(1, 50),
                    parts=rng.randrange(1, 50),
                    polka=rng.randrange(1, 200),
                    commit=rng.randrange(1, 200),
                    persist=rng.randrange(1, 20),
                    execspan=rng.randrange(1, 20),
                )
                wal = _StubWAL({h: {
                    "append_seconds": rng.random() * 0.05,
                    "fsync_seconds": rng.random() * 0.05,
                    "appends": 1, "fsyncs": 1,
                }})
                wf = cp.on_height_complete(h, fr, wal=wal)
                flagged.append((h, wf["critical_path"]))
            assert cp.analysis_errors == 0
            return flagged

        a, b = run_storm(12), run_storm(12)
        assert a == b
        assert all(phase in PHASES for _, phase in a)
        # the storm actually exercises multiple phases as dominant
        assert len({phase for _, phase in a}) >= 2
        # a different seed produces a different storm (sanity: the test
        # would be vacuous if every storm flagged one constant sequence)
        assert run_storm(99) != a


# -- trace_merge waterfall tier ----------------------------------------------------


def _mk_full_dump(node_id, heights, skew_ns=0, t0=_T0):
    """dump_flight payload with full milestone records (unlike test_flight's
    minimal _mk_dump) so every record yields a waterfall on merge."""
    records = []
    for n, h in enumerate(heights):
        base = t0 + n * 500_000_000 - skew_ns
        rec = _mk_rec(height=h, t0=base)
        rec["commit"]["hash"] = f"H{h:02d}"
        records.append(rec)
    return {"node_id": node_id, "enabled": True, "capacity": 512,
            "evicted": 0, "total_records": len(records),
            "truncated": False, "records": records}


class TestTraceMergeWaterfall:
    @pytest.fixture(scope="class")
    def tm(self):
        return _load_script("trace_merge")

    @pytest.fixture(scope="class")
    def fs(self):
        return _load_script("flight_smoke")

    def test_waterfall_slices_strict_validate(self, tm, fs):
        dumps = [_mk_full_dump("n0", [1, 2, 3])]
        merged = tm.merge(dumps, skews=[0])
        errors = fs.validate_chrome_trace(merged, 1, min_commits_per_node=3)
        assert errors == []

    def test_waterfall_slices_nest_in_parent(self, tm):
        merged = tm.merge([_mk_full_dump("n0", [1, 2])], skews=[0])
        evs = [e for e in merged["traceEvents"]
               if e.get("cat") == "critpath"]
        parents = {e["args"]["height"]: e for e in evs
                   if e["name"].startswith("waterfall ")}
        children = [e for e in evs
                    if not e["name"].startswith("waterfall ")]
        assert set(parents) == {1, 2}
        assert children, "no phase slices emitted"
        for ev in children:
            parent = parents[ev["args"]["height"]]
            assert ev["name"] in PHASES
            assert ev["tid"] == parent["tid"]
            assert ev["ts"] >= parent["ts"] - 1e-6
            assert ev["ts"] + ev["dur"] <= \
                parent["ts"] + parent["dur"] + 1e-6
        for h, parent in parents.items():
            assert parent["ph"] == "X" and parent["dur"] >= 0
            args = parent["args"]
            assert args["critical_path"] in PHASES
            assert args["commit_seconds"] == pytest.approx(0.190)

    def test_commit_anchor_skew_corrects_waterfalls(self, tm):
        """Two nodes, same commits, one clock 5ms behind: after anchor
        correction the same height's waterfall must end at the same merged
        timestamp on both tracks (the commit IS the anchor)."""
        d0 = _mk_full_dump("n0", [1, 2, 3])
        d1 = _mk_full_dump("n1", [1, 2, 3], skew_ns=5_000_000)
        skews = tm.compute_skews([d0, d1])
        assert skews == [0, 5_000_000]
        merged = tm.merge([d0, d1], skews=skews)
        ends = {}  # height -> {pid: parent end us}
        for e in merged["traceEvents"]:
            if e.get("cat") == "critpath" and \
                    e["name"].startswith("waterfall "):
                ends.setdefault(e["args"]["height"], {})[e["pid"]] = \
                    e["ts"] + e["dur"]
        for h, by_pid in ends.items():
            assert set(by_pid) == {0, 1}
            assert by_pid[0] == pytest.approx(by_pid[1], abs=1.0)  # <=1us


# -- 4-validator in-proc net tier --------------------------------------------------


class TestInProcNetReconciliation:
    TARGET_HEIGHT = 2
    TOL_S = 1e-6

    def test_phase_sums_reconcile_with_wall_time(self):
        fs = _load_script("flight_smoke")
        net = fs._Net()
        try:
            net.start()
            # wait for what the assertions below read, not for a height: a
            # node that is still in NewHeight when the complete proposal
            # arrives prevotes without entering the round (as the reference
            # does), so that height has no round stamp and no waterfall —
            # with staggered starts that is height 1 on three nodes of four
            ok = wait_for(
                lambda: all(cs.rs.height > self.TARGET_HEIGHT
                            and len(cs.critpath) >= self.TARGET_HEIGHT
                            for cs, _, _ in net.nodes),
                timeout=60.0,
            )
            seen = [(cs.rs.height, len(cs.critpath)) for cs, _, _ in net.nodes]
            assert ok, f"(height, waterfalls) per node never reached " \
                       f"{self.TARGET_HEIGHT + 1}, {self.TARGET_HEIGHT}: {seen}"
            snaps = [cs.critpath.snapshot() for cs, _, _ in net.nodes]
            dumps = [cs.flight.snapshot() for cs, _, _ in net.nodes]
        finally:
            net.stop()

        for snap in snaps:
            assert snap["analysis_errors"] == 0
            assert snap["total_records"] >= self.TARGET_HEIGHT
            assert snap["truncated"] is False
            for wf in snap["records"]:
                who = f"{snap['node_id']} h={wf['height']}"
                for phase in PHASES:
                    assert wf["phases"][phase] >= 0.0, who
                timeline = sum(wf["phases"][p] for p in TIMELINE_PHASES)
                assert timeline + wf["other_seconds"] == pytest.approx(
                    wf["wall_seconds"], abs=self.TOL_S
                ), who
                assert wf["other_seconds"] >= -self.TOL_S, who
                assert 0.0 <= wf["commit_seconds"] \
                    <= wf["wall_seconds"] + 1e-9, who
                assert wf["critical_path"] in PHASES, who

        # the merged trace over the REAL net strict-validates, waterfalls
        # included (tm was registered in sys.modules by flight_smoke)
        tm = sys.modules["trace_merge"]
        skews = tm.compute_skews(dumps)
        merged = tm.merge(dumps, skews=skews)
        errors = fs.validate_chrome_trace(
            merged, fs.N_VALS, min_commits_per_node=self.TARGET_HEIGHT
        )
        assert errors == []
        assert any(e.get("cat") == "critpath"
                   for e in merged["traceEvents"])
