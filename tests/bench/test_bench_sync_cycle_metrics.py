"""PR 41's per-layer metrics (the fast-sync cycle, the pool's intake, the
off-CPU column, the two ``valset.miss`` apart) through their reducers, on a
dump made by hand here, and their entries' place in BENCHMARK.json.

The dump, times in ms after the window's start.  Two cycles on the loop's
thread (2), one window verified in line and one harvested:

  cycle A  10..110  result=window n=4
    peek 10.5..11 | window(sync) 11..51 | speculate 52..54 | apply 55..95
    (cpu 30) | release 95.5..97 | tick 99..109.5
    own: 100 - (0.5 + 40 + 2 + 40 + 1.5 + 10.5) = 5.5
    inside the window: precheck 11..21 (cpu 8), planner.pack 21..27 (cpu 5),
    planner.execute 27..50 round verify.generic 28..49 (own 2), guard.submit
    29..31 (cpu 0.5), verify.dispatch 31..48 on thread 5 with
    dispatch.prepare 31..36 (cpu 4; valset.miss host 32..35),
    dispatch.pack 36..38 (cpu 2), dispatch.launch 38..41 (valset.miss
    device 39..40)
  cycle B  120..200  result=harvest n=6
    take 120.2..140.2 round harvest 120.5..140 (own 0.5) | speculate
    141..144 | apply 145..185 (cpu 38) | release 185.2..186.2 | tick 188..199
    own: 80 - (20 + 3 + 40 + 1 + 11) = 5

A speculative window on thread 4 (a root): precheck 60..70 (cpu 4),
planner.pack 70..75 (cpu 2), planner.execute 75..89 round verify.generic
76..88 (own 2).  ``pool.schedule`` on thread 3: 15..35 and 130..140.  One
cycle before the window, which nothing reads.  Ten blocks taken in, 3 ms.
"""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000
T0 = 1_000 * MS  # the window opens at 1 s on the spans' clock


def _sp(name, t0, t1, tid, sid, parent, root, **args):
    return {"name": name, "t0": T0 + int(t0 * MS), "t1": T0 + int(t1 * MS), "tid": tid,
            "args": dict(args, span_id=sid, parent_id=parent, root_id=root)}


SPANS = [
    {"name": "bench.window", "t0": T0, "t1": T0 + 1_000 * MS, "tid": 1, "args": {}},
    _sp("fastsync.cycle", -60, -10, 2, 90, None, 90, result="window", n=9, cpu_ms=1.0),
    _sp("fastsync.tick", -21, -10.5, 2, 91, 90, 90, after="window", cpu_ms=0.1),
    # cycle A
    _sp("fastsync.cycle", 10, 110, 2, 1, None, 1, result="window", n=4, cpu_ms=60.0),
    _sp("fastsync.peek", 10.5, 11, 2, 2, 1, 1, n=4, cpu_ms=0.5),
    _sp("fastsync.window", 11, 51, 2, 3, 1, 1, mode="sync", h0=1, n=4, cpu_ms=30.0),
    _sp("fastsync.precheck", 11, 21, 2, 4, 3, 1, n=4, cut="none", cpu_ms=8.0),
    _sp("planner.pack", 21, 27, 2, 5, 3, 1, H=4, cpu_ms=5.0),
    _sp("planner.execute", 27, 50, 2, 6, 3, 1, lanes=256, H=4, cpu_ms=4.0),
    _sp("verify.generic", 28, 49, 2, 7, 6, 1, n=256, cpu_ms=2.0),
    _sp("guard.submit", 29, 31, 2, 8, 7, 1, sampled=13, cpu_ms=0.5),
    _sp("verify.dispatch", 31, 48, 5, 9, 7, 1, cpu_ms=9.0),
    _sp("dispatch.prepare", 31, 36, 5, 10, 9, 1, cpu_ms=4.0),
    _sp("valset.miss", 32, 35, 5, 11, 10, 1, cache="host", lanes=256, cpu_ms=3.0),
    _sp("dispatch.pack", 36, 38, 5, 12, 9, 1, lanes=256, cpu_ms=2.0),
    _sp("dispatch.launch", 38, 41, 5, 13, 9, 1, cpu_ms=1.0),
    _sp("valset.miss", 39, 40, 5, 14, 13, 1, cache="device", lanes=256, cpu_ms=0.2),
    _sp("fastsync.speculate", 52, 54, 2, 15, 1, 1, started=1, cpu_ms=0.3),
    _sp("fastsync.apply", 55, 95, 2, 16, 1, 1, h0=1, n=4, cpu_ms=30.0),
    _sp("fastsync.release", 95.5, 97, 2, 17, 1, 1, n=4, cpu_ms=1.5),
    _sp("fastsync.tick", 99, 109.5, 2, 18, 1, 1, after="window", cpu_ms=0.1),
    # cycle B
    _sp("fastsync.cycle", 120, 200, 2, 20, None, 20, result="harvest", n=6, cpu_ms=45.0),
    _sp("fastsync.take", 120.2, 140.2, 2, 21, 20, 20, slots=1, cpu_ms=0.4),
    _sp("fastsync.harvest", 120.5, 140, 2, 22, 21, 20, h0=5, hit=True, cpu_ms=0.1),
    _sp("fastsync.speculate", 141, 144, 2, 23, 20, 20, started=1, cpu_ms=0.3),
    _sp("fastsync.apply", 145, 185, 2, 24, 20, 20, h0=5, n=6, cpu_ms=38.0),
    _sp("fastsync.release", 185.2, 186.2, 2, 25, 20, 20, n=6, cpu_ms=1.0),
    _sp("fastsync.tick", 188, 199, 2, 26, 20, 20, after="harvest", cpu_ms=0.1),
    # the speculative window, a root on its own thread
    _sp("fastsync.window", 60, 90, 4, 30, None, 30, mode="speculative", h0=5, n=6, cpu_ms=9.0),
    _sp("fastsync.precheck", 60, 70, 4, 31, 30, 30, n=6, cut="none", cpu_ms=4.0),
    _sp("planner.pack", 70, 75, 4, 32, 30, 30, H=6, cpu_ms=2.0),
    _sp("planner.execute", 75, 89, 4, 33, 30, 30, lanes=384, H=6, cpu_ms=1.0),
    _sp("verify.generic", 76, 88, 4, 34, 33, 30, n=384, cpu_ms=1.0),
    # the scheduler's passes that sent something
    _sp("pool.schedule", 15, 35, 3, 40, None, 40, sends=6, errors=0, cpu_ms=6.0),
    _sp("pool.schedule", 130, 140, 3, 41, None, 41, sends=4, errors=0, cpu_ms=3.0),
]
FAMILY = "tendermint_verify_block_intake_seconds"
COUNTERS = {FAMILY + "_sum": 0.003, FAMILY + "_count": 10.0}

WANT = {
    "cycle_ms_per_window.sync": (100 + 80) / 2,
    "cycle_unnamed_ms.sync": (5.5 + 5) / 2,
    "tick_wait_ms_per_window.sync": (10.5 + 11) / 2,
    "take_ms_per_window.sync": 0.5,
    "peek_ms_per_window.sync": 0.5,
    "speculate_start_ms_per_window.sync": (2 + 3) / 2,
    "release_ms_per_window.sync": (1.5 + 1) / 2,
    "schedule_ms_per_window.sync": (20 + 10) / 2,
    "intake_ms_per_block.sync": 0.3,
    "planner_execute_self_ms.sync": (2 + 2) / 2,
    "apply_offcpu_ms_per_block.sync": ((40 - 30) + (40 - 38)) / (4 + 6),
    "precheck_offcpu_ms_per_window.sync": ((10 - 8) + (10 - 4)) / 2,
    "pack_offcpu_ms_per_window.sync": ((6 - 5) + (5 - 2)) / 2,
    "submit_offcpu_ms_per_window.sync": 2 - 0.5,
    "dispatch_prepare_offcpu_ms.sync": 5 - 4,
    "dispatch_pack_offcpu_ms.sync": 2 - 2,
}
SPLIT = {  # (host, device, the entry they split, its cell)
    "churn": ("valset_miss_host_ms_per_window.churn", "valset_miss_device_ms_per_window.churn",
              "valset_miss_ms_per_window.churn", "sync64-churn"),
    "msig": ("valset_miss_host_ms_per_call.msig", "valset_miss_device_ms_per_call.msig",
             "valset_miss_ms_per_call.msig", "msig1k-stream"),
}
# what BENCHMARK.json held for these cells before this PR, in its order
EMPTY_22 = [
    "heights_per_dispatch.sync", "speculative_window_share.sync", "pack_ms_per_window.sync",
    "window_verify_ms.sync", "dispatch_ms.sync", "kernel_ms_per_dispatch.sync",
    "apply_ms_per_block.sync", "device_idle_share.sync", "compiles_in_window.sync",
    "precheck_ms_per_window.sync", "harvest_wait_ms_per_window.sync", "ticks_per_window.sync",
    "empty_tick_ratio.sync", "discarded_windows.sync", "audit_ms_per_window.sync",
    "dispatch_pack_ms.sync", "dispatch_wait_ms.sync", "result_wake_ms.sync",
    "valset_cache_miss_ratio.sync", "dispatch_prepare_ms.sync", "audit_pool_share.sync",
    "uniform_pack_share.sync"]
CHURN_5 = ["cut_window_share.churn", "valset_changes_per_sync.churn",
           "discard_ms_per_window.churn", "valset_miss_ms_per_window.churn",
           "valset_cache_clears_per_sync.churn"]
MSIG_7 = ["flatten_ms.msig", "group_reduce_ms.msig", "lanes_per_validator.msig",
          "host_decided_validators.msig", "valset_miss_ms_per_call.msig",
          "valset_cache_miss_ratio.msig", "call_p90_ms.msig"]


def _load(name):
    with open(os.path.join(ROOT, "benchmark", "testdata", name)) as f:
        return json.load(f)


def _data(spans, counters, cell="sync64-empty"):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell(cell), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=spans, counters=counters)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_on_the_hand_made_dump(name):
    d = _data(SPANS, COUNTERS)
    assert d.cell.reduce(name, d) == pytest.approx(WANT[name], abs=1e-9)


@pytest.mark.parametrize("dump", ["small_trace.json", "small_spans.json", "no_cpu_ms"])
@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_reads_nothing_of_a_program_that_draws_none_of_it(name, dump):
    """The parent's program under this PR's files: PR 23's and PR 24's
    recorded dumps, and this dump as a tracer without ``cpu_ms`` and a
    program without the new spans and the histogram would have drawn it."""
    if dump == "no_cpu_ms":
        new = ("fastsync.cycle", "fastsync.tick", "fastsync.take", "fastsync.peek",
               "fastsync.speculate", "fastsync.release", "pool.schedule", "planner.execute")
        spans = [dict(s, args={k: v for k, v in s["args"].items() if k != "cpu_ms"})
                 for s in SPANS if s["name"] not in new]
        counters = {}
    else:
        old = _load(dump)
        spans, counters = old["spans_sync"], old["counters"]
    d = _data(spans, counters)
    assert d.cell.reduce(name, d) is None


def test_span_offcpu_with_and_without_per_and_where():
    d = _data(SPANS, {})
    red = d.bench.module("reducers", "span_offcpu").reduce
    assert red({"span": "fastsync.apply"}, d) == pytest.approx((10 + 2) / 2)
    assert red({"span": "fastsync.apply", "per": "n"}, d) == pytest.approx(12 / 10)
    assert red({"span": "fastsync.precheck", "where": {"n": 6}}, d) == pytest.approx(6.0)
    # a wait reads as all wait; a span nobody drew, or one without the
    # argument, as nothing
    assert red({"span": "fastsync.tick"}, d) == pytest.approx((10.4 + 10.9) / 2)
    assert red({"span": "no.such"}, d) is None
    assert red({"span": "fastsync.apply", "per": "no_such_arg"}, d) is None
    assert red({"span": "bench.window"}, d) is None  # the benchmark's own: no cpu_ms


def test_histogram_mean_ms_needs_observations():
    d = _data([], COUNTERS)
    red = d.bench.module("reducers", "histogram_mean_ms").reduce
    assert red({"histogram": FAMILY}, d) == pytest.approx(0.3)
    assert red({"histogram": "tendermint_verify_no_such_seconds"}, d) is None
    d.counters = {FAMILY + "_sum": 0.0, FAMILY + "_count": 0.0}
    assert red({"histogram": FAMILY}, d) is None


def test_the_cycle_identity_on_the_hand_made_dump():
    """peek + take (own) + harvest + in-line window + speculate + apply +
    release + tick + the cycle's own time = the cycle, each as its reducer
    gives it, brought to one cycle."""
    d = _data(SPANS, {})
    total = d.bench.module("reducers", "span_total_per_span").reduce

    def per_cycle(span, where=None):
        args = {"span": span, "per_span": "fastsync.cycle"}
        return total(dict(args, where=where) if where else args, d)

    takes = len([s for s in SPANS if s["name"] == "fastsync.take"])
    parts = {
        "peek": per_cycle("fastsync.peek"),
        "take": d.cell.reduce("take_ms_per_window.sync", d) * takes / 2,
        "harvest": per_cycle("fastsync.harvest"),
        "window": per_cycle("fastsync.window", {"mode": "sync"}),
        "speculate": per_cycle("fastsync.speculate"),
        "apply": per_cycle("fastsync.apply"),
        "release": per_cycle("fastsync.release"),
        "tick": per_cycle("fastsync.tick"),
        "own": d.cell.reduce("cycle_unnamed_ms.sync", d),
    }
    assert parts == pytest.approx({
        "peek": 0.25, "take": 0.25, "harvest": 9.75, "window": 20.0, "speculate": 2.5,
        "apply": 40.0, "release": 1.25, "tick": 10.75, "own": 5.25})
    assert sum(parts.values()) == pytest.approx(d.cell.reduce("cycle_ms_per_window.sync", d))


@pytest.mark.parametrize("cell", sorted(SPLIT))
def test_the_two_valset_misses_sum_to_the_entry_they_split(cell):
    host, device, whole, workload = SPLIT[cell]
    d = _data(SPANS, {}, workload)
    assert d.cell.reduce(host, d) == pytest.approx(3.0)
    assert d.cell.reduce(device, d) == pytest.approx(1.0)
    assert d.cell.reduce(whole, d) == pytest.approx(4.0)
    # and on PR 33's dump: 0.5 + 0.6 on the host, 0.3 + 0.2 + 0.4 on the
    # device, four dispatches
    rec = _load("small_churn.json")
    d = _data(rec["spans_churn"], rec["counters"], workload)
    assert d.cell.reduce(host, d) == pytest.approx(1.1 / 4)
    assert d.cell.reduce(device, d) == pytest.approx(0.9 / 4)
    assert d.cell.reduce(host, d) + d.cell.reduce(device, d) == pytest.approx(
        d.cell.reduce(whole, d))
    d = _data([s for s in SPANS if s["name"] != "valset.miss"], {}, workload)
    assert d.cell.reduce(host, d) is None and d.cell.reduce(device, d) is None


def test_the_entries_and_what_was_there_before_them():
    """What ``test_bench_cells_multisig.py::test_the_churn_cell_keeps_its_five_entries``
    is about, place and count apart (``tests/conftest.py`` marks that test
    ``xfail``: it counts the entries, and this PR appended some)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = harness.Bench(ROOT)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    empty = [m["name"] for m in bench.cell("sync64-empty").per_layer]
    churn = [m["name"] for m in bench.cell("sync64-churn").per_layer]
    msig = [m["name"] for m in bench.cell("msig1k-stream").per_layer]
    assert empty[:22] == EMPTY_22 and set(empty[22:]) == set(WANT)
    assert [n for n in churn if n in EMPTY_22] == EMPTY_22
    assert [n for n in churn if n in CHURN_5] == CHURN_5
    assert all(by_name[n]["workloads"] == ["sync64-churn"] for n in CHURN_5)
    assert [n for n in msig if n in MSIG_7] == MSIG_7
    assert all(by_name[n]["workloads"] == ["msig1k-stream"] for n in MSIG_7)
    # this PR's: appended behind everything that was there, each with a file
    mine = sorted(WANT) + [n for pair in SPLIT.values() for n in pair[:2]]
    old = [m["name"] for m in spec["per_layer"][:-len(mine)]]
    assert set(m["name"] for m in spec["per_layer"][-len(mine):]) == set(mine)
    assert not set(old) & set(mine)
    for name in WANT:
        m = by_name[name]
        assert m["workloads"] == ["sync64-empty", "sync64-churn"]
        assert (m["moves"], m["better"], m["unit"]) == ("sync_blocks_per_s", "lower", "ms")
        assert m["source"] == ("program_counter" if name.startswith("intake") else "program_span")
    for host, device, whole, workload in SPLIT.values():
        for name in (host, device):
            assert by_name[name]["workloads"] == [workload]
            assert by_name[name]["moves"] == by_name[whole]["moves"]
            assert by_name[name]["layer"] == by_name[whole]["layer"]
    for name in mine:
        assert bench.read_json("metrics", name + ".json")["name"] == name
    # an off-CPU reading stands in its wall-time twin's layer
    twins = {"apply_offcpu_ms_per_block.sync": "apply_ms_per_block.sync",
             "precheck_offcpu_ms_per_window.sync": "precheck_ms_per_window.sync",
             "pack_offcpu_ms_per_window.sync": "pack_ms_per_window.sync",
             "submit_offcpu_ms_per_window.sync": "audit_submit_ms.commit",
             "dispatch_prepare_offcpu_ms.sync": "dispatch_prepare_ms.sync",
             "dispatch_pack_offcpu_ms.sync": "dispatch_pack_ms.sync"}
    for name, twin in twins.items():
        assert by_name[name]["layer"] == by_name[twin]["layer"]
        a, b = (bench.read_json("metrics", n + ".json")["args"]["span"] for n in (name, twin))
        assert a == b
