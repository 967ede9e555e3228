"""The two reducers that read span identity and the device's timeline, and
every metric file PR 24 added, on benchmark/testdata/small_spans.json
(times in ms below; see the file).

Commit set: two verify_commit calls on thread 1, each one tree (root ids 1
and 11), each with its dispatch on a worker thread of its own.

  call 1   commit.verify 2.0-10.0 (8.0) holds collect 0.8, verify.generic
           3.0-9.0 (6.0) and tally 0.5: own time 8.0 - 7.3 = 0.7
           verify.generic holds guard.call 3.2-8.9 (5.7): own 0.3
           guard.call holds verify.dispatch 3.4-7.2 (3.8) and guard.audit
           7.4-8.6 (1.2): own 5.7 - 5.0 = 0.7
           verify.dispatch holds prepare 0.1, pack 0.4, launch 0.2 and wait
           4.1-7.0 (2.9): own 3.8 - 3.6 = 0.2
  call 2   commit.verify 11.0-19.0 (8.0) holds 1.0 + 5.8 + 0.3: own 0.9
           verify.generic 5.8 holds guard.call 12.3-17.9 (5.6): own 0.2
           guard.call holds verify.dispatch 12.5-16.5 and guard.audit
           16.0-17.6, which overlap: their union 12.5-17.6 is 5.1, own 0.5
           verify.dispatch 4.0 holds 0.1 + 0.2 + 0.2 + wait 13.0-16.0 (3.0):
           own 0.5

A grandchild is never taken from its grandparent (dispatch.wait is not a
child of guard.call), and the commit.verify before the window is not read.

Device operations end at 6.0, 6.5, 15.0 and 19.5.  The commit set's waits
4.1-7.0 and 13.0-16.0 go on 0.5 and 1.0 after the last one inside them.  The
sync set's waits 5.5-6.8 and 13.9-15.4 go on 0.3 and 0.4; its third wait,
17.0-17.5, holds no operation's end and is left out of the mean.
"""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(name):
    with open(os.path.join(ROOT, "benchmark", "testdata", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rec():
    return _load("small_spans.json")


def _data(rec, cell_name):
    bench = harness.Bench(ROOT)
    spans = "spans_commit" if cell_name == "commit10k-stream" else "spans_sync"
    return harness.RunData(
        bench=bench, cell=bench.cell(cell_name), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=rec[spans], counters=rec["counters"],
        trace=rec["trace"])


def _reduce(rec, reducer, args, cell="commit10k-stream"):
    d = _data(rec, cell)
    return d.bench.module("reducers", reducer).reduce(args, d)


@pytest.mark.parametrize("span,want", [
    ("commit.verify", (0.7 + 0.9) / 2),
    ("verify.generic", (0.3 + 0.2) / 2),
    ("guard.call", (0.7 + 0.5) / 2),
    ("verify.dispatch", (0.2 + 0.5) / 2),
    ("commit.tally", (0.5 + 0.3) / 2),  # no children: its whole duration
])
def test_span_self_time_takes_out_the_union_of_direct_children(rec, span, want):
    assert _reduce(rec, "span_self_time", {"span": span}) == pytest.approx(want)


def test_span_self_time_reads_nothing_without_ids(rec):
    # the dump of a program that draws no ids (PR 23's): spans, no identity
    old = _load("small_trace.json")
    d = _data(rec, "commit10k-stream")
    d.spans = old["spans_commit"]
    reducer = d.bench.module("reducers", "span_self_time")
    assert reducer.reduce({"span": "verify.dispatch"}, d) is None
    assert _reduce(rec, "span_self_time", {"span": "no.such.span"}) is None


def test_span_tail_after_device(rec):
    args = {"span": "dispatch.wait"}
    assert _reduce(rec, "span_tail_after_device", args) == pytest.approx(0.75)
    assert _reduce(rec, "span_tail_after_device", args, "sync64-empty") \
        == pytest.approx(0.35)
    # a span that holds the end of no device operation, and no trace at all
    assert _reduce(rec, "span_tail_after_device", {"span": "commit.tally"}) is None
    d = _data(rec, "commit10k-stream")
    reducer = d.bench.module("reducers", "span_tail_after_device")
    for trace in (None, dict(rec["trace"], ops=[])):
        d.trace = trace
        assert reducer.reduce(args, d) is None


# every metric file this PR added, reduced through its own file as a run
# reduces it; the sync set's numbers:
#   precheck 2.0 and 1.0; harvest 3.0 and 0.4; audit 0.5 and 0.3; prepare 0.1
#   twice; pack 0.4 and 0.2; wait 1.3, 1.5 and 0.5; 8 looks (1 window, 2 harvests, 5 empty)
#   over 3 windows; one speculation in three thrown away; 3 of 6 valset
#   cache lookups missed
NEW_METRICS = {
    "collect_ms.commit": (0.8 + 1.0) / 2,
    "generic_self_ms.commit": 0.25,
    "tally_ms.commit": 0.4,
    "unattributed_ms.commit": 0.8,
    "audit_ms.commit": (1.2 + 1.6) / 2,
    "guard_self_ms.commit": 0.6,
    "dispatch_prepare_ms.commit": 0.1,
    "dispatch_pack_ms.commit": (0.4 + 0.2) / 2,
    "dispatch_launch_ms.commit": 0.2,
    "dispatch_wait_ms.commit": (2.9 + 3.0) / 2,
    "dispatch_self_ms.commit": 0.35,
    "result_wake_ms.commit": 0.75,
    "precheck_ms_per_window.sync": 1.5,
    "harvest_wait_ms_per_window.sync": 1.7,
    "ticks_per_window.sync": 8 / 3,
    "empty_tick_ratio.sync": 5 / 8,
    "discarded_windows.sync": 1.0,
    "audit_ms_per_window.sync": 0.4,
    "dispatch_prepare_ms.sync": 0.1,
    "dispatch_pack_ms.sync": 0.3,
    "dispatch_wait_ms.sync": 1.1,
    "result_wake_ms.sync": 0.35,
    "valset_cache_miss_ratio.sync": 0.5,
}


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_file_reduces_the_dump_to_its_number(rec, metric):
    bench = harness.Bench(ROOT)
    entry = next(m for m in bench.spec["per_layer"] if m["name"] == metric)
    # reduced against the entry's first cell; a later cell may list itself
    # on an existing entry, and then reports it too
    d = _data(rec, entry["workloads"][0])
    assert all(entry in bench.cell(name).per_layer for name in entry["workloads"])
    assert d.cell.reduce(metric, d) == pytest.approx(NEW_METRICS[metric])


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_reads_nothing_or_zero_from_a_program_without_it(metric):
    """The parent's side of a traced run: PR 23's dump has none of the new
    spans, ids or counter families, and no reducer may raise on it."""
    old = _load("small_trace.json")
    bench = harness.Bench(ROOT)
    entry = next(m for m in bench.spec["per_layer"] if m["name"] == metric)
    cell = bench.cell(entry["workloads"][0])
    spans = "spans_commit" if metric.endswith(".commit") else "spans_sync"
    d = harness.RunData(
        bench=bench, cell=cell, device_kind="TPU v5 lite", samples={},
        totals={}, spans=old[spans], counters=old["counters"], trace=old["trace"])
    assert cell.reduce(metric, d) in (None, 0.0)


def test_span_ids_reach_program_spans(tracing, monkeypatch):
    """The program's export, through json as `dump_trace` sends it, read by
    the harness: the ids ride in `args`, which is all it copies."""
    with tracing.span("fastsync.window", h0=1, n=2, mode="sync"):
        with tracing.span("planner.pack", H=2):
            pass
    events = json.loads(json.dumps(tracing.chrome_trace()))["traceEvents"]
    monkeypatch.setattr(tracing, "export", lambda: events)
    spans = {sp["name"]: sp for sp in harness.program_spans()}
    win, pack = spans["fastsync.window"], spans["planner.pack"]
    assert win["args"]["mode"] == "sync" and pack["args"]["H"] == 2
    assert win["args"]["parent_id"] is None
    assert pack["args"]["parent_id"] == win["args"]["span_id"]
    assert pack["args"]["root_id"] == win["args"]["root_id"] \
        == win["args"]["span_id"]
    assert win["t0"] <= pack["t0"] and pack["t1"] <= win["t1"]
