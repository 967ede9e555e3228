"""Each reducer and the trace reduction on the small recorded dict in
benchmark/testdata/small_trace.json (times in ms below; see the file).

Device 0 is busy 2.0-4.5 (three ops, two overlapping) and 7.0-9.5 inside
the traced 1.0-11.0: 5.0 of 10.0 ms.  The two kernels of each dispatch take
0.5 + 2.0 ms; a third ladder call lies outside the window and is not
counted.  Two verify_commit calls of 4.0 ms hold a 2.7 ms dispatch each."""

import json
import os

import pytest

from benchmark import harness, opcount, tracefile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(ROOT, "benchmark", "testdata", "small_trace.json")) as f:
        return json.load(f)


def _data(rec, spans):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell("commit10k-stream"),
        device_kind="TPU v5 lite",
        samples={"verify_commit_ms": [4.0, 4.0, 6.0, 10.0]},
        totals={"blocks_applied": 2032.0, "sync_seconds": 4.0},
        spans=rec[spans], counters=rec["counters"], trace=rec["trace"])


def _reduce(rec, reducer, args, spans="spans_commit"):
    d = _data(rec, spans)
    return d.bench.module("reducers", reducer).reduce(args, d)


def test_busy_idle_and_kernel_time(rec):
    t = rec["trace"]
    assert tracefile.busy_intervals(t, 0) == [(2_000_000, 4_500_000), (7_000_000, 9_500_000)]
    assert tracefile.busy_seconds(t) == pytest.approx(0.005)
    assert tracefile.window_seconds(t) == pytest.approx(0.010)
    assert tracefile.idle_share(t) == pytest.approx(50.0)
    seconds, count = tracefile.kernel_seconds(t, "_device_verify_packed")
    assert (count, seconds) == (4, pytest.approx(0.005))
    assert tracefile.kernel_seconds(t, r"_device_verify_packed\.3")[0] == pytest.approx(0.004)


def test_breakdown_names_ops_and_attributes_gaps_to_the_innermost_span(rec):
    ops = tracefile.top_device_ops(rec["trace"])
    assert ops[0][0].startswith("__device_verify_packed.3") and ops[0][1] == pytest.approx(0.005)
    assert all(" " not in name and len(name) <= 64 for name, _ in ops)
    gaps = dict(tracefile.idle_gaps_by_span(rec["trace"], rec["spans_commit"]))
    assert gaps["bench.verify_commit"] == pytest.approx(0.0026)
    assert gaps["_no_span_"] == pytest.approx(0.0020)
    assert gaps["verify.dispatch"] == pytest.approx(0.0004)
    assert sum(gaps.values()) == pytest.approx(0.005)


def test_no_device_plane_reads_as_nothing(rec):
    empty = dict(rec["trace"], ops=[], devices=0)
    assert tracefile.idle_share(empty) is None
    d = _data(rec, "spans_commit")
    d.trace = empty
    for reducer, args in (("trace_idle_share", {}),
                          ("trace_kernel_time", {"pattern": "x"}),
                          ("ops_over_time", {"pattern": "x", "function": "ed25519_bytes",
                                             "lanes_counter": "tendermint_verify_sigs_total"})):
        assert d.bench.module("reducers", reducer).reduce(args, d) is None


@pytest.mark.parametrize("reducer,args,spans,want", [
    ("span_mean", {"span": "verify.dispatch"}, "spans_commit", 2.7),
    ("span_minus_children", {"span": "bench.verify_commit", "child": "verify.dispatch"},
     "spans_commit", 1.3),
    ("span_mean", {"span": "fastsync.window"}, "spans_sync", 2.7),
    ("span_mean", {"span": "planner.pack"}, "spans_sync", 0.4),
    ("span_mean", {"span": "fastsync.apply", "per": "n"}, "spans_sync", 0.5),
    ("span_share", {"span": "fastsync.window", "where": {"mode": "speculative"}},
     "spans_sync", 50.0),
    ("counter_delta", {"counter": "compile.programs"}, "spans_commit", 0.0),
    ("counter_delta", {"counter": "tendermint_verify_sigs_total"}, "spans_commit", 20000.0),
    ("counter_ratio", {"numerator": "tendermint_verify_device_audit_total",
                       "denominator": "tendermint_verify_calls_total"}, "spans_commit", 500.0),
    ("counter_ratio", {"numerator": "tendermint_verify_window_heights_sum",
                       "denominator": "tendermint_verify_window_heights_count"},
     "spans_sync", 127.0),
    ("trace_kernel_time", {"pattern": "_device_verify_packed"}, "spans_commit", 2.5),
    ("trace_idle_share", {}, "spans_commit", 50.0),
    ("sample_percentile", {"samples": "verify_commit_ms", "q": 50}, "spans_commit", 5.0),
    ("sample_percentile", {"samples": "verify_commit_ms", "q": 90}, "spans_commit", 8.8),
    ("total_rate", {"count": "blocks_applied", "seconds": "sync_seconds"}, "spans_commit", 508.0),
])
def test_reducer_on_the_recorded_dump(rec, reducer, args, spans, want):
    assert _reduce(rec, reducer, args, spans) == pytest.approx(want)


def test_spans_outside_the_window_are_not_read(rec):
    # the dump holds a third verify.dispatch before the window opened
    assert sum(s["name"] == "verify.dispatch" for s in rec["spans_commit"]) == 3
    assert _reduce(rec, "span_mean", {"span": "verify.dispatch"}) == pytest.approx(2.7)


def test_ops_over_time_is_a_rate_and_a_share_of_a_published_peak(rec):
    lanes, seconds = 20000.0, 0.005
    rate = _reduce(rec, "ops_over_time", {
        "pattern": "_device_verify_packed", "function": "ed25519_row_products",
        "lanes_counter": "tendermint_verify_sigs_total", "scale": 1e-9})
    assert rate == pytest.approx(opcount.ed25519_row_products(lanes) / seconds / 1e9)
    share = _reduce(rec, "ops_over_time", {
        "pattern": "_device_verify_packed", "function": "ed25519_bytes",
        "lanes_counter": "tendermint_verify_sigs_total", "peak": "hbm_bytes_per_s"})
    assert share == pytest.approx(100 * opcount.ed25519_bytes(lanes) / seconds / 819e9)
    assert 0 < share < 100


def test_missing_things_return_nothing(rec):
    assert _reduce(rec, "span_mean", {"span": "no.such.span"}) is None
    assert _reduce(rec, "counter_delta", {"counter": "no_such_total"}) is None
    assert _reduce(rec, "counter_ratio", {"numerator": "a", "denominator": "b"}) is None
    assert _reduce(rec, "sample_percentile", {"samples": "nothing", "q": 50}) is None


def test_op_counts_follow_the_model():
    assert sum(opcount.ED25519_FE_MUL.values()) == 3825
    assert opcount.ed25519_row_products(1) == 3825 * 400
    assert opcount.ed25519_bytes(10, varying_words=2) == 10 * 268
