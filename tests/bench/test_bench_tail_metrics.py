"""The tail each cell is held to, and the secp cell's edge per layer (PR 32):
``sample_share_above`` on hand-made samples, the cell's sample metrics on a
recorded window of ``secp256-stream``
(benchmark/testdata/secp_call_samples.json: one run's samples as its
``samples_ms`` note printed them, with what a hand count makes of them),
and the schema's rule over every cell ``BENCHMARK.json`` has (only the secp
cell's own set is pinned: a later cell lists itself on the metrics that are
there).  The secp cell has no
guarded tail: no quantile above the 75th repeated within 10 % over two sets of
six runs of one tree (PERF.md 2, 7f)."""

import json
import os
import re

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEY = "verify_commit_ms"

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]

# what PR 32 leaves the secp cell held to, exactly: a median and the set-up,
# and no guarded tail
SECP_HELD_TO = {"verify_p50_ms": 0.05, "setup_s": 0.25}
# the cells that were there when PR 32 ran stay on their metrics; a later
# cell may list itself beside them
STAYS_ON = {
    "verify_p50_ms": {"commit10k-stream", "secp256-stream"},
    "verify_p90_ms": {"commit10k-stream"},
    "sync_blocks_per_s": {"sync64-empty"},
    "setup_s": {"commit10k-stream", "sync64-empty", "secp256-stream"},
}


def _is_tail(name):
    """A quantile from the 90th up, by the metric's name: ``verify_p90_ms``,
    ``tx_commit_p95_ms``."""
    return re.search(r"_p9\d(_|$)", name) is not None


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(ROOT, "benchmark", "testdata", "secp_call_samples.json")) as f:
        return json.load(f)


def _data(bench, samples, cell="secp256-stream"):
    return harness.RunData(
        bench=bench, cell=bench.cell(cell), device_kind="TPU v5 lite",
        samples=samples, totals={})


def _share(bench, values, factor):
    d = _data(bench, {KEY: values})
    return bench.module("reducers", "sample_share_above").reduce(
        {"samples": KEY, "times_median": factor}, d)


ONE_MODE = [17.9 + 0.002 * i for i in range(100)]
TWO_MODES = [17.8 + 0.005 * i for i in range(88)] + [20.6 + 0.05 * i for i in range(12)]


@pytest.mark.parametrize("values,factor,want", [
    (ONE_MODE, 1.08, 0.0),                       # one mode: nobody is slow
    (TWO_MODES, 1.08, 0.12),                     # a second hump of 12 %
    (TWO_MODES, 1.25, 0.0),                      # ... wholly under a far edge
    (ONE_MODE[:97] + [143.0] * 3, 1.08, 0.03),   # the +125 ms stalls count as slow calls
    ([18.0] * 9 + [19.44], 1.08, 0.0),           # strictly above: a call at the edge is not
    ([18.0] * 9 + [19.45], 1.08, 0.1),
    ([], 1.08, None),                            # nothing to read
])
def test_sample_share_above(bench, values, factor, want):
    got = _share(bench, values, factor)
    assert got is None if want is None else got == pytest.approx(want)


def test_sample_share_above_reads_nothing_without_its_samples(bench):
    d = _data(bench, {"another_ms": [1.0, 2.0]})
    reducer = bench.module("reducers", "sample_share_above")
    assert reducer.reduce({"samples": KEY, "times_median": 1.08}, d) is None


def test_the_factor_is_the_metric_files_own(bench):
    """Calls just under and just over ``times_median`` x the median: the
    metric counts the second kind alone, wherever its file puts the edge."""
    spec = bench.read_json("metrics", "slow_call_share.secp.json")
    factor = spec["args"]["times_median"]
    assert spec["reducer"] == "sample_share_above" and 1.0 < factor < 1.5
    values = [10.0] * 80 + [10.0 * factor - 0.01] * 10 + [10.0 * factor + 0.01] * 10
    d = _data(bench, {KEY: values})
    assert d.cell.reduce("slow_call_share.secp", d) == pytest.approx(0.10)


@pytest.mark.parametrize("metric", [
    "verify_p50_ms", "call_p90_ms.secp", "slow_call_share.secp"])
def test_metric_file_reduces_the_recorded_window_to_the_hand_count(bench, rec, metric):
    d = _data(bench, {KEY: rec["samples_ms"]})
    assert len(rec["samples_ms"]) == rec["calls"]
    assert d.cell.reduce(metric, d) == pytest.approx(rec["hand_count"][metric], abs=1e-9)


def test_the_recorded_window_has_the_modes_the_metrics_are_about(bench, rec):
    """What step 1 found, on the record itself: a valley at the metric file's
    edge, a tenth of the calls beyond it, and the 90th percentile just below
    it: on the edge between the main hump and the slow ones."""
    want = rec["hand_count"]
    factor = bench.read_json("metrics", "slow_call_share.secp.json")["args"]["times_median"]
    edge = factor * want["verify_p50_ms"]
    xs = rec["samples_ms"]
    slow = [x for x in xs if x > edge]
    assert len(slow) == round(want["slow_call_share.secp"] * len(xs)) == 270
    assert 0.9 * edge < want["call_p90_ms.secp"] < edge

    def count(lo, hi):  # calls in [lo, hi) x the median
        return sum(lo * want["verify_p50_ms"] <= x < hi * want["verify_p50_ms"] for x in xs)

    # fewer calls in the valley's 0.04 than in as much of either side's hump
    assert count(factor - 0.02, factor + 0.02) < count(1.24, 1.28) < count(1.04, 1.08)
    assert count(1.36, 1.42) < count(1.45, 1.51)  # and the third hump beyond a gap


@pytest.mark.parametrize("metric", ["call_p90_ms.secp", "slow_call_share.secp"])
def test_the_edge_is_reported_per_layer_in_the_secp_cell(bench, metric):
    entry = next(m for m in bench.spec["per_layer"] if m["name"] == metric)
    assert "secp256-stream" in entry["workloads"]
    assert entry["source"] == "host_clock"
    assert entry["moves"] == "verify_p50_ms" and entry["better"] == "lower"
    assert entry in bench.cell("secp256-stream").per_layer
    # a window with no samples (a run that made no call) leaves it out
    d = _data(bench, {})
    assert d.cell.reduce(metric, d) is None


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_and_a_central_metric_each_once(bench, cell):
    """The schema's rule, over whatever cells ``BENCHMARK.json`` has: the
    set-up, at least one metric that is neither it nor a tail, no name twice;
    a tail is optional (a rate has none, and ``secp256-stream`` has none that
    two sets of runs agree on)."""
    e2e = bench.cell(cell).end_to_end
    names = [m["name"] for m in e2e]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    assert [n for n in names if n != "setup_s" and not _is_tail(n)]
    for m in e2e:
        assert m["source"] in ("host_clock", "device_trace")
        # no guarded tail lets a fifth of the tail go
        assert m["bound"] <= (0.10 if _is_tail(m["name"]) else 0.25)


def test_the_secp_cell_is_held_to_a_median_and_the_setup_and_no_tail(bench):
    e2e = bench.cell("secp256-stream").end_to_end
    assert {m["name"]: m["bound"] for m in e2e} == SECP_HELD_TO
    assert not any(_is_tail(m["name"]) for m in e2e)


@pytest.mark.parametrize("metric", sorted(STAYS_ON))
def test_each_guarded_metric_is_one_entry_that_keeps_its_cells(bench, metric):
    entries = [m for m in bench.spec["end_to_end"] if m["name"] == metric]
    assert len(entries) == 1
    listed = entries[0].get("workloads", CELLS)
    assert STAYS_ON[metric] <= set(listed)
    assert len(listed) == len(set(listed))


def test_the_old_tail_keeps_its_name_reducer_and_bound(bench):
    entry = next(m for m in bench.spec["end_to_end"] if m["name"] == "verify_p90_ms")
    assert entry["bound"] == 0.07
    assert "secp256-stream" not in entry["workloads"]
    old = bench.read_json("metrics", "verify_p90_ms.json")
    new = bench.read_json("metrics", "call_p90_ms.secp.json")
    assert (old["reducer"], old["args"]) == (new["reducer"], new["args"]) \
        == ("sample_percentile", {"samples": KEY, "q": 90})
