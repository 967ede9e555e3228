"""The five per-layer metrics of ``sync64-churn`` on
benchmark/testdata/small_churn.json (times in ms; see the file), and their
entries in BENCHMARK.json.

Four dispatches in the window.  ``valset.miss``: 0.5 + 0.3 in the first
(host, device), 0.6 + 0.2 in the second, none in the third (both caches hit),
0.4 in the fourth (device alone: a bucket new to it): 2.0 over 4 dispatches.
The one before the window is not read.  ``fastsync.discard``: 0.4, 0.2 and
0.6 in the window, mean 0.4.  Eight ``verify_block_window`` calls, six cut by
a set change.  Two whole syncs with four set changes and three whole-cache
clears between them.
"""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sync64-churn"
WANT = {
    "cut_window_share.churn": 6 / 8,
    "valset_changes_per_sync.churn": 4 / 2,
    "discard_ms_per_window.churn": (0.4 + 0.2 + 0.6) / 3,
    "valset_miss_ms_per_window.churn": 2.0 / 4,
    "valset_cache_clears_per_sync.churn": 3 / 2,
}


def _load(name):
    with open(os.path.join(ROOT, "benchmark", "testdata", name)) as f:
        return json.load(f)


def _data(spans, counters, totals):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell(CELL), device_kind="TPU v5 lite",
        samples={}, totals=totals, spans=spans, counters=counters)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_on_the_hand_made_dump(name):
    rec = _load("small_churn.json")
    d = _data(rec["spans_churn"], rec["counters"], rec["totals"])
    assert d.cell.reduce(name, d) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_reads_nothing_of_a_program_without_the_spans_and_counters(name):
    """The parent's program under this PR's benchmark files: the sync set of
    PR 24's dump, and the totals ``reactor_sync_churn`` gives when the
    counter families are not there."""
    old = _load("small_spans.json")
    counters = {k: v for k, v in old["counters"].items()
                if "window_cut" not in k and "valset_c" not in k}
    spans = [s for s in old["spans_sync"]
             if s["name"] not in ("fastsync.discard", "valset.miss")]
    d = _data(spans, counters, {"blocks_applied": 10, "sync_seconds": 1.0,
                                "whole_syncs": 2})
    assert d.cell.reduce(name, d) is None


def test_span_total_per_span_needs_both_spans():
    rec = _load("small_churn.json")
    d = _data(rec["spans_churn"], {}, {})
    red = d.bench.module("reducers", "span_total_per_span").reduce
    args = {"span": "valset.miss", "per_span": "verify.dispatch"}
    assert red(args, d) == pytest.approx(0.5)
    assert red(dict(args, where={"cache": "device"}), d) == pytest.approx(0.9 / 4)
    assert red(dict(args, span="no.such"), d) is None
    assert red(dict(args, per_span="no.such"), d) is None


def test_the_cell_and_its_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fastsync-64v-churn", "churn-blocks", 1)
    assert all(w["chips"] == 1 for w in spec["workloads"])
    bench = harness.Bench(ROOT)
    c = bench.cell(CELL)
    assert {m["name"] for m in c.end_to_end} == {"sync_blocks_per_s", "setup_s"}
    names = [m["name"] for m in c.per_layer]
    # what sync64-empty reports per layer, and the five of its own
    empty = [m["name"] for m in bench.cell("sync64-empty").per_layer]
    assert len(empty) == 22 and [n for n in names if n in empty] == empty
    assert [n for n in names if n not in empty] == list(
        m["name"] for m in spec["per_layer"][-5:])
    assert set(names) - set(empty) == set(WANT)
    for m in spec["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "sync_blocks_per_s"


def test_the_configuration_file_states_the_deployment():
    bench = harness.Bench(ROOT)
    churn = bench.read_json("configs", "fastsync-64v-churn.json")
    plain = bench.read_json("configs", "fastsync-64v.json")
    same = ("chips", "validators", "voting_power", "key_type", "chain_id", "stores",
            "peers", "network_delay_ms", "reactor", "verify", "reduced")
    assert all(churn[k] == plain[k] for k in same)
    assert churn["guarantees"][:5] == plain["guarantees"] and len(churn["guarantees"]) == 7
    assert set(churn["assumed"]) >= {"change_interval", "change_shape", "power_range",
                                     "peers", "network_delay_ms", "allocator"}
    traffic = bench.read_json("traffic", "churn-blocks.json")
    assert (traffic["blocks"], traffic["change_interval"], traffic["join_power"],
            traffic["repowers"], traffic["power_range"]) == (2048, 64, 10, 4, [1, 20])
    changes = [h for h in range(1, 2049) if h % 64 == 0 and h < 2047]
    assert changes[0] == 64 and changes[-1] == 1984 and len(changes) == 31
