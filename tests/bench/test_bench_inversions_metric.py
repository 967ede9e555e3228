"""``inversions_per_dispatch.secp256k1``: the secp256k1 prologue's modular
inversions over the secp256k1 dispatches of a window, reduced through its
own file as a run reduces it.  1.0 says the batched inversion engaged (one a
dispatch, whatever its size); a program that has no such counter, and one
that ran no secp256k1 dispatch, read zero or nothing and never raise."""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRIC = "inversions_per_dispatch.secp256k1"
FAM = "tendermint_verify_"


def _data(counters, cell="secp256-stream"):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell(cell), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=[], counters=counters, trace=None)


def _window(dispatches, inversions=None, ed25519=0.0):
    """A window's counter growth as ``harness.counters_delta`` gives it."""
    c = {
        FAM + 'calls_total{backend="pallas",algo="secp256k1"}': dispatches,
        FAM + 'sigs_total{backend="pallas",algo="secp256k1"}': 256.0 * dispatches,
        FAM + 'device_audit_total{outcome="ok"}': 13.0 * dispatches,
        FAM + 'secp256k1_host_decided_total{reason="malformed"}': 0.0,
    }
    if ed25519:
        c[FAM + 'calls_total{backend="pallas",algo="ed25519"}'] = ed25519
    if inversions is not None:
        c[FAM + "secp256k1_inversions_total"] = inversions
    return c


def test_the_entry_is_the_issues_but_for_its_name():
    bench = harness.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == METRIC]
    # a later cell may list itself beside the issue's
    assert "secp256-stream" in entry["workloads"]
    assert dict(entry, workloads=None) == {
        "name": METRIC, "unit": "inversions", "better": "lower",
        "source": "program_counter",
        "layer": "device boundary (crypto/batch -> ops/dispatch)",
        "moves": "verify_p50_ms", "workloads": None}
    assert entry in bench.cell("secp256-stream").per_layer
    spec = bench.read_json("metrics", METRIC + ".json")
    assert spec["reducer"] == "counter_ratio"
    assert spec["args"]["numerator"] == FAM + "secp256k1_inversions_total"


@pytest.mark.parametrize("counters,want", [
    (_window(1068.0, 1068.0), 1.0),            # one inversion a dispatch
    (_window(4.0, 3.0), 0.75),                 # one dispatch refused every lane in pass one
    (_window(1068.0, 1068.0, ed25519=975.0), 1.0),  # another curve's dispatches are not ours
    (_window(2.0, 512.0), 256.0),              # what an inversion a lane would read
])
def test_it_reduces_a_window_to_inversions_a_secp256k1_dispatch(counters, want):
    d = _data(counters)
    assert d.cell.reduce(METRIC, d) == pytest.approx(want)


@pytest.mark.parametrize("dump", ["small_trace.json", "small_spans.json"])
def test_it_reads_nothing_or_zero_from_a_recorded_dump_without_the_counter(dump):
    """The recorded dumps of PR 23 and PR 24: ed25519 programs."""
    with open(os.path.join(ROOT, "benchmark", "testdata", dump)) as f:
        rec = json.load(f)
    d = _data(rec["counters"])
    assert d.cell.reduce(METRIC, d) in (None, 0.0)


def test_the_parent_of_this_metric_reads_zero_not_an_error():
    """A program with secp256k1 dispatches and no such counter (it inverts
    once a lane and does not say so): the line carries 0, or leaves it out."""
    d = _data(_window(1080.0))
    assert d.cell.reduce(METRIC, d) in (None, 0.0)
    empty = _data({})
    assert empty.cell.reduce(METRIC, empty) is None


def test_the_program_feeds_the_counter_the_file_names():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    text = VerifyMetrics().registry.expose_text().splitlines()
    assert FAM + "secp256k1_inversions_total 0" in text
