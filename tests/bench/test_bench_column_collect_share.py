"""``column_collect_share.commit``: the share of a window's
``ValidatorSet.verify_commit`` calls whose lanes went down as columns (an
all-ed25519 set: arrays from the set's own key and power columns) and not as
the lists of ``verify_generic``, reduced through its own file as a run
reduces it.  1.0 in ``commit10k-stream``, 0.0 in the two cells whose sets
are not all ed25519; a program without the counter family (the parent)
reads nothing and never raises, so its line leaves the metric out."""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAM = "tendermint_verify_"
FORM = FAM + "commit_collect_total"
METRIC = "column_collect_share.commit"
CELLS = ["commit10k-stream", "secp256-stream", "msig1k-stream"]


def _data(counters, cell):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell(cell), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=[], counters=counters, trace=None)


def _window(columns=None, lists=None, calls=1160.0):
    """A window's counter growth as ``harness.counters_delta`` gives it;
    ``None`` for a program that has no such series."""
    c = {
        FAM + 'calls_total{backend="pallas",algo="ed25519"}': calls,
        FAM + 'ed25519_pack_total{path="uniform"}': calls,
        FAM + 'device_audit_total{outcome="ok"}': 500.0 * calls,
    }
    if columns is not None:
        c[FORM + '{form="columns"}'] = columns
    if lists is not None:
        c[FORM + '{form="lists"}'] = lists
    return c


def test_the_entry_and_its_file_are_the_issues():
    bench = harness.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "ratio", "better": "higher",
        "source": "program_counter",
        "layer": "host packing + guard audit (types/validator_set, crypto/batch)",
        "moves": "verify_p50_ms", "workloads": CELLS}
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {
        m["layer"] for m in bench.spec["per_layer"] if m["name"] != METRIC}
    for cell in CELLS:
        assert entry in bench.cell(cell).per_layer
        assert "verify_p50_ms" in {m["name"] for m in bench.cell(cell).end_to_end}
    assert bench.read_json("metrics", METRIC + ".json") == {
        "name": METRIC, "reducer": "counter_ratio",
        "args": {"numerator": FORM, "numerator_labels": {"form": "columns"},
                 "denominator": FORM}}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("counters,want", [
    (_window(1160.0, 0.0), 1.0),              # every call an all-ed25519 commit
    (_window(0.0, 2841.0, calls=2841.0), 0.0),  # a secp256k1 or multisig set
    (_window(3.0, 1.0, calls=4.0), 0.75),     # one commit held a short signature
])
def test_it_reduces_a_window_to_the_share_of_column_calls(cell, counters, want):
    d = _data(counters, cell)
    assert d.cell.reduce(METRIC, d) == pytest.approx(want)


@pytest.mark.parametrize("cell", CELLS)
def test_the_parent_and_an_idle_window_read_nothing_not_an_error(cell):
    parent = _data(_window(), cell)
    assert parent.cell.reduce(METRIC, parent) is None
    # the family exposed from 0 and no verify_commit in the window
    idle = _data(_window(0.0, 0.0), cell)
    assert idle.cell.reduce(METRIC, idle) is None
    empty = _data({}, cell)
    assert empty.cell.reduce(METRIC, empty) is None


@pytest.mark.parametrize("dump", ["small_trace.json", "small_spans.json"])
def test_it_reads_nothing_from_a_recorded_dump_without_the_family(dump):
    with open(os.path.join(ROOT, "benchmark", "testdata", dump)) as f:
        rec = json.load(f)
    assert not any(k.startswith(FORM) for k in rec["counters"])
    d = _data(rec["counters"], "commit10k-stream")
    assert d.cell.reduce(METRIC, d) is None


def test_the_program_feeds_the_counter_the_file_names():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    text = VerifyMetrics().registry.expose_text().splitlines()
    for form in ("columns", "lists"):
        assert f'{FORM}{{form="{form}"}} 0' in text
