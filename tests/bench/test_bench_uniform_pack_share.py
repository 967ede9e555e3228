"""``uniform_pack_share.commit`` and ``.sync``: the share of a window's
Pallas ed25519 calls whose lanes went down as the caller's own columns (one
message length) and not regrouped by length, each reduced through its own
file as a run reduces it.  1.0 says every dispatch of the window took the
one-length path; a program without the counter family reads nothing and
never raises, so its line leaves the metric out."""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAM = "tendermint_verify_"
PACK = FAM + "ed25519_pack_total"
METRICS = {
    "uniform_pack_share.commit": ("commit10k-stream", "verify_p50_ms"),
    "uniform_pack_share.sync": ("sync64-empty", "sync_blocks_per_s"),
}


def _data(counters, cell):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell(cell), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=[], counters=counters, trace=None)


def _window(uniform=None, grouped=None, dispatches=975.0):
    """A window's counter growth as ``harness.counters_delta`` gives it;
    ``None`` for a program that has no such series."""
    c = {
        FAM + 'calls_total{backend="pallas",algo="ed25519"}': dispatches,
        FAM + 'valset_cache_total{cache="host",result="hit"}': dispatches,
        FAM + 'valset_cache_total{cache="device",result="hit"}': dispatches,
        FAM + 'device_audit_total{outcome="ok"}': 500.0 * dispatches,
    }
    if uniform is not None:
        c[PACK + '{path="uniform"}'] = uniform
    if grouped is not None:
        c[PACK + '{path="grouped"}'] = grouped
    return c


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_entry_and_its_file_are_the_issues(metric):
    cell, moves = METRICS[metric]
    bench = harness.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == metric]
    # a later cell may list itself beside the issue's
    assert cell in entry["workloads"]
    assert dict(entry, workloads=None) == {
        "name": metric, "unit": "ratio", "better": "higher",
        "source": "program_counter",
        "layer": "device boundary (crypto/batch -> ops/dispatch)",
        "moves": moves, "workloads": None}
    assert entry in bench.cell(cell).per_layer
    # appended behind what was there, wherever later PRs append theirs
    names = [m["name"] for m in bench.spec["per_layer"]]
    assert names.index(metric) > names.index("inversions_per_dispatch.secp256k1")
    assert bench.read_json("metrics", metric + ".json") == {
        "name": metric, "reducer": "counter_ratio",
        "args": {"numerator": PACK, "numerator_labels": {"path": "uniform"},
                 "denominator": PACK}}


@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("counters,want", [
    (_window(975.0, 0.0), 1.0),            # every call of the window one length
    (_window(397.0, 0.0, dispatches=397.0), 1.0),
    (_window(3.0, 1.0, dispatches=5.0), 0.75),   # one window held a nil precommit
    (_window(0.0, 12.0, dispatches=24.0), 0.0),  # every call regrouped
])
def test_it_reduces_a_window_to_the_share_of_one_length_calls(
        metric, counters, want):
    d = _data(counters, METRICS[metric][0])
    assert d.cell.reduce(metric, d) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("dump", ["small_trace.json", "small_spans.json"])
def test_it_reads_nothing_from_a_recorded_dump_without_the_family(metric, dump):
    """The recorded dumps of PR 23 and PR 24: programs that regrouped every
    call and had no counter to say so."""
    with open(os.path.join(ROOT, "benchmark", "testdata", dump)) as f:
        rec = json.load(f)
    assert not any(k.startswith(PACK) for k in rec["counters"])
    d = _data(rec["counters"], METRICS[metric][0])
    assert d.cell.reduce(metric, d) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_parent_and_an_idle_window_read_nothing_not_an_error(metric):
    cell = METRICS[metric][0]
    parent = _data(_window(), cell)
    assert parent.cell.reduce(metric, parent) is None
    # the family exposed from 0 and no ed25519 call in the window (the secp
    # cell's program): nothing to take a share of
    idle = _data(_window(0.0, 0.0, dispatches=0.0), cell)
    assert idle.cell.reduce(metric, idle) is None
    empty = _data({}, cell)
    assert empty.cell.reduce(metric, empty) is None


def test_the_program_feeds_the_counter_the_files_name():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    text = VerifyMetrics().registry.expose_text().splitlines()
    for path in ("uniform", "grouped"):
        assert f'{PACK}{{path="{path}"}} 0' in text
