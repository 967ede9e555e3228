"""The eight per-layer entries of ``commit10k-absent``, each reduced through
its own file as a run reduces it, over a window's counter growth and spans
as the chip's traced run records them.  Every entry is found BY NAME, never
by its place in ``per_layer`` or by a count of entries: later cells append
behind these.  A program without a family, or without a span, reads nothing
and never raises, so its line leaves the metric out."""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "commit10k-absent"
FAM = "tendermint_verify_"
HELD = FAM + "commit_precommits_total"
FORM = FAM + "commit_collect_total"
PACK = FAM + "ed25519_pack_total"
LAUNCHES = FAM + "ed25519_launches_total"
CACHE = FAM + "valset_cache_total"
HOST = "host packing + guard audit (types/validator_set, crypto/batch)"
DEV = "device boundary (crypto/batch -> ops/dispatch)"
MISS = {"span": "valset.miss", "per_span": "verify.dispatch"}

# name -> (unit, better, source, layer, reducer, args)
ENTRIES = {
    "launches_per_call.absent": (
        "launches", "lower", "program_counter", DEV, "counter_ratio",
        {"numerator": LAUNCHES, "denominator": PACK}),
    "stray_lanes_per_call.absent": (
        "lanes", "lower", "program_counter", HOST, "counter_ratio",
        {"numerator": HELD, "numerator_labels": {"kind": "stray"}, "denominator": FORM}),
    "absent_per_call.absent": (
        "slots", "lower", "program_counter", HOST, "counter_ratio",
        {"numerator": HELD, "numerator_labels": {"kind": "absent"}, "denominator": FORM}),
    "column_collect_share.absent": (
        "ratio", "higher", "program_counter", HOST, "counter_ratio",
        {"numerator": FORM, "numerator_labels": {"form": "columns"}, "denominator": FORM}),
    "valset_cache_miss_ratio.absent": (
        "ratio", "lower", "program_counter", DEV, "counter_ratio",
        {"numerator": CACHE, "numerator_labels": {"result": "miss"}, "denominator": CACHE}),
    "valset_miss_host_ms_per_call.absent": (
        "ms", "lower", "program_span", DEV, "span_total_per_span",
        dict(MISS, where={"cache": "host"})),
    "valset_miss_device_ms_per_call.absent": (
        "ms", "lower", "program_span", DEV, "span_total_per_span",
        dict(MISS, where={"cache": "device"})),
    "call_p90_ms.absent": (
        "ms", "lower", "host_clock", HOST, "sample_percentile",
        {"samples": "verify_commit_ms", "q": 90}),
}
# the entries commit10k-stream reports and this cell lists itself on: all
# of them but the one a test pins to three cells
SHARED = [
    "host_outside_dispatch_ms.commit", "audit_lanes_per_dispatch.commit",
    "dispatch_ms.commit", "kernel_ms_per_dispatch.commit", "kernel_mul_rate.commit",
    "kernel_hbm_share.commit", "device_idle_share.commit", "compiles_in_window.commit",
    "collect_ms.commit", "generic_self_ms.commit", "tally_ms.commit",
    "unattributed_ms.commit", "audit_ms.commit", "guard_self_ms.commit",
    "dispatch_pack_ms.commit", "dispatch_launch_ms.commit", "dispatch_wait_ms.commit",
    "dispatch_self_ms.commit", "result_wake_ms.commit", "dispatch_prepare_ms.commit",
    "audit_pool_share.commit", "audit_submit_ms.commit", "uniform_pack_share.commit",
]


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def _entry(bench, name):
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    return entry


def _window(calls=900.0, launches=2.0, grouped=True, held=True, caches=True,
            columns=0.0):
    """A window's counter growth as ``harness.counters_delta`` gives it for
    ``calls`` calls of the cell; a family left out is a program without it."""
    c = {
        FAM + 'calls_total{backend="pallas",algo="ed25519"}': calls,
        FAM + 'sigs_total{backend="pallas",algo="ed25519"}': 7000.0 * calls,
        FAM + 'device_audit_total{outcome="ok"}': 350.0 * calls,
        FAM + 'device_audit_total{outcome="mismatch"}': 0.0,
        FORM + '{form="columns"}': columns * calls,
        FORM + '{form="lists"}': (1.0 - columns) * calls,
        PACK + '{path="uniform"}': 0.0 if grouped else calls,
        PACK + '{path="grouped"}': calls if grouped else 0.0,
    }
    if launches is not None:
        c[LAUNCHES] = launches * calls
    if held:
        c[HELD + '{kind="for_block"}'] = 6667.0 * calls
        c[HELD + '{kind="stray"}'] = 333.0 * calls
        c[HELD + '{kind="absent"}'] = 3000.0 * calls
    if caches:  # one host lookup a call, one device lookup a launch: all miss
        c[CACHE + '{cache="host",result="miss"}'] = calls
        c[CACHE + '{cache="device",result="miss"}'] = 2.0 * calls
        c[CACHE + '{cache="host",result="hit"}'] = 0.0
        c[CACHE + '{cache="device",result="hit"}'] = 0.0
    return c


def _spans(calls=3, host_ms=9.0, device_ms=(1.5, 0.25), miss=True):
    """The spans of ``calls`` traced calls inside one window, on the
    ``perf_counter_ns`` clock: one ``verify.dispatch`` a call, under it one
    host ``valset.miss`` and one device ``valset.miss`` a launch."""
    ms = 1_000_000
    out = [{"name": "bench.window", "t0": 0, "t1": (calls * 60 + 1) * ms,
            "tid": 1, "args": {}}]
    for k in range(calls):
        t = (k * 60 + 1) * ms
        out.append({"name": "verify.dispatch", "t0": t, "t1": t + 50 * ms,
                    "tid": 2, "args": {"algo": "ed25519", "n": 7000}})
        if not miss:
            continue
        out.append({"name": "valset.miss", "t0": t, "t1": t + int(host_ms * ms),
                    "tid": 2, "args": {"cache": "host", "lanes": 7000}})
        for j, (lanes, d) in enumerate(zip((8192, 512), device_ms)):
            t0 = t + (20 + 10 * j) * ms
            out.append({"name": "valset.miss", "t0": t0, "t1": t0 + int(d * ms),
                        "tid": 2, "args": {"cache": "device", "lanes": lanes}})
    # a miss outside the window (the warm-up's, the checks') is not the window's
    out.append({"name": "valset.miss", "t0": (calls * 60 + 2) * ms,
                "t1": (calls * 60 + 30) * ms, "tid": 2, "args": {"cache": "host"}})
    return out


def _data(bench, counters=None, spans=None, samples=None):
    return harness.RunData(
        bench=bench, cell=bench.cell(CELL), device_kind="TPU v5 lite",
        samples=samples or {}, totals={}, spans=spans or [],
        counters=counters or {}, trace=None)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_and_its_file_are_the_issues(bench, name):
    unit, better, source, layer, reducer, args = ENTRIES[name]
    entry = _entry(bench, name)
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "verify_p50_ms", "workloads": [CELL]}
    # the layer is one the benchmark already named, letter for letter
    assert layer in {m["layer"] for m in bench.spec["per_layer"]
                     if not m["name"].endswith(".absent")}
    assert entry in bench.cell(CELL).per_layer
    assert bench.read_json("metrics", name + ".json") == {
        "name": name, "reducer": reducer, "args": args}
    # appended behind what the benchmark had, wherever later PRs append theirs
    names = [m["name"] for m in bench.spec["per_layer"]]
    assert names.index(name) > names.index("intake_bytes_per_block.full")


def test_the_cell_its_configuration_and_what_it_is_held_to(bench):
    (w,) = [w for w in bench.spec["workloads"] if w["name"] == CELL]
    assert w == dict(w, config="commit-ed25519-10k-live",
                     traffic="height-stream-absent", chips=1)
    assert len(w["why"]) <= 200 and "two launches" in w["why"]
    (cfg,) = [c for c in bench.spec["configs"] if c["name"] == w["config"]]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert cfg["file"] == "benchmark/configs/commit-ed25519-10k-live.json"
    cell = bench.cell(CELL)
    assert cell.config["source"] == cfg["source"] and cell.config["reduced"] == []
    assert cell.config["architecture"] is None and len(cell.config["guarantees"]) == 5
    stream = bench.cell("commit10k-stream").config
    for key in ("validators", "voting_power", "key_type", "chain_id", "verify", "chips"):
        assert cell.config[key] == stream[key]
    # a median and the set-up; the tail is per layer until a benchmark PR
    # reads two sets of six
    assert {m["name"] for m in cell.end_to_end} == {"verify_p50_ms", "setup_s"}
    assert CELL not in [m for m in bench.spec["end_to_end"]
                        if m["name"] == "verify_p90_ms"][0]["workloads"]


@pytest.mark.parametrize("name", SHARED)
def test_it_lists_itself_on_a_shared_commit_entry(bench, name):
    entry = _entry(bench, name)
    assert CELL in entry["workloads"] and "commit10k-stream" in entry["workloads"]
    assert entry in bench.cell(CELL).per_layer


def test_and_on_no_other(bench):
    mine = {m["name"] for m in bench.cell(CELL).per_layer}
    assert mine == set(SHARED) | set(ENTRIES)
    # held to three cells by tests/bench/test_bench_column_collect_share.py
    assert CELL not in _entry(bench, "column_collect_share.commit")["workloads"]
    every = {m["name"] for m in bench.cell("commit10k-stream").per_layer}
    assert every - mine == {"column_collect_share.commit"}


@pytest.mark.parametrize("name,counters,want", [
    ("launches_per_call.absent", _window(), 2.0),
    ("launches_per_call.absent", _window(launches=1.0), 1.0),  # the merged launch
    ("launches_per_call.absent", _window(calls=40.0, launches=1.5), 1.5),
    ("stray_lanes_per_call.absent", _window(), 333.0),
    ("absent_per_call.absent", _window(), 3000.0),
    ("absent_per_call.absent", _window(calls=1.0), 3000.0),
    ("column_collect_share.absent", _window(), 0.0),  # today: the lists
    ("column_collect_share.absent", _window(columns=1.0), 1.0),  # columns for two lengths
    ("column_collect_share.absent", _window(calls=4.0, columns=0.75), 0.75),
    ("valset_cache_miss_ratio.absent", _window(), 1.0),
])
def test_a_counter_entry_reduces_a_window(bench, name, counters, want):
    d = _data(bench, counters)
    assert d.cell.reduce(name, d) == pytest.approx(want)


def test_a_members_table_would_show_as_hits(bench):
    c = _window(calls=100.0)
    c[CACHE + '{cache="host",result="miss"}'] = 0.0
    c[CACHE + '{cache="host",result="hit"}'] = 100.0
    d = _data(bench, c)
    assert d.cell.reduce("valset_cache_miss_ratio.absent", d) == pytest.approx(2 / 3)


@pytest.mark.parametrize("name,want", [
    ("valset_miss_host_ms_per_call.absent", 9.0),
    ("valset_miss_device_ms_per_call.absent", 1.75),  # both launches' uploads
])
def test_the_miss_pair_is_read_a_call_over_both_launches(bench, name, want):
    d = _data(bench, spans=_spans())
    assert d.cell.reduce(name, d) == pytest.approx(want)
    # the pair sums to what the unsplit reading of msig1k-stream would say
    whole = bench.module("reducers", "span_total_per_span").reduce(MISS, d)
    assert whole == pytest.approx(9.0 + 1.75)


@pytest.mark.parametrize("q,want", [(90, 55.5), (50, 53.5)])
def test_the_tail_is_the_windows_own_samples(bench, q, want):
    samples = {"verify_commit_ms": [51.0 + 0.5 * i for i in range(11)]}
    d = _data(bench, samples=samples)
    if q == 90:
        assert d.cell.reduce("call_p90_ms.absent", d) == pytest.approx(want)
    else:
        assert d.cell.reduce("verify_p50_ms", d) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_program_without_the_reading_reads_nothing_not_an_error(bench, name):
    # the parent: no launches family, no precommits family; a host verifier:
    # no pack family growth, no valset cache, no miss span; an empty window
    parent = _window(launches=None, held=False, caches=False)
    for family in (PACK, FORM):
        parent = {k: v for k, v in parent.items() if not k.startswith(family)}
    for data in (_data(bench, parent, _spans(miss=False)), _data(bench)):
        assert data.cell.reduce(name, data) is None
    idle = _data(bench, {k: 0.0 for k in _window()}, _spans(calls=0))
    assert idle.cell.reduce(name, idle) is None


@pytest.mark.parametrize("dump", ["small_trace.json", "small_spans.json"])
def test_they_read_nothing_from_a_recorded_dump_of_another_cell(bench, dump):
    with open(os.path.join(ROOT, "benchmark", "testdata", dump)) as f:
        rec = json.load(f)
    assert not any(k.startswith((HELD, LAUNCHES)) for k in rec["counters"])
    d = _data(bench, rec["counters"])
    for name in ("launches_per_call.absent", "stray_lanes_per_call.absent",
                 "absent_per_call.absent"):
        assert d.cell.reduce(name, d) is None


def test_the_program_feeds_the_counters_the_files_name():
    from tendermint_tpu.libs.metrics import VerifyMetrics

    text = VerifyMetrics().registry.expose_text().splitlines()
    for kind in ("for_block", "stray", "absent"):
        assert f'{HELD}{{kind="{kind}"}} 0' in text
    assert f"{LAUNCHES} 0" in text
