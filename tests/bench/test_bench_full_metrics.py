"""The per-layer metrics of ``sync64-full`` on recorded counters, the reducer
they brought, and their entries in BENCHMARK.json, each found by its name.

The counters below are one window as ``harness.counters_delta`` hands it to
a reducer: 4 blocks applied, every stage observed once a block (seconds),
4,000 txs delivered, 5 blocks taken in (one more than applied: the tip's)
of 263,000 bytes each.
"""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sync64-full"
LAYER = "apply (state/execution, abci kvstore, stores)"
FAMILY = "tendermint_state_block_stage_seconds"
STAGE_SECONDS = {  # the sum of 4 observations of each stage
    "validate": 0.006, "deliver": 0.080, "save_responses": 0.200,
    "update_state": 0.012, "commit": 0.0004, "save_state": 0.0008,
    "save_block": 0.002,
}
WANT = {
    "validate_ms_per_block.full": 1.5,
    "deliver_ms_per_block.full": 20.0,
    "save_responses_ms_per_block.full": 50.0,
    "update_state_ms_per_block.full": 3.0,
    "app_commit_ms_per_block.full": 0.1,
    "save_state_ms_per_block.full": 0.2,
    "save_block_ms_per_block.full": 0.5,
    "txs_per_block.full": 1000.0,
    "intake_bytes_per_block.full": 263000.0,
}


def _counters():
    c = {}
    for stage, seconds in STAGE_SECONDS.items():
        c[f'{FAMILY}_sum{{stage="{stage}"}}'] = seconds
        c[f'{FAMILY}_count{{stage="{stage}"}}'] = 4.0
    c["tendermint_state_txs_delivered_total"] = 4000.0
    c["tendermint_verify_block_intake_bytes_total"] = 5 * 263000.0
    c["tendermint_verify_block_intake_seconds_count"] = 5.0
    c["tendermint_verify_block_intake_seconds_sum"] = 0.02
    return c


def _data(counters):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell(CELL), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=[], counters=counters)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_on_the_recorded_counters(name):
    d = _data(_counters())
    assert d.cell.reduce(name, d) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_reads_nothing_of_a_program_without_the_families(name):
    """The parent's program under this PR's benchmark files has the intake
    histogram and none of the three new families."""
    old = {k: v for k, v in _counters().items()
           if "block_stage" not in k and "txs_delivered" not in k
           and "intake_bytes" not in k}
    d = _data(old)
    got = d.cell.reduce(name, d)
    # the intake's ratio reads 0 bytes over 5 blocks there, the rest nothing
    assert got is None or (name == "intake_bytes_per_block.full" and got == 0.0)


def test_the_stage_metrics_are_the_whole_of_the_stages():
    """Seven stages, seven files, one label each; their sum is the apply."""
    bench = harness.Bench(ROOT)
    stages = {}
    for name in WANT:
        spec = bench.read_json("metrics", name + ".json")
        assert spec["name"] == name
        if spec["reducer"] == "histogram_label_mean_ms":
            assert spec["args"]["histogram"] == FAMILY
            stages[spec["args"]["labels"]["stage"]] = name
    assert set(stages) == set(STAGE_SECONDS)
    d = _data(_counters())
    total = sum(d.cell.reduce(n, d) for n in stages.values())
    assert total == pytest.approx(1e3 * sum(STAGE_SECONDS.values()) / 4)


def test_histogram_label_mean_ms_reads_one_series():
    d = _data(_counters())
    red = d.bench.module("reducers", "histogram_label_mean_ms").reduce
    args = {"histogram": FAMILY, "labels": {"stage": "deliver"}}
    assert red(args, d) == pytest.approx(20.0)
    assert red({"histogram": FAMILY, "labels": {"stage": "no_such"}}, d) is None
    assert red({"histogram": "tendermint_no_such", "labels": {"stage": "deliver"}}, d) is None
    # no label: every series of the family, as histogram_mean_ms reads it
    whole = d.bench.module("reducers", "histogram_mean_ms").reduce
    assert red({"histogram": FAMILY, "labels": {}}, d) == pytest.approx(
        whole({"histogram": FAMILY}, d))
    # a window in which the stage was never observed
    idle = dict(_counters(), **{f'{FAMILY}_count{{stage="deliver"}}': 0.0})
    assert red(args, _data(idle)) is None


def test_the_cell_and_its_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fastsync-64v-full", "full-blocks", 1)
    (cfg,) = [c for c in spec["configs"] if c["name"] == "fastsync-64v-full"]
    assert cfg["file"] == "benchmark/configs/fastsync-64v-full.json"
    assert cfg["reduced"] == ["blocks"] and len(cfg["source"]) <= 200
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in WANT:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "sync_blocks_per_s"
        assert m["layer"] == LAYER and m["source"] == "program_counter"
    assert by_name["apply_ms_per_block.sync"]["layer"] == LAYER
    c = harness.Bench(ROOT).cell(CELL)
    assert {m["name"] for m in c.end_to_end} == {"sync_blocks_per_s", "setup_s"}
    names = {m["name"] for m in c.per_layer}
    # what both sync cells report per layer, and the nine of its own
    both = {m["name"] for m in spec["per_layer"]
            if {"sync64-empty", "sync64-churn"} <= set(m.get("workloads", ()))}
    assert names == both | set(WANT) and len(both) == 38
    # every metric the cell reports has its file and its reducer
    bench = harness.Bench(ROOT)
    for name in names:
        bench.module("reducers", bench.read_json("metrics", name + ".json")["reducer"])


def test_the_configuration_file_states_the_deployment():
    bench = harness.Bench(ROOT)
    full = bench.read_json("configs", "fastsync-64v-full.json")
    plain = bench.read_json("configs", "fastsync-64v.json")
    same = ("chips", "validators", "voting_power", "key_type", "chain_id", "stores",
            "peers", "network_delay_ms", "reactor", "verify", "reduced")
    assert all(full[k] == plain[k] for k in same)
    assert full["guarantees"][:5] == plain["guarantees"] and len(full["guarantees"]) == 9
    assert set(full["assumed"]) >= {"every_block_full", "tx_alphabet", "commit_rule",
                                    "peers", "network_delay_ms", "allocator"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == full["name"]]
    assert entry["source"] == full["source"]
    traffic = bench.read_json("traffic", "full-blocks.json")
    assert (traffic["driver"], traffic["blocks"], traffic["txs_per_block"],
            traffic["tx_bytes"], traffic["warmup_syncs"], traffic["sync_timeout_s"]) == (
        "reactor_sync_full", 256, 1000, 250, 1, 300)
    empty = bench.read_json("traffic", "empty-blocks.json")
    assert traffic["warmup_window_heights"] == empty["warmup_window_heights"]
