"""The live-chain commit cell whole, on the CPU at 96 validators and a ring
of 6: a cell made of the files ``commit-ed25519-10k-live`` added, its
control, its generator, and what its driver asks of a program."""

import gc
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import chaingen_absent as gen
from benchmark import commit_reference as ref
from benchmark import control, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "commit96-absent"
FULL = "commit10k-absent"
# a third less one of 96 slots: 28 absent and 3 for nil leave 65 for the
# block, 65 x 3 = 195 > 192; one fewer is exactly two thirds
TINY = {"validators": 96}
TINY_TRAFFIC = {"ring": 6, "warmup_calls": 8, "absent": 28, "nil": 3,
                "check_commits": 6}
SEED = 2**31 + 23
STANDS = {"bad_signature": False, "bad_signature_on_nil": False,
          "wrong_validator": False, "s_plus_L": True, "wrong_block_id": False,
          "one_more_nil": False, "nil_to_other_block": True}


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout's benchmark files plus ``commit-ed25519-10k-live`` cut to
    96 validators, in a cell that reports whatever ``commit10k-absent`` does."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    base = harness.Bench(ROOT)
    cfg = dict(base.read_json("configs", "commit-ed25519-10k-live.json"),
               name="commit-ed25519-96-live", **TINY)
    with open(root / "benchmark" / "configs" / "commit-ed25519-96-live.json", "w") as f:
        json.dump(cfg, f)
    traffic = dict(base.read_json("traffic", "height-stream-absent.json"),
                   **TINY_TRAFFIC)
    with open(root / "benchmark" / "traffic" / "tiny-stream-absent.json", "w") as f:
        json.dump(traffic, f)
    spec["configs"].append(
        {"name": "commit-ed25519-96-live", "source": "test", "reduced": [],
         "file": "benchmark/configs/commit-ed25519-96-live.json", "why": "test"})
    spec["workloads"].append(
        {"name": CELL, "config": "commit-ed25519-96-live",
         "traffic": "tiny-stream-absent", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if FULL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    yield str(root)
    # a run freezes the heap and installs its verifier: undo both
    gc.unfreeze()
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import breaker, trace

    trace.disable()
    breaker.reset_device_guard()
    batch.set_batch_verifier(batch.HostBatchVerifier())


def _run(root, trace=False, device=None, seconds=0.4, seed=SEED):
    lines = []
    result = harness.run_cell(
        harness.Bench(root), CELL, seed, seconds, trace, "cpu", "cpu",
        lines.append, time.perf_counter(), device=device,
        trace_dir=os.path.join(root, ".bench_cache", "trace"))
    return result, lines


def test_the_cell_runs_and_is_correct_traced_and_untraced(tiny_root):
    result, lines = _run(tiny_root)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"verify_p50_ms", "setup_s"}
    checks = result["checks"]
    assert all(c["ok"] and c["limit"] == 0.0 for c in checks), checks
    names = [c["name"] for c in checks]
    assert names == [
        "warmup.rejected_valid_commits", "window.device_fallback_total",
        "window.host_fallback_total", "window.audit_mismatch",
        "window.audited_lanes_vs_ceil_0.05_of_each_call",
        "window.precommits_counted_vs_generator",
        "window.regrouped_calls_vs_one_a_call_of_several_lengths",
        "window.launches_vs_one_a_length_a_call", "window.compiles",
        "lanes.ring_vs_reference_over_6_commits_408_lanes",
        "tampered.verdict_vs_reference_over_7", "tampered.lanes_vs_reference",
        "checks.fallbacks_and_audit_mismatches"]
    assert any("65 for the block, 3 for nil, 28 absent; 68 lanes of 2 lengths"
               in ln for ln in lines), lines
    # the tail is read per layer on the host's clock, in untraced runs too
    clock = result["per_layer_host_clock"]
    assert set(clock) == {"call_p90_ms.absent"}
    assert clock["call_p90_ms.absent"]["value"] >= result["metrics"]["verify_p50_ms"]["value"]

    traced, lines = _run(tiny_root, trace=True)
    assert traced["correct"] is True, lines
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert got["stray_lanes_per_call.absent"] == 3.0
    assert got["absent_per_call.absent"] == 28.0
    assert got["column_collect_share.absent"] == 0.0  # two lengths: the lists
    assert got["audit_lanes_per_dispatch.commit"] == 4.0  # ceil(5 % of 68)
    assert got["compiles_in_window.commit"] == 0
    assert got["collect_ms.commit"] > 0 and got["tally_ms.commit"] > 0
    assert got["dispatch_ms.commit"] > 0 and got["call_p90_ms.absent"] > 0
    # the host verifier stands in for the device here: it packs nothing,
    # launches nothing and has no valset cache or device plane
    for name in ("launches_per_call.absent", "uniform_pack_share.commit",
                 "valset_cache_miss_ratio.absent",
                 "valset_miss_host_ms_per_call.absent",
                 "valset_miss_device_ms_per_call.absent"):
        assert name not in got
    assert not any(k.startswith(("kernel_", "device_idle")) for k in got)
    assert "column_collect_share.commit" not in got  # pinned to three cells


@pytest.mark.parametrize("kind", ["null", "flip"])
def test_a_broken_verifier_comes_out_not_correct(tiny_root, kind):
    result, lines = _run(tiny_root, device=control.make_device("cpu", kind),
                         seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1, lines
    failed = [c["name"] for c in result["checks"] if not c["ok"]]
    if kind == "null":  # valid commits pass; the bad lanes go through
        assert "tampered.verdict_vs_reference_over_7" in failed
        assert "tampered.lanes_vs_reference" in failed
        assert "warmup.rejected_valid_commits" not in failed


def test_a_program_without_the_counters_is_not_timed(monkeypatch):
    """What the parent does under this PR's benchmark files: ``setup`` ends
    the run before anything is generated."""
    from types import SimpleNamespace

    from benchmark.drivers import commit_stream_absent as drv

    ctx = SimpleNamespace(cell=SimpleNamespace(config_name="commit-ed25519-10k-live"))
    drv._require_the_counters(ctx)  # this program counts both
    full = harness.counters_snapshot()
    for family in (drv.HELD, drv.LAUNCHES):
        monkeypatch.setattr(drv, "counters_snapshot", lambda: {
            k: v for k, v in full.items() if not k.startswith(family)})
        with pytest.raises(RuntimeError, match="cannot run commit-ed25519-10k-live"):
            drv.setup(ctx)


def _tiny_inputs(seed=SEED, **traffic):
    cell = harness.Bench(ROOT).cell(FULL)
    cfg = dict(cell.config, **TINY)
    traffic = dict(cell.traffic, **dict(TINY_TRAFFIC, **traffic))
    keys = gen.make_keys(cfg, seed)
    return cell, cfg, keys, gen.make_ring(keys, cfg, traffic, seed)


def test_same_seed_same_inputs_and_the_set_is_in_order():
    import hashlib

    _, _, keys_a, a = _tiny_inputs()
    _, _, keys_b, b = _tiny_inputs()
    _, _, keys_c, c = _tiny_inputs(seed=SEED + 1)
    assert keys_a.pubs == keys_b.pubs and [x.wire for x in a] == [x.wire for x in b]
    assert keys_a.pubs != keys_c.pubs and a[0].wire != c[0].wire
    assert [x.height for x in a] == [500, 501, 502, 503, 504, 505]
    addrs = [hashlib.sha256(p).digest()[:20] for p in keys_a.pubs]
    assert addrs == sorted(addrs) and len(set(keys_a.pubs)) == 96
    assert keys_a.powers == [10] * 96


def test_every_height_holds_the_traffics_counts_and_no_two_share_an_absent_set():
    cell, _, _, ring = _tiny_inputs(ring=24)
    for live in ring:
        assert (live.count(gen.FOR_BLOCK), live.count(gen.FOR_NIL),
                live.count(gen.ABSENT)) == (65, 3, 28)
        assert all(p.block_id in (live.block_id, ref.NIL)
                   for p in live.precommits if p)
    absent = {tuple(p is None for p in live.precommits) for live in ring}
    nil = {tuple(bool(p and p.block_id == ref.NIL) for p in live.precommits)
           for live in ring}
    assert len(absent) == len(nil) == 24
    # the cell as it is run: the edge of the quorum, more heights than
    # either valset cache holds, one lap and twenty calls of warm-up
    t, c = cell.traffic, cell.config
    assert (t["absent"], t["nil"], c["validators"]) == (3000, 333, 10000)
    assert (c["validators"] - t["absent"] - t["nil"]) * 3 == 2 * c["validators"] + 1
    assert t["ring"] == 72 > 64 and t["warmup_calls"] == t["ring"] + 20
    assert t["first_height"] == 500 and t["check_commits"] in (24, 72)


def test_a_decoded_commits_votes_do_not_share_a_block_id_object():
    from benchmark.drivers import commit_stream_absent as drv

    _, _, keys, ring = _tiny_inputs()
    case = drv._case(ring[0], drv._valset(keys))
    votes = [v for v in case.commit.precommits if v is not None]
    assert len(votes) == 68
    assert len({id(v.block_id) for v in votes}) == 68
    assert all(v.block_id is not case.commit.block_id for v in votes)
    assert sum(v.block_id == case.commit.block_id for v in votes) == 65
    assert sum(v.is_nil for v in votes) == 3
    # the bytes are the whole of what the driver is given of a commit
    assert case.commit.marshal() == ring[0].wire


@pytest.mark.parametrize("kind", sorted(STANDS))
def test_each_tamper_is_decided_as_the_reference_decides(kind):
    from benchmark.drivers import commit_stream_absent as drv
    from tendermint_tpu.types.validator_set import CommitError

    cell, _, keys, ring = _tiny_inputs()
    assert sorted(cell.traffic["tampers"]) == sorted(STANDS)
    live = gen.tamper(ring[1], keys, kind, np.random.default_rng([SEED, 1]))
    want = gen.reference_verdict(live, keys)
    assert want.stands is STANDS[kind], want.rule
    case = drv._case(live, drv._valset(keys))
    try:
        case.valset.verify_commit(case.chain_id, case.block_id, case.height, case.commit)
        accepted = True
    except CommitError:
        accepted = False
    assert accepted is want.stands
    if kind == "wrong_block_id":
        assert want.lanes == [] and want.rule == "wrong block id"
    else:
        assert drv._device_lane_verdicts(case) == want.lanes
        bad = want.lanes.count(False)
        assert bad == {"bad_signature": 1, "bad_signature_on_nil": 1,
                       "wrong_validator": 2}.get(kind, 0)
    counts = (live.count(gen.FOR_BLOCK), live.count(gen.FOR_NIL), live.count(gen.ABSENT))
    assert counts == {"one_more_nil": (64, 4, 28)}.get(kind, (65, 3, 28))
    if kind == "nil_to_other_block":  # a stray for a block is still a stray
        assert want.tallied == 650 and sum(
            p.block_id not in (live.block_id, ref.NIL) for p in live.precommits if p) == 1


def test_the_generator_stops_where_the_programs_sign_bytes_differ(monkeypatch):
    from tendermint_tpu.types import vote as vote_mod

    _, cfg, keys, ring = _tiny_inputs()
    real = vote_mod.canonical_vote_sign_bytes

    def drifted(chain_id, vote_type, height, round, ts, block_id):
        out = real(chain_id, vote_type, height, round, ts, block_id)
        return out + b"\x00" if block_id.is_zero() else out

    monkeypatch.setattr(vote_mod, "canonical_vote_sign_bytes", drifted)
    with pytest.raises(RuntimeError, match="precommit for nil is not the reference's"):
        gen.wire(ring[0], keys)
