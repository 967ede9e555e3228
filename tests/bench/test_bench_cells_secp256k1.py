"""The secp256k1 commit cell whole, on the CPU at 16 validators: a cell made
of the files this deployment added, its controls, and its generator."""

import gc
import json
import os
import shutil
import time

import pytest

from benchmark import chaingen_secp256k1 as gen
from benchmark import control_secp256k1, harness
from benchmark import oracle_secp256k1 as oracle

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "secp16-tiny"


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout's benchmark files plus ``commit-secp256k1-256`` cut to 16
    validators, in a cell that reports whatever ``secp256-stream`` does."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    base = harness.Bench(ROOT)
    cfg = dict(base.read_json("configs", "commit-secp256k1-256.json"),
               validators=16, name="commit-secp256k1-16")
    with open(root / "benchmark" / "configs" / "commit-secp256k1-16.json", "w") as f:
        json.dump(cfg, f)
    traffic = dict(base.read_json("traffic", "height-stream-secp256k1.json"),
                   warmup_calls=4)
    with open(root / "benchmark" / "traffic" / "tiny-stream-secp256k1.json", "w") as f:
        json.dump(traffic, f)
    spec["configs"].append(
        {"name": "commit-secp256k1-16", "source": "test", "reduced": [],
         "file": "benchmark/configs/commit-secp256k1-16.json", "why": "test"})
    spec["workloads"].append(
        {"name": CELL, "config": "commit-secp256k1-16",
         "traffic": "tiny-stream-secp256k1", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "secp256-stream" in m.get("workloads", ()):
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


def _run(root, trace=False, device=None, seconds=0.4, seed=2**31 + 13):
    lines = []
    result = harness.run_cell(
        harness.Bench(root), CELL, seed, seconds, trace, "cpu", "cpu",
        lines.append, time.perf_counter(), device=device,
        trace_dir=os.path.join(root, ".bench_cache", "trace"))
    return result, lines


def test_the_secp_cell_runs_and_is_correct_traced_and_untraced(tiny_root):
    result, lines = _run(tiny_root)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    # no guarded tail (PERF.md 2); the old one and the slow calls' share
    # stand in an untraced line too, under a key of their own
    assert set(result["metrics"]) == {"verify_p50_ms", "setup_s"}
    clock = result["per_layer_host_clock"]
    assert set(clock) == {"call_p90_ms.secp", "slow_call_share.secp"}
    assert clock["call_p90_ms.secp"]["value"] >= result["metrics"]["verify_p50_ms"]["value"]
    assert 0.0 <= clock["slow_call_share.secp"]["value"] <= 1.0
    # every number compared stands beside its limit (the workers' check is
    # left out under 8 sampled lanes: 1 here)
    checks = result["checks"]
    assert len(checks) == 11 and all(c["ok"] and c["limit"] == 0.0 for c in checks), checks
    names = [c["name"] for c in checks]
    assert "tampered.verdict_vs_oracle_over_6" in names
    assert "lanes.ring_vs_oracle_over_4x16" in names
    assert any("DER signatures of" in ln for ln in lines)

    traced, lines = _run(tiny_root, trace=True)
    assert traced["correct"] is True, lines
    got = traced["metrics"]
    assert got["audit_lanes_per_dispatch.commit"]["value"] == 1.0  # ceil(5 % of 16)
    assert got["host_decided_lanes.secp"]["value"] == 0
    assert got["compiles_in_window.commit"]["value"] == 0
    assert got["dispatch_ms.commit"]["value"] > 0
    assert got["collect_ms.secp"]["value"] > 0 and got["tally_ms.secp"]["value"] > 0
    assert got["call_p90_ms.secp"]["value"] > 0
    assert 0.0 <= got["slow_call_share.secp"]["value"] <= 1.0
    # the host verifier stands in for the device here: no prologue span, no
    # device plane, so neither is printed under its name
    assert "prologue_ms.secp" not in got
    assert "per_layer_host_clock" not in traced  # they are among ``metrics`` there
    assert not any(k.startswith(("kernel_", "device_idle")) for k in got)


@pytest.mark.parametrize("kind", ["null", "flip"])
def test_a_broken_secp_verifier_comes_out_not_correct(tiny_root, kind):
    device = control_secp256k1.make_device("cpu", kind)
    result, lines = _run(tiny_root, device=device, seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1, lines
    assert any(not c["ok"] for c in result["checks"])


def test_the_ed25519_control_cannot_break_this_deployment(tiny_root):
    """Why ``control_secp256k1.py`` exists: ``control.py``'s stand-in hands
    ``verify_secp256k1`` through unaltered."""
    from benchmark import control

    result, lines = _run(tiny_root, device=control.make_device("cpu", "null"),
                         seconds=0.3)
    assert result["correct"] is True, lines


def test_same_seed_same_inputs():
    bench = harness.Bench(ROOT)
    cell = bench.cell("secp256-stream")
    cfg = dict(cell.config, validators=5)
    a = gen.make_commit_ring(cfg, cell.traffic, 2**31 + 5)
    b = gen.make_commit_ring(cfg, cell.traffic, 2**31 + 5)
    c = gen.make_commit_ring(cfg, cell.traffic, 2**31 + 6)
    assert [x.lanes for x in a] == [x.lanes for x in b]
    assert a[0].lanes.sigs != c[0].lanes.sigs and a[0].lanes.pubs != c[0].lanes.pubs
    assert len(a) == 4 and [x.height for x in a] == [500, 501, 502, 503]
    assert all(len(p) == 33 for p in a[0].lanes.pubs)


def test_each_tamper_is_what_its_name_says():
    import numpy as np

    bench = harness.Bench(ROOT)
    cell = bench.cell("secp256-stream")
    ring = gen.make_commit_ring(dict(cell.config, validators=20), cell.traffic, 9)
    base = ring[1]
    known, stands = gen.reference_verdict(base.lanes)
    assert stands and all(known)
    want_bad = {"bad_signature": 1, "wrong_validator": 2, "high_s": 1,
                "lax_der": 1, "wrong_block_id": 0, "under_quorum": 0}
    assert list(want_bad) == cell.traffic["tampers"]
    for kind, bad in want_bad.items():
        case = gen.tamper(base, kind, np.random.default_rng(4))
        lanes, stands = gen.reference_verdict(case.lanes, known, base.lanes)
        assert not stands, kind
        assert lanes.count(False) == bad, kind
        changed = [i for i, (x, y) in enumerate(zip(case.lanes.sigs, base.lanes.sigs))
                   if x != y and x is not None]
        if kind == "high_s":
            r, s = oracle.parse_der(base.lanes.sigs[changed[0]])
            assert oracle.parse_der(case.lanes.sigs[changed[0]]) == (r, oracle.N - s)
        if kind == "lax_der":  # lax, and the same (r, s) to a lax parser
            sig = case.lanes.sigs[changed[0]]
            assert oracle.parse_der(sig) is None and sig[4] == 0 and sig[5] < 0x80 \
                and len(sig) == len(base.lanes.sigs[changed[0]]) + 1
        if kind == "bad_signature":  # still strict, still low-s
            r, s = oracle.parse_der(case.lanes.sigs[changed[0]])
            assert s <= oracle.HALF_N
        if kind == "under_quorum":  # exactly two thirds, 15 % absent
            present = sum(p for p, s in zip(case.lanes.powers, case.lanes.sigs) if s)
            assert present * 3 == sum(case.lanes.powers) * 2
            assert case.lanes.sigs.count(None) == 3


# ---------------------------------------------------------------------------
# the new metric files, reduced as a run reduces them
# ---------------------------------------------------------------------------


def _data(spans, counters, ops):
    bench = harness.Bench(ROOT)
    trace = None
    if ops is not None:
        trace = {"ops": ops, "devices": 1, "t0": 0, "t1": 10_000_000}
    return harness.RunData(
        bench=bench, cell=bench.cell("secp256-stream"), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=spans, counters=counters, trace=trace)


def _span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "tid": 1, "args": args}


def test_the_new_metrics_read_their_spans_counters_and_kernel():
    from benchmark import opcount, opcount_secp256k1 as ops

    assert ops.secp256k1_fe_muls() == (15 + 256 + 128) * 12 + 2 == 4790
    assert ops.secp256k1_row_products(1) == 4790 * 400 + 399 * 2 * 20 + 64 * 2 * 3 * 16 * 20
    assert 1.3 < ops.secp256k1_row_products(7) / opcount.ed25519_row_products(7) < 1.4
    assert ops.secp256k1_bytes(2) == 2 * 840

    fam = "tendermint_verify_"
    spans = [
        _span("bench.window", 0, 10_000_000),
        _span("verify.dispatch", 1_000_000, 5_000_000, span_id=1),
        _span("secp.prologue", 1_100_000, 3_100_000, n=256, forced=0, parent_id=1),
        _span("verify.dispatch", 6_000_000, 9_000_000, span_id=2),
        _span("secp.prologue", 6_100_000, 7_100_000, n=256, forced=0, parent_id=2),
    ]
    counters = {
        fam + 'sigs_total{backend="pallas",algo="secp256k1"}': 512.0,
        fam + 'sigs_total{backend="pallas",algo="ed25519"}': 9999.0,  # another curve's lanes
        fam + 'secp256k1_host_decided_total{reason="malformed"}': 0.0,
        fam + 'secp256k1_host_decided_total{reason="degenerate"}': 0.0,
        fam + 'valset_cache_total{cache="secp256k1_pubkey",result="hit"}': 510.0,
        fam + 'valset_cache_total{cache="secp256k1_pubkey",result="miss"}': 2.0,
        fam + 'valset_cache_total{cache="host",result="miss"}': 77.0,  # ed25519's, not ours
    }
    ops_list = [
        ["%_device_verify_secp256k1.1 = u32[1,256] custom-call", 4_000_000, 800_000, 0],
        ["%_device_verify_secp256k1.1 = u32[1,256] custom-call", 8_000_000, 1_200_000, 0],
        ["%_device_verify_packed.3 = u32[1,10240] custom-call", 9_300_000, 500_000, 0],
        ["%copy-done = u32[256]", 9_900_000, 1_000, 0],
    ]
    d = _data(spans, counters, ops_list)
    reduce = d.cell.reduce
    assert reduce("prologue_ms.secp", d) == pytest.approx(1.5)
    assert reduce("host_decided_lanes.secp", d) == 0.0
    assert reduce("pubkey_cache_miss_ratio.secp", d) == pytest.approx(2 / 512)
    assert reduce("kernel_ms_per_dispatch.secp", d) == pytest.approx(1.0)  # the ed25519 op is not ours
    assert reduce("kernel_mul_rate.secp", d) == pytest.approx(
        ops.secp256k1_row_products(512) / 2e-3 * 1e-9)
    assert reduce("kernel_hbm_share.secp", d) == pytest.approx(
        100 * ops.secp256k1_bytes(512) / 2e-3 / 819e9)
    # and the ed25519 kernel's pattern does not match the secp operation
    assert reduce("kernel_ms_per_dispatch.commit", d) == pytest.approx(0.25)


def test_the_new_metrics_read_nothing_from_a_program_without_them():
    """The parent's side of a traced run: no ``secp.prologue``, neither
    counter series, the kernel under ``_ladder_call``'s old name: every new
    metric is left out, and none raises."""
    spans = [_span("bench.window", 0, 10_000_000),
             _span("verify.dispatch", 1_000_000, 5_000_000, span_id=1)]
    counters = {'tendermint_verify_sigs_total{backend="pallas",algo="secp256k1"}': 256.0,
                'tendermint_verify_valset_cache_total{cache="host",result="hit"}': 1.0}
    ops_list = [["%_ladder_call.1 = u32[1,256] custom-call", 4_000_000, 800_000, 0]]
    bench = harness.Bench(ROOT)
    new = [m["name"] for m in bench.spec["per_layer"]
           if m["name"].endswith(".secp") and m["workloads"] == ["secp256-stream"]]
    assert len(new) >= 17
    for ops_ in (ops_list, None):
        d = _data(spans, counters, ops_)
        for name in ("prologue_ms.secp", "host_decided_lanes.secp",
                     "pubkey_cache_miss_ratio.secp", "kernel_ms_per_dispatch.secp",
                     "kernel_mul_rate.secp", "kernel_hbm_share.secp"):
            assert d.cell.reduce(name, d) is None, name
        for name in new:  # the twins read the parent's own spans, or nothing
            d.cell.reduce(name, d)


def test_a_program_whose_host_oracle_takes_lax_der_is_not_timed(tiny_root, monkeypatch):
    """The parent of the PR that added this cell: its ``der_decode_sig`` took
    a padded r, in the prologue and in the guard's oracle alike, so the audit
    could not have caught it.  Set-up refuses such a program outright."""
    from tendermint_tpu.crypto import secp256k1 as program

    def lax(sig):  # what the parent's parser did: lengths only
        if len(sig) < 8 or sig[0] != 0x30 or sig[1] != len(sig) - 2 or sig[2] != 2:
            return None
        rl = sig[3]
        if sig[4 + rl] != 2 or 6 + rl + sig[5 + rl] != len(sig):
            return None
        return (int.from_bytes(sig[4:4 + rl], "big"),
                int.from_bytes(sig[6 + rl:], "big"))

    monkeypatch.setattr(program, "der_decode_sig", lax)
    with pytest.raises(RuntimeError, match="lax_der signature .* cannot run commit-secp256k1-16"):
        _run(tiny_root)
