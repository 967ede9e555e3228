"""Whole runs on the CPU at a tiny size: a cell made only of new files,
the generator's chain syncing to the generator's state, and the controls
that have to come out ``correct: false``."""

import gc
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import control, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEW_DRIVER = '''"""A driver a later PR might add: the commit stream, two callers' worth."""
from benchmark.harness import Bench
import os
_base = Bench(os.environ["BENCH_TEST_REPO"]).module("drivers", "commit_stream")
setup, warmup, check = _base.setup, _base.warmup, _base.check
def window(ctx, state, seconds):
    win = _base.window(ctx, state, seconds)
    win.totals["calls_twice"] = 2 * win.totals["calls"]
    return win
'''
NEW_REDUCER = '''def reduce(args, data):
    return data.totals.get(args["total"])
'''


@pytest.fixture()
def tiny_root(tmp_path, monkeypatch):
    """A checkout's benchmark files plus a tiny configuration of each kind
    and one cell built from nothing but new files and new entries."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    monkeypatch.setenv("BENCH_TEST_REPO", ROOT)

    def put(rel, obj):
        with open(root / "benchmark" / rel, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    base = harness.Bench(ROOT)
    commit_cfg = dict(base.read_json("configs", "commit-ed25519-10k.json"),
                      validators=16, name="commit-ed25519-16")
    sync_cfg = dict(base.read_json("configs", "fastsync-64v.json"),
                    validators=4, name="fastsync-4v")
    put("configs/commit-ed25519-16.json", commit_cfg)
    put("configs/fastsync-4v.json", sync_cfg)
    put("traffic/tiny-stream.json", dict(
        base.read_json("traffic", "height-stream.json"),
        driver="commit_stream_twice", warmup_calls=4))
    put("traffic/tiny-blocks.json", dict(
        base.read_json("traffic", "empty-blocks.json"), blocks=17,
        txs_per_block=3, tx_bytes=250, warmup_window_heights=[1, 4, 16],
        warmup_syncs=1, sync_timeout_s=20, forged_timeout_s=20))
    put("drivers/commit_stream_twice.py", NEW_DRIVER)
    put("reducers/total_value.py", NEW_REDUCER)
    put("metrics/calls_twice.tiny.json",
        {"name": "calls_twice.tiny", "reducer": "total_value",
         "args": {"total": "calls_twice"}})
    spec["configs"] += [
        {"name": "commit-ed25519-16", "source": "test", "reduced": [],
         "file": "benchmark/configs/commit-ed25519-16.json", "why": "test"},
        {"name": "fastsync-4v", "source": "test", "reduced": ["blocks"],
         "file": "benchmark/configs/fastsync-4v.json", "why": "test"}]
    spec["workloads"] += [
        {"name": "commit16-tiny", "config": "commit-ed25519-16",
         "traffic": "tiny-stream", "chips": 1, "why": "test"},
        {"name": "sync4-tiny", "config": "fastsync-4v",
         "traffic": "tiny-blocks", "chips": 1, "why": "test"}]
    # each tiny cell is held to what the cell it is cut from is held to, and
    # reports a per-layer entry by the end-to-end metric the entry moves,
    # not by the suffix of its name
    tiny = {"commit10k-stream": "commit16-tiny", "sync64-empty": "sync4-tiny"}
    for m in spec["end_to_end"]:
        for full, cut in tiny.items():
            if full in m.get("workloads", ()):
                m["workloads"].append(cut)
    held = {m["name"]: m["workloads"] for m in spec["end_to_end"] if "workloads" in m}
    for m in spec["per_layer"]:
        m["workloads"] += [t for t in tiny.values() if t in held[m["moves"]]]
    spec["per_layer"].append(
        {"name": "calls_twice.tiny", "unit": "calls", "better": "higher",
         "source": "program_counter", "layer": "test", "moves": "verify_p50_ms",
         "workloads": ["commit16-tiny"]})
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


def _run(root, cell, trace=False, device=None, seconds=0.4, seed=2**31 + 11):
    lines = []
    import time

    result = harness.run_cell(
        harness.Bench(root), cell, seed, seconds, trace, "cpu", "cpu",
        lines.append, time.perf_counter(), device=device,
        trace_dir=os.path.join(root, ".bench_cache", "trace"))
    return result, lines


def test_a_cell_of_new_files_only_loads_and_runs(tiny_root):
    result, lines = _run(tiny_root, "commit16-tiny")
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] > 4
    assert set(result["metrics"]) == {"verify_p50_ms", "verify_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    # every number compared rides beside its limit under the result's last
    # key, a list, so that two checks of one name stay two; ``run.py`` prints
    # them as the last lines of standard error
    checks = result["checks"]
    assert list(result)[-1] == "checks" and len(checks) >= 6
    assert all(set(c) == {"name", "value", "limit", "ok"} for c in checks)
    assert all((c["value"], c["limit"], c["ok"]) == (0.0, 0.0, True) for c in checks)
    assert len({c["name"] for c in checks}) == len(checks)
    assert harness.Check(**checks[0]).line() == (
        f"check {checks[0]['name']}: value=0.0 limit=0.0 ok=True")
    assert any(ln.startswith("halves: ") for ln in lines)

    traced, lines = _run(tiny_root, "commit16-tiny", trace=True)
    assert traced["correct"] is True, lines
    got = traced["metrics"]
    assert got["calls_twice.tiny"]["value"] == 2 * traced["attempted"]
    assert got["audit_lanes_per_dispatch.commit"]["value"] == 1.0  # ceil(5 % of 16)
    assert got["compiles_in_window.commit"]["value"] == 0
    assert got["dispatch_ms.commit"]["value"] > 0
    assert got["host_outside_dispatch_ms.commit"]["value"] > 0
    # no device plane on the cpu: no device metric is printed under its name
    assert not any(k.startswith(("kernel_", "device_idle")) for k in got)


def test_the_generators_chain_syncs_to_the_generators_state(tiny_root):
    result, lines = _run(tiny_root, "sync4-tiny", seconds=0.6)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert any("forged precommit at height" in ln for ln in lines)
    traced, lines = _run(tiny_root, "sync4-tiny", trace=True, seconds=0.6)
    assert traced["correct"] is True, lines
    got = traced["metrics"]
    # the windows are the reactor's own: up to the 16 heights there are
    assert 0 < got["heights_per_dispatch.sync"]["value"] <= 16.0
    assert got["apply_ms_per_block.sync"]["value"] > 0
    assert got["window_verify_ms.sync"]["value"] > 0
    assert 0.0 <= got["speculative_window_share.sync"]["value"] <= 100.0
    assert any(ln.startswith("warmup: window programs by heights 1:") for ln in lines)


@pytest.mark.parametrize("cell", ["commit16-tiny", "sync4-tiny"])
@pytest.mark.parametrize("kind", ["null", "flip"])
def test_a_broken_verifier_comes_out_not_correct(tiny_root, cell, kind):
    """``null``: the all-true NullVerifier shape in the device's place.
    ``flip``: one answer altered where it is produced.  The rest of the run
    is the run's own code, minus the look for a chip."""
    device = control.make_device("cpu", kind)
    result, lines = _run(tiny_root, cell, device=device, seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1, lines
    # the result names what failed beside its limit
    failed = [c for c in result["checks"] if not c["ok"]]
    assert failed or any("rejected a valid commit" in ln for ln in lines)
    assert all(c["value"] != c["limit"] for c in failed)


def test_same_seed_same_inputs(tiny_root):
    from benchmark import chaingen

    bench = harness.Bench(tiny_root)
    cell = bench.cell("sync4-tiny")
    a = chaingen.build_chain_bytes(cell.config, cell.traffic, 2**31 + 5)
    b = chaingen.build_chain_bytes(cell.config, cell.traffic, 2**31 + 5)
    c = chaingen.build_chain_bytes(cell.config, cell.traffic, 2**31 + 6)
    assert a.responses == b.responses and a.app_hash == b.app_hash
    assert a.responses != c.responses
    path = chaingen.chain_cache_path(
        os.path.join(tiny_root, "cache"), "fastsync-4v", "tiny-blocks",
        cell.config, cell.traffic, 2**31 + 5)
    assert chaingen.load_chain(path) is None
    chaingen.save_chain(path, a)
    loaded = chaingen.load_chain(path)
    assert loaded.responses == a.responses
    assert (loaded.final_height, loaded.app_hash_reference, loaded.validators_hash) == \
        (a.final_height, a.app_hash_reference, a.validators_hash)
    other = chaingen.chain_cache_path(
        os.path.join(tiny_root, "cache"), "fastsync-4v", "tiny-blocks",
        cell.config, dict(cell.traffic, blocks=18), 2**31 + 5)
    assert other != path


def test_the_command_prints_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the command exits non-zero and prints no result line."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "commit10k-stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
