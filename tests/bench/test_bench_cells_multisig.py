"""The multisig commit cell whole, on the CPU at 8 validators keyed 2-of-3: a
cell made of the files this deployment added, its controls, its generator
and its metric files."""

import gc
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import chaingen_multisig as gen
from benchmark import control, control_multisig, harness
from benchmark import oracle_multisig as oracle

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "msig8-tiny"
TINY = {"validators": 8, "multisig": {"k": 2, "n": 3, "sub_key_type": "ed25519"}}
TINY_TRAFFIC = {"ring": 6, "warmup_calls": 8, "signer_counts": {"2": 0.6, "3": 0.4},
                "lanes_per_commit": [16, 24]}


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout's benchmark files plus ``commit-multisig-1k`` cut to 8
    validators 2-of-3, in a cell that reports whatever ``msig1k-stream`` does."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    base = harness.Bench(ROOT)
    cfg = dict(base.read_json("configs", "commit-multisig-1k.json"),
               name="commit-multisig-8", **TINY)
    with open(root / "benchmark" / "configs" / "commit-multisig-8.json", "w") as f:
        json.dump(cfg, f)
    traffic = dict(base.read_json("traffic", "height-stream-multisig.json"),
                   **TINY_TRAFFIC)
    with open(root / "benchmark" / "traffic" / "tiny-stream-multisig.json", "w") as f:
        json.dump(traffic, f)
    spec["configs"].append(
        {"name": "commit-multisig-8", "source": "test", "reduced": [],
         "file": "benchmark/configs/commit-multisig-8.json", "why": "test"})
    spec["workloads"].append(
        {"name": CELL, "config": "commit-multisig-8",
         "traffic": "tiny-stream-multisig", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "msig1k-stream" in m.get("workloads", ()):
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


def _run(root, trace=False, device=None, seconds=0.4, seed=2**31 + 17):
    lines = []
    result = harness.run_cell(
        harness.Bench(root), CELL, seed, seconds, trace, "cpu", "cpu",
        lines.append, time.perf_counter(), device=device,
        trace_dir=os.path.join(root, ".bench_cache", "trace"))
    return result, lines


def test_the_multisig_cell_runs_and_is_correct_traced_and_untraced(tiny_root):
    result, lines = _run(tiny_root)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert {"verify_p50_ms", "setup_s"} <= set(result["metrics"])
    checks = result["checks"]
    assert len(checks) == 15 and all(c["ok"] and c["limit"] == 0.0 for c in checks), checks
    names = [c["name"] for c in checks]
    assert "tampered.verdict_vs_reference_over_9" in names
    assert "validators.ring_vs_reference_over_6x8" in names
    assert "window.flattened_validators_and_lanes_vs_generator" in names
    assert "window.dispatches_vs_one_ed25519_a_call" in names
    assert any(n.startswith("lanes.ring_vs_reference_over_6_commits_") for n in names)
    assert any("8 precommits 2-of-3" in ln for ln in lines)
    # the tail is read per layer on the host's clock, in untraced runs too
    clock = result["per_layer_host_clock"]
    assert set(clock) == {"call_p90_ms.msig"}
    assert clock["call_p90_ms.msig"]["value"] >= result["metrics"]["verify_p50_ms"]["value"]

    traced, lines = _run(tiny_root, trace=True)
    assert traced["correct"] is True, lines
    got = traced["metrics"]
    assert 2.0 <= got["lanes_per_validator.msig"]["value"] <= 3.0
    assert got["host_decided_validators.msig"]["value"] == 0
    assert got["flatten_ms.msig"]["value"] > 0
    assert got["group_reduce_ms.msig"]["value"] > 0
    assert got["audit_lanes_per_dispatch.commit"]["value"] in (1.0, 2.0)  # ceil(5 % of 16-24)
    assert got["compiles_in_window.commit"]["value"] == 0
    assert got["dispatch_ms.commit"]["value"] > 0
    assert got["collect_ms.commit"]["value"] > 0 and got["tally_ms.commit"]["value"] > 0
    assert got["generic_self_ms.commit"]["value"] > 0
    # the host verifier stands in for the device here: no valset cache, no
    # device plane, so nothing is printed under those names
    assert "valset_miss_ms_per_call.msig" not in got
    assert "valset_cache_miss_ratio.msig" not in got
    assert not any(k.startswith(("kernel_", "device_idle")) for k in got)


@pytest.mark.parametrize("kind", ["null", "flip", "first_of_group"])
def test_a_broken_verifier_comes_out_not_correct(tiny_root, kind):
    device = control_multisig.make_device("cpu", kind)
    result, lines = _run(tiny_root, device=device, seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1, lines
    failed = [c["name"] for c in result["checks"] if not c["ok"]]
    assert failed
    if kind == "first_of_group":  # valid commits pass; a later lane goes through
        assert "tampered.lanes_vs_reference" in failed
        assert "warmup.rejected_valid_commits" not in failed


def test_the_ed25519_controls_break_this_deployment_too(tiny_root):
    """Every sub-signature rides ``verify_ed25519``, which ``control.py``'s
    stand-in alters: its ``null`` cannot pass here."""
    result, lines = _run(tiny_root, device=control.make_device("cpu", "null"),
                         seconds=0.3)
    assert result["correct"] is False, lines


def _tiny_inputs(seed=2**31 + 5, validators=8):
    bench = harness.Bench(ROOT)
    cell = bench.cell("msig1k-stream")
    cfg = dict(cell.config, **dict(TINY, validators=validators))
    traffic = dict(cell.traffic, **TINY_TRAFFIC)
    ks = gen.make_keyset(cfg, seed)
    heights = gen.make_heights(traffic, seed)
    for at in heights:  # a stand-in for the program's sign-bytes template
        at.head, at.tail = b"h%d|" % at.height + at.block_hash, b"|" + at.parts_hash
    return cell, ks, gen.sign_ring(ks, heights, traffic, seed)


def test_same_seed_same_inputs():
    _, ks_a, a = _tiny_inputs()
    _, ks_b, b = _tiny_inputs()
    _, ks_c, c = _tiny_inputs(seed=2**31 + 6)
    assert ks_a.keys == ks_b.keys and [x.sigs for x in a] == [x.sigs for x in b]
    assert ks_a.keys != ks_c.keys and a[0].sigs != c[0].sigs
    assert [x.at.height for x in a] == [500, 501, 502, 503, 504, 505]
    # validator-set order, keys of 8 + 3 x 59 bytes, all sub-keys distinct
    import hashlib

    addrs = [hashlib.sha256(k).digest()[:20] for k in ks_a.keys]
    assert addrs == sorted(addrs) and all(len(k) == 185 for k in ks_a.keys)
    assert len({s.pub for g in ks_a.signers for s in g}) == 24
    # which sub-keys sign differs from height to height
    flagged = {tuple(oracle.parse_signature(x.sigs[v])[1] for v in range(8)) for x in a}
    assert len(flagged) == len(a)


def test_signer_counts_follow_the_traffic_file():
    cell, ks, ring = _tiny_inputs(validators=400)
    counts = [len(oracle.parse_signature(s)[2]) for pre in ring for s in pre.sigs]
    assert set(counts) == {2, 3}
    assert 0.36 < counts.count(3) / len(counts) < 0.44
    assert cell.traffic["signer_counts"] == {"3": 0.6, "4": 0.3, "5": 0.1}
    assert cell.traffic["ring"] == 72 > 64  # more than either valset cache holds


def test_the_window_goes_on_where_the_warm_up_stopped(monkeypatch):
    """The window's call j takes ``ring[j % 72]``.  Begun again at ``ring[0]``
    it would find, in both valset caches, the key arrays of the warm-up's
    last 20 calls (a height seen twice within twenty calls, 40 hits a run: the
    builder's first chip run).  ``warmup`` turns the ring; over the program's
    own cache sizes and policy (emptied whole when full) no call then hits."""
    from types import SimpleNamespace

    from benchmark.drivers import commit_stream_multisig as drv
    from tendermint_tpu.ops import ed25519_pallas as program

    traffic = harness.Bench(ROOT).cell("msig1k-stream").traffic
    n, calls = traffic["ring"], traffic["warmup_calls"]
    assert calls == 20 + n  # 20 calls, then one lap
    seen = []
    monkeypatch.setattr(drv, "_base", lambda ctx: SimpleNamespace(
        warmup=lambda ctx, state: seen.extend(
            state["ring"][j % n] for j in range(calls))))
    state = {"ring": list(range(n)), "lanes": [3000 + i for i in range(n)]}
    drv.warmup(SimpleNamespace(traffic=traffic), state)
    assert state["ring"][0] == (seen[-1] + 1) % n == 20
    assert state["lanes"] == [3000 + i for i in state["ring"]]
    for size in (program._VALSET_CACHE_MAX, program._DEV_VALSET_CACHE_MAX):
        cache, hits = set(), 0
        for j, key in enumerate(seen + [state["ring"][j % n] for j in range(1500)]):
            if key in cache:
                hits += j >= len(seen)
                continue
            if len(cache) >= size:
                cache.clear()
            cache.add(key)
        assert hits == 0 and n > size


def test_each_tamper_is_what_its_name_says():
    cell, ks, ring = _tiny_inputs(validators=20)
    base = ring[1]
    known, stands = gen.reference_verdicts(base)
    assert stands and all(v.ok for v in known)
    want_rule = {"bad_subsignature": "bad_subsignature", "subsigs_swapped": "bad_subsignature",
                 "under_threshold": "under_threshold", "too_many_sigs": "too_many_sigs",
                 "wrong_size": "wrong_size", "flag_without_sig": "flag_without_sig",
                 "unflagged_signer": "bad_subsignature", "wrong_block_id": None,
                 "under_quorum": None}
    assert list(want_rule) == cell.traffic["tampers"]
    for kind, rule in want_rule.items():
        case, v = gen.tamper(base, ks, kind, np.random.default_rng(4))
        verdicts, stands = gen.reference_verdicts(case, known, base)
        assert not stands, kind
        bad = [i for i, x in enumerate(verdicts) if x is not None and not x.ok]
        if rule is None:
            assert bad == [] and v == -1
        else:
            assert bad == [v] and verdicts[v].rule == rule, (kind, verdicts[v].rule)
        if kind == "bad_subsignature":
            assert verdicts[v].lane_ok.count(False) == 1
        if kind == "subsigs_swapped":
            assert verdicts[v].lane_ok.count(False) == 2
        if kind == "unflagged_signer":  # valid, by a key whose bit is not set
            size, elems, subs = oracle.parse_signature(case.sigs[v])
            outsiders = [s.pub for i, s in enumerate(ks.signers[v])
                         if not oracle.get_index(elems, size, i)]
            assert any(oracle.oracle.verify(p, case.msgs[v], subs[-1]) for p in outsiders)
            assert verdicts[v].lane_ok == [True] * (len(subs) - 1) + [False]
        if kind == "too_many_sigs":
            size, _, subs = oracle.parse_signature(case.sigs[v])
            assert len(subs) == size + 1
        if kind == "under_threshold":
            size, elems, subs = oracle.parse_signature(case.sigs[v])
            assert sum(oracle.get_index(elems, size, i) for i in range(size)) == ks.k - 1
            assert len(subs) == ks.k
        if kind == "under_quorum":  # exactly two thirds, 15 % absent
            present = sum(p for p, s in zip(case.powers, case.sigs) if s)
            assert present * 3 == sum(case.powers) * 2
            assert case.sigs.count(None) == 3


# ---------------------------------------------------------------------------
# the new metric files, reduced as a run reduces them
# ---------------------------------------------------------------------------


def _data(spans, counters):
    bench = harness.Bench(ROOT)
    return harness.RunData(
        bench=bench, cell=bench.cell("msig1k-stream"), device_kind="TPU v5 lite",
        samples={}, totals={}, spans=spans, counters=counters, trace=None)


def _span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "tid": 1, "args": args}


NEW = ("flatten_ms.msig", "group_reduce_ms.msig", "lanes_per_validator.msig",
       "host_decided_validators.msig", "valset_miss_ms_per_call.msig",
       "valset_cache_miss_ratio.msig")


def test_the_new_metrics_read_their_spans_and_counters():
    fam = "tendermint_verify_"
    spans = [
        _span("bench.window", 0, 100_000_000),
        _span("multisig.flatten", 1_000_000, 7_000_000, validators=1000, lanes=3500),
        _span("verify.dispatch", 8_000_000, 30_000_000),
        _span("valset.miss", 8_100_000, 13_100_000, cache="host"),
        _span("valset.miss", 15_000_000, 17_000_000, cache="device"),
        _span("multisig.reduce", 31_000_000, 33_000_000, groups=1000),
        _span("multisig.flatten", 41_000_000, 49_000_000, validators=1000, lanes=3520),
        _span("verify.dispatch", 50_000_000, 70_000_000),
        _span("valset.miss", 50_100_000, 55_100_000, cache="host"),
        _span("multisig.reduce", 71_000_000, 72_000_000, groups=1000),
        _span("multisig.flatten", 200_000_000, 900_000_000),  # outside the window
    ]
    counters = {
        fam + "multisig_groups_total": 2000.0,
        fam + "multisig_lanes_total": 7020.0,
        fam + 'host_fallback_total{reason="multisig_structural"}': 0.0,
        fam + 'host_fallback_total{reason="no_tpu"}': 1.0,  # not ours
        fam + 'valset_cache_total{cache="host",result="miss"}': 2.0,
        fam + 'valset_cache_total{cache="device",result="miss"}': 1.0,
        fam + 'valset_cache_total{cache="device",result="hit"}': 1.0,
    }
    d = _data(spans, counters)
    reduce = d.cell.reduce
    assert reduce("flatten_ms.msig", d) == pytest.approx(7.0)
    assert reduce("group_reduce_ms.msig", d) == pytest.approx(1.5)
    assert reduce("lanes_per_validator.msig", d) == pytest.approx(3.51)
    assert reduce("host_decided_validators.msig", d) == 0.0
    assert reduce("valset_miss_ms_per_call.msig", d) == pytest.approx(6.0)
    assert reduce("valset_cache_miss_ratio.msig", d) == pytest.approx(0.75)
    spec = {m["name"]: m for m in d.bench.spec["per_layer"]}
    assert all(spec[n]["workloads"] == ["msig1k-stream"] for n in NEW)


def test_the_new_metrics_read_nothing_from_a_run_that_flattened_no_validator():
    """The parent's side of a traced run, and the tiny ed25519 cells of
    ``test_bench_cells.py``, which list themselves on every entry that moves
    ``verify_p50_ms``: no multisig span, neither counter family, the
    ``multisig_structural`` series not exposed.  Every new metric is left
    out, and none raises."""
    spans = [_span("bench.window", 0, 10_000_000),
             _span("verify.dispatch", 1_000_000, 5_000_000)]
    counters = {'tendermint_verify_sigs_total{backend="host",algo="ed25519"}': 16.0}
    d = _data(spans, counters)
    for name in NEW:
        assert d.cell.reduce(name, d) is None, name
    d = _data([], {})
    for name in NEW:
        assert d.cell.reduce(name, d) is None, name


def test_a_program_without_the_len_sigs_bound_is_not_timed(tiny_root, monkeypatch):
    """The parent of the PR that added this cell (ROADMAP D13): its
    ``verify_bytes`` never looked at ``len(sigs) > n``, and the guard audits
    the device against it.  Set-up refuses such a program outright."""
    from tendermint_tpu.crypto import multisig as program

    def parents(self, msg, sig):
        ms = program.Multisignature.unmarshal(sig)
        if len(self.pubkeys) != ms.bitarray.bits or len(ms.sigs) < self.k:
            return False
        if ms.bitarray.count() > len(ms.sigs):
            return False
        j = 0
        for i in range(ms.bitarray.bits):
            if ms.bitarray.get_index(i):
                if not self.pubkeys[i].verify_bytes(msg, ms.sigs[j]):
                    return False
                j += 1
        return j >= self.k

    monkeypatch.setattr(program.PubKeyMultisigThreshold, "verify_bytes", parents)
    with pytest.raises(RuntimeError, match="True to a too_many_sigs signature .* "
                                           "cannot run commit-multisig-8"):
        _run(tiny_root)


def test_the_reference_and_the_generator_import_nothing_of_the_program():
    for name in ("oracle_multisig.py", "chaingen_multisig.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as f:
            code = [ln for ln in f if ln.lstrip().startswith(("import ", "from "))]
        assert code and not any("tendermint_tpu" in ln for ln in code), name


def test_the_churn_cell_keeps_its_five_entries():
    """What ``test_bench_churn_metrics.test_the_cell_and_its_entries`` holds
    of ``sync64-churn``, with the entries found by their ``workloads`` and not
    by their place at the list's end (conftest.py of this directory)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = harness.Bench(ROOT)
    names = [m["name"] for m in bench.cell("sync64-churn").per_layer]
    empty = [m["name"] for m in bench.cell("sync64-empty").per_layer]
    own = [m for m in spec["per_layer"] if m.get("workloads") == ["sync64-churn"]]
    assert len(empty) == 22 and [n for n in names if n in empty] == empty
    assert [n for n in names if n not in empty] == [m["name"] for m in own]
    assert len(own) == 5 and all(m["moves"] == "sync_blocks_per_s" for m in own)
    # and this PR's entries lie behind them, at the list's end
    mine = [m["name"] for m in spec["per_layer"] if m.get("workloads") == ["msig1k-stream"]]
    assert mine == [m["name"] for m in spec["per_layer"][-len(mine):]]
    assert set(mine) == set(NEW) | {"call_p90_ms.msig"}
