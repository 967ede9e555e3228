"""``sync64-full`` whole on the CPU at a tiny size: 16 validators, 12 blocks
of 40 txs, through ``BlockchainReactor`` with the host verifier behind the
guard; the chain against the reference, the forged transaction, the control."""

import gc
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import chaingen_full, control, harness
from benchmark import kvstore_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sync16-full-tiny"
SEED = 2**31 + 43
BLOCKS, TXS = 12, 40


@pytest.fixture()
def tiny_root(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    base = harness.Bench(ROOT)

    def put(rel, obj):
        with open(root / "benchmark" / rel, "w") as f:
            json.dump(obj, f)

    put("configs/fastsync-16v-full.json", dict(
        base.read_json("configs", "fastsync-64v-full.json"),
        validators=16, name="fastsync-16v-full"))
    put("traffic/tiny-full.json", dict(
        base.read_json("traffic", "full-blocks.json"), blocks=BLOCKS,
        txs_per_block=TXS, warmup_window_heights=[1, 4, 9],
        warmup_syncs=1, sync_timeout_s=30, forged_timeout_s=20))
    spec["configs"].append(
        {"name": "fastsync-16v-full", "source": "test", "reduced": ["blocks"],
         "file": "benchmark/configs/fastsync-16v-full.json", "why": "test"})
    spec["workloads"].append(
        {"name": CELL, "config": "fastsync-16v-full", "traffic": "tiny-full",
         "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "sync64-full" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    yield str(root)
    gc.unfreeze()
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import breaker, trace

    trace.disable()
    breaker.reset_device_guard()
    batch.set_batch_verifier(batch.HostBatchVerifier())


def _run(root, trace=False, device=None, seconds=0.8):
    lines = []
    result = harness.run_cell(
        harness.Bench(root), CELL, SEED, seconds, trace, "cpu", "cpu",
        lines.append, time.perf_counter(), device=device,
        trace_dir=os.path.join(root, ".bench_cache", "trace"))
    return result, lines


def _chain(root, seed=SEED):
    cell = harness.Bench(root).cell(CELL)
    return cell, chaingen_full.build_chain(cell.config, cell.traffic, seed)


def test_the_chain_is_the_references(tiny_root):
    from tendermint_tpu.blockchain.messages import unmarshal_msg

    cell, chain = _chain(tiny_root)
    assert chain.final_height == BLOCKS - 1 and len(chain.responses) == BLOCKS
    assert chain.size == TXS * (BLOCKS - 1) and chain.txs_per_block == TXS
    kv = ref.KVStore()
    want_app, want_results = b"", b""
    for h, response in enumerate(chain.responses, 1):
        block = unmarshal_msg(response).block
        txs = [bytes(tx) for tx in block.data.txs]
        assert txs == chaingen_full.block_txs(SEED, h, cell.traffic)
        assert len(txs) == TXS and all(len(tx) == 250 for tx in txs)
        head = block.header
        assert (head.data_hash, head.app_hash, head.last_results_hash) == (
            ref.data_hash(txs), want_app, want_results), h
        parts = block.make_part_set().header()
        assert (parts.total, parts.hash) == ref.part_set_header(ref.block_bytes(response))
        for tx in txs:
            kv.deliver(tx)
        want_app, want_results = kv.app_hash(), ref.results_hash(TXS)
    # a sync ends one block short of the tip: its answers are the reference's
    short = chaingen_full.reference_run(SEED, cell.traffic, BLOCKS - 1)
    assert chain.app_hash_reference == short.app_hash() == ref.put_varint(chain.size)
    assert len(chain.queries) == min(chaingen_full.QUERIED_KEYS, chain.size)
    assert all(short.query(k) == v and v for k, v in chain.queries)
    assert len(chain.probe_heights) == chaingen_full.PROBED_HEIGHTS
    assert all(1 <= h <= chain.final_height for h in chain.probe_heights)
    # the same seed gives the same bytes, another seed another chain
    assert _chain(tiny_root)[1].responses == chain.responses
    assert _chain(tiny_root, SEED + 1)[1].validators != chain.validators


def test_the_chain_cache_gives_the_chain_back(tiny_root, tmp_path):
    cell = harness.Bench(tiny_root).cell(CELL)
    said = []
    args = (str(tmp_path / "cache"), "cfg", "traffic", cell.config, cell.traffic, SEED)
    made = chaingen_full.cached_chain(*args, said.append)
    held = chaingen_full.cached_chain(*args, said.append)
    assert "chain cache hit" in said[-1] and "chain cache hit" not in said[0]
    assert isinstance(held, chaingen_full.FullChain)
    for key in ("responses", "validators", "final_height", "app_hash",
                "app_hash_reference", "validators_hash", "size", "queries",
                "probe_heights", "txs_per_block"):
        assert getattr(held, key) == getattr(made, key), key


def test_the_generator_stops_on_a_header_the_reference_does_not_compute(
        tiny_root, monkeypatch):
    """An app whose Commit is not the reference's: block 2's header carries
    its hash, and the chain is never made."""
    from tendermint_tpu.abci.examples import kvstore

    monkeypatch.setattr(kvstore, "UpstreamKVStoreApp", kvstore.KVStoreApp)
    cell = harness.Bench(tiny_root).cell(CELL)
    with pytest.raises(RuntimeError, match="at height 2 the program's data hash, app hash"):
        chaingen_full.build_chain(cell.config, cell.traffic, SEED)


def test_the_cell_syncs_to_the_references_answers(tiny_root):
    result, lines = _run(tiny_root)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["metrics"]["sync_blocks_per_s"]["value"] > 0
    names = [c["name"] for c in result["checks"]]
    assert len(names) == 14 and all(c["limit"] == 0 for c in result["checks"])
    for want in ("forged_precommit.stops_and_punishes", "forged_tx.stops_and_punishes",
                 "forged_tx.fallbacks_and_audit_mismatches",
                 "syncs.app_size_and_hash_vs_reference", "syncs.query_keys_vs_reference",
                 "syncs.abci_responses_vs_reference", "syncs.stored_blocks_vs_reference",
                 f"window.txs_delivered_a_block_applied_is_{TXS}"):
        assert want in names
    assert any(n.startswith("syncs.final_state_vs_generator_over_") for n in names)
    # both forged syncs are this driver's patient one (it says the app's size)
    for what in ("check: forged tx in block ", "check: forged precommit at height "):
        assert any(ln.startswith(what) and "app size" in ln for ln in lines), what

    traced, lines = _run(tiny_root, trace=True)
    assert traced["correct"] is True, lines
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert got["txs_per_block.full"] == TXS
    assert 40 * 250 < got["intake_bytes_per_block.full"] < 40 * 250 + 16 * 200 + 1500
    stages = [got[n] for n in (
        "validate_ms_per_block.full", "deliver_ms_per_block.full",
        "save_responses_ms_per_block.full", "update_state_ms_per_block.full",
        "app_commit_ms_per_block.full", "save_state_ms_per_block.full",
        "save_block_ms_per_block.full")]
    assert all(v > 0 for v in stages)
    # the stages are read inside the span, so they cannot pass it
    assert sum(stages) <= got["apply_ms_per_block.sync"] * 1.001
    assert sum(stages) >= got["apply_ms_per_block.sync"] * 0.7
    assert 0 < got["heights_per_dispatch.sync"] <= BLOCKS - 1


def test_an_all_true_device_comes_out_not_correct(tiny_root):
    device = control.make_device("cpu", "null")
    result, lines = _run(tiny_root, device=device, seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1, lines
    failed = {c["name"] for c in result["checks"] if not c["ok"]}
    assert failed & {"forged_precommit.stops_and_punishes",
                     "forged_precommit.fallbacks_and_audit_mismatches",
                     "window.audit_mismatch", "window.device_fallback_total"}, failed
    # the all-true device does not carry a forged transaction through: the
    # block id is compared on the host, before any lane goes down
    assert "forged_tx.stops_and_punishes" not in failed


def test_a_forged_tx_is_one_bit_of_one_tx_and_is_never_applied(tiny_root):
    from tendermint_tpu.blockchain.messages import unmarshal_msg
    from tendermint_tpu.blockchain.reactor import verify_block_window
    from tendermint_tpu.state.state_types import state_from_genesis

    cell, chain = _chain(tiny_root)
    height = 5
    forged = chaingen_full.forge_tx(
        chain, SEED, cell.traffic, height, np.random.default_rng(7))
    honest = chain.responses[height - 1]
    differ = [i for i, (a, b) in enumerate(zip(honest, forged)) if a != b]
    assert len(forged) == len(honest) and len(differ) == 1
    assert bin(honest[differ[0]] ^ forged[differ[0]]).count("1") == 1
    a, b = unmarshal_msg(honest).block, unmarshal_msg(forged).block
    changed = [i for i, (x, y) in enumerate(zip(a.data.txs, b.data.txs)) if x != y]
    assert len(changed) == 1 and a.header == b.header
    # neither hash of the bytes is what the chain signed
    assert ref.data_hash([bytes(t) for t in b.data.txs]) != b.header.data_hash
    assert ref.part_set_header(ref.block_bytes(forged)) != ref.part_set_header(
        ref.block_bytes(honest))
    responses = list(chain.responses)
    responses[height - 1] = forged
    blocks = [unmarshal_msg(r).block for r in responses]
    n_ok, err = verify_block_window(state_from_genesis(chain.genesis()), blocks)
    assert n_ok == height - 1 and err is not None and err.bad_index == height - 1
    with pytest.raises(ValueError, match="wrong DataHash"):
        b.validate_basic()
