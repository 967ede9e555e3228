"""``sync64-churn`` whole on the CPU at a tiny size: 16 validators, 48 blocks,
a change every 8, through ``BlockchainReactor`` with the host verifier behind
the guard; the chains against the reference, the stale signer, the control."""

import base64
import gc
import json
import os
import shutil
import time

import pytest

from benchmark import chaingen_churn, control_churn, harness
from benchmark import valset_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sync16-churn-tiny"
CHANGES = [10, 18, 26, 34, 42]  # blocks 8, 16, .., 40 bind two heights on


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

    put("configs/fastsync-16v-churn.json", dict(
        base.read_json("configs", "fastsync-64v-churn.json"),
        validators=16, name="fastsync-16v-churn"))
    put("traffic/tiny-churn.json", dict(
        base.read_json("traffic", "churn-blocks.json"), blocks=48,
        change_interval=8, chains=2, warmup_window_heights=[1, 4, 9],
        warmup_syncs=1, sync_timeout_s=30, forged_timeout_s=20))
    spec["configs"].append(
        {"name": "fastsync-16v-churn", "source": "test", "reduced": ["blocks"],
         "file": "benchmark/configs/fastsync-16v-churn.json", "why": "test"})
    spec["workloads"].append(
        {"name": CELL, "config": "fastsync-16v-churn", "traffic": "tiny-churn",
         "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "sync64-churn" in m.get("workloads", ()):
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


def _run(root, trace=False, device=None, seconds=0.8, seed=2**31 + 33):
    lines = []
    result = harness.run_cell(
        harness.Bench(root), CELL, seed, seconds, trace, "cpu", "cpu",
        lines.append, time.perf_counter(), device=device,
        trace_dir=os.path.join(root, ".bench_cache", "trace"))
    return result, lines


def _chain(root, seed=(2**31 + 33, 0)):
    cell = harness.Bench(root).cell(CELL)
    return cell, chaingen_churn.build_chain(cell.config, cell.traffic, list(seed))


def _updates(block):
    """A block's 'val:' transactions as (pubkey, power)."""
    out = []
    for tx in block.data.txs:
        pub, _, power = bytes(tx)[4:].partition(b"!")
        out.append((base64.b64decode(pub), int(power)))
    return out


def test_the_chain_is_the_references(tiny_root):
    from tendermint_tpu.blockchain.messages import unmarshal_msg

    cell, chain = _chain(tiny_root)
    assert chain.change_heights == CHANGES and chain.final_height == 47
    assert chain.keys == 16 + 5
    sets = ref.Evolution(chain.validators)
    blocks = [unmarshal_msg(r).block for r in chain.responses]
    for i, block in enumerate(blocks):
        h = block.header
        assert (h.validators_hash, h.next_validators_hash,
                h.proposer_address) == sets.header(), block.height
        assert len(sets.current.members()) == 16
        if i + 1 < len(blocks):
            # the commit for this height: the reference's set signs, in order
            pcs = blocks[i + 1].last_commit.precommits
            assert [pc.validator_address for pc in pcs] == [
                ref.address(p) for p, _ in sets.current.members()]
        updates = _updates(block)
        assert len(updates) == (6 if block.height % 8 == 0 and block.height < 47 else 0)
        if updates:
            powers = sorted(w for _, w in updates)
            assert powers[0] == 0 and 10 in powers[1:] and all(1 <= w <= 20 for w in powers[1:])
        sets.end_block(updates)
    assert sets.change_heights[: len(CHANGES)] == CHANGES
    # the same seed gives the same bytes, another seed other keys
    assert _chain(tiny_root)[1].responses == chain.responses
    assert _chain(tiny_root, (2**31 + 33, 1))[1].validators != chain.validators


def test_the_cell_syncs_to_the_generators_sets(tiny_root):
    result, lines = _run(tiny_root)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["metrics"]["sync_blocks_per_s"]["value"] > 0
    names = [c["name"] for c in result["checks"]]
    assert "stale_signer.stops_and_punishes" in names
    assert "forged_precommit.stops_and_punishes" in names
    assert any(n.startswith("syncs.final_state_vs_generator_over_") for n in names)
    assert any("signing for the one that joined at height 10" in ln for ln in lines)
    assert any(ln.startswith("warmup: window programs by heights 1:") for ln in lines)

    traced, lines = _run(tiny_root, trace=True)
    assert traced["correct"] is True, lines
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert got["valset_changes_per_sync.churn"] == len(CHANGES)
    assert 0.5 <= got["cut_window_share.churn"] <= 1.0
    assert got["discarded_windows.sync"] > 0 and got["discard_ms_per_window.churn"] >= 0
    assert got["speculative_window_share.sync"] < 50.0
    assert got["valset_cache_clears_per_sync.churn"] == 0  # the host verifier has no cache
    assert "valset_miss_ms_per_window.churn" not in got  # nor a miss to span
    assert 0 < got["heights_per_dispatch.sync"] <= 9.0
    assert got["apply_ms_per_block.sync"] > 0


def test_a_device_that_keeps_the_first_set_comes_out_not_correct(tiny_root):
    device = control_churn.make_device("cpu", "stale", 16)
    result, lines = _run(tiny_root, device=device, seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1, lines
    failed = {c["name"] for c in result["checks"] if not c["ok"]}
    assert failed & {"window.audit_mismatch", "window.device_fallback_total"} or any(
        n.startswith("syncs.final_state") for n in failed), failed
    # up to the first change its answers are the sound ones
    chain = _chain(tiny_root)[1]
    from tendermint_tpu.blockchain.messages import unmarshal_msg
    from tendermint_tpu.blockchain.reactor import verify_block_window
    from tendermint_tpu.state.state_types import state_from_genesis

    blocks = [unmarshal_msg(r).block for r in chain.responses[:12]]
    fresh = control_churn.make_device("cpu", "stale", 16)
    n_ok, err = verify_block_window(
        state_from_genesis(chain.genesis()), blocks, verifier=fresh)
    assert (n_ok, err) == (9, None)


def test_the_stale_signer_is_a_good_signature_by_a_key_the_set_has_not(tiny_root):
    from benchmark import oracle
    from tendermint_tpu.blockchain.messages import unmarshal_msg

    chain = _chain(tiny_root)[1]
    height, responses = chaingen_churn.stale_signer(chain)
    assert height == CHANGES[0]
    honest = unmarshal_msg(chain.responses[height]).block.last_commit
    stale = unmarshal_msg(responses[height]).block.last_commit
    slot = chain.stale_slot
    differ = [i for i, (a, b) in enumerate(zip(honest.precommits, stale.precommits))
              if a.signature != b.signature]
    assert differ == [slot] and honest.block_id == stale.block_id
    sets = ref.Evolution(chain.validators)
    blocks = [unmarshal_msg(r).block for r in chain.responses[:height]]
    before = None
    for block in blocks[: height - 1]:
        before = sets.current.members()
        sets.end_block(_updates(block))
    now = sets.current.members()
    (left,) = {p for p, _ in before} - {p for p, _ in now}
    msg = stale.precommits[slot].sign_bytes(chain.chain_id)
    assert oracle.verify(left, msg, stale.precommits[slot].signature)
    assert not oracle.verify(now[slot][0], msg, stale.precommits[slot].signature)
    assert oracle.verify(now[slot][0], msg, honest.precommits[slot].signature)
