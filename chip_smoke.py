"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on ONE TPU chip, through the entry points a user
calls, at the repo's own BASELINE.json sizes, and checks every answer
against the host oracle:

  commit_verify  config 2: one 10,000-validator ed25519 Commit through
                 ValidatorSet.verify_commit with the process-default
                 verifier, then the same lanes with a seeded 1 % corrupted
                 (signature / message / public-key bytes) through
                 collect_commit_sigs + verify_generic — all 10,000 verdicts
                 equal HostBatchVerifier's.
  fast_sync      config 3, cut from 50,000 blocks to the Makefile's
                 bench_fastsync size: 2,048 blocks x 64 validators, window
                 512 (32,768 signatures per dispatch), through
                 blockchain/reactor.verify_block_window + BlockExecutor
                 .apply_block — final height, app_hash and last block id
                 equal the same replay under HostBatchVerifier; a second
                 pass with one seeded commit signature flipped stops at the
                 same height with the same error.
  fast_sync_full the same replay over a chain that carries transactions
                 (BENCHMARK.json's fastsync-64v-full, cut to 64 blocks):
                 1,000 seeded txs of 250 bytes a block through the kvstore
                 with the reference's Commit; the device replay equals the
                 host's and ends at the app hash the rule gives by hand, and
                 a block with one tx byte altered in its wire bytes is never
                 applied.
  secp256k1      config 4: a 256-validator secp256k1 commit through
                 verify_commit / verify_generic -> ops/secp256k1_pallas.
  multisig       config 5, cut from 1,000 validators to 100: a commit of
                 validators keyed 3-of-5 (PubKeyMultisigThreshold over
                 ed25519 sub-keys, a seeded 3, 4 or 5 of the five signing)
                 through verify_commit; every sub-signature rides ONE
                 ed25519 dispatch and no validator is decided on the host;
                 then seeded bad sub-signatures through verify_generic,
                 each validator's verdict equal to its key's verify_bytes.
  commit_absent  a live chain's commit (BENCHMARK.json's
                 commit-ed25519-10k-live, cut to 1,000 validators): 300
                 slots absent, 33 for nil, decoded from its wire bytes,
                 through verify_commit: two message lengths regrouped once
                 into two launches, its keys gathered by slot from the
                 membership's table (one fill); a second height, another
                 subset absent: a hit and no valset.miss, and every lane
                 through the table path equals the host's, five spoiled
                 lanes among them; every verdict equals the host's; the
                 same commit with one more precommit for nil is refused.
  ed25519_msm    one 512-signature window with ed25519_path="msm" (the RLC
                 seed is a hash of the seeded content, so it is pinned).
  node           a live node through the CLI, no TM_BATCH_VERIFIER in its
                 environment: height 5, three broadcast_tx_commit read back
                 by abci_query, /commit?height=3&verify=1, SIGTERM -> exit 0,
                 and its start-up program a compile-cache HIT on what the
                 kernel stages stored.

A stage passes only with backend == "pallas", >= 1 device dispatch, zero
device_fallback_total and host_fallback_total, zero audit mismatches and a
closed breaker.  Seconds are printed as set-up facts, never recorded as
performance numbers.

One process per chip: this parent never imports jax.  The kernel
stages share one child; the CLI node is a second child started after the
first has exited.  Without a TPU the first child says so and exits before
any stage; the command takes no flag.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")

SEED = 42
N_VALIDATORS = 10_000
CORRUPT_SHARE = 0.01
FASTSYNC_BLOCKS, FASTSYNC_VALS, FASTSYNC_WINDOW = 2048, 64, 512
FULL_BLOCKS, FULL_TXS, FULL_TX_BYTES = 64, 1000, 250
SECP_VALIDATORS = 256
MULTISIG_VALIDATORS, MULTISIG_K, MULTISIG_N = 100, 3, 5
ABSENT_VALIDATORS, ABSENT_ABSENT, ABSENT_NIL = 1000, 300, 33
MSM_WINDOW = 512
NODE_HEIGHT = 5
BACKEND = "pallas"  # what every stage must have run on

KERNELS_DEADLINE_S = 1020.0
NODE_DEADLINE_S = 240.0
KERNEL_STAGES = (
    "commit_verify", "fast_sync", "fast_sync_full", "secp256k1", "multisig",
    "commit_absent", "ed25519_msm")
STAGE_PREFIX = "STAGE "

NO_TPU_EXIT = 3


# ---------------------------------------------------------------------------
# The verdict, shared by both children's reports (pure: tests drive it)
# ---------------------------------------------------------------------------


def stage_problems(report: dict) -> list:
    """Why a stage report does not pass; empty when it does."""
    name = report.get("stage", "?")
    bad = []
    if report.get("error") or not report.get("ok"):
        bad.append(f"{name}: {report.get('error') or 'stage did not pass'}")
    if report.get("platform") != "tpu":
        bad.append(f"{name}: platform is {report.get('platform')!r}, not tpu")
    if report.get("backend") != BACKEND:
        bad.append(
            f"{name}: backend is {report.get('backend')!r}, not {BACKEND}")
    device = sum(
        n for k, n in report.get("dispatches", {}).items()
        if k.startswith(BACKEND + "/")
    )
    if device < 1:
        bad.append(f"{name}: no device dispatch")
    for key in ("device_fallback_total", "host_fallback_total"):
        for reason, n in report.get(key, {}).items():
            if n:
                bad.append(f"{name}: {key}{{reason={reason}}} = {n:g}")
    mismatches = report.get("device_audit_total", {}).get("mismatch", 0)
    if mismatches:
        bad.append(f"{name}: {mismatches:g} audit mismatches")
    if report.get("breaker_state") != "closed":
        bad.append(f"{name}: breaker is {report.get('breaker_state')!r}")
    return bad


def aggregate(reports: list, expected=KERNEL_STAGES + ("node",)) -> int:
    """Exit code for a run: 0 only if every expected stage reported and
    passed.  Prints one line per problem."""
    problems = []
    seen = {r.get("stage") for r in reports}
    for name in expected:
        if name not in seen:
            problems.append(f"{name}: no report (stage did not run to an end)")
    for r in reports:
        problems.extend(stage_problems(r))
    for p in problems:
        print(f"chip_smoke: FAIL {p}", file=sys.stderr)
    return 1 if problems else 0


def _delta(after: dict, before: dict) -> dict:
    out = {k: v - before.get(k, 0) for k, v in after.items()}
    return {k: v for k, v in out.items() if v}


# ---------------------------------------------------------------------------
# Child 1: the kernel stages (the only code here that imports jax)
# ---------------------------------------------------------------------------


def _flip(data: bytes, rng) -> bytes:
    i = rng.randrange(len(data) * 8)
    b = bytearray(data)
    b[i // 8] ^= 1 << (i % 8)
    return bytes(b)


def _compare_with_host(pubkeys, msgs, sigs, checks, verifier=None):
    """Lane-for-lane parity of the device verdicts with the host oracle."""
    import numpy as np

    from tendermint_tpu.crypto.batch import HostBatchVerifier, verify_generic

    got = verify_generic(pubkeys, msgs, sigs, verifier=verifier)
    want = verify_generic(pubkeys, msgs, sigs, verifier=HostBatchVerifier())
    diff = np.flatnonzero(got != want)
    checks["lanes"] = len(want)
    checks["rejected_host"] = int(np.count_nonzero(~want))
    checks["rejected_device"] = int(np.count_nonzero(~got))
    assert diff.size == 0, f"device != host in lanes {diff[:8].tolist()}"
    return want


def _corrupt(pubkeys, msgs, sigs, lanes, rng, rekey):
    """Seeded one-bit corruptions, cycling signature / message / key."""
    for k, lane in enumerate(lanes):
        if k % 3 == 0:
            sigs[lane] = _flip(sigs[lane], rng)
        elif k % 3 == 1:
            msgs[lane] = _flip(msgs[lane], rng)
        else:
            pubkeys[lane] = rekey(_flip(pubkeys[lane].bytes(), rng))


def stage_commit_verify(checks: dict) -> None:
    import math
    import random

    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.testutil.chain import build_commit

    chain_id, height = "smoke-commit", 500
    valset, block_id, commit = build_commit(
        N_VALIDATORS, seed=SEED, chain_id=chain_id, height=height)
    # no verifier= argument: the process default, chosen as a node chooses it
    valset.verify_commit(chain_id, block_id, height, commit)
    checks["verify_commit_accepted"] = True

    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        chain_id, block_id, height, commit)
    pubkeys, msgs, sigs = list(pubkeys), list(msgs), list(sigs)
    rng = random.Random(SEED)
    lanes = rng.sample(
        range(len(sigs)), math.ceil(len(sigs) * CORRUPT_SHARE))
    _corrupt(pubkeys, msgs, sigs, lanes, rng, PubKeyEd25519)
    want = _compare_with_host(pubkeys, msgs, sigs, checks)
    checks["corrupted"] = len(lanes)
    assert not want[lanes].any(), "host oracle accepted a corrupted lane"
    assert checks["rejected_host"] == len(lanes)
    _resident_lanes_against_host(
        valset, valset.collect_commit_sigs(chain_id, block_id, height, commit),
        lanes, want, rng, checks)


def _resident_lanes_against_host(valset, lanes_of, spoiled, want, rng, checks):
    """The same commit's lanes THROUGH THE MEMBERSHIP'S WINDOW TABLES (every
    slot present, so ``slots`` None: the ladder's resident form, which the
    accepted commit above ran and whose lanes no accepted commit tells
    apart), the same hundredth of them spoiled in the signature or the
    message: equal to the host's, which the built form's lanes were compared
    with above."""
    import numpy as np

    from tendermint_tpu.crypto.batch import verify_generic
    from tendermint_tpu.libs.metrics import get_verify_metrics

    pubkeys, msgs, sigs, _ = lanes_of
    msgs, sigs = list(msgs), list(sigs)
    for k, lane in enumerate(spoiled):
        if k % 2:
            msgs[lane] = _flip(msgs[lane], rng)
        else:
            sigs[lane] = _flip(sigs[lane], rng)
    rows = valset._valset_rows(valset._member_columns(), [])
    assert rows.slots is None
    counted = get_verify_metrics().ed25519_ladder_lanes
    before = counted.snapshot()
    got = verify_generic(pubkeys, msgs, sigs, valset=rows)
    moved = _delta(counted.snapshot(), before)
    refused = np.flatnonzero(~got).tolist()
    assert refused == sorted(spoiled), (
        f"resident lanes != the spoiled ones: {refused[:8]}")
    assert refused == np.flatnonzero(~want).tolist()
    assert set(moved) == {("resident",)}, moved
    checks["resident"] = {
        "ladder_lanes": int(moved[("resident",)]), "refused": len(refused)}


def _replay(genesis, blocks, verifier, **executor):
    """Windowed verify + apply, the way blockchain/reactor's sync loop does
    it.  Returns (height, app_hash, last block id, error)."""
    from tendermint_tpu.blockchain.reactor import verify_block_window
    from tendermint_tpu.testutil.chain import fresh_executor
    from tendermint_tpu.types import BlockID

    st, block_exec = fresh_executor(genesis, **executor)
    trusted, pos, err = set(), 0, None
    while pos < len(blocks) - 1 and err is None:
        window = blocks[pos : pos + FASTSYNC_WINDOW + 1]
        parts = []
        n_ok, err = verify_block_window(
            st, window, verifier=verifier, parts_out=parts)
        trusted.update(b.height for b in window[:n_ok])
        for block, part_set in zip(window[:n_ok], parts):
            block_id = BlockID(hash=block.hash(), parts_header=part_set.header())
            st = block_exec.apply_block(
                st, block_id, block,
                trusted_last_commit=block.height - 1 in trusted,
            )
        if n_ok == 0 and err is None:
            raise AssertionError(f"window at {pos} verified nothing")
        pos += n_ok
    return (
        st.last_block_height, st.app_hash.hex(), st.last_block_id.hash.hex(),
        str(err) if err is not None else None,
    )


def stage_fast_sync(checks: dict) -> None:
    import random

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.testutil.chain import build_chain
    from tendermint_tpu.types.block import Commit

    # set-up: the chain is data, built under the host oracle
    device_default = batch.get_batch_verifier()
    batch.set_batch_verifier(batch.HostBatchVerifier())
    try:
        fx = build_chain(
            n_vals=FASTSYNC_VALS, n_heights=FASTSYNC_BLOCKS,
            chain_id="smoke-sync", txs_per_block=2)  # app_hash moves
    finally:
        batch.set_batch_verifier(device_default)
    load = fx.block_store.load_block
    blocks = [load(h) for h in range(1, FASTSYNC_BLOCKS + 1)]

    host = _replay(fx.genesis, blocks, batch.HostBatchVerifier())
    device = _replay(fx.genesis, blocks, None)  # None: the default verifier
    checks["replay"] = {"device": device, "host": host}
    assert device == host, "device replay differs from the host replay"
    assert host[0] == FASTSYNC_BLOCKS - 1 and host[3] is None, host

    # one seeded commit signature flipped: the commit FOR height h_bad
    # travels in block h_bad + 1
    rng = random.Random(SEED)
    h_bad = rng.randrange(FASTSYNC_WINDOW + 2, FASTSYNC_BLOCKS - 1)
    j = rng.randrange(FASTSYNC_VALS)
    carrier = load(h_bad + 1)
    pcs = list(carrier.last_commit.precommits)
    pcs[j] = pcs[j].with_signature(_flip(pcs[j].signature, rng))
    carrier.last_commit = Commit(carrier.last_commit.block_id, pcs)
    tampered = list(blocks)
    tampered[h_bad] = carrier
    host_bad = _replay(fx.genesis, tampered, batch.HostBatchVerifier())
    device_bad = _replay(fx.genesis, tampered, None)
    checks["tampered"] = {
        "height": h_bad, "validator": j,
        "device": device_bad, "host": host_bad,
    }
    assert device_bad == host_bad, "tampered replays differ"
    assert host_bad[0] == h_bad - 1 and host_bad[3] is not None, host_bad


def stage_fast_sync_full(checks: dict) -> None:
    import random

    from tendermint_tpu.abci.examples.kvstore import UpstreamKVStoreApp
    from tendermint_tpu.blockchain.messages import (
        BlockResponseMessage, encode_msg, unmarshal_msg)
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.testutil.chain import build_chain

    rng = random.Random(SEED)
    half = (FULL_TX_BYTES - 1) // 2

    def txs(_h, _st):  # hex key, '=', hex value; every key distinct
        return [(rng.randbytes(half // 2).hex() + "="
                 + rng.randbytes(half // 2 + 1).hex())[:FULL_TX_BYTES].encode()
                for _ in range(FULL_TXS)]

    device_default = batch.get_batch_verifier()
    batch.set_batch_verifier(batch.HostBatchVerifier())
    try:
        fx = build_chain(
            n_vals=FASTSYNC_VALS, n_heights=FULL_BLOCKS, chain_id="smoke-full",
            app_factory=UpstreamKVStoreApp, on_height=txs)
    finally:
        batch.set_batch_verifier(device_default)
    # as a peer's bytes, decoded: the blocks a sync applies
    wire = [encode_msg(BlockResponseMessage(fx.block_store.load_block(h)))
            for h in range(1, FULL_BLOCKS + 1)]
    blocks = [unmarshal_msg(w).block for w in wire]
    checks["block_bytes"] = len(wire[1])
    assert all(len(b.data.txs) == FULL_TXS for b in blocks)
    assert all(len(bytes(tx)) == FULL_TX_BYTES for tx in blocks[0].data.txs)

    app = {"app_factory": UpstreamKVStoreApp}
    host = _replay(fx.genesis, blocks, batch.HostBatchVerifier(), **app)
    device = _replay(fx.genesis, blocks, None, **app)
    checks["replay"] = {"device": device, "host": host}
    assert device == host, "device replay differs from the host replay"
    assert host[0] == FULL_BLOCKS - 1 and host[3] is None, host
    # binary.PutVarint(make([]byte, 8), 63,000 txs), worked by hand
    assert (FULL_BLOCKS - 1) * FULL_TXS == 63_000
    assert host[1] == "b0d8070000000000", host[1]

    # one byte of one tx altered in the bytes of a seeded block
    # late enough that its window fills the lane bucket of the whole one
    h_bad = rng.randrange(FULL_BLOCKS // 2 + 2, FULL_BLOCKS - 1)
    raw = bytearray(wire[h_bad - 1])
    tx = bytes(blocks[h_bad - 1].data.txs[rng.randrange(FULL_TXS)])
    raw[raw.index(tx) + rng.randrange(FULL_TX_BYTES)] ^= 1 << rng.randrange(8)
    tampered = list(blocks)
    tampered[h_bad - 1] = unmarshal_msg(bytes(raw)).block
    host_bad = _replay(fx.genesis, tampered, batch.HostBatchVerifier(), **app)
    device_bad = _replay(fx.genesis, tampered, None, **app)
    checks["tampered"] = {"height": h_bad, "device": device_bad, "host": host_bad}
    assert device_bad == host_bad, "tampered replays differ"
    assert host_bad[0] == h_bad - 1 and host_bad[3] is not None, host_bad


def stage_secp256k1(checks: dict) -> None:
    import math
    import random

    from tendermint_tpu.crypto import secp256k1 as secp
    from tendermint_tpu.crypto.keys import PrivKeySecp256k1, PubKeySecp256k1
    from tendermint_tpu.libs.metrics import get_verify_metrics
    from tendermint_tpu.types import BlockID, PartSetHeader, SignedMsgType, Vote
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    chain_id, height = "smoke-secp", 9
    rng = random.Random(SEED)
    privs = [
        PrivKeySecp256k1.generate(rng.randbytes(32))
        for _ in range(SECP_VALIDATORS)
    ]
    by_addr = {p.pub_key().address(): p for p in privs}
    valset = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    block_id = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))
    votes = []
    for idx, val in enumerate(valset.validators):
        vote = Vote(
            vote_type=SignedMsgType.PRECOMMIT, height=height, round=0,
            timestamp_ns=1_700_000_000_000_000_000 + idx, block_id=block_id,
            validator_address=val.address, validator_index=idx,
        )
        votes.append(vote.with_signature(
            by_addr[val.address].sign(vote.sign_bytes(chain_id))))
    commit = Commit(block_id=block_id, precommits=votes)
    # what the cell secp256-stream asserts of every call in its window
    # (benchmark/drivers/commit_stream_secp256k1.py), asserted here of the
    # valid commit, so that the smoke and the cell cannot drift apart: every
    # verdict is the device's, ceil(5 %) lanes are audited, by the workers;
    # and the host prologue inverted once for all 256 lanes
    m = get_verify_metrics()
    watched = (m.secp256k1_host_decided, m.device_audit, m.audit_oracle,
               m.secp256k1_inversions)
    before = [c.snapshot() for c in watched]
    valset.verify_commit(chain_id, block_id, height, commit)
    checks["verify_commit_accepted"] = True
    decided, audited, where, inverted = (
        _delta(c.snapshot(), b) for c, b in zip(watched, before))
    want = math.ceil(SECP_VALIDATORS * 0.05)
    checks["valid_commit"] = {
        "host_decided_lanes": sum(decided.values()),
        "audited_lanes": sum(audited.values()),
        "audited_on_the_oracle_workers": where.get(("pool",), 0),
        "prologue_inversions": sum(inverted.values()),
    }
    assert not decided, f"host prologue decided lanes of a valid commit: {decided}"
    assert inverted == {(): 1}, f"prologue inversions of one dispatch: {inverted}"
    assert audited == {("ok",): want}, f"audit of the valid commit: {audited}"
    assert where == {("pool",): want}, f"audit oracle ran {where}, not on the workers"

    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        chain_id, block_id, height, commit)
    pubkeys, msgs, sigs = list(pubkeys), list(msgs), list(sigs)
    lanes = rng.sample(range(len(sigs)), 6)
    _corrupt(pubkeys, msgs, sigs, lanes[:5], rng, PubKeySecp256k1)
    # and one well-formed DER signature over the wrong scalar
    r, s = secp.der_decode_sig(sigs[lanes[5]])
    sigs[lanes[5]] = secp.der_encode_sig(r, s ^ 1)
    want = _compare_with_host(pubkeys, msgs, sigs, checks)
    checks["corrupted"] = len(lanes)
    assert not want[lanes].any(), "host oracle accepted a corrupted lane"


def stage_multisig(checks: dict) -> None:
    import random

    import numpy as np

    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.batch import verify_generic
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.crypto.multisig import (
        Multisignature,
        PubKeyMultisigThreshold,
    )
    from tendermint_tpu.libs.metrics import get_verify_metrics
    from tendermint_tpu.types import BlockID, PartSetHeader, SignedMsgType, Vote
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    chain_id, height = "smoke-multisig", 11
    rng = random.Random(SEED + 2)
    privs = {}  # a validator's address -> its five sub-keys' private keys
    vals = []
    for _ in range(MULTISIG_VALIDATORS):
        subs = [ed.gen_privkey(rng.randbytes(32)) for _ in range(MULTISIG_N)]
        key = PubKeyMultisigThreshold(
            MULTISIG_K, tuple(PubKeyEd25519(p[32:]) for p in subs))
        privs[key.address()] = subs
        vals.append(Validator(key, 10))
    valset = ValidatorSet(vals)
    block_id = BlockID(b"\x55" * 32, PartSetHeader(1, b"\x66" * 32))
    votes, signed = [], 0
    for idx, val in enumerate(valset.validators):
        vote = Vote(
            vote_type=SignedMsgType.PRECOMMIT, height=height, round=0,
            timestamp_ns=1_700_000_000_000_000_000 + idx, block_id=block_id,
            validator_address=val.address, validator_index=idx,
        )
        msg = vote.sign_bytes(chain_id)
        # which of the five devices answer differs from validator to validator
        signers = sorted(rng.sample(
            range(MULTISIG_N), rng.choice((3, 3, 3, 4, 4, 5))))
        ms = Multisignature.new(MULTISIG_N)
        for j in signers:
            ms.add_signature_from_pubkey(
                ed.sign(privs[val.address][j], msg), val.pub_key.pubkeys[j],
                val.pub_key.pubkeys)
        signed += len(signers)
        votes.append(vote.with_signature(ms.marshal()))
    commit = Commit(block_id=block_id, precommits=votes)
    # what the cell msig1k-stream asserts of every call in its window
    # (benchmark/drivers/commit_stream_multisig.py), asserted here of the
    # valid commit: every validator flattened, a lane a sub-signature, all
    # of them in ONE ed25519 dispatch, none decided on the host
    m = get_verify_metrics()
    watched = (m.multisig_groups, m.multisig_lanes, m.calls)
    before = [c.snapshot() for c in watched]
    valset.verify_commit(chain_id, block_id, height, commit)
    checks["verify_commit_accepted"] = True
    groups, lanes, calls = (
        _delta(c.snapshot(), b) for c, b in zip(watched, before))
    checks["valid_commit"] = {
        "flattened_validators": sum(groups.values()),
        "lanes": sum(lanes.values()),
        "dispatches": {"/".join(k): v for k, v in calls.items()},
    }
    assert sum(groups.values()) == MULTISIG_VALIDATORS, groups
    assert sum(lanes.values()) == signed, (lanes, signed)
    assert calls == {(BACKEND, "ed25519"): 1}, f"dispatches of one commit: {calls}"

    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        chain_id, block_id, height, commit)
    pubkeys, msgs, sigs = list(pubkeys), list(msgs), list(sigs)
    bad = rng.sample(range(len(sigs)), 5)
    for v in bad:  # one bit of one sub-signature: still flattened
        ms = Multisignature.unmarshal(sigs[v])
        j = rng.randrange(len(ms.sigs))
        ms.sigs[j] = _flip(ms.sigs[j], rng)
        sigs[v] = ms.marshal()
    got = verify_generic(pubkeys, msgs, sigs)
    want = np.array([pk.verify_bytes(m_, s) for pk, m_, s in
                     zip(pubkeys, msgs, sigs)], dtype=bool)
    diff = np.flatnonzero(got != want)
    checks["validators"] = len(want)
    checks["corrupted"] = len(bad)
    checks["rejected_host"] = int(np.count_nonzero(~want))
    checks["rejected_device"] = int(np.count_nonzero(~got))
    assert diff.size == 0, f"device != verify_bytes in validators {diff[:8].tolist()}"
    assert not want[bad].any() and checks["rejected_host"] == len(bad)


def stage_commit_absent(checks: dict) -> None:
    """A live chain's commit at the edge of its quorum, read from the wire:
    of 1,000 slots 300 absent and 33 for nil, so 667 for the block.  What
    the cell commit10k-absent asserts of every call in its window
    (benchmark/drivers/commit_stream_absent.py), asserted here of one."""
    import random

    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.batch import get_batch_verifier
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.libs.metrics import get_verify_metrics
    from tendermint_tpu.types import BlockID, PartSetHeader, SignedMsgType, Vote
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.validator_set import (
        CommitError,
        Validator,
        ValidatorSet,
    )

    chain_id, height = "smoke-absent", 500
    rng = random.Random(SEED + 3)
    privs = {}
    for _ in range(ABSENT_VALIDATORS):
        priv = ed.gen_privkey(rng.randbytes(32))
        privs[PubKeyEd25519(priv[32:]).address()] = priv
    valset = ValidatorSet(
        [Validator(PubKeyEd25519(p[32:]), 10) for p in privs.values()])
    block_id = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))

    def precommit(idx, voted):
        val = valset.validators[idx]
        vote = Vote(
            vote_type=SignedMsgType.PRECOMMIT, height=height, round=0,
            timestamp_ns=1_700_000_000_000_000_000 + idx, block_id=voted,
            validator_address=val.address, validator_index=idx,
        )
        return vote.with_signature(
            ed.sign(privs[val.address], vote.sign_bytes(chain_id)))

    def live_commit(order):
        absent = set(order[:ABSENT_ABSENT])
        nil = set(order[ABSENT_ABSENT:ABSENT_ABSENT + ABSENT_NIL])
        votes = [None if i in absent
                 else precommit(i, BlockID() if i in nil else block_id)
                 for i in range(ABSENT_VALIDATORS)]
        return votes, Commit.unmarshal(Commit(block_id, votes).marshal())

    order = rng.sample(range(ABSENT_VALIDATORS), ABSENT_VALIDATORS)
    votes, commit = live_commit(order)
    assert commit.precommits[order[-1]].block_id is not commit.block_id

    # the process default, chosen as a node chooses it; chosen here, because
    # its selection self-test is a launch of its own
    get_batch_verifier()
    m = get_verify_metrics()
    watched = (m.ed25519_pack, m.ed25519_launches, m.commit_precommits,
               m.valset_cache, m.ed25519_ladder_lanes)
    before = [c.snapshot() for c in watched]
    valset.verify_commit(chain_id, block_id, height, commit)
    checks["verify_commit_accepted"] = True
    pack, launches, held, caches, ladder = (
        _delta(c.snapshot(), b) for c, b in zip(watched, before))
    checks["valid_commit"] = {
        "pack": {"/".join(k): v for k, v in pack.items()},
        "launches": sum(launches.values()),
        "held": {"/".join(k): v for k, v in held.items()},
        "valset_cache": {"/".join(k): v for k, v in caches.items()},
        "ladder_lanes": {"/".join(k): v for k, v in ladder.items()},
    }
    # both launches read their lanes' window tables from the membership's
    assert set(ladder) == {("resident",)}, ladder
    assert pack == {("grouped",): 1}, f"packing of one commit: {pack}"
    assert sum(launches.values()) == 2, launches
    assert held == {
        ("for_block",): ABSENT_VALIDATORS - ABSENT_ABSENT - ABSENT_NIL,
        ("stray",): ABSENT_NIL, ("absent",): ABSENT_ABSENT}, held
    # the lanes are rows of the membership: its table is filled, once, and
    # neither whole-array cache is shown this height's subset of the keys
    assert caches == {("table", "miss"): 1}, caches
    _second_height_through_the_table(
        valset, chain_id, block_id, height,
        live_commit(rng.sample(range(ABSENT_VALIDATORS), ABSENT_VALIDATORS))[1],
        checks)

    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        chain_id, block_id, height, commit)
    assert len({len(x) for x in msgs}) == 2
    _compare_with_host(list(pubkeys), list(msgs), list(sigs), checks)
    assert checks["rejected_host"] == 0

    # one more precommit for nil: every lane verifies, one vote under
    votes[order[-1]] = precommit(order[-1], BlockID())
    under = Commit.unmarshal(Commit(block_id, votes).marshal())
    try:
        valset.verify_commit(chain_id, block_id, height, under)
        raise AssertionError("a commit one vote under two thirds was accepted")
    except CommitError as e:
        assert "insufficient voting power" in str(e), e
    checks["one_more_nil_refused"] = True


def _second_height_through_the_table(
        valset, chain_id, block_id, height, commit, checks) -> None:
    """The same set's next commit, another 300 slots absent and another 33
    for nil: served from the table the first one filled (a hit, no
    ``valset.miss`` span), and every lane's verdict THROUGH THE TABLE PATH,
    the membership's ``ValsetRows`` handed down with the lanes, equal to
    the host's: the device gather, lane for lane, which no verdict of an
    accepted commit shows."""
    import numpy as np

    from tendermint_tpu.crypto.batch import (
        HostBatchVerifier,
        verify_ed25519_columns,
        verify_generic,
    )
    from tendermint_tpu.libs import trace
    from tendermint_tpu.libs.metrics import get_verify_metrics

    cache = get_verify_metrics().valset_cache
    before = cache.snapshot()
    trace.reset()
    trace.enable()
    try:
        valset.verify_commit(chain_id, block_id, height, commit)
        spans = [e["name"] for e in trace.export() if e.get("ph") == "X"]
    finally:
        trace.disable()
        trace.reset()
    moved = _delta(cache.snapshot(), before)
    assert moved == {("table", "hit"): 1}, moved
    assert "valset.miss" not in spans and spans.count("dispatch.launch") == 2, spans

    # the lanes as verify_commit sends them (lists of two lengths: one call
    # regrouped, the slots split between its two launches), five of them
    # spoiled, one a lane for nil
    absent = [i for i, pc in enumerate(commit.precommits) if pc is None]
    rows = valset._valset_rows(valset._member_columns(), absent)
    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        chain_id, block_id, height, commit)
    sigs = list(sigs)
    short = min(range(len(msgs)), key=lambda j: len(msgs[j]))
    bad = sorted({0, 7, len(sigs) // 2, len(sigs) - 1, short})
    for j in bad:
        sigs[j] = bytes([sigs[j][0] ^ 1]) + sigs[j][1:]
    host = HostBatchVerifier()
    before = cache.snapshot()
    got = verify_generic(pubkeys, msgs, sigs, valset=rows)
    want = verify_generic(pubkeys, msgs, sigs, verifier=host)
    diff = np.flatnonzero(got != want)
    assert diff.size == 0, f"table path != host in lanes {diff[:8].tolist()}"
    assert np.flatnonzero(~want).tolist() == bad, np.flatnonzero(~want)[:8]

    # and the lanes for the block as columns, with the same value
    long = [j for j in range(len(msgs)) if len(msgs[j]) != len(msgs[short])]
    cols = [np.frombuffer(b"".join(c[j] for j in long), dtype=np.uint8
                          ).reshape(len(long), -1)
            for c in ([pk.bytes() for pk in pubkeys], msgs, sigs)]
    of_block = rows._replace(slots=rows.slots[long])
    got_c = verify_ed25519_columns(*cols, valset=of_block)
    assert got_c.tolist() == want[long].tolist()
    moved = _delta(cache.snapshot(), before)
    assert moved == {("table", "hit"): 2}, moved
    checks["second_height"] = {
        "valset_miss_spans": spans.count("valset.miss"),
        "table_hits": 1 + int(moved[("table", "hit")]),
        "table_path_lanes": len(want) + len(long),
        "table_path_refused": int(np.count_nonzero(~got))
        + int(np.count_nonzero(~got_c)),
    }


def stage_ed25519_msm(checks: dict) -> None:
    import random

    from tendermint_tpu.crypto.batch import (
        GuardedBatchVerifier,
        TPUBatchVerifier,
    )
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.testutil.chain import build_commit

    verifier = GuardedBatchVerifier(
        TPUBatchVerifier(backend=BACKEND, ed25519_path="msm"))
    chain_id, height = "smoke-msm", 7
    valset, block_id, commit = build_commit(
        MSM_WINDOW, seed=SEED + 1, chain_id=chain_id, height=height)
    pubkeys, msgs, sigs, _ = valset.collect_commit_sigs(
        chain_id, block_id, height, commit)
    pubkeys, msgs, sigs = list(pubkeys), list(msgs), list(sigs)
    clean = {}
    want = _compare_with_host(pubkeys, msgs, sigs, clean, verifier=verifier)
    assert want.all(), "host oracle rejected a clean window"
    # a dirty window: the MSM rejects, chunk RLCs localize, the exact
    # ladder decides the dirty rows
    rng = random.Random(SEED + 1)
    lanes = rng.sample(range(len(sigs)), 5)
    _corrupt(pubkeys, msgs, sigs, lanes, rng, PubKeyEd25519)
    want = _compare_with_host(pubkeys, msgs, sigs, checks, verifier=verifier)
    checks["corrupted"] = len(lanes)
    checks["clean_window_accepted"] = True
    assert not want[lanes].any(), "host oracle accepted a corrupted lane"


def _report(stage, error, checks, t0, versions, info, before=None) -> dict:
    """One stage's report from ``verifier_info()`` taken after it (minus
    ``before``, when the process ran earlier stages too)."""
    def since(key):
        after = info.get(key) or {}
        return _delta(after, before[key]) if before else after

    dev = info.get("device") or {}
    compiled = {
        k: v for k, v in (info.get("compile") or {}).items() if k != "cache_dir"
    }
    return {
        "stage": stage,
        "ok": error is None,
        "error": error,
        "checks": checks,
        "platform": dev.get("platform"),
        "device_kind": dev.get("kind"),
        "device_count": dev.get("count"),
        "versions": versions,
        "backend": info.get("backend"),
        "wall_seconds": round(time.monotonic() - t0, 1),
        "compile_cache_dir": (info.get("compile") or {}).get("cache_dir"),
        "compile": (
            _delta(compiled, before["compile"]) if before else compiled),
        "dispatches": since("dispatches"),
        "device_fallback_total": since("device_fallback_total"),
        "host_fallback_total": since("host_fallback_total"),
        "device_audit_total": since("device_audit_total"),
        "breaker_state": info.get("breaker_state"),
    }


def _versions() -> dict:
    from importlib.metadata import version

    return {p: version(p) for p in ("jax", "jaxlib", "libtpu")}


def _run_kernel_stage(name: str, fn) -> dict:
    """One stage: run it, then report what the process-wide verify metrics,
    the breaker and the compile accounting saw during it."""
    from tendermint_tpu.crypto.batch import verifier_info

    before = verifier_info()
    t0 = time.monotonic()
    checks: dict = {}
    error = None
    try:
        fn(checks)
    except Exception as e:  # stage boundary: recorded, reported, exit != 0
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    return _report(
        name, error, checks, t0, _versions(), verifier_info(), before)


def kernels_main() -> int:
    import logging

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            "chip_smoke: no TPU — jax.devices()[0].platform is "
            f"{dev.platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); no stage was run",
            file=sys.stderr,
        )
        return NO_TPU_EXIT
    # warnings (every host completion of a device dispatch is one) and the
    # verifier-selection line; not the per-block chatter of 8,192 applies
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(levelname)s %(name)s: %(message)s",
    )
    logging.getLogger("tendermint_tpu.verify").setLevel(logging.INFO)

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.encoding import native

    native.build_all()  # raises when cc refuses a committed .c file
    try:
        verifier = batch.get_batch_verifier()
    except ValueError as e:
        print(f"chip_smoke: verifier refused: {e}", file=sys.stderr)
        return 4
    if batch.verifier_info()["device"] is None:
        print("chip_smoke: the default verifier is not a device verifier: "
              f"{batch.describe_verifier(verifier)}", file=sys.stderr)
        return 4
    stages = {
        "commit_verify": stage_commit_verify,
        "fast_sync": stage_fast_sync,
        "fast_sync_full": stage_fast_sync_full,
        "secp256k1": stage_secp256k1,
        "multisig": stage_multisig,
        "commit_absent": stage_commit_absent,
        "ed25519_msm": stage_ed25519_msm,
    }
    rc = 0
    for name in KERNEL_STAGES:
        report = _run_kernel_stage(name, stages[name])
        print(STAGE_PREFIX + json.dumps(report), flush=True)
        rc = rc or (1 if stage_problems(report) else 0)
    return rc


# ---------------------------------------------------------------------------
# Parent: orchestration (never imports jax) and the live-node stage
# ---------------------------------------------------------------------------


def _child_env() -> dict:
    env = dict(os.environ)
    env.pop("TM_BATCH_VERIFIER", None)  # the node chooses from the machine
    return env


def _run_kernels_child() -> tuple:
    """Run child 1 to its end under a deadline; (exit code, stage reports).
    Its stdout is echoed line by line; its stderr is inherited."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", "kernels"],
        stdout=subprocess.PIPE, text=True, cwd=HERE, env=_child_env(),
    )
    reports = []
    killer = _kill_after(proc, KERNELS_DEADLINE_S)
    try:
        for line in proc.stdout:
            print(line, end="", flush=True)
            if line.startswith(STAGE_PREFIX):
                reports.append(json.loads(line[len(STAGE_PREFIX):]))
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return rc, reports


def _kill_after(proc, seconds: float):
    import threading

    def _kill():
        print(f"chip_smoke: deadline of {seconds:.0f}s exceeded; killing "
              f"pid {proc.pid}", file=sys.stderr)
        proc.kill()

    t = threading.Timer(seconds, _kill)
    t.daemon = True
    t.start()
    return t


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _drive_node(client, proc, checks: dict, deadline: float) -> dict:
    """Height, transactions, stored-commit verification; returns /status."""
    import base64
    import http.client

    from tendermint_tpu.rpc.client import RPCClientError

    height = -1
    while height < NODE_HEIGHT:
        if proc.poll() is not None:
            raise AssertionError(f"node exited with code {proc.returncode}")
        if time.monotonic() > deadline:
            raise AssertionError(f"height {height} < {NODE_HEIGHT} at deadline")
        try:
            status = client.status()
            height = int(status["sync_info"]["latest_block_height"])
        except (OSError, http.client.HTTPException, RPCClientError):
            pass  # RPC not up yet
        time.sleep(0.5)
    checks["height"] = height

    for i in range(3):
        key, value = f"smoke{i}".encode(), f"v{i}".encode()
        res = client.broadcast_tx_commit(key + b"=" + value)
        assert res["check_tx"].get("code", 0) == 0, res
        assert res["deliver_tx"].get("code", 0) == 0, res
        got = client.abci_query(data=key)["response"]
        assert got["code"] == 0, got
        assert base64.b64decode(got["value"]) == value, got
    checks["txs_committed_and_read_back"] = 3

    verification = client.call("commit", height=3, verify=1)["verification"]
    checks["commit_3_verification"] = verification
    assert verification["verified"] is True, verification
    return client.status()


def node_stage() -> dict:
    """The CLI node as a user starts it: init, node, RPC, SIGTERM."""
    from tendermint_tpu.rpc.client import HTTPClient

    home = os.path.join(OUT, "node")
    shutil.rmtree(home, ignore_errors=True)
    cli = [sys.executable, "-m", "tendermint_tpu.cmd.tendermint", "--home", home]
    env = _child_env()
    t0 = time.monotonic()
    checks: dict = {}
    error = None
    info: dict = {}
    log_path = os.path.join(OUT, "node.log")
    proc = None
    try:
        subprocess.run(cli + ["init"], check=True, cwd=HERE, env=env, timeout=120)
        port = _free_port()
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                cli + ["node", "--proxy_app", "kvstore", "--rpc.laddr",
                       f"tcp://127.0.0.1:{port}", "--p2p.laddr", "none"],
                stdout=log, stderr=subprocess.STDOUT, cwd=HERE, env=env,
            )
        try:
            status = _drive_node(
                HTTPClient(f"127.0.0.1:{port}", timeout=90.0), proc, checks,
                deadline=t0 + NODE_DEADLINE_S,
            )
            info = status["verifier_info"]
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                    raise AssertionError("node ignored SIGTERM for 60 s")
        checks["sigterm_exit_code"] = proc.returncode
        assert proc.returncode == 0, f"node exit code {proc.returncode}"
        with open(log_path) as f:
            started = [ln.strip() for ln in f if ln.startswith("Batch verifier:")]
        checks["start_line"] = started[0] if started else None
        assert started and f"backend={BACKEND}" in started[0], started
        compiled = info.get("compile", {})
        assert compiled.get("cache_hits", 0) >= 1, (
            f"the node's start-up program was not a compile-cache hit: "
            f"{compiled} — the cache key moves between processes")
    except Exception as e:  # stage boundary: recorded, reported, exit != 0
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
        if os.path.exists(log_path):
            with open(log_path) as f:
                sys.stderr.write("---- node.log (tail) ----\n")
                sys.stderr.writelines(f.readlines()[-40:])
    return _report("node", error, checks, t0, _versions(), info)


def main() -> int:
    if not os.path.isdir(os.path.join(HERE, "tendermint_tpu")):
        print("chip_smoke: tendermint_tpu/ is not next to this script",
              file=sys.stderr)
        return 2
    os.makedirs(OUT, exist_ok=True)
    rc, reports = _run_kernels_child()
    if not reports:
        # the child named its reason (no TPU, a refused configuration, a
        # compiler that refused an extension); no stage ran
        return rc or 1
    # the chip is free again: the kernel child has exited
    node = node_stage()
    print(STAGE_PREFIX + json.dumps(node), flush=True)
    reports.append(node)
    with open(os.path.join(OUT, "report.json"), "w") as f:
        json.dump(reports, f, indent=1)
    failed = aggregate(reports) or rc
    if failed:
        print(f"chip_smoke: FAILED (kernel child exit code {rc})",
              file=sys.stderr)
        return failed
    first = reports[0]
    print(json.dumps({"ok": True, "device": {
        "platform": first["platform"], "kind": first["device_kind"],
        "count": first["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:] == ["--child", "kernels"]:
        sys.exit(kernels_main())
    sys.exit(main())
