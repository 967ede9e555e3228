"""``benchmark/commit_reference.py`` against the program: the canonical
precommit sign-bytes byte for byte, and ``VerifyCommit`` rule by rule against
``ValidatorSet.verify_commit`` at 4, 64 and 1,000 validators, each commit
handed to the program as the bytes a peer would send."""

import ast
import functools
import os

import numpy as np
import pytest

from benchmark import chaingen
from benchmark import chaingen_absent as gen
from benchmark import commit_reference as ref

CHAIN = "reference-chain"
HEIGHT = 77
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_it_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "commit_reference.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    assert not any(n.startswith("tendermint_tpu") for n in names), names
    assert "benchmark.oracle" in names  # which imports nothing of it either


@pytest.mark.parametrize("voted", ["block", "nil"])
@pytest.mark.parametrize("seed", range(6))
def test_sign_bytes_are_the_programs(seed, voted):
    from tendermint_tpu.types import BlockID, SignedMsgType, Vote
    from tendermint_tpu.types.core import PartSetHeader

    rng = np.random.default_rng([99, seed])
    height = int(rng.integers(1, 1 << 40))
    round = int(rng.integers(0, 300))
    stamp = int(rng.integers(1, 1 << 62))
    total = int(rng.integers(1, 400))
    chain_id = "chain-" + "x" * int(rng.integers(0, 140))  # past one length byte
    block_id = (ref.BlockId(rng.bytes(32), total, rng.bytes(32))
                if voted == "block" else ref.NIL)
    for vote_type in (SignedMsgType.PRECOMMIT, SignedMsgType.PREVOTE):
        vote = Vote(
            vote_type=vote_type, height=height, round=round, timestamp_ns=stamp,
            block_id=BlockID(block_id.hash, PartSetHeader(
                block_id.parts_total, block_id.parts_hash)),
            validator_address=bytes(20), validator_index=0)
        assert vote.is_nil is (voted == "nil")
        assert ref.sign_bytes(chain_id, int(vote_type), height, round, stamp,
                              block_id) == vote.sign_bytes(chain_id)
    # uvarint(type), three fixed64, the block id, the prefixed chain id
    prefix = 1 if len(chain_id) < 128 else 2
    assert len(ref.sign_bytes(chain_id, ref.PRECOMMIT, height, round, stamp, block_id)) == (
        25 + (3 if voted == "nil" else 66 + len(ref.uvarint(total)))
        + prefix + len(chain_id))


@pytest.mark.parametrize("n,want", [
    (0, b"\x00"), (1, b"\x01"), (127, b"\x7f"), (128, b"\x80\x01"),
    (300, b"\xac\x02"), ((1 << 64) - 1, b"\xff" * 9 + b"\x01")])
def test_uvarint(n, want):
    from tendermint_tpu.encoding.codec import encode_uvarint

    assert ref.uvarint(n) == want == encode_uvarint(n)


# ---------------------------------------------------------------------------
# VerifyCommit against verify_commit
# ---------------------------------------------------------------------------

BLOCK = ref.BlockId(b"\xaa" * 32, 1, b"\x55" * 32)
OTHER = ref.BlockId(b"\x5c" * 32, 1, b"\xa3" * 32)


@functools.lru_cache(maxsize=None)
def _set(n, heavy_first=False):
    """n validators of power 10 in set order (the first ``5 (n - 1)`` where
    asked: a third of the set's power in one slot) and the program's set."""
    from benchmark.drivers import commit_stream_absent as drv

    keys = gen.make_keys(
        {"key_type": "ed25519", "validators": n, "voting_power": 10}, 4300 + n)
    if heavy_first:
        keys.powers[0] = 5 * (n - 1)
    return keys, drv._valset(keys)


def _vote(keys, slot, block_id=BLOCK, height=HEIGHT, round=0,
          vote_type=ref.PRECOMMIT):
    stamp = chaingen.GENESIS_TIME_NS + 1_001 * slot
    msg = ref.sign_bytes(CHAIN, vote_type, height, round, stamp, block_id)
    return ref.Precommit(vote_type, height, round, stamp, block_id,
                         keys.signers[slot].sign(msg))


def _all_present(keys, n):
    return [_vote(keys, i) for i in range(n)]


# a variant: (keys, n) -> (precommits, block id asked, height asked, heavy set)
def _v_all_present(keys, n):
    return _all_present(keys, n), BLOCK, HEIGHT


def _v_some_absent(keys, n):
    pcs = _all_present(keys, n)
    for i in range(0, n, 4):  # a quarter: still over two thirds
        pcs[i] = None
    return pcs, BLOCK, HEIGHT


def _v_some_nil(keys, n):
    pcs = _all_present(keys, n)
    for i in range(1, n, 4):
        pcs[i] = _vote(keys, i, ref.NIL)
    return pcs, BLOCK, HEIGHT


def _v_stray_other_block(keys, n):
    pcs = _all_present(keys, n)
    pcs[n // 2] = _vote(keys, n // 2, OTHER)
    return pcs, BLOCK, HEIGHT


def _v_too_many_nil(keys, n):
    pcs = _all_present(keys, n)
    for i in range(0, n, 2):  # half the power counts: refused, every lane valid
        pcs[i] = _vote(keys, i, ref.NIL)
    return pcs, BLOCK, HEIGHT


def _v_exactly_two_thirds(keys, n):  # heavy set: slot 0 holds a third
    pcs = _all_present(keys, n)
    pcs[0] = None
    return pcs, BLOCK, HEIGHT


def _v_one_vote_over(keys, n):  # heavy set: slot 0 signs, slot 1 does not
    pcs = _all_present(keys, n)
    pcs[1] = None
    return pcs, BLOCK, HEIGHT


def _v_bad_signature_on_stray(keys, n):
    pcs = _all_present(keys, n)
    p = _vote(keys, n - 1, ref.NIL)
    pcs[n - 1] = p._replace(
        signature=bytes([p.signature[0] ^ 4]) + p.signature[1:])
    return pcs, BLOCK, HEIGHT


def _v_wrong_size(keys, n):
    return _all_present(keys, n)[:-1], BLOCK, HEIGHT


def _v_wrong_height(keys, n):
    return _all_present(keys, n), BLOCK, HEIGHT + 1


def _v_wrong_block_id(keys, n):
    return _all_present(keys, n), OTHER, HEIGHT


def _v_precommit_height(keys, n):
    pcs = _all_present(keys, n)
    pcs[n - 1] = _vote(keys, n - 1, height=HEIGHT + 3)
    return pcs, BLOCK, HEIGHT


def _v_precommit_round(keys, n):
    pcs = _all_present(keys, n)
    pcs[n - 1] = _vote(keys, n - 1, round=2)
    return pcs, BLOCK, HEIGHT


def _v_precommit_type(keys, n):
    pcs = _all_present(keys, n)
    pcs[n - 1] = _vote(keys, n - 1, vote_type=0x01)  # a prevote
    return pcs, BLOCK, HEIGHT


# variant -> (maker, heavy set, the rule that decides it)
VARIANTS = {
    "all_present": (_v_all_present, False, "ok"),
    "some_absent": (_v_some_absent, False, "ok"),
    "some_nil": (_v_some_nil, False, "ok"),
    "stray_other_block": (_v_stray_other_block, False, "ok"),
    "too_many_nil": (_v_too_many_nil, False, "insufficient voting power"),
    "exactly_two_thirds": (_v_exactly_two_thirds, True, "insufficient voting power"),
    "one_vote_over": (_v_one_vote_over, True, "ok"),
    "bad_signature_on_stray": (_v_bad_signature_on_stray, False, "invalid signature"),
    "wrong_size": (_v_wrong_size, False, "wrong set size"),
    "wrong_height": (_v_wrong_height, False, "wrong height"),
    "wrong_block_id": (_v_wrong_block_id, False, "wrong block id"),
    "precommit_height": (_v_precommit_height, False, "precommit height"),
    "precommit_round": (_v_precommit_round, False, "precommit round"),
    "precommit_type": (_v_precommit_type, False, "not a precommit"),
}


@pytest.mark.parametrize("n", (4, 64, 1000))
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_program_decides_a_commit_as_the_reference_does(variant, n):
    from benchmark.drivers import commit_stream_absent as drv
    from tendermint_tpu.types.validator_set import CommitError

    make, heavy, rule = VARIANTS[variant]
    keys, valset = _set(n, heavy)
    precommits, asked, height = make(keys, n)
    live = gen.LiveCommit(variant, CHAIN, HEIGHT, BLOCK, asked, precommits)
    want = ref.verify_commit(
        keys.pubs, keys.powers, CHAIN, asked, height, BLOCK, precommits)
    assert want.rule == rule and want.stands is (rule == "ok")

    case = drv._case(gen.wire(live, keys), valset)  # through the wire
    try:
        valset.verify_commit(CHAIN, case.block_id, height, case.commit)
        refused = None
    except CommitError as e:
        refused = str(e)
    assert (refused is None) is want.stands, refused
    if refused is not None:  # and by the same rule
        assert refused.startswith(rule.replace("not a precommit", "not a precommit @")
                                  .replace("precommit height", f"precommit height {HEIGHT + 3}")
                                  .replace("precommit round", "precommit round 2"))
    structural = rule in ("ok", "invalid signature", "insufficient voting power")
    if structural:  # the reference reached the signatures: lane for lane
        assert len(want.lanes) == sum(p is not None for p in precommits)
        assert drv._device_lane_verdicts(case) == want.lanes
        assert want.lanes.count(False) == (rule == "invalid signature")
    else:
        assert want.lanes == [] and want.tallied == 0
    if variant == "exactly_two_thirds":
        assert want.tallied * 3 == sum(keys.powers) * 2
    if variant == "one_vote_over":
        assert want.tallied * 3 == sum(keys.powers) * 2 + 15 * (n - 1) - 30
    if variant == "stray_other_block":
        assert want.tallied == 10 * (n - 1)


def test_an_empty_commit_and_the_memo():
    keys, _ = _set(4)
    none = ref.verify_commit(keys.pubs, keys.powers, CHAIN, BLOCK, 0, BLOCK, [None] * 4)
    assert (none.stands, none.rule, none.lanes) == (
        False, "insufficient voting power", [])
    assert ref.verify_commit(
        keys.pubs, keys.powers, CHAIN, BLOCK, HEIGHT, BLOCK, [None] * 4).rule == "wrong height"
    pcs = _all_present(keys, 4)
    memo = {}
    first = ref.verify_commit(keys.pubs, keys.powers, CHAIN, BLOCK, HEIGHT, BLOCK, pcs, memo)
    assert first.stands and len(memo) == 4 and all(memo.values())
    # a lane the memo holds is not put to the oracle again; another one is
    memo[next(iter(memo))] = False
    again = ref.verify_commit(keys.pubs, keys.powers, CHAIN, BLOCK, HEIGHT, BLOCK, pcs, memo)
    assert again.rule == "invalid signature" and again.lanes.count(False) == 1
    pcs[3] = _vote(keys, 3, ref.NIL)
    ref.verify_commit(keys.pubs, keys.powers, CHAIN, BLOCK, HEIGHT, BLOCK, pcs, memo)
    assert len(memo) == 5
