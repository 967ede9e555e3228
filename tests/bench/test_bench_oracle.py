"""The benchmark's own oracle: independent of the program, and of one mind
with it on the Go accept set's edges."""

import numpy as np
import pytest

from benchmark import chaingen, oracle
from tendermint_tpu.crypto import ed25519 as program_ed


def _vectors():
    rng = np.random.default_rng(2**31 + 3)
    signer = chaingen.Signer(rng.bytes(32))
    msg = rng.bytes(110)
    sig = signer.sign(msg)
    s = int.from_bytes(sig[32:], "little")
    vs = [("valid", signer.pub, msg, sig, True)]
    flipped = bytearray(sig)
    flipped[5] ^= 4
    vs.append(("bad R", signer.pub, msg, bytes(flipped), False))
    vs.append(("other message", signer.pub, msg + b"x", sig, False))
    s_l = (s + oracle.L).to_bytes(32, "little")
    vs.append(("s + L", signer.pub, msg, sig[:32] + s_l, s + oracle.L < 1 << 253))
    big = (s | (1 << 255)).to_bytes(32, "little")
    vs.append(("s with top bit", signer.pub, msg, sig[:32] + big, False))
    ipub, isig = oracle.sign_identity_key(12345)
    vs.append(("identity key, y = p + 1", ipub, msg, isig, True))
    vs.append(("identity key, other scalar", ipub, msg,
               isig[:32] + (12346).to_bytes(32, "little"), False))
    vs.append(("short signature", signer.pub, msg, sig[:63], False))
    return vs


@pytest.mark.parametrize("case", _vectors(), ids=lambda c: c[0])
def test_oracle_decides_as_the_accept_set_says_and_as_the_program_does(case):
    _name, pub, msg, sig, want = case
    assert oracle.verify(pub, msg, sig) is want
    assert oracle.verify_exact(pub, msg, sig) is want
    assert program_ed.verify(pub, msg, sig) is want


def test_oracle_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(oracle))
    mods = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not any(m and m.startswith(("tendermint_tpu", "benchmark")) for m in mods)


def test_merkle_root_and_txs_match_the_programs_app():
    from tendermint_tpu.crypto import merkle

    rng = np.random.default_rng(5)
    for n in (0, 1, 2, 3, 5, 8, 13):
        items = [rng.bytes(20) for _ in range(n)]
        assert chaingen.merkle_root(items) == merkle.hash_from_byte_slices(items)
    txs = chaingen.make_txs(rng, 7, 250)
    assert len(txs) == 7 and all(len(t) == 250 and t.count(b"=") == 1 for t in txs)
    assert len(set(txs)) == 7


@pytest.mark.parametrize("kind,stands,bad_lanes", [
    ("bad_signature", False, 1), ("wrong_validator", False, 2),
    ("s_plus_L", True, 0), ("wrong_block_id", False, 0), ("under_quorum", False, 0),
])
def test_tampered_commits_are_decided_alike_by_reference_and_program(kind, stands, bad_lanes):
    from tendermint_tpu.crypto.batch import HostBatchVerifier
    from tendermint_tpu.types.validator_set import CommitError

    cfg = {"validators": 10, "voting_power": 10, "chain_id": "t"}
    ring = chaingen.make_commit_ring(cfg, {"ring": 1, "first_height": 7}, 2**31 + 9)
    base_lanes, base_stands = chaingen.reference_verdict(ring[0].lanes)
    assert base_stands and all(base_lanes)
    case = chaingen.tamper(ring[0], kind, np.random.default_rng(3))
    lanes, got = chaingen.reference_verdict(case.lanes, base_lanes, ring[0].lanes)
    assert got is stands and lanes.count(False) == bad_lanes
    try:
        case.valset.verify_commit(case.chain_id, case.block_id, case.height,
                                  case.commit, verifier=HostBatchVerifier())
        accepted = True
    except CommitError:
        accepted = False
    assert accepted is stands
