"""Seeded inputs for the secp256k1 commit stream: a ring of commits over one
set of secp256k1 validators, its tampered variants, and the reference's
verdict on each.

Keys, nonces and signatures come from ``benchmark/oracle_secp256k1.py``'s own
arithmetic and from ``--seed`` alone (nonces are drawn from the seeded
generator, so the same seed gives the same bytes); the program signs
nothing here.  As in ``benchmark/chaingen.py``, whose ``Lanes`` and
``CommitCase`` this file fills, the sign-bytes template, ``Vote``, ``Commit``
and ``ValidatorSet`` are the program's, and the lanes the reference judges
are recorded here as they were signed, never read back from the program.
"""

from __future__ import annotations

from dataclasses import replace
from typing import List, Optional, Sequence, Tuple

import numpy as np

from benchmark import chaingen
from benchmark import oracle_secp256k1 as oracle
from benchmark.chaingen import CommitCase, Lanes

# tampers that do not depend on the signature scheme: chaingen builds them
_SCHEME_FREE = ("wrong_validator", "wrong_block_id", "under_quorum")


def _scalar(rng: np.random.Generator) -> int:
    """Uniform in [1, n) but for a bias of 2**-128."""
    return int.from_bytes(rng.bytes(40), "big") % (oracle.N - 1) + 1


class Signer:
    """One validator key: a scalar from the seeded generator and its
    compressed point, both by the oracle's arithmetic.  ``sign(msg)`` draws
    its nonce from the same generator, so ``chaingen._sign_commit`` signs
    with it as it does with an ed25519 key."""

    def __init__(self, rng: np.random.Generator):
        self._rng = rng
        self.d = _scalar(rng)
        self.pub = oracle.pubkey_of(self.d)

    def sign(self, msg: bytes) -> bytes:
        while True:
            try:
                return oracle.sign(self.d, msg, _scalar(self._rng))
            except ValueError:  # r or s came out 0: another nonce
                continue


def make_commit_ring(config: dict, traffic: dict, seed: int) -> List[CommitCase]:
    """``traffic['ring']`` commits at consecutive heights over one set of
    ``config['validators']`` equal-power secp256k1 validators: distinct
    block ids, per-validator timestamps inside one second, every precommit
    present and valid."""
    from tendermint_tpu.crypto.keys import PubKeySecp256k1
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.core import PartSetHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    if config["key_type"] != "secp256k1":
        raise ValueError(f"this generator signs secp256k1, not {config['key_type']!r}")
    rng = np.random.default_rng(seed)
    n = int(config["validators"])
    signers = [Signer(rng) for _ in range(n)]
    vals = [Validator(PubKeySecp256k1(s.pub), int(config["voting_power"]))
            for s in signers]
    valset = ValidatorSet(vals)
    by_addr = {v.address: s for v, s in zip(vals, signers)}
    chain_id = config["chain_id"]
    cases = []
    for k in range(int(traffic["ring"])):
        height = int(traffic["first_height"]) + k
        block_id = BlockID(rng.bytes(32), PartSetHeader(1, rng.bytes(32)))
        base = chaingen.GENESIS_TIME_NS + height * 1_000_000_000
        stamps = (base + rng.integers(0, 1 << 29, size=n)).tolist()
        commit, lanes = chaingen._sign_commit(
            chain_id, valset, by_addr, height, block_id, stamps)
        cases.append(CommitCase(
            f"ring{k}", valset, chain_id, block_id, height, commit, lanes))
    return cases


def tamper(case: CommitCase, kind: str, rng: np.random.Generator) -> CommitCase:
    """A seeded variant of a ring commit that the oracle decides; the
    program's answer through ``verify_commit`` has to be the oracle's."""
    from tendermint_tpu.types import Commit

    if kind in _SCHEME_FREE:
        return chaingen.tamper(case, kind, rng)
    votes = list(case.commit.precommits)
    ln = case.lanes
    lanes = Lanes(list(ln.pubs), list(ln.msgs), list(ln.sigs), list(ln.powers))
    i = int(rng.integers(0, len(votes)))
    r, s = oracle.parse_der(lanes.sigs[i])
    if kind == "bad_signature":
        # one bit of s, under the top byte so that s stays in (0, n/2]:
        # the curve equation refuses it, not a range check
        sig = oracle.encode_der(r, s ^ (1 << int(rng.integers(0, 248))))
    elif kind == "high_s":
        # the other root: a valid ECDSA signature that VerifyBytes refuses
        sig = oracle.encode_der(r, oracle.N - s)
    elif kind == "lax_der":
        # r with a leading zero it does not need, both lengths adjusted
        strict = lanes.sigs[i]
        sig = bytes([0x30, strict[1] + 1, 0x02, strict[3] + 1, 0x00]) + strict[4:]
    else:
        raise ValueError(f"unknown tamper kind {kind!r}")
    if sig == lanes.sigs[i]:
        raise RuntimeError(f"{kind}: the signature did not change")
    votes[i] = replace(votes[i], signature=sig)
    lanes.sigs[i] = sig
    return CommitCase(
        f"{case.name}.{kind}", case.valset, case.chain_id, case.block_id,
        case.height, Commit(case.commit.block_id, votes), lanes)


def reference_verdict(lanes: Lanes, known: Optional[Sequence[bool]] = None,
                      base: Optional[Lanes] = None) -> Tuple[List[bool], bool]:
    """(per-lane verdict of every present lane, whether the commit stands)
    by the oracle.  ``known``/``base``: verdicts already computed for the
    untampered lanes (all of them present), reused where a lane is
    byte-identical."""
    lane_ok, tally, all_ok = [], 0, True
    for i, sig in enumerate(lanes.sigs):
        if sig is None:
            continue
        if known is not None and base.sigs[i] == sig:
            ok = known[i]
        else:
            ok = oracle.verify(lanes.pubs[i], lanes.msgs[i], sig)
        lane_ok.append(ok)
        all_ok = all_ok and ok
        if ok:
            tally += lanes.powers[i]
    stands = (lanes.structural_ok and all_ok
              and tally * 3 > sum(lanes.powers) * 2)
    return lane_ok, stands
