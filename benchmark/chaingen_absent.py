"""Seeded inputs for the live-chain commit stream: one set of equal-power
ed25519 validators and a ring of commits in which, at every height, another
third less one of the slots is empty or holds a precommit for nil.

Every commit is plain data (``commit_reference.Precommit`` a slot that is
there, ``None`` one that is not) signed over the REFERENCE's sign-bytes, and
the bytes a peer would send: the program's ``Commit`` of that data,
marshalled here, which is all a driver is given of it.  ``wire`` stops where
the program's ``Vote.sign_bytes`` of one precommit for the block, and of one
not for it, is not what was signed.

Each height draws from ``[seed, height]``: the block id, a permutation of
the slots (the first ``absent`` of it empty, the next ``nil`` for nil, the
rest for the block) and every validator's timestamp inside one second.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence

import numpy as np

from benchmark import chaingen, oracle
from benchmark import commit_reference as ref

ABSENT, FOR_NIL, FOR_BLOCK = 0, 1, 2


@dataclass
class Keys:
    """The run's validators in validator-set order (by address)."""

    signers: List[chaingen.Signer]
    pubs: List[bytes]
    powers: List[int]


@dataclass
class LiveCommit:
    name: str
    chain_id: str
    height: int
    block_id: ref.BlockId  # the commit's own
    asked: ref.BlockId  # the block id verify_commit is asked about
    precommits: List[Optional[ref.Precommit]]
    wire: bytes = b""  # the marshalled Commit

    def count(self, kind: int) -> int:
        return sum(kind_of(p, self.block_id) == kind for p in self.precommits)


def kind_of(p: Optional[ref.Precommit], block_id: ref.BlockId) -> int:
    if p is None:
        return ABSENT
    return FOR_BLOCK if p.block_id == block_id else FOR_NIL


def make_keys(config: dict, seed: int) -> Keys:
    if config["key_type"] != "ed25519":
        raise ValueError("this generator keys validators with ed25519")
    n = int(config["validators"])
    signers = chaingen.make_signers(n, np.random.default_rng([seed, 0]))
    signers.sort(key=lambda s: hashlib.sha256(s.pub).digest()[:20])
    if len({s.pub for s in signers}) != n:
        raise RuntimeError("validator keys are not all distinct")
    return Keys(signers, [s.pub for s in signers],
                [int(config["voting_power"])] * n)


def _signed(keys: Keys, chain_id: str, slot: int, height: int, stamp: int,
            block_id: ref.BlockId) -> ref.Precommit:
    msg = ref.sign_bytes(chain_id, ref.PRECOMMIT, height, 0, stamp, block_id)
    return ref.Precommit(ref.PRECOMMIT, height, 0, stamp, block_id,
                         keys.signers[slot].sign(msg))


def make_commit(keys: Keys, chain_id: str, traffic: dict, seed: int,
                height: int) -> LiveCommit:
    rng = np.random.default_rng([seed, height])
    n = len(keys.pubs)
    block_id = ref.BlockId(rng.bytes(32), 1, rng.bytes(32))
    order = rng.permutation(n)
    n_absent, n_nil = int(traffic["absent"]), int(traffic["nil"])
    base = chaingen.GENESIS_TIME_NS + height * 1_000_000_000
    stamps = (base + rng.integers(0, 1 << 29, size=n)).tolist()
    precommits: List[Optional[ref.Precommit]] = [None] * n
    for rank, slot in enumerate(order.tolist()):
        if rank < n_absent:
            continue
        voted = ref.NIL if rank < n_absent + n_nil else block_id
        precommits[slot] = _signed(
            keys, chain_id, slot, height, stamps[slot], voted)
    return wire(LiveCommit(
        f"h{height}", chain_id, height, block_id, block_id, precommits), keys)


def make_ring(keys: Keys, config: dict, traffic: dict, seed: int) -> List[LiveCommit]:
    first = int(traffic["first_height"])
    return [make_commit(keys, config["chain_id"], traffic, seed, first + k)
            for k in range(int(traffic["ring"]))]


def wire(commit: LiveCommit, keys: Keys) -> LiveCommit:
    """``commit`` with its bytes on the wire: the program's ``Commit`` of the
    plain slots, marshalled.  The objects are dropped with this frame."""
    from tendermint_tpu.types import BlockID, Commit, SignedMsgType, Vote
    from tendermint_tpu.types.core import PartSetHeader

    def program_id(b: ref.BlockId) -> BlockID:
        return BlockID(b.hash, PartSetHeader(b.parts_total, b.parts_hash))

    ids = {}
    addresses = [hashlib.sha256(pub).digest()[:20] for pub in keys.pubs]
    votes = []
    for slot, p in enumerate(commit.precommits):
        if p is None:
            votes.append(None)
            continue
        first_of_its_id = p.block_id not in ids
        if first_of_its_id:
            ids[p.block_id] = program_id(p.block_id)
        vote = Vote(
            vote_type=SignedMsgType(p.type), height=p.height, round=p.round,
            timestamp_ns=p.timestamp_ns, block_id=ids[p.block_id],
            validator_address=addresses[slot], validator_index=slot,
            signature=p.signature)
        if first_of_its_id and vote.sign_bytes(commit.chain_id) != (
                ref.precommit_sign_bytes(commit.chain_id, p)):
            raise RuntimeError(
                f"{commit.name}: the program's Vote.sign_bytes of a "
                f"precommit for {p.block_id.hash.hex()[:8] or 'nil'} is "
                "not the reference's: nothing signed here would verify")
        votes.append(vote)
    return replace(commit, wire=Commit(
        program_id(commit.block_id), votes).marshal())


def tamper(commit: LiveCommit, keys: Keys, kind: str, rng) -> LiveCommit:
    """A seeded variant of a ring commit that the reference decides; the
    program's answer through ``verify_commit`` has to be the reference's."""
    pcs = list(commit.precommits)
    out = replace(commit, name=f"{commit.name}.{kind}", precommits=pcs)
    n = len(pcs)
    start = int(rng.integers(0, n))

    def slot_of(kind_wanted: int, skip: Sequence[int] = ()) -> int:
        """The first slot of that kind from a seeded start on."""
        for k in range(n):
            i = (start + k) % n
            if i not in skip and kind_of(pcs[i], commit.block_id) == kind_wanted:
                return i
        raise RuntimeError(f"{kind}: no slot of kind {kind_wanted}")

    def flip(i: int) -> None:
        sig = bytearray(pcs[i].signature)
        sig[int(rng.integers(0, 32))] ^= 1 << int(rng.integers(0, 8))
        pcs[i] = pcs[i]._replace(signature=bytes(sig))

    def resign(i: int, block_id: ref.BlockId) -> None:
        stamp = chaingen.GENESIS_TIME_NS + commit.height * 1_000_000_000 + i
        pcs[i] = _signed(keys, commit.chain_id, i, commit.height, stamp, block_id)

    if kind == "bad_signature":
        flip(slot_of(FOR_BLOCK))
    elif kind == "bad_signature_on_nil":  # a stray is verified too
        flip(slot_of(FOR_NIL))
    elif kind == "wrong_validator":  # two validators' precommits exchanged
        i = slot_of(FOR_BLOCK)
        j = slot_of(FOR_BLOCK, skip=(i,))
        pcs[i], pcs[j] = pcs[j], pcs[i]
    elif kind == "s_plus_L":
        # the first lane whose s + L still has its top three bits clear
        for k in range(n):
            i = (start + k) % n
            if pcs[i] is None:
                continue
            sig = pcs[i].signature
            s = int.from_bytes(sig[32:], "little") + oracle.L
            if s < 1 << 253:
                pcs[i] = pcs[i]._replace(
                    signature=sig[:32] + s.to_bytes(32, "little"))
                break
    elif kind == "wrong_block_id":
        out.asked = commit.block_id._replace(hash=bytes(32))
    elif kind == "one_more_nil":  # one vote under the quorum's edge
        resign(slot_of(FOR_BLOCK), ref.NIL)
    elif kind == "nil_to_other_block":  # a stray for a block: still a stray
        resign(slot_of(FOR_NIL), ref.BlockId(rng.bytes(32), 1, rng.bytes(32)))
    else:
        raise ValueError(f"unknown tamper kind {kind!r}")
    if kind != "wrong_block_id" and pcs == commit.precommits:
        raise RuntimeError(f"{kind}: the commit did not change")
    return wire(out, keys)


def reference_verdict(commit: LiveCommit, keys: Keys, memo=None) -> ref.Verdict:
    return ref.verify_commit(
        keys.pubs, keys.powers, commit.chain_id, commit.asked, commit.height,
        commit.block_id, commit.precommits, memo)
