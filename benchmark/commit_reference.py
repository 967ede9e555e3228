"""The benchmark's own ``VerifyCommit``: the plain reference a live chain's
commit is judged by.  It imports nothing of the program.

The rule is the reference's ``types/validator_set.go:260-309`` as Tendermint
v0.26.2 has it, over plain data (integers, ``bytes``, tuples):

  1. the commit has one slot a validator of the set;
  2. it is for the height asked about (the height of its first precommit
     that is there, 0 where none is);
  3. it is for the block id asked about;
  4. a slot may be empty (``None``: the proposer did not have that
     validator's precommit): skipped, no error, no power;
  5. every precommit that is there has the commit's height, the commit's
     round (its first precommit's) and the precommit type;
  6. every precommit that is there carries a valid signature, by the key of
     ITS SLOT, over its own canonical sign-bytes, whatever block it votes
     ("It's OK that the BlockID doesn't match.  We include stray precommits
     to measure validator availability");
  7. its power counts only where its block id equals the commit's;
  8. the power counted is MORE than two thirds of the set's.

The Go stops at the first signature that fails; this walks on and says of
every lane what it is, because the device answers for every lane, and then
refuses the commit all the same.

The canonical sign-bytes are written out here from integers and bytes
(``types/canonical.go`` in this repo's deterministic codec, which is not
amino): uvarint(type), fixed64 little-endian height, round and timestamp,
the block id (length-prefixed hash, uvarint part count, length-prefixed
parts hash: three bytes for nil), the length-prefixed chain id.
"""

from __future__ import annotations

import struct
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmark import oracle

PRECOMMIT = 0x02
_FIXED64 = struct.Struct("<q").pack


class BlockId(NamedTuple):
    hash: bytes
    parts_total: int
    parts_hash: bytes


NIL = BlockId(b"", 0, b"")


class Precommit(NamedTuple):
    """One slot of a commit that is not empty, as the wire carries it."""

    type: int
    height: int
    round: int
    timestamp_ns: int
    block_id: BlockId
    signature: bytes


class Verdict(NamedTuple):
    stands: bool
    rule: str  # "ok", or the first rule that refused the commit
    # one entry a precommit that is there, in slot order; empty where a
    # structural rule (1-3, 5) refused the commit before any signature
    lanes: List[bool]
    tallied: int


def uvarint(n: int) -> bytes:
    if not 0 <= n < 1 << 64:
        raise ValueError("uvarint must be in [0, 2^64)")
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _prefixed(b: bytes) -> bytes:
    return uvarint(len(b)) + b


def encode_block_id(block_id: BlockId) -> bytes:
    return (_prefixed(block_id.hash) + uvarint(block_id.parts_total)
            + _prefixed(block_id.parts_hash))


def sign_bytes(chain_id: str, vote_type: int, height: int, round: int,
               timestamp_ns: int, block_id: BlockId) -> bytes:
    """What a validator signs for one vote."""
    return (uvarint(vote_type) + _FIXED64(height) + _FIXED64(round)
            + _FIXED64(timestamp_ns) + encode_block_id(block_id)
            + _prefixed(chain_id.encode("utf-8")))


def precommit_sign_bytes(chain_id: str, p: Precommit) -> bytes:
    return sign_bytes(chain_id, p.type, p.height, p.round, p.timestamp_ns,
                      p.block_id)


def verify_commit(
    keys: Sequence[bytes], powers: Sequence[int], chain_id: str,
    block_id: BlockId, height: int, commit_block_id: BlockId,
    precommits: Sequence[Optional[Precommit]],
    memo: Optional[Dict[Tuple[bytes, bytes, bytes], bool]] = None,
) -> Verdict:
    """The rule above over one commit.  ``memo`` keeps the oracle's answer
    by (key, sign-bytes, signature), so that a variant of a commit pays only
    for the lanes that differ from it."""

    def refused(rule: str) -> Verdict:
        return Verdict(False, rule, [], 0)

    if len(keys) != len(precommits):
        return refused("wrong set size")
    first = next((p for p in precommits if p is not None), None)
    if height != (first.height if first else 0):
        return refused("wrong height")
    if block_id != commit_block_id:
        return refused("wrong block id")
    round = first.round if first else 0
    for p in precommits:
        if p is None:
            continue
        if p.height != height:
            return refused("precommit height")
        if p.round != round:
            return refused("precommit round")
        if p.type != PRECOMMIT:
            return refused("not a precommit")

    lanes: List[bool] = []
    tallied = 0
    for idx, p in enumerate(precommits):
        if p is None:
            continue
        lane = (keys[idx], precommit_sign_bytes(chain_id, p), p.signature)
        ok = memo.get(lane) if memo is not None else None
        if ok is None:
            ok = oracle.verify(*lane)
            if memo is not None:
                memo[lane] = ok
        lanes.append(ok)
        if ok and p.block_id == commit_block_id:
            tallied += powers[idx]
    if not all(lanes):
        return Verdict(False, "invalid signature", lanes, tallied)
    if tallied * 3 <= sum(powers) * 2:
        return Verdict(False, "insufficient voting power", lanes, tallied)
    return Verdict(True, "ok", lanes, tallied)
