"""A validator set's evolution in plain Python: the reference that the
deployment ``fastsync-64v-churn`` is held to.

Tendermint v0.26.2's rules, written out from the reference implementation's
Go and independent of the program: nothing here imports ``tendermint_tpu``
(the one import of the benchmark's is ``chaingen.merkle_root``, a dozen
lines of hashlib).  Integers are Python's, a set is a list of small records
kept in address order, and every step is the obvious loop.

  types/validator_set.go   NewValidatorSet, IncrementAccum, findProposer,
                           Add, Update, Remove, Hash
  state/state.go           MakeGenesisState
  state/execution.go       updateValidators, updateState: the updates that
                           block H's EndBlock returns are applied to a copy of
                           NextValidators and bind at H + 2
  abci/example/kvstore/persistent_kvstore.go
                           'val:' transactions become ValidatorUpdates

``Evolution`` walks the three sets a state carries (last, current, next) one
block at a time and says, for the height about to be proposed, what its
header must state: ``validators_hash``, ``next_validators_hash`` and the
round-0 proposer's address.

Where this file follows the reference implementation's Go and NOT this
repo's Python:

* **Accumulators clip at int64's ends** (``safeAddClip``/``safeSubClip``);
  the program clips at +-2**60.  No power here comes near either.
* **A removal of an unknown key is refused twice over in Go**: the app's
  DeliverTx answers code 3 ("Cannot remove non-existent validator") and
  keeps the update out of EndBlock, and ``updateValidators`` returns "Failed
  to remove validator" should one arrive all the same.  The program's app
  hands the update on and its ``update_validators`` raises.  ``apply_updates``
  raises ``ValueError`` as ``updateValidators`` does; so does a negative
  power.
* **An update replaces the validator whole**, accumulator included
  (``vals.Validators[index] = val.Copy()`` with a validator fresh from the
  ABCI update, accum 0), and an added validator starts at accum 0; later
  versions of Tendermint re-centre priorities, v0.26.2 does not.

Where it has to follow this repo's Python, because the bytes are the repo's
own and a header would not match otherwise (both are documented departures
of the program from amino, SURVEY.md 7.2):

* **A validator's hash bytes** are ``uvarint(len(pub)) || pub ||
  zigzag-varint(power)`` (Go: amino of the struct {PubKey, VotingPower}).
* **The merkle tree** has RFC 6962 prefixes (0x00 leaf, 0x01 inner), splits
  at the largest power of two below n, and an empty tree is SHA-256("").
* **A 'val:' transaction** is ``val:<base64 pubkey>!<power>`` (v0.26.2's
  app writes ``val:<HEX pubkey>/<power>``; base64 and '!' came later
  upstream and are what the program's app parses).
"""

from __future__ import annotations

import base64
import hashlib
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from benchmark.chaingen import merkle_root  # plain Python, the benchmark's own

INT64_MAX = (1 << 63) - 1
INT64_MIN = -(1 << 63)


def _clip(v: int) -> int:
    return INT64_MAX if v > INT64_MAX else INT64_MIN if v < INT64_MIN else v


def address(pub: bytes) -> bytes:
    """crypto/ed25519: the first 20 bytes of SHA-256(pubkey)."""
    return hashlib.sha256(pub).digest()[:20]


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


@dataclass
class Val:
    pub: bytes
    power: int
    accum: int = 0

    def __post_init__(self):
        self.address = address(self.pub)


class ValSet:
    """types/validator_set.go, the part that EndBlock updates and proposer
    rotation touch."""

    def __init__(self, vals: Sequence[Val] = ()):
        self.vals: List[Val] = sorted(
            (Val(v.pub, v.power, v.accum) for v in vals), key=lambda v: v.address)
        self.proposer: Optional[Val] = None

    @classmethod
    def new(cls, members: Sequence[Tuple[bytes, int]]) -> "ValSet":
        """NewValidatorSet: sorted by address, then one IncrementAccum."""
        vs = cls([Val(pub, power) for pub, power in members])
        if vs.vals:
            vs.increment_accum(1)
        return vs

    def copy(self) -> "ValSet":
        c = ValSet(self.vals)
        if self.proposer is not None:
            c.proposer = c.vals[self.index_of(self.proposer.address)]
        return c

    # lookup ------------------------------------------------------------------
    def index_of(self, addr: bytes) -> int:
        for i, v in enumerate(self.vals):
            if v.address == addr:
                return i
        return -1

    def members(self) -> List[Tuple[bytes, int]]:
        """(pubkey, power) in the set's order: the order of a commit's
        precommits."""
        return [(v.pub, v.power) for v in self.vals]

    def total_power(self) -> int:
        return sum(v.power for v in self.vals)

    # proposer rotation -------------------------------------------------------
    def _most_accum(self) -> Val:
        """findProposer: the highest accum, ties to the lower address."""
        best = self.vals[0]
        for v in self.vals[1:]:
            if v.accum > best.accum or (
                    v.accum == best.accum and v.address < best.address):
                best = v
        return best

    def increment_accum(self, times: int) -> None:
        if not self.vals:
            raise ValueError("empty validator set")
        for v in self.vals:
            v.accum = _clip(v.accum + _clip(v.power * times))
        total = self.total_power()
        for i in range(times):
            most = self._most_accum()
            most.accum = _clip(most.accum - total)
            if i == times - 1:
                self.proposer = most

    def get_proposer(self) -> Val:
        if self.proposer is None:
            self.proposer = self._most_accum()
        return self.proposer

    # membership (execution.go updateValidators) ------------------------------
    def apply_updates(self, updates: Sequence[Tuple[bytes, int]]) -> None:
        """Each (pubkey, power) in order: power 0 leaves, an unknown key
        joins, a known key is re-powered.  Any change forgets the proposer,
        as Add, Update and Remove do."""
        for pub, power in updates:
            if power < 0:
                raise ValueError(f"voting power can't be negative: {power}")
            i = self.index_of(address(pub))
            if power == 0:
                if i < 0:
                    raise ValueError(
                        f"failed to remove validator {address(pub).hex()}")
                del self.vals[i]
            elif i < 0:
                self.vals.append(Val(pub, power))
                self.vals.sort(key=lambda v: v.address)
            else:
                self.vals[i] = Val(pub, power)
            self.proposer = None

    # hash ----------------------------------------------------------------------
    def hash(self) -> bytes:
        return merkle_root([
            _uvarint(len(v.pub)) + v.pub + _uvarint(v.power << 1)
            for v in self.vals])


class Evolution:
    """state.go MakeGenesisState, then execution.go updateState a block at a
    time.  ``height`` is the height about to be proposed; ``current`` signs
    its commit, ``next`` is the set of ``height + 1`` as far as the blocks so
    far decide it."""

    def __init__(self, genesis: Sequence[Tuple[bytes, int]]):
        self.height = 1
        self.last = ValSet()
        self.current = ValSet.new(genesis)
        self.next = self.current.copy()
        self.next.increment_accum(1)
        # heights whose set is not the height before's, in order
        self.change_heights: List[int] = []

    def header(self) -> Tuple[bytes, bytes, bytes]:
        """(validators_hash, next_validators_hash, proposer_address) that
        the header of ``height`` must state."""
        return (self.current.hash(), self.next.hash(),
                self.current.get_proposer().address)

    def end_block(self, updates: Sequence[Tuple[bytes, int]] = ()) -> None:
        """The block at ``height`` is applied; its EndBlock returned
        ``updates``, which bind at ``height + 2``."""
        n = self.next.copy()
        if updates:
            n.apply_updates(updates)
        n.increment_accum(1)
        self.last, self.current, self.next = self.current, self.next, n
        self.height += 1
        if self.current.hash() != self.last.hash():
            self.change_heights.append(self.height)


def val_tx(pub: bytes, power: int) -> bytes:
    """The transaction that asks the example app for one update."""
    return b"val:" + base64.b64encode(pub) + b"!" + str(power).encode()
