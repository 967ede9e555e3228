"""Validator + ValidatorSet — proposer rotation and commit verification
(ref: types/validator.go, types/validator_set.go).

VerifyCommit is THE signature hot spot of the whole system
(validator_set.go:273-298 serial loop).  Here it collects every non-nil
precommit of the commit and dispatches ONE BatchVerifier call — device-batched
for ed25519 — then tallies voting power.  Error semantics match the reference:
any invalid signature fails the whole commit; nil precommits are fine; stray
precommits for other blocks count for availability but not power.
"""

from __future__ import annotations

import heapq
import struct as _struct
from dataclasses import dataclass, field, replace
from typing import Callable, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.batch import (
    ValsetRows,
    valset_key,
    verify_ed25519_columns,
    verify_generic,
)
from tendermint_tpu.crypto.keys import PubKey, PubKeyEd25519
from tendermint_tpu.encoding.codec import Reader, Writer
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import get_verify_metrics
from tendermint_tpu.types.core import (
    BlockID,
    SignedMsgType,
    canonical_vote_sign_bytes,
)
from tendermint_tpu.types.vote import Vote

_MAX_TOTAL_POWER = 1 << 60  # clip bound (reference uses int64 overflow clips)

# a canonical vote's fixed64 timestamp: behind uvarint(type) = 1 byte,
# fixed64(height) and fixed64(round)
_TS_AT = 17
_PACK_TS = _struct.Struct("<q").pack


def _clip(v: int) -> int:
    return max(-_MAX_TOTAL_POWER, min(_MAX_TOTAL_POWER, v))


@dataclass
class Validator:
    pub_key: PubKey
    voting_power: int
    accum: int = 0

    def __post_init__(self):
        # plain attribute, not a property: address is read on every
        # compare_accum/median-time/begin-block loop iteration and the
        # property+method+cache-lookup chain dominated those loops
        self.address = self.pub_key.address()

    def copy(self) -> "Validator":
        # bypass __init__/__post_init__: three whole-set copies run per
        # applied block (update_state), and the address is already computed
        v = Validator.__new__(Validator)
        v.pub_key = self.pub_key
        v.voting_power = self.voting_power
        v.accum = self.accum
        v.address = self.address
        return v

    def compare_accum(self, other: "Validator") -> "Validator":
        """Higher accum wins; ties break toward the lower address
        (ref validator.go CompareAccum)."""
        if self.accum > other.accum:
            return self
        if self.accum < other.accum:
            return other
        return self if self.address < other.address else other

    def hash_bytes(self) -> bytes:
        """Bytes folded into ValidatorsHash (ref validator.go:104 Bytes =
        pubkey + voting power)."""
        w = Writer()
        w.bytes(self.pub_key.bytes()).svarint(self.voting_power)
        return w.build()


class _MemberColumns(NamedTuple):
    """An all-ed25519 membership as verify_commit's columns: row i is
    validators[i].  Read-only; they depend on keys and powers alone."""

    keys: np.ndarray  # (n, 32) uint8, the raw keys
    powers: np.ndarray  # (n,) int64
    key_id: bytes  # crypto.batch.valset_key(keys)


class _Membership:
    """What every set of one membership shares however often it is copied
    or its accums advance: handed on by copy(), replaced by _invalidate().
    ``columns`` is None until asked for, then a _MemberColumns, or False for
    a membership that does not take the column form."""

    __slots__ = ("columns",)

    def __init__(self):
        self.columns = None


class ValidatorSet:
    """Sorted by address; proposer rotates by accumulated voting power."""

    def __init__(self, validators: Optional[Sequence[Validator]] = None):
        vals = [v.copy() for v in (validators or [])]
        vals.sort(key=lambda v: v.address)
        self.validators: List[Validator] = vals
        self.proposer: Optional[Validator] = None
        self._total_voting_power: Optional[int] = None
        self._addresses: Optional[List[bytes]] = None  # sorted, lazy
        self._hash: Optional[bytes] = None  # memoized; accum-independent
        self._mver = 0  # bumped on any accum/membership change
        self._marshal_cache: Optional[Tuple[int, bytes]] = None
        self._members_blob: Optional[bytes] = None  # encode()'s pubkey section
        self._membership = _Membership()
        self._cow = False  # True => `validators` is shared with another set
        if vals:
            self.increment_accum(1)

    def _materialize(self) -> None:
        """Ensure `validators` is privately owned before any in-place
        mutation.  copy() shares the list copy-on-write: update_state makes
        three whole-set copies per applied block and at most one of them is
        ever mutated (accum advance), so eager deep copies were the single
        largest slice of the fast-sync host ms/block."""
        if self._cow:
            self.validators = [v.copy() for v in self.validators]
            self._cow = False

    def _addr_list(self) -> List[bytes]:
        if self._addresses is None:
            self._addresses = [v.address for v in self.validators]
        return self._addresses

    def _invalidate(self) -> None:
        """Membership changed: drop every derived cache (ref invalidates
        Proposer and totalVotingPower on Add/Update/Remove)."""
        self.proposer = None
        self._total_voting_power = None
        self._addresses = None
        self._hash = None
        self._members_blob = None
        self._membership = _Membership()
        self._mver += 1

    def _member_columns(self) -> Optional[_MemberColumns]:
        """The membership's key and power columns, built once a membership
        (never per commit, height or call); None unless every member's key
        is a PubKeyEd25519 and the powers sum as int64."""
        box = self._membership
        if box.columns is None:
            box.columns = self._build_columns()
        return box.columns or None

    def _build_columns(self):
        vals = self.validators
        if not (
            vals
            and all(type(v.pub_key) is PubKeyEd25519 for v in vals)
            and min(v.voting_power for v in vals) >= 0
            and self.total_voting_power() <= _MAX_TOTAL_POWER
        ):
            return False
        keys = np.frombuffer(
            b"".join(v.pub_key.bytes() for v in vals), dtype=np.uint8
        ).reshape(len(vals), 32)
        return _MemberColumns(
            keys,
            np.array([v.voting_power for v in vals], dtype=np.int64),
            # taken here once a membership and not once a dispatch
            valset_key(keys),
        )

    # size / lookup --------------------------------------------------------
    @property
    def size(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def has_address(self, address: bytes) -> bool:
        return self.get_by_address(address)[0] != -1

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        """Binary search on the sorted-address invariant (ref sort.Search at
        validator_set.go:114) — this sits on the commit-verify hot path."""
        import bisect

        addrs = self._addr_list()
        i = bisect.bisect_left(addrs, address)
        if i < len(addrs) and addrs[i] == address:
            return i, self.validators[i].copy()
        return -1, None

    def get_by_index(self, index: int) -> Tuple[bytes, Optional[Validator]]:
        if 0 <= index < len(self.validators):
            v = self.validators[index]
            return v.address, v.copy()
        return b"", None

    def total_voting_power(self) -> int:
        if self._total_voting_power is None:
            self._total_voting_power = sum(v.voting_power for v in self.validators)
        return self._total_voting_power

    # proposer rotation ----------------------------------------------------
    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
            # marshal() encodes the proposer index: a cache filled while
            # proposer was unset would persist prop_idx=-1 nondeterministically
            self._mver += 1
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        # compare_accum inlined: this runs per applied block (and `times`
        # rounds deep in increment_accum) — higher accum wins, ties break
        # toward the lower address
        best = self.validators[0]
        ba, baddr = best.accum, best.address
        for v in self.validators[1:]:
            a = v.accum
            if a > ba or (a == ba and v.address < baddr):
                best, ba, baddr = v, a, v.address
        return best

    def increment_accum(self, times: int) -> None:
        """accum += power·times for all; then `times` rounds of: highest-accum
        becomes proposer, minus totalPower (ref validator_set.go:65-88)."""
        if not self.validators:
            raise ValueError("empty validator set")
        self._materialize()
        self._mver += 1  # accums change -> cached marshal bytes stale
        # _clip inlined (bounds semantics of the reference's int64-overflow
        # clips): two clipped adds per validator per block made this the
        # hottest line of fast-sync apply
        hi, lo = _MAX_TOTAL_POWER, -_MAX_TOTAL_POWER
        for v in self.validators:
            d = v.voting_power * times
            if d > hi:
                d = hi
            elif d < lo:
                d = lo
            a = v.accum + d
            v.accum = hi if a > hi else (lo if a < lo else a)
        total = self.total_voting_power()
        for i in range(times):
            mostest = self._find_proposer()
            a = mostest.accum - total
            mostest.accum = hi if a > hi else (lo if a < lo else a)
            if i == times - 1:
                self.proposer = mostest

    def copy(self) -> "ValidatorSet":
        # O(1): the validator list is SHARED until either side mutates
        # (_materialize above) — callers see deep-copy semantics throughout
        new = ValidatorSet.__new__(ValidatorSet)
        new.validators = self.validators
        new._cow = True
        self._cow = True
        new.proposer = self.proposer
        new._total_voting_power = self._total_voting_power
        new._addresses = self._addresses  # same membership (rebuilt-if-None)
        new._hash = self._hash  # membership identical; accum changes don't matter
        new._members_blob = self._members_blob
        new._membership = self._membership
        new._mver = 0
        new._marshal_cache = (
            (0, self._marshal_cache[1])
            if self._marshal_cache is not None and self._marshal_cache[0] == self._mver
            else None
        )
        return new

    def copy_increment_accum(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_accum(times)
        return c

    # membership updates (driven by ABCI EndBlock) -------------------------
    def add(self, val: Validator) -> bool:
        """Insert keeping address order; invalidates caches
        (ref validator_set.go:189-212)."""
        if self.has_address(val.address):
            return False
        self._materialize()
        self.validators.append(val.copy())
        self.validators.sort(key=lambda v: v.address)
        self._invalidate()
        return True

    def update(self, val: Validator) -> bool:
        """Wholesale replacement, accum included (ref validator_set.go:216-226:
        `vals.Validators[index] = val.Copy()`)."""
        idx, _ = self.get_by_address(val.address)
        if idx == -1:
            return False
        self._materialize()
        self.validators[idx] = val.copy()
        self._invalidate()
        return True

    def remove(self, address: bytes) -> Optional[Validator]:
        idx, _ = self.get_by_address(address)
        if idx == -1:
            return None
        self._materialize()
        removed = self.validators.pop(idx)
        self._invalidate()
        return removed

    # hashing --------------------------------------------------------------
    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [v.hash_bytes() for v in self.validators]
            )
        return self._hash

    # THE hot path ---------------------------------------------------------
    def _scan_commit(self, block_id: BlockID, height: int, commit):
        """Structural checks, and what the non-nil precommits carry.  The
        ONE place the per-precommit validity rules live: collect_commit_sigs
        and verify_commit's column form are both made from what it returns,
        ``(round, absent, timestamps, sigs, strays)`` — the indices of the
        nil precommits, a timestamp and a signature a present one, and
        ``(j, block id)`` for the j-th present precommit where it votes
        another block.  Raises CommitError."""
        precommits = commit.precommits
        if self.size != len(precommits):
            raise CommitError(
                f"wrong set size: {self.size} vs {len(precommits)}"
            )
        if height != commit.height():
            raise CommitError(f"wrong height: {height} vs {commit.height()}")
        if block_id != commit.block_id:
            raise CommitError("wrong block id")

        round = commit.round()
        precommit_type = SignedMsgType.PRECOMMIT
        # a precommit's block id against block_id: the same object (votes
        # made in process share it) or, field by field, an equal one (every
        # vote decoded from the wire carries its own); the dataclass __eq__
        # builds four tuples a precommit to say the same
        b_hash = block_id.hash
        b_parts = block_id.parts_header
        b_parts_hash, b_total = b_parts.hash, b_parts.total
        absent, timestamps, sigs, strays = [], [], [], []
        for idx, precommit in enumerate(precommits):
            if precommit is None:
                absent.append(idx)
                continue
            if precommit.height != height:
                raise CommitError(f"precommit height {precommit.height} != {height}")
            if precommit.round != round:
                raise CommitError(f"precommit round {precommit.round} != {round}")
            if precommit.vote_type != precommit_type:
                raise CommitError(f"not a precommit @ index {idx}")
            key = precommit.block_id
            if key is not block_id:
                parts = key.parts_header
                if (
                    key.hash != b_hash
                    or parts.hash != b_parts_hash
                    or parts.total != b_total
                ):  # stray vote: counts for availability, not power
                    strays.append((len(sigs), key))
            timestamps.append(precommit.timestamp_ns)
            sigs.append(precommit.signature)
        return round, absent, timestamps, sigs, strays

    def collect_commit_sigs(
        self, chain_id: str, block_id: BlockID, height: int, commit
    ) -> Tuple[List[PubKey], List[bytes], List[bytes], List[int]]:
        """Structural checks + (pubkeys, msgs, sigs, powers) for every non-nil
        precommit; powers[j] is 0 for precommits voting a different block.
        Shared by the single-commit path below and fast sync's windowed batch
        (blockchain/reactor.verify_block_window), whose 64-precommit calls
        stay plain Python. Raises CommitError."""
        return self._commit_lists(
            chain_id, block_id, height,
            self._scan_commit(block_id, height, commit),
        )

    def _commit_lists(self, chain_id, block_id, height, scan):
        round, absent, timestamps, sigs, strays = scan
        # Canonical precommit sign-bytes differ across validators ONLY in the
        # fixed64 timestamp at offset 17 (uvarint(type)=1 + fixed64(height)=8
        # + fixed64(round)=8) — and in block_id for stray votes. One template
        # a distinct block_id, the timestamps patched in, instead of
        # re-encoding ~110 bytes per precommit (the sign-bytes assembly was
        # a top host cost of fast sync; ref loop types/validator_set.go:281).
        tpl = canonical_vote_sign_bytes(
            chain_id, SignedMsgType.PRECOMMIT, height, round, 0, block_id
        )
        head, tail = tpl[:_TS_AT], tpl[_TS_AT + 8:]
        msgs = [head + ts + tail for ts in map(_PACK_TS, timestamps)]
        vals = self.validators
        if absent:
            gone = set(absent)
            vals = [v for i, v in enumerate(vals) if i not in gone]
        pubkeys = [v.pub_key for v in vals]
        powers = [v.voting_power for v in vals]
        templates = {}
        for j, key in strays:
            tpl = templates.get(key)
            if tpl is None:
                tpl = templates[key] = canonical_vote_sign_bytes(
                    chain_id, SignedMsgType.PRECOMMIT, height, round, 0, key
                )
            msgs[j] = tpl[:_TS_AT] + _PACK_TS(timestamps[j]) + tpl[_TS_AT + 8:]
            powers[j] = 0
        return pubkeys, msgs, sigs, powers

    @staticmethod
    def _valset_rows(members: _MemberColumns, absent: List[int]) -> ValsetRows:
        """Which rows of an all-ed25519 membership's key array a commit's
        lanes are, whichever form they go down in: the present slots, None
        where every slot is.  The kernel's host wrapper keeps what it
        derives from the keys a membership, not a height's subset of them."""
        slots = np.delete(np.arange(len(members.keys)), absent) if absent else None
        return ValsetRows(members.key_id, members.keys, slots)

    def _commit_columns(self, chain_id, block_id, height, scan, powers, rows):
        """The lanes of an all-ed25519 commit as columns, (keys (n, 32),
        sign-bytes (n, ln), signatures (n, 64), powers (n,)): the same lanes
        _commit_lists makes, with no object a lane; ``powers`` and ``rows``
        are the membership's, ``rows.slots`` the present slots.  None where
        a lane does not fit a column (a signature not 64 bytes, a stray vote
        whose sign-bytes have another length): the lists decide such a
        commit."""
        round, _absent, timestamps, sigs, strays = scan
        n = len(sigs)
        if set(map(len, sigs)) != {64}:
            return None
        tpl = canonical_vote_sign_bytes(
            chain_id, SignedMsgType.PRECOMMIT, height, round, 0, block_id
        )
        msgs = np.empty((n, len(tpl)), dtype=np.uint8)
        msgs[:] = np.frombuffer(tpl, dtype=np.uint8)
        keys = rows.keys
        if rows.slots is not None:
            keys, powers = keys[rows.slots], powers[rows.slots]
        if strays:
            powers = powers.copy()
            for j, key in strays:
                tpl = canonical_vote_sign_bytes(
                    chain_id, SignedMsgType.PRECOMMIT, height, round, 0, key
                )
                if len(tpl) != msgs.shape[1]:
                    return None
                msgs[j] = np.frombuffer(tpl, dtype=np.uint8)
                powers[j] = 0
        msgs[:, _TS_AT:_TS_AT + 8] = np.frombuffer(
            _struct.pack(f"<{n}q", *timestamps), dtype=np.uint8
        ).reshape(n, 8)
        sigs = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
        return keys, msgs, sigs, powers

    def verify_commit(
        self, chain_id: str, block_id: BlockID, height: int, commit, verifier=None
    ) -> None:
        """Raise unless +2/3 of this set signed blockID at height.

        One BatchVerifier dispatch for all non-nil precommits (the reference
        loops serially at validator_set.go:273-298).  An all-ed25519 set's
        lanes go down as columns (numpy arrays) whose keys and powers are
        the membership's own; any other set's, and a commit with a lane
        that fits no column, as lists through verify_generic."""
        with trace.span(
            "commit.verify", height=height, n=len(commit.precommits)
        ):
            # not inside collect_commit_sigs: fast sync calls that per block
            with trace.span("commit.collect", n=len(commit.precommits)) as sp:
                scan = self._scan_commit(block_id, height, commit)
                _round, absent, _timestamps, present, strays = scan
                sp.set(absent=len(absent), strays=len(strays))
                members = self._member_columns()
                rows = members and self._valset_rows(members, absent)
                columns = rows and self._commit_columns(
                    chain_id, block_id, height, scan, members.powers, rows
                )
                if not columns:
                    pubkeys, msgs, sigs, powers = self._commit_lists(
                        chain_id, block_id, height, scan
                    )
            try:
                vm = get_verify_metrics()
                vm.commit_collect.add(
                    1.0, ("columns" if columns else "lists",)
                )
                held = vm.commit_precommits
                held.add(float(len(present) - len(strays)), ("for_block",))
                held.add(float(len(strays)), ("stray",))
                held.add(float(len(absent)), ("absent",))
            except Exception:
                pass
            if columns:
                keys, msgs, sigs, powers = columns
                ok = verify_ed25519_columns(
                    keys, msgs, sigs, verifier=verifier, valset=rows
                )
            else:
                ok = verify_generic(
                    pubkeys, msgs, sigs, verifier=verifier, valset=rows
                )
            with trace.span("commit.tally", n=len(ok)):
                if not ok.all():
                    raise CommitError("invalid signature in commit")
                tallied = int(powers.sum()) if columns else sum(powers)
                if tallied * 3 <= self.total_voting_power() * 2:
                    raise CommitError(
                        f"insufficient voting power: got {tallied}, "
                        f"needed more than "
                        f"{self.total_voting_power() * 2 // 3}"
                    )

    def verify_future_commit(
        self, new_set: "ValidatorSet", chain_id: str, block_id: BlockID, height: int,
        commit, verifier=None,
    ) -> None:
        """Light-client rule (validator_set.go:339): the commit must be valid
        for the NEW set, and also signed by +2/3 of the OLD set's power."""
        new_set.verify_commit(chain_id, block_id, height, commit, verifier=verifier)

        old_voting_power = 0
        seen = set()
        round = commit.round()
        idxs, pubkeys, msgs, sigs, powers = [], [], [], [], []
        for idx, precommit in enumerate(commit.precommits):
            if precommit is None:
                continue
            if precommit.height != height:
                raise CommitError("precommit height mismatch")
            if precommit.round != round:
                raise CommitError("precommit round mismatch")
            if precommit.vote_type != SignedMsgType.PRECOMMIT:
                raise CommitError("not a precommit")
            old_idx, val = self.get_by_address(precommit.validator_address)
            if val is None or old_idx in seen:
                continue
            seen.add(old_idx)
            pubkeys.append(val.pub_key)
            msgs.append(precommit.sign_bytes(chain_id))
            sigs.append(precommit.signature)
            powers.append((val.voting_power, precommit.block_id))

        ok = verify_generic(pubkeys, msgs, sigs, verifier=verifier)
        for j in range(len(pubkeys)):
            if not ok[j]:
                raise CommitError("invalid signature (old set)")
            power, pc_block_id = powers[j]
            if block_id == pc_block_id:
                old_voting_power += power

        if old_voting_power * 3 <= self.total_voting_power() * 2:
            raise TooMuchChangeError(
                f"invalid commit -- insufficient old voting power: got "
                f"{old_voting_power}"
            )

    # codec ----------------------------------------------------------------
    def _members_bytes(self) -> bytes:
        """Pubkey section of the encoding (type names + raw keys), cached
        until membership changes: accums advance every applied block, so
        encode() runs per block, but the membership almost never changes —
        only the two small power/accum arrays need fresh bytes."""
        if self._members_blob is None:
            w = Writer()
            for v in self.validators:
                w.string(v.pub_key.type_name)
                w.bytes(v.pub_key.bytes())
            self._members_blob = w.build()
        return self._members_blob

    _CODEC_VERSION = 2  # 1 = per-validator svarint records (retired)

    def encode(self, w: Writer) -> None:
        vals = self.validators
        w.uvarint(self._CODEC_VERSION)
        w.uvarint(len(vals))
        w.bytes(self._members_bytes())
        w.bytes(_struct.pack(f"<{len(vals)}q", *(v.voting_power for v in vals)))
        w.bytes(_struct.pack(f"<{len(vals)}q", *(v.accum for v in vals)))
        prop_idx = -1
        if self.proposer is not None:
            for i, v in enumerate(vals):
                if v.address == self.proposer.address:
                    prop_idx = i
                    break
        w.svarint(prop_idx)

    def marshal(self) -> bytes:
        """Memoized until accum/membership changes — save_state re-encodes
        three valsets per block and two of them are always unchanged."""
        if self._marshal_cache is not None and self._marshal_cache[0] == self._mver:
            return self._marshal_cache[1]
        w = Writer()
        self.encode(w)
        out = w.build()
        self._marshal_cache = (self._mver, out)
        return out

    @classmethod
    def decode(cls, r: Reader) -> "ValidatorSet":
        from tendermint_tpu.crypto.keys import _PUBKEY_TYPES

        ver = r.uvarint()
        if ver != cls._CODEC_VERSION:
            raise ValueError(
                f"validator-set codec version {ver} unsupported "
                f"(this build reads {cls._CODEC_VERSION}); "
                "regenerate the state dir"
            )
        n = r.uvarint()
        members_blob = r.bytes()
        mr = Reader(members_blob)
        pks = [_PUBKEY_TYPES[mr.string()](mr.bytes()) for _ in range(n)]
        powers = _struct.unpack(f"<{n}q", r.bytes())
        accums = _struct.unpack(f"<{n}q", r.bytes())
        vals = [
            Validator(pub_key=pk, voting_power=p, accum=a)
            for pk, p, a in zip(pks, powers, accums)
        ]
        prop_idx = r.svarint()
        vs = cls.__new__(cls)
        vs.validators = vals
        vs._total_voting_power = None
        vs._addresses = None
        vs._hash = None
        vs._mver = 0
        vs._marshal_cache = None
        vs._members_blob = members_blob
        vs._membership = _Membership()
        vs._cow = False
        vs.proposer = vals[prop_idx] if 0 <= prop_idx < len(vals) else None
        return vs

    @classmethod
    def unmarshal(cls, data: bytes) -> "ValidatorSet":
        return cls.decode(Reader(data))

    def __iter__(self):
        return iter(self.validators)


class CommitError(Exception):
    pass


class TooMuchChangeError(CommitError):
    """Old set signed < 2/3 of a future commit (lite client bisection trigger)."""
