"""k-of-n threshold multisig pubkey + compact bit array.

Mirrors reference crypto/multisig/threshold_pubkey.go:34 (VerifyBytes walks the
sub-signatures in pubkey order, guided by a compact bit array) and
crypto/multisig/bitarray/compact_bit_array.go.

Two readers of one marshalled signature live here, on purpose apart:

* ``PubKeyMultisigThreshold.verify_bytes`` is the host oracle: it unmarshals
  into a ``Multisignature`` and walks it as the Go does.
* ``flatten_columns`` is the batch path (BASELINE.json configs[4]): a multisig
  verify over a batch of validators decomposes into the same three columns
  (32-byte key, message, 64-byte signature) the ed25519 batch kernel consumes.
  It reads the marshalled bytes in place and appends every flagged
  sub-signature of every member as one lane to the caller's columns, with the
  (result index, start, count) of each member's run of lanes; it builds no
  ``Multisignature``, ``CompactBitArray`` or tuple a lane.  A member it cannot
  flatten goes to the host list, which ``verify_bytes`` decides.
  ``PubKeyMultisigThreshold.flatten`` is its one-member case.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.crypto.hashing import tmhash_truncated
from tendermint_tpu.crypto.keys import PubKey, PubKeyEd25519


class CompactBitArray:
    """Bit array with minimal byte storage (cf. compact_bit_array.go)."""

    def __init__(self, bits: int):
        if bits < 0:
            raise ValueError("negative size")
        self.bits = bits
        self.elems = bytearray((bits + 7) // 8)

    def get_index(self, i: int) -> bool:
        if i < 0 or i >= self.bits:
            return False
        return bool(self.elems[i >> 3] & (1 << (7 - (i % 8))))

    def set_index(self, i: int, v: bool) -> bool:
        if i < 0 or i >= self.bits:
            return False
        if v:
            self.elems[i >> 3] |= 1 << (7 - (i % 8))
        else:
            self.elems[i >> 3] &= ~(1 << (7 - (i % 8))) & 0xFF
        return True

    def num_true_bits_before(self, index: int) -> int:
        return sum(1 for i in range(index) if self.get_index(i))

    def count(self) -> int:
        return self.num_true_bits_before(self.bits)

    def __eq__(self, other):
        return (
            isinstance(other, CompactBitArray)
            and self.bits == other.bits
            and self.elems == other.elems
        )

    def to_bytes(self) -> bytes:
        return self.bits.to_bytes(4, "big") + bytes(self.elems)

    @staticmethod
    def from_bytes(data: bytes) -> "CompactBitArray":
        if len(data) < 4:
            raise ValueError("bit array: truncated")
        bits = int.from_bytes(data[:4], "big")
        if len(data) - 4 < (bits + 7) // 8:  # before bits/8 bytes are made
            raise ValueError("bit array: truncated")
        ba = CompactBitArray(bits)
        ba.elems = bytearray(data[4 : 4 + (bits + 7) // 8])
        return ba


@dataclass
class Multisignature:
    """Ordered sub-signatures + participation bitmap (cf. multisignature.go)."""

    bitarray: CompactBitArray
    sigs: List[bytes] = field(default_factory=list)

    @staticmethod
    def new(n: int) -> "Multisignature":
        return Multisignature(CompactBitArray(n))

    def add_signature_from_pubkey(
        self, sig: bytes, pubkey: PubKey, keys: Sequence[PubKey]
    ) -> None:
        index = next((i for i, k in enumerate(keys) if k.equals(pubkey)), -1)
        if index < 0:
            raise ValueError("pubkey not in multisig key set")
        new_sig_index = self.bitarray.num_true_bits_before(index)
        if self.bitarray.get_index(index):
            self.sigs[new_sig_index] = sig  # replace
            return
        self.bitarray.set_index(index, True)
        self.sigs.insert(new_sig_index, sig)

    def marshal(self) -> bytes:
        out = self.bitarray.to_bytes()
        out += len(self.sigs).to_bytes(2, "big")
        for s in self.sigs:
            out += len(s).to_bytes(2, "big") + s
        return out

    @staticmethod
    def unmarshal(data: bytes) -> "Multisignature":
        """Raises ValueError on bytes ``marshal`` cannot have written: short
        of what their length fields announce, or with bytes left over (amino's
        UnmarshalBinaryBare refuses both, so VerifyBytes says false)."""
        ba = CompactBitArray.from_bytes(data)
        off = 4 + (ba.bits + 7) // 8
        if off + 2 > len(data):
            raise ValueError("multisignature: truncated")
        nsigs = int.from_bytes(data[off : off + 2], "big")
        off += 2
        sigs = []
        for _ in range(nsigs):
            ln = int.from_bytes(data[off : off + 2], "big")
            off += 2
            if off + ln > len(data):
                raise ValueError("multisignature: truncated")
            sigs.append(data[off : off + ln])
            off += ln
        if off != len(data):
            raise ValueError("multisignature: bytes left over")
        return Multisignature(ba, sigs)


@dataclass(frozen=True)
class PubKeyMultisigThreshold(PubKey):
    """k-of-n threshold key (cf. threshold_pubkey.go:11)."""

    k: int
    pubkeys: Tuple[PubKey, ...]
    type_name = "tendermint/PubKeyMultisigThreshold"

    def __post_init__(self):
        if self.k <= 0:
            raise ValueError("threshold k must be positive")
        if len(self.pubkeys) < self.k:
            raise ValueError("threshold k cannot exceed number of keys")
        # what flatten_columns reads of a key, made once: the signature's
        # first four bytes (bits == n), the bit array's length and the mask
        # of its last byte's real bits, and each sub-key's 32 bytes (None:
        # not ed25519, so a signature that flags it is the host's)
        n = len(self.pubkeys)
        subkeys = tuple(
            pk.bytes() if pk.type_name == PubKeyEd25519.type_name else None
            for pk in self.pubkeys
        )
        object.__setattr__(self, "_flat", (
            self.k, n.to_bytes(4, "big"), (n + 7) // 8,
            0xFF & (0xFF << (-n % 8)), subkeys,
        ))

    def address(self) -> bytes:
        return tmhash_truncated(self.bytes())

    def bytes(self) -> bytes:
        out = self.k.to_bytes(4, "big") + len(self.pubkeys).to_bytes(4, "big")
        for pk in self.pubkeys:
            tb = pk.type_name.encode()
            out += len(tb).to_bytes(1, "big") + tb
            kb = pk.bytes()
            out += len(kb).to_bytes(2, "big") + kb
        return out

    @classmethod
    def from_bytes(cls, data: bytes) -> "PubKeyMultisigThreshold":
        """The key ``bytes()`` wrote; sub-keys of any registered type.
        Raises ValueError on anything else."""
        from tendermint_tpu.crypto.keys import _PUBKEY_TYPES

        if len(data) < 8:
            raise ValueError("multisig pubkey: truncated")
        k = int.from_bytes(data[:4], "big")
        n = int.from_bytes(data[4:8], "big")
        off = 8
        pubkeys = []
        for _ in range(n):
            if off + 1 > len(data):
                raise ValueError("multisig pubkey: truncated")
            tl = data[off]
            name = data[off + 1 : off + 1 + tl]
            off += 1 + tl
            if off + 2 > len(data):
                raise ValueError("multisig pubkey: truncated")
            kl = int.from_bytes(data[off : off + 2], "big")
            off += 2
            if off + kl > len(data):
                raise ValueError("multisig pubkey: truncated")
            decode = _PUBKEY_TYPES.get(name.decode("utf-8", "replace"))
            if decode is None:
                raise ValueError(f"multisig pubkey: unknown sub-key type {name!r}")
            pubkeys.append(decode(data[off : off + kl]))
            off += kl
        if off != len(data):
            raise ValueError("multisig pubkey: bytes left over")
        return cls(k, tuple(pubkeys))

    def verify_bytes(self, msg: bytes, sig: bytes) -> bool:
        try:
            multisig = Multisignature.unmarshal(sig)
        except Exception:
            return False
        size = multisig.bitarray.bits
        if len(self.pubkeys) != size:
            return False
        # threshold_pubkey.go:46: the signature list is k..n long
        if len(multisig.sigs) < self.k or len(multisig.sigs) > size:
            return False
        # adversarial bytes can flag more signers than signatures supplied —
        # reject instead of indexing out of range (the reference would panic).
        # count < len(sigs) (unused trailing sigs, at most n in all) stays
        # ACCEPTED: the reference only indexes flagged entries and never
        # looks at the rest
        if multisig.bitarray.count() > len(multisig.sigs):
            return False
        # each flagged signer must verify (threshold_pubkey.go:41-55)
        sig_index = 0
        for i in range(size):
            if multisig.bitarray.get_index(i):
                if not self.pubkeys[i].verify_bytes(msg, multisig.sigs[sig_index]):
                    return False
                sig_index += 1
        return sig_index >= self.k

    def flatten(
        self, msg: bytes, sig: bytes
    ) -> Optional[List[Tuple[bytes, bytes, bytes]]]:
        """Decompose into (pubkey32, msg, sig64) tuples for the TPU batch path:
        ``flatten_columns`` over this one member.  Returns None where that
        leaves the member to the host (structurally invalid, a flagged
        sub-key that is not ed25519, fewer flagged signers than k)."""
        pubs: List[bytes] = []
        lane_msgs: List[bytes] = []
        lane_sigs: List[bytes] = []
        groups = flatten_columns(
            (self,), (msg,), (sig,), (0,), pubs, lane_msgs, lane_sigs
        )
        if groups.host:
            return None
        return list(zip(pubs, lane_msgs, lane_sigs))

    def __hash__(self):
        return hash((self.k, self.pubkeys))


# -- the batch path: lanes in column form -------------------------------------

# the set bits of one bit-array byte, most significant first
# (compact_bit_array.go GetIndex: bit i is elems[i >> 3] & (1 << (7 - i % 8)))
_SET_BITS = tuple(
    tuple(i for i in range(8) if byte & (0x80 >> i)) for byte in range(256)
)


class FlatGroups(NamedTuple):
    """The members ``flatten_columns`` flattened, one entry each in three
    integer arrays, and those it left to the host."""

    member: np.ndarray  # the member's place in the call (its result index)
    start: np.ndarray  # its first lane's place in the columns
    lanes: np.ndarray  # its lanes, k..n, contiguous from ``start``
    host: List[int]  # members for ``verify_bytes``, by place in the call


def flatten_columns(
    pubkeys: Sequence["PubKeyMultisigThreshold"],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    members: Sequence[int],
    pubs: List[bytes],
    lane_msgs: List[bytes],
    lane_sigs: List[bytes],
) -> FlatGroups:
    """Every multisig member of one call, ``pubkeys[i]`` / ``msgs[i]`` /
    ``sigs[i]`` for ``i`` in ``members``, read in place from the marshalled
    signature

        bits:u32be  ceil(bits/8) bytes  count:u16be  count x (len:u16be sig)

    A member that can ride the ed25519 batch gets one lane a flagged
    sub-signature, in key order, APPENDED to the caller's three columns
    ``pubs`` (32-byte sub-key), ``lane_msgs`` and ``lane_sigs`` (64 bytes),
    and its (place, first lane, lanes) in the arrays returned.  Any other goes
    on ``host``: its verdict is ``verify_bytes``', which stays the
    independent reading of the same rules (threshold_pubkey.go:34-60).  A
    member flattens only if

    * the bytes are what ``Multisignature.marshal`` writes: nothing short of
      what a length field announces, nothing left over;
    * ``bits`` is the key's n, and k <= ``count`` <= n (:41, :46);
    * the flagged bits (pad bits of the last byte are not bits) number at
      least k (:50) and at most ``count`` (the Go would index out of range);
    * every flagged sub-key is ed25519 and its sub-signature 64 bytes long.

    Signatures no bit points to are parsed and otherwise not looked at, as
    the Go never looks at them.
    """
    member: List[int] = []
    start: List[int] = []
    lanes: List[int] = []
    host: List[int] = []
    set_bits = _SET_BITS
    for i in members:
        k, head, nbytes, pad, subkeys = pubkeys[i]._flat
        sig = sigs[i]
        at = 4 + nbytes  # of the 2-byte count
        if len(sig) < at + 2 or sig[:4] != head:
            host.append(i)
            continue
        flagged = [
            8 * j + b for j in range(nbytes - 1) for b in set_bits[sig[4 + j]]
        ]
        flagged += [8 * (nbytes - 1) + b for b in set_bits[sig[at - 1] & pad]]
        nsigs = (sig[at] << 8) | sig[at + 1]
        if not (k <= len(flagged) <= nsigs <= len(subkeys)):
            host.append(i)
            continue
        subsigs = _walk_subsignatures(sig, at + 2, nsigs, len(flagged))
        keys = [subkeys[b] for b in flagged]
        if subsigs is None or None in keys:  # None: a sub-key not ed25519
            host.append(i)
            continue
        member.append(i)
        start.append(len(pubs))
        lanes.append(len(keys))
        pubs += keys
        lane_msgs += [msgs[i]] * len(keys)
        lane_sigs += subsigs
    return FlatGroups(
        np.array(member, dtype=np.intp),
        np.array(start, dtype=np.intp),
        np.array(lanes, dtype=np.intp),
        host,
    )


def _walk_subsignatures(
    sig: bytes, at: int, nsigs: int, lanes: int
) -> Optional[List[bytes]]:
    """The first ``lanes`` of the ``nsigs`` length-prefixed sub-signatures
    from ``sig[at:]``, or None: one of them is not 64 bytes long, a length
    field announces more than is there, or bytes are left over."""
    size = len(sig)
    out = []
    for j in range(nsigs):
        if at + 2 > size:
            return None
        ln = (sig[at] << 8) | sig[at + 1]
        at += 2
        if at + ln > size:
            return None
        if j < lanes:
            if ln != 64:
                return None
            out.append(sig[at : at + 64])
        at += ln
    return out if at == size else None
