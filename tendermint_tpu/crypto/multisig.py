"""k-of-n threshold multisig pubkey + compact bit array.

Mirrors reference crypto/multisig/threshold_pubkey.go:34 (VerifyBytes walks the
sub-signatures in pubkey order, guided by a compact bit array) and
crypto/multisig/bitarray/compact_bit_array.go.

TPU note: a multisig verify over a batch of validators decomposes into the same
flat (pubkey, msg, sig) tensor the ed25519 batch kernel consumes; Multisignature
provides `flatten()` for that path (BASELINE.json configs[4]).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from tendermint_tpu.crypto.hashing import tmhash_truncated
from tendermint_tpu.crypto.keys import PubKey


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
        ba = CompactBitArray(bits)
        ba.elems = bytearray(data[4 : 4 + (bits + 7) // 8])
        if len(ba.elems) != (bits + 7) // 8:
            raise ValueError("bit array: truncated")
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
        """Decompose into (pubkey32, msg, sig64) tuples for the TPU batch path.
        Returns None if structurally invalid or any sub-key is not ed25519."""
        try:
            multisig = Multisignature.unmarshal(sig)
        except Exception:
            return None
        if multisig.bitarray.bits != len(self.pubkeys):
            return None
        if not self.k <= len(multisig.sigs) <= len(self.pubkeys):
            return None  # verify_bytes' bound (threshold_pubkey.go:46)
        if multisig.bitarray.count() > len(multisig.sigs):
            return None  # mirrors verify_bytes' out-of-range rejection
        out = []
        sig_index = 0
        for i in range(len(self.pubkeys)):
            if multisig.bitarray.get_index(i):
                pk = self.pubkeys[i]
                if pk.type_name != "tendermint/PubKeyEd25519":
                    return None
                if sig_index >= len(multisig.sigs):
                    return None
                sub = multisig.sigs[sig_index]
                if len(sub) != 64:
                    # unmarshal accepts any sub-sig length; a short one would
                    # crash the whole batched dispatch downstream (frombuffer
                    # reshape) — bail to the host path, which returns False
                    return None
                out.append((pk.bytes(), msg, sub))
                sig_index += 1
        return out

    def __hash__(self):
        return hash((self.k, self.pubkeys))
