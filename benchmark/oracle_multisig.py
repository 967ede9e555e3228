"""The benchmark's own k-of-n threshold multisig verifier: the plain reference
every validator's verdict, and every sub-signature lane, is compared with.
It imports nothing of the program; sub-signatures are decided by
``benchmark/oracle.py`` (the Go ed25519 accept set).

The rules are those of Tendermint v0.26.2, ``crypto/multisig/
threshold_pubkey.go:34-60`` ``VerifyBytes`` over ``bitarray/
compact_bit_array.go``, one line of Go to a line here:

    unmarshal the signature                      else false
    size := sig.BitArray.Size()
    len(pk.PubKeys) != size                      -> false   (:41)
    len(sig.Sigs) < k || len(sig.Sigs) > size    -> false   (:46)
    sig.BitArray.NumTrueBitsBefore(size) < k     -> false   (:50)
    for i in 0..size: if GetIndex(i):
        !PubKeys[i].VerifyBytes(msg, Sigs[sigIndex]) -> false; sigIndex++
    true

and of ``types/validator_set.go:260`` ``VerifyCommit`` for the commit: every
present precommit must verify, and the power that signed the block id must
be more than two thirds of the set's.

Where this follows the Go and not this repo's Python (``tendermint_tpu/
crypto/multisig.py`` as it stood before PR 39):

* ``len(sig.Sigs) > size`` is refused (``:46``).  The Python never looked:
  six signatures beside five keys passed while three bits were set and their
  three signatures verified (ROADMAP D13).
* The bit count is tested BEFORE any signature is looked at (``:50``); the
  Python counted flagged signers as it verified them and compared at the
  end.  The verdicts are the same; which rule refuses first is not, and
  ``Verdict.rule`` names the Go's.
* A signature with bytes left over, or short of what its length fields
  announce, is no signature: amino's ``UnmarshalBinaryBare`` returns an error
  for both and ``VerifyBytes`` says false.  The Python sliced what was there.
* Bit ``i`` is ``elems[i >> 3] & (1 << (7 - i % 8))``, most significant bit
  first, as ``compact_bit_array.go`` ``GetIndex``.

The one place where it departs from the Go: **more bits set than signatures
supplied** (``flag_without_sig``).  The Go indexes ``sig.Sigs[sigIndex]`` out
of range and panics, which takes the node down inside ``VerifyCommit``; a
verifier cannot answer with a crash, so the reference here says false
(``rule="flag_without_sig"``), and so must the program.

The wire form of key and signature is this repo's length-prefixed encoding,
not amino (the configuration lists it under ``assumed``); it is parsed here
from the bytes, by this file's own code:

    key        k:u32be  n:u32be  n x ( len:u8 type_name  len:u16be key )
    signature  bits:u32be  ceil(bits/8) bytes  count:u16be
               count x ( len:u16be sub-signature )
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from benchmark import oracle

ED25519 = b"tendermint/PubKeyEd25519"


@dataclass
class Verdict:
    """One validator's precommit by the reference."""

    ok: bool
    rule: str  # "ok", or the rule that refused
    # the sub-signatures the walk looks at, in key order, as ed25519 lanes
    # (pub32, msg, sig) with the oracle's verdict on each; empty where a size
    # rule refused before the walk.  The Go stops at the first lane that
    # fails; every lane is decided here, so that a device can be held to each.
    lanes: List[Tuple[bytes, bytes, bytes]] = field(default_factory=list)
    lane_ok: List[bool] = field(default_factory=list)


def parse_pubkey(data: bytes) -> Optional[Tuple[int, List[bytes]]]:
    """(k, the n ed25519 sub-keys) or None: malformed, or a sub-key of
    another type (this deployment has none)."""
    if len(data) < 8:
        return None
    k = int.from_bytes(data[:4], "big")
    n = int.from_bytes(data[4:8], "big")
    off, keys = 8, []
    for _ in range(n):
        if off + 1 > len(data):
            return None
        tl = data[off]
        name = data[off + 1: off + 1 + tl]
        off += 1 + tl
        if off + 2 > len(data):
            return None
        kl = int.from_bytes(data[off: off + 2], "big")
        off += 2
        key = data[off: off + kl]
        off += kl
        if name != ED25519 or len(key) != kl or kl != 32:
            return None
        keys.append(key)
    if off != len(data) or k <= 0 or n < k:
        return None
    return k, keys


def parse_signature(data: bytes) -> Optional[Tuple[int, bytes, List[bytes]]]:
    """(bits, the bit array's bytes, the sub-signatures) or None."""
    if len(data) < 4:
        return None
    bits = int.from_bytes(data[:4], "big")
    nbytes = (bits + 7) // 8
    elems = data[4: 4 + nbytes]
    off = 4 + nbytes
    if len(elems) != nbytes or off + 2 > len(data):
        return None
    count = int.from_bytes(data[off: off + 2], "big")
    off += 2
    sigs = []
    for _ in range(count):
        if off + 2 > len(data):
            return None
        ln = int.from_bytes(data[off: off + 2], "big")
        off += 2
        if off + ln > len(data):
            return None
        sigs.append(data[off: off + ln])
        off += ln
    if off != len(data):
        return None
    return bits, elems, sigs


def get_index(elems: bytes, bits: int, i: int) -> bool:
    """compact_bit_array.go GetIndex."""
    if i < 0 or i >= bits:
        return False
    return bool(elems[i >> 3] & (1 << (7 - i % 8)))


def verify_bytes(key: bytes, msg: bytes, sig: bytes) -> Verdict:
    """``PubKeyMultisigThreshold.VerifyBytes`` of the key ``key`` encodes."""
    parsed_key = parse_pubkey(key)
    if parsed_key is None:
        return Verdict(False, "malformed_key")
    k, pubs = parsed_key
    parsed = parse_signature(sig)
    if parsed is None:
        return Verdict(False, "malformed_signature")
    size, elems, sigs = parsed
    if len(pubs) != size:
        return Verdict(False, "wrong_size")
    if len(sigs) < k:
        return Verdict(False, "too_few_sigs")
    if len(sigs) > size:
        return Verdict(False, "too_many_sigs")
    flagged = [i for i in range(size) if get_index(elems, size, i)]
    if len(flagged) < k:
        return Verdict(False, "under_threshold")
    if len(flagged) > len(sigs):
        return Verdict(False, "flag_without_sig")  # the Go panics here
    lanes = [(pubs[i], msg, sigs[j]) for j, i in enumerate(flagged)]
    lane_ok = [oracle.verify(p, m, s) for p, m, s in lanes]
    ok = all(lane_ok)
    return Verdict(ok, "ok" if ok else "bad_subsignature", lanes, lane_ok)


def commit_verdict(verdicts: Sequence[Optional[Verdict]], powers: Sequence[int],
                   structural_ok: bool = True) -> bool:
    """``VerifyCommit``: ``verdicts[i]`` is validator i's (None: precommit
    absent), ``powers[i]`` its power; every present precommit votes the
    commit's block id.  ``structural_ok`` False: the call is refused before
    any signature (wrong block id)."""
    tally = 0
    for v, power in zip(verdicts, powers):
        if v is None:
            continue
        if not v.ok:
            return False
        tally += power
    return structural_ok and tally * 3 > sum(powers) * 2
