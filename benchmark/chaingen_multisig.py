"""Seeded inputs for the multisig commit stream, as plain data: a set of
validators each keyed k-of-n over ed25519 sub-keys, a ring of heights at
which every validator precommits, the tampered variants of one of them, and
the reference's verdict on each.

It imports nothing of the program.  Keys, bit arrays and marshalled
signatures are encoded here (``encode_pubkey``, ``encode_signature``; the
format is written out in ``benchmark/oracle_multisig.py``, which parses it
with its own code), the sub-keys sign through ``benchmark/chaingen.Signer``,
and the validators stand in the order a validator set keeps, by
``SHA-256(key bytes)[:20]``, computed here.  What only the program can say,
the canonical sign-bytes of a precommit, the driver hands in as one template
a height (``Height.head`` / ``Height.tail`` round the 8-byte timestamp), and
it holds the program's ``Vote.sign_bytes`` to what was signed here.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import chaingen
from benchmark import oracle_multisig as oracle

_PACK_TS = struct.Struct("<q").pack

# tampers that leave every signature as it was signed
SCHEME_FREE = ("wrong_block_id", "under_quorum")


def encode_pubkey(k: int, subkeys: Sequence[bytes]) -> bytes:
    out = k.to_bytes(4, "big") + len(subkeys).to_bytes(4, "big")
    for key in subkeys:
        out += bytes([len(oracle.ED25519)]) + oracle.ED25519
        out += len(key).to_bytes(2, "big") + key
    return out


def encode_signature(bits: int, flagged: Sequence[int],
                     sigs: Sequence[bytes]) -> bytes:
    """A bit array of ``bits`` with ``flagged`` set (most significant bit of
    a byte first), then the sub-signatures in the order given."""
    elems = bytearray((bits + 7) // 8)
    for i in flagged:
        elems[i >> 3] |= 1 << (7 - i % 8)
    out = bits.to_bytes(4, "big") + bytes(elems) + len(sigs).to_bytes(2, "big")
    for s in sigs:
        out += len(s).to_bytes(2, "big") + s
    return out


@dataclass
class Keyset:
    """The run's validators, in validator-set order (by address)."""

    k: int
    n: int
    signers: List[List[chaingen.Signer]]  # [validator][sub-key]
    keys: List[bytes]  # each validator's marshalled threshold key
    powers: List[int]


@dataclass
class Height:
    """One height's block id as the seed drew it, and the program's
    sign-bytes of a precommit for it round the timestamp."""

    height: int
    block_hash: bytes
    parts_hash: bytes
    head: bytes = b""
    tail: bytes = b""


@dataclass
class Precommits:
    """One commit as the reference sees it, in validator-set order."""

    name: str
    at: Height
    keys: List[bytes]
    powers: List[int]
    stamps: List[int]
    msgs: List[bytes]
    sigs: List[Optional[bytes]]  # marshalled multisignatures; None = absent
    structural_ok: bool = True  # False: the call must be refused outright

    def lanes(self) -> int:
        """Sub-signatures of the commit as signed (its flagged bits)."""
        return sum(len(oracle.parse_signature(s)[2]) for s in self.sigs if s)


def make_keyset(config: dict, seed: int) -> Keyset:
    rng = np.random.default_rng([seed, 0])
    ms = config["multisig"]
    k, n = int(ms["k"]), int(ms["n"])
    if ms["sub_key_type"] != "ed25519" or config["key_type"] != "multisig_threshold":
        raise ValueError("this generator keys validators k-of-n over ed25519")
    count = int(config["validators"])
    flat = chaingen.make_signers(count * n, rng)
    if len({s.pub for s in flat}) != count * n:
        raise RuntimeError("sub-keys are not all distinct")
    groups = [flat[i * n: (i + 1) * n] for i in range(count)]
    keyed = sorted(
        ((encode_pubkey(k, [s.pub for s in g]), g) for g in groups),
        key=lambda kg: hashlib.sha256(kg[0]).digest()[:20])
    return Keyset(k, n, [g for _, g in keyed], [key for key, _ in keyed],
                  [int(config["voting_power"])] * count)


def make_heights(traffic: dict, seed: int) -> List[Height]:
    rng = np.random.default_rng([seed, 1])
    return [Height(int(traffic["first_height"]) + i, rng.bytes(32), rng.bytes(32))
            for i in range(int(traffic["ring"]))]


def _draw_signers(traffic: dict, ks: Keyset, rng) -> List[List[int]]:
    """For every validator, which of its sub-keys sign this height: a count
    drawn from ``signer_counts``, then that many of the n, in key order."""
    counts = sorted((int(c), float(p)) for c, p in traffic["signer_counts"].items())
    if any(not ks.k <= c <= ks.n for c, _ in counts):
        raise ValueError("a signer count outside k..n")
    many = rng.choice([c for c, _ in counts], p=[p for _, p in counts],
                      size=len(ks.keys))
    order = np.argsort(rng.random((len(ks.keys), ks.n)), axis=1)
    return [sorted(order[v, :c].tolist()) for v, c in enumerate(many.tolist())]


def sign_ring(ks: Keyset, heights: Sequence[Height], traffic: dict,
              seed: int) -> List[Precommits]:
    """Every validator precommits every height's block: per-validator
    timestamps inside one second, the signers of ``_draw_signers``."""
    rng = np.random.default_rng([seed, 2])
    ring = []
    for i, at in enumerate(heights):
        base = chaingen.GENESIS_TIME_NS + at.height * 1_000_000_000
        stamps = (base + rng.integers(0, 1 << 29, size=len(ks.keys))).tolist()
        chosen = _draw_signers(traffic, ks, rng)
        msgs, sigs = [], []
        for v, flagged in enumerate(chosen):
            msg = at.head + _PACK_TS(stamps[v]) + at.tail
            msgs.append(msg)
            sigs.append(encode_signature(
                ks.n, flagged, [ks.signers[v][j].sign(msg) for j in flagged]))
        ring.append(Precommits(f"ring{i}", at, ks.keys, list(ks.powers),
                               stamps, msgs, sigs))
    return ring


def tamper(case: Precommits, ks: Keyset, kind: str, rng) -> Tuple[Precommits, int]:
    """(a seeded variant of a ring commit that the reference decides, the
    validator it changed or -1).  Each kind is what the traffic file's name
    says; the program's answer through ``verify_commit`` has to be the
    reference's."""
    sigs = list(case.sigs)
    powers = list(case.powers)
    out = replace(case, name=f"{case.name}.{kind}", sigs=sigs, powers=powers)
    count = len(sigs)
    v = int(rng.integers(0, count))
    size, elems, subs = oracle.parse_signature(sigs[v])
    flagged = [i for i in range(size) if oracle.get_index(elems, size, i)]
    msg = case.msgs[v]

    if kind == "bad_subsignature":  # one bit of one sub-signature
        j = int(rng.integers(0, len(subs)))
        bad = bytearray(subs[j])
        bad[int(rng.integers(0, 32))] ^= 1 << int(rng.integers(0, 8))
        subs[j] = bytes(bad)
        sigs[v] = encode_signature(size, flagged, subs)
    elif kind == "subsigs_swapped":  # two of one validator's exchanged
        a, b = rng.permutation(len(subs))[:2].tolist()
        subs[a], subs[b] = subs[b], subs[a]
        sigs[v] = encode_signature(size, flagged, subs)
    elif kind == "under_threshold":  # k - 1 bits set beside k signatures
        keep = flagged[: ks.k - 1]
        sigs[v] = encode_signature(size, keep, subs[: ks.k])
    elif kind == "too_many_sigs":  # n + 1 signatures, the flagged ones valid
        extra = [ks.signers[v][i % ks.n].sign(msg) for i in range(size + 1 - len(subs))]
        sigs[v] = encode_signature(size, flagged, subs + extra)
    elif kind == "wrong_size":  # a bit array of n + 1
        sigs[v] = encode_signature(size + 1, flagged, subs)
    elif kind == "flag_without_sig":  # more bits than signatures
        every = list(range(size))
        sigs[v] = encode_signature(size, every, subs[: ks.k] if len(subs) == size else subs)
    elif kind == "unflagged_signer":
        # a valid signature of a sub-key whose bit is NOT set, in the place
        # of the last flagged key's: the first validator, from v on, that
        # left a sub-key out
        for step in range(count):
            w = (v + step) % count
            size, elems, subs = oracle.parse_signature(sigs[w])
            flagged = [i for i in range(size) if oracle.get_index(elems, size, i)]
            if len(flagged) < size:
                break
        else:
            raise RuntimeError("unflagged_signer: every validator flagged all keys")
        v = w
        outsider = next(i for i in range(size) if i not in flagged)
        subs[-1] = ks.signers[v][outsider].sign(case.msgs[v])
        sigs[v] = encode_signature(size, flagged, subs)
    elif kind == "wrong_block_id":
        out.structural_ok = False
        v = -1
    elif kind == "under_quorum":
        # exactly two thirds of the power present, which is not "more than":
        # a seeded 15 % of the validators are absent and, with the same keys
        # in the same order, hold exactly one third of the power.  The lanes
        # left stay in the ring's own bucket: the check compiles nothing.
        absent = sorted(rng.permutation(count)[: max(1, count * 15 // 100)].tolist())
        present = sum(powers) - sum(powers[i] for i in absent)
        if present % 2:
            raise ValueError("under_quorum needs an even present power")
        share, extra = divmod(present // 2, len(absent))
        for j, idx in enumerate(absent):
            powers[idx] = share + (1 if j < extra else 0)
            sigs[idx] = None
        v = -1
    else:
        raise ValueError(f"unknown tamper kind {kind!r}")
    if kind not in SCHEME_FREE and sigs[v] == case.sigs[v]:
        raise RuntimeError(f"{kind}: the signature did not change")
    return out, v


def reference_verdicts(
    case: Precommits, known: Optional[Sequence[oracle.Verdict]] = None,
    base: Optional[Precommits] = None,
) -> Tuple[List[Optional[oracle.Verdict]], bool]:
    """(the reference's verdict on every validator's precommit, None where
    absent; whether the commit stands).  ``known`` / ``base``: verdicts
    already computed for the untampered commit, reused where a validator's
    signature is byte-identical."""
    verdicts: List[Optional[oracle.Verdict]] = []
    for v, sig in enumerate(case.sigs):
        if sig is None:
            verdicts.append(None)
        elif known is not None and base.sigs[v] == sig:
            verdicts.append(known[v])
        else:
            verdicts.append(oracle.verify_bytes(case.keys[v], case.msgs[v], sig))
    return verdicts, oracle.commit_verdict(verdicts, case.powers, case.structural_ok)


def flat_lanes(verdicts: Sequence[Optional[oracle.Verdict]]) -> Dict[str, list]:
    """The sub-signature lanes of a commit as the reference walked them, with
    its verdict on each: what a device is held to lane for lane."""
    pubs, msgs, sigs, want = [], [], [], []
    for v in verdicts:
        if v is None:
            continue
        for (p, m, s), ok in zip(v.lanes, v.lane_ok):
            pubs.append(p)
            msgs.append(m)
            sigs.append(s)
            want.append(ok)
    return {"pubs": pubs, "msgs": msgs, "sigs": sigs, "want": want}
