"""Seeded inputs for the drivers: a ring of commits over one validator set,
and a signed, executed chain held as the bytes a peer would send.

A copy of ``tendermint_tpu/testutil/chain.py`` (``build_commit``,
``build_chain``) kept with the yardstick, with two differences: every
precommit is signed directly (no ``VoteSet.add_vote``, which verifies each
signature on the host again), and the chain's object graph is dropped once
each block has been encoded.  Blocks, part sets and state transitions are the
program's own, so headers are what a node produces.  What the reference
needs (the lanes as they were signed, the final application state) is
recorded here, by this file's own code, and never read back from the program.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import oracle

GENESIS_TIME_NS = 1_700_000_000_000_000_000
_PACK_TS = struct.Struct("<q").pack

try:
    from cryptography.hazmat.primitives import serialization as _ser
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
except ImportError:  # pragma: no cover
    Ed25519PrivateKey = None


class Signer:
    """One validator key, made from 32 seed bytes; signs with OpenSSL where
    the container has it (deterministic, so the bytes equal the program's)."""

    def __init__(self, seed32: bytes):
        if Ed25519PrivateKey is not None:
            self._key = Ed25519PrivateKey.from_private_bytes(seed32)
            self.pub = self._key.public_key().public_bytes(
                _ser.Encoding.Raw, _ser.PublicFormat.Raw)
            self.sign = self._key.sign
        else:  # pragma: no cover
            from tendermint_tpu.crypto import ed25519 as ed

            priv = ed.gen_privkey(seed32)
            self.pub = priv[32:]
            self.sign = lambda msg: ed.sign(priv, msg)


def make_signers(n: int, rng: np.random.Generator) -> List[Signer]:
    seeds = rng.bytes(32 * n)
    return [Signer(seeds[32 * i: 32 * i + 32]) for i in range(n)]


# ---------------------------------------------------------------------------
# commit ring
# ---------------------------------------------------------------------------


@dataclass
class Lanes:
    """One commit as the reference sees it, in validator-set order."""

    pubs: List[bytes]
    msgs: List[bytes]
    sigs: List[Optional[bytes]]  # None = precommit absent
    powers: List[int]  # every precommit present votes the commit's block_id
    structural_ok: bool = True  # False: the call must be refused outright


@dataclass
class CommitCase:
    name: str
    valset: object
    chain_id: str
    block_id: object
    height: int
    commit: object
    lanes: Lanes


def _sign_commit(chain_id, valset, signers_by_addr, height, block_id, stamps):
    """(Commit, Lanes): every validator of ``valset`` precommits block_id."""
    from tendermint_tpu.types import Commit, SignedMsgType, Vote
    from tendermint_tpu.types.core import canonical_vote_sign_bytes

    tpl = canonical_vote_sign_bytes(
        chain_id, SignedMsgType.PRECOMMIT, height, 0, 0, block_id)
    head, tail = tpl[:17], tpl[25:]
    votes, pubs, msgs, sigs, powers = [], [], [], [], []
    for idx, val in enumerate(valset.validators):
        signer = signers_by_addr[val.address]
        msg = head + _PACK_TS(stamps[idx]) + tail
        sig = signer.sign(msg)
        votes.append(Vote(
            vote_type=SignedMsgType.PRECOMMIT, height=height, round=0,
            timestamp_ns=stamps[idx], block_id=block_id,
            validator_address=val.address, validator_index=idx,
            signature=sig,
        ))
        pubs.append(signer.pub)
        msgs.append(msg)
        sigs.append(sig)
        powers.append(val.voting_power)
    if votes[0].sign_bytes(chain_id) != msgs[0]:
        raise RuntimeError("sign-bytes template does not match Vote.sign_bytes")
    return Commit(block_id, votes), Lanes(pubs, msgs, sigs, powers)


def make_commit_ring(config: dict, traffic: dict, seed: int) -> List[CommitCase]:
    """``traffic['ring']`` commits at consecutive heights over one set of
    ``config['validators']`` equal-power validators: distinct block ids,
    per-validator timestamps inside one second, all precommits valid."""
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.core import PartSetHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    rng = np.random.default_rng(seed)
    n = int(config["validators"])
    signers = make_signers(n, rng)
    vals = [Validator(PubKeyEd25519(s.pub), int(config["voting_power"]))
            for s in signers]
    valset = ValidatorSet(vals)
    by_addr = {v.address: s for v, s in zip(vals, signers)}
    chain_id = config["chain_id"]
    cases = []
    for k in range(int(traffic["ring"])):
        height = int(traffic["first_height"]) + k
        block_id = BlockID(rng.bytes(32), PartSetHeader(1, rng.bytes(32)))
        base = GENESIS_TIME_NS + height * 1_000_000_000
        stamps = (base + rng.integers(0, 1 << 29, size=n)).tolist()
        commit, lanes = _sign_commit(
            chain_id, valset, by_addr, height, block_id, stamps)
        cases.append(CommitCase(
            f"ring{k}", valset, chain_id, block_id, height, commit, lanes))
    return cases


def tamper(case: CommitCase, kind: str, rng: np.random.Generator) -> CommitCase:
    """A seeded variant of a ring commit that the oracle decides; the
    program's answer through ``verify_commit`` has to be the oracle's."""
    from dataclasses import replace

    from tendermint_tpu.types import Commit

    votes = list(case.commit.precommits)
    ln = case.lanes
    lanes = Lanes(list(ln.pubs), list(ln.msgs), list(ln.sigs), list(ln.powers))
    n = len(votes)
    i = int(rng.integers(0, n))
    block_id = case.block_id
    valset = case.valset

    def put(idx, sig):
        votes[idx] = replace(votes[idx], signature=sig)
        lanes.sigs[idx] = sig

    if kind == "bad_signature":
        sig = bytearray(lanes.sigs[i])
        sig[int(rng.integers(0, 32))] ^= 1 << int(rng.integers(0, 8))
        put(i, bytes(sig))
    elif kind == "wrong_validator":
        j = (i + 1 + int(rng.integers(0, n - 1))) % n
        si, sj = lanes.sigs[i], lanes.sigs[j]
        put(i, sj)
        put(j, si)
    elif kind == "s_plus_L":
        # the first lane whose s + L still has its top three bits clear
        for k in range(n):
            idx = (i + k) % n
            s = int.from_bytes(lanes.sigs[idx][32:], "little") + oracle.L
            if s < 1 << 253:
                put(idx, lanes.sigs[idx][:32] + s.to_bytes(32, "little"))
                break
    elif kind == "wrong_block_id":
        from tendermint_tpu.types import BlockID

        block_id = BlockID(bytes(32), case.block_id.parts_header)
        lanes.structural_ok = False
    elif kind == "under_quorum":
        # exactly two thirds of the power present, which is not "more than":
        # a seeded 15 % of the validators are absent and, in a validator
        # set of the same keys made for this case, hold exactly one third
        # of the power.  The lanes left stay in the ring's own lane bucket,
        # so the check runs the window's program and compiles no other.
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        absent = sorted(rng.permutation(n)[: max(1, n * 15 // 100)].tolist())
        present_power = sum(lanes.powers) - sum(lanes.powers[i] for i in absent)
        if present_power % 2:
            raise ValueError("under_quorum needs an even present power")
        share, extra = divmod(present_power // 2, len(absent))
        for k, idx in enumerate(absent):
            lanes.powers[idx] = share + (1 if k < extra else 0)
            votes[idx] = None
            lanes.sigs[idx] = None
        valset = ValidatorSet([
            Validator(v.pub_key, p)
            for v, p in zip(case.valset.validators, lanes.powers)])
        if [v.address for v in valset.validators] != [
                v.address for v in case.valset.validators]:
            raise RuntimeError("under_quorum: the validator order changed")
    else:
        raise ValueError(f"unknown tamper kind {kind!r}")
    return CommitCase(
        f"{case.name}.{kind}", valset, case.chain_id, block_id,
        case.height, Commit(case.commit.block_id, votes), lanes)


def reference_verdict(lanes: Lanes, known: Optional[Sequence[bool]] = None,
                      base: Optional[Lanes] = None) -> Tuple[List[bool], bool]:
    """(per-lane verdict of every present lane, whether the commit stands)
    by the oracle.  ``known``/``base``: verdicts already computed for the
    untampered lanes, reused where a lane is byte-identical."""
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


# ---------------------------------------------------------------------------
# chain as bytes
# ---------------------------------------------------------------------------


@dataclass
class ChainBytes:
    chain_id: str
    genesis_time_ns: int
    validators: List[Tuple[bytes, int]]  # (pubkey, power), genesis order
    responses: List[bytes]  # BlockResponseMessage bytes, index = height - 1
    # what a sync of this chain must end at: the tip's commit is not in the
    # chain yet, so the last block applied is ``len(responses) - 1``
    final_height: int = 0
    app_hash: bytes = b""  # by the program's app, as the generator ran it
    app_hash_reference: bytes = b""  # by this file's own kvstore + merkle
    validators_hash: bytes = b""
    seconds: Dict[str, float] = field(default_factory=dict)

    def genesis(self):
        from tendermint_tpu.crypto.keys import PubKeyEd25519
        from tendermint_tpu.types import GenesisDoc, GenesisValidator

        g = GenesisDoc(
            chain_id=self.chain_id, genesis_time_ns=self.genesis_time_ns,
            validators=[GenesisValidator(PubKeyEd25519(p), w)
                        for p, w in self.validators],
        )
        g.validate_and_complete()
        return g


def make_txs(rng: np.random.Generator, count: int, size: int) -> List[bytes]:
    """``count`` kvstore txs of ``size`` bytes: hex key, '=', hex value."""
    if count == 0:
        return []
    klen = (size - 1) // 2
    step = size - 1
    raw = rng.bytes(count * (step + 1) // 2 + 1).hex().encode()
    return [raw[j * step: j * step + klen] + b"=" + raw[j * step + klen: (j + 1) * step]
            for j in range(count)]


def merkle_root(items: Sequence[bytes]) -> bytes:
    """Tendermint's simple merkle tree (RFC 6962 prefixes, split at the
    largest power of two below n), written out here for the reference."""
    sha = hashlib.sha256

    def node(lo: int, hi: int) -> bytes:
        n = hi - lo
        if n == 0:
            return sha(b"").digest()
        if n == 1:
            return sha(b"\x00" + items[lo]).digest()
        k = 1
        while k * 2 < n:
            k *= 2
        return sha(b"\x01" + node(lo, lo + k) + node(lo + k, hi)).digest()

    return node(0, len(items))


def build_chain_bytes(config: dict, traffic: dict, seed: int) -> ChainBytes:
    import time

    from tendermint_tpu.abci.examples.kvstore import KVStoreApp
    from tendermint_tpu.blockchain.messages import (
        BlockResponseMessage,
        encode_msg,
    )
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.libs.db.kv import MemDB
    from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
    from tendermint_tpu.state import store as sm_store
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state_types import state_from_genesis
    from tendermint_tpu.types import BlockID, Commit

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    n_vals = int(config["validators"])
    n_blocks = int(traffic["blocks"])
    signers = make_signers(n_vals, rng)
    chain = ChainBytes(
        chain_id=config["chain_id"], genesis_time_ns=GENESIS_TIME_NS,
        validators=[(s.pub, int(config["voting_power"])) for s in signers],
        responses=[],
    )
    st = state_from_genesis(chain.genesis())
    by_addr = {PubKeyEd25519(s.pub).address(): s for s in signers}

    state_db = MemDB()
    sm_store.save_state(state_db, st)
    conn = MultiAppConn(LocalClientCreator(KVStoreApp()))
    conn.start()
    block_exec = BlockExecutor(state_db, conn.consensus)

    kv: Dict[bytes, bytes] = {}  # the reference's own application state
    t_sign = t_apply = t_encode = 0.0
    last_commit = Commit()
    for h in range(1, n_blocks + 1):
        txs = make_txs(rng, int(traffic["txs_per_block"]), int(traffic.get("tx_bytes", 0)))
        proposer = st.validators.get_proposer()
        block = st.make_block(h, txs, last_commit, [], proposer.address)
        parts = block.make_part_set()
        block_id = BlockID(hash=block.hash(), parts_header=parts.header())
        t1 = time.perf_counter()
        chain.responses.append(encode_msg(BlockResponseMessage(block)))
        t2 = time.perf_counter()
        # precommit stamps strictly after the block's time, each validator
        # its own, so the next block's median passes the monotonic check
        base = GENESIS_TIME_NS + (h + 1) * 1_000_000_000
        stamps = (base + rng.integers(0, 1 << 29, size=n_vals)).tolist()
        last_commit, _ = _sign_commit(
            chain.chain_id, st.validators, by_addr, h, block_id, stamps)
        t3 = time.perf_counter()
        if h < n_blocks:  # the tip only carries the last commit
            # the commit was signed a line above: not verified again
            st = block_exec.apply_block(
                st, block_id, block, trusted_last_commit=True)
            for tx in txs:
                k, _, v = tx.partition(b"=")
                kv[k] = v
        t4 = time.perf_counter()
        t_encode += t2 - t1
        t_sign += t3 - t2
        t_apply += t4 - t3
    conn.stop()

    chain.final_height = n_blocks - 1
    chain.app_hash = st.app_hash
    chain.validators_hash = st.validators.hash()
    chain.app_hash_reference = merkle_root(
        [k + b"=" + v for k, v in sorted(kv.items())])
    if chain.app_hash_reference != chain.app_hash:
        raise RuntimeError(
            "generator: the program's app hash differs from the reference's "
            f"({chain.app_hash.hex()} vs {chain.app_hash_reference.hex()})")
    chain.seconds = {
        "sign": t_sign, "apply": t_apply, "encode": t_encode,
        "total": time.perf_counter() - t0,
    }
    return chain


def forge_precommit(chain: ChainBytes, height: int, rng: np.random.Generator) -> bytes:
    """The response for ``height + 1`` with one precommit of the commit for
    ``height`` forged: one bit of its signature flipped in the bytes, as a
    peer would send them (a decoded block keeps its wire buffer)."""
    from tendermint_tpu.blockchain.messages import unmarshal_msg

    raw = bytearray(chain.responses[height])
    pcs = unmarshal_msg(bytes(raw)).block.last_commit.precommits
    sig = pcs[int(rng.integers(0, len(pcs)))].signature
    pos = raw.rindex(sig)
    raw[pos + int(rng.integers(0, 32))] ^= 1 << int(rng.integers(0, 8))
    return bytes(raw)


# ---------------------------------------------------------------------------
# the chain cache: keyed by everything the bytes depend on
# ---------------------------------------------------------------------------

_MAGIC = b"tmbench-chain-1\n"
KEEP_CHAINS = 8  # a check's set of seeds, once over


def _source_hash() -> str:
    h = hashlib.sha256()
    for path in (__file__, oracle.__file__):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def chain_cache_path(cache_dir: str, config_name: str, traffic_name: str,
                     config: dict, traffic: dict, seed: int) -> str:
    params = hashlib.sha256(json.dumps(
        [config, traffic], sort_keys=True).encode()).hexdigest()[:12]
    return os.path.join(
        cache_dir, "chain",
        f"{config_name}.{traffic_name}.{seed}.{params}.{_source_hash()}.bin")


def save_chain(path: str, chain: ChainBytes) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    head = json.dumps({
        "chain_id": chain.chain_id, "genesis_time_ns": chain.genesis_time_ns,
        "validators": [[p.hex(), w] for p, w in chain.validators],
        "final_height": chain.final_height, "app_hash": chain.app_hash.hex(),
        "app_hash_reference": chain.app_hash_reference.hex(),
        "validators_hash": chain.validators_hash.hex(),
        "sizes": [len(r) for r in chain.responses],
    }).encode()
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        f.write(_MAGIC + struct.pack("<Q", len(head)) + head)
        for r in chain.responses:
            f.write(r)
    os.replace(tmp, path)
    # bound what a check leaves on disk: the newest few chains stay
    folder = os.path.dirname(path)
    files = sorted(
        (os.path.join(folder, n) for n in os.listdir(folder) if n.endswith(".bin")),
        key=os.path.getmtime)
    for old in files[:-KEEP_CHAINS]:
        try:
            os.remove(old)
        except OSError:
            pass


def load_chain(path: str) -> Optional[ChainBytes]:
    try:
        with open(path, "rb") as f:
            if f.read(len(_MAGIC)) != _MAGIC:
                return None
            (n,) = struct.unpack("<Q", f.read(8))
            head = json.loads(f.read(n))
            responses = [f.read(size) for size in head["sizes"]]
    except (OSError, ValueError, KeyError, struct.error):
        return None
    if any(len(r) != s for r, s in zip(responses, head["sizes"])):
        return None
    return ChainBytes(
        chain_id=head["chain_id"], genesis_time_ns=head["genesis_time_ns"],
        validators=[(bytes.fromhex(p), w) for p, w in head["validators"]],
        responses=responses, final_height=head["final_height"],
        app_hash=bytes.fromhex(head["app_hash"]),
        app_hash_reference=bytes.fromhex(head["app_hash_reference"]),
        validators_hash=bytes.fromhex(head["validators_hash"]),
    )
