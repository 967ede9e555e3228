"""A seeded chain whose every block is full of transactions, held as the
bytes a peer would send: the input of the deployment ``fastsync-64v-full``.

``chaingen.build_chain_bytes`` runs the program's ``KVStoreApp`` and holds
its merkle app hash against its own.  Here the in-process app is the
program's ``UpstreamKVStoreApp`` (the reference's Commit), every block
carries ``txs_per_block`` txs of ``tx_bytes`` bytes in ``chaingen.make_txs``'s
shape (hex key, ``=``, hex value; every key of the chain distinct), and what
a header must say of them is ``benchmark/kvstore_reference``'s business and
not the program's: the generator stops if a header the program built
carries another data hash, app hash or last-results hash, or a block's part
set another total or root, than the reference computes from the txs and the
encoded bytes.  Blocks, part sets and the state transition are the
program's own, and each height's commit is signed with plain OpenSSL keys,
as in ``chaingen``.

A block's txs come from ``[seed, 1, height]`` alone, so a check can make
any block's txs again without the chain.
"""

from __future__ import annotations

import hashlib
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import List, Tuple

import numpy as np

from benchmark import chaingen
from benchmark import kvstore_reference as ref

QUERIED_KEYS = 1000
PROBED_HEIGHTS = 8


@dataclass
class FullChain(chaingen.ChainBytes):
    txs_per_block: int = 0
    size: int = 0  # txs delivered by a whole sync
    # what a whole sync is asked afterwards, by the reference: seeded keys
    # with the value its state machine ends with, and seeded heights
    queries: List[Tuple[bytes, bytes]] = field(default_factory=list)
    probe_heights: List[int] = field(default_factory=list)
    # the driver's tally of what the whole syncs of this run got wrong, by
    # question; it lives with the chain because a sync is handed the chain
    misses: Counter = field(default_factory=Counter)


def block_txs(seed: int, height: int, traffic: dict) -> List[bytes]:
    rng = np.random.default_rng([seed, 1, height])
    return chaingen.make_txs(
        rng, int(traffic["txs_per_block"]), int(traffic["tx_bytes"]))


def reference_run(seed: int, traffic: dict, heights: int) -> ref.KVStore:
    """The reference's state machine after blocks 1..``heights``."""
    kv = ref.KVStore()
    for h in range(1, heights + 1):
        for tx in block_txs(seed, h, traffic):
            kv.deliver(tx)
    return kv


def finish(chain: FullChain, seed: int, traffic: dict) -> FullChain:
    """What the reference says a whole sync of ``chain`` ends at, and the
    seeded questions put to it; the same for a chain made and one loaded."""
    kv = reference_run(seed, traffic, chain.final_height)
    if len(kv.state) != kv.size:
        raise RuntimeError(
            f"generator: {kv.size - len(kv.state)} keys of the chain repeat")
    chain.txs_per_block = int(traffic["txs_per_block"])
    chain.size = kv.size
    chain.app_hash_reference = kv.app_hash()
    if chain.app_hash != chain.app_hash_reference:
        raise RuntimeError(
            "generator: the program's app hash differs from the reference's "
            f"({chain.app_hash.hex()} vs {chain.app_hash_reference.hex()})")
    rng = np.random.default_rng([seed, 2])
    keys = list(kv.state)
    picks = rng.permutation(len(keys))[:QUERIED_KEYS]
    chain.queries = [(keys[int(i)], kv.query(keys[int(i)])) for i in picks]
    chain.probe_heights = sorted(
        int(h) + 1 for h in rng.permutation(chain.final_height)[:PROBED_HEIGHTS])
    return chain


def build_chain(config: dict, traffic: dict, seed: int) -> FullChain:
    from tendermint_tpu.abci.examples.kvstore import UpstreamKVStoreApp
    from tendermint_tpu.blockchain.messages import BlockResponseMessage, encode_msg
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
    signers = chaingen.make_signers(n_vals, rng)
    chain = FullChain(
        chain_id=config["chain_id"], genesis_time_ns=chaingen.GENESIS_TIME_NS,
        validators=[(s.pub, int(config["voting_power"])) for s in signers],
        responses=[],
    )
    st = state_from_genesis(chain.genesis())
    by_addr = {PubKeyEd25519(s.pub).address(): s for s in signers}
    state_db = MemDB()
    sm_store.save_state(state_db, st)
    conn = MultiAppConn(LocalClientCreator(UpstreamKVStoreApp()))
    conn.start()
    block_exec = BlockExecutor(state_db, conn.consensus)

    delivered = 0  # the app hash is a function of this; finish() runs the state machine whole
    want_app_hash, want_results = b"", b""  # what genesis hands block 1
    t_sign = t_apply = t_reference = 0.0
    last_commit = Commit()
    for h in range(1, n_blocks + 1):
        txs = block_txs(seed, h, traffic)
        proposer = st.validators.get_proposer()
        block = st.make_block(h, txs, last_commit, [], proposer.address)
        parts = block.make_part_set()
        response = encode_msg(BlockResponseMessage(block))
        t1 = time.perf_counter()
        header = block.header
        got = (header.data_hash, header.app_hash, header.last_results_hash,
               (parts.header().total, parts.header().hash))
        want = (ref.data_hash(txs), want_app_hash, want_results,
                ref.part_set_header(ref.block_bytes(response)))
        if got != want:
            raise RuntimeError(
                f"generator: at height {h} the program's data hash, app hash, "
                "last-results hash or part-set header is not the reference's: "
                f"{got} vs {want}")
        t2 = time.perf_counter()
        chain.responses.append(response)
        block_id = BlockID(hash=block.hash(), parts_header=parts.header())
        # precommit stamps strictly after the block's time, each validator
        # its own, so the next block's median passes the monotonic check
        base = chaingen.GENESIS_TIME_NS + (h + 1) * 1_000_000_000
        stamps = (base + rng.integers(0, 1 << 29, size=n_vals)).tolist()
        last_commit, _ = chaingen._sign_commit(
            chain.chain_id, st.validators, by_addr, h, block_id, stamps)
        t3 = time.perf_counter()
        if h < n_blocks:  # the tip only carries the last commit
            st = block_exec.apply_block(
                st, block_id, block, trusted_last_commit=True)
            delivered += len(txs)
            want_app_hash = ref.put_varint(delivered)
            want_results = ref.results_hash(len(txs))
        t_reference += t2 - t1
        t_sign += t3 - t2
        t_apply += time.perf_counter() - t3
    conn.stop()

    chain.final_height = n_blocks - 1
    chain.app_hash = st.app_hash
    chain.validators_hash = st.validators.hash()
    finish(chain, seed, traffic)
    chain.seconds = {
        "sign": t_sign, "apply": t_apply, "reference": t_reference,
        "total": time.perf_counter() - t0,
    }
    return chain


def _source_hash() -> str:
    h = hashlib.sha256()
    for path in (__file__, ref.__file__):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


def cached_chain(cache_dir: str, config_name: str, traffic_name: str,
                 config: dict, traffic: dict, seed: int, log) -> FullChain:
    """The chain from ``chaingen``'s cache (its path also keyed by this
    file and the reference), else made and put there."""
    t0 = time.perf_counter()
    path = chaingen.chain_cache_path(
        cache_dir, config_name, f"{traffic_name}.{_source_hash()}",
        config, traffic, seed)
    held = chaingen.load_chain(path)
    if held is None:
        chain = build_chain(config, traffic, seed)
        chaingen.save_chain(path, chain)
        log("setup.generate: " + " ".join(
            f"{k}={v:.3f}s" for k, v in chain.seconds.items()))
        return chain
    chain = finish(FullChain(**vars(held)), seed, traffic)
    log(f"setup.generate: chain cache hit ({time.perf_counter() - t0:.3f}s): {path}")
    return chain


def forge_tx(chain: FullChain, seed: int, traffic: dict, height: int,
             rng: np.random.Generator) -> bytes:
    """The response for ``height`` with one bit of one byte of one of its
    txs flipped in the bytes, as a peer would send them.  The header still
    states the honest data hash and the next block's commit still signs the
    honest part-set header, so neither is what these bytes hash to."""
    raw = bytearray(chain.responses[height - 1])
    txs = block_txs(seed, height, traffic)
    tx = txs[int(rng.integers(0, len(txs)))]
    at = raw.index(tx) + int(rng.integers(0, len(tx)))
    raw[at] ^= 1 << int(rng.integers(0, 8))
    return bytes(raw)
