"""Seeded chains whose validator set changes, held as the bytes a peer would
send: the inputs of the deployment ``fastsync-64v-churn``.

``chaingen.build_chain_bytes`` signs one set for the whole chain and runs
the plain kvstore.  Here the in-process app is the program's
``PersistentKVStoreApp`` and every ``interval``-th block carries 'val:'
transactions drawn from the seed: one sitting validator leaves (power 0),
one fresh key joins at ``join_power``, and ``repowers`` other sitting
validators move to another power in ``power_range``.  The set keeps its size.

Who is in the set at a height, in which order, with which power, and what
the header of that height must say of it, is ``benchmark/valset_reference``'s
business and not the program's: the generator signs block h's commit with the
REFERENCE's set for h, in the reference's order, and stops if a header the
program built states another ``validators_hash``, ``next_validators_hash`` or
proposer than the reference does.  Blocks, part sets and the application are
the program's own, as in ``chaingen``.

One cell syncs ``chains`` such chains in turn (sub-seeds of ``--seed``): a
node catching up never sees a window twice, and one chain synced again and
again would meet its own windows in the program's valset caches, which are
keyed by a window's whole key array.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import List, Tuple

import numpy as np

from benchmark import chaingen
from benchmark import valset_reference as ref


@dataclass
class ChurnChain(chaingen.ChainBytes):
    next_validators_hash: bytes = b""  # of the height after the tip's
    # first heights of a new set, by the reference: what a sync must see
    change_heights: List[int] = field(default_factory=list)
    # the first commit a changed set signs (for ``stale_height``), with the
    # validator that left signing in the slot of the one that joined: the
    # response that carries it, whole (``stale_signer``)
    stale_height: int = 0
    stale_slot: int = 0
    stale_response: bytes = b""
    keys: int = 0  # distinct validator keys over the chain


def draw_updates(rng: np.random.Generator, sitting: List[Tuple[bytes, int]],
                 fresh_pub: bytes, traffic: dict) -> List[Tuple[bytes, int]]:
    """One change: (pubkey, power) updates in a seeded order."""
    lo, hi = (int(x) for x in traffic["power_range"])
    picks = rng.permutation(len(sitting))[: 1 + int(traffic["repowers"])]
    leaver = sitting[int(picks[0])]
    updates = [(leaver[0], 0), (fresh_pub, int(traffic["join_power"]))]
    for i in picks[1:]:
        pub, power = sitting[int(i)]
        others = [p for p in range(lo, hi + 1) if p != power]
        updates.append((pub, others[int(rng.integers(0, len(others)))]))
    return [updates[int(i)] for i in rng.permutation(len(updates))]


def build_chain(config: dict, traffic: dict, seed) -> ChurnChain:
    """``seed`` is anything ``numpy.random.default_rng`` takes."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
    from tendermint_tpu.blockchain.messages import BlockResponseMessage, encode_msg
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
    interval = int(traffic["change_interval"])
    signers = chaingen.make_signers(n_vals, rng)
    by_addr = {ref.address(s.pub): s for s in signers}
    chain = ChurnChain(
        chain_id=config["chain_id"], genesis_time_ns=chaingen.GENESIS_TIME_NS,
        validators=[(s.pub, int(config["voting_power"])) for s in signers],
        responses=[],
    )
    sets = ref.Evolution(chain.validators)
    st = state_from_genesis(chain.genesis())
    state_db = MemDB()
    sm_store.save_state(state_db, st)
    conn = MultiAppConn(LocalClientCreator(PersistentKVStoreApp()))
    conn.start()
    conn.consensus.init_chain_sync(abci.RequestInitChain(
        chain_id=chain.chain_id,
        validators=[abci.ValidatorUpdate("ed25519", p, w)
                    for p, w in chain.validators]))
    block_exec = BlockExecutor(state_db, conn.consensus)

    last_commit = Commit()
    last_set: List[Tuple[bytes, int]] = []
    stale_swap = None  # (honest signature, stale signature) of the next response
    for h in range(1, n_blocks + 1):
        updates: List[Tuple[bytes, int]] = []
        if h % interval == 0 and h < n_blocks - 1:
            (joiner,) = chaingen.make_signers(1, rng)
            by_addr[ref.address(joiner.pub)] = joiner
            updates = draw_updates(rng, sets.next.members(), joiner.pub, traffic)
        want_vals, want_next, want_proposer = sets.header()
        block = st.make_block(
            h, [ref.val_tx(p, w) for p, w in updates], last_commit, [],
            want_proposer)
        got = (block.header.validators_hash, block.header.next_validators_hash,
               st.validators.get_proposer().address)
        if got != (want_vals, want_next, want_proposer):
            raise RuntimeError(
                f"generator: at height {h} the program's header states another "
                "validator set, next set or proposer than the reference: "
                f"{[x.hex() for x in got]} vs "
                f"{[want_vals.hex(), want_next.hex(), want_proposer.hex()]}")
        parts = block.make_part_set()
        block_id = BlockID(hash=block.hash(), parts_header=parts.header())
        chain.responses.append(encode_msg(BlockResponseMessage(block)))
        if stale_swap:  # this block carries the commit signed a turn ago
            chain.stale_response = chain.responses[-1].replace(*stale_swap)
            stale_swap = None
        # the commit for h, by the reference's set for h, in its order
        members = sets.current.members()
        valset = SimpleNamespace(validators=[
            SimpleNamespace(address=ref.address(p), voting_power=w)
            for p, w in members])
        base = chaingen.GENESIS_TIME_NS + (h + 1) * 1_000_000_000
        stamps = (base + rng.integers(0, 1 << 29, size=len(members))).tolist()
        last_commit, lanes = chaingen._sign_commit(
            chain.chain_id, valset, by_addr, h, block_id, stamps)
        if not chain.stale_height and last_set and (
                {p for p, _ in members} != {p for p, _ in last_set}):
            # the first commit a new set signs: who left signs for who joined
            (left,) = {p for p, _ in last_set} - {p for p, _ in members}
            (joined,) = {p for p, _ in members} - {p for p, _ in last_set}
            slot = lanes.pubs.index(joined)
            chain.stale_height, chain.stale_slot = h, slot
            stale_swap = (lanes.sigs[slot],
                          by_addr[ref.address(left)].sign(lanes.msgs[slot]))
        last_set = members
        if h < n_blocks:  # the tip only carries the last commit
            st = block_exec.apply_block(
                st, block_id, block, trusted_last_commit=True)
            sets.end_block(updates)
    conn.stop()

    chain.final_height = n_blocks - 1
    chain.app_hash = st.app_hash
    # 'val:' transactions never enter the key-value state
    chain.app_hash_reference = chaingen.merkle_root([])
    if chain.app_hash_reference != chain.app_hash:
        raise RuntimeError("generator: the program's app hash differs from "
                           "the reference's")
    chain.validators_hash, chain.next_validators_hash, _ = sets.header()
    chain.change_heights = list(sets.change_heights)
    chain.keys = len(by_addr)
    chain.seconds = {"total": time.perf_counter() - t0}
    return chain


def stale_signer(chain: ChurnChain) -> Tuple[int, List[bytes]]:
    """(height, responses): the chain with the commit for ``height``, the
    first that a changed set signs, carrying the signature of the validator
    that left in the place of the one that joined.  The signature is a good
    one, over the right bytes, by a key the set of that height does not
    hold."""
    responses = list(chain.responses)
    responses[chain.stale_height] = chain.stale_response
    return chain.stale_height, responses
