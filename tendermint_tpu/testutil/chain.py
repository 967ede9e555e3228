"""Synthetic signed-chain builder — N validators, K heights, real commits,
real state execution (the reference grows such fixtures ad hoc in
types/test_util.go MakeCommit + consensus/wal_generator.go:31).

Used by the fast-sync tests, the light-client tests, and the fast-sync
benchmark (50k-block replay config, BASELINE.md).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, Optional

from tendermint_tpu.abci.examples.kvstore import KVStoreApp
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.libs.db.kv import DB, MemDB
from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn
from tendermint_tpu.state import store as sm_store
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state_types import State, state_from_genesis
from tendermint_tpu.types import (
    BlockID,
    Commit,
    GenesisDoc,
    GenesisValidator,
    MockPV,
    SignedMsgType,
    Vote,
    VoteSet,
)


@dataclass
class ChainFixture:
    chain_id: str
    genesis: GenesisDoc
    pvs: List[MockPV]  # sorted-set order
    state: State  # state after the last applied block
    state_db: DB
    block_store: BlockStore
    height: int


def build_chain(
    n_vals: int = 4,
    n_heights: int = 10,
    chain_id: str = "chain-fixture",
    txs_per_block: int = 0,
    block_store_db: Optional[DB] = None,
    state_db: Optional[DB] = None,
    app_factory: Optional[Callable[[], object]] = None,
    genesis: Optional[GenesisDoc] = None,
    pvs: Optional[List[MockPV]] = None,
    on_height: Optional[Callable[[int, State], List[bytes]]] = None,
    extra_pvs: Optional[List[MockPV]] = None,
) -> ChainFixture:
    """Builds and EXECUTES a chain: every block's commit is signed by all
    validators and applied through a real BlockExecutor + app, so headers
    (app_hash, results, valset hashes) are exactly what a live node produces.

    on_height(h, state) -> txs lets callers inject txs (e.g. valset changes
    via PersistentKVStoreApp val-txs)."""
    if genesis is None:
        # 4-byte counter repeated: unique for any n_vals (a single repeated
        # byte capped fixtures at 255 validators)
        seeds = [(i + 1).to_bytes(4, "big") * 8 for i in range(n_vals)]
        pv_list = [MockPV(PrivKeyEd25519.generate(s)) for s in seeds]
        genesis = GenesisDoc(
            chain_id=chain_id,
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pv_list],
        )
        genesis.validate_and_complete()
    else:
        pv_list = list(pvs or [])
        chain_id = genesis.chain_id

    st = state_from_genesis(genesis)
    # order pvs by sorted validator-set position; extra_pvs = keys for
    # validators that JOIN mid-chain (via app val-txs) and must sign commits
    by_addr = {pv.get_pub_key().address(): pv for pv in pv_list}
    for pv in extra_pvs or []:
        by_addr[pv.get_pub_key().address()] = pv
    sorted_pvs = [by_addr[v.address] for v in st.validators.validators]

    state_db = state_db if state_db is not None else MemDB()
    sm_store.save_state(state_db, st)
    conn = MultiAppConn(
        LocalClientCreator(app_factory() if app_factory else KVStoreApp())
    )
    conn.start()
    block_exec = BlockExecutor(state_db, conn.consensus)
    block_store = BlockStore(block_store_db if block_store_db is not None else MemDB())

    last_commit = Commit()
    base_time = genesis.genesis_time_ns
    for h in range(1, n_heights + 1):
        if on_height is not None:
            txs = on_height(h, st)
        else:
            txs = [
                f"k{h}-{j}=v{h}".encode() for j in range(txs_per_block)
            ]
        proposer = st.validators.get_proposer()
        block = st.make_block(h, txs, last_commit, [], proposer.address)
        parts = block.make_part_set()
        block_id = BlockID(hash=block.hash(), parts_header=parts.header())

        # all validators precommit (timestamps strictly after block time so
        # the NEXT block's median passes the monotonic-time check)
        vote_set = VoteSet(chain_id, h, 0, SignedMsgType.PRECOMMIT, st.validators)
        for idx, val in enumerate(st.validators.validators):
            pv = by_addr[val.address]
            vote = Vote(
                vote_type=SignedMsgType.PRECOMMIT,
                height=h,
                round=0,
                timestamp_ns=base_time + (h + 1) * 1_000_000_000,
                block_id=block_id,
                validator_address=val.address,
                validator_index=idx,
            )
            vote_set.add_vote(pv.sign_vote(chain_id, vote))
        seen_commit = vote_set.make_commit()

        block_store.save_block(block, parts, seen_commit)
        st = block_exec.apply_block(st, block_id, block)
        last_commit = seen_commit

    return ChainFixture(
        chain_id=chain_id,
        genesis=genesis,
        pvs=sorted_pvs,
        state=st,
        state_db=state_db,
        block_store=block_store,
        height=n_heights,
    )


def fresh_executor(genesis: GenesisDoc, app_factory=KVStoreApp):
    """(genesis state, BlockExecutor over a fresh in-memory kvstore app) —
    the replay side of a fast-sync bench or smoke."""
    st = state_from_genesis(genesis)
    db = MemDB()
    sm_store.save_state(db, st)
    conn = MultiAppConn(LocalClientCreator(app_factory()))
    conn.start()
    return st, BlockExecutor(db, conn.consensus)


def build_commit(
    n_validators: int,
    seed: int = 42,
    chain_id: str = "bench-chain",
    height: int = 500,
):
    """(valset, block_id, commit): one real Commit over ``n_validators``
    seeded ed25519 validators, each precommit's canonical sign-bytes
    differing only in its fixed64 timestamp (as in production).  The
    headline bench (bench.py) and chip_smoke.py verify this commit."""
    from dataclasses import replace

    import numpy as np

    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.types.core import PartSetHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    seeds = np.random.default_rng(seed).bytes(32 * n_validators)
    block_id = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32))
    vals, votes = [], {}
    for i in range(n_validators):
        priv = ed.gen_privkey(seeds[32 * i : 32 * (i + 1)])
        pub = PubKeyEd25519(priv[32:])
        vals.append(Validator(pub, 10))
        vote = Vote(
            vote_type=SignedMsgType.PRECOMMIT,
            height=height,
            round=0,
            timestamp_ns=1_700_000_000_000_000_000 + i * 1_000,
            block_id=block_id,
            validator_address=pub.address(),
            validator_index=i,
        )
        votes[pub.address()] = vote.with_signature(
            ed.sign(priv, vote.sign_bytes(chain_id))
        )
    # ValidatorSet sorts by (power, address): precommits go in set order
    valset = ValidatorSet(vals)
    ordered = [
        replace(votes[val.address], validator_index=i)
        for i, val in enumerate(valset.validators)
    ]
    return valset, block_id, Commit(block_id, ordered)
