"""``benchmark/valset_reference`` against the program's ``ValidatorSet``,
``update_state`` and ``PersistentKVStoreApp`` on seeded update lists: the
order, both hashes and the proposer equal at every height."""

import numpy as np
import pytest

from benchmark import chaingen
from benchmark import valset_reference as ref


def _keys(rng, n):
    return [s.pub for s in chaingen.make_signers(n, rng)]


class Program:
    """The program's side, a block at a time: 'val:' transactions through
    the example app's DeliverTx and EndBlock, then ``update_state``."""

    def __init__(self, genesis):
        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApp
        from tendermint_tpu.crypto.keys import PubKeyEd25519
        from tendermint_tpu.state.state_types import state_from_genesis
        from tendermint_tpu.types import GenesisDoc, GenesisValidator

        doc = GenesisDoc(
            chain_id="ref-chain", genesis_time_ns=chaingen.GENESIS_TIME_NS,
            validators=[GenesisValidator(PubKeyEd25519(p), w) for p, w in genesis])
        doc.validate_and_complete()
        self.state = state_from_genesis(doc)
        self.app = PersistentKVStoreApp()

    def header(self):
        st = self.state
        return (st.validators.hash(), st.next_validators.hash(),
                st.validators.get_proposer().address)

    def members(self):
        return [(v.pub_key.bytes(), v.voting_power)
                for v in self.state.validators.validators]

    def end_block(self, updates):
        from types import SimpleNamespace

        from tendermint_tpu.abci import types as abci
        from tendermint_tpu.state import store
        from tendermint_tpu.state.execution import update_state
        from tendermint_tpu.types import BlockID

        self.app.begin_block(abci.RequestBeginBlock())
        for pub, power in updates:
            res = self.app.deliver_tx(abci.RequestDeliverTx(tx=ref.val_tx(pub, power)))
            assert res.code == abci.CODE_TYPE_OK
        end = self.app.end_block(abci.RequestEndBlock())
        header = SimpleNamespace(
            height=self.state.last_block_height + 1, num_txs=len(updates),
            time_ns=0)
        self.state = update_state(
            self.state, BlockID(), header,
            store.ABCIResponses(deliver_tx=[], end_block=end))


def _same(program, sets, height):
    assert program.header() == sets.header(), height
    assert program.members() == sets.current.members(), height


def _random_updates(rng, sitting, fresh, lo=1, hi=20):
    """A seeded mix: each block draws some of join, removal, re-power."""
    kinds = rng.permutation(["join", "leave", "repower", "repower"])
    kinds = kinds[: int(rng.integers(1, 5))]
    updates, touched = [], set()
    for kind in kinds:
        free = [m for m in sitting if m[0] not in touched]
        if kind == "join":
            updates.append((fresh.pop(), int(rng.integers(lo, hi + 1))))
        elif kind == "leave" and len(free) > 2:
            pub = free[int(rng.integers(0, len(free)))][0]
            updates.append((pub, 0))
            touched.add(pub)
        elif kind == "repower" and free:
            pub = free[int(rng.integers(0, len(free)))][0]
            updates.append((pub, int(rng.integers(lo, hi + 1))))
            touched.add(pub)
    return updates


@pytest.mark.parametrize("n_vals", [4, 8, 16])
def test_reference_and_program_agree_at_every_height(n_vals):
    rng = np.random.default_rng([2**31 + 33, n_vals])
    genesis = [(p, 10) for p in _keys(rng, n_vals)]
    fresh = _keys(rng, 40)
    program, sets = Program(genesis), ref.Evolution(genesis)
    _same(program, sets, 1)
    kinds = set()
    for h in range(1, 61):
        updates = []
        if h % 3 == 0:
            updates = _random_updates(rng, sets.next.members(), fresh)
            known = {p for p, _ in sets.next.members()}
            kinds |= {"leave" if w == 0 else "repower" if p in known else "join"
                      for p, w in updates}
        program.end_block(updates)
        sets.end_block(updates)
        _same(program, sets, h + 1)
    assert kinds == {"join", "leave", "repower"}
    assert len(sets.change_heights) >= 10
    # a change made in block h binds h + 2, never h + 1
    assert all((h - 2) % 3 == 0 for h in sets.change_heights)


@pytest.mark.parametrize("n_vals", [4, 8, 16])
def test_a_removal_and_a_join_in_one_block(n_vals):
    rng = np.random.default_rng([2**31 + 34, n_vals])
    genesis = [(p, 10) for p in _keys(rng, n_vals)]
    (joiner,) = _keys(rng, 1)
    program, sets = Program(genesis), ref.Evolution(genesis)
    updates = [(genesis[1][0], 0), (joiner, 10)]
    for u in (updates, [], [], []):
        program.end_block(u)
        sets.end_block(u)
        _same(program, sets, sets.height)
    assert sets.change_heights == [3]
    members = dict(sets.current.members())
    assert joiner in members and genesis[1][0] not in members
    assert len(members) == n_vals
    # in address order, which is not the order of arrival
    addrs = [ref.address(p) for p, _ in sets.current.members()]
    assert addrs == sorted(addrs)


@pytest.mark.parametrize("n_vals", [4, 8, 16])
def test_an_unknown_removal_and_a_negative_power_are_refused(n_vals):
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.state.execution import update_validators

    rng = np.random.default_rng([2**31 + 35, n_vals])
    genesis = [(p, 10) for p in _keys(rng, n_vals)]
    (stranger,) = _keys(rng, 1)
    for bad in ([(stranger, 0)], [(genesis[0][0], -1)]):
        sets = ref.Evolution(genesis)
        with pytest.raises(ValueError):
            sets.end_block(bad)
        program = Program(genesis)
        with pytest.raises(ValueError):
            update_validators(
                program.state.next_validators.copy(),
                [abci.ValidatorUpdate("ed25519", p, w) for p, w in bad])


def test_the_reference_imports_nothing_of_the_programs_set_code():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(ref))
    names = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not [n for n in names if n.startswith("tendermint_tpu")]
    assert [n for n in names if n.startswith("benchmark")] == ["benchmark.chaingen"]


def test_the_proposer_rotates_by_power():
    """Three validators of power 1, 2, 3: over six heights each proposes as
    often as its power says (validator_set.go's weighted round robin)."""
    rng = np.random.default_rng(2**31 + 36)
    pubs = _keys(rng, 3)
    genesis = list(zip(pubs, (1, 2, 3)))
    sets, program = ref.Evolution(genesis), Program(genesis)
    seen = []
    for _ in range(6):
        seen.append(sets.header()[2])
        _same(program, sets, sets.height)
        sets.end_block()
        program.end_block([])
    assert sorted(seen.count(ref.address(p)) for p in pubs) == [1, 2, 3]
