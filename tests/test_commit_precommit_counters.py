"""What a commit held and the launches a call made, as the program counts
them: ``tendermint_verify_commit_precommits_total{kind}`` (three adds a
``verify_commit``, never one a lane) and
``tendermint_verify_ed25519_launches_total`` (one a ``_verify_uniform``
launch), the second through the kernel's host wrapper with the program stood
in for, as tests/test_ed25519_pack.py does."""

from types import SimpleNamespace

import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.libs.metrics import VerifyMetrics, get_verify_metrics
from tendermint_tpu.types.block import Commit
from tendermint_tpu.types.core import BlockID, PartSetHeader, SignedMsgType
from tendermint_tpu.types.validator_set import CommitError, Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote

CHAIN = "counted-chain"
HEIGHT = 31
HELD = "tendermint_verify_commit_precommits_total"
LAUNCHES = "tendermint_verify_ed25519_launches_total"
PACK = "tendermint_verify_ed25519_pack_total"
BLOCK = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\x55" * 32))
OTHER = BlockID(b"\x5c" * 32, PartSetHeader(1, b"\xa3" * 32))
NIL = BlockID()


def test_both_families_are_exposed_from_zero():
    text = VerifyMetrics().registry.expose_text()
    for kind in ("for_block", "stray", "absent"):
        assert f'{HELD}{{kind="{kind}"}} 0' in text
    assert f"{LAUNCHES} 0" in text
    assert f"# TYPE {HELD} counter" in text and f"# TYPE {LAUNCHES} counter" in text


def _chain(n):
    seeds = np.random.default_rng(5100 + n).bytes(32 * n)
    privs = [PrivKeyEd25519.generate(seeds[32 * i:32 * (i + 1)]) for i in range(n)]
    valset = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_address = {p.pub_key().address(): p for p in privs}
    privs = [by_address[v.address] for v in valset.validators]

    def vote(i, block_id=BLOCK):
        v = Vote(vote_type=SignedMsgType.PRECOMMIT, height=HEIGHT, round=0,
                 timestamp_ns=1_700_000_000_000_000_000 + 1_001 * i,
                 block_id=block_id, validator_address=valset.validators[i].address,
                 validator_index=i)
        return v.with_signature(privs[i].sign(v.sign_bytes(CHAIN)))

    return SimpleNamespace(valset=valset, vote=vote, n=n)


CHAINS = {}


def _commit(n, absent=(), nil=(), other=()):
    ch = CHAINS.setdefault(n, _chain(n))
    votes = [None if i in absent
             else ch.vote(i, NIL if i in nil else OTHER if i in other else BLOCK)
             for i in range(n)]
    return ch, Commit(BLOCK, votes)


@pytest.fixture
def adds(monkeypatch):
    """Every ``add`` of the family during the test, in order."""
    counter = get_verify_metrics().commit_precommits
    seen = []
    real = counter.add

    def add(value, labels=()):
        seen.append((labels[0], value))
        real(value, labels)

    monkeypatch.setattr(counter, "add", add)
    return seen


# a tenth of the slots absent, another tenth for nil, one for another block
@pytest.mark.parametrize("n", (4, 64, 1000))
@pytest.mark.parametrize("shape", ["all_for_block", "absent_and_nil", "one_other_block"])
def test_three_adds_a_call_whatever_the_lanes(n, shape, adds, verify_counters):
    absent = range(0, n, 10) if shape == "absent_and_nil" else ()
    nil = range(1, n, 10) if shape == "absent_and_nil" else ()
    other = (n - 1,) if shape == "one_other_block" else ()
    ch, commit = _commit(n, set(absent), set(nil), set(other))
    before = {k: verify_counters(HELD, {"kind": k})
              for k in ("for_block", "stray", "absent")}
    try:
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit)
    except CommitError as e:  # 2 of 4 slots for the block: counted all the same
        assert "insufficient voting power" in str(e) and n == 4
    want = {"absent": len(absent), "stray": len(nil) + len(other)}
    want["for_block"] = n - want["absent"] - want["stray"]
    assert adds == [("for_block", float(want["for_block"])),
                    ("stray", float(want["stray"])),
                    ("absent", float(want["absent"]))]
    assert {k: verify_counters(HELD, {"kind": k}) - before[k] for k in before} == want


def test_a_commit_refused_by_its_shape_counts_nothing_and_one_refused_later_counts(adds):
    ch, commit = _commit(64, absent={3}, nil={5})
    with pytest.raises(CommitError, match="wrong block id"):
        ch.valset.verify_commit(CHAIN, OTHER, HEIGHT, commit)
    with pytest.raises(CommitError, match="wrong height"):
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT + 1, commit)
    assert adds == []
    # a bad signature and a missing quorum are found after the collector
    bad = ch.vote(7)
    bad = bad.with_signature(bytes([bad.signature[0] ^ 1]) + bad.signature[1:])
    pcs = list(commit.precommits)
    pcs[7] = bad
    with pytest.raises(CommitError, match="invalid signature"):
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, Commit(BLOCK, pcs))
    assert adds == [("for_block", 62.0), ("stray", 1.0), ("absent", 1.0)]
    del adds[:]
    _, few = _commit(64, nil=set(range(0, 64, 2)))
    with pytest.raises(CommitError, match="insufficient voting power"):
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, few)
    assert adds == [("for_block", 32.0), ("stray", 32.0), ("absent", 0.0)]


def test_the_collect_span_says_what_the_commit_held(tracing):
    ch, commit = _commit(64, absent={3, 9}, nil={5}, other={6})
    ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit)
    (collect,) = [e for e in tracing.export()
                  if e.get("ph") == "X" and e["name"] == "commit.collect"]
    assert (collect["args"]["n"], collect["args"]["absent"],
            collect["args"]["strays"]) == (64, 2, 2)


# ---------------------------------------------------------------------------
# Launches: the kernel's host wrapper, the program stood in for
# ---------------------------------------------------------------------------


@pytest.fixture
def pallas(monkeypatch):
    """``ops/ed25519_pallas`` with no chip: ``call_jit`` stood in for, each
    launch recorded by its padded lanes; every lane comes back true."""
    from tendermint_tpu.ops import dispatch
    from tendermint_tpu.ops import ed25519_pallas as ep

    launches = []

    def fake_call_jit(fn, *args, **static):
        if fn is ep._gather_valset_rows:  # a membership's rows: it runs, here
            return fn(*args)
        if fn is ep._build_valset_windows:  # its window tables: not here
            return np.zeros((args[0].shape[0], ep._WINDOW_WORDS), np.uint32)
        assert fn is ep._device_verify_packed
        launches.append(int(np.asarray(args[3]).shape[0]))
        return np.ones((launches[-1],), dtype=bool)

    monkeypatch.setattr(dispatch, "accelerator", lambda: object())
    monkeypatch.setattr(ep, "call_jit", fake_call_jit)
    monkeypatch.setattr(ep, "_valset_cache", {})
    monkeypatch.setattr(ep, "_dev_valset_cache", {})
    monkeypatch.setattr(ep, "_valset_tables", {})
    return SimpleNamespace(ep=ep, launches=launches,
                           device=batch.TPUBatchVerifier(backend="pallas"))


def _lanes(lengths):
    """One lane a length given, with a real key (the limbs are decompressed
    on the host before any launch)."""
    n = len(lengths)
    pub = PrivKeyEd25519.generate(b"\x07" * 32).pub_key().bytes()
    pubs = np.frombuffer(pub * n, dtype=np.uint8).reshape(n, 32)
    sigs = np.zeros((n, 64), dtype=np.uint8)
    return pubs, [bytes([i % 251]) * ln for i, ln in enumerate(lengths)], sigs


@pytest.mark.parametrize("lengths,launches,path", [
    ([105] * 40, 1, "uniform"),
    ([105] * 37 + [41] * 3, 2, "grouped"),
    ([41] * 3 + [105] * 37, 2, "grouped"),  # the order of the lanes is no matter
    ([105] * 30 + [41] * 5 + [110] * 5, 3, "grouped"),
    ([41], 1, "uniform"),
])
def test_one_launch_a_message_length(lengths, launches, path, pallas, verify_counters):
    before = {name: verify_counters(*key) for name, key in {
        "launches": (LAUNCHES,), "uniform": (PACK, {"path": "uniform"}),
        "grouped": (PACK, {"path": "grouped"})}.items()}
    ok = pallas.ep.verify_batch(*_lanes(lengths))
    assert ok.shape == (len(lengths),) and ok.all()
    assert len(pallas.launches) == launches
    assert verify_counters(LAUNCHES) - before["launches"] == launches
    moved = {p: verify_counters(PACK, {"path": p}) - before[p]
             for p in ("uniform", "grouped")}
    assert moved == {"uniform": float(path == "uniform"),
                     "grouped": float(path == "grouped")}


def test_no_lane_no_launch(pallas, verify_counters):
    before = verify_counters(LAUNCHES)
    assert pallas.ep.verify_batch(*_lanes([])).shape == (0,)
    assert verify_counters(LAUNCHES) == before and pallas.launches == []


@pytest.mark.parametrize("nil,launches,lanes", [
    (set(), 1, [128]), ({1, 40}, 2, [128, 128]), (set(range(64)), 1, [128])])
def test_a_commit_with_a_precommit_for_nil_is_two_launches(
        nil, launches, lanes, pallas, verify_counters, tracing):
    ch, commit = _commit(64, absent={3, 9}, nil=nil - {3, 9})
    before = verify_counters(LAUNCHES), verify_counters(PACK, {"path": "grouped"})
    try:  # the stand-in's verdicts are all true; the tally decides
        ch.valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit, verifier=pallas.device)
    except CommitError as e:
        assert "insufficient voting power" in str(e) and len(nil) == 64
    assert verify_counters(LAUNCHES) - before[0] == launches
    assert verify_counters(PACK, {"path": "grouped"}) - before[1] == (launches == 2)
    assert pallas.launches == lanes
    spans = [e for e in tracing.export() if e.get("ph") == "X"]
    (prepare,) = [e for e in spans if e["name"] == "dispatch.prepare"]
    assert prepare["args"]["groups"] == launches
    assert sum(e["name"] == "dispatch.launch" for e in spans) == launches
    assert sum(e["name"] == "verify.dispatch" for e in spans) == 1
