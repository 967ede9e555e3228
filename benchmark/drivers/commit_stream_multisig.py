"""The commit stream over a set of k-of-n multisig validators: closed loop,
one caller, ``ValidatorSet.verify_commit`` over a ring of commits, every call
a sample.

``warmup`` and ``window`` are ``commit_stream``'s, unchanged (the loop does
not know a key type).  ``setup`` and ``check`` are this file's.  The inputs
are ``benchmark/chaingen_multisig.py``'s plain data, judged by
``benchmark/oracle_multisig.py``; this file is the one place where they
become the program's objects: the run's validator set is built from a
genesis document written to JSON and read back and a ``ValidatorSet``
marshalled and decoded, as a node that restarts loads it, never from key
objects in hand.  ``setup`` also refuses, before anything is timed, a
program whose host ``verify_bytes`` does not hold the reference's rules
(``_require_the_guards_oracle``).

Here lanes are not validators: 1,000 precommits flatten into about 3,500
ed25519 lanes, the guard samples 5 % of the LANES of each dispatch, and the
tally reads one verdict a VALIDATOR.  The window answers for both counts.

Traffic parameters: ``ring`` (72: more than either valset cache of the
ed25519 path holds, 64 and 32 entries, since on a live chain no two heights
show one array of signing sub-keys), ``first_height``, ``warmup_calls`` (92:
20 calls and then one lap of the ring), ``signer_counts`` (how many of a
validator's n sub-keys sign a height, with their probabilities),
``lanes_per_commit`` (the band every ring commit must fall in: one lane
bucket, one program), ``tampers``.
"""

from __future__ import annotations

import math
import time

from benchmark import chaingen_multisig as gen
from benchmark.chaingen import CommitCase
from benchmark.harness import (
    check_equal,
    counter_sum,
    counters_delta,
    counters_snapshot,
    guard_events,
)

FAMILY = "tendermint_verify_"


def _base(ctx):
    return ctx.cell.bench.module("drivers", "commit_stream")


def _program_key(ks, v):
    """Validator v's key as the program's object, from its parts."""
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.crypto.multisig import PubKeyMultisigThreshold

    return PubKeyMultisigThreshold(
        ks.k, tuple(PubKeyEd25519(s.pub) for s in ks.signers[v]))


def _block_id(at):
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.core import PartSetHeader

    return BlockID(at.block_hash, PartSetHeader(1, at.parts_hash))


def _with_templates(heights, chain_id):
    """The program's canonical precommit sign-bytes for each height, split
    round the fixed64 timestamp at offset 17 (uvarint type, fixed64 height,
    fixed64 round); ``_case`` holds ``Vote.sign_bytes`` to the result."""
    from tendermint_tpu.types import SignedMsgType
    from tendermint_tpu.types.core import canonical_vote_sign_bytes

    for at in heights:
        tpl = canonical_vote_sign_bytes(
            chain_id, SignedMsgType.PRECOMMIT, at.height, 0, 0, _block_id(at))
        at.head, at.tail = tpl[:17], tpl[25:]
    return heights


def _valset_through_the_round_trip(ks, powers, chain_id):
    """The set as a restarted node holds it: genesis JSON written and read,
    then the ``ValidatorSet`` marshalled and decoded."""
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    doc = GenesisDoc(
        chain_id=chain_id, genesis_time_ns=gen.chaingen.GENESIS_TIME_NS,
        validators=[GenesisValidator(_program_key(ks, v), p, name=f"v{v}")
                    for v, p in enumerate(powers)])
    doc = GenesisDoc.from_json(doc.to_json())
    valset = ValidatorSet.unmarshal(ValidatorSet(
        [Validator(g.pub_key, g.power) for g in doc.validators]).marshal())
    if [v.pub_key.bytes() for v in valset.validators] != ks.keys:
        raise RuntimeError(
            "the program's validator order or key bytes are not the generator's")
    return valset


def _case(pre, valset, chain_id) -> CommitCase:
    """A generator's commit as the program's ``Commit`` over ``valset``."""
    from tendermint_tpu.types import Commit, SignedMsgType, Vote

    block_id = _block_id(pre.at)
    votes = [None if sig is None else Vote(
        vote_type=SignedMsgType.PRECOMMIT, height=pre.at.height, round=0,
        timestamp_ns=pre.stamps[i], block_id=block_id,
        validator_address=val.address, validator_index=i, signature=sig)
        for i, (val, sig) in enumerate(zip(valset.validators, pre.sigs))]
    first = next(i for i, v in enumerate(votes) if v is not None)
    if votes[first].sign_bytes(chain_id) != pre.msgs[first]:
        raise RuntimeError("sign-bytes template does not match Vote.sign_bytes")
    if not pre.structural_ok:  # asked about another block than was signed
        from tendermint_tpu.types import BlockID

        asked = BlockID(bytes(32), block_id.parts_header)
    else:
        asked = block_id
    return CommitCase(pre.name, valset, chain_id, asked, pre.at.height,
                      Commit(block_id, votes), pre)


def setup(ctx):
    t0 = time.perf_counter()
    cfg, traffic = ctx.config, ctx.traffic
    ks = gen.make_keyset(cfg, ctx.seed)
    for v in (0, len(ks.keys) - 1):
        if _program_key(ks, v).bytes() != ks.keys[v]:
            raise RuntimeError("the program's key encoding is not the generator's")
    heights = _with_templates(gen.make_heights(traffic, ctx.seed), cfg["chain_id"])
    t1 = time.perf_counter()
    ring = gen.sign_ring(ks, heights, traffic, ctx.seed)
    lanes = [pre.lanes() for pre in ring]
    lo, hi = traffic["lanes_per_commit"]
    if not all(lo <= n <= hi for n in lanes):
        raise RuntimeError(f"a ring commit outside {lo}-{hi} lanes: {sorted(lanes)}")
    ctx.log(f"setup.generate: keys {t1 - t0:.3f}s, ring "
            f"{time.perf_counter() - t1:.3f}s ({len(ring)} commits x "
            f"{len(ks.keys)} precommits {ks.k}-of-{ks.n}, "
            f"{min(lanes)}-{max(lanes)} lanes a commit, {sum(lanes)} signed)")
    _require_the_guards_oracle(ctx, ks, ring[0])
    t2 = time.perf_counter()
    valset = _valset_through_the_round_trip(ks, ks.powers, cfg["chain_id"])
    cases = [_case(pre, valset, cfg["chain_id"]) for pre in ring]
    ctx.log(f"setup.program_objects: {time.perf_counter() - t2:.3f}s "
            "(genesis JSON and ValidatorSet round trip, 72 Commits)")
    return {"ring": cases, "keyset": ks, "lanes": lanes}


def _require_the_guards_oracle(ctx, ks, pre):
    """The configuration's third guarantee is an audit against the program's
    host code, and a validator whose signature cannot be flattened is decided
    by ``PubKeyMultisigThreshold.verify_bytes`` outright.  A program whose
    ``verify_bytes`` does not hold the reference's rules cannot give the
    configuration's guarantees, and nothing of it is timed: one signature of
    each kind the traffic tampers with, and the valid one, is put to it
    here, and a disagreement with ``benchmark/oracle_multisig.py`` ends the
    run at once (the command exits non-zero, without a result line)."""
    rng = ctx.rng(2)
    trials = [("valid", pre, 0)]
    for kind in ctx.traffic["tampers"]:
        if kind not in gen.SCHEME_FREE:
            trials.append((kind, *gen.tamper(pre, ks, kind, rng)))
    for kind, variant, v in trials:
        want = gen.oracle.verify_bytes(
            variant.keys[v], variant.msgs[v], variant.sigs[v])
        got = _program_key(ks, v).verify_bytes(variant.msgs[v], variant.sigs[v])
        if bool(got) != want.ok:
            raise RuntimeError(
                f"this program's PubKeyMultisigThreshold.verify_bytes, which "
                f"decides what cannot be flattened and which the configuration's "
                f"rules are audited against, says {bool(got)} to a {kind} "
                f"signature and the reference says {want.ok} ({want.rule}): it "
                f"cannot run {ctx.cell.config_name}")


def warmup(ctx, state):
    """``commit_stream``'s, and then the ring turned to where it stopped: the
    window's call j takes ``ring[j % len(ring)]``, and a window that began
    again at ``ring[0]`` would show the valset caches, in its first calls,
    the key arrays the warm-up's last calls left there: a height seen twice
    within twenty, as no chain shows it."""
    _base(ctx).warmup(ctx, state)
    ring = state["ring"]
    at = max(int(ctx.traffic["warmup_calls"]), len(ring)) % len(ring)
    for name in ("ring", "lanes"):
        state[name] = state[name][at:] + state[name][:at]


def window(ctx, state, seconds):
    return _base(ctx).window(ctx, state, seconds)


def _device_verdicts(case, verdicts):
    """(the device's verdict on every sub-signature lane the reference
    walked, sent as the generator recorded them; the program's verdict on
    every present validator, over the columns it collects itself), both
    through the verifier the window used."""
    from tendermint_tpu.crypto.batch import get_batch_verifier, verify_generic

    flat = gen.flat_lanes(verdicts)
    lanes = ([bool(x) for x in get_batch_verifier().verify_ed25519_raw(
        flat["pubs"], flat["msgs"], flat["sigs"])] if flat["pubs"] else [])
    pubkeys, msgs, sigs, _powers = case.valset.collect_commit_sigs(
        case.chain_id, case.commit.block_id, case.height, case.commit)
    return lanes, flat["want"], [bool(x) for x in verify_generic(pubkeys, msgs, sigs)]


def _differ(got, want) -> int:
    return sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))


def check(ctx, state, win, data):
    from tendermint_tpu.types.validator_set import CommitError

    base = _base(ctx)
    ring, ks, lanes = state["ring"], state["keyset"], state["lanes"]
    rng = ctx.rng(1)
    checks = [check_equal(
        "warmup.rejected_valid_commits", state["warmup_rejected"])]

    # in the window.  The loop takes ring[j % len(ring)] for its call j.
    c = data.counters
    calls = win.attempted
    sent = [lanes[j % len(ring)] for j in range(calls)]
    rate = float(ctx.config["verify"]["audit_sample_rate"])
    checks.append(check_equal(
        "window.device_fallback_total",
        int(counter_sum(c, FAMILY + "device_fallback_total"))))
    checks.append(check_equal(
        "window.host_fallback_total",  # multisig_structural among them
        int(counter_sum(c, FAMILY + "host_fallback_total"))))
    checks.append(check_equal(
        "window.audit_mismatch",
        int(counter_sum(c, FAMILY + "device_audit_total", {"outcome": "mismatch"}))))
    want_audited = sum(math.ceil(n * rate) for n in sent)
    checks.append(check_equal(
        f"window.audited_lanes_vs_ceil_{rate:g}_of_each_dispatch",
        int(abs(counter_sum(c, FAMILY + "device_audit_total") - want_audited))))
    # all sub-signatures of a commit ride ONE ed25519 dispatch
    checks.append(check_equal(
        "window.dispatches_vs_one_ed25519_a_call",
        int(abs(counter_sum(c, FAMILY + "calls_total", {"algo": "ed25519"}) - calls)
            + counter_sum(c, FAMILY + "calls_total", {"algo": "secp256k1"}))))
    checks.append(check_equal(
        "window.flattened_validators_and_lanes_vs_generator",
        int(abs(counter_sum(c, FAMILY + "multisig_groups_total") - calls * len(ks.keys))
            + abs(counter_sum(c, FAMILY + "multisig_lanes_total") - sum(sent)))))
    # no two heights show one array of signing sub-keys: a cache keyed by
    # the call's whole key array can never hit (a table keyed by sub-key
    # will, and the benchmark PR that follows it moves this check)
    hits = counter_sum(c, FAMILY + "valset_cache_total", {"result": "hit"})
    misses = [counter_sum(c, FAMILY + "valset_cache_total",
                          {"cache": which, "result": "miss"})
              for which in ("host", "device")]
    off = sum(abs(m - calls) for m in misses) if ctx.platform == "tpu" else 0
    checks.append(check_equal(
        "window.valset_caches_not_missing_every_call", int(hits + off)))
    checks.append(check_equal(
        "window.compiles", int(c.get("compile.programs", 0))))

    # after the window: every sub-signature lane and every validator of
    # every ring commit, and each tampered variant whole, validator for
    # validator and lane for lane, against the reference
    t0 = time.perf_counter()
    before = counters_snapshot()
    known = {}
    lane_mismatch = validator_mismatch = n_lanes = 0
    for i, case in enumerate(ring):
        verdicts, stands = gen.reference_verdicts(case.lanes)
        known[i] = verdicts
        got_lanes, want_lanes, got_vals = _device_verdicts(case, verdicts)
        n_lanes += len(want_lanes)
        lane_mismatch += _differ(got_lanes, want_lanes) + (0 if stands else 1)
        validator_mismatch += _differ(got_vals, [v.ok for v in verdicts if v])
    checks.append(check_equal(
        f"lanes.ring_vs_reference_over_{len(ring)}_commits_{n_lanes}_lanes",
        lane_mismatch))
    checks.append(check_equal(
        f"validators.ring_vs_reference_over_{len(ring)}x{len(ks.keys)}",
        validator_mismatch))

    base_i = int(rng.integers(0, len(ring)))
    valset, chain_id = ring[base_i].valset, ring[base_i].chain_id
    verdict_mismatch = tamper_validator_mismatch = tamper_lane_mismatch = 0
    for kind in ctx.traffic["tampers"]:
        pre, _v = gen.tamper(ring[base_i].lanes, ks, kind, rng)
        verdicts, stands = gen.reference_verdicts(
            pre, known[base_i], ring[base_i].lanes)
        if pre.powers != ks.powers:  # the same keys, the variant's powers
            valset_k = _valset_through_the_round_trip(ks, pre.powers, chain_id)
        else:
            valset_k = valset
        case = _case(pre, valset_k, chain_id)
        try:
            base._call(case)
            accepted = True
        except CommitError:
            accepted = False
        if accepted != stands:
            verdict_mismatch += 1
            ctx.log(f"check: {case.name}: program accepted={accepted}, "
                    f"reference says {stands}")
        if pre.structural_ok:
            got_lanes, want_lanes, got_vals = _device_verdicts(case, verdicts)
            tamper_lane_mismatch += _differ(got_lanes, want_lanes)
            tamper_validator_mismatch += _differ(
                got_vals, [v.ok for v in verdicts if v])
    n_t = len(ctx.traffic["tampers"])
    checks.append(check_equal(
        f"tampered.verdict_vs_reference_over_{n_t}", verdict_mismatch))
    checks.append(check_equal(
        "tampered.validators_vs_reference", tamper_validator_mismatch))
    checks.append(check_equal(
        "tampered.lanes_vs_reference", tamper_lane_mismatch))
    # a wrong device verdict that the audit caught and the host put right
    # shows in no comparison above: it shows here
    checks.append(check_equal(
        "checks.fallbacks_and_audit_mismatches",
        guard_events(counters_delta(before, counters_snapshot()))))
    ctx.log(f"check: reference and tampered commits took "
            f"{time.perf_counter() - t0:.3f}s")
    return checks
