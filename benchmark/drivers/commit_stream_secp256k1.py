"""The commit stream over a secp256k1 validator set: closed loop, one caller,
``ValidatorSet.verify_commit`` over a ring of commits, every call a sample.

``warmup`` and ``window`` are ``commit_stream``'s, unchanged (the loop does
not know a key type).  ``setup`` and ``check`` are this file's: the ring is
signed by ``benchmark/chaingen_secp256k1.py`` and judged by
``benchmark/oracle_secp256k1.py``, and the window also answers for what only
this path has: lanes its host prologue decided (since PR 27 two passes over
the lanes round ONE modular inversion a dispatch), and where the audit's
oracle ran (plain Python, about 4.1 ms a lane on the v5e's host: the 4-lane
worker answers after the 13.8 ms dispatch has, so the audit paces the call,
and 3-12 % of a window's calls end 4 or 8 ms late behind one late worker,
PERF.md 7f).  ``setup`` also
refuses, before anything is timed, a program whose host oracle does not hold
the accept set (``_require_the_guards_oracle``).

Traffic parameters: ``ring``, ``first_height``, ``warmup_calls``,
``tampers``, as ``commit_stream``.
"""

from __future__ import annotations

import math
import time

from benchmark import chaingen_secp256k1 as gen
from benchmark.harness import (
    check_equal,
    counter_sum,
    counters_delta,
    counters_snapshot,
    guard_events,
)

# from this many sampled lanes up the guard hands the audit to its oracle
# workers (crypto/oracle_pool.MIN_POOL_LANES, written out: the yardstick
# does not move with the program's constant)
POOL_FROM_LANES = 8


def _base(ctx):
    return ctx.cell.bench.module("drivers", "commit_stream")


def setup(ctx):
    t0 = time.perf_counter()
    ring = gen.make_commit_ring(ctx.config, ctx.traffic, ctx.seed)
    sizes = sorted({len(s) for case in ring for s in case.lanes.sigs})
    ctx.log(f"setup.generate: {time.perf_counter() - t0:.3f}s "
            f"({len(ring)} commits x {len(ring[0].lanes.pubs)} precommits, "
            f"DER signatures of {sizes[0]}-{sizes[-1]} bytes)")
    _require_the_guards_oracle(ctx, ring[0])
    return {"ring": ring}


def _require_the_guards_oracle(ctx, case):
    """The configuration's third guarantee is an audit against the program's
    host oracle (``PubKeySecp256k1.verify_bytes``).  A program whose host
    oracle does not hold the accept set cannot give it: the audit would
    agree with a device that shares the fault.  Such a program cannot run
    this configuration, and nothing of it is timed: one signature of each
    kind the traffic tampers with is put to the host oracle here, and a
    disagreement with ``benchmark/oracle_secp256k1.py`` ends the run at once
    (the command exits non-zero, without a result line)."""
    rng = ctx.rng(2)
    for kind in ctx.traffic["tampers"]:
        if kind in gen._SCHEME_FREE:
            continue
        variant = gen.tamper(case, kind, rng)
        lanes = variant.lanes
        i = next(k for k, sig in enumerate(lanes.sigs) if sig != case.lanes.sigs[k])
        want = gen.oracle.verify(lanes.pubs[i], lanes.msgs[i], lanes.sigs[i])
        got = variant.valset.validators[i].pub_key.verify_bytes(
            lanes.msgs[i], lanes.sigs[i])
        if bool(got) != want:
            raise RuntimeError(
                f"this program's secp256k1 host oracle, which its guard audits "
                f"the device with, says {bool(got)} to a {kind} signature and "
                f"the configuration's accept set says {want}: it cannot run "
                f"{ctx.cell.config_name}")


def warmup(ctx, state):
    return _base(ctx).warmup(ctx, state)


def window(ctx, state, seconds):
    return _base(ctx).window(ctx, state, seconds)


def check(ctx, state, win, data):
    from tendermint_tpu.types.validator_set import CommitError

    base = _base(ctx)
    ring = state["ring"]
    rng = ctx.rng(1)
    checks = [check_equal(
        "warmup.rejected_valid_commits", state["warmup_rejected"])]

    # in the window: no fallback, no audit mismatch, ceil(5 %) lanes audited
    # a dispatch (by the workers, from 8 lanes up), no lane decided by the
    # host prologue, no item sent round the batch, no compile
    c = data.counters
    checks.append(check_equal(
        "window.device_fallback_total",
        int(counter_sum(c, "tendermint_verify_device_fallback_total"))))
    checks.append(check_equal(
        "window.host_fallback_total",
        int(counter_sum(c, "tendermint_verify_host_fallback_total"))))
    checks.append(check_equal(
        "window.audit_mismatch",
        int(counter_sum(c, "tendermint_verify_device_audit_total",
                        {"outcome": "mismatch"}))))
    lanes = len(ring[0].lanes.pubs)
    want = math.ceil(lanes * float(ctx.config["verify"]["audit_sample_rate"]))
    dispatches = counter_sum(c, "tendermint_verify_calls_total",
                             {"algo": "secp256k1"})
    audited = counter_sum(c, "tendermint_verify_device_audit_total")
    checks.append(check_equal(
        f"window.audited_lanes_vs_{want}_per_dispatch",
        int(abs(audited - want * dispatches)) if dispatches else 1))
    if want >= POOL_FROM_LANES:
        checks.append(check_equal(
            "window.audited_lanes_not_on_the_oracle_workers",
            int(audited - counter_sum(
                c, "tendermint_verify_audit_oracle_total", {"where": "pool"}))))
    checks.append(check_equal(
        "window.host_decided_lanes",
        int(counter_sum(c, "tendermint_verify_secp256k1_host_decided_total"))))
    checks.append(check_equal(
        "window.compiles", int(c.get("compile.programs", 0))))

    # after the window: every lane of every ring commit, and each tampered
    # variant whole, against the oracle
    t0 = time.perf_counter()
    before = counters_snapshot()
    known = {}
    lane_mismatch = 0
    for i, case in enumerate(ring):
        want_lanes, stands = gen.reference_verdict(case.lanes)
        known[i] = want_lanes
        got = base._device_lane_verdicts(case)
        lane_mismatch += sum(a != b for a, b in zip(got, want_lanes))
        lane_mismatch += abs(len(got) - len(want_lanes)) + (0 if stands else 1)
    checks.append(check_equal(
        f"lanes.ring_vs_oracle_over_{len(known)}x{lanes}", lane_mismatch))

    base_i = int(rng.integers(0, len(ring)))
    verdict_mismatch = tamper_lane_mismatch = 0
    for kind in ctx.traffic["tampers"]:
        case = gen.tamper(ring[base_i], kind, rng)
        want_lanes, stands = gen.reference_verdict(
            case.lanes, known[base_i], ring[base_i].lanes)
        try:
            base._call(case)
            accepted = True
        except CommitError:
            accepted = False
        if accepted != stands:
            verdict_mismatch += 1
            ctx.log(f"check: {case.name}: program accepted={accepted}, "
                    f"oracle says {stands}")
        if case.lanes.structural_ok:
            got = base._device_lane_verdicts(case)
            tamper_lane_mismatch += sum(
                a != b for a, b in zip(got, want_lanes))
            tamper_lane_mismatch += abs(len(got) - len(want_lanes))
    checks.append(check_equal(
        f"tampered.verdict_vs_oracle_over_{len(ctx.traffic['tampers'])}",
        verdict_mismatch))
    checks.append(check_equal("tampered.lanes_vs_oracle", tamper_lane_mismatch))
    # a wrong device verdict that the audit caught and the host put right
    # shows in neither comparison above: it shows here
    checks.append(check_equal(
        "checks.fallbacks_and_audit_mismatches",
        guard_events(counters_delta(before, counters_snapshot()))))
    ctx.log(f"check: oracle and tampered commits took "
            f"{time.perf_counter() - t0:.3f}s")
    return checks
