"""Closed loop, one caller: ``ValidatorSet.verify_commit`` over a ring of
commits of one validator set, call after call for the window.  Every call
is a sample; host packing, copies, kernel, guard and audit are all inside.

Traffic parameters: ``ring`` (commits), ``first_height``, ``warmup_calls``,
``tampers`` (the seeded variants checked after the window).
"""

from __future__ import annotations

import math
import statistics
import time

from benchmark import chaingen
from benchmark.harness import (
    Window,
    check_equal,
    counter_sum,
    counters_delta,
    counters_snapshot,
    guard_events,
    highest_supported_percentile,
    percentile,
)


def setup(ctx):
    t0 = time.perf_counter()
    ring = chaingen.make_commit_ring(ctx.config, ctx.traffic, ctx.seed)
    ctx.log(f"setup.generate: {time.perf_counter() - t0:.3f}s "
            f"({len(ring)} commits x {len(ring[0].lanes.pubs)} precommits)")
    return {"ring": ring}


def _call(case):
    case.valset.verify_commit(
        case.chain_id, case.block_id, case.height, case.commit)


def warmup(ctx, state):
    from tendermint_tpu.types.validator_set import CommitError

    ring = state["ring"]
    state["warmup_rejected"] = 0
    for k in range(max(int(ctx.traffic["warmup_calls"]), len(ring))):
        try:
            _call(ring[k % len(ring)])
        except CommitError:  # shows as a failed check; the run goes on
            state["warmup_rejected"] += 1


def window(ctx, state, seconds):
    from tendermint_tpu.types.validator_set import CommitError

    ring = state["ring"]
    span = ctx.spans.span
    ends, samples, failed = [], [], 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    k = 0
    while True:
        t0 = time.perf_counter()
        if t0 >= deadline:
            break
        try:
            with span("bench.verify_commit"):
                _call(ring[k % len(ring)])
        except CommitError as e:
            failed += 1
            ctx.log(f"window: call {k} rejected a valid commit: {e}")
        t1 = time.perf_counter()
        samples.append((t1 - t0) * 1e3)
        ends.append(t1)
        k += 1
    elapsed = time.perf_counter() - t_start
    mid = t_start + elapsed / 2
    first = [s for s, e in zip(samples, ends) if e <= mid]
    halves = [
        {"samples": {"verify_commit_ms": first}, "totals": {}},
        {"samples": {"verify_commit_ms": samples[len(first):]}, "totals": {}},
    ]
    notes = [f"window: {len(samples)} calls in {elapsed:.3f}s"]
    q = highest_supported_percentile(len(samples))
    if q is not None:  # the highest tail with ten samples beyond it
        notes.append(f"tail: p{q}={percentile(samples, q):.3f}ms over {len(samples)} calls")
    # every call in order, so a step or a drift inside a run shows
    notes.append("samples_ms: " + " ".join(f"{s:.1f}" for s in samples))
    notes.append(_by_tenth(samples))
    return Window(
        attempted=len(samples), failed=failed, seconds=elapsed,
        samples={"verify_commit_ms": samples}, totals={"calls": len(samples)},
        halves=halves,
        notes=notes,
    )


def _by_tenth(samples):
    """The median call of ten consecutive groups: a step inside a run (the
    allocator's two states were one, PERF.md) shows at a glance."""
    cuts = [len(samples) * i // 10 for i in range(11)]
    ms = [round(statistics.median(samples[a:b]), 1)
          for a, b in zip(cuts, cuts[1:]) if b > a]
    return f"by_tenth: p50_ms={ms}"


def _device_lane_verdicts(case):
    """Per-lane verdicts through the verifier the window used, over the
    columns the program itself collects from the commit."""
    from tendermint_tpu.crypto.batch import verify_generic

    pubkeys, msgs, sigs, _powers = case.valset.collect_commit_sigs(
        case.chain_id, case.commit.block_id, case.height, case.commit)
    return [bool(x) for x in verify_generic(pubkeys, msgs, sigs)]


def check(ctx, state, win, data):
    from tendermint_tpu.types.validator_set import CommitError

    ring = state["ring"]
    rng = ctx.rng(1)
    checks = [check_equal(
        "warmup.rejected_valid_commits", state["warmup_rejected"])]

    # in the window: no fallback, no audit mismatch, ceil(5 %) lanes audited
    c = data.counters
    checks.append(check_equal(
        "window.device_fallback_total",
        int(counter_sum(c, "tendermint_verify_device_fallback_total"))))
    checks.append(check_equal(
        "window.audit_mismatch",
        int(counter_sum(c, "tendermint_verify_device_audit_total",
                        {"outcome": "mismatch"}))))
    lanes = len(ring[0].lanes.pubs)
    want = math.ceil(lanes * float(ctx.config["verify"]["audit_sample_rate"]))
    dispatches = counter_sum(c, "tendermint_verify_calls_total")
    audited = counter_sum(c, "tendermint_verify_device_audit_total")
    checks.append(check_equal(
        f"window.audited_lanes_vs_{want}_per_dispatch",
        int(abs(audited - want * dispatches)) if dispatches else 1))
    checks.append(check_equal(
        "window.compiles", int(c.get("compile.programs", 0))))

    # after the window: every lane of every ring commit, and each tampered
    # variant whole, against the oracle
    t0 = time.perf_counter()
    before = counters_snapshot()
    known = {}
    lane_mismatch = 0
    for i in range(len(ring)):
        want_lanes, stands = chaingen.reference_verdict(ring[i].lanes)
        known[i] = want_lanes
        got = _device_lane_verdicts(ring[i])
        lane_mismatch += sum(a != b for a, b in zip(got, want_lanes))
        lane_mismatch += abs(len(got) - len(want_lanes)) + (0 if stands else 1)
    checks.append(check_equal(
        f"lanes.ring_vs_oracle_over_{len(known)}x{lanes}", lane_mismatch))

    base_i = int(rng.integers(0, len(ring)))
    verdict_mismatch = tamper_lane_mismatch = 0
    for kind in ctx.traffic["tampers"]:
        case = chaingen.tamper(ring[base_i], kind, rng)
        want_lanes, stands = chaingen.reference_verdict(
            case.lanes, known[base_i], ring[base_i].lanes)
        try:
            _call(case)
            accepted = True
        except CommitError:
            accepted = False
        if accepted != stands:
            verdict_mismatch += 1
            ctx.log(f"check: {case.name}: program accepted={accepted}, "
                    f"oracle says {stands}")
        if case.lanes.structural_ok:
            got = _device_lane_verdicts(case)
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
