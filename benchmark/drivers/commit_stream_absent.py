"""The commit stream of a live chain at the edge of its quorum: closed loop,
one caller, ``ValidatorSet.verify_commit`` over a ring of commits in which a
third less one of the slots is empty or for nil, another subset every
height, every commit decoded from its wire bytes.

``warmup`` and ``window`` are ``commit_stream``'s (the loop does not know
what a commit holds); ``setup`` and ``check`` are this file's.  The inputs
are ``benchmark/chaingen_absent.py``'s plain data, judged by
``benchmark/commit_reference.py``; this file is the one place where they
become the program's objects, and a commit becomes one only through
``Commit.unmarshal`` of its bytes, so every vote carries a ``BlockID`` of
its own, as on a node.

A call sends 7,000 lanes of two message lengths (a precommit for nil signs
shorter bytes): the kernel's host wrapper regroups them and launches once a
length, and no two heights show one key array, so both valset caches miss.
The window answers for all of it by count: what each commit held, the
launches, the regrouped calls, the audited lanes.

Traffic parameters: ``ring`` (72: more than either valset cache holds),
``first_height``, ``warmup_calls`` (92: one lap of the ring and 20 calls),
``absent`` and ``nil`` (slots a height), ``check_commits`` (how many ring
commits are judged lane for lane after the window), ``tampers``.
"""

from __future__ import annotations

import math
import time
from typing import Dict, NamedTuple

from benchmark import chaingen_absent as gen
from benchmark import commit_reference as ref
from benchmark.chaingen import CommitCase
from benchmark.drivers import commit_stream as base
from benchmark.harness import (
    check_equal,
    counter_sum,
    counters_delta,
    counters_snapshot,
    guard_events,
)

FAMILY = "tendermint_verify_"
HELD = FAMILY + "commit_precommits_total"
LAUNCHES = FAMILY + "ed25519_launches_total"
KINDS = {"for_block": gen.FOR_BLOCK, "stray": gen.FOR_NIL, "absent": gen.ABSENT}


class Shape(NamedTuple):
    """What one commit holds, from the generator's data."""

    held: Dict[str, int]  # slots of each kind
    lanes: int  # precommits that are there
    lengths: int  # distinct sign-bytes lengths among them


def _require_the_counters(ctx):
    """The cell's window is judged by what the program counts: the slots a
    commit held, by kind, and the launches a call made.  A program without
    either family cannot be held to the configuration's guarantees, and
    nothing of it is timed: the run ends here (the command exits non-zero,
    without a result line)."""
    have = {key.partition("{")[0] for key in counters_snapshot()}
    missing = [fam for fam in (HELD, LAUNCHES) if fam not in have]
    if missing:
        raise RuntimeError(
            f"this program does not count {' or '.join(missing)}: what a "
            f"commit held and the launches a call made cannot be read, so it "
            f"cannot run {ctx.cell.config_name}")


def _valset(keys):
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    valset = ValidatorSet([Validator(PubKeyEd25519(pub), power)
                           for pub, power in zip(keys.pubs, keys.powers)])
    if [v.pub_key.bytes() for v in valset.validators] != keys.pubs:
        raise RuntimeError("the program's validator order is not the generator's")
    return valset


def _case(live, valset) -> CommitCase:
    """A generator's commit as a node holds it: decoded from its bytes."""
    from tendermint_tpu.types import BlockID, Commit
    from tendermint_tpu.types.core import PartSetHeader

    asked = BlockID(live.asked.hash, PartSetHeader(
        live.asked.parts_total, live.asked.parts_hash))
    return CommitCase(live.name, valset, live.chain_id, asked, live.height,
                      Commit.unmarshal(live.wire), live)


def _shape(live, chain_id) -> Shape:
    held = {name: live.count(kind) for name, kind in KINDS.items()}
    voted = {p.block_id for p in live.precommits if p}
    lengths = {len(ref.sign_bytes(chain_id, ref.PRECOMMIT, live.height, 0, 0, b))
               for b in voted}
    return Shape(held, held["for_block"] + held["stray"], len(lengths))


def setup(ctx):
    _require_the_counters(ctx)
    cfg, traffic = ctx.config, ctx.traffic
    t0 = time.perf_counter()
    keys = gen.make_keys(cfg, ctx.seed)
    t1 = time.perf_counter()
    ring = gen.make_ring(keys, cfg, traffic, ctx.seed)
    t2 = time.perf_counter()
    shapes = [_shape(live, cfg["chain_id"]) for live in ring]
    held, lanes, lengths = shapes[0]
    ctx.log(f"setup.generate: keys {t1 - t0:.3f}s, ring {t2 - t1:.3f}s "
            f"({len(ring)} commits x {len(keys.pubs)} slots: "
            f"{held['for_block']} for the block, {held['stray']} for nil, "
            f"{held['absent']} absent; {lanes} lanes of {lengths} lengths, "
            f"{sum(len(c.wire) for c in ring)} bytes on the wire)")
    if len({tuple(p is None for p in c.precommits) for c in ring}) != len(ring):
        raise RuntimeError("two heights of the ring share an absent set")
    valset = _valset(keys)
    cases = [_case(live, valset) for live in ring]
    ctx.log(f"setup.program_objects: {time.perf_counter() - t2:.3f}s "
            f"(one ValidatorSet, {len(cases)} commits decoded from bytes)")
    return {"ring": cases, "keys": keys, "shapes": shapes}


def warmup(ctx, state):
    """``commit_stream``'s, and then the ring turned to where it stopped:
    the window's call j takes ``ring[j % len(ring)]``, and a window that
    began again at ``ring[0]`` would show the valset caches, in its first
    calls, the key arrays of the warm-up's last calls."""
    base.warmup(ctx, state)
    ring = state["ring"]
    at = max(int(ctx.traffic["warmup_calls"]), len(ring)) % len(ring)
    for name in ("ring", "shapes"):
        state[name] = state[name][at:] + state[name][:at]


window = base.window
# per-lane verdicts through the verifier the window used, over the lanes the
# program itself collects from the decoded commit
_device_lane_verdicts = base._device_lane_verdicts


def _differ(got, want) -> int:
    return sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))


def check(ctx, state, win, data):
    from tendermint_tpu.types.validator_set import CommitError

    ring, keys, shapes = state["ring"], state["keys"], state["shapes"]
    rng = ctx.rng(1)
    checks = [check_equal(
        "warmup.rejected_valid_commits", state["warmup_rejected"])]

    # in the window.  The loop takes ring[j % len(ring)] for its call j.
    c = data.counters
    calls = win.attempted
    sent = [shapes[j % len(ring)] for j in range(calls)]
    rate = float(ctx.config["verify"]["audit_sample_rate"])
    on_chip = ctx.platform == "tpu"  # the host verifier packs and launches nothing
    checks.append(check_equal(
        "window.device_fallback_total",
        int(counter_sum(c, FAMILY + "device_fallback_total"))))
    checks.append(check_equal(
        "window.host_fallback_total",
        int(counter_sum(c, FAMILY + "host_fallback_total"))))
    checks.append(check_equal(
        "window.audit_mismatch",
        int(counter_sum(c, FAMILY + "device_audit_total", {"outcome": "mismatch"}))))
    want_audited = sum(math.ceil(sh.lanes * rate) for sh in sent)
    checks.append(check_equal(
        f"window.audited_lanes_vs_ceil_{rate:g}_of_each_call",
        int(abs(counter_sum(c, FAMILY + "device_audit_total") - want_audited))))
    checks.append(check_equal(
        "window.precommits_counted_vs_generator",
        int(sum(abs(counter_sum(c, HELD, {"kind": kind})
                    - sum(sh.held[kind] for sh in sent))
                for kind in KINDS))))
    # a call whose lanes have several lengths is regrouped and launched once
    # a length (the PR that merges the launches moves these two)
    grouped = sum(sh.lengths > 1 for sh in sent)
    pack = FAMILY + "ed25519_pack_total"
    checks.append(check_equal(
        "window.regrouped_calls_vs_one_a_call_of_several_lengths",
        int(abs(counter_sum(c, pack, {"path": "grouped"}) - grouped)
            + abs(counter_sum(c, pack, {"path": "uniform"}) - (calls - grouped)))
        if on_chip else 0))
    checks.append(check_equal(
        "window.launches_vs_one_a_length_a_call",
        int(abs(counter_sum(c, LAUNCHES) - sum(sh.lengths for sh in sent)))
        if on_chip else 0))
    checks.append(check_equal(
        "window.compiles", int(c.get("compile.programs", 0))))

    # after the window: every lane of the ring's commits through the device
    # path, and each tampered commit whole and lane for lane, against the
    # reference
    t0 = time.perf_counter()
    before = counters_snapshot()
    n_checked = min(int(ctx.traffic["check_commits"]), len(ring))
    picked = sorted(rng.permutation(len(ring))[:n_checked].tolist())
    base_i = picked[int(rng.integers(0, n_checked))]
    memo = {}  # the oracle's answers for the commit the tampers start from
    lane_mismatch = n_lanes = 0
    for i in picked:
        want = gen.reference_verdict(
            ring[i].lanes, keys, memo if i == base_i else None)
        n_lanes += len(want.lanes)
        lane_mismatch += _differ(_device_lane_verdicts(ring[i]), want.lanes)
        lane_mismatch += 0 if want.stands else 1
    checks.append(check_equal(
        f"lanes.ring_vs_reference_over_{n_checked}_commits_{n_lanes}_lanes",
        lane_mismatch))

    verdict_mismatch = tamper_lane_mismatch = 0
    for kind in ctx.traffic["tampers"]:
        live = gen.tamper(ring[base_i].lanes, keys, kind, rng)
        want = gen.reference_verdict(live, keys, memo)
        case = _case(live, ring[base_i].valset)
        try:
            base._call(case)
            accepted = True
        except CommitError:
            accepted = False
        if accepted != want.stands:
            verdict_mismatch += 1
            ctx.log(f"check: {case.name}: program accepted={accepted}, "
                    f"reference says {want.stands} ({want.rule})")
        if want.lanes:  # the reference reached the signatures
            tamper_lane_mismatch += _differ(
                _device_lane_verdicts(case), want.lanes)
    n_t = len(ctx.traffic["tampers"])
    checks.append(check_equal(
        f"tampered.verdict_vs_reference_over_{n_t}", verdict_mismatch))
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
