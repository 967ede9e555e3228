"""Fast-sync replay benchmark (BASELINE.md "50k-block fast-sync replay",
ref harness: benchmarks/blockchain/localsync.sh + blockchain/reactor.go:335).

Measures the verify→apply pipeline blocks/s on a pre-built signed chain:
  * baseline — the reference's shape: per-height serial host commit verify
    (types/validator_set.go:273-298) + apply;
  * ours — windowed batched device verification (verify_block_window: every
    (height, validator) signature of a window in ONE dispatch) + apply with
    trusted commits.

Usage: python scripts/bench_fastsync.py [n_blocks] [n_vals] [window]
       python scripts/bench_fastsync.py [n_blocks] [n_vals] --sweep
       ... [--metrics-out PATH]  # Prometheus snapshot of the verify families
Prints one JSON line: {"metric": "fastsync_replay", "value": blocks/s, ...}
--sweep instead re-runs the verify+apply pipeline over a ladder of window
sizes and prints one JSON line per window (how VERIFY_WINDOW's default was
chosen — blockchain/reactor.py:46).
--null-verify swaps in a free all-true verifier: the resulting blocks/s is
the HOST PIPELINE CEILING (sign-bytes assembly, packing, apply, store) that
bounds end-to-end throughput no matter how fast the device verifies — the
number the window-size sweep is judged by on machines without the chip.
--ragged-valsets skips the chain replay and instead benches the
verification planner on the acceptance workload (32 heights, valset sizes
cycling {1, 4, 16, 64}): ragged lane packing vs the dense (H × max V) grid,
emitting lane-occupancy and bucket-hit stats in the JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _bench_metrics import (  # noqa: E402
    bench_verifier,
    pop_metrics_out,
    write_snapshot,
)

METRICS_OUT = pop_metrics_out()
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
N_BLOCKS = int(_pos[0]) if len(_pos) > 0 else 2048
N_VALS = int(_pos[1]) if len(_pos) > 1 else 64
WINDOW = int(_pos[2]) if len(_pos) > 2 else 512
SWEEP = "--sweep" in sys.argv
NULL_VERIFY = "--null-verify" in sys.argv
RAGGED = "--ragged-valsets" in sys.argv
SWEEP_WINDOWS = [16, 64, 128, 256, 512, 1024]
BASELINE_SAMPLE_BLOCKS = 64  # serial blocks to time (extrapolated)
RAGGED_SIZES = [1, 4, 16, 64] * 8  # 32 heights, 680 present lanes
RAGGED_REPS = 8


class NullVerifier:
    """All-true, zero-cost: isolates the host pipeline ceiling."""

    name = "null"

    def verify_ed25519(self, items):
        import numpy as np

        return np.ones((len(items),), dtype=bool)

    verify_secp256k1 = verify_ed25519

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        # column form: the ceiling must measure the same fast path the
        # production verifiers take (crypto/batch.py verify_ed25519_raw)
        import numpy as np

        return np.ones((len(pubs),), dtype=bool)


def run_ragged():
    """Planner occupancy/throughput on the ragged acceptance workload:
    lane-packed bucketed dispatch vs the unpacked (H × max V) grid path —
    both on the same backend, so the ratio isolates the packing win."""
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.parallel import commit_verify as cv
    from tendermint_tpu.parallel import planner

    sizes = RAGGED_SIZES
    votes, powers, totals = [], [], []
    i = 0
    for h, V in enumerate(sizes):
        vrow, prow = [], []
        for v in range(V):
            priv = ed.gen_privkey(bytes([(i % 251) + 1, (i // 251) + 1]) * 16)
            msg = b"ragged-%d-%d" % (h, v)
            vrow.append((priv[32:], msg, ed.sign(priv, msg)))
            prow.append(v % 7 + 1)
            i += 1
        votes.append(vrow)
        powers.append(prow)
        totals.append(sum(prow))
    present = sum(sizes)
    grid_lanes = len(sizes) * max(sizes)
    print(
        f"# ragged window: {len(sizes)} heights, {present} votes "
        f"(grid would dispatch {grid_lanes} lanes)", file=sys.stderr,
    )

    # warm both paths: jit compiles + constant uploads land here, so the
    # timed loops compare steady-state dispatches
    planner.reset_cache()
    verdict = planner.verify_window(votes, powers, totals, use_device=True)
    cv.verify_commit_window(cv.pack_commit_window(votes, powers), max(totals))

    t0 = time.perf_counter()
    for _ in range(RAGGED_REPS):
        verdict = planner.verify_window(votes, powers, totals, use_device=True)
    ragged_s = (time.perf_counter() - t0) / RAGGED_REPS

    t0 = time.perf_counter()
    for _ in range(RAGGED_REPS):
        win = cv.pack_commit_window(votes, powers)
        cv.verify_commit_window(win, max(totals))
    grid_s = (time.perf_counter() - t0) / RAGGED_REPS

    grid_occ = present / grid_lanes
    dispatches = RAGGED_REPS + 1  # the warm dispatch compiled; the rest hit
    compiles = planner.compile_count()
    print(
        json.dumps(
            {
                "metric": f"planner_ragged_{len(sizes)}h",
                "value": round(1.0 / ragged_s, 1),
                "unit": "windows/s",
                "heights": len(sizes),
                "present_lanes": present,
                "lanes_dispatched": verdict.lanes_dispatched,
                "occupancy": round(verdict.occupancy, 4),
                "grid_occupancy": round(grid_occ, 4),
                "occupancy_vs_grid": round(verdict.occupancy / grid_occ, 2),
                "bucket_compiles": compiles,
                "bucket_hits": dispatches - compiles,
                "vs_unpacked": round(grid_s / ragged_s, 2),
            }
        ),
        flush=True,
    )
    write_snapshot(METRICS_OUT)


def main():
    if RAGGED:
        return run_ragged()

    from tendermint_tpu.crypto import batch as _batch
    from tendermint_tpu.crypto.batch import HostBatchVerifier
    from tendermint_tpu.blockchain.reactor import verify_block_window
    from tendermint_tpu.testutil.chain import build_chain, fresh_executor
    from tendermint_tpu.types import BlockID

    # chain generation + the serial baseline must use the host oracle — the
    # process default would route every per-block verify over the device
    _batch.set_batch_verifier(HostBatchVerifier())

    if N_BLOCKS < 2:
        raise SystemExit("need at least 2 blocks (commit N lives in block N+1)")

    t0 = time.perf_counter()
    fx = build_chain(n_vals=N_VALS, n_heights=N_BLOCKS, chain_id="bench-sync")
    gen_s = time.perf_counter() - t0
    blocks = [fx.block_store.load_block(h) for h in range(1, N_BLOCKS + 1)]
    print(
        f"# chain: {N_BLOCKS} blocks x {N_VALS} validators "
        f"(built in {gen_s:.1f}s)", file=sys.stderr,
    )

    # --- baseline: reference-shaped serial loop (verify every commit on host,
    # then apply) over a sample, extrapolated.  With --null-verify both sides
    # get the free verifier so the comparison isolates pipeline shape. ---
    base_verifier = NullVerifier() if NULL_VERIFY else HostBatchVerifier()
    st, block_exec = fresh_executor(fx.genesis)
    sample = min(BASELINE_SAMPLE_BLOCKS, N_BLOCKS - 1)
    t0 = time.perf_counter()
    for i in range(sample):
        block, next_block = blocks[i], blocks[i + 1]
        parts = block.make_part_set()
        block_id = BlockID(hash=block.hash(), parts_header=parts.header())
        st.validators.verify_commit(
            fx.chain_id, block_id, block.height, next_block.last_commit,
            verifier=base_verifier,
        )
        st = block_exec.apply_block(st, block_id, block, trusted_last_commit=True)
    baseline_s = (time.perf_counter() - t0) * (N_BLOCKS / sample)
    print(
        f"# baseline (serial {base_verifier.name} verify): "
        f"{N_BLOCKS / baseline_s:.0f} blocks/s", file=sys.stderr,
    )

    # --- ours: windowed batched verify + apply, on the verifier a node
    # would select from this environment (never a silent host substitute)
    if NULL_VERIFY:
        verifier, backend = NullVerifier(), "null"
    else:
        verifier, info = bench_verifier()
        backend = info["backend"]

    def run_pipeline(window_size: int) -> float:
        st, block_exec = fresh_executor(fx.genesis)
        t0 = time.perf_counter()
        applied = 0
        pos = 0
        while pos < N_BLOCKS - 1:
            window = blocks[pos : pos + window_size + 1]
            parts_list = []
            n_ok, err = verify_block_window(
                st, window, verifier=verifier, parts_out=parts_list
            )
            if err is not None or n_ok == 0:
                raise SystemExit(f"verification failed at {pos}: {err}")
            for i in range(n_ok):
                block = window[i]
                block_id = BlockID(
                    hash=block.hash(), parts_header=parts_list[i].header()
                )
                st = block_exec.apply_block(
                    st, block_id, block, trusted_last_commit=True
                )
                applied += 1
            pos += n_ok
        return applied / (time.perf_counter() - t0)

    # warm the device path (compile + upload) on the first window, from a
    # FRESH genesis state — the baseline loop's `st` has advanced past
    # genesis and would silently warm nothing under valset churn
    warm_st, _ = fresh_executor(fx.genesis)
    verify_block_window(
        warm_st, blocks[: min(WINDOW, len(blocks))], verifier=verifier
    )

    base_rate = N_BLOCKS / baseline_s
    tag = "_null" if NULL_VERIFY else ""
    if SWEEP:
        from tendermint_tpu.blockchain.reactor import auto_verify_window

        auto_w = auto_verify_window(N_VALS)
        for w in sorted(set(SWEEP_WINDOWS + [auto_w])):
            if w >= N_BLOCKS:
                continue
            rate = run_pipeline(w)
            print(
                json.dumps(
                    {
                        "metric": f"fastsync_replay{tag}_{N_BLOCKS}x{N_VALS}_w{w}",
                        "value": round(rate, 1),
                        "unit": "blocks/s",
                        "vs_baseline": round(rate / base_rate, 2),
                        "auto_window": auto_w,
                    }
                ),
                flush=True,
            )
        write_snapshot(METRICS_OUT)
        return

    ours_rate = run_pipeline(WINDOW)
    print(
        json.dumps(
            {
                "metric": f"fastsync_replay{tag}_{N_BLOCKS}x{N_VALS}",
                "value": round(ours_rate, 1),
                "unit": "blocks/s",
                "vs_baseline": round(ours_rate / base_rate, 2),
                "verifier": backend,
            }
        )
    )
    write_snapshot(METRICS_OUT)


if __name__ == "__main__":
    sys.exit(main())
