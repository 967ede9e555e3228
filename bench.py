"""Headline benchmark: 10,000-validator ed25519 commit verification through
the PRODUCTION path — ValidatorSet.verify_commit dispatching one batched
device call (TPUBatchVerifier, Pallas pipeline on a real chip) — plus the
fast-sync replay rate (windowed batch verify + apply).

Reference cost model: one serial host ed25519 verify per precommit
(`/root/reference/types/validator_set.go:273-298`) — measured here as the
baseline on this same machine (same `cryptography` C fast path the Go fork's
pure-Go code is *slower* than, so the comparison flatters the reference).

ONE PROCESS PER CHIP.  A chip belongs to the process that initialised JAX,
so this parent NEVER imports jax: every device stage runs in a child under
its own deadline, with the child's stderr passed through.  Nothing degrades:
without a TPU the run fails (non-zero exit) unless the CPU was asked for
explicitly — TM_BATCH_VERIFIER=host or JAX_PLATFORMS=cpu measure the host
verifier, and the JSON line names the platform either way.

Output: up to three JSON lines; the LAST is the most complete.
  {"metric": "ed25519_commit_verify_10k_validators", "value": <wall ms>,
   "unit": "ms", "vs_baseline": <baseline/ours>, "backend": "pallas|host",
   "platform": "tpu|cpu", "device_kind": "...",
   "fastsync_blocks_per_s": N, "fastsync_vs_baseline": N,
   ["device_p50_ms": N]}

device_p50_ms times the fused pipeline with all inputs device-resident; the
headline wall number includes host packing and the host->device copies.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

# overridable for the BASELINE 1k-validator config: bench.py [n_validators]
# (the driver's no-arg invocation stays the headline 10k config)
N_VALIDATORS = next(
    (int(a) for a in sys.argv[1:] if a.isdigit()), 10_000
)
BASELINE_SAMPLE = min(2_000, N_VALIDATORS)  # serial verifies (extrapolated)
CHAIN_ID = "bench-chain"
HEIGHT = 500

DEVICE_WALL_TIMEOUT_S = 420  # child: build + compile + upload + 6 verifies
DEVICE_P50_TIMEOUT_S = 240  # additional budget for the device-resident stage
FASTSYNC_TIMEOUT_S = 300
MEMPOOL_TIMEOUT_S = 120
MEMPOOL_TXS = 20_000
MEMPOOL_BATCH = 64

FASTSYNC_BLOCKS = 512
FASTSYNC_VALS = 64
FASTSYNC_WINDOW = 512

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)


def _build_commit():
    from tendermint_tpu.testutil.chain import build_commit

    return build_commit(N_VALIDATORS, seed=42, chain_id=CHAIN_ID, height=HEIGHT)


def _wall_p50(valset, block_id, commit, verifier, reps=5):
    valset.verify_commit(CHAIN_ID, block_id, HEIGHT, commit, verifier=verifier)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        valset.verify_commit(CHAIN_ID, block_id, HEIGHT, commit, verifier=verifier)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


# --------------------------------------------------------------------------
# device child: the ONLY code here that touches jax.  Emits one JSON line per
# completed stage so the parent can harvest the wall number even if a later
# stage wedges (the parent kills this child at its deadline).
# --------------------------------------------------------------------------


def _device_child():
    import jax

    from tendermint_tpu.crypto.batch import describe_verifier, get_batch_verifier

    # the process default, chosen the way a node chooses it
    verifier = get_batch_verifier()
    print(f"# {describe_verifier(verifier)}", file=sys.stderr, flush=True)
    if getattr(verifier, "backend", None) != "pallas":
        print("bench: device stage needs the pallas backend on a TPU; got "
              f"{describe_verifier(verifier)}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    valset, block_id, commit = _build_commit()
    ours_s = _wall_p50(valset, block_id, commit, verifier)
    print(json.dumps({"stage": "wall", "wall_ms": ours_s * 1e3,
                      "platform": dev.platform,
                      "device_kind": dev.device_kind}), flush=True)

    p50_ms = _device_p50(valset, commit)
    print(json.dumps({"stage": "device", "device_p50_ms": p50_ms}), flush=True)
    return 0


def _device_p50(valset, commit, iters: int = 10):
    """Median ms of the packed verify dispatch with ALL inputs already on
    device (valset limbs, signatures, message words) — times the fused
    pipeline itself, without the host packing and copies of the wall number."""
    import jax

    from tendermint_tpu.ops import ed25519_pallas as ep

    pubs = [v.pub_key.bytes() for v in valset.validators]
    msgs = [pc.sign_bytes(CHAIN_ID) for pc in commit.precommits]
    sigs = [pc.signature for pc in commit.precommits]
    pubs_a = np.frombuffer(b"".join(pubs), np.uint8).reshape(-1, 32)
    sigs_a = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    ln = len(msgs[0])
    b = ep._bucket(pubs_a.shape[0])
    neg_ax, ay, _valid = ep._decompress_valset(pubs_a)
    sig_words = np.ascontiguousarray(sigs_a).view("<u4").astype(np.uint32)
    tmpl, vrows, vwords = ep.pack_variable_words(pubs_a, msgs, sigs_a, ln, b)
    put = jax.numpy.asarray
    negax_d, ay_d, pubw_d = ep._upload_valset(pubs_a, neg_ax, ay, b)
    sig_d = put(ep._pad_rows(sig_words, b))
    tmpl_d, vrows_d, vwords_d = put(tmpl), put(vrows), put(vwords)
    # warm (jit cache shared with the production dispatch above)
    ep._device_verify_packed(
        negax_d, ay_d, pubw_d, sig_d, tmpl_d, vrows_d, vwords_d
    ).block_until_ready()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        ep._device_verify_packed(
            negax_d, ay_d, pubw_d, sig_d, tmpl_d, vrows_d, vwords_d
        ).block_until_ready()
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)) * 1e3


# --------------------------------------------------------------------------
# parent orchestration
# --------------------------------------------------------------------------


def _read_stage_lines(proc, deadlines):
    """Read JSON stage lines from a child, each stage under its own deadline
    (seconds from now).  Returns {stage: payload}.  Kills the child on a
    missed deadline — already-harvested stages survive."""
    import threading
    from queue import Empty, Queue

    q: Queue = Queue()

    def _pump():
        for line in proc.stdout:
            q.put(line)
        q.put(None)

    threading.Thread(target=_pump, daemon=True).start()
    out = {}
    for stage, budget in deadlines:
        deadline = time.monotonic() + budget
        while stage not in out:
            try:
                line = q.get(timeout=max(0.0, deadline - time.monotonic()))
            except Empty:
                print(f"# stage {stage}: deadline exceeded", file=sys.stderr)
                proc.kill()
                return out
            if line is None:  # child exited
                return out
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                payload = json.loads(line)
            except ValueError:
                continue
            out[payload.pop("stage", "?")] = payload
    return out


def _run_device_stages():
    """Spawn the device child; harvest wall + device_p50 under deadlines.
    Returns (stages, child exit code)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--stage", "device",
         str(N_VALIDATORS)],
        stdout=subprocess.PIPE,
        text=True,
        cwd=_REPO,
    )
    try:
        stages = _read_stage_lines(
            proc,
            [("wall", DEVICE_WALL_TIMEOUT_S), ("device", DEVICE_P50_TIMEOUT_S)],
        )
        # both lines are in (or the child was killed at a deadline): let
        # it leave on its own — releasing the chip takes a few seconds —
        # so its exit code is its own
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        print("# device child did not exit within 60 s", file=sys.stderr)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    return stages, proc.returncode


def _last_json_line(stdout: str, key: str = ""):
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            if not key or key in parsed:
                return parsed
    return None


def _run_fastsync():
    """Fast-sync replay rate via scripts/bench_fastsync.py in a child under a
    deadline; the child selects its verifier from the same environment."""
    try:
        res = subprocess.run(
            [
                sys.executable,
                os.path.join(_REPO, "scripts", "bench_fastsync.py"),
                str(FASTSYNC_BLOCKS),
                str(FASTSYNC_VALS),
                str(FASTSYNC_WINDOW),
            ],
            timeout=FASTSYNC_TIMEOUT_S,
            stdout=subprocess.PIPE,
            text=True,
            cwd=_REPO,
        )
    except subprocess.TimeoutExpired:
        print("# fastsync stage: deadline exceeded", file=sys.stderr)
        return None
    parsed = _last_json_line(res.stdout) if res.returncode == 0 else None
    if parsed is None:
        print(f"# fastsync stage failed rc={res.returncode}", file=sys.stderr)
    return parsed


def _run_mempool():
    """Mempool ingestion rate via scripts/bench_mempool.py — pure host
    (CPython) work, so it runs the same with or without the chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # never contends for the chip
    try:
        res = subprocess.run(
            [
                sys.executable,
                os.path.join(_REPO, "scripts", "bench_mempool.py"),
                str(MEMPOOL_TXS),
                str(MEMPOOL_BATCH),
            ],
            timeout=MEMPOOL_TIMEOUT_S,
            stdout=subprocess.PIPE,
            text=True,
            env=env,
            cwd=_REPO,
        )
    except subprocess.TimeoutExpired:
        print("# mempool stage: deadline exceeded", file=sys.stderr)
        return None
    parsed = (
        _last_json_line(res.stdout, "mempool_checktx_per_s")
        if res.returncode == 0 else None
    )
    if parsed is None:
        print(f"# mempool stage failed rc={res.returncode}", file=sys.stderr)
    return parsed


def main():
    from scripts._bench_metrics import cpu_requested
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.batch import HostBatchVerifier

    valset, block_id, commit = _build_commit()

    # --- baseline: the reference's serial-verify loop shape ---
    msgs = [pc.sign_bytes(CHAIN_ID) for pc in commit.precommits]
    pubs = [v.pub_key.bytes() for v in valset.validators]
    sigs = [pc.signature for pc in commit.precommits]
    t0 = time.perf_counter()
    for i in range(BASELINE_SAMPLE):
        ed.verify(pubs[i], msgs[i], sigs[i])
    baseline_s = (time.perf_counter() - t0) * (N_VALIDATORS / BASELINE_SAMPLE)

    # --- production wall: the device child, or the host verifier when the
    # CPU was asked for.  A missing chip is a failure, not a host number. ---
    device_p50_ms = None
    if cpu_requested():
        backend, platform, device_kind = "host", "cpu", "cpu"
        ours_s = _wall_p50(valset, block_id, commit, HostBatchVerifier())
    else:
        stages, rc = _run_device_stages()
        if rc != 0 or "wall" not in stages or "device" not in stages:
            print(f"bench: device stage failed (rc={rc}, stages="
                  f"{sorted(stages)}); set TM_BATCH_VERIFIER=host or "
                  "JAX_PLATFORMS=cpu to measure the host verifier instead",
                  file=sys.stderr)
            return 1
        backend = "pallas"
        ours_s = stages["wall"]["wall_ms"] / 1e3
        platform = stages["wall"]["platform"]
        device_kind = stages["wall"]["device_kind"]
        device_p50_ms = stages["device"]["device_p50_ms"]

    n_label = (
        f"{N_VALIDATORS // 1000}k"
        if N_VALIDATORS >= 1000 and N_VALIDATORS % 1000 == 0
        else str(N_VALIDATORS)
    )
    result = {
        "metric": f"ed25519_commit_verify_{n_label}_validators",
        "value": round(ours_s * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(baseline_s / ours_s, 2),
        "backend": backend,
        "platform": platform,
        "device_kind": device_kind,
    }
    if device_p50_ms is not None:
        result["device_p50_ms"] = round(device_p50_ms, 3)
    print(json.dumps(result), flush=True)

    # fastsync rides only the headline (10k) invocation: its config is
    # fixed at 512x64, so alternate-N runs would just repeat the number
    rc = 0
    if N_VALIDATORS == 10_000:
        fastsync = _run_fastsync()
        if fastsync is None:
            rc = 1
        else:
            result["fastsync_blocks_per_s"] = fastsync.get("value")
            result["fastsync_vs_baseline"] = fastsync.get("vs_baseline")
            result["fastsync_verifier"] = fastsync.get("verifier")
            print(json.dumps(result), flush=True)
        mempool = _run_mempool()
        if mempool is None:
            rc = 1
        else:
            result["mempool_checktx_per_s"] = mempool.get(
                "mempool_checktx_per_s"
            )
            result["mempool_checktx_vs_serial"] = mempool.get("vs_serial")
            print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    if "--stage" in sys.argv and "device" in sys.argv:
        sys.exit(_device_child())
    sys.exit(main())
