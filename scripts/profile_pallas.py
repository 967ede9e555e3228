"""Per-stage timing of the Pallas ed25519 verify path on the real chip.

Emits JSON lines:
  pallas_e2e_10k       — full verify_batch wall (host packing + dispatch)
  pallas_prologue_10k  — SHA-512 + mod-L + digit extraction kernel
  pallas_ladder_10k    — full 64-window Straus ladder kernel
  pallas_ladder_w{n}   — reduced-window ladder runs; with the full run these
                         separate the per-window slope from the fixed cost
                         (per-signature table build + fe_inv + canonical
                         compare), attributing the ladder milliseconds
  pallas_ladder_window_slope / pallas_ladder_fixed
                       — the w1/w16 least-cost split itself: slope is the
                         marginal cost of one Straus window (where the limb
                         multiplier lives), fixed is table build + fe_inv +
                         canonical compare
  pallas_host_packing  — host-side packing with a warm decompression cache
  ed25519_sigs_per_s   — headline throughput (gated by scripts/bench_check.py)

`--ed25519-path msm` ADDITIONALLY measures the one-MSM-per-window RLC
path (ops/ed25519_msm) against the per-row ladder at n=512 on the XLA
kernels:
  xla_ladder_512 / xla_msm_512      — wall ms per batch (median of 3)
  ed25519_ladder512_sigs_per_s      — ladder throughput at the MSM shape
  ed25519_msm_sigs_per_s            — MSM throughput (gated by bench_check)
  ed25519_msm_speedup               — msm/ladder ratio (PERF.md floor: 2x)

Without a TPU the Pallas stage split is unmeasurable (interpret mode is
minutes per call).  JAX_PLATFORMS=cpu asks for the XLA kernel on the CPU
instead — slower, but it keeps ``make pallas-bench`` producing an
``ed25519_sigs_per_s`` round end-to-end, labelled ``"backend": "xla"`` and
``"platform": "cpu"``.  With neither a TPU nor that switch the script exits
non-zero: it never measures one thing under the name of another.

`--round-dir DIR` appends a BENCH_rNN.json round (same schema as the
committed driver ledger) under DIR for scripts/bench_check.py to gate;
`--metrics-out PATH` snapshots the verify metric families.  PERF.md holds
the matching op-count model.
"""
import argparse
import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N = 10_000
N_CPU = 64  # XLA on the CPU: jit compile alone is minutes at 10k
MSG_LEN = 110

_emitted = {}


def _median_ms(fn, reps=5):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def _make_corpus(n):
    from tendermint_tpu.crypto import ed25519 as ed

    rng = np.random.default_rng(42)
    seeds = rng.bytes(32 * n)
    pubs = np.zeros((n, 32), np.uint8)
    sigs = np.zeros((n, 64), np.uint8)
    msgs = []
    for i in range(n):
        priv = ed.gen_privkey(seeds[32 * i : 32 * (i + 1)])
        msg = bytes([i & 0xFF, (i >> 8) & 0xFF]) * (MSG_LEN // 2)
        pubs[i] = np.frombuffer(priv[32:], np.uint8)
        sigs[i] = np.frombuffer(ed.sign(priv, msg), np.uint8)
        msgs.append(msg)
    return pubs, msgs, sigs


def _profile_pallas(emit):
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519_pallas as pk

    pubs, msgs, sigs = _make_corpus(N)
    print("# devices:", jax.devices(), file=sys.stderr)

    ok = pk.verify_batch(pubs, msgs, sigs)  # warm
    assert ok.all()
    e2e_ms = _median_ms(
        lambda: pk.verify_batch(pubs, msgs, sigs)
    )
    emit("pallas_e2e_10k", e2e_ms)

    # stage split: host packing vs prologue vs ladder
    neg_ax, ay, _valid = pk._decompress_valset(pubs)
    n = N
    b = pk._bucket(n)
    total = 64 + MSG_LEN
    nblocks = (total + 1 + 16 + 127) // 128
    padded = np.zeros((b, nblocks * 128), dtype=np.uint8)
    padded[:n, :32] = sigs[:, :32]
    padded[:n, 32:64] = pubs
    m = np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(n, MSG_LEN)
    padded[:n, 64:total] = m
    padded[:, total] = 0x80
    padded[:, -16:] = np.frombuffer((total * 8).to_bytes(16, "big"), np.uint8)
    msg_words = padded.reshape(b, -1, 4)[:, :, ::-1].reshape(b, -1)
    msg_words = np.ascontiguousarray(msg_words).view("<u4").astype(np.uint32)
    sig_words = np.ascontiguousarray(sigs).view("<u4").astype(np.uint32)

    negax_d = jnp.asarray(pk._pad_rows(neg_ax, b)).T
    ay_d = jnp.asarray(pk._pad_rows(ay, b)).T
    sigw_d = jnp.asarray(pk._pad_rows(sig_words, b)).T
    msgw_d = jnp.asarray(msg_words).T

    prologue = jax.jit(lambda mw, sw: pk._prologue_call(mw, sw))
    ladder = jax.jit(
        lambda nx, ayy, digs, digh, rl, rs: pk._ladder_call(
            nx, ayy, digs, digh, rl, rs
        )
    )

    digs, digh, rlimb, rsign = jax.block_until_ready(prologue(msgw_d, sigw_d))
    jax.block_until_ready(ladder(negax_d, ay_d, digs, digh, rlimb, rsign))

    emit(
        "pallas_prologue_10k",
        _median_ms(lambda: jax.block_until_ready(prologue(msgw_d, sigw_d))),
    )
    emit(
        "pallas_ladder_10k",
        _median_ms(
            lambda: jax.block_until_ready(
                ladder(negax_d, ay_d, digs, digh, rlimb, rsign)
            )
        ),
    )

    # fixed-vs-slope attribution: the ladder kernel takes its window count
    # from the digit rows, so short digit arrays time the same kernel with
    # fewer windows.  cost(nwin) ≈ fixed (table build + fe_inv + canonical
    # compare) + slope·nwin; see PERF.md for the matching op counts.
    w_ms = {}
    for nwin in (1, 16):
        digs_n = digs[:nwin]
        digh_n = digh[:nwin]
        lad_n = jax.jit(
            lambda nx, ayy, dg, dh, rl, rs: pk._ladder_call(
                nx, ayy, dg, dh, rl, rs
            )
        )
        jax.block_until_ready(
            lad_n(negax_d, ay_d, digs_n, digh_n, rlimb, rsign)
        )
        w_ms[nwin] = _median_ms(
            lambda: jax.block_until_ready(
                lad_n(negax_d, ay_d, digs_n, digh_n, rlimb, rsign)
            )
        )
        emit(f"pallas_ladder_w{nwin}", w_ms[nwin])

    # slope isolates the windowed point ops (where fe_mul lives), fixed
    # the per-signature table build and epilogue
    slope = (w_ms[16] - w_ms[1]) / 15.0
    emit("pallas_ladder_window_slope", slope)
    emit("pallas_ladder_fixed", max(w_ms[1] - slope, 0.0))

    def _pack():
        pk._decompress_valset(pubs)
        padded2 = np.zeros((b, nblocks * 128), dtype=np.uint8)
        padded2[:n, :32] = sigs[:, :32]
        padded2[:n, 32:64] = pubs
        padded2[:n, 64:total] = m
        mw = padded2.reshape(b, -1, 4)[:, :, ::-1].reshape(b, -1)
        np.ascontiguousarray(mw).view("<u4").astype(np.uint32)

    emit("pallas_host_packing", _median_ms(_pack))
    return N, e2e_ms, "pallas"


def _profile_xla(emit):
    from tendermint_tpu.ops import ed25519_verify as xk

    pubs, msgs, sigs = _make_corpus(N_CPU)
    ok = xk.verify_batch(pubs, msgs, sigs)  # compile
    assert ok.all()
    e2e_ms = _median_ms(
        lambda: xk.verify_batch(pubs, msgs, sigs),
        reps=3,
    )
    emit(f"xla_e2e_{N_CPU}", e2e_ms)
    return N_CPU, e2e_ms, "xla"


N_MSM = 512


def _profile_msm(emit):
    """MSM-vs-ladder comparison at N_MSM rows on the XLA kernels.

    Both paths run on whatever platform jax resolved (the committed
    rounds use JAX_PLATFORMS=cpu) with the SAME corpus, so the ratio is
    the Pippenger amortization alone.  The RLC seed is pinned to the
    deterministic corpus seed (rlc_seed) — the digit schedule, and with
    it the jit cache key, is identical across reps."""
    from tendermint_tpu.ops import ed25519_verify as xk

    pubs, msgs, sigs = _make_corpus(N_MSM)
    ok = xk.verify_batch(pubs, msgs, sigs)  # compile
    assert ok.all()
    lad_ms = _median_ms(
        lambda: xk.verify_batch(pubs, msgs, sigs),
        reps=3,
    )
    emit(f"xla_ladder_{N_MSM}", lad_ms)
    seed = xk.rlc_seed(pubs, sigs)
    ok = xk.rlc_verify_batch(
        pubs, msgs, sigs, seed=seed
    )  # compile
    assert ok.all()
    msm_ms = _median_ms(
        lambda: xk.rlc_verify_batch(
            pubs, msgs, sigs, seed=seed
        ),
        reps=3,
    )
    emit(f"xla_msm_{N_MSM}", msm_ms)
    return lad_ms, msm_ms


def _write_round(round_dir, parsed, rc):
    os.makedirs(round_dir, exist_ok=True)
    nums = [
        int(m.group(1))
        for p in glob.glob(os.path.join(round_dir, "BENCH_r*.json"))
        if (m := re.search(r"BENCH_r(\d+)\.json$", os.path.basename(p)))
    ]
    n = max(nums, default=0) + 1
    path = os.path.join(round_dir, f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump(
            {
                "n": n,
                "cmd": " ".join(sys.argv),
                "rc": rc,
                "tail": "",
                "parsed": parsed,
            },
            f,
            indent=1,
        )
        f.write("\n")
    print(f"# bench round -> {path}", file=sys.stderr)


def main(argv=None):
    from scripts._bench_metrics import (
        bench_platform,
        pop_metrics_out,
        write_snapshot,
    )

    metrics_out = pop_metrics_out(argv)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ed25519-path", default="ladder",
                   choices=("ladder", "msm"),
                   help="msm: also bench the one-MSM-per-window RLC path "
                        "vs the ladder at n=512 ([verify] ed25519_path)")
    p.add_argument("--round-dir", default="",
                   help="append a BENCH_rNN.json round under DIR "
                        "(for scripts/bench_check.py --dir DIR)")
    args = p.parse_args(argv)

    def emit(metric, ms):
        _emitted[metric] = round(ms, 3)
        print(json.dumps({"metric": metric, "value": round(ms, 3),
                          "unit": "ms"}), flush=True)

    platform = bench_platform()
    if platform == "tpu":
        n, e2e_ms, kind = _profile_pallas(emit)
    else:
        n, e2e_ms, kind = _profile_xla(emit)

    sigs_per_s = round(n / (e2e_ms / 1e3), 1)
    _emitted["ed25519_sigs_per_s"] = sigs_per_s
    # headline line: carries the metric under its own key too so the
    # driver's parsed-dict (last JSON line) gates by name in bench_check
    print(json.dumps({
        "metric": "ed25519_sigs_per_s",
        "value": sigs_per_s,
        "unit": "sigs/s",
        "backend": kind,
        "platform": platform,
        "ed25519_sigs_per_s": sigs_per_s,
    }), flush=True)

    if args.ed25519_path == "msm":
        lad_ms, msm_ms = _profile_msm(emit)
        lad_sps = round(N_MSM / (lad_ms / 1e3), 1)
        msm_sps = round(N_MSM / (msm_ms / 1e3), 1)
        speedup = round(lad_ms / msm_ms, 2) if msm_ms else 0.0
        for name, value, unit in (
            (f"ed25519_ladder{N_MSM}_sigs_per_s", lad_sps, "sigs/s"),
            ("ed25519_msm_sigs_per_s", msm_sps, "sigs/s"),
            ("ed25519_msm_speedup", speedup, "x"),
        ):
            _emitted[name] = value
            print(json.dumps({"metric": name, "value": value, "unit": unit,
                              name: value}), flush=True)

    try:
        from tendermint_tpu.libs.metrics import get_verify_metrics

        get_verify_metrics().record_dispatch(
            kind, "ed25519", n, e2e_ms / 1e3,
            carry_mode="lazy",  # the kernels' default schedule
            ed25519_path="ladder",
        )
        if args.ed25519_path == "msm":
            get_verify_metrics().record_dispatch(
                "xla", "ed25519", N_MSM, msm_ms / 1e3,
                carry_mode="lazy", ed25519_path="msm",
            )
    except Exception:
        pass
    if metrics_out and os.path.dirname(metrics_out):
        os.makedirs(os.path.dirname(metrics_out), exist_ok=True)
    write_snapshot(metrics_out)
    if args.round_dir:
        _write_round(args.round_dir, dict(_emitted), 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
