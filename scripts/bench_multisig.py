"""Multisig aggregate verification benchmark (BASELINE.md's "1k-validator
k-of-n multisig aggregate verify" config; ref the serial loop at
crypto/multisig/threshold_pubkey.go:41-55).

A validator set of N_VALS validators, each keyed with a k-of-n ed25519
threshold multisig, signs one canonical message each:

  * baseline — the reference's shape: per-validator verify_bytes, which
    loops each flagged signer's ed25519 verify serially on host
    (N_VALS × K verifies, one at a time);
  * ours — verify_generic: every aggregate FLATTENS into one ed25519 batch
    (N_VALS × K signatures in a single device dispatch).

Usage: python scripts/bench_multisig.py [n_vals] [k] [n_keys]
Env: TM_BATCH_VERIFIER=host to keep the 'ours' path off the device.
Prints ONE JSON line.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _bench_metrics import (  # noqa: E402
    bench_verifier,
    pop_metrics_out,
    write_snapshot,
)

METRICS_OUT = pop_metrics_out()
N_VALS = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
K = int(sys.argv[2]) if len(sys.argv) > 2 else 3
N_KEYS = int(sys.argv[3]) if len(sys.argv) > 3 else 5
BASELINE_SAMPLE = 200  # serial aggregates to time (extrapolated)


def main():
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.batch import verify_generic
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.crypto.multisig import (
        Multisignature,
        PubKeyMultisigThreshold,
    )

    rng = np.random.default_rng(7)
    pubkeys, msgs, sigs = [], [], []
    t0 = time.perf_counter()
    for v in range(N_VALS):
        privs = [ed.gen_privkey(rng.bytes(32)) for _ in range(N_KEYS)]
        subkeys = tuple(PubKeyEd25519(p[32:]) for p in privs)
        agg_key = PubKeyMultisigThreshold(K, subkeys)
        msg = b"multisig-bench|%08d|" % v + rng.bytes(89)
        ms = Multisignature.new(N_KEYS)
        for j in range(K):  # first K signers sign
            ms.add_signature_from_pubkey(
                ed.sign(privs[j], msg), subkeys[j], subkeys
            )
        pubkeys.append(agg_key)
        msgs.append(msg)
        sigs.append(ms.marshal())
    print(
        f"# {N_VALS} validators x {K}-of-{N_KEYS} multisig "
        f"(built in {time.perf_counter() - t0:.1f}s)", file=sys.stderr,
    )

    # --- baseline: serial host verify_bytes per aggregate ---
    sample = min(BASELINE_SAMPLE, N_VALS)
    t0 = time.perf_counter()
    for i in range(sample):
        assert pubkeys[i].verify_bytes(msgs[i], sigs[i])
    baseline_s = (time.perf_counter() - t0) * (N_VALS / sample)

    # --- ours: one flattened batch dispatch, on the verifier a node would
    # select from this environment (TM_BATCH_VERIFIER, then jax.devices()
    # under JAX_PLATFORMS); a missing chip fails instead of benching the host
    verifier, info = bench_verifier()
    ok = verify_generic(pubkeys, msgs, sigs, verifier=verifier)  # warm
    assert bool(np.all(ok)), "batched multisig verify rejected valid aggregates"
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        verify_generic(pubkeys, msgs, sigs, verifier=verifier)
        times.append(time.perf_counter() - t0)
    ours_s = float(np.median(times))

    print(
        json.dumps(
            {
                "metric": f"multisig_{K}of{N_KEYS}_aggregate_verify_{N_VALS}",
                "value": round(ours_s * 1e3, 3),
                "unit": "ms",
                "vs_baseline": round(baseline_s / ours_s, 2),
                "verifier": info["backend"],
            }
        )
    )
    write_snapshot(METRICS_OUT)


if __name__ == "__main__":
    sys.exit(main())
