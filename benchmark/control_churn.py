"""The control of the churn cells: the same run over a device that keeps
verifying against the first validator set it was given, which has to come
out ``correct: false``.

    python3 benchmark/control_churn.py --workload sync64-churn \
        --seeds 1,2,3 --seconds 5 --control stale|none

``stale`` is the fault that a validator set held on the device invites: the
keys of a chain's first set stay resident and every later lane is verified
against the key that sat in its place then, whatever key the caller sends
(the device still runs, on the resident keys).  Up to the first change the
answers are right; from it on, the honest signatures of whoever moved place
fail and a signature of the validator that left passes in its old place.
The configuration's "every commit is verified against the validator set of
its own height" is broken.  A chain that shares under half its first row of
keys with the resident set is a fresh node's: its first set is taken up in
turn.  ``none`` is the sound program, for the other side of the table.
``benchmark/control.py``'s ``null`` and ``flip`` run on this cell as on the
others.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class StaleValset:
    """A device verifier that never lets go of a chain's first set."""

    def __init__(self, inner, set_size: int):
        self.inner = inner
        self.n = set_size
        self.resident = None  # the first set's keys, in its order
        self.backend = getattr(inner, "backend", getattr(inner, "name", "device"))
        self.name = f"control-{self.backend}"
        self.verify_secp256k1 = inner.verify_secp256k1

    def _stale(self, pubs) -> list:
        pubs = list(pubs)
        first = pubs[: self.n]
        if len(first) < self.n:
            return pubs
        if self.resident is None or 2 * sum(
                a == b for a, b in zip(first, self.resident)) < self.n:
            self.resident = first
        return [self.resident[i % self.n] for i in range(len(pubs))]

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        return self.inner.verify_ed25519_raw(self._stale(pubs), msgs, sigs)

    def verify_ed25519(self, items):
        return self.verify_ed25519_raw(
            [it.pubkey for it in items], [it.msg for it in items],
            [it.sig for it in items])


def make_device(platform: str, control: str, set_size: int):
    """The device verifier a run would use, behind the control."""
    if control == "none":
        return None
    from tendermint_tpu.crypto import batch

    inner = (batch.TPUBatchVerifier(backend="pallas") if platform == "tpu"
             else batch.HostBatchVerifier())
    return StaleValset(inner, set_size)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", choices=("none", "stale"), required=True)
    args = ap.parse_args(argv)

    from benchmark import harness

    t_process = _T_IMPORT - harness.process_age_s()
    bench = harness.Bench(ROOT)
    harness.place_caches(ROOT)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    set_size = int(bench.cell(args.workload).config["validators"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(
            bench, args.workload, seed, args.seconds, False, dev.platform,
            dev.device_kind, lambda m: print(m, flush=True), t_process,
            device=make_device(dev.platform, args.control, set_size))
        rows.append({"seed": seed, "control": args.control,
                     "correct": result["correct"], "failed": result["failed"],
                     "failed_checks": [c["name"] for c in result["checks"]
                                       if not c["ok"]]})
        print("CONTROL " + json.dumps(rows[-1]), flush=True)
    want = args.control == "none"
    print("CONTROL_SUMMARY " + json.dumps({
        "workload": args.workload, "control": args.control,
        "platform": dev.platform, "rows": rows,
        "as_expected": all(r["correct"] == want for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
