"""The controls: the same run with a guarantee broken underneath, which has
to come out ``correct: false``.  Not part of a benchmark run; the tests run
it at a small size on the CPU, and the builder ran it on the chip at each
cell's own size (PERF.md has the readings):

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \
        --seconds 5 --control null|flip|none

One process reads all its seeds, since set-up is long.  ``null`` is the
repo's all-true ``NullVerifier`` shape put in the device verifier's place
(the device still runs, its verdicts are replaced): the configuration's
"accept/reject equals the oracle on every lane" is broken.  ``flip`` alters
one answer where it is produced: lane 0 of every dispatch comes back
inverted.  ``none`` is the sound program, for the other side of the table.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Standin:
    """A device verifier whose verdicts pass through ``alter``."""

    def __init__(self, inner, alter):
        self.inner = inner
        self.alter = alter
        self.backend = getattr(inner, "backend", getattr(inner, "name", "device"))
        self.name = f"control-{self.backend}"

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        return self.alter(np.asarray(
            self.inner.verify_ed25519_raw(pubs, msgs, sigs), dtype=bool))

    def verify_ed25519(self, items):
        return self.alter(np.asarray(
            self.inner.verify_ed25519(items), dtype=bool))

    def verify_secp256k1(self, items):
        return self.inner.verify_secp256k1(items)


def all_true(ok: np.ndarray) -> np.ndarray:
    return np.ones_like(ok)


def flip_lane0(ok: np.ndarray) -> np.ndarray:
    out = ok.copy()
    if out.size:
        out[0] = not out[0]
    return out


CONTROLS = {"null": all_true, "flip": flip_lane0}


def make_device(platform: str, control: str):
    """The device verifier a run would use, behind the control."""
    if control == "none":
        return None
    from tendermint_tpu.crypto import batch

    inner = (batch.TPUBatchVerifier(backend="pallas") if platform == "tpu"
             else batch.HostBatchVerifier())
    return Standin(inner, CONTROLS[control])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", choices=("none", "null", "flip"), required=True)
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
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(
            bench, args.workload, seed, args.seconds, False, dev.platform,
            dev.device_kind, lambda m: print(m, flush=True), t_process,
            device=make_device(dev.platform, args.control))
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
