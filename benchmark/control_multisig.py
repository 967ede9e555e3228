"""The controls of the multisig cells: the same run with a guarantee broken
underneath, which has to come out ``correct: false``.

    python3 benchmark/control_multisig.py --workload msig1k-stream \
        --seeds 1,2,3 --seconds 5 --control null|flip|first_of_group|none

``null`` (every device verdict true) and ``flip`` (lane 0 of every dispatch
inverted) are ``benchmark/control.py``'s.  ``first_of_group`` is the fault
this deployment invites, a device that takes a validator for one lane: it
answers true for every lane whose message equals the lane before it, so of a
validator's run of sub-signatures only the first is really decided.  Valid
commits pass; a bad sub-signature that is not its validator's first goes
through.  ``none`` is the sound program, for the other side of the table.
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

from benchmark.control import CONTROLS as _LANE_CONTROLS  # noqa: E402


def first_of_group(ok: np.ndarray, msgs) -> np.ndarray:
    out = ok.copy()
    for i in range(1, len(msgs)):
        if msgs[i] == msgs[i - 1]:
            out[i] = True
    return out


CONTROLS = {name: (lambda ok, msgs, f=f: f(ok)) for name, f in _LANE_CONTROLS.items()}
CONTROLS["first_of_group"] = first_of_group


class Standin:
    """A device verifier whose ed25519 verdicts pass through ``alter``,
    which sees the lanes' messages beside them."""

    def __init__(self, inner, alter):
        self.inner = inner
        self.alter = alter
        self.backend = getattr(inner, "backend", getattr(inner, "name", "device"))
        self.name = f"control-{self.backend}"
        self.verify_secp256k1 = inner.verify_secp256k1

    def verify_ed25519_raw(self, pubs, msgs, sigs):
        return self.alter(np.asarray(
            self.inner.verify_ed25519_raw(pubs, msgs, sigs), dtype=bool), msgs)

    def verify_ed25519(self, items):
        return self.alter(np.asarray(
            self.inner.verify_ed25519(items), dtype=bool),
            [it.msg for it in items])


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
    ap.add_argument("--control", choices=("none", *CONTROLS), required=True)
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
