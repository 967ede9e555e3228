"""The controls of the secp256k1 cells: the same run with a guarantee broken
underneath, which has to come out ``correct: false``.  ``benchmark/control.py``
passes ``verify_secp256k1`` through unaltered, so it cannot break a verdict
of this deployment; this file's stand-in alters that call and passes the
ed25519 ones through.  Same command line, same controls:

    python3 benchmark/control_secp256k1.py --workload secp256-stream \
        --seeds 1,2,3 --seconds 5 --control null|flip|none

``null``: every device verdict comes back true (the device still runs).
``flip``: lane 0 of every dispatch comes back inverted.  ``none``: the sound
program, for the other side of the table.
"""

from __future__ import annotations

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.control import CONTROLS  # noqa: E402


class Standin:
    """A device verifier whose secp256k1 verdicts pass through ``alter``."""

    def __init__(self, inner, alter):
        self.inner = inner
        self.alter = alter
        self.backend = getattr(inner, "backend", getattr(inner, "name", "device"))
        self.name = f"control-{self.backend}"
        self.verify_ed25519 = inner.verify_ed25519
        self.verify_ed25519_raw = inner.verify_ed25519_raw

    def verify_secp256k1(self, items):
        return self.alter(np.asarray(
            self.inner.verify_secp256k1(items), dtype=bool))


def make_device(platform: str, control: str):
    """The device verifier a run would use, behind the control."""
    if control == "none":
        return None
    from tendermint_tpu.crypto import batch

    inner = (batch.TPUBatchVerifier(backend="pallas") if platform == "tpu"
             else batch.HostBatchVerifier())
    return Standin(inner, CONTROLS[control])


def main(argv=None) -> int:
    """``control.main`` with this file's stand-in: it builds its device
    through its module's ``make_device``, looked up when it runs."""
    from benchmark import control

    control.make_device = make_device
    return control.main(argv)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
