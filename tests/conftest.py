"""Test harness config: tier-1 is CPU only.  A virtual 8-device CPU mesh
exercises the multi-chip sharding paths without hardware; the chip itself is
covered by `python chip_smoke.py` through the chip tool, never from here."""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The persistent XLA compile cache (the crypto-kernel parity tests compile
# graphs that take minutes on this CPU the first time) is placed by the
# package import — tendermint_tpu/__init__.py, the one place that sets it.
#
# Consensus/state tests verify tiny commits in their hot loops; the
# process-wide default verifier is pinned to the host oracle so none of them
# pays device selection.  Device-path tests build their own verifiers.
from tendermint_tpu.crypto import batch as _batch  # noqa: E402

_batch.set_batch_verifier(_batch.HostBatchVerifier())
