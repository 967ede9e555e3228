"""Test harness config: tier-1 is CPU only.  A virtual 8-device CPU mesh
exercises the multi-chip sharding paths without hardware; the chip itself is
covered by `python chip_smoke.py` through the chip tool, never from here."""

import os
import sys

import pytest

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


@pytest.fixture
def tracing():
    """libs/trace's module-level tracer (the one the program's call sites
    use), on for one test and left off and empty after it."""
    from tendermint_tpu.libs import trace

    trace.reset(4096)
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.reset(trace.DEFAULT_CAPACITY)


@pytest.fixture
def verify_counters():
    """Reads a verify metric family's series as /metrics prints them:
    ``verify_counters(family, {label: value})`` -> their sum now."""
    from tendermint_tpu.libs.metrics import get_verify_metrics

    def read(family, labels=None):
        want = [f'{k}="{v}"' for k, v in (labels or {}).items()]
        total = 0.0
        for line in get_verify_metrics().registry.expose_text().splitlines():
            series, _, value = line.rpartition(" ")
            name, _, rest = series.partition("{")
            if name == family and all(w in rest for w in want):
                total += float(value)
        return total

    return read
