"""--metrics-out support shared by the bench_* scripts.

`--metrics-out PATH` (or `--metrics-out=PATH`) snapshots the process-wide
verify metric families (`Registry.expose_text()`, Prometheus text format
v0.0.4) to PATH next to the JSON ledger line — per-stage breakdowns (batch
sizes, per-backend dispatch/compile latency, fallback counts) to go with
the end-to-end number.
"""

import os
import sys
from typing import Optional


def cpu_requested() -> bool:
    """The two explicit CPU switches.  A bench never measures the host or
    XLA-on-CPU in place of a missing chip: the CPU has to be asked for."""
    return (
        os.environ.get("TM_BATCH_VERIFIER", "").lower() == "host"
        or os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    )


def bench_verifier():
    """(verifier, verifier_info) selected the way a node selects it — from
    TM_BATCH_VERIFIER, then jax.devices() under JAX_PLATFORMS.  Exits
    non-zero when that lands on the host because no TPU was found and the
    CPU was not asked for."""
    from tendermint_tpu.crypto import batch

    verifier = batch.reprobe(force=True)
    info = batch.verifier_info()
    print(f"# verifier: {info['description']}", file=sys.stderr, flush=True)
    if info["latched_reason"] is not None and not cpu_requested():
        raise SystemExit(
            f"no device verifier ({info['description']}); set "
            "TM_BATCH_VERIFIER=host or JAX_PLATFORMS=cpu to bench the CPU"
        )
    return verifier, info


def bench_platform() -> str:
    """jax.devices()[0].platform; exits non-zero when it is not a TPU and
    the CPU was not asked for."""
    import jax

    dev = jax.devices()[0]
    print(f"# device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}",
          file=sys.stderr, flush=True)
    if dev.platform != "tpu" and not cpu_requested():
        raise SystemExit(
            f"no TPU (jax.devices()[0] is {dev.platform}); set "
            "JAX_PLATFORMS=cpu to bench the XLA kernel on the CPU"
        )
    return dev.platform


def pop_metrics_out(argv=None) -> Optional[str]:
    """Remove --metrics-out PATH (or --metrics-out=PATH) from argv and
    return PATH, so the scripts' positional arg parsing stays untouched."""
    argv = sys.argv if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--metrics-out":
            if i + 1 >= len(argv):
                raise SystemExit("--metrics-out needs a path")
            path = argv[i + 1]
            del argv[i : i + 2]
            return path
        if a.startswith("--metrics-out="):
            del argv[i]
            return a.split("=", 1)[1]
    return None


def write_snapshot(path: Optional[str]) -> None:
    if not path:
        return
    from tendermint_tpu.libs.metrics import get_verify_metrics

    with open(path, "w") as f:
        f.write(get_verify_metrics().registry.expose_text())
    print(f"# metrics snapshot -> {path}", file=sys.stderr)
