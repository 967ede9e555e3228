"""The one command of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It makes its inputs from ``--seed``,
warms up with the cell's own traffic, measures for ``--seconds``, checks
what the timed path produced against the benchmark's own oracle, and prints
one JSON object as the last line of its standard output.  Without a TPU (or
with fewer chips than the cell asks for) it exits non-zero and prints no
result; ``JAX_PLATFORMS=cpu`` set by hand is a rehearsal of the control flow:
the line then names the cpu and carries no device metric.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    t_process = _T_IMPORT - harness.process_age_s()
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    cache = harness.place_caches(ROOT)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    kind = devices[0].device_kind
    rehearsal = platform != "tpu" and os.environ.get("JAX_PLATFORMS") == "cpu"
    if platform != "tpu" and not rehearsal:
        print(f"benchmark: no TPU (jax.devices()[0] is {platform} {kind!r}); "
              "nothing is measured in its place", file=sys.stderr)
        return 3
    need = int(cell.workload["chips"])
    if not rehearsal and len(devices) < need:
        print(f"benchmark: cell {cell.name} asks for {need} chips, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 3
    if not rehearsal:
        bench.peaks(kind)  # an unknown device is an error, not a default
    log(f"device: platform={platform} kind={kind!r} count={len(devices)}"
        + ("  [REHEARSAL on the cpu: no number below is a device number]"
           if rehearsal else ""))
    log(f"compile cache: {os.environ['JAX_COMPILATION_CACHE_DIR']}")

    result = harness.run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        platform, kind, log, t_process,
        trace_dir=os.path.join(cache, "trace"))

    peak = 0
    for d in devices[:max(1, need)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    trace_data = result.pop("_trace", None)
    spans = result.pop("_spans", None)
    if trace_data is not None and trace_data["ops"]:
        from benchmark import tracefile

        device["busy_s"] = tracefile.busy_seconds(trace_data)
        device["window_s"] = tracefile.window_seconds(trace_data)
        result["breakdown"] = {
            "device_ops": tracefile.top_device_ops(trace_data),
            "idle_gaps": tracefile.idle_gaps_by_span(trace_data, spans),
        }
        log(f"trace: {len(trace_data['ops'])} device ops, clock drift "
            f"{trace_data['drift_ns']} ns, planes {trace_data['planes']}")
    result["device"] = device
    # what was compared, beside its limit: the result line's last key and
    # the last lines of standard error
    checks = result["checks"] = result.pop("checks")
    for c in checks:
        print(harness.Check(**c).line(), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    # daemon threads of a stopped reactor must not hold the exit
    os._exit(code)
