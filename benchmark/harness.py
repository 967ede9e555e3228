"""What every cell shares: reading ``BENCHMARK.json`` and the data files it
names, the arithmetic of percentiles and rates, spans and counter
snapshots, and the orchestration of one run (set-up, frozen heap, warm-up,
window, checks, reduction, the result line).

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name.  Each is a file found by the name ``BENCHMARK.json`` gives:

  benchmark/configs/<config>.json    the deployment as it is run
  benchmark/traffic/<traffic>.json   {"driver": ..., parameters}
  benchmark/drivers/<driver>.py      setup / warmup / window / check
  benchmark/metrics/<metric>.json    {"reducer": ..., "args": {...}}
  benchmark/reducers/<reducer>.py    reduce(args, data) -> number or None

A later PR adds files and entries; it edits none.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import threading
import time
from array import array
from contextlib import contextmanager
from dataclasses import asdict, dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

DATA_DIR = "benchmark"


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between the
    two nearest order statistics (numpy's default), over ALL the values."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over no time")
    return count / seconds


def highest_supported_percentile(n: int) -> Optional[int]:
    """The highest of 50/90/95/99 that has ten samples beyond it."""
    best = None
    for q in (50, 90, 95, 99):
        if n * (100 - q) / 100.0 >= 10:
            best = q
    return best


# ---------------------------------------------------------------------------
# the benchmark as data
# ---------------------------------------------------------------------------


class Bench:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self._modules: Dict[tuple, object] = {}

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, DATA_DIR, *parts)

    def read_json(self, *parts: str) -> dict:
        with open(self.path(*parts)) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        """``benchmark/<kind>/<name>.py`` loaded from this root by path, so
        a driver or a reducer is found by the name a data file gives it."""
        key = (kind, name)
        if key not in self._modules:
            path = self.path(kind, name + ".py")
            spec = importlib.util.spec_from_file_location(
                f"benchmark_{kind}_{name}", path)
            if spec is None or not os.path.exists(path):
                raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def cell(self, name: str) -> "Cell":
        for w in self.spec["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(
                f"no workload {name!r}; BENCHMARK.json has "
                f"{[w['name'] for w in self.spec['workloads']]}")
        cfg_entry = next(
            c for c in self.spec["configs"] if c["name"] == w["config"])
        with open(os.path.join(self.root, cfg_entry["file"])) as f:
            config = json.load(f)
        traffic = self.read_json("traffic", w["traffic"] + ".json")

        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        e2e_names = {m["name"] for m in e2e}
        per_layer = [
            m for m in self.spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_names)
        ]
        return Cell(self, w, cfg_entry["name"], config, traffic, e2e, per_layer)

    def peaks(self, device_kind: str) -> dict:
        table = self.read_json("peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device kind {device_kind!r} is not in benchmark/peaks.json "
                f"({sorted(table['devices'])}): add it with its source")
        return table["devices"][device_kind]


@dataclass
class Cell:
    bench: Bench
    workload: dict
    config_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    def driver(self):
        return self.bench.module("drivers", self.traffic["driver"])

    def reduce(self, metric_name: str, data: "RunData") -> Optional[float]:
        spec = self.bench.read_json("metrics", metric_name + ".json")
        reducer = self.bench.module("reducers", spec["reducer"])
        return reducer.reduce(spec.get("args", {}), data)


# ---------------------------------------------------------------------------
# spans, counters
# ---------------------------------------------------------------------------


class Spans:
    """The benchmark's own spans (``bench.*``) around the calls it makes.
    With ``annotate`` each is also written into the profiler's trace, so
    the device timeline and the host's share one clock."""

    def __init__(self, annotate: bool = False):
        self.records: List[dict] = []
        self.annotate = annotate
        self._lock = threading.Lock()

    @contextmanager
    def span(self, name: str, **args):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.add(name, t0, t1, **args)

    def add(self, name: str, t0_ns: int, t1_ns: int, **args) -> None:
        rec = {"name": name, "t0": t0_ns, "t1": t1_ns,
               "tid": threading.get_ident(), "args": args}
        with self._lock:
            self.records.append(rec)


def program_spans() -> List[dict]:
    """``libs/trace``'s ring (``perf_counter_ns`` clock) in the shape of
    ``Spans.records``."""
    from tendermint_tpu.libs import trace

    out = []
    for ev in trace.export():
        if ev.get("ph") != "X":
            continue
        t0 = int(round(ev["ts"] * 1000.0))
        out.append({
            "name": ev["name"], "t0": t0,
            "t1": t0 + int(round(ev["dur"] * 1000.0)),
            "tid": ev["tid"], "args": ev.get("args", {}),
        })
    return out


def counters_snapshot() -> Dict[str, float]:
    """The verify metric families as their exposition prints them (one
    entry per series, histograms as ``_sum``/``_count``), and the compile
    accounting of ``ops/dispatch`` under ``compile.*``."""
    from tendermint_tpu.libs.metrics import get_verify_metrics

    snap: Dict[str, float] = {}
    for line in get_verify_metrics().registry.expose_text().splitlines():
        if not line or line.startswith("#") or "_bucket{" in line:
            continue
        key, _, value = line.rpartition(" ")
        try:
            snap[key] = float(value)
        except ValueError:
            continue
    if "jax" in sys.modules:
        from tendermint_tpu.ops.dispatch import compile_stats

        cs = compile_stats()
        snap["compile.cache_hits"] = float(cs["cache_hits"])
        snap["compile.cache_misses"] = float(cs["cache_misses"])
        snap["compile.programs"] = float(cs["cache_hits"] + cs["cache_misses"])
        snap["compile.seconds"] = float(cs["compile_seconds"])
    return snap


def counters_delta(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def counter_sum(counters: Dict[str, float], family: str,
                labels: Optional[Dict[str, str]] = None) -> float:
    """Sum of a family's series whose labels include ``labels``."""
    total = 0.0
    for key, value in counters.items():
        name, _, rest = key.partition("{")
        if name != family:
            continue
        if labels and not all(f'{k}="{v}"' in rest for k, v in labels.items()):
            continue
        total += value
    return total


def guard_events(counters: Dict[str, float]) -> int:
    """Device dispatches completed on the host plus audited lanes that
    disagreed with the host oracle: 0 wherever the device is sound."""
    return int(
        counter_sum(counters, "tendermint_verify_device_fallback_total")
        + counter_sum(counters, "tendermint_verify_device_audit_total",
                      {"outcome": "mismatch"}))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Window:
    """What a driver hands back from its measured window."""

    attempted: int
    failed: int
    seconds: float
    samples: Dict[str, List[float]] = field(default_factory=dict)
    totals: Dict[str, float] = field(default_factory=dict)
    # the same two dicts for the first and the second half of the window
    halves: List[dict] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)


class GcWatch:
    """The collector's work inside the window, by generation.  A full
    collection (generation 2) walks every object that is not frozen, and
    whether one falls into every call, every fifth or none depends on the
    collector's own counters, so it can carry a run's whole spread.  Holds
    no tracked object of its own: counters in place, arrays of floats."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.full_at = array("d")
        self.full_seconds = array("d")
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        g = info["generation"]
        d = time.perf_counter() - self._t
        self.count[g] += 1
        self.seconds[g] += d
        if g == 2:
            self.full_at.append(self._t)
            self.full_seconds.append(d)

    def line(self, t0: float, t1: float, unfrozen: int) -> str:
        tenths = [0] * 10
        for t in self.full_at:
            tenths[min(9, max(0, int(10 * (t - t0) / max(t1 - t0, 1e-9))))] += 1
        gens = " ".join(f"gen{g}={self.count[g]}/{self.seconds[g]:.3f}s"
                        for g in range(3))
        worst = max(self.full_seconds) * 1e3 if len(self.full_seconds) else 0.0
        return (f"gc: {gens} full_max={worst:.1f}ms full_by_tenth={tenths} "
                f"unfrozen_at_start={unfrozen}")


@dataclass
class RunData:
    """What a reducer reads."""

    bench: Bench
    cell: Cell
    device_kind: str
    samples: Dict[str, List[float]]
    totals: Dict[str, float]
    spans: List[dict] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)
    trace: Optional[dict] = None  # tracefile.read_xplane's dict


@dataclass
class Check:
    name: str
    value: float
    limit: float
    ok: bool

    def line(self) -> str:
        return (f"check {self.name}: value={self.value!r} "
                f"limit={self.limit!r} ok={self.ok}")


def check_equal(name: str, mismatches: int) -> Check:
    """An exact comparison: the number of disagreements, limit 0."""
    return Check(name, float(mismatches), 0.0, mismatches == 0)


@dataclass
class Context:
    """What a driver is given."""

    cell: Cell
    seed: int
    cache_dir: str
    spans: Spans
    platform: str
    log: Callable[[str], None]

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def rng(self, salt: int = 0):
        import numpy as np

        return np.random.default_rng([self.seed, salt])


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record, so
    that set-up counts the interpreter's start and the imports."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def cache_dir(root: str) -> str:
    """The benchmark's cache directory: fixed, inside the checkout."""
    return os.path.join(os.path.abspath(root), ".bench_cache")


def place_caches(root: str) -> str:
    """Before the program is imported: JAX's persistent compilation cache
    goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to a fixed path in
    the checkout (the path is part of the cache's key); the program takes
    the variable as it finds it.  Every program is worth caching here: the
    ladder's trace is slow, its backend compile slower."""
    base = cache_dir(root)
    os.makedirs(base, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(base, "jax"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return base


def install_verifier(platform: str, device=None):
    """The process default verifier, as a node selects it: on a TPU the
    guarded Pallas pipeline with the ``[verify]`` defaults.  A rehearsal on
    the CPU (or a test's stand-in ``device``) keeps the same guard around
    the host oracle or the stand-in, so the guard's counters and the audit
    run as they do on the chip."""
    from tendermint_tpu.crypto import batch

    if device is None and platform == "tpu":
        batch.set_batch_verifier(None)  # select afresh, as a node does
        v = batch.get_batch_verifier()
        if not isinstance(v, batch.GuardedBatchVerifier) or v.backend != "pallas":
            raise RuntimeError(
                f"no guarded pallas verifier on this TPU: {batch.describe_verifier(v)}")
        return v
    from tendermint_tpu.libs import breaker

    breaker.reset_device_guard()
    v = batch.GuardedBatchVerifier(device or batch.HostBatchVerifier())
    batch.set_batch_verifier(v)
    return v


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, platform: str, device_kind: str,
             log: Callable[[str], None], t_process: float,
             device=None, trace_dir: Optional[str] = None) -> dict:
    """One run of one cell; returns the result object (without ``device``).

    ``t_process`` is ``time.perf_counter()`` minus the process's age when it
    was read, i.e. the process's start on the perf_counter clock."""
    from benchmark import tracefile

    cell = bench.cell(workload)
    spans = Spans(annotate=trace)
    ctx = Context(cell=cell, seed=seed, cache_dir=cache_dir(bench.root),
                  spans=spans, platform=platform, log=log)
    install_verifier(platform, device)
    driver = cell.driver()

    t0 = time.perf_counter()
    state = driver.setup(ctx)
    t_setup = time.perf_counter()
    # what set-up left behind is never walked again, as on a node that has
    # been up for a day; garbage the program makes is collected as usual
    gc.collect()
    gc.freeze()
    driver.warmup(ctx, state)
    t_warm = time.perf_counter()
    log(f"setup: import+select={t0 - t_process:.3f}s data={t_setup - t0:.3f}s "
        f"warmup={t_warm - t_setup:.3f}s")

    if trace:
        from tendermint_tpu.libs import trace as ptrace

        ptrace.reset(1 << 16)
        ptrace.enable()
        tracefile.start(trace_dir)
    before = counters_snapshot()
    unfrozen = len(gc.get_objects())
    watch = GcWatch()
    gc.callbacks.append(watch)
    setup_s = time.perf_counter() - t_process
    t_open = time.perf_counter()
    try:
        with spans.span("bench.window"):
            window: Window = driver.window(ctx, state, seconds)
    finally:
        t_close = time.perf_counter()
        gc.callbacks.remove(watch)
    after = counters_snapshot()
    trace_data = None
    if trace:
        trace_data = tracefile.stop(trace_dir, spans)
        from tendermint_tpu.libs import trace as ptrace

        ptrace.disable()

    data = RunData(
        bench=bench, cell=cell, device_kind=device_kind,
        samples=window.samples, totals=window.totals,
        spans=spans.records + (program_spans() if trace else []),
        counters=counters_delta(before, after), trace=trace_data,
    )
    for note in window.notes:
        log(note)
    log(watch.line(t_open, t_close, unfrozen))

    checks: List[Check] = list(driver.check(ctx, state, window, data))

    def reduce_all(entries) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for m in entries:
            value = setup_s if m["name"] == "setup_s" else cell.reduce(m["name"], data)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    metrics = reduce_all(cell.per_layer if trace else cell.end_to_end)
    if window.halves:
        halves = {}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                continue
            both = []
            for h in window.halves:
                hd = RunData(bench, cell, device_kind, h["samples"], h["totals"])
                try:
                    both.append(cell.reduce(m["name"], hd))
                except (ValueError, ZeroDivisionError):
                    both.append(None)
            halves[m["name"]] = both
        log("halves: " + json.dumps(halves))
    # a compile inside an untraced window must not pass unseen
    log(f"compiles_in_window: {data.counters.get('compile.programs', 0.0):g}")

    result = {
        "correct": bool(window.failed == 0 and all(c.ok for c in checks)),
        "attempted": int(window.attempted),
        "failed": int(window.failed + sum(0 if c.ok else 1 for c in checks)),
        "metrics": metrics,
    }
    if trace_data is not None:
        result["_trace"] = trace_data
        result["_spans"] = data.spans
    else:
        # per-layer readings that need no trace stand in an untraced line
        # too, under a key of their own (``metrics`` is the end-to-end set)
        clock = reduce_all(m for m in cell.per_layer if m["source"] == "host_clock")
        if clock:
            result["per_layer_host_clock"] = clock
    # each number compared beside its limit; ``run.py`` puts the key last
    # and prints the lines
    result["checks"] = [asdict(c) for c in checks]
    return result
