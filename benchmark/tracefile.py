"""From the profiler's trace to numbers: the yardstick's own reduction.

``start``/``stop`` bracket the measured window of a ``--trace 1`` run.
``read_xplane`` turns the ``.xplane.pb`` the profiler wrote into a small
neutral dict (device operations with start and duration, on the
``perf_counter_ns`` clock the spans use), and the functions below it reduce
that dict to busy seconds, kernel time, idle share and the breakdown.  The
reducers under ``benchmark/reducers`` and the tests call the same functions;
``benchmark/testdata`` holds a small recorded dict.

Clocks: the profiler stamps events relative to its own start.  A
``bench.anchor`` annotation, entered at a known ``perf_counter_ns`` when the
trace starts and again when it stops, gives the offset between the two.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
_state: dict = {}


def _anchor() -> int:
    import jax

    t0 = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(ANCHOR):
        t1 = time.perf_counter_ns()
    return (t0 + t1) // 2


def start(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the host's story comes from the spans
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    _state["anchors"] = [_anchor()]
    _state["t0"] = time.perf_counter()


def stop(trace_dir: str, spans=None) -> dict:
    import jax

    _state["anchors"].append(_anchor())
    window_s = time.perf_counter() - _state["t0"]
    jax.profiler.stop_trace()
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    data = read_xplane(files[-1], _state["anchors"])
    data["window_s"] = window_s
    shutil.rmtree(trace_dir, ignore_errors=True)
    return data


def read_xplane(path: str, anchors_perf_ns: Sequence[int]) -> dict:
    """{"ops": [[name, start_ns, dur_ns, device], ...], "devices": n,
    "t0": ns, "t1": ns, "drift_ns": ...}: device operations of every TPU
    plane's ``XLA Ops`` line, on the perf_counter clock, and the traced
    interval [t0, t1] between the two anchors."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    anchor_prof: List[float] = []
    planes = list(pd.planes)
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    anchor_prof.append(float(ev.start_ns))
    anchor_prof.sort()
    if len(anchor_prof) != len(anchors_perf_ns):
        raise RuntimeError(
            f"found {len(anchor_prof)} anchors in the trace, "
            f"wrote {len(anchors_perf_ns)}")
    offsets = [p - a for p, a in zip(anchors_perf_ns, anchor_prof)]
    offset = sum(offsets) / len(offsets)
    ops: List[list] = []
    device = 0
    names: Dict[str, List[str]] = {}
    for plane in planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        names[plane.name] = [ln.name for ln in plane.lines]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append([ev.name, int(ev.start_ns + offset),
                            int(ev.duration_ns), device])
        device += 1
    return {
        "ops": ops, "devices": device,
        "t0": int(anchors_perf_ns[0]), "t1": int(anchors_perf_ns[-1]),
        "drift_ns": int(max(offsets) - min(offsets)),
        "planes": names,
    }


# ---------------------------------------------------------------------------
# reductions over the neutral dict
# ---------------------------------------------------------------------------


def _merged(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_intervals(trace: dict, device: int) -> List[Tuple[int, int]]:
    """The union of the intervals in which an operation ran on ``device``,
    clipped to the traced interval."""
    t0, t1 = trace["t0"], trace["t1"]
    iv = []
    for _name, start, dur, dev in trace["ops"]:
        if dev != device:
            continue
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            iv.append((s, e))
    return _merged(iv)


def busy_seconds(trace: dict) -> float:
    """Seconds an operation ran on the device, averaged over the devices."""
    n = max(1, trace["devices"])
    total = sum(e - s for d in range(trace["devices"])
                for s, e in busy_intervals(trace, d))
    return total / n / 1e9


def window_seconds(trace: dict) -> float:
    return (trace["t1"] - trace["t0"]) / 1e9


def idle_share(trace: dict) -> Optional[float]:
    """1 - busy/window in percent; None when no device plane was traced."""
    if not trace["devices"] or not trace["ops"]:
        return None
    return 100.0 * (1.0 - busy_seconds(trace) / window_seconds(trace))


def kernel_seconds(trace: dict, pattern: str) -> Tuple[float, int]:
    """(summed device seconds, number) of the operations whose name matches
    ``pattern``, inside the traced interval, averaged over the devices."""
    rx = re.compile(pattern)
    t0, t1 = trace["t0"], trace["t1"]
    total, count = 0, 0
    for name, start, dur, _dev in trace["ops"]:
        if start >= t0 and start + dur <= t1 and rx.search(name):
            total += dur
            count += 1
    n = max(1, trace["devices"])
    return total / n / 1e9, count


def _short(name: str, limit: int = 64) -> str:
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:limit]


def top_device_ops(trace: dict, limit: int = 10) -> List[list]:
    acc: Dict[str, int] = {}
    for name, _start, dur, _dev in trace["ops"]:
        key = _short(name)
        acc[key] = acc.get(key, 0) + dur
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps_by_span(trace: dict, spans: Sequence[dict],
                      limit: int = 10) -> List[list]:
    """Idle time of device 0 inside the traced interval, attributed to the
    innermost host span open in it (the one started last), ``_no_span_``
    where none is.  One sweep over span and busy-interval boundaries."""
    t0, t1 = trace["t0"], trace["t1"]
    events: List[Tuple[int, int, int]] = []  # (time, kind, index)
    busy = busy_intervals(trace, 0)
    for s, e in busy:
        events.append((s, 0, -1))
        events.append((e, 1, -1))
    live = [sp for sp in spans
            if sp["t1"] > t0 and sp["t0"] < t1 and sp["name"] != "bench.window"]
    for i, sp in enumerate(live):
        events.append((max(sp["t0"], t0), 2, i))
        events.append((min(sp["t1"], t1), 3, i))
    events.append((t1, 4, -1))
    events.sort()
    acc: Dict[str, int] = {}
    active: Dict[int, int] = {}
    n_busy = 0
    prev = t0
    for t, kind, idx in events:
        if t > prev and n_busy == 0:
            if active:
                inner = max(active, key=lambda i: (active[i], i))
                key = live[inner]["name"]
            else:
                key = "_no_span_"
            acc[key] = acc.get(key, 0) + (t - prev)
        prev = max(prev, t)
        if kind == 0:
            n_busy += 1
        elif kind == 1:
            n_busy -= 1
        elif kind == 2:
            active[idx] = live[idx]["t0"]
        elif kind == 3:
            active.pop(idx, None)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in top]
