"""Span tracer — a lock-protected ring buffer of timed spans exported in
Chrome trace-event JSON (load via chrome://tracing or https://ui.perfetto.dev).

The Go reference leans on pprof/go-trace for this (node/node.go:474-479);
here the interesting timelines are host-side seams the device profiler never
sees: consensus step transitions, WAL fsync, the fast-sync window pipeline,
mempool recheck, RPC dispatch.  Usage:

    from tendermint_tpu.libs import trace
    with trace.span("fastsync.window", h0=h, n=n):
        ...
    trace.instant("consensus.step", height=h, round=r, step=s)

Disabled (the default) the hot-path cost is one attribute check and a shared
no-op context manager — nothing is allocated and nothing is recorded; the
host fast-sync bench gates this at <1% overhead.  Enable with TM_TRACE=1 in
the environment, trace.enable(), or the `trace_reset` RPC; export with the
`dump_trace` RPC or trace.chrome_trace().

The buffer is a fixed-size ring: recording never blocks on a consumer and
never grows memory — old spans are overwritten (dropped() counts them).

Identity.  Every recorded span carries three ints in its exported ``args``:
``span_id`` (unique in the process), ``parent_id`` (the span open on the same
thread when it was entered, else None) and ``root_id`` (its top ancestor's
id: every span of one verify_commit call, or of one fast-sync window, shares
it).  The parent comes from a thread-local stack of open spans, touched only
while the tracer is enabled.  Work handed to another thread keeps its place
in the tree through a handle:

    handle = trace.current()            # None when disabled or nothing open
    # ... on the other thread:
    trace.adopt(handle)                 # spans opened here are its children

Args known only at exit go through the span: ``with trace.span(..) as sp:
... sp.set(hit=True)``.  A span that turns out to have covered nothing worth
a record (a look of a polling loop that found no work) is taken back with
``sp.drop()`` before it exits: it is then never written to the ring.

CPU time.  A recorded span also carries ``cpu_ms``: the CPU time of ITS
thread between entry and exit (``time.thread_time_ns``, taken inside the
wall-clock pair, so ``cpu_ms <= dur`` up to the clocks' grain).  ``dur -
cpu_ms`` is the time the thread was off the CPU inside the span: waiting for
the interpreter lock or the scheduler in a span of plain Python, and the wait
itself in a span drawn round one (a sleep, a join, a blocking device read).
Another thread's work under the span never counts.  The clock is read only
on the enabled path.

Rule for call sites: a span is per call, per dispatch or per window — never
per block, per lane or per signature — and its kwargs are O(1) to compute
(``len(x)``, an int already in hand).  A fast-sync window applies thousands
of blocks and the benchmark's ring holds 65,536 records.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import List, Optional

_now_ns = time.perf_counter_ns
_thread_ns = time.thread_time_ns  # this thread's CPU time; enabled path only
_ids = itertools.count(1)  # next() is atomic under the GIL

DEFAULT_CAPACITY = 8192


class _NoopSpan:
    """Shared do-nothing context manager — the disabled-path return value."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass

    def drop(self) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    """One open span; also the handle ``current()`` hands to other threads."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_c0", "_dropped",
                 "span_id", "parent_id", "root_id")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._dropped = False

    def __enter__(self) -> "_Span":
        stack = self._tracer._stack()
        self.span_id = next(_ids)
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            self.root_id = parent.root_id
        else:
            self.parent_id = None
            self.root_id = self.span_id
        stack.append(self)
        self._t0 = _now_ns()
        self._c0 = _thread_ns()  # inside the wall pair: cpu_ms <= dur
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        c1 = _thread_ns()
        t1 = _now_ns()
        stack = self._tracer._stack()
        if stack and stack[-1] is self:  # not so after an adopt() under it
            stack.pop()
        if self._dropped:
            return False
        self.args.update(span_id=self.span_id, parent_id=self.parent_id,
                         root_id=self.root_id,
                         cpu_ms=round((c1 - self._c0) / 1e6, 3))
        self._tracer.record(self.name, self._t0, t1, self.args)
        return False

    def set(self, **args) -> None:
        """Args known only at exit (a verdict, a count)."""
        self.args.update(args)

    def drop(self) -> None:
        """Never record this span (it covered nothing: an empty look).  Its
        children, if any were recorded, would be left without a parent, so
        drop only what opened none."""
        self._dropped = True


class Tracer:
    """The ring buffer.  One module-level instance serves the process; tests
    construct their own."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._mtx = threading.Lock()
        self._tls = threading.local()  # .stack: this thread's open spans
        self.enabled = False
        self._configure(capacity)

    def _configure(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError(f"tracer capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._buf: List[Optional[tuple]] = [None] * capacity
        self._next = 0  # total records ever written; ring slot = _next % cap

    # control ---------------------------------------------------------------
    def enable(self, capacity: Optional[int] = None) -> None:
        with self._mtx:
            if capacity is not None and capacity != self.capacity:
                self._configure(capacity)
            self.enabled = True

    def disable(self) -> None:
        with self._mtx:
            self.enabled = False

    def reset(self, capacity: Optional[int] = None) -> None:
        with self._mtx:
            self._configure(capacity if capacity is not None else self.capacity)

    def dropped(self) -> int:
        """Spans overwritten by ring wraparound since the last reset."""
        with self._mtx:
            return max(0, self._next - self.capacity)

    def __len__(self) -> int:
        with self._mtx:
            return min(self._next, self.capacity)

    # recording -------------------------------------------------------------
    def _stack(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            stack = self._tls.stack = []
            return stack

    def span(self, name: str, **args) -> object:
        if not self.enabled:
            return _NOOP
        return _Span(self, name, args)

    def current(self) -> Optional[_Span]:
        """The innermost span open on this thread, as a handle another
        thread can adopt; None when disabled or nothing is open."""
        if not self.enabled:
            return None
        stack = self._stack()
        return stack[-1] if stack else None

    def adopt(self, handle: Optional[_Span]) -> None:
        """Make ``handle`` (another thread's ``current()``) the parent of
        the spans this thread opens from now on.  For a thread made for one
        hand-off; None (tracing was off at the hand-off) does nothing."""
        if handle is not None and self.enabled:
            self._tls.stack = [handle]

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        t = _now_ns()
        self.record(name, t, None, args)

    def record(self, name: str, t0_ns: int, t1_ns: Optional[int],
               args: dict) -> None:
        """t1_ns None marks an instant event.  Called from arbitrary threads;
        the lock covers one list store + one increment."""
        if not self.enabled:
            return
        ident = threading.get_ident()
        tname = threading.current_thread().name
        with self._mtx:
            self._buf[self._next % self.capacity] = (
                name, t0_ns, t1_ns, ident, tname, args
            )
            self._next += 1

    # export ----------------------------------------------------------------
    def export(self) -> List[dict]:
        """Chrome trace-event list, oldest first.  ts/dur are microseconds
        (the trace-event spec's unit); tid carries the Python thread ident
        with thread names emitted as metadata events."""
        with self._mtx:
            n = self._next
            if n <= self.capacity:
                records = [r for r in self._buf[:n]]
            else:
                cut = n % self.capacity
                records = self._buf[cut:] + self._buf[:cut]
        pid = os.getpid()
        events: List[dict] = []
        seen_tids = {}
        for rec in records:
            if rec is None:
                continue
            name, t0, t1, tid, tname, args = rec
            seen_tids.setdefault(tid, tname)
            ev = {
                "name": name,
                "cat": name.split(".", 1)[0],
                "pid": pid,
                "tid": tid,
                "ts": t0 / 1000.0,
            }
            if t1 is None:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = (t1 - t0) / 1000.0
            if args:
                ev["args"] = args
            events.append(ev)
        meta = [
            {
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in seen_tids.items()
        ]
        return meta + events

    def chrome_trace(self) -> dict:
        return {"traceEvents": self.export(), "displayTimeUnit": "ms"}


# -- module-level default tracer ------------------------------------------------

_tracer = Tracer(
    int(os.environ.get("TM_TRACE_BUFFER", "") or DEFAULT_CAPACITY)
)
if os.environ.get("TM_TRACE", "") not in ("", "0"):
    _tracer.enable()


def get_tracer() -> Tracer:
    return _tracer


def enabled() -> bool:
    return _tracer.enabled


def enable(capacity: Optional[int] = None) -> None:
    _tracer.enable(capacity)


def disable() -> None:
    _tracer.disable()


def reset(capacity: Optional[int] = None) -> None:
    _tracer.reset(capacity)


def dropped() -> int:
    return _tracer.dropped()


def span(name: str, **args) -> object:
    """`with trace.span("fastsync.window", h0=.., n=..): ...` — returns the
    shared no-op when disabled (zero allocation beyond the kwargs the caller
    already built)."""
    if not _tracer.enabled:
        return _NOOP
    return _Span(_tracer, name, args)


def current() -> Optional[_Span]:
    return _tracer.current()


def adopt(handle: Optional[_Span]) -> None:
    _tracer.adopt(handle)


def instant(name: str, **args) -> None:
    if not _tracer.enabled:
        return
    _tracer.instant(name, **args)


def export() -> List[dict]:
    return _tracer.export()


def chrome_trace() -> dict:
    return _tracer.chrome_trace()
