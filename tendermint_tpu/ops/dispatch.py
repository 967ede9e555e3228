"""The one place device kernels are launched from, and where the process
learns which device it has.

Three facts every device path needs, kept together so they cannot drift:

  * ``accelerator()`` — the chip, as ``jax.devices()`` reports it under
    whatever ``JAX_PLATFORMS`` says.  One in-process discovery: without a
    chip the installed runtime answers in about two seconds (it does not
    hang), and with ``JAX_PLATFORMS=cpu`` it is never asked.
  * ``call_jit`` — runs a jitted kernel; the first call for an argument
    signature, which traces, lowers and compiles, is wrapped in
    ``breaker.compile_grace()`` so the guard's dispatch deadline does not
    count compilation.  JAX enqueues the execution asynchronously, so the
    grace covers the compile and nothing of the device run.
  * ``compile_stats()`` — this process's persistent-cache hits and misses
    (JAX's own monitoring events) and the seconds its kernels' first calls
    took.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import jax
from jax import monitoring

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.breaker import compile_grace

_mtx = threading.Lock()
_seen: set = set()
_stats = {"cache_hits": 0, "cache_misses": 0, "compile_seconds": 0.0}


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _mtx:
            _stats["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _mtx:
            _stats["cache_misses"] += 1


monitoring.register_event_listener(_on_event)


def compile_stats() -> dict:
    """This process's compile accounting: the persistent cache directory,
    programs read from it (hits), programs compiled and stored in it
    (misses), and the wall seconds ``call_jit`` first calls took — trace,
    lower, and compile or cache load."""
    with _mtx:
        out = dict(_stats)
    out["compile_seconds"] = round(out["compile_seconds"], 3)
    out["cache_dir"] = jax.config.jax_compilation_cache_dir
    return out


def accelerator() -> Optional["jax.Device"]:
    """``jax.devices()[0]`` when the default backend is a TPU, else None.
    The environment chooses (``JAX_PLATFORMS``); nothing is probed in a
    child and nothing is pinned as a side effect."""
    dev = jax.devices()[0]
    return dev if dev.platform == "tpu" else None


def device_info() -> dict:
    """The device as JAX reports it, for start-up lines and /status."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "id": int(devs[0].id),
        "count": len(devs),
    }


def _signature(fn, args, static) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (
        fn, treedef,
        tuple(
            (getattr(a, "shape", ()), str(getattr(a, "dtype", type(a))),
             getattr(a, "sharding", None))
            for a in leaves
        ),
        tuple(sorted(static.items())),
    )


def call_jit(fn, *args, **static):
    """``fn(*args, **static)`` for a jitted ``fn``; the first call per
    (shapes, dtypes, placement, static values) runs under compile_grace."""
    key = _signature(fn, args, static)
    with _mtx:
        seen = key in _seen
    if seen:
        return fn(*args, **static)
    t0 = time.monotonic()
    # names the program when something traces, lowers or compiles where a
    # steady state was expected; lanes is the leading dimension (the bucket)
    shape = getattr(args[0], "shape", ()) if args else ()
    with trace.span(
        "jit.first_call", fn=getattr(fn, "__name__", type(fn).__name__),
        lanes=int(shape[0]) if shape else 0,
    ) as sp:
        with compile_grace():
            out = fn(*args, **static)
        seconds = time.monotonic() - t0
        sp.set(seconds=round(seconds, 3))
    with _mtx:
        _seen.add(key)
        _stats["compile_seconds"] += seconds
    return out
