"""Build + load native C extensions (encoding/_codec_native.c and friends).

Compiled lazily on first import (cc against the running interpreter's
headers, cached next to the source, rebuilt when the .c changes); any
failure falls back to the pure-Python implementation with a logged
warning — behavior is identical, only the constant factor changes. Set
TM_NO_NATIVE_CODEC=1 to force the fallback (tests exercise both paths).
``build_all()`` rebuilds every extension from its committed source and
raises if the compiler refuses (chip_smoke.py starts with it).
"""

from __future__ import annotations

import importlib.util
import logging
import os
import subprocess
import sys
import sysconfig

_HERE = os.path.dirname(os.path.abspath(__file__))
_PKG = os.path.dirname(_HERE)
_SOABI = sysconfig.get_config_var("SOABI")
logger = logging.getLogger("tendermint_tpu.native")

# every extension in the tree: (source relative to the package, ldflags)
EXTENSIONS = (
    ("encoding/_codec_native.c", ()),
    ("crypto/_hash_native.c", ()),
    ("consensus/_wal_native.c", ("-lz",)),
)


def _build(src: str, so: str, extra_cflags=(), extra_ldflags=()) -> bool:
    include = sysconfig.get_paths()["include"]
    cc = os.environ.get("CC", "cc")
    # unique temp path: N processes building concurrently (localnet launch)
    # must not interleave writes into one file — a corrupt .so with a fresh
    # mtime would silently disable the native codec forever
    tmp = f"{so}.{os.getpid()}.tmp"
    # libraries go AFTER the source: GNU ld with --as-needed drops any
    # -l<lib> it has seen no undefined references for yet
    cmd = [cc, "-O2", "-shared", "-fPIC", f"-I{include}", *extra_cflags,
           src, *extra_ldflags, "-o", tmp]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        sys.stderr.write(f"native build failed ({os.path.basename(src)}): {e}\n")
        return False
    if res.returncode != 0:
        sys.stderr.write(
            f"native build failed ({os.path.basename(src)}):\n"
            f"{res.stderr[-1000:]}\n"
        )
        return False
    os.replace(tmp, so)
    return True


def load_ext(src: str, module_name: str, extra_cflags=(), extra_ldflags=()):
    """Compile (if stale) and import the extension at `src`; None on failure
    or when TM_NO_NATIVE_CODEC is set."""
    if os.environ.get("TM_NO_NATIVE_CODEC"):
        return None
    so = os.path.splitext(src)[0] + f".{_SOABI}.so"
    try:
        if not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src):
            if not _build(src, so, extra_cflags, extra_ldflags):
                logger.warning("%s did not build; pure Python serves instead",
                               os.path.basename(src))
                return None
        spec = importlib.util.spec_from_file_location(module_name, so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except Exception:
        logger.warning("loading %s failed; pure Python serves instead",
                       os.path.basename(so), exc_info=True)
        return None


def build_all() -> list:
    """Rebuild every extension from its committed .c file, whatever *.so
    lies in the tree; raises RuntimeError when the compiler refuses one.
    Returns the built paths."""
    built = []
    for rel, ldflags in EXTENSIONS:
        src = os.path.join(_PKG, rel)
        so = os.path.splitext(src)[0] + f".{_SOABI}.so"
        if not _build(src, so, extra_ldflags=ldflags):
            raise RuntimeError(f"cc refused {rel} (see stderr)")
        built.append(so)
    return built


def load():
    """The compiled codec module, or None when unavailable."""
    return load_ext(
        os.path.join(_HERE, "_codec_native.c"),
        "tendermint_tpu.encoding._codec_native",
    )
