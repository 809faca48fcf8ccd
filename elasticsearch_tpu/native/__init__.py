"""Native (C) components of the runtime.

The compute path is JAX/XLA; these are the host-side hot loops where
the reference uses native code too (SURVEY.md: the runtime around the
device kernels is native). Libraries build lazily from the in-tree C
sources with the system compiler and cache next to them; every native
path has a pure-Python fallback, so a missing toolchain degrades
performance, never behavior."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

logger = logging.getLogger("elasticsearch_tpu.native")

_HERE = os.path.dirname(__file__)
_LOCK = threading.Lock()
_LIBS = {}


def load(name: str):
    """dlopen `<name>.so`, building it from `<name>.c` on first use.
    Returns None when the build fails (callers use their fallback)."""
    with _LOCK:
        if name in _LIBS:
            return _LIBS[name]
        src = os.path.join(_HERE, f"{name}.c")
        so = os.path.join(_HERE, f"{name}.so")
        lib = None
        try:
            if (not os.path.exists(so)
                    or os.path.getmtime(so) < os.path.getmtime(src)):
                # a name of this process's own: test workers that build
                # at once must not replace each other's half-written file
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["cc", "-O3", "-shared", "-fPIC", "-o", tmp, src],
                    check=True, capture_output=True, timeout=60)
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
        except Exception as exc:  # noqa: BLE001 — perf path only
            logger.warning("native [%s] unavailable (%s); using the "
                           "python fallback", name, exc)
            lib = None
        _LIBS[name] = lib
        return lib


def bind(lib_name: str, symbol: str, restype, argtypes,
         hold_gil: bool = False):
    """load() + bind one symbol's ctypes signature; None when the
    native library is unavailable (callers use their Python fallback).

    A call lets go of the interpreter lock for its length and takes it
    back after; `hold_gil` keeps it instead (`ctypes.PyDLL`), for a call
    shorter than the wait to take the lock back from busy threads."""
    lib = load(lib_name)
    if lib is None:
        return None
    if hold_gil:
        lib = ctypes.PyDLL(lib._name, handle=lib._handle)
    fn = getattr(lib, symbol, None)
    if fn is None:
        # a library left from an older source whose mtime says otherwise
        # (a copied checkout): remove the .so to have it built again
        logger.warning("native [%s] has no %s; using the python fallback",
                       lib_name, symbol)
        return None
    fn.restype = restype
    fn.argtypes = argtypes
    return fn
