"""Native (C++) runtime components, loaded via ctypes (SURVEY.md stance:
pybind11 is absent from this image — C ABI + ctypes is the binding layer).

Build-on-first-use with g++ from the ``*.cc`` beside this file; artifacts
cached under ``paddle_tpu/native/_build/`` (git-ignored), keyed by a digest
of their sources. Every native component has a pure-Python
fallback so the framework works without a toolchain (the reference requires
a full CMake build; we degrade gracefully instead).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

_HERE = os.path.dirname(__file__)
_BUILD = os.path.join(_HERE, "_build")
_lock = threading.Lock()
_libs = {}


def _compile(name: str, sources) -> Optional[str]:
    """g++ -O2 -shared; returns .so path or None when unavailable.

    The artifact is named after a digest of its sources, so a build
    product left in ``_build/`` by other sources (an earlier commit, a
    copied tree) is never what runs — file times say nothing after a
    checkout or a copy. Compiles to a per-process temp path and
    os.rename()s into place so sibling processes racing on a cold cache
    never dlopen a half-written .so (rename is atomic within a
    filesystem)."""
    srcs = [os.path.join(_HERE, s) for s in sources]
    digest = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            digest.update(f.read())
    so = os.path.join(_BUILD, f"lib{name}.{digest.hexdigest()[:16]}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD, exist_ok=True)
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           "-o", tmp, *srcs]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if r.returncode != 0:
        print(f"[paddle_tpu.native] build of {name} failed:\n{r.stderr}",
              file=sys.stderr)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    try:
        os.rename(tmp, so)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        if not os.path.exists(so):
            return None
    return so


def load(name: str, sources) -> Optional[ctypes.CDLL]:
    """Build (if needed) + dlopen a native component; None on failure
    (callers engage their pure-Python fallback)."""
    with _lock:
        if name in _libs:
            return _libs[name]
        so = _compile(name, sources)
        try:
            lib = ctypes.CDLL(so) if so else None
        except OSError as e:
            print(f"[paddle_tpu.native] dlopen of {name} failed: {e}",
                  file=sys.stderr)
            lib = None
        _libs[name] = lib
        return lib


def tcp_store_lib() -> Optional[ctypes.CDLL]:
    lib = load("tcp_store", ["tcp_store.cc"])
    if lib is None:
        return None
    lib.ts_server_start.restype = ctypes.c_void_p
    lib.ts_server_start.argtypes = [ctypes.c_int]
    lib.ts_server_stop.argtypes = [ctypes.c_void_p]
    lib.ts_client_connect.restype = ctypes.c_void_p
    lib.ts_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                      ctypes.c_int]
    lib.ts_client_close.argtypes = [ctypes.c_void_p]
    lib.ts_set.restype = ctypes.c_int64
    lib.ts_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                           ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32]
    lib.ts_get.restype = ctypes.c_int64
    lib.ts_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                           ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32,
                           ctypes.POINTER(ctypes.c_uint32)]
    lib.ts_add.restype = ctypes.c_int64
    lib.ts_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.ts_check.restype = ctypes.c_int64
    lib.ts_check.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ts_delete.restype = ctypes.c_int64
    lib.ts_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    return lib
