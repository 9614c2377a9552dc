"""paddle.utils.cpp_extension parity (reference:
python/paddle/utils/cpp_extension/ — JIT-compile user C++/CUDA ops and
register them; SURVEY.md A25: "jax.ffi / Pallas custom-kernel registration
helper").

TPU stance: device kernels are Pallas (see paddle_tpu/ops/pallas/); this
module covers the HOST-side C++ extension path — compile a shared object
with the baked toolchain and hand back a ctypes handle (the same machinery
that builds the native TCPStore). CUDA sources are rejected explicitly.
"""
from __future__ import annotations

import ctypes
import os
from typing import Optional, Sequence

__all__ = ["load", "load_ffi", "CppExtension", "CUDAExtension"]


def load(name: str, sources: Sequence[str], extra_cxx_cflags=None,
         extra_cuda_cflags=None, extra_ldflags=None, extra_include_paths=None,
         build_directory: Optional[str] = None, verbose: bool = False):
    """JIT-compile C++ ``sources`` into a shared object and dlopen it.
    Returns the ctypes.CDLL (callers declare argtypes/restypes, or wrap via
    jax.ffi for in-graph custom calls)."""
    if any(str(s).endswith((".cu", ".cuh")) for s in sources):
        raise ValueError(
            "CUDA sources are not buildable on TPU — write device kernels "
            "in Pallas (paddle_tpu/ops/pallas) and host code in C++")
    import subprocess
    import sys

    build = build_directory or os.path.join(
        os.path.expanduser("~"), ".cache", "paddle_tpu_extensions")
    os.makedirs(build, exist_ok=True)
    srcs = [os.path.abspath(s) for s in sources]
    # cache key covers the FULL build configuration, not just the name —
    # same-name loads with different sources/flags must not collide
    import hashlib

    cfg = repr((sorted(srcs), extra_cxx_cflags, extra_ldflags,
                extra_include_paths))
    tag = hashlib.sha1(cfg.encode()).hexdigest()[:10]
    so = os.path.join(build, f"lib{name}.{tag}.so")
    if not (os.path.exists(so) and all(
            os.path.getmtime(so) >= os.path.getmtime(s) for s in srcs)):
        # temp + atomic rename: concurrent processes on a cold cache must
        # never dlopen a partially-written .so
        tmp = f"{so}.tmp.{os.getpid()}"
        cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread"]
        for inc in (extra_include_paths or []):
            cmd += ["-I", inc]
        cmd += (extra_cxx_cflags or [])
        cmd += ["-o", tmp, *srcs]
        cmd += (extra_ldflags or [])
        if verbose:
            print(" ".join(cmd), file=sys.stderr)
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise RuntimeError(f"cpp_extension build failed:\n{r.stderr}")
        try:
            os.rename(tmp, so)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            if not os.path.exists(so):
                raise
    return ctypes.CDLL(so)


def load_ffi(name: str, sources: Sequence[str], functions: Sequence[str],
             platform: str = "cpu", **load_kwargs):
    """Compile C++ ``sources`` implementing XLA FFI handlers and register
    each symbol in ``functions`` as an XLA custom-call target — the
    registration path the reference provides through paddle/phi/capi
    (SURVEY.md A7: out-of-tree kernels entering dispatch) and
    op_meta_info.h custom ops (A25), here entering XLA's dispatch so the op
    is usable INSIDE jit.

    Handlers use the jaxlib-shipped headers (xla/ffi/api/ffi.h +
    XLA_FFI_DEFINE_HANDLER_SYMBOL); targets are registered as
    ``{name}.{function}``. Returns ``{function: caller}`` where
    ``caller(result_shape_dtypes, *args, **attrs)`` invokes
    ``jax.ffi.ffi_call``. ``platform`` is "cpu": XLA custom calls execute on
    the host even in TPU programs (TPU device code stays Pallas)."""
    import jax

    jax_ffi = jax.ffi

    inc = list(load_kwargs.pop("extra_include_paths", []) or [])
    inc.append(jax_ffi.include_dir())
    lib = load(name, sources, extra_include_paths=inc, **load_kwargs)

    callers = {}
    for fn_name in functions:
        sym = getattr(lib, fn_name)
        target = f"{name}.{fn_name}"
        # XLA rejects re-registering a target name at a different address;
        # same build → reuse, different build of the same name → a
        # uniquified target (the reference's registry similarly keys on the
        # registering module)
        seen = _ffi_registry.get((target, platform))
        if seen is not None and seen != lib._name:
            n = 1
            while _ffi_registry.get((f"{target}#{n}", platform),
                                    lib._name) != lib._name:
                n += 1
            target = f"{target}#{n}"
            seen = _ffi_registry.get((target, platform))
        if seen is None:
            jax_ffi.register_ffi_target(target, jax_ffi.pycapsule(sym),
                                        platform=platform)
            _ffi_registry[(target, platform)] = lib._name

        def caller(result_shape_dtypes, *args, _target=target, **attrs):
            return jax_ffi.ffi_call(_target, result_shape_dtypes)(
                *args, **attrs)

        callers[fn_name] = caller
    return callers


_ffi_registry: dict = {}


class CppExtension:
    """setup()-style descriptor parity (reference CppExtension)."""

    def __init__(self, sources, *args, **kwargs):
        self.sources = list(sources)
        self.kwargs = kwargs


def CUDAExtension(*args, **kwargs):  # pragma: no cover
    raise NotImplementedError(
        "CUDAExtension is CUDA-only; on TPU write Pallas kernels "
        "(paddle_tpu/ops/pallas) or host C++ via CppExtension/load")
