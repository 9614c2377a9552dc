"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas/pjit (NOT a port; see SURVEY.md).

Top-level namespace mirrors `import paddle`: tensor ops, nn, optimizer, amp,
io, distributed, jit, vision, metric, profiler, incubate.
"""
from __future__ import annotations

__version__ = "0.1.0"

from .framework import (  # noqa: F401
    CPUPlace,
    CustomPlace,
    Parameter,
    Place,
    TPUPlace,
    Tensor,
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    uint8,
    device_count,
    enable_grad,
    get_device,
    get_flags,
    is_grad_enabled,
    no_grad,
    seed,
    set_default_dtype,
    set_device,
    set_flags,
)
from .framework.param_attr import ParamAttr  # noqa: F401
from .framework.compile_cache import register_compile_listeners

register_compile_listeners()  # every trace / lowering / compile jax does
del register_compile_listeners
from .ops import *  # noqa: F401,F403
from .ops import creation, linalg, manipulation, math  # noqa: F401
from .serialization import load, save  # noqa: F401

from . import amp  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401

# Subpackages imported lazily to keep `import paddle_tpu` light and avoid
# cycles; they self-register on first access.
import importlib as _importlib

_LAZY = {
    "analysis": "paddle_tpu.analysis",
    "io": "paddle_tpu.io",
    "jit": "paddle_tpu.jit",
    "vision": "paddle_tpu.vision",
    "metric": "paddle_tpu.metric",
    "distributed": "paddle_tpu.distributed",
    "profiler": "paddle_tpu.profiler",
    "incubate": "paddle_tpu.incubate",
    "hapi": "paddle_tpu.hapi",
    "static": "paddle_tpu.static",
    "models": "paddle_tpu.models",
    "parallel": "paddle_tpu.parallel",
    "utils": "paddle_tpu.utils",
    "device": "paddle_tpu.device_ns",
    "inference": "paddle_tpu.inference",
    "tensor": "paddle_tpu.tensor",
    "fft": "paddle_tpu.fft",
    "distribution": "paddle_tpu.distribution",
    "sparse": "paddle_tpu.sparse",
    "signal": "paddle_tpu.signal",
}


def __getattr__(name):
    if name in _LAZY:
        mod = _importlib.import_module(_LAZY[name])
        globals()[name] = mod
        return mod
    if name == "Model":  # paddle.Model — hapi's high-level trainer
        from .hapi import Model

        globals()["Model"] = Model
        return Model
    if name == "DataParallel":  # paddle.DataParallel
        from .distributed.parallel import DataParallel

        globals()["DataParallel"] = DataParallel
        return DataParallel
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def grad(outputs, inputs, grad_outputs=None, retain_graph=False, create_graph=False):
    """paddle.grad parity (eager): returns grads of outputs w.r.t. inputs."""
    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    saved = [(p, p.grad) for p in ins]
    for p in ins:
        p.grad = None
    for o in outs:
        o.backward()
    grads = [p.grad for p in ins]
    for p, g in saved:
        p.grad = g
    return grads


def enable_static():
    from . import static as _static

    _static._enable()


def disable_static():
    from . import static as _static

    _static._disable()


def in_dynamic_mode():
    try:
        from . import static as _static

        return not _static._enabled()
    except Exception:
        return True


def summary(net, input_size=None, dtypes=None):
    n_params = sum(p.size for p in net.parameters())
    trainable = sum(p.size for p in net.parameters() if p.trainable)
    return {"total_params": n_params, "trainable_params": trainable}


# top-level aliases resolved from submodules (paddle exports these at root)
from .ops.linalg import (  # noqa: F401,E402
    cross,
    histogram,
    histogramdd,
    mv,
    norm,
    tensordot,
)
from .nn.functional.activation import log_softmax  # noqa: F401,E402
from .ops.math import bincount, einsum, nonzero, unique  # noqa: F401,E402

# attach the functional tensor API as Tensor methods (reference:
# python/paddle/tensor/__init__.py tensor_method_func monkey-patching)
from .framework.tensor_methods import register_tensor_methods  # noqa: E402

register_tensor_methods()
