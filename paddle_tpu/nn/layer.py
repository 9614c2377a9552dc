"""nn.Layer: the module system (reference: python/paddle/nn/layer/layers.py).

Stateful module tree with named parameters/buffers/sublayers, forward hooks,
state_dict — the Paddle-shaped shell. The functional core extracts the
parameter pytree (``paddle_tpu.jit.functional_call``) so the whole training
step can be one compiled XLA program; eager calls run op-by-op through the
autograd tape.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import jax
import numpy as np

from ..framework import dtypes, random as _random
from ..framework.tensor import Parameter, Tensor
from . import initializer as I

__all__ = ["Layer", "LayerList", "Sequential", "ParameterList"]


class _HookRemoveHelper:
    def __init__(self, hooks, hid):
        self._hooks, self._hid = hooks, hid

    def remove(self):
        self._hooks.pop(self._hid, None)


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        self._dtype = dtypes.convert_dtype(dtype) or dtypes.get_default_dtype()
        self._full_name = name_scope or type(self).__name__.lower()
        self.training = True
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._hook_id = 0

    # ------------------------------------------------------------------ attrs
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        sublayers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            sublayers.pop(name, None) if sublayers else None
        elif isinstance(value, Layer):
            if sublayers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            sublayers[name] = value
            value._held_as(self._child_scope(name))
        elif params is not None and name in params:
            if value is None:
                params.pop(name)
            else:
                params[name] = value
            # keep the instance __dict__ fast path coherent with _parameters
            self.__dict__.pop(name, None)
            if value is not None:
                object.__setattr__(self, name, value)
            return
        elif buffers is not None and name in buffers:
            buffers[name] = value
            self.__dict__.pop(name, None)
            if value is not None:
                object.__setattr__(self, name, value)
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        found = False
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                found = True
        # the instance __dict__ fast-path copy must go too, else the
        # attribute stays reachable after deletion
        if name in self.__dict__:
            object.__delattr__(self, name)
        elif not found:
            object.__delattr__(self, name)

    # ------------------------------------------------------------- parameters
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer: Optional[I.Initializer] = None,
    ) -> Parameter:
        dtype = dtypes.convert_dtype(dtype) or self._dtype
        init = default_initializer
        if attr is not None and getattr(attr, "initializer", None) is not None:
            init = attr.initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        name = getattr(attr, "name", None) if attr is not None else None
        key = _param_key(self._full_name, name or ("b" if is_bias else "w"), shape)
        p = Parameter(init(tuple(int(s) for s in shape), dtype, key), name=name)
        if attr is not None and getattr(attr, "learning_rate", None) is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
        if attr is not None and getattr(attr, "trainable", True) is False:
            p.stop_gradient = True
            p.trainable = False
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        sublayer._held_as(self._child_scope(name))
        return sublayer

    # the key the parent holds this layer under ("attn", "qkv_proj"): the
    # jax.named_scope its forward runs in, so that every operation of a
    # compiled program carries the path of the layer that caused it
    # (".../h/3/attn/qkv_proj/dot_general", the backward twin under
    # "transpose(jvp(...))/h/3/attn/..."). Metadata only: the compiled
    # program is the same. None for a root, which runs under its class name.
    _scope_name: Optional[str] = None

    def _held_as(self, name: str):
        object.__setattr__(self, "_scope_name", name)

    def _child_scope(self, key: str) -> str:
        return key

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if tensor is not None:
            tensor.name = name

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer, lp in self._walk(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (f"{lp}.{pname}" if lp else pname), p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer, lp in self._walk(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (f"{lp}.{bname}" if lp else bname), b

    def _walk(self, prefix="", include_sublayers=True):
        yield "", self, prefix
        if include_sublayers:
            for name, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{name}" if prefix else name
                yield from sub._walk(sp, True)

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for _, sub, _ in self._walk("", True):
            if sub is not self:
                out.append(sub)
        return out

    def named_sublayers(self, prefix="", include_self=False):
        for name, sub, lp in self._walk(prefix, True):
            if sub is self and not include_self:
                continue
            yield lp, sub

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # -------------------------------------------------------------- state IO
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix=""):
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix, include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix, include_sublayers=include_sublayers):
            dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name in own:
                own[name].set_value(value)
            else:
                unexpected.append(name)
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # ------------------------------------------------------------------ modes
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    # ---------------------------------------------------------------- dtypes
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = dtypes.convert_dtype(dtype)
            for _, p in self.named_parameters():
                if dtypes.is_floating_point(p.dtype):
                    p._data = p._data.astype(dt)
            for _, b in self.named_buffers():
                if b is not None and dtypes.is_floating_point(b.dtype):
                    b._data = b._data.astype(dt)
            for layer in self.sublayers(include_self=True):
                layer._dtype = dt
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def float(self):
        return self.to(dtype="float32")

    # ----------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return _HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ------------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        with jax.named_scope(self._scope_name or type(self).__name__.lower()):
            for hook in self._forward_pre_hooks.values():
                result = hook(self, inputs)
                if result is not None:
                    inputs = result if isinstance(result, tuple) else (result,)
            outputs = self.forward(*inputs, **kwargs)
            for hook in self._forward_post_hooks.values():
                result = hook(self, inputs, outputs)
                if result is not None:
                    outputs = result
            return outputs

    def full_name(self):
        return self._full_name

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}"]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        return "\n".join(lines) + ")" if len(lines) > 1 else lines[0] + ")"


def _param_key(scope: str, name: str, shape) -> jax.Array:
    """Deterministic per-parameter PRNG key: fold a stable hash of the
    (scope, name, shape) identity into the global base key. Replaces the
    reference's rank-0 init + broadcast (fleet/utils hybrid_parallel_util
    broadcast_*_parameters) — every process computes identical inits."""
    ident = f"{scope}/{name}/{tuple(shape)}".encode()
    h = int.from_bytes(hashlib.sha256(ident).digest()[:4], "little")
    return jax.random.fold_in(_random.base_key(), h)


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def _held_as(self, name: str):
        """A list is iterated, never called: it opens no scope of its own,
        so its items run under "<the list's key>/<index>" ("h/3")."""
        super()._held_as(name)
        for key, sub in self._sub_layers.items():
            sub._held_as(self._child_scope(key))

    def _child_scope(self, key: str) -> str:
        return f"{self._scope_name}/{key}" if self._scope_name else key

    def append(self, sublayer):
        self.add_sublayer(str(len(self._sub_layers)), sublayer)
        return self

    def insert(self, index, sublayer):
        layers = list(self._sub_layers.values())
        layers.insert(index, sublayer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def extend(self, sublayers):
        for l in sublayers:
            self.append(l)
        return self

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return list(self._sub_layers.values())[idx]
        return self._sub_layers[str(idx % len(self._sub_layers) if idx < 0 else idx)]

    def __setitem__(self, idx, layer):
        self.add_sublayer(str(idx), layer)

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and not isinstance(layers[0], Layer):
            layers = layers[0]
        for i, l in enumerate(layers):
            if isinstance(l, (list, tuple)):
                name, l = l
            else:
                name = str(i)
            self.add_sublayer(name, l)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())
