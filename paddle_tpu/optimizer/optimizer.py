"""Optimizer base + SGD/Momentum/Adam/AdamW/Lamb.

Reference: python/paddle/optimizer/*.py over fused CUDA kernels
(paddle/phi/kernels/gpu/adamw_kernel.cu etc.). Here each optimizer is a pure
per-parameter update rule used two ways:

* eager: ``opt.step()`` reads ``param.grad`` (populated by the tape) and
  applies a jitted update per parameter — API parity with dygraph Paddle;
* compiled: ``opt.init_state_tree`` / ``opt.apply_gradients_tree`` run the
  same rule over whole pytrees inside the jitted training step (the perf
  path; sharding specs on the state tree give ZeRO stage-1/2 for free).

``multi_precision`` keeps fp32 master weights when params are bf16/fp16
(reference: multi_precision arg + MixPrecisionOptimizer main-grad pattern).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtypes
from ..framework.tensor import Parameter, Tensor
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adagrad", "RMSProp", "Lamb"]


# Elements of a leaf from which its update stands apart from the product
# that makes its gradient (``apply_gradients_tree``). Measured on a TPU v5e
# (PERF.md section 6, PR 36), product + update, fused -> apart: 4.2 M
# elements ([1024, 4096]) 0.73 -> 0.64 ms but 0.2 ms of exposed copies of
# the float32 state beside it, a loss of 1.1% of GPT-2 medium's step;
# 6.6 M ([2560, 2560]) 1.17 -> 0.89; 26 M ([2560, 10240]) 4.49 -> 3.72;
# 67 M ([4096, 16384]) 21.75 -> 15.42. The break-even lies between the
# first two.
_UPDATE_APART_FROM = 5 * 2**20


def _is_low_precision(dt):
    return np.dtype(dt) in (np.dtype(dtypes.float16), np.dtype(dtypes.bfloat16))


class Optimizer:
    _update_rule: Callable  # (param_f32, grad_f32, state_dict, lr, wd, ctx) -> (new_p, new_state)

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        self._lr = learning_rate
        self._params = list(parameters) if parameters is not None else []
        self._weight_decay = 0.0 if weight_decay is None else (
            weight_decay if isinstance(weight_decay, float) else float(weight_decay))
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: Dict[int, Dict[str, Any]] = {}
        self._master_weights: Dict[int, jax.Array] = {}
        self._step_count = 0
        self._jit_update = jax.jit(self._fused_update, static_argnames=("wd", "apply_decay"))

    # ---------------------------------------------------------------- config
    def _parameter_list(self):
        return [p for p in self._params if p.trainable]

    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    @property
    def _learning_rate(self):
        return self._lr

    # ---------------------------------------------------------------- state
    def _state_for(self, p: Parameter):
        pid = id(p)
        if pid not in self._accumulators:
            self._accumulators[pid] = self.init_state(jnp.asarray(p._data, jnp.float32))
            if self._multi_precision and _is_low_precision(p.dtype):
                self._master_weights[pid] = p._data.astype(jnp.float32)
        return self._accumulators[pid]

    def init_state(self, param_f32) -> Dict[str, Any]:
        return {}

    # ------------------------------------------------------------ eager step
    def step(self):
        lr = self.get_lr()
        self._step_count += 1
        params = self._parameter_list()
        if self._grad_clip is not None:
            pg = [(p, p.grad) for p in params]
            for (p, _), (_, g) in zip(pg, self._grad_clip(pg)):
                p.grad = g
        for p in params:
            if p.grad is None:
                continue
            state = self._state_for(p)
            pid = id(p)
            master = self._master_weights.get(pid)
            pf = master if master is not None else p._data
            apply_decay = self._decay_applies(p)
            new_p, new_state = self._jit_update(
                pf, p.grad._data, state, jnp.float32(lr),
                jnp.int32(self._step_count), wd=self._weight_decay,
                apply_decay=apply_decay,
            )
            if master is not None:
                self._master_weights[pid] = new_p
                p._data = new_p.astype(p.dtype)
            else:
                p._data = new_p.astype(p.dtype)
            self._accumulators[pid] = new_state

    def _decay_applies(self, p: Parameter) -> bool:
        return True

    def _fused_update(self, pf, g, state, lr, step, *, wd, apply_decay):
        pf32 = pf.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        return self._update_rule(pf32, g32, state, lr, step, wd if apply_decay else 0.0)

    def _update_rule(self, p, g, state, lr, step, wd):
        raise NotImplementedError

    def clear_grad(self, set_to_zero=False):
        for p in self._params:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()

    # -------------------------------------------------------- functional API
    def init_state_tree(self, params_tree):
        """Pure: build the optimizer state pytree for a params pytree (fp32
        master copies included when multi_precision and param is bf16)."""
        def per_param(p):
            st = self.init_state(jnp.asarray(p, jnp.float32))
            if self._multi_precision and _is_low_precision(p.dtype):
                st = dict(st, master=p.astype(jnp.float32))
            return st

        with jax.named_scope("optimizer"):
            return jax.tree_util.tree_map(per_param, params_tree)

    def apply_gradients_tree(self, params_tree, grads_tree, state_tree, lr, step,
                             decay_mask_tree=None):
        """Pure: one optimizer step over pytrees. ``lr``/``step`` may be traced.
        Returns (new_params, new_state)."""
        def per_param(p, g, st, decay):
            master = st.pop("master", None) if isinstance(st, dict) else None
            pf = master if master is not None else p.astype(jnp.float32)
            wd_eff = self._weight_decay if decay else 0.0
            new_pf, new_st = self._update_rule(pf, g.astype(jnp.float32), st,
                                               lr, step, wd_eff)
            if master is not None:
                new_st = dict(new_st, master=new_pf)
            return new_pf.astype(p.dtype), new_st

        flat_p, treedef = jax.tree_util.tree_flatten(params_tree)
        flat_g = treedef.flatten_up_to(grads_tree)
        flat_s = treedef.flatten_up_to(state_tree)
        if decay_mask_tree is None:
            flat_m = [True] * len(flat_p)
        else:
            flat_m = treedef.flatten_up_to(decay_mask_tree)
        new_p, new_s = [], []
        # every operation of the update carries the scope "optimizer" in
        # the compiled program (metadata only)
        with jax.named_scope("optimizer"):
            for p, g, st, m in zip(flat_p, flat_g, flat_s, flat_m):
                if g.ndim >= 2 and g.size > _UPDATE_APART_FROM:
                    # a large matrix's gradient is a product's output: it
                    # reaches the update as a finished array, or the
                    # compiler folds the update (three float32 operands,
                    # four outputs a tile) into the product's epilogue,
                    # which halves the product's tile. One call a leaf: a
                    # barrier over the tree keeps every gradient alive
                    # until the last is made.
                    g = jax.lax.optimization_barrier(g)
                np_, ns_ = per_param(p, g, dict(st), m)
                new_p.append(np_)
                new_s.append(ns_)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))

    # -------------------------------------------------------------- state IO
    def state_dict(self):
        out = {"step": self._step_count}
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        params = self._parameter_list()
        for i, p in enumerate(params):
            name = p.name or f"param_{i}"
            st = self._accumulators.get(id(p), {})
            for k, v in st.items():
                out[f"{name}.{k}"] = Tensor._wrap(v) if not isinstance(v, Tensor) else v
            if id(p) in self._master_weights:
                out[f"{name}.master"] = Tensor._wrap(self._master_weights[id(p)])
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("step", 0))
        if isinstance(self._lr, LRScheduler) and "LR_Scheduler" in state:
            self._lr.set_state_dict(state["LR_Scheduler"])
        params = self._parameter_list()
        for i, p in enumerate(params):
            name = p.name or f"param_{i}"
            st = self._state_for(p)
            for k in list(st.keys()):
                key = f"{name}.{k}"
                if key in state:
                    v = state[key]
                    st[k] = v._data if isinstance(v, Tensor) else jnp.asarray(v)
            mkey = f"{name}.master"
            if mkey in state:
                v = state[mkey]
                self._master_weights[id(p)] = v._data if isinstance(v, Tensor) else jnp.asarray(v)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _update_rule(self, p, g, state, lr, step, wd):
        g = g + wd * p
        return p - lr * g, state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        self._momentum = momentum
        self._nesterov = use_nesterov
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def init_state(self, param_f32):
        return {"velocity": jnp.zeros_like(param_f32)}

    def _update_rule(self, p, g, state, lr, step, wd):
        g = g + wd * p
        v = self._momentum * state["velocity"] + g
        if self._nesterov:
            update = g + self._momentum * v
        else:
            update = v
        return p - lr * update, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None):
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def init_state(self, param_f32):
        return {"moment1": jnp.zeros_like(param_f32),
                "moment2": jnp.zeros_like(param_f32)}

    def _update_rule(self, p, g, state, lr, step, wd):
        # L2-style decay folded into grad (paddle Adam semantics)
        g = g + wd * p
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        stepf = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
        mhat = m / (1 - self._beta1**stepf)
        vhat = v / (1 - self._beta2**stepf)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + self._eps)
        return new_p, {"moment1": m, "moment2": v}


class AdamW(Optimizer):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py;
    apply_decay_param_fun controls which params decay)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, name=None):
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._apply_decay_fun = apply_decay_param_fun
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def init_state(self, param_f32):
        return {"moment1": jnp.zeros_like(param_f32),
                "moment2": jnp.zeros_like(param_f32)}

    def _decay_applies(self, p):
        if self._apply_decay_fun is not None:
            return bool(self._apply_decay_fun(p.name or ""))
        return True

    def _update_rule(self, p, g, state, lr, step, wd):
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        stepf = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
        mhat = m / (1 - self._beta1**stepf)
        vhat = v / (1 - self._beta2**stepf)
        new_p = p - lr * (mhat / (jnp.sqrt(vhat) + self._eps) + wd * p)
        return new_p, {"moment1": m, "moment2": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True,
                 initial_accumulator_value=0.0, name=None):
        self._eps = epsilon
        self._init_acc = initial_accumulator_value
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def init_state(self, param_f32):
        return {"moment": jnp.full_like(param_f32, self._init_acc)}

    def _update_rule(self, p, g, state, lr, step, wd):
        g = g + wd * p
        acc = state["moment"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(acc) + self._eps), {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        self._rho, self._eps, self._momentum, self._centered = rho, epsilon, momentum, centered
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def init_state(self, param_f32):
        st = {"mean_square": jnp.zeros_like(param_f32),
              "moment": jnp.zeros_like(param_f32)}
        if self._centered:
            st["mean_grad"] = jnp.zeros_like(param_f32)
        return st

    def _update_rule(self, p, g, state, lr, step, wd):
        g = g + wd * p
        ms = self._rho * state["mean_square"] + (1 - self._rho) * jnp.square(g)
        new_state = {"mean_square": ms}
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._eps)
            new_state["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * state["moment"] + lr * g / denom
        new_state["moment"] = mom
        return p - mom, new_state


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=True, name=None):
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip,
                         multi_precision, name)

    def init_state(self, param_f32):
        return {"moment1": jnp.zeros_like(param_f32),
                "moment2": jnp.zeros_like(param_f32)}

    def _decay_applies(self, p):
        if self._exclude_fn is not None:
            return not self._exclude_fn(p)
        return True

    def _update_rule(self, p, g, state, lr, step, wd):
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        stepf = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
        mhat = m / (1 - self._beta1**stepf)
        vhat = v / (1 - self._beta2**stepf)
        r = mhat / (jnp.sqrt(vhat) + self._eps) + wd * p
        w_norm = jnp.linalg.norm(p)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, {"moment1": m, "moment2": v}
