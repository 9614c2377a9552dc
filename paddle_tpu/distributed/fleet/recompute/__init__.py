"""Activation recomputation (reference: python/paddle/distributed/fleet/
recompute/recompute.py — ``recompute``, ``recompute_sequential``; strategy
knob ``recompute_granularity``).

TPU-native: ``jax.checkpoint`` (remat) IS the mechanism — SURVEY.md C15. The
reference's PyLayer saves inputs + RNG states and re-runs forward inside
backward; ``jax.checkpoint`` does exactly that at the XLA level, and because
PRNG keys are constants of the traced function, dropout replay is
automatically bit-exact (no RNG state juggling needed).

Two call contexts, one code path:
* inside a compiled step (functional_call / PipelineParallel body): the
  checkpointed region embeds into the surrounding trace;
* eager/dygraph: the tape node's VJP is built from the checkpointed
  function, so residual memory is genuinely reduced and the forward is
  re-run during ``loss.backward()`` — faithful reference semantics.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax

from ....framework.tensor import Tensor, apply_op, pause_tape

__all__ = ["recompute", "recompute_sequential", "POLICY_MAP"]

_save_dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

#: recompute_granularity → jax.checkpoint policy (reference knob:
#: DistributedStrategy.recompute_configs["granularity"]); "full" re-runs
#: everything, "full_attn"/"core_attn" keep matmul outputs resident.
POLICY_MAP = {
    "full": None,
    "full_attn": _save_dots,
    "core_attn": _save_dots,
}


def _is_layer(fn) -> bool:
    return hasattr(fn, "forward") and hasattr(fn, "named_parameters")


def recompute(function, *args, **kwargs):
    """Run ``function(*args)`` with activation checkpointing (reference:
    fleet.recompute.recompute). ``function`` may be an ``nn.Layer`` or a
    callable over Tensors. Keyword-only knobs: ``use_reentrant`` (accepted,
    ignored — one implementation), ``granularity`` ("full" default)."""
    kwargs.pop("use_reentrant", None)
    kwargs.pop("preserve_rng_state", None)
    granularity = kwargs.pop("granularity", "full")
    policy = POLICY_MAP.get(granularity)

    if _is_layer(function):
        named = list(function.named_parameters())
        n_inputs = len(args)

        def raw(*arrs):
            ins, params = arrs[:n_inputs], arrs[n_inputs:]
            saved = [p._data for _, p in named]
            try:
                for (_, p), a in zip(named, params):
                    p._data = a
                with pause_tape():
                    out = function(*[Tensor._wrap(a) for a in ins], **kwargs)
                return jax.tree_util.tree_map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor),
                )
            finally:
                for (_, p), d in zip(named, saved):
                    p._data = d

        ck = jax.checkpoint(raw, policy=policy)
        return apply_op(ck, *args, *[p for _, p in named])

    def raw(*arrs):
        with pause_tape():
            out = function(*[Tensor._wrap(a) for a in arrs], **kwargs)
        return jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor),
        )

    ck = jax.checkpoint(raw, policy=policy)
    return apply_op(ck, *args)


def recompute_sequential(ctx: dict, functions, *args, **kwargs):
    """Checkpoint a Sequential in ``segments`` chunks (reference:
    fleet.recompute.recompute_sequential; ctx = {"segments": n,
    "preserve_rng_state": ...})."""
    segments = int(ctx.get("segments", 1))
    layers = list(functions)  # Sequential and plain lists both iterate
    if not layers:
        raise ValueError("recompute_sequential: empty layer list")
    per = max(1, len(layers) // segments)
    out = args
    i = 0
    while i < len(layers):
        chunk = layers[i: i + per]
        i += per

        class _Chunk:
            def __init__(self, ls):
                self._ls = ls

            def forward(self, *xs):
                x = xs[0] if len(xs) == 1 else xs
                for l in self._ls:
                    x = l(x) if not isinstance(x, tuple) else l(*x)
                return x

            __call__ = forward

            def named_parameters(self):
                for j, l in enumerate(self._ls):
                    for n, p in l.named_parameters():
                        yield f"{j}.{n}", p

        res = recompute(_Chunk(chunk),
                        *(out if isinstance(out, tuple) else (out,)),
                        **kwargs)
        out = res
    return out
