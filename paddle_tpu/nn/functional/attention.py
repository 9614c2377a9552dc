"""Attention functional ops.

``flash_attention`` mirrors the reference's API
(python/paddle/nn/functional/flash_attention.py over
paddle/phi/kernels/gpu/flash_attn_kernel.cu) and routes to the Pallas flash
kernel (paddle_tpu/ops/pallas/flash_attention.py) when shapes are MXU-tile
aligned on TPU, else to an XLA-fused naive composite (still O(S^2) memory —
the kernel is the memory win).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...amp import amp_cast
from ...framework.flags import get_flags
from ...framework.tensor import Tensor, apply_op

__all__ = ["scaled_dot_product_attention", "flash_attention", "naive_attention"]


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(x)


def naive_attention(q, k, v, causal=False, scale=None, bias=None):
    """Pure-jax reference attention on [B, S, H, D] arrays (paddle layout)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d**0.5)
    # [B,S,H,D] -> [B,H,S,D]
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) * s
    if bias is not None:
        logits = logits + bias
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(qt.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, training=True, name=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle flash_attention layout).

    Returns (out, softmax_lse_placeholder) like the reference API; the second
    element is None unless return_softmax (discouraged — defeats the fusion).
    """
    q, k, v = amp_cast("attention", _t(query), _t(key), _t(value))
    use_pallas = bool(get_flags("FLAGS_use_flash_attention")["FLAGS_use_flash_attention"])

    def fn(qa, ka, va):
        if use_pallas and _pallas_ok(qa, ka):
            from ...ops.pallas.flash_attention import flash_attention_fused
            from ...ops.pallas.sharded import per_shard

            # [B, S, H, D]: batch rows and heads are independent
            return per_shard(
                lambda q, k, v: flash_attention_fused(q, k, v,
                                                      causal=causal),
                [qa, ka, va], dims=[(0, 2)] * 3, out_dims=(0, 2),
                out_ndim=4)
        return naive_attention(qa, ka, va, causal=causal)

    out = apply_op(fn, q, k, v)
    if dropout > 0.0 and training:
        from .common import dropout as _dropout

        out = _dropout(out, p=dropout, training=True)
    if return_softmax:
        probs = apply_op(lambda qa, ka: _softmax_probs(qa, ka, causal), q, k)
        return out, probs
    return out, None


def _softmax_probs(qa, ka, causal):
    d = qa.shape[-1]
    qt, kt = jnp.swapaxes(qa, 1, 2), jnp.swapaxes(ka, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) / (d**0.5)
    if causal:
        s = logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
    return jax.nn.softmax(logits, -1)


def _pallas_ok(qa, ka) -> bool:
    if jax.default_backend() != "tpu":
        return False
    _, sq, _, d = qa.shape
    sk = ka.shape[1]
    return sq % 128 == 0 and sk % 128 == 0 and d in (64, 128, 256) and qa.shape[2] == ka.shape[2]


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True):
    """paddle.nn.functional.scaled_dot_product_attention parity
    ([B, S, H, D] layout, mask broadcastable to [B, H, Sq, Sk])."""
    if attn_mask is None:
        out, _ = flash_attention(query, key, value, dropout=dropout_p, causal=is_causal,
                                 training=training)
        return out
    q, k, v = amp_cast("attention", _t(query), _t(key), _t(value))
    mask = attn_mask._data if isinstance(attn_mask, Tensor) else jnp.asarray(attn_mask)

    def fn(qa, ka, va):
        bias = mask if mask.dtype != jnp.bool_ else jnp.where(mask, 0.0, -jnp.inf)
        return naive_attention(qa, ka, va, causal=is_causal, bias=bias)

    out = apply_op(fn, q, k, v)
    if dropout_p > 0.0 and training:
        from .common import dropout as _dropout

        out = _dropout(out, p=dropout_p, training=True)
    return out
