"""Context parallelism: ring attention + Ulysses (DeepSpeed-style) all-to-all
attention over the ``sep`` mesh axis.

Reference parity (SURVEY.md C10/C11, §5.7): upstream Paddle ≤2.6 has the
``sep`` topology axis in fleet/base/topology.py but ring attention itself
lives in PaddleNLP (``ring_flash_attention.py`` — isend/irecv KV rotation +
online-softmax merge). The TPU-native build makes long context first-class:

* :func:`ring_attention` — blockwise attention under ``shard_map``: Q stays
  put, K/V blocks rotate around the ICI ring via ``lax.ppermute``, partial
  results merge with the online-softmax recurrence (running max / running
  denominator). Differentiable (jax transposes the ring), causal-correct for
  ANY sequence layout because masking is driven by explicit global position
  indices that rotate with K/V — which makes zig-zag load balancing a pure
  layout choice (:func:`zigzag_indices`).
* :func:`ulysses_attention` — all-to-all head↔seq swap around a local full
  attention (DeepSpeed-Ulysses): seq-sharded activations become head-sharded
  for exact attention, then swap back. Head count must divide the sep degree.

Both run inside jit on the hybrid mesh; other axes (dp/mp/…) stay in GSPMD
"auto" mode, so these compose with TP/DP/pipeline.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...jax_compat import shard_map as compat_shard_map

__all__ = [
    "ring_attention",
    "ring_attention_op",
    "ulysses_attention",
    "zigzag_indices",
    "RingAttention",
]


@functools.lru_cache(maxsize=64)
def _jitted(mapped):
    return jax.jit(mapped)


def _run_maybe_jit(mapped, *args):
    """Partial-manual shard_map only lowers under jit. Route every call
    through a cached jit — correct both eagerly and inside an enclosing
    trace (jit inlines as a pjit call). ``mapped`` must come from the
    lru-cached builders below so its identity is stable across calls."""
    return _jitted(mapped)(*args)


@functools.lru_cache(maxsize=64)
def _ring_mapped(mesh, axis_name: str, causal: bool, scale: float,
                 impl: str = "flash"):
    seq_spec = P(None, axis_name, None, None)
    pos_spec = P(axis_name)
    body = functools.partial(
        _ring_body_flash if impl == "flash" else _ring_body,
        axis_name=axis_name, causal=causal, scale=scale,
    )
    return compat_shard_map(
        body, mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, pos_spec, pos_spec),
        out_specs=seq_spec,
        axis_names={axis_name},
    )


def _online_merge(m, l, o, m_new, l_new, o_new):
    """Merge two partial softmax results (FlashAttention recurrence).
    -inf running maxima (fully-masked rows) are kept exp-safe."""
    m_next = jnp.maximum(m, m_new)
    m_ref = jnp.where(jnp.isfinite(m_next), m_next, 0.0)
    a = jnp.where(jnp.isfinite(m), jnp.exp(m - m_ref), 0.0)
    b = jnp.where(jnp.isfinite(m_new), jnp.exp(m_new - m_ref), 0.0)
    l_next = a * l + b * l_new
    o_next = a[..., None] * o + b[..., None] * o_new
    return m_next, l_next, o_next


def _block_attend(q, k, v, scale, mask):
    """One Q-block × KV-block partial attention; returns (m, l, o) stats.

    q [B,Sq,H,D], k/v [B,Sk,H,D], mask [Sq,Sk] boolean (True = attend)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    # guard fully-masked rows (m = -inf): exp(-inf - -inf) -> use safe m
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bhqd", p, v)  # [B,H,Sq,D]
    m = jnp.where(jnp.isfinite(m), m, -jnp.inf)
    return m, l, o


def _ring_drive(k, v, kv_pos, axis_name, attend, merge):
    """Shared ring-rotation protocol: attend to the local KV chunk, then
    ``world−1`` × (rotate K/V/positions one hop via ``lax.ppermute``;
    attend; merge).  ``attend(k_c, v_c, kv_pos_c) -> partial`` and
    ``merge(acc, partial) -> acc`` define the per-impl math; jax transposes
    the ring for gradients."""
    world = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % world) for i in range(world)]
    acc = attend(k, v, kv_pos)

    def step(carry, _):
        acc, k_c, v_c, kv_pos_c = carry
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        kv_pos_c = jax.lax.ppermute(kv_pos_c, axis_name, perm)
        acc = merge(acc, attend(k_c, v_c, kv_pos_c))
        return (acc, k_c, v_c, kv_pos_c), None

    if world > 1:
        (acc, _, _, _), _ = jax.lax.scan(
            step, (acc, k, v, kv_pos), None, length=world - 1
        )
    return acc


def _ring_body(q, k, v, q_pos, kv_pos, *, axis_name, causal, scale):
    """Materialized-logits ("xla") ring impl: per-chunk (m, l, o) running
    stats merged with the online-softmax recurrence.  Stats and accumulator
    are float32 regardless of input dtype (flash-attention convention —
    bf16 recurrence over many ring steps compounds rounding)."""
    in_dtype = q.dtype
    qf = q.astype(jnp.float32)

    def attend(k_c, v_c, kv_pos_c):
        if causal:
            mask = q_pos[:, None] >= kv_pos_c[None, :]
        else:
            mask = jnp.ones((q.shape[1], k_c.shape[1]), bool)
        return _block_attend(
            qf, k_c.astype(jnp.float32), v_c.astype(jnp.float32), scale, mask
        )

    def merge(acc, part):
        return _online_merge(*acc, *part)

    m, l, o = _ring_drive(k, v, kv_pos, axis_name, attend, merge)
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).astype(in_dtype)  # [B,H,Sq,D]
    return jnp.transpose(out, (0, 2, 1, 3))  # [B,Sq,H,D]


def _flash_chunk(q, k, v, q_pos, kv_pos, causal, scale):
    """One ring step's Q-chunk × KV-chunk attention through the Pallas flash
    kernel (joint (out, lse) custom_vjp — VERDICT r1 #4: the inner block
    attend must be the flash kernel, not materialized jnp logits)."""
    from ....ops.pallas.flash_attention import flash_attention_with_lse

    # tpulint: disable=TPL301 -- `causal` is a static python bool selecting
    # the kernel variant at trace time, never a traced value
    if causal:
        out, lse = flash_attention_with_lse(
            q, k, v, scale=scale, q_positions=q_pos, kv_positions=kv_pos
        )
    else:
        out, lse = flash_attention_with_lse(q, k, v, causal=False, scale=scale)
    return out.astype(jnp.float32), lse  # [B,S,H,D] f32, [B,H,S] f32


def _lse_merge(o, lse, o_new, lse_new):
    """Merge two normalized partial attention results via their lse stats.
    Fully-masked chunks carry lse ≈ -1e30 and o = 0, which this treats as
    zero weight (and when BOTH sides are masked, o stays 0)."""
    lse_next = jnp.logaddexp(lse, lse_new)
    aw = jnp.swapaxes(jnp.exp(lse - lse_next), 1, 2)[..., None]  # [B,S,H,1]
    bw = jnp.swapaxes(jnp.exp(lse_new - lse_next), 1, 2)[..., None]
    return aw * o + bw * o_new, lse_next


def _ring_body_flash(q, k, v, q_pos, kv_pos, *, axis_name, causal, scale):
    """Flash-kernel-backed ring impl: per-chunk (out, lse) through the
    Pallas flash kernel, merged in log-space.  Gradients flow through the
    flash custom_vjp (the lse cotangent re-enters its bwd kernels)."""
    in_dtype = q.dtype

    def attend(k_c, v_c, kv_pos_c):
        return _flash_chunk(q, k_c, v_c, q_pos, kv_pos_c, causal, scale)

    def merge(acc, part):
        return _lse_merge(*acc, *part)

    o, _ = _ring_drive(k, v, kv_pos, axis_name, attend, merge)
    return o.astype(in_dtype)  # [B,Sq,H,D]


def ring_attention(q, k, v, *, mesh=None, axis_name: str = "sep",
                   causal: bool = False, scale: Optional[float] = None,
                   q_positions=None, kv_positions=None, impl: str = "flash"):
    """Blockwise ring attention over ``axis_name`` (SURVEY.md C11).

    ``q``/``k``/``v``: [batch, seq, heads, head_dim] GLOBAL arrays whose seq
    dim is (or will be) sharded over ``axis_name``. ``*_positions``: global
    token index of every position ([seq] int32) — defaults to ``arange``;
    pass :func:`zigzag_indices` output for load-balanced causal rings.
    ``impl``: "flash" (default — Pallas flash kernel per chunk, (out, lse)
    log-space merge) or "xla" (materialized-logits reference path).
    """
    from ...parallel import get_mesh

    mesh = mesh or get_mesh()
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}")
    world = mesh.shape[axis_name]
    B, S, H, D = q.shape
    if S % world:
        raise ValueError(f"seq {S} not divisible by {axis_name}={world}")
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))
    if q_positions is None:
        q_positions = jnp.arange(S, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1], dtype=jnp.int32)

    mapped = _ring_mapped(mesh, axis_name, bool(causal), scale, impl)
    return _run_maybe_jit(mapped, q, k, v, q_positions, kv_positions)


def zigzag_indices(seq_len: int, world: int) -> np.ndarray:
    """Zig-zag chunk assignment for causal load balance: split the sequence
    into ``2·world`` chunks; rank i gets chunks ``(i, 2·world−1−i)`` so every
    rank sees the same causal-mask work (the PaddleNLP/Megatron-CP layout).

    Returns ``perm`` with ``reordered = x[:, perm]``; position arrays for
    :func:`ring_attention` are just ``perm`` itself (global index of each
    reordered slot). Invert with ``argsort(perm)``.
    """
    if seq_len % (2 * world):
        raise ValueError(f"seq {seq_len} must divide by 2*world={2*world}")
    chunk = seq_len // (2 * world)
    order = []
    for r in range(world):
        order.extend(range(r * chunk, (r + 1) * chunk))
        hi = 2 * world - 1 - r
        order.extend(range(hi * chunk, (hi + 1) * chunk))
    return np.asarray(order, dtype=np.int32)


def _a2a(x, axis_name, split_axis, concat_axis):
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )


def ulysses_attention(q, k, v, *, mesh=None, axis_name: str = "sep",
                      causal: bool = False, scale: Optional[float] = None,
                      attn_fn=None):
    """DeepSpeed-Ulysses attention (SURVEY.md C10): all-to-all swaps the
    sharded dim from seq to heads, runs EXACT local attention on full
    sequences, and swaps back. ``heads`` must be divisible by the sep degree.

    ``attn_fn(q, k, v, causal, scale)`` defaults to plain softmax attention;
    pass the Pallas flash kernel for long sequences.
    """
    from ...parallel import get_mesh

    mesh = mesh or get_mesh()
    world = mesh.shape[axis_name]
    B, S, H, D = q.shape
    if H % world:
        raise ValueError(f"heads {H} not divisible by {axis_name}={world}")
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))

    mapped = _ulysses_mapped(mesh, axis_name, bool(causal), scale, attn_fn)
    return _run_maybe_jit(mapped, q, k, v)


def _default_attn(q, k, v, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), Sk - Sq)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@functools.lru_cache(maxsize=64)
def _ulysses_mapped(mesh, axis_name: str, causal: bool, scale: float,
                    attn_fn=None):
    attn = attn_fn or _default_attn

    def body(q, k, v):
        # local [B, S/P, H, D] → [B, S, H/P, D]
        q = _a2a(q, axis_name, 2, 1)
        k = _a2a(k, axis_name, 2, 1)
        v = _a2a(v, axis_name, 2, 1)
        o = attn(q, k, v, causal, scale)
        return _a2a(o, axis_name, 1, 2)  # back to seq-sharded

    seq_spec = P(None, axis_name, None, None)
    return compat_shard_map(
        body, mesh, in_specs=(seq_spec,) * 3, out_specs=seq_spec,
        axis_names={axis_name},
    )


def ring_attention_op(q, k, v, **kw):
    """Tensor-level ring attention: records ONE tape node so eager
    ``loss.backward()`` differentiates through the ring (repo convention:
    framework.tensor.apply_op)."""
    from ....framework.tensor import apply_op

    return apply_op(lambda qa, ka, va: ring_attention(qa, ka, va, **kw),
                    q, k, v)


class RingAttention:
    """Thin layer-style wrapper for :func:`ring_attention` (keeps the
    incubate fused-layer calling convention)."""

    def __init__(self, axis_name: str = "sep", causal: bool = True):
        self.axis_name = axis_name
        self.causal = causal

    def __call__(self, q, k, v, **kw):
        return ring_attention_op(
            q, k, v, axis_name=self.axis_name, causal=self.causal, **kw
        )
