"""Pallas decode attention with KV cache (generation hot loop).

TPU-native equivalent of the reference's masked_multihead_attention CUDA
kernel (paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu;
invoked per-layer by fused_multi_transformer_op.cu in decode phase, one CTA
per (batch, head)). Here: one Pallas grid instance per (batch, head) reading
that head's whole cache row from HBM into VMEM, masking positions beyond the
batch element's current length (scalar-prefetched), and producing one output
row. Logits/softmax in fp32; the QK^T and PV contractions are MXU dots.

Layouts
  q               [B, H, D]        — the single new token's heads
  k_cache/v_cache [B, H, S, D]     — S = max_seq (static), cache layout
                                     matching the reference's
                                     [2, bsz, nh, max_seq, dh] split in two
  lengths         [B] int32        — valid entries INCLUDING the new token
                                     (already written at lengths-1)

GQA: H_kv may divide H; q head h reads kv head h // (H // H_kv).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
_Q_ROWS = 8  # pad the single q row to a full sublane tile


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale, max_seq):
    b = pl.program_id(0)
    length = len_ref[b]

    q = q_ref[0].astype(jnp.float32)  # [_Q_ROWS, D] (row 0 is real)
    k = k_ref[0, 0]  # [S, D]
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [_Q_ROWS, S]

    ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ids < length, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / jnp.maximum(l, 1e-37)  # [_Q_ROWS, D]
    o_ref[0] = out.astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, lengths, scale=None):
    """q [B,H,D], caches [B,Hkv,S,D], lengths [B] → [B,H,D]."""
    b, h, d = q.shape
    h_kv, s_max = k_cache.shape[1], k_cache.shape[2]
    group = h // h_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    dpad = (128 - d % 128) % 128
    spad = (8 - s_max % 8) % 8
    if dpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dpad)))
    if dpad or spad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, spad), (0, dpad)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, spad), (0, dpad)))
    dp = d + dpad

    # [B,H,D] -> [B*H, _Q_ROWS, D] with the real row broadcast (row 0 used)
    qr = jnp.broadcast_to(q.reshape(b * h, 1, dp), (b * h, _Q_ROWS, dp))

    grid = (b, h)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, max_seq=s_max),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, _Q_ROWS, dp),
                             lambda i, j, lens: (i * h + j, 0, 0)),
                pl.BlockSpec((1, 1, s_max + spad, dp),
                             lambda i, j, lens: (i, j // group, 0, 0)),
                pl.BlockSpec((1, 1, s_max + spad, dp),
                             lambda i, j, lens: (i, j // group, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, _Q_ROWS, dp),
                                   lambda i, j, lens: (i * h + j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, _Q_ROWS, dp), q.dtype),
        interpret=_interpret(),
        name="decode_attention",
    )(jnp.asarray(lengths, jnp.int32), qr, k_cache, v_cache)
    return out[:, 0, :d].reshape(b, h, d)


def decode_attention_ref(q, k_cache, v_cache, lengths, scale=None):
    """Batched-matvec decode attention in plain XLA — and the DEFAULT TPU
    path: at decode shapes the work per (batch, head) is a [1, S]x[S, D]
    matvec, so the Pallas kernel's per-program cost dominates (measured
    v5e, B=8 H=12 S=1024 D=64 bf16 cache: 0.081 ms here vs 0.125 ms for
    the kernel). GQA is grouped via reshape — no jnp.repeat
    materialization of the expanded cache."""
    b, h, d = q.shape
    h_kv, s_max = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    group = h // h_kv
    qg = q.reshape(b, h_kv, group, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg,
                   k_cache.astype(jnp.float32)) * scale
    ids = jnp.arange(s_max)[None, None, None, :]
    s = jnp.where(ids < jnp.asarray(lengths)[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _decode_dispatch(q, k_cache, v_cache, lengths, scale):
    from ...framework.flags import get_flags

    if (jax.default_backend() == "tpu"
            and get_flags("FLAGS_decode_attention_kernel")[
                "FLAGS_decode_attention_kernel"]):
        return decode_attention_pallas(q, k_cache, v_cache, lengths, scale)
    return decode_attention_ref(q, k_cache, v_cache, lengths, scale)


def _decode_fwd(q, k_cache, v_cache, lengths, scale):
    return _decode_dispatch(q, k_cache, v_cache, lengths, scale), (q, k_cache, v_cache, lengths)


def _decode_bwd(scale, res, g):
    # gradient through the differentiable jnp twin — decode attention is an
    # inference kernel, so bwd is a rarely-hit correctness fallback, not a
    # perf path (training uses the flash kernel's fused bwd)
    q, k_cache, v_cache, lengths = res
    _, vjp = jax.vjp(lambda a, b, c: decode_attention_ref(a, b, c, lengths, scale),
                     q, k_cache, v_cache)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_decode_dispatch.defvjp(_decode_fwd, _decode_bwd)


def decode_attention(q, k_cache, v_cache, lengths, scale=None):
    """Dispatch: Pallas on TPU, reference math elsewhere (interpret mode is
    exact but slow; eager CPU tests use the jnp twin directly).
    Differentiable: bwd routes through the jnp twin via custom_vjp."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _decode_dispatch(q, k_cache, v_cache, jnp.asarray(lengths), scale)


# --------------------------------------------------- slab decode kernel
# The serving-loop fast path. The cache is ONE array [2, B, S, Hkv*D]:
# its minor dimension (Hkv*D, a multiple of 128 for real configs) takes an
# unpadded tiled layout — the reference-parity [2,B,H,S,D] layout has a
# 64-wide minor that XLA pads 2x (T(8,128)), and inside the decode scan the
# in-place update + padded re-layout cost ~0.13 ms/(layer*token) at GPT-2
# scale where the pure bandwidth floor is ~0.03 ms. One program per batch
# element keeps per-program overhead off the critical path (the per-(b,h)
# kernel above pays ~0.5 us x B*H programs).


# ----------------------------------------- windowed online softmax (shared)
# The slab kernels (this file's contiguous one, paged_attention's decode and
# verify ones) score a sequence one WINDOW of tokens at a time and carry the
# softmax across windows in VMEM scratch: a whole sequence resident at once
# does not fit fast memory at serving widths (llama2_7b: 4096 lanes x 2048
# tokens = 16 MiB for K and again for V, against a 16 MiB default budget).
# A sequence short enough for one window takes one step, and the arithmetic
# is then what the whole-resident kernels did. The window is sized so that
# K and V of one window hold _WINDOW_BYTES; this makes the kernels compile,
# it is not tuned.
_WINDOW_BYTES = 8 << 20


def _stat_lanes(num_heads: int) -> int:
    """Lanes of the running max / denominator scratch: one column a head."""
    return -(-num_heads // 128) * 128


def _softmax_scratch(rows: int, num_heads: int, head_dim: int):
    """(m, l, acc) VMEM scratch carried across the windows of a sequence."""
    return [pltpu.VMEM((rows, _stat_lanes(num_heads)), jnp.float32),
            pltpu.VMEM((rows, _stat_lanes(num_heads)), jnp.float32),
            pltpu.VMEM((rows, num_heads * head_dim), jnp.float32)]


def _softmax_init(m_sc, l_sc, acc_sc):
    m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)


def _softmax_window(q_ref, k_of, v_of, mask, m_sc, l_sc, acc_sc, *, scale,
                    num_heads, head_dim, group):
    """Fold one window into the running softmax of every head.

    ``q_ref`` [1, R, H*D]; ``k_of(kv_head)`` / ``v_of(kv_head)`` return that
    head's [W, D] f32 window; ``mask`` [R, W] marks the live columns. Per
    64/128-lane head slices, like the whole-resident kernels before: a
    full-lane-width formulation multiplies every head against ALL kv lanes."""
    for h in range(num_heads):
        lo = h * head_dim
        qh = q_ref[0, :, lo:lo + head_dim].astype(jnp.float32)  # [R, D]
        s = jax.lax.dot_general(
            qh, k_of(h // group), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [R, W]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[:, h:h + 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row can be fully masked in this window (with m still at NEG_INF
        # exp(s - m) would be 1 there) — guard
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        l_sc[:, h:h + 1] = alpha * l_sc[:, h:h + 1] + jnp.sum(
            p, axis=-1, keepdims=True)
        m_sc[:, h:h + 1] = m_new
        acc_sc[:, lo:lo + head_dim] = (
            acc_sc[:, lo:lo + head_dim] * alpha + jax.lax.dot_general(
                p, v_of(h // group), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))


def _softmax_finish(o_ref, l_sc, acc_sc, *, num_heads, head_dim):
    for h in range(num_heads):
        lo = h * head_dim
        o_ref[0, :, lo:lo + head_dim] = (
            acc_sc[:, lo:lo + head_dim]
            / jnp.maximum(l_sc[:, h:h + 1], 1e-37)).astype(o_ref.dtype)


def _slab_kernel(len_ref, q_ref, kv_ref, o_ref, m_sc, l_sc, acc_sc, *, scale,
                 num_heads, head_dim, window):
    b = pl.program_id(0)
    w = pl.program_id(1)
    length = len_ref[b]
    h_kv = kv_ref.shape[-1] // head_dim

    @pl.when(w == 0)
    def _init():
        _softmax_init(m_sc, l_sc, acc_sc)

    # windows wholly past the sequence contribute nothing: skip their math
    # (their block was still fetched by the pipeline)
    @pl.when(w * window < length)
    def _window():
        ids = w * window + jax.lax.broadcasted_iota(
            jnp.int32, (_Q_ROWS, window), 1)

        def part(which):
            return lambda kh: kv_ref[
                which, 0, :, kh * head_dim:(kh + 1) * head_dim].astype(
                    jnp.float32)  # [W, D]

        _softmax_window(q_ref, part(0), part(1), ids < length, m_sc, l_sc,
                        acc_sc, scale=scale, num_heads=num_heads,
                        head_dim=head_dim, group=num_heads // h_kv)

    @pl.when(w == pl.num_programs(1) - 1)
    def _finish():
        _softmax_finish(o_ref, l_sc, acc_sc, num_heads=num_heads,
                        head_dim=head_dim)


def _slab_ref(q, kv_slab, lengths, scale):
    """Differentiable jnp twin of the slab kernel (CPU path + VJP route)."""
    b, h, d = q.shape
    s_max = kv_slab.shape[2]
    h_kv = kv_slab.shape[-1] // d
    kv = kv_slab.reshape(2, b, s_max, h_kv, d).transpose(0, 1, 3, 2, 4)
    return decode_attention_ref(q, kv[0], kv[1], lengths, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _slab_dispatch(q, kv_slab, lengths, scale):
    if _interpret() or kv_slab.shape[-1] % 128:
        return _slab_ref(q, kv_slab, lengths, scale)
    return _slab_pallas(q, kv_slab, lengths, scale)


def _slab_fwd(q, kv_slab, lengths, scale):
    return _slab_dispatch(q, kv_slab, lengths, scale), (q, kv_slab, lengths)


def _slab_bwd(scale, res, g):
    q, kv_slab, lengths = res
    _, vjp = jax.vjp(lambda a, b: _slab_ref(a, b, lengths, scale), q, kv_slab)
    dq, dkv = vjp(g)
    return dq, dkv, None


_slab_dispatch.defvjp(_slab_fwd, _slab_bwd)


def decode_attention_slab(q, kv_slab, lengths, scale=None):
    """q [B, H, D], kv_slab [2, B, S, Hkv*D], lengths [B] → [B, H, D]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _slab_dispatch(q, kv_slab, jnp.asarray(lengths), scale)


def _slab_window(s_max: int, lanes: int, itemsize: int) -> int:
    """Tokens of the slab resident at once: the whole sequence when its K
    and V fit _WINDOW_BYTES, else the largest divisor of ``s_max`` that
    does and keeps blocks sublane-tile aligned (a ragged last block would
    read past the slab, and 0 * garbage is not 0)."""
    # the pipeline double-buffers the block: half the budget a buffer
    fit = _WINDOW_BYTES // 2 // (2 * lanes * itemsize)
    # tpulint: disable=TPL301 -- s_max/lanes/itemsize are static python
    # ints (array shapes at pallas_call build time), not traced values
    if s_max <= fit:
        return s_max
    tile = 32 // itemsize  # sublanes of one packed tile: 8 f32, 16 bf16
    for w in range(fit - fit % tile, 0, -tile):
        # tpulint: disable=TPL301 -- same static shape arithmetic
        if s_max % w == 0:
            return w
    raise ValueError(
        f"decode_attention_slab: no {tile}-aligned window of at most {fit} "
        f"tokens divides max_seq={s_max} (lanes={lanes}); allocate the "
        "cache with a max_seq that has such a divisor")


def _slab_pallas(q, kv_slab, lengths, scale):
    b, h, d = q.shape
    s_max, lanes = kv_slab.shape[2], kv_slab.shape[3]
    window = _slab_window(s_max, lanes, jnp.dtype(kv_slab.dtype).itemsize)
    qr = jnp.broadcast_to(q.reshape(b, 1, h * d), (b, _Q_ROWS, h * d))
    out = pl.pallas_call(
        functools.partial(_slab_kernel, scale=scale, num_heads=h,
                          head_dim=d, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s_max // window),
            in_specs=[
                pl.BlockSpec((1, _Q_ROWS, h * d),
                             lambda i, w, lens: (i, 0, 0)),
                pl.BlockSpec((2, 1, window, lanes),
                             lambda i, w, lens: (0, i, w, 0)),
            ],
            out_specs=pl.BlockSpec((1, _Q_ROWS, h * d),
                                   lambda i, w, lens: (i, 0, 0)),
            scratch_shapes=_softmax_scratch(_Q_ROWS, h, d),
        ),
        out_shape=jax.ShapeDtypeStruct((b, _Q_ROWS, h * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="decode_attention_slab",
    )(jnp.asarray(lengths, jnp.int32), qr, kv_slab)
    return out[:, 0].reshape(b, h, d)


# ------------------------------------------------- shared cache plumbing
# One implementation of the cache write/step dataflow, used by both the GPT
# model family and the incubate FusedMultiTransformer (review: keep the two
# decode paths from diverging). Layout-polymorphic: 4-D caches are the fast
# slab layout [2, B, S, Hkv*D] (what model init_caches now allocates); 5-D
# caches are the reference layout [2, B, Hkv, S, D]
# (fused_multi_transformer_op.cu convention), kept for API parity with
# user-allocated caches (e.g. masked_multihead_attention).


def make_kv_slab(batch, max_seq, num_kv_heads, head_dim, dtype=jnp.float32):
    return jnp.zeros((2, batch, max_seq, num_kv_heads * head_dim), dtype)


def cache_prefill_write(cache, k, v):
    """Write prompt k/v ([b,s,nh,hd]) into the cache at positions [0, s)."""
    if cache.ndim == 4:  # slab [2,B,S,Hkv*D]
        b, s = k.shape[0], k.shape[1]
        upd = jnp.stack([k.reshape(b, s, -1), v.reshape(b, s, -1)])
        return jax.lax.dynamic_update_slice(cache, upd.astype(cache.dtype),
                                            (0, 0, 0, 0))
    upd = jnp.stack([jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)])
    return jax.lax.dynamic_update_slice(cache, upd.astype(cache.dtype),
                                        (0, 0, 0, 0, 0))


def cache_decode_step(cache, q, k, v, time_step, scale=None):
    """Append one token's k/v ([b,1,nh,hd]) at ``time_step`` and attend q
    over the cache. Returns (out [b,1,nh,hd], new_cache)."""
    ts = jnp.asarray(time_step, jnp.int32).reshape(())
    b = q.shape[0]
    lengths = jnp.full((b,), ts + 1, jnp.int32)
    qh = jnp.swapaxes(q, 1, 2)[:, :, 0]  # [b,nh,hd]
    if cache.ndim == 4:  # slab layout
        upd = jnp.stack([k.reshape(b, 1, -1), v.reshape(b, 1, -1)])
        cache = jax.lax.dynamic_update_slice(cache, upd.astype(cache.dtype),
                                             (0, 0, ts, 0))
        out = decode_attention_slab(qh, cache, lengths, scale)
        return out[:, None], cache
    upd = jnp.stack([jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)])
    cache = jax.lax.dynamic_update_slice(cache, upd.astype(cache.dtype),
                                         (0, 0, 0, ts, 0))
    out = decode_attention(qh, cache[0], cache[1], lengths, scale)
    return out[:, None], cache
