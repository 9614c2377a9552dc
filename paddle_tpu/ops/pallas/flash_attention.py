"""Pallas flash attention (FlashAttention-2 style), fwd + bwd.

Replaces the reference's external flash-attn CUDA library
(paddle/phi/kernels/gpu/flash_attn_kernel.cu + cmake/external/flashattn.cmake)
with a TPU-native tiled online-softmax kernel:

* fwd: grid (batch*heads, q_blocks, kv_blocks), kv innermost; VMEM scratch
  carries running max m, normalizer l, and the output accumulator across the
  kv loop; logits/accum in fp32 on the MXU (q/k/v may be bf16).
* bwd: FlashAttention-2 recompute scheme — delta = rowsum(dO*O) precomputed
  in XLA, then one kernel accumulating dK/dV over the q loop and one
  accumulating dQ over the kv loop, both re-forming P from (q,k,lse).

Layout: [B, S, H, D] (paddle flash_attention layout) is transposed to
[B*H, S, D] outside the kernel. Tiles are 128×128 (MXU native); D must be a
multiple of 128 lanes handled by padding at the wrapper level if needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
# None → adaptive (see flash_attention_fused): whole-sequence tiles up to
# 1024 when they fit, else 512/1024 blocked. Measured on v5e, GPT-2 S=1024:
# 128/128 tiles 20.0% train MFU → adaptive 46.7%.
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------- fwd


def _mask_logits(s, *, causal, kv_valid, block_q, block_k, iq, ik, pos=None):
    """Apply causal and/or kv-padding validity masks. ``kv_valid`` is the
    original (unpadded) kv length, or None when no padding was added.
    ``pos`` — optional ``(q_ids [bq,1], k_ids [1,bk])`` float32 global token
    positions; when given, the mask is ``q_ids >= k_ids`` (position-driven
    causality — what ring attention with zig-zag layouts needs) and the iota
    paths are skipped (padding is handled by sentinel positions)."""
    if pos is not None:
        q_ids, k_ids = pos
        return jnp.where(q_ids >= k_ids, s, NEG_INF)
    if not causal and kv_valid is None:
        return s
    k_ids = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    keep = jnp.ones(s.shape, jnp.bool_)
    if causal:
        q_ids = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        keep = jnp.logical_and(keep, q_ids >= k_ids)
    if kv_valid is not None:
        keep = jnp.logical_and(keep, k_ids < kv_valid)
    return jnp.where(keep, s, NEG_INF)


def _guard_p(s, p):
    """Zero attention weights at masked logits. Only needed in position-mask
    mode, where rows can be FULLY masked (ring-attention blocks whose whole
    q chunk precedes the kv chunk): there m/lse sit at ~NEG_INF, so
    ``exp(s - m)`` would be exp(0)=1 at masked entries. In plain causal mode
    every row attends column 0, so m/lse are always finite and masked
    entries exp to 0 on their own. Real logits never approach NEG_INF/2."""
    return jnp.where(s > NEG_INF * 0.5, p, 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, kv_valid,
                block_q, block_k, num_kv, pos_mask):
    if pos_mask:
        qp_ref, kp_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        qp_ref, kp_ref = None, None
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    iq = pl.program_id(1)
    # causal block skip: kv blocks entirely above the diagonal contribute
    # nothing — skip their compute (the ~2x triangular win); their DMA is
    # cheap relative to the dots
    live = jnp.logical_or(jnp.logical_not(causal),
                          ik * block_k < (iq + 1) * block_q)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        pos = (qp_ref[...], kp_ref[...]) if pos_mask else None
        s = _mask_logits(s, causal=causal, kv_valid=kv_valid, block_q=block_q,
                         block_k=block_k, iq=iq, ik=ik, pos=pos)

        m_prev = m_s[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # keep m at NEG_INF while every block so far is fully masked, so the
        # final lse of such rows is ~NEG_INF (≈ -inf), which the online merge
        # in ring attention relies on
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        p = jnp.exp(s - m_new)  # [bq, bk]
        if pos_mask:
            p = _guard_p(s, p)
        l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ik == num_kv - 1)
    def _finish():
        # max-guard keeps padded q rows (l==0) finite; they are sliced off
        # by the wrapper and their cotangents are zero in bwd
        l = jnp.maximum(l_s[:, :1], 1e-37)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_s[:] + jnp.log(jnp.maximum(l_s[:], 1e-37))).astype(jnp.float32)


def _fwd(q, k, v, qp=None, kp=None, *, scale, causal, kv_valid, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    grid = (bh, nq, nk)
    pos_mask = qp is not None
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, kv_valid=kv_valid,
        block_q=block_q, block_k=block_k, num_kv=nk, pos_mask=pos_mask,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    inputs = [q, k, v]
    if pos_mask:
        in_specs += [
            pl.BlockSpec((block_q, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((1, block_k), lambda b, i, j: (0, j)),
        ]
        inputs += [qp, kp]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(*inputs)
    return out, lse[:, :, :1]  # lse [bh, sq, 1]


# --------------------------------------------------------------------- bwd


def fused_bwd_math(q, k, v, out, do, lse_col, *, scale, causal, kv_valid):
    """Whole-sequence fused backward math on 2-D [S, D] operands, the body
    of this module's _bwd_fused_kernel. The logits are re-formed ONCE (the
    split dkv/dq kernel pair re-forms them twice), delta = rowsum(dO*O) is
    computed in-kernel
    (no [bh,sq,128] broadcast operands), and the five dots run in the input
    dtype (bf16 on the train path) with fp32 accumulation — fp32 MXU dots
    run at a fraction of bf16 rate, which made the old bwd the dominant
    attention cost. Returns (dq, dk, dv) in fp32."""
    sq, sk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask_logits(s, causal=causal, kv_valid=kv_valid, block_q=sq,
                     block_k=sk, iq=0, ik=0)
    p = jnp.exp(s - lse_col)  # masked entries: exp(NEG_INF - finite) == 0
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [sq, 1]
    mxu = q.dtype
    # dV = P^T @ dO
    dv = jax.lax.dot_general(p.astype(mxu), do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # dP = dO @ V^T ; dS = P * (dP - delta)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - delta)).astype(mxu)
    # dK = dS^T @ Q * scale ; dQ = dS @ K * scale
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    return dq, dk, dv


def _bwd_fused_kernel(q_ref, k_ref, v_ref, out_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, causal, kv_valid,
                      sq, sk):
    # lse arrives as a [1, 1, sq] row; re-layout to a [sq, 1] column
    lse_col = jnp.transpose(lse_ref[0], (1, 0))
    dq, dk, dv = fused_bwd_math(
        q_ref[0], k_ref[0], v_ref[0], out_ref[0], do_ref[0], lse_col,
        scale=scale, causal=causal, kv_valid=kv_valid)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_fused(scale, causal, kv_valid, res, do):
    """Fused whole-seq backward dispatch; caller guarantees sq·sk fits one
    program's VMEM budget (see _FUSED_BWD_MAX_SEQ)."""
    q, k, v, out, lse, _, _ = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    lse2d = lse[:, :, 0][:, None, :]  # [bh, 1, sq] f32 (TPU-tileable row)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          kv_valid=kv_valid, sq=sq, sk=sk),
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=_interpret(),
        name="flash_attention_bwd",
    )(q, k, v, out, do, lse2d)
    return dq, dk, dv


# whole-seq fused bwd needs the [sq, sk] fp32 logits plus bf16 copies
# resident in one program's VMEM; 1024x1024 ≈ 4 MB fp32 comfortably fits,
# 2048 would push ~16 MB per fp32 temporary — stay on the split kernels there
_FUSED_BWD_MAX_SEQ = 1024


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, kv_valid, block_q, block_k, num_q, pos_mask):
    if pos_mask:
        qp_ref, kp_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        qp_ref, kp_ref = None, None
        dk_ref, dv_ref, dk_s, dv_s = rest
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    ik = pl.program_id(1)
    live = jnp.logical_or(jnp.logical_not(causal),
                          ik * block_k < (iq + 1) * block_q)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = (qp_ref[...], kp_ref[...]) if pos_mask else None
        s = _mask_logits(s, causal=causal, kv_valid=kv_valid, block_q=block_q,
                         block_k=block_k, iq=iq, ik=ik, pos=pos)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [bq, bk]
        if pos_mask:
            p = _guard_p(s, p)
        do = do_ref[0]
        mxu = q.dtype  # dots in input dtype (bf16 train path), f32 accum
        # dV += P^T @ dO
        dv_s[:] += jax.lax.dot_general(p.astype(mxu), do,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        # dP = dO @ V^T ; dS = P * (dP - delta)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, :1])).astype(mxu)
        # dK += dS^T @ Q * scale
        dk_s[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32) * scale

    @pl.when(iq == num_q - 1)
    def _finish():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, kv_valid, block_q, block_k, num_kv, pos_mask):
    if pos_mask:
        qp_ref, kp_ref, dq_ref, dq_s = rest
    else:
        qp_ref, kp_ref = None, None
        dq_ref, dq_s = rest
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    iq = pl.program_id(1)
    live = jnp.logical_or(jnp.logical_not(causal),
                          ik * block_k < (iq + 1) * block_q)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = (qp_ref[...], kp_ref[...]) if pos_mask else None
        s = _mask_logits(s, causal=causal, kv_valid=kv_valid, block_q=block_q,
                         block_k=block_k, iq=iq, ik=ik, pos=pos)
        p = jnp.exp(s - lse_ref[0][:, :1])
        if pos_mask:
            p = _guard_p(s, p)
        do = do_ref[0]
        mxu = q.dtype
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, :1])).astype(mxu)
        dq_s[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32) * scale

    @pl.when(ik == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _bwd(scale, causal, kv_valid, block_q, block_k, res, do, dlse=None):
    q, k, v, out, lse, qp, kp = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    if (qp is None and dlse is None and sq == sk
            and sq <= _FUSED_BWD_MAX_SEQ):
        # common train-path shape: one fused program per (batch·head)
        return _bwd_fused(scale, causal, kv_valid, res, do)
    nq, nk = sq // block_q, sk // block_k
    pos_mask = qp is not None
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [bh, sq, 1]
    if dlse is not None:
        # lse cotangent folds into delta: ds = P·(dP − Δ + g) = P·(dP − (Δ − g))
        delta = delta - dlse.astype(jnp.float32)
    lse_b = jnp.broadcast_to(lse, (bh, sq, 128))
    delta_b = jnp.broadcast_to(delta, (bh, sq, 128))

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
    ]
    dkv_inputs = [q, k, v, do, lse_b, delta_b]
    if pos_mask:
        dkv_in_specs += [
            pl.BlockSpec((block_q, 1), lambda b, j, i: (i, 0)),
            pl.BlockSpec((1, block_k), lambda b, j, i: (0, j)),
        ]
        dkv_inputs += [qp, kp]

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          kv_valid=kv_valid, block_q=block_q, block_k=block_k,
                          num_q=nq, pos_mask=pos_mask),
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
    )(*dkv_inputs)
    dk, dv = dkv

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
    ]
    dq_inputs = [q, k, v, do, lse_b, delta_b]
    if pos_mask:
        dq_in_specs += [
            pl.BlockSpec((block_q, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((1, block_k), lambda b, i, j: (0, j)),
        ]
        dq_inputs += [qp, kp]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          kv_valid=kv_valid, block_q=block_q, block_k=block_k,
                          num_kv=nk, pos_mask=pos_mask),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
    )(*dq_inputs)
    return dq, dk, dv


# ------------------------------------------------------------------ public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, scale, causal, kv_valid, block_q, block_k):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal, kv_valid=kv_valid,
                  block_q=block_q, block_k=block_k)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, kv_valid, block_q, block_k):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, kv_valid=kv_valid,
                    block_q=block_q, block_k=block_k)
    return out, (q, k, v, out, lse, None, None)


def _flash_bwd_rule(scale, causal, kv_valid, block_q, block_k, res, do):
    return _bwd(scale, causal, kv_valid, block_q, block_k, res, do)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# Joint (out, lse) variant with optional position-driven masks. The lse
# output is what blockwise/ring attention merges partial results with; its
# cotangent re-enters the same bwd kernels via delta (see _bwd). Positions
# are float32 arrays ([sq,1] / [1,sk]) so custom_vjp can hand back ordinary
# zero cotangents for them; f32 is exact for any realistic token index.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bhsd_lse(q, k, v, qp, kp, scale, causal, kv_valid, block_q, block_k):
    return _fwd(q, k, v, qp, kp, scale=scale, causal=causal,
                kv_valid=kv_valid, block_q=block_q, block_k=block_k)


def _flash_lse_fwd_rule(q, k, v, qp, kp, scale, causal, kv_valid, block_q,
                        block_k):
    out, lse = _fwd(q, k, v, qp, kp, scale=scale, causal=causal,
                    kv_valid=kv_valid, block_q=block_q, block_k=block_k)
    return (out, lse), (q, k, v, out, lse, qp, kp)


def _flash_lse_bwd_rule(scale, causal, kv_valid, block_q, block_k, res, cts):
    do, dlse = cts
    dq, dk, dv = _bwd(scale, causal, kv_valid, block_q, block_k, res, do,
                      dlse=dlse)
    qp, kp = res[5], res[6]
    dqp = None if qp is None else jnp.zeros_like(qp)
    dkp = None if kp is None else jnp.zeros_like(kp)
    return dq, dk, dv, dqp, dkp


_flash_bhsd_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _up8(n):
    return ((n + 7) // 8) * 8


def _prep_bhsd(q, k, v, block_q, block_k):
    """Shared wrapper preamble: adaptive block sizing, seq/head-dim padding,
    and [B,S,H,D] → [B*H,S,D] layout. Returns
    ``(qb, kb, vb, block_q, block_k, qpad, kpad, dpad)``."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if block_q is None:
        block_q = _up8(sq) if sq <= 1024 else 512
    if block_k is None:
        block_k = _up8(sk) if sk <= 1024 else 1024
    block_q = min(block_q, _up8(sq))
    block_k = min(block_k, _up8(sk))
    qpad = (block_q - sq % block_q) % block_q
    kpad = (block_k - sk % block_k) % block_k

    # d ∈ {64, 128, 256}: no padding — Mosaic tiles 64-lane minors natively,
    # and padding d doubles every dot and all q/k/v traffic (measured 2x)
    dpad = 0 if d in (64, 128, 256) else (128 - d % 128) % 128

    def to_bh(x, s, spad):
        x = jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)
        if spad or dpad:
            x = jnp.pad(x, ((0, 0), (0, spad), (0, dpad)))
        return x

    return (to_bh(q, sq, qpad), to_bh(k, sk, kpad), to_bh(v, sk, kpad),
            block_q, block_k, qpad, kpad, dpad)


def flash_attention_fused(q, k, v, causal=True, scale=None,
                          block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Flash attention on [B, S, H, D] arrays (paddle layout). Returns same
    layout. Seq lens and head dim are padded to tile multiples internally;
    padded kv positions are masked in-kernel, padded q rows sliced off."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qb, kb, vb, block_q, block_k, qpad, kpad, dpad = _prep_bhsd(
        q, k, v, block_q, block_k)
    kv_valid = sk if kpad else None
    out = _flash_bhsd(qb, kb, vb, scale, causal, kv_valid, block_q, block_k)
    if qpad or dpad:
        out = out[:, :sq, :d]
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def flash_attention_with_lse(q, k, v, causal=True, scale=None,
                             q_positions=None, kv_positions=None,
                             block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Flash attention on [B, S, H, D] returning ``(out, lse)`` where ``lse``
    is [B, H, Sq] float32 log-sum-exp of the scaled logits — the statistic
    blockwise/ring attention needs to merge partial results, and whose
    cotangent flows back through the same Pallas bwd kernels.

    ``q_positions`` / ``kv_positions`` ([Sq] / [Sk] int arrays): global token
    index of each position. When given, the mask is ``q_pos >= kv_pos``
    (position-driven causality — supports zig-zag ring layouts) and
    ``causal`` is ignored. Rows with no attendable key get out=0 and
    lse ≈ -1e30 (≈ -inf), which :func:`jnp.logaddexp`-style merges treat
    correctly.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qb, kb, vb, block_q, block_k, qpad, kpad, dpad = _prep_bhsd(
        q, k, v, block_q, block_k)

    pos_mask = q_positions is not None
    if pos_mask:
        if kv_positions is None:
            raise ValueError("q_positions given without kv_positions")
        # sentinels make padded q rows fully masked and padded kv cols
        # never attended; kv_valid is then unnecessary
        qp = jnp.pad(q_positions.astype(jnp.float32), (0, qpad),
                     constant_values=-2.0 ** 30)[:, None]  # [sq_p, 1]
        kp = jnp.pad(kv_positions.astype(jnp.float32), (0, kpad),
                     constant_values=2.0 ** 30)[None, :]  # [1, sk_p]
        kv_valid, causal = None, False
    else:
        qp = kp = None
        kv_valid = sk if kpad else None

    out, lse = _flash_bhsd_lse(qb, kb, vb, qp, kp, scale, causal, kv_valid,
                               block_q, block_k)
    if qpad or dpad:
        out = out[:, :sq, :d]
    lse = lse[:, :sq, 0].reshape(b, h, sq)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2), lse
