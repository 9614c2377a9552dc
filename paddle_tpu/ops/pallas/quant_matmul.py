"""Fused weight-only quant matmul Pallas kernels for the decode path.

TPU-native rewrite of the ``fused_multi_transformer_int8_op.cu``-class
weight-only GEMMs (SURVEY A3.x). Small-batch decode is weight-bandwidth
bound, so the dequant happens inside the kernel in VMEM and every weight
byte streams from HBM exactly once:

* int8  — weight block [bk, bn] loads once as int8, casts to the
  activation dtype on the VPU, one MXU dot per (n, k) grid step.
* int4  — the PACKED byte block [bk//2, bn] loads once; both nibbles
  sign-extend in VMEM (int32 shift pair) into ONE dequantized [bk, bn]
  slab — low-nibble rows stacked over high-nibble rows, paired with the
  activation's pre-split even/odd K columns so no in-kernel sublane
  interleave is needed — and a SINGLE full-depth MXU dot contracts the
  slab (two half-depth dots per block would double the accumulator
  traffic, which costs more than halving the weight bytes saves).

f32 accumulation lives in VMEM scratch across the k grid dimension; the
per-output-channel scale (and optional bias) apply in the epilogue at the
last k step. Decode rows pad to a sublane tile. Block shapes are
DIVISOR-AWARE (``select_block_shapes``): a block that does not divide the
problem forces ``jnp.pad`` to materialize a padded copy of the whole
weight OUTSIDE the kernel — an extra full read+write of the weight
stream per GEMM, which is exactly the traffic the kernel exists to
avoid (768-dim layers would pad to 1024 on both axes). Non-conforming
shapes still pad and stay correct. Shapes are picked per
(rows, in, out, dtype) and memoized
through ``framework.compile_cache.memoize_kernel_choice`` so a warm
server never retunes mid-flight. On non-TPU backends the kernel runs in
Pallas interpret mode (exact, slow) — CI covers it; dispatch policy
lives in ``nn/quant.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.compile_cache import memoize_kernel_choice

__all__ = ["quant_matmul", "quant_matmul_pallas", "quant_matmul_ref",
           "unpack_int4", "select_block_shapes"]

_ROW_TILE = 8  # pad decode rows to one f32 sublane tile
# prefill-sized row counts are compute-bound: route them back to XLA
# (nn/quant.py consults this) — the fused kernel targets skinny decode
PALLAS_MAX_ROWS = 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------- unpack


def unpack_int4(packed):
    """[K//2, N] packed nibbles → [K, N] int8 (row 2k = low nibble of
    byte k, row 2k+1 = high nibble; the ``weight_quantize`` layout)."""
    w = jnp.asarray(packed).astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(w, 28), 28)
    hi = jnp.right_shift(w, 4)
    k2, n = w.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n).astype(jnp.int8)


# ------------------------------------------------------- block selection


# VMEM budget for ONE weight block: leave room for double-buffered
# operand prefetch, the activation block and the f32 accumulator inside
# the ~16 MB VMEM envelope
_WEIGHT_BLOCK_BYTES = 4 << 20


def select_block_shapes(rows, k, n, weight_dtype):
    """(bk, bn) for the fused kernel, memoized per problem shape.

    Divisor-aware (ISSUE 9 tentpole c): a block that does not divide the
    problem pads the WEIGHT outside the kernel — a materialized copy
    whose write+read costs more than the bandwidth the quantization
    saved (GPT's 768/2304-wide layers padded to 1024-multiples under the
    old widest-block-that-fits rule). So: ``bn`` is the widest of
    {512, 256, 128} lanes dividing n (wide blocks amortize the
    scale/bias epilogue), falling back to widest-that-fits for
    non-conforming n; ``bk`` is the WHOLE K dimension when the weight
    block fits the VMEM budget and K is lane-tileable — one accumulator
    pass, zero epilogue revisits, and the packed int4 block is half the
    int8 bytes so it goes twice as deep — else the deepest power-of-two
    stripe dividing k, else the old pad-up heuristic.
    """
    def compute():
        bn = next((c for c in (512, 256, 128) if n % c == 0), None)
        if bn is None:
            bn = 128
            for cand in (512, 256):
                if n >= cand:
                    bn = cand
                    break
        # bytes one K row of the weight block costs in VMEM (packed
        # nibbles store two K rows per byte row; the grouped MoE kernel
        # reuses this budget logic for its float expert weight stacks)
        per_row = {"int8": bn, "int4": bn // 2, "bfloat16": 2 * bn,
                   "float32": 4 * bn}[weight_dtype]
        # whole-K needs the activation block's minor dim (bk for int8,
        # bk//2 for the int4 even/odd halves) to stay a 128-lane multiple
        lane_mult = 256 if weight_dtype == "int4" else 128
        if k % lane_mult == 0 and k * per_row <= _WEIGHT_BLOCK_BYTES:
            bk = k
        else:
            bk = next((c for c in (2048, 1024, 512, 256)
                       if k % c == 0 and c * per_row
                       <= _WEIGHT_BLOCK_BYTES), None)
            if bk is None:
                bk = 128
                for cand in (1024, 512, 256):
                    if k >= cand:
                        bk = cand
                        break
        return bk, bn

    return memoize_kernel_choice(
        ("wq_matmul_blocks", rows, k, n, weight_dtype), compute)


# --------------------------------------------------------------- kernels


def _epilogue(k_step, grid_k, acc_ref, s_ref, b_ref, o_ref):
    @pl.when(k_step == grid_k - 1)
    def _():
        y = acc_ref[:] * s_ref[:].astype(jnp.float32)  # [rows,bn]*[1,bn]
        if b_ref is not None:
            y = y + b_ref[:].astype(jnp.float32)
        o_ref[:] = y.astype(o_ref.dtype)


def _int8_kernel(x_ref, w_ref, s_ref, *rest, grid_k):
    b_ref, o_ref, acc_ref = rest if len(rest) == 3 else (None,) + rest
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:], w_ref[:].astype(x_ref.dtype),
                          preferred_element_type=jnp.float32)
    _epilogue(k_step, grid_k, acc_ref, s_ref, b_ref, o_ref)


def _int4_kernel(xe_ref, xo_ref, w_ref, s_ref, *rest, grid_k):
    b_ref, o_ref, acc_ref = rest if len(rest) == 3 else (None,) + rest
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # ONE load of the packed bytes; both nibbles dequant in VMEM into a
    # single [bk, bn] slab — low-nibble rows stacked over high-nibble
    # rows (a tile-aligned sublane concat, not an interleave Mosaic
    # would re-layout), contracted by ONE full-depth MXU dot against the
    # activation's matching (even ‖ odd) K-column halves
    w = w_ref[:].astype(jnp.int32)  # [bk//2, bn]
    lo = jnp.right_shift(jnp.left_shift(w, 28), 28)
    hi = jnp.right_shift(w, 4)
    slab = jnp.concatenate([lo, hi], axis=0).astype(xe_ref.dtype)
    x = jnp.concatenate([xe_ref[:], xo_ref[:]], axis=1)  # [rows, bk]
    acc_ref[:] += jnp.dot(x, slab, preferred_element_type=jnp.float32)
    _epilogue(k_step, grid_k, acc_ref, s_ref, b_ref, o_ref)


# --------------------------------------------------------------- wrapper


def quant_matmul_pallas(x, wq, scales, bias=None, weight_dtype="int8",
                        block_shapes=None, interpret=None):
    """y = x @ dequant(wq) * scales + bias as ONE fused Pallas kernel.

    x [..., K] (f32/bf16) · wq int8 [K, N] or packed int4 [K//2, N] ·
    scales f32 [N] · bias [N] optional → [..., N] in x.dtype.
    """
    x = jnp.asarray(x)
    wq = jnp.asarray(wq)
    scales = jnp.asarray(scales)
    if weight_dtype not in ("int8", "int4"):
        raise NotImplementedError(f"quant_matmul: {weight_dtype!r}")
    k = x.shape[-1]
    if weight_dtype == "int4":
        if k % 2:
            raise ValueError(f"int4 needs even K (got {k})")
        if wq.shape[0] * 2 != k:
            raise ValueError(
                f"packed int4 weight rows {wq.shape[0]} != K/2 = {k // 2}")
    elif wq.shape[0] != k:
        raise ValueError(f"weight rows {wq.shape[0]} != K = {k}")
    n = wq.shape[1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    rows = x2.shape[0]
    if interpret is None:
        interpret = _interpret()

    bk, bn = block_shapes or select_block_shapes(rows, k, n, weight_dtype)
    rows_p = _round_up(max(rows, 1), _ROW_TILE)
    kp = _round_up(k, bk)
    np_ = _round_up(n, bn)
    grid = (np_ // bn, kp // bk)

    x2 = jnp.pad(x2, ((0, rows_p - rows), (0, kp - k)))
    sc = jnp.pad(scales.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    operands, in_specs = [], []
    if weight_dtype == "int4":
        wp = jnp.pad(wq, ((0, (kp - k) // 2), (0, np_ - n)))
        # even/odd activation columns split OUTSIDE the kernel — a cheap
        # re-layout of the tiny decode activation, never of the weight
        operands += [x2[:, 0::2], x2[:, 1::2], wp, sc]
        in_specs += [
            pl.BlockSpec((rows_p, bk // 2), lambda j, kk: (0, kk)),
            pl.BlockSpec((rows_p, bk // 2), lambda j, kk: (0, kk)),
            pl.BlockSpec((bk // 2, bn), lambda j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk: (0, j)),
        ]
        kernel = _int4_kernel
    else:
        wp = jnp.pad(wq, ((0, kp - k), (0, np_ - n)))
        operands += [x2, wp, sc]
        in_specs += [
            pl.BlockSpec((rows_p, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk: (0, j)),
        ]
        kernel = _int8_kernel
    if bias is not None:
        b = jnp.pad(jnp.asarray(bias).astype(jnp.float32),
                    (0, np_ - n)).reshape(1, np_)
        operands.append(b)
        in_specs.append(pl.BlockSpec((1, bn), lambda j, kk: (0, j)))

    out = pl.pallas_call(
        functools.partial(kernel, grid_k=grid[1]),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows_p, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows_p, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows_p, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="quant_matmul",
    )(*operands)
    return out[:rows, :n].reshape(*lead, n)


def quant_matmul_ref(x, wq, scales, bias=None, weight_dtype="int8"):
    """Plain-XLA dequant-dot reference (the parity oracle: independent of
    every Pallas code path, same dtype discipline as the fused kernel —
    weight cast to x.dtype, f32 accumulate, scale/bias in f32)."""
    x = jnp.asarray(x)
    w = unpack_int4(wq) if weight_dtype == "int4" else jnp.asarray(wq)
    y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
    y = y * jnp.asarray(scales).astype(jnp.float32)
    if bias is not None:
        y = y + jnp.asarray(bias).astype(jnp.float32)
    return y.astype(x.dtype)


def quant_matmul(x, wq, scales, bias=None, weight_dtype="int8"):
    """Fused kernel on TPU, interpret-mode kernel elsewhere. Most callers
    want ``nn.quant.weight_only_linear`` (flag-dispatched, Tensor-aware);
    this is the raw-array entry point."""
    return quant_matmul_pallas(x, wq, scales, bias=bias,
                               weight_dtype=weight_dtype)
