"""Grouped-expert matmul Pallas kernel for MoE serving (ISSUE 17).

One kernel over ALL experts' tokens. The per-expert-dispatch antipattern
(a Python loop issuing one matmul per expert — what tpulint TPL1301
flags) costs E kernel launches and E weight-stream setups per MoE layer;
MegaBlocks-style grouped compute instead sorts the (token, choice) pairs
by expert into contiguous row groups and streams each expert's weight
block exactly once against its group:

* host-side (jnp, outside the kernel): segment offsets from
  ``group_sizes``, each group padded up to the row tile so a row block
  never straddles two experts' weights;
* scalar-prefetch metadata (``PrefetchScalarGridSpec``): a per-row-block
  expert id drives the rhs BlockSpec index_map — the weight stream
  follows the routing, no gather of the [E, K, N] stack ever
  materializes — plus a per-row-block valid count so blocks holding only
  capacity padding skip their MXU dots entirely;
* f32 VMEM accumulator across the k grid dimension, zeroed at the first
  k step and flushed at the last (the ``quant_matmul`` idiom);
* block selection reuses ``quant_matmul.select_block_shapes`` — the same
  divisor-aware VMEM-budget logic (a non-dividing block pads the WHOLE
  expert weight stack outside the kernel, the exact traffic the kernel
  exists to avoid), extended with float weight byte widths.

Semantics are ``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` with two
additions: rows past ``sum(group_sizes)`` and rows past an expert's
``valid_sizes[e]`` come back EXACTLY zero (both paths enforce it, so the
capacity-padded serving layout needs no masking downstream). The
interpret-mode kernel and the ``ragged_dot`` twin are BITWISE equal
whenever the k grid is a single block (every tier-1 shape — one f32
accumulation chain per output element either way); larger shapes agree
to float tolerance (XLA re-associates its accumulation per problem
shape). Dispatch (``grouped_matmul``): the fused kernel on TPU, the SAME
kernel in interpret mode elsewhere, so CPU tier-1 exercises the exact
serving semantics and per-row results stay invariant under expert-stack
splits — the property the ep=1 vs ep=N bit-identity rests on.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_matmul import _interpret, _round_up, select_block_shapes

__all__ = ["grouped_matmul", "grouped_matmul_pallas", "grouped_matmul_ref",
           "aligned_segment_offsets"]

# one f32 sublane tile per row block: groups pad to this so a block's
# rows all read the SAME expert's weight block
_GROUP_TILE = 8


def aligned_segment_offsets(group_sizes, tile: int = _GROUP_TILE):
    """(aligned_sizes, aligned_offsets) with every expert's segment
    padded up to ``tile`` rows — the host-side layout the kernel's
    block→expert metadata is derived from."""
    sizes = jnp.maximum(jnp.asarray(group_sizes, jnp.int32), 0)
    aligned = -(-sizes // tile) * tile
    return aligned, jnp.cumsum(aligned) - aligned


# --------------------------------------------------------------- kernel


def _grouped_kernel(b2g_ref, rows_ref, x_ref, w_ref, o_ref, acc_ref, *,
                    grid_k):
    i = pl.program_id(0)
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a block holding only capacity padding (valid count 0) skips its
    # dot — with unbalanced routing most of an overloaded layout's
    # blocks are dead and this is where the grouped kernel wins
    @pl.when(rows_ref[i] > 0)
    def _():
        acc_ref[:] += jnp.dot(x_ref[:], w_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(k_step == grid_k - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def grouped_matmul_pallas(lhs, rhs, group_sizes, valid_sizes=None,
                          block_shapes=None, interpret: Optional[bool] = None):
    """``ragged_dot``-semantics grouped GEMM as ONE fused Pallas kernel.

    ``lhs`` [M, K] sorted so expert ``e``'s rows are the contiguous
    segment of ``group_sizes[e]`` rows; ``rhs`` [E, K, N] stacked expert
    weights; optional ``valid_sizes`` [E] (≤ group_sizes) zeroes each
    group's tail rows and lets the kernel skip their compute (the
    capacity-padded serving layout passes kept-token counts here).
    """
    lhs = jnp.asarray(lhs)
    rhs = jnp.asarray(rhs)
    sizes = jnp.maximum(jnp.asarray(group_sizes, jnp.int32), 0)
    m, k = lhs.shape
    e, k2, n = rhs.shape
    if k2 != k:
        raise ValueError(f"rhs K {k2} != lhs K {k}")
    if sizes.shape != (e,):
        raise ValueError(f"group_sizes {sizes.shape} != ({e},)")
    vsz = sizes if valid_sizes is None else jnp.minimum(
        sizes, jnp.asarray(valid_sizes, jnp.int32))
    if interpret is None:
        interpret = _interpret()

    bm = _GROUP_TILE
    dt = "bfloat16" if rhs.dtype == jnp.bfloat16 else "float32"
    bk, bn = block_shapes or select_block_shapes(m, k, n, dt)
    kp, np_ = _round_up(k, bk), _round_up(n, bn)

    # ---- host-side sort-by-expert layout: aligned segment offsets ----
    aligned, aoff = aligned_segment_offsets(sizes, bm)
    poff = jnp.cumsum(sizes) - sizes                    # packed offsets
    ma = _round_up(max(m, 1), bm) + e * bm              # static bound
    r = jnp.arange(ma, dtype=jnp.int32)
    g = jnp.clip(jnp.searchsorted(aoff, r, side="right") - 1, 0, e - 1)
    local = r - aoff[g]
    ok = local < vsz[g]                                 # real, kept rows
    src = jnp.clip(poff[g] + local, 0, max(m - 1, 0))
    xa = jnp.where(ok[:, None], lhs[src], 0)
    if kp != k:
        xa = jnp.pad(xa, ((0, 0), (0, kp - k)))
    wp = rhs if (kp, np_) == (k, n) else jnp.pad(
        rhs, ((0, 0), (0, kp - k), (0, np_ - n)))

    blk2grp = g[::bm]                                   # [ma//bm]
    blk_rows = jnp.clip(vsz[blk2grp] - (r[::bm] - aoff[blk2grp]), 0, bm)

    grid = (ma // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, grid_k=grid[2]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, b2g, rows: (i, kk)),
                pl.BlockSpec((1, bk, bn),
                             lambda i, j, kk, b2g, rows: (b2g[i], kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda i, j, kk, b2g, rows: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((ma, np_), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul",
    )(blk2grp, blk_rows, xa, wp)

    # ---- scatter back to the packed row order -------------------------
    p = jnp.arange(m, dtype=jnp.int32)
    gp = jnp.searchsorted(jnp.cumsum(sizes), p, side="right")
    gpc = jnp.clip(gp, 0, e - 1)
    lp = p - poff[gpc]
    keep = (gp < e) & (lp < vsz[gpc])
    dst = jnp.clip(aoff[gpc] + lp, 0, ma - 1)
    return jnp.where(keep[:, None], out[dst, :n], 0)


def grouped_matmul_ref(lhs, rhs, group_sizes, valid_sizes=None):
    """The ``jax.lax.ragged_dot`` twin — independent of every Pallas code
    path, same dtype discipline (f32 accumulate, cast to lhs dtype),
    same zeroed-tail semantics. Bitwise equal to the interpret-mode
    kernel at single-k-block shapes (see module docstring)."""
    lhs = jnp.asarray(lhs)
    rhs = jnp.asarray(rhs)
    sizes = jnp.maximum(jnp.asarray(group_sizes, jnp.int32), 0)
    m = lhs.shape[0]
    e = rhs.shape[0]
    vsz = sizes if valid_sizes is None else jnp.minimum(
        sizes, jnp.asarray(valid_sizes, jnp.int32))
    y = jax.lax.ragged_dot(lhs, rhs, sizes,
                           preferred_element_type=jnp.float32)
    y = y.astype(lhs.dtype)
    p = jnp.arange(m, dtype=jnp.int32)
    gp = jnp.searchsorted(jnp.cumsum(sizes), p, side="right")
    gpc = jnp.clip(gp, 0, e - 1)
    lp = p - (jnp.cumsum(sizes) - sizes)[gpc]
    keep = (gp < e) & (lp < vsz[gpc])
    return jnp.where(keep[:, None], y, 0)


def grouped_matmul(lhs, rhs, group_sizes, valid_sizes=None):
    """Fused grouped kernel on TPU, the SAME kernel in interpret mode
    elsewhere (the quant_matmul dispatch policy) — so CPU tier-1 and the
    cross-ep identity suite run the exact serving semantics. The
    ``ragged_dot`` twin is the independent parity oracle, not a fallback
    path: per-row f32 accumulation chains must be split-invariant for
    ep=1 vs ep=N streams to be bit-identical, and the kernel's per-block
    dots are (verified by the identity suite) while XLA's ragged_dot is
    free to re-associate per problem shape."""
    return grouped_matmul_pallas(lhs, rhs, group_sizes, valid_sizes)
