"""Packed-QKV causal flash attention, v2 train-path kernel.

Reference capability: the fused attention inside
paddle/fluid/operators/fused/fused_multi_transformer_op.cu and the external
flash-attn library (paddle/phi/kernels/gpu/flash_attn_kernel.cu). This TPU
design differs from ops/pallas/flash_attention.py (the general kernel) in
two ways that dominate its speedup at train shapes:

1. **Packed layout, zero glue.** Input is the QKV projection output viewed
   as ``[B, 3H/hpb, S, hpb*D]`` and the output is ``[B, H/hpb, S, hpb*D]``
   — both reachable from the surrounding GEMMs by einsum alone (the weight
   is reshaped, the layout lands inside the dot), so nothing materializes
   between GEMM and kernel (the general kernel's [B,S,H,D]→[B*H,S,D]
   transposes + qkv unbind copies cost ~0.4 ms/layer at GPT-medium scale).
   ``hpb`` (heads per lane block) is 2 for D=64 so the minor dimension is
   128 lanes: a [..., 64] minor array takes a T(8,128) layout at 2.0x
   padded footprint (seen directly in XLA's HBM analysis), doubling HBM
   traffic for every operand — pair-packing removes the padding entirely.
   The same qkv array is passed three times with different index maps — no
   slicing copies. The lse residual is written as [B, H/hpb, S, hpb]
   columns (the general kernel wrote a 128-lane broadcast, 64 MB of pure
   padding per layer).
2. **One fused backward.** dQ, dK, dV come out of a single whole-sequence
   program per (batch, head block) — logits re-formed once per tile pair
   and shared by the three accumulations (``_pair_grads``, one body for
   this program and the S > 1024 per-pair grid), delta in-kernel, dots in
   the input dtype with fp32 accumulation — written into one
   ``[B, 3, H/hpb, S, hpb*D]`` array that bitcasts to the packed layout
   the QKV projection's backward consumes.

Three regimes by sequence length (VERDICT r3 #2 lifted the old S<=1024
cap; r5 added the whole-row middle regime; PR 32's cell is the first to
run the third, at S = 8192 with 12 heads of 128: ``_fwd_tiled`` 3.28 and
``_bwd_tiled`` 6.99 ms a call, 61.2% of the causal roofline):

* **S <= 1024 — whole-sequence programs.** One program per (batch, head
  block), no grid over the sequence. The forward at S = 1024 is the
  whole-row kernel below (3 of 4 512-tiles); other S pay the full square.
  The backward walks the causal square by q-tiles of 256 rows, unrolled
  at trace time: each tile's masked diagonal square and ONE unmasked
  rectangle over every k row to its left — (n+1)/2n of the square,
  62.5% at S = 1024. The kernel is bound by the matrix unit (D = 64
  fills half of the v5e's 128 x 128 array: the whole square ran 95
  TFLOP/s of a ~98.5 ceiling, D = 128 ran 189 of 197), so time follows
  the executed area. Measured on one v5e chip, B=12 x 16 heads x 1024 x
  64 bf16, ``causal_flash_bwd`` ms a call from the profiler (PR 27):
  whole square 1.352; this walk 0.955; square 512-tiles 1.020 (a-outer)
  / 1.024 (b-outer); square 256-tiles 1.043 / 1.060; square 128-tiles
  1.349; rows of 128 with their rectangle 0.965; per-k-tile columns of
  256 with a rectangle below 0.970; 512-tiles with the diagonal refined
  once 1.001. Small square tiles lose per executed FLOP what they save
  in area; the rectangle keeps the products long. (An in-kernel DYNAMIC
  fori chunk loop and a finer GRID were both slower than the square at
  these sizes, r3-r5, not re-measured.)
* **1024 < S <= 4096 — whole-ROW forward + per-pair backward.** The
  forward runs one program per (batch, head block, q-row of 512): the
  row's k-chunk walk is fully unrolled per static row length
  (``_fwd_row_kernel``), softmax state in SSA — measured +4.4% MFU on
  the 355M S=2048 train step over the per-pair grid, which spent the
  difference on per-grid-step overhead. The backward keeps the
  triangle-packed per-pair grid with shared-p single-pass math (a
  whole-column unrolled variant measured no better — the backward is
  not grid-overhead-bound).
* **4096 < S <= 8192 — tiled per-pair grids with causal block skip.**
  The triangle-packed scalar-prefetched (q-block, k-chunk) pair grid for
  both passes: the row unroll's O(nq^2/2) code size is a compile-time
  hazard past nq=8, and K/V whole-seq residency outgrows VMEM.

A fourth regime beside them, by MASK and not by length:

* **Band (``window=512``) — the pairs the window touches, and no others.**
  Query i sees keys j with ``0 <= i - j < window``. The scalar-prefetched
  pair grid again, forward (``window_flash_fwd``) and backward
  (``window_flash_bwd``, its math ``_pair_grads``), over the tables of
  ``_band_tables``: with tiles of 512 rows q tile a meets k tiles a - 1 and
  a, ``2 S/512 - 1`` pairs (31 at S = 8192 where the causal triangle has
  136), the diagonal one masked causally and the trailing one at the
  window's edge (key c of it is seen by query r where c > r), so about half
  of what executes is masked. dK/dV of tile b are met by rows b and b + 1
  only and roll through two scratch slots instead of the tiled regime's
  whole-sequence scratch. Measured on one v5e chip, 2 x 18 heads x 8192 x
  128 bf16, ms a call (PR 32; alone, host-timed over 30 calls): tiles of
  512: forward 2.474, backward 3.448; tiles of 256 (three tiles a q tile,
  0.75 of the area, twice the grid steps): 4.132 / 4.396; the causal
  kernels on the same heads 4.938 / 11.475. In the training step (device
  trace): ``window_flash_fwd`` 2.45 and ``window_flash_bwd`` 2.58 ms a
  call, 22.7% of the band's own roofline (the cost function counts the
  band, not the tiles): the forward spends as long on its 31 half-masked
  tiles as the backward, since every q tile pays the accumulators' set-up
  and write-out for two tiles' work; one program a q tile holding both k
  tiles (the whole-row kernel's form) has not been tried (ROADMAP A12).

Constraints: D in {64, 128, 256}, causal only, no dropout inside the
kernel (the model applies dropout outside); S % 8 == 0 up to 1024,
S % 512 == 0 for the tiled regime. The band regime: window 512, D = 128, S
a multiple of 512 from 1024 to 8192, bf16 or float32; elsewhere (and off
the chip) callers keep their ``jax.numpy`` masked softmax. With
``window=None`` every path above traces as it did before the band existed.

**Narrower q and k under wider v: the padded call.** ``head_dim`` is the
width of q, k AND v, and the softmax scale is ``1 / sqrt(head_dim)``. A
caller whose scores are d wide and whose values are wider (differential
attention: heads of 64 under value pairs of 128, ``models/phi4flash.py::
softmax_heads``) calls at ``head_dim`` = the values' width with q and k
zero-padded to it and q multiplied by ``sqrt(head_dim / d)``, so that the
kernel's scale comes out as the caller's ``1 / sqrt(d)``: the padded lanes
add nought to every score and take nought of the gradient. That is how D =
64 runs under a window today (the band regime takes D = 128 only, and not by
accident: its tiles are sized for 128 lanes); on the v5e a 64-deep
contraction half-fills the 128 x 128 array either way, so the padding costs
loads, not passes. A regime with q, k narrower than v is not written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30

# whole-sequence programs: q/k/v/o/do/dqkv blocks resident and double-
# buffered (~5 MB at S=1024 bf16) beside the backward's [256, S-256] f32
# tile temps; past it K/V residency and the unroll's code size take over
_MAX_SEQ = 1024
# tiled regime: q/k/v/o/do whole-seq resident -> ~5*S*256B, plus [blk, blk]
# fp32 logits temps; 8192 -> ~12 MB
_MAX_SEQ_TILED = 8192
_BLK = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _causal_mask(s, sq, sk):
    q_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return jnp.where(q_ids >= k_ids, s, NEG_INF)


# ---------------------------------------------------------------------- fwd


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, seq, d, hpb):
    for sub in range(hpb):  # static unroll over the heads sharing the lanes
        lo = sub * d
        q = q_ref[0, 0, :, lo:lo + d]  # [S, D]
        k = k_ref[0, 0, :, lo:lo + d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _causal_mask(s, seq, seq)
        m = jnp.max(s, axis=-1, keepdims=True)  # causal row 0 sees col 0
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jax.lax.dot_general(p.astype(v_ref.dtype),
                                  v_ref[0, 0, :, lo:lo + d],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0, 0, :, lo:lo + d] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, sub:sub + 1] = m + jnp.log(l)


def _fwd(qkv, num_heads, head_dim, scale):
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb  # head blocks
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, seq=seq, d=head_dim,
                          hpb=hpb),
        grid=(b, gh),
        in_specs=[
            pl.BlockSpec((1, 1, seq, lanes), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes),
                         lambda bi, hi, gh=gh: (bi, hi + gh, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes),
                         lambda bi, hi, gh=gh: (bi, hi + 2 * gh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, seq, lanes), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, seq, hpb), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, gh, seq, lanes), qkv.dtype),
            jax.ShapeDtypeStruct((b, gh, seq, hpb), jnp.float32),
        ],
        interpret=_interpret(),
        name="causal_flash_fwd",
    )(qkv, qkv, qkv)
    return out, lse


# -------------------------------------------------------------- tiled fwd


def _exact_in_bf16(scale: float) -> bool:
    """True when multiplying a bf16 operand by ``scale`` is exact (a
    power of two): then the softmax scale folds into the [blk, D] q (or
    do) operand instead of costing a [blk, blk] f32 multiply per tile.
    D in {64, 256} → 2^-3 / 2^-4 exact; D=128 keeps the wide multiply."""
    import math

    frac, _ = math.frexp(scale)
    return frac == 0.5


def _fwd_tiled_kernel(qi_tab, kc_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_s, l_s, acc_s, *, scale, seq, d, hpb, blk):
    # TRIANGLE-PACKED grid: the last grid axis enumerates only the
    # nq*(nq+1)/2 live (q-block, k-chunk) pairs; the scalar-prefetched
    # tables map the linear step to (qi, kc) for both the BlockSpec index
    # maps and the in-kernel branches. A rectangular (qi, kc) grid wasted
    # ~nq/2/(nq+1) of its steps above the diagonal, and an in-kernel fori
    # over k-chunks measured far slower still (the dynamic trip count
    # defeats Mosaic's cross-step software pipelining).
    t = pl.program_id(2)
    qi = qi_tab[t]
    kc = kc_tab[t]
    fold = _exact_in_bf16(scale)

    @pl.when(kc == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _tile(masked):
        for sub in range(hpb):
            lo = sub * d
            q = q_ref[0, 0, :, lo:lo + d]  # [blk, D]
            if fold:  # exact: scale the narrow operand, not [blk, blk]
                q = q * jnp.asarray(scale, q.dtype)
            k = k_ref[0, 0, :, lo:lo + d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [blk, blk]
            if not fold:
                s = s * scale
            if masked:  # only the diagonal block pays the triangle mask
                q_ids = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
                k_ids = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
                s = jnp.where(q_ids >= k_ids, s, NEG_INF)
            m_prev = m_s[sub, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # (bf16 exp measured SLOWER here — Mosaic upconverts, so the
            # extra cast only adds work; keep f32)
            p = jnp.exp(s - m_new)
            # narrow [blk, 1] stores: broadcasting the running stats to
            # all 128 lanes cost a full-tile VPU write per k-chunk
            l_s[sub, :, :1] = (alpha * l_s[sub, :, :1]
                               + jnp.sum(p, axis=-1, keepdims=True))
            m_s[sub, :, :1] = m_new
            acc_s[:, lo:lo + d] = acc_s[:, lo:lo + d] * alpha + (
                jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, 0, :, lo:lo + d],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    @pl.when(kc < qi)
    def _interior():
        _tile(masked=False)

    @pl.when(kc == qi)
    def _diag():
        _tile(masked=True)

    @pl.when(kc == qi)  # last live chunk for this q block: finalize
    def _finish():
        for sub in range(hpb):
            lo = sub * d
            l = l_s[sub, :, :1]
            o_ref[0, 0, :, lo:lo + d] = (acc_s[:, lo:lo + d] / l).astype(
                o_ref.dtype)
            lse_ref[0, 0, :, sub:sub + 1] = m_s[sub, :, :1] + jnp.log(l)


def _triangle_tables(nq):
    """qi/kc lookup tables for the packed triangle grid, kc fastest so the
    q block (and the output accumulators) stay resident within a row."""
    import numpy as np

    qi = np.concatenate([np.full(q + 1, q, np.int32) for q in range(nq)])
    kc = np.concatenate([np.arange(q + 1, dtype=np.int32)
                         for q in range(nq)])
    return qi, kc


def _fwd_blk(seq, dtype):
    # f32 operands double every block/temp footprint — shrink tiles to
    # stay inside the ~16 MB scoped-VMEM budget (train dtype is bf16).
    # blk=1024 wins over 512 despite computing 1.5x the causal triangle
    # (vs 1.25x): measured 0.539 vs 0.501 MFU at S=2048 — per-step
    # overhead beats the wasted half-tiles at these sizes.
    if jnp.dtype(dtype).itemsize > 2:
        return _BLK
    # tpulint: disable=TPL301 -- `seq` is a static python int (grid sizing
    # at pallas_call build time), not a traced value
    return 1024 if seq % 1024 == 0 else _BLK


def _bwd_blk(dtype):
    # measured at S=2048: blk=1024 fits VMEM but loses to 512 (0.530 vs
    # 0.539 MFU) — the bigger p/dp/ds temps throttle the pipeline; at
    # S=4096, 512 vs 1024 measured equal (0.3244 vs 0.3230 step MFU)
    return _BLK if jnp.dtype(dtype).itemsize <= 2 else _BLK // 2


def _fwd_tiled(qkv, num_heads, head_dim, scale):
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb
    blk = _fwd_blk(seq, qkv.dtype)
    nq = seq // blk
    qi_tab, kc_tab = _triangle_tables(nq)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_tiled_kernel, scale=scale, seq=seq,
                          d=head_dim, hpb=hpb, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, gh, len(qi_tab)),
            in_specs=[
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, qt, kt: (bi, hi, qt[t], 0)),
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, qt, kt, gh=gh:
                             (bi, hi + gh, kt[t], 0)),
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, qt, kt, gh=gh:
                             (bi, hi + 2 * gh, kt[t], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, qt, kt: (bi, hi, qt[t], 0)),
                pl.BlockSpec((1, 1, blk, hpb),
                             lambda bi, hi, t, qt, kt: (bi, hi, qt[t], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((hpb, blk, 128), jnp.float32),
                pltpu.VMEM((hpb, blk, 128), jnp.float32),
                pltpu.VMEM((blk, lanes), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, gh, seq, lanes), qkv.dtype),
            jax.ShapeDtypeStruct((b, gh, seq, hpb), jnp.float32),
        ],
        interpret=_interpret(),
        name="causal_flash_fwd_tiled",
    )(jnp.asarray(qi_tab), jnp.asarray(kc_tab), qkv, qkv, qkv)
    return out, lse


# ---------------------------------------------------------- whole-row fwd


def _fwd_row_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, seq, d,
                    hpb, blk, nq):
    """One grid step per (batch, head block, q-ROW): the row's k-chunk
    walk is fully unrolled inside the program (one ``pl.when`` branch per
    static row length), with the running softmax state in plain SSA
    values. Versus the triangle-packed per-pair grid this removes ALL
    cross-step scratch traffic and ~nq/2x of the per-grid-step overhead —
    measured the dominant cost at blk=512 (0.501 vs 0.539 MFU came almost
    entirely from the 640-step grid). K/V index maps are constant in the
    row coordinate, so Mosaic keeps them VMEM-resident per (b, hb).
    Compile cost is O(nq^2/2) unrolled tiles: nq=8 (S=4096) compiles in
    ~90 s and is the regime's practical edge — S=8192 stays on the
    per-pair grid (_row_blk gates)."""
    qi = pl.program_id(2)
    fold = _exact_in_bf16(scale)

    def row(r):
        for sub in range(hpb):
            lo = sub * d
            q = q_ref[0, 0, :, lo:lo + d]  # [blk, D]
            if fold:
                q = q * jnp.asarray(scale, q.dtype)
            m = jnp.full((blk, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((blk, 1), jnp.float32)
            acc = jnp.zeros((blk, d), jnp.float32)
            for kc in range(r + 1):
                k = k_ref[0, 0, kc * blk:(kc + 1) * blk, lo:lo + d]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if not fold:
                    s = s * scale
                if kc == r:  # only the diagonal tile pays the mask
                    q_ids = jax.lax.broadcasted_iota(
                        jnp.int32, (blk, blk), 0)
                    k_ids = jax.lax.broadcasted_iota(
                        jnp.int32, (blk, blk), 1)
                    s = jnp.where(q_ids >= k_ids, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                m = m_new
                acc = acc * alpha + jax.lax.dot_general(
                    p.astype(v_ref.dtype),
                    v_ref[0, 0, kc * blk:(kc + 1) * blk, lo:lo + d],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            o_ref[0, 0, :, lo:lo + d] = (acc / l).astype(o_ref.dtype)
            lse_ref[0, 0, :, sub:sub + 1] = m + jnp.log(l)

    for r in range(nq):
        @pl.when(qi == r)
        def _branch(r=r):
            row(r)


def _fwd_row(qkv, num_heads, head_dim, scale, blk):
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb
    nq = seq // blk
    # S=4096 sits 1 MB over the default 16 MB scoped-VMEM budget (the
    # whole-seq-resident K/V grow with S); raise the cap — v5e has the
    # physical VMEM, 16 MB is just the compiler's conservative default
    params = (pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)
              if seq > 2048 else None)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_row_kernel, scale=scale, seq=seq,
                          d=head_dim, hpb=hpb, blk=blk, nq=nq),
        compiler_params=params,
        grid=(b, gh, nq),
        in_specs=[
            pl.BlockSpec((1, 1, blk, lanes),
                         lambda bi, hi, r: (bi, hi, r, 0)),
            pl.BlockSpec((1, 1, seq, lanes),
                         lambda bi, hi, r, gh=gh: (bi, hi + gh, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes),
                         lambda bi, hi, r, gh=gh: (bi, hi + 2 * gh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, lanes),
                         lambda bi, hi, r: (bi, hi, r, 0)),
            pl.BlockSpec((1, 1, blk, hpb),
                         lambda bi, hi, r: (bi, hi, r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, gh, seq, lanes), qkv.dtype),
            jax.ShapeDtypeStruct((b, gh, seq, hpb), jnp.float32),
        ],
        interpret=_interpret(),
        name="causal_flash_fwd_row",
    )(qkv, qkv, qkv)
    return out, lse


def _row_blk(seq, dtype):
    """Whole-row regime tile size: the [blk, blk] f32 temps (Mosaic keeps
    ~2 unrolled iterations live for pipelining) + whole-seq-resident K/V
    must fit the 16 MB scoped VMEM — blk=1024 rows OOM at S=4096, so the
    row regime is blk=512 throughout and ends where its unroll gets too
    big to compile."""
    if jnp.dtype(dtype).itemsize > 2:
        # tpulint: disable=TPL301 -- `seq` is a static python int (row-regime
        # tile sizing at pallas_call build time), not a traced value
        return _BLK if seq <= 2048 else None
    # tpulint: disable=TPL301 -- same static `seq` as above
    return _BLK if seq <= 4096 else None  # S=8192: per-pair grid


# ------------------------------------------------------- bwd: one tile pair


def _scaled_delta(do, o, scale):
    """delta = rowsum(dO * O) as an f32 [blk, 1] column, in the units
    ``_pair_grads`` forms dp in: times ``scale`` where the scale folds
    into the narrow operands. Once per q-tile, not per pair (a [blk, 1]
    column costs a quarter of a [blk, blk] tile's vector work)."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return delta * scale if _exact_in_bf16(scale) else delta


def _pair_grads(q, do, k, v, lse, delta, *, scale, masked, edge=False):
    """One live pair of the causal backward: the rows of q-tile a ([bq, D]
    q/do, [bq, 1] lse/delta) against a span of k rows at or left of the
    diagonal ([bk, D] k/v). p and dp = do_a . v^T are formed ONCE and feed
    all three products (a two-pass scheme re-forms them per side). Returns
    the pair's f32 contributions to (dQ_a [bq, D], dK [bk, D], dV [bk, D]).
    Only the diagonal square (``masked``, bq == bk at the same offset)
    straddles the causal boundary and pays the iota mask; the band regime's
    trailing square (``edge``, the k tile a window behind the q tile) keeps
    what lies INSIDE the window, the strict upper triangle. Dots run in the
    input dtype with f32 accumulation."""
    fold = _exact_in_bf16(scale)
    if fold:
        # exact power-of-two scale: fold into the narrow operands feeding
        # the s and dp dots ([blk, D] multiplies) instead of two
        # [blk, blk] f32 multiplies per pair; the dq/dk/dv dots keep the
        # unscaled q/do
        q_in = q * jnp.asarray(scale, q.dtype)
        do_in = do * jnp.asarray(scale, do.dtype)
    else:
        q_in, do_in = q, do
    s = jax.lax.dot_general(q_in, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if not fold:
        s = s * scale
    p = jnp.exp(s - lse)
    if masked:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.where(q_ids >= k_ids, p, jnp.zeros((), p.dtype))
    if edge:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.where(k_ids > q_ids, p, jnp.zeros((), p.dtype))
    dp = jax.lax.dot_general(do_in, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if not fold:
        ds = ds * scale
    ds = ds.astype(k.dtype)
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dv = jax.lax.dot_general(p.astype(do.dtype), do,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return dq, dk, dv


# -------------------------------------------------------------- tiled bwd


def _bwd_tiled_kernel(a_tab, b_tab, qa_ref, doa_ref, oa_ref, lsea_ref,
                      kb_ref, vb_ref, dq_ref, dkv_ref, dq_s, dk_s, dv_s,
                      delta_s, *, scale, seq, d, hpb, blk):
    # TRIANGLE-PACKED shared-p backward: one step per live (a, b) pair
    # (q-block a, k-chunk b, b <= a; b fastest within a row), its math in
    # _pair_grads. dQ_a lives in row scratch (zeroed at b == 0, flushed
    # at b == a); dK_b/dV_b accumulate ACROSS rows in per-b scratch
    # (zeroed on first touch a == b, written out during the last row
    # a == nblk-1, whose flushes land last and overwrite any earlier
    # unwritten-buffer flushes of the dkv output blocks). delta_a is
    # cached per row (narrow [blk, 1] store).
    t = pl.program_id(2)
    a = a_tab[t]
    b = b_tab[t]
    nblk = seq // blk

    @pl.when(b == 0)
    def _row_start():
        dq_s[:] = jnp.zeros_like(dq_s)
        for sub in range(hpb):
            lo = sub * d
            delta_s[sub, :, :1] = _scaled_delta(
                doa_ref[0, 0, :, lo:lo + d], oa_ref[0, 0, :, lo:lo + d],
                scale)

    @pl.when(a == b)
    def _first_touch_b():
        dk_s[pl.ds(b, 1)] = jnp.zeros((1,) + dk_s.shape[1:], dk_s.dtype)
        dv_s[pl.ds(b, 1)] = jnp.zeros((1,) + dv_s.shape[1:], dv_s.dtype)

    def _pair(masked):
        for sub in range(hpb):
            lo = sub * d
            dq, dk, dv = _pair_grads(
                qa_ref[0, 0, :, lo:lo + d], doa_ref[0, 0, :, lo:lo + d],
                kb_ref[0, 0, :, lo:lo + d], vb_ref[0, 0, :, lo:lo + d],
                lsea_ref[0, 0, :, sub:sub + 1], delta_s[sub, :, :1],
                scale=scale, masked=masked)
            dq_s[:, lo:lo + d] = dq_s[:, lo:lo + d] + dq
            dv_s[b, :, lo:lo + d] = dv_s[b, :, lo:lo + d] + dv
            dk_s[b, :, lo:lo + d] = dk_s[b, :, lo:lo + d] + dk

    @pl.when(a == b)
    def _diag_pair():
        _pair(masked=True)

    @pl.when(a != b)
    def _interior_pair():
        _pair(masked=False)

    @pl.when(a == b)  # diag = end of row a: dQ_a complete
    def _write_dq():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)

    @pl.when(a == nblk - 1)  # last row touches every b: dK_b/dV_b complete
    def _write_dkv():
        dkv_ref[0, 0, 0] = dk_s[b].astype(dkv_ref.dtype)
        dkv_ref[0, 1, 0] = dv_s[b].astype(dkv_ref.dtype)


def _bwd_tiled(num_heads, head_dim, scale, res, do):
    qkv, out, lse = res
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb
    blk = _bwd_blk(qkv.dtype)
    nblk = seq // blk
    a_tab, b_tab = _triangle_tables(nblk)

    def at_a(group, width=None):
        w = lanes if width is None else width
        return pl.BlockSpec(
            (1, 1, blk, w),
            lambda bi, hi, t, at, bt, g=group, gh=gh:
            (bi, hi + g * gh, at[t], 0))

    def at_b(group):
        return pl.BlockSpec(
            (1, 1, blk, lanes),
            lambda bi, hi, t, at, bt, g=group, gh=gh:
            (bi, hi + g * gh, bt[t], 0))

    dq4, dkv5 = pl.pallas_call(
        functools.partial(_bwd_tiled_kernel, scale=scale, seq=seq,
                          d=head_dim, hpb=hpb, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, gh, len(a_tab)),
            in_specs=[
                at_a(0),            # q at a
                at_a(0),            # do at a (same indexing as q/out rows)
                at_a(0),            # o at a
                at_a(0, hpb),       # lse at a
                at_b(1),            # k at b
                at_b(2),            # v at b
            ],
            out_specs=[
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, at, bt: (bi, hi, at[t], 0)),
                pl.BlockSpec((1, 2, 1, blk, lanes),
                             lambda bi, hi, t, at, bt: (bi, 0, hi, bt[t], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk, lanes), jnp.float32),
                pltpu.VMEM((nblk, blk, lanes), jnp.float32),
                pltpu.VMEM((nblk, blk, lanes), jnp.float32),
                pltpu.VMEM((hpb, blk, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, gh, seq, lanes), qkv.dtype),
            jax.ShapeDtypeStruct((b, 2, gh, seq, lanes), qkv.dtype),
        ],
        interpret=_interpret(),
        name="causal_flash_bwd_tiled",
    )(jnp.asarray(a_tab), jnp.asarray(b_tab),
      qkv, do, out, lse, qkv, qkv)
    # [B, 3H/hpb, S, lanes]: dq rows then dk rows then dv rows — the same
    # group layout the packed QKV projection backward consumes. XLA folds
    # this concat into the consuming GEMMs (dot-of-concat => sum of dots).
    return jnp.concatenate(
        [dq4, dkv5[:, 0], dkv5[:, 1]], axis=1)


# ------------------------------------------------------------- band regime
#
# Sliding-window attention: query i sees keys j with 0 <= i - j < window.
# With tiles of ``blk`` rows (window a multiple of blk, nb = window / blk)
# q tile a meets k tiles a - nb ... a: the diagonal one masked causally, the
# trailing one (a - nb, where it exists) masked at the window's edge, those
# between whole. The grid's last axis enumerates only these pairs through
# scalar-prefetched tables, as the triangle-packed grids above do.

# tile rows of the band regime: 512 beat 256 on the chip (the numbers in the
# module docstring)
_WIN_BLK = 512


def _band_tables(nq, nb):
    """qi/kc lookup tables of the band's pairs, kc fastest and rising, so
    that a q tile's accumulators stay resident within its row."""
    import numpy as np

    rows = [(q, np.arange(max(q - nb, 0), q + 1, dtype=np.int32))
            for q in range(nq)]
    return (np.concatenate([np.full(len(k), q, np.int32) for q, k in rows]),
            np.concatenate([k for _, k in rows]))


def _window_fwd_kernel(qi_tab, kc_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_s, l_s, acc_s, *, scale, d, hpb, blk, nb):
    t = pl.program_id(2)
    qi = qi_tab[t]
    kc = kc_tab[t]
    fold = _exact_in_bf16(scale)

    @pl.when(kc == jnp.maximum(qi - nb, 0))
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _tile(mask):
        for sub in range(hpb):
            lo = sub * d
            q = q_ref[0, 0, :, lo:lo + d]  # [blk, D]
            if fold:
                q = q * jnp.asarray(scale, q.dtype)
            s = jax.lax.dot_general(
                q, k_ref[0, 0, :, lo:lo + d], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [blk, blk]
            if not fold:
                s = s * scale
            if mask is not None:
                q_ids = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
                k_ids = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
                s = jnp.where(q_ids >= k_ids if mask == "causal"
                              else k_ids > q_ids, s, NEG_INF)
            # a row the edge masks whole reads exp(0) = 1 here (NEG_INF is
            # finite); the diagonal tile, which always follows and holds a
            # live entry for every row, scales that away by alpha = 0
            m_prev = m_s[sub, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_s[sub, :, :1] = (alpha * l_s[sub, :, :1]
                               + jnp.sum(p, axis=-1, keepdims=True))
            m_s[sub, :, :1] = m_new
            acc_s[:, lo:lo + d] = acc_s[:, lo:lo + d] * alpha + (
                jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, 0, :, lo:lo + d],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    @pl.when(kc == qi - nb)
    def _edge():
        _tile("edge")

    @pl.when((kc > qi - nb) & (kc < qi))
    def _interior():
        _tile(None)

    @pl.when(kc == qi)  # the row's last pair: the diagonal, then finalize
    def _diag():
        _tile("causal")
        for sub in range(hpb):
            lo = sub * d
            l = l_s[sub, :, :1]
            o_ref[0, 0, :, lo:lo + d] = (acc_s[:, lo:lo + d] / l).astype(
                o_ref.dtype)
            lse_ref[0, 0, :, sub:sub + 1] = m_s[sub, :, :1] + jnp.log(l)


def _window_fwd(qkv, num_heads, head_dim, scale, window):
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb
    blk = _WIN_BLK
    qi_tab, kc_tab = _band_tables(seq // blk, window // blk)
    at_q = lambda bi, hi, t, qt, kt: (bi, hi, qt[t], 0)
    out, lse = pl.pallas_call(
        functools.partial(_window_fwd_kernel, scale=scale, d=head_dim,
                          hpb=hpb, blk=blk, nb=window // blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, gh, len(qi_tab)),
            in_specs=[
                pl.BlockSpec((1, 1, blk, lanes), at_q),
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, qt, kt, gh=gh:
                             (bi, hi + gh, kt[t], 0)),
                pl.BlockSpec((1, 1, blk, lanes),
                             lambda bi, hi, t, qt, kt, gh=gh:
                             (bi, hi + 2 * gh, kt[t], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, blk, lanes), at_q),
                pl.BlockSpec((1, 1, blk, hpb), at_q),
            ],
            scratch_shapes=[
                pltpu.VMEM((hpb, blk, 128), jnp.float32),
                pltpu.VMEM((hpb, blk, 128), jnp.float32),
                pltpu.VMEM((blk, lanes), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, gh, seq, lanes), qkv.dtype),
            jax.ShapeDtypeStruct((b, gh, seq, hpb), jnp.float32),
        ],
        interpret=_interpret(),
        name="window_flash_fwd",
    )(jnp.asarray(qi_tab), jnp.asarray(kc_tab), qkv, qkv, qkv)
    return out, lse


def _window_bwd_kernel(a_tab, b_tab, qa_ref, doa_ref, oa_ref, lsea_ref,
                       kb_ref, vb_ref, dq_ref, dkv_ref, dq_s, dk_s, dv_s,
                       delta_s, *, scale, seq, d, hpb, blk, nb):
    # one step per pair of the band (q tile a, k tile b, a - nb <= b <= a;
    # b fastest and rising), its math in _pair_grads. dQ_a lives in row
    # scratch (zeroed at the row's first pair, flushed at b == a). k tile b
    # is met by rows b ... b + nb, so dK_b/dV_b accumulate in slot b mod
    # (nb + 1) of a rolling scratch (zeroed on first touch a == b) and are
    # written out on the last touch, a == min(b + nb, last row): that write
    # lands after any flush of the block's unwritten buffer by the rows
    # between. delta_a is cached per row.
    t = pl.program_id(2)
    a = a_tab[t]
    b = b_tab[t]
    slot = b % (nb + 1)
    last = seq // blk - 1

    @pl.when(b == jnp.maximum(a - nb, 0))
    def _row_start():
        dq_s[:] = jnp.zeros_like(dq_s)
        for sub in range(hpb):
            lo = sub * d
            delta_s[sub, :, :1] = _scaled_delta(
                doa_ref[0, 0, :, lo:lo + d], oa_ref[0, 0, :, lo:lo + d],
                scale)

    @pl.when(a == b)
    def _first_touch_b():
        dk_s[pl.ds(slot, 1)] = jnp.zeros((1,) + dk_s.shape[1:], dk_s.dtype)
        dv_s[pl.ds(slot, 1)] = jnp.zeros((1,) + dv_s.shape[1:], dv_s.dtype)

    def _pair(masked, edge):
        for sub in range(hpb):
            lo = sub * d
            dq, dk, dv = _pair_grads(
                qa_ref[0, 0, :, lo:lo + d], doa_ref[0, 0, :, lo:lo + d],
                kb_ref[0, 0, :, lo:lo + d], vb_ref[0, 0, :, lo:lo + d],
                lsea_ref[0, 0, :, sub:sub + 1], delta_s[sub, :, :1],
                scale=scale, masked=masked, edge=edge)
            dq_s[:, lo:lo + d] = dq_s[:, lo:lo + d] + dq
            dv_s[slot, :, lo:lo + d] = dv_s[slot, :, lo:lo + d] + dv
            dk_s[slot, :, lo:lo + d] = dk_s[slot, :, lo:lo + d] + dk

    @pl.when(b == a - nb)
    def _edge_pair():
        _pair(masked=False, edge=True)

    @pl.when((b > a - nb) & (b < a))
    def _interior_pair():
        _pair(masked=False, edge=False)

    @pl.when(a == b)  # diag = end of row a: dQ_a complete
    def _diag_pair():
        _pair(masked=True, edge=False)
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)

    @pl.when(a == jnp.minimum(b + nb, last))  # the last row that meets b
    def _write_dkv():
        dkv_ref[0, 0, 0] = dk_s[slot].astype(dkv_ref.dtype)
        dkv_ref[0, 1, 0] = dv_s[slot].astype(dkv_ref.dtype)


def _window_bwd(num_heads, head_dim, scale, window, res, do):
    qkv, out, lse = res
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb
    blk = _WIN_BLK
    nb = window // blk
    a_tab, b_tab = _band_tables(seq // blk, nb)

    def at_a(width=lanes):
        return pl.BlockSpec((1, 1, blk, width),
                            lambda bi, hi, t, at, bt: (bi, hi, at[t], 0))

    def at_b(group):
        return pl.BlockSpec(
            (1, 1, blk, lanes),
            lambda bi, hi, t, at, bt, g=group, gh=gh:
            (bi, hi + g * gh, bt[t], 0))

    dq4, dkv5 = pl.pallas_call(
        functools.partial(_window_bwd_kernel, scale=scale, seq=seq,
                          d=head_dim, hpb=hpb, blk=blk, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, gh, len(a_tab)),
            # q, do, o, lse at a; k, v at b
            in_specs=[at_a(), at_a(), at_a(), at_a(hpb), at_b(1), at_b(2)],
            out_specs=[
                at_a(),
                pl.BlockSpec((1, 2, 1, blk, lanes),
                             lambda bi, hi, t, at, bt: (bi, 0, hi, bt[t], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk, lanes), jnp.float32),
                pltpu.VMEM((nb + 1, blk, lanes), jnp.float32),
                pltpu.VMEM((nb + 1, blk, lanes), jnp.float32),
                pltpu.VMEM((hpb, blk, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, gh, seq, lanes), qkv.dtype),
            jax.ShapeDtypeStruct((b, 2, gh, seq, lanes), qkv.dtype),
        ],
        interpret=_interpret(),
        name="window_flash_bwd",
    )(jnp.asarray(a_tab), jnp.asarray(b_tab), qkv, do, out, lse, qkv, qkv)
    return jnp.concatenate([dq4, dkv5[:, 0], dkv5[:, 1]], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _banded(qkv, num_heads, head_dim, scale, window):
    out, _ = _window_fwd(qkv, num_heads, head_dim, scale, window)
    return out


def _banded_fwd_rule(qkv, num_heads, head_dim, scale, window):
    out, lse = _window_fwd(qkv, num_heads, head_dim, scale, window)
    return out, (qkv, out, lse)


def _banded_bwd_rule(num_heads, head_dim, scale, window, res, do):
    return (_window_bwd(num_heads, head_dim, scale, window, res,
                        do.astype(res[0].dtype)),)


_banded.defvjp(_banded_fwd_rule, _banded_bwd_rule)


# ---------------------------------------------------------------------- bwd


def _bwd_sq_blk(seq):
    """q-tile rows of the whole-sequence backward's in-program triangle,
    from what is static: ``seq``. 256 measured best on the v5e (the table
    in the module docstring). Where ``seq`` has no second tile the one
    tile is the whole square (n = 1, the masked diagonal pair alone)."""
    blk = 256
    # tpulint: disable=TPL301 -- `seq` is a static python int (unroll
    # sizing at pallas_call build time), not a traced value
    return blk if seq > blk and seq % blk == 0 else seq


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dqkv_ref, *,
                scale, seq, d, hpb, blk):
    """One program per (batch, head block) walks the live part of the
    causal square by q-tiles of ``blk`` rows, unrolled at trace time (no
    dynamic trip count, no extra grid step, no cross-step scratch): tile
    a's masked diagonal square, then ONE unmasked rectangle over all the
    k rows to its left, [blk, a*blk]. The tiles above the diagonal are
    exactly those the mask zeroes and are never formed: (n+1)/2n of the
    square is executed, in 2n-1 pair bodies. dQ_a is complete at the end
    of its row and stored there; dK_b/dV_b accumulate in f32 values."""
    n = seq // blk
    for sub in range(hpb):  # static unroll over the heads sharing the lanes
        cols = slice(sub * d, (sub + 1) * d)
        dk, dv = [None] * n, [None] * n
        for a in range(n):
            rows = slice(a * blk, (a + 1) * blk)
            q = q_ref[0, 0, rows, cols]
            do = do_ref[0, 0, rows, cols]
            lse = lse_ref[0, 0, rows, sub:sub + 1]
            delta = _scaled_delta(do, o_ref[0, 0, rows, cols], scale)
            dq, dk[a], dv[a] = _pair_grads(
                q, do, k_ref[0, 0, rows, cols], v_ref[0, 0, rows, cols],
                lse, delta, scale=scale, masked=True)
            if a:
                left = slice(0, a * blk)
                dq_l, dk_l, dv_l = _pair_grads(
                    q, do, k_ref[0, 0, left, cols], v_ref[0, 0, left, cols],
                    lse, delta, scale=scale, masked=False)
                dq = dq + dq_l
                for b in range(a):
                    at_b = slice(b * blk, (b + 1) * blk)
                    dk[b] = dk[b] + dk_l[at_b]
                    dv[b] = dv[b] + dv_l[at_b]
            dqkv_ref[0, 0, 0, rows, cols] = dq.astype(dqkv_ref.dtype)
        for b in range(n):
            at_b = slice(b * blk, (b + 1) * blk)
            dqkv_ref[0, 1, 0, at_b, cols] = dk[b].astype(dqkv_ref.dtype)
            dqkv_ref[0, 2, 0, at_b, cols] = dv[b].astype(dqkv_ref.dtype)


def _bwd(num_heads, head_dim, scale, res, do):
    return _bwd_traced(num_heads, head_dim, scale, _interpret(), res, do)


# traced ONCE for all the layers of a model that share a shape, then inlined
# at each call under the caller's scope: the unrolled body costs 0.1 s of
# host time a trace (24 layers: 2.5 s of every run's set-up, PR 27)
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), inline=True)
def _bwd_traced(num_heads, head_dim, scale, interpret, res, do):
    qkv, out, lse = res
    b, groups, seq, lanes = qkv.shape
    hpb = lanes // head_dim
    gh = num_heads // hpb
    dqkv5 = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, seq=seq, d=head_dim,
                          hpb=hpb, blk=_bwd_sq_blk(seq)),
        # f32 at S=1024: with 256-row tiles D=64 and D=128 compile inside
        # the default 16 MB scoped VMEM, D=256 (1 MB per resident block,
        # double-buffered) still does not (AOT for v5e, PR 27): raise the
        # cap like _fwd_row
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024)
            if seq >= 1024 and jnp.dtype(qkv.dtype).itemsize > 2
            else None),
        grid=(b, gh),
        in_specs=[
            pl.BlockSpec((1, 1, seq, lanes), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes),
                         lambda bi, hi, gh=gh: (bi, hi + gh, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes),
                         lambda bi, hi, gh=gh: (bi, hi + 2 * gh, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, seq, lanes), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, seq, hpb), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        # one out array [B, 3, H/hpb, S, hpb*D]; the (1,3,1,S,lanes) block
        # lets a single program write its heads' dQ, dK, dV — reshaping to
        # the packed [B, 3H/hpb, S, hpb*D] is a free bitcast for the caller
        out_specs=pl.BlockSpec((1, 3, 1, seq, lanes),
                               lambda bi, hi: (bi, 0, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 3, gh, seq, lanes), qkv.dtype),
        interpret=interpret,
        name="causal_flash_bwd",
    )(qkv, qkv, qkv, out, do, lse)
    return dqkv5.reshape(b, 3 * gh, seq, lanes)


# ------------------------------------------------------------------- public


def _fwd_dispatch(qkv, num_heads, head_dim, scale):
    seq = qkv.shape[2]
    # the whole-ROW forward wins wherever its 512-divisible grid applies:
    # at S=1024 it beats the whole-sequence square by +1.1% step MFU on
    # the 355M train bench (triangle-only compute at the same per-step
    # overhead), so the row regime starts as soon as S has >= 2 rows
    if seq > _BLK and seq % _BLK == 0:
        blk = _row_blk(seq, qkv.dtype)
        if blk is not None:
            return _fwd_row(qkv, num_heads, head_dim, scale, blk)
    if seq <= _MAX_SEQ:
        return _fwd(qkv, num_heads, head_dim, scale)
    return _fwd_tiled(qkv, num_heads, head_dim, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _packed(qkv, num_heads, head_dim, scale):
    out, _ = _fwd_dispatch(qkv, num_heads, head_dim, scale)
    return out


def _packed_fwd_rule(qkv, num_heads, head_dim, scale):
    out, lse = _fwd_dispatch(qkv, num_heads, head_dim, scale)
    return out, (qkv, out, lse)


def _packed_bwd_rule(num_heads, head_dim, scale, res, do):
    # an upcast cotangent (f32 via an f32 loss tail) would double every
    # block footprint in the kernels — the math accumulates in f32 either
    # way, so carry do at the qkv dtype
    do = do.astype(res[0].dtype)
    if res[0].shape[2] <= _MAX_SEQ:
        return (_bwd(num_heads, head_dim, scale, res, do),)
    # S > 1024: the triangle-packed per-pair grid. (A whole-column
    # unrolled backward mirroring _fwd_row_kernel measured equal to it at
    # S=2048, r5; the in-program walk of _bwd_kernel has not been tried
    # past 1024, where K/V residency and 2n-1 unrolled bodies grow.)
    return (_bwd_tiled(num_heads, head_dim, scale, res, do),)


_packed.defvjp(_packed_fwd_rule, _packed_bwd_rule)


def heads_per_block(num_heads: int, head_dim: int) -> int:
    """2 when pair-packing D=64 heads into full 128-lane tiles is possible
    (even head count), else 1."""
    return 2 if (head_dim == 64 and num_heads % 2 == 0) else 1


def supported(seq: int, head_dim: int, window=None) -> bool:
    """Whether the kernels take heads of ``head_dim`` (the width of q, k and
    v alike) at length ``seq``; with ``window``, whether the band regime
    does: window 512 at D = 128 only. Narrower heads under a window, or q
    and k narrower than v, go through the padded call (module docstring)."""
    if window is not None:
        # the band regime: one window, one head width, whole tiles
        return (window == 512 and head_dim == 128 and seq % _WIN_BLK == 0
                and 2 * window <= seq <= _MAX_SEQ_TILED)
    if head_dim not in (64, 128, 256):
        return False
    if seq <= _MAX_SEQ:
        return seq % 8 == 0
    # tiled regime (causal block skip over _BLK-sized S-blocks). The
    # backward's per-k-block dK/dV scratch is 2*seq*lanes*4 bytes — at
    # D=256 (256-lane blocks) the S=8192 allocation alone would blow the
    # ~16 MB scoped-VMEM budget, so the cap halves there.
    limit = _MAX_SEQ_TILED if head_dim <= 128 else _MAX_SEQ_TILED // 2
    return seq % _BLK == 0 and seq <= limit


def enabled(seq: int, head_dim: int, window=None) -> bool:
    """Whether a train path should take this kernel: where
    ``FLAGS_use_packed_attention`` says so (unset: on the TPU only) and the
    shape (with the window, on a sliding layer) is supported."""
    from ...framework.flags import get_flags

    flag = get_flags("FLAGS_use_packed_attention")[
        "FLAGS_use_packed_attention"]
    if flag is None:
        flag = jax.default_backend() == "tpu"
    return bool(flag) and supported(seq, head_dim, window)


def causal_flash_qkv(qkv, num_heads, head_dim=None, window=None):
    """Causal self-attention on a packed QKV tensor.

    qkv: ``[B, 3H/hpb, S, hpb*D]`` — q head blocks, then k, then v, where
    ``hpb = heads_per_block(H, D)`` (exactly the reshaped-weight einsum of
    the fused projection). Returns ``[B, H/hpb, S, hpb*D]``. With
    ``window`` query i sees keys j with ``0 <= i - j < window`` only (the
    band regime; ``supported`` says at which shapes). q, k and v are all
    ``head_dim`` wide and the scores are scaled by ``1 / sqrt(head_dim)``;
    scores narrower than the values: zero-pad q and k to the values' width
    and fold the caller's scale into q (module docstring, "the padded
    call"; tested in ``tests/test_phi4flash.py``).
    """
    b, groups, seq, lanes = qkv.shape
    if head_dim is None:
        head_dim = lanes  # hpb == 1 call style
    hpb = lanes // head_dim
    if (lanes % head_dim or num_heads % hpb
            or groups * hpb != 3 * num_heads):
        raise ValueError(
            f"causal_flash_qkv: qkv shape {qkv.shape} inconsistent with "
            f"num_heads={num_heads}, head_dim={head_dim}")
    scale = 1.0 / (head_dim ** 0.5)
    if window is not None:
        if not supported(seq, head_dim, window):
            raise ValueError(
                f"causal_flash_qkv: window {window} at shape {qkv.shape}: "
                f"the band regime takes window 512, D = 128 and S a "
                f"multiple of {_WIN_BLK} from 1024 to {_MAX_SEQ_TILED}")
        return _banded(qkv, num_heads, head_dim, float(scale), window)
    if not supported(seq, head_dim):
        raise ValueError(
            f"causal_flash_qkv: unsupported shape {qkv.shape}; need "
            f"D in (64,128,256) and S % 8 == 0 (S <= {_MAX_SEQ}) or "
            f"S % {_BLK} == 0 (S <= {_MAX_SEQ_TILED})")
    return _packed(qkv, num_heads, head_dim, float(scale))
