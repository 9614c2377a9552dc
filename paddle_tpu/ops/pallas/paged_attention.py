"""Paged (block) KV cache + decode attention over block tables.

Serving-grade KV cache in the vLLM/PagedAttention mold — the TPU-native
answer to the reference's contiguous per-sequence cache in
``paddle/fluid/operators/fused/fused_multi_transformer_op.cu`` and its int8
variant ``fused_multi_transformer_int8_op.cu`` (SURVEY.md A3.x names the
paged/contiguous KV cache as the Pallas flagship):

* K/V live in a pool of fixed-size **pages** ``[H_kv, P, page_size, D]``;
  each sequence owns a list of physical pages via a **block table**
  ``[B, max_pages]``.  No per-sequence max_seq reservation: memory scales
  with tokens actually written, and pages are recycled on free.
* The decode kernel runs one Pallas grid instance per (batch, head, page):
  the block table is scalar-prefetched, and each page's BlockSpec index_map
  gathers the *physical* page for the logical page — the gather happens in
  the DMA engine, not as a jnp.take.  Online-softmax scratch accumulates
  across pages; pages beyond the sequence length are skipped.
* **int8 cache**: pages stored int8 with one f32 scale per cache row
  (per-token, amax/127 symmetric) — write-local quantization, so appending
  never rescales old data.  Dequantized in-kernel before the dots.

Layouts
  q               [B, H, D]
  k/v pages       [H_kv, P, page_size, D]   (+ scales [H_kv, P, page_size])
  block_tables    [B, max_pages] int32      physical page of logical page i
  lengths         [B] int32                 valid tokens incl. the new one

GQA: q head h reads kv head ``h // (H // H_kv)``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (_WINDOW_BYTES, _softmax_finish,
                               _softmax_init, _softmax_scratch,
                               _softmax_window)

NEG_INF = -1.0e30
_Q_ROWS = 8  # pad the single q row to a full sublane tile
# safely under the 16 MiB of fast memory the chip's compiler grants a kernel
# unasked (v5e), with slack for what the estimate below leaves out; a kernel
# whose resident set is larger names its own limit
_DEFAULT_VMEM_BYTES = 12 << 20

__all__ = ["paged_decode_attention", "paged_decode_attention_ref",
           "PagedKVCache", "quantize_rows_int8",
           "paged_verify_slab_attention", "paged_multi_query_attention"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ kernel


def _paged_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, *rest, scale,
                  page_size, num_pages, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_s, l_s, acc_s = rest
    b = pl.program_id(0)
    p = pl.program_id(2)
    length = len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # skip pages entirely past this sequence's length
    live = p * page_size < length

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [_Q_ROWS, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [page_size, D]
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0][:, :1]
            v = v * vs_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [_Q_ROWS, page_size]
        ids = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(ids < length, s, NEG_INF)

        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # rows of a live page can still be fully masked (last partial page);
        # with m stuck at NEG_INF exp(s - m) would be 1 there — guard
        pexp = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        l_s[:] = jnp.broadcast_to(
            alpha * l_s[:, :1] + jnp.sum(pexp, axis=-1, keepdims=True),
            l_s.shape)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(p == num_pages - 1)
    def _finish():
        o_ref[0] = (acc_s[:] / jnp.maximum(l_s[:, :1], 1e-37)).astype(
            o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           scale=None, k_scales=None, v_scales=None):
    """q [B,H,D] against paged caches; returns [B,H,D].

    ``k_scales``/``v_scales`` [H_kv, P, page_size] activate the int8 path
    (pages must then be int8)."""
    b, h, d = q.shape
    h_kv, _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    group = h // h_kv
    quantized = k_scales is not None
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    dpad = (128 - d % 128) % 128
    if dpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dpad)))
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, dpad)))
    dp = d + dpad

    qr = jnp.broadcast_to(q.reshape(b * h, 1, dp), (b * h, _Q_ROWS, dp))

    in_specs = [
        pl.BlockSpec((1, _Q_ROWS, dp),
                     lambda i, j, p, lens, bt: (i * h + j, 0, 0)),
        pl.BlockSpec((1, 1, page_size, dp),
                     lambda i, j, p, lens, bt: (j // group, bt[i, p], 0, 0)),
        pl.BlockSpec((1, 1, page_size, dp),
                     lambda i, j, p, lens, bt: (j // group, bt[i, p], 0, 0)),
    ]
    inputs = [qr, k_pages, v_pages]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, 1, page_size, 1),
            lambda i, j, p, lens, bt: (j // group, bt[i, p], 0, 0))
        in_specs += [sc_spec, sc_spec]
        inputs += [k_scales[..., None], v_scales[..., None]]

    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=page_size,
                          num_pages=max_pages, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, max_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, _Q_ROWS, dp),
                                   lambda i, j, p, lens, bt: (i * h + j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_Q_ROWS, 128), jnp.float32),
                pltpu.VMEM((_Q_ROWS, 128), jnp.float32),
                pltpu.VMEM((_Q_ROWS, dp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, _Q_ROWS, dp), jnp.float32),
        interpret=_interpret(),
        name="paged_attention",
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      *inputs)
    return out[:, 0, :d].reshape(b, h, d).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                               scale=None, k_scales=None, v_scales=None):
    """Pure-jax twin: gather pages into contiguous caches, run plain masked
    attention. Exact reference for the kernel (and the CPU fallback)."""
    b, h, d = q.shape
    h_kv, _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bt = jnp.asarray(block_tables, jnp.int32)

    def gather(pages, scales):
        pg = pages[:, bt]  # [H_kv, B, max_pages, page_size, D]
        pg = pg.astype(jnp.float32)
        if scales is not None:
            pg = pg * scales[:, bt][..., None]
        return jnp.transpose(pg, (1, 0, 2, 3, 4)).reshape(
            b, h_kv, max_pages * page_size, d)

    k_c = gather(k_pages, k_scales)
    v_c = gather(v_pages, v_scales)
    if h_kv != h:
        rep = h // h_kv
        k_c = jnp.repeat(k_c, rep, axis=1)
        v_c = jnp.repeat(v_c, rep, axis=1)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), k_c) * scale
    ids = jnp.arange(max_pages * page_size)[None, None, :]
    s = jnp.where(ids < jnp.asarray(lengths)[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, v_c).astype(jnp.float32)


def quantize_rows_int8(x):
    """Symmetric per-row int8 quantization over the last dim.
    x [..., D] → (int8 values, f32 scales [...])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scales = jnp.maximum(amax, 1e-8) / 127.0
    vals = jnp.clip(jnp.round(x.astype(jnp.float32) / scales[..., None]),
                    -127, 127).astype(jnp.int8)
    return vals, scales


# ----------------------------------------------------------------- manager


class PagedKVCache:
    """Host-side page pool + block tables for one transformer layer.

    Functional-on-device, mutable-on-host: page arrays are jnp arrays
    replaced on every write; allocation bookkeeping (free list, per-slot
    tables) is host numpy, as in serving engines.  ``batch_size`` slots are
    sequence slots; ``free``ing a slot recycles its pages.
    """

    def __init__(self, num_pages: int, page_size: int, batch_size: int,
                 num_kv_heads: int, head_dim: int, max_pages_per_seq: int,
                 dtype=jnp.bfloat16, quantized: bool = False):
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages = max_pages_per_seq
        self.quantized = bool(quantized)
        store = jnp.int8 if quantized else dtype
        shape = (num_kv_heads, num_pages, page_size, head_dim)
        self.k_pages = jnp.zeros(shape, store)
        self.v_pages = jnp.zeros(shape, store)
        if quantized:
            self.k_scales = jnp.zeros(shape[:-1], jnp.float32)
            self.v_scales = jnp.zeros(shape[:-1], jnp.float32)
        else:
            self.k_scales = self.v_scales = None
        self.block_tables = np.zeros((batch_size, max_pages_per_seq),
                                     np.int32)
        self.lengths = np.zeros((batch_size,), np.int32)
        self._free = list(range(num_pages - 1, -1, -1))

    # -- allocation ----------------------------------------------------
    def _ensure_pages(self, slot: int, new_len: int):
        need = (new_len + self.page_size - 1) // self.page_size
        have = (self.lengths[slot] + self.page_size - 1) // self.page_size
        if need > self.max_pages:
            raise ValueError(f"sequence exceeds max_pages={self.max_pages}")
        for i in range(have, need):
            if not self._free:
                raise RuntimeError("KV page pool exhausted")
            self.block_tables[slot, i] = self._free.pop()

    def free(self, slot: int):
        used = (int(self.lengths[slot]) + self.page_size - 1) // self.page_size
        self._free.extend(int(p) for p in self.block_tables[slot, :used])
        self.block_tables[slot, :] = 0
        self.lengths[slot] = 0

    # -- writes --------------------------------------------------------
    def _store(self, rows):
        """rows [..., D] → (values, scales-or-None) in storage dtype."""
        if self.quantized:
            return quantize_rows_int8(rows)
        return rows.astype(self.k_pages.dtype), None

    def append(self, k, v):
        """Append ONE token per slot: k/v [B, H_kv, D] at each slot's current
        length (slots must all be active)."""
        bsz = k.shape[0]
        phys = np.empty((bsz,), np.int32)
        slots = np.empty((bsz,), np.int32)
        for bidx in range(bsz):
            t = int(self.lengths[bidx])
            self._ensure_pages(bidx, t + 1)
            phys[bidx] = self.block_tables[bidx, t // self.page_size]
            slots[bidx] = t % self.page_size
        kq, ks = self._store(k)
        vq, vs = self._store(v)
        # [B,H,D] → [H,B,D] scatter at (head, phys[b], slot[b])
        self.k_pages = self.k_pages.at[:, phys, slots].set(
            jnp.swapaxes(kq, 0, 1))
        self.v_pages = self.v_pages.at[:, phys, slots].set(
            jnp.swapaxes(vq, 0, 1))
        if self.quantized:
            self.k_scales = self.k_scales.at[:, phys, slots].set(
                jnp.swapaxes(ks, 0, 1))
            self.v_scales = self.v_scales.at[:, phys, slots].set(
                jnp.swapaxes(vs, 0, 1))
        self.lengths += 1

    def prefill(self, k, v):
        """Write a whole prompt: k/v [B, S0, H_kv, D] into fresh slots."""
        bsz, s0 = k.shape[:2]
        for bidx in range(bsz):
            if self.lengths[bidx]:
                raise ValueError("prefill into non-empty slot; free() first")
            self._ensure_pages(bidx, s0)
        logical = np.arange(s0)
        phys = self.block_tables[:bsz, logical // self.page_size]  # [B,S0]
        slots = np.broadcast_to(logical % self.page_size, (bsz, s0))
        kq, ks = self._store(k)
        vq, vs = self._store(v)
        # [B,S0,H,D] → [H,B,S0,D]
        self.k_pages = self.k_pages.at[:, phys, slots].set(
            jnp.transpose(kq, (2, 0, 1, 3)))
        self.v_pages = self.v_pages.at[:, phys, slots].set(
            jnp.transpose(vq, (2, 0, 1, 3)))
        if self.quantized:
            self.k_scales = self.k_scales.at[:, phys, slots].set(
                jnp.transpose(ks, (2, 0, 1)))
            self.v_scales = self.v_scales.at[:, phys, slots].set(
                jnp.transpose(vs, (2, 0, 1)))
        self.lengths[:bsz] += s0

    # -- attend --------------------------------------------------------
    def attend(self, q):
        """Decode attention for the current state: q [B, H, D] → [B, H, D]."""
        fn = (paged_decode_attention if jax.default_backend() == "tpu"
              else paged_decode_attention_ref)
        return fn(q, self.k_pages, self.v_pages,
                  jnp.asarray(self.block_tables), jnp.asarray(self.lengths),
                  k_scales=self.k_scales, v_scales=self.v_scales)


# ------------------------------------------------ slab-paged kernel (v2)
# The engine's throughput path. Pages are stored slab-style
# [P, page_size, Hkv*D] (contiguous 128-lane-aligned rows). One program per
# (batch element, window of its block table) gathers that window's LIVE
# pages HBM→VMEM with explicit async DMA (block table scalar-prefetched,
# copies all issued before one wait), then folds the window into an online
# softmax carried in VMEM scratch (decode_attention._softmax_window). A
# sequence whose K and V fit _WINDOW_BYTES is one window — the GPT-2 small
# geometry the kernel was written at; at llama2_7b widths (4096 lanes) a
# 2048-token sequence is 4 windows, where the whole sequence resident at
# once (2 x 16 MiB) is refused by the chip's compiler.
# The v1 kernel above runs grid (B, H, max_pages) — at GPT-2 serving shapes
# that is ~6000 programs/layer whose per-program cost (~0.5 us) dwarfs the
# ~30 us of actual bandwidth, measured 18x slower than the contiguous slab
# path; this design needs B x windows programs and copies only
# ceil(len/ps) pages.


def _window_pages(max_pages, page_size, lanes, itemsize):
    """Pages of one window: as many as hold _WINDOW_BYTES of K plus V."""
    return max(1, min(max_pages,
                      _WINDOW_BYTES // (2 * page_size * lanes * itemsize)))


def _gather_window(b, first, live, bt_ref, kp_ref, vp_ref, sc_ref, kwin,
                   vwin, scwin, kv_sem, sc_sem, *, page_size, quantized):
    """DMA logical pages [first, first + live) of row ``b`` into the window
    scratch and zero the rest of it."""
    win_pages = kwin.shape[0]

    def issue(j, _):
        pg = bt_ref[b, first + j]
        pltpu.make_async_copy(
            kp_ref.at[pl.ds(pg, 1)], kwin.at[pl.ds(j, 1)], kv_sem).start()
        pltpu.make_async_copy(
            vp_ref.at[pl.ds(pg, 1)], vwin.at[pl.ds(j, 1)], kv_sem).start()
        if quantized:
            pltpu.make_async_copy(
                sc_ref.at[pl.ds(pg, 1)], scwin.at[pl.ds(j, 1)],
                sc_sem).start()
        return _

    jax.lax.fori_loop(0, live, issue, 0)

    # scratch persists across grid steps: zero the dead tail while the live
    # DMAs fly (stale NaN patterns would poison the PV dot via 0*NaN)
    def ztail(j, _):
        # tpulint: disable=TPL402 -- kwin/vwin/scwin are Pallas VMEM scratch
        # Refs: in-place Ref stores ARE the kernel-side memory model, the
        # closure is over memory handles, not traced values
        kwin[pl.ds(j, 1)] = jnp.zeros((1, page_size, kwin.shape[-1]),
                                      kwin.dtype)
        # tpulint: disable=TPL402 -- same scratch-Ref store as above
        vwin[pl.ds(j, 1)] = jnp.zeros((1, page_size, vwin.shape[-1]),
                                      vwin.dtype)
        if quantized:
            # tpulint: disable=TPL402 -- same scratch-Ref store as above
            scwin[pl.ds(j, 1)] = jnp.zeros((1, page_size, 128), scwin.dtype)
        return _

    jax.lax.fori_loop(live, win_pages, ztail, 0)

    # DMA semaphores count bytes: drain with same-sized descriptors, one
    # wait per issued copy
    def drain_kv(i, _):
        pltpu.make_async_copy(
            kp_ref.at[pl.ds(0, 1)], kwin.at[pl.ds(0, 1)], kv_sem).wait()
        return _

    jax.lax.fori_loop(0, 2 * live, drain_kv, 0)
    if quantized:
        def drain_sc(i, _):
            pltpu.make_async_copy(
                sc_ref.at[pl.ds(0, 1)], scwin.at[pl.ds(0, 1)],
                sc_sem).wait()
            return _

        jax.lax.fori_loop(0, live, drain_sc, 0)


def _window_heads(kwin, vwin, scwin, *, head_dim, quantized):
    """Per-KV-head [W, D] f32 loaders over the gathered window
    (dequantizing int8 pages in place)."""
    win_tokens = kwin.shape[0] * kwin.shape[1]
    h_kv = kwin.shape[-1] // head_dim
    scw = scwin[...].reshape(win_tokens, 128) if quantized else None

    def part(win, sc_lo):
        def load(kh):
            x = win[:, :, kh * head_dim:(kh + 1) * head_dim].reshape(
                win_tokens, head_dim).astype(jnp.float32)
            if quantized:
                # [W, 1] scale broadcast along lanes (a [W,1]→[1,W]
                # transpose of the scale row, the previous scheme, is a
                # lane↔sublane re-layout per head — measured 2x slowdown
                # of the whole int8 decode step)
                x = x * scw[:, sc_lo + kh:sc_lo + kh + 1]
            return x
        return load

    return part(kwin, 0), part(vwin, h_kv)


def _paged_window_kernel(lim_ref, bt_ref, q_ref, kp_ref, vp_ref, sc_ref,
                         o_ref, kwin, vwin, scwin, m_sc, l_sc, acc_sc,
                         kv_sem, sc_sem, *, scale, num_heads, head_dim, m,
                         page_size, max_pages, quantized):
    """Decode (m = 0: every query row attends tokens < lim[b], the row's
    length) and verify/suffix (m >= 1: query row j attends tokens
    < lim[b] + j + 1, lim the row's base length) over one window."""
    b = pl.program_id(0)
    w = pl.program_id(1)
    win_pages = kwin.shape[0]
    win_tokens = win_pages * page_size
    seq = max_pages * page_size
    # the window set must cover every live token: the cached context plus,
    # for verify, the freshly written slab. Clamped at the table capacity
    # like the refs, so an overshooting row (a length or base + m past it)
    # never drives OOB block-table reads or DMA writes past the scratch
    covered = jnp.minimum(lim_ref[b] + m, seq)
    npages = (covered + page_size - 1) // page_size
    first = w * win_pages
    live = jnp.clip(npages - first, 0, win_pages)

    @pl.when(w == 0)
    def _init():
        _softmax_init(m_sc, l_sc, acc_sc)

    # a window past the row's last live page holds nothing: no DMA, no math
    @pl.when(live > 0)
    def _window():
        _gather_window(b, first, live, bt_ref, kp_ref, vp_ref, sc_ref, kwin,
                       vwin, scwin, kv_sem, sc_sem, page_size=page_size,
                       quantized=quantized)
        rows = q_ref.shape[1]
        col = first * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, win_tokens), 1)
        if m:
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, win_tokens), 0)
            # causal per-position limits — the ref's `limit` expression
            mask = col < jnp.minimum(lim_ref[b] + row + 1, seq)
        else:
            mask = col < lim_ref[b]
        k_of, v_of = _window_heads(kwin, vwin, scwin, head_dim=head_dim,
                                   quantized=quantized)
        _softmax_window(q_ref, k_of, v_of, mask, m_sc, l_sc, acc_sc,
                        scale=scale, num_heads=num_heads, head_dim=head_dim,
                        group=num_heads * head_dim // kwin.shape[-1])

    @pl.when(w == pl.num_programs(1) - 1)
    def _finish():
        _softmax_finish(o_ref, l_sc, acc_sc, num_heads=num_heads,
                        head_dim=head_dim)


def _paged_window_call(lim, block_tables, qr, k_pages, v_pages, scale_pages,
                       out_dtype, *, scale, num_heads, head_dim, m,
                       interpret):
    """The one pallas_call behind the decode and verify slab kernels.
    ``qr`` [B, R, H*D] (R a sublane-tile multiple of query rows)."""
    b, rows, hd = qr.shape
    _, page_size, khd = k_pages.shape
    max_pages = block_tables.shape[1]
    quantized = scale_pages is not None
    if scale_pages is None:
        scale_pages = jnp.zeros((1, page_size, 128), jnp.bfloat16)
    itemsize = jnp.dtype(k_pages.dtype).itemsize
    win_pages = _window_pages(max_pages, page_size, khd, itemsize)
    # resident at once: the window, the double-buffered q and out blocks,
    # the accumulator, and per-head f32 temporaries ([W, D] K/V slices,
    # [R, W] scores). The compiler's default budget (16 MiB on v5e) holds
    # a decode step; a wide verify slab (chunked prefill's m = hundreds of
    # rows x 4096 lanes) needs the explicit limit
    win_tokens = win_pages * page_size
    resident = (2 * win_tokens * khd * itemsize
                + 2 * rows * hd * (jnp.dtype(qr.dtype).itemsize
                                   + jnp.dtype(out_dtype).itemsize)
                + rows * hd * 4
                + 4 * win_tokens * (head_dim + rows) * 4)
    return pl.pallas_call(
        functools.partial(
            _paged_window_kernel, scale=scale, num_heads=num_heads,
            head_dim=head_dim, m=m, page_size=page_size,
            max_pages=max_pages, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, -(-max_pages // win_pages)),
            in_specs=[
                pl.BlockSpec((1, rows, hd), lambda i, w, lim, bt: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, hd),
                                   lambda i, w, lim, bt: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((win_pages, page_size, khd), k_pages.dtype),
                pltpu.VMEM((win_pages, page_size, khd), k_pages.dtype),
                pltpu.VMEM((win_pages, page_size, 128), jnp.bfloat16),
                *_softmax_scratch(rows, num_heads, head_dim),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=(None if resident <= _DEFAULT_VMEM_BYTES
                              else resident + (resident >> 2))),
        interpret=interpret,
        # m == 0: one query row a sequence (decode); m > 0: the verify slab
        name="paged_attention_slab" if m == 0 else "paged_attention_verify",
    )(jnp.asarray(lim, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      qr, k_pages, v_pages, scale_pages)


def paged_slab_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                num_heads, scale=None, scale_pages=None):
    """Slab-paged decode attention.

    q [B, H, D]; pages [P, page_size, Hkv*D]; block_tables [B, max_pages];
    lengths [B]. ``scale_pages`` [P, page_size, 128] bf16 activates the
    int8 path: data pages are int8 with per-token-per-head symmetric
    scales packed into a 128-lane scale page (k scales at lanes [0, Hkv),
    v scales at [Hkv, 2*Hkv) — a full-lane minor so the page tiles/DMAs,
    unlike a [.., Hkv]-minor scale array). Returns [B, H, D].

    Sharded-pool dispatch (ISSUE 11): every shape here may be a PER-SHARD
    view — under the serving runner's ``shard_map`` the pool arrives as
    ``[P, page_size, (Hkv/tp)*D]`` and q as the shard's ``H/tp`` heads.
    The kernel/ref math is already local (head counts derive from the
    operand shapes, GQA group = local H / local Hkv), so the same
    dispatch serves both; the guard below catches a mis-sharded pool
    (lanes that split a head) before it becomes silent garbage."""
    b, h, d = q.shape
    khd = k_pages.shape[-1]
    if khd % d:
        raise ValueError(
            f"page lanes ({khd}) must hold whole KV heads of head_dim="
            f"{d} — a TP shard that splits a head mid-lane cannot "
            "attend (tp must divide num_kv_heads)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if _interpret() or khd % 128 or (h * d) % 128:
        # CPU, or sub-128-lane rows (tiny test configs): the jnp twin —
        # sub-tile lane layouts don't lower through Mosaic
        return _paged_slab_ref(q, k_pages, v_pages, block_tables, lengths,
                               scale, scale_pages)
    qr = jnp.broadcast_to(q.reshape(b, 1, h * d), (b, _Q_ROWS, h * d))
    out = _paged_window_call(
        lengths, block_tables, qr, k_pages, v_pages, scale_pages, q.dtype,
        scale=scale, num_heads=h, head_dim=d, m=0, interpret=False)
    return out[:, 0].reshape(b, h, d)


def _paged_slab_ref(q, k_pages, v_pages, block_tables, lengths, scale,
                    scale_pages=None):
    """jnp twin of the slab-paged kernel (CPU path / exact reference)."""
    b, h, d = q.shape
    p_total, page_size, khd = k_pages.shape
    h_kv = khd // d
    bt = jnp.asarray(block_tables, jnp.int32)
    max_pages = bt.shape[1]

    def window(pages, sc):
        win = pages[bt].astype(jnp.float32)  # [B, max_pages, ps, KHD]
        win = win.reshape(b, max_pages * page_size, h_kv, d)
        if sc is not None:
            win = win * sc.astype(jnp.float32)[..., None]
        return jnp.swapaxes(win, 1, 2)  # [B, Hkv, S, D]

    ks = vs = None
    if scale_pages is not None:
        scw = scale_pages[bt].reshape(b, max_pages * page_size, 128)
        ks, vs = scw[..., :h_kv], scw[..., h_kv:2 * h_kv]
    k_c = window(k_pages, ks)
    v_c = window(v_pages, vs)
    from .decode_attention import decode_attention_ref

    return decode_attention_ref(q, k_c, v_c, lengths, scale).astype(q.dtype)


# ------------------------------------------ verify/suffix slab kernel (v3)
# The multi-query twin of the slab decode kernel (ISSUE 9 tentpole a), the
# same windowed program with m query rows: row j of batch element b attends
# tokens < base_len[b] + j + 1, exactly `_paged_multi_query_ref`'s
# causal-window semantics, over the cached prefix PLUS the freshly written
# slab. ONE kernel replaces the jnp window-gather for spec-decode verify
# (m = k+1), prefix-cache suffix prefill (per-row widths, base 0 on miss
# rows) and chunked prefill (m = chunk, decode rows at width 1): the gather
# of pages moves the same bytes the decode kernel moves per step, amortized
# over all m positions, with zero XLA gathers.
#
# The softmax is carried across windows and normalized after the PV dot,
# where jax.nn.softmax normalizes before it: the kernel agrees with the jnp
# reference to a few ulp of f32, not bitwise.


def paged_verify_slab_attention(q, k_pages, v_pages, block_tables,
                                base_len, scale=None, scale_pages=None,
                                interpret=False):
    """Fused multi-query verify/suffix slab attention (ISSUE 9).

    q [B, m, H, D] against slab pages [P, page_size, Hkv*D]; query j of
    row b attends the window tokens ``< base_len[b] + j + 1`` (cached
    context + causal prefix of the freshly written slab). Returns
    [B, m, H, D] f32 — ``_paged_multi_query_ref`` to a few ulp.
    ``scale_pages`` [P, ps, 128] bf16 activates the int8 path (k
    scales at lanes [0, Hkv), v at [Hkv, 2Hkv), the decode-slab layout).

    VMEM: one window of pages like the decode slab kernel; on top of it
    the q/out blocks and the accumulator are [m_pad, H*D] and the per-head
    score slab [m_pad, window] f32, so m is engine-bounded (spec k+1,
    prefill_chunk, or the suffix bucket ≤ max_position)."""
    b, m, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    mp = -(-m // _Q_ROWS) * _Q_ROWS
    qr = q.reshape(b, m, h * d)
    if mp != m:
        qr = jnp.pad(qr, ((0, 0), (0, mp - m), (0, 0)))
    out = _paged_window_call(
        base_len, block_tables, qr, k_pages, v_pages, scale_pages,
        jnp.float32, scale=scale, num_heads=h, head_dim=d, m=m,
        interpret=interpret)
    return out[:, :m].reshape(b, m, h, d)


# ------------------------------------------------- functional (jit) state


@jax.tree_util.register_pytree_node_class
class PagedCacheState:
    """Functional, jit-traceable view of one layer's paged cache — what the
    continuous-batching engine threads through a compiled decode chunk
    (reference capability: the serving cache of fused_multi_transformer_op
    driven by an analysis_predictor serving loop; TPU design: the block
    tables and lengths are ordinary traced arrays, so a whole chunk of
    decode steps compiles into ONE program and the host only intervenes at
    page-allocation boundaries).

    Slab page layout: data pages ``[P, page_size, Hkv*D]``; when quantized,
    int8 data plus bf16 ``scale_pages [P, page_size, 128]`` holding the
    per-token-per-head scales (k at lanes [0, Hkv), v at [Hkv, 2Hkv)).

    Per-slot semantics: ``lengths[b] == 0`` marks an idle slot — its writes
    are redirected to physical page 0 (the engine's reserved trash page)
    and its attention output is garbage the engine discards. Positions are
    per-slot (``lengths``), so ragged batches decode correctly — the
    advisor's round-2 finding against the scalar-time_step host path.
    """

    def __init__(self, k_pages, v_pages, scale_pages, block_tables,
                 lengths, page_size, prefill_valid=None, verify=False):
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.scale_pages = scale_pages    # [P, ps, 128] bf16 or None
        self.block_tables = block_tables  # [B, max_pages] int32 (traced)
        self.lengths = lengths            # [B] int32 (traced)
        self.page_size = int(page_size)
        # [B] int32 valid widths of a padded prompt during prefill (None →
        # the whole width is valid); models keep passing time_step=None
        self.prefill_valid = prefill_valid
        # static flag: a multi-token forward over this state is a spec-
        # decode VERIFY (append s tokens at [len, len+s) and attend each
        # over cache + causal prefix), not a prefill — see paged_forward
        self.verify = bool(verify)

    @property
    def quantized(self):
        return self.scale_pages is not None

    def positions(self, s):
        """Per-slot token positions for the next ``s`` tokens:
        slot b's tokens sit at [lengths[b], lengths[b] + s) — the ONE
        definition shared by GPT wpe lookup, LLaMA RoPE, and the page
        writes (ragged-batch position bugs come from re-deriving this).
        Clamped to the table capacity minus one: a chain-overshooting
        straggler saturates ``lengths`` AT the capacity (== max_position
        for engine-built tables), and the embedding lookup for its
        (discarded) garbage tokens must not index past the wpe/rope
        tables — OOB-gather clamping is not a contract (ADVICE r3)."""
        cap = self.block_tables.shape[1] * self.page_size
        pos = (self.lengths[:, None]
               + jnp.arange(s, dtype=jnp.int32)[None])
        return jnp.minimum(pos, cap - 1)

    def tree_flatten(self):
        return ((self.k_pages, self.v_pages, self.scale_pages,
                 self.block_tables, self.lengths, self.prefill_valid),
                (self.page_size, self.verify))

    @classmethod
    def tree_unflatten(cls, aux, children):
        page_size, verify = aux
        return cls(*children[:5], page_size, prefill_valid=children[5],
                   verify=verify)

    def replace(self, **kw):
        fields = dict(k_pages=self.k_pages, v_pages=self.v_pages,
                      scale_pages=self.scale_pages,
                      block_tables=self.block_tables, lengths=self.lengths,
                      prefill_valid=self.prefill_valid, verify=self.verify)
        fields.update(kw)
        return PagedCacheState(page_size=self.page_size, **fields)


def _store_rows(state, k, v):
    """k/v [..., Hkv, D] → (k_vals, v_vals [..., Hkv*D], scale_rows
    [..., 128] bf16 or None). Slab page layout, heads side by side."""
    lead = k.shape[:-2]
    h_kv = k.shape[-2]
    flat = lead + (h_kv * k.shape[-1],)
    if not state.quantized:
        dt = state.k_pages.dtype
        return k.astype(dt).reshape(flat), v.astype(dt).reshape(flat), None
    kq, ks = quantize_rows_int8(k)
    vq, vs = quantize_rows_int8(v)
    sc = jnp.zeros(lead + (128,), jnp.bfloat16)
    sc = sc.at[..., :h_kv].set(ks.astype(jnp.bfloat16))
    sc = sc.at[..., h_kv:2 * h_kv].set(vs.astype(jnp.bfloat16))
    return kq.reshape(flat), vq.reshape(flat), sc


def paged_state_prefill(state, k, v, real_len):
    """Write a (padded) prompt into the pages. k/v [B, S0, Hkv, D];
    ``real_len`` [B] traced — positions >= real_len scatter to the trash
    page (0), so bucketed/padded prompts are safe. Returns the new state
    with ``lengths += real_len``."""
    b, s0 = k.shape[:2]
    pos = state.positions(s0)
    valid = jnp.arange(s0, dtype=jnp.int32)[None] < real_len[:, None]
    logical = jnp.clip(pos // state.page_size, 0,
                       state.block_tables.shape[1] - 1)
    phys = jnp.where(valid,
                     jnp.take_along_axis(state.block_tables, logical, axis=1),
                     0)
    slotpos = jnp.where(valid, pos % state.page_size, 0)
    kq, vq, sc = _store_rows(state, k, v)  # [B, S0, KHD]
    new = dict(
        k_pages=state.k_pages.at[phys, slotpos].set(kq),
        v_pages=state.v_pages.at[phys, slotpos].set(vq),
        lengths=state.lengths + real_len.astype(state.lengths.dtype),
    )
    if state.quantized:
        new["scale_pages"] = state.scale_pages.at[phys, slotpos].set(sc)
    return state.replace(**new)


def paged_state_step(state, q, k, v, scale=None):
    """Append one token per active slot and attend. q [B, H, D],
    k/v [B, Hkv, D] → (out [B, H, D], new state). Idle slots (length 0)
    write to the trash page and read a garbage output the engine
    discards."""
    b = q.shape[0]
    active = state.lengths > 0
    pos = state.lengths
    logical = jnp.clip(pos // state.page_size, 0,
                       state.block_tables.shape[1] - 1)
    phys = jnp.where(active, state.block_tables[jnp.arange(b), logical], 0)
    slotpos = jnp.where(active, pos % state.page_size, 0)
    kq, vq, sc = _store_rows(state, k, v)  # [B, KHD]
    # cap lengths at the table capacity: a chained straggler that keeps
    # decoding past its budget (engine chain overshoot) must never push
    # npages past max_pages in the attention kernel — at the cap its
    # writes recirculate in the last page and its output is garbage the
    # engine was going to discard anyway
    cap = state.block_tables.shape[1] * state.page_size
    new = dict(
        k_pages=state.k_pages.at[phys, slotpos].set(kq),
        v_pages=state.v_pages.at[phys, slotpos].set(vq),
        lengths=jnp.minimum(
            state.lengths + active.astype(state.lengths.dtype), cap),
    )
    if state.quantized:
        new["scale_pages"] = state.scale_pages.at[phys, slotpos].set(sc)
    state = state.replace(**new)
    out = paged_slab_decode_attention(
        q, state.k_pages, state.v_pages, state.block_tables, state.lengths,
        q.shape[1], scale=scale, scale_pages=state.scale_pages)
    return out.astype(q.dtype), state


def _paged_multi_query_ref(q, state, base_len, scale=None):
    """Multi-position paged attention: query j of slot b attends over the
    cache window tokens ``< base_len[b] + j + 1`` — the cached context plus
    the causal prefix of the freshly written verify block. q [B, m, H, D]
    against slab pages; returns [B, m, H, D] f32.

    jnp window-gather implementation (the exact twin family of
    ``_paged_slab_ref``): materializes each slot's padded window once and
    masks per position. The CPU path and the exactness oracle for the
    fused ``paged_verify_slab_attention`` kernel — production TPU traffic
    dispatches the kernel via ``paged_multi_query_attention``.
    """
    b, m, h, d = q.shape
    p_total, page_size, khd = state.k_pages.shape
    if khd % d:
        raise ValueError(
            f"page lanes ({khd}) must hold whole KV heads of head_dim="
            f"{d} (sharded-pool dispatch: tp must divide num_kv_heads)")
    h_kv = khd // d
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bt = jnp.asarray(state.block_tables, jnp.int32)
    max_pages = bt.shape[1]
    seq = max_pages * page_size

    def window(pages, sc):
        win = pages[bt].astype(jnp.float32)  # [B, max_pages, ps, KHD]
        win = win.reshape(b, seq, h_kv, d)
        if sc is not None:
            win = win * sc.astype(jnp.float32)[..., None]
        return win  # [B, S, Hkv, D]

    ks = vs = None
    if state.quantized:
        scw = state.scale_pages[bt].reshape(b, seq, 128)
        ks, vs = scw[..., :h_kv], scw[..., h_kv:2 * h_kv]
    k_c = window(state.k_pages, ks)
    v_c = window(state.v_pages, vs)
    if h_kv != h:
        rep = h // h_kv
        k_c = jnp.repeat(k_c, rep, axis=2)
        v_c = jnp.repeat(v_c, rep, axis=2)
    s = jnp.einsum("bmhd,bshd->bmhs", q.astype(jnp.float32), k_c) * scale
    # causal per-position limits, clamped at the table capacity so an
    # overshooting verify block (positions saturated at cap-1) still
    # masks consistently with what was actually written
    limit = jnp.minimum(
        base_len[:, None] + jnp.arange(m, dtype=jnp.int32)[None] + 1, seq)
    mask = (jnp.arange(seq, dtype=jnp.int32)[None, None]
            < limit[..., None])  # [B, m, S]
    s = jnp.where(mask[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bmhs,bshd->bmhd", p, v_c)


def paged_multi_query_attention(q, state, base_len, scale=None):
    """Multi-position paged attention dispatch — the ONE entry the spec
    verifier, prefix-cache suffix prefill and chunked prefill all ride:
    the fused Pallas slab kernel on TPU at tile-aligned shapes (one
    ``pallas_call``, zero gathers), the jnp window-gather twin elsewhere
    (CPU tier-1, or sub-128-lane test configs that don't lower through
    Mosaic)."""
    b, m, h, d = q.shape
    khd = state.k_pages.shape[-1]
    if _interpret() or khd % 128 or (h * d) % 128:
        return _paged_multi_query_ref(q, state, base_len, scale=scale)
    return paged_verify_slab_attention(
        q, state.k_pages, state.v_pages, state.block_tables, base_len,
        scale=scale, scale_pages=state.scale_pages)


def paged_state_verify(state, q, k, v, scale=None):
    """Speculative-decoding verify step: append ``m`` tokens per active
    slot at positions [len, len+m) and score EVERY position in one pass.
    q [B, m, H, D], k/v [B, m, Hkv, D] → (out [B, m, H, D], new state with
    ``lengths += m``).

    The caller (the engine's verify program) decides post-hoc how many of
    the m freshly written rows to KEEP: it rolls ``lengths`` back to the
    accepted prefix (rejected rows become dead data past ``lengths`` that
    the next append overwrites — the same data-only-exists-up-to-lengths
    invariant the trash page relies on) and returns the headroom pages via
    ``Engine._trim_pages``. Idle slots (length 0) write to the trash page
    and read garbage the engine discards, exactly like the decode step.

    With ``state.prefill_valid`` set this is a PARTIAL PREFILL (prefix
    cache, ISSUE 8): row b holds ``lengths[b]`` cached tokens (spliced
    pages a prior request computed) and appends its ``prefill_valid[b]``
    uncached suffix tokens — every suffix position attends over the
    cached prefix plus the causal part of the fresh block, exactly the
    multi-query semantics the verify path already implements. Columns
    past a row's valid width write to the trash page and advance nothing;
    a row with ``lengths == 0`` (a cache miss sharing the wave) reduces
    to a from-scratch prefill, and a row with ``prefill_valid == 0`` (a
    pad row) is idle."""
    b, m = q.shape[:2]
    base = state.lengths
    if state.prefill_valid is not None:
        widths = jnp.asarray(state.prefill_valid, jnp.int32)
        active = widths > 0
        valid = (jnp.arange(m, dtype=jnp.int32)[None, :]
                 < widths[:, None])  # [B, m] per-row suffix mask
        adv = widths
    else:
        active = base > 0
        valid = jnp.broadcast_to(active[:, None], (b, m))
        adv = m * active.astype(state.lengths.dtype)
    pos = state.positions(m)  # [B, m], clamped at capacity - 1
    logical = jnp.clip(pos // state.page_size, 0,
                       state.block_tables.shape[1] - 1)
    phys = jnp.where(valid,
                     jnp.take_along_axis(state.block_tables, logical, axis=1),
                     0)
    slotpos = jnp.where(valid, pos % state.page_size, 0)
    kq, vq, sc = _store_rows(state, k, v)  # [B, m, KHD]
    cap = state.block_tables.shape[1] * state.page_size
    new = dict(
        k_pages=state.k_pages.at[phys, slotpos].set(kq),
        v_pages=state.v_pages.at[phys, slotpos].set(vq),
        lengths=jnp.minimum(
            base + adv.astype(state.lengths.dtype), cap),
    )
    if state.quantized:
        new["scale_pages"] = state.scale_pages.at[phys, slotpos].set(sc)
    state = state.replace(**new)
    out = paged_multi_query_attention(q, state, base, scale=scale)
    return out.astype(q.dtype), state


def paged_forward(cache: "PagedKVCache", q, k, v, time_step,
                  context_attention):
    """Shared model-side paged-cache step (one copy for every attention
    layer — GPT, LLaMA, FusedMultiTransformer). Eager/serving only: the
    manager mutates host-side block tables.

    ``q/k/v``: [b, s, heads, head_dim] Tensors or raw arrays (unwrapped
    here — the callers share this glue). Prefill (``time_step`` None)
    writes the prompt and returns ``context_attention()``'s result; decode
    appends one token and attends over the pages. Decode validates that the
    caller's ``time_step`` equals EVERY slot's cache length — a replayed or
    skipped step corrupts a paged cache silently (append ≠ overwrite), and
    ragged per-slot lengths need the functional ``PagedCacheState`` path
    (per-slot positions), so either disagreement must be an error.

    With a ``PagedCacheState`` (the compiled engine path) everything is
    traced and ``time_step`` is ignored: prefill takes per-slot valid
    widths from ``state.prefill_valid`` (None → the full padded width) and
    decode positions each slot at its own length. ALWAYS returns
    ``(out, cache)`` (the host-managed cache returns itself)."""
    q, k, v = (getattr(t, "_data", t) for t in (q, k, v))
    if isinstance(cache, PagedCacheState):
        # spec-decode verify (static flag, checked FIRST: a verify block
        # is multi-token and would otherwise mis-route to prefill, whose
        # context_attention ignores the cached prefix)
        if cache.verify:
            out, new_state = paged_state_verify(cache, q, k, v)
            return out, new_state
        # prefill when the state carries prefill_valid (the engine sets it
        # for every admission — including single-token prompts, which the
        # old s > 1 heuristic mis-routed to the decode path) or when the
        # prompt is plainly multi-token
        if cache.prefill_valid is not None or q.shape[1] > 1:
            s0 = k.shape[1]
            real_len = (jnp.full((q.shape[0],), s0, jnp.int32)
                        if cache.prefill_valid is None
                        else jnp.asarray(cache.prefill_valid, jnp.int32))
            new_state = paged_state_prefill(cache, k, v, real_len)
            return context_attention(), new_state
        out, new_state = paged_state_step(cache, q[:, 0], k[:, 0], v[:, 0])
        return out[:, None], new_state
    if time_step is None:
        cache.prefill(k, v)
        return context_attention(), cache
    ts = int(time_step)
    if not np.all(cache.lengths == ts):
        raise ValueError(
            f"paged decode at time_step={ts} but cache slots hold "
            f"{cache.lengths.tolist()} tokens — paged caches append; replay/"
            "skip requires free()+prefill, and ragged per-slot lengths need "
            "the functional PagedCacheState engine path")
    cache.append(k[:, 0], v[:, 0])
    return cache.attend(q[:, 0])[:, None], cache
