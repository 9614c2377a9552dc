"""Mamba-1's selective scan as one Pallas kernel pair: ``selective_scan_fwd``
/ ``selective_scan_bwd``.

    h_t[c, n] = exp(delta_t[c] A[c, n]) h_{t-1}[c, n] + delta_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]

The decay is one number for every channel AND state (Mamba-2's is one a
head, which is what lets ``ssd_scan.py`` turn a chunk into matrix products):
here nothing is a product of matrices, every (channel, state) pair is a
recurrence of its own over the positions, and the work is the vector
unit's and the exponential's. Whole, the states are ``[S, channels,
states]`` float32 (1.34 GB a layer at 8192 x 2560 x 16), so they never
leave fast memory: the grid walks the sequence in chunks of ``CHUNK``
positions (sequential, ``"arbitrary"``), the running state ``[states,
channels]`` float32 lives in VMEM scratch for the whole row, and the
forward writes only the state every chunk STARTS from (``S / CHUNK x
states x channels`` float32: 21 MB at the size above), the backward's
residual.

Layout. Channels lie on sublanes and lanes: ``[.., channels]`` is viewed as
``[.., channels / 128, 128]``, so one vector register holds 1024 channels
of ONE state and one position; a state's ``B_t[n]`` and ``C_t[n]`` are then
scalars, read from SMEM and splat. Nothing is broadcast along a register
and nothing is reduced in the forward (``y`` accumulates over the states
register by register). A program walks its chunk once for every group of 8
rows of 128 channels (2560 channels: rows 0-7, 8-15 and the half group
16-19), position by position in a ``fori_loop`` that carries the group's
``states`` registers.

* forward, per position and state: ``h <- exp(delta A) h + (delta x) B``,
  ``y += h C``; then ``y += D x``.
* backward: the chunks in reverse with ``dh`` (the gradient of the state
  LEAVING the chunk, already decayed into it) in scratch. Per group, pass 1
  walks the chunk forward from its entering state and keeps every
  position's state in scratch; pass 2 walks it in reverse: ``G = dh + dy
  C``; ``q = G h_{t-1} exp(delta A)`` is the exponent's gradient, so ``dA +=
  q delta`` and ``d delta += sum_n q A``; ``s = sum_n G B`` gives ``dx = s
  delta + D dy`` and ``d delta += s x``; ``dD += dy x``; ``dh <- exp(delta
  A) G``. ``dB_t[n] = sum_c G (delta x)`` and ``dC_t[n] = sum_c dy h_t`` are
  sums over CHANNELS, across registers' lanes: the products are kept whole
  in scratch (summed over the groups) and one matrix product with a row of
  ones a chunk sums their lanes; the 8 sublanes are summed outside.

Everything inside is float32: x is raised on load, ``delta`` comes in
float32, outputs are float32 (the caller rounds). The recurrence is
therefore the reference's to rounding order.

VMEM per program at 2560 channels, 16 states, chunk 64: forward 2.4 MB
(blocks double-buffered), backward 19 MB (``_footprint``: the kept states
4.3 MB, the two product arrays 4 MB each, ``dh`` 0.2 MB, blocks 6 MB), so
the backward raises the scoped limit to 48 MiB of the chip's 128.

Constraints (``supported``): channels a multiple of 128, at most 16 states,
``_footprint`` under ``_VMEM_BUDGET``. Any length: the tail is padded with
positions that neither decay nor add (``delta = 0``). Off the chip, and at
shapes the kernels refuse, ``selective_scan`` runs the same recurrence as a
``jax.lax.scan`` over chunks of a ``lax.scan`` over positions, each chunk
worked out again in the backward from the state it starts from: the same
chunk-boundary rule, by ``jax.checkpoint`` instead of by hand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
LANES, ROWS = 128, 8
_MAX_STATES = 16
_VMEM_LIMIT = 48 * 1024 * 1024
_VMEM_BUDGET = 40 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _groups(rows):
    """The rows of 128 channels in groups of 8 (one register a state)."""
    return [slice(r, min(r + ROWS, rows)) for r in range(0, rows, ROWS)]


# ------------------------------------------------------------------ kernels


def _fwd_kernel(b_ref, c_ref, x_ref, dl_ref, a_ref, d_ref, y_ref, hin_ref,
                h_scr, *, chunk, states):
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hin_ref[0, 0] = h_scr[...]
    f32 = jnp.float32
    for rows in _groups(x_ref.shape[2]):
        a = [a_ref[i, rows] for i in range(states)]
        skip = d_ref[rows]

        def step(t, h, rows=rows, a=a, skip=skip):
            x = x_ref[0, t].astype(f32)[rows]
            dl = dl_ref[0, t, rows]
            dx, y, new = dl * x, skip * x, []
            for i in range(states):
                hi = jnp.exp(dl * a[i]) * h[i] + dx * b_ref[0, 0, 0, t * states + i]
                y = y + hi * c_ref[0, 0, 0, t * states + i]
                new.append(hi)
            # tpulint: disable=TPL402 -- the position's output is written from inside the walk
            y_ref[0, t, rows] = y
            return tuple(new)

        h = jax.lax.fori_loop(
            0, chunk, step, tuple(h_scr[i, rows] for i in range(states)))
        for i in range(states):
            h_scr[i, rows] = h[i]


def _bwd_kernel(b_ref, c_ref, x_ref, dl_ref, a_ref, d_ref, hin_ref, dy_ref,
                dx_ref, ddl_ref, db_ref, dc_ref, da_ref, dd_ref,
                dh_scr, hs_scr, pb_scr, pc_scr, *, chunk, states):
    f32 = jnp.float32
    n_rows = x_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    if n_rows < ROWS:  # rows of the product arrays the one group leaves unwritten
        pb_scr[...] = jnp.zeros_like(pb_scr)
        pc_scr[...] = jnp.zeros_like(pc_scr)

    def kept(t, i, r):  # state i entering position t, rows [0, r)
        return (pl.ds(pl.multiple_of((t * states + i) * ROWS, ROWS), r),
                slice(None))

    for g, rows in enumerate(_groups(n_rows)):
        r = rows.stop - rows.start
        a = [a_ref[i, rows] for i in range(states)]
        skip = d_ref[rows]

        # pass 1: the chunk forward from its entering state, every state kept
        def forward(t, h, rows=rows, a=a, r=r):
            x = x_ref[0, t].astype(f32)[rows]
            dl = dl_ref[0, t, rows]
            dx, new = dl * x, []
            for i in range(states):
                # tpulint: disable=TPL402 -- the kept states are this pass's product
                hs_scr[kept(t, i, r)] = h[i]
                new.append(jnp.exp(dl * a[i]) * h[i]
                           + dx * b_ref[0, 0, 0, t * states + i])
            return tuple(new)

        h = jax.lax.fori_loop(
            0, chunk, forward,
            tuple(hin_ref[0, 0, i, rows] for i in range(states)))
        for i in range(states):
            hs_scr[kept(chunk, i, r)] = h[i]

        # pass 2: the chunk in reverse
        def reverse(j, carry, g=g, rows=rows, a=a, r=r, skip=skip):
            dh, da, dd = carry
            t = chunk - 1 - j
            x = x_ref[0, t].astype(f32)[rows]
            dl = dl_ref[0, t, rows]
            dy = dy_ref[0, t, rows]
            dx = dl * x
            s = jnp.zeros_like(x)
            ddl = jnp.zeros_like(x)
            new_dh, new_da = [], []
            for i in range(states):
                decay = jnp.exp(dl * a[i])
                grad = dh[i] + dy * c_ref[0, 0, 0, t * states + i]
                q = grad * hs_scr[kept(t, i, r)] * decay
                new_da.append(da[i] + q * dl)
                ddl = ddl + q * a[i]
                s = s + grad * b_ref[0, 0, 0, t * states + i]
                at = kept(t, i, r)
                pb, pc = grad * dx, dy * hs_scr[kept(t + 1, i, r)]
                # tpulint: disable=TPL301 -- g is the group's static index, not a traced value
                if g:
                    pb, pc = pb_scr[at] + pb, pc_scr[at] + pc
                # tpulint: disable=TPL402 -- the products wait for the chunk's one sum over lanes
                pb_scr[at] = pb
                # tpulint: disable=TPL402 -- the products wait for the chunk's one sum over lanes
                pc_scr[at] = pc
                new_dh.append(decay * grad)
            # tpulint: disable=TPL402 -- the position's gradients are written from inside the walk
            dx_ref[0, t, rows] = s * dl + skip * dy
            # tpulint: disable=TPL402 -- the position's gradients are written from inside the walk
            ddl_ref[0, t, rows] = ddl + s * x
            return tuple(new_dh), tuple(new_da), dd + dy * x

        zero = jnp.zeros((r, LANES), f32)
        dh, da, dd = jax.lax.fori_loop(
            0, chunk, reverse,
            (tuple(dh_scr[i, rows] for i in range(states)),
             (zero,) * states, zero))
        for i in range(states):
            dh_scr[i, rows] = dh[i]
            da_ref[0, i, rows] = da_ref[0, i, rows] + da[i]
        dd_ref[0, rows] = dd_ref[0, rows] + dd

    # the sums over channels: lanes by one product with ones, the 8 sublanes
    # (and the batch's rows) outside
    ones = jnp.ones((ROWS, LANES), f32)
    lanes = lambda p: jax.lax.dot_general(
        ones, p, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32)[0:1]
    db_ref[0, 0] = lanes(pb_scr[...])
    dc_ref[0, 0] = lanes(pc_scr[...])


# ------------------------------------------------------------ pallas calls


def _specs(chunk, states, rows, chunk_of):
    """Block specs: B or C in SMEM ``[b, chunks, 1, chunk states]``; x, delta,
    y ``[b, s, rows, 128]``; A ``[states, rows, 128]``; D ``[rows, 128]``;
    the entering states ``[b, chunks, states, rows, 128]``."""
    smem = pl.BlockSpec((1, 1, 1, chunk * states),
                        lambda bi, ci: (bi, chunk_of(ci), 0, 0),
                        memory_space=pltpu.SMEM)
    wide = pl.BlockSpec((1, chunk, rows, LANES),
                        lambda bi, ci: (bi, chunk_of(ci), 0, 0))
    a = pl.BlockSpec((states, rows, LANES), lambda bi, ci: (0, 0, 0))
    d = pl.BlockSpec((rows, LANES), lambda bi, ci: (0, 0))
    state = pl.BlockSpec((1, 1, states, rows, LANES),
                         lambda bi, ci: (bi, chunk_of(ci), 0, 0, 0))
    return smem, wide, a, d, state


def _sizes(x, a):
    b, s, rows, _ = x.shape
    return b, s, rows, a.shape[0]


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _fwd_traced(interpret, chunk, bm, cm, x, dl, a, d):
    b, s, rows, states = _sizes(x, a)
    nc = s // chunk
    smem, wide, a_spec, d_spec, state = _specs(chunk, states, rows,
                                               lambda ci: ci)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, states=states),
        grid=(b, nc),
        in_specs=[smem, smem, wide, wide, a_spec, d_spec],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, nc, states, rows, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((states, rows, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_fwd",
    )(bm, cm, x, dl, a, d)


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _bwd_traced(interpret, chunk, bm, cm, x, dl, a, d, entering, dy):
    b, s, rows, states = _sizes(x, a)
    nc = s // chunk
    f32 = jnp.float32
    back = lambda ci: nc - 1 - ci
    smem, wide, a_spec, d_spec, state = _specs(chunk, states, rows, back)
    summed = pl.BlockSpec((1, 1, 1, chunk * states * ROWS),
                          lambda bi, ci: (bi, back(ci), 0, 0))
    products = chunk * states * ROWS
    dx, ddl, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, states=states),
        grid=(b, nc),
        in_specs=[smem, smem, wide, wide, a_spec, d_spec, state, wide],
        out_specs=[wide, wide, summed, summed,
                   pl.BlockSpec((1, states, rows, LANES),
                                lambda bi, ci: (bi, 0, 0, 0)),
                   pl.BlockSpec((1, rows, LANES), lambda bi, ci: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct((b, nc, 1, chunk * states * ROWS),
                                        f32),
                   jax.ShapeDtypeStruct((b, nc, 1, chunk * states * ROWS),
                                        f32),
                   jax.ShapeDtypeStruct((b, states, rows, LANES), f32),
                   jax.ShapeDtypeStruct((b, rows, LANES), f32)],
        scratch_shapes=[pltpu.VMEM((states, rows, LANES), f32),
                        pltpu.VMEM((products + states * ROWS, LANES), f32),
                        pltpu.VMEM((products, LANES), f32),
                        pltpu.VMEM((products, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="selective_scan_bwd",
    )(bm, cm, x, dl, a, d, entering, dy)
    # [b, chunks, 1, chunk states 8] -> [b, s, states]: the sublanes' sum
    by_state = lambda t: jnp.sum(
        t.reshape(b, s, states, ROWS), axis=-1)
    return (by_state(db), by_state(dc), dx, ddl, jnp.sum(da, axis=0),
            jnp.sum(dd, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(chunk, bm, cm, x, dl, a, d):
    return _fwd_traced(_interpret(), chunk, bm, cm, x, dl, a, d)[0]


def _scan_fwd(chunk, bm, cm, x, dl, a, d):
    y, entering = _fwd_traced(_interpret(), chunk, bm, cm, x, dl, a, d)
    return y, (bm, cm, x, dl, a, d, entering)


def _scan_bwd(chunk, res, dy):
    bm, cm, x = res[:3]
    db, dc, dx, ddl, da, dd = _bwd_traced(_interpret(), chunk, *res, dy)
    flat = lambda t, like: t.reshape(like.shape).astype(like.dtype)
    return flat(db, bm), flat(dc, cm), dx.astype(x.dtype), ddl, da, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


def _scan_kernels(x, delta, a, bm, cm, d, chunk):
    b, s, c = x.shape
    n, rows, nc = a.shape[1], c // LANES, s // chunk
    f32 = jnp.float32
    wide = lambda t: t.reshape(b, s, rows, LANES)
    scalars = lambda t: t.astype(f32).reshape(b, nc, 1, chunk * n)
    y = _scan(chunk, scalars(bm), scalars(cm), wide(x), wide(delta.astype(f32)),
              a.astype(f32).T.reshape(n, rows, LANES),
              d.astype(f32).reshape(rows, LANES))
    return y.reshape(b, s, c)


# ------------------------------------------------------------ the lax form


def _scan_lax(x, delta, a, bm, cm, d, chunk):
    """The recurrence as written, a ``lax.scan`` over chunks of a
    ``lax.scan`` over positions, float32; each chunk is worked out again in
    the backward from the state it starts from."""
    b, s, c = x.shape
    n = a.shape[1]
    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)
    # [chunks, chunk, b, ..]: positions lead inside a chunk
    chunks = lambda t: jnp.moveaxis(
        t.astype(f32).reshape(b, s // chunk, chunk, -1), 0, 2)

    def position(h, inp):
        x_t, dl_t, b_t, c_t = inp
        h = (jnp.exp(dl_t[..., None] * a) * h
             + (dl_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d * x_t

    def one_chunk(h, inp):
        return jax.lax.scan(position, h, inp)

    _, y = jax.lax.scan(jax.checkpoint(one_chunk), jnp.zeros((b, c, n), f32),
                        tuple(chunks(t) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 2, 0).reshape(b, s, c)


# ------------------------------------------------------------------- public


def _footprint(chunk, channels, states):
    """Bytes of VMEM one program of the backward (the larger of the two)
    holds in blocks, each double-buffered, and in scratch."""
    rows = -(-(channels // LANES) // ROWS) * ROWS
    wide = 4 * chunk * rows * LANES                  # x, delta, dy, dx, ddelta
    state = 4 * states * rows * LANES                # A, entering, dA, dh
    kept = 4 * (chunk + 1) * states * ROWS * LANES   # states, two products
    summed = 4 * ROWS * chunk * states * ROWS
    return 2 * (5 * wide + 3 * state + 2 * summed) + state + 3 * kept


def supported(seq: int, channels: int, states: int,
              chunk: int = CHUNK) -> bool:
    """Whether the kernels take a scan of ``channels`` channels with
    ``states`` numbers each (any ``seq``: the tail is padded)."""
    if seq < 1 or channels % LANES or not 1 <= states <= _MAX_STATES:
        return False
    return _footprint(chunk, channels, states) <= _VMEM_BUDGET


def enabled(seq: int, channels: int, states: int,
            chunk: int = CHUNK) -> bool:
    """Whether ``selective_scan`` takes the kernels: on the TPU, at a shape
    they support."""
    return (jax.default_backend() == "tpu"
            and supported(seq, channels, states, chunk))


def selective_scan(x, delta, a, bm, cm, d, chunk: int = CHUNK):
    """Mamba-1's recurrence over positions, float32 inside: x ``[b, s, c]``
    (any float type), ``delta`` ``[b, s, c]`` (positive), a ``[c, n]``
    (negative), B and C ``[b, s, n]``, the skip's weights d ``[c]``.
    Returns ``y`` ``[b, s, c]`` float32, ``D x`` included. Differentiable in
    all six."""
    s, c = x.shape[1:]
    pad = -s % chunk
    if pad:  # positions with delta = 0 neither decay the state nor add to it
        x, delta, bm, cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                            for t in (x, delta, bm, cm))
    run = _scan_kernels if enabled(s, c, a.shape[1], chunk) else _scan_lax
    return run(x, delta, a, bm, cm, d, chunk)[:, :s]
