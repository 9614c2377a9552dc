"""The chunk walk of Mamba-2's state-space recurrence (SSD) as one Pallas
kernel pair: ``ssd_scan_fwd`` / ``ssd_scan_bwd``.

``models/nemotron_h.py::ssd_chunked`` forms, in ``jax.numpy``, the cumulative
decay exponent inside every chunk (``acs``, never positive); everything after
that runs here: ``dt x``, the within-chunk term, what the chunk adds to the
state, the carry from chunk to chunk, the read-out of the state a chunk
starts from, and the skip ``D x``. One program handles one (batch, group,
chunk); the chunk axis of the grid is sequential (``"arbitrary"``) and the
running state of the group's heads, ``[e p, n]`` float32, lives in VMEM
scratch for the whole row, so no chunk state travels through HBM but the one
copy the backward reads (the ``jax.numpy`` lines write, re-lay, cast and scan
two state-shaped tensors forward and again as gradients). ``dt x`` and ``D x``
are inside because every elementwise pass over ``[.., e, p]`` beside the
custom call made the compiler re-lay 67 MB from its positions-minor layout to
the row-major one a kernel reads (PERF.md, PR 29).

Per program, with C, B ``[q, n]`` the group's rows of the chunk, ``col`` /
``row`` a head's ``acs`` as a column / a row, ``last = acs[q-1]``, h ``[e p,
n]`` the heads' states entering the chunk, stacked:

* forward: ``S = C B^T`` and ``R = C lo(h)^T`` once for the group; per head
  ``D = exp(col - row)`` on and below the diagonal, else 0; ``L = lo(S * D)``;
  ``xdt = lo(x * dt)``; ``y = L xdt + exp(col) * R + D_skip x``; ``U =
  lo(xdt * exp(last - col))``; then for all heads at once ``U^T B``, and per
  head ``h <- exp(last) h + (U^T B)``. Writes y (float32) and the ENTERING
  state of every chunk (float32), the backward's residual.
* backward: the same walk with the chunk index reversed and ``dh``, the
  gradient of the state LEAVING the chunk, in scratch. Rebuilds S, D, L, R,
  xdt, U; for the group ``dU = B lo(dh)^T``; per head ``dS += (dy xdt^T) *
  D``; ``dxdt = L^T dy + exp(last - col) * dU``; ``dx = dxdt * dt + D_skip
  dy``; ``d dt = sum_p(dxdt * x)``; the skip's gradient ``sum_q(dy * x)`` a
  program (summed over programs outside); then over all heads at once (one
  product each, contracting over ``e p``) ``dC = lo(exp(col) * dy) lo(h) +
  dS B``, ``dB = U lo(dh) + dS^T C`` and ``(exp(col) * dy)^T C``, and per head
  ``dh <- exp(last) dh + ((exp(col) * dy)^T C)``.
* the exponents' gradient in the backward: ``d acs_i = sum_p(dy_lo * (L xdt)
  + (exp(col) * dy) * R - xdt * dxdt)_i`` and at the chunk's last position
  also ``sum_j(exp(last - col_j) sum_p(dU * xdt)_j) + exp(last) sum(dh * h)``.
  That is ``rowsum(G) - colsum(G) + exp(col) sum_p(dy * R) - T`` with ``G = dL
  * S * D`` folded into products that are there anyway (no ``[q, q]``
  reduction, no transposed vector); both sides of a pair (i, j) are the SAME
  products ``dy_lo[i] L[i, j] xdt[j]``, as jax's one array G gives both, so
  what reaches ``a`` through the cumulative sum is the pair's own span and
  not the difference of two roundings over the whole chunk.

Precisions are ``ssd_chunked``'s: products take their operands in ``lo`` (x's
type, bfloat16 in training) and accumulate in float32; exponents, decays,
the carried state and the sums are float32; ``xdt``, ``L``, ``U`` and the
state read by ``R`` are rounded to ``lo`` exactly where the ``jax.numpy`` lines
round them. Cotangents enter the products rounded to ``lo`` (what the chip's
default precision does to jax's own transposes); everything else of the
backward is float32 (``dxdt`` is not rounded before ``dx`` and ``d dt`` are
taken from it, as jax's transpose does). Every exponent is a span running
forward in time; the mask is applied to the exponent, so nothing overflows
and no ``inf - inf`` can form.

VMEM per program at the cell's shapes (q = n = 128, e = 16 heads of p = 64,
bfloat16; blocks double-buffered): forward 3.6 MB (x 256 KB, B and C 32 KB
each, ``acs``, its transpose and dt 136 KB padded, y 512 KB, the state out
512 KB; scratch: the state 512 KB, U 256 KB), backward 4.9 MB (``_footprint``:
the same inputs and dy 512 KB, the residual state 512 KB; dx 256 KB, dB, dC,
``d acs``, ``d dt``; scratch: dh 512 KB, U and ``lo(exp(col) * dy)`` 256 KB
each), both under the 16 MiB scoped limit with room for the unrolled body's
temporaries (compiled for a described v5e up to 32 heads a group, chunks of
256, float32 operands: ``tests/test_chip_compile.py``).

Constraints (``supported``): chunk and state multiples of 128 (the [q, q] and
[q, n] tiles fill the matrix unit and the lanes), ``e * p`` a multiple of 128
lanes, p a multiple of 8, ``_footprint`` under ``_VMEM_BUDGET``. Any number
of groups: the grid's second axis. The sequence is a whole number of chunks
(``ssd_chunked`` pads). Both kernels unroll the group's heads: the per-head
body is a jitted function of values, so a kernel's trace holds it once, and
each kernel is traced once per shape (``jax.jit(inline=True)``) and inlined
under each caller's scope. Measured on one v5e chip at ``[4, 4096, 16, 64]``, n = 128
(PR 29): forward 0.57 ms, backward 1.53 ms a call in the training step, 2.33
ms the pair alone against 4.51 ms for the ``jax.numpy`` lines; the bodies are
bound by the vector unit (every ``[q, p]`` array fills half a register's
lanes at p = 64), not by memory (0.45 ms of traffic) or the matrix unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# an exponent no span reaches: exp gives 0 without an infinity anywhere
_MASKED = -1.0e30
# what the blocks and the scratch of one program may take of the 16 MiB
# scoped VMEM; the rest is the unrolled body's temporaries
_VMEM_BUDGET = 10 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a b^T
_NN = ((1,), (0,))   # a b
_TN = ((0,), (0,))   # a^T b


def _group(c_ref, b_ref):
    """C, B and S = C B^T of the program's group, and the causal mask."""
    c, bm = c_ref[0], b_ref[0]
    q = c.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return c, bm, _dot(c, bm, _NT), rows >= cols


def _decays(s, live, col, row, last, n):
    """One head's decays: D [q, q]; exp(col) and exp(last - col) [q, 1];
    exp(last) [1, n] (Mosaic spreads a [1, 1] over lanes or over sublanes,
    not over both at once)."""
    d = jnp.exp(jnp.where(live, col - row, _MASKED))
    return d, jnp.exp(col), jnp.exp(last - col), \
        jnp.exp(jnp.broadcast_to(last, (1, n)))


def _exponents(acs_ref, acst_ref, e):
    """A head's ``acs`` as a column [q, 1], as a row [1, q], and its last."""
    q = acs_ref.shape[3]
    return (acs_ref[0, 0, 0, :, e:e + 1], acst_ref[0, 0, 0, e:e + 1, :],
            acs_ref[0, 0, 0, q - 1:q, e:e + 1])


# The per-head bodies are jitted functions of VALUES: a kernel's trace then
# holds each body once and sixteen calls of it (the Mosaic lowering inlines
# them), not sixteen copies: tracing the unrolled bodies cost 1.3 s of every
# run's set-up on the chip's host (PR 29)
@functools.partial(jax.jit, static_argnames="n")
def _fwd_head(s, live, col, row, last, dt, x, read, skip, n):
    """y [q, p] float32, U [q, p] in x's type and exp(last) [1, n]."""
    lo, f32 = x.dtype, jnp.float32
    d, from_start, to_end, whole = _decays(s, live, col, row, last, n)
    xf = x.astype(f32)
    xdt = (xf * dt).astype(lo)
    y = _dot((s * d).astype(lo), xdt, _NN) + from_start * read + skip * xf
    return y, (xdt.astype(f32) * to_end).astype(lo), whole


def _fwd_kernel(c_ref, b_ref, acs_ref, acst_ref, dt_ref, x_ref, d_ref, y_ref,
                hin_ref, h_scr, u_scr, *, heads, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    c, bm, s, live = _group(c_ref, b_ref)
    h = h_scr[...]                                   # [e p, n]
    hin_ref[0, 0, 0] = h
    read = _dot(c, h.astype(c.dtype), _NT)           # [q, e p], all heads
    decays = []
    for e in range(heads):  # static unroll over the group's heads
        at = slice(e * p, (e + 1) * p)
        y_ref[0, :, at], u_scr[:, at], whole = _fwd_head(
            s, live, *_exponents(acs_ref, acst_ref, e),
            dt_ref[0, 0, 0, :, e:e + 1], x_ref[0, :, at], read[:, at],
            d_ref[0, :, at], n=bm.shape[1])
        decays.append(whole)
    add = _dot(u_scr[...], bm, _TN)                  # [e p, n], all heads
    for e, whole in enumerate(decays):
        at = slice(e * p, (e + 1) * p)
        h_scr[at, :] = whole * h[at] + add[at]


@functools.partial(jax.jit, static_argnames="n")
def _bwd_head(s, live, col, row, last, dt, x, skip, dy, read, du, h, dh, n):
    """Of one head: its part of dS [q, q]; dx [q, p] in x's type; d dt and
    d acs [q, 1]; the skip's gradient [1, p]; ``lo(exp(col) * dy)`` and U
    [q, p]; exp(last) [1, n]."""
    lo, f32 = x.dtype, jnp.float32
    q = s.shape[0]
    d, from_start, to_end, whole = _decays(s, live, col, row, last, n)
    l = (s * d).astype(lo)
    xf = x.astype(f32)
    xdt = (xf * dt).astype(lo)
    xdtf = xdt.astype(f32)
    dy_lo = dy.astype(lo)
    dxdt = _dot(l, dy_lo, _TN) + to_end * du
    dread = from_start * dy
    # the exponents: a pair (i, j) of the chunk gives acs_i what it takes
    # from acs_j, to the last bit: both sides are the SAME products
    # dy_lo[i] L[i, j] (dt x)[j] (as jax's one array dL * S * D gives both),
    # so what reaches ``a`` is the pair's own span and not the difference of
    # two roundings over the whole chunk
    t = to_end * jnp.sum(du * xdtf, axis=1, keepdims=True)
    dacs = jnp.sum(dy_lo.astype(f32) * _dot(l, xdt, _NN) + dread * read
                   - xdtf * dxdt, axis=1, keepdims=True)
    through = jnp.sum(t, axis=0, keepdims=True) + jnp.sum(
        jnp.sum(whole * dh * h, axis=1, keepdims=True), axis=0,
        keepdims=True)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    return (_dot(dy_lo, xdt, _NT) * d,
            (dxdt * dt + skip * dy).astype(lo),
            jnp.sum(dxdt * xf, axis=1, keepdims=True),
            dacs + jnp.where(at_last, through, 0.0),
            jnp.sum(dy * xf, axis=0, keepdims=True),
            dread.astype(lo), (xdtf * to_end).astype(lo), whole)


def _bwd_kernel(c_ref, b_ref, acs_ref, acst_ref, dt_ref, x_ref, d_ref,
                hin_ref, dy_ref, dc_ref, db_ref, dacs_ref, ddt_ref, dx_ref,
                dd_ref, dh_scr, u_scr, read_scr, *, heads, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    c, bm, s, live = _group(c_ref, b_ref)
    lo = c.dtype
    h, dh = hin_ref[0, 0, 0], dh_scr[...]            # [e p, n]
    h_lo, dh_lo = h.astype(lo), dh.astype(lo)
    read = _dot(c, h_lo, _NT)                        # [q, e p], all heads
    du = _dot(bm, dh_lo, _NT)                        # [q, e p]
    ds = jnp.zeros_like(s)
    decays = []
    for e in range(heads):
        at, one = slice(e * p, (e + 1) * p), slice(e, e + 1)
        (ds_e, dx_ref[0, :, at], ddt_ref[0, 0, 0, :, one],
         dacs_ref[0, 0, 0, :, one], dd_ref[0, 0, 0, :, at], read_scr[:, at],
         u_scr[:, at], whole) = _bwd_head(
            s, live, *_exponents(acs_ref, acst_ref, e),
            dt_ref[0, 0, 0, :, one], x_ref[0, :, at], d_ref[0, :, at],
            dy_ref[0, :, at], read[:, at], du[:, at], h[at], dh[at],
            n=bm.shape[1])
        ds = ds + ds_e
        decays.append(whole)
    ds_lo, dread_lo, u = ds.astype(lo), read_scr[...], u_scr[...]
    dc_ref[0] = (_dot(dread_lo, h_lo, _NN)
                 + _dot(ds_lo, bm, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (_dot(u, dh_lo, _NN)
                 + _dot(ds_lo, c, _TN)).astype(db_ref.dtype)
    add = _dot(dread_lo, c, _TN)                     # [e p, n], all heads
    for e, whole in enumerate(decays):
        at = slice(e * p, (e + 1) * p)
        dh_scr[at, :] = whole * dh[at] + add[at]


def _specs(b, g, nc, q, e, p, n, chunk_of):
    """Block specs, in the order: C or B ``[b, s, g n]``; acs or dt ``[b, g,
    nc, q, e]``; acs transposed; x or y ``[b, s, g e p]``; the skip's row
    ``[g, 1, e p]``; the states ``[b, g, nc, e p, n]``; the skip's partial
    gradient ``[b, g, nc, 1, e p]``. ``chunk_of`` maps the grid's third
    index to the chunk."""
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda bi, gi, ci: index(
            bi, gi, chunk_of(ci)))

    by_row = lambda bi, gi, ci: (bi, ci, gi)
    by_chunk = lambda bi, gi, ci: (bi, gi, ci, 0, 0)
    return (spec((1, q, n), by_row), spec((1, 1, 1, q, e), by_chunk),
            spec((1, 1, 1, e, q), by_chunk), spec((1, q, e * p), by_row),
            spec((1, 1, e * p), lambda bi, gi, ci: (gi, 0, 0)),
            spec((1, 1, 1, e * p, n), by_chunk),
            spec((1, 1, 1, 1, e * p), by_chunk))


_WALK = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sizes(cm, acs, x):
    b, g, nc, q, e = acs.shape
    return b, g, nc, q, e, x.shape[2] // (g * e), cm.shape[2] // g


# each traced ONCE for all the layers of a model that share a shape, then
# inlined at each call under the caller's scope (the bodies unroll the
# group's heads), as causal_flash._bwd_traced
@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _fwd_traced(interpret, cm, bm, acs, dt, x, d):
    b, g, nc, q, e, p, n = _sizes(cm, acs, x)
    rows, col, row, wide, skip, state, _ = _specs(b, g, nc, q, e, p, n,
                                                  lambda ci: ci)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=e, p=p),
        grid=(b, g, nc),
        in_specs=[rows, rows, col, row, col, wide, skip],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, g, nc, e * p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((e * p, n), jnp.float32),
                        pltpu.VMEM((q, e * p), cm.dtype)],
        compiler_params=_WALK,
        interpret=interpret,
        name="ssd_scan_fwd",
    )(cm, bm, acs, jnp.swapaxes(acs, 3, 4), dt, x, d)


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _bwd_traced(interpret, cm, bm, acs, dt, x, d, entering, dy):
    b, g, nc, q, e, p, n = _sizes(cm, acs, x)
    rows, col, row, wide, skip, state, part = _specs(
        b, g, nc, q, e, p, n, lambda ci: nc - 1 - ci)
    f32 = jnp.float32
    dc, db, dacs, ddt, dx, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=e, p=p),
        grid=(b, g, nc),
        in_specs=[rows, rows, col, row, col, wide, skip, state, wide],
        out_specs=[rows, rows, col, col, wide, part],
        out_shape=[jax.ShapeDtypeStruct(cm.shape, cm.dtype),
                   jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(acs.shape, f32),
                   jax.ShapeDtypeStruct(acs.shape, f32),
                   jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, g, nc, 1, e * p), f32)],
        scratch_shapes=[pltpu.VMEM((e * p, n), f32),
                        pltpu.VMEM((q, e * p), cm.dtype),
                        pltpu.VMEM((q, e * p), cm.dtype)],
        compiler_params=_WALK,
        interpret=interpret,
        name="ssd_scan_bwd",
    )(cm, bm, acs, jnp.swapaxes(acs, 3, 4), dt, x, d, entering, dy)
    return dc, db, dacs, ddt, dx, jnp.sum(dd, axis=(0, 2))


@jax.custom_vjp
def _scan(cm, bm, acs, dt, x, d):
    return _fwd_traced(_interpret(), cm, bm, acs, dt, x, d)[0]


def _scan_fwd(cm, bm, acs, dt, x, d):
    y, entering = _fwd_traced(_interpret(), cm, bm, acs, dt, x, d)
    return y, (cm, bm, acs, dt, x, d, entering)


def _scan_bwd(res, dy):
    return _bwd_traced(_interpret(), *res, dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ------------------------------------------------------------------- public


def _footprint(chunk, state, heads, head_dim, itemsize):
    """Bytes of VMEM one program of the backward (the larger of the two)
    holds in blocks, each double-buffered, and in scratch."""
    up = lambda v, to: -(-v // to) * to
    wide, rows = chunk * heads * head_dim, chunk * state
    states = 4 * heads * head_dim * state
    column, row = 4 * chunk * up(heads, 128), 4 * up(heads, 8) * chunk
    skip = 4 * 8 * heads * head_dim
    taken = (2 * itemsize * rows + 2 * column + row + itemsize * wide + skip
             + states + 4 * wide)          # C, B, acs, dt, acs^T, x, D, h, dy
    given = 2 * itemsize * rows + 2 * column + itemsize * wide + skip
    return 2 * (taken + given) + states + 2 * itemsize * wide


def supported(chunk: int, state: int, heads: int, head_dim: int,
              itemsize: int = 2) -> bool:
    """Whether the kernels take a layer whose groups hold ``heads`` heads of
    ``head_dim``, in chunks of ``chunk`` positions with ``state`` numbers a
    channel and ``itemsize`` bytes an operand."""
    if chunk % 128 or state % 128 or (heads * head_dim) % 128 or head_dim % 8:
        return False
    return _footprint(chunk, state, heads, head_dim, itemsize) <= _VMEM_BUDGET


def enabled(chunk: int, state: int, heads: int, head_dim: int,
            itemsize: int = 2) -> bool:
    """Whether ``ssd_chunked`` should take the kernels: on the TPU, at a
    shape they support."""
    return (jax.default_backend() == "tpu"
            and supported(chunk, state, heads, head_dim, itemsize))


def ssd_scan(cm, bm, acs, dt, x, d):
    """``ssd_chunked`` from its chunked operands on: C and B ``[b, c, q, g,
    n]`` and x ``[b, c, q, g, e, p]`` in the products' type, ``acs`` (the
    cumulative ``dt a`` inside each chunk) and dt ``[b, c, q, g, e]``
    float32, the skip's weights d ``[g, e]`` float32. Returns ``y + d x``
    ``[b, c, q, g, e, p]`` float32. Differentiable in all six."""
    b, nc, q, g, e, p = x.shape
    n = cm.shape[-1]
    f32 = jnp.float32
    chunks_last = lambda t: jnp.transpose(t, (0, 3, 1, 2, 4))
    y = _scan(cm.reshape(b, nc * q, g * n), bm.reshape(b, nc * q, g * n),
              chunks_last(acs), chunks_last(dt),
              x.reshape(b, nc * q, g * e * p),
              jnp.repeat(d.astype(f32), p, axis=-1).reshape(g, 1, e * p))
    return y.reshape(b, nc, q, g, e, p)
