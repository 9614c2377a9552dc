"""Which k of each row's entries are the largest, as a dense boolean mask:
``topk_mask(v, k)[t, e]`` is true exactly where ``e`` is among
``jax.lax.top_k(v[t], k)[1]``, equal entries decided as ``top_k`` decides
them (the lowest index first), without an index in sight.

One algorithm, a threshold and dense compares. With ``tau`` a row's k-th
largest entry, the chosen are the entries above ``tau`` and as many of the
entries EQUAL to it, in index order, as fill the k places. The first step,
finding ``tau``, has two implementations:

* ``jax.numpy`` (every backend, any shape; the kernel's twin in the tests):
  ``tau = top_k(v, k)[0][:, -1:]``; the equal entries are counted along the
  row (``cumsum``) and taken while the count is at most ``k - count(v > tau)``.
  No faster than the index sort on the TPU: asked for values only, its
  compiler still sorts (key, index) pairs, and a one-operand ``sort`` is
  slower still (PERF.md, PR 31).
* the Pallas kernel ``topk_mask`` (TPU, shapes ``supported`` takes). The rows
  arrive transposed, ``[experts, tokens / 128, 128]``, and one program holds
  ``[experts, 8, 128]`` in VMEM: every expert's scores of 1024 tokens are ONE
  register, so a compare-exchange between two experts is one ``max`` and one
  ``min`` over whole registers and nothing ever crosses a lane. The experts
  are sorted in groups of ``w`` (k rounded up to a power of two; a bitonic
  network in registers, ``w (log w)(log w + 1) / 4`` exchanges) and each
  sorted group is merged into the running ``w`` largest (``w`` maxima
  against the reversed list leave a bitonic sequence of the ``w`` largest of
  both, ``w / 2 log w`` exchanges sort it). ``tau`` is the k-th of what is
  left; how many entries lie above it is read off the same list; one more
  pass over the experts finds ``cut``, the index of the last equal entry
  taken. The kernel writes ``tau`` and ``cut`` (8 bytes a token), and the
  mask is ``(v > tau) | ((v == tau) & (index <= cut))`` in ``jax.numpy``, in
  the layout its consumers read, fused into them. At 512 experts and k = 22
  a program of 1024 tokens takes about 14 000 register operations (10 750
  the network, 3 000 the last pass), where a bisection on the bit pattern
  takes 49 000 (32 rounds of compare, convert, add) and k rounds of
  maximum-and-retire 56 000.

Nothing differentiable passes through a selection: the input is taken under
``stop_gradient`` and the kernel has no backward. Entries compare as floats
do (``-0.0`` ties with ``0.0``); a row holding a NaN chooses nothing sound
on either path, as it does not under ``top_k``.

Constraints (``supported``): float32; k at most 32 (the running list and a
sorted group are then the 64 registers there are); experts a multiple of
``w``; tokens a multiple of 1024; the double-buffered block under
``_VMEM_BUDGET``. Traced once per shape (``jax.jit(inline=True)``) and
inlined under each caller's scope, as ``ssd_scan`` and ``causal_flash`` are.
Measured on one v5e chip at ``[16384, 512]``, k = 22 (PR 31): 0.055 ms a call
in the training step (33.5 MB read: two thirds of its memory roofline) where
``top_k``'s sort took 1.46 ms and the index compare 0.16; the transpose rides
in the producer's fusion and the compares in the consumer's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens of one program: a float32 register, 8 sublanes of 128 lanes
_SUB, _LANES = 8, 128
_TILE = _SUB * _LANES
# what the double-buffered block of scores may take of the 16 MiB scoped VMEM
_VMEM_BUDGET = 8 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _width(k: int) -> int:
    """k rounded up to a power of two: the length of the sorted lists."""
    return 1 << (k - 1).bit_length()


def _exchange(xs, i, j):
    xs[i], xs[j] = jax.lax.max(xs[i], xs[j]), jax.lax.min(xs[i], xs[j])


def _clean(xs):
    """Sorts a bitonic list (a power of two long) into descending order."""
    stride = len(xs) // 2
    while stride:
        for i in range(len(xs)):
            if not i & stride:
                _exchange(xs, i, i + stride)
        stride //= 2
    return xs


def _merge(a, b):
    """The ``len(a)`` largest of two descending lists of that length, in
    descending order."""
    return _clean([jax.lax.max(x, y) for x, y in zip(a, reversed(b))])


def _sort(xs):
    """Bitonic sort into descending order, every exchange putting the larger
    at the lower index: the two sorted halves are merged by exchanging the
    first with the second's mirror image (the larger half then stands first
    and both are bitonic) and cleaning each."""
    half = len(xs) // 2
    if not half:
        return xs
    both = _sort(xs[:half]) + _sort(xs[half:])
    for i in range(half):
        _exchange(both, i, len(xs) - 1 - i)
    return _clean(both[:half]) + _clean(both[half:])


def _rows(block):
    """The registers of a ``[n, 8, 128]`` block, as a list."""
    return [jax.lax.index_in_dim(block, i, keepdims=False)
            for i in range(block.shape[0])]


def _kernel(v_ref, tau_ref, cut_ref, top_ref, *, k):
    """v ``[experts, 8, 128]``: 1024 tokens' scores, an expert a register;
    ``top_ref`` ``[w, 8, 128]``: the w largest so far, descending. Blocks
    are read and written whole and the unrolled parts bind ``jax.lax``
    primitives: a ``jax.numpy`` call or a ref access an element would
    cost every run's set-up a second of tracing (PERF.md, PR 31)."""
    experts, w = v_ref.shape[0], top_ref.shape[0]
    i32 = jnp.int32
    top_ref[...] = jnp.full(top_ref.shape, -jnp.inf, top_ref.dtype)

    def fold(g, carry):
        group = _sort(_rows(v_ref[pl.ds(g * w, w)]))
        # tpulint: disable=TPL402 -- top_ref is a Pallas VMEM scratch Ref:
        # the closure is over a memory handle, not a traced value
        top_ref[...] = jnp.stack(_merge(_rows(top_ref[...]), group))
        return carry

    jax.lax.fori_loop(0, experts // w, fold, 0)
    tau = top_ref[k - 1]
    # every entry above tau is in the list, before it
    need = k - jnp.sum((top_ref[...] > tau).astype(i32), axis=0)
    index = jax.lax.broadcasted_iota(i32, top_ref.shape, 0)

    def find(g, carry):
        seen, cut = carry
        tie = v_ref[pl.ds(g * w, w)] == tau
        counts = []
        for t in _rows(tie.astype(i32)):
            seen = seen + t
            counts.append(seen)
        last = tie & (jnp.stack(counts) == need)
        here = jnp.max(jnp.where(last, index + g * w, -1), axis=0)
        return seen, jax.lax.max(cut, here)

    zero = jnp.zeros(tau.shape, i32)
    _, cut = jax.lax.fori_loop(0, experts // w, find, (zero, zero))
    tau_ref[...] = tau
    cut_ref[...] = cut


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _threshold_traced(interpret, k, v):
    """``tau`` (float) and ``cut`` (int32), each ``[tokens, 1]``: the k-th
    largest of every row of v ``[tokens, experts]`` and the index of the
    last entry equal to it that is among the k."""
    t, experts = v.shape
    w = _width(k)
    row = pl.BlockSpec((_SUB, _LANES), lambda i: (i, 0))
    tau, cut = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(t // _TILE,),
        in_specs=[pl.BlockSpec((experts, _SUB, _LANES), lambda i: (0, i, 0))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((t // _LANES, _LANES), v.dtype),
                   jax.ShapeDtypeStruct((t // _LANES, _LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((w, _SUB, _LANES), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="topk_mask",
    )(v.T.reshape(experts, t // _LANES, _LANES))
    return tau.reshape(t, 1), cut.reshape(t, 1)


def supported(tokens: int, experts: int, k: int, itemsize: int = 4) -> bool:
    """Whether the kernel takes ``[tokens, experts]`` scores and this k."""
    w = _width(k)
    return (itemsize == 4 and 1 <= k <= 32 and k <= experts
            and experts % w == 0 and tokens % _TILE == 0 and tokens > 0
            and 2 * experts * _TILE * itemsize <= _VMEM_BUDGET)


def enabled(tokens: int, experts: int, k: int, itemsize: int = 4) -> bool:
    """Whether ``topk_mask`` should take the kernel: on the TPU, at a shape
    it supports."""
    return (jax.default_backend() == "tpu"
            and supported(tokens, experts, k, itemsize))


def topk_mask(v, k: int):
    """Boolean ``[tokens, experts]``: the k largest entries of every row of
    v, the set ``jax.lax.top_k(v, k)`` names, equal entries by lowest index.
    No gradient passes."""
    v = jax.lax.stop_gradient(v)
    tokens, experts = v.shape
    if enabled(tokens, experts, k, v.dtype.itemsize):
        tau, cut = _threshold_traced(_interpret(), k, v)
        taken = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) <= cut
    else:
        tau = jax.lax.top_k(v, k)[0][:, -1:]
        room = k - jnp.sum(v > tau, -1, keepdims=True, dtype=jnp.int32)
        taken = jnp.cumsum(v == tau, -1, dtype=jnp.int32) <= room
    return (v > tau) | ((v == tau) & taken)
