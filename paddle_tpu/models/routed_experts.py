"""The routed experts a chip holds, dropless: what ``LatentMoE``
(``models/nemotron_h.py``) and ``LagunaMoE`` (``models/laguna.py``) share.

A layer routes over ALL published experts and holds ``held`` of them from
``first`` on. From the dense mask of each token's chosen set
(``ops/pallas/topk_mask.py``) come the weights of the held columns
(``held_weights``); the (token, held expert) pairs are sorted by expert into
a buffer of a static size (``sort_pairs``; its rows from a stated bound,
``buffer_rows``); the experts run over the buffer as two stages of
``jax.lax.ragged_dot`` around the layer's own activation and every pair's
weighted output is added back to its token (``mix``). ``mix`` leaves out
the pairs over its one buffer; ``mix_every_pair`` sends them through further
buffers of the same size, so that no routing loses a pair. Nothing is
dropped in silence: under ``moe_stats_tap`` (``models/moe_stats.py``) both
append ``[pairs routed to held experts, tokens with none of them, pairs left
out, rows walked]`` (float32) to the tap's list, for the caller to thread
out of the traced function as an output.

The rows move between token order and buffer order through ``take_rows`` and
``add_rows``, which walk only the rows that hold a pair, ``CHUNK`` at a time:
a buffer is headroom by design, and what it does not hold costs nothing to
move. Rows walked over buffer rows is the share they did not skip.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import moe_stats

__all__ = ["buffer_rows", "held_weights", "sort_pairs", "take_rows",
           "add_rows", "mix", "mix_every_pair"]

# rows a trip of ``take_rows`` / ``add_rows`` moves; a smaller buffer is one
CHUNK = 512


def buffer_rows(tokens: int, top_k: int, held: int, experts: int,
                bound: float) -> int:
    """Rows of the local pairs' buffer: ``bound`` times the pairs uniform
    routing sends here, at most one a token and expert."""
    fair = tokens * top_k * held / experts
    return min(tokens * min(held, top_k),
               -(-int(math.ceil(bound * fair)) // 8) * 8)


def held_weights(scores, picked, scale, first, held):
    """(routed, w_local), both ``[tokens, held]``: which held experts each
    token chose, and ``scale * score / sum over ALL the chosen of score``
    there, nought elsewhere. ``scores`` and the boolean ``picked`` are
    ``[tokens, experts]`` over every published expert."""
    total = jnp.sum(jnp.where(picked, scores, 0.0), -1, keepdims=True)
    here = slice(first, first + held)
    routed = picked[:, here]
    return routed, jnp.where(routed, scale * scores[:, here] / total, 0.0)


class Pairs(NamedTuple):
    """The (token, held expert) pairs sorted by expert then token in a
    buffer of ``len(token)`` rows: ``sizes`` are the rows of each expert
    that fit, ``live`` the rows that hold a pair, ``counts`` every pair
    routed to each expert, ``ends`` the running count the buffer took."""
    token: jax.Array
    expert: jax.Array
    sizes: jax.Array
    live: jax.Array
    counts: jax.Array
    ends: jax.Array


def sort_pairs(routed, rows: int) -> Pairs:
    t = routed.shape[0]
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(counts), rows)
    sizes = jnp.diff(ends, prepend=0)
    flat, = jnp.nonzero(routed.T.reshape(-1), size=rows, fill_value=0)
    expert, token = flat // t, flat % t
    live = jnp.arange(rows) < ends[-1]
    return Pairs(token, expert, sizes, live, counts, ends)


def _chunk(rows: int) -> int:
    return min(CHUNK, rows)


def rows_walked(n, rows: int):
    """Rows of a buffer of ``rows`` that ``take_rows`` and ``add_rows`` walk
    for its first ``n``: whole chunks, and never more than the buffer."""
    return jnp.minimum(-(-n // _chunk(rows)) * _chunk(rows), rows)


def _over_chunks(n, rows: int, one_chunk, shape, dtype):
    """``one_chunk(carried, first row, under, fresh)`` over the chunks that
    hold the first ``n`` of ``rows`` rows, in order, carrying an array of
    ``shape`` and ``dtype`` that starts as noughts; the trip count is read
    from ``n`` on the device. Of a chunk's rows ``under`` marks those below
    ``n`` and ``fresh`` those no earlier chunk reached: the last chunk of a
    buffer that is no whole number of chunks starts early, over rows the one
    before it has done."""
    chunk = _chunk(rows)
    # noughts from a value the compiler cannot fold: the broadcast of a
    # constant carries no scope in a trace, so the filling of the carried
    # array would be read under no layer's name
    start = jnp.full(shape, jnp.where(n < 0, 1, 0), dtype)

    def trip(c, carried):
        first = jnp.minimum(c * chunk, rows - chunk)
        row = first + jnp.arange(chunk)
        return one_chunk(carried, first, row < n, row >= c * chunk)

    return jax.lax.fori_loop(0, -(-n // chunk), trip, start)


def _chunk_of(a, first, rows: int):
    return jax.lax.dynamic_slice_in_dim(a, first, _chunk(rows))


def take_rows(x, token, n):
    """``[len(token), width]`` in x's type: row r is ``x[token[r]]`` for
    r < ``n`` and nought from ``n`` on, whatever ``token`` holds there. Only
    the chunks under ``n`` are walked. Its transpose is ``add_rows``."""
    return _take_rows(x.shape[0], x, token, n)


def add_rows(y, token, n, tokens: int):
    """``[tokens, width]`` in y's type: the first ``n`` rows of ``y`` added
    to the rows ``token`` names, in row order (the order
    ``zeros.at[token].add(y)`` adds them in) and in float32, rounded to y's
    type once at the end; ``y`` and ``token`` from ``n`` on are not read. Its
    transpose is ``take_rows``."""
    return _add_rows(tokens, y, token, n)


# jitted under the gradient rule: traced once for every layer of a shape.
# ``tokens`` (x's rows) is static so that each backward knows its output
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@functools.partial(jax.jit, static_argnums=0, inline=True)
def _take_rows(tokens: int, x, token, n):
    rows = token.shape[0]

    def one_chunk(out, first, under, fresh):
        taken = jnp.where(under[:, None],
                          x[_chunk_of(token, first, rows)], 0)
        return jax.lax.dynamic_update_slice_in_dim(out, taken, first, 0)

    return _over_chunks(n, rows, one_chunk, (rows, x.shape[1]), x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@functools.partial(jax.jit, static_argnums=0, inline=True)
def _add_rows(tokens: int, y, token, n):
    rows = token.shape[0]
    # a type narrower than float32 is summed in float32 and rounded once at
    # the end, as the chip rounds ``zeros.at[token].add(y)`` over the whole
    # buffer: rounded chunk by chunk the sum would depend on ``CHUNK``
    wide = jnp.promote_types(y.dtype, jnp.float32)

    def one_chunk(out, first, under, fresh):
        # a row past ``n``, or one an earlier chunk added, is sent out of
        # range and dropped: the chunk of ``y`` goes into the scatter as it is
        to = jnp.where(under & fresh, _chunk_of(token, first, rows), tokens)
        return out.at[to].add(_chunk_of(y, first, rows).astype(wide),
                              mode="drop")

    return _over_chunks(n, rows, one_chunk, (tokens, y.shape[1]),
                        wide).astype(y.dtype)


# each keeps ``token`` and ``n`` for the backward, and nothing else
_take_rows.defvjp(
    lambda tokens, x, token, n: (_take_rows(tokens, x, token, n), (token, n)),
    lambda tokens, kept, ct: (_add_rows(tokens, ct, *kept), None, None))
_add_rows.defvjp(
    lambda tokens, y, token, n: (_add_rows(tokens, y, token, n), (token, n)),
    lambda tokens, kept, ct: (_take_rows(tokens, ct, *kept), None, None))


def _experts_over(pairs: Pairs, x, w_local, w_in, act, w_out):
    """One buffer of pairs through the experts, added back to its tokens."""
    f32 = jnp.float32
    token, sizes, n = pairs.token, pairs.sizes, pairs.ends[-1]
    # what ragged_dot leaves in the rows past its groups is not ours
    only_live = lambda a: jnp.where(pairs.live[:, None], a, 0)
    xs = take_rows(x, token, n)
    hid = only_live(act(*(jax.lax.ragged_dot(xs, w.astype(xs.dtype), sizes,
                                             preferred_element_type=f32)
                          for w in w_in)))
    y = jax.lax.ragged_dot(hid.astype(xs.dtype), w_out.astype(xs.dtype),
                           sizes, preferred_element_type=f32)
    y = only_live(y * w_local[token, pairs.expert][:, None])
    return add_rows(y, token, n, x.shape[0])


def _tap(routed, counts):
    """``counts()``: pairs routed here, pairs left out, rows walked."""
    tap = moe_stats.armed()
    if tap is not None:
        total, left_out, walked = counts()
        tap.append(jnp.stack([
            total, jnp.sum(~jnp.any(routed, axis=1)), left_out,
            walked]).astype(jnp.float32))


def mix(pairs: Pairs, x, routed, w_local, w_in, act, w_out):
    """``[tokens, out]`` float32: for every token the sum over its chosen
    held experts e of ``w_local[token, e] * act(x W_e for W in w_in) Wout_e``,
    over the pairs ``pairs``' ONE buffer took; those over it are left out
    and counted. ``x`` ``[tokens, width]`` is what the experts read, ``w_in``
    the stacked ``[held, width, ff]`` weights whose products ``act`` takes
    (one for ``relu^2``, two for ``silu(a) * b``), ``w_out`` ``[held, ff,
    out]``. Operands in x's type, accumulation in float32."""
    mixed = _experts_over(pairs, x, w_local, w_in, act, w_out)
    total, n = lambda: jnp.sum(pairs.counts), pairs.ends[-1]
    _tap(routed, lambda: (total(), total() - n,
                          rows_walked(n, len(pairs.token))))
    return mixed


def _later_buffers(rows: int, buffers: int, act):
    """What buffers 1 .. ``buffers - 1`` add in ``mix_every_pair``, as a
    function of (routed, place, total, x, w_local, w_in, w_out) with a
    gradient rule of its own: a ``cond`` skips every buffer no pair reaches,
    forward and backward, and the backward computes a buffer again and adds
    its cotangents into one running sum. (Differentiated by jax, a ``cond``
    inside a ``scan`` hands what it kept through its outputs once a buffer:
    ``buffers`` copies of ``x`` and of every weight.)"""
    tree_map = jax.tree_util.tree_map

    def buffer(c, routed, place, x, w_local, w_in, w_out):
        part = routed & (place > c * rows) & (place <= (c + 1) * rows)
        return _experts_over(sort_pairs(part, rows), x, w_local, w_in, act,
                             w_out)

    def over_buffers(total, one_more, start, finish, none):
        """``finish`` of ``one_more(sum, c)`` over every buffer c a pair
        reaches, from ``start()``; ``none()`` where the first took all."""
        step = lambda s, c: (jax.lax.cond(
            total > c * rows, one_more, lambda s, c: s, s, c), None)
        return jax.lax.cond(
            total > rows, lambda: finish(jax.lax.scan(
                step, start(), jnp.arange(1, buffers))[0]), none)

    @jax.custom_vjp
    def later(routed, place, total, *diff):
        zero = lambda: jnp.zeros((diff[0].shape[0], diff[-1].shape[-1]),
                                 jnp.float32)
        return over_buffers(
            total, lambda mixed, c: mixed + buffer(c, routed, place, *diff),
            zero, lambda mixed: mixed, zero)

    def backward(kept, ct):
        routed, place, total, *diff = kept
        diff = tuple(diff)

        def one_more(sums, c):
            _, pull = jax.vjp(lambda *d: buffer(c, routed, place, *d), *diff)
            return tree_map(lambda s, g: s + g.astype(s.dtype), sums,
                            pull(ct))

        # summed in float32 (several buffers' parts of a bfloat16 leaf)
        return (None, None, None) + over_buffers(
            total, one_more,
            lambda: tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), diff),
            lambda sums: tree_map(lambda s, a: s.astype(a.dtype), sums, diff),
            lambda: tree_map(jnp.zeros_like, diff))

    later.defvjp(lambda *args: (later(*args), args), backward)
    return later


def mix_every_pair(routed, rows: int, most: int, x, w_local, w_in, act,
                   w_out):
    """``mix`` with no pair left out whatever the routing, in memory for one
    buffer of ``rows``: the pairs the first buffer does not take go through
    further buffers of the same size, one after the other, as many as are
    needed (a step whose pairs fit the first buffer runs none of them) and
    at most enough for ``most`` pairs (``tokens * min(held, k)``: every token
    to every expert held). A later buffer's intermediate values are computed
    again for its gradient and not kept, so the memory taken is the first
    buffer's plus one buffer's work, and the time follows the load. The
    tap's third number is what even those leave out: nought where ``most``
    is the true bound; its fourth sums the rows walked over the buffers run."""
    t, held = routed.shape
    mixed = _experts_over(sort_pairs(routed, rows), x, w_local, w_in, act,
                          w_out)
    # 1-based place of every pair in the order by expert, then token
    place = jnp.cumsum(routed.T.reshape(-1), dtype=jnp.int32)
    total, place = place[-1], place.reshape(held, t).T
    buffers = -(-most // rows)
    if buffers > 1:
        mixed = mixed + _later_buffers(rows, buffers, act)(
            routed, place, total, x, w_local, tuple(w_in), w_out)
    # buffer c holds the pairs from place c * rows on, as many as it has rows
    _tap(routed, lambda: (
        total, jnp.maximum(total - buffers * rows, 0), jnp.sum(rows_walked(
            jnp.clip(total - rows * jnp.arange(buffers), 0, rows), rows))))
    return mixed
