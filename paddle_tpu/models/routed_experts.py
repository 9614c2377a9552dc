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
out]`` (float32) to the tap's list, for the caller to thread out of the
traced function as an output.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import moe_stats

__all__ = ["buffer_rows", "held_weights", "sort_pairs", "mix",
           "mix_every_pair"]


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


def _experts_over(pairs: Pairs, x, w_local, w_in, act, w_out):
    """One buffer of pairs through the experts, added back to its tokens."""
    f32 = jnp.float32
    token, sizes = pairs.token, pairs.sizes
    # what ragged_dot leaves in the rows past its groups is not ours
    only_live = lambda a: jnp.where(pairs.live[:, None], a, 0)
    xs = only_live(x[token])
    hid = only_live(act(*(jax.lax.ragged_dot(xs, w.astype(xs.dtype), sizes,
                                             preferred_element_type=f32)
                          for w in w_in)))
    y = jax.lax.ragged_dot(hid.astype(xs.dtype), w_out.astype(xs.dtype),
                           sizes, preferred_element_type=f32)
    y = only_live(y * w_local[token, pairs.expert][:, None])
    return jnp.zeros((x.shape[0], w_out.shape[-1]), f32).at[token].add(y)


def _tap(routed, total_and_left_out):
    tap = moe_stats.armed()
    if tap is not None:
        total, left_out = total_and_left_out()
        tap.append(jnp.stack([
            total, jnp.sum(~jnp.any(routed, axis=1)),
            left_out]).astype(jnp.float32))


def mix(pairs: Pairs, x, routed, w_local, w_in, act, w_out):
    """``[tokens, out]`` float32: for every token the sum over its chosen
    held experts e of ``w_local[token, e] * act(x W_e for W in w_in) Wout_e``,
    over the pairs ``pairs``' ONE buffer took; those over it are left out
    and counted. ``x`` ``[tokens, width]`` is what the experts read, ``w_in``
    the stacked ``[held, width, ff]`` weights whose products ``act`` takes
    (one for ``relu^2``, two for ``silu(a) * b``), ``w_out`` ``[held, ff,
    out]``. Operands in x's type, accumulation in float32."""
    mixed = _experts_over(pairs, x, w_local, w_in, act, w_out)
    total = lambda: jnp.sum(pairs.counts)
    _tap(routed, lambda: (total(), total() - pairs.ends[-1]))
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
    is the true bound."""
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
    _tap(routed, lambda: (total, jnp.maximum(total - buffers * rows, 0)))
    return mixed
