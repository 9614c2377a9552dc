"""``models/routed_experts.py``: ``LatentMoE`` through the lifted dispatch
is the function it was before the lift, bit for bit; the rows move through
``take_rows`` / ``add_rows``, which walk only the rows that hold a pair and
are each other's transpose; the pieces do what their callers count on."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import moe_stats, nemotron_h as nh, routed_experts as rx
from paddle_tpu.ops.pallas.topk_mask import topk_mask


def plain_add(y, token, n, tokens):
    """``zeros.at[token].add(y)`` over the first n rows as the chip computes
    it: a narrow type summed in float32 and rounded once (the CPU backend
    rounds a bfloat16 sum at every row it adds)."""
    y = jnp.where((jnp.arange(len(token)) < n)[:, None], y, 0)
    wide = jnp.promote_types(y.dtype, jnp.float32)
    return jnp.zeros((tokens, y.shape[1]), wide).at[token].add(
        y.astype(wide)).astype(y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def gather_as_on_the_chip(tokens, x, token):
    """``x[token]`` whose transpose is the chip's: ``plain_add``."""
    return x[token]


gather_as_on_the_chip.defvjp(
    lambda tokens, x, token: (x[token], token),
    lambda tokens, token, ct: (plain_add(ct, token, len(token), tokens), None))


def route_and_mix_before_the_lift(self, u, w_r, bias, w_dn, w_up, w1, w2,
                                  ws1, ws2):
    """``LatentMoE._route_and_mix`` as PR 31 left it, line for line but for
    ``latent[token]``, whose transpose is stated as the chip computes it."""
    _mm = nh._mm
    b, s, hidden = u.shape
    t, held, f32 = b * s, self.held, jnp.float32
    ut = u.reshape(t, hidden)
    relu2 = lambda a: jnp.square(jax.nn.relu(a))

    scores = jax.nn.sigmoid(jnp.dot(ut, w_r.astype(ut.dtype),
                                    preferred_element_type=f32))
    picked = topk_mask(scores + bias.astype(f32), self.top_k)
    total = jnp.sum(jnp.where(picked, scores, 0.0), -1, keepdims=True)
    here = slice(self.first, self.first + held)
    routed = picked[:, here]
    w_local = jnp.where(routed, self.scale * scores[:, here] / total, 0.0)

    rows = self.buffer_rows(t)
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(counts), rows)
    sizes = jnp.diff(ends, prepend=0)
    flat, = jnp.nonzero(routed.T.reshape(-1), size=rows, fill_value=0)
    expert, token = flat // t, flat % t
    live = jnp.arange(rows) < ends[-1]
    latent = _mm(ut, w_dn)
    only_live = lambda a: jnp.where(live[:, None], a, 0)
    x = only_live(gather_as_on_the_chip(t, latent, token))
    hid = only_live(relu2(jax.lax.ragged_dot(x, w1.astype(x.dtype), sizes,
                                             preferred_element_type=f32)))
    y = jax.lax.ragged_dot(hid.astype(x.dtype), w2.astype(x.dtype), sizes,
                           preferred_element_type=f32)
    y = only_live(y * w_local[token, expert][:, None])
    mixed = jnp.zeros((t, latent.shape[1]), f32).at[token].add(y)

    out = _mm(mixed.astype(ut.dtype), w_up) + _mm(
        relu2(_mm(ut, ws1)), ws2)
    tap = moe_stats.armed()
    if tap is not None:
        total = jnp.sum(counts)
        tap.append(jnp.stack([
            total, jnp.sum(~jnp.any(routed, axis=1)),
            total - ends[-1]]).astype(f32))
    return out.reshape(b, s, hidden)


def layer_and_arrays(dtype, bound=3.0):
    cfg = nh.NemotronHConfig(
        hidden_size=64, n_routed_experts=16, num_experts_per_tok=5,
        moe_intermediate_size=48, moe_latent_size=32,
        moe_shared_expert_intermediate_size=96, experts_held=4,
        first_expert=4, shared_width_held=48, local_pairs_bound=bound)
    layer = nh.LatentMoE(cfg)
    names = ("router", "e_score_correction_bias", "latent_down", "latent_up",
             "experts_w1", "experts_w2", "shared_up", "shared_down")
    keys = jax.random.split(jax.random.PRNGKey(11), len(names) + 1)
    arrays = []
    for name, key in zip(names, keys):
        leaf = getattr(layer, name)
        shape = getattr(leaf, "weight", leaf).shape
        arrays.append((0.05 * jax.random.normal(key, shape)).astype(
            jnp.float32 if name.endswith("bias") else dtype))
    u = jax.random.normal(keys[-1], (2, 40, 64)).astype(dtype)
    return layer, u, arrays


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("bound", [3.0, 0.5], ids=["fits", "overflows"])
def test_latent_moe_is_bit_for_bit_what_it_was(dtype, bound):
    layer, u, arrays = layer_and_arrays(dtype, bound)
    now = lambda u, *a: layer._route_and_mix(u, *a)
    was = lambda u, *a: route_and_mix_before_the_lift(layer, u, *a)
    assert jnp.array_equal(jax.jit(now)(u, *arrays), jax.jit(was)(u, *arrays))
    diff = tuple(i for i in range(9) if i != 2)   # the bias has no gradient
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: f(*a).astype(jnp.float32).sum(), argnums=diff))(u, *arrays)
    for got, want in zip(grads(now), grads(was)):
        assert jnp.array_equal(got, want)


def test_latent_moe_counters_are_what_they_were():
    """Under the tap the first three counters are the numbers of before the
    lift, and the fourth is the rows walked for the pairs the buffer took."""
    layer, u, arrays = layer_and_arrays(jnp.bfloat16)

    def counters(f):
        with moe_stats.moe_stats_tap() as tap:
            f(u, *arrays)
        return np.asarray(tap[0])

    now = counters(lambda u, *a: layer._route_and_mix(u, *a))
    was = counters(lambda u, *a: route_and_mix_before_the_lift(layer, u, *a))
    np.testing.assert_array_equal(now[:3], was)
    assert now[0] > 0 and now[2] == 0
    assert now[3] == walked(int(now[0]), layer.buffer_rows(80))


# ------------------------------------------------- take_rows and add_rows

ROWS, TOKENS, WIDTH = 2 * rx.CHUNK + rx.CHUNK // 2, 700, 16
COUNTS = [0, 1, rx.CHUNK - 1, rx.CHUNK, rx.CHUNK + 1, 2 * rx.CHUNK + 1, ROWS]


def walked(n, rows):
    chunk = min(rx.CHUNK, rows)
    return min(-(-n // chunk) * chunk, rows)


def rows_case(dtype, seed=5):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(TOKENS, WIDTH)), dtype)
    y = jnp.asarray(rng.normal(size=(ROWS, WIDTH)), dtype)
    # few tokens: each is the sum of three and more rows, so the order shows
    token = jnp.asarray(rng.integers(0, TOKENS, ROWS), jnp.int32)
    return x, y, token


def plain_take(x, token, n):
    return jnp.where((jnp.arange(len(token)) < n)[:, None], x[token], 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("n", COUNTS, ids=lambda n: f"n{n}")
def test_take_rows_and_add_rows_are_the_plain_gather_and_scatter_add(n, dtype):
    """The first n rows, bit for bit, whatever n is to the chunk (the
    buffer is two and a half chunks: its last chunk starts early)."""
    x, y, token = rows_case(dtype)
    took = jax.jit(rx.take_rows)(x, token, n)
    assert took.dtype == dtype and jnp.array_equal(took,
                                                   plain_take(x, token, n))
    added = jax.jit(lambda y, t, n: rx.add_rows(y, t, n, TOKENS))(y, token, n)
    assert added.dtype == dtype and jnp.array_equal(
        added, plain_add(y, token, n, TOKENS))
    assert int(rx.rows_walked(n, ROWS)) == walked(n, ROWS)


@pytest.mark.parametrize("n", [0, rx.CHUNK + 1, ROWS], ids=lambda n: f"n{n}")
def test_take_rows_and_add_rows_are_each_others_transpose(n):
    """``jax.vjp`` of each is the plain function's ``jax.vjp``, bit for bit
    (so the backward of one walks the chunks of the other), and the inner
    products agree: <take(x), y> = <x, add(y)>."""
    x, y, token = rows_case(jnp.float32)
    took, pull = jax.vjp(lambda x: rx.take_rows(x, token, n), x)
    want, plain_pull = jax.vjp(lambda x: plain_take(x, token, n), x)
    assert jnp.array_equal(pull(y)[0], plain_pull(y)[0])
    assert jnp.array_equal(pull(y)[0], rx.add_rows(y, token, n, TOKENS))
    added, pull = jax.vjp(lambda y: rx.add_rows(y, token, n, TOKENS), y)
    _, plain_pull = jax.vjp(lambda y: plain_add(y, token, n, TOKENS), y)
    assert jnp.array_equal(pull(x)[0], plain_pull(x)[0])
    assert jnp.array_equal(pull(x)[0], took)
    np.testing.assert_allclose(jnp.vdot(took, y), jnp.vdot(x, added),
                               rtol=1e-5)
    # under jit and twice over: the gradient rule has one of its own
    twice = jax.jit(jax.grad(lambda x: jnp.sum(jax.grad(
        lambda y: jnp.vdot(rx.add_rows(y, token, n, TOKENS), x))(y) ** 2)))
    np.testing.assert_allclose(
        twice(x), jax.grad(lambda x: jnp.sum(plain_take(x, token, n) ** 2))(x),
        rtol=1e-6)


@pytest.mark.parametrize("n", [0, 1, rx.CHUNK + 1], ids=lambda n: f"n{n}")
def test_rows_from_n_on_are_never_read(n):
    """NaN in ``y`` and ``x``'s gathered rows, and token ids out of range,
    from row n on: nothing changes, value or cotangent."""
    x, y, token = rows_case(jnp.float32)
    dead = jnp.arange(ROWS) >= n
    wild = jnp.where(dead, jnp.where(jnp.arange(ROWS) % 2 == 0, TOKENS + 7,
                                     -3), token)
    nan_y = jnp.where(dead[:, None], jnp.nan, y)
    assert jnp.array_equal(rx.add_rows(nan_y, wild, n, TOKENS),
                           plain_add(y, token, n, TOKENS))
    assert jnp.array_equal(rx.take_rows(x, wild, n), plain_take(x, token, n))
    # the cotangents take the same walk
    pulled, = jax.vjp(lambda x: rx.take_rows(x, wild, n), x)[1](nan_y)
    assert jnp.array_equal(pulled, plain_add(y, token, n, TOKENS))
    pulled, = jax.vjp(lambda y: rx.add_rows(y, wild, n, TOKENS), nan_y)[1](x)
    assert jnp.array_equal(pulled, plain_take(x, token, n))


def test_a_buffer_under_a_chunk_is_one_chunk():
    x, y, token = rows_case(jnp.float32)
    rows = 40
    for n in (0, 1, rows):
        assert jnp.array_equal(rx.take_rows(x, token[:rows], n),
                               plain_take(x, token[:rows], n))
        assert jnp.array_equal(rx.add_rows(y[:rows], token[:rows], n, TOKENS),
                               plain_add(y[:rows], token[:rows], n, TOKENS))
        assert int(rx.rows_walked(n, rows)) == (rows if n else 0)


def test_the_walk_is_a_loop_read_from_the_count():
    """In the traced layer, value and gradients: four ``while`` loops (the
    gather, the scatter-add and the transpose of each), and outside them no
    gather or scatter-add of rows of the latent width."""
    layer, u, arrays = layer_and_arrays(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda u, *a: layer._route_and_mix(u, *a).astype(
            jnp.float32).sum()))(u, *arrays)
    latent = arrays[2].shape[-1]
    assert str(jaxpr).count(" while[") == 4
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name in ("gather", "scatter-add"):
            assert eqn.outvars[0].aval.shape[-1] != latent, eqn


def test_the_pair_is_traced_once_for_layers_of_one_shape(monkeypatch):
    """Three layers of one shape, value and gradients: the four loops (the
    gather, the scatter-add and the transpose of each) are traced once, not
    once a layer (a trace of theirs is 30 ms of every run's set-up)."""
    walks = []
    plain = rx._over_chunks
    monkeypatch.setattr(rx, "_over_chunks",
                        lambda *a: walks.append(a[1]) or plain(*a))
    routed, (x, w_local, w1, w3, w2) = _loop_case(seed=9, t=37)

    def loss(x, w_local, w1, w3, w2):
        for _ in range(3):
            x = x + _every_pair(routed, 88, 88, x, w_local, w1, w3, w2)
        return jnp.sum(x * x)

    jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(5)))(
        x, w_local, w1, w3, w2)
    assert len(walks) == 4 and set(walks) == {88}


def test_buffer_rows_bound_and_cap():
    # the hybrid cell's and the Laguna cell's buffers
    assert rx.buffer_rows(16384, 22, 8, 512, 3.0) == 16896
    assert rx.buffer_rows(16384, 10, 8, 256, 3.0) == 15360
    # never more than one row a token and held expert (or chosen expert)
    assert rx.buffer_rows(48, 5, 4, 16, 16.0) == 48 * 4
    assert rx.buffer_rows(48, 2, 4, 16, 16.0) == 48 * 2
    assert rx.buffer_rows(100, 5, 4, 16, 1.0) % 8 == 0


def test_sort_pairs_and_mix_against_a_loop():
    """Pairs sorted by expert then token; ``mix`` equals a loop over the
    experts; pairs past the buffer are left out and counted."""
    rng = np.random.default_rng(3)
    t, held, width, ff = 24, 3, 8, 16
    routed = jnp.asarray(rng.random((t, held)) < 0.4)
    w_local = jnp.where(routed, jnp.asarray(rng.random((t, held)),
                                            jnp.float32), 0.0)
    x = jnp.asarray(rng.normal(size=(t, width)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(held, width, ff)), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(held, ff, width)), jnp.float32)
    act = lambda a, g: jax.nn.silu(a) * g
    total = int(routed.sum())
    pairs = rx.sort_pairs(routed, 8 * -(-total // 8))
    n = int(pairs.ends[-1])
    assert n == total and int(pairs.live.sum()) == total
    order = np.stack([np.asarray(pairs.expert), np.asarray(pairs.token)])[:, :n]
    assert (np.lexsort(order[::-1]) == np.arange(n)).all()
    assert bool(routed[order[1], order[0]].all())
    with moe_stats.moe_stats_tap() as tap:
        got = rx.mix(pairs, x, routed, w_local, (w1, w3), act, w2)
    want = sum(w_local[:, e:e + 1] * (act(x @ w1[e], x @ w3[e]) @ w2[e])
               for e in range(held))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(tap[0]), [total, int((~routed.any(1)).sum()), 0,
                             len(pairs.token)])
    # a buffer of 8 rows: the first 8 pairs in (expert, token) order stay
    small = rx.sort_pairs(routed, 8)
    with moe_stats.moe_stats_tap() as tap:
        cut = rx.mix(small, x, routed, w_local, (w1, w3), act, w2)
    kept = np.zeros((t, held), bool)
    kept[order[1, :8], order[0, :8]] = True
    want = sum((w_local * kept)[:, e:e + 1]
               * (act(x @ w1[e], x @ w3[e]) @ w2[e]) for e in range(held))
    np.testing.assert_allclose(cut, want, rtol=2e-5, atol=2e-5)
    assert float(tap[0][2]) == total - 8 and float(tap[0][3]) == 8


def _loop_case(seed=3, t=24, held=3, width=8, ff=16, share=0.6):
    rng = np.random.default_rng(seed)
    routed = jnp.asarray(rng.random((t, held)) < share)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    w_local = jnp.where(routed, jnp.abs(f(t, held)), 0.0)
    return routed, (f(t, width), w_local, f(held, width, ff),
                    f(held, width, ff), f(held, ff, width))


def _loop(routed, x, w_local, w1, w3, w2):
    return sum(jnp.where(routed, w_local, 0.0)[:, e:e + 1]
               * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
               for e in range(routed.shape[1]))


def _every_pair(routed, rows, most, x, w_local, w1, w3, w2):
    return rx.mix_every_pair(routed, rows, most, x, w_local, (w1, w3),
                             lambda a, g: jax.nn.silu(a) * g, w2)


@pytest.mark.parametrize("rows", [8, 16, 24, 72], ids=lambda r: f"rows{r}")
def test_mix_every_pair_loses_none_whatever_the_buffer(rows):
    """Buffers of 8 to 72 rows under 39 pairs (five buffers to one):
    value and every gradient equal a loop over the experts, and the tap
    reads no pair left out."""
    routed, arrays = _loop_case()
    total, most = int(routed.sum()), routed.size
    assert 8 * 4 < total < 8 * 5
    with moe_stats.moe_stats_tap() as tap:
        got = _every_pair(routed, rows, most, *arrays)
    np.testing.assert_allclose(got, _loop(routed, *arrays), rtol=2e-5,
                               atol=2e-5)
    # a buffer under a chunk is walked whole or not at all
    np.testing.assert_array_equal(
        np.asarray(tap[0]), [total, int((~routed.any(1)).sum()), 0,
                             rows * -(-total // rows)])
    probe = jnp.asarray(np.random.default_rng(4).normal(size=got.shape),
                        jnp.float32)
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * probe), argnums=range(5)))(*arrays)
    for got, want in zip(grads(lambda *a: _every_pair(routed, rows, most, *a)),
                         grads(lambda *a: _loop(routed, *a))):
        np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)


def test_mix_every_pair_is_mix_where_one_buffer_takes_all():
    """With a buffer no load can pass it is ``mix``'s program (no ``cond``
    is traced); with one the load happens to fit it is ``mix``'s numbers."""
    routed, arrays = _loop_case()
    act = lambda a, g: jax.nn.silu(a) * g
    one = lambda rows: lambda x, w, w1, w3, w2: rx.mix(
        rx.sort_pairs(routed, rows), x, routed, w, (w1, w3), act, w2)
    both = lambda f: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.square(f(*a))), argnums=range(5)))(*arrays)
    whole = lambda *a: _every_pair(routed, 72, 72, *a)
    assert " cond[" not in str(jax.make_jaxpr(whole)(*arrays))
    fits = lambda *a: _every_pair(routed, 48, 72, *a)
    assert " cond[" in str(jax.make_jaxpr(fits)(*arrays))
    for rows, f in ((72, whole), (48, fits)):
        for got, want in zip(jax.tree_util.tree_leaves(both(f)),
                             jax.tree_util.tree_leaves(both(one(rows)))):
            assert jnp.array_equal(got, want)


def test_mix_every_pair_counts_what_a_false_bound_leaves_out():
    routed, arrays = _loop_case()
    total = int(routed.sum())
    with moe_stats.moe_stats_tap() as tap:
        _every_pair(routed, 8, 24, *arrays)     # three buffers of 8
    assert float(tap[0][2]) == total - 24 and float(tap[0][3]) == 24


@pytest.mark.parametrize("share", [0.0, 0.02, 0.3, 0.7],
                         ids=lambda s: f"share{s}")
def test_the_tap_counts_the_rows_walked(share):
    """Buffers of two and a half chunks: the fourth number is the pairs each
    buffer took rounded up to whole chunks, at most the buffer, summed over
    the buffers run; 0 where no pair is routed; never over the buffers'
    rows; and ``mix_every_pair`` still equals the loop over the experts."""
    routed, arrays = _loop_case(seed=8, t=1400, held=3, share=share)
    total, most = int(routed.sum()), routed.size
    with moe_stats.moe_stats_tap() as tap:
        got = _every_pair(routed, ROWS, most, *arrays)
        rx.mix(rx.sort_pairs(routed, ROWS), arrays[0], routed, arrays[1],
               arrays[2:4], lambda a, g: jax.nn.silu(a) * g, arrays[4])
    every, one = np.asarray(tap[0]), np.asarray(tap[1])
    buffers = -(-most // ROWS)
    want = sum(walked(min(max(total - c * ROWS, 0), ROWS), ROWS)
               for c in range(buffers))
    assert every[3] == want and every[2] == 0 and every[0] == total
    assert every[3] <= ROWS * max(1, -(-total // ROWS))
    assert one[3] == walked(min(total, ROWS), ROWS)
    assert one[2] == max(total - ROWS, 0)
    assert (every[3] == 0) == (total == 0)
    assert (total > ROWS) == (share == 0.7)        # a pair in a later buffer
    np.testing.assert_allclose(got, _loop(routed, *arrays), rtol=2e-4,
                               atol=2e-4)
    # and every gradient, through the chunks of each buffer run
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.square(f(*a))), argnums=range(5)))(*arrays)
    for got, want in zip(grads(lambda *a: _every_pair(routed, ROWS, most, *a)),
                         grads(lambda *a: _loop(routed, *a))):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_held_weights_normalise_over_all_the_chosen():
    scores = jnp.asarray([[0.1, 0.4, 0.3, 0.2], [0.25, 0.25, 0.25, 0.25]])
    picked = topk_mask(scores, 2)
    routed, w = rx.held_weights(scores, picked, 2.5, 2, 2)
    np.testing.assert_array_equal(routed, [[True, False], [False, False]])
    np.testing.assert_allclose(w, [[2.5 * 0.3 / 0.7, 0.0], [0.0, 0.0]],
                               rtol=1e-6)
