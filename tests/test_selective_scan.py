"""Mamba-1's selective scan (``paddle_tpu.ops.pallas.selective_scan``): the
kernel pair in interpret mode and the chunked ``lax`` form against the
recurrence written position by position in a Python loop, value and all six
gradients.

Tolerance 2e-5 of each array's largest value: everything is float32 on the
CPU and the three differ in the order of sums only (the kernels sum ``dB``
and ``dC`` over lanes by a product with ones, the loop by ``jnp.sum``); the
worst reading was 5e-7. A wrong carry across a chunk's boundary, a state
read one position off or a missing ``D x`` reads 1e-2 and more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import selective_scan as ss

TOL = 2e-5
NAMES = ("x", "delta", "A", "B", "C", "D")


def by_position(x, delta, a, bm, cm, d):
    """``h_t = exp(delta_t (x) A) h_{t-1} + (delta_t x_t) (x) B_t``; ``y_t =
    h_t . C_t + D x_t``, one position after the other."""
    b, s, c = x.shape
    h, ys = jnp.zeros((b, c, a.shape[1]), jnp.float32), []
    for t in range(s):
        h = (jnp.exp(delta[:, t, :, None] * a) * h
             + (delta[:, t] * x[:, t])[..., None] * bm[:, t, None, :])
        ys.append(jnp.sum(h * cm[:, t, None, :], -1) + d * x[:, t])
    return jnp.stack(ys, 1)


def operands(b, s, c, n, a_log, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return (mk(b, s, c), jax.nn.softplus(mk(b, s, c)),
            -jnp.exp(a_log + 0.02 * mk(c, n)), mk(b, s, n), mk(b, s, n),
            1 + 0.1 * mk(c)), mk(b, s, c)


def value_and_grads(fn, args, dy):
    y, vjp = jax.vjp(fn, *args)
    return (y,) + vjp(dy)


def close(got, want, what):
    for name, g, w in zip(("y",) + NAMES, got, want):
        gap = float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        assert gap <= TOL, f"{what}, {name}: gap {gap:.3e}"


@pytest.fixture
def kernels(monkeypatch):
    """``selective_scan`` through the kernel pair (interpret mode off the
    chip) wherever the shape is supported."""
    monkeypatch.setattr(ss, "enabled", ss.supported)


FORMS = pytest.mark.parametrize("form", ["kernels", "lax"])
# the seeded decay (A ~ -1: half a position) and one near 1 (a long memory)
DECAYS = pytest.mark.parametrize("a_log", [0.0, -4.0],
                                 ids=["seeded", "long-memory"])


@FORMS
@DECAYS
@pytest.mark.parametrize("seq,chunk", [(16, 16), (48, 16), (40, 16)],
                         ids=["one-chunk", "three-chunks", "ragged"])
def test_value_and_six_gradients_against_the_recurrence(form, a_log, seq,
                                                        chunk, monkeypatch):
    if form == "kernels":
        monkeypatch.setattr(ss, "enabled", ss.supported)
    args, dy = operands(2, seq, 256, 16, a_log)
    got = value_and_grads(lambda *t: ss.selective_scan(*t, chunk=chunk), args,
                          dy)
    close(got, value_and_grads(by_position, args, dy),
          f"{form}, S = {seq} in chunks of {chunk}")


@DECAYS
def test_rows_that_do_not_fill_a_register_and_several_groups(kernels, a_log):
    """1280 channels are 10 rows of 128: a whole group of 8 and one of 2 (the
    cell's 2560 are 8 + 8 + 4); 8 states; one row of the batch."""
    args, dy = operands(1, 24, 1280, 8, a_log, seed=3)
    got = value_and_grads(lambda *t: ss.selective_scan(*t, chunk=8), args, dy)
    close(got, value_and_grads(by_position, args, dy), "10 rows, 8 states")


def test_long_memory_is_carried_across_every_chunk(kernels):
    """At A ~ -0.018 and delta ~ 0.7 the first position still weighs 0.6 at
    position 40: y at the end changes with x at the start, by the
    recurrence's own amount."""
    args, _ = operands(1, 40, 128, 16, -4.0, seed=5)
    moved = (args[0].at[:, 0].add(1.0),) + args[1:]
    change = lambda fn: fn(*moved)[:, -1] - fn(*args)[:, -1]
    got = change(lambda *t: ss.selective_scan(*t, chunk=8))
    want = change(by_position)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_bfloat16_x_is_raised_inside(kernels):
    """x, B and C in bfloat16 (the training step's types): the scan equals
    the float32 recurrence on the same values, and dx comes back in
    bfloat16."""
    args, dy = operands(2, 32, 256, 16, 0.0, seed=7)
    lo = lambda t: t.astype(jnp.bfloat16)
    args = (lo(args[0]), args[1], args[2], lo(args[3]), lo(args[4]), args[5])
    got = value_and_grads(lambda *t: ss.selective_scan(*t, chunk=16), args,
                          dy)
    assert got[1].dtype == got[4].dtype == jnp.bfloat16
    up = tuple(t.astype(jnp.float32) for t in args)
    want = value_and_grads(by_position, up, dy)
    for name, g, w in zip(("y",) + NAMES, got, want):
        tol = 5e-3 if g.dtype == jnp.bfloat16 else TOL
        gap = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                    / jnp.max(jnp.abs(w)))
        assert gap <= tol, f"{name}: gap {gap:.3e}"


def test_the_two_forms_keep_the_same_chunk_boundaries(monkeypatch):
    """Kernels and ``lax`` form at the same chunk agree to rounding order."""
    args, dy = operands(2, 64, 256, 16, 0.0, seed=9)
    lax = value_and_grads(lambda *t: ss.selective_scan(*t, chunk=16), args, dy)
    monkeypatch.setattr(ss, "enabled", ss.supported)
    close(value_and_grads(lambda *t: ss.selective_scan(*t, chunk=16), args,
                          dy), lax, "kernels against lax")


def test_supported_says_which_shapes_the_kernels_take():
    assert ss.supported(8192, 2560, 16)         # the cell's
    assert ss.supported(100, 5120, 16)          # the uncut layer, any length
    assert not ss.supported(8192, 2500, 16)     # channels off the lanes
    assert not ss.supported(8192, 2560, 32)     # more states than registers
    assert not ss.supported(8192, 128 * 1024, 16)   # past fast memory
    assert not ss.enabled(8192, 2560, 16)       # off the chip: the lax form
