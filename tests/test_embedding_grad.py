"""``F.embedding``'s gradient: a table whose rows the chip's scatter-add walks
slowly (wider than ``common._SLOW_ROW_LANES``, 5, 7, ... times a power of
two: 2560 = 5 x 512) sums the lookup's cotangent rows by id in float32, one
scatter-add a power-of-two group of columns (2048 + 512), rounded once to the
table's type
(``common._take_rows_apart``, PR 38); any other table keeps ``jnp.take``'s
own transpose. Either way the forward is ``jnp.take``'s, bit for bit, and
ids are read as ``jnp.take`` reads them. Here the bound is 512 lanes, so a
640-wide table (5 x 128) stands for the chip's 2560-wide one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from paddle_tpu.nn.functional import common

N = 96
BF16 = jnp.bfloat16


def _ids(case, rows):
    rng = np.random.default_rng(38)
    ids = rng.integers(0, rows, N)
    ids[: N // 4] = ids[0]  # a quarter of the rows on one id
    if case == "negative":
        ids[1::3] -= rows  # wraps once, as jnp.take reads it
    if case == "out_of_range":
        ids[1::5] = rows + 7
        ids[2::5] = -rows - 3
    return jnp.asarray(ids.reshape(2, N // 2), jnp.int32)


def chip_sum(ct, ids, rows, width):
    """``zeros.at[id].add(row)`` as the chip computes it for a narrow type:
    the float32 sum rounded once; ids wrap once, those still out of range
    add nothing."""
    flat = ids.reshape(-1)
    flat = jnp.where(flat < 0, flat + rows, flat)
    ct = ct.reshape(-1, width).astype(jnp.float32)
    ok = (flat >= 0) & (flat < rows)
    return jnp.zeros((rows, width), jnp.float32).at[jnp.where(ok, flat, 0)].add(
        jnp.where(ok[:, None], ct, 0)).astype(BF16)


@pytest.mark.parametrize("tied", [False, True], ids=["lookup", "tied_head"])
@pytest.mark.parametrize("case", ["duplicates", "negative", "out_of_range",
                                  "padding_idx"])
@pytest.mark.parametrize("rows", [25008, 25088, 50304])
@pytest.mark.parametrize("width", [640, 1024], ids=["apart", "whole"])
def test_lookup_and_its_gradient(monkeypatch, width, rows, case, tied):
    monkeypatch.setattr(common, "_SLOW_ROW_LANES", 512)
    assert bool(common._lane_groups(width)) == (width == 640)
    k = jax.random.split(jax.random.PRNGKey(rows), 4)
    table = jax.random.normal(k[0], (rows, width), jnp.float32).astype(BF16)
    ids = _ids(case, rows)
    pad = int(ids[0, 0]) if case == "padding_idx" else None
    weights = jax.random.normal(k[1], ids.shape + (width,), jnp.float32)
    hidden = jax.random.normal(k[2], (3, width), jnp.float32).astype(BF16)
    dlogits = jax.random.normal(k[3], (3, rows), jnp.float32)

    def lookup(t):
        return F.embedding(ids, t, padding_idx=pad)._data

    def head(t):
        return jnp.sum(jnp.dot(hidden, t.T).astype(jnp.float32) * dlogits)

    def loss(t):
        out = jnp.sum(lookup(t).astype(jnp.float32) * weights)
        return out + head(t) if tied else out

    want = jnp.take(table, ids, axis=0)
    if pad is not None:
        want = jnp.where((ids == pad)[..., None], jnp.zeros((), BF16), want)
    np.testing.assert_array_equal(np.asarray(lookup(table), np.float32),
                                  np.asarray(want, np.float32))

    ct = weights.astype(BF16)
    if pad is not None:
        ct = jnp.where((ids == pad)[..., None], jnp.zeros((), BF16), ct)
    if width == 640:
        summed = chip_sum(ct, ids, rows, width)
    else:  # jnp.take's own transpose, the parent's
        summed = jax.grad(lambda t: jnp.sum(
            jnp.take(t, ids, axis=0).astype(jnp.float32)
            * ct.astype(jnp.float32)))(table)
    if tied:
        summed = summed + jax.grad(head)(table)
    got = jax.grad(loss)(table)
    assert got.dtype == BF16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(summed, np.float32))
    if pad is not None:
        assert not np.asarray(jax.grad(lambda t: jnp.sum(
            lookup(t).astype(jnp.float32) * weights))(table)[pad]).any()
