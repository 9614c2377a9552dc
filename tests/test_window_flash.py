"""The band regime of ``ops/pallas/causal_flash.py`` (query i sees keys j with
``0 <= i - j < window``; kernels ``window_flash_fwd`` / ``window_flash_bwd``)
in interpret mode against a masked softmax in float32: forward and the three
gradients. Tolerances: float32 differs in the order of sums only (read 2e-7
to 5e-7 of the largest entry); bfloat16 keeps 8 bits and the kernel rounds p
and ds to it before their products (read 2.5e-3 to 3.9e-3)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import causal_flash as cf

WINDOW, D = 512, 128
TOL = {jnp.float32: 5e-6, jnp.bfloat16: 8e-3}


def masked_softmax(q, k, v, window):
    s = q.shape[2]
    f32 = jnp.float32
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                        k.astype(f32)) / math.sqrt(q.shape[3])
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(f32))


def banded(q, k, v, heads):
    return cf.causal_flash_qkv(jnp.concatenate([q, k, v], 1), heads, D,
                               window=WINDOW)


def gap(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                 / jnp.max(jnp.abs(want)))


def inputs(seq, dtype, batch=1, heads=1):
    keys = jax.random.split(jax.random.PRNGKey(seq), 4)
    return [jax.random.normal(k, (batch, heads, seq, D),
                              jnp.float32).astype(dtype) for k in keys]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("seq", [1024, 2048, 8192])
def test_forward_and_gradients_match_the_masked_softmax(seq, dtype):
    q, k, v, do = inputs(seq, dtype)
    out, vjp = jax.vjp(lambda q, k, v: banded(q, k, v, 1), q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: masked_softmax(q, k, v, WINDOW),
                             q, k, v)
    tol = TOL[dtype]
    assert out.dtype == dtype and gap(out, want) <= tol
    for name, got, ref in zip("qkv", vjp(do),
                              want_vjp(do.astype(jnp.float32))):
        assert gap(got, ref) <= tol, f"d{name}: {gap(got, ref):.2e}"


@pytest.mark.parametrize("blk", [256, 512])
def test_first_rows_see_fewer_keys_than_the_window(blk, monkeypatch):
    """Rows 0 ... 511 have fewer than 512 keys behind them (the first q tile
    meets no trailing tile); rows 512 ... see exactly 512. Two heads and two
    rows of the batch, at either tile size (256: a whole tile between the
    diagonal and the edge)."""
    monkeypatch.setattr(cf, "_WIN_BLK", blk)
    q, k, v, _ = inputs(1024, jnp.float32, batch=2, heads=2)
    out = banded(q, k, v, 2)
    want = masked_softmax(q, k, v, WINDOW)
    assert gap(out[:, :, :WINDOW], want[:, :, :WINDOW]) <= TOL[jnp.float32]
    assert gap(out[:, :, WINDOW:], want[:, :, WINDOW:]) <= TOL[jnp.float32]
    # the first rows equal plain causal attention, the later ones do not
    causal = cf.causal_flash_qkv(jnp.concatenate([q, k, v], 1), 2, D)
    assert gap(out[:, :, :WINDOW], causal[:, :, :WINDOW].astype(
        jnp.float32)) <= TOL[jnp.float32]
    assert gap(out[:, :, WINDOW + 64:], causal[:, :, WINDOW + 64:].astype(
        jnp.float32)) > 1e-3


@pytest.mark.parametrize("nq,nb,pairs", [(2, 1, 3), (16, 1, 31), (4, 2, 9)])
def test_band_tables_hold_only_the_pairs_the_band_touches(nq, nb, pairs):
    qi, kc = cf._band_tables(nq, nb)
    assert len(qi) == len(kc) == pairs
    assert all(max(q - nb, 0) <= k <= q for q, k in zip(qi, kc))
    assert len(set(zip(qi.tolist(), kc.tolist()))) == pairs
    # the triangle at the cell's size, for scale: 136 pairs against 31
    assert len(cf._triangle_tables(16)[0]) == 136


def test_supported_takes_one_window_and_whole_tiles():
    for seq in range(1024, 8192 + 1, 512):
        assert cf.supported(seq, 128, window=512)
    assert not cf.supported(512, 128, window=512)
    assert not cf.supported(1024 + 256, 128, window=512)
    assert not cf.supported(8192 + 512, 128, window=512)
    assert not cf.supported(2048, 64, window=512)
    assert not cf.supported(2048, 128, window=256)
    assert not cf.supported(2048, 128, window=1024)
    with pytest.raises(ValueError, match="window 256"):
        cf.causal_flash_qkv(jnp.zeros((1, 3, 2048, 128)), 1, 128, window=256)


def test_enabled_follows_the_flag_and_the_window(monkeypatch):
    from paddle_tpu.framework import flags

    assert not cf.enabled(2048, 128, 512)          # off the chip, flag unset
    monkeypatch.setitem(flags._REGISTRY, "FLAGS_use_packed_attention", True)
    assert cf.enabled(2048, 128, 512) and cf.enabled(2048, 128)
    assert not cf.enabled(2048, 128, 384)


@pytest.mark.parametrize("seq,heads,d", [(1024, 2, 64), (2048, 1, 128),
                                         (8192, 1, 128)])
def test_without_a_window_every_path_traces_as_before(seq, heads, d):
    """``window=None`` is the call of before: the same jaxpr, forward and
    backward, with the causal kernels' names and none of the band's."""
    hpb = cf.heads_per_block(heads, d)
    qkv = jnp.zeros((1, 3 * heads // hpb, seq, hpb * d), jnp.bfloat16)
    grad_of = lambda f: jax.make_jaxpr(jax.grad(
        lambda x: f(x).astype(jnp.float32).sum()))(qkv)
    before = str(grad_of(lambda x: cf.causal_flash_qkv(x, heads, d)))
    none = str(grad_of(lambda x: cf.causal_flash_qkv(x, heads, d,
                                                     window=None)))
    assert before == none
    assert "causal_flash_" in before and "window_flash" not in before
    if cf.supported(seq, d, window=512):
        band = str(grad_of(lambda x: cf.causal_flash_qkv(x, heads, d,
                                                         window=512)))
        assert "window_flash_fwd" in band and "window_flash_bwd" in band
        assert "causal_flash_" not in band


def test_pair_grads_edge_keeps_the_strict_upper_triangle():
    """The trailing tile of a row: key c of the tile a window behind is seen
    by query r exactly where c > r."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, do, k, v = (jax.random.normal(x, (8, 128), jnp.float32) for x in ks)
    lse = jnp.full((8, 1), 3.0)
    delta = jnp.zeros((8, 1))
    scale = 1 / math.sqrt(128)
    dq, dk, dv = cf._pair_grads(q, do, k, v, lse, delta, scale=scale,
                                masked=False, edge=True)
    p = jnp.exp(q @ k.T * scale - lse) * np.triu(np.ones((8, 8)), 1)
    np.testing.assert_allclose(dv, p.T @ do, rtol=1e-5, atol=1e-6)
    ds = p * (do @ v.T) * scale
    np.testing.assert_allclose(dq, ds @ k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dk, ds.T @ q, rtol=1e-5, atol=1e-6)
