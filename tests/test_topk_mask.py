"""``ops/pallas/topk_mask.py``: the chosen set found by threshold is
``jax.lax.top_k``'s, element for element, equal entries included — through
the ``jax.numpy`` path and through the kernel in interpret mode — and
``LatentMoE`` with it is the layer it was with the index sort, bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.ops.pallas import topk_mask as tm


def by_index_sort(v, k):
    """The selection as ``LatentMoE`` made it before the threshold: the twin."""
    chosen = jax.lax.top_k(jax.lax.stop_gradient(v), k)[1]
    return jnp.any(chosen[..., None] == jnp.arange(v.shape[-1]), axis=1)


def _scores(case, tokens, experts):
    v = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(31),
                                         (tokens, experts), jnp.float32))
    if case == "rounded":        # most rows tie AT the k-th place
        v = jnp.round(v * 64) / 64
    elif case == "all-equal":    # the first k indices are chosen
        v = v.at[3].set(0.5).at[tokens - 1].set(-jnp.inf)
    elif case == "biased":       # a selection bias reorders the set
        v = v + jnp.linspace(-0.4, 0.4, experts, dtype=jnp.float32)[::-1]
    return v


# (case, tokens, experts, k); the kernel takes those ``supported`` takes
CASES = [("seeded", 512, 512, 22), ("rounded", 512, 512, 22),
         ("all-equal", 512, 512, 22), ("biased", 512, 512, 22),
         ("seeded", 512, 512, 1), ("rounded", 512, 512, 511),
         ("rounded", 384, 80, 22), ("seeded", 40, 16, 5)]
KERNEL_CASES = [("seeded", 1024, 512, 22), ("rounded", 1024, 512, 22),
                ("all-equal", 1024, 512, 22), ("biased", 1024, 512, 22),
                ("rounded", 2048, 64, 1), ("rounded", 1024, 96, 32),
                ("rounded", 1024, 40, 5)]
_ids = lambda cases: [f"{c}-{t}x{e}-k{k}" for c, t, e, k in cases]


@pytest.mark.parametrize("case,tokens,experts,k", CASES, ids=_ids(CASES))
def test_mask_is_top_ks_set(case, tokens, experts, k):
    v = _scores(case, tokens, experts)
    assert not tm.enabled(tokens, experts, k)      # the CPU: jax.numpy
    got, want = tm.topk_mask(v, k), by_index_sort(v, k)
    assert got.dtype == jnp.bool_ and got.shape == v.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "rounded":
        kth = jax.lax.top_k(v, k)[0][:, -1:]
        assert int(jnp.sum(jnp.sum(v == kth, -1) > 1)) > tokens // 2
    if case == "all-equal":
        assert np.asarray(got)[3].nonzero()[0].tolist() == list(range(k))


@pytest.mark.parametrize("case,tokens,experts,k", KERNEL_CASES,
                         ids=_ids(KERNEL_CASES))
def test_kernel_mask_is_top_ks_set(monkeypatch, case, tokens, experts, k):
    """The same through the kernel, interpreted here."""
    monkeypatch.setattr(tm, "enabled", tm.supported)
    assert tm.supported(tokens, experts, k)
    v = _scores(case, tokens, experts)
    np.testing.assert_array_equal(np.asarray(tm.topk_mask(v, k)),
                                  np.asarray(by_index_sort(v, k)))


@pytest.mark.parametrize("tokens,experts,k,itemsize", [
    (16384, 500, 22, 4), (16384, 512, 33, 4), (1000, 512, 22, 4),
    (1024, 2048, 22, 4), (1024, 512, 22, 2), (1024, 16, 22, 4)],
    ids=["experts-off-the-lists", "k-over-32", "tokens-off-the-tile",
         "block-over-vmem", "not-float32", "k-over-experts"])
def test_unsupported_shapes_take_the_twin(tokens, experts, k, itemsize):
    assert not tm.supported(tokens, experts, k, itemsize)
    assert tm.supported(16384, 512, 22) and not tm.enabled(16384, 512, 22)


def _layer():
    cfg = nh.NemotronHConfig(
        hidden_size=64, n_routed_experts=64, num_experts_per_tok=6,
        moe_intermediate_size=48, moe_latent_size=32, experts_held=8,
        first_expert=8, moe_shared_expert_intermediate_size=96,
        local_pairs_bound=8.0)
    layer = nh.LatentMoE(cfg)
    ws = [jnp.asarray(w._data) for w in (
        layer.router.weight, layer.e_score_correction_bias,
        layer.latent_down.weight, layer.latent_up.weight, layer.experts_w1,
        layer.experts_w2, layer.shared_up.weight, layer.shared_down.weight)]
    # a bias that reorders the set; router columns equal in pairs, so that
    # scores tie exactly and the lowest index has to win
    ws[1] = jnp.linspace(0.2, -0.2, 64, dtype=jnp.float32)
    ws[0] = ws[0].at[:, 1::2].set(ws[0][:, ::2])
    ws[1] = ws[1].at[1::2].set(ws[1][::2])
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    u = jax.random.normal(ks[0], (1, 1024, 64), jnp.float32)
    du = jax.random.normal(ks[1], (1, 1024, 64), jnp.float32)
    return layer, u, du, ws


def _out_and_grads(layer, u, du, ws):
    y, vjp = jax.vjp(layer._route_and_mix, u, *ws)
    return (y,) + vjp(du)


@pytest.mark.parametrize("path", ["jax.numpy", "kernel"])
def test_latent_moe_is_bit_equal_to_the_index_sort(monkeypatch, path):
    """Output and all nine gradients of ``LatentMoE``: the selection by
    threshold against the index sort kept here as its twin."""
    layer, u, du, ws = _layer()
    if path == "kernel":
        monkeypatch.setattr(tm, "enabled", tm.supported)
    got = _out_and_grads(layer, u, du, ws)
    monkeypatch.setattr(tm, "topk_mask", by_index_sort)
    want = _out_and_grads(layer, u, du, ws)
    assert len(got) == 10
    assert float(jnp.abs(got[1]).max()) > 0 and float(jnp.abs(got[2]).max()) > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_selection_bias_chooses_and_does_not_weigh():
    """A bias moves the chosen set and stays out of the sum over it (the
    mask is boolean: ``total`` is taken from the unbiased scores)."""
    v = _scores("seeded", 64, 64)
    bias = jnp.where(jnp.arange(64) < 6, 2.0, 0.0)
    picked = tm.topk_mask(v + bias, 6)
    assert bool(jnp.all(picked[:, :6])) and int(picked.sum()) == 64 * 6
    assert not bool(jnp.all(picked == tm.topk_mask(v, 6)))
    total = jnp.sum(jnp.where(picked, v, 0.0), -1)
    np.testing.assert_allclose(np.asarray(total), np.asarray(v[:, :6].sum(-1)),
                               rtol=1e-6)
