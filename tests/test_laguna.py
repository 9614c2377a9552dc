"""Laguna (``paddle_tpu.models.laguna``) against the plain reference
(``benchmarks/reference/laguna.py``: float32, explicit masks, experts as a
masked loop, rotary written from the formulas) on seeded weights at a small
size.

Tolerances. ``F32``: program and reference both in float32 on the CPU; they
differ in the order of sums only (a sorted buffer against a masked loop, the
head-major layout against a reshape): the worst leaf's gradient read 2e-6 of
its largest value, the limit stands at 1e-4; a wrong window edge, rotary
pairing or head-to-group map reads 1e-2 and more. ``BF16_*``: the compiled
step with bfloat16 leaves against the float32 reference, by the benchmark's
own numbers (gap of norms by leaf block), limits as
``tests/test_nemotron_h.py`` sets them.
"""
import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_nemotron_h import (ADAM, BF16_GRAD, BF16_LOSS, BF16_UPDATE, F32,
                             IDENT, close, ids_of)

from benchmarks.builders import laguna as builder
from benchmarks.reference import laguna as ref
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import functional_call, param_arrays
from paddle_tpu.models import laguna as lg

SEED = 2**31 + 9
FULL, WINDOW = "full_attention", "sliding_attention"
ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000,
             "partial_rotary_factor": 1}}

# unequal group sizes: 6 query heads a key/value head on full layers, 9 on
# sliding ones (the source's 48 / 8 and 72 / 8), at 2 key/value heads
WHOLE = {
    "reference": "laguna", "builder": "laguna",
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "sliding_window": 8, "rms_norm_eps": 1e-6, "vocab_size": 128,
    "num_hidden_layers": 5,
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12],
    "num_key_value_heads": 2,
    "num_experts": 16, "num_experts_per_tok": 5,
    "moe_intermediate_size": 48, "shared_expert_intermediate_size": 32,
    "moe_routed_scaling_factor": 2.5, "rope_parameters": ROPE,
    "held": {"first_expert": 0, "dense_mlp_columns": 96,
             "shared_expert_columns": 32, "local_pairs_bound": 16.0},
    "dtype": "float32",
}
WHOLE["published"] = {k: WHOLE[k] for k in (
    "vocab_size", "layer_types", "num_attention_heads_per_layer",
    "num_key_value_heads", "num_experts")}


def config(layers=None, **changes):
    """WHOLE with ``layers`` [(attention kind, ffn kind)] and ``changes``
    (keys of ``held`` go there)."""
    cfg = copy.deepcopy(WHOLE)
    held = {k: changes.pop(k) for k in list(changes) if k in cfg["held"]}
    cfg.update(changes, held=dict(cfg["held"], **held))
    if layers:
        cfg["layer_types"] = [a for a, _ in layers]
        cfg["mlp_layer_types"] = [f for _, f in layers]
        cfg["num_hidden_layers"] = len(layers)
    group = {FULL: 6, WINDOW: 9}
    cfg["num_attention_heads_per_layer"] = [
        group[a] * cfg["num_key_value_heads"] for a in cfg["layer_types"]]
    return cfg


def share(layers=None):
    """A share as the cell cuts it: half the heads of each kind with the
    key/value head they read, 4 of 16 experts from the fifth on, a quarter of
    the dense layer's columns and half the shared expert's, a quarter of the
    vocabulary."""
    return config(layers, vocab_size=32, num_key_value_heads=1,
                  num_experts=4, first_expert=4, dense_mlp_columns=24,
                  shared_expert_columns=16, local_pairs_bound=4.0)


def program_loss_and_grads(cfg, params, ids, labels):
    model = lg.LagunaForCausalLM(builder.model_config(cfg))

    def loss(p):
        logits = functional_call(model, p, Tensor._wrap(ids))
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(logz - gold.astype(jnp.float32))

    have = param_arrays(model)
    assert {k: v.shape for k, v in have.items()} == \
        {k: v.shape for k, v in params.items()}
    return jax.jit(jax.value_and_grad(loss))(params)


def reference_loss_and_grads(cfg, params, ids, labels):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, ids, labels, cfg, IDENT)))(params)


def agree(cfg, seq=40, tol=F32, batch=2):
    params = ref.initial_params(cfg, SEED, jnp.float32)
    ids, labels = ids_of(cfg, batch, seq), ids_of(cfg, batch, seq, seed=1)
    loss, grads = program_loss_and_grads(cfg, params, ids, labels)
    want, want_grads = reference_loss_and_grads(cfg, params, ids, labels)
    assert abs(float(loss) - float(want)) <= tol * abs(float(want))
    for k in want_grads:
        close(grads[k], want_grads[k], tol, k)


KINDS = pytest.mark.parametrize(
    "layer", [(FULL, "dense"), (WINDOW, "dense"), (FULL, "sparse"),
              (WINDOW, "sparse")],
    ids=["full-dense", "window-dense", "full-moe", "window-moe"])


@KINDS
def test_each_layer_kind_alone_loss_and_gradients(layer):
    agree(config([layer]))


@KINDS
def test_each_layer_kinds_share_alone_loss_and_gradients(layer):
    agree(share([layer]))


@pytest.mark.parametrize("cut", [config, share], ids=["uncut", "share"])
def test_five_layers_loss_and_gradients(cut):
    agree(cut())


@pytest.mark.parametrize("seq", [5, 8, 9, 24],
                         ids=["inside-window", "window", "one-past", "three"])
def test_window_edge_at_every_length(seq):
    """Rows with fewer keys than the window, exactly the window, one more."""
    agree(config([(WINDOW, "dense")]), seq=seq)


def test_logits_forward():
    for cfg in (config(), share()):
        params = ref.initial_params(cfg, SEED, jnp.float32)
        ids = ids_of(cfg, 2, 40)
        model = lg.LagunaForCausalLM(builder.model_config(cfg))
        got = jax.jit(lambda p: functional_call(
            model, p, Tensor._wrap(ids)))(params)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda p: ref.forward(p, ids, cfg, IDENT))(params)
        assert got.shape == (2, 40, cfg["vocab_size"])
        close(got, want, F32, "logits")


# ------------------------------------------------------------------ rotary


def test_yarn_frequencies_and_factor_as_the_source_gives_them():
    """At the published sizes (head 128, half rotated): 32 pairs, the first
    9 keep theta^(-2i/64), those from the 18th on are divided by 128, the
    blend between; cos and sin carry the attention factor."""
    inv, factor, r = ref.inverse_frequencies(ROPE[FULL], 128)
    f = 500000.0 ** (-np.arange(32) / 32.0)
    assert r == 64 and factor == 1.4852030263919618
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[18:], f[18:] / 128, rtol=1e-12)
    ramp = (np.arange(10, 18) - 9) / 9.0
    np.testing.assert_allclose(inv[10:18], f[10:18] * (1 - ramp)
                               + f[10:18] / 128 * ramp, rtol=1e-12)
    sin, cos = lg.rotary_tables(ROPE[FULL], 128, 48)
    assert sin.shape == cos.shape == (48, 64)
    angle = np.arange(48)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos[:, :32], factor * np.cos(angle), atol=2e-6)
    np.testing.assert_allclose(sin[:, 32:], factor * np.sin(angle), atol=2e-6)
    sin_w, cos_w = lg.rotary_tables(ROPE[WINDOW], 128, 48)
    assert sin_w.shape == (48, 128)
    np.testing.assert_allclose(
        cos_w[:, :64], np.cos(np.arange(48)[:, None]
                              * 10000.0 ** (-np.arange(64) / 64.0)),
        atol=2e-6)


def test_partial_rotation_leaves_the_second_half_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 24, 128))
    for rotate in (lambda x, rule: ref.rotary(x, rule),
                   lambda x, rule: lg._rotate(
                       x, *lg.rotary_tables(rule, 128, 24))):
        full, window = rotate(x, ROPE[FULL]), rotate(x, ROPE[WINDOW])
        assert jnp.array_equal(full[..., 64:], x[..., 64:])
        assert not jnp.any(full[:, :, 1:, :64] == x[:, :, 1:, :64])
        # the sliding rule turns every pair (position 0 turns none)
        assert float(jnp.mean(window[:, :, 1:] == x[:, :, 1:])) < 0.01
    close(lg._rotate(x, *lg.rotary_tables(ROPE[FULL], 128, 24)),
          ref.rotary(x, ROPE[FULL]), 1e-6, "yarn, half of the head")
    close(lg._rotate(x, *lg.rotary_tables(ROPE[WINDOW], 128, 24)),
          ref.rotary(x, ROPE[WINDOW]), 1e-6, "plain, the whole head")


# ------------------------------------------------------------------ shares


def mixer_out(fn, cfg, params, mixer, u, *more):
    with jax.default_matmul_precision("highest"):
        _, p = ref.layer_params(params, 0, "norm_attn", mixer)
        return fn(u, p, cfg, IDENT, *more)


def program_mixer_out(layer, params, mixer, u):
    pre = f"model.layers.0.{mixer}."
    own = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    return functional_call(layer, own, Tensor._wrap(u))


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_attention_head_shares_add_up_through_w_o(kind):
    """Two shares, each one key/value head with the 6 or 9 query heads that
    read it, the matching columns of W_g and rows of W_o."""
    cfg = config([(kind, "dense")])
    d, hq = cfg["head_dim"], cfg["num_attention_heads_per_layer"][0]
    mixer = ref.ATTN[kind]
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    whole = mixer_out(ref.attention, cfg, params, mixer, u, kind)
    model_cfg = builder.model_config(cfg)
    close(program_mixer_out(lg.LagunaAttention(model_cfg, kind), params,
                            mixer, u), whole, F32, "uncut")
    pre = f"model.layers.0.{mixer}."
    part = share([(kind, "dense")])
    total = 0.0
    for i in range(2):
        heads = np.arange(i * hq // 2, (i + 1) * hq // 2)
        qc = (heads[:, None] * d + np.arange(d)).reshape(-1)
        kc = i * d + np.arange(d)
        cut = dict(params)
        cut.update({pre + "q_proj.weight": params[pre + "q_proj.weight"][:, qc],
                    pre + "k_proj.weight": params[pre + "k_proj.weight"][:, kc],
                    pre + "v_proj.weight": params[pre + "v_proj.weight"][:, kc],
                    pre + "g_proj.weight": params[pre + "g_proj.weight"][:, heads],
                    pre + "o_proj.weight": params[pre + "o_proj.weight"][qc]})
        out = program_mixer_out(
            lg.LagunaAttention(builder.model_config(part), kind), cut, mixer,
            u)
        close(out, mixer_out(ref.attention, part, cut, mixer, u, kind), F32,
              f"share {i}")
        total = total + out
    close(total, whole, F32, "sum of the head shares")


def test_dense_mlp_column_shares_add_up():
    cfg = config([(FULL, "dense")])
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    whole = mixer_out(ref.dense_ffn, cfg, params, "mlp", u)
    pre = "model.layers.0.mlp."
    part = builder.model_config(share([(FULL, "dense")]))
    total = 0.0
    for i in range(4):
        col = i * 24 + np.arange(24)
        cut = {pre + "gate_proj.weight": params[pre + "gate_proj.weight"][:, col],
               pre + "up_proj.weight": params[pre + "up_proj.weight"][:, col],
               pre + "down_proj.weight": params[pre + "down_proj.weight"][col]}
        total = total + program_mixer_out(
            lg.LagunaMLP(part, part.dense_width_held), cut, "mlp", u)
    close(total, whole, F32, "sum of the column shares")


def test_moe_shares_add_up_to_the_uncut_layer():
    """4 expert shares (4 of 16 experts each) and the shared expert's 2
    column slices, each counted once: the expert shares past the second
    hold a slice whose output is nought."""
    cfg = config([(FULL, "sparse")])
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    whole = mixer_out(ref.moe_ffn, cfg, params, "moe", u)
    close(program_mixer_out(lg.LagunaMoE(builder.model_config(cfg)), params,
                            "moe", u), whole, F32, "uncut")
    pre = "model.layers.0.moe."
    total = 0.0
    for i in range(4):
        e = np.arange(4 * i, 4 * i + 4)
        col = (i % 2) * 16 + np.arange(16)
        part = config([(FULL, "sparse")], num_experts=4, first_expert=4 * i,
                      shared_expert_columns=16)
        once = 1.0 if i < 2 else 0.0           # each slice counted once
        cut = dict(params)
        cut.update({pre + f"experts_{k}": params[pre + f"experts_{k}"][e]
                    for k in ("gate", "up", "down")})
        cut.update({
            pre + "shared.gate_proj.weight":
                params[pre + "shared.gate_proj.weight"][:, col],
            pre + "shared.up_proj.weight":
                params[pre + "shared.up_proj.weight"][:, col],
            pre + "shared.down_proj.weight":
                once * params[pre + "shared.down_proj.weight"][col]})
        out = program_mixer_out(lg.LagunaMoE(builder.model_config(part)),
                                cut, "moe", u)
        close(out, mixer_out(ref.moe_ffn, part, cut, "moe", u), F32,
              f"share {i}")
        total = total + out
    close(total, whole, F32, "sum of the shares")


@pytest.mark.parametrize("bound", [4.0, 0.5], ids=["fits", "overflows"])
def test_routing_counters_and_a_buffer_too_small(bound):
    """Under ``moe_stats_tap`` a layer reports pairs routed here, tokens with
    none, pairs left out, rows walked; a buffer under the load leaves none
    out either (further buffers take them, and their rows are walked too)
    and the layer's output is the reference's."""
    from paddle_tpu.models.moe_stats import moe_stats_tap

    cfg = share([(FULL, "sparse")])
    cfg["held"]["local_pairs_bound"] = bound
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        chosen, _ = ref.choose(u, params["model.layers.0.moe.router.weight"],
                               cfg, IDENT)
    here = (chosen >= 4) & (chosen < 8)
    layer = lg.LagunaMoE(builder.model_config(cfg))
    with moe_stats_tap() as tap:
        out = program_mixer_out(layer, params, "moe", u)
    (pairs, none, left_out, walked), = np.asarray(tap)
    assert pairs == int(jnp.sum(here))
    assert none == int(jnp.sum(~jnp.any(here, -1)))
    rows = layer.buffer_rows(48)
    assert (pairs > rows) == (bound < 1) and left_out == 0
    # these buffers are under a chunk: each one run is walked whole
    assert pairs <= walked == rows * -(-pairs // rows)
    close(out, mixer_out(ref.moe_ffn, cfg, params, "moe", u), F32, "moe")


@pytest.mark.parametrize("bound", [0.25, 0.5], ids=lambda b: f"bound{b}")
def test_a_buffer_under_the_load_changes_no_number(bound):
    """A share whose buffer holds a quarter or a half of the uniform load:
    loss and every gradient are what one large buffer gives, and equal the
    reference's (``agree``)."""
    cfg = share()
    params = ref.initial_params(cfg, SEED, jnp.float32)
    ids, labels = ids_of(cfg, 2, 40), ids_of(cfg, 2, 40, seed=1)
    loss, grads = program_loss_and_grads(cfg, params, ids, labels)
    cfg["held"]["local_pairs_bound"] = bound
    agree(cfg)
    small_loss, small = program_loss_and_grads(cfg, params, ids, labels)
    assert float(small_loss) == pytest.approx(float(loss), rel=1e-6)
    for k in grads:
        close(small[k], grads[k], 1e-5, k)


# ------------------------------------------------------------ the real step

@pytest.mark.parametrize("cut", [config, share], ids=["uncut", "share"])
def test_three_adamw_steps_bf16_through_the_benchmarks_step(cut):
    """The compiled step the cell runs (``functional_call`` +
    ``AdamW.apply_gradients_tree``, bfloat16 leaves, float32 master) against
    the reference's three steps, by the cell's own numbers."""
    from benchmarks.drivers import train_steps as drv

    cfg = dict(cut(), dtype="bfloat16")
    traffic = {"batch": 4, "seq": 40, "optimizer": ADAM}
    step, params, state = drv.build_program(cfg, traffic, SEED)
    got = {"losses": []}
    for i in (1, 2, 3):
        x, y = drv.feed(cfg, traffic, SEED, i)
        params, state, loss = step(params, state, x, y, jnp.float32(i))
        got["losses"].append(float(loss))
        if i == 1:
            got["grad_norms"] = drv._moment_norms(state, 0.9, 1)
    got["update_norms"] = drv._update_norms(params, state, SEED, 1)
    want = ref.train_readings(
        cfg, SEED, [drv.feed(cfg, traffic, SEED, i) for i in (1, 2, 3)],
        traffic["optimizer"], 1)
    read = drv.numbers(got, want)
    assert max(read[f"loss{i}_gap"] for i in (1, 2, 3)) <= BF16_LOSS
    assert read["grad_norm_gap"] <= BF16_GRAD
    assert read["update_norm_gap"] <= BF16_UPDATE


def test_reference_in_blocks_equals_the_reference_whole(monkeypatch):
    """The blocks the reference works in at the cell's size (query rows,
    positions) change no number's terms."""
    cfg = share()
    params = ref.initial_params(cfg, SEED, jnp.float32)
    ids, labels = ids_of(cfg, 2, 32), ids_of(cfg, 2, 32, seed=1)
    whole = reference_loss_and_grads(cfg, params, ids, labels)
    monkeypatch.setattr(ref, "QUERY_ROWS", 8)
    monkeypatch.setattr(ref, "POSITIONS", 16)
    loss, grads = reference_loss_and_grads(cfg, params, ids, labels)
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    for k in grads:
        close(grads[k], whole[1][k], 1e-5, k)


# -------------------------------------------------- the attention kernels


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_attention_through_the_packed_kernels_at_d128(kind, monkeypatch):
    """6 (full) or 9 (sliding) query heads reading one key/value head through
    ``causal_flash_qkv`` at D = 128, S = 1024 (interpret mode here; the band
    regime on the sliding layer), against the masked softmax path."""
    from paddle_tpu.framework import flags

    cfg = lg.LagunaConfig(hidden_size=64, layer_types=[kind],
                          mlp_layer_types=["dense"], intermediate_size=64,
                          kv_heads_held=1,
                          q_heads_held={FULL: 6, WINDOW: 9})
    layer = lg.LagunaAttention(cfg, kind)
    u = Tensor._wrap(jax.random.normal(jax.random.PRNGKey(0), (1, 1024, 64)))
    plain = layer(u)._data
    monkeypatch.setitem(flags._REGISTRY, "FLAGS_use_packed_attention", True)
    packed = layer(u)._data
    assert math.isfinite(float(jnp.sum(packed)))
    close(packed, plain, 2e-5, "kernel against softmax")


def test_config_refuses_what_does_not_fit():
    with pytest.raises(ValueError):
        lg.LagunaConfig(layer_types=["linear_attention"],
                        mlp_layer_types=["dense"])
    with pytest.raises(ValueError):
        lg.LagunaConfig(layer_types=[FULL], mlp_layer_types=["dense"] * 2)
    with pytest.raises(ValueError):   # 2 key/value heads bring 12 and 18
        lg.LagunaConfig(kv_heads_held=2, q_heads_held={FULL: 12, WINDOW: 12})
    with pytest.raises(ValueError):
        lg.LagunaConfig(experts_held=8, first_expert=250)
    with pytest.raises(ValueError):
        builder.by_kind([FULL, FULL], [12, 18])
