"""Nemotron-H's shares, long memory, planted faults and routing counters:
helpers and tolerances are ``test_nemotron_h.py``'s (a file of its own so
that another worker takes it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_nemotron_h import (F32, IDENT, SEED, SEQS, agree, builder, close,
                             config, ids_of, ref, share)

from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import functional_call
from paddle_tpu.models import nemotron_h as nh


# ------------------------------------------------------------------ shares


def mixer_out(kind, cfg, params, u):
    """The reference's mixer ``kind`` of layer 0 on input ``u``."""
    with jax.default_matmul_precision("highest"):
        _, p = ref.layer_params(params, 0, kind)
        return ref.MIXERS[kind](u, p, cfg, IDENT)


def program_mixer_out(kind, cfg, params, u):
    key, mixer = nh.NemotronHBlock.KINDS[kind]
    pre = f"backbone.layers.0.{key}."
    own = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    return functional_call(mixer(builder.model_config(cfg)), own,
                           Tensor._wrap(u))


def test_mamba_head_shares_add_up_to_the_uncut_layer():
    cfg = config("M")
    z = ref.sizes(cfg)
    h, g, p, n = z["h"], z["g"], z["p"], z["n"]
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64))
    whole = mixer_out("M", cfg, params, u)
    pre = "backbone.layers.0.mamba."
    total = 0.0
    for i in range(g):               # share i: heads of group i, group i
        heads = np.arange(i * h // g, (i + 1) * h // g)
        chan = (heads[:, None] * p + np.arange(p)).reshape(-1)
        grp = i * n + np.arange(n)
        inner, gn = z["inner"], g * n
        cols = np.concatenate([chan, inner + chan, 2 * inner + grp,
                               2 * inner + gn + grp,
                               2 * inner + 2 * gn + heads])
        conv = np.concatenate([chan, inner + grp, inner + gn + grp])
        part = config("M", mamba_num_heads=h // g, n_groups=1)
        cut = dict(params)
        cut.update({
            pre + "in_proj.weight": params[pre + "in_proj.weight"][:, cols],
            pre + "conv1d_weight": params[pre + "conv1d_weight"][conv],
            pre + "conv1d_bias": params[pre + "conv1d_bias"][conv],
            pre + "norm_weight": params[pre + "norm_weight"][chan],
            pre + "out_proj.weight": params[pre + "out_proj.weight"][chan]})
        for leaf in ("dt_bias", "A_log", "D"):
            cut[pre + leaf] = params[pre + leaf][heads]
        out = program_mixer_out("M", part, cut, u)
        close(out, mixer_out("M", part, cut, u), F32, f"share {i}")
        total = total + out
    close(total, whole, F32, "sum of the head shares")


def test_attention_head_shares_add_up_to_the_uncut_layer():
    cfg = config("*")
    hq, hk, d = 4, 2, cfg["head_dim"]
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 64))
    whole = mixer_out("*", cfg, params, u)
    close(program_mixer_out("*", cfg, params, u), whole, F32, "uncut")
    pre = "backbone.layers.0.attn."
    total = 0.0
    for i in range(hq):    # share i: query head i and the kv head it reads
        qc = i * d + np.arange(d)
        kc = (i // (hq // hk)) * d + np.arange(d)
        part = config("*", num_attention_heads=1, num_key_value_heads=1)
        cut = dict(params)
        cut.update({pre + "q_proj.weight": params[pre + "q_proj.weight"][:, qc],
                    pre + "k_proj.weight": params[pre + "k_proj.weight"][:, kc],
                    pre + "v_proj.weight": params[pre + "v_proj.weight"][:, kc],
                    pre + "o_proj.weight": params[pre + "o_proj.weight"][qc]})
        total = total + program_mixer_out("*", part, cut, u)
    close(total, whole, F32, "sum of the head shares")


def test_moe_shares_add_up_to_the_uncut_layer():
    """8 expert shares (2 of 16 experts each), each through the whole
    up-projection, and the shared expert's 4 slices, each counted once: the
    expert shares past the fourth hold a slice whose output is nought."""
    cfg = config("E")
    params = ref.initial_params(cfg, SEED, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64))
    whole = mixer_out("E", cfg, params, u)
    pre = "backbone.layers.0.moe."
    width, slices, shares = 96, 4, 8
    total = 0.0
    for i in range(shares):
        e = np.arange(2 * i, 2 * i + 2)
        j = i % slices
        col = j * (width // slices) + np.arange(width // slices)
        part = config("E", n_routed_experts=2, first_expert=2 * i,
                      shared_expert_columns=width // slices)
        cut = dict(params)
        once = 1.0 if i < slices else 0.0      # each slice counted once
        cut.update({
            pre + "experts_w1": params[pre + "experts_w1"][e],
            pre + "experts_w2": params[pre + "experts_w2"][e],
            pre + "shared_up.weight": params[pre + "shared_up.weight"][:, col],
            pre + "shared_down.weight":
                once * params[pre + "shared_down.weight"][col]})
        out = program_mixer_out("E", part, cut, u)
        close(out, mixer_out("E", part, cut, u), F32, f"share {i}")
        total = total + out
    close(total, whole, F32, "sum of the shares")


# ------------------------------------------- long memory, and planted faults


def long_memory(cfg, params, seed=11):
    """``A_log`` and ``dt_bias`` drawn in the published ranges (the HF
    initialisation: A uniform in [1, 16]; dt log-uniform in [time_step_min,
    time_step_max] = [0.001, 0.1], floor 1e-4, ``dt_bias`` its inverse
    softplus): a decay of 0.85 to 0.999 a position, a memory of hundreds."""
    rng = np.random.default_rng(seed)
    out = dict(params)
    for k in params:
        if k.endswith("A_log"):
            out[k] = jnp.asarray(np.log(rng.uniform(1, 16, params[k].shape)),
                                 jnp.float32)
        if k.endswith("dt_bias"):
            dt = np.maximum(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                               params[k].shape)), 1e-4)
            out[k] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    # B, C and x at the size of a trained model's, so that the carried state
    # weighs in the output as it does there
    for k in params:
        if k.endswith("conv1d_weight"):
            out[k] = params[k] * 25.0
    return out


@SEQS
def test_long_memory_agrees(seq):
    cfg = share("MEM*E")
    agree(cfg, long_memory(cfg, ref.initial_params(cfg, SEED, jnp.float32)),
          seq)


def carry_dropped(x, dt, a, bm, cm, chunk=16, true=ref.recurrence):
    """The recurrence with the state set to nought at every chunk's start."""
    s = x.shape[1]
    return jnp.concatenate(
        [true(*(t[:, i:i + chunk] for t in (x, dt)), a,
              *(t[:, i:i + chunk] for t in (bm, cm)))
         for i in range(0, s, chunk)], axis=1)


def wrong_group(t, heads, true=ref.own_group):
    return true(jnp.roll(t, 1, axis=2), heads)


def held_only(u, w_r, bias, cfg, rnd, true=ref.choose):
    """The chosen experts' weights normalised over those held here."""
    chosen, w = true(u, w_r, bias, cfg, rnd)
    first = cfg["held"]["first_expert"]
    kept = jnp.where((chosen >= first)
                     & (chosen < first + cfg["n_routed_experts"]), w, 0.0)
    return chosen, cfg["routed_scaling_factor"] * kept / jnp.maximum(
        jnp.sum(kept, -1, keepdims=True), 1e-30)


@pytest.mark.parametrize("fault", ["carry_dropped", "wrong_group",
                                   "normalised_over_the_held"])
def test_planted_faults_fail_the_same_tolerance(fault, monkeypatch):
    plant = {"carry_dropped": ("M", "recurrence", carry_dropped),
             "wrong_group": ("M", "own_group", wrong_group),
             "normalised_over_the_held": ("E", "choose", held_only)}
    kind, *plant = plant[fault]
    cfg = config(kind, n_routed_experts=4, first_expert=4, mamba_num_heads=4,
                 n_groups=2)
    params = long_memory(cfg, ref.initial_params(cfg, SEED, jnp.float32))
    agree(cfg, params, 48)                      # sound: passes
    monkeypatch.setattr(ref, *plant)
    with pytest.raises(AssertionError, match="gap"):
        agree(cfg, params, 48)


# ---------------------------------------------------------------- counters


def routing_counts(cfg, seq=40):
    params = ref.initial_params(cfg, SEED, jnp.float32)
    model = nh.NemotronHForCausalLM(builder.model_config(cfg))
    ids = ids_of(cfg, 2, seq)

    def run(p):
        with nh.moe_stats_tap() as tap:
            logits = functional_call(model, p, Tensor._wrap(ids))
        return logits, jnp.stack(tap)

    logits, stats = jax.jit(run)(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(p, ids, cfg, IDENT))(params)
    return np.asarray(stats), logits, want


def test_moe_counters_no_pair_over_the_buffer():
    cfg = share("EME")
    stats, logits, want = routing_counts(cfg)
    assert stats.shape == (2, 4)                 # one row an E layer
    tokens, k = 80, cfg["num_experts_per_tok"]
    assert np.all(stats[:, 2] == 0)              # nothing over the buffer
    rows = nh.LatentMoE(builder.model_config(cfg)).buffer_rows(tokens)
    assert np.all(stats[:, 0] <= stats[:, 3]) and np.all(stats[:, 3] <= rows)
    assert np.all(stats[:, 0] + stats[:, 1] >= tokens)
    assert np.all(stats[:, 0] <= tokens * min(k, 4))
    assert 0.5 < stats[:, 0].mean() / (tokens * k * 4 / 16) < 2.0
    close(logits, want, F32, "logits")


def test_a_pair_over_the_buffer_is_counted_and_caught():
    """A buffer sized under the load does not drop in silence: the counter
    says how many pairs went over, and the reference (dropless) disagrees."""
    cfg = share()
    cfg["held"]["local_pairs_bound"] = 0.25
    stats, logits, want = routing_counts(cfg)
    assert np.all(stats[:, 2] > 0)
    # the one buffer is full, and walked whole
    assert np.all(stats[:, 3] == stats[:, 0] - stats[:, 2])
    with pytest.raises(AssertionError, match="gap"):
        close(logits, want, F32, "logits")


def test_buffer_rows_follow_the_stated_bound():
    moe = nh.LatentMoE(nh.NemotronHConfig(
        hidden_size=64, n_routed_experts=512, num_experts_per_tok=22,
        experts_held=8, moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, local_pairs_bound=2.0))
    # 16384 tokens x 22 x 8 / 512 = 5632 pairs expected, twice that held
    assert moe.buffer_rows(16384) == 11264
    assert moe.buffer_rows(8) == 8               # a multiple of 8 rows
    moe.bound = 1e9                              # never over one a token
    assert moe.buffer_rows(100) == 800           # and held expert
