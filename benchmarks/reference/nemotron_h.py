"""Nemotron-H, plainly: forward, loss, gradients and AdamW in ``jax.numpy``
and float32 at ``highest`` matmul precision; no kernel, no chunked scan, no
dispatch buffer, nothing of the program. Weights come from the seed by leaf
name (``harness.weights``), in the type the configuration states, raised to
float32.

Every layer is ``x + mixer(RMSNorm(x))`` (eps ``layer_norm_epsilon``, no bias
in any projection), the mixer by the letter of ``hybrid_override_pattern``:

* ``M``, Mamba-2: ``[z, xBC, dt] = u W_in``; ``xBC = silu(causal depthwise
  conv(xBC) + b_conv)``; ``[x, B, C] = split(xBC)``; ``Delta = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence OVER POSITIONS (a
  ``lax.scan``) ``h_t = exp(Delta_t A) h_{t-1} + Delta_t x_t (x) B_t``,
  ``y_t = h_t C_t + D x_t``, head h reading group ``h // (H / G)``; ``y =
  RMSNorm_per_group(y silu(z))``; ``out = y W_out``.
* ``*``, attention: causal ``softmax(q k^T / sqrt(d))`` over the full S x S
  scores, query head h reading key/value head ``h // (Hq / Hkv)``; no rotary
  embedding (the configuration's ``assumed`` says why).
* ``E``, LatentMoE: ``s = sigmoid(u W_r)`` over ALL published experts; the
  ``num_experts_per_tok`` largest of ``s + bias`` are chosen; ``w_e =
  routed_scaling_factor s_e / sum over the chosen of s``; ``l = u W_dn``;
  ``r = sum over the chosen experts HELD HERE of w_e relu(l W1_e)^2 W2_e``
  (a loop over the held experts, each over every token under a mask);
  ``out = r W_up + relu(u Ws1)^2 Ws2``.

The configuration gives what is held here under the source's own keys
(``mamba_num_heads``, ``n_groups``, ``num_attention_heads``,
``num_key_value_heads``, ``n_routed_experts``, ``vocab_size``) and the
published counts under ``published``; ``held`` gives the first expert's id
and the columns of the shared expert. What absent chips would add is left
out here as in the program. Departures from the published model: those the
configuration lists under ``left_out`` and ``assumed``.
"""
import time

import numpy as np

from ..harness import weights
from ..harness.norms import block_norms
from .gpt2 import adamw
from .quant import operand_rounding

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
SEGMENT = 64  # positions between the states the recurrence's backward keeps


def sizes(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"h": h, "p": p, "g": g, "n": n, "inner": h * p,
            "conv": h * p + 2 * g * n}


def leaf_specs(cfg, dtype):
    hid, z = cfg["hidden_size"], sizes(cfg)
    d, lat = cfg["head_dim"], cfg["moe_latent_size"]
    ff, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    shared = cfg["held"]["shared_expert_columns"]
    specs = [("backbone.embeddings.weight", (cfg["vocab_size"], hid))]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"backbone.layers.{i}."
        specs.append((p + "norm.weight", (hid,)))
        p += KINDS[kind] + "."
        if kind == "M":
            specs += [
                (p + "in_proj.weight", (hid, z["inner"] + z["conv"] + z["h"])),
                (p + "conv1d_weight", (z["conv"], cfg["conv_kernel"])),
                (p + "conv1d_bias", (z["conv"],)),
                (p + "dt_bias", (z["h"],)), (p + "A_log", (z["h"],)),
                (p + "D", (z["h"],)), (p + "norm_weight", (z["inner"],)),
                (p + "out_proj.weight", (z["inner"], hid))]
        elif kind == "*":
            hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
            specs += [(p + "q_proj.weight", (hid, hq * d)),
                      (p + "k_proj.weight", (hid, hk * d)),
                      (p + "v_proj.weight", (hid, hk * d)),
                      (p + "o_proj.weight", (hq * d, hid))]
        else:
            specs += [
                (p + "router.weight",
                 (hid, cfg["published"]["n_routed_experts"])),
                (p + "latent_down.weight", (hid, lat)),
                (p + "latent_up.weight", (lat, hid)),
                (p + "experts_w1", (held, lat, ff)),
                (p + "experts_w2", (held, ff, lat)),
                (p + "shared_up.weight", (hid, shared)),
                (p + "shared_down.weight", (shared, hid))]
    specs += [("backbone.norm_f.weight", (hid,)),
              ("lm_head.weight", (hid, cfg["vocab_size"]))]
    return [(n, s, dtype) for n, s in specs]


def initial_params(cfg, seed, dtype):
    """{name: float32 array} holding the values of the stated type."""
    import jax.numpy as jnp

    specs = leaf_specs(cfg, dtype)
    leaves = weights.make_leaves(seed, specs)
    return {n: a.astype(jnp.float32) for (n, _, _), a in zip(specs, leaves)}


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def recurrence(x, dt, a, bm, cm):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t``,
    one position at a time. x ``[b, s, h, p]``, dt ``[b, s, h]``, a ``[h]``,
    B and C ``[b, s, h, n]`` (each head's own group already picked). The
    backward keeps the state every ``SEGMENT`` positions and works the rest
    out again, so that a long row's states fit; the arithmetic is the plain
    recurrence's."""
    import jax
    import jax.numpy as jnp

    b, s, h, p = x.shape

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    def segment(state, inp):
        return jax.lax.scan(step, state, inp)

    seg = SEGMENT if s % SEGMENT == 0 else s
    by_time = [t.swapaxes(0, 1).reshape(s // seg, seg, *t.shape[:1],
                                        *t.shape[2:])
               for t in (x, dt, bm, cm)]
    _, y = jax.lax.scan(jax.checkpoint(segment),
                        jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32),
                        by_time)
    return y.reshape(s, b, h, p).swapaxes(0, 1)


def own_group(t, heads):
    """B or C ``[b, s, g, n]`` as each of ``heads`` heads reads it: head h
    reads group ``h // (heads / g)``."""
    import jax.numpy as jnp

    return jnp.repeat(t, heads // t.shape[2], axis=2)


def mamba(u, p, cfg, rnd):
    import jax
    import jax.numpy as jnp

    z_ = sizes(cfg)
    h, g, hd, n = z_["h"], z_["g"], z_["p"], z_["n"]
    b, s, _ = u.shape
    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    z, xbc, dt = jnp.split(mm(u, p["in_proj.weight"]),
                           [z_["inner"], z_["inner"] + z_["conv"]], -1)
    k = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + s] * p["conv1d_weight"][:, i]
                          for i in range(k)) + p["conv1d_bias"])
    x, bm, cm = jnp.split(xbc, [z_["inner"], z_["inner"] + g * n], -1)
    x = x.reshape(b, s, h, hd)
    own = lambda t: own_group(t.reshape(b, s, g, n), h)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(rnd(x), dt, -jnp.exp(p["A_log"]), rnd(own(bm)),
                   rnd(own(cm)))
    y = (y + p["D"][:, None] * x).reshape(b, s, g, -1)
    y = y * jax.nn.silu(z).reshape(y.shape)          # gate, then the norm
    y = _rms(y, p["norm_weight"].reshape(g, -1), cfg["layer_norm_epsilon"])
    return mm(y.reshape(b, s, -1), p["out_proj.weight"])


def attention(u, p, cfg, rnd):
    import jax
    import jax.numpy as jnp

    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    b, s, _ = u.shape
    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    heads = lambda w, n: mm(u, w).reshape(b, s, n, d).transpose(0, 2, 1, 3)
    q = heads(p["q_proj.weight"], hq)
    k, v = (jnp.repeat(heads(p[f"{n}_proj.weight"], hk), hq // hk, axis=1)
            for n in "kv")
    scores = jnp.einsum("bhqd,bhkd->bhqk", rnd(q), rnd(k)) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", rnd(probs), rnd(v))
    return mm(att.transpose(0, 2, 1, 3).reshape(b, s, hq * d),
              p["o_proj.weight"])


def choose(u, w_r, bias, cfg, rnd):
    """(chosen expert ids [.., k], their weights [.., k]): the k largest of
    ``s + bias``, weighed ``scale s_e / sum over the chosen of s``."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.matmul(rnd(u), rnd(w_r)))
    _, chosen = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, (cfg["routed_scaling_factor"] * s_chosen
                    / jnp.sum(s_chosen, -1, keepdims=True))


def latent_moe(u, p, cfg, rnd):
    import jax
    import jax.numpy as jnp

    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    chosen, w = choose(u, p["router.weight"], 0.0, cfg, rnd)
    latent = mm(u, p["latent_down.weight"])
    routed = jnp.zeros_like(latent)
    for e in range(cfg["n_routed_experts"]):       # the experts held here
        w_e = jnp.sum(jnp.where(chosen == cfg["held"]["first_expert"] + e,
                                w, 0.0), axis=-1, keepdims=True)
        routed += w_e * mm(relu2(mm(latent, p["experts_w1"][e])),
                           p["experts_w2"][e])
    return mm(routed, p["latent_up.weight"]) + mm(
        relu2(mm(u, p["shared_up.weight"])), p["shared_down.weight"])


MIXERS = {"M": mamba, "*": attention, "E": latent_moe}


def layer_params(params, i, kind):
    pre = f"backbone.layers.{i}."
    mix = pre + KINDS[kind] + "."
    return (params[pre + "norm.weight"],
            {k[len(mix):]: v for k, v in params.items() if k.startswith(mix)})


def forward(params, ids, cfg, rnd):
    """Logits [b, s, rows held] of rows ``ids`` [b, s]; every layer is
    worked out again in the backward, so that one row's activations fit."""
    import jax
    import jax.numpy as jnp

    eps = cfg["layer_norm_epsilon"]
    x = params["backbone.embeddings.weight"][ids]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        def layer(x, norm_w, p, mixer=MIXERS[kind]):
            return x + mixer(_rms(x, norm_w, eps), p, cfg, rnd)

        x = jax.checkpoint(layer)(x, *layer_params(params, i, kind))
    x = _rms(x, params["backbone.norm_f.weight"], eps)
    return jnp.matmul(rnd(x), rnd(params["lm_head.weight"]))


def loss_fn(params, ids, labels, cfg, rnd):
    """Mean next-token cross-entropy of rows ``ids`` [b, s]."""
    import jax
    import jax.numpy as jnp

    logits = forward(params, ids, cfg, rnd)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def train_readings(cfg, seed, batches, adam, blocks, precision="float32",
                   rows=1, fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights, as
    ``reference/gpt2.py`` does: gradients accumulated over blocks of
    ``rows`` rows, the same two faults (``"half_batch"``,
    ``"state_unchanged"``), the same readings by leaf block.

    The configuration states its parameters' TYPE (``dtype``) beside
    AdamW's float32 master, and that is part of the recipe, not of the
    arithmetic: every step's loss and gradient are taken at the master
    rounded to that type (what the model's leaves hold), the update is
    the master's. The seed's leaves lie on the type's grid and AdamW's
    first step moves every element by ``lr``, so at bfloat16 and ``lr`` =
    3e-4 four of ten elements (those above 2**-6) move by 2 or 1 whole
    spacings, 0.81 of the step: a reference that read its master
    unrounded took a longer first step than any bfloat16 model can and
    read a loss 3e-4 off at steps 2 and 3, on every seed (PERF.md
    section 6, PR 28). Everything else is float32."""
    import jax
    import jax.numpy as jnp

    rnd = operand_rounding(precision)
    dtype = jnp.dtype(cfg["dtype"])
    with jax.default_matmul_precision("highest"):
        params = initial_params(cfg, seed, dtype)
        # the leaves are made in the stated type by a call of their own: a
        # round trip inside one compiled function is the compiler's to drop
        leaves = jax.jit(lambda master: {k: v.astype(dtype)
                                         for k, v in master.items()})
        vg = jax.jit(lambda p, i, l: jax.value_and_grad(loss_fn)(
            {k: v.astype(jnp.float32) for k, v in p.items()}, i, l, cfg, rnd))
        acc = jax.jit(lambda a, g: jax.tree_util.tree_map(jnp.add, a, g),
                      donate_argnums=(0, 1))
        scale = jax.jit(lambda a, n: jax.tree_util.tree_map(
            lambda x: x / n, a), donate_argnums=(0,))
        update = jax.jit(lambda p, g, s, t: adamw(p, g, s, t, adam),
                         donate_argnums=(0, 1, 2))
        norms = jax.jit(lambda a: block_norms(a, blocks))
        delta = jax.jit(lambda a, b: block_norms(
            {k: a[k] - b[k] for k in a}, blocks))
        state = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
                 for k, v in params.items()}
        losses, grad_norms = [], None
        clock = time.perf_counter()
        for t, (ids, labels) in enumerate(batches, start=1):
            if fault == "half_batch":
                ids, labels = ids[: len(ids) // 2], labels[: len(ids) // 2]
            n_blocks = len(ids) // rows
            total, grads, at = 0.0, None, leaves(params)
            for j in range(n_blocks):
                sl = slice(j * rows, (j + 1) * rows)
                loss, g = vg(at, jnp.asarray(ids[sl]),
                             jnp.asarray(labels[sl]))
                total += float(loss)
                grads = g if grads is None else acc(grads, g)
            grads = scale(grads, jnp.float32(n_blocks))
            losses.append(total / n_blocks)
            if t == 1:
                grad_norms = {k: float(v) for k, v in
                              jax.device_get(norms(grads)).items()}
            if fault != "state_unchanged":
                params, state = update(params, grads, state, jnp.float32(t))
            del grads, at
            print(f"reference: step {t} followed after "
                  f"{time.perf_counter() - clock:.1f} s", flush=True)
        if fault == "state_unchanged":
            grad_norms = dict.fromkeys(grad_norms, 0.0)
        del state
        update_norms = {k: float(v) for k, v in jax.device_get(delta(
            params, initial_params(cfg, seed, dtype))).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
