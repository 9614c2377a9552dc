"""Laguna, plainly: forward, loss, gradients and AdamW in ``jax.numpy`` and
float32 at ``highest`` matmul precision; no kernel, no dispatch buffer,
nothing of the program. Weights come from the seed by leaf name
(``harness.weights``), in the type the configuration states, raised to
float32.

Block l, pre-norm (RMSNorm eps ``rms_norm_eps``, no bias anywhere, untied
head): ``x <- x + Attn_l(RMSNorm(x))``, ``x <- x + FFN_l(RMSNorm(x))``.

* Attention, ``H = num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` of ``head_dim``, query head h reading key/value
  head ``h // (H / Hkv)``: rotary on q and k by the layer's kind
  (``rope_parameters[layer_types[l]]``: ``default`` rotates
  ``partial_rotary_factor`` of each head at ``theta^(-2i/r)``; ``yarn``
  blends that frequency with itself over ``factor`` between the pairs that
  turn ``beta_fast`` and ``beta_slow`` times in the original length and
  multiplies cos and sin by ``attention_factor``; pairs ``(i, i + r/2)``);
  scores ``q.k / sqrt(head_dim)`` under an explicit mask over (query i, key
  j): ``j <= i``, and on a ``sliding_attention`` layer ``i - j <
  sliding_window`` too; ``out = concat_h(sigmoid(u W_g)_h softmax(scores_h)
  v) W_o``.
* FFN ``dense``: ``(silu(u W_gate) * (u W_up)) W_down``. ``sparse``: ``p =
  softmax(u W_r)`` over ALL published experts; the ``num_experts_per_tok``
  largest are chosen (``top_k``: equal entries by lowest index); ``w_e =
  moe_routed_scaling_factor p_e / sum over the chosen of p``; ``out = sum
  over the chosen experts HELD HERE of w_e expert_e(u)`` (a loop over the
  held experts, each over every token under a mask) ``+ shared(u)``, both
  the same SwiGLU.

The configuration gives what is held here under the source's own keys
(``num_attention_heads_per_layer``, ``num_key_value_heads``,
``num_experts``, ``vocab_size``) and the published counts under
``published``; ``held`` gives the first expert's id and the columns held of
the dense layer's and the shared expert's widths. What absent chips would
add is left out here as in the program. Departures from the source: what
the configuration lists under ``assumed`` (softmax router scores, the
shared expert ungated, the per-head gate a sigmoid of a linear map of the
layer's normed input, the window's edge ``i - j < window``, no QK norm);
each is one function below.

So that the cell's size fits the chip: attention is computed for a block of
query rows at a time against every key (the mask is the block's rows of the
``[S, S]`` mask), the position-wise parts (dense FFN, the head with its loss)
for a block of positions at a time, and every layer is worked out again in
the backward. None of that changes a sum's terms.
"""
import math
import time

import numpy as np

from ..harness import weights
from ..harness.norms import block_norms
from .gpt2 import adamw
from .quant import operand_rounding

ATTN = {"full_attention": "attn_full", "sliding_attention": "attn_window"}
FFN = {"dense": "mlp", "sparse": "moe"}
QUERY_ROWS = 512    # query rows of one block of the attention
POSITIONS = 1024    # positions of one block of the dense FFN and the head


def leaf_specs(cfg, dtype):
    hid, d = cfg["hidden_size"], cfg["head_dim"]
    hk, held = cfg["num_key_value_heads"], cfg["num_experts"]
    ff = cfg["moe_intermediate_size"]
    swiglu = lambda p, width: [(p + "gate_proj.weight", (hid, width)),
                               (p + "up_proj.weight", (hid, width)),
                               (p + "down_proj.weight", (width, hid))]
    specs = [("model.embeddings.weight", (cfg["vocab_size"], hid))]
    for i, (kind, ffn) in enumerate(zip(cfg["layer_types"],
                                        cfg["mlp_layer_types"])):
        p = f"model.layers.{i}."
        hq = cfg["num_attention_heads_per_layer"][i]
        a = p + ATTN[kind] + "."
        specs += [(p + "norm_attn.weight", (hid,)),
                  (a + "q_proj.weight", (hid, hq * d)),
                  (a + "k_proj.weight", (hid, hk * d)),
                  (a + "v_proj.weight", (hid, hk * d)),
                  (a + "g_proj.weight", (hid, hq)),
                  (a + "o_proj.weight", (hq * d, hid)),
                  (p + "norm_ffn.weight", (hid,))]
        f = p + FFN[ffn] + "."
        if ffn == "dense":
            specs += swiglu(f, cfg["held"]["dense_mlp_columns"])
        else:
            specs += [(f + "router.weight",
                       (hid, cfg["published"]["num_experts"])),
                      (f + "experts_gate", (held, hid, ff)),
                      (f + "experts_up", (held, hid, ff)),
                      (f + "experts_down", (held, ff, hid))]
            specs += swiglu(f + "shared.",
                            cfg["held"]["shared_expert_columns"])
    specs += [("model.norm_f.weight", (hid,)),
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


def in_blocks(fn, size, axis, *arrays):
    """``fn(*blocks)`` over blocks of ``size`` along ``axis`` of every
    array, one after the other, each worked out again in the backward;
    ``fn`` returns a block along the same axis. One block where ``size``
    does not divide the length."""
    import jax
    import jax.numpy as jnp

    n = arrays[0].shape[axis]
    if n <= size or n % size:
        return fn(*arrays)
    split = lambda x: jnp.moveaxis(
        x.reshape(*x.shape[:axis], n // size, size, *x.shape[axis + 1:]),
        axis, 0)
    out = jax.lax.map(lambda parts: jax.checkpoint(fn)(*parts),
                      tuple(split(x) for x in arrays))
    out = jnp.moveaxis(out, 0, axis)
    return out.reshape(*out.shape[:axis], n, *out.shape[axis + 2:])


# ------------------------------------------------------------------ rotary


def inverse_frequencies(rule, head_dim):
    """(inv_freq [r / 2], factor on cos and sin, r) of one rotary rule."""
    r = int(round(rule["partial_rotary_factor"] * head_dim))
    theta = float(rule["rope_theta"])
    f = np.array([theta ** (-2.0 * i / r) for i in range(r // 2)])
    if rule["rope_type"] == "default":
        return f, 1.0, r
    assert rule["rope_type"] == "yarn", rule["rope_type"]
    length = rule["original_max_position_embeddings"]

    def pair_turning(turns):  # the pair that turns ``turns`` times in length
        return (r * math.log(length / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = min(max(math.floor(pair_turning(rule["beta_fast"])), 0), r - 1)
    high = min(max(math.ceil(pair_turning(rule["beta_slow"])), 0), r - 1)
    ramp = np.array([min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
                     for i in range(r // 2)])
    return (f * (1 - ramp) + f / rule["factor"] * ramp,
            float(rule["attention_factor"]), r)


def rotary(x, rule):
    """Heads ``[b, h, s, d]`` turned by their positions 0 ... s - 1: of the
    first r dimensions, pair ``(i, i + r/2)`` by the angle ``position *
    inv_freq_i``, cos and sin times the rule's factor; the rest untouched."""
    import jax.numpy as jnp

    inv, factor, r = inverse_frequencies(rule, x.shape[-1])
    angle = np.arange(x.shape[2])[:, None] * inv[None, :]          # [s, r/2]
    cos = jnp.asarray(np.cos(angle) * factor, jnp.float32)
    sin = jnp.asarray(np.sin(angle) * factor, jnp.float32)
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


# --------------------------------------------------------------- the layers


def seen(kind, window, rows, seq):
    """The rows ``rows`` of the ``[S, S]`` mask: query i sees key j."""
    import jax.numpy as jnp

    ahead = rows[:, None] - jnp.arange(seq)[None, :]               # i - j
    mask = ahead >= 0
    if kind == "sliding_attention":  # assumed (d): the edge is i - j < window
        mask = mask & (ahead < window)
    return mask


def head_gate(u, w_g, rnd):
    """assumed (c): ``sigmoid`` of a linear map of the layer's normed
    input, one number a head and position. ``[b, s, h]``."""
    import jax
    import jax.numpy as jnp

    return jax.nn.sigmoid(jnp.matmul(rnd(u), rnd(w_g)))


def attention(u, p, cfg, rnd, kind):
    import jax
    import jax.numpy as jnp

    d, hk = cfg["head_dim"], cfg["num_key_value_heads"]
    b, s, _ = u.shape
    hq = p["q_proj.weight"].shape[1] // d
    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    heads = lambda w, n: mm(u, w).reshape(b, s, n, d).transpose(0, 2, 1, 3)
    rule = cfg["rope_parameters"][kind]
    q = rotary(heads(p["q_proj.weight"], hq), rule)
    k = rotary(heads(p["k_proj.weight"], hk), rule)    # no QK norm: assumed
    k, v = (jnp.repeat(t, hq // hk, axis=1)
            for t in (k, heads(p["v_proj.weight"], hk)))

    def block(qb, rows):  # [b, h, rows, d] queries at positions rows
        scores = jnp.einsum("bhqd,bhkd->bhqk", rnd(qb), rnd(k)) / np.sqrt(d)
        mask = seen(kind, cfg["sliding_window"], rows.reshape(-1), s)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", rnd(probs), rnd(v))

    att = in_blocks(block, QUERY_ROWS, 2, q,
                    jnp.arange(s).reshape(1, 1, s, 1))
    att = att * head_gate(u, p["g_proj.weight"], rnd).transpose(
        0, 2, 1)[..., None]
    return mm(att.transpose(0, 2, 1, 3).reshape(b, s, hq * d),
              p["o_proj.weight"])


def swiglu(u, gate, up, down, rnd):
    import jax
    import jax.numpy as jnp

    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def swiglu_leaves(p, pre=""):
    return [p[f"{pre}{k}_proj.weight"] for k in ("gate", "up", "down")]


def dense_ffn(u, p, cfg, rnd):
    return in_blocks(lambda part: swiglu(part, *swiglu_leaves(p), rnd),
                     POSITIONS, 1, u)


def router_scores(u, w_r, rnd):
    """assumed (a): the router's scores are a softmax over all experts."""
    import jax
    import jax.numpy as jnp

    return jax.nn.softmax(jnp.matmul(rnd(u), rnd(w_r)), axis=-1)


def choose(u, w_r, cfg, rnd):
    """(chosen expert ids [.., k], their weights [.., k])."""
    import jax
    import jax.numpy as jnp

    p = router_scores(u, w_r, rnd)
    p_chosen, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return chosen, (cfg["moe_routed_scaling_factor"] * p_chosen
                    / jnp.sum(p_chosen, -1, keepdims=True))


def moe_ffn(u, p, cfg, rnd):
    import jax.numpy as jnp

    chosen, w = choose(u, p["router.weight"], cfg, rnd)
    routed = jnp.zeros_like(u)
    for e in range(cfg["num_experts"]):            # the experts held here
        w_e = jnp.sum(jnp.where(chosen == cfg["held"]["first_expert"] + e,
                                w, 0.0), axis=-1, keepdims=True)
        routed += w_e * swiglu(u, p["experts_gate"][e], p["experts_up"][e],
                               p["experts_down"][e], rnd)
    # assumed (b): the shared expert is added without a gate of its own
    return routed + swiglu(u, *swiglu_leaves(p, "shared."), rnd)


FFNS = {"dense": dense_ffn, "sparse": moe_ffn}


def layer_params(params, i, norm, mixer):
    """(the norm's scale, the leaves of the mixer under key ``mixer``) of
    layer i."""
    pre = f"model.layers.{i}."
    mix = pre + mixer + "."
    return (params[pre + norm + ".weight"],
            {k[len(mix):]: v for k, v in params.items() if k.startswith(mix)})


def hidden_states(params, ids, cfg, rnd):
    """The final norm's output [b, s, hidden] of rows ``ids`` [b, s]."""
    import jax

    eps = cfg["rms_norm_eps"]
    x = params["model.embeddings.weight"][ids]
    for i, (kind, ffn) in enumerate(zip(cfg["layer_types"],
                                        cfg["mlp_layer_types"])):
        def attn(x, norm_w, p, kind=kind):
            return x + attention(_rms(x, norm_w, eps), p, cfg, rnd, kind)

        def feed(x, norm_w, p, ffn=ffn):
            return x + FFNS[ffn](_rms(x, norm_w, eps), p, cfg, rnd)

        x = jax.checkpoint(attn)(
            x, *layer_params(params, i, "norm_attn", ATTN[kind]))
        x = jax.checkpoint(feed)(
            x, *layer_params(params, i, "norm_ffn", FFN[ffn]))
    return _rms(x, params["model.norm_f.weight"], eps)


def forward(params, ids, cfg, rnd):
    """Logits [b, s, rows held]."""
    import jax.numpy as jnp

    return jnp.matmul(rnd(hidden_states(params, ids, cfg, rnd)),
                      rnd(params["lm_head.weight"]))


def loss_fn(params, ids, labels, cfg, rnd):
    """Mean next-token cross-entropy of rows ``ids`` [b, s], the logits
    formed a block of positions at a time."""
    import jax
    import jax.numpy as jnp

    x = hidden_states(params, ids, cfg, rnd)
    head = rnd(params["lm_head.weight"])

    def block(xb, gold_ids):
        logits = jnp.matmul(rnd(xb), head)
        gold = jnp.take_along_axis(logits, gold_ids, axis=-1)
        return jax.nn.logsumexp(logits, axis=-1, keepdims=True) - gold

    nll = in_blocks(block, POSITIONS, 1, x, labels[..., None])
    return jnp.mean(nll)


def train_readings(cfg, seed, batches, adam, blocks, precision="float32",
                   rows=1, fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights, as
    ``reference/nemotron_h.py`` does and by its rule: every step's loss and
    gradient are taken at the float32 master ROUNDED to the configuration's
    ``dtype`` (what the model's leaves hold), the update is the master's;
    gradients accumulated over blocks of ``rows`` rows (into one tree, so
    that two never stand on the chip together) with AdamW's moments on the
    host meanwhile; the same two faults
    (``"half_batch"``, ``"state_unchanged"``), the same readings by leaf
    block."""
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

        def add_grad(acc, p, i, l):
            loss, g = jax.value_and_grad(loss_fn)(
                {k: v.astype(jnp.float32) for k, v in p.items()}, i, l, cfg,
                rnd)
            return loss, jax.tree_util.tree_map(jnp.add, acc, g)

        vg = jax.jit(add_grad, donate_argnums=(0,))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        scale = jax.jit(lambda a, n: jax.tree_util.tree_map(
            lambda x: x / n, a), donate_argnums=(0,))
        update = jax.jit(lambda p, g, s, t: adamw(p, g, s, t, adam),
                         donate_argnums=(0, 1, 2))
        norms = jax.jit(lambda a: block_norms(a, blocks))
        delta = jax.jit(lambda a, b: block_norms(
            {k: a[k] - b[k] for k in a}, blocks))
        # AdamW's two moments wait on the host between updates: at the
        # cell's size they and the gradient's program do not fit the chip
        # together (compiled for a described v5e: 8.6 GiB, 9.7 in fp8, beside
        # 2.1 of master and 4.2 of moments)
        first_state = jax.jit(lambda p: {
            k: (jnp.zeros_like(v), jnp.zeros_like(v)) for k, v in p.items()})
        state = None
        losses, grad_norms = [], None
        clock = time.perf_counter()
        for t, (ids, labels) in enumerate(batches, start=1):
            if fault == "half_batch":
                ids, labels = ids[: len(ids) // 2], labels[: len(ids) // 2]
            n_blocks = len(ids) // rows
            total, grads, at = 0.0, zeros(params), leaves(params)
            for j in range(n_blocks):
                sl = slice(j * rows, (j + 1) * rows)
                loss, grads = vg(grads, at, jnp.asarray(ids[sl]),
                                 jnp.asarray(labels[sl]))
                total += float(loss)
            grads = scale(grads, jnp.float32(n_blocks))
            losses.append(total / n_blocks)
            if t == 1:
                grad_norms = {k: float(v) for k, v in
                              jax.device_get(norms(grads)).items()}
            if fault != "state_unchanged":
                params, on_chip = update(
                    params, grads, first_state(params) if state is None
                    else jax.device_put(state), jnp.float32(t))
                state = jax.device_get(on_chip) if t < len(batches) else None
                del on_chip
            del grads, at
            print(f"reference: step {t} followed after "
                  f"{time.perf_counter() - clock:.1f} s", flush=True)
        if fault == "state_unchanged":
            grad_norms = dict.fromkeys(grad_norms, 0.0)
        update_norms = {k: float(v) for k, v in jax.device_get(delta(
            params, initial_params(cfg, seed, dtype))).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
