"""GPT-2, plainly: forward, loss, gradients and AdamW in ``jax.numpy`` and
float32 at ``highest`` matmul precision; no kernel, no cache, nothing of the
program. Weights come from the seed by leaf name (``harness.weights``), in
the type the configuration states, raised to float32.

Follows Radford et al. 2019 as the HF ``GPT2LMHeadModel`` computes it:
pre-LN blocks, packed c_attn [h, 3h] split q|k|v, ``gelu_new`` (tanh), learned
positions, head tied to the token table. Departure: the vocabulary is padded
to the configuration's ``padded_vocab_size`` rows, as the program pads it
(token ids stay below ``vocab_size``; the padded logits take part in the
softmax, as they do in the program).
"""
import time

import numpy as np

from ..harness import weights
from ..harness.norms import block_norms
from .quant import operand_rounding

def leaf_specs(cfg, dtype):
    h, ff = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    specs = [("gpt.wte.weight", (cfg["padded_vocab_size"], h)),
             ("gpt.wpe.weight", (cfg["n_positions"], h))]
    for i in range(cfg["n_layer"]):
        p = f"gpt.h.{i}."
        specs += [(p + "ln_1.weight", (h,)), (p + "ln_1.bias", (h,)),
                  (p + "attn.qkv_proj.weight", (h, 3 * h)),
                  (p + "attn.qkv_proj.bias", (3 * h,)),
                  (p + "attn.out_proj.weight", (h, h)),
                  (p + "attn.out_proj.bias", (h,)),
                  (p + "ln_2.weight", (h,)), (p + "ln_2.bias", (h,)),
                  (p + "mlp.fc.weight", (h, ff)), (p + "mlp.fc.bias", (ff,)),
                  (p + "mlp.proj.weight", (ff, h)),
                  (p + "mlp.proj.bias", (h,))]
    specs += [("gpt.ln_f.weight", (h,)), ("gpt.ln_f.bias", (h,))]
    return [(n, s, dtype) for n, s in specs]


def initial_params(cfg, seed, dtype):
    """{name: float32 array} holding the values of the stated type."""
    import jax.numpy as jnp

    specs = leaf_specs(cfg, dtype)
    leaves = weights.make_leaves(seed, specs)
    return {n: a.astype(jnp.float32) for (n, _, _), a in zip(specs, leaves)}


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def loss_fn(params, ids, labels, cfg, rnd):
    """Mean next-token cross-entropy of rows ``ids`` [b, s]."""
    import jax
    import jax.numpy as jnp

    h, nh = cfg["n_embd"], cfg["n_head"]
    hd, eps = h // nh, cfg["layer_norm_epsilon"]
    b, s = ids.shape
    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, p):
        a = _layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps)
        qkv = mm(a, p["attn.qkv_proj.weight"]) + p["attn.qkv_proj.bias"]
        q, k, v = (t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bhqd,bhkd->bhqk", rnd(q), rnd(k)) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att = jnp.einsum("bhqk,bhkd->bhqd", rnd(probs), rnd(v))
        att = att.transpose(0, 2, 1, 3).reshape(b, s, h)
        x = x + mm(att, p["attn.out_proj.weight"]) + p["attn.out_proj.bias"]
        m = _layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps)
        m = jax.nn.gelu(mm(m, p["mlp.fc.weight"]) + p["mlp.fc.bias"],
                        approximate=True)
        return x + mm(m, p["mlp.proj.weight"]) + p["mlp.proj.bias"]

    x = params["gpt.wte.weight"][ids] + params["gpt.wpe.weight"][:s]
    # the layers are alike: one traced block scanned over the stacked leaves
    # (the same arithmetic as a loop, a twentieth of the program to compile)
    pre = "gpt.h.0."
    stacked = {k[len(pre):]: jnp.stack(
        [params[f"gpt.h.{i}.{k[len(pre):]}"] for i in range(cfg["n_layer"])])
        for k in params if k.startswith(pre)}
    x, _ = jax.lax.scan(lambda x, p: (jax.checkpoint(block)(x, p), None),
                        x, stacked)
    x = _layer_norm(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                    eps)
    logits = mm(x, params["gpt.wte.weight"].T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def adamw(params, grads, state, step, a):
    """AdamW with decoupled decay on every leaf; ``a`` holds lr, beta1,
    beta2, eps and weight_decay (the traffic file's ``optimizer``)."""
    import jax.numpy as jnp

    new_p, new_s = {}, {}
    for k, p in params.items():
        g = grads[k]
        m = a["beta1"] * state[k][0] + (1 - a["beta1"]) * g
        v = a["beta2"] * state[k][1] + (1 - a["beta2"]) * jnp.square(g)
        mhat = m / (1 - a["beta1"] ** step)
        vhat = v / (1 - a["beta2"] ** step)
        new_p[k] = p - a["lr"] * (mhat / (jnp.sqrt(vhat) + a["eps"])
                                  + a["weight_decay"] * p)
        new_s[k] = (m, v)
    return new_p, new_s


def train_readings(cfg, seed, batches, adam, blocks, precision="float32",
                   rows=2, fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.

    ``batches``: [(ids, labels)] int arrays [B, S]; gradients are
    accumulated over blocks of ``rows`` rows so the float32 activations
    fit. ``fault`` plants one in the reference, for the control's readings:
    ``"half_batch"``, a step that leaves half of its rows out and takes the
    mean over the rest; ``"state_unchanged"``, a step that returns its state
    as it got it (the optimizer's state then holds no gradient). Returns
    {"losses": [...], "grad_norms": {block: norm of the first gradient},
     "update_norms": {block: norm of the change after the last step}},
    by leaf block (harness/norms.py)."""
    import jax
    import jax.numpy as jnp

    rnd = operand_rounding(precision)
    dtype = jnp.dtype(cfg["dtype"])
    with jax.default_matmul_precision("highest"):
        params = initial_params(cfg, seed, dtype)
        vg = jax.jit(jax.value_and_grad(
            lambda p, i, l: loss_fn(p, i, l, cfg, rnd)))
        acc = jax.jit(lambda a, g: jax.tree_util.tree_map(jnp.add, a, g))
        scale = jax.jit(lambda a, n: jax.tree_util.tree_map(
            lambda x: x / n, a))
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
            total, grads = 0.0, None
            for j in range(n_blocks):
                sl = slice(j * rows, (j + 1) * rows)
                loss, g = vg(params, jnp.asarray(ids[sl]),
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
            print(f"reference: step {t} followed after "
                  f"{time.perf_counter() - clock:.1f} s", flush=True)
        if fault == "state_unchanged":
            grad_norms = dict.fromkeys(grad_norms, 0.0)
        update_norms = {k: float(v) for k, v in jax.device_get(delta(
            params, initial_params(cfg, seed, dtype))).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
