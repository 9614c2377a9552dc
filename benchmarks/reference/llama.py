"""A Llama-shaped decoder (Mistral-7B-v0.3), plainly: one full forward over
a prompt and the tokens served after it, in ``jax.numpy`` and float32 at
``highest`` matmul precision; no kernel, no cache, no batching, nothing of
the program. Weights come from the seed by leaf name, layer by layer, in the
type the configuration states, raised to float32, so no more than one
layer's weights are on the chip at a time.

Follows the HF ``MistralForCausalLM``: RMSNorm, grouped-query attention with
rotate-half RoPE (theta from the configuration), SwiGLU, untied head. v0.3
has no sliding window.
"""
import numpy as np

from ..harness import weights
from .quant import operand_rounding

PAD_TO = 512


def layer_specs(cfg, i, dtype):
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    p = f"model.layers.{i}."
    return [(p + "input_layernorm.weight", (h,), dtype),
            (p + "self_attn.q_proj.weight", (h, q), dtype),
            (p + "self_attn.k_proj.weight", (h, kv), dtype),
            (p + "self_attn.v_proj.weight", (h, kv), dtype),
            (p + "self_attn.o_proj.weight", (q, h), dtype),
            (p + "post_attention_layernorm.weight", (h,), dtype),
            (p + "mlp.gate_proj.weight", (h, ff), dtype),
            (p + "mlp.up_proj.weight", (h, ff), dtype),
            (p + "mlp.down_proj.weight", (ff, h), dtype)]


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                               + eps)) * w


def _rope(x, theta):
    """x [s, heads, d], rotate-half, positions 0..s-1."""
    import jax.numpy as jnp

    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    emb = jnp.concatenate([f, f], axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def layer_forward(x, leaves, cfg, rnd):
    """One decoder layer over one sequence x [s, h] (float32)."""
    import jax
    import jax.numpy as jnp

    ln1, wq, wk, wv, wo, ln2, wg, wu, wd = (
        a.astype(jnp.float32) for a in leaves)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    s = x.shape[0]
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b))
    a = _rms(x, ln1, cfg["rms_norm_eps"])
    q = _rope(mm(a, wq).reshape(s, nh, hd), cfg["rope_theta"])
    k = _rope(mm(a, wk).reshape(s, nkv, hd), cfg["rope_theta"])
    v = mm(a, wv).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k)) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v)).reshape(s, nh * hd)
    x = x + mm(att, wo)
    m = _rms(x, ln2, cfg["rms_norm_eps"])
    return x + mm(jax.nn.silu(mm(m, wg)) * mm(m, wu), wd)


def next_token_logits(cfg, seed, sequences, first_rows, precision="float32"):
    """For each sequence of token ids, the float32 logits of the rows from
    ``first_rows[i]`` on: row r predicts token r + 1. The sequences are
    padded to one length, a multiple of ``PAD_TO`` (causal: padding after
    the end changes nothing before it), and go through each layer one after
    the other inside one program, so a layer is one dispatch and few
    programs are compiled."""
    import jax
    import jax.numpy as jnp

    rnd = operand_rounding(precision)
    dtype = jnp.dtype(cfg["dtype"])
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    longest = max(len(ids) for ids in sequences)
    width = longest + (-longest % PAD_TO)
    ids = np.zeros((len(sequences), width), np.int32)
    for i, seq in enumerate(sequences):
        ids[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        emb = weights.make_leaf(seed, "model.embed_tokens.weight",
                                (vocab, h), dtype)
        x = jax.jit(lambda e, i: e[i].astype(jnp.float32))(
            emb, jnp.asarray(ids))
        del emb
        layer = jax.jit(lambda x, leaves: jax.lax.map(
            lambda xi: layer_forward(xi, leaves, cfg, rnd), x))
        maker = weights.leaf_maker(layer_specs(cfg, 0, dtype))
        for i in range(cfg["num_hidden_layers"]):
            names = [n for n, _, _ in layer_specs(cfg, i, dtype)]
            x = layer(x, maker(weights.words_for(seed, names)))
        norm = weights.make_leaf(seed, "model.norm.weight", (h,), dtype)
        head = weights.make_leaf(seed, "lm_head.weight", (h, vocab), dtype)
        logits = jax.jit(lambda rows, norm, head: jnp.matmul(
            rnd(_rms(rows, norm.astype(jnp.float32), cfg["rms_norm_eps"])),
            rnd(head.astype(jnp.float32))))
        out = []
        for i, (seq, r0) in enumerate(zip(sequences, first_rows)):
            # rows padded to a multiple of 128, so few head programs
            n = len(seq) - r0
            rows = jnp.zeros((n + (-n % 128), h), jnp.float32)
            rows = rows.at[:n].set(x[i, r0:len(seq)])
            out.append(np.asarray(logits(rows, norm, head))[:n])
    return out


def served_gaps(cfg, seed, samples, precision="float32", control=None):
    """For samples [(prompt ids, served tokens)], the gap at every served
    position by which the served token's reference logit lies below the
    reference's best. With ``control`` (a precision), the token judged at
    each position is not the served one but the one that the reference
    computed in that lower precision puts first, over the same prompts and
    tokens. Returns one array of gaps per sample."""
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)])
            for p, t in samples]
    first = [len(p) - 1 for p, _ in samples]
    logits = next_token_logits(cfg, seed, seqs, first, precision)
    if control:
        low = next_token_logits(cfg, seed, seqs, first, control)
        judged = [lo.argmax(-1) for lo in low]
    else:
        judged = [np.asarray(t) for _, t in samples]
    return [lg.max(-1) - lg[np.arange(len(tok)), tok]
            for lg, tok in zip(logits, judged)]
