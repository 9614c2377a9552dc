"""Forward and backward of every token one chip's share of a Nemotron-H
configuration trained in the window: 6 x the multiplying parameters held
here (a routed expert counted for the share of tokens uniform routing sends
it), the state-space products by the formula below, causal attention."""
from ..harness.costs import causal_attention_train


def matmul_params(cfg):
    """{kind: parameters that multiply a token in one layer of that kind},
    and ``head``. The keys that count heads, groups, experts and vocabulary
    rows give what is held here; the router spans the published experts."""
    hid = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    d, lat = cfg["head_dim"], cfg["moe_latent_size"]
    heads = cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    expert = 2 * lat * cfg["moe_intermediate_size"]
    # (token, held expert) pairs a token under uniform routing
    pairs = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["published"]["n_routed_experts"])
    return {
        "M": (hid * (inner + conv + cfg["mamba_num_heads"])
              + conv * cfg["conv_kernel"] + inner * hid),
        "*": hid * heads * d + cfg["num_attention_heads"] * d * hid,
        "E": (hid * cfg["published"]["n_routed_experts"] + 2 * hid * lat
              + 2 * hid * cfg["held"]["shared_expert_columns"]
              + pairs * expert),
        "head": hid * cfg["vocab_size"]}


def ssd_flops_per_token(cfg):
    """The chunked recurrence's products for one token of one layer,
    forward: inside its chunk a token meets (Q + 1) / 2 positions on
    average, so C.B over the state width N per group is N (Q + 1) and the
    weighted sum of their dt x is P (Q + 1) per head; what the token adds
    to the chunk's state and what it reads of the entering state are 2 P N
    per head each."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, q = cfg["ssm_state_size"], cfg["chunk_size"]
    return n * (q + 1) * cfg["n_groups"] + p * (q + 1) * h + 4 * p * n * h


def flops_per_token(cfg, seq):
    per = matmul_params(cfg)
    pattern = cfg["hybrid_override_pattern"]
    mult = sum(per[kind] for kind in pattern) + per["head"]
    ssd = 3 * ssd_flops_per_token(cfg) * pattern.count("M")
    attn = causal_attention_train(
        1, cfg["num_attention_heads"], seq, cfg["head_dim"],
        pattern.count("*"))["flops"] / seq
    return 6 * mult + ssd + attn


def cost(cfg, facts):
    return {"flops": flops_per_token(cfg, facts["seq"]) * facts["tokens"],
            "bytes": 0}
