"""Forward and backward of every token one chip's share of a Laguna
configuration trained in the window: 6 x the multiplying parameters held
here (a routed expert counted for the share of tokens uniform routing sends
it, the router whole, the embedding's gather none), causal attention on the
full layers and the band's own area on the sliding ones. The experts' part
is nominal: the driver's facts do not hold the pairs the routers really
sent (``moe_stats_tap`` counts them in the program), and in the cell they
fall under the uniform load within 50 steps (PERF.md section 6, PR 32)."""
from ..harness.costs import causal_attention_train
from .laguna_window_flash_traced import band_attention_train, layers_of


def matmul_params(cfg):
    """{"attention": [parameters that multiply a token, by layer], "dense",
    "sparse": one FFN of that kind, "head"}. The keys that count heads,
    experts and vocabulary rows give what is held here; the router spans
    the published experts."""
    hid, d = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    # (token, held expert) pairs a token under uniform routing
    pairs = (cfg["num_experts_per_tok"] * cfg["num_experts"]
             / cfg["published"]["num_experts"])
    swiglu = lambda width: 3 * hid * width
    return {
        # q, k, v and the per-head gate in, W_o out
        "attention": [hid * (h + 2 * kv) * d + hid * h + h * d * hid
                      for h in cfg["num_attention_heads_per_layer"]],
        "dense": swiglu(cfg["held"]["dense_mlp_columns"]),
        "sparse": (hid * cfg["published"]["num_experts"]
                   + swiglu(cfg["held"]["shared_expert_columns"])
                   + pairs * swiglu(cfg["moe_intermediate_size"])),
        "head": hid * cfg["vocab_size"]}


def flops_per_token(cfg, seq):
    per = matmul_params(cfg)
    mult = (sum(per["attention"])
            + sum(per[kind] for kind in cfg["mlp_layer_types"])
            + per["head"])
    heads, layers = layers_of(cfg, "full_attention")
    causal = causal_attention_train(1, heads, seq, cfg["head_dim"],
                                    layers)["flops"] / seq
    heads, layers = layers_of(cfg, "sliding_attention")
    band = band_attention_train(
        1, heads, cfg["num_key_value_heads"], seq, cfg["head_dim"],
        cfg["sliding_window"], layers)["flops"] / seq
    return 6 * mult + causal + band


def cost(cfg, facts):
    return {"flops": flops_per_token(cfg, facts["seq"]) * facts["tokens"],
            "bytes": 0}
