"""Forward and backward of every token a GPT-2 shaped configuration trained
in the window: 6 x the multiplying parameters and causal attention."""
from ..harness.costs import causal_attention_train


def matmul_params(cfg):
    """Parameters that multiply every token: the blocks' matrices and the
    output head (tied to the token table, which the head multiplies by;
    the position table and the look-up multiply nothing)."""
    h, ff = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    return cfg["n_layer"] * (4 * h * h + 2 * h * ff) + cfg["vocab_size"] * h


def flops_per_token(cfg, seq):
    attn = causal_attention_train(1, cfg["n_head"], seq,
                                  cfg["n_embd"] // cfg["n_head"],
                                  cfg["n_layer"])["flops"] / seq
    return 6 * matmul_params(cfg) + attn


def cost(cfg, facts):
    return {"flops": flops_per_token(cfg, facts["seq"]) * facts["tokens"],
            "bytes": 0}
