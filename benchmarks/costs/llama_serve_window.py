"""Every prompt and output token of the requests a Llama-shaped
configuration finished in the window, through the blocks and the head."""


def matmul_params(cfg):
    """Parameters a served token is multiplied by: the blocks and the
    head, without the embedding table (a look-up)."""
    h = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    layer = h * q + 2 * h * kv + q * h + 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * h


def cost(cfg, facts):
    return {"flops": 2 * matmul_params(cfg) * facts["finished_tokens"],
            "bytes": 0}
