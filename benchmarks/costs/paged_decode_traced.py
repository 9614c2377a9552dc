"""Decode attention of the output tokens delivered while traced, for a
Llama-shaped configuration: each output token reads the K and V of its
whole context over all layers. No operations counted (one query row: the
kernel is bound by bytes)."""


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of one token over all layers, as the pages hold them."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
            * cfg["num_hidden_layers"])


def cost(cfg, facts):
    return {"flops": 0,
            "bytes": sum(facts["traced_contexts"]) * kv_bytes_per_token(cfg)}
