"""Sliding-window attention, forward and backward, of the traced steps of a
Laguna configuration, counted by the band itself whatever implements it:
query i meets ``min(i + 1, window)`` keys, so a head and row of the batch
hold ``window (window + 1) / 2 + (S - window) window`` (query, key) pairs
(S >= window); forward QK^T and PV are ``2 head_dim`` operations a pair
each, backward dV, dP, dQ and dK twice that (the recomputation of QK^T that
a flash kernel does is not counted). Bytes: q, o, dO, dq at the query heads
held and k, v, dk, dv at the key/value heads the mathematics needs (not at
what an implementation repeats them to), two-byte elements, each array
once. One call a ``sliding_attention`` layer."""


def band_pairs(seq, window):
    """(query, key) pairs of one head and row: sum over i of min(i + 1,
    window)."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def band_attention_train(batch, heads, kv_heads, seq, head_dim, window,
                         layers):
    fwd = 2 * 2 * head_dim * band_pairs(seq, window) * batch * heads
    one = batch * seq * head_dim * 2
    return {"flops": 3 * fwd * layers,
            "bytes": 4 * one * (heads + kv_heads) * layers}


def layers_of(cfg, kind):
    """(query heads held on a layer of ``kind``, how many such layers)."""
    heads = [h for k, h in zip(cfg["layer_types"],
                               cfg["num_attention_heads_per_layer"])
             if k == kind]
    return (heads[0] if heads else 0), len(heads)


def cost(cfg, facts):
    heads, layers = layers_of(cfg, "sliding_attention")
    one = band_attention_train(
        facts["batch"], heads, cfg["num_key_value_heads"], facts["seq"],
        cfg["head_dim"], cfg["sliding_window"], layers)
    return {k: v * facts["traced_steps"] for k, v in one.items()}
