"""Forward and backward of every token one chip's share of a Phi-4-flash
configuration trained in the window: 6 x the multiplying parameters held
here (the tied table once, for the logits: the embedding's gather multiplies
nothing), differential attention by the mathematics' own widths (the causal
half on ``F`` and ``C`` layers, the band's area on ``S`` layers:
``phi4flash_attention``) and the selective scan by formula
(``phi4flash_selective_scan_traced``)."""
from .phi4flash_attention import causal_layers, window_layers
from .phi4flash_selective_scan_traced import scan_train


def matmul_params(cfg):
    """{layer letter: parameters that multiply a token in one layer of that
    kind, its MLP included}, and ``head``. The keys that count heads and
    vocabulary rows, and ``held``, give what is held here."""
    hid, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    ch, ff = cfg["held"]["scan_channels"], cfg["held"]["mlp_columns"]
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    mlp = hid * 2 * ff + ff * hid
    attn = hid * (q + 2 * kv) + q * hid
    return {
        # W_in, the convolution's taps, W_x, W_dt, W_out
        "M": (hid * 2 * ch + ch * cfg["mamba_d_conv"] + ch * (rank + 2 * n)
              + rank * ch + ch * hid + mlp),
        "S": attn + mlp, "F": attn + mlp,
        "C": hid * q + q * hid + mlp,        # q and W_o only
        "G": hid * ch + ch * hid + mlp,
        "head": hid * cfg["vocab_size"]}


def flops_per_token(cfg, seq):
    per = matmul_params(cfg)
    mult = sum(per[kind] for kind in cfg["layer_pattern"]) + per["head"]
    facts = {"batch": 1, "seq": seq}
    rest = (causal_layers(cfg, facts)["flops"]
            + window_layers(cfg, facts)["flops"]
            + scan_train(1, seq, cfg["held"]["scan_channels"],
                         cfg["mamba_d_state"],
                         cfg["layer_pattern"].count("M"))["flops"])
    return 6 * mult + rest / seq


def cost(cfg, facts):
    return {"flops": flops_per_token(cfg, facts["seq"]) * facts["tokens"],
            "bytes": 0}
