"""The program's Phi-4-flash from a configuration file (HF key names where
the source has one): the keys that count heads and vocabulary rows give what
is held here, ``published`` gives the counts of the whole model, ``held`` the
scan channels and MLP columns held and each layer's published index. Built
as a user of the library builds it (construct, then ``bfloat16()``), then
every parameter replaced by ``harness.weights``."""
from ..harness import weights


def model_config(config):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig

    pub, held = config["published"], config["held"]
    return Phi4FlashConfig(
        vocab_size=pub["vocab_size"], vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        mlp_columns_held=held["mlp_columns"],
        layer_pattern=config["layer_pattern"],
        layer_indices=held["layers"],
        num_attention_heads=pub["num_attention_heads"],
        q_heads_held=config["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"],
        kv_heads_held=config["num_key_value_heads"],
        sliding_window=config["sliding_window"],
        layer_norm_eps=config["layer_norm_eps"],
        ssm_state_size=config["mamba_d_state"],
        conv_kernel=config["mamba_d_conv"], expand=config["mamba_expand"],
        dt_rank=config["mamba_dt_rank"],
        scan_channels_held=held["scan_channels"])


def build(config, seed, train):
    import paddle_tpu as paddle
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM

    paddle.seed(int(seed) % 2**31)
    model = Phi4FlashForCausalLM(model_config(config))
    model.train() if train else model.eval()
    if config["dtype"] == "bfloat16":
        model.bfloat16()
    weights.load_into(model, seed)
    return model
