"""The program's Llama-shaped model (Mistral-7B-v0.3 runs through
``LlamaForCausalLM``) from a configuration file (HF key names), holding the
seeded leaves: construct (the library builds fp32), ``bfloat16()``, then
every parameter replaced by ``harness.weights``."""
from ..harness import weights


def build(config, seed, train):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if config["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim = hidden / heads")
    paddle.seed(int(seed) % 2**31)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        max_position=config["max_position_embeddings"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"]))
    model.train() if train else model.eval()
    if config["dtype"] == "bfloat16":
        model.bfloat16()
    weights.load_into(model, seed)
    return model
