"""The program's GPT-2 from a configuration file (HF key names), holding
the seeded leaves: built as a user of the library builds it (construct, then
``bfloat16()``), then every parameter replaced by ``harness.weights``."""
from ..harness import weights


def build(config, seed, train):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(int(seed) % 2**31)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=config["padded_vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        max_position=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"]))
    model.train() if train else model.eval()
    if config["dtype"] == "bfloat16":
        model.bfloat16()
    weights.load_into(model, seed)
    return model
