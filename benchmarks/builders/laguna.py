"""The program's Laguna from a configuration file (HF key names): the keys
that count heads, experts and vocabulary rows give what is held here,
``published`` gives the counts of the whole model, ``held`` the first
expert's id and the columns held of the dense layer's and the shared
expert's widths. Built as a user of the library builds it (construct, then
``bfloat16()``), then every parameter replaced by ``harness.weights``."""
from ..harness import weights


def by_kind(layer_types, per_layer):
    """{layer kind: heads} from a list by layer; every layer of a kind has
    the same count, in the source and in any cut of it."""
    out = {}
    for kind, heads in zip(layer_types, per_layer):
        if out.setdefault(kind, heads) != heads:
            raise ValueError(f"{kind} layers of {out[kind]} and of {heads} "
                             "query heads")
    return out


def model_config(config):
    from paddle_tpu.models.laguna import LagunaConfig

    pub, held = config["published"], config["held"]
    return LagunaConfig(
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        layer_types=config["layer_types"],
        mlp_layer_types=config["mlp_layer_types"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        rope=config["rope_parameters"], rms_norm_eps=config["rms_norm_eps"],
        vocab_size=pub["vocab_size"], vocab_rows_held=config["vocab_size"],
        q_heads=by_kind(pub["layer_types"],
                        pub["num_attention_heads_per_layer"]),
        q_heads_held=by_kind(config["layer_types"],
                             config["num_attention_heads_per_layer"]),
        num_key_value_heads=pub["num_key_value_heads"],
        kv_heads_held=config["num_key_value_heads"],
        num_experts=pub["num_experts"], experts_held=config["num_experts"],
        first_expert=held["first_expert"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        moe_routed_scaling_factor=config["moe_routed_scaling_factor"],
        dense_width_held=held["dense_mlp_columns"],
        shared_width_held=held["shared_expert_columns"],
        local_pairs_bound=held.get("local_pairs_bound",
                                   LagunaConfig.local_pairs_bound))


def build(config, seed, train):
    import paddle_tpu as paddle
    from paddle_tpu.models.laguna import LagunaForCausalLM

    paddle.seed(int(seed) % 2**31)
    model = LagunaForCausalLM(model_config(config))
    model.train() if train else model.eval()
    if config["dtype"] == "bfloat16":
        model.bfloat16()
    weights.load_into(model, seed)
    return model
