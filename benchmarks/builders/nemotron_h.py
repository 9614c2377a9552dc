"""The program's Nemotron-H from a configuration file (HF key names): the
keys that count heads, groups, experts and vocabulary rows give what is held
here, ``published`` gives the counts of the whole model, ``held`` the first
expert's id and the shared expert's columns. Built as a user of the library
builds it (construct, then ``bfloat16()``), then every parameter replaced by
``harness.weights``."""
from ..harness import weights


def model_config(config):
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    pub, held = config["published"], config["held"]
    return NemotronHConfig(
        hidden_size=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        rms_eps=config["layer_norm_epsilon"],
        vocab_size=pub["vocab_size"], vocab_rows_held=config["vocab_size"],
        mamba_num_heads=pub["mamba_num_heads"],
        mamba_heads_held=config["mamba_num_heads"],
        n_groups=pub["n_groups"], mamba_groups_held=config["n_groups"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        num_attention_heads=pub["num_attention_heads"],
        q_heads_held=config["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"],
        kv_heads_held=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_routed_experts=pub["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        first_expert=held["first_expert"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_latent_size=config["moe_latent_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        shared_width_held=held["shared_expert_columns"],
        routed_scaling_factor=config["routed_scaling_factor"],
        local_pairs_bound=held.get("local_pairs_bound",
                                   NemotronHConfig.local_pairs_bound))


def build(config, seed, train):
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

    paddle.seed(int(seed) % 2**31)
    model = NemotronHForCausalLM(model_config(config))
    model.train() if train else model.eval()
    if config["dtype"] == "bfloat16":
        model.bfloat16()
    weights.load_into(model, seed)
    return model
