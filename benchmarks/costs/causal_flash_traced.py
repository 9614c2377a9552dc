"""Causal attention, forward and backward, of the traced steps of a GPT-2
shaped configuration (keys ``n_head``, ``n_embd``, ``n_layer``)."""
from ..harness.costs import causal_attention_train


def cost(cfg, facts):
    one = causal_attention_train(facts["batch"], cfg["n_head"], facts["seq"],
                                 cfg["n_embd"] // cfg["n_head"],
                                 cfg["n_layer"])
    return {k: v * facts["traced_steps"] for k, v in one.items()}
