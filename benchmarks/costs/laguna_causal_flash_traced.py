"""Causal attention, forward and backward, of the traced steps of a Laguna
configuration's ``full_attention`` layers: the query heads held on such a
layer (each key/value head is expanded to the query heads that read it
before the kernel), ``head_dim``, one call a layer."""
from ..harness.costs import causal_attention_train
from .laguna_window_flash_traced import layers_of


def cost(cfg, facts):
    heads, layers = layers_of(cfg, "full_attention")
    one = causal_attention_train(facts["batch"], heads, facts["seq"],
                                 cfg["head_dim"], layers)
    return {k: v * facts["traced_steps"] for k, v in one.items()}
