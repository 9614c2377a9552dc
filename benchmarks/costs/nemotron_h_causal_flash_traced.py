"""Causal attention, forward and backward, of the traced steps of a
Nemotron-H configuration: the query heads held here (each key/value head is
expanded to the query heads that read it before the kernel), ``head_dim``,
one call a ``*`` of the pattern."""
from ..harness.costs import causal_attention_train


def cost(cfg, facts):
    one = causal_attention_train(
        facts["batch"], cfg["num_attention_heads"], facts["seq"],
        cfg["head_dim"], cfg["hybrid_override_pattern"].count("*"))
    return {k: v * facts["traced_steps"] for k, v in one.items()}
