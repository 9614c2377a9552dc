"""Causal differential attention, forward and backward, of the traced steps
of a Phi-4-flash configuration: its ``F`` and ``C`` layers (both attend over
every ``j <= i``; ``C`` reads layer ``F``'s keys and values), at the
mathematics' widths (``phi4flash_attention``), one call a layer."""
from .phi4flash_attention import causal_layers


def cost(cfg, facts):
    return {k: v * facts["traced_steps"]
            for k, v in causal_layers(cfg, facts).items()}
