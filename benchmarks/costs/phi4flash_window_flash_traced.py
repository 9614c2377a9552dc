"""Sliding-window differential attention, forward and backward, of the
traced steps of a Phi-4-flash configuration: its ``S`` layers, by the band's
own area at the mathematics' widths (``phi4flash_attention``), one call a
layer."""
from .phi4flash_attention import window_layers


def cost(cfg, facts):
    return {k: v * facts["traced_steps"]
            for k, v in window_layers(cfg, facts).items()}
