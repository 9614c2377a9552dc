"""The routing tap of the MoE layers: a side channel for counters that a
traced forward threads out as a program output.

A builder arms the tap around ``model.forward``; each MoE layer traced
under it appends one float32 vector (what it holds is the layer's own:
``LlamaMoEMLP``'s per-expert kept tokens, dropped pairs, router entropy and
routed tokens; ``LatentMoE``'s and ``LagunaMoE``'s pairs routed here, tokens
with none, pairs left out, rows walked), and the builder returns the list from the traced
function. Unarmed, the layers skip the counters entirely and their traces
are unchanged.
"""
import contextlib

__all__ = ["moe_stats_tap", "armed"]

_TAP = None


@contextlib.contextmanager
def moe_stats_tap():
    """Collect per-MoE-layer routing stats emitted during a forward
    traced under this context. Yields the list the layers append to."""
    global _TAP
    prev = _TAP
    _TAP = tap = []
    try:
        yield tap
    finally:
        _TAP = prev


def armed():
    """The list the layers append to, or None where no tap is armed."""
    return _TAP
