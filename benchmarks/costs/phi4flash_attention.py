"""Differential attention, forward and backward, of one step of a Phi-4-flash
configuration, counted by the mathematics' own widths whatever implements
it: every query head is one softmax over ``head_dim``-wide q and k and the
pair's ``2 head_dim``-wide values, so a (query, key) pair under the mask
costs ``2 head_dim`` operations for the score and ``2 (2 head_dim)`` for the
values, forward; backward dV, dP, dQ and dK twice that (the recomputation of
QK^T that a flash kernel does is not counted, nor the lanes a kernel pads q
and k to). Pairs a head and row: ``S^2 / 2`` under the causal mask
(``harness.costs.causal_attention_train``'s count) and the band's own area
under the window. Bytes, two-byte elements, each array as often as a flash
kernel must touch it (forward reads q, k, v and writes o; backward reads q,
k, v, o, dO and writes dq, dk, dv): q and its gradient at the query heads
held and ``head_dim``, o and dO at the query heads and ``2 head_dim``, k, v
and their gradients at the key/value heads the mathematics needs (not at
what an implementation repeats them to)."""
from .laguna_window_flash_traced import band_pairs


def attention_train(batch, q_heads, kv_heads, seq, head_dim, pairs, layers):
    fwd = 2 * 3 * head_dim * pairs * batch * q_heads
    one = batch * seq * head_dim * 2          # one head's array, bytes
    touched = (3 * q_heads                    # q twice, dq
               + 2 * 3 * q_heads              # o twice, dO (2 head_dim wide)
               + 2 * 3 * kv_heads)            # k, v twice each, dk, dv
    return {"flops": 3 * fwd * layers, "bytes": one * touched * layers}


def _layers(cfg, facts, kinds, pairs):
    return attention_train(
        facts["batch"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], facts["seq"], cfg["head_dim"], pairs,
        sum(cfg["layer_pattern"].count(k) for k in kinds))


def causal_layers(cfg, facts):
    """One step of the ``F`` and ``C`` layers: every ``j <= i``."""
    return _layers(cfg, facts, "FC", facts["seq"] * facts["seq"] / 2)


def window_layers(cfg, facts):
    """One step of the ``S`` layers: the band's own area."""
    return _layers(cfg, facts, "S",
                   band_pairs(facts["seq"], cfg["sliding_window"]))
