"""Mamba-1's selective scan, forward and backward, of the traced steps of a
Phi-4-flash configuration's ``M`` layers, by formula.

Operations a position, channel and state (an exponential counts one):
forward ``delta A``, ``exp``, ``a h``, ``(delta x) B``, their sum, ``h C``
and its sum into y: 7; backward ``G = dh + dy C`` 2, ``q = G h a`` 2, ``dA +=
q delta`` 2, ``d delta += q A`` 2, ``s += G B`` 2, the products of dB and dC
and their sums over channels 4, ``dh = a G`` 1: 15. A position and channel
besides: ``delta x``, ``D x`` and its sum forward 3; ``s delta``, ``D dy``,
their sum, ``s x`` and its sum, ``dy x`` and its sum backward 7. What a
backward works out again (the decays, the states inside a chunk) is not
counted.

Bytes the mathematics must move, each array once, at the types the cell
holds them in: x and its gradient 2 each, delta, y and their gradients 4
each (the recurrence is float32), B, Cm and their gradients 2 each a
position and state; A, D and their gradients 4 a channel (and state)."""


def scan_train(batch, seq, channels, states, layers):
    at = batch * seq * channels
    flops = at * ((7 + 15) * states + 3 + 7)
    nbytes = (at * (2 + 2 + 4 * 4) + batch * seq * states * 4 * 2
              + channels * (states + 1) * 2 * 4)
    return {"flops": flops * layers, "bytes": nbytes * layers}


def cost(cfg, facts):
    one = scan_train(facts["batch"], facts["seq"],
                     cfg["held"]["scan_channels"], cfg["mamba_d_state"],
                     cfg["layer_pattern"].count("M"))
    return {k: v * facts["traced_steps"] for k, v in one.items()}
