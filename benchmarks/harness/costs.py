"""Arithmetic every cost module shares. No recomputation is counted; a
matrix multiplication of m x k by k x n is 2 m k n operations. What one
kernel or one model's step needs is a module of its own under
``benchmarks/costs/``, found by the name a per-layer metric's file gives:
``cost(config, facts) -> {"flops": ..., "bytes": ...}`` from the
configuration's sizes and the counts the driver took."""


def causal_attention_train(batch, heads, seq, head_dim, layers):
    """Causal self-attention, forward and backward, of one step.

    Forward is QK^T and PV over the causal half: 2 * (2 b h s^2 d) / 2.
    Backward needs dV, dP, dQ and dK: four such products, twice the forward
    (the recomputation of QK^T that a flash kernel does is not counted).
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o, dO
    and writes dq, dk, dv: 12 arrays of b s h d two-byte elements."""
    fwd = 2 * batch * heads * seq * seq * head_dim
    return {"flops": 3 * fwd * layers,
            "bytes": 12 * batch * seq * heads * head_dim * 2 * layers}


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "memory"))
