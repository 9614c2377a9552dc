"""Phi-4-flash, plainly: forward, loss, gradients and AdamW in ``jax.numpy``
and float32 at ``highest`` matmul precision; no kernel, nothing of the
program. Weights come from the seed by leaf name (``harness.weights``), in
the type the configuration states, raised to float32.

Block l of ``layer_pattern`` (published index ``held.layers[l]``), with u =
LayerNorm(x) (scale and bias, eps ``layer_norm_eps``): ``x <- x +
mixer_l(LayerNorm(x))``, ``x <- x + MLP(LayerNorm(x))``; a final LayerNorm;
logits ``h E^T`` with the embedding's table E; no positional encoding.

* MLP: ``[g, y] = u W_1``, ``(y silu(g)) W_2``.
* ``M``: ``[x, z] = u W_in``; ``x = silu(conv(x) + b_conv)`` (causal,
  depthwise, ``mamba_d_conv`` taps); ``[r, B, Cm] = x W_x``; ``delta =
  softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(delta_t (x) A)
  h_{t-1} + (delta_t x_t) (x) B_t``; ``y_t = h_t . Cm_t + D x_t``, one
  position after the other; ``out = (y silu(z)) W_out``. ``y`` is also the
  memory m the later ``G`` layers read.
* ``G``: ``out = (silu(u W_g) m) W_o'``.
* ``S``, ``F``, ``C``, differential attention: heads of ``head_dim`` pair up,
  (2p, 2p + 1); query pair P reads key/value pair ``P // g`` (g query pairs
  a key/value pair); ``a_1 = softmax(q_2P k_2p^T / sqrt(head_dim))``, ``a_2``
  of the odd heads, under the mask ``j <= i`` (``S``: and ``i - j <
  sliding_window``); ``o_P = (a_1 - lambda a_2) [v_2p | v_2p+1]``; ``lambda
  = exp(lq_1 . lk_1) - exp(lq_2 . lk_2) + lambda_init(l)``; ``o_P =
  RMSNorm(o_P; scale) (1 - lambda_init(l))``; the pairs side by side through
  ``W_o``; biases on q, k, v, o. ``C`` projects q only and reads the k and v
  layer ``F`` made.

The configuration gives what is held here under the source's own keys
(``num_attention_heads``, ``num_key_value_heads``, ``vocab_size``) and under
``held`` (``scan_channels``, ``mlp_columns``, ``layers``); what absent chips
would add is left out here as in the program (``[r, B, Cm]`` is the held
channels' part of its sum). Departures from the source: none known; what no
key of the source says is listed under the configuration's ``assumed``, and
each is one function below.

So that the cell's size fits the chip: attention is computed for a block of
query rows at a time against every key, the position-wise parts (MLP, the
head with its loss) for a block of positions at a time, the recurrence in
chunks of positions, and every layer (every chunk) is worked out again in
the backward. None of that changes a sum's terms.
"""
import math
import time

from ..harness import weights
from ..harness.norms import block_norms
from .gpt2 import adamw
from .laguna import in_blocks
from .quant import operand_rounding

MIXER = {"M": "mamba", "S": "attn_window", "F": "attn_full", "G": "gmu",
         "C": "attn_cross"}
QUERY_ROWS = 512    # query rows of one block of the attention
POSITIONS = 1024    # positions of one block of the MLP and the head
SCAN_CHUNK = 128    # positions of one chunk of the recurrence


def leaf_specs(cfg, dtype):
    hid, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ch, ff = cfg["held"]["scan_channels"], cfg["held"]["mlp_columns"]
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    norm = lambda p: [(p + ".weight", (hid,)), (p + ".bias", (hid,))]
    linear = lambda p, i, o: [(p + ".weight", (i, o)), (p + ".bias", (o,))]
    specs = [("model.embeddings.weight", (cfg["vocab_size"], hid))]
    for i, kind in enumerate(cfg["layer_pattern"]):
        p = f"model.layers.{i}."
        m = p + MIXER[kind] + "."
        specs += norm(p + "norm_mixer")
        if kind == "M":
            specs += [(m + "in_proj.weight", (hid, 2 * ch)),
                      (m + "conv1d_weight", (ch, cfg["mamba_d_conv"])),
                      (m + "conv1d_bias", (ch,)),
                      (m + "x_proj.weight", (ch, rank + 2 * n))]
            specs += linear(m + "dt_proj", rank, ch)
            specs += [(m + "A_log", (ch, n)), (m + "D", (ch,)),
                      (m + "out_proj.weight", (ch, hid))]
        elif kind == "G":
            specs += [(m + "in_proj.weight", (hid, ch)),
                      (m + "out_proj.weight", (ch, hid))]
        else:
            specs += linear(m + "q_proj", hid, hq * d)
            if kind != "C":
                specs += linear(m + "k_proj", hid, hk * d)
                specs += linear(m + "v_proj", hid, hk * d)
            specs += [(m + "lambda_q", (2, d)), (m + "lambda_k", (2, d)),
                      (m + "subln.weight", (2 * d,))]
            specs += linear(m + "o_proj", hq * d, hid)
        specs += norm(p + "norm_mlp")
        specs += [(p + "mlp.gate_up_proj.weight", (hid, 2 * ff)),
                  (p + "mlp.down_proj.weight", (ff, hid))]
    specs += norm("model.norm_f")
    return [(name, shape, dtype) for name, shape in specs]


def initial_params(cfg, seed, dtype):
    """{name: float32 array} holding the values of the stated type."""
    import jax.numpy as jnp

    specs = leaf_specs(cfg, dtype)
    leaves = weights.make_leaves(seed, specs)
    return {n: a.astype(jnp.float32) for (n, _, _), a in zip(specs, leaves)}


def layer_norm(x, p, name, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p[name + ".weight"]
            + p[name + ".bias"])


# ----------------------------------------------------------------- the scan


def causal_conv(x, w, bias):
    """assumed (a): depthwise over the positions, tap k of ``w [channels,
    taps]`` on the input ``taps - 1 - k`` positions back, plus a bias."""
    import jax.numpy as jnp

    taps, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + s] * w[:, k] for k in range(taps)) + bias


def recurrence(x, delta, a, bm, cm, d):
    """``h_t = exp(delta_t (x) A) h_{t-1} + (delta_t x_t) (x) B_t``, ``y_t =
    h_t . Cm_t + D x_t``: x, delta ``[b, s, c]``, A ``[c, n]``, B and Cm
    ``[b, s, n]``. A ``lax.scan`` over the positions."""
    import jax
    import jax.numpy as jnp

    b, s, c = x.shape

    def position(h, at):
        x_t, delta_t, b_t, c_t = at
        h = (jnp.exp(delta_t[:, :, None] * a) * h
             + (delta_t * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + d * x_t

    def chunk(h, at):
        return jax.lax.scan(position, h, at)

    size = SCAN_CHUNK if s % SCAN_CHUNK == 0 else s
    by_chunk = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        s // size, size, *t.shape[:1], *t.shape[2:])
    _, y = jax.lax.scan(jax.checkpoint(chunk),
                        jnp.zeros((b, c, a.shape[1]), jnp.float32),
                        tuple(by_chunk(t) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y.reshape(s, b, c), 0, 1)


def scan_mixer(u, p, cfg, rnd):
    """(the layer's part of the residual, y: assumed (e), the memory is y
    before the gate)."""
    import jax
    import jax.numpy as jnp

    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    x, z = jnp.split(mm(u, p["in_proj.weight"]), 2, axis=-1)
    x = jax.nn.silu(causal_conv(x, p["conv1d_weight"], p["conv1d_bias"]))
    rbc = mm(x, p["x_proj.weight"])        # the held channels' part of the sum
    r, bm, cm = rbc[..., :rank], rbc[..., rank:rank + n], rbc[..., rank + n:]
    delta = jax.nn.softplus(mm(r, p["dt_proj.weight"]) + p["dt_proj.bias"])
    y = recurrence(x, delta, -jnp.exp(p["A_log"]), bm, cm, p["D"])
    return mm(y * jax.nn.silu(z), p["out_proj.weight"]), y


def memory_unit(u, memory, p, rnd):
    import jax
    import jax.numpy as jnp

    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    return mm(jax.nn.silu(mm(u, p["in_proj.weight"])) * memory,
              p["out_proj.weight"])


# --------------------------------------------------------------- attention


def lambda_init(layer):
    """assumed (c): the constant at a layer's PUBLISHED index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def seen(kind, window, rows, seq):
    """The rows ``rows`` of the ``[S, S]`` mask: query i sees key j.
    assumed (b): on an ``S`` layer the edge is ``i - j < window``."""
    import jax.numpy as jnp

    ahead = rows[:, None] - jnp.arange(seq)[None, :]               # i - j
    mask = ahead >= 0
    if kind == "S":
        mask = mask & (ahead < window)
    return mask


def keys_values(u, p, cfg, rnd):
    """k ``[b, pairs, 2, s, d]`` and the pairs' values ``[b, pairs, s, 2
    d]`` of an ``S`` or ``F`` layer."""
    import jax.numpy as jnp

    b, s, _ = u.shape
    d, pairs = cfg["head_dim"], cfg["num_key_value_heads"] // 2
    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    k = mm(u, p["k_proj.weight"]) + p["k_proj.bias"]
    v = mm(u, p["v_proj.weight"]) + p["v_proj.bias"]
    return (k.reshape(b, s, pairs, 2, d).transpose(0, 2, 3, 1, 4),
            v.reshape(b, s, pairs, 2 * d).transpose(0, 2, 1, 3))


def diff_attention(u, kv, p, cfg, rnd, kind, layer):
    """assumed (c): the pairing (2p, 2p + 1), lambda's form, the RMSNorm
    over the pair's 2 d with scale, eps ``layer_norm_eps``."""
    import jax
    import jax.numpy as jnp

    b, s, _ = u.shape
    d, eps = cfg["head_dim"], cfg["layer_norm_eps"]
    q_pairs = cfg["num_attention_heads"] // 2
    k, v = kv
    g = q_pairs // k.shape[1]           # query pairs a key/value pair
    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))
    q = (mm(u, p["q_proj.weight"]) + p["q_proj.bias"]).reshape(
        b, s, k.shape[1], g, 2, d).transpose(0, 2, 3, 4, 1, 5)
    lq, lk, init = p["lambda_q"], p["lambda_k"], lambda_init(layer)
    lam = jnp.exp(jnp.dot(lq[0], lk[0])) - jnp.exp(jnp.dot(lq[1], lk[1])) \
        + init

    def block(qb, rows):  # [b, pairs, g, 2, rows, d] queries at positions rows
        scores = jnp.einsum("bpgtqd,bptkd->bpgtqk", rnd(qb), rnd(k)) \
            / math.sqrt(d)
        mask = seen(kind, cfg["sliding_window"], rows.reshape(-1), s)
        a = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bpgqk,bpkd->bpgqd",
                          rnd(a[:, :, :, 0] - lam * a[:, :, :, 1]), rnd(v))

    def rows_of(qb, rows):  # in_blocks cuts along one axis of every array
        return block(qb, rows)[:, :, :, None]

    o = in_blocks(rows_of, QUERY_ROWS, 4, q,
                  jnp.arange(s).reshape(1, 1, 1, 1, s, 1))[:, :, :, 0]
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * p["subln.weight"] * (1.0 - init)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, s, q_pairs * 2 * d)
    return mm(o, p["o_proj.weight"]) + p["o_proj.bias"]


# ---------------------------------------------------------------- the model


def mlp(u, p, rnd):
    import jax
    import jax.numpy as jnp

    mm = lambda x, w: jnp.matmul(rnd(x), rnd(w))

    def part(ub):
        g, y = jnp.split(mm(ub, p["gate_up_proj.weight"]), 2, axis=-1)
        return mm(y * jax.nn.silu(g), p["down_proj.weight"])

    return in_blocks(part, POSITIONS, 1, u)


def under(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden_states(params, ids, cfg, rnd):
    """The final norm's output [b, s, hidden] of rows ``ids`` [b, s]."""
    import jax

    eps = cfg["layer_norm_eps"]
    x = params["model.embeddings.weight"][ids]
    memory = kv = None
    for i, (kind, layer) in enumerate(zip(cfg["layer_pattern"],
                                          cfg["held"]["layers"])):
        p = under(params, f"model.layers.{i}.")

        def mixer(x, memory, kv, p, kind=kind, layer=layer):
            u = layer_norm(x, p, "norm_mixer", eps)
            m = under(p, MIXER[kind] + ".")
            if kind == "M":
                out, memory = scan_mixer(u, m, cfg, rnd)
            elif kind == "G":
                out = memory_unit(u, memory, m, rnd)
            else:  # S and F attend over their own keys and values, C over F's
                read = kv if kind == "C" else keys_values(u, m, cfg, rnd)
                kv = read if kind == "F" else kv
                out = diff_attention(u, read, m, cfg, rnd, kind, layer)
            return x + out, memory, kv

        def feed(x, p):
            return x + mlp(layer_norm(x, p, "norm_mlp", eps), under(p, "mlp."),
                           rnd)

        x, memory, kv = jax.checkpoint(mixer)(x, memory, kv, p)
        x = jax.checkpoint(feed)(x, p)
    return layer_norm(x, params, "model.norm_f", eps)


def forward(params, ids, cfg, rnd):
    """Logits [b, s, rows held]."""
    import jax.numpy as jnp

    return jnp.matmul(rnd(hidden_states(params, ids, cfg, rnd)),
                      rnd(params["model.embeddings.weight"]).T)


def loss_fn(params, ids, labels, cfg, rnd, positions=None):
    """Mean next-token cross-entropy of rows ``ids`` [b, s] over the first
    ``positions`` positions (None: all), the logits formed a block of
    positions at a time."""
    import jax
    import jax.numpy as jnp

    x = hidden_states(params, ids, cfg, rnd)
    table = rnd(params["model.embeddings.weight"]).T

    def block(xb, gold_ids):
        logits = jnp.matmul(rnd(xb), table)
        gold = jnp.take_along_axis(logits, gold_ids, axis=-1)
        return jax.nn.logsumexp(logits, axis=-1, keepdims=True) - gold

    nll = in_blocks(block, POSITIONS, 1, x, labels[..., None])
    return jnp.mean(nll[:, :positions])


def train_readings(cfg, seed, batches, adam, blocks, precision="float32",
                   fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights, as
    ``reference/laguna.py`` does and by its rule: every step's loss and
    gradient are taken at the float32 master ROUNDED to the configuration's
    ``dtype`` (what the model's leaves hold), the update is the master's,
    with AdamW's moments on the host meanwhile; the same readings by leaf
    block. The faults: ``"half_batch"`` leaves the second half of every
    row's positions out of the loss (the cell has one row a step, so there
    is no half of the rows to leave out), ``"state_unchanged"`` makes no
    update."""
    import jax
    import jax.numpy as jnp

    rnd = operand_rounding(precision)
    dtype = jnp.dtype(cfg["dtype"])
    with jax.default_matmul_precision("highest"):
        params = initial_params(cfg, seed, dtype)
        # the leaves are made in the stated type by a call of their own: a
        # round trip inside one compiled function is the compiler's to drop
        leaves = jax.jit(lambda master: {k: v.astype(dtype)
                                         for k, v in master.items()})

        def value_and_grad(p, i, l, positions):
            return jax.value_and_grad(loss_fn)(
                {k: v.astype(jnp.float32) for k, v in p.items()}, i, l, cfg,
                rnd, positions)

        vg = jax.jit(value_and_grad, static_argnums=(3,))
        update = jax.jit(lambda p, g, s, t: adamw(p, g, s, t, adam),
                         donate_argnums=(0, 1, 2))
        norms = jax.jit(lambda a: block_norms(a, blocks))
        delta = jax.jit(lambda a, b: block_norms(
            {k: a[k] - b[k] for k in a}, blocks))
        first_state = jax.jit(lambda p: {
            k: (jnp.zeros_like(v), jnp.zeros_like(v)) for k, v in p.items()})
        state = None
        losses, grad_norms = [], None
        clock = time.perf_counter()
        for t, (ids, labels) in enumerate(batches, start=1):
            positions = (ids.shape[1] // 2 if fault == "half_batch" else None)
            at = leaves(params)
            loss, grads = vg(at, jnp.asarray(ids), jnp.asarray(labels),
                             positions)
            losses.append(float(loss))
            if t == 1:
                grad_norms = {k: float(v) for k, v in
                              jax.device_get(norms(grads)).items()}
            if fault != "state_unchanged":
                params, on_chip = update(
                    params, grads, first_state(params) if state is None
                    else jax.device_put(state), jnp.float32(t))
                state = jax.device_get(on_chip) if t < len(batches) else None
                del on_chip
            del grads, at
            print(f"reference: step {t} followed after "
                  f"{time.perf_counter() - clock:.1f} s", flush=True)
        if fault == "state_unchanged":
            grad_norms = dict.fromkeys(grad_norms, 0.0)
        update_norms = {k: float(v) for k, v in jax.device_get(delta(
            params, initial_params(cfg, seed, dtype))).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
