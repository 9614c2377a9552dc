"""Nemotron-H: a hybrid decoder whose every layer is ``x + mixer(RMSNorm(x))``
with the mixer one of a Mamba-2 state-space layer (``M``), grouped-query
attention (``*``) or a LatentMoE layer (``E``), in the order a pattern string
gives (the ``hybrid_override_pattern`` of the ``nemotron_h`` family).

Every mixer can hold a SHARE of the published layer, the way tensor and
expert parallelism divide it: a Mamba-2 mixer some of the heads and of the
B/C groups, attention some of the query heads and the key/value heads they
read, the LatentMoE some of the routed experts and a slice of the shared
expert, the embedding and head some rows of the vocabulary. The widths are
the published ones either way; with everything held (the defaults) the same
code is the whole model. A share adds its partial result to the residual
and exchanges nothing: nothing here stands in for absent chips.

* **Mamba-2** runs the chunked (SSD) form: products inside chunks of
  ``chunk_size`` positions on the matrix unit, and the float32 state
  ``[heads, head_dim, state]`` carried from chunk to chunk. On the chip,
  at shapes its tiles take, everything after the cumulative decay exponent
  is one Pallas kernel pair that walks the chunks with the state in VMEM
  (``ops/pallas/ssd_scan.py``); elsewhere the same mathematics in
  ``jax.numpy`` with a ``lax.scan`` over the chunk states, the kernel's twin
  in the tests. Any sequence length: the tail is padded with steps that
  neither decay nor add.
* **Attention** has no rotary embedding (the Mamba layers carry position);
  each key/value head is expanded to the query heads that read it and the
  packed ``causal_flash`` kernel runs on the chip (plain softmax elsewhere).
* **LatentMoE** routes over ALL published experts (sigmoid scores, a
  selection bias without gradient, top-k normalised over the chosen and
  scaled; the chosen set is ``jax.lax.top_k``'s, equal scores to the lowest
  index, found as a dense mask from each token's k-th largest score with
  no index sorted: ``ops/pallas/topk_mask.py``, a kernel on the chip and
  ``jax.numpy`` elsewhere), computes the experts held here in the latent
  width through ``jax.lax.ragged_dot`` over a static buffer of (token,
  expert) pairs sorted by expert (``models/routed_experts.py``, shared with
  ``models/laguna.py``), and drops nothing: the buffer is sized from a
  stated bound and the pairs over it are counted (``moe_stats_tap``), never
  hidden.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor, apply_op
from . import routed_experts
from .moe_stats import moe_stats_tap  # noqa: F401  the repo's one routing tap

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "NemotronHModel",
           "Mamba2Mixer", "NemotronHAttention", "LatentMoE", "ssd_chunked"]


@dataclass
class NemotronHConfig:
    """Published sizes under the source's own names; ``*_held`` say what of
    each layer lives here (None: all of it)."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "MEMEMEMEM*E"
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # LatentMoE
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    # the share held here
    vocab_rows_held: Optional[int] = None
    mamba_heads_held: Optional[int] = None
    mamba_groups_held: Optional[int] = None
    q_heads_held: Optional[int] = None
    kv_heads_held: Optional[int] = None
    experts_held: Optional[int] = None
    first_expert: int = 0
    shared_width_held: Optional[int] = None
    # rows of the buffer of local (token, expert) pairs, as a multiple of
    # what uniform routing sends here: tokens * k * held / experts (seeded
    # routers sent 8 of 512 experts up to 1.6 times that, PERF.md PR 28)
    local_pairs_bound: float = 3.0

    def __post_init__(self):
        for held, whole in (("vocab_rows_held", "vocab_size"),
                            ("mamba_heads_held", "mamba_num_heads"),
                            ("mamba_groups_held", "n_groups"),
                            ("q_heads_held", "num_attention_heads"),
                            ("kv_heads_held", "num_key_value_heads"),
                            ("experts_held", "n_routed_experts"),
                            ("shared_width_held",
                             "moe_shared_expert_intermediate_size")):
            if getattr(self, held) is None:
                setattr(self, held, getattr(self, whole))
        if set(self.pattern) - set("M*E"):
            raise ValueError(f"pattern {self.pattern!r}: layers are M, * or E")
        if self.mamba_heads_held % self.mamba_groups_held:
            raise ValueError("the Mamba heads held must divide over the "
                             "groups held")
        if self.q_heads_held % self.kv_heads_held:
            raise ValueError("the query heads held must divide over the "
                             "key/value heads held")
        if self.first_expert + self.experts_held > self.n_routed_experts:
            raise ValueError("experts held past the published count")


def _mm(a, w):
    """a @ w in a's type with float32 accumulation."""
    return jnp.dot(a, w.astype(a.dtype),
                   preferred_element_type=jnp.float32).astype(a.dtype)


def _rms(x, w, eps, groups=1):
    """RMSNorm over the last axis in ``groups`` equal parts, float32."""
    xf = x.astype(jnp.float32)
    parts = xf.reshape(*xf.shape[:-1], groups, xf.shape[-1] // groups)
    ms = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
    out = (parts * jax.lax.rsqrt(ms + eps)).reshape(xf.shape)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------ Mamba-2


def ssd_chunked(x, dt, a, bm, cm, chunk, d=None):
    """The state-space recurrence in its chunked matrix form.

    ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t`` with
    x ``[b, s, h, p]``, dt ``[b, s, h]`` (positive, float32), a ``[h]``
    (negative, float32), B and C ``[b, s, g, n]``; head ``i`` reads group
    ``i // (h / g)``. Returns y ``[b, s, h, p]`` float32, plus the skip
    ``d x`` where d ``[h]`` is given. Products take their operands in x's
    type and accumulate in float32; decays, the carried state ``[b, h, p,
    n]`` and the sums are float32. Every exponent is a sum of ``dt a`` over
    a span that runs forward in time, so none is positive.

    The padding, the chunked views and the cumulative sum ``acs`` are
    ``jax.numpy`` on every path. Where ``ssd_scan.enabled`` says so (the TPU
    backend; chunk and state multiples of 128, a group's heads filling whole
    lane tiles and fitting its VMEM budget) the rest, ``dt x`` and ``d x``
    included, is the kernel pair ``ssd_scan_fwd`` / ``ssd_scan_bwd``; the
    lines below it run otherwise, with the precisions the kernels keep."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    pad = -s % chunk
    if pad:  # steps with dt = 0 neither decay the state nor add to it
        x, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    nc, q = (s + pad) // chunk, chunk
    f32, lo = jnp.float32, x.dtype
    xc = x.reshape(b, nc, q, g, h // g, p)
    bc, cc = bm.reshape(b, nc, q, g, n), cm.reshape(b, nc, q, g, n)
    dtc = dt.astype(f32).reshape(b, nc, q, g, h // g)
    acs = jnp.cumsum(dtc * a.astype(f32).reshape(g, h // g), axis=2)
    from ..ops.pallas import ssd_scan

    if ssd_scan.enabled(q, n, h // g, p, jnp.dtype(lo).itemsize):
        # everything below, chunk by chunk with the state held in VMEM
        skip = jnp.zeros((h,), f32) if d is None else d.astype(f32)
        y = ssd_scan.ssd_scan(cc, bc, acs, dtc, xc, skip.reshape(g, h // g))
        return y.reshape(b, nc * q, h, p)[:, :s]
    acs_t = jnp.moveaxis(acs, 2, -1)                         # [b,c,g,e,q]
    xdt = (xc.astype(f32) * dtc[..., None]).astype(lo)       # dt_j x_j

    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(acs_i - acs_j) dt_j x_j
    scores = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                        preferred_element_type=f32)
    span = acs_t[..., :, None] - acs_t[..., None, :]         # [b,c,g,e,i,j]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), span,
                              -jnp.inf))
    y = jnp.einsum("bcgeij,bcjgep->bcigep",
                   (scores[:, :, :, None] * decay).astype(lo), xdt,
                   preferred_element_type=f32)

    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(acs[:, :, -1:] - acs)                   # [b,c,q,g,e]
    add = jnp.einsum("bcjgep,bcjgn->bcgepn",
                     (xdt.astype(f32) * to_end[..., None]).astype(lo), bc,
                     preferred_element_type=f32)
    whole = jnp.exp(acs[:, :, -1])                           # [b,c,g,e]

    def carry(state, inp):
        add_c, whole_c = inp
        return state * whole_c[..., None, None] + add_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, g, h // g, p, n), f32),
        (add.swapaxes(0, 1), whole.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                       # [b,c,g,e,p,n]
    # the state a chunk starts from, read by every position of the chunk
    y = y + jnp.exp(acs)[..., None] * jnp.einsum(
        "bcign,bcgepn->bcigep", cc, entering.astype(lo),
        preferred_element_type=f32)
    y = y.reshape(b, nc * q, h, p)
    if d is not None:
        y = y + d.astype(f32)[:, None] * x.astype(f32)
    return y[:, :s]


class Mamba2Mixer(nn.Layer):
    """``[z, xBC, dt] = u W_in``; ``xBC = silu(conv(xBC) + b)`` (causal,
    depthwise); the recurrence of ``ssd_chunked`` with ``dt = softplus(dt +
    dt_bias)`` and ``a = -exp(A_log)``, plus ``D x``; the gate
    ``y silu(z)`` BEFORE an RMSNorm taken per group's channels; ``W_out``.
    Holds ``mamba_heads_held`` heads and ``mamba_groups_held`` groups."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.heads, self.groups = c.mamba_heads_held, c.mamba_groups_held
        self.head_dim, self.state = c.mamba_head_dim, c.ssm_state_size
        self.chunk, self.eps = c.chunk_size, c.rms_eps
        inner = self.heads * self.head_dim
        self.inner = inner
        self.conv_dim = inner + 2 * self.groups * self.state
        init = nn.initializer.Normal(std=c.initializer_range)
        one = nn.initializer.Constant(1.0)
        self.in_proj = nn.Linear(c.hidden_size,
                                 inner + self.conv_dim + self.heads,
                                 weight_attr=init, bias_attr=False)
        self.conv1d_weight = self.create_parameter(
            [self.conv_dim, c.conv_kernel], default_initializer=init)
        self.conv1d_bias = self.create_parameter([self.conv_dim], is_bias=True)
        self.dt_bias = self.create_parameter([self.heads], is_bias=True)
        self.A_log = self.create_parameter([self.heads],
                                           default_initializer=one)
        self.D = self.create_parameter([self.heads], default_initializer=one)
        self.norm_weight = self.create_parameter([inner],
                                                 default_initializer=one)
        self.out_proj = nn.Linear(inner, c.hidden_size, weight_attr=init,
                                  bias_attr=False)

    def forward(self, u):
        return apply_op(self._mix, u, self.in_proj.weight, self.conv1d_weight,
                        self.conv1d_bias, self.dt_bias, self.A_log, self.D,
                        self.norm_weight, self.out_proj.weight)

    def _mix(self, u, w_in, w_conv, b_conv, dt_bias, a_log, d, w_norm, w_out):
        b, s, _ = u.shape
        h, g, p, n = self.heads, self.groups, self.head_dim, self.state
        f32 = jnp.float32
        z, xbc, dt = jnp.split(_mm(u, w_in),
                               [self.inner, self.inner + self.conv_dim], -1)
        k = w_conv.shape[1]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0))).astype(f32)
        conv = sum(padded[:, i:i + s] * w_conv[:, i].astype(f32)
                   for i in range(k)) + b_conv.astype(f32)
        xbc = jax.nn.silu(conv).astype(u.dtype)
        x, bm, cm = jnp.split(xbc, [self.inner, self.inner + g * n], -1)
        x = x.reshape(b, s, h, p)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        y = ssd_chunked(x, dt, -jnp.exp(a_log.astype(f32)),
                        bm.reshape(b, s, g, n), cm.reshape(b, s, g, n),
                        self.chunk, d)
        y = y.reshape(b, s, self.inner) * jax.nn.silu(z.astype(f32))
        return _mm(_rms(y, w_norm, self.eps, groups=g).astype(u.dtype), w_out)


# ---------------------------------------------------------------- attention


class NemotronHAttention(nn.Layer):
    """Causal grouped-query attention without rotary embedding or bias,
    holding ``q_heads_held`` query heads and the ``kv_heads_held`` key/value
    heads they read."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.q_heads, self.kv_heads = c.q_heads_held, c.kv_heads_held
        self.head_dim = c.head_dim
        init = nn.initializer.Normal(std=c.initializer_range)
        lin = lambda i, o: nn.Linear(i, o, weight_attr=init, bias_attr=False)
        self.q_proj = lin(c.hidden_size, self.q_heads * c.head_dim)
        self.k_proj = lin(c.hidden_size, self.kv_heads * c.head_dim)
        self.v_proj = lin(c.hidden_size, self.kv_heads * c.head_dim)
        self.o_proj = lin(self.q_heads * c.head_dim, c.hidden_size)

    def forward(self, u):
        return apply_op(self._attend, u, self.q_proj.weight,
                        self.k_proj.weight, self.v_proj.weight,
                        self.o_proj.weight)

    def _attend(self, u, wq, wk, wv, wo):
        from ..ops.pallas import causal_flash

        b, s, _ = u.shape
        hq, hk, d = self.q_heads, self.kv_heads, self.head_dim
        f32 = jnp.float32

        def heads(w, n):  # [b, n, s, d]: the layout lands inside the product
            return jnp.einsum("bsi,ihd->bhsd", u,
                              w.reshape(-1, n, d).astype(u.dtype),
                              preferred_element_type=f32).astype(u.dtype)

        q = heads(wq, hq)
        k, v = (jnp.repeat(heads(w, hk), hq // hk, axis=1) for w in (wk, wv))
        if causal_flash.enabled(s, d):
            o = causal_flash.causal_flash_qkv(
                jnp.concatenate([q, k, v], axis=1), hq, d)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=f32) / math.sqrt(d)
            causal = jnp.tril(jnp.ones((s, s), bool))
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(u.dtype), v,
                           preferred_element_type=f32).astype(u.dtype)
        return jnp.einsum("bhsd,hdo->bso", o,
                          wo.reshape(hq, d, -1).astype(u.dtype),
                          preferred_element_type=f32).astype(u.dtype)


# ---------------------------------------------------------------- LatentMoE


class LatentMoE(nn.Layer):
    """``s = sigmoid(u W_r)``; the k largest of ``s + bias`` are chosen
    (the bias is a buffer: it selects, carries no gradient and does not
    weigh). Equal scores go to the lowest index, ``jax.lax.top_k``'s order,
    and the set is found by threshold: the entries above a token's k-th
    largest and as many of those equal to it as fill the k places, a
    boolean ``[tokens, experts]`` mask and never an index (``topk_mask``);
    ``w_e = scale * s_e / sum over the chosen of s``; the experts
    work in the latent width between two shared projections: ``out = (sum
    over chosen e of w_e relu(l W1_e)^2 W2_e) W_up + relu(u Ws1)^2 Ws2``
    with ``l = u W_dn``. Holds experts ``first_expert ... + experts_held``
    and ``shared_width_held`` columns of the shared expert; the sum runs
    over the chosen experts held here, the k and their normalisation stay
    as published. Traced under ``moe_stats_tap`` (``models/moe_stats.py``), each
    layer appends ``[pairs routed to held experts, tokens with none of
    them, pairs over the buffer, rows walked]`` (float32) to the tap's list, for the
    caller to thread out of the traced function as an output. The bias's
    load-balancing update belongs to the training loop (a buffer update
    between steps); nothing here runs it."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.experts, self.top_k = c.n_routed_experts, c.num_experts_per_tok
        self.held, self.first = c.experts_held, c.first_expert
        self.scale, self.bound = c.routed_scaling_factor, c.local_pairs_bound
        init = nn.initializer.Normal(std=c.initializer_range)
        lin = lambda i, o: nn.Linear(i, o, weight_attr=init, bias_attr=False)
        lat, ff = c.moe_latent_size, c.moe_intermediate_size
        self.router = lin(c.hidden_size, self.experts)
        self.register_buffer("e_score_correction_bias",
                             Tensor(jnp.zeros((self.experts,), jnp.float32)))
        self.latent_down = lin(c.hidden_size, lat)
        self.latent_up = lin(lat, c.hidden_size)
        self.experts_w1 = self.create_parameter([self.held, lat, ff],
                                                default_initializer=init)
        self.experts_w2 = self.create_parameter([self.held, ff, lat],
                                                default_initializer=init)
        self.shared_up = lin(c.hidden_size, c.shared_width_held)
        self.shared_down = lin(c.shared_width_held, c.hidden_size)

    def buffer_rows(self, tokens: int) -> int:
        """Rows of the local pairs' buffer: ``local_pairs_bound`` times the
        pairs uniform routing sends here, at most one a token and expert."""
        return routed_experts.buffer_rows(tokens, self.top_k, self.held,
                                          self.experts, self.bound)

    def forward(self, u):
        return apply_op(self._route_and_mix, u, self.router.weight,
                        self.e_score_correction_bias, self.latent_down.weight,
                        self.latent_up.weight, self.experts_w1,
                        self.experts_w2, self.shared_up.weight,
                        self.shared_down.weight)

    def _route_and_mix(self, u, w_r, bias, w_dn, w_up, w1, w2, ws1, ws2):
        from ..ops.pallas.topk_mask import topk_mask
        b, s, hidden = u.shape
        t, f32 = b * s, jnp.float32
        ut = u.reshape(t, hidden)
        relu2 = lambda a: jnp.square(jax.nn.relu(a))

        scores = jax.nn.sigmoid(jnp.dot(ut, w_r.astype(ut.dtype),
                                        preferred_element_type=f32))
        # [t, experts]: which experts each token chose, found by threshold.
        # A mask, not indices: everything the weights need is dense
        picked = topk_mask(scores + bias.astype(f32), self.top_k)
        routed, w_local = routed_experts.held_weights(
            scores, picked, self.scale, self.first, self.held)
        # the held experts over the sorted buffer of local pairs, in the
        # latent width (``models/routed_experts.py``)
        pairs = routed_experts.sort_pairs(routed, self.buffer_rows(t))
        latent = _mm(ut, w_dn)
        mixed = routed_experts.mix(pairs, latent, routed, w_local, (w1,),
                                   relu2, w2)
        out = _mm(mixed.astype(ut.dtype), w_up) + _mm(
            relu2(_mm(ut, ws1)), ws2)
        return out.reshape(b, s, hidden)


# -------------------------------------------------------------------- model


class NemotronHBlock(nn.Layer):
    """``x + mixer(RMSNorm(x))``; the mixer sits under the key of its kind
    (``mamba``, ``attn``, ``moe``), which is its scope in a device trace."""

    KINDS = {"M": ("mamba", Mamba2Mixer), "*": ("attn", NemotronHAttention),
             "E": ("moe", LatentMoE)}

    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_eps)
        self.key, mixer = self.KINDS[kind]
        setattr(self, self.key, mixer(config))

    def forward(self, x):
        return x + getattr(self, self.key)(self.norm(x))


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        init = nn.initializer.Normal(std=config.initializer_range)
        self.embeddings = nn.Embedding(config.vocab_rows_held,
                                       config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([NemotronHBlock(config, kind)
                                    for kind in config.pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size, epsilon=config.rms_eps)

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        for block in self.layers:
            x = block(x)
        return self.norm_f(x)


class NemotronHForCausalLM(nn.Layer):
    """Logits over the vocabulary rows held here (a sliced vocabulary is a
    smaller vocabulary: ids, logits and loss are over the slice); the head
    is not tied to the embedding."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_rows_held, bias_attr=False,
            weight_attr=nn.initializer.Normal(std=config.initializer_range))

    def forward(self, input_ids):
        return self.lm_head(self.backbone(input_ids))
