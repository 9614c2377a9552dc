"""Laguna: a pre-norm decoder (``x + Attn(RMSNorm(x))``, ``x +
FFN(RMSNorm(x))``, no bias, untied head) whose attention layers are of two
kinds in a period the configuration gives (``layer_types``) and whose FFN
is a dense SwiGLU in the leading layers and a routed SwiGLU MoE with a
shared expert after them (``mlp_layer_types``).

* **Attention** is grouped-query over ``num_key_value_heads`` heads of
  ``head_dim``, with a query-head count OF ITS OWN for each kind
  (``q_heads``: 48 on ``full_attention`` layers, 72 on
  ``sliding_attention`` ones), a rotary rule of its own for each kind
  (``rope``: full layers rotate half of each head by YaRN's blended
  frequencies with the tables scaled by the attention factor, sliding layers
  rotate the whole head plainly; pairs ``(i, i + r/2)`` of the rotated part,
  through ``fused_rotary_position_embedding``), and a gate on every head's
  output: ``out = concat_h(sigmoid(u W_g)_h a_h) W_o``. A sliding layer's
  query i sees keys j with ``0 <= i - j < sliding_window``. On the chip the
  packed ``causal_flash`` kernel runs, in its band regime on the sliding
  layers (``window_flash_fwd`` / ``window_flash_bwd``); elsewhere a masked
  softmax in ``jax.numpy``. Key/value heads are expanded to the query heads
  that read them before the kernel.
* **The MoE** takes ``p = softmax(u W_r)`` over ALL published experts in
  float32, chooses the k largest (equal entries by lowest index, as a dense
  mask: ``ops/pallas/topk_mask.py``), weighs ``scale p_e / sum over the
  chosen of p``, runs the SwiGLU experts held here at the model width over
  the dropless sorted buffer of ``models/routed_experts.py`` (shared with
  ``LatentMoE``) and adds the shared expert ungated.

Every layer can hold a SHARE of the published layer, as tensor and expert
parallelism divide it: attention some query heads of each kind and the
key/value heads they read (with the matching rows of ``W_o`` and columns of
``W_g``), the dense and shared MLPs some columns, the MoE some experts (the
router, k and the normalisation over all the chosen stay as published),
embedding and head some rows of the vocabulary. The widths are the
published ones either way; with everything held (the defaults) the same
code is the whole model. A share adds its partial result to the residual
and exchanges nothing: nothing here stands in for absent chips.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import apply_op
from . import routed_experts

__all__ = ["LagunaConfig", "LagunaForCausalLM", "LagunaModel", "LagunaBlock",
           "LagunaAttention", "LagunaMLP", "LagunaMoE", "rotary_tables"]

FULL, WINDOW = "full_attention", "sliding_attention"
# the key a block holds its mixer under, which is its scope in a trace
ATTN_KEY = {FULL: "attn_full", WINDOW: "attn_window"}
FFN_KEY = {"dense": "mlp", "sparse": "moe"}


def _published_rope():
    return {
        FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 128.0,
               "original_max_position_embeddings": 8192, "beta_slow": 1.0,
               "beta_fast": 32.0, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        WINDOW: {"rope_type": "default", "rope_theta": 10000.0,
                 "partial_rotary_factor": 1.0}}


@dataclass
class LagunaConfig:
    """Published sizes under the source's own names (``q_heads`` is
    ``num_attention_heads_per_layer`` by layer kind, ``rope`` is
    ``rope_parameters``); ``*_held`` say what of each layer lives here
    (None: all of it)."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    layer_types: tuple = (FULL, WINDOW, WINDOW, WINDOW) * 12
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 47
    q_heads: dict = field(default_factory=lambda: {FULL: 48, WINDOW: 72})
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope: dict = field(default_factory=_published_rope)
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # the MoE
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    moe_routed_scaling_factor: float = 2.5
    # the share held here
    vocab_rows_held: Optional[int] = None
    q_heads_held: Optional[dict] = None
    kv_heads_held: Optional[int] = None
    experts_held: Optional[int] = None
    first_expert: int = 0
    dense_width_held: Optional[int] = None
    shared_width_held: Optional[int] = None
    # rows of the buffer of local (token, expert) pairs, as a multiple of
    # what uniform routing sends here: tokens * k * held / experts
    local_pairs_bound: float = 3.0

    def __post_init__(self):
        for held, whole in (("vocab_rows_held", "vocab_size"),
                            ("kv_heads_held", "num_key_value_heads"),
                            ("experts_held", "num_experts"),
                            ("dense_width_held", "intermediate_size"),
                            ("shared_width_held",
                             "shared_expert_intermediate_size")):
            if getattr(self, held) is None:
                setattr(self, held, getattr(self, whole))
        if self.q_heads_held is None:
            self.q_heads_held = dict(self.q_heads)
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        if set(self.layer_types) - set(ATTN_KEY):
            raise ValueError(f"layer_types {set(self.layer_types)}: an "
                             f"attention layer is one of {sorted(ATTN_KEY)}")
        if set(self.mlp_layer_types) - set(FFN_KEY):
            raise ValueError(f"mlp_layer_types {set(self.mlp_layer_types)}: "
                             f"an FFN is one of {sorted(FFN_KEY)}")
        for kind in set(self.layer_types):
            if self.q_heads[kind] % self.num_key_value_heads:
                raise ValueError(f"{kind}: the query heads must divide over "
                                 "the key/value heads")
            group = self.q_heads[kind] // self.num_key_value_heads
            if self.q_heads_held[kind] != group * self.kv_heads_held:
                raise ValueError(
                    f"{kind}: the key/value heads held read {group} query "
                    f"heads each, so {group * self.kv_heads_held} are held "
                    f"with them, not {self.q_heads_held[kind]}")
        if self.first_expert + self.experts_held > self.num_experts:
            raise ValueError("experts held past the published count")


def _mm(a, w):
    """a @ w in a's type with float32 accumulation."""
    return jnp.dot(a, w.astype(a.dtype),
                   preferred_element_type=jnp.float32).astype(a.dtype)


def _swiglu(u, w_gate, w_up, w_down):
    """``(silu(u W_gate) * (u W_up)) W_down``."""
    return _mm(jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up), w_down)


# ------------------------------------------------------------------ rotary


def rotary_tables(rule: dict, head_dim: int, seq: int):
    """(sin, cos), float32 ``[seq, r]`` for positions 0 ... seq - 1, with
    ``r = partial_rotary_factor * head_dim`` the rotated part of a head and
    both halves of the last axis holding the r / 2 pairs' angles (pair i is
    dimensions ``(i, i + r / 2)``). ``rope_type`` ``default``:
    ``inv_freq_i = theta^(-2i / r)``. ``yarn``: below ``low`` the pair keeps
    that frequency, above ``high`` it is divided by ``factor``, between them
    the two are blended linearly, where ``low`` and ``high`` are the pairs
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    length; sin and cos are multiplied by ``attention_factor``."""
    r = int(rule["partial_rotary_factor"] * head_dim)
    inv = float(rule["rope_theta"]) ** (-np.arange(0, r, 2, dtype=np.float64)
                                        / r)
    scale = 1.0
    if rule["rope_type"] == "yarn":
        turns = lambda beta: (r * math.log(
            rule["original_max_position_embeddings"] / (2 * math.pi * beta))
            / (2 * math.log(rule["rope_theta"])))
        low = max(math.floor(turns(rule["beta_fast"])), 0)
        high = min(math.ceil(turns(rule["beta_slow"])), r - 1)
        ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv = inv * (1.0 - ramp) + inv / rule["factor"] * ramp
        scale = float(rule["attention_factor"])
    elif rule["rope_type"] != "default":
        raise ValueError(f"rope_type {rule['rope_type']!r}")
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    return ((np.sin(angle) * scale).astype(np.float32),
            (np.cos(angle) * scale).astype(np.float32))


def _rotate(x, sin, cos):
    """Heads ``[b, h, s, d]`` with their first ``sin.shape[-1]`` dimensions
    rotated and the rest passed through."""
    from ..incubate.nn.functional import fused_rotary_position_embedding

    b, h, s, d = x.shape
    r = sin.shape[-1]
    # the function's layout is [batch, seq, heads, dim]: every (row, head)
    # is a batch entry of one head, which is a reshape of what is here
    turned, _, _ = fused_rotary_position_embedding(
        x[..., :r].reshape(b * h, s, 1, r), sin=sin, cos=cos,
        use_neox_rotary_style=True)
    turned = turned._data.reshape(b, h, s, r)
    return turned if r == d else jnp.concatenate([turned, x[..., r:]], -1)


# --------------------------------------------------------------- attention


class LagunaAttention(nn.Layer):
    """Grouped-query attention of one ``kind``, holding
    ``q_heads_held[kind]`` query heads and the ``kv_heads_held`` key/value
    heads they read, with the kind's rotary rule, the window on a sliding
    layer, and the per-head sigmoid gate before ``W_o``."""

    def __init__(self, config: LagunaConfig, kind: str):
        super().__init__()
        c = config
        self.q_heads, self.kv_heads = c.q_heads_held[kind], c.kv_heads_held
        self.head_dim = c.head_dim
        self.window = c.sliding_window if kind == WINDOW else None
        self.rule = c.rope[kind]
        init = nn.initializer.Normal(std=c.initializer_range)
        lin = lambda i, o: nn.Linear(i, o, weight_attr=init, bias_attr=False)
        self.q_proj = lin(c.hidden_size, self.q_heads * c.head_dim)
        self.k_proj = lin(c.hidden_size, self.kv_heads * c.head_dim)
        self.v_proj = lin(c.hidden_size, self.kv_heads * c.head_dim)
        self.g_proj = lin(c.hidden_size, self.q_heads)
        self.o_proj = lin(self.q_heads * c.head_dim, c.hidden_size)

    def forward(self, u):
        return apply_op(self._attend, u, self.q_proj.weight,
                        self.k_proj.weight, self.v_proj.weight,
                        self.g_proj.weight, self.o_proj.weight)

    def _attend(self, u, wq, wk, wv, wg, wo):
        from ..ops.pallas import causal_flash

        b, s, _ = u.shape
        hq, hk, d = self.q_heads, self.kv_heads, self.head_dim
        f32 = jnp.float32

        def heads(w, n):  # [b, n, s, d]: the layout lands inside the product
            return jnp.einsum("bsi,ihd->bhsd", u,
                              w.reshape(-1, n, d).astype(u.dtype),
                              preferred_element_type=f32).astype(u.dtype)

        sin, cos = rotary_tables(self.rule, d, s)
        q = _rotate(heads(wq, hq), sin, cos)
        k = jnp.repeat(_rotate(heads(wk, hk), sin, cos), hq // hk, axis=1)
        v = jnp.repeat(heads(wv, hk), hq // hk, axis=1)
        if causal_flash.enabled(s, d, self.window):
            o = causal_flash.causal_flash_qkv(
                jnp.concatenate([q, k, v], axis=1), hq, d, window=self.window)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=f32) / math.sqrt(d)
            ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
            seen = ahead >= 0
            if self.window is not None:
                seen &= ahead < self.window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(u.dtype), v,
                           preferred_element_type=f32).astype(u.dtype)
        # [b, s, h], transposed afterwards: the CPU backend has no bfloat16
        # product whose output is transposed ("bsi,ih->bhs")
        gate = jax.nn.sigmoid(jnp.dot(u, wg.astype(u.dtype),
                                      preferred_element_type=f32))
        o = o * gate.transpose(0, 2, 1)[..., None].astype(u.dtype)
        return jnp.einsum("bhsd,hdo->bso", o,
                          wo.reshape(hq, d, -1).astype(u.dtype),
                          preferred_element_type=f32).astype(u.dtype)


# --------------------------------------------------------------------- FFNs


class LagunaMLP(nn.Layer):
    """SwiGLU holding ``width`` of its columns: the leading dense layers'
    FFN and the MoE layers' shared expert."""

    def __init__(self, config: LagunaConfig, width: int):
        super().__init__()
        init = nn.initializer.Normal(std=config.initializer_range)
        lin = lambda i, o: nn.Linear(i, o, weight_attr=init, bias_attr=False)
        self.gate_proj = lin(config.hidden_size, width)
        self.up_proj = lin(config.hidden_size, width)
        self.down_proj = lin(width, config.hidden_size)

    def forward(self, u):
        return apply_op(_swiglu, u, self.gate_proj.weight,
                        self.up_proj.weight, self.down_proj.weight)


class LagunaMoE(nn.Layer):
    """``p = softmax(u W_r)`` in float32 over all ``num_experts``; the k
    largest are chosen (equal entries to the lowest index); ``w_e = scale
    p_e / sum over the chosen of p``, on the expert's output; ``out = sum
    over chosen e of w_e (silu(u W1_e) * (u W3_e)) W2_e + shared(u)``. Holds
    experts ``first_expert ... + experts_held`` and ``shared_width_held``
    columns of the shared expert; the sum runs over the chosen experts held
    here, the k and their normalisation stay as published. No pair is left
    out whatever the routing sends here: the buffer of ``local_pairs_bound``
    times the uniform load takes the first pairs and further buffers of its
    size the rest (``routed_experts.mix_every_pair``). Under ``moe_stats_tap``
    each layer appends its routing counts."""

    def __init__(self, config: LagunaConfig):
        super().__init__()
        c = config
        self.experts, self.top_k = c.num_experts, c.num_experts_per_tok
        self.held, self.first = c.experts_held, c.first_expert
        self.scale, self.bound = (c.moe_routed_scaling_factor,
                                  c.local_pairs_bound)
        init = nn.initializer.Normal(std=c.initializer_range)
        hid, ff = c.hidden_size, c.moe_intermediate_size
        self.router = nn.Linear(hid, self.experts, weight_attr=init,
                                bias_attr=False)
        self.experts_gate = self.create_parameter([self.held, hid, ff],
                                                  default_initializer=init)
        self.experts_up = self.create_parameter([self.held, hid, ff],
                                                default_initializer=init)
        self.experts_down = self.create_parameter([self.held, ff, hid],
                                                  default_initializer=init)
        self.shared = LagunaMLP(c, c.shared_width_held)

    def buffer_rows(self, tokens: int) -> int:
        return routed_experts.buffer_rows(tokens, self.top_k, self.held,
                                          self.experts, self.bound)

    def forward(self, u):
        return apply_op(self._route_and_mix, u, self.router.weight,
                        self.experts_gate, self.experts_up,
                        self.experts_down) + self.shared(u)

    def _route_and_mix(self, u, w_r, w1, w3, w2):
        from ..ops.pallas.topk_mask import topk_mask
        b, s, hidden = u.shape
        ut = u.reshape(b * s, hidden)
        probs = jax.nn.softmax(jnp.dot(ut, w_r.astype(ut.dtype),
                                       preferred_element_type=jnp.float32))
        routed, w_local = routed_experts.held_weights(
            probs, topk_mask(probs, self.top_k), self.scale, self.first,
            self.held)
        mixed = routed_experts.mix_every_pair(
            routed, self.buffer_rows(b * s),
            b * s * min(self.held, self.top_k), ut, w_local, (w1, w3),
            lambda a, g: jax.nn.silu(a) * g, w2)
        return mixed.astype(u.dtype).reshape(b, s, hidden)


# -------------------------------------------------------------------- model


class LagunaBlock(nn.Layer):
    """``x + Attn(RMSNorm(x))``, then ``x + FFN(RMSNorm(x))``; the mixers
    sit under the keys of their kinds (``attn_full`` or ``attn_window``,
    ``mlp`` or ``moe``), which are their scopes in a device trace."""

    def __init__(self, config: LagunaConfig, attn_kind: str, ffn_kind: str):
        super().__init__()
        c = config
        self.norm_attn = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.norm_ffn = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.attn_key, self.ffn_key = ATTN_KEY[attn_kind], FFN_KEY[ffn_kind]
        setattr(self, self.attn_key, LagunaAttention(c, attn_kind))
        setattr(self, self.ffn_key,
                LagunaMLP(c, c.dense_width_held) if ffn_kind == "dense"
                else LagunaMoE(c))

    def forward(self, x):
        x = x + getattr(self, self.attn_key)(self.norm_attn(x))
        return x + getattr(self, self.ffn_key)(self.norm_ffn(x))


class LagunaModel(nn.Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        c = config
        init = nn.initializer.Normal(std=c.initializer_range)
        self.embeddings = nn.Embedding(c.vocab_rows_held, c.hidden_size,
                                       weight_attr=init)
        self.layers = nn.LayerList([
            LagunaBlock(c, a, f)
            for a, f in zip(c.layer_types, c.mlp_layer_types)])
        self.norm_f = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        for block in self.layers:
            x = block(x)
        return self.norm_f(x)


class LagunaForCausalLM(nn.Layer):
    """Logits over the vocabulary rows held here (a sliced vocabulary is a
    smaller vocabulary: ids, logits and loss are over the slice); the head
    is not tied to the embedding."""

    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.model = LagunaModel(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_rows_held, bias_attr=False,
            weight_attr=nn.initializer.Normal(std=config.initializer_range))

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))
