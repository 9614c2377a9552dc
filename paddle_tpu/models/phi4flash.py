"""Phi-4-flash (``model_type`` ``phi4flash``): a decoder-hybrid-decoder.
Every block is ``x += mixer(LayerNorm(x)); x += MLP(LayerNorm(x))``
(LayerNorm with scale and bias), the final norm a LayerNorm, the head tied
to the embedding, and no positional encoding anywhere. The mixer is one of
five kinds, given a letter a layer by ``layer_pattern``:

* ``M``, a Mamba-1 selective scan: ``[x, z] = u W_in``; ``x =
  silu(causal_conv(x) + b_conv)``; ``[r, B, Cm] = x W_x``; ``delta =
  softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(delta_t (x) A)
  h_{t-1} + (delta_t x_t) (x) B_t``; ``y_t = h_t . Cm_t + D x_t`` (the
  recurrence in float32: ``ops/pallas/selective_scan.py``, a kernel pair on
  the chip and a chunked ``lax.scan`` elsewhere); ``out = (y silu(z))
  W_out``. It also hands on ``y`` (before the gate) as the MEMORY.
* ``S`` / ``F``, differential attention under a window (query i sees keys j
  with ``0 <= i - j < sliding_window``) / over every ``j <= i``. Heads pair
  up, (2p, 2p + 1): ``a_1 = softmax(q_2p k_2p^T / sqrt(d))``, ``a_2`` of the
  odd heads, ``V_p = [v_2p | v_2p+1]`` (2 d wide); ``o_p = (a_1 - lambda
  a_2) V_p`` with ``lambda = exp(lq_1 . lk_1) - exp(lq_2 . lk_2) +
  lambda_init`` and ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at the layer's
  PUBLISHED index l; ``o_p = RMSNorm_2d(o_p) (1 - lambda_init)``; the pairs
  side by side through ``W_o``. Grouped queries: ``q_heads / kv_heads``
  query pairs read one key/value pair. Biases on q, k, v and o. An ``F``
  layer also hands on its KEYS AND VALUES (after their projection).
* ``G``, a gated memory unit: ``out = (silu(u W_g) * m) W_o'`` with m the
  memory of the nearest ``M`` layer before it.
* ``C``, cross attention: differential attention whose queries are this
  layer's (``W_q``, its own lambdas, norm and ``W_o``) and whose keys and
  values are the nearest ``F`` layer's, the same arrays.

So blocks are not ``x -> x``: a block takes and returns ``(x, memory, kv)``,
and what layers ``M`` and ``F`` of the boundary make receives gradient from
every ``G`` and ``C`` layer that reads it.

On the chip each query head is one softmax over d-wide q, k and the pair's
2 d-wide V, which is ``causal_flash_qkv`` at ``head_dim = 2 d`` with q and k
zero-padded to 2 d lanes (q times sqrt 2: the kernel scales by 1 / sqrt(2
d)): the tiled causal regime on ``F`` and ``C`` layers, the band regime on
``S`` layers. Elsewhere a masked softmax in ``jax.numpy``.

Every layer can hold a SHARE of the published layer, as tensor parallelism
divides it: attention some key/value pairs with the query pairs that read
them (and the matching rows of ``W_o``), the scan and the memory unit some
channels (``W_in`` columns, the convolution, ``W_x`` ROWS, ``W_dt`` columns,
``A_log``, ``D``, ``W_out`` rows; ``W_g`` columns, ``W_o'`` rows), the MLP
some columns, embedding and head some rows of the vocabulary. The widths are
the published ones either way; with everything held (the defaults) the same
code is the whole model. A share adds its partial result to the residual and
exchanges nothing: ``[r, B, Cm] = x W_x`` is the held channels' part of that
sum, and nothing here stands in for absent chips.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import apply_op

__all__ = ["Phi4FlashConfig", "Phi4FlashForCausalLM", "Phi4FlashModel",
           "Phi4FlashBlock", "SelectiveScanMixer", "DiffAttention",
           "GatedMemoryUnit", "Phi4FlashMLP", "lambda_init"]

# the key a block holds its mixer under, which is its scope in a trace
MIXER_KEY = {"M": "mamba", "S": "attn_window", "F": "attn_full",
             "G": "gmu", "C": "attn_cross"}
ATTN_KIND = {"S": "window", "F": "full", "C": "cross"}
# the kind of layer whose output a layer reads besides x
READS = {"G": "M", "C": "F"}


@dataclass
class Phi4FlashConfig:
    """Published sizes under the source's own names where it has one;
    ``*_held`` say what of each layer lives here (None: all of it) and
    ``layer_indices`` the published index of each layer of ``layer_pattern``
    (None: 0, 1, 2, ...)."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    layer_pattern: str = "MS" * 8 + "MF" + "GC" * 7
    layer_indices: Optional[tuple] = None
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    # the scan
    ssm_state_size: int = 16
    conv_kernel: int = 4
    expand: int = 2
    dt_rank: int = 160
    # the share held here
    vocab_rows_held: Optional[int] = None
    q_heads_held: Optional[int] = None
    kv_heads_held: Optional[int] = None
    scan_channels_held: Optional[int] = None
    mlp_columns_held: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def scan_channels(self) -> int:
        return self.expand * self.hidden_size

    def __post_init__(self):
        for held, whole in (("vocab_rows_held", "vocab_size"),
                            ("q_heads_held", "num_attention_heads"),
                            ("kv_heads_held", "num_key_value_heads"),
                            ("scan_channels_held", "scan_channels"),
                            ("mlp_columns_held", "intermediate_size")):
            if getattr(self, held) is None:
                setattr(self, held, getattr(self, whole))
        if set(self.layer_pattern) - set(MIXER_KEY):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a layer "
                             f"is one of {sorted(MIXER_KEY)}")
        if self.layer_indices is None:
            self.layer_indices = tuple(range(len(self.layer_pattern)))
        self.layer_indices = tuple(self.layer_indices)
        if len(self.layer_indices) != len(self.layer_pattern):
            raise ValueError("layer_indices and layer_pattern differ in "
                             "length")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the query heads must divide over the "
                             "key/value heads")
        group = self.num_attention_heads // self.num_key_value_heads
        if self.kv_heads_held % 2 or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs the key/value "
                             "heads: an even number is held")
        if self.q_heads_held != group * self.kv_heads_held:
            raise ValueError(
                f"the key/value heads held are read by {group} query heads "
                f"each, so {group * self.kv_heads_held} are held with them, "
                f"not {self.q_heads_held}")
        for at, kind in enumerate(self.layer_pattern):
            if READS.get(kind, kind) not in self.layer_pattern[:at + 1]:
                raise ValueError(f"layer_pattern {self.layer_pattern!r}: a "
                                 f"{kind} layer before the layer it reads")


def lambda_init(layer_index: int) -> float:
    """Differential attention's constant at a layer's published index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


def _mm(a, w):
    """a @ w in a's type with float32 accumulation."""
    return jnp.dot(a, w.astype(a.dtype),
                   preferred_element_type=jnp.float32).astype(a.dtype)


# ------------------------------------------------------------------ the scan


def scan_inputs(u, w_in, w_conv, b_conv):
    """``[x, z] = u W_in``; ``x = silu(causal_conv(x) + b_conv)``. Both
    ``[b, s, channels]`` in u's type."""
    b, s, _ = u.shape
    f32 = jnp.float32
    x, z = jnp.split(_mm(u, w_in), 2, axis=-1)
    k = w_conv.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(f32)
    conv = sum(padded[:, i:i + s] * w_conv[:, i].astype(f32)
               for i in range(k)) + b_conv.astype(f32)
    return jax.nn.silu(conv).astype(u.dtype), z


def scan_outputs(x, z, rbc, w_dt, b_dt, a_log, d, w_out):
    """From ``[r, B, Cm]`` (``rbc``, summed over the chips that share the
    layer where there are several) on: the step, the recurrence, the gate
    and ``W_out``. Returns (the layer's part of the residual, ``y``)."""
    from ..ops.pallas.selective_scan import selective_scan

    f32 = jnp.float32
    rank, states = w_dt.shape[0], a_log.shape[1]
    r, bm, cm = jnp.split(rbc, [rank, rank + states], axis=-1)
    delta = jax.nn.softplus(
        jnp.dot(r, w_dt.astype(r.dtype), preferred_element_type=f32)
        + b_dt.astype(f32))
    y = selective_scan(x, delta, -jnp.exp(a_log.astype(f32)), bm, cm,
                       d.astype(f32)).astype(x.dtype)
    gated = y.astype(f32) * jax.nn.silu(z.astype(f32))
    return _mm(gated.astype(x.dtype), w_out), y


class SelectiveScanMixer(nn.Layer):
    """The ``M`` layer over ``scan_channels_held`` channels. Returns ``(out,
    y)``: its part of the residual and the scan's output before the gate."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = config
        ch, n = c.scan_channels_held, c.ssm_state_size
        init = nn.initializer.Normal(std=c.initializer_range)
        one = nn.initializer.Constant(1.0)
        self.in_proj = nn.Linear(c.hidden_size, 2 * ch, weight_attr=init,
                                 bias_attr=False)
        self.conv1d_weight = self.create_parameter(
            [ch, c.conv_kernel], default_initializer=init)
        self.conv1d_bias = self.create_parameter([ch], is_bias=True)
        self.x_proj = nn.Linear(ch, c.dt_rank + 2 * n, weight_attr=init,
                                bias_attr=False)
        self.dt_proj = nn.Linear(c.dt_rank, ch, weight_attr=init)
        self.A_log = self.create_parameter([ch, n], default_initializer=init)
        self.D = self.create_parameter([ch], default_initializer=one)
        self.out_proj = nn.Linear(ch, c.hidden_size, weight_attr=init,
                                  bias_attr=False)

    def forward(self, u):
        return apply_op(self._mix, u, self.in_proj.weight, self.conv1d_weight,
                        self.conv1d_bias, self.x_proj.weight,
                        self.dt_proj.weight, self.dt_proj.bias, self.A_log,
                        self.D, self.out_proj.weight)

    @staticmethod
    def _mix(u, w_in, w_conv, b_conv, w_x, w_dt, b_dt, a_log, d, w_out):
        x, z = scan_inputs(u, w_in, w_conv, b_conv)
        return scan_outputs(x, z, _mm(x, w_x), w_dt, b_dt, a_log, d, w_out)


class GatedMemoryUnit(nn.Layer):
    """The ``G`` layer: ``(silu(u W_g) * m) W_o'`` over the channels held of
    the memory m ``[b, s, scan_channels_held]``."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = config
        init = nn.initializer.Normal(std=c.initializer_range)
        self.in_proj = nn.Linear(c.hidden_size, c.scan_channels_held,
                                 weight_attr=init, bias_attr=False)
        self.out_proj = nn.Linear(c.scan_channels_held, c.hidden_size,
                                  weight_attr=init, bias_attr=False)

    def forward(self, u, memory):
        return apply_op(self._gate, u, memory, self.in_proj.weight,
                        self.out_proj.weight)

    @staticmethod
    def _gate(u, m, w_g, w_o):
        f32 = jnp.float32
        gated = jax.nn.silu(_mm(u, w_g).astype(f32)) * m.astype(f32)
        return _mm(gated.astype(u.dtype), w_o)


# --------------------------------------------------------------- attention


def _expand_to_queries(k, v, q_heads, d):
    """Keys ``[b, q_heads, s, d]`` and value pairs ``[b, q_heads, s, 2 d]``
    as each query head reads them, from k, v ``[b, s, kv_heads d]``: query
    head h of pair P = h // 2 reads key head ``2 (P // g) + h % 2`` and the
    pair ``P // g``'s values, g query pairs a key/value pair."""
    b, s, width = k.shape
    pairs = width // (2 * d)
    g = q_heads // (2 * pairs)
    k = k.reshape(b, s, pairs, 1, 2, d).transpose(0, 2, 3, 4, 1, 5)
    k = jnp.broadcast_to(k, (b, pairs, g, 2, s, d))
    v = v.reshape(b, s, pairs, 1, 2 * d).transpose(0, 2, 3, 1, 4)
    v = jnp.broadcast_to(v, (b, pairs, 2 * g, s, 2 * d))
    return k.reshape(b, q_heads, s, d), v.reshape(b, q_heads, s, 2 * d)


def softmax_heads(q, k, v, window):
    """Every query head's ``softmax(q k^T / sqrt(d)) V`` under the causal
    mask (and the window): q ``[b, h, s, d]``, k, v ``[b, s, kv_heads d]``.
    Returns ``[b, h, s, 2 d]``. The packed ``causal_flash`` kernel where it
    takes the shape (heads of 2 d lanes: q and k zero-padded, q times sqrt 2
    for the kernel's 1 / sqrt(2 d)), else a masked softmax."""
    from ..ops.pallas import causal_flash

    b, h, s, d = q.shape
    f32 = jnp.float32
    k, v = _expand_to_queries(k, v, h, d)
    if causal_flash.enabled(s, 2 * d, window):
        wide = lambda t: jnp.pad(t, ((0, 0),) * 3 + ((0, d),))
        q = (q.astype(f32) * math.sqrt(2.0)).astype(q.dtype)
        return causal_flash.causal_flash_qkv(
            jnp.concatenate([wide(q), wide(k), v], axis=1), h, 2 * d,
            window=window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=f32) / math.sqrt(d)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v,
                      preferred_element_type=f32).astype(q.dtype)


def differential(a, lambda_q, lambda_k, w_norm, lam_init, eps):
    """``a`` ``[b, h, s, 2 d]``, the heads' softmax outputs: ``o_p = a_2p -
    lambda a_2p+1``, RMSNorm over the 2 d with scale, times ``1 -
    lambda_init``. Returns ``[b, s, h d]``, the pairs side by side."""
    b, h, s, wide = a.shape
    f32 = jnp.float32
    lq, lk = lambda_q.astype(f32), lambda_k.astype(f32)
    lam = (jnp.exp(jnp.sum(lq[0] * lk[0])) - jnp.exp(jnp.sum(lq[1] * lk[1]))
           + lam_init)
    a = a.astype(f32).reshape(b, h // 2, 2, s, wide)
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * w_norm.astype(f32) * (1.0 - lam_init)
    return o.transpose(0, 2, 1, 3).reshape(b, s, (h // 2) * wide)


class DiffAttention(nn.Layer):
    """Differential attention of one ``kind``: ``window`` and ``full``
    project q, k, v and return ``(out, (k, v))``; ``cross`` projects q only
    and attends over the ``(k, v)`` it is given. ``layer_index`` is the
    layer's published index (``lambda_init``)."""

    def __init__(self, config: Phi4FlashConfig, kind: str, layer_index: int):
        super().__init__()
        c = config
        self.kind, self.eps = kind, c.layer_norm_eps
        self.q_heads, self.head_dim = c.q_heads_held, c.head_dim
        self.window = c.sliding_window if kind == "window" else None
        self.lambda_init = lambda_init(layer_index)
        init = nn.initializer.Normal(std=c.initializer_range)
        lin = lambda i, o: nn.Linear(i, o, weight_attr=init)
        self.q_proj = lin(c.hidden_size, self.q_heads * c.head_dim)
        if kind != "cross":
            self.k_proj = lin(c.hidden_size, c.kv_heads_held * c.head_dim)
            self.v_proj = lin(c.hidden_size, c.kv_heads_held * c.head_dim)
        # (lq_1, lq_2) and (lk_1, lk_2): two-dimensional leaves, so that a
        # seeding rule for matrices gives them small values
        self.lambda_q = self.create_parameter([2, c.head_dim],
                                              default_initializer=init)
        self.lambda_k = self.create_parameter([2, c.head_dim],
                                              default_initializer=init)
        self.subln = nn.RMSNorm(2 * c.head_dim, epsilon=c.layer_norm_eps)
        self.o_proj = lin(self.q_heads * c.head_dim, c.hidden_size)

    def forward(self, u, kv=None):
        if self.kind != "cross":
            kv = apply_op(self._keys_values, u, self.k_proj.weight,
                          self.k_proj.bias, self.v_proj.weight,
                          self.v_proj.bias)
        out = apply_op(self._attend, u, kv[0], kv[1], self.q_proj.weight,
                       self.q_proj.bias, self.lambda_q, self.lambda_k,
                       self.subln.weight, self.o_proj.weight,
                       self.o_proj.bias)
        return out, kv

    @staticmethod
    def _keys_values(u, wk, bk, wv, bv):
        return _mm(u, wk) + bk.astype(u.dtype), _mm(u, wv) + bv.astype(u.dtype)

    def _attend(self, u, k, v, wq, bq, lq, lk, w_norm, wo, bo):
        b, s, _ = u.shape
        h, d = self.q_heads, self.head_dim
        f32 = jnp.float32
        # [b, h, s, d]: the layout lands inside the product
        q = jnp.einsum("bsi,ihd->bhsd", u, wq.reshape(-1, h, d).astype(u.dtype),
                       preferred_element_type=f32)
        q = (q + bq.astype(f32).reshape(h, 1, d)).astype(u.dtype)
        o = differential(softmax_heads(q, k, v, self.window), lq, lk, w_norm,
                         self.lambda_init, self.eps)
        return _mm(o.astype(u.dtype), wo) + bo.astype(u.dtype)


# -------------------------------------------------------------------- model


class Phi4FlashMLP(nn.Layer):
    """``[g, y] = u W_1``; ``(y silu(g)) W_2``, no bias, holding
    ``mlp_columns_held`` of the gate's and of the up projection's columns."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = config
        init = nn.initializer.Normal(std=c.initializer_range)
        self.gate_up_proj = nn.Linear(c.hidden_size, 2 * c.mlp_columns_held,
                                      weight_attr=init, bias_attr=False)
        self.down_proj = nn.Linear(c.mlp_columns_held, c.hidden_size,
                                   weight_attr=init, bias_attr=False)

    def forward(self, u):
        return apply_op(self._swiglu, u, self.gate_up_proj.weight,
                        self.down_proj.weight)

    @staticmethod
    def _swiglu(u, w1, w2):
        g, y = jnp.split(_mm(u, w1), 2, axis=-1)
        return _mm(y * jax.nn.silu(g), w2)


class Phi4FlashBlock(nn.Layer):
    """One layer of kind ``M``, ``S``, ``F``, ``G`` or ``C``; the mixer sits
    under the key of its kind (``mamba``, ``attn_window``, ``attn_full``,
    ``gmu``, ``attn_cross``), which is its scope in a device trace. Takes
    and returns ``(x, memory, kv)``: an ``M`` layer replaces the memory, an
    ``F`` layer the keys and values; ``G`` and ``C`` read them."""

    def __init__(self, config: Phi4FlashConfig, kind: str, layer_index: int):
        super().__init__()
        c = config
        self.kind, self.mixer_key = kind, MIXER_KEY[kind]
        self.norm_mixer = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.norm_mlp = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        if kind == "M":
            mixer = SelectiveScanMixer(c)
        elif kind == "G":
            mixer = GatedMemoryUnit(c)
        else:
            mixer = DiffAttention(c, ATTN_KIND[kind], layer_index)
        setattr(self, self.mixer_key, mixer)
        self.mlp = Phi4FlashMLP(c)

    def forward(self, x, memory=None, kv=None):
        mixer, u = getattr(self, self.mixer_key), self.norm_mixer(x)
        if self.kind == "M":
            out, memory = mixer(u)
        elif self.kind == "G":
            out = mixer(u, memory)
        elif self.kind == "C":
            out, _ = mixer(u, kv)
        else:
            out, made = mixer(u)
            if self.kind == "F":
                kv = made
        x = x + out
        return x + self.mlp(self.norm_mlp(x)), memory, kv


class Phi4FlashModel(nn.Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = config
        init = nn.initializer.Normal(std=c.initializer_range)
        self.embeddings = nn.Embedding(c.vocab_rows_held, c.hidden_size,
                                       weight_attr=init)
        self.layers = nn.LayerList([
            Phi4FlashBlock(c, kind, index)
            for kind, index in zip(c.layer_pattern, c.layer_indices)])
        self.norm_f = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)

    def forward(self, input_ids):
        x, memory, kv = self.embeddings(input_ids), None, None
        for block in self.layers:
            x, memory, kv = block(x, memory, kv)
        return self.norm_f(x)


class Phi4FlashForCausalLM(nn.Layer):
    """Logits over the vocabulary rows held here (a sliced vocabulary is a
    smaller vocabulary: ids, logits and loss are over the slice), by the
    embedding's own table."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)

    def forward(self, input_ids):
        x = self.model(input_ids)
        # the tied head sits in no sublayer: a scope of its own, so its
        # operations (and their gradients) are found in a device trace
        with jax.named_scope("lm_head"):
            return apply_op(
                lambda a, table: jnp.einsum("bsh,vh->bsv", a,
                                            table.astype(a.dtype)),
                x, self.model.embeddings.weight)
