"""LLaMA-family decoder-only transformer (RMSNorm + rotary embeddings +
SwiGLU + grouped-query attention).

Reference capability: the PaddleNLP llama model family served through the
same fused stack the survey maps (fused_multi_transformer_op.cu with GQA
decode, paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu,
rms_norm_kernel.cu — SURVEY.md A3.x). TPU-native design mirrors models/gpt:

* pre-RMSNorm blocks; rotary q/k via the shared fused_rotary helper
  (position_ids-aware, so decode steps rotate at their true positions);
* training/prefill attention through the Pallas flash kernel — GQA expands
  k/v head groups before the kernel (compute-equivalent, standard TPU
  practice); decode uses the Pallas decode kernel's NATIVE GQA path
  (q head h reads kv head h // group) over the reference cache layout
  [2, b, n_kv_heads, max_seq, head_dim];
* SwiGLU MLP (gate ⊙ silu(up) — llama convention: down(silu(gate) * up));
* untied LM head (llama convention), generation via GenerationMixin.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..framework.tensor import Tensor, apply_op
from . import moe_stats
from .generation import GenerationMixin
from .moe_stats import moe_stats_tap

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama2_7b",
           "tiny_llama_config", "tiny_moe_llama_config", "LlamaMoEMLP",
           "moe_stats_tap", "moe_stats_size"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32  # < num_heads → grouped-query attention
    intermediate_size: int = 11008
    max_position: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    initializer_range: float = 0.02
    use_flash: bool = True
    # MoE (ISSUE 17): num_experts > 0 swaps every block's MLP for a
    # top-k routed expert FFN (LlamaMoEMLP). moe_intermediate_size is
    # the PER-EXPERT FF width (0 → intermediate_size); active params per
    # token are moe_top_k * moe_intermediate_size vs the dense MLP's
    # intermediate_size. capacity_factor sizes the static per-expert
    # token budget C = ceil(cf * top_k * T / E); overflow pairs DROP
    # (renormalized combine), never OOM or recompile.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_intermediate_size: int = 0
    capacity_factor: float = 1.25

    def __post_init__(self):
        assert self.num_heads % self.num_kv_heads == 0
        if self.num_experts:
            assert 0 < self.moe_top_k <= self.num_experts
            if not self.moe_intermediate_size:
                self.moe_intermediate_size = self.intermediate_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def num_params(self, include_embeddings=True):
        h, l = self.hidden_size, self.num_layers
        kvh = self.num_kv_heads * self.head_dim
        if self.num_experts:
            mlp = (self.num_experts * 3 * h * self.moe_intermediate_size
                   + h * self.num_experts)             # experts + router
        else:
            mlp = 3 * h * self.intermediate_size       # gate, up, down
        n = l * (h * h + 2 * h * kvh + h * h + mlp)    # q, k, v, o, mlp
        if include_embeddings:
            n += 2 * self.vocab_size * h  # embed + untied head
        return n


def llama2_7b():
    return LlamaConfig()


def tiny_llama_config(**kw):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, intermediate_size=128, max_position=128)
    base.update(kw)
    return LlamaConfig(**base)


def tiny_moe_llama_config(**kw):
    """Tiny MoE twin of ``tiny_llama_config``: 8 experts, top-2, 64-wide
    expert FFs — active params per token (2 * 64) equal the tiny dense
    MLP's 128-wide FF, so the identity suites compare like for
    like. 8 experts divide every ep in {1, 2, 4, 8}."""
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, intermediate_size=128, max_position=128,
                num_experts=8, moe_top_k=2, moe_intermediate_size=64)
    base.update(kw)
    return LlamaConfig(**base)


def _is_paged(cache) -> bool:
    """One shared predicate with GPT (covers PagedKVCache and the engine's
    functional PagedCacheState)."""
    from .gpt import _is_paged as _gpt_is_paged

    return _gpt_is_paged(cache)


def _tp_reduce(t, axis):
    """The Megatron ``g`` collective of a row-parallel projection: sum
    the per-shard partial products over the tensor-parallel axis. The
    serving model-runner (``inference/runner.py``) arms ``_tp_axis`` on
    attention/MLP modules only for the duration of a sharded trace —
    everywhere else ``axis`` is None and this is the identity, so the
    single-chip path is textually and bitwise unchanged."""
    if axis is None:
        return t
    return apply_op(lambda a: jax.lax.psum(a, axis), t)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = hd
        self.rope_theta = config.rope_theta
        self.q_proj = nn.Linear(h, config.num_heads * hd, bias_attr=False)
        self.k_proj = nn.Linear(h, config.num_kv_heads * hd, bias_attr=False)
        self.v_proj = nn.Linear(h, config.num_kv_heads * hd, bias_attr=False)
        self.o_proj = nn.Linear(config.num_heads * hd, h, bias_attr=False)

    def _rope(self, q, k, time_step, cache=None):
        from ..incubate.nn.functional import fused_rotary_position_embedding
        from ..ops.pallas.paged_attention import PagedCacheState

        b, s = (q._data if isinstance(q, Tensor) else q).shape[:2]
        if isinstance(cache, PagedCacheState):
            # per-slot positions — ragged serving batches rotate each slot
            # at its own length (advisor r2: one scalar time_step mis-rotates
            # every slot but slot 0)
            pos = apply_op(lambda: cache.positions(s))
            q, k, _ = fused_rotary_position_embedding(
                q, k, position_ids=pos, rotary_emb_base=self.rope_theta)
        elif time_step is None:
            q, k, _ = fused_rotary_position_embedding(
                q, k, rotary_emb_base=self.rope_theta)
        else:
            pos = apply_op(
                lambda: jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32)[None] + time_step, (b, s)))
            q, k, _ = fused_rotary_position_embedding(
                q, k, position_ids=pos, rotary_emb_base=self.rope_theta)
        return q, k

    def forward(self, x, cache=None, time_step=None):
        b, s, h = x.shape
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q_proj(x).reshape([b, s, nh, hd])
        k = self.k_proj(x).reshape([b, s, nkv, hd])
        v = self.v_proj(x).reshape([b, s, nkv, hd])
        q, k = self._rope(q, k, time_step, cache)
        new_cache = None
        group = nh // nkv

        def expand_kv(t):
            if group == 1:
                return t
            return apply_op(lambda a: jnp.repeat(a, group, axis=2), t)

        if cache is None:
            out, _ = F.flash_attention(q, expand_kv(k), expand_kv(v),
                                       causal=True, training=self.training)
        elif _is_paged(cache):
            # serving path: block-table page pool (GQA native in the kernel)
            from ..ops.pallas.paged_attention import paged_forward

            out_raw, new_cache = paged_forward(
                cache, q, k, v, time_step,
                lambda: F.flash_attention(q, expand_kv(k), expand_kv(v),
                                          causal=True, training=False)[0])
            out = (out_raw if isinstance(out_raw, Tensor)
                   else Tensor._wrap(out_raw))
        elif time_step is None:
            from ..ops.pallas.decode_attention import cache_prefill_write

            new_cache = apply_op(cache_prefill_write, cache, k, v)
            out, _ = F.flash_attention(q, expand_kv(k), expand_kv(v),
                                       causal=True, training=False)
        else:
            # decode: the Pallas kernel reads kv head h // group natively
            from ..ops.pallas.decode_attention import cache_decode_step

            out, new_cache = apply_op(
                lambda c, qa, ka, va: cache_decode_step(
                    c, qa, ka, va, time_step),
                cache, q, k, v)
        # nh here is the LOCAL head count under a sharded trace (the
        # runner's local_view divides it), so the reshape and the
        # row-parallel o_proj consume exactly this shard's heads; the
        # psum reassembles the full projection (bias-free, so partial
        # sums add exactly)
        out = _tp_reduce(self.o_proj(out.reshape([b, s, nh * hd])),
                         getattr(self, "_tp_axis", None))
        if cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, m, bias_attr=False)
        self.up_proj = nn.Linear(h, m, bias_attr=False)
        self.down_proj = nn.Linear(m, h, bias_attr=False)

    def forward(self, x):
        # gate/up are column-sharded under a TP trace (each shard holds
        # an FF slice), down is row-sharded; the psum after down is the
        # MLP's Megatron g collective (identity off-mesh — see
        # _tp_reduce)
        return _tp_reduce(
            self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x)),
            getattr(self, "_tp_axis", None))


# ----------------------------------------------------------------- MoE
# Serving-telemetry side channel (ISSUE 17 tentpole c): the engine's raw
# program builders arm the tap around model.forward; each MoE layer then
# appends one [E+3] f32 vector — per-expert kept-token counts, dropped
# pairs, router-entropy sum, routed tokens — which the builder threads
# out of the trace as ONE extra program output. Unarmed (training,
# generation, the spec verify program) the layers skip stats entirely,
# so those traces are unchanged. The tap itself is ``models/moe_stats.py``.


def moe_stats_size(config) -> int:
    """Length of the per-program MoE stats vector (0 for dense models):
    [0:E] per-expert kept tokens, [E] dropped pairs, [E+1] router
    entropy sum, [E+2] routed tokens."""
    e = getattr(config, "num_experts", 0) or 0
    return e + 3 if e else 0


def _raw(t):
    return t._data if isinstance(t, Tensor) else jnp.asarray(t)


class LlamaMoEMLP(nn.Layer):
    """Top-k routed expert FFN (ISSUE 17): GShard-lineage routing with
    MegaBlocks-style grouped expert compute through the Pallas grouped
    matmul (``ops/pallas/grouped_matmul``) instead of per-expert
    dispatch.

    The routing math (logits → softmax → top-k → global arrival ranks →
    capacity keep/drop → renormalized combine weights) is REPLICATED:
    every shard routes all T tokens, so the drop set and combine weights
    are bitwise those of the ep=1 engine by construction. Only the
    expert FFN itself scales with ep — under an ep-sharded trace
    (``_ep_axis`` armed by the model-runner's ``local_view``) each shard
    scatters its token slice's kept pairs into the capacity-padded
    [E, C, H] dispatch layout, an ``all_to_all`` moves every pair to its
    expert's owner shard, the grouped kernel runs the E/ep local experts
    over their C-row segments (skipping capacity padding via per-expert
    kept counts), and an ``all_gather`` returns the expert outputs for
    the replicated combine. Capacity overflow drops pairs (combine
    weights renormalize over the kept ones) — never an OOM, never a
    recompile.

    Serving-oriented: the expert dispatch runs on raw jnp arrays, so the
    autograd tape does not thread through it (train dense, serve MoE —
    the honest gap documented in README)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, f = config.hidden_size, config.moe_intermediate_size
        e = config.num_experts
        self.num_experts = e
        self.top_k = config.moe_top_k
        self.capacity_factor = float(config.capacity_factor)
        self.router = nn.Linear(h, e, bias_attr=False)
        init = nn.initializer.Normal(std=config.initializer_range)
        # stacked expert weights, ragged_dot rhs orientation [E, in, out]
        # (bias-free, the llama convention): P('ep', None, None) under an
        # ep-sharded trace — see inference/runner.py's spec table
        self.experts_gate = self.create_parameter(
            [e, h, f], default_initializer=init)
        self.experts_up = self.create_parameter(
            [e, h, f], default_initializer=init)
        self.experts_down = self.create_parameter(
            [e, f, h], default_initializer=init)

    def forward(self, x):
        xd = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        out = _moe_forward(self, xd)
        return Tensor._wrap(out) if isinstance(x, Tensor) else out


def _moe_forward(m: LlamaMoEMLP, x):
    from ..ops.pallas.grouped_matmul import grouped_matmul

    b, s, hd = x.shape
    e, k = m.num_experts, m.top_k
    ax = getattr(m, "_ep_axis", None)
    wg, wu, wd = (_raw(m.experts_gate), _raw(m.experts_up),
                  _raw(m.experts_down))
    el = wg.shape[0]        # local experts: E under ep=1, E/ep sharded
    ep = e // el
    t = b * s
    xt = x.reshape(t, hd)

    # ---- routing (replicated over every mesh axis) --------------------
    logits = jnp.dot(xt, _raw(m.router.weight).astype(xt.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]
    gate_val, gate_idx = jax.lax.top_k(probs, k)                 # [T, k]
    cap = max(1, int(math.ceil(m.capacity_factor * k * t / e)))
    one = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)           # [T,k,E]
    # global arrival rank in gshard COLUMN-major pair order (all
    # choice-0 pairs in token order, then choice-1, … — the counting
    # rule shared with incubate's gshard_dispatch/ragged_routing), so
    # the capacity drop set is a pure function of the routing, not of ep
    oc = jnp.swapaxes(one, 0, 1).reshape(k * t, e)
    rank = jnp.swapaxes(
        (jnp.sum(jnp.cumsum(oc, axis=0) * oc, axis=-1) - 1).reshape(k, t),
        0, 1)                                                    # [T, k]
    keep = rank < cap
    tot = jnp.sum(oc, axis=0)                                    # [E]
    kc = jnp.minimum(tot, cap)          # kept per expert (kernel skip)

    # ---- dispatch: capacity-padded [E, C, H], slots by global rank ----
    slot = gate_idx * cap + jnp.clip(rank, 0, cap - 1)
    pair_ok = keep
    if ax is not None:
        # each shard scatters only ITS token slice's pairs; the
        # all_to_all then moves every pair to its expert's owner shard
        # (slots are globally unique, so the receive-side sum over
        # source shards adds exact zeros — bitwise-safe)
        sidx = jax.lax.axis_index(ax)
        tl = -(-t // ep)
        tok = jnp.arange(t, dtype=jnp.int32)
        pair_ok = pair_ok & ((tok >= sidx * tl)
                             & (tok < (sidx + 1) * tl))[:, None]
    slot = jnp.where(pair_ok, slot, e * cap)          # dump row for drops
    xp = jnp.broadcast_to(xt[:, None, :], (t, k, hd)).reshape(t * k, hd)
    disp = jnp.zeros((e * cap + 1, hd), xt.dtype)
    disp = disp.at[slot.reshape(-1)].add(xp)[:e * cap]
    if ax is not None:
        recv = jax.lax.all_to_all(disp.reshape(ep, el, cap, hd), ax,
                                  split_axis=0, concat_axis=0)
        x_exp = jnp.sum(recv, axis=0)                         # [El, C, H]
        kc_l = jax.lax.dynamic_slice_in_dim(kc, sidx * el, el)
    else:
        x_exp = disp.reshape(e, cap, hd)
        kc_l = kc

    # ---- grouped expert FFN (SwiGLU) over contiguous C-row segments ---
    rows = x_exp.reshape(el * cap, hd)
    gs = jnp.full((el,), cap, jnp.int32)
    h1 = grouped_matmul(rows, wg.astype(rows.dtype), gs, kc_l)
    h2 = grouped_matmul(rows, wu.astype(rows.dtype), gs, kc_l)
    y = grouped_matmul(jax.nn.silu(h1) * h2, wd.astype(rows.dtype), gs,
                       kc_l)
    if ax is not None:
        y = jax.lax.all_gather(y.reshape(el, cap, hd), ax, axis=0,
                               tiled=True)
    y_all = y.reshape(e * cap, hd)

    # ---- combine (replicated): renormalized over kept choices, summed
    # in canonical choice order — identical f32 chains at every ep -----
    wk = jnp.where(keep, gate_val, 0.0)
    den = jnp.sum(wk, axis=-1, keepdims=True)
    wc = jnp.where(den > 0, wk / den, 0.0)                       # [T, k]
    # dropped pairs gather a deterministic in-buffer row and multiply by
    # an exact-zero weight — same row, same zero, at every ep
    gslot = gate_idx * cap + jnp.clip(rank, 0, cap - 1)
    out = jnp.zeros((t, hd), jnp.float32)
    for j in range(k):
        out = out + wc[:, j:j + 1] * y_all[gslot[:, j]].astype(jnp.float32)

    tap = moe_stats.armed()
    if tap is not None:
        ent = -jnp.sum(probs * jnp.log(probs + 1e-20), axis=-1)
        tap.append(jnp.concatenate([
            kc.astype(jnp.float32),
            jnp.sum(tot - kc).astype(jnp.float32)[None],
            jnp.sum(ent)[None],
            jnp.asarray([float(t)], jnp.float32)]))
    return out.astype(x.dtype).reshape(b, s, hd)


class LlamaBlock(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_eps)
        self.mlp = (LlamaMoEMLP(config) if config.num_experts
                    else LlamaMLP(config))

    def forward(self, x, cache=None, time_step=None):
        if cache is None:
            x = x + self.self_attn(self.input_layernorm(x))
            return x + self.mlp(self.post_attention_layernorm(x))
        attn, new_cache = self.self_attn(self.input_layernorm(x),
                                         cache=cache, time_step=time_step)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, new_cache


class LlamaModel(nn.Layer):
    """Trunk: embedding + decoder stack + final RMSNorm."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(std=config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList(
            [LlamaBlock(config) for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_eps)

    def forward(self, input_ids, caches=None, time_step=None):
        x = self.embed_tokens(input_ids)
        if caches is None:
            for block in self.layers:
                x = block(x)
            return self.norm(x)
        new_caches = []
        for block, cache in zip(self.layers, caches):
            x, nc = block(x, cache=cache, time_step=time_step)
            new_caches.append(nc)
        return self.norm(x), new_caches

    def init_caches(self, batch_size, max_seq, dtype=jnp.float32):
        """KV caches (reference capability: the GQA-narrow
        [2,b,n_kv_heads,S,hd] cache of fused_multi_transformer_op.cu) in the
        TPU slab layout [2, b, S, n_kv_heads*hd] — see GPTModel.init_caches
        for the layout rationale."""
        cfg = self.config
        from ..ops.pallas.decode_attention import make_kv_slab

        return [Tensor._wrap(make_kv_slab(batch_size, max_seq,
                                          cfg.num_kv_heads, cfg.head_dim,
                                          dtype))
                for _ in range(cfg.num_layers)]


class LlamaForCausalLM(GenerationMixin, nn.Layer):
    """Untied LM head (llama convention)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, caches=None, time_step=None):
        if caches is None:
            return self.lm_head(self.model(input_ids))
        x, new_caches = self.model(input_ids, caches=caches,
                                   time_step=time_step)
        return self.lm_head(x), new_caches

    def init_caches(self, batch_size, max_seq, dtype=jnp.float32):
        return self.model.init_caches(batch_size, max_seq, dtype)

    def loss(self, input_ids, labels):
        """Mean causal-LM loss via the vocab-parallel CE when an mp>1 mesh
        is active (see GPTForCausalLM.loss)."""
        from ..distributed.fleet.meta_parallel import ParallelCrossEntropy

        logits = self.forward(input_ids)
        v = logits.shape[-1]
        per_tok = ParallelCrossEntropy()(
            logits.reshape([-1, v]), labels.reshape([-1]))
        return per_tok.mean()
