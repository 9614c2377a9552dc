"""Common functional ops: linear, dropout, embedding, pad, interpolate."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...amp import amp_cast
from ...framework import random as _random
from ...framework.tensor import Tensor, apply_op

__all__ = ["linear", "dropout", "embedding", "pad", "interpolate", "unfold",
           "one_hot", "label_smooth", "cosine_similarity", "normalize"]


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(x)


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b with paddle weight layout [in_features, out_features]
    (reference: paddle/phi/kernels/impl/matmul_kernel_impl.h via nn.Linear)."""
    x, weight = amp_cast("linear", _t(x), _t(weight))
    if bias is not None:
        (bias,) = amp_cast("linear", _t(bias))
        return apply_op(lambda a, w, b: jnp.matmul(a, w) + b, x, weight, bias)
    return apply_op(jnp.matmul, x, weight)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    x = _t(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    key = _random.op_key()

    def fn(a):
        shape = a.shape if axis is None else tuple(
            a.shape[i] if (i in (axis if isinstance(axis, (list, tuple)) else [axis])) else 1
            for i in range(a.ndim)
        )
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        out = jnp.where(keep, a, jnp.zeros((), a.dtype))
        if mode == "upscale_in_train":
            out = out / (1.0 - p)
        return out

    return apply_op(fn, x)


# The chip's scatter-add walks a row slowly where the row is wider than this
# and its width is 5, 7, ... times a power of two. 8192 rows of bfloat16 onto
# 25 088, ms: 2560 wide 10.19, 3584 5.58, 5120 44.46; against 1280 0.75, 1536
# 1.23, 2048 1.67, 3072 2.46, 4096 2.06, and 512 0.25 (PERF.md section 6,
# PR 38: the break-even). The gradient of a lookup into such a table is
# summed in power-of-two groups of columns, one scatter-add each
# (``_take_rows_apart``).
_SLOW_ROW_LANES = 2048
# rows of the sum padded to whole tiles: the slice and cast that follow are
# then fused into their reader at its own rate (PR 38)
_ROW_TILE = 128


def _lane_groups(width: int):
    """Columns of each scatter-add of a lookup's gradient summed apart, the
    width's powers of two widest first (2560 -> (2048, 512)), or () where
    one scatter-add of the whole row is fast."""
    if width <= _SLOW_ROW_LANES or width // (width & -width) < 5:
        return ()
    return tuple(1 << b for b in reversed(range(width.bit_length()))
                 if width >> b & 1)


@jax.custom_vjp
def _take_rows_apart(w, idx):
    """``jnp.take(w, idx, axis=0)`` whose transpose sums the cotangent's rows
    by id in float32, one scatter-add a group of ``_lane_groups`` columns
    into arrays of its own, rows padded to whole tiles, and hands back the
    table's gradient as one array in w's type."""
    return jnp.take(w, idx, axis=0)


def _take_rows_apart_fwd(w, idx):
    # a [rows, 0] array carries the table's row count and type, no bytes
    return jnp.take(w, idx, axis=0), (idx, jnp.zeros((w.shape[0], 0), w.dtype))


def _take_rows_apart_bwd(res, ct):
    idx, like = res
    rows, width = like.shape[0], ct.shape[-1]
    held = rows + -rows % _ROW_TILE
    with jax.named_scope("rows_apart"):
        # ids as jnp.take's transpose reads them: a negative id wraps once,
        # one still out of range adds nothing (sent past the padded rows)
        flat = idx.reshape(-1)
        flat = jnp.where(flat < 0, flat + rows, flat)
        flat = jnp.where((flat >= 0) & (flat < rows), flat, held)
        # a narrow type is summed in float32 and rounded once, as the chip's
        # own scatter-add of it does
        wide = jnp.promote_types(like.dtype, jnp.float32)
        ct = ct.reshape(-1, width).astype(wide)
        groups, at = [], 0
        for lanes in _lane_groups(width):
            groups.append(jnp.zeros((held, lanes), wide).at[flat].add(
                ct[:, at:at + lanes], mode="drop"))
            at += lanes
        summed = jnp.concatenate(groups, axis=1)
        return summed[:rows].astype(like.dtype), None


_take_rows_apart.defvjp(_take_rows_apart_fwd, _take_rows_apart_bwd)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Lookup rows of weight [vocab, dim] (reference: phi embedding kernel;
    vocab-parallel variant lives in distributed.fleet.meta_parallel)."""
    idx = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    weight = _t(weight)

    def fn(w):
        if _lane_groups(w.shape[-1]):
            out = _take_rows_apart(w, idx)
        else:
            out = jnp.take(w, idx, axis=0)
        if padding_idx is not None and padding_idx >= 0:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros((), out.dtype), out)
        return out

    return apply_op(fn, weight)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW"):
    x = _t(x)

    def fn(a):
        if isinstance(pad, (list, tuple)) and len(pad) == a.ndim * 2:
            widths = [(pad[2 * i], pad[2 * i + 1]) for i in range(a.ndim)]
        else:
            # paddle style: pad applies to last len(pad)//2 dims, reversed pairs
            n = len(pad) // 2
            widths = [(0, 0)] * (a.ndim - n)
            for i in range(n):
                widths.append((pad[2 * i], pad[2 * i + 1]))
        jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge", "circular": "wrap"}[mode]
        if jmode == "constant":
            return jnp.pad(a, widths, mode=jmode, constant_values=value)
        return jnp.pad(a, widths, mode=jmode)

    return apply_op(fn, x)


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW"):
    x = _t(x)
    n, c, h, w = x._data.shape
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor, scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[mode]

    def fn(a):
        # jax.image.resize operates on spatial dims; NCHW → resize dims 2,3
        return jax.image.resize(a, (a.shape[0], a.shape[1], size[0], size[1]), method=method)

    return apply_op(fn, x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    x = _t(x)
    k = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) else (kernel_sizes, kernel_sizes)
    s = strides if isinstance(strides, (list, tuple)) else (strides, strides)
    p = paddings if isinstance(paddings, (list, tuple)) else (paddings, paddings)
    d = dilations if isinstance(dilations, (list, tuple)) else (dilations, dilations)

    def fn(a):
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=k, window_strides=s,
            padding=[(p[0], p[0]), (p[1], p[1])], rhs_dilation=d,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        n, ckk, oh, ow = patches.shape
        return patches.reshape(n, ckk, oh * ow)

    return apply_op(fn, x)


def one_hot(x, num_classes):
    idx = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    return Tensor._wrap(jax.nn.one_hot(idx, num_classes))


def label_smooth(label, prior_dist=None, epsilon=0.1):
    label = _t(label)

    def fn(l):
        k = l.shape[-1]
        uniform = 1.0 / k if prior_dist is None else jnp.asarray(getattr(prior_dist, "_data", prior_dist))
        return (1 - epsilon) * l + epsilon * uniform

    return apply_op(fn, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    return apply_op(
        lambda a, b: jnp.sum(a * b, axis=axis)
        / jnp.maximum(jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis), eps),
        _t(x1), _t(x2),
    )


def normalize(x, p=2, axis=1, epsilon=1e-12):
    return apply_op(
        lambda a: a / jnp.maximum(jnp.linalg.norm(a, ord=p, axis=axis, keepdims=True), epsilon),
        _t(x),
    )
