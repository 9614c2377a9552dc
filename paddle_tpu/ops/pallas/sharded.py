"""Pallas kernels under the fleet mesh.

A Mosaic kernel cannot be partitioned automatically: a ``pallas_call`` traced
into a jit that spans several devices (the GSPMD way fleet places dp / mp /
sharding) is refused when the program is lowered for the chip — "Mosaic
kernels cannot be automatically partitioned. Please wrap the call in a
shard_map" — and interpret mode on virtual CPU devices never shows it.
Attention is independent across batch rows and across heads, so its
dispatchers run the kernel once per shard: batch over the data-parallel
axes, heads over ``mp``, everything else whole. GSPMD moves the operands to
that layout if the surrounding program left them in another.
"""
from __future__ import annotations

import math
from typing import Callable, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["per_shard"]

# mesh axes that split the batch / the heads (distributed.topology.HYBRID_AXES)
_BATCH_AXES = ("dp", "sharding")
_HEAD_AXIS = "mp"


def per_shard(kernel: Callable, arrays: Sequence, dims: Sequence[Tuple[int, int]],
              out_dims: Tuple[int, int], out_ndim: int):
    """``kernel(*arrays)``, run per shard of the mesh fleet was given.

    ``dims[i]`` is ``(batch_dim, head_dim)`` of ``arrays[i]`` and
    ``out_dims`` the same of the single output (``out_ndim`` dimensions).
    With no mesh set (``fleet.init`` / ``set_mesh`` not called), one device
    along these axes, or a trace already inside a manual region (the serving
    runner's and the pipeline engine's own ``shard_map``), this is the plain
    call. A dimension the axis sizes do not divide stays whole."""
    from ...distributed.jax_compat import shard_map
    from ...distributed.parallel import mesh_if_set

    mesh = mesh_if_set()
    if mesh is None or (jax.sharding.AxisType.Manual
                        in jax.sharding.get_abstract_mesh().axis_types):
        return kernel(*arrays)
    batch_axes = tuple(a for a in _BATCH_AXES if mesh.shape.get(a, 1) > 1)
    n_batch = math.prod(mesh.shape[a] for a in batch_axes)
    n_head = mesh.shape.get(_HEAD_AXIS, 1)
    if any(a.shape[b] % n_batch for a, (b, _) in zip(arrays, dims)):
        batch_axes = ()
    head_axis = _HEAD_AXIS
    if n_head == 1 or any(a.shape[h] % n_head
                          for a, (_, h) in zip(arrays, dims)):
        head_axis = None
    if not batch_axes and head_axis is None:
        return kernel(*arrays)

    def spec(ndim, batch_dim, head_dim):
        entries = [None] * ndim
        entries[batch_dim] = batch_axes or None
        entries[head_dim] = head_axis
        return P(*entries)

    return shard_map(
        kernel, mesh,
        in_specs=tuple(spec(a.ndim, b, h) for a, (b, h) in zip(arrays, dims)),
        out_specs=spec(out_ndim, *out_dims))(*arrays)
