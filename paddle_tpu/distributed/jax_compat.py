"""Thin names over jax's manual-sharding API, kept where the call sites
read better with them.

* :func:`shard_map` — ``jax.shard_map`` with the two keywords this repo
  uses everywhere: ``axis_names`` (the mesh axes the body handles
  manually; None = all) and ``check`` (``check_vma``, off by default —
  the bodies here mix replicated and per-shard values on purpose).
* :func:`virtual_mesh` — a mesh for *tracing* at any device count.
* :func:`ambient_mesh_axis_names` — axis names of the mesh surrounding
  the current trace, for "is this constraint legal here" checks.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

import jax

__all__ = ["shard_map", "ambient_mesh_axis_names", "virtual_mesh"]


def shard_map(f, mesh, in_specs, out_specs,
              axis_names: Optional[Iterable[str]] = None,
              check: bool = False):
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def virtual_mesh(axes: Dict[str, int]):
    """A mesh for *tracing* sharded programs at an arbitrary device
    count — the ``tools/analyze_tpu.py --mesh N`` sweep path.

    When enough local devices exist (the virtual-8-CPU-device harness,
    a real slice) this returns a concrete ``Mesh`` — everything works:
    shard_map, NamedSharding constraints, actual execution. When the
    requested shape exceeds the local device count it falls back to
    ``AbstractMesh`` (device-free), which supports ``jax.make_jaxpr``
    analysis but not execution.
    """
    import numpy as np

    n = 1
    for s in axes.values():
        n *= int(s)
    devices = jax.devices()
    if n <= len(devices):
        from jax.sharding import Mesh

        shape = tuple(int(s) for s in axes.values())
        return Mesh(np.array(devices[:n]).reshape(shape),
                    tuple(axes.keys()))
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(int(v) for v in axes.values()),
                        tuple(axes.keys()))


def ambient_mesh_axis_names() -> Tuple[str, ...]:
    """Axis names of the mesh enclosing the current trace, or ``()``."""
    return tuple(jax.sharding.get_abstract_mesh().axis_names)
