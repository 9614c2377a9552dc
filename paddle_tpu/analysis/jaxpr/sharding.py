"""tpushard sharding audit: what the traced program actually does on a
mesh.

GSPMD-style sharding propagation makes three failure shapes decidable
from the program alone — no 8-device run needed to see them:

* **TPC501** — implicit full replication. ``shard_map`` replicates every
  operand its ``in_specs`` entry does not shard, silently. For a
  parameter-sized array (>= ``PassContext.min_sharding_bytes``, default
  1MiB) on a >1-device mesh that multiplies HBM by the mesh size and
  defeats the sharding the surrounding code thinks it has.
* **TPC502** — resharding copies at region boundaries. When the spec a
  value was *produced* under (a shard_map ``out_specs`` entry or a
  ``with_sharding_constraint``) disagrees with the spec its *consuming*
  region expects, XLA inserts a resharding copy — a full gather+reslice
  over ICI per step, invisible in the source.
* **TPC503** — degenerate or materializing collectives. A collective
  over axes that all have size 1 lowers to a no-op copy (the program
  was written for a different mesh factorization); an ``all_gather``
  whose result is parameter-sized materializes the full tensor on every
  device — the accidental full-weight all-gather whose psum-scatter
  form moves 1/n the bytes and keeps the result sharded.

The pass walks the jaxpr structurally for TPC501/TPC503 (binder scopes
matter, as in :mod:`collectives`) and uses the flattened IR for TPC502
(boundary tracking wants one index space). Mesh axis sizes come from
:func:`core.mesh_axis_sizes`, which understands both concrete ``Mesh``
and the device-free ``AbstractMesh`` the ``--mesh N`` sweep traces
under.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from . import rules as R
from .core import (Finding, PassContext, bytes_of_aval, eqn_source,
                   mesh_axis_sizes, subjaxprs, _raw)
from .liveness import _fmt_bytes

__all__ = ["ShardingPass", "spec_to_names"]

# collectives whose operand sharding TPC503 inspects (jaxpr-level names)
_GATHERING = {"all_gather", "pgather"}
_AXIS_COLLECTIVES = {
    "psum", "psum2", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pgather", "psum_scatter", "reduce_scatter",
}


def _axis_names_of(params: dict) -> Tuple[str, ...]:
    names = params.get("axes", params.get("axis_name", ()))
    if names is None:
        return ()
    if isinstance(names, (str, int)) or not isinstance(
            names, (tuple, list, frozenset, set)):
        names = (names,)
    return tuple(n for n in names if isinstance(n, str))


def spec_to_names(spec) -> Tuple[Tuple[int, Tuple[str, ...]], ...]:
    """Canonical form of a PartitionSpec (a shard_map ``in_specs`` /
    ``out_specs`` entry, a sharding constraint's spec): ``((dim, axes),
    ...)`` with unsharded dims dropped — so two specs compare equal iff
    they shard the same dims over the same axes."""
    out = []
    try:
        for dim, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            axes = tuple(a for a in axes if isinstance(a, str))
            if axes:
                out.append((dim, axes))
    except Exception:
        return ()
    return tuple(out)


def _mesh_key(sizes: Dict[str, Optional[int]]):
    return tuple(sorted(sizes.items()))


def _total(sizes: Dict[str, Optional[int]]) -> int:
    total = 1
    for s in sizes.values():
        if s:
            total *= int(s)
    return total


class ShardingPass:
    name = "sharding"

    def run(self, ctx: PassContext, report) -> None:
        self._ctx = ctx
        self._report = report
        self._floor = ctx.min_sharding_bytes
        self._walk(_raw(ctx.closed), {})
        self._boundaries(ctx)

    def _finding(self, rule, eqn, msg, **data):
        self._report.findings.append(Finding(
            rule.id, self.name, msg, entry=self._ctx.entry,
            primitive=eqn.primitive.name if eqn is not None else "",
            source=eqn_source(eqn) if eqn is not None else "",
            data=data))

    # -- TPC501 + TPC503: structural walk -------------------------------

    def _walk(self, jaxpr, sizes: Dict[str, Optional[int]]) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "shard_map":
                binder = mesh_axis_sizes(eqn.params.get("mesh"))
                self._check_replication(eqn, binder)
                sub = eqn.params.get("jaxpr")
                if sub is not None:
                    inner = dict(sizes)
                    inner.update(binder)
                    self._walk(_raw(sub), inner)
            elif prim == "xla_pmap":
                name = eqn.params.get("axis_name")
                binder = {name: eqn.params.get("axis_size")} \
                    if isinstance(name, str) else {}
                sub = eqn.params.get("call_jaxpr")
                if sub is not None:
                    inner = dict(sizes)
                    inner.update(binder)
                    self._walk(_raw(sub), inner)
            else:
                if prim in _AXIS_COLLECTIVES:
                    self._check_collective(eqn, sizes)
                for _, sub in subjaxprs(eqn.params):
                    self._walk(_raw(sub), sizes)

    def _check_replication(self, eqn, binder: Dict[str, Optional[int]]):
        if _total(binder) <= 1:
            return  # a 1-device mesh replicates everything trivially
        in_specs = eqn.params.get("in_specs") or ()
        for pos, (var, spec) in enumerate(zip(eqn.invars, in_specs)):
            if spec_to_names(spec):
                continue  # sharded on at least one dim
            nbytes = bytes_of_aval(getattr(var, "aval", None))
            if nbytes < self._floor:
                continue
            aval = var.aval
            self._finding(
                R.IMPLICIT_FULL_REPLICATION, eqn,
                f"shard_map operand {pos} "
                f"({getattr(aval, 'dtype', '?')}"
                f"[{','.join(map(str, getattr(aval, 'shape', ())))}], "
                f"{_fmt_bytes(nbytes)}) has an empty in_spec: every one "
                f"of the {_total(binder)} devices holds the full array. "
                f"Shard it over a mesh axis or justify the replication",
                operand=pos, nbytes=nbytes,
                mesh_axes={k: v for k, v in binder.items()})

    def _check_collective(self, eqn, sizes: Dict[str, Optional[int]]):
        prim = eqn.primitive.name
        axes = _axis_names_of(eqn.params)
        if not axes:
            return
        known = [sizes.get(a) for a in axes]
        if _total(sizes) > 1 and known and all(s == 1 for s in known):
            self._finding(
                R.DEGENERATE_COLLECTIVE, eqn,
                f"{prim} over {list(axes)} where every named axis has "
                f"size 1 on the bound mesh "
                f"({ {k: v for k, v in sizes.items()} }): the collective "
                f"lowers to a no-op copy — the code was factored for a "
                f"different mesh shape",
                axes=list(axes), degenerate=True)
            return
        if prim in _GATHERING:
            out_bytes = sum(bytes_of_aval(v.aval) for v in eqn.outvars)
            n = 1
            for s in known:
                if s:
                    n *= int(s)
            if n > 1 and out_bytes >= self._floor:
                self._finding(
                    R.DEGENERATE_COLLECTIVE, eqn,
                    f"{prim} over {list(axes)} (x{n}) materializes "
                    f"{_fmt_bytes(out_bytes)} on EVERY device — "
                    f"parameter-sized full gather. If the result feeds a "
                    f"contraction, the psum-scatter form keeps it "
                    f"sharded and moves 1/{n} the bytes",
                    axes=list(axes), out_bytes=out_bytes,
                    degenerate=False)

    # -- TPC502: boundary resharding over the flat IR -------------------

    def _boundaries(self, ctx: PassContext) -> None:
        flat = ctx.flat
        # uid -> (mesh_key, normalized spec) as last produced/constrained
        spec_of: Dict[int, Tuple[Any, Tuple]] = {}
        # shape-preserving ops a sharding annotation survives through
        passthrough = {"copy", "stop_gradient", "convert_element_type"}
        for op in flat.ops:
            if op.prim == "shard_map":
                sizes = mesh_axis_sizes(op.params.get("mesh"))
                key = _mesh_key(sizes)
                in_specs = op.params.get("in_specs") or ()
                for pos, (rec, spec) in enumerate(zip(op.invars, in_specs)):
                    if rec is None or rec.nbytes < self._floor:
                        continue
                    want = spec_to_names(spec)
                    got = spec_of.get(rec.uid)
                    if got is not None and got[0] == key and got[1] != want:
                        self._finding(
                            R.RESHARD_AT_BOUNDARY, None,
                            f"shard_map operand {pos} at op {op.index} "
                            f"was produced under spec {got[1]} but this "
                            f"region consumes it under {want}: XLA "
                            f"inserts a resharding copy "
                            f"({_fmt_bytes(rec.nbytes)} gathered + "
                            f"resliced over ICI) at the boundary",
                            operand=pos, op_index=op.index,
                            produced=list(got[1]), consumed=list(want),
                            nbytes=rec.nbytes)
                out_specs = op.params.get("out_specs") or ()
                for rec, spec in zip(op.outvars, out_specs):
                    spec_of[rec.uid] = (key, spec_to_names(spec))
            elif op.prim == "sharding_constraint":
                sh = op.params.get("sharding")
                mesh = getattr(sh, "mesh", None)
                spec = getattr(sh, "spec", None)
                if mesh is None or spec is None:
                    continue
                key = _mesh_key(mesh_axis_sizes(mesh))
                want = spec_to_names(spec)
                rec = op.invars[0] if op.invars else None
                if rec is not None and rec.nbytes >= self._floor:
                    got = spec_of.get(rec.uid)
                    if got is not None and got[0] == key and got[1] != want:
                        self._finding(
                            R.RESHARD_AT_BOUNDARY, None,
                            f"sharding constraint at op {op.index} "
                            f"re-annotates a value produced under "
                            f"{got[1]} as {want}: a resharding copy "
                            f"({_fmt_bytes(rec.nbytes)}) lands here",
                            op_index=op.index, produced=list(got[1]),
                            consumed=list(want), nbytes=rec.nbytes)
                for out in op.outvars:
                    spec_of[out.uid] = (key, want)
            elif op.prim in passthrough:
                src = op.invars[0] if op.invars else None
                if src is not None and src.uid in spec_of:
                    for out in op.outvars:
                        spec_of[out.uid] = spec_of[src.uid]
