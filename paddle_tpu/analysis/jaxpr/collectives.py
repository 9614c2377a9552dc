"""Collective/mesh consistency over the traced program.

Walks the jaxpr structurally (NOT the flattened IR — binder scopes
matter here), carrying the set of named axes each enclosing
shard_map/pmap binds together with the axis sizes it knows, plus a
value-dependent-control-flow depth. Three checks:

* TPC201 — a collective's axis must resolve against the binders AND the
  binders' mesh must agree with the active mesh the program will run
  under (the "code written for last month's mesh" failure).
* TPC202 — a collective reachable only under a value-dependent
  ``cond``/``while`` is the canonical multi-host deadlock shape: at
  trace time every jaxpr ``cond`` predicate is a traced value, so if it
  is computed from per-host data, hosts disagree about entering the
  branch and the ones inside block forever. ``scan`` is exempt — its
  trip count is static.
* TPC203 — ppermute (src, dst) pairs must form a partial permutation of
  the axis: in-range, no duplicate source, no duplicate destination.
  jax traces violations without complaint (the coll_bad_ppermute
  fixture still traces on jax 0.9.0); the chip hangs or silently drops
  data.

``pbroadcast`` and ``axis_index`` eqns are exempt from TPC202 (the
``_BLOCKING`` subset below): shard_map's replication rewrite inserts
``pbroadcast`` mechanically, and ``axis_index`` lowers to a local
partition-id read — neither blocks on peers, so per-shard index math
under a value-dependent ``cond`` is NOT a deadlock shape. Both stay in
``COLLECTIVE_PRIMS`` on purpose: they still NAME an axis, so TPC201's
axis-vs-mesh check must see them (an ``axis_index('mp')`` against a
mesh with no ``mp`` is the same written-for-another-mesh bug as a
``psum``). Regression fixture:
``tests/fixtures/analysis/coll_axis_index_cond.py``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, PassContext, eqn_source, mesh_axis_sizes,
                   subjaxprs, _raw)
from . import rules as R

__all__ = ["CollectivePass", "COLLECTIVE_PRIMS"]

# primitives that communicate across a named axis (jaxpr-level names;
# psum traces as psum2 on current jax)
COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pgather", "psum_scatter", "reduce_scatter", "pbroadcast",
    "axis_index",
}

# communicating subset: these block until peers arrive (deadlock-capable).
# axis_index/pbroadcast compile to local computation.
_BLOCKING = COLLECTIVE_PRIMS - {"axis_index", "pbroadcast"}


def _axis_names_of(params: dict) -> Tuple[str, ...]:
    names = params.get("axes", params.get("axis_name", ()))
    if names is None:
        return ()
    if isinstance(names, (str, int)) or not isinstance(names, (tuple, list,
                                                               frozenset,
                                                               set)):
        names = (names,)
    # skip anonymous/internal axes (jax uses object() markers for some
    # internal rewrites)
    return tuple(n for n in names if isinstance(n, str))


@dataclass
class _Scope:
    bound: Dict[str, Optional[int]] = field(default_factory=dict)
    # names of value-dependent control-flow constructs we are under
    value_dep: Tuple[str, ...] = ()

    def child(self, extra_axes: Dict[str, Optional[int]] = None,
              enter_value_dep: Optional[str] = None) -> "_Scope":
        bound = dict(self.bound)
        if extra_axes:
            bound.update(extra_axes)
        vd = self.value_dep + ((enter_value_dep,) if enter_value_dep else ())
        return _Scope(bound, vd)


class CollectivePass:
    name = "collectives"

    def run(self, ctx: PassContext, report) -> None:
        mesh_axes: Dict[str, Optional[int]] = mesh_axis_sizes(ctx.mesh)
        self._mesh_axis_names: Set[str] = set(mesh_axes)
        self._ctx = ctx
        self._report = report
        self._walk(_raw(ctx.closed), _Scope(dict(mesh_axes)))

    # -- helpers --------------------------------------------------------

    def _finding(self, rule, eqn, msg, **data):
        self._report.findings.append(Finding(
            rule.id, self.name, msg, entry=self._ctx.entry,
            primitive=eqn.primitive.name, source=eqn_source(eqn),
            data=data))

    def _binder_axes(self, eqn) -> Dict[str, Optional[int]]:
        """Axes a shard_map/pmap eqn binds, with sizes where known."""
        prim = eqn.primitive.name
        if prim == "shard_map":
            axes = mesh_axis_sizes(eqn.params.get("mesh"))
            auto = eqn.params.get("auto") or frozenset()
            binder = {n: s for n, s in axes.items() if n not in auto}
            # the binder's mesh must itself agree with the active mesh
            if self._mesh_axis_names:
                stray = sorted(set(binder) - self._mesh_axis_names)
                if stray:
                    self._finding(
                        R.UNKNOWN_COLLECTIVE_AXIS, eqn,
                        f"shard_map binds mesh axes {stray} that the "
                        f"active mesh (axes "
                        f"{sorted(self._mesh_axis_names)}) does not "
                        f"define — traced against a different mesh "
                        f"topology than the one it will run under",
                        binder_axes=sorted(binder),
                        mesh_axes=sorted(self._mesh_axis_names))
            return binder
        if prim == "xla_pmap":
            name = eqn.params.get("axis_name")
            size = eqn.params.get("axis_size")
            if isinstance(name, str):
                return {name: size}
        return {}

    # -- the walk -------------------------------------------------------

    def _walk(self, jaxpr, scope: _Scope) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMS:
                self._check_collective(eqn, scope)
            if prim in ("shard_map", "xla_pmap"):
                binder = self._binder_axes(eqn)
                sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                if sub is not None:
                    self._walk(_raw(sub), scope.child(binder))
            elif prim == "cond":
                for b in (eqn.params.get("branches") or ()):
                    self._walk(_raw(b), scope.child(
                        enter_value_dep="cond"))
            elif prim == "while":
                for key in ("cond_jaxpr", "body_jaxpr"):
                    sub = eqn.params.get(key)
                    if sub is not None:
                        self._walk(_raw(sub), scope.child(
                            enter_value_dep="while"))
            else:
                # scan and the call-like prims keep the same scope (scan
                # trip count is static — not a divergence hazard)
                for _, sub in subjaxprs(eqn.params):
                    self._walk(_raw(sub), scope)

    def _check_collective(self, eqn, scope: _Scope) -> None:
        prim = eqn.primitive.name
        axes = _axis_names_of(eqn.params)
        for ax in axes:
            if ax not in scope.bound:
                self._finding(
                    R.UNKNOWN_COLLECTIVE_AXIS, eqn,
                    f"{prim} over axis {ax!r}, but neither an enclosing "
                    f"shard_map/pmap nor the active mesh binds it "
                    f"(bound here: {sorted(scope.bound) or 'none'})",
                    axis=ax, bound=sorted(scope.bound))
        if prim in _BLOCKING and scope.value_dep:
            self._finding(
                R.COLLECTIVE_UNDER_VALUE_DEP, eqn,
                f"{prim} over {list(axes) or '?'} is reachable only under "
                f"value-dependent {'/'.join(scope.value_dep)} — if the "
                f"predicate diverges across hosts, the ranks inside the "
                f"branch wait on peers that never arrive",
                axes=list(axes), under=list(scope.value_dep))
        if prim == "ppermute":
            self._check_ppermute(eqn, scope)

    def _check_ppermute(self, eqn, scope: _Scope) -> None:
        perm = eqn.params.get("perm") or ()
        axes = _axis_names_of(eqn.params)
        size = None
        for ax in axes:
            if scope.bound.get(ax) is not None:
                size = scope.bound[ax]
                break
        bad: List[str] = []
        srcs: Set[int] = set()
        dsts: Set[int] = set()
        for pair in perm:
            try:
                s, d = int(pair[0]), int(pair[1])
            except Exception:
                bad.append(f"malformed pair {pair!r}")
                continue
            if size is not None and not (0 <= s < size and 0 <= d < size):
                bad.append(f"({s},{d}) outside axis size {size}")
            if s in srcs:
                bad.append(f"duplicate source {s}")
            if d in dsts:
                bad.append(f"duplicate destination {d}")
            srcs.add(s)
            dsts.add(d)
        if bad:
            self._finding(
                R.MALFORMED_PPERMUTE, eqn,
                f"ppermute over {list(axes) or '?'}: " + "; ".join(bad),
                problems=bad, perm=[tuple(p) for p in perm])
