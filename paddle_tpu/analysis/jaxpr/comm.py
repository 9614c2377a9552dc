"""tpushard communication roofline: per-collective ICI cost over the
traced program.

The compute roofline (:mod:`cost`) answers "how long does one device
compute"; this pass answers "how long do the devices spend talking, and
does the talking hide under the compute". Three outputs, all static:

* **predicted comm time** — every collective costed with the standard
  ring/torus formulas below, using per-device ICI peak tables (same
  single-source-of-truth convention as the HBM/FLOPs tables in
  ``cost.py``; tools/multichip.py imports THESE numbers);
* **comm/compute overlap fraction** — a dependency-window model: the
  compute issued between a collective and its first consumer can hide
  under the transfer (Megatron-style overlap). Windows are counted per
  collective, so the estimate is optimistic when windows share ops;
* **predicted multichip step time** — ``compute + comm - overlapped``,
  the number the multichip harness tracks drift against
  (``MULTICHIP_r*.json`` records the measured counterpart).

Cost formulas (S = per-device operand bytes, O = per-device result
bytes, n = product of the named axis sizes, B = ICI bytes/s, a = per-
step latency; all bidirectional-ring algorithms, which is what XLA
emits on a torus axis):

=================  ============================  ==========
collective         wire bytes per device         steps
=================  ============================  ==========
psum (all-reduce)  2 * S * (n-1)/n               2*(n-1)
all_gather         O * (n-1)/n                   n-1
reduce_scatter     S * (n-1)/n                   n-1
all_to_all         S * (n-1)/n                   n-1
ppermute           S                             1
=================  ============================  ==========

``time = wire/B + steps*a``. GSPMD ``sharding_constraint`` eqns are
costed as a potential reshard (all-to-all bound) — XLA may elide the
copy when the producer already agrees, so that bucket is an upper
bound and is reported separately (``assumed_reshard``).

TPC601 (info) fires when effective comm (after overlap) exceeds
compute: the program is ICI-bound at this mesh shape.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import rules as R
from .core import (FlatOp, Finding, PassContext, flatten, materialize,
                   mesh_axis_sizes)
from .cost import (DEFAULT_DEVICE_KIND, _cost_op, CostRollup,
                   device_peaks, hbm_bw, peak_flops)
from .liveness import _fmt_bytes

__all__ = ["CommCostPass", "CommEstimate", "KindTraffic", "comm_kind",
           "comm_rollup", "ICI_LATENCY_S",
           "ICI_COLLECTIVE_OVERHEAD_S", "ici_bw", "ici_latency",
           "predicted_step_seconds", "collective_cost"]

# ------------------------------------------------------------- ICI terms
#
# Per-chip aggregate ICI bandwidth is a column of cost.DEVICE_PEAKS.

# per-step (per-hop) collective latency: ~1us on ICI across generations
ICI_LATENCY_S = 1e-6

# fixed per-collective dispatch/rendezvous overhead on ICI. The host
# payload sweep (tools/multichip.py, MULTICHIP_r16) measures this term
# at ~0.5ms on the virtual-CPU mesh; on real ICI the launch+rendezvous
# cost is a few microseconds. The planner prices device-retargeted
# plans with this constant so small latency-bound collectives (the
# decode regime) are never free.
ICI_COLLECTIVE_OVERHEAD_S = 2e-6


def ici_bw(device_or_kind) -> float:
    return device_peaks(device_or_kind).ici_bytes_per_sec


def ici_latency(device_or_kind) -> float:
    return ICI_LATENCY_S


# ------------------------------------------------------------- estimate

# collective primitives grouped into the CALIBRATION kinds the multichip
# payload sweep fits one overhead-vs-payload curve per (MULTICHIP_r16):
# the ring algorithm, not the reduction operator, sets the cost shape.
_KIND_OF = {
    "psum": "psum", "psum2": "psum", "pmax": "psum", "pmin": "psum",
    "pmean": "psum",
    "all_gather": "all_gather", "pgather": "all_gather",
    "reduce_scatter": "reduce_scatter", "psum_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
}


def comm_kind(prim: str) -> str:
    """Calibration bucket of a collective primitive (``assumed_reshard``
    and anything unknown keep their own bucket and fall back to the
    table pricing)."""
    return _KIND_OF.get(prim, prim)


@dataclass
class KindTraffic:
    """Per-calibration-kind traffic totals (wire bytes, ring steps and
    EXECUTED collective count — counts inside a scan are multiplied by
    the trip count, unlike r11's static count, because each iteration
    pays the dispatch floor again)."""
    wire: float = 0.0
    steps: float = 0.0
    n: float = 0.0


@dataclass
class CommEstimate:
    wire_bytes: float = 0.0         # total per-device ICI traffic
    steps: float = 0.0              # total latency-bound ring steps
    comm_seconds: float = 0.0       # at the device kind it was built for
    overlapped_seconds: float = 0.0
    by_prim: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    by_kind: Dict[str, KindTraffic] = field(default_factory=dict)
    n_collectives: float = 0
    unknown_axes: int = 0           # collectives skipped (axis size unknown)
    device_kind: str = DEFAULT_DEVICE_KIND

    def add(self, prim: str, wire: float, steps: float, seconds: float,
            overlapped: float = 0.0, count: float = 1.0):
        self.wire_bytes += wire
        self.steps += steps
        self.comm_seconds += seconds
        self.overlapped_seconds += min(overlapped, seconds)
        b, s = self.by_prim.get(prim, (0.0, 0.0))
        self.by_prim[prim] = (b + wire, s + seconds)
        kt = self.by_kind.setdefault(comm_kind(prim), KindTraffic())
        kt.wire += wire
        kt.steps += steps
        kt.n += count
        self.n_collectives += count

    def seconds_at(self, bw: float, latency: float = ICI_LATENCY_S,
                   per_collective_s: float = 0.0,
                   calibration: Optional[Dict[str, dict]] = None) -> float:
        """Re-price the same traffic under a different link profile (the
        host-calibrated prediction in tools/multichip.py).

        ``per_collective_s`` is the measured FIXED overhead each
        collective pays once, independent of ring steps — runtime launch
        + rendezvous cost. ``calibration`` (MULTICHIP_r16 rework) maps a
        collective KIND (see :func:`comm_kind`) to its fitted
        overhead-vs-payload curve ``{"overhead_s", "per_byte_s"}``; kinds
        present in the table are priced ``n*overhead + wire*per_byte``
        — NO separate ``steps*latency`` term, because the curve is fit
        from in-program measurements at the calibration mesh size, so
        the ring-step latency is already inside the intercept — while
        absent kinds fall back to the scalar ``bw``/``latency``/
        ``per_collective_s`` path. The one-point r11 fit priced every
        collective from a single tiny-psum line, which left the decode
        regime (many small in-program collectives, each paying the
        dispatch floor) mispredicted 15x."""
        if not calibration:
            return (self.wire_bytes / max(bw, 1.0) + self.steps * latency
                    + self.n_collectives * per_collective_s)
        total = 0.0
        for kind, t in self.by_kind.items():
            cal = calibration.get(kind)
            if cal is None:
                total += (t.wire / max(bw, 1.0) + t.steps * latency
                          + t.n * per_collective_s)
            else:
                per_byte = cal.get("per_byte_s")
                per_byte = (float(per_byte) if per_byte is not None
                            else 1.0 / max(bw, 1.0))
                total += (t.n * float(cal.get("overhead_s", 0.0))
                          + t.wire * per_byte)
        return total

    @property
    def overlap_fraction(self) -> float:
        return (self.overlapped_seconds / self.comm_seconds
                if self.comm_seconds > 0 else 0.0)


def collective_cost(prim: str, operand_bytes: float, result_bytes: float,
                    n: int, bw: float,
                    latency: float = ICI_LATENCY_S
                    ) -> Tuple[float, float, float]:
    """(wire_bytes, steps, seconds) for one collective over an n-way axis."""
    if n <= 1:
        return 0.0, 0.0, 0.0
    S, O = float(operand_bytes), float(result_bytes)
    frac = (n - 1) / n
    if prim in ("psum", "psum2", "pmax", "pmin", "pmean"):
        wire, steps = 2.0 * S * frac, 2.0 * (n - 1)
    elif prim in ("all_gather", "pgather"):
        wire, steps = O * frac, float(n - 1)
    elif prim in ("reduce_scatter", "psum_scatter"):
        wire, steps = S * frac, float(n - 1)
    elif prim == "all_to_all":
        wire, steps = S * frac, float(n - 1)
    elif prim == "ppermute":
        wire, steps = S, 1.0
    else:
        return 0.0, 0.0, 0.0
    return wire, steps, wire / max(bw, 1.0) + steps * latency


def predicted_step_seconds(cost_rollup: Optional[CostRollup],
                           comm_est: Optional["CommEstimate"],
                           peak: float, hbm: float, ici: float,
                           latency: float = ICI_LATENCY_S,
                           per_collective_s: float = 0.0,
                           calibration: Optional[Dict[str, dict]] = None
                           ) -> float:
    """Compute + comm - overlap under explicit peaks (device tables OR a
    host-calibrated profile). Overlap is scaled with comm: re-pricing
    the wire keeps the same overlapped *fraction*. ``calibration`` is
    the per-collective-kind curve table (see
    :meth:`CommEstimate.seconds_at`)."""
    compute = 0.0
    if cost_rollup is not None:
        compute = sum(max(f / peak, b / hbm)
                      for f, b in cost_rollup.by_prim.values())
    comm = overlapped = 0.0
    if comm_est is not None:
        comm = comm_est.seconds_at(ici, latency, per_collective_s,
                                   calibration=calibration)
        overlapped = min(comm * comm_est.overlap_fraction, compute)
    return compute + comm - overlapped


# ------------------------------------------------------------- the walk

_COMM_PRIMS = {"psum", "psum2", "pmax", "pmin", "pmean", "all_gather",
               "pgather", "psum_scatter", "reduce_scatter", "all_to_all",
               "ppermute"}


def _axis_names_of(params: dict) -> Tuple[str, ...]:
    names = params.get("axes", params.get("axis_name", ()))
    if names is None:
        return ()
    if isinstance(names, (str, int)) or not isinstance(
            names, (tuple, list, frozenset, set)):
        names = (names,)
    return tuple(n for n in names if isinstance(n, str))


def _op_seconds(op: FlatOp, kind: str) -> float:
    """Compute-roofline seconds of ONE flat op (the overlap window
    currency)."""
    cr = CostRollup()
    _cost_op(op, cr, scale=1.0)
    peak, bw = peak_flops(kind), hbm_bw(kind)
    return sum(max(f / peak, b / bw) for f, b in cr.by_prim.values())


def _walk(jaxpr_like, sizes: Dict[str, Optional[int]], scale: float,
          kind: str, est: CommEstimate) -> None:
    """Accumulate collective costs from one (sub)jaxpr level. The level
    is flattened so call-like wrappers disappear and the first-consumer
    windows live in one index space."""
    prog = flatten(jaxpr_like)
    materialize(prog)
    ops = prog.ops
    consumers: Dict[int, List[int]] = {}
    for op in ops:
        for rec in op.invars:
            if rec is not None:
                consumers.setdefault(rec.uid, []).append(op.index)
    bw = ici_bw(kind)
    lat = ici_latency(kind)
    for op in ops:
        prim = op.prim
        if prim == "scan":
            length = float(op.params.get("length", 1) or 1)
            sub = op.params.get("jaxpr")
            if sub is not None:
                _walk(sub, sizes, scale * length, kind, est)
        elif prim == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                sub = op.params.get(key)
                if sub is not None:
                    _walk(sub, sizes, scale, kind, est)
        elif prim == "cond":
            # worst branch, matching the cost pass's "how slow can a
            # step be" stance
            best: Optional[CommEstimate] = None
            for b in (op.params.get("branches") or ()):
                sub_est = CommEstimate(device_kind=kind)
                _walk(b, sizes, scale, kind, sub_est)
                if best is None or sub_est.comm_seconds > best.comm_seconds:
                    best = sub_est
            if best is not None:
                _merge(est, best)
        elif prim == "shard_map":
            binder = mesh_axis_sizes(op.params.get("mesh"))
            inner = dict(sizes)
            inner.update(binder)
            sub = op.params.get("jaxpr")
            if sub is not None:
                _walk(sub, inner, scale, kind, est)
        elif prim == "xla_pmap":
            name = op.params.get("axis_name")
            inner = dict(sizes)
            if isinstance(name, str):
                inner[name] = op.params.get("axis_size")
            sub = op.params.get("call_jaxpr")
            if sub is not None:
                _walk(sub, inner, scale, kind, est)
        elif prim in _COMM_PRIMS:
            axes = _axis_names_of(op.params)
            n = 1
            unknown = False
            for a in axes:
                s = sizes.get(a)
                if s is None:
                    unknown = True
                else:
                    n *= int(s)
            if unknown:
                est.unknown_axes += 1
                continue
            S = sum(r.nbytes for r in op.invars if r is not None)
            O = sum(r.nbytes for r in op.outvars)
            wire, steps, secs = collective_cost(prim, S, O, n, bw, lat)
            if secs <= 0.0:
                continue
            # overlap window: compute between the collective and its
            # first consumer at this level
            first = min((min(consumers.get(r.uid, [len(ops)]))
                         for r in op.outvars), default=len(ops))
            window = sum(_op_seconds(o, kind)
                         for o in ops[op.index + 1:first]
                         if o.prim not in _COMM_PRIMS)
            est.add(prim, scale * wire, scale * steps, scale * secs,
                    scale * min(secs, window), count=scale)
        elif prim == "sharding_constraint":
            sh = op.params.get("sharding")
            spec = getattr(sh, "spec", None)
            mesh = getattr(sh, "mesh", None)
            if spec is None or mesh is None:
                continue
            msizes = mesh_axis_sizes(mesh)
            n = 1
            for entry in tuple(spec):
                if entry is None:
                    continue
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    s = msizes.get(str(a))
                    if s:
                        n *= int(s)
            if n <= 1:
                continue
            S = sum(r.nbytes for r in op.invars if r is not None)
            wire, steps, secs = collective_cost("all_to_all", S, S, n,
                                                bw, lat)
            if secs > 0.0:
                est.add("assumed_reshard", scale * wire, scale * steps,
                        scale * secs, count=scale)


def _merge(est: CommEstimate, other: CommEstimate) -> None:
    est.wire_bytes += other.wire_bytes
    est.steps += other.steps
    est.comm_seconds += other.comm_seconds
    est.overlapped_seconds += other.overlapped_seconds
    est.n_collectives += other.n_collectives
    est.unknown_axes += other.unknown_axes
    for prim, (b, s) in other.by_prim.items():
        pb, ps = est.by_prim.get(prim, (0.0, 0.0))
        est.by_prim[prim] = (pb + b, ps + s)
    for kind, t in other.by_kind.items():
        kt = est.by_kind.setdefault(kind, KindTraffic())
        kt.wire += t.wire
        kt.steps += t.steps
        kt.n += t.n


def comm_rollup(closed, mesh=None,
                device_kind: Optional[str] = None) -> CommEstimate:
    """Roll up the communication cost of a (closed) jaxpr. ``mesh``
    seeds the ambient axis sizes (collectives inside shard_map regions
    read their own binder mesh regardless)."""
    kind = device_kind or DEFAULT_DEVICE_KIND
    est = CommEstimate(device_kind=kind)
    _walk(closed, mesh_axis_sizes(mesh), 1.0, kind, est)
    return est


# ------------------------------------------------------------- the pass


class CommCostPass:
    name = "comm"

    def run(self, ctx: PassContext, report) -> None:
        kind = ctx.device_kind or DEFAULT_DEVICE_KIND
        est = comm_rollup(ctx.closed, mesh=ctx.mesh, device_kind=kind)
        report.comm = est
        if est.n_collectives == 0 and est.wire_bytes == 0.0:
            return
        compute = (report.cost.predicted_seconds(kind)
                   if report.cost is not None else 0.0)
        overlapped = min(est.overlapped_seconds, compute)
        effective = est.comm_seconds - overlapped
        step = compute + effective
        if effective > compute:
            report.findings.append(Finding(
                R.COMM_BOUND.id, self.name,
                f"predicted comm {est.comm_seconds * 1e6:.1f}us "
                f"({_fmt_bytes(int(est.wire_bytes))} over ICI, "
                f"{est.n_collectives:g} collectives, overlap "
                f"{est.overlap_fraction:.0%}) exceeds compute "
                f"{compute * 1e6:.1f}us on {kind}: ICI-bound at this "
                f"mesh shape; predicted multichip step "
                f"{step * 1e3:.3f} ms",
                entry=ctx.entry,
                data={"comm_seconds": est.comm_seconds,
                      "compute_seconds": compute,
                      "overlapped_seconds": overlapped,
                      "overlap_fraction": est.overlap_fraction,
                      "predicted_step_seconds": step,
                      "wire_bytes": est.wire_bytes,
                      "n_collectives": est.n_collectives,
                      "unknown_axes": est.unknown_axes,
                      "device_kind": kind,
                      "by_prim": {k: (b, s) for k, (b, s)
                                  in est.by_prim.items()}}))
