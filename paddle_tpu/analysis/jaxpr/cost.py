"""Roofline cost model over the flattened program.

Per-op FLOPs and HBM bytes rolled up into a predicted step time:
``sum over ops of max(flops / peak_flops, bytes / hbm_bw)`` — the
op-serial roofline. Byte accounting reuses the liveness pass's
materialization model (a fused elementwise producer streams through
registers; only HBM-resident buffers count): actual storage bytes, so
int8/int4 weight streams count their packed sizes.

The device peak table lives HERE (``DEVICE_PEAKS``) and everything else —
comm model, planner, profiler.mfu — reads it: one source of
truth for "what the hardware allows" (ROADMAP north star).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .core import (FlatOp, FlatProgram, Finding, PassContext, flatten,
                   materialize)
from . import rules as R
from .liveness import _fmt_bytes

__all__ = ["CostModelPass", "CostRollup", "rollup", "rollup_fn",
           "DevicePeaks", "DEVICE_PEAKS", "device_peaks", "peak_flops",
           "hbm_bw", "DEFAULT_DEVICE_KIND"]

# ---------------------------------------------------------------- devices

@dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float          # per-chip dense bf16 peak
    hbm_bytes_per_sec: float   # per-chip HBM bandwidth
    ici_bytes_per_sec: float   # per-chip AGGREGATE ICI bandwidth, all links
    hbm_capacity_bytes: int    # per-chip HBM (the liveness gate's budget)


# THE table of per-chip peaks (datasheets), keyed by a prefix of
# ``device.device_kind``. ICI provenance (Gbps / 8):
#   v4   — 3D torus, 6 links x 400 Gbps  = 2400 Gbps   = 300 GB/s
#   v5e  — 2D torus, 4 links x 400 Gbps  = 1600 Gbps   = 200 GB/s
#   v5p  — 3D torus, 6 links x 800 Gbps  = 4800 Gbps   = 600 GB/s
#   v6e  — 2D torus, 4 links x 896 Gbps  = 3584 Gbps   = 448 GB/s
_V4 = DevicePeaks(275e12, 1.2e12, 300e9, 32 << 30)
_V5E = DevicePeaks(197e12, 819e9, 200e9, 16 << 30)
_V5P = DevicePeaks(459e12, 2.77e12, 600e9, 95 << 30)
_V6E = DevicePeaks(918e12, 1.64e12, 448e9, 32 << 30)
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v4": _V4,
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5": _V5P,
    "TPU v5p": _V5P,
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}

# what static analysis prices when it is TOLD no target (tools/analyze_tpu,
# plan_tpu). Never a stand-in for an attached device the table lacks.
DEFAULT_DEVICE_KIND = "TPU v5e"


def device_peaks(device_or_kind) -> DevicePeaks:
    """Peaks of a device (or a ``device_kind`` string), longest matching
    prefix first. A kind the table does not hold is an error, not a
    default: a rate divided by another chip's peak is not a utilisation."""
    kind = str(getattr(device_or_kind, "device_kind", device_or_kind) or "")
    for key in sorted(DEVICE_PEAKS, key=len, reverse=True):
        if kind.startswith(key):
            return DEVICE_PEAKS[key]
    raise ValueError(
        f"unknown device kind {kind!r}: no peak rates for it in "
        f"analysis.jaxpr.cost.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); "
        "add a row with its datasheet source")


def peak_flops(device_or_kind) -> float:
    return device_peaks(device_or_kind).bf16_flops


def hbm_bw(device_or_kind) -> float:
    return device_peaks(device_or_kind).hbm_bytes_per_sec


# ---------------------------------------------------------------- rollup


@dataclass
class CostRollup:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0           # collective traffic, reported apart
    by_prim: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    f64_ops: List[Tuple[str, str]] = field(default_factory=list)
    unknown_trip_counts: int = 0     # while loops costed at 1 iteration

    @property
    def intensity(self) -> float:
        return self.flops / self.hbm_bytes if self.hbm_bytes else math.inf

    def predicted_seconds(self, device_kind: str = DEFAULT_DEVICE_KIND
                          ) -> float:
        peak, bw = peak_flops(device_kind), hbm_bw(device_kind)
        # per-prim roofline, summed: finer than whole-program max, coarser
        # than per-op (which over-rewards fusion the model already took)
        return sum(max(f / peak, b / bw)
                   for f, b in self.by_prim.values())

    def add(self, prim: str, flops: float, nbytes: float):
        self.flops += flops
        self.hbm_bytes += nbytes
        f, b = self.by_prim.get(prim, (0.0, 0.0))
        self.by_prim[prim] = (f + flops, b + nbytes)


_TRANSCENDENTAL = {"exp", "exp2", "expm1", "log", "log1p", "tanh",
                   "logistic", "erf", "erfc", "erf_inv", "sin", "cos",
                   "tan", "pow", "rsqrt", "sqrt", "cbrt"}

_COLLECTIVES = {"psum", "psum2", "pmax", "pmin", "all_gather", "all_to_all",
                "ppermute", "psum_scatter", "reduce_scatter", "pgather"}


def _dot_flops(op: FlatOp) -> float:
    (lc, rc), (lb, rb) = op.params["dimension_numbers"]
    lhs = op.invars[0].aval if op.invars[0] is not None else None
    rhs = op.invars[1].aval if op.invars[1] is not None else None
    if lhs is None or rhs is None:
        return 0.0
    lshape, rshape = lhs.shape, rhs.shape
    batch = 1
    for d in lb:
        batch *= int(lshape[d])
    contract = 1
    for d in lc:
        contract *= int(lshape[d])
    m = 1
    for i, d in enumerate(lshape):
        if i not in lc and i not in lb:
            m *= int(d)
    n = 1
    for i, d in enumerate(rshape):
        if i not in rc and i not in rb:
            n *= int(d)
    return 2.0 * batch * m * n * contract


def _conv_flops(op: FlatOp) -> float:
    out = op.outvars[0].aval if op.outvars else None
    rhs = op.invars[1].aval if len(op.invars) > 1 and op.invars[1] else None
    if out is None or rhs is None:
        return 0.0
    out_elems = 1
    for d in out.shape:
        out_elems *= int(d)
    rhs_elems = 1
    for d in rhs.shape:
        rhs_elems *= int(d)
    # per output element: one MAC per kernel element per input channel of
    # its group — rhs holds [out_ch, in_ch/g, *window]; out_ch divides out
    out_ch = int(rhs.shape[op.params["dimension_numbers"].rhs_spec[0]]) \
        if hasattr(op.params.get("dimension_numbers"), "rhs_spec") else None
    if not out_ch:
        return 2.0 * out_elems * rhs_elems  # coarse upper bound
    return 2.0 * out_elems * (rhs_elems // out_ch)


def _elems(aval) -> float:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return float(n)


def _op_bytes(op: FlatOp) -> float:
    """HBM traffic of one op under the materialization model: read every
    materialized input buffer, write every materialized output."""
    total = 0.0
    seen = set()
    for rec in op.invars:
        if rec is None or rec.uid in seen:
            continue
        seen.add(rec.uid)
        if rec.materialized:
            total += rec.nbytes
    for rec in op.outvars:
        if rec.materialized and rec.reuse_of is None:
            total += rec.nbytes
        elif rec.materialized:  # in-place: one write stream, no alloc
            total += rec.nbytes
    return total


def _is_f64(op: FlatOp) -> bool:
    for rec in list(op.outvars) + [r for r in op.invars if r is not None]:
        if str(getattr(rec.aval, "dtype", "")) == "float64":
            return True
    return False


def rollup(closed, prog: Optional[FlatProgram] = None) -> CostRollup:
    if prog is None:
        prog = flatten(closed)
        materialize(prog)
    cr = CostRollup()
    for op in prog.ops:
        _cost_op(op, cr, scale=1.0)
    return cr


def _cost_op(op: FlatOp, cr: CostRollup, scale: float) -> None:
    prim = op.prim
    if prim == "scan":
        length = float(op.params.get("length", 1) or 1)
        sub = op.params.get("jaxpr")
        if sub is not None:
            _cost_sub(sub, cr, scale * length)
        return
    if prim == "while":
        cr.unknown_trip_counts += 1
        for key in ("cond_jaxpr", "body_jaxpr"):
            sub = op.params.get(key)
            if sub is not None:
                _cost_sub(sub, cr, scale)
        return
    if prim == "cond":
        # cost the most expensive branch (the roofline question is "how
        # slow can a step be")
        best = None
        for b in (op.params.get("branches") or ()):
            sub_cr = CostRollup()
            _cost_sub(b, sub_cr, scale)
            if best is None or sub_cr.flops + sub_cr.hbm_bytes > \
                    best.flops + best.hbm_bytes:
                best = sub_cr
        if best is not None:
            _merge(cr, best)
        return
    if prim in ("shard_map", "xla_pmap", "pallas_call"):
        sub = op.params.get("jaxpr") or op.params.get("call_jaxpr")
        if sub is not None and prim != "pallas_call":
            _cost_sub(sub, cr, scale)
            return
        # pallas_call: opaque kernel — count its operand/result traffic
        cr.add(prim, 0.0, scale * _op_bytes(op))
        return

    if _is_f64(op) and prim in ("dot_general", "conv_general_dilated",
                                "reduce_sum", "reduce_max", "reduce_min",
                                "reduce_prod"):
        cr.f64_ops.append((prim, op.source))

    if prim in _COLLECTIVES:
        cr.ici_bytes += scale * sum(r.nbytes for r in op.outvars)
        return
    if prim == "dot_general":
        cr.add(prim, scale * _dot_flops(op), scale * _op_bytes(op))
        return
    if prim == "conv_general_dilated":
        cr.add(prim, scale * _conv_flops(op), scale * _op_bytes(op))
        return
    out_elems = sum(_elems(r.aval) for r in op.outvars)
    if prim.startswith("reduce_") or prim in ("argmax", "argmin"):
        in_elems = sum(_elems(r.aval) for r in op.invars if r is not None)
        cr.add(prim, scale * in_elems, scale * _op_bytes(op))
        return
    if prim in ("sort", "top_k"):
        in_elems = sum(_elems(r.aval) for r in op.invars if r is not None)
        cr.add(prim, scale * in_elems * max(
            math.log2(max(in_elems, 2)), 1.0), scale * _op_bytes(op))
        return
    flops_per = 10.0 if prim in _TRANSCENDENTAL else 1.0
    cr.add(prim, scale * flops_per * out_elems, scale * _op_bytes(op))


def _cost_sub(sub, cr: CostRollup, scale: float) -> None:
    p = flatten(sub)
    materialize(p)
    for op in p.ops:
        _cost_op(op, cr, scale)


def _merge(cr: CostRollup, other: CostRollup) -> None:
    cr.flops += other.flops
    cr.hbm_bytes += other.hbm_bytes
    cr.ici_bytes += other.ici_bytes
    cr.f64_ops.extend(other.f64_ops)
    cr.unknown_trip_counts += other.unknown_trip_counts
    for prim, (f, b) in other.by_prim.items():
        pf, pb = cr.by_prim.get(prim, (0.0, 0.0))
        cr.by_prim[prim] = (pf + f, pb + b)


def rollup_fn(fn, *args, **kwargs) -> CostRollup:
    """Trace ``fn(*args, **kwargs)`` and roll up its roofline cost."""
    import jax

    return rollup(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))


# ---------------------------------------------------------------- the pass


class CostModelPass:
    name = "cost"

    def run(self, ctx: PassContext, report) -> None:
        cr = rollup(ctx.closed, ctx.flat)
        report.cost = cr
        kind = ctx.device_kind or DEFAULT_DEVICE_KIND
        ridge = peak_flops(kind) / hbm_bw(kind)
        pred = cr.predicted_seconds(kind)
        if cr.hbm_bytes and cr.intensity < ridge:
            report.findings.append(Finding(
                R.MEMORY_BOUND.id, self.name,
                f"arithmetic intensity {cr.intensity:.1f} flop/B is below "
                f"the {kind} ridge ({ridge:.0f}): HBM-bandwidth-bound "
                f"({_fmt_bytes(int(cr.hbm_bytes))} moved, "
                f"{cr.flops / 1e9:.2f} GFLOP, predicted "
                f"{pred * 1e3:.3f} ms/step on {kind})",
                entry=ctx.entry,
                data={"intensity": cr.intensity, "ridge": ridge,
                      "flops": cr.flops, "hbm_bytes": cr.hbm_bytes,
                      "predicted_ms": pred * 1e3,
                      "device_kind": kind,
                      "unknown_trip_counts": cr.unknown_trip_counts}))
        for prim, src in cr.f64_ops[:8]:
            report.findings.append(Finding(
                R.F64_COMPUTE.id, self.name,
                f"{prim} computes in float64{f' at {src}' if src else ''} "
                f"— TPUs emulate f64 an order of magnitude slower than "
                f"f32 and double the HBM stream; cast at the boundary",
                entry=ctx.entry, primitive=prim, source=src,
                data={"primitive": prim}))
