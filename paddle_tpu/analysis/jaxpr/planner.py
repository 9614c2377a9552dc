"""tpuplan — the autosharding planner (ISSUE 16 tentpole): invert the
tpushard audit into a search.

The analysis stack so far AUDITS a hand-written sharding (TPC5xx) and
PRICES it (cost/comm/liveness). This pass closes ROADMAP item 5's loop:
given a registry-traced program, ENUMERATE candidate plans — mesh
shapes × axis assignments × (DP/TP/SP/EP/PP) splits — and cost each one
with the same three models the audit uses, composed:

* **compute** — the cost pass's roofline (:func:`cost.rollup`), with
  per-``dot_general`` flops scaled by the product of shard factors of
  the operands each dot consumes;
* **comm** — the template's induced collectives priced through
  :meth:`CommEstimate.seconds_at` (ring formulas + per-collective
  dispatch overhead; optionally the MULTICHIP_r16 host-calibrated
  per-kind curves);
* **liveness gate** — per-device peak HBM (sharded operand bytes +
  scaled temporaries) against the device's capacity; infeasible plans
  are pruned with the violated budget attached, NOT silently dropped.

The hand-written sharding rides along as the **oracle** candidate,
priced from its own mesh-N trace (real per-shard compute, real
collectives), so "the planner's choice costs no more than the
hand-written spec" holds by construction whenever the search includes
the oracle — and when a template candidate wins, the report says why
the oracle lost.

Every candidate is self-audited with the TPC501/502/503 predicates
before it may win: the planner never emits a plan its own sharding
linter would reject (large operands silently replicated, reshard at a
boundary, degenerate collectives).

Deliberate gaps (honest, per the README): no inter-op / pipeline-stage
*search* (PP is a single template, not a stage partitioner), host-side
costs (dispatch, scheduling threads) are unmodeled, and template comm
is first-order (no fused/overlapped collective schedules).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .comm import (ICI_COLLECTIVE_OVERHEAD_S, ICI_LATENCY_S, CommEstimate,
                   _merge as _merge_comm, collective_cost, comm_rollup,
                   ici_bw)
from .cost import (DEFAULT_DEVICE_KIND, device_peaks, hbm_bw, peak_flops,
                   rollup)
from .liveness import _fmt_bytes, estimate_memory
from .sharding import spec_to_names

__all__ = ["PlanProblem", "Candidate", "PlanCost", "PlanReport",
           "DEVICE_ALIASES", "extract_problem",
           "enumerate_candidates", "price_candidate", "audit_candidate",
           "plan_program", "spec_str"]

# ------------------------------------------------------------- devices

DEVICE_ALIASES = {
    "v4": "TPU v4",
    "v5e": "TPU v5e",
    "v5p": "TPU v5p",
    "v6e": "TPU v6e",
}

# operands below this size never gate a plan on replication (mirrors
# the sharding pass's TPC501 floor)
MIN_SHARDING_BYTES = 1 << 20


def device_kind(name: str) -> str:
    return DEVICE_ALIASES.get(name, name)


def hbm_capacity(kind: str) -> int:
    return device_peaks(kind).hbm_capacity_bytes


# ------------------------------------------------------------- problem


@dataclass
class Operand:
    index: int
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int
    # roles harvested from the mesh-1 trace: which side of dot_generals
    # this operand (or a structural alias of it) feeds
    is_dot_rhs: bool = False
    is_dot_lhs: bool = False
    # total bytes this operand streams through the program (each use,
    # scan-scaled) — what sharding it actually saves in HBM traffic
    use_bytes: float = 0.0

    @property
    def label(self) -> str:
        return f"in{self.index}:{self.dtype}{list(self.shape)}"


@dataclass
class DotUse:
    """One dot_general in the mesh-1 trace, with the top-level operands
    (if any) its lhs/rhs trace back to through structural ops."""
    flops: float
    out_bytes: float
    lhs: Optional[int]
    rhs: Optional[int]
    scale: float = 1.0


@dataclass
class PlanProblem:
    entry: str
    operands: List[Operand]
    out_avals: List[Tuple[Tuple[int, ...], str]]
    dots: List[DotUse]
    total_flops: float
    total_hbm_bytes: float
    peak_temp_bytes: float
    trains: bool
    # operand indices that are persistent parameters (dot rhs; in a
    # train step additionally shape-matched to an output, since every
    # activation is the rhs of its own weight-grad dot there)
    weight_idx: frozenset = frozenset()
    # the hand-written plan, traced at the target mesh. "shard_map"
    # oracles carry harvested specs and per-shard rollups; "gspmd"
    # oracles (sharding-constraint entries) trace GLOBAL shapes, so
    # their compute/HBM is divided by the mesh under the ideal-
    # partition assumption GSPMD itself makes.
    oracle_mode: Optional[str] = None
    oracle_specs: Optional[List[Tuple]] = None
    oracle_out_specs: Optional[List[Tuple]] = None
    oracle_compute: Optional[object] = None     # CostRollup at mesh N
    oracle_comm: Optional[CommEstimate] = None
    oracle_peak_bytes: Optional[int] = None


# structural primitives an operand keeps its identity through when we
# trace dot provenance (covers the transposes autodiff inserts)
_ALIAS_PRIMS = {"transpose", "reshape", "convert_element_type", "copy",
                "stop_gradient", "squeeze", "broadcast_in_dim", "slice",
                "rev"}
_CALL_PARAM_KEYS = ("jaxpr", "call_jaxpr")


def _aval_bytes(aval) -> int:
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return size * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params.get("dimension_numbers")
    try:
        (lc, _), (lb, _) = dnums
        contract = 1
        for d in lc:
            contract *= int(lhs.shape[d])
        batch = 1
        for d in lb:
            batch *= int(lhs.shape[d])
        out = 1
        for d in eqn.outvars[0].aval.shape:
            out *= int(d)
        return 2.0 * out * contract
    except Exception:
        m = 1
        for d in lhs.shape:
            m *= int(d)
        n = 1
        for d in rhs.shape:
            n *= int(d)
        return 2.0 * (m * n) ** 0.5


def _sub_jaxpr(params: dict):
    for key in _CALL_PARAM_KEYS:
        sub = params.get(key)
        if sub is not None:
            yield sub
    for b in (params.get("branches") or ()):
        yield b
    for key in ("cond_jaxpr", "body_jaxpr"):
        sub = params.get(key)
        if sub is not None:
            yield sub


def _env_get(env: Dict, v):
    """env lookup tolerating jaxpr Literals (unhashable)."""
    try:
        return env.get(v)
    except TypeError:
        return None


def _env_set(env: Dict, v, idx) -> None:
    try:
        env[v] = idx
    except TypeError:
        pass


def _walk_roles(jaxpr, env: Dict, problem: PlanProblem,
                scale: float) -> None:
    """Propagate top-level operand identity through one jaxpr level and
    record dot roles / use bytes. ``env`` maps this level's vars to a
    top-level operand index (or None)."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        prim = eqn.primitive.name
        srcs = [_env_get(env, v) for v in eqn.invars
                if hasattr(v, "aval")]
        for v in eqn.invars:
            idx = _env_get(env, v)
            if idx is not None:
                problem.operands[idx].use_bytes += (
                    _aval_bytes(v.aval) * scale)
        if prim == "dot_general":
            lhs_i = _env_get(env, eqn.invars[0])
            rhs_i = _env_get(env, eqn.invars[1])
            if lhs_i is not None:
                problem.operands[lhs_i].is_dot_lhs = True
            if rhs_i is not None:
                problem.operands[rhs_i].is_dot_rhs = True
            problem.dots.append(DotUse(
                flops=_dot_flops(eqn) * scale,
                out_bytes=_aval_bytes(eqn.outvars[0].aval),
                lhs=lhs_i, rhs=rhs_i, scale=scale))
        elif prim in _ALIAS_PRIMS and len(eqn.outvars) == 1:
            src = srcs[0] if srcs else None
            if src is not None:
                env[eqn.outvars[0]] = src
        else:
            inner_scale = scale
            if prim == "scan":
                inner_scale = scale * float(
                    eqn.params.get("length", 1) or 1)
            for sub in _sub_jaxpr(eqn.params):
                sub_jx = getattr(sub, "jaxpr", sub)
                sub_env: Dict = {}
                for inner_v, outer_v in zip(sub_jx.invars, eqn.invars):
                    idx = _env_get(env, outer_v)
                    if idx is not None:
                        _env_set(sub_env, inner_v, idx)
                _walk_roles(sub, sub_env, problem, inner_scale)
                # map call outputs back: a call output that IS a passed-
                # through operand keeps identity (scan carries etc.)
                for inner_o, outer_o in zip(sub_jx.outvars, eqn.outvars):
                    idx = _env_get(sub_env, inner_o)
                    if idx is not None:
                        _env_set(env, outer_o, idx)


def _pairs_to_dims(pairs, ndim: int) -> Tuple:
    """spec_to_names ((dim, axes), ...) pairs -> the planner's per-dim
    tuple form used by spec_str/_shard_factor."""
    entries: List[Tuple] = [() for _ in range(ndim)]
    for dim, axes in pairs:
        if 0 <= dim < ndim:
            entries[dim] = tuple(axes)
    return _norm(entries)


def _harvest_oracle_specs(closed) -> Tuple[Optional[List], Optional[List],
                                           Optional[str]]:
    """Pull the hand-written in/out specs from the outermost shard_map
    of the mesh-N trace (the registry convention: one top-level region),
    as spec_to_names pairs aligned to that region's operands. Falls
    back to "gspmd" mode when the entry shards via sharding_constraint
    instead of shard_map."""
    jx = getattr(closed, "jaxpr", closed)
    saw_gspmd = False
    for eqn in jx.eqns:
        if eqn.primitive.name == "shard_map":
            ins = [spec_to_names(s) for s in eqn.params["in_specs"]]
            outs = [spec_to_names(s) for s in eqn.params["out_specs"]]
            return ins, outs, "shard_map"
        if eqn.primitive.name == "sharding_constraint":
            saw_gspmd = True
        for sub in _sub_jaxpr(eqn.params):
            ins, outs, mode = _harvest_oracle_specs(sub)
            if mode == "shard_map":
                return ins, outs, mode
            if mode == "gspmd":
                saw_gspmd = True
    if saw_gspmd:
        return None, None, "gspmd"
    return None, None, None


def extract_problem(closed, *, entry: str = "program",
                    oracle_closed=None, oracle_mesh=None,
                    device: str = DEFAULT_DEVICE_KIND) -> PlanProblem:
    """Build the plan problem from the mesh-1 (unsharded) trace, plus
    the oracle's own mesh-N trace when the entry has a hand-written
    sharding to compete against."""
    jx = getattr(closed, "jaxpr", closed)
    operands = []
    for i, v in enumerate(jx.invars):
        aval = v.aval
        operands.append(Operand(
            index=i, shape=tuple(int(d) for d in aval.shape),
            dtype=str(aval.dtype), nbytes=_aval_bytes(aval)))
    out_avals = [(tuple(int(d) for d in v.aval.shape), str(v.aval.dtype))
                 for v in jx.outvars]
    cr = rollup(closed)
    mem = estimate_memory(closed)
    problem = PlanProblem(
        entry=entry, operands=operands, out_avals=out_avals, dots=[],
        total_flops=float(cr.flops), total_hbm_bytes=float(cr.hbm_bytes),
        peak_temp_bytes=float(mem.peak_temp_out_bytes),
        trains=False)
    env = {v: i for i, v in enumerate(jx.invars)}
    _walk_roles(closed, env, problem, 1.0)
    # a program that returns an array shaped like a weight operand is
    # updating parameters: DP must pay the grad all-reduce
    weight_shapes = {(o.shape, o.dtype) for o in operands if o.is_dot_rhs}
    problem.trains = any((s, d) in weight_shapes for s, d in out_avals)
    out_set = set(out_avals)
    if problem.trains:
        problem.weight_idx = frozenset(
            o.index for o in operands
            if o.is_dot_rhs and (o.shape, o.dtype) in out_set)
    else:
        problem.weight_idx = frozenset(
            o.index for o in operands
            if o.is_dot_rhs and not o.is_dot_lhs)
    if oracle_closed is not None:
        ins, outs, mode = _harvest_oracle_specs(oracle_closed)
        problem.oracle_mode = mode
        problem.oracle_specs = ins
        problem.oracle_out_specs = outs
        if mode is not None:
            problem.oracle_compute = rollup(oracle_closed)
            problem.oracle_comm = comm_rollup(
                oracle_closed, mesh=oracle_mesh, device_kind=device)
            problem.oracle_peak_bytes = estimate_memory(
                oracle_closed).peak_bytes
    return problem


# ------------------------------------------------------------- plans


@dataclass
class Candidate:
    name: str
    mesh_shape: Dict[str, int]
    specs: List[Tuple]              # normalized (dim, (axes...)) tuples
    out_specs: List[Tuple]
    est: CommEstimate
    dot_factor: Dict[int, int] = field(default_factory=dict)
    act_factor: int = 1             # temporaries shrink by this
    note: str = ""
    oracle: bool = False


@dataclass
class PlanCost:
    candidate: Candidate
    compute_s: float
    comm_s: float
    peak_hbm_bytes: float
    feasible: bool
    violated: str = ""

    @property
    def step_s(self) -> float:
        return self.compute_s + self.comm_s


@dataclass
class PlanReport:
    entry: str
    device: str
    mesh_total: int
    chosen: Optional[PlanCost]
    oracle: Optional[PlanCost]
    ranked: List[PlanCost]

    def to_json_dict(self) -> dict:
        def cost_dict(pc: Optional[PlanCost], why: str = "") -> dict:
            if pc is None:
                return {}
            c = pc.candidate
            d = {
                "name": c.name,
                "mesh_shape": dict(sorted(c.mesh_shape.items())),
                "in_specs": [spec_str(s) for s in c.specs],
                "out_specs": [spec_str(s) for s in c.out_specs],
                "compute_ms": round(pc.compute_s * 1e3, 6),
                "comm_ms": round(pc.comm_s * 1e3, 6),
                "step_ms": round(pc.step_s * 1e3, 6),
                "peak_hbm_gib": round(
                    pc.peak_hbm_bytes / (1 << 30), 6),
                "feasible": pc.feasible,
            }
            if pc.violated:
                d["violated"] = pc.violated
            if why:
                d["why_rejected"] = why
            if c.note:
                d["note"] = c.note
            return d

        rejected = []
        for pc in self.ranked:
            if self.chosen is not None and pc is self.chosen:
                continue
            rejected.append(cost_dict(pc, why=self._why_lost(pc)))
        payload = {
            "schema": "paddle_tpu.plan.v1",
            "entry": self.entry,
            "device": self.device,
            "mesh": self.mesh_total,
            "n_candidates": len(self.ranked),
            "chosen": cost_dict(self.chosen),
            "oracle": cost_dict(self.oracle),
            "rejected": rejected,
        }
        if (self.chosen is not None and self.oracle is not None
                and self.oracle.step_s > 0):
            payload["chosen_vs_oracle"] = round(
                self.chosen.step_s / self.oracle.step_s, 6)
        return payload

    def _why_lost(self, pc: PlanCost) -> str:
        if not pc.feasible:
            return pc.violated
        w = self.chosen
        if w is None:
            return ""
        dc = pc.compute_s - w.compute_s
        dm = pc.comm_s - w.comm_s
        if dm >= dc and dm > 0:
            return (f"comm {pc.comm_s * 1e3:.4f}ms vs winner "
                    f"{w.comm_s * 1e3:.4f}ms "
                    f"({pc.candidate.est.n_collectives:g} collectives)")
        if dc > 0:
            return (f"compute {pc.compute_s * 1e3:.4f}ms vs winner "
                    f"{w.compute_s * 1e3:.4f}ms (less parallelism)")
        return "ties the winner; ranked below by name"


def spec_str(spec: Sequence) -> str:
    """Executable ``P(...)`` source for a normalized spec tuple."""
    parts = []
    for entry in spec:
        if entry is None or entry == ():
            parts.append("None")
        elif isinstance(entry, (tuple, list)):
            if len(entry) == 1:
                parts.append(repr(entry[0]))
            else:
                parts.append("(" + ", ".join(repr(a) for a in entry) + ")")
        else:
            parts.append(repr(entry))
    while parts and parts[-1] == "None":
        parts.pop()
    return "P(" + ", ".join(parts) + ")"


def _norm(spec_entries: Sequence) -> Tuple:
    """Canonical per-dim tuple form: each dim -> tuple of axis names."""
    out = []
    for e in spec_entries:
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(e))
        else:
            out.append((e,))
    while out and out[-1] == ():
        out.pop()
    return tuple(out)


def _shard_factor(spec: Tuple, mesh_shape: Dict[str, int]) -> int:
    f = 1
    for dim in spec:
        for ax in dim:
            f *= int(mesh_shape.get(ax, 1))
    return f


def _match_out_specs(problem: PlanProblem, specs: List[Tuple],
                     mesh_shape: Dict[str, int]) -> List[Tuple]:
    """Outputs that alias a planned operand's aval keep its spec (the
    TPC502 no-reshard-at-the-boundary convention: cycled state like KV
    pages leaves sharded the way it came in); everything else is
    replicated."""
    by_aval: Dict[Tuple, Tuple] = {}
    for op, spec in zip(problem.operands, specs):
        by_aval.setdefault((op.shape, op.dtype), spec)
    return [by_aval.get((shape, dtype), ())
            for shape, dtype in problem.out_avals]


def _divisible(shape: Tuple[int, ...], dim: int, n: int) -> bool:
    return (0 <= dim < len(shape) and shape[dim] >= n
            and shape[dim] % n == 0)


def _spec_sharding(op: Operand, dim: int, axis: str) -> Tuple:
    entries: List[Tuple] = [() for _ in op.shape]
    entries[dim] = (axis,)
    return _norm(entries)


def _template_candidates(problem: PlanProblem, mesh_shape: Dict[str, int],
                         device: str,
                         include_replicated: bool = False
                         ) -> List[Candidate]:
    """The split templates at one mesh shape. Axis names double as the
    split kind; a template that finds nothing to shard at this shape is
    skipped (it would be `replicated` wearing a different name)."""
    bw = ici_bw(device)
    out: List[Candidate] = []
    axes = list(mesh_shape.items())

    def base_specs() -> List[Tuple]:
        return [() for _ in problem.operands]

    def add(name, specs, collectives, dot_factor, act_factor, note,
            shape_override=None):
        est = CommEstimate(device_kind=device)
        for prim, payload, n_axis, count in collectives:
            if n_axis <= 1 or count <= 0 or payload <= 0:
                continue
            wire, steps, secs = collective_cost(
                prim, payload, payload * (n_axis if prim == "all_gather"
                                          else 1), n_axis, bw)
            est.add(prim, wire * count, steps * count, secs * count,
                    count=count)
        specs = [_norm(s) if not isinstance(s, tuple) else s
                 for s in specs]
        shape = shape_override or dict(mesh_shape)
        out.append(Candidate(
            name=name, mesh_shape=shape, specs=specs,
            out_specs=_match_out_specs(problem, specs, shape),
            est=est, dot_factor=dot_factor, act_factor=act_factor,
            note=note))

    # ---- replicated baseline: every device runs the whole program
    if include_replicated:
        total = 1
        for n in mesh_shape.values():
            total *= n
        add("replicated", base_specs(), [], {}, 1,
            "baseline: no sharding, no comm, no speedup",
            shape_override={"x": total})

    for ax_name, ax_n in axes:
        if ax_n <= 1:
            continue
        # ---- DP: shard the leading (batch) dim of pure-data operands
        if ax_name == "dp":
            specs = base_specs()
            sharded = []
            for op in problem.operands:
                if (op.is_dot_lhs and op.index not in problem.weight_idx
                        and len(op.shape) >= 2
                        and _divisible(op.shape, 0, ax_n)):
                    specs[op.index] = _spec_sharding(op, 0, ax_name)
                    sharded.append(op.index)
            if sharded:
                colls = []
                if problem.trains:
                    # grad all-reduce over every replicated parameter
                    out_set = set(problem.out_avals)
                    for op in problem.operands:
                        if op.index in sharded:
                            continue
                        if (op.index in problem.weight_idx
                                or (op.shape, op.dtype) in out_set):
                            colls.append(("psum", float(op.nbytes),
                                          ax_n, 1.0))
                dot_factor = {d: ax_n for d, du in enumerate(problem.dots)
                              if du.lhs in sharded}
                add(f"dp{ax_n}", specs, colls, dot_factor, ax_n,
                    "batch split; weights replicated"
                    + (", grads all-reduced" if problem.trains else ""))
        # ---- TP: Megatron column/row alternation over 2-D weights
        elif ax_name == "tp":
            specs = base_specs()
            sharded: Dict[int, str] = {}
            order = []
            seen = set()
            for du in problem.dots:
                if (du.rhs is not None and du.rhs not in seen
                        and du.rhs in problem.weight_idx):
                    seen.add(du.rhs)
                    order.append(du.rhs)
            col = True
            col_out_dims: List[int] = []
            for idx in order:
                op = problem.operands[idx]
                if len(op.shape) != 2:
                    continue
                if col and _divisible(op.shape, 1, ax_n):
                    specs[idx] = _spec_sharding(op, 1, ax_name)
                    sharded[idx] = "col"
                    col_out_dims.append(op.shape[1])
                    col = False
                elif not col and _divisible(op.shape, 0, ax_n):
                    specs[idx] = _spec_sharding(op, 0, ax_name)
                    sharded[idx] = "row"
                    col = True
            # 1-D biases riding a column-sharded out dim shard with it
            for op in problem.operands:
                if (len(op.shape) == 1 and op.shape[0] in col_out_dims
                        and _divisible(op.shape, 0, ax_n)):
                    specs[op.index] = _spec_sharding(op, 0, ax_name)
            # >=3-D head-carrying operands (KV page pools) shard their
            # trailing feature dim
            for op in problem.operands:
                if (len(op.shape) >= 3 and not op.is_dot_rhs
                        and _divisible(op.shape, len(op.shape) - 1, ax_n)
                        and op.nbytes >= 4096):
                    specs[op.index] = _spec_sharding(
                        op, len(op.shape) - 1, ax_name)
            if sharded:
                colls = []
                n_row = 0
                for d, du in enumerate(problem.dots):
                    if sharded.get(du.rhs) == "row":
                        n_row += 1
                        colls.append(("psum", du.out_bytes, ax_n,
                                      du.scale))
                if problem.trains:
                    # the backward f collective mirrors each forward g
                    for d, du in enumerate(problem.dots):
                        if sharded.get(du.rhs) == "row":
                            colls.append(("psum", du.out_bytes, ax_n,
                                          du.scale))
                dot_factor = {d: ax_n for d, du in enumerate(problem.dots)
                              if du.rhs in sharded}
                add(f"tp{ax_n}", specs, colls, dot_factor, ax_n,
                    f"Megatron column/row split, {n_row} g-psum(s)")
        # ---- SP: shard the sequence dim of >=3-D activations
        elif ax_name == "sp":
            specs = base_specs()
            sharded = []
            for op in problem.operands:
                if (op.is_dot_lhs and op.index not in problem.weight_idx
                        and len(op.shape) >= 3
                        and _divisible(op.shape, 1, ax_n)):
                    specs[op.index] = _spec_sharding(op, 1, ax_name)
                    sharded.append(op.index)
            if len(sharded) >= 2:  # ring attention needs q AND k/v split
                kv_bytes = sum(problem.operands[i].nbytes / ax_n
                               for i in sharded[1:])
                colls = [("ppermute", kv_bytes / max(len(sharded) - 1, 1),
                          ax_n, float(ax_n - 1) * (len(sharded) - 1))]
                dot_factor = {d: ax_n for d, du in enumerate(problem.dots)
                              if du.lhs in sharded}
                add(f"sp{ax_n}", specs, colls, dot_factor, ax_n,
                    "sequence (ring) split; KV shards rotate")
        # ---- EP: shard the expert-stacked leading dim
        elif ax_name == "ep":
            specs = base_specs()
            experts = []
            tokens = []
            for op in problem.operands:
                if (op.index in problem.weight_idx
                        and len(op.shape) >= 2
                        and _divisible(op.shape, 0, ax_n)):
                    specs[op.index] = _spec_sharding(op, 0, ax_name)
                    experts.append(op.index)
                elif (op.is_dot_lhs and op.index not in problem.weight_idx
                        and len(op.shape) >= 2
                        and _divisible(op.shape, 0, ax_n)):
                    specs[op.index] = _spec_sharding(op, 0, ax_name)
                    tokens.append(op.index)
            if experts and tokens:
                tok_bytes = sum(problem.operands[i].nbytes / ax_n
                                for i in tokens)
                colls = [("all_to_all", tok_bytes, ax_n, 2.0)]
                dot_factor = {d: ax_n for d, du in enumerate(problem.dots)
                              if du.rhs in experts or du.lhs in tokens}
                add(f"ep{ax_n}", specs, colls, dot_factor, ax_n,
                    "expert split; dispatch+combine all_to_all")
        # ---- PP: shard a stage-stacked weight dim (no stage SEARCH —
        # the honest gap: this places one template, it does not
        # partition the graph into stages)
        elif ax_name == "pp":
            specs = base_specs()
            stages = []
            for op in problem.operands:
                if (op.index in problem.weight_idx and len(op.shape) >= 3
                        and op.shape[0] == ax_n):
                    specs[op.index] = _spec_sharding(op, 0, ax_name)
                    stages.append(op.index)
            if stages:
                act = max((op.nbytes for op in problem.operands
                           if op.is_dot_lhs and not op.is_dot_rhs),
                          default=0)
                n_ticks = max((du.scale for du in problem.dots
                               if du.rhs in stages), default=1.0)
                colls = [("ppermute", float(act), ax_n, n_ticks)]
                dot_factor = {d: ax_n for d, du in enumerate(problem.dots)
                              if du.rhs in stages}
                add(f"pp{ax_n}", specs, colls, dot_factor, 1,
                    "stage-stacked split; per-tick boundary ppermute")
    return out


def _merge_candidates(problem: PlanProblem, a: Candidate, b: Candidate,
                      mesh_shape: Dict[str, int], device: str
                      ) -> Optional[Candidate]:
    """Hybrid of two 1-axis candidates on a 2-axis mesh (dp x tp): specs
    merge where they don't collide, comm and dot factors compose."""
    specs: List[Tuple] = []
    for sa, sb in zip(a.specs, b.specs):
        if sa and sb and sa != sb:
            return None  # colliding assignment: not a valid hybrid
        specs.append(sa or sb)
    est = CommEstimate(device_kind=device)
    _merge_comm(est, a.est)
    _merge_comm(est, b.est)
    dot_factor = dict(a.dot_factor)
    for d, f in b.dot_factor.items():
        dot_factor[d] = dot_factor.get(d, 1) * f
    return Candidate(
        name=f"{a.name}x{b.name}", mesh_shape=dict(mesh_shape),
        specs=specs,
        out_specs=_match_out_specs(problem, specs, mesh_shape),
        est=est, dot_factor=dot_factor,
        act_factor=a.act_factor * b.act_factor,
        note=f"hybrid: {a.note} + {b.note}")


def _mesh_shapes(total: int) -> List[Dict[str, int]]:
    """1-axis shapes for each split kind, plus 2-axis dp x tp hybrids."""
    shapes: List[Dict[str, int]] = []
    for ax in ("dp", "tp", "sp", "ep", "pp"):
        shapes.append({ax: total})
    for a in range(2, total):
        if total % a == 0:
            shapes.append({"dp": a, "tp": total // a})
    return shapes


def enumerate_candidates(problem: PlanProblem, mesh_total: int,
                         device: str) -> List[Candidate]:
    cands: List[Candidate] = []
    seen = set()

    def push(c: Candidate):
        key = (tuple(c.specs), tuple(sorted(c.mesh_shape.items())))
        if key not in seen:
            seen.add(key)
            cands.append(c)

    first = True
    for shape in _mesh_shapes(mesh_total):
        if len(shape) == 1:
            for c in _template_candidates(problem, shape, device,
                                          include_replicated=first):
                push(c)
            first = False
        else:
            parts = []
            for ax, n in shape.items():
                sub = _template_candidates(problem, {ax: n}, device)
                parts.append([c for c in sub if c.name != "replicated"])
            if len(parts) == 2 and parts[0] and parts[1]:
                for a in parts[0]:
                    for b in parts[1]:
                        m = _merge_candidates(problem, a, b, shape, device)
                        if m is not None:
                            push(m)
    # the hand-written plan competes on its own traced costs
    if problem.oracle_mode is not None:
        n_ops = len(problem.operands)
        if problem.oracle_specs is not None:
            pairs = list(problem.oracle_specs)
            # a shard_map region may carry extra leading const operands;
            # align the tail with the program's operands
            if len(pairs) > n_ops:
                pairs = pairs[len(pairs) - n_ops:]
            while len(pairs) < n_ops:
                pairs.append(())
            specs = [_pairs_to_dims(p, len(op.shape))
                     for p, op in zip(pairs, problem.operands)]
        else:
            specs = [() for _ in range(n_ops)]
        out_pairs = problem.oracle_out_specs or []
        outs = [_pairs_to_dims(p, len(shape))
                for p, (shape, _) in zip(out_pairs, problem.out_avals)]
        while len(outs) < len(problem.out_avals):
            outs.append(())
        note = ("the hand-written sharding, priced from its own trace"
                if problem.oracle_mode == "shard_map" else
                "the hand-written GSPMD constraints (compute assumed "
                "perfectly partitioned)")
        push(Candidate(
            name="oracle", mesh_shape={"mesh": mesh_total},
            specs=specs, out_specs=outs,
            est=problem.oracle_comm or CommEstimate(device_kind=device),
            note=note, oracle=True))
    return cands


# ------------------------------------------------------------- pricing


def price_candidate(problem: PlanProblem, cand: Candidate, device: str,
                    calibration: Optional[Dict[str, dict]] = None
                    ) -> PlanCost:
    """comm ⊕ compute ⊕ liveness gate, the ISSUE 16 composition."""
    peak = peak_flops(device)
    hbw = hbm_bw(device)
    ibw = ici_bw(device)
    cap = hbm_capacity(device)
    mesh_total = 1
    for n in cand.mesh_shape.values():
        mesh_total *= n

    if cand.oracle and problem.oracle_mode == "gspmd":
        # GSPMD traces keep GLOBAL shapes: assume the partitioner's own
        # ideal — compute and residency divided evenly across the mesh
        compute_s = max(problem.total_flops / mesh_total / peak,
                        problem.total_hbm_bytes / mesh_total / hbw)
        peak_hbm = (sum(op.nbytes for op in problem.operands)
                    + problem.peak_temp_bytes) / mesh_total
    elif cand.oracle and problem.oracle_compute is not None:
        # per-shard trace: its rollup already IS the per-device cost
        cr = problem.oracle_compute
        compute_s = sum(max(f / peak, b / hbw)
                        for f, b in cr.by_prim.values())
        peak_hbm = float(problem.oracle_peak_bytes or 0)
    else:
        dot_flops_saved = 0.0
        for d, du in enumerate(problem.dots):
            f = cand.dot_factor.get(d, 1)
            if f > 1:
                dot_flops_saved += du.flops * (1.0 - 1.0 / f)
        flops_eff = max(problem.total_flops - dot_flops_saved, 0.0)
        bytes_saved = 0.0
        for op, spec in zip(problem.operands, cand.specs):
            f = _shard_factor(spec, cand.mesh_shape)
            if f > 1:
                bytes_saved += op.use_bytes * (1.0 - 1.0 / f)
        bytes_eff = max(problem.total_hbm_bytes - bytes_saved, 0.0)
        if cand.act_factor > 1:
            # activation traffic (the non-operand share) shrinks too
            operand_traffic = sum(op.use_bytes for op in problem.operands)
            act_traffic = max(bytes_eff - operand_traffic, 0.0)
            bytes_eff -= act_traffic * (1.0 - 1.0 / cand.act_factor)
        compute_s = max(flops_eff / peak, bytes_eff / hbw)
        arg_bytes = sum(
            op.nbytes / _shard_factor(spec, cand.mesh_shape)
            for op, spec in zip(problem.operands, cand.specs))
        peak_hbm = arg_bytes + problem.peak_temp_bytes / max(
            cand.act_factor, 1)

    comm_s = cand.est.seconds_at(ibw, ICI_LATENCY_S,
                                 ICI_COLLECTIVE_OVERHEAD_S,
                                 calibration=calibration)
    pc = PlanCost(candidate=cand, compute_s=compute_s, comm_s=comm_s,
                  peak_hbm_bytes=peak_hbm, feasible=True)
    if peak_hbm > cap:
        pc.feasible = False
        pc.violated = (f"peak HBM {_fmt_bytes(int(peak_hbm))} exceeds "
                       f"{device} capacity {_fmt_bytes(int(cap))}")
        return pc
    audit = audit_candidate(problem, cand, mesh_total)
    if audit:
        pc.feasible = False
        pc.violated = audit
    return pc


def audit_candidate(problem: PlanProblem, cand: Candidate,
                    mesh_total: int) -> str:
    """The planner's self-audit: the TPC501/502/503 predicates applied
    to the plan it is about to emit. A non-empty string disqualifies.
    Oracle candidates are exempt — their real traces already sweep
    through the full sharding pass in ``make analyze``, and the
    harvested-spec alignment here is best-effort."""
    if mesh_total <= 1 or cand.oracle:
        return ""
    # TPC501: a large operand left fully replicated
    for op, spec in zip(problem.operands, cand.specs):
        if (op.nbytes >= MIN_SHARDING_BYTES
                and _shard_factor(spec, cand.mesh_shape) == 1):
            return (f"TPC501: would replicate operand {op.label} "
                    f"({_fmt_bytes(op.nbytes)}) across {mesh_total} "
                    f"devices")
    # TPC502: an output aliasing an operand must keep its spec
    by_aval: Dict[Tuple, Tuple] = {}
    for op, spec in zip(problem.operands, cand.specs):
        by_aval.setdefault((op.shape, op.dtype), spec)
    for (shape, dtype), ospec in zip(problem.out_avals, cand.out_specs):
        want = by_aval.get((shape, dtype))
        if want is not None and _norm(ospec) != _norm(want):
            return (f"TPC502: output {dtype}{list(shape)} would reshard "
                    f"at the boundary ({spec_str(_norm(ospec))} vs "
                    f"operand's {spec_str(_norm(want))})")
    # TPC503: degenerate collectives (size-1 axes) or a gather
    # materializing a large result
    for kind, t in cand.est.by_kind.items():
        if t.n > 0 and t.steps == 0 and kind != "ppermute":
            return f"TPC503: degenerate {kind} over a size-1 axis"
        if (kind == "all_gather" and t.n > 0
                and t.wire / max(t.n, 1) >= MIN_SHARDING_BYTES):
            return ("TPC503: all_gather would materialize "
                    f"{_fmt_bytes(int(t.wire / max(t.n, 1)))} per "
                    "collective")
    return ""


# ------------------------------------------------------------- driver


def plan_program(closed, *, entry: str = "program", mesh_total: int,
                 device: str = "v5e", oracle_closed=None,
                 oracle_mesh=None,
                 calibration: Optional[Dict[str, dict]] = None
                 ) -> PlanReport:
    """Plan one traced program: extract the problem from the mesh-1
    trace, enumerate and price candidates (oracle included when its
    mesh-N trace is supplied), gate on HBM and the self-audit, rank."""
    kind = device_kind(device)
    problem = extract_problem(closed, entry=entry,
                              oracle_closed=oracle_closed,
                              oracle_mesh=oracle_mesh, device=kind)
    cands = enumerate_candidates(problem, mesh_total, kind)
    priced = [price_candidate(problem, c, kind, calibration=calibration)
              for c in cands]
    # deterministic rank: feasible first, then step time, then name
    priced.sort(key=lambda pc: (not pc.feasible, pc.step_s,
                                pc.candidate.name))
    chosen = next((pc for pc in priced if pc.feasible), None)
    oracle = next((pc for pc in priced if pc.candidate.oracle), None)
    return PlanReport(entry=entry, device=kind, mesh_total=mesh_total,
                      chosen=chosen, oracle=oracle, ranked=priced)
