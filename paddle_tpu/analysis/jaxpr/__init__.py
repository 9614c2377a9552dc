"""tpucheck — jaxpr-level program analysis for the compiled path.

Where tpulint (``paddle_tpu.analysis``, pure-AST) reads what the source
*says*, this package analyzes what the tracer actually *built*: run
``jax.make_jaxpr`` over any ``StaticFunction``/pjit entry point and six
passes inspect the traced program with concrete shapes, dtypes, mesh
axes and donation decisions —

* **liveness** — backward liveness → peak-HBM estimate + the top-k live
  buffers at the high-water mark (validated against
  ``Compiled.memory_analysis()``);
* **collectives** — axis names vs the active mesh, collectives under
  value-dependent control flow (multi-host deadlock), malformed
  ppermutes;
* **donation** — donated-but-unusable buffers (silent copy) and missed
  copy-free donation opportunities;
* **cost** — roofline FLOPs/HBM-bytes rollup with a predicted step time;
* **sharding** (tpushard) — implicit full replication of parameter-
  sized shard_map operands, resharding copies at region boundaries,
  degenerate/materializing collectives, and the host-divergence
  detector (trace under simulated process identities);
* **comm** (tpushard) — per-collective ICI roofline over ring/torus
  cost formulas: predicted comm time, comm/compute overlap fraction,
  predicted multichip step time (the multichip harness records the
  measured counterpart).

Findings carry stable ``TPC1xx``–``TPC6xx`` IDs and render through the
tpulint reporter. Run via ``make analyze`` / ``python
tools/analyze_tpu.py``, opt into trace-time analysis with
``FLAGS_analyze_on_compile=1`` (findings land in the metrics registry
as ``paddle_tpu_analysis_findings_total{pass,rule}``), or
programmatically:

    from paddle_tpu.analysis.jaxpr import analyze_fn
    report = analyze_fn(train_step, params, batch, donate_argnums=(0,))
    assert not report.gating()
"""
from .core import (AnalysisReport, Finding, analyze_fn,  # noqa: F401
                   analyze_jaxpr, flatten, mesh_axis_sizes)
from .rules import JRULES, JaxprRule  # noqa: F401
from .liveness import LivenessPass, MemoryEstimate, estimate_memory  # noqa: F401
from .collectives import CollectivePass  # noqa: F401
from .donation import DonationPass  # noqa: F401
from .cost import (CostModelPass, CostRollup, rollup, rollup_fn,  # noqa: F401
                   peak_flops, hbm_bw)
from .sharding import ShardingPass  # noqa: F401
from .comm import (CommCostPass, CommEstimate, comm_rollup,  # noqa: F401
                   ici_bw, ici_latency, predicted_step_seconds)
from .planner import (PlanProblem, PlanReport, extract_problem,  # noqa: F401
                      plan_program)
from .divergence import check_host_divergence, trace_signature  # noqa: F401

__all__ = [
    "AnalysisReport", "Finding", "analyze_fn", "analyze_jaxpr", "flatten",
    "mesh_axis_sizes",
    "JRULES", "JaxprRule",
    "LivenessPass", "MemoryEstimate", "estimate_memory",
    "CollectivePass", "DonationPass",
    "CostModelPass", "CostRollup", "rollup", "rollup_fn",
    "peak_flops", "hbm_bw",
    "ShardingPass",
    "CommCostPass", "CommEstimate", "comm_rollup", "ici_bw", "ici_latency",
    "predicted_step_seconds",
    "PlanProblem", "PlanReport", "extract_problem", "plan_program",
    "check_host_divergence", "trace_signature",
]
