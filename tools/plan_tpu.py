#!/usr/bin/env python
"""tpuplan CLI — autosharding planner over the tpucheck registry
(``make plan``).

For each meshable registry entry this traces the program twice — once
unsharded (mesh 1) to extract the plan problem, once at the target mesh
to price the hand-written sharding as the *oracle* candidate — then
runs :func:`paddle_tpu.analysis.jaxpr.planner.plan_program`: enumerate
mesh shapes × axis assignments × (DP/TP/SP/EP/PP) splits, price each
with comm ⊕ compute ⊕ the liveness HBM gate, self-audit with the
TPC501/502/503 predicates, and rank.

Modes:

* default — human-readable report: the winning ``in_specs``/
  ``out_specs`` as executable ``P(...)`` source plus the ranked
  rejected-plans table with per-plan comm/compute/HBM and why each lost;
* ``--json`` — the sorted/diffable payload (`paddle_tpu.plan.v1`), one
  object per (entry, mesh), written to ``--out-dir`` as
  ``{entry}_m{mesh}_{device}.json`` when given;
* ``--check-goldens DIR`` — CI gate: re-plan and byte-compare against
  committed fixtures; any drift is a regression (exit 1);
* ``--fail-on-audit`` — CI gate: exit 1 if any entry ends with no
  feasible plan, or with a chosen plan costing more than the
  hand-written oracle (the planner must never lose to the spec it was
  inverted from);
* ``--calibrated FILE`` — price comm with the host-calibrated
  per-collective curves from a MULTICHIP_r16-style artifact instead of
  the pure device tables (goldens always use device tables so they
  stay host-independent).

Exit codes: 0 clean, 1 regression/audit failure, 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import analyze_tpu as registry  # noqa: E402  (forces virtual devices)

# entries the planner sweeps: every meshable registry entry
PLAN_ENTRIES = [e.name for e in registry.ENTRIES if e.meshable]
# the committed golden fixtures (satellite: ≥3 entries, byte-stable)
GOLDEN_ENTRIES = ("tp_train_step", "tp_sharded_decode_step",
                  "moe_ep_gspmd", "moe_decode_step")
GOLDEN_MESH = 8
GOLDEN_DEVICE = "v5e"


def _trace(entry, mesh_n: int):
    """Trace one registry entry at one mesh size (no analysis passes —
    the planner prices the raw jaxpr)."""
    import jax

    saved = registry._MESH_N
    registry._MESH_N = mesh_n
    try:
        fn, args, kw = entry.build()
    finally:
        registry._MESH_N = saved
    static = tuple(kw.get("static_argnums", ()))
    closed = jax.make_jaxpr(fn, static_argnums=static)(*args)
    return closed, kw.get("mesh")


def plan_entry(name: str, mesh_n: int, device: str,
               calibration: Optional[Dict[str, dict]] = None):
    """Plan one registry entry: mesh-1 problem trace + mesh-N oracle."""
    from paddle_tpu.analysis.jaxpr.planner import plan_program

    entry = next((e for e in registry.ENTRIES if e.name == name), None)
    if entry is None:
        raise SystemExit(f"plan_tpu: unknown entry {name!r} "
                         f"(--list-entries)")
    closed, _ = _trace(entry, 1)
    oracle_closed, oracle_mesh = _trace(entry, mesh_n)
    return plan_program(closed, entry=name, mesh_total=mesh_n,
                        device=device, oracle_closed=oracle_closed,
                        oracle_mesh=oracle_mesh, calibration=calibration)


def payload_text(report) -> str:
    return json.dumps(report.to_json_dict(), indent=2,
                      sort_keys=True) + "\n"


def golden_name(entry: str, mesh_n: int, device: str) -> str:
    return f"{entry}_m{mesh_n}_{device}.json"


def _render_text(report) -> List[str]:
    d = report.to_json_dict()
    lines = [f"== {report.entry} @ mesh {report.mesh_total} "
             f"({report.device}) — {d['n_candidates']} candidates"]
    ch = d.get("chosen")
    if not ch:
        lines.append("  NO FEASIBLE PLAN")
        return lines
    lines.append(f"  chosen: {ch['name']}  step {ch['step_ms']:.4f}ms "
                 f"(compute {ch['compute_ms']:.4f} + comm "
                 f"{ch['comm_ms']:.4f})  peak HBM "
                 f"{ch['peak_hbm_gib']:.3f}GiB")
    if "chosen_vs_oracle" in d:
        lines.append(f"  vs hand-written: {d['chosen_vs_oracle']:.4f}x")
    lines.append(f"    in_specs  = ({', '.join(ch['in_specs'])})")
    lines.append(f"    out_specs = ({', '.join(ch['out_specs'])})")
    for r in d.get("rejected", []):
        why = r.get("why_rejected") or r.get("violated") or ""
        tag = "" if r["feasible"] else " [infeasible]"
        lines.append(f"  - {r['name']}{tag}: step {r['step_ms']:.4f}ms "
                     f"(comm {r['comm_ms']:.4f}, hbm "
                     f"{r['peak_hbm_gib']:.3f}GiB) — {why}")
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="plan_tpu",
        description="tpuplan — autosharding planner over the tpucheck "
                    "registry entries.")
    ap.add_argument("--entry", action="append", default=None,
                    help="entry name (repeatable; default: all meshable)")
    ap.add_argument("--mesh", action="append", type=int, default=None,
                    help="mesh size to plan for (repeatable; default 8)")
    ap.add_argument("--device", default="v5e",
                    choices=("v4", "v5e", "v5p", "v6e"),
                    help="target device tables (default v5e)")
    ap.add_argument("--json", action="store_true",
                    help="emit the sorted/diffable JSON payloads")
    ap.add_argument("--out-dir", default=None,
                    help="write one {entry}_m{mesh}_{device}.json per "
                         "plan into this directory")
    ap.add_argument("--check-goldens", default=None, metavar="DIR",
                    help="byte-compare payloads against committed "
                         "fixtures in DIR (CI regression gate)")
    ap.add_argument("--fail-on-audit", action="store_true",
                    help="exit 1 if any entry has no feasible plan or "
                         "the chosen plan costs more than the oracle")
    ap.add_argument("--calibrated", default=None, metavar="FILE",
                    help="price comm with the host-calibrated curves "
                         "from a MULTICHIP_r16-style JSON artifact")
    ap.add_argument("--list-entries", action="store_true")
    args = ap.parse_args(argv)

    if args.list_entries:
        for name in PLAN_ENTRIES:
            print(name)
        return 0

    entries = args.entry or list(PLAN_ENTRIES)
    meshes = args.mesh or [8]
    for name in entries:
        if name not in PLAN_ENTRIES:
            print(f"plan_tpu: {name!r} is not a meshable registry entry",
                  file=sys.stderr)
            return 2
    calibration = None
    if args.calibrated:
        try:
            with open(args.calibrated) as f:
                payload = json.load(f)
            calibration = (payload.get("tp_step", {})
                           .get("calibration", {}).get("coll_curves"))
        except (OSError, ValueError) as e:
            print(f"plan_tpu: cannot load calibration: {e}",
                  file=sys.stderr)
            return 2

    failures: List[str] = []
    payloads = []
    for name in entries:
        for mesh_n in meshes:
            report = plan_entry(name, mesh_n, args.device,
                                calibration=calibration)
            payloads.append((name, mesh_n, report))
            d = report.to_json_dict()
            if report.chosen is None:
                failures.append(f"{name}@m{mesh_n}: no feasible plan")
            elif (report.oracle is not None and report.oracle.feasible
                    and report.chosen.step_s
                    > report.oracle.step_s * 1.000001):
                failures.append(
                    f"{name}@m{mesh_n}: chosen plan "
                    f"({report.chosen.candidate.name}) costs "
                    f"{d.get('chosen_vs_oracle')}x the hand-written "
                    f"oracle")
            if args.check_goldens:
                gpath = os.path.join(
                    args.check_goldens,
                    golden_name(name, mesh_n, args.device))
                if os.path.exists(gpath):
                    with open(gpath) as f:
                        want = f.read()
                    got = payload_text(report)
                    if got != want:
                        failures.append(
                            f"{name}@m{mesh_n}: plan drifted from "
                            f"golden {gpath} (re-bless with --out-dir "
                            f"after reviewing the diff)")
            if args.out_dir:
                os.makedirs(args.out_dir, exist_ok=True)
                opath = os.path.join(
                    args.out_dir, golden_name(name, mesh_n, args.device))
                with open(opath, "w") as f:
                    f.write(payload_text(report))

    if args.json:
        blob = {f"{name}@m{mesh_n}": r.to_json_dict()
                for name, mesh_n, r in payloads}
        print(json.dumps(blob, indent=2, sort_keys=True))
    else:
        for name, mesh_n, r in payloads:
            for line in _render_text(r):
                print(line)
        if failures:
            print()
    for msg in failures:
        print(f"plan_tpu: FAIL {msg}", file=sys.stderr)
    if failures and (args.fail_on_audit or args.check_goldens):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
