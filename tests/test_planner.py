"""tpuplan tests (ISSUE 16): the autosharding planner and the
recalibrated collective model it prices with.

Three layers:

* the committed calibration artifact (``MULTICHIP_r16.json``) — the
  decode/train prediction bands the tentpole gates on, and the
  per-collective-kind payload-sweep fits (overhead + per-byte slope,
  residual asserted by refitting the committed points);
* the calibrated ``CommEstimate.seconds_at`` path itself (synthetic
  traffic, exact arithmetic);
* the planner — template enumeration, oracle dominance, golden
  byte-stability against ``tests/fixtures/plan/``, the
  TPC501/502/503 self-audit, and the seeded-bad twin where a
  deliberately replicated plan must lose to the sharded winner at
  non-toy shapes.
"""
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

MULTICHIP = os.path.join(REPO, "MULTICHIP_r16.json")
PLAN_FIXTURES = os.path.join(REPO, "tests", "fixtures", "plan")


def _artifact():
    with open(MULTICHIP, encoding="utf-8") as f:
        return json.load(f)


# --------------------------------------------------------- calibration


class TestCommittedCalibration:
    def test_decode_band_and_train_gate(self):
        """The tentpole's acceptance bands, asserted on the committed
        artifact: decode pred_vs_measured in [0.8, 1.25], train <= 1.15."""
        d = _artifact()
        assert d["ok"] is True
        serving = d["tp_serving"]
        assert 0.8 <= serving["decode_pred_vs_measured"] <= 1.25
        assert 0.8 <= serving["mixed_pred_vs_measured"] <= 1.25
        assert d["tp_step"]["pred_vs_measured"] <= 1.15

    def test_payload_sweep_recorded_per_kind(self):
        """r11 calibrated from ONE tiny-psum point; r16 must carry a
        decode-sized payload sweep for every collective kind."""
        curves = _artifact()["tp_step"]["calibration"]["coll_curves"]
        assert {"psum", "all_gather", "reduce_scatter", "all_to_all",
                "ppermute"} <= set(curves)
        for kind, c in curves.items():
            assert c["overhead_s"] >= 0.0, kind
            assert c["per_byte_s"] >= 0.0, kind
            pts = c["points"]
            assert len(pts) >= 3, f"{kind}: not a sweep"
            payloads = [p[0] for p in pts]
            assert max(payloads) / max(min(payloads), 1) >= 64, \
                f"{kind}: payload range too narrow to fit a slope"

    def test_fit_residual(self):
        """Refit the committed sweep points and check the recorded
        residual is honest (matches a fresh least-squares fit) and
        small enough to trust the decode-regime extrapolation."""
        curves = _artifact()["tp_step"]["calibration"]["coll_curves"]
        for kind, c in curves.items():
            pts = c["points"]  # [payload_bytes, wire, steps, per_coll_s]
            xs = [p[1] for p in pts]
            ys = [p[3] for p in pts]
            n = len(pts)
            mx, my = sum(xs) / n, sum(ys) / n
            sxx = sum((x - mx) ** 2 for x in xs)
            slope = (sum((x - mx) * (y - my) for x, y in zip(xs, ys))
                     / sxx if sxx else 0.0)
            slope = max(slope, 0.0)
            inter = max(my - slope * mx, 0.0)
            pred = [inter + slope * x for x in xs]
            rms = math.sqrt(sum((p - y) ** 2
                                for p, y in zip(pred, ys)) / n)
            resid = rms / my if my > 0 else 0.0
            assert resid == pytest.approx(c["residual_rel"], abs=0.02), \
                f"{kind}: recorded residual is not the fit residual"
            assert c["residual_rel"] < 0.35, \
                f"{kind}: fit too loose to calibrate with"

    def test_calibrated_seconds_at_math(self):
        """The calibrated path prices each kind as
        n*overhead + wire*per_byte (the curve intercept already folds
        the ring-step latency at the calibration mesh), falling back to
        the scalar roofline for unknown kinds."""
        from paddle_tpu.analysis.jaxpr.comm import CommEstimate

        est = CommEstimate(device_kind="TPU v5e")
        est.add("psum", wire=7168.0, steps=28.0, seconds=1e-4,
                count=2.0)
        est.add("assumed_reshard", wire=4096.0, steps=2.0, seconds=5e-5,
                count=2.0)
        cal = {"psum": {"overhead_s": 8e-5, "per_byte_s": 1e-9}}
        got = est.seconds_at(1e11, latency=1e-6, per_collective_s=3e-6,
                             calibration=cal)
        want_psum = 2.0 * 8e-5 + 7168.0 * 1e-9
        want_fallback = 4096.0 / 1e11 + 2.0 * 1e-6 + 2.0 * 3e-6
        assert got == pytest.approx(want_psum + want_fallback, rel=1e-9)

    def test_scan_scaled_collective_counts(self):
        """A collective inside a scan of length L pays the dispatch
        floor L times — the r11 model counted it once, which is exactly
        why decode (many small in-scan collectives) mispredicted."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis.jaxpr.comm import comm_rollup
        from paddle_tpu.distributed.jax_compat import (shard_map,
                                                       virtual_mesh)

        mesh = virtual_mesh({"dp": 8})
        from jax.sharding import PartitionSpec as P

        def body(x):
            def step(c, _):
                return jax.lax.psum(c, "dp") * 0.5, ()

            out, _ = jax.lax.scan(step, x, None, length=5)
            return out

        fn = shard_map(body, mesh, in_specs=P(), out_specs=P())
        closed = jax.make_jaxpr(fn)(jnp.ones((4, 4), jnp.float32))
        est = comm_rollup(closed, mesh=mesh)
        assert est.n_collectives == 5.0
        assert est.by_kind["psum"].n == 5.0


# --------------------------------------------------------- the planner


def _toy_problem_closed():
    import jax
    import jax.numpy as jnp

    H, FF, B = 64, 256, 32

    def fwd(x, w1, w2):
        h = jnp.maximum(x @ w1, 0.0)
        return h @ w2

    return jax.make_jaxpr(fwd)(
        jnp.zeros((B, H), jnp.float32), jnp.zeros((H, FF), jnp.float32),
        jnp.zeros((FF, H), jnp.float32))


class TestPlanner:
    def test_plan_space_and_report_shape(self):
        from paddle_tpu.analysis.jaxpr.planner import plan_program

        report = plan_program(_toy_problem_closed(), entry="toy",
                              mesh_total=8, device="v5e")
        names = {pc.candidate.name for pc in report.ranked}
        assert "replicated" in names
        assert "tp8" in names
        assert report.chosen is not None
        d = report.to_json_dict()
        assert d["schema"] == "paddle_tpu.plan.v1"
        # every rejected plan names why it lost
        for r in d["rejected"]:
            assert r.get("why_rejected") or r.get("violated"), r["name"]

    def test_specs_are_executable(self):
        from jax.sharding import PartitionSpec
        from paddle_tpu.analysis.jaxpr.planner import plan_program

        report = plan_program(_toy_problem_closed(), entry="toy",
                              mesh_total=8, device="v5e")
        for pc in report.ranked:
            for src in (report.to_json_dict().get("chosen", {})
                        .get("in_specs", [])):
                spec = eval(src, {"P": PartitionSpec})  # noqa: S307
                assert isinstance(spec, PartitionSpec)

    def test_device_retargeting_changes_pricing(self):
        """--device retargets the tables: v5p's fatter ICI must price
        the same comm strictly cheaper than v5e's."""
        from paddle_tpu.analysis.jaxpr.planner import plan_program

        closed = _toy_problem_closed()
        v5e = plan_program(closed, entry="toy", mesh_total=8,
                           device="v5e")
        v5p = plan_program(closed, entry="toy", mesh_total=8,
                           device="v5p")
        tp_e = next(pc for pc in v5e.ranked
                    if pc.candidate.name == "tp8")
        tp_p = next(pc for pc in v5p.ranked
                    if pc.candidate.name == "tp8")
        assert tp_p.comm_s < tp_e.comm_s
        assert v5p.device == "TPU v5p"

    def test_seeded_bad_twin_replication_loses(self):
        """At non-toy shapes the deliberately replicated plan must lose
        to the sharded winner: TPC501 disqualifies it outright AND the
        sharded plan is faster even before the audit."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis.jaxpr.planner import plan_program

        H, FF, B = 2048, 8192, 256

        def fwd(x, w1, w2):
            h = jnp.maximum(x @ w1, 0.0)
            return h @ w2

        closed = jax.make_jaxpr(fwd)(
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((H, FF), jnp.float32),
            jax.ShapeDtypeStruct((FF, H), jnp.float32))
        report = plan_program(closed, entry="seeded_bad", mesh_total=8,
                              device="v5e")
        rep = next(pc for pc in report.ranked
                   if pc.candidate.name == "replicated")
        assert not rep.feasible
        assert "TPC501" in rep.violated
        assert report.chosen is not None
        assert report.chosen.candidate.name != "replicated"
        assert report.chosen.step_s < rep.step_s
        # the winner shards the big weights
        assert any(s for s in report.chosen.candidate.specs)

    def test_hbm_gate_prunes_with_budget_attached(self):
        """A plan that cannot fit per-device HBM is pruned with the
        violated budget named, not silently dropped."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis.jaxpr.planner import plan_program

        H = 1 << 14  # 16Ki x 64Ki f32 weight = 4GiB; v5e HBM = 16GiB

        def fwd(x, w1, w2):
            h = x @ w1
            return h @ w2

        closed = jax.make_jaxpr(fwd)(
            jax.ShapeDtypeStruct((64, H), jnp.float32),
            jax.ShapeDtypeStruct((H, 4 * H), jnp.float32),
            jax.ShapeDtypeStruct((4 * H, H), jnp.float32))
        report = plan_program(closed, entry="hbm_gate", mesh_total=8,
                              device="v5e")
        d = report.to_json_dict()
        infeasible = [r for r in d["rejected"] if not r["feasible"]]
        assert infeasible
        assert any("exceeds" in r.get("violated", "")
                   or "TPC" in r.get("violated", "") for r in infeasible)

    def test_registry_plan_beats_handwritten_and_is_stable(self):
        """tp_train_step through the real registry: chosen <= oracle,
        payload byte-stable across runs, and matching the committed
        golden fixture."""
        import plan_tpu

        r1 = plan_tpu.plan_entry("tp_train_step", 8, "v5e")
        r2 = plan_tpu.plan_entry("tp_train_step", 8, "v5e")
        t1, t2 = plan_tpu.payload_text(r1), plan_tpu.payload_text(r2)
        assert t1 == t2, "plan payload is not byte-stable"
        assert r1.oracle is not None
        assert r1.chosen.step_s <= r1.oracle.step_s * 1.000001
        golden = os.path.join(
            PLAN_FIXTURES, plan_tpu.golden_name("tp_train_step", 8,
                                                "v5e"))
        with open(golden, encoding="utf-8") as f:
            assert f.read() == t1, (
                "plan drifted from the committed golden; review the "
                "diff and re-bless with tools/plan_tpu.py --out-dir "
                "tests/fixtures/plan")

    def test_golden_fixtures_exist_for_required_entries(self):
        for entry in ("tp_train_step", "tp_sharded_decode_step",
                      "moe_ep_gspmd"):
            path = os.path.join(PLAN_FIXTURES,
                                f"{entry}_m8_v5e.json")
            assert os.path.exists(path), path
            with open(path, encoding="utf-8") as f:
                d = json.load(f)
            assert d["schema"] == "paddle_tpu.plan.v1"
            assert d["chosen"]["feasible"] is True
            # sorted/diffable like analyze_tpu --json
            assert json.dumps(d, indent=2, sort_keys=True) + "\n" == \
                json.dumps(d, indent=2, sort_keys=True) + "\n"

    def test_oracle_exempt_audit_but_templates_are_not(self):
        """The self-audit must disqualify template plans that TPC501
        would flag, while the chosen plan is always audit-clean."""
        from paddle_tpu.analysis.jaxpr.planner import (audit_candidate,
                                                       extract_problem,
                                                       plan_program)

        report = plan_program(_toy_problem_closed(), entry="toy",
                              mesh_total=8, device="v5e")
        assert report.chosen.feasible
        problem = extract_problem(_toy_problem_closed(), entry="toy")
        assert audit_candidate(problem, report.chosen.candidate, 8) == ""
