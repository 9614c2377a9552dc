"""tpucheck (paddle_tpu.analysis.jaxpr) suite.

Four layers of proof, mirroring what the subsystem promises:

* **Golden reports** — every fixture under ``tests/fixtures/analysis/``
  must produce EXACTLY the rule IDs its committed JSON twin records:
  each pass fires on its seeded bug, stays silent on its clean twin.
* **Estimator validation** — the liveness peak (temps+outputs axis) must
  land within 20% of ``Compiled.memory_analysis()`` on the real entry
  points (llama decode step, hapi train step, quant matmul) — the
  acceptance band that makes TPC101 trustworthy.
* **Cost-model ground truths** — dot FLOPs are exact, scans multiply by
  their static length.
* **Toolchain** — the ``make analyze`` registry sweeps clean (this is
  what chains the gate into tier-1), the CLI renders/exits correctly,
  and ``FLAGS_analyze_on_compile`` lands findings in the metrics
  registry without perturbing the entry's result.
"""
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "fixtures", "analysis")
sys.path.insert(0, os.path.join(REPO, "tools"))

FIXTURES = sorted(
    f[:-3] for f in os.listdir(FIXDIR)
    if f.endswith(".py") and f != "__init__.py")


def _golden(name):
    with open(os.path.join(FIXDIR, "expected", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _fixture_report(name):
    mod = importlib.import_module(f"tests.fixtures.analysis.{name}")
    return mod.run()


class TestGoldenReports:
    @pytest.mark.parametrize("name", FIXTURES)
    def test_exact_rule_ids(self, name):
        report = _fixture_report(name)
        want = _golden(name)
        got_gating = sorted({f.rule for f in report.gating()})
        got_info = sorted({f.rule for f in report.findings
                           if f.severity == "info"})
        assert got_gating == want["gating"], (
            f"{name}: gating findings drifted from golden\n"
            f"  got:  {got_gating}\n  want: {want['gating']}\n  "
            + "\n  ".join(f"{f.rule}: {f.message}" for f in report.gating()))
        assert got_info == want["info"], (
            f"{name}: advisory findings drifted from golden: "
            f"{got_info} != {want['info']}")
        for rule, frag in want.get("message_contains", {}).items():
            msgs = [f.message for f in report.findings if f.rule == rule]
            assert any(frag in m for m in msgs), (rule, frag, msgs)
        for rule, kv in want.get("finding_data", {}).items():
            datas = [f.data for f in report.findings if f.rule == rule]
            assert any(all(d.get(k) == v for k, v in kv.items())
                       for d in datas), (rule, kv, datas)

    def test_high_water_live_set_golden(self):
        report = _fixture_report("mem_oom")
        want = _golden("mem_oom")["high_water_top"]
        est = report.memory
        assert est is not None and est.high_water
        top = est.high_water[0]
        assert list(top.shape) == want["shape"]
        assert top.dtype == want["dtype"]
        # the TPC102 report carries the same data for dashboards/CLI
        tpc102 = [f for f in report.findings if f.rule == "TPC102"]
        assert tpc102 and tpc102[0].data["high_water"]
        assert "4096" in tpc102[0].data["high_water"][0]

    def test_every_pass_has_seeded_bug_and_clean_fixture(self):
        """The acceptance criterion, asserted structurally: per pass, at
        least one fixture fires a gating finding and one is clean. The
        comm family's seeded shape is the TPC601 advisory (info by
        design — it prices, it does not gate), so that family counts
        info hits."""
        by_pass = {"liveness": [], "collectives": [], "donation": [],
                   "cost": [], "sharding": [], "comm": []}
        clean_names = set()
        fam = {"TPC1": "liveness", "TPC2": "collectives",
               "TPC3": "donation", "TPC4": "cost", "TPC5": "sharding",
               "TPC6": "comm"}
        for name in FIXTURES:
            g = _golden(name)
            if not g["gating"]:
                clean_names.add(name)
            for rule in g["gating"]:
                by_pass[fam[rule[:4]]].append(name)
            if name.startswith("comm_") and "TPC601" in g["info"]:
                by_pass["comm"].append(name)
        for passname, hits in by_pass.items():
            assert hits, f"no seeded-bug fixture fires for {passname}"
        for prefix in ("mem_", "coll_", "donate_", "cost_", "shard_",
                       "comm_", "div_"):
            assert any(n.startswith(prefix) for n in clean_names), (
                f"no clean fixture for {prefix}*")


class TestEstimatorValidation:
    """Peak-memory estimate vs Compiled.memory_analysis() on the real
    entry points (acceptance: within 20% on >= 3 of them, CPU)."""

    TOL = 0.20

    def _check(self, fn, args):
        from paddle_tpu.analysis.jaxpr import estimate_memory

        closed = jax.make_jaxpr(fn)(*args)
        est = estimate_memory(closed)
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        want = ma.temp_size_in_bytes + ma.output_size_in_bytes
        got = est.peak_temp_out_bytes
        assert want > 0
        ratio = got / want
        assert abs(ratio - 1.0) <= self.TOL, (
            f"estimate {got} vs measured {want} (ratio {ratio:.3f}) "
            f"outside the {self.TOL:.0%} band")
        return ratio

    def test_llama_decode_step(self):
        from analyze_tpu import ENTRIES

        entry = next(e for e in ENTRIES if e.name == "llama_decode_step")
        fn, args, _ = entry.build()
        self._check(fn, args)

    def test_hapi_train_step(self):
        from analyze_tpu import ENTRIES

        entry = next(e for e in ENTRIES if e.name == "hapi_train_step")
        fn, args, _ = entry.build()
        self._check(fn, args)

    def test_quant_matmul(self):
        from analyze_tpu import ENTRIES

        entry = next(e for e in ENTRIES if e.name == "quant_matmul_int8")
        fn, args, _ = entry.build()
        self._check(fn, args)


class TestCostModel:
    def test_dot_flops_exact(self):
        from paddle_tpu.analysis.jaxpr import rollup_fn

        M, K, N = 64, 128, 256
        cr = rollup_fn(lambda a, b: a @ b,
                       jnp.ones((M, K)), jnp.ones((K, N)))
        assert cr.by_prim["dot_general"][0] == 2.0 * M * K * N

    def test_scan_multiplies_by_length(self):
        from paddle_tpu.analysis.jaxpr import rollup_fn

        T, M = 12, 64

        def step(c, x):
            return c @ x, ()

        def f(c, xs):
            out, _ = jax.lax.scan(step, c, xs)
            return out

        cr = rollup_fn(f, jnp.ones((M, M)), jnp.ones((T, M, M)))
        assert cr.flops == pytest.approx(T * 2.0 * M * M * M, rel=0.05)

    def test_predicted_seconds_positive_and_device_scaled(self):
        from paddle_tpu.analysis.jaxpr import rollup_fn

        cr = rollup_fn(lambda a, b: a @ b,
                       jnp.ones((512, 512)), jnp.ones((512, 512)))
        v5e = cr.predicted_seconds("TPU v5e")
        v5p = cr.predicted_seconds("TPU v5p")
        assert v5e > 0 and v5p > 0 and v5p < v5e

    def test_f64_flagged_only_on_f64(self):
        from paddle_tpu.analysis.jaxpr import rollup_fn

        cr = rollup_fn(lambda a, b: a @ b,
                       jnp.ones((64, 64)), jnp.ones((64, 64)))
        assert cr.f64_ops == []


class TestToolchain:
    # slow: duplicates the `make analyze` gate (the full registry sweep
    # runs there on every make test); tier-1 wall budget
    @pytest.mark.slow
    def test_registry_sweeps_clean(self):
        """The `make analyze` gate: every registered entry point analyzes
        with ZERO unsuppressed error/warn findings, and any suppression
        carries a written justification (tpulint's standard)."""
        from analyze_tpu import ENTRIES, run_entry

        for e in ENTRIES:
            for rule, reason in e.suppress.items():
                assert reason.strip(), (
                    f"{e.name}: suppression of {rule} has no justification")
            report = run_entry(e)
            gating = [f for f in report.gating()
                      if f.rule not in e.suppress]
            assert not gating, (
                f"{e.name}: unsuppressed findings: "
                + "; ".join(f"{f.rule} {f.message[:80]}" for f in gating))

    def test_cli_text_and_exit_codes(self, capsys):
        from analyze_tpu import main

        assert main(["--entry", "quant_matmul_int8",
                     "--fail-on-violation"]) == 0
        out = capsys.readouterr().out
        assert "tpucheck:" in out
        assert main(["--entry", "nope"]) == 2
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("TPC101", "TPC201", "TPC301", "TPC401"):
            assert rid in out

    def test_cli_json(self, capsys):
        from analyze_tpu import main

        assert main(["--entry", "hapi_train_step", "--format",
                     "json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["entries"] == ["hapi_train_step"]
        assert payload["memory"]["hapi_train_step"]["peak_bytes"] > 0
        assert payload["cost"]["hapi_train_step"]["flops"] > 0

    def test_findings_render_like_tpulint(self):
        report = _fixture_report("mem_oom")
        line = next(f for f in report.findings
                    if f.rule == "TPC101").to_violation().format()
        # path:line:col: RULE message — greppable like make lint
        assert line.startswith("mem_oom:") or line.startswith("f:"), line
        assert ": TPC101 " in line


class TestAnalyzeOnCompileHook:
    def test_hook_counts_findings_and_preserves_result(self):
        from paddle_tpu.framework import flags
        from paddle_tpu.framework.tensor import Tensor
        from paddle_tpu.jit import to_static
        from paddle_tpu.observability import REGISTRY, metric_total

        before_runs = metric_total("paddle_tpu_analysis_runs_total") \
            if REGISTRY.get("paddle_tpu_analysis_runs_total") else 0.0
        flags.set_flags({"FLAGS_analyze_on_compile": True})
        try:
            @to_static
            def entry(x):
                return (x * 3).sum()

            out = entry(Tensor._wrap(jnp.ones((16, 16))))
            assert float(np.asarray(jax.device_get(out._data))) == 768.0
            runs = metric_total("paddle_tpu_analysis_runs_total")
            assert runs == before_runs + 1
            c = REGISTRY.get("paddle_tpu_analysis_findings_total")
            assert c is not None
            labelled = dict(c.series())
            # the liveness high-water advisory fires on any program
            assert any(key[1] == "TPC102" and leaf.value >= 1
                       for key, leaf in labelled.items())
            # second call, same signature: no re-analysis
            entry(Tensor._wrap(jnp.ones((16, 16))))
            assert metric_total("paddle_tpu_analysis_runs_total") == runs
        finally:
            flags.set_flags({"FLAGS_analyze_on_compile": False})

    def test_hook_failure_is_contained(self):
        """A crashing analysis must not break the entry point."""
        import warnings

        from paddle_tpu.analysis.jaxpr import hook

        def boom(*a):
            raise RuntimeError("fixture crash")

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            hook.analyze_and_record(boom, (jnp.ones(2),), "boom_entry")
        assert any("tpucheck hook failed" in str(x.message) for x in w)


class TestCommModel:
    """tpushard comm roofline: cost-formula ground truths + the ICI
    tables tools/multichip.py reprices against."""

    def test_collective_cost_formulas_exact(self):
        from paddle_tpu.analysis.jaxpr.comm import collective_cost

        S, n, bw, lat = 1 << 20, 8, 200e9, 1e-6
        frac = (n - 1) / n
        wire, steps, secs = collective_cost("psum", S, S, n, bw, lat)
        assert wire == 2.0 * S * frac and steps == 2 * (n - 1)
        assert secs == pytest.approx(wire / bw + steps * lat)
        wire, steps, _ = collective_cost("all_gather", S, S * n, n, bw)
        assert wire == S * n * frac and steps == n - 1
        wire, steps, _ = collective_cost("psum_scatter", S, S // n, n, bw)
        assert wire == S * frac
        wire, steps, _ = collective_cost("all_to_all", S, S, n, bw)
        assert wire == S * frac
        wire, steps, _ = collective_cost("ppermute", S, S, n, bw)
        assert wire == S and steps == 1
        # a 1-way axis communicates nothing
        assert collective_cost("psum", S, S, 1, bw) == (0.0, 0.0, 0.0)

    def test_rollup_counts_shard_map_psum(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.analysis.jaxpr import comm_rollup, ici_bw
        from paddle_tpu.distributed.jax_compat import shard_map

        ndev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()).reshape(ndev), ("dp",))
        g = jnp.ones((256, 256), jnp.float32)

        def f(g):
            return shard_map(lambda x: jax.lax.psum(x, "dp"), mesh,
                             in_specs=P(), out_specs=P(),
                             check=False)(g)

        est = comm_rollup(jax.make_jaxpr(f)(g), mesh=mesh)
        S = 256 * 256 * 4
        assert est.n_collectives == 1
        assert est.wire_bytes == pytest.approx(2 * S * (ndev - 1) / ndev)
        # repricing under a different link speed scales the byte term
        fast = est.seconds_at(ici_bw("TPU v5p"))
        slow = est.seconds_at(ici_bw("TPU v5e"))
        assert slow > fast > 0

    def test_scan_multiplies_comm(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.analysis.jaxpr import comm_rollup
        from paddle_tpu.distributed.jax_compat import shard_map

        ndev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()).reshape(ndev), ("dp",))
        x = jnp.ones((8, 64), jnp.float32)
        T = 6

        def f(x):
            def body(xs):
                def tick(c, _):
                    return jax.lax.psum(c, "dp"), ()

                c, _ = jax.lax.scan(tick, xs, None, length=T)
                return c

            return shard_map(body, mesh, in_specs=P(), out_specs=P(),
                             check=False)(x)

        est = comm_rollup(jax.make_jaxpr(f)(x), mesh=mesh)
        S = 8 * 64 * 4
        assert est.wire_bytes == pytest.approx(
            T * 2 * S * (ndev - 1) / ndev)

    def test_overlap_window_hides_comm(self):
        """A collective whose first consumer sits behind a big matmul
        overlaps; one consumed immediately does not."""
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.analysis.jaxpr import comm_rollup
        from paddle_tpu.distributed.jax_compat import shard_map

        ndev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()).reshape(ndev), ("dp",))
        g = jnp.ones((128, 128), jnp.float32)
        a = jnp.ones((1024, 1024), jnp.float32)

        def overlapped(g, a):
            def body(g, a):
                r = jax.lax.psum(g, "dp")
                big = a @ a          # independent compute window
                return r + big[:128, :128]

            return shard_map(body, mesh, in_specs=(P(), P()),
                             out_specs=P(), check=False)(g, a)

        def eager(g, a):
            def body(g, a):
                r = jax.lax.psum(g, "dp")
                s = r * 2.0          # consumed immediately
                big = a @ a
                return s + big[:128, :128]

            return shard_map(body, mesh, in_specs=(P(), P()),
                             out_specs=P(), check=False)(g, a)

        e1 = comm_rollup(jax.make_jaxpr(overlapped)(g, a), mesh=mesh)
        e2 = comm_rollup(jax.make_jaxpr(eager)(g, a), mesh=mesh)
        assert e1.overlap_fraction > 0.9
        assert e2.overlap_fraction < e1.overlap_fraction

    def test_one_peak_table_and_unknown_kind_raises(self):
        from paddle_tpu.analysis.jaxpr import hbm_bw, ici_bw, peak_flops
        from paddle_tpu.analysis.jaxpr.cost import DEVICE_PEAKS
        from paddle_tpu.analysis.jaxpr.planner import hbm_capacity
        from paddle_tpu.profiler import mfu

        for kind in DEVICE_PEAKS:
            # ICI is always the slower fabric — a sanity invariant the
            # comm-bound advisory depends on
            assert ici_bw(kind) < hbm_bw(kind)
        # the attached v5e reports itself as "TPU v5 lite"
        assert peak_flops("TPU v5 lite") == peak_flops("TPU v5e") == 197e12
        # a device the table does not hold is an error, never another
        # chip's numbers: an unknown TPU, the CPU, an empty kind
        for kind in ("TPU v9x", "cpu", ""):
            for fn in (peak_flops, hbm_bw, ici_bw, hbm_capacity):
                with pytest.raises(ValueError, match="unknown device kind"):
                    fn(kind)
        # profiler.mfu asks the attached device (the CPU here)
        with pytest.raises(ValueError, match="unknown device kind"):
            mfu(n_params=1e9, tokens_per_sec_per_chip=1000)


class TestHostDivergence:
    def test_patch_is_restored(self):
        from paddle_tpu.analysis.jaxpr import check_host_divergence

        orig_idx, orig_cnt = jax.process_index, jax.process_count
        check_host_divergence(lambda x: x * 2, (jnp.ones(4),),
                              n_processes=2)
        assert jax.process_index is orig_idx
        assert jax.process_count is orig_cnt

    def test_identical_traces_are_silent(self):
        from paddle_tpu.analysis.jaxpr import check_host_divergence

        assert check_host_divergence(
            lambda x: jnp.tanh(x) * 3, (jnp.ones((8, 8)),),
            n_processes=4) == []

    def test_structural_divergence_detected(self):
        from paddle_tpu.analysis.jaxpr import check_host_divergence

        def f(x):
            if jax.process_index() == 0:
                return jnp.tanh(x)
            return x

        (finding,) = check_host_divergence(f, (jnp.ones(4),),
                                           n_processes=2)
        assert finding.rule == "TPC510"
        assert "different programs" in finding.message

    def test_baked_scalar_divergence_detected(self):
        from paddle_tpu.analysis.jaxpr import check_host_divergence

        def f(x):
            return x * np.float32(jax.process_index() + 1)

        (finding,) = check_host_divergence(f, (jnp.ones(4),),
                                           n_processes=2)
        assert finding.rule == "TPC510"
        assert "literal" in finding.message

    def test_process_count_divergence_detected(self):
        """Branching on process_count vs a threshold also diverges the
        program when the count changes the structure."""
        from paddle_tpu.analysis.jaxpr import check_host_divergence

        def f(x):
            # pathological: per-process shift baked via process_index
            shift = jnp.full((4,), float(jax.process_index()))
            return x + shift

        (finding,) = check_host_divergence(f, (jnp.ones(4),),
                                           n_processes=2)
        assert finding.rule == "TPC510"


class TestMeshSweep:
    """--mesh N: the distributed entries stay clean at every swept mesh
    shape (the make-analyze gate runs 1/4/8; 8 is pytest's default
    device count and covered by test_registry_sweeps_clean)."""

    @pytest.mark.parametrize("mesh_n", [1, 4])
    def test_meshable_entries_clean(self, mesh_n):
        from analyze_tpu import ENTRIES, run_entry

        for e in ENTRIES:
            if not e.meshable:
                continue
            report = run_entry(e, mesh_n=mesh_n,
                               label=f"{e.name}@m{mesh_n}")
            gating = [f for f in report.gating() if f.rule not in e.suppress]
            assert not gating, (
                f"{e.name}@m{mesh_n}: "
                + "; ".join(f"{f.rule} {f.message[:80]}" for f in gating))

    def test_registry_has_distributed_programs(self):
        """ISSUE 10 acceptance: >= 14 entries including TP, pipeline,
        context-parallel and MoE programs."""
        from analyze_tpu import ENTRIES

        names = {e.name for e in ENTRIES}
        assert len(ENTRIES) >= 14
        for want in ("tp_train_step", "pipeline_1f1b_stage",
                     "context_parallel_attention", "moe_all_to_all",
                     "moe_ep_gspmd"):
            assert want in names

    def test_virtual_mesh_abstract_fallback(self):
        """Requesting more devices than exist falls back to AbstractMesh
        and still TRACES shard_map programs (the device-free compat
        path the --mesh sweep relies on)."""
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.analysis.jaxpr import analyze_fn, mesh_axis_sizes
        from paddle_tpu.distributed.jax_compat import (shard_map,
                                                       virtual_mesh)

        n = 4 * len(jax.devices())  # beyond the local device count
        mesh = virtual_mesh({"dp": n})
        assert mesh_axis_sizes(mesh) == {"dp": n}
        assert type(mesh).__name__ == "AbstractMesh"  # device-free

        def f(x):
            return shard_map(lambda xs: jax.lax.psum(xs, "dp"), mesh,
                             in_specs=P("dp"), out_specs=P(),
                             check=False)(x)

        report = analyze_fn(f, jnp.ones((n * 2,)), mesh=mesh)
        assert not report.gating()
        assert report.comm is not None and report.comm.n_collectives == 1

    def test_concrete_mesh_when_devices_suffice(self):
        from paddle_tpu.distributed.jax_compat import virtual_mesh

        ndev = len(jax.devices())
        mesh = virtual_mesh({"dp": ndev})
        assert hasattr(mesh, "devices")


class TestDonationFlatExpansion:
    def test_pytree_donation_expands_to_leaves(self):
        """donate_argnums follows jit semantics: donating a pytree arg
        donates every leaf."""
        from paddle_tpu.analysis.jaxpr import analyze_fn

        def step(params, x):
            return ({k: v - 1.0 for k, v in params.items()},
                    jnp.mean(x))

        params = {"a": jnp.ones((512, 512)), "b": jnp.ones((512, 512))}
        report = analyze_fn(step, params, jnp.ones((8,)),
                            donate_argnums=(0,))
        # both leaves alias cleanly: no TPC301
        assert not [f for f in report.findings if f.rule == "TPC301"]
