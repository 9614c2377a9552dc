#!/usr/bin/env python
"""Multichip harness (ISSUE 10 satellite): structured per-suite timings
and a measured-vs-predicted communication roofline for the TP step.

``MULTICHIP_r*.json`` used to record only ``{n_devices, rc, ok, tail}``
— a green light with no numbers, so the tpushard comm pass (TPC601) had
no measured counterpart to track drift against. This harness emits:

* **suites** — wall time of each strategy-surface dryrun
  (``__graft_entry__``'s hybrid pipeline, sep ring attention, MoE EP,
  auto-parallel Engine, stage-3 sharding);
* **tp_step** — the tensor-parallel train step measured three ways:
  the full step, a collective-stripped local twin (their difference is
  the MEASURED comm fraction), and the tpushard-predicted step time
  under a host-calibrated device profile (matmul flops, memcpy
  bandwidth, and per-collective-step latency are measured on THIS
  host, then fed through the same cost formulas the TPC601 advisory
  uses) — with the predicted/measured ratio.

Runs on the virtual-8-CPU-device mesh (no TPU slice needed); on a real
slice the same code measures real ICI. ``--json`` prints one
machine-readable object; the driver-visible ``dryrun_multichip`` prints
the same object on its ``MULTICHIP_METRICS`` tail line.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from typing import Dict, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _force_virtual_devices(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m:
        if int(m.group(1)) < n:
            os.environ["XLA_FLAGS"] = flags.replace(
                m.group(0), f"--xla_force_host_platform_device_count={n}")
    else:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


_force_virtual_devices()


# ------------------------------------------------------------ calibration

# payload sweep shape: collectives per swept program and the payload
# grid (f32 element counts, all divisible by the 8-device mesh). The
# grid brackets both regimes: decode-sized psums (~2KiB) up through
# train-step activations (~1MiB).
_SWEEP_COLLECTIVES = 4
_SWEEP_ELEMS = (512, 4096, 32768, 262144)

_CAL_CACHE: Optional[Dict[str, object]] = None


def _sweep_programs(kind: str, ndev: int, elems: int):
    """(full, twin) jitted shard_map programs issuing
    ``_SWEEP_COLLECTIVES`` chained collectives of ``kind`` over an
    ``elems``-float replicated payload, with a tiny serializing compute
    op between rounds; the twin swaps each collective for a local
    shape-preserving identity (the strip_collectives convention), so
    ``(t_full - t_twin)/K`` is the IN-PROGRAM cost of one collective —
    rendezvous floor included, unlike an isolated microbench where the
    floor cancels against the empty-dispatch baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.jax_compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:ndev]).reshape(ndev), ("dp",))
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]

    def coll(v):
        if kind == "psum":
            return jax.lax.psum(v, "dp")
        if kind == "all_gather":
            return jax.lax.all_gather(v, "dp")
        if kind == "reduce_scatter":
            return jax.lax.psum_scatter(v, "dp", tiled=True)
        if kind == "all_to_all":
            return jax.lax.all_to_all(v.reshape(ndev, -1), "dp", 0, 0)
        if kind == "ppermute":
            return jax.lax.ppermute(v, "dp", perm)
        raise ValueError(kind)

    def twin(v):
        if kind == "psum":
            return v
        if kind == "all_gather":
            return jnp.broadcast_to(v[None], (ndev,) + v.shape)
        if kind == "reduce_scatter":
            return v.reshape(ndev, -1).sum(0)
        if kind == "all_to_all":
            return v.reshape(ndev, -1)
        if kind == "ppermute":
            return v
        raise ValueError(kind)

    def make(with_collectives: bool):
        def body(x):
            acc = jnp.float32(0.0)
            v = x
            for i in range(_SWEEP_COLLECTIVES):
                y = coll(v) if with_collectives else twin(v)
                acc = acc + jnp.sum(y) * jnp.float32(1e-9)
                # data dependence serializes the rounds without adding
                # meaningful compute (a broadcast add over the payload)
                v = x + acc * jnp.float32(1e-9)
            return acc
        return jax.jit(shard_map(body, mesh, in_specs=P(),
                                 out_specs=P(), check=False))

    return make(True), make(False)


def _sweep_collective_curves(ndev: int) -> Dict[str, Dict[str, object]]:
    """Per-collective-kind overhead-vs-payload fit (the ISSUE 16
    recalibration): each kind is timed IN-PROGRAM across the payload
    grid, and ``per_coll = overhead + per_byte * wire_bytes`` is
    least-squares fit over the sweep at the calibration mesh size (ring
    steps, fixed at that size, fold into the intercept). The intercept
    is the explicit dispatch-floor term — the rendezvous every
    collective pays once regardless of payload, which the r11 one-point
    fit subtracted away and which dominates the decode regime."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.analysis.jaxpr.comm import collective_cost

    prim_of = {"psum": "psum", "all_gather": "all_gather",
               "reduce_scatter": "psum_scatter",
               "all_to_all": "all_to_all", "ppermute": "ppermute"}
    curves: Dict[str, Dict[str, object]] = {}
    for kknd, prim in prim_of.items():
        pts = []  # (payload_bytes, wire_bytes, steps, per_coll_seconds)
        for elems in _SWEEP_ELEMS:
            x = jnp.ones((elems,), jnp.float32)
            full, twin = _sweep_programs(kknd, ndev, elems)
            full(x).block_until_ready()
            twin(x).block_until_ready()
            t_full = sorted(_timed(
                lambda: full(x).block_until_ready(), 9))[4]
            t_twin = sorted(_timed(
                lambda: twin(x).block_until_ready(), 9))[4]
            S = float(x.nbytes)
            O = S * ndev if kknd == "all_gather" else (
                S / ndev if kknd == "reduce_scatter" else S)
            wire, steps, _ = collective_cost(prim, S, O, ndev, 1.0, 0.0)
            per_coll = max(0.0, (t_full - t_twin) / _SWEEP_COLLECTIVES)
            pts.append((S, wire, steps, per_coll))
        xs = np.array([w for _, w, _, _ in pts])
        ys = np.array([t for _, _, _, t in pts])
        slope, intercept = np.polyfit(xs, ys, 1)
        per_byte = float(max(slope, 0.0))
        overhead = float(max(intercept, 0.0))
        pred = overhead + per_byte * xs
        mean_y = float(np.mean(ys))
        residual = (float(np.sqrt(np.mean((pred - ys) ** 2))) / mean_y
                    if mean_y > 0 else 0.0)
        curves[kknd] = {
            "overhead_s": overhead,
            "per_byte_s": per_byte,
            "residual_rel": residual,
            "points": [[float(p), float(w), float(s), float(t)]
                       for p, w, s, t in pts],
        }
    return curves


def calibrate_host() -> Dict[str, object]:
    """Measured peaks of THIS host, the device profile the prediction
    prices against: dense matmul flops/s, memcpy bytes/s, and the
    collective cost model.

    Calibration rework round 2 (ISSUE 16, ROADMAP item 5 first step):
    r11 fit ONE tiny psum (32 bytes) at ring sizes {2,4,8} and shared
    that line across every collective kind, so decode-shaped programs —
    many small in-program collectives — extrapolated from zero data and
    mispredicted 15x (measured decode comm fraction 0.207 vs predicted
    0.014). Now, on top of the ring-size fit (which still supplies the
    per-hop latency slope), every collective KIND is timed in-program
    across a decode-sized payload sweep and fit to
    ``overhead + per_byte * wire`` with the dispatch floor as the
    explicit intercept; the curves feed
    ``CommEstimate.seconds_at(..., calibration=...)`` (the same rollup
    TPC601 uses) and land the decode ratio in the 0.8-1.25 gate
    recorded in MULTICHIP_r16.json."""
    global _CAL_CACHE
    if _CAL_CACHE is not None:
        return _CAL_CACHE

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.jax_compat import shard_map

    # flops: a 512^3 matmul, best of 3
    a = jnp.ones((512, 512), jnp.float32)
    mm = jax.jit(lambda a: a @ a)
    mm(a).block_until_ready()
    best = min(_timed(lambda: mm(a).block_until_ready(), 3))
    flops = 2.0 * 512 ** 3 / best

    # memory bandwidth: copy 32MiB, read+write
    big = jnp.ones((8 << 20,), jnp.float32)  # 32MiB
    cp = jax.jit(lambda x: x + 1.0)
    cp(big).block_until_ready()
    best = min(_timed(lambda: cp(big).block_until_ready(), 3))
    membw = 2.0 * big.nbytes / best

    ndev = len(jax.devices())
    lat, overhead, dispatch = 20e-6, 0.0, 0.0
    curves: Dict[str, Dict[str, object]] = {}
    if ndev > 1:
        tiny = jnp.ones((8,), jnp.float32)
        sizes = sorted({2, max(2, ndev // 2), ndev})
        pts = []  # (ring steps, collective seconds above dispatch floor)
        for n in sizes:
            mesh = Mesh(np.array(jax.devices()[:n]).reshape(n), ("dp",))
            ps = jax.jit(shard_map(
                lambda x: jax.lax.psum(x, "dp"), mesh,
                in_specs=P(), out_specs=P(), check=False))
            nop = jax.jit(shard_map(
                lambda x: x + 0.0, mesh,
                in_specs=P(), out_specs=P(), check=False))
            ps(tiny).block_until_ready()
            nop(tiny).block_until_ready()
            t_ps = sorted(_timed(
                lambda: ps(tiny).block_until_ready(), 9))[4]
            t_nop = sorted(_timed(
                lambda: nop(tiny).block_until_ready(), 9))[4]
            if n == ndev:
                dispatch = t_nop
            pts.append((2.0 * (n - 1), max(0.0, t_ps - t_nop)))
        xs = np.array([s for s, _ in pts])
        ys = np.array([t for _, t in pts])
        if len(pts) >= 2 and float(np.ptp(xs)) > 0:
            slope, intercept = np.polyfit(xs, ys, 1)
            lat = float(max(slope, 0.0))
            overhead = float(max(intercept, 0.0))
        else:
            lat = float(ys[-1] / max(xs[-1], 1.0))
        curves = _sweep_collective_curves(ndev)
    _CAL_CACHE = {"flops_per_s": flops, "mem_bytes_per_s": membw,
                  "coll_step_latency_s": lat, "coll_overhead_s": overhead,
                  "dispatch_floor_s": dispatch, "coll_curves": curves}
    return _CAL_CACHE


def _round_cal(cal: Dict[str, object]) -> Dict[str, object]:
    """6-sig-digit rounding of the (now nested) calibration record for
    the JSON payload."""
    def r(v):
        if isinstance(v, dict):
            return {k: r(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [r(x) for x in v]
        if isinstance(v, float):
            return float(f"{v:.6g}")
        return v
    return r(cal)


def _timed(fn, n: int):
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


# ------------------------------------------------------------ TP step


def _tp_programs(n: int):
    """(full_step, local_twin, args): the Megatron Column+Row pair from
    the tp_train_step analyze entry at bench shapes; the twin strips
    the collectives (same per-shard compute, no wire) so full - twin
    isolates the measured comm cost."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.jax_compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:n]).reshape(n), ("mp",))
    H, FF, B = 256, 1024, 64
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.standard_normal((H, FF)) * 0.02, jnp.float32)
    b1 = jnp.zeros((FF,), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((FF, H)) * 0.02, jnp.float32)
    b2 = jnp.zeros((H,), jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    args = (x, w1, b1, w2, b2)

    def make(with_collectives: bool):
        def body(x, w1, b1, w2, b2):
            def loss_fn(w1, b1, w2, b2):
                h = jax.nn.gelu(x @ w1 + b1)
                y = h @ w2
                if with_collectives:
                    y = jax.lax.psum(y, "mp")
                y = y + b2
                return jnp.mean(y * y)

            loss, grads = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2, 3))(w1, b1, w2, b2)
            g1, gb1, g2, gb2 = grads
            if with_collectives:
                gb2 = jax.lax.psum(gb2, "mp")
                loss = jax.lax.pmean(loss, "mp")
            lr = 1e-2
            return (w1 - lr * g1, b1 - lr * gb1, w2 - lr * g2,
                    b2 - lr * gb2, loss)

        return shard_map(
            body, mesh,
            in_specs=(P(), P(None, "mp"), P("mp"), P("mp", None), P()),
            out_specs=(P(None, "mp"), P("mp"), P("mp", None), P(), P()),
            check=False)

    return make(True), make(False), args, mesh


def tp_step_metrics(n_devices: int, steps: int = 16) -> Dict[str, object]:
    import jax

    full, twin, args, mesh = _tp_programs(n_devices)
    jfull, jtwin = jax.jit(full), jax.jit(twin)

    def run(fn):
        out = fn(*args)
        jax.block_until_ready(out)
        # median, not min: on the CPU-host run the twin/full difference
        # sits inside scheduler noise and min() flips their order
        ts = sorted(_timed(lambda: jax.block_until_ready(fn(*args)),
                           steps))
        return ts[len(ts) // 2]

    t_full = run(jfull)
    t_twin = run(jtwin)
    comm_frac_measured = max(0.0, 1.0 - t_twin / t_full)

    # predicted under the host-calibrated profile, through the SAME
    # rollups the TPC601 advisory uses
    from paddle_tpu.analysis.jaxpr import comm_rollup, rollup

    cal = calibrate_host()
    closed = jax.make_jaxpr(full)(*args)
    cr = rollup(closed)
    est = comm_rollup(closed, mesh=mesh)
    compute_s = sum(max(f / cal["flops_per_s"],
                        b / cal["mem_bytes_per_s"])
                    for f, b in cr.by_prim.values())
    comm_s = est.seconds_at(cal["mem_bytes_per_s"],
                            cal["coll_step_latency_s"],
                            cal["coll_overhead_s"],
                            calibration=cal.get("coll_curves"))
    overlapped = min(comm_s * est.overlap_fraction, compute_s)
    pred_s = compute_s + comm_s - overlapped
    # the drift-tracking prediction swaps the modeled compute term for
    # the MEASURED collective-stripped twin: the comm model is what
    # TPC601 asserts (the compute roofline is validated separately in
    # tests/test_jaxpr_analysis.py), and on a CPU host the virtual
    # devices share cores in ways the per-device compute model cannot
    # see — isolating the comm term keeps the ratio meaningful there
    hybrid_s = t_twin + comm_s - min(comm_s * est.overlap_fraction,
                                     t_twin)
    return {
        "n_devices": n_devices,
        "measured_step_ms": round(t_full * 1e3, 4),
        "measured_local_twin_ms": round(t_twin * 1e3, 4),
        "comm_fraction_measured": round(comm_frac_measured, 4),
        "predicted_step_ms": round(hybrid_s * 1e3, 4),
        "predicted_step_model_ms": round(pred_s * 1e3, 4),
        "predicted_comm_ms": round(comm_s * 1e3, 4),
        "comm_fraction_predicted": round(
            comm_s / pred_s if pred_s else 0.0, 4),
        "overlap_fraction_predicted": round(est.overlap_fraction, 4),
        "pred_vs_measured": round(
            hybrid_s / t_full if t_full else 0.0, 4),
        "pred_vs_measured_model": round(
            pred_s / t_full if t_full else 0.0, 4),
        "calibration": _round_cal(cal),
        "host": "cpu" if jax.default_backend() == "cpu" else
                jax.devices()[0].device_kind,
    }


# ------------------------------------------------------------ tp serving


def _tp_serving_engine(tp: int):
    """A tiny sharded serving engine over the virtual mesh (the ISSUE 11
    tp_serving bench surface: sharded paged decode + chunked prefill)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import Engine
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    cfg = tiny_llama_config(num_heads=8, num_kv_heads=8, hidden_size=128,
                            intermediate_size=256)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return Engine(model, max_slots=4, num_pages=96, page_size=8,
                  chunk_size=4, dtype=jnp.float32, max_chain=4,
                  prefill_chunk=8, disaggregate=True,
                  tp=tp if tp > 1 else None)


def tp_serving_metrics(n_devices: int, steps: int = 16
                       ) -> Dict[str, object]:
    """Measured-vs-predicted comm for the SHARDED SERVING programs
    (ISSUE 11 satellite): the tensor-parallel decode chain and the mixed
    chunk+decode step — the two programs a disaggregated serving step
    dispatches — each timed warm against a collective-stripped twin
    (same sharded weights and per-shard compute, psums skipped), with
    the tpushard comm rollup priced under the host calibration."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.analysis.jaxpr import comm_rollup

    eng = _tp_serving_engine(n_devices)
    runner = eng.runner
    cal = calibrate_host()
    nb = 4
    rng = np.random.default_rng(0)

    def decode_args():
        tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
        for i in range(nb):
            tables[i, :2] = [1 + 2 * i, 2 + 2 * i]
        return [eng._params, eng._pages_flat(), jnp.asarray(tables),
                jnp.asarray(np.full((nb,), 9, np.int32)),
                jnp.asarray(rng.integers(
                    0, eng.cfg.vocab_size, (nb,)).astype(np.int32)),
                jnp.zeros((nb,), jnp.float32),
                jnp.zeros((nb, 2), jnp.uint32)]

    def mixed_args():
        tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
        for i in range(nb):
            tables[i, :2] = [1 + 2 * i, 2 + 2 * i]
        ids = rng.integers(0, eng.cfg.vocab_size,
                           (nb, eng.prefill_chunk)).astype(np.int32)
        return [eng._params, eng._pages_flat(), jnp.asarray(ids),
                jnp.asarray(np.array([8, 1, 8, 1], np.int32)),  # widths
                jnp.asarray(np.array([0, 1, 0, 1], np.int32)),  # emit
                jnp.asarray(tables),
                jnp.asarray(np.array([3, 9, 0, 7], np.int32)),  # lengths
                jnp.zeros((nb,), jnp.float32),
                jnp.zeros((nb, 2), jnp.uint32)]

    out: Dict[str, object] = {"n_devices": n_devices,
                              "schema": "paddle_tpu.tp_serving.v1"}
    tot_full = tot_pred = 0.0
    for kind, args_fn, kk in (("decode", decode_args, 2),
                              ("mixed", mixed_args, 1)):
        raw = runner.traceable(kind, sampling=False, k=kk)
        twin_raw = (runner.traceable(kind, sampling=False, k=kk,
                                     strip_collectives=True)
                    if runner.sharded else raw)
        jfull = jax.jit(raw)
        jtwin = jax.jit(twin_raw)

        def run(fn):
            res = fn(*args_fn())
            jax.block_until_ready(res)
            ts = sorted(_timed(
                lambda: jax.block_until_ready(fn(*args_fn())), steps))
            return ts[len(ts) // 2]

        t_full = run(jfull)
        t_twin = run(jtwin) if runner.sharded else t_full
        est = comm_rollup(jax.make_jaxpr(raw)(*args_fn()),
                          mesh=runner.mesh)
        comm_s = est.seconds_at(cal["mem_bytes_per_s"],
                                cal["coll_step_latency_s"],
                                cal["coll_overhead_s"],
                                calibration=cal.get("coll_curves"))
        hybrid = t_twin + comm_s - min(comm_s * est.overlap_fraction,
                                       t_twin)
        tot_full += t_full
        tot_pred += hybrid
        out[f"{kind}_step_ms"] = round(t_full * 1e3, 4)
        out[f"{kind}_twin_ms"] = round(t_twin * 1e3, 4)
        out[f"{kind}_predicted_comm_ms"] = round(comm_s * 1e3, 4)
        out[f"{kind}_comm_fraction_measured"] = round(
            max(0.0, 1.0 - t_twin / t_full) if t_full else 0.0, 4)
        out[f"{kind}_comm_fraction_predicted"] = round(
            comm_s / hybrid if hybrid else 0.0, 4)
        out[f"{kind}_n_collectives"] = est.n_collectives
        # the ISSUE 16 acceptance gate reads the per-program ratio
        # (decode must land in 0.8-1.25), not just the combined one
        out[f"{kind}_pred_vs_measured"] = round(
            hybrid / t_full if t_full else 0.0, 4)
    out["pred_vs_measured"] = round(
        tot_pred / tot_full if tot_full else 0.0, 4)
    out["comm_fraction_measured"] = round(max(
        out["decode_comm_fraction_measured"],
        out["mixed_comm_fraction_measured"]), 4)
    out["comm_fraction_predicted"] = round(max(
        out["decode_comm_fraction_predicted"],
        out["mixed_comm_fraction_predicted"]), 4)
    out["calibration"] = _round_cal(cal)
    return out


# ------------------------------------------------------------ suites


def suite_timings(n_devices: int) -> Dict[str, Dict[str, object]]:
    """Each claimed strategy surface, one tiny executed step, timed."""
    import __graft_entry__ as g

    suites = {
        "hybrid_pipeline": g._dryrun_hybrid_pipeline,
        "sep_ring_attention": g._dryrun_sep_ring_attention,
        "moe_ep": g._dryrun_moe_ep,
        "autoparallel_engine": g._dryrun_autoparallel_engine,
        "sharding_stage3": g._dryrun_sharding_stage3,
    }
    out: Dict[str, Dict[str, object]] = {}
    for name, fn in suites.items():
        t0 = time.perf_counter()
        try:
            fn(n_devices)
            out[name] = {"ok": True,
                         "seconds": round(time.perf_counter() - t0, 3)}
        except Exception as e:
            out[name] = {"ok": False,
                         "seconds": round(time.perf_counter() - t0, 3),
                         "error": f"{type(e).__name__}: {e}"}
    return out


def multichip_metrics(n_devices: int, tp_only: bool = False
                      ) -> Dict[str, object]:
    payload: Dict[str, object] = {
        "schema": "paddle_tpu.multichip.v3",
        "n_devices": n_devices,
        "tp_step": tp_step_metrics(n_devices),
        # ISSUE 11: the sharded serving programs (TP decode chain +
        # mixed chunk step) measured vs their collective-stripped twins
        # vs the calibrated tpushard prediction
        "tp_serving": tp_serving_metrics(n_devices),
    }
    if not tp_only:
        payload["suites"] = suite_timings(n_devices)
        payload["ok"] = all(s.get("ok") for s in payload["suites"].values())
    return payload


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="multichip",
        description="structured multichip harness: suite timings + "
                    "measured-vs-predicted TP comm roofline")
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument("--tp-only", action="store_true",
                    help="skip the strategy-surface suites")
    ap.add_argument("--json", action="store_true",
                    help="print one machine-readable JSON object")
    ap.add_argument("--out", default=None,
                    help="also write the payload to this file")
    args = ap.parse_args(argv)

    import jax

    if len(jax.devices()) < args.n_devices:
        print(json.dumps({"ok": False,
                          "error": f"only {len(jax.devices())} devices "
                                   f"(need {args.n_devices}); run from a "
                                   f"fresh shell so the virtual-device "
                                   f"flag takes effect"}))
        return 1

    payload = multichip_metrics(args.n_devices, tp_only=args.tp_only)
    text = json.dumps(payload, indent=None if args.json else 2,
                      sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
