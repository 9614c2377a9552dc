#!/usr/bin/env python
"""Headline benchmark suite, one JSON line on stdout.

Headline metric (``value``): model-FLOPs MFU of a fully-jitted GPT-medium
(355M param) causal-LM train step on one chip — the >=350M-param config the
round-2 verdict requires (VERDICT r2 next-round #1). GPT-2 small (124M) is
reported alongside as the regression guard, and the serving metrics cover
greedy decode with the slab KV cache (+ the computed bandwidth floor, so
``decode_roofline_frac`` says how far off roofline the decode loop runs).

MFU convention (BASELINE.md): 6*N*tokens_per_sec / peak_flops, model FLOPs
(attention extra FLOPs excluded from the headline, reported separately),
per-chip over per-chip. vs_baseline = MFU / 0.45 (BASELINE.json target).
"""
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


# the device peak table lives with the tpucheck cost model (ISSUE 4: one
# source of truth for predicted AND measured rooflines)
from paddle_tpu.analysis.jaxpr.cost import hbm_bw, peak_flops  # noqa: E402


def decode_step_cost(model, batch, total_seq, device):
    """tpucheck roofline rollup of ONE decode step of ``model`` at this
    cache geometry: (predicted ms/token on ``device``, rollup). The
    prediction shares the measured floor's byte conventions (packed
    quant buffers count packed bytes), so predicted/measured drift is an
    estimator bug, not a units mismatch — BENCH_r06+ tracks the ratio."""
    import jax.numpy as jnp

    from paddle_tpu.analysis.jaxpr import rollup_fn
    from paddle_tpu.framework.tensor import Tensor, pause_tape
    from paddle_tpu.jit import functional_call, state_arrays

    caches = [c._data for c in model.init_caches(batch, total_seq)]
    state = state_arrays(model)
    tok = jnp.zeros((batch, 1), jnp.int32)

    def step(state, caches, tok, t):
        with pause_tape():
            return functional_call(
                model, state, Tensor._wrap(tok),
                caches=[Tensor._wrap(c) for c in caches],
                time_step=Tensor._wrap(t))

    cr = rollup_fn(step, state, caches, tok, jnp.int32(1))
    return 1e3 * cr.predicted_seconds(device.device_kind), cr


def bench_train(cfg, batch, seq, steps):
    """MFU of forward+backward+momentum-SGD update (bf16 compute, fp32
    master — the O2 recipe), chained dispatch, one fetch."""
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.jit import functional_call, param_arrays
    from paddle_tpu.framework.tensor import Tensor

    model = GPTForCausalLM(cfg)
    model.eval()  # dropout off; loss path is what we time
    master = param_arrays(model)  # fp32 master weights (O2 recipe)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), master)

    def loss_fn(params_bf16, ids, labels):
        logits = functional_call(model, params_bf16, Tensor._wrap(ids))
        # CE on bf16 logits with f32 reductions: skips materializing the
        # [B,S,V] f32 logits tensor (measured win on v5e)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0].astype(jnp.float32)
        return jnp.mean(logz - gold)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, master, opt_m, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        new_m = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g, opt_m, grads)
        new_master = jax.tree_util.tree_map(lambda p, m: p - 1e-4 * m,
                                            master, new_m)
        new_p = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                       new_master)
        return new_p, new_master, new_m, loss

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    opt_m = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), master)

    # warmup (compile + first dispatch); the device_get is the completion
    # fence (dispatch is asynchronous).
    params, master, opt_m, loss = train_step(params, master, opt_m, ids, labels)
    float(jax.device_get(loss))

    # Chained dispatch: steps serialize on-device via the params dependency;
    # the final fetch waits for the whole chain. One host round trip total.
    t0 = time.perf_counter()
    for _ in range(steps):
        params, master, opt_m, loss = train_step(params, master, opt_m, ids, labels)
    final_loss = float(jax.device_get(loss))
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_params = cfg.num_params()
    model_flops_per_tok = 6 * n_params
    attn_flops_per_tok = 12 * cfg.num_layers * cfg.hidden_size * seq // 2
    peak = peak_flops(jax.devices()[0])
    return {
        "mfu": tokens_per_sec * model_flops_per_tok / peak,
        "mfu_incl_attn": tokens_per_sec * (
            model_flops_per_tok + attn_flops_per_tok) / peak,
        "tokens_per_sec": tokens_per_sec,
        "loss": final_loss,
        "n_params": n_params,
        "batch": batch,
    }


def weight_stream_bytes(model):
    """Per-token weight-side HBM bytes: every parameter and buffer byte
    read once, dedup'd by array identity (the tied wte/lm-head streams
    once). Counts ACTUAL storage — packed int4 buffers contribute their
    packed bytes (half the int8 bytes), scales their f32 bytes — so the
    bf16/int8w/int4w roofline fractions all divide by the same byte
    model and are directly comparable."""
    seen, total = set(), 0
    for _, t in (list(model.named_parameters())
                 + list(model.named_buffers())):
        d = t._data
        if id(d) in seen:
            continue
        seen.add(id(d))
        total += d.nbytes
    return int(total)


def bench_decode(cfg, on_tpu):
    """Greedy decode throughput over the slab KV cache, bf16 weights (the
    serving dtype), plus the weight+KV HBM bandwidth floor. The generate
    call is ONE compiled prefill + ONE compiled scan — per-token numbers
    divide out the scan; the host round trip is amortized by decoding
    enough tokens."""
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.framework.tensor import Tensor

    model = GPTForCausalLM(cfg)
    model.eval()
    model.bfloat16()
    if on_tpu:
        batch, prompt, new = 8, 128, 512
    else:
        batch, prompt, new = 2, 16, 8
    rng = np.random.default_rng(1)
    ids = Tensor._wrap(jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt)), jnp.int32))

    def timed(n):
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=n, temperature=0.0,
                             max_seq=min(cfg.max_position, prompt + new))
        np.asarray(out)
        return time.perf_counter() - t0

    # same prefill + same compiled scan both times (max_seq pinned, scan
    # length bucketed pow2): the long-minus-short difference isolates pure
    # decode steps, cancelling prefill cost and the host round trip.
    # The differential is REPEATED and medianed — a single sample rides
    # host jitter, which is how r3 shipped a >100% roofline fraction
    # (VERDICT r3 weak #1 / next #3).
    short = new // 4
    timed(new)
    timed(short)  # warm both scan lengths
    reps = 3 if on_tpu else 1
    diffs = sorted(timed(new) - timed(short) for _ in range(reps))
    dt = diffs[reps // 2]
    steps = new - short

    dev = jax.devices()[0]
    total = min(cfg.max_position, prompt + new)
    # per-token HBM floor: every weight byte once (actual storage bytes,
    # see weight_stream_bytes) + every layer's K and V cache read once
    # (window averaged over the decode range)
    weight_bytes = weight_stream_bytes(model)  # bf16 params
    avg_window = (prompt + total) / 2
    kv_bytes = cfg.num_layers * 2 * batch * avg_window * cfg.hidden_size * 2
    floor_s = (weight_bytes + kv_bytes) / hbm_bw(dev)
    ms_per_tok = 1e3 * dt / steps
    # tpucheck cost-model prediction beside the measured number (ISSUE 4):
    # same jaxpr the chip runs, same byte conventions as the floor —
    # the ratio says how far the estimator drifts from reality
    pred_ms, _ = decode_step_cost(model, batch, total, dev)
    out = {
        "decode_tokens_per_sec": round(batch / (ms_per_tok * 1e-3), 1),
        "decode_ms_per_token": round(ms_per_tok, 3),
        "decode_batch": batch,
        "decode_new_tokens": new,
        "decode_floor_ms_per_token": round(floor_s * 1e3, 3),
        "decode_roofline_frac": round(floor_s * 1e3 / ms_per_tok, 3),
        "decode_pred_ms_per_token": round(pred_ms, 3),
        "decode_cost_ratio": round(pred_ms / ms_per_tok, 3),
    }

    # weight-only int8 decode (VERDICT r2 #4): same model, int8 projection
    # weights — the dominant HBM stream halves. The floor re-derives from
    # the quantized model's actual buffers: int8 weight bytes + f32
    # scales for the swapped Linears, bf16 for whatever stayed
    # (embeddings, the tied wte lm head).
    from paddle_tpu.nn.quant import quant_backend, quantize_for_decode

    quantize_for_decode(model)
    timed(new)
    timed(short)
    diffs8 = sorted(timed(new) - timed(short) for _ in range(reps))
    ms8 = 1e3 * diffs8[reps // 2] / steps
    floor8_s = (weight_stream_bytes(model) + kv_bytes) / hbm_bw(dev)
    pred8_ms, _ = decode_step_cost(model, batch, total, dev)
    out.update({
        "decode_int8w_ms_per_token": round(ms8, 3),
        "decode_int8w_roofline_frac": round(floor8_s * 1e3 / ms8, 3),
        "decode_int8w_pred_ms_per_token": round(pred8_ms, 3),
        "decode_int8w_cost_ratio": round(pred8_ms / ms8, 3),
        "quant_backend": quant_backend(rows=batch),
    })

    # weight-only int4 decode (VERDICT r4 #3): packed nibbles quarter the
    # projection stream; rebuild from a fresh bf16 model (the int8 swap
    # above replaced the Linears in place)
    model4 = GPTForCausalLM(cfg)
    model4.eval()
    model4.bfloat16()
    _, swapped4 = quantize_for_decode(model4, algo="weight_only_int4")
    if swapped4:
        def timed4(n):
            t0 = time.perf_counter()
            o = model4.generate(ids, max_new_tokens=n, temperature=0.0,
                                max_seq=min(cfg.max_position,
                                            prompt + new))
            np.asarray(o)
            return time.perf_counter() - t0

        timed4(new)
        timed4(short)
        diffs4 = sorted(timed4(new) - timed4(short) for _ in range(reps))
        ms4 = 1e3 * diffs4[reps // 2] / steps
        # actual packed bytes moved: the int4 buffers are [in/2, out]
        # int8 arrays, so weight_stream_bytes counts exactly half the
        # int8 weight bytes — the int8w and int4w fractions divide by
        # the same byte model and are directly comparable
        floor4_s = (weight_stream_bytes(model4) + kv_bytes) / hbm_bw(dev)
        pred4_ms, _ = decode_step_cost(model4, batch, total, dev)
        out.update({
            "decode_int4w_ms_per_token": round(ms4, 3),
            "decode_int4w_roofline_frac": round(floor4_s * 1e3 / ms4, 3),
            "decode_int4w_pred_ms_per_token": round(pred4_ms, 3),
            "decode_int4w_cost_ratio": round(pred4_ms / ms4, 3),
        })
    # a roofline fraction above 1.0 is physically impossible — it means
    # the byte model or the timing is wrong; flag loudly rather than ship
    # a number that erodes trust in the rest (VERDICT r3 #3)
    for key in ("decode_roofline_frac", "decode_int8w_roofline_frac",
                "decode_int4w_roofline_frac"):
        if key not in out:
            continue
        if out[key] > 1.0:
            print(f"WARNING: {key}={out[key]} exceeds the physical "
                  "roofline; timing jitter or byte-model error",
                  file=sys.stderr)
            out[key + "_suspect"] = True
    return out


def bench_verify_slab(cfg, on_tpu):
    """ms per multi-query verify/suffix slab attention dispatch at the
    serving geometry (ISSUE 9): the attention program spec verify,
    prefix-cache suffix prefill and chunked prefill all ride — the fused
    Pallas slab kernel on TPU, its jnp window-gather twin on CPU. One
    layer's call at spec shape (m = k+1 = 5), scan-fenced like the
    microbenches; ``tools/mb_verify.py`` holds the full m×batch×pages
    sweep."""
    try:
        from paddle_tpu.ops.pallas.paged_attention import (
            PagedCacheState, paged_multi_query_attention)

        n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
        d = cfg.hidden_size // cfg.num_heads
        batch, m = (8, 5) if on_tpu else (2, 5)
        page_size = 16
        max_pages = cfg.max_position // page_size
        live = max_pages // 2
        rng = np.random.default_rng(2)
        n_pages = 1 + batch * max_pages
        kp = jnp.asarray(
            rng.standard_normal((n_pages, page_size, n_kv * d)) * 0.3,
            jnp.bfloat16)
        vp = jnp.asarray(
            rng.standard_normal((n_pages, page_size, n_kv * d)) * 0.3,
            jnp.bfloat16)
        bt = jnp.asarray(np.arange(1, 1 + batch * max_pages,
                                   dtype=np.int32).reshape(batch, -1))
        base = jnp.full((batch,), live * page_size, jnp.int32)
        st = PagedCacheState(kp, vp, None, bt,
                             base + m, page_size)
        q = jnp.asarray(rng.standard_normal((batch, m, cfg.num_heads, d))
                        * 0.3, jnp.bfloat16)

        @jax.jit
        def loop(q):
            def body(carry, _):
                q, acc = carry
                s = jnp.sum(paged_multi_query_attention(
                    q, st, base).astype(jnp.float32))
                return (q * (1.0 + 0.0 * s).astype(q.dtype), acc + s), None

            (_, acc), _ = jax.lax.scan(body, (q, jnp.float32(0)), None,
                                       length=30 if on_tpu else 2)
            return acc

        float(jax.device_get(loop(q)))  # compile + warm
        t0 = time.perf_counter()
        float(jax.device_get(loop(q)))
        dt = (time.perf_counter() - t0) / (30 if on_tpu else 2)
        return {"decode_verify_slab_ms": round(dt * 1e3, 4),
                "decode_verify_slab_m": m,
                "decode_verify_slab_batch": batch}
    except Exception as e:
        return {"verify_slab_error": f"{type(e).__name__}: {e}"[:120]}


def bench_paged_decode(cfg, on_tpu):
    """Continuous-batching engine over the paged KV cache (serving
    flagship): mixed workload driven through inference.Engine; reports
    steady-state decode throughput. Present only when the engine import
    succeeds so bench.py never breaks mid-round."""
    try:
        from paddle_tpu.inference.engine import bench_engine_decode

        return bench_engine_decode(cfg, on_tpu)
    except Exception as e:  # engine still landing — report, don't fail
        return {"paged_decode_error": f"{type(e).__name__}: {e}"[:120]}


def bench_spec(cfg, on_tpu):
    """Speculative decoding (ISSUE 5): ngram-drafted serving on a
    repeated-structure workload vs the vanilla engine — accepted
    tokens/verify-step, acceptance rate, decode_spec_ms_per_token."""
    try:
        from paddle_tpu.inference.engine import bench_spec_decode

        return bench_spec_decode(cfg, on_tpu)
    except Exception as e:
        return {"spec_decode_error": f"{type(e).__name__}: {e}"[:120]}


def bench_fault(cfg, on_tpu):
    """Fault-rate scenario (ISSUE 6): mixed serving with ~1% injected
    request failures must hold throughput within 10% of clean with zero
    engine restarts; failures are isolated and scrape-visible."""
    try:
        from paddle_tpu.inference.engine import bench_fault_tolerance

        return bench_fault_tolerance(cfg, on_tpu)
    except Exception as e:
        return {"fault_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_prefix(cfg, on_tpu):
    """Prefix-caching scenario (ISSUE 8): templated 90%-overlap prompts
    served with refcounted copy-on-write page reuse — effective prefill
    throughput >= 5x cache-off on TPU (CPU gate: strictly faster at hit
    rate > 0.8), and < 5% steady-state cost on zero-overlap traffic."""
    try:
        from paddle_tpu.inference.engine import bench_prefix_cache

        return bench_prefix_cache(cfg, on_tpu)
    except Exception as e:
        return {"prefix_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_kv_tier(cfg, on_tpu):
    """Tiered-KV-cache scenario (ISSUE 15): a templated workload whose
    cached working set is ~8x the paged pool, served with and without
    the host-DRAM spill tier — sustained hit-rate >= 0.8 tier-on where
    tier-off collapses < 0.2, effective prefill throughput no worse
    than recompute (interleaved medians over the 50 ms single-core
    jitter floor), every promotion checksum-verified, zero drops."""
    try:
        from paddle_tpu.inference.kv_tier import bench_kv_tier as run

        return run(cfg, on_tpu)
    except Exception as e:
        return {"kv_tier_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_moe(cfg, on_tpu):
    """Expert-parallel MoE serving scenario (ISSUE 17): tiny-MoE decode
    tokens/s (8 experts, top-2, grouped-expert Pallas FFN, capacity
    drops) vs the equal-active-params dense twin — interleaved-rep
    medians over the 50 ms jitter floor, gate: dense/MoE <= 1.5x — plus
    the router's drop fraction and per-expert load imbalance."""
    try:
        from paddle_tpu.inference.engine import bench_moe_serving

        return bench_moe_serving(cfg, on_tpu)
    except Exception as e:
        return {"moe_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_slo(cfg, on_tpu):
    """Serving-front-end SLO scenario (ISSUE 12): multi-step decode
    speedup (multi_step=4 >= 1.2x multi_step=1), an open-loop Poisson
    load sustaining target QPS with p99 TTFT/TPOT under budget, and a
    tenant-fairness run where a batch flood degrades the interactive
    tenant's p99 TTFT < 2x."""
    try:
        from paddle_tpu.serving.loadgen import bench_slo_serving

        return bench_slo_serving(cfg, on_tpu)
    except Exception as e:
        return {"slo_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_failover(cfg, on_tpu):
    """Multi-replica failover scenario (ISSUE 13): open-loop load over
    a 2-replica router with one injected replica kill — every stream
    completes (migrated, not failed) and the p99 TTFT of unaffected
    requests degrades < 2x vs a no-kill baseline (interleaved rep
    pairs, jitter-floored on the single-core smoke host)."""
    try:
        from paddle_tpu.serving.loadgen import bench_failover_serving

        return bench_failover_serving(cfg, on_tpu)
    except Exception as e:
        return {"failover_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_cluster(cfg, on_tpu):
    """Cluster-scale serving scenario (ISSUE 20): shared-prefix
    multi-tenant load over a 3-replica prefill/decode cluster with
    cross-replica KV handoff and cache-aware placement. Gates: fleet
    prefix hit rate within 1.2x of a single-giant-cache oracle, mixed
    p99 TTFT < 2x the unpooled baseline over the jitter floor, zero
    stream failures."""
    try:
        from paddle_tpu.serving.loadgen import bench_cluster_serving

        return bench_cluster_serving(cfg, on_tpu)
    except Exception as e:
        return {"cluster_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_trace(cfg, on_tpu):
    """Request-tracing overhead scenario (ISSUE 18): the span recorder's
    steady-state cost as an interleaved-rep ratio of median scheduling-
    step times, tracing on vs off, on the bench_slo engine geometry.
    Gate: <2% median step overhead over the 50 ms single-core jitter
    floor, with >0 spans recorded."""
    try:
        from paddle_tpu.serving.loadgen import bench_trace_serving

        return bench_trace_serving(cfg, on_tpu)
    except Exception as e:
        return {"trace_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_ownership(cfg, on_tpu):
    """Runtime ownership-guard scenario (ISSUE 19): the guard's
    steady-state cost — every hot-path attribute write on a fully
    guarded tiered engine paying the __setattr__ interception — as an
    interleaved-rep ratio of median scheduling-step times, armed vs
    disarmed. Gate: <2% median step overhead over the 50 ms single-core
    jitter floor; an OwnershipError anywhere surfaces as a bench error
    (a finishing run is the clean-tree runtime proof at bench
    geometry)."""
    try:
        from paddle_tpu.serving.loadgen import bench_ownership_serving

        return bench_ownership_serving(cfg, on_tpu)
    except Exception as e:
        return {"ownership_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_integrity(cfg, on_tpu):
    """Data-integrity scenario (ISSUE 14): the online-audit layer's
    steady-state cost — weight-shard audits, per-page KV checksums at
    splice/registration, shadow recompute — as an interleaved-rep ratio
    of median scheduling-step times, sentinel strict vs off, on a
    prefix-heavy workload. Gate: <2% median step overhead over the
    50 ms single-core jitter floor, with >0 checks and 0 failures."""
    try:
        from paddle_tpu.inference.integrity import bench_integrity_overhead

        return bench_integrity_overhead(cfg, on_tpu)
    except Exception as e:
        return {"integrity_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_resume(on_tpu):
    """Training-resilience scenario (ISSUE 7): amortized per-step
    checkpoint-save overhead through the raw train-step path — sync vs
    async CheckpointManager.save at a production-shaped interval — and
    resume-to-first-step latency (restore `latest` + one completed
    step). Gate: async save overhead < 5% of baseline step time (lands
    in BENCH_r07; the CPU smoke run is expected to warn — host compute
    and the writer thread share the same cores there)."""
    import shutil
    import tempfile

    try:
        from paddle_tpu.distributed import CheckpointManager
        from paddle_tpu.framework.tensor import Tensor
        from paddle_tpu.jit import functional_call, param_arrays
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        if on_tpu:
            cfg = GPTConfig(hidden_size=512, num_layers=8, num_heads=8,
                            max_position=512, vocab_size=32000)
            batch, seq, steps, every = 8, 512, 32, 16
        else:
            cfg = GPTConfig(hidden_size=128, num_layers=2, num_heads=4,
                            max_position=256, vocab_size=1024)
            batch, seq, steps, every = 2, 64, 16, 4

        model = GPTForCausalLM(cfg)
        model.eval()
        params = param_arrays(model)
        names = [f"p{i:03d}" for i in range(
            len(jax.tree_util.tree_leaves(params)))]
        treedef = jax.tree_util.tree_structure(params)

        def loss_fn(p, ids, labels):
            logits = functional_call(model, p, Tensor._wrap(ids))
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits, labels[..., None],
                axis=-1)[..., 0].astype(jnp.float32)
            return jnp.mean(logz - gold)

        # NO buffer donation here on purpose: the checkpoint snapshot
        # reads the params the step just produced
        @jax.jit
        def train_step(p, ids, labels):
            loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
            return jax.tree_util.tree_map(
                lambda a, g: a - 1e-4 * g, p, grads), loss

        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                          jnp.int32)
        labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                             jnp.int32)

        def flat_state(p):
            return dict(zip(names, jax.tree_util.tree_leaves(p)))

        def run(n, saver=None, mgr=None):
            p = params
            t0 = time.perf_counter()
            for i in range(n):
                p, loss = train_step(p, ids, labels)
                float(jax.device_get(loss))  # per-step fence
                if saver is not None and (i + 1) % every == 0:
                    saver(i + 1, flat_state(p))
            if mgr is not None:
                mgr.wait()  # trailing write counts against async too
            return 1e3 * (time.perf_counter() - t0) / n

        p_warm, l_warm = train_step(params, ids, labels)  # compile
        float(jax.device_get(l_warm))
        base_ms = run(steps)

        root = tempfile.mkdtemp(prefix="bench_resume_")
        try:
            sync_dir, async_dir = f"{root}/sync", f"{root}/async"
            mgr_s = CheckpointManager(sync_dir, keep_last_n=2)
            sync_ms = run(steps, saver=mgr_s.save)
            mgr_a = CheckpointManager(async_dir, keep_last_n=2,
                                      async_save=True)
            async_ms = run(steps, saver=mgr_a.save, mgr=mgr_a)

            # resume-to-first-step latency: restore `latest`, rebuild the
            # param tree, complete one step
            t0 = time.perf_counter()
            mgr_r = CheckpointManager(async_dir)
            _, state = mgr_r.restore()
            restored = jax.tree_util.tree_unflatten(
                treedef, [state[n] for n in names])
            p2, loss2 = train_step(restored, ids, labels)
            float(jax.device_get(loss2))
            resume_ms = 1e3 * (time.perf_counter() - t0)
        finally:
            shutil.rmtree(root, ignore_errors=True)

        sync_frac = (sync_ms - base_ms) / base_ms
        async_frac = (async_ms - base_ms) / base_ms
        out = {
            "resume_ckpt_every_steps": every,
            "resume_step_ms_baseline": round(base_ms, 3),
            "resume_step_ms_sync_ckpt": round(sync_ms, 3),
            "resume_step_ms_async_ckpt": round(async_ms, 3),
            "resume_sync_overhead_frac": round(sync_frac, 3),
            "resume_async_overhead_frac": round(async_frac, 3),
            "resume_async_overhead_ok": bool(async_frac < 0.05),
            "resume_restore_ms": round(resume_ms, 3),
        }
        if not out["resume_async_overhead_ok"]:
            print(f"WARNING: async checkpoint overhead "
                  f"{async_frac:.1%} exceeds the 5% budget",
                  file=sys.stderr)
        return out
    except Exception as e:
        return {"resume_bench_error": f"{type(e).__name__}: {e}"[:120]}


def bench_multichip():
    """Multichip comm-roofline drift (ISSUE 10): the TP step measured
    vs the tpushard-predicted step time, via tools/multichip.py in a
    fresh subprocess (it forces the virtual-8-device mesh without
    perturbing THIS process's device topology). Records the
    predicted-vs-measured ratio the TPC601 advisory is gated on (the
    same convention as the decode _cost_ratio lines from ISSUE 4)."""
    import os
    import subprocess

    try:
        tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "multichip.py")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # let the tool pick its own topology
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, tool, "--tp-only", "--json"],
            capture_output=True, text=True, timeout=600, env=env)
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
        tp = payload["tp_step"]
        out = {
            "multichip_tp_step_ms": tp["measured_step_ms"],
            "multichip_tp_pred_ms": tp["predicted_step_ms"],
            "multichip_comm_fraction_measured":
                tp["comm_fraction_measured"],
            "multichip_comm_fraction_pred":
                tp["comm_fraction_predicted"],
            "multichip_pred_vs_measured": tp["pred_vs_measured"],
            # calibration satellite (ISSUE 11): intercept/slope split of
            # the tiny-psum fit; target ≤1.15x on the TP train step
            "multichip_tp_calibrated_ok": bool(
                tp["pred_vs_measured"] <= 1.15),
        }
        ts = payload.get("tp_serving")
        if ts is not None:
            # sharded serving programs (ISSUE 11): TP decode chain +
            # mixed chunk step vs their collective-stripped twins,
            # gated by the same 2x ratio band as the TP train step
            r = ts["pred_vs_measured"]
            rd = ts.get("decode_pred_vs_measured", 0.0)
            out.update({
                "multichip_tp_serving_decode_ms": ts["decode_step_ms"],
                "multichip_tp_serving_mixed_ms": ts["mixed_step_ms"],
                "multichip_tp_serving_comm_fraction_measured":
                    ts["comm_fraction_measured"],
                "multichip_tp_serving_comm_fraction_pred":
                    ts["comm_fraction_predicted"],
                "multichip_tp_serving_pred_vs_measured": r,
                "multichip_tp_serving_ok": bool(0.5 <= r <= 2.0),
                # decode-regime recalibration (ISSUE 16): the per-kind
                # payload-sweep curves must hold the decode chain's
                # prediction inside the 0.8-1.25 acceptance band
                "multichip_tp_serving_decode_pred_vs_measured": rd,
                "multichip_decode_calibrated_ok": bool(
                    0.8 <= rd <= 1.25),
            })
        return out
    except Exception as e:
        return {"multichip_error": f"{type(e).__name__}: {e}"}


def bench_plan(multichip):
    """Autosharding planner surface (ISSUE 16): plan every meshable
    registry entry at mesh 8 in a fresh subprocess (tools/plan_tpu.py
    --fail-on-audit) and report (a) ``plan_beats_handwritten`` — the
    planner's chosen spec costs no more than the hand-written oracle
    for EVERY entry under the calibrated model, with the self-audit
    (TPC501/502/503) clean; (b) ``plan_pred_vs_measured`` — the
    measured validity of the pricing model the planner inherits, i.e.
    the decode-regime pred_vs_measured the r16 recalibration moved
    into band (small in-scan collectives are exactly what the planner
    must cost right to rank decode plans)."""
    import os
    import subprocess

    try:
        tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "plan_tpu.py")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, tool, "--json", "--mesh", "8",
             "--fail-on-audit"],
            capture_output=True, text=True, timeout=600, env=env)
        blob = json.loads(proc.stdout.strip())
        ratios = [b["chosen_vs_oracle"] for b in blob.values()
                  if "chosen_vs_oracle" in b]
        beats = bool(ratios) and proc.returncode == 0 and all(
            v <= 1.000001 for v in ratios)
        pvm = multichip.get(
            "multichip_tp_serving_decode_pred_vs_measured", 0.0)
        if not pvm:
            # no live multichip run (e.g. it errored): fall back to the
            # committed r16 calibration artifact
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "MULTICHIP_r16.json")
            with open(art, encoding="utf-8") as f:
                pvm = json.load(f)["tp_serving"][
                    "decode_pred_vs_measured"]
        return {
            "plan_entries": len(blob),
            "plan_beats_handwritten": beats,
            "plan_worst_vs_oracle": round(max(ratios), 4) if ratios
            else 0.0,
            "plan_pred_vs_measured": round(float(pvm), 4),
            "plan_ok": bool(beats and 0.8 <= pvm <= 1.25),
        }
    except Exception as e:
        return {"plan_error": f"{type(e).__name__}: {e}"}


def main():
    from paddle_tpu.framework.compile_cache import enable_compilation_cache
    from paddle_tpu.models.gpt import GPTConfig

    # persist XLA/Mosaic compiles across bench runs: on this host a cold
    # compile of the big programs costs minutes of single-core time, and
    # the numbers themselves are unaffected (timing starts after warmup)
    enable_compilation_cache()

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        medium = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                           max_position=1024, vocab_size=50304)
        medium2k = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                             max_position=2048, vocab_size=50304)
        small = GPTConfig(hidden_size=768, num_layers=12, num_heads=12,
                          max_position=1024, vocab_size=50304)
        medium4k = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                             max_position=4096, vocab_size=50304)
        r_med = bench_train(medium, batch=12, seq=1024, steps=15)
        # long-seq line (VERDICT r3 #2): whole-row packed flash, S=2048 —
        # fits HBM at b=8 without remat
        r_2k = bench_train(medium2k, batch=8, seq=2048, steps=10)
        # S=4096 (VERDICT r4 #1): b=4 keeps activation bytes at the
        # S=2048 level, so no remat needed at this model size either
        r_4k = bench_train(medium4k, batch=4, seq=4096, steps=8)
        r_small = bench_train(small, batch=8, seq=1024, steps=20)
        decode_cfg = small
    else:  # CPU smoke mode so the script always runs
        tiny = GPTConfig(hidden_size=128, num_layers=2, num_heads=4,
                         max_position=256, vocab_size=1024)
        r_med = bench_train(tiny, batch=2, seq=128, steps=3)
        r_2k = None
        r_4k = None
        r_small = r_med
        decode_cfg = tiny

    decode = bench_decode(decode_cfg, on_tpu)
    vslab = bench_verify_slab(decode_cfg, on_tpu)
    paged = bench_paged_decode(decode_cfg, on_tpu)
    spec = bench_spec(decode_cfg, on_tpu)
    fault = bench_fault(decode_cfg, on_tpu)
    prefix = bench_prefix(decode_cfg, on_tpu)
    kv_tier = bench_kv_tier(decode_cfg, on_tpu)
    moe = bench_moe(decode_cfg, on_tpu)
    slo = bench_slo(decode_cfg, on_tpu)
    failover = bench_failover(decode_cfg, on_tpu)
    cluster = bench_cluster(decode_cfg, on_tpu)
    integrity = bench_integrity(decode_cfg, on_tpu)
    trace = bench_trace(decode_cfg, on_tpu)
    ownership = bench_ownership(decode_cfg, on_tpu)
    resume = bench_resume(on_tpu)
    multichip = bench_multichip()
    plan = bench_plan(multichip)

    # observability snapshot (ISSUE 3): the perf trajectory carries the
    # telemetry the run produced — how many programs compiled, whether
    # anything retraced mid-bench (a retrace here is a perf bug), and the
    # serving engine's decode-latency distribution as measured by its own
    # TPOT histogram rather than the bench's external timers.
    from paddle_tpu.observability import histogram_summary, metric_total

    tpot = histogram_summary("paddle_serving_tpot_seconds")
    spec_proposed = metric_total("paddle_tpu_spec_proposed_total")
    spec_accepted = metric_total("paddle_tpu_spec_accepted_total")
    metrics_block = {
        "compile_count": int(
            metric_total("paddle_jit_compiles_total")
            + metric_total("paddle_serving_compiled_programs_total")),
        "retrace_count": int(metric_total("paddle_jit_retraces_total")),
        "preemptions": int(metric_total("paddle_serving_preemptions_total")),
        "decode_latency_ms": {
            "count": int(tpot.get("count", 0)),
            "mean": round(1e3 * tpot.get("mean", 0.0), 3),
            "p50": round(1e3 * tpot.get("p50", 0.0), 3),
            "p99": round(1e3 * tpot.get("p99", 0.0), 3),
        },
        # spec acceptance as the registry counters saw it (ISSUE 5):
        # cross-checkable against the bench_spec block's own ratios
        "spec_proposed": int(spec_proposed),
        "spec_accepted": int(spec_accepted),
        "spec_accept_rate": round(
            spec_accepted / spec_proposed if spec_proposed else 0.0, 3),
        "decode_spec_ms_per_token": spec.get(
            "decode_spec_ms_per_token", 0.0),
        # fault-tolerance surface (ISSUE 6): the taxonomy counters and
        # degraded-mode gauge as the registry saw them across the run
        "request_failures": int(
            metric_total("paddle_tpu_request_failures_total")),
        "admission_rejected": int(
            metric_total("paddle_tpu_admission_rejected_total")),
        "request_retries": int(
            metric_total("paddle_tpu_request_retries_total")),
        "engine_recoveries": int(
            metric_total("paddle_tpu_engine_recoveries_total")),
        "degraded_mode": int(
            metric_total("paddle_tpu_engine_degraded")),
        # prefix-cache surface (ISSUE 8): hit rate and eviction pressure
        # as the registry counters saw them across the whole run
        "prefix_hit_rate": round(
            metric_total("paddle_tpu_prefix_cache_hits_total")
            / max(1.0,
                  metric_total("paddle_tpu_prefix_cache_hits_total")
                  + metric_total("paddle_tpu_prefix_cache_misses_total")),
            3),
        "prefix_cached_tokens": int(
            metric_total("paddle_tpu_prefix_cached_prefill_tokens_total")),
        "prefix_computed_tokens": int(
            metric_total("paddle_tpu_prefix_computed_prefill_tokens_total")),
        "prefix_evictions": int(
            metric_total("paddle_tpu_prefix_cache_evictions_total")),
        # KV host-tier surface (ISSUE 15): the demote/promote ladder as
        # the registry counters saw it across the run, beside the tier
        # block's own hit-rate/throughput gates
        "kv_tier_demotions": int(
            metric_total("paddle_tpu_kv_tier_demotions_total")),
        "kv_tier_promotions": int(
            metric_total("paddle_tpu_kv_tier_promotions_total")),
        "kv_tier_hits": int(
            metric_total("paddle_tpu_kv_tier_hits_total")),
        "kv_tier_drops": int(
            metric_total("paddle_tpu_kv_tier_drops_total")),
        "kv_tier_hit_rate_on": kv_tier.get("kv_tier_hit_rate_on", 0.0),
        "kv_tier_hit_rate_off": kv_tier.get("kv_tier_hit_rate_off", 0.0),
        "kv_tier_prefill_ratio": kv_tier.get(
            "kv_tier_prefill_ratio", 0.0),
        # expert-parallel MoE serving surface (ISSUE 17): the router's
        # registry counters across the run (capacity drops, per-expert
        # load spread) beside the MoE block's own throughput gate
        "moe_tokens_dropped": int(
            metric_total("paddle_tpu_moe_tokens_dropped_total")),
        "moe_expert_tokens": int(
            metric_total("paddle_tpu_moe_expert_tokens_total")),
        "moe_drop_frac": moe.get("moe_drop_frac", 0.0),
        "moe_load_imbalance": moe.get("moe_load_imbalance", 0.0),
        "moe_dense_over_moe_ratio": moe.get(
            "moe_dense_over_moe_ratio", 0.0),
        # decode hot-path kernel surface (ISSUE 9): prompt chunks
        # streamed through mixed steps, and fused-slab-path dispatches
        # across the three consumers (verify / suffix / chunked)
        "prefill_chunks": int(
            metric_total("paddle_tpu_prefill_chunks_total")),
        "slab_verify_dispatches": int(
            metric_total("paddle_tpu_slab_verify_dispatch_total")),
        # serving front-end surface (ISSUE 12): iterations batched per
        # host round trip (1.0 mean = the fast path never engaged) and
        # the SLO block's own gates beside it
        "steps_per_roundtrip_mean": round(histogram_summary(
            "paddle_tpu_engine_steps_per_roundtrip").get("mean", 0.0), 3),
        "multistep_speedup": slo.get("multistep_speedup", 0.0),
        "slo_p99_ttft_ms": slo.get("slo_p99_ttft_ms", 0.0),
        "fairness_ttft_degrade": slo.get("fairness_ttft_degrade", 0.0),
        # multi-replica failover surface (ISSUE 13): streams migrated
        # across replica deaths and supervised restarts, as the router's
        # counters saw them, beside the failover block's own gate
        "paddle_tpu_router_migrations_total": int(
            metric_total("paddle_tpu_router_migrations_total")),
        "paddle_tpu_replica_restarts_total": int(
            metric_total("paddle_tpu_replica_restarts_total")),
        "router_hedges": int(
            metric_total("paddle_tpu_router_hedges_total")),
        "slow_client_cancels": int(
            metric_total("paddle_tpu_slow_client_cancels_total")),
        "failover_ttft_degrade": failover.get(
            "failover_ttft_degrade", 0.0),
        # cluster-serving surface (ISSUE 20): prefill->decode KV
        # shipments, bytes moved, recompute fallbacks and pool resizes
        # as the registry saw them, beside the cluster block's gates
        "cluster_handoffs": int(
            metric_total("paddle_tpu_cluster_handoffs_total")),
        "cluster_handoff_bytes": int(
            metric_total("paddle_tpu_cluster_handoff_bytes_total")),
        "cluster_fallbacks": int(
            metric_total("paddle_tpu_cluster_fallbacks_total")),
        "cluster_rebalances": int(
            metric_total("paddle_tpu_cluster_rebalances_total")),
        "cluster_hit_rate": cluster.get("cluster_hit_rate", 0.0),
        "cluster_ttft_degrade": cluster.get(
            "cluster_ttft_degrade", 0.0),
        # data-integrity surface (ISSUE 14): every audit probe and every
        # detection across the whole run (checkpoint digests, weight
        # audits, KV checksums, shadow recompute), plus the overhead
        # block's own gate and the quarantine count
        "integrity_checks": int(
            metric_total("paddle_tpu_integrity_checks_total")),
        "integrity_failures": int(
            metric_total("paddle_tpu_integrity_failures_total")),
        "replica_quarantines": int(
            metric_total("paddle_tpu_replica_quarantines_total")),
        "integrity_overhead_frac": integrity.get(
            "integrity_overhead_frac", 0.0),
        # request-tracing surface (ISSUE 18): the overhead block's own
        # gate
        "trace_overhead_frac": trace.get("trace_overhead_frac", 0.0),
        # thread-ownership guard surface (ISSUE 19): the runtime twin
        # of `make races` — armed-vs-disarmed step overhead on a fully
        # guarded tiered engine, gated <2% like bench_trace
        "ownership_guard_overhead_frac": ownership.get(
            "ownership_guard_overhead_frac", 0.0),
        # training-resilience surface (ISSUE 7): checkpoint commits and
        # the in-loop guard counters as the registry saw them
        "train_checkpoints": int(
            metric_total("paddle_tpu_train_checkpoints_total")),
        "train_step_retries": int(
            metric_total("paddle_tpu_train_step_retries_total")),
        "train_rollbacks": int(
            metric_total("paddle_tpu_train_rollbacks_total")),
        "train_preemptions": int(
            metric_total("paddle_tpu_train_preemptions_total")),
        "train_resumes": int(
            metric_total("paddle_tpu_train_resumes_total")),
        # multichip comm-roofline drift (ISSUE 10): TPC601's predicted
        # TP step vs the measured one (tools/multichip.py subprocess)
        "multichip_pred_vs_measured": multichip.get(
            "multichip_pred_vs_measured", 0.0),
        # tensor-parallel serving drift (ISSUE 11): the sharded decode
        # chain + mixed chunk step vs their collective-stripped twins
        "multichip_tp_serving_pred_vs_measured": multichip.get(
            "multichip_tp_serving_pred_vs_measured", 0.0),
        # autosharding planner surface (ISSUE 16): the planner never
        # loses to the hand-written specs under the calibrated model,
        # and the decode-regime calibration it prices with holds
        # against measurement (0.8-1.25 band)
        "plan_pred_vs_measured": plan.get("plan_pred_vs_measured", 0.0),
        "plan_beats_handwritten": plan.get(
            "plan_beats_handwritten", False),
    }

    out = {
        "metric": "gpt_medium_355m_train_mfu_1chip",
        "value": round(float(r_med["mfu"]), 4),
        "unit": "fraction_of_peak_bf16",
        "vs_baseline": round(float(r_med["mfu"]) / 0.45, 4),
        "mfu_incl_attn": round(float(r_med["mfu_incl_attn"]), 4),
        "tokens_per_sec": round(r_med["tokens_per_sec"], 1),
        "train_batch": r_med["batch"],
        "n_params": r_med["n_params"],
        "loss": r_med["loss"],
        "gpt2_small_mfu": round(float(r_small["mfu"]), 4),
        "gpt2_small_tokens_per_sec": round(r_small["tokens_per_sec"], 1),
        **({"s2048_mfu": round(float(r_2k["mfu"]), 4),
            "s2048_mfu_incl_attn": round(float(r_2k["mfu_incl_attn"]), 4),
            "s2048_tokens_per_sec": round(r_2k["tokens_per_sec"], 1),
            "s2048_batch": r_2k["batch"]} if r_2k else {}),
        **({"s4096_mfu": round(float(r_4k["mfu"]), 4),
            "s4096_mfu_incl_attn": round(float(r_4k["mfu_incl_attn"]), 4),
            "s4096_tokens_per_sec": round(r_4k["tokens_per_sec"], 1),
            "s4096_batch": r_4k["batch"]} if r_4k else {}),
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        **decode,
        **vslab,
        **paged,
        **spec,
        **fault,
        **prefix,
        **kv_tier,
        **moe,
        **slo,
        **failover,
        **cluster,
        **integrity,
        **trace,
        **ownership,
        **resume,
        **multichip,
        "metrics": metrics_block,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
