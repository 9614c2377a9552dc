"""FLAGS registry (reference: gflags-style PHI_DEFINE_EXPORTED_* in
paddle/phi/core/flags.cc; paddle.set_flags/get_flags API).

A typed dict with env-var override (FLAGS_xxx) at first read. XLA-level knobs
are deliberately passed through to XLA_FLAGS / LIBTPU_INIT_ARGS rather than
being re-modeled here (SURVEY.md §5.6).
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}
_DEFINED: Dict[str, type] = {}


def define_flag(name: str, default, help_str: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    env = os.environ.get(name)
    value = default
    if env is not None:
        t = type(default)
        if t is bool:
            value = env.lower() in ("1", "true", "yes")
        else:
            value = t(env)
    _REGISTRY[name] = value
    _DEFINED[name] = type(default)
    return value


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        _REGISTRY[k] = v


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        out[k] = _REGISTRY.get(k)
    return out


# Core flags (names mirror the reference where a concept carries over).
define_flag("FLAGS_allocator_strategy", "xla_bfc", "allocator is XLA/PJRT's BFC; informational")
define_flag("FLAGS_use_flash_attention", True, "route attention through the Pallas flash kernel")
define_flag("FLAGS_use_packed_attention", None,
            "packed-QKV causal kernel on the train path: None = auto "
            "(TPU only), True = force (interpret mode off-TPU), False = off")
define_flag("FLAGS_flash_attn_block_q", 128, "flash attention q tile")
define_flag("FLAGS_flash_attn_block_k", 128, "flash attention kv tile")
define_flag("FLAGS_check_nan_inf", False, "enable debug nan checks in optimizer steps")
define_flag("FLAGS_weight_only_quant_backend", "auto",
            "weight_only_linear GEMM backend: 'auto' = fused Pallas "
            "dequant-in-kernel matmul on TPU, plain-XLA dequant dots "
            "elsewhere (so tier-1 runs under JAX_PLATFORMS=cpu); "
            "'pallas' forces the fused kernel (interpret mode off-TPU); "
            "'xla' forces the convert-fusion path everywhere")
define_flag("FLAGS_decode_attention_kernel", False,
            "use the Pallas decode-attention kernel instead of the XLA "
            "batched-matvec path (measured slower at decode shapes on v5e)")
define_flag("FLAGS_log_level", "INFO", "python log level")
define_flag("FLAGS_analyze_on_compile",
            os.environ.get("PADDLE_TPU_ANALYZE_ON_COMPILE", "").lower()
            in ("1", "true", "yes"),
            "run the tpucheck jaxpr passes (paddle_tpu.analysis.jaxpr) at "
            "every first trace of a StaticFunction entry: peak-memory "
            "liveness, collective/mesh consistency, donation, roofline "
            "cost. Findings are counted into the metrics registry "
            "(paddle_tpu_analysis_findings_total{pass,rule}) and "
            "error/warn findings are logged. Off by default: analysis "
            "adds one make_jaxpr per compile (~ms at serving shapes, "
            "more for big train steps); also settable via env "
            "PADDLE_TPU_ANALYZE_ON_COMPILE=1")
define_flag("FLAGS_fault_inject",
            os.environ.get("PADDLE_TPU_FAULT_INJECT", ""),
            "deterministic fault-injection plan for the serving engine "
            "(paddle_tpu.testing.faultinject; ISSUE 6). Grammar: "
            "'point[:key=val,...][;point2:...]' over the named points "
            "pool-exhaustion / step-exception / nan-logits / "
            "drafter-corruption / slow-step, e.g. "
            "'nan-logits:rid=2,times=1;slow-step:every=4,delay_ms=30'. "
            "Empty (the default) disables injection; also settable via "
            "env PADDLE_TPU_FAULT_INJECT. Engine(fault_plan=...) "
            "overrides per instance")
define_flag("FLAGS_check_ownership",
            os.environ.get("PADDLE_TPU_CHECK_OWNERSHIP", "").lower()
            in ("1", "true", "yes"),
            "arm the runtime thread-ownership guard "
            "(paddle_tpu.analysis.ownership_guard; ISSUE 19): guarded "
            "objects (Engine/CacheCoordinator/PrefixCache/HostTier via "
            "guard_engine) stamp the first writing thread per attribute "
            "and raise OwnershipError on a write from any other thread "
            "— the dynamic twin of the tpurace TPL1501-TPL1504 static "
            "pass. Also settable via env PADDLE_TPU_CHECK_OWNERSHIP=1. "
            "Off by default: adds a dict lookup to every guarded "
            "attribute write")
define_flag("FLAGS_check_tracers",
            os.environ.get("PADDLE_TPU_CHECK_TRACERS", "").lower()
            in ("1", "true", "yes"),
            "arm jax.check_tracer_leaks around compiled-path entries "
            "(paddle_tpu.analysis.leak_guard) so a tracer leaked into "
            "global/closure state hard-fails at the trace instead of "
            "detonating later; also settable via env "
            "PADDLE_TPU_CHECK_TRACERS=1. Off by default: leak checking "
            "disables tracing fast paths")
