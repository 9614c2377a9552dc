"""Persistent XLA compilation cache (SURVEY.md §7 hard part 6: restart
goodput — a restarted worker must not pay the multi-minute XLA compile for a
program it already compiled before the failure).

The reference has no equivalent (CUDA kernels are precompiled; its restart
cost is NCCL re-init). On TPU the compile IS the restart cost, so the cache
is wired into the elastic path: ``ElasticSupervisor`` exports
``JAX_COMPILATION_CACHE_DIR`` to every (re)spawned worker — JAX reads that
variable itself at import — and ``init_parallel_env`` lowers the caching
thresholds.

Also home to the in-process kernel-choice memo (``memoize_kernel_choice``):
hand-written Pallas kernels pick launch geometry (block shapes, grid
layout) per problem shape, and that choice must be pinned for the life of
the process — a heuristic consulted fresh at every trace could retune a
warm serving binary and silently recompile every cached program built on
the old geometry. One level up from the XLA cache: same idea, applied to
the selection logic instead of the compiled artifact.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Hashable, Optional, Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# Where the cache lives when ENV_VAR is not set: one fixed path inside the
# checkout (git-ignored). The path is part of a cache entry's key, so a
# directory named after $HOME, a pid, a time or a temporary name never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled_dir: Optional[str] = None

_KERNEL_CHOICES: Dict[Tuple[Hashable, ...], Any] = {}
_KERNEL_CHOICES_LOCK = threading.Lock()

# ------------------------------------------------- compile-path telemetry
# "Why is my server recompiling" must be answerable from metrics alone
# (ISSUE 3). Two sources feed one block of metrics:
#
# * jax's own monitoring events (``register_compile_listeners``, called
#   when ``paddle_tpu`` is imported): every program jax builds, whatever
#   its entry (``jax.jit`` over ``functional_call``, an eager op, the
#   optimizer's state init, a serving program, ``StaticFunction``), reports
#   its jaxpr trace, its lowering to MLIR and its backend compile or
#   persistent-cache load: ``paddle_jit_trace_seconds``,
#   ``paddle_jit_lower_seconds``, ``paddle_jit_backend_seconds``,
#   ``paddle_jit_backend_compiles_total``, ``paddle_jit_cache_loads_total``.
# * the jit entry (StaticFunction) reports every program-cache hit, every
#   first call's wall time, and attributes each RETRACE to the shape/dtype
#   signature that triggered it.
#
# The metric objects are built lazily so importing compile_cache never
# pulls in the observability package (and the first record costs one dict
# build, the rest a lookup).

_JIT_METRICS: Optional[Dict[str, Any]] = None


def _jit_metrics() -> Dict[str, Any]:
    global _JIT_METRICS
    if _JIT_METRICS is None:
        from ..observability import counter, histogram

        _JIT_METRICS = {
            "compiles": counter(
                "paddle_jit_compiles_total",
                "programs traced+compiled at a jit entry point"),
            "compile_seconds": histogram(
                "paddle_jit_compile_seconds",
                "wall time of the first call per program signature "
                "(trace + XLA compile + first dispatch)"),
            "hits": counter(
                "paddle_jit_cache_hits_total",
                "jit-entry calls served by an already-compiled program"),
            "retraces": counter(
                "paddle_jit_retraces_total",
                "compiles AFTER an entry's first program, attributed to "
                "the triggering shape/dtype signature",
                labelnames=("fn", "signature")),
            "kernel_hits": counter(
                "paddle_kernel_choice_hits_total",
                "kernel-geometry memo hits, by namespace",
                labelnames=("kind",)),
            "kernel_misses": counter(
                "paddle_kernel_choice_misses_total",
                "kernel-geometry choices computed+pinned, by namespace",
                labelnames=("kind",)),
            "trace_seconds": histogram(
                "paddle_jit_trace_seconds",
                "jaxpr trace of any program jax builds, less the phases "
                "nested in it"),
            "lower_seconds": histogram(
                "paddle_jit_lower_seconds",
                "jaxpr -> MLIR lowering of any program, less the phases "
                "nested in it"),
            "backend_seconds": histogram(
                "paddle_jit_backend_seconds",
                "backend compile or persistent-cache load of any program"),
            "backend_compiles": counter(
                "paddle_jit_backend_compiles_total",
                "programs the backend compiled (no persistent-cache hit)"),
            "cache_loads": counter(
                "paddle_jit_cache_loads_total",
                "programs loaded from the persistent compilation cache"),
        }
    return _JIT_METRICS


# jax's names (jax/_src/dispatch.py, compiler.py). A phase opens with a
# scalar event (its start time) and closes with its duration; a cache hit
# is reported inside the backend phase that it ends.
_PHASE_METRIC = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "backend_seconds",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _OpenPhases(threading.local):
    """[seconds of the phases nested in it, loaded from the cache] of each
    phase open on this thread, innermost last. A phase nests in another
    where a trace runs an eager op (which traces, lowers and compiles a
    program of its own) or a lowering traces a nested jit; each histogram
    takes a phase's own time only, so the three never count a second
    twice and their sums add up to the wall time the phases cover."""

    def __init__(self):
        self.stack = []


_OPEN = _OpenPhases()


def _phase_opens(event, start_time, **kwargs):
    if event in _PHASE_METRIC:
        _OPEN.stack.append([0.0, False])


def _cache_event(event, **kwargs):
    if event == _CACHE_HIT_EVENT and _OPEN.stack:
        _OPEN.stack[-1][1] = True


def _phase_closes(event, seconds, **kwargs):
    name = _PHASE_METRIC.get(event)
    if name is None:
        return
    stack = _OPEN.stack
    # a phase that opened before the listeners were registered has no entry
    nested, loaded = stack.pop() if stack else (0.0, False)
    if stack:
        stack[-1][0] += seconds
    _JIT_METRICS[name].observe(seconds - nested)
    if name == "backend_seconds":
        _JIT_METRICS["cache_loads" if loaded else "backend_compiles"].inc()


for _fn in (_phase_opens, _cache_event, _phase_closes):
    _fn._paddle_compile_listener = True
del _fn


def register_compile_listeners() -> None:
    """Hear every trace, lowering and backend compile or cache load jax
    does in this process (module comment above). Called when
    ``paddle_tpu`` is imported; idempotent across calls and re-imports of
    this module. A warm program fires no event, so a steady loop pays
    nothing; an event that is not a compile phase costs one dict lookup."""
    import jax.monitoring as monitoring
    from jax._src import monitoring as registered  # the lists are private

    if any(getattr(f, "_paddle_compile_listener", False)
           for f in registered.get_event_duration_listeners()):
        return
    _jit_metrics()
    monitoring.register_scalar_listener(_phase_opens)
    monitoring.register_event_listener(_cache_event)
    monitoring.register_event_duration_secs_listener(_phase_closes)


def ensure_compile_metrics() -> None:
    """Register the compile-path metrics zero-valued so a scrape shows
    the full catalogue before the first compile happens (a dashboard
    query against an absent series looks like a broken exporter)."""
    _jit_metrics()


def record_jit_cache_hit() -> None:
    _jit_metrics()["hits"].inc()


def record_jit_compile(fn_name: str, signature: str, seconds: float,
                       retrace: bool) -> None:
    m = _jit_metrics()
    m["compiles"].inc()
    m["compile_seconds"].observe(seconds)
    if retrace:
        m["retraces"].labels(fn=fn_name, signature=signature).inc()


def memoize_kernel_choice(key: Tuple[Hashable, ...],
                          compute: Callable[[], Any]) -> Any:
    """First call per ``key`` runs ``compute()``; every later call returns
    the pinned value. Keys are namespaced tuples, e.g.
    ``("wq_matmul_blocks", rows, k, n, dtype)``. Thread-safe (the serving
    engine traces from worker threads). Hit/miss counters land in the
    metrics registry (these run on the host at trace time — a miss per
    execution would mean the pinning is broken)."""
    kind = str(key[0]) if key else "?"
    try:
        value = _KERNEL_CHOICES[key]
        _jit_metrics()["kernel_hits"].labels(kind=kind).inc()
        return value
    except KeyError:
        pass
    with _KERNEL_CHOICES_LOCK:
        if key not in _KERNEL_CHOICES:
            _jit_metrics()["kernel_misses"].labels(kind=kind).inc()
            _KERNEL_CHOICES[key] = compute()
        else:
            _jit_metrics()["kernel_hits"].labels(kind=kind).inc()
        return _KERNEL_CHOICES[key]


def clear_kernel_choices() -> None:
    """Drop pinned kernel choices (tests; a live process should never)."""
    with _KERNEL_CHOICES_LOCK:
        _KERNEL_CHOICES.clear()


def enable_compilation_cache() -> str:
    """Turn jax's persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own handling of the
    variable decides the directory and this function sets none in code;
    where it is not, the cache goes to ``DEFAULT_CACHE_DIR``. Thresholds
    are lowered so even small programs are cached — restart goodput beats
    the few MB of disk. Idempotent."""
    global _enabled_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc

    if _enabled_dir is not None:
        return _enabled_dir
    if os.environ.get(ENV_VAR):
        cache_dir = jax.config.jax_compilation_cache_dir
    else:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax initializes its cache singleton lazily at the FIRST compile and
    # never re-reads the config: if anything compiled before this call
    # (typical in a warm process), the settings above would silently
    # never apply. Reset so the next compile re-initializes against them.
    jcc.reset_cache()
    _enabled_dir = cache_dir
    return cache_dir


def compilation_cache_dir() -> Optional[str]:
    """The active cache directory, or None when not enabled."""
    return _enabled_dir


def maybe_enable_from_env() -> Optional[str]:
    """Enable iff JAX_COMPILATION_CACHE_DIR is set (the elastic
    supervisor's contract with restarted workers)."""
    if os.environ.get(ENV_VAR):
        return enable_compilation_cache()
    return None
