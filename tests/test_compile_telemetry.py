"""The compile telemetry hears every program jax builds (jax's monitoring
events, ``framework/compile_cache.register_compile_listeners``), and
``benchmarks/tools/setup_split.py`` splits a cell's set-up by it."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  registers the listeners
from paddle_tpu.framework import compile_cache
from paddle_tpu.observability import histogram_summary, metric_total

ROOT = Path(__file__).resolve().parent.parent


def heard():
    """(trace, lower, backend) events so far, and programs compiled and
    loaded."""
    count = lambda n: histogram_summary(n).get("count", 0)  # noqa: E731
    return (count("paddle_jit_trace_seconds"),
            count("paddle_jit_lower_seconds"),
            count("paddle_jit_backend_seconds"),
            metric_total("paddle_jit_backend_compiles_total"),
            metric_total("paddle_jit_cache_loads_total"))


def run_fresh(fn):
    """Events of ``fn``'s first and second call at one shape (a lax op
    only: a jnp ufunc would trace a nested jit of its own)."""
    x = jnp.asarray(np.arange(6, dtype=np.float32))
    before = heard()
    jax.block_until_ready(fn(x))
    first = heard()
    jax.block_until_ready(fn(x))
    second = heard()
    return ([a - b for a, b in zip(first, before)],
            [a - b for a, b in zip(second, first)])


def test_a_fresh_program_is_heard_once():
    first, second = run_fresh(
        jax.jit(lambda x: jax.lax.mul(jax.lax.sin(x), x)))
    assert first[:3] == [1, 1, 1]
    assert first[3] + first[4] == 1     # compiled, or loaded where a cache is on
    assert second == [0, 0, 0, 0, 0]    # the same shape again: nothing


def test_nested_phases_count_their_own_time_once():
    # each jnp call traces a jit of its own inside the outer trace: all
    # are heard, and the histogram's sum is the outer trace's wall time
    traces = []

    def listen(event, seconds, **kwargs):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traces.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        sum0 = histogram_summary("paddle_jit_trace_seconds")["sum"]
        first, _ = run_fresh(jax.jit(lambda x: jnp.sin(jnp.cos(x))))
        sum1 = histogram_summary("paddle_jit_trace_seconds")["sum"]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert first[:3] == [3, 1, 1] and len(traces) == 3
    assert sum1 - sum0 == pytest.approx(traces[-1])   # the outer one ends last
    assert traces[-1] > max(traces[:-1])


def _listeners():
    from jax._src import monitoring

    return (len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners()),
            len(monitoring.get_scalar_listeners()))


def test_registering_again_or_reimporting_adds_no_listener():
    before = _listeners()
    compile_cache.register_compile_listeners()
    # a second copy of the module, as a re-import makes
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.framework._compile_cache_again", compile_cache.__file__)
    again = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(again)
    again.register_compile_listeners()
    assert _listeners() == before
    first, _ = run_fresh(jax.jit(lambda x: jax.lax.add(jax.lax.cos(x), x)))
    assert first[:3] == [1, 1, 1]       # each event still counted once


def test_a_persistent_cache_hit_counts_as_a_load(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as jcc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}

    def body(x):
        return jax.lax.tanh(jax.lax.exp(x))

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jcc.reset_cache()
        first, _ = run_fresh(jax.jit(body))
        assert first[3:] == [1, 0]      # compiled and written
        jax.clear_caches()              # the process forgets it
        again, _ = run_fresh(jax.jit(body))
        assert again[2:] == [1, 0, 1]   # one backend phase: a load
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        jcc.reset_cache()
    assert jax.config.jax_compilation_cache_dir == saved[
        "jax_compilation_cache_dir"]


# ------------------------------------------------ the split of set-up


@pytest.fixture(scope="module")
def setup_split():
    spec = importlib.util.spec_from_file_location(
        "setup_split", ROOT / "benchmarks" / "tools" / "setup_split.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def marks(window_programs=0):
    """Marks of a made-up run: (host clock, totals) at each point."""
    def at(t, trace, lower, backend, compiled, loaded):
        return (t, dict(trace=trace, lower=lower, backend=backend,
                        compiled=compiled, loaded=loaded))

    return {
        "start": at(100.0, 0, 0, 0, 0, 0),
        "driver": at(108.0, 0, 0, 0, 0, 0),
        "build_start": at(108.1, 0, 0, 0, 0, 0),
        "build_end": at(112.0, 0.4, 0.3, 1.8, 2, 40),
        "step1": at(122.0, 4.4, 1.2, 8.8, 2, 41),
        "window_start": at(126.5, 4.6, 1.4, 9.9, 2, 44),
        "window_end": at(180.0, 4.6, 1.4, 9.9, 2,
                         44 + window_programs),
    }


def test_the_five_groups_sum_to_setup_s(setup_split):
    at = marks()
    groups, programs = setup_split.split(at, setup_s=26.5)
    assert list(groups) == ["import", "trace", "compile", "build", "other"]
    assert sum(groups.values()) == pytest.approx(26.5, abs=1e-9)
    assert all(v >= 0 for v in groups.values())
    assert groups["import"] == pytest.approx(8.0)
    assert groups["trace"] == pytest.approx(6.0)
    assert groups["compile"] == pytest.approx(9.9)
    assert groups["build"] == pytest.approx(3.9 - 2.5)
    assert programs == {"compiled": 2, "loaded": 44, "in_window": 0}
    # the stretches cover set-up and the window without a gap
    st = setup_split.stretches(at)
    assert sum(s["wall"] for s in st.values()) == pytest.approx(80.0)
    assert st["build_end-step1"]["backend"] == pytest.approx(7.0)


def test_programs_in_the_window_read_what_the_snapshots_differ_by(
        setup_split):
    assert setup_split.split(marks(), 26.5)[1]["in_window"] == 0
    assert setup_split.split(marks(3), 26.5)[1][
        "in_window"] == 3


def test_totals_of_a_program_without_listeners_are_zero(setup_split):
    assert setup_split.totals({}) == setup_split.ZERO
    from paddle_tpu.observability import REGISTRY

    now = setup_split.totals(REGISTRY.snapshot())
    assert now["compiled"] + now["loaded"] >= 1   # this process compiled
