"""The program joined to the device trace (ISSUE 26).

* **device side**: every operation of a compiled program carries the layer
  that caused it (``nn.Layer.__call__`` under ``jax.named_scope``, the tied
  head under ``lm_head``, the optimizer under ``optimizer``), forward and in
  ``transpose(`` form, and every ``pallas_call`` carries its kernel's name;
* **host side**: the tracer's spans are ``jax.profiler.TraceAnnotation``s
  of the same name, whatever the tracer's mode: a profiler session around a
  few turns of a serving frontend holds the engine thread's phases, nested
  as opened; ``profiler.RecordEvent`` and ``tracing.span`` reach the
  profiler through one call;
* **counters**: ``paddle_serving_fair_queue_wait_seconds`` counts one
  observation per ticket handed to the engine.
"""
import glob
import importlib.util
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.engine import Engine
from paddle_tpu.jit import functional_call, param_arrays
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import tracing
from paddle_tpu.observability.metrics import REGISTRY
from paddle_tpu.observability.tracing import TRACER, configure_tracing
from paddle_tpu.serving import ServingFrontend


@pytest.fixture(autouse=True)
def trace_reset():
    configure_tracing("off")
    TRACER.clear()
    yield
    configure_tracing("off", process="main")
    TRACER.clear()


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        hidden_size=64, num_layers=2, num_heads=2, max_position=128,
        vocab_size=97))
    model.eval()
    return model


# ------------------------------------------------------ layer scope names
class TestLayerScopes:
    def test_a_child_runs_under_the_key_its_parent_holds_it_under(self):
        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 4)
                self.add_sublayer("named", nn.Linear(4, 4))

        b = Block()
        assert b._scope_name is None            # a root: its class name
        assert b.fc._scope_name == "fc"
        assert b.named._scope_name == "named"

    def test_list_items_run_under_the_lists_key_and_their_index(self):
        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.h = nn.LayerList([nn.Linear(4, 4) for _ in range(2)])

        net = Net()
        assert [l._scope_name for l in net.h] == ["h/0", "h/1"]
        net.h.append(nn.Linear(4, 4))
        assert net.h[2]._scope_name == "h/2"
        net.h.insert(0, nn.Linear(4, 4))
        assert [l._scope_name for l in net.h] == [
            "h/0", "h/1", "h/2", "h/3"]
        net.h[1] = nn.Linear(4, 4)
        assert net.h[1]._scope_name == "h/1"
        loose = nn.LayerList([nn.Linear(4, 4)])  # held by nobody
        assert loose[0]._scope_name == "0"

    def test_the_scope_reaches_the_jaxpr(self):
        net = nn.Sequential(nn.Linear(4, 4), nn.ReLU())
        x = Tensor._wrap(jnp.ones((2, 4)))
        txt = jax.jit(lambda a: functional_call(
            net, param_arrays(net), Tensor._wrap(a))).lower(
                x._data).as_text(debug_info=True)
        assert "sequential/0/dot_general" in txt


@pytest.fixture(scope="module")
def step_locations(gpt):
    """Name stacks of the lowered tiny train step (``functional_call`` +
    ``AdamW.apply_gradients_tree``), as the compiled program keeps them in
    each operation's metadata."""
    opt = optimizer.AdamW(learning_rate=1e-3, multi_precision=True)

    def step(params, state, ids, labels, n):
        def loss_fn(p):
            logits = functional_call(gpt, p, Tensor._wrap(ids))
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(logz - gold)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return opt.apply_gradients_tree(params, grads, state, 1e-3, n) \
            + (loss,)

    params = param_arrays(gpt)
    state = opt.init_state_tree(params)
    ids = jnp.zeros((2, 32), jnp.int32)
    txt = jax.jit(step).lower(params, state, ids, ids,
                              jnp.float32(1)).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', txt))


@pytest.mark.parametrize("scope,backward", [
    ("gpt/h/0/attn/", False), ("gpt/h/0/attn/", True),
    ("gpt/h/1/attn/out_proj/", False),
    ("gpt/h/0/mlp/", False), ("gpt/h/0/mlp/fc/", True),
    ("gpt/h/1/ln_2/", False), ("gpt/wte/", True),
    ("/lm_head/", False), ("/lm_head/", True),
    ("jit(step)/optimizer/", False)])
def test_train_step_carries_layer_scopes(step_locations, scope, backward):
    hits = [l for l in step_locations if scope in l
            and ("transpose(" in l) == backward]
    assert hits, (scope, backward)


def test_every_optimizer_operation_sits_under_its_scope(step_locations):
    """No layer of the model leaks into the optimizer's scope or back."""
    opt = [l for l in step_locations if "/optimizer/" in l]
    assert opt and not any("/gpt/" in l or "lm_head" in l for l in opt)


# ----------------------------------------------------------- kernel names
def _pallas_names(fn, *args):
    """The ``name=`` of every pallas_call equation reachable from the
    jaxpr of ``fn(*args)`` (abstract arguments: nothing runs)."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                info = eqn.params.get("name_and_src_info")
                names.append(info.name if info is not None
                             else eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sorted(names)


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _causal(seq):
    from paddle_tpu.ops.pallas import causal_flash

    f = lambda x: causal_flash.causal_flash_qkv(x, 4, 64).astype(
        jnp.float32).sum()
    return jax.grad(f), (_sds((1, 6, seq, 128)),)


def _window(seq):
    from paddle_tpu.ops.pallas import causal_flash

    f = lambda x: causal_flash.causal_flash_qkv(
        x, 2, 128, window=512).astype(jnp.float32).sum()
    return jax.grad(f), (_sds((1, 6, seq, 128)),)


def _causal_tiled():
    from paddle_tpu.ops.pallas import causal_flash

    return (lambda x: causal_flash._fwd_tiled(x, 4, 64, 0.125),
            (_sds((1, 6, 2048, 128)),))


def _flash(seq):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fused

    f = lambda q, k, v: flash_attention_fused(q, k, v).astype(
        jnp.float32).sum()
    x = _sds((1, seq, 2, 64))
    return jax.grad(f, argnums=(0, 1, 2)), (x, x, x)


def _decode():
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas

    return decode_attention_pallas, (
        _sds((2, 4, 64)), _sds((2, 2, 64, 64)), _sds((2, 2, 64, 64)),
        _sds((2,), jnp.int32))


def _decode_slab():
    from paddle_tpu.ops.pallas.decode_attention import _slab_pallas

    return (lambda q, kv, n: _slab_pallas(q, kv, n, 0.125),
            (_sds((2, 4, 64)), _sds((2, 2, 64, 128)), _sds((2,), jnp.int32)))


def _paged():
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    return paged_decode_attention, (
        _sds((2, 4, 64)), _sds((2, 16, 8, 64)), _sds((2, 16, 8, 64)),
        _sds((2, 4), jnp.int32), _sds((2,), jnp.int32))


def _paged_slab(m):
    from paddle_tpu.ops.pallas import paged_attention as pa

    if m:
        return (lambda q, k, v, bt, n: pa.paged_verify_slab_attention(
            q, k, v, bt, n, interpret=True),
            (_sds((2, m, 4, 64)), _sds((16, 8, 128)), _sds((16, 8, 128)),
             _sds((2, 4), jnp.int32), _sds((2,), jnp.int32)))
    return (lambda n, bt, q, k, v: pa._paged_window_call(
        n, bt, q, k, v, None, jnp.bfloat16, scale=0.125, num_heads=4,
        head_dim=64, m=0, interpret=True),
        (_sds((2,), jnp.int32), _sds((2, 4), jnp.int32),
         _sds((2, 8, 256)), _sds((16, 8, 128)), _sds((16, 8, 128))))


def _grouped():
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

    return (lambda a, w, g: grouped_matmul_pallas(a, w, g, interpret=True),
            (_sds((256, 128)), _sds((2, 128, 128)), _sds((2,), jnp.int32)))


def _quant():
    from paddle_tpu.ops.pallas.quant_matmul import quant_matmul_pallas

    return (lambda x, w, s: quant_matmul_pallas(x, w, s, interpret=True),
            (_sds((8, 256)), _sds((256, 256), jnp.int8),
             _sds((256,), jnp.float32)))


def _ssd_scan():
    from paddle_tpu.ops.pallas import ssd_scan

    f32 = jnp.float32
    f = lambda c, b, a, dt, x, d: ssd_scan.ssd_scan(c, b, a, dt, x, d).sum()
    return (jax.grad(f, argnums=4),
            (_sds((1, 2, 128, 1, 128)), _sds((1, 2, 128, 1, 128)),
             _sds((1, 2, 128, 1, 2), f32), _sds((1, 2, 128, 1, 2), f32),
             _sds((1, 2, 128, 1, 2, 64)), _sds((1, 2), f32)))


def _selective_scan():
    from paddle_tpu.ops.pallas import selective_scan as ss

    f32 = jnp.float32
    f = lambda x, dl, a, b, c, d: ss._scan_kernels(x, dl, a, b, c, d, 64).sum()
    return (jax.grad(f),
            (_sds((1, 128, 256)), _sds((1, 128, 256), f32),
             _sds((256, 16), f32), _sds((1, 128, 16)), _sds((1, 128, 16)),
             _sds((256,), f32)))


def _topk_mask():
    from paddle_tpu.ops.pallas import topk_mask

    return (lambda v: topk_mask._threshold_traced(True, 22, v),
            (_sds((1024, 64), jnp.float32),))


@pytest.mark.parametrize("recipe,expected", [
    (_topk_mask, ["topk_mask"]),
    (_ssd_scan, ["ssd_scan_bwd", "ssd_scan_fwd"]),
    (_selective_scan, ["selective_scan_bwd", "selective_scan_fwd"]),
    (lambda: _causal(256), ["causal_flash_bwd", "causal_flash_fwd"]),
    (lambda: _causal(1024), ["causal_flash_bwd", "causal_flash_fwd_row"]),
    (lambda: _causal(2048),
     ["causal_flash_bwd_tiled", "causal_flash_fwd_row"]),
    (_causal_tiled, ["causal_flash_fwd_tiled"]),
    (lambda: _window(1024), ["window_flash_bwd", "window_flash_fwd"]),
    (lambda: _flash(256), ["flash_attention_bwd", "flash_attention_fwd"]),
    (lambda: _flash(2048), ["flash_attention_bwd_dkv",
                            "flash_attention_bwd_dq",
                            "flash_attention_fwd"]),
    (_decode, ["decode_attention"]),
    (_decode_slab, ["decode_attention_slab"]),
    (_paged, ["paged_attention"]),
    (lambda: _paged_slab(0), ["paged_attention_slab"]),
    (lambda: _paged_slab(3), ["paged_attention_verify"]),
    (_grouped, ["grouped_matmul"]),
    (_quant, ["quant_matmul"]),
], ids=["topk_mask", "ssd_scan", "selective_scan", "causal_flash-s256", "causal_flash-s1024", "causal_flash-s2048",
        "causal_flash-fwd_tiled", "window_flash-s1024", "flash_attention-s256",
        "flash_attention-s2048", "decode_attention",
        "decode_attention_slab", "paged_attention", "paged_attention_slab",
        "paged_attention_verify", "grouped_matmul", "quant_matmul"])
def test_every_pallas_call_carries_its_kernels_name(recipe, expected):
    fn, args = recipe()
    assert _pallas_names(fn, *args) == expected


def test_no_pallas_call_site_is_without_a_name():
    """The parametrised cases above reach every site: as many names as
    ``pl.pallas_call(`` occurrences under ops/pallas."""
    root = os.path.join(os.path.dirname(paddle.__file__), "ops", "pallas")
    sites = named = 0
    for path in glob.glob(os.path.join(root, "*.py")):
        src = open(path).read()
        sites += len(re.findall(r"pl\.pallas_call\(", src))
        named += len(re.findall(r"^\s+name=", src, re.M))
    assert sites == named == 22


# -------------------------------------------------------------- host side
def _host_events(trace_dir):
    """{thread line: [(name, start_ns, end_ns)]} of the program's spans in
    the newest .xplane.pb under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events
                  if e.name.startswith(("engine.", "frontend.", "rec."))]
            if ev:
                lines.setdefault(line.name, []).extend(ev)
    return lines


def _serve_under_profiler(gpt, trace_dir, mode):
    configure_tracing(mode, process="test")
    eng = Engine(gpt, max_slots=2, num_pages=64, page_size=8, chunk_size=4,
                 dtype=jnp.float32)
    fe = ServingFrontend(eng, idle_wait_s=0.005)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fe.start()
        tickets = [fe.submit(list(range(1, 9)), 6) for _ in range(3)]
        for t in tickets:
            assert len(t.result(timeout=120)) == 6
        time.sleep(0.05)   # a few idle turns
    finally:
        fe.shutdown()
        jax.profiler.stop_trace()
    return tickets


@pytest.mark.parametrize("mode", ["off", "on"])
def test_engine_phases_are_host_events_of_a_profiler_session(
        gpt, tmp_path, mode):
    _serve_under_profiler(gpt, tmp_path, mode)
    lines = _host_events(str(tmp_path))
    ev = [e for evs in lines.values() for e in evs]
    names = {n for n, _, _ in ev}
    assert {"frontend.loop", "frontend.feed", "engine.step", "engine.admit",
            "engine.prefill_dispatch", "engine.chain_dispatch",
            "engine.harvest", "frontend.complete",
            "frontend.idle_wait"} <= names, names
    # all on ONE thread's line, nested as opened: every engine.step lies
    # inside a frontend.loop, every phase inside an engine.step
    (thread, evs), = [(k, v) for k, v in lines.items()
                      if any(n == "engine.step" for n, _, _ in v)]

    def inside(child, parent):
        outer = [(s, e) for n, s, e in evs if n == parent]
        inner = [(s, e) for n, s, e in evs if n == child]
        assert inner and all(any(ps <= s and e <= pe for ps, pe in outer)
                             for s, e in inner), (child, parent)

    inside("engine.step", "frontend.loop")
    inside("frontend.feed", "frontend.loop")
    inside("frontend.idle_wait", "frontend.loop")
    inside("engine.harvest", "engine.step")
    inside("engine.chain_dispatch", "engine.step")
    inside("engine.prefill_dispatch", "engine.admit")
    # the ring is the tracer's own business: empty when off, and when on
    # the same spans with their parents
    ring = TRACER.snapshot()
    if mode == "off":
        assert ring == []
        return
    by_id = {r["id"]: r for r in ring}
    steps = [r for r in ring if r["name"] == "engine.step"]
    assert steps and all(r["ph"] == "X" and r["dur"] > 0
                         and r["args"]["path"] == "chained" for r in steps)
    assert all(by_id[r["parent"]]["name"] == "frontend.loop"
               for r in steps if r["parent"] in by_id)
    harvests = [r for r in ring
                if r["name"] == "engine.harvest" and r["ph"] == "X"]
    assert harvests and all(
        by_id[r["parent"]]["name"] == "engine.step"
        for r in harvests if r["parent"] in by_id)
    assert TRACER.open_spans == 0


def test_idle_turns_stay_out_of_the_ring(gpt):
    configure_tracing("on", process="test")
    eng = Engine(gpt, max_slots=2, num_pages=64, page_size=8, chunk_size=4,
                 dtype=jnp.float32)
    fe = ServingFrontend(eng, idle_wait_s=0.002).start()
    try:
        time.sleep(0.1)
        assert TRACER.snapshot() == []   # dozens of turns, none with work
        assert len(fe.submit(list(range(1, 9)), 4).result(timeout=120)) == 4
    finally:
        fe.shutdown()
    names = {r["name"] for r in TRACER.snapshot()}
    assert {"frontend.loop", "engine.step"} <= names


def test_record_event_and_span_reach_the_profiler_through_one_call(
        monkeypatch, tmp_path):
    from paddle_tpu import profiler

    seen = []
    real = tracing.annotation
    monkeypatch.setattr(tracing, "annotation",
                        lambda name: seen.append(name) or real(name))
    monkeypatch.setattr(profiler, "_annotation", tracing.annotation)
    with profiler.RecordEvent("rec.event"):
        pass
    with tracing.span("rec.span_off"):
        pass
    with tracing.nested("rec.nested_off"):
        pass
    configure_tracing("on")
    with tracing.span("rec.span_on"):
        with tracing.nested("rec.nested_on"):
            pass
    assert seen == ["rec.event", "rec.span_off", "rec.nested_off",
                    "rec.span_on", "rec.nested_on"]


def test_nested_spans_record_their_parents_and_self_time_adds_up():
    configure_tracing("on", process="test")
    with tracing.nested("outer", "t") as outer:
        with tracing.nested("first", "t"):
            time.sleep(0.002)
        with tracing.nested("second", "t") as second:
            with tracing.nested("leaf", "t"):
                pass
    recs = {r["name"]: r for r in TRACER.snapshot()}
    assert recs["outer"]["parent"] is None
    assert recs["first"]["parent"] == recs["second"]["parent"] \
        == recs["outer"]["id"] == outer.ctx.span_id
    assert recs["leaf"]["parent"] == second.ctx.span_id
    assert len({r["trace"] for r in recs.values()}) == 1
    children = recs["first"]["dur"] + recs["second"]["dur"]
    assert 0 <= recs["outer"]["dur"] - children < recs["outer"]["dur"]
    assert TRACER.open_spans == 0
    with tracing.nested("later", "t"):   # the stack emptied: a new trace
        pass
    assert TRACER.snapshot()[-1]["parent"] is None


def test_the_tracer_module_imports_and_works_without_jax(monkeypatch):
    path = tracing.__file__
    monkeypatch.setitem(sys.modules, "jax", None)       # import jax fails
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    spec = importlib.util.spec_from_file_location("_tracing_nojax", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.annotation("x") is None
    with mod.span("a") as s:           # off: the shared no-op handle
        s.set(k=1)
    assert s.ctx is None
    mod.configure_tracing("on")
    with mod.nested("b", "t"):
        pass
    assert [r["name"] for r in mod.TRACER.snapshot()] == ["b"]


# --------------------------------------------------------------- counters
def _fair_wait_count():
    m = REGISTRY.get("paddle_serving_fair_queue_wait_seconds")
    return 0 if m is None else sum(leaf.count for _, leaf in m.series())


@pytest.mark.parametrize("mode", ["off", "on"])
def test_fair_queue_wait_counts_one_observation_per_admitted_ticket(
        gpt, mode):
    configure_tracing(mode, process="test")
    eng = Engine(gpt, max_slots=2, num_pages=64, page_size=8, chunk_size=4,
                 dtype=jnp.float32)
    fe = ServingFrontend(eng).start()
    before = _fair_wait_count()
    try:
        tickets = [fe.submit(list(range(1, 9)), 4) for _ in range(5)]
        for t in tickets:
            assert len(t.result(timeout=120)) == 4
    finally:
        fe.shutdown()
    assert _fair_wait_count() - before == 5
    m = REGISTRY.get("paddle_serving_fair_queue_wait_seconds")
    assert sum(leaf.sum for _, leaf in m.series()) > 0


def test_fair_queue_wait_follows_the_engines_metrics_switch(gpt):
    eng = Engine(gpt, max_slots=2, num_pages=64, page_size=8, chunk_size=4,
                 dtype=jnp.float32, metrics=False)
    assert ServingFrontend(eng)._m_fair_wait is None
