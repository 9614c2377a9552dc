#!/usr/bin/env python
"""tpucheck CLI — run the jaxpr analysis passes over the repo's real
entry points (``make analyze``), or over a chosen subset.

Each registered entry builds a tiny-config version of a real compiled
path (llama decode, train steps, the quant matmul, the shard_map
data-parallel step, ...) — small enough to trace in milliseconds under
``JAX_PLATFORMS=cpu``, structurally identical to the production trace.
Findings render through the tpulint reporter, one
``entry:op_index:0: TPCxxx message`` line each, so the output greps like
``make lint``.

Suppressions are per-entry, declared IN the registry with a written
justification (mirroring tpulint's ``# tpulint: disable=... -- reason``
standard): an entry may carry ``suppress={"TPC301": "why"}``. A
suppression without a justification still fails the gate.

Exit codes: 0 clean, 1 unsuppressed error/warn findings (with
``--fail-on-violation``), 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _force_virtual_devices(n: int = 8) -> None:
    """Raise the virtual-CPU-device count to >= n BEFORE jax initializes
    (same trick as tests/conftest.py): the distributed entries trace
    real meshes, and the ``--mesh {1,4,8}`` sweep needs 8 devices even
    from a bare ``make analyze`` shell. A no-op when the flag is already
    high enough (pytest) or when jax was initialized first (the mesh
    helpers then fall back to AbstractMesh)."""
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

# the mesh size the distributed entries build against; None = all local
# devices (the --mesh sweep rebinds this per pass)
_MESH_N: Optional[int] = None


def _mesh_n() -> int:
    import jax

    return _MESH_N if _MESH_N is not None else min(8, len(jax.devices()))


def _dist_mesh(**axes: int):
    """Mesh for a distributed entry: concrete over the virtual CPU
    devices when they suffice, AbstractMesh beyond (trace-only)."""
    from paddle_tpu.distributed.jax_compat import virtual_mesh

    return virtual_mesh(dict(axes))


@dataclass
class Entry:
    name: str
    build: Callable  # () -> (fn, args:list, kwargs for analyze_fn)
    note: str = ""
    suppress: Dict[str, str] = field(default_factory=dict)
    # meshable entries re-run under every --mesh size (their build reads
    # _mesh_n()); the rest trace once per sweep
    meshable: bool = False


# --------------------------------------------------------------- entries


def _llama():
    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor, pause_tape
    from paddle_tpu.jit import functional_call, state_arrays
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    return model, Tensor, pause_tape, functional_call, state_arrays


def _llama_decode_step():
    import jax.numpy as jnp

    model, Tensor, pause_tape, functional_call, state_arrays = _llama()
    caches = [c._data for c in model.init_caches(2, 64)]
    state = state_arrays(model)
    tok = jnp.zeros((2, 1), jnp.int32)

    def llama_decode_step(state, caches, tok, t):
        with pause_tape():
            return functional_call(
                model, state, Tensor._wrap(tok),
                caches=[Tensor._wrap(c) for c in caches],
                time_step=Tensor._wrap(t))

    # serving donates the caches (generation scan's donate_argnums=(1,))
    return llama_decode_step, [state, caches, tok, jnp.int32(5)], {
        "donate_argnums": (1,)}


def _llama_prefill():
    import jax.numpy as jnp

    model, Tensor, pause_tape, functional_call, state_arrays = _llama()
    state = state_arrays(model)
    ids = jnp.zeros((2, 32), jnp.int32)

    def llama_prefill(state, ids):
        with pause_tape():
            return functional_call(model, state, Tensor._wrap(ids))

    return llama_prefill, [state, ids], {}


def _hapi_train_step():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import functional_call, param_arrays

    paddle.seed(0)
    mlp = nn.Sequential(nn.Linear(256, 512), nn.ReLU(),
                        nn.Linear(512, 256), nn.ReLU(),
                        nn.Linear(256, 10))
    params = param_arrays(mlp)
    x = jnp.ones((64, 256), jnp.float32)
    y = jnp.zeros((64,), jnp.int32)

    def hapi_train_step(params, x, y):
        def loss_fn(p):
            logits = functional_call(mlp, p, Tensor._wrap(x))
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p = jax.tree_util.tree_map(lambda p, g: p - 1e-3 * g,
                                       params, grads)
        return new_p, loss

    return hapi_train_step, [params, x, y], {"donate_argnums": (0,)}


def _gpt_train_step():
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import functional_call, param_arrays
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(hidden_size=128, num_layers=2, num_heads=4,
                    max_position=128, vocab_size=512)
    model = GPTForCausalLM(cfg)
    model.eval()
    master = param_arrays(model)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), master)
    opt_m = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), master)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)

    def loss_fn(p, ids, labels):
        logits = functional_call(model, p, Tensor._wrap(ids))
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0].astype(jnp.float32)
        return jnp.mean(logz - gold)

    def gpt_train_step(params, master, opt_m, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        new_m = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g,
                                       opt_m, grads)
        new_master = jax.tree_util.tree_map(lambda p, m: p - 1e-4 * m,
                                            master, new_m)
        new_p = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), new_master)
        return new_p, new_master, new_m, loss

    return gpt_train_step, [params, master, opt_m, ids, labels], {
        "donate_argnums": (0, 1, 2)}


def _quant_matmul(weight_dtype):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.nn.quant import weight_only_linear

    rng = np.random.default_rng(0)
    if weight_dtype == "int4":
        w = jnp.asarray(rng.integers(-8, 7, (256, 1024)), jnp.int8)  # packed
    else:
        w = jnp.asarray(rng.integers(-127, 127, (512, 1024)), jnp.int8)
    sc = jnp.ones((1024,), jnp.float32)
    x = jnp.ones((4, 512), jnp.float32)

    def quant_matmul(x, w, sc):
        out = weight_only_linear(Tensor._wrap(x), Tensor._wrap(w),
                                 weight_scale=Tensor._wrap(sc),
                                 weight_dtype=weight_dtype)
        return out._data if isinstance(out, Tensor) else out

    quant_matmul.__name__ = f"quant_matmul_{weight_dtype}"
    return quant_matmul, [x, w, sc], {}


def _dp_psum_step():
    """The examples/train_bert_dp shape: shard_map data-parallel grad
    averaging over the 'dp' axis of the active mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.jax_compat import shard_map

    ndev = _mesh_n()
    mesh = _dist_mesh(dp=ndev)
    W = jnp.ones((128, 128), jnp.float32)
    x = jnp.ones((8 * ndev, 128), jnp.float32)

    def step(W, x):
        def shard_step(W, xs):
            y = xs @ W
            loss = jnp.mean(y * y)
            g = jax.grad(lambda w: jnp.mean((xs @ w) ** 2))(W)
            g = jax.lax.pmean(g, "dp")
            return W - 1e-2 * g, loss

        return shard_map(shard_step, mesh,
                         in_specs=(P(), P("dp", None)),
                         out_specs=(P(), P()))(W, x)

    dp_psum_step = step
    return dp_psum_step, [W, x], {"mesh": mesh, "donate_argnums": (0,)}


def _spec_verify_step():
    """The spec-decode verify program (ISSUE 5): k+1 positions scored in
    one forward through the paged path + in-program acceptance, traced
    exactly as the engine jits it (pages donated)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import Engine
    from paddle_tpu.inference.spec.verifier import make_verify_fn
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    eng = Engine(model, max_slots=2, num_pages=32, page_size=8,
                 chunk_size=4, dtype=jnp.float32, spec="ngram", spec_k=4)
    nb, k = 2, 4
    fn = make_verify_fn(eng, sampling=False)
    fn.__name__ = "spec_verify_step"
    tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
    tables[:, :2] = [[1, 2], [3, 4]]
    args = [eng._params, eng._pages_flat(), jnp.asarray(tables),
            jnp.asarray(np.array([9, 6], np.int32)),       # lengths
            jnp.zeros((nb,), jnp.int32),                   # last_tok
            jnp.zeros((nb, k), jnp.int32),                 # drafts
            jnp.full((nb,), k, jnp.int32),                 # draft_len
            jnp.zeros((nb,), jnp.float32),                 # temps
            jnp.zeros((nb, 2), jnp.uint32)]                # keys
    return fn, args, {"donate_argnums": (1,)}


def _verify_slab_attention():
    """The fused verify/suffix slab kernel (ISSUE 9 tentpole a), traced
    through its interpret-mode pallas_call so liveness/cost see the real
    kernel boundary (the cost pass counts a pallas_call's operand/result
    traffic — the pages stream once, which IS the kernel's byte model)."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import (
        paged_verify_slab_attention)

    rng = np.random.default_rng(0)
    B, m, H, HKV, D, PS, MAXP = 4, 5, 4, 2, 64, 16, 8
    kp = jnp.asarray(rng.standard_normal((1 + B * MAXP, PS, HKV * D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((1 + B * MAXP, PS, HKV * D)),
                     jnp.float32)
    bt = jnp.asarray(np.arange(1, 1 + B * MAXP,
                               dtype=np.int32).reshape(B, MAXP))
    base = jnp.asarray([9, 0, 40, 100], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, m, H, D)), jnp.float32)

    def verify_slab_attention(q, kp, vp, bt, base):
        return paged_verify_slab_attention(q, kp, vp, bt, base,
                                           interpret=True)

    return verify_slab_attention, [q, kp, vp, bt, base], {}


def _chunked_prefill_step():
    """The mixed chunk+decode step (ISSUE 9 tentpole b): one fixed-shape
    program advancing prefilling rows by a chunk and decoding rows by
    one token through the verify/suffix attention path, traced exactly
    as the engine jits it (pages donated)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import Engine, make_mixed_step_fn
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    eng = Engine(model, max_slots=2, num_pages=32, page_size=8,
                 chunk_size=4, dtype=jnp.float32, prefill_chunk=4)
    nb, chunk = 2, 4
    fn = make_mixed_step_fn(eng, sampling=False)
    fn.__name__ = "chunked_prefill_step"
    tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
    tables[:, :2] = [[1, 2], [3, 4]]
    ids = np.zeros((nb, chunk), np.int32)
    args = [eng._params, eng._pages_flat(), jnp.asarray(ids),
            jnp.asarray(np.array([4, 1], np.int32)),   # widths: chunk+decode
            jnp.asarray(np.array([0, 1], np.int32)),   # emit
            jnp.asarray(tables),
            jnp.asarray(np.array([3, 9], np.int32)),   # lengths
            jnp.zeros((nb,), jnp.float32),             # temps
            jnp.zeros((nb, 2), jnp.uint32)]            # keys
    return fn, args, {"donate_argnums": (1,)}


def _tp_train_step():
    """Megatron tensor-parallel train step over the 'mp' axis (ISSUE 10
    tentpole): the Column+Row pair from test_tensor_parallel's model,
    written as the manual shard_map twin of the layers' GSPMD specs —
    forward psum after the row matmul (the Megatron g collective),
    backward psum on the replicated input's grad (the f collective),
    local SGD update on the sharded weights."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, RowParallelLinear)
    from paddle_tpu.distributed.jax_compat import shard_map

    paddle.seed(0)
    mp = _mesh_n()
    mesh = _dist_mesh(mp=mp)
    H, FF, B = 16, 64, 8
    col = ColumnParallelLinear(H, FF, gather_output=False)
    row = RowParallelLinear(FF, H, input_is_parallel=True)
    w1, b1 = col.weight._data, col.bias._data
    w2, b2 = row.weight._data, row.bias._data
    x = jnp.ones((B, H), jnp.float32)

    def tp_train_step(x, w1, b1, w2, b2):
        def body(x, w1, b1, w2, b2):
            def loss_fn(w1, b1, w2, b2):
                h = jax.nn.gelu(x @ w1 + b1)        # [B, FF/mp] local
                y = jax.lax.psum(h @ w2, "mp") + b2  # the g collective
                return jnp.mean(y * y)

            loss, grads = jax.value_and_grad(loss_fn,
                                             argnums=(0, 1, 2, 3))(
                w1, b1, w2, b2)
            g1, gb1, g2, gb2 = grads
            # replicated bias grad reduces over mp (the f conjugate);
            # sharded weight grads are already local
            gb2 = jax.lax.psum(gb2, "mp")
            lr = 1e-2
            return (w1 - lr * g1, b1 - lr * gb1, w2 - lr * g2,
                    b2 - lr * gb2, jax.lax.pmean(loss, "mp"))

        # in_specs mirror the layers' dist_specs: column weight
        # P(None,'mp'), its bias P('mp'), row weight P('mp',None),
        # row bias replicated (post-reduction)
        return shard_map(
            body, mesh,
            in_specs=(P(), P(None, "mp"), P("mp"), P("mp", None), P()),
            out_specs=(P(None, "mp"), P("mp"), P("mp", None), P(), P()),
            check=False)(x, w1, b1, w2, b2)

    return tp_train_step, [x, w1, b1, w2, b2], {
        "mesh": mesh, "check_processes": 2}


def _pipeline_1f1b_stage():
    """One 1F1B pipeline stage over the 'pp' axis: scan over microbatch
    ticks, each tick applying the stage-local layer and ppermuting the
    activation to the next stage — the stage-boundary transfer
    pipeline_engine's shard_map pipe drives (comm that should overlap
    with the next tick's compute)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.jax_compat import shard_map

    pp = _mesh_n()
    mesh = _dist_mesh(pp=pp)
    H, B, M = 32, 4, 4  # hidden, microbatch rows, microbatches
    W = jnp.ones((pp, H, H), jnp.float32) * 0.01  # stage-stacked weights
    x = jnp.ones((B, H), jnp.float32)
    perm = [(i, i + 1) for i in range(pp - 1)]  # fwd stage ring, no wrap

    def pipeline_1f1b_stage(x, W):
        def body(x, w):
            w = w[0]  # this stage's layer

            def tick(h, _):
                out = jax.nn.gelu(h @ w)
                recv = jax.lax.ppermute(out, "pp", perm) if perm else out
                return recv, out

            h, outs = jax.lax.scan(tick, x, None, length=M)
            return h, outs

        return shard_map(body, mesh,
                         in_specs=(P(), P("pp", None, None)),
                         out_specs=(P(), P()), check=False)(x, W)

    return pipeline_1f1b_stage, [x, W], {"mesh": mesh,
                                         "check_processes": 2}


def _context_parallel_attention():
    """Ring attention (context parallelism) over the 'sep' axis: the
    REAL distributed/fleet/meta_parallel/context_parallel.py kernel —
    per-chunk flash attention with (out, lse) log-space merges riding
    ppermute inside a scan."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.distributed.fleet.meta_parallel.context_parallel import (
        ring_attention)

    sep = _mesh_n()
    mesh = _dist_mesh(sep=sep)
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 8 * max(sep, 1), 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
               for _ in range(3))

    def context_parallel_attention(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True)

    return context_parallel_attention, [q, k, v], {
        "mesh": mesh, "check_processes": 2}


def _moe_all_to_all():
    """Expert-parallel MoE dispatch (ISSUE 10 / ROADMAP item 5): the
    reference global_scatter/global_gather shape written as explicit
    all_to_alls over the 'ep' axis — gshard_dispatch (incubate/nn's real
    routing) builds the [T,E,C] one-hots, tokens exchange to their
    expert's device, the local ExpertFFN runs, and the combine a2a
    returns them. Grads flow through both all_to_alls (their transpose
    IS the reverse exchange)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.distributed.jax_compat import shard_map
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
        ExpertFFN, gshard_dispatch)

    paddle.seed(0)
    ep = _mesh_n()
    mesh = _dist_mesh(ep=ep)
    E = ep                      # one expert per device
    H, FF, T, C = 16, 32, 8 * ep, 8  # tokens global, capacity per expert
    experts = [ExpertFFN(H, FF, activation="gelu") for _ in range(E)]
    w1 = jnp.stack([e.fc1.weight._data for e in experts])
    bb1 = jnp.stack([e.fc1.bias._data for e in experts])
    w2 = jnp.stack([e.fc2.weight._data for e in experts])
    bb2 = jnp.stack([e.fc2.bias._data for e in experts])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    gate_logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)

    def moe_all_to_all(x, gate_logits, w1, b1, w2, b2):
        def body(x, gate_logits, w1, b1, w2, b2):
            # top-1 routing over the LOCAL token shard
            val = jax.nn.softmax(gate_logits, axis=-1)
            idx = jnp.argmax(gate_logits, axis=-1)
            top = jnp.take_along_axis(val, idx[:, None], axis=-1)
            dispatch, combine = gshard_dispatch(top, idx[:, None], E, C)
            ein = jnp.einsum("tec,th->ech", dispatch, x)   # [E, C, H]
            # the global_scatter: slot e of every device -> device e
            recv = jax.lax.all_to_all(ein, "ep", split_axis=0,
                                      concat_axis=0)        # [E, C, H]
            toks = recv.reshape(E * C, -1)
            hmid = jax.nn.gelu(toks @ w1[0] + b1[0])
            out = (hmid @ w2[0] + b2[0]).reshape(E, C, -1)
            # the global_gather: results return to their source device
            back = jax.lax.all_to_all(out, "ep", split_axis=0,
                                      concat_axis=0)
            y = jnp.einsum("tec,ech->th", combine, back)
            return jax.lax.pmean(jnp.mean(y * y), "ep")

        def loss_fn(w1, b1, w2, b2):
            return shard_map(
                body, mesh,
                in_specs=(P("ep", None), P("ep", None),
                          P("ep", None, None), P("ep", None),
                          P("ep", None, None), P("ep", None)),
                out_specs=P(), check=False)(
                x, gate_logits, w1, b1, w2, b2)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2, 3))(
            w1, b1, w2, b2)
        return loss, grads

    return moe_all_to_all, [x, gate_logits, w1, bb1, w2, bb2], {
        "mesh": mesh, "check_processes": 2}


def _moe_ep_gspmd():
    """The incubate/nn MoELayer's OWN expert-parallel path (GSPMD): the
    [E,C,H] dispatch einsum with a with_sharding_constraint over the
    mesh axis — the sharding pass sees the constraint boundary, the
    comm pass prices the XLA-inserted exchange (assumed_reshard)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.parallel import set_mesh
    from paddle_tpu.framework.tensor import Tensor, pause_tape
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.incubate.distributed.models.moe.gate import NaiveGate
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import ExpertFFN
    from paddle_tpu.jit import swapped_params

    paddle.seed(0)
    ep = _mesh_n()
    mesh = _dist_mesh(ep=ep)
    H, E = 16, 8  # 8 experts: divisible at every swept mesh size (1/4/8)
    layer = MoELayer(
        d_model=H, experts=[ExpertFFN(H, 2 * H) for _ in range(E)],
        gate=NaiveGate(H, E, topk=2), capacity_factor=4.0,
        axis_name="ep", use_ragged=False)
    layer.eval()
    params = [p._data for _, p in layer.named_parameters()]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, H)), jnp.float32)

    def moe_ep_gspmd(params, x):
        set_mesh(mesh)  # host-side: the layer reads the active mesh
        try:
            with swapped_params(layer, params), pause_tape():
                out = layer(Tensor._wrap(x))
            o = out._data if isinstance(out, Tensor) else out
            return jnp.mean(o.astype(jnp.float32) ** 2)
        finally:
            set_mesh(None)

    return moe_ep_gspmd, [params, x], {"mesh": mesh, "check_processes": 2}


def _tp_serving_engine(prefill_chunk=None):
    """Tiny sharded serving engine at the active sweep mesh size
    (ISSUE 11): tp=1 builds the plain single-chip program, tp>1 the
    shard_map program with column/row-sharded weights and a
    head-sharded page pool — the registry traces whichever the sweep
    asks for, so `make analyze --mesh 1 --mesh 4 --mesh 8` statically
    gates the whole comm plan before any multi-device run."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import Engine
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    tp = _mesh_n()
    cfg = tiny_llama_config(num_heads=8, num_kv_heads=8)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return Engine(model, max_slots=2, num_pages=32, page_size=8,
                  chunk_size=4, dtype=jnp.float32, max_chain=2,
                  prefill_chunk=prefill_chunk,
                  disaggregate=prefill_chunk is not None,
                  tp=tp if tp > 1 else None)


def _tp_sharded_decode_step():
    """The tensor-parallel decode chain (ISSUE 11 tentpole): weights
    column/row-sharded, KV pool head-sharded, the whole lax.scan inside
    ONE shard_map region so page shards carry locally across steps (no
    TPC502 reshard at the step boundary) and the only collectives are
    the per-layer Megatron g psums (no TPC503 weight gather)."""
    import jax.numpy as jnp
    import numpy as np

    eng = _tp_serving_engine()
    nb = 2
    fn = eng.runner.traceable("decode", sampling=False, k=1)
    fn.__name__ = "tp_sharded_decode_step"
    tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
    tables[:, :2] = [[1, 2], [3, 4]]
    args = [eng._params, eng._pages_flat(), jnp.asarray(tables),
            jnp.asarray(np.array([9, 6], np.int32)),   # lengths
            jnp.zeros((nb,), jnp.int32),               # last_tok
            jnp.zeros((nb,), jnp.float32),             # temps
            jnp.zeros((nb, 2), jnp.uint32)]            # keys
    kw = {"donate_argnums": (1,), "check_processes": 2}
    if eng.runner.mesh is not None:
        kw["mesh"] = eng.runner.mesh
    return fn, args, kw


def _tp_sharded_mixed_step():
    """The tensor-parallel mixed chunk+decode step (ISSUE 11): the
    prefill-role program of the disaggregated scheduler, sharded
    exactly like the decode chain."""
    import jax.numpy as jnp
    import numpy as np

    eng = _tp_serving_engine(prefill_chunk=4)
    nb, chunk = 2, 4
    fn = eng.runner.traceable("mixed", sampling=False)
    fn.__name__ = "tp_sharded_mixed_step"
    tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
    tables[:, :2] = [[1, 2], [3, 4]]
    ids = np.zeros((nb, chunk), np.int32)
    args = [eng._params, eng._pages_flat(), jnp.asarray(ids),
            jnp.asarray(np.array([4, 1], np.int32)),   # widths
            jnp.asarray(np.array([0, 1], np.int32)),   # emit
            jnp.asarray(tables),
            jnp.asarray(np.array([3, 9], np.int32)),   # lengths
            jnp.zeros((nb,), jnp.float32),             # temps
            jnp.zeros((nb, 2), jnp.uint32)]            # keys
    kw = {"donate_argnums": (1,), "check_processes": 2}
    if eng.runner.mesh is not None:
        kw["mesh"] = eng.runner.mesh
    return fn, args, kw


def _multi_step_decode():
    """The multi-step scheduling handoff (ISSUE 12): two decode-chain
    programs composed back-to-back the way ``Engine.step(n)``'s fast
    path dispatches them — the second chain's inputs are the first's
    device outputs (pages, lengths, keys, final token column), with no
    host fetch between. The composed twin statically gates the chain-
    to-chain boundary at tp>1: page shards must carry locally between
    the two shard_map regions (no TPC502 reshard) and the only
    collectives stay the per-layer Megatron g psums (no TPC503)."""
    import jax.numpy as jnp
    import numpy as np

    eng = _tp_serving_engine()
    nb = 2
    chain = eng.runner.traceable("decode", sampling=False, k=1)

    def multi_step_decode(params, pages_flat, tables, lengths, last,
                          temps, keys):
        toks1, pages_flat, lengths, keys, bad1 = chain(
            params, pages_flat, tables, lengths, last, temps, keys)
        toks2, pages_flat, lengths, keys, bad2 = chain(
            params, pages_flat, tables, lengths, toks1[:, -1], temps,
            keys)
        return toks1, toks2, pages_flat, lengths, keys, bad1 | bad2

    tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
    tables[:, :2] = [[1, 2], [3, 4]]
    args = [eng._params, eng._pages_flat(), jnp.asarray(tables),
            jnp.asarray(np.array([9, 6], np.int32)),   # lengths
            jnp.zeros((nb,), jnp.int32),               # last_tok
            jnp.zeros((nb,), jnp.float32),             # temps
            jnp.zeros((nb, 2), jnp.uint32)]            # keys
    kw = {"donate_argnums": (1,), "check_processes": 2}
    if eng.runner.mesh is not None:
        kw["mesh"] = eng.runner.mesh
    return multi_step_decode, args, kw


def _moe_decode_step():
    """Expert-parallel MoE decode chain (ISSUE 17): routing replicated
    (every shard ranks ALL tokens, so the drop set and combine weights
    are bit-identical to ep=1 by construction), stacked expert weights
    P('ep', ...), and per MoE layer exactly one all_to_all (capacity-
    slot token dispatch) + one all_gather (expert outputs) INSIDE the
    same shard_map region as the decode scan — no TPC502 boundary
    reshard, no TPC503 weight gather. At mesh 1 the python-level
    ``ax is None`` branches emit no collectives at all."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import Engine
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         tiny_moe_llama_config)

    paddle.seed(0)
    ep = _mesh_n()
    model = LlamaForCausalLM(tiny_moe_llama_config())
    model.eval()
    eng = Engine(model, max_slots=2, num_pages=32, page_size=8,
                 chunk_size=4, dtype=jnp.float32, max_chain=2,
                 ep=ep if ep > 1 else None)
    nb = 2
    fn = eng.runner.traceable("decode", sampling=False, k=1)
    fn.__name__ = "moe_decode_step"
    tables = np.zeros((nb, eng.max_pages_per_seq), np.int32)
    tables[:, :2] = [[1, 2], [3, 4]]
    args = [eng._params, eng._pages_flat(), jnp.asarray(tables),
            jnp.asarray(np.array([9, 6], np.int32)),   # lengths
            jnp.zeros((nb,), jnp.int32),               # last_tok
            jnp.zeros((nb,), jnp.float32),             # temps
            jnp.zeros((nb, 2), jnp.uint32)]            # keys
    kw = {"donate_argnums": (1,), "check_processes": 2}
    if eng.runner.mesh is not None:
        kw["mesh"] = eng.runner.mesh
    return fn, args, kw


ENTRIES: List[Entry] = [
    Entry("llama_decode_step", _llama_decode_step,
          "serving decode: one token through the slab KV cache"),
    Entry("llama_prefill", _llama_prefill, "serving prefill (flash path)"),
    Entry("hapi_train_step", _hapi_train_step,
          "hapi Model-style MLP train step (fwd+bwd+SGD)"),
    Entry("gpt_train_step", _gpt_train_step,
          "train step: bf16 compute, fp32 master, momentum"),
    Entry("quant_matmul_int8", lambda: _quant_matmul("int8"),
          "weight-only int8 GEMM (nn.quant XLA path)"),
    Entry("quant_matmul_int4", lambda: _quant_matmul("int4"),
          "weight-only packed-int4 GEMM"),
    Entry("dp_psum_step", _dp_psum_step,
          "shard_map data-parallel step (collective pass coverage)",
          meshable=True),
    Entry("tp_train_step", _tp_train_step,
          "Megatron TP train step: Column+Row pair, fwd/bwd psum, SGD",
          meshable=True),
    Entry("pipeline_1f1b_stage", _pipeline_1f1b_stage,
          "1F1B stage: microbatch scan + ppermute stage boundary",
          meshable=True),
    Entry("context_parallel_attention", _context_parallel_attention,
          "ring attention over 'sep' (real context_parallel kernel)",
          meshable=True),
    Entry("moe_all_to_all", _moe_all_to_all,
          "expert-parallel MoE: gshard dispatch + explicit all_to_alls",
          meshable=True),
    Entry("moe_ep_gspmd", _moe_ep_gspmd,
          "MoELayer GSPMD EP path: sharding-constraint boundary",
          meshable=True),
    Entry("spec_verify_step", _spec_verify_step,
          "spec-decode verify: k+1 positions + acceptance, paged path"),
    Entry("verify_slab_attention", _verify_slab_attention,
          "fused verify/suffix slab kernel (pallas_call boundary)"),
    Entry("chunked_prefill_step", _chunked_prefill_step,
          "mixed chunk+decode step: chunked prefill + width-1 decode"),
    Entry("tp_sharded_decode_step", _tp_sharded_decode_step,
          "TP serving decode chain: sharded weights/pool, per-layer "
          "g psums (ISSUE 11)", meshable=True),
    Entry("tp_sharded_mixed_step", _tp_sharded_mixed_step,
          "TP mixed chunk+decode step: the disaggregated prefill role "
          "sharded like decode", meshable=True),
    Entry("multi_step_decode", _multi_step_decode,
          "multi-step scheduling: two decode chains composed device-"
          "side, one harvest fence (ISSUE 12)", meshable=True),
    Entry("moe_decode_step", _moe_decode_step,
          "EP MoE decode chain: replicated routing, expert-sharded "
          "weights, a2a dispatch + all_gather combine (ISSUE 17)",
          meshable=True),
]


# --------------------------------------------------------------- running


def run_entry(entry: Entry, budget_bytes: Optional[int] = None,
              mesh_n: Optional[int] = None,
              label: Optional[str] = None):
    """Analyze one registry entry, optionally under an explicit mesh
    size (rebinds the module-global the meshable builders read)."""
    global _MESH_N

    from paddle_tpu.analysis.jaxpr import analyze_fn

    saved = _MESH_N
    if mesh_n is not None:
        _MESH_N = mesh_n
    try:
        fn, args, kw = entry.build()
    finally:
        _MESH_N = saved
    kw["entry"] = label or entry.name
    if budget_bytes is not None:
        kw.setdefault("budget_bytes", budget_bytes)
    return analyze_fn(fn, *args, **kw)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="analyze_tpu",
        description="tpucheck — jaxpr-level program analysis over the "
                    "repo's compiled entry points. Suppress a finding by "
                    "adding a justified entry-level suppression in the "
                    "registry (tools/analyze_tpu.py).")
    ap.add_argument("--entry", action="append", default=None,
                    help="entry name (repeatable; default: all)")
    ap.add_argument("--list-entries", action="store_true")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="alias for --format json (sorted, diffable)")
    ap.add_argument("--mesh", action="append", type=int, default=None,
                    metavar="N",
                    help="mesh size to trace the distributed entries "
                         "under (repeatable: --mesh 1 --mesh 4 --mesh 8 "
                         "sweeps; uses virtual devices / AbstractMesh, "
                         "no real slice needed). Non-mesh entries trace "
                         "once per sweep.")
    ap.add_argument("--fail-on-violation", action="store_true",
                    help="exit 1 on any unsuppressed error/warn finding")
    ap.add_argument("--show-info", action="store_true",
                    help="also print advisory (info) findings")
    ap.add_argument("--budget-gb", type=float, default=None,
                    help="HBM budget for TPC101, in GiB")
    args = ap.parse_args(argv)
    if args.json:
        args.format = "json"

    if args.list_rules:
        from paddle_tpu.analysis.jaxpr.rules import JRULES

        fam = None
        for r in sorted(JRULES.values(), key=lambda r: r.id):
            if r.family != fam:
                fam = r.family
                print(f"\n[{fam}]")
            print(f"  {r.id}  {r.name} ({r.severity})\n      "
                  f"{r.description}")
        return 0
    if args.list_entries:
        for e in ENTRIES:
            print(f"  {e.name:22s} {e.note}")
        return 0

    chosen = ENTRIES
    if args.entry:
        by_name = {e.name: e for e in ENTRIES}
        missing = [n for n in args.entry if n not in by_name]
        if missing:
            print(f"analyze_tpu: unknown entries {missing}; "
                  f"--list-entries shows the registry", file=sys.stderr)
            return 2
        chosen = [by_name[n] for n in args.entry]

    budget = (int(args.budget_gb * (1 << 30))
              if args.budget_gb is not None else None)

    mesh_sizes: List[Optional[int]] = list(args.mesh) if args.mesh \
        else [None]

    gating = []        # unsuppressed error/warn
    suppressed = []    # (finding, reason)
    infos = []
    reports = {}       # label -> report
    n_runs = 0
    for i, mn in enumerate(mesh_sizes):
        for e in chosen:
            if i > 0 and not e.meshable:
                continue  # non-mesh entries are mesh-invariant
            label = e.name
            if mn is not None and e.meshable and len(mesh_sizes) > 1:
                label = f"{e.name}@m{mn}"
            report = run_entry(e, budget, mesh_n=mn, label=label)
            reports[label] = report
            n_runs += 1
            for f in report.findings:
                if f.severity == "info":
                    infos.append(f)
                elif f.rule in e.suppress and e.suppress[f.rule].strip():
                    suppressed.append((f, e.suppress[f.rule]))
                else:
                    gating.append(f)

    if args.format == "json":
        payload = {
            "entries": sorted(reports),
            "mesh_sizes": [m for m in mesh_sizes if m is not None],
            "findings": [vars(f.to_violation()) | {
                "severity": f.severity, "pass": f.passname, "data": f.data}
                for f in gating],
            "suppressed": [vars(f.to_violation()) | {"reason": r}
                           for f, r in suppressed],
            "info": [vars(f.to_violation()) for f in infos],
            "memory": {
                n: {"peak_bytes": r.memory.peak_bytes,
                    "peak_temp_out_bytes": r.memory.peak_temp_out_bytes}
                for n, r in sorted(reports.items())
                if r.memory is not None},
            "cost": {
                n: {"flops": r.cost.flops, "hbm_bytes": r.cost.hbm_bytes,
                    "predicted_ms": r.cost.predicted_seconds() * 1e3}
                for n, r in sorted(reports.items())
                if r.cost is not None},
            "comm": {
                n: {"wire_bytes": r.comm.wire_bytes,
                    "comm_ms": r.comm.comm_seconds * 1e3,
                    "overlap_fraction": round(r.comm.overlap_fraction, 4),
                    "n_collectives": r.comm.n_collectives,
                    "predicted_step_ms": (
                        (r.cost.predicted_seconds() if r.cost else 0.0)
                        + r.comm.comm_seconds
                        - min(r.comm.overlapped_seconds,
                              r.cost.predicted_seconds()
                              if r.cost else 0.0)) * 1e3}
                for n, r in sorted(reports.items())
                if r.comm is not None and r.comm.n_collectives > 0},
        }
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        for f in gating:
            print(f.to_violation().format())
        for f, reason in suppressed:
            v = f.to_violation()
            v.suppressed, v.suppress_reason = True, reason
            print(v.format())
        if args.show_info:
            for f in infos:
                print(f.to_violation().format())
        mesh_note = ""
        if args.mesh:
            mesh_note = f" (mesh sweep {sorted(set(args.mesh))})"
        print(f"tpucheck: {n_runs} entry runs{mesh_note}, {len(gating)} "
              f"finding{'s' if len(gating) != 1 else ''}, "
              f"{len(suppressed)} suppressed, {len(infos)} advisory")

    if args.fail_on_violation and gating:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
