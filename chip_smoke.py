#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one TPU chip: serve phase, train phase
    python3 chip_smoke.py --chips 4  # four chips: the sharded paths ONLY

One process, no platform variables, run from the root of a plain copy of the
tree. It drives the two main paths once, through the entry points a user
calls, at the full WIDTH of the models (depth is cut where one chip's 16 GB
forces it; weights are random, from ``--seed``):

* serve: ``LlamaForCausalLM`` at ``llama2_7b()`` widths, bf16 weights and KV
  pages, ``Engine`` -> ``ServingFrontend`` -> ``ApiServer`` on an ephemeral
  port, streaming HTTP completions of mixed prompt length (some >= 1024
  tokens, more requests than slots); then prefill + paged decode against a
  full forward pass of the same model, on LOGITS;
* train: ``GPTForCausalLM(gpt2_medium())`` at full depth, S=1024, bf16
  compute with an fp32 master, five steps of the user-facing compiled step
  (``functional_call`` + a ``paddle_tpu.optimizer``) on a fixed batch.

Every phase fails loudly: an exception or a failed check anywhere ends the
process non-zero, and the result line is printed only after every phase has
passed. There is no CPU branch and no size switch: without an accelerator
the script says why and exits non-zero. Earlier output lines are set-up
facts (``setup:``) and phase facts, none of them a performance number — wall
seconds here include compilation and are for budgeting a chip call only.
The LAST line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# ---- sizes (module constants: a rehearsal on the CPU replaces THESE from a
# scratch script; the program itself has no tiny mode) ----------------------
# llama2_7b() is 32 layers x 202 M parameters = 13.5 GB in bf16, which alone
# fills the chip. 16 layers are 6.5 GB of weights (+0.5 GB embeddings/head)
# and leave room for a 4 GB page pool and the prefill's temporaries.
SERVE_DEPTH = 16
SERVE_SLOTS = 4
SERVE_PAGE_SIZE = 16
SERVE_NUM_PAGES = 1024   # 16k tokens x 256 KB a token (16 layers) = 4 GB
SERVE_CHUNK = 8
# (prompt tokens, tokens to generate): two length classes keep the prefill
# programs to two buckets (2048 and 128); 8 requests on 4 slots, so slots
# recycle mid-flight; two budgets, so completions are ragged; the longest
# context passes 2048 tokens. A few hundred tokens each are many decode
# chains at one bucket, which is what lets the engine measure its
# dispatch ratio (decode is cheap here; the compiles are what costs)
SERVE_REQUESTS = [(1536, 384), (90, 320), (1100, 384), (70, 320),
                  (1900, 320), (120, 384), (1300, 320), (100, 384)]
CHECK_PREFILL, CHECK_DECODE = 1024, 8   # logits check: 1024 prefilled + 8
HTTP_TIMEOUT_S = 1000    # a stream's first token waits for cold compiles
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 12, 1024, 5, 3e-4
# bf16 keeps 8 bits of mantissa (eps = 2^-8 = 0.0039). Two bf16 programs of
# the same model that differ in order of accumulation (Pallas paged decode
# against XLA attention; four-way split sums against one) may differ by a
# few eps per layer, compounding over the depth: hold the largest logit
# difference to 16 eps = 6% of the largest reference logit. A masking or
# paging bug moves logits by their own magnitude and cannot hide under it.
LOGIT_REL_TOL = 16 * 2.0 ** -8
# one-chip and dp2 x mp2 training run the same bf16 arithmetic in another
# order; the loss is a mean over 12k tokens, so its rounding error averages
# out and 1% is already generous
LOSS_REL_TOL = 1e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def require_tpu(chips):
    """The device the run is for, or an exit: no accelerator, no smoke."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: jax.default_backend() is {backend!r}, not "
                 "'tpu' — this script proves the system on the chip and has "
                 "no CPU branch (rehearse on the CPU with the tests)")
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but jax sees "
                 f"{len(devices)} device(s)")
    return devices[:chips]


def setup_facts():
    """Turn the compile cache on (before the first compile) and say what
    the run rests on: cache directory, native helpers."""
    from paddle_tpu import native
    from paddle_tpu.framework.compile_cache import enable_compilation_cache

    print(f"setup: compile cache at {enable_compilation_cache()}")
    for name in ("tcp_store", "ring_buffer"):
        built = native.load(name, [f"{name}.cc"]) is not None
        print(f"setup: native {name}: "
              + ("built from source" if built else "pure-Python fallback"))


def memory_line(tag, devices):
    for d in devices:
        s = d.memory_stats()
        print(f"setup: memory {tag} [{d.id}]: in_use "
              f"{s['bytes_in_use'] / 2**30:.2f} GiB, peak "
              f"{s['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{s['bytes_limit'] / 2**30:.2f} GiB")


def require_mosaic(text, what, also=()):
    """The compiled program must hold the Mosaic kernel: one that gave way
    to its jnp twin at these aligned shapes is a failure, not a pass."""
    for needle in ("tpu_custom_call",) + tuple(also):
        check(needle in text,
              f"{what}: no {needle!r} in the compiled program")
    print(f"{what}: compiled program holds "
          + ", ".join(("tpu_custom_call",) + tuple(also)))


# ------------------------------------------------------------------ serve


def serve_config():
    from paddle_tpu.models.llama import llama2_7b

    return dataclasses.replace(llama2_7b(), num_layers=SERVE_DEPTH)


def build_serve_model(seed):
    """Seeded random weights at llama2_7b() widths, bf16 the way a user of
    the library gets them: construct (fp32), then ``model.bfloat16()``."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    cfg = serve_config()
    model = LlamaForCausalLM(cfg)
    model.eval()
    model.bfloat16()
    n = cfg.num_params()
    print(f"serve: LlamaForCausalLM hidden {cfg.hidden_size}, "
          f"{cfg.num_heads} heads x {cfg.head_dim}, FF "
          f"{cfg.intermediate_size}, vocab {cfg.vocab_size}, depth "
          f"{cfg.num_layers} of 32 ({n / 1e9:.2f} B parameters, "
          f"{2 * n / 2**30:.2f} GiB in bf16 via model.bfloat16())")
    return cfg, model


def build_engine(model, tp=None, num_pages=SERVE_NUM_PAGES):
    import jax.numpy as jnp

    from paddle_tpu.inference.engine import Engine

    return Engine(model, max_slots=SERVE_SLOTS, num_pages=num_pages,
                  page_size=SERVE_PAGE_SIZE, chunk_size=SERVE_CHUNK,
                  dtype=jnp.bfloat16, tp=tp)


def stream_completion(base, prompt, max_tokens):
    """One streaming HTTP completion, read to its end."""
    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    tokens, finish, done = [], None, False
    with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
        for line in r:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            if line[6:] == "[DONE]":
                done = True
                break
            choice = json.loads(line[6:])["choices"][0]
            tokens.extend(choice["token_ids"])
            finish = choice["finish_reason"] or finish
    return tokens, finish, done


def serve_requests(eng, cfg, seed):
    """Engine -> ServingFrontend -> ApiServer, as
    examples/serve_llama_paged.py builds them; every request streamed over
    HTTP by its own client thread."""
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.server import ApiServer

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n, _ in SERVE_REQUESTS]
    server = ApiServer(ServingFrontend(eng), port=0,
                       model_name="llama-paged")

    async def serve():
        await server.start()
        base = f"http://127.0.0.1:{server.port}"
        print(f"serve: api on {base}/v1/completions, "
              f"{len(SERVE_REQUESTS)} streaming requests on "
              f"{SERVE_SLOTS} slots")
        loop = asyncio.get_running_loop()
        try:
            with ThreadPoolExecutor(len(SERVE_REQUESTS)) as pool:
                return await asyncio.gather(*[
                    loop.run_in_executor(pool, stream_completion, base, p,
                                         new)
                    for p, (_, new) in zip(prompts, SERVE_REQUESTS)])
        finally:
            await server.shutdown()

    results = asyncio.run(serve())
    for i, ((n, new), (toks, finish, done)) in enumerate(
            zip(SERVE_REQUESTS, results)):
        check(done and finish == "stop" and len(toks) == new,
              f"request {i} (prompt {n}, {new} tokens asked): got "
              f"{len(toks)} tokens, finish_reason {finish!r}, stream "
              f"{'ended' if done else 'cut'}")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"request {i}: token ids out of the vocabulary")
    print(f"serve: {len(results)} of {len(results)} streams finished "
          "un-failed with the token count asked for")
    return prompts


def decode_evidence(eng, also=()):
    """Every decode-chain program the engine built to serve: its compiled
    text holds the Mosaic paged-attention kernel."""
    check(eng.runner.decode_fns, "serve: no decode program was built")
    for nb, k, sampling in sorted(eng.runner.decode_fns):
        require_mosaic(eng.runner.decode_program_text(nb, k, sampling),
                       f"serve: decode program (rows {nb}, chain {k})",
                       also)


def paged_logits(eng, model, ids):
    """Logits of the PAGED path for ``ids`` [1, CHECK_PREFILL +
    CHECK_DECODE]: prefill the first CHECK_PREFILL tokens into pages, then
    decode the rest one token a step (teacher-forced), through the model's
    paged forward under the engine's own runner — so a tp=4 engine computes
    them sharded, with the weights where the engine placed them. Pages are a
    private little pool, not the engine's. Returns [1 + CHECK_DECODE, V]."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.tensor import Tensor, pause_tape
    from paddle_tpu.jit import swapped_tensors
    from paddle_tpu.ops.pallas.paged_attention import PagedCacheState

    cfg, ps = eng.cfg, eng.page_size
    layers = cfg.num_layers
    total = CHECK_PREFILL + CHECK_DECODE
    n_pages = -(-total // ps)
    lanes = cfg.num_kv_heads * cfg.head_dim
    pages = eng.runner.place_pages(
        [jnp.zeros((n_pages + 1, ps, lanes), eng.dtype)
         for _ in range(2 * layers)])
    # page 0 is the trash page, like the engine's
    table = np.zeros((1, eng.max_pages_per_seq), np.int32)
    table[0, :n_pages] = 1 + np.arange(n_pages)

    def forward(params, pages_flat, ids, table, lengths, valid):
        with swapped_tensors(eng._swap, params), pause_tape():
            states = [PagedCacheState(pages_flat[i], pages_flat[layers + i],
                                      None, table, lengths, ps,
                                      prefill_valid=valid)
                      for i in range(layers)]
            logits, new = model.forward(Tensor._wrap(ids), caches=states)
        return (logits._data[:, -1].astype(jnp.float32),
                [s.k_pages for s in new] + [s.v_pages for s in new],
                new[0].lengths)

    prefill = eng.runner.wrap(
        lambda p, pg, i, t, ln, v: forward(p, pg, i, t, ln, v),
        n_rest=4, out_desc=("r", "pages", "r"), donate=())
    decode = eng.runner.wrap(
        lambda p, pg, i, t, ln: forward(p, pg, i, t, ln, None),
        n_rest=3, out_desc=("r", "pages", "r"), donate=())
    table = jnp.asarray(table)
    out, pages, lengths = prefill(
        eng._params, pages, ids[:, :CHECK_PREFILL], table,
        jnp.zeros((1,), jnp.int32), jnp.full((1,), CHECK_PREFILL, jnp.int32))
    rows = [out]
    for t in range(CHECK_PREFILL, total):
        out, pages, lengths = decode(eng._params, pages, ids[:, t:t + 1],
                                     table, lengths)
        rows.append(out)
    check(int(lengths[0]) == total, "paged path lost count of its tokens")
    return np.asarray(jax.device_get(jnp.concatenate(rows)))


def full_forward_logits(model, ids):
    """The reference: one cache-less forward of the same model over the
    whole sequence (its length is not a multiple of 128, so attention is
    plain XLA, no Pallas kernel). Rows CHECK_PREFILL-1 .. end."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import functional_call, state_arrays

    logits = jax.jit(lambda state, ids: functional_call(
        model, state, Tensor._wrap(ids))[0, CHECK_PREFILL - 1:].astype(
            jnp.float32))(state_arrays(model), ids)
    return np.asarray(jax.device_get(logits))


def compare_logits(got, ref, what):
    check(got.shape == ref.shape == (1 + CHECK_DECODE, ref.shape[-1]),
          f"{what}: logits shape {got.shape} against {ref.shape}")
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{what}: non-finite logits")
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    same = int((got.argmax(-1) == ref.argmax(-1)).sum())
    print(f"{what}: max |dlogit| / max |logit| = {rel:.4f} (tolerance "
          f"{LOGIT_REL_TOL:.4f}), greedy token agrees at {same} of "
          f"{len(ref)} positions")
    check(rel <= LOGIT_REL_TOL,
          f"{what}: logits differ by {rel:.4f} of the largest logit, over "
          f"the {LOGIT_REL_TOL:.4f} that bf16 rounding explains")


def check_ids(cfg, seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + 1)
    return jnp.asarray(rng.integers(
        0, cfg.vocab_size, (1, CHECK_PREFILL + CHECK_DECODE)), jnp.int32)


def serve_phase(seed, devices):
    t0 = time.perf_counter()
    cfg, model = build_serve_model(seed)
    memory_line("after the model is built and cast", devices)
    eng = build_engine(model)
    check(cfg.max_position - SERVE_CHUNK - 1 >= 2048,
          "a context of 2048 tokens is not admissible")
    pool_gib = (2 * cfg.num_layers * SERVE_NUM_PAGES * SERVE_PAGE_SIZE
                * cfg.num_kv_heads * cfg.head_dim * 2) / 2**30
    print(f"serve: Engine(max_slots={SERVE_SLOTS}, num_pages="
          f"{SERVE_NUM_PAGES}, page_size={SERVE_PAGE_SIZE}, chunk_size="
          f"{SERVE_CHUNK}, dtype=bfloat16): {pool_gib:.2f} GiB of pages, "
          f"contexts up to {cfg.max_position - SERVE_CHUNK - 1} tokens")
    t1 = time.perf_counter()
    serve_requests(eng, cfg, seed)
    print(f"setup: serve wall {time.perf_counter() - t1:.0f} s, cold "
          "compiles included (prefill buckets "
          f"{sorted(b for (_, b), *_ in eng.runner.prefill_fns)}, decode "
          f"programs {sorted(eng.runner.decode_fns)})")
    ratio = eng._dispatch_ratio
    print("setup: engine dispatch ratio (chain-boundary cost in chunks of "
          "compute, measured on this chip; the prior is "
          f"{eng.DISPATCH_COST_CHUNKS_PRIOR}): "
          + ("not measured in this run (the engine fits it only from warm "
             "pure-decode steps at two depths of one bucket)"
             if ratio is None else f"{ratio:.3f}"))
    decode_evidence(eng)
    ids = check_ids(cfg, seed)
    compare_logits(paged_logits(eng, model, ids),
                   full_forward_logits(model, ids),
                   "serve: prefill + paged decode against a full forward")
    memory_line("after the serve phase", devices)
    print(f"setup: serve phase wall {time.perf_counter() - t0:.0f} s")


# ------------------------------------------------------------------ train


def build_train(seed):
    """gpt2_medium() at full depth: bf16 parameters, and an AdamW whose
    state tree keeps the fp32 master (multi_precision) — the compiled path
    of paddle_tpu.optimizer, as examples/train_bert_dp.py uses it."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_medium

    paddle.seed(seed)
    cfg = gpt2_medium()
    model = GPTForCausalLM(cfg)
    model.train()
    model.bfloat16()
    opt = optimizer.AdamW(learning_rate=TRAIN_LR, multi_precision=True)
    rng = np.random.default_rng(seed)
    batch = rng.integers(0, cfg.vocab_size,
                         (TRAIN_BATCH, TRAIN_SEQ + 1)).astype(np.int32)
    print(f"train: GPTForCausalLM(gpt2_medium()) {cfg.num_layers} x "
          f"{cfg.hidden_size} x {cfg.num_heads} heads, vocab "
          f"{cfg.vocab_size}, batch {TRAIN_BATCH} x S={TRAIN_SEQ}, bf16 "
          "compute with fp32 master (AdamW multi_precision)")
    return cfg, model, opt, batch[:, :-1], batch[:, 1:]


def make_train_step(model, opt):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import functional_call

    def step(params, opt_state, ids, labels, step_no):
        def loss_fn(p):
            logits = functional_call(model, p, Tensor._wrap(ids))
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits, labels[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold.astype(jnp.float32))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p, new_s = opt.apply_gradients_tree(params, grads, opt_state,
                                                TRAIN_LR, step_no)
        return new_p, new_s, loss

    return jax.jit(step, donate_argnums=(0, 1))


def run_train(step, params, opt_state, ids, labels, what):
    """TRAIN_STEPS steps on the fixed batch; the loss of every step."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, ids, labels,
                          jnp.float32(1)).compile()
    print(f"setup: {what}: step compiled in "
          f"{time.perf_counter() - t0:.0f} s")
    losses = []
    for i in range(TRAIN_STEPS):
        params, opt_state, loss = compiled(params, opt_state, ids, labels,
                                           jnp.float32(i + 1))
        losses.append(float(jax.device_get(loss)))
    print(f"{what}: losses " + " ".join(f"{x:.4f}" for x in losses))
    check(all(np.isfinite(losses)), f"{what}: non-finite loss")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall on a fixed batch "
          f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    return losses, compiled.as_text()


def train_phase(seed, devices):
    import jax.numpy as jnp

    from paddle_tpu.jit import param_arrays

    t0 = time.perf_counter()
    cfg, model, opt, ids, labels = build_train(seed)
    check(all(blk.attn._packed_ok(TRAIN_SEQ) for blk in model.gpt.h),
          "train: the packed-attention path is not eligible at S="
          f"{TRAIN_SEQ}")
    params = param_arrays(model)
    step = make_train_step(model, opt)
    losses, text = run_train(step, params, opt.init_state_tree(params),
                             jnp.asarray(ids), jnp.asarray(labels), "train")
    # the packed path's kernel, not merely some kernel
    require_mosaic(text, "train: step program", also=("causal_flash",))
    memory_line("after the train phase", devices)
    print(f"setup: train phase wall {time.perf_counter() - t0:.0f} s, cold "
          "compile included")


# ------------------------------------------------------------ four chips


def sharded_serve_phase(seed, devices):
    """The serve model behind Engine(tp=4) against Engine(tp=1) in this
    process: same weights, same prompt, logits of the paged path."""
    cfg, model = build_serve_model(seed)
    # both engines' pools share device 0 with the whole model here: half
    # the one-chip pool each, ample for the few requests below
    eng1 = build_engine(model, num_pages=SERVE_NUM_PAGES // 2)
    eng4 = build_engine(model, tp=len(devices),
                        num_pages=SERVE_NUM_PAGES // 2)
    memory_line("with the tp=1 and tp=4 engines built", devices)

    def holders(arr):
        return {s.device for s in arr.addressable_shards
                if s.data.size < arr.size}

    weights = [a for a in eng4._params if a.ndim == 2 and holders(a)]
    check(weights, "tp=4: no weight is split across devices")
    for what, arr in (("projection weight", weights[0]),
                      ("layer-0 K pages", eng4.k_pages[0])):
        on = holders(arr)
        check(len(on) == len(devices),
              f"tp=4: a {what} sits on {len(on)} device(s), not "
              f"{len(devices)}")
        print(f"sharded serve: {what} {tuple(arr.shape)} in shards of "
              f"{tuple(arr.addressable_shards[0].data.shape)} on "
              f"{len(on)} distinct devices")
    ids = check_ids(cfg, seed)
    compare_logits(paged_logits(eng4, model, ids),
                   paged_logits(eng1, model, ids),
                   "sharded serve: tp=4 paged logits against tp=1")
    # the same few prompts through both engines: every stream finishes.
    # Greedy tokens are reported, not held equal: one near-tie in bf16
    # logits forks a stream, and the logits above are the check
    rng = np.random.default_rng(seed)
    asked = SERVE_REQUESTS[:SERVE_SLOTS]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _ in asked]
    streams = {}
    for eng, name in ((eng1, "tp=1"), (eng4, "tp=4")):
        reqs = [eng.add_request(p, new)
                for p, (_, new) in zip(prompts, asked)]
        eng.run()
        for r, (n, new) in zip(reqs, asked):
            check(r.done and not r.failed and len(r.tokens) == new,
                  f"{name}: request (prompt {n}) ended with "
                  f"{len(r.tokens)} of {new} tokens, failure "
                  f"{r.failure_reason!r}")
        streams[name] = [t for r in reqs for t in r.tokens]
    same = sum(a == b for a, b in zip(streams["tp=1"], streams["tp=4"]))
    print(f"sharded serve: both engines served {len(asked)} requests; "
          f"{same} of {len(streams['tp=1'])} greedy tokens identical")
    decode_evidence(eng4, also=("all-reduce",))
    memory_line("after the sharded serve phase", devices)


def hybrid_train_phase(seed, devices):
    """fleet dp=2 x mp=2 of the train model on the 2x2 mesh against its
    one-chip loss trajectory: the same step function, the parameters placed
    by their Megatron dist specs and the batch split over dp."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import param_arrays

    cfg, model, opt, ids, labels = build_train(seed)
    step = make_train_step(model, opt)
    params = param_arrays(model)
    start = {k: np.asarray(v) for k, v in params.items()}
    one_chip, _ = run_train(step, params, opt.init_state_tree(params),
                            jnp.asarray(ids), jnp.asarray(labels),
                            "hybrid train: one chip")

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    mesh = fleet.init(is_collective=True, strategy=strategy).mesh
    for name, p in model.named_parameters():
        p._data = jnp.asarray(start[name])  # the step donated the old ones
        p.dist_spec = megatron_spec(name, P)
    fleet.distributed_model(model)          # places by dist_spec
    params = param_arrays(model)
    split = [k for k, v in params.items()
             if len({s.device for s in v.addressable_shards
                     if s.data.size < v.size}) > 1]
    check(split, "hybrid train: no parameter is split over mp")
    data = NamedSharding(mesh, P("dp", None))
    with mesh:
        hybrid, text = run_train(
            step, params, opt.init_state_tree(params),
            jax.device_put(jnp.asarray(ids), data),
            jax.device_put(jnp.asarray(labels), data),
            "hybrid train: fleet dp=2 x mp=2")
    require_mosaic(text, "hybrid train: step program",
                   also=("causal_flash", "all-reduce"))
    worst = max(abs(a - b) / abs(b) for a, b in zip(hybrid, one_chip))
    print(f"hybrid train: {len(split)} parameters split over mp; largest "
          f"relative loss difference to one chip {worst:.5f} (tolerance "
          f"{LOSS_REL_TOL})")
    check(worst <= LOSS_REL_TOL,
          f"hybrid train: loss trajectory off the one-chip one by {worst}")
    memory_line("after the hybrid train phase", devices)


def megatron_spec(name, P):
    """Megatron TP over 'mp', the GSPMD way, for GPT's parameter names:
    column-parallel weights split on the out dim, row-parallel on the in
    dim, the embedding on the vocabulary."""
    if "qkv_proj.weight" in name or "mlp.fc.weight" in name:
        return P(None, "mp")
    if "qkv_proj.bias" in name or "mlp.fc.bias" in name:
        return P("mp")
    if "out_proj.weight" in name or "mlp.proj.weight" in name:
        return P("mp", None)
    if "wte.weight" in name:
        return P("mp", None)
    return P()


# ------------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded paths (tp=4 serving, dp=2 x "
                         "mp=2 training) and what they are compared "
                         "with, and no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and batch")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    t0 = time.perf_counter()
    print(f"setup: {len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform}), seed {args.seed}")
    setup_facts()
    phases = ((serve_phase, train_phase) if args.chips == 1
              else (sharded_serve_phase, hybrid_train_phase))
    for phase in phases:
        phase(args.seed, devices)
        gc.collect()  # the phase's model and pool leave the chip
    print(f"setup: total wall {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
