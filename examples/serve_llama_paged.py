"""LLaMA serving through the continuous-batching engine
(reference capability: analysis_predictor serving loop +
fused_multi_transformer_op.cu decode; TPU stack: inference.Engine over the
paged KV cache — compiled decode chunks, block-table page pool,
paddle_tpu/ops/pallas/paged_attention.py).

Demonstrates what the reference's contiguous cache can't give you:
sequences of different lengths share one page pool, a finished request's
pages recycle into the next admission mid-flight (no head-of-line
blocking), and tokens stream back per chunk.

Run (tiny, CPU ok):
    env JAX_PLATFORMS=cpu python examples/serve_llama_paged.py --tiny
"""
import argparse
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

# --tp N / --ep M on a CPU host needs N*M virtual devices BEFORE jax
# initializes (same trick as tests/conftest.py); a real slice has real chips
if ("--tp" in _sys.argv or "--ep" in _sys.argv) and \
        "xla_force_host_platform_device_count" not in \
        _os.environ.get("XLA_FLAGS", ""):
    def _degree(flag):
        if flag not in _sys.argv:
            return 1
        try:
            return max(1, int(_sys.argv[_sys.argv.index(flag) + 1]))
        except (ValueError, IndexError):
            return 8
    _n = max(2, _degree("--tp") * _degree("--ep"))
    _os.environ["XLA_FLAGS"] = (_os.environ.get("XLA_FLAGS", "")
                                + f" --xla_force_host_platform_device_count={_n}").strip()

import numpy as np

import paddle_tpu as paddle


def run_cluster_smoke(model, cfg, args):
    """``--pools prefill=K,decode=M`` smoke (ISSUE 20): an in-process
    prefill/decode fleet behind one Router — prompts prefill on the
    prefill pool, their KV ships to a decode replica (digest-verified,
    recompute on any failure), shared-prefix streams converge onto warm
    decode replicas. Prints the handoff/fallback counters the chaos
    suite gates on."""
    import time

    import jax.numpy as jnp

    from paddle_tpu.observability import metric_total
    from paddle_tpu.serving import (InProcReplica, Router,
                                    ServingFrontend, parse_pools)

    pools = parse_pools(args.pools)
    n = sum(pools.values())

    def factory():
        from paddle_tpu.inference.engine import Engine

        eng = Engine(model, max_slots=4, num_pages=96, page_size=16,
                     chunk_size=8, dtype=jnp.float32, prefix_cache=True)
        return ServingFrontend(eng)

    reps = [InProcReplica(factory, name=f"pool-r{i}", index=i)
            for i in range(n)]
    router = Router(reps, heartbeat_s=0.05, stall_s=None,
                    pools=pools, fault_plan=args.fault_inject)
    router.start()
    try:
        deadline = time.perf_counter() + 60.0
        while router.cluster._page_size is None \
                and time.perf_counter() < deadline:
            time.sleep(0.05)  # a sweep feeds geometry into the view
        rng = np.random.default_rng(0)
        shared = rng.integers(0, cfg.vocab_size, (32,))
        tickets = []
        for i in range(6):
            prompt = np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, (8,))])
            tickets.append(router.submit(prompt, 12,
                                         tenant=f"t{i % 2}"))
        for t in tickets:
            t.result(timeout=300.0)
        ok = all(t.failure_reason is None for t in tickets)
        roles = {r.name: router.cluster.role_of(r) for r in reps}
        print(f"cluster smoke: pools={pools} roles={roles}")
        print(f"  streams: {len(tickets)} submitted, "
              f"{sum(1 for t in tickets if t.done)} done, ok={ok}")
        print("  handoffs=%d fallbacks=%d shipped_kb=%.1f" % (
            metric_total("paddle_tpu_cluster_handoffs_total"),
            metric_total("paddle_tpu_cluster_fallbacks_total"),
            metric_total("paddle_tpu_cluster_handoff_bytes_total")
            / 1024.0))
        if not ok:
            raise SystemExit("cluster smoke: stream failures")
    finally:
        router.shutdown()


def run_api_server(eng, args):
    """Serve the OpenAI-compatible streaming API (ISSUE 12) until
    SIGTERM/SIGINT, then drain gracefully: admissions stop (new
    requests get 429/503), in-flight streams finish inside
    ``--drain-grace``, stragglers are cancelled through the engine's
    taxonomy path so every stream terminates cleanly."""
    import asyncio

    from paddle_tpu.serving import ServingFrontend, parse_tenant_weights
    from paddle_tpu.serving.server import ApiServer

    frontend = ServingFrontend(
        eng, tenant_weights=parse_tenant_weights(args.tenant_weights),
        stream_stall_s=(args.stream_stall_ms / 1e3
                        if args.stream_stall_ms is not None else None))
    server = ApiServer(frontend, port=args.api_port,
                       model_name="llama-paged",
                       grace_s=args.drain_grace)

    async def serve():
        await server.start()
        print(f"api: http://127.0.0.1:{server.port}/v1/completions "
              f"(multi_step={args.multi_step}, "
              f"tenants={args.tenant_weights or 'default'})", flush=True)
        smoke = None
        if args.api_smoke:
            loop = asyncio.get_running_loop()
            smoke = loop.run_in_executor(None, _api_smoke, server)
        await server.serve_until_signal()
        if smoke is not None:
            ok = await smoke
            print("SMOKE " + ("OK" if ok else "FAILED"), flush=True)
            if not ok:
                raise SystemExit(1)

    asyncio.run(serve())


def _api_smoke(server):
    """HTTP self-test run in an executor thread (make serve-smoke):
    streaming identity, unary, chat, backpressure shape, then SIGTERM
    mid-stream to exercise the graceful drain."""
    import json
    import os
    import signal
    import threading
    import urllib.request

    base = f"http://127.0.0.1:{server.port}"

    def post(path, payload, stream=False):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json",
                     "X-Tenant": "interactive"})
        if not stream:
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())
        toks = []
        with urllib.request.urlopen(req, timeout=120) as r:
            for line in r:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line[6:] == "[DONE]":
                    break
                toks.extend(json.loads(line[6:])["choices"][0]
                            ["token_ids"])
        return toks

    try:
        prompt = list(range(1, 21))
        unary = post("/v1/completions",
                     {"prompt": prompt, "max_tokens": 8})
        toks_u = unary["choices"][0]["token_ids"]
        toks_s = post("/v1/completions",
                      {"prompt": prompt, "max_tokens": 8,
                       "stream": True}, stream=True)
        assert toks_s == toks_u and len(toks_u) == 8, (toks_u, toks_s)
        chat = post("/v1/chat/completions",
                    {"messages": [{"role": "user", "content": "hi"}],
                     "max_tokens": 4})
        assert len(chat["choices"][0]["token_ids"]) == 4
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
        print(f"smoke: unary == streamed == {toks_u}", flush=True)

        # SIGTERM mid-stream: the drain must finish this stream cleanly
        got = {}

        def long_stream():
            got["toks"] = post("/v1/completions",
                               {"prompt": prompt, "max_tokens": 24,
                                "stream": True}, stream=True)

        t = threading.Thread(target=long_stream)
        t.start()
        import time

        time.sleep(0.3)  # let the stream start
        os.kill(os.getpid(), signal.SIGTERM)
        t.join(timeout=60)
        assert "toks" in got and got["toks"], "drain lost the stream"
        print(f"smoke: drained stream delivered {len(got['toks'])} "
              "tokens", flush=True)
        return True
    except Exception as e:  # smoke harness: report, flag failure
        print(f"smoke error: {type(e).__name__}: {e}", flush=True)
        try:
            server.request_stop()
        except Exception:
            pass
        return False


def _trace_report(args):
    """End-of-run tracing surface (--trace on/flight-only): per-run
    TTFT decomposition stats line (queue/placement/prefill/promote
    fractions from the component histogram) and the optional ring
    snapshot dump for tools/trace_tpu.py."""
    import json

    from paddle_tpu.observability.tracing import (
        TRACER, ttft_decomposition_summary)

    if not TRACER.enabled:
        return
    d = ttft_decomposition_summary()
    if d.get("n"):
        mean_ms = 1e3 * d["ttft_sum_s"] / d["n"]
        print("ttft decomposition: "
              f"queue {100 * d.get('queue_wait_frac', 0.0):.1f}% | "
              f"placement {100 * d.get('placement_frac', 0.0):.1f}% | "
              f"prefill {100 * d.get('prefill_frac', 0.0):.1f}% | "
              f"promote {100 * d.get('promote_wait_frac', 0.0):.1f}% "
              f"(n={int(d['n'])}, mean ttft {mean_ms:.1f} ms)",
              flush=True)
    if args.trace_dump:
        records = TRACER.snapshot()
        with open(args.trace_dump, "w", encoding="utf-8") as f:
            json.dump({"mode": args.trace, "process": "serve",
                       "records": records}, f)
        print(f"trace: {len(records)} records -> {args.trace_dump} "
              "(export: python tools/trace_tpu.py --from-file "
              f"{args.trace_dump} --out trace.json)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--int8-cache", action="store_true",
                    help="store KV pages int8 with per-row scales")
    ap.add_argument("--weight-quant", choices=["none", "int8", "int4"],
                    default="none",
                    help="weight-only-quantize the Linears before "
                         "serving; the GEMM backend (fused Pallas "
                         "dequant-in-kernel on TPU, XLA convert-fusion "
                         "on CPU) follows FLAGS_weight_only_quant_backend"
                         " — no engine changes needed")
    ap.add_argument("--spec", choices=["off", "ngram", "draft"],
                    default="off",
                    help="speculative decoding (ISSUE 5): 'ngram' drafts "
                         "by prompt lookup (model-free), 'draft' drafts "
                         "with a 1-layer llama sharing the vocab; greedy "
                         "output is identical to --spec off, sampled "
                         "output stays distribution-exact via rejection "
                         "sampling")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per verify step (the verify "
                         "block scores k+1 positions in one forward); "
                         "per-request depth adapts to an acceptance EMA")
    ap.add_argument("--prefix-cache", choices=["on", "off"], default="on",
                    help="refcounted copy-on-write prefix caching "
                         "(ISSUE 8): admissions splice cached "
                         "block-aligned prompt prefixes into their page "
                         "table and prefill only the uncached suffix; "
                         "output tokens are identical either way")
    ap.add_argument("--kv-host-pages", type=int, default=0,
                    help="host-DRAM KV tier size in pages (ISSUE 15; "
                         "needs --prefix-cache on): idle cached pages "
                         "spill to a host slab asynchronously instead "
                         "of being evicted, and a later hash-chain hit "
                         "promotes them back checksum-verified — "
                         "effective prefix-cache capacity grows to the "
                         "slab for roughly one page copy per re-hit "
                         "page. 0 (default) = tier off: no worker "
                         "thread, byte-identical scheduling, existing "
                         "behavior unchanged. Output tokens are "
                         "identical either way")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel degree (ISSUE 11): shard the "
                         "engine's compiled programs over a tp-way mesh "
                         "via shard_map — weights column/row-sharded, "
                         "the paged KV pool sharded by KV head, the "
                         "host scheduler unchanged. Output tokens are "
                         "identical to --tp 1. On CPU this uses the "
                         "virtual-device mesh (the harness forces 8); "
                         "on a TPU slice it shards over real chips. "
                         "tp must divide num_heads/num_kv_heads")
    ap.add_argument("--ep", type=int, default=None,
                    help="expert-parallel degree (ISSUE 17, implies "
                         "--moe): shard the MoE expert weights over an "
                         "ep-way mesh axis — routing stays replicated "
                         "(every shard routes all tokens, so output "
                         "tokens are identical to --ep 1), only the "
                         "expert FFN is distributed: one all_to_all "
                         "dispatch + one all_gather combine per MoE "
                         "layer. Composes with --tp (devices reshape to "
                         "tp x ep). ep must divide num_experts")
    ap.add_argument("--moe", action="store_true",
                    help="serve the MoE twin of the model (ISSUE 17): "
                         "8 experts, top-2 routing, grouped-expert "
                         "Pallas FFN, capacity-factor token dropping")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="MoE per-expert token budget factor (ISSUE "
                         "17): each expert accepts at most C = ceil(cf "
                         "* top_k * T / E) tokens per dispatch; "
                         "overflow pairs drop (combine renormalizes "
                         "over the survivors) — overload degrades "
                         "quality, never OOMs or recompiles. Default "
                         "from the model config (1.25)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode role separation (ISSUE 11, "
                         "needs --prefill-chunk): mid-prompt slots "
                         "stream chunks through the prefill-role "
                         "program while decoding slots ride deep "
                         "chains in the same step — long prompts never "
                         "pin the decode batch to one token per round "
                         "trip; output tokens are identical either way")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (ISSUE 9): stream prompts "
                         "into the cache this many tokens per mixed "
                         "chunk+decode step (the fused slab-attention "
                         "program) instead of one bucketed prefill "
                         "dispatch — long prompts stop stalling the "
                         "decode batch and the cold-start compile "
                         "surface collapses to one program; output "
                         "tokens are identical either way")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request TTL (ISSUE 6): a request that "
                         "hasn't finished this many ms after submission "
                         "fails with reason 'deadline' — queued or "
                         "mid-decode — freeing its slot and pages")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded wait queue: add_request raises "
                         "QueueFull (backpressure) once this many "
                         "requests are waiting for a slot")
    ap.add_argument("--integrity", choices=["off", "audit", "strict"],
                    default="off",
                    help="online silent-data-corruption defense "
                         "(ISSUE 14): 'audit' arms load-time weight "
                         "digests with periodic shard-slice audits and "
                         "per-page KV checksums verified at every "
                         "prefix-cache splice; 'strict' adds the "
                         "shadow-recompute sentinel (one greedy row "
                         "re-scored through the contiguous twin every "
                         "N steps) and a tighter audit period. "
                         "Detection is containment, not crash: KV "
                         "corruption costs a cache miss, a weight-"
                         "audit failure quarantines the replica "
                         "(/readyz -> 503) so a router migrates and "
                         "restarts it")
    ap.add_argument("--fault-inject", default=None,
                    help="deterministic fault-injection plan "
                         "(paddle_tpu.testing.faultinject grammar, e.g. "
                         "'nan-logits:rid=2,times=1'); defaults to "
                         "FLAGS_fault_inject / PADDLE_TPU_FAULT_INJECT. "
                         "Faulted requests end FAILED with a taxonomy "
                         "reason; the engine never dies")
    ap.add_argument("--api-port", type=int, default=None,
                    help="serve the OpenAI-compatible streaming HTTP "
                         "API (ISSUE 12) on this port instead of the "
                         "local demo; 0 picks an ephemeral port, "
                         "printed as 'api: http://...'. SSE "
                         "/v1/completions + /v1/chat/completions, "
                         "X-Tenant header keys admission/fairness, "
                         "SIGTERM drains in-flight streams gracefully. "
                         "Smoke it:  curl -N -H 'Content-Type: "
                         "application/json' -d '{\"prompt\": [1,2,3], "
                         "\"max_tokens\": 8, \"stream\": true}' "
                         "http://localhost:PORT/v1/completions")
    ap.add_argument("--multi-step", type=int, default=1,
                    help="multi-step scheduling (ISSUE 12): batch up "
                         "to N decode iterations behind one host round "
                         "trip in pure-decode phases; token streams "
                         "are identical for every N")
    ap.add_argument("--tenant-weights", default=None,
                    help="weighted fairness map 'name=weight,...' "
                         "(e.g. 'interactive=4,batch=1'): tenants get "
                         "weight-proportional slot shares and queue "
                         "service, so a batch flood cannot starve "
                         "interactive traffic; unlisted tenants share "
                         "the default weight")
    ap.add_argument("--stream-stall-ms", type=float, default=None,
                    help="slow-client watchdog (ISSUE 13): a streaming "
                         "consumer that stops draining chunks for this "
                         "many ms (or backlogs past the per-stream "
                         "buffer bound) is cancelled and its slot/"
                         "pages freed — an abandoned-but-connected "
                         "client cannot pin a slot. Off by default")
    ap.add_argument("--drain-grace", type=float, default=30.0,
                    help="SIGTERM drain budget (seconds): in-flight "
                         "streams get this long to finish before being "
                         "cancelled cleanly")
    ap.add_argument("--pools", default=None, metavar="SPEC",
                    help="cluster-serving smoke (ISSUE 20): run SPEC "
                         "(e.g. prefill=1,decode=2) in-process replicas "
                         "behind one Router — prefill pool + KV handoff "
                         "+ cache-aware decode placement — then print "
                         "the handoff counters and exit")
    ap.add_argument("--api-smoke", action="store_true",
                    help="self-smoke (make serve-smoke): start the API "
                         "server, run streaming + unary + chat + 429 "
                         "checks against it over HTTP, exercise the "
                         "SIGTERM drain mid-stream, exit 0 on success")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text exposition on this port "
                         "(/metrics); 0 picks an ephemeral port, printed "
                         "at startup")
    ap.add_argument("--metrics-linger", type=float, default=0.0,
                    help="keep the /metrics endpoint up this many "
                         "seconds after serving completes (scrape tests; "
                         "a real deployment's process simply stays up)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append one JSONL metrics snapshot here after "
                         "the run")
    ap.add_argument("--trace", choices=["off", "on", "flight-only"],
                    default="off",
                    help="request tracing (ISSUE 18): 'on' records "
                         "spans/events into the in-memory ring and "
                         "serves live snapshots at /debug/trace (export "
                         "with tools/trace_tpu.py); 'flight-only' "
                         "records the ring for crash postmortems but "
                         "refuses live scrapes. Off by default — the "
                         "disabled path is a single attribute check")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="write the final trace-ring snapshot here as "
                         "JSON (the /debug/trace body shape; feed to "
                         "tools/trace_tpu.py --from-file). Needs "
                         "--trace on/flight-only")
    args = ap.parse_args()

    import jax.numpy as jnp

    from paddle_tpu.inference.engine import Engine
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    server = None
    if args.metrics_port is not None:
        from paddle_tpu.framework.compile_cache import ensure_compile_metrics
        from paddle_tpu.observability import start_metrics_server

        ensure_compile_metrics()  # full catalogue visible from scrape #1
        server = start_metrics_server(args.metrics_port)
        # the scrape contract: TTFT/TPOT histograms, page-pool gauges,
        # preemption/retrace counters — see README "Observability"
        print(f"metrics: http://localhost:{server.port}/metrics",
              flush=True)

    if args.trace != "off":
        from paddle_tpu.observability.tracing import configure_tracing

        configure_tracing(args.trace, process="serve")

    paddle.seed(0)
    moe = args.moe or (args.ep or 0) > 1 or args.capacity_factor is not None
    if moe:
        from paddle_tpu.models.llama import tiny_moe_llama_config

        # expert FF width = intermediate/top_k keeps active params per
        # token equal to the dense config it replaces
        cfg = tiny_moe_llama_config() if args.tiny else \
            tiny_moe_llama_config(
                hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=4,
                intermediate_size=512, max_position=512,
                moe_intermediate_size=256)
    else:
        cfg = tiny_llama_config() if args.tiny else tiny_llama_config(
            hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=4,
            intermediate_size=512, max_position=512)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if args.weight_quant != "none":
        from paddle_tpu.nn.quant import quant_backend, quantize_for_decode

        _, swapped = quantize_for_decode(
            model, algo=f"weight_only_{args.weight_quant}")
        print(f"weight-only {args.weight_quant}: {swapped} Linears "
              f"swapped, GEMM backend={quant_backend()}")

    if args.pools is not None:
        run_cluster_smoke(model, cfg, args)
        _trace_report(args)
        if server is not None:
            server.close()
        return

    draft_model = None
    if args.spec == "draft":
        # a deliberately tiny draft: 1 layer, narrow — correctness never
        # depends on its quality (greedy acceptance is token-exact
        # against the TARGET), only the accepted tokens/step does
        dcfg = tiny_llama_config(
            num_layers=1, hidden_size=32, num_heads=2, num_kv_heads=2,
            intermediate_size=64, vocab_size=cfg.vocab_size,
            max_position=cfg.max_position)
        draft_model = LlamaForCausalLM(dcfg)
        draft_model.eval()

    eng = Engine(model, max_slots=4, num_pages=96, page_size=16,
                 chunk_size=8, dtype=jnp.float32,
                 quantized_cache=args.int8_cache,
                 spec=None if args.spec == "off" else args.spec,
                 spec_k=args.spec_k, draft_model=draft_model,
                 deadline_s=(args.deadline_ms / 1e3
                             if args.deadline_ms is not None else None),
                 max_queue=args.max_queue,
                 fault_plan=args.fault_inject,
                 prefix_cache=args.prefix_cache == "on",
                 kv_host_pages=args.kv_host_pages,
                 prefill_chunk=args.prefill_chunk,
                 tp=args.tp, ep=args.ep,
                 capacity_factor=args.capacity_factor,
                 disaggregate=args.disaggregate,
                 multi_step=args.multi_step,
                 integrity=None if args.integrity == "off"
                 else args.integrity)
    if eng.runner.sharded:
        print(f"sharded: tp={eng.runner.tp} ep={eng.runner.ep} over "
              f"{[str(d) for d in eng.runner.mesh.devices.flat]}")

    if args.api_port is not None:
        run_api_server(eng, args)
        _trace_report(args)
        if server is not None:
            server.close()
        return

    rng = np.random.default_rng(0)

    # mixed-length requests, more requests than slots: admission interleaves
    # with decode, finished slots recycle their pages for queued requests
    streams = {}
    reqs = []
    for i, (plen, new) in enumerate([(20, 12), (33, 6), (8, 24), (27, 10),
                                     (15, 16), (41, 8)]):
        prompt = rng.integers(0, cfg.vocab_size, (plen,))
        streams[i] = []
        reqs.append(eng.add_request(
            prompt, new, on_token=lambda ts, i=i: streams[i].extend(ts)))

    free0 = len(eng._free_pages)
    rounds = 0
    while eng.step():
        rounds += 1
        in_use = free0 - len(eng._free_pages)
        print(f"round {rounds}: active={len(eng._active)} "
              f"queued={len(eng._queue)} pages_in_use={in_use}")

    for i, r in enumerate(reqs):
        assert r.done and streams[i] == r.tokens
        if r.failed:
            # fault tolerance (ISSUE 6): a failed request is terminal
            # with an attributable taxonomy reason — the batch lived on
            print(f"request {r.rid}: prompt {r.prompt.size:>2} -> "
                  f"FAILED ({r.failure_reason}) after "
                  f"{len(r.tokens)} tokens")
            continue
        print(f"request {r.rid}: prompt {r.prompt.size:>2} -> "
              f"{len(r.tokens)} tokens (streamed {len(streams[i])})")
    # cached-idle pages are resident on purpose (refcount 0, LRU-evictable
    # the moment an allocation needs them) — they count as recycled
    resident = eng._pcache.n_pages if eng._pcache is not None else 0
    print(f"pool fully recycled: {len(eng._free_pages)}+{resident} cached "
          f"of {free0} (int8_cache={args.int8_cache})")
    if eng._pcache is not None:
        pc = eng._pcache
        print(f"prefix cache: {pc.hits} hits / {pc.misses} misses, "
              f"{pc.n_pages} pages resident, {pc.evictions} evictions")
    if eng.kv_tier is not None:
        t = eng.kv_tier
        print(f"kv tier: {t.demotions} demotions / {t.promotions} "
              f"promotions, {t.hits} tier hits, {t.drops} drops, "
              f"{t.host_pages - len(t._free_hslots)}/{t.host_pages} "
              "host pages resident")
        eng._cache.shutdown_tier()
    ms = eng.moe_stats()
    if ms:
        print(f"moe[ep={eng.runner.ep}] {cfg.num_experts} experts "
              f"top-{cfg.moe_top_k}: "
              f"{int(ms['pairs_dropped'])} dropped / "
              f"{int(ms['pairs_kept']) + int(ms['pairs_dropped'])} routed "
              f"pairs (drop_frac {ms['drop_frac']:.3f}), "
              f"load imbalance {ms['load_imbalance']:.2f}x, "
              f"router entropy {ms['router_entropy']:.2f} nats")
    if eng._spec is not None:
        s = eng._spec.stats()
        print(f"spec[{s['drafter']}] k={s['k']}: "
              f"{s['accept_per_step']:.2f} tokens/verify-step, "
              f"accept rate {s['accept_rate']:.2f}, "
              f"{s['spec_ms_per_token']:.2f} ms/token")

    _trace_report(args)
    if args.metrics_jsonl:
        from paddle_tpu.observability import write_jsonl_snapshot

        write_jsonl_snapshot(args.metrics_jsonl,
                             extra={"source": "serve_llama_paged"})
        print(f"metrics snapshot appended to {args.metrics_jsonl}")
    if server is not None:
        if args.metrics_linger > 0:
            import time

            print(f"metrics: lingering {args.metrics_linger}s for "
                  "scrapes", flush=True)
            time.sleep(args.metrics_linger)
        server.close()


if __name__ == "__main__":
    main()
