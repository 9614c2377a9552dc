"""Driver ``serve_open_loop``: open-loop traffic into ``ServingFrontend``.

The window drives ``ServingFrontend.submit(..., on_chunk=...)`` in-process on
a frontend over the configuration's ``Engine``; no HTTP server, no client
thread per stream. Requests are sent on wall-clock deadlines whatever has
completed, each timed from the moment it was DUE; how late the generator ran
is printed in every run. The loop and the warm-up are copies of
``paddle_tpu/serving/loadgen.py`` (``run_open_loop``, ``_precompile``) with
latency from the due time, lateness reported and the mix's own lengths.

The comparison that decides ``correct`` runs once the window has closed, the
peak memory is read and the engine is freed: over a sample of the requests
the window finished (the longest context among them), the plain reference
runs once over each prompt with its served tokens, and the widest gap by
which a served token's reference logit lies below the reference's best is
held to the cell's limit. Greedy tokens only.
"""
import gc
import threading
import time

import numpy as np

from ..harness import device, loader, trace_window, traffic as gen

class Record:
    """One request: what was asked, when it was due and sent, what came."""

    def __init__(self, index, due, prompt, out_len):
        self.index, self.due, self.prompt, self.out_len = (
            index, due, prompt, out_len)
        self.sent = None      # wall time of submit
        self.refused = None   # the exception's name if submit raised
        self.chunks = []      # (wall time, tokens in the chunk)
        self.t_done = None
        self.ticket = None

    def on_chunk(self, toks):  # engine thread: keep it short
        now = time.perf_counter()
        if toks is None:
            self.t_done = now
        else:
            self.chunks.append((now, len(toks)))

    @property
    def finished(self):
        return (self.t_done is not None and self.ticket is not None
                and not self.ticket.failure_reason
                and len(self.ticket.tokens) == self.out_len)


def build_engine(config, seed):
    import jax.numpy as jnp

    from paddle_tpu.inference.engine import Engine

    model = loader.find("builders", config["builder"]).build(
        config, seed, train=False)
    e = config["engine"]
    return Engine(model, max_slots=e["max_slots"], num_pages=e["num_pages"],
                  page_size=e["page_size"], dtype=jnp.dtype(config["dtype"]))


def precompile(eng, seq_buckets):
    """Compile (or load) every decode program (pow2 rows x pow2 chain depth)
    and the prefill bucket of every prompt length the mix can send, each run
    once on the trash page. Copy of ``loadgen._precompile``; reaches into
    ``Engine`` privates until the program has a public warm-up."""
    import jax
    import jax.numpy as jnp

    nb_full = 1 << (eng.max_slots - 1).bit_length()
    nbs = [1 << i for i in range(nb_full.bit_length())]
    ks = [1 << i for i in range(eng.max_chain.bit_length())
          if (1 << i) <= eng.max_chain]
    took = []

    def rows_on_the_trash_page(nb):
        return (jnp.zeros((nb, eng.max_pages_per_seq), jnp.int32),
                jnp.zeros((nb,), jnp.int32), jnp.zeros((nb,), jnp.float32),
                jnp.zeros((nb, 2), jnp.uint32))

    for nb in nbs:
        tables, zi, temps, keys = rows_on_the_trash_page(nb)
        for k in ks:
            t = time.perf_counter()
            decode = eng._get_decode(nb, k, False)
            _, pages, _, _, bad, *_ = decode(
                eng._params, eng._pages_flat(), tables, zi, zi, temps, keys)
            eng._set_pages(pages)
            jax.device_get(bad)
            took.append(f"d{nb}x{k}:{time.perf_counter() - t:.1f}")
    tables, zi, temps, keys = rows_on_the_trash_page(nb_full)
    for seq in seq_buckets:
        t = time.perf_counter()
        prefill = eng._get_prefill((nb_full, seq), False, False)
        _, _, bad, pages, *_ = prefill(
            eng._params, eng._pages_flat(),
            jnp.zeros((nb_full, seq), jnp.int32),
            jnp.ones((nb_full,), jnp.int32), tables, zi, temps, keys)
        eng._set_pages(pages)
        jax.device_get(bad)
        took.append(f"p{seq}:{time.perf_counter() - t:.1f}")
    print("setup: seconds a program (decode rows x chain, prefill bucket): "
          + " ".join(took), flush=True)
    return len(took)


def prefill_buckets(mix):
    lo = 1 << (mix["prompt_len"]["min"] - 1).bit_length()
    hi = 1 << (mix["prompt_len"]["max"] - 1).bit_length()
    return [1 << i for i in range(lo.bit_length() - 1, hi.bit_length())]


def make_records(mix, config, seed, seconds, stream):
    return [Record(i, due, gen.prompt_ids(seed, stream, i, p,
                                          config["vocab_size"]), o)
            for i, (due, p, o) in enumerate(
                gen.schedule(mix, seconds, seed, stream))]


def open_loop(frontend, records, mix, t0):
    """Submit every record at ``t0 + due`` (open loop: no self-throttling);
    returns when the last one is sent."""
    for r in records:
        delay = t0 + r.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        with trace_window.span("bench.submit"):
            r.sent = time.perf_counter()
            try:
                r.ticket = frontend.submit(
                    r.prompt, r.out_len,
                    temperature=mix.get("temperature", 0.0),
                    seed=r.index, on_chunk=r.on_chunk)
            except Exception as e:  # noqa: BLE001 - a refusal is a result
                r.refused = type(e).__name__


def wait_all(records, deadline):
    """Wait until every sent record is done or ``deadline`` has passed."""
    for r in records:
        while (r.ticket is not None and r.t_done is None
               and time.perf_counter() < deadline):
            time.sleep(0.01)


def latencies(records, t0, t_end):
    """(ttft seconds, seconds per output token) per record, timed from when
    the request was due. A refused, failed or unfinished request ranks above
    every finished one: it is valued at what it had waited by ``t_end``, or
    at the slowest finished request's value where that is more."""
    ttft, per_tok, unfinished = [], [], []
    for r in records:
        due = t0 + r.due
        if r.finished:
            ttft.append(r.chunks[0][0] - due)
            per_tok.append((r.chunks[-1][0] - due) / r.out_len)
        else:
            unfinished.append((t_end - due, (t_end - due) / r.out_len))
    top_ttft, top_tok = max(ttft, default=0.0), max(per_tok, default=0.0)
    for waited, waited_per_tok in unfinished:
        ttft.append(max(top_ttft, waited))
        per_tok.append(max(top_tok, waited_per_tok))
    return ttft, per_tok


def pick_sample(records, seed, size):
    done = [r for r in records if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.out_len)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 77])
    pick = rng.choice(len(rest), size=min(size - 1, len(rest)),
                      replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(config, seed, sample, cell, wrong_length):
    ref_mod = loader.find("reference", config["reference"])
    t = time.perf_counter()
    pairs = [(r.prompt, np.asarray(r.ticket.tokens, np.int32))
             for r in sample]
    gaps = ref_mod.served_gaps(config, seed, pairs) if pairs else []
    n = sum(len(g) for g in gaps)
    widest = max((float(g.max()) for g in gaps), default=float("nan"))
    print(f"reference: {len(pairs)} requests, {n} served tokens compared "
          f"in {time.perf_counter() - t:.1f} s; longest context "
          f"{max((len(p) + len(s) for p, s in pairs), default=0)}; widest "
          f"gap {widest:.5f}, mean gap "
          f"{np.mean(np.concatenate(gaps)) if n else float('nan'):.6f}, "
          f"positions with a gap {sum(int((g > 0).sum()) for g in gaps)}",
          flush=True)
    return [("served_logit_gap", widest, cell["limits"]["served_logit_gap"]),
            ("compared_tokens_short",
             float(max(0, cell["min_compared_tokens"] - n)), 0.0),
            ("wrong_length_requests", float(wrong_length), 0.0)]


def run(ctx):
    out, sample = serve(ctx)
    out["checks"] = compare(ctx["config"], ctx["seed"], sample, ctx["cell"],
                            out.pop("wrong_length"))
    return out


def control(ctx):
    """The control and the fault of this kind of cell, read on the sample
    of a (short) window at the cell's own load: the program's gap, the gap
    of the token that the reference in fp8 puts first over the same prompts
    and tokens, and the program's gap with one served token altered."""
    out, sample = serve(ctx)
    cfg, seed = ctx["config"], ctx["seed"]
    ref_mod = loader.find("reference", cfg["reference"])
    pairs = [(r.prompt, np.asarray(r.ticket.tokens, np.int32))
             for r in sample]
    widest = lambda gaps: max(float(g.max()) for g in gaps)
    readings = {"program": widest(ref_mod.served_gaps(cfg, seed, pairs)),
                "control_fp8": widest(ref_mod.served_gaps(
                    cfg, seed, pairs, control="fp8"))}
    toks = pairs[-1][1].copy()
    toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % cfg["vocab_size"]
    readings["fault_token_altered"] = widest(ref_mod.served_gaps(
        cfg, seed, [(pairs[-1][0], toks)]))
    readings["compared_tokens"] = sum(len(t) for _, t in pairs)
    return readings


def set_up(ctx):
    """Build the engine, warm every program the mix can reach, start the
    frontend and serve a few seconds of the cell's own traffic from another
    stream, so that what the engine measures of itself has landed."""
    from paddle_tpu.serving import ServingFrontend

    config, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    t = time.perf_counter()
    eng = build_engine(config, seed)
    device.memory_line("after the model and the page pool are built",
                       ctx["devices"])
    print(f"setup: model and engine built in {time.perf_counter() - t:.1f}"
          " s", flush=True)
    t = time.perf_counter()
    n = precompile(eng, prefill_buckets(mix))
    print(f"setup: {n} programs compiled or loaded and run once in "
          f"{time.perf_counter() - t:.1f} s", flush=True)
    fe = ServingFrontend(eng).start()
    t = time.perf_counter()
    warm = make_records(mix, config, seed, mix["warm_seconds"], stream=1)
    open_loop(fe, warm, mix, t)
    wait_all(warm, time.perf_counter() + mix["drain_seconds"])
    print(f"setup: warm-up traffic, {sum(r.finished for r in warm)} of "
          f"{len(warm)} requests finished in {time.perf_counter() - t:.1f} "
          f"s; engine dispatch ratio {getattr(eng, '_dispatch_ratio', None)}",
          flush=True)
    return eng, fe


def window(ctx, fe, mix, seconds, tracer=None):
    """One measured window of ``mix`` on a frontend that is set up, and the
    drain after it. Returns the records with the window's clock marks and
    the registry's snapshots at its start and end."""
    from paddle_tpu.observability import REGISTRY

    records = make_records(mix, ctx["config"], ctx["seed"], seconds,
                           stream=0)
    before = REGISTRY.snapshot()
    setup_s = time.perf_counter() - ctx["t_start"]
    print(f"setup: {setup_s:.3f} s from process start to window start",
          flush=True)
    t0 = time.perf_counter()
    if tracer:
        # a slice from the middle of the window, not the ramp from an empty
        # engine; a shorter window is traced over its last seconds
        after = min(float(ctx["cell"]["trace_after_seconds"]),
                    max(0.0, seconds - tracer.seconds))

        def traced_slice():
            time.sleep(after)
            tracer.start()
            time.sleep(tracer.seconds)
            tracer.stop()

        slicer = threading.Thread(target=traced_slice)
        slicer.start()
    open_loop(fe, records, mix, t0)
    t_close = t0 + seconds
    time.sleep(max(0.0, t_close - time.perf_counter()))
    after = REGISTRY.snapshot()
    wait_all(records, t_close + mix["drain_seconds"])
    t_end = time.perf_counter()
    if tracer:
        slicer.join()
    return {"records": records, "t0": t0, "t_close": t_close,
            "t_end": t_end, "registry": (before, after), "setup_s": setup_s}


def summarize(w, mix, seconds):
    """What a window's records say: the end-to-end numbers, the counts."""
    records, t0, t_close, t_end = (w["records"], w["t0"], w["t_close"],
                                   w["t_end"])
    lateness = [r.sent - (t0 + r.due) for r in records
                if r.sent is not None]
    tol = mix["late_tolerance_ms"] / 1e3
    print(f"generator: {len(records)} requests due, lateness max "
          f"{max(lateness, default=0) * 1e3:.3f} ms, mean "
          f"{np.mean(lateness) * 1e3 if lateness else 0:.3f} ms"
          + (f"; LATE beyond the {tol * 1e3:.0f} ms it tolerates"
             if max(lateness, default=0) > tol else ""), flush=True)
    failed = [r for r in records if not r.finished]
    out_tokens = sum(n for r in records for tc, n in r.chunks
                     if tc <= t_close)
    in_window = [r for r in records if r.finished and r.t_done <= t_close]
    ttft, per_tok = latencies(records, t0, t_end)
    print(f"window: {len(records)} due, {len(in_window)} finished inside, "
          f"{len(records) - len(failed)} finished by the end of the drain "
          f"({t_end - t_close:.1f} s after the close), {len(failed)} failed "
          f"(refused {sum(1 for r in records if r.refused)}); "
          f"{out_tokens} output tokens inside; ttft p50 "
          f"{gen.nearest_rank(ttft, 50) * 1e3:.1f} ms", flush=True)
    return {
        "attempted": len(records), "failed": len(failed),
        "wrong_length": sum(
            1 for r in records if r.t_done is not None
            and r.ticket is not None and not r.ticket.failure_reason
            and len(r.ticket.tokens) != r.out_len),
        "end_to_end": {
            "ttft_p90_ms": gen.nearest_rank(ttft, 90) * 1e3,
            "latency_per_token_p90_ms": gen.nearest_rank(per_tok, 90) * 1e3,
            "out_tokens_per_s": out_tokens / seconds,
            "setup_s": w["setup_s"]},
        "facts": {"window_s": seconds, "out_tokens": out_tokens,
                  "finished_tokens": sum(len(r.prompt) + r.out_len
                                         for r in in_window),
                  "drain_s": t_end - t_close,
                  "ttft_p50_ms": gen.nearest_rank(ttft, 50) * 1e3},
    }


def serve(ctx):
    """Set-up, the window and the drain; returns (the result without its
    checks, the sample of finished requests to compare)."""
    mix, cell, seconds = ctx["traffic"], ctx["cell"], ctx["seconds"]
    devices = ctx["devices"]
    eng, fe = set_up(ctx)
    try:
        tracer = trace_window.TraceWindow(ctx) if ctx["trace"] else None
        w = window(ctx, fe, mix, seconds, tracer)
    finally:
        fe.shutdown()
    peak = device.memory_peak_bytes(devices)
    device.memory_line("after the window", devices)
    out = summarize(w, mix, seconds)
    records = w["records"]
    if tracer:
        # contexts the decode kernel read for the output tokens delivered
        # while traced (a request's first token comes from its prefill)
        ctxs = []
        for r in records:
            pos = 0
            for tc, n in r.chunks:
                if tracer.t_start <= tc <= tracer.t_stop:
                    ctxs.extend(len(r.prompt) + j
                                for j in range(max(pos, 1), pos + n))
                pos += n
        out["facts"]["traced_contexts"] = ctxs
    sample = pick_sample(records, ctx["seed"], cell["sample_requests"])
    # free the engine, its pages and the model before the reference runs
    del fe, eng
    gc.collect()
    out.update(memory_peak_bytes=peak, registry=w["registry"],
               trace=tracer.reduced() if tracer else None)
    return out, sample
