"""Verify/suffix slab-attention microbench: slab kernel vs window-gather.

Usage: python tools/mb_verify.py [HKV] [D] [TAG]
       (defaults HKV=4, D=64 — the GPT-small GQA serving geometry)

One JSON line per (m, batch, pages) combo appended to
tools/mb_results.jsonl, like mb_quant.py, comparing the two
implementations of multi-query paged attention (ISSUE 9 tentpole a):

* ``slab``   — ``paged_verify_slab_attention``, the fused Pallas kernel
  (per-row DMA page gather + m-position causal-window scoring in ONE
  program; interpret mode off-TPU — parity smoke, not a perf number).
* ``gather`` — ``_paged_multi_query_ref``, the jnp window-gather twin
  (materializes every row's FULL padded window through an XLA gather —
  what spec verify and suffix prefill rode before this kernel).

The headline column is ``kv_gbps`` — achieved KV-window bandwidth (live
window bytes over kernel time; a verify step is window-bandwidth-bound,
amortized over m query positions) — and ``bw_frac``, its fraction of the
v5e HBM roofline. The sweep spans the three consumers' regimes: spec
verify (m = k+1 ∈ {5, 9}), chunked prefill (m = 32) and suffix prefill
(m = 64) across batch × live-page depth.

Fenced via a chained scalar accumulator + one device_get (dispatch is
asynchronous; the fetch is the fence)."""
import json
import sys
import time

sys.path.insert(0, ".")

from paddle_tpu.framework.compile_cache import enable_compilation_cache

enable_compilation_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    PagedCacheState,
    _paged_multi_query_ref,
    paged_verify_slab_attention,
)

MS = (5, 9, 32, 64)          # spec k+1, chunked, suffix regimes
BATCHES = (4, 8)
LIVE_PAGES = (8, 24)         # cache depth per row, in pages
PAGE_SIZE = 16
HBM_BPS = 819e9              # v5e datasheet (mirrors mb_quant.py)


def timeit(fn, q, reps):
    """ONE dispatched scan of ``reps`` serialized calls; the scalar
    feedback serializes iterations and defeats DCE."""
    @jax.jit
    def loop(q):
        def body(carry, _):
            q, acc = carry
            s = jnp.sum(fn(q).astype(jnp.float32))
            return (q * (1.0 + 0.0 * s).astype(q.dtype), acc + s), None

        (_, acc), _ = jax.lax.scan(body, (q, jnp.float32(0)), None,
                                   length=reps)
        return acc

    float(jax.device_get(loop(q)))  # compile + warm
    t0 = time.perf_counter()
    float(jax.device_get(loop(q)))
    return (time.perf_counter() - t0) / reps


def main():
    hkv = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    tag = sys.argv[3] if len(sys.argv) > 3 else "verify"
    h = 12 if d == 64 else hkv  # q heads: GPT-small geometry by default
    on_tpu = jax.default_backend() == "tpu"
    reps = 30 if on_tpu else 2

    rng = np.random.default_rng(0)
    for batch in BATCHES:
        for live in LIVE_PAGES:
            max_pages = live + (max(MS) + PAGE_SIZE - 1) // PAGE_SIZE
            n_pages = 1 + batch * max_pages
            kp = jnp.asarray(
                rng.standard_normal((n_pages, PAGE_SIZE, hkv * d)) * 0.3,
                jnp.bfloat16)
            vp = jnp.asarray(
                rng.standard_normal((n_pages, PAGE_SIZE, hkv * d)) * 0.3,
                jnp.bfloat16)
            bt = np.arange(1, 1 + batch * max_pages,
                           dtype=np.int32).reshape(batch, max_pages)
            base = np.full((batch,), live * PAGE_SIZE, np.int32)
            st = PagedCacheState(kp, vp, None, jnp.asarray(bt),
                                 jnp.asarray(base), PAGE_SIZE)
            basej = jnp.asarray(base)
            for m in MS:
                q = jnp.asarray(
                    rng.standard_normal((batch, m, h, d)) * 0.3,
                    jnp.bfloat16)
                # live window bytes one call must move (k+v, bf16)
                win_bytes = 2 * batch * (live * PAGE_SIZE + m) \
                    * hkv * d * 2
                impls = {
                    "gather": lambda a: _paged_multi_query_ref(
                        a, st, basej),
                    "slab": lambda a: paged_verify_slab_attention(
                        a, kp, vp, st.block_tables, basej,
                        interpret=not on_tpu),
                }
                for name, fn in impls.items():
                    t = timeit(fn, q, reps)
                    line = {"tag": tag, "bench": "verify_slab",
                            "impl": name, "m": m, "batch": batch,
                            "live_pages": live, "hkv": hkv, "d": d,
                            "device": "tpu" if on_tpu else "cpu",
                            "ms": round(t * 1e3, 4),
                            "kv_gbps": round(win_bytes / t / 1e9, 1),
                            "bw_frac": round(win_bytes / t / HBM_BPS, 3)}
                    with open("tools/mb_results.jsonl", "a") as f:
                        f.write(json.dumps(line) + "\n")
                    print(json.dumps(line))


if __name__ == "__main__":
    main()
