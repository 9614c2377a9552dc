"""Weight-only quant matmul microbench: rows × dtype × backend sweep.

Usage: python tools/mb_quant.py [K] [N] [TAG]
       (defaults K=N=3072 — the GPT-medium qkv/fc decode GEMM)

One JSON line per (rows, weight_dtype, backend) combo appended to
tools/mb_results.jsonl, like mb_flash.py. ``backend='pallas'`` is the
fused dequant-in-kernel matmul (ops/pallas/quant_matmul.py; interpret
mode off-TPU — correct but slow, so CPU runs are parity smoke, not perf
numbers); ``'xla'`` is the convert-fusion / two-dot path. The headline
column is ``w_gbps`` — achieved weight-stream bandwidth (packed weight +
scale bytes over kernel time) — and ``bw_frac``, its fraction of the v5e
HBM roofline: a decode GEMM is weight-bound, so bw_frac IS the roofline
fraction and the two backends are directly comparable per row count.

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

from paddle_tpu.nn.quant import quant_matmul_xla  # noqa: E402
from paddle_tpu.ops.pallas.quant_matmul import quant_matmul_pallas  # noqa: E402

ROWS = (1, 8, 32, 64)
HBM_BPS = 819e9  # v5e datasheet (mirrors bench.py's default)


def timeit(fn, x, reps):
    """ONE dispatched scan of ``reps`` serialized calls — per-call
    dispatch from the host would swamp sub-ms kernels. The scalar
    feedback serializes iterations and defeats DCE."""
    @jax.jit
    def loop(x):
        def body(carry, _):
            x, acc = carry
            s = jnp.sum(fn(x).astype(jnp.float32))
            return (x * (1.0 + 0.0 * s).astype(x.dtype), acc + s), None

        (_, acc), _ = jax.lax.scan(body, (x, jnp.float32(0)), None,
                                   length=reps)
        return acc

    float(jax.device_get(loop(x)))  # compile + warm
    t0 = time.perf_counter()
    float(jax.device_get(loop(x)))
    return (time.perf_counter() - t0) / reps


def main():
    k = int(sys.argv[1]) if len(sys.argv) > 1 else 3072
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 3072
    tag = sys.argv[3] if len(sys.argv) > 3 else "quant"
    on_tpu = jax.default_backend() == "tpu"
    reps = 30 if on_tpu else 2

    rng = np.random.default_rng(0)
    w8 = rng.integers(-127, 128, (k, n)).astype(np.int8)
    q4 = rng.integers(-7, 8, (k, n)).astype(np.int8)
    w4 = np.bitwise_or(
        np.bitwise_and(q4[0::2], np.int8(0x0F)),
        np.left_shift(q4[1::2], 4).astype(np.int8)).astype(np.int8)
    sc = ((rng.random(n) + 0.1) / 127).astype(np.float32)
    weights = {"int8": jnp.asarray(w8), "int4": jnp.asarray(w4)}
    scj = jnp.asarray(sc)

    for rows in ROWS:
        x = jnp.asarray(rng.standard_normal((rows, k)) * 0.3,
                        jnp.bfloat16)
        for wdt, wq in weights.items():
            wbytes = wq.nbytes + scj.nbytes
            for backend in ("xla", "pallas"):
                if backend == "pallas":
                    fn = lambda a, wq=wq, wdt=wdt: quant_matmul_pallas(
                        a, wq, scj, weight_dtype=wdt)
                else:
                    fn = lambda a, wq=wq, wdt=wdt: quant_matmul_xla(
                        a, wq, scj, weight_dtype=wdt)
                t = timeit(fn, x, reps)
                line = {"tag": tag, "bench": "quant_matmul",
                        "rows": rows, "k": k, "n": n,
                        "weight_dtype": wdt, "backend": backend,
                        "device": "tpu" if on_tpu else "cpu",
                        "ms": round(t * 1e3, 4),
                        "w_gbps": round(wbytes / t / 1e9, 1),
                        "bw_frac": round(wbytes / t / HBM_BPS, 3)}
                with open("tools/mb_results.jsonl", "a") as f:
                    f.write(json.dumps(line) + "\n")
                print(json.dumps(line))


if __name__ == "__main__":
    main()
