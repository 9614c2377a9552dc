"""Standalone packed causal-flash microbench, one S per run.

Usage: python tools/mb_flash.py S [B] [TAG]
Appends a JSON line to tools/mb_results.jsonl. Fenced via a chained
scalar accumulator + one device_get (dispatch is asynchronous; the fetch
is the fence)."""
import json
import sys
import time

sys.path.insert(0, ".")

from paddle_tpu.framework.compile_cache import enable_compilation_cache

enable_compilation_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops.pallas import causal_flash as cf  # noqa: E402

H, D = 16, 64
PEAK = 197e12


def timeit(fn, x, reps=20):
    """ONE dispatched scan of ``reps`` serialized kernel calls — per-call
    dispatch from the host would otherwise add to ~2 ms of kernel
    compute. The scalar feedback serializes iterations."""
    @jax.jit
    def loop(x):
        def body(carry, _):
            x, acc = carry
            s = jnp.sum(fn(x).astype(jnp.float32))
            # next input depends on this output -> no overlap, no DCE
            return (x * (1.0 + 0.0 * s).astype(x.dtype), acc + s), None

        (xf, acc), _ = jax.lax.scan(body, (x, jnp.float32(0)), None,
                                    length=reps)
        return acc

    float(jax.device_get(loop(x)))
    t0 = time.perf_counter()
    float(jax.device_get(loop(x)))
    return (time.perf_counter() - t0) / reps


def main():
    S = int(sys.argv[1])
    B = int(sys.argv[2]) if len(sys.argv) > 2 else (8 if S <= 2048 else 4)
    tag = sys.argv[3] if len(sys.argv) > 3 else "flash"
    hpb = cf.heads_per_block(H, D)
    qkv = jax.random.normal(jax.random.PRNGKey(0),
                            (B, 3 * H // hpb, S, hpb * D), jnp.bfloat16)
    fwd = jax.jit(lambda x: cf.causal_flash_qkv(x, H, D))
    gfn = jax.jit(jax.grad(
        lambda x: jnp.sum(cf.causal_flash_qkv(x, H, D).astype(
            jnp.float32))))
    t_f = timeit(fwd, qkv)
    t_g = timeit(gfn, qkv)
    tri = S * S / 2
    f_fwd = 2 * 2 * tri * D * H * B
    # grad runs fwd (2 dots) + bwd (5 dots) over the triangle
    f_tot = 2 * 2 * tri * D * H * B + 5 * 2 * tri * D * H * B
    line = {"tag": tag, "seq": S, "batch": B,
            "fwd_ms": round(t_f * 1e3, 3),
            "fwd_tf": round(f_fwd / t_f / 1e12, 1),
            "fwd_frac": round(f_fwd / t_f / PEAK, 3),
            "fwdbwd_ms": round(t_g * 1e3, 3),
            "fwdbwd_tf": round(f_tot / t_g / 1e12, 1),
            "fwdbwd_frac": round(f_tot / t_g / PEAK, 3)}
    with open("tools/mb_results.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line))


if __name__ == "__main__":
    main()
