"""Cost seeded bug: a float64 matmul (the accidental-x64 promotion).
TPUs emulate f64 an order of magnitude slower than f32 — TPC402."""
import jax
import jax.numpy as jnp

from paddle_tpu.analysis.jaxpr import analyze_fn


def run():
    with jax.enable_x64(True):
        def f(x, w):
            return jnp.dot(x, w)  # f64 in, f64 dot

        x = jnp.ones((256, 256), jnp.float64)
        w = jnp.ones((256, 256), jnp.float64)
        return analyze_fn(f, x, w)
