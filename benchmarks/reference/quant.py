"""The control's arithmetic: the plain reference with the operands of every
matrix multiplication rounded to fp8 (e4m3, one scale per tensor), the
nearest precision below the bf16 the configurations state. Straight-through
for gradients."""


def fp8(x):
    import jax
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def identity(x):
    return x


def operand_rounding(precision):
    """``float32`` (the reference) or ``fp8`` (the control)."""
    return {"float32": identity, "fp8": fp8}[precision]
