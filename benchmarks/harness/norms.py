"""Norms by leaf block, the unit the training comparison is taken in.

A leaf whose last axis divides by ``blocks`` is read in that many equal
parts along it (a packed q|k|v projection is three leaves in one array: the
key's bias has no gradient under softmax, the query's and the value's do);
any other leaf is one block. Keys are ``<leaf>#<part>``."""


def block_norms(tree, blocks):
    """{leaf#part: l2 norm}, float32, inside or outside jit."""
    import jax.numpy as jnp

    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        n = blocks if a.ndim and a.shape[-1] % blocks == 0 else 1
        for j, part in enumerate(jnp.split(a, n, axis=-1)):
            out[f"{name}#{j}"] = jnp.sqrt(jnp.sum(jnp.square(part)))
    return out
