"""Seeded weights, made on the device in one jitted call, in the type they
are used in. One rule for every model: a leaf of two or more dimensions is
normal(0, STD); a one-dimensional leaf named ``*.bias`` is zero; any other
one-dimensional leaf (a norm's scale) is one. A leaf's stream depends on the
seed and on the leaf's NAME only, so the plain reference makes the same
leaf again by name, alone, without the program."""
import zlib

import numpy as np

STD = 0.02


def key_words(seed, name):
    """Two 32-bit words for (seed, leaf name). ``seed`` is any whole number
    (the driver's pass 2**31)."""
    return np.random.SeedSequence(
        [int(seed), zlib.crc32(name.encode())]).generate_state(2)


def leaf_from_words(words, name, shape, dtype):
    import jax
    import jax.numpy as jnp

    if len(shape) >= 2:
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        return (jax.random.normal(key, shape, jnp.float32)
                * STD).astype(dtype)
    if name.endswith("bias"):
        return jnp.zeros(shape, dtype)
    return jnp.ones(shape, dtype)


def make_leaf(seed, name, shape, dtype):
    """One leaf alone (the reference's way in)."""
    import jax
    import jax.numpy as jnp

    shape = tuple(int(s) for s in shape)
    fn = jax.jit(lambda w: leaf_from_words(w, name, shape, dtype))
    return fn(jnp.asarray(key_words(seed, name), jnp.uint32))


def leaf_maker(specs):
    """A jitted function of the key words [n, 2] that makes every leaf of
    ``specs`` [(name, shape, dtype)] in one call. Names enter only through
    the words and the rule for one-dimensional leaves, so one maker serves
    every layer of a stack."""
    import jax

    specs = [(n, tuple(int(s) for s in shape), dt) for n, shape, dt in specs]

    def all_leaves(words):
        return [leaf_from_words(words[i], n, shape, dt)
                for i, (n, shape, dt) in enumerate(specs)]

    return jax.jit(all_leaves)


def words_for(seed, names):
    import jax.numpy as jnp

    return jnp.asarray(np.stack([key_words(seed, n) for n in names]),
                       jnp.uint32)


def make_leaves(seed, specs):
    """Every leaf of ``specs`` in one jitted call."""
    return leaf_maker(specs)(words_for(seed, [n for n, _, _ in specs]))


def load_into(model, seed):
    """Replace every parameter of a constructed ``paddle_tpu`` model by the
    seeded leaf of its name, shape and type. The old buffers are dropped
    first, so old and new never sit on the chip together."""
    import jax.numpy as jnp

    named = list(model.named_parameters())
    specs = [(n, tuple(p.shape), p._data.dtype) for n, p in named]
    for _, p in named:
        p._data = jnp.zeros((), p._data.dtype)
    for (_, p), leaf in zip(named, make_leaves(seed, specs)):
        p._data = leaf
