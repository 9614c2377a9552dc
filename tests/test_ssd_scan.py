"""The Mamba-2 chunk walk's kernel pair (``ops/pallas/ssd_scan.py``, interpret
mode here) against its twin, the ``jax.numpy`` lines of
``models/nemotron_h.py::ssd_chunked``: the output and all six cotangents
(x, dt, a, B, C and the skip's D); then ``Mamba2Mixer`` with the kernel
forced against the plain reference (``benchmarks/reference/nemotron_h.py``);
then the rule that chooses the path.

Tolerances are ``test_nemotron_h.py``'s. ``F32``: both paths in float32, they
differ in the order of sums only; the worst cotangent read 7e-7 of its
largest value (``D``'s, two groups) and 1.3e-5 (dt's under decays of 140 a
position, where the exponents' gradient is a difference of equal sums
multiplied by ``a``), the limit stands at 1e-4. bfloat16 at the kernel's own
tile (chunk and state 128, heads of 64): each path's distance from a float32
reading of the same operands, over that reading's norm, read 0.0019 to 0.0041
for both; the limit stands at 0.01, and the two outputs agree to 1e-6 (the
forward rounds where its twin rounds).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_nemotron_h import F32, SEED, SEQS, agree, close, config, ref, share
from test_nemotron_h_shares import long_memory

from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.ops.pallas import ssd_scan

NAMES = ("y", "dx", "ddt", "da", "dB", "dC", "dD")
SMALL = dict(b=2, s=32, h=2, p=4, g=1, n=8, chunk=8)
CASES = {
    "one-chunk": dict(SMALL, s=8),
    "four-chunks": SMALL,                  # the carry and its reversed twin
    "two-groups": dict(SMALL, h=4, g=2),
    "padded": dict(SMALL, s=29),
    "dt-nought-rows": dict(SMALL, still=(3, 8, 9, 31)),
    "strong-decay": dict(SMALL, a_scale=200.0),   # exp underflows
    "long-memory": dict(SMALL, a_scale=1e-4),     # decays near nought
}
EACH_CASE = pytest.mark.parametrize("case", list(CASES))


def operands(b, s, h, p, g, n, dtype=jnp.float32, a_scale=1.0, still=(),
             **_):
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    narrow = lambda k, shape: (0.5 * jax.random.normal(k, shape)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    if still:  # positions that neither decay the state nor add to it
        dt = dt.at[:, np.asarray(still)].set(0.0)
    return (narrow(ks[0], (b, s, h, p)), dt,
            -a_scale * jnp.exp(0.3 * jax.random.normal(ks[2], (h,))),
            narrow(ks[3], (b, s, g, n)), narrow(ks[4], (b, s, g, n)),
            jax.random.normal(ks[5], (h,)),
            jax.random.normal(ks[6], (b, s, h, p)))


def out_and_cotangents(chunk, x, dt, a, bm, cm, d, dy):
    y, vjp = jax.vjp(lambda *t: nh.ssd_chunked(*t[:5], chunk, t[5]),
                     x, dt, a, bm, cm, d)
    return (y,) + vjp(dy)


@pytest.fixture
def forced(monkeypatch):
    """``ssd_chunked`` takes the kernel pair, whatever the backend and the
    shape (interpret mode has no tiles to respect)."""
    monkeypatch.setattr(ssd_scan, "enabled", lambda *a: True)


@pytest.fixture(scope="module")
def twin():
    """Each case's output and cotangents by the ``jax.numpy`` lines."""
    assert not ssd_scan.enabled(8, 8, 2, 4, 4)
    return {k: out_and_cotangents(c["chunk"], *operands(**c))
            for k, c in CASES.items()}


@EACH_CASE
def test_forward_agrees_with_the_jnp_lines(case, twin, forced):
    c = CASES[case]
    x, dt, a, bm, cm, d, _ = operands(**c)
    y = nh.ssd_chunked(x, dt, a, bm, cm, c["chunk"], d)
    assert y.shape == x.shape and y.dtype == jnp.float32
    close(y, twin[case][0], F32, "y")
    # without the skip: its weights default to nought inside the kernel
    bare = nh.ssd_chunked(x, dt, a, bm, cm, c["chunk"])
    close(bare + d[:, None] * x, twin[case][0], F32, "y without D")


@EACH_CASE
def test_cotangents_agree_with_the_jnp_lines(case, twin, forced):
    c = CASES[case]
    got = out_and_cotangents(c["chunk"], *operands(**c))
    # a's gradient is a sum of the exponents' gradients times dt: where the
    # decays leave it at nought its measure is dt's own gradient
    floor = float(jnp.max(jnp.abs(twin[case][2])))
    for name, have, want in zip(NAMES, got, twin[case]):
        assert bool(jnp.all(jnp.isfinite(have))), name
        scale = max(float(jnp.max(jnp.abs(want))), floor * (name == "da"))
        gap = float(jnp.max(jnp.abs(have - want))) / (scale or 1.0)
        assert gap <= F32, f"{case} {name}: gap {gap:.3e} over {F32:.1e}"


def test_bfloat16_at_the_kernels_own_tile(monkeypatch):
    """Chunk and state of 128, heads of 64, two groups of two heads, four
    chunks: the shape class the chip runs, operands in bfloat16."""
    shape = dict(b=1, s=512, h=4, p=64, g=2, n=128)
    assert ssd_scan.supported(128, 128, 2, 64)
    ops = operands(**shape, dtype=jnp.bfloat16, a_scale=2.7)
    exact = out_and_cotangents(128, *(t.astype(jnp.float32) for t in ops))
    plain = out_and_cotangents(128, *ops)
    monkeypatch.setattr(ssd_scan, "enabled", lambda *a: True)
    kernel = out_and_cotangents(128, *ops)
    off = lambda t, want: float(jnp.linalg.norm(
        (t.astype(jnp.float32) - want).ravel()) / jnp.linalg.norm(want.ravel()))
    assert off(kernel[0], plain[0]) <= 1e-6
    for name, k, j, want in zip(NAMES, kernel, plain, exact):
        assert k.dtype == j.dtype and k.shape == j.shape, name
        assert off(k, want) <= 0.01 and off(j, want) <= 0.01, \
            f"{name}: kernel {off(k, want):.4f}, jnp {off(j, want):.4f}"


# ------------------------------------------------- the mixer, kernel forced


@SEQS
def test_mixer_through_the_kernel_against_the_reference(seq, forced):
    """``Mamba2Mixer`` uncut (4 groups of 2 heads: the grid's group axis)
    with its scan in the kernel pair, loss and every leaf's gradient against
    the recurrence over positions."""
    cfg = config("M")
    agree(cfg, ref.initial_params(cfg, SEED, jnp.float32), seq)


@pytest.mark.parametrize("cut", [config, share], ids=["whole", "share"])
def test_long_memory_through_the_kernel(cut, forced):
    cfg = cut("MEM*E")
    agree(cfg, long_memory(cfg, ref.initial_params(cfg, SEED, jnp.float32)),
          40)


# ----------------------------------------------------------- the shape rule


@pytest.mark.parametrize("chunk,state,heads,head_dim,ok", [
    (128, 128, 16, 64, True),     # the cell's share, and a group of the
    (128, 128, 2, 64, True),      # uncut layer: 8 groups of 16 heads
    (256, 128, 16, 64, True),
    (64, 128, 16, 64, False),     # a chunk of 64 does not fill the tiles
    (128, 64, 16, 64, False),
    (128, 128, 3, 64, False),     # 192 lanes
    (128, 128, 16, 60, False),
    (128, 128, 64, 64, False),    # 64 heads a group: past the VMEM budget
], ids=lambda v: str(v))
def test_shapes_the_kernels_take(chunk, state, heads, head_dim, ok):
    assert ssd_scan.supported(chunk, state, heads, head_dim) is ok


def test_off_the_chip_the_jnp_lines_run(monkeypatch):
    """The path is chosen from what the code can observe: the backend and
    the shape. Here the backend is the CPU."""
    assert jax.default_backend() != "tpu"
    assert not ssd_scan.enabled(128, 128, 16, 64)
    called = []
    monkeypatch.setattr(ssd_scan, "ssd_scan",
                        lambda *a: called.append(a) or 1 / 0)
    x, dt, a, bm, cm, d, _ = operands(**SMALL)
    nh.ssd_chunked(x, dt, a, bm, cm, 8, d)
    assert not called
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd_scan.enabled(128, 128, 16, 64)
    assert not ssd_scan.enabled(64, 128, 16, 64)
