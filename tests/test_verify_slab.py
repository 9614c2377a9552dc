"""Windowed slab kernel parity: fused verify/suffix (ISSUE 9 tentpole a)
and paged decode, in interpret mode against their jnp twins.

The kernels (``paged_verify_slab_attention``, and the decode form of the
same program) must agree with the jnp window-gather references
(``_paged_multi_query_ref``, ``_paged_slab_ref``) to a FEW ULP of f32, not
bitwise: the kernel carries its softmax across windows of pages and
normalizes after the PV dot, the reference normalizes before it, and the
installed XLA:CPU no longer accumulates the two contractions in one order
(the whole-resident kernel already differed by 1 ulp there). A masking /
window / dequant bug moves the output by whole values of V, orders of
magnitude past that bound. What IS one program run twice — the kernel
against itself, and the page/scale/length writes around it — stays
bitwise. Covered: per-row base lengths, GQA, int8 pages + packed scale
lanes, one window and several, mixed hit/miss suffix waves driven
end-to-end through ``paged_state_verify`` (per-row ``prefill_valid``
widths incl. pad rows), capacity-clamp overshoot, idle decode slots, and
the dispatch shape itself — ONE ``pallas_call``, ZERO gathers in the
kernel jaxpr. On-chip Mosaic parity lives in
``tests/onchip/test_kernels_onchip.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import (
    PagedCacheState,
    _paged_multi_query_ref,
    _paged_slab_ref,
    _paged_window_call,
    paged_state_verify,
    paged_verify_slab_attention,
)

H, HKV, D, PS, MAXP = 4, 2, 32, 8, 4
KHD = HKV * D
# _WINDOW_BYTES that makes a window ONE page of f32 K plus V (4 windows a
# row here) / of int8 (the same one page); None = the row is one window
ONE_PAGE_WINDOW = {False: 2 * PS * KHD * 4, True: 2 * PS * KHD}


def assert_few_ulp(out, ref):
    """|out - ref| within 16 ulp of the largest reference magnitude: the
    outputs are softmax-weighted sums of V, so their rounding error scales
    with the magnitudes summed, not with each (possibly cancelled) value."""
    out, ref = np.asarray(out), np.asarray(ref)
    assert out.dtype == ref.dtype == np.float32
    tol = 16 * np.finfo(np.float32).eps * np.abs(ref).max()
    np.testing.assert_allclose(out, ref, rtol=0, atol=tol)


@pytest.fixture(params=[None, "one-page"], ids=["1win", "4win"])
def windows(request, monkeypatch):
    """Run the test with a row in one window, then split over four."""
    def arm(quantized=False):
        if request.param is not None:
            monkeypatch.setattr(pa, "_WINDOW_BYTES",
                                ONE_PAGE_WINDOW[quantized])
            assert pa._window_pages(MAXP, PS, KHD,
                                    1 if quantized else 4) == 1
    return arm


def make_state(rng, b, quantized=False, fill_pages=12):
    """A paged state with ``fill_pages`` pages of random content and a
    block table pointing rows at distinct physical pages."""
    p_total = 1 + b * MAXP
    if quantized:
        kp = jnp.asarray(rng.integers(-127, 128, (p_total, PS, KHD)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (p_total, PS, KHD)),
                         jnp.int8)
        sc = jnp.zeros((p_total, PS, 128), jnp.bfloat16)
        sc = sc.at[..., :2 * HKV].set(jnp.asarray(
            rng.standard_normal((p_total, PS, 2 * HKV)) * 0.05 + 0.1,
            jnp.bfloat16))
    else:
        kp = jnp.asarray(rng.standard_normal((p_total, PS, KHD)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((p_total, PS, KHD)),
                         jnp.float32)
        sc = None
    tables = np.arange(1, 1 + b * MAXP, dtype=np.int32).reshape(b, MAXP)
    return PagedCacheState(kp, vp, sc, jnp.asarray(tables),
                           jnp.zeros((b,), jnp.int32), PS)


@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_vs_ref(rng, windows, quantized):
    """Pure attention parity at ragged per-row base lengths (GQA)."""
    windows(quantized)
    b, m = 3, 5
    st = make_state(np.random.default_rng(0), b, quantized=quantized)
    base = jnp.asarray([17, 0, 26], jnp.int32)
    st = st.replace(lengths=base + m)
    q = jnp.asarray(rng.standard_normal((b, m, H, D)), jnp.float32)
    ref = _paged_multi_query_ref(q, st, base)
    out = paged_verify_slab_attention(
        q, st.k_pages, st.v_pages, st.block_tables, base,
        scale_pages=st.scale_pages, interpret=True)
    assert_few_ulp(out, ref)
    # the same program run twice IS bitwise
    again = paged_verify_slab_attention(
        q, st.k_pages, st.v_pages, st.block_tables, base,
        scale_pages=st.scale_pages, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


def test_kernel_at_capacity_clamp(rng, windows):
    """base + m past the table capacity must clamp exactly like the ref
    (an overshooting straggler's window never reads OOB)."""
    windows()
    b, m = 2, 6
    st = make_state(np.random.default_rng(1), b)
    base = jnp.asarray([MAXP * PS - 2, MAXP * PS], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, m, H, D)), jnp.float32)
    ref = _paged_multi_query_ref(q, st, base)
    out = paged_verify_slab_attention(
        q, st.k_pages, st.v_pages, st.block_tables, base, interpret=True)
    assert_few_ulp(out, ref)


def test_kernel_sublane_padded_m(rng, windows):
    """m not a multiple of the sublane tile pads inside the wrapper; the
    visible rows are unaffected by the pad rows."""
    windows()
    b = 2
    st = make_state(np.random.default_rng(2), b)
    base = jnp.asarray([9, 3], jnp.int32)
    for m in (1, 2, 8, 9):
        q = jnp.asarray(rng.standard_normal((b, m, H, D)), jnp.float32)
        ref = _paged_multi_query_ref(q, st, base)
        out = paged_verify_slab_attention(
            q, st.k_pages, st.v_pages, st.block_tables, base,
            interpret=True)
        assert_few_ulp(out, ref)


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_kernel_vs_ref(rng, windows, quantized):
    """The decode form of the windowed program (every query row attends
    tokens < length) against ``_paged_slab_ref``: ragged lengths, a row at
    the table capacity, one past it (clamped), and an idle slot (length 0
    → zeros, no page read). The public dispatch only takes this kernel on
    the chip, so interpret mode drives the call beneath it."""
    windows(quantized)
    st = make_state(np.random.default_rng(5), 5, quantized=quantized)
    lengths = jnp.asarray([13, MAXP * PS, 0, 1, MAXP * PS + 3], jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, H, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    ref = _paged_slab_ref(q, st.k_pages, st.v_pages, st.block_tables,
                          jnp.minimum(lengths, MAXP * PS), scale,
                          st.scale_pages)
    qr = jnp.broadcast_to(q.reshape(5, 1, H * D), (5, pa._Q_ROWS, H * D))
    out = _paged_window_call(
        lengths, st.block_tables, qr, st.k_pages, st.v_pages,
        st.scale_pages, jnp.float32, scale=scale, num_heads=H, head_dim=D,
        m=0, interpret=True)
    # every broadcast query row computed the same thing
    np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                  np.asarray(out[:, -1]))
    out = np.asarray(out[:, 0]).reshape(5, H, D)
    assert not out[2].any(), "idle slot must read as zeros"
    live = [0, 1, 3, 4]
    assert_few_ulp(out[live], np.asarray(ref)[live])


@pytest.mark.parametrize("quantized", [False, True])
def test_state_verify_mixed_hit_miss_wave(rng, windows, quantized):
    """End-to-end ``paged_state_verify`` with per-row suffix widths —
    a cache-hit row (base>0, partial width), a miss row (base 0, full
    width), a full-hit row (width 1) and a pad row (width 0) in ONE wave
    — agrees whether the attention runs the kernel or the jnp twin:
    outputs to a few ulp; pages, scales and lengths (the same writes,
    whichever attends) bitwise."""
    windows(quantized)
    b, m = 4, 6
    st0 = make_state(np.random.default_rng(3), b, quantized=quantized)
    st0 = st0.replace(lengths=jnp.asarray([16, 0, 24, 0], jnp.int32),
                      prefill_valid=jnp.asarray([4, 6, 1, 0], jnp.int32))
    q = jnp.asarray(rng.standard_normal((b, m, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, m, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, m, HKV, D)), jnp.float32)

    out_ref, st_ref = paged_state_verify(st0, q, k, v)

    def kernel_dispatch(q, state, base_len, scale=None):
        return paged_verify_slab_attention(
            q, state.k_pages, state.v_pages, state.block_tables, base_len,
            scale=scale, scale_pages=state.scale_pages, interpret=True)

    orig = pa.paged_multi_query_attention
    pa.paged_multi_query_attention = kernel_dispatch
    try:
        out_k, st_k = paged_state_verify(st0, q, k, v)
    finally:
        pa.paged_multi_query_attention = orig

    assert_few_ulp(out_k, out_ref)
    np.testing.assert_array_equal(np.asarray(st_k.lengths),
                                  np.asarray(st_ref.lengths))
    np.testing.assert_array_equal(np.asarray(st_k.k_pages),
                                  np.asarray(st_ref.k_pages))
    np.testing.assert_array_equal(np.asarray(st_k.v_pages),
                                  np.asarray(st_ref.v_pages))
    if quantized:
        np.testing.assert_array_equal(np.asarray(st_k.scale_pages),
                                      np.asarray(st_ref.scale_pages))


def test_one_pallas_call_zero_gathers(rng):
    """The fused path is ONE kernel: exactly one pallas_call in the
    jaxpr and no gather anywhere — the window materializes via in-kernel
    DMA, never an XLA pages[bt] gather (the thing this kernel exists to
    delete from the verify hot path)."""
    b, m = 2, 5
    st = make_state(np.random.default_rng(4), b)
    base = jnp.asarray([9, 3], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, m, H, D)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, kp, vp, bt, bl: paged_verify_slab_attention(
            q, kp, vp, bt, bl, interpret=True))(
        q, st.k_pages, st.v_pages, st.block_tables, base)
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert prims.count("pallas_call") == 1, prims
    assert "gather" not in prims, prims
