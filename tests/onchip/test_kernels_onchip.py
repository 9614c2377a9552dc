"""Non-interpret (Mosaic-lowered) equivalence for every Pallas kernel
family (VERDICT r3 #4: a Mosaic-only lowering bug must surface as a test
failure, not a wrong bench number). Each test compares the real-TPU kernel
against its jnp reference twin at serving/train-representative shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


class TestCausalFlashOnChip:
    @staticmethod
    def _ref(qkv, H, D):
        """Plain-XLA attention reference — independent of every Pallas
        code path, so a Mosaic lowering bug can't hide in both sides."""
        B, G, S, lanes = qkv.shape
        hpb = lanes // D
        x = qkv.astype(jnp.float32).reshape(B, 3, G // 3, S, hpb, D)
        q, k, v = x[:, 0], x[:, 1], x[:, 2]
        logits = jnp.einsum("bgshd,bgthd->bghst", q, k) / np.sqrt(D)
        mask = np.tril(np.ones((S, S), bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        o = jnp.einsum("bghst,bgthd->bgshd",
                       jax.nn.softmax(logits, -1), v)
        return o.reshape(B, G // 3, S, lanes)

    def test_whole_seq_fwd_bwd(self, rng):
        from paddle_tpu.ops.pallas import causal_flash as cf

        B, H, D, S = 2, 4, 64, 512
        qkv = jnp.asarray(rng.standard_normal((B, 6, S, 128)) * 0.3,
                          jnp.bfloat16)
        assert not cf._interpret()
        out, lse = cf._fwd(qkv, H, D, 1 / 8.0)
        assert _err(out, self._ref(qkv, H, D)) < 2e-2
        g = jnp.asarray(rng.standard_normal(out.shape) * 0.1, jnp.bfloat16)
        d = cf._bwd(H, D, 1 / 8.0, (qkv, out, lse), g)
        # independent reference grad via jax AD of the plain-XLA math
        dref = jax.grad(lambda x: jnp.sum(
            self._ref(x, H, D) * g.astype(jnp.float32)))(qkv)
        rel = _err(d, dref) / (float(jnp.max(jnp.abs(
            dref.astype(jnp.float32)))) + 1e-9)
        assert rel < 5e-2, rel
        # tiled bwd against the same independent reference
        d2 = cf._bwd_tiled(H, D, 1 / 8.0, (qkv, out, lse), g)
        rel2 = _err(d2, dref) / (float(jnp.max(jnp.abs(
            dref.astype(jnp.float32)))) + 1e-9)
        assert rel2 < 5e-2, rel2

    def test_tiled_long_seq(self, rng):
        from paddle_tpu.ops.pallas.causal_flash import causal_flash_qkv

        B, H, D, S = 1, 2, 64, 2048
        qkv = jnp.asarray(rng.standard_normal((B, 3, S, 128)) * 0.3,
                          jnp.bfloat16)
        out = causal_flash_qkv(qkv, H, D)
        # reference in f32 on the same chip (plain XLA ops, no Pallas)
        x = qkv.astype(jnp.float32).reshape(B, 3, 1, S, 2, D)
        q, k, v = x[:, 0], x[:, 1], x[:, 2]
        logits = jnp.einsum("bgshd,bgthd->bghst", q, k) / np.sqrt(D)
        mask = np.tril(np.ones((S, S), bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        want = jnp.einsum("bghst,bgthd->bgshd",
                          jax.nn.softmax(logits, -1), v)
        want = want.reshape(B, 1, S, 2 * D)
        assert _err(out, want) < 2e-2

    def test_tiled_grad_matches_ref_grad(self, rng):
        from paddle_tpu.ops.pallas.causal_flash import causal_flash_qkv

        B, H, D, S = 1, 2, 64, 2048
        qkv = jnp.asarray(rng.standard_normal((B, 3, S, 128)) * 0.3,
                          jnp.float32)

        def ref(x):
            xr = x.reshape(B, 3, 1, S, 2, D)
            q, k, v = xr[:, 0], xr[:, 1], xr[:, 2]
            logits = jnp.einsum("bgshd,bgthd->bghst", q, k) / np.sqrt(D)
            mask = np.tril(np.ones((S, S), bool))
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            o = jnp.einsum("bghst,bgthd->bgshd",
                           jax.nn.softmax(logits, -1), v)
            return o.reshape(B, 1, S, 2 * D)

        ct = jnp.asarray(rng.standard_normal((B, 1, S, 128)) * 0.1,
                         jnp.float32)
        g1 = jax.grad(lambda x: jnp.sum(causal_flash_qkv(x, H, D) * ct))(
            qkv)
        g2 = jax.grad(lambda x: jnp.sum(ref(x) * ct))(qkv)
        rel = _err(g1, g2) / (float(jnp.max(jnp.abs(g2))) + 1e-9)
        assert rel < 1e-2, rel


class TestGeneralFlashOnChip:
    def test_fused_fwd_bwd(self, rng):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_fused)

        B, S, H, D = 2, 512, 4, 64
        q = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.3,
                        jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.3,
                        jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.3,
                        jnp.bfloat16)
        out = flash_attention_fused(q, k, v, causal=True)

        def ref(q, k, v):
            qf = q.astype(jnp.float32)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
            s = s / np.sqrt(D)
            mask = np.tril(np.ones((S, S), bool))
            s = jnp.where(mask[None, None], s, -1e30)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                              v.astype(jnp.float32))

        assert _err(out, ref(q, k, v)) < 2e-2
        ct = jnp.asarray(rng.standard_normal(out.shape) * 0.1, jnp.bfloat16)
        g1 = jax.grad(lambda a: jnp.sum((flash_attention_fused(
            a, k, v, causal=True) * ct).astype(jnp.float32)))(q)
        g2 = jax.grad(lambda a: jnp.sum(ref(a, k, v) * ct))(q)
        rel = _err(g1, g2) / (float(jnp.max(jnp.abs(
            g2.astype(jnp.float32)))) + 1e-9)
        assert rel < 5e-2, rel


class TestDecodeOnChip:
    def test_decode_attention_pallas(self, rng):
        from paddle_tpu.ops.pallas.decode_attention import (
            decode_attention_pallas, decode_attention_ref)

        B, H, D, S = 8, 12, 64, 1024
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
        vc = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
        lengths = jnp.asarray(rng.integers(1, S, (B,)), jnp.int32)
        got = decode_attention_pallas(q, kc, vc, lengths)
        want = decode_attention_ref(q, kc, vc, lengths)
        assert _err(got, want) < 2e-2

    def test_slab_decode(self, rng):
        from paddle_tpu.ops.pallas.decode_attention import (
            _slab_pallas, _slab_ref)

        B, H, D, S = 8, 12, 64, 640
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        slab = jnp.asarray(rng.standard_normal((2, B, S, H * D)),
                           jnp.bfloat16)
        lengths = jnp.asarray(rng.integers(1, S, (B,)), jnp.int32)
        got = _slab_pallas(q, slab, lengths, 1 / 8.0)
        want = _slab_ref(q, slab, lengths, 1 / 8.0)
        assert _err(got, want) < 2e-2

    def test_slab_decode_llama_widths_several_windows(self, rng):
        """32 heads x 128 = 4096 lanes, S=2048: K+V of the whole slab are
        32 MiB a row, so the kernel walks it in 8 windows and carries the
        softmax across them; short rows skip the windows past their end."""
        from paddle_tpu.ops.pallas.decode_attention import (
            _slab_pallas, _slab_ref, _slab_window)

        B, H, D, S = 4, 32, 128, 2048
        assert S // _slab_window(S, H * D, 2) > 1
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        slab = jnp.asarray(rng.standard_normal((2, B, S, H * D)),
                           jnp.bfloat16)
        lengths = jnp.asarray([1, 300, 1500, S], jnp.int32)
        got = _slab_pallas(q, slab, lengths, 1 / np.sqrt(D))
        want = _slab_ref(q, slab, lengths, 1 / np.sqrt(D))
        assert _err(got, want) < 2e-2


class TestPagedOnChip:
    def _tables(self, rng, B, NP, PS, MAXP):
        bt = np.zeros((B, MAXP), np.int32)
        lengths = rng.integers(1, MAXP * PS, (B,)).astype(np.int32)
        used = set()
        for b in range(B):
            for j in range(-(-int(lengths[b]) // PS)):
                pg = int(rng.integers(1, NP))
                while pg in used:
                    pg = int(rng.integers(1, NP))
                used.add(pg)
                bt[b, j] = pg
        return jnp.asarray(bt), jnp.asarray(lengths)

    @pytest.mark.parametrize("hkv", [12, 4])
    def test_slab_paged_bf16(self, rng, hkv):
        from paddle_tpu.ops.pallas.paged_attention import (
            _paged_slab_ref, paged_slab_decode_attention)

        B, H, D, PS, NP, MAXP = 8, 12, 64, 16, 120, 24
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal((NP, PS, hkv * D)),
                         jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((NP, PS, hkv * D)),
                         jnp.bfloat16)
        bt, lengths = self._tables(rng, B, NP, PS, MAXP)
        got = paged_slab_decode_attention(q, kp, vp, bt, lengths, H)
        want = _paged_slab_ref(q, kp, vp, bt, lengths, 1 / 8.0)
        assert _err(got, want) < 5e-2

    def test_slab_paged_int8(self, rng):
        from paddle_tpu.ops.pallas.paged_attention import (
            _paged_slab_ref, paged_slab_decode_attention,
            quantize_rows_int8)

        B, H, D, HKV, PS, NP, MAXP = 8, 12, 64, 4, 16, 120, 24
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        kq, ks = quantize_rows_int8(jnp.asarray(
            rng.standard_normal((NP, PS, HKV, D)), jnp.float32))
        vq, vs = quantize_rows_int8(jnp.asarray(
            rng.standard_normal((NP, PS, HKV, D)), jnp.float32))
        sc = (jnp.zeros((NP, PS, 128), jnp.bfloat16)
              .at[..., :HKV].set(ks.astype(jnp.bfloat16))
              .at[..., HKV:2 * HKV].set(vs.astype(jnp.bfloat16)))
        kq = kq.reshape(NP, PS, HKV * D)
        vq = vq.reshape(NP, PS, HKV * D)
        bt, lengths = self._tables(rng, B, NP, PS, MAXP)
        got = paged_slab_decode_attention(q, kq, vq, bt, lengths, H,
                                          scale_pages=sc)
        want = _paged_slab_ref(q, kq, vq, bt, lengths, 1 / 8.0,
                               scale_pages=sc)
        assert _err(got, want) < 5e-2


    @pytest.mark.parametrize("quantized", [False, True])
    def test_slab_paged_llama_widths_several_windows(self, rng, quantized):
        """llama2_7b() geometry: 4096 lanes, page 16, context 2048 = 128
        pages a row, walked in windows of 32 (bf16) / 64 (int8) pages."""
        from paddle_tpu.ops.pallas.paged_attention import (
            _paged_slab_ref, _window_pages, paged_slab_decode_attention,
            quantize_rows_int8)

        B, H, D, PS, NP, MAXP = 4, 32, 128, 16, 600, 128
        assert _window_pages(MAXP, PS, H * D, 1 if quantized else 2) < MAXP
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        bt, lengths = self._tables(rng, B, NP, PS, MAXP)
        sc = None
        if quantized:
            kp, ks = quantize_rows_int8(jnp.asarray(
                rng.standard_normal((NP, PS, H, D)), jnp.float32))
            vp, vs = quantize_rows_int8(jnp.asarray(
                rng.standard_normal((NP, PS, H, D)), jnp.float32))
            sc = (jnp.zeros((NP, PS, 128), jnp.bfloat16)
                  .at[..., :H].set(ks.astype(jnp.bfloat16))
                  .at[..., H:2 * H].set(vs.astype(jnp.bfloat16)))
            kp, vp = kp.reshape(NP, PS, H * D), vp.reshape(NP, PS, H * D)
        else:
            kp = jnp.asarray(rng.standard_normal((NP, PS, H * D)),
                             jnp.bfloat16)
            vp = jnp.asarray(rng.standard_normal((NP, PS, H * D)),
                             jnp.bfloat16)
        got = paged_slab_decode_attention(q, kp, vp, bt, lengths, H,
                                          scale_pages=sc)
        want = _paged_slab_ref(q, kp, vp, bt, lengths, 1 / np.sqrt(D),
                               scale_pages=sc)
        assert _err(got, want) < 5e-2


class TestVerifySlabOnChip:
    """Mosaic-lowered fused verify/suffix slab attention (ISSUE 9) vs
    the jnp window-gather reference, plus the dispatch-shape contract:
    the verify path is ONE pallas_call with ZERO gathers."""

    def _state(self, rng, B, HKV, D, PS, NP, MAXP, quantized=False):
        from paddle_tpu.ops.pallas.paged_attention import PagedCacheState

        if quantized:
            kp = jnp.asarray(rng.integers(-127, 128, (NP, PS, HKV * D)),
                             jnp.int8)
            vp = jnp.asarray(rng.integers(-127, 128, (NP, PS, HKV * D)),
                             jnp.int8)
            sc = (jnp.zeros((NP, PS, 128), jnp.bfloat16)
                  .at[..., :2 * HKV].set(jnp.asarray(
                      rng.random((NP, PS, 2 * HKV)) * 0.05 + 0.02,
                      jnp.bfloat16)))
        else:
            kp = jnp.asarray(rng.standard_normal((NP, PS, HKV * D)),
                             jnp.bfloat16)
            vp = jnp.asarray(rng.standard_normal((NP, PS, HKV * D)),
                             jnp.bfloat16)
            sc = None
        bt = np.zeros((B, MAXP), np.int32)
        pool = list(range(1, NP))
        for b in range(B):
            for j in range(MAXP):
                bt[b, j] = pool.pop(int(rng.integers(0, len(pool))))
        return PagedCacheState(kp, vp, sc, jnp.asarray(bt),
                               jnp.zeros((B,), jnp.int32), PS)

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("m", [5, 32])
    def test_kernel_matches_window_gather_ref(self, rng, m, quantized):
        from paddle_tpu.ops.pallas.paged_attention import (
            _interpret, _paged_multi_query_ref,
            paged_verify_slab_attention)

        assert not _interpret()
        B, H, HKV, D, PS, NP, MAXP = 8, 12, 4, 64, 16, 220, 24
        st = self._state(rng, B, HKV, D, PS, NP, MAXP,
                         quantized=quantized)
        base = jnp.asarray(rng.integers(0, MAXP * PS - m, (B,)), jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, m, H, D)), jnp.bfloat16)
        got = paged_verify_slab_attention(
            q, st.k_pages, st.v_pages, st.block_tables, base,
            scale_pages=st.scale_pages)
        want = _paged_multi_query_ref(q, st, base)
        assert _err(got, want) < 5e-2

    def test_kernel_llama_widths_several_windows(self, rng):
        """m=5 (spec verify's k+1) at llama2_7b() geometry: 4 windows."""
        from paddle_tpu.ops.pallas.paged_attention import (
            _paged_multi_query_ref, _window_pages,
            paged_verify_slab_attention)

        B, H, D, PS, NP, MAXP, m = 4, 32, 128, 16, 600, 128, 5
        assert _window_pages(MAXP, PS, H * D, 2) < MAXP
        st = self._state(rng, B, H, D, PS, NP, MAXP)
        base = jnp.asarray([0, 17, 1000, MAXP * PS - m], jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, m, H, D)), jnp.bfloat16)
        got = paged_verify_slab_attention(
            q, st.k_pages, st.v_pages, st.block_tables, base)
        want = _paged_multi_query_ref(q, st, base)
        assert _err(got, want) < 5e-2

    def test_verify_path_is_one_pallas_call_zero_gathers(self, rng):
        """On TPU `paged_multi_query_attention` (the entry spec verify,
        suffix prefill and chunked prefill all ride) must lower to ONE
        pallas_call and no XLA gather — the window-gather twin is gone
        from the hot path."""
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_multi_query_attention)

        B, H, HKV, D, PS, NP, MAXP = 4, 12, 4, 64, 16, 120, 8
        st = self._state(rng, B, HKV, D, PS, NP, MAXP)
        base = jnp.asarray([9, 0, 40, 100], jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, 5, H, D)), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda q, bl: paged_multi_query_attention(q, st, bl))(q, base)
        prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
        assert prims.count("pallas_call") == 1, prims
        assert "gather" not in prims, prims


class TestQuantMatmulOnChip:
    """Mosaic-lowered fused weight-only matmul vs the plain-XLA
    dequant-dot reference (a nibble-shift or epilogue lowering bug must
    surface here, not as a wrong decode bench number)."""

    @pytest.mark.parametrize("weight_dtype", ["int8", "int4"])
    @pytest.mark.parametrize("rows,k,n", [(8, 768, 3072), (1, 3072, 768),
                                          (8, 768, 2500)])
    def test_fused_matches_reference(self, rng, weight_dtype, rows, k, n):
        from paddle_tpu.ops.pallas.quant_matmul import (
            _interpret, quant_matmul_pallas, quant_matmul_ref)

        assert not _interpret()
        x = jnp.asarray(rng.standard_normal((rows, k)) * 0.3,
                        jnp.bfloat16)
        lim = 7 if weight_dtype == "int4" else 127
        q = rng.integers(-lim, lim + 1, (k, n)).astype(np.int8)
        if weight_dtype == "int4":
            q = np.bitwise_or(
                np.bitwise_and(q[0::2], np.int8(0x0F)),
                np.left_shift(q[1::2], 4).astype(np.int8)).astype(np.int8)
        sc = ((rng.random(n) + 0.1) / lim).astype(np.float32)
        b = rng.standard_normal(n).astype(np.float32)
        got = quant_matmul_pallas(x, q, sc, b, weight_dtype)
        want = quant_matmul_ref(x, q, sc, b, weight_dtype)
        # identical f32 accumulate both sides; daylight is the bf16 round
        assert _err(got, want) < 5e-2

    def test_weight_only_linear_routes_pallas_on_tpu(self, rng):
        from paddle_tpu.nn.quant import quant_backend

        assert quant_backend(rows=8) == "pallas"  # auto on TPU
