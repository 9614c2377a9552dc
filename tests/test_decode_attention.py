"""Decode attention + fused transformer + cached generation tests
(reference patterns: test/legacy_test/test_fused_multi_transformer_op.py —
fused op vs unfused composite to ~1e-3, incl. the cache decode path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention_pallas,
    decode_attention_ref,
)


def numpy_decode(q, kc, vc, lengths):
    b, h, d = q.shape
    out = np.zeros_like(q, dtype=np.float32)
    for bi in range(b):
        L = lengths[bi]
        for hi in range(h):
            s = (kc[bi, hi, :L] @ q[bi, hi]) / np.sqrt(d)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[bi, hi] = p @ vc[bi, hi, :L]
    return out


class TestDecodeKernel:
    @pytest.mark.parametrize("b,h,s,d", [(2, 4, 16, 32), (1, 2, 40, 64)])
    def test_pallas_interpret_matches_numpy(self, rng, b, h, s, d):
        q = rng.standard_normal((b, h, d)).astype(np.float32)
        kc = rng.standard_normal((b, h, s, d)).astype(np.float32)
        vc = rng.standard_normal((b, h, s, d)).astype(np.float32)
        lengths = rng.integers(1, s + 1, (b,)).astype(np.int32)
        got = np.asarray(decode_attention_pallas(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), lengths))
        want = numpy_decode(q, kc, vc, lengths)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_ref_matches_numpy_gqa(self, rng):
        b, h, hkv, s, d = 2, 8, 2, 12, 16
        q = rng.standard_normal((b, h, d)).astype(np.float32)
        kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        lengths = np.array([5, 12], np.int32)
        got = np.asarray(decode_attention_ref(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), lengths))
        want = numpy_decode(q, np.repeat(kc, h // hkv, 1),
                            np.repeat(vc, h // hkv, 1), lengths)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_pallas_interpret_gqa(self, rng):
        b, h, hkv, s, d = 1, 4, 2, 8, 16
        q = rng.standard_normal((b, h, d)).astype(np.float32)
        kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        lengths = np.array([8], np.int32)
        got = np.asarray(decode_attention_pallas(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), lengths))
        want = numpy_decode(q, np.repeat(kc, h // hkv, 1),
                            np.repeat(vc, h // hkv, 1), lengths)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestSlabKernel:
    """Slab-layout decode path (cache [2,B,S,Hkv*D]) — the serving-loop
    fast path; _slab_pallas exercised in interpret mode, plus the
    layout-polymorphic cache_decode_step dispatch."""

    # window_bytes: None = the sequence fits one window (the whole-resident
    # arithmetic); a small budget forces 16-token windows, so the softmax is
    # carried across 4 of them and windows past a short row are skipped
    @pytest.mark.parametrize("b,h,hkv,s,d,window_bytes", [
        (2, 4, 4, 16, 32, None), (1, 4, 2, 24, 64, None),
        (3, 4, 2, 64, 32, 16 * 2 * 2 * 64 * 4)])
    def test_slab_pallas_interpret(self, rng, monkeypatch, b, h, hkv, s, d,
                                   window_bytes):
        import importlib

        # the package re-exports a FUNCTION under the module's name
        mod = importlib.import_module(
            "paddle_tpu.ops.pallas.decode_attention")
        _slab_pallas = mod._slab_pallas
        if window_bytes is not None:
            monkeypatch.setattr(mod, "_WINDOW_BYTES", window_bytes)
            assert mod._slab_window(s, hkv * d, 4) == 16

        q = rng.standard_normal((b, h, d)).astype(np.float32)
        kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        lengths = rng.integers(1, s + 1, (b,)).astype(np.int32)
        slab = jnp.stack([
            jnp.swapaxes(jnp.asarray(kc), 1, 2).reshape(b, s, hkv * d),
            jnp.swapaxes(jnp.asarray(vc), 1, 2).reshape(b, s, hkv * d)])
        got = np.asarray(_slab_pallas(jnp.asarray(q), slab, lengths,
                                      1.0 / np.sqrt(d)))
        want = numpy_decode(q, np.repeat(kc, h // hkv, 1),
                            np.repeat(vc, h // hkv, 1), lengths)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_cache_decode_step_slab_vs_reference_layout(self, rng):
        """The 4-D slab path and the 5-D reference-layout path must produce
        identical outputs and equivalent cache contents."""
        from paddle_tpu.ops.pallas.decode_attention import (
            cache_decode_step, cache_prefill_write, make_kv_slab)

        b, nh, smax, hd = 2, 4, 12, 16
        k0 = jnp.asarray(rng.standard_normal((b, 5, nh, hd)), jnp.float32)
        v0 = jnp.asarray(rng.standard_normal((b, 5, nh, hd)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.float32)
        k1 = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.float32)
        v1 = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.float32)

        slab = cache_prefill_write(make_kv_slab(b, smax, nh, hd), k0, v0)
        ref5 = cache_prefill_write(
            jnp.zeros((2, b, nh, smax, hd), jnp.float32), k0, v0)
        out_s, slab = cache_decode_step(slab, q, k1, v1, 5)
        out_r, ref5 = cache_decode_step(ref5, q, k1, v1, 5)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        slab_as5 = slab.reshape(2, b, smax, nh, hd).transpose(0, 1, 3, 2, 4)
        np.testing.assert_allclose(np.asarray(slab_as5), np.asarray(ref5),
                                   rtol=1e-6, atol=1e-6)


class TestMaskedMHA:
    def test_functional_updates_cache_and_matches_ref(self, rng):
        from paddle_tpu.incubate.nn.functional import masked_multihead_attention

        b, nh, smax, hd = 2, 4, 16, 8
        H = nh * hd
        cache = rng.standard_normal((2, b, nh, smax, hd)).astype(np.float32)
        lens = np.array([3, 7], np.int32)
        # zero out invalid cache region for the numpy twin
        for bi in range(b):
            cache[:, bi, :, lens[bi]:] = 0.0
        x = rng.standard_normal((b, 3 * H)).astype(np.float32)
        out, new_cache = masked_multihead_attention(
            paddle.to_tensor(x), paddle.to_tensor(cache),
            sequence_lengths=paddle.to_tensor(lens))
        nc = new_cache.numpy()
        qkv = x.reshape(b, 3, nh, hd)
        # new token written at lens[b]
        for bi in range(b):
            np.testing.assert_allclose(nc[0, bi, :, lens[bi]], qkv[bi, 1], rtol=1e-6)
            np.testing.assert_allclose(nc[1, bi, :, lens[bi]], qkv[bi, 2], rtol=1e-6)
        want = numpy_decode(qkv[:, 0], nc[0], nc[1], lens + 1).reshape(b, H)
        np.testing.assert_allclose(out.numpy(), want, rtol=2e-5, atol=2e-5)


class TestFusedMultiTransformer:
    def _build(self, h=32, nh=4, ff=64, layers=2):
        from paddle_tpu.incubate.nn import FusedMultiTransformer

        return FusedMultiTransformer(h, nh, ff, num_layers=layers)

    def test_forward_matches_unfused_composite(self, rng):
        """Fused stack vs a per-op composite built from primitives (the
        reference's test strategy for fused_multi_transformer)."""
        import paddle_tpu.nn.functional as F

        m = self._build()
        m.eval()
        b, s, h = 2, 8, 32
        x = rng.standard_normal((b, s, h)).astype(np.float32)
        got = m(paddle.to_tensor(x)).numpy()

        # numpy/jnp composite twin
        xt = jnp.asarray(x)
        for i in range(m.num_layers):
            ln = F.layer_norm(Tensor._wrap(xt), [h], m.ln_scales[i], m.ln_biases[i],
                              m.epsilon)._data
            qkv = jnp.einsum("bsh,tndh->bstnd", ln, m.qkv_weights[i]._data)
            qkv = qkv + m.qkv_biases[i]._data
            q, k, v = (jnp.swapaxes(qkv[:, :, j], 1, 2) for j in range(3))
            lg = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(h // 4)
            mask = jnp.tril(jnp.ones((s, s), bool))
            lg = jnp.where(mask, lg, -jnp.inf)
            at = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(lg, -1), v)
            at = jnp.swapaxes(at, 1, 2).reshape(b, s, h)
            at = at @ m.linear_weights[i]._data + m.linear_biases[i]._data
            xt = xt + at
            ln2 = F.layer_norm(Tensor._wrap(xt), [h], m.ffn_ln_scales[i],
                               m.ffn_ln_biases[i], m.epsilon)._data
            ff_ = jax.nn.gelu(ln2 @ m.ffn1_weights[i]._data + m.ffn1_biases[i]._data,
                              approximate=True)
            xt = xt + (ff_ @ m.ffn2_weights[i]._data + m.ffn2_biases[i]._data)
        np.testing.assert_allclose(got, np.asarray(xt), rtol=2e-4, atol=2e-4)

    def test_cached_decode_matches_uncached_full_forward(self, rng):
        """context(prompt) + N decode steps == full forward on the whole
        sequence, position by position (the cache-correctness twin)."""
        m = self._build(layers=2)
        m.eval()
        b, prompt, new, h = 1, 4, 3, 32
        smax = prompt + new
        x = rng.standard_normal((b, smax, h)).astype(np.float32)

        # uncached: full causal forward
        full = m(paddle.to_tensor(x)).numpy()

        # cached: prefill then per-token decode
        caches = [paddle.to_tensor(np.zeros((2, b, 4, smax, 8), np.float32))
                  for _ in range(m.num_layers)]
        out_ctx, caches = m(paddle.to_tensor(x[:, :prompt]), caches=caches)
        np.testing.assert_allclose(out_ctx.numpy(), full[:, :prompt], rtol=2e-4, atol=2e-4)
        for t in range(prompt, smax):
            out_t, caches = m(paddle.to_tensor(x[:, t:t + 1]), caches=caches,
                              time_step=t)
            np.testing.assert_allclose(
                out_t.numpy()[:, 0], full[:, t], rtol=2e-4, atol=2e-4,
                err_msg=f"decode step {t}")


class TestGPTGenerate:
    @pytest.mark.slow  # tier-1 wall budget; still runs under make test
    def test_greedy_cache_matches_no_cache(self, rng):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, max_position=64)
        model = GPTForCausalLM(cfg)
        model.eval()
        ids = rng.integers(0, 128, (2, 5)).astype(np.int32)

        got = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             temperature=0.0).numpy()

        # no-cache greedy twin: full forward each step
        cur = ids.copy()
        for _ in range(6):
            logits = model(paddle.to_tensor(cur)).numpy()
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(got, cur)
