"""Packed-QKV causal flash kernel (ops/pallas/causal_flash.py) — the v2
train-path attention (VERDICT r2 #1 perf work). Twin-equivalence against
the naive reference and against the general kernel path through the GPT
model (reference capability: flash_attn_kernel.cu + the fused attention in
fused_multi_transformer_op.cu)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.causal_flash import causal_flash_qkv, supported


@pytest.fixture
def qkv(rng):
    B, H, S, D = 2, 3, 256, 64
    return jnp.asarray(rng.standard_normal((B, 3 * H, S, D)) * 0.3,
                       jnp.float32)


def _ref(qkv, H):
    S, D = qkv.shape[2], qkv.shape[3]
    q, k, v = qkv[:, :H], qkv[:, H:2 * H], qkv[:, 2 * H:]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _to_lanes(x, hpb):
    """[B, G*hpb, S, D] per-head -> [B, G, S, hpb*D] (heads paired along
    the lanes, as the packed QKV projection lays them out)."""
    B, GH, S, D = x.shape
    return x.reshape(B, GH // hpb, hpb, S, D).transpose(
        0, 1, 3, 2, 4).reshape(B, GH // hpb, S, hpb * D)


def _from_lanes(x, hpb):
    B, G, S, lanes = x.shape
    D = lanes // hpb
    return x.reshape(B, G, S, hpb, D).transpose(
        0, 1, 3, 2, 4).reshape(B, G * hpb, S, D)


class TestPackedKernel:
    def test_forward_matches_reference(self, qkv):
        out = causal_flash_qkv(qkv, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(qkv, 3)),
                                   atol=2e-6)

    def test_grads_match_reference(self, qkv, rng):
        ct = jnp.asarray(rng.standard_normal((2, 3, 256, 64)) * 0.1,
                         jnp.float32)
        g1 = jax.grad(lambda x: jnp.sum(causal_flash_qkv(x, 3) * ct))(qkv)
        g2 = jax.grad(lambda x: jnp.sum(_ref(x, 3) * ct))(qkv)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=5e-6)

    def test_supported_predicate(self):
        assert supported(1024, 64)
        assert not supported(1030, 64)  # not multiple of 8
        assert supported(2048, 64)      # tiled regime (VERDICT r3 #2)
        assert supported(8192, 64)
        assert not supported(2048 + 8, 64)   # tiled needs S % 512 == 0
        assert not supported(16384, 64)      # beyond tiled VMEM budget
        assert not supported(256, 96)   # head dim not MXU-native

    def test_row_regime_s1024_matches_reference(self, rng):
        """S=1024 routes to the whole-ROW forward (r5: it beats the
        whole-sequence square) paired with the whole-sequence program's
        backward, which walks only the live tiles of the causal square
        (PR 27) — the cross-regime composition must match naive
        attention exactly."""
        B, H, S, D = 1, 2, 1024, 64
        qkv = jnp.asarray(rng.standard_normal((B, 3 * H, S, D)) * 0.3,
                          jnp.float32)
        out = causal_flash_qkv(qkv, H)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(qkv, H)), atol=1e-5)
        ct = jnp.asarray(rng.standard_normal(out.shape) * 0.1, jnp.float32)
        g1 = jax.grad(lambda x: jnp.sum(causal_flash_qkv(x, H) * ct))(qkv)
        g2 = jax.grad(lambda x: jnp.sum(_ref(x, H) * ct))(qkv)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-5)

    def test_tiled_long_seq_matches_reference(self, rng):
        """S=2048 routes to the tiled causal-block-skip kernels (VERDICT
        r3 #2); fwd and the shared-p triangle backward must match naive
        attention."""
        B, H, S, D = 1, 2, 2048, 64
        qkv = jnp.asarray(rng.standard_normal((B, 3 * H, S, D)) * 0.3,
                          jnp.float32)
        out = causal_flash_qkv(qkv, H)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(qkv, H)), atol=1e-5)
        ct = jnp.asarray(rng.standard_normal(out.shape) * 0.1, jnp.float32)
        g1 = jax.grad(lambda x: jnp.sum(causal_flash_qkv(x, H) * ct))(qkv)
        g2 = jax.grad(lambda x: jnp.sum(_ref(x, H) * ct))(qkv)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-5)

    @pytest.mark.slow  # tier-1 wall budget; still runs under make test
    def test_tiled_pair_packed_long_seq(self, rng):
        """Pair-packed (hpb=2) layout through the tiled kernels at
        S=2048: forward + backward vs the per-head reference."""
        from paddle_tpu.ops.pallas.causal_flash import heads_per_block

        B, H, S, D = 1, 2, 2048, 64
        assert heads_per_block(H, D) == 2
        per_head = jnp.asarray(
            rng.standard_normal((B, 3 * H, S, D)) * 0.3, jnp.float32)
        paired = _to_lanes(per_head, 2)
        out = causal_flash_qkv(paired, H, D)
        want = _to_lanes(_ref(per_head, H), 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)
        ct = jnp.asarray(rng.standard_normal(out.shape) * 0.1, jnp.float32)
        g = jax.grad(lambda x: jnp.sum(causal_flash_qkv(x, H, D) * ct))(
            paired)
        # reference grad in the paired layout
        def ref_paired(x):
            return _to_lanes(_ref(_from_lanes(x, 2), H), 2)
        g2 = jax.grad(lambda x: jnp.sum(ref_paired(x) * ct))(paired)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g2),
                                   atol=2e-5)

    def test_pair_packed_matches_reference(self, rng):
        """hpb=2 lane pairing (D=64, even heads) must equal per-head attn."""
        from paddle_tpu.ops.pallas.causal_flash import heads_per_block

        B, H, S, D = 2, 4, 256, 64
        assert heads_per_block(H, D) == 2
        # heads laid out in pairs along the lane dim: [B, 3H/2, S, 128]
        per_head = jnp.asarray(
            rng.standard_normal((B, 3 * H, S, D)) * 0.3, jnp.float32)
        paired = _to_lanes(per_head, 2)
        out = causal_flash_qkv(paired, H, D)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_to_lanes(_ref(per_head, H), 2)),
                                   atol=2e-6)
        # grads through the pair-packed bwd
        ct = jnp.asarray(rng.standard_normal(out.shape) * 0.1, jnp.float32)
        g1 = jax.grad(
            lambda x: jnp.sum(causal_flash_qkv(x, H, D) * ct))(paired)
        g2 = jax.grad(lambda x: jnp.sum(
            _to_lanes(_ref(x, H), 2) * ct))(per_head)
        np.testing.assert_allclose(np.asarray(g1),
                                   np.asarray(_to_lanes(g2, 2)), atol=5e-6)


def _grad_gap(rng, S, D, H, dtype):
    """Largest |packed kernel's gradient - naive attention's| on one seeded
    input; the reference runs in f32 on the same (rounded) inputs."""
    from paddle_tpu.ops.pallas.causal_flash import heads_per_block

    hpb = heads_per_block(H, D)
    per_head = jnp.asarray(rng.standard_normal((1, 3 * H, S, D)) * 0.3,
                           dtype)
    ct = jnp.asarray(rng.standard_normal((1, H, S, D)) * 0.1, dtype)
    ct_lanes = _to_lanes(ct, hpb).astype(jnp.float32)
    got = jax.grad(lambda x: jnp.sum(
        causal_flash_qkv(x, H, D).astype(jnp.float32) * ct_lanes))(
            _to_lanes(per_head, hpb))
    want = jax.grad(lambda x: jnp.sum(_ref(x, H) * ct.astype(jnp.float32)))(
        per_head.astype(jnp.float32))
    return float(jnp.max(jnp.abs(
        _from_lanes(got, hpb).astype(jnp.float32) - want)))


class TestWholeSequenceBackward:
    """The S <= 1024 backward (``_bwd_kernel``): q-tiles of 256 rows, each
    its masked diagonal square and one unmasked rectangle over the k rows
    to its left; where S has no second tile, the whole square (n = 1)."""

    # f32: the tightest gradient tolerance of the file's older tests (both
    # kernels read under 3e-7 here). bf16: the parent commit's whole-square
    # kernel read 6.5e-4..9.8e-4 on these very inputs (interpret mode) and
    # this one the same to three digits; the limit is 1.5x the largest
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                           (jnp.bfloat16, 1.5e-3)],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("D,H", [(64, 2), (128, 1)],
                             ids=["d64-paired", "d128"])
    @pytest.mark.parametrize("S", [256, 512, 520, 1024])
    def test_grads_match_reference(self, rng, S, D, H, dtype, tol):
        assert _grad_gap(rng, S, D, H, dtype) <= tol

    @pytest.mark.parametrize("seq,blk,live,square", [
        (256, 256, 1, 1), (512, 256, 3, 4), (520, 520, 1, 1),
        (768, 256, 6, 9), (1016, 1016, 1, 1), (1024, 256, 10, 16)])
    def test_tile_table(self, seq, blk, live, square):
        from paddle_tpu.ops.pallas.causal_flash import _bwd_sq_blk

        assert _bwd_sq_blk(seq) == blk
        n = seq // blk
        assert (n * (n + 1) // 2, n * n) == (live, square)

    @pytest.mark.parametrize("S,D,H", [(1024, 64, 2), (1024, 128, 1),
                                       (512, 64, 2), (256, 64, 2),
                                       (520, 128, 1)])
    def test_the_skip_engages(self, S, D, H):
        """The mechanism's counter is static: the kernel body's jaxpr holds
        five products for the diagonal square of each of the n q-tiles and
        five for each rectangle to its left, 5 * hpb * (2n - 1), and
        together they execute n(n+1)/2 of the square's n*n tiles."""
        from paddle_tpu.ops.pallas import causal_flash as cf

        hpb = cf.heads_per_block(H, D)
        gh = H // hpb
        sds = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(
            lambda qkv, out, lse, do: cf._bwd(
                H, D, D ** -0.5, (qkv, out, lse), do))(
            sds((1, 3 * gh, S, hpb * D), jnp.bfloat16),
            sds((1, gh, S, hpb * D), jnp.bfloat16),
            sds((1, gh, S, hpb), jnp.float32),
            sds((1, gh, S, hpb * D), jnp.bfloat16))
        (call,) = [e for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        dots = [e for e in call.params["jaxpr"].eqns
                if e.primitive.name == "dot_general"]
        n = S // cf._bwd_sq_blk(S)
        assert len(dots) == 5 * hpb * (2 * n - 1)
        flops = 0
        for e in dots:
            (lhs_c, _), _ = e.params["dimension_numbers"]
            k = e.invars[0].aval.shape[lhs_c[0]]
            m, w = e.outvars[0].aval.shape
            flops += 2 * m * w * k
        square = 5 * hpb * 2 * S * S * D
        assert flops * 2 * n == square * (n + 1)


class TestPackedInModel:
    @pytest.mark.parametrize("hidden,heads", [(128, 2),   # hpb=2 pairing
                                              (192, 3)])  # hpb=1 (odd heads)
    @pytest.mark.slow  # tier-1 wall budget; still runs under make test
    def test_gpt_train_step_equivalence(self, rng, hidden, heads):
        """Forcing the packed path must not change loss or grads vs the
        general kernel path (twin equivalence at f32)."""
        import paddle_tpu as paddle
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(hidden_size=hidden, num_layers=2, num_heads=heads,
                        max_position=256, vocab_size=128)
        model = GPTForCausalLM(cfg)
        model.eval()
        ids = paddle.to_tensor(
            jnp.asarray(rng.integers(0, 128, (2, 256)), jnp.int32))
        labels = paddle.to_tensor(
            jnp.asarray(rng.integers(0, 128, (2, 256)), jnp.int32))

        def loss_and_grads():
            loss = model.loss(ids, labels)
            loss.backward()
            gs = {n: np.asarray(p.grad._data) for n, p in
                  model.named_parameters() if p.grad is not None}
            for p in model.parameters():
                p.clear_grad()
            return float(np.asarray(loss._data)), gs

        set_flags({"FLAGS_use_packed_attention": False})
        try:
            l0, g0 = loss_and_grads()
            set_flags({"FLAGS_use_packed_attention": True})
            l1, g1 = loss_and_grads()
        finally:
            set_flags({"FLAGS_use_packed_attention": None})
        assert np.isfinite(l0) and abs(l0 - l1) < 1e-4, (l0, l1)
        assert g0.keys() == g1.keys() and len(g0) > 0
        for name in g0:
            np.testing.assert_allclose(g0[name], g1[name], atol=2e-3,
                                       rtol=2e-3, err_msg=name)


class TestPackedUnderFleetMesh:
    """A Mosaic kernel cannot be partitioned by GSPMD: under the mesh fleet
    sets, the packed path runs the kernel once per shard (batch over dp,
    head groups over mp — ops/pallas/sharded.py). Same loss and gradients
    as the plain call; the compile for the chip is tests/test_chip_compile."""

    def test_gpt_loss_and_grads_match_the_unsharded_step(self, rng,
                                                         monkeypatch):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.distributed import parallel
        from paddle_tpu.distributed.topology import build_mesh
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.framework.tensor import Tensor
        from paddle_tpu.jit import functional_call, param_arrays
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        # 4 heads of 64 pair-pack into 2 groups: mp=2 gives each shard one
        cfg = GPTConfig(hidden_size=256, num_layers=1, num_heads=4,
                        max_position=128, vocab_size=128)
        model = GPTForCausalLM(cfg)
        model.eval()
        params = param_arrays(model)
        ids = jnp.asarray(rng.integers(0, 128, (4, 128)), jnp.int32)

        def loss_fn(p, ids):
            logits = functional_call(model, p, Tensor._wrap(ids))
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1)
                            - logits[..., 0])

        # the mesh is process-global (an earlier test's fleet.init leaves
        # one behind): no mesh for the plain step, then dp2 x mp2
        monkeypatch.setattr(parallel, "_global_mesh", None)
        set_flags({"FLAGS_use_packed_attention": True})
        try:
            l0, g0 = jax.jit(jax.value_and_grad(loss_fn))(params, ids)
            mesh = build_mesh(dp=2, mp=2)
            monkeypatch.setattr(parallel, "_global_mesh", mesh)
            assert parallel.mesh_if_set() is mesh
            jaxpr = str(jax.make_jaxpr(loss_fn)(params, ids))
            assert "shard_map" in jaxpr and "pallas_call" in jaxpr
            with mesh:
                l1, g1 = jax.jit(jax.value_and_grad(loss_fn))(
                    params, jax.device_put(
                        ids, NamedSharding(mesh, P("dp", None))))
        finally:
            set_flags({"FLAGS_use_packed_attention": None})
        assert abs(float(l0) - float(l1)) < 1e-5, (l0, l1)
        for name in g0:
            np.testing.assert_allclose(np.asarray(g0[name]),
                                       np.asarray(g1[name]), atol=1e-5,
                                       rtol=1e-4, err_msg=name)
