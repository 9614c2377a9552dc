"""Memory-correctness suite (VERDICT r1 #9 / SURVEY.md §5.2's prescribed
substitute for sanitizers): ZeRO-3 per-device footprint verified from real
array shards and compiled-program memory analysis — "via PJRT stats, not
hope" — plus donation correctness for the buffer-aliasing paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.parallel import set_mesh
from paddle_tpu.distributed.sharding import group_sharded_parallel
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import functional_call, param_arrays


def make_mlp(seed=0):
    paddle.seed(seed)
    return nn.Sequential(
        nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 64), nn.ReLU(),
        nn.Linear(64, 8),
    )


class TestZeRO3Footprint:
    def test_param_shard_bytes_are_fractional(self):
        """ZeRO-3 (p_g_os): each device must HOLD 1/N of every divisible
        parameter — checked on the actual array shards, not the spec."""
        devs = np.array(jax.devices()[:8]).reshape(1, 8)
        mesh = Mesh(devs, ("dp", "sharding"))
        set_mesh(mesh)
        try:
            model = make_mlp()
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=model.parameters())
            model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
            total = sharded = 0
            for name, p in model.named_parameters():
                n_bytes = p._data.nbytes
                shard = p._data.addressable_shards[0].data.nbytes
                total += n_bytes
                sharded += shard
                if "weight" in name:  # divisible dims in this MLP
                    assert shard * 8 == n_bytes, (name, shard, n_bytes)
            # whole-model per-device high water ≤ ~1/4 of replicated (biases
            # may stay replicated)
            assert sharded <= total / 4
        finally:
            set_mesh(None)

    def test_compiled_argument_bytes_shrink(self):
        """The compiled train step's per-device argument bytes under ZeRO-3
        must be a fraction of the replicated run's (compile-time memory
        analysis = the CPU-mesh stand-in for on-chip PJRT stats)."""
        devs = np.array(jax.devices()[:8]).reshape(1, 8)
        mesh = Mesh(devs, ("dp", "sharding"))
        x = jnp.ones((8, 16), jnp.float32)

        def build(shard):
            set_mesh(mesh if shard else None)
            try:
                model = make_mlp()
                if shard:
                    opt = paddle.optimizer.AdamW(
                        learning_rate=1e-3, parameters=model.parameters())
                    model, opt, _ = group_sharded_parallel(
                        model, opt, "p_g_os")
                params = param_arrays(model)

                def loss(p, xb):
                    out = functional_call(
                        model._layers if shard else model, p,
                        Tensor._wrap(xb))
                    return jnp.mean(out ** 2)

                c = jax.jit(jax.grad(loss)).lower(params, x).compile()
                return c.memory_analysis().argument_size_in_bytes
            finally:
                set_mesh(None)

        replicated = build(False)
        sharded = build(True)
        assert sharded < replicated / 2, (sharded, replicated)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="PJRT memory stats need a real device")
class TestPJRTMemoryStats:
    def test_high_water_readout(self):
        from paddle_tpu import device_ns

        base = device_ns.max_memory_allocated()
        big = jnp.ones((1024, 1024), jnp.float32) + 0
        big.block_until_ready()
        assert device_ns.max_memory_allocated() >= base


class TestDonationCorrectness:
    def test_donated_input_deleted_and_result_exact(self):
        @jax.jit
        def ref(p, g):
            return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

        import functools

        @functools.partial(jax.jit, donate_argnums=(0,))
        def donating(p, g):
            return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

        p1 = {"w": jnp.arange(8.0), "b": jnp.ones((4,))}
        p2 = {k: v + 0 for k, v in p1.items()}
        g = {"w": jnp.full((8,), 2.0), "b": jnp.full((4,), 3.0)}
        out_ref = ref(p1, g)
        out_don = donating(p2, g)
        for k in p1:
            np.testing.assert_array_equal(np.asarray(out_ref[k]),
                                          np.asarray(out_don[k]))
            assert p2[k].is_deleted(), k  # buffer actually reused

    def test_donated_sharded_update_matches(self):
        """Donation composes with sharding: a ZeRO-style sharded param tree
        updated with donation equals the non-donated update."""
        import functools

        devs = np.array(jax.devices()[:8])
        mesh = Mesh(devs, ("sharding",))
        sh = NamedSharding(mesh, P("sharding"))
        p = jax.device_put(jnp.arange(64.0), sh)
        g = jax.device_put(jnp.ones((64,)), sh)
        expect = np.asarray(p) - 0.5

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(p, g):
            return p - 0.5 * g

        out = step(p, g)
        assert p.is_deleted()
        np.testing.assert_array_equal(np.asarray(out), expect)
        assert out.sharding == sh

    def test_generate_twice_same_tokens(self):
        """The compiled decode path donates its caches (models/gpt.py);
        repeated generation from the same prompt must be identical — donated
        buffers must never leak state across calls."""
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=4,
                        max_position=64, vocab_size=128)
        paddle.seed(7)
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(
            np.asarray(rng.integers(0, 128, (2, 8)), np.int32))
        a = model.generate(ids, max_new_tokens=6, temperature=0.0)
        b = model.generate(ids, max_new_tokens=6, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(a.numpy()),
                                      np.asarray(b.numpy()))


@pytest.fixture
def fresh_cache_state(monkeypatch):
    """enable_compilation_cache() is process-global: hand each test a
    not-yet-enabled module and put jax's settings back afterwards, so the
    rest of this worker's tests do not run against a persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from paddle_tpu.framework import compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    yield compile_cache
    for n, v in saved.items():
        jax.config.update(n, v)
    jcc.reset_cache()


class TestCompilationCache:
    def test_env_var_set_no_directory_set_in_code(self, fresh_cache_state,
                                                  monkeypatch, tmp_path):
        """Where JAX_COMPILATION_CACHE_DIR is set, jax's own handling of
        it decides: the program names no directory."""
        cc = fresh_cache_state
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "outside"))
        updated = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, val: (updated.append(name),
                               real_update(name, val))[1])
        d = cc.enable_compilation_cache()
        assert "jax_compilation_cache_dir" not in updated, updated
        assert d == jax.config.jax_compilation_cache_dir
        assert cc.compilation_cache_dir() == d
        assert cc.maybe_enable_from_env() == d

    def test_env_var_unset_fixed_path_in_checkout(self, fresh_cache_state,
                                                  monkeypatch):
        import os

        cc = fresh_cache_state
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        assert cc.maybe_enable_from_env() is None
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        d = cc.enable_compilation_cache()
        assert d == cc.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == d
        assert cc.enable_compilation_cache() == d  # idempotent
        f = jax.jit(lambda x: x * 3 + 1)
        f(jnp.arange(17.0)).block_until_ready()
        assert os.listdir(d), "compilation cache not populated"

    def test_supervisor_exports_cache_env(self, tmp_path, monkeypatch):
        from paddle_tpu.distributed.launch.controllers import (
            ElasticSupervisor)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        sup = ElasticSupervisor(lambda r: ["true"], 1, ["127.0.0.1:0"],
                                log_dir=str(tmp_path))
        assert sup.compile_cache_dir == str(tmp_path / "xla_cache")
        # a cache placed from outside wins over the next-to-the-logs default
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        sup = ElasticSupervisor(lambda r: ["true"], 1, ["127.0.0.1:0"],
                                log_dir=str(tmp_path))
        assert sup.compile_cache_dir == "/placed/outside"
