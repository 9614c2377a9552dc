"""Nemotron-H (``paddle_tpu.models.nemotron_h``) against the plain reference
(``benchmarks/reference/nemotron_h.py``: float32, the recurrence over
positions, experts as a masked loop) on seeded weights at a small size.

Tolerances. ``F32``: program and reference both in float32 on the CPU; they
differ in the order of sums only (chunked products against a recurrence, a
sorted buffer against a masked loop): the worst leaf's gradient read 4e-7
(attention), 7e-7 (experts) to 1.1e-5 (``A_log``, a sum over every position
of a layer) of its largest value, the limit stands at 1e-4; the planted
faults read 0.7 to 3.4. ``BF16_*``: the compiled step with bfloat16 leaves
against the float32 reference, by the benchmark's own numbers (gap of norms
by leaf block): bfloat16 keeps 8 bits, so a product's operands carry 2e-3 of
rounding and a norm over a block averages it down the more the larger the
block. Read on two seeds and two cuts: losses to 4.3e-5, gradient norms
0.003 to 0.014 (the worst block is ``D``, 2 numbers at this size), update
norms 0.003 to 0.010; the limits stand at three to five times that.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import nemotron_h as builder
from benchmarks.reference import nemotron_h as ref
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import functional_call, param_arrays
from paddle_tpu.models import nemotron_h as nh

F32 = 1e-4
BF16_LOSS, BF16_GRAD, BF16_UPDATE = 2e-4, 0.04, 0.03
SEED = 2**31 + 7
IDENT = lambda x: x
# one chunk, several chunks, and a length that is no multiple of the chunk
SEQS = pytest.mark.parametrize("seq", [16, 48, 40],
                               ids=["one-chunk", "three-chunks", "ragged"])

WHOLE = {
    "reference": "nemotron_h", "builder": "nemotron_h",
    "hidden_size": 64, "hybrid_override_pattern": "MEMEMEMEM*E",
    "layer_norm_epsilon": 1e-5, "vocab_size": 128,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 4,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 5,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "routed_scaling_factor": 5.0,
    "held": {"first_expert": 0, "shared_expert_columns": 96,
             "local_pairs_bound": 16.0},
    "dtype": "float32",
}
WHOLE["published"] = {k: WHOLE[k] for k in (
    "vocab_size", "mamba_num_heads", "n_groups", "num_attention_heads",
    "num_key_value_heads", "n_routed_experts")}


def config(pattern=None, **changes):
    cfg = copy.deepcopy(WHOLE)
    held = {k: changes.pop(k) for k in list(changes) if k in cfg["held"]}
    cfg.update(changes, held=dict(cfg["held"], **held))
    if pattern:
        cfg["hybrid_override_pattern"] = pattern
    return cfg


def share(pattern="MEMEMEMEM*E"):
    """A share as the cell cuts it: a quarter of the Mamba heads with one
    group, one query head with its key/value head, 4 of 16 experts from the
    fifth on, half the shared expert, a quarter of the vocabulary."""
    return config(pattern, vocab_size=32, mamba_num_heads=2, n_groups=1,
                  num_attention_heads=1, num_key_value_heads=1,
                  n_routed_experts=4, first_expert=4,
                  shared_expert_columns=48, local_pairs_bound=4.0)


def ids_of(cfg, batch, seq, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq)), jnp.int32)


def close(got, want, tol, what=""):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    gap = float(jnp.max(jnp.abs(got - want))) / scale
    assert gap <= tol, f"{what}: gap {gap:.3e} over {tol:.1e}"
    return gap


def program_loss_and_grads(cfg, params, ids, labels):
    model = nh.NemotronHForCausalLM(builder.model_config(cfg))

    def loss(p):
        logits = functional_call(model, p, Tensor._wrap(ids))
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(logz - gold.astype(jnp.float32))

    have = param_arrays(model)
    assert {k: v.shape for k, v in have.items()} == \
        {k: v.shape for k, v in params.items()}
    return jax.jit(jax.value_and_grad(loss))(params)


def reference_loss_and_grads(cfg, params, ids, labels):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, ids, labels, cfg, IDENT)))(params)


def agree(cfg, params, seq, tol=F32, batch=2):
    ids, labels = ids_of(cfg, batch, seq), ids_of(cfg, batch, seq, seed=1)
    loss, grads = program_loss_and_grads(cfg, params, ids, labels)
    want, want_grads = reference_loss_and_grads(cfg, params, ids, labels)
    assert abs(float(loss) - float(want)) <= tol * abs(float(want))
    for k in want_grads:
        close(grads[k], want_grads[k], tol, k)


@SEQS
@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_mixer_alone_loss_and_gradients(kind, seq):
    cfg = config(kind)
    agree(cfg, ref.initial_params(cfg, SEED, jnp.float32), seq)


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_mixers_share_alone_loss_and_gradients(kind):
    cfg = share(kind)
    agree(cfg, ref.initial_params(cfg, SEED, jnp.float32), 40)


@pytest.mark.parametrize("cut,seq", [(config, 48), (share, 40)],
                         ids=["whole-three-chunks", "share-ragged"])
def test_eleven_layers_loss_and_gradients(cut, seq):
    cfg = cut()
    agree(cfg, ref.initial_params(cfg, SEED, jnp.float32), seq)


@SEQS
def test_five_layers_of_every_kind_loss_and_gradients(seq):
    cfg = share("MEM*E")
    agree(cfg, ref.initial_params(cfg, SEED, jnp.float32), seq)


def test_logits_forward():
    cfg = share("MEM*E")
    params = ref.initial_params(cfg, SEED, jnp.float32)
    ids = ids_of(cfg, 2, 40)
    model = nh.NemotronHForCausalLM(builder.model_config(cfg))
    got = jax.jit(lambda p: functional_call(model, p, Tensor._wrap(ids)))(
        params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(p, ids, cfg, IDENT))(params)
    assert got.shape == (2, 40, 32)
    close(got, want, F32, "logits")


ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
        "weight_decay": 0.01}


@pytest.mark.parametrize("cut", [lambda: config("MEM*E"), share],
                         ids=["whole-five-layers", "share-eleven-layers"])
def test_three_adamw_steps_bf16_through_the_benchmarks_step(cut):
    """The compiled step the cell runs (``functional_call`` +
    ``AdamW.apply_gradients_tree``, bfloat16 leaves, float32 master) against
    the reference's three steps, by the cell's own numbers."""
    from benchmarks.drivers import train_steps as drv

    cfg = dict(cut(), dtype="bfloat16")
    traffic = {"batch": 4, "seq": 40, "optimizer": ADAM}
    step, params, state = drv.build_program(cfg, traffic, SEED)
    got = {"losses": []}
    for i in (1, 2, 3):
        x, y = drv.feed(cfg, traffic, SEED, i)
        params, state, loss = step(params, state, x, y, jnp.float32(i))
        got["losses"].append(float(loss))
        if i == 1:
            got["grad_norms"] = drv._moment_norms(state, 0.9, 1)
    got["update_norms"] = drv._update_norms(params, state, SEED, 1)
    want = ref.train_readings(
        cfg, SEED, [drv.feed(cfg, traffic, SEED, i) for i in (1, 2, 3)],
        traffic["optimizer"], 1)
    read = drv.numbers(got, want)
    assert max(read[f"loss{i}_gap"] for i in (1, 2, 3)) <= BF16_LOSS
    assert read["grad_norm_gap"] <= BF16_GRAD
    assert read["update_norm_gap"] <= BF16_UPDATE


def test_reference_reads_its_master_through_the_stated_type():
    """A bfloat16 model's leaves are its float32 master ROUNDED: the seed's
    leaves lie on the bfloat16 grid and AdamW's first step moves each by
    ``lr``, which for the larger elements is 1.2 or 2.5 grid spacings, so
    the leaves move by less than the master. The reference's second loss is
    the loss at the rounded master, as the configuration states the type,
    and not the loss at the master (at the cell's size the two differ by
    3e-4 on every seed, PERF.md section 6, PR 28)."""
    from benchmarks.drivers import train_steps as drv
    from benchmarks.reference.gpt2 import adamw

    cfg = dict(config("ME"), dtype="bfloat16")
    traffic = {"batch": 2, "seq": 32}
    (x1, y1), (x2, y2) = (drv.feed(cfg, traffic, SEED, i) for i in (1, 2))
    read = ref.train_readings(cfg, SEED, [(x1, y1), (x2, y2)], ADAM, 1)
    with jax.default_matmul_precision("highest"):
        loss = lambda p, x, y: ref.loss_fn(p, jnp.asarray(x), jnp.asarray(y),
                                           cfg, IDENT)
        seeded = ref.initial_params(cfg, SEED, jnp.bfloat16)
        first, grads = jax.value_and_grad(loss)(seeded, x1, y1)
        master, _ = adamw(seeded, grads, {k: (0.0, 0.0) for k in seeded},
                          1.0, ADAM)
        rounded = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                   for k, v in master.items()}
        at_leaves, at_master = (float(loss(p, x2, y2))
                                for p in (rounded, master))
    assert read["losses"][0] == pytest.approx(float(first), rel=1e-6)
    assert read["losses"][1] == pytest.approx(at_leaves, rel=1e-6)
    assert abs(at_master - at_leaves) > 20 * abs(read["losses"][1] - at_leaves)


# ------------------------------------------------------ the attention kernel


def test_attention_through_the_packed_kernel_at_d128(monkeypatch):
    """4 query heads reading one key/value head through ``causal_flash_qkv``
    at D = 128 (interpret mode here), against the plain softmax path."""
    from paddle_tpu.framework import flags

    cfg = nh.NemotronHConfig(hidden_size=64, num_attention_heads=8,
                             num_key_value_heads=2, head_dim=128,
                             q_heads_held=4, kv_heads_held=1)
    layer = nh.NemotronHAttention(cfg)
    u = Tensor._wrap(jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64)))
    plain = layer(u)._data
    monkeypatch.setitem(flags._REGISTRY, "FLAGS_use_packed_attention", True)
    packed = layer(u)._data
    close(packed, plain, 2e-5, "kernel against softmax")


def test_config_refuses_what_does_not_divide():
    with pytest.raises(ValueError):
        nh.NemotronHConfig(pattern="MXE")
    with pytest.raises(ValueError):
        nh.NemotronHConfig(mamba_heads_held=16, mamba_groups_held=3)
    with pytest.raises(ValueError):
        nh.NemotronHConfig(q_heads_held=4, kv_heads_held=3)
    with pytest.raises(ValueError):
        nh.NemotronHConfig(experts_held=8, first_expert=510)
