"""Phi-4-flash (``paddle_tpu.models.phi4flash``) against the plain reference
(``benchmarks/reference/phi4flash.py``: float32, explicit masks, the scan a
``lax.scan`` over positions, ``(a_1 - lambda a_2) V`` as written) on seeded
weights at a small size.

Tolerances. ``F32``: program and reference both in float32 on the CPU; they
differ in the order of sums only (two softmaxes times V subtracted against
the subtracted softmaxes times V, heads laid out head-major against a
reshape, the chunked scan): the worst leaf's gradient read 3e-6 of its
largest value, the limit stands at 1e-4; a wrong window edge, head-to-pair
map, lambda or a memory taken after the gate reads 1e-2 and more.
``BF16_*``: the compiled step with bfloat16 leaves against the float32
reference, by the benchmark's own numbers (gap of norms by leaf block),
limits as ``tests/test_nemotron_h.py`` sets them.
"""
import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_nemotron_h import (ADAM, BF16_GRAD, BF16_LOSS, BF16_UPDATE, F32,
                             IDENT, close, ids_of)

from benchmarks.builders import phi4flash as builder
from benchmarks.reference import phi4flash as ref
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import functional_call, param_arrays
from paddle_tpu.models import phi4flash as pf

SEED = 2**31 + 35
# 8 query heads (4 pairs) on 4 key/value heads (2 pairs) of 8: two query
# pairs a key/value pair, as the source's 40 on 20
WHOLE = {
    "reference": "phi4flash", "builder": "phi4flash",
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 8,
    "sliding_window": 8, "layer_norm_eps": 1e-5, "vocab_size": 128,
    "num_hidden_layers": 8, "layer_pattern": "MSMSMFGC",
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4,
    "held": {"layers": [0, 1, 2, 3, 16, 17, 18, 19], "scan_channels": 128,
             "mlp_columns": 96},
    "dtype": "float32",
}
WHOLE["published"] = {k: WHOLE[k] for k in (
    "vocab_size", "num_attention_heads", "num_key_value_heads")}


def config(pattern=None, **changes):
    """WHOLE with ``pattern`` (published indices 0, 1, ... unless given) and
    ``changes`` (keys of ``held`` go there)."""
    cfg = copy.deepcopy(WHOLE)
    held = {k: changes.pop(k) for k in list(changes) if k in cfg["held"]}
    cfg.update(changes, held=dict(cfg["held"], **held))
    if pattern:
        cfg["layer_pattern"] = pattern
        cfg["num_hidden_layers"] = len(pattern)
        if "layers" not in held:
            cfg["held"]["layers"] = list(range(len(pattern)))
    return cfg


def share(pattern=None, **changes):
    """A share as the cell cuts it: one of the two key/value pairs with the
    two query pairs that read it, half the scan's channels and the MLP's
    columns, a quarter of the vocabulary."""
    return config(pattern, vocab_size=32, num_attention_heads=4,
                  num_key_value_heads=2, scan_channels=64, mlp_columns=48,
                  **changes)


def loss_of(logits, labels):
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold.astype(jnp.float32))


def program_loss_and_grads(cfg, params, ids, labels):
    model = pf.Phi4FlashForCausalLM(builder.model_config(cfg))
    have = param_arrays(model)
    assert {k: v.shape for k, v in have.items()} == \
        {k: v.shape for k, v in params.items()}
    return jax.jit(jax.value_and_grad(lambda p: loss_of(
        functional_call(model, p, Tensor._wrap(ids)), labels)))(params)


def reference_loss_and_grads(cfg, params, ids, labels):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, ids, labels, cfg, IDENT)))(params)


def seeded(cfg):
    """The seed's leaves with the scan's small projections scaled up for
    the test's widths: at 64 wide, rank 4 and std 0.02 the state would stay
    near nought and ``delta`` would not move with its input, so the
    recurrence would hide behind ``D x``."""
    params = ref.initial_params(cfg, SEED, jnp.float32)
    wider = {"x_proj.weight": 20.0, "dt_proj.weight": 20.0}
    wider["mamba.in_proj.weight"] = 6.0   # x of order one, as at 2560 wide
    return {k: v * next((f for end, f in wider.items() if k.endswith(end)),
                        1.0) for k, v in params.items()}


def agree(cfg, seq=40, tol=F32, batch=2):
    params = seeded(cfg)
    ids, labels = ids_of(cfg, batch, seq), ids_of(cfg, batch, seq, seed=1)
    loss, grads = program_loss_and_grads(cfg, params, ids, labels)
    want, want_grads = reference_loss_and_grads(cfg, params, ids, labels)
    assert abs(float(loss) - float(want)) <= tol * abs(float(want))
    leaves_close(grads, want_grads, tol)


def leaves_close(grads, want_grads, tol):
    for k in want_grads:
        if k.endswith("k_proj.bias"):
            # a constant added to every key moves no softmax: the gradient
            # is nought in the mathematics and rounding on both sides
            assert float(jnp.max(jnp.abs(grads[k]))) < 1e-6, k
            continue
        # lambda's gradient is ONE number (dL / d lambda, a sum over every
        # position of terms of both signs) times the other vector: the sum's
        # rounding is the whole leaf's; it read to 1.2e-4 at five positions
        close(grads[k], want_grads[k], 10 * tol if "lambda_" in k else tol, k)


# each kind alone; G and C behind the layer whose output they read
KINDS = pytest.mark.parametrize("pattern", ["M", "S", "F", "MG", "FC"])


@KINDS
def test_each_layer_kind_loss_and_gradients(pattern):
    agree(config(pattern))


@KINDS
def test_each_layer_kinds_share_loss_and_gradients(pattern):
    agree(share(pattern))


@pytest.mark.parametrize("cut", [config, share], ids=["uncut", "share"])
def test_eight_layers_loss_and_gradients(cut):
    agree(cut())


@pytest.mark.parametrize("seq", [5, 8, 9, 24],
                         ids=["inside-window", "window", "one-past", "three"])
def test_window_edge_at_every_length(seq):
    """Rows with fewer keys than the window, exactly the window, one more."""
    agree(config("S"), seq=seq)


def test_lambda_init_follows_the_published_index():
    """The cut keeps layers 16-19: their constants are the published
    layers', not those of layers 4-7."""
    assert pf.lambda_init(0) == pytest.approx(0.2)
    assert pf.lambda_init(17) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))
    assert ref.lambda_init(17) == pf.lambda_init(17)
    model = pf.Phi4FlashForCausalLM(builder.model_config(config()))
    got = [getattr(b, b.mixer_key).lambda_init
           for b in model.model.layers if b.kind in "SFC"]
    assert got == [pf.lambda_init(i) for i in (1, 3, 17, 19)]
    # and the loss sees it: the same layers under other indices differ
    cfg, ids = config("FC"), ids_of(WHOLE, 2, 24)
    moved = config("FC", layers=[17, 19])
    params = seeded(cfg)
    a, _ = program_loss_and_grads(cfg, params, ids, ids)
    b, _ = program_loss_and_grads(moved, params, ids, ids)
    assert abs(float(a) - float(b)) > 1e-4
    agree(moved)


def test_logits_forward():
    for cfg in (config(), share()):
        params = seeded(cfg)
        ids = ids_of(cfg, 2, 40)
        model = pf.Phi4FlashForCausalLM(builder.model_config(cfg))
        got = jax.jit(lambda p: functional_call(
            model, p, Tensor._wrap(ids)))(params)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda p: ref.forward(p, ids, cfg, IDENT))(params)
        assert got.shape == (2, 40, cfg["vocab_size"])
        close(got, want, F32, "logits")


# --------------------------------------------------- differential attention


def two_softmaxes(q, k, v, lam, window):
    """The formula, head by head in a Python loop: q ``[s, hq, d]``, k, v
    ``[s, hk, d]``; returns ``[s, hq / 2, 2 d]`` before the norm."""
    s, hq, d = q.shape
    g = hq // k.shape[1]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    out = []
    for p in range(hq // 2):
        kp = p // g                       # the key/value pair this pair reads
        a = [jax.nn.softmax(jnp.where(
            seen, q[:, 2 * p + t] @ k[:, 2 * kp + t].T / math.sqrt(d),
            -jnp.inf), -1) for t in (0, 1)]
        out.append((a[0] - lam * a[1])
                   @ jnp.concatenate([v[:, 2 * kp], v[:, 2 * kp + 1]], -1))
    return jnp.stack(out, 1)


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
def test_differential_attention_against_the_two_softmax_formula(window):
    """``softmax_heads`` + ``differential`` on 8 query heads over 4 key/value
    heads (two query pairs a key/value pair), lambda from its four vectors,
    the pair's RMSNorm and ``1 - lambda_init``."""
    s, hq, hk, d = 24, 8, 4, 8
    rng = jax.random.split(jax.random.PRNGKey(2), 6)
    q, k, v = (jax.random.normal(r, (s, h, d))
               for r, h in zip(rng, (hq, hk, hk)))
    lq, lk = (0.3 * jax.random.normal(r, (2, d)) for r in rng[3:5])
    w = 1 + 0.1 * jax.random.normal(rng[5], (2 * d,))
    init = pf.lambda_init(3)
    lam = (math.exp(float(lq[0] @ lk[0])) - math.exp(float(lq[1] @ lk[1]))
           + init)
    o = two_softmaxes(q, k, v, lam, window)
    want = (o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * w
            * (1 - init)).reshape(s, hq * d)
    heads = pf.softmax_heads(q.transpose(1, 0, 2)[None],
                             k.reshape(1, s, hk * d), v.reshape(1, s, hk * d),
                             window)
    got = pf.differential(heads, lq, lk, w, init, 1e-5)[0]
    close(got, want, 1e-5, "differential attention")


def test_window_edge_at_511_and_512():
    """At the published window: query 515 sees key 4 (i - j = 511) and not
    key 3 (i - j = 512)."""
    s, d = 520, 8
    rng = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(rng[0], (1, 2, s, d))
    k, v = (jax.random.normal(r, (1, s, 2 * d)) for r in rng[1:])
    at = lambda v: pf.softmax_heads(q, k, v, 512)[0, :, 515]
    base = at(v)
    assert jnp.array_equal(at(v.at[0, 3].add(1.0)), base)
    assert float(jnp.max(jnp.abs(at(v.at[0, 4].add(1.0)) - base))) > 1e-4
    assert jnp.array_equal(at(v.at[0, 516].add(1.0)), base)      # causal


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window"])
def test_attention_through_the_packed_kernels_padded_to_128(window,
                                                            monkeypatch):
    """Heads of 64 under value pairs of 128 through ``causal_flash_qkv`` at
    head_dim 128 (q and k zero-padded, q times sqrt 2), S = 1024: the tiled
    causal kernels and the band regime in interpret mode, against the masked
    softmax path, value and the gradient of q, k and v."""
    from paddle_tpu.framework import flags

    s, d = 1024, 64
    rng = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(rng[0], (1, 4, s, d))
    k, v = (jax.random.normal(r, (1, s, 2 * d)) for r in rng[1:3])
    dy = jax.random.normal(rng[3], (1, 4, s, 2 * d))
    run = lambda: jax.vjp(lambda *t: pf.softmax_heads(*t, window), q, k, v)
    plain, plain_vjp = run()
    monkeypatch.setitem(flags._REGISTRY, "FLAGS_use_packed_attention", True)
    packed, packed_vjp = run()
    close(packed, plain, 2e-5, "kernel against softmax")
    for name, g, w in zip("qkv", packed_vjp(dy), plain_vjp(dy)):
        close(g, w, 5e-5, f"d{name}")


# ---------------------------------------------------------- the cross-decoder


def blocks_of(cfg):
    model = pf.Phi4FlashForCausalLM(builder.model_config(cfg))
    leaves = seeded(cfg)
    for name, p in model.named_parameters():
        p._data = leaves[name]
    return model, list(model.model.layers)


def test_gmu_reads_the_boundary_scans_output_and_cross_reads_fs_keys():
    """``G``'s output changes with the boundary ``M``'s ``A_log`` and
    ``C``'s with layer ``F``'s ``W_k``, through what those layers hand on."""
    model, (m, f, g, c) = blocks_of(config("MFGC"))
    x = Tensor._wrap(jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64)))

    def outputs():
        x1, memory, _ = m(x)
        x2, _, kv = f(x1, memory)
        u = g.norm_mixer(x2)
        return (g.gmu(u, memory)._data, c.attn_cross(u, kv)[0]._data,
                memory._data, kv)

    g0, c0, memory, kv = outputs()
    assert memory.shape == (2, 24, 128) and tuple(kv[0].shape) == (2, 24, 32)
    m.mamba.A_log._data = m.mamba.A_log._data - 1.0
    g1, c1, _, _ = outputs()
    assert float(jnp.max(jnp.abs(g1 - g0))) > 1e-6
    f.attn_full.k_proj.weight._data = f.attn_full.k_proj.weight._data * 1.5
    g2, c2, _, _ = outputs()
    assert float(jnp.max(jnp.abs(c2 - c1))) > 1e-6
    # the memory is y BEFORE the gate: it does not move with z's half of W_in
    before = outputs()[2]
    w_in = m.mamba.in_proj.weight._data
    m.mamba.in_proj.weight._data = w_in.at[:, 128:].multiply(2.0)
    assert jnp.array_equal(outputs()[2], before)
    assert not jnp.array_equal(before, memory)


def test_shared_leaves_gradients_are_the_sum_over_their_readers(monkeypatch):
    """``M F G C G C``: the gradient of layer ``F``'s ``W_k`` and of the
    boundary ``M``'s ``A_log`` is what reaches them through their own layer
    plus what each reader sends back (every other reader's copy held
    still)."""
    cfg = config("MFGCGC", layers=[16, 17, 18, 19, 20, 21])
    model = pf.Phi4FlashForCausalLM(builder.model_config(cfg))
    params = seeded(cfg)
    ids, labels = ids_of(cfg, 2, 24), ids_of(cfg, 2, 24, seed=1)
    index = {id(b): i for i, b in enumerate(model.model.layers)}
    forward = pf.Phi4FlashBlock.forward
    still = lambda t: (None if t is None else Tensor._wrap(
        jax.lax.stop_gradient(t._data)))

    def grads(live):
        """Gradients with only the readers in ``live`` sending any back."""
        def block(self, x, memory=None, kv=None):
            if self.kind in "GC" and index[id(self)] not in live:
                x, _, _ = forward(self, x, still(memory),
                                  kv and tuple(still(t) for t in kv))
                return x, memory, kv   # the later readers' copies are whole
            return forward(self, x, memory, kv)

        monkeypatch.setattr(pf.Phi4FlashBlock, "forward", block)
        return jax.jit(jax.grad(lambda p: loss_of(
            functional_call(model, p, Tensor._wrap(ids)), labels)))(params)

    whole = grads({2, 3, 4, 5})
    own = grads(set())
    through = {r: grads({r}) for r in (2, 3, 4, 5)}
    # (leaf, the readers of what its layer hands on); the scan's leaves also
    # hear from the C layers, through layer F's input
    for leaf, readers in (("model.layers.1.attn_full.k_proj.weight", (3, 5)),
                          ("model.layers.1.attn_full.v_proj.bias", (3, 5)),
                          ("model.layers.0.mamba.A_log", (2, 4)),
                          ("model.layers.0.mamba.in_proj.weight", (2, 4))):
        parts = {r: g[leaf] - own[leaf] for r, g in through.items()}
        for r in readers:
            assert float(jnp.max(jnp.abs(parts[r]))) > 0, (leaf, r)
        close(own[leaf] + sum(parts.values()), whole[leaf], 1e-4, leaf)
    for r in (2, 4):   # a memory unit sends nothing back to layer F's keys
        assert jnp.array_equal(
            through[r]["model.layers.1.attn_full.k_proj.weight"],
            own["model.layers.1.attn_full.k_proj.weight"])
    monkeypatch.undo()
    agree(cfg, seq=24)


# ------------------------------------------------------------------ shares


def mixer_params(params, mixer):
    pre = f"model.layers.0.{mixer}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def heads_cut(own, i, d=8):
    """Share i of 2 of an attention layer's leaves: key/value pair i with
    query pairs 2 i and 2 i + 1; the lambdas and the norm whole; W_o's bias
    once."""
    qc = i * 4 * d + np.arange(4 * d)
    kc = i * 2 * d + np.arange(2 * d)
    cut = dict(own)
    cut.update({"q_proj.weight": own["q_proj.weight"][:, qc],
                "q_proj.bias": own["q_proj.bias"][qc],
                "o_proj.weight": own["o_proj.weight"][qc],
                "o_proj.bias": own["o_proj.bias"] * (i == 0)})
    for name in ("k_proj", "v_proj"):
        if name + ".weight" in own:
            cut.update({name + ".weight": own[name + ".weight"][:, kc],
                        name + ".bias": own[name + ".bias"][kc]})
    return cut


@pytest.mark.parametrize("kind", ["S", "F", "C"])
def test_attention_pair_shares_add_up_through_w_o(kind):
    pattern = "FC" if kind == "C" else kind
    at = len(pattern) - 1
    cfg, part = config(pattern), share(pattern)
    params = seeded(cfg)
    # biases that are not nought, so that counting one twice would show
    params = {k: v + (0.1 if k.endswith("proj.bias") else 0.0)
              for k, v in params.items()}
    mixer = ref.MIXER[kind]
    pre = f"model.layers.{at}.{mixer}."
    own = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        kv_leaves = mixer_params(params, "attn_full") if kind == "C" else own
        kv = ref.keys_values(u * 0.5 if kind == "C" else u, kv_leaves, cfg,
                             IDENT)
        whole = ref.diff_attention(u, kv, own, cfg, IDENT, kind, at)
    total = 0.0
    for i in range(2):
        layer = pf.DiffAttention(builder.model_config(part),
                                 pf.ATTN_KIND[kind], at)
        cut = heads_cut(own, i)
        args = (Tensor._wrap(u),)
        if kind == "C":   # the share's own slice of what layer F made
            f_cut = heads_cut(kv_leaves, i)
            k = u * 0.5 @ f_cut["k_proj.weight"] + f_cut["k_proj.bias"]
            v = u * 0.5 @ f_cut["v_proj.weight"] + f_cut["v_proj.bias"]
            args += ((Tensor._wrap(k), Tensor._wrap(v)),)
        out, _ = functional_call(layer, cut, *args)
        total = total + out
    close(total, whole, F32, "sum of the pair shares")


def test_scan_channel_shares_add_up_once_x_w_x_is_summed():
    """Two shares of 64 of the 128 channels. ``[r, B, Cm] = x W_x`` is a sum
    over channels, which the deployment's two chips exchange: with that sum
    handed to both, the shares' outputs add up to the uncut layer's and their
    memories lie side by side in its memory. (In the cell nothing is
    exchanged: each share goes on with its own part of the sum, in the
    program and in the reference alike: ``agree(share('M'))`` above.)"""
    cfg = config("M")
    own = mixer_params(seeded(cfg), "mamba")
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        whole, memory = ref.scan_mixer(u, own, cfg, IDENT)
        parts = []
        for i in range(2):
            ch = i * 64 + np.arange(64)
            w_in = own["in_proj.weight"][:, np.concatenate([ch, 128 + ch])]
            x, z = pf.scan_inputs(u, w_in, own["conv1d_weight"][ch],
                                  own["conv1d_bias"][ch])
            parts.append((ch, x, z, x @ own["x_proj.weight"][ch]))
        rbc = parts[0][3] + parts[1][3]
        total, memories = 0.0, []
        for ch, x, z, _ in parts:
            out, y = pf.scan_outputs(
                x, z, rbc, own["dt_proj.weight"][:, ch],
                own["dt_proj.bias"][ch], own["A_log"][ch], own["D"][ch],
                own["out_proj.weight"][ch])
            total = total + out
            memories.append(y)
    close(total, whole, F32, "sum of the channel shares")
    close(jnp.concatenate(memories, -1), memory, F32, "the memory's channels")


def test_memory_unit_and_mlp_column_shares_add_up():
    cfg, part = config("MG"), builder.model_config(share("MG"))
    params = seeded(cfg)
    u = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
    m = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 128))
    gmu, mlp = (mixer_params({k.replace("layers.1", "layers.0"): v
                              for k, v in params.items() if "layers.1" in k},
                             name) for name in ("gmu", "mlp"))
    with jax.default_matmul_precision("highest"):
        whole_g = ref.memory_unit(u, m, gmu, IDENT)
        whole_m = ref.mlp(u, mlp, IDENT)
    total_g = total_m = 0.0
    for i in range(2):
        ch, col = i * 64 + np.arange(64), i * 48 + np.arange(48)
        total_g = total_g + functional_call(
            pf.GatedMemoryUnit(part),
            {"in_proj.weight": gmu["in_proj.weight"][:, ch],
             "out_proj.weight": gmu["out_proj.weight"][ch]},
            Tensor._wrap(u), Tensor._wrap(m[..., ch]))
        total_m = total_m + functional_call(
            pf.Phi4FlashMLP(part),
            {"gate_up_proj.weight": mlp["gate_up_proj.weight"][
                :, np.concatenate([col, 96 + col])],
             "down_proj.weight": mlp["down_proj.weight"][col]},
            Tensor._wrap(u))
    close(total_g, whole_g, F32, "memory unit")
    close(total_m, whole_m, F32, "mlp")


# ------------------------------------------------------------ the real step


@pytest.mark.parametrize("cut", [config, share], ids=["uncut", "share"])
def test_three_adamw_steps_bf16_through_the_benchmarks_step(cut):
    """The compiled step the cell runs (``functional_call`` +
    ``AdamW.apply_gradients_tree``, bfloat16 leaves, float32 master) against
    the reference's three steps, by the cell's own numbers."""
    from benchmarks.drivers import train_steps as drv

    cfg = dict(cut(), dtype="bfloat16")
    traffic = {"batch": 2, "seq": 40, "optimizer": ADAM}
    step, params, state = drv.build_program(cfg, traffic, SEED)
    got = {"losses": []}
    for i in (1, 2, 3):
        x, y = drv.feed(cfg, traffic, SEED, i)
        params, state, loss = step(params, state, x, y, jnp.float32(i))
        got["losses"].append(float(loss))
        if i == 1:
            got["grad_norms"] = drv._moment_norms(state, 0.9, 1)
    got["update_norms"] = drv._update_norms(params, state, SEED, 1)
    batches = [drv.feed(cfg, traffic, SEED, i) for i in (1, 2, 3)]
    want = ref.train_readings(cfg, SEED, batches, traffic["optimizer"], 1)
    read = drv.numbers(got, want)
    assert max(read[f"loss{i}_gap"] for i in (1, 2, 3)) <= BF16_LOSS
    assert read["grad_norm_gap"] <= BF16_GRAD
    # the lambda vectors move by lr x Adam's normalised step, whose size
    # follows the rounding of ONE number where dL / d lambda is small: the
    # share's worst leaf read 0.095 there, every other leaf under 0.03
    assert read["update_norm_gap"] <= 5 * BF16_UPDATE
    # the planted faults read far outside that
    half = ref.train_readings(cfg, SEED, batches, traffic["optimizer"], 1,
                              fault="half_batch")
    assert drv.numbers(half, want)["grad_norm_gap"] > 5 * BF16_GRAD
    unchanged = ref.train_readings(cfg, SEED, batches, traffic["optimizer"],
                                   1, fault="state_unchanged")
    assert drv.numbers(unchanged, want)["update_norm_gap"] == 1.0


def test_reference_in_blocks_equals_the_reference_whole(monkeypatch):
    """The blocks the reference works in at the cell's size (query rows,
    positions, the scan's chunks) change no number's terms."""
    cfg = share()
    params = seeded(cfg)
    ids, labels = ids_of(cfg, 2, 32), ids_of(cfg, 2, 32, seed=1)
    whole = reference_loss_and_grads(cfg, params, ids, labels)
    monkeypatch.setattr(ref, "QUERY_ROWS", 8)
    monkeypatch.setattr(ref, "POSITIONS", 16)
    monkeypatch.setattr(ref, "SCAN_CHUNK", 8)
    loss, grads = reference_loss_and_grads(cfg, params, ids, labels)
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    leaves_close(grads, whole[1], 1e-5)


def test_config_refuses_what_does_not_fit():
    with pytest.raises(ValueError):
        pf.Phi4FlashConfig(layer_pattern="MXS")
    with pytest.raises(ValueError):   # a reader before the layer it reads
        pf.Phi4FlashConfig(layer_pattern="MSGC")
    with pytest.raises(ValueError):
        pf.Phi4FlashConfig(layer_pattern="MS", layer_indices=(0,))
    with pytest.raises(ValueError):   # 10 key/value heads bring 20
        pf.Phi4FlashConfig(kv_heads_held=10, q_heads_held=10)
    with pytest.raises(ValueError):   # the pairs stay whole
        pf.Phi4FlashConfig(kv_heads_held=5, q_heads_held=10)
    whole = pf.Phi4FlashConfig()
    assert (whole.head_dim, whole.scan_channels, len(whole.layer_pattern)) \
        == (64, 5120, 32)
    assert whole.layer_pattern[16:18] == "MF"
