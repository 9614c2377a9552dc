"""The chip's compiler, asked without the chip: the Pallas kernels of the
served and trained paths compile for a DESCRIBED TPU v5e at the widths
``chip_smoke.py`` runs them at (``llama2_7b()``: 32 heads x 128 = 4096
lanes, FF 11008; ``gpt2_medium()``: 16 heads x 64, S=1024), at the
2048-wide / 16 x 128 geometry ROADMAP B1 needs next, and at the GPT-2
small geometry the kernels were first written at.

Interpret mode cannot show what this shows: a kernel that keeps more in
fast memory than the chip grants, or slices off the tiling, passes every
interpret-mode test and is refused here (the whole-sequence-resident slab
kernels were, at 4096 lanes x 2048 tokens). A compile that passes is not
a chip run — nothing executes; ``chip_smoke.py`` is the run.

The topology is described inside a module-scoped fixture, in this file
only, and the compiles run in the test's own process: one process at a
time may load the TPU's library, so nothing here touches it at import, in
a ``skipif`` or in ``parametrize``, and no other file may do the same.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16
# (heads, kv_heads, head_dim, page_size, context)
LLAMA_7B = (32, 32, 128, 16, 2048)
LLAMA_7B_MAXPOS = (32, 32, 128, 16, 4096)  # llama2_7b().max_position
WIDE_2048 = (16, 16, 128, 16, 2048)
GPT2_SMALL = (12, 12, 64, 16, 1024)
GEOMETRIES = pytest.mark.parametrize(
    "geom", [LLAMA_7B, LLAMA_7B_MAXPOS, WIDE_2048, GPT2_SMALL],
    ids=["llama7b-ctx2048", "llama7b-ctx4096", "16x128-ctx2048",
         "gpt2small-ctx1024"])
BATCH, POOL_PAGES = 8, 1024


def _mod(name):
    # the package re-exports functions under some of its modules' names
    return importlib.import_module(f"paddle_tpu.ops.pallas.{name}")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises when it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jcc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    jcc.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache, monkeypatch):
    """``compiles(fn, *shapes)``: lower ``fn`` for the described chip and
    require the Mosaic kernel in the compiled program. The dispatchers ask
    ``jax.default_backend()`` (the CPU here) and would take their jnp
    twins, so the test steers their ``_interpret`` to the chip branch."""
    for name in ("paged_attention", "decode_attention", "causal_flash",
                 "flash_attention", "ssd_scan", "topk_mask",
                 "selective_scan"):
        monkeypatch.setattr(_mod(name), "_interpret", lambda: False)

    def compiles(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, \
            "compiled, but without the Mosaic kernel (a jnp twin ran)"

    return compiles


def _pages(geom, dtype=BF16):
    h, hkv, d, ps, ctx = geom
    page = ((POOL_PAGES, ps, hkv * d), dtype)
    return [page, page, ((BATCH, ctx // ps), jnp.int32),
            ((BATCH,), jnp.int32)]


@GEOMETRIES
def test_paged_slab_decode(chip, geom):
    pa = _mod("paged_attention")
    h, hkv, d, ps, ctx = geom
    chip(lambda q, k, v, bt, ln: pa.paged_slab_decode_attention(
        q, k, v, bt, ln, h), ((BATCH, h, d), BF16), *_pages(geom))


def test_paged_slab_decode_int8_pages(chip):
    pa = _mod("paged_attention")
    h, hkv, d, ps, ctx = LLAMA_7B
    chip(lambda q, k, v, bt, ln, sc: pa.paged_slab_decode_attention(
        q, k, v, bt, ln, h, scale_pages=sc),
        ((BATCH, h, d), BF16), *_pages(LLAMA_7B, jnp.int8),
        ((POOL_PAGES, ps, 128), BF16))


@GEOMETRIES
def test_paged_verify_slab_m4(chip, geom):
    pa = _mod("paged_attention")
    h, hkv, d, ps, ctx = geom
    chip(lambda q, k, v, bt, base: pa.paged_verify_slab_attention(
        q, k, v, bt, base), ((BATCH, 4, h, d), BF16), *_pages(geom))


@GEOMETRIES
def test_contiguous_slab_decode(chip, geom):
    da = _mod("decode_attention")
    h, hkv, d, ps, ctx = geom
    chip(lambda q, kv, ln: da.decode_attention_slab(q, kv, ln),
         ((BATCH, h, d), BF16), ((2, BATCH, ctx, hkv * d), BF16),
         ((BATCH,), jnp.int32))


# decode-shaped GEMMs of the two widths: [rows, K] x [K, N]
GEMMS = pytest.mark.parametrize(
    "k,n", [(4096, 11008), (11008, 4096), (4096, 32000), (2048, 5632)],
    ids=["llama7b-up", "llama7b-down", "llama7b-head", "2048-wide-up"])


@GEMMS
@pytest.mark.parametrize("weight_dtype", ["int8", "int4"])
def test_quant_matmul(chip, weight_dtype, k, n):
    qm = _mod("quant_matmul")
    rows_k = k // 2 if weight_dtype == "int4" else k  # two nibbles a byte
    chip(lambda x, w, s: qm.quant_matmul_pallas(
        x, w, s, weight_dtype=weight_dtype, interpret=False),
        ((BATCH, k), BF16), ((rows_k, n), jnp.int8), ((n,), jnp.float32))


@pytest.mark.parametrize("k,n", [(4096, 11008), (2048, 5632)],
                         ids=["llama7b-ff", "2048-wide-ff"])
def test_grouped_matmul(chip, k, n):
    gm = _mod("grouped_matmul")
    e, rows = 8, 1024
    chip(lambda x, w, g: gm.grouped_matmul_pallas(x, w, g, interpret=False),
         ((rows, k), BF16), ((e, k, n), BF16), ((e,), jnp.int32))


@pytest.mark.parametrize("heads,d,grad", [
    (16, 64, False), (16, 64, True), (8, 128, True)],
    ids=["fwd", "grad", "head128-grad"])
def test_causal_flash_qkv_gpt_medium(chip, heads, d, grad):
    """The packed-attention path of the train phase: gpt2_medium(), batch
    12, S=1024 — 16 heads x 64 pair-packed into 128-lane blocks. And a
    width no benchmark cell trains: head_dim 128 (one head a lane block;
    the scale is no power of two, so it does not fold), through the
    whole-row forward and the whole-sequence program's tiled backward."""
    cf = _mod("causal_flash")
    seq = 1024
    hpb = cf.heads_per_block(heads, d)

    def fwd(qkv):
        return cf.causal_flash_qkv(qkv, heads, d)

    fn = fwd if not grad else jax.grad(
        lambda qkv: fwd(qkv).astype(jnp.float32).sum())
    chip(fn, ((12, 3 * heads // hpb, seq, hpb * d), BF16))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_causal_flash_qkv_nemotron_share(chip, grad):
    """The attention of ``nemotron3s-pretrain-s4096``: 4 query heads of 128
    (one key/value head expanded to them) at batch 4, S=4096: the whole-row
    forward and the per-pair tiled backward."""
    cf = _mod("causal_flash")

    def fwd(qkv):
        return cf.causal_flash_qkv(qkv, 4, 128)

    fn = fwd if not grad else jax.grad(
        lambda qkv: fwd(qkv).astype(jnp.float32).sum())
    chip(fn, ((4, 12, 4096, 128), BF16))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("heads,window,dtype", [
    (12, None, BF16), (18, 512, BF16), (2, 512, jnp.float32)],
    ids=["full-12-heads", "window-18-heads", "window-float32"])
def test_causal_flash_qkv_laguna_share(chip, heads, window, dtype, grad):
    """The attention of ``laguna-s-pretrain-s8192`` at batch 2, S=8192, D=128:
    12 query heads through the tiled per-pair grids (``_fwd_tiled`` /
    ``_bwd_tiled``, the regime's first cell), 18 through the band regime at
    window 512 (``window_flash_fwd`` / ``window_flash_bwd``), and the band
    regime in float32."""
    cf = _mod("causal_flash")

    def fwd(qkv):
        return cf.causal_flash_qkv(qkv, heads, 128, window=window)

    fn = fwd if not grad else jax.grad(
        lambda qkv: fwd(qkv).astype(jnp.float32).sum())
    chip(fn, ((2, 3 * heads, 8192, 128), dtype))


@pytest.mark.parametrize("batch,seq,heads,groups,chunk,dtype", [
    (4, 4096, 16, 1, 128, BF16), (1, 4096, 128, 8, 128, BF16),
    (2, 1024, 32, 1, 128, BF16), (2, 1024, 16, 1, 256, BF16),
    (2, 1024, 16, 1, 128, jnp.float32)],
    ids=["nemotron-share", "uncut-8-groups", "32-heads-a-group", "chunk-256",
         "float32"])
def test_ssd_scan_pair(chip, monkeypatch, batch, seq, heads, groups, chunk,
                       dtype):
    """The Mamba-2 chunk walk of ``nemotron3s-pretrain-s4096`` (16 heads of
    64 on one group, state 128, batch 4, S=4096), forward and backward
    through ``ssd_chunked``; the uncut layer's 8 groups of 16 heads (the
    grid's group axis); and the widest shapes ``ssd_scan.supported`` lets
    through, whose blocks must still fit the scoped fast memory."""
    from paddle_tpu.models.nemotron_h import ssd_chunked

    ssd = _mod("ssd_scan")
    monkeypatch.setattr(ssd, "enabled", ssd.supported)
    p, n = 64, 128
    assert ssd.supported(chunk, n, heads // groups, p,
                         jnp.dtype(dtype).itemsize)

    def both(x, dt, a, bm, cm, d, dy):
        y, vjp = jax.vjp(lambda *t: ssd_chunked(*t[:5], chunk, t[5]),
                         x, dt, a, bm, cm, d)
        return (y,) + vjp(dy)

    f32 = jnp.float32
    chip(both, ((batch, seq, heads, p), dtype), ((batch, seq, heads), f32),
         ((heads,), f32), ((batch, seq, groups, n), dtype),
         ((batch, seq, groups, n), dtype), ((heads,), f32),
         ((batch, seq, heads, p), f32))


@pytest.mark.parametrize("batch,seq,channels,states,dtype", [
    (1, 8192, 2560, 16, BF16), (1, 4096, 5120, 16, BF16),
    (2, 1000, 1280, 8, BF16), (2, 1024, 2560, 16, jnp.float32)],
    ids=["phi4flash-share", "uncut-5120-channels", "ragged-10-rows-8-states",
         "float32"])
def test_selective_scan_pair(chip, monkeypatch, batch, seq, channels, states,
                             dtype):
    """The Mamba-1 scan of ``phi4flash-pretrain-s8192`` (2560 channels of 16
    states, one row of 8192: groups of 8 + 8 + 4 rows of 128 channels),
    forward and backward; the uncut layer's 5120 channels, whose blocks and
    kept states must still fit the raised scoped limit; a length that is no
    whole number of chunks over a half-filled last group; float32."""
    ss = _mod("selective_scan")
    monkeypatch.setattr(ss, "enabled", ss.supported)
    assert ss.supported(seq, channels, states)

    def both(x, delta, a, bm, cm, d, dy):
        y, vjp = jax.vjp(ss.selective_scan, x, delta, a, bm, cm, d)
        return (y,) + vjp(dy)

    f32 = jnp.float32
    chip(both, ((batch, seq, channels), dtype), ((batch, seq, channels), f32),
         ((channels, states), f32), ((batch, seq, states), dtype),
         ((batch, seq, states), dtype), ((channels,), f32),
         ((batch, seq, channels), f32))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("window", [None, 512], ids=["full", "window"])
def test_padded_differential_attention_phi4flash_share(chip, monkeypatch,
                                                       window, grad):
    """The attention of ``phi4flash-pretrain-s8192``: 20 query heads of 64
    over 5 key/value pairs whose values are 128 wide, one row of 8192,
    through ``causal_flash_qkv`` at head_dim 128 with q and k zero-padded
    (``models/phi4flash.py::softmax_heads``): the tiled causal kernels on
    layers F and C, the band regime on the S layers."""
    from paddle_tpu.framework import flags
    from paddle_tpu.models.phi4flash import softmax_heads

    monkeypatch.setitem(flags._REGISTRY, "FLAGS_use_packed_attention", True)
    fwd = lambda q, k, v: softmax_heads(q, k, v, window)
    fn = fwd if not grad else jax.grad(
        lambda *t: fwd(*t).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    chip(fn, ((1, 20, 8192, 64), BF16), ((1, 8192, 640), BF16),
         ((1, 8192, 640), BF16))


@pytest.mark.parametrize("tokens,experts,k", [
    (16384, 512, 22), (1024, 1024, 32), (2048, 64, 1)],
    ids=["nemotron-share", "widest", "k-1"])
def test_topk_mask(chip, monkeypatch, tokens, experts, k):
    """LatentMoE's selection in ``nemotron3s-pretrain-s4096`` (16 384 tokens
    a step over a 512-wide router, 22 chosen); the widest block and longest
    sorted lists ``topk_mask.supported`` lets through; a list of one."""
    tm = _mod("topk_mask")
    monkeypatch.setattr(tm, "enabled", tm.supported)
    assert tm.supported(tokens, experts, k)
    chip(lambda v: tm.topk_mask(v, k), ((tokens, experts), jnp.float32))


def _computations(text):
    """({name: lines}, the entry's name) of a compiled program's text."""
    import re

    comps, name, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif name:
            comps[name].append(line)
    return comps, entry


def _loop_bodies(text):
    """{name: lines} of the computations the compiled text's ``while``
    instructions name as their bodies."""
    import re

    bodies = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    comps, _ = _computations(text)
    assert bodies <= set(comps)
    return {name: comps[name] for name in bodies}


@pytest.mark.parametrize("layer", ["laguna", "latent"])
def test_an_expert_layer_walks_its_rows_in_place_in_one_buffers_memory(
        one_chip, no_persistent_cache, layer):
    """One expert layer of each share cell, value and gradients.

    ``laguna``: ``routed_experts.mix_every_pair`` at one MoE layer of
    ``laguna-s-pretrain-s8192`` (16 384 tokens of width 3072, 8 held experts
    of width 1024, a first buffer of 10 240 rows and twelve more for the
    131 072 pairs no routing can pass): the later buffers keep nothing for
    the backward, so the layer takes memory for the first buffer and one
    buffer's work (the thirteen buffers' rows kept would be 4.4 GiB).
    ``latent``: ``mix`` at one E layer of ``nemotron3s-pretrain-s4096``
    (16 384 tokens of latent width 1024, 8 experts of width 2688, one
    buffer of 16 896 rows: 33 whole chunks; a last chunk that starts early
    is ``tests/test_routed_experts.py``'s, at two and a half chunks).

    Both move their rows through ``take_rows`` / ``add_rows``: ``while``
    loops over the chunks that hold a pair, whose bodies update the carried
    array in place (a ``copy`` of it inside a body would cost the whole
    array once a chunk)."""
    from paddle_tpu.models import routed_experts as rx

    t, held = 16384, 8
    if layer == "laguna":
        width, ff, rows, limit, loops = 3072, 1024, 10240, 2.5, 4 + 4 + 1
        act = lambda a, g: jax.nn.silu(a) * g
        w_in = [((held, width, ff), BF16)] * 2
        mix = lambda routed, x, w_local, w_in, w_out: rx.mix_every_pair(
            routed, rows, t * held, x, w_local, w_in, act, w_out)
    else:
        width, ff, rows, limit, loops = 1024, 2688, 16896, 0.75, 4
        act = lambda a: jnp.square(jax.nn.relu(a))
        w_in = [((held, width, ff), BF16)]
        mix = lambda routed, x, w_local, w_in, w_out: rx.mix(
            rx.sort_pairs(routed, rows), x, routed, w_local, w_in, act,
            w_out)

    def loss(x, scores, w_out, *w_in):
        routed = scores > 0
        out = mix(routed, x, jnp.where(routed, scores, 0.0), w_in, w_out)
        return jnp.sum(out * out)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((t, width), BF16), ((t, held), jnp.float32),
        ((held, ff, width), BF16), *w_in)]
    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(len(args)))
                       ).lower(*args).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    assert (" conditional(" in text) == (layer == "laguna")
    # the compiler's figure read when this was written: 2.08 and 0.49 GiB
    assert compiled.memory_analysis().temp_size_in_bytes < limit * 2**30
    bodies = _loop_bodies(text)
    assert len(bodies) >= loops
    walks = {name: lines for name, lines in bodies.items()
             if any(f"[{n},{width}]" in line for line in lines
                    for n in (t, rows))}
    assert len(walks) >= 4
    for name, lines in walks.items():
        assert not any(" copy(" in line for line in lines), name


def _entry_fusions(text):
    """[(output shape without layouts, called computation's lines)] of the
    fusions of the compiled text's entry computation."""
    import re

    comps, entry = _computations(text)
    found = []
    for line in comps[entry]:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) fusion\(.*"
                     r"calls=%?([\w.\-]+)", line)
        if m:
            found.append((re.sub(r"\{[^}]*\}", "", m.group(1)),
                          comps[m.group(2)]))
    return found


def test_a_weight_gradient_and_its_adamw_update_stand_apart(
        one_chip, no_persistent_cache):
    """The gate-and-up weight gradient of ``phi4flash-pretrain-s8192``
    (``x^T dy`` over 8192 rows into a bfloat16 ``[2560, 10240]`` leaf) with
    ``AdamW.apply_gradients_tree`` on a float32 master behind it. The
    gradient reaches the update as a finished array: no fusion holds both
    the product and the update's outputs (leaf, master, two moments). Folded
    into the product's epilogue, three float32 operands and four outputs a
    tile share fast memory with it, the tile halves, and on the chip the
    product ran at half its rate (PERF.md section 6, PR 36). The state is
    updated in place and nothing is kept but the one gradient."""
    from paddle_tpu import optimizer

    rows, k, n = 8192, 2560, 10240
    opt = optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                          multi_precision=True)

    def step(params, state, x, dy, step_no):
        grads = jax.vjp(lambda p: {"y": jnp.dot(x, p["w"])}, params)[1](
            {"y": dy})[0]
        return opt.apply_gradients_tree(params, grads, state, 3e-4, step_no)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    leaf = sds((k, n), jnp.float32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        {"w": sds((k, n), BF16)},
        {"w": {"master": leaf, "moment1": leaf, "moment2": leaf}},
        sds((rows, k), BF16), sds((rows, n), BF16),
        sds((), jnp.int32)).compile()
    update = f"(bf16[{k},{n}], " + ", ".join([f"f32[{k},{n}]"] * 3) + ")"
    is_product = lambda lines: any(
        " convolution(" in l or " dot(" in l for l in lines)
    fusions = _entry_fusions(compiled.as_text())
    assert [shape for shape, lines in fusions if is_product(lines)] \
        == [f"bf16[{k},{n}]"]
    assert [is_product(lines) for shape, lines in fusions
            if shape == update] == [False]
    # the compiler's figure read when this was written: one gradient
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.01 * k * n * 2


@pytest.mark.parametrize("rows,width,tokens", [(25008, 2560, 8192),
                                               (50304, 1024, 12288)],
                         ids=["phi4flash", "gpt2m"])
def test_the_tied_lookups_gradient(one_chip, no_persistent_cache, rows, width,
                                   tokens):
    """A tied table's gradient: the head's weight gradient plus the lookup's
    transpose. At ``phi4flash-pretrain-s8192``'s ``[25008, 2560]`` the
    lookup's rows are summed apart (scope ``rows_apart``): one float32
    scatter-add of 2048 columns and one of 512, onto rows padded to whole
    tiles, where the chip's one scatter of 2560-wide rows took 10.5 ms
    (PERF.md section 6, PR 38). At ``gpt2m-pretrain``'s
    ``[50304, 1024]``, the parent's form: one bfloat16 scatter-add in place
    onto the head's product."""
    import re

    import paddle_tpu.nn.functional as F

    def grad(table, hidden, ids, ct, dlogits):
        lookup_and_head = lambda t: (F.embedding(ids, t)._data,
                                     jnp.dot(hidden, t.T))
        return jax.vjp(lookup_and_head, table)[1]((ct, dlogits))[0]

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    text = jax.jit(grad).lower(
        sds((rows, width), BF16), sds((tokens, width), BF16),
        sds((1, tokens), jnp.int32), sds((1, tokens, width), BF16),
        sds((tokens, rows), BF16)).compile().as_text()
    comps, entry = _computations(text)
    made_by = {line.split(" = ")[0].split()[-1]: line for line in comps[entry]
               if " = " in line}
    scatters = [line for line in comps[entry]
                if " fusion(" in line and "kind=kCustom" in line
                and "/scatter-add" in line]
    shapes = sorted(re.sub(r"\{[^}]*\}", "", line.split(" = ")[1].split()[0])
                    for line in scatters)
    if width == 2560:
        assert "rows_apart" in text
        assert shapes == ["f32[25088,2048]", "f32[25088,512]"]
    else:
        assert "rows_apart" not in text
        [line] = scatters
        assert shapes == [f"bf16[{rows},{width}]"]
        onto = re.search(r" fusion\((%[\w.\-]+)", line).group(1)
        assert "dot_general" in made_by[onto]
        assert '"aliasing_operands":{"lists":[{"indices":["0"' in line


@pytest.mark.parametrize("batch,seq", [(1, 2048), (8, 1024), (8, 128)])
def test_flash_attention_llama_prefill(chip, batch, seq):
    """The serve phase's prefill: ``F.flash_attention`` at head_dim 128,
    one prompt-length bucket a case."""
    fa = _mod("flash_attention")
    q = ((batch, seq, 32, 128), BF16)
    chip(lambda q, k, v: fa.flash_attention_fused(q, k, v, causal=True),
         q, q, q)


def test_attention_kernels_under_the_fleet_mesh(topo, no_persistent_cache,
                                                monkeypatch):
    """Across chips: a Mosaic kernel cannot be partitioned automatically, so
    a jit over the 2x2 mesh that reaches one is refused when lowered for the
    chip — unless its dispatcher runs it per shard (ops/pallas/sharded.py).
    dp=2 x mp=2 as ``chip_smoke.py --chips 4`` trains: the packed kernel at
    gpt2_medium() widths, ``F.flash_attention``'s at llama widths."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import parallel
    from paddle_tpu.distributed.topology import HYBRID_AXES
    from paddle_tpu.ops.pallas.sharded import per_shard

    cf, fa = _mod("causal_flash"), _mod("flash_attention")
    monkeypatch.setattr(cf, "_interpret", lambda: False)
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 1, 1, 1, 2),
                HYBRID_AXES)

    def on_mesh(shape, *spec):
        return jax.ShapeDtypeStruct(shape, BF16,
                                    sharding=NamedSharding(mesh, P(*spec)))

    # gpt2_medium(): 16 heads x 64 pair-packed -> [B, 3, 8 groups, S, 128]
    qkv5 = on_mesh((12, 3, 8, 1024, 128), "dp", None, "mp")

    def packed(qkv5):
        b, _, g, s, lanes = qkv5.shape
        return cf.causal_flash_qkv(qkv5.reshape(b, 3 * g, s, lanes),
                                   2 * g, 64)

    qkv = on_mesh((8, 1024, 32, 128), "dp", None, "mp")

    def flash(q, k, v):
        return fa.flash_attention_fused(q, k, v, causal=True)

    monkeypatch.setattr(parallel, "_global_mesh", None)
    with pytest.raises(NotImplementedError, match="automatically partition"):
        jax.jit(packed).lower(qkv5)

    monkeypatch.setattr(parallel, "_global_mesh", mesh)
    for fn, args in (
            (lambda x: per_shard(packed, [x], dims=[(0, 2)],
                                 out_dims=(0, 1), out_ndim=4), (qkv5,)),
            (lambda q, k, v: per_shard(flash, [q, k, v], dims=[(0, 2)] * 3,
                                       out_dims=(0, 2), out_ndim=4),
             (qkv,) * 3)):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
