"""The cell ``laguna-s-pretrain-s8192``: its files against the catalog row and
the contract, its three cost functions against a hand count, its scopes file
on a recorded scope list, and a tiny rehearsal of it through ``run.main`` (a
fixture of its own beside ``conftest.tiny_cells``, whose table of tiny
configurations knows the configurations it was written with)."""
import json
import math
import re

import pytest
from conftest import TINY

from benchmarks.costs import (laguna_causal_flash_traced,
                              laguna_train_window, laguna_window_flash_traced)
from benchmarks.harness import loader
from benchmarks.readers import trace_scope

CELL = "laguna-s-pretrain-s8192"
REAL_LOAD_CELL = loader.load_cell  # before any fixture replaces it
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts_per_tok", "sliding_window", "rope_parameters")
GROUPS = ("attn_full", "attn_window", "moe", "mlp", "head", "optimizer",
          "other")


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def test_cell_loads_with_its_metrics(cell):
    assert cell["cell"]["driver"] == "train_steps"
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"device_idle_pct.train", "train_mfu_pct.lagunas",
                     "causal_flash_roofline.lagunas",
                     "window_flash_roofline.lagunas"} | {
        f"{g}_ms_per_step.lagunas" for g in GROUPS}
    for m in cell["per_layer"]:
        loader.find("readers", m["reader"])
        if "cost" in m["params"]:
            loader.find("costs", m["params"]["cost"])
    # the two roofline shares read different kernels
    pattern = {m["name"]: m["params"]["pattern"] for m in cell["per_layer"]
               if m["reader"] == "trace_kernel"}
    assert not re.search(pattern["causal_flash_roofline.lagunas"],
                         "%window_flash_fwd.3")
    assert re.search(pattern["window_flash_roofline.lagunas"],
                     "%window_flash_bwd.1")
    # the other training cells report none of this cell's metrics
    for other in ("gpt2m-pretrain", "nemotron3s-pretrain-s4096"):
        assert not any(m["name"].endswith(".lagunas")
                       for m in loader.load_cell(other)["per_layer"])


def test_configuration_keeps_every_width_and_says_what_it_cut(cell):
    cfg = cell["config"]
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except FileNotFoundError:
        pytest.skip("no catalog beside the guide here")
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) and len(changed) == 9
    assert not changed & set(WIDTHS)
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the leading dense layer once, then one whole period (3 sliding : 1 full)
    src = row["config"]
    for key in ("layer_types", "mlp_layer_types", "gating_types"):
        assert cfg[key] == src[key][:5]
    assert cfg["layer_types"][1:].count("sliding_attention") == 3
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    # key/value heads with the query heads that read them: 6 and 9 a head
    share = src["num_key_value_heads"] // cfg["num_key_value_heads"]
    assert cfg["num_attention_heads_per_layer"] == [
        h // share for h in src["num_attention_heads_per_layer"][:5]]
    assert cfg["num_attention_heads"] * share == src["num_attention_heads"]
    assert cfg["held"]["dense_mlp_columns"] * share == src["intermediate_size"]
    assert cfg["held"]["shared_expert_columns"] * share \
        == src["shared_expert_intermediate_size"]
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= src["vocab_size"]
    assert cell["traffic"]["batch"] * cell["traffic"]["seq"] == 16384
    assert cell["traffic"]["seq"] == src["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]


def test_builder_makes_the_share_the_file_states(cell):
    from benchmarks.builders import laguna as builder
    from benchmarks.reference import laguna as ref

    mc = builder.model_config(cell["config"])
    assert mc.q_heads == {"full_attention": 48, "sliding_attention": 72}
    assert mc.q_heads_held == {"full_attention": 12, "sliding_attention": 18}
    assert (mc.kv_heads_held, mc.experts_held, mc.vocab_rows_held) \
        == (2, 8, 25088)
    specs = ref.leaf_specs(cell["config"], "bfloat16")
    assert sum(math.prod(shape) for _, shape, _ in specs) == 566_504_448


def test_flops_per_token_against_a_hand_count(cell):
    cfg = cell["config"]
    per = laguna_train_window.matmul_params(cfg)
    # full: q, k, v 3072 x (12 + 4) x 128, gate 3072 x 12, o 1536 x 3072
    # sliding: 3072 x (18 + 4) x 128, 3072 x 18, 2304 x 3072
    assert per["attention"] == [6_291_456 + 36_864 + 4_718_592,
                                8_650_752 + 55_296 + 7_077_888] + [
        15_783_936] * 2 + [11_046_912]
    assert per["dense"] == 3 * 3072 * 3072
    # router 3072 x 256, shared 3 x 3072 x 256, and 10 x 8 / 256 of an
    # expert's 3 x 3072 x 1024
    assert per["sparse"] == 786_432 + 2_359_296 + 2_949_120
    assert per["head"] == 77_070_336
    # the band at S = 8192: 512 x 513 / 2 + 7680 x 512 pairs a head and row
    assert laguna_window_flash_traced.band_pairs(8192, 512) == 4_063_488
    assert laguna_window_flash_traced.band_pairs(1024, 512) \
        == 131_328 + 512 * 512 == 393_472
    assert laguna_window_flash_traced.band_pairs(256, 512) == 256 * 257 // 2
    # 6 x 199 206 912 + 3 x 2 x 8192 x 128 x 12 x 2 layers
    #   + 3 x 4 x 128 x 4 063 488 x 18 x 3 layers / 8192
    assert laguna_train_window.flops_per_token(cfg, 8192) \
        == 1_195_241_472 + 150_994_944 + 12 * 128 * 4_063_488 * 54 / 8192
    facts = {"seq": 8192, "batch": 2, "tokens": 16384, "traced_steps": 2}
    assert laguna_train_window.cost(cfg, facts)["flops"] \
        == 1_387_379_232 * 16384
    # the causal kernel: 12 heads of 128 at 2 x 8192, 2 layers; 12 arrays
    assert laguna_causal_flash_traced.cost(cfg, facts) == {
        "flops": 2 * 3 * 2 * 2 * 12 * 8192 * 8192 * 128 * 2,
        "bytes": 2 * 12 * (2 * 8192 * 12 * 128) * 2 * 2}
    # the band: 3 x (2 products of 2 x 128 a pair) over 2 rows x 18 heads x
    # 3 layers; q, o, dO, dq at 18 heads and k, v, dk, dv at 2
    assert laguna_window_flash_traced.cost(cfg, facts) == {
        "flops": 2 * 3 * 4 * 128 * 4_063_488 * 2 * 18 * 3,
        "bytes": 2 * 4 * (2 * 8192 * 128 * 2) * (18 + 2) * 3}


def test_costs_at_a_tiny_size():
    """S = 1024 (two tiles of the band), one row, one layer of each kind."""
    cfg = {"layer_types": ["full_attention", "sliding_attention"],
           "num_attention_heads_per_layer": [6, 9], "num_key_value_heads": 1,
           "head_dim": 128, "sliding_window": 512}
    facts = {"seq": 1024, "batch": 1, "tokens": 1024, "traced_steps": 1}
    assert laguna_window_flash_traced.cost(cfg, facts) == {
        "flops": 3 * 4 * 128 * 393_472 * 9,
        "bytes": 4 * (1024 * 128 * 2) * 10}
    assert laguna_causal_flash_traced.cost(cfg, facts) == {
        "flops": 3 * 2 * 6 * 1024 * 1024 * 128,
        "bytes": 12 * 1024 * 6 * 128 * 2}
    # a configuration with no sliding layer costs the band nothing
    assert laguna_window_flash_traced.cost(
        dict(cfg, layer_types=["full_attention"] * 2), facts)["flops"] == 0


def test_scopes_file_groups_a_recorded_scope_list():
    """Scopes as the step's operations carry them (read off the compiled
    program, ``jit(step)/...`` paths and instruction names)."""
    scopes = trace_scope.load_scopes("laguna-train")
    groups = [(g, re.compile(p)) for g, p in scopes["groups"]]
    assert [g for g, _ in groups] == ["optimizer"] + list(GROUPS[:5]) + [
        "other"]
    pre = "jit(step)/jit(main)/"
    recorded = [
        (pre + "jvp(model)/layers/0/attn_full/dot_general", "%fusion.1",
         "attn_full"),
        (pre + "transpose(jvp(model))/layers/4/attn_full/mul", "%fusion.2",
         "attn_full"),
        (pre + "jvp(model)/layers/4/attn_full/pallas_call",
         "%causal_flash_fwd_tiled.1 = custom-call tpu_custom_call",
         "attn_full"),
        (pre + "jvp(model)/layers/1/attn_window/pallas_call",
         "%window_flash_fwd.2 = custom-call tpu_custom_call", "attn_window"),
        ("", "%window_flash_bwd = custom-call tpu_custom_call",
         "attn_window"),
        (pre + "jvp(model)/layers/2/attn_window/concatenate", "%fusion.9",
         "attn_window"),
        (pre + "jvp(model)/layers/0/mlp/dot_general", "%fusion.3", "mlp"),
        (pre + "jvp(model)/layers/1/moe/router/dot_general", "%fusion.4",
         "moe"),
        (pre + "jvp(model)/layers/1/moe/shared/dot_general", "%fusion.5",
         "moe"),
        ("", "%ragged-dot.7", "moe"),
        (pre + "jvp(model)/layers/3/moe/pallas_call",
         "%topk_mask.1 = custom-call tpu_custom_call", "moe"),
        (pre + "jvp(model)/layers/1/norm_ffn/reduce_sum", "%fusion.6",
         "other"),
        (pre + "jvp(model)/embeddings/gather", "%fusion.7", "head"),
        (pre + "transpose(jvp(lm_head))/dot_general", "%fusion.8", "other"),
        (pre + "jvp(model)/lm_head/dot_general", "%fusion.10", "head"),
        (pre + "optimizer/mul", "%fusion.11", "optimizer"),
        ("", "%copy-done.3", "other"),
    ]
    for scope, name, want in recorded:
        assert trace_scope.group_of(groups, scope, name) == want, (scope,
                                                                   name)
    assert re.search(scopes["backward"], recorded[1][0])
    assert not re.search(scopes["backward"], recorded[0][0])


@pytest.fixture
def tiny_cell(tiny_cells, monkeypatch):
    """``conftest.tiny_cells`` (the harness's look for a chip skipped) with
    THIS cell cut to a size the CPU holds. Limits set as conftest's
    TINY_LIMITS are: between the program's readings there (losses to 6e-5,
    gradient norms 0.004 to 0.006, update norms 0.005 to 0.012 on four
    seeds) and the fp8 control's (gradient norms 0.03 to 0.06); every other
    file is the real one."""

    def load(workload):
        cell = REAL_LOAD_CELL(workload)
        with open(TINY / "laguna-tiny.json") as f:
            cell["config"] = json.load(f)
        cell["traffic"] = dict(cell["traffic"], batch=4, seq=40)
        cell["cell"] = dict(cell["cell"], trace_seconds=1, limits={
            "loss3_gap": 3e-4, "grad_norm_gap": 0.015,
            "update_norm_gap": 0.04})
        return cell

    monkeypatch.setattr(loader, "load_cell", load)
    return tiny_cells


def test_tiny_rehearsal_last_line(tiny_cell, capsys):
    rc = tiny_cell.main(["--workload", CELL, "--seed", str(2**31 + 11),
                         "--seconds", "3", "--trace", "0"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0
    assert set(line["checks"]) == {"loss3_gap", "grad_norm_gap",
                                   "update_norm_gap", "last_loss_finite"}


def test_readers_say_nothing_where_there_is_nothing_to_read(cell):
    """Without a device trace, or with one that holds no such kernel (a
    program from before this cell's kernels), the readers of the new
    metrics return nothing and raise nothing; the share of the peak comes
    from the driver's counts alone."""
    ctx = dict(cell, chips=1, peaks={"flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9})
    facts = {"seq": 8192, "batch": 2, "tokens": 16384 * 100, "steps": 100,
             "traced_steps": 10, "window_s": 23.0}
    no_kernels = {"device_ops": 0, "busy_s": 0.0, "window_s": 0.0,
                  "by_name_s": {"%fusion.1": 0.5}}
    for spec in cell["per_layer"]:
        read = loader.find("readers", spec["reader"]).read
        if spec["reader"] in ("trace_kernel", "trace_scope"):
            assert read(spec, {"trace": None, "facts": facts}, ctx) is None
        if spec["reader"] == "trace_kernel":
            assert read(spec, {"trace": no_kernels, "facts": facts},
                        ctx) is None
        if spec["reader"] == "flops_share":
            got = read(spec, {"facts": facts}, ctx)
            assert got == pytest.approx(
                100 * 1_387_379_232 * 1_638_400 / (23.0 * 197e12))


def test_tiny_control_and_faults_fail(tiny_cell):
    from benchmarks.drivers import train_steps

    readings = train_steps.control(dict(loader.load_cell(CELL),
                                        seed=2**31 + 12))
    assert set(readings) == {"control_fp8", "fault_half_batch",
                             "fault_state_unchanged"}
    for name, r in readings.items():
        assert r["correct"] is False, (name, r)
