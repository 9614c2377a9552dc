"""The cell ``phi4flash-pretrain-s8192``: its files against the catalog row
and the contract, its cost functions against a hand count, its scopes file
on a recorded scope list, and a tiny rehearsal of it through ``run.main`` (a
fixture of its own beside ``conftest.tiny_cells``, whose table of tiny
configurations knows the configurations it was written with)."""
import json
import math
import re

import pytest
from conftest import TINY

from benchmarks.costs import (phi4flash_attention,
                              phi4flash_causal_flash_traced,
                              phi4flash_selective_scan_traced,
                              phi4flash_train_window,
                              phi4flash_window_flash_traced)
from benchmarks.harness import loader
from benchmarks.readers import trace_scope

CELL = "phi4flash-pretrain-s8192"
REAL_LOAD_CELL = loader.load_cell  # before any fixture replaces it
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "sliding_window")
GROUPS = ("attn_cross", "attn_full", "attn_window", "ssm", "gmu", "mlp",
          "head", "optimizer", "other")


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def test_cell_loads_with_its_metrics(cell):
    assert cell["cell"]["driver"] == "train_steps"
    assert cell["traffic"]["batch"] * cell["traffic"]["seq"] == 8192
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"device_idle_pct.train", "train_mfu_pct.phi4flash",
                     "selective_scan_roofline.phi4flash",
                     "causal_flash_roofline.phi4flash",
                     "window_flash_roofline.phi4flash"} | {
        f"{g}_ms_per_step.phi4flash" for g in GROUPS}
    for m in cell["per_layer"]:
        loader.find("readers", m["reader"])
        if "cost" in m["params"]:
            loader.find("costs", m["params"]["cost"])
    # the three roofline shares read different kernels
    pattern = {m["name"]: m["params"]["pattern"] for m in cell["per_layer"]
               if m["reader"] == "trace_kernel"}
    kernels = {"causal_flash_roofline.phi4flash": "%causal_flash_bwd_tiled.1",
               "window_flash_roofline.phi4flash": "%window_flash_fwd.3",
               "selective_scan_roofline.phi4flash": "%selective_scan_bwd"}
    for metric, rx in pattern.items():
        assert [m for m, k in kernels.items() if re.search(rx, k)] == [metric]
    # the other training cells report none of this cell's metrics
    for other in ("gpt2m-pretrain", "nemotron3s-pretrain-s4096",
                  "laguna-s-pretrain-s8192"):
        assert not any(m["name"].endswith(".phi4flash")
                       for m in loader.load_cell(other)["per_layer"])


def test_configuration_keeps_every_width_and_says_what_it_cut(cell):
    cfg = cell["config"]
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except FileNotFoundError:
        pytest.skip("no catalog beside the guide here")
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    src = row["config"]
    changed = {k for k, v in src.items() if cfg[k] != v}
    # layer_pattern is this file's key for the order of the kinds: the
    # source has no such key (it gives mb_per_layer and its model code)
    assert changed | {"layer_pattern"} == set(cfg["reduced"])
    assert len(changed) == 4 and not changed & set(WIDTHS)
    assert cfg["published"] == dict(
        {k: src[k] for k in changed},
        layer_pattern="MS" * 8 + "MF" + "GC" * 7)
    assert len(cfg["published"]["layer_pattern"]) == src["num_hidden_layers"]
    # two periods of the self-decoder, the boundary pair, one period of the
    # cross-decoder, under their published indices
    pub = cfg["published"]["layer_pattern"]
    assert cfg["layer_pattern"] == "MSMSMFGC" \
        == "".join(pub[i] for i in cfg["held"]["layers"])
    assert len(cfg["layer_pattern"]) == cfg["num_hidden_layers"] == 8
    # two chips a layer: the pairs stay whole
    for key in ("num_attention_heads", "num_key_value_heads"):
        assert cfg[key] * 2 == src[key] and cfg[key] % 2 == 0
    assert cfg["head_dim"] * src["num_attention_heads"] == src["hidden_size"]
    assert cfg["held"]["mlp_columns"] * 2 == src["intermediate_size"]
    assert cfg["held"]["scan_channels"] * 2 \
        == cfg["mamba_expand"] * src["hidden_size"]
    assert cfg["mamba_dt_rank"] * 16 == src["hidden_size"]
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"]) == (16, 4)
    assert cfg["vocab_size"] * 8 == src["vocab_size"]
    assert set(cfg["assumed"]) >= {"scan_sizes", "layer_kinds",
                                   "differential_attention", "attention_bias",
                                   "memory", "seeded_leaves"}


def test_builder_makes_the_share_the_file_states(cell):
    from benchmarks.builders import phi4flash as builder
    from benchmarks.reference import phi4flash as ref

    mc = builder.model_config(cell["config"])
    assert (mc.num_attention_heads, mc.num_key_value_heads) == (40, 20)
    assert (mc.q_heads_held, mc.kv_heads_held, mc.head_dim) == (20, 10, 64)
    assert (mc.scan_channels, mc.scan_channels_held) == (5120, 2560)
    assert (mc.mlp_columns_held, mc.vocab_rows_held) == (5120, 25008)
    assert mc.layer_indices == (0, 1, 2, 3, 16, 17, 18, 19)
    specs = ref.leaf_specs(cell["config"], "bfloat16")
    by_layer = {}
    for name, shape, _ in specs:
        key = name.split(".")[2] if "layers" in name else "rest"
        by_layer[key] = by_layer.get(key, 0) + math.prod(shape)
    # M 20.66 M + MLP 39.32 M + two norms; S / F 9.84 M; G 13.11 M; C 6.56 M
    mlp, norms = 3 * 2560 * 5120, 4 * 2560
    scan = (2560 * 5120 + 2560 * 4 + 2560 + 2560 * 192 + 160 * 2560 + 2560
            + 2560 * 16 + 2560 + 2560 * 2560)
    attn = 2560 * 1280 + 1280 + 2 * (2560 * 640 + 640) + 4 * 64 + 128 \
        + 1280 * 2560 + 2560
    cross = 2560 * 1280 + 1280 + 4 * 64 + 128 + 1280 * 2560 + 2560
    assert by_layer["0"] == by_layer["2"] == by_layer["4"] \
        == scan + mlp + norms == 59_952_640
    assert by_layer["1"] == by_layer["3"] == by_layer["5"] \
        == attn + mlp + norms == 49_167_744
    assert by_layer["6"] == 2 * 2560 * 2560 + mlp + norms == 52_439_040
    assert by_layer["7"] == cross + mlp + norms == 45_889_664
    assert by_layer["rest"] == 25008 * 2560 + 2 * 2560
    assert sum(by_layer.values()) == 489_715_456


def test_flops_per_token_against_a_hand_count(cell):
    cfg = cell["config"]
    per = phi4flash_train_window.matmul_params(cfg)
    mlp = 3 * 2560 * 5120
    # W_in 2560 x 5120, taps 2560 x 4, W_x 2560 x 192, W_dt 160 x 2560,
    # W_out 2560 x 2560
    assert per["M"] - mlp == 13_107_200 + 10_240 + 491_520 + 409_600 \
        + 6_553_600
    # q 2560 x 1280, k and v 2560 x 640 each, W_o 1280 x 2560
    assert per["S"] - mlp == per["F"] - mlp == 3 * 3_276_800
    assert per["C"] - mlp == 2 * 3_276_800
    assert per["G"] - mlp == 2 * 6_553_600
    assert per["head"] == 2560 * 25008
    facts = {"seq": 8192, "batch": 1, "tokens": 8192, "traced_steps": 2}
    # attention at the mathematics' widths: 2 x (64 + 128) a pair forward,
    # three times that with the backward, 20 query heads
    causal = phi4flash_causal_flash_traced.cost(cfg, facts)
    assert causal["flops"] == 2 * 3 * 2 * 192 * (8192 * 8192 // 2) * 20 * 2
    band = phi4flash_window_flash_traced.cost(cfg, facts)
    assert band["flops"] == 2 * 3 * 2 * 192 * 4_063_488 * 20 * 2
    # q, q again, dq at 20 x 64; o, o again, dO at 20 x 128; k, v twice and
    # dk, dv at 10 x 64: two-byte elements
    assert causal["bytes"] == band["bytes"] \
        == 2 * 2 * 8192 * 2 * (3 * 20 * 64 + 3 * 20 * 128 + 6 * 10 * 64)
    # the scan: 22 operations a position, channel and state and 10 a position
    # and channel; x and dx 2 bytes, delta, y and their gradients 4
    scan = phi4flash_selective_scan_traced.cost(cfg, facts)
    assert scan["flops"] == 2 * 3 * 8192 * 2560 * (22 * 16 + 10)
    assert scan["bytes"] == 2 * 3 * (8192 * 2560 * 20 + 8192 * 16 * 8
                                     + 2560 * 17 * 8)
    mult = 3 * per["M"] + 2 * per["S"] + per["F"] + per["G"] + per["C"] \
        + per["head"]
    # all but the norms, the biases, the lambdas and A_log, D of 489 715 456
    assert mult == 489_461_760
    want = 6 * mult + (causal["flops"] + band["flops"] + scan["flops"]) \
        / 2 / 8192
    assert phi4flash_train_window.flops_per_token(cfg, 8192) \
        == pytest.approx(want, rel=1e-12)
    assert phi4flash_train_window.cost(cfg, facts)["flops"] \
        == pytest.approx(want * 8192, rel=1e-12)


def test_costs_at_a_tiny_size():
    """S = 1024 (two tiles of the band), one row, one layer of each kind; a
    configuration with no layer of a kind costs that kernel nothing."""
    cfg = {"layer_pattern": "MSFC", "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 64, "sliding_window": 512,
           "mamba_d_state": 16, "held": {"scan_channels": 256}}
    facts = {"seq": 1024, "batch": 1, "tokens": 1024, "traced_steps": 1}
    assert phi4flash_window_flash_traced.cost(cfg, facts)["flops"] \
        == 3 * 2 * 192 * 393_472 * 4
    assert phi4flash_causal_flash_traced.cost(cfg, facts)["flops"] \
        == 3 * 2 * 192 * (1024 * 1024 // 2) * 4 * 2
    assert phi4flash_attention.attention_train(1, 4, 2, 1024, 64, 10, 1) \
        == {"flops": 3 * 2 * 192 * 10 * 4,
            "bytes": 1024 * 64 * 2 * (12 + 24 + 12)}
    assert phi4flash_selective_scan_traced.cost(cfg, facts)["flops"] \
        == 1024 * 256 * 362
    none = dict(cfg, layer_pattern="MG")
    assert phi4flash_window_flash_traced.cost(none, facts)["flops"] == 0
    assert phi4flash_causal_flash_traced.cost(none, facts)["flops"] == 0
    assert phi4flash_selective_scan_traced.cost(
        dict(cfg, layer_pattern="SF"), facts) == {"flops": 0, "bytes": 0}


def test_scopes_file_groups_a_recorded_scope_list():
    """Scopes as the step's operations carry them (read off the compiled
    program, ``jit(step)/...`` paths and instruction names); the groups
    take every operation once, so they sum to the whole."""
    scopes = trace_scope.load_scopes("phi4flash-train")
    groups = [(g, re.compile(p)) for g, p in scopes["groups"]]
    assert [g for g, _ in groups] == ["optimizer"] + list(GROUPS[:7]) + [
        "other"]
    pre = "jit(step)/jit(main)/"
    call = " = custom-call tpu_custom_call"
    recorded = [
        (pre + "jvp(model)/layers/0/mamba/dot_general", "%fusion.1", "ssm"),
        (pre + "jvp(model)/layers/4/mamba/pallas_call",
         "%selective_scan_fwd.2" + call, "ssm"),
        ("", "%selective_scan_bwd" + call, "ssm"),
        (pre + "transpose(jvp(model))/layers/2/mamba/mul", "%fusion.2",
         "ssm"),
        (pre + "jvp(model)/layers/1/attn_window/pallas_call",
         "%window_flash_fwd.1" + call, "attn_window"),
        (pre + "jvp(model)/layers/3/attn_window/pad", "%fusion.3",
         "attn_window"),
        (pre + "jvp(model)/layers/5/attn_full/pallas_call",
         "%causal_flash_fwd_tiled" + call, "attn_full"),
        # layer C's call of the same kernel: its scope comes first
        (pre + "jvp(model)/layers/7/attn_cross/pallas_call",
         "%causal_flash_fwd_tiled.1" + call, "attn_cross"),
        (pre + "transpose(jvp(model))/layers/7/attn_cross/pallas_call",
         "%causal_flash_bwd_tiled.1" + call, "attn_cross"),
        # what C sends back to layer F's keys is summed in F's backward
        (pre + "transpose(jvp(model))/layers/5/attn_full/add_any",
         "%fusion.4", "attn_full"),
        (pre + "jvp(model)/layers/6/gmu/dot_general", "%fusion.5", "gmu"),
        (pre + "jvp(model)/layers/6/mlp/dot_general", "%fusion.6", "mlp"),
        (pre + "jvp(model)/layers/6/norm_mixer/reduce_sum", "%fusion.7",
         "other"),
        (pre + "jvp(model)/embeddings/gather", "%fusion.8", "head"),
        (pre + "jvp(lm_head)/dot_general", "%fusion.9", "other"),
        (pre + "jvp(model)/lm_head/dot_general", "%fusion.10", "head"),
        (pre + "optimizer/mul", "%fusion.11", "optimizer"),
        ("", "%copy-done.3", "other"),
    ]
    for scope, name, want in recorded:
        assert trace_scope.group_of(groups, scope, name) == want, (scope,
                                                                   name)
    assert re.search(scopes["backward"], recorded[3][0])
    assert not re.search(scopes["backward"], recorded[0][0])
    # every operation falls into exactly one group: the nine sum to the whole
    ops = {"device": {0: [(name, 0, 1_000_000, scope)
                          for scope, name, _ in recorded]},
           "modules": {0: [("jit_step", 0, 10**9)]}}
    t = trace_scope.table(ops, scopes, 1)
    assert t["total_ms"] == pytest.approx(len(recorded))
    assert sum(v["fwd"] + v["bwd"] for v in t["groups"].values()) \
        == pytest.approx(len(recorded))
    assert t["kernels"]["causal_flash_fwd_tiled"]["calls"] == 2


@pytest.fixture
def tiny_cell(tiny_cells, monkeypatch):
    """``conftest.tiny_cells`` (the harness's look for a chip skipped) with
    THIS cell cut to a size the CPU holds. Limits set as conftest's
    TINY_LIMITS are: between the program's readings there and the planted
    faults'; every other file is the real one."""

    def load(workload):
        cell = REAL_LOAD_CELL(workload)
        with open(TINY / "phi4flash-tiny.json") as f:
            cell["config"] = json.load(f)
        cell["traffic"] = dict(cell["traffic"], batch=1, seq=48)
        cell["cell"] = dict(cell["cell"], trace_seconds=1, limits={
            "loss3_gap": 3e-4, "grad_norm_gap": 0.02,
            "update_norm_gap": 0.15})
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
    facts = {"seq": 8192, "batch": 1, "tokens": 8192 * 100, "steps": 100,
             "traced_steps": 10, "window_s": 23.0}
    no_kernels = {"device_ops": 0, "busy_s": 0.0, "window_s": 0.0,
                  "by_name_s": {"%fusion.1": 0.5}}
    per_token = phi4flash_train_window.flops_per_token(cell["config"], 8192)
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
                100 * per_token * 819_200 / (23.0 * 197e12))


def test_tiny_control_and_faults_fail(tiny_cell):
    from benchmarks.drivers import train_steps

    readings = train_steps.control(dict(loader.load_cell(CELL),
                                        seed=2**31 + 12))
    assert set(readings) == {"control_fp8", "fault_half_batch",
                             "fault_state_unchanged"}
    for name, r in readings.items():
        assert r["correct"] is False, (name, r)
