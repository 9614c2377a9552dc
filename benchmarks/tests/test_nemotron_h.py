"""The cell ``nemotron3s-pretrain-s4096``: its files against the catalog row
and the contract, its cost functions against a hand count, and a tiny
rehearsal of it through ``run.main`` (a fixture of its own beside
``conftest.tiny_cells``, whose table of tiny configurations knows the
configurations it was written with)."""
import json

import pytest
from conftest import TINY

from benchmarks.costs import (nemotron_h_causal_flash_traced,
                              nemotron_h_train_window)
from benchmarks.harness import loader

CELL = "nemotron3s-pretrain-s4096"
REAL_LOAD_CELL = loader.load_cell  # before any fixture replaces it
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
          "conv_kernel", "chunk_size", "expand", "intermediate_size",
          "moe_intermediate_size", "moe_latent_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok")


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def test_cell_loads_with_its_metrics(cell):
    assert cell["cell"]["driver"] == "train_steps"
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"device_idle_pct.train", "train_mfu_pct.nemotron3s",
                     "causal_flash_roofline.nemotron3s"} | {
        f"{g}_ms_per_step.nemotron3s"
        for g in ("ssm", "moe", "attn", "head", "optimizer", "other")}
    for m in cell["per_layer"]:
        loader.find("readers", m["reader"])
        if "cost" in m["params"]:
            loader.find("costs", m["params"]["cost"])
    # the other training cell reports none of this cell's metrics
    other = {m["name"] for m in loader.load_cell("gpt2m-pretrain")["per_layer"]}
    assert not any(n.endswith(".nemotron3s") for n in other)


def test_configuration_keeps_every_width_and_says_what_it_cut(cell):
    cfg = cell["config"]
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except FileNotFoundError:
        pytest.skip("no catalog beside the guide here")
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"])
    assert not changed & set(WIDTHS)
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # one whole period in the model's own 40 : 40 : 8, the guide's floors
    assert sorted(cfg["hybrid_override_pattern"]) == sorted("M" * 5 + "E" * 5
                                                            + "*")
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cell["traffic"]["batch"] * cell["traffic"]["seq"] == 16384


def test_flops_per_token_against_a_hand_count(cell):
    cfg = cell["config"]
    per = nemotron_h_train_window.matmul_params(cfg)
    # Mamba-2: in 4096 x 2320, conv 1280 x 4, out 1024 x 4096
    assert per["M"] == 9_502_720 + 5_120 + 4_194_304
    # attention: q, k, v 4096 x (512 + 128 + 128), o 512 x 4096
    assert per["*"] == 3_145_728 + 2_097_152
    # router 4096 x 512, latent 2 x 4096 x 1024, shared 2 x 4096 x 672,
    # and 22 x 8 / 512 of an expert's 2 x 1024 x 2688
    assert per["E"] == 2_097_152 + 8_388_608 + 5_505_024 + 1_892_352
    assert per["head"] == 67_108_864
    # a chunk of 128, state 128, 16 heads of 64, 1 group:
    # 128 x 129 + 64 x 129 x 16 + 4 x 64 x 128 x 16
    assert nemotron_h_train_window.ssd_flops_per_token(cfg) == 672_896
    # 6 x 230 278 144 + 3 x 5 x 672 896 + 3 x 2 x 4 x 4096 x 128
    assert nemotron_h_train_window.flops_per_token(cfg, 4096) \
        == 1_381_668_864 + 10_093_440 + 12_582_912
    facts = {"seq": 4096, "batch": 4, "tokens": 16384, "traced_steps": 2}
    assert nemotron_h_train_window.cost(cfg, facts)["flops"] \
        == 1_404_345_216 * 16384
    # the kernel: 4 heads of 128 at 4 x 4096, forward 2 products over the
    # causal half, backward twice that; 12 arrays of b s h d bf16
    assert nemotron_h_causal_flash_traced.cost(cfg, facts) == {
        "flops": 2 * 3 * 2 * 4 * 4 * 4096 * 4096 * 128,
        "bytes": 2 * 12 * (4 * 4096 * 4 * 128) * 2}


@pytest.fixture
def tiny_cell(tiny_cells, monkeypatch):
    """``conftest.tiny_cells`` (the harness's look for a chip skipped) with
    THIS cell cut to a size the CPU holds: its table of tiny configurations
    knows the configurations it was written with. Limits set as conftest's
    TINY_LIMITS are: between the program's readings there (loss 3e-5,
    gradient norms 0.004, update norms 0.02) and the fp8 control's
    (gradient norms 0.03-0.06); every other file is the real one."""

    def load(workload):
        cell = REAL_LOAD_CELL(workload)
        with open(TINY / "nemotron-h-tiny.json") as f:
            cell["config"] = json.load(f)
        cell["traffic"] = dict(cell["traffic"], batch=4, seq=40)
        cell["cell"] = dict(cell["cell"], trace_seconds=1, limits={
            "loss3_gap": 3e-4, "grad_norm_gap": 0.015,
            "update_norm_gap": 0.08})
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


def test_tiny_control_and_faults_fail(tiny_cell):
    from benchmarks.drivers import train_steps

    readings = train_steps.control(dict(loader.load_cell(CELL),
                                        seed=2**31 + 12))
    assert set(readings) == {"control_fp8", "fault_half_batch",
                             "fault_state_unchanged"}
    for name, r in readings.items():
        assert r["correct"] is False, (name, r)
