"""The ops-and-bytes functions against hand-worked numbers, one shape each."""
import json

from conftest import ROOT

from benchmarks.costs import (gpt2_train_window, llama_serve_window,
                              paged_decode_traced)
from benchmarks.harness import costs
from benchmarks.harness.peaks import PEAKS, peaks_for


def _config(name):
    with open(ROOT / "benchmarks" / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_causal_attention_train_at_the_cells_shape():
    # forward: 2 products of 2 b h s^2 d, causal half: 2*12*16*1024^2*64
    fwd = 25_769_803_776
    got = costs.causal_attention_train(12, 16, 1024, 64, 24)
    assert got["flops"] == 3 * fwd * 24 == 1_855_425_871_872
    # 12 arrays of b s h d two-byte elements a layer
    assert got["bytes"] == 12 * (12 * 1024 * 16 * 64) * 2 * 24 \
        == 7_247_757_312


def test_gpt2_medium_flops_per_token():
    cfg = _config("gpt2-medium")
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 50257 x 1024
    assert gpt2_train_window.matmul_params(cfg) == 353_453_056
    # 6 N + 6 s H L
    assert gpt2_train_window.flops_per_token(cfg, 1024) == 2_271_713_280


def test_mistral_layer_and_kv():
    cfg = _config("mistral-7b-v0.3")
    # a layer: q, o 16.8 M each; k, v 4.2 M each; MLP 176.2 M
    one = dict(cfg, num_hidden_layers=1)
    assert llama_serve_window.matmul_params(one) - 32768 * 4096 == 218_103_808
    assert llama_serve_window.matmul_params(cfg) == 3_187_671_040
    assert paged_decode_traced.kv_bytes_per_token(cfg) == 57_344  # 56 KiB
    assert paged_decode_traced.cost(cfg, {"traced_contexts": [100, 200]}) \
        == {"flops": 0, "bytes": 300 * 57_344}
    assert llama_serve_window.cost(cfg, {"finished_tokens": 10})["flops"] \
        == 2 * 3_187_671_040 * 10


def test_roofline_names_the_bound():
    p = PEAKS["TPU v5 lite"]
    assert costs.roofline_seconds(197e12, 0, p) == (1.0, "compute")
    assert costs.roofline_seconds(0, 819e9, p) == (1.0, "memory")
    assert costs.roofline_seconds(197e12, 2 * 819e9, p) == (2.0, "memory")


def test_unknown_device_kind_is_an_error():
    import pytest

    with pytest.raises(KeyError):
        peaks_for("cpu")
