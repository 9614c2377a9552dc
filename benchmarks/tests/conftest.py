"""Tests of the benchmark's own code, on the CPU at tiny sizes:

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not among the repo's tier-1 tests (``tests/``)."""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))
TINY = Path(__file__).resolve().parent / "tiny"

from benchmarks.harness import device, loader  # noqa: E402
from benchmarks.harness.peaks import PEAKS  # noqa: E402

TINY_MIX = {
    "rate_per_s": 6.0, "warm_seconds": 1, "drain_seconds": 30,
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                   "min": 8, "max": 64},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.7,
                   "min": 4, "max": 40}}


# limits at the tiny sizes, set as the cells' own are set at theirs: between
# what the program reads there (gradient norms
# 0.0028, update norms 0.012, served-token gap 0.0) and what the fp8 control
# reads (gradient norms 0.016-0.019, update norms 0.038-0.045, gap 0.019);
# the third step's loss between the program's 5e-6 to 3e-5 and the 1.8e-4 to
# 3.1e-4 of a state left unchanged
TINY_LIMITS = {
    "train_steps": {"loss3_gap": 1e-4,
                    "grad_norm_gap": 0.008, "update_norm_gap": 0.025},
    "serve_open_loop": {"served_logit_gap": 0.005}}


def with_pending():
    """BENCHMARK.json with the entries of ``benchmarks/pending/*.json``
    (cells kept out of the benchmark until their bounds are measured)
    added, so that their files stay rehearsed."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for path in sorted((ROOT / "benchmarks" / "pending").glob("*.json")):
        more = json.loads(path.read_text())
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[group] = bench[group] + more[group]
    return bench


@pytest.fixture
def pending_cells(monkeypatch):
    monkeypatch.setattr(loader, "benchmark", with_pending)


@pytest.fixture
def tiny_cells(monkeypatch, pending_cells):
    """The cells (pending ones too) with their configuration and traffic
    cut to a size the CPU holds, and the harness's look for a chip skipped:
    every other file (cell, metrics, BENCHMARK.json) is the real one."""
    import jax

    real = loader.load_cell

    def load(workload):
        cell = real(workload)
        tiny = {"gpt2-medium": "gpt2-tiny", "mistral-7b-v0.3": "llama-tiny"}
        with open(TINY / f"{tiny[cell['cell']['config']]}.json") as f:
            cell["config"] = json.load(f)
        if cell["traffic"]["kind"] == "train_steps":
            cell["traffic"] = dict(cell["traffic"], batch=4, seq=128)
        else:
            cell["traffic"] = dict(cell["traffic"], **TINY_MIX)
        cell["cell"] = dict(cell["cell"], trace_seconds=1,
                            min_compared_tokens=20,
                            limits=TINY_LIMITS[cell["cell"]["driver"]])
        return cell

    monkeypatch.setattr(loader, "load_cell", load)
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "memory_peak_bytes", lambda d: 1)
    monkeypatch.setattr(device, "memory_line", lambda *a: None)
    monkeypatch.setattr(device, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(device, "peaks_for",
                        lambda k: PEAKS["TPU v5 lite"])
    import benchmarks.run as run

    return run
