"""A tiny-configuration rehearsal of ``tools/setup_split.py`` through the
training driver, unchanged: the five groups sum to ``setup_s``."""
import importlib.util
import json
from pathlib import Path

import pytest


def test_split_of_a_tiny_training_run(tiny_cells, capsys):
    spec = importlib.util.spec_from_file_location(
        "setup_split",
        Path(__file__).resolve().parent.parent / "tools" / "setup_split.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--workload", "gpt2m-pretrain", "--seed",
                      str(2**31 + 13), "--seconds", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    groups = line["setup"]
    assert list(groups) == ["import", "trace", "compile", "build", "other"]
    assert sum(groups.values()) == pytest.approx(line["setup_s"], abs=1e-9)
    assert all(v >= 0 for v in groups.values())
    assert groups["trace"] > 0 and groups["compile"] > 0
    assert line["programs"]["in_window"] == 0
    assert line["programs"]["compiled"] + line["programs"]["loaded"] > 1
    # the step program's phases fall inside step 1
    step1 = line["stretches"]["build_end-step1"]
    assert step1["trace"] + step1["lower"] + step1["backend"] < step1["wall"]
    # the driver is put back as it was
    from benchmarks.drivers import train_steps
    assert "print" not in vars(train_steps)
    assert train_steps.run.__name__ == "run"
