"""BENCHMARK.json against the rules of its contract that a file can be
checked for, and against the files it names."""
import json
import re

import pytest
from conftest import ROOT, with_pending

from benchmarks.harness import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loader.benchmark()


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"][1].startswith("benchmarks/")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e["name"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("pending", [False, True])
def test_every_cell_loads_and_reports_what_it_must(bench, pending,
                                                   monkeypatch):
    if pending:
        bench = with_pending()
        monkeypatch.setattr(loader, "benchmark", with_pending)
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    used = set()
    for w in bench["workloads"]:
        cell = loader.load_cell(w["name"])
        used.add(w["config"])
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])
            loader.find("readers", m["reader"])
            if "cost" in m["params"]:
                loader.find("costs", m["params"]["cost"])
        loader.find("drivers", cell["cell"]["driver"])
        loader.find("reference", cell["config"]["reference"])
        loader.find("builders", cell["config"]["builder"])
    assert used == {c["name"] for c in bench["configs"]}


def test_configurations_say_what_was_cut():
    files = set()
    for c in with_pending()["configs"]:
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not key.endswith(("_dim", "_rank", "_size")), key
