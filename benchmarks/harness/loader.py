"""Find a cell and everything it names, by name, from data files.

``BENCHMARK.json`` (root of the checkout) is the contract with the driver;
the files below hold what one cell, configuration, traffic mix or per-layer
metric needs, so a later PR adds files and entries and edits none:

    benchmarks/cells/<workload>.json          driver, trace window, limits
    benchmarks/configs/<config>.json          the sizes as run, with source
    benchmarks/traffic/<traffic>.json         parameters of the traffic mix
    benchmarks/layer_metrics/<metric>.json    reader, its parameters, layer

and the code one of them names is a module of its own, found by that name
(``find``): ``drivers/``, ``readers/``, ``costs/``, ``builders/``,
``reference/``.
"""
import importlib
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent


class BenchmarkError(RuntimeError):
    """The benchmark's own files do not fit together."""


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"no file {path.relative_to(ROOT)}") from None


def benchmark():
    return _load(ROOT / "BENCHMARK.json")


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(f"BENCHMARK.json lists no {what} named {name!r}")


def load_cell(workload):
    """The cell's entry of BENCHMARK.json joined with its own files."""
    bench = benchmark()
    entry = _entry(bench["workloads"], workload, "workload")
    cell = _load(BENCH_DIR / "cells" / f"{workload}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise BenchmarkError(
                f"cell {workload}: {key} is {cell[key]!r} in its file and "
                f"{entry[key]!r} in BENCHMARK.json")
    cfg_entry = _entry(bench["configs"], cell["config"], "config")
    config = _load(ROOT / cfg_entry["file"])
    traffic = _load(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    here = lambda m: workload in m.get("workloads", [workload])
    end_to_end = [m for m in bench["end_to_end"] if here(m)]
    per_layer = []
    for m in bench["per_layer"]:
        if not here(m):
            continue
        spec = _load(BENCH_DIR / "layer_metrics" / f"{m['name']}.json")
        for key in ("unit", "layer", "moves"):
            if spec[key] != m[key]:
                raise BenchmarkError(
                    f"per-layer metric {m['name']}: {key} differs between "
                    "its file and BENCHMARK.json")
        per_layer.append(dict(spec, name=m["name"]))
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}


def find(kind, name):
    """Module ``benchmarks.<kind>.<name>`` (a driver, a reader, a cost, a
    builder, a reference), found by the name a data file gives."""
    try:
        return importlib.import_module(f"benchmarks.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.{kind}.{name}":
            raise
        raise BenchmarkError(
            f"no module benchmarks/{kind}/{name}.py") from None
