"""One benchmark: ``benchmarks/`` (ROADMAP aim 3 — benchmark code lives
outside the package and fails without a chip). Pure ast + pathlib, no jax.
"""
import ast
import functools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@functools.lru_cache(maxsize=None)
def _parsed(d):
    return [(path.relative_to(ROOT), ast.parse(path.read_text()))
            for path in sorted((ROOT / d).rglob("*.py"))]


def _trees(*dirs):
    return [pair for d in dirs for pair in _parsed(d)]


def _bench_functions_in_package():
    """Functions and methods under ``paddle_tpu/`` named ``bench_*``."""
    return [f"{path}:{node.lineno} {node.name}"
            for path, tree in _trees("paddle_tpu")
            for node in ast.walk(tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name.startswith("bench_")]


def _imports_of_bench():
    """Imports of a module named ``bench`` from code that ships."""
    found = []
    for path, tree in _trees("paddle_tpu", "tools", "examples"):
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            else:
                continue
            if any(n.split(".")[0] == "bench" for n in names):
                found.append(f"{path}:{node.lineno}")
    return found


def _makefile_scripts_missing():
    """Scripts and test files a ``python``/``python3`` recipe line of the
    Makefile names and the tree lacks."""
    text = (ROOT / "Makefile").read_text().replace("\\\n", " ")
    missing = []
    for line in text.splitlines():
        if not line.startswith("\t") or not re.search(r"\bpython3?\b", line):
            continue
        for token in line.split():
            if (token.endswith(".py") and not token.startswith("/")
                    and not (ROOT / token).is_file()):
                missing.append(token)
    return missing


@pytest.mark.parametrize("offenders", [
    _bench_functions_in_package, _imports_of_bench,
    _makefile_scripts_missing], ids=lambda f: f.__name__.strip("_"))
def test_one_benchmark(offenders):
    assert offenders() == []
