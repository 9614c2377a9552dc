"""Device time by the layer of the model that caused it, in ms per step.

Every operation of a compiled program carries, in the trace, the jax name
stack it was compiled from (``harness/trace_stats.py`` says where), and the
program puts its layers into that stack: ``.../gpt/h/3/attn/...`` from
``nn.Layer.__call__``, ``lm_head`` and ``optimizer`` where the model and the
optimizer say so, a Pallas kernel's ``name=``. This reader re-opens the
run's ``.xplane.pb``, takes the operations of one program (containers left
out, as ``trace_reduce.reduce`` does), puts each into exactly ONE group (the
first whose pattern is found in ``"<scope> <operation>"``; the groups and
their order are one file under ``benchmarks/scopes/``, shared by the metrics
that read it, and its last group takes the rest) and returns the metric's
group's summed device time over the traced steps. A fusion is attributed by
the scope its event carries (the fusion's root). Once per run it prints the
table: group x forward / backward (``transpose(`` in the scope) x ms per
step, then each Pallas kernel by its own name with ms and calls per step.

A program that carries no scope (a parent commit from before the scopes, a
CPU trace) matches no named group: nothing is returned, nothing raised.
"""
import json
import re

from ..harness import trace_host, trace_reduce, trace_stats
from ..harness.loader import BENCH_DIR, BenchmarkError
from ..harness.trace_window import TRACE_DIR

CONTAINERS = (" while", " conditional", " call")
# a Pallas kernel's instruction is named by the kernel's ``name=``
KERNEL = re.compile(r"^%([\w\-]+?)(?:\.\d+)? .*tpu_custom_call$")
_tables = {}


def load_scopes(name):
    try:
        with open(BENCH_DIR / "scopes" / f"{name}.json") as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(
            f"no file benchmarks/scopes/{name}.json") from None


def group_of(groups, scope, name):
    """The first group whose pattern is found in "<scope> <name>"."""
    text = f"{scope} {name}"
    for group, rx in groups:
        if rx.search(text):
            return group
    raise BenchmarkError(f"no group takes {text!r}: the last pattern of a "
                         "scopes file must match everything")


def table(ops, scopes, steps):
    """{"groups": {group: {"fwd": ms, "bwd": ms}}, "kernels": {name:
    {"ms": ms, "calls": n}}, "total_ms": ms, "unscoped_ms": ms, "named":
    n}, all per step and averaged over the chips: the operations of
    ``scopes["program"]``. ``named`` counts the operations a pattern other
    than the last one claimed."""
    groups = [(g, re.compile(p)) for g, p in scopes["groups"]]
    backward = re.compile(scopes["backward"])
    rest = groups[-1][0]
    out = {g: {"fwd": 0.0, "bwd": 0.0} for g, _ in groups}
    kernels, named, unscoped = {}, 0, 0.0
    chips = ops["device"]
    per = 1e6 * max(steps, 1) * max(len(chips), 1)  # ns -> ms per step
    for chip, events in chips.items():
        at = trace_host.program_at(ops["modules"].get(chip, []))
        for name, start, dur, scope in events:
            if name.endswith(CONTAINERS) or at(start) != scopes["program"]:
                continue
            group = group_of(groups, scope, name)
            named += group != rest
            out[group]["bwd" if backward.search(scope) else "fwd"] += dur / per
            if not scope:
                unscoped += dur / per
            k = KERNEL.match(name)
            if k:
                row = kernels.setdefault(k.group(1), {"ms": 0.0, "calls": 0})
                row["ms"] += dur / per
                row["calls"] += 1e6 / per
    return {"groups": out, "kernels": kernels, "named": named,
            "unscoped_ms": unscoped,
            "total_ms": sum(v["fwd"] + v["bwd"] for v in out.values())}


def print_table(t):
    print("trace_scope: device time by layer, ms per step "
          "(forward, backward, both)", flush=True)
    for group, v in t["groups"].items():
        print(f"trace_scope:   {group:<12} {v['fwd']:10.3f} {v['bwd']:10.3f} "
              f"{v['fwd'] + v['bwd']:10.3f}", flush=True)
    print(f"trace_scope:   {'all':<12} {'':10} {'':10} {t['total_ms']:10.3f}"
          f"   ({t['unscoped_ms']:.3f} ms in operations that carry no scope)",
          flush=True)
    for name, row in sorted(t["kernels"].items()):
        print(f"trace_scope:   kernel {name}: {row['ms']:.3f} ms and "
              f"{row['calls']:.2f} calls per step", flush=True)


def read(spec, out, ctx):
    tr = out.get("trace")
    steps = out.get("facts", {}).get("traced_steps")
    if not tr or not steps:
        return None
    p = spec["params"]
    try:
        path = trace_reduce.newest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (path, p["scopes"], steps)
    if key not in _tables:
        t = table(trace_stats.load_ops(path), load_scopes(p["scopes"]), steps)
        _tables.clear()
        _tables[key] = t
        print_table(t)
        print(f"trace_scope: the groups sum to {t['total_ms']:.3f} ms per "
              f"step; busy_s over {steps} traced steps is "
              f"{1e3 * tr['busy_s'] / steps:.3f} ms", flush=True)
    t = _tables[key]
    if not t["named"]:
        return None  # the program put no layer into its operations' names
    v = t["groups"][p["group"]]
    return v["fwd"] + v["bwd"]
