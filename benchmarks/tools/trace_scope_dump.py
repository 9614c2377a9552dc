#!/usr/bin/env python3
"""Look at what the profiler knows of each executed operation: the stats on
the events' METADATA (``trace_dump.py`` shows the events' own), which is
where the chip's trace carries an operation's jax name stack and a Pallas
kernel's name; then the table ``readers/trace_scope.py`` makes of the trace,
and (``--record``) a small sample of one run's operations WITH their
scope, in the form that reader's test reads. Needs no jax and no chip.

    python3 benchmarks/tools/trace_scope_dump.py [xplane.pb] \
        [--scopes gpt2-train] [--steps N] [--record out.json]
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks.harness import trace_reduce, trace_stats  # noqa: E402
from benchmarks.harness.trace_window import TRACE_DIR  # noqa: E402
from benchmarks.readers import trace_scope  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane", nargs="?")
    ap.add_argument("--scopes", default="gpt2-train")
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--record")
    ap.add_argument("--events", type=int, default=600)
    args = ap.parse_args()
    path = args.xplane or trace_reduce.newest_xplane(str(TRACE_DIR))
    for chip, plane in sorted(trace_stats.load_planes(path).items()):
        print(f"chip {chip}: {len(plane['ops'])} operations, "
              f"{len(plane['modules'])} programs, {len(plane['meta'])} "
              "instructions")
        keys, total = {}, {}
        for _, _, stats in plane["meta"].values():
            for k in stats:
                keys[k] = keys.get(k, 0) + 1
        print(f"  stats on the instructions' metadata: {keys}")
        for k, _, d in plane["ops"]:
            total[k] = total.get(k, 0) + d
        top = sorted(total, key=lambda k: -total[k])[:12]
        kernels = [k for k in total
                   if "tpu_custom_call" in plane["meta"][k][0]][:6]
        for k in top + kernels:
            name, display, stats = plane["meta"][k]
            print(f"  {total[k] / 1e6:10.3f} ms  {name[:80]!r} "
                  f"display {display!r}")
            for sk, sv in stats.items():
                print(f"      {sk}: {str(sv)[:300]}")
    ops = trace_stats.load_ops(path)
    table = trace_scope.table(ops, trace_scope.load_scopes(args.scopes),
                              args.steps)
    trace_scope.print_table(table)
    if args.record:
        # every n-th operation of the program's first whole run and its
        # 24 longest, so that a few hundred events hold forward, backward,
        # optimizer, the head's few large fusions and a kernel alike
        chip = min(ops["device"])
        program = trace_scope.load_scopes(args.scopes)["program"]
        run = next(m for m in ops["modules"][chip] if m[0] == program)
        dev = [e for e in ops["device"][chip]
               if run[1] <= e[1] < run[1] + run[2]]
        longest = sorted(dev, key=lambda e: -e[2])[:24]
        dev = sorted(set(dev[::max(1, -(-len(dev) // args.events))]
                         + longest), key=lambda e: e[1])
        with open(args.record, "w") as f:
            json.dump({"device": {str(chip): dev},
                       "modules": {str(chip): [run]}}, f)
        print(f"recorded {len(dev)} operations of one {program} on chip "
              f"{chip}")

if __name__ == "__main__":
    main()
