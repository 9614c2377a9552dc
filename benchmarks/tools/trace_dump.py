#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, the operations that took most
time with one event's stats each, and (``--record``) a small sample of the
device's events in the form the reducer's test reads.

    python3 benchmarks/tools/trace_dump.py [xplane.pb] [--record out.json]
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks.harness import trace_reduce  # noqa: E402
from benchmarks.harness.trace_window import TRACE_DIR  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane", nargs="?")
    ap.add_argument("--record")
    ap.add_argument("--events", type=int, default=400)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    path = args.xplane or trace_reduce.newest_xplane(str(TRACE_DIR))
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            totals, sample = {}, {}
            for e in events:
                totals[e.name] = totals.get(e.name, 0) + e.duration_ns
                sample.setdefault(e.name, e)
            for name, t in sorted(totals.items(), key=lambda kv: -kv[1])[:25]:
                stats = {k: str(v)[:120] for k, v in sample[name].stats}
                print(f"    {t / 1e6:10.3f} ms  {name[:100]}  {stats}")
    ev = trace_reduce.load_events(path)
    red = trace_reduce.reduce(ev)
    print(f"reduced: window {red['window_s']:.4f} s, busy {red['busy_s']:.4f}"
          f" s, idle gaps {red['idle_gaps']}")
    by_module = {}
    for name, t in red["by_name_s"].items():
        mod = name.split("/", 1)[0] if "/" in name else "(no module)"
        by_module[mod] = by_module.get(mod, 0) + t
    for mod, t in sorted(by_module.items(), key=lambda kv: -kv[1]):
        print(f"  module {mod}: {t:.4f} s")
    print("operations, most time first:")
    for name, t in list(red["by_name_s"].items())[:40]:
        print(f"  {t * 1e3:10.3f} ms  {name}")
    print("Pallas kernels (tpu_custom_call):")
    for name, t in red["by_name_s"].items():
        if "tpu_custom_call" in name:
            print(f"  {t * 1e3:10.3f} ms  {name}")
    if args.record:
        chip = min(ev["device"])
        dev = ev["device"][chip][: args.events]
        lo, hi = dev[0][1], dev[-1][1] + dev[-1][2]
        inside = lambda xs: [x for x in xs if x[1] + x[2] > lo and x[1] < hi]
        host = inside(ev["host"])
        with open(args.record, "w") as f:
            json.dump({"device": {str(chip): dev},
                       "modules": {str(chip): inside(
                           ev["modules"].get(chip, []))},
                       "host": host}, f)
        print(f"recorded {len(dev)} device and {len(host)} host events")


if __name__ == "__main__":
    main()
