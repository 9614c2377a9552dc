#!/usr/bin/env python3
"""The control and the faults of a cell, on the chip at the cell's own size:

    python3 benchmarks/tools/control.py --workload <name> --seeds 1 2 3 [--seconds 15]

For each seed the cell's driver reads what its ``control`` says (the plain
reference in fp8 put in the program's place, and each fault the cell can
have), one JSON line a seed; a training cell's readings carry ``correct``,
the verdict of the cell's own limits through ``result.is_correct``, which
has to be false for each. The benchmark's own runs never run this."""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks.harness import device, loader  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    cell = loader.load_cell(args.workload)
    devices = device.require_tpu(cell["cell"]["chips"])
    device.enable_compile_cache()
    driver = loader.find("drivers", cell["cell"]["driver"])
    for seed in args.seeds:
        ctx = device.context(cell, devices, seed, args.seconds, False,
                             time.perf_counter())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": driver.control(ctx)}), flush=True)


if __name__ == "__main__":
    main()
