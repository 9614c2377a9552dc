#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for (there is no CPU path: without a TPU the run says why and exits
non-zero). Everything the cell is comes from data files found by name
(see harness/loader.py). Earlier lines are facts of the run; the LAST line
of standard output is the result, as the contract of BENCHMARK.json has it.
"""
import argparse
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.harness import device, loader, result  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = loader.load_cell(args.workload)
    devices = device.require_tpu(cell["cell"]["chips"])
    import paddle_tpu  # noqa: F401  the system under test, or an exit

    print(f"device: {len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform}); compile cache at "
          f"{device.enable_compile_cache()}", flush=True)
    ctx = device.context(
        cell, devices, args.seed,
        args.seconds if args.seconds is not None else cell["run_seconds"],
        args.trace, T_START)
    out = loader.find("drivers", cell["cell"]["driver"]).run(ctx)

    block = device.device_block(devices)
    block["memory_peak_bytes"] = out["memory_peak_bytes"]
    breakdown = None
    if args.trace:
        tr = out["trace"]
        if not tr:
            sys.exit("benchmarks/run.py: the traced window holds no device "
                     "operation")
        block["busy_s"], block["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
        values = {}
        for spec in cell["per_layer"]:
            v = loader.find("readers", spec["reader"]).read(spec, out, ctx)
            if v is not None:
                values[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        values = {m["name"]: {"value": out["end_to_end"][m["name"]],
                              "unit": m["unit"]}
                  for m in cell["end_to_end"]}
    result.emit(result.is_correct(out["checks"]), out["attempted"],
                out["failed"], values, block, out["checks"], breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
