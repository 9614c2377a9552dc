#!/usr/bin/env python3
"""The one sweep that finds the knee of a serving cell: several offered
rates in one process, so set-up is paid once.

    python3 benchmarks/tools/sweep.py --workload mistral7b-chat --rates 1.5 2 2.5 3 3.5 --seconds 30

For each rate one window of the cell's mix at that rate, then its drain; a
rate is sustained when the backlog does not grow: the requests due in the
window finish within a short drain and the time to first token of the
window's last third is not above that of its first third."""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks.drivers import serve_open_loop as drv  # noqa: E402
from benchmarks.harness import device, loader, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = loader.load_cell(args.workload)
    devices = device.require_tpu(cell["cell"]["chips"])
    device.enable_compile_cache()
    ctx = device.context(cell, devices, args.seed, args.seconds, False,
                         time.perf_counter())
    eng, fe = drv.set_up(ctx)
    try:
        for rate in args.rates:
            mix = dict(cell["traffic"], rate_per_s=rate, drain_seconds=60)
            w = drv.window(ctx, fe, mix, args.seconds)
            out = drv.summarize(w, mix, args.seconds)
            rec = sorted(w["records"], key=lambda r: r.due)
            third = len(rec) // 3

            def p50(rs):
                xs = [r.chunks[0][0] - (w["t0"] + r.due) for r in rs
                      if r.chunks]
                return traffic.nearest_rank(xs, 50) * 1e3 if xs else None

            print(json.dumps({
                "rate_per_s": rate, "due": out["attempted"],
                "failed": out["failed"],
                "drain_s": out["facts"]["drain_s"],
                "ttft_p50_first_third_ms": p50(rec[:third]),
                "ttft_p50_last_third_ms": p50(rec[-third:]),
                **{k: v for k, v in out["end_to_end"].items()
                   if k != "setup_s"}}), flush=True)
    finally:
        fe.shutdown()


if __name__ == "__main__":
    main()
