#!/usr/bin/env python3
"""Where a training cell's set-up goes, read from inside the program:

    python3 benchmarks/tools/setup_split.py --workload <name> --seed N [--seconds 50]

Runs the cell as ``benchmarks/run.py --trace 0`` does, in this process and
through the same driver, unchanged, and reads the program's compile telemetry
(``paddle_tpu/framework/compile_cache.py``: every trace, lowering and backend
compile or persistent-cache load jax does) at marks the driver's own calls
give: its first line (``run``), around ``build_program``, and its ``setup:``
and ``window:`` lines (each checked step, the window's start and end).
``setup_s`` splits into five groups, disjoint by construction, that sum to it:

    import   process start to the driver's first line (imports, backend
             init, cache set-up), less any compile phase in it
    trace    jaxpr trace + lowering of every program made before the window
    compile  backend compile or cache load of every program before the window
    build    ``build_program``'s wall time less the phases inside it
    other    the rest: the checked steps' and the reading programs'
             execution, transfers

beside the programs compiled and loaded before the window, those in the
window (there should be none), the same phases by stretch of set-up, and the
programs that took longest by jax's ``fun_name`` (inclusive of the phases
nested in them). The split is the last line, one JSON object; the process then
exits, as the plain reference after the window reads nothing of set-up.
The benchmark's own runs do not take these marks (``PERF.md`` section 7).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

PHASES = {"trace": "paddle_jit_trace_seconds",
          "lower": "paddle_jit_lower_seconds",
          "backend": "paddle_jit_backend_seconds"}
COUNTS = {"compiled": "paddle_jit_backend_compiles_total",
          "loaded": "paddle_jit_cache_loads_total"}
ZERO = dict.fromkeys(list(PHASES) + list(COUNTS), 0.0)
# jax's own events of the three phases, heard here by program name
EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "backend"}
# the stretches of set-up, between consecutive marks
ORDER = ("start", "driver", "build_start", "build_end", "step1", "step2",
         "step3", "window_start", "window_end")


def totals(snapshot):
    """The compile phases' seconds and the programs' counts so far, from a
    ``REGISTRY.snapshot()``; zeros where a program has no listeners."""
    out = dict(ZERO)
    for key, name in PHASES.items():
        if name in snapshot:
            out[key] = sum(s["sum"]
                           for s in snapshot[name]["series"].values())
    for key, name in COUNTS.items():
        if name in snapshot:
            out[key] = sum(snapshot[name]["values"].values())
    return out


def delta(at, a, b, *keys):
    return sum(at[b][1][k] - at[a][1][k] for k in keys)


def split(at, setup_s):
    """The five groups of ``setup_s`` from the marks ``at`` ({name: (host
    clock, totals)}), and the programs counted before and in the window."""
    fe, be = ("trace", "lower"), ("backend",)
    wall = lambda a, b: at[b][0] - at[a][0]  # noqa: E731
    groups = {
        "import": wall("start", "driver")
        - delta(at, "start", "driver", *fe, *be),
        "trace": delta(at, "start", "window_start", *fe),
        "compile": delta(at, "start", "window_start", *be),
        "build": wall("build_start", "build_end")
        - delta(at, "build_start", "build_end", *fe, *be),
    }
    groups["other"] = setup_s - sum(groups.values())
    programs = {k: at["window_start"][1][k] - at["start"][1][k]
                for k in COUNTS}
    programs["in_window"] = delta(at, "window_start", "window_end", *COUNTS)
    return groups, programs


def stretches(at):
    """{stretch: wall, phases, counts} between each pair of consecutive
    marks taken."""
    names = [n for n in ORDER if n in at]
    return {f"{a}-{b}": dict(
        wall=at[b][0] - at[a][0],
        **{k: at[b][1][k] - at[a][1][k] for k in ZERO})
        for a, b in zip(names, names[1:])}


class WindowClosed(Exception):
    """Raised from the driver's ``window:`` line: set-up is read."""


class Marks:
    """Takes the marks from inside the driver while it is entered, by
    wrapping its ``run`` and ``build_program`` and the ``print`` its module
    calls, and counts each program's phases by name until the window
    opens; leaving puts the driver back as it was."""

    def __init__(self, driver):
        self.at = {"start": (T_START, dict(ZERO))}
        self.setup_s = None
        self.by_name = defaultdict(lambda: [0, 0.0])
        self.driver = driver

    def __enter__(self):
        import jax.monitoring

        driver = self.driver
        self.was = driver.run, driver.build_program
        run, build = self.was

        def run_marked(ctx):
            self.take("driver")
            return run(ctx)

        def build_marked(*args, **kwargs):
            self.take("build_start")
            try:
                return build(*args, **kwargs)
            finally:
                self.take("build_end")

        driver.run, driver.build_program = run_marked, build_marked
        driver.print = self.print
        jax.monitoring.register_event_duration_secs_listener(self.hear)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self.hear)
        self.driver.run, self.driver.build_program = self.was
        del self.driver.print

    def take(self, name):
        from paddle_tpu.observability import REGISTRY

        self.at[name] = (time.perf_counter(), totals(REGISTRY.snapshot()))

    def print(self, *args, **kwargs):
        line = str(args[0]) if args else ""
        if line.startswith("setup: step "):
            self.take("step" + line.split()[2])
        elif line.endswith("to window start"):
            self.take("window_start")
            self.setup_s = float(line.split()[1])
        elif line.startswith("window: "):
            self.take("window_end")
        print(*args, **kwargs)
        if "window_end" in self.at:
            raise WindowClosed

    def hear(self, event, seconds, fun_name="?", **kwargs):
        """jax's phase events by program name, until the window opens."""
        phase = EVENTS.get(event)
        if phase and "window_start" not in self.at:
            self.by_name[phase, fun_name][0] += 1
            self.by_name[phase, fun_name][1] += seconds

    def top(self, n=8):
        out = {}
        for phase in PHASES:
            rows = sorted(((name, c, s) for (p, name), (c, s)
                           in self.by_name.items() if p == phase),
                          key=lambda r: -r[2])
            out[phase] = {"events": sum(r[1] for r in rows),
                          "names": len(rows),
                          "longest": [[r[0], r[1], round(r[2], 4)]
                                      for r in rows[:n]]}
        return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run
    from benchmarks.harness import loader

    cell = loader.load_cell(args.workload)
    if cell["cell"]["driver"] != "train_steps":
        sys.exit(f"setup_split: {args.workload} is not a training cell")
    bench_run.T_START = T_START
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "0"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    with Marks(loader.find("drivers", "train_steps")) as marks:
        try:
            bench_run.main(argv)
        except WindowClosed:
            pass
        else:
            sys.exit("setup_split: the driver printed no window line")
    groups, programs = split(marks.at, marks.setup_s)
    print("setup split: " + ", ".join(f"{k} {v:.3f} s"
                                      for k, v in groups.items())
          + f" = {sum(groups.values()):.3f} of setup_s {marks.setup_s:.3f}; "
          f"{programs['compiled']:.0f} programs compiled and "
          f"{programs['loaded']:.0f} loaded before the window, "
          f"{programs['in_window']:.0f} in it", flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "setup_s": marks.setup_s, "setup": groups,
                      "programs": programs,
                      "stretches": stretches(marks.at),
                      "top": marks.top()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
