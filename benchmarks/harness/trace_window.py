"""The profiler around a part of the window, and the benchmark's own host
spans on the trace's clock."""
import shutil
import time

from . import trace_reduce
from .loader import BENCH_DIR

TRACE_DIR = BENCH_DIR / ".out" / "trace"


def span(name):
    """A host span in the profiler's own trace (free when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class TraceWindow:
    """Traces ``trace_seconds`` (the cell's file) of the window, from when
    the driver starts it. Python's own call tracer is off: it slows the host
    it measures."""

    def __init__(self, ctx):
        self.seconds = float(ctx["cell"]["trace_seconds"])
        self.running = False
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.running = True
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False

    @property
    def traced_s(self):
        return self.t_stop - self.t_start

    def reduced(self):
        """The reduction of what was traced; the device window is taken
        from the trace itself (first to last device event)."""
        t = time.perf_counter()
        events = trace_reduce.load_events(
            trace_reduce.newest_xplane(str(TRACE_DIR)))
        out = trace_reduce.reduce(events)
        n = sum(len(v) for v in events["device"].values())
        print(f"trace: {n} device events read and reduced in "
              f"{time.perf_counter() - t:.1f} s; host clock says "
              f"{self.traced_s:.3f} s traced", flush=True)
        return out
