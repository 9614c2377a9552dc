"""Profiler facade (reference: python/paddle/profiler/profiler.py over the
C++ host/CUPTI tracers — SURVEY.md §5.1).

TPU-native: ``jax.profiler`` (XProf) is the device tracer; host annotations
via ``jax.profiler.TraceAnnotation``. The reference's scheduler
(wait/warmup/active windows keyed by step) and summary UX are preserved;
the trace itself is an XProf artifact viewable in tensorboard.
"""
from __future__ import annotations

import contextlib
import os
import time
from enum import Enum
from typing import Callable, Optional

import jax

from ..observability.tracing import annotation as _annotation

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "mfu",
]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-keyed state machine (reference: paddle.profiler.make_scheduler)."""
    period = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback: the XProf trace directory is the artifact."""

    def handler(prof: "Profiler"):
        prof._last_export = dir_name

    handler._dir = dir_name
    return handler


class RecordEvent:
    """Host-span annotation (reference: paddle.profiler.RecordEvent →
    here jax.profiler.TraceAnnotation so spans appear in XProf). Ends in
    ``observability.tracing.annotation``, the same call the tracer's
    spans (``tracing.span``) reach the profiler through: one mechanism,
    and a RecordEvent is the span that keeps no ring record."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self._t0 = None

    def begin(self):
        self._ann = _annotation(self.name)
        self._t0 = time.perf_counter()

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False):
        if isinstance(scheduler, tuple):
            lo, hi = scheduler
            scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo, repeat=1)
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._dir = getattr(on_trace_ready, "_dir", None) or os.path.join(
            os.getcwd(), "profiler_log"
        )
        self._last_export = None
        self._step_times = []
        self._t_last = None

    # --------------------------------------------------------------- control
    def start(self):
        self._t_last = time.perf_counter()
        self._transition()

    def stop(self):
        # the final in-flight step (started by the last step()/start())
        # used to be dropped — its time belongs in the summary
        if self._t_last is not None:
            self._step_times.append(time.perf_counter() - self._t_last)
            self._t_last = None
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self._on_trace_ready:
                self._on_trace_ready(self)

    def step(self):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now
        self._step += 1
        self._transition()

    def _transition(self):
        state = (self._scheduler(self._step) if self._scheduler
                 else ProfilerState.RECORD)
        if self._timer_only:
            self._state = state
            return
        should_trace = state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if should_trace and not self._tracing:
            os.makedirs(self._dir, exist_ok=True)
            jax.profiler.start_trace(self._dir)
            self._tracing = True
        elif not should_trace and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self._on_trace_ready:
                self._on_trace_ready(self)
        self._state = state

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # --------------------------------------------------------------- summary
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np

        ts = np.asarray(self._step_times) * 1e3
        steps_per_sec = 1e3 * len(ts) / ts.sum() if ts.sum() > 0 else 0.0
        lines = [
            "---- step time summary ----",
            f"steps: {len(ts)}   mean: {ts.mean():.2f} ms   p50: {np.percentile(ts, 50):.2f} ms"
            f"   p90: {np.percentile(ts, 90):.2f} ms   p99: {np.percentile(ts, 99):.2f} ms"
            f"   max: {ts.max():.2f} ms",
            f"steps/sec: {steps_per_sec:.2f}",
        ]
        if self._last_export:
            lines.append(f"trace exported to: {self._last_export}")
        return "\n".join(lines)


def mfu(n_params: int, tokens_per_sec_per_chip: float,
        peak_flops_per_chip: Optional[float] = None,
        flops_per_token: Optional[float] = None) -> float:
    """North-star runtime readout (BASELINE.md convention: 6N model FLOPs,
    remat excluded, per-chip over per-chip)."""
    if peak_flops_per_chip is None:
        from ..analysis.jaxpr.cost import peak_flops

        # raises on a device kind the table does not hold (CPU included):
        # an MFU against another chip's peak is not an MFU
        peak_flops_per_chip = peak_flops(jax.devices()[0])
    fpt = flops_per_token if flops_per_token is not None else 6.0 * n_params
    return tokens_per_sec_per_chip * fpt / peak_flops_per_chip
