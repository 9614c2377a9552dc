"""paddle_tpu.observability — low-overhead runtime telemetry.

The profiler (``paddle_tpu/profiler``) answers episodic questions with
traces; this package answers *continuous* ones with metrics: TTFT/TPOT
histograms and scheduler gauges from the paged serving engine, compile /
retrace counters from the jit path, exported as Prometheus text
(``start_metrics_server``), JSONL snapshots, and TensorBoard scalars
(``TBEventsBridge``).

Hard rule: recording happens on the HOST, outside traced code — a metric
call inside a jit-traced function runs once at trace time (or captures a
tracer) and is flagged by tpulint rule TPL601.

Pure stdlib at import time; safe to import from anywhere in the tree.
"""
from .metrics import (
    LATENCY_BUCKETS,
    REGISTRY,
    SIZE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    gauge,
    histogram,
)
from .export import (
    JsonlSink,
    MetricsServer,
    TBEventsBridge,
    render_prometheus,
    start_metrics_server,
    write_jsonl_snapshot,
)
from .tracing import (
    TRACER,
    Span,
    SpanContext,
    Tracer,
    configure_tracing,
    flight_record,
    get_tracer,
    instant,
    span,
    ttft_decomposition_summary,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "LATENCY_BUCKETS", "SIZE_BUCKETS",
    "counter", "gauge", "histogram",
    "render_prometheus", "MetricsServer", "start_metrics_server",
    "write_jsonl_snapshot", "JsonlSink", "TBEventsBridge",
    "metric_total", "histogram_summary",
    "Tracer", "TRACER", "Span", "SpanContext", "configure_tracing",
    "get_tracer", "span", "instant", "flight_record",
    "ttft_decomposition_summary",
]


def metric_total(name: str, registry: Registry = REGISTRY) -> float:
    """Sum of a counter/gauge across all label series; 0.0 if absent.
    Convenience for embedding single numbers."""
    m = registry.get(name)
    if m is None:
        return 0.0
    return float(sum(leaf.value for _, leaf in m.series()))


def histogram_summary(name: str, registry: Registry = REGISTRY) -> dict:
    """count/sum/mean/p50/p90/p99/max of a histogram's unlabeled series
    (or the merge across label series); {} if absent."""
    m = registry.get(name)
    if not isinstance(m, Histogram):
        return {}
    leaves = [leaf for _, leaf in m.series()]
    if len(leaves) == 1:
        return leaves[0].summary()
    out = {"count": sum(l.count for l in leaves),
           "sum": sum(l.sum for l in leaves)}
    out["mean"] = out["sum"] / out["count"] if out["count"] else 0.0
    out["max"] = max((l._max for l in leaves), default=0.0)
    return out
