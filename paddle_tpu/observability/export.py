"""Metric export surfaces: Prometheus text exposition (+HTTP endpoint),
JSONL snapshots, and the TensorBoard bridge over ``utils/tbevents``.

Three consumers, one registry:

* **Prometheus** — the operational scrape for a serving deployment
  (``examples/serve_llama_paged.py --metrics-port``). Text exposition
  format 0.0.4; histograms emit the standard cumulative ``_bucket{le=}``
  / ``_sum`` / ``_count`` triple, so stock Prometheus/Grafana histogram
  functions (``histogram_quantile``) work unmodified.
* **JSONL** — one self-contained snapshot line per call, append-only:
  the plain-tooling sink (jq, pandas).
* **TensorBoard** — training runs already write scalars through
  ``utils/tbevents.EventFileWriter``; the bridge publishes the same
  registry there, mapping metric ``name{label="v"}`` to tag
  ``metrics/name/label=v`` and histograms to ``/count|mean|p50|p99``
  sub-tags.

The HTTP server is stdlib ``ThreadingHTTPServer`` on a daemon thread —
scrapes read the registry without locks (GIL-consistent floats; a scrape
racing an update sees a value at most one sample stale), so serving
``/metrics`` never stalls the scheduler.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, Optional

from .metrics import REGISTRY, Histogram, Registry, _label_key

__all__ = [
    "render_prometheus", "MetricsServer", "start_metrics_server",
    "write_jsonl_snapshot", "JsonlSink", "TBEventsBridge",
]


# ------------------------------------------------------ prometheus text


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return (s.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(pairs) -> str:
    if not pairs:
        return ""
    inner = ",".join(f'{n}="{_escape_label(v)}"' for n, v in pairs)
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def render_prometheus(registry: Optional[Registry] = None) -> str:
    """Text exposition format 0.0.4 for every metric in the registry."""
    registry = registry or REGISTRY
    lines = []
    for m in registry.collect():
        if m.help:
            lines.append(f"# HELP {m.name} {_escape_help(m.help)}")
        lines.append(f"# TYPE {m.name} {m.kind}")
        for key, leaf in m.series():
            pairs = m.label_pairs(key)
            if isinstance(m, Histogram):
                cum = leaf.cumulative()
                for bound, c in zip(leaf.bounds, cum[:-1]):
                    lines.append(
                        f"{m.name}_bucket"
                        f"{_fmt_labels(pairs + [('le', _fmt_value(bound))])}"
                        f" {c}")
                lines.append(
                    f"{m.name}_bucket"
                    f"{_fmt_labels(pairs + [('le', '+Inf')])} {cum[-1]}")
                lines.append(
                    f"{m.name}_sum{_fmt_labels(pairs)} "
                    f"{_fmt_value(leaf.sum)}")
                lines.append(
                    f"{m.name}_count{_fmt_labels(pairs)} {leaf.count}")
            else:
                lines.append(
                    f"{m.name}{_fmt_labels(pairs)} {_fmt_value(leaf.value)}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------- HTTP server


class MetricsServer:
    """Prometheus scrape endpoint on a daemon thread.

    ``port=0`` binds an ephemeral port (tests); the bound port is
    ``.port``. Serves ``GET /metrics``; anything else is 404. ``close()``
    shuts the listener down (idempotent).
    """

    def __init__(self, port: int = 0, registry: Optional[Registry] = None,
                 host: str = ""):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        registry = registry or REGISTRY

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path.split("?")[0] != "/metrics":
                    self.send_error(404)
                    return
                body = render_prometheus(registry).encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass  # scrapes every few seconds would spam stderr

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="paddle-metrics",
            daemon=True)
        self._thread.start()

    def close(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


def start_metrics_server(port: int = 0,
                         registry: Optional[Registry] = None,
                         host: str = "") -> MetricsServer:
    """Start serving ``/metrics`` in the background; returns the server
    (``.port`` has the bound port, ``.close()`` stops it)."""
    return MetricsServer(port=port, registry=registry, host=host)


# ----------------------------------------------------------- JSONL sink


def write_jsonl_snapshot(path: str, registry: Optional[Registry] = None,
                         extra: Optional[Dict] = None) -> Dict:
    """Append one self-contained snapshot line to ``path``. Returns the
    record written (callers may embed it)."""
    registry = registry or REGISTRY
    record = {"ts": time.time(), "metrics": registry.snapshot()}
    if extra:
        record.update(extra)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
    return record


class JsonlSink:
    """Bound (path, registry) snapshot writer for periodic dumps."""

    def __init__(self, path: str, registry: Optional[Registry] = None):
        self.path = path
        self.registry = registry or REGISTRY

    def write(self, extra: Optional[Dict] = None) -> Dict:
        return write_jsonl_snapshot(self.path, self.registry, extra)


# ----------------------------------------------------- tbevents bridge


class TBEventsBridge:
    """Publish the registry into TensorBoard scalars via the native
    ``utils/tbevents.EventFileWriter`` (no torch, no tensorboard pip).

    Tag mapping (documented in README "Observability"):

    * counter/gauge ``name`` → ``metrics/name``
    * labeled series ``name{a="x",b="y"}`` → ``metrics/name/a=x,b=y``
    * histogram ``name`` → ``metrics/name/count``, ``/mean``, ``/p50``,
      ``/p99`` (per label series, same label path rule)

    Training callbacks (``hapi.callbacks.VisualDL``) write into the same
    log_dir, so one TensorBoard run shows losses and runtime telemetry
    side by side.
    """

    def __init__(self, writer_or_logdir, registry: Optional[Registry] = None,
                 prefix: str = "metrics/"):
        if isinstance(writer_or_logdir, str):
            from ..utils.tbevents import EventFileWriter

            self._writer = EventFileWriter(writer_or_logdir)
            self._owns_writer = True
        else:
            self._writer = writer_or_logdir
            self._owns_writer = False
        self.registry = registry or REGISTRY
        self.prefix = prefix

    def _tag(self, metric, key) -> str:
        tag = self.prefix + metric.name
        label = _label_key(metric, key).replace('"', "")
        if label:
            tag += "/" + label
        return tag

    def publish(self, step: int):
        """Write every metric's current value at ``step``."""
        for m in self.registry.collect():
            for key, leaf in m.series():
                tag = self._tag(m, key)
                if isinstance(m, Histogram):
                    s = leaf.summary()
                    for stat in ("count", "mean", "p50", "p99"):
                        self._writer.add_scalar(
                            f"{tag}/{stat}", float(s[stat]), step)
                else:
                    self._writer.add_scalar(tag, float(leaf.value), step)

    def close(self):
        if self._owns_writer and self._writer is not None:
            self._writer.close()
            self._writer = None
