"""Unified telemetry (ISSUE 3): metric primitives + registry semantics,
Prometheus/JSONL/tbevents export, serving-engine instrumentation
(TTFT/TPOT per request, preemption counters, page-pool gauges), compile-
path retrace attribution, and the example's ``--metrics-port`` scrape
contract. All CPU tier-1 runnable."""
import json
import os
import subprocess
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (
    LATENCY_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    TBEventsBridge,
    histogram_summary,
    metric_total,
    render_prometheus,
    start_metrics_server,
    write_jsonl_snapshot,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def reg():
    return Registry()


class TestCounter:
    def test_inc_and_value(self, reg):
        c = reg.counter("c_total", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_monotonic(self, reg):
        c = reg.counter("c_total")
        with pytest.raises(ValueError, match="decrease"):
            c.inc(-1)

    def test_get_or_create_same_object(self, reg):
        assert reg.counter("c_total") is reg.counter("c_total")

    def test_type_mismatch_raises(self, reg):
        reg.counter("c_total")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("c_total")

    def test_labels(self, reg):
        c = reg.counter("l_total", labelnames=("depth",))
        c.labels(depth=4).inc()
        c.labels(depth=4).inc()
        c.labels(depth=2).inc()
        assert c.labels(depth=4).value == 2
        assert c.total() == 3
        with pytest.raises(ValueError, match="labels"):
            c.inc()  # parent of a labeled metric records nothing itself

    def test_reset_keeps_registration(self, reg):
        c = reg.counter("c_total")
        c.inc(5)
        reg.reset()
        assert reg.counter("c_total") is c and c.value == 0


class TestGauge:
    def test_set_inc_dec(self, reg):
        g = reg.gauge("g")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5

    def test_timeline_ring_buffer(self, reg):
        g = reg.gauge("g")
        for i in range(300):
            g.set(i)
        assert g.value == 299.0  # the level itself is never decimated
        recent = g.recent()
        # timeline samples 1-in-16 (hot-path cost): 300 sets → samples at
        # 0, 16, ..., 288, bounded by the ring size
        assert [v for _, v in recent] == [float(16 * i) for i in range(19)]
        assert all(t > 0 for t, _ in recent)
        for i in range(16 * 241):
            g.set(i)
        assert len(g.recent()) == 240  # ring bound holds


class TestHistogram:
    def test_bucket_boundaries_le_semantics(self, reg):
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 4.0, 9.0):
            h.observe(v)
        # le-cumulative: v <= bound lands at that bound
        assert h.cumulative() == [2, 3, 4, 5]
        assert h.count == 5
        assert h.sum == pytest.approx(16.0)

    def test_default_buckets_log_spaced(self):
        assert LATENCY_BUCKETS[0] == pytest.approx(1e-4)
        ratios = {round(b / a, 6) for a, b in
                  zip(LATENCY_BUCKETS, LATENCY_BUCKETS[1:])}
        assert ratios == {2.0}  # fixed log spacing

    def test_percentiles_and_summary(self, reg):
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0))
        for v in [0.5] * 50 + [3.0] * 49 + [100.0]:
            h.observe(v)
        assert h.percentile(50) == 1.0
        assert h.percentile(99) == 4.0
        s = h.summary()
        assert s["count"] == 100 and s["max"] == 100.0
        assert s["mean"] == pytest.approx((0.5 * 50 + 3 * 49 + 100) / 100)

    def test_empty_histogram(self, reg):
        h = reg.histogram("h")
        assert h.percentile(99) == 0.0 and h.summary()["count"] == 0

    def test_labeled_histogram_children_share_buckets(self, reg):
        h = reg.histogram("h", labelnames=("kind",), buckets=(1.0, 2.0))
        h.labels(kind="a").observe(0.5)
        assert h.labels(kind="a").bounds == (1.0, 2.0)
        assert h.labels(kind="a").count == 1


class TestSnapshotAndPrometheus:
    def test_snapshot_roundtrips_json(self, reg):
        reg.counter("c_total", "c").inc(2)
        reg.gauge("g", "g").set(1.5)
        reg.histogram("h", "h", buckets=(1.0,)).observe(0.5)
        snap = reg.snapshot()
        snap2 = json.loads(json.dumps(snap))
        assert snap2["c_total"]["values"][""] == 2
        assert snap2["g"]["values"][""] == 1.5
        assert snap2["h"]["series"][""]["count"] == 1

    def test_prometheus_exposition(self, reg):
        reg.counter("req_total", "requests served").inc(3)
        lab = reg.counter("by_depth_total", labelnames=("depth",))
        lab.labels(depth=8).inc()
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = render_prometheus(reg)
        assert "# HELP req_total requests served" in text
        assert "# TYPE req_total counter" in text
        assert "req_total 3" in text
        assert 'by_depth_total{depth="8"} 1' in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_sum 5.05" in text
        assert "lat_seconds_count 2" in text

    def test_label_value_escaping(self, reg):
        c = reg.counter("esc_total", labelnames=("sig",))
        c.labels(sig='f32["w"]\nx').inc()
        text = render_prometheus(reg)
        assert '\\"w\\"' in text and "\\n" in text


class TestExporters:
    def test_http_scrape_and_404(self, reg):
        reg.counter("http_total", "h").inc()
        srv = start_metrics_server(0, registry=reg, host="127.0.0.1")
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
                body = r.read().decode()
                assert r.headers["Content-Type"].startswith("text/plain")
            assert "http_total 1" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/other", timeout=10)
        finally:
            srv.close()
        srv.close()  # idempotent

    def test_jsonl_snapshot_sink(self, reg, tmp_path):
        reg.counter("j_total").inc(4)
        path = str(tmp_path / "snap.jsonl")
        write_jsonl_snapshot(path, reg, extra={"tag": "t1"})
        write_jsonl_snapshot(path, reg)
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["tag"] == "t1"
        assert lines[0]["metrics"]["j_total"]["values"][""] == 4
        assert lines[0]["ts"] > 0

    def test_tbevents_bridge_tag_mapping(self, reg):
        reg.counter("steps_total", "s").inc(2)
        lab = reg.counter("by_kind_total", labelnames=("kind",))
        lab.labels(kind="decode").inc()
        reg.histogram("lat_seconds", buckets=(1.0,)).observe(0.5)

        written = []

        class FakeWriter:
            def add_scalar(self, tag, value, step):
                written.append((tag, value, step))

        TBEventsBridge(FakeWriter(), registry=reg).publish(step=7)
        tags = {t for t, _, _ in written}
        assert ("metrics/steps_total", 2.0, 7) in written
        assert "metrics/by_kind_total/kind=decode" in tags
        # histograms publish summary sub-tags
        for stat in ("count", "mean", "p50", "p99"):
            assert f"metrics/lat_seconds/{stat}" in tags

    def test_tbevents_bridge_writes_real_event_file(self, reg, tmp_path):
        reg.gauge("g").set(1.0)
        bridge = TBEventsBridge(str(tmp_path), registry=reg)
        bridge.publish(step=1)
        bridge.close()
        files = os.listdir(tmp_path)
        assert files and files[0].startswith("events.out.tfevents.")
        assert os.path.getsize(tmp_path / files[0]) > 0


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=2,
                    max_position=128, vocab_size=97)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


class TestEngineInstrumentation:
    def test_ttft_tpot_per_request_and_scheduler_gauges(self, gpt, rng):
        from paddle_tpu.inference.engine import Engine

        REGISTRY.reset()
        eng = Engine(gpt, max_slots=2, num_pages=48, page_size=8,
                     chunk_size=4, dtype=jnp.float32)
        reqs = [eng.add_request(rng.integers(0, 97, (n,)), 8)
                for n in (5, 9, 7)]
        eng.run()
        assert all(r.done for r in reqs)
        # one TTFT and one queue-wait sample per request
        assert histogram_summary("paddle_serving_ttft_seconds")["count"] == 3
        assert histogram_summary(
            "paddle_serving_queue_wait_seconds")["count"] == 3
        # TPOT recorded for the decode tail of every request
        tpot = histogram_summary("paddle_serving_tpot_seconds")
        assert tpot["count"] >= 3 and tpot["mean"] > 0
        assert metric_total("paddle_serving_tokens_total") == 24
        assert metric_total("paddle_serving_requests_total") == 3
        assert metric_total("paddle_serving_requests_completed_total") == 3
        # drained engine: occupancy gauges back to idle
        assert metric_total("paddle_serving_pages_in_use") == 0
        assert metric_total("paddle_serving_active_slots") == 0
        assert metric_total("paddle_serving_queue_depth") == 0
        assert metric_total("paddle_serving_pages_total") == 47
        # programs were compiled and chains dispatched
        assert metric_total("paddle_serving_compiled_programs_total") >= 2
        assert metric_total("paddle_serving_chain_depth_total") >= 1
        assert histogram_summary(
            "paddle_serving_decode_batch_size")["count"] >= 1
        assert histogram_summary(
            "paddle_serving_prefill_batch_size")["count"] >= 1

    def test_preemption_counters_increment(self, gpt, rng):
        from paddle_tpu.inference.engine import Engine

        REGISTRY.reset()
        # pool sized so two full-length requests cannot coexist — the
        # same pressure shape as the engine preemption tests
        eng = Engine(gpt, max_slots=2, num_pages=13, page_size=8,
                     chunk_size=4, dtype=jnp.float32)
        reqs = [eng.add_request(rng.integers(0, 97, (16,)), 36)
                for _ in range(2)]
        eng.run()
        assert all(r.done for r in reqs)
        assert metric_total("paddle_serving_preemptions_total") >= 1
        assert metric_total("paddle_serving_page_evictions_total") >= 1

    def test_metrics_disabled_records_nothing(self, gpt, rng):
        from paddle_tpu.inference.engine import Engine

        REGISTRY.reset()
        eng = Engine(gpt, max_slots=2, num_pages=48, page_size=8,
                     chunk_size=4, dtype=jnp.float32, metrics=False)
        r = eng.add_request(rng.integers(0, 97, (5,)), 4)
        eng.run()
        assert r.done
        assert metric_total("paddle_serving_tokens_total") == 0
        assert histogram_summary("paddle_serving_ttft_seconds").get(
            "count", 0) == 0


class TestCompileMetrics:
    def test_retrace_attributed_to_signature(self):
        REGISTRY.reset()

        @paddle.jit.to_static
        def f(x):
            return x * 2

        c0 = metric_total("paddle_jit_compiles_total")
        h0 = metric_total("paddle_jit_cache_hits_total")
        f(paddle.to_tensor(np.ones((4, 2), np.float32)))
        assert metric_total("paddle_jit_compiles_total") == c0 + 1
        f(paddle.to_tensor(np.ones((4, 2), np.float32)))  # warm hit
        assert metric_total("paddle_jit_cache_hits_total") == h0 + 1
        f(paddle.to_tensor(np.ones((8, 2), np.float32)))  # retrace
        assert metric_total("paddle_jit_compiles_total") == c0 + 2
        assert metric_total("paddle_jit_retraces_total") == 1
        # the retrace names its trigger: fn + shape/dtype signature
        text = render_prometheus()
        assert 'fn="f"' in text
        assert 'float32[8,2]' in text
        assert histogram_summary(
            "paddle_jit_compile_seconds")["count"] >= 2

    def test_kernel_choice_memo_counters(self):
        from paddle_tpu.framework.compile_cache import memoize_kernel_choice

        REGISTRY.reset()
        key = ("obs_test_kind", 1, 2)
        memoize_kernel_choice(key, lambda: "v")
        memoize_kernel_choice(key, lambda: "w")
        snap = REGISTRY.snapshot()
        misses = snap["paddle_kernel_choice_misses_total"]["values"]
        hits = snap["paddle_kernel_choice_hits_total"]["values"]
        assert misses['kind="obs_test_kind"'] == 1
        assert hits['kind="obs_test_kind"'] == 1


class TestTrainingIntegration:
    def test_visualdl_publishes_runtime_metrics(self, tmp_path):
        """runtime_metrics=True lands registry values in the SAME scalar
        stream as the losses (here: the jsonl fallback, so the tags are
        directly inspectable)."""
        from paddle_tpu.hapi.callbacks import VisualDL

        REGISTRY.reset()
        REGISTRY.counter("paddle_jit_compiles_total").inc(3)
        cb = VisualDL(log_dir=str(tmp_path), runtime_metrics=True)
        cb._jsonl = open(tmp_path / "scalars.jsonl", "a")  # force fallback
        cb.on_train_batch_end(0, {"loss": 1.25})
        cb.on_epoch_end(0, {"loss": 1.25})
        cb.on_train_end()
        recs = [json.loads(l) for l in open(tmp_path / "scalars.jsonl")]
        tags = {r["tag"] for r in recs}
        assert "train/loss" in tags
        assert "metrics/paddle_jit_compiles_total" in tags
        by_tag = {r["tag"]: r["value"] for r in recs}
        assert by_tag["metrics/paddle_jit_compiles_total"] == 3.0

    def test_fit_exception_still_closes_scalar_writers(self, tmp_path):
        """A crash mid-epoch must flush+close the scalar writers (the
        satellite guarantee) without running on_train_end side effects,
        and the original error must propagate."""
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi.callbacks import Callback, VisualDL
        from paddle_tpu.hapi.model import Model

        class Boom(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step >= 1:
                    raise RuntimeError("injected mid-epoch failure")

        vdl = VisualDL(log_dir=str(tmp_path))
        model = Model(nn.Linear(4, 2))
        model.prepare()
        x = np.ones((2, 4), np.float32)
        batches = [(x, np.zeros((2, 2), np.float32)) for _ in range(4)]
        with pytest.raises(RuntimeError, match="injected"):
            model.fit(train_data=batches, epochs=1, verbose=0,
                      callbacks=[vdl, Boom()])
        # writers are closed (handles dropped), and the pre-crash events
        # made it to disk
        assert vdl._writer is None and vdl._jsonl is None
        files = os.listdir(tmp_path)
        assert files and all(os.path.getsize(tmp_path / f) > 0
                             for f in files)


class TestServeExampleScrape:
    @pytest.mark.timeout(300)
    def test_metrics_port_serves_ttft_tpot_pages_preemption_retrace(self):
        """The acceptance scrape: ``serve_llama_paged.py --metrics-port``
        must expose TTFT and TPOT histograms, page-pool occupancy, and
        preemption/retrace counters in Prometheus text format."""
        proc = subprocess.Popen(
            [sys.executable, "-u",
             os.path.join(REPO, "examples", "serve_llama_paged.py"),
             "--tiny", "--metrics-port", "0", "--metrics-linger", "60"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            port = None
            lingering = False
            for line in proc.stdout:
                if line.startswith("metrics: http"):
                    port = int(line.rsplit(":", 1)[1].split("/")[0])
                if "lingering" in line:
                    lingering = True
                    break
            assert port is not None, proc.stderr.read()
            assert lingering, "example never reached the linger phase"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
                text = r.read().decode()
            # TTFT + TPOT histograms, with samples
            assert "# TYPE paddle_serving_ttft_seconds histogram" in text
            # TTFT carries the tenant label (ISSUE 12 satellite);
            # engine-direct traffic lands on the default tenant
            assert 'paddle_serving_ttft_seconds_count{tenant="default"} 6' \
                in text
            assert "# TYPE paddle_serving_tpot_seconds histogram" in text
            assert 'paddle_serving_tpot_seconds_bucket{le="+Inf"}' in text
            # page-pool occupancy gauges
            assert "# TYPE paddle_serving_pages_in_use gauge" in text
            assert "paddle_serving_pages_total 95" in text
            # preemption + retrace counters present (zero is fine — the
            # tiny workload fits its pool and compiles fresh programs)
            assert "paddle_serving_preemptions_total" in text
            assert "paddle_jit_retraces_total" in text
            assert "paddle_serving_tokens_total 76" in text
        finally:
            proc.terminate()
            proc.wait(timeout=30)
