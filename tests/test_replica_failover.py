"""Multi-replica failover suite (ISSUE 13) — wired into ``make chaos``
(and ``make chaos-serve`` standalone).

Layers covered:

* **resume-from-emitted** — ``Engine.add_request(resume_tokens=...)``:
  a stream re-admitted as prompt‖emitted continues bit-identically
  (greedy, seeded-sampled via the replayed key schedule, chunked), and
  the sampled-resume preconditions are validated up front;
* **health surface** — watchdog/frontend readiness, the
  ``/healthz`` (liveness) vs ``/readyz`` (readiness) split, 429
  ``Retry-After``;
* **slow clients** — a consumer stalled past ``stream_stall_s`` is
  cancelled and its slot/pages freed;
* **router failover** — in-process replicas killed (poisoned) or
  heartbeat-dropped mid-stream: the client stream completes
  bit-identically with zero request failures, the dead replica
  restarts under supervision, placement failure is bounded and
  attributable, and a slow first token can be hedged;
* **subprocess SIGKILL** (slow-marked: single-core host, tier-1 wall
  budget; chaos-enforced) — the acceptance gate: with 2 worker
  replicas, SIGKILL one mid-stream and every in-flight greedy stream
  is bit-identical to an unkilled run with zero failed requests.
"""
import json
import os
import sys
import threading
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import Engine
from paddle_tpu.inference.errors import ValidationError
from paddle_tpu.inference.watchdog import SMALL_BATCH
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import metric_total
from paddle_tpu.serving import (InProcReplica, Router, ServingFrontend,
                                SubprocessReplica)
from paddle_tpu.serving.server import ApiServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 97
PROMPT = list(range(1, 21))


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=2,
                    max_position=128, vocab_size=VOCAB)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def make_engine(gpt, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("dtype", jnp.float32)
    return Engine(gpt, **kw)


@pytest.fixture(scope="module")
def reference(gpt):
    """Unkilled greedy tokens for PROMPT — the identity target every
    migrated stream must reproduce."""
    eng = make_engine(gpt)
    req = eng.add_request(np.asarray(PROMPT, np.int32), 16)
    eng.run()
    assert req.done and not req.failed
    return list(req.tokens)


# ------------------------------------------------------ resume admission
class TestResumeFromEmitted:
    def test_greedy_resume_is_bit_identical(self, gpt, reference):
        eng = make_engine(gpt)
        fresh = []
        req = eng.add_request(np.asarray(PROMPT, np.int32), 16,
                              on_token=lambda ts: fresh.extend(ts),
                              resume_tokens=reference[:6])
        eng.run()
        assert req.done and not req.failed
        # full history restored, only the continuation delivered
        assert req.tokens == reference
        assert fresh == reference[6:]

    def test_sampled_resume_replays_key_schedule(self, gpt):
        eng = make_engine(gpt)
        ref = eng.add_request(np.asarray(PROMPT, np.int32), 14,
                              temperature=0.8, seed=1234)
        eng.run()
        sref = list(ref.tokens)
        assert len(sref) == 14
        res = eng.add_request(np.asarray(PROMPT, np.int32), 14,
                              temperature=0.8, seed=1234,
                              resume_tokens=sref[:5])
        eng.run()
        assert res.tokens == sref

    def test_chunked_engine_resumes_identically(self, gpt, reference):
        eng = make_engine(gpt, prefill_chunk=4)
        req = eng.add_request(np.asarray(PROMPT, np.int32), 16,
                              resume_tokens=reference[:3])
        eng.run()
        assert req.tokens == reference

    def test_resume_preconditions_validated(self, gpt):
        eng = make_engine(gpt, eos_id=96)
        prompt = np.asarray(PROMPT, np.int32)
        with pytest.raises(ValidationError):  # budget already met
            eng.add_request(prompt, 4, resume_tokens=[1, 2, 3, 4])
        with pytest.raises(ValidationError):  # eos already emitted
            eng.add_request(prompt, 8, resume_tokens=[1, 96])
        with pytest.raises(ValidationError):  # out-of-vocab history
            eng.add_request(prompt, 8, resume_tokens=[VOCAB + 3])
        with pytest.raises(ValidationError):  # sampled resume w/o seed
            eng.add_request(prompt, 8, temperature=0.5,
                            resume_tokens=[1, 2])
        spec_eng = make_engine(gpt, spec="ngram")
        with pytest.raises(ValidationError):  # sampled resume + spec
            spec_eng.add_request(prompt, 8, temperature=0.5, seed=7,
                                 resume_tokens=[1, 2])
        # greedy resume under spec is fine (identical by construction)
        req = spec_eng.add_request(prompt, 8, resume_tokens=[1, 2])
        assert req.tokens == [1, 2]


# -------------------------------------------------------- health surface
class TestHealthSurface:
    def test_watchdog_readiness_levels(self, gpt):
        eng = make_engine(gpt)
        wd = eng._watchdog
        assert wd.ready and wd.readiness()["ready"]
        wd.level = SMALL_BATCH
        wd._apply()
        r = wd.readiness()
        assert not r["ready"] and r["mode"] == "small-batch"
        assert metric_total("paddle_tpu_engine_ready") == 0.0

    def test_frontend_liveness_vs_readiness(self, gpt):
        fe = ServingFrontend(make_engine(gpt))
        assert not fe.alive  # not started yet
        fe.start()
        try:
            assert fe.alive and fe.readiness()["ready"]
            # queue depth past the bound -> not ready, still alive
            fe.ready_queue_depth = -1
            r = fe.readiness()
            assert fe.alive and not r["ready"]
        finally:
            fe.shutdown()
        assert not fe.alive

    def test_poison_kills_liveness_without_draining(self, gpt):
        fe = ServingFrontend(make_engine(gpt)).start()
        t = fe.submit(PROMPT, 200)
        fe.poison()
        for _ in range(100):
            if not fe.alive:
                break
            time.sleep(0.02)
        assert not fe.alive
        assert not t.done  # silence, not a clean finish — by design

    def test_healthz_readyz_split_and_retry_after(self, gpt):
        """Liveness stays 200 while readiness flips 503 (with
        Retry-After) once the watchdog degrades past its threshold."""
        import asyncio

        eng = make_engine(gpt)
        fe = ServingFrontend(eng)
        srv = ApiServer(fe, port=0)
        loop = asyncio.new_event_loop()
        thread = threading.Thread(
            target=lambda: (asyncio.set_event_loop(loop),
                            loop.run_until_complete(srv.start()),
                            loop.run_forever()), daemon=True)
        thread.start()
        for _ in range(200):
            if srv.port:
                break
            time.sleep(0.05)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=30) as r:
                assert json.loads(r.read())["status"] == "ok"
            with urllib.request.urlopen(base + "/readyz",
                                        timeout=30) as r:
                assert json.loads(r.read())["status"] == "ready"
            eng._watchdog.level = SMALL_BATCH
            eng._watchdog._apply()
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + "/readyz", timeout=30)
            assert e.value.code == 503
            assert int(e.value.headers["Retry-After"]) >= 1
            assert json.loads(e.value.read())["status"] == "not-ready"
            # liveness is unmoved by degradation
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=30) as r:
                assert json.loads(r.read())["status"] == "ok"
        finally:
            fut = asyncio.run_coroutine_threadsafe(srv.shutdown(), loop)
            fut.result(timeout=30)
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=10)

    def test_retry_after_derivation(self, gpt):
        eng = make_engine(gpt)
        fe = ServingFrontend(eng)
        srv = ApiServer(fe, port=0)
        assert srv._retry_after_s() == 1  # empty queue floors at 1
        for _ in range(10):
            eng.add_request(np.asarray(PROMPT, np.int32), 4)
        assert 1 <= srv._retry_after_s() <= 30
        assert srv._retry_after_s() >= 5  # 10 queued / 2 slots


# ----------------------------------------------------------- slow client
class TestSlowClient:
    def test_stalled_consumer_is_cancelled_and_freed(self, gpt):
        """An on_chunk consumer that never acks trips the stall
        watchdog: the stream is cancelled, slot and pages recycle. The
        slow-step fault pins emission at ~10 tokens/s so the stream is
        provably mid-flight when the stall budget expires."""
        eng = make_engine(gpt, chunk_size=1, max_chain=1,
                          fault_plan="slow-step:every=1,delay_ms=100")
        fe = ServingFrontend(eng, stream_stall_s=0.3).start()
        try:
            got = threading.Event()
            t = fe.submit(PROMPT, 60, on_chunk=lambda c: got.set())
            assert got.wait(timeout=60), "stream never started"
            t.result(timeout=60)
            assert t.failure_reason == "cancelled"
            assert t.stall_cancelled
            for _ in range(200):
                if (len(eng._free_slots) == eng.max_slots
                        and len(eng._free_pages) == eng.num_pages - 1):
                    break
                time.sleep(0.02)
            assert len(eng._free_slots) == eng.max_slots
            assert len(eng._free_pages) == eng.num_pages - 1
        finally:
            fe.shutdown()

    def test_acking_consumer_survives(self, gpt):
        eng = make_engine(gpt)
        fe = ServingFrontend(eng, stream_stall_s=5.0).start()
        try:
            ticket = {}

            def consume(c):
                if c is not None:
                    ticket["t"].ack()

            ticket["t"] = fe.submit(PROMPT, 10, on_chunk=consume)
            out = ticket["t"].result(timeout=120)
            assert len(out) == 10
            assert ticket["t"].failure_reason is None
        finally:
            fe.shutdown()

    def test_buffer_bound_reports_infinite_stall(self, gpt):
        fe = ServingFrontend(make_engine(gpt), max_buffered_chunks=2)
        t = fe.submit(PROMPT, 8)
        for _ in range(3):
            t._on_tokens([1])
        assert t.stalled_for() == float("inf")


# -------------------------------------------------------- router (inproc)
def _slow_factory(gpt, delay_ms=30):
    def factory():
        eng = Engine(gpt, max_slots=2, num_pages=64, page_size=8,
                     chunk_size=1, max_chain=1, dtype=jnp.float32,
                     fault_plan=f"slow-step:every=1,delay_ms={delay_ms}")
        return ServingFrontend(eng)
    return factory


def _wait_tokens(ticket, n, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if len(ticket.tokens) >= n:
            return True
        time.sleep(0.02)
    return False


class TestRouterFailover:
    @pytest.mark.slow  # chaos-enforced (make chaos / chaos-serve run it
    # unconditionally); out of tier-1's wall budget — 3 engine builds +
    # a supervised restart on the single-core host (~10 s)
    def test_kill_mid_stream_is_bit_identical(self, gpt, reference):
        """The in-process chaos gate: 2 replicas, poison the one
        hosting the stream mid-flight — the client sees ONE unbroken,
        bit-identical sequence; zero request failures; the dead
        replica restarts under supervision."""
        fails0 = metric_total("paddle_tpu_request_failures_total")
        reps = [InProcReplica(_slow_factory(gpt), name=f"r{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=True, restart_backoff_s=0.05)
        router.start()
        try:
            chunks = []
            t = router.submit(PROMPT, 16,
                              on_chunk=lambda c: chunks.append(c))
            assert _wait_tokens(t, 4), t.tokens
            assert len(t.tokens) < 16, "stream finished before the kill"
            victim = next(r for r in reps if r.name == t.replica)
            victim.kill()
            out = t.result(timeout=180)
            assert out == reference
            assert t.migrations >= 1
            assert t.failure_reason is None
            # the spliced callback stream carries no duplicates/gaps
            flat = [tok for c in chunks if c for tok in c]
            assert flat == reference and chunks[-1] is None
            assert metric_total(
                "paddle_tpu_request_failures_total") == fails0
            assert metric_total(
                "paddle_tpu_router_migrations_total") >= 1
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline and not (
                    victim.alive() and victim.restarts >= 1):
                time.sleep(0.1)
            assert victim.alive() and victim.restarts >= 1
            assert metric_total(
                "paddle_tpu_replica_restarts_total") >= 1
        finally:
            router.shutdown()

    def test_all_replicas_dead_fails_bounded(self, gpt):
        """No healthy replica: placement fails ATTRIBUTABLY (reason
        ``replica_lost``) after the bounded retry — no livelock, no
        hang."""
        reps = [InProcReplica(_slow_factory(gpt), name=f"d{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False, max_place_attempts=3,
                        place_backoff_s=0.01)
        router.start()
        try:
            for rep in reps:
                rep.kill()
            time.sleep(0.3)
            t = router.submit(PROMPT, 8)
            t.result(timeout=60)
            assert t.failure_reason == "replica_lost"
        finally:
            router.shutdown()

    @pytest.mark.slow  # chaos-enforced; tier-1 wall budget
    def test_sampled_stream_migrates_exactly(self, gpt):
        eng = make_engine(gpt, chunk_size=1, max_chain=1)
        ref = eng.add_request(np.asarray(PROMPT, np.int32), 16,
                              temperature=0.7, seed=42)
        eng.run()
        sref = list(ref.tokens)
        reps = [InProcReplica(_slow_factory(gpt), name=f"s{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False)
        router.start()
        try:
            t = router.submit(PROMPT, 16, temperature=0.7, seed=42)
            assert _wait_tokens(t, 4) and len(t.tokens) < 16
            next(r for r in reps if r.name == t.replica).kill()
            assert t.result(timeout=180) == sref
            assert t.migrations >= 1 and t.failure_reason is None
        finally:
            router.shutdown()

    @pytest.mark.slow  # chaos-enforced; tier-1 wall budget
    def test_heartbeat_drop_migrates_without_kill(self, gpt, reference):
        """The ``heartbeat-drop`` fault point: the replica is secretly
        fine, but the router must treat it as dead — cancel its stream
        FIRST (no double-delivery), then resume elsewhere, still
        bit-identical."""
        reps = [InProcReplica(_slow_factory(gpt), name=f"h{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False,
                        fault_plan="heartbeat-drop:rid=0,at=5,times=60")
        router.start()
        try:
            ta = router.submit(PROMPT, 16)
            tb = router.submit(PROMPT, 16)
            assert ta.result(timeout=180) == reference
            assert tb.result(timeout=180) == reference
            assert ta.failure_reason is None and tb.failure_reason is None
            # whichever stream landed on h0 was forced to move
            assert ta.migrations + tb.migrations >= 1
        finally:
            router.shutdown()

    @pytest.mark.slow  # chaos-enforced; tier-1 wall budget
    def test_hedge_rescues_slow_first_token(self, gpt):
        """Single-hedge policy: replica 0 is pathologically slow before
        its first token; the hedge on replica 1 wins the race and the
        stream completes (greedy — both candidates are identical, so
        the race is divergence-free)."""
        hedges0 = metric_total("paddle_tpu_router_hedges_total")
        factories = [_slow_factory(gpt, delay_ms=700),
                     _slow_factory(gpt, delay_ms=10)]
        reps = [InProcReplica(factories[i], name=f"g{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False, hedge_ms=400.0)
        router.start()
        try:
            # with both replicas idle, placement picks g0 (the slow
            # one, first in the list) — its first token is behind a
            # 700 ms/step fault plus cold compile, far past hedge_ms
            t = router.submit(PROMPT, 8)
            out = t.result(timeout=180)
            assert len(out) == 8 and t.failure_reason is None
            assert t.hedged
            assert metric_total(
                "paddle_tpu_router_hedges_total") > hedges0
        finally:
            router.shutdown()


# ---------------------------------------------------- subprocess (chaos)
@pytest.mark.slow  # single-core host, tier-1 wall budget; chaos-enforced
class TestSubprocessSigkill:
    @pytest.mark.timeout(600)
    def test_sigkill_mid_stream_bit_identical(self):
        """THE acceptance gate (ISSUE 13): 2 subprocess replicas behind
        the router, SIGKILL one mid-stream — every in-flight greedy
        stream completes bit-identical to an unkilled run, with zero
        request failures."""
        fails0 = metric_total("paddle_tpu_request_failures_total")
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        argv = [sys.executable, "-u",
                os.path.join(REPO, "examples", "serve_llama_paged.py"),
                "--tiny", "--api-port", "0",
                "--fault-inject", "slow-step:every=1,delay_ms=120"]
        reps = [SubprocessReplica(argv, name=f"w{i}", index=i, env=env,
                                  cwd=REPO) for i in range(2)]
        router = Router(reps, heartbeat_s=0.1, stall_s=None,
                        restart_dead=True, restart_backoff_s=0.1)
        router.start()
        try:
            # unkilled reference straight from a worker (same seed ->
            # same weights -> same greedy stream in every process)
            req = urllib.request.Request(
                f"http://127.0.0.1:{reps[1].port}/v1/completions",
                data=json.dumps({"prompt": PROMPT,
                                 "max_tokens": 40}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                ref = json.loads(r.read())["choices"][0]["token_ids"]
            assert len(ref) == 40

            # two in-flight streams (one per replica, least-loaded)
            ta = router.submit(PROMPT, 40)
            tb = router.submit(PROMPT, 40)
            assert _wait_tokens(ta, 8, 180) and _wait_tokens(tb, 8, 180)
            assert len(ta.tokens) < 40, "stream finished pre-kill"
            victim = next(r for r in reps if r.name == ta.replica)
            victim.kill()  # real SIGKILL
            out_a = ta.result(timeout=300)
            out_b = tb.result(timeout=300)
            # EVERY in-flight stream: completed, bit-identical
            assert out_a == ref and out_b == ref
            assert ta.failure_reason is None and tb.failure_reason is None
            assert ta.migrations >= 1
            assert metric_total(
                "paddle_tpu_request_failures_total") == fails0
            # supervised restart brings the worker back ready
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline and not (
                    victim.alive() and victim.restarts >= 1):
                time.sleep(0.5)
            assert victim.alive() and victim.restarts >= 1
            assert victim.ready().get("ready")
        finally:
            router.shutdown()
