"""Request-tracing + flight-recorder suite (ISSUE 18) — wired into
``make chaos``.

Layers covered:

* **span-tree integrity** — every span closed after a served request
  (``TRACER.open_spans == 0``), parentage acyclic, ids stable across
  thread hops (the SpanContext wire encoding);
* **zero interference** — token streams are bit-identical tracing on
  vs off across greedy/sampled/spec/chunked/preemption (tracing is
  pure host telemetry: it must never perturb scheduling);
* **TTFT decomposition** — the ``ttft.*`` component spans laid out at
  first harvest partition the ``ttft`` parent span exactly (placement
  + queue_wait + promote_wait + prefill sums to the measured TTFT
  within the 1 ms acceptance budget — by construction, to float
  error), and the labeled histogram mirrors them;
* **cross-replica contiguity** — a stream killed mid-flight and
  migrated renders as ONE trace: both placements, both frontends, and
  the migration event all share the root trace id;
* **flight recorder** — chaos-asserted on the replica-crash and
  quarantine fault points: the JSONL postmortem exists, names the
  reason, and contains the victim's last decode steps
  (``engine.harvest`` records); the dump cap is enforced;
* **bounded ring** — sustained load never grows past capacity;
* **/debug/trace** — scrape-visible live, 404 when off/flight-only.
"""
import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import Engine
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability.tracing import (
    TRACER,
    SpanContext,
    configure_tracing,
    new_trace_id,
    ttft_decomposition_summary,
)
from paddle_tpu.serving import InProcReplica, Router, ServingFrontend
from paddle_tpu.serving.server import ApiServer

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


@pytest.fixture(autouse=True)
def trace_reset():
    """Every test starts from a clean, DISABLED tracer and leaves it
    that way (other suites must never see a configured tracer)."""
    cap0 = TRACER.capacity
    configure_tracing("off")
    TRACER.clear()
    yield
    TRACER.flight_dir = None
    configure_tracing("off", process="main", capacity=cap0)
    TRACER.clear()


@pytest.fixture(scope="module")
def reference(gpt):
    eng = make_engine(gpt)
    req = eng.add_request(np.asarray(PROMPT, np.int32), 16)
    eng.run()
    assert req.done and not req.failed
    return list(req.tokens)


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


def _wait_closed(timeout_s=10.0):
    """Spans may close on a delivery thread a beat after result()
    returns — poll before asserting the leak check."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and TRACER.open_spans:
        time.sleep(0.02)
    return TRACER.open_spans


# ------------------------------------------------------------- wire form
class TestSpanContext:
    def test_encode_decode_roundtrip(self):
        ctx = SpanContext("abc123", "def-9")
        back = SpanContext.decode(ctx.encode())
        assert back.trace_id == "abc123" and back.span_id == "def-9"
        assert SpanContext.decode(ctx) is ctx

    def test_malformed_wire_is_none_not_an_error(self):
        for bad in (None, "", "nodelimiter", "/x", "x/", 42, b"a/b"):
            assert SpanContext.decode(bad) is None

    def test_ids_are_unique(self):
        assert len({new_trace_id() for _ in range(1000)}) == 1000


# ---------------------------------------------------------- disabled path
class TestDisabledPath:
    def test_off_records_nothing_and_mints_no_ids(self):
        s1 = TRACER.start("a", "t")
        s2 = TRACER.nested("b", "t")
        # off, a span is the profiler's annotation alone: no ids, no
        # clock read, no ring record (tests/test_trace_join.py reads
        # the annotations back from a profiler session)
        assert s1.ctx is None and s2.ctx is None
        with s1:
            s1.set(x=1)
        s2.end()
        s2.end(x=2)  # idempotent
        TRACER.instant("ev", "t")
        TRACER.complete("c", "t", time.time(), 0.1)
        assert TRACER.snapshot() == []
        assert TRACER.open_spans == 0

    def test_off_flight_record_is_none(self, tmp_path):
        assert TRACER.flight_record(
            "x", path=str(tmp_path / "f.jsonl")) is None


# ------------------------------------------------------- span-tree shape
class TestSpanTree:
    def test_served_request_closes_every_span_acyclically(self, gpt,
                                                          reference):
        configure_tracing("on", process="test")
        reps = [InProcReplica(lambda: ServingFrontend(make_engine(gpt)),
                              name="t0", index=0)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False)
        router.start()
        try:
            t = router.submit(PROMPT, 8)
            assert len(t.result(timeout=120)) == 8
            assert _wait_closed() == 0, "open spans leaked"
            snap = TRACER.snapshot()
            assert snap, "tracing on recorded nothing"
            by_id = {r["id"]: r for r in snap}
            assert len(by_id) == len(snap), "span ids collide"
            for rec in snap:
                # walk to the root: parent chains never cycle (a parent
                # evicted from the ring just ends the walk)
                seen, cur = set(), rec
                while cur is not None and cur.get("parent"):
                    assert cur["id"] not in seen, "parent cycle"
                    seen.add(cur["id"])
                    cur = by_id.get(cur["parent"])
            # the root request span committed with its outcome
            roots = [r for r in snap if r["name"] == "request"]
            assert len(roots) == 1 and roots[0]["dur"] is not None
            assert roots[0]["args"]["tokens"] == 8
        finally:
            router.shutdown()

    def test_ids_stable_across_thread_hops(self):
        configure_tracing("on", process="test")
        root = TRACER.start("request", "test")
        wire = root.ctx.encode()  # the string that crosses boundaries

        def hop():
            with TRACER.start("child", "test", parent=wire):
                pass

        th = threading.Thread(target=hop)
        th.start()
        th.join(timeout=30)
        root.end()
        assert TRACER.open_spans == 0
        child = next(r for r in TRACER.snapshot()
                     if r["name"] == "child")
        parent = next(r for r in TRACER.snapshot()
                      if r["name"] == "request")
        assert child["trace"] == parent["trace"] == root.ctx.trace_id
        assert child["parent"] == parent["id"] == root.ctx.span_id
        assert child["tid"] != parent["tid"]


# --------------------------------------------------- tracing-off identity
# (eng_kwargs, req_kwargs, budget): every scheduling variant the ISSUE
# names must stream bit-identically with the recorder on
_IDENTITY_CASES = {
    "greedy": (dict(), dict(), 16),
    "sampled": (dict(), dict(temperature=0.8, seed=7), 16),
    "spec": (dict(spec="ngram"), dict(), 16),
    "chunked": (dict(prefill_chunk=4), dict(), 16),
    "preemption": (dict(num_pages=14, max_chain=4), dict(), 24),
}


def _run_tokens(gpt, eng_kw, req_kw, budget):
    eng = make_engine(gpt, **eng_kw)
    rng = np.random.default_rng(3)
    prompts = [np.asarray(PROMPT, np.int32),
               rng.integers(0, VOCAB, (13,)).astype(np.int32),
               rng.integers(0, VOCAB, (29,)).astype(np.int32)]
    reqs = [eng.add_request(p, budget, **req_kw) for p in prompts]
    eng.run()
    assert all(r.done and not r.failed for r in reqs)
    return [list(r.tokens) for r in reqs]


class TestBitIdenticalStreams:
    @pytest.mark.parametrize(
        "case",
        ["greedy"] + [pytest.param(c, marks=pytest.mark.slow)
                      # chaos-enforced; out of tier-1's wall budget
                      for c in _IDENTITY_CASES if c != "greedy"])
    def test_tokens_identical_tracing_on_vs_off(self, gpt, case):
        eng_kw, req_kw, budget = _IDENTITY_CASES[case]
        configure_tracing("off")
        toks_off = _run_tokens(gpt, eng_kw, req_kw, budget)
        configure_tracing("on", process="test")
        toks_on = _run_tokens(gpt, eng_kw, req_kw, budget)
        assert toks_on == toks_off
        assert TRACER.snapshot(), "tracing on recorded nothing"


# ------------------------------------------------------ TTFT decomposition
class TestTTFTDecomposition:
    def _groups(self, snap):
        """(tid, rid) -> {ttft record, components} — one group per
        first-token layout (a migrated stream lays out one per engine
        request, on distinct frontend threads)."""
        groups = {}
        for r in snap:
            if r["name"] == "ttft" or r["name"].startswith("ttft."):
                key = (r["tid"], (r.get("args") or {}).get("rid"))
                groups.setdefault(key, []).append(r)
        return groups

    def test_components_partition_the_ttft_span_exactly(self, gpt):
        configure_tracing("on", process="test")
        reps = [InProcReplica(lambda: ServingFrontend(make_engine(gpt)),
                              name="d0", index=0)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False)
        router.start()
        try:
            t = router.submit(PROMPT, 8)
            t.result(timeout=120)
            snap = TRACER.snapshot()
            groups = self._groups(snap)
            assert groups, "no ttft spans laid out"
            for recs in groups.values():
                ttft = next(r for r in recs if r["name"] == "ttft")
                comps = {r["name"]: r["dur"] for r in recs
                         if r["name"].startswith("ttft.")}
                assert set(comps) == {
                    "ttft.placement", "ttft.queue_wait",
                    "ttft.promote_wait", "ttft.prefill"}
                # the acceptance budget is 1 ms; the partition is exact
                # on one perf_counter clock, so float error is all that
                # remains
                assert abs(sum(comps.values()) - ttft["dur"]) < 1e-6
                # the components nest under the request root
                root = next(r for r in snap if r["name"] == "request")
                assert ttft["trace"] == root["trace"]
                assert ttft["parent"] == root["id"]
            # host-measured TTFT (ticket clock) agrees up to delivery
            ttft_dur = next(r["dur"] for r in snap
                            if r["name"] == "ttft")
            assert t.ttft_s is not None
            assert abs(ttft_dur - t.ttft_s) < 0.25
            # the labeled histogram mirrors the same partition
            d = ttft_decomposition_summary()
            assert d and d["n"] >= 1
            fracs = sum(v for k, v in d.items() if k.endswith("_frac"))
            assert abs(fracs - 1.0) < 1e-6
        finally:
            router.shutdown()


# ------------------------------------------------- cross-replica migration
class TestMigrationTrace:
    @pytest.mark.slow  # chaos-enforced; 3 engine builds on the
    # single-core host — out of tier-1's wall budget
    def test_killed_stream_renders_as_one_contiguous_trace(self, gpt,
                                                           reference,
                                                           tmp_path):
        # flight_dir: the kill also triggers a replica-dead flight
        # dump, which must not litter the working directory
        configure_tracing("on", process="test",
                          flight_dir=str(tmp_path))
        reps = [InProcReplica(_slow_factory(gpt), name=f"m{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False)
        router.start()
        try:
            t = router.submit(PROMPT, 16)
            assert _wait_tokens(t, 4), t.tokens
            assert len(t.tokens) < 16, "stream finished before the kill"
            next(r for r in reps if r.name == t.replica).kill()
            assert t.result(timeout=180) == reference
            assert t.migrations >= 1 and t.failure_reason is None
            assert _wait_closed() == 0, "open spans leaked"
            snap = TRACER.snapshot()
            root = next(r for r in snap if r["name"] == "request")
            tid = root["trace"]
            mine = [r for r in snap if r["trace"] == tid]
            names = [r["name"] for r in mine]
            # ONE trace spans both replicas: both placements, both
            # frontend admissions, and the migration event itself
            assert names.count("router.place") >= 2
            assert names.count("frontend.submit") >= 2
            assert names.count("engine.enqueue") >= 2
            assert "router.migrate" in names
            assert root["args"]["migrations"] >= 1
            # every first-token layout in the trace still partitions
            # exactly (victim and resumed engine alike)
            groups = TestTTFTDecomposition()._groups(mine)
            assert groups
            for recs in groups.values():
                ttft = [r for r in recs if r["name"] == "ttft"]
                comps = [r["dur"] for r in recs
                         if r["name"].startswith("ttft.")]
                if ttft:
                    assert abs(sum(comps) - ttft[0]["dur"]) < 1e-6
        finally:
            router.shutdown()


# --------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_quarantine_dumps_a_postmortem(self, gpt, tmp_path):
        """The watchdog-quarantine fault point: the dump exists, names
        the cause, and holds the last decode steps."""
        configure_tracing("flight-only", process="test",
                          flight_dir=str(tmp_path))
        eng = make_engine(gpt)
        req = eng.add_request(np.asarray(PROMPT, np.int32), 8)
        eng.run()
        assert req.done
        eng._watchdog.quarantine(RuntimeError("injected"))
        files = glob.glob(str(tmp_path / "flight-quarantine-*.jsonl"))
        assert len(files) == 1
        lines = [json.loads(x) for x in
                 open(files[0], encoding="utf-8").read().splitlines()]
        head, records = lines[0], lines[1:]
        assert head["kind"] == "flight"
        assert head["reason"].startswith("quarantine-RuntimeError")
        assert head["records"] == len(records)
        # the victim's last decode steps made it into the postmortem
        harvests = [r for r in records if r["name"] == "engine.harvest"]
        assert harvests
        assert any(r["args"]["rid"] == req.rid for r in harvests)

    @pytest.mark.slow  # chaos-enforced; 3 engine builds — out of
    # tier-1's wall budget
    def test_replica_crash_dumps_a_postmortem(self, gpt, reference,
                                              tmp_path):
        """The replica-crash fault point: the router supervisor's
        death detection snapshots the ring BEFORE migration churn can
        overwrite the victim's records."""
        configure_tracing("flight-only", process="test",
                          flight_dir=str(tmp_path))
        reps = [InProcReplica(_slow_factory(gpt), name=f"f{i}", index=i)
                for i in range(2)]
        router = Router(reps, heartbeat_s=0.05, stall_s=None,
                        restart_dead=False)
        router.start()
        try:
            t = router.submit(PROMPT, 16)
            assert _wait_tokens(t, 4), t.tokens
            victim = next(r for r in reps if r.name == t.replica)
            victim.kill()
            assert t.result(timeout=180) == reference
            assert t.migrations >= 1
        finally:
            router.shutdown()
        files = glob.glob(str(tmp_path / "flight-replica-dead-*.jsonl"))
        assert files, os.listdir(tmp_path)
        lines = [json.loads(x) for x in
                 open(files[0], encoding="utf-8").read().splitlines()]
        head, records = lines[0], lines[1:]
        assert head["reason"] == f"replica-dead-{victim.name}"
        # the victim's last decode steps are in the dump: harvests of
        # OUR stream recorded before the kill was even detected
        harvests = [r for r in records if r["name"] == "engine.harvest"]
        assert harvests, "no decode steps in the postmortem"

    def test_dump_cap_and_explicit_path_bypass(self, tmp_path):
        configure_tracing("flight-only", process="test",
                          flight_dir=str(tmp_path))
        TRACER.instant("ev", "t")
        seq0 = TRACER._flight_seq
        try:
            TRACER._flight_seq = 10_000  # at the cap
            assert TRACER.flight_record("looping-crash") is None
            # an explicit path (operator-requested dump) still works
            out = TRACER.flight_record(
                "manual", path=str(tmp_path / "manual.jsonl"))
            assert out and os.path.exists(out)
        finally:
            TRACER._flight_seq = seq0


# ------------------------------------------------------------ bounded ring
class TestBoundedRing:
    def test_sustained_load_never_grows_past_capacity(self):
        configure_tracing("on", process="test", capacity=256)
        for i in range(5000):
            TRACER.instant("ev", "t", i=i)
        snap = TRACER.snapshot()
        assert len(snap) == 256
        # the ring keeps the NEWEST records (postmortem semantics)
        assert snap[-1]["args"]["i"] == 4999
        assert snap[0]["args"]["i"] == 4999 - 255

    def test_capacity_reconfigure_preserves_tail(self):
        configure_tracing("on", process="test", capacity=64)
        for i in range(100):
            TRACER.instant("ev", "t", i=i)
        configure_tracing("on", capacity=16)
        snap = TRACER.snapshot()
        assert len(snap) == 16 and snap[-1]["args"]["i"] == 99


# ------------------------------------------------------------ /debug/trace
class TestDebugTraceEndpoint:
    def _serve(self, gpt):
        eng = make_engine(gpt)
        fe = ServingFrontend(eng)
        srv = ApiServer(fe, port=0)
        import asyncio

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
        return srv, loop, thread

    def test_scrape_live_and_refused_when_not_live(self, gpt):
        import asyncio

        configure_tracing("on", process="api")
        TRACER.instant("engine.harvest", "engine", rid=0)
        srv, loop, thread = self._serve(gpt)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(base + "/debug/trace",
                                        timeout=30) as r:
                body = json.loads(r.read())
            assert body["mode"] == "on" and body["process"] == "api"
            assert any(rec["name"] == "engine.harvest"
                       for rec in body["records"])
            # flight-only records but refuses live scrapes
            configure_tracing("flight-only")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + "/debug/trace", timeout=30)
            assert e.value.code == 404
        finally:
            fut = asyncio.run_coroutine_threadsafe(srv.shutdown(), loop)
            fut.result(timeout=30)
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=10)
