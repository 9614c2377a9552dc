"""Async streaming front-end suite (ISSUE 12 tentpole).

Covers the three layers above the engine:

* **fairness** — FairQueue stride scheduling (weighted service order,
  idle-clock clamping, per-tenant backpressure, bounded tenant
  cardinality);
* **frontend** — ServingFrontend tickets: streamed tokens identical to
  a direct-engine run, cancel-mid-stream frees slots/pages, the
  tenant starvation bound under a batch flood, drain semantics;
* **server** — the OpenAI-compatible HTTP/SSE surface (in-process
  asyncio server driven over real sockets): streaming == unary ==
  direct engine, backpressure → 429, client disconnect cancels, and
  (slow-marked, subprocess) ``serve_llama_paged.py --api-port`` with a
  real SIGTERM drain mid-stream.

Wired into ``make chaos``; the subprocess lifecycle test is
slow-marked out of tier-1's wall budget.
"""
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import Engine
from paddle_tpu.inference.errors import QueueFull
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (FairQueue, ServingFrontend,
                                parse_tenant_weights)
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
    """Direct-engine greedy tokens for PROMPT (the identity target)."""
    eng = make_engine(gpt)
    req = eng.add_request(np.asarray(PROMPT, np.int32), 10)
    eng.run()
    assert req.done and not req.failed
    return list(req.tokens)


class _Server:
    """In-process ApiServer on a thread-owned event loop."""

    def __init__(self, gpt, **engine_kw):
        weights = engine_kw.pop("tenant_weights", None)
        self.engine = make_engine(gpt, **engine_kw)
        self.frontend = ServingFrontend(self.engine,
                                        tenant_weights=weights)
        self.srv = ApiServer(self.frontend, port=0, grace_s=15.0)
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        for _ in range(200):
            if self.srv.port:
                break
            time.sleep(0.05)
        assert self.srv.port, "server never bound"

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_until_complete(self.srv.start())
        self.loop.run_forever()

    @property
    def base(self):
        return f"http://127.0.0.1:{self.srv.port}"

    def post(self, path, payload, tenant=None, stream=False,
             timeout=120):
        headers = {"Content-Type": "application/json"}
        if tenant:
            headers["X-Tenant"] = tenant
        req = urllib.request.Request(self.base + path,
                                     data=json.dumps(payload).encode(),
                                     headers=headers)
        if not stream:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())
        toks = []
        with urllib.request.urlopen(req, timeout=timeout) as r:
            for line in r:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line[6:] == "[DONE]":
                    break
                toks.extend(
                    json.loads(line[6:])["choices"][0]["token_ids"])
        return toks

    def close(self):
        fut = asyncio.run_coroutine_threadsafe(self.srv.shutdown(),
                                               self.loop)
        fut.result(timeout=30)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)


# --------------------------------------------------------------- fairness
class TestFairQueue:
    def test_weighted_service_order(self):
        q = FairQueue(weights={"a": 2.0, "b": 1.0})
        for i in range(6):
            q.submit(("a", i), tenant="a", cost=10)
            q.submit(("b", i), tenant="b", cost=10)
        order = [q.pop()[1] for _ in range(9)]
        # weight 2:1 → a gets ~2x the service in any prefix window
        assert order.count("a") >= 2 * order.count("b") - 1

    def test_big_request_charges_its_tenant(self):
        q = FairQueue()
        q.submit("huge", tenant="a", cost=1000)
        for i in range(4):
            q.submit(("small", i), tenant="b", cost=10)
        assert q.pop()[0] in ("huge", ("small", 0))
        # after the 32k-style request lands, b's small ones go first
        assert [q.pop()[1] for _ in range(3)].count("b") >= 2

    def test_backpressure_and_removal(self):
        q = FairQueue(max_queue_per_tenant=2)
        q.submit(1, tenant="t")
        q.submit(2, tenant="t")
        with pytest.raises(QueueFull):
            q.submit(3, tenant="t")
        assert q.remove(1) and not q.remove(1)
        q.submit(3, tenant="t")  # slot freed by removal

    def test_tenant_cardinality_bounded(self):
        q = FairQueue(max_tenants=4)
        for i in range(16):
            q.submit(i, tenant=f"t{i}")
        assert len(q.queued_tenants()) <= 5  # 4 named + "other"

    def test_parse_tenant_weights(self):
        assert parse_tenant_weights("a=4, b=1.5") == {"a": 4.0,
                                                      "b": 1.5}
        assert parse_tenant_weights(None) is None
        with pytest.raises(ValueError):
            parse_tenant_weights("a=0")
        with pytest.raises(ValueError):
            parse_tenant_weights("justaname")


# --------------------------------------------------------------- frontend
class TestFrontend:
    def test_ticket_stream_matches_direct_engine(self, gpt, reference):
        fe = ServingFrontend(make_engine(gpt)).start()
        try:
            chunks = []
            t = fe.submit(PROMPT, 10,
                          on_chunk=lambda c: chunks.append(c))
            assert t.result(timeout=120) == reference
            # chunk callbacks carry the same stream + the end sentinel
            flat = [tok for c in chunks if c for tok in c]
            assert flat == reference and chunks[-1] is None
            assert t.ttft_s is not None and t.ttft_s >= 0
        finally:
            fe.shutdown()

    def test_cancel_mid_stream_frees_slots_and_pages(self, gpt):
        # one chunk a step and a slowed step: the 80 tokens take 20 round
        # trips, so the cancel lands mid-stream however the host is
        # loaded (with the default chain depth, which the engine fits to
        # measured dispatch times, a busy host could see the whole
        # stream delivered by the first harvest and nothing to cancel)
        eng = make_engine(gpt, max_chain=1,
                          fault_plan="slow-step:every=1,delay_ms=10")
        fe = ServingFrontend(eng).start()
        try:
            got = threading.Event()
            t = fe.submit(PROMPT, 80,
                          on_chunk=lambda c: c and got.set())
            assert got.wait(timeout=60), "stream never started"
            fe.cancel(t)
            t.result(timeout=60)
            assert t.failure_reason == "cancelled"
            # the engine recycles the slot and every page; poll — the
            # engine thread applies the cancel at its next loop turn
            for _ in range(200):
                if (len(eng._free_slots) == eng.max_slots
                        and len(eng._free_pages) == eng.num_pages - 1):
                    break
                time.sleep(0.02)
            assert len(eng._free_slots) == eng.max_slots
            assert len(eng._free_pages) == eng.num_pages - 1
            assert np.all(eng.tables == 0)
        finally:
            fe.shutdown()

    @pytest.mark.slow  # chaos-enforced; tier-1 wall budget
    def test_tenant_starvation_bound(self, gpt):
        """A batch flood cannot starve the interactive tenant: with
        weights 4:1 over 2 slots the batch tenant caps at one slot, so
        an interactive request admits without waiting for the flood."""
        fe = ServingFrontend(
            make_engine(gpt),
            tenant_weights={"interactive": 4.0, "batch": 1.0}).start()
        try:
            r = np.random.default_rng(7)
            flood = [fe.submit(r.integers(0, VOCAB, (24,)), 60,
                               tenant="batch") for _ in range(8)]
            time.sleep(0.2)  # let the flood occupy its share
            t0 = time.perf_counter()
            inter = fe.submit(r.integers(0, VOCAB, (8,)), 4,
                              tenant="interactive")
            inter.result(timeout=120)
            inter_done = time.perf_counter() - t0
            assert not inter.failure_reason
            done_batch = sum(1 for b in flood if b.done)
            assert done_batch <= 2, (
                f"interactive waited out {done_batch} batch requests")
            for b in flood:
                b.result(timeout=300)
            assert all(not b.failure_reason for b in flood)
            assert inter_done < 60.0
        finally:
            fe.shutdown()

    def test_submit_while_draining_is_backpressure(self, gpt):
        fe = ServingFrontend(make_engine(gpt)).start()
        t = fe.submit(PROMPT, 4)
        assert fe.drain(grace_s=60.0)
        assert t.done and not t.failure_reason
        with pytest.raises(QueueFull):
            fe.submit(PROMPT, 4)

    def test_validation_error_fails_ticket_not_loop(self, gpt):
        fe = ServingFrontend(make_engine(gpt)).start()
        try:
            bad = fe.submit([0] * 500, 10)  # prompt beyond max_position
            bad.result(timeout=60)
            assert bad.failure_reason is not None
            ok = fe.submit(PROMPT, 4)
            assert ok.result(timeout=60) and not ok.failure_reason
        finally:
            fe.shutdown()


# ----------------------------------------------------------------- server
class TestApiServer:
    @pytest.fixture(scope="class")
    def server(self, gpt):
        s = _Server(gpt, multi_step=4,
                    tenant_weights={"interactive": 4.0, "batch": 1.0})
        yield s
        s.close()

    def test_streamed_equals_unary_equals_direct(self, server,
                                                 reference):
        unary = server.post("/v1/completions",
                            {"prompt": PROMPT, "max_tokens": 10})
        assert unary["choices"][0]["token_ids"] == reference
        assert unary["choices"][0]["finish_reason"] == "stop"
        assert unary["usage"]["completion_tokens"] == len(reference)
        streamed = server.post("/v1/completions",
                               {"prompt": PROMPT, "max_tokens": 10,
                                "stream": True}, stream=True)
        assert streamed == reference

    def test_chat_and_models_and_health(self, server):
        chat = server.post("/v1/chat/completions",
                           {"messages": [
                               {"role": "user", "content": "hello"}],
                            "max_tokens": 4})
        assert len(chat["choices"][0]["token_ids"]) == 4
        assert chat["choices"][0]["message"]["role"] == "assistant"
        with urllib.request.urlopen(server.base + "/v1/models",
                                    timeout=30) as r:
            assert json.loads(r.read())["data"][0]["id"]
        with urllib.request.urlopen(server.base + "/healthz",
                                    timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"

    def test_validation_maps_to_400(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            server.post("/v1/completions", {"prompt": 7})
        assert e.value.code == 400
        assert json.loads(e.value.read())["error"]["type"]

    def test_string_prompt_and_token_prompt_agree(self, server):
        a = server.post("/v1/completions",
                        {"prompt": "hello world", "max_tokens": 4})
        ids = [b % VOCAB for b in b"hello world"]
        b2 = server.post("/v1/completions",
                         {"prompt": ids, "max_tokens": 4})
        assert (a["choices"][0]["token_ids"]
                == b2["choices"][0]["token_ids"])

    def test_disconnect_mid_stream_cancels_and_frees(self, server):
        """Closing the socket mid-SSE cancels the request: the engine
        frees its slot and pages instead of decoding to the budget."""
        eng = server.engine
        payload = json.dumps({"prompt": PROMPT, "max_tokens": 400,
                              "stream": True}).encode()
        raw = socket.create_connection(("127.0.0.1", server.srv.port),
                                       timeout=30)
        raw.sendall(
            b"POST /v1/completions HTTP/1.1\r\n"
            b"Host: x\r\nContent-Type: application/json\r\n"
            + f"Content-Length: {len(payload)}\r\n\r\n".encode()
            + payload)
        assert raw.recv(4096)  # headers + first chunk(s) flowing
        raw.close()
        for _ in range(300):
            if (not eng._active
                    and len(eng._free_pages) == eng.num_pages - 1):
                break
            time.sleep(0.02)
        assert not eng._active, "disconnected stream still decoding"
        assert len(eng._free_pages) == eng.num_pages - 1

    @pytest.mark.slow  # chaos-enforced; tier-1 wall budget
    def test_backpressure_maps_to_429(self, gpt):
        """Tenant backlog full → HTTP 429. The slow-step fault point
        pins the engine at ~10 steps/s so the occupied-slot window is
        deterministic (the smoke host is a single core — wall-clock
        racing would be a coin flip)."""
        s = _Server(gpt, max_slots=1, tenant_weights=None,
                    fault_plan="slow-step:every=1,delay_ms=100")
        try:
            s.frontend.queue._max_queue = 1
            # occupier holds the only slot for many slowed steps...
            occ = s.frontend.submit(PROMPT, 40)
            for _ in range(100):  # ...once the engine thread admits it
                if occ.rid is not None:
                    break
                time.sleep(0.05)
            assert occ.rid is not None
            # ...then the second ticket fills the 1-deep tenant backlog
            queued = s.frontend.submit(PROMPT, 40)
            with pytest.raises(urllib.error.HTTPError) as e:
                s.post("/v1/completions",
                       {"prompt": PROMPT, "max_tokens": 8}, timeout=30)
            assert e.value.code == 429
            assert json.loads(e.value.read())["error"]["type"] \
                == "queue_full"
            occ.result(timeout=120)
            queued.result(timeout=120)
        finally:
            s.close()


# ------------------------------------------------------------- subprocess
@pytest.mark.slow
class TestSubprocessLifecycle:
    @pytest.mark.timeout(300)
    def test_example_serves_and_drains_on_sigterm(self):
        """The acceptance lifecycle: ``serve_llama_paged.py --api-port``
        serves OpenAI-compatible streams from its own process, and
        SIGTERM mid-stream drains gracefully (stream finishes, process
        exits 0)."""
        proc = subprocess.Popen(
            [sys.executable, "-u",
             os.path.join(REPO, "examples", "serve_llama_paged.py"),
             "--tiny", "--api-port", "0", "--multi-step", "2",
             "--tenant-weights", "interactive=4,batch=1"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            port = None
            for line in proc.stdout:
                if line.startswith("api: http"):
                    # "api: http://127.0.0.1:PORT/v1/completions (...)"
                    port = int(line.split("/v1/")[0].rsplit(":", 1)[1])
                    break
            assert port is not None, proc.stderr.read()
            base = f"http://127.0.0.1:{port}"

            def stream(n):
                req = urllib.request.Request(
                    base + "/v1/completions",
                    data=json.dumps({"prompt": PROMPT,
                                     "max_tokens": n,
                                     "stream": True}).encode(),
                    headers={"Content-Type": "application/json"})
                toks = []
                with urllib.request.urlopen(req, timeout=120) as r:
                    for line in r:
                        line = line.decode().strip()
                        if not line.startswith("data: "):
                            continue
                        if line[6:] == "[DONE]":
                            break
                        toks.extend(json.loads(line[6:])
                                    ["choices"][0]["token_ids"])
                return toks

            first = stream(8)
            assert len(first) == 8
            assert stream(8) == first  # server-side determinism
            # SIGTERM mid-stream: the drain finishes the stream
            got = {}
            t = threading.Thread(
                target=lambda: got.update(toks=stream(24)))
            t.start()
            time.sleep(0.5)
            proc.send_signal(signal.SIGTERM)
            t.join(timeout=120)
            assert got.get("toks"), "drain lost the in-flight stream"
            assert proc.wait(timeout=60) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
