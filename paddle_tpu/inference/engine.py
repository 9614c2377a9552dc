"""Continuous-batching serving engine over the paged KV cache.

Reference capability: the serving loop behind
``paddle/fluid/inference/api/analysis_predictor.cc`` driving
``fused_multi_transformer_op.cu`` decode passes (SURVEY A19 + A3.x) —
request admission, KV cache management, decode scheduling, streaming
output. TPU-first design instead of a C++ executor loop:

* **Slots + pages.** ``max_slots`` sequence slots share one page pool per
  layer (vLLM-style block tables). A finished request's pages recycle
  immediately; physical page 0 is reserved as the trash page idle slots
  write into, so the compiled step needs no active-slot branching.
* **Compiled chunks, host scheduling.** Decode runs ``chunk_size`` steps
  per dispatch as ONE jitted ``lax.scan`` over functional
  ``PagedCacheState`` pytrees (block tables and lengths are traced
  operands — no recompile as requests come and go). The host only runs
  between chunks: harvest tokens, finish/free, admit, top up page
  allocations.
* **Chunk chaining (VERDICT r3 #1).** Every chain boundary costs a
  dispatch plus a blocking fetch; where that cost is large against a
  chunk's compute, fetching after every chunk is dispatch-latency-bound.
  ``step`` therefore dispatches up to ``max_chain`` chunks back-to-back
  on device arrays (each chunk's carry feeds the next without a host
  round trip) and fetches ALL their tokens in one ``device_get``. The chain depth maximizes USEFUL tokens
  per unit time (see ``_chain_depth``): stragglers may overshoot their
  budget mid-chain — overshoot tokens are harvested away, their writes
  land in trash/recycled pages, and the cache-write path caps lengths
  at the table capacity so overshoot can never run the attention kernel
  out of bounds. Pages are pre-allocated for the whole chain (capped at
  each request's own budget).
* **Batched admission, fused into the step (VERDICT r3 #1, r4 #2).**
  ALL admissible queued requests prefill in ONE bucketed dispatch: rows
  pad to the fixed max_slots bucket, prompts to a shared pow2 length
  bucket (capped at ``max_position`` so position ids never index past
  the embedding table), padding rows write to the trash page. The
  prefill dispatches back-to-back with the decode chain — the chain's
  inputs splice the prefill's device outputs — and ONE blocking fetch
  harvests both, so a scheduling step costs a single host round trip.
* **Pre-admission (VERDICT r4 #2).** When completions are predictable
  (no eos: budgets are host-known), the queue heads that will take over
  this chain's completing slots prefill DURING the chain, into freshly
  allocated pages; at harvest they activate into the freed slots with
  warm caches. Slot turnover then needs no extra round trip, and the
  straggler chain-depth clamp is only needed when an eos makes
  completions unpredictable.
* **Measured chain-boundary cost (VERDICT r4 #2).** Chain depth
  maximizes useful tokens per unit time against a MEASURED
  dispatch+fetch cost (EMA-fitted from warm pure-decode step timings,
  with a strictly bounded neighboring-depth probe when the workload is
  single-depth); ``DISPATCH_COST_CHUNKS_PRIOR`` seeds the estimate only
  until data arrives, so the same code picks sane depths whether a
  boundary costs many chunks of compute or next to none
  (``chip_smoke.py`` prints the ratio measured on the attached chip).
* **Active-slot buckets (VERDICT r3 #1).** The compiled decode chunk is
  sized to the pow2 bucket of the ACTIVE slot count, not ``max_slots``:
  the host compacts active slots' tables/lengths/last-token rows,
  decodes the compact batch, and scatters results back. At low
  occupancy per-token cost tracks load, not capacity.
* **Sampling (VERDICT r3 #9).** Per-request ``temperature`` (0 = greedy
  argmax — bit-identical to the contiguous path) with optional engine-
  level ``top_k``; per-slot PRNG keys thread through the compiled scan,
  and the key state survives preemption, so a preempted sampled request
  resumes with exactly the tokens it would have produced uninterrupted.
* **No head-of-line blocking.** Admission fills any free slot while other
  slots keep decoding; short requests drain and recycle their pages while
  long ones continue.
* **Speculative decoding (ISSUE 5).** ``Engine(..., spec="ngram"|"draft",
  spec_k=k)`` swaps the chained decode for drafter→verify scheduling:
  a pluggable drafter (model-free prompt lookup, or a small draft LM
  over its own paged pool) proposes up to k tokens, ONE verify forward
  through the same paged path scores all k+1 positions, and acceptance
  (token-exact for greedy — output identical to vanilla decode;
  distribution-preserving rejection sampling for temp>0) lands 1..k+1
  tokens per step. Rejected rows roll back through ``_trim_pages``;
  per-request draft depth adapts to an acceptance-rate EMA. See
  ``paddle_tpu/inference/spec/`` and README "Speculative decoding".
* **Fault tolerance (ISSUE 6).** ``step()`` never raises. Request-scoped
  faults — validation, page-pool exhaustion, non-finite logits (an
  in-program isfinite guard rides every compiled program), drafter
  faults, deadline/TTL expiry, cancellation, streaming-callback errors —
  move ONE request to the terminal ``FAILED`` state with a taxonomy
  reason (``paddle_tpu/inference/errors.py``) while co-batched requests
  keep decoding bit-identically to a fault-free run. Engine-scoped
  faults (a compiled dispatch dies) trigger requeue-all recompute
  recovery (prefixes re-prefill, PRNG keys travel — the preemption
  machinery reused wholesale) and feed the watchdog
  (``paddle_tpu/inference/watchdog.py``), which degrades spec→vanilla
  and halves the admission cap rather than dying, probing back up when
  healthy. Admission is bounded (``max_queue`` backpressure, per-request
  ``deadline_s``/``cancel()``, ``max_retries`` recompute bound with
  front-of-queue aging). Every failure path is drivable deterministically
  through the named fault-injection points
  (``paddle_tpu/testing/faultinject.py``, ``FLAGS_fault_inject``) and
  proven by ``tests/test_fault_tolerance.py`` (``make chaos``).
* **Chunked prefill (ISSUE 9).** ``Engine(..., prefill_chunk=N)`` stops
  long prompts from stalling the decode batch: instead of one bucketed
  prefill dispatch sized to the longest prompt, prompts stream into the
  cache N tokens at a time through a FIXED-SHAPE mixed step — one
  compiled program (the fused verify/suffix slab attention path,
  ``paged_multi_query_attention``) advances EVERY active slot each
  dispatch: decoding slots by one token (a width-1 slab row), prefilling
  slots by one chunk. One program shape per sampling flag, so a cold
  server compiles (or cache-loads) a couple of programs instead of a
  prefill bucket per prompt-length pow2 — first-wave throughput
  approaches steady state — and decode tokens keep landing every step
  while a 32k-token prompt trickles in (the Sarathi/vLLM chunked-prefill
  schedule). The final chunk's logits produce the request's first token
  exactly where classic prefill would, sampled key burns are gated to
  token-emitting rows, and the prefix cache splices/registers precisely
  as in the unchunked path — output streams are identical chunked on or
  off (``tests/test_chunked_prefill.py``, ``make chaos``).
* **Tensor-parallel serving (ISSUE 11).** The engine is split into
  engine-core (THIS module: the host scheduler — admission, harvest,
  retries, watchdog; device-count-agnostic), model-runner
  (``inference/runner.py``: the compiled programs and, with
  ``Engine(tp=N)``, the TP mesh they trace under — weights column/
  row-sharded via ``shard_map``, the paged pool sharded by KV head,
  host operands replicated) and cache-coordinator
  (``inference/cache_coord.py``: pool + refcount allocator + prefix
  cache; page tables host-global, device buffers per-shard). On top,
  ``Engine(disaggregate=True)`` separates prefill/decode ROLES within
  a scheduling step: mid-prompt slots stream chunks through the mixed
  program while decoding slots ride deep chains, one harvest fence,
  pages handed over through the shared pool. Token streams are
  bit-identical to the single-chip engine in every mode
  (``tests/test_tp_serving.py``); the sharded programs are statically
  gated by tpushard (``make analyze --mesh 1 --mesh 4 --mesh 8``).
* **Multi-step scheduling (ISSUE 12).** ``Engine(multi_step=N)`` (or an
  explicit ``step(n=N)``) amortizes the host round trip over N decode
  iterations: in pure-decode phases (queue empty, spec off, no prompt
  mid-stream) the scheduler dispatches N chained-decode programs
  BACK-TO-BACK — each chain's device outputs (pages, lengths, PRNG
  keys, last token) feed the next with no host fetch between — and
  harvests all N with ONE blocking ``device_get``. The Orca
  iteration-level-scheduling move: host work (numpy packing, harvest,
  metrics, the step spine) is paid once per N iterations instead of
  per iteration. Token streams are BIT-IDENTICAL to ``multi_step=1``
  in every mode (greedy, sampled, spec, chunked, disaggregated, TP —
  ``tests/test_multi_step.py``, ``make chaos``): per-row computation is
  unchanged, chains compose exactly as sequential steps would, and the
  harvest walks the chains in order with the same per-request isolation
  — early-exiting the moment the active set drains (eos/budget/fault),
  so later chains' rows for finished requests are discarded exactly
  like chain overshoot. Steps that must consult the host every
  iteration (admission waves, mixed chunk scheduling, spec drafting)
  keep classic stepping; ``paddle_tpu_engine_steps_per_roundtrip``
  records how many iterations each round trip actually batched.
* **Data integrity (ISSUE 14).** ``Engine(integrity="audit"|"strict")``
  arms the :class:`~paddle_tpu.inference.integrity.IntegritySentinel`
  against SILENT data corruption — the failure class where nothing
  raises and the engine streams confidently wrong tokens: load-time
  per-tensor weight digests re-checked by a periodic idle-step shard
  audit (mismatch → sticky watchdog QUARANTINE: the engine fail-stops,
  ``/readyz`` drops, the router migrates streams and supervised-
  restarts with verified weights); per-page KV checksums recorded at
  prefix-cache registration and re-verified before every splice
  commits (mismatch → invalidate-on-doubt + preempt active referents —
  corruption costs a miss or an exact-resume recompute, never a
  token); and, in strict mode, an every-N-steps shadow recompute of
  one greedy row through the contiguous twin (divergence → that
  request fails typed). Drive it with the ``bit-flip-weight`` /
  ``bit-flip-kv`` fault points; ``make chaos-integrity`` asserts no
  injected flip ever reaches a delivered token. See README "Data
  integrity".
* **Continuous telemetry (ISSUE 3).** Every scheduling step records the
  vLLM/Orca-style operational surface into the process-global metrics
  registry (``paddle_tpu.observability``): TTFT/TPOT/queue-wait
  histograms, batch-occupancy and chain-depth distributions, preemption
  and page-eviction counters, page-pool gauges. All recording is host
  code between dispatches (never traced — tpulint TPL601) and is
  disabled wholesale by ``Engine(..., metrics=False)``. Scrape it via
  ``observability.start_metrics_server`` (see
  ``examples/serve_llama_paged.py --metrics-port``).

The engine is model-agnostic: anything with the causal-LM cache contract
(``forward(ids, caches=..., time_step=None)`` handling ``PagedCacheState``,
plus ``config`` with num_layers / num_kv_heads / head_dim) serves — GPT and
LLaMA both qualify.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor, pause_tape
from ..observability.tracing import TRACER as _TRACER
from ..observability.tracing import flight_record as _flight_record
from ..ops.pallas.paged_attention import PagedCacheState
from ..testing.faultinject import FaultPlan, InjectedFault, plan_from_flags
from .errors import (
    AdmissionRejected,
    CallbackError,
    CancelledError,
    DeadlineExceeded,
    NumericsError,
    PoolExhausted,
    QueueFull,
    RequestError,
    RetriesExhausted,
    StepFault,
    ValidationError,
    failure_reason,
)
from .watchdog import Watchdog


def _phase(name):
    """Run the method inside the tracer's nested span ``name``: one phase
    of a scheduling step on the engine thread, a child of ``engine.step``
    (or of the phase that called it), so a phase's self time is its span
    less its children. With the tracer off the span is the profiler's
    annotation alone, which is what joins the host to a device trace."""
    def deco(fn):
        @functools.wraps(fn)
        def run(self, *args, **kwargs):
            with _TRACER.nested(name, "engine"):
                return fn(self, *args, **kwargs)
        return run
    return deco


@jax.jit
def _advance_sample_key(key, burns):
    """Replay ``burns`` sampling-key splits host-free (one fori_loop
    dispatch, ``burns`` a traced scalar so every count shares one
    compiled program). The vanilla decode/prefill paths burn EXACTLY one
    ``jax.random.split`` per DELIVERED token for a temp>0 request (see
    ``_select_token``: ``new_keys = splits[:, 0]``; chunked prefill is
    emit-gated the same way), so a stream migrated to another replica
    with only (prompt, emitted tokens, seed) in hand can reconstruct its
    live key state as ``split^t(seed_key)[0]`` — the resume-from-emitted
    admission path (ISSUE 13). Spec decode burns a fixed k+2 keys per
    VERIFY STEP instead (step count is not recoverable from the token
    count), which is why ``add_request`` rejects sampled resumes on a
    spec-enabled engine."""
    return jax.lax.fori_loop(
        0, burns, lambda _, k: jax.random.split(k, 2)[0], key)


@jax.jit
def _patch_rows(last_c, keys_c, rows, toks, keys):
    """Splice a prefill wave's first tokens and PRNG keys into the decode
    chain's compacted inputs ON DEVICE — the glue that lets freshly
    admitted requests join the same step's chain without the host ever
    fetching the prefill results separately. Pad rows carry an
    out-of-bounds index and drop. (jit caches per shape by itself.)"""
    return (last_c.at[rows].set(toks, mode="drop"),
            keys_c.at[rows].set(keys, mode="drop"))


@jax.jit
def _last_col(toks):
    """Final token column of a chain's [nb, steps] output block — the
    next chain's last-token input in a multi-step round trip (ISSUE 12).
    Jitted: the eager dynamic-slice dispatch costs ~10x a cached jit
    call on the hot path (measured ~46% of the multi-step loop)."""
    return toks[:, -1]


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pages(pages_flat, src, dst):
    """Copy-on-write page duplication ON DEVICE: physical pages ``src``
    copied to ``dst`` across every layer's k/v (and scale) buffers in one
    dispatch — the whole admission wave's COW set at once. Donated so the
    pool updates in place."""
    return [p.at[dst].set(p[src]) for p in pages_flat]


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@contextlib.contextmanager
def _moe_tap(n: int):
    """Arm the MoE router-stats tap around ONE ``model.forward`` when
    the engine serves an MoE config (``n`` = stats width,
    ``moe_stats_size(cfg)``; 0 = dense engine, no-op). Yields the
    per-layer stats list the MoE layers append to (traced arrays — the
    raw program sums them into its trailing stats output)."""
    # tpulint: disable=TPL301 -- n is a static Python int (the config's
    # stats width, fixed at program-build time), never a tracer; the
    # branch selects program STRUCTURE (dense vs MoE), not a data path
    if not n:
        yield None
        return
    from ..models.llama import moe_stats_tap

    with moe_stats_tap() as tap:
        yield tap


def make_mixed_step_fn(engine, sampling):
    """Build the raw mixed chunk+decode step (ISSUE 9 tentpole b) — the
    fixed-shape program ``Engine(prefill_chunk=)`` dispatches every
    scheduling step. ``ids [nb, chunk]`` carries, per row, EITHER the
    next chunk of a streaming prompt (width w ≤ chunk) OR a decoding
    slot's last token (width 1); ``paged_state_verify`` (verify=True +
    per-row ``prefill_valid`` widths) writes each row's w tokens at
    [len, len+w) and scores every position over cache + causal prefix
    through ``paged_multi_query_attention`` — the fused slab kernel on
    TPU, its jnp twin elsewhere. The token at position w-1 is the row's
    next token: meaningful for decode rows and for a prompt's FINAL
    chunk (the first generated token, taken exactly where classic
    prefill takes it); mid-prompt rows discard it. ``emit`` gates the
    sampled-key burn to token-emitting rows, so a sampled stream burns
    exactly one draw per delivered token — the invariant that makes
    chunked-on output bit-identical to chunked-off.

    Returns the UNJITTED python function (the engine wraps it with
    ``jax.jit(donate_argnums=(1,))``); the tpucheck registry traces the
    same raw function (``tools/analyze_tpu.py`` entry
    ``chunked_prefill_step``)."""
    model = engine.model
    moe_n = getattr(engine, "_moe_stats_n", 0)

    def mixed_chunk_step(params, pages_flat, ids, widths, emit, tables,
                         lengths, temps, keys):
        from ..jit import swapped_tensors

        with swapped_tensors(engine._swap, params), pause_tape():
            states = engine._states_from(pages_flat, tables, lengths,
                                         prefill_valid=widths,
                                         verify=True)
            with _moe_tap(moe_n) as tap:
                logits, new_states = model.forward(Tensor._wrap(ids),
                                                   caches=states)
            lg = logits._data if isinstance(logits, Tensor) else logits
            last = jnp.take_along_axis(
                lg, (widths - 1)[:, None, None], axis=1)[:, 0]
            last = last.astype(jnp.float32)
            # NaN/inf logit guard (ISSUE 6): the host fails THAT request
            bad = ~jnp.all(jnp.isfinite(last), axis=-1)
            greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
            if sampling:
                tok, burned = engine._select_token(last, greedy, temps,
                                                   keys)
                new_keys = jnp.where((emit > 0)[:, None], burned, keys)
            else:
                tok, new_keys = greedy, keys
            out = tok, new_keys, bad, engine._pages_of(new_states)
            if moe_n:
                out += (jnp.sum(jnp.stack(tap), axis=0),)
            return out

    return mixed_chunk_step


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    on_token: Optional[Callable] = None  # streaming callback(list[int])
    temperature: float = 0.0  # 0 → greedy argmax
    seed: Optional[int] = None  # sampling seed (None → rid)
    # multi-tenant serving (ISSUE 12): the admission-control/fairness
    # identity; labels the TTFT/queue-wait/failure metrics (bounded
    # cardinality — see _EngineMetrics._tenant_label)
    tenant: str = "default"
    tokens: List[int] = field(default_factory=list)  # generated tokens
    done: bool = False
    slot: Optional[int] = None
    # lifecycle hardening (ISSUE 6):
    deadline: Optional[float] = None   # absolute perf_counter deadline
    retries: int = 0                   # recompute re-queues so far
    failure: Optional[BaseException] = None  # taxonomy error on FAILED
    failure_reason: Optional[str] = None     # its stable reason slug
    _key: Optional[np.ndarray] = None  # live PRNG key (survives preemption)
    # request tracing (ISSUE 18): parent SpanContext wire string the
    # engine's spans/instants nest under; None when tracing is off or
    # the caller didn't propagate one
    trace: Optional[str] = None
    # telemetry timestamps (host wall clock, perf_counter units):
    _t_arrival: float = 0.0          # add_request time (TTFT base)
    _t_submit: Optional[float] = None  # upstream submit time (placement)
    _t_admit: Optional[float] = None   # slot admission (prefill base)
    _t_promote_wait: float = 0.0       # KV-tier promote wait inside admit
    _t_first: Optional[float] = None   # first generated-token harvest
    _t_last: Optional[float] = None    # latest harvest (TPOT base)
    _admitted: bool = False            # queue-wait recorded once

    @property
    def failed(self) -> bool:
        return self.failure_reason is not None

    @property
    def state(self) -> str:
        """Lifecycle state: QUEUED → ACTIVE → FINISHED | FAILED.
        FAILED is terminal and carries ``failure_reason`` (the taxonomy
        slug) + ``failure`` (the exception)."""
        if self.failed:
            return "FAILED"
        if self.done:
            return "FINISHED"
        if self.slot is not None:
            return "ACTIVE"
        return "QUEUED"


class _EngineMetrics:
    """The engine's serving telemetry bundle (ISSUE 3 tentpole). Every
    record site lives in the scheduler's HOST code — between dispatches,
    never inside traced functions (tpulint TPL601). Metrics are process-
    global (the registry get-or-creates by name), so several engines in
    one process aggregate into one scrape — the Prometheus convention."""

    def __init__(self):
        from ..observability import SIZE_BUCKETS, counter, gauge, histogram

        # TTFT/queue-wait/failures carry a ``tenant`` label (ISSUE 12
        # satellite) so per-tenant SLOs are scrape-visible; engine-direct
        # traffic lands on the "default" tenant. Cardinality is bounded:
        # past _TENANT_CAP distinct tenants, new ones share "other".
        self.ttft = histogram(
            "paddle_serving_ttft_seconds",
            "request arrival to first generated token, by tenant",
            labelnames=("tenant",))
        self.tpot = histogram(
            "paddle_serving_tpot_seconds",
            "mean inter-token latency per harvest (time-per-output-token)")
        self.queue_wait = histogram(
            "paddle_serving_queue_wait_seconds",
            "request arrival to slot admission, by tenant",
            labelnames=("tenant",))
        # TTFT latency attribution (ISSUE 18): the components partition
        # [submit, first-token] exactly on one perf_counter clock —
        # placement (upstream submit → engine arrival) + queue_wait
        # (arrival → admission, minus promote) + promote_wait (KV-tier
        # promotions awaited during admission splice) + prefill
        # (admission → first harvest) sum to the observed TTFT.
        self.ttft_component = histogram(
            "paddle_serving_ttft_component_seconds",
            "TTFT decomposition: placement|queue_wait|promote_wait|"
            "prefill component of arrival-to-first-token",
            labelnames=("component",))
        self.step_seconds = histogram(
            "paddle_serving_step_seconds",
            "wall time of one scheduling step (dispatch+harvest fence)")
        self.prefill_batch = histogram(
            "paddle_serving_prefill_batch_size",
            "requests per bucketed prefill wave", buckets=SIZE_BUCKETS)
        self.decode_batch = histogram(
            "paddle_serving_decode_batch_size",
            "active slots per decode chain dispatch", buckets=SIZE_BUCKETS)
        self.chain_depth = counter(
            "paddle_serving_chain_depth_total",
            "decode chains dispatched, by chosen chunk depth",
            labelnames=("depth",))
        self.preemptions = counter(
            "paddle_serving_preemptions_total",
            "requests evicted under page-pool pressure (recompute policy)")
        self.page_evictions = counter(
            "paddle_serving_page_evictions_total",
            "KV pages recycled by preemption")
        self.requests = counter(
            "paddle_serving_requests_total", "requests accepted")
        self.completed = counter(
            "paddle_serving_requests_completed_total", "requests finished")
        self.tokens = counter(
            "paddle_serving_tokens_total", "generated tokens delivered")
        self.compiled = counter(
            "paddle_serving_compiled_programs_total",
            "engine programs compiled, by kind", labelnames=("kind",))
        self.pages_in_use = gauge(
            "paddle_serving_pages_in_use", "KV pages currently allocated")
        self.pages_total = gauge(
            "paddle_serving_pages_total", "allocatable KV pages in the pool")
        self.active_slots = gauge(
            "paddle_serving_active_slots", "slots currently decoding")
        self.queue_depth = gauge(
            "paddle_serving_queue_depth", "requests waiting for a slot")
        # fault-tolerance surface (ISSUE 6): the reason label mirrors the
        # error-taxonomy slugs in inference/errors.py one-to-one
        self.failures = counter(
            "paddle_tpu_request_failures_total",
            "requests moved to terminal FAILED, by taxonomy reason and "
            "tenant", labelnames=("reason", "tenant"))
        self.admission_rejected = counter(
            "paddle_tpu_admission_rejected_total",
            "requests rejected at add_request (validation, capacity, "
            "queue backpressure)")
        self.retries = counter(
            "paddle_tpu_request_retries_total",
            "recompute re-queues (preemption or step-fault recovery)")
        self.recoveries = counter(
            "paddle_tpu_engine_recoveries_total",
            "whole-step fault recoveries (requeue-all + page-pool reset)")
        self.degraded = gauge(
            "paddle_tpu_engine_degraded",
            "degraded-mode level: 0 healthy, 1 spec decode disabled, "
            "2 admission cap halved on top")
        # readiness export (ISSUE 13): the /readyz surface and the
        # router's health gate read this — 1 while the watchdog judges
        # the engine fit for NEW traffic (level < SMALL_BATCH)
        self.ready = gauge(
            "paddle_tpu_engine_ready",
            "watchdog readiness: 1 = accepting new traffic, 0 = "
            "degraded past the readiness threshold (in-flight work "
            "still completes)")
        # prefix-cache surface (ISSUE 8): admission hit/miss, the cached-
        # vs-computed prefill-token split, pressure evictions, and the
        # pool share the cache currently holds
        self.pc_hits = counter(
            "paddle_tpu_prefix_cache_hits_total",
            "admissions that spliced a cached block-aligned prefix")
        self.pc_misses = counter(
            "paddle_tpu_prefix_cache_misses_total",
            "admissions that found no cached prefix")
        self.pc_evictions = counter(
            "paddle_tpu_prefix_cache_evictions_total",
            "idle cached pages reclaimed under pool pressure (LRU)")
        self.pc_cached_tokens = counter(
            "paddle_tpu_prefix_cached_prefill_tokens_total",
            "prefill tokens served from cached pages (compute skipped)")
        self.pc_computed_tokens = counter(
            "paddle_tpu_prefix_computed_prefill_tokens_total",
            "prefill tokens actually computed by a prefill wave")
        self.pc_pages = gauge(
            "paddle_tpu_prefix_cache_pages",
            "physical pages currently mapped by the prefix cache "
            "(pool share = this / paddle_serving_pages_total)")
        # decode hot-path kernel surface (ISSUE 9): how many prompt
        # chunks streamed through the mixed step, and which paths
        # dispatched the fused verify/suffix slab program (the label
        # mirrors the three consumers: spec verify, prefix-cache suffix
        # prefill, chunked prefill)
        # expert-parallel MoE serving surface (ISSUE 17): capacity-drop
        # pressure, per-expert routing load (bounded labels), and the
        # router's distribution entropy (collapse detector: uniform
        # routing sits at ln(num_experts), a collapsed router near 0)
        self.moe_dropped = counter(
            "paddle_tpu_moe_tokens_dropped_total",
            "(token, expert-choice) pairs dropped by the capacity "
            "factor; combine weights renormalize over the survivors")
        self.moe_expert_tokens = counter(
            "paddle_tpu_moe_expert_tokens_total",
            "routed (token, choice) pairs kept per expert (bounded "
            "cardinality: experts past the cap share 'other')",
            labelnames=("expert",))
        self.moe_router_entropy = gauge(
            "paddle_tpu_moe_router_entropy_nats",
            "mean router-distribution entropy of the most recently "
            "drained MoE dispatches")
        self._moe_expert_children: Dict[int, object] = {}
        self.prefill_chunks = counter(
            "paddle_tpu_prefill_chunks_total",
            "prompt chunks admitted into the mixed chunk+decode step")
        self.slab_dispatch = counter(
            "paddle_tpu_slab_verify_dispatch_total",
            "multi-query slab-attention programs dispatched, by path "
            "(the fused Pallas kernel on TPU, its jnp twin on CPU)",
            labelnames=("path",))
        # KV host-tier surface (ISSUE 15): the demote/promote ladder
        # under the prefix cache — spills to host DRAM, checksum-
        # verified restores, lookups that reached host-resident content,
        # blocks lost to host-capacity pressure or a failed promote
        # digest, per-tier page occupancy, and how long a promotion
        # spent between the hit that requested it and the verified
        # payload landing back on device
        self.kv_demotions = counter(
            "paddle_tpu_kv_tier_demotions_total",
            "idle cached KV pages spilled device -> host (eviction "
            "turned demotion)")
        self.kv_promotions = counter(
            "paddle_tpu_kv_tier_promotions_total",
            "demoted KV pages restored host -> device after their "
            "checksum verified")
        self.kv_tier_hits = counter(
            "paddle_tpu_kv_tier_hits_total",
            "admission lookups whose hash chain reached host-tier "
            "content (the hit that triggers an async promote-back)")
        self.kv_drops = counter(
            "paddle_tpu_kv_tier_drops_total",
            "demoted blocks lost: host slab full, or a promotion "
            "failed its demotion-time digest (invalidate + recompute)")
        self.kv_tier_pages = gauge(
            "paddle_tpu_kv_tier_pages",
            "prefix-cache pages resident per tier (hbm = spliceable "
            "device pages, host = spilled slab rows)",
            labelnames=("tier",))
        self.kv_promote_seconds = histogram(
            "paddle_tpu_kv_tier_promote_seconds",
            "hash-chain hit on a demoted page to its verified bytes "
            "landing back in the device pool")
        # multi-step scheduling surface (ISSUE 12): how many engine
        # iterations each host round trip actually batched (1 = classic
        # stepping; N = the multi-step fast path engaged at depth N)
        self.steps_per_roundtrip = histogram(
            "paddle_tpu_engine_steps_per_roundtrip",
            "engine iterations batched behind one host round trip "
            "(multi-step scheduling; 1 = classic per-iteration stepping)",
            buckets=SIZE_BUCKETS)
        # per-depth counter children cached here: .labels() costs a
        # tuple build + dict probe per call, and step() hits one depth
        # every iteration
        self._depth_children: Dict[int, object] = {}
        # per-tenant histogram/counter children, same rationale; the
        # seen-set bounds label cardinality (a hostile client cycling
        # tenant strings must not grow the scrape unboundedly)
        self._tenant_seen: set = set()
        self._ttft_children: Dict[str, object] = {}
        self._qwait_children: Dict[str, object] = {}
        # TTFT-component children: four fixed labels, cached eagerly
        self._component_children: Dict[str, object] = {
            c: self.ttft_component.labels(component=c)
            for c in ("placement", "queue_wait", "promote_wait",
                      "prefill")}

    _TENANT_CAP = 24  # distinct tenant label values before "other"
    _EXPERT_CAP = 32  # distinct expert label values before "other"

    def moe_expert_at(self, e: int):
        child = self._moe_expert_children.get(e)
        if child is None:
            label = str(e) if e < self._EXPERT_CAP else "other"
            child = self.moe_expert_tokens.labels(expert=label)
            self._moe_expert_children[e] = child
        return child

    def chain_depth_at(self, k: int):
        child = self._depth_children.get(k)
        if child is None:
            child = self.chain_depth.labels(depth=k)
            self._depth_children[k] = child
        return child

    def _tenant_label(self, tenant: str) -> str:
        t = tenant or "default"
        if t not in self._tenant_seen:
            if len(self._tenant_seen) >= self._TENANT_CAP:
                return "other"
            self._tenant_seen.add(t)
        return t

    def ttft_for(self, tenant: str):
        t = self._tenant_label(tenant)
        child = self._ttft_children.get(t)
        if child is None:
            child = self.ttft.labels(tenant=t)
            self._ttft_children[t] = child
        return child

    def queue_wait_for(self, tenant: str):
        t = self._tenant_label(tenant)
        child = self._qwait_children.get(t)
        if child is None:
            child = self.queue_wait.labels(tenant=t)
            self._qwait_children[t] = child
        return child

    def on_harvest(self, req: Request, fresh: int):
        """Per-request token-latency accounting; called once per harvest
        with the number of fresh tokens DELIVERED — never an assumed
        per-step constant. A vanilla chained step lands k*chunk_size
        tokens, a spec verify step lands 1..spec_k+1 depending on
        acceptance (ISSUE 5 satellite): both normalize the harvest span
        by the accepted count, so the TPOT histogram stays a true
        per-token latency while acceptance varies. (The chain-depth
        maximizer's dispatch-cost EMA is likewise acceptance-proof: it
        only samples pure-decode CHAIN steps — _observe_chain_time —
        which spec steps never feed.)"""
        now = time.perf_counter()
        if req._t_first is None:
            req._t_first = now
            self.ttft_for(req.tenant).observe(now - req._t_arrival)
            self._on_first_token(req, now)
            if fresh > 1:
                # a chained harvest delivers first token + decode tokens
                # at once; attribute the span evenly to the decode tokens
                self.tpot.observe((now - req._t_arrival) / fresh)
        elif req._t_last is not None and fresh:
            self.tpot.observe((now - req._t_last) / fresh)
        req._t_last = now
        self.tokens.inc(fresh)

    def _on_first_token(self, req: Request, now: float):
        """TTFT latency attribution (ISSUE 18), emitted once at first
        harvest: the four components partition [submit, first-token] on
        the perf_counter clock — placement = submit→arrival, queue_wait
        = arrival→admit minus the promote wait spent inside the
        admission splice, promote_wait = that wait, prefill =
        admit→first-token — so their sum IS the TTFT (float error
        only). Observed into the labeled histogram always; laid down as
        retroactive child spans when the request carries a trace."""
        base = req._t_submit if req._t_submit is not None \
            else req._t_arrival
        admit = req._t_admit if req._t_admit is not None \
            else req._t_arrival
        promote = req._t_promote_wait
        comps = (
            ("placement", base, req._t_arrival - base),
            ("queue_wait", req._t_arrival,
             (admit - req._t_arrival) - promote),
            ("promote_wait", admit - promote, promote),
            ("prefill", admit, now - admit),
        )
        for cname, _, dur in comps:
            self._component_children[cname].observe(max(0.0, dur))
        if _TRACER.enabled and req.trace is not None:
            wall = time.time()
            for cname, t0, dur in comps:
                _TRACER.complete(f"ttft.{cname}", "ttft",
                                 wall - (now - t0), dur,
                                 parent=req.trace, rid=req.rid)
            _TRACER.complete("ttft", "ttft", wall - (now - base),
                             now - base, parent=req.trace,
                             rid=req.rid, tenant=req.tenant)


class Engine:
    """Continuous-batching engine; see module docstring."""

    def __init__(self, model, max_slots=8, num_pages=512, page_size=16,
                 chunk_size=16, eos_id: Optional[int] = None,
                 dtype=jnp.bfloat16, quantized_cache=False, max_chain=8,
                 top_k: Optional[int] = None, metrics: bool = True,
                 spec: Optional[str] = None, spec_k: int = 4,
                 draft_model=None, max_queue: Optional[int] = None,
                 deadline_s: Optional[float] = None, max_retries: int = 8,
                 fault_plan=None, watchdog: Optional[dict] = None,
                 prefix_cache: bool = False, kv_host_pages: int = 0,
                 prefill_chunk: Optional[int] = None,
                 tp: Optional[int] = None, ep: Optional[int] = None,
                 capacity_factor: Optional[float] = None,
                 disaggregate: bool = False,
                 multi_step: int = 1, integrity=None):
        cfg = model.config
        self.model = model
        self.cfg = cfg
        self.max_slots = max_slots
        self.page_size = page_size
        self.chunk_size = chunk_size
        self.dtype = dtype
        self.max_chain = max(1, int(max_chain))
        if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
            # fail here, not as an opaque trace-time lax.top_k error at
            # the first sampled request (code-review r4)
            raise ValueError(
                f"top_k={top_k} must be in [1, vocab_size="
                f"{cfg.vocab_size}]")
        self.top_k = top_k
        self.eos_id = eos_id
        self.quantized = bool(quantized_cache)
        self.max_pages_per_seq = cfg.max_position // page_size
        self.num_pages = num_pages
        # expert-parallel MoE serving (ISSUE 17): an MoE config grows
        # every compiled program ONE trailing router-stats output
        # (per-expert kept counts, capacity drops, entropy — see
        # models.llama.moe_stats_size); _moe_pending holds undrained
        # device handles, _moe_tot the cumulative host aggregate.
        n_exp = int(getattr(cfg, "num_experts", 0) or 0)
        self._moe_stats_n = (n_exp + 3) if n_exp else 0
        self._moe_pending: List = []
        self._moe_tot = np.zeros((self._moe_stats_n,), np.float64)
        if capacity_factor is not None:
            if not n_exp:
                raise ValueError(
                    "capacity_factor= on a dense model: the capacity "
                    "factor sizes each expert's token buffer — serve an "
                    "MoE config or drop the knob")
            cf = float(capacity_factor)
            if cf <= 0:
                raise ValueError(
                    f"capacity_factor={cf} must be > 0 (it scales the "
                    "per-expert token capacity ceil(cf*k*T/E))")
            # host-side override BEFORE any trace: capacity is a static
            # shape input, so changing it later would silently recompile
            for lyr in model.sublayers(include_self=True):
                if hasattr(lyr, "router") and hasattr(lyr, "experts_gate"):
                    lyr.capacity_factor = cf
        # model-runner (ISSUE 11 tentpole): owns the compiled programs
        # and — at tp>1 / ep>1 — the mesh they trace under (weights
        # column/row-sharded over tp, stacked expert weights sharded
        # over ep, KV pool head-sharded, host operands replicated; one
        # shard_map per dispatch). The scheduler below stays
        # device-count-agnostic.
        from .runner import ModelRunner

        self.runner = ModelRunner(self, tp, ep)
        # compiled-program shapes quantize to this (watchdog batch
        # shrink must keep slot caps mesh-aligned — ISSUE 11 satellite)
        self._batch_quantum = self.runner.tp if self.runner.sharded else 1
        # chunked prefill (ISSUE 9): prompts stream into the cache
        # prefill_chunk tokens per mixed step instead of one bucketed
        # prefill dispatch; _chunk_left maps a mid-prefill slot to the
        # prompt tokens not yet written
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if not 2 <= prefill_chunk <= cfg.max_position:
                # 1-wide slabs would hit the reference's GEMV path and
                # one chunk per token is a pathological schedule anyway;
                # fail at construction, not mid-serve
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be in "
                    f"[2, max_position={cfg.max_position}]")
        self.prefill_chunk = prefill_chunk
        # prefill/decode role disaggregation (ISSUE 11): prefill-role
        # slots stream chunks through the mixed program while
        # decode-role slots ride deep chains in the SAME scheduling
        # step, pages handed over through the cache-coordinator
        self.disaggregate = bool(disaggregate)
        if self.disaggregate and prefill_chunk is None:
            raise ValueError(
                "disaggregate=True requires prefill_chunk (prefill-role "
                "steps stream prompts chunk-by-chunk)")
        self._chunk_left: Dict[int, np.ndarray] = {}
        # cache-coordinator (ISSUE 11 tentpole): the paged pool +
        # allocator + prefix cache. Page tables and refcounts stay
        # host-global (PR 8's COW logic untouched); the device buffers
        # partition across the TP axis when the runner is sharded.
        # kv_host_pages > 0 (ISSUE 15) arms the host-DRAM spill tier
        # below the pool: idle cached pages demote asynchronously
        # instead of evicting, and hash-chain hits on demoted pages
        # promote back checksum-verified — 0 (the default) builds no
        # tier, no worker thread, and byte-identical scheduling.
        from .cache_coord import CacheCoordinator

        self._cache = CacheCoordinator(self, prefix_cache=prefix_cache,
                                       kv_host_pages=kv_host_pages)
        self._queue: List[Request] = []
        self._active: Dict[int, Request] = {}  # slot -> request
        self._last_tok = np.zeros((max_slots,), np.int32)
        self._temps = np.zeros((max_slots,), np.float32)
        self._keys = np.zeros((max_slots, 2), np.uint32)
        self._next_rid = 0
        # multi-step scheduling (ISSUE 12): default iterations batched
        # per host round trip when step() is called without n; the fast
        # path only engages where streams provably stay bit-identical
        # (see _multi_chained_step)
        self.multi_step = max(1, int(multi_step))
        self._chain_time_ema = {}   # depth k -> EMA step wall seconds
        self._chain_obs = 0          # pure-decode steps observed
        self._probe_budget = 2       # bounded depth-calibration probes
        self._dispatch_ratio = None  # measured boundary cost, chunk units
        # serving state that must travel as jit ARGUMENTS: parameters
        # plus buffers (a weight-only-quantized model keeps its int8/int4
        # weights + scales as buffers; baking them in as jit constants
        # would bloat every compiled bucket by the full weight bytes)
        self._swap = [p for _, p in model.named_parameters()]
        self._swap += [b for _, b in model.named_buffers()
                       if b is not None]
        # placed ONCE on the runner's mesh (column/row shards at tp>1),
        # so no dispatch ever re-shards the weights
        self._params = self.runner.place_params(
            [t._data for t in self._swap])
        # process-global serving telemetry; metrics=False drops every
        # record site to a single None check
        self._m = _EngineMetrics() if metrics else None
        if self._m is not None:
            self._m.pages_total.set(num_pages - 1)  # page 0 is trash
        # speculative decoding (ISSUE 5): spec="ngram" (model-free prompt
        # lookup) or "draft" (small draft LM, pass draft_model=); the
        # scheduling loop swaps the chained decode for drafter→verify
        # steps landing 1..spec_k+1 tokens each — see _spec_step
        self._spec = None
        if spec not in (None, "off"):
            from .spec import SpecDecoder

            self._spec = SpecDecoder(self, mode=spec, k=spec_k,
                                     draft_model=draft_model)
        # ---- fault tolerance (ISSUE 6) --------------------------------
        self.max_queue = max_queue
        self.deadline_s = deadline_s
        self.max_retries = int(max_retries)
        self._has_deadlines = deadline_s is not None
        self._stall_steps = 0  # consecutive queued-but-unadmittable steps
        self._pending_inflight = []  # pre-admissions the current step owns
        # promote wait measured by the most recent _splice_prefix — the
        # admission loop attributes it to the request it spliced for
        # (TTFT decomposition, ISSUE 18)
        self._last_promote_wait_s = 0.0
        # deterministic fault injection: explicit plan/spec wins, else the
        # FLAGS_fault_inject / PADDLE_TPU_FAULT_INJECT flag
        self._fi = (FaultPlan.from_spec(fault_plan)
                    if fault_plan is not None else plan_from_flags())
        # the watchdog owns _spec_enabled and _slot_cap (degraded-mode
        # state machine: spec→vanilla, then admission cap halved, with
        # recovery probing); kwargs tune its thresholds
        self._spec_enabled = True
        self._slot_cap = max_slots
        self._watchdog = Watchdog(self, **(watchdog or {}))
        # ---- data-integrity sentinel (ISSUE 14) -----------------------
        # integrity="audit"|"strict"|dict|IntegrityConfig arms online
        # SDC audits: load-time weight digests with periodic idle-step
        # shard probes, per-page KV checksums verified at splice and
        # re-registration, and (strict) an every-N-steps shadow
        # recompute of one greedy row through the contiguous twin.
        # Constructed LAST: the weight baseline digests the freshly
        # placed _params, and the cache-coordinator's alloc hooks read
        # the attribute via getattr (it does not exist during the
        # coordinator's own construction above).
        from .integrity import IntegritySentinel

        self._integrity = IntegritySentinel.build(self, integrity)

    # --------------------------------------------- engine-core delegation
    # The tentpole split (ISSUE 11) moved pool/allocator state into the
    # cache-coordinator and program caches into the model-runner; the
    # scheduler (and its tests) keep reading them through these
    # delegators, so PR 6-9's host logic runs textually unchanged.
    @property
    def tables(self):
        return self._cache.tables

    @property
    def lengths(self):
        return self._cache.lengths

    @property
    def _page_ref(self):
        return self._cache.page_ref

    @property
    def _pcache(self):
        return self._cache.pcache

    @property
    def kv_tier(self):
        """The host-DRAM spill tier (ISSUE 15), or None when
        ``kv_host_pages`` was 0."""
        return self._cache.tier

    @property
    def _cow_pending(self):
        return self._cache.cow_pending

    @_cow_pending.setter
    def _cow_pending(self, v):
        self._cache.cow_pending = v

    @property
    def _free_pages(self):
        return self._cache.free_pages

    @_free_pages.setter
    def _free_pages(self, v):
        self._cache.free_pages = v

    @property
    def _free_slots(self):
        return self._cache.free_slots

    @_free_slots.setter
    def _free_slots(self, v):
        self._cache.free_slots = v

    @property
    def k_pages(self):
        return self._cache.k_pages

    @k_pages.setter
    def k_pages(self, v):
        self._cache.k_pages = v

    @property
    def v_pages(self):
        return self._cache.v_pages

    @v_pages.setter
    def v_pages(self, v):
        self._cache.v_pages = v

    @property
    def scale_pages(self):
        return self._cache.scale_pages

    @scale_pages.setter
    def scale_pages(self, v):
        self._cache.scale_pages = v

    @property
    def _decode_fns(self):
        return self.runner.decode_fns

    @property
    def _prefill_fns(self):
        return self.runner.prefill_fns

    @property
    def _mixed_fns(self):
        return self.runner.mixed_fns

    # ------------------------------------------------------------- requests
    def _reject(self, exc):
        """Reject-at-submission: count it and raise the taxonomy error
        (all admission-time classes also subclass ValueError)."""
        if self._m is not None:
            self._m.admission_rejected.inc()
        raise exc

    def add_request(self, prompt, max_new_tokens, on_token=None,
                    temperature=0.0, seed=None,
                    deadline_s: Optional[float] = None,
                    tenant: Optional[str] = None,
                    resume_tokens=None, trace=None,
                    t_submit: Optional[float] = None) -> Request:
        """Submit a request. EVERY way the request could be unservable is
        checked here, up front (ISSUE 6 satellite): malformed input →
        ``ValidationError``, a sequence the pool/table geometry can never
        hold → ``AdmissionRejected``, bounded-queue backpressure →
        ``QueueFull``. Nothing about a single request can fail mid-step
        for a reason that was knowable at submission.

        ``resume_tokens`` (ISSUE 13) is the resume-from-emitted admission
        path for replica failover: tokens this stream ALREADY emitted on
        a replica that died. They count against ``max_new_tokens`` but
        are never re-delivered through ``on_token`` — admission
        re-prefills prompt‖emitted (the preemption machinery's
        ``_prefix``, with the prefix cache absorbing the recompute) and
        generation continues bit-identically where the dead replica
        stopped. Seeded-sampled streams reconstruct their key state by
        replaying one key split per emitted token
        (``_advance_sample_key``); that replay is exact for the vanilla
        and chunked paths but not under spec decode (fixed k+2 burns per
        verify STEP), so a sampled resume on a spec-enabled engine is
        rejected up front rather than silently diverging."""
        raw = np.asarray(prompt)
        if raw.dtype.kind not in "iu":
            self._reject(ValidationError(
                f"prompt must be integer token ids, got dtype {raw.dtype}"))
        prompt = raw.astype(np.int32).reshape(-1)
        if prompt.size == 0:
            self._reject(ValidationError("empty prompt"))
        if int(prompt.min()) < 0 or int(prompt.max()) >= self.cfg.vocab_size:
            self._reject(ValidationError(
                f"prompt token ids must lie in [0, {self.cfg.vocab_size}); "
                f"got range [{int(prompt.min())}, {int(prompt.max())}]"))
        if int(max_new_tokens) <= 0:
            self._reject(ValidationError(
                f"max_new_tokens must be positive, got {max_new_tokens}"))
        if float(temperature) < 0.0:
            self._reject(ValidationError(
                f"temperature must be >= 0, got {temperature}"))
        # keep one chunk of headroom below max_position; NOTE this does
        # not bound chain overshoot (up to max_chain*chunk_size) — the
        # cache-write path's length cap and positions() clamp are the
        # actual out-of-bounds safety mechanism for overshooting
        # stragglers, this limit just keeps USEFUL tokens in range
        limit = self.cfg.max_position - self.chunk_size - 1
        if prompt.size + max_new_tokens > limit:
            clamped = max(0, limit - prompt.size)
            if clamped == 0:
                # a silent zero-token "completion" would mis-diagnose as an
                # engine bug downstream (ADVICE r3) — fail fast instead
                self._reject(ValidationError(
                    f"prompt ({prompt.size}) leaves no room to generate: "
                    f"prompt + generation must stay under max_position - "
                    f"chunk_size ({limit})"))
            import warnings

            warnings.warn(
                f"max_new_tokens clamped {max_new_tokens} -> {clamped}: "
                f"prompt ({prompt.size}) + generation must stay under "
                f"max_position - chunk_size ({limit})", RuntimeWarning,
                stacklevel=2)
            max_new_tokens = clamped
        # fail fast on a request that could NEVER be served — otherwise the
        # scheduler would spin forever waiting for pages that cannot exist
        worst = self._pages_needed(prompt.size + max_new_tokens
                                   + self.chunk_size)
        if worst > min(self.max_pages_per_seq, self.num_pages - 1):
            self._reject(AdmissionRejected(
                f"request needs up to {worst} pages but the pool/table caps "
                f"at {min(self.max_pages_per_seq, self.num_pages - 1)} — "
                "grow num_pages or shrink the request"))
        # bounded wait queue (backpressure): refuse to buffer unboundedly
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self._reject(QueueFull(
                f"wait queue full ({len(self._queue)}/{self.max_queue}); "
                "retry later or raise max_queue"))
        resumed: List[int] = []
        if resume_tokens is not None and len(resume_tokens):
            raw_r = np.asarray(resume_tokens)
            if raw_r.dtype.kind not in "iu":
                self._reject(ValidationError(
                    f"resume_tokens must be integer token ids, got dtype "
                    f"{raw_r.dtype}"))
            resumed = [int(t) for t in raw_r.reshape(-1)]
            if min(resumed) < 0 or max(resumed) >= self.cfg.vocab_size:
                self._reject(ValidationError(
                    f"resume_tokens must lie in [0, {self.cfg.vocab_size})"))
            if len(resumed) >= int(max_new_tokens):
                self._reject(ValidationError(
                    f"resume_tokens ({len(resumed)}) already meet the "
                    f"generation budget ({max_new_tokens}) — the stream "
                    "is complete, nothing to resume"))
            if self.eos_id is not None and self.eos_id in resumed:
                self._reject(ValidationError(
                    "resume_tokens contain eos — the stream already "
                    "terminated on its source replica"))
            if float(temperature) > 0.0 and self._spec is not None:
                self._reject(ValidationError(
                    "sampled resume on a spec-enabled engine: spec "
                    "decode burns keys per verify step, not per token, "
                    "so the migrated key state cannot be reconstructed "
                    "from the emitted-token count — resume on a "
                    "spec=off replica (greedy resumes are exact either "
                    "way)"))
            if float(temperature) > 0.0 and seed is None:
                self._reject(ValidationError(
                    "sampled resume needs an explicit seed: the source "
                    "replica's implicit per-rid seed does not transfer "
                    "across engines"))
        req = Request(self._next_rid, prompt, max_new_tokens, on_token,
                      temperature=float(temperature), seed=seed,
                      tenant=str(tenant) if tenant else "default")
        if resumed:
            # pre-populate emitted history: _prefix() re-prefills
            # prompt‖emitted exactly like a preemption re-admission, and
            # _harvest appends (and delivers) only FRESH tokens
            req.tokens = resumed
            if float(temperature) > 0.0:
                seed_v = int(seed if seed is not None else req.rid)
                key0 = np.array(
                    [(seed_v >> 32) & 0xFFFFFFFF, seed_v & 0xFFFFFFFF],
                    np.uint32)
                req._key = np.asarray(jax.device_get(_advance_sample_key(
                    jnp.asarray(key0), jnp.int32(len(resumed)))),
                    np.uint32)
        req._t_arrival = time.perf_counter()
        if _TRACER.enabled:
            # ISSUE 18: carry the upstream span context (wire string)
            # so engine spans/instants land in the caller's trace, and
            # the upstream submit time so the TTFT decomposition's
            # placement component spans submit -> engine arrival
            req.trace = trace if isinstance(trace, str) and trace else None
            if t_submit is not None:
                req._t_submit = float(t_submit)
            _TRACER.instant("engine.enqueue", "engine",
                            parent=req.trace, rid=req.rid,
                            prompt_len=int(prompt.size),
                            queue_depth=len(self._queue))
        ttl = deadline_s if deadline_s is not None else self.deadline_s
        if ttl is not None:
            req.deadline = req._t_arrival + float(ttl)
            self._has_deadlines = True
        self._next_rid += 1
        self._queue.append(req)
        if self._cache.tier is not None:
            # promote PREFETCH (ISSUE 15): peek the hash chain now so a
            # demoted prefix starts its host->device copy while the
            # request waits in the queue — by admission the promoted
            # pages splice like ordinary cached ones. Pure peek: no LRU
            # re-stamp, no hit/miss accounting (the splice-time lookup
            # owns those), and a promote that hasn't landed by then
            # simply degrades this admission to a partial-prefill miss.
            _, _, demoted = self._pcache.lookup(self._prefix(req),
                                                touch=False, tiers=True)
            if demoted:
                self._cache.tier.request_promote(demoted)
        if self._m is not None:
            self._m.requests.inc()
        return req

    def cancel(self, rid: int) -> bool:
        """Host-side cancellation: fail the request (terminal FAILED,
        reason ``cancelled``) wherever it lives — queued or mid-decode —
        recycling its slot and pages immediately. Returns False when the
        id is unknown or the request already reached a terminal state."""
        for req in list(self._active.values()) + list(self._queue):
            if req.rid == rid and not req.done:
                self._fail_request(req, CancelledError(
                    f"request {rid} cancelled by caller", rid=rid))
                return True
        return False

    def _fail_request(self, req: Request, exc: BaseException):
        """Move ONE request to terminal FAILED: record the taxonomy
        reason, recycle its slot/pages, drop it from the queue — and
        leave every other request untouched. The single choke point all
        per-request failure paths funnel through."""
        if req.done:
            return
        req.failure = exc
        req.failure_reason = failure_reason(exc)
        req.done = True
        if req.slot is not None:
            self._active.pop(req.slot, None)
            self._free_slot(req.slot)
            req.slot = None
        if req in self._queue:
            self._queue.remove(req)
        if self._spec is not None:
            self._spec.controller.forget(req)
        if self._m is not None:
            self._m.failures.labels(
                reason=req.failure_reason,
                tenant=self._m._tenant_label(req.tenant)).inc()

    def _expire_deadlines(self):
        """Fail every queued/active request whose deadline/TTL elapsed
        (reason ``deadline``). Runs at the top of each scheduling step —
        a deadline is enforced at step granularity, the engine's only
        host-visible clock edge."""
        now = time.perf_counter()
        for req in list(self._active.values()) + list(self._queue):
            if req.deadline is not None and now > req.deadline \
                    and not req.done:
                self._fail_request(req, DeadlineExceeded(
                    f"request {req.rid} exceeded its deadline "
                    f"({now - req._t_arrival:.3f}s since arrival)",
                    rid=req.rid))

    def _note_stall(self):
        """Queued requests, nothing active, no admission possible. The
        pre-ISSUE-6 behavior was a hard RuntimeError; now the engine
        tolerates a couple of steps (deadline expiry or recovery may
        free pages), then sheds the queue head with ``PoolExhausted`` —
        forward progress without crashing the batch that isn't there."""
        self._stall_steps += 1
        if self._stall_steps >= 3 and self._queue:
            self._stall_steps = 0
            head = self._queue[0]
            self._fail_request(head, PoolExhausted(
                f"scheduler stalled: page pool too fragmented/small to "
                f"admit request {head.rid}", rid=head.rid))

    @staticmethod
    def _wrap_step_fault(exc: BaseException, req: Request) -> StepFault:
        err = StepFault(f"{type(exc).__name__}: {exc}", rid=req.rid)
        err.__cause__ = exc
        return err

    # ------------------------------------------------------------ allocator
    def _pages_needed(self, length):
        return (int(length) + self.page_size - 1) // self.page_size

    def _alloc_page(self) -> Optional[int]:
        """Claim one physical page — see CacheCoordinator.alloc_page
        (free list first, then LRU eviction of an idle cached page)."""
        return self._cache.alloc_page()

    def _release_page(self, page):
        """Drop one page reference — see CacheCoordinator.release_page
        (the single release choke point; shared pages never double-free)."""
        self._cache.release_page(page)

    def _available_pages(self) -> int:
        """Pages an allocation burst could claim (free + idle cached)."""
        return self._cache.available_pages()

    def _ensure_pages(self, slot, new_len):
        need = self._pages_needed(new_len)
        # count actual allocations (chain headroom can exceed
        # pages_needed(length); recomputing from length would overwrite —
        # and leak — last round's headroom pages)
        have = int(np.count_nonzero(self.tables[slot]))
        if need > self.max_pages_per_seq:
            # taxonomy, not RuntimeError: callers fail the REQUEST
            # (add_request's up-front check makes this unreachable for
            # well-formed traffic, so hitting it is an engine bug — but
            # an engine bug one request wide, not batch wide)
            raise PoolExhausted(
                f"sequence needs {need} pages but the per-sequence table "
                f"caps at {self.max_pages_per_seq}")
        if need > have and self._fi is not None \
                and self._fi.fire("pool-exhaustion"):
            # injected exhaustion only when a real allocation would
            # happen — a no-op ensure succeeds even over an empty pool
            return False
        taken = []
        for i in range(have, need):
            page = self._alloc_page()
            if page is None:
                # roll back the partial allocation — a False return must
                # leave the allocator unchanged or the pages leak
                for j in range(have, have + len(taken)):
                    self.tables[slot, j] = 0
                for pg in reversed(taken):
                    self._release_page(pg)
                return False
            taken.append(page)
            self.tables[slot, i] = page
        return True

    def _trim_pages(self, slot, keep_len):
        """Release a slot's headroom pages beyond ``keep_len`` (headroom
        pages are empty by construction — data only exists up to
        ``lengths[slot]``). Refcount-aware: a spliced shared page merely
        loses this slot's reference (callers only ever trim back to at
        least the prefilled prefix, so shared pages stay in range — the
        release path is the safety net, not the common case)."""
        need = self._pages_needed(keep_len)
        have = int(np.count_nonzero(self.tables[slot]))
        for i in range(have - 1, need - 1, -1):
            self._release_page(int(self.tables[slot, i]))
            self.tables[slot, i] = 0

    # --------------------------------------------------- prefix cache (ISSUE 8)
    def _splice_prefix(self, row, prefix) -> int:
        """Prefix-cache admission: splice the cached block-aligned prefix
        of ``prefix`` into the (fresh, all-zero) table ``row`` — refcount++
        per shared page — and return the token count the prefill may skip.

        Copy-on-write at divergence: a FULL-prefix match still needs the
        last prompt token recomputed (its logits produce the first
        generated token), and that token's KV write lands inside the final
        matched page — which is shared. The page is copied to a fresh one
        (device copies batch per wave in ``_prefill_wave``) and the splice
        reports ``prefix.size - 1`` cached tokens, so the write — and
        every decode append after it — only ever touches pages this slot
        owns. Partial matches divide at a page boundary by construction
        (only full blocks are cached), so their suffix writes open fresh
        pages and need no copy.

        The ``prefix-cache-corruption`` fault point fires here: a doubted
        page gets its device bytes flipped (when idle — an in-use page is
        never corrupted by the harness), the cache invalidates it and
        every descendant block, and THIS admission recomputes from scratch
        — corruption costs a miss, never a wrong token."""
        self._last_promote_wait_s = 0.0
        if self._pcache is None:
            return 0
        if self._cache.tier is not None:
            # tiered splice (ISSUE 15): peek the chain, start promotions
            # for any demoted continuation (usually already in flight —
            # add_request prefetched them while the request queued), and
            # give in-flight ones a BOUNDED drain-wait far below the
            # recompute they would otherwise cost. Whatever landed
            # splices below like ordinary cached pages; whatever is
            # still in flight rides partial prefill — a slow promote
            # degrades to a miss, never a stall or a wrong token.
            tier = self._cache.tier
            _, _, demoted = self._pcache.lookup(prefix, touch=False,
                                                tiers=True)
            if demoted:
                tier.request_promote(demoted)
                t0 = time.perf_counter()
                tier.await_promotions(demoted)
                # attributed to the admitting request's promote_wait
                # TTFT component by _admit_dispatch/_bind_chunked
                self._last_promote_wait_s = time.perf_counter() - t0
                if _TRACER.enabled:
                    _TRACER.instant(
                        "kvtier.promote_wait", "cache",
                        waited_s=self._last_promote_wait_s,
                        pages=len(demoted))
            pages, matched, _ = self._pcache.lookup(prefix, tiers=True)
        else:
            pages, matched = self._pcache.lookup(prefix)
        if matched and self._fi is not None \
                and self._fi.fire("prefix-cache-corruption"):
            doubted = pages[-1]
            if int(self._page_ref[doubted]) == 0:
                self._corrupt_page(doubted)
            for p in self._pcache.invalidate_page(doubted):
                if int(self._page_ref[p]) == 0:
                    self._free_pages.append(p)
            pages, matched = [], 0  # invalidate-on-doubt: recompute all
            # the lookup scored a hit before doubt struck; the admission
            # is in fact a miss — keep the cache's own tallies consistent
            # with the prometheus counters below
            self._pcache.hits -= 1
            self._pcache.misses += 1
        if matched and self._fi is not None \
                and self._fi.fire("bit-flip-kv"):
            # SILENT corruption (ISSUE 14): flip a matched idle page's
            # device bytes with NO doubt signal — unlike the
            # prefix-cache-corruption point above, nothing invalidates,
            # so only the checksum probe below stands between this flip
            # and a wrong token
            doomed = pages[-1]
            if int(self._page_ref[doomed]) == 0:
                self._corrupt_page(doomed)
        if matched and self._integrity is not None:
            # close the PR 8 trust window: the token re-verify in
            # PrefixCache.lookup proves the ENTRY matches the prompt,
            # but said nothing about the page BYTES between
            # registration and this splice — the checksum probe does
            bad = self._integrity.verify_pages(pages)
            if bad:
                self._contain_kv_corruption(bad)
                pages, matched = [], 0
                self._pcache.hits -= 1
                self._pcache.misses += 1
        if self._m is not None:
            (self._m.pc_hits if matched else self._m.pc_misses).inc()
        if _TRACER.enabled:
            _TRACER.instant("cache.prefix_lookup", "cache",
                            matched=int(matched),
                            prefix_len=int(prefix.size))
        if not matched:
            return 0
        cow = None
        if matched == int(prefix.size):
            cow = self._alloc_page()
            if cow is None:
                # no page for the copy under extreme pressure: fall back
                # to recomputing the whole last block instead
                pages = pages[:-1]
                matched -= self.page_size
                if not matched:
                    return 0
        for i, p in enumerate(pages if cow is None else pages[:-1]):
            row[i] = p
            self._page_ref[p] += 1
        if cow is not None:
            self._cow_pending.append((int(pages[-1]), int(cow)))
            row[len(pages) - 1] = cow
            matched -= 1  # the recomputed final token
        if self._m is not None:
            self._m.pc_cached_tokens.inc(matched)
        return matched

    def _corrupt_page(self, page):
        """The ``prefix-cache-corruption`` fault point's actual damage:
        garbage layer-0 K rows for one cached page. Safe to leave behind
        because a page is only ever read below ``lengths`` — rows the
        next owner rewrites during its own prefill/decode before they
        become visible — so with the invalidate-on-doubt path routing
        lookups around it, the flip can cost a miss but never a token."""
        self._cache.corrupt_page(page)

    def _register_prefix(self, prefix, row):
        """Publish the freshly prefilled FULL pages of ``prefix`` into the
        cache (content-addressed by block-chain hash). Pages stay owned by
        the slot/row; once released they stay resident at refcount 0 until
        LRU eviction reclaims them. Blocks already cached keep their
        original page (the COW copy, in particular, stays private — its
        final row diverges the moment decode appends into it).

        With the integrity sentinel armed (ISSUE 14) every page now
        backing these blocks gets a checksum: fresh pages record their
        baseline, and an already-cached block's page — possibly parked
        at refcount 0 since its first registration — is RE-verified, so
        corruption of an idle page is caught at the earliest touch."""
        if self._pcache is None:
            return
        full = int(prefix.size) // self.page_size
        if full:
            blocks = prefix[:full * self.page_size]
            self._pcache.register(
                blocks, [int(row[i]) for i in range(full)])
            if self._integrity is not None:
                # the canonical backing pages (dedup may differ from
                # this row's private pages): peek, never re-stamp
                pages, _ = self._pcache.lookup(blocks, touch=False)
                bad = self._integrity.note_registered(pages)
                if bad:
                    self._contain_kv_corruption(bad)

    def adopt_kv_pages(self, payload) -> int:
        """Decode-side adoption of a cross-replica KV handoff payload
        (ISSUE 20): digest-verify the shipped page rows, restore them
        into freshly allocated pool pages, and publish them in the
        prefix cache so the next admission of the same prompt splices
        instead of recomputing. Engine thread (the cluster reaches it
        through ``ServingFrontend.call``). Returns the number of pages
        adopted; 0 on any mismatch/pressure — the caller's fallback is
        plain resume-from-emitted recompute, so a bad payload costs a
        cache miss, never a stall or a wrong token.

        Verification truncates at the FIRST digest mismatch: chain keys
        commit to the whole prefix, so a clean prefix of the shipment
        is still independently trustworthy. Blocks the local cache
        already holds HBM-resident are skipped (first-writer-wins, same
        as ``PrefixCache.register``); a shipped block whose entry is
        host-tier re-binds to the restored page (recompute-as-promote,
        minus the recompute)."""
        if self._pcache is None or not payload:
            return 0
        pc = self._pcache
        if int(payload.get("page_size", -1)) != self.page_size:
            return 0
        tokens = np.asarray(payload.get("tokens", ()), np.int32)
        rows_per_page = payload.get("pages") or []
        digests = payload.get("digests") or []
        dev_sums = payload.get("dev_sums") or [None] * len(rows_per_page)
        n_blocks = min(tokens.size // self.page_size,
                       len(rows_per_page), len(digests))
        good = 0
        for j in range(n_blocks):
            d = hashlib.blake2b(digest_size=16)
            for a in rows_per_page[j]:
                d.update(np.ascontiguousarray(a).tobytes())
            if d.hexdigest() != digests[j]:
                break  # later blocks chain through this one: truncate
            good += 1
        from .integrity import count_integrity_check

        count_integrity_check("kv_handoff", good == n_blocks)
        if not good:
            return 0
        # skip what is already resident (peek, no stamp/accounting) —
        # re-restoring an identical block would only burn a page
        _, matched = pc.lookup(tokens[:good * self.page_size],
                               touch=False)
        start = matched // self.page_size
        fresh = []  # (block_index, page)
        for j in range(start, good):
            page = self._cache.alloc_page()
            if page is None:
                break  # pool pressure: adopt the prefix that fits
            fresh.append((j, int(page)))
        if not fresh:
            return 0
        import jax.numpy as jnp

        w = 32  # fixed-width restore waves (HostTier.COPY_WIDTH idiom)
        for off in range(0, len(fresh), w):
            chunk = fresh[off:off + w]
            m = len(chunk)
            idx = np.zeros((w,), np.int32)
            idx[:m] = [p for _, p in chunk]
            stacked = [
                np.stack([np.asarray(rows_per_page[j][i])
                          for j, _ in chunk]
                         + [np.zeros_like(
                             np.asarray(rows_per_page[chunk[0][0]][i]))]
                         * (w - m))
                for i in range(len(rows_per_page[chunk[0][0]]))
            ]
            self._cache.set_pages(self.runner.restore_pages(
                self._cache.pages_flat(), jnp.asarray(idx), stacked))
        end = fresh[-1][0] + 1
        blocks = tokens[:end * self.page_size]
        row = [0] * end
        for j, p in fresh:
            row[j] = p
        pc.register(blocks, row)
        adopted = 0
        for j, p in fresh:
            # uniform release: ref 1 -> 0; a page the register adopted
            # stays resident (cache-owned, LRU-evictable), a page an
            # existing entry beat stays off the index and returns to the
            # free list — leak-free either way
            registered = pc.contains_page(p)
            self._cache.release_page(p)
            if not registered:
                continue
            adopted += 1
            if self._integrity is not None and dev_sums[j] is not None:
                # the shipped bytes hash-matched their capture digest,
                # so the source replica's device-side sum describes the
                # restored page too (same contract as tier promotion)
                self._integrity.adopt_page_sum(p, float(dev_sums[j]))
        if _TRACER.enabled:
            _TRACER.instant("cluster.kv_adopt", "cache",
                            adopted=int(adopted),
                            shipped=int(n_blocks), verified=int(good))
        return adopted

    def _contain_kv_corruption(self, bad_pages):
        """Containment ladder, KV arm (ISSUE 14): a checksum-failed page
        invalidates out of the cache with every descendant block (the
        invalidate-on-doubt path — future lookups miss and recompute),
        and any ACTIVE slot whose table references a bad page is
        preempted: its KV may already be poisoned, and the recompute
        requeue re-prefills prompt+generated exactly (the same
        machinery replica migration rides), so the stream's delivered
        tokens stay bit-identical. Corruption costs a miss or a
        re-prefill — never a wrong token."""
        dead = set()
        for pg in bad_pages:
            for p in self._pcache.invalidate_page(int(pg)):
                dead.add(int(p))
                if self._integrity is not None:
                    self._integrity.forget_page(p)
                if int(self._page_ref[p]) == 0:
                    self._free_pages.append(p)
        dead.update(int(p) for p in bad_pages)
        for slot in list(self._active):
            if any(int(p) in dead for p in self.tables[slot] if p):
                self._preempt(slot)

    def _drop_cow_for(self, row):
        """Cancel pending COW copies whose destination lives in ``row`` —
        called when an admission aborts between splice and dispatch (the
        row's pages are being released, so the copy must not run)."""
        if self._cow_pending:
            dead = {int(p) for p in row if p}
            self._cow_pending = [sd for sd in self._cow_pending
                                 if sd[1] not in dead]

    def _preempt(self, slot):
        """Evict a running request under pool pressure: recycle its pages
        and requeue it — re-admission prefills prompt+generated prefix, and
        the live PRNG key travels with the request, so generation resumes
        exactly where it stopped for greedy AND sampled decode. The vLLM
        recompute-preemption policy."""
        req = self._active.pop(slot)
        req._key = self._keys[slot].copy()
        if self._m is not None:
            self._m.preemptions.inc()
            self._m.page_evictions.inc(
                int(np.count_nonzero(self.tables[slot])))
        self._free_slot(slot)
        req.slot = None
        self._requeue(req)

    def _requeue(self, req):
        """Recompute-policy re-queue with a hard retry bound: a request
        that keeps getting evicted (allocator livelock, repeated step
        faults) fails attributably (``retries_exhausted``) instead of
        spinning forever. Front insertion doubles as priority aging — a
        retried request outranks fresh arrivals at the next admission,
        so retries can't starve it either."""
        req.retries += 1
        if self._m is not None:
            self._m.retries.inc()
        if req.retries > self.max_retries:
            self._fail_request(req, RetriesExhausted(
                f"request {req.rid} re-queued more than max_retries="
                f"{self.max_retries} times", rid=req.rid))
            return
        self._queue.insert(0, req)

    def _free_slot(self, slot):
        if slot in self._free_slots:
            # idempotent release (ISSUE 6 satellite): a double free would
            # hand the same slot to two requests and recycle its pages
            # twice — the second call must be a no-op
            return
        # release every allocated table entry — chain headroom means the
        # slot can hold pages beyond pages_needed(length) (0 is the trash
        # page, never allocated). A slot release DECREMENTS: spliced
        # shared pages survive for their other referents, and pages the
        # prefix cache indexes stay resident at refcount 0
        for p in self.tables[slot]:
            if p:
                self._release_page(int(p))
        self.tables[slot, :] = 0
        self.lengths[slot] = 0
        self._chunk_left.pop(slot, None)  # mid-prefill state dies with the slot
        self._free_slots.append(slot)
        if self._spec is not None:
            # a draft-model drafter mirrors engine slots in its own page
            # pool; recycle its side too (no-op for the ngram drafter)
            self._spec.drafter.release(slot)

    def _reset_pool(self):
        """(Re)create the device page buffers and allocator free lists —
        delegated to the cache-coordinator, which rebuilds a sharded
        pool PER-SHARD (donated-dead buffers after a failed dispatch
        must come back with the same mesh placement, ISSUE 11
        satellite). Content is entirely recomputable: every requeued
        request re-prefills its prompt+generated prefix on re-admission,
        so a fresh zeroed pool loses nothing."""
        self._cache.reset()
        # mid-prefill progress refers to pages that just died; requeued
        # requests re-chunk from scratch (recompute policy)
        if getattr(self, "_chunk_left", None):
            self._chunk_left.clear()
        if getattr(self, "_spec", None) is not None:
            self._spec.drafter.reset()

    def _reserve_step_pages(self, k, target_len):
        """Allocate this step's pages for every active slot — shrinking
        the chain depth, then preempting (retry-bounded), then failing
        the lone unservable request — NEVER raising. ``target_len(slot,
        req, k)`` gives the desired cache length per slot at depth ``k``.
        Returns the depth actually reserved, or 0 once nothing is active
        (every caller re-checks ``self._active``)."""
        while self._active:
            short = failed = False
            for slot in sorted(self._active,
                               key=lambda s: -int(self.lengths[s])):
                req = self._active[slot]
                try:
                    if not self._ensure_pages(slot, target_len(slot, req, k)):
                        short = True
                        break
                except RequestError as e:
                    # per-sequence table overflow and kin: one request's
                    # fault, one request's failure
                    self._fail_request(req, e)
                    failed = True
                    break
            if not short and not failed:
                return k
            # roll back EVERY slot's chain headroom before retrying:
            # pages an earlier (longer) slot grabbed for the failed
            # attempt would otherwise starve the retry and force a
            # preemption that a smaller uniform depth avoids
            for slot in self._active:
                self._trim_pages(slot, int(self.lengths[slot]))
            if failed:
                continue  # the failed request's pages just freed
            if k > 1:
                k = max(1, k // 2)
                continue
            # k == 1 and still short: preempt under the recompute policy.
            # Victim = longest sequence (most pages back), ties broken
            # toward the FEWEST retries so a much-retried request isn't
            # repeatedly chosen (anti-livelock, with max_retries as the
            # hard bound behind it).
            victims = sorted(self._active,
                             key=lambda s: (-int(self.lengths[s]),
                                            self._active[s].retries))
            if len(victims) <= 1:
                # alone and still unservable: pool genuinely cannot hold
                # it (or injection says so) — fail the request, never the
                # engine (pre-ISSUE-6 this was a RuntimeError)
                self._fail_request(self._active[victims[0]], PoolExhausted(
                    "KV page pool exhausted with nothing left to preempt",
                    rid=self._active[victims[0]].rid))
                continue
            self._preempt(victims[0])
        return 0

    # ----------------------------------------------------------- jit bodies
    # Pages travel as a flat list so jit sees ordinary pytrees and donation
    # reuses the (large) page buffers in place. These helpers are PURE with
    # respect to the engine (never mutate self inside a trace).
    def _states_from(self, pages_flat, tables, lengths, prefill_valid=None,
                     verify=False):
        L = self.cfg.num_layers
        kp, vp = pages_flat[:L], pages_flat[L:2 * L]
        sc = pages_flat[2 * L:3 * L] if self.quantized else [None] * L
        return [
            PagedCacheState(kp[i], vp[i], sc[i], tables, lengths,
                            self.page_size, prefill_valid=prefill_valid,
                            verify=verify)
            for i in range(L)
        ]

    @staticmethod
    def _pages_of(states):
        out = [st.k_pages for st in states] + [st.v_pages for st in states]
        if states[0].quantized:
            out += [st.scale_pages for st in states]
        return out

    def _set_pages(self, pages_flat):
        """Host-side writeback after a jitted call returns."""
        self._cache.set_pages(pages_flat)

    def _pages_flat(self):
        return self._cache.pages_flat()

    def _select_token(self, logits, greedy_tok, temps, keys):
        """Shared prefill/decode token selection: argmax where temp == 0,
        top-k temperature sampling otherwise. ``logits`` [B, V] f32,
        ``keys`` [B, 2] uint32. Returns (tok [B] i32, new_keys)."""
        if self.top_k is not None:
            kth = jax.lax.top_k(logits, self.top_k)[0][:, -1]
            logits = jnp.where(logits >= kth[:, None], logits, -jnp.inf)
        splits = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
        new_keys, step_keys = splits[:, 0], splits[:, 1]
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.vmap(jax.random.categorical)(step_keys, scaled)
        tok = jnp.where(temps > 0.0, sampled.astype(jnp.int32),
                        greedy_tok).astype(jnp.int32)
        # only burn key state for slots that actually sample, so greedy
        # requests stay key-independent and mixed batches stay deterministic
        new_keys = jnp.where((temps > 0.0)[:, None], new_keys, keys)
        return tok, new_keys

    def _make_prefill_raw(self, sampling, suffix=False):
        """Raw (unjitted) bucketed-prefill program — one per (sampling?,
        suffix?); the model-runner wraps it (jit, plus shard_map at
        tp>1) and caches per pow2 bucket.

        ``suffix=True`` is the prefix-cache partial-prefill program
        (ISSUE 8): ``lengths_rows`` carries each row's cached token count
        and ``verify=True`` routes attention through the multi-query
        cache-aware path (``paged_state_verify`` honoring per-row
        ``prefill_valid`` widths), so hit rows compute only their uncached
        suffix while miss rows (base 0) reduce to a from-scratch prefill.
        All-miss waves keep this ``suffix=False`` program — bitwise the
        cache-off path, so zero-overlap traffic never pays for the
        cache."""
        model, engine = self.model, self
        moe_n = self._moe_stats_n

        def prefill(params, pages_flat, ids, valid, tables_rows,
                    lengths_rows, temps, keys):
            from ..jit import swapped_tensors

            with swapped_tensors(engine._swap, params), pause_tape():
                states = engine._states_from(pages_flat, tables_rows,
                                             lengths_rows,
                                             prefill_valid=valid,
                                             verify=suffix)
                with _moe_tap(moe_n) as tap:
                    logits, new_states = model.forward(Tensor._wrap(ids),
                                                       caches=states)
                lg = logits._data if isinstance(logits, Tensor) else logits
                last = jnp.take_along_axis(
                    lg, (valid - 1)[:, None, None], axis=1)[:, 0]
                last = last.astype(jnp.float32)
                # NaN/inf logit guard (ISSUE 6): a non-finite row means
                # argmax/sampling is garbage — flag it so the host fails
                # THAT request instead of streaming junk
                bad = ~jnp.all(jnp.isfinite(last), axis=-1)
                greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
                if sampling:
                    tok, new_keys = engine._select_token(last, greedy,
                                                         temps, keys)
                else:
                    tok, new_keys = greedy, keys
                out = tok, new_keys, bad, engine._pages_of(new_states)
                if moe_n:
                    out += (jnp.sum(jnp.stack(tap), axis=0),)
                return out

        return prefill

    def _get_prefill(self, bucket, sampling, suffix=False):
        """One compiled prefill per (pow2 row count, pow2 prompt bucket,
        sampling?, suffix?): a whole admission wave in one dispatch.
        Greedy-only waves compile without the sampling machinery."""
        return self.runner.get_prefill(bucket, sampling, suffix)

    def _make_decode_raw(self, k, sampling):
        """Raw (unjitted) chained-decode program: a single ``lax.scan``
        of ``k * chunk_size`` steps — the model-runner wraps it (jit +
        shard_map at tp>1; the scan carries the page shards LOCALLY, so
        no reshard crosses a step boundary — the tpushard TPC502
        property the sharded chain is gated on)."""
        model, engine = self.model, self
        steps = k * self.chunk_size
        moe_n = self._moe_stats_n

        def decode_chain(params, pages_flat, tables, lengths, last_tok,
                         temps, keys):
            from ..jit import swapped_tensors

            with swapped_tensors(engine._swap, params), pause_tape():
                def body(carry, _):
                    pages_flat, lengths, last, keys, bad, mstat = carry
                    states = engine._states_from(pages_flat, tables, lengths)
                    # the tap must arm INSIDE the scan body — its traced
                    # stats belong to this iteration; they fold into the
                    # carry accumulator, never escape the body
                    with _moe_tap(moe_n) as tap:
                        logits, new_states = model.forward(
                            Tensor._wrap(last[:, None]), caches=states)
                    if moe_n:
                        mstat = mstat + jnp.sum(jnp.stack(tap), axis=0)
                    lg = (logits._data if isinstance(logits, Tensor)
                          else logits)
                    lg = lg[:, -1].astype(jnp.float32)
                    # NaN/inf logit guard (ISSUE 6): OR-accumulated per
                    # row across the chain; the host fails flagged rows
                    bad = bad | ~jnp.all(jnp.isfinite(lg), axis=-1)
                    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    if sampling:
                        nxt, keys = engine._select_token(lg, greedy, temps,
                                                         keys)
                    else:
                        nxt = greedy
                    # idle slots keep emitting garbage; host discards
                    return ((engine._pages_of(new_states),
                             new_states[0].lengths, nxt, keys, bad,
                             mstat), nxt)

                (pages_flat, lengths, _, keys, bad, mstat), toks = \
                    jax.lax.scan(
                        body, (pages_flat, lengths, last_tok, keys,
                               jnp.zeros(last_tok.shape, bool),
                               jnp.zeros((moe_n,), jnp.float32)), None,
                        length=steps)
            out = (jnp.swapaxes(toks, 0, 1), pages_flat, lengths, keys,
                   bad)
            if moe_n:
                out += (mstat,)
            return out

        return decode_chain

    def _get_decode(self, nb, k, sampling):
        """One compiled decode program per (pow2 active-slot bucket ``nb``,
        pow2 chain depth ``k``, sampling?): a whole chain costs ONE
        dispatch + ONE fetch (chaining k separate chunk dispatches
        would pay the boundary k times). Greedy-only batches compile
        without the per-step vocab-wide sampling draw."""
        return self.runner.get_decode(nb, k, sampling)

    def _get_mixed(self, nb, sampling):
        """ONE compiled mixed chunk+decode step per sampling flag
        (ISSUE 9): rows pad to the fixed max_slots bucket and the token
        axis is the static ``prefill_chunk``, so chunked serving's whole
        compile surface is this program plus the decode chains — no
        prompt-length prefill buckets, which is what lets a cold server's
        first wave approach steady-state throughput."""
        return self.runner.get_mixed(nb, sampling)

    # ------------------------------------------------------------ scheduling
    @staticmethod
    def _prefix(req):
        """Tokens that must be in the cache before decode continues: the
        prompt plus anything already generated (non-empty after a
        preemption — re-prefilling the full prefix resumes generation)."""
        if req.tokens:
            return np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
        return req.prompt

    @_phase("engine.admit")
    def _admit_dispatch(self):
        """Dispatch one bucketed prefill for ALL admissible queued
        requests WITHOUT blocking (rows pad to pow2, prompts to a shared
        pow2 bucket). Returns ``(admits, tok_dev, keys_dev)`` — device
        handles the caller threads into the same step's decode chain and
        harvests with the chain's fetch, so admission costs no host sync
        of its own (VERDICT r4 #2)."""
        # land any finished spill/promote completions first (ISSUE 15):
        # a promotion that arrived since the last step makes THIS wave's
        # lookups splice instead of recompute
        self._cache.drain_tier()
        admits = []  # (req, slot, prefix, base)
        while (self._queue and self._free_slots
               and len(self._active) + len(admits) < self._slot_cap):
            # _slot_cap == max_slots when healthy; the watchdog halves it
            # in SMALL_BATCH degraded mode (less page pressure, smaller
            # blast radius) and restores it on recovery
            req = self._queue[0]
            prefix = self._prefix(req)
            need = self._pages_needed(prefix.size + self.chunk_size)
            if self._pcache is not None:
                # a cached prefix shrinks the allocation this admission
                # actually needs (peek only — no LRU touch, no hit/miss
                # accounting until the splice commits)
                _, peeked = self._pcache.lookup(prefix, touch=False)
                reuse = peeked // self.page_size
                if peeked and peeked == int(prefix.size):
                    reuse -= 1  # the COW copy still needs a fresh page
                need -= reuse
            if need > self._available_pages():
                break  # pool pressure: let running requests drain first
            slot = self._free_slots.pop()
            self._queue.pop(0)
            base = self._splice_prefix(self.tables[slot], prefix)
            # attribute the splice's KV-tier promote wait to THIS
            # request's TTFT decomposition (first admission only —
            # re-admission after preemption is preemption cost, just
            # like queue-wait in _note_admitted)
            if not req._admitted:
                req._t_promote_wait += self._last_promote_wait_s
            try:
                got = self._ensure_pages(slot, prefix.size)
            except RequestError as e:
                self._drop_cow_for(self.tables[slot])
                self._free_slot(slot)
                self._fail_request(req, e)
                continue
            if not got:
                self._drop_cow_for(self.tables[slot])
                self._free_slot(slot)
                self._queue.insert(0, req)
                break
            admits.append((req, slot, prefix, base))
        if not admits:
            return [], None, None, None
        # register the wave for step-fault recovery BEFORE the prefill
        # dispatch: these requests were popped from _queue but are not in
        # _active until the commit below, so a trace/dispatch error here
        # used to lose them from the engine entirely — the request never
        # reached a terminal state and its stream (and any router ticket
        # waiting on it) hung forever instead of failing attributably
        self._pending_inflight = admits
        tok, new_keys, bad = self._prefill_wave(
            [(req, prefix, self.tables[slot], base)
             for req, slot, prefix, base in admits])
        # commit host bookkeeping now; token values arrive at harvest
        for req, slot, prefix, _base in admits:
            self.lengths[slot] = prefix.size
            req.slot = slot
            self._active[slot] = req
            self._temps[slot] = req.temperature
            # commit the PRE-prefill key now (the post-draw key arrives at
            # harvest): if a step fault forces recovery before the
            # harvest, re-prefilling from this key replays the same draw,
            # so even a sampled stream resumes exactly (ISSUE 6)
            if req._key is not None:
                self._keys[slot] = req._key
            self._note_admitted(req)
        self._pending_inflight = []
        return admits, tok, new_keys, bad

    def _note_admitted(self, req):
        """Queue-wait telemetry: first slot admission only (re-admission
        after preemption is preemption cost, already counted there)."""
        if req._admitted:
            return
        req._admitted = True
        req._t_admit = time.perf_counter()
        if self._m is not None:
            self._m.queue_wait_for(req.tenant).observe(
                req._t_admit - req._t_arrival)
        if _TRACER.enabled:
            _TRACER.instant("engine.admit", "engine",
                            parent=req.trace, rid=req.rid,
                            slot=req.slot,
                            promote_wait_s=req._t_promote_wait)

    @_phase("engine.prefill_dispatch")
    def _prefill_wave(self, rows):
        """Dispatch ONE bucketed prefill for ``rows`` of (req, prefix,
        table_row, base) — shared by admission and pre-admission. Returns
        the (tok, keys) device handles; never blocks.

        ``base`` is the row's cached-prefix token count (prefix cache,
        ISSUE 8): any hit in the wave routes the WHOLE wave through the
        suffix program (cache-aware multi-query attention; miss rows with
        base 0 behave exactly like a prefill), the seq bucket shrinks to
        the longest uncached SUFFIX, and pending copy-on-write page
        duplications flush in one device dispatch first. An all-miss wave
        keeps the classic prefill program — bitwise the cache-off path.

        The pow2 seq bucket caps at max_position so prefill position ids
        (arange over the padded width) never index past the embedding
        table (ADVICE r3: don't rely on XLA's OOB-gather clamping). Rows
        pad to the FIXED max_slots bucket, not the wave size: a variable
        row axis multiplies the compiled-program space and lets
        scheduling nondeterminism hit novel shapes long after warmup (a
        39 s Mosaic compile observed mid-serve); padding rows write to
        the trash page, costing ~one chunk of compute at these slot
        counts. Deployments with very large max_slots would revisit."""
        if self._m is not None:
            self._m.prefill_batch.observe(len(rows))
        if _TRACER.enabled:
            _TRACER.instant(
                "engine.prefill_wave", "engine", wave=len(rows),
                rids=[req.rid for req, *_ in rows])
        self._flush_cow()
        suffix_mode = any(base for *_, base in rows)
        if suffix_mode and self._m is not None:
            # the suffix program rides the fused verify/suffix slab
            # attention path (ISSUE 9) — count the dispatch
            self._m.slab_dispatch.labels(path="suffix_prefill").inc()
        seq_bucket = min(_pow2ceil(max(p.size - b for _, p, _, b in rows)),
                         self.cfg.max_position)
        nb = _pow2ceil(self.max_slots)
        ids = np.zeros((nb, seq_bucket), np.int32)
        valid = np.ones((nb,), np.int32)  # pad rows: 1 token → trash page
        bases = np.zeros((nb,), np.int32)
        tables = np.zeros((nb, self.max_pages_per_seq), np.int32)
        temps = np.zeros((nb,), np.float32)
        keys = np.zeros((nb, 2), np.uint32)
        for i, (req, prefix, table_row, base) in enumerate(rows):
            suf = prefix[base:]
            ids[i, :suf.size] = suf
            valid[i] = suf.size
            bases[i] = base
            tables[i] = table_row
            temps[i] = req.temperature
            if self._m is not None:
                self._m.pc_computed_tokens.inc(int(suf.size))
            if req._key is None:
                seed = int(req.seed if req.seed is not None else req.rid)
                # threefry2x32 key layout, built host-side — going through
                # jax.random.PRNGKey here costs a device round trip PER
                # ADMISSION
                req._key = np.array(
                    [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)
            keys[i] = req._key
        prefill = self._get_prefill((nb, seq_bucket),
                                    bool(np.any(temps > 0.0)), suffix_mode)
        tok, new_keys, bad, pages_flat, *ex = prefill(
            self._params, self._pages_flat(), jnp.asarray(ids),
            jnp.asarray(valid), jnp.asarray(tables),
            jnp.asarray(bases), jnp.asarray(temps),
            jnp.asarray(keys))
        self._set_pages(pages_flat)
        self._note_moe_stats(ex)
        return tok, new_keys, bad

    # ------------------------------------------ MoE router stats (ISSUE 17)
    def _note_moe_stats(self, ex):
        """Stash the trailing router-stats device handle an MoE
        program's dispatch returned (``ex`` is the splat-captured tail —
        empty on dense engines). Non-blocking; drained at the step
        boundary / :meth:`moe_stats`. The soft cap bounds growth when a
        caller dispatches outside ``step()`` (e.g. blocking admission
        in a tight loop) — by then the producing program's sibling
        outputs were fetched, so the drain's ``device_get`` is cheap."""
        if ex:
            self._moe_pending.append(ex[0])
            if len(self._moe_pending) > 64:
                self._drain_moe_stats()

    def _drain_moe_stats(self):
        """Fold pending router-stats vectors into the host aggregate and
        record the MoE metrics (HOST code between dispatches — TPL601)."""
        if not self._moe_pending:
            return
        pend, self._moe_pending = self._moe_pending, []
        try:
            vals = jax.device_get(tuple(pend))
        except Exception:  # tpulint: disable=TPL701 -- observability drain: the producing step's OWN harvest already routed this failure through _recover_step_fault; the stats sibling dying with it is the recovery contract, and a metrics drain must never take down the scheduler
            return
        agg = np.zeros_like(self._moe_tot)
        for v in vals:
            agg += np.asarray(v, np.float64)
        self._moe_tot += agg
        if _TRACER.enabled:
            e = self._moe_stats_n - 3
            _TRACER.instant("engine.moe_dispatch", "moe",
                            dispatches=len(pend),
                            kept=float(np.sum(agg[:e])),
                            dropped=float(agg[e]))
        if self._m is not None:
            e = self._moe_stats_n - 3
            if agg[e]:
                self._m.moe_dropped.inc(float(agg[e]))
            for i in range(e):
                if agg[i]:
                    self._m.moe_expert_at(i).inc(float(agg[i]))
            routed = float(agg[e + 2])
            if routed > 0:
                self._m.moe_router_entropy.set(float(agg[e + 1]) / routed)

    def moe_stats(self) -> Dict[str, object]:
        """Cumulative MoE routing stats since engine construction
        (serve_llama_paged's stats line reads this). ``{}`` on dense
        engines. ``drop_frac`` is dropped pairs /
        total routed pairs (kept + dropped); ``load_imbalance`` is
        max/mean over the per-expert kept counts (1.0 = perfectly
        balanced); ``router_entropy`` is the per-token mean in nats."""
        if not self._moe_stats_n:
            return {}
        self._drain_moe_stats()
        e = self._moe_stats_n - 3
        t = self._moe_tot
        load = t[:e]
        kept = float(load.sum())
        dropped = float(t[e])
        pairs = kept + dropped
        routed = float(t[e + 2])
        mean = kept / e if e else 0.0
        return {
            "tokens_routed": routed,
            "pairs_kept": kept,
            "pairs_dropped": dropped,
            "drop_frac": dropped / pairs if pairs else 0.0,
            "expert_load": [float(x) for x in load],
            "load_imbalance": float(load.max()) / mean if mean > 0 else 0.0,
            "router_entropy": float(t[e + 1]) / routed if routed else 0.0,
        }

    def _flush_cow(self):
        """Flush pending copy-on-write page duplications in one device
        dispatch — owed BEFORE any program writes into a spliced table."""
        if self._cow_pending:
            src = np.asarray([s for s, _ in self._cow_pending], np.int32)
            dst = np.asarray([d for _, d in self._cow_pending], np.int32)
            self._set_pages(_copy_pages(self._pages_flat(),
                                        jnp.asarray(src), jnp.asarray(dst)))
            self._cow_pending = []

    def _admit(self):
        """Blocking admission (compat surface for tests/tools that admit
        outside a step): dispatch + immediate harvest."""
        admits, tok_dev, keys_dev, bad_dev = self._admit_dispatch()
        if admits:
            self._harvest_admits(admits, *jax.device_get(
                (tok_dev, keys_dev, bad_dev)))
        return [r for r, *_ in admits]

    def _harvest_admits(self, admits, first, new_keys, bad):
        first = np.asarray(first)
        new_keys = np.asarray(new_keys)
        bad = np.asarray(bad)
        for i, (req, slot, prefix, _base) in enumerate(admits):
            try:
                if self._fi is not None:
                    if self._fi.fire("step-exception", rid=req.rid):
                        raise InjectedFault(
                            f"injected step fault (rid {req.rid})")
                    if self._fi.fire("nan-logits", rid=req.rid):
                        raise NumericsError(
                            "injected non-finite logits", rid=req.rid)
                if bad[i]:
                    raise NumericsError(
                        "non-finite logits at prefill", rid=req.rid)
                if req.slot != slot:
                    # preempted between dispatch and harvest: keep the
                    # token it generated (the re-prefill prefix includes
                    # it) and the post-prefill key so a sampled stream
                    # resumes exactly; no slot bookkeeping — the slot was
                    # freed
                    self._harvest(req, [int(first[i])])
                    req._key = new_keys[i].copy()
                    if req.done and req in self._queue:
                        self._queue.remove(req)  # budget met at prefill
                    continue
                self._keys[slot] = new_keys[i]
                # the prefix KV just computed is now valid on device:
                # publish its full pages for future admissions (before
                # harvest, so even a finished-at-prefill or callback-
                # failed request leaves its prompt cached)
                self._register_prefix(prefix, self.tables[slot])
                self._harvest(req, [int(first[i])])
                self._last_tok[slot] = int(first[i])
                if req.done:  # single remaining token: finished at prefill
                    del self._active[slot]
                    self._free_slot(slot)
                    req.slot = None
            except RequestError as e:
                self._fail_request(req, e)
            except Exception as e:
                # anything else while processing ONE request fails that
                # request, not the batch (per-request isolation)
                self._fail_request(req, self._wrap_step_fault(e, req))

    def _harvest(self, req, toks) -> int:
        """Append generated tokens to a request, honoring eos/max. Returns
        the number of tokens actually CONSUMED — a multi-token append (a
        decode chain's overshoot, or a spec verify block with an eos or
        budget edge mid-block) truncates, and the caller needs the real
        count to roll the slot's KV length/pages back to match (ISSUE 5
        satellite: eos mid-block must not leave post-eos rows live)."""
        was_done = req.done
        fresh = []
        for t in toks:
            if req.done or len(req.tokens) >= req.max_new_tokens:
                req.done = True
                break
            req.tokens.append(int(t))
            fresh.append(int(t))
            if self.eos_id is not None and t == self.eos_id:
                req.done = True
            elif len(req.tokens) >= req.max_new_tokens:
                req.done = True
        if self._m is not None:
            if fresh:
                self._m.on_harvest(req, len(fresh))
            if req.done and not was_done:
                self._m.completed.inc()
        if _TRACER.enabled and fresh:
            # the flight recorder's "victim's last decode steps": one
            # instant per harvest, carrying the delivered tokens
            _TRACER.instant("engine.harvest", "engine",
                            parent=req.trace, rid=req.rid,
                            fresh=len(fresh), total=len(req.tokens),
                            done=req.done)
        if fresh and req.on_token is not None:
            try:
                req.on_token(fresh)
            except Exception as e:
                # the streaming callback belongs to the CALLER; its crash
                # fails this request (reason "callback" — tokens up to
                # here were delivered), never the batch. Every _harvest
                # call site sits inside a per-request isolation block.
                err = CallbackError(
                    f"on_token raised {type(e).__name__}: {e}", rid=req.rid)
                err.__cause__ = e
                raise err
        return len(fresh)

    # pre-measurement PRIOR for the cost of a chain boundary (dispatch +
    # blocking fetch) in units of one chunk's compute time. Only seeds
    # ``_dispatch_ratio`` until real step timings replace it: where a
    # boundary is cheap the ratio measures near 0 and the depth maximizer
    # stops over-chaining (VERDICT r4 #2: a measured ratio, not a magic
    # constant). 8 chunks is a deliberately high seed — over-chaining
    # before the first measurement costs bounded garbage compute, while
    # under-chaining costs a boundary per chunk.
    DISPATCH_COST_CHUNKS_PRIOR = 8.0

    def _observe_chain_time(self, nb, k, wall):
        """EMA the wall time of a pure-decode step at (bucket ``nb``,
        depth ``k``); with two distinct depths observed AT THE SAME
        BUCKET (chunk compute differs across buckets), T(k) = rtt +
        k*chunk_time yields the measured rtt/chunk ratio."""
        self._chain_obs += 1
        bucket = self._chain_time_ema.setdefault(nb, {})
        ema = bucket.get(k)
        bucket[k] = wall if ema is None else 0.7 * ema + 0.3 * wall
        ks = sorted(bucket)
        if len(ks) >= 2:
            k1, k2 = ks[0], ks[-1]
            t1, t2 = bucket[k1], bucket[k2]
            chunk_t = (t2 - t1) / (k2 - k1)
            # require a significant positive slope: timing jitter between
            # two near-equal EMAs would otherwise fit an absurd ratio
            if chunk_t > 0.02 * t1 / k1:
                ratio = min(max(0.0, (t1 - k1 * chunk_t) / chunk_t), 64.0)
                self._dispatch_ratio = (
                    ratio if self._dispatch_ratio is None
                    else 0.7 * self._dispatch_ratio + 0.3 * ratio)

    def _boundary_cost_chunks(self):
        return (self._dispatch_ratio if self._dispatch_ratio is not None
                else self.DISPATCH_COST_CHUNKS_PRIOR)

    def _chain_depth(self):
        """Chunks to chain before the next host fetch. Ending the chain
        the moment the first slot finishes (min over remaining) lets one
        straggler force tiny chains — and every chain boundary pays a full
        host round trip. Instead pick the pow2 depth (pow2 keeps the
        (bucket, depth) compile cache ≤ log2·log2 programs) that maximizes
        USEFUL tokens per unit time: stragglers may overshoot (their
        overshoot writes land in pages the harvest frees anyway and the
        tokens are discarded), which costs bounded garbage compute but
        saves a round trip per straggler."""
        rem = [req.max_new_tokens - len(req.tokens)
               for req in self._active.values()]
        kmax = self.max_chain
        if self._queue and self.eos_id is not None:
            # requests are WAITING and completions are UNPREDICTABLE
            # (eos): end the chain when the first slot can finish so it
            # turns over to the queue — deep chains would hold a finished
            # slot hostage for up to max_chain*chunk_size steps and wreck
            # queued-request time-to-first-token. Without an eos,
            # pre-admission prefills the replacement in the chain's
            # shadow, so turnover no longer needs early boundaries and
            # the useful-tokens-per-cost maximizer below decides alone
            # (waiting requests still pay their TTFT until the boundary —
            # the throughput/TTFT trade the reference's serving loop
            # makes the same way under continuous batching).
            kmax = min(kmax, max(1, -(-min(rem) // self.chunk_size)))
        cost = self._boundary_cost_chunks()
        best_k, best_u = 1, -1.0
        k = 1
        while k <= kmax:
            useful = sum(min(r, k * self.chunk_size) for r in rem)
            u = useful / (cost + k)
            if u > best_u:
                best_k, best_u = k, u
            k *= 2
        if (self._dispatch_ratio is None and self._probe_budget > 0
                and self._chain_obs >= 3
                and all(len(b) == 1
                        for b in self._chain_time_ema.values())):
            # steady single-depth workload: T(k) at ONE depth cannot
            # separate rtt from chunk time — probe a neighboring depth
            # (a slightly sub-optimal chain buys the calibration that
            # replaces the transport-tuned prior). STRICTLY bounded: a
            # noisy slope that keeps failing the significance guard must
            # not turn every steady-state step into a probe (measured
            # -13% steady decode when it did). Stays within kmax — the
            # straggler clamp protects queued requests' TTFT.
            probe = best_k // 2 if best_k > 1 else 2
            if 1 <= probe <= kmax and probe != best_k:
                self._probe_budget -= 1
                return probe
        return best_k


    def _alloc_len(self, req, k):
        """Page allocation target for a chained slot: the chain writes
        ``k * chunk_size`` tokens unconditionally, but tokens past the
        request's own budget are garbage — cap the allocation there and
        let the page-write clip route overshoot to the trash page."""
        limit = req.prompt.size + req.max_new_tokens + 1
        return min(int(self.lengths[req.slot]) + k * self.chunk_size, limit)

    def _alloc_row(self, length, prefix=None):
        """Allocate a STANDALONE page-table row (not bound to a slot) for
        a pre-admitted request's prefill, splicing any cached prefix of
        ``prefix`` first. Returns ``(row, base)`` or ``(None, 0)``."""
        need = self._pages_needed(length)
        if need > self.max_pages_per_seq:
            return None, 0
        row = np.zeros((self.max_pages_per_seq,), np.int32)
        base = (self._splice_prefix(row, prefix)
                if prefix is not None else 0)
        for i in range(int(np.count_nonzero(row)), need):
            page = self._alloc_page()
            if page is None:
                self._free_row(row)
                return None, 0
            row[i] = page
        return row, base

    def _free_row(self, row):
        self._drop_cow_for(row)
        for p in row:
            if p:
                self._release_page(int(p))

    @_phase("engine.admit")
    def _preadmit_dispatch(self, k, exclude=()):
        """PRE-ADMISSION (VERDICT r4 #2, the last serve-vs-steady gap):
        while the just-dispatched chain runs, prefill the queue heads
        that will take over the slots the chain is PREDICTED to free.
        Without an eos the prediction is exact (budgets are host-known),
        so at harvest the new requests activate into the freed slots and
        start decoding at the very next boundary — the turnover's prefill
        round trip vanishes into the chain's shadow. Prefills land in
        freshly allocated pages (never the completing slots' — no overlap
        with in-flight writes); a prediction miss (only possible with
        eos set, which gates this off entirely) would requeue + recompute.
        Returns (pending, tok_dev, keys_dev)."""
        if self.eos_id is not None or not self._queue \
                or self.prefill_chunk is not None:
            # chunked mode: admission belongs to the mixed step (a
            # pre-admission wave would compile the very prompt-length
            # prefill buckets chunking exists to avoid)
            return [], None, None, None
        horizon = k * self.chunk_size
        n_pred = sum(
            1 for req in self._active.values()
            if req.max_new_tokens - len(req.tokens) <= horizon)
        if not n_pred:
            return [], None, None, None
        pending = []  # (req, row, prefix, base)
        while self._queue and len(pending) < n_pred:
            req = self._queue[0]
            if req in exclude:
                # admitted-then-preempted THIS step: its admit prefill is
                # still in flight and its first token/key only arrive at
                # the harvest fence — re-prefilling now would double-count
                # that token (code-review r5). Stop (not skip): taking a
                # later request over the queue head would break FIFO.
                break
            prefix = self._prefix(req)
            row, base = self._alloc_row(prefix.size + self.chunk_size,
                                        prefix)
            if row is None:
                break  # pool pressure: normal admission will retry later
            self._queue.pop(0)
            pending.append((req, row, prefix, base))
        if not pending:
            return [], None, None, None
        # same step-fault-recovery registration as the admission wave:
        # pre-admitted requests are in neither _queue nor _active until
        # _activate_pending commits, and the caller's own registration
        # happens only AFTER this dispatch returns — a trace/dispatch
        # fault inside the wave used to black-hole the whole batch
        self._pending_inflight = pending
        tok, new_keys, bad = self._prefill_wave(
            [(req, prefix, row, base) for req, row, prefix, base in pending])
        return pending, tok, new_keys, bad

    def _activate_pending(self, pending, first, new_keys, bad):
        """Post-harvest: move pre-admitted requests into the slots the
        chain freed (their caches are already warm). Each request is its
        own isolation domain: a fault here fails it alone, and its
        standalone page row is returned whichever path it dies on."""
        first = np.asarray(first)
        new_keys = np.asarray(new_keys)
        bad = np.asarray(bad)
        for i, (req, row, prefix, _base) in enumerate(pending):
            try:
                if self._fi is not None:
                    if self._fi.fire("step-exception", rid=req.rid):
                        raise InjectedFault(
                            f"injected step fault (rid {req.rid})")
                    if self._fi.fire("nan-logits", rid=req.rid):
                        raise NumericsError(
                            "injected non-finite logits", rid=req.rid)
                if bad[i]:
                    raise NumericsError(
                        "non-finite logits at pre-admission prefill",
                        rid=req.rid)
                # the prefix KV in this row is valid on device: publish
                # its full pages — even the prediction-miss path below
                # then requeues into a warm cache instead of recomputing
                self._register_prefix(prefix, row)
                if not self._free_slots:
                    # prediction miss (cannot happen with eos gating; kept
                    # as a correctness net): recompute policy — requeue
                    # with the generated token folded into the prefix
                    self._free_row(row)
                    row = None  # ownership returned before harvest
                    self._harvest(req, [int(first[i])])
                    req._key = new_keys[i].copy()
                    if not req.done:
                        self._queue.insert(0, req)
                    continue
                slot = self._free_slots.pop()
                self.tables[slot] = row
                self.lengths[slot] = prefix.size
                req.slot = slot  # row ownership now travels with the slot
                self._active[slot] = req
                self._temps[slot] = req.temperature
                self._keys[slot] = new_keys[i]
                self._note_admitted(req)
                self._harvest(req, [int(first[i])])
                self._last_tok[slot] = int(first[i])
                if req.done:
                    del self._active[slot]
                    self._free_slot(slot)
                    req.slot = None
            except RequestError as e:
                if req.slot is None and row is not None:
                    self._free_row(row)
                self._fail_request(req, e)
            except Exception as e:
                if req.slot is None and row is not None:
                    self._free_row(row)
                self._fail_request(req, self._wrap_step_fault(e, req))

    def _wants_mixed(self) -> bool:
        """Route to the mixed chunk+decode step? Yes while any prompt is
        mid-stream, or when a queued request could take a slot (the
        mixed step owns admission in chunked mode). Pure-decode phases
        fall back to the chained path — deep chains amortize the host
        round trip far better than depth-1 mixed steps."""
        if self.prefill_chunk is None:
            return False
        if self._chunk_left:
            return True
        return bool(self._queue) and bool(self._free_slots) \
            and len(self._active) < self._slot_cap

    def _bind_chunked(self):
        """Chunked-mode admission: bind queued requests to slots WITHOUT
        a prefill dispatch — their first chunk rides the very next mixed
        (or disaggregated prefill-role) step. Shared by ``_mixed_step``
        and ``_disagg_step``."""
        chunk = self.prefill_chunk
        self._cache.drain_tier()  # promoted pages splice this admission
        while (self._queue and self._free_slots
               and len(self._active) < self._slot_cap):
            req = self._queue[0]
            prefix = self._prefix(req)
            # pages this admission needs NOW: the first chunk only —
            # later chunks allocate step by step, so a long prompt's
            # tail never holds pages before the tokens arrive
            need = self._pages_needed(min(prefix.size, chunk))
            if self._pcache is not None:
                _, peeked = self._pcache.lookup(prefix, touch=False)
                reuse = peeked // self.page_size
                if peeked and peeked == int(prefix.size):
                    reuse -= 1  # the COW copy still needs a fresh page
                need = max(0, self._pages_needed(
                    min(prefix.size, peeked + chunk)) - reuse)
            if need > self._available_pages():
                break  # pool pressure: let running requests drain first
            slot = self._free_slots.pop()
            self._queue.pop(0)
            base = self._splice_prefix(self.tables[slot], prefix)
            # attribute the splice's KV-tier promote wait to THIS
            # request's TTFT decomposition (first admission only —
            # re-admission after preemption is preemption cost, just
            # like queue-wait in _note_admitted)
            if not req._admitted:
                req._t_promote_wait += self._last_promote_wait_s
            try:
                got = self._ensure_pages(
                    slot, min(prefix.size, base + chunk))
            except RequestError as e:
                self._drop_cow_for(self.tables[slot])
                self._free_slot(slot)
                self._fail_request(req, e)
                continue
            if not got:
                self._drop_cow_for(self.tables[slot])
                self._free_slot(slot)
                self._queue.insert(0, req)
                break
            self.lengths[slot] = base
            self._chunk_left[slot] = prefix[base:]
            req.slot = slot
            self._active[slot] = req
            self._temps[slot] = req.temperature
            if req._key is None:
                seed = int(req.seed if req.seed is not None else req.rid)
                # threefry2x32 key layout, built host-side (see
                # _prefill_wave: PRNGKey costs a device round trip)
                req._key = np.array(
                    [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)
            self._keys[slot] = req._key
            self._note_admitted(req)

    def _mixed_step(self):
        """Chunked-prefill scheduling iteration (ISSUE 9 tentpole b).
        Admission binds queued requests to slots WITHOUT a prefill
        dispatch — their first chunk rides this very step — then one
        fixed-shape mixed program advances every active slot: decoding
        slots by one token, prefilling slots by up to ``prefill_chunk``
        prompt tokens. Long prompts never stall the decode batch (decode
        tokens land every step while the prompt streams in), pages
        allocate chunk-by-chunk instead of prompt-at-once, and the whole
        wave harvests with one blocking fetch."""
        chunk = self.prefill_chunk
        self._bind_chunked()
        if not self._active:
            if self._queue:
                self._note_stall()
            return
        self._stall_steps = 0

        def target(slot, req, _k):
            left = self._chunk_left.get(slot)
            if left is not None:
                return int(self.lengths[slot]) + min(left.size, chunk)
            return min(int(self.lengths[slot]) + 1,
                       req.prompt.size + req.max_new_tokens + 1)

        # allocate this step's pages — shrink (no-op at depth 1), then
        # preempt, then fail the lone unservable request, never raise; a
        # preempted mid-prefill slot drops its _chunk_left with the slot
        # and re-chunks from scratch on re-admission (recompute policy)
        self._reserve_step_pages(1, target)
        if not self._active:
            return
        slots, widths, tok_d, keys_d, bad_d = self._mixed_dispatch(
            sorted(self._active))
        tok, keys_h, bad_h = (np.asarray(a) for a in jax.device_get(
            (tok_d, keys_d, bad_d)))
        self._mixed_harvest(slots, widths, tok, keys_h, bad_h)

    @_phase("engine.mixed_dispatch")
    def _mixed_dispatch(self, slots):
        """Build + dispatch ONE mixed chunk+decode program over exactly
        ``slots`` (rows pad to the fixed max_slots bucket; slots not
        listed — e.g. the decode-role batch of a disaggregated step —
        simply aren't rows). Returns device handles; never blocks."""
        chunk = self.prefill_chunk
        n = len(slots)
        nb = _pow2ceil(self.max_slots)
        ids = np.zeros((nb, chunk), np.int32)
        widths = np.ones((nb,), np.int32)  # pad rows: width 1 → trash page
        emit = np.zeros((nb,), np.int32)
        tables_c = np.zeros((nb, self.max_pages_per_seq), np.int32)
        lengths_c = np.zeros((nb,), np.int32)
        temps_c = np.zeros((nb,), np.float32)
        keys_c = np.zeros((nb, 2), np.uint32)
        tables_c[:n] = self.tables[slots]
        lengths_c[:n] = self.lengths[slots]
        temps_c[:n] = self._temps[slots]
        keys_c[:n] = self._keys[slots]
        n_chunks = chunk_toks = 0
        for i, slot in enumerate(slots):
            left = self._chunk_left.get(slot)
            if left is not None:
                w = min(left.size, chunk)
                ids[i, :w] = left[:w]
                widths[i] = w
                emit[i] = int(w == left.size)
                n_chunks += 1
                chunk_toks += w
            else:
                ids[i, 0] = self._last_tok[slot]
                emit[i] = 1
        if self._m is not None:
            self._m.decode_batch.observe(n)
            if n_chunks:
                self._m.prefill_chunks.inc(n_chunks)
                self._m.pc_computed_tokens.inc(chunk_toks)
            self._m.slab_dispatch.labels(path="chunked_prefill").inc()
        if _TRACER.enabled and n_chunks:
            _TRACER.instant("engine.prefill_chunk", "engine",
                            chunks=n_chunks, tokens=chunk_toks,
                            decode_rows=n - n_chunks)
        self._flush_cow()
        sampling = bool(np.any(temps_c > 0.0))
        mixed = self._get_mixed(nb, sampling)
        tok_d, keys_d, bad_d, pages, *ex = mixed(
            self._params, self._pages_flat(), jnp.asarray(ids),
            jnp.asarray(widths), jnp.asarray(emit),
            jnp.asarray(tables_c), jnp.asarray(lengths_c),
            jnp.asarray(temps_c), jnp.asarray(keys_c))
        self._set_pages(pages)
        self._note_moe_stats(ex)
        return slots, widths, tok_d, keys_d, bad_d

    @_phase("engine.harvest")
    def _mixed_harvest(self, slots, widths, tok, keys_h, bad_h):
        """Host harvest of a mixed dispatch: advance chunk state, take
        tokens from emitting rows, per-request fault isolation."""
        cap = self.max_pages_per_seq * self.page_size
        for i, slot in enumerate(slots):
            req = self._active.get(slot)
            if req is None or req.slot != slot:
                continue  # failed between dispatch and harvest
            try:
                if self._fi is not None:
                    if self._fi.fire("step-exception", rid=req.rid):
                        raise InjectedFault(
                            f"injected step fault (rid {req.rid})")
                    if self._fi.fire("nan-logits", rid=req.rid):
                        raise NumericsError(
                            "injected non-finite logits", rid=req.rid)
                if bad_h[i]:
                    raise NumericsError(
                        "non-finite logits in mixed chunk step",
                        rid=req.rid)
                self.lengths[slot] = min(
                    int(self.lengths[slot]) + int(widths[i]), cap)
                left = self._chunk_left.get(slot)
                if left is not None and int(widths[i]) < left.size:
                    # mid-prompt chunk: the KV landed; the emitted token
                    # predicts a prompt token we already have — discard
                    self._chunk_left[slot] = left[int(widths[i]):]
                    continue
                if left is not None:
                    # final chunk: prompt fully resident — publish it to
                    # the prefix cache and take the first generated
                    # token, exactly where classic prefill takes it
                    del self._chunk_left[slot]
                    self._register_prefix(self._prefix(req),
                                          self.tables[slot])
                self._keys[slot] = keys_h[i]
                self._harvest(req, [int(tok[i])])
                self._last_tok[slot] = int(tok[i])
                if req.done:
                    del self._active[slot]
                    self._free_slot(slot)
                    req.slot = None
            except RequestError as e:
                self._fail_request(req, e)
            except Exception as e:
                self._fail_request(req, self._wrap_step_fault(e, req))

    # ------------------------------- prefill/decode disaggregation (ISSUE 11)
    @_phase("engine.chain_dispatch")
    def _chain_dispatch(self, slots, k):
        """Dispatch a decode chain over exactly ``slots`` (compacted to
        their own pow2 bucket) — the decode-role half of a disaggregated
        step. No admission splicing, no pre-admission: those belong to
        the chunked admission path. Returns the chain tuple; never
        blocks."""
        slot_reqs = [self._active[s] for s in slots]
        n = len(slots)
        nb = _pow2ceil(n)
        if self._m is not None:
            self._m.chain_depth_at(k).inc()
            self._m.decode_batch.observe(n)
        tables_c = np.zeros((nb, self.max_pages_per_seq), np.int32)
        lengths_c = np.zeros((nb,), np.int32)
        last_c = np.zeros((nb,), np.int32)
        temps_c = np.zeros((nb,), np.float32)
        keys_c = np.zeros((nb, 2), np.uint32)
        tables_c[:n] = self.tables[slots]
        lengths_c[:n] = self.lengths[slots]
        last_c[:n] = self._last_tok[slots]
        temps_c[:n] = self._temps[slots]
        keys_c[:n] = self._keys[slots]
        sampling = bool(np.any(temps_c > 0.0))
        decode = self._get_decode(nb, k, sampling)
        toks_d, pages, lengths_d, keys_d, bad_d, *ex = decode(
            self._params, self._pages_flat(), jnp.asarray(tables_c),
            jnp.asarray(lengths_c), jnp.asarray(last_c),
            jnp.asarray(temps_c), jnp.asarray(keys_c))
        self._set_pages(pages)
        self._note_moe_stats(ex)
        return (slots, slot_reqs, toks_d, lengths_d, keys_d, bad_d)

    @_phase("engine.harvest")
    def _chain_harvest(self, slots, slot_reqs, toks, lengths_h, keys_h,
                       bad_h):
        """Host harvest of a decode chain (per-request isolation — the
        same contract as the vanilla chained step's harvest loop)."""
        for i, (slot, req) in enumerate(zip(slots, slot_reqs)):
            if req.done and req.slot is None:
                continue  # finished elsewhere this step; slot freed
            if req.slot != slot:
                continue  # preempted mid-step; chain row is garbage
            try:
                if self._fi is not None:
                    if self._fi.fire("step-exception", rid=req.rid):
                        raise InjectedFault(
                            f"injected step fault (rid {req.rid})")
                    if self._fi.fire("nan-logits", rid=req.rid):
                        raise NumericsError(
                            "injected non-finite logits", rid=req.rid)
                if bad_h[i]:
                    raise NumericsError(
                        "non-finite logits in decode chain", rid=req.rid)
                self._harvest(req, toks[i])
                self._last_tok[slot] = int(toks[i, -1])
                self.lengths[slot] = int(lengths_h[i])
                self._keys[slot] = keys_h[i]
                if req.done:
                    del self._active[slot]
                    self._free_slot(slot)
                    # clearing the binding makes the done-and-unbound
                    # guard above skip this request's rows in any LATER
                    # chain of a multi-step round trip (ISSUE 12)
                    req.slot = None
            except RequestError as e:
                self._fail_request(req, e)
            except Exception as e:
                self._fail_request(req, self._wrap_step_fault(e, req))

    def _disagg_step(self):
        """Prefill/decode role disaggregation (ISSUE 11 tentpole): one
        scheduling step dispatches the PREFILL-ROLE program (the mixed
        chunk step over mid-prompt slots — streaming each prompt
        ``prefill_chunk`` tokens into the shared pool) and the
        DECODE-ROLE chain (depth-k over fully-prefilled slots)
        back-to-back, then harvests both with ONE blocking fetch.

        Versus the plain mixed step — which locks every decoding slot to
        ONE token per host round trip while any prompt streams — decode
        slots keep their deep chains (k·chunk_size tokens per round
        trip) while long prompts trickle in beside them: the
        DistServe/vLLM prefill-decode separation, in-process, with the
        cache-coordinator's shared (possibly TP-sharded) pool as the
        page handoff instead of a cross-worker KV transfer. A prompt
        whose final chunk lands this step emits its first token here
        and joins the decode-role batch at the very next boundary —
        that handoff is the "stream finished KV pages to the decode
        batch" edge, and prefix-cache hits ride it too (spliced pages
        skip the prefill role entirely).

        Token streams are identical to the mixed step's (and so to the
        single-chip engine's): per-token computation and key burns are
        unchanged, only WHICH program advances a slot differs —
        asserted by tests/test_tp_serving.py across greedy/sampled/
        cache/chaos scenarios."""
        chunk = self.prefill_chunk
        self._bind_chunked()
        if not self._active:
            if self._queue:
                self._note_stall()
            return
        self._stall_steps = 0
        dec = [s for s in sorted(self._active)
               if s not in self._chunk_left]
        k = 1
        if dec:
            # chain depth over the decode-role batch only (the useful-
            # tokens-per-round-trip maximizer, with the eos turnover
            # clamp — same policy as _chain_depth, scoped to dec slots)
            rem = [self._active[s].max_new_tokens
                   - len(self._active[s].tokens) for s in dec]
            kmax = self.max_chain
            if self._queue and self.eos_id is not None:
                kmax = min(kmax, max(1, -(-min(rem) // self.chunk_size)))
            cost = self._boundary_cost_chunks()
            best_k, best_u = 1, -1.0
            kk = 1
            while kk <= kmax:
                useful = sum(min(r, kk * self.chunk_size) for r in rem)
                u = useful / (cost + kk)
                if u > best_u:
                    best_k, best_u = kk, u
                kk *= 2
            k = best_k

        def target(slot, req, kk):
            left = self._chunk_left.get(slot)
            if left is not None:
                return int(self.lengths[slot]) + min(left.size, chunk)
            return self._alloc_len(req, kk)

        # role-aware page reservation: chunk slots need one chunk, chain
        # slots k*chunk_size — the shared shrink→preempt→fail ladder
        # halves k under pressure before anyone is evicted
        k = self._reserve_step_pages(k, target)
        if not self._active:
            return
        k = max(1, k)
        pre = [s for s in sorted(self._active) if s in self._chunk_left]
        dec = [s for s in sorted(self._active)
               if s not in self._chunk_left]
        mixed_d = self._mixed_dispatch(pre) if pre else None
        chain = self._chain_dispatch(dec, k) if dec else None
        # ---- single harvest fence for both roles ----
        handles = []
        if mixed_d is not None:
            handles += list(mixed_d[2:])
        if chain is not None:
            handles += list(chain[2:])
        fetched = jax.device_get(tuple(handles))
        off = 0
        if mixed_d is not None:
            tok, keys_h, bad_h = (np.asarray(a) for a in fetched[:3])
            self._mixed_harvest(mixed_d[0], mixed_d[1], tok, keys_h,
                                bad_h)
            off = 3
        if chain is not None:
            toks, lengths_h, keys_h, bad_h = (
                np.asarray(a) for a in fetched[off:off + 4])
            self._chain_harvest(chain[0], chain[1], toks, lengths_h,
                                keys_h, bad_h)

    def step(self, n: Optional[int] = None) -> int:
        """One scheduling round trip. NEVER raises (ISSUE 6): request-
        scoped faults fail the one request (terminal FAILED with a
        taxonomy reason) inside ``_chained_step``/``_spec_step``'s
        per-request isolation blocks; anything that escapes them is an
        engine-scoped fault handled by ``_recover_step_fault`` —
        requeue-all recompute + pool reset + watchdog degradation.

        ``n`` (default ``Engine(multi_step=)``) is the multi-step budget
        (ISSUE 12): in pure-decode phases up to ``n`` decode iterations
        dispatch back-to-back and harvest behind ONE blocking fetch;
        phases that need per-iteration host decisions (admission waves,
        mixed chunk scheduling, spec drafting) run exactly one iteration
        regardless. Token streams are bit-identical for every ``n``.
        Returns the number of live requests remaining (queued + active)."""
        t0 = time.perf_counter()
        if self._watchdog.quarantined:
            # fail-stop on proven corruption (ISSUE 14): a quarantined
            # engine must not mint another token through weights its own
            # audit proved corrupt — silence is recoverable (the router
            # migrates stalled streams via resume-from-emitted, every
            # delivered token predates the corruption), a wrong token is
            # not. Requests stay live so the migration journal sees them.
            return len(self._queue) + len(self._active)
        if self._fi is not None and self._fi.fire("slow-step"):
            time.sleep(self._fi.param("slow-step", "delay_ms", 20.0) / 1e3)
        if self._has_deadlines:
            self._expire_deadlines()
        budget = self.multi_step if n is None else max(1, int(n))
        batched = 1
        # which of the five step paths this round trip takes
        if self._wants_mixed():
            path = "disagg" if self.disaggregate else "mixed"
        elif self._spec is not None and self._spec_enabled:
            path = "spec"
        elif budget > 1 and self._active and not self._queue:
            path = "multi_chained"
        else:
            path = "chained"
        # the ONE engine.step span site, whatever the path: open for the
        # whole round trip, its phases (engine.admit, .prefill_dispatch,
        # .chain_dispatch, .harvest) nest under it
        with _TRACER.nested("engine.step", "engine",
                            path=path) as step_span:
            try:
                # KV-tier completions land at the step boundary (ISSUE 15):
                # even a step that admits nothing applies finished spills/
                # promotions, so the tier converges while the engine decodes
                self._cache.drain_tier()
                if path == "disagg":
                    self._disagg_step()
                elif path == "mixed":
                    self._mixed_step()
                elif path == "spec":
                    self._spec_step()
                elif path == "multi_chained":
                    batched = self._multi_chained_step(budget)
                else:
                    self._chained_step(t0)
                self._watchdog.note_step_ok()
                if self._integrity is not None:
                    # online SDC audits (ISSUE 14): weight-shard probe on
                    # idle steps, shadow recompute every N — host-side,
                    # never raises (detections route through quarantine /
                    # _fail_request inside the sentinel)
                    self._integrity.on_step()
            except Exception as e:
                self._recover_step_fault(e)
            if self._moe_stats_n:
                # router-stats handles fold at the step boundary: their
                # producing programs were fenced by the harvest above, so
                # this never blocks on in-flight compute
                self._drain_moe_stats()
            if self._m is not None:
                self._m.steps_per_roundtrip.observe(batched)
                self._m.step_seconds.observe(time.perf_counter() - t0)
                self._m.active_slots.set(len(self._active))
                self._m.queue_depth.set(len(self._queue))
                self._m.pages_in_use.set(
                    self.num_pages - 1 - len(self._free_pages))
                if self._pcache is not None:
                    self._m.pc_pages.set(self._pcache.n_pages)
            step_span.set(active=len(self._active),
                          queued=len(self._queue), batched=batched)
        return len(self._queue) + len(self._active)

    def _recover_step_fault(self, exc: BaseException):
        """Engine-scoped fault recovery (a compiled dispatch died, or the
        step's host spine raised with bookkeeping mid-commit). Never
        re-raises. The recompute policy generalizes preemption: every
        active request requeues (front of queue, retry-bounded) with its
        live PRNG key, and the page pool is rebuilt from scratch —
        donated buffers may be dead after a failed dispatch, and their
        content is fully recomputable from host-side token history. The
        watchdog counts the fault; repeated faults degrade the engine
        (spec→vanilla, then admission cap halved) instead of killing it."""
        self._watchdog.note_step_fault(exc)
        if _TRACER.enabled:
            # flight recorder (ISSUE 18): the ring holds the last N
            # spans/harvests before this fault — dump the postmortem
            # BEFORE recovery rewrites the scheduler state
            _TRACER.instant("engine.step_fault", "fault",
                            error=type(exc).__name__, msg=str(exc)[:200])
            _flight_record(f"step-fault-{type(exc).__name__}")
        if self._m is not None:
            self._m.recoveries.inc()
        for slot in sorted(self._active):
            req = self._active.pop(slot)
            req._key = self._keys[slot].copy()
            req.slot = None
            self._requeue(req)
        # admission-wave/pre-admitted requests whose prefill was in
        # flight live only in the failed step's locals — without this
        # they would vanish from the engine entirely (their standalone
        # page rows die with the pool reset below, which is fine:
        # recompute policy). The _queue check covers a fault landing
        # AFTER the wave committed to _active: the loop above already
        # requeued those, and a double insert would duplicate the stream
        for req, *_ in self._pending_inflight:
            if not req.done and req not in self._queue:
                self._requeue(req)
        self._pending_inflight = []
        # router-stats handles of the failed step's dispatches are dead
        # with their programs; the requeued work re-counts on recompute
        self._moe_pending = []
        self._reset_pool()

    def _chained_step(self, t0):
        """The vanilla scheduling iteration: dispatch the admission
        prefill AND the decode chain back-to-back (the chain's inputs
        splice the prefill's device outputs, so freshly admitted requests
        decode in the same step), then harvest EVERYTHING with a single
        blocking fetch. One host round trip per step instead of the old
        two — admission never stalls the decode pipeline (VERDICT r4 #2).
        With ``prefill_chunk`` set the mixed step owns admission (``step``
        routes there whenever the queue is non-empty), so this path runs
        pure decode chains."""
        if self.prefill_chunk is None:
            admits, pre_tok, pre_keys, pre_bad = self._admit_dispatch()
        else:
            admits, pre_tok, pre_keys, pre_bad = [], None, None, None
        chain = None
        if self._active:
            self._stall_steps = 0
            # pick a chain depth, then allocate pages for the whole chain;
            # under pool pressure shrink the chain before preempting anyone
            # (bounded), before failing the lone unservable request
            k = self._reserve_step_pages(
                self._chain_depth(),
                lambda slot, req, kk: self._alloc_len(req, kk))
        if self._active:
            with _TRACER.nested("engine.chain_dispatch", "engine"):
                # compact active slots into a pow2 bucket: per-token cost
                # follows load, not max_slots capacity
                slots = sorted(self._active)
                slot_reqs = [self._active[s] for s in slots]
                n = len(slots)
                nb = _pow2ceil(n)
                if self._m is not None:
                    self._m.chain_depth_at(k).inc()
                    self._m.decode_batch.observe(n)
                tables_c = np.zeros((nb, self.max_pages_per_seq), np.int32)
                lengths_c = np.zeros((nb,), np.int32)
                last_c = np.zeros((nb,), np.int32)
                temps_c = np.zeros((nb,), np.float32)
                keys_c = np.zeros((nb, 2), np.uint32)
                tables_c[:n] = self.tables[slots]
                lengths_c[:n] = self.lengths[slots]
                last_c[:n] = self._last_tok[slots]
                temps_c[:n] = self._temps[slots]
                keys_c[:n] = self._keys[slots]
                last_in = jnp.asarray(last_c)
                keys_in = jnp.asarray(keys_c)
                if admits:
                    # admitted slots' first token / key state live ONLY on
                    # device (prefill outputs): splice them into the chain
                    # inputs with a tiny scatter — still no host sync
                    row_of = {s: i for i, s in enumerate(slots)}
                    nba = int(pre_tok.shape[0])
                    rows = np.full((nba,), nb, np.int32)  # OOB pads drop
                    for i, (_, slot, *_rest) in enumerate(admits):
                        rows[i] = row_of.get(slot, nb)  # preempted → drop
                    last_in, keys_in = _patch_rows(
                        last_in, keys_in, jnp.asarray(rows), pre_tok,
                        pre_keys)
                sampling = bool(np.any(temps_c > 0.0))
                fresh = (nb, k, sampling) not in self._decode_fns
                decode = self._get_decode(nb, k, sampling)
                # the whole chain is ONE compiled scan: one dispatch; the ONLY
                # blocking fetch of the step happens below and covers the
                # prefill results too
                toks_d, pages, lengths_d, keys_d, bad_d, *ex = decode(
                    self._params, self._pages_flat(), jnp.asarray(tables_c),
                    jnp.asarray(lengths_c), last_in,
                    jnp.asarray(temps_c), keys_in)
                self._set_pages(pages)
                self._note_moe_stats(ex)
                chain = (slots, slot_reqs, nb, k, fresh, toks_d, lengths_d,
                         keys_d, bad_d)
                # queue heads whose slots this chain will free prefill NOW,
                # in the chain's shadow
                pending, pend_tok, pend_keys, pend_bad = \
                    self._preadmit_dispatch(
                        k, exclude=[r for r, *_ in admits])
                # registered for step-fault recovery: pending requests live
                # outside queue AND active until _activate_pending commits
                self._pending_inflight = pending
        else:
            if self._queue and not admits:
                # queued but nothing active and no admission possible:
                # tolerated briefly, then the queue head is shed
                # (pre-ISSUE-6 this raised out of step())
                self._note_stall()
            pending, pend_tok, pend_keys, pend_bad = [], None, None, None
        # ---- single harvest fence for prefill + chain + pre-admission ----
        with _TRACER.nested("engine.harvest", "engine"):
            fetched = jax.device_get((
                pre_tok, pre_keys, pre_bad, pend_tok, pend_keys, pend_bad,
                *(chain[5:] if chain else ())))
            if admits:
                self._harvest_admits(admits, fetched[0], fetched[1],
                                     fetched[2])
            if chain:
                slots, slot_reqs, nb, k, fresh, *_ = chain
                toks = np.asarray(fetched[6])  # [nb, k*chunk]
                lengths_h = np.asarray(fetched[7])
                keys_h = np.asarray(fetched[8])
                bad_h = np.asarray(fetched[9])
                for i, (slot, req) in enumerate(zip(slots, slot_reqs)):
                    if req.done and req.slot is None:
                        continue  # finished at prefill harvest; slot freed
                    if req.slot != slot:
                        continue  # preempted mid-step; chain row is garbage
                    try:
                        if self._fi is not None:
                            if self._fi.fire("step-exception", rid=req.rid):
                                raise InjectedFault(
                                    f"injected step fault (rid {req.rid})")
                            if self._fi.fire("nan-logits", rid=req.rid):
                                raise NumericsError(
                                    "injected non-finite logits", rid=req.rid)
                        if bad_h[i]:
                            raise NumericsError(
                                "non-finite logits in decode chain",
                                rid=req.rid)
                        self._harvest(req, toks[i])
                        self._last_tok[slot] = int(toks[i, -1])
                        self.lengths[slot] = int(lengths_h[i])
                        self._keys[slot] = keys_h[i]
                        if req.done:
                            del self._active[slot]
                            self._free_slot(slot)
                    except RequestError as e:
                        self._fail_request(req, e)
                    except Exception as e:
                        # per-request isolation: ONE request's harvest going
                        # wrong must never take down its batchmates
                        self._fail_request(req, self._wrap_step_fault(e, req))
                if pending:
                    self._activate_pending(pending, fetched[3], fetched[4],
                                           fetched[5])
                self._pending_inflight = []
                if not admits and not pending and not fresh:
                    # pure-decode step on a warm program: a clean T(k) sample
                    # for the measured dispatch-cost ratio (a fresh compile's
                    # trace/cache-load seconds would poison the fit)
                    self._observe_chain_time(nb, k, time.perf_counter() - t0)

    def _multi_chained_step(self, budget: int) -> int:
        """Multi-step scheduling fast path (ISSUE 12 tentpole): up to
        ``budget`` chained-decode iterations per host round trip.

        Engages only from ``step()`` when the round is PURE DECODE —
        active slots, empty queue, spec off, no prompt mid-chunk — the
        phase where every iteration would otherwise pay the full host
        round trip (pack, dispatch, fetch, harvest) for identical
        scheduling decisions. The same compiled (bucket, depth) decode
        program dispatches ``budget`` times back-to-back with its device
        outputs (pages, lengths, keys, last token) feeding the next
        dispatch — no host fetch between — and ONE ``device_get`` fence
        harvests every chain in submission order.

        Bit-identical to sequential ``step()`` calls by construction:

        * per-row computation is the untouched decode program; chaining
          N dispatches computes exactly what N sequential steps compute
          (the host fetch/re-upload between steps is value-preserving);
        * the harvest walks chains in order through ``_chain_harvest``'s
          per-request isolation blocks — eos/budget truncation, NaN
          guards, and fault-injection points fire per request per chain
          exactly as they do per step;
        * a request finishing (or failing) at chain i frees its slot
          there; its rows in chains i+1.. are garbage the harvest guards
          skip — the same discard path as chain overshoot, with writes
          confined to pages the slot owned (released on free);
        * once the active set drains the harvest EARLY-EXITS, discarding
          the remaining chains wholesale.

        Page reservation covers all ``budget`` chains up front; under
        pool pressure the budget halves BEFORE the shrink→preempt→fail
        ladder can evict anyone a single step wouldn't have (and even a
        preemption keeps streams identical — recompute policy). Returns
        the number of iterations actually harvested."""
        self._stall_steps = 0
        k = self._chain_depth()
        # cap the budget at the work that exists: chains past every
        # request's remaining budget would be pure garbage compute
        max_rem = max(req.max_new_tokens - len(req.tokens)
                      for req in self._active.values())
        budget = max(1, min(budget, -(-max_rem // (k * self.chunk_size))))

        def need_for(b):
            tot = 0
            for slot, req in self._active.items():
                have = int(np.count_nonzero(self.tables[slot]))
                want = min(int(self.lengths[slot]) + b * k * self.chunk_size,
                           req.prompt.size + req.max_new_tokens + 1)
                tot += max(0, self._pages_needed(want) - have)
            return tot

        while budget > 1 and need_for(budget) > self._available_pages():
            budget //= 2
        k = self._reserve_step_pages(
            k, lambda slot, req, kk: min(
                int(self.lengths[slot]) + kk * budget * self.chunk_size,
                req.prompt.size + req.max_new_tokens + 1))
        if not self._active:
            return 1
        k = max(1, k)
        slots = sorted(self._active)
        slot_reqs = [self._active[s] for s in slots]
        n = len(slots)
        nb = _pow2ceil(n)
        if self._m is not None:
            self._m.decode_batch.observe(n)
        tables_c = np.zeros((nb, self.max_pages_per_seq), np.int32)
        lengths_c = np.zeros((nb,), np.int32)
        last_c = np.zeros((nb,), np.int32)
        temps_c = np.zeros((nb,), np.float32)
        keys_c = np.zeros((nb, 2), np.uint32)
        tables_c[:n] = self.tables[slots]
        lengths_c[:n] = self.lengths[slots]
        last_c[:n] = self._last_tok[slots]
        temps_c[:n] = self._temps[slots]
        keys_c[:n] = self._keys[slots]
        sampling = bool(np.any(temps_c > 0.0))
        decode = self._get_decode(nb, k, sampling)
        tables_j = jnp.asarray(tables_c)
        temps_j = jnp.asarray(temps_c)
        pages = self._pages_flat()
        lengths_in = jnp.asarray(lengths_c)
        last_in = jnp.asarray(last_c)
        keys_in = jnp.asarray(keys_c)
        chains = []
        for _ in range(budget):
            toks_d, pages, lengths_in, keys_in, bad_d, *ex = decode(
                self._params, pages, tables_j, lengths_in, last_in,
                temps_j, keys_in)
            self._note_moe_stats(ex)
            # the chain-to-chain handoff stays ON DEVICE: the next
            # chain's last-token input is the previous chain's final
            # column (statically gated by the analyze registry's
            # multi_step_decode twin at tp>1 — shards carry locally)
            last_in = _last_col(toks_d)
            chains.append((toks_d, lengths_in, keys_in, bad_d))
            if self._m is not None:
                self._m.chain_depth_at(k).inc()
        self._set_pages(pages)
        # ---- the round trip's ONLY blocking fence ----
        fetched = jax.device_get(tuple(h for c in chains for h in c))
        done = 0
        for i in range(budget):
            toks, lengths_h, keys_h, bad_h = (
                np.asarray(a) for a in fetched[4 * i:4 * i + 4])
            self._chain_harvest(slots, slot_reqs, toks, lengths_h,
                                keys_h, bad_h)
            done = i + 1
            if not self._active:
                break  # early exit: everyone finished/failed — the
                # remaining chains' outputs are overshoot, discarded
        return done

    # ------------------------------------------------ speculative decoding
    def _spec_step(self):
        """One spec-decode scheduling iteration (ISSUE 5 tentpole):
        admit (blocking — drafting needs the host-side token history of
        every active request anyway), let the drafter propose up to k
        tokens per request, score ALL k+1 positions in ONE verify
        forward through the paged decode path, then accept — token-exact
        prefix matching for greedy, distribution-preserving rejection
        sampling for temperature>0 (reusing the per-request key state) —
        and roll rejected rows back via ``_trim_pages`` so the
        preemption/eviction invariants hold. Each step lands 1..k+1
        tokens per request; every metric normalizes by the ACTUAL count
        (see ``_EngineMetrics.on_harvest``), and spec steps never feed
        ``_observe_chain_time`` — the chain-depth calibration stays a
        vanilla-only fit that varying acceptance cannot skew.

        Drafter faults (ISSUE 6): a drafter that raises — or is
        fault-injected via ``drafter-corruption`` — degrades THIS step to
        zero drafts, and a zero-draft verify is exactly a vanilla decode
        step, so greedy output is unchanged. The drafter's private cache
        resets so its next proposal re-syncs from the host-side token
        history (slot reconciliation after failure), and the watchdog
        counts faults toward disabling spec outright."""
        t0 = time.perf_counter()
        spec = self._spec
        self._admit()
        if not self._active:
            if self._queue:
                self._note_stall()
            return
        self._stall_steps = 0
        k = spec.k
        # allocate the k+1-row verify block for every slot, preempting
        # the longest request under pool pressure exactly like the
        # vanilla depth-1 chain (writes past a request's own budget cap
        # route to the trash page via the zero table entries)
        self._reserve_step_pages(
            1, lambda slot, req, _kk: min(
                int(self.lengths[slot]) + k + 1,
                req.prompt.size + req.max_new_tokens + 1))
        if not self._active:
            return
        slots = sorted(self._active)
        reqs = [self._active[s] for s in slots]
        n = len(slots)
        nb = _pow2ceil(n)
        want = [spec.controller.draft_len(r) for r in reqs]
        try:
            if self._fi is not None and self._fi.fire("drafter-corruption"):
                if self._fi.param("drafter-corruption", "corrupt", 0.0):
                    # corrupt the PROPOSALS, not the drafter: acceptance
                    # only ever keeps tokens matching the target, so this
                    # proves rejection absorbs garbage drafts
                    drafts, dlen = spec.drafter.propose(
                        self, slots, reqs, want, k)
                    drafts = ((np.asarray(drafts) + 1)
                              % self.cfg.vocab_size).astype(np.int32)
                else:
                    raise InjectedFault("injected drafter fault")
            else:
                drafts, dlen = spec.drafter.propose(self, slots, reqs,
                                                    want, k)
            self._watchdog.note_drafter_ok()
        except Exception as e:
            # drafter fault fallback: draft NOTHING this step (vanilla-
            # equivalent), reset the drafter's private cache, let the
            # watchdog decide whether spec should stay on
            spec.note_drafter_fault(e)
            self._watchdog.note_drafter_fault()
            drafts = np.zeros((nb, k), np.int32)
            dlen = np.zeros((n,), np.int32)
        tables_c = np.zeros((nb, self.max_pages_per_seq), np.int32)
        lengths_c = np.zeros((nb,), np.int32)
        last_c = np.zeros((nb,), np.int32)
        temps_c = np.zeros((nb,), np.float32)
        keys_c = np.zeros((nb, 2), np.uint32)
        dlen_c = np.zeros((nb,), np.int32)
        tables_c[:n] = self.tables[slots]
        lengths_c[:n] = self.lengths[slots]
        last_c[:n] = self._last_tok[slots]
        temps_c[:n] = self._temps[slots]
        keys_c[:n] = self._keys[slots]
        dlen_c[:n] = dlen
        sampling = bool(np.any(temps_c > 0.0))
        verify = spec.get_verify(nb, sampling)
        if self._m is not None:
            self._m.decode_batch.observe(n)
            # the verify program rides the fused verify/suffix slab
            # attention path (ISSUE 9) — count the dispatch
            self._m.slab_dispatch.labels(path="verify").inc()
        # ONE dispatch scores every draft position; the fetch below is
        # the step's only blocking sync besides admission
        toks_d, nem_d, len_d, keys_d, bad_d, pages = verify(
            self._params, self._pages_flat(), jnp.asarray(tables_c),
            jnp.asarray(lengths_c), jnp.asarray(last_c),
            jnp.asarray(drafts), jnp.asarray(dlen_c),
            jnp.asarray(temps_c), jnp.asarray(keys_c))
        self._set_pages(pages)
        toks, nem, lengths_h, keys_h, bad_h = (
            np.asarray(a) for a in jax.device_get(
                (toks_d, nem_d, len_d, keys_d, bad_d)))
        step_proposed = step_accepted = 0
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            try:
                if self._fi is not None:
                    if self._fi.fire("step-exception", rid=req.rid):
                        raise InjectedFault(
                            f"injected step fault (rid {req.rid})")
                    if self._fi.fire("nan-logits", rid=req.rid):
                        raise NumericsError(
                            "injected non-finite logits", rid=req.rid)
                if bad_h[i]:
                    raise NumericsError(
                        "non-finite logits in verify block", rid=req.rid)
                n_emit = int(nem[i])
                accepted = n_emit - 1  # drafts accepted (bonus is free)
                consumed = self._harvest(req, toks[i, :n_emit].tolist())
                spec.note(req, proposed=int(dlen[i]), accepted=accepted,
                          landed=consumed)
                step_proposed += int(dlen[i])
                step_accepted += min(accepted, int(dlen[i]))
                if req.done:
                    # eos/budget mid-block: _harvest truncated the
                    # accepted block at the boundary; freeing the slot
                    # recycles every page — INCLUDING rows past the eos —
                    # the same step (ISSUE 5 satellite)
                    del self._active[slot]
                    self._free_slot(slot)
                    req.slot = None
                    spec.drafter.release(slot)
                    spec.controller.forget(req)
                else:
                    # keep exactly the accepted prefix: lengths rolls
                    # back to base + 1 + accepted (computed in-program)
                    # and the headroom pages — rejected draft rows
                    # included — return to the pool
                    self.lengths[slot] = int(lengths_h[i])
                    self._last_tok[slot] = int(toks[i, n_emit - 1])
                    self._keys[slot] = keys_h[i]
                    self._trim_pages(slot, int(lengths_h[i]))
            except RequestError as e:
                self._fail_request(req, e)
            except Exception as e:
                self._fail_request(req, self._wrap_step_fault(e, req))
        spec.observe_step(time.perf_counter() - t0)
        # acceptance-collapse detection: a full window of near-zero
        # acceptance means drafting burns a dispatch per step for
        # nothing — the watchdog degrades spec→vanilla, probes back later
        self._watchdog.note_acceptance(step_proposed, step_accepted)

    def run(self, requests=None) -> List[Request]:
        """Serve ``requests`` (or whatever is queued) to completion.
        A quarantined engine (integrity fail-stop, ISSUE 14) returns
        early with work still live — ``step()`` is a no-op there, and
        spinning on it would never terminate; the multi-replica router
        is the layer that finishes those streams elsewhere."""
        if requests:
            done = list(requests)
        else:
            done = list(self._queue)
        while self.step():
            if self._watchdog.quarantined:
                break
        return done
