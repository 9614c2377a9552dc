"""paddle_tpu.inference.spec — speculative decoding for the paged engine.

ISSUE 5 tentpole: decode is memory-bound (the PR 4 roofline pass
confirmed each step streams ALL weight bytes to emit one token per
sequence), so the step's cost is nearly flat in how many positions it
scores. Speculative decoding amortizes the weight stream over k+1
positions per step: a cheap **drafter** proposes k tokens, one batched
**verifier** forward through the existing paged decode path scores every
position at once, and an **acceptance** rule keeps the usable prefix —
token-exact argmax matching for greedy requests (output provably
identical to vanilla decode), distribution-preserving rejection sampling
for temperature > 0. Rejected rows roll back through the engine's page
allocator (``_trim_pages``), so preemption/eviction invariants hold.

Wiring: ``Engine(model, spec="ngram"|"draft", spec_k=4,
draft_model=...)`` — see ``Engine._spec_step`` for the scheduling loop
and README "Speculative decoding" for semantics and flags.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Set, Tuple

from .acceptance import accept_tokens
from .controller import AdaptiveDraftController
from .drafter import DraftModelDrafter, NgramDrafter
from .verifier import make_verify_fn

__all__ = ["SpecDecoder", "NgramDrafter", "DraftModelDrafter",
           "AdaptiveDraftController", "accept_tokens", "make_verify_fn"]


class _SpecMetrics:
    """Spec observability bundle (ISSUE 5 satellite): registered only
    when spec decoding is ON, so vanilla engines keep their scrape
    unchanged. All recording is host code between dispatches."""

    def __init__(self, drafter_name: str):
        from ...observability import SIZE_BUCKETS, counter, histogram

        self.proposed = counter(
            "paddle_tpu_spec_proposed_total",
            "draft tokens proposed to the verifier",
            labelnames=("drafter",)).labels(drafter=drafter_name)
        self.accepted = counter(
            "paddle_tpu_spec_accepted_total",
            "draft tokens accepted by the verifier",
            labelnames=("drafter",)).labels(drafter=drafter_name)
        self.draft_len = histogram(
            "paddle_tpu_spec_draft_len",
            "drafts proposed per request per verify step",
            buckets=SIZE_BUCKETS)
        self.tokens_per_step = histogram(
            "paddle_tpu_spec_tokens_per_verify_step",
            "tokens landed per request per verify step (1 + accepted)",
            buckets=SIZE_BUCKETS)
        self.drafter_faults = counter(
            "paddle_tpu_spec_drafter_faults_total",
            "drafter proposals that raised (step fell back to zero "
            "drafts — vanilla-equivalent)",
            labelnames=("drafter",)).labels(drafter=drafter_name)


class SpecDecoder:
    """Engine-side spec-decode state: the drafter, the per-request
    adaptive controller, the compiled verify programs, and the rolling
    stats the Prometheus scrape reports."""

    def __init__(self, engine, mode: str, k: int = 4, draft_model=None,
                 max_ngram: int = 3, min_ngram: int = 1):
        if mode == "ngram":
            self.drafter = NgramDrafter(max_ngram=max_ngram,
                                        min_ngram=min_ngram)
        elif mode == "draft":
            if draft_model is None:
                raise ValueError(
                    'spec="draft" needs draft_model=<small causal LM '
                    "sharing the target's vocab>")
            self.drafter = DraftModelDrafter(draft_model, engine)
        else:
            raise ValueError(
                f"spec={mode!r}: expected 'ngram' or 'draft' (or "
                "None/'off' for vanilla decode)")
        # the verify block (k+1 rows) must fit the chunk_size headroom
        # add_request reserves below max_position, so positions never
        # outrun the page tables even at a request's budget edge
        self.k = max(1, min(int(k), engine.chunk_size))
        self.engine = engine
        self.controller = AdaptiveDraftController(self.k)
        self._verify_raw: Dict[bool, object] = {}
        self._verify_fns: Dict[bool, object] = {}
        self._seen_shapes: Set[Tuple[int, int, bool]] = set()
        self._m: Optional[_SpecMetrics] = (
            _SpecMetrics(self.drafter.name)
            if engine._m is not None else None)
        # rolling totals for the adaptive-depth export
        self.verify_steps = 0      # verify dispatches
        self.request_steps = 0     # per-request verify rows harvested
        self.tokens_landed = 0     # tokens delivered via spec steps
        self.drafts_proposed = 0
        self.drafts_accepted = 0
        self.drafter_faults = 0    # proposals that raised (ISSUE 6)
        self.last_drafter_fault = None
        self.wall_seconds = 0.0    # _spec_step wall covered by the above

    # ---------------------------------------------------------- programs
    def get_verify(self, nb: int, sampling: bool):
        fn = self._verify_fns.get(sampling)
        if fn is None:
            raw = make_verify_fn(self.engine, sampling)
            # the model-runner wraps (jit, plus shard_map at tp>1 —
            # verify rides the same sharded weights/pool as decode)
            fn = self.engine.runner.wrap_verify(raw)
            self._verify_fns[sampling] = fn
        shape = (nb, self.k, sampling)
        if shape not in self._seen_shapes:
            self._seen_shapes.add(shape)
            if self.engine._m is not None:
                self.engine._m.compiled.labels(kind="verify").inc()
        return fn

    # ------------------------------------------------------- accounting
    def note(self, req, proposed: int, accepted: int, landed: int):
        """Per-request post-harvest bookkeeping for one verify row."""
        self.controller.update(req, proposed, accepted)
        self.request_steps += 1
        self.tokens_landed += landed
        self.drafts_proposed += proposed
        self.drafts_accepted += min(accepted, proposed)
        if self._m is not None:
            if proposed:
                self._m.proposed.inc(proposed)
                self._m.accepted.inc(min(accepted, proposed))
            self._m.draft_len.observe(proposed)
            self._m.tokens_per_step.observe(landed)

    def observe_step(self, wall: float):
        self.verify_steps += 1
        self.wall_seconds += wall

    def note_drafter_fault(self, exc: BaseException):
        """Drafter raised (ISSUE 6): count it and reset the drafter's
        private cache so the next proposal re-syncs every slot from the
        request's host-side token history — the slot-reconciliation-
        after-failure contract. ``reset()`` never raises by contract."""
        self.drafter_faults += 1
        self.last_drafter_fault = exc
        self.drafter.reset()
        if self._m is not None:
            self._m.drafter_faults.inc()

    def stats(self) -> dict:
        """Rolling summary: mean landed tokens per request-row per verify
        step, draft acceptance rate, measured spec ms/token."""
        return {
            "drafter": self.drafter.name,
            "k": self.k,
            "verify_steps": self.verify_steps,
            "tokens_landed": self.tokens_landed,
            "accept_per_step": (
                self.tokens_landed / self.request_steps
                if self.request_steps else 0.0),
            "accept_rate": (
                self.drafts_accepted / self.drafts_proposed
                if self.drafts_proposed else 0.0),
            "drafter_faults": self.drafter_faults,
            "spec_ms_per_token": (
                1e3 * self.wall_seconds / self.tokens_landed
                if self.tokens_landed else 0.0),
        }
