"""Pluggable drafters for speculative decoding (ISSUE 5 tentpole, part 1).

Two implementations of the same contract — ``propose(engine, slots,
reqs, want, k)`` returns ``(drafts, dlen)`` where ``drafts`` is a
``[pow2ceil(n), k]`` int32 array (host numpy or device jnp — the verify
program takes either) aligned with the sorted-slot batch order and
``dlen[i] <= k`` counts the valid proposals per row:

* ``NgramDrafter`` — model-free prompt lookup (PLD / n-gram): match the
  request's most recent n-gram earlier in its own prompt+generation
  history and propose the tokens that followed. Pure host numpy, zero
  extra dispatches, works on any model including the tiny test configs —
  and is remarkably effective on repetitive continuations (exactly what
  memory-bound decode serves a lot of: code, templated text, and — on
  the untrained tiny models — greedy repetition loops).
* ``DraftModelDrafter`` — a small causal LM drafts k tokens by greedy
  chained decode over ITS OWN paged KV pool (same page/table machinery
  as the engine, one jitted k-step scan per proposal). The draft cache
  tracks the target's accepted history by construction: before each
  proposal, ``_sync`` reconciles the per-slot draft cache against the
  request's host-side token history — rolling back rejected draft rows,
  appending catch-up tokens through a verify-mode forward (full-context
  attention, logits discarded — prefill-window attention would compute
  WRONG deep-layer k/v over a non-empty cache), and re-prefilling from
  scratch after preemption or slot reuse. No callbacks needed: the sync
  derives everything from ``(rid, cached_len)`` vs the request state.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np

__all__ = ["NgramDrafter", "DraftModelDrafter"]


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _history(req) -> np.ndarray:
    """The request's full token history: prompt + everything generated
    (INCLUDING the current last token — drafting continues from it)."""
    if req.tokens:
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
    return np.asarray(req.prompt, np.int32)


class NgramDrafter:
    """Prompt-lookup drafting: propose the continuation of the latest
    earlier occurrence of the current tail n-gram, longest n first."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError("need 1 <= min_ngram <= max_ngram")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def _lookup(self, ctx: np.ndarray, want: int) -> np.ndarray:
        L = ctx.size
        if want <= 0 or L < self.min_ngram + 1:
            return np.zeros((0,), np.int32)
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            pat = ctx[L - n:]
            windows = np.lib.stride_tricks.sliding_window_view(ctx, n)
            hits = np.nonzero((windows == pat).all(axis=1))[0]
            # earlier occurrences only (the tail n-gram matches itself),
            # with at least one continuation token
            hits = hits[hits <= L - n - 1]
            if not hits.size:
                continue
            # prefer the LATEST hit whose continuation window is FULL:
            # in a repetition run the latest hit sits flush against the
            # end of context and would truncate the proposal to a token
            # or two — exactly the regime where a full-width proposal
            # all lands. Fall back to the latest hit otherwise.
            full = hits[hits <= L - n - want]
            j = int(full[-1] if full.size else hits[-1]) + n
            return ctx[j:j + want].astype(np.int32)
        return np.zeros((0,), np.int32)

    def propose(self, engine, slots, reqs, want, k):
        n = len(reqs)
        drafts = np.zeros((_pow2ceil(max(n, 1)), k), np.int32)
        dlen = np.zeros((n,), np.int32)
        for i, req in enumerate(reqs):
            got = self._lookup(_history(req), min(int(want[i]), k))
            drafts[i, :got.size] = got
            dlen[i] = got.size
        return drafts, dlen

    def release(self, slot):  # stateless
        pass

    def reset(self):  # stateless; part of the drafter fault contract —
        pass          # reset() must never raise (engine calls it bare)


class DraftModelDrafter:
    """Draft with a small causal LM over its own paged KV pool."""

    name = "draft"

    def __init__(self, model, engine):
        cfg = model.config
        if cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError(
                f"draft model vocab ({cfg.vocab_size}) must match the "
                f"target's ({engine.cfg.vocab_size})")
        self.model = model
        self.cfg = cfg
        self.page_size = engine.page_size
        self.num_pages = engine.num_pages
        self.max_pages_per_seq = min(engine.max_pages_per_seq,
                                     cfg.max_position // engine.page_size)
        self.dtype = engine.dtype
        import jax.numpy as jnp

        n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
        shape = (self.num_pages, self.page_size, n_kv * cfg.head_dim)
        self.k_pages = [jnp.zeros(shape, self.dtype)
                        for _ in range(cfg.num_layers)]
        self.v_pages = [jnp.zeros(shape, self.dtype)
                        for _ in range(cfg.num_layers)]
        # host allocator mirrors the engine's: page 0 is the trash page
        self.tables = np.zeros((engine.max_slots, self.max_pages_per_seq),
                               np.int32)
        self.lengths = np.zeros((engine.max_slots,), np.int32)
        self._free_pages = list(range(self.num_pages - 1, 0, -1))
        # prefix caching (ISSUE 8): the drafter runs the engine's
        # refcount+cache machinery over its OWN pool (draft-model KV is
        # different content, so it needs its own index), enabled iff the
        # engine's cache is — a re-prefill after preemption/slot reuse
        # then splices cached draft pages instead of recomputing them
        self._page_ref = np.zeros((self.num_pages,), np.int32)
        if getattr(engine, "_pcache", None) is not None:
            from ..prefix_cache import PrefixCache

            self._pcache = PrefixCache(self.page_size)
        else:
            self._pcache = None
        self._slot_rid = np.full((engine.max_slots,), -1, np.int64)
        self._last = np.zeros((engine.max_slots,), np.int32)
        self._swap = [p for _, p in model.named_parameters()]
        self._swap += [b for _, b in model.named_buffers() if b is not None]
        self._params = [t._data for t in self._swap]
        self._propose_fns: Dict[int, object] = {}  # k -> jitted scan
        self._catchup_fn = None

    # ------------------------------------------------------- allocator
    def _pages_needed(self, length):
        return (int(length) + self.page_size - 1) // self.page_size

    def _alloc_page(self):
        """Free list first, then LRU-evict an idle cached draft page —
        the drafter twin of ``Engine._alloc_page``."""
        if self._free_pages:
            page = self._free_pages.pop()
        elif self._pcache is not None:
            page = self._pcache.evict_lru(self._page_ref)
            if page is None:
                return None
        else:
            return None
        self._page_ref[page] = 1
        return page

    def _release_page(self, page):
        page = int(page)
        if page <= 0:
            return
        ref = int(self._page_ref[page]) - 1
        assert ref >= 0, f"draft page {page} refcount went negative"
        self._page_ref[page] = ref
        if ref == 0 and not (self._pcache is not None
                             and self._pcache.contains_page(page)):
            self._free_pages.append(page)

    def _ensure_pages(self, slot, new_len) -> bool:
        need = min(self._pages_needed(new_len), self.max_pages_per_seq)
        have = int(np.count_nonzero(self.tables[slot]))
        taken: List[int] = []
        for i in range(have, need):
            page = self._alloc_page()
            if page is None:
                for j in range(have, have + len(taken)):
                    self.tables[slot, j] = 0
                for pg in reversed(taken):
                    self._release_page(pg)
                return False
            taken.append(page)
            self.tables[slot, i] = page
        return True

    def _trim_pages(self, slot, keep_len):
        need = self._pages_needed(keep_len)
        have = int(np.count_nonzero(self.tables[slot]))
        for i in range(have - 1, need - 1, -1):
            self._release_page(int(self.tables[slot, i]))
            self.tables[slot, i] = 0

    def release(self, slot):
        """Forget a slot (request finished / preempted / slot reused).
        Refcount-aware: cached draft pages stay resident at refcount 0."""
        for p in self.tables[slot]:
            if p:
                self._release_page(int(p))
        self.tables[slot, :] = 0
        self.lengths[slot] = 0
        self._slot_rid[slot] = -1

    def reset(self):
        """Drop ALL drafter state and rebuild the page buffers (ISSUE 6:
        slot reconciliation after a drafter fault or engine pool reset).
        Safe because ``_sync`` re-prefills any slot whose cache doesn't
        match the request's host-side history — which after this is
        every slot. Must never raise."""
        import jax.numpy as jnp

        n_kv = getattr(self.cfg, "num_kv_heads", self.cfg.num_heads)
        shape = (self.num_pages, self.page_size, n_kv * self.cfg.head_dim)
        self.k_pages = [jnp.zeros(shape, self.dtype)
                        for _ in range(self.cfg.num_layers)]
        self.v_pages = [jnp.zeros(shape, self.dtype)
                        for _ in range(self.cfg.num_layers)]
        self.tables[:] = 0
        self.lengths[:] = 0
        self._free_pages = list(range(self.num_pages - 1, 0, -1))
        # cached content died with the buffers: flush (stale-pointer
        # safety, same contract as Engine._reset_pool)
        self._page_ref[:] = 0
        if self._pcache is not None:
            self._pcache.clear()
        self._slot_rid[:] = -1

    # ------------------------------------------------------ jit bodies
    def _states_from(self, pages_flat, tables, lengths, verify=False):
        from ...ops.pallas.paged_attention import PagedCacheState

        L = self.cfg.num_layers
        return [PagedCacheState(pages_flat[i], pages_flat[L + i], None,
                                tables, lengths, self.page_size,
                                verify=verify)
                for i in range(L)]

    @staticmethod
    def _pages_of(states):
        return [st.k_pages for st in states] + [st.v_pages for st in states]

    def _pages_flat(self):
        return list(self.k_pages) + list(self.v_pages)

    def _set_pages(self, pages_flat):
        L = self.cfg.num_layers
        self.k_pages = list(pages_flat[:L])
        self.v_pages = list(pages_flat[L:2 * L])

    def _get_catchup(self):
        """Verify-mode forward that only WRITES: appends each row's delta
        tokens to the draft cache with full-context attention (correct
        deep-layer k/v) and discards the logits."""
        if self._catchup_fn is not None:
            return self._catchup_fn
        import jax
        import jax.numpy as jnp

        drafter, dmodel = self, self.model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def draft_catchup(params, pages_flat, tables, lengths, ids, delta):
            from ...framework.tensor import Tensor, pause_tape
            from ...jit import swapped_tensors

            with swapped_tensors(drafter._swap, params), pause_tape():
                states = drafter._states_from(pages_flat, tables, lengths,
                                              verify=True)
                _, new_states = dmodel.forward(Tensor._wrap(ids),
                                               caches=states)
                # rows past each slot's true delta are garbage the next
                # write overwrites; lengths advances by delta only
                return (drafter._pages_of(new_states), lengths + delta)

        self._catchup_fn = draft_catchup
        return draft_catchup

    def _get_propose(self, k):
        """k greedy decode steps as ONE jitted scan (the draft-side twin
        of ``Engine._get_decode`` at chunk depth k)."""
        fn = self._propose_fns.get(k)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        drafter, dmodel = self, self.model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def draft_propose(params, pages_flat, tables, lengths, last_tok):
            from ...framework.tensor import Tensor, pause_tape
            from ...jit import swapped_tensors

            with swapped_tensors(drafter._swap, params), pause_tape():
                def body(carry, _):
                    pages_flat, lengths, last = carry
                    states = drafter._states_from(pages_flat, tables,
                                                  lengths)
                    logits, new_states = dmodel.forward(
                        Tensor._wrap(last[:, None]), caches=states)
                    lg = (logits._data if isinstance(logits, Tensor)
                          else logits)
                    nxt = jnp.argmax(lg[:, -1].astype(jnp.float32),
                                     axis=-1).astype(jnp.int32)
                    return ((drafter._pages_of(new_states),
                             new_states[0].lengths, nxt), nxt)

                (pages_flat, lengths, _), toks = jax.lax.scan(
                    body, (pages_flat, lengths, last_tok), None, length=k)
            return jnp.swapaxes(toks, 0, 1), pages_flat, lengths

        self._propose_fns[k] = draft_propose
        return draft_propose

    # -------------------------------------------------------- proposal
    def _sync(self, slots, reqs):
        """Reconcile each slot's draft cache with the request's accepted
        history. Returns the catch-up rows [(slot, delta_tokens)]. The
        draft cache invariant matches the engine's: it holds the full
        context EXCEPT the current last token (whose k/v the next
        propose scan appends)."""
        rows = []
        for slot, req in zip(slots, reqs):
            hist = _history(req)
            expected = hist.size - 1  # everything but the last token
            if int(self._slot_rid[slot]) != req.rid:
                self.release(slot)
                self._slot_rid[slot] = req.rid
                if self._pcache is not None and expected > 0:
                    # re-prefill (admission / preemption / slot reuse)
                    # hits the draft-side prefix cache too (ISSUE 8):
                    # splice the cached block-aligned prefix so the
                    # catch-up forward only computes the uncached tail.
                    # matched is block-aligned and <= expected, so the
                    # catch-up/propose writes land past every shared page
                    pages, matched = self._pcache.lookup(hist[:expected])
                    for i, p in enumerate(pages):
                        self.tables[slot, i] = p
                        self._page_ref[p] += 1
                    self.lengths[slot] = matched
            cached = int(self.lengths[slot])
            if cached > expected:
                # roll back past-propose rows the verifier rejected
                self.lengths[slot] = expected
                self._trim_pages(slot, expected)
                cached = expected
            if cached < expected:
                rows.append((slot, hist[cached:expected]))
            self._last[slot] = hist[-1]
        return rows

    def propose(self, engine, slots, reqs, want, k):
        import jax
        import jax.numpy as jnp

        n = len(slots)
        nb = _pow2ceil(max(n, 1))
        dlen = np.asarray([min(int(w), k) for w in want], np.int32)
        sync_rows = self._sync(slots, reqs)
        # ---- catch-up wave (admission/preemption/bonus-token deltas) ----
        # A slot the draft pool can't grow is RELEASED outright (tables
        # zeroed → its propose-scan row writes to the trash page and
        # stays idle): proposing over a half-synced cache would leave
        # stale k/v behind the rollback watermark — silent corruption.
        degraded = set()
        rows = []
        for s, d in sync_rows:
            if self._ensure_pages(s, int(self.lengths[s]) + d.size):
                rows.append((s, d))
            else:
                self.release(s)
                degraded.add(s)
        if rows:
            width = _pow2ceil(max(d.size for _, d in rows))
            rb = _pow2ceil(len(rows))
            ids = np.zeros((rb, width), np.int32)
            tables_c = np.zeros((rb, self.max_pages_per_seq), np.int32)
            lengths_c = np.zeros((rb,), np.int32)
            delta_c = np.zeros((rb,), np.int32)
            for i, (s, d) in enumerate(rows):
                ids[i, :d.size] = d
                tables_c[i] = self.tables[s]
                lengths_c[i] = self.lengths[s]
                delta_c[i] = d.size
            pages, new_len = self._get_catchup()(
                self._params, self._pages_flat(), jnp.asarray(tables_c),
                jnp.asarray(lengths_c), jnp.asarray(ids),
                jnp.asarray(delta_c))
            self._set_pages(pages)
            for i, (s, _) in enumerate(rows):
                self.lengths[s] = int(lengths_c[i] + delta_c[i])
        if self._pcache is not None:
            # publish every synced slot's full draft-KV blocks (content-
            # addressed, so a future re-prefill of the same history — or
            # another request sharing the template — splices them)
            for s, req in zip(slots, reqs):
                if s in degraded:
                    continue
                hist = _history(req)
                full = int(self.lengths[s]) // self.page_size
                if full:
                    self._pcache.register(
                        hist[:full * self.page_size],
                        [int(self.tables[s, i]) for i in range(full)])
        # ---- propose scan: k greedy steps for the whole batch ----------
        for i, s in enumerate(slots):
            if s not in degraded and not self._ensure_pages(
                    s, int(self.lengths[s]) + k):
                self.release(s)
                degraded.add(s)
            if s in degraded:
                dlen[i] = 0  # draft pool pressure: degrade, don't stall
        tables_c = np.zeros((nb, self.max_pages_per_seq), np.int32)
        lengths_c = np.zeros((nb,), np.int32)
        last_c = np.zeros((nb,), np.int32)
        for i, s in enumerate(slots):
            tables_c[i] = self.tables[s]
            lengths_c[i] = self.lengths[s]
            last_c[i] = self._last[s]
        drafts, pages, new_len = self._get_propose(k)(
            self._params, self._pages_flat(), jnp.asarray(tables_c),
            jnp.asarray(lengths_c), jnp.asarray(last_c))
        self._set_pages(pages)
        new_len = np.asarray(jax.device_get(new_len))
        for i, s in enumerate(slots):
            self.lengths[s] = int(new_len[i])
        # drafts stay on device: the verify program consumes them directly
        return drafts, dlen
