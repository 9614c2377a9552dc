"""Host-DRAM spill tier under the paged KV pool (ISSUE 15 tentpole).

At millions-of-users scale the prefix cache (ISSUE 8) is HBM-bound:
refcount-0 cached pages are evicted leaf-first exactly when the working
set outgrows the paged pool, throwing away the reuse that makes caching
pay. Mooncake-style KV tiering and vLLM's paged swapping show the fix —
host DRAM is ~100x HBM for KV purposes, and one PCIe/ICI page copy is
far cheaper than recomputing the page's prefill FLOPs — so eviction
becomes DEMOTION and a later hash-chain hit becomes PROMOTION:

* **Demote (device→host, async).** When the allocator reclaims an idle
  cached page, the engine thread dispatches a tiny jitted gather of that
  page's bytes out of every layer's K/V (and scale) buffer into fresh
  arrays (``ModelRunner.capture_pages`` — an async dispatch, never a
  sync) and hands the handles to the background spill worker. The
  worker — the ONLY place in the serving stack allowed to block on a
  device→host page transfer (tpulint TPL1101 enforces this) — fetches
  the bytes, records a blake2b digest over them, and writes them into
  its host slab row. The physical page was surrendered to the new owner
  the moment the gather was dispatched, so demotion never delays an
  allocation; the prefix-cache entry rides ``spilling → host``.
* **Promote (host→device, async, checksum-verified).** A lookup that
  matches into demoted blocks cannot splice them (their device bytes
  are gone) — the request rides partial prefill for that suffix, a
  MISS, never a stall — but it queues a promote: the worker re-reads
  the slab row, re-hashes it against the digest recorded at demotion
  (a bit flipped while the page sat in host DRAM — the
  ``kv-spill-corrupt`` fault point — fails here and costs an
  invalidate + recompute, never a token), and posts the verified
  payload. The engine thread then allocates a device page and restores
  the bytes with one batched ``_copy_pages``-style donated dispatch
  (``ModelRunner.restore_pages``), re-binds the entry to it, and — when
  the integrity sentinel is armed — re-adopts the page's device-side
  checksum so the ISSUE 14 splice-time probe keeps guarding promoted
  pages exactly like never-demoted ones.
* **Recompute-as-promote.** If a request recomputes a demoted block
  before its promotion lands (the common first-touch race), harvest-
  time registration re-binds the entry to the freshly computed page
  and the in-flight promotion is discarded by its job token — both
  paths converge on identical bytes, so streams are bit-identical
  tier-on vs tier-off by construction.

All prefix-cache and allocator state stays engine-thread-only: the
worker communicates exclusively through the job queue (in) and the
completion deque (out, drained by the engine thread at step / admission
boundaries). The host slab is worker-owned; a slab row is written only
by the spill job that was assigned it and read only by promote jobs,
and jobs are FIFO, so no row is ever touched by two jobs concurrently.

Lifecycle: ``reset()`` (pool reset after an engine-scoped fault) drops
the WHOLE tier — host copies describe trust established before the
fault, and the recompute policy makes them free to re-earn — and
``stop()`` (frontend drain/shutdown, replica quarantine/restart) ends
the worker thread so a restarted replica never inherits a stale spill
pipeline.
"""
from __future__ import annotations

import hashlib
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

__all__ = ["HostTier", "capture_handoff_spill"]

# capture/restore waves use one fixed index width (mirrors
# HostTier.COPY_WIDTH): a per-wave width would mint a fresh XLA program
# per distinct size
_HANDOFF_COPY_WIDTH = 32


def capture_handoff_spill(engine, tokens) -> Optional[dict]:
    """Capture the prompt's cached KV pages into a host-side handoff
    slab (ISSUE 20): the cross-replica twin of the demotion capture
    above. Engine thread; BLOCKS on the device→host fetch — the name
    carries the ``spill`` hint because this is a deliberate second
    blocking-copy site (tpulint TPL1101), invoked only on the cluster's
    dedicated handoff thread via ``ServingFrontend.call``, never from
    the scheduling loop.

    Returns the wire payload — per-page buffer rows in ``pages_flat``
    order plus a per-page blake2b digest (chain-contiguous from the
    root, so the importer can truncate at the first mismatch) and the
    integrity sentinel's device-side sums — or ``None`` when nothing is
    cached for the prompt (the caller falls back to recompute). Only
    the HBM-resident chain prefix ships: host-tier tails would need a
    promote round trip that costs more than the recompute they save."""
    import jax
    import jax.numpy as jnp

    coord = getattr(engine, "_cache", None)
    pc = getattr(engine, "_pcache", None)
    if coord is None or pc is None:
        return None
    pages, matched = pc.lookup(tokens, touch=False)
    if not pages:
        return None
    ps = int(pc.page_size)
    ig = getattr(engine, "_integrity", None)
    w = _HANDOFF_COPY_WIDTH
    rows_per_page: List[List[np.ndarray]] = []
    for off in range(0, len(pages), w):
        chunk = pages[off:off + w]
        idx = np.zeros((w,), np.int32)
        idx[:len(chunk)] = chunk
        handles = engine.runner.capture_pages(coord.pages_flat(),
                                              jnp.asarray(idx))
        arrays = [np.asarray(jax.device_get(h)) for h in handles]
        for j in range(len(chunk)):
            rows_per_page.append([np.array(a[j]) for a in arrays])
    digests, nbytes = [], 0
    for rows in rows_per_page:
        d = hashlib.blake2b(digest_size=16)
        for a in rows:
            d.update(a.tobytes())
            nbytes += a.nbytes
        digests.append(d.hexdigest())
    dev_sums = [None if ig is None else ig.sum_of_page(p) for p in pages]
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    return {
        "tokens": [int(t) for t in toks[:matched]],
        "page_size": ps,
        "digests": digests,
        "pages": rows_per_page,
        "dev_sums": dev_sums,
        "nbytes": int(nbytes),
    }


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class HostTier:
    """Background host-DRAM spill tier; see module docstring. Owned by
    the :class:`~paddle_tpu.inference.cache_coord.CacheCoordinator`;
    every public method except the worker loop runs on the engine
    thread."""

    def __init__(self, coord, host_pages: int):
        self.coord = coord
        self.engine = coord.engine
        self.host_pages = int(host_pages)
        self._free_hslots: List[int] = list(range(self.host_pages - 1,
                                                  -1, -1))
        self._digest: Dict[int, bytes] = {}    # hslot -> blake2b digest
        self._dev_sum: Dict[int, float] = {}   # hslot -> sentinel sum
        self._gen = 0                          # bumped by reset()
        self._slabs: Optional[List[np.ndarray]] = None  # worker-owned
        self._q: "queue.Queue" = queue.Queue()
        self._done: deque = deque()            # worker -> engine thread
        self._done_evt = threading.Event()     # set on every completion
        self._pending: List = []               # demotions awaiting capture
        self._stopped = False
        # plain-int telemetry (mirrored into the metrics registry by the
        # record sites below; kept here so tests/benches can read the
        # tier's story without a scrape)
        self.demotions = 0   # pages spilled device -> host
        self.promotions = 0  # pages restored host -> device
        self.hits = 0        # lookups that reached host-tier content
        self.drops = 0       # demoted blocks lost (capacity/corruption)
        pc = coord.pcache
        pc.owner_release = self.release_entry
        self._worker = threading.Thread(
            target=self._worker_loop, name="paddle-kv-spill", daemon=True)
        self._worker.start()

    # ------------------------------------------------------------ metrics
    @property
    def _m(self):
        # getattr: the coordinator (and its construction-time reset)
        # builds before the engine's metrics bundle exists
        return getattr(self.engine, "_m", None)

    def _update_occupancy(self):
        m = self._m
        if m is not None:
            m.kv_tier_pages.labels(tier="host").set(
                self.host_pages - len(self._free_hslots))
            m.kv_tier_pages.labels(tier="hbm").set(
                self.coord.pcache.n_pages)

    # ----------------------------------------------------- engine thread
    def demote(self, page: int, ent) -> None:
        """Queue ``ent``'s spill: its bytes are still resident in device
        page ``page``, which the allocator is handing to a new owner.
        Nothing is dispatched here — demotions accumulate and ONE
        batched capture gather goes out in :meth:`flush_captures`,
        which every dispatch path triggers through
        ``CacheCoordinator.pages_flat()`` BEFORE any program could
        overwrite the page (the ``_flush_cow`` idiom). When the host
        tier itself is full and nothing in it is droppable, the block
        is dropped outright (counted; exactly what the un-tiered cache
        did on every eviction)."""
        hslot = self._alloc_hslot()
        if hslot is None:
            self.drops += 1
            if self._m is not None:
                self._m.kv_drops.inc()
            # no host room: the demotion degenerates to the classic
            # eviction — remove the entry (and any stranded descendants)
            self._drop_entry(ent)
            return
        dev_sum = None
        ig = getattr(self.engine, "_integrity", None)
        if ig is not None:
            # the sentinel's device-side checksum travels with the bytes
            # so a verified promotion can re-adopt it (ISSUE 14 probes
            # keep covering the page after its round trip); read NOW —
            # the allocator forgets it the moment the page re-homes
            dev_sum = ig.sum_of_page(page)
        ent.hslot = hslot
        self.demotions += 1
        if self._m is not None:
            self._m.kv_demotions.inc()
        self._pending.append((int(page), ent, ent.job, hslot, dev_sum))
        self._update_occupancy()

    # capture/restore dispatches use ONE fixed index width (padded with
    # page 0, the trash page; longer waves chunk): a per-wave pow2 width
    # would mint a fresh XLA program per distinct size, and on the
    # single-core smoke host every such compile is tens of ms landing
    # straight in the serving path (memory: one cold compile ≈ 1 s in
    # p99). Two programs total — one gather, one scatter — forever.
    COPY_WIDTH = 32

    def flush_captures(self, pages_list) -> None:
        """Dispatch batched page-gathers for every queued demotion
        (engine thread; ``pages_list`` is the coordinator's CURRENT
        buffer list, passed raw to avoid recursing through
        ``pages_flat``). Async: the worker gets device handles, the
        engine thread never blocks."""
        if not self._pending:
            return
        import jax.numpy as jnp

        batch, self._pending = self._pending, []
        w = self.COPY_WIDTH
        for off in range(0, len(batch), w):
            chunk = batch[off:off + w]
            idx = np.zeros((w,), np.int32)
            idx[:len(chunk)] = [p for p, *_ in chunk]
            handles = self.engine.runner.capture_pages(pages_list,
                                                       jnp.asarray(idx))
            self._q.put(("spill", self._gen,
                         [(ent, token, hslot, dev_sum)
                          for _, ent, token, hslot, dev_sum in chunk],
                         handles))

    def request_promote(self, entries) -> None:
        """Queue async promote-backs for host-resident entries a lookup
        just matched (the hash-chain hit on demoted pages). Entries
        mid-spill or already promoting are left alone — their in-flight
        job is the promotion. Never blocks; the requesting admission
        rides partial prefill either way."""
        queued = False
        for ent in entries:
            if ent.tier != "host" or ent.hslot is None:
                continue
            ent.tier = "promoting"
            self._q.put(("promote", self._gen, ent, ent.job, ent.hslot,
                         self._digest.get(ent.hslot),
                         self._dev_sum.get(ent.hslot),
                         time.perf_counter()))
            queued = True
        if queued:
            # one hit per lookup that actually started promotions (a
            # re-touch of an already-promoting chain is the same hit)
            self.hits += 1
            if self._m is not None:
                self._m.kv_tier_hits.inc()

    # a splice may briefly wait for an in-flight promotion: the wait is
    # bounded WELL below the prefill recompute it avoids (one host
    # memcpy + hash vs re-running the model over the whole block), so
    # it is a scheduling micro-pause, not a stall — and a promote that
    # overruns it (the slow-host-copy fault point, a genuinely slow
    # host) degrades this admission to a partial-prefill miss
    PROMOTE_WAIT_S = 0.02

    def await_promotions(self, entries, budget_s: Optional[float] = None
                         ) -> None:
        """Bounded drain-wait for in-flight promotions of ``entries``
        (engine thread). Returns as soon as none are ``promoting`` or
        the budget lapses — NEVER unbounded: a slow promote leaves the
        entries in flight and the caller recomputes them as a miss."""
        budget = self.PROMOTE_WAIT_S if budget_s is None else budget_s
        deadline = time.monotonic() + budget
        while any(e.tier == "promoting" for e in entries):
            left = deadline - time.monotonic()
            if left <= 0:
                return
            self._done_evt.wait(left)
            self._done_evt.clear()
            self.drain()

    def drain(self) -> None:
        """Apply worker completions (engine thread, step/admission
        boundaries): land finished spills as ``host`` entries, splice
        verified promotions back into the device pool — all of a
        drain's promotions through ONE batched restore dispatch — and
        contain checksum failures as invalidate + recompute-as-miss."""
        pc = self.coord.pcache

        def current(ent, token):
            return ent.job == token and pc._by_key.get(ent.key) is ent

        promotes = []
        while True:
            try:
                msg = self._done.popleft()
            except IndexError:
                break
            kind, gen = msg[0], msg[1]
            if gen != self._gen:
                continue  # predates a reset; owner_release cleaned up
            if kind == "spill":
                for ent, token, hslot, digest, dev_sum in msg[2]:
                    if not current(ent, token):
                        continue  # moved on (e.g. recompute re-bind)
                    ent.tier = "host"
                    self._digest[hslot] = digest
                    if dev_sum is not None:
                        self._dev_sum[hslot] = dev_sum
            elif kind == "promote":
                _, _, ent, token, hslot, payload, dev_sum, dt = msg
                if current(ent, token):
                    promotes.append((ent, hslot, payload, dev_sum, dt))
            else:  # "promote-bad" / "fault": doubt the block
                ent, token = msg[2], msg[3]
                if current(ent, token):
                    self._contain_bad(ent)
        if promotes:
            self._land_promotions(promotes)
        self._update_occupancy()

    def _land_promotions(self, promotes) -> None:
        """Splice a drain's verified promotions back into the pool with
        one batched ``_copy_pages``-style donated dispatch."""
        pc = self.coord.pcache
        landed = []
        for ent, hslot, payload, dev_sum, dt in promotes:
            page = self.coord.alloc_page()
            if page is None:
                # pool genuinely full even after demotion pressure: stay
                # host-resident, a future lookup re-requests
                ent.tier = "host"
                continue
            landed.append((ent, int(page), hslot, payload, dev_sum, dt))
        if not landed:
            return
        import jax.numpy as jnp

        w = self.COPY_WIDTH
        for off in range(0, len(landed), w):
            chunk = landed[off:off + w]
            m = len(chunk)
            idx = np.zeros((w,), np.int32)
            idx[:m] = [page for _, page, *_ in chunk]
            stacked = [
                np.stack([lan[3][i] for lan in chunk]
                         + [np.zeros_like(chunk[0][3][i])] * (w - m))
                for i in range(len(chunk[0][3]))
            ]
            # pages_flat() flushes queued captures first, so a page the
            # alloc above just demoted is read BEFORE this restore
            # writes its new bytes (jax orders dispatches by data
            # dependency); pad rows re-write the trash page
            self.coord.set_pages(self.engine.runner.restore_pages(
                self.coord.pages_flat(), jnp.asarray(idx), stacked))
        ig = getattr(self.engine, "_integrity", None)
        for ent, page, hslot, _payload, dev_sum, dt in landed:
            # the entry owns the page from here (idle cached: ref 0)
            self.coord.page_ref[page] = 0
            self._free_hslot(hslot)
            ent.hslot = None
            if not pc.promote(ent, page):
                # raced out of the index between the token check and
                # now (not reachable today — single-threaded — but a
                # freed page must never leak)
                self.coord.free_pages.append(page)
                continue
            if ig is not None and dev_sum is not None:
                ig.adopt_page_sum(page, dev_sum)
            self.promotions += 1
            if self._m is not None:
                self._m.kv_promotions.inc()
                self._m.kv_promote_seconds.observe(dt)

    def _contain_bad(self, ent):
        """A promotion failed its checksum (or the worker faulted on the
        job): invalidate-on-doubt — the entry and every descendant drop,
        future lookups recompute-as-miss, and the failure is counted on
        the integrity surface. Never a wrong token: the corrupt bytes
        were never spliced."""
        self.drops += 1
        if self._m is not None:
            self._m.kv_drops.inc()
        self._drop_entry(ent)

    def _drop_entry(self, ent):
        """Remove ``ent`` + descendants from the index, routing freed
        device pages (a descendant may still be HBM-resident) exactly
        like every other invalidation path."""
        eng = self.engine
        ig = getattr(eng, "_integrity", None)
        for p in self.coord.pcache.invalidate_entry(ent):
            if ig is not None:
                ig.forget_page(p)
            if int(self.coord.page_ref[p]) == 0:
                self.coord.free_pages.append(p)

    # hooks -----------------------------------------------------------
    def release_entry(self, ent) -> None:
        """``PrefixCache.owner_release``: the entry left the index or
        re-bound to a device page — reclaim its host slot (in-flight
        jobs die by token; FIFO job order makes a stale slab write
        harmless to any later reassignment of the row)."""
        if ent.hslot is not None:
            self._free_hslot(ent.hslot)
            ent.hslot = None
            self._update_occupancy()

    def _alloc_hslot(self) -> Optional[int]:
        if self._free_hslots:
            return self._free_hslots.pop()
        victim = self.coord.pcache.evict_host_lru()
        if victim is not None:
            # _remove fired release_entry, so the free list has a slot
            self.drops += 1
            if self._m is not None:
                self._m.kv_drops.inc()
        return self._free_hslots.pop() if self._free_hslots else None

    def _free_hslot(self, hslot: int):
        self._digest.pop(hslot, None)
        self._dev_sum.pop(hslot, None)
        self._free_hslots.append(hslot)

    # lifecycle -------------------------------------------------------
    def reset(self):
        """Pool reset (engine fault recovery): drop the whole tier. The
        host copies were captured from a pool that just died mid-fault;
        the recompute policy makes them free to re-earn, and never
        serving spill state that predates a fault is the same trust
        posture the device cache takes (``PrefixCache.clear``)."""
        self._gen += 1
        self._free_hslots = list(range(self.host_pages - 1, -1, -1))
        self._digest.clear()
        self._dev_sum.clear()
        self._done.clear()
        self._pending = []  # un-captured demotions die with the pool
        self._update_occupancy()

    def stop(self, timeout: float = 5.0):
        """End the worker thread (frontend drain/shutdown, replica
        quarantine/restart). Idempotent; pending jobs are abandoned —
        the tier is bookkeeping over recomputable bytes, so there is
        nothing to flush."""
        if self._stopped:
            return
        self._stopped = True
        self._gen += 1
        self._q.put(None)
        self._worker.join(timeout=timeout)

    # ----------------------------------------------------- worker thread
    def _worker_loop(self):
        """The spill worker: the one blocking device→host copy site in
        the serving stack, deliberately off the engine thread so a slow
        host copy (the ``slow-host-copy`` fault point) degrades hits to
        misses instead of stalling scheduling."""
        while True:
            job = self._q.get()
            if job is None:
                return
            fi = self.engine._fi
            if fi is not None and fi.fire("slow-host-copy"):
                time.sleep(fi.param("slow-host-copy", "delay_ms", 25.0)
                           / 1e3)
            try:
                self._worker_job(job)
            except Exception:  # noqa: BLE001 - worker isolation: a
                # failed copy must doubt the block, never kill the tier
                self._post_fault(job)
            self._done_evt.set()

    def _post_fault(self, job):
        """Route a worker-side failure into containment: the completion
        drives :meth:`_contain_bad` on the engine thread (invalidate +
        recompute-as-miss + drop accounting) — a faulted copy doubts
        the block, it never silently parks it. A spill job carries a
        WAVE of (ent, token, ...) items where a promote job carries one
        entry inline — post one fault per entry, or the drain's
        ``ent.job == token`` check would choke on the raw item list
        (found by the ISSUE 19 ``_done``-drain audit: the old
        single-message form was promote-shaped only)."""
        if job[0] == "spill":
            for ent, token, _hslot, _dev_sum in job[2]:
                self._done.append(("fault", job[1], ent, token))
        else:
            self._done.append(("fault", job[1], job[2], job[3]))

    def _worker_job(self, job):
        import jax

        fi = self.engine._fi
        if fi is not None and fi.fire("racey-worker-write"):
            # deliberate ownership violation (ISSUE 19 satellite): poke
            # an engine-owned counter from the worker, bypassing the
            # job-queue/completion-deque channel. setattr keeps the
            # write invisible to the static tpurace pass (reflection is
            # a documented blind spot) — proving the RUNTIME guard
            # covers what the linter cannot: with ownership_guard()
            # armed this raises OwnershipError, the worker isolation
            # above routes it through _post_fault, and the engine drain
            # contains the job as a counted drop (chaos-asserted).
            # Guard off: value-identical no-op.
            setattr(self, "demotions", self.demotions + 0)
        kind = job[0]
        if kind == "spill":
            _, gen, items, handles = job
            # one blocking fetch for the whole demotion wave: each
            # handle is [m_pad, page_size, lanes] for one K/V/scale
            # buffer (device_get assembles the global logical pages —
            # at tp>1 the lanes arrive shard-assembled)
            arrays = [np.asarray(jax.device_get(h)) for h in handles]
            if self._slabs is None:
                self._slabs = [
                    np.zeros((self.host_pages,) + a.shape[1:], a.dtype)
                    for a in arrays]
            done = []
            for j, (ent, token, hslot, dev_sum) in enumerate(items):
                digest = hashlib.blake2b(digest_size=16)
                for slab, a in zip(self._slabs, arrays):
                    slab[hslot] = a[j]
                    digest.update(a[j].tobytes())
                done.append((ent, token, hslot, digest.digest(),
                             dev_sum))
            # the engine thread stores the digests/dev_sums at drain so
            # a stale completion can't poison a reassigned row
            self._done.append(("spill", gen, done))
        else:  # promote
            _, gen, ent, token, hslot, want, dev_sum, t0 = job
            fi = self.engine._fi
            if fi is not None and fi.fire("kv-spill-corrupt"):
                # SILENT host-DRAM damage (ISSUE 15 satellite): flip one
                # seed-chosen byte of the host-resident page — nothing
                # signals doubt, only the digest below stands between
                # this flip and a wrong token
                row = self._slabs[0][hslot]
                view = row.view(np.uint8).reshape(-1)
                view[fi.draw("kv-spill-corrupt", view.size)] ^= 0xFF
            payload = [np.array(s[hslot]) for s in self._slabs]
            digest = hashlib.blake2b(digest_size=16)
            for a in payload:
                digest.update(a.tobytes())
            ok = want is not None and digest.digest() == want
            from .integrity import count_integrity_check

            count_integrity_check("kv_tier", ok)
            if ok:
                self._done.append(
                    ("promote", gen, ent, token, hslot, payload, dev_sum,
                     time.perf_counter() - t0))
            else:
                self._done.append(("promote-bad", gen, ent, token))
