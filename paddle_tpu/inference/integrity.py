"""Online silent-data-corruption audits for the serving engine
(ISSUE 14 tentpole, part b).

The fault stack so far handles LOUD failures: a dispatch dies, a thread
vanishes, a checkpoint is torn — something raises and the machinery of
PRs 6–13 contains it. Silent data corruption is the opposite threat
model ("Cores that don't count", HotOS'21): a flaky core or a flipped
HBM/DRAM bit changes VALUES without changing control flow, and the
engine keeps streaming tokens that are confidently wrong. Before this
module, weights were trusted forever after ``device_put``, cached KV
page bytes were trusted forever after registration, and nothing ever
cross-checked a delivered token. The :class:`IntegritySentinel` closes
those three windows with host-scheduled probes that ride the existing
step loop:

* **Weight audits.** At engine construction the sentinel snapshots a
  blake2b digest per block of every PLACED parameter (the post-
  ``device_put`` bytes — layout- and shard-independent, fetched through
  ``ModelRunner.fetch_param_slice`` so a TP mesh assembles the global
  view). A periodic idle-step probe re-fetches one sampled block and
  compares. Weights never legitimately change while serving, so any
  drift is corruption; containment is the QUARANTINE ladder — the
  watchdog drops ``/readyz``, the router migrates every stream off the
  replica (resume-from-emitted, bit-identical), and the supervised
  restart comes back with freshly verified weights.
* **KV page checksums.** Each cached full block's physical page gets a
  checksum at registration (one tiny jitted reduction over the page's
  K/V lanes across every layer — a scalar per page crosses the wire,
  not the page). A prefix-cache hit re-verifies the matched pages
  BEFORE the splice commits (closing the PR 8 window where page BYTES
  were trusted between the token re-verify and use), and a
  re-registration of an idle refcount-0 page re-verifies its stored
  sum. A mismatch routes through invalidate-on-doubt: the entry and its
  descendants drop, active slots referencing the page are preempted
  (requeue — recompute resumes the stream exactly), and the admission
  recomputes from scratch. Corruption costs a MISS, never a token.
* **Shadow recompute.** Every N steps one sampled greedy decode row is
  re-scored through the model's contiguous (non-paged) forward — an
  independent numeric path — and the delivered token is compared
  against the twin's argmax (tie-aware: an untrained model's near-tie
  margins are not divergence; a corrupted path's are enormous). A
  divergence fails that request with the typed ``IntegrityError``
  instead of letting the stream keep going — kernel/SDC divergence is
  caught online, not in a post-mortem.

Every probe lands in ``paddle_tpu_integrity_checks_total{target}`` /
``paddle_tpu_integrity_failures_total{target}`` (targets ``weights`` /
``kv`` / ``shadow``; the checkpoint layer shares the same pair with
``target="checkpoint"``), so a fleet can alert on "integrity failures
per replica-hour" — the SDC rate the HotOS'21 paper says you must
measure to believe.

All sentinel code is host-side scheduler work between dispatches (never
traced); ``Engine(integrity=None)`` (the default) constructs nothing
and costs nothing.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .errors import IntegrityError

__all__ = ["IntegrityConfig", "IntegritySentinel",
           "count_integrity_check"]


def _counter(name: str, help_: str):
    from ..observability import counter

    return counter(name, help_, labelnames=("target",))


def _count_check(target: str, ok: bool, n: int = 1):
    _counter("paddle_tpu_integrity_checks_total",
             "data-integrity verifications performed, by audit target"
             ).labels(target=target).inc(n)
    if not ok:
        _counter("paddle_tpu_integrity_failures_total",
                 "data-integrity verifications that FAILED, by audit "
                 "target").labels(target=target).inc()


def count_integrity_check(target: str, ok: bool, n: int = 1):
    """Public recording surface for integrity verifications performed
    OUTSIDE the sentinel — the KV host tier's promote-time digest
    compare (ISSUE 15, ``target="kv_tier"``) lands on the same
    ``paddle_tpu_integrity_{checks,failures}_total`` pair the fleet
    alerts on, whether or not an ``IntegritySentinel`` is armed."""
    _count_check(target, ok, n)


class IntegrityConfig:
    """Sentinel knobs. ``mode`` presets:

    * ``"audit"``  — weight audits + KV page checksums (the always-on
      production posture: probes are cheap and detection is containment,
      not crash).
    * ``"strict"`` — audit plus the shadow-recompute sentinel and a
      tighter weight-audit period (the paranoid posture for hosts with
      a known SDC history).

    A dict value for ``Engine(integrity=...)`` starts from the
    ``audit`` preset and overrides per key."""

    __slots__ = ("mode", "weight_audit_every", "weight_blocks",
                 "kv_checksums", "shadow_every", "shadow_tol")

    def __init__(self, mode: str = "audit",
                 weight_audit_every: int = 16, weight_blocks: int = 2,
                 kv_checksums: bool = True, shadow_every: int = 0,
                 shadow_tol: float = 0.05):
        self.mode = mode
        self.weight_audit_every = int(weight_audit_every)
        self.weight_blocks = max(1, int(weight_blocks))
        self.kv_checksums = bool(kv_checksums)
        self.shadow_every = int(shadow_every)
        # tie tolerance, relative to the logit scale: the shadow twin is
        # an independent numeric path, so near-argmax-tie margins (the
        # reason the repo's greedy identity tests are tie-aware) must
        # not count as divergence — real corruption's margins are
        # orders of magnitude past this
        self.shadow_tol = float(shadow_tol)

    @classmethod
    def coerce(cls, spec) -> Optional["IntegrityConfig"]:
        """``Engine(integrity=...)`` front door: None/"off" → no
        sentinel; "audit"/"strict" → preset; dict → audit preset with
        overrides; an IntegrityConfig passes through."""
        if spec is None or spec == "off" or spec is False:
            return None
        if isinstance(spec, cls):
            return spec
        if spec == "audit" or spec is True:
            return cls(mode="audit")
        if spec == "strict":
            return cls(mode="strict", weight_audit_every=8,
                       shadow_every=16)
        if isinstance(spec, dict):
            return cls(**{"mode": "audit", **spec})
        raise ValueError(
            f"integrity={spec!r}: expected None/'off'/'audit'/'strict', "
            "an IntegrityConfig, or a dict of its fields")


def _page_sums_raw(bufs, idx):
    """The tiny jitted per-page checksum reduction: for each physical
    page in ``idx``, a position-weighted f32 sum over that page's bytes
    in EVERY layer's K/V (and scale) buffer. Deterministic for a fixed
    backend+shape (jit fixes the reduction order), so equality is an
    exact content check; a single flipped bit shifts at least one
    weighted term. One scalar per page crosses the device boundary —
    the page bytes never do."""
    out = jnp.zeros(idx.shape[0], jnp.float32)
    for j, b in enumerate(bufs):
        sel = b[idx].astype(jnp.float32).reshape(idx.shape[0], -1)
        w = 1.0 + (jnp.arange(sel.shape[1], dtype=jnp.float32) % 911.0)
        out = out + (j + 1) * jnp.sum(sel * w, axis=1)
    return out


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class IntegritySentinel:
    """Engine-owned SDC auditor; see module docstring. Construction
    snapshots the weight digest baseline (the weights are verified-fresh
    at that moment: just loaded/placed), so it happens LAST in
    ``Engine.__init__``."""

    def __init__(self, engine, cfg: IntegrityConfig):
        self.engine = engine
        self.cfg = cfg
        self.last_error: Optional[IntegrityError] = None
        self._steps = 0
        self._since_audit = 0
        self._probe_cursor = 0
        self._shadow_cursor = 0
        self._page_sum: Dict[int, float] = {}
        self._sum_fn = jax.jit(_page_sums_raw)
        # weight baseline: per param, (element_count, [(a, b, digest)])
        self._weight_base: List[Tuple[int, List[Tuple[int, int, str]]]] = []
        self._probe_targets: List[Tuple[int, int]] = []  # (param, block)
        if cfg.weight_audit_every:
            self._snapshot_weights()

    @classmethod
    def build(cls, engine, spec) -> Optional["IntegritySentinel"]:
        cfg = IntegrityConfig.coerce(spec)
        return None if cfg is None else cls(engine, cfg)

    # ------------------------------------------------------- weight audit
    def _snapshot_weights(self):
        """Digest every placed parameter block-wise from the bytes the
        compiled programs will actually consume (``engine._params``,
        fetched through the runner so a TP mesh assembles the global
        view)."""
        nb = self.cfg.weight_blocks
        for i in range(len(self.engine._params)):
            host = self.engine.runner.fetch_param_slice(i, 0, None)
            n = int(host.size)
            raw = host.tobytes()
            itemsize = host.dtype.itemsize
            blocks: List[Tuple[int, int, str]] = []
            per = max(1, -(-n // nb))
            for b in range(0, n, per):
                a, e = b, min(n, b + per)
                dg = hashlib.blake2b(raw[a * itemsize:e * itemsize],
                                     digest_size=16).hexdigest()
                blocks.append((a, e, dg))
                self._probe_targets.append((i, len(blocks) - 1))
            self._weight_base.append((n, blocks))

    def audit_weights_once(self) -> bool:
        """Probe ONE (param, block): re-fetch the shard slice and
        compare its digest against the load-time baseline. Returns
        False — after quarantining the engine — on a mismatch."""
        if not self._probe_targets:
            return True
        i, b = self._probe_targets[
            self._probe_cursor % len(self._probe_targets)]
        self._probe_cursor += 1
        a, e, want = self._weight_base[i][1][b]
        fi = self.engine._fi
        if fi is not None and fi.fire("bit-flip-weight"):
            self._flip_weight_bit(i, a, e, fi)
        got = hashlib.blake2b(
            self.engine.runner.fetch_param_slice(i, a, e).tobytes(),
            digest_size=16).hexdigest()
        ok = got == want
        _count_check("weights", ok)
        if not ok:
            err = IntegrityError(
                f"weight audit digest mismatch: param {i} elements "
                f"[{a}, {e}) no longer match the load-time baseline — "
                "silent weight corruption; quarantining the engine")
            self.last_error = err
            # containment ladder, weight arm: readiness drops, the
            # router drains/migrates, the supervised restart reloads
            # verified weights
            self.engine._watchdog.quarantine(err)
        return ok

    def _flip_weight_bit(self, i: int, a: int, e: int, fi):
        """``bit-flip-weight`` damage: XOR one seed-chosen bit of one
        seed-chosen element inside the block the NEXT probe will fetch
        (so a single fire is always observable), written back through a
        sharding-preserving scatter."""
        p = self.engine._params[i]
        flat = a + fi.draw("bit-flip-weight", max(1, e - a))
        idx = np.unravel_index(flat, p.shape) if p.ndim else ()
        val = np.asarray(jax.device_get(p[idx]))
        raw = bytearray(val.tobytes())
        bit = fi.draw("bit-flip-weight", 8 * len(raw))
        raw[bit // 8] ^= 1 << (bit % 8)
        new = np.frombuffer(bytes(raw), dtype=val.dtype).reshape(val.shape)
        self.engine._params[i] = p.at[idx].set(jnp.asarray(new))

    # -------------------------------------------------- KV page checksums
    def _page_sums(self, pages: List[int]) -> List[float]:
        m = len(pages)
        idx = np.zeros((_pow2ceil(m),), np.int32)
        idx[:m] = pages
        vals = np.asarray(jax.device_get(
            self._sum_fn(self.engine._pages_flat(), jnp.asarray(idx))))
        return [float(v) for v in vals[:m]]

    def note_registered(self, pages: List[int]) -> List[int]:
        """Checksum freshly registered cache pages; for a page that
        ALREADY carries a sum (an idle refcount-0 block re-registered by
        a later identical prompt) the stored sum is re-verified instead
        — corruption of a parked page is caught at the earliest touch.
        Returns the pages that FAILED (caller contains)."""
        if not self.cfg.kv_checksums or not pages:
            return []
        sums = self._page_sums([int(p) for p in pages])
        bad: List[int] = []
        for pg, s in zip(pages, sums):
            pg = int(pg)
            old = self._page_sum.get(pg)
            if old is None:
                self._page_sum[pg] = s
                continue
            ok = old == s
            _count_check("kv", ok)
            if not ok:
                bad.append(pg)
        if bad:
            self.last_error = IntegrityError(
                f"KV page checksum mismatch at re-registration: pages "
                f"{bad} changed while parked in the prefix cache")
        return bad

    def verify_pages(self, pages: List[int]) -> List[int]:
        """The splice-time probe: re-reduce every matched page that has
        a stored checksum and compare exactly. Returns the bad pages —
        the caller invalidates and recomputes, so a flipped page bit
        costs a cache miss, never a wrong token."""
        if not self.cfg.kv_checksums:
            return []
        known = [int(p) for p in pages if int(p) in self._page_sum]
        if not known:
            return []
        sums = self._page_sums(known)
        bad: List[int] = []
        for pg, s in zip(known, sums):
            ok = self._page_sum[pg] == s
            _count_check("kv", ok)
            if not ok:
                bad.append(pg)
        if bad:
            self.last_error = IntegrityError(
                f"KV page checksum mismatch at splice: pages {bad} "
                "changed between registration and reuse")
        return bad

    def forget_page(self, page: int):
        """The page left the cache (eviction, invalidation, realloc for
        new content) — its stored sum no longer describes anything."""
        self._page_sum.pop(int(page), None)

    def sum_of_page(self, page: int) -> Optional[float]:
        """The stored device-side checksum for ``page`` (None when the
        page was never registered). The KV host tier reads it at
        demotion so the sum can travel with the spilled bytes
        (ISSUE 15)."""
        return self._page_sum.get(int(page))

    def adopt_page_sum(self, page: int, s: float):
        """Checksum-verified promotion (ISSUE 15): the tier restored a
        page whose bytes hash-matched their demotion-time digest, so
        the device-side sum recorded before the round trip describes
        the new physical page too — re-adopting it keeps the splice-
        time probe (:meth:`verify_pages`) guarding promoted pages
        exactly like never-demoted ones."""
        self._page_sum[int(page)] = float(s)

    def reset_kv(self):
        """Pool reset: the buffers (and every checksum over them) died."""
        self._page_sum.clear()

    # ---------------------------------------------------- shadow recompute
    def shadow_check(self) -> Optional[bool]:
        """Re-score one sampled greedy decode row through the model's
        contiguous (non-paged) forward — an independent numeric path —
        and compare the delivered last token against the twin's argmax,
        tie-aware (``shadow_tol`` of the logit scale). A divergence is
        kernel/SDC corruption caught ONLINE: that request fails typed
        (``integrity``) instead of streaming on."""
        eng = self.engine
        cands = [r for r in eng._active.values()
                 if r.temperature == 0.0 and r.tokens and not r.done]
        if not cands:
            return None
        req = cands[self._shadow_cursor % len(cands)]
        self._shadow_cursor += 1
        hist = req.tokens[:-1]
        ids = (np.concatenate([req.prompt,
                               np.asarray(hist, np.int32)])
               if hist else np.asarray(req.prompt, np.int32))
        from ..framework.tensor import Tensor, pause_tape

        with pause_tape():
            logits = eng.model.forward(
                Tensor._wrap(jnp.asarray(ids[None, :])))
        lg = logits._data if isinstance(logits, Tensor) else logits
        row = np.asarray(jax.device_get(lg[0, -1].astype(jnp.float32)))
        delivered = int(req.tokens[-1])
        top = float(row.max())
        margin = top - float(row[delivered])
        scale = max(1.0, abs(top))
        ok = margin <= self.cfg.shadow_tol * scale
        _count_check("shadow", ok)
        if not ok:
            err = IntegrityError(
                f"shadow recompute divergence: request {req.rid} "
                f"delivered token {delivered} but the contiguous twin "
                f"argmaxes {int(row.argmax())} (margin {margin:.4f} at "
                f"scale {scale:.4f}) — kernel/SDC divergence",
                rid=req.rid)
            self.last_error = err
            eng._fail_request(req, err)
        return ok

    # ------------------------------------------------------------ driver
    def on_step(self) -> None:
        """The engine's per-step hook (host side, after a successful
        step). Weight audits prefer IDLE steps — nothing queued — but a
        sustained-load engine still audits at 4x the period, so a busy
        replica cannot dodge its own probes forever. Never raises: a
        probe blowing up must not fault the serving step it rides."""
        self._steps += 1
        try:
            cfg = self.cfg
            if cfg.weight_audit_every and not \
                    self.engine._watchdog.quarantined:
                self._since_audit += 1
                idle = not self.engine._queue
                if self._since_audit >= cfg.weight_audit_every and (
                        idle or self._since_audit
                        >= 4 * cfg.weight_audit_every):
                    self._since_audit = 0
                    self.audit_weights_once()
            if cfg.shadow_every and self._steps % cfg.shadow_every == 0:
                self.shadow_check()
        except Exception as e:  # noqa: BLE001 - probe isolation
            self._note_probe_fault(e)

    def _note_probe_fault(self, exc: BaseException):
        """A probe itself failed (not a detection — the probe broke).
        Routed to the taxonomy counters as a failed ``sentinel`` check
        so it is scrape-visible rather than silently absorbed."""
        err = IntegrityError(
            f"integrity probe raised {type(exc).__name__}: {exc}")
        err.__cause__ = exc
        self.last_error = err
        _count_check("sentinel", False)
