"""paddle_tpu.serving — the async streaming front-end (ISSUE 12).

The layer that turns the paged ``inference.Engine`` into a *service*:

* :mod:`fairness` — the weighted-fair multi-tenant request queue
  (stride scheduling with per-tenant admission bounds) that sits in
  front of the engine-core scheduler, so one tenant's 32k-token batch
  flood cannot starve interactive traffic.
* :mod:`frontend` — ``ServingFrontend``: the engine-core loop on its
  own thread (every ``Engine`` call lives there — the engine is not
  thread-safe), multi-step scheduling when the queue is idle, stream
  tickets bridging harvest callbacks to any consumer (blocking
  iterators, asyncio queues), and the graceful SIGTERM drain.
* :mod:`server` — ``ApiServer``: an OpenAI-compatible streaming HTTP
  server (pure stdlib asyncio; SSE ``/v1/completions`` +
  ``/v1/chat/completions``) decoupled from the engine by the fair
  queue. tpulint rule TPL901 enforces that nothing inside this
  package's ``async def`` bodies blocks the event loop.
* :mod:`replica` / :mod:`router` — the replica-resilience layer
  (ISSUE 13): supervised engine replicas (in-process or subprocess
  workers behind the ApiServer protocol) with split liveness/readiness,
  health-gated routing, and KV-free mid-stream request migration —
  a dead replica's streams re-admit elsewhere as prompt‖emitted and
  the client sees one uninterrupted, bit-identical token sequence.
* :mod:`cluster` — cluster-scale serving (ISSUE 20):
  ``Router(pools={"prefill": k, "decode": m})`` splits the fleet into
  role pools, ships finished prefill KV across replicas
  (digest-verified; every failure degrades to resume-from-emitted
  recompute), scores placement by prefix-chain overlap before load,
  and autoscales pools from queue-depth/p99-TTFT signals.

The package itself is stdlib+numpy; only the frontend's engine thread
ever touches jax/compiled programs — the event loop and the fair queue
never do (tpulint TPL901 keeps it that way; TPL902 additionally bans
unbounded retry loops anywhere in this package).
"""
from .cluster import ClusterCoordinator, parse_pools
from .fairness import DEFAULT_TENANT, FairQueue, parse_tenant_weights
from .frontend import ServingFrontend, StreamTicket
from .replica import InProcReplica, Replica, StreamSpec, SubprocessReplica
from .router import Router, RouterTicket

__all__ = [
    "DEFAULT_TENANT", "FairQueue", "parse_tenant_weights",
    "ServingFrontend", "StreamTicket",
    "Replica", "InProcReplica", "SubprocessReplica", "StreamSpec",
    "Router", "RouterTicket",
    "ClusterCoordinator", "parse_pools",
]
