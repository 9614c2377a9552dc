"""Process bootstrap + DataParallel (reference:
python/paddle/distributed/parallel.py).

``init_parallel_env`` replaces the reference's TCPStore/ProcessGroupNCCL
bootstrap (paddle/fluid/distributed/store/tcp_store.cc +
collective/process_group_nccl.cc) with ``jax.distributed.initialize`` — the
coordination service over DCN is the store, PJRT owns the device world.
One process per host owns all local chips (the TPU process model), so the
env contract maps PADDLE_TRAINER_ID → process index, not chip index.
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax
import numpy as np


class ParallelEnv:
    """Reads the launch env contract (reference env vars kept verbatim:
    PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM, PADDLE_TRAINER_ENDPOINTS,
    PADDLE_CURRENT_ENDPOINT, PADDLE_MASTER — SURVEY.md L11)."""

    def __init__(self):
        self.rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self.world_size = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        self.current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        self.trainer_endpoints: List[str] = eps.split(",") if eps else []
        self.master = os.environ.get(
            "PADDLE_MASTER",
            self.trainer_endpoints[0] if self.trainer_endpoints else "",
        )
        self.device_id = int(os.environ.get("FLAGS_selected_tpus", "0").split(",")[0])
        self.initialized = False

    @property
    def local_rank(self):
        return self.rank

    @property
    def nranks(self):
        return self.world_size

    def __repr__(self):
        return (f"ParallelEnv(rank={self.rank}, world_size={self.world_size}, "
                f"master={self.master!r})")


_env = ParallelEnv()
_default_group = None
_global_mesh = None


def init_parallel_env(strategy=None):
    """Initialize the distributed world. Multi-process when the env contract
    says so; no-op world of 1 otherwise. Idempotent."""
    global _default_group
    if _env.initialized:
        return _default_group
    # restart goodput: workers (re)spawned by the elastic supervisor carry
    # JAX_COMPILATION_CACHE_DIR so recompiles after a failure are disk hits
    from ..framework.compile_cache import maybe_enable_from_env

    maybe_enable_from_env()
    if _env.world_size > 1 and not jax.distributed.is_initialized():
        coordinator = _env.master or _env.trainer_endpoints[0]
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=_env.world_size,
            process_id=_env.rank,
        )
    _env.initialized = True
    from .topology import Group

    _default_group = Group(list(range(_env.world_size)), axis_name=None,
                           rank=_env.rank)
    return _default_group


def get_rank(group=None) -> int:
    if group is not None:
        return group.rank
    return _env.rank


def get_world_size(group=None) -> int:
    if group is not None:
        return group.nranks
    return _env.world_size


def is_initialized() -> bool:
    return _env.initialized


def new_group(ranks: Optional[List[int]] = None, backend: str = "xla", timeout=None):
    from .topology import Group

    ranks = ranks if ranks is not None else list(range(_env.world_size))
    rank = ranks.index(_env.rank) if _env.rank in ranks else -1
    return Group(ranks, axis_name=None, rank=rank, backend=backend)


def get_group(gid=None):
    return _default_group


# --------------------------------------------------------------------- mesh


def set_mesh(mesh):
    global _global_mesh
    _global_mesh = mesh


def mesh_if_set():
    """The mesh ``set_mesh`` / ``fleet.init`` installed, or None: what code
    that must not invent a multi-device layout asks (``get_mesh`` answers
    with a pure-dp default over every device when none was set)."""
    return _global_mesh


def get_mesh():
    if _global_mesh is None:
        from .topology import build_mesh

        # not stored: "no mesh was set" stays observable (mesh_if_set)
        return build_mesh(dp=jax.device_count())
    return _global_mesh


# ------------------------------------------------------------- DataParallel


class DataParallel:
    """DP wrapper (reference: paddle.DataParallel → the C++ Reducer,
    paddle/fluid/imperative/reducer.cc).

    TPU-native: in the compiled step, DP is a sharding spec (batch on 'dp')
    and grads are psum'd by XLA — no reducer needed. This wrapper provides
    the eager-mode API surface: grad averaging across processes after
    backward (via eager all_reduce), ``no_sync`` accumulation windows, and
    transparent attribute delegation."""

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        self._layers = layers
        self._group = group
        self._sync = True
        init_parallel_env()

    # paddle API: model(x)
    def __call__(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def no_sync(self):
        import contextlib

        dp = self

        @contextlib.contextmanager
        def ctx():
            prev = dp._sync
            dp._sync = False
            try:
                yield
            finally:
                dp._sync = prev

        return ctx()

    def apply_collective_grads(self):
        """Average grads across the dp world (call after backward; the
        reference's reducer does this automatically per bucket — eager mode
        here keeps it explicit and cheap to reason about)."""
        if not self._sync or get_world_size() <= 1:
            return
        from .collective import ReduceOp, all_reduce

        for p in self._layers.parameters():
            if p.grad is not None:
                all_reduce(p.grad, op=ReduceOp.AVG, group=self._group)

    # delegate the Layer surface
    def __getattr__(self, name):
        return getattr(self._layers, name)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, *args, **kwargs):
        return self._layers.set_state_dict(*args, **kwargs)

    def parameters(self, *args, **kwargs):
        return self._layers.parameters(*args, **kwargs)

    def named_parameters(self, *args, **kwargs):
        return self._layers.named_parameters(*args, **kwargs)

    def train(self):
        self._layers.train()
        return self

    def eval(self):
        self._layers.eval()
        return self
