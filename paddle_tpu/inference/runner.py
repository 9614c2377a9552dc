"""Model-runner layer of the serving engine (ISSUE 11 tentpole).

The engine split is engine-core / model-runner / cache-coordinator:

* **engine-core** (``engine.Engine``) — the host scheduler: admission,
  slot bookkeeping, harvest, retries, watchdog. Device-count-agnostic;
  it never mentions a mesh.
* **model-runner** (this module) — owns the COMPILED programs (prefill,
  decode chain, mixed chunk+decode, spec verify) and, when ``tp > 1``,
  the tensor-parallel mesh they trace under: weights column/row-sharded
  over the ``tp`` axis via ``shard_map``, the paged KV pool sharded
  along its KV-head lanes, host-built operands (ids, tables, lengths,
  temps, keys) replicated. ``tp=None``/1 builds exactly the single-chip
  programs — bit-compatible with the pre-split engine.
* **cache-coordinator** (``cache_coord.CacheCoordinator``) — the paged
  pool + allocator; pages physically partitioned across the TP axis,
  page tables host-global.

Sharding layout (the vLLM/Megatron TP plan, rebuilt JAX-idiomatically
as ONE ``shard_map`` region per dispatched program — no per-step
reshard boundary, which is exactly what tpushard TPC502 gates):

==============================  =========================  ============
tensor                          global shape               spec
==============================  =========================  ============
q/k/v/gate/up projection w      [H, out]                   P(None, 'tp')
o/down projection w             [in, H]                    P('tp', None)
column-parallel bias            [out]                      P('tp')
embeddings, norms, lm_head      (any)                      P() replicated
KV pages (per layer, k and v)   [P, page_size, Hkv*D]      P(None, None, 'tp')
ids/tables/lengths/temps/keys   (any)                      P() replicated
==============================  =========================  ============

Inside the region each shard computes its head/FF slice; the Megatron
``g`` collectives (one ``psum`` after the attention output projection
and one after the MLP down projection, per layer) are inserted by the
model's ``_tp_axis`` hook, which :meth:`ModelRunner.local_view` arms
only for the duration of the trace. Activations stay replicated across
``tp`` at the program boundary, so tokens/keys/bad flags come back with
``out_specs=P()`` and the host scheduler reads them exactly as in the
single-chip engine.

Static gating: :meth:`ModelRunner.traceable` returns the UNJITTED
shard_map-wrapped program, which the tpucheck registry traces
(``tools/analyze_tpu.py`` entries ``tp_sharded_decode_step`` /
``tp_sharded_mixed_step``) — the comm plan is verified clean (TPC501/
502/503, TPC601 roofline) before any multi-device run.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ModelRunner"]

# projection leaves by the layer attribute that owns them (duck-typed —
# any model family exposing the llama-style separate projections shards)
_COL_LAYERS = ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj")
_ROW_LAYERS = ("o_proj", "down_proj")
# stacked expert weights ([E, ...] leading expert dim) shard over 'ep';
# the router stays replicated so routing is identical on every shard
_EXPERT_LEAVES = ("experts_gate", "experts_up", "experts_down")


class ModelRunner:
    """Builds and caches the engine's compiled programs; owns the TP/EP
    mesh and sharding specs when ``tp > 1`` or ``ep > 1`` (see module
    docstring)."""

    AXIS = "tp"
    EP_AXIS = "ep"

    def __init__(self, engine, tp: Optional[int] = None,
                 ep: Optional[int] = None):
        self.engine = engine
        self.tp = int(tp) if tp else 1
        self.ep = int(ep) if ep else 1
        self.mesh = None
        self.param_specs: Optional[List] = None
        # compiled-program caches (moved here from the monolithic Engine;
        # engine-core reaches them through delegating properties)
        self.decode_fns: Dict[Tuple, object] = {}
        self.prefill_fns: Dict[Tuple, object] = {}
        self.mixed_fns: Dict[Tuple, object] = {}
        if self.tp > 1 or self.ep > 1:
            self._validate_and_build_mesh()

    # ------------------------------------------------------------- mesh
    def _validate_and_build_mesh(self):
        from jax.sharding import Mesh

        cfg = self.engine.cfg
        tp, ep = self.tp, self.ep
        if tp > 1 and self.engine.quantized:
            raise NotImplementedError(
                "tp > 1 with quantized_cache: the int8 scale pages pack "
                "k/v scales against the GLOBAL kv-head count in their "
                "128-lane layout, which a lane-sharded pool would split "
                "mid-field — serve bf16/f32 pages or tp=1")
        devices = jax.devices()
        if len(devices) < tp * ep:
            raise ValueError(
                f"tp={tp} x ep={ep} needs {tp * ep} local devices, found "
                f"{len(devices)} (tests/tools force 8 virtual CPU "
                "devices via --xla_force_host_platform_device_count)")
        if tp > 1:
            n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
            if cfg.num_heads % tp or n_kv % tp:
                raise ValueError(
                    f"tp={tp} must divide num_heads={cfg.num_heads} and "
                    f"num_kv_heads={n_kv} (the KV pool shards by head)")
            inter = getattr(cfg, "intermediate_size", 0)
            if inter and inter % tp:
                raise ValueError(
                    f"tp={tp} must divide intermediate_size={inter}")
        if ep > 1:
            n_exp = getattr(cfg, "num_experts", 0)
            if not n_exp:
                raise ValueError(
                    f"ep={ep} on a dense model: expert parallelism "
                    "shards the stacked expert weights, which this "
                    "config does not have (num_experts=0) — serve an "
                    "MoE config or ep=1")
            if n_exp % ep:
                raise ValueError(
                    f"ep={ep} must divide num_experts={n_exp} (each "
                    "shard owns a contiguous block of experts)")
        if tp > 1 and ep > 1:
            # ep innermost: an expert all-to-all crosses the devices
            # that already exchange the Megatron psums' partners' data
            self.mesh = Mesh(
                np.asarray(devices[:tp * ep]).reshape(tp, ep),
                (self.AXIS, self.EP_AXIS))
        elif ep > 1:
            self.mesh = Mesh(np.asarray(devices[:ep]), (self.EP_AXIS,))
        else:
            self.mesh = Mesh(np.asarray(devices[:tp]), (self.AXIS,))
        self.param_specs = self._infer_param_specs()

    def _infer_param_specs(self) -> List:
        """One PartitionSpec per entry of the engine's ``_swap`` list
        (named_parameters then named_buffers, the order the compiled
        programs receive them in). Column/row assignment follows the
        owning layer's name; everything else replicates."""
        from jax.sharding import PartitionSpec as P

        model, tp = self.engine.model, self.tp
        specs: List = []
        for name, t in model.named_parameters():
            specs.append(self._spec_for(name, t.shape, P))
        for name, b in model.named_buffers():
            if b is not None:
                specs.append(P())
        return specs

    def _spec_for(self, name: str, shape, P):
        parts = name.split(".")
        layer = parts[-2] if len(parts) >= 2 else ""
        leaf = parts[-1]
        if leaf in _EXPERT_LEAVES:
            if self.ep == 1:
                return P()
            if shape[0] % self.ep:
                raise ValueError(
                    f"{name}: expert dim {shape[0]} not divisible by "
                    f"ep={self.ep}")
            return P(self.EP_AXIS, None, None)
        if self.tp == 1:
            return P()  # ep-only mesh: dense weights replicate
        if "qkv_proj" in name:
            raise NotImplementedError(
                "tp > 1 over a packed-QKV projection (GPT's [H, 3H] "
                "weight interleaves q/k/v per head in a layout a "
                "contiguous column shard would split wrongly) — serve a "
                "model family with separate q/k/v projections (LLaMA) "
                "or tp=1")
        if layer in _COL_LAYERS:
            if leaf == "weight":
                if shape[-1] % self.tp:
                    raise ValueError(
                        f"{name}: output dim {shape[-1]} not divisible "
                        f"by tp={self.tp}")
                return P(None, self.AXIS)
            return P(self.AXIS)  # column-parallel bias shards with cols
        if layer in _ROW_LAYERS:
            if leaf != "weight":
                raise NotImplementedError(
                    f"{name}: a row-parallel projection with a bias "
                    "would double-count it through the psum — bias-free "
                    "row layers only (the llama convention)")
            if shape[0] % self.tp:
                raise ValueError(
                    f"{name}: input dim {shape[0]} not divisible by "
                    f"tp={self.tp}")
            return P(self.AXIS, None)
        return P()

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def page_spec(self):
        from jax.sharding import PartitionSpec as P

        # pages shard by KV-head lane over tp only; an ep-only mesh
        # keeps the pool replicated (every shard runs full attention)
        return P(None, None, self.AXIS) if self.tp > 1 else P()

    # -------------------------------------------------------- placement
    def place_params(self, arrays: List) -> List:
        """Pre-place the weight arrays on the mesh with their specs ONCE
        (engine init) so dispatches never re-shard them."""
        if not self.sharded:
            return list(arrays)
        from jax.sharding import NamedSharding

        return [jax.device_put(a, NamedSharding(self.mesh, s))
                for a, s in zip(arrays, self.param_specs)]

    def place_pages(self, arrays: List) -> List:
        """Shard page buffers across the TP axis (KV-head lanes). Used
        by the cache-coordinator at construction AND by pool reset after
        a step fault — donated-dead buffers rebuild per-shard, never as
        a replicated host array (ISSUE 11 satellite)."""
        if not self.sharded:
            return list(arrays)
        from jax.sharding import NamedSharding

        sh = NamedSharding(self.mesh, self.page_spec)
        return [jax.device_put(a, sh) for a in arrays]

    # ------------------------------------------------- host-tier copies
    # The KV tier's demote/promote dispatches (ISSUE 15). Both are tiny
    # jitted page-axis gathers/scatters in the _copy_pages idiom: the
    # page axis is unsharded, so at tp>1 GSPMD runs them over the
    # lane-sharded pool without a reshard — capture's outputs carry the
    # lane sharding (device_get in the spill worker assembles the GLOBAL
    # logical page for the host slab) and restore's donated outputs keep
    # the pool's NamedSharding, so a tp=N demote/promote round trip
    # preserves both bytes and layout. Neither ever blocks the engine
    # thread: capture hands back device handles (the worker does the
    # one synchronous device->host fetch), restore is a donated async
    # dispatch whose host->device payload transfer rides the dispatch.
    # Both take a PADDED page-index vector (pow2, pad slot 0 = the trash
    # page, the same convention every padded program row uses), so one
    # dispatch moves a whole demotion/promotion wave and the compile
    # cache stays one program per pow2 width.
    @property
    def capture_pages(self):
        fn = getattr(self, "_capture_fn", None)
        if fn is None:
            import jax

            def _capture(pages_flat, idx):
                return [b[idx] for b in pages_flat]

            fn = self._capture_fn = jax.jit(_capture)
        return fn

    @property
    def restore_pages(self):
        fn = getattr(self, "_restore_fn", None)
        if fn is None:
            import jax

            def _restore(pages_flat, idx, payload):
                return [b.at[idx].set(x)
                        for b, x in zip(pages_flat, payload)]

            fn = self._restore_fn = jax.jit(_restore, donate_argnums=0)
        return fn

    # ------------------------------------------------------ weight audit
    def fetch_param_slice(self, i: int, start: int,
                          stop: Optional[int]) -> np.ndarray:
        """Host copy of elements ``[start, stop)`` (row-major flat
        order; ``stop=None`` = whole tensor) of PLACED parameter ``i`` —
        the integrity sentinel's audit probe (ISSUE 14). TP-aware the
        same way the dispatches are: ``_params[i]`` carries its
        ``NamedSharding``, so the eager ravel+slice runs under GSPMD
        over the column/row shards and ``device_get`` assembles the
        GLOBAL logical values. The digest baseline is therefore
        layout-independent — a bit flipped in ANY shard's HBM lands in
        the fetched window's bytes regardless of which device holds it,
        and a tp=1 engine fetches the exact same values."""
        p = self.engine._params[i]
        flat = jnp.ravel(p)
        if start or stop is not None:
            flat = flat[int(start):(None if stop is None else int(stop))]
        return np.asarray(jax.device_get(flat))

    # ------------------------------------------------------- local view
    @contextlib.contextmanager
    def local_view(self, strip_collectives: bool = False):
        """Arm the model for a PER-SHARD trace: attention modules see the
        LOCAL head counts (global // tp) and row-parallel layers get
        their ``_tp_axis`` set so the forward inserts the Megatron g
        psums. A no-op at tp=1. ``strip_collectives`` keeps the sharded
        weights but skips the psums — the collective-stripped timing
        twin ``tools/multichip.py`` measures comm against (its outputs
        are partial sums, meaningful for wall-clock only)."""
        if not self.sharded:
            yield
            return
        tp = self.tp
        axis = None if strip_collectives else self.AXIS
        patched = []  # (obj, attr, old)

        def patch(obj, attr, new):
            patched.append((obj, attr, getattr(obj, attr, None),
                            hasattr(obj, attr)))
            setattr(obj, attr, new)

        for lyr in self.engine.model.sublayers(include_self=True):
            if hasattr(lyr, "router") and hasattr(lyr, "experts_gate"):
                # MoE: the all_to_all/all_gather pair is STRUCTURAL (a
                # shard only holds its expert block), so it stays armed
                # even under strip_collectives
                patch(lyr, "_ep_axis",
                      self.EP_AXIS if self.ep > 1 else None)
            elif tp > 1 and hasattr(lyr, "o_proj") \
                    and hasattr(lyr, "num_heads"):
                patch(lyr, "num_heads", lyr.num_heads // tp)
                if hasattr(lyr, "num_kv_heads"):
                    patch(lyr, "num_kv_heads", lyr.num_kv_heads // tp)
                patch(lyr, "_tp_axis", axis)
            elif tp > 1 and hasattr(lyr, "down_proj") \
                    and hasattr(lyr, "gate_proj"):
                patch(lyr, "_tp_axis", axis)
        try:
            yield
        finally:
            for obj, attr, old, existed in reversed(patched):
                if existed:
                    setattr(obj, attr, old)
                else:
                    delattr(obj, attr)

    # --------------------------------------------------------- wrapping
    def shard(self, raw, n_rest: int, out_desc: Tuple[str, ...],
              strip_collectives: bool = False):
        """shard_map-wrap a raw engine program (UNJITTED — the analyze
        registry traces this directly). ``raw(params, pages_flat,
        *rest)`` with ``n_rest`` trailing replicated operands;
        ``out_desc`` names each element of the return tuple: ``"r"``
        (replicated) or ``"pages"`` (the sharded pages_flat list)."""
        from jax.sharding import PartitionSpec as P

        from ..distributed.jax_compat import shard_map

        n_pages = 2 * self.engine.cfg.num_layers
        pg = [self.page_spec] * n_pages
        in_specs = (self.param_specs, pg) + (P(),) * n_rest
        out_specs = tuple(pg if d == "pages" else P() for d in out_desc)

        def body(params, pages_flat, *rest):
            with self.local_view(strip_collectives=strip_collectives):
                return raw(params, pages_flat, *rest)

        body.__name__ = getattr(raw, "__name__", "sharded_step")
        return shard_map(body, self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check=False)

    def wrap(self, raw, n_rest: int, out_desc: Tuple[str, ...],
             donate=(1,)):
        """jit (tp=1) or jit∘shard_map (tp>1) a raw program, donating
        the page buffers either way."""
        fn = raw if not self.sharded else self.shard(raw, n_rest, out_desc)
        return functools.partial(jax.jit, donate_argnums=donate)(fn)

    # ------------------------------------------------- program builders
    # Raw builders live beside the engine (make_mixed_step_fn, the
    # closures below); the runner is where they meet the mesh. Each
    # get_* caches per shape key exactly as the monolithic engine did.
    # MoE engines grow ONE trailing replicated output per program (the
    # router-stats vector; replicated routing computes it identically
    # on every shard) — verify stays stats-free (tap unarmed there).
    @property
    def _moe_extra(self) -> Tuple[str, ...]:
        return ("r",) if getattr(self.engine, "_moe_stats_n", 0) else ()

    def get_decode(self, nb: int, k: int, sampling: bool):
        key = (nb, k, sampling)
        fn = self.decode_fns.get(key)
        if fn is None:
            eng = self.engine
            if eng._m is not None:
                eng._m.compiled.labels(kind="decode").inc()
            raw = eng._make_decode_raw(k, sampling)
            fn = self.wrap(raw, n_rest=5,
                           out_desc=("r", "pages", "r", "r", "r")
                           + self._moe_extra)
            self.decode_fns[key] = fn
        return fn

    def decode_program_text(self, nb: int, k: int, sampling: bool) -> str:
        """Compiled text of the decode-chain program for bucket
        ``(nb, k, sampling)``: the jit entry the engine dispatches,
        lowered at the shapes it dispatches it at. What ``chip_smoke.py``
        reads to show that the program which ran holds the Mosaic kernel
        (``tpu_custom_call``) and, at tp>1, the Megatron collective."""
        eng = self.engine

        def host(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        return self.get_decode(nb, k, sampling).lower(
            eng._params, eng._pages_flat(),
            host((nb, eng.max_pages_per_seq), jnp.int32),
            host((nb,), jnp.int32), host((nb,), jnp.int32),
            host((nb,), jnp.float32), host((nb, 2), jnp.uint32),
        ).compile().as_text()

    def get_prefill(self, bucket, sampling: bool, suffix: bool = False):
        key = (bucket, sampling, suffix)
        fn = self.prefill_fns.get(key)
        if fn is None:
            eng = self.engine
            if eng._m is not None:
                eng._m.compiled.labels(kind="prefill").inc()
            raw = eng._make_prefill_raw(sampling, suffix)
            fn = self.wrap(raw, n_rest=6,
                           out_desc=("r", "r", "r", "pages")
                           + self._moe_extra)
            self.prefill_fns[key] = fn
        return fn

    def get_mixed(self, nb: int, sampling: bool):
        key = (nb, sampling)
        fn = self.mixed_fns.get(key)
        if fn is None:
            eng = self.engine
            if eng._m is not None:
                eng._m.compiled.labels(kind="mixed").inc()
            from .engine import make_mixed_step_fn

            raw = make_mixed_step_fn(eng, sampling)
            fn = self.wrap(raw, n_rest=7,
                           out_desc=("r", "r", "r", "pages")
                           + self._moe_extra)
            self.mixed_fns[key] = fn
        return fn

    def wrap_verify(self, raw):
        """Spec-decode verify program (built by spec/verifier.py; the
        SpecDecoder caches per sampling flag)."""
        return self.wrap(raw, n_rest=7,
                         out_desc=("r", "r", "r", "r", "r", "pages"))

    # ----------------------------------------------------- traceability
    def traceable(self, kind: str, sampling: bool = False, k: int = 1,
                  strip_collectives: bool = False):
        """The UNJITTED program for static analysis and the multichip
        harness: shard_map-wrapped at tp>1, the raw python function at
        tp=1. ``kind`` in {"decode", "mixed", "prefill", "suffix"}."""
        eng = self.engine
        if kind == "decode":
            raw, n_rest = eng._make_decode_raw(k, sampling), 5
            out = ("r", "pages", "r", "r", "r") + self._moe_extra
        elif kind == "mixed":
            from .engine import make_mixed_step_fn

            raw, n_rest = make_mixed_step_fn(eng, sampling), 7
            out = ("r", "r", "r", "pages") + self._moe_extra
        elif kind in ("prefill", "suffix"):
            raw = eng._make_prefill_raw(sampling, kind == "suffix")
            n_rest, out = 6, ("r", "r", "r", "pages") + self._moe_extra
        else:
            raise ValueError(f"unknown program kind {kind!r}")
        if not self.sharded:
            return raw
        fn = self.shard(raw, n_rest, out,
                        strip_collectives=strip_collectives)
        fn.__name__ = f"tp_sharded_{kind}_step"
        return fn
