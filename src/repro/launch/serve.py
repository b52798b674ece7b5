"""Serving engine for heterogeneous decentralized diffusion.

Loads a directory of self-describing expert checkpoints (each carries its
objective / schedule / cluster metadata — §5 limitation iv) plus a router
checkpoint, and serves batched text-to-image requests with the paper's
Fig. 2 pipeline on the compute-sparse hot path: router posterior → Top-K
expert selection → **routed-expert-only** native predictions (stacked
params + gather dispatch; CFG batched along the batch axis) → one fused
schedule-aware ε→v-and-combine kernel per Euler step.

Serving properties:

* **compute-sparse** — only the routed experts run each step (k forwards
  instead of K; 1 forward with batched CFG instead of 2), matching the
  paper's claim that Top-K routing pays single-model cost at ensemble
  quality.  Heterogeneous-architecture expert sets fall back to the dense
  fused path automatically.
* **pluggable dispatch** — ``SamplerConfig.dispatch`` selects the expert
  executor backend (``core.dispatch``): ``gathered`` (per-sample param
  gather + vmap, the default), ``grouped`` (sort-based grouped execution:
  one segment pass per resident expert instead of ``B·k`` vmapped lanes —
  the DDM/Paris-style serving layout), or ``dense``.  The per-step
  ``DispatchPlan`` replicates across the mesh
  (``launch.sharding.dispatch_plan_sharding``) while grouped segment
  params resolve from *static* expert slices of the stacked pytree, so
  each shard executes its resident experts' groups without a per-sample
  params all-gather.
* **quantized experts** — ``SamplerConfig.param_dtype`` (CLI
  ``--param-dtype``) stores the stacked expert pytree as a typed
  ``core.param_store.ExpertParamStore``: ``int8``/``fp8`` quantize on
  load with per-expert symmetric scales (~4x fewer resident expert-param
  bytes than fp32), the full-precision per-expert list is dropped, and
  routed slices dequantize through the fused ``hetero_fuse_dequant``
  Pallas kernel — stacked leaves never round-trip through HBM at full
  precision.
* **step-fused** — ``SamplerConfig.step_fused`` (default on) folds the
  CFG combine and the Euler update into the convert-and-fuse kernel
  (``kernels.ops.fused_step``): one fused kernel launch per step, the
  latent read once and written once instead of three latent-sized HBM
  round-trips; ``--no-step-fuse`` restores the unfused op chain.
* **plan reuse** — ``SamplerConfig.plan_refresh_every`` / CLI
  ``--plan-refresh R`` recomputes the router posterior + ``DispatchPlan``
  only every R-th Euler step (posteriors change slowly in t), carrying
  the plan through the scan; R=1 is bit-identical to per-step routing
  and ``stats['plan_refreshes']`` counts refresh work.
* **conditioning cache** — a content-hash-keyed LRU
  (``cond_cache_size`` / ``--cond-cache``) dedupes text embeddings
  across ``submit()``/``generate()`` calls, so the intra-prompt-diversity
  workload (one prompt, many seeds) holds one resident buffer per
  distinct prompt; ``stats['cond_cache_hits'/'cond_cache_misses']``
  expose the behavior.
* **retrace-free** — ``ServingEngine`` caches a jitted sampling function
  per (batch size, latent shape, sampler config, conditioning signature)
  with the noise buffer donated, so repeated requests with the same shape
  never recompile; ``engine.stats['traces']`` exposes the compile count.
* **sharded** — ``n_expert_shards`` / ``n_data_shards`` place the engine
  on an expert-parallel mesh (topology below) so a host never needs to
  hold the full ensemble's parameters per device.
* **cross-request batching** — ``submit()`` enqueues requests and
  ``flush()`` coalesces compatible ones (same latent shape and sampler
  config — engine invariants — plus the same conditioning signature) into
  one sharded batch, slicing per-request outputs back out, so concurrent
  small requests share a single compiled sampler dispatch.

Topology
--------
The sharded engine lives on an ``("expert", "data")`` mesh
(``launch.mesh.make_expert_mesh``):

* the stacked expert store (leaves ``(K, ...)``, ``core.param_store``)
  shards its leading K axis over "expert" — each device group holds
  ``K / n_expert_shards`` resident experts (DDM/Paris-style placement:
  experts are *placed across* devices, not replicated per host).  With
  ``n_expert_shards > 1`` the store is built shard by shard
  (``ServingEngine._place_experts``, host span ``engine.place_experts``):
  each shard's experts are stacked on their own device and the shards
  joined into sharded arrays, so no device ever holds another shard's
  experts, not even during set-up;
* request batches (initial noise, text embeddings, the evolving latent
  state) shard their leading batch dim over "data";
* the ragged backend runs each step's expert forward inside one
  ``shard_map`` over the mesh (``core.dispatch.RaggedExecutor``): every
  device runs all ``B·k`` routed pairs against its own experts' leaves,
  zeroes the pairs it does not own, and one ``psum`` over "expert"
  (device scope ``expert_exchange``) joins the predictions — no expert
  weight crosses devices, and the forward holds no collective.  The
  router, CFG and the fused convert/Euler step run replicated.  The
  gathered and grouped backends still resolve routed slices through
  GSPMD, which gathers them from their owning shards;
* the single-host path is the degenerate 1×1 mesh (or ``mesh=None``) and
  is bit-identical to unsharded serving.

Also exposes ``ServingEngine`` programmatically (used by examples/ and the
benchmark harness).
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import hashlib
import os
import re
import time
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    DenseStore,
    ExpertSpec,
    SamplerConfig,
    coeff_tables_cached,
    make_store,
    pad_to_capacity,
    params_are_stackable,
    sample_ensemble,
)
from repro.core.sampling import _resolve_engine
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_expert_mesh
from repro.launch.sharding import (
    dispatch_plan_sharding,
    expert_param_shardings,
    mesh_scope,
    serve_batch_spec,
)
from repro.models import dit as D
from repro.models.config import DiTConfig, dit_b2, router_b2
from repro.serving.resilience import (
    DeadlineExceeded,
    RequestFailed,
    RequestTimeout,
)
from repro.training import load_checkpoint

#: ``expert7.npz`` / ``expert_07.npz`` → checkpoint index 7 (ordering
#: fallback when the metadata carries no ``cluster_id``).
_EXPERT_IDX_RE = re.compile(r"expert[_-]?(\d+)")

#: Per-capacity-slot health states (elastic membership):
#: ``EMPTY`` — never-filled capacity padding (zero params, masked);
#: ``ACTIVE`` — live, routable;
#: ``DRAINING`` — ``retire_expert``: masked immediately (no NEW routing)
#: but held until the next ``flush()`` completes the in-flight requests
#: admitted under it, then transitions to ``EVICTED``;
#: ``QUARANTINED`` — masked because its artifact/params failed integrity
#: checks (recorded in ``ServingEngine.quarantine``);
#: ``PROBATION`` — masked by the circuit breaker (``trip_expert``:
#: rolling fault score crossed the trip threshold); canary probes on a
#: backoff schedule move it back to ``ACTIVE`` via ``restore_expert``
#: (see ``repro.serving.resilience``);
#: ``EVICTED`` — masked by ``evict_expert``; the slot is reusable by
#: ``add_expert``.
EXPERT_HEALTH_STATES = ("EMPTY", "ACTIVE", "DRAINING", "QUARANTINED",
                        "PROBATION", "EVICTED")


def _validate_expert_params(params, template, path: str) -> None:
    """Integrity gate for a contributor checkpoint's param pytree.

    Raises ``ValueError`` naming the file and the reason: tree-structure
    or leaf-shape mismatch against the ensemble's slot template, or
    non-finite (NaN/Inf) leaf values — the failure classes a corrupt or
    foreign artifact produces *after* the archive itself parsed.
    """
    leaves, treedef = jax.tree.flatten(params)
    if template is not None:
        tdef, shapes = template
        if treedef != tdef:
            raise ValueError(
                f"{path}: param tree structure does not match the "
                f"ensemble's expert template — wrong architecture or a "
                f"partially-written checkpoint"
            )
        for leaf, shape in zip(leaves, shapes):
            if tuple(np.shape(leaf)) != tuple(shape):
                raise ValueError(
                    f"{path}: leaf shape mismatch {tuple(np.shape(leaf))} "
                    f"!= template {tuple(shape)}"
                )
    for leaf in leaves:
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            raise ValueError(
                f"{path}: non-finite leaf values (NaN/Inf) — corrupt "
                f"training artifact"
            )


def _count_shard_rows(bump, rows) -> None:
    """Report an expert shard's executed rows from inside the
    expert-parallel ``shard_map``: ``bump(rows, shard, busiest,
    first_replica)``, where ``busiest`` is the most rows any shard ran
    this step.  Every device reports; only the first replica along the
    mesh's other axes is counted, once per shard."""
    mesh = jax.sharding.get_abstract_mesh()
    first = jnp.bool_(True)
    for axis in mesh.axis_names:
        if axis != "expert":
            first &= jax.lax.axis_index(axis) == 0
    jax.debug.callback(bump, rows, jax.lax.axis_index("expert"),
                       jax.lax.pmax(rows, "expert"), first)


@dataclasses.dataclass
class PendingRequest:
    """Handle returned by ``ServingEngine.submit``; resolved by ``flush``.

    ``state`` walks QUEUED → DONE, or to one of two terminal failure
    states: FAILED once the request's dispatch group exhausted its
    automatic re-queues, or DEADLINE_EXCEEDED once its
    ``deadline_s``/``max_steps`` lifetime bound expired — ``result()``
    then raises the named error (``RequestFailed`` / ``DeadlineExceeded``,
    both carrying the request id and requeue count) instead of hanging
    the caller.  On an elastic engine the request also snapshots the
    membership it was admitted under (store + coefficient tables +
    cluster map, all immutable), so later evictions/hot-adds cannot
    change its output.
    """

    key: jax.Array
    text_emb: jnp.ndarray | None
    batch_size: int
    _result: jnp.ndarray | None = None
    done: bool = False
    state: str = "QUEUED"
    error: BaseException | None = None
    requeues: int = 0
    _membership: tuple | None = None
    #: global submission order (engine-wide monotonic counter) — the
    #: deterministic FIFO key re-queues and the continuous scheduler
    #: order by.  -1 until assigned by ``submit`` (or the scheduler).
    seq: int = -1
    #: lifetime bounds (``repro.serving.resilience``): wall-clock
    #: seconds from submit, and scheduler ticks from submit.  None = no
    #: bound.  ``flush()`` enforces ``deadline_s`` only (it has no tick
    #: granularity); the resilient scheduler enforces both at tick
    #: boundaries.
    deadline_s: float | None = None
    max_steps: int | None = None
    submit_t: float | None = None

    def result(self, timeout: float | None = None) -> jnp.ndarray:
        """Resolved latents, or the request's named terminal error.

        ``timeout`` (seconds) bounds how long to wait for a concurrent
        driver (another thread ticking the scheduler / flushing the
        engine) to resolve this handle; expiry raises
        :class:`~repro.serving.resilience.RequestTimeout` instead of
        blocking forever on a lost request.  ``timeout=None`` keeps the
        classic non-blocking behavior (raise immediately if unresolved);
        ``timeout=0`` is an explicit instant poll.
        """
        if timeout is not None:
            give_up = time.monotonic() + timeout
            while not self.done and self.state not in (
                "FAILED", "DEADLINE_EXCEEDED"
            ):
                if time.monotonic() >= give_up:
                    raise RequestTimeout(
                        f"request seq={self.seq} still {self.state} "
                        f"after {timeout}s ({self.requeues} requeue(s))",
                        seq=self.seq, requeues=self.requeues,
                    )
                time.sleep(min(0.005, max(timeout, 1e-4)))
        if self.state == "DEADLINE_EXCEEDED":
            if isinstance(self.error, DeadlineExceeded):
                raise self.error
            raise DeadlineExceeded(
                f"request seq={self.seq} exceeded its deadline "
                f"({self.requeues} requeue(s))",
                seq=self.seq, requeues=self.requeues,
            )
        if self.state == "FAILED":
            raise RequestFailed(
                f"request seq={self.seq} failed after {self.requeues} "
                f"dispatch attempt(s): {self.error!r}",
                seq=self.seq, requeues=self.requeues,
            ) from self.error
        if not self.done:
            raise RuntimeError(
                "request not yet flushed — submit() only enqueues; call "
                "ServingEngine.flush() to execute the batched dispatch "
                "before reading result()"
            )
        return self._result


@dataclasses.dataclass
class ServingEngine:
    experts: list[ExpertSpec]
    expert_params: list
    router_fn: object | None
    latent_shape: tuple[int, int, int]
    sampler: SamplerConfig = dataclasses.field(default_factory=SamplerConfig)
    #: 'auto' | 'routed' | 'dense' | 'reference' (see core.sample_ensemble)
    engine: str = "auto"
    #: expert-parallel mesh placement (see module docstring "Topology").
    #: Defaults (1, None) keep the classic unsharded single-device path;
    #: setting either stands up an ("expert", "data") mesh — a forced 1×1
    #: mesh is the degenerate case and stays bit-identical.
    n_expert_shards: int = 1
    n_data_shards: int | None = None
    #: cross-request conditioning cache: max distinct text embeddings /
    #: cond pytrees kept resident, keyed by content hash and evicted LRU.
    #: The paper's intra-prompt-diversity workload re-submits the SAME
    #: prompt embedding across many requests (different seeds), so repeat
    #: ``submit()``/``generate()`` calls reuse one device buffer instead
    #: of re-transferring + re-retaining a copy per request.  Applies to
    #: HOST (numpy) inputs only — device-resident ``jax.Array``
    #: embeddings pass through unhashed (no forced device→host copy).
    #: 0 disables.
    cond_cache_size: int = 64
    #: elastic membership: when set, the stacked store pads to this many
    #: capacity slots with a traced ``(K_cap,)`` validity mask, and the
    #: engine gains ``add_expert``/``evict_expert``/``retire_expert``/
    #: ``quarantine_expert`` — membership changes reach the compiled
    #: sampler as new argument *values* (store, coefficient tables,
    #: cluster map), never a retrace.  None keeps the classic
    #: fixed-membership engine bit-identical.
    capacity: int | None = None
    #: automatic re-queues per request before a failing dispatch group
    #: marks its requests FAILED (carrying the exception) instead of
    #: re-poisoning every subsequent ``flush()`` forever.
    max_request_requeues: int = 1
    #: per-slot startup health (elastic): lets ``from_checkpoint_dir``
    #: mark quarantined-at-load slots; defaults to all-ACTIVE.
    initial_health: list | None = None
    #: opt-in dispatch-padding observability: wraps the shared expert
    #: forwards with a ``jax.debug.callback`` row counter so
    #: ``stats['padded_model_rows']`` tracks rows the backend *executed*
    #: (grouped: power-of-two bucket padding included; ragged: exactly
    #: the routed rows) against the ``routed_model_rows`` the plans
    #: asked for — read via :meth:`padding_stats`.  On an expert mesh
    #: each shard counts the rows it ran (its owned pairs × g), in
    #: ``shard_model_rows``, and the busiest shard's, in
    #: ``busiest_shard_model_rows``.  Off by default: the callback forces
    #: host sync points on the hot path.
    track_padding: bool = False

    def __post_init__(self) -> None:
        self._compiled: dict = {}
        self._queue: list[PendingRequest] = []
        self._seq = 0                      # global submission counter
        self._cond_cache: OrderedDict[tuple, jnp.ndarray] = OrderedDict()
        self.stats = {"traces": 0, "requests": 0,
                      "merged_batches": 0, "batched_requests": 0,
                      "cond_cache_hits": 0, "cond_cache_misses": 0,
                      "plan_refreshes": 0,
                      "experts_added": 0, "experts_evicted": 0,
                      "quarantined_checkpoints": 0, "degraded_steps": 0,
                      "request_requeues": 0, "failed_requests": 0,
                      "padded_model_rows": 0, "routed_model_rows": 0,
                      "model_steps": 0, "shard_model_rows": {},
                      "busiest_shard_model_rows": 0,
                      "deadline_exceeded": 0, "watchdog_trips": 0,
                      "breaker_trips": 0, "breaker_probes": 0,
                      "breaker_restores": 0, "journal_snapshots": 0}
        self.quarantine: list[dict] = []
        if self.track_padding:
            self._instrument_row_counting()
        self.elastic = self.capacity is not None
        self.homogeneous = len(self.experts) <= 1 or (
            all(e.apply_fn is self.experts[0].apply_fn for e in self.experts)
            and params_are_stackable(self.expert_params)
        )
        # Typed stacked-expert store (core.param_store): the routed
        # engine's dispatch substrate.  ``sampler.param_dtype`` selects
        # the storage — 'native' keeps checkpoint precision
        # (bit-identical), 'int8'/'fp8' quantize with per-expert scales
        # (~4x fewer resident expert-param bytes vs fp32).
        pd = self.sampler.param_dtype
        quantized = pd in ("int8", "fp8")
        if pd != "native":
            # The store only serves ROUTED execution: a dense/reference
            # engine (heterogeneous set, strategy='full', single expert,
            # engine override) runs from the per-expert params list at
            # native precision — accepting param_dtype there would either
            # lie about resident bytes (cast dtypes: unused store built
            # next to the fp32 list) or construct an engine whose every
            # generate() fails later (quantized dtypes drop that list).
            # Reject at construction, where strategy/engine are known.
            routed_capable = (
                self.homogeneous and len(self.experts) > 1
                and self.sampler.strategy in ("top1", "topk", "threshold")
                and self.engine in ("auto", "routed")
            )
            if not routed_capable:
                raise ValueError(
                    f"param_dtype={pd!r} changes the stacked expert "
                    f"store's storage, which only routed execution uses: "
                    f"it needs a homogeneous ensemble of ≥ 2 experts "
                    f"(shared apply_fn + stackable params), strategy in "
                    f"top1/topk/threshold, and engine auto/routed — got "
                    f"{len(self.experts)} expert(s), homogeneous="
                    f"{self.homogeneous}, strategy="
                    f"{self.sampler.strategy!r}, engine={self.engine!r}"
                )
        self.mesh = None
        if self.n_expert_shards != 1 or self.n_data_shards is not None:
            slots = self.capacity or len(self.experts)
            if self.n_expert_shards > 1 and (
                len(self.experts) % self.n_expert_shards
                or slots % self.n_expert_shards
            ):
                # sanitize_spec would silently fall back to replicating
                # the expert axis — zero memory savings while reporting a
                # sharded mesh; make the misconfiguration loud instead.
                raise ValueError(
                    f"n_expert_shards={self.n_expert_shards} does not "
                    f"divide the {len(self.experts)}-expert ensemble "
                    f"({slots} slots); expert placement would silently "
                    f"replicate"
                )
            self.mesh = make_expert_mesh(self.n_expert_shards,
                                         self.n_data_shards)
        with TraceAnnotation("engine.place_experts"):
            self.param_store = None
            if self.homogeneous and self.expert_params:
                self.param_store = (
                    self._place_experts(pd) if self.n_expert_shards > 1
                    else make_store(D.stack_expert_params(
                        self.expert_params), dtype=pd)
                )
        # Slot template for integrity-validating incoming checkpoints
        # (captured before a quantized store drops the fp list).
        self._slot_template = None
        if self.expert_params:
            leaves, treedef = jax.tree.flatten(self.expert_params[0])
            self._slot_template = (
                treedef, [tuple(np.shape(leaf)) for leaf in leaves]
            )
        #: dispatches run routed over the stacked store and never read
        #: the per-expert list
        self._store_only = (self.param_store is not None
                            and self._resolves_routed())
        if quantized:
            # The quantized store IS the resident representation: drop
            # the full-precision per-expert list so the ~4x byte saving
            # is real, not an extra copy.  (The dense fallback and the
            # reference engine need that list; they raise clearly.)
            self.expert_params = None
        elif self._store_only:
            # The per-expert list (what the reference and dense engines
            # read) stays in host memory, so the device holds the
            # weights once, not twice.
            self.expert_params = jax.device_get(self.expert_params)
        self.expert_health = ["ACTIVE"] * len(self.experts)
        self.membership_epoch = 0
        if self.elastic:
            self._init_elastic()
        if self.router_fn is not None and not isinstance(
            self.router_fn, jax.tree_util.Partial
        ):
            # A pytree callable can be a jit argument (its bound arrays,
            # if any, are traced rather than baked into the program).
            self.router_fn = jax.tree_util.Partial(self.router_fn)
        if self.mesh is not None:
            if self.param_store is not None:
                self.param_store = self._put_store(self.param_store)
            self.router_fn = jax.device_put(
                self.router_fn, NamedSharding(self.mesh, P())
            )

    def _place_experts(self, pd: str):
        """The stacked store of an expert mesh, built shard by shard.

        Shard ``s`` holds capacity slots ``s·K_cap/N … (s+1)·K_cap/N − 1``
        (``K_cap`` is ``capacity`` on an elastic engine, else K): its
        experts are moved to the device at mesh row ``s``, stacked and
        stored (quantized, capacity-padded) there, so a scale or padding
        leaf lives with its shard and no device ever holds another
        shard's experts.  The shards' leaves are then joined into
        ``(K_cap, …)`` arrays sharded ``P("expert")`` without a copy
        (replicated over "data" on a mesh that has it).  The validity mask
        is left to ``_init_elastic``.
        """
        rows = self.mesh.devices                      # (N, data) devices
        n, k = rows.shape[0], len(self.expert_params)
        per = (self.capacity or k) // n
        shards = []
        for s in range(n):
            home = rows[s, 0]
            ids = range(s * per, min((s + 1) * per, k))
            with jax.default_device(home):
                params = [jax.device_put(self.expert_params[e], home)
                          for e in ids]
                if not params:              # capacity pad only: a 0-stack
                    params = [jax.tree.map(
                        lambda x: jnp.zeros(np.shape(x), x.dtype),
                        self.expert_params[0])]
                store = make_store(D.stack_expert_params(params), dtype=pd)
                if not ids:
                    store = store.static_slice(0, 0)
                if store.num_experts < per:
                    store = pad_to_capacity(store, per)
            shards.append(store.with_valid(None))
        sharding = NamedSharding(self.mesh, P("expert"))

        def join(*parts):
            arrays = [part if d == 0 else jax.device_put(part, rows[s, d])
                      for s, part in enumerate(parts)
                      for d in range(rows.shape[1])]
            return jax.make_array_from_single_device_arrays(
                (n * per,) + parts[0].shape[1:], sharding, arrays)

        return dataclasses.replace(jax.tree.map(join, *shards),
                                   num_experts=n * per)

    def _put_store(self, store):
        """Place a store on the expert mesh (no-op unsharded).

        Stores are registered pytrees: the quantized scales AND the
        elastic validity mask are ``(K,)`` leaves annotated with the same
        leading "expert" axis, so they shard with the leaves they
        rescale/gate.  Membership updates re-place the (functionally
        new) store through the same shardings.
        """
        if store is None or self.mesh is None:
            return store
        return jax.device_put(
            store,
            expert_param_shardings(
                store, self.mesh, logical_axes=store.logical_axes(),
            ),
        )

    # -- dispatch-padding observability -------------------------------------

    def _instrument_row_counting(self) -> None:
        """Wrap the shared expert forwards with runtime row counters.

        One wrapper per forward kind, shared by every spec — the
        homogeneity check (and ragged eligibility) compares functions by
        identity, so per-spec closures would silently force the dense
        engine.  ``jax.debug.callback`` fires only in branches that
        execute, which is the point: the grouped trace holds every
        power-of-two bucket branch, and trace-time counting would tally
        padding that never runs.
        """
        if not self.experts:
            return
        if any(e.apply_fn is not self.experts[0].apply_fn
               for e in self.experts):
            raise ValueError(
                "track_padding=True needs a homogeneous ensemble (one "
                "shared apply_fn): heterogeneous sets run the dense "
                "executor, which has no dispatch padding to observe"
            )

        def _bump(rows, shard=0, busiest=None, first_replica=True):
            if not first_replica:
                return
            rows, shard = int(rows), int(shard)
            self.stats["padded_model_rows"] += rows
            per_shard = self.stats["shard_model_rows"]
            per_shard[shard] = per_shard.get(shard, 0) + rows
            if shard == 0:
                self.stats["busiest_shard_model_rows"] += (
                    rows if busiest is None else int(busiest))

        base_apply = self.experts[0].apply_fn

        def counted_apply(params, x, t, **cond):
            jax.debug.callback(_bump, x.shape[0])
            return base_apply(params, x, t, **cond)

        base_ragged = getattr(self.experts[0], "ragged_apply_fn", None)
        counted_ragged = None
        if base_ragged is not None:
            def counted_ragged(view, x_p, t_p, cond, pe, g, live=None):
                if live is None:
                    jax.debug.callback(_bump, x_p.shape[0] * g)
                else:
                    _count_shard_rows(_bump, jnp.sum(live, dtype=jnp.int32)
                                      * g)
                return base_ragged(view, x_p, t_p, cond, pe, g, live=live)

        self.experts = [
            dataclasses.replace(e, apply_fn=counted_apply,
                                ragged_apply_fn=counted_ragged)
            for e in self.experts
        ]

    def _count_routed_rows(self, batch_size: int, has_text: bool) -> None:
        """Deterministic per-dispatch routed-row demand: ``B·k·g·S`` —
        the rows the plans ask for, before any backend padding."""
        if not self.track_padding:
            return
        k_cap = max(len(self.experts), 1)
        k_slots = 1 if self.sampler.strategy in ("top1", "threshold") \
            else min(self.sampler.top_k, k_cap)
        g = 2 if (has_text and self.sampler.cfg_scale != 1.0) else 1
        steps = self.sampler.num_steps
        self.stats["routed_model_rows"] += batch_size * k_slots * g * steps
        self.stats["model_steps"] += steps

    def padding_stats(self) -> dict:
        """Flush pending row-count callbacks and derive per-step padding
        figures into ``stats`` (requires ``track_padding=True``).

        ``padded_rows_per_step`` is the runtime-executed row count per
        sampling step; ``padding_overhead`` is executed/routed − 1 (the
        grouped backend's bucket padding tax; 0.0 under ``ragged``, on
        an expert mesh too, where each shard runs only its owned pairs).
        ``shard_rows_per_step`` splits the executed rows by expert shard
        and ``busiest_shard_rows_per_step`` is the busiest shard's rows,
        averaged over steps: on an expert mesh that shard sets the step.
        """
        if not self.track_padding:
            raise ValueError(
                "padding stats need ServingEngine(track_padding=True) — "
                "row counting instruments the expert forwards at "
                "construction time"
            )
        jax.effects_barrier()                  # callbacks may be in flight
        steps = max(self.stats["model_steps"], 1)
        routed = max(self.stats["routed_model_rows"], 1)
        self.stats["padded_rows_per_step"] = (
            self.stats["padded_model_rows"] / steps
        )
        self.stats["routed_rows_per_step"] = (
            self.stats["routed_model_rows"] / steps
        )
        self.stats["padding_overhead"] = (
            self.stats["padded_model_rows"] / routed - 1.0
        )
        self.stats["shard_rows_per_step"] = {
            s: rows / steps
            for s, rows in sorted(self.stats["shard_model_rows"].items())
        }
        self.stats["busiest_shard_rows_per_step"] = (
            self.stats["busiest_shard_model_rows"] / steps
        )
        return {
            k: self.stats[k]
            for k in ("padded_rows_per_step", "routed_rows_per_step",
                      "padding_overhead", "shard_rows_per_step",
                      "busiest_shard_rows_per_step")
        }

    # -- elastic membership -------------------------------------------------

    def _init_elastic(self) -> None:
        k0 = len(self.experts)
        if self.param_store is None:
            raise ValueError(
                "elastic serving (capacity=...) needs a homogeneous "
                "ensemble with stackable params — the validity-masked "
                "capacity layout lives in the stacked ExpertParamStore"
            )
        if self.capacity < k0:
            raise ValueError(
                f"capacity={self.capacity} < {k0} loaded experts"
            )
        if self.sampler.strategy not in ("top1", "topk"):
            raise ValueError(
                f"elastic serving requires per-sample routing (strategy "
                f"'top1' or 'topk'); got {self.sampler.strategy!r}"
            )
        if self.engine not in ("auto", "routed"):
            raise ValueError(
                f"elastic serving requires the routed engine (engine "
                f"'auto' or 'routed'); got {self.engine!r}"
            )
        if self.router_fn is None:
            raise ValueError(
                "elastic serving routes per sample; a router_fn is "
                "required"
            )
        if self.sampler.ddpm_low_noise_only > 0.0:
            raise ValueError(
                "elastic serving is incompatible with ddpm_low_noise_only "
                "> 0: the §7.3 gate bakes each slot's objective into the "
                "trace, so a hot-added expert changing a slot's objective "
                "would silently bypass it"
            )
        # Own the membership lists: slots mutate on add/evict and must not
        # alias the caller's.
        self.experts = list(self.experts)
        health = (list(self.initial_health) if self.initial_health
                  else ["ACTIVE"] * k0)
        if len(health) != k0 or any(
            h not in EXPERT_HEALTH_STATES for h in health
        ):
            raise ValueError(
                f"initial_health must be {k0} states from "
                f"{EXPERT_HEALTH_STATES}; got {health}"
            )
        # Capacity padding: EMPTY slots carry zero params, a placeholder
        # spec (same apply_fn — objectives/schedules reach the sampler as
        # traced coefficient tables, so the placeholder values never
        # execute), and a dead validity bit.
        for i in range(k0, self.capacity):
            self.experts.append(dataclasses.replace(
                self.experts[0], name=f"<empty:{i}>", objective="fm",
                schedule="linear", cluster_id=0,
            ))
        self.expert_health = health + ["EMPTY"] * (self.capacity - k0)
        if self.param_store.num_experts < self.capacity:
            self.param_store = pad_to_capacity(self.param_store,
                                               self.capacity)
        mask = jnp.array([h == "ACTIVE" for h in self.expert_health])
        self.param_store = self.param_store.with_valid(mask)
        self._refresh_membership_arrays()

    def _refresh_membership_arrays(self) -> None:
        """Rebuild the traced membership side-cars from the slot specs.

        The ``(S, 5, K_cap)`` unified-coefficient tables and the
        ``(K_cap,)`` cluster map are jit *arguments* on elastic engines —
        a hot-added expert's objective/schedule/cluster lands as new
        values under the existing trace (``coeff_tables_cached`` makes
        the rebuild a process-wide cache hit for repeated memberships).
        """
        self._coeff_tables = coeff_tables_cached(
            tuple(e.objective for e in self.experts),
            tuple(e.schedule for e in self.experts),
            self.sampler.num_steps, self.sampler.conversion,
        )
        self._cluster_map = jnp.array(
            [max(e.cluster_id, 0) for e in self.experts], jnp.int32
        )

    def _membership(self) -> tuple | None:
        """Immutable admission-time snapshot (epoch, store, tables, map).

        Store/table/map updates are pure-functional, so holding the tuple
        pins a request's routing substrate bit-exactly whatever
        membership ops happen before its flush.
        """
        if not self.elastic:
            return None
        return (self.membership_epoch, self.param_store,
                self._coeff_tables, self._cluster_map)

    def _require_elastic(self, op: str) -> None:
        if not self.elastic:
            raise ValueError(
                f"{op} requires an elastic engine — construct the "
                f"ServingEngine with capacity=<K_cap> (or "
                f"from_checkpoint_dir(capacity=...))"
            )

    @property
    def num_live_experts(self) -> int:
        return sum(h == "ACTIVE" for h in self.expert_health)

    def add_expert(self, ckpt_path: str, *, slot: int | None = None) -> int:
        """Hot-add a contributor checkpoint into a free capacity slot.

        Pipeline: integrity-validate (named ``ValueError``s; failures are
        recorded in ``self.quarantine`` and counted before re-raising —
        the engine itself stays healthy) → quantize per
        ``sampler.param_dtype`` into the slot (``store.set_expert``) →
        incremental router-cluster refresh (coefficient tables + cluster
        map rebuilt from the slot specs) → flip the slot's validity bit.
        A reader can never observe a half-installed expert: the store
        update is functional and the mask flips last, in the same new
        store object.  Returns the slot index.
        """
        self._require_elastic("add_expert")
        if slot is None:
            free = [i for i, h in enumerate(self.expert_health)
                    if h in ("EMPTY", "EVICTED")]
            if not free:
                raise RuntimeError(
                    f"no free capacity slot (capacity={self.capacity}, "
                    f"health={self.expert_health}); evict or retire an "
                    f"expert first"
                )
            slot = free[0]
        elif self.expert_health[slot] in ("ACTIVE", "DRAINING"):
            raise ValueError(
                f"slot {slot} is {self.expert_health[slot]}; evict it "
                f"before overwriting"
            )
        try:
            params, meta = load_checkpoint(ckpt_path)
            for field in ("objective", "schedule"):
                if field not in meta:
                    raise ValueError(
                        f"{ckpt_path}: metadata missing {field!r} — not a "
                        f"self-describing expert checkpoint"
                    )
            _validate_expert_params(params, self._slot_template, ckpt_path)
        except (ValueError, FileNotFoundError) as e:
            self.quarantine.append(
                {"path": ckpt_path, "reason": str(e), "slot": None}
            )
            self.stats["quarantined_checkpoints"] += 1
            raise
        store = self.param_store.set_expert(slot, params)
        store = store.with_valid(store.valid_mask().at[slot].set(True))
        cid = int(meta.get("cluster_id", slot))
        self.experts[slot] = dataclasses.replace(
            self.experts[0],
            name=meta.get("name", os.path.basename(ckpt_path)),
            objective=meta["objective"], schedule=meta["schedule"],
            cluster_id=max(cid, 0),
        )
        self.expert_health[slot] = "ACTIVE"
        self.param_store = self._put_store(store)
        self._refresh_membership_arrays()
        self.membership_epoch += 1
        self.stats["experts_added"] += 1
        return slot

    def _mask_slot(self, e: int, state: str) -> int:
        if not (0 <= e < len(self.experts)):
            raise IndexError(
                f"expert slot {e} out of range [0, {len(self.experts)})"
            )
        if self.expert_health[e] not in ("ACTIVE", "DRAINING"):
            raise ValueError(
                f"slot {e} is {self.expert_health[e]}, not servable"
            )
        store = self.param_store.with_valid(
            self.param_store.valid_mask().at[e].set(False)
        )
        self.param_store = self._put_store(store)
        self.expert_health[e] = state
        self.membership_epoch += 1
        return e

    def evict_expert(self, e: int) -> int:
        """Mask slot ``e`` immediately (state ``EVICTED``).

        New ``generate``/``submit`` calls route over the survivors; any
        already-``submit()``ed request completes against its
        admission-time membership snapshot, bit-identical to a flush
        issued before the eviction.
        """
        self._require_elastic("evict_expert")
        self._mask_slot(e, "EVICTED")
        self.stats["experts_evicted"] += 1
        return e

    def retire_expert(self, e: int) -> int:
        """Graceful eviction: masked immediately, ``DRAINING`` until the
        next ``flush()`` completes the in-flight requests admitted under
        it, then ``EVICTED`` (and reusable by ``add_expert``)."""
        self._require_elastic("retire_expert")
        self._mask_slot(e, "DRAINING")
        self.stats["experts_evicted"] += 1
        return e

    def quarantine_expert(self, e: int, reason: str = "") -> int:
        """Mask slot ``e`` as ``QUARANTINED`` (suspect params at runtime,
        e.g. a health checker caught NaNs) and record it."""
        self._require_elastic("quarantine_expert")
        self._mask_slot(e, "QUARANTINED")
        self.quarantine.append(
            {"path": self.experts[e].name, "reason": reason or "runtime",
             "slot": e}
        )
        self.stats["quarantined_checkpoints"] += 1
        return e

    def trip_expert(self, e: int, reason: str = "") -> int:
        """Circuit-breaker trip: mask slot ``e`` as ``PROBATION``.

        Exactly the ``quarantine_expert`` masking path (validity-bit
        flip + epoch bump through ``_mask_slot`` — capacity-stable
        shapes, never a retrace), but the slot stays owned by the
        breaker: canary probes (``serving.resilience``) move it back to
        ``ACTIVE`` via :meth:`restore_expert` on a finite pass."""
        self._require_elastic("trip_expert")
        self._mask_slot(e, "PROBATION")
        self.quarantine.append(
            {"path": self.experts[e].name,
             "reason": reason or "breaker trip", "slot": e}
        )
        self.stats["breaker_trips"] += 1
        return e

    def restore_expert(self, e: int) -> int:
        """Un-mask a ``PROBATION``/``QUARANTINED`` slot back to
        ``ACTIVE`` (validity-bit flip + epoch bump — no retrace).  The
        breaker calls this after a passing canary probe; operators can
        call it directly after re-validating a quarantined slot."""
        self._require_elastic("restore_expert")
        if not (0 <= e < len(self.experts)):
            raise IndexError(
                f"expert slot {e} out of range [0, {len(self.experts)})"
            )
        if self.expert_health[e] not in ("PROBATION", "QUARANTINED"):
            raise ValueError(
                f"slot {e} is {self.expert_health[e]}; only PROBATION/"
                f"QUARANTINED slots can be restored"
            )
        store = self.param_store.with_valid(
            self.param_store.valid_mask().at[e].set(True)
        )
        self.param_store = self._put_store(store)
        self.expert_health[e] = "ACTIVE"
        self.membership_epoch += 1
        return e

    def _note_degraded(self, store, steps: int | None = None) -> None:
        """Count degraded-mode steps: serving with fewer live experts
        than the routing width wants (k slots renormalize over the
        survivors — correct, but quality-degraded; §3.1).

        ``steps`` overrides the per-dispatch step count: a lockstep
        dispatch runs ``num_steps`` Euler steps, a rolling-scheduler
        tick runs exactly one."""
        if not self.elastic:
            return
        n_live = int(np.asarray(store.valid_mask()).sum())
        k_slots = 1 if self.sampler.strategy == "top1" \
            else min(self.sampler.top_k, store.num_experts)
        if n_live < k_slots:
            self.stats["degraded_steps"] += (
                self.sampler.num_steps if steps is None else steps
            )

    def membership_line(self) -> str:
        """One-line membership/fault summary (the serve CLI prints it, and
        the quarantine counters round-trip through it — tested)."""
        s = self.stats
        cap = self.capacity if self.elastic else len(self.experts)
        probation = sum(h == "PROBATION" for h in self.expert_health)
        return (f"membership: live={self.num_live_experts}/{cap} "
                f"added={s['experts_added']} "
                f"evicted={s['experts_evicted']} "
                f"quarantined={s['quarantined_checkpoints']} "
                f"degraded_steps={s['degraded_steps']} "
                f"requeues={s['request_requeues']} "
                f"failed={s['failed_requests']} "
                f"probation={probation} "
                f"trips={s['breaker_trips']} "
                f"probes={s['breaker_probes']} "
                f"restores={s['breaker_restores']} "
                f"deadline_exceeded={s['deadline_exceeded']}")

    def restore(self, journal_dir: str, **kwargs):
        """Crash recovery: rebuild a resilient scheduler from a request
        journal written by a previous process and re-admit its in-flight
        requests at their last snapshot (bitwise-identical continuation —
        see ``repro.serving.resilience.ResilientScheduler.restore`` for
        the exact semantics and membership-verification rules).  The
        engine must be assembled from the same checkpoints/membership
        the journal was written under.  Returns the scheduler."""
        from repro.serving.resilience import ResilientScheduler

        return ResilientScheduler.restore(self, journal_dir, **kwargs)

    @property
    def stacked_params(self):
        """Back-compat view of the dispatch substrate.

        Dense stores expose their raw stacked pytree (the pre-store
        convention); quantized stores return the store itself — reading
        full-precision stacked leaves out of a quantized engine would
        defeat its resident-byte budget.
        """
        if isinstance(self.param_store, DenseStore):
            return self.param_store.stacked
        return self.param_store

    @classmethod
    def from_checkpoint_dir(
        cls, ckpt_dir: str, *, dit_cfg: DiTConfig,
        router_cfg: DiTConfig | None = None,
        sampler: SamplerConfig | None = None,
        engine: str = "auto",
        param_dtype: str | None = None,
        n_expert_shards: int = 1,
        n_data_shards: int | None = None,
        cond_cache_size: int = 64,
        capacity: int | None = None,
        on_bad_checkpoint: str = "raise",
        track_padding: bool = False,
    ) -> "ServingEngine":
        """Assemble an engine from a directory of expert checkpoints.

        Experts are ordered **numerically by cluster id** (from each
        checkpoint's metadata, falling back to the ``expert<N>.npz``
        filename index), never lexicographically — with ≥10 experts
        ``sorted(glob(...))`` would load ``expert10`` before ``expert2``
        and silently scramble the router's positional cluster→expert
        mapping.  Duplicate cluster ids always raise.

        ``on_bad_checkpoint`` controls what a corrupt/truncated/
        shape-mismatched artifact does: ``'raise'`` (default) propagates
        the named ``ValueError``; ``'skip'`` quarantines the file
        (recorded on ``engine.quarantine`` and in
        ``stats['quarantined_checkpoints']``) and serves the remaining
        experts, filling any cluster-id hole the bad file leaves with a
        masked EMPTY slot — which forces the elastic (capacity) path so
        the hole never routes.  ``capacity`` (> number of slots) reserves
        padded slots for :meth:`add_expert` hot-joins.

        ``param_dtype`` (overrides ``sampler.param_dtype`` when given)
        selects the stacked-store storage: ``'int8'``/``'fp8'`` quantize
        **on load** and drop the full-precision per-expert list, so an
        8-expert ensemble holds ~¼ the resident expert-param bytes of
        the fp32 checkpoints it was assembled from.
        """
        if on_bad_checkpoint not in ("raise", "skip"):
            raise ValueError(
                f"on_bad_checkpoint must be 'raise' or 'skip', "
                f"got {on_bad_checkpoint!r}"
            )
        apply_fn = D.make_expert_apply(dit_cfg)
        # One shared pair-major ragged forward per ensemble: publishing it
        # on every ExpertSpec makes dispatch='auto' pick the one-kernel
        # ragged grouped-GEMM backend (class-conditional configs keep the
        # grouped backend — the ragged forward is text/uncond only).
        ragged_fn = None
        if not dit_cfg.num_classes:
            ragged_fn = D.make_ragged_expert_apply(dit_cfg)
        paths = glob.glob(os.path.join(ckpt_dir, "expert*.npz"))
        if not paths:
            raise FileNotFoundError(f"no expert*.npz under {ckpt_dir}")
        loaded: list[tuple[int, str, object, dict]] = []
        quarantined: list[dict] = []
        template = None
        for path in sorted(paths):
            try:
                p, meta = load_checkpoint(path)
                for field in ("objective", "schedule"):
                    if field not in meta:
                        raise ValueError(
                            f"{path}: missing '{field}' metadata — not a "
                            f"self-describing expert checkpoint"
                        )
                cid = int(meta.get("cluster_id", -1))
                if cid < 0:
                    m = _EXPERT_IDX_RE.search(os.path.basename(path))
                    if m is None:
                        raise ValueError(
                            f"{path}: no cluster_id metadata and no numeric "
                            f"index in the filename — cannot place this "
                            f"expert"
                        )
                    cid = int(m.group(1))
                if template is None:
                    leaves, treedef = jax.tree_util.tree_flatten(p)
                    template = (treedef, [tuple(np.shape(x)) for x in leaves])
                else:
                    _validate_expert_params(p, template, path)
            except (ValueError, FileNotFoundError) as e:
                if on_bad_checkpoint == "raise":
                    raise
                quarantined.append({"path": path, "reason": str(e)})
                continue
            loaded.append((cid, path, p, meta))
        if not loaded:
            raise ValueError(
                f"every expert checkpoint under {ckpt_dir} was quarantined: "
                f"{[q['path'] for q in quarantined]}"
            )
        seen: dict[int, str] = {}
        for cid, path, _, _ in loaded:
            if cid in seen:
                raise ValueError(
                    f"duplicate cluster_id {cid}: {seen[cid]} and {path}"
                )
            seen[cid] = path
        n_slots = max(seen) + 1
        holes = sorted(set(range(n_slots)) - set(seen))
        if holes and on_bad_checkpoint == "raise":
            raise ValueError(
                f"expert checkpoints must cover cluster ids 0..{n_slots - 1} "
                f"exactly (the router posterior's columns are positional); "
                f"got {sorted(seen)} — missing {holes}"
            )
        loaded.sort(key=lambda item: item[0])
        by_cid = {cid: (path, p, meta) for cid, path, p, meta in loaded}
        experts, params, health = [], [], []
        for cid in range(n_slots):
            if cid in by_cid:
                path, p, meta = by_cid[cid]
                experts.append(ExpertSpec(
                    name=meta.get("name", os.path.basename(path)),
                    objective=meta["objective"],
                    schedule=meta["schedule"],
                    apply_fn=apply_fn,
                    cluster_id=cid,
                    ragged_apply_fn=ragged_fn,
                ))
                params.append(p)
                health.append("ACTIVE")
            else:
                # Masked placeholder for a quarantined slot: zero params,
                # valid=False — never routed, never gathered.
                experts.append(ExpertSpec(
                    name=f"<quarantined:{cid}>", objective="fm",
                    schedule="linear", apply_fn=apply_fn, cluster_id=cid,
                    ragged_apply_fn=ragged_fn,
                ))
                params.append(jax.tree.map(jnp.zeros_like, loaded[0][2]))
                health.append("EMPTY")
        if holes and capacity is None:
            capacity = n_slots                   # masking needs elastic mode
        router_fn = None
        router_path = os.path.join(ckpt_dir, "router.npz")
        if router_cfg is not None and os.path.exists(router_path):
            rp, _ = load_checkpoint(router_path)
            router_fn = D.make_router_fn(router_cfg, rp)
        sampler = sampler if sampler is not None else SamplerConfig()
        if param_dtype is not None:
            sampler = dataclasses.replace(sampler, param_dtype=param_dtype)
        eng = cls(
            experts=experts, expert_params=params, router_fn=router_fn,
            latent_shape=(dit_cfg.latent_size, dit_cfg.latent_size,
                          dit_cfg.latent_channels),
            sampler=sampler,
            engine=engine,
            n_expert_shards=n_expert_shards, n_data_shards=n_data_shards,
            cond_cache_size=cond_cache_size,
            capacity=capacity,
            initial_health=health if capacity is not None else None,
            track_padding=track_padding,
        )
        if quarantined:
            eng.quarantine.extend(quarantined)
            eng.stats["quarantined_checkpoints"] += len(quarantined)
        return eng

    # -- cross-request conditioning cache -----------------------------------

    def _cached_cond(self, text_emb):
        """Content-hash-keyed LRU over conditioning arrays.

        Requests carrying byte-identical embeddings (the common case for
        the paper's intra-prompt-diversity workload: one prompt, many
        seeds) resolve to ONE resident device buffer; distinct contents
        evict least-recently-used.  ``stats['cond_cache_hits'/'..misses']``
        expose the behavior.  Hashing happens on host bytes, off the
        compiled hot path — and therefore only for HOST inputs: an
        embedding already resident on device (``jax.Array``) passes
        through untouched, because hashing it would force a blocking
        device→host transfer per request just to dedupe a buffer the
        caller is already sharing.
        """
        if text_emb is None:
            return None
        if isinstance(text_emb, jax.Array) or self.cond_cache_size <= 0:
            return jnp.asarray(text_emb)
        arr = np.asarray(text_emb)
        key = (arr.shape, str(arr.dtype),
               hashlib.sha1(arr.tobytes()).hexdigest())
        cached = self._cond_cache.get(key)
        if cached is not None:
            self._cond_cache.move_to_end(key)
            self.stats["cond_cache_hits"] += 1
            return cached
        self.stats["cond_cache_misses"] += 1
        val = jnp.asarray(arr)
        self._cond_cache[key] = val
        while len(self._cond_cache) > self.cond_cache_size:
            self._cond_cache.popitem(last=False)
        return val

    def _count_plan_refreshes(self) -> None:
        """One sampler dispatch refreshes the plan ceil(S/R) times (the
        i % R == 0 steps of the scan) — deterministic, so counted exactly
        without a runtime callback on the hot path."""
        r = max(1, self.sampler.plan_refresh_every)
        self.stats["plan_refreshes"] += -(-self.sampler.num_steps // r)

    # -- retrace-free compiled-sampler cache --------------------------------

    def _sampler_args(self, membership: tuple | None = None) -> tuple:
        """What a compiled sampler reads besides the request, as jit
        arguments: ``(expert params, store, router, coeff tables,
        cluster map)``.

        Closed over instead, every array would be embedded in the XLA
        program as a constant — at dit-b2 widths gigabytes of weights
        inside the HLO.  ``membership`` is an admission-time snapshot
        ``(epoch, store, tables, cmap)``; ``None`` means the current one
        (a fixed-membership engine has no tables or map).  The
        per-expert params list rides along only where the resolved
        engine runs it (dense / reference, or routed without a store).
        """
        if membership is None and self.elastic:
            membership = self._membership()
        if membership is None:
            store, tables, cmap = self.param_store, None, None
        else:
            _, store, tables, cmap = membership
        params = None if self._store_only else self.expert_params
        return params, store, self.router_fn, tables, cmap

    def _resolves_routed(self) -> bool:
        """Whether dispatches resolve to the routed engine; False for a
        misconfigured engine, whose first dispatch raises the error."""
        try:
            return _resolve_engine(self.engine, self.experts,
                                   self.expert_params,
                                   self.sampler) == "routed"
        except ValueError:
            return False

    def _sampler_arg_shardings(self) -> list:
        """Mesh shardings of :meth:`_sampler_args`, in order."""
        rep = NamedSharding(self.mesh, P())
        store = rep if self.param_store is None else expert_param_shardings(
            self.param_store, self.mesh,
            logical_axes=self.param_store.logical_axes(),
        )
        return [rep, store, rep, rep, rep]

    def _get_compiled(self, batch_size: int, has_text: bool) -> Callable:
        """Jitted sampler keyed by everything that changes the trace.

        Called as ``fn(key, noise, text, *self._sampler_args(...))``.
        Elastic engines' membership substrate — store (with its validity
        mask), coefficient tables, cluster map — is argument *data*, so
        every epoch hits the same compiled fn (shapes are
        capacity-stable).  The initial-noise buffer is donated — XLA
        reuses it for the evolving latent state instead of allocating a
        fresh buffer per request.  On a sharded engine the noise/text
        inputs carry explicit "data"-axis shardings and the latent state
        is pinned to them throughout the scan.
        """
        cache_key = (batch_size, self.latent_shape, self.sampler,
                     self.engine, has_text)
        fn = self._compiled.get(cache_key)
        if fn is None:
            shape = (batch_size,) + self.latent_shape
            latent_sharding = None
            plan_sharding = None
            jit_kwargs: dict = {}
            if self.mesh is not None:
                lat_spec = serve_batch_spec(self.mesh, shape)
                latent_sharding = NamedSharding(self.mesh, lat_spec)
                plan_sharding = dispatch_plan_sharding(self.mesh)
                batch_sharded = len(lat_spec) > 0 and lat_spec[0] is not None
                text_spec = P("data") if (has_text and batch_sharded) else P()
                jit_kwargs["in_shardings"] = (
                    NamedSharding(self.mesh, P()),        # PRNG key
                    latent_sharding,                      # initial noise
                    NamedSharding(self.mesh, text_spec),  # text embeddings
                    *self._sampler_arg_shardings(),
                )

            def _sample(key, noise, text_emb, params, store, router_fn,
                        tables, cmap):
                self.stats["traces"] += 1  # runs at trace time only
                cond = {"text_emb": text_emb} if has_text else None
                null = {"text_emb": None} if has_text else None
                with mesh_scope(self.mesh):
                    return sample_ensemble(
                        key, self.experts, params, router_fn,
                        shape, cond=cond, null_cond=null,
                        config=self.sampler,
                        engine=self.engine, init_noise=noise,
                        stacked_params=store,
                        latent_sharding=latent_sharding,
                        plan_sharding=plan_sharding,
                        coeff_tables=tables, cluster_map=cmap,
                    )

            # donation is a no-op (with a warning) on CPU; only request it
            # where XLA can actually alias the buffer.
            donate = () if jax.default_backend() == "cpu" else (1,)
            fn = jax.jit(_sample, donate_argnums=donate, **jit_kwargs)
            self._compiled[cache_key] = fn
        return fn

    def _run_compiled(self, fn, key, noise, text, membership=None):
        """Invoke a compiled sampler with the right membership arguments.

        ``membership`` is an admission-time snapshot tuple for queued
        requests; ``None`` means current membership (``generate``)."""
        args = self._sampler_args(membership)
        if self.elastic:
            self._note_degraded(args[1])
        return fn(key, noise, text, *args)

    def generate(
        self, key, batch_text_emb: jnp.ndarray | None, batch_size: int,
    ) -> jnp.ndarray:
        with TraceAnnotation("engine.prepare"):
            self.stats["requests"] += 1
            has_text = batch_text_emb is not None
            fn = self._get_compiled(batch_size, has_text)
            noise = jax.random.normal(
                key, (batch_size,) + self.latent_shape, dtype=jnp.float32
            )
            if has_text:
                batch_text_emb = self._cached_cond(batch_text_emb)
            else:                                       # static filler
                batch_text_emb = jnp.zeros((0,), jnp.float32)
            self._count_plan_refreshes()
            self._count_routed_rows(batch_size, has_text)
        with TraceAnnotation("engine.dispatch"):
            return self._run_compiled(fn, key, noise, batch_text_emb)

    # -- cross-request batching queue ---------------------------------------

    def _next_seq(self) -> int:
        """Allocate the next global submission-order stamp (shared by
        ``submit`` and the continuous scheduler, so the two admission
        paths order against each other deterministically)."""
        seq = self._seq
        self._seq += 1
        return seq

    def submit(
        self, key, text_emb: jnp.ndarray | None = None,
        batch_size: int | None = None, *,
        deadline_s: float | None = None,
    ) -> PendingRequest:
        """Enqueue a request; returns a handle resolved by ``flush()``.

        Noise is derived from the request's own key at flush time, so a
        coalesced request produces the same samples it would have produced
        through ``generate`` with that key.  ``deadline_s`` bounds the
        request's wall-clock lifetime: a request still queued past it is
        moved to DEADLINE_EXCEEDED at the next ``flush()`` instead of
        dispatching stale work (``result()`` raises the named error).
        """
        if batch_size is None:
            batch_size = text_emb.shape[0] if text_emb is not None else 1
        if text_emb is not None and text_emb.shape[0] != batch_size:
            raise ValueError(
                f"text_emb batch {text_emb.shape[0]} != batch_size "
                f"{batch_size}"
            )
        req = PendingRequest(key=key, text_emb=self._cached_cond(text_emb),
                             batch_size=batch_size,
                             _membership=self._membership(),
                             seq=self._next_seq(),
                             deadline_s=deadline_s,
                             submit_t=time.monotonic())
        self._queue.append(req)
        self.stats["requests"] += 1
        return req

    def flush(self) -> int:
        """Run all queued requests, coalescing compatible ones.

        Latent shape and sampler config are engine invariants, so within
        one engine compatibility reduces to the conditioning signature
        (text present + trailing text shape) — plus, on an elastic
        engine, the membership epoch the request was admitted under, so
        every request executes against its own snapshot.  Each group
        becomes ONE batched sampler dispatch; the merged batch is padded
        up to a power-of-two bucket (bounding compile count under varying
        request mixes) that is also a multiple of the mesh "data" axis on
        a sharded engine (so the batch dim always shards cleanly), and
        per-request slices (padding dropped) are written back to the
        handles.

        Failures are isolated **per group**: a failing dispatch (compile
        error, OOM on a new bucket size, a poison request) re-queues only
        its own group's requests — every other group still dispatches —
        and each request is automatically re-queued at most
        ``max_request_requeues`` times before being marked FAILED with
        the exception on its handle (``result()`` re-raises it), so a
        persistently-bad group can't re-poison every subsequent flush.
        Returns the number of successfully merged dispatches.
        """
        if not self._queue:
            return 0
        now = time.monotonic()
        live = []
        for req in self._queue:
            if (req.deadline_s is not None and req.submit_t is not None
                    and now - req.submit_t >= req.deadline_s):
                req.state = "DEADLINE_EXCEEDED"
                req.error = DeadlineExceeded(
                    f"request seq={req.seq} exceeded deadline_s="
                    f"{req.deadline_s} before dispatch "
                    f"({req.requeues} requeue(s))",
                    seq=req.seq, requeues=req.requeues,
                )
                self.stats["deadline_exceeded"] += 1
            else:
                live.append(req)
        self._queue = live
        groups: dict[tuple, list[PendingRequest]] = {}
        for req in self._queue:
            sig = (req.text_emb is not None,
                   tuple(req.text_emb.shape[1:])
                   if req.text_emb is not None else (),
                   req._membership[0] if req._membership is not None
                   else -1)
            groups.setdefault(sig, []).append(req)
        self._queue = []
        ok = 0
        for (has_text, text_tail, _epoch), reqs in groups.items():
            try:
                self._dispatch_group(has_text, text_tail, reqs)
                ok += 1
            except Exception as e:
                for r in reqs:
                    r.requeues += 1
                    if r.requeues > self.max_request_requeues:
                        r.state = "FAILED"
                        r.error = e
                        self.stats["failed_requests"] += 1
                    else:
                        self.stats["request_requeues"] += 1
                        self._queue.append(r)
        # Re-queues above appended in GROUP iteration order; restore the
        # global submission order so a partially-failed flush retries
        # requests deterministically FIFO (interleaved groups would
        # otherwise leapfrog earlier failed requests — regression-tested
        # in tests/test_continuous.py).
        self._queue.sort(key=lambda r: r.seq)
        if self.elastic:
            # DRAINING slots held for their in-flight snapshots are done
            # (dispatched or failed/re-queued with the snapshot intact).
            for i, h in enumerate(self.expert_health):
                if h == "DRAINING":
                    self.expert_health[i] = "EVICTED"
        return ok

    def _dispatch_group(
        self, has_text: bool, text_tail: tuple, reqs: list[PendingRequest],
    ) -> None:
        total = sum(r.batch_size for r in reqs)
        # Bucket the merged batch to the next power of two (and a
        # "data"-axis multiple on a sharded engine): varying request
        # mixes then land on O(log max_batch) compiled sizes instead
        # of one compile per distinct total, keeping the engine
        # retrace-free under real traffic.
        bucket = 1 << (total - 1).bit_length()
        if self.mesh is not None:
            nd = self.mesh.shape["data"]
            bucket += (-bucket) % nd
        pad = bucket - total
        noise = [
            jax.random.normal(
                r.key, (r.batch_size,) + self.latent_shape, jnp.float32
            )
            for r in reqs
        ]
        if pad:
            noise.append(jnp.zeros((pad,) + self.latent_shape, jnp.float32))
        noise = jnp.concatenate(noise, axis=0)
        if has_text:
            text = [jnp.asarray(r.text_emb) for r in reqs]
            if pad:
                text.append(jnp.zeros((pad,) + text_tail, text[0].dtype))
            text = jnp.concatenate(text, axis=0)
        else:
            text = jnp.zeros((0,), jnp.float32)             # static filler
        fn = self._get_compiled(total + pad, has_text)
        self._count_plan_refreshes()
        self._count_routed_rows(total + pad, has_text)
        out = self._run_compiled(fn, reqs[0].key, noise, text,
                                 membership=reqs[0]._membership)
        self.stats["merged_batches"] += 1
        self.stats["batched_requests"] += len(reqs)
        off = 0
        for r in reqs:
            r._result = out[off:off + r.batch_size]
            r.state = "DONE"
            r.done = True
            off += r.batch_size


def serve_configs(reduced: bool,
                  latent_size: int) -> tuple[DiTConfig, DiTConfig]:
    """The CLI's expert and router configs: dit-b2 / router-b2 at the
    published widths, or their reduced smoke preset at ``latent_size``."""
    dit_cfg, rcfg = dit_b2(), router_b2()
    if reduced:
        dit_cfg = dit_cfg.reduced(latent_size=latent_size)
        rcfg = rcfg.reduced(latent_size=latent_size)
    return dit_cfg, rcfg


def main() -> None:
    ap = argparse.ArgumentParser(
        epilog="shards > 1 need that many visible devices — on a CPU host "
               "set XLA_FLAGS=--xla_force_host_platform_device_count=N "
               "before launching (as launch/dryrun.py does)."
    )
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--cfg-scale", type=float, default=7.5)
    ap.add_argument("--strategy", default="topk",
                    choices=("top1", "topk", "full", "threshold"))
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "routed", "dense", "reference"))
    ap.add_argument("--dispatch", default="auto",
                    choices=("auto", "gathered", "grouped", "ragged",
                             "dense"),
                    help="expert-dispatch executor backend "
                         "(core.dispatch): per-sample gather+vmap, "
                         "sort-based grouped segment execution, or the "
                         "one-kernel ragged grouped GEMM (pair-major, "
                         "zero bucket padding)")
    ap.add_argument("--param-dtype", default="native",
                    choices=("native", "fp32", "bf16", "int8", "fp8"),
                    help="stacked expert-param storage "
                         "(core.param_store): int8/fp8 quantize on load "
                         "with per-expert scales and dequantize routed "
                         "slices through the fused Pallas kernel "
                         "(~4x fewer resident expert-param bytes)")
    ap.add_argument("--plan-refresh", type=int, default=1,
                    help="recompute the router posterior + DispatchPlan "
                         "only every R-th Euler step, carrying the plan "
                         "through the scan in between (R=1 = per-step "
                         "routing, bit-identical to the classic path; "
                         "R>1 trades bounded drift for skipping the "
                         "router forward on the other steps)")
    ap.add_argument("--no-step-fuse", action="store_true",
                    help="disable the step-fused kernel (CFG combine + "
                         "Euler update folded into convert-and-fuse) and "
                         "run the unfused three-op chain instead")
    ap.add_argument("--cond-cache", type=int, default=64,
                    help="cross-request conditioning LRU capacity "
                         "(content-hash-keyed text-embedding reuse "
                         "across submit()/generate() calls; 0 disables)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the 2-layer d=128 smoke preset of dit-b2 "
                         "(--no-reduced: the published widths, 32x32x4 "
                         "latents)")
    ap.add_argument("--latent-size", type=int, default=8,
                    help="latent side of the --reduced preset")
    ap.add_argument("--expert-shards", type=int, default=1)
    ap.add_argument("--data-shards", type=int, default=None)
    ap.add_argument("--coalesce", action="store_true",
                    help="drive requests through submit()/flush() instead "
                         "of per-request generate()")
    ap.add_argument("--continuous", action="store_true",
                    help="drive requests through the rolling "
                         "mixed-timestep scheduler (repro.serving): "
                         "requests join/leave the always-full batch at "
                         "step boundaries instead of lockstep flushing")
    ap.add_argument("--max-resident", type=int, default=8,
                    help="rolling-batch capacity per shape bucket "
                         "(continuous mode)")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="scheduler queue-depth bound before submit() "
                         "raises QueueBackpressure (continuous mode)")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="continuous mode: submit one request every N "
                         "scheduler ticks (staggered open-loop arrivals)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline in seconds "
                         "(expired requests land in DEADLINE_EXCEEDED "
                         "and result() raises the named error)")
    ap.add_argument("--tick-budget", type=float, default=None,
                    help="continuous mode: wall-clock watchdog budget "
                         "per bucket launch; a slower tick fails only "
                         "that bucket with bounded-backoff retry")
    ap.add_argument("--journal-dir", default=None,
                    help="continuous mode: write the crash-recovery "
                         "request journal (submit/admit/tick/resolve "
                         "records + row-state snapshots) here; recover "
                         "with ServingEngine.restore(journal_dir)")
    ap.add_argument("--capacity", type=int, default=None,
                    help="expert-slot capacity (>= checkpoint count): pads "
                         "the store with masked EMPTY slots and enables "
                         "elastic membership (hot add_expert/evict_expert "
                         "without recompiling)")
    ap.add_argument("--on-bad-checkpoint", default="raise",
                    choices=("raise", "skip"),
                    help="'skip' quarantines corrupt/truncated/mismatched "
                         "expert checkpoints and serves the rest in "
                         "degraded mode instead of refusing to start")
    ap.add_argument("--track-padding", action="store_true",
                    help="instrument the expert forwards with a runtime "
                         "row counter and print padded vs routed rows "
                         "per step after serving (grouped bucket-padding "
                         "tax; 0.0 under --dispatch ragged)")
    args = ap.parse_args()
    enable_compile_cache()

    dit_cfg, rcfg = serve_configs(args.reduced, args.latent_size)
    engine = ServingEngine.from_checkpoint_dir(
        args.ckpt_dir, dit_cfg=dit_cfg, router_cfg=rcfg,
        sampler=SamplerConfig(
            num_steps=args.steps, cfg_scale=args.cfg_scale,
            strategy=args.strategy, top_k=args.top_k,
            dispatch=args.dispatch, param_dtype=args.param_dtype,
            step_fused=not args.no_step_fuse,
            plan_refresh_every=args.plan_refresh,
        ),
        engine=args.engine,
        n_expert_shards=args.expert_shards, n_data_shards=args.data_shards,
        cond_cache_size=args.cond_cache,
        capacity=args.capacity,
        on_bad_checkpoint=args.on_bad_checkpoint,
        track_padding=args.track_padding,
    )
    print(f"loaded {len(engine.experts)} experts "
          f"({[e.objective for e in engine.experts]}) "
          f"homogeneous={engine.homogeneous} "
          f"mesh={dict(engine.mesh.shape) if engine.mesh else None}")
    if engine.elastic:
        print(engine.membership_line())
    if args.continuous:
        from repro.serving import (
            ContinuousScheduler, ResiliencePolicy, ResilientScheduler,
        )

        resilient = (args.deadline_s is not None
                     or args.tick_budget is not None
                     or args.journal_dir is not None)
        if resilient:
            sched = ResilientScheduler(
                engine, max_resident=args.max_resident,
                max_queue_depth=args.max_queue,
                policy=ResiliencePolicy(tick_budget_s=args.tick_budget),
                journal_dir=args.journal_dir,
            )
        else:
            sched = ContinuousScheduler(
                engine, max_resident=args.max_resident,
                max_queue_depth=args.max_queue,
            )
        t0 = time.time()
        handles = []
        for r in range(args.requests):
            key = jax.random.PRNGKey(r)
            text = np.asarray(jax.random.normal(
                key, (args.batch, dit_cfg.text_len, dit_cfg.text_dim)
            ))
            if resilient:
                handles.append(
                    sched.submit(key, text, deadline_s=args.deadline_s)
                )
            else:
                handles.append(sched.submit(key, text))
            for _ in range(max(args.arrival_every, 0)):
                sched.step()
        sched.run_until_idle()
        outs = [jax.block_until_ready(h.result()) for h in handles]
        dt = time.time() - t0
        n = sum(o.shape[0] for o in outs)
        print(f"continuous {len(handles)} requests in "
              f"{sched.step_count} ticks: {n} imgs in {dt:.2f}s "
              f"({n / dt:.1f} img/s) traces={engine.stats['traces']}")
        print(sched.line())
        if engine.elastic:
            print(engine.membership_line())
        return
    if args.coalesce:
        t0 = time.time()
        handles = []
        for r in range(args.requests):
            key = jax.random.PRNGKey(r)
            # host-side ndarray, as a remote text encoder would deliver —
            # the form the conditioning cache hashes and dedupes
            text = np.asarray(jax.random.normal(
                key, (args.batch, dit_cfg.text_len, dit_cfg.text_dim)
            ))
            handles.append(engine.submit(key, text))
        engine.flush()
        outs = [jax.block_until_ready(h.result()) for h in handles]
        dt = time.time() - t0
        n = sum(o.shape[0] for o in outs)
        print(f"coalesced {len(handles)} requests -> "
              f"{engine.stats['merged_batches']} dispatch(es): "
              f"{n} imgs in {dt:.2f}s ({n / dt:.1f} img/s) "
              f"traces={engine.stats['traces']}")
        print(f"cache: cond_hits={engine.stats['cond_cache_hits']} "
              f"cond_misses={engine.stats['cond_cache_misses']} "
              f"plan_refreshes={engine.stats['plan_refreshes']} "
              f"(R={args.plan_refresh}, {args.steps} steps/dispatch)")
        if engine.elastic:
            print(engine.membership_line())
        return
    for r in range(args.requests):
        key = jax.random.PRNGKey(r)
        t0 = time.time()
        # host-side ndarray, as a remote text encoder would deliver
        text = np.asarray(jax.random.normal(
            key, (args.batch, dit_cfg.text_len, dit_cfg.text_dim)
        ))
        out = engine.generate(key, text, args.batch)
        out = jax.block_until_ready(out)
        dt = time.time() - t0
        print(f"request {r}: {out.shape} in {dt:.2f}s "
              f"({args.batch / dt:.1f} img/s) "
              f"traces={engine.stats['traces']} "
              f"finite={bool(np.isfinite(np.asarray(out)).all())}")
    print(f"cache: cond_hits={engine.stats['cond_cache_hits']} "
          f"cond_misses={engine.stats['cond_cache_misses']} "
          f"plan_refreshes={engine.stats['plan_refreshes']} "
          f"(R={args.plan_refresh}, {args.steps} steps/request)")
    if args.track_padding:
        ps = engine.padding_stats()
        print(f"padding: padded_rows/step={ps['padded_rows_per_step']:.2f} "
              f"routed_rows/step={ps['routed_rows_per_step']:.2f} "
              f"overhead={ps['padding_overhead']:.3f} "
              f"busiest_shard_rows/step="
              f"{ps['busiest_shard_rows_per_step']:.2f}")
    if engine.elastic:
        print(engine.membership_line())


if __name__ == "__main__":
    main()
