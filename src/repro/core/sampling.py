"""Compute-sparse fused ODE sampling with heterogeneous experts (Fig. 2, §3).

The unified sampler integrates the data-to-noise velocity *backwards*
(t = 1 → 0) with Euler steps: ``x_{t-Δt} = x_t − v · Δt`` (Eq. 8 remark).
All experts — DDPM or FM — contribute through the common velocity space.

Serving hot path (the paper's central efficiency claim, §3.1): Top-K /
threshold routing means inference only pays for the *selected* experts.
Three mechanisms realize that here:

* **batched CFG** — the conditional and unconditional branches are stacked
  along the batch axis (null conditioning expressed via the model's
  ``drop_mask``), so guidance costs one expert forward instead of two;
* **routed-expert-only execution** — homogeneous-architecture expert
  params stack into a typed ``core.param_store.ExpertParamStore``
  (dense, or int8/fp8-quantized via ``SamplerConfig.param_dtype`` with
  dequant fused into the hot path) and each step builds a
  ``core.dispatch.DispatchPlan`` from the router posterior, then
  executes only the routed experts through a pluggable
  ``ExpertExecutor`` backend (``SamplerConfig.dispatch``): per-sample
  gather+vmap (``gathered``), sort-based grouped segment execution
  (``grouped``), or the heterogeneous dense fallback (``dense``);
* **fused convert-and-fuse** — the per-step (alpha, sigma, dalpha, dsigma,
  vscale) conversion coefficients are tabulated once per run key
  (``coeff_tables_cached``, a process-wide cache over
  ``conversion.unified_coeff_tables``) and the ε→v conversion + Eq. 1
  weighting run as a single ``kernels.ops.fused_velocity`` kernel call
  (Pallas on TPU, oracle elsewhere);
* **step fusion** — with ``SamplerConfig.step_fused`` (the default) the
  CFG combine ``u_u + s·(u_c − u_u)`` and the Euler update ``x ← x − u·dt``
  fold INTO that kernel (``kernels.ops.fused_step``): executors hand back
  per-branch routed predictions and one kernel launch reads the latent
  once and writes the updated latent once per step;
* **plan reuse** — ``SamplerConfig.plan_refresh_every`` recomputes the
  router posterior + ``DispatchPlan`` only every R-th step, carrying the
  plan through the scan (posteriors change slowly in t); R=1 is
  bit-identical to per-step routing.

The dense all-experts path is kept as an automatic fallback for expert
sets the sparse engine cannot stack (heterogeneous ``apply_fn``s) and the
original per-expert reference path remains available (``engine=
"reference"``) for parity testing and the ``snr_match`` time map.

Also provided: classifier-free guidance (train-time drop prob 0.1, learned
null embeddings — §2.5), the native DDPM ancestral sampler (Table 3 "Native
DDPM" row), and the deterministic two-expert threshold sampler (§3.3).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.conversion import ConversionConfig, unified_coeff_tables
from repro.core.dispatch import (
    full_dispatch_plan,
    make_dispatch_plan,
    make_executor,
    plan_from_slots,
    resolve_dispatch,
    routed_slots,
    slot_coef,
    slot_coef_rows,
)
from repro.kernels import ops
from repro.core.fusion import (
    ExpertSpec,
    fuse_predictions,
    fusion_weights,
    unified_expert_velocities,
)
from repro.core.param_store import as_store, make_store
from repro.core.schedules import get_schedule

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    """Inference settings.  Paper defaults: aligned = (7.5, 50); conversion
    study = (6.0, 75)."""

    num_steps: int = 50
    cfg_scale: float = 7.5
    strategy: str = "topk"          # 'top1' | 'topk' | 'full' | 'threshold'
    top_k: int = 2
    threshold: float = 0.5          # for strategy='threshold'
    #: default_factory (not a class-level instance) so every config owns
    #: its conversion settings; with frozen=True on both dataclasses the
    #: pair stays hashable by construction — serving jit-cache keys depend
    #: on that.
    conversion: ConversionConfig = dataclasses.field(
        default_factory=ConversionConfig
    )
    #: identity (paper) or snr_match (beyond-paper time alignment)
    time_map: str = "identity"
    #: §7.3 finding: ε→v conversion is only stable at low noise.  If > 0,
    #: DDPM experts' routing weights are zeroed for t above this value
    #: (renormalized over the remaining experts).
    ddpm_low_noise_only: float = 0.0
    #: stack cond/uncond along the batch axis so CFG costs one forward.
    #: Requires apply_fns that accept ``drop_mask`` when the null branch
    #: uses a model-internal null embedding; automatically falls back to
    #: the two-pass formulation when the cond dicts cannot be batched.
    batched_cfg: bool = True
    #: expert-dispatch backend for routed execution (``core.dispatch``):
    #: 'auto' (grouped when params stack — 1.22x faster per
    #: BENCH_sampler.json and bounded by resident experts; gathered for
    #: batch-uniform threshold plans; dense otherwise) | 'gathered'
    #: (per-sample param gather + vmap) | 'grouped' (sort-based grouped
    #: segment execution, one forward per resident expert) | 'dense'
    #: (every expert via its own apply_fn).
    dispatch: str = "auto"
    #: storage dtype of the stacked expert params
    #: (``core.param_store.PARAM_DTYPES``): 'native' keeps checkpoint
    #: precision (bit-identical DenseStore — the default), 'fp32'/'bf16'
    #: cast dense storage, 'int8'/'fp8' quantize with per-expert
    #: symmetric scales and dequantize routed slices through the fused
    #: ``hetero_fuse_dequant`` Pallas kernel (~4x / ~4x fewer resident
    #: expert-param bytes vs fp32).
    param_dtype: str = "native"
    #: fold the CFG combine and the Euler update into the convert-and-
    #: fuse kernel (``kernels.ops.fused_step``), so one sampling step
    #: costs one fused kernel launch — the latent is read once and the
    #: updated latent written once per step instead of round-tripping
    #: through HBM for ``fused_velocity`` → ``cfg_combine`` → ``x − u·dt``.
    #: The fused engines only; the reference engine ignores it.  False
    #: keeps the unfused three-op chain (parity baseline, benchmarks).
    step_fused: bool = True
    #: recompute the router posterior + ``DispatchPlan`` only every R-th
    #: Euler step, carrying the plan through the scan in between — the
    #: ROADMAP "KV/latent caching" observation that router posteriors
    #: change slowly in t.  R=1 (default) refreshes every step and is
    #: bit-identical to per-step routing; R>1 trades bounded sampler
    #: drift (tracked in ``BENCH_sampler.json`` ``plan_reuse``) for
    #: skipping the router forward and the ``B·k`` argsort on the other
    #: R−1 of every R steps.  Fused engines only; the reference engine
    #: rejects R>1.
    plan_refresh_every: int = 1


def cfg_combine(cond_pred: Array, uncond_pred: Array, scale: float) -> Array:
    """Classifier-free guidance: ``u + s (c - u)``."""
    return uncond_pred + scale * (cond_pred - uncond_pred)


# ---------------------------------------------------------------------------
# Engine selection
# ---------------------------------------------------------------------------


def params_are_stackable(params: Sequence) -> bool:
    """True when every expert's param pytree has identical structure and
    leaf shapes/dtypes — the precondition for stacked-params dispatch."""
    if len(params) <= 1:
        return True
    try:
        t0 = jax.tree.structure(params[0])
        l0 = jax.tree.leaves(params[0])
        for p in params[1:]:
            if jax.tree.structure(p) != t0:
                return False
            lp = jax.tree.leaves(p)
            for a, b in zip(l0, lp):
                a, b = jnp.asarray(a), jnp.asarray(b)
                if a.shape != b.shape or a.dtype != b.dtype:
                    return False
    except Exception:
        return False
    return True


def _resolve_engine(
    engine: str,
    experts: Sequence[ExpertSpec],
    params: Sequence | None,
    config: SamplerConfig,
) -> str:
    if engine not in ("auto", "routed", "dense", "reference"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine == "reference":
        if config.dispatch != "auto":
            raise ValueError(
                "the reference engine predates the dispatch API; use "
                "dispatch='auto' (executor backends apply to the fused "
                "engines only)"
            )
        if config.plan_refresh_every != 1:
            raise ValueError(
                "plan_refresh_every > 1 requires the fused engines (the "
                "reference path recomputes routing every step by design)"
            )
        return engine
    if config.time_map != "identity":
        # snr_match queries experts at rebased times/inputs — only the
        # per-expert reference path implements it.
        if engine != "auto":
            raise ValueError(
                f"engine={engine!r} requires time_map='identity'"
            )
        if config.dispatch != "auto":
            # fail loudly rather than silently running the reference path
            # while the caller believes an executor backend is in effect.
            raise ValueError(
                f"dispatch={config.dispatch!r} requires time_map="
                f"'identity'; snr_match resolves to the reference engine, "
                f"which predates the dispatch API"
            )
        if config.plan_refresh_every != 1:
            raise ValueError(
                "plan_refresh_every > 1 requires time_map='identity'; "
                "snr_match resolves to the reference engine, which "
                "recomputes routing every step by design"
            )
        return "reference"
    K = len(experts)
    # params=None means the caller holds stacked params only as an
    # ExpertParamStore (e.g. a quantized serving engine that dropped the
    # full-precision per-expert list); a store is stackable by
    # construction.
    homogeneous = K == 1 or (
        all(e.apply_fn is experts[0].apply_fn for e in experts)
        and (params is None or params_are_stackable(params))
    )
    routed_ok = K > 1 and (
        (config.strategy in ("top1", "topk") and homogeneous)
        or config.strategy == "threshold"
    )
    if engine == "auto":
        return "routed" if routed_ok else "dense"
    if engine == "routed" and not routed_ok:
        raise ValueError(
            "routed engine needs strategy in (top1, topk, threshold) and, "
            "for per-sample routing, a shared apply_fn with stackable params"
        )
    return engine


# ---------------------------------------------------------------------------
# Batched classifier-free guidance
# ---------------------------------------------------------------------------


def _cfg_batchable(cond: dict, null_cond: dict) -> bool:
    """Can the cond/uncond branches be expressed as one doubled batch?"""
    if "drop_mask" in cond or "drop_mask" in null_cond:
        return False
    for k, v in null_cond.items():
        if v is not None and cond.get(k) is None:
            return False
    return True


def _cfg_grouped_cond(cond: dict, null_cond: dict | None, batch: int) -> dict:
    """Per-sample CFG-branch conditioning: leaves gain a ``(B, G, ...)``
    group axis (G=2 cond/uncond, G=1 without guidance batching).

    This is the conditioning form every ``ExpertExecutor`` backend
    receives: the gathered backend runs both guidance branches inside one
    vmapped instance (params gathered once, not per branch); the grouped
    and dense backends flatten the group axis branch-major, recovering
    the classic ``[cond; uncond]`` concatenated batch.
    """
    if null_cond is None:
        return {
            k: v[:, None] for k, v in cond.items() if v is not None
        }
    out: dict = {}
    need_drop = False
    for key in sorted(set(cond) | set(null_cond)):
        c, n = cond.get(key), null_cond.get(key)
        if c is None and n is None:
            continue
        if n is None:
            out[key] = jnp.stack([c, c], axis=1)
            need_drop = True
        else:
            out[key] = jnp.stack(
                [jnp.asarray(c), jnp.asarray(n)], axis=1
            )
    if need_drop:
        out["drop_mask"] = jnp.broadcast_to(
            jnp.array([False, True])[None], (batch, 2)
        )
    return out


# ---------------------------------------------------------------------------
# Fused compute-sparse engine
# ---------------------------------------------------------------------------


def _stack_params(params: Sequence):
    if len(params) == 1:
        return jax.tree.map(lambda x: jnp.asarray(x)[None], params[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params)


@functools.lru_cache(maxsize=128)
def _time_grid(num_steps: int) -> Array:
    """Euler time grid ``linspace(1, 0, S+1)`` as a host-side constant.

    Computed eagerly (compile-time) and cached so every jit program —
    the lockstep scan and the stepwise continuous-batching entry —
    embeds the *same bytes*.  ``jnp.linspace`` traced inside a program
    can constant-fold to values 1 ulp away from its eager result
    depending on the surrounding graph, which would silently break the
    bitwise scan-vs-stepwise parity the rolling batch is built on.
    """
    with jax.ensure_compile_time_eval():
        return jnp.linspace(1.0, 0.0, num_steps + 1)


@functools.lru_cache(maxsize=128)
def coeff_tables_cached(
    objectives: tuple[str, ...],
    schedule_names: tuple[str, ...],
    num_steps: int,
    conv: ConversionConfig,
) -> Array:
    """Per-run ``unified_coeff_tables`` result, cached by its run key.

    The ``(S, 5, K)`` table depends only on static run parameters —
    expert objectives/schedules, the step count and the conversion
    config — yet was rebuilt (K schedule sweeps + stacking) on every
    sampler trace.  A long-lived ``ServingEngine`` retraces per (batch,
    shape, conditioning) cache entry, so identical tables were being
    recomputed per entry; this cache builds each distinct table once per
    process.  All key parts are hashable by construction
    (``ConversionConfig`` is frozen).
    """
    # The first call usually happens INSIDE a sampler trace;
    # ensure_compile_time_eval forces concrete (non-tracer) arrays so the
    # cached table is safe to reuse across traces.
    with jax.ensure_compile_time_eval():
        ts = jnp.linspace(1.0, 0.0, num_steps + 1)[:-1]
        return unified_coeff_tables(
            list(objectives),
            [get_schedule(name) for name in schedule_names],
            ts, conv,
        )


def _sample_fused(
    key: jax.Array,
    experts: Sequence[ExpertSpec],
    params: Sequence,
    router_fn,
    shape: tuple[int, ...],
    cond: dict,
    null_cond: dict | None,
    config: SamplerConfig,
    mode: str,
    init_noise: Array | None,
    stacked_params=None,
    latent_sharding=None,
    plan_sharding=None,
    coeff_tables=None,
    cluster_map=None,
) -> Array:
    K = len(experts)
    B = shape[0]
    conv = config.conversion
    homogeneous = all(e.apply_fn is experts[0].apply_fn for e in experts)

    use_cfg = null_cond is not None and config.cfg_scale != 1.0
    batched = (
        use_cfg and config.batched_cfg
        and _cfg_batchable(cond, null_cond or {})
    )

    if mode == "routed":
        k_slots = 1 if config.strategy in ("top1", "threshold") \
            else min(config.top_k, K)
        uniform = config.strategy == "threshold"
    else:
        k_slots, uniform = K, False

    # Routed dispatch substrate, resolved to a typed ExpertParamStore
    # (core.param_store): callers that keep long-lived stacked params
    # (ServingEngine) pass a store — or the legacy raw stacked pytree —
    # in; otherwise the per-expert list stacks once per trace, into the
    # storage dtype requested by ``config.param_dtype`` (quantized stores
    # dequantize routed slices through the fused hetero_fuse_dequant
    # kernel).  _resolve_engine already guaranteed stackability for
    # per-sample routing; the batch-uniform threshold path re-checks
    # because it also serves heterogeneous expert sets (via the dense
    # executor's switch).
    stacked = as_store(stacked_params, dtype=config.param_dtype)
    # Elastic membership (capacity stores): the liveness mask is traced
    # data riding the store, so an eviction/hot-add reaches this engine as
    # new argument *values* under the same trace — no recompile.
    valid = getattr(stacked, "valid", None)
    if stacked is None and params is None:
        raise ValueError(
            "params=None requires stacked_params (an ExpertParamStore or "
            "raw stacked pytree)"
        )
    if stacked is None and mode == "routed" and homogeneous and (
        not uniform or params_are_stackable(params)
    ):
        stacked = make_store(_stack_params(params),
                             dtype=config.param_dtype)

    # Pluggable expert-dispatch backend (core.dispatch): the executor owns
    # HOW routed forwards run; the plan built per step owns WHICH experts
    # run; CFG orchestration below is shared across all backends.
    # Ragged eligibility: every expert must publish the SAME pair-major
    # ragged forward (ExpertSpec.ragged_apply_fn) — the one-kernel backend
    # gathers weights per (sample, slot) pair, so a single shared forward
    # is a structural requirement, mirroring the homogeneous-apply_fn rule.
    ragged_fn = getattr(experts[0], "ragged_apply_fn", None)
    ragged_ok = (
        mode == "routed" and not uniform and ragged_fn is not None
        and all(getattr(e, "ragged_apply_fn", None) is ragged_fn
                for e in experts)
    )
    backend = resolve_dispatch(
        config.dispatch, mode, stacked is not None, uniform, ragged_ok,
    )
    executor = make_executor(
        backend,
        apply_fns=[e.apply_fn for e in experts],
        params=params,
        stacked_params=stacked,
        conv=conv,
        ragged_apply_fn=ragged_fn if ragged_ok else None,
    )

    x = init_noise if init_noise is not None \
        else jax.random.normal(key, shape, dtype=jnp.float32)
    if latent_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, latent_sharding)
    ts = _time_grid(config.num_steps)
    # Schedule-coefficient tables: computed ONCE per run key (cached
    # process-wide, so serving retraces reuse them), gathered per step.
    # Elastic engines instead pass ``coeff_tables`` as a traced argument:
    # a hot-added expert may change a capacity slot's objective/schedule,
    # which must reach the sampler as new table *values*, not a new trace.
    if coeff_tables is not None:
        tables = coeff_tables                             # (S, 5, K)
    else:
        tables = coeff_tables_cached(
            tuple(e.objective for e in experts),
            tuple(e.schedule for e in experts),
            config.num_steps, conv,
        )                                                 # (S, 5, K)

    refresh_every = int(config.plan_refresh_every)
    if refresh_every < 1:
        raise ValueError(
            f"plan_refresh_every must be >= 1, got {refresh_every}"
        )

    def make_plan(w):
        if backend == "dense" and not uniform:
            plan = full_dispatch_plan(w)
        else:
            plan = make_dispatch_plan(w, k_slots, uniform=uniform,
                                      valid=valid)
        if plan_sharding is not None:
            # Sharded serving: routing metadata replicates across the mesh
            # (every shard needs the full plan to slice its resident
            # experts' groups); see launch.sharding.dispatch_plan_sharding.
            plan = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, plan_sharding),
                plan,
            )
        return plan

    def routed_plan(x, tb):
        with jax.named_scope("router"):
            w = fusion_weights(
                experts, router_fn, x, tb,
                strategy=config.strategy, top_k=config.top_k,
                threshold=config.threshold,
                ddpm_low_noise_only=config.ddpm_low_noise_only,
                valid=valid, cluster_map=cluster_map,
            )                                             # (B, K)
            return make_plan(w)

    def velocity_update(plan, x, tb, dt, tab):
        # Unfused three-op chain: fused velocity, CFG combine, Euler —
        # each a latent-sized HBM round-trip (parity/bench baseline).
        if batched:
            cond_g = _cfg_grouped_cond(cond, null_cond or {}, B)
            fused = executor.velocity(plan, x, tb, cond_g, 2, tab)
            u = cfg_combine(fused[:B], fused[B:], config.cfg_scale)
        elif use_cfg:
            u_c = executor.velocity(
                plan, x, tb, _cfg_grouped_cond(cond, None, B), 1, tab)
            u_u = executor.velocity(
                plan, x, tb,
                _cfg_grouped_cond(dict(null_cond or {}), None, B), 1, tab)
            u = cfg_combine(u_c, u_u, config.cfg_scale)
        else:
            u = executor.velocity(
                plan, x, tb, _cfg_grouped_cond(cond, None, B), 1, tab)
        return x - u * dt

    def fused_step_update(plan, x, tb, dt, tab):
        # Step-fused hot path: the executor hands back per-branch routed
        # predictions and ONE kernel (kernels.ops.fused_step) does the
        # convert-and-fuse, CFG combine and Euler update — the latent is
        # read once and written once; no velocity materializes in HBM.
        if batched:
            cond_g = _cfg_grouped_cond(cond, null_cond or {}, B)
            preds, w_all, idx_all = executor.predictions(
                plan, x, tb, cond_g, 2, tab)
            g, scale = 2, config.cfg_scale
        elif use_cfg:
            p_c, w1, i1 = executor.predictions(
                plan, x, tb, _cfg_grouped_cond(cond, None, B), 1, tab)
            p_u, _, _ = executor.predictions(
                plan, x, tb,
                _cfg_grouped_cond(dict(null_cond or {}), None, B), 1, tab)
            # branch-major [cond; uncond], the layout batched CFG emits
            preds = jnp.concatenate([p_c, p_u], axis=1)
            w_all = jnp.concatenate([w1, w1], axis=0)
            idx_all = jnp.concatenate([i1, i1], axis=0)
            g, scale = 2, config.cfg_scale
        else:
            preds, w_all, idx_all = executor.predictions(
                plan, x, tb, _cfg_grouped_cond(cond, None, B), 1, tab)
            g, scale = 1, 1.0
        return ops.fused_step(
            preds, x, w_all, slot_coef(tab, idx_all), dt,
            g=g, cfg_scale=scale,
            clamp=conv.clamp, alpha_min=conv.alpha_min,
        )

    update = fused_step_update if config.step_fused else velocity_update

    def advance(plan, x, i):
        t_hi, t_lo = ts[i], ts[i + 1]
        tb = jnp.full((B,), t_hi)
        x = update(plan, x, tb, t_hi - t_lo, tables[i])
        if latent_sharding is not None:
            # Pin the evolving latent's batch dim to the mesh "data" axis
            # every step — without the constraint GSPMD may re-replicate
            # the batch through the routed param resolution and serialize
            # the data-parallel shards.  On the step-fused path this is
            # the constraint on the fused kernel's output.
            x = jax.lax.with_sharding_constraint(x, latent_sharding)
        return x

    if refresh_every == 1:

        def step(x, i):
            plan = routed_plan(x, jnp.full((B,), ts[i]))
            return advance(plan, x, i), None

        x, _ = jax.lax.scan(step, x, jnp.arange(config.num_steps))
    else:
        # Plan reuse: routing (router forward + top-k + the grouped
        # argsort, all inside routed_plan) runs only on refresh steps;
        # in between, the registered-pytree DispatchPlan rides the scan
        # carry.  lax.cond executes a single branch at run time, so
        # non-refresh steps pay zero routing compute.
        def step(carry, i):
            x, plan = carry
            plan = jax.lax.cond(
                i % refresh_every == 0,
                lambda: routed_plan(x, jnp.full((B,), ts[i])),
                lambda: plan,
            )
            return (advance(plan, x, i), plan), None

        # Structural placeholder only — step 0 always refreshes.
        init_plan = make_plan(jnp.zeros((B, K), jnp.float32))
        (x, _), _ = jax.lax.scan(
            step, (x, init_plan), jnp.arange(config.num_steps)
        )
    return x


# ---------------------------------------------------------------------------
# Reference (per-expert, all-experts, two-pass CFG) path
# ---------------------------------------------------------------------------


def _expert_velocities_with_cfg(
    experts: Sequence[ExpertSpec],
    params: Sequence,
    x_t: Array,
    t: Array,
    cond: dict,
    null_cond: dict | None,
    cfg: SamplerConfig,
) -> Array:
    v_c = unified_expert_velocities(
        experts, params, x_t, t, cond, conv_cfg=cfg.conversion,
        time_map=cfg.time_map,
    )
    if null_cond is None or cfg.cfg_scale == 1.0:
        return v_c
    v_u = unified_expert_velocities(
        experts, params, x_t, t, null_cond, conv_cfg=cfg.conversion,
        time_map=cfg.time_map,
    )
    return cfg_combine(v_c, v_u, cfg.cfg_scale)


def _sample_reference(
    key: jax.Array,
    experts: Sequence[ExpertSpec],
    params: Sequence,
    router_fn,
    shape: tuple[int, ...],
    cond: dict,
    null_cond: dict | None,
    config: SamplerConfig,
    init_noise: Array | None,
) -> Array:
    x = init_noise if init_noise is not None \
        else jax.random.normal(key, shape, dtype=jnp.float32)
    # The fused engines' grid bytes: a 1-ulp different t can round to the
    # neighbouring discrete DiT timestep (round(999·t) at t = 0.5).
    ts = _time_grid(config.num_steps)

    def step(x, i):
        t_hi, t_lo = ts[i], ts[i + 1]
        dt = t_hi - t_lo
        tb = jnp.full((shape[0],), t_hi)
        v = _expert_velocities_with_cfg(
            experts, params, x, tb, cond, null_cond, config
        )
        w = fusion_weights(
            experts, router_fn, x, tb,
            strategy=config.strategy, top_k=config.top_k,
            threshold=config.threshold,
            ddpm_low_noise_only=config.ddpm_low_noise_only,
        )
        u = fuse_predictions(v, w)
        return x - u * dt, None

    x, _ = jax.lax.scan(step, x, jnp.arange(config.num_steps))
    return x


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def sample_ensemble(
    key: jax.Array,
    experts: Sequence[ExpertSpec],
    params: Sequence | None,
    router_fn: Callable[[Array, Array], Array] | None,
    shape: tuple[int, ...],
    *,
    cond: dict | None = None,
    null_cond: dict | None = None,
    config: SamplerConfig | None = None,
    engine: str = "auto",
    init_noise: Array | None = None,
    stacked_params=None,
    latent_sharding=None,
    plan_sharding=None,
    coeff_tables=None,
    cluster_map=None,
) -> Array:
    """Euler-ODE sampling with router-weighted heterogeneous fusion.

    Args:
      router_fn: ``(x_t, t) -> (B, K) posterior``; may be None only for
        single-expert sampling or the threshold strategy.
      shape: sample shape ``(B, ...)`` in latent space.
      engine: ``'auto'`` picks the compute-sparse routed engine when the
        strategy and expert set allow it, falling back to the dense
        fused engine otherwise; ``'routed'`` / ``'dense'`` force a path;
        ``'reference'`` is the original per-expert two-pass formulation
        (required for ``time_map='snr_match'``, kept for parity tests).
      init_noise: optional pre-drawn ``N(0,1)`` latents of ``shape`` (lets
        serving donate the buffer); drawn from ``key`` when omitted.
      stacked_params: optional pre-stacked expert params — an
        ``ExpertParamStore`` (``core.param_store``; quantized stores keep
        int8/fp8 leaves resident and dequantize routed slices through the
        fused kernel) or the legacy raw stacked pytree (leaves
        ``(K, ...)``, see ``models.dit.stack_expert_params``) — so
        long-lived engines don't re-stack per compiled cache entry.  May
        arrive device_put on an ("expert", "data") mesh — the routed
        gather then resolves via an all-gather of the selected experts'
        shards (expert-parallel serving, ``launch.serve``).  When given,
        ``params`` may be None (routed execution only).
      latent_sharding: optional ``NamedSharding`` for the evolving latent
        state; the fused engine re-constrains x to it every Euler step so
        the batch stays on the mesh "data" axis under sharded serving.
      plan_sharding: optional ``NamedSharding`` for the per-step
        ``DispatchPlan`` arrays (typically replicated — see
        ``launch.sharding.dispatch_plan_sharding``) so routing metadata
        never forces collectives inside the executor's expert branches.
      coeff_tables: optional pre-built ``(S, 5, K)`` unified-coefficient
        tables *as traced data* — elastic serving passes them so a
        hot-added expert's objective/schedule reaches the sampler as new
        values instead of a retrace; omitted, they come from the static
        per-``ExpertSpec`` ``coeff_tables_cached`` path (fused engines
        only — the reference engine derives coefficients per expert).
      cluster_map: optional ``(K,)`` int cluster-id-per-slot array, the
        traced counterpart of ``ExpertSpec.cluster_id`` for elastic
        engines (see ``fusion.fusion_weights``); fused engines only.

    ``stacked_params`` carrying an ``ExpertParamStore`` with a ``valid``
    liveness mask (``param_store.pad_to_capacity``) makes the fused
    engines membership-aware: routing renormalizes over live slots only
    and dispatch never gathers or runs a dead slot's params.

    Returns samples at t=0 (clean latents).
    """
    cond = cond or {}
    config = config if config is not None else SamplerConfig()
    mode = _resolve_engine(engine, experts, params, config)
    if params is None and mode == "reference":
        raise ValueError(
            "the reference engine runs each expert from its own params "
            "list; params=None (store-only serving) supports the fused "
            "engines only"
        )
    if mode == "reference":
        if coeff_tables is not None or cluster_map is not None:
            raise ValueError(
                "coeff_tables/cluster_map (elastic membership) require "
                "the fused engines; the reference engine derives "
                "coefficients from the static ExpertSpec list"
            )
        return _sample_reference(
            key, experts, params, router_fn, shape, cond, null_cond,
            config, init_noise,
        )
    return _sample_fused(
        key, experts, params, router_fn, shape, cond, null_cond, config,
        mode, init_noise, stacked_params, latent_sharding, plan_sharding,
        coeff_tables, cluster_map,
    )


def sample_ensemble_step(
    experts: Sequence[ExpertSpec],
    params: Sequence | None,
    router_fn: Callable[[Array, Array], Array] | None,
    x: Array,
    t_idx: Array,
    slot_idx: Array,
    slot_w: Array,
    *,
    cond: dict | None = None,
    null_cond: dict | None = None,
    config: SamplerConfig | None = None,
    engine: str = "auto",
    stacked_params=None,
    latent_sharding=None,
    plan_sharding=None,
    coeff_tables=None,
    cluster_map=None,
) -> tuple[Array, Array, Array, Array]:
    """One Euler step of a *mixed-timestep* batch (continuous batching).

    The stepwise counterpart of :func:`sample_ensemble`'s fused scan: the
    unit of work is one step of each resident row, where every row sits
    at its **own** position ``t_idx[r]`` on the shared ``num_steps``-step
    Euler grid.  The per-run ``(S, 5, K)`` coefficient tables are already
    per-step lookups, so a mixed batch is a *gather* (``tables[t_idx]``,
    per-row ``ts``/``dt``) feeding the same ``kernels.ops.fused_step``
    launch — not a retrace and not a second kernel.  `repro.serving`
    drives this in a rolling batch where requests join and leave at step
    boundaries.

    Row state (all ``(B, ...)``-leading, carried by the caller across
    steps):

    * ``x`` — current latents;
    * ``t_idx`` — int32 step index per row: ``0 <= t_idx < num_steps``
      is an active row, ``num_steps`` (or any out-of-range value) marks
      a finished/empty row, which is frozen: its latent passes through
      unchanged and its ``t_idx`` does not advance;
    * ``slot_idx``/``slot_w`` — ``(B, k)`` carried routing slots
      (``core.dispatch.routed_slots``), refreshed per row on the row's
      own ``plan_refresh_every`` phase (``t_idx % R == 0``), so each
      request carries its own R-phase exactly as the lockstep scan does.

    Bitwise parity with the sequential scan rests on batch-row
    independence: the router and expert forwards compute row ``r``'s
    outputs from row ``r``'s inputs only (the same property `flush()`
    coalescing already relies on), and the fused-step kernel is
    elementwise per row with per-row ``dt``/coefficients.  A row
    advancing from ``t_idx = i`` therefore sees exactly the values the
    lockstep scan's step ``i`` would feed it, whatever its neighbors are
    doing — proven bitwise in ``tests/test_continuous.py``.

    Restrictions (fail loudly): routed engine, ``strategy`` in
    ``('top1', 'topk')``, ``step_fused=True`` — threshold/uniform plans
    collapse routing to a batch-global scalar gather, which has no
    per-row meaning in a mixed batch.

    Returns the advanced ``(x, t_idx, slot_idx, slot_w)``.
    """
    cond = cond or {}
    config = config if config is not None else SamplerConfig()
    if config.strategy not in ("top1", "topk"):
        raise ValueError(
            f"continuous batching requires per-sample routing (strategy "
            f"in ('top1', 'topk')); strategy={config.strategy!r} plans "
            f"are batch-uniform or dense and have no per-row meaning in "
            f"a mixed-timestep batch"
        )
    if not config.step_fused:
        raise ValueError(
            "continuous batching runs on the step-fused hot path only "
            "(step_fused=True): per-row dt is a fused-kernel operand"
        )
    mode = _resolve_engine(engine, experts, params, config)
    if mode != "routed":
        raise ValueError(
            f"continuous batching requires the routed engine; this "
            f"configuration resolved to {mode!r} (need a shared apply_fn "
            f"with stackable params and >1 expert)"
        )

    K = len(experts)
    B = x.shape[0]
    conv = config.conversion
    k_slots = 1 if config.strategy == "top1" else min(config.top_k, K)
    if slot_idx.shape != (B, k_slots) or slot_w.shape != (B, k_slots):
        raise ValueError(
            f"slot state must be ({B}, {k_slots}); got "
            f"slot_idx {slot_idx.shape}, slot_w {slot_w.shape}"
        )
    slot_idx = slot_idx.astype(jnp.int32)
    slot_w = slot_w.astype(jnp.float32)
    t_idx = t_idx.astype(jnp.int32)

    use_cfg = null_cond is not None and config.cfg_scale != 1.0
    batched = (
        use_cfg and config.batched_cfg
        and _cfg_batchable(cond, null_cond or {})
    )

    # Dispatch substrate — identical to _sample_fused's resolution.
    stacked = as_store(stacked_params, dtype=config.param_dtype)
    if stacked is None and params is None:
        raise ValueError(
            "params=None requires stacked_params (an ExpertParamStore or "
            "raw stacked pytree)"
        )
    if stacked is None:
        stacked = make_store(_stack_params(params),
                             dtype=config.param_dtype)
    # Bitwise-parity guard: expert params that are trace literals (toy
    # closures, tests) must NOT constant-fold into the expert forward.
    # The lockstep scan's loop body already treats them as opaque loop
    # inputs, so folding here (a loop-free program) would reassociate
    # constant adds — e.g. fma(x, a, b) + c vs fma(x, a, b + c) — and
    # break rolling == lockstep at the ulp level.  Real checkpoints
    # arrive as jit arguments and are unaffected.
    stacked = jax.tree.map(jax.lax.optimization_barrier, stacked)
    valid = getattr(stacked, "valid", None)
    ragged_fn = getattr(experts[0], "ragged_apply_fn", None)
    ragged_ok = ragged_fn is not None and all(
        getattr(e, "ragged_apply_fn", None) is ragged_fn for e in experts
    )
    backend = resolve_dispatch(config.dispatch, mode, True, False, ragged_ok)
    executor = make_executor(
        backend,
        apply_fns=[e.apply_fn for e in experts],
        params=params,
        stacked_params=stacked,
        conv=conv,
        ragged_apply_fn=ragged_fn if ragged_ok else None,
    )

    S = config.num_steps
    ts = _time_grid(S)
    if coeff_tables is not None:
        tables = coeff_tables                             # (S, 5, K)
    else:
        tables = coeff_tables_cached(
            tuple(e.objective for e in experts),
            tuple(e.schedule for e in experts),
            S, conv,
        )
    num_slots = tables.shape[-1]                          # capacity K

    refresh_every = int(config.plan_refresh_every)
    if refresh_every < 1:
        raise ValueError(
            f"plan_refresh_every must be >= 1, got {refresh_every}"
        )

    # Per-row grid state: finished/empty rows clip to a valid index (the
    # gathered values are discarded by the `active` mask below).
    i = jnp.clip(t_idx, 0, S - 1)                         # (B,)
    active = (t_idx >= 0) & (t_idx < S)                   # (B,)
    tb = ts[i]                                            # (B,)
    dt = ts[i] - ts[i + 1]                                # (B,)
    row_tab = tables[i]                                   # (B, 5, K)

    # Per-request R-phase: a row refreshes its routing slots on ITS OWN
    # refresh steps.  lax.cond skips the router forward entirely on
    # ticks where no resident row is at a refresh phase.
    refresh = active & (t_idx % refresh_every == 0)       # (B,)

    def fresh_slots():
        w = fusion_weights(
            experts, router_fn, x, tb,
            strategy=config.strategy, top_k=config.top_k,
            threshold=config.threshold,
            ddpm_low_noise_only=config.ddpm_low_noise_only,
            valid=valid, cluster_map=cluster_map,
        )                                                 # (B, K)
        return routed_slots(w, k_slots, valid=valid)

    with jax.named_scope("router"):
        new_idx, new_w = jax.lax.cond(
            jnp.any(refresh), fresh_slots, lambda: (slot_idx, slot_w)
        )
        slot_idx = jnp.where(refresh[:, None], new_idx, slot_idx)
        slot_w = jnp.where(refresh[:, None], new_w, slot_w)

        plan = plan_from_slots(slot_idx, slot_w, num_slots)
        if plan_sharding is not None:
            plan = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, plan_sharding),
                plan,
            )

    # CFG orchestration mirrors _sample_fused.fused_step_update; the
    # `tab` executors receive is unused by `predictions` (only the
    # unfused `velocity` reads it), so a representative (5, K) slice
    # keeps the signature satisfied.
    tab0 = tables[0]
    if batched:
        cond_g = _cfg_grouped_cond(cond, null_cond or {}, B)
        preds, w_all, idx_all = executor.predictions(
            plan, x, tb, cond_g, 2, tab0)
        g, scale = 2, config.cfg_scale
    elif use_cfg:
        p_c, w1, i1 = executor.predictions(
            plan, x, tb, _cfg_grouped_cond(cond, None, B), 1, tab0)
        p_u, _, _ = executor.predictions(
            plan, x, tb,
            _cfg_grouped_cond(dict(null_cond or {}), None, B), 1, tab0)
        preds = jnp.concatenate([p_c, p_u], axis=1)
        w_all = jnp.concatenate([w1, w1], axis=0)
        idx_all = jnp.concatenate([i1, i1], axis=0)
        g, scale = 2, config.cfg_scale
    else:
        preds, w_all, idx_all = executor.predictions(
            plan, x, tb, _cfg_grouped_cond(cond, None, B), 1, tab0)
        g, scale = 1, 1.0
    # Per-row coefficient slices, tiled branch-major like the weights.
    tab_all = row_tab if g == 1 \
        else jnp.concatenate([row_tab, row_tab], axis=0)  # (g·B, 5, K)
    x_step = ops.fused_step(
        preds, x, w_all, slot_coef_rows(tab_all, idx_all), dt,
        g=g, cfg_scale=scale,
        clamp=conv.clamp, alpha_min=conv.alpha_min,
    )
    mask = active.reshape((B,) + (1,) * (x.ndim - 1))
    x = jnp.where(mask, x_step, x)
    if latent_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, latent_sharding)
    t_idx = t_idx + active.astype(jnp.int32)
    return x, t_idx, slot_idx, slot_w


def sample_single_expert(
    key: jax.Array,
    expert: ExpertSpec,
    params,
    shape: tuple[int, ...],
    *,
    cond: dict | None = None,
    null_cond: dict | None = None,
    config: SamplerConfig | None = None,
) -> Array:
    """Single-expert ODE sampling (Table 3 'FM' and 'DDPM→FM' rows)."""
    config = config if config is not None else SamplerConfig()
    return sample_ensemble(
        key, [expert], [params], None, shape,
        cond=cond, null_cond=null_cond,
        config=dataclasses.replace(config, strategy="full"),
    )


def sample_ddpm_ancestral(
    key: jax.Array,
    apply_fn: Callable[..., Array],
    params,
    shape: tuple[int, ...],
    *,
    cond: dict | None = None,
    null_cond: dict | None = None,
    num_steps: int = 75,
    cfg_scale: float = 6.0,
    schedule_name: str = "cosine",
) -> Array:
    """Native DDPM ancestral sampler (Table 3 baseline row).

    DDIM-style deterministic-σ=... we use the stochastic ancestral update
    with the VP cosine schedule, operating on the discrete grid.
    """
    cond = cond or {}
    sched = get_schedule(schedule_name)
    ts = jnp.linspace(1.0, 0.0, num_steps + 1)
    x = jax.random.normal(key, shape, dtype=jnp.float32)

    def pred_eps(x, tb):
        e_c = apply_fn(params, x, tb, **cond)
        if null_cond is None or cfg_scale == 1.0:
            return e_c
        e_u = apply_fn(params, x, tb, **null_cond)
        return cfg_combine(e_c, e_u, cfg_scale)

    def step(carry, i):
        x, key = carry
        key, nk = jax.random.split(key)
        t_hi, t_lo = ts[i], ts[i + 1]
        tb = jnp.full((shape[0],), t_hi)
        eps = pred_eps(x, tb)
        a_hi, s_hi = sched.coeffs(t_hi)
        a_lo, s_lo = sched.coeffs(t_lo)
        x0 = (x - s_hi * eps) / jnp.maximum(a_hi, 0.01)
        x0 = jnp.clip(x0, -20.0, 20.0)
        # DDIM (eta=0) update on the continuous grid.
        x_next = a_lo * x0 + s_lo * eps
        return (x_next, key), None

    (x, _), _ = jax.lax.scan(step, (x, key), jnp.arange(num_steps))
    return x
