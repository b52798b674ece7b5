"""Serving hot-path benchmark: seed dense sampler vs compute-sparse engine.

Measures, for the paper's 8-expert top-2 + CFG serving configuration:

* **expert forwards per step** — counted exactly by tracing the sampler
  with an instrumented ``apply_fn`` (``lax.scan`` traces its body once, so
  trace-time call counts == per-step execution counts).  Seed path:
  ``2·K`` (every expert, twice for CFG).  Sparse path: ``k`` (routed
  experts only, CFG batched) — within the ``(k+1)`` acceptance budget.
* **img/s** — wall-clock of the jitted end-to-end sampler (compile
  excluded via warmup; median of repeated runs).
* **retrace count** — ``ServingEngine.stats['traces']`` across repeated
  same-shape requests (must stay at 1).

* **dispatch backends** (``--dispatch grouped``) — the ``core.dispatch``
  executor axis: sort-based grouped execution is measured against the
  per-sample gathered baseline on the same ensemble.  Grouped forwards
  are counted at *runtime* (``jax.debug.callback``): the grouped trace
  compiles one bucket branch per power-of-two segment size, so a
  trace-time count would tally every branch while only one executes per
  expert per step.  Budget: executed segment passes ≤ resident experts,
  vs ``B·k·2`` gathered model-rows with batched CFG.

* **quantized expert stores** (``--param-dtype {bf16,int8,fp8}``) — the
  ``core.param_store`` storage axis: resident expert-param bytes
  (``ExpertParamStore.nbytes()``, int8 gate ≥ 3.5× smaller than dense
  fp32), img/s, and max-abs final-latent parity vs the dense store on the
  same key, recorded under the ``quantized`` section keyed by dtype.

* **step fusion + plan reuse** (``--plan-refresh N``, always collected) —
  the ``core.sampling`` step-fused hot path vs the unfused grouped
  baseline, two JSON sections:

  - ``fused_step``: img/s of the step-fused sampler (R=1 and R=N),
    parity vs the unfused path (gate: max-abs diff == 0 at R=1 — the
    ``hetero_fuse_step`` oracle reuses the exact unfused math), and an
    HBM-bytes-per-step estimate from XLA's own cost model
    (``launch.hlo_analysis.compiled_bytes_accessed``); acceptance:
    img/s ≥ 1.1× the unfused grouped baseline;
  - ``plan_reuse``: keyed ``R<N>`` (sub-merged like ``quantized``), with
    per-interval img/s, refreshes/run, and max-abs drift vs per-step
    routing (the FID-proxy for the router-posteriors-change-slowly
    premise).

Emits ``name,us_per_call,derived`` CSV rows for the harness and a JSON
artifact (``BENCH_sampler.json``) via ``--json-out`` / ``write_json`` so
future PRs can track the perf trajectory.  ``write_json`` merges into an
existing artifact by top-level section, so a ``--shards``-only or
``--dispatch``-only rerun refreshes its own section without dropping the
others.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time


def _peek_shards() -> int:
    """Parse --shards from argv BEFORE importing jax: the sharded mode
    needs that many host devices, and jax locks the device count at first
    init (same constraint as launch/dryrun.py)."""
    for i, a in enumerate(sys.argv):
        if a == "--shards" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--shards="):
            return int(a.split("=", 1)[1])
    return 1


_SHARDS = _peek_shards()
if _SHARDS > 1 and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_SHARDS}"
    ).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ExpertSpec, SamplerConfig, sample_ensemble
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import ServingEngine
from repro.models import dit as D
from repro.models.config import dit_b2, router_b2

NUM_EXPERTS = 8
BATCH = int(os.environ.get("REPRO_BENCH_SAMPLER_BATCH", 8))
STEPS = int(os.environ.get("REPRO_BENCH_SAMPLER_STEPS", 8))
TOP_K = 2
CFG_SCALE = 7.5
LATENT = int(os.environ.get("REPRO_BENCH_SAMPLER_LATENT", 16))
REPS = int(os.environ.get("REPRO_BENCH_SAMPLER_REPS", 5))


@functools.lru_cache(maxsize=2)
def _build(latent: int = LATENT):
    """8 heterogeneous (DDPM/FM) experts sharing one instrumented apply.

    16×16 latents (256-token sequences after 2×2 patching at d=128) are
    the smallest scale where CPU wall-clock is forward-compute- rather
    than dispatch/gather-dominated, so the measured speedup reflects the
    forward-count reduction rather than scan overhead.  (The continuous
    section passes a smaller ``latent`` — see ``collect_continuous``.)
    """
    cfg = dit_b2().reduced(latent_size=latent)
    base_apply = D.make_expert_apply(cfg)
    counter = {"n": 0}

    def counted_apply(params, x, t, **cond):
        counter["n"] += 1                       # trace-time call counter
        return base_apply(params, x, t, **cond)

    experts, params = [], []
    for i in range(NUM_EXPERTS):
        obj = "ddpm" if i % 4 == 0 else "fm"    # paper-style 2 DDPM : 6 FM
        experts.append(ExpertSpec(
            f"e{i}", obj, "cosine" if obj == "ddpm" else "linear",
            counted_apply, i,
        ))
        params.append(D.init(cfg, jax.random.PRNGKey(10 + i)))
    rcfg = router_b2(num_clusters=NUM_EXPERTS).reduced(latent_size=latent)
    router_fn = D.make_router_fn(rcfg, D.init(rcfg, jax.random.PRNGKey(99)))
    text = jax.random.normal(
        jax.random.PRNGKey(5), (BATCH, cfg.text_len, cfg.text_dim)
    )
    return cfg, experts, params, router_fn, text, counter


def _sampler_fn(experts, params, router_fn, text, engine, dispatch="auto",
                param_dtype="native", step_fused=True, plan_refresh=1,
                latent=LATENT, top_k=TOP_K):
    sampler = SamplerConfig(
        num_steps=STEPS, cfg_scale=CFG_SCALE, strategy="topk", top_k=top_k,
        dispatch=dispatch, param_dtype=param_dtype,
        step_fused=step_fused, plan_refresh_every=plan_refresh,
    )

    def fn(key):
        return sample_ensemble(
            key, experts, params, router_fn,
            (BATCH, latent, latent, 4),
            cond={"text_emb": text}, null_cond={"text_emb": None},
            config=sampler, engine=engine,
        )

    return fn


def _forwards_per_step(counter, fn) -> float:
    # ``lax.scan`` traces its body exactly once, so the trace-time call
    # count of the instrumented apply IS the per-step forward count.
    counter["n"] = 0
    jax.eval_shape(fn, jax.random.PRNGKey(0))
    return float(counter["n"])


def _time_imgs_per_s(*fns, return_outputs=False, pre_compiled=False):
    """Interleaved best-of-REPS timing (min is robust to load spikes).

    ``return_outputs=True`` additionally returns each fn's warm-up output
    (all computed from ``PRNGKey(0)``, so they are directly comparable —
    the parity inputs for cross-backend/cross-store sections).
    ``pre_compiled=True`` accepts AOT-compiled executables (from
    ``jax.jit(fn).lower(key).compile()``) and times them as-is, so a
    caller that also needs the compiled object (cost analysis) pays for
    exactly one compile.
    """
    jitted = list(fns) if pre_compiled else [jax.jit(fn) for fn in fns]
    outs = [jax.block_until_ready(f(jax.random.PRNGKey(0)))
            for f in jitted]                                # compile
    warm = list(outs)
    times = [[] for _ in fns]
    for r in range(REPS):
        for i, f in enumerate(jitted):
            t0 = time.time()
            outs[i] = jax.block_until_ready(f(jax.random.PRNGKey(r + 1)))
            times[i].append(time.time() - t0)
    res = [
        (BATCH / float(np.min(ts)),
         bool(np.isfinite(np.asarray(out)).all()))
        for ts, out in zip(times, outs)
    ]
    return (res, warm) if return_outputs else res


def _retrace_count(experts, params, router_fn, text, requests=3) -> int:
    engine = ServingEngine(
        experts=experts, expert_params=params, router_fn=router_fn,
        latent_shape=(LATENT, LATENT, 4),
        sampler=SamplerConfig(num_steps=STEPS, cfg_scale=CFG_SCALE,
                              strategy="topk", top_k=TOP_K),
    )
    for r in range(requests):
        jax.block_until_ready(
            engine.generate(jax.random.PRNGKey(r), text, BATCH)
        )
    return int(engine.stats["traces"])


def collect() -> dict:
    cfg, experts, params, router_fn, text, counter = _build()

    seed_fn = _sampler_fn(experts, params, router_fn, text, "reference")
    # dispatch pinned to 'gathered': this section's forwards/step is
    # counted at TRACE time, and the grouped backend (what 'auto' now
    # resolves to) traces every power-of-two bucket branch — its runtime
    # forward count is tracked separately in the 'grouped' section
    # (--dispatch grouped), with jax.debug.callback counting.
    sparse_fn = _sampler_fn(experts, params, router_fn, text, "auto",
                            dispatch="gathered")

    seed_fwd = _forwards_per_step(counter, seed_fn)
    sparse_fwd = _forwards_per_step(counter, sparse_fn)
    (seed_ips, seed_ok), (sparse_ips, sparse_ok) = _time_imgs_per_s(
        seed_fn, sparse_fn
    )
    retraces = _retrace_count(experts, params, router_fn, text)

    return {
        "config": {
            "num_experts": NUM_EXPERTS, "top_k": TOP_K, "batch": BATCH,
            "num_steps": STEPS, "cfg_scale": CFG_SCALE,
            "latent": [LATENT, LATENT, 4], "model": cfg.name,
            "backend": jax.default_backend(),
        },
        "seed": {
            "expert_forwards_per_step": seed_fwd,
            "img_per_s": seed_ips,
            "finite": seed_ok,
        },
        "sparse": {
            "expert_forwards_per_step": sparse_fwd,
            "img_per_s": sparse_ips,
            "finite": sparse_ok,
            "serving_retraces_3_requests": retraces,
        },
        "speedup": sparse_ips / max(seed_ips, 1e-9),
        "forward_reduction": seed_fwd / max(sparse_fwd, 1e-9),
        "meets_forward_budget": sparse_fwd <= TOP_K + 1,   # ≤ (k+1)/step
        "meets_2x_speedup": sparse_ips >= 2.0 * seed_ips,
    }


def collect_sharded(shards: int) -> dict:
    """Expert-parallel serving benchmark on a forced multi-device host.

    Places the stacked 8-expert pytree on an ("expert", "data") mesh with
    ``shards`` expert shards (run with ``--shards N`` so the module forces
    N host devices) and reports per-shard forwards/step — each shard
    holds K/N resident experts and owns 1/N of the routed gather — plus
    end-to-end img/s against the unsharded engine on the same host.
    """
    ndev = jax.device_count()
    if ndev < shards:
        raise RuntimeError(
            f"--shards {shards} needs {shards} devices, have {ndev} "
            f"(pass --shards on the command line so XLA_FLAGS is set "
            f"before jax initializes)"
        )
    if NUM_EXPERTS % shards:
        # ServingEngine would raise too; fail here with bench context so
        # BENCH_sampler.json never records fictitious per-shard stats.
        raise RuntimeError(
            f"--shards {shards} must divide NUM_EXPERTS={NUM_EXPERTS}"
        )
    cfg, experts, params, router_fn, text, counter = _build()
    sampler = SamplerConfig(
        num_steps=STEPS, cfg_scale=CFG_SCALE, strategy="topk", top_k=TOP_K,
    )

    def make_engine(**shard_kw):
        return ServingEngine(
            experts=experts, expert_params=params, router_fn=router_fn,
            latent_shape=(LATENT, LATENT, 4), sampler=sampler, **shard_kw,
        )

    engines = [
        make_engine(),
        make_engine(n_expert_shards=shards,
                    n_data_shards=max(1, ndev // shards)),
    ]
    # compile each (scan body traces once -> counter == forwards/step),
    # then interleave the timed reps (min is robust to load spikes, and
    # interleaving keeps the sharded-vs-unsharded ratio fair under load —
    # same policy as _time_imgs_per_s).
    fwds, outs = [], []
    for engine in engines:
        counter["n"] = 0
        outs.append(jax.block_until_ready(
            engine.generate(jax.random.PRNGKey(0), text, BATCH)
        ))
        fwds.append(float(counter["n"]))
    times = [[] for _ in engines]
    for r in range(REPS):
        for i, engine in enumerate(engines):
            t0 = time.time()
            outs[i] = jax.block_until_ready(
                engine.generate(jax.random.PRNGKey(r + 1), text, BATCH)
            )
            times[i].append(time.time() - t0)
    (base_fwd, sh_fwd) = fwds
    base_ips, sh_ips = (BATCH / float(np.min(ts)) for ts in times)
    base_ok, sh_ok = (bool(np.isfinite(np.asarray(o)).all()) for o in outs)
    engine = engines[1]
    return {
        "shards": shards,
        "devices": ndev,
        "mesh": {k: int(v) for k, v in engine.mesh.shape.items()},
        "resident_experts_per_shard": NUM_EXPERTS / shards,
        "expert_forwards_per_step_global": sh_fwd,
        "expert_forwards_per_step_unsharded": base_fwd,
        "per_shard_forwards_per_step": sh_fwd / shards,
        "img_per_s": sh_ips,
        "img_per_s_unsharded_same_host": base_ips,
        "finite": sh_ok and base_ok,
        "parity_note": "outputs asserted equal in tests/"
                       "test_sharded_serving.py + launch/sharded_parity.py",
    }


def collect_dispatch(dispatch: str) -> dict:
    """Executor-backend section (``core.dispatch``), vs the gathered path.

    Measures, for the same 8-expert top-2 + CFG ensemble:

    * **executed forwards/step** — counted at runtime via
      ``jax.debug.callback`` (fires only in the bucket branch that
      actually runs), since the grouped trace contains every power-of-two
      bucket branch and a trace-time count would tally all of them;
    * **model-rows/step** — total latent rows pushed through expert
      forwards (grouped: padded segment rows; gathered reference:
      ``B·k·2`` with batched CFG);
    * **img/s** vs the gathered backend, interleaved timing;
    * **parity** — max |grouped − gathered| on the same key.
    """
    cfg, experts, params, router_fn, text, counter = _build()
    shared_apply = experts[0].apply_fn

    runtime = {"calls": 0, "rows": 0}

    def _bump(rows):
        runtime["calls"] += 1
        runtime["rows"] += int(rows)

    def rt_apply(p, x, t, **cond):
        jax.debug.callback(_bump, x.shape[0])
        return shared_apply(p, x, t, **cond)

    rt_experts = [dataclasses.replace(e, apply_fn=rt_apply)
                  for e in experts]

    base_fn = jax.jit(_sampler_fn(experts, params, router_fn, text,
                                  "routed", dispatch="gathered"))
    disp_fn = jax.jit(_sampler_fn(experts, params, router_fn, text,
                                  "routed", dispatch=dispatch))
    # compile (once per backend) + parity on the same key
    out_b = jax.block_until_ready(base_fn(jax.random.PRNGKey(0)))
    out_d = jax.block_until_ready(disp_fn(jax.random.PRNGKey(0)))
    max_diff = float(jnp.abs(out_d - out_b).max())
    times: list[list[float]] = [[], []]
    for r in range(REPS):
        for i, f in enumerate((base_fn, disp_fn)):
            t0 = time.time()
            out = jax.block_until_ready(f(jax.random.PRNGKey(r + 1)))
            times[i].append(time.time() - t0)
            if i:
                out_d = out
            else:
                out_b = out
    base_ips, disp_ips = (BATCH / float(np.min(ts)) for ts in times)
    base_ok = bool(np.isfinite(np.asarray(out_b)).all())
    disp_ok = bool(np.isfinite(np.asarray(out_d)).all())

    # runtime forward count: one warm-up compile, then a counted run.
    # block_until_ready only waits for array outputs; on asynchronous
    # backends debug callbacks can still be in flight, so fence with
    # effects_barrier before touching the host-side counters.
    rt_fn = jax.jit(_sampler_fn(rt_experts, params, router_fn, text,
                                "routed", dispatch=dispatch))
    jax.block_until_ready(rt_fn(jax.random.PRNGKey(0)))
    jax.effects_barrier()
    runtime["calls"] = runtime["rows"] = 0
    jax.block_until_ready(rt_fn(jax.random.PRNGKey(1)))
    jax.effects_barrier()
    fwd_per_step = runtime["calls"] / STEPS
    rows_per_step = runtime["rows"] / STEPS

    gathered_rows = BATCH * TOP_K * 2           # B·k lanes × batched CFG
    # routed rows the plan actually asked for; anything above this in the
    # runtime row count is bucket padding (grouped pads each expert's
    # segment to a power of two so segment growth doesn't retrace).
    routed_rows = BATCH * TOP_K * 2
    return {
        "dispatch": dispatch,
        "expert_forwards_per_step_executed": fwd_per_step,
        "model_rows_per_step": rows_per_step,
        "padded_rows_per_step": rows_per_step,
        "routed_rows_per_step": routed_rows,
        "padding_overhead": rows_per_step / routed_rows - 1.0,
        "resident_experts": NUM_EXPERTS,
        "meets_resident_forward_budget": fwd_per_step <= NUM_EXPERTS,
        "gathered_rows_per_step": gathered_rows,
        "img_per_s": disp_ips,
        "img_per_s_gathered": base_ips,
        "speedup_vs_gathered": disp_ips / max(base_ips, 1e-9),
        "finite": disp_ok and base_ok,
        "parity_max_abs_diff_vs_gathered": max_diff,
    }


def collect_ragged(top_k: int = 4, latent: int = 20) -> dict:
    """One-kernel ragged backend section, vs the grouped backend.

    ``collect_dispatch`` measures a backend against the *gathered*
    reference and counts rows through the per-expert ``apply_fn`` — the
    ragged backend never calls it (one pair-major forward per step), so
    this section instead compares ragged against grouped directly:

    * **img/s** both backends, interleaved timing, plus the tracked
      ``meets_1p15x_vs_grouped`` acceptance gate;
    * **parity** — max |ragged − grouped| on the same key; dense float32
      params must be *bitwise* (the pair-major unscatter is exact);
    * **rows/step** — runtime-counted via an instrumented ragged
      forward.  Ragged runs exactly the ``B·k·g`` routed rows — zero
      bucket padding — so ``padding_overhead`` is the measured 0.0
      against the grouped section's padded number.

    Regime choice: like ``collect_continuous``, this section pins its
    own routing width — ``top_k=4`` against the other sections'
    ``TOP_K=2``.  What the ragged kernel removes is the grouped
    backend's *per-expert* costs: power-of-two segment buckets and one
    ``lax.switch`` branch per resident expert.  Those scale with how
    finely the routed rows split across experts, and at ``top_k=2``
    the B=8 bench batch lands segments on bucket boundaries (measured
    padding only +12.5%), hiding the effect the kernel exists to
    delete.  ``top_k=4`` (heavier per-sample fusion — more experts
    blended per image, the serving knob this ensemble exposes) makes
    the bench router's skew land 5–7-pair segments that grouped rounds
    to 8: +28% padded rows on average over steps/keys, never below
    +15% — while ragged still runs exactly ``B·k·g`` rows (measured
    below, ``padding_overhead == 0.0``).  ``latent=20`` keeps per-row
    compute large enough that the CPU fallback's per-pair weight
    gather (``wd[expert_ids]`` — a fixed byte cost per routed pair
    that the Pallas path doesn't pay; its tiles index the stacked
    leaves in place) doesn't mask the padding difference the section
    exists to measure.

    Timing: the host is a single shared core, so load drift between
    the two arms' windows is the dominant error.  Each rep times the
    two samplers back-to-back (the pair shares one load regime) and
    the tracked ``speedup_vs_grouped`` is the *median of the per-rep
    paired ratios* — robust both to spikes (unlike a ratio of sums)
    and to drift between windows (unlike a ratio of per-arm minima).
    The per-arm ``img_per_s`` floors stay best-of-reps, matching the
    other sections.

    The timed ragged sampler is *uninstrumented*: the rows counter is a
    runtime ``jax.debug.callback`` (a host round-trip every step) that
    the grouped arm does not pay — it runs in a separate jit used only
    for the rows/parity measurement.
    """
    cfg, experts, params, router_fn, text, counter = _build(latent)
    ragged_apply = D.make_ragged_expert_apply(cfg)

    runtime = {"rows": 0}

    def _bump(rows):
        runtime["rows"] += int(rows)

    def rt_ragged(view, x_p, t_p, cond, pe, g):
        jax.debug.callback(_bump, x_p.shape[0] * g)
        return ragged_apply(view, x_p, t_p, cond, pe, g)

    r_experts = [dataclasses.replace(e, ragged_apply_fn=ragged_apply)
                 for e in experts]
    rt_experts = [dataclasses.replace(e, ragged_apply_fn=rt_ragged)
                  for e in experts]
    mk = functools.partial(_sampler_fn, top_k=top_k, latent=latent)
    grouped_fn = jax.jit(mk(experts, params, router_fn, text,
                            "routed", dispatch="grouped"))
    ragged_fn = jax.jit(mk(r_experts, params, router_fn, text,
                           "routed", dispatch="ragged"))
    rt_ragged_fn = jax.jit(mk(rt_experts, params, router_fn, text,
                              "routed", dispatch="ragged"))
    out_g = jax.block_until_ready(grouped_fn(jax.random.PRNGKey(0)))
    out_r = jax.block_until_ready(rt_ragged_fn(jax.random.PRNGKey(0)))
    jax.effects_barrier()
    max_diff = float(jnp.abs(out_r - out_g).max())

    runtime["rows"] = 0
    jax.block_until_ready(rt_ragged_fn(jax.random.PRNGKey(1)))
    jax.effects_barrier()
    rows_per_step = runtime["rows"] / STEPS

    jax.block_until_ready(ragged_fn(jax.random.PRNGKey(0)))  # compile
    reps = max(REPS, 9)
    times: list[list[float]] = [[], []]
    for r in range(reps):
        for i, f in enumerate((grouped_fn, ragged_fn)):
            t0 = time.time()
            out = jax.block_until_ready(f(jax.random.PRNGKey(r + 1)))
            times[i].append(time.time() - t0)
            if i:
                out_r = out
            else:
                out_g = out
    grouped_ips, ragged_ips = (BATCH / float(np.min(ts)) for ts in times)
    speedup = float(np.median(np.asarray(times[0]) / np.asarray(times[1])))

    routed_rows = BATCH * top_k * 2             # B·k pairs × CFG branches
    return {
        "dispatch": "ragged",
        "top_k": top_k,
        "latent": latent,
        "img_per_s": ragged_ips,
        "img_per_s_grouped": grouped_ips,
        "speedup_vs_grouped": speedup,
        "meets_1p15x_vs_grouped": bool(speedup >= 1.15),
        "parity_max_abs_diff_vs_grouped": max_diff,
        "bitwise_vs_grouped": bool(max_diff == 0.0),
        "padded_rows_per_step": rows_per_step,
        "routed_rows_per_step": routed_rows,
        "padding_overhead": rows_per_step / routed_rows - 1.0,
        "finite": bool(np.isfinite(np.asarray(out_r)).all()
                       and np.isfinite(np.asarray(out_g)).all()),
    }


def collect_step_fusion(plan_refresh: int) -> tuple[dict, dict]:
    """Step-fused hot path + plan-reuse sections, vs the unfused baseline.

    Three samplers on the same grouped 8-expert top-2 + CFG ensemble:

    * **unfused** — ``step_fused=False``, per-step routing: the PR-3/4
      grouped baseline (``fused_velocity`` → ``cfg_combine`` → Euler as
      separate ops);
    * **fused R=1** — the step-fused kernel, per-step routing.  Must be
      *bit-identical* to unfused (``parity_max_abs_diff == 0``: the
      oracle delegates to the same convert-and-fuse math);
    * **fused R=N** — plan recomputed every N-th step only (``--plan-
      refresh``), the full new hot path.  Drift vs R=1 is the tracked
      quality proxy.

    Also records an HBM-bytes-per-step estimate for the fused vs unfused
    executable (``launch.hlo_analysis.compiled_bytes_accessed`` — XLA's
    own "bytes accessed" cost model, 0.0 where the backend reports none).

    Returns ``(fused_step_section, plan_reuse_section)``; ``plan_reuse``
    is keyed ``"R<N>"`` so reruns with other refresh intervals merge.
    """
    from repro.launch.hlo_analysis import compiled_bytes_accessed

    cfg, experts, params, router_fn, text, counter = _build()
    mk = functools.partial(_sampler_fn, experts, params, router_fn, text,
                           "routed", dispatch="grouped")
    unfused_fn = mk(step_fused=False)
    fused_fn = mk(step_fused=True)

    # AOT-compile each sampler exactly once: the same executables feed
    # the timing loop AND XLA's cost analysis.  plan_refresh == 1 IS the
    # fused R=1 sampler — don't compile and time the same config twice.
    key0 = jax.random.PRNGKey(0)
    fns = [unfused_fn, fused_fn]
    if plan_refresh > 1:
        fns.append(mk(step_fused=True, plan_refresh=plan_refresh))
    compiled = [jax.jit(fn).lower(key0).compile() for fn in fns]
    bytes_unfused = compiled_bytes_accessed(compiled[0])
    bytes_fused = compiled_bytes_accessed(compiled[1])

    timings, outs = _time_imgs_per_s(
        *compiled, return_outputs=True, pre_compiled=True)
    if plan_refresh == 1:
        timings = timings + [timings[1]]
        outs = outs + [outs[1]]
    ((unf_ips, unf_ok), (fus_ips, fus_ok), (reuse_ips, reuse_ok)) = timings
    (out_u, out_f, out_r) = outs
    fused_parity = float(jnp.abs(out_f - out_u).max())
    drift = float(jnp.abs(out_r - out_f).max())
    latent_scale = float(jnp.abs(out_f).max())

    fused_step = {
        "plan_refresh": plan_refresh,
        "img_per_s": reuse_ips,
        "img_per_s_fused_R1": fus_ips,
        "img_per_s_unfused": unf_ips,
        # step fusion in isolation (R=1 both sides): on CPU this hovers
        # around 1.0 — its gate only demands no regression, so a fusion
        # slowdown can't hide behind a healthy plan-reuse number ...
        "speedup_vs_unfused": fus_ips / max(unf_ips, 1e-9),
        "meets_1p0x_speedup_fusion_only": bool(fus_ips >= 1.0 * unf_ips),
        # ... while the 1.1x acceptance gate reads the full hot path
        # (fusion + plan reuse at R=N) and says so in its name.
        "speedup_with_plan_reuse": reuse_ips / max(unf_ips, 1e-9),
        "meets_1p1x_speedup_with_plan_reuse": bool(
            reuse_ips >= 1.1 * unf_ips
        ),
        "parity_max_abs_diff_vs_unfused": fused_parity,   # R=1, must be 0
        "hbm_bytes_per_step": bytes_fused / STEPS,
        "hbm_bytes_per_step_unfused": bytes_unfused / STEPS,
        "hbm_bytes_per_step_saved": (bytes_unfused - bytes_fused) / STEPS,
        "finite": bool(unf_ok and fus_ok and reuse_ok),
    }
    plan_reuse = {
        "R1": {
            "plan_refresh": 1,
            "img_per_s": fus_ips,
            "plan_refreshes_per_run": STEPS,
            # acceptance gate: R=1 must match the unfused path exactly
            "parity_max_abs_diff": fused_parity,
        },
    }
    if plan_refresh > 1:
        plan_reuse[f"R{plan_refresh}"] = {
            "plan_refresh": plan_refresh,
            "img_per_s": reuse_ips,
            "plan_refreshes_per_run": -(-STEPS // plan_refresh),
            "speedup_vs_R1": reuse_ips / max(fus_ips, 1e-9),
            "drift_max_abs_vs_R1": drift,
            "drift_rel_to_latent_scale": drift / max(latent_scale, 1e-9),
        }
    return fused_step, plan_reuse


def collect_and_merge_step_fusion(
    json_out: str | None, plan_refresh: int,
) -> tuple[dict, dict]:
    """Collect the ``fused_step``/``plan_reuse`` sections and stage them
    for ``write_json``.

    The single entry point shared by this module's ``main`` and
    ``benchmarks/run.py --plan-refresh``: runs :func:`collect_step_fusion`,
    stashes both sections in ``_LAST``, and sub-merges ``plan_reuse`` by
    refresh interval against any existing artifact at ``json_out``.
    """
    fused_sec, reuse_sec = collect_step_fusion(max(1, plan_refresh))
    _LAST["fused_step"] = fused_sec
    _LAST["plan_reuse"] = (
        submerge_section(json_out, "plan_reuse", reuse_sec)
        if json_out else reuse_sec
    )
    return fused_sec, reuse_sec


def _jitter_params(tree, key):
    """Add small noise to every leaf (defeats §2.5 zero-init layers)."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        leaf + 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])


def collect_quantized(param_dtype: str) -> dict:
    """Quantized expert-store section (``core.param_store``), vs dense.

    Measures, for the same 8-expert top-2 + CFG ensemble:

    * **resident param bytes** — ``ExpertParamStore.nbytes()`` of the
      requested storage vs the native (fp32) dense store.  int8 must hit
      the ≥ 3.5× reduction acceptance gate;
    * **img/s** vs the dense store on the same dispatch backend,
      interleaved timing;
    * **parity** — max |quantized − dense| over the final latents for
      the same key (the FID-proxy tracked across PRs).
    """
    from repro.core.param_store import make_store
    from repro.models import dit as D

    cfg, experts, params, router_fn, text, counter = _build()
    # Freshly-initialized DiT experts carry §2.5 zero-init output layers,
    # which make the forward weight-independent (identically zero final
    # projection) and the parity metric vacuously 0.  Jitter every leaf
    # so the recorded parity measures real quantization error.
    params = [_jitter_params(p, jax.random.PRNGKey(1234 + i))
              for i, p in enumerate(params)]
    stacked = D.stack_expert_params(params)
    dense_bytes = make_store(stacked, dtype="native").nbytes()
    q_bytes = make_store(stacked, dtype=param_dtype).nbytes()

    dense_fn = _sampler_fn(experts, params, router_fn, text, "routed")
    quant_fn = _sampler_fn(experts, params, router_fn, text, "routed",
                           param_dtype=param_dtype)
    ((dense_ips, dense_ok), (quant_ips, quant_ok)), (out_d, out_q) = \
        _time_imgs_per_s(dense_fn, quant_fn, return_outputs=True)
    max_diff = float(jnp.abs(out_q - out_d).max())
    dense_scale = float(jnp.abs(out_d).max())
    reduction = dense_bytes / max(q_bytes, 1)
    return {
        "param_dtype": param_dtype,
        "resident_param_bytes": int(q_bytes),
        "resident_param_bytes_dense": int(dense_bytes),
        "byte_reduction_vs_dense": reduction,
        "meets_3p5x_byte_reduction": bool(reduction >= 3.5)
        if param_dtype in ("int8", "fp8") else None,
        "img_per_s": quant_ips,
        "img_per_s_dense": dense_ips,
        "parity_max_abs_diff_vs_dense": max_diff,
        "parity_rel_to_dense_latent_scale": max_diff / max(dense_scale,
                                                          1e-9),
        "finite": bool(dense_ok and quant_ok),
    }


def collect_continuous(
    n_requests: int = 144, max_resident: int = 48, arrival_every: int = 1,
    arrivals_per_tick: int = 6, latent: int = 4,
) -> dict:
    """Continuous-batching section (``repro.serving``), vs lockstep flush.

    Two arms over the same DiT ensemble and the same ``n_requests``
    single-image text-conditioned requests:

    * **continuous** — ``arrivals_per_tick`` requests arrive every
      ``arrival_every`` scheduler ticks into a
      :class:`repro.serving.ContinuousScheduler` rolling batch of
      ``max_resident``; mixed-timestep residents share one fused-step
      launch per tick, so arrivals overlap instead of queueing behind
      full ``num_steps`` runs.  Latency percentiles come from the
      scheduler's own ``LatencyRecorder`` (what ``ServingEngine.stats``
      reports in production).
    * **lockstep flush baseline** — the pre-existing serving path: each
      request is a dedicated ``submit`` + ``flush()`` pair, i.e. a full
      ``num_steps`` batch-1 scan per request, one after another.

    Regime choice: this harness runs on a single CPU core, where the
    expert forward itself scales nearly linearly in batch — the only
    real batching economy is the grouped executor's per-expert gemms,
    whose dispatch/sort/padding overhead amortizes at LARGE resident
    batches and SMALL latents.  Measured per-row-step cost at
    ``latent=4``: lockstep B=1 ≈ 2.1 ms vs rolling B=16 ≈ 1.28 ms,
    B=48 ≈ 0.87 ms — the headroom the gate certifies.
    ``arrivals_per_tick=6`` matches the offered load to the service
    rate (``max_resident/num_steps`` = 6 requests per tick), keeping
    the rolling batch full; at 1/tick the steady-state residency is
    only ``num_steps`` rows and capacity padding burns the advantage.
    At the other sections' ``LATENT=16``, batch-1 already saturates the
    core and no scheduler can beat sequential lockstep on wall-clock —
    that regime measures kernels, not scheduling.

    Both arms pay one warm-up request first (compile excluded; the
    scheduler's recorder is reset after warm-up).  Acceptance gate:
    continuous img/s ≥ 1.2× the lockstep baseline.
    """
    from repro.serving import ContinuousScheduler

    cfg, experts, params, router_fn, text, counter = _build(latent)
    sampler = SamplerConfig(
        num_steps=STEPS, cfg_scale=CFG_SCALE, strategy="topk", top_k=TOP_K,
    )
    text1 = text[:1]

    def make_engine():
        return ServingEngine(
            experts=experts, expert_params=params, router_fn=router_fn,
            latent_shape=(latent, latent, 4), sampler=sampler,
        )

    # --- continuous arm -------------------------------------------------
    engine = make_engine()
    sched = ContinuousScheduler(engine, max_resident=max_resident)
    warm = sched.submit(jax.random.PRNGKey(0), text1)     # compile
    sched.run_until_idle()
    jax.block_until_ready(warm.result())
    sched.metrics.reset()
    t0 = time.time()
    handles = []
    r = 0
    while r < n_requests:
        for _ in range(min(arrivals_per_tick, n_requests - r)):
            handles.append(sched.submit(jax.random.PRNGKey(100 + r), text1))
            r += 1
        for _ in range(arrival_every):
            sched.step()
    sched.run_until_idle()
    outs = [h.result() for h in handles]
    jax.block_until_ready(outs)
    cont_s = time.time() - t0
    snap = sched.metrics.snapshot()
    cont_ips = n_requests / cont_s
    cont_ok = all(bool(np.isfinite(np.asarray(o)).all()) for o in outs)

    # --- lockstep flush baseline ----------------------------------------
    twin = make_engine()
    h = twin.submit(jax.random.PRNGKey(0), text1, 1)      # compile
    twin.flush()
    jax.block_until_ready(h.result())
    e2e: list[float] = []
    t0 = time.time()
    for r in range(n_requests):
        rt0 = time.time()
        h = twin.submit(jax.random.PRNGKey(100 + r), text1, 1)
        twin.flush()
        out = h.result()
        jax.block_until_ready(out)
        e2e.append(time.time() - rt0)
    base_s = time.time() - t0
    base_ips = n_requests / base_s
    base_ok = bool(np.isfinite(np.asarray(out)).all())

    from repro.serving import percentile
    return {
        "n_requests": n_requests,
        "max_resident": max_resident,
        "arrival_every_ticks": arrival_every,
        "arrivals_per_tick": arrivals_per_tick,
        "latent": [latent, latent, 4],
        "img_per_s": cont_ips,
        "img_per_s_lockstep_flush": base_ips,
        "speedup_vs_lockstep": cont_ips / max(base_ips, 1e-9),
        "meets_1p2x_throughput": bool(cont_ips >= 1.2 * base_ips),
        "latency_p50_s": snap["latency_p50_s"],
        "latency_p95_s": snap["latency_p95_s"],
        "queue_wait_p50_s": snap["queue_wait_p50_s"],
        "queue_wait_p95_s": snap["queue_wait_p95_s"],
        "latency_p50_s_lockstep": percentile(e2e, 50),
        "latency_p95_s_lockstep": percentile(e2e, 95),
        "scheduler_traces": int(engine.stats["traces"]),
        "finite": bool(cont_ok and base_ok),
    }


_LAST: dict = {}


def run():
    """Harness entry — yields ``name,us_per_call,derived`` rows."""
    res = collect()
    _LAST.clear()
    _LAST.update(res)
    us = lambda ips: 1e6 / max(ips, 1e-9)  # noqa: E731
    yield ("sampler_seed_dense", f"{us(res['seed']['img_per_s']):.1f}",
           f"fwd/step={res['seed']['expert_forwards_per_step']:.0f}")
    yield ("sampler_sparse_routed", f"{us(res['sparse']['img_per_s']):.1f}",
           f"fwd/step={res['sparse']['expert_forwards_per_step']:.0f}")
    yield ("sampler_speedup", "0", f"{res['speedup']:.2f}x")
    yield ("sampler_retraces", "0",
           str(res['sparse']['serving_retraces_3_requests']))


def submerge_section(path: str, section: str, new: dict) -> dict:
    """Merge ``new`` into an existing artifact's sub-keyed section.

    ``write_json`` merges by *top-level* section, so sections keyed by a
    sweep axis (``quantized`` by dtype, ``plan_reuse`` by refresh
    interval) would drop their other keys on a single-axis rerun; this
    reads the current artifact's sub-dict and overlays the fresh entries.
    """
    existing: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f).get(section, {}) or {}
        except (OSError, ValueError):
            existing = {}
    existing.update(new)
    return existing


def write_json(path: str, res: dict | None = None) -> str:
    """Write (merging by top-level section into any existing artifact).

    The baseline, ``sharded`` and dispatch sections are produced by
    different invocations (``--shards`` needs a forced multi-device
    host); merging keeps one ``BENCH_sampler.json`` tracking all axes.
    """
    res = res or _LAST or collect()
    merged: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    merged.update(res)
    with open(path, "w") as f:
        json.dump(merged, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", default="BENCH_sampler.json")
    ap.add_argument("--shards", type=int, default=1,
                    help="expert-parallel shards; > 1 forces that many "
                         "host devices (must be a command-line arg so it "
                         "is seen before jax initializes)")
    ap.add_argument("--dispatch", default=None,
                    choices=("gathered", "grouped", "ragged"),
                    help="benchmark a core.dispatch executor backend "
                         "against the gathered baseline (ragged: against "
                         "the grouped backend it replaces) and record it "
                         "as a JSON section")
    ap.add_argument("--param-dtype", default=None,
                    choices=("bf16", "int8", "fp8"),
                    help="benchmark a quantized/cast expert store "
                         "(core.param_store) against the dense baseline "
                         "and record it under the 'quantized' JSON "
                         "section (keyed by dtype)")
    ap.add_argument("--continuous", action="store_true",
                    help="benchmark the repro.serving continuous-batching "
                         "scheduler (staggered single-image requests, "
                         "rolling mixed-timestep batch) against the "
                         "lockstep submit+flush baseline and record it "
                         "under the 'continuous' JSON section")
    ap.add_argument("--plan-refresh", type=int, default=8,
                    help="refresh interval R for the plan-reuse arm of "
                         "the step-fusion benchmark: the fused_step and "
                         "plan_reuse sections compare unfused vs "
                         "step-fused (R=1, bit-exact) vs plan-reused "
                         "(every R-th step) samplers; plan_reuse "
                         "sub-merges by R so reruns keep other intervals")
    args = ap.parse_args()
    enable_compile_cache()
    if args.shards > 1:
        # fail fast on a bad flag BEFORE the ~1 min unsharded benchmark
        if jax.device_count() < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs that many devices, have "
                f"{jax.device_count()}"
            )
        if NUM_EXPERTS % args.shards:
            raise SystemExit(
                f"--shards {args.shards} must divide NUM_EXPERTS="
                f"{NUM_EXPERTS}"
            )
    for row in run():
        print(",".join(str(x) for x in row))
    fused_sec, reuse_sec = collect_and_merge_step_fusion(
        args.json_out, args.plan_refresh
    )
    print(f"sampler_fused_step,{1e6 / max(fused_sec['img_per_s'], 1e-9):.1f},"
          f"{fused_sec['speedup_with_plan_reuse']:.2f}x_vs_unfused "
          f"parity={fused_sec['parity_max_abs_diff_vs_unfused']:.3g}")
    rkey = f"R{max(1, args.plan_refresh)}"
    print(f"sampler_plan_reuse_{rkey},"
          f"{1e6 / max(reuse_sec[rkey]['img_per_s'], 1e-9):.1f},"
          f"refreshes/run={reuse_sec[rkey]['plan_refreshes_per_run']} "
          f"drift={reuse_sec[rkey].get('drift_max_abs_vs_R1', 0.0):.3g}")
    if args.shards > 1:
        sharded = collect_sharded(args.shards)
        _LAST["sharded"] = sharded
        yield_us = 1e6 / max(sharded["img_per_s"], 1e-9)
        print(f"sampler_sharded_{args.shards}x,{yield_us:.1f},"
              f"fwd/step/shard={sharded['per_shard_forwards_per_step']:.2f}")
    if args.dispatch == "ragged":
        sec = collect_ragged()
        _LAST["ragged"] = sec
        us = 1e6 / max(sec["img_per_s"], 1e-9)
        print(f"sampler_dispatch_ragged,{us:.1f},"
              f"{sec['speedup_vs_grouped']:.2f}x_vs_grouped "
              f"parity={sec['parity_max_abs_diff_vs_grouped']:.3g} "
              f"padding={sec['padding_overhead']:.3f}")
    elif args.dispatch:
        sec = collect_dispatch(args.dispatch)
        _LAST[args.dispatch] = sec
        us = 1e6 / max(sec["img_per_s"], 1e-9)
        print(f"sampler_dispatch_{args.dispatch},{us:.1f},"
              f"fwd/step={sec['expert_forwards_per_step_executed']:.1f}")
    if args.continuous:
        sec = collect_continuous()
        _LAST["continuous"] = sec
        us = 1e6 / max(sec["img_per_s"], 1e-9)
        print(f"sampler_continuous,{us:.1f},"
              f"{sec['speedup_vs_lockstep']:.2f}x_vs_lockstep "
              f"p50={sec['latency_p50_s']:.2f}s "
              f"p95={sec['latency_p95_s']:.2f}s")
    if args.param_dtype:
        sec = collect_quantized(args.param_dtype)
        # sub-merge by dtype so an --param-dtype bf16 rerun doesn't drop
        # the tracked int8 numbers (write_json merges whole sections).
        _LAST["quantized"] = submerge_section(
            args.json_out, "quantized", {args.param_dtype: sec}
        )
        us = 1e6 / max(sec["img_per_s"], 1e-9)
        print(f"sampler_quantized_{args.param_dtype},{us:.1f},"
              f"bytes={sec['resident_param_bytes']} "
              f"({sec['byte_reduction_vs_dense']:.2f}x smaller) "
              f"parity={sec['parity_max_abs_diff_vs_dense']:.3g}")
    path = write_json(args.json_out)
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
