"""Compute-sparse fused sampling engine: parity with the dense reference.

Acceptance gates for the sparse serving hot path:
  (a) routed-expert-only execution == dense all-experts execution for
      top1 / topk / threshold (CPU + Pallas interpret mode);
  (b) batched CFG == two-pass CFG;
  (c) the coefficient-folded fused kernel == the per-expert
      ``unified_expert_velocities`` + ``fuse_predictions`` reference;
plus tie-determinism of top-k selection and serving-cache behaviour.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ConversionConfig,
    ExpertSpec,
    SamplerConfig,
    fuse_predictions,
    get_schedule,
    sample_ensemble,
    select_topk,
    topk_slots,
    unified_coeff_tables,
    unified_expert_velocities,
)
from repro.kernels import ops, ref as R
from repro.kernels.hetero_fuse import hetero_fuse_coeffs

KEY = jax.random.PRNGKey(0)
LATENT = (4, 4, 2)


def _shared_apply(params, x, t, *, text_emb=None, drop_mask=None, **_):
    """Toy homogeneous expert: params-dependent, text/drop_mask aware."""
    null = jnp.float32(0.07)
    if text_emb is None:
        cond_term = null
    else:
        ct = text_emb.mean(axis=(1, 2))[:, None, None, None]
        if drop_mask is not None:
            ct = jnp.where(drop_mask[:, None, None, None], null, ct)
        cond_term = ct
    return x * params["a"] + params["b"] + cond_term


def _ensemble(k=4):
    params = [
        {"a": jnp.float32(0.7 + 0.06 * i), "b": jnp.float32(0.01 * i)}
        for i in range(k)
    ]
    experts = [
        ExpertSpec(
            f"e{i}", "ddpm" if i % 2 == 0 else "fm",
            "cosine" if i % 2 == 0 else "linear", _shared_apply, i,
        )
        for i in range(k)
    ]

    def router_fn(x, t):
        logits = (
            jnp.tile(jnp.arange(float(k))[None], (x.shape[0], 1))
            + x.mean(axis=(1, 2, 3))[:, None]
        )
        return jax.nn.softmax(logits, axis=-1)

    return experts, params, router_fn


# --- (a) sparse routed == dense reference -----------------------------------


@pytest.mark.parametrize("strategy", ["top1", "topk", "threshold"])
@pytest.mark.parametrize("low_noise", [0.0, 0.7])
def test_routed_matches_reference(strategy, low_noise):
    experts, params, router_fn = _ensemble()
    cfg = SamplerConfig(
        num_steps=6, cfg_scale=1.0, strategy=strategy,
        ddpm_low_noise_only=low_noise,
    )
    ref = sample_ensemble(KEY, experts, params, router_fn, (3,) + LATENT,
                          config=cfg, engine="reference")
    routed = sample_ensemble(KEY, experts, params, router_fn, (3,) + LATENT,
                             config=cfg, engine="routed")
    np.testing.assert_allclose(np.asarray(routed), np.asarray(ref),
                               atol=1e-5)


def test_dense_fused_matches_reference_full_strategy():
    experts, params, router_fn = _ensemble()
    cfg = SamplerConfig(num_steps=6, cfg_scale=1.0, strategy="full")
    ref = sample_ensemble(KEY, experts, params, router_fn, (3,) + LATENT,
                          config=cfg, engine="reference")
    dense = sample_ensemble(KEY, experts, params, router_fn, (3,) + LATENT,
                            config=cfg, engine="dense")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ref), atol=1e-5)


def test_heterogeneous_apply_fns_threshold_uses_switch():
    """Different apply_fn objects: threshold still runs routed (lax.switch);
    per-sample strategies fall back to the dense fused path."""

    def other_apply(params, x, t, **_):
        return 0.4 * x

    experts = [
        ExpertSpec("h0", "ddpm", "cosine", _shared_apply, 0),
        ExpertSpec("h1", "fm", "linear", other_apply, 1),
    ]
    params = [{"a": jnp.float32(0.9), "b": jnp.float32(0.0)}, None]
    cfg = SamplerConfig(num_steps=5, cfg_scale=1.0, strategy="threshold")
    ref = sample_ensemble(KEY, experts, params, None, (2,) + LATENT,
                          config=cfg, engine="reference")
    routed = sample_ensemble(KEY, experts, params, None, (2,) + LATENT,
                             config=cfg, engine="routed")
    np.testing.assert_allclose(np.asarray(routed), np.asarray(ref), atol=1e-5)

    router_fn = lambda x, t: jnp.full((x.shape[0], 2), 0.5)  # noqa: E731
    cfg1 = SamplerConfig(num_steps=5, cfg_scale=1.0, strategy="top1")
    with pytest.raises(ValueError):
        sample_ensemble(KEY, experts, params, router_fn, (2,) + LATENT,
                        config=cfg1, engine="routed")
    auto = sample_ensemble(KEY, experts, params, router_fn, (2,) + LATENT,
                           config=cfg1, engine="auto")
    ref1 = sample_ensemble(KEY, experts, params, router_fn, (2,) + LATENT,
                           config=cfg1, engine="reference")
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref1), atol=1e-5)


# --- (b) batched CFG == two-pass CFG ----------------------------------------


@pytest.mark.parametrize("strategy", ["top1", "topk", "threshold", "full"])
def test_batched_cfg_matches_two_pass(strategy):
    experts, params, router_fn = _ensemble()
    text = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 6))
    cond = {"text_emb": text}
    null = {"text_emb": None}
    cfg = SamplerConfig(num_steps=6, cfg_scale=4.0, strategy=strategy)
    batched = sample_ensemble(
        KEY, experts, params, router_fn, (3,) + LATENT,
        cond=cond, null_cond=null, config=cfg,
    )
    two_pass = sample_ensemble(
        KEY, experts, params, router_fn, (3,) + LATENT,
        cond=cond, null_cond=null,
        config=dataclasses.replace(cfg, batched_cfg=False),
    )
    ref = sample_ensemble(
        KEY, experts, params, router_fn, (3,) + LATENT,
        cond=cond, null_cond=null, config=cfg, engine="reference",
    )
    np.testing.assert_allclose(np.asarray(batched), np.asarray(two_pass),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(batched), np.asarray(ref),
                               atol=1e-5)


def test_batched_cfg_with_concrete_null_embedding():
    """Null conditioning given as a concrete tensor (no drop_mask needed)."""
    experts, params, router_fn = _ensemble()
    text = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 6))
    null_text = jnp.zeros_like(text)
    cfg = SamplerConfig(num_steps=5, cfg_scale=3.0, strategy="topk")
    batched = sample_ensemble(
        KEY, experts, params, router_fn, (2,) + LATENT,
        cond={"text_emb": text}, null_cond={"text_emb": null_text},
        config=cfg,
    )
    ref = sample_ensemble(
        KEY, experts, params, router_fn, (2,) + LATENT,
        cond={"text_emb": text}, null_cond={"text_emb": null_text},
        config=cfg, engine="reference",
    )
    np.testing.assert_allclose(np.asarray(batched), np.asarray(ref),
                               atol=1e-5)


# --- (c) fused kernel == unified_expert_velocities reference ----------------


def _kernel_case(seed=0, k=3, b=4):
    kx = jax.random.PRNGKey(seed)
    preds = jax.random.normal(kx, (k, b) + LATENT)
    x_t = jax.random.normal(jax.random.fold_in(kx, 1), (b,) + LATENT)
    w = jax.nn.softmax(
        jax.random.normal(jax.random.fold_in(kx, 2), (b, k)), -1
    )
    objectives = ["ddpm" if i % 2 == 0 else "fm" for i in range(k)]
    schedules = [
        get_schedule("cosine" if o == "ddpm" else "linear")
        for o in objectives
    ]
    return preds, x_t, w, objectives, schedules


@pytest.mark.parametrize("t_val", [0.15, 0.5, 0.92])
def test_fused_coeff_step_matches_unified_reference(t_val):
    preds, x_t, w, objectives, schedules = _kernel_case()
    k, b = preds.shape[0], preds.shape[1]
    conv = ConversionConfig()
    tb = jnp.full((b,), t_val)
    tab = unified_coeff_tables(objectives, schedules, jnp.array([t_val]),
                               conv)[0]                     # (5, K)
    coef = jnp.broadcast_to(tab[:, :, None], (5, k, b))
    fused = ops.fused_velocity(preds, x_t, w, coef,
                               clamp=conv.clamp, alpha_min=conv.alpha_min)

    # reference: per-expert unify (via apply_fns returning the fixed preds)
    experts = [
        ExpertSpec(f"e{i}", o, s.name,
                   (lambda i: lambda p, x, t, **c: preds[i])(i))
        for i, (o, s) in enumerate(zip(objectives, schedules))
    ]
    v_ref = unified_expert_velocities(
        experts, [None] * k, x_t, tb, {}, conv_cfg=conv,
    )
    ref = fuse_predictions(v_ref, w)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("k,b,t,bt", [(2, 3, 128, 32), (8, 2, 256, 128),
                                      (4, 1, 64, 64)])
def test_hetero_fuse_coeffs_kernel_interpret_mode(k, b, t, bt):
    """Pallas interpret-mode kernel == oracle for the folded-coeff op."""
    kx = jax.random.PRNGKey(1)
    preds = jax.random.normal(kx, (k, b, t))
    xt = jax.random.normal(jax.random.fold_in(kx, 1), (b, t))
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(kx, 2), (b, k)),
                       -1)
    alpha = jax.random.uniform(jax.random.fold_in(kx, 3), (k, b),
                               minval=0.05, maxval=1.0)
    coef = jnp.stack([
        alpha,
        jnp.sqrt(1.0 - alpha ** 2),
        -jnp.ones((k, b)),
        jnp.ones((k, b)),
        jnp.full((k, b), 0.93),
    ])
    out = hetero_fuse_coeffs(preds, xt, w, coef, block_t=bt, interpret=True)
    ref = R.ref_hetero_fuse_coeffs(preds, xt, w, coef)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_sparse_engine_parity_under_forced_pallas_interpret(monkeypatch):
    """End-to-end routed sampling through the interpret-mode Pallas kernel."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    experts, params, router_fn = _ensemble()
    cfg = SamplerConfig(num_steps=4, cfg_scale=1.0, strategy="topk")
    routed = sample_ensemble(KEY, experts, params, router_fn, (2,) + LATENT,
                             config=cfg, engine="routed")
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    ref = sample_ensemble(KEY, experts, params, router_fn, (2,) + LATENT,
                          config=cfg, engine="reference")
    np.testing.assert_allclose(np.asarray(routed), np.asarray(ref),
                               atol=1e-5)


# --- satellites: tie-break determinism, slots, serving cache ----------------


def test_select_topk_tie_break_exactly_k():
    probs = jnp.array([
        [0.25, 0.25, 0.25, 0.25],      # full tie
        [0.4, 0.3, 0.3, 0.0],          # tie at the k-th value
        [0.1, 0.2, 0.3, 0.4],
    ])
    w, mask = select_topk(probs, 2)
    counts = np.asarray(mask).sum(-1)
    np.testing.assert_array_equal(counts, [2, 2, 2])
    # deterministic: ties resolve toward the lowest expert index
    np.testing.assert_array_equal(np.asarray(mask[0]),
                                  [True, True, False, False])
    np.testing.assert_array_equal(np.asarray(mask[1]),
                                  [True, True, False, False])
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w[1]), [0.4 / 0.7, 0.3 / 0.7, 0, 0],
                               rtol=1e-5)


def test_topk_slots_match_weights():
    probs = jnp.array([[0.5, 0.1, 0.25, 0.15]])
    w, _ = select_topk(probs, 2)
    idx, sw = topk_slots(w, 2)
    np.testing.assert_array_equal(np.asarray(idx[0]), [0, 2])
    np.testing.assert_allclose(np.asarray(sw[0]), [0.5 / 0.75, 0.25 / 0.75],
                               rtol=1e-5)


def _dit_checkpoints(tmp_path):
    from repro.models import dit as D
    from repro.models.config import dit_b2, router_b2
    from repro.training import expert_metadata, save_checkpoint
    import os

    cfg = dit_b2().reduced(latent_size=8)
    for cid, (obj, sch) in enumerate([("ddpm", "cosine"), ("fm", "linear")]):
        save_checkpoint(
            os.path.join(tmp_path, f"expert{cid}.npz"),
            D.init(cfg, jax.random.PRNGKey(cid)),
            metadata=expert_metadata(name=f"e{cid}", objective=obj,
                                     schedule=sch, cluster_id=cid,
                                     arch=cfg.name, step=0),
        )
    rcfg = router_b2(num_clusters=2).reduced(latent_size=8)
    save_checkpoint(os.path.join(tmp_path, "router.npz"),
                    D.init(rcfg, jax.random.PRNGKey(9)),
                    metadata={"num_clusters": 2})
    return cfg, rcfg


def test_serving_engine_is_retrace_free(tmp_path):
    from repro.launch.serve import ServingEngine

    cfg, rcfg = _dit_checkpoints(tmp_path)
    engine = ServingEngine.from_checkpoint_dir(
        str(tmp_path), dit_cfg=cfg, router_cfg=rcfg,
        sampler=SamplerConfig(num_steps=3, cfg_scale=2.0, strategy="topk"),
    )
    assert engine.homogeneous and engine.stacked_params is not None
    text = jax.random.normal(KEY, (2, cfg.text_len, cfg.text_dim))
    for r in range(3):
        out = engine.generate(jax.random.PRNGKey(r), text, 2)
        assert bool(jnp.isfinite(out).all())
    assert engine.stats["traces"] == 1          # same shape -> no retrace
    engine.generate(KEY, jax.random.normal(KEY, (4, cfg.text_len,
                                                 cfg.text_dim)), 4)
    assert engine.stats["traces"] == 2          # new batch size -> one more


def _constant_bytes(stablehlo: str) -> int:
    return sum(len(h) // 2 for h in re.findall(r'dense<"0x([0-9A-F]+)">',
                                                stablehlo))


@pytest.mark.parametrize("capacity", [None, 3])
def test_compiled_samplers_take_weights_as_arguments(tmp_path, capacity):
    """Expert and router weights reach both served programs (lockstep
    ``generate`` and the rolling scheduler step) as jit arguments.  Closed
    over, XLA embeds them as program constants — at dit-b2 widths
    gigabytes inside the HLO, which the chip's compiler cannot take."""
    from repro.launch.serve import ServingEngine
    from repro.serving import ContinuousScheduler

    cfg, rcfg = _dit_checkpoints(tmp_path)
    engine = ServingEngine.from_checkpoint_dir(
        str(tmp_path), dit_cfg=cfg, router_cfg=rcfg,
        sampler=SamplerConfig(num_steps=3, cfg_scale=2.0, strategy="topk"),
        capacity=capacity,
    )
    weight_bytes = engine.param_store.nbytes() + sum(
        x.nbytes for x in jax.tree.leaves(engine.router_fn))
    assert weight_bytes > 2**20
    text = jax.random.normal(KEY, (2, cfg.text_len, cfg.text_dim))
    noise = jnp.zeros((2,) + engine.latent_shape)
    gen = engine._get_compiled(2, True).lower(
        KEY, noise, text, *engine._sampler_args())
    sched = ContinuousScheduler(engine, max_resident=2)
    roll = sched._get_rolling_compiled(True, text.shape[1:]).lower(
        noise, jnp.zeros((2,), jnp.int32), jnp.zeros((2, 2), jnp.int32),
        jnp.zeros((2, 2)), text, *engine._sampler_args())
    for lowered in (gen, roll):
        assert _constant_bytes(lowered.as_text()) < 2**16


def test_stack_and_gather_expert_params():
    from repro.models import dit as D

    params = [{"w": jnp.full((3, 2), float(i)), "b": {"v": jnp.ones((4,)) * i}}
              for i in range(3)]
    stacked = D.stack_expert_params(params)
    assert stacked["w"].shape == (3, 3, 2)
    per_sample = D.gather_expert_params(stacked, jnp.array([2, 0]))
    np.testing.assert_allclose(np.asarray(per_sample["w"][0]), 2.0)
    np.testing.assert_allclose(np.asarray(per_sample["b"]["v"][1]), 0.0)
    one = D.gather_expert_params(stacked, jnp.asarray(1))
    np.testing.assert_allclose(np.asarray(one["w"]), 1.0)


@pytest.mark.parametrize("reduced", [True, False])
def test_serve_cli_configs_published_or_reduced(reduced):
    """``--no-reduced`` serves dit-b2 at its published widths (latents
    from the config, 32), ``--reduced`` the smoke preset."""
    from repro.launch.serve import serve_configs
    from repro.models.config import dit_b2

    dit_cfg, rcfg = serve_configs(reduced, 8)
    if reduced:
        assert (dit_cfg.latent_size, dit_cfg.num_layers) == (8, 2)
    else:
        assert dit_cfg == dit_b2() and dit_cfg.latent_size == 32
    assert rcfg.latent_size == dit_cfg.latent_size
