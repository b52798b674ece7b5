"""The system under test: the served ensemble, assembled from a
configuration file and weights made on the device from the seed.

This is the only module of the benchmark that imports the program
(``repro``); the reference, the traffic and the reductions do not.
"""

from __future__ import annotations

import os
import sys

from bench import weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))


def model_sizes(cfg: dict) -> dict:
    from bench.reference import MODEL_KEYS

    return {k: cfg[k] for k in MODEL_KEYS}


def dit_configs(cfg: dict):
    from repro.models.config import DiTConfig

    m, r = model_sizes(cfg), cfg["router"]
    expert = DiTConfig(name=cfg["name"], use_text=True, **m)
    router = DiTConfig(name=cfg["name"] + "-router", use_text=False, **r)
    return expert, router


def expert_devices(cfg: dict) -> list | None:
    """The device of each expert shard of ``cfg``, in the order of the
    program's own expert mesh (``jax.make_mesh`` may order devices by
    topology); None for a configuration on one chip."""
    from repro.launch.mesh import make_expert_mesh

    shards = cfg.get("expert_shards", 1)
    if shards == 1:
        return None
    return list(make_expert_mesh(shards, 1).devices[:, 0])


def build_engine(cfg: dict, seed: int):
    """``ServingEngine`` over ``cfg``'s experts and router, with weights
    drawn on the device from ``seed``.  A configuration with
    ``expert_shards`` N > 1 gets an (expert N, data 1) mesh, and each
    expert is drawn on the device of its shard."""
    from repro.core import ExpertSpec, SamplerConfig
    from repro.launch.serve import ServingEngine
    from repro.models import dit as D

    ecfg, rcfg = dit_configs(cfg)
    apply_fn = D.make_expert_apply(ecfg)
    ragged_fn = D.make_ragged_expert_apply(ecfg)
    specs = [
        ExpertSpec(name=f"expert{i}", objective=x["objective"],
                   schedule=x["schedule"], apply_fn=apply_fn, cluster_id=i,
                   ragged_apply_fn=ragged_fn)
        for i, x in enumerate(cfg["experts"])
    ]
    devices = expert_devices(cfg)
    params = weights.expert_list(seed, model_sizes(cfg), len(specs), devices)
    layout = {} if devices is None else {
        "n_expert_shards": len(devices), "n_data_shards": 1}
    router_fn = D.make_router_fn(rcfg, weights.router(seed, cfg["router"]))
    engine = ServingEngine(
        experts=specs, expert_params=params, router_fn=router_fn,
        latent_shape=(ecfg.latent_size, ecfg.latent_size,
                      ecfg.latent_channels),
        sampler=SamplerConfig(**cfg["sampler"]), **layout,
    )
    del params                  # the engine holds the weights from here on
    return engine


def scheduler(engine, traffic: dict):
    from repro.serving import ContinuousScheduler

    return ContinuousScheduler(
        engine, max_resident=traffic["max_resident"],
        steps_per_tick=traffic["steps_per_tick"],
        max_queue_depth=traffic["max_queue_depth"])


def backpressure_error():
    from repro.serving.scheduler import QueueBackpressure

    return QueueBackpressure


def enable_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, so a second run of a cell compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
