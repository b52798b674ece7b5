"""A configuration of the benchmark's shape at a size the CPU runs in
seconds: 8 experts (2 DDPM : 6 FM), 2 layers, d=32, 8x8x4 latents."""

import copy

TINY = {
    "name": "tiny",
    "num_layers": 2, "d_model": 32, "num_heads": 2, "patch_size": 2,
    "latent_size": 8, "latent_channels": 4, "mlp_ratio": 4.0,
    "text_dim": 16, "text_len": 8, "num_timesteps": 1000,
    "router": {"num_layers": 2, "d_model": 32, "num_heads": 2,
               "patch_size": 2, "latent_size": 8, "latent_channels": 4,
               "mlp_ratio": 4.0, "num_timesteps": 1000, "num_classes": 8},
    "experts": [{"objective": "ddpm" if i % 4 == 0 else "fm",
                 "schedule": "cosine" if i % 4 == 0 else "linear"}
                for i in range(8)],
    "sampler": {"num_steps": 6, "cfg_scale": 7.5, "strategy": "topk",
                "top_k": 2, "dispatch": "auto", "param_dtype": "native",
                "step_fused": True, "plan_refresh_every": 1},
    "conversion": {"alpha_min": 0.01, "clamp": 20.0,
                   "velocity_scaling": "piecewise"},
    "matmul_precision": "highest",
}


def tiny(shards: int = 1):
    """The tiny configuration; with ``shards`` > 1 its experts placed on
    that many devices (``expert_shards``)."""
    cfg = copy.deepcopy(TINY)
    if shards > 1:
        cfg.update(name=f"tiny-ep{shards}", expert_shards=shards)
    return cfg


#: the tiny size reads about 1e-6 on sound runs and 6e-5 for the control
TINY["check"] = {"latent_gap_limit": 2e-5}

MIXES = {
    "tiny-open": {"kind": "open", "arrivals": "stratified_poisson",
                  "rate_rps": 200.0, "images_per_request": 1,
                  "prompts": "unique",
                  "max_resident": 4, "steps_per_tick": 1,
                  "max_queue_depth": 256, "warmup_s": 0.3, "drain_s": 20.0,
                  "trace_s": 0.2, "check_requests": 4},
    "tiny-closed": {"kind": "closed", "clients": 1, "batch": 3,
                    "prompts": "unique", "warmup_calls": 1, "trace_s": 0.2,
                    "check_calls": 1},
}


#: the tiny closed mix on four devices, two experts to a device
EP4 = "tiny-closed-ep4"


def make_root(tmp_path, bench_dir):
    """A checkout at ``tmp_path`` holding a copy of ``bench_dir`` and a
    ``BENCHMARK.json`` whose cells run the tiny configuration: one cell
    per mix on one chip, and ``EP4`` on four."""
    import json
    import pathlib
    import shutil

    root = pathlib.Path(tmp_path) / "checkout"
    shutil.copytree(bench_dir, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cfg in (tiny(), tiny(4)):
        (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
    for name, mix in MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench = {
        "run_seconds": 1,
        "configs": [{"name": name, "file": f"bench/configs/{name}.json"}
                    for name in ("tiny", "tiny-ep4")],
        "workloads": [
            {"name": name, "config": "tiny", "traffic": name, "chips": 1}
            for name in MIXES] + [
            {"name": EP4, "config": "tiny-ep4", "traffic": "tiny-closed",
             "chips": 4}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "img_per_s", "unit": "img/s",
             "workloads": ["tiny-closed", EP4]},
            {"name": "latency_p50_s", "unit": "s",
             "workloads": ["tiny-open"]},
        ],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
