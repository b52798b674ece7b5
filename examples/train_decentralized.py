"""End-to-end decentralized training driver (paper Fig. 6).

Full pipeline: corpus → stub-DINOv2 features → hierarchical k-means →
K isolated heterogeneous experts (2 DDPM + (K-2) FM, the paper's
2DDPM:6FM recipe scaled down) → independent router → self-describing
checkpoints → ensemble sampling report.

Default: tiny CPU-friendly config.  ``--full`` trains DiT-B/2 (121M
params/expert, the paper's small scale) for ``--steps`` steps — sized for a
real accelerator; a few hundred steps of the 121M model also run on CPU in
tens of minutes.

  PYTHONPATH=src python examples/train_decentralized.py --out /tmp/hddm
  PYTHONPATH=src python examples/train_decentralized.py --full --steps 300
"""

import argparse
import os
import time

import jax
import numpy as np

from repro.core import ExpertSpec, SamplerConfig, sample_ensemble
from repro.data import SyntheticSpec, fit_clusters, sample_fid
from repro.data.pipeline import ExpertDataStream, RouterDataStream
from repro.models import dit as D
from repro.models.config import dit_b2, router_b2
from repro.training import (
    AdamWConfig,
    ExpertTrainer,
    RouterTrainer,
    expert_metadata,
    save_checkpoint,
)


def train(out: str, *, experts: int = 4, ddpm_experts: int = 2,
          steps: int = 60, batch: int = 32, full: bool = False,
          seed: int = 0) -> None:
    """Train ``experts`` isolated experts and a router into ``out``.

    Runs in the calling process, so a server that needs checkpoints
    first (``serve_heterogeneous.py``) trains on the same device it then
    serves from — a second process could not take the accelerator.
    """
    K = experts
    latent = 32 if full else 8
    spec = SyntheticSpec(num_categories=K, latent_size=latent,
                         separation=3.0)
    print(f"[1/4] clustering corpus into {K} partitions ...")
    cm, assign = fit_clusters(spec, corpus_size=1024, num_clusters=K,
                              num_fine=128, seed=seed)
    print(f"      cluster sizes: {np.bincount(assign, minlength=K)}")

    cfg = dit_b2() if full else dit_b2().reduced(latent_size=latent)
    apply_fn = D.make_expert_apply(cfg)
    n_params = None
    os.makedirs(out, exist_ok=True)

    print(f"[2/4] training {K} isolated experts "
          f"({ddpm_experts} DDPM : {K - ddpm_experts} FM) ...")
    for cid in range(K):
        obj = "ddpm" if cid < ddpm_experts else "fm"
        sch = "cosine" if obj == "ddpm" else "linear"
        trainer = ExpertTrainer(
            apply_fn=apply_fn, objective=obj, schedule_name=sch,
            opt=AdamWConfig(learning_rate=1e-4 if full else 3e-4,
                            warmup_steps=min(100, steps // 10)),
            ema_decay=0.9999 if full else 0.8,
        )
        params = D.init(cfg, jax.random.PRNGKey(seed + cid))
        if n_params is None:
            n_params = D.param_count(params)
            print(f"      expert size: {n_params/1e6:.1f}M params")
        state = trainer.init_state(params)
        stream = ExpertDataStream(spec, cm, cluster_id=cid,
                                  batch_size=batch, seed=cid)
        t0 = time.time()
        for i in range(steps):
            state, m = trainer.train_step(
                state, jax.random.fold_in(jax.random.PRNGKey(99), i),
                stream.next_batch(i),
            )
        print(f"      expert {cid} [{obj}] loss {m['loss']:.4f} "
              f"({time.time()-t0:.1f}s)")
        save_checkpoint(
            os.path.join(out, f"expert{cid}.npz"), state.ema,
            metadata=expert_metadata(
                name=f"expert{cid}", objective=obj, schedule=sch,
                cluster_id=cid, arch=cfg.name, step=steps,
            ),
        )

    print("[3/4] training router (independent, all clusters) ...")
    rcfg = router_b2(num_clusters=K)
    rcfg = rcfg if full else rcfg.reduced(latent_size=latent)
    rtrainer = RouterTrainer(
        apply_fn=lambda p, x, t: D.apply(rcfg, p, x, t), num_clusters=K,
    )
    rstate = rtrainer.init_state(D.init(rcfg, jax.random.PRNGKey(777)))
    rstream = RouterDataStream(spec, cm, batch_size=batch)
    for i in range(steps):
        rstate, rm = rtrainer.train_step(
            rstate, jax.random.fold_in(jax.random.PRNGKey(55), i),
            rstream.next_batch(i),
        )
    print(f"      router acc {rm['acc']:.2f}")
    save_checkpoint(os.path.join(out, "router.npz"), rstate.params,
                    metadata={"num_clusters": K})

    print("[4/4] sampling with heterogeneous fusion ...")
    from repro.training import load_checkpoint
    experts, eparams = [], []
    for cid in range(K):
        p, meta = load_checkpoint(os.path.join(out,
                                               f"expert{cid}.npz"))
        experts.append(ExpertSpec(meta["name"], meta["objective"],
                                  meta["schedule"], apply_fn,
                                  meta["cluster_id"]))
        eparams.append(p)
    samples = sample_ensemble(
        jax.random.PRNGKey(1), experts, eparams,
        D.make_router_fn(rcfg, rstate.params),
        (64, latent, latent, 4),
        config=SamplerConfig(num_steps=12, cfg_scale=1.0,
                             strategy="topk", top_k=2),
    )
    fid = sample_fid(spec, np.asarray(samples))
    print(f"done: {samples.shape} samples, FID-proxy {fid:.3f}, "
          f"checkpoints in {out}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--ddpm-experts", type=int, default=2,
                    help="paper's hetero recipe: 2 DDPM : rest FM")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--out", default="/tmp/hddm_ckpts")
    ap.add_argument("--full", action="store_true",
                    help="full DiT-B/2 (121M/expert) instead of reduced")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    train(args.out, experts=args.experts, ddpm_experts=args.ddpm_experts,
          steps=args.steps, batch=args.batch, full=args.full,
          seed=args.seed)


if __name__ == "__main__":
    main()
