"""Serve a heterogeneous expert ensemble with batched requests.

Loads the self-describing checkpoints written by
``examples/train_decentralized.py`` (trains them in this process first if
the directory is empty) and serves batched "prompts" through the ServingEngine
with the Fig. 2 inference pipeline, reporting latency per strategy.

  PYTHONPATH=src python examples/serve_heterogeneous.py --ckpt /tmp/hddm

Quantized expert storage (``--param-dtype``, ``core.param_store``): the
stacked expert pytree loads into a typed ``ExpertParamStore`` whose
storage dtype is independent of the checkpoints.  ``int8``/``fp8``
quantize on load with symmetric per-expert-per-leaf scales, drop the
full-precision per-expert param list, and dequantize only the *routed*
slices each step through the fused ``hetero_fuse_dequant`` Pallas
kernel.  Resident expert-param bytes per stored parameter (fp32
checkpoints; exact ratios for an 8-expert dit-b2 ensemble are tracked in
the ``quantized`` section of ``BENCH_sampler.json`` via
``benchmarks/bench_sampler.py --param-dtype int8``):

  ============  =======================  ==========
  param_dtype   bytes/param              vs fp32
  ============  =======================  ==========
  native/fp32   4                        1.0x
  bf16          2                        2.0x
  int8          1 (+4·K/leaf scales)     ~3.99x
  fp8           1 (+4·K/leaf scales)     ~3.99x
  ============  =======================  ==========

int8 round-trip error is ≤ 1/254 ≈ 4e-3 of each expert-leaf's absmax
(sampler outputs stay within FID-proxy tolerance of dense — see
``tests/test_param_store.py``); fp8 (e4m3) carries ≤ 6.25e-2 element
relative error.

On an **elastic** engine (``capacity=K_cap``, see the walkthrough at the
end of this example) the table scales by the capacity, not the live
count: the store is padded to ``K_cap`` slots along the expert axis, so
resident bytes carry a ``(K_cap - K)/K`` overhead of zero-filled padded
slots (int8/fp8 pad with 0 qvals and unit scales).  Padded and evicted
slots are masked by the store's validity bit-vector — never routed,
never gathered — so the overhead is memory-only, not compute.

Step-fused sampling + plan reuse (``--plan-refresh``,
``core.sampling``): every engine here runs the step-fused hot path by
default (``SamplerConfig.step_fused`` — CFG combine + Euler update
folded into the convert-and-fuse kernel, bit-identical to the unfused
chain).  ``--plan-refresh R`` additionally recomputes the router
posterior + ``DispatchPlan`` only every R-th Euler step, carrying the
plan through the scan between refreshes.  The R-vs-parity trade-off
(vs per-step routing; drift measured on the 8-expert top-2 CFG bench
ensemble, ``plan_reuse`` section of ``BENCH_sampler.json``):

  ====  ==========================  =================================
  R     routing work per run        parity vs per-step routing
  ====  ==========================  =================================
  1     every step (S refreshes)    bit-identical (max abs diff = 0)
  2     ceil(S/2) refreshes         small drift: routed experts only
                                    change between refresh steps
  4     ceil(S/4) refreshes         ~1.09x img/s; drift ≈ 0.27 of the
                                    latent scale on the UNTRAINED
                                    bench router (trained routers
                                    whose posteriors vary slowly in t
                                    — the §3.1 premise — drift less)
  8     ceil(S/8) refreshes         ~1.16x img/s; drift ≈ 0.40 of the
                                    latent scale, same caveat
  ====  ==========================  =================================

Cross-request conditioning cache (``--cond-cache``,
``ServingEngine.cond_cache_size``): a content-hash-keyed LRU dedupes
byte-identical text embeddings across ``generate()``/``submit()``
calls — the intra-prompt-diversity workload (one prompt, many seeds)
holds ONE resident device buffer per distinct prompt.  Hit/miss
behavior is observable via ``engine.stats['cond_cache_hits']`` /
``['cond_cache_misses']`` (printed below), not inferred from timings;
0 disables the cache.
"""

import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro.core import SamplerConfig
from repro.launch.serve import ServingEngine
from repro.models.config import dit_b2, router_b2
from train_decentralized import train


def elastic_walkthrough(steps: int) -> None:
    """Fault-tolerant elastic membership, end to end.

    Builds a 6-expert ensemble with 8 capacity slots, admits a request,
    then — *mid-serving* — hot-adds a freshly published 7th expert and
    evicts expert 2.  The in-flight request still completes against the
    membership it was admitted under (bit-identical routing snapshot);
    the next request routes over the new membership; and neither
    membership change retraced the compiled sampler (K is a capacity,
    not a trace constant — membership is data).
    """
    from repro.models import dit as D
    from repro.training import expert_metadata, save_checkpoint

    cfg = dit_b2().reduced(latent_size=8)
    rcfg = router_b2(num_clusters=8).reduced(latent_size=8)
    with tempfile.TemporaryDirectory() as d:
        for cid in range(6):
            save_checkpoint(
                os.path.join(d, f"expert{cid}.npz"),
                D.init(cfg, jax.random.PRNGKey(10 + cid)),
                metadata=expert_metadata(
                    name=f"e{cid}", objective="fm" if cid % 2 else "ddpm",
                    schedule="linear" if cid % 2 else "cosine",
                    cluster_id=cid, arch=cfg.name),
            )
        save_checkpoint(os.path.join(d, "router.npz"),
                        D.init(rcfg, jax.random.PRNGKey(99)))
        engine = ServingEngine.from_checkpoint_dir(
            d, dit_cfg=cfg, router_cfg=rcfg,
            sampler=SamplerConfig(num_steps=steps, cfg_scale=1.0,
                                  strategy="topk", top_k=2),
            capacity=8,
        )
        print(f"elastic: {engine.membership_line()}")
        key = jax.random.PRNGKey(0)
        text = np.asarray(jax.random.normal(
            key, (4, cfg.text_len, cfg.text_dim)))
        h_inflight = engine.submit(key, text, 4)   # 6-expert membership
        # a 7th contributor publishes a checkpoint mid-serving ...
        joiner = os.path.join(d, "joiner.npz")
        save_checkpoint(joiner, D.init(cfg, jax.random.PRNGKey(16)),
                        metadata=expert_metadata(
                            name="e6", objective="fm", schedule="linear",
                            cluster_id=6, arch=cfg.name))
        slot = engine.add_expert(joiner)
        # ... and expert 2's node drops out
        engine.evict_expert(2)
        h_after = engine.submit(jax.random.PRNGKey(1), text, 4)
        dispatches = engine.flush()    # one dispatch per membership epoch
        for h in (h_inflight, h_after):
            assert np.isfinite(np.asarray(h.result())).all()
        print(f"elastic: hot-added slot {slot}, evicted slot 2 between "
              f"submit() and flush() — {dispatches} dispatches, "
              f"traces={engine.stats['traces']} (no retrace)")
        print(f"elastic: {engine.membership_line()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default="/tmp/hddm_ckpts")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--dispatch", default="gathered",
                    choices=("gathered", "grouped"),
                    help="expert-dispatch executor for the routed "
                         "strategies (core.dispatch): 'gathered' = "
                         "per-sample param gather + vmap, 'grouped' = "
                         "sort-based grouped segment execution (one "
                         "forward per resident expert)")
    ap.add_argument("--param-dtype", default="native",
                    choices=("native", "fp32", "bf16", "int8", "fp8"),
                    help="stacked expert-param storage "
                         "(core.param_store): int8/fp8 quantize on load "
                         "(~4x fewer resident bytes, see module "
                         "docstring) and dequantize routed slices "
                         "through the fused Pallas kernel")
    ap.add_argument("--plan-refresh", type=int, default=1,
                    help="recompute router posterior + DispatchPlan only "
                         "every R-th Euler step (R=1 per-step routing, "
                         "bit-identical; see the R-vs-parity table in "
                         "the module docstring)")
    ap.add_argument("--cond-cache", type=int, default=64,
                    help="cross-request conditioning LRU capacity "
                         "(content-hash dedupe of text embeddings; "
                         "0 disables)")
    args = ap.parse_args()

    if not os.path.exists(os.path.join(args.ckpt, "expert0.npz")):
        print(f"no checkpoints under {args.ckpt} — training a tiny "
              "ensemble first ...")
        train(args.ckpt, steps=40)

    dit_cfg = dit_b2().reduced(latent_size=8)
    rcfg = router_b2(num_clusters=4).reduced(latent_size=8)

    for strategy in ("top1", "topk", "full"):
        # routed strategies go through the selected executor backend and
        # param store; the 'full' strategy runs every expert, where only
        # the dense executor applies (and needs the full-precision
        # per-expert params), so it stays on auto/native.
        routed = strategy in ("top1", "topk")
        dispatch = args.dispatch if routed else "auto"
        param_dtype = args.param_dtype if routed else "native"
        engine = ServingEngine.from_checkpoint_dir(
            args.ckpt, dit_cfg=dit_cfg, router_cfg=rcfg,
            sampler=SamplerConfig(num_steps=args.steps, cfg_scale=1.0,
                                  strategy=strategy, top_k=2,
                                  dispatch=dispatch,
                                  param_dtype=param_dtype,
                                  plan_refresh_every=args.plan_refresh),
            cond_cache_size=args.cond_cache,
        )
        objectives = [e.objective for e in engine.experts]
        lat = []
        for r in range(args.requests):
            key = jax.random.PRNGKey(r)
            # host-side ndarray, as a remote text encoder would deliver —
            # the form the conditioning cache hashes (device-resident
            # jax.Arrays pass through unhashed)
            text = np.asarray(jax.random.normal(
                key, (args.batch, dit_cfg.text_len, dit_cfg.text_dim)
            ))
            t0 = time.time()
            out = jax.block_until_ready(
                engine.generate(key, text, args.batch)
            )
            lat.append(time.time() - t0)
            assert np.isfinite(np.asarray(out)).all()
        # first request includes compile; report steady-state
        steady = np.mean(lat[1:]) if len(lat) > 1 else lat[0]
        print(f"strategy={strategy:5s} dispatch={dispatch:8s} "
              f"params={param_dtype:6s} experts={objectives} "
              f"first={lat[0]:.2f}s steady={steady:.2f}s "
              f"({args.batch/steady:.1f} img/s) "
              f"cond_cache={engine.stats['cond_cache_hits']}h/"
              f"{engine.stats['cond_cache_misses']}m "
              f"plan_refreshes={engine.stats['plan_refreshes']}")

    elastic_walkthrough(args.steps)


if __name__ == "__main__":
    main()
