#!/usr/bin/env python3
"""Chip smoke test: serve the 8-expert dit-b2 ensemble on a TPU, check it.

Drives the served path once through the entry points a user calls, at
dit-b2's published widths (12 layers, d=768, 32x32x4 latents, 77x768
text).  Eight experts (2 DDPM : 6 FM) and an 8-cluster ``router_b2``
get random weights from ``--seed``, jittered so no output layer is the
zero-init of a fresh DiT, and are written with
``training.save_checkpoint``; ``ServingEngine.from_checkpoint_dir``
assembles them, and requests sample with 50 Euler steps, CFG 7.5, top-2
routing, the ragged dispatch and the step-fused kernel.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # 4 chips: expert-sharded serving

One chip: 3 requests through ``generate()``, 6 staggered requests
through ``ContinuousScheduler``, a census of the TPU kernels in both
compiled served programs, and comparisons of generate() with the plain
float32 reference engine and of the scheduler with generate().
``--four-chips`` runs only the expert-sharded engine on an (expert=4,
data=1) mesh and the same engine unsharded on device 0, and compares
them.  Comparisons are gated at highest matmul precision (see
``BOUND``).  Any failed check exits non-zero; with no TPU the
script exits non-zero before printing any result.  Times and memory it
prints are smoke figures of one cold run (compiles included), not
benchmark numbers.  The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
CKPT_DIR = os.path.join(ROOT, ".smoke_ckpts")
NUM_EXPERTS = 8
BATCH = 2                         # images per generate() request
STAGGER = [(0, 1), (1, 2), (2, 1), (4, 1), (5, 2), (7, 1)]  # (tick, batch)
JITTER = 0.02

#: Bound on max|a − b| / RMS(b) for the final latents of two served
#: paths, both under ``default_matmul_precision("highest")``.  They
#: then run the same float32 math and differ only in summation order
#: (one ragged GEMM over all experts and a fused convert+CFG+Euler
#: kernel against per-expert einsums and separate ops), about 1e-6
#: relative per step.  1e-3 leaves three orders of magnitude for 50
#: steps of the CFG-7.5 ODE to amplify that, while a wrong block, index
#: or coefficient in a kernel moves the latents by O(1).  The bound
#: holds generate() to the reference engine, the rolling scheduler to
#: generate(), and the sharded engine to the unsharded one.
BOUND = 1e-3
#: Why default-precision differences are printed but not gated.
DEFAULT_NOTE = ("default precision rounds float32 matmul operands to "
                "bfloat16 where each program's fusion puts the cast, and "
                "50 CFG-7.5 steps of random experts behind a near-flat "
                "random router amplify the difference")


#: failed checks; a phase records its failure and the run goes on, so
#: one run shows every comparison, and the script exits non-zero at the end
FAILURES: list[str] = []


def _check(ok: bool, msg: str) -> None:
    if not ok:
        print(f"FAILED: {msg}", flush=True)
        FAILURES.append(msg)


def _mem() -> str:
    """Device 0's bytes in use and peak so far (where reported)."""
    import jax

    st = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use', '-')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use', '-')}")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.sqrt(np.mean(b * b)))


def write_checkpoints(ckpt_dir, cfg, rcfg, seed: int) -> None:
    """Seeded, jittered expert + router checkpoints (2 DDPM : 6 FM)."""
    import jax

    from repro.models import dit as D
    from repro.training import expert_metadata, save_checkpoint

    def jittered(c, key):
        k_init, k_jit = jax.random.split(key)
        params = D.init(c, k_init)
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(k_jit, len(leaves))
        return treedef.unflatten([
            x + JITTER * jax.random.normal(k, x.shape, x.dtype)
            for x, k in zip(leaves, keys)
        ])

    make = jax.jit(jittered, static_argnums=0)
    base = jax.random.PRNGKey(seed)
    for cid in range(NUM_EXPERTS):
        ddpm = cid % 4 == 0
        save_checkpoint(
            os.path.join(ckpt_dir, f"expert{cid}.npz"),
            make(cfg, jax.random.fold_in(base, cid)),
            metadata=expert_metadata(
                name=f"expert{cid}", objective="ddpm" if ddpm else "fm",
                schedule="cosine" if ddpm else "linear", cluster_id=cid,
                arch=cfg.name),
        )
    save_checkpoint(os.path.join(ckpt_dir, "router.npz"),
                    make(rcfg, jax.random.fold_in(base, 1000)),
                    metadata={"num_clusters": NUM_EXPERTS})


def build_engine(ckpt_dir, cfg, rcfg, steps: int, **kw):
    from repro.core import SamplerConfig
    from repro.launch.serve import ServingEngine

    return ServingEngine.from_checkpoint_dir(
        ckpt_dir, dit_cfg=cfg, router_cfg=rcfg,
        sampler=SamplerConfig(
            num_steps=steps, cfg_scale=7.5, strategy="topk", top_k=2,
            dispatch="auto", param_dtype="native", step_fused=True,
        ),
        **kw,
    )


def request(cfg, seed: int, i: int, batch: int):
    """Request ``i``'s key and host-side text embeddings."""
    import jax

    key = jax.random.PRNGKey(seed * 1000 + i)
    text = np.random.default_rng([seed, i]).standard_normal(
        (batch, cfg.text_len, cfg.text_dim), dtype=np.float32)
    return key, text


def dense_fallback_layers(engine, cfg) -> list[str]:
    """Expert dense layers whose row groups the ragged GEMM cannot tile
    (rows per routed pair not a multiple of 8: the per-pair conditioning
    vectors, and the 77-token text rows), so they run as plain XLA math.
    The ragged forward is traced once with the kernel entry points
    wrapped, to see which ``ragged_expert_matmul`` calls launch
    ``ragged_gemm``; each call is named by its line in the model."""
    import traceback

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    calls = []
    real_mm, real_gemm = ops.ragged_expert_matmul, ops._ragged_gemm

    def spy_mm(x, w, expert_ids, **kw):
        site = [m.group(1) for f in traceback.extract_stack()
                if (m := re.search(r"\bpd\(([^,]+),", f.line or ""))]
        calls.append([site[-1] if site else "?", x.shape, w.shape, False])
        return real_mm(x, w, expert_ids, **kw)

    def spy_gemm(*a, **kw):
        calls[-1][3] = True
        return real_gemm(*a, **kw)

    pairs, g = NUM_EXPERTS, 2
    shapes = (
        jax.ShapeDtypeStruct((pairs,) + engine.latent_shape, jnp.float32),
        jax.ShapeDtypeStruct((pairs,), jnp.float32),
        jax.ShapeDtypeStruct((pairs, g, cfg.text_len, cfg.text_dim),
                             jnp.float32),
        jax.ShapeDtypeStruct((pairs,), jnp.int32),
    )
    apply = engine.experts[0].ragged_apply_fn
    ops.ragged_expert_matmul, ops._ragged_gemm = spy_mm, spy_gemm
    try:
        jax.eval_shape(
            lambda v, x, t, text, pe: apply(v, x, t, {"text_emb": text},
                                            pe, g),
            engine.param_store.ragged_view(), *shapes)
    finally:
        ops.ragged_expert_matmul, ops._ragged_gemm = real_mm, real_gemm
    out = collections.Counter(
        f"{name} ({ws[-2]}->{ws[-1]}, {int(np.prod(xs[1:-1]))} rows/pair)"
        for name, xs, ws, kernel in calls if not kernel)
    return [f"{k} x{n}" for k, n in sorted(out.items())]


def kernel_census(compiled_text: str) -> collections.Counter:
    """TPU custom calls of a compiled program, by kernel name."""
    return collections.Counter(re.findall(
        r"%([A-Za-z_]+)[\w.-]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text))


def staggered(engine, cfg, seed: int):
    """Serve the ``STAGGER`` arrivals through a fresh
    ``ContinuousScheduler`` until idle: ``(scheduler, latents, requests,
    seconds)``."""
    import jax

    from repro.serving import ContinuousScheduler

    sched = ContinuousScheduler(engine, max_resident=8)
    handles, reqs, tick = [], [], 0
    t0 = time.perf_counter()
    for i, (arrive, bs) in enumerate(STAGGER):
        while tick < arrive:
            sched.step()
            tick += 1
        key, text = request(cfg, seed, 100 + i, bs)
        handles.append(sched.submit(key, text))
        reqs.append((key, text))
    sched.run_until_idle()
    results = [np.asarray(jax.block_until_ready(h.result()))
               for h in handles]
    return sched, results, reqs, time.perf_counter() - t0


def smoke_one_chip(cfg, rcfg, *, steps: int, seed: int,
                   census: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import sample_ensemble

    t0 = time.perf_counter()
    write_checkpoints(CKPT_DIR, cfg, rcfg, seed)
    engine = build_engine(CKPT_DIR, cfg, rcfg, steps)
    print(f"engine: {len(engine.experts)} experts "
          f"{[e.objective for e in engine.experts]}, ragged dispatch, "
          f"built in {time.perf_counter() - t0:.1f} s "
          f"(checkpoints written and loaded); {_mem()}", flush=True)
    print("dense fallback (narrow row groups, XLA math): "
          + "; ".join(dense_fallback_layers(engine, cfg)), flush=True)

    # -- generate(): 3 requests ----------------------------------------
    outs = []
    for i in range(3):
        key, text = request(cfg, seed, i, BATCH)
        t0 = time.perf_counter()
        out = jax.block_until_ready(engine.generate(key, text, BATCH))
        dt = time.perf_counter() - t0
        finite = bool(np.isfinite(np.asarray(out)).all())
        print(f"generate request {i}: {out.shape} in {dt:.2f} s "
              f"({'compile + run' if i == 0 else 'run'}) "
              f"traces={engine.stats['traces']} finite={finite}; {_mem()}",
              flush=True)
        _check(finite, f"generate request {i} is not finite")
        _check(out.shape == (BATCH,) + engine.latent_shape,
               f"generate request {i} has shape {out.shape}")
        outs.append(np.asarray(out))
    _check(engine.stats["traces"] == 1,
           f"generate retraced: traces={engine.stats['traces']}")

    # -- ContinuousScheduler: 6 staggered requests ---------------------
    sched, results, reqs, dt = staggered(engine, cfg, seed)
    finite = all(np.isfinite(r).all() for r in results)
    print(f"scheduler: {len(results)} staggered requests in "
          f"{sched.step_count} ticks, {dt:.2f} s (compile + run) "
          f"traces={engine.stats['traces']} finite={finite}; {_mem()}",
          flush=True)
    _check(finite, "a scheduled request is not finite")
    _check(engine.stats["traces"] == 2,
           f"rolling step retraced: traces={engine.stats['traces']}")
    j = next(i for i, (_, bs) in enumerate(STAGGER) if bs == BATCH)
    lone = np.asarray(engine.generate(*reqs[j], BATCH))
    print(f"scheduler request {j} vs generate() on its key at default "
          f"precision: max|diff| / RMS = {_rel(results[j], lone):.3e} "
          f"(not gated: {DEFAULT_NOTE})", flush=True)

    # -- which kernels the compiled served programs launch -------------
    if census:
        key, text = request(cfg, seed, 0, BATCH)
        tail = (cfg.text_len, cfg.text_dim)
        r = sched.max_resident
        roll = sched._get_rolling_compiled(True, tail).lower(
            jnp.zeros((r,) + engine.latent_shape), jnp.zeros((r,), jnp.int32),
            jnp.zeros((r, 2), jnp.int32), jnp.zeros((r, 2)),
            jnp.zeros((r,) + tail), *engine._sampler_args()).compile()
        gen = engine._get_compiled(BATCH, True).lower(
            key, jnp.zeros((BATCH,) + engine.latent_shape), jnp.asarray(text),
            *engine._sampler_args()).compile()
        for name, prog in (("rolling step", roll), ("generate", gen)):
            found = kernel_census(prog.as_text())
            print(f"kernels in the compiled {name}: {dict(found)}",
                  flush=True)
            for want in ("ragged_gemm", "hetero_fuse_step"):
                _check(found[want] > 0, f"{want} missing from the {name}")

    # -- comparisons, every side at highest matmul precision -----------
    key, text = request(cfg, seed, 0, BATCH)
    shape = (BATCH,) + engine.latent_shape
    noise = jax.random.normal(key, shape, jnp.float32)

    def reference(key, noise, text, params, router_fn):
        return sample_ensemble(
            key, engine.experts, params, router_fn, shape,
            cond={"text_emb": text}, null_cond={"text_emb": None},
            config=engine.sampler, engine="reference", init_noise=noise)

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(reference)(
            key, noise, jnp.asarray(text), engine.expert_params,
            engine.router_fn))
        served = np.asarray(engine.generate(key, text, BATCH))
    dt = time.perf_counter() - t0
    rel = _rel(served, ref)
    print(f"reference ({steps} steps, every expert, two-pass CFG) vs "
          f"generate(), both at highest matmul precision: max|diff| / RMS "
          f"= {rel:.3e} (bound {BOUND:g}; {dt:.1f} s, compiles included)",
          flush=True)
    ref_default = np.asarray(jax.jit(reference)(
        key, noise, jnp.asarray(text), engine.expert_params,
        engine.router_fn))
    print(f"at default precision: reference vs generate() max|diff| / RMS "
          f"= {_rel(outs[0], ref_default):.3e}; the reference's own "
          f"default vs highest = {_rel(ref_default, ref):.3e} (not gated: "
          f"{DEFAULT_NOTE})", flush=True)
    _check(np.isfinite(ref).all(), "reference latents are not finite")
    _check(rel <= BOUND, f"served vs reference {rel:.3e} > {BOUND:g}")

    with jax.default_matmul_precision("highest"):
        _, results, reqs, _ = staggered(engine, cfg, seed)
        lone = np.asarray(engine.generate(*reqs[j], BATCH))
    rel = _rel(results[j], lone)
    print(f"scheduler request {j} vs generate() on its key, both at "
          f"highest matmul precision: max|diff| / RMS = {rel:.3e} "
          f"(bound {BOUND:g})", flush=True)
    _check(rel <= BOUND, f"scheduler vs generate {rel:.3e} > {BOUND:g}")


def smoke_four_chips(cfg, rcfg, *, steps: int, seed: int) -> None:
    import jax

    n = len(jax.devices())
    if n < 4:
        _check(False, f"--four-chips needs 4 devices, JAX sees {n}")
        return
    write_checkpoints(CKPT_DIR, cfg, rcfg, seed)
    key, text = request(cfg, seed, 0, BATCH)
    outs = {}
    for label, kw in (("unsharded (device 0)", {}),
                      ("sharded (expert=4, data=1)",
                       {"n_expert_shards": 4, "n_data_shards": 1})):
        engine = build_engine(CKPT_DIR, cfg, rcfg, steps, **kw)
        mesh = dict(engine.mesh.shape) if engine.mesh is not None else None
        for prec in ("default", "highest"):
            t0 = time.perf_counter()
            with jax.default_matmul_precision(prec):
                out = np.asarray(jax.block_until_ready(
                    engine.generate(key, text, BATCH)))
            dt = time.perf_counter() - t0
            finite = bool(np.isfinite(out).all())
            print(f"{label}, {prec} precision: mesh={mesh} {out.shape} in "
                  f"{dt:.2f} s (compile + run) finite={finite}; {_mem()}",
                  flush=True)
            _check(finite, f"{label} latents are not finite")
            outs[label, prec] = out
        del engine
        gc.collect()
        jax.clear_caches()          # drop compiled programs holding it
    for prec in ("default", "highest"):
        u = outs["unsharded (device 0)", prec]
        s = outs["sharded (expert=4, data=1)", prec]
        rel = _rel(s, u)
        gated = prec == "highest"
        print(f"sharded vs unsharded at {prec} precision: max|diff| = "
              f"{float(np.max(np.abs(s - u))):.3e}, / RMS = {rel:.3e} "
              f"({f'bound {BOUND:g}' if gated else 'not gated: ' + DEFAULT_NOTE})",
              flush=True)
        if gated:
            _check(rel <= BOUND,
                   f"sharded vs unsharded {rel:.3e} > {BOUND:g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only expert-sharded serving on 4 chips and "
                         "its unsharded comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform}); "
              f"nothing to check", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.config import dit_b2, router_b2

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {dev.device_kind} x{len(jax.devices())}; smoke "
          f"figures of one cold run, not benchmark numbers", flush=True)
    cfg, rcfg = dit_b2(), router_b2(num_clusters=NUM_EXPERTS)
    t0 = time.perf_counter()
    if args.four_chips:
        smoke_four_chips(cfg, rcfg, steps=50, seed=args.seed)
    else:
        smoke_one_chip(cfg, rcfg, steps=50, seed=args.seed)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed", flush=True)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): "
          f"{stats.get('peak_bytes_in_use', 'not reported')}; total "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
