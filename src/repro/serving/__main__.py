"""Deterministic continuous-batching self-check (CI smoke).

Builds a toy homogeneous ensemble (analytic expert closures — no model
weights, so the smoke runs in seconds on the CPU container), drives
staggered requests through :class:`repro.serving.ContinuousScheduler`,
and asserts each resolved request is **bitwise identical** to a
dedicated ``generate`` call on a twin engine, with exactly one trace of
the rolling step program.  Exits non-zero on any mismatch.

Run as ``PYTHONPATH=src python -m repro.serving``.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ExpertSpec, SamplerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import ServingEngine
from repro.serving import ContinuousScheduler

LATENT = (4, 4, 2)
TEXT_TAIL = (3, 5)
K = 8


def _toy_apply(params, x, t, text_emb=None, drop_mask=None):
    """Analytic expert: batch-leading, row-independent, cond-sensitive."""
    tt = t.reshape((-1,) + (1,) * (x.ndim - 1))
    out = x * params["a"] + params["b"] * tt
    if text_emb is not None:
        c = jnp.tanh(text_emb.mean(axis=tuple(range(1, text_emb.ndim))))
        if drop_mask is not None:
            c = jnp.where(drop_mask, 0.07, c)
        out = out + 0.1 * c.reshape(tt.shape)
    return out


def _toy_router(x, t):
    m = x.mean(axis=tuple(range(1, x.ndim)))
    logits = (jnp.arange(K, dtype=jnp.float32)[None] * 0.3
              + m[:, None] * 3.0 + t[:, None])
    return jax.nn.softmax(logits, axis=-1)


def _make_engine() -> ServingEngine:
    experts = [
        ExpertSpec(
            name=f"toy{i}",
            objective="ddpm" if i % 2 == 0 else "fm",
            schedule="cosine" if i % 2 == 0 else "linear",
            apply_fn=_toy_apply,
            cluster_id=i,
        )
        for i in range(K)
    ]
    params = [
        {"a": jnp.float32(0.8 + 0.03 * i), "b": jnp.float32(0.05 * i - 0.1)}
        for i in range(K)
    ]
    return ServingEngine(
        experts=experts, expert_params=params, router_fn=_toy_router,
        latent_shape=LATENT,
        sampler=SamplerConfig(num_steps=6, cfg_scale=3.0,
                              strategy="topk", top_k=2),
    )


def main() -> int:
    enable_compile_cache()
    engine = _make_engine()
    sched = ContinuousScheduler(engine, max_resident=4)

    # Staggered arrivals: requests join mid-flight, so the rolling batch
    # genuinely mixes timesteps before the parity check.
    specs = [(0, 1), (1, 2), (2, 1), (4, 1), (5, 2), (7, 1)]  # (tick, bs)
    handles, texts, keys = [], [], []
    tick = 0
    for arrive, bs in specs:
        while tick < arrive:
            sched.step()
            tick += 1
        key = jax.random.PRNGKey(100 + len(handles))
        text = jax.random.normal(
            jax.random.fold_in(key, 1), (bs,) + TEXT_TAIL, jnp.float32
        )
        handles.append(sched.submit(key, text))
        keys.append(key)
        texts.append(text)
    sched.run_until_idle()

    twin = _make_engine()
    ok = True
    for i, (h, key, text) in enumerate(zip(handles, keys, texts)):
        want = np.asarray(twin.generate(key, text, text.shape[0]))
        got = np.asarray(h.result())
        if not np.array_equal(got, want):
            ok = False
            print(f"request {i}: rolling output != generate "
                  f"(max |diff| = {np.abs(got - want).max():.3e})")
    traces = engine.stats["traces"]
    if traces != 1:
        ok = False
        print(f"expected exactly 1 rolling-step trace, got {traces}")
    for k in ("latency_p50_s", "latency_p95_s", "queue_wait_p50_steps"):
        if k not in engine.stats:
            ok = False
            print(f"missing stats key {k!r}")
    print(sched.line())
    if not ok:
        print("continuous-batching smoke FAILED")
        return 1
    print(f"continuous-batching smoke OK: {len(handles)} staggered "
          f"requests bitwise == sequential generate(), traces={traces}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
