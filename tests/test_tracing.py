"""Tracing inside the serving path: the device scopes the compiled step
names, the scheduler's ticks-in-flight counter, and stats published only
on ticks that resolve a request.

(a) the compiled ``generate`` program and the rolling tick of a DiT
    ensemble carry the ``router``, ``attention``, ``layer_weights`` and
    ``fused_step`` scopes in their instructions' ``op_name`` metadata;
(b) the in-flight counter rises while the device reports no tick done,
    falls back to 0 once every output is ready, keeps its history ring
    bounded, and shows in ``line()``;
(c) ``engine.stats`` takes a fresh snapshot only on resolving ticks.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.array import ArrayImpl

from repro.core import ExpertSpec, SamplerConfig
from repro.launch.serve import ServingEngine
from repro.launch.sharded_parity import toy_ensemble
from repro.models import dit as D
from repro.models.config import DiTConfig
from repro.serving import ContinuousScheduler, scheduler as sched_mod

SCOPES = ("router", "attention", "layer_weights", "fused_step")
TEXT = (6, 16)

_SIZES = dict(num_layers=2, d_model=32, num_heads=2, patch_size=2,
              latent_size=8, latent_channels=4, mlp_ratio=4.0,
              num_timesteps=1000)


@pytest.fixture(scope="module")
def dit_engine():
    ecfg = DiTConfig(name="tiny", use_text=True, text_dim=TEXT[1],
                     text_len=TEXT[0], **_SIZES)
    rcfg = DiTConfig(name="tiny-router", use_text=False, num_classes=4,
                     **_SIZES)
    apply_fn = D.make_expert_apply(ecfg)
    ragged_fn = D.make_ragged_expert_apply(ecfg)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    specs = [ExpertSpec(name=f"e{i}", objective=("ddpm", "fm")[i % 2],
                        schedule=("cosine", "linear")[i % 2],
                        apply_fn=apply_fn, cluster_id=i,
                        ragged_apply_fn=ragged_fn) for i in range(4)]
    return ServingEngine(
        experts=specs, expert_params=[D.init(ecfg, k) for k in keys[:4]],
        router_fn=D.make_router_fn(rcfg, D.init(rcfg, keys[4])),
        latent_shape=(8, 8, 4),
        sampler=SamplerConfig(num_steps=3, cfg_scale=7.5, strategy="topk",
                              top_k=2, step_fused=True))


def _scopes(hlo: str) -> set[str]:
    names = re.findall(r'op_name="([^"]*)"', hlo)
    return {part for n in names for part in n.split("/") if part in SCOPES}


def test_generate_program_carries_the_device_scopes(dit_engine):
    eng = dit_engine
    fn = eng._get_compiled(2, True)
    args = (jax.random.PRNGKey(0), jnp.zeros((2, 8, 8, 4)),
            jnp.zeros((2,) + TEXT), *eng._sampler_args(None))
    assert _scopes(fn.lower(*args).compile().as_text()) == set(SCOPES)


def test_rolling_tick_carries_the_device_scopes(dit_engine):
    eng = dit_engine
    sched = ContinuousScheduler(eng, max_resident=2)
    fn = sched._get_rolling_compiled(True, TEXT)
    args = (jnp.zeros((2, 8, 8, 4)), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 2), jnp.int32), jnp.zeros((2, 2)),
            jnp.zeros((2,) + TEXT), *eng._sampler_args(None))
    assert _scopes(fn.lower(*args).compile().as_text()) == set(SCOPES)


LATENT = (4, 4, 2)


def _toy_scheduler(**kw):
    experts, params, router_fn, _ = toy_ensemble(4)
    eng = ServingEngine(experts=experts, expert_params=params,
                        router_fn=router_fn, latent_shape=LATENT,
                        sampler=SamplerConfig(num_steps=6, cfg_scale=3.0,
                                              strategy="topk", top_k=2))
    return eng, ContinuousScheduler(eng, max_resident=2, **kw)


def _submit(sched, i):
    key = jax.random.PRNGKey(50 + i)
    return sched.submit(key, jax.random.normal(key, (1, 5, 6)))


def test_in_flight_counter_rises_while_no_tick_is_done(monkeypatch):
    monkeypatch.setattr(sched_mod, "IN_FLIGHT_RING", 3)
    eng, sched = _toy_scheduler()
    assert "inflight=0" in sched.line()
    req = _submit(sched, 0)
    monkeypatch.setattr(ArrayImpl, "is_ready", lambda self: False)
    for _ in range(5):
        sched.step()
    # before tick n, the n - 1 earlier ticks all read not done
    assert eng.stats["ticks_in_flight"] == 4
    assert list(sched.in_flight) == [2, 3, 4]          # a ring of 3
    assert "inflight=4" in sched.line()
    monkeypatch.undo()
    for bucket in sched._buckets.values():
        jax.block_until_ready(bucket.t_idx)     # every tick so far is done
    sched.step()
    assert eng.stats["ticks_in_flight"] == 0
    assert list(sched.in_flight) == [3, 4, 0]
    assert req.done and "inflight=0" in sched.line()


def test_in_flight_counter_never_blocks(monkeypatch):
    """The counter asks ``is_ready`` and nothing that waits."""
    eng, sched = _toy_scheduler()
    _submit(sched, 1)

    def no_wait(*a, **k):
        raise AssertionError("the counter waited on the device")

    monkeypatch.setattr(ArrayImpl, "block_until_ready", no_wait)
    monkeypatch.setattr(ArrayImpl, "is_ready", lambda self: False)
    sched._count_in_flight()
    sched.step()
    sched.step()
    assert eng.stats["ticks_in_flight"] == 1


def test_stats_snapshot_only_on_resolving_ticks():
    eng, sched = _toy_scheduler()
    calls = []
    snap = sched.metrics.snapshot
    sched.metrics.snapshot = lambda: calls.append(sched.step_count) or snap()
    for i in range(3):
        _submit(sched, 10 + i)
    resolving = []
    while sched.queue_depth or sched.num_resident:
        if sched.step():
            resolving.append(sched.step_count)
    assert resolving and calls == resolving
    assert eng.stats["completed_requests"] == 3.0
    assert eng.stats["scheduler_steps"] == sched.step_count
