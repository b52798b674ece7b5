"""Sharded multi-device serving + expert-identity correctness.

Covers this rung of the perf ladder:
  (a) multi-shard parity — the expert-parallel / data-parallel engine
      matches the single-device routed engine (same seed) on a forced
      multi-device CPU host (subprocess: the in-process suite must keep
      the single real CPU device, and jax locks the device count at
      first init);
  (b) checkpoint-ordering regression — 12 experts load in *numeric*
      cluster order, never lexicographic glob order, and duplicate /
      missing cluster ids raise;
  (c) config-identity — sampler/conversion defaults are per-instance
      (default_factory) and frozen, so jit-cache keys stay hashable and
      engines can't poison each other;
  (d) cross-request batching — coalesced submit()/flush() slices match
      per-request generate() outputs.
"""

import os
import subprocess
import sys

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SamplerConfig
from repro.launch.mesh import make_expert_mesh
from repro.launch.serve import ServingEngine
from repro.launch.sharding import expert_param_specs, serve_batch_spec
from repro.models import dit as D
from repro.models.config import dit_b2
from repro.training import expert_metadata, save_checkpoint

KEY = jax.random.PRNGKey(0)
LATENT = (4, 4, 2)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


_UNSET = object()


def _toy_engine(k=4, sampler=_UNSET, **kwargs):
    # importing sharded_parity in-process is safe: its XLA_FLAGS override
    # is guarded on jax not being initialized yet.
    from repro.launch.sharded_parity import toy_ensemble

    experts, params, router_fn, _latent = toy_ensemble(k)
    if sampler is _UNSET:
        sampler = SamplerConfig(num_steps=4, cfg_scale=3.0,
                                strategy="topk", top_k=2)
    if sampler is not None:          # None -> exercise the dataclass default
        kwargs["sampler"] = sampler
    return ServingEngine(
        experts=experts, expert_params=params, router_fn=router_fn,
        latent_shape=LATENT, **kwargs,
    )


def _save_fake_experts(tmp_path, cluster_ids, *, with_meta_cid=True):
    """Tiny stackable fake checkpoints named expert<N>.npz."""
    for name_idx, cid in enumerate(cluster_ids):
        md = expert_metadata(
            name=f"fake{cid}", objective="fm", schedule="linear",
            cluster_id=cid, arch="toy", step=0,
        )
        if not with_meta_cid:
            del md["cluster_id"]
        save_checkpoint(
            os.path.join(tmp_path, f"expert{cid}.npz"),
            {"a": jnp.full((2, 2), float(cid)), "b": jnp.zeros((3,))},
            metadata=md,
        )


# --- (a) multi-shard parity (subprocess: forced multi-device CPU) -----------


def _run_parity(extra_args=(), devices=2):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_PARITY_DEVICES"] = str(devices)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.sharded_parity", *extra_args],
        env=env, capture_output=True, text=True, timeout=600,
    )


def test_multi_shard_parity_toy_two_devices():
    proc = _run_parity()
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"parity": "ok"' in proc.stdout
    assert '"grouped_parity": "ok"' in proc.stdout
    # quantized store: scales shard with their leaves on "expert" + parity
    assert '"quantized_parity": "ok"' in proc.stdout
    # step fusion bit-parity + plan-reuse (R=2) parity across mesh layouts
    assert '"step_fusion_parity": "ok"' in proc.stdout
    # masked elastic membership: sharded validity mask + eviction parity
    assert '"elastic_masked_parity": "ok"' in proc.stdout
    assert '"devices": 2' in proc.stdout


_MESH_KERNELS = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops
from repro.launch.mesh import make_expert_mesh
from repro.launch.sharding import mesh_scope

mesh = make_expert_mesh(2, 1)
k = jax.random.split(jax.random.PRNGKey(0), 6)
x = jax.random.normal(k[0], (4, 16, 32))
w = jax.random.normal(k[1], (4, 32, 40))
e = jnp.array([0, 3, 1, 3], jnp.int32)
preds = jax.random.normal(k[2], (2, 4, 4, 4, 2))
xt = jax.random.normal(k[3], (2, 4, 4, 2))
wts = jax.nn.softmax(jax.random.normal(k[4], (4, 2)), axis=-1)
coef = jax.random.uniform(k[5], (5, 2, 4)) + 0.5

def run(x, w, e, preds, xt, wts, coef):
    return (ops.ragged_expert_matmul(x, w, e),
            ops.fused_step(preds, xt, wts, coef, 0.1, g=2, cfg_scale=7.5))

def own_experts(x, w, e):
    # inside the shard_map: w is this device's 2 experts, e global ids
    lo = jax.lax.axis_index("expert") * w.shape[0]
    mine = (e >= lo) & (e < lo + w.shape[0])
    y = ops.ragged_expert_matmul(x, w, jnp.where(mine, e - lo, 0))
    return jax.lax.psum(jnp.where(mine[:, None, None], y, 0.0), "expert")

def on_mesh(x, w, e, preds, xt, wts, coef):
    with mesh_scope(mesh):
        y = jax.shard_map(own_experts, mesh=mesh,
                          in_specs=(P(), P("expert"), P()), out_specs=P(),
                          check_vma=False)(x, w, e)
        return y, ops.fused_step(preds, xt, wts, coef, 0.1, g=2,
                                 cfg_scale=7.5)

args = (x, w, e, preds, xt, wts, coef)
plain = jax.jit(run)(*args)
w_sh = jax.device_put(w, NamedSharding(mesh, P("expert")))
meshed = jax.jit(on_mesh)(x, w_sh, *args[2:])
for a, b in zip(plain, meshed):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
hlo = jax.jit(on_mesh).lower(x, w_sh, *args[2:]).compile().as_text()
assert "all-gather" not in hlo, "a weight leaf was gathered"
assert hlo.count(" all-reduce(") == 1, "one exchange of the products"
print("mesh kernels ok")
"""


def test_pallas_launches_run_replicated_on_a_mesh():
    """The TPU compiler cannot partition a Pallas launch.  On a mesh a
    launch outside any ``shard_map`` (here the fused step) runs
    replicated under ``shard_map``; inside the expert-parallel
    ``shard_map`` every axis is manual and a launch (here the ragged
    GEMM over each device's own two experts) is a plain call on local
    operands, so no weight leaf is gathered and one ``psum`` joins the
    products.  Interpret mode on a forced 2-device CPU host; both
    bitwise equal to the meshless calls."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["REPRO_FORCE_PALLAS"] = "1"
    proc = subprocess.run([sys.executable, "-c", _MESH_KERNELS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "mesh kernels ok" in proc.stdout


@pytest.mark.slow
def test_multi_shard_parity_dit_two_devices():
    proc = _run_parity(["--dit", "--steps", "3"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"parity": "ok"' in proc.stdout


def test_degenerate_mesh_in_process_bit_identical():
    """On the single real CPU device a 1×1 mesh must change nothing."""
    text = jax.random.normal(KEY, (4, 5, 6))
    base = _toy_engine()
    ref = np.asarray(base.generate(KEY, text, 4))
    degen = _toy_engine(n_expert_shards=1, n_data_shards=1)
    assert degen.mesh is not None
    out = np.asarray(degen.generate(KEY, text, 4))
    np.testing.assert_array_equal(out, ref)


def test_non_divisible_expert_shards_raise():
    """Silent expert-axis replication (zero memory savings behind a
    'sharded' mesh) must be a loud misconfiguration instead."""
    # guard fires before mesh construction: 4 experts on 3 expert shards
    with pytest.raises(ValueError, match="does not divide"):
        _toy_engine(k=4, n_expert_shards=3)
    # divisible but over-subscribed: mesh construction rejects it next
    with pytest.raises(ValueError, match="devices"):
        _toy_engine(k=3, n_expert_shards=3)


def test_expert_param_specs_leading_axis():
    mesh = make_expert_mesh(1, 1)
    stacked = D.stack_expert_params([
        {"w": jnp.ones((3, 2)), "b": {"v": jnp.ones((4,))}}
        for _ in range(2)
    ])
    axes = D.stacked_param_logical_axes(stacked)
    assert axes["w"] == ("expert", None, None)
    specs = expert_param_specs(stacked, mesh, logical_axes=axes)
    assert specs["w"][0] == "expert"
    assert specs["b"]["v"][0] == "expert"
    # non-divisible leading dim falls back to replication
    odd = {"w": jnp.ones((3, 2))}
    mesh2 = make_expert_mesh(1, 1)
    spec = expert_param_specs(odd, mesh2)["w"]
    assert spec[0] in ("expert", None)   # 3 % 1 == 0 -> kept
    assert serve_batch_spec(mesh2, (4, 8, 8, 2))[0] == "data"
    assert serve_batch_spec(mesh2, (0,)) == jax.sharding.PartitionSpec(None)


# --- (b) checkpoint ordering ------------------------------------------------


def test_twelve_expert_checkpoints_load_in_cluster_order(tmp_path):
    """Regression: lexicographic glob gives expert10 < expert2; the engine
    must order numerically so index == cluster_id for >= 10 experts."""
    _save_fake_experts(tmp_path, list(range(12)))
    cfg = dit_b2().reduced(latent_size=8)
    engine = ServingEngine.from_checkpoint_dir(str(tmp_path), dit_cfg=cfg)
    assert [e.cluster_id for e in engine.experts] == list(range(12))
    assert [e.name for e in engine.experts] == [f"fake{i}" for i in range(12)]
    for i, p in enumerate(engine.expert_params):
        np.testing.assert_allclose(np.asarray(p["a"]), float(i))
    # the stacked dispatch substrate inherits the corrected order
    assert engine.stacked_params is not None
    np.testing.assert_allclose(
        np.asarray(engine.stacked_params["a"][:, 0, 0]),
        np.arange(12.0),
    )


def test_checkpoint_order_from_filename_when_no_metadata(tmp_path):
    _save_fake_experts(tmp_path, list(range(11)), with_meta_cid=False)
    cfg = dit_b2().reduced(latent_size=8)
    engine = ServingEngine.from_checkpoint_dir(str(tmp_path), dit_cfg=cfg)
    assert [e.cluster_id for e in engine.experts] == list(range(11))
    for i, p in enumerate(engine.expert_params):
        np.testing.assert_allclose(np.asarray(p["a"]), float(i))


def test_duplicate_cluster_ids_raise(tmp_path):
    _save_fake_experts(tmp_path, [0, 1])
    # second file, same metadata cluster_id as expert1
    md = expert_metadata(name="dup", objective="fm", schedule="linear",
                         cluster_id=1, arch="toy", step=0)
    save_checkpoint(os.path.join(tmp_path, "expert2.npz"),
                    {"a": jnp.zeros((2, 2)), "b": jnp.zeros((3,))},
                    metadata=md)
    cfg = dit_b2().reduced(latent_size=8)
    with pytest.raises(ValueError, match="duplicate cluster_id 1"):
        ServingEngine.from_checkpoint_dir(str(tmp_path), dit_cfg=cfg)


def test_missing_cluster_ids_raise(tmp_path):
    _save_fake_experts(tmp_path, [0, 2, 3])
    cfg = dit_b2().reduced(latent_size=8)
    with pytest.raises(ValueError, match="missing \\[1\\]"):
        ServingEngine.from_checkpoint_dir(str(tmp_path), dit_cfg=cfg)


# --- (c) config identity ----------------------------------------------------


def test_sampler_defaults_are_per_instance_and_frozen():
    a, b = SamplerConfig(), SamplerConfig()
    assert a.conversion is not b.conversion      # default_factory, not shared
    assert a == b and hash(a) == hash(b)         # still value-equal/hashable
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.cfg_scale = 1.0
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.conversion.alpha_min = 0.5


def test_engine_sampler_defaults_are_per_instance():
    e1, e2 = _toy_engine(sampler=None), _toy_engine(sampler=None)
    # dataclasses.field(default_factory=...) on ServingEngine.sampler
    assert e1.sampler is not e2.sampler
    assert e1.sampler == e2.sampler


# --- (d) cross-request batching queue ---------------------------------------


def test_flush_coalesces_compatible_requests_and_slices():
    engine = _toy_engine()
    text = jax.random.normal(jax.random.PRNGKey(3), (6, 5, 6))
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    h1 = engine.submit(keys[0], text[:2], 2)
    h2 = engine.submit(keys[1], text[2:3], 1)
    h3 = engine.submit(keys[2], text[3:6], 3)
    # unflushed handles must fail loudly with an actionable message,
    # never hand back a None/placeholder result
    with pytest.raises(RuntimeError, match=r"not yet flushed.*flush\(\)"):
        h1.result()
    merged = engine.flush()
    assert merged == 1                           # one compatible group
    assert engine.stats["merged_batches"] == 1
    assert engine.stats["batched_requests"] == 3
    # parity: each slice == what generate() would have produced per request
    ref_engine = _toy_engine()
    np.testing.assert_allclose(
        np.asarray(h1.result()),
        np.asarray(ref_engine.generate(keys[0], text[:2], 2)), atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(h2.result()),
        np.asarray(ref_engine.generate(keys[1], text[2:3], 1)), atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(h3.result()),
        np.asarray(ref_engine.generate(keys[2], text[3:6], 3)), atol=1e-5,
    )


def test_flush_groups_incompatible_signatures_separately():
    engine = _toy_engine()
    text = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 6))
    engine.submit(jax.random.PRNGKey(0), text, 2)
    engine.submit(jax.random.PRNGKey(1), None, 2)      # unconditional
    merged = engine.flush()
    assert merged == 2
    assert engine.stats["merged_batches"] == 2
    assert engine.flush() == 0                         # queue drained


def test_flush_failure_requeues_pending_requests(monkeypatch):
    """A failed group dispatch must not strand other queued handles —
    and must not raise out of flush(): the failing group re-queues (up
    to the requeue cap) while the caller keeps control of the loop."""
    engine = _toy_engine()
    text = jax.random.normal(KEY, (2, 5, 6))
    h1 = engine.submit(jax.random.PRNGKey(0), text, 2)
    h2 = engine.submit(jax.random.PRNGKey(1), None, 2)
    orig = engine._get_compiled

    def boom(*a, **k):
        raise RuntimeError("compile blew up")

    monkeypatch.setattr(engine, "_get_compiled", boom)
    assert engine.flush() == 0                   # no group dispatched...
    assert len(engine._queue) == 2               # ...both re-queued
    assert engine.stats["request_requeues"] == 2
    monkeypatch.setattr(engine, "_get_compiled", orig)
    assert engine.flush() == 2                   # retry succeeds
    assert h1.result().shape == (2,) + LATENT
    assert h2.result().shape == (2,) + LATENT
    assert h1.state == "DONE" and h2.state == "DONE"


def test_flush_partial_failure_isolated_to_poison_group(monkeypatch):
    """One poison group must not take down the healthy group's dispatch."""
    engine = _toy_engine()
    text = jax.random.normal(KEY, (2, 5, 6))
    h_text = engine.submit(jax.random.PRNGKey(0), text, 2)      # group A
    h_uncond = engine.submit(jax.random.PRNGKey(1), None, 2)    # group B
    orig = engine._dispatch_group

    def poison(has_text, text_tail, reqs):
        if has_text:
            raise RuntimeError("poison group")
        return orig(has_text, text_tail, reqs)

    monkeypatch.setattr(engine, "_dispatch_group", poison)
    assert engine.flush() == 1                   # healthy group dispatched
    assert h_uncond.result().shape == (2,) + LATENT
    assert len(engine._queue) == 1               # poison group re-queued once
    # cap exhausted on the second failure: FAILED, exception on the handle
    assert engine.flush() == 0
    assert h_text.state == "FAILED"
    assert engine.stats["failed_requests"] == 1
    assert len(engine._queue) == 0               # not re-poisoning every flush
    with pytest.raises(RuntimeError, match="poison group"):
        h_text.result()


def test_flush_mismatched_batch_raises():
    engine = _toy_engine()
    text = jax.random.normal(KEY, (2, 5, 6))
    with pytest.raises(ValueError, match="batch"):
        engine.submit(KEY, text, 3)
