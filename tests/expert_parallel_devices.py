"""The expert-parallel engine on four devices, at a tiny DiT size: run as
a script in a process whose CPU backend was forced to four devices before
JAX started, since a process keeps the device count it first saw.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python tests/expert_parallel_devices.py

Prints one JSON line: for each check its readings, or the traceback of
what it raised.  ``test_expert_parallel.py`` runs it and judges them.
"""

from __future__ import annotations

import dataclasses
import json
import os
from contextlib import nullcontext
import re
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import ExpertSpec, SamplerConfig
from repro.core import dispatch
from repro.core.param_store import as_store
from repro.launch.serve import ServingEngine
from repro.launch.sharding import mesh_scope
from repro.models import dit as D
from repro.models.config import dit_b2
from repro.serving import ContinuousScheduler

K, SHARDS, BATCH, STEPS = 8, 4, 4, 3
KEY = jax.random.PRNGKey(0)
CFG = dit_b2().reduced(d_model=64, num_heads=2, text_dim=16, text_len=4,
                       latent_size=8)
LATENT = (CFG.latent_size, CFG.latent_size, CFG.latent_channels)
#: cross-chip collectives of XLA's HLO text
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def _jitter(tree, key):
    """Every leaf moved off its zero init, so each expert's output
    depends on its own weights."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _params():
    return [_jitter(D.init(CFG, jax.random.PRNGKey(40 + i)),
                    jax.random.PRNGKey(50 + i)) for i in range(K)]


#: a fixed random projection of the latent: samples and steps route to
#: different experts, on every shard
_ROUTE = jax.random.normal(jax.random.PRNGKey(7), (int(np.prod(LATENT)), K))


def router(x, t):
    logits = 3.0 * x.reshape(x.shape[0], -1) @ _ROUTE / np.sqrt(_ROUTE.shape[0])
    return jax.nn.softmax(logits + t[:, None], axis=-1)


def _experts():
    apply_fn = D.make_expert_apply(CFG)
    ragged = D.make_ragged_expert_apply(CFG)
    return [ExpertSpec(f"e{i}", "ddpm" if i % 4 == 0 else "fm",
                       "cosine" if i % 4 == 0 else "linear", apply_fn, i,
                       ragged_apply_fn=ragged) for i in range(K)]


def fixed_router(routes):
    """A router that sends sample ``b`` to experts ``routes[b]`` (top-2)
    at every step, whatever the latent."""
    table = np.full((len(routes), K), -20.0, np.float32)
    for b, (first, second) in enumerate(routes):
        table[b, first], table[b, second] = 2.0, 1.0

    def route(x, t):
        return jax.nn.softmax(jnp.asarray(table)[:x.shape[0]], axis=-1)

    return route


#: every pair on shard 0 (experts 0 and 1)
ONE_SHARD = [(0, 1)] * BATCH
#: pairs split 4 : 1 : 1 : 2 over the shards (shard = expert // 2)
UNEVEN = [(0, 1), (0, 2), (4, 6), (1, 7)]


def _engine(params, sampler=None, route=router, **kw):
    sampler = sampler or SamplerConfig(num_steps=STEPS, cfg_scale=7.5,
                                       strategy="topk", top_k=2)
    return ServingEngine(experts=_experts(), expert_params=params,
                         router_fn=route, latent_shape=LATENT,
                         sampler=sampler, **kw)


def _on_shard_devices(params):
    """Each expert on the device of its shard, as a deployment draws or
    loads it: shard ``s`` is row ``s`` of the engine's mesh."""
    from repro.launch.mesh import make_expert_mesh

    rows = make_expert_mesh(SHARDS, 1).devices[:, 0]
    per = K // SHARDS
    return [jax.device_put(p, rows[e // per]) for e, p in enumerate(params)]


def _text(n=BATCH, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (n, CFG.text_len, CFG.text_dim))


def _gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.sqrt(np.mean(b ** 2)))


class per_pair_rows:
    """Inside the block, the small-row fallback of
    ``ops.ragged_expert_matmul`` (groups of at most 4 rows: the timestep,
    AdaLN and final-modulation vectors of each pair) contracts each pair
    against its own expert's leaf instead of one GEMM against every
    expert's leaf.  The CPU's GEMM blocks its contraction by the width of
    that GEMM, which is ``K/N`` experts on a shard and ``K`` on one
    device, so the two engines round differently there and nowhere
    else; per pair, both run the same products."""

    def __enter__(self):
        from repro.kernels import ops

        self.real = real = ops.ragged_expert_matmul

        def matmul(x, w, expert_ids, *, bias=None, w_scale=None,
                   live=None):
            if x.ndim > 2 or w_scale is not None:
                return real(x, w, expert_ids, bias=bias, w_scale=w_scale,
                            live=live)
            y = jnp.einsum("pd,pdf->pf", x, w[expert_ids])
            if live is not None:
                y = jnp.where(live[:, None], y, 0.0)
            return y if bias is None else y + bias[expert_ids]

        ops.ragged_expert_matmul = matmul
        return self

    def __exit__(self, *exc):
        from repro.kernels import ops

        ops.ragged_expert_matmul = self.real


def generate() -> dict:
    params = _params()
    out = {}
    for rows in ("all_experts", "per_pair"):
        with per_pair_rows() if rows == "per_pair" else nullcontext():
            one = np.asarray(_engine(params).generate(KEY, _text(), BATCH))
            ep = _engine(_on_shard_devices(params), n_expert_shards=SHARDS,
                         n_data_shards=1)
            got = np.asarray(ep.generate(KEY, _text(), BATCH))
        out[rows] = _gap(got, one)
    q = dataclasses.replace(ep.sampler, param_dtype="int8")
    q_one = np.asarray(_engine(params, q).generate(KEY, _text(), BATCH))
    q_ep = np.asarray(_engine(_on_shard_devices(params), q,
                              n_expert_shards=SHARDS, n_data_shards=1)
                      .generate(KEY, _text(), BATCH))
    return {"gap": out, "finite": bool(np.isfinite(got).all()),
            "int8_gap": _gap(q_ep, q_one), "mesh": dict(ep.mesh.shape)}


def one_shard_routing() -> dict:
    """Every pair routed to one shard: the other shards' GEMMs skip every
    row tile, and the latents still match the one-device engine's."""
    params = _params()
    route = fixed_router(ONE_SHARD)
    out = {}
    for rows in ("all_experts", "per_pair"):
        with per_pair_rows() if rows == "per_pair" else nullcontext():
            one = np.asarray(_engine(params, route=route).generate(
                KEY, _text(), BATCH))
            got = np.asarray(_engine(
                _on_shard_devices(params), route=route,
                n_expert_shards=SHARDS, n_data_shards=1).generate(
                    KEY, _text(), BATCH))
        out[rows] = _gap(got, one)
    return {"gap": out}


def skipping_is_bitwise() -> dict:
    """The expert-parallel engine through the Pallas kernels (interpret
    mode): skipping the pairs a shard does not own against computing
    every pair and masking them, as before skipping."""
    real = dispatch.shard_share

    def compute_every_pair(apply_fn, *args):
        return real(lambda *a, live=None: apply_fn(*a), *args)

    params = _on_shard_devices(_params())
    outs = {}
    os.environ["REPRO_FORCE_PALLAS"] = "1"
    try:
        for name, share in (("skip", real), ("compute", compute_every_pair)):
            dispatch.shard_share = share
            outs[name] = np.asarray(_engine(
                params, n_expert_shards=SHARDS, n_data_shards=1).generate(
                    KEY, _text(), BATCH))
    finally:
        dispatch.shard_share = real
        del os.environ["REPRO_FORCE_PALLAS"]
    return {"equal": bool(np.array_equal(outs["skip"], outs["compute"])),
            "gap": _gap(outs["skip"], outs["compute"])}


def shard_rows() -> dict:
    """The row counter (``track_padding``) on the expert mesh under a
    fixed uneven routing, with what the plan makes each shard own."""
    g, per = 2, K // SHARDS
    owned = [0] * SHARDS
    for pair in UNEVEN:
        for e in pair:
            owned[e // per] += 1
    out = {"plan_rows_per_step": [n * g for n in owned],
           "plan_busiest": max(owned) * g}
    for name, kw in (("one_device", {}),
                     ("sharded", {"n_expert_shards": SHARDS,
                                  "n_data_shards": 1})):
        params = _params() if not kw else _on_shard_devices(_params())
        eng = _engine(params, route=fixed_router(UNEVEN), track_padding=True,
                      **kw)
        eng.generate(KEY, _text(), BATCH)
        stats = eng.padding_stats()
        stats["shard_rows_per_step"] = [
            stats["shard_rows_per_step"][s]
            for s in sorted(stats["shard_rows_per_step"])]
        out[name] = stats
    return out


def rolling_tick() -> dict:
    """Two requests through the rolling scheduler, each tick one step of
    ``sample_ensemble_step``, on both engines; as ``generate``, with the
    program's small-row products and with them per pair."""
    out = {}
    for rows in ("all_experts", "per_pair"):
        with per_pair_rows() if rows == "per_pair" else nullcontext():
            out[rows] = _rolling_tick()
    return {"gap": out}


def _rolling_tick() -> float:
    params = _params()
    outs = []
    for eng in (_engine(params),
                _engine(_on_shard_devices(params), n_expert_shards=SHARDS,
                        n_data_shards=1)):
        sched = ContinuousScheduler(eng, max_resident=4)
        handles = [sched.submit(jax.random.PRNGKey(10 + i), _text(1, 20 + i))
                   for i in range(2)]
        for _ in range(4 * STEPS):
            if all(h.done for h in handles):
                break
            sched.step()
        outs.append(np.concatenate([np.asarray(h.result()) for h in handles]))
    return _gap(outs[1], outs[0])


def placement() -> dict:
    """Where the store's leaves live, and every stack set-up made."""
    stacks = []
    real = D.stack_expert_params

    def spy(params_list):
        out = real(params_list)
        stacks.append({
            "experts": len(params_list),
            "devices": sorted({d.id for leaf in jax.tree.leaves(out)
                               for d in leaf.devices()})})
        return out

    D.stack_expert_params = spy
    try:
        out = {}
        for name, kw in (("dense", {}), ("int8", {"param_dtype": "int8"}),
                         ("elastic", {"capacity": K + SHARDS})):
            stacks.clear()
            sampler = SamplerConfig(num_steps=STEPS, cfg_scale=7.5,
                                    strategy="topk", top_k=2,
                                    **{k: v for k, v in kw.items()
                                       if k == "param_dtype"})
            eng = _engine(_on_shard_devices(_params()), sampler,
                          n_expert_shards=SHARDS, n_data_shards=1,
                          capacity=kw.get("capacity"))
            rows = [d.id for d in eng.mesh.devices[:, 0]]
            slots = eng.param_store.num_experts
            per = slots // SHARDS
            homes = []
            for leaf in jax.tree.leaves(eng.param_store):
                homes.append(sorted(
                    [rows.index(sh.device.id), sh.index[0].start or 0,
                     sh.index[0].stop if sh.index[0].stop is not None
                     else leaf.shape[0]]
                    for sh in leaf.addressable_shards))
            out[name] = {"per": per, "slots": slots, "homes": homes,
                         "stacks": list(stacks),
                         "specs": sorted({str(leaf.sharding.spec) for leaf
                                          in jax.tree.leaves(eng.param_store)
                                          })}
        return out
    finally:
        D.stack_expert_params = real


def shares(pallas: bool = False) -> dict:
    """One step's ragged predictions: the shards' masked shares, summed on
    the host, against the one-device ragged apply of all pairs.  With
    ``pallas`` both run the Pallas kernels in interpret mode, so the
    shards' launches skip their dead row tiles, and the small-row
    products per pair, so that both run the same products."""
    from repro.launch.mesh import make_expert_mesh

    if pallas:
        os.environ["REPRO_FORCE_PALLAS"] = "1"
        try:
            with per_pair_rows():
                return shares()
        finally:
            del os.environ["REPRO_FORCE_PALLAS"]

    params = _params()
    stacked = as_store(D.stack_expert_params(params))
    mesh = make_expert_mesh(SHARDS, 1)
    pairs, g = 2 * BATCH, 2
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.standard_normal((pairs,) + LATENT), jnp.float32)
    ts = jnp.asarray(rng.uniform(0.05, 0.95, pairs), jnp.float32)
    cs = {"text_emb": jnp.asarray(rng.standard_normal(
        (pairs, g, CFG.text_len, CFG.text_dim)), jnp.float32)}
    pe = jnp.asarray(rng.integers(0, K, pairs), jnp.int32)
    apply_fn = D.make_ragged_expert_apply(CFG)
    whole = np.asarray(jax.jit(
        lambda v, *a: apply_fn(v, *a, g))(stacked.ragged_view(), xs, ts,
                                          cs, pe))

    def per_shard(store, xs, ts, cs, pe):
        return dispatch.shard_share(apply_fn, store, xs, ts, cs, pe, g)[None]

    placed = jax.device_put(stacked, jax.sharding.NamedSharding(
        mesh, P("expert")))
    with mesh_scope(mesh):
        parts = np.asarray(jax.jit(jax.shard_map(
            per_shard, mesh=mesh, in_specs=(P("expert"),) + (P(),) * 4,
            out_specs=P("expert"), check_vma=False))(placed, xs, ts, cs, pe))
    nonzero = (np.abs(parts).reshape(SHARDS, pairs, -1).max(-1) > 0)
    return {"gap": _gap(parts.sum(0), whole),
            "owners": nonzero.sum(0).tolist(),
            "owner_is_shard": [bool(nonzero[int(e) // (K // SHARDS), p])
                               for p, e in enumerate(np.asarray(pe))]}


def compiled_step() -> dict:
    """Collectives in the compiled ``generate`` program of the
    expert-parallel engine (the sampler's steps are one ``while`` loop)."""
    ep = _engine(_on_shard_devices(_params()), n_expert_shards=SHARDS,
                 n_data_shards=1)
    fn = ep._get_compiled(BATCH, True)
    noise = jnp.zeros((BATCH,) + LATENT, jnp.float32)
    text = ep._cached_cond(_text())
    hlo = fn.lower(KEY, noise, text, *ep._sampler_args()).compile().as_text()
    ops = []
    for line in hlo.splitlines():
        m = re.search(r"=\s*\S+\s+(" + "|".join(COLLECTIVES)
                      + r")(-start)?\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            shape = line.split("=", 1)[1].split(m.group(1))[0].strip()
            ops.append({"op": m.group(1), "shape": shape,
                        "op_name": name.group(1) if name else ""})
    weights = {str(tuple(np.shape(leaf))[1:])
               for leaf in jax.tree.leaves(ep.param_store)}
    return {"collectives": ops, "weight_shapes": sorted(weights),
            "whiles": len(re.findall(r"\swhile\(", hlo))}


def place_span() -> dict:
    """Host spans of a profiler trace taken around the engine's set-up."""
    import glob
    import tempfile

    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        params = _on_shard_devices(_params())
        jax.profiler.start_trace(d)
        try:
            _engine(params, n_expert_shards=SHARDS, n_data_shards=1)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        data = ProfileData.from_file(path)
        names = {e.name for plane in data.planes for line in plane.lines
                 for e in line.events if e.name.startswith("engine.")}
    return {"spans": sorted(names)}


def main() -> int:
    checks = {"devices": lambda: {"count": jax.device_count()},
              "generate": generate, "rolling_tick": rolling_tick,
              "one_shard_routing": one_shard_routing,
              "shard_rows": shard_rows,
              "skipping_is_bitwise": skipping_is_bitwise,
              "placement": placement, "place_span": place_span,
              "shares": shares,
              "shares_pallas": lambda: shares(pallas=True),
              "compiled_step": compiled_step}
    out = {}
    for name, fn in checks.items():
        try:
            out[name] = fn()
        except Exception:                   # reported, judged by the test
            out[name] = {"error": traceback.format_exc()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
