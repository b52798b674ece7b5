"""GSPMD partition rules for the model zoo + DiT experts.

Strategy (DESIGN.md §5): 2D "FSDP × TP" —

* column-parallel weights (attention q/k/v, FFN up/gate, SSM in_proj,
  MoE up/gate): last dim on "model", second-to-last on "data";
* row-parallel weights (attention o, FFN down, SSM out_proj, MoE down):
  last dim on "data", second-to-last on "model";
* embeddings: feature dim on "model";
* norms / scalars / small tables: replicated;
* batch dims of inputs/caches on ("pod","data") (pod folds into data);
* batch-1 long-context decode: KV-cache *sequence* axis shards on "data"
  (sequence-parallel cache attention), SSM-state heads on "model".

GSPMD tolerates non-divisible dims (pads); every d_model/d_ff/kv_dim in
the assigned configs is divisible by 16 regardless.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes
from repro.models.config import DiTConfig, LMConfig

# Leaf-name → (trailing-dims spec builder). `dp` = data axes tuple.
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "w1", "in_proj", "vision_proj",
        "text_proj", "mlp1", "mlp2", "out", "mod", "cls_head"}
_ROW = {"wo", "w_down", "w2", "out_proj"}
# The unembed projection only TP-shards its vocab dim: FSDP-sharding its
# d_model (contraction) dim on "data" collides with batch-on-"data" in the
# CE backward and GSPMD re-replicates the global batch (measured 12×
# memory-traffic blowup on internlm2 train_4k — see EXPERIMENTS.md §Perf).
_COL_TP_ONLY = {"unembed"}


def _path_names(path) -> list[str]:
    names = []
    for e in path:
        if hasattr(e, "key"):
            names.append(str(e.key))
        elif hasattr(e, "idx"):
            names.append(str(e.idx))
    return names


def _rule_for(names: list[str], ndim: int, dp) -> P:
    """Trailing-dim partition rule; leading (stacked-layer) dims -> None."""
    dpa = dp if len(dp) > 1 else dp[0]
    owner = None
    for n in reversed(names):
        if (n in _COL or n in _ROW or n in _COL_TP_ONLY
                or n in ("emb", "router", "conv_w", "table", "block_embed")):
            owner = n
            break
    if ndim <= 1:
        return P()
    if owner == "emb":
        # embedding tables (V, D) / pos tables (S, D): shard feature dim.
        return _pad(P("model"), ndim, trailing=1)
    if owner == "table":
        return P(*([None] * ndim))
    if owner == "router":                    # MoE gate: replicate (small)
        return P(*([None] * ndim))
    if owner == "conv_w":                    # (K, C): shard channels
        return _pad(P("model"), ndim, trailing=1)
    if owner == "block_embed":               # (L, 6, d)
        return P(*([None] * ndim))
    if owner in _COL_TP_ONLY:
        if ndim >= 2:
            return _pad(P(None, "model"), ndim, trailing=2)
        return P("model")
    if owner in _COL:
        if ndim >= 2:
            return _pad(P(dpa, "model"), ndim, trailing=2)
        return P("model")
    if owner in _ROW:
        if ndim >= 2:
            return _pad(P("model", dpa), ndim, trailing=2)
        return P(dpa)
    # biases / norms / A_log / dt_bias / D / unknowns: replicate.
    return P(*([None] * ndim))


def _pad(spec: P, ndim: int, trailing: int) -> P:
    return P(*([None] * (ndim - trailing) + list(spec)))


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def sanitize_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis assignments whose mesh size doesn't divide the dim.

    jit in_shardings require exact divisibility (unlike internal GSPMD
    propagation); any non-divisible assignment falls back to replication
    of that dim.
    """
    out = []
    for i, axis in enumerate(spec):
        if axis is None or i >= len(shape):
            out.append(None if i >= len(shape) else axis)
            continue
        if shape[i] % _axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    # pad/trim to ndim
    out = out[: len(shape)] + [None] * (len(shape) - len(out))
    return P(*out)


def param_specs(params_shape: Any, mesh: Mesh, *, fsdp: bool = False) -> Any:
    """PartitionSpec pytree matching an eval_shape'd param tree.

    ``fsdp=False`` (default): TP-only weight sharding + pure data
    parallelism — fits every arch below ~8B.  ``fsdp=True``: weight
    matrices additionally shard over the data axis (storage); models must
    run under the launch.fsdp gather-before-use policy.
    """
    dp = data_axes(mesh)

    def leaf(path, x):
        names = _path_names(path)
        # bias vectors follow their weight's last-dim sharding.
        if names[-1] == "b":
            w_spec = _rule_for(names[:-1] + ["w"], 2, dp)
            last = w_spec[-1] if len(w_spec) else None
            spec = P(last)
        elif names[-1] == "w":
            spec = _rule_for(names[:-1], x.ndim, dp)
        else:
            spec = _rule_for(names, x.ndim, dp)
        if not fsdp:
            dset = set(dp)
            spec = P(*[
                None if (a in dset or isinstance(a, tuple)) else a
                for a in spec
            ])
        return sanitize_spec(spec, x.shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf, params_shape)


def param_shardings(params_shape: Any, mesh: Mesh, *, fsdp: bool = False) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs(params_shape, mesh, fsdp=fsdp),
    )


# ---------------------------------------------------------------------------
# Input/batch/cache specs
# ---------------------------------------------------------------------------


def batch_specs(cfg: LMConfig, mesh: Mesh, batch: dict) -> dict:
    """Shard batch dicts: leading batch dim over (pod, data)."""
    dp = data_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    ndev = 1
    for a in dp:
        ndev *= mesh.shape[a]

    def leaf(x):
        b = x.shape[0]
        if b % ndev == 0:
            return P(dpa, *([None] * (x.ndim - 1)))
        return P(*([None] * x.ndim))

    return jax.tree.map(leaf, batch)


def _first_divisible(shape, dims: list[int], mesh: Mesh, axis) -> int | None:
    """First dim (by priority) divisible by the mesh axis size."""
    n = _axis_size(mesh, axis)
    for d in dims:
        if d < len(shape) and shape[d] % n == 0 and shape[d] >= n:
            return d
    return None


def cache_specs(cfg: LMConfig, mesh: Mesh, cache: dict, batch: int) -> dict:
    """KV/SSM cache sharding.

    Batch shards over (pod, data) when divisible; otherwise (long_500k,
    batch=1) the cache *sequence* axis shards over "data"
    (sequence-parallel attention over the cache).
    """
    dp = data_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    ndev = 1
    for a in dp:
        ndev *= mesh.shape[a]
    batch_ok = batch % ndev == 0

    def spec_for(name, x):
        nd = x.ndim
        parts: list = [None] * nd
        if name in ("k", "v", "cross_k", "cross_v"):  # (L|G, B, S, H, hd)
            if batch_ok:
                parts[1] = dpa
            elif x.shape[2] % _axis_size(mesh, dpa) == 0:
                parts[2] = dpa                   # sequence-parallel cache
            # model axis: prefer heads (Megatron TP); when kv heads don't
            # divide (GQA with few kv heads), fall back to
            # sequence-parallel cache (flash-decode style), then head_dim.
            prio = [3] + ([2] if parts[2] is None else []) + [4]
            d = _first_divisible(x.shape, prio, mesh, "model")
            if d is not None:
                parts[d] = "model"
        elif name == "pos":                      # (B, S)
            if batch_ok:
                parts[0] = dpa
            elif x.shape[1] % _axis_size(mesh, dpa) == 0:
                parts[1] = dpa
        elif name == "ssm":                      # (L, B, H, P, N)
            if batch_ok:
                parts[1] = dpa
            d = _first_divisible(x.shape, [2, 3, 4], mesh, "model")
            if d is not None:
                parts[d] = "model"
        elif name == "conv":                     # (L, B, K-1, C)
            if batch_ok:
                parts[1] = dpa
            if x.shape[3] % _axis_size(mesh, "model") == 0:
                parts[3] = "model"
        return sanitize_spec(P(*parts), x.shape, mesh)

    return {k: spec_for(k, v) for k, v in cache.items()}


# ---------------------------------------------------------------------------
# Expert-parallel serving specs (("expert", "data") mesh, launch.serve)
# ---------------------------------------------------------------------------


def expert_param_specs(
    stacked: Any, mesh: Mesh, *, logical_axes: Any = None
) -> Any:
    """PartitionSpec pytree for stacked expert params (leaves ``(K, ...)``).

    Accepts a raw stacked pytree or any ``core.param_store.
    ExpertParamStore`` (stores are registered pytrees): a quantized
    store's per-expert scale arrays are just more ``(K,)`` leaves, so
    they shard over the mesh "expert" axis **together with the int8/fp8
    leaves they rescale** — a static expert slice resolves both from the
    same resident shard.

    The leading expert axis shards over the mesh's "expert" axis so each
    device group holds only ``K / n_expert_shards`` resident experts; all
    trailing (weight) dims replicate.  The ragged backend reads each
    device's own experts inside a ``shard_map`` and exchanges
    predictions, never weights (``core.dispatch.RaggedExecutor``); the
    gathered and grouped backends leave the routed slices to GSPMD, which
    gathers them from the owning shards.

    ``logical_axes`` optionally supplies per-leaf axis-name annotations
    (``models.dit.stacked_param_logical_axes`` / ``ExpertParamStore.
    logical_axes``); by default every leaf is assumed to carry the
    stacked layout's leading "expert" axis.  Non-divisible K falls back
    to replication (``sanitize_spec``), which keeps the degenerate
    1-shard mesh bit-identical to unsharded serving.
    """
    leaves, treedef = jax.tree.flatten(stacked)
    if logical_axes is None:
        ax_leaves = [("expert",) + (None,) * (x.ndim - 1) for x in leaves]
    else:
        # annotation leaves are axis-name tuples — themselves pytrees, so
        # flatten with an explicit is_leaf instead of zipping tree_maps.
        ax_leaves = jax.tree.leaves(
            logical_axes, is_leaf=lambda n: isinstance(n, tuple)
        )
        if len(ax_leaves) != len(leaves):
            raise ValueError("logical_axes does not match the stacked pytree")

    def leaf(x, axes):
        spec = P(*[a if a in mesh.axis_names else None for a in axes])
        return sanitize_spec(spec, x.shape, mesh)

    return jax.tree.unflatten(
        treedef, [leaf(x, a) for x, a in zip(leaves, ax_leaves)]
    )


def expert_param_shardings(
    stacked: Any, mesh: Mesh, *, logical_axes: Any = None
) -> Any:
    return to_shardings(
        mesh, expert_param_specs(stacked, mesh, logical_axes=logical_axes)
    )


def dispatch_plan_sharding(mesh: Mesh) -> NamedSharding:
    """Executor-aware placement for ``core.dispatch.DispatchPlan`` arrays.

    Routing metadata (per-sample slot indices/weights, the expert-sorted
    assignment order, per-expert segment offsets) replicates across the
    mesh: every shard needs the full plan to slice its resident experts'
    groups (grouped backend), gather its param slices (gathered backend),
    or build the pair-major expert ids of all ``B·k`` pairs (ragged
    backend — on an expert mesh each device maps them to its own local
    experts' ids and masks the pairs it does not own), and the arrays
    are O(B·k) ints — replication costs nothing next to the latents.
    Constraining them explicitly keeps GSPMD from threading a sharded
    batch axis into the executor's per-expert branches, which would
    force collectives inside every bucket branch (grouped) or inside the
    ragged forward, whose one collective is the exchange of predictions
    at its end.
    """
    return NamedSharding(mesh, P())


def mesh_scope(mesh: Mesh | None):
    """Context a served program is traced in when it runs on ``mesh``.

    The hot-path kernels (``kernels.ops``) look for this ambient mesh:
    the TPU compiler cannot partition a Pallas launch, so on a
    multi-device mesh each launch runs replicated under ``shard_map``,
    except inside the expert-parallel ragged apply's own ``shard_map``,
    where it is a plain call on each device's operands.  The executor
    reads the mesh's "expert" axis from it too
    (``core.dispatch.expert_parallel_mesh``).  No mesh — no context.
    """
    if mesh is None:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def serve_batch_spec(mesh: Mesh, shape: tuple[int, ...]) -> P:
    """Request-batch spec on the expert mesh: leading dim over "data".

    Falls back to replication when the batch doesn't divide the data axis
    (jit in_shardings need exact divisibility).  Rank-0/size-0 leaves
    (PRNG keys, the no-text static filler) replicate.
    """
    if not shape or 0 in shape:
        return P(*([None] * len(shape)))
    return sanitize_spec(
        P("data", *([None] * (len(shape) - 1))), shape, mesh
    )


def rolling_state_shardings(
    mesh: Mesh, shape: tuple[int, ...]
) -> tuple[NamedSharding, NamedSharding]:
    """Shardings for a rolling batch's ``(latent, row-state)`` buffers.

    The continuous scheduler (``repro.serving``) carries four
    ``(B_cap, ...)``-leading buffers across ticks: the latent ``x``
    shards like any request batch (leading dim over "data",
    :func:`serve_batch_spec`); the per-row scalar state — ``t_idx``,
    ``slot_idx``, ``slot_w`` — replicates, exactly like the
    ``DispatchPlan`` arrays it feeds: O(B·k) ints/floats that every
    shard needs whole to build its per-step plan, so splitting them
    would buy nothing and cost a collective inside the step.

    Returns ``(latent_sharding, row_state_sharding)``.
    """
    lat = NamedSharding(mesh, serve_batch_spec(mesh, shape))
    return lat, NamedSharding(mesh, P())


def dit_batch_specs(mesh: Mesh, batch: dict) -> dict:
    dp = data_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    return jax.tree.map(
        lambda x: P(dpa, *([None] * (x.ndim - 1))), batch
    )


def to_shardings(mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
