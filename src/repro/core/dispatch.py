"""Pluggable expert-dispatch API: ``DispatchPlan`` + executor backends.

The paper's inference-time fusion routes each sample to its top-k experts
(§3.1); *how* those routed forwards execute is a serving-engine decision
that every perf rung (grouped dispatch, quantized experts, cross-host
routing) needs to plug into.  This module is that seam:

* ``DispatchPlan`` — a traced, batch-shaped description of one step's
  routing decisions, computed once per step from the router posterior:
  per-sample expert slots and fusion weights, plus the sort-based *group*
  view of the same assignments (flat sort order, its inverse, and
  per-expert segment offsets).
* ``ExpertExecutor`` — the protocol every backend implements: turn a plan
  plus the step inputs into the raw per-slot routed ``predictions``
  (plus tiled weights/slot ids — the fused-kernel operands).  The sampler
  chooses the kernel: ``velocity`` (Eq. 1 combine through
  ``kernels.ops.fused_velocity``) on the unfused path, or the step-fused
  ``kernels.ops.fused_step`` which additionally folds the CFG combine
  and Euler update so no intermediate velocity materializes in HBM.
* Three backends:

  - ``GatheredExecutor`` — per-sample param gather + ``vmap`` (the
    original compute-sparse path, extracted): each routed slot gathers
    its expert's params per sample and runs one vmapped lane per sample.
    Batch-uniform plans (threshold router) collapse to a scalar gather.
  - ``GroupedExecutor`` — sort-based grouped execution (DDM/Paris-style):
    argsort the ``B·k`` assignments by expert, run each expert **once**
    over its contiguous segment (padded to a power-of-two bucket so the
    trace stays static-shaped; ``lax.switch`` picks the bucket at run
    time and empty segments skip the forward entirely), then unsort.
    Per-expert params come from *static* slices of the stacked pytree, so
    on an ``("expert", "data")`` mesh each expert's weights resolve from
    their resident shard instead of a per-sample dynamic-gather
    (all-gather) of ``B·k`` param copies.
  - ``DenseExecutor`` — the heterogeneous-``apply_fn`` fallback: every
    expert runs through its own apply (no stacking required); batch-
    uniform plans run only the routed expert via ``lax.switch``.

Plan invariants (tested in ``tests/test_dispatch.py``):

* ``segment_offsets`` is monotone with ``segment_offsets[0] == 0`` and
  ``segment_offsets[-1] == B·k`` (every assignment lands in exactly one
  expert's segment);
* ``unsort_order`` is the true inverse permutation of ``sort_order``;
* sorted assignment ``r`` belongs to expert ``e`` iff
  ``segment_offsets[e] <= r < segment_offsets[e+1]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.conversion import ConversionConfig
from repro.core.param_store import DenseStore, ExpertParamStore, as_store
from repro.kernels import ops

Array = jax.Array

#: valid ``SamplerConfig.dispatch`` values (``auto`` resolves per engine
#: mode and expert-set shape, see ``resolve_dispatch``).
DISPATCH_BACKENDS = ("auto", "gathered", "grouped", "ragged", "dense")


# ---------------------------------------------------------------------------
# DispatchPlan
# ---------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("slot_idx", "slot_w", "sort_order", "unsort_order",
                 "segment_offsets"),
    meta_fields=("num_experts", "uniform"),
)
@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Traced, batch-shaped routing decisions for one sampling step.

    With ``B`` samples, ``k`` routed slots per sample and ``K`` experts,
    the ``N = B·k`` flat *assignments* are numbered ``a = s·k + j``
    (sample ``s``, slot ``j``).

    Attributes:
      slot_idx: ``(B, k)`` int32 — expert id per routed slot.
      slot_w: ``(B, k)`` — fusion weight per slot (zero-weight slots are
        legal; their forward is wasted but the fused result is exact).
      sort_order: ``(N,)`` int32 — assignment ids in expert-grouped order
        (stable argsort of the flattened ``slot_idx``; ties keep
        assignment order, so the plan is deterministic).
      unsort_order: ``(N,)`` int32 — inverse permutation:
        ``unsort_order[a]`` is assignment ``a``'s position in the sorted
        view; ``sort_order[unsort_order] == arange(N)``.
      segment_offsets: ``(K+1,)`` int32 — expert ``e``'s sorted segment is
        ``sort_order[segment_offsets[e]:segment_offsets[e+1]]``.
      num_experts: static ``K``.
      uniform: static flag — every sample routes to the same expert(s)
        (the §3.3 threshold router); executors may collapse the batch to
        a single expert forward.
    """

    slot_idx: Array
    slot_w: Array
    sort_order: Array
    unsort_order: Array
    segment_offsets: Array
    num_experts: int
    uniform: bool = False

    @property
    def batch(self) -> int:
        return self.slot_idx.shape[0]

    @property
    def slots_per_sample(self) -> int:
        return self.slot_idx.shape[1]

    @property
    def num_assignments(self) -> int:
        return self.sort_order.shape[0]


def topk_slots(weights: Array, k: int) -> tuple[Array, Array]:
    """Expert slots for routed-only execution.

    Args:
      weights: ``(B, K)`` final fusion weights (≤ k nonzero per row).
      k: number of slots to run.

    Returns:
      ``(slot_idx, slot_w)`` both ``(B, k)`` — the expert index and fusion
      weight per slot.  Slots beyond the nonzero support carry zero weight
      (their forward is wasted but the fused result is exact).
    """
    slot_w, slot_idx = jax.lax.top_k(weights, min(k, weights.shape[-1]))
    return slot_idx, slot_w


def plan_from_slots(
    slot_idx: Array,
    slot_w: Array,
    num_experts: int,
    *,
    uniform: bool = False,
) -> DispatchPlan:
    """Build a plan (including the sorted group view) from routed slots.

    The group view costs one stable argsort over the ``B·k`` assignments
    plus a scatter for the inverse permutation and a bincount-cumsum for
    the segment offsets; executors that never touch it (gathered, dense)
    let XLA dead-code-eliminate it.
    """
    flat = slot_idx.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    sort_order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    unsort_order = (
        jnp.zeros((n,), jnp.int32).at[sort_order].set(
            jnp.arange(n, dtype=jnp.int32))
    )
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    segment_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    return DispatchPlan(
        slot_idx=slot_idx.astype(jnp.int32),
        slot_w=slot_w,
        sort_order=sort_order,
        unsort_order=unsort_order,
        segment_offsets=segment_offsets,
        num_experts=num_experts,
        uniform=uniform,
    )


def routed_slots(
    weights: Array,
    k: int,
    *,
    valid: Array | None = None,
) -> tuple[Array, Array]:
    """Top-``k`` slot selection with the elastic-membership guard.

    The slot half of :func:`make_dispatch_plan`, exposed separately for
    callers that carry raw ``(slot_idx, slot_w)`` row state across steps
    (the continuous-batching scheduler refreshes slots per request on its
    own R-phase and rebuilds the plan's group view with
    :func:`plan_from_slots` each step).

    ``valid`` (optional ``(K,)`` bool): any slot whose selected expert is
    invalid — possible only when ``k`` exceeds the live count, since
    masked fusion weights give dead slots zero probability — is remapped
    to the first valid expert with weight exactly 0, keeping the slots
    NaN-safe against whatever bytes an evicted capacity slot holds.
    """
    slot_idx, slot_w = topk_slots(weights, k)
    if valid is not None:
        valid = jnp.asarray(valid, dtype=bool)
        fallback = jnp.argmax(valid).astype(jnp.int32)
        ok = valid[slot_idx]                              # (B, k)
        slot_idx = jnp.where(ok, slot_idx, fallback)
        slot_w = jnp.where(ok, slot_w, jnp.zeros_like(slot_w))
    return slot_idx, slot_w


def make_dispatch_plan(
    weights: Array,
    k: int,
    *,
    uniform: bool = False,
    valid: Array | None = None,
) -> DispatchPlan:
    """Plan for routed execution: top-``k`` slots of the fusion weights.

    This is the §3.1 slot selection (formerly ``fusion.topk_slots``)
    folded into plan construction — the single per-step entry point for
    every routed backend.

    ``valid`` (optional ``(K,)`` bool) is the elastic-membership guard:
    any slot whose selected expert is invalid — possible only when ``k``
    exceeds the live count, since masked fusion weights give dead slots
    zero probability — is remapped to the first valid expert with weight
    exactly 0 (see :func:`routed_slots`).  The remap keeps the plan
    NaN-safe against whatever bytes an evicted/empty capacity slot
    holds: a dead expert's params are never gathered and never run a
    segment forward, and a zero-weight fallback slot contributes exact
    ``0.0`` to the fused combine.
    """
    slot_idx, slot_w = routed_slots(weights, k, valid=valid)
    return plan_from_slots(slot_idx, slot_w, weights.shape[-1],
                           uniform=uniform)


def full_dispatch_plan(weights: Array) -> DispatchPlan:
    """Plan with one slot per expert (dense execution, strategy='full').

    ``slot_idx`` is ``arange(K)`` per row and ``slot_w`` the full weight
    matrix, so slot ``j`` *is* expert ``j`` and the dense executor's
    expert-order prediction stack lines up with the fused-kernel slots.
    """
    b, num_experts = weights.shape
    slot_idx = jnp.broadcast_to(
        jnp.arange(num_experts, dtype=jnp.int32)[None], (b, num_experts)
    )
    return plan_from_slots(slot_idx, weights, num_experts)


def tile_plan(plan: DispatchPlan, g: int) -> DispatchPlan:
    """Plan for ``g`` stacked guidance branches of the same batch.

    Batched CFG concatenates the cond/uncond branches along the batch
    axis; both branches share each sample's routing, so the tiled plan
    just repeats the slots ``g`` times and rebuilds the group view over
    the ``g·B·k`` assignments.
    """
    if g == 1:
        return plan
    return plan_from_slots(
        jnp.concatenate([plan.slot_idx] * g, axis=0),
        jnp.concatenate([plan.slot_w] * g, axis=0),
        plan.num_experts,
        uniform=plan.uniform,
    )


# ---------------------------------------------------------------------------
# Executor protocol + shared helpers
# ---------------------------------------------------------------------------


@runtime_checkable
class ExpertExecutor(Protocol):
    """Backend turning a plan + step inputs into routed predictions.

    ``predictions`` receives the pre-CFG batch ``x``/``tb`` of size ``B``
    with grouped conditioning ``cond_g`` (leaves ``(B, g, ...)`` from
    ``sampling._cfg_grouped_cond``; ``g=2`` when CFG branches are batched,
    else 1) plus the step's ``(5, K)`` unified-coefficient table, and
    returns the raw per-slot native predictions ``(k, g·B, *latent)`` in
    ``[cond; uncond]`` branch-major order together with the tiled fusion
    weights and slot indices (both ``(g·B, k)``) — the exact operands of
    the fused kernels.  How those feed a kernel is the *sampler's*
    decision: the unfused path runs ``kernels.ops.fused_velocity`` (via
    ``velocity`` below) and combines CFG + Euler as separate ops; the
    step-fused hot path hands the same operands to
    ``kernels.ops.fused_step``, which folds CFG combine and the Euler
    update into the convert-and-fuse kernel so no intermediate velocity
    ``u`` ever materializes in HBM.

    ``velocity`` is the unfused convenience form: ``predictions``
    followed by the Eq. 1 convert-and-fuse, returning the fused velocity
    ``(g·B, *latent)``.
    """

    name: str

    def predictions(
        self,
        plan: DispatchPlan,
        x: Array,
        tb: Array,
        cond_g: dict,
        g: int,
        tab: Array,
    ) -> tuple[Array, Array, Array]:
        ...

    def velocity(
        self,
        plan: DispatchPlan,
        x: Array,
        tb: Array,
        cond_g: dict,
        g: int,
        tab: Array,
    ) -> Array:
        ...


class _FusedVelocity:
    """Shared unfused ``velocity``: ``predictions`` + convert-and-fuse."""

    def velocity(self, plan, x, tb, cond_g, g, tab):
        preds, w_all, idx_all = self.predictions(plan, x, tb, cond_g, g,
                                                 tab)
        return _fused(preds, _tile(x, g), w_all, idx_all, tab, self.conv)


def _tile(a: Array, g: int) -> Array:
    return a if g == 1 else jnp.concatenate([a] * g, axis=0)


def _flatten_groups(cond_g: dict, g: int) -> dict:
    """``(B, g, ...)`` grouped cond -> ``(g·B, ...)`` branch-major flat."""
    return {
        key: jnp.moveaxis(v, 1, 0).reshape((g * v.shape[0],) + v.shape[2:])
        for key, v in cond_g.items()
    }


def slot_coef(tab: Array, idx_all: Array) -> Array:
    """Gather the ``(5, K)`` step table into per-slot form ``(5, k, Bx)``.

    The coefficient operand shared by ``kernels.ops.fused_velocity`` and
    the step-fused ``kernels.ops.fused_step``.
    """
    return jnp.moveaxis(tab[:, idx_all], 1, 2)


def slot_coef_rows(tabs: Array, idx_all: Array) -> Array:
    """Per-row variant of :func:`slot_coef` for mixed-timestep batches.

    Each batch row carries its *own* ``(5, K)`` step table (``tabs`` is
    ``(Bx, 5, K)`` — row ``r``'s slice of the per-run ``(S, 5, K)``
    table at that row's current timestep), and the gather picks row
    ``r``'s routed-slot columns from row ``r``'s table:
    ``out[c, j, r] = tabs[r, c, idx_all[r, j]]``, returned ``(5, k,
    Bx)``.  When every row holds the same table this is bitwise equal to
    ``slot_coef(tab, idx_all)`` — the lockstep path is the uniform
    special case.
    """
    g = jnp.take_along_axis(tabs, idx_all[:, None, :], axis=2)  # (Bx, 5, k)
    return jnp.moveaxis(g, 0, 2)                                # (5, k, Bx)


def _fused(
    preds: Array,        # (k, Bx, *latent) per-slot native predictions
    x_all: Array,        # (Bx, *latent)
    w_all: Array,        # (Bx, k)
    idx_all: Array,      # (Bx, k)
    tab: Array,          # (5, K)
    conv: ConversionConfig,
) -> Array:
    """Per-slot coefficient gather + fused convert-and-fuse kernel."""
    return ops.fused_velocity(
        preds, x_all, w_all, slot_coef(tab, idx_all),
        clamp=conv.clamp, alpha_min=conv.alpha_min,
    )


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# GatheredExecutor — per-sample gather + vmap (the original routed path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GatheredExecutor(_FusedVelocity):
    """Per-sample param gather + vmap over routed slots.

    Each of the ``k`` slots gathers its expert's params per sample
    (``store.gather(slot_idx[:, j])`` — leaves come back ``(B, ...)``)
    and runs one vmapped model instance per sample; the ``g`` guidance
    branches share the sample's latent *and* routed expert, so they run
    inside the same vmapped instance and the params are gathered once,
    not per branch.  Batch-uniform plans collapse to a scalar gather and
    a single plain forward.  Params resolve through an
    ``ExpertParamStore``: a ``DenseStore`` emits the exact gather ops
    this executor used to hand-roll, while a ``QuantizedStore`` gathers
    int8/fp8 bytes and dequantizes only the routed slices through the
    fused ``hetero_fuse_dequant`` kernel.
    """

    apply_fn: Callable[..., Array]
    store: ExpertParamStore
    conv: ConversionConfig
    name: str = "gathered"

    def _vmapped(self, g: int):
        apply_fn = self.apply_fn

        def one(p1, x1, t1, c1):
            xg = jnp.broadcast_to(x1[None], (g,) + x1.shape)
            tg = jnp.full((g,), t1)
            return apply_fn(p1, xg, tg, **c1)             # (g, *latent)

        return jax.vmap(one)

    def predictions(self, plan, x, tb, cond_g, g, tab):
        b = x.shape[0]
        k = plan.slots_per_sample
        w_all = _tile(plan.slot_w, g)
        idx_all = _tile(plan.slot_idx, g)
        if plan.uniform:
            # Whole batch routes to one expert: scalar gather, one forward.
            p = self.store.gather(plan.slot_idx[0, 0])
            cond_all = _flatten_groups(cond_g, g)
            preds = self.apply_fn(p, _tile(x, g), _tile(tb, g),
                                  **cond_all)[None]
            return preds, w_all, idx_all
        vmapped = self._vmapped(g)
        cols = []
        for j in range(k):
            pj = self.store.gather(plan.slot_idx[:, j])
            cols.append(vmapped(pj, x, tb, cond_g))       # (B, g, *latent)
        preds = jnp.moveaxis(jnp.stack(cols), 2, 1)       # (k, g, B, ...)
        preds = preds.reshape((k, g * b) + preds.shape[3:])
        return preds, w_all, idx_all


# ---------------------------------------------------------------------------
# GroupedExecutor — sort-based grouped execution (DDM/Paris-style)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GroupedExecutor(_FusedVelocity):
    """Sort assignments by expert; one segment pass per resident expert.

    Pipeline per step (all static-shaped so it traces once under scan):

    1. flatten the ``g`` guidance branches to ``Bx = g·B`` rows and tile
       the plan (both branches share each sample's routing);
    2. gather the ``N = Bx·k`` assignment rows into expert-sorted order
       (a cheap gather of *latents*, not params) and zero-pad the sorted
       buffer to the next power of two ``Np``;
    3. for each expert ``e`` (static Python loop): pick the padded
       power-of-two bucket covering its segment length with
       ``lax.switch`` and run ONE forward over that bucket slice — empty
       segments take the 0-bucket branch and skip the forward entirely.
       Params come from a *static* slice ``store.expert(e)``, so on an
       ``("expert", "data")`` mesh the weights resolve from the shard
       that owns expert ``e`` instead of a per-sample dynamic-gather
       (expert-axis all-gather) of ``B·k`` param copies; a
       ``QuantizedStore`` dequantizes exactly that resident slice inline
       (fused ``hetero_fuse_dequant``), so only int8/fp8 bytes sit
       stacked in HBM;
    4. scatter each bucket's valid rows back into a flat prediction
       buffer (out-of-segment bucket rows are dropped), unsort, and fuse
       through the same ``fused_velocity`` kernel as every other backend.

    Per-step expert forwards: at most one per expert with a non-empty
    segment — ≤ ``K`` resident experts, vs ``B·k`` vmapped per-sample
    lanes on the gathered path.  Bucket overshoot bounds wasted rows at
    < 2× the true segment length.
    """

    apply_fn: Callable[..., Array]
    store: ExpertParamStore
    conv: ConversionConfig
    name: str = "grouped"

    def predictions(self, plan, x, tb, cond_g, g, tab):
        b = x.shape[0]
        k = plan.slots_per_sample
        n_experts = plan.num_experts
        x_all = _tile(x, g)
        t_all = _tile(tb, g)
        cond_all = _flatten_groups(cond_g, g)
        p = tile_plan(plan, g)
        n = p.num_assignments                              # g·B·k
        np2 = _next_pow2(n)
        off = p.segment_offsets

        # Sorted assignment rows (gathers of latents/cond, not params).
        sample_ids = p.sort_order // k                     # (N,)
        xs = x_all[sample_ids]
        ts = t_all[sample_ids]
        cs = {key: v[sample_ids] for key, v in cond_all.items()}
        if np2 > n:
            pad = [(0, np2 - n)]
            xs = jnp.pad(xs, pad + [(0, 0)] * (xs.ndim - 1))
            ts = jnp.pad(ts, pad)
            cs = {key: jnp.pad(v, pad + [(0, 0)] * (v.ndim - 1))
                  for key, v in cs.items()}

        out_sd = jax.eval_shape(
            lambda p_, x_, t_, c_: self.apply_fn(p_, x_, t_, **c_),
            self.store.expert(0),
            xs[:1], ts[:1], {key: v[:1] for key, v in cs.items()},
        )
        buf = jnp.zeros((np2,) + out_sd.shape[1:], out_sd.dtype)

        sizes = [1 << j for j in range(np2.bit_length())]  # 1..np2
        thresholds = jnp.array([0] + sizes[:-1], jnp.int32)

        # Dense stores: one cheap static slice per expert, hoisted out of
        # the switch (slicing it once per bucket branch would only bloat
        # the already branch-heavy grouped trace).  Quantized stores:
        # slice+dequant trace INSIDE each branch instead, so an expert
        # with an empty segment skips its fused dequant along with the
        # forward.
        dense_slices = (
            [self.store.expert(e) for e in range(n_experts)]
            if isinstance(self.store, DenseStore) else None
        )

        def _branches(e):
            def run(size):
                def branch(buf):
                    params_e = dense_slices[e] if dense_slices is not None \
                        else self.store.expert(e)
                    start = jnp.minimum(off[e], np2 - size)
                    xb = jax.lax.dynamic_slice_in_dim(xs, start, size)
                    tb_ = jax.lax.dynamic_slice_in_dim(ts, start, size)
                    cb = {
                        key: jax.lax.dynamic_slice_in_dim(v, start, size)
                        for key, v in cs.items()
                    }
                    pred = self.apply_fn(params_e, xb, tb_, **cb)
                    pos = start + jnp.arange(size, dtype=jnp.int32)
                    valid = (pos >= off[e]) & (pos < off[e + 1])
                    # invalid rows target index np2 -> dropped by scatter
                    tgt = jnp.where(valid, pos, np2)
                    return buf.at[tgt].set(pred.astype(buf.dtype),
                                           mode="drop")
                return branch

            # branch 0: empty segment — no forward at all.
            return [lambda buf: buf] + [run(s) for s in sizes]

        for e in range(n_experts):
            seg_len = off[e + 1] - off[e]
            bucket_id = jnp.sum(seg_len > thresholds)
            buf = jax.lax.switch(bucket_id, _branches(e), buf)

        preds_flat = buf[p.unsort_order]                   # (N, *latent)
        preds = preds_flat.reshape((g * b, k) + preds_flat.shape[1:])
        preds = jnp.moveaxis(preds, 1, 0)                  # (k, g·B, ...)
        return preds, p.slot_w, p.slot_idx


# ---------------------------------------------------------------------------
# RaggedExecutor — one-kernel ragged grouped GEMM (pair-major)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RaggedExecutor(_FusedVelocity):
    """Pair-major ragged execution: all experts' segments in one pass.

    Walks the same expert-sorted segment layout as the grouped backend,
    but at *pair* granularity instead of row granularity: the ``g``
    guidance replicas of a (sample, slot) assignment share the latent,
    the timestep and the routed expert (``tile_plan`` repeats slots per
    branch), so the sorted ``N = g·B·k`` rows regroup into ``P = B·k``
    pairs of ``g`` replicas each.  The executor hands the
    ``ragged_apply_fn`` one representative latent per pair plus the
    per-pair expert ids derived from ``segment_offsets`` (via the
    plan's sort), and the apply runs every dense layer as ONE ragged
    grouped GEMM over all resident experts
    (``kernels.ops.ragged_expert_matmul`` →
    ``kernels.ragged_gemm.ragged_gemm`` on TPU):

    * no per-expert ``lax.switch`` branches, no power-of-two bucket
      padding — work scales with actual assignments, and empty segments
      / dead validity slots cost zero kernel tiles;
    * weights resolve per row *tile* from the raw stacked leaves
      (``store.ragged_view()``) — quantized stores contract on int8/fp8
      operands with the dequant scale fused into the GEMM epilogue,
      never materializing full-precision copies;
    * the conditioning-independent prefix of the network computes once
      per pair and broadcasts to the replicas (the grouped backend's
      black-box ``apply_fn`` contract cannot see that structure).

    * on a mesh whose ``"expert"`` axis spans several devices the apply
      runs inside one ``shard_map`` (``_expert_parallel_apply``): each
      device runs the pairs routed to its own experts (the expert GEMMs
      skip the other pairs' row tiles), and one ``psum`` a step
      (``_exchange``) joins the devices' predictions.

    Dense float32 stores are bitwise-identical to the grouped backend;
    quantized stores match within the store's quantization error.
    Membership (``valid``) stays traced data: hot add/evict reaches
    this executor as new plan/store *values* under the same trace.
    """

    ragged_apply_fn: Callable[..., Array]
    store: ExpertParamStore
    conv: ConversionConfig
    name: str = "ragged"

    def predictions(self, plan, x, tb, cond_g, g, tab):
        b = x.shape[0]
        k = plan.slots_per_sample
        x_all = _tile(x, g)
        t_all = _tile(tb, g)
        cond_all = _flatten_groups(cond_g, g)
        p = tile_plan(plan, g)
        n = p.num_assignments                              # g·B·k
        npair = n // g                                     # B·k

        # Pair view of the sorted assignments: sorted row r is replica
        # ``gidx`` of pair ``pair`` (sample-major pair ids, slot minor).
        sample_ids = p.sort_order // k                     # (N,) in [0, g·B)
        gidx = sample_ids // b                             # guidance branch
        base = sample_ids % b                              # sample in [0, B)
        slot = p.sort_order % k
        pair = base * k + slot                             # (N,) pair id
        # pg_pos[q, j] = sorted position of pair q's replica j — exists
        # and is unique because tile_plan repeats each slot per branch.
        pg_pos = jnp.zeros((npair, g), jnp.int32).at[pair, gidx].set(
            jnp.arange(n, dtype=jnp.int32)
        )
        rep = pg_pos[:, 0]                                 # representative
        row_e = p.slot_idx.reshape(-1)[p.sort_order]       # (N,) expert/row
        pe = row_e[rep]                                    # (P,) expert/pair

        xs = x_all[sample_ids][rep]                        # (P, *latent)
        ts = t_all[sample_ids][rep]                        # (P,)
        cs = {key: v[sample_ids][pg_pos] for key, v in cond_all.items()}

        mesh = expert_parallel_mesh()
        if mesh is None:
            out = self.ragged_apply_fn(self.store.ragged_view(), xs, ts, cs,
                                       pe, g)              # (P·g, ...)
        else:
            out = _expert_parallel_apply(self.ragged_apply_fn, self.store,
                                         xs, ts, cs, pe, g, mesh)
        out = out.reshape((npair, g) + out.shape[1:])
        preds_sorted = out[pair, gidx]                     # (N, *latent)
        preds_flat = preds_sorted[p.unsort_order]
        preds = preds_flat.reshape((g * b, k) + preds_flat.shape[1:])
        preds = jnp.moveaxis(preds, 1, 0)                  # (k, g·B, ...)
        return preds, p.slot_w, p.slot_idx


def expert_parallel_mesh():
    """The ambient mesh if its ``"expert"`` axis spans several devices and
    the trace is not already inside a ``shard_map`` over it, else None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.are_all_axes_manual \
            or dict(mesh.shape).get("expert", 1) == 1:
        return None
    return mesh


def _exchange(out: Array) -> Array:
    """The one cross-chip collective of an expert-parallel step: each
    pair's prediction is non-zero on the one shard that owns its expert,
    so the sum over ``"expert"`` is exact."""
    with jax.named_scope("expert_exchange"):
        return jax.lax.psum(out, "expert")


def shard_share(apply_fn, store, xs, ts, cs, pe, g):
    """One shard's share of a step's ragged predictions, inside a
    ``shard_map`` over ``"expert"``: ``store`` holds this shard's experts
    only.  Each pair's global expert id maps to a local one, and the
    forward keeps all ``P`` pair slots (static shapes) but is told which
    pairs this shard owns (``live``): the expert GEMMs skip the row tiles
    of the others, so a shard's GEMM work follows its share of the
    router's split.  The pairs another shard owns are zeroed.  Returns
    ``(P·g, ...)``."""
    n_local = jax.tree.leaves(store)[0].shape[0]
    lo = jax.lax.axis_index("expert") * n_local
    own = (pe >= lo) & (pe < lo + n_local)
    out = apply_fn(store.ragged_view(), xs, ts, cs,
                   jnp.where(own, pe - lo, 0), g, live=own)
    keep = jnp.repeat(own, g).reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(keep, out, 0.0)


def _expert_parallel_apply(apply_fn, store, xs, ts, cs, pe, g, mesh):
    """The ragged apply of one step on an expert-sharded store: one
    ``shard_map`` over the mesh, store leaves ``P("expert")``, the step's
    pairs, timesteps, conditioning and global expert ids ``pe``
    replicated; each device computes its ``shard_share`` and
    ``_exchange`` sums them.  No weight leaf crosses devices, and the
    forward holds no collective."""
    def shard(store, xs, ts, cs, pe):
        return _exchange(shard_share(apply_fn, store, xs, ts, cs, pe, g))

    return jax.shard_map(
        shard, mesh=mesh, in_specs=(P("expert"), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(store, xs, ts, cs, pe)


# ---------------------------------------------------------------------------
# DenseExecutor — heterogeneous apply_fn fallback
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DenseExecutor(_FusedVelocity):
    """Run every expert through its own ``apply_fn`` (no stacking needed).

    The fallback for expert sets the sparse backends cannot stack
    (heterogeneous architectures / param structures).  Batch-uniform
    plans (threshold router) still run only the routed expert, via
    ``lax.switch`` over the expert closures.
    """

    apply_fns: Sequence[Callable[..., Array]]
    params: Sequence
    conv: ConversionConfig
    name: str = "dense"

    def predictions(self, plan, x, tb, cond_g, g, tab):
        x_all = _tile(x, g)
        t_all = _tile(tb, g)
        cond_all = _flatten_groups(cond_g, g)
        w_all = _tile(plan.slot_w, g)
        idx_all = _tile(plan.slot_idx, g)
        if plan.uniform:
            idx0 = plan.slot_idx[0, 0]
            branches = [
                functools.partial(
                    lambda fn, p, op: fn(p, op[0], op[1], **op[2]), fn, p,
                )
                for fn, p in zip(self.apply_fns, self.params)
            ]
            preds = jax.lax.switch(
                idx0, branches, (x_all, t_all, cond_all)
            )[None]
        else:
            preds = jnp.stack([
                fn(p, x_all, t_all, **cond_all)
                for fn, p in zip(self.apply_fns, self.params)
            ])
        return preds, w_all, idx_all


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------


def resolve_dispatch(
    dispatch: str, mode: str, stackable: bool, uniform: bool = False,
    ragged_ok: bool = False,
) -> str:
    """Map a ``SamplerConfig.dispatch`` request to a concrete backend.

    Args:
      dispatch: requested backend (``DISPATCH_BACKENDS``).
      mode: resolved engine mode (``'routed'`` or ``'dense'`` — the
        reference engine never reaches executor selection).
      stackable: stacked single-pytree params are available (homogeneous
        apply_fn + identical param structure, as a raw stacked pytree or
        an ``ExpertParamStore``).
      uniform: the plan is batch-uniform (§3.3 threshold router) — every
        sample routes to the same expert(s).
      ragged_ok: the expert set publishes a shared ``ragged_apply_fn``
        (``ExpertSpec``) so the one-kernel ragged GEMM backend can run.

    ``auto`` prefers the **ragged** backend whenever the expert set can
    run it (params stack, per-sample routing, a published
    ``ragged_apply_fn``): one ragged grouped GEMM per dense layer
    replaces the grouped backend's per-expert ``lax.switch`` branches
    and power-of-two bucket padding, is bitwise-identical to grouped
    for dense float32 stores, and measures ≥1.15× grouped img/s on the
    tracked configuration (``BENCH_sampler.json`` ``ragged`` section).
    Expert sets without a ragged apply keep the previous preference
    order: grouped (1.22× faster than gathered on the same tracked
    config) when params stack and routing is per-sample; batch-uniform
    plans fall back to gathered, whose scalar-gather path runs exactly
    one forward with none of the bucket machinery; non-stackable expert
    sets fall back to dense.  Explicit ``gathered``/``grouped``/
    ``ragged`` raise a clear error when their preconditions don't hold,
    instead of silently degrading.
    """
    if dispatch not in DISPATCH_BACKENDS:
        raise ValueError(
            f"unknown dispatch backend {dispatch!r}; "
            f"expected one of {DISPATCH_BACKENDS}"
        )
    if mode == "dense":
        if dispatch in ("gathered", "grouped", "ragged"):
            raise ValueError(
                f"dispatch={dispatch!r} requires routed execution "
                f"(strategy in top1/topk/threshold with a routable expert "
                f"set); this configuration resolved to the dense engine"
            )
        return "dense"
    if dispatch == "auto":
        if not stackable:
            return "dense"
        if uniform:
            return "gathered"
        return "ragged" if ragged_ok else "grouped"
    if dispatch in ("gathered", "grouped", "ragged") and not stackable:
        raise ValueError(
            f"dispatch={dispatch!r} needs a shared apply_fn with stackable "
            f"params (see models.dit.stack_expert_params); heterogeneous "
            f"expert sets must use dispatch='dense'"
        )
    if dispatch == "ragged" and not ragged_ok:
        raise ValueError(
            "dispatch='ragged' needs a shared ragged_apply_fn on every "
            "ExpertSpec (see models.dit.make_ragged_expert_apply) and "
            "per-sample routing; this expert set does not publish one"
        )
    return dispatch


def make_executor(
    backend: str,
    *,
    apply_fns: Sequence[Callable[..., Array]],
    params: Sequence,
    stacked_params,
    conv: ConversionConfig,
    ragged_apply_fn: Callable[..., Array] | None = None,
) -> ExpertExecutor:
    """Instantiate the executor for a resolved backend name.

    ``stacked_params`` may be a raw stacked pytree (the pre-store calling
    convention, wrapped into a bit-identical ``DenseStore``) or any
    ``ExpertParamStore`` (e.g. a ``QuantizedStore`` for int8/fp8 expert
    weights).  ``ragged_apply_fn`` is the shared pair-major forward
    required by the ``ragged`` backend (``ExpertSpec.ragged_apply_fn``).
    """
    if backend in ("gathered", "grouped", "ragged"):
        store = as_store(stacked_params)
        if store is None:
            raise ValueError(
                f"dispatch={backend!r} needs stacked params or an "
                f"ExpertParamStore; got None"
            )
        if backend == "gathered":
            return GatheredExecutor(apply_fns[0], store, conv)
        if backend == "ragged":
            if ragged_apply_fn is None:
                raise ValueError(
                    "dispatch='ragged' needs a shared ragged_apply_fn "
                    "(see models.dit.make_ragged_expert_apply)"
                )
            return RaggedExecutor(ragged_apply_fn, store, conv)
        return GroupedExecutor(apply_fns[0], store, conv)
    if backend == "dense":
        if params is None:
            raise ValueError(
                "dispatch='dense' runs each expert through its own params "
                "list, which this engine no longer holds (a quantized "
                "ExpertParamStore replaced the full-precision per-expert "
                "params); use a routed strategy or param_dtype='native'"
            )
        return DenseExecutor(tuple(apply_fns), tuple(params), conv)
    raise ValueError(f"unknown executor backend {backend!r}")
