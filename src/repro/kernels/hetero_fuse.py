"""Fused heterogeneous convert-and-fuse Pallas TPU kernel (paper Fig. 2).

The paper's core inference op: for each sampling step, every expert's
native prediction is unified into velocity space (ε→v conversion, Eqs.
23–24 with §8.3 safeguards) and combined with router weights (Eq. 1).

Done naively this is K reads + K writes of a latent-sized tensor per step;
the fused kernel reads the K stacked predictions once, applies the
per-expert schedule coefficients (scalar per expert×sample, broadcast from
a (K, B) operand), and writes only the fused velocity.

Grid: (row blocks, T/block_t); the expert axis K is kept whole inside
the block (K ≤ 8 in the paper).  The hot-path kernels block rows 8 at a
time (or whole) and carry per-row scalars as ``(..., rows, 1)`` columns,
the block shapes the TPU lowering accepts.

Four entry points share the module's dispatch policy:

* :func:`hetero_fuse` — per-expert objective flags + raw schedule coeffs
  (the original dense-ensemble signature);
* :func:`hetero_fuse_coeffs` — the serving hot path: a single ``(5, K, B)``
  coefficient stack with FM experts already folded to the identity
  coefficients ``(1, 0, 0, 1, 1)`` (see ``conversion.unified_coeff_tables``),
  so the kernel needs no flag select and the K axis can hold *routed slots*
  (per-sample gathered experts) instead of the full ensemble;
* :func:`hetero_fuse_step` — the step-fused hot path: the coeffs kernel
  with the CFG combine ``u_u + s (u_c − u_u)`` (over a leading guidance
  branch axis) and the Euler update ``x ← x − u·dt`` folded in, so one
  sampling step costs one latent read + one latent write instead of the
  three round-trips of ``fused_velocity`` → ``cfg_combine`` → ``x − u·dt``;
* :func:`hetero_fuse_dequant` — the quantized-expert companion on the same
  hot path: expands an int8/fp8 gathered/sliced param view to compute
  precision by applying the symmetric per-row ``scale · q`` inline
  (``core.param_store.QuantizedStore``).  One kernel launch per leaf
  replaces the ``astype`` + broadcast-multiply HLO pair, and because it
  runs on the *gathered* slice, the stacked quantized leaves never
  round-trip through HBM at full precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _fuse_kernel(
    preds_ref, xt_ref, w_ref, flags_ref, coef_ref, o_ref,
    *, clamp: float, alpha_min: float,
):
    preds = preds_ref[:, 0].astype(jnp.float32)       # (K, bt)
    xt = xt_ref[0].astype(jnp.float32)                # (bt,)
    w = w_ref[0].astype(jnp.float32)                  # (K,)
    flags = flags_ref[...].astype(jnp.float32)        # (K,) 1.0 = ddpm
    coef = coef_ref[:, :, 0].astype(jnp.float32)      # (5, K)
    alpha, sigma, dalpha, dsigma, vscale = (
        coef[0], coef[1], coef[2], coef[3], coef[4]
    )

    a_safe = jnp.maximum(alpha, alpha_min)[:, None]
    x0h = (xt[None] - sigma[:, None] * preds) / a_safe
    x0h = jnp.clip(x0h, -clamp, clamp)
    v_conv = (dalpha[:, None] * x0h + dsigma[:, None] * preds) \
        * vscale[:, None]
    v = flags[:, None] * v_conv + (1.0 - flags[:, None]) * preds
    fused = jnp.sum(w[:, None] * v, axis=0)           # (bt,)
    o_ref[0] = fused.astype(o_ref.dtype)


def _row_block(rows: int) -> int:
    """Sublane block over a kernel's row axis: 8 rows (the TPU sublane
    tile) when they divide the axis, else the whole axis — the two row
    extents Mosaic lowers for a non-final block dimension."""
    return 8 if rows % 8 == 0 else rows


def _fuse_coeffs_kernel(
    preds_ref, xt_ref, w_ref, coef_ref, o_ref,
    *, clamp: float, alpha_min: float,
):
    preds = preds_ref[...].astype(jnp.float32)        # (K, bb, bt)
    xt = xt_ref[...].astype(jnp.float32)              # (bb, bt)
    w = w_ref[...].astype(jnp.float32)                # (K, bb, 1)
    coef = coef_ref[...].astype(jnp.float32)          # (5, K, bb, 1)
    alpha, sigma, dalpha, dsigma, vscale = (
        coef[0], coef[1], coef[2], coef[3], coef[4]
    )

    a_safe = jnp.maximum(alpha, alpha_min)
    x0h = (xt[None] - sigma * preds) / a_safe
    x0h = jnp.clip(x0h, -clamp, clamp)
    v = (dalpha * x0h + dsigma * preds) * vscale
    fused = jnp.sum(w * v, axis=0)                    # (bb, bt)
    o_ref[...] = fused.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("clamp", "alpha_min", "block_t", "interpret")
)
def hetero_fuse_coeffs(
    preds: Array,     # (K, B, T) native predictions of the routed slots
    x_t: Array,       # (B, T)
    weights: Array,   # (B, K) fusion weights (rows sum to 1)
    coef: Array,      # (5, K, B) unified (alpha, sigma, dalpha, dsigma, vscale)
    *,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
    block_t: int = 1024,
    interpret: bool = False,
) -> Array:
    """Per-row scalars (weights, coefficients) enter the kernel as
    ``(..., B, 1)`` columns, so every block's last two dims are a
    ``(rows, lanes)`` tile the TPU lowering accepts."""
    k, b, t = preds.shape
    block_t = min(block_t, t)
    assert t % block_t == 0
    bb = _row_block(b)
    kernel = functools.partial(
        _fuse_coeffs_kernel, clamp=clamp, alpha_min=alpha_min
    )
    return pl.pallas_call(
        kernel,
        grid=(b // bb, t // block_t),
        in_specs=[
            pl.BlockSpec((k, bb, block_t), lambda bi, ti: (0, bi, ti)),
            pl.BlockSpec((bb, block_t), lambda bi, ti: (bi, ti)),
            pl.BlockSpec((k, bb, 1), lambda bi, ti: (0, bi, 0)),
            pl.BlockSpec((5, k, bb, 1), lambda bi, ti: (0, 0, bi, 0)),
        ],
        out_specs=pl.BlockSpec((bb, block_t), lambda bi, ti: (bi, ti)),
        out_shape=jax.ShapeDtypeStruct((b, t), preds.dtype),
        interpret=interpret,
        name="hetero_fuse_coeffs",
    )(preds, x_t, jnp.swapaxes(weights, 0, 1)[..., None],
      coef.astype(jnp.float32)[..., None])


def _fuse_step_kernel(
    preds_ref, xt_ref, w_ref, coef_ref, dt_ref, o_ref,
    *, cfg_scale: float, clamp: float, alpha_min: float,
):
    preds = preds_ref[...].astype(jnp.float32)        # (K, G, bb, bt)
    xt = xt_ref[...].astype(jnp.float32)              # (bb, bt)
    w = w_ref[...].astype(jnp.float32)                # (K, G, bb, 1)
    coef = coef_ref[...].astype(jnp.float32)          # (5, K, G, bb, 1)
    dt = dt_ref[...].astype(jnp.float32)              # (bb, 1) or (1, 1)
    g = preds.shape[1]
    alpha, sigma, dalpha, dsigma, vscale = (
        coef[0], coef[1], coef[2], coef[3], coef[4]
    )                                                 # each (K, G, bb, 1)

    a_safe = jnp.maximum(alpha, alpha_min)
    x0h = (xt[None, None] - sigma * preds) / a_safe
    x0h = jnp.clip(x0h, -clamp, clamp)
    v = (dalpha * x0h + dsigma * preds) * vscale
    fused = jnp.sum(w * v, axis=0)                    # (G, bb, bt)
    if g == 1:
        u = fused[0]
    else:
        # branch 0 = cond, branch 1 = uncond: u_u + s (u_c − u_u)
        u = fused[1] + cfg_scale * (fused[0] - fused[1])
    o_ref[...] = (xt - u * dt).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cfg_scale", "clamp", "alpha_min", "block_t",
                     "interpret"),
)
def hetero_fuse_step(
    preds: Array,     # (K, G, B, T) per-branch routed-slot predictions
    x_t: Array,       # (B, T) current latent
    weights: Array,   # (G, B, K) fusion weights per guidance branch
    coef: Array,      # (5, K, G, B) unified coefficient stack
    dt: Array,        # (1,) shared or (B,) per-row Euler step size (traced)
    *,
    cfg_scale: float = 1.0,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
    block_t: int = 1024,
    interpret: bool = False,
) -> Array:
    """Step-fused serving hot path: convert + fuse + CFG + Euler in one
    kernel launch.

    Extends :func:`hetero_fuse_coeffs` by folding the classifier-free
    guidance combine across the ``G`` branch axis (branch 0 = cond,
    branch 1 = uncond; ``G = 1`` skips it) and the Euler update
    ``x ← x − u·dt`` into the same kernel, so per sampling step the
    latent is read once and the updated latent written once — instead of
    the three latent-sized HBM round-trips of the unfused
    ``fused_velocity → cfg_combine → x − u·dt`` op chain.

    ``dt`` is either the classic batch-shared ``(1,)`` step size or a
    per-row ``(B,)`` vector — the mixed-timestep rolling-batch case,
    where each resident request sits at its own step of the schedule
    grid.  Only the BlockSpec index map differs (row block ``bi`` reads
    its own rows instead of the one shared entry); the kernel body is
    identical, so the per-row form is bitwise equal to the scalar form
    whenever the rows agree.

    Grid ``(B / bb, T / block_t)`` with ``bb`` 8 rows or the whole batch;
    the per-row scalars (weights, coefficients, dt) ride along as
    ``(..., B, 1)`` columns blocked like the rows they scale.
    """
    k, g, b, t = preds.shape
    block_t = min(block_t, t)
    assert t % block_t == 0
    assert dt.shape[0] in (1, b), dt.shape
    bb = _row_block(b)
    dt_spec = (
        pl.BlockSpec((bb, 1), lambda bi, ti: (bi, 0))
        if dt.shape[0] == b
        else pl.BlockSpec((1, 1), lambda bi, ti: (0, 0))
    )
    kernel = functools.partial(
        _fuse_step_kernel,
        cfg_scale=cfg_scale, clamp=clamp, alpha_min=alpha_min,
    )
    return pl.pallas_call(
        kernel,
        grid=(b // bb, t // block_t),
        in_specs=[
            pl.BlockSpec((k, g, bb, block_t),
                         lambda bi, ti: (0, 0, bi, ti)),
            pl.BlockSpec((bb, block_t), lambda bi, ti: (bi, ti)),
            pl.BlockSpec((k, g, bb, 1), lambda bi, ti: (0, 0, bi, 0)),
            pl.BlockSpec((5, k, g, bb, 1),
                         lambda bi, ti: (0, 0, 0, bi, 0)),
            dt_spec,
        ],
        out_specs=pl.BlockSpec((bb, block_t), lambda bi, ti: (bi, ti)),
        out_shape=jax.ShapeDtypeStruct((b, t), x_t.dtype),
        interpret=interpret,
        name="hetero_fuse_step",
    )(preds, x_t, jnp.moveaxis(weights, 2, 0)[..., None],
      coef.astype(jnp.float32)[..., None], dt.reshape(-1, 1))


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                # (R, bt)
    s = s_ref[...].astype(jnp.float32)                # (R, 1) row scales
    o_ref[...] = (q * s).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "block_t", "interpret")
)
def hetero_fuse_dequant(
    q: Array,         # (R, T) quantized values (int8 / float8_e4m3fn)
    scale: Array,     # (R,) symmetric per-row scales
    *,
    out_dtype=jnp.float32,
    block_t: int = 1024,
    interpret: bool = False,
) -> Array:
    """Fused ``scale · q`` dequantization of a row-major quantized view.

    Rows are whatever the caller gathered: ``B`` per-sample experts, one
    static expert slice, or the full ``K`` stack (off-hot-path
    materialize).  The scale broadcast happens inside the kernel, so the
    quantized bytes are read once and only the compute-precision result
    is written.  Blocks hold every row (a handful of experts) — whole-axis
    row blocks lower for any row count and any storage dtype's sublane
    packing.
    """
    r, t = q.shape
    block_t = min(block_t, t)
    assert t % block_t == 0
    return pl.pallas_call(
        _dequant_kernel,
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((r, block_t), lambda ti: (0, ti)),
            pl.BlockSpec((r, 1), lambda ti: (0, 0)),
        ],
        out_specs=pl.BlockSpec((r, block_t), lambda ti: (0, ti)),
        out_shape=jax.ShapeDtypeStruct((r, t), out_dtype),
        interpret=interpret,
        name="hetero_fuse_dequant",
    )(q, scale.reshape(r, 1))


@functools.partial(
    jax.jit, static_argnames=("clamp", "alpha_min", "block_t", "interpret")
)
def hetero_fuse(
    preds: Array,     # (K, B, T) native expert predictions
    x_t: Array,       # (B, T)
    weights: Array,   # (B, K) router weights
    is_ddpm: Array,   # (K,) bool
    alpha: Array,     # (K, B)
    sigma: Array,     # (K, B)
    dalpha: Array,    # (K, B)
    dsigma: Array,    # (K, B)
    vscale: Array,    # (K, B)
    *,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
    block_t: int = 1024,
    interpret: bool = False,
) -> Array:
    k, b, t = preds.shape
    block_t = min(block_t, t)
    assert t % block_t == 0
    coef = jnp.stack(
        [alpha, sigma, dalpha, dsigma, vscale], axis=0
    ).astype(jnp.float32)                             # (5, K, B)
    kernel = functools.partial(
        _fuse_kernel, clamp=clamp, alpha_min=alpha_min
    )
    return pl.pallas_call(
        kernel,
        grid=(b, t // block_t),
        in_specs=[
            pl.BlockSpec((k, 1, block_t), lambda bi, ti: (0, bi, ti)),
            pl.BlockSpec((1, block_t), lambda bi, ti: (bi, ti)),
            pl.BlockSpec((1, k), lambda bi, ti: (bi, 0)),
            pl.BlockSpec((k,), lambda bi, ti: (0,)),
            pl.BlockSpec((5, k, 1), lambda bi, ti: (0, 0, bi)),
        ],
        out_specs=pl.BlockSpec((1, block_t), lambda bi, ti: (bi, ti)),
        out_shape=jax.ShapeDtypeStruct((b, t), preds.dtype),
        interpret=interpret,
    )(preds, x_t, weights, is_ddpm, coef)
