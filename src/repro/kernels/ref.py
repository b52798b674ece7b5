"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each ``<name>`` kernel in this package has a ``ref_<name>`` here with the
exact same signature; tests sweep shapes/dtypes and assert_allclose.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Array = jax.Array


def ref_flash_attention(
    q: Array, k: Array, v: Array, *, causal: bool = True,
    window: int = 0, softmax_scale: float | None = None,
) -> Array:
    """Oracle attention.  q/k/v: (B, H, S, D) (kernel layout)."""
    b, h, s, d = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window:
        mask = mask & (qpos - kpos < window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ref_ssd_scan(x: Array, dt: Array, A: Array, B: Array, C: Array):
    """Oracle SSD recurrence — delegates to the sequential reference.

    Matches the kernel's contract exactly: the Pallas ``ssd_scan`` always
    starts from a zero state, so the oracle takes no ``init_state``
    (resumable-state scans go through ``models.mamba2.ssd_sequential``
    directly).
    """
    from repro.models.mamba2 import ssd_sequential

    return ssd_sequential(x, dt, A, B, C)


def ref_adaln_fuse(
    x: Array, gamma: Array, beta: Array, eps: float = 1e-6
) -> Array:
    """Oracle for fused LN-modulate: LN(x)·(1+γ)+β (Eqs. 17/19 inner op).

    x: (B, S, D); gamma/beta: (B, D).
    """
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = y * (1.0 + gamma[:, None].astype(jnp.float32)) + beta[
        :, None
    ].astype(jnp.float32)
    return out.astype(x.dtype)


def ref_hetero_fuse(
    preds: Array,        # (K, B, T) native expert predictions (flattened)
    x_t: Array,          # (B, T)
    weights: Array,      # (B, K) router weights
    is_ddpm: Array,      # (K,) bool — needs ε→v conversion
    alpha: Array,        # (K, B) schedule coeff per expert/sample
    sigma: Array,        # (K, B)
    dalpha: Array,       # (K, B)
    dsigma: Array,       # (K, B)
    vscale: Array,       # (K, B) Eq. 31 dampening (1.0 for FM experts)
    *,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
) -> Array:
    """Oracle for the fused convert-and-fuse inference op (paper Fig. 2).

    For DDPM experts: x̂0 = clip((x_t - σ ε)/max(α, α_min)); v = α'x̂0 + σ'ε,
    scaled by vscale.  FM experts pass through.  Output: Σ_k w_k v_k.
    """
    K = preds.shape[0]
    a = jnp.maximum(alpha, alpha_min)[..., None]
    x0h = (x_t[None] - sigma[..., None] * preds) / a
    x0h = jnp.clip(x0h, -clamp, clamp)
    v_conv = (dalpha[..., None] * x0h + dsigma[..., None] * preds) * vscale[
        ..., None
    ]
    v = jnp.where(is_ddpm[:, None, None], v_conv, preds)
    w = jnp.moveaxis(weights, -1, 0)[..., None]            # (K, B, 1)
    return jnp.sum(w * v, axis=0)


def ref_hetero_fuse_dequant(
    q: Array,            # (R, T) quantized values (int8 / float8_e4m3fn)
    scale: Array,        # (R,) symmetric per-row scales
    *,
    out_dtype=jnp.float32,
) -> Array:
    """Oracle for the fused ``scale · q`` dequantization op.

    ``out_dtype`` mirrors the kernel's output-cast knob: the multiply
    always runs in float32, the cast is the last op — same as the Pallas
    path, so mixed-precision parity tests compare like against like.
    """
    out = q.astype(jnp.float32) * scale.astype(jnp.float32)[:, None]
    return out.astype(out_dtype)


def ref_hetero_fuse_step(
    preds: Array,        # (K, G, B, T) per-branch routed-slot predictions
    x_t: Array,          # (B, T)
    weights: Array,      # (G, B, K) fusion weights per guidance branch
    coef: Array,         # (5, K, G, B) unified coefficient stack
    dt: Array,           # (1,) shared or (B,) per-row Euler step size
    *,
    cfg_scale: float = 1.0,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
) -> Array:
    """Oracle for the step-fused convert+CFG+Euler hot-path op.

    Folds the whole per-step latent update into one op: per-branch
    convert-and-fuse (exactly :func:`ref_hetero_fuse_coeffs` over the
    branch-major flattened batch), the CFG combine
    ``u = u_u + s (u_c − u_u)`` (branch 0 = cond, branch 1 = uncond; a
    single branch skips the combine), and the Euler update
    ``x ← x − u·dt``.  Delegating the fuse to the coeffs oracle keeps
    this numerically identical to the unfused three-op path.

    ``dt`` may be the classic batch-shared ``(1,)`` scalar or a per-row
    ``(B,)`` vector (mixed-timestep rolling batches, where each request
    sits at its own step of the schedule grid); both forms broadcast
    elementwise over the latent row, so a ``(B,)`` dt whose entries all
    equal the scalar is bitwise identical to the scalar form.
    """
    k, g, b, t = preds.shape
    fused = ref_hetero_fuse_coeffs(
        preds.reshape(k, g * b, t),
        jnp.concatenate([x_t] * g, axis=0),
        weights.reshape(g * b, k),
        coef.reshape(5, k, g * b),
        clamp=clamp, alpha_min=alpha_min,
    )                                                      # (G·B, T)
    if g == 1:
        u = fused
    else:
        u = fused[b:] + cfg_scale * (fused[:b] - fused[b:])
    return x_t - u * jnp.asarray(dt, jnp.float32).reshape(-1, 1)


def ref_ragged_gemm(
    x: Array,                 # (M, D) expert-sorted rows
    w: Array,                 # (K, D, F) stacked expert weights
    tile_experts: Array,      # (M // block_m,) int32 expert id per row tile
    x_scale: Array | None = None,   # (M,) per-row activation scales
    w_scale: Array | None = None,   # (K,) per-expert weight scales
    *,
    tile_live: Array | None = None,  # (M // block_m,) int32, 0 = dead tile
) -> Array:
    """Oracle for the ragged grouped expert GEMM with fused dequant.

    ``tile_experts`` carries one expert id per ``block_m`` row tile; the
    oracle recovers the per-row expert map by even division (the kernel
    wrapper guarantees tile-aligned single-expert row groups).  Dense
    operands contract in float32.  Quantized operands mirror the
    kernel's MXU contract exactly: int8×int8 accumulates in int32 (bit-
    exact integers) and fp8×fp8 in float32, then the dequant epilogue
    applies ``x_scale[row] · w_scale[expert]`` in float32 — the same
    multiply order as the kernel, so the int8 path is bitwise
    comparable.  Rows of a dead tile (``tile_live`` 0) are zero.  Output
    is float32 ``(M, F)``.
    """
    m = x.shape[0]
    gm = tile_experts.shape[0]
    bm = m // gm
    row_e = jnp.repeat(tile_experts.astype(jnp.int32), bm)
    wr = w[row_e]                                          # (M, D, F)
    if w.dtype == jnp.int8:
        acc = jnp.einsum(
            "md,mdf->mf", x.astype(jnp.int32), wr.astype(jnp.int32)
        )
    else:
        acc = jnp.einsum(
            "md,mdf->mf", x.astype(jnp.float32), wr.astype(jnp.float32),
        )
    out = acc.astype(jnp.float32)
    if x_scale is not None and w_scale is not None:
        out = (out * x_scale.astype(jnp.float32)[:, None]) \
            * w_scale.astype(jnp.float32)[row_e][:, None]
    if tile_live is not None:
        out = jnp.where(jnp.repeat(tile_live != 0, bm)[:, None], out, 0.0)
    return out


def ref_hetero_fuse_coeffs(
    preds: Array,        # (K, B, T) native predictions of the routed slots
    x_t: Array,          # (B, T)
    weights: Array,      # (B, K) fusion weights
    coef: Array,         # (5, K, B) unified coefficient stack
    *,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
) -> Array:
    """Oracle for the coefficient-folded convert-and-fuse hot-path op.

    FM slots carry the identity coefficients (1, 0, 0, 1, 1), under which
    ``v = 0·x̂0 + 1·pred`` — exact pass-through without a flag select.
    """
    coef = coef.astype(jnp.float32)
    alpha, sigma, dalpha, dsigma, vscale = (
        coef[0], coef[1], coef[2], coef[3], coef[4]
    )                                                      # each (K, B)
    a = jnp.maximum(alpha, alpha_min)[..., None]
    x0h = (x_t[None] - sigma[..., None] * preds) / a
    x0h = jnp.clip(x0h, -clamp, clamp)
    v = (dalpha[..., None] * x0h + dsigma[..., None] * preds) * vscale[
        ..., None
    ]
    w = jnp.moveaxis(weights, -1, 0)[..., None]            # (K, B, 1)
    return jnp.sum(w * v, axis=0)
