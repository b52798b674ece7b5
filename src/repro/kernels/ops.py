"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU backends the compiled Pallas kernels always run
natively.  Elsewhere the pure-jnp oracles run by default, and
``REPRO_FORCE_PALLAS=1`` runs the same kernel bodies under
``interpret=True`` instead (the kernel-parity tests).  ``use_pallas()`` is
the single switch; nothing turns the kernels off on a TPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.conversion import ConversionConfig, velocity_scale
from repro.core.schedules import Schedule
from repro.kernels import ref as _ref
from repro.kernels.adaln_fuse import adaln_fuse as _adaln_fuse
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.hetero_fuse import hetero_fuse as _hetero_fuse
from repro.kernels.hetero_fuse import hetero_fuse_coeffs as _hetero_fuse_coeffs
from repro.kernels.hetero_fuse import hetero_fuse_dequant as _hetero_fuse_dequant
from repro.kernels.hetero_fuse import hetero_fuse_step as _hetero_fuse_step
from repro.kernels.ragged_gemm import ragged_gemm as _ragged_gemm
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan

Array = jax.Array


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    return on_tpu() or os.environ.get("REPRO_FORCE_PALLAS") == "1"


def _interpret() -> bool:
    return not on_tpu()


def _launch(kernel, *args):
    """Run one Pallas launch on the ambient mesh.

    Mosaic kernels cannot be partitioned by the compiler.  Inside a
    ``shard_map`` body every mesh axis is manual and the operands are
    already each device's own (the expert-parallel ragged apply,
    ``core.dispatch.RaggedExecutor``, hands its launches the local
    experts' leaves), so the launch is a plain call there.  Elsewhere on
    a multi-device mesh (sharded serving traces its programs under
    ``jax.sharding.use_abstract_mesh``) the launch runs under
    ``shard_map`` with every operand replicated: each device gathers the
    operands and runs the whole launch.  Without a mesh it is a plain
    call.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.are_all_axes_manual:
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


# --- flash attention -------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, window=0, **kw):
    """(B, H, S, D) attention.  Pallas on TPU, interpret elsewhere."""
    if use_pallas():
        return _flash(q, k, v, causal=causal, window=window,
                      interpret=_interpret(), **kw)
    return _ref.ref_flash_attention(q, k, v, causal=causal, window=window)


def flash_attention_gqa(q, k, v, *, causal=True, window=0, **kw):
    """GQA front-end: q (B, Hq, S, D), k/v (B, Hkv, S, D)."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    return flash_attention(q, k, v, causal=causal, window=window, **kw)


# --- SSD scan ---------------------------------------------------------------


def ssd_scan(x, dt, A, B, C, *, chunk=128, **kw):
    """(B, H, S, P) Mamba2 scan.  Pallas on TPU, interpret elsewhere."""
    if use_pallas():
        return _ssd_scan(x, dt, A, B, C, chunk=chunk,
                         interpret=_interpret(), **kw)
    return _ref.ref_ssd_scan(
        jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2), A, B, C
    )[0].swapaxes(1, 2), None


# --- AdaLN fuse --------------------------------------------------------------


def adaln_modulate(x, gamma, beta, *, eps=1e-6, **kw):
    if use_pallas():
        return _adaln_fuse(x, gamma, beta, eps=eps,
                           interpret=_interpret(), **kw)
    return _ref.ref_adaln_fuse(x, gamma, beta, eps=eps)


# --- hetero fuse -------------------------------------------------------------


def fused_velocity(
    preds: Array,             # (K, B, *latent) routed-slot native predictions
    x_t: Array,               # (B, *latent)
    weights: Array,           # (B, K) fusion weights
    coef: Array,              # (5, K, B) unified coefficient stack
    *,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
) -> Array:
    """Hot-path convert-and-fuse with precomputed unified coefficients.

    The serving engine precomputes ``conversion.unified_coeff_tables`` once
    per run and gathers the per-step ``(5, K, B)`` slice (per routed slot
    when execution is compute-sparse); this op then does the entire per-step
    fusion — ε→v conversion + Eq. 1 weighting — in one kernel launch
    (Pallas on TPU, oracle elsewhere).
    """
    k, b = preds.shape[0], preds.shape[1]
    latent_shape = preds.shape[2:]
    tsize = 1
    for s in latent_shape:
        tsize *= s
    pf = preds.reshape(k, b, tsize)
    xf = x_t.reshape(b, tsize)
    if use_pallas():
        out = _launch(functools.partial(
            _hetero_fuse_coeffs,
            clamp=clamp, alpha_min=alpha_min, interpret=_interpret(),
        ), pf, xf, weights, coef)
    else:
        out = _ref.ref_hetero_fuse_coeffs(
            pf, xf, weights, coef, clamp=clamp, alpha_min=alpha_min,
        )
    return out.reshape((b,) + latent_shape)


#: hot-path kernel tile width — multiple of the 128-lane VPU width; rows
#: smaller than one tile pad up to the next 128 multiple instead.
_TILE_BLOCK = 1024


def _tile_pad(t: int) -> tuple[int, int]:
    """Padded row length and block size for a ``t``-wide kernel row.

    Shared padding policy of the row-major hot-path kernels
    (``fused_step``, ``dequant_params``): rows at most one block wide pad
    to the next 128-lane multiple and run as a single block; wider rows
    pad to a whole number of ``_TILE_BLOCK`` tiles.
    """
    if t <= _TILE_BLOCK:
        tp = -(-t // 128) * 128
        return tp, tp
    return -(-t // _TILE_BLOCK) * _TILE_BLOCK, _TILE_BLOCK


def fused_step(
    preds: Array,             # (K, G·B, *latent) per-branch slot predictions
    x_t: Array,               # (B, *latent) current latent
    weights: Array,           # (G·B, K) fusion weights
    coef: Array,              # (5, K, G·B) unified coefficient stack
    dt: Array,                # scalar or (B,) per-row Euler step size (traced)
    *,
    g: int,
    cfg_scale: float = 1.0,
    clamp: float = 20.0,
    alpha_min: float = 0.01,
) -> Array:
    """Step-fused hot path: one kernel for convert + fuse + CFG + Euler.

    Takes the exact :func:`fused_velocity` operands — per-slot native
    predictions over the branch-major ``G·B`` guidance batch (branch 0 =
    cond, branch 1 = uncond), fusion weights, and the per-step ``(5, K,
    G·B)`` coefficient slice — plus the Euler ``dt``, and returns the
    *updated latent* ``x − u·dt`` where ``u`` is the CFG-combined fused
    velocity.  The latent is read once and written once per step instead
    of round-tripping through HBM for each of the three unfused ops.
    Non-tile-aligned latents pad up to the kernel tile width (padded
    rows are self-contained zeros and are sliced away).  Pallas
    (``hetero_fuse_step``) on TPU, oracle elsewhere — the oracle
    delegates to ``ref_hetero_fuse_coeffs``, keeping the fused step
    bit-identical to the unfused op chain on the reference path.

    ``dt`` is either the classic batch-shared scalar or a per-row
    ``(B,)`` vector (mixed-timestep rolling batches); a per-row dt whose
    entries equal the scalar is bitwise identical to the scalar form on
    both dispatch paths.
    """
    with jax.named_scope("fused_step"):
        k = preds.shape[0]
        b = x_t.shape[0]
        latent_shape = x_t.shape[1:]
        tsize = 1
        for s in latent_shape:
            tsize *= s
        pf = preds.reshape(k, g, b, tsize)
        xf = x_t.reshape(b, tsize)
        wf = weights.reshape(g, b, k)
        cf = coef.reshape(5, k, g, b)
        dt = jnp.asarray(dt, jnp.float32).reshape(-1)
        assert dt.shape[0] in (1, b), dt.shape
        if use_pallas():
            t = tsize
            tp, block = _tile_pad(t)
            if tp != t:
                pad = ((0, 0), (0, 0), (0, 0), (0, tp - t))
                pf = jnp.pad(pf, pad)
                xf = jnp.pad(xf, ((0, 0), (0, tp - t)))
            out = _launch(functools.partial(
                _hetero_fuse_step,
                cfg_scale=cfg_scale, clamp=clamp, alpha_min=alpha_min,
                block_t=block, interpret=_interpret(),
            ), pf, xf, wf, cf, dt)[:, :t]
        else:
            out = _ref.ref_hetero_fuse_step(
                pf, xf, wf, cf, dt,
                cfg_scale=cfg_scale, clamp=clamp, alpha_min=alpha_min,
            )
        return out.reshape((b,) + latent_shape)


def dequant_params(
    q: Array,                 # (R, ...) quantized leaf view (int8 / fp8)
    scale: Array,             # (R,) symmetric per-row scales
    *,
    out_dtype=jnp.float32,
) -> Array:
    """Fused ``scale · q`` dequantization of a gathered/sliced param leaf.

    The hot-path expansion step for ``core.param_store.QuantizedStore``:
    rows are whatever was gathered (per-sample experts, a static expert
    slice, or the full stack for off-path ``materialize``); trailing dims
    flatten into the kernel's tile axis and pad up to the tile width.
    Pallas (``hetero_fuse_dequant``) on TPU, oracle elsewhere.
    """
    q = jnp.asarray(q)
    rows = q.shape[0]
    trailing = q.shape[1:]
    qf = q.reshape(rows, -1) if trailing else q.reshape(rows, 1)
    t = qf.shape[1]
    if use_pallas():
        tp, block = _tile_pad(t)
        if tp != t:
            qf = jnp.pad(qf, ((0, 0), (0, tp - t)))
        out = _launch(functools.partial(
            _hetero_fuse_dequant, out_dtype=out_dtype, block_t=block,
            interpret=_interpret(),
        ), qf, scale)[:, :t]
    else:
        out = _ref.ref_hetero_fuse_dequant(qf, scale, out_dtype=out_dtype)
    return out.reshape((rows,) + trailing)


#: max rows per ragged-GEMM tile — whole per-group row blocks halve down
#: to at most this many rows so tiles stay VMEM-friendly.
_RAGGED_BLOCK_M = 256

#: VMEM one ragged-GEMM grid step may hold.  The default scoped-VMEM
#: limit of a v5e kernel is 16 MiB; the rest is left to Mosaic's own
#: scratch.  Deep contractions (the MLP down-projection, 3072→768 at
#: dit-b2 widths) overflow it with a whole-width output tile.
_RAGGED_VMEM_BUDGET = 12 * 2**20


def ragged_block_m(m: int) -> int | None:
    """Row-tile size for a ragged GEMM whose row groups are ``m`` wide.

    Every tile must be single-expert, so the block must divide the
    per-group row count exactly; groups narrower than the 8-row TPU
    sublane (or with an odd factor that cannot halve under the cap)
    return ``None`` — the wrapper then takes the dense-math fallback.
    """
    if m <= 0 or m % 8:
        return None
    bm = m
    while bm > _RAGGED_BLOCK_M:
        if bm % 2:
            return None
        bm //= 2
    return bm


def _ragged_step_bytes(bm: int, d: int, bf: int, x_bytes: int,
                       w_bytes: int, quantized: bool) -> int:
    """VMEM held by one ragged-GEMM grid step: the double-buffered x, w
    and out blocks, the f32 accumulator and epilogue, and (dense body
    only) the f32 upcasts of operands stored narrower than f32."""
    held = 2 * (bm * d * x_bytes + d * bf * w_bytes + bm * bf * 4)
    held += 2 * bm * bf * 4
    if not quantized:
        held += (x_bytes < 4) * bm * d * 4 + (w_bytes < 4) * d * bf * 4
    return held


def ragged_tiles(m: int, d: int, f: int, x_bytes: int, w_bytes: int,
                 quantized: bool) -> tuple[int, int, int] | None:
    """``(block_m, padded F, block_f)`` for a ragged GEMM, or ``None``.

    Rows start from :func:`ragged_block_m`.  Output lanes pad only to
    the next lane multiple (the lane width from :func:`_tile_pad`), not
    to whole ``_TILE_BLOCK`` tiles: the grid tiles F by any lane-multiple
    block, so a lane-aligned width (1152 = 9 lanes at DiT-XL/2) runs
    unpadded, with no padded columns to multiply and no per-call pad of
    the weight leaf.  The block is the widest lane-multiple divisor of
    the padded width (at most ``_TILE_BLOCK``) whose grid step fits
    ``_RAGGED_VMEM_BUDGET`` at contraction depth ``d``.  When even a
    one-lane-tile block overflows, the row block halves (staying a
    multiple of 8).  ``None`` — row groups that cannot tile — sends the
    caller to the dense-math fallback.
    """
    bm = ragged_block_m(m)
    if bm is None:
        return None
    lane, _ = _tile_pad(1)
    fp = -(-f // lane) * lane
    while True:
        for bf in range(min(fp, _TILE_BLOCK), 0, -lane):
            if fp % bf == 0 and _ragged_step_bytes(
                bm, d, bf, x_bytes, w_bytes, quantized
            ) <= _RAGGED_VMEM_BUDGET:
                return bm, fp, bf
        if bm % 16:
            return None
        bm //= 2


def ragged_expert_matmul(
    x: Array,                 # (P, ..., D) per-group activations
    w: Array,                 # (K, D, F) stacked expert weights (or quant)
    expert_ids: Array,        # (P,) int32 expert per row group
    *,
    bias: Array | None = None,       # (K, F) stacked bias, optional
    w_scale: Array | None = None,    # (K,) per-expert scales (quant only)
    live: Array | None = None,       # (P,) bool, False = group not needed
) -> Array:
    """Grouped expert dense: ``y[p] = x[p] @ w[expert_ids[p]] (+ bias)``.

    The executor-facing ragged GEMM seam (``dispatch='ragged'``): ``x``
    carries ``P`` expert-sorted row groups (one per routed sample×slot
    pair, each ``m = prod(middle dims)`` rows wide), and every group
    contracts against its own expert's stacked leaf — all experts in
    one op, empty segments costing nothing.

    On the Pallas path the groups flatten to ``(P·m, D)`` tile-aligned
    rows for :func:`repro.kernels.ragged_gemm.ragged_gemm` (tiles from
    :func:`ragged_tiles`; a width that is not a lane multiple pads its
    weight leaf and slices the output back, a lane-aligned one neither
    pads nor slices); quantized
    weights (int8 / fp8, with ``w_scale``) keep their storage dtype all
    the way to the MXU — activations quantize per row symmetrically to
    the same storage format and the kernel fuses the
    ``x_scale·w_scale`` dequant epilogue.  Off-TPU (and for row groups
    too narrow to tile) the same contraction runs as dense jnp math:
    small groups take one all-experts GEMM plus a column select, wide
    groups a per-group gathered einsum; quantized leaves dequantize
    with the exact ``hetero_fuse_dequant`` float32 multiply first, so
    the fallback is bitwise-consistent with the grouped backend's
    store-dequant path.  Output is float32 ``(P, ..., F)``.

    ``live`` marks the groups whose rows are read (on an expert mesh,
    the pairs this shard owns); the other groups' rows are not
    meaningful.  On the Pallas path a dead group's row tiles skip the
    MXU and their input DMAs (``ragged_gemm``'s ``tile_live``) and come
    out zero before the bias.  The fallback computes every group, as
    without ``live``: a select on its output would change how XLA fuses
    the ops around it, and with that the rounding of the live rows.  On
    both paths live groups are bitwise what the call without ``live``
    gives.
    """
    p = x.shape[0]
    d = x.shape[-1]
    mids = x.shape[1:-1]
    m = 1
    for s in mids:
        m *= s
    kx, dw, f = w.shape
    is_int8 = w.dtype == jnp.int8
    is_fp8 = w.dtype == jnp.float8_e4m3fn
    quantized = is_int8 or is_fp8
    if quantized and w_scale is None:
        raise ValueError("quantized ragged_expert_matmul needs w_scale")
    expert_ids = expert_ids.astype(jnp.int32)

    x_bytes = w.dtype.itemsize if quantized else x.dtype.itemsize
    tiles = ragged_tiles(m, d, f, x_bytes, w.dtype.itemsize, quantized)
    if use_pallas() and tiles is not None:
        bm, fp, bf = tiles
        xf = x.reshape(p * m, d)
        wp = w
        if fp != f:
            with jax.named_scope("layer_weights"):
                wp = jnp.pad(w, ((0, 0), (0, 0), (0, fp - f)))
        tile_e = jnp.repeat(expert_ids, m // bm)
        kernel = functools.partial(
            _ragged_gemm, block_m=bm, block_f=bf, interpret=_interpret(),
        )
        if quantized:
            x32 = xf.astype(jnp.float32)
            qmax = 127.0 if is_int8 else 448.0
            xs = jnp.maximum(jnp.max(jnp.abs(x32), axis=1), 1e-12) / qmax
            xq = x32 / xs[:, None]
            if is_int8:
                xq = jnp.clip(jnp.round(xq), -127, 127).astype(jnp.int8)
            else:
                xq = xq.astype(jnp.float8_e4m3fn)
            operands = (xq, wp, tile_e, xs, w_scale)
        else:
            operands = (xf, wp, tile_e)
        if live is None:
            y = _launch(kernel, *operands)
        else:
            tile_live = jnp.repeat(live.astype(jnp.int32), m // bm)
            y = _launch(lambda tl, *a: kernel(*a, tile_live=tl),
                        tile_live, *operands)
        if fp != f:
            y = y[:, :f]
        y = y.reshape((p,) + mids + (f,))
    else:
        if quantized:
            wd = w.astype(jnp.float32) * w_scale.astype(jnp.float32).reshape(
                (kx,) + (1,) * (w.ndim - 1)
            )
        else:
            wd = w
        mtot = p * m
        if m <= 4:
            # few rows per group: one GEMM against every expert's leaf,
            # then select each group's expert column block.
            y_all = x.reshape(mtot, d) @ jnp.moveaxis(wd, 0, 1).reshape(
                d, kx * f
            )
            y_all = y_all.reshape(x.shape[:-1] + (kx, f))
            e = expert_ids.reshape((p,) + (1,) * (x.ndim - 1))
            y = jnp.take_along_axis(
                y_all,
                jnp.broadcast_to(e[..., None], y_all.shape[:-2] + (1, f)),
                axis=-2,
            )[..., 0, :]
        else:
            y = jnp.einsum("p...d,pdf->p...f", x, wd[expert_ids])
    if bias is not None:
        y = y + bias[expert_ids].reshape(
            (p,) + (1,) * (x.ndim - 2) + (-1,)
        )
    return y


def fused_convert_and_fuse(
    preds: Array,             # (K, B, *latent) native predictions
    x_t: Array,               # (B, *latent)
    weights: Array,           # (B, K)
    objectives: list[str],    # per-expert 'ddpm' | 'fm'
    schedules: list[Schedule],
    t: Array,                 # (B,) native time
    conv: ConversionConfig = ConversionConfig(),
) -> Array:
    """High-level entry: computes per-expert schedule coefficients on host
    trace, then runs the fused kernel (or its oracle) over flattened
    latents.  This is the per-step fusion op of Fig. 2."""
    k, b = preds.shape[0], preds.shape[1]
    latent_shape = preds.shape[2:]
    tsize = 1
    for s in latent_shape:
        tsize *= s

    alpha = jnp.stack([s.alpha(t) for s in schedules])        # (K, B)
    sigma = jnp.stack([s.sigma(t) for s in schedules])
    if conv.derivative_mode == "fd":
        d = [s.fd_derivs(t) for s in schedules]
    else:
        d = [s.derivs(t) for s in schedules]
    dalpha = jnp.stack([x[0] for x in d])
    dsigma = jnp.stack([x[1] for x in d])
    is_ddpm = jnp.array([o == "ddpm" for o in objectives])
    vs = velocity_scale(t, conv.velocity_scaling)             # (B,)
    vscale = jnp.where(is_ddpm[:, None], vs[None], 1.0)

    pf = preds.reshape(k, b, tsize)
    xf = x_t.reshape(b, tsize)
    args = (pf, xf, weights, is_ddpm, alpha, sigma, dalpha, dsigma, vscale)
    kwargs = dict(clamp=conv.clamp, alpha_min=conv.alpha_min)
    if use_pallas():
        out = _hetero_fuse(*args, interpret=_interpret(), **kwargs)
    else:
        out = _ref.ref_hetero_fuse(*args, **kwargs)
    return out.reshape((b,) + latent_shape)
