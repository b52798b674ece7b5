"""Ragged grouped expert GEMM with fused dequant (ROADMAP perf item 1).

One Pallas launch runs the block-diagonal matmul of every resident
expert's contiguous row segment against that expert's stacked weight
leaf — the Megablocks-style grouped-GEMM economy, applied to the
``DispatchPlan``'s expert-sorted row layout:

* the grid iterates over ``(row-tile, out-tile)`` pairs of the *actual*
  row count, so an expert with an empty segment (or a dead validity
  slot, which routing never selects) contributes **zero grid steps** —
  there is no per-expert branch, no power-of-two bucket padding;
* each row tile is single-expert by construction (the ``ops`` wrapper
  derives tiles from the plan's pair-major segments) and its expert id
  is scalar-prefetched, so the tile's weight block DMA reads the stacked
  leaf ``w[e]`` directly — no gather, no materialized per-row weights;
* quantized stores skip materialization entirely: int8 operands contract
  on the MXU with ``preferred_element_type=int32`` (fp8 with float32
  accumulation) and the ``hetero_fuse_dequant`` scale multiply is folded
  into the epilogue — ``acc · x_scale[row] · w_scale[e]`` — so
  quantization buys compute, not just resident bytes.

Tile geometry (``block_m`` rows × ``block_f`` output lanes, full-depth
contraction) is decided by the ``ops.ragged_expert_matmul`` wrapper
(``ops.ragged_tiles``: F pads only to the next 128-lane multiple, since
``block_f`` may be any lane-multiple divisor of the width, so a
lane-aligned layer runs unpadded; plus a VMEM budget that counts the
contraction depth); this module never hard-codes lane arithmetic.
``debug=True`` adds a per-grid-step tile counter output so tests can
*measure* that empty segments cost zero tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _dense_body(e_ref, x_ref, w_ref, o_ref, *cnt, i=None):
    del e_ref, i                    # expert id consumed by the index map
    o_ref[...] = jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if cnt:
        cnt[0][...] = jnp.ones_like(cnt[0])


def _quant_body(acc_dtype, e_ref, ws_ref, x_ref, xs_ref, w_ref, o_ref,
                *cnt, i=None):
    if i is None:
        i = pl.program_id(0)
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )
    e = e_ref[i]
    o_ref[...] = (
        acc.astype(jnp.float32)
        * xs_ref[...].astype(jnp.float32)
    ) * ws_ref[e].astype(jnp.float32)
    if cnt:
        cnt[0][...] = jnp.ones_like(cnt[0])


def _live_tiles_only(body, n_prefetch, n_out, *refs):
    """``body`` on a live row tile; a dead one writes zero blocks and does
    no MXU work.  ``refs`` are ``body``'s scalar-prefetch refs, then the
    masked launch's two (``src``, ``col``; a tile is live where ``col``
    is negative), then ``body``'s input and output refs.  The row tile's
    index is read here, outside the branch, as interpret mode requires."""
    col_ref = refs[n_prefetch + 1]
    rest = refs[n_prefetch + 2:]
    i = pl.program_id(0)
    live = col_ref[i] < 0

    @pl.when(live)
    def _():
        body(*refs[:n_prefetch], *rest, i=i)

    @pl.when(jnp.logical_not(live))
    def _():
        for o in rest[len(rest) - n_out:]:
            o[...] = jnp.zeros(o.shape, o.dtype)


def _dead_tile_sources(tile_live: Array, gf: int) -> tuple[Array, Array]:
    """``(src, col)`` per row tile: the row tile and output-lane tile whose
    input blocks a tile reads.  A live tile reads its own row block at
    every lane tile (``src = i``, ``col = -1``).  A dead tile re-points at
    blocks the pipeline already holds in VMEM, so it fetches nothing:
    those of the last live tile before it at that tile's last lane tile,
    or, before the first live tile, those of the first live tile at lane
    tile 0, which that tile then reads without a fetch of its own."""
    live = tile_live != 0
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    src = jnp.where(last >= 0, last, first)
    col = jnp.where(live, -1, jnp.where(last >= 0, gf - 1, 0))
    return src, col.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_f", "interpret", "debug"),
)
def ragged_gemm(
    x: Array,                 # (M, D) expert-sorted rows (f32/bf16 or q)
    w: Array,                 # (K, D, F) stacked expert weights
    tile_experts: Array,      # (M // block_m,) int32 expert id per row tile
    x_scale: Array | None = None,   # (M,) per-row act scales (quant only)
    w_scale: Array | None = None,   # (K,) per-expert weight scales
    *,
    tile_live: Array | None = None,  # (M // block_m,) int32, 0 = dead tile
    block_m: int,
    block_f: int,
    interpret: bool = False,
    debug: bool = False,
):
    """One-launch ragged grouped GEMM: ``y[r] = x[r] @ w[e(r)]``.

    Rows arrive expert-sorted and tile-aligned (every ``block_m`` row
    tile belongs to one expert — ``tile_experts[i]``); the grid is
    ``(M/block_m, F/block_f)`` so work scales with actual rows, never
    with the expert count.  Dense operands contract in float32.  int8
    operands contract as int8×int8→int32 and fp8 as fp8×fp8→f32 (MXU
    native), then the fused dequant epilogue applies
    ``x_scale[row] · w_scale[expert]``.  Output is float32 ``(M, F)``.

    ``tile_live`` marks row tiles whose rows nobody reads (the pairs
    another shard owns, on an expert mesh): a dead tile's output is
    exactly zero, it runs no ``dot``, and its input blocks point at
    blocks already in VMEM (:func:`_dead_tile_sources`), so it costs no
    DMA either.  Live tiles are bitwise what the unmasked launch gives.
    Without it the launch has no liveness operand and no branch.

    ``debug=True`` returns ``(y, tiles)`` where ``tiles`` is an
    ``(M/block_m, F/block_f)`` int32 map with a 1 per grid step that ran
    the contraction — the runtime proof that empty segments and dead
    tiles cost zero tiles.  Each grid step writes a whole ``(8, 128)``
    counter tile (the smallest int32 block the TPU lowering accepts);
    the map is its corners.
    """
    m, d = x.shape
    k_cap, dw, f = w.shape
    if dw != d:
        raise ValueError(f"contraction mismatch: x depth {d}, w depth {dw}")
    if m % block_m or f % block_f:
        raise ValueError(
            f"rows/lanes must be tile-aligned: ({m}, {f}) vs "
            f"block ({block_m}, {block_f})"
        )
    gm, gf = m // block_m, f // block_f
    if tile_experts.shape != (gm,):
        raise ValueError(
            f"tile_experts must be ({gm},), got {tile_experts.shape}"
        )
    is_int8 = w.dtype == jnp.int8
    is_fp8 = w.dtype == jnp.float8_e4m3fn
    quantized = is_int8 or is_fp8

    out_shape = [jax.ShapeDtypeStruct((m, f), jnp.float32)]
    out_specs = [
        pl.BlockSpec((block_m, block_f), lambda i, j, *pf: (i, j))
    ]
    if debug:
        out_shape.append(
            jax.ShapeDtypeStruct((gm * 8, gf * 128), jnp.int32)
        )
        out_specs.append(pl.BlockSpec((8, 128), lambda i, j, *pf: (i, j)))

    prefetch = [tile_experts.astype(jnp.int32)]
    operands = [x]
    if quantized:
        if x.dtype != w.dtype:
            raise ValueError(
                f"quantized ragged GEMM needs matching operand storage "
                f"dtypes, got x={x.dtype} w={w.dtype}"
            )
        if x_scale is None or w_scale is None:
            raise ValueError("quantized ragged GEMM needs x_scale + w_scale")
        prefetch.append(w_scale.astype(jnp.float32))
        operands.append(x_scale.astype(jnp.float32).reshape(m, 1))
        body = functools.partial(
            _quant_body, jnp.int32 if is_int8 else jnp.float32
        )
    else:
        body = _dense_body
    operands.append(w)

    def row_block(i, j, *pf):
        return i, 0

    def weight_block(i, j, e, *pf):
        return e[i], 0, j

    if tile_live is not None:
        if tile_live.shape != (gm,):
            raise ValueError(
                f"tile_live must be ({gm},), got {tile_live.shape}"
            )
        src, col = _dead_tile_sources(tile_live, gf)
        body = functools.partial(_live_tiles_only, body, len(prefetch),
                                 len(out_shape))
        prefetch = [prefetch[0][src]] + prefetch[1:] + [src, col]

        def row_block(i, j, *pf):
            return pf[-2][i], 0

        def weight_block(i, j, e, *pf):
            c = pf[-1][i]
            return e[i], 0, jnp.where(c < 0, j, c)

    in_specs = [pl.BlockSpec((block_m, d), row_block)]
    if quantized:
        in_specs.append(pl.BlockSpec((block_m, 1), row_block))
    in_specs.append(pl.BlockSpec((1, d, block_f), weight_block))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(gm, gf),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="ragged_gemm",
    )(*prefetch, *operands)
    if debug:
        return out[0], out[1][::8, ::128]
    return out[0]
