"""One-kernel ragged grouped expert GEMM (ROADMAP perf item 1).

Acceptance gates for the ragged dispatch stack:
  (a) ragged_gemm == ref_ragged_gemm across dense / int8 / fp8 operand
      sweeps, including empty segments, single-expert and all-experts
      tile maps, and dead capacity slots whose NaN weights stay inert;
  (b) the int8 MXU contraction accumulates in int32 (asserted on the
      jaxpr) and the fp8 contraction in float32;
  (c) the debug tile counter proves grid steps scale with actual rows
      only — empty expert segments cost zero tiles;
  (d) ops.ragged_expert_matmul (Pallas and fallback paths) matches the
      gathered dense einsum, with quantized storage inside the store
      dequant error envelope;
  (e) RaggedExecutor == GroupedExecutor bitwise on a real DiT ensemble
      (dense store; CFG drop_mask, stacked-null, and no-text variants)
      and within quantized bounds for int8/fp8 stores, end-to-end
      through sample_ensemble;
  (f) dead row tiles (``tile_live`` / ``live``): live tiles bitwise
      equal to the unmasked launch, dead ones exactly zero, no
      contraction and no input fetch for them; live groups bitwise on
      the dense fallback too.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ExpertSpec,
    GroupedExecutor,
    RaggedExecutor,
    SamplerConfig,
    make_dispatch_plan,
    plan_from_slots,
    resolve_dispatch,
    sample_ensemble,
)
from repro.core.conversion import ConversionConfig
from repro.core.param_store import make_store
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.ragged_gemm import _dead_tile_sources, ragged_gemm
from repro.models import dit as D
from repro.models.config import dit_b2

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _quantize(w, dtype):
    """Per-expert symmetric quantization matching QuantizedStore's math."""
    qmax = 127.0 if dtype == "int8" else 448.0
    axes = tuple(range(1, w.ndim))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes), 1e-12) / qmax
    q = w / scale.reshape((-1,) + (1,) * (w.ndim - 1))
    if dtype == "int8":
        q = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)
    else:
        q = q.astype(jnp.float8_e4m3fn)
    return q, scale


# --- (a) kernel vs oracle ----------------------------------------------------

RAGGED_CASES = [
    # (m, d, f, k_cap, block_m, block_f, seed)
    (256, 32, 128, 4, 64, 128, 0),
    (128, 16, 256, 3, 32, 128, 1),
    (64, 48, 128, 8, 8, 128, 2),       # 8-row tiles (TPU sublane floor)
    (512, 64, 384, 2, 128, 128, 3),
]


@pytest.mark.parametrize("m,d,f,k,bm,bf,seed", RAGGED_CASES)
def test_ragged_gemm_dense_sweep(m, d, f, k, bm, bf, seed):
    x = _rand((m, d), seed=seed)
    w = _rand((k, d, f), seed=seed + 10)
    te = jax.random.randint(jax.random.PRNGKey(seed + 20), (m // bm,), 0, k)
    out = ragged_gemm(x, w, te, block_m=bm, block_f=bf, interpret=True)
    ref = R.ref_ragged_gemm(x, w, te)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("m,d,f,k,bm,bf,seed", RAGGED_CASES[:2])
def test_ragged_gemm_int8_bitwise_vs_oracle(m, d, f, k, bm, bf, seed):
    """int8×int8→int32 accumulation is exact integer math, and the dequant
    epilogue multiplies in the oracle's order — so kernel == oracle at the
    bit level, not just within tolerance."""
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.randint(kx, (m, d), -127, 128).astype(jnp.int8)
    w = jax.random.randint(ky, (k, d, f), -127, 128).astype(jnp.int8)
    xs = jax.random.uniform(jax.random.PRNGKey(seed + 1), (m,)) + 0.01
    ws = jax.random.uniform(jax.random.PRNGKey(seed + 2), (k,)) + 0.01
    te = jax.random.randint(jax.random.PRNGKey(seed + 3), (m // bm,), 0, k)
    out = ragged_gemm(x, w, te, xs, ws, block_m=bm, block_f=bf,
                      interpret=True)
    ref = R.ref_ragged_gemm(x, w, te, xs, ws)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("m,d,f,k,bm,bf,seed", RAGGED_CASES[:2])
def test_ragged_gemm_fp8_vs_oracle(m, d, f, k, bm, bf, seed):
    x = _rand((m, d), seed=seed).astype(jnp.float8_e4m3fn)
    w = _rand((k, d, f), seed=seed + 10).astype(jnp.float8_e4m3fn)
    xs = jax.random.uniform(jax.random.PRNGKey(seed + 1), (m,)) + 0.01
    ws = jax.random.uniform(jax.random.PRNGKey(seed + 2), (k,)) + 0.01
    te = jax.random.randint(jax.random.PRNGKey(seed + 3), (m // bm,), 0, k)
    out = ragged_gemm(x, w, te, xs, ws, block_m=bm, block_f=bf,
                      interpret=True)
    ref = R.ref_ragged_gemm(x, w, te, xs, ws)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("tile_map", ["single", "all", "sparse"])
def test_ragged_gemm_segment_shapes(tile_map):
    """Single-expert, all-experts-hit, and mostly-empty segment maps all
    reduce to the same per-tile contract."""
    m, d, f, k, bm = 128, 16, 128, 8, 16
    x = _rand((m, d), seed=4)
    w = _rand((k, d, f), seed=5)
    gm = m // bm
    te = {
        "single": jnp.zeros((gm,), jnp.int32),
        "all": jnp.arange(gm, dtype=jnp.int32) % k,
        "sparse": jnp.where(jnp.arange(gm) < gm // 2, 2, 5).astype(jnp.int32),
    }[tile_map]
    out = ragged_gemm(x, w, te, block_m=bm, block_f=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(R.ref_ragged_gemm(x, w, te)),
        rtol=1e-6, atol=1e-5,
    )


def test_ragged_gemm_dead_slots_stay_inert():
    """K_cap capacity slots the plan never references (evicted / invalid
    validity-mask entries) are never DMA'd: NaN weights in those leaves
    cannot poison the output."""
    m, d, f, k, bm = 64, 16, 128, 6, 16
    x = _rand((m, d), seed=6)
    w = _rand((k, d, f), seed=7)
    live = jnp.array([1, 4])
    dead = jnp.array([0, 2, 3, 5])
    w = w.at[dead].set(jnp.nan)
    te = live[jnp.arange(m // bm) % 2].astype(jnp.int32)
    out = ragged_gemm(x, w, te, block_m=bm, block_f=128, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(R.ref_ragged_gemm(x, w, te)),
        rtol=1e-6, atol=1e-5,
    )


# --- (b) accumulation dtypes -------------------------------------------------

def test_int8_contraction_accumulates_in_int32():
    """The quantized MXU contract: int8 operands must accumulate in int32
    (exact) — a float32 accumulation would silently round 8-bit products."""
    x = jnp.ones((16, 8), jnp.int8)
    w = jnp.ones((2, 8, 128), jnp.int8)
    te = jnp.zeros((2,), jnp.int32)
    scales = jnp.ones((16,)), jnp.ones((2,))
    jaxpr = str(jax.make_jaxpr(
        lambda *a: ragged_gemm(*a, block_m=8, block_f=128, interpret=True)
    )(x, w, te, *scales))
    prefs = re.findall(r"preferred_element_type=(\w+)", jaxpr)
    assert prefs == ["int32"], prefs
    assert "i8[" in jaxpr            # operands reach the dot as int8


def test_fp8_contraction_accumulates_in_float32():
    x = jnp.ones((16, 8), jnp.float8_e4m3fn)
    w = jnp.ones((2, 8, 128), jnp.float8_e4m3fn)
    te = jnp.zeros((2,), jnp.int32)
    scales = jnp.ones((16,)), jnp.ones((2,))
    jaxpr = str(jax.make_jaxpr(
        lambda *a: ragged_gemm(*a, block_m=8, block_f=128, interpret=True)
    )(x, w, te, *scales))
    prefs = re.findall(r"preferred_element_type=(\w+)", jaxpr)
    assert prefs == ["float32"], prefs


# --- (c) zero-cost empty segments (runtime tile count) -----------------------

def test_grid_steps_scale_with_rows_not_experts():
    """The runtime proof of the ragged economy: the executed-tile map has
    exactly (M/block_m)·(F/block_f) entries whether one expert or eight
    absorb the rows, and growing the resident capacity K adds nothing."""
    m, d, f, bm, bf = 128, 16, 256, 16, 128
    x = _rand((m, d), seed=8)
    gm, gf = m // bm, f // bf
    counts = []
    for k, spread in [(8, False), (8, True), (64, True)]:
        w = _rand((k, d, f), seed=9)
        te = (jnp.arange(gm, dtype=jnp.int32) % k if spread
              else jnp.zeros((gm,), jnp.int32))
        out, tiles = ragged_gemm(x, w, te, block_m=bm, block_f=bf,
                                 interpret=True, debug=True)
        assert tiles.shape == (gm, gf)
        assert bool(jnp.all(tiles == 1))   # each grid step ran exactly once
        counts.append(int(tiles.sum()))
    # one expert hit vs all hit vs 8× capacity: identical tile counts
    assert counts == [gm * gf] * 3


# --- (f) dead row tiles --------------------------------------------------------

#: row-tile liveness patterns over 8 tiles
LIVE_PATTERNS = {
    "all": [1] * 8,
    "none": [0] * 8,
    "alternating": [1, 0] * 4,
    "run": [0, 0, 1, 1, 1, 0, 0, 0],
}


def _operands(storage, m, d, f, k, bm, seed=30):
    te = jax.random.randint(jax.random.PRNGKey(seed), (m // bm,), 0, k)
    if storage == "dense":
        return (_rand((m, d), seed=seed + 1), _rand((k, d, f), seed=seed + 2),
                te)
    kx, ky = jax.random.split(jax.random.PRNGKey(seed + 3))
    if storage == "int8":
        x = jax.random.randint(kx, (m, d), -127, 128).astype(jnp.int8)
        w = jax.random.randint(ky, (k, d, f), -127, 128).astype(jnp.int8)
    else:
        x = _rand((m, d), seed=seed + 1).astype(jnp.float8_e4m3fn)
        w = _rand((k, d, f), seed=seed + 2).astype(jnp.float8_e4m3fn)
    xs = jax.random.uniform(jax.random.PRNGKey(seed + 4), (m,)) + 0.01
    ws = jax.random.uniform(jax.random.PRNGKey(seed + 5), (k,)) + 0.01
    return x, w, te, xs, ws


@pytest.mark.parametrize("pattern", sorted(LIVE_PATTERNS))
@pytest.mark.parametrize("storage", ["dense", "int8", "fp8"])
def test_ragged_gemm_dead_tiles_skip_the_contraction(storage, pattern):
    """Live tiles are bitwise the unmasked launch's, dead tiles exactly
    zero, and the tile map counts a contraction on the live tiles only."""
    m, d, f, k, bm, bf = 128, 16, 256, 4, 16, 128
    args = _operands(storage, m, d, f, k, bm)
    live = jnp.array(LIVE_PATTERNS[pattern], jnp.int32)
    ref = ragged_gemm(*args, block_m=bm, block_f=bf, interpret=True)
    out, tiles = ragged_gemm(*args, tile_live=live, block_m=bm, block_f=bf,
                             interpret=True, debug=True)
    rows = np.repeat(np.asarray(live) > 0, bm)
    np.testing.assert_array_equal(np.asarray(out)[rows],
                                  np.asarray(ref)[rows])
    assert not np.asarray(out)[~rows].any()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(R.ref_ragged_gemm(*args, tile_live=live)),
        rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(tiles),
        np.repeat(np.asarray(live)[:, None], f // bf, axis=1))


def _fetches(live, gf):
    """Input block fetches of a ``(row tile, lane tile)`` grid walk: the
    pipeline fetches a block when its index differs from the previous
    grid step's.  A tile's x block is ``src[i]``, its weight block's lane
    index ``j``, or ``col[i]`` on a dead tile."""
    src, col = (np.asarray(a) for a in _dead_tile_sources(
        jnp.array(live, jnp.int32), gf))
    blocks = [(int(src[i]), j if col[i] < 0 else int(col[i]))
              for i in range(len(live)) for j in range(gf)]
    x = sum(a[0] != b[0] for a, b in zip(blocks, blocks[1:])) + 1
    w = sum(a != b for a, b in zip(blocks, blocks[1:])) + 1
    return x, w


@pytest.mark.parametrize("pattern", sorted(LIVE_PATTERNS))
def test_dead_tiles_fetch_no_input_block(pattern):
    """A dead tile points at blocks already in VMEM: the grid fetches
    exactly the x and weight blocks of the live tiles alone (one of each
    when none is live)."""
    live, gf = LIVE_PATTERNS[pattern], 3
    n_live = sum(live)
    x, w = _fetches(live, gf)
    assert (x, w) == ((n_live, n_live * gf) if n_live else (1, 1))


@pytest.mark.parametrize("width", ["tiled", "narrow"])
@pytest.mark.parametrize("storage", ["dense", "int8"])
@pytest.mark.parametrize("force_pallas", ["1", "0"])
def test_ragged_expert_matmul_live_contract(force_pallas, storage, width,
                                            monkeypatch):
    """``live`` keeps live groups bitwise the unmasked call's on the
    Pallas path and the dense fallback (off-TPU, or groups too narrow to
    tile).  The Pallas path skips dead groups (zero before the bias);
    the fallback computes them as without ``live``."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", force_pallas)
    P, m, d, f, K = 6, 16 if width == "tiled" else 1, 32, 128, 4
    x = _rand((P, m, d), seed=40)
    w = _rand((K, d, f), seed=41)
    b = _rand((K, f), seed=42)
    kw = {}
    if storage == "int8":
        w, kw["w_scale"] = _quantize(w, "int8")
    eids = jnp.array([0, 3, 1, 3, 2, 0], jnp.int32)
    live = jnp.array([True, False, False, True, False, True])
    ref = np.asarray(ops.ragged_expert_matmul(x, w, eids, bias=b, **kw))
    out = np.asarray(ops.ragged_expert_matmul(x, w, eids, bias=b, live=live,
                                              **kw))
    keep = np.asarray(live)
    np.testing.assert_array_equal(out[keep], ref[keep])
    skipped = force_pallas == "1" and width == "tiled"
    dead = (np.broadcast_to(np.asarray(b)[np.asarray(eids)][:, None],
                            ref.shape) if skipped else ref)[~keep]
    np.testing.assert_array_equal(out[~keep], dead)


def _pallas_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            found = inner is not None and _pallas_eqn(
                getattr(inner, "jaxpr", inner))
            if found:
                return found
    return None


def test_unmasked_launch_has_no_liveness_operand():
    """Without ``tile_live`` the launch is the plain one: one prefetched
    operand (the tile experts) and no branch in the kernel."""
    x, w, te = _operands("dense", 64, 16, 128, 2, 16)
    launches = {}
    for name, kw in (("plain", {}),
                     ("masked", {"tile_live": jnp.ones((4,), jnp.int32)})):
        eqn = _pallas_eqn(jax.make_jaxpr(lambda *a: ragged_gemm(
            *a, block_m=16, block_f=128, interpret=True, **kw))(x, w, te)
            .jaxpr)
        launches[name] = (
            eqn.params["grid_mapping"].num_index_operands,
            sum(q.primitive.name == "cond" for q in eqn.params["jaxpr"].eqns))
    assert launches == {"plain": (1, 0), "masked": (3, 2)}


def test_tile_misalignment_is_loud():
    x = _rand((100, 16))
    w = _rand((2, 16, 128))
    with pytest.raises(ValueError, match="tile-aligned"):
        ragged_gemm(x, w, jnp.zeros((2,), jnp.int32),
                    block_m=64, block_f=128, interpret=True)
    with pytest.raises(ValueError, match="x_scale"):
        ragged_gemm(x.astype(jnp.int8)[:64], w.astype(jnp.int8),
                    jnp.zeros((1,), jnp.int32),
                    block_m=64, block_f=128, interpret=True)


# --- (d) ops.ragged_expert_matmul wrapper ------------------------------------

def test_ragged_block_m_policy():
    assert ops.ragged_block_m(16) == 16
    assert ops.ragged_block_m(256) == 256
    assert ops.ragged_block_m(1024) == 256
    assert ops.ragged_block_m(2560) == 160    # halves under the cap
    assert ops.ragged_block_m(8) == 8
    assert ops.ragged_block_m(12) is None     # below-sublane remainder
    assert ops.ragged_block_m(7) is None
    assert ops.ragged_block_m(0) is None


#: the expert denses of the two served widths, (d_in, d_out) -> today's
#: (block_m, padded F, block_f) at 256-row groups and a float32 store.
B2_TILES = {
    (16, 768): (256, 768, 768),       # patch embedding
    (768, 768): (256, 768, 768),      # attention projections
    (768, 3072): (256, 3072, 1024),   # MLP up
    (3072, 768): (256, 768, 128),     # MLP down (deep contraction)
    (768, 16): (256, 128, 128),       # final.out, not a lane multiple
}


@pytest.mark.parametrize("d", [1152, 4608])
@pytest.mark.parametrize("f", [1152, 4608, 3456])
def test_ragged_tiles_lane_aligned_width_runs_unpadded(d, f):
    """A lane-multiple output width that is not a multiple of the
    1024-lane tile (DiT-XL/2's 1152 and 4608) is tiled by a divisor of
    its own width instead of padding to the next tile multiple."""
    bm, fp, bf = ops.ragged_tiles(256, d, f, 4, 4, False)
    assert fp == f
    assert f % bf == 0 and bf <= ops._TILE_BLOCK
    assert ops._ragged_step_bytes(bm, d, bf, 4, 4, False) \
        <= ops._RAGGED_VMEM_BUDGET


@pytest.mark.parametrize("shape", sorted(B2_TILES))
def test_ragged_tiles_b2_shapes_unchanged(shape):
    d, f = shape
    assert ops.ragged_tiles(256, d, f, 4, 4, False) == B2_TILES[shape]


@pytest.mark.parametrize("f", [40, 16])
def test_ragged_tiles_unaligned_width_pads_to_lane(f):
    assert ops.ragged_tiles(256, 768, f, 4, 4, False)[1:] == (128, 128)


@pytest.mark.parametrize("f,padded", [(40, True), (1152, False)])
def test_ragged_expert_matmul_pads_weights_only_off_lane(
    f, padded, monkeypatch
):
    """The Pallas path pads the weight leaf (and slices the output) only
    for a width that is not a lane multiple."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    x = _rand((4, 16, 32), seed=16)
    w = _rand((3, 32, f), seed=17)
    eids = jnp.array([0, 2, 1, 2], jnp.int32)
    jaxpr = str(jax.make_jaxpr(ops.ragged_expert_matmul)(x, w, eids))
    assert (" pad[" in jaxpr) == padded
    assert (" slice[" in jaxpr) == padded


@pytest.mark.parametrize("force_pallas,f", [
    pytest.param("1", 40, id="1"),
    pytest.param("0", 40, id="0"),
    pytest.param("1", 1152, id="1-f1152"),
])
def test_ragged_expert_matmul_matches_gathered_einsum(
    force_pallas, f, monkeypatch
):
    """Wrapper == gathered dense einsum on both the Pallas (interpret) and
    fallback paths, with a non-tile-aligned output width (F=40 pads to the
    lane multiple and slices back) and a lane-aligned one that is not a
    multiple of the 1024-lane tile (F=1152 runs unpadded, 3 lane tiles)."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", force_pallas)
    P, m, d, K = 6, 16, 32, 4
    x = _rand((P, m, d), seed=10)
    w = _rand((K, d, f), seed=11)
    b = _rand((K, f), seed=12)
    eids = jax.random.randint(jax.random.PRNGKey(13), (P,), 0, K)
    out = ops.ragged_expert_matmul(x, w, eids, bias=b)
    ref = jnp.einsum("pmd,pdf->pmf", x, w[eids]) + b[eids][:, None, :]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


def test_ragged_expert_matmul_narrow_groups_take_fallback(monkeypatch):
    """Row groups below the 8-row sublane floor (e.g. per-pair vectors)
    run the dense-math fallback even when Pallas is forced."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    P, d, f, K = 5, 16, 24, 3
    x = _rand((P, 1, d), seed=14)
    w = _rand((K, d, f), seed=15)
    eids = jnp.array([0, 2, 1, 2, 0], jnp.int32)
    out = ops.ragged_expert_matmul(x, w, eids)
    ref = jnp.einsum("pmd,pdf->pmf", x, w[eids])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("qdtype,bound", [("int8", 0.03), ("fp8", 0.12)])
@pytest.mark.parametrize("force_pallas", ["1", "0"])
def test_ragged_expert_matmul_quantized_bounds(
    qdtype, bound, force_pallas, monkeypatch
):
    """Quantized storage ends within the store-dequant error envelope of
    the full-precision contraction on both execution paths."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", force_pallas)
    P, m, d, f, K = 6, 16, 32, 40, 4
    x = _rand((P, m, d), seed=16)
    wf = _rand((K, d, f), seed=17)
    eids = jax.random.randint(jax.random.PRNGKey(18), (P,), 0, K)
    dense = jnp.einsum("pmd,pdf->pmf", x, wf[eids])
    q, scale = _quantize(wf, qdtype)
    out = ops.ragged_expert_matmul(x, q, eids, w_scale=scale)
    rel = float(jnp.max(jnp.abs(out - dense)) / jnp.max(jnp.abs(dense)))
    assert rel < bound, rel


# --- (e) executor + end-to-end parity on a real DiT --------------------------

_CFG = dit_b2().reduced(d_model=64, num_heads=2, text_dim=16, text_len=4)
_K, _B, _TOPK = 4, 5, 2


@pytest.fixture(scope="module")
def dit_ensemble():
    keys = jax.random.split(KEY, _K)
    params = [D.init(_CFG, k) for k in keys]
    stacked = D.stack_expert_params(params)
    apply_fn = D.make_expert_apply(_CFG)
    ragged_fn = D.make_ragged_expert_apply(_CFG)
    return params, stacked, apply_fn, ragged_fn


def _plan(b=_B, k=_TOPK, seed=1):
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(seed), (b, _K)), -1
    )
    return make_dispatch_plan(probs, k)


def _latents(b=_B, seed=2):
    shape = (b, _CFG.latent_size, _CFG.latent_size, _CFG.latent_channels)
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


@pytest.mark.parametrize("variant", ["drop_mask", "stacked_null", "no_text"])
def test_ragged_executor_matches_grouped_bitwise(dit_ensemble, variant):
    _, stacked, apply_fn, ragged_fn = dit_ensemble
    store = make_store(stacked)
    x, tb = _latents(), jax.random.uniform(jax.random.PRNGKey(3), (_B,))
    text = _rand((_B, _CFG.text_len, _CFG.text_dim), seed=4)
    if variant == "drop_mask":
        g = 2
        cond_g = {
            "text_emb": jnp.stack([text, text], axis=1),
            "drop_mask": jnp.broadcast_to(
                jnp.array([False, True])[None], (_B, 2)
            ),
        }
    elif variant == "stacked_null":
        g = 2
        null = _rand((_B, _CFG.text_len, _CFG.text_dim), seed=5)
        cond_g = {"text_emb": jnp.stack([text, null], axis=1)}
    else:
        g, cond_g = 1, {}
    tab = jnp.ones((5, _K), jnp.float32)
    conv = ConversionConfig()
    plan = _plan()
    pg, wg, ig = GroupedExecutor(apply_fn, store, conv).predictions(
        plan, x, tb, cond_g, g, tab
    )
    pr, wr, ir = RaggedExecutor(ragged_fn, store, conv).predictions(
        plan, x, tb, cond_g, g, tab
    )
    assert pr.shape == pg.shape
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(wg), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(ig), np.asarray(ir))


@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_ragged_executor_quantized_matches_grouped(dit_ensemble, qdtype):
    """Quantized stores: the fallback dequant multiplies in the store's
    exact float32 order, so ragged == grouped bitwise off-TPU too."""
    _, stacked, apply_fn, ragged_fn = dit_ensemble
    store = make_store(stacked, dtype=qdtype)
    x, tb = _latents(seed=6), jax.random.uniform(jax.random.PRNGKey(7), (_B,))
    text = _rand((_B, _CFG.text_len, _CFG.text_dim), seed=8)
    cond_g = {
        "text_emb": jnp.stack([text, text], axis=1),
        "drop_mask": jnp.broadcast_to(
            jnp.array([False, True])[None], (_B, 2)
        ),
    }
    tab = jnp.ones((5, _K), jnp.float32)
    conv = ConversionConfig()
    plan = _plan(seed=9)
    pg, _, _ = GroupedExecutor(apply_fn, store, conv).predictions(
        plan, x, tb, cond_g, 2, tab
    )
    pr, _, _ = RaggedExecutor(ragged_fn, store, conv).predictions(
        plan, x, tb, cond_g, 2, tab
    )
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(pr))


def test_ragged_executor_dead_validity_slots(dit_ensemble):
    """A plan over capacity K with invalid slots remapped to weight-0
    (routed_slots semantics): ragged == grouped when some slots never
    receive an assignment."""
    _, stacked, apply_fn, ragged_fn = dit_ensemble
    store = make_store(stacked)
    # all assignments on experts {0, 3}: segments 1 and 2 are empty
    idx = jnp.array([[0, 3]] * _B, jnp.int32)
    w = jnp.full((_B, 2), 0.5)
    plan = plan_from_slots(idx, w, _K)
    x, tb = _latents(seed=10), jnp.full((_B,), 0.4)
    tab = jnp.ones((5, _K), jnp.float32)
    conv = ConversionConfig()
    pg, _, _ = GroupedExecutor(apply_fn, store, conv).predictions(
        plan, x, tb, {}, 1, tab
    )
    pr, _, _ = RaggedExecutor(ragged_fn, store, conv).predictions(
        plan, x, tb, {}, 1, tab
    )
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(pr))


def test_resolve_dispatch_ragged_rules():
    # auto prefers ragged when the expert set publishes a ragged forward
    assert resolve_dispatch("auto", "routed", True, False, True) == "ragged"
    assert resolve_dispatch("auto", "routed", True, False, False) == "grouped"
    # batch-uniform plans keep the single-forward gathered path
    assert resolve_dispatch("auto", "routed", True, True, True) == "gathered"
    # explicit ragged needs the forward, stackable params, routed mode
    assert resolve_dispatch("ragged", "routed", True, False, True) == "ragged"
    with pytest.raises(ValueError, match="ragged_apply_fn"):
        resolve_dispatch("ragged", "routed", True, False, False)
    with pytest.raises(ValueError, match="stackable"):
        resolve_dispatch("ragged", "routed", False)
    with pytest.raises(ValueError, match="routed"):
        resolve_dispatch("ragged", "dense", True)


def test_sample_ensemble_ragged_end_to_end(dit_ensemble):
    """Full sampler: dispatch='ragged' == dispatch='grouped' bitwise, and
    'auto' now lands on the ragged backend for this expert set."""
    params, stacked, apply_fn, ragged_fn = dit_ensemble
    experts = [
        ExpertSpec(
            f"e{i}", "ddpm" if i % 2 == 0 else "fm",
            "cosine" if i % 2 == 0 else "linear", apply_fn, i,
            ragged_apply_fn=ragged_fn,
        )
        for i in range(_K)
    ]

    def router_fn(x, t):
        logits = (
            jnp.tile(jnp.arange(float(_K))[None], (x.shape[0], 1))
            + x.mean(axis=(1, 2, 3))[:, None] * 3.0
        )
        return jax.nn.softmax(logits, axis=-1)

    text = _rand((_B, _CFG.text_len, _CFG.text_dim), seed=11)
    shape = (_B, _CFG.latent_size, _CFG.latent_size, _CFG.latent_channels)
    store = make_store(stacked)
    outs = {}
    for disp in ("grouped", "ragged", "auto"):
        cfg = SamplerConfig(num_steps=2, strategy="topk", top_k=2,
                            cfg_scale=4.0, dispatch=disp)
        outs[disp] = sample_ensemble(
            jax.random.PRNGKey(12), experts, params, router_fn, shape,
            cond={"text_emb": text}, null_cond={}, config=cfg,
            stacked_params=store,
        )
    np.testing.assert_array_equal(
        np.asarray(outs["grouped"]), np.asarray(outs["ragged"])
    )
    np.testing.assert_array_equal(
        np.asarray(outs["auto"]), np.asarray(outs["ragged"])
    )
