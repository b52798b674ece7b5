"""The serving hot-path kernels compile for a TPU v5e at dit-b2 widths.

Interpret mode cannot see what the TPU compiler refuses: blocks whose
last two dims are not ``(8, 128)``-divisible (or whole), and tiles that
overflow the kernel's scoped VMEM.  These tests compile each kernel of
the served step for a *described* v5e chip (no chip is attached; the
TPU compiler runs on the host) at the shapes dit-b2 serving launches:
8 dit-b2 experts, batch 8, top-2 routing and CFG, i.e. 32 row groups of
256 tokens, and 32×32×4 latents.  Each asserts that the compiled HLO
holds the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every test
worker imports every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (
    AxisType,
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from repro.kernels import ops
from repro.kernels.hetero_fuse import (
    hetero_fuse_coeffs,
    hetero_fuse_dequant,
    hetero_fuse_step,
)
from repro.kernels.ragged_gemm import ragged_gemm
from repro.launch.sharding import mesh_scope
from repro.models.config import dit_b2, dit_xl2

_CFG = dit_b2()
_D = _CFG.d_model
_PATCH_IN = _CFG.patch_size ** 2 * _CFG.latent_channels       # 16
_TOKENS = (_CFG.latent_size // _CFG.patch_size) ** 2          # 256
_LATENT = _CFG.latent_size ** 2 * _CFG.latent_channels        # 4096
_K, _BATCH, _TOP_K, _G = 8, 8, 2, 2
_GROUPS = _BATCH * _TOP_K * _G                                # 32
_MLP = _CFG.d_ff                                              # 3072

_XL = dit_xl2()
_XL_D, _XL_MLP = _XL.d_model, _XL.d_ff                        # 1152, 4608

#: every expert dense that runs through the ragged GEMM: (d_in, d_out);
#: the dit-xl2 widths are lane multiples that are not whole 1024-lane
#: tiles, so they run unpadded with a narrower ``block_f``.
DENSE_SHAPES = {
    "patch_embed": (_PATCH_IN, _D),
    "attn_proj": (_D, _D),
    "mlp_up": (_D, _MLP),
    "mlp_down": (_MLP, _D),
    "final_out": (_D, _PATCH_IN),
    "xl2_attn_proj": (_XL_D, _XL_D),
    "xl2_mlp_up": (_XL_D, _XL_MLP),
    "xl2_mlp_down": (_XL_MLP, _XL_D),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A persistent compilation cache cannot read back entries compiled
    # for a described chip; keep it off for these compiles.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_hlo(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str, name: str) -> None:
    assert "tpu_custom_call" in hlo
    calls = re.findall(r"%([\w.-]+) = .*custom_call_target=\"tpu_custom_call\"",
                       hlo)
    assert any(c.split(".")[0] == name for c in calls), calls


#: weight storage -> (activation dtype at the kernel, weight dtype):
#: quantized stores quantize activations to their own format; dense
#: stores feed float32 activations.
STORAGE = {
    "f32": (jnp.float32, jnp.float32),
    "bf16": (jnp.float32, jnp.bfloat16),
    "int8": (jnp.int8, jnp.int8),
    "fp8": (jnp.float8_e4m3fn, jnp.float8_e4m3fn),
}


@pytest.mark.parametrize("storage", sorted(STORAGE))
@pytest.mark.parametrize("layer", sorted(DENSE_SHAPES))
def test_ragged_gemm_compiles_for_v5e(one_chip, layer, storage):
    d, f = DENSE_SHAPES[layer]
    x_dtype, w_dtype = STORAGE[storage]
    quantized = storage in ("int8", "fp8")
    tiles = ops.ragged_tiles(_TOKENS, d, f, jnp.dtype(x_dtype).itemsize,
                             jnp.dtype(w_dtype).itemsize, quantized)
    assert tiles is not None
    bm, fp, bf = tiles
    m = _GROUPS * _TOKENS
    gm = m // bm
    if quantized:
        def fn(x, w, te, xs, ws):
            return ragged_gemm(x, w, te, xs, ws, block_m=bm, block_f=bf)

        hlo = _compiled_hlo(
            fn, one_chip, ((m, d), x_dtype), ((_K, d, fp), w_dtype),
            ((gm,), jnp.int32), ((m,), jnp.float32), ((_K,), jnp.float32),
        )
    else:
        def fn(x, w, te):
            return ragged_gemm(x, w, te, block_m=bm, block_f=bf)

        hlo = _compiled_hlo(
            fn, one_chip, ((m, d), x_dtype), ((_K, d, fp), w_dtype),
            ((gm,), jnp.int32),
        )
    _assert_kernel(hlo, "ragged_gemm")


#: the dit-xl2 denses that the expert-parallel cell runs with dead tiles
XL2_LAYERS = ("xl2_attn_proj", "xl2_mlp_up", "xl2_mlp_down")


@pytest.mark.parametrize("storage", ["f32", "int8"])
@pytest.mark.parametrize("layer", XL2_LAYERS)
def test_masked_ragged_gemm_compiles_for_v5e(one_chip, layer, storage):
    """The dead-tile variant (``tile_live``: prefetched liveness, a branch
    around the contraction, dead tiles' blocks re-pointed) compiles at
    the dit-xl2 widths within the tile policy's VMEM budget."""
    d, f = DENSE_SHAPES[layer]
    x_dtype, w_dtype = STORAGE[storage]
    quantized = storage == "int8"
    x_bytes, w_bytes = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize
    bm, fp, bf = ops.ragged_tiles(_TOKENS, d, f, x_bytes, w_bytes, quantized)
    assert ops._ragged_step_bytes(bm, d, bf, x_bytes, w_bytes, quantized) \
        <= ops._RAGGED_VMEM_BUDGET
    m = _GROUPS * _TOKENS
    gm = m // bm
    shapes = [((m, d), x_dtype), ((_K, d, fp), w_dtype), ((gm,), jnp.int32)]
    if quantized:
        shapes += [((m,), jnp.float32), ((_K,), jnp.float32)]
    shapes.append(((gm,), jnp.int32))

    def fn(*a):
        return ragged_gemm(*a[:-1], tile_live=a[-1], block_m=bm,
                           block_f=bf)

    _assert_kernel(_compiled_hlo(fn, one_chip, *shapes), "ragged_gemm")


def test_ragged_tiles_bound_deep_contraction_vmem():
    """The MLP down-projection's full-depth weight tile is what overflowed
    VMEM with a whole-width output block: the policy must narrow it."""
    bm, fp, bf = ops.ragged_tiles(_TOKENS, _MLP, _D, 4, 4, False)
    assert fp == _D and bf < _D
    assert ops._ragged_step_bytes(bm, _MLP, bf, 4, 4, False) \
        <= ops._RAGGED_VMEM_BUDGET


def test_ragged_tiles_bound_xl2_deep_contraction_vmem():
    """The dit-xl2 MLP down-projection contracts 4608 deep into 1152
    lanes: unpadded, it halves the row block and takes one-lane tiles."""
    bm, fp, bf = ops.ragged_tiles(_TOKENS, _XL_MLP, _XL_D, 4, 4, False)
    assert (bm, fp, bf) == (128, _XL_D, 128)
    assert ops._ragged_step_bytes(bm, _XL_MLP, bf, 4, 4, False) \
        <= ops._RAGGED_VMEM_BUDGET


@pytest.mark.parametrize("batch", [_BATCH, 3])
@pytest.mark.parametrize("per_row_dt", [False, True])
def test_hetero_fuse_step_compiles_for_v5e(one_chip, per_row_dt, batch):
    tp, block = ops._tile_pad(_LATENT)

    def fn(preds, x, w, coef, dt):
        return hetero_fuse_step(preds, x, w, coef, dt, cfg_scale=7.5,
                                block_t=block)

    hlo = _compiled_hlo(
        fn, one_chip,
        ((_TOP_K, _G, batch, tp), jnp.float32), ((batch, tp), jnp.float32),
        ((_G, batch, _TOP_K), jnp.float32),
        ((5, _TOP_K, _G, batch), jnp.float32),
        ((batch if per_row_dt else 1,), jnp.float32),
    )
    _assert_kernel(hlo, "hetero_fuse_step")


def test_hetero_fuse_coeffs_compiles_for_v5e(one_chip):
    tp, block = ops._tile_pad(_LATENT)
    rows = _G * _BATCH

    def fn(preds, x, w, coef):
        return hetero_fuse_coeffs(preds, x, w, coef, block_t=block)

    hlo = _compiled_hlo(
        fn, one_chip,
        ((_TOP_K, rows, tp), jnp.float32), ((rows, tp), jnp.float32),
        ((rows, _TOP_K), jnp.float32), ((5, _TOP_K, rows), jnp.float32),
    )
    _assert_kernel(hlo, "hetero_fuse_coeffs")


@pytest.mark.parametrize("storage", ["int8", "fp8"])
def test_hetero_fuse_dequant_compiles_for_v5e(one_chip, storage):
    tp, block = ops._tile_pad(_D * _MLP)

    def fn(q, scale):
        return hetero_fuse_dequant(q, scale, block_t=block)

    hlo = _compiled_hlo(
        fn, one_chip, ((_K, tp), STORAGE[storage][1]),
        ((_K,), jnp.float32),
    )
    _assert_kernel(hlo, "hetero_fuse_dequant")


@pytest.fixture
def expert_mesh(one_chip, topo, monkeypatch):
    """(expert=4, data=1) mesh over the described chips, with the kernel
    wrappers steered onto their TPU branch (this host's backend is the
    CPU, so ``ops.on_tpu()`` would otherwise pick the jnp oracles)."""
    import numpy as np

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    return Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("expert", "data"), axis_types=(AxisType.Auto,) * 2)


def test_hot_path_kernels_compile_on_expert_mesh(expert_mesh):
    """Expert-sharded serving: the compiler cannot partition a Pallas
    launch, so under the serving mesh scope each launch must run under
    ``shard_map`` — without it lowering raises."""
    mesh = expert_mesh
    rep, by_expert = NamedSharding(mesh, P()), NamedSharding(mesh, P("expert"))
    pairs = _GROUPS // _G
    tp, _ = ops._tile_pad(_LATENT)

    def fn(x, w, e, preds, xt, wts, coef):
        with mesh_scope(mesh):
            y = ops.ragged_expert_matmul(x, w, e)
            return y, ops.fused_step(preds, xt, wts, coef, 0.02, g=_G,
                                     cfg_scale=7.5)

    shapes = [
        (((pairs, _G * _TOKENS, _D)), rep), ((_K, _D, _D), by_expert),
        ((pairs,), rep), ((_TOP_K, _G * _BATCH, tp), rep),
        ((_BATCH, tp), rep), ((_G * _BATCH, _TOP_K), rep),
        ((5, _TOP_K, _G * _BATCH), rep),
    ]
    args = [jax.ShapeDtypeStruct(s, jnp.int32 if len(s) == 1 else
                                 jnp.float32, sharding=sh)
            for s, sh in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    _assert_kernel(hlo, "ragged_gemm")
    _assert_kernel(hlo, "hetero_fuse_step")
