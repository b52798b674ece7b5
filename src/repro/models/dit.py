"""Diffusion Transformer expert with PixArt-α AdaLN-Single (paper §2.5).

Processes 32×32×4 VAE latents with 2×2 patch embedding (256 tokens).

AdaLN-Single (Eqs. 14–16): a single global MLP maps the timestep embedding
τ(t) to all ``L × 6 × d`` modulation vectors at once; each block adds its
learned embedding ``E_b`` (init N(0, 1/√d)).  Per block (Eqs. 17–19):

    h1 = h  + α_msa ⊙ MSA(LN(h) ⊙ (1+γ_msa) + β_msa)
    h2 = h1 + CrossAttn(LN(h1), e_text)
    h' = h2 + α_mlp ⊙ FFN(LN(h2) ⊙ (1+γ_mlp) + β_mlp)

LN has no learnable affine.  Zero-init: modulation-path final linear,
cross-attn output projections (§2.5 Initialization Strategy).

Timesteps: the discrete 1000-entry sinusoidal table from the pretrained
DiT is kept; continuous FM times are mapped through ``round(999 t)``
(Eq. 21) at runtime.

Parameter top-level groups intentionally mirror the Eq. 20 checkpoint-
conversion policy keys: patch_embed / pos_embed / blocks / t_embed /
adaln_single / cross_attn / text_proj / final_layer / null_text_embed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import DiTConfig
from repro.core.param_store import (
    DenseStore, ExpertParamStore, QuantLeaf, dequant_leaf,
)
from repro.core.param_store import EXPERT_AXIS as EXPERT_AXIS  # re-export
from repro.core.schedules import to_ddpm_timestep
from repro.kernels import ops

Array = jax.Array


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def sinusoidal_table(num: int, dim: int) -> Array:
    """Frozen sinusoidal timestep features (the 'learned table' initializer)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(num)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def patchify(x: Array, p: int) -> Array:
    """(B, H, W, C) -> (B, H/p * W/p, p*p*C)."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def unpatchify(x: Array, p: int, hw: int, c: int) -> Array:
    b, n, _ = x.shape
    g = hw // p
    x = x.reshape(b, g, g, p, p, c)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(b, hw, hw, c)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _block_init(cfg: DiTConfig, key) -> dict:
    d = cfg.d_model
    hd = d // cfg.num_heads
    ks = jax.random.split(key, 2)
    return {
        "attn": L.gqa_init(ks[0], d, cfg.num_heads, cfg.num_heads, hd,
                           cfg.param_dtype),
        "mlp": L.gelu_mlp_init(ks[1], d, cfg.d_ff, cfg.param_dtype),
    }


def _cross_attn_init(cfg: DiTConfig, key) -> dict:
    d = cfg.d_model
    hd = d // cfg.num_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, d, cfg.param_dtype),
        "wk": L.dense_init(ks[1], d, d, cfg.param_dtype),
        "wv": L.dense_init(ks[2], d, d, cfg.param_dtype),
        # §2.5: cross-attn output projection zero-initialized.
        "wo": L.zeros_dense_init(ks[3], d, d, cfg.param_dtype),
    }


def init(cfg: DiTConfig, key) -> dict:
    d = cfg.d_model
    p = cfg.patch_size
    in_dim = p * p * cfg.latent_channels
    ks = jax.random.split(key, 10)
    t_feat = 256

    params: dict = {
        "patch_embed": L.dense_init_b(ks[0], in_dim, d, cfg.param_dtype),
        "pos_embed": {
            "emb": (0.02 * jax.random.normal(ks[1], (cfg.num_tokens, d))
                    ).astype(cfg.param_dtype)
        },
        "t_embed": {
            "table": sinusoidal_table(cfg.num_timesteps, t_feat).astype(
                cfg.param_dtype
            ),
            "mlp1": L.dense_init_b(ks[2], t_feat, d, cfg.param_dtype),
            "mlp2": L.dense_init_b(ks[3], d, d, cfg.param_dtype),
        },
        "blocks": jax.vmap(lambda k: _block_init(cfg, k))(
            jax.random.split(ks[4], cfg.num_layers)
        ),
        "final_layer": {
            # zero-init final projection -> identity-ish start (§2.5).
            "mod": L.zeros_dense_init(ks[5], d, 2 * d, cfg.param_dtype),
            "out": L.zeros_dense_init(ks[5], d, in_dim, cfg.param_dtype),
        },
    }
    if cfg.adaln_single:
        params["adaln_single"] = {
            # Eq. 14 global MLP.  The (L,6,d) tensor of Eq. 15 is the global
            # (6,d) modulation broadcast over layers plus per-block E_b —
            # a literal d->6Ld dense would alone cost more than the
            # per-block MLPs it replaces (PixArt-α §2.3).  Final linear
            # zero-init (§2.5).
            "mlp1": L.dense_init_b(ks[6], d, d, cfg.param_dtype),
            "mlp2": L.zeros_dense_init(ks[6], d, 6 * d),
            # Eq. 16 per-block embeddings E_b ~ N(0, 1/sqrt(d)).
            "block_embed": (
                jax.random.normal(ks[7], (cfg.num_layers, 6, d))
                / math.sqrt(d)
            ).astype(cfg.param_dtype),
        }
    else:
        # classic per-block adaLN-Zero (ablation baseline; 30% more params)
        params["adaln_per_block"] = jax.vmap(
            lambda k: L.zeros_dense_init(k, d, 6 * d, cfg.param_dtype)
        )(jax.random.split(ks[6], cfg.num_layers))
    if cfg.use_text:
        params["text_proj"] = L.dense_init_b(ks[8], cfg.text_dim, d,
                                             cfg.param_dtype)
        params["cross_attn"] = jax.vmap(lambda k: _cross_attn_init(cfg, k))(
            jax.random.split(ks[9], cfg.num_layers)
        )
        params["null_text_embed"] = {
            "emb": (0.02 * jax.random.normal(ks[9], (cfg.text_len,
                                                     cfg.text_dim))
                    ).astype(cfg.param_dtype)
        }
    if cfg.num_classes:
        params["cls_head"] = L.dense_init_b(ks[8], d, cfg.num_classes,
                                            cfg.param_dtype)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def timestep_embedding(cfg: DiTConfig, params, t: Array) -> Array:
    """τ(t) via the discrete table + MLP (Eq. 21 runtime mapping)."""
    idx = to_ddpm_timestep(t, cfg.num_timesteps)
    feat = jnp.take(params["t_embed"]["table"], idx, axis=0)
    h = jax.nn.silu(L.dense(params["t_embed"]["mlp1"], feat))
    return L.dense(params["t_embed"]["mlp2"], h)            # (B, d)


def global_modulation(cfg: DiTConfig, params, tau: Array) -> Array:
    """Eq. 14/15: (B, L, 6, d) modulation tensor C (+E_b added per block).

    Computed as a single global (6, d) modulation broadcast across the L
    layers (the per-layer variation comes from E_b in Eq. 16)."""
    b = tau.shape[0]
    h = jax.nn.silu(L.dense(params["adaln_single"]["mlp1"], tau))
    c = L.dense(params["adaln_single"]["mlp2"], h)
    c = c.reshape(b, 1, 6, cfg.d_model)
    return jnp.broadcast_to(c, (b, cfg.num_layers, 6, cfg.d_model))


def _modulate(x: Array, gamma: Array, beta: Array) -> Array:
    return x * (1.0 + gamma[:, None]) + beta[:, None]


def _self_attn(cfg: DiTConfig, p, x: Array) -> Array:
    d = cfg.d_model
    hd = d // cfg.num_heads
    b, s, _ = x.shape
    q, k, v = L.gqa_project(p, x, cfg.num_heads, cfg.num_heads, hd)
    pos = jnp.arange(s)
    with jax.named_scope("attention"):
        out = L.chunked_attention(
            q, k, v, q_positions=pos, kv_positions=pos, causal=False,
            chunk_size=cfg.attn_chunk,
        )
    return L.dense(p["wo"], out.reshape(b, s, d))


def _cross_attn(cfg: DiTConfig, p, x: Array, text: Array) -> Array:
    d = cfg.d_model
    hd = d // cfg.num_heads
    b, s, _ = x.shape
    m = text.shape[1]
    q = L.dense(p["wq"], x).reshape(b, s, cfg.num_heads, hd)
    k = L.dense(p["wk"], text).reshape(b, m, cfg.num_heads, hd)
    v = L.dense(p["wv"], text).reshape(b, m, cfg.num_heads, hd)
    with jax.named_scope("attention"):
        out = L.chunked_attention(
            q, k, v, q_positions=jnp.arange(s), kv_positions=jnp.arange(m),
            causal=False, chunk_size=cfg.attn_chunk,
        )
    return L.dense(p["wo"], out.reshape(b, s, d))


def apply(
    cfg: DiTConfig,
    params,
    x_t: Array,
    t: Array,
    *,
    text_emb: Array | None = None,
    drop_mask: Array | None = None,
) -> Array:
    """Predict ε or velocity (objective decided by the training loss).

    Args:
      x_t: (B, H, W, C) noisy latents.
      t: (B,) native-time (continuous [0,1] or discrete indices).
      text_emb: (B, text_len, text_dim) frozen CLIP embeddings; None uses the
        learned null embedding (CFG unconditional branch).
      drop_mask: optional (B,) bool — per-sample CFG dropout: True rows use
        the null embedding (train-time p=0.1 conditioning drop, §2.5).
    """
    b = x_t.shape[0]
    p = cfg.patch_size
    x = patchify(x_t.astype(cfg.activation_dtype), p)
    h = L.dense(params["patch_embed"], x)
    h = h + params["pos_embed"]["emb"][None].astype(h.dtype)

    tau = timestep_embedding(cfg, params, t)                 # (B, d)

    if cfg.use_text:
        null = jnp.broadcast_to(
            params["null_text_embed"]["emb"][None],
            (b, cfg.text_len, cfg.text_dim),
        )
        if text_emb is None:
            text_emb = null
        elif drop_mask is not None:
            text_emb = jnp.where(drop_mask[:, None, None], null, text_emb)
        text = L.dense(params["text_proj"],
                       text_emb.astype(cfg.activation_dtype))
    else:
        text = None

    if cfg.adaln_single:
        mods = global_modulation(cfg, params, tau)           # (B, L, 6, d)
        mods = mods + params["adaln_single"]["block_embed"][None].astype(
            mods.dtype
        )
        mods = jnp.moveaxis(mods, 1, 0)                      # (L, B, 6, d)
    else:
        def per_block(pb):
            return L.dense(pb, jax.nn.silu(tau)).reshape(b, 6, cfg.d_model)

        mods = jax.vmap(per_block)(params["adaln_per_block"])

    xs: tuple = (params["blocks"], mods)
    if cfg.use_text:
        xs = xs + (params["cross_attn"],)

    def body(h, inputs):
        if cfg.use_text:
            bp, mod, cp = inputs
        else:
            bp, mod = inputs
            cp = None
        g_msa, b_msa, a_msa = mod[:, 0], mod[:, 1], mod[:, 2]
        g_mlp, b_mlp, a_mlp = mod[:, 3], mod[:, 4], mod[:, 5]
        # Eq. 17
        hn = _modulate(L.layernorm({}, h), g_msa, b_msa)
        h = h + a_msa[:, None] * _self_attn(cfg, bp["attn"], hn)
        # Eq. 18
        if cp is not None:
            h = h + _cross_attn(cfg, cp, L.layernorm({}, h), text)
        # Eq. 19
        hn = _modulate(L.layernorm({}, h), g_mlp, b_mlp)
        h = h + a_mlp[:, None] * L.gelu_mlp(bp["mlp"], hn)
        return h, None

    h, _ = jax.lax.scan(body, h, xs)

    if cfg.num_classes:
        pooled = jnp.mean(h, axis=1)
        return L.dense(params["cls_head"], pooled)           # router logits

    # Final layer: adaLN modulation from tau, then linear to patch pixels.
    mod = L.dense(params["final_layer"]["mod"], jax.nn.silu(tau))
    shift, scale = jnp.split(mod, 2, axis=-1)
    h = L.layernorm({}, h) * (1.0 + scale[:, None]) + shift[:, None]
    out = L.dense(params["final_layer"]["out"], h)
    return unpatchify(out, p, cfg.latent_size,
                      cfg.latent_channels).astype(jnp.float32)


def stack_expert_params(params_list):
    """Stack K homogeneous-architecture expert pytrees into one pytree.

    Every leaf gains a leading expert axis ``(K, ...)``.  This is the
    precondition for the sampler's routed-expert-only execution, and the
    raw material for a typed ``core.param_store.ExpertParamStore``
    (``make_store`` wraps the result dense or int8/fp8-quantized).
    Raises if structures or leaf shapes differ — callers should check
    ``repro.core.params_are_stackable`` first and fall back to the dense
    path for heterogeneous expert sets.
    """
    if len(params_list) == 1:
        return jax.tree.map(lambda x: jnp.asarray(x)[None], params_list[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def stacked_param_logical_axes(stacked):
    """Logical sharding annotation for stacked expert params.

    Thin delegator to ``ExpertParamStore.logical_axes`` — the annotation
    lives with the storage layout now, so quantized stores' per-expert
    scales automatically ride the same leading ``EXPERT_AXIS`` as the
    leaves they rescale.  Accepts a store or the raw stacked pytree
    (wrapped in a bit-identical ``DenseStore``); returns a
    structure-matching pytree of axis-name tuples either way
    (``launch.sharding.expert_param_specs`` consumes it).
    """
    if isinstance(stacked, ExpertParamStore):
        return stacked.logical_axes()
    return DenseStore.from_stacked(stacked).logical_axes().stacked


def gather_expert_params(stacked, expert_idx: Array):
    """Gather per-sample expert params from a stacked pytree.

    Delegates to ``core.param_store``: ``expert_idx`` is ``(B,)``
    (per-sample routing — leaves become ``(B, ...)``, for a vmapped
    apply) or a scalar (batch-uniform routing — one expert's params, for
    a plain apply).  Accepts a store or the raw stacked pytree.
    """
    store = stacked if isinstance(stacked, ExpertParamStore) \
        else DenseStore.from_stacked(stacked)
    return store.gather(expert_idx)


# ---------------------------------------------------------------------------
# Ragged pair-major apply (dispatch='ragged')
# ---------------------------------------------------------------------------


def _ragged_dense(leaf: dict, x: Array, pe: Array,
                  live: Array | None = None) -> Array:
    """Per-pair expert dense through the one-kernel ragged GEMM.

    ``leaf`` is a ``{"w": ..., "b"?: ...}`` node of a store's
    ``ragged_view()``: weights stay raw (``QuantLeaf`` keeps int8/fp8
    bytes + scales all the way into the kernel's fused-dequant
    epilogue); the bias — tiny — expands through ``dequant_leaf``.
    ``live`` (per pair) is ``ops.ragged_expert_matmul``'s.
    """
    w = leaf["w"]
    if isinstance(w, QuantLeaf):
        wq, ws = w.q, w.scale
    else:
        wq, ws = w, None
    b = leaf.get("b")
    bias = None if b is None else dequant_leaf(b)
    return ops.ragged_expert_matmul(x, wq, pe, bias=bias, w_scale=ws,
                                    live=live)


def _layer_view(tree, layer: int):
    """Slice layer ``layer`` from stacked ``(K, L, ...)`` view leaves.

    ``QuantLeaf``s slice their bytes and keep their per-expert scales
    (quantization is per-expert per-leaf, so every layer of a leaf
    shares the same ``(K,)`` scale vector).
    """
    def f(a):
        if isinstance(a, QuantLeaf):
            return QuantLeaf(a.q[:, layer], a.scale, a.compute_dtype)
        return a[:, layer]

    with jax.named_scope("layer_weights"):
        return jax.tree.map(f, tree)


def make_ragged_expert_apply(cfg: DiTConfig):
    """Pair-major ragged forward, matching ``ExpertSpec.ragged_apply_fn``.

    The grouped executor treats ``apply_fn`` as a black box, so it must
    run every guidance replica as an independent row; this adapter sees
    the whole routed step at once and exploits the structure the plan
    guarantees — the ``g`` CFG replicas of a (sample, slot) pair share
    the latent, the timestep AND the routed expert:

    * every dense layer runs as ONE ragged grouped GEMM over all
      resident experts' row groups (``kernels.ops.ragged_expert_matmul``
      walking the plan-derived per-pair expert ids) — no per-expert
      ``lax.switch`` branches and no power-of-two bucket padding;
    * the conditioning-independent prefix (patch/pos embed, timestep
      path, AdaLN-Single modulations, the layer-0 self-attention, which
      precedes the first cross-attention) computes once per *pair* and
      broadcasts to the replicas — conditioning first touches the
      stream at layer-0 cross-attention;
    * quantized stores never materialize: weight leaves reach the GEMM
      as raw int8/fp8 bytes + scales (``QuantLeaf``) and contract on
      quantized operands with int32/f32 accumulation.

    Signature::

        ragged_apply_fn(view, x_p, t_p, cond_pg, expert_ids, g, live=None)

    ``view`` = ``ExpertParamStore.ragged_view()``; ``x_p`` ``(P, H, W,
    C)`` one latent per routed pair; ``t_p`` ``(P,)``; ``cond_pg``
    leaves ``(P, g, ...)`` (``text_emb``/``drop_mask`` follow
    ``dit.apply`` semantics exactly — absent text uses the learned null
    embedding, ``drop_mask`` rows substitute it per replica); returns
    ``(P·g, H, W, C)`` float32, pair-major (replicas of a pair
    adjacent).  Bitwise-identical to the grouped executor for dense
    float32 params.  ``live`` ``(P,)`` bool, given only on an expert
    mesh (``core.dispatch.shard_share``), marks the pairs whose
    predictions are read: the ragged GEMMs skip the others' row tiles,
    and their outputs are not meaningful.
    """
    if cfg.num_classes:
        raise ValueError(
            "ragged apply serves expert prediction only; the router head "
            "(num_classes > 0) goes through the dense apply"
        )

    def ragged_apply(view, x_p, t_p, cond, pe, g, live=None):
        p_pairs = x_p.shape[0]
        d = cfg.d_model
        hd = d // cfg.num_heads
        ps = cfg.patch_size

        def pd(leaf, x):
            return _ragged_dense(leaf, x, pe, live)

        xp = patchify(x_p.astype(cfg.activation_dtype), ps)
        h_r = pd(view["patch_embed"], xp)                  # (P, T, d)
        h_r = h_r + dequant_leaf(view["pos_embed"]["emb"])[pe].astype(
            h_r.dtype
        )

        # Timestep path — replicas share t, so one row per pair.
        idx = to_ddpm_timestep(t_p, cfg.num_timesteps)
        feat = dequant_leaf(view["t_embed"]["table"])[pe, idx]
        ht = jax.nn.silu(pd(view["t_embed"]["mlp1"], feat))
        tau = pd(view["t_embed"]["mlp2"], ht)              # (P, d)

        if cfg.adaln_single:
            hm = jax.nn.silu(pd(view["adaln_single"]["mlp1"], tau))
            c = pd(view["adaln_single"]["mlp2"], hm).reshape(
                p_pairs, 1, 6, d
            )
            mods = jnp.broadcast_to(c, (p_pairs, cfg.num_layers, 6, d))
            mods = mods + dequant_leaf(
                view["adaln_single"]["block_embed"]
            )[pe].astype(mods.dtype)
            mods = jnp.moveaxis(mods, 1, 0)                # (L, P, 6, d)
        else:
            mods = jnp.stack([
                pd(_layer_view(view["adaln_per_block"], l),
                   jax.nn.silu(tau)).reshape(p_pairs, 6, d)
                for l in range(cfg.num_layers)
            ])                                             # (L, P, 6, d)

        def self_attn(bp, h, mod):
            # h: (P, T, d) prefix or (P, g, T, d) expanded; mod (P, 6, d)
            nb = h.ndim - 2
            g_msa, b_msa, a_msa = mod[:, 0], mod[:, 1], mod[:, 2]
            ex = (slice(None),) + (None,) * (nb - 1)
            hn = L.layernorm({}, h) * (1.0 + g_msa[ex + (None,)]) \
                + b_msa[ex + (None,)]
            t_tok = hn.shape[-2]
            q = pd(bp["attn"]["wq"], hn).reshape(
                -1, t_tok, cfg.num_heads, hd)
            k = pd(bp["attn"]["wk"], hn).reshape(
                -1, t_tok, cfg.num_heads, hd)
            v = pd(bp["attn"]["wv"], hn).reshape(
                -1, t_tok, cfg.num_heads, hd)
            pos = jnp.arange(t_tok)
            with jax.named_scope("attention"):
                att = L.chunked_attention(
                    q, k, v, q_positions=pos, kv_positions=pos,
                    causal=False, chunk_size=cfg.attn_chunk,
                )
            att = pd(bp["attn"]["wo"], att.reshape(h.shape))
            return h + a_msa[ex + (None,)] * att

        # Prefix: layer-0 self-attention on the per-pair representative —
        # exact because cross-attention (the first conditioning-dependent
        # op) runs AFTER self-attention within a block (Eqs. 17→18).
        h_r = self_attn(_layer_view(view["blocks"], 0), h_r, mods[0])
        # Expand to the replicas: pure broadcast, no recompute.
        h = jnp.broadcast_to(h_r[:, None], (p_pairs, g) + h_r.shape[1:])

        if cfg.use_text:
            nulle = dequant_leaf(view["null_text_embed"]["emb"])[pe]
            text_emb = cond.get("text_emb")
            if text_emb is None:
                text_emb = jnp.broadcast_to(
                    nulle[:, None], (p_pairs, g) + nulle.shape[1:]
                )
            else:
                drop = cond.get("drop_mask")
                if drop is not None:
                    text_emb = jnp.where(
                        drop[..., None, None], nulle[:, None], text_emb
                    )
            text = pd(view["text_proj"],
                      text_emb.astype(cfg.activation_dtype))
            t_txt = text.shape[-2]

        for layer in range(cfg.num_layers):
            bp = _layer_view(view["blocks"], layer)
            mod = mods[layer]
            g_mlp, b_mlp, a_mlp = mod[:, 3], mod[:, 4], mod[:, 5]
            if layer > 0:
                h = self_attn(bp, h, mod)                  # Eq. 17
            if cfg.use_text:                               # Eq. 18
                cp = _layer_view(view["cross_attn"], layer)
                t_tok = h.shape[-2]
                hn = L.layernorm({}, h)
                q = pd(cp["wq"], hn).reshape(-1, t_tok, cfg.num_heads, hd)
                k = pd(cp["wk"], text).reshape(
                    -1, t_txt, cfg.num_heads, hd)
                v = pd(cp["wv"], text).reshape(
                    -1, t_txt, cfg.num_heads, hd)
                with jax.named_scope("attention"):
                    att = L.chunked_attention(
                        q, k, v, q_positions=jnp.arange(t_tok),
                        kv_positions=jnp.arange(t_txt), causal=False,
                        chunk_size=cfg.attn_chunk,
                    )
                h = h + pd(cp["wo"], att.reshape(h.shape))
            hn = L.layernorm({}, h) * (1.0 + g_mlp[:, None, None]) \
                + b_mlp[:, None, None]                     # Eq. 19
            hmid = jax.nn.gelu(pd(bp["mlp"]["w1"], hn))
            h = h + a_mlp[:, None, None] * pd(bp["mlp"]["w2"], hmid)

        mod = pd(view["final_layer"]["mod"], jax.nn.silu(tau))
        shift, scale = jnp.split(mod, 2, axis=-1)
        h = L.layernorm({}, h) * (1.0 + scale[:, None, None]) \
            + shift[:, None, None]
        out = pd(view["final_layer"]["out"], h)
        out = out.reshape((p_pairs * g,) + out.shape[2:])
        return unpatchify(out, ps, cfg.latent_size,
                          cfg.latent_channels).astype(jnp.float32)

    return ragged_apply


def make_expert_apply(cfg: DiTConfig):
    """Adapter matching the ``ExpertSpec.apply_fn`` signature."""

    def apply_fn(params, x_t, t, **cond):
        return apply(cfg, params, x_t, t,
                     text_emb=cond.get("text_emb"),
                     drop_mask=cond.get("drop_mask"))

    return apply_fn


def _router_posterior(cfg: DiTConfig, params, x_t, t):
    return jax.nn.softmax(apply(cfg, params, x_t, t), axis=-1)


def make_router_fn(cfg: DiTConfig, params):
    """Router posterior p(k | x_t, t) (Eq. 2), as ``router_fn(x_t, t)``.

    A ``jax.tree_util.Partial`` whose leaves are the router params, so a
    jitted sampler takes it as an argument: the weights stay traced
    buffers instead of constants baked into the program.
    """
    return jax.tree_util.Partial(
        functools.partial(_router_posterior, cfg), params
    )
