"""Work of one sampler step, from the model's shapes alone.

The counts do not depend on how the program tiles, fuses or types its
kernels, so a later change to a kernel is judged against the same
yardstick.  A step serves ``images`` latents; each is routed to ``k``
experts (``pairs = images * k``), and each pair runs ``g`` guidance
branches (conditional and unconditional).  Multiply-adds count 2 FLOPs.
``m`` is a configuration (expert sizes at its top level, ``router``
nested).
"""

from __future__ import annotations


def _dims(m: dict) -> tuple[int, int, int, int, int]:
    d = m["d_model"]
    tokens = (m["latent_size"] // m["patch_size"]) ** 2
    ff = int(d * m["mlp_ratio"])
    in_dim = m["patch_size"] ** 2 * m["latent_channels"]
    return d, tokens, ff, in_dim, m["num_layers"]


def token_gemm_sites(m: dict, pairs: int, g: int) -> list[tuple]:
    """``(name, rows, depth, width)`` of every token-row GEMM of one
    expert forward over ``pairs`` routed pairs: the patch embedding, per
    layer the self-attention q, k, v, o, the cross-attention q, o and the
    MLP's two layers, and the output projection (``2 + 8 L`` sites).
    Rows are tokens per (pair, branch), and per pair where the work does
    not depend on the prompt: the patch embedding and layer 0's
    self-attention, which precedes the first cross-attention.  The text
    rows (77 per branch) and the per-pair conditioning vectors are not
    token rows and are not counted here."""
    d, t, ff, in_dim, layers = _dims(m)
    per_pair, per_branch = pairs * t, pairs * g * t
    sites = [("patch_embed", per_pair, in_dim, d)]
    for layer in range(layers):
        rows = per_pair if layer == 0 else per_branch
        sites += [(f"l{layer}.self.{n}", rows, d, d)
                  for n in ("q", "k", "v", "o")]
        sites += [(f"l{layer}.cross.q", per_branch, d, d),
                  (f"l{layer}.cross.o", per_branch, d, d),
                  (f"l{layer}.mlp.w1", per_branch, d, ff),
                  (f"l{layer}.mlp.w2", per_branch, ff, d)]
    sites.append(("final.out", per_branch, d, in_dim))
    return sites


#: bytes of one stored weight for each ``SamplerConfig.param_dtype``;
#: ``native`` keeps the leaves as ``bench/weights.py`` makes them, float32.
#: A quantized store's scales (one per output column) are not counted.
WEIGHT_BYTES = {"native": 4, "fp32": 4, "bf16": 2, "int8": 1, "fp8": 1}


def weight_bytes(param_dtype: str) -> int:
    """Bytes of one stored weight under ``param_dtype``; raises
    ``KeyError`` for a store type the table does not hold."""
    return WEIGHT_BYTES[param_dtype]


def ragged_gemm(m: dict, pairs: int, g: int, experts: int,
                weight_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one step's token-row GEMMs: ``2 rows D F``
    FLOPs; bytes for the rows in and out in float32, and each site's
    weights, of ``weight_bytes`` each, read once for every expert the step
    touches, taken as ``min(experts, pairs)`` (an upper bound: routing may
    touch fewer)."""
    touched = min(experts, pairs)
    flops = nbytes = 0.0
    for _, rows, depth, width in token_gemm_sites(m, pairs, g):
        flops += 2.0 * rows * depth * width
        nbytes += 4.0 * rows * (depth + width)
        nbytes += float(touched) * depth * width * weight_bytes
    return flops, nbytes


def _vector_path(d: int) -> float:
    """Per-row conditioning vectors: timestep MLP (256 -> d -> d) and
    AdaLN-Single (d -> d -> 6d)."""
    return 2.0 * (256 * d + d * d + d * d + d * 6 * d)


def _self_attention(t: int, d: int) -> float:
    return 2.0 * 4 * t * d * d + 2.0 * 2 * t * t * d


def expert_forward(m: dict, pairs: int, g: int) -> float:
    """Model FLOPs of one expert forward over ``pairs`` pairs with ``g``
    branches; the prompt-free prefix (patch embedding, conditioning
    vectors, layer 0's self-attention) counts once per pair."""
    d, t, ff, in_dim, layers = _dims(m)
    lt, dt = m["text_len"], m["text_dim"]
    per_pair = (2.0 * t * in_dim * d + _vector_path(d)
                + 2.0 * d * 2 * d + _self_attention(t, d))
    per_branch = 2.0 * lt * dt * d + 2.0 * t * d * in_dim
    per_branch += (layers - 1) * _self_attention(t, d)
    per_branch += layers * (2.0 * 2 * t * d * d          # cross q, o
                            + 2.0 * 2 * lt * d * d       # cross k, v
                            + 2.0 * 2 * t * lt * d       # scores, values
                            + 2.0 * 2 * t * d * ff)      # MLP
    return pairs * per_pair + pairs * g * per_branch


def router_forward(r: dict, images: int) -> float:
    """Model FLOPs of the router (DiT without text, mean-pooled head)."""
    d, t, ff, in_dim, layers = _dims(r)
    per = 2.0 * t * in_dim * d + _vector_path(d) + 2.0 * d * r["num_classes"]
    per += layers * (_self_attention(t, d) + 2.0 * 2 * t * d * ff)
    return images * per


def model_flops(m: dict, images: int, k: int, g: int = 2) -> float:
    """Model FLOPs of one sampler step over ``images`` latents."""
    return (router_forward(m["router"], images)
            + expert_forward(m, images * k, g))
