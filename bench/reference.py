"""Plain reference of the served ensemble sampler, in float32 jax.numpy.

Written from the paper's equations and the configuration file alone; it
imports nothing of the served program and reads only weights that
``weights.py`` made from the seed.  Per Euler step on the grid
``t_i = linspace(1, 0, S + 1)`` (continuous time, t = 1 is noise):

1. router: a DiT without text whose mean-pooled tokens feed a linear head;
   ``p = softmax(logits)`` over the K experts (expert k owns cluster k);
2. top-k routing: the k most probable experts, weights ``p_j / sum p``;
3. each routed expert (DiT with PixArt-alpha AdaLN-Single and text
   cross-attention) predicts on the conditional branch (the prompt) and
   the unconditional one (the expert's learned null text);
4. conversion to velocity: an FM expert's prediction is the velocity; a
   DDPM expert's epsilon becomes ``v = s(t) (a' x0 + s' eps)`` with
   ``x0 = clip((x - sigma eps) / max(alpha, alpha_min), -clamp, clamp)``
   on the cosine schedule and the piecewise dampening ``s(t)``;
5. ``u = sum_j w_j v_j`` per branch, CFG ``u = u_u + g (u_c - u_u)``, and
   the Euler step ``x <- x - u (t_i - t_{i+1})``.

Timesteps enter the DiT through ``round(999 t)`` into a 1000-row table.
Every matrix product runs at the ``precision`` given, so the same code is
the reference (``highest``) and the control (one step lower).  Each
routed pair gathers its expert's weights one layer at a time, so the
whole ensemble is never copied per pair.

An ensemble larger than one chip is held in blocks of experts, one block
a device (``sample_blocked``): each block computes the pairs routed to
its experts, a share of ``u`` in step 5 that is exactly zero for the
others, and the shares are summed in block order before CFG.  With one
block that is ``sample``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

#: matrix-product precisions: float32 (``highest``), three bfloat16
#: passes (``high``: hi*hi + hi*lo + lo*hi of each operand's bfloat16
#: split) and one bfloat16 pass (``default``), all accumulated in
#: float32.  The two lower ones are written out, so they mean the same on
#: every backend.
PRECISIONS = ("highest", "high", "default")


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _ein(spec, a, b, prec):
    if prec == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    (ah, al), (bh, bl) = _split(a), _split(b)

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    if prec == "default":
        return one(ah, bh)
    if prec == "high":
        return one(ah, bh) + (one(ah, bl) + one(al, bh))
    raise ValueError(f"unknown precision {prec!r}")


def _mm(x, w, prec):
    return _ein("...d,df->...f", x, w, prec)


def _pmm(x, w, prec):
    """Per-pair product: ``x`` (P, ..., D) against ``w`` (P, D, F)."""
    return _ein("p...d,pdf->p...f", x, w, prec)


def _ln(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6)


def _attend(q, k, v, heads, prec):
    """Softmax attention over (..., S, D) with ``heads`` heads."""
    *lead, sq, dm = q.shape
    sk = k.shape[-2]
    hd = dm // heads
    q = q.reshape(*lead, sq, heads, hd)
    k = k.reshape(*lead, sk, heads, hd)
    v = v.reshape(*lead, sk, heads, hd)
    s = _ein("...qhd,...khd->...hqk", q, k, prec)
    a = jax.nn.softmax(s / math.sqrt(hd), axis=-1)
    o = _ein("...hqk,...khd->...qhd", a, v, prec)
    return o.reshape(*lead, sq, dm)


def _patchify(x, p):
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def _unpatchify(x, p, hw, c):
    lead = x.shape[:-2]
    g = hw // p
    x = x.reshape(lead + (g, g, p, p, c))
    n = len(lead)
    x = jnp.moveaxis(x, n + 2, n + 1)            # (g, p, g, p, c) order
    return x.reshape(lead + (hw, hw, c))


def _tstep(t, num):
    return jnp.clip(jnp.round((num - 1) * t), 0, num - 1).astype(jnp.int32)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def router_probs(m, params, x, t, prec):
    """Router posterior (B, K) of latents ``x`` (B, H, W, C) at ``t`` (B,)."""
    d = m["d_model"]

    def lin(node, h):
        y = _mm(h, node["w"], prec)
        return y + node["b"] if "b" in node else y

    h = lin(params["patch_embed"], _patchify(x, m["patch_size"]))
    h = h + params["pos_embed"]["emb"]
    feat = params["t_embed"]["table"][_tstep(t, m["num_timesteps"])]
    tau = lin(params["t_embed"]["mlp2"],
              _silu(lin(params["t_embed"]["mlp1"], feat)))
    c = lin(params["adaln_single"]["mlp2"],
            _silu(lin(params["adaln_single"]["mlp1"], tau)))
    mods = c.reshape(-1, 1, 6, d) + params["adaln_single"]["block_embed"]
    blocks = params["blocks"]
    for layer in range(m["num_layers"]):
        mod = mods[:, layer]
        at = jax.tree.map(lambda a: a[layer], blocks["attn"])
        hn = _ln(h) * (1.0 + mod[:, 0, None]) + mod[:, 1, None]
        q, k, v = (_mm(hn, at[n]["w"], prec) for n in ("wq", "wk", "wv"))
        o = _mm(_attend(q, k, v, m["num_heads"], prec), at["wo"]["w"], prec)
        h = h + mod[:, 2, None] * o
        mp = jax.tree.map(lambda a: a[layer], blocks["mlp"])
        hn = _ln(h) * (1.0 + mod[:, 3, None]) + mod[:, 4, None]
        f = lin(mp["w2"], _gelu(lin(mp["w1"], hn)))
        h = h + mod[:, 5, None] * f
    logits = lin(params["cls_head"], jnp.mean(h, axis=1))
    return jax.nn.softmax(logits, axis=-1)


def expert_predict(m, stack, e, x, t, text, prec):
    """Native predictions of routed pairs on both guidance branches.

    ``stack``: expert weights with a leading expert axis; ``e`` (P,) the
    expert of each pair; ``x`` (P, H, W, C); ``t`` (P,); ``text``
    (P, 77, text_dim) the prompt.  Returns (P, 2, H, W, C): branch 0 is
    conditional, branch 1 uses the expert's null text.
    """
    d, heads, p = m["d_model"], m["num_heads"], m["patch_size"]

    def g(path):
        node = stack
        for k in path:
            node = node[k]
        return node

    def lin(path, h, layer=None):
        node = g(path)
        w = node["w"][e] if layer is None else node["w"][e, layer]
        y = _pmm(h, w, prec)
        if "b" in node:
            b = node["b"][e] if layer is None else node["b"][e, layer]
            y = y + b.reshape(b.shape[:1] + (1,) * (y.ndim - 2) + b.shape[1:])
        return y

    def per_pair(a, like):
        return a.reshape(a.shape[:1] + (1,) * (like.ndim - a.ndim) + a.shape[1:])

    h = lin(("patch_embed",), _patchify(x, p))
    h = h + g(("pos_embed", "emb"))[e]
    feat = g(("t_embed", "table"))[e, _tstep(t, m["num_timesteps"])]
    tau = lin(("t_embed", "mlp2"), _silu(lin(("t_embed", "mlp1"), feat)))
    c = lin(("adaln_single", "mlp2"),
            _silu(lin(("adaln_single", "mlp1"), tau)))
    mods = c.reshape(-1, 1, 6, d) + g(("adaln_single", "block_embed"))[e]
    null = g(("null_text_embed", "emb"))[e]
    txt = jnp.stack([text, null], axis=1)                  # (P, 2, 77, Dt)
    txt = lin(("text_proj",), txt)
    h = jnp.broadcast_to(h[:, None], (h.shape[0], 2) + h.shape[1:])
    for layer in range(m["num_layers"]):
        mod = mods[:, layer]                               # (P, 6, d)

        def md(j):
            return per_pair(mod[:, j], h)

        hn = _ln(h) * (1.0 + md(0)) + md(1)
        q, k, v = (lin(("blocks", "attn", n), hn, layer)
                   for n in ("wq", "wk", "wv"))
        o = lin(("blocks", "attn", "wo"), _attend(q, k, v, heads, prec), layer)
        h = h + md(2) * o
        hn = _ln(h)
        q = lin(("cross_attn", "wq"), hn, layer)
        k = lin(("cross_attn", "wk"), txt, layer)
        v = lin(("cross_attn", "wv"), txt, layer)
        h = h + lin(("cross_attn", "wo"), _attend(q, k, v, heads, prec),
                    layer)
        hn = _ln(h) * (1.0 + md(3)) + md(4)
        f = lin(("blocks", "mlp", "w2"),
                _gelu(lin(("blocks", "mlp", "w1"), hn, layer)), layer)
        h = h + md(5) * f
    mod = lin(("final_layer", "mod"), _silu(tau))
    shift, scale = per_pair(mod[:, :d], h), per_pair(mod[:, d:], h)
    h = _ln(h) * (1.0 + scale) + shift
    out = lin(("final_layer", "out"), h)
    return _unpatchify(out, p, m["latent_size"], m["latent_channels"])


def _coefficients(objectives, t):
    """(K, 5) conversion coefficients (alpha, sigma, alpha', sigma', scale)
    of each expert at time ``t``: DDPM experts on the cosine schedule, FM
    experts (linear path) passing their velocity through."""
    hp = math.pi / 2.0
    vs = jnp.where(t > 0.85, 0.88, jnp.where(t > 0.6, 0.93, 0.96))
    ddpm = jnp.stack([jnp.cos(hp * t), jnp.sin(hp * t), -hp * jnp.sin(hp * t),
                      hp * jnp.cos(hp * t), vs])
    fm = jnp.array([1.0, 0.0, 0.0, 1.0, 1.0], jnp.float32)
    return jnp.stack([ddpm if o == "ddpm" else fm for o in objectives])


def _route(cfg, router, x, t, prec):
    """Top-k routing of latents ``x`` at time ``t``: the renormalised
    weights (B, k) and the experts (B, k)."""
    probs = router_probs(dict(cfg["router"]), router, x,
                         jnp.full((x.shape[0],), t), prec)
    vals, idx = jax.lax.top_k(probs, cfg["top_k"])
    return vals / jnp.sum(vals, axis=-1, keepdims=True), idx


def _share(cfg, stack, first, w, idx, x, t, text, prec):
    """The fused velocity (B, 2, H, W, C), both guidance branches, of the
    routed pairs whose expert lies in ``stack``: experts ``first`` up to
    ``first`` + its size.  A pair whose expert lies elsewhere adds exactly
    zero; a stack of every expert takes every pair."""
    m = dict(cfg["model"])
    b, k = idx.shape
    e = idx.reshape(-1)
    n = jax.tree.leaves(stack)[0].shape[0]
    mine = None
    if n < len(cfg["objectives"]):
        mine = (e >= first) & (e < first + n)
        local = jnp.where(mine, e - first, 0)
    else:
        local = e
    xp = jnp.repeat(x, k, axis=0)
    preds = expert_predict(m, stack, local, xp, jnp.full((b * k,), t),
                           jnp.repeat(text, k, axis=0), prec)
    co = _coefficients(cfg["objectives"], t)[e]          # (P, 5)
    a, s, da, ds, vs = (co[:, j].reshape(-1, 1, 1, 1, 1) for j in range(5))
    x0 = jnp.clip((xp[:, None] - s * preds) / jnp.maximum(a, cfg["alpha_min"]),
                  -cfg["clamp"], cfg["clamp"])
    v = (da * x0 + ds * preds) * vs                       # (P, 2, H, W, C)
    v = v.reshape((b, k) + v.shape[1:])
    wv = w.reshape(b, k, 1, 1, 1, 1) * v
    if mine is not None:
        wv = jnp.where(mine.reshape(b, k, 1, 1, 1, 1), wv, 0.0)
    return jnp.sum(wv, axis=1)


def _euler(cfg, x, u, t_hi, t_lo):
    """CFG over the fused velocity ``u`` and one Euler step."""
    u = u[:, 1] + cfg["cfg_scale"] * (u[:, 0] - u[:, 1])
    return x - u * (t_hi - t_lo)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def sample(noise, text, stack, router, grid, *, spec, precision):
    """Final latents (B, H, W, C) from ``noise`` and prompts ``text``
    (B, 77, Dt).  ``spec`` is the hashable ``freeze(config)``; ``grid``
    the (S + 1,) time grid; ``precision`` one of ``PRECISIONS``."""
    cfg = dict(spec)

    def step(x, i):
        t_hi, t_lo = grid[i], grid[i + 1]
        w, idx = _route(cfg, router, x, t_hi, precision)
        u = _share(cfg, stack, 0, w, idx, x, t_hi, text, precision)
        return _euler(cfg, x, u, t_hi, t_lo), None

    x, _ = jax.lax.scan(step, noise, jnp.arange(grid.shape[0] - 1))
    return x


def sample_blocked(noise, text, blocks, router, grid, *, spec, precision):
    """``sample`` over experts held in blocks, one block a device.

    ``blocks`` are stacks of consecutive experts of equal size, block
    ``j`` wholly on one device; the router on another, or on one of
    theirs.  Each Euler step routes on the router's device, computes every
    block's share of the fused velocity on the block's own device (all at
    once, one program over the blocks' devices), sums the shares on the
    router's device in block order, and applies CFG and the Euler step
    there.  With one block it is ``sample`` itself.
    """
    if len(blocks) == 1:
        return sample(noise, text, blocks[0], router, grid, spec=spec,
                      precision=precision)
    home = _device(router)
    mesh = jax.sharding.Mesh(np.array([_device(b) for b in blocks]),
                             ("block",))
    whole = NamedSharding(mesh, P())

    def glue(*leaves):
        shape = (sum(a.shape[0] for a in leaves),) + leaves[0].shape[1:]
        return jax.make_array_from_single_device_arrays(
            shape, NamedSharding(mesh, P("block")), list(leaves))

    stack = jax.tree.map(glue, *blocks)
    text = jax.device_put(text, whole)
    x = jax.device_put(noise, home)
    ts = np.asarray(grid)
    for i in range(ts.shape[0] - 1):
        t_hi, t_lo = ts[i], ts[i + 1]
        w, idx = _route_on(router, x, t_hi, spec=spec, precision=precision)
        shares = _shares(stack, *jax.device_put((w, idx, x), whole), t_hi,
                         text, spec=spec, precision=precision, mesh=mesh)
        x = _euler_on(x, jax.device_put(shares, home), t_hi, t_lo, spec=spec)
    return x


def _device(tree):
    (device,) = jax.tree.leaves(tree)[0].devices()
    return device


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _route_on(router, x, t, *, spec, precision):
    return _route(dict(spec), router, x, t, precision)


@functools.partial(jax.jit, static_argnames=("spec", "precision", "mesh"))
def _shares(stack, w, idx, x, t, text, *, spec, precision, mesh):
    """Every block's share (blocks, B, 2, H, W, C), each on its device."""
    cfg = dict(spec)

    def one(stack, w, idx, x, t, text):
        n = jax.tree.leaves(stack)[0].shape[0]
        first = jax.lax.axis_index("block") * n
        return _share(cfg, stack, first, w, idx, x, t, text, precision)[None]

    return jax.shard_map(one, mesh=mesh, in_specs=(P("block"),) + (P(),) * 5,
                         out_specs=P("block"))(stack, w, idx, x, t, text)


@functools.partial(jax.jit, static_argnames=("spec",))
def _euler_on(x, shares, t_hi, t_lo, *, spec):
    u = shares[0]
    for j in range(1, shares.shape[0]):
        u = u + shares[j]
    return _euler(dict(spec), x, u, t_hi, t_lo)


def time_grid(num_steps: int) -> jax.Array:
    """The Euler grid, computed eagerly so every program reads the same
    bytes (a traced linspace may fold to values an ulp away, and t = 0.5
    sits exactly on a rounding tie of ``round(999 t)``)."""
    with jax.ensure_compile_time_eval():
        return jnp.linspace(1.0, 0.0, num_steps + 1)


def freeze(cfg: dict) -> tuple:
    """Hashable reference spec of a benchmark configuration."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    conv = cfg["conversion"]
    for x in cfg["experts"]:
        if (x["objective"], x["schedule"]) not in (("ddpm", "cosine"),
                                                   ("fm", "linear")):
            raise ValueError(f"the reference knows DDPM/cosine and "
                             f"FM/linear experts, not {x}")
    if conv["velocity_scaling"] != "piecewise":
        raise ValueError("the reference knows the piecewise dampening only")
    return tuple(sorted({
        "model": tuple(sorted(model.items())),
        "router": tuple(sorted(cfg["router"].items())),
        "objectives": tuple(x["objective"] for x in cfg["experts"]),
        "top_k": cfg["sampler"]["top_k"],
        "cfg_scale": cfg["sampler"]["cfg_scale"],
        "num_steps": cfg["sampler"]["num_steps"],
        "alpha_min": conv["alpha_min"],
        "clamp": conv["clamp"],
    }.items()))


#: the configuration keys that size one expert
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "patch_size",
              "latent_size", "latent_channels", "mlp_ratio", "text_dim",
              "text_len", "num_timesteps")
