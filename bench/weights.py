"""Seeded random weights of a DiT ensemble, made on the device.

The layout is the expert and router parameter tree that the served
program reads (``patch_embed``, ``blocks``, ``cross_attn`` ... with the
layer axis leading inside ``blocks`` and ``cross_attn``), written out here
from the configuration's sizes so that neither the served program nor the
reference supplies its own weights.  Every leaf is drawn as a fresh
initialisation plus a seeded jitter of ``JITTER``, so that no output layer
sits at the zero of a fresh DiT: a leaf that starts at zero draws
``JITTER * N(0, 1)``, a matrix ``sqrt(1/fan_in + JITTER**2) * N(0, 1)``,
and the timestep table is the sinusoidal table plus the jitter.

``expert_list`` gives one tree per expert (what the serving engine takes),
``expert_blocks`` the same values as stacks with a leading expert axis
(what the reference takes).  Given the devices of a configuration's
``expert_shards``, both draw shard ``s`` (experts ``s * K/N`` up to
``(s + 1) * K/N``) on ``devices[s]`` alone, so no device holds another
shard's experts; with none, every expert is drawn on the default device.
Each shard is one jitted program keyed by the seed, and expert ``e`` is
drawn from ``fold_in(key, e)`` wherever it lives, so the values do not
depend on the layout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

JITTER = 0.02
#: fold-in constant of the router's weights (the experts take 0..K-1)
ROUTER_FOLD = 1000


def _dense(din: int, dout: int, *, bias: bool, zero: bool = False,
           lead: tuple = ()) -> dict:
    std = 0.0 if zero else 1.0 / math.sqrt(din)
    out = {"w": (lead + (din, dout), std)}
    if bias:
        out["b"] = (lead + (dout,), 0.0)
    return out


def layout(m: dict, *, router: bool) -> dict:
    """``{path: (shape, std)}`` tree of an expert (or the router) of
    model sizes ``m``; ``std`` is the initial scale before the jitter,
    ``None`` marks the sinusoidal timestep table."""
    d, L = m["d_model"], m["num_layers"]
    p = m["patch_size"]
    in_dim = p * p * m["latent_channels"]
    tokens = (m["latent_size"] // p) ** 2
    ff = int(d * m["mlp_ratio"])
    lead = (L,)
    tree = {
        "patch_embed": _dense(in_dim, d, bias=True),
        "pos_embed": {"emb": ((tokens, d), 0.02)},
        "t_embed": {
            "table": ((m["num_timesteps"], 256), None),
            "mlp1": _dense(256, d, bias=True),
            "mlp2": _dense(d, d, bias=True),
        },
        "blocks": {
            "attn": {k: _dense(d, d, bias=False, lead=lead)
                     for k in ("wq", "wk", "wv", "wo")},
            "mlp": {"w1": _dense(d, ff, bias=True, lead=lead),
                    "w2": _dense(ff, d, bias=True, lead=lead)},
        },
        "final_layer": {"mod": _dense(d, 2 * d, bias=False, zero=True),
                        "out": _dense(d, in_dim, bias=False, zero=True)},
        "adaln_single": {
            "mlp1": _dense(d, d, bias=True),
            "mlp2": _dense(d, 6 * d, bias=False, zero=True),
            "block_embed": ((L, 6, d), 1.0 / math.sqrt(d)),
        },
    }
    if router:
        tree["cls_head"] = _dense(d, m["num_classes"], bias=True)
    else:
        tree["text_proj"] = _dense(m["text_dim"], d, bias=True)
        tree["cross_attn"] = {
            "wq": _dense(d, d, bias=False, lead=lead),
            "wk": _dense(d, d, bias=False, lead=lead),
            "wv": _dense(d, d, bias=False, lead=lead),
            "wo": _dense(d, d, bias=False, zero=True, lead=lead),
        }
        tree["null_text_embed"] = {"emb": ((m["text_len"], m["text_dim"]),
                                           0.02)}
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def sinusoidal_table(num: int, dim: int) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(num)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _draw(tree: dict, key) -> dict:
    specs, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    leaves = []
    for i, (shape, std) in enumerate(specs):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if std is None:
            leaves.append(sinusoidal_table(*shape) + JITTER * z)
        else:
            leaves.append(math.sqrt(std * std + JITTER * JITTER) * z)
    return jax.tree.unflatten(treedef, leaves)


def seed_key(seed: int) -> np.ndarray:
    """Raw ``uint32[2]`` weight key of a seed of any size."""
    return np.random.default_rng([seed % 2**64, 0]).integers(
        0, 2**32, size=2, dtype=np.uint32)


def _frozen(m: dict) -> tuple:
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _expert_list(key, m: tuple, ids: tuple):
    m = dict(m)
    return [_draw(layout(m, router=False), jax.random.fold_in(key, e))
            for e in ids]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _expert_stack(key, m: tuple, ids: tuple):
    m = dict(m)
    keys = jnp.stack([jax.random.fold_in(key, e) for e in ids])
    return jax.vmap(lambda k: _draw(layout(m, router=False), k))(keys)


@functools.partial(jax.jit, static_argnums=(1,))
def _router(key, m: tuple):
    return _draw(layout(dict(m), router=True),
                 jax.random.fold_in(key, ROUTER_FOLD))


def _shards(n: int, devices) -> list:
    """``(expert ids, device)`` of each shard; one shard of all ``n`` on
    the default device (``None``) where ``devices`` is None."""
    if devices is None:
        return [(tuple(range(n)), None)]
    per = n // len(devices)
    return [(tuple(range(s * per, (s + 1) * per)), d)
            for s, d in enumerate(devices)]


def expert_list(seed: int, m: dict, n: int, devices=None) -> list:
    """One tree per expert; expert ``e`` on ``devices[e // (n / N)]``,
    uncommitted there, so that a program which gathers the list still
    may."""
    key, m = seed_key(seed), _frozen(m)
    out = []
    for ids, device in _shards(n, devices):
        with jax.default_device(device):
            out += _expert_list(key, m, ids)
    return out


def expert_blocks(seed: int, m: dict, n: int, devices=None) -> list:
    """The experts as one stack per shard, each on its shard's device."""
    key, m = seed_key(seed), _frozen(m)
    out = []
    for ids, device in _shards(n, devices):
        with jax.default_device(device):
            out.append(_expert_stack(key, m, ids))
    return out


def router(seed: int, m: dict, device=None) -> dict:
    with jax.default_device(device):
        return _router(seed_key(seed), _frozen(m))
