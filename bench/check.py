"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
what the window served, drawn from the seed, is run again through the
plain reference (``reference.py``, float32 at ``highest``) from each
request's own noise key and prompt, with weights that ``weights.py``
makes anew from the seed, in blocks of the configuration's
``expert_shards``, one block a device.  The number compared is

    latent_gap = max over sampled images of max|served - ref| / RMS(ref)

against the configuration's ``check.latent_gap_limit``, and every served
value must be finite.  The sample covers the router's choices, the
routed experts' forwards (ragged GEMMs, attention, the XLA dense layers),
the fused convert/CFG/Euler step, and, in an open mix, that the scheduler
returned each request its own rows.
"""

from __future__ import annotations

import numpy as np

from bench import reference, traffic, weights


def gap(served, ref) -> float:
    """Widest per-image ``max|served - ref| / RMS(ref)``."""
    s = np.asarray(served, np.float64)
    r = np.asarray(ref, np.float64)
    return max(float(np.max(np.abs(s[i] - r[i])) / np.sqrt(np.mean(r[i] ** 2)))
               for i in range(r.shape[0]))


def sample(cfg: dict, mix: dict, seed: int, pool: list):
    """Draw the compared requests from ``pool`` (``(index, served)``
    pairs, served as host latents; an open mix's entries are records with
    ``out``).  Returns ``(keys, texts, served)``: per request its noise key
    and prompts, and the served latents stacked over images."""
    rng = np.random.default_rng([traffic.entropy(seed), 3])
    n = mix["check_requests"] if mix["kind"] == "open" else mix["check_calls"]
    picks = sorted(rng.choice(len(pool), size=min(n, len(pool)),
                              replace=False).tolist())
    per = mix["images_per_request"] if mix["kind"] == "open" else mix["batch"]
    keys, texts, served = [], [], []
    for p in picks:
        i, got = pool[p]
        key, text = traffic.request(seed, i, per, cfg["text_len"],
                                    cfg["text_dim"])
        keys.append(key)
        texts.append(text)
        served.append(got["out"] if isinstance(got, dict) else got)
    return keys, texts, np.concatenate(served) if served else None


def reference_latents(cfg: dict, seed: int, keys, texts,
                      precision: str = "highest", devices=None) -> np.ndarray:
    """The reference's final latents for the sampled requests.

    With ``expert_shards`` N > 1 in ``cfg`` the experts are drawn as N
    blocks, block ``j`` on ``devices[j]`` (default: the first N devices),
    and the router on ``devices[0]``.
    """
    import jax
    import jax.numpy as jnp

    shards = cfg.get("expert_shards", 1)
    if shards == 1:
        devices = None
    elif devices is None:
        devices = jax.devices()[:shards]
    shape = (cfg["latent_size"], cfg["latent_size"], cfg["latent_channels"])
    noise = jnp.concatenate([
        jax.random.normal(jnp.asarray(k), (t.shape[0],) + shape, jnp.float32)
        for k, t in zip(keys, texts)])
    text = jnp.asarray(np.concatenate(texts))
    m = {k: cfg[k] for k in reference.MODEL_KEYS}
    blocks = weights.expert_blocks(seed, m, len(cfg["experts"]), devices)
    router = weights.router(seed, cfg["router"],
                            None if devices is None else devices[0])
    out = reference.sample_blocked(
        noise, text, blocks, router,
        reference.time_grid(cfg["sampler"]["num_steps"]),
        spec=reference.freeze(cfg), precision=precision)
    return np.asarray(out)


def check(cfg: dict, mix: dict, seed: int, pool: list, devices=None) -> dict:
    """``{"correct": bool, "numbers": {name: {"value", "limit"}}}``;
    ``devices`` as ``reference_latents`` takes them."""
    limit = cfg["check"]["latent_gap_limit"]
    keys, texts, served = sample(cfg, mix, seed, pool)
    if served is None:
        return {"correct": False, "numbers": {
            "latent_gap": {"value": None, "limit": limit},
            "nonfinite": {"value": None, "limit": 0}}}
    ref = reference_latents(cfg, seed, keys, texts, devices=devices)
    g = gap(served, ref)
    bad = int(np.size(served) - np.count_nonzero(np.isfinite(served)))
    return {
        "correct": bool(bad == 0 and g <= limit),
        "numbers": {"latent_gap": {"value": g, "limit": limit},
                    "nonfinite": {"value": bad, "limit": 0}},
    }
