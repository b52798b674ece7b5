"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) is one of two kinds:

* ``"open"``: independent users.  Requests of ``images_per_request``
  images arrive at ``rate_rps`` requests per second, whatever the
  server's state, by the one law the generator implements,
  ``"arrivals": "stratified_poisson"``: a replayed, stratified Poisson
  trace.  The warm-up and the window each get ``round(rate * span)``
  arrivals whose gaps are the exponential quantiles
  ``-ln(1 - (i + 1/2) / n)``, scaled to fill the span exactly, in one
  fixed order that the seed shuffles within blocks of ``BLOCK``.  So
  every seed replays the same trace into the window, with the same
  bursts at the scale of a request's lifetime, and changes only the
  order of neighbouring arrivals.
* ``"closed"``: one client (``"clients": 1``) sending its next request
  of ``batch`` images when the last one's latents are back.

Each request carries a unique prompt embedding (``"prompts":
"unique"``) and a unique noise key, both drawn from the seed and the
request's index alone.  ``load`` refuses a mix with a key the generator
does not read or a setting it does not implement, so a file never states
traffic other than what runs.
"""

from __future__ import annotations

import json

import numpy as np


#: the keys of each kind of mix; every one is read
KEYS = {
    "open": {"kind", "arrivals", "rate_rps", "images_per_request",
             "prompts", "max_resident", "steps_per_tick", "max_queue_depth",
             "warmup_s", "drain_s", "trace_s", "check_requests"},
    "closed": {"kind", "clients", "batch", "prompts", "warmup_calls",
               "trace_s", "check_calls"},
}

#: the only values of these settings that the generator implements
IMPLEMENTED = {"arrivals": ("stratified_poisson",), "prompts": ("unique",),
               "clients": (1,)}


def load(path: str) -> dict:
    """The mix in ``path``; raises ``ValueError`` for a mix that states a
    key or a setting the generator does not implement."""
    with open(path) as f:
        mix = json.load(f)
    kind = mix.get("kind")
    if kind not in KEYS:
        raise ValueError(f"{path}: traffic kind must be 'open' or 'closed'")
    if set(mix) != KEYS[kind]:
        raise ValueError(
            f"{path}: a {kind} mix has the keys {sorted(KEYS[kind])}; "
            f"missing {sorted(KEYS[kind] - set(mix))}, "
            f"unknown {sorted(set(mix) - KEYS[kind])}")
    for key, values in IMPLEMENTED.items():
        if key in mix and mix[key] not in values:
            raise ValueError(f"{path}: {key} {mix[key]!r} is not implemented "
                             f"(only {list(values)})")
    return mix


#: random streams: timed requests, arrival order, warm-up requests
TIMED, ORDER, WARMUP = 1, 2, 4

#: neighbouring arrivals a seed may reorder
BLOCK = 4


def entropy(seed: int) -> int:
    """A seed of any sign and size as numpy seed material."""
    return seed % 2**64


def request(seed: int, i: int, images: int, text_len: int, text_dim: int,
            stream: int = TIMED) -> tuple[np.ndarray, np.ndarray]:
    """Request ``i``'s raw ``uint32[2]`` noise key and its prompt
    embeddings ``(images, text_len, text_dim)``; warm-up requests draw
    from a stream of their own."""
    rng = np.random.default_rng([entropy(seed), stream, i])
    key = rng.integers(0, 2**32, size=2, dtype=np.uint32)
    text = rng.standard_normal((images, text_len, text_dim),
                               dtype=np.float32)
    return key, text


def arrivals(mix: dict, seed: int, warm_s: float, window_s: float
             ) -> np.ndarray:
    """Due times (seconds from the start) of an open mix's requests: the
    warm-up's in ``[0, warm_s)``, then the window's in ``[warm_s,
    warm_s + window_s)``."""
    rate = float(mix["rate_rps"])
    due, start = [], 0.0
    for part, span in enumerate((warm_s, window_s)):
        n = round(rate * span)
        if n == 0:
            start += span
            continue
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = gaps[np.random.default_rng([0, ORDER, part]).permutation(n)]
        rng = np.random.default_rng([entropy(seed), ORDER, part])
        for b in range(0, n, BLOCK):
            gaps[b:b + BLOCK] = rng.permutation(gaps[b:b + BLOCK])
        gaps *= span / gaps.sum()
        due.append(start + np.concatenate([[0.0], np.cumsum(gaps[:-1])]))
        start += span
    return np.concatenate(due)
