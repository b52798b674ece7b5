#!/usr/bin/env python3
"""Sweep the arrival rate of an open cell to find its knee, on the chip.

    python3 bench/knee.py --workload b2-poisson --seed 3 --rates 1,1.5,2 \
        --seconds 30
    python3 bench/knee.py --workload b2-poisson --seed 3 \
        --rates x0.6,x0.8,x0.9,x1.0,x1.1

A rate written ``xF`` is F times the capacity the rolling batch would have
if the host cost nothing: ``max_resident / (num_steps * tick)``, with the
tick timed on a full batch first.

One process builds the cell's engine once and serves each rate in turn
(the mix file's other settings unchanged) for ``--seconds`` after its
warm-up.  A rate is sustained when the requests due in the second half of
the window wait no longer than those of the first half (median latency
within 15 %) and none is refused or left unfinished: the backlog does not
grow.  The knee is the highest sustained rate; the cell's mix file then
states a rate of about four fifths of it.  Prints one JSON line per rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, run, system  # noqa: E402
from bench.metrics_util import percentile  # noqa: E402

GROWTH = 1.15


def _f(x):
    return None if x is None else float(x)


def full_batch_capacity(engine, cfg, mix, seed, ticks=20) -> float:
    """Requests per second a full rolling batch completes, from the time
    of ``ticks`` ticks with every row resident."""
    import time

    import numpy as np

    from bench import traffic

    sched = system.scheduler(engine, mix)
    rows = mix["max_resident"] // mix["images_per_request"]
    for i in range(rows):
        sched.submit(*traffic.request(seed, i, mix["images_per_request"],
                                      cfg["text_len"], cfg["text_dim"],
                                      traffic.WARMUP))
    sched.step()
    bucket = next(iter(sched._buckets.values()))
    np.asarray(bucket.x[:1])
    t = time.perf_counter()
    for _ in range(ticks):
        sched.step()
    np.asarray(bucket.x[:1])
    tick = (time.perf_counter() - t) / ticks
    sched.run_until_idle()
    return rows / (cfg["sampler"]["num_steps"] * tick)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform == "cpu":
        print("bench/knee.py: no accelerator", file=sys.stderr)
        return 2
    cell = harness.cell(ROOT, args.workload)
    cfg, mix = cell["config"], cell["traffic"]
    system.enable_compile_cache(os.path.join(ROOT, run.CACHE))
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    counter = run.CompileCounter()
    engine = system.build_engine(cfg, args.seed)
    capacity = full_batch_capacity(engine, cfg, mix, args.seed)
    print(json.dumps({"capacity_rps": capacity}), flush=True)
    knee = None
    for word in args.rates.split(","):
        rate = capacity * float(word[1:]) if word.startswith("x") \
            else float(word)
        got = run.drive_open(engine, cfg, dict(mix, rate_rps=rate),
                             args.seed, args.seconds, None, counter)
        reqs = sorted(got["window"].values(), key=lambda r: r["due"])
        half = len(reqs) // 2

        def p50(rs):
            return percentile([r["done"] - r["due"] for r in rs
                               if r["state"] == "DONE"], 50)

        first, second = p50(reqs[:half]), p50(reqs[half:])
        failed = len(reqs) - len(got["done"])
        ok = bool(failed == 0 and first is not None and second is not None
                  and second <= GROWTH * first)
        if ok:
            knee = rate
        print(json.dumps({
            "rate_rps": rate, "requests": len(reqs), "failed": failed,
            "p50_first_half_s": _f(first), "p50_second_half_s": _f(second),
            "p90_s": _f(percentile(got["latencies"], 90)),
            "tick_ms": 1e3 * sum(got["ticks"]) / max(len(got["ticks"]), 1),
            "sustained": ok}), flush=True)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
