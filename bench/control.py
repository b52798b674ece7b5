#!/usr/bin/env python3
"""Read the control of a cell's check on the chip: the reference one
precision step below the configuration's (``high``: three bfloat16 passes,
for float32 at ``highest``), put in the served program's place.

    python3 bench/control.py --workload b2-batch --seeds 11,12,13

For each seed it draws the requests the check would compare (the cell's
traffic, its first indices), computes the reference at ``highest`` and
the control, and prints ``latent_gap`` of the control beside the cell's
limit: the control has to read above it.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, harness, traffic  # noqa: E402

#: the precision one step below each stated one
LOWER = {"highest": "high", "high": "default"}


def requests(cfg: dict, mix: dict, seed: int):
    """Keys and prompts of the requests a check of this mix compares."""
    if mix["kind"] == "open":
        n, per = mix["check_requests"], mix["images_per_request"]
    else:
        n, per = mix["check_calls"], mix["batch"]
    pairs = [traffic.request(seed, i, per, cfg["text_len"], cfg["text_dim"])
             for i in range(n)]
    return [k for k, _ in pairs], [t for _, t in pairs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform == "cpu":
        print("bench/control.py: no accelerator", file=sys.stderr)
        return 2
    cell = harness.cell(ROOT, args.workload)
    cfg, mix = cell["config"], cell["traffic"]
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    lower = LOWER[cfg["matmul_precision"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        keys, texts = requests(cfg, mix, seed)
        ref = check.reference_latents(cfg, seed, keys, texts)
        ctl = check.reference_latents(cfg, seed, keys, texts, lower)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": lower,
            "latent_gap": check.gap(ctl, ref),
            "limit": cfg["check"]["latent_gap_limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
