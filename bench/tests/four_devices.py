"""The harness on four devices, at the tiny size: run as a script in a
process whose CPU backend was forced to four devices before JAX started
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), since a process
keeps the device count it first saw.

    python3 -m bench.tests.four_devices <scratch dir>

Prints one JSON line: for each check its readings, or the traceback of
what it raised.  ``test_expert_shards.py`` runs it and judges them.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

import jax
import numpy as np
import pytest

from bench import check, run, system, weights
from bench.tests import test_faults
from bench.tests.tiny import EP4, make_root, tiny

SEED = test_faults.SEED
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the faults a closed cell can have (``test_faults.FAULTS``)
FAULTS = ("unchanged", "half_batch", "altered", "control")


def _homes(tree) -> list:
    """The device ids that hold each leaf of ``tree``."""
    return [sorted(d.id for d in leaf.devices())
            for leaf in jax.tree.leaves(tree)]


def _same(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def placement() -> dict:
    cfg = tiny(4)
    devices = system.expert_devices(cfg)
    m = system.model_sizes(cfg)
    n = len(cfg["experts"])
    placed = weights.expert_list(SEED, m, n, devices)
    blocks = weights.expert_blocks(SEED, m, n, devices)
    # the single-device draw (the list equals it: test_reference.py)
    (stack,) = weights.expert_blocks(SEED, m, n)
    per = n // len(devices)
    return {
        "shard_device": [devices[e // per].id for e in range(n)],
        "expert_homes": [_homes(placed[e]) for e in range(n)],
        "experts_equal": [
            _same(placed[e], jax.tree.map(lambda a, e=e: a[e], stack))
            for e in range(n)],
        "block_homes": [_homes(b) for b in blocks],
        "blocks_equal": [
            _same(b, jax.tree.map(lambda a, j=j: a[j * per:(j + 1) * per],
                                  stack))
            for j, b in enumerate(blocks)],
        "block_device": [d.id for d in devices],
    }


def blocked_reference() -> dict:
    key = np.array([3, 5], np.uint32)
    text = np.random.default_rng(1).standard_normal((4, 8, 16),
                                                    dtype=np.float32)
    one = check.reference_latents(tiny(), SEED, [key], [text])
    four = check.reference_latents(tiny(4), SEED, [key], [text])
    return {"gap": check.gap(four, one),
            "max_abs": float(np.max(np.abs(four - one)))}


def sharded_run(root: str, fault: str | None) -> dict:
    mp = pytest.MonkeyPatch()
    try:
        if fault == "control":
            test_faults._control(mp, tiny(4))
        elif fault is not None:
            test_faults.FAULTS[fault](mp)
        res = run.run(root, EP4, SEED, 0.5, False, require_chip=False,
                      cache=False)
    finally:
        mp.undo()
    return {"correct": res["correct"], "checks": res["checks"],
            "attempted": res["attempted"], "failed": res["failed"],
            "count": res["device"]["count"]}


def engine_mesh() -> dict:
    engine = system.build_engine(tiny(4), SEED)
    return {"mesh": dict(engine.mesh.shape)}


def main(argv) -> int:
    root = make_root(argv[1], BENCH)
    # as ``run.run`` sets it, so that its programs are the ones compiled here
    jax.config.update("jax_default_matmul_precision", "highest")
    checks = {"devices": lambda: {"count": jax.device_count()},
              "placement": placement,
              "blocked_reference": blocked_reference,
              "engine_mesh": engine_mesh,
              "run": lambda: sharded_run(root, None)}
    for fault in FAULTS:
        checks[f"run_{fault}"] = lambda f=fault: sharded_run(root, f)
    out = {}
    for name, fn in checks.items():
        try:
            out[name] = fn()
        except Exception:                   # reported, judged by the test
            out[name] = {"error": traceback.format_exc()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
