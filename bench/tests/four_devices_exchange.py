"""The expert-parallel cell on four devices, at the tiny size, with and
without the exchange that joins the chips' predictions: run as a script
in a process whose CPU backend was forced to four devices before JAX
started (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

    python3 -m bench.tests.four_devices_exchange <scratch dir>

Prints one JSON line: for each run its readings, or the traceback of what
it raised.  ``test_expert_exchange_fault.py`` runs it and judges them.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

import jax
import pytest

from bench import run
from bench.tests import test_faults
from bench.tests.tiny import EP4, make_root

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _no_exchange(monkeypatch):
    """The exchange is left out: each chip keeps its own masked share of
    the step's predictions, as if the collective never ran."""
    from repro.core import dispatch

    monkeypatch.setattr(dispatch, "_exchange", lambda out: out)


def sharded_run(root: str, fault) -> dict:
    mp = pytest.MonkeyPatch()
    try:
        if fault is not None:
            fault(mp)
        res = run.run(root, EP4, test_faults.SEED, 0.5, False,
                      require_chip=False, cache=False)
    finally:
        mp.undo()
    return {"correct": res["correct"], "checks": res["checks"],
            "attempted": res["attempted"], "failed": res["failed"],
            "count": res["device"]["count"]}


def main(argv) -> int:
    root = make_root(argv[1], BENCH)
    jax.config.update("jax_default_matmul_precision", "highest")
    out = {}
    for name, fault in (("sound", None), ("no_exchange", _no_exchange)):
        try:
            out[name] = sharded_run(root, fault)
        except Exception:                   # reported, judged by the test
            out[name] = {"error": traceback.format_exc()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
