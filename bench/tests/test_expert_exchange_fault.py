"""The collective of an expert-parallel cell is under the check: on four
forced CPU devices the tiny four-chip cell reads correct, and with the
exchange left out (each chip keeps its own masked share of the
predictions) it reads not correct, on the gap and not elsewhere."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.join(REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.four_devices_exchange",
         str(tmp_path_factory.mktemp("exchange"))],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _got(runs, name):
    got = runs[name]
    assert "error" not in got, got["error"]
    return got


def test_sound_expert_parallel_run_is_correct(runs):
    got = _got(runs, "sound")
    assert got["correct"], got["checks"]
    assert got["count"] == 4 and got["attempted"] > 0 and got["failed"] == 0


def test_run_without_the_exchange_is_not_correct(runs):
    got = _got(runs, "no_exchange")
    assert not got["correct"], got["checks"]
    gap = got["checks"]["latent_gap"]
    assert gap["value"] > gap["limit"], got["checks"]
    assert got["checks"]["nonfinite"]["value"] == 0
