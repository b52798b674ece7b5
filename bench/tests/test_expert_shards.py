"""A configuration whose experts span four chips (``expert_shards`` 4),
driven on four forced CPU devices in a process of its own: each expert's
weights live on its shard's device alone, the reference computed in
blocks agrees with the reference in one, a sound run is correct and a
broken one is not.  The harness refuses a cell whose chips do not match
its configuration's shards."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.four_devices import FAULTS
from bench.tests.tiny import EP4, make_root

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.join(REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.four_devices",
         str(tmp_path_factory.mktemp("four"))],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _got(four, name):
    got = four[name]
    assert "error" not in got, got["error"]
    return got


def test_four_devices_are_forced(four):
    assert _got(four, "devices")["count"] == 4


def test_expert_weights_live_on_their_shards_device(four):
    got = _got(four, "placement")
    for e, homes in enumerate(got["expert_homes"]):
        assert all(h == [got["shard_device"][e]] for h in homes), (e, homes)
    assert all(got["experts_equal"])
    for j, homes in enumerate(got["block_homes"]):
        assert all(h == [got["block_device"][j]] for h in homes), (j, homes)
    assert all(got["blocks_equal"])
    assert len(set(got["block_device"])) == 4


def test_engine_gets_the_expert_mesh(four):
    assert _got(four, "engine_mesh")["mesh"] == {"expert": 4, "data": 1}


def test_blocked_reference_matches_one_block(four):
    """Only the order of summation differs: within 1e-6 of the RMS."""
    assert _got(four, "blocked_reference")["gap"] <= 1e-6


def test_sharded_run_is_correct(four):
    got = _got(four, "run")
    assert got["correct"], got["checks"]
    assert got["count"] == 4 and got["attempted"] > 0 and got["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_sharded_broken_path_is_not_correct(four, fault):
    got = _got(four, f"run_{fault}")
    assert not got["correct"], got["checks"]


@pytest.mark.parametrize("chips,shards", [(1, 4), (4, 1), (4, 3)])
def test_cell_refuses_chips_that_do_not_match_shards(tmp_path, chips,
                                                     shards):
    root = make_root(tmp_path, BENCH)
    path = os.path.join(root, "bench", "configs", "tiny-ep4.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["expert_shards"] = shards
    with open(path, "w") as f:
        json.dump(cfg, f)
    bench = harness.benchmark(root)
    next(w for w in bench["workloads"] if w["name"] == EP4)["chips"] = chips
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with pytest.raises(ValueError):
        harness.cell(root, EP4)


def test_cell_takes_chips_that_match_shards(tmp_path):
    root = make_root(tmp_path, BENCH)
    assert harness.cell(root, EP4)["config"]["expert_shards"] == 4
