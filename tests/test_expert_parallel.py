"""Expert-parallel serving on four forced CPU devices (a subprocess, since
the in-process suite keeps the single real CPU device): each device holds
and runs its own experts, and one exchange a step joins their
predictions.  ``expert_parallel_devices.py`` takes the readings; the
tests here judge them."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")
SHARDS = 4


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={SHARDS}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_FORCE_PALLAS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "expert_parallel_devices.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _got(four, name):
    got = four[name]
    assert "error" not in got, got["error"]
    return got


def test_four_devices_are_forced(four):
    assert _got(four, "devices")["count"] == SHARDS


def test_expert_parallel_generate_matches_one_device(four):
    """Equal to 1e-6 of the latent RMS once both engines run the same
    small-row products (measured 0.0); with the program's own all-experts
    GEMM for those rows the CPU rounds them by the GEMM's width, which
    3 CFG-7.5 steps carry to about 2e-5 (measured 2.1e-5)."""
    got = _got(four, "generate")
    assert got["mesh"] == {"expert": SHARDS, "data": 1}
    assert got["finite"]
    assert got["gap"]["per_pair"] <= 1e-6, got
    assert got["gap"]["all_experts"] <= 1e-4, got
    assert got["int8_gap"] <= 1e-4, got


def test_expert_parallel_rolling_tick_matches_one_device(four):
    got = _got(four, "rolling_tick")["gap"]
    assert got["per_pair"] <= 1e-6, got
    assert got["all_experts"] <= 1e-4, got


def test_routing_every_pair_to_one_shard_matches_one_device(four):
    """The other three shards skip every row tile of the step."""
    got = _got(four, "one_shard_routing")["gap"]
    assert got["per_pair"] <= 1e-6, got
    assert got["all_experts"] <= 1e-4, got


def test_skipping_dead_tiles_is_bitwise(four):
    """Through the Pallas kernels, skipping the pairs a shard does not own
    gives the latents of computing every pair and masking the others."""
    got = _got(four, "skipping_is_bitwise")
    assert got["equal"], got


def test_row_counter_reports_each_shards_owned_rows(four):
    """On the expert mesh each shard counts its owned pairs × g; the
    busiest shard's rows are the plan's busiest, and nothing is counted
    beyond the routed rows.  One device is one shard that runs them all."""
    got = _got(four, "shard_rows")
    sharded, one = got["sharded"], got["one_device"]
    assert sharded["shard_rows_per_step"] == got["plan_rows_per_step"]
    assert sharded["busiest_shard_rows_per_step"] == got["plan_busiest"]
    assert sharded["padding_overhead"] == 0.0
    routed = sum(got["plan_rows_per_step"])
    assert sharded["padded_rows_per_step"] == routed
    assert one["shard_rows_per_step"] == [routed]
    assert one["busiest_shard_rows_per_step"] == routed


@pytest.mark.parametrize("store", ["dense", "int8", "elastic"])
def test_store_leaves_live_on_their_shards_device(four, store):
    got = _got(four, "placement")[store]
    per = got["per"]
    assert got["slots"] == SHARDS * per
    for homes in got["homes"]:
        assert homes == [[s, s * per, (s + 1) * per] for s in range(SHARDS)]
    assert all(spec.startswith("PartitionSpec('expert'")
               for spec in got["specs"]), got["specs"]


@pytest.mark.parametrize("store", ["dense", "int8", "elastic"])
def test_no_device_held_more_than_its_shard_during_set_up(four, store):
    got = _got(four, "placement")[store]
    assert len(got["stacks"]) == SHARDS, got["stacks"]
    for stack in got["stacks"]:
        assert stack["experts"] <= got["per"], stack
        assert len(stack["devices"]) == 1, stack
    assert sorted(s["devices"][0] for s in got["stacks"]) == list(
        range(SHARDS))


def test_placement_is_a_host_span(four):
    assert "engine.place_experts" in _got(four, "place_span")["spans"]


def test_shards_shares_sum_to_the_one_device_predictions(four):
    got = _got(four, "shares")
    assert got["gap"] <= 1e-6, got
    assert got["owners"] == [1] * len(got["owners"])
    assert all(got["owner_is_shard"])


def test_shards_pallas_shares_skip_dead_tiles_exactly(four):
    """Through the Pallas kernel (interpret mode) each shard's launches
    skip the row tiles of pairs it does not own; its owned pairs are
    bitwise the one-device apply's."""
    got = _got(four, "shares_pallas")
    assert got["gap"] == 0.0, got
    assert got["owners"] == [1] * len(got["owners"])
    assert all(got["owner_is_shard"])


def test_compiled_step_has_one_exchange_and_gathers_no_weight(four):
    got = _got(four, "compiled_step")
    assert got["whiles"] == 1
    (op,) = got["collectives"]
    assert op["op"] == "all-reduce", op
    assert "while/body" in op["op_name"], op
    assert "expert_exchange" in op["op_name"], op
