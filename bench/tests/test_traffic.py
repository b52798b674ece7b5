"""The traffic generator: the same work for every seed, in another order."""

import json
import os

import numpy as np
import pytest

from bench import traffic

MIX = {"kind": "open", "rate_rps": 1.15}


def test_every_seed_brings_the_same_arrivals_into_the_window():
    a = traffic.arrivals(MIX, 2**31 + 3, 20.0, 51.0)
    b = traffic.arrivals(MIX, 7, 20.0, 51.0)
    for due in (a, b):
        window = due[(due >= 20.0) & (due < 71.0)]
        assert len(window) == round(1.15 * 51.0)
        assert len(due) == round(1.15 * 20.0) + round(1.15 * 51.0)
        assert np.all(np.diff(due) > 0)
    assert not np.allclose(a, b)

    def window_gaps(due):
        return np.sort(np.diff(np.append(due[due >= 20.0], 71.0)))

    np.testing.assert_allclose(window_gaps(a), window_gaps(b), atol=1e-9)


def test_seed_reorders_neighbours_only():
    a = traffic.arrivals(MIX, 1, 0.0, 51.0)
    b = traffic.arrivals(MIX, 2, 0.0, 51.0)
    blk = traffic.BLOCK
    ga, gb = np.diff(np.append(a, 51.0)), np.diff(np.append(b, 51.0))
    for i in range(0, len(ga), blk):
        np.testing.assert_allclose(np.sort(ga[i:i + blk]),
                                   np.sort(gb[i:i + blk]), atol=1e-9)


def test_requests_are_unique_and_repeatable():
    k1, t1 = traffic.request(5, 0, 1, 77, 768)
    k2, t2 = traffic.request(5, 1, 1, 77, 768)
    k3, t3 = traffic.request(5, 0, 1, 77, 768)
    assert not np.array_equal(t1, t2) and not np.array_equal(k1, k2)
    np.testing.assert_array_equal(t1, t3)
    np.testing.assert_array_equal(k1, k3)
    assert t1.shape == (1, 77, 768) and t1.dtype == np.float32


TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def test_every_shipped_mix_loads():
    for name in os.listdir(TRAFFIC):
        mix = traffic.load(os.path.join(TRAFFIC, name))
        assert set(mix) == traffic.KEYS[mix["kind"]]


@pytest.mark.parametrize("name,change", [
    ("poisson-b2", {"arrivals": "bursty"}),
    ("poisson-b2", {"prompts": "zipf"}),
    ("closed-batch8", {"clients": 4}),
    ("closed-batch8", {"prompts": "zipf"}),
    ("closed-batch8", {"burst": 3}),
    ("poisson-b2", {"kind": "replay"}),
])
def test_a_setting_the_generator_does_not_implement_is_refused(
        tmp_path, name, change):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        mix = json.load(f)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps({**mix, **change}))
    with pytest.raises(ValueError):
        traffic.load(str(path))


def test_a_missing_key_is_refused(tmp_path):
    with open(os.path.join(TRAFFIC, "closed-batch8.json")) as f:
        mix = json.load(f)
    del mix["warmup_calls"]
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load(str(path))
