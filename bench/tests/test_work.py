"""Work counts against hand counts at dit-b2 and DiT-XL/2 widths."""

import json
import os

import pytest

from bench import work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,layers", [("ddm8-dit-b2", 12),
                                         ("ddm8-dit-xl2", 7)])
def test_token_gemm_sites(name, layers):
    m = _cfg(name)
    sites = work.token_gemm_sites(m, pairs=16, g=2)
    assert len(sites) == 2 + 8 * layers              # 98 at dit-b2
    rows = {n: r for n, r, _, _ in sites}
    assert rows["patch_embed"] == 16 * 256
    assert rows["l0.self.q"] == 16 * 256             # once per pair
    assert rows["l0.cross.q"] == 16 * 2 * 256        # per (pair, branch)
    assert rows["l1.self.q"] == 16 * 2 * 256
    assert rows["final.out"] == 16 * 2 * 256


def test_ragged_gemm_flops_and_bytes_by_hand_at_b2():
    m = _cfg("ddm8-dit-b2")
    d, ff, t = 768, 3072, 256
    pairs, g = 16, 2
    pb, pp = pairs * g * t, pairs * t
    flops = 2 * (pp * 16 * d + 4 * pp * d * d + 4 * 11 * pb * d * d
                 + 12 * (2 * pb * d * d + 2 * pb * d * ff) + pb * d * 16)
    f, b = work.ragged_gemm(m, pairs, g, experts=8, weight_bytes=4)
    assert f == pytest.approx(flops)
    weights = 8 * 4 * (16 * d + 12 * (6 * d * d + 2 * d * ff) + d * 16)
    rows = 4 * (pp * (16 + d) + 4 * pp * 2 * d + 4 * 11 * pb * 2 * d
                + 12 * (2 * pb * 2 * d + 2 * pb * (d + ff)) + pb * (d + 16))
    assert b == pytest.approx(weights + rows)
    # one pair touches one expert's weights, not eight
    f1, b1 = work.ragged_gemm(m, 1, g, experts=8, weight_bytes=4)
    assert b1 < b / 8


@pytest.mark.parametrize("param_dtype,nbytes", [("native", 4), ("fp32", 4),
                                                ("bf16", 2), ("int8", 1)])
def test_weight_bytes_follow_the_store_type(param_dtype, nbytes):
    m = _cfg("ddm8-dit-b2")
    assert work.weight_bytes(param_dtype) == nbytes
    f4, b4 = work.ragged_gemm(m, 16, 2, 8, 4)
    f, b = work.ragged_gemm(m, 16, 2, 8, work.weight_bytes(param_dtype))
    assert f == f4
    weights = 8 * (16 * 768 + 12 * (6 * 768 * 768 + 2 * 768 * 3072)
                   + 768 * 16)
    assert b4 - b == pytest.approx((4 - nbytes) * weights)


def test_unknown_store_type_is_an_error():
    with pytest.raises(KeyError):
        work.weight_bytes("int4")


def test_model_flops_counts_router_and_experts():
    m = _cfg("ddm8-dit-b2")
    f = work.model_flops(m, images=8, k=2, g=2)
    assert f == pytest.approx(work.router_forward(m["router"], 8)
                              + work.expert_forward(m, 16, 2))
    # the prompt-free prefix is paid once per pair, so two branches cost
    # less than twice one
    assert work.expert_forward(m, 16, 2) < 2 * work.expert_forward(m, 16, 1)
    # about 2.2 TFLOP per step at batch 8 (56 GFLOP per expert forward)
    assert 1.8e12 < f < 2.6e12
