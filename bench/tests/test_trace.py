"""The trace reduction on a synthetic trace with known intervals."""

import pytest

from bench import trace as T


def _trace():
    # window: spans from 0 to 100 ns
    spans = [("generate", 0, 40), ("fetch", 40, 50), ("step", 50, 100)]
    dev0 = [
        ("fusion.1", 5, 15),
        ("ragged_gemm.3", 10, 20),      # overlaps fusion.1 by 5
        ("ragged_gemm.7", 20, 30),      # touches the previous end
        ("hetero_fuse_step", 60, 70),
        ("fusion.1", 90, 110),          # runs past the window's end
    ]
    dev1 = [("ragged_gemm.3", 0, 50)]
    return T.Trace(devices=[dev0, dev1], spans=spans)


def test_window_spans_the_benchmark_spans():
    assert T.window(_trace()) == (0, 100)


def test_busy_is_the_union_clipped_to_the_window():
    tr = _trace()
    # chip 0: [5, 30] + [60, 70] + [90, 100] = 25 + 10 + 10
    assert T.busy_ns(tr.devices[0], 0, 100) == 45
    s = T.summary(tr)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((45 + 50) / 2 * 1e-9)


def test_kernel_time_by_name_without_instance_number():
    total, count = T.kernel_ns(_trace(), "ragged_gemm")
    assert total == pytest.approx((10 + 10 + 50) / 2)
    assert count == 2
    assert T.kernel_ns(_trace(), "hetero_fuse_step") == (5.0, 0)


def test_idle_gaps_are_named_by_the_covering_host_span():
    gaps = T.idle_gaps(_trace())
    # gaps of chip 0: [0, 5] generate, [30, 60] mid 45 fetch, [70, 90] step
    assert [g[0] for g in gaps] == ["fetch", "step", "generate"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 5e-9])


def test_top_ops_rank_operations_inside_the_window():
    ops = dict(T.top_ops(_trace()))
    assert ops["ragged_gemm"] == pytest.approx(20e-9)
    assert ops["fusion"] == pytest.approx(10e-9)       # 90-110 is cut
    assert ops["hetero_fuse_step"] == pytest.approx(10e-9)


def test_nested_operations_count_once():
    """A device plane names operations by their HLO text and nests a
    loop's body inside the loop's own event."""
    dev = [
        ("%while.3 = (f32[8]) while(...)", 0, 100),
        ("%ragged_gemm.12 = f32[8192,768] custom-call(...)", 10, 40),
        ("%fusion.7 = f32[8] fusion(...)", 50, 60),
        ("%while.4 = (f32[8]) while(...)", 60, 90),
        ("%fusion.9 = f32[8] fusion(...)", 65, 75),
    ]
    tr = T.Trace(devices=[dev], spans=[("generate", 0, 100)])
    assert T.self_ns(dev) == [100 - 30 - 10 - 30, 30, 10, 30 - 10, 10]
    ops = dict(T.top_ops(tr))
    assert ops["while"] == pytest.approx((30 + 20) * 1e-9)
    assert ops["ragged_gemm"] == pytest.approx(30e-9)
    assert ops["fusion"] == pytest.approx(20e-9)
    assert T.kernel_ns(tr, "ragged_gemm") == (30.0, 1)
    assert T.summary(tr)["busy_s"] == pytest.approx(100e-9)


def test_a_trace_without_spans_has_no_window():
    with pytest.raises(ValueError):
        T.window(T.Trace(devices=[[("x", 0, 1)]], spans=[]))
