"""The per-layer metrics of an expert-parallel cell on hand-built traces:
the exchange's own device time from each operation's name stack, and the
model step's and the ragged GEMM's shares of all the cell's chips."""

import os

import pytest

from bench import harness, trace as T
from bench.metrics_util import Run
from bench.tests.test_program_trace import _pb, _plane
from bench.tests.tiny import make_root, tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
NEW = ("mfu.ep", "ragged_gemm_roofline.ep", "expert_exchange_ms.ep")

EXCHANGE = "%all-reduce.9 = f32[16,32,32,4] all-reduce(...)"
GEMM = "%ragged_gemm.6 = f32[8] custom-call(...)"
STACKS = {
    EXCHANGE: "jit(_sample)/while/body/closed_call/shard_map/"
              "expert_exchange/psum",
    GEMM: "jit(_sample)/while/body/closed_call/shard_map/dot_general",
}


def _trace(chips=4, exchange=True):
    """A 0-100 ns window; on chip ``c`` the GEMM runs 10-50 and the
    exchange 50-(60 + c)."""
    devices = []
    for c in range(chips):
        ops = [("%while.1 = (f32[8]) while(...)", 5, 90), (GEMM, 10, 50)]
        if exchange:
            ops.append((EXCHANGE, 50, 60 + c))
        devices.append(ops)
    return T.Trace(devices=devices, spans=[("generate", 0, 100)])


def _write_xplane(root, chips=4, stacks=STACKS):
    d = os.path.join(root, ".bench_trace", "plugins", "profile", "run")
    os.makedirs(d)
    data = b"".join(_plane(f"/device:TPU:{c}", stacks,
                           lines=_pb(2, "XLA Ops")) for c in range(chips))
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(data)


def _run(trace, config, steps=2):
    return Run(config=config, traffic={"batch": 8}, peaks=PEAKS,
               got={"steps_traced": steps}, trace=trace,
               summary=T.summary(trace) if trace is not None else None)


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path, BENCH)


def _read(root, name):
    return harness.load_reader(harness.metric_readers(root)[name])


def test_exchange_own_time_per_step_averaged_over_chips(root):
    _write_xplane(root)
    got = _read(root, "expert_exchange_ms.ep")(_run(_trace(), tiny(4)))
    # chips 0-3 spend 10, 11, 12, 13 ns: 11.5 ns a chip over 2 steps
    assert got == pytest.approx(11.5e-6 / 2)


def test_exchange_reads_nothing_where_no_operation_carries_the_scope(root):
    """A program without the exchange (the one before it), a run without
    a trace, and a run whose trace directory is gone."""
    read = _read(root, "expert_exchange_ms.ep")
    assert read(_run(_trace(), tiny(4))) is None            # no trace dir
    _write_xplane(root, stacks={GEMM: STACKS[GEMM]})
    assert read(_run(_trace(), tiny(4))) is None
    assert read(_run(None, tiny(4))) is None


@pytest.mark.parametrize("ep,batch", [("mfu.ep", "mfu.batch"),
                                      ("ragged_gemm_roofline.ep",
                                       "ragged_gemm_roofline.batch")])
def test_shares_are_of_all_the_cells_chips(ep, batch):
    """Over ``expert_shards`` chips, a share of the one-chip reading of
    the same run divided by the chips; on one chip the same reading."""
    readers = harness.metric_readers(ROOT)
    one = harness.load_reader(readers[batch])
    many = harness.load_reader(readers[ep])
    trace = _trace()
    assert many(_run(trace, tiny(4))) == pytest.approx(
        one(_run(trace, tiny(4))) / 4)
    assert many(_run(trace, tiny())) == pytest.approx(
        one(_run(trace, tiny())))


def test_benchmark_reports_the_new_metrics_in_the_four_chip_cell():
    bench = harness.benchmark(ROOT)
    cell = harness.cell(ROOT, "xl2-28L-ep4")
    assert cell["workload"]["chips"] == cell["config"]["expert_shards"] == 4
    assert cell["config"]["num_layers"] == 28
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"img_per_s", "setup_s"} == {m["name"]
                                        for m in cell["end_to_end"]}
    # the one-chip shares stay out of the four-chip cell
    assert not {"mfu.batch", "ragged_gemm_roofline.batch"} & names
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["xl2-28L-ep4"]
