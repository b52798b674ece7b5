"""The program's spans and device scopes: the readings on hand-built
traces, the metric readers on runs with and without them, and the spans
a real profiler trace of the scheduler holds."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness, program_trace as P, trace as T
from bench.metrics_util import Run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NEW = ("ticks_in_flight.serve", "tick_dispatch_ms.serve",
       "step_device_ms.serve", "router_step_ms.batch",
       "attention_step_ms.batch", "layer_weights_step_ms.batch")

CALL = " = f32[8] custom-call(...)"


def _traces():
    """A window of 0-100 ns holding a tick (benchmark span ``step``) whose
    program spans cover 10-60, and a device step that runs router,
    attention, weight slices and the fused-step kernel."""
    bench_spans = [("step", 0, 60), ("fetch", 60, 100)]
    dev = [
        ("%while.1 = (f32[8]) while(...)", 20, 80),
        ("%fusion.2 = f32[8] fusion(...)", 22, 30),          # router
        ("%fusion.3 = f32[8] fusion(...)", 30, 40),          # attention
        ("%slice_bitcast_fusion.4 = f32[8] fusion(...)", 40, 45),
        ("%pad.5 = f32[8] pad(...)", 45, 47),
        ("%ragged_gemm.6" + CALL, 47, 60),
        ("%hetero_fuse_step.7" + CALL, 62, 64),
        ("%hetero_fuse_step.7" + CALL, 70, 72),
        ("%pad.8 = f32[8] pad(...)", 72, 73),                # under fused_step
        ("%hetero_fuse_step.7" + CALL, 95, 110),             # past the window
    ]
    scopes = [None, "router", "attention", "layer_weights", "layer_weights",
              None, "fused_step", "fused_step", "fused_step", "fused_step"]
    trace = T.Trace(devices=[[d[:3] for d in dev]], spans=bench_spans)
    pt = P.ProgramTrace(
        spans=[("sched.admit", 2, 8, {}),
               ("sched.advance", 10, 15, {"inflight": 3}),
               ("sched.advance", 16, 19, {"inflight": 5}),
               ("request.resolve", 85, 90, {"seq": 7}),
               ("sched.advance", 98, 104, {"inflight": 9})],   # cut off
        ops=[[d + (s,) for d, s in zip(dev, scopes)]])
    return trace, pt


def test_scope_is_the_outermost_named_scope():
    assert P.scope_of("jit(_step)/jit(main)/router/while/body/attention/"
                      "dot_general") == "router"
    assert P.scope_of("jit(_sample)/while/body/attention/dot_general") \
        == "attention"
    assert P.scope_of("jit(_sample)/while/body/routers/dot") is None
    assert P.scope_of("") is None


def _pb(field: int, value) -> bytes:
    """One protobuf field: a varint for an int, length-delimited else."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(field << 3 | 2) + varint(len(value)) + value


def _plane(name, ops, lines=b""):
    """An ``XPlane`` whose event metadata names ``ops`` (name -> stack)."""
    body = _pb(2, name) + _pb(3, lines)
    body += _pb(5, _pb(1, 7) + _pb(2, _pb(1, 7) + _pb(2, "tf_op")))
    body += _pb(5, _pb(1, 8) + _pb(2, _pb(1, 8) + _pb(2, "hlo_category")))
    for i, (op, stack) in enumerate(ops.items(), 1):
        meta = _pb(1, i) + _pb(2, op) + _pb(4, f"op{i}")
        meta += _pb(5, _pb(1, 8) + _pb(5, "loop fusion"))
        if stack is not None:
            meta += _pb(5, _pb(1, 7) + _pb(5, stack))
        body += _pb(4, _pb(1, i) + _pb(2, meta))
    return _pb(1, body)


def test_name_stacks_come_from_device_event_metadata():
    data = _plane("/host:CPU", {"sched.advance": "host/router/x"}) + \
        _plane("/device:TPU:0", {
            "%fusion.1 = f32[8] fusion(...)": "jit(_sample)/while/body/"
                                              "router/reduce_sum:",
            "%copy.2 = f32[8] copy(...)": None,
        }, lines=_pb(2, "XLA Ops") + _pb(4, _pb(1, 1))) + \
        _plane("/device:TPU:1", {
            "%fusion.1 = f32[8] fusion(...)": "jit(_sample)/attention/x:",
            "%pad.3 = f32[8] pad(...)": "jit(_sample)/layer_weights/pad:",
        })
    stacks = P.name_stacks(data)
    assert stacks == {
        "%fusion.1 = f32[8] fusion(...)":
            "jit(_sample)/while/body/router/reduce_sum:",
        "%pad.3 = f32[8] pad(...)": "jit(_sample)/layer_weights/pad:",
    }
    assert P.scope_of(stacks["%fusion.1 = f32[8] fusion(...)"]) == "router"


def test_scope_own_time_inside_the_window():
    _, pt = _traces()
    assert P.scope_ns(pt, "router", 0, 100) == 8
    assert P.scope_ns(pt, "attention", 0, 100) == 10
    assert P.scope_ns(pt, "layer_weights", 0, 100) == 5 + 2
    # the kernel launches at 62 and 70 and the pad; 95-110 is cut
    assert P.scope_ns(pt, "fused_step", 0, 100) == 2 + 2 + 1
    assert P.scope_ns(pt, "router", 50, 100) is None


def test_nested_operations_count_once_under_a_scope():
    pt = P.ProgramTrace(spans=[], ops=[[
        ("%conditional.1 = f32[8] conditional(...)", 0, 50, "router"),
        ("%fusion.2 = f32[8] fusion(...)", 10, 30, "router"),
        ("%fusion.3 = f32[8] fusion(...)", 30, 40, None),
    ]])
    assert P.scope_ns(pt, "router", 0, 100) == (50 - 20 - 10) + 20


def test_span_mean_and_arguments_inside_the_window():
    _, pt = _traces()
    assert P.span_durations_ns(pt, "sched.advance", 0, 100) == [5, 3]
    assert P.span_args(pt, "sched.advance", "inflight", 0, 100) == [3, 5]
    assert P.span_args(pt, "request.resolve", "seq", 0, 100) == [7]
    assert P.span_args(pt, "sched.admit", "inflight", 0, 100) == []


def test_device_steps_count_kernel_launches_under_fused_step():
    _, pt = _traces()
    assert P.device_steps(pt, 0, 100) == 2
    assert P.device_steps(P.ProgramTrace(spans=[], ops=[]), 0, 100) == 0


def test_idle_gaps_name_the_program_span_inside_the_benchmark_span():
    trace, pt = _traces()
    gaps = P.idle_gaps(trace, pt)
    # chip 0 idles 0-20 (mid 10, inside sched.advance), 80-95 (mid 87.5,
    # inside request.resolve) and 100 is the window's end
    assert gaps == [["step/sched.advance", pytest.approx(20e-9)],
                    ["fetch/request.resolve", pytest.approx(15e-9)]]
    # the benchmark's own naming is unchanged
    assert [g[0] for g in T.idle_gaps(trace)] == ["step", "fetch"]


def _run(trace, steps=2):
    summary = T.summary(trace) if trace is not None else None
    return Run(config={}, traffic={"batch": 8}, peaks=None,
               got={"steps_traced": steps}, trace=trace, summary=summary)


def _readers():
    paths = harness.metric_readers(ROOT)
    return {name: harness.load_reader(paths[name]) for name in NEW}


def test_readers_read_the_program_trace(monkeypatch):
    trace, pt = _traces()
    monkeypatch.setattr(P, "read_xspace", lambda d: pt)
    read = _readers()
    run = _run(trace)
    assert read["ticks_in_flight.serve"](run) == 4.0
    assert read["tick_dispatch_ms.serve"](run) == pytest.approx(4e-6)
    busy_ms = 1e3 * T.summary(trace)["busy_s"]
    assert read["step_device_ms.serve"](run) == pytest.approx(busy_ms / 2)
    assert read["router_step_ms.batch"](run) == pytest.approx(8e-6 / 2)
    assert read["attention_step_ms.batch"](run) == pytest.approx(10e-6 / 2)
    assert read["layer_weights_step_ms.batch"](run) == \
        pytest.approx(7e-6 / 2)


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_program_spans_or_scopes(
        name, monkeypatch):
    """A program without spans and scopes (the one before them), a run
    without a trace, and a run whose trace directory is gone."""
    trace, pt = _traces()
    bare = P.ProgramTrace(spans=[], ops=[[d[:3] + (None,) for d in pt.ops[0]]])
    monkeypatch.setattr(P, "read_xspace", lambda d: bare)
    read = _readers()[name]
    assert read(_run(trace)) is None
    assert read(_run(None)) is None
    monkeypatch.undo()
    assert read(_run(trace)) is None      # no .bench_trace in the checkout


def test_benchmark_names_each_new_reader():
    cells = {m["name"]: m for m in harness.benchmark(ROOT)["per_layer"]}
    for name in NEW:
        assert name in cells
        assert name in harness.metric_readers(ROOT)


def test_profiled_scheduler_writes_its_spans(tmp_path):
    """A real profiler trace of the rolling scheduler on the CPU holds
    every phase span, ``inflight`` on each ``sched.advance``, and the
    request spans, each with its request's ``seq``."""
    from repro.core import SamplerConfig
    from repro.launch.serve import ServingEngine
    from repro.launch.sharded_parity import toy_ensemble
    from repro.serving import ContinuousScheduler

    experts, params, router_fn, _ = toy_ensemble(4)
    eng = ServingEngine(experts=experts, expert_params=params,
                        router_fn=router_fn, latent_shape=(4, 4, 2),
                        sampler=SamplerConfig(num_steps=3, cfg_scale=3.0,
                                              strategy="topk", top_k=2))
    sched = ContinuousScheduler(eng, max_resident=2)
    key = jax.random.PRNGKey(1)
    warm = sched.submit(key, jnp.ones((1, 5, 6)))
    sched.run_until_idle()
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [sched.submit(jax.random.fold_in(key, i), jnp.ones((1, 5, 6)))
                for i in range(2)]
        sched.run_until_idle()
        jax.block_until_ready([r.result() for r in reqs])
    finally:
        jax.profiler.stop_trace()
    assert warm.done and all(r.done for r in reqs)
    assert glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    pt = P.read_xspace(str(tmp_path))
    names = {n for n, _, _, _ in pt.spans}
    assert {"sched.admit", "sched.advance", "sched.collect",
            "sched.publish", "request.submit", "request.admit",
            "request.resolve"} <= names
    seqs = {r.seq for r in reqs}
    for kind in ("request.submit", "request.admit", "request.resolve"):
        assert {a.get("seq") for n, _, _, a in pt.spans if n == kind} \
            == seqs, kind
    advances = [a for n, _, _, a in pt.spans if n == "sched.advance"]
    assert advances and all("inflight" in a for a in advances)
