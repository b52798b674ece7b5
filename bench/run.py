#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip it is started on.

    python3 bench/run.py --workload b2-batch --seed 7 --seconds 30 --trace 0

Reads ``BENCHMARK.json``, builds the cell's configuration with weights
made on the device from ``--seed``, warms up every program the cell's
traffic uses (counted in ``setup_s``), drives the traffic for
``--seconds``, and then checks a sample of what the window served against
the plain reference (``bench/reference.py``).  With ``--trace 0`` the
result line carries the cell's end-to-end metrics; with ``--trace 1`` it
carries the per-layer metrics, read from host spans and a profiler trace
of the end of the window.  The last line of standard output is the JSON
result; the numbers compared for ``correct`` are the last lines of
standard error.  With no accelerator, or fewer chips than the cell asks
for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, harness, traffic  # noqa: E402
from bench.metrics_util import percentile  # noqa: E402
from bench.peaks import peaks  # noqa: E402

#: compile-cache and trace directories, fixed inside the checkout
CACHE = ".jax_cache"
TRACES = ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations (cache loads excluded) while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == self.EVENT:
            self.count += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- closed traffic: back-to-back generate() calls ---------------------------

def drive_closed(engine, cfg, mix, seed, seconds, trace_dir, counter):
    """Drive a closed mix; with ``trace_dir``, profile the window's end."""
    import jax
    import numpy as np

    trace = trace_dir is not None

    n = mix["batch"]
    lt, dt = cfg["text_len"], cfg["text_dim"]

    def call(i, stream=traffic.TIMED):
        with _span("prepare"):
            key, text = traffic.request(seed, i, n, lt, dt, stream)
        with _span("generate"):
            out = engine.generate(key, text, n)
        with _span("fetch"):
            return np.asarray(out)

    for i in range(mix["warmup_calls"]):
        call(i, traffic.WARMUP)
    traces_before = engine.stats["traces"]
    outputs, ends = [], []
    trace_from = None
    counter.on = True
    start = time.perf_counter()
    i = 0
    while not ends or ends[-1] - start < seconds:
        if trace and trace_from is None and \
                time.perf_counter() - start >= seconds - mix["trace_s"]:
            jax.profiler.start_trace(trace_dir)
            trace_from = i
        outputs.append(call(i))
        ends.append(time.perf_counter())
        i += 1
    counter.on = False
    if trace:
        if trace_from is None:          # window shorter than trace_s
            jax.profiler.start_trace(trace_dir)
            trace_from = i
            outputs.append(call(i))
            ends.append(time.perf_counter())
            i += 1
        jax.profiler.stop_trace()
    return {
        "start": start, "calls": i, "images": i * n, "ends": ends,
        "outputs": outputs, "traces": (traces_before, engine.stats["traces"]),
        "steps_traced": (i - trace_from) * cfg["sampler"]["num_steps"]
        if trace else 0,
    }


# -- open traffic: Poisson arrivals into the rolling scheduler ---------------

def drive_open(engine, cfg, mix, seed, seconds, trace_dir, counter):
    """Drive an open mix; with ``trace_dir``, profile the window's end."""
    import jax
    import numpy as np

    trace = trace_dir is not None

    from bench import system

    sched = system.scheduler(engine, mix)
    refused = system.backpressure_error()
    n = mix["images_per_request"]
    lt, dt = cfg["text_len"], cfg["text_dim"]
    warm, drain = mix["warmup_s"], mix["drain_s"]
    due = traffic.arrivals(mix, seed, warm, seconds)

    # Prime every program the loop runs (admission scatter, noise, the
    # rolling step) with one request before the arrival clock starts.
    key, text = traffic.request(seed, 0, n, lt, dt, traffic.WARMUP)
    prime = sched.submit(key, text)
    sched.step()
    traces_before = engine.stats["traces"]

    origin = time.perf_counter()
    w_lo, w_hi = origin + warm, origin + warm + seconds
    recs = {}               # i -> dict(due, submit, done, state)
    live = []               # (i, handle), submitted and not yet resolved
    ticks, trace_state, steps_traced = [], 0, 0
    host_until = float("inf")     # host metrics stop where the profiler starts
    i = 0
    while True:
        now = time.perf_counter()
        counter.on = trace_state == 0 and w_lo <= now < w_hi
        while i < len(due) and origin + due[i] <= now \
                and origin + due[i] < w_hi:
            with _span("prepare"):
                key, text = traffic.request(seed, i, n, lt, dt)
            rec = recs[i] = {"due": origin + due[i], "state": "QUEUED"}
            with _span("submit"):
                try:
                    live.append((i, sched.submit(key, text)))
                except refused:
                    rec["state"] = "REFUSED"
            rec["submit"] = time.perf_counter()
            i += 1
        if sched.queue_depth or sched.num_resident:
            if trace and trace_state == 0 and now >= w_hi - mix["trace_s"]:
                counter.on = False
                host_until = time.perf_counter()
                jax.profiler.start_trace(trace_dir)
                trace_state = 1
            t = time.perf_counter()
            with _span("step"):
                sched.step()
            if trace_state == 1:
                steps_traced += sched.steps_per_tick
            elif w_lo <= t < w_hi:
                ticks.append(time.perf_counter() - t)
            still = []
            for j, h in live:
                if h.done:
                    with _span("fetch"):
                        out = np.asarray(h.result())
                    if j in recs:
                        recs[j].update(done=time.perf_counter(),
                                       state="DONE", out=out)
                elif h.state == "FAILED":
                    recs[j]["state"] = "FAILED"
                else:
                    still.append((j, h))
            live = still
            if trace_state == 1 and time.perf_counter() >= w_hi:
                jax.profiler.stop_trace()
                trace_state = 2
        else:
            if i >= len(due) or origin + due[i] >= w_hi:
                break
            time.sleep(max(0.0, min(origin + due[i] - now, 0.01)))
        if now >= w_hi + drain:
            break
    if trace_state == 1:
        jax.profiler.stop_trace()
    counter.on = False
    del prime
    window = {j: r for j, r in recs.items() if w_lo <= r["due"] < w_hi}
    done = [r for r in window.values() if r["state"] == "DONE"]
    return {
        "start": w_lo, "window": window, "done": done,
        "latencies": [r["done"] - r["due"] for r in done],
        "submit_lag": [r["submit"] - r["due"] for r in window.values()
                       if r["submit"] < host_until],
        "ticks": ticks, "steps_traced": steps_traced,
        "traces": (traces_before, engine.stats["traces"]),
    }


# -- the run ------------------------------------------------------------------

def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, require_chip: bool = True, cache: bool = True) -> dict:
    """One run of ``workload`` from the checkout at ``root``; returns the
    result line (a dict).  Tests pass ``require_chip=False`` to drive a
    run on the CPU, and ``cache=False`` to leave JAX's compilation cache
    off."""
    import jax

    cell = harness.cell(root, workload)
    cfg, mix = cell["config"], cell["traffic"]
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        chips = cell["workload"]["chips"]
        if dev.platform == "cpu" or len(devices) < chips:
            raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                         f"found {len(devices)} {dev.platform} device(s)")
        chip_peaks = peaks(dev.device_kind)
    else:
        chip_peaks = None

    from bench import system

    if cache:
        system.enable_compile_cache(os.path.join(root, CACHE))
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    counter = CompileCounter()
    trace_dir = os.path.join(root, TRACES) if trace else None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    engine = system.build_engine(cfg, seed)
    drive = drive_open if mix["kind"] == "open" else drive_closed
    try:
        got = drive(engine, cfg, mix, seed, seconds, trace_dir, counter)
    finally:
        counter.close()
    setup_s = got["start"] - T_START
    log(f"setup_s {setup_s:.3f}; engine traces before/after the window "
        f"{got['traces'][0]}/{got['traces'][1]}; XLA compiles in the window "
        f"{counter.count}")
    peak_each = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices[:cell["workload"]["chips"]]]
    del engine
    gc.collect()

    if mix["kind"] == "open":
        attempted = len(got["window"])
        failed = attempted - len(got["done"])
        pool = [(j, r) for j, r in sorted(got["window"].items())
                if r["state"] == "DONE"]
    else:
        attempted, failed = got["calls"], 0
        pool = list(enumerate(got["outputs"]))
    t_check = time.perf_counter()
    checks = check.check(cfg, mix, seed, pool, system.expert_devices(cfg))
    log(f"check_s {time.perf_counter() - t_check:.3f}")

    result = {
        "correct": checks["correct"],
        "attempted": attempted,
        "failed": failed,
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": max(peak_each),
                   "memory_peak_bytes_per_device": peak_each},
    }
    if trace:
        from bench import trace as trace_mod
        from bench.metrics_util import Run

        tr = trace_mod.read_xspace(trace_dir)
        summ = trace_mod.summary(tr)
        result["device"]["busy_s"] = summ["busy_s"]
        result["device"]["window_s"] = summ["window_s"]
        ctx = Run(config=cfg, traffic=mix, peaks=chip_peaks, got=got,
                  trace=tr, summary=summ)
        for metric in cell["per_layer"]:
            value = harness.load_reader(metric["path"])(ctx)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        result["breakdown"] = summ["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = {"setup_s": setup_s}
        if mix["kind"] == "open":
            lat = got["latencies"]
            e2e["latency_p50_s"] = percentile(lat, 50)
            log(f"{len(lat)} of {attempted} requests due in the window "
                f"completed")
        else:
            e2e["img_per_s"] = got["images"] / (got["ends"][-1]
                                                - got["start"])
            log(f"{got['calls']} calls of {mix['batch']} images in "
                f"{got['ends'][-1] - got['start']:.3f} s")
        for metric in cell["end_to_end"]:
            value = e2e.get(metric["name"])
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    result["checks"] = checks["numbers"]
    for name, c in checks["numbers"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
