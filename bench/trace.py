"""Reduction of a profiler trace to device busy and idle time, kernel time
by name, and the breakdown that the result line carries.

A trace is read into two lists on one clock (nanoseconds): the device
operations of each chip (``name, start, end``), and the benchmark's own
host spans (``TraceAnnotation``s named in ``SPANS``).  The traced window
runs from the start of the first host span to the end of the last, so it
covers whole calls or ticks.  Busy time is the union of the operation
intervals inside the window, averaged over chips; an idle gap is a stretch
of the window in which no operation runs, named by the host span that
covers its midpoint (``"none"`` where none does).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

#: host spans the benchmark records around its calls into the program
SPANS = ("submit", "step", "fetch", "generate", "prepare")


@dataclasses.dataclass
class Trace:
    #: per chip, the device operations ``(name, start_ns, end_ns)``
    devices: list[list[tuple[str, float, float]]]
    #: the benchmark's host spans ``(name, start_ns, end_ns)``
    spans: list[tuple[str, float, float]]


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(trace: Trace) -> tuple[float, float]:
    if not trace.spans:
        raise ValueError("the trace holds no benchmark span")
    return (min(s for _, s, _ in trace.spans),
            max(e for _, _, e in trace.spans))


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in _union([(s, e) for _, s, e in ops], lo, hi))


def op_name(event_name: str) -> str:
    """The instruction name of a trace event: the device plane names an
    operation by its HLO text (``%ragged_gemm.12 = f32[...] custom-call(
    ...)``); the name is what precedes `` = ``."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def _base(name: str) -> str:
    """An operation's kernel: its instruction name without the instance
    number (``%ragged_gemm.12 = ...`` -> ``ragged_gemm``)."""
    return re.sub(r"\.\d+$", "", op_name(name))


def self_ns(ops) -> list[float]:
    """Each operation's own time: its duration less that of the
    operations nested in it (a ``while`` holds its body's operations)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: list[int] = []
    for i in order:
        _, s, e = ops[i]
        while stack and (ops[stack[-1]][2] <= s or ops[stack[-1]][2] < e):
            stack.pop()             # ended, or overlaps without nesting
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def kernel_ns(trace: Trace, kernel: str) -> tuple[float, int]:
    """Summed device time and count of ``kernel``'s operations in the
    window, averaged over chips."""
    lo, hi = window(trace)
    total = count = 0.0
    for ops in trace.devices:
        for name, s, e in ops:
            if _base(name) == kernel and s >= lo and e <= hi:
                total += e - s
                count += 1
    n = max(len(trace.devices), 1)
    return total / n, int(round(count / n))


def idle_gaps(trace: Trace, top: int = 10) -> list[list]:
    """The ``top`` longest stretches of the window in which chip 0 runs
    nothing, as ``[host span, seconds]``."""
    lo, hi = window(trace)
    busy = _union([(s, e) for _, s, e in trace.devices[0]], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        covering = [n for n, a, b in trace.spans if a <= mid <= b]
        out.append([covering[-1] if covering else "none", (e - s) * 1e-9])
    return out


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    """The ``top`` device operations of chip 0 by summed own time in the
    window (nested operations not counted twice), grouped by kernel name,
    as ``[name, seconds]``."""
    lo, hi = window(trace)
    ops = trace.devices[0]
    acc: dict[str, float] = collections.defaultdict(float)
    for (name, s, e), own in zip(ops, self_ns(ops)):
        if s >= lo and e <= hi:
            acc[_base(name)] += own
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in ranked]


def summary(trace: Trace) -> dict:
    """``busy_s`` (averaged over chips), ``window_s`` and the breakdown."""
    lo, hi = window(trace)
    busy = sum(busy_ns(ops, lo, hi) for ops in trace.devices)
    return {
        "busy_s": busy / max(len(trace.devices), 1) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "breakdown": {"device_ops": top_ops(trace),
                      "idle_gaps": idle_gaps(trace)},
    }


#: trace lines of a TPU device plane that hold one event per operation
OPS_LINE = "XLA Ops"


def read_xspace(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.name, e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns)
                      for line in plane.lines for e in line.events
                      if e.name in SPANS]
    if not devices:
        raise ValueError(f"{paths[0]}: no device operations in the trace")
    return Trace(devices=devices, spans=spans)
