"""The program's own spans and device scopes, read from a profiler trace.

The served program opens host spans (``jax.profiler.TraceAnnotation``)
around the phases of a scheduler tick and of ``generate`` (``SPANS``),
and names parts of its compiled step with ``jax.named_scope``
(``SCOPES``).  A span lands in the trace's host plane on the clock of the
device operations; a scope reaches each device operation's event as its
name stack (the ``tf_op`` stat of the event's metadata, the instruction's
``op_name``).  A fusion carries the name stack of its root instruction.

``bench/trace.py`` reads the operations and the benchmark's own spans;
this module reads the same ``.xplane.pb`` again for what it leaves out,
for the per-layer metrics that need it.  A trace of a program that opens
no such span and names no such scope yields no spans and no scoped
operation, and those metrics then read nothing.

    python3 bench/program_trace.py <trace dir>

prints what a kept trace holds: span means, scope times, device steps
and the longest idle gaps, named by benchmark and program span.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as trace_mod  # noqa: E402

#: host spans the program opens: a scheduler tick's phases, ``generate``'s
#: two halves, and one request's submission, admission and resolution
SPANS = ("sched.admit", "sched.advance", "sched.collect", "sched.publish",
         "engine.prepare", "engine.dispatch",
         "request.submit", "request.admit", "request.resolve")

#: device scopes the program names in its compiled step
SCOPES = ("router", "attention", "layer_weights", "fused_step")

#: the stat of a device operation's event metadata that holds its name
#: stack (the instruction's ``op_name``)
NAME_STACK = "tf_op"

#: where ``bench/run.py`` has the profiler write, inside the checkout
TRACE_DIR = ".bench_trace"


@dataclasses.dataclass
class ProgramTrace:
    #: the program's host spans ``(name, start_ns, end_ns, args)``
    spans: list[tuple[str, float, float, dict]]
    #: per chip, the device operations ``(name, start_ns, end_ns, scope)``
    #: in the order of ``bench/trace.py``'s reading; ``scope`` is the
    #: outermost of ``SCOPES`` in the name stack, or ``None``
    ops: list[list[tuple[str, float, float, str | None]]]


def scope_of(name_stack: str) -> str | None:
    """The outermost of ``SCOPES`` among the parts of a name stack
    (``jit(_step)/jit(main)/router/while/body/attention/dot_general`` ->
    ``router``): the router's own attention counts as router time."""
    for part in name_stack.split("/"):
        if part in SCOPES:
            return part
    return None


def _varint(b: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        c = b[i]
        i += 1
        value |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return value, i


def _fields(b: bytes, i: int, end: int):
    """``(field, value)`` of a protobuf message in ``b[i:end]``; a
    length-delimited value is its ``(start, end)``, left unread."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def name_stacks(data: bytes) -> dict[str, str]:
    """Operation name -> name stack, from the event metadata of each
    device plane of a serialized ``XSpace`` (``tsl/profiler/protobuf/
    xplane.proto``: ``XSpace.planes`` 1; ``XPlane.name`` 2,
    ``event_metadata`` 4, ``stat_metadata`` 5; ``XEventMetadata.name`` 2,
    ``stats`` 5; ``XStat.metadata_id`` 1, ``str_value`` 5).  The profiler's
    own reader does not expose metadata stats; a plane's lines are skipped
    unread."""
    def text(span):
        return data[span[0]:span[1]].decode("utf-8", "replace")

    out: dict[str, str] = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, stat_names, metas = "", {}, []
        for pf, v in _fields(data, *plane):
            if pf == 2:
                name = text(v)
            elif pf in (4, 5):          # map entries: key 1, value 2
                for mf, mv in _fields(data, *v):
                    if mf != 2:
                        continue
                    if pf == 4:
                        metas.append(mv)
                    else:
                        d = dict(_fields(data, *mv))
                        if 2 in d:
                            stat_names[d.get(1, 0)] = text(d[2])
        if not name.startswith("/device:"):
            continue
        ids = {k for k, n in stat_names.items() if n == NAME_STACK}
        for mv in metas:
            op, stack = None, None
            for xf, xv in _fields(data, *mv):
                if xf == 2:
                    op = text(xv)
                elif xf == 5:
                    d = dict(_fields(data, *xv))
                    if d.get(1, 0) in ids and 5 in d:
                        stack = text(d[5])
            if op is not None and stack and op not in out:
                out[op] = stack
    return out


def read_xspace(trace_dir: str) -> ProgramTrace:
    """The program spans and scoped operations of the trace under
    ``trace_dir``, read once per file and kept."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    st = os.stat(paths[0])
    key = (paths[0], st.st_mtime_ns, st.st_size)
    if key in _CACHE:
        return _CACHE[key]
    with open(paths[0], "rb") as f:
        raw = f.read()
    stacks = name_stacks(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            chip = [(e.name, e.start_ns, e.end_ns,
                     scope_of(stacks.get(e.name, "")))
                    for line in plane.lines
                    if line.name == trace_mod.OPS_LINE
                    for e in line.events]
            if chip:
                ops.append(chip)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                      for line in plane.lines for e in line.events
                      if e.name in SPANS]
    _CACHE.clear()
    _CACHE[key] = ProgramTrace(spans=spans, ops=ops)
    return _CACHE[key]


_CACHE: dict = {}


def of_run(run, reader_file: str):
    """``(program trace, window)`` of a traced run, for the metric file
    at ``reader_file`` (under ``<checkout>/bench/metrics/``); ``None``
    where the run holds no trace."""
    if run.trace is None:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))
    try:
        pt = read_xspace(os.path.join(root, TRACE_DIR))
    except (FileNotFoundError, ValueError):
        return None
    return pt, trace_mod.window(run.trace)


def scope_ms_per_step(run, reader_file: str, scope: str) -> float | None:
    """Own device milliseconds under ``scope`` a sampler step traced, for
    the metric file at ``reader_file``; ``None`` where there is none."""
    got = of_run(run, reader_file)
    if got is None or not run.steps_traced:
        return None
    pt, (lo, hi) = got
    ns = scope_ns(pt, scope, lo, hi)
    return None if ns is None else 1e-6 * ns / run.steps_traced


def span_durations_ns(pt: ProgramTrace, name: str, lo: float,
                      hi: float) -> list[float]:
    """Durations of the spans ``name`` that lie inside ``[lo, hi]``."""
    return [e - s for n, s, e, _ in pt.spans
            if n == name and s >= lo and e <= hi]


def span_args(pt: ProgramTrace, name: str, arg: str, lo: float,
              hi: float) -> list:
    """The argument ``arg`` of the spans ``name`` inside ``[lo, hi]``."""
    return [a[arg] for n, s, e, a in pt.spans
            if n == name and s >= lo and e <= hi and arg in a]


def scope_ns(pt: ProgramTrace, scope: str, lo: float,
             hi: float) -> float | None:
    """Own device time of the operations under ``scope`` inside ``[lo,
    hi]`` (nested operations counted once), averaged over chips; ``None``
    where no operation there carries the scope."""
    total, seen = 0.0, False
    for ops in pt.ops:
        own = trace_mod.self_ns([(n, s, e) for n, s, e, _ in ops])
        for (_, s, e, sc), t in zip(ops, own):
            if sc == scope and s >= lo and e <= hi:
                total += t
                seen = True
    return total / len(pt.ops) if seen else None


def device_steps(pt: ProgramTrace, lo: float, hi: float) -> float:
    """Euler steps the device ran inside ``[lo, hi]``, averaged over
    chips: launches of the fused-step kernel (the custom call under
    ``fused_step``, one per step)."""
    count = sum(1 for ops in pt.ops for n, s, e, sc in ops
                if sc == "fused_step" and s >= lo and e <= hi
                and " custom-call(" in n)
    return count / max(len(pt.ops), 1)


def idle_gaps(trace: trace_mod.Trace, pt: ProgramTrace,
              top: int = 10) -> list[list]:
    """``bench/trace.py``'s idle gaps of chip 0, each named by the
    benchmark span and the innermost program span that cover its midpoint
    (``step/sched.advance``; the benchmark span alone where no program
    span covers it), as ``[name, seconds]``."""
    lo, hi = trace_mod.window(trace)
    busy = trace_mod._union([(s, e) for _, s, e in trace.devices[0]], lo, hi)
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
        outer = [n for n, a, b in trace.spans if a <= mid <= b]
        inner = [(a, n) for n, a, b, _ in pt.spans if a <= mid <= b]
        name = outer[-1] if outer else "none"
        if inner:
            name += "/" + max(inner)[1]
        out.append([name, (e - s) * 1e-9])
    return out


def describe(trace_dir: str) -> dict:
    """What a kept trace holds, over ``bench/trace.py``'s window."""
    tr = trace_mod.read_xspace(trace_dir)
    pt = read_xspace(trace_dir)
    lo, hi = trace_mod.window(tr)
    spans = collections.defaultdict(list)
    for name, s, e, _ in pt.spans:
        if s >= lo and e <= hi:
            spans[name].append((e - s) * 1e-6)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": trace_mod.summary(tr)["busy_s"],
        "span_ms": {n: {"count": len(v), "mean": sum(v) / len(v)}
                    for n, v in sorted(spans.items())},
        "inflight": span_args(pt, "sched.advance", "inflight", lo, hi),
        "scope_s": {sc: (scope_ns(pt, sc, lo, hi) or 0.0) * 1e-9
                    for sc in SCOPES},
        "device_steps": device_steps(pt, lo, hi),
        "idle_gaps": idle_gaps(tr, pt),
    }


if __name__ == "__main__":
    print(json.dumps(describe(sys.argv[1]), indent=1))
