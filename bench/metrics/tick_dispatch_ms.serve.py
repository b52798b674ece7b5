"""Mean host time of the program's ``sched.advance`` span in the traced
window: per bucket, the sampler's arguments and the call of the compiled
tick, including any time the runtime blocks that call."""

from bench import program_trace


def read(run):
    got = program_trace.of_run(run, __file__)
    if got is None:
        return None
    pt, (lo, hi) = got
    spans = program_trace.span_durations_ns(pt, "sched.advance", lo, hi)
    if not spans:
        return None
    return 1e-6 * sum(spans) / len(spans)
