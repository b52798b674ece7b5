"""Mean number of earlier ticks the device had not finished when the
scheduler dispatched the next: the ``inflight`` argument of the program's
``sched.advance`` spans in the traced window (the scheduler's counter,
``engine.stats["ticks_in_flight"]``)."""

from bench import program_trace


def read(run):
    got = program_trace.of_run(run, __file__)
    if got is None:
        return None
    pt, (lo, hi) = got
    counts = program_trace.span_args(pt, "sched.advance", "inflight", lo, hi)
    if not counts:
        return None
    return sum(counts) / len(counts)
